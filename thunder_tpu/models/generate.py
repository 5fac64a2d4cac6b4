"""Autoregressive inference with a KV cache (BASELINE milestone E).

The reference has no in-tree generation loop (models come from LitGPT, which
brings its own `generate`); milestone E requires MoE inference with the
quantized path.  The TPU-native design:

- **prefill**: one forward over the prompt writes K/V for every position into
  a preallocated ``(L, B, ng, T_max, hs)`` cache — static shapes, one XLA
  program;
- **decode**: the whole new-token loop is ONE compiled program — a
  ``lax.scan`` whose body runs a single-token forward against the cache,
  updates it in place with ``dynamic_update_slice`` (XLA aliases the buffer;
  no reallocation), and samples the next token.  No per-token dispatch or
  retracing, which is where naive eager decode loops lose on TPU;
- causality is positional: a query at global position ``p`` attends to cache
  slots ``<= p``, so no (T, T) mask is ever materialized;
- ``quantized=True`` routes every weight matmul through the int8 executor's
  kernels (``executors/quantex.int8_linear``: dynamic per-token/per-channel
  scales, int32 MXU accumulation) — the TransformerEngine-analog inference
  path.

Math mirrors ``models/llama`` (same param pytree, configs, GQA, partial
rotary, RMSNorm/LayerNorm, LLaMAMLP/GptNeoxMLP/LLaMAMoE); written in plain
jnp because the decode step lives inside ``lax.scan``.
"""
from __future__ import annotations

import math
from functools import partial
from typing import Any

import jax
import jax.numpy as jnp

from thunder_tpu.models.llama import Config, build_rope_cache
from thunder_tpu.observability.events import scope

__all__ = [
    "init_cache",
    "forward_with_cache",
    "generate",
    "cache_len",
    "cache_shape",
    "state_shapes",
    "gdn_mixer",
    "shortconv_mixer",
    "ssm_mixer",
    "mamba2_mixer",
    "gmu_mixer",
    "diff_attention",
    "ring_blocks",
    "ring_block_shape",
    "mla_mixer",
    "mla_latent",
    "hc_maps",
    "hc_open",
    "hc_close",
    "hc_step",
    "moe_share_mlp",
    "moe_row_tile",
    "route_sigmoid_group",
    "route_sigmoid_bias",
    "kv_block_shape",
    "kv_lane_pack",
    "ring_slot",
    "ring_gather_positions",
    "sample_token",
]


def _linear(x, w, b=None, *, quantized=False):
    if quantized:
        from thunder_tpu.executors.quantex import int8_linear

        out = int8_linear(x, w)
    else:
        out = x @ w.T
    return out if b is None else out + b


def _norm(x, w, cfg: Config, b=None):
    xf = x.astype(jnp.float32)
    if cfg.norm_class == "RMSNorm":
        ms = jnp.mean(xf * xf, axis=-1, keepdims=True)
        xf = xf * jax.lax.rsqrt(ms + cfg.norm_eps)
    else:
        mu = jnp.mean(xf, axis=-1, keepdims=True)
        var = jnp.var(xf, axis=-1, keepdims=True)
        xf = (xf - mu) * jax.lax.rsqrt(var + cfg.norm_eps)
    out = xf * w.astype(jnp.float32)
    if b is not None:
        out = out + b.astype(jnp.float32)
    return out.astype(x.dtype)


def _rms(x, w, eps):
    """RMSNorm over the last axis in float32, back in ``x``'s dtype."""
    xf = x.astype(jnp.float32)
    xf = xf * jax.lax.rsqrt(jnp.mean(xf * xf, axis=-1, keepdims=True) + eps)
    return (xf * w.astype(jnp.float32)).astype(x.dtype)


def _rope(x, cos, sin):
    # x: (B, h, T, n_elem); cos/sin: (T, n_elem) for the global positions
    half = x.shape[-1] // 2
    rotated = jnp.concatenate([-x[..., half:], x[..., :half]], axis=-1)
    return (x * cos + rotated * sin).astype(x.dtype)


def _mlp(mp, x, cfg: Config, *, quantized=False, lora=None, lora_scaling=1.0, moe_rows=None, route_x=None):
    lin = partial(_linear, quantized=quantized)
    if cfg.mlp_class == "LLaMAMoE":
        # stacked per-expert weights: per-request LoRA deltas are not
        # supported here (AdapterRegistry rejects MoE MLP targets)
        E, k = cfg.n_expert, cfg.n_expert_per_token
        with scope("router"):
            router = x.astype(jnp.float32) @ mp["gate"].T.astype(jnp.float32)
            top_logits, top_idx = jax.lax.top_k(router, k)
            probs = jax.nn.softmax(top_logits, axis=-1)
        y = None
        with scope("experts"):
            for e in range(E):
                w_e = jnp.sum(probs * (top_idx == e).astype(jnp.float32), axis=-1)
                xe = lin(jax.nn.silu(lin(x, mp["fc_1"][e])) * lin(x, mp["fc_2"][e]), mp["proj"][e])
                contrib = xe * w_e[..., None].astype(x.dtype)
                y = contrib if y is None else y + contrib
        return y

    kind = cfg.mlp_class
    if kind == "SparseMoE":
        if "gate" in mp:
            return moe_share_mlp(mp, x, cfg, lin=lin, moe_rows=moe_rows, route_x=route_x)
        kind = "LLaMAMLP"       # one of the model's leading dense layers

    def ll(name, inp, bias=None):
        # one targeted matmul: the per-request LoRA delta rides on the
        # matmul INPUT (same placement rule as _project_qkv / wo)
        o = lin(inp, mp[name], mp.get(bias) if bias else None)
        if lora is not None and name in lora:
            o = o + _lora_delta(inp, *lora[name], lora_scaling)
        return o

    with scope("up"):
        if kind == "LLaMAMLP":
            h = jax.nn.silu(ll("fc_1", x, "fc_1_b")) * ll("fc_2", x, "fc_2_b")
        elif kind == "GemmaMLP":
            h = (jax.nn.gelu(ll("fc_1", x, "fc_1_b"), approximate=cfg.gelu_approximate == "tanh")
                 * ll("fc_2", x, "fc_2_b"))
        else:
            h = jax.nn.gelu(ll("fc", x, "fc_b"), approximate=cfg.gelu_approximate == "tanh")
    with scope("down"):
        return ll("proj", h, "proj_b")


def route_sigmoid_group(scores, cfg: Config):
    """The group-limited choice (DeepSeek-V3 without its score-correction
    bias) from float32 ``scores (N, E)`` in (0, 1): the experts in ``n_group``
    groups, a group's score the sum of its two best, the best ``topk_group``
    groups kept, the top ``n_expert_per_token`` of their experts; the chosen
    scores renormalised to sum one and scaled by ``routed_scaling_factor``.
    Returns ``(top_w, top_idx)``, both ``(N, k)``."""
    N, E = scores.shape
    G = cfg.n_group
    grouped = scores.reshape(N, G, E // G)
    group_score = jnp.sum(jax.lax.top_k(grouped, 2)[0], axis=-1)                       # (N, G)
    _, best = jax.lax.top_k(group_score, cfg.topk_group)
    kept = jnp.any(best[:, :, None] == jnp.arange(G)[None, None, :], axis=1)           # (N, G)
    masked = jnp.where(kept[:, :, None], grouped, 0.0).reshape(N, E)
    _, top_idx = jax.lax.top_k(masked, cfg.n_expert_per_token)
    top_w = jnp.take_along_axis(scores, top_idx, axis=-1)
    top_w = top_w / (jnp.sum(top_w, axis=-1, keepdims=True) + 1e-20) * cfg.routed_scaling_factor
    return top_w, top_idx


def route_sigmoid_bias(scores, bias, cfg: Config):
    """The biased choice (LFM2; DeepSeek-V3's score correction without its
    groups) from float32 ``scores (N, E)`` in (0, 1) and ``bias (E,)``: the top
    ``n_expert_per_token`` of ``scores + bias``; the weights are the chosen
    *scores*, without the bias, over their sum plus 1e-6 (hf's
    ``norm_topk_prob``; LFM2's epsilon, where the DeepSeek-V3 family's code and
    Nemotron-H's add 1e-20: a departure of under one part in a million of a
    weight at two to 22 scores in (0, 1), noted in those configurations'
    ``assumed``), scaled by ``routed_scaling_factor``.  The bias moves the
    choice and never a weight.  Returns ``(top_w, top_idx)``, both ``(N, k)``."""
    _, top_idx = jax.lax.top_k(scores + bias.astype(jnp.float32), cfg.n_expert_per_token)
    top_w = jnp.take_along_axis(scores, top_idx, axis=-1)
    top_w = top_w / (jnp.sum(top_w, axis=-1, keepdims=True) + 1e-6) * cfg.routed_scaling_factor
    return top_w, top_idx


def route_softmax(logits, cfg: Config):
    """The softmax choice (the trainer's, ``llama.sparse_moe_mlp``; Qwen3-MoE,
    SmallThinker) from float32 router ``logits (N, E)``: a softmax over *all*
    ``n_expert``, the top ``n_expert_per_token`` of the probabilities, renormalised
    to sum one (hf's ``norm_topk_prob``; no epsilon: the chosen probabilities are
    the largest of a softmax, never all zero).  Returns ``(top_w, top_idx)``, both
    ``(N, k)``."""
    top_w, top_idx = jax.lax.top_k(jax.nn.softmax(logits, axis=-1), cfg.n_expert_per_token)
    return top_w / jnp.sum(top_w, axis=-1, keepdims=True), top_idx


MOE_DECODE_ROW_TILE = 16   # bfloat16's sublane tile: the fewest rows moe_grouped_mm compiles for


def moe_row_tile(rows_an_expert: float) -> int:
    """Rows of a tile of the served expert share's sorted rows, from the rows an
    even routing sends a held expert.  ``moe_grouped_mm`` keeps an expert's
    weight block in VMEM while its row tiles pass, so a second tile costs no
    bytes; it costs the matrix unit a second load of every weight tile, which
    at a few rows a tile is all of a tile's time.  So a tile holds about twice
    an expert's even share (a power of two, so that an expert that drew a few
    rows more still fits one), no fewer than ``MOE_DECODE_ROW_TILE`` and no
    more than the trainer's ``MOE_ROW_TILE``, which a prompt's rows fill
    several times over.  (The cell that showed it, 32 rows an expert, before
    the block stayed: tiles of 16 read every expert's weights 2.5 times a
    step, 0.37 of the product's roofline; ``PERF.md`` section 6, PRs 39, 40.)"""
    from thunder_tpu.core.prims import MOE_ROW_TILE

    if rows_an_expert >= MOE_ROW_TILE // 2:
        return MOE_ROW_TILE
    return min(MOE_ROW_TILE, max(MOE_DECODE_ROW_TILE, 1 << math.ceil(math.log2(max(2 * rows_an_expert, 1)))))


def moe_share_mlp(mp, x, cfg: Config, *, lin=_linear, moe_rows=None, route_x=None):
    """A SparseMoE layer in the server, on ``x (B, T, C)``: the router scores
    *all* ``n_expert`` in float32 (:func:`route_softmax`, :func:`route_sigmoid_group`,
    or :func:`route_sigmoid_bias` on ``mp["expert_bias"]``), the layer
    holds experts ``[expert_first, expert_first + expert_held)`` and computes
    their part (``jaxex._moe_share``, the trainer's forward: the step's rows
    sorted by held expert into whole row tiles, grouped products through
    ``moe_grouped_mm``, each token gathering its ``k`` results back by where
    they landed and summing them in float32, nothing dropped); what the other
    experts would add is left out, and the shared expert is added once.  A
    decode step routes a few rows an expert, so its tiles are narrow
    (:func:`moe_row_tile`); an expert's weights are fetched once a product
    however many tiles its rows fill.

    ``cfg.moe_latent_size``: the routed experts read ``x W_down`` (one shared
    projection to the latent, made once) and their weighted sum goes through
    ``W_up`` once; the router and the shared expert read ``x`` itself.
    ``cfg.moe_activation`` "relu2": experts and shared expert are ``W2 relu(W1
    .)^2``, two matrices (``jaxex._moe_share_planned`` with no ``fc_2``; the plan, the
    sort, the gathers and the grouped products are the gated form's); "reglu":
    ``W2 (relu(W1 .) * W3 .)``, SwiGLU's three matrices under another gate.

    ``route_x (B, T, C)``: what the router reads where that is not what the experts
    read (``cfg.moe_route_block_input``: the block's input, and then required, so that
    a caller that goes round :func:`_close_block` fails by name; else None).  The
    shared expert and the latent read ``x``.

    ``moe_rows``: a list that takes this layer's ``(rows that landed on held
    experts, held experts with a row)``, int32 ``(2,)``, from the plan's own
    counts (a decode step's: ``engine.stats()["moe"]``)."""
    from thunder_tpu.executors import jaxex

    B, T, C = x.shape
    I, Eh = cfg.intermediate_size, cfg.expert_held
    Cx = cfg.moe_latent_size or C
    gated = cfg.moe_activation != "relu2"
    gate = jax.nn.relu if cfg.moe_activation == "reglu" else jax.nn.silu      # of the gated forms
    assert (route_x is not None) == cfg.moe_route_block_input, (
        "moe_route_block_input: the router reads the block's input, which only _close_block hands through")
    x2 = x.reshape(B * T, C)
    with scope("router"):
        routed = x2 if route_x is None else route_x.reshape(B * T, C)
        logits = routed.astype(jnp.float32) @ mp["gate"].T.astype(jnp.float32)
        if cfg.moe_router == "softmax":
            top_w, top_idx = route_softmax(logits, cfg)
        elif cfg.moe_router == "sigmoid_bias":
            top_w, top_idx = route_sigmoid_bias(jax.nn.sigmoid(logits), mp["expert_bias"], cfg)
        else:
            top_w, top_idx = route_sigmoid_group(jax.nn.sigmoid(logits), cfg)
    even = B * T * cfg.n_expert_per_token / cfg.n_expert      # rows an even routing sends a held expert
    xe = x2
    if cfg.moe_latent_size:
        with scope("latent_down"):
            xe = lin(x2, mp["latent_down"])
    with scope("experts"):
        y, plan = jaxex._moe_share_planned(
            xe, top_idx, top_w, mp["fc_1"].reshape(Eh, Cx, I), mp["fc_2"].reshape(Eh, Cx, I) if gated else None,
            mp["proj"].reshape(Eh, I, Cx), cfg.expert_first, cfg.n_expert, moe_row_tile(even), gate=gate)
        if moe_rows is not None:
            moe_rows.append(jnp.stack([jnp.sum(plan["cnt"]), jnp.sum(plan["cnt"] > 0, dtype=jnp.int32)]))
    if cfg.moe_latent_size:
        with scope("latent_up"):
            y = lin(y, mp["latent_up"])
    if cfg.shared_expert_size:
        with scope("shared"):
            sp = mp["shared"]
            hidden = (gate(lin(x2, sp["fc_1"])) * lin(x2, sp["fc_2"]) if gated
                      else jnp.square(jax.nn.relu(lin(x2, sp["fc_1"]))))
            shared = lin(hidden, sp["proj"])
            if cfg.shared_expert_gate:
                shared = jax.nn.sigmoid(lin(x2, sp["gate"])) * shared
            y = y + shared
    return y.reshape(B, T, C)


def pad_lanes(x, width: int):
    """``x`` with zeros after its last axis up to ``width`` (a latent row as wide as its cache's)."""
    pad = width - x.shape[-1]
    return jnp.pad(x, ((0, 0),) * (x.ndim - 1) + ((0, pad),)) if pad else x


@scope("mla/latent")
def mla_latent(ap, x, cos_t, sin_t, cfg: Config, lin=_linear):
    """What a latent-attention layer's cache holds of new tokens ``x (B, T,
    C)``: ``[c_kv | k_r] (B, T, kv_lora_rank + qk_rope_head_dim)``, the latent
    after its RMSNorm and the one rotated key all heads share."""
    dc = cfg.kv_lora_rank
    kv = lin(x, ap["wkv_a"])
    c_kv = _rms(kv[..., :dc], ap["kv_norm"], cfg.norm_eps)
    k_r = _rope(kv[..., dc:][:, None], cos_t, sin_t)[:, 0]
    return jnp.concatenate([c_kv, k_r], axis=-1)


def mla_heads(ap, cfg: Config):
    """``wkv_b`` a head: ``(W_k (nh, dn, dc), W_v (nh, dv, dc))``: the maps
    from the latent to a head's unrotated key and to its value."""
    w = ap["wkv_b"].reshape(cfg.n_head, cfg.qk_nope_head_dim + cfg.v_head_dim, cfg.kv_lora_rank)
    return w[:, :cfg.qk_nope_head_dim], w[:, cfg.qk_nope_head_dim:]


@scope("mla/expand")
def mla_expand(ap, latent, cfg: Config):
    """The expanded keys and values of cached tokens: ``latent (B, S, >= dc +
    dr)`` to ``k (B, nh, S, dn + dr)`` (a head's unrotated part, then the
    shared rotated key) and ``v (B, nh, S, dv)``."""
    dc, dr, nh = cfg.kv_lora_rank, cfg.qk_rope_head_dim, cfg.n_head
    w_k, w_v = mla_heads(ap, cfg)
    c_kv, k_r = latent[..., :dc], latent[..., dc:dc + dr]
    k_nope = jnp.einsum("bsc,hdc->bhsd", c_kv, w_k.astype(c_kv.dtype))
    v = jnp.einsum("bsc,hdc->bhsd", c_kv, w_v.astype(c_kv.dtype))
    k = jnp.concatenate([k_nope, jnp.broadcast_to(k_r[:, None], (*k_nope.shape[:3], dr))], axis=-1)
    return k, v


@scope("mla/absorb")
def mla_absorb(ap, q_nope, q_rope, cfg: Config, width: int | None = None):
    """The absorbed query: ``[q_nope W_k | q_rope] (B, nh, T, dc + dr)``, which
    scores a cached ``[c_kv | k_r]`` row as the expanded head would; padded
    with zeros to ``width`` where the cache's rows are wider."""
    w_k, _ = mla_heads(ap, cfg)
    qt = jnp.einsum("bhtd,hdc->bhtc", q_nope, w_k.astype(q_nope.dtype))
    q = jnp.concatenate([qt, q_rope], axis=-1)
    return pad_lanes(q, width or q.shape[-1])


@scope("mla/unabsorb")
def mla_unabsorb(ap, ot, cfg: Config):
    """A head's output from its weighted sum of latents: ``ot (B, nh, T, dc)``
    to ``(B, nh, T, dv)``."""
    _, w_v = mla_heads(ap, cfg)
    return jnp.einsum("bhtc,hdc->bhtd", ot, w_v.astype(ot.dtype))


@scope("attn")
def mla_attend_latents(q, latents, keep, cfg: Config):
    """Absorbed attention in XLA: ``q (B, nh, T, W)`` (:func:`mla_absorb`) over
    cached rows ``latents (B, S, W)``, ``keep`` broadcastable to ``(B, nh, T,
    S)``; float32 scores and sums.  Returns the weighted latents ``(B, nh, T,
    dc)`` at ``q``'s dtype."""
    lat = latents.astype(q.dtype)
    s = jnp.einsum("bhtw,bsw->bhts", q, lat, preferred_element_type=jnp.float32) * cfg.attn_scale
    p = jax.nn.softmax(jnp.where(keep, s, -jnp.inf), axis=-1).astype(q.dtype)
    return jnp.einsum("bhts,bsc->bhtc", p, lat[..., :cfg.kv_lora_rank])


def mla_mixer(ap, x, cos_t, sin_t, cfg: Config, attend, *, lin=_linear):
    """A latent-attention layer's mixer on new tokens ``x (B, T, C)``, for the
    dense cache and the paged server alike.  Projects the queries (through the
    rank-``q_lora_rank`` bottleneck and its norm) and the tokens' cache rows
    (:func:`mla_latent`); attention itself is
    ``attend(q_nope (B, nh, T, dn), q_rope (B, nh, T, dr), latent (B, T, dc +
    dr)) -> o (B, nh, T, dv)``: the caller's closure keeps the rows wherever
    its cache is and attends in the expanded form (:func:`mla_expand`) or the
    absorbed one (:func:`mla_absorb`, :func:`mla_unabsorb`).  Returns ``y (B,
    T, C)``."""
    B, T, _ = x.shape
    nh, dn, dv = cfg.n_head, cfg.qk_nope_head_dim, cfg.v_head_dim
    with scope("mla/q"):
        q = lin(_rms(lin(x, ap["wq_a"]), ap["q_norm"], cfg.norm_eps), ap["wq_b"])
        q = q.reshape(B, T, nh, cfg.head_size).transpose(0, 2, 1, 3)
        q_nope, q_rope = q[..., :dn], _rope(q[..., dn:], cos_t, sin_t)
    o = attend(q_nope, q_rope, mla_latent(ap, x, cos_t, sin_t, cfg, lin))
    with scope("out"):
        return lin(o.transpose(0, 2, 1, 3).reshape(B, T, nh * dv), ap["wo"])


def _mla_with_cache(ap, x, cos_t, sin_t, cl, pos, cfg: Config, *, quantized=False, sharded=False):
    """A latent-attention layer against the dense cache ``cl (B, 1, Tc, W)``, W
    at least ``cfg.latent_width`` (the paged pool's rows are padded to whole
    lane tiles): writes the new tokens' rows at ``[pos, pos + T)`` and attends.
    A whole prompt at the static position 0 attends its own expanded keys,
    through the flash kernel where that takes the shapes (q and k a head of
    ``head_size``, v and the result of ``v_head_dim``); a later piece the expanded
    keys of the whole cache; one token (T = 1) the cached rows themselves, in
    the absorbed form.  Returns ``(y, cl)``."""
    B, T, _ = x.shape
    Tc, W = cl.shape[2], cl.shape[3]
    vec = _is_vec_pos(pos)
    fresh = isinstance(pos, int) and pos == 0 and T > 1
    box = [cl]

    def attend(q_nope, q_rope, latent):
        with scope("cache"):
            row = pad_lanes(latent, W).astype(cl.dtype)[:, None]
            if vec:
                box[0] = jax.vmap(lambda c, u, p: jax.lax.dynamic_update_slice_in_dim(c, u, p, axis=1))(cl, row, pos)
            else:
                box[0] = jax.lax.dynamic_update_slice_in_dim(cl, row, pos, axis=2)
            if fresh:
                keys, keep = latent, _band_keep(T, None)
            else:
                keys = box[0][:, 0]
                qpos = ((pos[:, None] + jnp.arange(T)[None, :])[:, None, :, None] if vec
                        else (pos + jnp.arange(T))[None, None, :, None])
                keep = jnp.arange(Tc)[None, None, None, :] <= qpos
        if T == 1:
            return mla_unabsorb(ap, mla_attend_latents(mla_absorb(ap, q_nope, q_rope, cfg, W), keys, keep, cfg), cfg)
        q = jnp.concatenate([q_nope, q_rope], axis=-1)
        k, v = mla_expand(ap, keys.astype(q.dtype), cfg)
        with scope("attn"):
            if fresh and not sharded:
                from thunder_tpu.executors import pallasex

                flash = pallasex.flash_sdpa(q, k, v, None, True, cfg.attn_scale, None)
                if flash is not None:
                    return flash[0]
            s = jnp.einsum("bhqd,bhkd->bhqk", q, k, preferred_element_type=jnp.float32) * cfg.attn_scale
            w = jax.nn.softmax(jnp.where(keep, s, -jnp.inf), axis=-1).astype(q.dtype)
            return jnp.einsum("bhqk,bhkd->bhqd", w, v)

    y = mla_mixer(ap, x, cos_t, sin_t, cfg, attend, lin=partial(_linear, quantized=quantized))
    return y, box[0]


def _lora_delta(x, a, b, scaling):
    """Per-request (batched) LoRA delta: ``scaling * B(A(x))`` with one
    adapter per batch row.  ``x``: (B, T, fin); ``a``: (B, r, fin);
    ``b``: (B, fout, r) → (B, T, fout).  Row ``i``'s delta depends only on
    row ``i``'s activations and factors, so a request's math is identical
    whatever else shares the batch (the serving bit-exactness contract)."""
    d = jnp.einsum("btc,brc->btr", x, a.astype(x.dtype))
    return jnp.einsum("btr,bor->bto", d, b.astype(x.dtype)) * scaling


def _project_qkv(ap, x, cos_t, sin_t, cfg: Config, *, lin=None, lora=None,
                 lora_scaling=1.0, delta_fn=None, rope=True, gate=None):
    """QKV projections + partial rotary for new tokens: x (B, T, C) →
    q (B, nh, T, hs), k/v (B, ng, T, hs) — K/V stay at the grouped head
    count.  Shared by KV-cache decode and sequence-parallel training.
    ``lora``: optional ``{target: (a, b)}`` per-request factors for this
    layer (see :func:`_lora_delta`); ``delta_fn`` swaps the delta
    implementation (the serving kernel path passes its fused epilogue —
    same ``(x, a, b, scaling)`` contract, bit-identical math).  ``rope``
    False: this layer rotates nothing (``Config.rotates``).  ``gate``: a list
    that takes the output gate's logits ``(B, T, nh * hs)`` of a model with
    ``attn_output_gate`` (``wq`` projects to q and gate a head; the caller
    multiplies the heads' outputs by their sigmoid before ``wo``)."""
    if lin is None:
        lin = _linear
    if delta_fn is None:
        delta_fn = _lora_delta
    B, T, C = x.shape
    hs, nh, ng = cfg.head_size, cfg.n_head, cfg.n_query_groups

    def proj(name, bias):
        o = lin(x, ap[name], ap.get(bias))
        if lora is not None and name in lora:
            o = o + delta_fn(x, *lora[name], lora_scaling)
        return o

    with scope("qkv"):
        q, k = proj("wq", "bq"), proj("wk", "bk")
        if cfg.qk_norm_whole:   # over the whole projection, before the split into heads
            q, k = _rms(q, ap["q_norm"], cfg.norm_eps), _rms(k, ap["k_norm"], cfg.norm_eps)
        if cfg.attn_output_gate:
            assert gate is not None, "attn_output_gate: the caller takes the gate (gate=[])"
            q = q.reshape(B, T, nh, 2 * hs)
            gate.append(q[..., hs:].reshape(B, T, nh * hs))
            q = q[..., :hs]
        q, k = q.reshape(B, T, nh, hs), k.reshape(B, T, ng, hs)
        if cfg.qk_norm:         # a head at a time, one weight of ``hs`` a layer each, before the rotation
            q, k = _rms(q, ap["q_norm"], cfg.norm_eps), _rms(k, ap["k_norm"], cfg.norm_eps)
        q, k = q.transpose(0, 2, 1, 3), k.transpose(0, 2, 1, 3)
        v = proj("wv", "bv").reshape(B, T, ng, hs).transpose(0, 2, 1, 3)
    n_elem = cfg.rope_n_elem
    if n_elem > 0 and rope:
        with scope("rope"):
            q_r = _rope(q[..., :n_elem], cos_t, sin_t)
            k_r = _rope(k[..., :n_elem], cos_t, sin_t)
            q = jnp.concatenate([q_r, q[..., n_elem:]], axis=-1) if n_elem < hs else q_r
            k = jnp.concatenate([k_r, k[..., n_elem:]], axis=-1) if n_elem < hs else k_r
    return q, k, v


def cache_len(cfg: Config, T_max: int) -> int:
    """Sequence capacity of the KV cache: ``sliding_window`` bounds it — a
    banded model never attends further back, so the cache is a **ring** of
    ``window`` slots (slot = position % window) and decode memory is
    O(window), not O(T_max).  (Mistral's serving memory property; beyond-ref
    — the reference has no generation loop at all.)"""
    if cfg.sliding_window is not None:
        return min(T_max, cfg.sliding_window)
    return T_max


_cache_len = cache_len  # back-compat alias


def cache_shape(cfg: Config, B: int, T_max: int) -> tuple[int, int, int, int, int]:
    """Dense KV-cache geometry ``(L, B, n_query_groups, Tc, hs)`` — the one
    layout every cache consumer (``init_cache``, the serving KV pool's
    gathered views) agrees on.  ``L`` counts the layers that keep K and V
    (``cfg.kv_layers``): a linear_attention layer has none; a looped model keeps
    them a pass (``cfg.kv_slabs``, laid out by ``cfg.kv_slab``).  A
    sliding_attention layer's slots are positions, as a full_attention layer's
    beside it are (one stacked array): its window lives in the mask
    (``_attn_with_cache(layer_window=)``), and the server, which keeps such a
    layer a ring a request, moves a prompt's last blocks there
    (``engine._blocks_back``); what either holds of the last ``layer_window``
    tokens is the same numbers."""
    if cfg.latent:   # one row a token a layer, read by every head: ``latent`` in the cache's dict
        return (cfg.n_layer, B, 1, T_max, cfg.latent_width)
    return (cfg.kv_slabs, B, cfg.n_query_groups, cache_len(cfg, T_max), cfg.head_size)


def state_shapes(cfg: Config, B: int) -> dict:
    """What a sequence keeps beside its KV, by the kind of the model's layers.
    linear_attention: ``conv (L_lin, B, K - 1, channels)``, the conv's last
    inputs, and ``state (L_lin, B, nv, dk, dv)``, the delta rule's.  conv (a
    gated short convolution): ``conv (L_conv, B, conv_kernel - 1, n_embd)``, the
    conv's last inputs, and nothing else.  ssm (a selective scan): ``conv (L_ssm,
    B, ssm_conv_kernel - 1, ssm_inner)`` and the scan's ``state (L_ssm, B,
    ssm_state, ssm_inner)``, the channels on the last axis (the chip would pad a
    last axis of 16 states to 128 lanes).  mamba2 (a Mamba-2 scan): ``conv
    (L_m, B, mamba_conv_kernel - 1, mamba_conv_width)`` (the inputs ``[x | B | C]``)
    and ``state (L_m, B, mamba_state, mamba_inner)``: head ``h``'s matrix ``S[h]
    (head_dim, N)`` lies transposed in columns ``[h head_dim, (h + 1) head_dim)``,
    so that a head of 64 channels fills its lanes with its neighbour and a
    token's ``B_t`` and ``C_t`` broadcast along them as columns, as the ssm kind's
    do.  Empty for a model of attention layers alone."""
    if cfg.conv_layers:
        return {"conv": (len(cfg.conv_layers), B, cfg.conv_kernel - 1, cfg.n_embd)}
    if cfg.ssm_layers:
        n = len(cfg.ssm_layers)
        return {"conv": (n, B, cfg.ssm_conv_kernel - 1, cfg.ssm_inner), "state": (n, B, cfg.ssm_state, cfg.ssm_inner)}
    if cfg.mamba2_layers:       # the same layout: a head's (head_dim, N) matrix transposed, its channels on the lanes
        n = len(cfg.mamba2_layers)
        return {"conv": (n, B, cfg.mamba_conv_kernel - 1, cfg.mamba_conv_width),
                "state": (n, B, cfg.mamba_state, cfg.mamba_inner)}
    n = len(cfg.linear_layers)
    if not n:
        return {}
    return {"conv": (n, B, cfg.linear_conv_kernel - 1, cfg.linear_qkv_width),
            "state": (n, B, cfg.linear_num_value_heads, cfg.linear_key_head_dim, cfg.linear_value_head_dim)}


def kv_lane_pack(cfg: Config) -> int:
    """How many KV heads the paged pool may lay side by side in one 128-lane
    row: ``128 // head_size`` where the head size divides 128 and the KV heads
    come in whole such sets, else 1.  The chip holds a narrower last axis
    padded to 128 lanes (twice the bytes at a head of 64) and Mosaic refuses
    to copy a slice of such a row, so a packed arena is what lets a narrow head
    ride ``paged_attn_decode``'s walk."""
    hs, ng = cfg.head_size, cfg.n_query_groups
    if cfg.latent or hs >= 128 or 128 % hs:
        return 1
    pack = 128 // hs
    return pack if ng % pack == 0 else 1


def kv_block_shape(cfg: Config, block_size: int, lane_pack: int = 1) -> tuple[int, int, int, int]:
    """Per-block geometry ``(L, n_query_groups, block_size, hs)`` of the
    paged serving pool's arena — one block holds ``block_size`` consecutive
    token slots of every layer's K (or V), so a gather over a request's
    block table reassembles exactly the :func:`cache_shape` layout (of
    ``cfg.paged_kv_layers``: a sliding_attention layer's K and V live in the ring
    arenas, :func:`ring_block_shape`).  With
    ``lane_pack`` P > 1 (:func:`kv_lane_pack`; the pool's choice) a row holds
    P consecutive KV heads of one token side by side: ``(L, n_query_groups / P,
    block_size, P * hs)``, head ``g`` in lanes ``[(g % P) hs, (g % P + 1) hs)``
    of group ``g // P``, the same bytes in the same order as ``(token, head,
    hs)`` would give.  A
    latent-attention model's one arena holds ``(L, 1, block_size, W)``: a row
    is a token's latent and its rotated key, ``W`` their ``latent_width`` padded
    with zeros to whole 128-lane tiles (576 to 640), which is how the chip
    lays a narrower row out anyway and what the decode kernel's copies need."""
    if cfg.latent:
        return (cfg.n_layer, 1, block_size, -(-cfg.latent_width // 128) * 128)
    return (cfg.paged_kv_slabs, cfg.n_query_groups // lane_pack, block_size, cfg.head_size * lane_pack)


def ring_blocks(cfg: Config, block_size: int) -> int:
    """Blocks of a request's ring: the ``layer_window`` tokens a
    sliding_attention layer may attend, and a block of slack (the window
    seldom starts on a block's edge).  Block ``i`` of a sequence lives in entry
    ``i % ring_blocks`` and is overwritten ``ring_blocks`` blocks later, when every
    token of it has left the window.  0 for a model without such layers."""
    return -(-cfg.layer_window // block_size) + 1 if cfg.ring_layers else 0


def ring_block_shape(cfg: Config, block_size: int, lane_pack: int = 1) -> tuple[int, int, int, int]:
    """:func:`kv_block_shape` of the sliding_attention layers' ring arenas."""
    return (len(cfg.ring_layers), *kv_block_shape(cfg, block_size, lane_pack)[1:])


def ring_slot(pos, window: int):
    """Ring-cache slot of global position ``pos``: ``pos % window``."""
    return jax.lax.rem(pos, window)


def ring_gather_positions(T: int, window: int):
    """Prefill→ring scatter map: for each ring slot ``j``, the latest prompt
    position ``p < T`` with ``p ≡ j (mod window)`` (clamped to 0 for slots no
    prompt position reaches; those stay garbage and are masked positionally
    at decode)."""
    import numpy as _np

    src_pos = _np.array([j + ((T - 1 - j) // window) * window for j in range(window)])
    return _np.maximum(src_pos, 0)


def init_cache(cfg: Config, B: int, T_max: int, dtype=jnp.bfloat16, *, mesh=None, axis="tp") -> dict:
    """Preallocated KV cache: ``{"k"/"v": (L, B, n_query_groups, Tc, hs)}``
    where ``Tc = T_max``, bounded by ``cfg.sliding_window`` (ring cache).

    With ``mesh``, the KV-group dim shards over ``axis`` per
    ``distributed.kv_cache_spec`` — the ONE spec rule shared with the
    serving pool's block arena (tensor-parallel serving: each device holds
    its heads' cache; attention stays device-local and only the output
    projection reduces).  An indivisible group count degrades to
    replication rather than erroring (same policy as the sharding rules)."""
    shape = cache_shape(cfg, B, T_max)
    sh = None
    if mesh is not None:
        from jax.sharding import NamedSharding
        from thunder_tpu.distributed.sharding import kv_cache_spec

        spec = kv_cache_spec(cfg, mesh, axis=axis)
        if len(spec):  # non-empty spec: the heads dim actually shards
            sh = NamedSharding(mesh, spec)

    def zeros():  # two independent buffers, no copy traffic
        z = jnp.zeros(shape, dtype=dtype)
        return jax.device_put(z, sh) if sh is not None else z

    if cfg.latent:
        assert mesh is None, "a latent cache has no sharded layout yet"
        return {"latent": zeros()}
    cache = {"k": zeros(), "v": zeros()}
    shapes = state_shapes(cfg, B)
    if shapes:
        assert mesh is None, "a recurrent state has no sharded cache yet"
        cache.update({name: jnp.zeros(shape, jnp.float32 if name == "state" else dtype)
                      for name, shape in shapes.items()})
    return cache


def _is_vec_pos(pos) -> bool:
    """True when ``pos`` is per-row positions (B,) rather than one scalar."""
    return getattr(pos, "ndim", 0) == 1


def _expand_groups(kk, vv, nh):
    B, ng, Tc, hs = kk.shape
    if ng != nh:
        rep = nh // ng
        kk = jnp.broadcast_to(kk[:, :, None], (B, ng, rep, Tc, hs)).reshape(B, nh, Tc, hs)
        vv = jnp.broadcast_to(vv[:, :, None], (B, ng, rep, Tc, hs)).reshape(B, nh, Tc, hs)
    return kk, vv


def _band_keep(T: int, W):
    """``(1, 1, T, T)`` keep-mask of a prompt attending itself: causal, and
    within the window ``W`` where there is one."""
    row = jnp.arange(T)[None, None, :, None]
    col = jnp.arange(T)[None, None, None, :]
    keep = col <= row
    return keep if W is None else jnp.logical_and(keep, col > row - W)


def attend_dense(q, kk, vv, keep):
    """The scoring tail of attention over keys and values in hand: ``q (B, nh,
    T, hs)``, ``kk``/``vv (B, ng, Tk, hs)`` (grouped; any dtype), ``keep`` a mask
    that broadcasts against ``(B, nh, T, Tk)``.  One softmax in float32, the
    weights rounded to ``q``'s dtype before the value product.  The dense cache
    ends in it, and so does the paged kernels' XLA form
    (``pallasex.paged_attn_xla``): the two agree to the bit.  Returns ``(B, nh,
    T, hs)``."""
    kk, vv = _expand_groups(kk, vv, q.shape[1])
    scores = jnp.einsum(
        "bhqd,bhkd->bhqk", q, kk.astype(q.dtype), preferred_element_type=jnp.float32
    ) / math.sqrt(q.shape[-1])
    w = jax.nn.softmax(jnp.where(keep, scores, -jnp.inf), axis=-1).astype(q.dtype)
    return jnp.einsum("bhqk,bhkd->bhqd", w, vv.astype(q.dtype))


def gated_out(y, gate):
    """The heads' outputs ``y (B, T, nh * hs)`` times the sigmoid of their gate's
    logits (``_project_qkv(gate=)``), before ``wo``; ``y`` where there is none."""
    if not gate:
        return y
    with scope("gate"):
        return y * jax.nn.sigmoid(gate[0].astype(jnp.float32)).astype(y.dtype)


def _attn_with_cache(ap, x, cos_t, sin_t, ck, cv, pos, cfg: Config, *, quantized=False,
                     lora=None, lora_scaling=1.0, sharded=False, layer_window=None, rope=True):
    """x: (B, T, C) new tokens at global positions [pos, pos+T).  Writes their
    K/V into the per-layer cache (ck/cv: (B, ng, Tc, hs)) and attends against
    every slot the model may see.

    Two cache layouts (see ``_cache_len``): the plain layout (slot =
    position) when the cache covers the full sequence, and the **ring**
    layout (slot = position % window) when ``sliding_window`` bounds it.
    Each branch decides (kk, vv, keep-mask, cache writes); the scoring tail
    is shared.

    A prompt at a *static* position 0 (``pos`` the Python integer 0, ``T >
    1``) can see nothing but its own causal triangle, in either layout: it
    attends the fresh ``k``/``v`` and reads no slot of the cache, through the
    flash kernel where that takes the shapes (grouped K/V as they are), else
    through the shared tail over ``(T, T)``.  ``sharded`` (the operands live on
    a mesh, where a bare ``pallas_call`` would be replicated) keeps the tail.

    ``layer_window``: a sliding_attention layer's (``cfg.layer_window``): the
    plain layout whatever the cache's length, the window in the mask (the
    server moves such a layer's last blocks to the request's ring,
    ``engine._blocks_back``).  ``rope`` False: the layer rotates nothing.
    """
    B, T, C = x.shape
    hs, nh, ng = cfg.head_size, cfg.n_head, cfg.n_query_groups
    lin = partial(_linear, quantized=quantized)
    gate = []
    q, k, v = _project_qkv(ap, x, cos_t, sin_t, cfg, lin=lin, lora=lora,
                           lora_scaling=lora_scaling, rope=rope, gate=gate)
    Tc = ck.shape[2]
    W = cfg.sliding_window if layer_window is None else layer_window
    ring = layer_window is None and W is not None and Tc == W
    vec = _is_vec_pos(pos)
    assert not (ring and vec), "per-row positions are not supported with a ring cache"
    fresh = isinstance(pos, int) and pos == 0 and T > 1

    # the cache's writes, the slots attended and their mask
    with scope("cache"):
        if not ring:
            if vec:
                upd = jax.vmap(lambda c, u, p: jax.lax.dynamic_update_slice_in_dim(c, u, p, axis=1))
                ck = upd(ck, k.astype(ck.dtype), pos)
                cv = upd(cv, v.astype(cv.dtype), pos)
            else:
                ck = jax.lax.dynamic_update_slice_in_dim(ck, k.astype(ck.dtype), pos, axis=2)
                cv = jax.lax.dynamic_update_slice_in_dim(cv, v.astype(cv.dtype), pos, axis=2)
            if fresh:
                kk, vv, keep = k, v, _band_keep(T, W)
            else:
                kk, vv = ck, cv
                # query at global position pos+t sees cache slots (pos+t-W, pos+t]
                j = jnp.arange(Tc)[None, None, None, :]
                if vec:
                    qpos = (pos[:, None] + jnp.arange(T)[None, :])[:, None, :, None]  # (B,1,T,1)
                else:
                    qpos = (pos + jnp.arange(T))[None, None, :, None]
                keep = j <= qpos
                if W is not None:
                    keep = jnp.logical_and(keep, j > qpos - W)
        elif T > 1:
            # ring prefill: the chunk attends within itself (banded); the cache
            # keeps each ring slot's latest prompt position.  pos==0 because a
            # later chunk would need K/V already evicted from the ring.
            assert fresh, "ring-cache prefill must start at position 0"
            kk, vv, keep = k, v, _band_keep(T, W)
            # slot j <- the latest prompt position p ≡ j (mod W); slots with no
            # such position stay garbage (masked positionally at decode)
            gather = ring_gather_positions(T, W)
            ck = jnp.take(k, gather, axis=2).astype(ck.dtype)
            cv = jnp.take(v, gather, axis=2).astype(cv.dtype)
        else:
            # ring decode: one token at global position pos -> slot pos % W
            slot = ring_slot(pos, W)
            ck = jax.lax.dynamic_update_slice_in_dim(ck, k.astype(ck.dtype), slot, axis=2)
            cv = jax.lax.dynamic_update_slice_in_dim(cv, v.astype(cv.dtype), slot, axis=2)
            kk, vv = ck, cv
            # slot j holds global position pos - ((pos - j) mod W) — always in
            # (pos-W, pos]; mask only slots never written (negative position)
            j = jnp.arange(W)
            gp = pos - jax.lax.rem(jax.lax.rem(pos - j, W) + W, W)
            keep = (gp >= 0)[None, None, None, :]

    flash = None
    with scope("attn" if layer_window is None else "swa"):
        if fresh and not sharded:
            from thunder_tpu.executors import pallasex

            flash = pallasex.flash_sdpa(q, k, v, None, True, 1.0 / math.sqrt(hs),
                                        W if W is not None and T > W else None)
        y = flash[0] if flash is not None else attend_dense(q, kk, vv, keep)
    y = gated_out(y.transpose(0, 2, 1, 3).reshape(B, T, nh * hs), gate)
    with scope("out"):
        out = lin(y, ap["wo"], ap.get("bo"))
        if lora is not None and "wo" in lora:
            out = out + _lora_delta(y, *lora["wo"], lora_scaling)
    return out, ck, cv


def _l2norm(x, eps: float = 1e-6):
    xf = x.astype(jnp.float32)
    return (xf * jax.lax.rsqrt(jnp.sum(xf * xf, axis=-1, keepdims=True) + eps)).astype(x.dtype)


def _causal_taps(tail, x, w, n_real=None):
    """A causal depthwise conv over time in XLA, its state carried: ``x (B, T,
    channels)`` after ``tail (B, K - 1, channels)``, the inputs of the K - 1
    tokens before it; ``w (channels, K)``, tap j of a channel weighing the token
    K - 1 - j back; no bias.  Returns the sums in float32 ``(B, T, channels)``
    and the new tail, which ends at token ``n_real`` (the last, where None) and
    keeps ``tail``'s dtype."""
    T, K = x.shape[1], w.shape[1]
    seen = jnp.concatenate([tail.astype(x.dtype), x], axis=1)                          # (B, K - 1 + T, channels)
    new_tail = (seen[:, T:] if n_real is None
                else jax.lax.dynamic_slice_in_dim(seen, n_real, K - 1, axis=1)).astype(tail.dtype)
    w = w.astype(jnp.float32)
    return sum(seen[:, j:j + T].astype(jnp.float32) * w[:, j] for j in range(K)), new_tail


def gdn_mixer(gp, x, tail, cfg: Config, recur, *, n_real=None, lin=_linear):
    """A linear_attention layer's mixer on new tokens ``x (B, T, C)``, for the
    dense cache and the paged server alike (``llama.gated_delta_net`` with its
    state carried).  ``tail (B, K - 1, channels)`` holds the conv's inputs of
    the K - 1 tokens before ``x`` (zeros before a sequence's first); the
    delta rule itself is ``recur(q, k, v, g, beta) -> o``: q, k ``(B, nk, T,
    dk)`` (unit keys, scaled queries), v ``(B, nv, T, dv)``, g (log-decay) and
    beta ``(B, nv, T)`` float32, o ``(B, nv, T, dv)``; the caller's closure
    reads and writes the state wherever it keeps it.  Of the T tokens the
    first ``n_real`` are real (all, where None): the others get ``g = 0`` and
    ``beta = 0``, which leaves the state exactly as it was, and the new tail
    ends at the last real token.  Returns ``(y (B, T, C), new tail)``."""
    B, T, _ = x.shape
    nk, nv = cfg.linear_num_key_heads, cfg.linear_num_value_heads
    dk, dv = cfg.linear_key_head_dim, cfg.linear_value_head_dim
    n_qkv = cfg.linear_qkv_width
    with scope("gdn/in_proj"):
        qkvz, ba = lin(x, gp["in_proj_qkvz"]), lin(x, gp["in_proj_ba"])
        z = qkvz[..., n_qkv:]
    with scope("gdn/conv"):
        conv, new_tail = _causal_taps(tail, qkvz[..., :n_qkv], gp["conv_w"], n_real)
        qkv = jax.nn.silu(conv).astype(x.dtype)
    with scope("gdn/gates"):
        heads = lambda a, n, d: a.reshape(B, T, n, d).transpose(0, 2, 1, 3)  # noqa: E731
        q = _l2norm(heads(qkv[..., :nk * dk], nk, dk)) * (dk ** -0.5)
        k = _l2norm(heads(qkv[..., nk * dk:2 * nk * dk], nk, dk))
        v = heads(qkv[..., 2 * nk * dk:], nv, dv)
        beta = jax.nn.sigmoid(ba[..., :nv].astype(jnp.float32)) * (2.0 if cfg.linear_allow_neg_eigval else 1.0)
        g = -jnp.exp(gp["A_log"].astype(jnp.float32)) * jax.nn.softplus(
            ba[..., nv:].astype(jnp.float32) + gp["dt_bias"].astype(jnp.float32))
        if n_real is not None:
            real = (jnp.arange(T) < n_real)[None, :, None]
            g, beta = jnp.where(real, g, 0.0), jnp.where(real, beta, 0.0)
    with scope("gdn/scan"):
        o = recur(q, k, v, g.transpose(0, 2, 1), beta.transpose(0, 2, 1))               # (B, nv, T, dv)
    with scope("gdn/out"):
        o = _rms(o.transpose(0, 2, 1, 3), gp["norm"], cfg.norm_eps) * jax.nn.silu(z.reshape(B, T, nv, dv))
        return lin(o.reshape(B, T, nv * dv), gp["out_proj"]), new_tail


def shortconv_mixer(cp, x, tail, cfg: Config, *, n_real=None, lin=_linear):
    """A conv layer's mixer (a gated short convolution, hf ``Lfm2ShortConv``) on
    new tokens ``x (B, T, C)``, for the dense cache and the paged server alike:
    ``[B | C | u] = x W_in``; ``v = B * u``; a causal depthwise conv of
    ``conv_kernel`` taps over ``v`` (tap j of a channel weighs the token K - 1 -
    j back; no bias, no activation), in float32 over ``[tail | v]``; ``y = C *
    conv``; ``W_out``.  ``tail (B, K - 1, C)`` holds ``v`` of the K - 1 tokens
    before ``x`` (zeros before a sequence's first), and is all a sequence
    keeps.  Of the T tokens the first ``n_real`` are real (all, where None): the
    new tail ends at the last real one.  Returns ``(y (B, T, C), new tail)``."""
    with scope("conv/in_proj"):
        b, c, u = jnp.split(lin(x, cp["in_proj"]), 3, axis=-1)
    with scope("conv/gate"):
        v = b * u
    with scope("conv/conv"):
        conv, new_tail = _causal_taps(tail, v, cp["conv_w"], n_real)
    with scope("conv/gate"):
        y = (c.astype(jnp.float32) * conv).astype(x.dtype)
    with scope("conv/out"):
        return lin(y, cp["out_proj"]), new_tail



def ssm_mixer(sp, x, tail, cfg: Config, recur, *, n_real=None, lin=_linear):
    """An ssm layer's mixer (a selective scan, Mamba-1) on new tokens ``x (B, T,
    C)``, for the dense cache and the paged server alike: ``[u | z] = x W_in``;
    ``u <- SiLU(conv(u) + b)``, causal and depthwise over ``[tail | u]`` in
    float32; ``[r | B | C] = u W_x``; ``dt = softplus(r W_dt + b_dt)``; the scan
    itself is ``recur(u, dt, B, C, A) -> S C`` (u ``(B, T, d)`` at x's dtype, dt
    float32, B and C ``(B, T, N)``, ``A = -exp(A_log)`` transposed to ``(N, d)``;
    the result ``(B, T, d)`` float32), the caller's closure reading and writing
    the state wherever it keeps it; ``m = S C + D u``; ``(m SiLU(z)) W_out``.
    ``tail (B, K - 1, d)`` holds the conv's inputs of the K - 1 tokens before
    ``x``.  Of the T tokens the first ``n_real`` are real (all, where None): the
    others get ``dt = 0``, which leaves the state exactly as it was, and the new
    tail ends at the last real token.  Returns ``(y (B, T, C), new tail, m (B,
    T, d) at x's dtype)``: a gmu layer gates ``m``."""
    f32 = jnp.float32
    N, R = cfg.ssm_state, cfg.ssm_dt_rank
    with scope("ssm/in_proj"):
        u, z = jnp.split(lin(x, sp["in_proj"]), 2, axis=-1)
    with scope("ssm/conv"):
        conv, new_tail = _causal_taps(tail, u, sp["conv_w"], n_real)
        u = jax.nn.silu(conv + sp["conv_b"].astype(f32)).astype(x.dtype)
    with scope("ssm/scan"):
        rbc = lin(u, sp["x_proj"])
        Bm, Cm = rbc[..., R:R + N].astype(f32), rbc[..., R + N:].astype(f32)
        dt = jax.nn.softplus(lin(rbc[..., :R], sp["dt_proj"]).astype(f32) + sp["dt_bias"].astype(f32))
        if n_real is not None:
            dt = jnp.where((jnp.arange(x.shape[1]) < n_real)[None, :, None], dt, 0.0)
        m = recur(u, dt, Bm, Cm, -jnp.exp(sp["A_log"].astype(f32)).T)
        m = (m + sp["D"].astype(f32) * u.astype(f32)).astype(x.dtype)
    with scope("ssm/out"):
        return lin((m.astype(f32) * jax.nn.silu(z.astype(f32))).astype(x.dtype), sp["out_proj"]), new_tail, m


def ssm_recur_dense(state):
    """``recur`` for :func:`ssm_mixer` over a dense state ``(B, N, d)``: the scan
    for a piece of a prompt, one step for a token.  Returns ``(recur, box)``;
    after the call ``box[0]`` is the state after the last token, in ``state``'s dtype."""
    box = [state]

    def recur(u, dt, Bm, Cm, A):
        from thunder_tpu.executors import pallasex

        if u.shape[1] > 1:
            y, box[0] = pallasex.ssm_scan(u, dt, Bm, Cm, A, state)
            return y
        f32 = jnp.float32
        y, S = jax.vmap(pallasex.ssm_step_math, in_axes=(0, 0, 0, 0, 0, None))(
            state.astype(f32), dt, u.astype(f32), Bm[:, 0, :, None], Cm[:, 0, :, None], A)
        box[0] = S.astype(state.dtype)
        return y

    return recur, box


def mamba2_mixer(mp, x, tail, cfg: Config, recur, *, n_real=None, lin=_linear):
    """A mamba2 layer's mixer (Mamba-2, state-space duality) on new tokens ``x
    (B, T, C)``, for the dense cache and the paged server alike: ``[z | xBC | dt]
    = x W_in``; ``xBC <- SiLU(conv(xBC) + b)``, causal and depthwise over ``[tail |
    xBC]`` in float32; ``[xs | B | C] = xBC`` (``xs`` the heads' channels, ``B`` and
    ``C`` a group of heads); ``dt = softplus(dt + dt_bias)`` a head; the scan itself
    is ``recur(xs, dt, B, C, A) -> S C`` (xs ``(B, T, d)`` at x's dtype, dt ``(B, T,
    H)`` float32, B and C ``(B, T, G, N)``, ``A = -exp(A_log) (H,)``; the result ``(B,
    T, d)`` float32), the caller's closure reading and writing the state wherever
    it keeps it; ``y = S C + D xs``; ``y <- RMSNorm_group(y SiLU(z)) w`` (the gate
    before the norm, the norm over each group's ``d / G`` channels); ``y W_out``.
    ``tail (B, K - 1, d + 2 G N)`` holds the conv's inputs of the K - 1 tokens
    before ``x``.  Of the T tokens the first ``n_real`` are real (all, where None):
    the others get ``dt = 0``, which leaves the state exactly as it was, and the
    new tail ends at the last real token.  Returns ``(y (B, T, C), new tail)``."""
    f32 = jnp.float32
    B, T, _ = x.shape
    H, G, N, d = cfg.mamba_heads, cfg.mamba_groups, cfg.mamba_state, cfg.mamba_inner
    W = cfg.mamba_conv_width
    with scope("mamba2/in_proj"):
        zxd = lin(x, mp["in_proj"])
        z, xbc, dt = zxd[..., :d], zxd[..., d:d + W], zxd[..., d + W:]
    with scope("mamba2/conv"):
        conv, new_tail = _causal_taps(tail, xbc, mp["conv_w"], n_real)
        xbc = jax.nn.silu(conv + mp["conv_b"].astype(f32)).astype(x.dtype)
    with scope("mamba2/scan"):
        xs = xbc[..., :d]
        Bm, Cm = (xbc[..., d + i * G * N:d + (i + 1) * G * N].reshape(B, T, G, N) for i in (0, 1))
        dt = jax.nn.softplus(dt.astype(f32) + mp["dt_bias"].astype(f32))
        if n_real is not None:
            dt = jnp.where((jnp.arange(T) < n_real)[None, :, None], dt, 0.0)
        y = recur(xs, dt, Bm, Cm, -jnp.exp(mp["A_log"].astype(f32)))
        y = y + jnp.repeat(mp["D"].astype(f32), cfg.mamba_head_dim) * xs.astype(f32)
    with scope("mamba2/norm"):
        y = (y * jax.nn.silu(z.astype(f32))).reshape(B, T, G, d // G)
        y = y * jax.lax.rsqrt(jnp.mean(y * y, axis=-1, keepdims=True) + cfg.norm_eps)
        y = (y.reshape(B, T, d) * mp["norm"].astype(f32)).astype(x.dtype)
    with scope("mamba2/out"):
        return lin(y, mp["out_proj"]), new_tail


def mamba2_recur_dense(state):
    """``recur`` for :func:`mamba2_mixer` over a dense state ``(B, N, d)``: the
    chunked scan for a piece of a prompt, one step for a token.  Returns
    ``(recur, box)``; after the call ``box[0]`` is the state after the last token,
    in ``state``'s dtype."""
    box = [state]

    def recur(xs, dt, Bm, Cm, A):
        from thunder_tpu.executors import pallasex

        if xs.shape[1] > 1:
            y, box[0] = pallasex.ssd_chunk(xs, dt, Bm, Cm, A, state)
            return y
        # one token: the paged step's XLA form on a one-layer arena whose slots are the rows
        y, S = pallasex.ssd_decode_step_xla(state[:, None], jnp.arange(state.shape[0]), xs[:, 0], dt[:, 0],
                                            Bm[:, 0], Cm[:, 0], A, layer=0)
        box[0] = S[:, 0]
        return y[:, None]

    return recur, box


def gmu_mixer(gp, x, m, *, lin=_linear):
    """A gmu layer's mixer (a gated memory unit): ``(m SiLU(x W_1)) W_2``, ``m (B,
    T, d)`` the scan output of the model's last ssm layer at the same
    positions.  No cache: in a decode step ``m`` is a value inside the step."""
    with scope("gmu/in_proj"):
        g = lin(x, gp["in_proj"])
    with scope("gmu/gate"):
        y = (m.astype(jnp.float32) * jax.nn.silu(g.astype(jnp.float32))).astype(x.dtype)
    with scope("gmu/out"):
        return lin(y, gp["out_proj"])


def diff_lambda(ap, layer: int):
    """``(lambda, lambda_init)`` of a differential-attention layer, float32:
    ``l0 = 0.8 - 0.6 exp(-0.3 layer)``, ``lambda = exp(lq1 . lk1) - exp(lq2 . lk2) + l0``."""
    f32 = jnp.float32
    l0 = 0.8 - 0.6 * math.exp(-0.3 * layer)
    dot = lambda a, b: jnp.sum(ap[a].astype(f32) * ap[b].astype(f32))  # noqa: E731
    return jnp.exp(dot("lambda_q1", "lambda_k1")) - jnp.exp(dot("lambda_q2", "lambda_k2")) + l0, l0


def diff_attention(ap, x, layer: int, cfg: Config, attend, *, kv_x=None, cross=False, lin=_linear, name="attn"):
    """A differential-attention layer's mixer, for the dense cache and the paged
    server alike.  ``x (B, T, C)`` gives the queries; ``kv_x (B, Tk, C)`` the keys
    and values (``x`` itself where None); ``cross``: a cross_attention layer,
    which projects no K and V (``attend`` reads another layer's).  Query heads pair ``(2j, 2j + 1)``, KV heads ``(2g, 2g + 1)`` with
    ``g = j // (n_head / n_query_groups)``: a pair's keys are one row of ``2 hs``
    lanes, its values ``V_g = [v_2g | v_2g+1]`` another, which is how the paged
    arena holds them (``kv_lane_pack``).  ``attend(q, k, v) -> a``: ``q (B, G, 2,
    J, T, hs)`` (G pairs of KV heads; first or second of the pair; J
    differential heads a pair), ``k`` and ``v`` ``(B, ng, Tk, hs)`` or None, ``a
    (B, G, 2, J, T, 2 hs)`` the softmax-weighted ``V_g`` of each query, the first
    over ``k_2g`` and the second over ``k_2g+1``, scores scaled by ``hs^-1/2``.
    ``o_j = (1 - l0) RMSNorm_2hs(a_1 - lambda a_2)`` (:func:`diff_lambda`), then
    ``W_o``.  ``name``: the scopes' prefix (``swa``, ``cross``, ``attn``).  Returns
    ``y (B, T, C)``."""
    B, T, _ = x.shape
    hs, nh, ng = cfg.head_size, cfg.n_head, cfg.n_query_groups
    G, J = ng // 2, nh // ng
    with scope(f"{name}/qkv"):
        q = lin(x, ap["wq"], ap.get("bq")).reshape(B, T, G, J, 2, hs).transpose(0, 2, 4, 3, 1, 5)
        k = v = None
        if not cross:
            src = x if kv_x is None else kv_x
            heads = lambda a: a.reshape(B, src.shape[1], ng, hs).transpose(0, 2, 1, 3)  # noqa: E731
            k, v = heads(lin(src, ap["wk"], ap.get("bk"))), heads(lin(src, ap["wv"], ap.get("bv")))
    a = attend(q, k, v)
    with scope(f"{name}/diff"):
        lam, l0 = diff_lambda(ap, layer)
        o = a[:, :, 0].astype(jnp.float32) - lam * a[:, :, 1].astype(jnp.float32)     # (B, G, J, T, 2 hs)
        o = (_rms(o, ap["subln"], cfg.norm_eps) * (1.0 - l0)).astype(x.dtype)
    with scope(f"{name}/out"):
        return lin(o.transpose(0, 3, 1, 2, 4).reshape(B, T, nh * hs), ap["wo"], ap.get("bo"))


def pair_rows(k):
    """K (or V) ``(B, ng, T, hs)`` as a head pair's rows ``(B, ng / 2, T, 2 hs)``: heads ``2g`` and ``2g + 1`` side by side."""
    B, ng, T, hs = k.shape
    return k.reshape(B, ng // 2, 2, T, hs).transpose(0, 1, 3, 2, 4).reshape(B, ng // 2, T, 2 * hs)


def diff_attend_dense(q, kr, vr, keep, *, causal_window=False):
    """``attend`` of :func:`diff_attention` over key and value rows in hand: ``q
    (B, G, 2, J, T, hs)``, ``kr``/``vr (B, G, Tk, 2 hs)`` (:func:`pair_rows`),
    ``keep`` a mask that broadcasts against ``(B, G, J, T, Tk)``.
    ``causal_window``: the mask is a prompt's own causal triangle, banded by
    that window where it is an int (None: no band; False: some other mask): the
    flash kernel takes it where it takes the shapes, the queries padded with
    zeros to the row's ``2 hs`` lanes in their own half, which cancels the
    other head's keys exactly."""
    B, G, _, J, T, hs = q.shape
    scale = 1.0 / math.sqrt(hs)
    if causal_window is not False:
        from thunder_tpu.executors import pallasex

        own = jnp.eye(2, dtype=q.dtype)                                 # (half of the query, half of the lanes)
        qp = (q[:, :, :, :, :, None, :] * own[None, None, :, None, None, :, None]).reshape(B, G * 2 * J, T, 2 * hs)
        flash = pallasex.flash_sdpa(qp, kr, vr, None, True, scale, causal_window)
        if flash is not None:
            return flash[0].reshape(B, G, 2, J, T, 2 * hs)
    out = []
    for half in range(2):
        s = jnp.einsum("bgjtd,bgkd->bgjtk", q[:, :, half], kr[..., half * hs:(half + 1) * hs].astype(q.dtype),
                       preferred_element_type=jnp.float32) * scale
        w = jax.nn.softmax(jnp.where(keep, s, -jnp.inf), axis=-1).astype(q.dtype)
        out.append(jnp.einsum("bgjtk,bgkd->bgjtd", w, vr.astype(q.dtype)))
    return jnp.stack(out, axis=2)


def _diff_attn_with_cache(ap, x, layer, ck, cv, pos, cfg: Config, *, kind, row=None, lin=_linear, sharded=False):
    """A differential-attention layer of :func:`forward_with_cache`: ``x (B, T,
    C)`` at positions ``[pos, pos + T)`` against and into this layer's dense
    cache ``ck``/``cv (B, ng, Tc, hs)`` (slot = position; a sliding_attention
    layer's window lives in the mask).  ``kind`` "cross_attention": ``ck``/``cv``
    are the cross source's, read and not written.  ``row``: the queries are row
    ``row`` of ``x`` alone (a prompt's cross half; the layer's own K and V still
    come from every row).  A prompt at a static position 0 attends its fresh keys
    (the flash kernel where it takes them).  Returns ``(y, ck, cv)``."""
    B, T, _ = x.shape
    W = cfg.layer_window if kind == "sliding_attention" else None
    vec = _is_vec_pos(pos)
    fresh = isinstance(pos, int) and pos == 0 and T > 1 and kind != "cross_attention"
    name = {"sliding_attention": "swa", "cross_attention": "cross"}.get(kind, "attn")
    box = [ck, cv]
    # a cross layer after the narrowed one is handed the one row already
    xq = x if row is None or T == 1 else jax.lax.dynamic_slice_in_dim(x, row, 1, axis=1)
    Tq = xq.shape[1]

    def attend(q, k, v):
        with scope(f"{name}/cache"):
            if k is not None:
                if vec:
                    upd = jax.vmap(lambda c, u, p: jax.lax.dynamic_update_slice_in_dim(c, u, p, axis=1))
                    box[0], box[1] = upd(ck, k.astype(ck.dtype), pos), upd(cv, v.astype(cv.dtype), pos)
                else:
                    box[0] = jax.lax.dynamic_update_slice_in_dim(ck, k.astype(ck.dtype), pos, axis=2)
                    box[1] = jax.lax.dynamic_update_slice_in_dim(cv, v.astype(cv.dtype), pos, axis=2)
            kk, vv = (k, v) if fresh else (box[0], box[1])
            kr, vr = pair_rows(kk), pair_rows(vv)
            first = (pos[:, None] if vec else pos) + (0 if row is None else row)
            qpos = (first + jnp.arange(Tq))[..., :, None]                           # (B?, Tq, 1)
            j = jnp.arange(kr.shape[2])
            keep = j <= qpos
            if W is not None:
                keep = jnp.logical_and(keep, j > qpos - W)
            keep = keep[:, None, None] if vec else keep[None, None, None]
        with scope(f"{name}/attn"):
            # a whole prompt attending itself off a mesh: the flash kernel's case
            whole = fresh and row is None and not sharded
            return diff_attend_dense(q, kr, vr, keep,
                                     causal_window=(W if W is not None and T > W else None) if whole else False)

    y = diff_attention(ap, xq, layer, cfg, attend, kv_x=x, cross=kind == "cross_attention", lin=lin, name=name)
    return y, box[0], box[1]


def gdn_recur_dense(state):
    """``recur`` for :func:`gdn_mixer` over a dense state ``(B, nv, dk, dv)``:
    the chunked scan for a piece of a prompt, one step of the rule for a
    token.  Returns ``(recur, box)``; after the call ``box[0]`` is the state
    after the last token, in ``state``'s dtype."""
    box = [state]

    def recur(q, k, v, g, beta):
        from thunder_tpu.executors import jaxex, pallasex

        T, rep = v.shape[2], v.shape[1] // q.shape[1]
        if T > 1:
            o, box[0] = jaxex.gdn_chunk_state(q, k, v, g, beta, state)
            return o
        f32 = jnp.float32
        col = lambda a: jnp.repeat(a[:, :, 0], rep, axis=1).astype(f32)[..., None]  # noqa: E731 -- (B, nv, dk, 1)
        row = lambda a: jnp.broadcast_to(a.astype(f32), v.shape)  # noqa: E731 -- (B, nv, 1, dv)
        o, S = jax.vmap(jax.vmap(pallasex.gdn_step_math))(
            state.astype(f32), col(k), col(q), v.astype(f32), row(jnp.exp(g)[..., None]), row(beta[..., None]))
        box[0] = S.astype(state.dtype)
        return o.astype(v.dtype)

    return recur, box


def _stream_product(x, w):
    """``sum_jc x[b, j, t, c] w[m, j, c]`` as ``(m, B, T)`` float32, to float32's
    accuracy whatever the stream's dtype.  A float32 stream at the highest
    precision.  A bfloat16 one is exact as it stands, so ``w`` alone is split into
    bfloat16 pieces (three hold float32's 24 bits) and each piece is one pass of
    the matrix unit with float32 sums; a stream a (T, C) slab, so no slab is
    transposed to be multiplied."""
    n = x.shape[1]
    if x.dtype == jnp.float32:
        return sum(jnp.einsum("btc,mc->mbt", x[:, j], w[:, j], precision=jax.lax.Precision.HIGHEST) for j in range(n))
    pieces, rest = [], w
    for _ in range(3):
        pieces.append(rest.astype(x.dtype))
        rest = rest - pieces[-1].astype(jnp.float32)
    w3 = jnp.concatenate(pieces)                                                    # (3 m, n, C)
    out = sum(jnp.einsum("btc,mc->mbt", x[:, j], w3[:, j], preferred_element_type=jnp.float32) for j in range(n))
    return out.reshape(3, w.shape[0], *out.shape[1:]).sum(axis=0)


def hc_maps(hp, x, cfg: Config):
    """The three maps one hyper-connection gives each token of the stream ``x
    (B, n, T, C)`` (``n`` = ``cfg.hc_mult``; a stream is a major axis, a ``(T, C)``
    slab, so nothing is tiled over an axis of 4), all in float32 and with the
    tokens on the last axis (a map is a few numbers a token: ``(n, n, B, T)`` fills
    the lanes where ``(B, T, n, n)`` would fill a thirty-second of a tile):

        x' = rms_nC(vec(X)) * norm                eps hc_eps; the weight is folded into phi, the map being linear
        H_pre  = sigmoid(a_0 (x' phi_pre) + b_pre)                                    (n, B, T)
        H_post = 2 sigmoid(a_1 (x' phi_post) + b_post)                                (n, B, T)
        M = exp(clip(a_2 (x' phi_res) + b_res, hc_res_clamp)) as (n, n): [i, j] to stream i from stream j
        hc_sinkhorn_iters times: M /= its columns' sums + hc_eps, then M /= its rows' sums + hc_eps   -> H_res (n, n, B, T)

    ``hp``: ``phi (n (n + 2), n C)`` rows ``[pre | post | res]``, ``norm (n C)``,
    ``alpha (3,)`` and ``bias (n (n + 2),)`` float32."""
    B, n, T, C = x.shape
    f32 = jnp.float32
    with scope("norm"):
        xf = x.astype(f32)
        r = jax.lax.rsqrt(jnp.mean(xf * xf, axis=(1, 3)) + cfg.hc_eps)               # (B, T)
    with scope("maps"):
        phi = (hp["phi"].astype(f32) * hp["norm"].astype(f32)).reshape(-1, n, C)
        raw = _stream_product(x, phi) * r                                           # (n (n + 2), B, T)
        a, b = hp["alpha"].astype(f32), hp["bias"].astype(f32)[:, None, None]
        h_pre = jax.nn.sigmoid(a[0] * raw[:n] + b[:n])
        h_post = 2.0 * jax.nn.sigmoid(a[1] * raw[n:2 * n] + b[n:2 * n])
    with scope("sinkhorn"):
        lo, hi = cfg.hc_res_clamp
        m = jnp.exp(jnp.clip(a[2] * raw[2 * n:] + b[2 * n:], lo, hi)).reshape(n, n, B, T)
        for _ in range(cfg.hc_sinkhorn_iters):
            m = m / (jnp.sum(m, axis=0, keepdims=True) + cfg.hc_eps)
            m = m / (jnp.sum(m, axis=1, keepdims=True) + cfg.hc_eps)
    return h_pre, h_post, m


def hc_open(hp, x, cfg: Config):
    """What a sublayer reads of the stream ``x (B, n, T, C)``: ``u = H_pre X (B, T,
    C)`` in float32 as it was summed, and the two maps its :func:`hc_close`
    writes back by.  The sublayer's norm rounds ``u`` to the stream's dtype,
    once: a copy of it at 16 bits in between is a second rounding of every
    sublayer's input, which XLA's fusion of these lines drops on the chip and a
    kernel that wrote one would not (PERF.md, PR 56: the deepest latents' error
    7.5% up with it, 2% without).  :func:`hc_step` opens every sublayer (under
    ``mixer`` or ``mlp``), here or in its kernel."""
    with scope("hc/open"):
        h_pre, h_post, h_res = hc_maps(hp, x, cfg)
        with scope("read"):
            u = sum(h_pre[j][..., None] * x[:, j].astype(jnp.float32) for j in range(x.shape[1]))
            return u, (h_post, h_res)


def hc_close(x, f, maps):
    """The stream after a sublayer gave ``f (B, T, C)``: ``H_res X + H_post^T f
    (B, n, T, C)``, in float32, stored at the stream's dtype.  No
    ``optimization_barrier`` writes the stream out, as :func:`_close_block`
    writes a sandwich norm's sums: the rounding to the stream's dtype ends each
    sublayer's fusion (with these lines at every boundary an 8,192-token
    prompt's program held the same 1.195 GB of temporaries with a barrier here
    as without: its buffer assignment for a v5e; PERF.md, PR 53.  Since PR 56 a
    prompt's boundaries are :func:`hc_step`'s kernel and the program holds 0.991
    GB; these lines run a decode step's rows and whatever else falls back)."""
    h_post, h_res = maps
    n = x.shape[1]
    with scope("hc/close"):
        xf, ff = x.astype(jnp.float32), f.astype(jnp.float32)
        return jnp.stack([sum(h_res[i, j][..., None] * xf[:, j] for j in range(n)) + h_post[i][..., None] * ff
                          for i in range(n)], axis=1).astype(x.dtype)


_HC_MAPS = hc_maps      # whoever plants another (the benchmark's controls, the tests') gets what it planted


def hc_step(hp, xs, cfg: Config, *, sharded=False):
    """The stream through one boundary between two sublayers.  ``xs``: ``(x, owed)``,
    the stream ``(B, n, T, C)`` and the close it is still owed, ``(f, maps)``: what
    the sublayer that ended gave and the maps its open returned (None at the
    model's first open).  ``hp``: the hyper-connection of the sublayer that begins
    (None at the last close).  Returns ``(x', u, maps')``: the stream closed, what
    the sublayer reads of it (float32: its norm rounds it) and the maps its own
    close is owed (both None without ``hp``).

    Whole prompts and pieces take ``pallasex.hc_mix``, which reads the stream
    once for both halves (under ``hc/join``; ``hc/open`` or ``hc/close`` with one
    half absent); a decode step's rows, a mesh (``sharded``), a ``C`` that is
    not whole lane tiles, the CPU without the interpreter and a planted
    :func:`hc_maps` take :func:`hc_close` then :func:`hc_open`: the same numbers
    from XLA's fusions, and what the tests hold the kernel to."""
    from thunder_tpu.executors import pallasex

    x, owed = xs
    why = "mesh" if sharded else "planted maps" if hc_maps is not _HC_MAPS else ""
    with scope("hc/" + ("close" if hp is None else "open" if owed is None else "join")):
        fused = pallasex.hc_mix(x, owed, hp, eps=cfg.hc_eps, iters=cfg.hc_sinkhorn_iters, clamp=cfg.hc_res_clamp, why=why)
    if fused is not None:
        return fused
    if owed is not None:
        x = hc_close(x, *owed)
    return (x, *hc_open(hp, x, cfg)) if hp is not None else (x, None, None)


def require_servable(cfg: Config) -> None:
    """The one refusal of a config this module's forward cannot run (norms with
    zero-centred weights: ``Config.training_only``): such a model trains
    through ``tt.jit`` / ``make_train_step``; serving it is not built yet."""
    why = getattr(cfg, "training_only", None)
    if why:
        raise NotImplementedError(
            f"config {cfg.name!r} cannot be served by models.generate / tt.serve: {why}. "
            "It trains through tt.jit / distributed.make_train_step (llama.gpt_loss).")


def _close_block(bp, x, n1, h, cfg: Config, *, quantized=False, lora=None, lora_scaling=1.0, moe_rows=None, hc=None,
                 sharded=False):
    """A block from its mixer's output ``h`` on: the residual sums, the norms
    and the MLP, for every block layout (``n1``: the mixer's input, which a
    shared attention norm hands to the MLP too).  The dense cache's forward
    and the paged server's end their blocks here.

    A model of single sublayers (``cfg.single_sublayer``): a mixer layer ends in
    its residual sum; an "mlp" layer has no mixer (``h`` None) and is ``x +
    MLP(norm_1(x))``, all of it under the ``mlp`` scope.  ``moe_rows``: see
    :func:`moe_share_mlp`.  ``cfg.moe_route_block_input``: the expert layer's router
    reads ``x`` as it comes in, the block's input, beside the experts'
    ``norm_2(x + h)`` (its products keep the scope ``mlp/router``).
    ``hc``: under hyper-connections (``cfg.hc_mult`` > 1)
    ``x`` is the stream ``(B, n, T, C)`` and ``hc`` the maps the mixer's open
    returned beside its input; what comes back is the stream with the close the
    MLP leaves owed, ``(x, (f, maps))``, which the next layer's first open (or the
    head) joins to its own read of the stream (:func:`hc_step`; ``sharded`` as
    there)."""
    if cfg.single_sublayer and h is not None:
        with scope("mixer/residual"):
            return x + h
    mlp = partial(_mlp, bp["mlp"], cfg=cfg, quantized=quantized, lora=lora, lora_scaling=lora_scaling,
                  moe_rows=moe_rows, route_x=x if cfg.moe_route_block_input else None)
    if cfg.single_sublayer:
        with scope("mlp"):
            with scope("norm"):
                n = _norm(x, bp["norm_1"], cfg)
            m = mlp(n)
            with scope("residual"):
                return x + m
    if cfg.hc_mult > 1:                 # a hyper-connection where each residual sum of the pre-norm block stands
        with scope("mlp"):
            x, u, hc = hc_step(bp["hc_2"], (x, (h, hc)), cfg, sharded=sharded)
            with scope("norm"):
                n2 = _norm(u, bp["norm_2"], cfg, bp.get("norm_2_b")).astype(x.dtype)
            return x, (mlp(n2), hc)
    # each sublayer's norm and residual sum count with the sublayer
    if cfg.sandwich_norm:               # a norm on what each sublayer takes and on what it gives
        # A prompt's sums are each written out (``optimization_barrier``).  Given its row's one
        # statistic a norm is elementwise, so XLA would keep every sublayer's output of every layer to
        # the program's end and add them all up again wherever the stream is read: 64 arrays of
        # ``(T, C)`` at 32 layers, 2.6 GB of a 9,984-token prompt's program (its buffer assignment;
        # PERF.md, PR 48).  A decode step's rows are a few KB each and stay as XLA fuses them.
        written = jax.lax.optimization_barrier if x.shape[-2] > 1 else (lambda a: a)
        with scope("mixer/post_norm"):
            x = written(x + _norm(h, bp["norm_1_post"], cfg))
        with scope("mlp"):
            with scope("norm"):
                n2 = _norm(x, bp["norm_2"], cfg)
            m = mlp(n2)
            with scope("post_norm"):
                m = _norm(m, bp["norm_2_post"], cfg)
            with scope("residual"):
                return written(x + m)
    if cfg.post_sublayer_norm:          # OLMo: the norms sit on what the sublayers give
        with scope("mixer/norm"):
            x = x + _norm(h, bp["norm_1"], cfg)
        with scope("mlp"):
            m = mlp(x)
            with scope("norm"):
                return x + _norm(m, bp["norm_2"], cfg)
    with scope("mlp"):
        if cfg.parallel_residual:
            if cfg.shared_attention_norm:
                n2 = n1
            else:
                with scope("norm"):
                    n2 = _norm(x, bp["norm_2"], cfg, bp.get("norm_2_b"))
            with scope("residual"):
                xh = x + h
            m = mlp(n2)
            with scope("residual"):
                return xh + m
        with scope("residual"):     # the mixer's, summed where the MLP's norm reads it
            x = x + h
        with scope("norm"):
            n2 = _norm(x, bp["norm_2"], cfg, bp.get("norm_2_b"))
        m = mlp(n2)
        with scope("residual"):
            return x + m


def _to_streams(x, cfg: Config):
    """The embedding ``(B, T, C)`` as the residual stream: itself, or under
    hyper-connections copied to ``hc_mult`` streams ``(B, n, T, C)`` (arXiv:2409.19606),
    with the close it is owed, none yet: ``(x, None)``, as :func:`hc_step` takes it."""
    if cfg.hc_mult == 1:
        return x
    return jnp.broadcast_to(x[:, None], (x.shape[0], cfg.hc_mult, *x.shape[1:])), None


def _slab(a, j):
    """Layer ``j`` of a stacked cache: a slice where ``j`` is a Python integer (every
    one-pass model), a dynamic index where a looped model's pass number is traced."""
    return a[j] if isinstance(j, int) else jax.lax.dynamic_index_in_dim(a, j, 0, keepdims=False)


def close_pass(params, u, cfg: Config, t):
    """What closes pass ``t`` of a looped model: the last norm, after every pass."""
    return _norm(u, params["ln_f"], cfg, params.get("ln_f_b"))


def exit_rule(gates, cfg: Config):
    """A looped model's exit rule on the gates' logits ``(n_pass, ...)`` float32:
    ``lambda_t = sigmoid(g_t)``, ``p_t = lambda_t prod_{j<t} (1 - lambda_j)`` and the
    last pass takes what is left; a row exits at the first pass whose cumulative
    ``p`` reaches ``cfg.exit_threshold``, at the last where none does.  Returns
    ``(pass (...) int32, p (n_pass, ...))``."""
    lam = jax.nn.sigmoid(gates)
    stay = jnp.cumprod(1.0 - lam, axis=0)
    before = jnp.concatenate([jnp.ones_like(stay[:1]), stay[:-1]])      # prod_{j<t} (1 - lambda_j)
    p = jnp.concatenate([(lam * before)[:-1], before[-1:]])
    reached = jnp.cumsum(p, axis=0) >= cfg.exit_threshold
    last = gates.shape[0] - 1
    return jnp.where(jnp.any(reached, axis=0), jnp.argmax(reached, axis=0), last).astype(jnp.int32), p


def run_passes(params, x, cfg: Config, blocks, row=None):
    """A looped model's passes over the embedding ``x (B, T, C)``: ``blocks(h, t) ->
    (u, kept)`` (the stack on ``h`` with pass ``t``'s slabs of the cache, ``t`` traced)
    is the body of one ``lax.scan``, so the program holds the blocks once whatever
    ``cfg.n_pass``; :func:`close_pass` closes every pass and its output opens the
    next; the exit gate reads each closed state (of row ``row`` alone where given)
    and :func:`exit_rule` picks, a row, the pass whose state the head reads.  Every
    pass runs whatever the rule says: later tokens attend this token's K/V of every
    pass.  Returns ``(closed state (B, T or 1, C), kept stacked (n_pass, ...),
    (pass chosen (B, T or 1), exit probabilities (n_pass, B, T or 1)))``."""
    gate = params["exit_gate"]

    def one(h, t):
        u, kept = blocks(h, t)
        with scope("head/norm"):
            h = close_pass(params, u, cfg, t)
        with scope("head/gate"):
            r = h if row is None else jax.lax.dynamic_slice_in_dim(h, row, 1, axis=1)
            g = r.astype(jnp.float32) @ gate["w"].astype(jnp.float32) + gate["b"].astype(jnp.float32)
        return h, (kept, r, g)

    _, (kept, rows, gates) = jax.lax.scan(one, x, jnp.arange(cfg.n_pass, dtype=jnp.int32))
    with scope("head/exit"):
        chosen, p = exit_rule(gates, cfg)
        x = jnp.take_along_axis(rows, chosen[None, ..., None], axis=0)[0]
    return x, kept, (chosen, p)


def forward_with_cache(params, idx, pos, cache, cos_all, sin_all, cfg: Config, *,
                       quantized=False, lora=None, lora_scaling=1.0, n_real=None, logits_at=None,
                       sharded=False, exits=None):
    """Forward of new tokens ``idx`` (B, T) at global positions [pos, pos+T)
    against/into ``cache``.  Returns (logits (B, T, V), updated cache); with
    ``logits_at`` (an index into the T tokens, traced or not) the head runs on
    that one row alone and the logits are ``(B, 1, V)``: a prefill samples
    from its last real token and from nothing else.  ``sharded``: the caller
    placed params and cache on a mesh (:func:`_attn_with_cache`).

    A model with linear_attention layers keeps ``cache["conv"]`` and
    ``cache["state"]`` beside ``k``/``v`` (:func:`state_shapes`), which hold
    the full-attention layers only; they are the conv's last inputs and the
    delta rule's state *before* position ``pos``.  A model with conv layers
    keeps ``cache["conv"]`` alone (:func:`shortconv_mixer`'s tails); one with
    mamba2 layers the scan's ``state`` and the conv's tail (:func:`mamba2_mixer`).
    ``n_real``: how many of the
    T tokens are real (a padded prompt: the tail must leave the state alone).

    ``lora``: optional per-request LoRA factors —
    ``{target: {"a": (B, L, r, fin), "b": (B, L, fout, r)}}`` with one
    adapter per batch row (the layout
    :func:`serving.lora.gather_adapter_slots` produces); the delta
    ``lora_scaling * B(A(x))`` lands next to each target's matmul.

    A looped model (``cfg.n_pass`` > 1, :func:`run_passes`): the cache holds a
    slab a layer a pass (``cfg.kv_slab``), and ``exits`` (a list) takes the exit
    rule's ``(pass chosen, exit probabilities)`` of the rows the head read."""
    B, T = idx.shape
    vec = _is_vec_pos(pos)
    with scope("embed"):        # the tokens' rows, and their positions' rows of the rope tables
        x = params["wte"][idx]
        if cfg.scale_embedding:
            x = x * (cfg.n_embd ** 0.5)  # weak-typed scalar: multiply stays in x.dtype
        if cfg.learned_pos_embedding:
            if vec:
                x = x + jax.vmap(
                    lambda p: jax.lax.dynamic_slice_in_dim(params["wpe"], p, T, axis=0))(pos)
            else:
                x = x + jax.lax.dynamic_slice_in_dim(params["wpe"], pos, T, axis=0)
        x = _to_streams(x, cfg)
        if vec:
            # (B, 1, T, n_elem): broadcasts against (B, nh, T, hs) inside _rope
            cos_t = jax.vmap(lambda p: jax.lax.dynamic_slice_in_dim(cos_all, p, T, axis=0))(pos)[:, None]
            sin_t = jax.vmap(lambda p: jax.lax.dynamic_slice_in_dim(sin_all, p, T, axis=0))(pos)[:, None]
        else:
            cos_t = jax.lax.dynamic_slice_in_dim(cos_all, pos, T, axis=0)
            sin_t = jax.lax.dynamic_slice_in_dim(sin_all, pos, T, axis=0)

    lin = partial(_linear, quantized=quantized)
    # a model with a cross half (``cfg.cross_from``) whose caller wants one row's
    # logits runs that half on the one row: the layer the cross layers read
    # projects K and V on every position and its query on the row; the layers
    # after it keep no cache and see the row alone.  Exact, and half a prompt's products
    one_row = logits_at is not None and cfg.cross_from is not None

    def blocks(x, t):
        """The stack of blocks on ``x``, once: pass ``t`` of a looped model (its slabs of the
        cache, ``cfg.kv_slab``; traced there), the Python integer 0 for every other.  Returns the
        stream and what each kind of layer keeps, a list a kind."""
        new_k, new_v, new_conv, new_state, new_latent = [], [], [], [], []
        gmu_m = None
        for l, bp in enumerate(params["blocks"]):
            lora_l = None
            if lora:
                lora_l = {name: (ab["a"][:, l], ab["b"][:, l]) for name, ab in lora.items()}
            kind = cfg.layer_kind(l)
            if kind == "mlp":       # the layer is its feed-forward alone: no mixer, no cache
                with scope(f"blk{l}"):
                    x = _close_block(bp, x, None, None, cfg, quantized=quantized)
                continue
            with scope(f"blk{l}"):
                with scope("mixer"):
                    # OLMo's blocks norm what a sublayer gives, not what it takes
                    # under hyper-connections the sublayer reads a mixture of the streams
                    x, u, hc = hc_step(bp["hc_1"], x, cfg, sharded=sharded) if cfg.hc_mult > 1 else (x, x, None)
                    if cfg.post_sublayer_norm:
                        n1 = u
                    else:
                        with scope("norm"):     # (under hyper-connections ``u`` is float32: rounded here)
                            n1 = _norm(u, bp["norm_1"], cfg, bp.get("norm_1_b")).astype(x.dtype)
                    if kind == "mamba2":
                        j = len(new_state)
                        recur, box = mamba2_recur_dense(cache["state"][j])
                        h, tail = mamba2_mixer(bp["mamba2"], n1, cache["conv"][j], cfg, recur, n_real=n_real, lin=lin)
                        new_conv.append(tail)
                        new_state.append(box[0])
                    elif kind == "ssm":
                        j = len(new_state)
                        recur, box = ssm_recur_dense(cache["state"][j])
                        h, tail, m = ssm_mixer(bp["ssm"], n1, cache["conv"][j], cfg, recur, n_real=n_real, lin=lin)
                        new_conv.append(tail)
                        new_state.append(box[0])
                        if l == cfg.gmu_source:
                            gmu_m = m
                    elif kind == "gmu":
                        h = gmu_mixer(bp["gmu"], n1, gmu_m, lin=lin)
                    elif cfg.diff_attention:
                        j = len(new_k) if kind != "cross_attention" else cfg.kv_layers.index(cfg.cross_from)
                        src = cache if kind != "cross_attention" else {"k": new_k, "v": new_v}
                        narrow = one_row and l == cfg.cross_from
                        h, ck, cv = _diff_attn_with_cache(
                            bp["attn"], n1, l, src["k"][j], src["v"][j], pos, cfg, kind=kind, lin=lin, sharded=sharded,
                            row=logits_at if narrow or (one_row and kind == "cross_attention") else None)
                        if kind != "cross_attention":
                            new_k.append(ck)
                            new_v.append(cv)
                        if narrow:      # from here on the row alone
                            x, n1 = (jax.lax.dynamic_slice_in_dim(a, logits_at, 1, axis=1) for a in (x, n1))
                            gmu_m = None if gmu_m is None else jax.lax.dynamic_slice_in_dim(gmu_m, logits_at, 1, axis=1)
                    elif kind == "linear_attention":
                        j = len(new_state)
                        recur, box = gdn_recur_dense(cache["state"][j])
                        h, tail = gdn_mixer(bp["gdn"], n1, cache["conv"][j], cfg, recur, n_real=n_real, lin=lin)
                        new_conv.append(tail)
                        new_state.append(box[0])
                    elif kind == "conv":
                        h, tail = shortconv_mixer(bp["conv"], n1, cache["conv"][len(new_conv)], cfg,
                                                  n_real=n_real, lin=lin)
                        new_conv.append(tail)
                    elif cfg.latent:
                        h, cl = _mla_with_cache(bp["attn"], n1, cos_t, sin_t, cache["latent"][l], pos, cfg,
                                                quantized=quantized, sharded=sharded)
                        new_latent.append(cl)
                    else:
                        j = cfg.kv_slab(t, len(new_k))
                        h, ck, cv = _attn_with_cache(
                            bp["attn"], n1, cos_t, sin_t, _slab(cache["k"], j), _slab(cache["v"], j), pos, cfg,
                            quantized=quantized, lora=lora_l, lora_scaling=lora_scaling, sharded=sharded,
                            layer_window=cfg.layer_window if kind == "sliding_attention" else None, rope=cfg.rotates(l),
                        )
                        new_k.append(ck)
                        new_v.append(cv)
                x = _close_block(bp, x, n1, h, cfg, quantized=quantized, lora=lora_l, lora_scaling=lora_scaling, hc=hc,
                                 sharded=sharded)
        return x, (new_k, new_v, new_conv, new_state, new_latent)

    if cfg.n_pass > 1:      # a looped model: the stack as the body of one loop over the passes
        def kept(h, t):
            u, (new_k, new_v, *_) = blocks(h, t)
            with scope("mixer/cache"):
                return u, (jnp.stack(new_k), jnp.stack(new_v))

        x, (ks, vs), exit_ = run_passes(params, x, cfg, kept, row=logits_at)
        if exits is not None:
            exits.append(exit_)
        with scope("mixer/cache"):      # (n_pass, L, ...) is the slabs' own order
            cache = {"k": ks.reshape(-1, *ks.shape[2:]), "v": vs.reshape(-1, *vs.shape[2:])}
        return _head_logits(params, x, cfg, None, quantized, sharded=sharded, closed=True), cache
    x, (new_k, new_v, new_conv, new_state, new_latent) = blocks(x, 0)
    with scope("mixer/cache"):
        cache = {"latent": jnp.stack(new_latent)} if cfg.latent else {"k": jnp.stack(new_k), "v": jnp.stack(new_v)}
        if new_conv:
            cache.update(conv=jnp.stack(new_conv))
        if new_state:
            cache.update(state=jnp.stack(new_state))
    return _head_logits(params, x, cfg, None if one_row else logits_at, quantized, sharded=sharded), cache


def _head_logits(params, x, cfg: Config, logits_at, quantized, *, sharded=False, closed=False):
    """The last norm and the logits in float32, of row ``logits_at`` alone where
    given (``closed``: a looped model's state, which its pass's norm has closed
    already).  Under hyper-connections ``x`` comes with the last sublayer's close
    still owed (:func:`hc_step`): the row is cut out of the stream, of what the
    sublayer gave and of its maps first, so a prompt's last close is one row's;
    then the streams are summed (arXiv:2409.19606)."""
    if cfg.hc_mult > 1:
        with scope("head"):
            x, (f, maps) = x
            if logits_at is not None:
                row = lambda a, axis: jax.lax.dynamic_slice_in_dim(a, logits_at, 1, axis=axis)  # noqa: E731
                x, f, maps, logits_at = row(x, 2), row(f, 1), tuple(row(a, a.ndim - 1) for a in maps), None
            x = hc_step(None, (x, (f, maps)), cfg, sharded=sharded)[0]
        with scope("head/norm"):
            x = jnp.sum(x.astype(jnp.float32), axis=1).astype(x.dtype)
    if not closed:
        with scope("head/norm"):
            x = _norm(x, params["ln_f"], cfg, params.get("ln_f_b"))
            if logits_at is not None:
                x = jax.lax.dynamic_slice_in_dim(x, logits_at, 1, axis=1)
    with scope("head/logits"):
        head = params["wte"] if cfg.tie_embeddings else params["lm_head"]
        return (_linear(x, head, params.get("lm_head_b"), quantized=quantized)).astype(jnp.float32)


@scope("head/sample")
def sample_token(logits, temperature, key):
    """Greedy (``temperature == 0``) or temperature sampling over the last
    axis; ``temperature`` is static (baked into the compiled program)."""
    if temperature == 0.0:
        return jnp.argmax(logits, axis=-1).astype(jnp.int32)
    return jax.random.categorical(key, logits / temperature, axis=-1).astype(jnp.int32)


_sample = sample_token  # back-compat alias


def generate(
    params,
    prompt,
    cfg: Config,
    max_new_tokens: int,
    *,
    T_max: int | None = None,
    temperature: float = 0.0,
    key: jax.Array | None = None,
    quantized: bool = False,
    cache_dtype=None,
    mesh=None,
) -> jax.Array:
    """Greedy/temperature sampling.  ``prompt``: (B, T_prompt) int tokens.
    Returns (B, T_prompt + max_new_tokens).  Prefill is one compiled program;
    the entire decode loop is a second one (lax.scan over the cache).

    Tensor-parallel serving: pass ``mesh`` (with a ``tp`` axis) and params
    already placed with TP shardings (``distributed.tp_fsdp``) — the cache
    shards its KV-group dim, and XLA partitions the decode program from the
    input placements (per-head attention local, one reduce at the output
    projection)."""
    require_servable(cfg)
    prompt = jnp.asarray(prompt)
    B, T_prompt = prompt.shape
    assert max_new_tokens >= 0, max_new_tokens
    if max_new_tokens == 0:
        return prompt
    if T_max is None:
        T_max = min(cfg.block_size, T_prompt + max_new_tokens)
    assert T_prompt + max_new_tokens <= T_max, "T_max too small"
    if cfg.learned_pos_embedding:
        # wpe has block_size rows; dynamic_slice would silently clamp past it
        assert T_max <= cfg.block_size, (
            f"T_max {T_max} exceeds block_size {cfg.block_size} with learned position embeddings"
        )
    if key is None:
        key = jax.random.PRNGKey(0)
    dtype = cache_dtype if cache_dtype is not None else params["wte"].dtype

    prefill, decode_all = _compiled_generate(
        cfg, B, T_prompt, max_new_tokens, T_max, float(temperature), quantized, str(dtype),
        mesh is not None,
    )
    cache = init_cache(cfg, B, T_max, dtype=dtype, mesh=mesh)
    first, cache, key = prefill(params, prompt, cache, key)
    from thunder_tpu.executors.donation import suppress_unusable_donation_warnings

    # decode returns only tokens, so the donated cache can't alias an
    # output; the donation still frees it for scratch — the shared helper
    # silences jax's "donated buffers were not usable" note
    with suppress_unusable_donation_warnings():
        new_toks = decode_all(params, first, cache, key)
    return jnp.concatenate([prompt, new_toks], axis=1)


_generate_cache: dict = {}


def _compiled_generate(cfg, B, T_prompt, max_new_tokens, T_max, temperature, quantized, dtype_str,
                       sharded=False):
    """Jitted prefill/decode pair, cached per static configuration so
    repeated generate() calls hit steady-state compiled
    programs instead of re-tracing."""
    import dataclasses

    # of the mesh the key holds only that there is one (the prefill's flash
    # call has no partitioning rule, so a placed prompt keeps the einsum form):
    # jax.jit re-specializes on input shardings, so one cached pair serves
    # every placement
    key = (
        tuple(sorted(dataclasses.asdict(cfg).items())),
        B, T_prompt, max_new_tokens, T_max, temperature, quantized, dtype_str, sharded,
    )
    cached = _generate_cache.get(key)
    if cached is not None:
        return cached
    if len(_generate_cache) >= 16:  # LRU-ish bound for long-lived serving loops
        _generate_cache.pop(next(iter(_generate_cache)))

    cos_all, sin_all = build_rope_cache(cfg, T_max)

    @partial(jax.jit, donate_argnums=(2,))
    def prefill(params, prompt, cache, key):
        logits, cache = forward_with_cache(
            params, prompt, 0, cache, cos_all, sin_all, cfg, quantized=quantized,
            logits_at=T_prompt - 1, sharded=sharded,
        )
        key, sub = jax.random.split(key)
        nxt = _sample(logits[:, -1], temperature, sub)
        return nxt, cache, key

    @partial(jax.jit, donate_argnums=(2,))
    def decode_all(params, first, cache, key):
        def step(carry, _):
            tok, pos, cache, key = carry
            logits, cache = forward_with_cache(
                params, tok[:, None], pos, cache, cos_all, sin_all, cfg,
                quantized=quantized,
            )
            key, sub = jax.random.split(key)
            nxt = _sample(logits[:, -1], temperature, sub)
            return (nxt, pos + 1, cache, key), nxt

        # N-1 steps: `first` (sampled at prefill) is the first new token
        _, toks = jax.lax.scan(
            step, (first, T_prompt, cache, key), None, length=max_new_tokens - 1
        )
        return jnp.concatenate([first[:, None], toks.transpose(1, 0)], axis=1)

    _generate_cache[key] = (prefill, decode_all)
    return prefill, decode_all
