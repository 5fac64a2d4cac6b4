"""A sparse decoder with multi-head latent attention as A.X-K1's public
``config.json`` describes it (``model_type: axk1``, DeepSeek-V3's shape): every
layer attends through one compressed latent a token, the first layers keep a
dense SwiGLU, the others route each token to a few of many narrow experts
beside one shared expert.  This file is the benchmark's own copy of the
architecture: the weights it makes from the seed, the plain float32 reference
(expanded attention over the whole sequence: no cache, no absorbed weights,
no kernel, the experts by a mask), and the sizes the per-layer readers count
from.  It reads the published keys of the configuration file and imports
nothing of the program under test.

The equations (``C`` = hidden size, ``eps`` = ``rms_norm_eps``, no bias
anywhere, untied head).  ``rms_n(x) = x / sqrt(mean_n(x^2) + eps) * w``.

*Latent attention* (``nh`` heads; ``rq`` = ``q_lora_rank``, ``dc`` =
``kv_lora_rank``, ``dn`` = ``qk_nope_head_dim``, ``dr`` = ``qk_rope_head_dim``,
``dv`` = ``v_head_dim``), a token ``t``::

    c_q = rms_rq(W_qa x)                      q_h = W_qb,h c_q = [q_nope_h (dn) | q_rope_h (dr)]
    [c_kv | k_r] = W_kva x  (dc | dr)         c_kv <- rms_dc(c_kv)
    q_rope_h <- rope(q_rope_h, t)             k_r <- rope(k_r, t), one for all heads
    [k_nope_h | v_h](u) = W_kvb,h c_kv(u)     (dn | dv)
    s_h(t, u) = (q_nope_h(t) . k_nope_h(u) + q_rope_h(t) . k_r(u)) * scale,   u <= t
    o_h(t) = sum_u softmax_u(s_h(t, .)) v_h(u)          y = W_o concat_h(o_h)   (nh dv -> C)
    scale = (dn + dr)^-1/2 * m^2,   m = 0.1 * mscale_all_dim * ln(factor) + 1

*YaRN* over the ``dr`` rotary dims (``theta`` = ``rope_theta``; ``factor``,
``original_max_position_embeddings`` = ``orig``, ``beta_fast``, ``beta_slow``)::

    f_i = theta^(-2i/dr), i = 0 .. dr/2 - 1;   dim(b) = dr ln(orig / (2 pi b)) / (2 ln theta)
    low = max(floor(dim(beta_fast)), 0),  high = min(ceil(dim(beta_slow)), dr - 1)
    ramp_i = clip((i - low) / (high - low), 0, 1);   inv_freq_i = f_i (1 - ramp_i) + f_i / factor * ramp_i
    cos, sin of t * inv_freq, times mscale(factor, mscale) / mscale(factor, mscale_all_dim)  (1 here)

``rope`` rotates halves (``x cos + [-x2 | x1] sin``).  *Departure:* hf's
DeepSeek code rotates interleaved pairs after a permutation of the rotary
dims; with random weights the two are the same distribution (``assumed``).

*Feed-forward.*  Layers ``< first_k_dense_replace``: SwiGLU of width
``intermediate_size``, ``W_down (silu(W_gate h) * W_up h)``.  The others::

    s = sigmoid(W_g h)  in float32 over all ``published_n_routed_experts``
    groups of E / n_group experts; a group's score the sum of its two best s
    the best topk_group groups kept; the top num_experts_per_tok of their experts
    w_i = s_i / (sum of the chosen s + 1e-20) * routed_scaling_factor
    y = sum_{i chosen and held} w_i E_i(h) + S(h)

``E_i`` and ``S`` are SwiGLUs of width ``moe_intermediate_size`` (``S``:
``n_shared_experts`` times it), ``S`` ungated.  *Departure (the share):* this
chip holds experts ``[expert_first, expert_first + n_routed_experts)`` of the
published count; the router scores all of them and the sum runs over the held
ones, what the others would add is left out here as in the program.
*Departure:* ``topk_method: "none"`` is read as no score-correction bias
(DeepSeek-V3's ``noaux_tc`` adds one before the choice) with the group-limited
choice kept (``assumed``).

*Block*: ``h = x + attn(rms_C(x))``, ``out = h + ffn(rms_C(h))``; after the
last block one more ``rms_C``, then the head.

Weight layout (what the program is handed; a linear layer is ``x @ W.T``
unless said otherwise): ``wte (V, C)``, ``lm_head (V, C)``, ``ln_f (C)``; a
block: ``norm_1``, ``norm_2 (C)``; ``attn.wq_a (rq, C)``, ``attn.q_norm
(rq)``, ``attn.wq_b (nh (dn + dr), rq)``, ``attn.wkv_a (dc + dr, C)``,
``attn.kv_norm (dc)``, ``attn.wkv_b (nh (dn + dv), dc)`` (a head's ``dn`` key
rows, then its ``dv`` value rows), ``attn.wo (C, nh dv)``; a dense layer's
``mlp.fc_1/fc_2 (I, C)`` (gate, up), ``mlp.proj (C, I)``; an expert layer's
``mlp.gate (E, C)`` over all E, ``mlp.fc_1/fc_2 (held C, Im)`` and ``mlp.proj
(held Im, C)`` (the held experts stacked, ``x @ W`` layout: expert ``e`` is
rows ``[e C, (e + 1) C)``), ``mlp.shared.fc_1/fc_2 (Is, C)``,
``mlp.shared.proj (C, Is)``.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

from chipbench.common import seed_key

OUTSIDE_BLOCKS = 0xFFFF   # the place folded into the keys of the embedding and the head
ROWS = 256                # rows of an expert and of attention's queries at a time
HEADS = 16                # heads whose queries, keys and values are made at a time
COLUMNS = 2048            # hidden columns of a SwiGLU whose weights are upcast at a time


def sizes(hf: dict) -> dict:
    rope = dict(hf["rope_scaling"])
    return {"C": hf["hidden_size"], "nh": hf["num_attention_heads"], "L": hf["num_hidden_layers"],
            "V": hf["vocab_size"], "rq": hf["q_lora_rank"], "dc": hf["kv_lora_rank"],
            "dn": hf["qk_nope_head_dim"], "dr": hf["qk_rope_head_dim"], "dv": hf["v_head_dim"],
            "I": hf["intermediate_size"], "Im": hf["moe_intermediate_size"],
            "Is": hf["n_shared_experts"] * hf["moe_intermediate_size"],
            "E": hf.get("published_n_routed_experts", hf["n_routed_experts"]), "held": hf["n_routed_experts"],
            "first": hf.get("expert_first", 0), "k": hf["num_experts_per_tok"],
            "n_group": hf["n_group"], "topk_group": hf["topk_group"], "route_scale": hf["routed_scaling_factor"],
            "dense": hf["first_k_dense_replace"], "theta": float(hf["rope_theta"]), "rope": rope,
            "eps": hf["rms_norm_eps"], "std": hf["initializer_range"]}


def attn_scale(s: dict) -> float:
    m = 0.1 * s["rope"]["mscale_all_dim"] * math.log(s["rope"]["factor"]) + 1.0
    return (s["dn"] + s["dr"]) ** -0.5 * m * m


def program_config(hf: dict) -> dict:
    """The published keys under the names ``thunder_tpu.models.llama.Config``
    gives them."""
    s = sizes(hf)
    yarn = {k: v for k, v in s["rope"].items() if k != "type"}
    return dict(name=hf["model_name"], block_size=hf["max_position_embeddings"], vocab_size=s["V"],
                n_layer=s["L"], n_head=s["nh"], n_embd=s["C"], norm_eps=s["eps"], rope_base=int(s["theta"]),
                rope_scaling_yarn=yarn, q_lora_rank=s["rq"], kv_lora_rank=s["dc"], qk_nope_head_dim=s["dn"],
                qk_rope_head_dim=s["dr"], v_head_dim=s["dv"],
                mlp_class="SparseMoE", intermediate_size=s["Im"], n_expert=s["E"], n_expert_per_token=s["k"],
                expert_first=s["first"], expert_held=s["held"], shared_expert_size=s["Is"],
                shared_expert_gate=False, moe_router="sigmoid_group", n_group=s["n_group"],
                topk_group=s["topk_group"], routed_scaling_factor=s["route_scale"],
                first_k_dense=s["dense"], dense_intermediate_size=s["I"])


def attn_params(hf: dict) -> int:
    s = sizes(hf)
    return (s["C"] * s["rq"] + s["rq"] + s["rq"] * s["nh"] * (s["dn"] + s["dr"]) + s["C"] * (s["dc"] + s["dr"])
            + s["dc"] + s["dc"] * s["nh"] * (s["dn"] + s["dv"]) + s["nh"] * s["dv"] * s["C"])


def expert_params(hf: dict) -> int:
    s = sizes(hf)
    return 3 * s["C"] * s["Im"]


def layer_params(hf: dict, i: int) -> int:
    s = sizes(hf)
    if i < s["dense"]:
        ffn = 3 * s["C"] * s["I"]
    else:
        ffn = s["E"] * s["C"] + s["held"] * expert_params(hf) + 3 * s["C"] * s["Is"]
    return attn_params(hf) + ffn + 2 * s["C"]


def param_count(hf: dict) -> int:
    s = sizes(hf)
    return sum(layer_params(hf, i) for i in range(s["L"])) + 2 * s["V"] * s["C"] + s["C"]


def latent_bytes_per_token(hf: dict, itemsize: int = 2) -> int:
    """What the cache keeps a token: a latent and a rotated key a layer."""
    s = sizes(hf)
    return s["L"] * (s["dc"] + s["dr"]) * itemsize


def make_params(hf: dict, seed_words, dtype=jnp.bfloat16) -> dict:
    """Random weights from the seed (``common.seed_words``, an argument of the
    caller's ``jit``, not a constant of it), made where that ``jit`` puts
    them.  Each leaf's key is folded from its place in the model, so layer
    ``i`` is the same whatever the depth, and a held expert from its number
    among all the experts, so expert ``e`` is the same whatever the share.
    Matrices normal of std ``initializer_range``; norms 1."""
    s = sizes(hf)
    root = seed_key(seed_words)
    key = lambda tag, layer: jax.random.fold_in(jax.random.fold_in(root, layer), tag)  # noqa: E731

    def dense(tag: int, layer: int, fan_out: int, fan_in: int):
        return (jax.random.normal(key(tag, layer), (fan_out, fan_in), jnp.float32) * s["std"]).astype(dtype)

    def experts(tag: int, layer: int, fan_in: int, fan_out: int):
        """The held experts' matrices, ``x @ W`` layout, stacked on rows: ``(held fan_in, fan_out)``."""
        one = lambda e: (jax.random.normal(jax.random.fold_in(key(tag, layer), e), (fan_in, fan_out),  # noqa: E731
                                           jnp.float32) * s["std"]).astype(dtype)
        return jnp.concatenate([one(s["first"] + e) for e in range(s["held"])], axis=0)

    ones = lambda n: jnp.ones((n,), dtype)  # noqa: E731
    blocks = []
    for i in range(s["L"]):
        attn = {"wq_a": dense(0, i, s["rq"], s["C"]), "q_norm": ones(s["rq"]),
                "wq_b": dense(1, i, s["nh"] * (s["dn"] + s["dr"]), s["rq"]),
                "wkv_a": dense(2, i, s["dc"] + s["dr"], s["C"]), "kv_norm": ones(s["dc"]),
                "wkv_b": dense(3, i, s["nh"] * (s["dn"] + s["dv"]), s["dc"]),
                "wo": dense(4, i, s["C"], s["nh"] * s["dv"])}
        if i < s["dense"]:
            mlp = {"fc_1": dense(5, i, s["I"], s["C"]), "fc_2": dense(6, i, s["I"], s["C"]),
                   "proj": dense(7, i, s["C"], s["I"])}
        else:
            mlp = {"gate": dense(8, i, s["E"], s["C"]),
                   "fc_1": experts(9, i, s["C"], s["Im"]), "fc_2": experts(10, i, s["C"], s["Im"]),
                   "proj": experts(11, i, s["Im"], s["C"]),
                   "shared": {"fc_1": dense(12, i, s["Is"], s["C"]), "fc_2": dense(13, i, s["Is"], s["C"]),
                              "proj": dense(14, i, s["C"], s["Is"])}}
        blocks.append({"norm_1": ones(s["C"]), "attn": attn, "norm_2": ones(s["C"]), "mlp": mlp})
    return {"wte": dense(0, OUTSIDE_BLOCKS, s["V"], s["C"]), "blocks": blocks,
            "ln_f": ones(s["C"]), "lm_head": dense(1, OUTSIDE_BLOCKS, s["V"], s["C"])}


# --------------------------------------------------------------------------
# The plain reference: float32, highest matmul precision, no kernel, no cache
# --------------------------------------------------------------------------

def _lin(x, w):
    return x @ w.astype(jnp.float32).T


def _rms(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * w.astype(jnp.float32)


def yarn_inv_freq(s: dict):
    """``inv_freq (dr / 2,)`` float32 and the factor cos and sin carry."""
    dr, r = s["dr"], s["rope"]
    f = s["theta"] ** (-jnp.arange(0, dr, 2, dtype=jnp.float32) / dr)
    dim = lambda b: dr * math.log(r["original_max_position_embeddings"] / (b * 2 * math.pi)) / (2 * math.log(s["theta"]))  # noqa: E731
    low, high = max(math.floor(dim(r["beta_fast"])), 0), min(math.ceil(dim(r["beta_slow"])), dr - 1)
    ramp = jnp.clip((jnp.arange(dr // 2, dtype=jnp.float32) - low) / max(high - low, 0.001), 0.0, 1.0)
    mscale = lambda m: 0.1 * m * math.log(r["factor"]) + 1.0  # noqa: E731
    return f * (1.0 - ramp) + f / r["factor"] * ramp, mscale(r["mscale"]) / mscale(r["mscale_all_dim"])


def rope_tables(s: dict, seq_len: int):
    """cos and sin ``(T, dr)`` in float32, rotate-half layout."""
    inv, mag = yarn_inv_freq(s)
    ang = jnp.outer(jnp.arange(seq_len, dtype=jnp.float32), inv)
    ang = jnp.concatenate([ang, ang], axis=-1)
    return jnp.cos(ang) * mag, jnp.sin(ang) * mag


def _rotate(x, cos, sin):
    half = x.shape[-1] // 2
    return x * cos + jnp.concatenate([-x[..., half:], x[..., :half]], axis=-1) * sin


def _by_rows(fn, x, *rest):
    """``fn(rows, first_row, *rest)`` over blocks of ``ROWS`` rows of ``x``, one
    block after another: the intermediates of one block at a time."""
    T = x.shape[0]
    n = ROWS if T % ROWS == 0 else T
    blocks = x.reshape(T // n, n, *x.shape[1:])
    out = jax.lax.map(lambda a: fn(a[0], a[1], *rest), (blocks, jnp.arange(T // n) * n))
    return out.reshape(T, *out.shape[2:])


def latent(x, ap, cos, sin, s):
    """What a cache would hold of every token: ``[rms(c_kv) | rope(k_r)] (T, dc + dr)``."""
    kv = _lin(x, ap["wkv_a"])
    return jnp.concatenate([_rms(kv[:, :s["dc"]], ap["kv_norm"], s["eps"]), _rotate(kv[:, s["dc"]:], cos, sin)], axis=-1)


def _attend(qb, q0, k, v, scale):
    """Causal softmax attention of a block of queries ``(t, h, dn + dr)``, the
    first at position ``q0``, over all the keys ``(S, h, dn + dr)``."""
    keep = jnp.arange(k.shape[0])[None, :] <= (q0 + jnp.arange(qb.shape[0]))[:, None]
    sc = jnp.where(keep[None], jnp.einsum("thd,shd->hts", qb, k) * scale, -jnp.inf)
    return jnp.einsum("hts,shd->thd", jax.nn.softmax(sc, axis=-1), v)


def _attention(x, ap, cos, sin, s):
    """Expanded form: every head's keys and values made from the latents, a
    group of ``HEADS`` heads at a time."""
    T, nh, dn, dr, dv, dc = x.shape[0], s["nh"], s["dn"], s["dr"], s["dv"], s["dc"]
    c_q = _rms(_lin(x, ap["wq_a"]), ap["q_norm"], s["eps"])
    lat = latent(x, ap, cos, sin, s)
    c_kv, k_r = lat[:, :dc], lat[:, dc:]
    hb = HEADS if nh % HEADS == 0 else nh
    w_q = ap["wq_b"].reshape(nh // hb, hb, dn + dr, s["rq"])
    w_kv = ap["wkv_b"].reshape(nh // hb, hb, dn + dv, dc)

    def heads(a):
        wq, wkv = a                                            # (hb, dn + dr, rq), (hb, dn + dv, dc)
        q = jnp.einsum("tr,hdr->thd", c_q, wq.astype(jnp.float32))
        q = jnp.concatenate([q[..., :dn], _rotate(q[..., dn:], cos[:, None], sin[:, None])], axis=-1)
        kv = jnp.einsum("sc,hdc->shd", c_kv, wkv.astype(jnp.float32))
        k = jnp.concatenate([kv[..., :dn], jnp.broadcast_to(k_r[:, None], (T, hb, dr))], axis=-1)
        return _by_rows(functools.partial(_attend, scale=attn_scale(s)), q, k, kv[..., dn:])

    o = jax.lax.map(heads, (w_q, w_kv))                        # (nh / hb, T, hb, dv)
    return _lin(o.swapaxes(0, 1).reshape(T, nh * dv), ap["wo"])


def _swiglu(h, mp):
    """``W_down (silu(W_gate h) * W_up h)``, ``COLUMNS`` hidden columns at a
    time: the float32 copy of that many columns' weights, and no more."""
    I = mp["fc_1"].shape[0]
    n = COLUMNS if I % COLUMNS == 0 else I
    f32 = lambda w: w.astype(jnp.float32)  # noqa: E731

    def columns(y, c):
        w1, w2 = (jax.lax.dynamic_slice_in_dim(mp[k], c * n, n, axis=0) for k in ("fc_1", "fc_2"))
        w3 = jax.lax.dynamic_slice_in_dim(mp["proj"], c * n, n, axis=1)
        return y + (jax.nn.silu(h @ f32(w1).T) * (h @ f32(w2).T)) @ f32(w3).T, None

    return jax.lax.scan(columns, jnp.zeros_like(h), jnp.arange(I // n))[0]


def route(h, gate, s):
    """The group-limited choice: ``(weights (T, k), experts (T, k))`` over all E."""
    T, E, G = h.shape[0], s["E"], s["n_group"]
    sc = jax.nn.sigmoid(_lin(h, gate))
    grouped = sc.reshape(T, G, E // G)
    group_score = jnp.sum(jax.lax.top_k(grouped, 2)[0], axis=-1)
    kept = jnp.zeros((T, G), bool).at[jnp.arange(T)[:, None], jax.lax.top_k(group_score, s["topk_group"])[1]].set(True)
    _, idx = jax.lax.top_k(jnp.where(kept[:, :, None], grouped, 0.0).reshape(T, E), s["k"])
    w = jnp.take_along_axis(sc, idx, axis=-1)
    return w / (jnp.sum(w, axis=-1, keepdims=True) + 1e-20) * s["route_scale"], idx


def _expert_share(h, mp, s):
    """The held experts by a mask: every token through every held expert, one
    expert at a time, weighted by what the router gave it (zero if not chosen)."""
    C, Im, held = s["C"], s["Im"], s["held"]
    w, idx = route(h, mp["gate"], s)
    fc_1, fc_2, proj = (mp["fc_1"].reshape(held, C, Im), mp["fc_2"].reshape(held, C, Im),
                        mp["proj"].reshape(held, Im, C))

    def one(y, a):
        e, w1, w2, w3 = a
        we = jnp.sum(jnp.where(idx == s["first"] + e, w, 0.0), axis=-1)                  # (T,)
        f32 = lambda m: m.astype(jnp.float32)  # noqa: E731
        rows = lambda hb, _: (jax.nn.silu(hb @ f32(w1)) * (hb @ f32(w2))) @ f32(w3)  # noqa: E731
        return y + we[:, None] * _by_rows(rows, h), None

    y, _ = jax.lax.scan(one, jnp.zeros_like(h), (jnp.arange(held), fc_1, fc_2, proj))
    return y + _swiglu(h, mp["shared"])


def _block(x, bp, cos, sin, s, dense):
    """One block on ``x (T, C)`` float32."""
    h = x + _attention(_rms(x, bp["norm_1"], s["eps"]), bp["attn"], cos, sin, s)
    n2 = _rms(h, bp["norm_2"], s["eps"])
    return h + (_swiglu(n2, bp["mlp"]) if dense else _expert_share(n2, bp["mlp"], s))


def _hf_items(hf: dict) -> tuple:
    flat = {k: (tuple(sorted(v.items())) if k == "rope_scaling" else v) for k, v in hf.items()}
    return tuple(sorted((k, v) for k, v in flat.items() if not isinstance(v, (dict, list))))


@functools.partial(jax.jit, static_argnames=("hf_items", "dense"))
def _ref_block(x, bp, *, hf_items, dense):
    with jax.default_matmul_precision("highest"):
        s = sizes(dict(hf_items))
        cos, sin = rope_tables(s, x.shape[0])
        return _block(x, bp, cos, sin, s, dense)


@functools.partial(jax.jit, static_argnames=("hf_items",))
def _ref_latent(x, bp, *, hf_items):
    with jax.default_matmul_precision("highest"):
        s = sizes(dict(hf_items))
        cos, sin = rope_tables(s, x.shape[0])
        return latent(_rms(x, bp["norm_1"], s["eps"]), bp["attn"], cos, sin, s)


@functools.partial(jax.jit, static_argnames=("eps",))
def _ref_logits(x, ln_f, head, *, eps):
    with jax.default_matmul_precision("highest"):
        return _rms(x, ln_f, eps) @ head.astype(jnp.float32).T


def ref_hidden(hf: dict, params: dict, tokens):
    """Final hidden states ``(T, C)`` before the last norm, for one sequence
    of token ids ``(T,)``.  One block's weights are upcast at a time, an
    expert's inside it."""
    items, dense = _hf_items(hf), sizes(hf)["dense"]
    x = params["wte"][tokens].astype(jnp.float32)
    for i, bp in enumerate(params["blocks"]):
        x = _ref_block(x, bp, hf_items=items, dense=i < dense)
    return x


def ref_latents(hf: dict, params: dict, tokens, n_real: int):
    """What a server's cache holds of one sequence after its first ``n_real``
    tokens (the rest of ``tokens`` is padding), a layer at a time: ``(n_real,
    dc + dr)`` float32, the normed latent and the rotated key.  A generator."""
    items, dense = _hf_items(hf), sizes(hf)["dense"]
    x = params["wte"][tokens].astype(jnp.float32)
    for i, bp in enumerate(params["blocks"]):
        yield _ref_latent(x, bp, hf_items=items)[:n_real]
        x = _ref_block(x, bp, hf_items=items, dense=i < dense)


def ref_logits(hf: dict, params: dict, tokens, positions):
    """Logits ``(len(positions), V)`` of the full forward pass at the given
    positions of one sequence."""
    x = ref_hidden(hf, params, tokens)
    return _ref_logits(x[positions], params["ln_f"], params["lm_head"], eps=hf["rms_norm_eps"])
