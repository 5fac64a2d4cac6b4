"""Paged-attention decode: the serving forward that never densifies the KV.

:func:`forward_paged` runs the same per-layer math as
``models.generate.forward_with_cache`` (norms, QKV projection + rope, LoRA
deltas, MLP, head) but attention reads K/V **directly from the arena** via
``executors.pallasex.paged_attn_decode`` (flash-decoding over the block
table, positional keep-mask and int8/fp8 dequant fused in-kernel), and
:func:`write_fresh_kv` lands the step's fresh K/V in place via
``paged_token_write`` — so the compiled decode program contains zero
gather/scatter primitives (asserted in tests/test_paged_attention.py).  It is
the one decode program: where the kernel does not run (Pallas off, or an
arena it does not take: :func:`decode_path`) the attention call is its XLA
form, ``pallasex.paged_attn_xla``, which gathers the rows' blocks of one layer
and ends in the dense cache's own attention.

Parity contract (the serving bit-exactness bar): the kernel scores the
arena's strictly-older slots and folds the *fresh* token — at the cache
compute dtype, exactly what the dense cache would have just written — as the
final online-softmax term, so greedy/temperature tokens match solo
``generate()`` across f32/bf16 caches, int8/fp8 KV, LoRA mixes, and meshes.
Quantization happens with ``quant.quantize_kv``'s math, so stored bytes are
what ``scatter_token_q`` would store.

Mesh: the kernels are plain ``pallas_call``s with no SPMD rule, so under a
mesh each call is wrapped in ``jax.shard_map`` over the ``tp`` axis with
heads-local specs matching ``distributed.kv_cache_spec`` (arena heads at
axis 2, query heads at axis 1) — attention stays device-local.  Where the
heads do not split over ``tp`` (``kv_cache_spec`` replicates the arena then)
the attention call is the XLA form outside ``shard_map`` and the writers run
on each device's whole copy.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from thunder_tpu.executors.pallasex import (
    gdn_decode_step,
    lora_delta_fused,
    mla_paged_decode,
    paged_attn_decode,
    paged_attn_verify,
    paged_attn_xla,
    paged_chunk_write,
    paged_chunk_write_fused,
    paged_decode_path,
    paged_token_write,
    paged_token_write_fused,
    ssd_decode_step,
    ssm_decode_step,
)
from thunder_tpu.models.generate import (
    diff_attention,
    gated_out,
    gdn_mixer,
    gmu_mixer,
    kv_lane_pack,
    ring_blocks,
    mamba2_mixer,
    ssm_mixer,
    shortconv_mixer,
    mla_absorb,
    mla_mixer,
    mla_unabsorb,
    pad_lanes,
    hc_step,
    _close_block,
    _head_logits,
    _linear,
    _lora_delta,
    _norm,
    _project_qkv,
    _to_streams,
    run_passes,
)
from thunder_tpu.observability.events import scope
from thunder_tpu.serving.kv_pool import pack_state_heads, ring_tables, unpack_state_heads
from thunder_tpu.serving.quant import quantize_kv

__all__ = ["forward_paged", "with_state", "write_fresh_kv",
           "write_fresh_kv_masked", "write_fresh_kv_chunk", "decode_path"]


def _smap(fn, mesh, in_specs, out_specs):
    """shard_map through the repo's one entry (replication checking off)."""
    from thunder_tpu.distributed.prims import shard_map_compat

    return shard_map_compat(fn, mesh=mesh, in_specs=in_specs, out_specs=out_specs)


def _tp_axis(mesh, *heads) -> str | None:
    """``"tp"`` where the mesh's ``tp`` axis splits head counts ``heads`` the way
    ``kv_cache_spec`` lays the arena out (the kernels then run on a device's own
    heads), else None: the arena is replicated, and a spec that names ``tp``
    would disagree with it."""
    if "tp" in mesh.axis_names and all(h % int(mesh.shape["tp"]) == 0 for h in heads):
        return "tp"
    return None


def decode_path(cfg, mesh=None, arena_lanes: int | None = None) -> str:
    """The form the decode program's attention call takes for this config:
    ``"walk"``, ``"by_blocks"`` or ``"xla"`` (``pallasex.paged_decode_path`` of the
    arena's rows: ``arena_lanes``, the pool's where the caller has built one,
    else what the pool would lay out on one device: a head of whole tiles, or
    a head that divides 128 with its KV heads in whole rows, lane-packed), and
    ``"xla"`` under a mesh whose ``tp`` axis does not split the heads, and for a
    looped model whose head the walk does not take."""
    if mesh is not None and _tp_axis(mesh, cfg.n_head, cfg.n_query_groups) is None:
        return "xla"
    if arena_lanes is None:
        arena_lanes = cfg.head_size * (1 if mesh is not None else kv_lane_pack(cfg))
    path = paged_decode_path(arena_lanes, cfg.sliding_window)
    # a block a grid step takes the layer as a constant of its index maps; a looped model's slab is traced
    return "xla" if path == "by_blocks" and cfg.n_pass > 1 else path


def _attn_paged(q, arenas, fresh_k, fresh_v, tables, pos, *, layer, mesh, window=None):
    """One layer's attention call: ``q (B, nh, hs)`` through
    ``paged_attn_decode``, ``(B, nh, T, hs)`` (a draft's verify, a piece of a
    prompt; no window) through ``paged_attn_verify``.  Under a mesh it is
    shard_map-wrapped (specs match ``kv_cache_spec``: arena/scale heads at axis
    2, q/fresh heads at axis 1, a query-position axis riding along unsharded);
    where ``tp`` does not split the heads, the XLA form on the arrays as GSPMD
    holds them."""
    multi = q.ndim == 4
    scales = {name: arenas[name] for name in ("k_scale", "v_scale") if name in arenas}
    entry = paged_attn_verify if multi else partial(paged_attn_decode, window=window)
    if mesh is None:
        return entry(q, arenas["k"], arenas["v"], fresh_k, fresh_v, tables, pos, layer=layer, **scales)
    tp = _tp_axis(mesh, q.shape[1], arenas["k"].shape[2])
    if tp is None:
        expand = (lambda x: x) if multi else (lambda x: x[:, :, None])
        out = paged_attn_xla(expand(q), arenas["k"], arenas["v"], expand(fresh_k), expand(fresh_v), tables, pos,
                             layer=layer, window=window, **scales)
        return out if multi else out[:, :, 0]
    hspec = P(None, tp, None, None) if multi else P(None, tp, None)    # (B, heads[, T], hs)
    aspec = P(None, None, tp, None, None)                              # (nb, L, ng, bs, hs)
    sspec = P(None, None, tp, None)                                    # (nb, L, ng, bs)
    names = tuple(scales)

    def local(q_, ka, va, fk, fv, t, p, *sc):
        return entry(q_, ka, va, fk, fv, t, p, layer=layer, **dict(zip(names, sc)))

    in_specs = (hspec, aspec, aspec, hspec, hspec, P(None, None), P(None)) + (sspec,) * len(names)
    return _smap(local, mesh, in_specs, hspec)(
        q, arenas["k"], arenas["v"], fresh_k, fresh_v, tables, pos, *scales.values())


def _gdn_paged(gp, x, arenas, sslots, pos, cfg, *, layer, n_real, lin):
    """A linear_attention layer of :func:`forward_paged`: ``generate.gdn_mixer``
    (the one mixer; the dense cache calls it too) with the state where the
    server keeps it.  A decode step (T = 1) runs ``gdn_decode_step`` on the
    state arena in place; a piece of a prompt (one row) runs the chunked scan
    from the slot's state, from zeros where the piece starts at position 0,
    (the arena's rows taken apart into heads, ``kv_pool.unpack_state_heads``),
    and writes the last state back.  Returns ``(y, state arena, conv arena)``."""
    from thunder_tpu.executors import jaxex

    T = x.shape[1]
    held = {"state": arenas["state"]}
    with scope("cache"):
        tail = arenas["conv"][sslots, layer]                         # (B, K - 1, channels)
        fresh = (pos == 0) if T > 1 else None
        if fresh is not None:
            tail = jnp.where(fresh[:, None, None], jnp.zeros_like(tail), tail)

    def recur(q, k, v, g, beta):
        if T == 1:
            o, held["state"] = gdn_decode_step(held["state"], sslots, q[:, :, 0], k[:, :, 0], v[:, :, 0],
                                               g[:, :, 0], beta[:, :, 0], layer=layer)
            return o[:, :, None]
        with scope("cache"):
            h0 = unpack_state_heads(held["state"][sslots, layer], v.shape[1])
            h0 = jnp.where(fresh[:, None, None, None], jnp.zeros_like(h0), h0)
        o, last = jaxex.gdn_chunk_state(q, k, v, g, beta, h0)
        with scope("cache"):
            held["state"] = held["state"].at[sslots, layer].set(pack_state_heads(last))
        return o

    y, new_tail = gdn_mixer(gp, x, tail, cfg, recur, n_real=n_real, lin=lin)
    with scope("cache"):
        return y, held["state"], arenas["conv"].at[sslots, layer].set(new_tail)


def _conv_paged(cp, x, conv_arena, sslots, pos, cfg, *, layer, n_real, lin):
    """A conv layer of :func:`forward_paged`: ``generate.shortconv_mixer`` (the
    one mixer; the dense cache calls it too) with the tails where the server
    keeps them: a row's slot of the conv arena, zeros where a piece of a prompt
    starts at position 0.  Returns ``(y, conv arena)``."""
    with scope("cache"):
        tail = conv_arena[sslots, layer]                             # (B, K - 1, C)
        if x.shape[1] > 1:
            tail = jnp.where((pos == 0)[:, None, None], jnp.zeros_like(tail), tail)
    y, new_tail = shortconv_mixer(cp, x, tail, cfg, n_real=n_real, lin=lin)
    with scope("cache"):
        return y, conv_arena.at[sslots, layer].set(new_tail)


def _mla_paged(ap, x, arena, tables, pos, cos_t, sin_t, cfg, *, layer, cdtype, lin):
    """A latent-attention layer of :func:`forward_paged`, one token a row:
    ``generate.mla_mixer`` (the one mixer; the dense cache calls it too) with
    the rows where the server keeps them.  The absorbed queries attend the
    row's blocks of the latent arena through ``mla_paged_decode`` (every head
    reads the same rows, fetched once) and the heads' weighted latents go
    through ``W_v``.  Returns ``(y, this step's rows (B, W))`` at the cache
    compute dtype, padded as the arena's rows are."""
    if x.shape[1] != 1:
        raise NotImplementedError(
            "a latent cache is attended one token a row (mla_paged_decode); a piece of a prompt or a "
            "draft's verify has no multi-query latent kernel and goes through the dense form")
    W = arena.shape[-1]
    box = []

    def attend(q_nope, q_rope, latent):
        with scope("cache"):
            row = pad_lanes(latent[:, 0], W).astype(cdtype)
        box.append(row)
        q = mla_absorb(ap, q_nope, q_rope, cfg, W)[:, :, 0]
        with scope("attn"):
            ot = mla_paged_decode(q, arena, row, tables, pos,
                                  layer=layer, dc=cfg.kv_lora_rank, scale=cfg.attn_scale)
        return mla_unabsorb(ap, ot[:, :, None], cfg)

    return mla_mixer(ap, x, cos_t, sin_t, cfg, attend, lin=lin), box[0]


def _ssm_paged(sp, x, arenas, sslots, cfg, *, layer, lin):
    """An ssm layer of :func:`forward_paged`, one token a row:
    ``generate.ssm_mixer`` (the one mixer; the dense cache calls it too) with the
    state where the server keeps it: ``ssm_decode_step`` on the row's slot of the
    state arena, in place, and the conv's tail in its slot of the conv arena.
    Returns ``(y, state arena, conv arena, m)``; ``m`` is what a gmu layer gates."""
    if x.shape[1] != 1:
        raise NotImplementedError(
            "a selective scan is served one token a row here (ssm_decode_step); a whole prompt goes through "
            "the prefill_fresh program's scan, and a piece of one has no program yet")
    held = {"state": arenas["state"]}
    with scope("ssm/cache"):
        tail = arenas["conv"][sslots, layer]                         # (B, K - 1, d)

    def recur(u, dt, Bm, Cm, A):
        y, held["state"] = ssm_decode_step(held["state"], sslots, u[:, 0], dt[:, 0], Bm[:, 0], Cm[:, 0], A, layer=layer)
        return y[:, None]

    y, new_tail, m = ssm_mixer(sp, x, tail, cfg, recur, lin=lin)
    with scope("ssm/cache"):
        return y, held["state"], arenas["conv"].at[sslots, layer].set(new_tail), m


def _mamba2_paged(mp, x, arenas, sslots, cfg, *, layer, lin):
    """A mamba2 layer of :func:`forward_paged`, one token a row:
    ``generate.mamba2_mixer`` (the one mixer; the dense cache calls it too) with
    the state where the server keeps it: ``ssd_decode_step`` on the row's slot of
    the state arena, in place, and the conv's tail in its slot of the conv arena.
    Returns ``(y, state arena, conv arena)``."""
    if x.shape[1] != 1:
        raise NotImplementedError(
            "a Mamba-2 scan is served one token a row here (ssd_decode_step); a whole prompt goes through "
            "the prefill_fresh program's chunked scan, and a piece of one has no program yet")
    held = {"state": arenas["state"]}
    with scope("mamba2/cache"):
        tail = arenas["conv"][sslots, layer]                         # (B, K - 1, d + 2 G N)

    def recur(xs, dt, Bm, Cm, A):
        y, held["state"] = ssd_decode_step(held["state"], sslots, xs[:, 0], dt[:, 0], Bm[:, 0], Cm[:, 0], A, layer=layer)
        return y[:, None]

    y, new_tail = mamba2_mixer(mp, x, tail, cfg, recur, lin=lin)
    with scope("mamba2/cache"):
        return y, held["state"], arenas["conv"].at[sslots, layer].set(new_tail)


def _diff_paged(ap, x, l, cfg, k_arena, v_arena, tables, pos, *, layer, window, cdtype, name, lin, fresh_kv=None):
    """A differential-attention layer of :func:`forward_paged`, one token a
    row: ``generate.diff_attention`` (the one mixer; the dense cache calls it
    too) with the keys and values where the server keeps them.  The pair's two
    softmaxes are one walk over ``layer`` of the lane-packed arenas
    (``paged_attn_decode(packed_out=True)``: a 128-lane row is the pair's two K
    heads, another its two V heads side by side, fetched once for both).  ``fresh_kv``: a
    cross_attention layer's: this step's K and V of the layer it reads, which
    the arena does not hold yet.  Returns ``(y, (fresh K, fresh V))``, ``(B, ng,
    hs)`` at the cache compute dtype."""
    box = []

    def attend(q, k, v):
        B, G, _, J, _, hs = q.shape
        fk, fv = fresh_kv if k is None else (k[:, :, 0].astype(cdtype), v[:, :, 0].astype(cdtype))
        box.extend((fk, fv))
        with scope(f"{name}/attn"):
            out = paged_attn_decode(q[..., 0, :].reshape(B, 2 * G * J, hs), k_arena, v_arena, fk, fv, tables, pos,
                                    layer=layer, window=window, packed_out=True)
            return out.reshape(B, G, 2, J, 1, 2 * hs)

    y = diff_attention(ap, x, l, cfg, attend, cross=fresh_kv is not None, lin=lin, name=name)
    return y, (box[0], box[1])


def with_state(arenas, fresh):
    """The arenas a paged program returns: K and V as the writers left them,
    the state and conv arenas as :func:`forward_paged` left them."""
    return {**arenas, **{k: fresh[k] for k in ("state", "conv") if k in fresh}}


def forward_paged(params, idx, pos, arenas, tables, cos_all, sin_all, cfg, *,
                  cdtype, quantized=False, lora=None, lora_scaling=1.0,
                  mesh=None, lora_fused=False, sslots=None, n_real=None, moe_rows=False):
    """Decode/verify forward straight off the KV block arenas.

    Mirrors ``forward_with_cache`` (vec-pos) except attention: instead of
    consuming a gathered dense cache, each layer calls the paged kernel
    against the arenas + block tables.  ``idx``: (B, T) tokens — T=1 is the
    decode step, T=K+1 the speculative verify chunk (causal intra-chunk mask
    fused in-kernel); ``pos``: (B,) int32; ``arenas``: the pool's
    ``{"k","v"(,"k_scale","v_scale")}``; ``tables``: (B, nbb) sink-padded
    block tables; ``cdtype``: the cache compute dtype (fresh K/V are cast to
    it before attending, matching the dense path's cache write).  Returns
    ``(logits (B, T, V), fresh)`` with ``fresh = {"k"/"v": (B, L, ng, hs)}``
    for T=1 or ``(B, L, ng, T, hs)`` for T>1, at cdtype — the caller
    persists it with :func:`write_fresh_kv` / :func:`write_fresh_kv_masked`
    / :func:`write_fresh_kv_chunk` (same step, after sampling's logits are
    taken; order doesn't matter as the kernel already attended it).
    ``lora_fused`` routes the per-target adapter deltas through the fused
    ``lora_delta_fused`` kernel instead of standalone HLO einsums —
    bit-identical math, meshless only (a bare pallas_call has no SPMD
    rule).

    A model with linear_attention layers: ``arenas`` also holds the state
    pool's ``state`` and ``conv``, ``sslots`` (B,) names each row's slot (0
    the sink) and ``n_real`` how many of a prompt piece's T tokens are real;
    the K/V arenas' layer axis counts the full-attention layers only
    (``cfg.kv_layers``), and ``fresh`` carries the two updated state arenas
    beside the fresh K/V (:func:`with_state`).  A model with conv layers: the
    same with ``conv`` alone (the tails, :func:`_conv_paged`).  A
    decoder-hybrid-decoder (ssm, sliding_attention, gmu, cross_attention; one
    token a row): ``state`` and ``conv`` are the scans', ``k_ring`` / ``v_ring`` hold
    the sliding_attention layers' K and V, a ring a state slot
    (``kv_pool.ring_tables``), ``k`` / ``v`` the full_attention layers' alone, which
    the cross_attention layers walk too; ``fresh`` carries ``k_ring`` / ``v_ring``
    ``(B, L_ring, ng, hs)`` and ``ring_tables`` for :func:`write_fresh_kv`.  A model
    with mamba2 layers (one token a row): ``state`` and ``conv`` are the Mamba-2
    scans' (:func:`_mamba2_paged`); an "mlp" layer (a feed-forward alone) keeps
    nothing.  An ordinary decoder with sliding_attention layers (one token a row):
    those layers walk ``k_ring`` / ``v_ring`` through the slots' ring tables under
    ``cfg.layer_window`` and the full_attention layers their own blocks, as the
    hybrid's do; ``fresh`` carries both.  ``moe_rows``: ``fresh`` also carries ``moe_rows (L_moe, 2)`` int32, each
    expert layer's rows that landed on held experts and held experts with a row
    (``generate.moe_share_mlp``), which the decode program sums for
    ``engine.stats()["moe"]``.  A looped model (``cfg.n_pass`` > 1; one token a row):
    the stack is the body of ``generate.run_passes``' loop, layer ``l`` of pass ``t``
    walks slab ``cfg.kv_slab(t, l)`` of the arenas, ``fresh`` holds every slab's K and V
    in that order and ``exit``: the pass the exit rule chose a row ``(B,)`` and its
    exit probabilities ``(n_pass, B)``."""
    B, T = idx.shape
    hs, nh = cfg.head_size, cfg.n_head
    window = cfg.sliding_window
    with scope("embed"):        # the tokens' rows, and their positions' rows of the rope tables
        x = params["wte"][idx]
        if cfg.scale_embedding:
            x = x * (cfg.n_embd ** 0.5)
        if cfg.learned_pos_embedding:
            x = x + jax.vmap(
                lambda p: jax.lax.dynamic_slice_in_dim(params["wpe"], p, T, axis=0))(pos)
        cos_t = jax.vmap(lambda p: jax.lax.dynamic_slice_in_dim(cos_all, p, T, axis=0))(pos)[:, None]
        sin_t = jax.vmap(lambda p: jax.lax.dynamic_slice_in_dim(sin_all, p, T, axis=0))(pos)[:, None]
        x = _to_streams(x, cfg)

    lin = partial(_linear, quantized=quantized)
    delta_fn = lora_delta_fused if (lora_fused and mesh is None) else _lora_delta
    rows_of = [] if moe_rows else None

    def blocks(x, t, state_arena, conv_arena):
        """The stack of blocks on ``x``, once: pass ``t`` of a looped model (its slabs of the
        arenas, ``cfg.kv_slab``; traced there), the Python integer 0 for every other."""
        n_lin = n_conv = 0
        fresh_k, fresh_v, fresh_rows = [], [], []
        ring_k, ring_v, ring_tabs, gmu_m = [], [], None, None
        for l, bp in enumerate(params["blocks"]):
            lora_l = None
            if lora:
                lora_l = {name: (ab["a"][:, l], ab["b"][:, l]) for name, ab in lora.items()}
            if cfg.layer_kind(l) == "mlp":      # the layer is its feed-forward alone: no mixer, no cache
                with scope(f"blk{l}"):
                    x = _close_block(bp, x, None, None, cfg, quantized=quantized, moe_rows=rows_of)
                continue
            with scope(f"blk{l}"):
                with scope("mixer"):
                    # under hyper-connections the sublayer reads a mixture of the streams
                    x, u, hc = hc_step(bp["hc_1"], x, cfg, sharded=mesh is not None) if cfg.hc_mult > 1 else (x, x, None)
                    if cfg.post_sublayer_norm:
                        n1 = u
                    else:
                        with scope("norm"):     # (under hyper-connections ``u`` is float32: rounded here)
                            n1 = _norm(u, bp["norm_1"], cfg, bp.get("norm_1_b")).astype(x.dtype)
                    kind = cfg.layer_kind(l)
                    if kind == "mamba2":
                        h, state_arena, conv_arena = _mamba2_paged(
                            bp["mamba2"], n1, {"state": state_arena, "conv": conv_arena}, sslots, cfg, layer=n_lin, lin=lin)
                        n_lin += 1
                    elif kind == "ssm":
                        h, state_arena, conv_arena, m = _ssm_paged(
                            bp["ssm"], n1, {"state": state_arena, "conv": conv_arena}, sslots, cfg, layer=n_lin, lin=lin)
                        n_lin += 1
                        if l == cfg.gmu_source:
                            gmu_m = m
                    elif kind == "gmu":
                        h = gmu_mixer(bp["gmu"], n1, gmu_m, lin=lin)
                    elif cfg.diff_attention:
                        if T != 1:
                            raise NotImplementedError("differential attention is walked one token a row (a draft's "
                                                      "verify and a piece of a prompt have no such kernel)")
                        if kind == "sliding_attention":
                            if ring_tabs is None:
                                ring_tabs = ring_tables(sslots, ring_blocks(cfg, arenas["k_ring"].shape[3]), tables.shape[1])
                            h, kv = _diff_paged(bp["attn"], n1, l, cfg, arenas["k_ring"], arenas["v_ring"], ring_tabs, pos,
                                                layer=len(ring_k), window=cfg.layer_window, cdtype=cdtype, name="swa", lin=lin)
                            ring_k.append(kv[0])
                            ring_v.append(kv[1])
                        else:   # a full_attention layer's own blocks, or the cross source's: the walk's layer is the owner's
                            cross = kind == "cross_attention"
                            own = cfg.paged_kv_layers.index(cfg.cross_from if cross else l)
                            h, kv = _diff_paged(bp["attn"], n1, l, cfg, arenas["k"], arenas["v"], tables, pos, layer=own,
                                                window=None, cdtype=cdtype, name="cross" if cross else "attn", lin=lin,
                                                fresh_kv=(fresh_k[own], fresh_v[own]) if cross else None)
                            if not cross:
                                fresh_k.append(kv[0])
                                fresh_v.append(kv[1])
                    elif kind == "linear_attention":
                        h, state_arena, conv_arena = _gdn_paged(
                            bp["gdn"], n1, {"state": state_arena, "conv": conv_arena}, sslots, pos, cfg,
                            layer=n_lin, n_real=n_real, lin=lin)
                        n_lin += 1
                    elif kind == "conv":
                        h, conv_arena = _conv_paged(bp["conv"], n1, conv_arena, sslots, pos, cfg,
                                                    layer=n_conv, n_real=n_real, lin=lin)
                        n_conv += 1
                    elif cfg.latent:
                        h, row = _mla_paged(bp["attn"], n1, arenas["latent"], tables, pos, cos_t, sin_t, cfg,
                                            layer=l, cdtype=cdtype, lin=lin)
                        fresh_rows.append(row)
                    else:
                        gate = []
                        q, k, v = _project_qkv(bp["attn"], n1, cos_t, sin_t, cfg, lin=lin,
                                               lora=lora_l, lora_scaling=lora_scaling,
                                               delta_fn=delta_fn, rope=cfg.rotates(l), gate=gate)
                        # an ordinary decoder's window kind walks the slot's ring under the
                        # kind's window; every other layer its own blocks of the paged arenas
                        swa = kind == "sliding_attention"
                        if swa and T != 1:
                            raise NotImplementedError("a sliding_attention layer's ring is walked one token a row (a "
                                                      "draft's verify and a piece of a prompt have no such program)")
                        if swa and ring_tabs is None:
                            ring_tabs = ring_tables(sslots, ring_blocks(cfg, arenas["k_ring"].shape[3]), tables.shape[1])
                        kvl = len(ring_k) if swa else len(fresh_k)      # this layer's place in its kind's arenas
                        # fresh K/V at the cache compute dtype — the exact values the dense
                        # path writes before attending
                        with scope("swa" if swa else "attn"):
                            if T == 1:
                                # q: (B, nh, 1, hs) → (B, nh, hs)
                                fk = k[:, :, 0].astype(cdtype)
                                fv = v[:, :, 0].astype(cdtype)
                                if swa:
                                    y = _attn_paged(q[:, :, 0], {"k": arenas["k_ring"], "v": arenas["v_ring"]}, fk, fv,
                                                    ring_tabs, pos, layer=kvl, window=cfg.layer_window, mesh=mesh)
                                else:
                                    y = _attn_paged(q[:, :, 0], arenas, fk, fv, tables, pos,
                                                    layer=cfg.kv_slab(t, kvl), window=window, mesh=mesh)
                                y = y.reshape(B, 1, nh * hs)
                            else:
                                fk = k.astype(cdtype)                  # (B, ng, T, hs)
                                fv = v.astype(cdtype)
                                y = _attn_paged(q, arenas, fk, fv, tables, pos, layer=kvl, mesh=mesh)
                                y = y.transpose(0, 2, 1, 3).reshape(B, T, nh * hs)
                        y = gated_out(y, gate)
                        with scope("out"):
                            h = lin(y, bp["attn"]["wo"], bp["attn"].get("bo"))
                            if lora_l is not None and "wo" in lora_l:
                                h = h + delta_fn(y, *lora_l["wo"], lora_scaling)
                        (ring_k if swa else fresh_k).append(fk)
                        (ring_v if swa else fresh_v).append(fv)
                x = _close_block(bp, x, n1, h, cfg, quantized=quantized, lora=lora_l, lora_scaling=lora_scaling,
                                 moe_rows=rows_of, hc=hc, sharded=mesh is not None)
        return x, (fresh_k, fresh_v, fresh_rows, ring_k, ring_v, ring_tabs), state_arena, conv_arena

    if cfg.n_pass > 1:      # a looped model: the stack as the body of one loop over the passes
        if T != 1:
            raise NotImplementedError("a looped model's arenas are walked one token a row (a draft's verify and a "
                                      "piece of a prompt take the layer as a constant of their kernel)")

        def kept(h, t):
            u, (fresh_k, fresh_v, *_), _, _ = blocks(h, t, None, None)
            with scope("mixer/cache"):
                return u, (jnp.stack(fresh_k, axis=1), jnp.stack(fresh_v, axis=1))

        x, (ks, vs), (chosen, p) = run_passes(params, x, cfg, kept)
        logits = _head_logits(params, x, cfg, None, quantized, sharded=mesh is not None, closed=True)
        with scope("mixer/cache"):      # (n_pass, B, L, ng, hs) to the writer's (B, slabs, ng, hs), the slabs' own order
            fresh = {name: jnp.moveaxis(a, 0, 1).reshape(B, -1, *a.shape[3:]) for name, a in (("k", ks), ("v", vs))}
        fresh.update(exit=(chosen[:, 0], p[:, :, 0]))
        return logits, fresh
    x, (fresh_k, fresh_v, fresh_rows, ring_k, ring_v, ring_tabs), state_arena, conv_arena = blocks(
        x, 0, arenas.get("state"), arenas.get("conv"))
    logits = _head_logits(params, x, cfg, None, quantized, sharded=mesh is not None)
    with scope("mixer/cache"):
        if cfg.latent:          # (B, L, 1, W): the token writer's layout, one group
            fresh = {"latent": jnp.stack(fresh_rows, axis=1)[:, :, None]}
        else:
            fresh = {"k": jnp.stack(fresh_k, axis=1), "v": jnp.stack(fresh_v, axis=1)}
        if ring_k:      # the sliding_attention layers' K and V of this step, and the tables of their rings
            fresh.update(k_ring=jnp.stack(ring_k, axis=1), v_ring=jnp.stack(ring_v, axis=1), ring_tables=ring_tabs)
    if conv_arena is not None:
        fresh.update(conv=conv_arena)
    if state_arena is not None:
        fresh.update(state=state_arena)
    if rows_of:
        fresh.update(moe_rows=jnp.stack(rows_of))
    return logits, fresh


def _write(arena, vals, tables, pos, *, block_size, mesh, n_emit=None, offset=0,
           name="paged_token_write"):
    if mesh is None:
        return paged_token_write(arena, vals, tables, pos, block_size=block_size,
                                 n_emit=n_emit, offset=offset, name=name)
    rank5, tp = arena.ndim == 5, _tp_axis(mesh, arena.shape[2])
    aspec = P(None, None, tp, None, None) if rank5 else P(None, None, tp, None)
    vspec = P(None, None, tp, None) if rank5 else P(None, None, tp)
    if n_emit is None:
        return _smap(
            lambda a, v, t, p: paged_token_write(a, v, t, p, block_size=block_size),
            mesh, (aspec, vspec, P(None, None), P(None)), aspec,
        )(arena, vals, tables, pos)
    return _smap(
        lambda a, v, t, p, n: paged_token_write(
            a, v, t, p, block_size=block_size, n_emit=n, offset=offset),
        mesh, (aspec, vspec, P(None, None), P(None), P(None)), aspec,
    )(arena, vals, tables, pos, n_emit)


def _write_fused(arena, scale, vals, tables, pos, *, block_size, mesh,
                 n_emit=None, offset=0):
    """Fused quantize-on-write: ``vals`` at the compute dtype go through the
    in-kernel absmax epilogue (``paged_token_write_fused``), landing value +
    scale in one aliased pallas_call — no standalone quantize op in the
    program.  The per-slot-head scale is an absmax over ``hs``, computed
    per KV group, so under a mesh each shard quantizes its own heads
    (shard-local, no collective)."""
    if mesh is None:
        return paged_token_write_fused(arena, scale, vals, tables, pos,
                                       block_size=block_size, n_emit=n_emit,
                                       offset=offset)
    tp = _tp_axis(mesh, arena.shape[2])
    aspec = P(None, None, tp, None, None)
    sspec = P(None, None, tp, None)
    vspec = P(None, None, tp, None)
    if n_emit is None:
        return _smap(
            lambda a, s, v, t, p: paged_token_write_fused(
                a, s, v, t, p, block_size=block_size),
            mesh, (aspec, sspec, vspec, P(None, None), P(None)), (aspec, sspec),
        )(arena, scale, vals, tables, pos)
    return _smap(
        lambda a, s, v, t, p, n: paged_token_write_fused(
            a, s, v, t, p, block_size=block_size, n_emit=n, offset=offset),
        mesh, (aspec, sspec, vspec, P(None, None), P(None), P(None)),
        (aspec, sspec),
    )(arena, scale, vals, tables, pos, n_emit)


@scope("mixer/cache")
def write_fresh_kv(arenas, fresh, tables, pos, *, block_size, kv_dtype=None,
                   mesh=None):
    """Lands one decode step's fresh K/V in the arenas, in place.

    ``fresh``: ``{"k"/"v": (B, L, ng, hs) at the compute dtype}`` from
    :func:`forward_paged`.  ``kv_dtype``: the storage dtype when the pool is
    quantized (int8/fp8) — quantization is **fused into the writer kernel**
    (``paged_token_write_fused`` runs the exact ``quantize_kv`` absmax math
    as its epilogue and lands value + scale through two aliased outputs),
    so the stored bytes stay bit-identical to ``scatter_token_q``'s while no
    standalone quantize op appears in the program.  Returns the updated
    arenas dict (aliased buffers: no scatter primitive, untouched blocks
    keep their bytes; padding rows land in sink block 0, never attended).
    A latent arena takes its one row a layer the same way, under the kernel
    name ``mla_latent_write``."""
    if "latent" in arenas:
        return {"latent": _write(arenas["latent"], fresh["latent"], tables, pos, block_size=block_size,
                                 mesh=mesh, name="mla_latent_write")}
    if kv_dtype is None:
        w = partial(_write, tables=tables, pos=pos, block_size=block_size,
                    mesh=mesh)
        out = {"k": w(arenas["k"], fresh["k"]), "v": w(arenas["v"], fresh["v"])}
        if "k_ring" in fresh:   # the sliding_attention layers': the same writer over their rings' tables
            w = partial(_write, tables=fresh["ring_tables"], pos=pos, block_size=block_size, mesh=mesh)
            out.update(k_ring=w(arenas["k_ring"], fresh["k_ring"]), v_ring=w(arenas["v_ring"], fresh["v_ring"]))
        return out
    ka, ks = _write_fused(arenas["k"], arenas["k_scale"], fresh["k"], tables,
                          pos, block_size=block_size, mesh=mesh)
    va, vs = _write_fused(arenas["v"], arenas["v_scale"], fresh["v"], tables,
                          pos, block_size=block_size, mesh=mesh)
    return {"k": ka, "v": va, "k_scale": ks, "v_scale": vs}


@scope("mixer/cache")
def write_fresh_kv_masked(arenas, fresh, tables, pos, n_emit, *, block_size,
                          kv_dtype=None, mesh=None):
    """Lands a verify step's accepted-prefix K/V in the arenas, in place.

    ``fresh``: ``{"k"/"v": (B, L, ng, T, hs)}`` from a T=K+1
    :func:`forward_paged` call; ``n_emit``: (B,) int32 accepted counts.  For
    each chunk offset ``k`` only rows with ``k < n_emit`` commit at
    ``pos + k``; the rest are sink-routed (block 0, never attended), so
    rejected candidates leave no trace and the next round re-derives them
    from scratch.  Quantization matches :func:`write_fresh_kv` — the fused
    in-kernel absmax epilogue per chunk offset, bit-identical bytes to
    ``scatter_token_q``'s."""
    T = fresh["k"].shape[3]
    out = dict(arenas)
    if kv_dtype is None:
        for name in ("k", "v"):
            a = out[name]
            for k in range(T):
                a = _write(a, fresh[name][:, :, :, k], tables, pos,
                           n_emit=n_emit, offset=k, block_size=block_size,
                           mesh=mesh)
            out[name] = a
        return out
    for name in ("k", "v"):
        a, s = out[name], out[name + "_scale"]
        for k in range(T):
            a, s = _write_fused(a, s, fresh[name][:, :, :, k], tables, pos,
                                block_size=block_size, mesh=mesh,
                                n_emit=n_emit, offset=k)
        out[name], out[name + "_scale"] = a, s
    return out


def _chunk_blocks(x, bs):
    """(1, L, ng, T, hs) chunk-fresh layout → (T // bs, L, ng, bs, hs) block
    granules for the chunk writer — pure reshape/transpose, no gather."""
    _, L, ng, T, hs = x.shape
    return x[0].reshape(L, ng, T // bs, bs, hs).transpose(2, 0, 1, 3, 4)


@scope("mixer/cache")
def write_fresh_kv_chunk(arenas, fresh, dest, pos, *, block_size,
                         kv_dtype=None, mesh=None):
    """Lands one chunked-prefill piece's K/V in the arenas, block-granule,
    in place — the ``prefill_chunk_paged`` program's ``scatter_blocks``
    replacement.

    ``fresh``: ``{"k"/"v": (1, L, ng, T, hs)}`` from a T = chunk-width
    :func:`forward_paged` call (B=1 prefill layout, T block-aligned);
    ``dest``: (nbb,) int32 scatter table from ``kv_pool.chunk_tables`` (sink
    outside the chunk's own block range); ``pos``: (1,) int32 block-aligned
    chunk start.  Each chunk block lands as one whole (L, ng, bs, hs) slab
    at ``dest[pos // bs + c]``; quantized pools run the fused absmax
    epilogue (``paged_chunk_write_fused``) with in-kernel masked error sums.
    Returns ``(arenas, qerr)`` with ``qerr`` the same
    ``0.5 * (k_rel + v_rel)`` figure the gather chunk program reports
    (0.0 unquantized)."""
    bs = block_size

    tp = None if mesh is None else _tp_axis(mesh, arenas["k"].shape[2])

    def plain(arena, vals):
        if mesh is None:
            return paged_chunk_write(arena, vals, dest, pos, block_size=bs)
        aspec = P(None, None, tp, None, None)
        return _smap(
            lambda a, v, d, p: paged_chunk_write(a, v, d, p, block_size=bs),
            mesh, (aspec, aspec, P(None), P(None)), aspec,
        )(arena, vals, dest, pos)

    def fused(arena, scale, vals):
        if mesh is None:
            return paged_chunk_write_fused(arena, scale, vals, dest, pos,
                                           block_size=bs)
        aspec = P(None, None, tp, None, None)
        sspec = P(None, None, tp, None)
        return _smap(
            lambda a, s, v, d, p: paged_chunk_write_fused(
                a, s, v, d, p, block_size=bs),
            mesh, (aspec, sspec, aspec, P(None), P(None)),
            (aspec, sspec, P(None, tp, None)),
        )(arena, scale, vals, dest, pos)

    kb = _chunk_blocks(fresh["k"], bs)
    vb = _chunk_blocks(fresh["v"], bs)
    if kv_dtype is None:
        out = {"k": plain(arenas["k"], kb.astype(arenas["k"].dtype)),
               "v": plain(arenas["v"], vb.astype(arenas["v"].dtype))}
        return out, jnp.float32(0.0)
    ka, ks, ke = fused(arenas["k"], arenas["k_scale"], kb)
    va, vs, ve = fused(arenas["v"], arenas["v_scale"], vb)

    def rel(e):
        # per-block masked sums ride in last-dim cols 0 (|dq - x|) and 1
        # (|x|), zeros elsewhere — summing every row keeps the figure exact
        # under a mesh, where the shards' err slabs concatenate on axis 1
        return jnp.sum(e[..., 0]) / (jnp.sum(e[..., 1]) + 1e-30)

    qerr = 0.5 * (rel(ke) + rel(ve))
    return {"k": ka, "v": va, "k_scale": ks, "v_scale": vs}, qerr
