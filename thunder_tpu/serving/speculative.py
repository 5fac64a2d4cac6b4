"""Speculative continuous batching: a draft/verify lane over the paged arena.

``tt.serve(..., speculative=SpecConfig(draft_params, draft_cfg, K=4))`` adds
a second, cheaper proposal model to the serving engine.  Each decode-lane
turn then runs TWO bucket programs instead of one:

- ``draft_decode`` — K autoregressive single-token forwards of the draft
  model, chained on-device (a ``lax.scan``, exactly the solo
  ``models.speculative._spec_step`` draft loop), reading and writing a
  **draft KV block arena** that sits beside the target arena: its own
  ``PagedKVPool`` storage with the same dtype/quantization/mesh sharding,
  but *sharing the target pool's block tables* — block ids are allocated
  once per request and index both arenas, so the allocator, free list, and
  prefix index stay single;
- ``verify`` / ``verify_paged`` — ONE target forward over the K+1 query
  positions ``[cur, d_1..d_K]``, the shared rejection rule from
  :func:`thunder_tpu.models.speculative.accept_tokens` (one implementation
  for solo and served paths — pinned by tests), and a keep-masked commit
  that writes only the accepted prefix's K/V into the target arena
  (rejected offsets sink-route; static shapes throughout, so the program
  set stays bounded by the same bucket accounting as plain decode).

Reproducibility contract (the whole point): per-request PRNG keys split
exactly like solo ``speculative_generate()`` at B=1 — one split per round
in the draft program (greedy), plus one acceptance split in verify under
temperature — and keys only advance at harvest, so served tokens are
**bit-identical** to the solo path, the KV arenas stay soft state, and
re-prefill recovery (which replays prompt + emitted tokens through
``spec_prefill_chunk``) rebuilds both arenas bit-identically: every
attended draft-arena slot ``p`` holds the draft K/V of the emitted token
``x_p`` (rejected-draft slots above the accepted prefix are rewritten
before the next attend), so the replay reproduces them exactly, greedy or
sampled.

Emission is variable-rate: a round emits ``n_emit ∈ [1, K+1]`` tokens per
row (accepted drafts + the resampled/bonus token), harvested in order
through the engine's normal ``_emit_token`` path — EOS/length finishes can
land mid-round, in which case the surplus tokens are dropped exactly like
solo's buffer trim.  The decode-state device chain carries ``(y, pos +
n_emit)`` so steady-state rounds cost zero host->device transfers, same as
plain decode.

This module holds the five bucket-program builders plus the dispatch and
harvest halves of the speculative decode lane; the engine owns state
(pools, scheduler, program cache, counters) and calls in.  No engine
import — the engine imports lazily from here.
"""
from __future__ import annotations

import time
from dataclasses import dataclass
from functools import partial
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np

from thunder_tpu.models.generate import build_rope_cache, forward_with_cache
from thunder_tpu.models.speculative import accept_tokens
from thunder_tpu.serving.faults import FP_DRAFT, FP_SCATTER, FP_VERIFY
from thunder_tpu.serving.kv_pool import (
    SINK_BLOCK,
    gather_dense,
    scatter_blocks,
    scatter_token,
)
from thunder_tpu.serving.quant import (
    gather_dense_q,
    scatter_blocks_q,
    scatter_token_q,
)

__all__ = ["SpecConfig", "validate_spec"]


@dataclass
class SpecConfig:
    """Speculative-serving knob for ``tt.serve``.

    ``draft_params``/``draft_cfg``: the small proposal model (must share
    the target's padded vocab; LoRA and custom forwards stay target-only).
    ``K``: drafted tokens per round — each round costs one K-step draft
    scan plus one (K+1)-position target verify, and emits 1..K+1 tokens.
    ``draft_kv_dtype``: storage dtype of the DRAFT arena only (``"int8"``,
    ``"fp8"``, or ``None`` to inherit the engine's ``kv_dtype``) — the
    draft cache only feeds proposals that the acceptance rule corrects
    against the target, so it tolerates aggressive quantization even when
    the target arena stays full-precision (and vice versa).
    """

    draft_params: Any
    draft_cfg: Any
    K: int = 4
    draft_kv_dtype: Any = None


def validate_spec(spec: SpecConfig, cfg, *, sliding_window) -> None:
    """Engine-construction validation: everything the key-chain mirroring
    and the K-token arena math require, checked before any allocation."""
    if not isinstance(spec, SpecConfig):
        raise TypeError(f"speculative= expects SpecConfig, got {type(spec).__name__}")
    if spec.K < 1:
        raise ValueError(f"SpecConfig.K must be >= 1, got {spec.K}")
    if spec.draft_cfg.padded_vocab_size != cfg.padded_vocab_size:
        raise ValueError(
            "speculative serving needs a shared tokenizer: draft "
            f"padded_vocab_size={spec.draft_cfg.padded_vocab_size} != target "
            f"{cfg.padded_vocab_size}"
        )
    if sliding_window is not None or getattr(cfg, "sliding_window", None) \
            or getattr(spec.draft_cfg, "sliding_window", None):
        raise ValueError(
            "speculative serving does not support sliding-window engines: "
            "window expiry would invalidate the K-token draft/verify arena "
            "math (solo speculative_generate has the same restriction)"
        )


#
# shared in-program pieces
#


def _gather(arenas, tables, qkv, cdtype):
    """Dense {k, v} cache view of ``tables``'s blocks (dequantizing when
    the pool is int8/fp8) — the same gather every plain bucket program
    opens with."""
    if qkv:
        kd, vd = gather_dense_q(
            arenas["k"], arenas["v"], arenas["k_scale"], arenas["v_scale"],
            tables, cdtype,
        )
    else:
        kd, vd = gather_dense(arenas["k"], arenas["v"], tables)
    return {"k": kd, "v": vd}


def _scatter_prefill(arenas, cache, dest, qkv):
    """Block-granular prefill writeback (quantize-on-scatter when the pool
    stores int8/fp8); returns (arenas, measured quantization error)."""
    if qkv:
        k_arena, k_scale, k_err = scatter_blocks_q(
            arenas["k"], arenas["k_scale"], cache["k"], dest)
        v_arena, v_scale, v_err = scatter_blocks_q(
            arenas["v"], arenas["v_scale"], cache["v"], dest)
        return ({"k": k_arena, "v": v_arena, "k_scale": k_scale, "v_scale": v_scale},
                0.5 * (k_err + v_err))
    return ({"k": scatter_blocks(arenas["k"], cache["k"], dest),
             "v": scatter_blocks(arenas["v"], cache["v"], dest)},
            jnp.float32(0.0))


def _scatter_at(arenas, kc, vc, p_k, db, ds, qkv):
    """Commits one offset's per-row K/V (picked from the dense cache at
    position ``p_k``) into the arena at (block ``db``, slot ``ds``)."""
    pick = jax.vmap(
        lambda c, p: jax.lax.dynamic_index_in_dim(c, p, axis=2, keepdims=False))
    if qkv:
        k_arena, k_scale = scatter_token_q(
            arenas["k"], arenas["k_scale"], pick(kc, p_k), db, ds)
        v_arena, v_scale = scatter_token_q(
            arenas["v"], arenas["v_scale"], pick(vc, p_k), db, ds)
        return {"k": k_arena, "v": v_arena, "k_scale": k_scale, "v_scale": v_scale}
    return {"k": scatter_token(arenas["k"], pick(kc, p_k), db, ds),
            "v": scatter_token(arenas["v"], pick(vc, p_k), db, ds)}


def _acceptance(tlogits, drafts, q_rows, keys, temp, K):
    """The shared rejection rule, vectorized per row with per-request key
    chains.  Greedy: accept drafts while they match the target's argmax
    (no key split — solo's greedy round splits once, in the draft half).
    Temperature: one more per-row split, then
    :func:`~thunder_tpu.models.speculative.accept_tokens` at B=1 — the
    ``split(k, 1)[0]`` inner split reproduces solo's
    ``vmap(accept_tokens)(split(ka, B), ...)`` draw exactly.

    Returns ``(emitted (B, K+1), n_emit (B,), y (B,), new_keys)`` —
    ``emitted[:, :n_emit]`` are the round's tokens, the tail is garbage
    masked by ``n_emit`` (solo's fixed-shape emission rule verbatim)."""
    B = drafts.shape[0]
    if temp == 0.0:
        tgt = jnp.argmax(tlogits, axis=-1).astype(jnp.int32)   # (B, K+1)
        match = drafts == tgt[:, :K]
        m = jnp.argmin(
            jnp.concatenate([match, jnp.zeros((B, 1), bool)], axis=1).astype(jnp.int32),
            axis=1,
        )
        y = jnp.take_along_axis(tgt, m[:, None], axis=1)[:, 0]
        new_keys = keys
    else:
        p_all = jax.nn.softmax(tlogits / temp, axis=-1)        # (B, K+1, V)
        sp = jax.vmap(jax.random.split)(keys)
        new_keys, kas = sp[:, 0], sp[:, 1]
        m, y = jax.vmap(
            lambda k, d, p, q: accept_tokens(jax.random.split(k, 1)[0], d, p, q)
        )(kas, drafts, p_all, q_rows)
    n_emit = m + 1
    iota = jnp.arange(K + 1)[None, :]
    emitted = jnp.where(
        iota < m[:, None],
        jnp.concatenate([drafts, jnp.zeros((B, 1), jnp.int32)], axis=1),
        y[:, None],
    )
    return emitted, n_emit, y, new_keys


#
# bucket-program builders (called from ServingEngine._program)
#


def build_spec_prefill(eng, Tb: int, nbb: int):
    """The speculative twin of ``_build_prefill``: one extra draft forward
    writes the prompt's draft K/V through the SAME chunk-granular dest
    table (shared block ids), and the first-token draw mirrors solo
    ``speculative_generate``'s ``decode_all`` entry — one key split always,
    then argmax (greedy) or a ``split(kf, 1)`` categorical (temperature) —
    NOT the plain engine's ``sample_token``, whose key use differs."""
    cfg, dcfg = eng.cfg, eng.spec.draft_cfg
    temp, quantized = eng.temperature, eng.quantized
    qkv = eng.pool.quantized_kv
    dqkv = eng.draft_pool.quantized_kv
    cdtype = jnp.dtype(eng.pool.dtype)
    ddtype = jnp.dtype(eng.draft_pool.dtype)
    cap = eng.pool.capacity_tokens(nbb)
    cos, sin = build_rope_cache(cfg, cap)
    cos_d, sin_d = build_rope_cache(dcfg, cap)

    @partial(jax.jit, donate_argnums=(5, 6), **eng._jit_kwargs("spec_prefill"))
    def spec_prefill(params, dparams, toks, pos, n_real, arenas, darenas,
                     table, dest, key, lora, slot):
        dense = _gather(arenas, table[None, :], qkv, cdtype)
        logits, cache = forward_with_cache(
            params, toks, pos, dense, cos, sin, cfg,
            **eng._fwd_kwargs(lora, slot),
        )
        # LoRA rides the target only (solo contract): the draft is a cheap
        # base proposal and the acceptance rule corrects any q/p mismatch
        ddense = _gather(darenas, table[None, :], dqkv, ddtype)
        _dlogits, dcache = forward_with_cache(
            dparams, toks, pos, ddense, cos_d, sin_d, dcfg, quantized=quantized)
        last = jax.lax.dynamic_index_in_dim(logits, n_real - 1, axis=1,
                                            keepdims=False)     # (1, V)
        key, kf = jax.random.split(key)
        if temp == 0.0:
            tok = jnp.argmax(last, axis=-1).astype(jnp.int32)
        else:
            tok = jax.vmap(jax.random.categorical)(
                jax.random.split(kf, 1), last / temp).astype(jnp.int32)
        arenas, qerr = _scatter_prefill(arenas, cache, dest, qkv)
        darenas, _dqerr = _scatter_prefill(darenas, dcache, dest, dqkv)
        return tok, arenas, darenas, key, qerr

    return spec_prefill


def build_spec_prefill_chunk(eng, Tb: int, nbb: int):
    """Intermediate chunk piece with the draft forward alongside: KV into
    both arenas, no sampling, no key split (the final ``spec_prefill``
    piece does both) — also the replay program for re-prefill recovery,
    which rebuilds BOTH arenas bit-identically (every attended draft slot
    holds the draft K/V of the emitted token at that position)."""
    cfg, dcfg = eng.cfg, eng.spec.draft_cfg
    quantized = eng.quantized
    qkv = eng.pool.quantized_kv
    dqkv = eng.draft_pool.quantized_kv
    cdtype = jnp.dtype(eng.pool.dtype)
    ddtype = jnp.dtype(eng.draft_pool.dtype)
    cap = eng.pool.capacity_tokens(nbb)
    cos, sin = build_rope_cache(cfg, cap)
    cos_d, sin_d = build_rope_cache(dcfg, cap)

    @partial(jax.jit, donate_argnums=(4, 5), **eng._jit_kwargs("spec_prefill_chunk"))
    def spec_prefill_chunk(params, dparams, toks, pos, arenas, darenas,
                           table, dest, lora, slot):
        dense = _gather(arenas, table[None, :], qkv, cdtype)
        _logits, cache = forward_with_cache(
            params, toks, pos, dense, cos, sin, cfg,
            **eng._fwd_kwargs(lora, slot),
        )
        ddense = _gather(darenas, table[None, :], dqkv, ddtype)
        _dlogits, dcache = forward_with_cache(
            dparams, toks, pos, ddense, cos_d, sin_d, dcfg, quantized=quantized)
        arenas, qerr = _scatter_prefill(arenas, cache, dest, qkv)
        darenas, _dqerr = _scatter_prefill(darenas, dcache, dest, dqkv)
        return arenas, darenas, qerr

    return spec_prefill_chunk


def build_draft_decode(eng, Bb: int, nbb: int):
    """K+1 chained single-token draft forwards as one bucket program (the
    solo ``_spec_step`` draft scan over the gathered draft-arena view).

    Key chain per row: ``keys -> split -> (keys_mid, kd)``, ``kd -> K+1``
    iteration keys; a temperature draw at iteration i is
    ``categorical(split(dks[i], 1)[0], rows / T)`` — bit-equal to solo's
    ``vmap(categorical)(split(kk, B), rows / T)`` at B=1.  Greedy rounds
    split once and never draw, exactly like solo.

    All K+1 fresh draft K/V land in the draft arena unconditionally (no
    acceptance mask): solo's draft cache does the same, and slots above the
    accepted prefix are rewritten before the next attend (write-before-
    attend + the ``j <= qpos`` keep mask), so stale tails are unreachable.
    """
    dcfg = eng.spec.draft_cfg
    K, temp, quantized = eng.spec.K, eng.temperature, eng.quantized
    qkv = eng.draft_pool.quantized_kv
    cdtype = jnp.dtype(eng.draft_pool.dtype)
    bs = eng.draft_pool.block_size
    cap = eng.draft_pool.capacity_tokens(nbb)
    cos_d, sin_d = build_rope_cache(dcfg, cap)

    @partial(jax.jit, donate_argnums=(4,), **eng._jit_kwargs("draft_decode"))
    def draft_decode(dparams, toks, pos, tables, darenas, keys):
        dc = _gather(darenas, tables, qkv, cdtype)
        sp = jax.vmap(jax.random.split)(keys)          # per-request key chains
        keys_mid, kds = sp[:, 0], sp[:, 1]
        dks = jax.vmap(lambda k: jax.random.split(k, K + 1))(kds)
        dks = dks.transpose(1, 0, 2)                   # (K+1, B, 2) scan xs

        def dbody(carry, kk):
            tok, dpos, dc = carry
            dlogits, dc = forward_with_cache(
                dparams, tok[:, None], dpos, dc, cos_d, sin_d, dcfg,
                quantized=quantized,
            )
            rows = dlogits[:, -1]                      # (B, V)
            if temp == 0.0:
                nxt = jnp.argmax(rows, axis=-1).astype(jnp.int32)
                qrows = rows                           # unused in the greedy path
            else:
                qrows = jax.nn.softmax(rows / temp, axis=-1)
                nxt = jax.vmap(
                    lambda k, r: jax.random.categorical(
                        jax.random.split(k, 1)[0], r / temp)
                )(kk, rows).astype(jnp.int32)
            return (nxt, dpos + 1, dc), (nxt, qrows)

        (_, _, dc2), (drafts_x, q_rows_x) = jax.lax.scan(
            dbody, (toks, pos, dc), dks)
        drafts = drafts_x[:K].transpose(1, 0)          # (B, K)
        q_rows = q_rows_x[:K].transpose(1, 0, 2)       # (B, K, V)
        kc = dc2["k"].transpose(1, 0, 2, 3, 4)         # (B, L, ng, cap, hs)
        vc = dc2["v"].transpose(1, 0, 2, 3, 4)
        for k in range(K + 1):
            p_k = pos + k
            db = jnp.take_along_axis(tables, (p_k // bs)[:, None], axis=1)[:, 0]
            darenas = _scatter_at(darenas, kc, vc, p_k, db, p_k % bs, qkv)
        return drafts, q_rows, keys_mid, darenas

    return draft_decode


def build_verify_paged(eng, Bb: int, nbb: int):
    """ONE target forward over the K+1 chunk ``[cur, d_1..d_K]`` (per-row
    vector positions), the shared rejection rule, and a keep-masked commit:
    offset k's fresh K/V lands at ``pos + k`` iff ``k < n_emit``, else it
    sink-routes — the target arena only ever holds committed tokens' K/V.
    Attention runs the multi-token-query paged kernel straight off the arenas
    (q_len K+1, causal intra-chunk mask inside the online softmax) and the
    accepted prefix commits through the keep-masked write kernel, so with the
    kernel in it the compiled program touches the arenas with zero
    gather/scatter primitives (jaxpr-asserted by tests, with the kernel's XLA
    form as the positive control)."""
    from thunder_tpu.serving.paged_attention import (
        forward_paged,
        write_fresh_kv_masked,
    )

    cfg = eng.cfg
    K, temp = eng.spec.K, eng.temperature
    qkv = eng.pool.quantized_kv
    cdtype = jnp.dtype(eng.pool.dtype)
    kv_dtype = jnp.dtype(eng.pool.kv_dtype) if qkv else None
    bs = eng.pool.block_size
    cap = eng.pool.capacity_tokens(nbb)
    cos, sin = build_rope_cache(cfg, cap)
    mesh = eng.mesh

    @partial(jax.jit, donate_argnums=(4,), **eng._jit_kwargs("verify_paged"))
    def verify_paged(params, toks, pos, tables, arenas, drafts, q_rows, keys,
                     lora, slots):
        chunk = jnp.concatenate([toks[:, None], drafts], axis=1)  # (B, K+1)
        logits, fresh = forward_paged(
            params, chunk, pos, arenas, tables, cos, sin, cfg,
            cdtype=cdtype, mesh=mesh, lora_fused=True,
            **eng._fwd_kwargs(lora, slots),
        )
        emitted, n_emit, y, new_keys = _acceptance(
            logits, drafts, q_rows, keys, temp, K)
        arenas = write_fresh_kv_masked(
            arenas, fresh, tables, pos, n_emit, block_size=bs,
            kv_dtype=kv_dtype, mesh=mesh,
        )
        return emitted, n_emit, y, new_keys, pos + n_emit, arenas

    return verify_paged


#
# the speculative decode lane (dispatch/harvest halves, engine calls in)
#


def spec_decode_dispatch(eng) -> dict:
    """One speculative round for the decode-ready batch: draft program →
    verify program, chained on-device through ``eng._spec_state`` exactly
    like plain decode's ``_decode_state`` (steady state moves zero bytes
    host->device; the carried ``toks``/``pos`` are the previous round's
    ``y``/``pos + n_emit``).  ``host_pos`` advances at HARVEST (the round's
    ``n_emit`` is device-side until then), so dispatch reads it as-is."""
    sch, pool, dpool = eng.scheduler, eng.pool, eng.draft_pool
    K = eng.spec.K
    running = (sch.decode_ready() if eng.async_step
               else list(sch.running))                 # FIFO admission order
    eng._fault_point(FP_DRAFT, tuple(r.rid for r in running))
    Bb, _nbb_raw = sch.decode_bucket(running)
    nbb = eng._nbb(_nbb_raw)
    sig = (tuple(r.rid for r in running), Bb, nbb)
    st = eng._spec_state
    steady = st is not None and st["sig"] == sig
    if steady:
        toks_d, pos_d = st["toks"], st["pos"]
        tables_d, keys_d, slots_d = st["tables"], st["keys"], st["slots"]
        host_pos = st["host_pos"]
    else:
        toks = np.zeros(Bb, dtype=np.int32)
        host_pos = np.zeros(Bb, dtype=np.int32)
        tables = np.full((Bb, nbb), SINK_BLOCK, dtype=np.int32)
        keys = np.zeros((Bb, *np.shape(running[0].key)),
                        dtype=np.asarray(running[0].key).dtype)
        slots = np.zeros(Bb, dtype=np.int32)           # padding rows: base slot
        for i, r in enumerate(running):
            wpos = r.prompt_len + len(r.generated) - 1  # slot cur's K/V lands in
            toks[i] = r.generated[-1]
            host_pos[i] = wpos
            tables[i, : len(r.block_table)] = r.block_table
            keys[i] = r.key
            slots[i] = r.adapter_slot
        toks_d, pos_d = jnp.asarray(toks), jnp.asarray(host_pos)
        tables_d, keys_d = jnp.asarray(tables), jnp.asarray(keys)
        slots_d = jnp.asarray(slots)
    dprog, dcompiled = eng._program("draft_decode", Bb, nbb)
    with eng._span("serve.decode_dispatch.call"), \
            eng._compile_span(dcompiled, "draft_decode", Bb, nbb):
        drafts, q_rows, keys_mid, darenas = dprog(
            eng.spec.draft_params, toks_d, pos_d, tables_d, dpool.arenas, keys_d)
    dpool.set_arenas(darenas)
    # a fault HERE retries safely even though the draft arenas were donated:
    # the rerun recommits the same deterministic slots (this round's writes
    # depend only on history below pos, which the draft program never
    # touches), so the retried round stays bit-identical
    eng._fault_point(FP_VERIFY, tuple(r.rid for r in running))
    vkind = "verify_paged"
    vprog, vcompiled = eng._program(vkind, Bb, nbb)
    lora_arenas = eng._lora_arenas()
    if eng.mesh is not None and eng._mesh_collectives is None:
        # census BEFORE the call: the arenas are donated by it
        eng._mesh_collectives = eng._collective_census(
            (vkind, Bb, nbb), vprog,
            (eng.params, toks_d, pos_d, tables_d, pool.arenas,
             drafts, q_rows, keys_mid, lora_arenas, slots_d),
        )
    eng._note_attn_step()
    tr = eng._tracer
    if tr is not None:
        for r in running:
            tr.begin(r.rid, "decode", step=eng.decode_steps,
                     compile=dcompiled or vcompiled, bucket=[Bb, nbb],
                     lane="decode", attn=eng._attn_path, spec=True, K=K)
    with eng._span("serve.decode_dispatch.call"), \
            eng._compile_span(vcompiled, vkind, Bb, nbb):
        emitted, n_emit, y, new_keys, new_pos, arenas = vprog(
            eng.params, toks_d, pos_d, tables_d, pool.arenas,
            drafts, q_rows, keys_mid, lora_arenas, slots_d,
        )
    # past the point of no return: the call consumed the donated arenas
    eng._fault_point(FP_SCATTER, tuple(r.rid for r in running))
    pool.set_arenas(arenas)
    eng._spec_state = {
        "sig": sig, "toks": y, "pos": new_pos, "tables": tables_d,
        "keys": new_keys, "slots": slots_d, "host_pos": host_pos,
    }
    rec = {"kind": "decode", "spec": True, "running": running,
           "emitted": emitted, "n_emit": n_emit, "new_keys": new_keys,
           "pos": host_pos, "bucket": [Bb, nbb], "vkind": vkind,
           "compiled": dcompiled or vcompiled, "step": eng.decode_steps,
           "steady": steady,
           "t_disp": time.perf_counter(), "t_clock": sch.clock()}
    eng.decode_steps += 1
    eng.spec_rounds += 1
    eng._occupancy_sum += len(running)
    eng._m_steps_decode.inc()
    eng._m_spec_rounds.inc()
    eng._m_occupancy.observe(len(running))
    return rec


def spec_decode_harvest(eng, rec: dict) -> None:
    """Materializes one speculative round: per live row, advance the key
    chain and position by the row's own ``n_emit``, then emit the accepted
    prefix + correction token IN ORDER through ``_emit_token`` (EOS/length
    can finish the row mid-round — surplus tokens drop, like solo's
    buffer trim past ``max_new``).  Feeds the acceptance histogram
    (``serving.spec.accept_len``) and the accepted/drafted counters."""
    from thunder_tpu.serving.faults import FP_HARVEST

    running = rec["running"]
    eng._fault_point(FP_HARVEST, tuple(r.rid for r in running))
    t0 = time.perf_counter()
    with eng._span("serve.harvest.wait", kind="decode", rows=len(running)):
        emitted = np.asarray(rec["emitted"])           # the host block
        n_emit = np.asarray(rec["n_emit"])
        new_keys = np.asarray(rec["new_keys"])
    stall = time.perf_counter() - t0
    with eng._span("serve.harvest.emit"):
        _spec_emit(eng, rec, t0, stall, emitted, n_emit, new_keys)


def _spec_emit(eng, rec: dict, t0: float, stall: float, emitted, n_emit, new_keys) -> None:
    running = rec["running"]
    if eng.async_step:
        overlapped = t0 - rec["t_disp"]
        frac = overlapped / (overlapped + stall) if (overlapped + stall) > 0 else 0.0
        eng._stall_s_sum += stall
        eng._overlap_frac_sum += frac
        eng._overlap_obs += 1
        eng._m_stall.observe(stall)
        eng._m_overlap.set(frac)
    K = eng.spec.K
    gp, gtag = eng._goodput, None
    if gp is not None:
        # exact pre-emit classification of the round's device slots, two
        # dispatches per round.  Draft (Bb x K): accepted positions are
        # committed from the verifier's ne-1 (trim-independent, so the
        # ledger's acceptance integers reproduce spec_accepted_tokens /
        # spec_draft_tokens exactly); the rest were rejected.  Verify
        # (Bb x (K+1)): committed slots are the tokens that actually
        # stream; unused verify positions are draft_rejected; accepted-
        # but-trimmed (EOS/length mid-round) slots are dead scan rows.
        Bb = rec["bucket"][0]
        d_comm = d_rej = d_dead = 0
        v_comm = v_rej = v_dead = 0
        for i, r in enumerate(running):
            if r.state != "running":
                d_dead += K
                v_dead += K + 1
                continue
            ne = int(n_emit[i])
            d_comm += ne - 1
            d_rej += K - (ne - 1)
            streamed = min(ne, r.max_new_tokens - len(r.generated))
            if eng.eos_id is not None:
                for s in range(streamed):
                    if int(emitted[i, s]) == eng.eos_id:
                        streamed = s + 1
                        break
            v_comm += streamed
            v_rej += (K + 1) - ne
            v_dead += ne - streamed
        npad = Bb - len(running)
        gp.account("draft_decode", Bb, K, committed=d_comm,
                   **{k: v for k, v in (("pad_row", npad * K),
                                        ("draft_rejected", d_rej),
                                        ("dead_scan_row", d_dead)) if v})
        gtag = gp.account(rec["vkind"], Bb, K + 1, committed=v_comm,
                          **{k: v for k, v in (("pad_row", npad * (K + 1)),
                                               ("draft_rejected", v_rej),
                                               ("dead_scan_row", v_dead))
                             if v})
        # one wall interval covers both programs: split by their slot share
        dt = time.perf_counter() - rec["t_disp"]
        gp.note_device_s("draft_decode", dt * K / (2 * K + 1))
        gp.note_device_s(rec["vkind"], dt * (K + 1) / (2 * K + 1))
    tr = eng._tracer
    if tr is not None:                                 # tokens host-visible
        for r in running:
            tr.end(r.rid, "decode",
                   **({"goodput": gtag} if gtag is not None else {}))
    if eng._flight is not None:
        eng._flight.record("decode", step=rec["step"], batch=len(running),
                           bucket=rec["bucket"], compiled=rec["compiled"],
                           rids=[r.rid for r in running], spec=True,
                           accept_len=[int(n_emit[i]) for i in range(len(running))],
                           **({"goodput": gtag} if gtag is not None else {}))
    pos = rec["pos"]
    count = 0
    invalidate = False
    for i, r in enumerate(running):
        if r.state != "running":
            invalidate = True                          # finished mid-flight
            continue
        ne = int(n_emit[i])
        r.key = new_keys[i]
        r.pos = int(pos[i]) + ne
        eng._spec_accept_hist[ne - 1] += 1
        eng.spec_draft_tokens += K
        eng.spec_accepted_tokens += ne - 1
        eng._m_spec_accept_len.observe(ne)
        if ne > 1:
            eng._m_spec_accepted.inc(ne - 1)
        for k in range(ne):
            count += 1
            eng._emit_token(r, int(emitted[i, k]))
            if r.state != "running":
                # EOS/length landed mid-round: the remaining accepted
                # tokens were never promised — drop them (solo trims the
                # same overshoot off its fixed buffer)
                invalidate = True
                break
    if gp is not None:
        gp.commit_tokens(count)
    eng.tokens_generated += count
    eng.decode_lane_tokens += count
    eng.host_visits += 1
    eng._m_host_visits.inc()
    if count:
        eng._m_tokens.inc(count)
    if invalidate:
        # the chained round inputs assumed an unchanged batch/tables;
        # the next dispatch rebuilds from host state
        eng._spec_state = None
    else:
        st = eng._spec_state
        if st is not None:
            # the device chain already carries pos + n_emit; mirror it on
            # the host (a NEW array — rec["pos"] must keep dispatch's view)
            st["host_pos"] = st["host_pos"] + n_emit
