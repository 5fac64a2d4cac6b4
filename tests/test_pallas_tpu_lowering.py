"""Every ``pallas_call`` site lowered for the TPU, on the CPU.

The CPU suite runs the kernels under the Pallas interpreter
(``pallasex._interpret()`` is true off-TPU), and the interpreter accepts
what the TPU compiler refuses: a block whose last two dims neither tile by
(8, 128) nor span the array, a ``scatter``, a matmul without a float32
accumulator.  Nine of the twelve serving variants were refused at lowering
for exactly those reasons while every differential test passed.  Here each
kernel is lowered with ``lowering_platforms=("tpu",)`` and ``interpret``
forced off — and, where the installed ``libtpu`` hands out a device-less
``v5e`` topology, compiled by the real Mosaic too, which is where a
``dot_general`` Mosaic cannot lay out shows up.  Seconds, no chip.

One GQA shape (4 query heads per KV group, as Mistral-7B) and one MHA shape;
head size 128 and block size 16 as served.  A variant left unrepaired would
be ``xfail(strict=True)`` with the compiler's message; none is.  The decode
walk is also compiled at the benchmark cell's shapes (plain, windowed, int8,
fp8), at head size 256 and block size 8, and at head sizes 64 and 96, where
Mosaic refuses the walk's copies and the token goes block by block; a head of
64 in a lane-packed arena (two KV heads a 128-lane row) is walked, at the LFM2
cell's shapes and in its two programs.  The flash
kernels are compiled with ``lse`` as lane-dense rows ``(BH, 1, T)``, with a
padding mask and a mask block a grid step (the transposes of
``_flash_bwd_dkv``), and at the train cells' own kinds: a window that spans
several blocks of 1024, head 256 with 8 query heads a group, and the widest
tiles blocks of 1024 are derived for (float32 at head 128; a padding mask's
row over the whole rectangle).  The scan kernels (``gdn_chunk_fwd``,
``gdn_chunk_bwd``) are compiled with the log-decay and beta a row a chunk and
the state a block, at the widest heads ``_gdn_supported`` lets through, and at
the hybrid cell's own shape, where no float32 column may reach them.  The
causal conv's two (``causal_conv1d_fwd``, ``causal_conv1d_bwd``) are compiled
with a ragged last tile and at both hybrid models' published widths.
"""
from __future__ import annotations

import functools
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from thunder_tpu.executors import pallasex as px
from thunder_tpu.serving.kernel_check import run_checks

BF, F32, I32, I8, F8 = jnp.bfloat16, jnp.float32, jnp.int32, jnp.int8, jnp.float8_e4m3fn
HS, BS, L, B, NBB, NB, T = 128, 16, 2, 2, 4, 9, 32
SHAPES = {"gqa": (8, 2), "mha": (4, 4)}          # (n_head, n_query_groups)


@pytest.fixture(scope="module")
def tpu_sharding():
    """A sharding on a TPU v5e device that exists only as a compile target,
    or None where this installation cannot describe one (lowering alone is
    checked then)."""
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    try:
        topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — no libtpu, or one without AOT topologies
        print(f"no TPU topology here ({type(e).__name__}: {e}); lowering only")
        return None
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(autouse=True)
def compiled_not_interpreted(monkeypatch):
    """As on the chip: Pallas on (the paged attention entries take their kernels
    and not the XLA form) and nothing interpreted."""
    monkeypatch.setattr(px, "_interpret", lambda: False)
    monkeypatch.setattr(px, "_pallas_available", lambda: True)


def _cases(nh: int, ng: int) -> dict:
    """name -> (fn, [(shape, dtype), ...]) for every pallas_call variant."""
    q, fk = ((B, nh, HS), BF), ((B, ng, HS), BF)
    qT, fT = ((B, nh, T, HS), BF), ((B, ng, T, HS), BF)
    q5, f5 = ((B, nh, 5, HS), BF), ((B, ng, 5, HS), BF)
    tab, pos, ne = ((B, NBB), I32), ((B,), I32), ((B,), I32)
    vals, svals = ((B, L, ng, HS), BF), ((B, L, ng), F32)
    scales = ((NB, L, ng, BS), F32)
    chunk, dest, p1 = ((T // BS, L, ng, BS, HS), BF), ((NBB,), I32), ((1,), I32)

    def arena(dt):
        return ((NB, L, ng, BS, HS), dt)

    decode = functools.partial(px.paged_attn_decode, layer=1)
    verify = functools.partial(px.paged_attn_verify, layer=1)
    write = functools.partial(px.paged_token_write, block_size=BS)
    fused = functools.partial(px.paged_token_write_fused, block_size=BS)
    cases = {
        "paged_attn_decode": (decode, [q, arena(BF), arena(BF), fk, fk, tab, pos]),
        "paged_attn_decode/window": (
            functools.partial(decode, window=24), [q, arena(BF), arena(BF), fk, fk, tab, pos]),
        "paged_attn_verify": (verify, [qT, arena(BF), arena(BF), fT, fT, tab, pos]),
        "paged_attn_verify/T5": (verify, [q5, arena(BF), arena(BF), f5, f5, tab, pos]),
        "paged_token_write": (write, [arena(BF), vals, tab, pos]),
        "paged_token_write/scales": (write, [scales, svals, tab, pos]),
        "paged_token_write/masked": (
            lambda a, v, t, p, n: write(a, v, t, p, n_emit=n, offset=1),
            [arena(BF), vals, tab, pos, ne]),
        "paged_token_write/masked_scales": (
            lambda a, v, t, p, n: write(a, v, t, p, n_emit=n, offset=0),
            [scales, svals, tab, pos, ne]),
        "paged_chunk_write": (
            functools.partial(px.paged_chunk_write, block_size=BS),
            [arena(BF), chunk, dest, p1]),
        "lora_delta_fused/T1": (
            functools.partial(px.lora_delta_fused, scaling=2.0),
            [((B, 1, nh * HS), BF), ((B, 8, nh * HS), BF), ((B, ng * HS, 8), BF)]),
        "lora_delta_fused/chunk": (
            functools.partial(px.lora_delta_fused, scaling=2.0),
            [((1, T, nh * HS), BF), ((1, 8, nh * HS), BF), ((1, ng * HS, 8), BF)]),
    }
    for name, dt in (("int8", I8), ("fp8", F8)):
        quant = lambda f: (lambda q_, k, v, ks, vs, fk_, fv, t, p:
                           f(q_, k, v, fk_, fv, t, p, k_scale=ks, v_scale=vs))
        cases[f"paged_attn_decode/{name}"] = (
            quant(decode), [q, arena(dt), arena(dt), scales, scales, fk, fk, tab, pos])
        cases[f"paged_attn_verify/{name}"] = (
            quant(verify), [qT, arena(dt), arena(dt), scales, scales, fT, fT, tab, pos])
        cases[f"paged_token_write_fused/{name}"] = (
            fused, [arena(dt), scales, vals, tab, pos])
        cases[f"paged_token_write_fused/{name}/masked"] = (
            lambda a, s, v, t, p, n: fused(a, s, v, t, p, n_emit=n, offset=1),
            [arena(dt), scales, vals, tab, pos, ne])
        cases[f"paged_chunk_write_fused/{name}"] = (
            functools.partial(px.paged_chunk_write_fused, block_size=BS),
            [arena(dt), scales, chunk, dest, p1])

    # the training kernels: the jitted inner functions are called unwrapped,
    # so no cached interpreted trace can stand in for the compiled one
    Tq, scale = 256, 1.0 / np.sqrt(HS)
    qf, kf = ((B * nh, Tq, HS), BF), ((B * ng, Tq, HS), BF)
    lse = ((B * nh, 1, Tq), F32)
    shared, full = ((1, 1, Tq), F32), ((B * nh, Tq, Tq), F32)

    def flash(name, window=None, mask=None, mode=None, mq=1, causal=True, heads=(nh, ng), ops=(qf, kf, lse)):
        qs, ks, ls = ops
        ms = [] if mask is None else [mask]
        cases[f"flash_sdpa_fwd{name}"] = (
            lambda q_, k, v, *m: px._flash_fwd.__wrapped__(
                q_, k, v, *(m or (None,)), causal, scale, *heads, mode, mq, window), [qs, ks, ks, *ms])
        cases[f"flash_sdpa_bwd{name}"] = (
            lambda g, q_, k, v, o, l, *m: px._flash_bwd.__wrapped__(
                g, q_, k, v, o, l, *(m or (None,)), causal, scale, *heads, mode, mq, window),
            [qs, qs, ks, ks, qs, ls, *ms])

    flash("")
    flash("/window", window=128)
    flash("/padding_mask", mask=shared, mode="shared", mq=1, causal=False)
    flash("/full_mask", mask=full, mode="full", mq=Tq)
    # the benchmark cells' own kinds: a window that spans several blocks, and
    # head 256 with 8 query heads a group and no window (T cut, blocks as there)
    Tc = 2048
    flash("/band_of_blocks", window=1024,
          ops=(((nh, Tc, HS), BF), ((ng, Tc, HS), BF), ((nh, 1, Tc), F32)))
    flash("/head256_rep8", heads=(8, 1),
          ops=(((8, Tc, 256), BF), ((1, Tc, 256), BF), ((8, 1, Tc), F32)))
    # ... under a window: 512 bytes a row and the window's compare do not fit blocks of 1024 (``_flash_bwd_dkv``)
    flash("/head256_window", window=2048, heads=(8, 1),
          ops=(((8, 2 * Tc, 256), BF), ((1, 2 * Tc, 256), BF), ((8, 1, 2 * Tc), F32)))
    # the widest tiles ``_flash_blocks`` derives blocks of 1024 for: float32 at
    # head 128, and every block of the rectangle with a padding mask's row
    flash("/float32_blocks_of_1024",
          ops=(((nh, Tc, HS), F32), ((ng, Tc, HS), F32), ((nh, 1, Tc), F32)))
    flash("/padding_mask_blocks_of_1024", mask=((1, 1, Tc), F32), mode="shared", mq=1, causal=False,
          ops=(((nh, Tc, HS), BF), ((ng, Tc, HS), BF), ((nh, 1, Tc), F32)))
    # ... and with a ragged last block (T 6912 = 6.75 blocks of 1024): the tail's form of each body beside the other two
    Tr = 6912
    flash("/ragged_head256_rep8", heads=(8, 1), ops=(((8, Tr, 256), BF), ((1, Tr, 256), BF), ((8, 1, Tr), F32)))
    cases["flash_cross_entropy"] = (
        px._flash_ce.__wrapped__, [((256, 2048), BF), ((256,), I32)])
    # the chunked gated delta rule (2 key heads, 4 value heads, two blocks of
    # 8 chunks; the log-decay and beta a row a chunk, the state a block) and
    # the grouped products over sorted rows (8 tiles, 4 groups)
    Tg, Cg = 1024, 64

    def gdn_bwd_specs(hs, dt):        # do, then the forward's q, k, v, G, beta, then the states
        qk, v, row = ((2, Tg, hs), dt), ((4, Tg, hs), dt), ((4, Tg // Cg, Cg), F32)
        return [v, qk, qk, v, row, row, ((4, Tg // 512, hs, hs), F32)]

    gdn_bwd = lambda do, q_, k, v, g, b, st: px._gdn_bwd.__wrapped__(do, q_, k, v, g, b, st, 2, 4, Cg, 512)  # noqa: E731
    cases["gdn_chunk_fwd"] = (
        lambda q_, k, v, g, b: px._gdn_fwd.__wrapped__(q_, k, v, g, b, 2, 4, Cg, 512), gdn_bwd_specs(HS, BF)[1:6])
    cases["gdn_chunk_bwd"] = (gdn_bwd, gdn_bwd_specs(HS, BF))
    # the widest blocks ``_gdn_supported`` lets through: what a block's chunks
    # keep live between the kernel's walks has to fit beside the operands
    cases["gdn_chunk_bwd/float32"] = (gdn_bwd, gdn_bwd_specs(HS, F32))
    cases["gdn_chunk_bwd/head256"] = (gdn_bwd, gdn_bwd_specs(2 * HS, BF))
    # the server's two: the scan from a state to a state, and one step a row on the
    # state arena in place, at heads of 96 and 192 (three fourths of a lane tile
    # and one and a half: the arena's four heads side by side are six tiles, walked
    # two heads at a time), and at a bfloat16 arena
    cases["gdn_chunk_fwd/state"] = (
        lambda q_, k, v, g, b, h: px._gdn_fwd.__wrapped__(q_, k, v, g, b, 2, 4, Cg, 512, h0=h),
        gdn_bwd_specs(HS, BF)[1:6] + [((4, HS, HS), F32)])
    step = lambda a, s, q_, k, v, g, b: px.gdn_decode_step(a, s, q_, k, v, g, b, layer=1)  # noqa: E731
    step_specs = lambda dt: [((5, 2, 96, 4 * 192), dt), ((3,), I32), ((3, 2, 96), BF), ((3, 2, 96), BF),  # noqa: E731
                             ((3, 4, 192), BF), ((3, 4), F32), ((3, 4), F32)]
    cases["gdn_decode_step"] = (step, step_specs(F32))
    cases["gdn_decode_step/bfloat16"] = (step, step_specs(BF))
    rows, tiles, plan = ((1024, 256), BF), ((8,), I32), ((1,), I32)
    cases["moe_grouped_mm"] = (
        px._moe_grouped_mm.__wrapped__, [rows, ((4, 256, 512), BF), tiles, plan])
    cases["moe_grouped_mm/transposed"] = (
        lambda x, w, tg, tu: px._moe_grouped_mm.__wrapped__(x, w, tg, tu, transpose_w=True),
        [rows, ((4, 512, 256), BF), tiles, plan])
    cases["moe_grouped_mm_dw"] = (
        lambda x, dy, tg, tu: px._moe_grouped_mm_dw.__wrapped__(x, dy, tg, tu, groups=4),
        [rows, ((1024, 512), BF), tiles, plan])
    # the DeltaNet layers' causal conv with its SiLU: a ragged last tile of a float32
    # sequence (the masks of the backward pass), and three taps without an activation
    conv = lambda act, tiles: (   # noqa: E731
        lambda x, w: px._conv_fwd.__wrapped__(x, w, act, tiles),
        lambda g, x, w: px._conv_bwd.__wrapped__(g, x, w, act, tiles))
    x_f32, x_bf = ((2, 1040, 384), F32), ((1, 2048, 256), BF)
    fwd, bwd = conv("silu", px._conv_tiles(1040, 384, 4))
    cases["causal_conv1d_fwd"] = (fwd, [x_f32, ((384, 4), F32)])
    cases["causal_conv1d_bwd"] = (bwd, [x_f32, x_f32, ((384, 4), F32)])
    fwd, bwd = conv(None, px._conv_tiles(2048, 256, 2))
    cases["causal_conv1d_fwd/three_taps"] = (fwd, [x_bf, ((256, 3), BF)])
    cases["causal_conv1d_bwd/three_taps"] = (bwd, [x_bf, x_bf, ((256, 3), BF)])
    return cases


CASES = {shape: _cases(*heads) for shape, heads in SHAPES.items()}


def kernel_names(case: str) -> list[str]:
    """The ``name=`` of every ``pallas_call`` a case makes: the function that
    builds the kernel, and its variant (quantised arenas, keep-masked write)."""
    base, _, variant = case.partition("/")
    if base == "flash_sdpa_fwd":
        return ["_flash_fwd"]
    if base == "flash_sdpa_bwd":
        return ["_flash_bwd_dq", "_flash_bwd_dkv"]
    if base in ("paged_attn_decode", "paged_attn_verify") and variant in ("int8", "fp8"):
        base += "_quant"
    if variant.startswith("masked") or variant.endswith("/masked"):
        base += "_masked"
    return [base]


@pytest.mark.parametrize("kernel", ["gdn_chunk_state", "gdn_decode_step"])
def test_the_servers_scan_and_step_compile_at_the_published_heads(kernel, tpu_sharding, monkeypatch):
    """Olmo-Hybrid's delta-rule heads are 96 and 192 wide, neither a whole
    lane tile.  The scan reaches ``gdn_chunk_fwd`` padded to 128 and 256
    (zero columns: exact) instead of falling to the XLA form, at the longest
    prefill bucket; the step takes a row of the arena, its thirty heads side by
    side as ``(96, 5760)`` (45 lane tiles, no padding), a grid step, two heads at
    a time, 32 rows of 12 layers' arena.
    Only the compile shows that Mosaic takes those blocks at these shapes and
    that XLA's call keeps the arena aliased to its result."""
    monkeypatch.setattr(px, "_interpret", lambda: False)
    monkeypatch.setattr(px, "_enabled", lambda: True)
    H, dk, dv = 30, 96, 192
    if kernel == "gdn_chunk_state":
        Ts = 2560
        fn = px.gdn_chunk_state
        specs = [((1, H, Ts, dk), BF), ((1, H, Ts, dk), BF), ((1, H, Ts, dv), BF), ((1, H, Ts), F32), ((1, H, Ts), F32),
                 ((1, H, dk, dv), F32)]
        name = "gdn_chunk_fwd"
    else:
        fn = functools.partial(px.gdn_decode_step, layer=11)
        specs = [((33, 12, dk, H * dv), F32), ((32,), I32), ((32, H, dk), BF), ((32, H, dk), BF), ((32, H, dv), BF),
                 ((32, H), F32), ((32, H), F32)]
        name = "gdn_decode_step"
    before = dict(px.stats)
    args = [jax.ShapeDtypeStruct(s, dt, sharding=tpu_sharding) for s, dt in specs]
    lowered = jax.jit(fn).trace(*args).lower(lowering_platforms=("tpu",))
    text = lowered.as_text()
    assert text.count("tpu_custom_call") == 1 and f'kernel_name = "{name}"' in text
    claim = "gdn" if kernel == "gdn_chunk_state" else "gdn_decode"
    assert px.stats.get(claim, 0) == before.get(claim, 0) + 1            # claimed, not the XLA form
    if tpu_sharding is not None:
        compiled = lowered.compile()
        call = next(l for l in compiled.as_text().splitlines() if re.search(rf"%{name}(\.\d+)? = ", l))
        if kernel == "gdn_decode_step":
            # in place: the arena aliases its result; and nothing of the call's
            # shape fits the reader that finds paged_attn_decode by its operands
            assert "output_to_operand_aliasing" in call
            assert not re.match(r"^\s*%\S+ = \w+\[\d+(,\d+){3}\]\S* custom-call\(s32\[\d+,\d+\]", call)


@pytest.mark.parametrize("kernel", ["causal_conv1d_fwd", "causal_conv1d_bwd"])
@pytest.mark.parametrize("cell", ["qwen3next_train", "olmo_hybrid"])
def test_conv_kernels_compile_at_the_published_widths(cell, kernel, tpu_sharding, monkeypatch):
    """The causal conv as a DeltaNet layer calls it: q | k | v of Qwen3-Next
    side by side over the hybrid cell's two sequences, ``(2, 8192, 8192)``, and
    of Olmo-Hybrid over a prompt, ``(1, 2560, 11520)`` (90 lane tiles: tiles of
    768 channels).  One kernel a call, no padded or float32 copy beside it: the
    call's only temporaries are the backward pass's partial rows of ``dw``:
    only the compile shows them (``memory_analysis``), and that Mosaic takes
    the tiles."""
    monkeypatch.setattr(px, "_enabled", lambda: True)
    B_, Ts, C = {"qwen3next_train": (2, 8192, 8192), "olmo_hybrid": (1, 2560, 11520)}[cell]
    x, w = ((B_, Ts, C), BF), ((C, 4), BF)
    if kernel == "causal_conv1d_fwd":
        fn, specs = functools.partial(px.causal_conv1d, activation="silu"), [x, w]
    else:
        fn, specs = functools.partial(px.causal_conv1d_backward, activation="silu"), [x, x, w]
    before = px.stats.get("causal_conv", 0)
    args = [jax.ShapeDtypeStruct(s, dt, sharding=tpu_sharding) for s, dt in specs]
    lowered = jax.jit(fn).trace(*args).lower(lowering_platforms=("tpu",))
    assert px.stats["causal_conv"] == before + 1
    assert px.conv_schedule["bytes_a_forward_call"] == 2 * B_ * Ts * C * 2
    assert C % px.conv_schedule["tile_c"] == 0 and px.conv_schedule["tile_t"] == {8192: 512, 11520: 640}[C]
    text = lowered.as_text()
    assert text.count("tpu_custom_call") == 1 and f'kernel_name = "{kernel}"' in text
    if tpu_sharding is not None:
        compiled = lowered.compile()
        assert re.search(rf"%{kernel}(\.\d+)? = ", compiled.as_text())
        # at most dw's eight float32 rows a tap a sequence and its small sums; never a copy of x
        assert compiled.memory_analysis().temp_size_in_bytes <= 4 * B_ * 8 * 4 * C * 4


@pytest.mark.parametrize("shape", SHAPES)
@pytest.mark.parametrize("kernel", CASES["gqa"])
def test_kernel_lowers_and_compiles_for_tpu(shape, kernel, tpu_sharding):
    """Every kernel at a small shape, lowered to a Mosaic call under its name.
    Only the compile shows that Mosaic takes its blocks and operations (the
    interpreter takes what the TPU compiler refuses) and what XLA names the call."""
    fn, specs = CASES[shape][kernel]
    args = [jax.ShapeDtypeStruct(s, dt, sharding=tpu_sharding) for s, dt in specs]
    lowered = jax.jit(fn).trace(*args).lower(lowering_platforms=("tpu",))
    text = lowered.as_text()
    assert "tpu_custom_call" in text   # Mosaic, not the interpreter
    for name in kernel_names(kernel):
        assert f'kernel_name = "{name}"' in text, (kernel, name)
    if tpu_sharding is not None:
        # the TPU compiler names the custom call after its innermost scope,
        # and that is the name an operation has in a device trace
        compiled = lowered.compile().as_text()
        for name in kernel_names(kernel):
            assert re.search(rf"%{name}(\.\d+)? = ", compiled), (kernel, name)


# H, G, T (None: ``COMPILED_BUCKET``), head, window, a full mask a query row
ONE_WALK = {"bucket": (8, 2, None, HS, None, False), "bucket_full_mask": (8, 2, None, HS, None, True),
            "mistral_train": (32, 8, 8192, HS, 4096, False), "head256_rep8": (8, 1, 2048, 256, None, False),
            "ragged": (8, 2, 2304, HS, None, False)}


@pytest.mark.parametrize("case", ONE_WALK)
def test_flash_bwd_one_walk_compiles_for_a_v5e(case, tpu_sharding, monkeypatch):
    """The backward pass as the chip builds it (PR 63), the grouped product's way
    of giving a compile on this CPU a v5e's 96 MiB to ask for: one kernel named
    ``_flash_bwd``, five products a form of its body, dq of a head and dk, dv
    of the group resident in float32 beside the output blocks, and the limit
    it states from those bytes.  At ``COMPILED_BUCKET``, with a mask a query
    row, at the Mistral train cell's call (24 MiB resident), at heads of 256,
    8 to a group, and with a ragged last block (the tail's form of the body).  Only the compile shows that
    Mosaic takes the product over the tile's first axis and sums into rows of
    a scratch the whole sequence long.  ``CASES`` keeps the two kernels: they
    are what this CPU's 16 MiB take."""
    for which in "QK":
        monkeypatch.delenv(f"THUNDER_TPU_FLASH_B{which}", raising=False)
    monkeypatch.setattr(px, "_gmm_vmem_cap", lambda: 96 << 20)
    H, G, T_, hs, window, masked = ONE_WALK[case]
    T_ = T_ or COMPILED_BUCKET
    spec = lambda *shape, dt=BF: jax.ShapeDtypeStruct(shape, dt, sharding=tpu_sharding)  # noqa: E731
    q, k, lse = spec(H, T_, hs), spec(G, T_, hs), spec(H, 1, T_, dt=F32)
    mask = [spec(H, T_, T_, dt=F32)] if masked else []
    before = dict(px.stats)
    lowered = jax.jit(lambda g, q_, k_, v, o, l, *m: px._flash_bwd.__wrapped__(
        g, q_, k_, v, o, l, *(m or (None,)), True, hs ** -0.5, H, G, "full" if masked else None, T_ if masked else 1, window,
    )).trace(q, q, k, k, q, lse, *mask).lower(lowering_platforms=("tpu",))
    sched = dict(px.flash_schedule)
    block = sched["block_q"]
    assert sched["bwd_form"] == "one_walk" and sched["bwd_grid_steps"] == H // G * sched["grid_steps"]
    assert sched["bwd_resident_bytes"] == 4 * hs * 3 * -(-T_ // block) * block + 2 * 2 * hs * 3 * T_
    assert px.stats["flash_bwd_one_walk"] == before.get("flash_bwd_one_walk", 0) + 1
    assert px.stats.get("flash_bwd_two_kernels", 0) == before.get("flash_bwd_two_kernels", 0)
    text = lowered.as_text()
    assert text.count("tpu_custom_call") == 1 and 'kernel_name = "_flash_bwd"' in text
    assert f'\\22size\\22: {sched["bwd_resident_bytes"] + (16 << 20)}}}' in text       # the scoped limit the call states
    module = _mosaic_module(text)
    assert module.count("tpu.matmul") == 5 * (3 if sched["tail_rows"] else 2)      # whole and edge, and the tail's
    assert f"memref<{-(-T_ // block) * block}x{hs}xf32, #tpu.memory_space<vmem>>" in module      # the sums, a sequence long
    if tpu_sharding is not None:
        assert re.search(r"%_flash_bwd(\.\d+)? = ", lowered.compile().as_text())


def _lowered_flash_fwd(H, G, T, hs, hv, sharding, window=None):
    q = jax.ShapeDtypeStruct((H, T, hs), BF, sharding=sharding)
    k = jax.ShapeDtypeStruct((G, T, hs), BF, sharding=sharding)
    v = jax.ShapeDtypeStruct((G, T, hv), BF, sharding=sharding)
    return jax.jit(lambda q_, k_, v_: px._flash_fwd.__wrapped__(
        q_, k_, v_, None, True, hs ** -0.5, H, G, None, 1, window)).trace(q, k, v).lower(lowering_platforms=("tpu",))


@pytest.mark.parametrize("window", [None, 2048], ids=["global", "window2048"])
@pytest.mark.parametrize("T", [3840, 5888, 7936, 9984])
def test_flash_fwd_compiles_at_the_docqa_buckets_with_a_ragged_last_block(T, window, tpu_sharding, monkeypatch):
    """``trinity-mini-serve-1chip.offline-docqa``'s four prefill buckets, 32
    heads over 4 of 128, a global layer's call and a window layer's: odd
    multiples of 256, which ``_flash_blocks`` gives blocks of 1024 whose last
    reaches 256 rows past the end.  Mosaic compiles that for a v5e (VMEM
    fits, a ragged block's copies are laid out: what only the compile
    shows); the tail's form of the body
    is in the module there and in none at 8192, which the block divides."""
    monkeypatch.delenv("THUNDER_TPU_FLASH_BQ", raising=False)
    monkeypatch.delenv("THUNDER_TPU_FLASH_BK", raising=False)

    lowered = lambda T_: _lowered_flash_fwd(32, 4, T_, HS, HS, tpu_sharding, window)  # noqa: E731
    whole = _mosaic_module(lowered(8192).as_text())
    assert px.flash_schedule["block_q"] == 1024 and px.flash_schedule["tail_rows"] == 0
    ragged = lowered(T)
    assert (px.flash_schedule["block_q"], px.flash_schedule["block_k"], px.flash_schedule["tail_rows"]) == (1024, 1024, 256)
    module = _mosaic_module(ragged.as_text())
    # three forms of the body for two: one more pair of products, and the select that zeroes V's tail
    assert module.count("tpu.matmul") == whole.count("tpu.matmul") + 2 == 6
    if tpu_sharding is not None:
        assert re.search(r"%_flash_fwd(\.\d+)? = ", ragged.compile().as_text())


@pytest.mark.parametrize("window", [None, 4096], ids=["global", "window4096"])
@pytest.mark.parametrize("T", [2560, 5120, 7680, 10240])
def test_flash_fwd_compiles_at_seven_query_heads_a_kv_head(T, window, tpu_sharding, monkeypatch):
    """``smallthinker-serve-1chip.offline-mixedlen``'s four prefill buckets, 28 heads
    over 4 of 128: ``rep`` = 7 reaches the kernel through its index map alone (``kh =
    h // rep``), a global layer's causal call and a window layer's banded one at a
    window of 4,096.  Blocks of 1024, ragged by 512 at the odd buckets; the band at
    10,240 keeps 40 of the triangle's 55 blocks."""
    monkeypatch.delenv("THUNDER_TPU_FLASH_BQ", raising=False)
    monkeypatch.delenv("THUNDER_TPU_FLASH_BK", raising=False)
    lowered = _lowered_flash_fwd(28, 4, T, HS, HS, tpu_sharding, window)
    sched, n = px.flash_schedule, -(-T // 1024)
    assert (sched["block_q"], sched["block_k"], sched["tail_rows"]) == (1024, 1024, -T % 1024)
    band = sum(min(i + 1, 5) for i in range(n))               # a query block sees its own and the four before it
    assert sched["grid_steps"] == (band if window and n > 5 else n * (n + 1) // 2)
    assert lowered.as_text().count('kernel_name = "_flash_fwd"') == 1
    if tpu_sharding is not None:
        assert re.search(r"%_flash_fwd(\.\d+)? = ", lowered.compile().as_text())


@pytest.mark.parametrize("heads", [32, 64], ids=["xing4", "axk1"])
def test_flash_fwd_compiles_with_values_at_their_own_width(heads, tpu_sharding, monkeypatch):
    """A latent prompt's call at both latent cells' heads and 8,192 tokens, as
    ``_fwd_local`` builds it (PR 54): q/k a head of 192 as they are (a block's
    last dimension the array's whole width), v, the accumulator and the result
    at 128.  Blocks of 1024; the score product takes 192 lanes, the weighted
    sum 128, and nothing in the module is 256 wide.  Only the compile shows
    that Mosaic lays out a block whose last dimension is 192 lanes."""
    monkeypatch.delenv("THUNDER_TPU_FLASH_BQ", raising=False)
    monkeypatch.delenv("THUNDER_TPU_FLASH_BK", raising=False)
    lowered = _lowered_flash_fwd(heads, heads, 8192, 192, 128, tpu_sharding)
    assert (px.flash_schedule["block_q"], px.flash_schedule["block_k"], px.flash_schedule["tail_rows"]) == (1024, 1024, 0)
    text = lowered.as_text()
    assert f"-> (tensor<{heads}x8192x128xbf16>, tensor<{heads}x1x8192xf32>" in text      # the result as wide as v
    module = _mosaic_module(text)
    assert module.count("tpu.matmul") == 4
    assert "vector<1024x192xbf16>, vector<1024x192xbf16>, vector<1024x1024xf32>" in module       # q k^T
    assert "vector<1024x1024xbf16>, vector<1024x128xbf16>, vector<1024x128xf32>" in module       # p v
    assert "memref<1024x128xf32, #tpu.memory_space<vmem>>" in module and not re.search(r"x256x(bf16|f32)", module)
    if tpu_sharding is not None:
        assert re.search(r"%_flash_fwd(\.\d+)? = ", lowered.compile().as_text())


def test_flash_fwd_at_one_width_is_the_module_it_was(tpu_sharding, monkeypatch):
    """The unchanged path stays the unchanged path: 32 heads over 4 of 128 at
    8,192 (Trinity-Mini's layer, the shape the ragged cases above compare
    with) lowers to two forms of the body and no tail's, every operand, the
    accumulator and the result 128 wide.  The compile: that Mosaic still
    takes the module the ragged cases are compared with."""
    monkeypatch.delenv("THUNDER_TPU_FLASH_BQ", raising=False)
    monkeypatch.delenv("THUNDER_TPU_FLASH_BK", raising=False)
    lowered = _lowered_flash_fwd(32, 4, 8192, HS, HS, tpu_sharding)
    assert (px.flash_schedule["block_q"], px.flash_schedule["tail_rows"]) == (1024, 0)
    module = _mosaic_module(lowered.as_text())
    assert module.count("tpu.matmul") == 4 and "arith.select" in module          # the edge form's mask, no tail form
    assert module.count("vector<1024x128xbf16>, vector<1024x128xbf16>, vector<1024x1024xf32>") == 2
    assert module.count("vector<1024x1024xbf16>, vector<1024x128xbf16>, vector<1024x128xf32>") == 2
    assert not re.search(r"x(192|256)x(bf16|f32)", module)
    if tpu_sharding is not None:
        assert re.search(r"%_flash_fwd(\.\d+)? = ", lowered.compile().as_text())


@pytest.mark.parametrize("kernel", ["gdn_chunk_fwd", "gdn_chunk_bwd"])
def test_scan_kernels_at_the_hybrid_cell_take_rows_not_columns(kernel, tpu_sharding, monkeypatch):
    """The chunked gated delta rule as the hybrid cell calls it (two sequences
    of 8192 tokens, 16 key and 32 value heads of 128): the log-decay, beta
    and their gradients reach the kernels a row a chunk.  A ``(..., T, 1)``
    float32 operand or result is a column of 128-lane tiles, 128 times its
    bytes in HBM and in the copies XLA puts before the call (268 MB each
    here); the backward call takes the state a block of 512 tokens and no
    forward call comes before it.  Only the compile shows the layouts XLA
    gives the call's operands and that the forward needs no temporary."""
    monkeypatch.setattr(px, "_interpret", lambda: False)
    monkeypatch.setattr(px, "_enabled", lambda: True)
    B_, Hk, Hv, Ts = 2, 16, 32, 8192
    qk, v, g = ((B_, Hk, Ts, HS), BF), ((B_, Hv, Ts, HS), BF), ((B_, Hv, Ts), F32)
    if kernel == "gdn_chunk_fwd":
        fn, specs = px.gdn_chunk, [qk, qk, v, g, g]
    else:
        fn, specs = px.gdn_chunk_backward, [v, qk, qk, v, g, g, ((B_, Hv, Ts // 512, HS, HS), F32)]
    args = [jax.ShapeDtypeStruct(s, dt, sharding=tpu_sharding) for s, dt in specs]
    lowered = jax.jit(fn).trace(*args).lower(lowering_platforms=("tpu",))
    text = lowered.as_text()
    assert text.count("tpu_custom_call") == 1 and f'kernel_name = "{kernel}"' in text
    assert not re.search(r"tensor<(\d+x)+1xf32>", text), "a float32 column reaches the kernel"
    if tpu_sharding is not None:
        compiled = lowered.compile()
        call = next(l for l in compiled.as_text().splitlines() if re.search(rf"%{kernel}(\.\d+)? = ", l))
        assert not re.search(r"f32\[(\d+,)+1\]", call), call
        if kernel == "gdn_chunk_fwd":                        # no padded copy, no temporary at all
            assert compiled.memory_analysis().temp_size_in_bytes == 0


def _decode_args(nh, ng, hs, bs, rows, width, pool, layers, store, sharding):
    """``paged_attn_decode``'s operands as shapes: ``(fn kwargs -> call, args)``
    with the scale arenas where ``store`` is a quantised dtype."""
    arena = ((pool, layers, ng, bs, hs), store)
    specs = [((rows, nh, hs), BF), arena, arena, ((rows, ng, hs), BF),
             ((rows, ng, hs), BF), ((rows, width), I32), ((rows,), I32)]
    if store != BF:
        specs += [((pool, layers, ng, bs), F32)] * 2
    return [jax.ShapeDtypeStruct(s, dt, sharding=sharding) for s, dt in specs]


def _decode(layer, window=None):
    def fn(q, k, v, fk, fv, tables, pos, ks=None, vs=None):
        return px.paged_attn_decode(q, k, v, fk, fv, tables, pos, layer=layer,
                                    window=window, k_scale=ks, v_scale=vs)
    return fn


@pytest.mark.parametrize("variant,store,window", [
    ("plain", BF, None), ("window", BF, 4096), ("int8", I8, None), ("fp8", F8, None)])
def test_decode_walk_compiles_at_offline_batch_shapes(variant, store, window, tpu_sharding):
    """``paged_attn_decode`` at the benchmark cell's own shapes (Mistral-7B
    heads 32/8, 32 rows, a table 224 blocks wide, the cell's pool): the chunk
    the kernel derives there, and the VMEM its buffers and its score tile
    take, are checked by Mosaic without a chip (only the compile does, as it
    alone shows the call the roofline reader matches and that no arena is
    copied).  The quantised stores walk
    twice the blocks a chunk (C = 32) and add the two dequantised buffers."""
    nh, ng, rows, width, pool, layers = 32, 8, 32, 224, 6144, 16
    quantized = store != BF
    assert px.paged_kv_chunk_blocks(ng, BS, HS, jnp.dtype(store).itemsize) * BS == (
        512 if quantized else 256)
    args = _decode_args(nh, ng, HS, BS, rows, width, pool, layers, store, tpu_sharding)
    lowered = jax.jit(_decode(layers - 1, window)).trace(*args).lower(
        lowering_platforms=("tpu",))
    name = "paged_attn_decode" + ("_quant" if quantized else "")
    assert f'kernel_name = "{name}"' in lowered.as_text()
    if tpu_sharding is not None:
        compiled = lowered.compile()
        # the call as a device trace shows it: the block table first, one
        # four-dimensional result (what the benchmark's roofline reader matches)
        call = next(l for l in compiled.as_text().splitlines()
                    if re.search(rf"%{name}(\.\d+)? = ", l))
        assert re.search(r"= bf16\[32,8,4,128\]\S* custom-call\(%tables", call), call
        if not quantized:                                   # no arena copy
            assert compiled.memory_analysis().temp_size_in_bytes == 0


# the six serve cells that walk, from ``chipbench/configs`` and ``chipbench/traffic``: (query heads, KV heads, head size,
# rows of the decode program, table width, pool blocks, attention layers held, window, packed_out); heads of 64 lane-packed
WALK_CELLS = {
    "mistral7b-serve-1chip.offline-batch": (32, 8, 128, 32, 224, 6144, 16, 4096, False),
    "olmo-hybrid-serve-1chip.offline-longgen": (30, 30, 128, 32, 208, 5120, 4, None, False),
    "lfm2moe-serve-1chip.offline-wide": (32, 8, 64, 256, 256, 47104, 3, None, False),
    "phi4flash-serve-1chip.offline-reason": (40, 20, 64, 96, 552, 36864, 1, None, True),
    "phi4flash-serve-1chip.offline-reason/ring": (40, 20, 64, 96, 552, 97 * 33, 8, 512, True),
    "nemotron3super-serve-1chip.offline-rollouts": (32, 2, 128, 128, 496, 43008, 1, None, False),
    "trinity-mini-serve-1chip.offline-docqa": (32, 4, 128, 20, 720, 10240, 8, None, False),
    "trinity-mini-serve-1chip.offline-docqa/ring": (32, 4, 128, 20, 720, 21 * 129, 24, 2048, False),
    # 7 query heads a KV head: no power of two, no whole sublane tile of 8
    "smallthinker-serve-1chip.offline-mixedlen": (28, 4, 128, 64, 688, 25600, 2, None, False),
    "smallthinker-serve-1chip.offline-mixedlen/ring": (28, 4, 128, 64, 688, 65 * 257, 6, 4096, False),
}


@pytest.mark.parametrize("cell", WALK_CELLS)
def test_the_walk_compiles_at_every_cells_shapes_as_the_call_the_readers_find(cell, tpu_sharding, monkeypatch):
    """The rebuilt loop (static buffers a slot, a chunk's ``2 C`` starts
    unrolled: copies of 8 to 120 KB, ``C`` 32 to 4) at each cell's decode
    program's shapes: Mosaic takes its straight-line code and its VMEM, no arena
    is copied, and the compiled call is what
    ``chipbench/kernels/paged_attn_decode.py`` matches: the block table the
    first operand (the positions, ``chain`` and the layer after it), one
    four-dimensional result.  All three are the compile's to show: Mosaic's
    limits, XLA's temporaries, the call as XLA writes it."""
    monkeypatch.setattr(px, "_enabled", lambda: True)
    nh, ng, hs, rows, width, pool, layers, window, packed_out = WALK_CELLS[cell]
    groups = ng * hs // 128                                                     # the arena's rows a token
    arena = ((pool, layers, groups, BS, 128), BF)
    specs = [((rows, nh, hs), BF), arena, arena, ((rows, ng, hs), BF), ((rows, ng, hs), BF), ((rows, width), I32), ((rows,), I32)]
    args = [jax.ShapeDtypeStruct(s, dt, sharding=tpu_sharding) for s, dt in specs]
    assert px.paged_kv_chunk_blocks(groups, BS, 128, 2) == {2: 32, 4: 32, 8: 16, 10: 12, 30: 4}[groups]
    before = px.stats.get("paged_walk", 0)
    fn = functools.partial(px.paged_attn_decode, layer=layers - 1, window=window, packed_out=packed_out)
    lowered = jax.jit(fn).trace(*args).lower(lowering_platforms=("tpu",))
    assert px.stats["paged_walk"] == before + 1
    assert lowered.as_text().count('kernel_name = "paged_attn_decode"') == 1
    if tpu_sharding is not None:
        compiled = lowered.compile()
        call = next(l for l in compiled.as_text().splitlines() if re.search(r"%paged_attn_decode(\.\d+)? = ", l))
        assert re.search(rf"= bf16\[{rows},{groups},{nh // groups},128\]\S* custom-call\(%", call), call
        shapes = {m.group(1): m.group(2) for m in re.finditer(r"%([\w.-]+) = (\w+\[[\d,]*\])", compiled.as_text())}
        first = re.search(r"custom-call\(%([\w.-]+), %([\w.-]+), %([\w.-]+), %([\w.-]+), ", call).groups()
        assert [shapes[name] for name in first] == [f"s32[{rows},{width}]", f"s32[{rows}]", f"s32[{rows + 1}]", "s32[1]"], call
        assert compiled.memory_analysis().temp_size_in_bytes < 1 << 20        # no arena copy: the packed queries, the chain


@pytest.mark.parametrize("store", [BF, I8], ids=["plain", "int8"])
@pytest.mark.parametrize("bs", [8, 16])
@pytest.mark.parametrize("hs", [128, 256])
@pytest.mark.parametrize("shape", SHAPES)
def test_decode_walk_compiles_at_other_widths(shape, hs, bs, store, tpu_sharding):
    """Whole lane tiles of head size, and a block of half a bfloat16 sublane
    tile: what :func:`paged_attn_decode`'s docstring says the TPU takes, which
    only Mosaic can say: the compile is the whole test."""
    if tpu_sharding is None:
        pytest.skip("no device-less TPU topology to compile for")
    nh, ng = SHAPES[shape]
    args = _decode_args(nh, ng, hs, bs, B, NBB, NB, L, store, tpu_sharding)
    jax.jit(_decode(1, window=24)).trace(*args).lower(lowering_platforms=("tpu",)).compile()


@pytest.mark.parametrize("store", [BF, I8, F8], ids=["plain", "int8", "fp8"])
@pytest.mark.parametrize("hs", [64, 96])
@pytest.mark.parametrize("shape", [*SHAPES, "gpt2"])
def test_narrow_heads_decode_block_by_block(shape, hs, store, tpu_sharding, monkeypatch):
    """A head size that is not whole 128-lane tiles.  The walk's own arena
    copies cannot be compiled (Mosaic holds the arena padded to 128 lanes and
    refuses the narrower slice), so ``paged_attn_decode`` sends the token
    through ``paged_attn_verify``'s per-block grid, which compiles; with a
    sliding window, which that kernel has not, it takes its XLA form (no
    kernel in the lowering).  If the last check fails because Mosaic
    now compiles the walk, drop ``paged_walk_lanes_ok``.  (A head of 64 in a
    lane-packed arena is another arena: rows of 128 lanes, which the walk takes:
    ``test_the_narrow_head_cells_kernels_compile_at_its_shapes``.)  Both
    compiles are Mosaic's answer, which no lowering holds: it takes the
    per-block grid and raises on the walk's narrow slice."""
    nh, ng = {**SHAPES, "gpt2": (12, 12)}[shape]
    args = _decode_args(nh, ng, hs, BS, B, NBB, NB, L, store, tpu_sharding)
    assert not px.paged_walk_lanes_ok(hs) and px.paged_walk_lanes_ok(128)
    assert px.paged_head_size_ok(64) and not px.paged_head_size_ok(96)      # 64 divides a lane tile: packable
    assert px.paged_decode_path(hs, 24) == "xla" and px.paged_decode_path(hs) == "by_blocks"
    assert "tpu_custom_call" not in jax.jit(_decode(1, window=24)).trace(*args).lower(lowering_platforms=("tpu",)).as_text()
    lowered = jax.jit(_decode(1)).trace(*args).lower(lowering_platforms=("tpu",))
    name = "paged_attn_verify" + ("_quant" if store != BF else "")
    assert f'kernel_name = "{name}"' in lowered.as_text()
    if tpu_sharding is None:
        return
    assert re.search(rf"%{name}(\.\d+)? = ", lowered.compile().as_text())
    monkeypatch.setattr(px, "paged_walk_lanes_ok", lambda lanes: True)
    with pytest.raises(Exception, match=r"must be aligned to tiling \(128\)"):
        jax.jit(_decode(1)).trace(*args).lower(lowering_platforms=("tpu",)).compile()


@pytest.mark.parametrize("hs,store", [(64, "int8"), (96, None)], ids=["64_unpacked", "96"])
def test_a_windowed_narrow_heads_decode_program_lowers_with_the_xla_form(hs, store, tpu_sharding):
    """The whole ``decode_paged`` program of a windowed model whose arena rows
    the walk cannot fetch (heads of 64 a row each, as a quantised arena keeps
    them, and heads of 96): the engine says "xla" when it is built, and the
    program lowers for the TPU with the attention gathered in XLA and the
    token writers still kernels (before PR 44: ``NotImplementedError`` from
    the entry, kept off it by a second decode program).  The compile (a tiny
    model: seconds) shows that XLA takes the gathered form beside the writers'
    Mosaic calls."""
    import thunder_tpu as tt
    from thunder_tpu.models import llama

    cfg = llama.Config(name=f"windowed-{hs}", n_layer=2, n_head=4, n_query_groups=2, n_embd=4 * hs, head_size=hs,
                       intermediate_size=256, vocab_size=512, block_size=512, sliding_window=64)
    params = jax.eval_shape(lambda: llama.init_params(cfg, dtype=BF))
    eng = tt.serve(None, params, cfg, num_blocks=64, block_size=BS, max_batch=4, **({"kv_dtype": store} if store else {}))
    st = eng.stats()["attn"]
    assert st["path"] == "xla" and st["lane_pack"] == 1 and eng.pool.k_arena.shape[-1] == hs
    sds = lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=tpu_sharding)  # noqa: E731
    one = lambda shape, dt=I32: jax.ShapeDtypeStruct(shape, dt, sharding=tpu_sharding)  # noqa: E731
    weights, arenas = jax.tree_util.tree_map(sds, params), jax.tree_util.tree_map(sds, eng.pool.arenas)
    prog = eng._build_decode_paged(4, 16)
    lowered = prog.trace(weights, one((4,)), one((4,)), one((4, 16)), arenas, one((4, 2), jnp.uint32), {}, one((4,))
                         ).lower(lowering_platforms=("tpu",))
    text = lowered.as_text()
    assert "paged_attn_decode" not in text and "paged_attn_verify" not in text
    assert f'kernel_name = "paged_token_write{"_fused" if store else ""}"' in text
    assert re.search(r"gather", text)
    if tpu_sharding is not None:
        lowered.compile()


# --------------------------------------------------------------------------
# the serving engine's whole-prompt prefill, as the two serve cells build it
# --------------------------------------------------------------------------

# A whole program's compile for the v5e is XLA's passes over its plain part, and they grow with the bucket (A.X-K1's
# two layers: 47.9 s at 4,096 tokens, 13.2 s at 1,024, 5.3 s at 256, the kernels alone 2.6-4.7 s at the full shapes;
# PR 55).  What only that compile shows (XLA takes the program with its Mosaic calls among its own operations and
# keeps each under the name a device trace shows) it shows at sixteen blocks of the pool; the lowering, with every
# assertion on its text, stays at the cell's bucket, and the kernels are compiled alone at the cell's shapes.
COMPILED_BUCKET = 256


def _fresh_prefill(eng, weights, arenas, one, state_slots=True):
    """``Tb -> (program, operands)``: an engine's ``prefill_fresh`` at a bucket, over shapes alone; ``state_slots``:
    the engine leases a state slot a request and the program takes it."""
    def at(Tb):
        return eng._build_prefill(Tb, Tb // BS, fresh=True), (
            weights, one((1, Tb)), one(()), arenas, one((Tb // BS,)), one((2,), jnp.uint32), {}, one((1,)),
            *([one((1,))] if state_slots else []))
    return at


def _compiled_prefill(prefill):
    """``prefill(Tb) -> (program, operands)`` compiled for the v5e at ``COMPILED_BUCKET``."""
    prog, args = prefill(COMPILED_BUCKET)
    return prog.trace(*args).lower(lowering_platforms=("tpu",)).compile()


SERVE_CELLS = {   # cell -> (layers lowered, full-attention layers among them, a prefill bucket, its flash blocks)
    "mistral7b-serve-1chip.offline-batch": (2, 2, 3072, 1024),
    "olmo-hybrid-serve-1chip.offline-longgen": (4, 1, 2560, 1024),     # two and a half: the third block is ragged
}


@functools.cache
def _cell_engine(cell: str):
    """The cell's engine at its published widths with the depth cut further,
    over weights that are shapes alone (nothing of 7B is allocated here)."""
    import thunder_tpu as tt
    from chipbench import common
    from thunder_tpu.models import llama

    _, config, mix = common.open_cell(cell)
    arch = common.load_module("models", config["arch"])
    layers = SERVE_CELLS[cell][0]
    hf = {**config, "num_hidden_layers": layers}
    if "layer_types" in hf:
        hf["layer_types"] = hf["layer_types"][:layers]
    cfg = llama.Config(**arch.program_config(hf))
    params = jax.eval_shape(functools.partial(arch.make_params, hf), common.seed_words(1))
    kw = {**config["engine"], **mix["engine"], "num_blocks": 240, "max_batch": 2, "batch_buckets": [2]}
    return cfg, params, tt.serve(None, params, cfg, **kw)


def _lower_prefill(cell: str, kind: str, sharding):
    cfg, params, eng = _cell_engine(cell)
    Tb, bs = SERVE_CELLS[cell][2], eng.pool.block_size
    nbb = Tb // bs if kind == "prefill_fresh" else eng._nbb(Tb // bs)
    prog = eng._build_prefill(Tb, nbb, fresh=kind == "prefill_fresh")
    sds = lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=sharding)  # noqa: E731
    one = lambda shape, dt=I32: jax.ShapeDtypeStruct(shape, dt, sharding=sharding)  # noqa: E731
    where = (one((nbb,)),) if kind == "prefill_fresh" else (one((nbb,)), one((nbb,)))
    args = (jax.tree_util.tree_map(sds, params), one((1, Tb)), *([] if kind == "prefill_fresh" else [one(())]), one(()),
            jax.tree_util.tree_map(sds, eng.pool.arenas), *where, one((2,), jnp.uint32), {}, one((1,)),
            *([one((1,))] if eng._hybrid else []))
    before = px.stats["direct"]
    lowered = prog.trace(*args).lower(lowering_platforms=("tpu",))
    return cfg, eng, nbb, lowered, px.stats["direct"] - before


@pytest.mark.parametrize("kind", ["prefill_fresh", "prefill"])
@pytest.mark.parametrize("cell", SERVE_CELLS)
def test_a_whole_prompts_prefill_lowers_to_flash_and_a_head_of_one_row(cell, kind, tpu_sharding, monkeypatch):
    """``prefill_fresh`` at a bucket of each serve cell, lowered for a v5e: a
    ``_flash_fwd`` custom call a full-attention layer on grouped K/V, no
    gather from the K/V arenas, no float32 tensor of (heads, bucket, table
    width), and a head product of one row.  The ``prefill`` kind beside it
    still gathers the table and scores all of it, with the head on one row
    too (both take ``logits_at``).  Compiled at the cell's bucket, since the
    compile alone shows the program's temporaries there (under one layer's
    float32 scores) and the call as the readers meet it."""
    monkeypatch.setattr(px, "_enabled", lambda: True)
    layers, full, Tb, block = SERVE_CELLS[cell]
    cfg, eng, nbb, lowered, claims = _lower_prefill(cell, kind, tpu_sharding)
    text = lowered.as_text()
    table = nbb * eng.pool.block_size
    V, nh = cfg.padded_vocab_size, cfg.n_head
    scored = {int(m) for m in re.findall(rf"tensor<1x{nh}x{Tb}x(\d+)xf32>", text)}      # widths of float32 (heads, bucket, .)
    head_rows = {int(m) for m in re.findall(rf"tensor<1x(\d+)x{V}xf32>", text)}
    gathers = len(re.findall(r"stablehlo\.gather", text)) + len(re.findall(r"stablehlo\.dynamic_gather", text))
    assert head_rows == {1}, head_rows                                  # the head on one row, both kinds
    if kind == "prefill_fresh":
        assert claims == full and 'kernel_name = "_flash_fwd"' in text      # one function, called a layer
        assert not {w for w in scored if w >= Tb}, scored
        # what is gathered: the token embeddings (and nothing of an arena, which has five dims)
        assert not re.search(r"gather[^\n]*tensor<\d+x\d+x\d+x\d+x\d+x", text)
        sched = px.flash_schedule
        n = -(-Tb // block)
        assert sched["grid_steps"] == sched["running_blocks"] == n * (n + 1) // 2      # the causal triangle's blocks
        assert (sched["block_q"], sched["tail_rows"]) == (block, -Tb % block)
    else:
        assert claims == 0 and "_flash_fwd" not in text
        assert f"tensor<1x{nh}x{Tb}x{table}xf32>" in text               # scores against every slot of the table
        assert gathers >= 2
    if tpu_sharding is not None and kind == "prefill_fresh":
        compiled = lowered.compile()
        hlo = compiled.as_text()
        assert len(re.findall(r"%_flash_fwd(\.\d+)? = ", hlo)) == full
        # the reader of paged_attn_decode finds its call by operands (a table first, one
        # four-dimensional result): the flash call's tuple result must not fit it
        for line in (l for l in hlo.splitlines() if re.search(r"%_flash_fwd(\.\d+)? = ", l)):
            assert not re.match(r"^\s*%\S+ = \w+\[\d+(,\d+){3}\]\S* custom-call\(s32\[\d+,\d+\]", line)
        temps = compiled.memory_analysis().temp_size_in_bytes
        assert temps < 4 * nh * Tb * table                            # less than one layer's float32 scores


# --------------------------------------------------------------------------
# latent attention as the A.X-K1 cell serves it
# --------------------------------------------------------------------------

MLA_CELL = "axk1-serve-1chip.offline-longctx"


def _mosaic_module(lowered_text: str) -> str:
    """The one Mosaic kernel of a lowered program, as MLIR text (the custom
    call carries it as bytecode)."""
    import base64

    from jax._src.lib import tpu
    from jax._src.lib.mlir import ir

    (body,) = re.findall(r'\\22body\\22: \\22([A-Za-z0-9+/=]+)\\22', lowered_text)
    ctx = ir.Context()
    ctx.allow_unregistered_dialects = True
    tpu.register_dialect(ctx)
    with ctx:
        return str(ir.Module.parse(base64.b64decode(body)))


@pytest.mark.parametrize("kernel", ["mla_paged_decode", "mla_paged_decode/128heads", "mla_paged_decode/32heads",
                                    "mla_latent_write", "mla_latent_write_masked"])
def test_the_latent_kernels_compile_at_the_cells_shapes(kernel, tpu_sharding, monkeypatch):
    """64 rows, a table 640 blocks wide, the cell's arena of 32768 blocks of 16
    rows of 640 (576 padded to whole lane tiles), 64 heads (and 128, a whole pass
    of the matrix unit): the walk's copies are whole-tile slabs, the table fits
    the scalar memory, nothing of the arena is copied; the two chunk buffers are
    what ``mla_chunk_keys`` derives and fit the budget it states; the rows are
    held and the queries streamed, so the module transposes nothing.  ``32heads``:
    the Xing4.0 cell's walk, 32 rows of 32 heads over a table 524 blocks wide and
    an arena of 16,784 blocks (60 operations a byte: bound by bytes alone).
    Only the compile shows Mosaic taking the buffers and the table at these
    shapes, no temporary beside the call, and the call as XLA writes it."""
    monkeypatch.setattr(px, "_pallas_available", lambda: True)
    kernel, _, heads = kernel.partition("/")
    rows, width, pool, layers, nh, W, dc = 64, 640, 32768, 6, {"": 64, "128heads": 128, "32heads": 32}[heads], 640, 512
    if heads == "32heads":
        rows, width, pool = 32, 524, 16784
    arena, tab, pos = ((pool, layers, 1, BS, W), BF), ((rows, width), I32), ((rows,), I32)
    if kernel == "mla_paged_decode":
        fn = functools.partial(px.mla_paged_decode, layer=layers - 1, dc=dc, scale=0.13)
        specs = [((rows, nh, W), BF), arena, ((rows, W), BF), tab, pos]
    elif kernel == "mla_latent_write":
        fn = functools.partial(px.paged_token_write, block_size=BS, name="mla_latent_write")
        specs = [arena, ((rows, layers, 1, W), BF), tab, pos]
    else:
        fn = lambda a, v, t, p, n: px.paged_token_write(a, v, t, p, block_size=BS, n_emit=n, offset=0,  # noqa: E731
                                                        name="mla_latent_write")
        specs = [arena, ((rows, layers, 1, W), BF), tab, pos, ((rows,), I32)]
    before = px.stats.get("mla_decode", 0)
    args = [jax.ShapeDtypeStruct(s, dt, sharding=tpu_sharding) for s, dt in specs]
    lowered = jax.jit(fn).trace(*args).lower(lowering_platforms=("tpu",))
    text = lowered.as_text()
    assert text.count("tpu_custom_call") == 1 and f'kernel_name = "{kernel}"' in text
    if kernel == "mla_paged_decode":
        assert px.stats["mla_decode"] == before + 1                     # claimed, not the XLA form
        keys = px.mla_chunk_keys(BS, W, 2)
        assert keys == 1024 and 2 * keys * W * 2 <= px._MLA_BUFFER_BYTES
        module = _mosaic_module(text)
        assert module.count(f"memref<{keys // BS}x{BS}x{W}xbf16, #tpu.memory_space<vmem>>") >= 2    # the two buffers
        assert f"vector<{nh}x{W}xbf16>, vector<{keys}x{W}xbf16>, vector<{nh}x{keys}xf32>" in module   # q streamed, rows held
        assert not re.search(r'[a-z_.]+\.transpose"?\(', module)
    if tpu_sharding is not None:
        compiled = lowered.compile()
        assert re.search(rf"%{kernel}(\.\d+)? = ", compiled.as_text())
        if kernel == "mla_paged_decode":
            assert compiled.memory_analysis().temp_size_in_bytes == 0       # no arena copy
            # nothing of the call fits the reader that finds paged_attn_decode by its operands
            call = next(l for l in compiled.as_text().splitlines() if re.search(r"%mla_paged_decode(\.\d+)? = ", l))
            assert not re.match(r"^\s*%\S+ = \w+\[\d+(,\d+){3}\]\S* custom-call\(s32\[\d+,\d+\]", call)


def test_a_row_of_576_is_refused_by_the_walks_copies(tpu_sharding, monkeypatch):
    """Why the arena's rows are padded: the kernel asks for whole lane tiles."""
    monkeypatch.setattr(px, "_pallas_available", lambda: True)
    with pytest.raises(AssertionError):
        px.mla_paged_decode(jnp.zeros((2, 4, 576), BF), jnp.zeros((9, 1, 1, BS, 576), BF), jnp.zeros((2, 576), BF),
                            jnp.zeros((2, 4), I32), jnp.zeros((2,), I32), layer=0, dc=512, scale=1.0)


HC_CELL = "xing4-serve-1chip.offline-digest"
# cell -> (a prefill bucket, decode rows, table width)
MLA_PROGRAMS = {MLA_CELL: (4096, 64, 640), HC_CELL: (8192, 32, 524)}


@functools.cache
def _mla_engine(cell=MLA_CELL):
    """The cell's engine at its published widths, the dense layer and one
    expert layer, over weights that are shapes alone."""
    import thunder_tpu as tt
    from chipbench import common
    from thunder_tpu.models import llama

    _, config, mix = common.open_cell(cell)
    arch = common.load_module("models", config["arch"])
    hf = {**config, "num_hidden_layers": 2}
    cfg = llama.Config(**arch.program_config(hf))
    params = jax.eval_shape(functools.partial(arch.make_params, hf), common.seed_words(1))
    return cfg, params, tt.serve(None, params, cfg, **{**config["engine"], **mix["engine"], "num_blocks": 700})


@pytest.mark.parametrize("kind", ["prefill_fresh", "decode_paged"])
@pytest.mark.parametrize("cell", sorted(MLA_PROGRAMS), ids=lambda c: c.partition("-")[0])
def test_the_latent_cells_programs_lower_to_their_kernels(cell, kind, tpu_sharding, monkeypatch):
    """A whole prompt's prefill attends its expanded keys through ``_flash_fwd``
    (heads of 192 over values of 128, each as it is: no pad, no slice, PR 54) and
    sorts its rows through ``moe_grouped_mm``; a decode step calls
    ``mla_paged_decode`` once a layer, ``moe_grouped_mm`` for the expert layer
    and lands its rows through one ``mla_latent_write``; no arena is gathered.
    The Xing4.0 cell: 32 heads, all 64 experts of 3584 x 1024 a layer, the stream
    four wide under hyper-connections, its 8,192 bucket and its 32-row step: a
    prompt's boundaries between sublayers are ``hc_mix`` calls (the first open,
    a joined close and open at each of the others, the last close one row's and
    XLA's), a step's rows keep to XLA's fusions.  Only the compile shows XLA
    taking the program with the Mosaic calls among its own operations and
    keeping each under its name, ``hc_mix`` under the ``hc/`` scope its two
    readers look for, with no float32 copy of the stream left beside it: the
    prompt's at ``COMPILED_BUCKET``, the step's as it is."""
    monkeypatch.setattr(px, "_enabled", lambda: True)
    monkeypatch.setattr(px, "_pallas_available", lambda: True)
    if cell == HC_CELL:
        monkeypatch.setattr(px, "_gmm_vmem_cap", lambda: 96 << 20)      # a v5e's, which the chip reports: what it runs
    cfg, params, eng = _mla_engine(cell)
    Tb, rows, width = MLA_PROGRAMS[cell]
    sds = lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=tpu_sharding)  # noqa: E731
    one = lambda shape, dt=I32: jax.ShapeDtypeStruct(shape, dt, sharding=tpu_sharding)  # noqa: E731
    weights, arenas = jax.tree_util.tree_map(sds, params), jax.tree_util.tree_map(sds, eng.pool.arenas)
    before = dict(px.stats)
    prefill = _fresh_prefill(eng, weights, arenas, one, state_slots=False)
    if kind == "prefill_fresh":
        prog, args = prefill(Tb)
    else:
        prog = eng._build_decode_paged(rows, width)
        args = (weights, one((rows,)), one((rows,)), one((rows, width)), arenas, one((rows, 2), jnp.uint32), {},
                one((rows,)), one((4,), F32))         # the expert share's running sums (PR 45)
    lowered = prog.trace(*args).lower(lowering_platforms=("tpu",))
    text = lowered.as_text()
    claimed = lambda k: px.stats.get(k, 0) - before.get(k, 0)  # noqa: E731
    assert claimed("grouped_mm") >= 3 and 'kernel_name = "moe_grouped_mm"' in text
    assert not re.search(r"gather[^\n]*tensor<\d+x\d+x\d+x\d+x\d+x", text)      # nothing of an arena's five dims
    # a prompt's tokens take their experts' rows back through ``moe_combine`` (PR 58: the first wave's call and the
    # later waves' behind their ``cond``), a decode step's few rows through XLA's gather
    assert (claimed("moe_combine") >= 1) == ('kernel_name = "moe_combine"' in text) == (kind == "prefill_fresh")
    if kind == "prefill_fresh":
        assert px.combine_schedule["streams"] == {MLA_CELL: 12, HC_CELL: 64}[cell] and px.combine_schedule["chunk_rows"] == 16   # the experts held
        assert not re.search(r"stablehlo\.scatter[^\n]*xf32>", text) and not re.search(rf"tensor<{cfg.n_expert_per_token}x{Tb}x{cfg.n_embd}xbf16>", text)
    if kind == "prefill_fresh":
        assert claimed("direct") == cfg.n_layer and 'kernel_name = "_flash_fwd"' in text
        assert px.flash_schedule["grid_steps"] > 0
        assert (px.flash_schedule["head_qk"], px.flash_schedule["head_v"], px.flash_schedule["lanes_padded"]) == (192, 128, 0)
        nh, (hs, hv) = cfg.n_head, (cfg.head_size, cfg.v_head_dim)
        assert (hs, hv) == (192, 128) and f"tensor<1x{nh}x{Tb}x{hs}xbf16>" in text and f"tensor<1x{nh}x{Tb}x{hv}xbf16>" in text
        # q and k at the kept width, v and the call's result at v_head_dim: no zero is added to a head and none cut off
        heads = lambda w: rf"tensor<{nh}x{Tb}x{w}xbf16>"  # noqa: E731
        assert re.search(rf"call @_flash_fwd\S*\([^)]*\) : \({heads(hs)}, {heads(hs)}, {heads(hv)}\) -> \(?{heads(hv)}", text)
        assert not re.search(rf"stablehlo\.pad[^\n]*\(tensor<(1x)?{nh}x{Tb}x\d+xbf16>", text)
        assert not re.search(rf"stablehlo\.slice[^\n]*\(tensor<{nh}x{Tb}x\d+xbf16>\)", text)
        # one cut of a head of 192 to 128 a layer: the query's part without a position; the result has none
        assert len(re.findall(rf"stablehlo\.slice[^\n]*\(tensor<1x{nh}x{Tb}x{hs}xbf16>\) -> tensor<1x{nh}x{Tb}x{hv}xbf16>", text)) == cfg.n_layer
        assert {int(m) for m in re.findall(rf"tensor<1x(\d+)x{cfg.padded_vocab_size}xf32>", text)} == {1}
    else:
        assert claimed("mla_decode") == cfg.n_layer
        assert text.count('kernel_name = "mla_latent_write"') == 1
    if cell == HC_CELL:     # a layer's two sublayers a boundary each, and the close after the last
        fused = 2 * cfg.n_layer if kind == "prefill_fresh" else 0
        assert (claimed("hc_fused"), claimed("hc_fallback")) == (fused, 2 * cfg.n_layer + 1 - fused)
        assert ('kernel_name = "hc_mix"' in text) == bool(fused)
    if tpu_sharding is not None:
        hlo = (_compiled_prefill(prefill) if kind == "prefill_fresh" else lowered.compile()).as_text()
        names = ("_flash_fwd",) if kind == "prefill_fresh" else ("mla_paged_decode", "mla_latent_write")
        for name in (*names, "moe_grouped_mm"):
            assert re.search(rf"%{name}(\.\d+)? = ", hlo), name
        if cell == HC_CELL and kind == "prefill_fresh":
            calls = re.findall(r"%hc_mix(?:\.\d+)? = [^\n]*", hlo)
            assert len(calls) == 2 * cfg.n_layer and all(re.search(r'op_name="[^"]*/hc/(open|join)/', c) for c in calls), calls
            wide = rf"f32\[1,{cfg.hc_mult},{COMPILED_BUCKET},{cfg.n_embd}\]"
            assert re.search(wide.replace("f32", "bf16"), hlo) and not [
                line for line in hlo.splitlines() if re.search(wide, line) and re.search(r'op_name="[^"]*/hc/', line)]


@pytest.mark.parametrize("form,T", [("open", 8192), ("join", 8192), ("close", 8192), ("join", 5000)],
                         ids=["open", "join", "close", "join/ragged"])
def test_the_hyper_connections_boundary_compiles_at_the_cells_shapes(form, T, tpu_sharding, monkeypatch):
    """``hc_mix`` at the Xing4.0 cell's widths, four streams of 3,584 in bfloat16, an 8,192-token prompt (and a
    length whose last tile is ragged): the first open, a joined close and open, the last close.  Only the compile
    shows Mosaic taking the three turned tiles, the products that contract both last axes and the unaligned rows
    of the maps' block, all inside the scoped limit the call states (a quarter of a v5e core's VMEM)."""
    monkeypatch.setattr(px, "_gmm_vmem_cap", lambda: 96 << 20)      # a v5e's, which the chip reports
    n, C = 4, 3584
    m = n * (n + 2)
    sds = lambda shape, dt=BF: jax.ShapeDtypeStruct(shape, dt, sharding=tpu_sharding)  # noqa: E731
    x = sds((1, n, T, C))
    owed = None if form == "open" else (sds((1, T, C)), (sds((n, 1, T), F32), sds((n, n, 1, T), F32)))
    hp = None if form == "close" else {"phi": sds((m, n * C)), "norm": sds((n * C,)), "alpha": sds((3,), F32), "bias": sds((m,), F32)}
    before = dict(px.stats)
    lowered = jax.jit(lambda x, owed, hp: px.hc_mix(x, owed, hp, eps=1e-6, iters=20, clamp=(-30.0, 30.0))).trace(
        x, owed, hp).lower(lowering_platforms=("tpu",))
    text = lowered.as_text()
    assert px.stats["hc_fused"] - before.get("hc_fused", 0) == 1 and px.hc_schedule["block_tokens"] == 128
    assert px.hc_schedule["grid_steps"] == -(-T // 128)
    assert text.count("tpu_custom_call") == 1 and 'kernel_name = "hc_mix"' in text
    limit = px.hc_schedule["vmem_limit_bytes"]
    assert px._GMM_VMEM_DEFAULT < limit <= 32 << 20 and f'\\22size\\22: {limit}}}' in text       # the scoped limit the call states
    assert f"tensor<1x{n}x{T}x{C}xbf16>" in text and f"tensor<1x{n}x{T}x{C}xf32>" not in text    # no float32 copy of the stream beside the call
    if tpu_sharding is not None:
        assert re.search(r"%hc_mix(\.\d+)? = ", lowered.compile().as_text())


# --------------------------------------------------------------------------
# a head of 64 in a lane-packed arena, as the LFM2 cell serves it
# --------------------------------------------------------------------------

LFM2_CELL = "lfm2moe-serve-1chip.offline-wide"


@pytest.mark.parametrize("kernel", ["paged_attn_decode", "paged_attn_decode/window", "paged_token_write",
                                    "paged_token_write_masked"])
def test_the_narrow_head_cells_kernels_compile_at_its_shapes(kernel, tpu_sharding):
    """256 rows, a table 256 blocks wide (65,536 entries of scalar memory), the
    cell's arena of 47,104 blocks of 16 tokens, 8 KV heads of 64 two to a
    128-lane row (``(blocks, 3, 4, 16, 128)``), 32 query heads: the walk's copies
    are whole-tile slabs, nothing of the arena is copied, and no per-block
    kernel stands in.  Only the compile shows Mosaic taking 65,536 entries of
    scalar memory and the slabs, and no temporary beside the walk."""
    kernel, _, windowed = kernel.partition("/")
    rows, width, pool, layers, nh, ng, hs = 256, 256, 47104, 3, 32, 8, 64
    arena, tab, pos = ((pool, layers, ng // 2, BS, 128), BF), ((rows, width), I32), ((rows,), I32)
    if kernel == "paged_attn_decode":
        fn = functools.partial(px.paged_attn_decode, layer=layers - 1, window=1024 if windowed else None)
        specs = [((rows, nh, hs), BF), arena, arena, ((rows, ng, hs), BF), ((rows, ng, hs), BF), tab, pos]
    elif kernel == "paged_token_write":
        fn = functools.partial(px.paged_token_write, block_size=BS)
        specs = [arena, ((rows, layers, ng, hs), BF), tab, pos]
    else:
        fn = lambda a, v, t, p, n: px.paged_token_write(a, v, t, p, block_size=BS, n_emit=n, offset=0)  # noqa: E731
        specs = [arena, ((rows, layers, ng, hs), BF), tab, pos, ((rows,), I32)]
    assert px.paged_walk_lanes_ok(128) and not px.paged_walk_lanes_ok(hs)
    assert px.paged_kv_chunk_blocks(ng // 2, BS, 128, 2) == 32              # 512 keys a chunk, 1 MiB of K and V in flight
    args = [jax.ShapeDtypeStruct(s, dt, sharding=tpu_sharding) for s, dt in specs]
    lowered = jax.jit(fn).trace(*args).lower(lowering_platforms=("tpu",))
    text = lowered.as_text()
    assert text.count("tpu_custom_call") == 1 and f'kernel_name = "{kernel}"' in text and "paged_attn_verify" not in text
    if tpu_sharding is not None:
        compiled = lowered.compile()
        assert re.search(rf"%{kernel}(\.\d+)? = ", compiled.as_text())
        if kernel == "paged_attn_decode":
            assert compiled.memory_analysis().temp_size_in_bytes == 0       # no arena copy, no padded copy of a row


@functools.cache
def _lfm2_engine():
    """The cell's engine at its published widths, the first four layers (conv,
    conv, attention, conv: both dense layers and two expert layers of 32), over
    weights that are shapes alone."""
    import thunder_tpu as tt
    from chipbench import common
    from thunder_tpu.models import llama

    _, config, mix = common.open_cell(LFM2_CELL)
    arch = common.load_module("models", config["arch"])
    hf = {**config, "num_hidden_layers": 4}
    cfg = llama.Config(**arch.program_config(hf))
    params = jax.eval_shape(functools.partial(arch.make_params, hf), common.seed_words(1))
    return cfg, params, tt.serve(None, params, cfg, **{**config["engine"], **mix["engine"], "num_blocks": 700})


@pytest.mark.parametrize("kind", ["prefill_fresh", "decode_paged"])
def test_the_narrow_head_cells_programs_lower_to_their_kernels(kind, tpu_sharding, monkeypatch):
    """A whole prompt's prefill attends through ``_flash_fwd`` (heads of 64
    padded with zeros to 128) and sorts its rows through ``moe_grouped_mm``; a
    decode step of 256 rows walks the lane-packed arena through
    ``paged_attn_decode`` once an attention layer, routes through
    ``moe_grouped_mm`` and lands its K and V through one ``paged_token_write``
    each; the conv layers are XLA's, their tails a slot's rows; no arena is
    gathered and no per-block kernel is called.  Only the compile shows XLA
    taking the program with its Mosaic calls and keeping each under its name:
    the prompt's at ``COMPILED_BUCKET``, the step's as it is."""
    monkeypatch.setattr(px, "_enabled", lambda: True)
    monkeypatch.setattr(px, "_pallas_available", lambda: True)
    cfg, params, eng = _lfm2_engine()
    st = eng.stats()
    assert st["attn"]["path"] == "walk" and st["attn"]["lane_pack"] == 2
    assert eng.pool.k_arena.shape == (700, 1, 4, 16, 128) and eng.pool.state.conv.shape == (257, 3, 2, 2048)
    occ = st["pool_occupancy"]
    assert occ["token_bytes_counted"] == occ["token_bytes_laid_out"] == 2 * 8 * 64 * 2       # one attention layer here
    sds = lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=tpu_sharding)  # noqa: E731
    one = lambda shape, dt=I32: jax.ShapeDtypeStruct(shape, dt, sharding=tpu_sharding)  # noqa: E731
    weights, arenas = jax.tree_util.tree_map(sds, params), jax.tree_util.tree_map(sds, eng.pool.arenas)
    before = dict(px.stats)
    prefill = _fresh_prefill(eng, weights, arenas, one)
    if kind == "prefill_fresh":
        Tb = 2560
        prog, args = prefill(Tb)
    else:
        prog = eng._build_decode_paged(256, 256)
        args = (weights, one((256,)), one((256,)), one((256, 256)), arenas, one((256, 2), jnp.uint32), {}, one((256,)),
                one((4,), F32), one((256,)))        # the expert share's running sums (PR 45), then the state slots
    lowered = prog.trace(*args).lower(lowering_platforms=("tpu",))
    text = lowered.as_text()
    claimed = lambda k: px.stats.get(k, 0) - before.get(k, 0)  # noqa: E731
    assert claimed("grouped_mm") >= 3 and 'kernel_name = "moe_grouped_mm"' in text
    assert "paged_attn_verify" not in text
    assert not re.search(r"gather[^\n]*tensor<\d+x\d+x\d+x\d+x\d+x", text)      # nothing of an arena's five dims
    if kind == "prefill_fresh":
        assert claimed("direct") == len(cfg.kv_layers) and 'kernel_name = "_flash_fwd"' in text
        assert {int(m) for m in re.findall(rf"tensor<1x(\d+)x{cfg.padded_vocab_size}xf32>", text)} == {1}
    else:
        assert text.count('kernel_name = "paged_attn_decode"') == len(cfg.kv_layers)
        assert text.count('kernel_name = "paged_token_write"') == 2
    if tpu_sharding is not None:
        hlo = (_compiled_prefill(prefill) if kind == "prefill_fresh" else lowered.compile()).as_text()
        names = ("_flash_fwd",) if kind == "prefill_fresh" else ("paged_attn_decode", "paged_token_write")
        for name in (*names, "moe_grouped_mm"):
            assert re.search(rf"%{name}(\.\d+)? = ", hlo), name


def _window_global_engine():
    """The Trinity-Mini cell's engine at its published widths, the first period of
    four layers (three window layers and a global one: both dense layers and two
    expert layers of 16 held), over weights that are shapes alone."""
    import thunder_tpu as tt
    from chipbench import common
    from thunder_tpu.models import llama

    _, config, mix = common.open_cell("trinity-mini-serve-1chip.offline-docqa")
    arch = common.load_module("models", config["arch"])
    hf = {**config, "num_hidden_layers": 4}
    cfg = llama.Config(**arch.program_config(hf))
    params = jax.eval_shape(functools.partial(arch.make_params, hf), common.seed_words(1))
    kw = {**config["engine"], **mix["engine"], "num_blocks": 1500, "max_batch": 2, "batch_buckets": [2]}
    return cfg, params, tt.serve(None, params, cfg, **kw)


@pytest.mark.parametrize("kind", ["prefill_fresh", "decode_paged"])
def test_the_window_global_cells_programs_lower_to_their_kernels(kind, tpu_sharding, monkeypatch):
    """A whole prompt of 3,840 tokens attends through ``_flash_fwd`` in every layer,
    banded by the window of 2,048 in the three window layers and causal alone in
    the global one, and sorts its rows through ``moe_grouped_mm``; a decode step
    walks the slot's ring (129 entries of the table's 720) through
    ``paged_attn_decode`` in the window layers and the request's own blocks in the
    global one, and lands its K and V through one ``paged_token_write`` an arena:
    two for the rings, two for the blocks; no arena is gathered.  Only the
    compile shows XLA taking the program with its Mosaic calls and keeping each
    under its name: the prompt's at ``COMPILED_BUCKET``, the step's as it is."""
    monkeypatch.setattr(px, "_enabled", lambda: True)
    monkeypatch.setattr(px, "_pallas_available", lambda: True)
    cfg, params, eng = _window_global_engine()
    st = eng.stats()
    assert st["attn"]["path"] == "walk" and st["attn"]["lane_pack"] == 1
    assert eng.pool.k_arena.shape == (1500, 1, 4, 16, 128) and eng.pool.state.shapes["k_ring"] == (3 * 129, 3, 4, 16, 128)
    assert sorted(eng.pool.arenas) == ["k", "k_ring", "v", "v_ring"]
    sds = lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=tpu_sharding)  # noqa: E731
    one = lambda shape, dt=I32: jax.ShapeDtypeStruct(shape, dt, sharding=tpu_sharding)  # noqa: E731
    weights, arenas = jax.tree_util.tree_map(sds, params), jax.tree_util.tree_map(sds, eng.pool.arenas)
    before = dict(px.stats)
    prefill = _fresh_prefill(eng, weights, arenas, one)
    if kind == "prefill_fresh":
        Tb = 3840
        prog, args = prefill(Tb)
    else:
        prog = eng._build_decode_paged(2, 720)
        args = (weights, one((2,)), one((2,)), one((2, 720)), arenas, one((2, 2), jnp.uint32), {}, one((2,)),
                one((4,), F32), one((2,)))          # the expert share's running sums, then the state slots
    lowered = prog.trace(*args).lower(lowering_platforms=("tpu",))
    text = lowered.as_text()
    claimed = lambda k: px.stats.get(k, 0) - before.get(k, 0)  # noqa: E731
    assert claimed("grouped_mm") >= 3 and 'kernel_name = "moe_grouped_mm"' in text
    assert "paged_attn_verify" not in text
    assert not re.search(r"gather[^\n]*tensor<\d+x\d+x\d+x\d+x\d+x", text)      # nothing of an arena's five dims
    if kind == "prefill_fresh":
        # four calls claimed, two kernels in the text: the banded one the window layers share, the causal one
        assert claimed("direct") == 4 and text.count('kernel_name = "_flash_fwd"') == 2
        assert {int(m) for m in re.findall(rf"tensor<1x(\d+)x{cfg.padded_vocab_size}xf32>", text)} == {1}
    else:
        # the lowered text holds a function once however many layers call it: a walk a kind, four calls claimed
        assert text.count('kernel_name = "paged_attn_decode"') == 2 and claimed("paged_walk") == 4
        assert 1 <= text.count('kernel_name = "paged_token_write"') <= 4
    if tpu_sharding is not None:
        hlo = (_compiled_prefill(prefill) if kind == "prefill_fresh" else lowered.compile()).as_text()
        names = ("_flash_fwd",) if kind == "prefill_fresh" else ("paged_attn_decode", "paged_token_write")
        for name in (*names, "moe_grouped_mm"):
            assert re.search(rf"%{name}(\.\d+)? = ", hlo), name


def _prerouted_engine():
    """The SmallThinker cell's engine at its published widths, the first period of
    four layers (the global layer, then three window layers; 64 experts held in
    each), over weights that are shapes alone."""
    import thunder_tpu as tt
    from chipbench import common
    from thunder_tpu.models import llama

    _, config, mix = common.open_cell("smallthinker-serve-1chip.offline-mixedlen")
    arch = common.load_module("models", config["arch"])
    hf = {**config, "num_hidden_layers": 4}
    cfg = llama.Config(**arch.program_config(hf))
    params = jax.eval_shape(functools.partial(arch.make_params, hf), common.seed_words(1))
    kw = {**config["engine"], **mix["engine"], "num_blocks": 1500, "max_batch": 2, "batch_buckets": [2]}
    return cfg, params, tt.serve(None, params, cfg, **kw)


@pytest.mark.parametrize("kind", ["prefill_fresh", "decode_paged"])
def test_the_prerouted_cells_programs_lower_to_their_kernels(kind, tpu_sharding, monkeypatch):
    """A whole prompt of 2,560 tokens attends through ``_flash_fwd`` in every layer
    (causal alone in the global layer, which comes first; banded by the window of
    4,096 in the three after it) and sorts its rows through ``moe_grouped_mm`` (three
    products an expert layer: the gated ReLU is SwiGLU's three matrices); a decode
    step walks the request's own blocks in the global layer and the slot's ring (257
    entries of the table's 688) in the window layers, all at 7 query heads a KV
    head.  The router's product reads the block's input and lowers under
    ``mlp/router``; no arena is gathered."""
    monkeypatch.setattr(px, "_enabled", lambda: True)
    monkeypatch.setattr(px, "_pallas_available", lambda: True)
    cfg, params, eng = _prerouted_engine()
    st = eng.stats()
    assert st["attn"]["path"] == "walk" and st["attn"]["lane_pack"] == 1
    assert eng.pool.k_arena.shape == (1500, 1, 4, 16, 128) and eng.pool.state.shapes["k_ring"] == (3 * 257, 3, 4, 16, 128)
    sds = lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=tpu_sharding)  # noqa: E731
    one = lambda shape, dt=I32: jax.ShapeDtypeStruct(shape, dt, sharding=tpu_sharding)  # noqa: E731
    weights, arenas = jax.tree_util.tree_map(sds, params), jax.tree_util.tree_map(sds, eng.pool.arenas)
    before = dict(px.stats)
    prefill = _fresh_prefill(eng, weights, arenas, one)
    if kind == "prefill_fresh":
        prog, args = prefill(2560)
    else:
        prog = eng._build_decode_paged(2, 688)
        args = (weights, one((2,)), one((2,)), one((2, 688)), arenas, one((2, 2), jnp.uint32), {}, one((2,)),
                one((4,), F32), one((2,)))          # the expert share's running sums, then the state slots
    lowered = prog.trace(*args).lower(lowering_platforms=("tpu",))
    text = lowered.as_text(debug_info=True)
    claimed = lambda k: px.stats.get(k, 0) - before.get(k, 0)  # noqa: E731
    assert claimed("grouped_mm") >= 3 and 'kernel_name = "moe_grouped_mm"' in text
    assert "blk0/mlp/router/" in text and "/mixer/router/" not in text
    assert not re.search(r"gather[^\n]*tensor<\d+x\d+x\d+x\d+x\d+x", text)      # nothing of an arena's five dims
    if kind == "prefill_fresh":
        # at 2,560 tokens the window of 4,096 masks nothing: the banded call is the causal one's kernel
        assert claimed("direct") == 4 and 1 <= text.count('kernel_name = "_flash_fwd"') <= 2
    else:
        assert text.count('kernel_name = "paged_attn_decode"') == 2 and claimed("paged_walk") == 4
        assert 1 <= text.count('kernel_name = "paged_token_write"') <= 4
    if tpu_sharding is not None:
        hlo = (_compiled_prefill(prefill) if kind == "prefill_fresh" else lowered.compile()).as_text()
        names = ("_flash_fwd",) if kind == "prefill_fresh" else ("paged_attn_decode", "paged_token_write")
        for name in (*names, "moe_grouped_mm"):
            assert re.search(rf"%{name}(\.\d+)? = ", hlo), name


# K, N, rows a tile, tiles, groups, and whether the device's VMEM is known (a v5e's) or not
GMM_SHAPES = {
    "lfm2/2048x1792/decode": (2048, 1792, 64, 56, 32, True), "lfm2/1792x2048/decode": (1792, 2048, 64, 56, 32, True),
    "lfm2/2048x1792/prefill": (2048, 1792, 128, 104, 32, True), "lfm2/1792x2048/prefill": (1792, 2048, 128, 104, 32, True),
    "axk1/7168x2048/decode": (7168, 2048, 16, 16, 12, True), "axk1/7168x2048/prefill": (7168, 2048, 128, 48, 12, True),
    "axk1/2048x7168/prefill": (2048, 7168, 128, 48, 12, True),
    # Xing4.0: all 64 experts of a layer, 2 rows an expert a decode step, 512 a prompt of 8,192
    "xing4/3584x1024/decode": (3584, 1024, 16, 72, 64, True), "xing4/3584x1024/prefill": (3584, 1024, 128, 320, 64, True),
    "xing4/1024x3584/prefill": (1024, 3584, 128, 320, 64, True),
    # SmallThinker: all 64 experts, 6 rows an expert a decode step, 960 a prompt of 10,240
    "smallthinker/2560x768/decode": (2560, 768, 16, 96, 64, True), "smallthinker/2560x768/prefill": (2560, 768, 128, 608, 64, True),
    "smallthinker/768x2560/prefill": (768, 2560, 128, 608, 64, True),
    # Trinity-Mini: 16 of 128 experts, 1.25 rows an expert a decode step, 624 a prompt of 9,984
    "trinity/2048x1024/decode": (2048, 1024, 16, 24, 16, True), "trinity/2048x1024/prefill": (2048, 1024, 128, 104, 16, True),
    "trinity/1024x2048/prefill": (1024, 2048, 128, 104, 16, True),
    # Nemotron 3 Super at its latent width: 128 of 512 experts, 5.5 rows an expert a decode step, 220 a prompt of 5,120
    "nemotron/1024x2688/decode": (1024, 2688, 16, 184, 128, True), "nemotron/1024x2688/prefill": (1024, 2688, 128, 376, 128, True),
    "nemotron/2688x1024/prefill": (2688, 1024, 128, 376, 128, True),
    # the hybrid trainer's forward products, and transposed the gradients of their rows
    "hybrid/2048x512/train": (2048, 512, 128, 128, 32, True), "hybrid/512x2048/train": (512, 2048, 128, 128, 32, True),
    # a matrix the VMEM asked for does not hold twice: column blocks inside a tile, under the default limit
    "unknown_vmem/7168x2048/decode": (7168, 2048, 16, 16, 12, False),
    "unknown_vmem/2048x7168/prefill": (2048, 7168, 128, 48, 12, False),
}

# sha256 of what a decode step's ``moe_grouped_mm`` lowers to for a TPU (the program's text, the kernel's body printed
# without its source locations in its bytecode's place) on the commit before a prompt's product came to copy its own
# weights (PR 60): a decode step keeps the ``BlockSpec`` form, letter for letter.  ``w`` alone; after a deliberate
# change to that form regenerate with ``python tests/test_pallas_tpu_lowering.py``.
GMM_DECODE_TEXT = {
    "axk1/7168x2048/decode": "ef168ff5c30cf0ddfe7c9ce78732263d4f60070329e7cc2e7dd29d204cc6a695",
    "lfm2/1792x2048/decode": "1226f7297fd3ed2d7c73904c6b80a85cc7bb57ab37b4d2836d58d4099db73524",
    "lfm2/2048x1792/decode": "13eef86754ae74507739f4c5ff1d30df6bb237b8d13d8b505d100499ca993f46",
    "nemotron/1024x2688/decode": "c5adb361c7b46538554a1079e1c5f25f12b00d894d1d08326851a936565aac7c",
    "smallthinker/2560x768/decode": "5595142452c18c7f7f02d9ced6104f7a32cf90a89dc6804277e971bd7aa95876",
    "trinity/2048x1024/decode": "544746324616635ed05d9af9366c3e9e604164d10e6bec3671cae795e9fa8fc5",
    "xing4/3584x1024/decode": "bc80aeb0b5db46b2797bc011ca0840d65bedb6321858b6b3bb3e93c47e6bf613",
}


def _gmm_lowered(case, transpose_w, sharding):
    K, N, TM, nt, groups, _ = GMM_SHAPES[case]
    specs = [((nt * TM, K), BF), ((groups, N, K) if transpose_w else (groups, K, N), BF), ((nt,), I32), ((1,), I32)]
    args = [jax.ShapeDtypeStruct(s, dt, sharding=sharding) for s, dt in specs]
    fn = functools.partial(px._moe_grouped_mm.__wrapped__, transpose_w=transpose_w)
    return jax.jit(fn).trace(*args).lower(lowering_platforms=("tpu",))


def _gmm_text_digest(text: str) -> str:
    """As ``tools/lowered_same.py`` compares two trees' programs: the kernel's body printed without locations."""
    import hashlib
    import os
    import sys

    sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
    from tools.lowered_same import plain

    plain_text, bodies = plain(text)
    assert bodies == 1
    return hashlib.sha256(plain_text.encode()).hexdigest()


@pytest.mark.parametrize("transpose_w", [False, True], ids=["w", "w_transposed"])
@pytest.mark.parametrize("case", sorted(GMM_SHAPES))
def test_the_grouped_product_compiles_inside_the_vmem_it_asks_for(case, transpose_w, tpu_sharding, monkeypatch):
    """``moe_grouped_mm`` at the widths the serving cells and the hybrid
    trainer run it, the whole matrix the block (2.1 MB at the trainer's widths,
    3.9 at SmallThinker's, 7.3 at LFM2's and Xing4.0's, 29.4 at A.X-K1's): Mosaic
    takes each inside the scoped limit the call states (``_gmm_blocks``: none
    where the default holds it), a limit only the compile holds it to.  A
    prompt's and the trainer's products, a row tile of 128, copy their own
    weights a group ahead: ``w`` whole in HBM, two buffers of a block and two
    semaphores, one grid axis, five prefetched vectors, the copy of the next
    group at the low priority, under the one name the metrics find the kernel
    by.  A decode step's keep the ``BlockSpec`` form.  Where the device's VMEM
    is not known the call states none and the column blocks it falls back to
    compile inside the default."""
    K, N, TM, nt, groups, known = GMM_SHAPES[case]
    if known:
        monkeypatch.setattr(px, "_gmm_vmem_cap", lambda: 96 << 20)      # three quarters of a v5e core's
    plan = px._gmm_blocks(K, N, 2, TM, nt)
    lowered = _gmm_lowered(case, transpose_w, tpu_sharding)
    text = lowered.as_text()
    assert text.count("tpu_custom_call") == 1 and 'kernel_name = "moe_grouped_mm"' in text
    module = _mosaic_module(text)
    w_block = f"{N}x{K}" if transpose_w else f"{K}x{N}"
    if known:
        assert plan["col_blocks"] == 1 and plan["weight_fetches_a_group"] == 1 and plan["weights_ahead"] == (TM == 128)
        need = px._gmm_vmem(TM, K, N, 2) + px._GMM_VMEM_MARGIN      # two blocks of the weights, of the rows and of the product
        assert plan["vmem_limit_bytes"] == (need if need > px._GMM_VMEM_DEFAULT else 0) <= 96 << 20
    else:
        assert plan["col_blocks"] > 1 and plan["weight_fetches_a_group"] == nt and plan["weights_ahead"] == 0
    if plan["vmem_limit_bytes"]:
        assert f'\\22size\\22: {plan["vmem_limit_bytes"]}}}' in text              # the scoped limit the call states
    else:
        assert "scoped_memory_configs" not in text
    if plan["weights_ahead"]:
        assert f"iteration_bounds = array<i64: {nt}>" in module and "scalar_prefetch = 5" in module
        assert f"memref<{groups}x{w_block}xbf16, #tpu.memory_space<any>>" in module
        assert module.count(f"memref<{w_block}xbf16, #tpu.memory_space<vmem>>") >= 2
        # three copies in the text: the call's first block, and the next run's from either buffer's arm, those two behind the rows' own
        assert module.count("tpu.enqueue_dma") == 3 and module.count("priority = 1 : i32") == 2
    else:
        assert f"iteration_bounds = array<i64: {nt}, {plan['col_blocks']}>" in module and "scalar_prefetch = 2" in module
        assert "tpu.enqueue_dma" not in module and "memory_space<any>" not in module
    if case in GMM_DECODE_TEXT and not transpose_w:
        assert _gmm_text_digest(_gmm_lowered(case, False, None).as_text()) == GMM_DECODE_TEXT[case]
    if tpu_sharding is not None:
        assert re.search(r"%moe_grouped_mm(\.\d+)? = ", lowered.compile().as_text())


def test_every_cells_decode_step_is_held_to_the_text_the_parent_lowers():
    assert sorted(GMM_DECODE_TEXT) == sorted(c for c in GMM_SHAPES if c.endswith("/decode") and GMM_SHAPES[c][5])


def test_the_hybrid_trainers_grouped_product_states_no_vmem_limit(tpu_sharding):
    """``2048 x 512``: whole 2 MiB blocks, two of them the kernel's own, under the default limit, as it was."""
    text = _gmm_lowered("hybrid/2048x512/train", False, tpu_sharding).as_text()
    assert 'kernel_name = "moe_grouped_mm"' in text and "scoped_memory_configs" not in text
    module = _mosaic_module(text)
    assert "iteration_bounds = array<i64: 128>" in module and module.count("memref<2048x512xbf16, #tpu.memory_space<vmem>>") >= 2


# tokens, k, held, the wave's rows, their tile, C: a prompt of each cell that holds an expert share, the trainer's step, a decode step's
COMBINE_SHAPES = {
    "axk1_prefill": (8192, 8, 12, 6144, 128, 7168), "xing4_prefill": (8192, 4, 64, 45056, 128, 3584),
    "hybrid_train": (16384, 10, 32, 16384, 128, 2048), "trinity_prefill": (9984, 8, 16, 13312, 128, 2048),
    "lfm2_prefill": (2048, 4, 32, 12288, 128, 2048), "axk1_decode": (64, 8, 12, 128, 16, 7168),
    "lfm2_decode": (256, 4, 32, 3200, 64, 2048),
}


@pytest.mark.parametrize("summed", ["float32", "bfloat16"], ids=["the_shares_sum", "the_rows_gradients_sum"])
@pytest.mark.parametrize("cell", sorted(COMBINE_SHAPES))
def test_the_combine_compiles_at_the_cells_shapes_inside_the_vmem_it_asks_for(cell, summed, tpu_sharding, monkeypatch):
    """``moe_combine`` at the cells' own shapes, bfloat16 rows: ``(8192, 8)``
    into ``(N, 7168)`` from a wave of 6,144 rows, ``(8192, 4)`` into ``(N,
    3584)`` from 45,056, the trainer's ``(16384, 10)`` into ``(N, 2048)`` from
    16,384, a decode step's (which ``jaxex`` leaves to XLA: the kernel compiles
    all the same).  Only the compile shows that Mosaic takes the chunk copies
    (a sublane tile of rows out of a tiled array in HBM), the wave's whole list
    of rows in SMEM and the row reads at a dynamic sublane; and holds the call to the
    scoped limit it states, which stays under what a v5e gives."""
    monkeypatch.setattr(px, "_enabled", lambda: True)
    monkeypatch.setattr(px, "_gmm_vmem_cap", lambda: 96 << 20)      # three quarters of a v5e core's, which the chip reports
    N, k, held, R, tile, C = COMBINE_SHAPES[cell]
    args = [jax.ShapeDtypeStruct(sh, dt, sharding=tpu_sharding) for sh, dt in (((R, C), BF), ((R,), I32), ((R // tile,), I32))]
    before = px.stats.get("moe_combine", 0)
    lowered = jax.jit(lambda vb, rs, tg: px.combine(vb, rs, tg, N, k, held, summed)).trace(*args).lower(lowering_platforms=("tpu",))
    assert px.stats["moe_combine"] == before + 1
    plan = dict(px.combine_schedule)
    assert plan["block_tokens"] == min(N, 128) and plan["streams"] == held and plan["chunk_rows"] == 16 and plan["rows_listed"] == R
    assert plan["vmem_limit_bytes"] == px._combine_vmem(plan["block_tokens"], C, held, 2, jnp.dtype(summed).itemsize) <= 96 << 20
    text = lowered.as_text()
    assert text.count("tpu_custom_call") == 1 and 'kernel_name = "moe_combine"' in text
    assert f'\\22size\\22: {max(plan["vmem_limit_bytes"], px._GMM_VMEM_DEFAULT)}}}' in text     # the scoped limit the call states
    module = _mosaic_module(text)
    assert f"memref<{R}x{C}xbf16, #tpu.memory_space<any>>" in module and f"iteration_bounds = array<i64: {-(-N // 128)}>" in module
    if tpu_sharding is not None:
        assert re.search(r"%moe_combine(\.\d+)? = ", lowered.compile().as_text())


def test_the_combine_declines_what_does_not_fit_the_vmem_of_an_unknown_device(monkeypatch):
    """Where the device's VMEM is not known (this CPU, a lowering for another
    host's chip) the chunks of a wide share do not fit the default 16 MiB and
    the call is XLA's; the token tile shrinks before that."""
    monkeypatch.setattr(px, "_enabled", lambda: True)
    assert px._gmm_vmem_cap() == px._GMM_VMEM_DEFAULT
    assert px.combine_declines(8192, 45056, 352, 3584, 64, BF, F32) == "VMEM"
    assert px.combine_declines(8192, 6144, 48, 7168, 12, BF, F32) == "" and px._combine_tile(8192, 7168, 12, 2, 4) == 16
    assert px.combine_declines(16384, 16384, 128, 2048, 32, BF, BF) == "" and px._combine_tile(16384, 2048, 32, 2, 2) == 128
    # a row, its group and its token in one int32 of the list, and the list in SMEM
    assert px.combine_declines(16384, 1 << 18, 2048, 2048, 32, BF, BF) == "shape"
    assert px.combine_declines(16384, 1 << 17, 1024, 2048, 1024, BF, BF) == "shape"


def test_every_pallas_call_site_is_named():
    import inspect

    src = inspect.getsource(px)
    assert src.count("pallas_call(") == len(re.findall(r"\n +name=", src)) == 25      # PR 45: the Mamba-2 scan's two; PR 56: hc_mix; PR 58: moe_combine; PR 63: the flash backward's one walk
    assert {n for names in map(kernel_names, CASES["gqa"]) for n in names} == {
        "_flash_fwd", "_flash_bwd_dq", "_flash_bwd_dkv", "flash_cross_entropy",
        "paged_attn_decode", "paged_attn_decode_quant", "paged_attn_verify",
        "paged_attn_verify_quant", "paged_token_write", "paged_token_write_masked",
        "paged_token_write_fused", "paged_token_write_fused_masked",
        "paged_chunk_write", "paged_chunk_write_fused", "lora_delta_fused",
        "gdn_chunk_fwd", "gdn_chunk_bwd", "gdn_decode_step", "moe_grouped_mm", "moe_grouped_mm_dw",
        "causal_conv1d_fwd", "causal_conv1d_bwd"}


@pytest.mark.slow
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_kernels_agree_with_their_jnp_references_interpreted(dtype):
    """The check ``chip_smoke.py`` runs compiled on the chip, interpreted
    here at tiny widths: every serving kernel against the gather path's own
    building blocks, tolerances as documented in ``serving.kernel_check``."""
    rows = run_checks(n_head=4, n_query_groups=2, head_size=16, block_size=4,
                      dtype=dtype, window=7)
    assert len(rows) >= 20
    assert all(r["ok"] for r in rows), [r for r in rows if not r["ok"]]


# --------------------------------------------------------------------------
# a decoder-hybrid-decoder as the Phi-4-mini-flash cell serves it: a selective
# scan, differential attention over a ring and over one layer's shared blocks
# --------------------------------------------------------------------------

FLASH_CELL = "phi4flash-serve-1chip.offline-reason"


@pytest.mark.parametrize("kernel", ["ssm_scan_fwd", "ssm_decode_step", "paged_attn_decode/shared", "paged_attn_decode/ring"])
def test_the_hybrid_decoder_cells_kernels_compile_at_its_shapes(kernel, tpu_sharding, monkeypatch):
    """The scan over the longest prefill bucket (5,120 tokens of 5,120 channels
    by 16 states, the state carried in VMEM, nothing of the state's history
    kept); the step on 96 rows' slots of the nine layers' arena, in place; the
    differential walk (two softmaxes a head pair, ``packed_out``) over the one
    global layer's blocks at a table of 552 and over a ring's at a window of
    512: whole-tile slabs, no arena copied.  Only the compile shows Mosaic
    holding the state in VMEM at these widths, the step's arena aliased to its
    result and no temporary beside the walk."""
    monkeypatch.setattr(px, "_enabled", lambda: True)
    kernel, _, which = kernel.partition("/")
    d, N, rows, width = 5120, 16, 96, 552
    if kernel == "ssm_scan_fwd":
        Ts = 5120
        fn, claim = px.ssm_scan, "ssm_scan"
        specs = [((1, Ts, d), BF), ((1, Ts, d), F32), ((1, Ts, N), F32), ((1, Ts, N), F32), ((N, d), F32), ((1, N, d), F32)]
    elif kernel == "ssm_decode_step":
        fn, claim = functools.partial(px.ssm_decode_step, layer=8), "ssm_decode"
        specs = [((rows + 1, 9, N, d), F32), ((rows,), I32), ((rows, d), BF), ((rows, d), F32), ((rows, N), F32),
                 ((rows, N), F32), ((N, d), F32)]
    else:
        ring = which == "ring"
        arena = (((rows + 1) * 33, 8, 10, BS, 128) if ring else (36864, 1, 10, BS, 128), BF)
        fn, claim = functools.partial(px.paged_attn_decode, layer=7 if ring else 0, window=512 if ring else None,
                                      packed_out=True), None
        specs = [((rows, 40, 64), BF), arena, arena, ((rows, 20, 64), BF), ((rows, 20, 64), BF), ((rows, width), I32),
                 ((rows,), I32)]
    before = dict(px.stats)
    args = [jax.ShapeDtypeStruct(s, dt, sharding=tpu_sharding) for s, dt in specs]
    lowered = jax.jit(fn).trace(*args).lower(lowering_platforms=("tpu",))
    text = lowered.as_text()
    assert text.count("tpu_custom_call") == 1 and f'kernel_name = "{kernel}"' in text
    if claim:
        assert px.stats.get(claim, 0) == before.get(claim, 0) + 1            # claimed, not the XLA form
    if tpu_sharding is not None:
        compiled = lowered.compile()
        call = next(l for l in compiled.as_text().splitlines() if re.search(rf"%{kernel}(\.\d+)? = ", l))
        if kernel == "ssm_decode_step":
            assert "output_to_operand_aliasing" in call                       # in place on the arena
        if kernel == "paged_attn_decode":
            assert compiled.memory_analysis().temp_size_in_bytes == 0         # no arena copy
            assert jax.eval_shape(fn, *args).shape == (rows, 10, 4, 128)      # a pair's two softmaxes, V_g whole


@functools.cache
def _flash_engine():
    """The cell's engine at its published widths, eight layers (three scans, two
    window layers, the global layer, a gated memory unit and a cross layer), over
    weights that are shapes alone."""
    import thunder_tpu as tt
    from chipbench import common
    from thunder_tpu.models import llama

    _, config, mix = common.open_cell(FLASH_CELL)
    arch = common.load_module("models", config["arch"])
    hf = {**config, "num_hidden_layers": 8}
    cfg = llama.Config(**arch.program_config(hf))
    params = jax.eval_shape(functools.partial(arch.make_params, hf), common.seed_words(1))
    return cfg, params, tt.serve(None, params, cfg, **{**config["engine"], **mix["engine"], "num_blocks": 700})


@pytest.mark.parametrize("kind", ["prefill_fresh", "decode_paged"])
def test_the_hybrid_decoder_cells_programs_lower_to_their_kernels(kind, tpu_sharding, monkeypatch):
    """A whole prompt's prefill scans through ``ssm_scan_fwd``, attends through
    ``_flash_fwd`` (a pair's queries padded with zeros into their half of a
    128-lane row) in the self-decoder and projects the head for one row; a decode
    step of 96 rows runs ``ssm_decode_step`` a scan layer, walks the rings and the
    one global layer's blocks through ``paged_attn_decode`` (the cross layer
    walks the global layer's: one more call, no more arena), and lands K and V
    through one ``paged_token_write`` a kind each; no arena is gathered.  Only
    the compile shows XLA taking the program with its Mosaic calls and keeping
    each under its name: the prompt's at ``COMPILED_BUCKET``, the step's as it is."""
    monkeypatch.setattr(px, "_enabled", lambda: True)
    monkeypatch.setattr(px, "_pallas_available", lambda: True)
    cfg, params, eng = _flash_engine()
    st = eng.stats()
    assert st["attn"]["path"] == "walk" and st["attn"]["lane_pack"] == 2
    assert st["attn"]["shared_kv_layers"] == 2
    state = eng.pool.state
    assert eng.pool.k_arena.shape == (700, 1, 10, 16, 128) and state.ring_blocks == 33
    assert state.shapes == {"conv": (97, 3, 3, 5120), "state": (97, 3, 16, 5120),
                            "k_ring": (97 * 33, 2, 10, 16, 128), "v_ring": (97 * 33, 2, 10, 16, 128)}
    sds = lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=tpu_sharding)  # noqa: E731
    one = lambda shape, dt=I32: jax.ShapeDtypeStruct(shape, dt, sharding=tpu_sharding)  # noqa: E731
    weights, arenas = jax.tree_util.tree_map(sds, params), jax.tree_util.tree_map(sds, eng.pool.arenas)
    before = dict(px.stats)
    prefill = _fresh_prefill(eng, weights, arenas, one)
    if kind == "prefill_fresh":
        Tb = 5120
        prog, args = prefill(Tb)
    else:
        prog = eng._build_decode_paged(96, 552)
        args = (weights, one((96,)), one((96,)), one((96, 552)), arenas, one((96, 2), jnp.uint32), {}, one((96,)),
                one((96,)))
    lowered = prog.trace(*args).lower(lowering_platforms=("tpu",))
    text = lowered.as_text()
    claimed = lambda k: px.stats.get(k, 0) - before.get(k, 0)  # noqa: E731
    assert "paged_attn_verify" not in text
    assert not re.search(r"gather[^\n]*tensor<\d+x\d+x\d+x\d+x\d+x", text)      # nothing of an arena's five dims
    if kind == "prefill_fresh":
        assert claimed("ssm_scan") == 3 and text.count('kernel_name = "ssm_scan_fwd"') >= 1
        assert claimed("direct") == 2 and 'kernel_name = "_flash_fwd"' in text   # the two window layers: whole prompts
        assert {int(m) for m in re.findall(rf"tensor<1x(\d+)x{cfg.padded_vocab_size}xf32>", text)} == {1}
        assert {int(m) for m in re.findall(r"tensor<1x(\d+)x10240xbf16>", text)} == {1, Tb}   # the cross half's MLPs: a row
    else:
        assert claimed("ssm_decode") == 3 and text.count('kernel_name = "ssm_decode_step"') >= 1
        # two rings, the global layer, the cross layer: four call sites, and one lowered body a form (the layer is
        # an operand of ``_paged_decode_call``): the rings' (a window, their arenas) and the shared blocks'
        assert claimed("paged_walk") == 4 and text.count('kernel_name = "paged_attn_decode"') == 2
        assert len(re.findall(r"call @_paged_decode_call", text)) == 4
        assert text.count('kernel_name = "paged_token_write"') == 4              # K and V of the paged kind and of the ring
    if tpu_sharding is not None:
        hlo = (_compiled_prefill(prefill) if kind == "prefill_fresh" else lowered.compile()).as_text()
        names = ("_flash_fwd", "ssm_scan_fwd") if kind == "prefill_fresh" else (
            "paged_attn_decode", "paged_token_write", "ssm_decode_step")
        for name in names:
            assert re.search(rf"%{name}(\.\d+)? = ", hlo), name


NEMO_CELL = "nemotron3super-serve-1chip.offline-rollouts"


@pytest.mark.parametrize("kernel", ["ssd_chunk_fwd", "ssd_chunk_fwd/float32", "ssd_decode_step"])
def test_the_mamba2_cells_kernels_compile_at_its_shapes(kernel, tpu_sharding, monkeypatch):
    """The chunked scan over the longest prefill bucket (5,120 tokens of 128 heads
    of 64 channels in 8 groups, 128 states; a group's ``(128, 1024)`` float32 state
    carried in VMEM across the token blocks, the last state out), in bfloat16 and
    at full precision (a float32 witness); the step on 128 rows' slots of the five
    layers' arena, ``(128, 8192)`` = 4.19 MB a slot a layer, in place.  Only
    the compile shows Mosaic carrying the state in VMEM at these shapes, the
    arena aliased to the step's result and no copy of it."""
    monkeypatch.setattr(px, "_enabled", lambda: True)
    kernel, _, which = kernel.partition("/")
    H, P, G, N, rows, Ts = 128, 64, 8, 128, 128, 5120
    d, xdt = H * P, F32 if which == "float32" else BF
    if kernel == "ssd_chunk_fwd":
        fn, claim = px.ssd_chunk, "ssd_chunk"
        specs = [((1, Ts, d), xdt), ((1, Ts, H), F32), ((1, Ts, G, N), xdt), ((1, Ts, G, N), xdt), ((H,), F32),
                 ((1, N, d), F32)]
    else:
        fn, claim = functools.partial(px.ssd_decode_step, layer=4), "ssd_decode"
        specs = [((rows + 1, 5, N, d), F32), ((rows,), I32), ((rows, d), BF), ((rows, H), F32), ((rows, G, N), BF),
                 ((rows, G, N), BF), ((H,), F32)]
    before = dict(px.stats)
    args = [jax.ShapeDtypeStruct(s, dt, sharding=tpu_sharding) for s, dt in specs]
    lowered = jax.jit(fn).trace(*args).lower(lowering_platforms=("tpu",))
    text = lowered.as_text()
    assert text.count("tpu_custom_call") == 1 and f'kernel_name = "{kernel}"' in text
    assert px.stats.get(claim, 0) == before.get(claim, 0) + 1 and px.stats["ssd"] == before.get("ssd", 0) + 1
    if kernel == "ssd_chunk_fwd":
        assert px.ssd_schedule == {"block_tokens": 512, "chunk": 128, "heads": H, "head_dim": P, "groups": G, "states": N}
    if tpu_sharding is not None:
        compiled = lowered.compile()
        call = next(l for l in compiled.as_text().splitlines() if re.search(rf"%{kernel}(\.\d+)? = ", l))
        if kernel == "ssd_decode_step":
            assert "output_to_operand_aliasing" in call                       # in place on the arena
            assert compiled.memory_analysis().temp_size_in_bytes < 64 << 20   # no copy of the 2.7 GB arena


@functools.cache
def _nemo_engine():
    """The cell's engine at its published widths and its own depth (one period of
    eleven: five Mamba-2, five expert and one attention layer), over weights that
    are shapes alone."""
    import thunder_tpu as tt
    from chipbench import common
    from thunder_tpu.models import llama

    _, config, mix = common.open_cell(NEMO_CELL)
    arch = common.load_module("models", config["arch"])
    cfg = llama.Config(**arch.program_config(config))
    params = jax.eval_shape(functools.partial(arch.make_params, config), common.seed_words(1))
    return cfg, params, tt.serve(None, params, cfg, **{**config["engine"], **mix["engine"], "num_blocks": 700,
                                                       "max_batch": 8, "batch_buckets": [8]})


@pytest.mark.parametrize("kind", ["prefill_fresh", "decode_paged"])
def test_the_mamba2_cells_programs_lower_to_their_kernels(kind, tpu_sharding, monkeypatch):
    """A whole prompt's prefill scans through ``ssd_chunk_fwd`` a Mamba-2 layer,
    attends through ``_flash_fwd`` in the one attention layer, sorts its rows
    through ``moe_grouped_mm`` (two products an expert layer: no ``fc_2``) and
    projects the head for one row; a decode step runs ``ssd_decode_step`` a Mamba-2
    layer on the state arena in place, walks the attention layer's blocks through
    ``paged_attn_decode`` and returns the expert share's running sums; no arena is
    gathered.  Only the compile shows XLA taking the program with its Mosaic calls
    and keeping each under its name: the prompt's at ``COMPILED_BUCKET``, the
    step's as it is."""
    monkeypatch.setattr(px, "_enabled", lambda: True)
    monkeypatch.setattr(px, "_pallas_available", lambda: True)
    monkeypatch.setattr(px, "_gmm_vmem_cap", lambda: 96 << 20)
    cfg, params, eng = _nemo_engine()
    st = eng.stats()
    assert st["attn"]["path"] == "walk" and st["attn"]["lane_pack"] == 1 and st["moe"]["experts_held"] == 128
    assert eng.pool.k_arena.shape == (700, 1, 2, 16, 128)
    assert eng.pool.state.shapes == {"conv": (9, 5, 3, 10240), "state": (9, 5, 128, 8192)}
    sds = lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=tpu_sharding)  # noqa: E731
    one = lambda shape, dt=I32: jax.ShapeDtypeStruct(shape, dt, sharding=tpu_sharding)  # noqa: E731
    weights, arenas = jax.tree_util.tree_map(sds, params), jax.tree_util.tree_map(sds, eng.pool.arenas)
    before = dict(px.stats)
    prefill = _fresh_prefill(eng, weights, arenas, one)
    if kind == "prefill_fresh":
        Tb = 5120
        prog, args = prefill(Tb)
    else:
        prog = eng._build_decode_paged(8, 496)
        args = (weights, one((8,)), one((8,)), one((8, 496)), arenas, one((8, 2), jnp.uint32), {}, one((8,)),
                one((4,), F32), one((8,)))
    lowered = prog.trace(*args).lower(lowering_platforms=("tpu",))
    text = lowered.as_text()
    claimed = lambda k: px.stats.get(k, 0) - before.get(k, 0)  # noqa: E731
    assert "paged_attn_verify" not in text
    assert not re.search(r"gather[^\n]*tensor<\d+x\d+x\d+x\d+x\d+x", text)      # nothing of an arena's five dims
    assert claimed("grouped_mm") >= 10 and 'kernel_name = "moe_grouped_mm"' in text
    if kind == "prefill_fresh":
        assert claimed("ssd_chunk") == 5 and claimed("ssd") == 5 and 'kernel_name = "ssd_chunk_fwd"' in text
        assert claimed("direct") == 1 and 'kernel_name = "_flash_fwd"' in text
        assert {int(m) for m in re.findall(rf"tensor<1x(\d+)x{cfg.padded_vocab_size}xf32>", text)} == {1}
    else:
        assert claimed("ssd_decode") == 5 and claimed("ssd") == 5 and 'kernel_name = "ssd_decode_step"' in text
        assert text.count('kernel_name = "paged_attn_decode"') == 1
        assert text.count('kernel_name = "paged_token_write"') == 2
    if tpu_sharding is not None:
        compiled = _compiled_prefill(prefill) if kind == "prefill_fresh" else lowered.compile()
        hlo = compiled.as_text()
        names = ("_flash_fwd", "ssd_chunk_fwd", "moe_grouped_mm") if kind == "prefill_fresh" else (
            "paged_attn_decode", "paged_token_write", "ssd_decode_step", "moe_grouped_mm")
        for name in names:
            assert re.search(rf"%{name}(\.\d+)? = ", hlo), name
        print(kind, f"temporaries (a bucket of {COMPILED_BUCKET})" if kind == "prefill_fresh" else "temporaries",
              compiled.memory_analysis().temp_size_in_bytes)


if __name__ == "__main__":      # the digests of ``GMM_DECODE_TEXT``, from the tree this file's ``thunder_tpu`` is imported from
    px._interpret = lambda: False
    px._gmm_vmem_cap = lambda: 96 << 20
    print(px.__file__)
    for case_ in sorted(c for c in GMM_SHAPES if c.endswith("/decode") and GMM_SHAPES[c][5]):
        print(f'    "{case_}": "{_gmm_text_digest(_gmm_lowered(case_, False, None).as_text())}",')


# --------------------------------------------------------------------------
# a looped model as the Ouro-2.6B cell serves it: the slab a traced operand
# --------------------------------------------------------------------------

def test_the_walk_compiles_with_the_slab_a_traced_operand_inside_a_scan(tpu_sharding, monkeypatch):
    """``paged_attn_decode`` at the looped cell's decode shapes (16 query heads over 16
    KV heads of 128, 192 slabs, 12 rows of 28 blocks, the cell's pool) with the layer
    ``t * 48 + 47``, ``t`` the carry of a ``lax.scan`` over the four passes: the walk takes
    its layer as an operand, so the scan's body holds one kernel, Mosaic compiles it
    and no arena is copied or sliced."""
    monkeypatch.setattr(px, "_enabled", lambda: True)
    nh, ng, rows, width, pool, slabs = 16, 16, 12, 28, 336, 192
    arena = ((pool, slabs, ng, BS, 128), BF)
    specs = [((rows, nh, HS), BF), arena, arena, ((rows, ng, HS), BF), ((rows, ng, HS), BF), ((rows, width), I32), ((rows,), I32)]
    args = [jax.ShapeDtypeStruct(s, dt, sharding=tpu_sharding) for s, dt in specs]

    def passes(q, k, v, fk, fv, tables, pos):
        def one(h, t):
            y = px.paged_attn_decode(h, k, v, fk, fv, tables, pos, layer=t * 48 + 47)
            return y, None
        return jax.lax.scan(one, q, jnp.arange(4, dtype=I32))[0]

    before = px.stats.get("paged_walk", 0)
    lowered = jax.jit(passes).trace(*args).lower(lowering_platforms=("tpu",))
    text = lowered.as_text()
    assert px.stats["paged_walk"] == before + 1 and text.count('kernel_name = "paged_attn_decode"') == 1
    assert "stablehlo.while" in text and "paged_attn_verify" not in text
    if tpu_sharding is not None:
        compiled = lowered.compile()
        call = next(l for l in compiled.as_text().splitlines() if re.search(r"%paged_attn_decode(\.\d+)? = ", l))
        assert re.search(rf"= bf16\[{rows},{ng},1,128\]\S* custom-call\(%", call), call
        assert compiled.memory_analysis().temp_size_in_bytes < 1 << 20        # no slab of the arena copied out


@functools.cache
def _looped_engine():
    """The Ouro-2.6B cell's engine at its published widths and its four passes, the
    depth cut to two blocks (8 slabs), over weights that are shapes alone."""
    import thunder_tpu as tt
    from chipbench import common
    from thunder_tpu.models import llama

    _, config, mix = common.open_cell("ouro-serve-1chip.offline-shortqa")
    arch = common.load_module("models", config["arch"])
    hf = {**config, "num_hidden_layers": 2}
    cfg = llama.Config(**arch.program_config(hf))
    params = jax.eval_shape(functools.partial(arch.make_params, hf), common.seed_words(1))
    return cfg, params, tt.serve(None, params, cfg, **{**config["engine"], **mix["engine"]})


@pytest.mark.parametrize("kind", ["prefill_fresh", "decode_paged"])
def test_the_looped_cells_programs_lower_to_their_kernels(kind, tpu_sharding, monkeypatch):
    """The passes are one loop in each program: a whole prompt of 384 tokens attends
    through one ``_flash_fwd`` a block of the body (two here), whatever the four passes;
    a decode step of 12 rows x 28 blocks walks its slab through one ``paged_attn_decode``
    body and lands all 8 slabs' K and V through one ``paged_token_write`` an arena; no
    arena is gathered, and both return the exit rule's rows beside the token."""
    monkeypatch.setattr(px, "_enabled", lambda: True)
    cfg, params, eng = _looped_engine()
    st = eng.stats()
    assert st["attn"]["path"] == "walk" and eng.pool.k_arena.shape == (336, 8, 16, 16, 128)
    sds = lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=tpu_sharding)  # noqa: E731
    one = lambda shape, dt=I32: jax.ShapeDtypeStruct(shape, dt, sharding=tpu_sharding)  # noqa: E731
    weights, arenas = jax.tree_util.tree_map(sds, params), jax.tree_util.tree_map(sds, eng.pool.arenas)
    before = dict(px.stats)
    if kind == "prefill_fresh":
        prog, args = _fresh_prefill(eng, weights, arenas, one, state_slots=False)(384)
    else:
        prog = eng._build_decode_paged(12, 28)
        args = (weights, one((12,)), one((12,)), one((12, 28)), arenas, one((12, 2), jnp.uint32), {}, one((12,)))
    lowered = prog.trace(*args).lower(lowering_platforms=("tpu",))
    text = lowered.as_text()
    claimed = lambda k: px.stats.get(k, 0) - before.get(k, 0)  # noqa: E731
    out = jax.eval_shape(prog, *args)
    assert out[-1].shape == ((1, 5) if kind == "prefill_fresh" else (12, 5)) and out[-1].dtype == F32
    assert "stablehlo.while" in text and "paged_attn_verify" not in text
    assert not re.search(r"gather[^\n]*tensor<\d+x\d+x\d+x\d+x\d+x", text)      # nothing of an arena's five dims
    if kind == "prefill_fresh":
        assert claimed("direct") == 2 and 'kernel_name = "_flash_fwd"' in text
        assert {int(m) for m in re.findall(rf"tensor<1x(\d+)x{cfg.padded_vocab_size}xf32>", text)} == {1}
    else:
        assert text.count('kernel_name = "paged_attn_decode"') == 1 and claimed("paged_walk") == 2
        assert 1 <= text.count('kernel_name = "paged_token_write"') <= 2
    if tpu_sharding is not None:
        hlo = lowered.compile().as_text()
        for name in (("_flash_fwd",) if kind == "prefill_fresh" else ("paged_attn_decode", "paged_token_write")):
            assert re.search(rf"%{name}(\.\d+)? = ", hlo), name
