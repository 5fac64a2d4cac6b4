"""The flash kernels at lengths their blocks do not divide (PR 49): a ragged
last block under the interpreter against the float32 reference, the schedule
over ``ceil(T / B)`` blocks, the block ``_flash_blocks`` takes at every
benchmark cell's calls, and what stays as it was where the block divides."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from thunder_tpu.executors import pallasex
from thunder_tpu.executors.jaxex import _sdpa_backward_reference, _sdpa_reference


@pytest.fixture
def interpret_kernels(monkeypatch):
    monkeypatch.setenv("THUNDER_TPU_PALLAS_INTERPRET", "1")


RAGGED = [
    # Tq, Tk, BQ, BK, causal, window: no length is a multiple of its block
    (640, 640, 256, 256, True, None),       # the triangle, a ragged diagonal block
    (640, 640, 512, 512, True, 384),        # ... under a window, more of the last block past the end than in it
    (896, 896, 256, 512, True, 384),        # BQ != BK
    (896, 896, 512, 256, False, None),      # the rectangle: no causal mask to cut the columns past Tk
    (640, 896, 256, 256, True, None),       # Tq < Tk
    (896, 640, 512, 512, True, None),       # Tq > Tk: rows whose causal mask reaches past Tk
    (896, 640, 256, 256, False, None),
    (640, 896, 512, 512, True, 384),
]
RAGGED_HEADS = [(1, 128), (4, 64), (8, 128)]    # query heads a KV group, head size (64: padded to 128)


@pytest.mark.parametrize("case,rep,hs", [
    (case, *RAGGED_HEADS[(n + turn) % 3]) for turn in (0, 1) for n, case in enumerate(RAGGED) if turn == 0 or n % 2 == 0],
    ids=lambda c: "-".join(map(str, c)) if isinstance(c, tuple) else str(c))
def test_flash_ragged_last_block_matches_reference(monkeypatch, interpret_kernels, case, rep, hs):
    """Lengths their blocks do not divide (the two environment variables fix
    the blocks): out, lse and the three gradients against the float32
    reference.  The interpreter fills what a ragged block's copy does not
    bring with NaN, so a tail that reached any sum would show in every one."""
    Tq, Tk, BQ, BK, causal, window = case
    monkeypatch.setenv("THUNDER_TPU_FLASH_BQ", str(BQ))
    monkeypatch.setenv("THUNDER_TPU_FLASH_BK", str(BK))
    ks = jax.random.split(jax.random.PRNGKey(rep * 1000 + hs), 4)
    q, g = (jax.random.normal(k, (1, 2 * rep, Tq, hs), jnp.float32) for k in ks[:2])
    k, v = (jax.random.normal(k, (1, 2, Tk, hs), jnp.float32) for k in ks[2:])
    scale = 1.0 / np.sqrt(hs)
    out, lse = pallasex.flash_sdpa(q, k, v, None, causal, scale, window)
    sched = dict(pallasex.flash_schedule)
    assert (sched["block_q"], sched["block_k"], sched["tail_rows"]) == (BQ, BK, -Tq % BQ) and sched["tail_rows"]
    got = (out, lse, *pallasex.flash_sdpa_backward(g, q, k, v, out, lse, None, causal, scale, window))
    oref, lref = _sdpa_reference(q, k, v, None, causal, scale, window)
    want = (oref, lref, *_sdpa_backward_reference(g, q, k, v, oref, lref, None, causal, scale, window))
    for a, b, n, tol in zip(got, want, ("out", "lse", "dq", "dk", "dv"), (2e-5, 2e-5, 1e-4, 1e-4, 1e-4)):
        assert np.isfinite(np.asarray(a)).all(), n
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=tol, rtol=tol, err_msg=n)


def _kept_in_ragged_blocks(Tq, Tk, BQ, BK, causal, window):
    """``_kept_by_brute_force`` with both lengths rounded up: a pair past
    either end is a masked one."""
    r, c = np.arange(-(-Tq // BQ) * BQ)[:, None], np.arange(-(-Tk // BK) * BK)[None, :]
    keep = (r < Tq) & (c < Tk)
    if causal:
        keep &= r >= c
    if window is not None:
        keep &= c > r - window
    blocks = keep.reshape(-(-Tq // BQ), BQ, -(-Tk // BK), BK)
    real = ((r < Tq) & (c < Tk)).reshape(blocks.shape)
    return blocks.any(axis=(1, 3)), (blocks | ~real).all(axis=(1, 3))


@pytest.mark.parametrize("by_column", [False, True], ids=["rows", "columns"])
@pytest.mark.parametrize("case", RAGGED + [(9984, 9984, 1024, 1024, True, None), (9984, 9984, 1024, 1024, True, 2048)],
                         ids=lambda c: "-".join(map(str, c)))
def test_flash_schedule_of_a_ragged_rectangle(case, by_column):
    """The list over ``ceil(T / B)`` blocks: every block with a kept pair of
    the real rectangle, ``_EDGE`` where a pair *inside* both lengths is masked
    (what lies past them is the tail flags' to say), ``_TAILQ`` / ``_TAILK``
    on the last row and column of blocks."""
    Tq, Tk, BQ, BK, causal, window = case
    some, whole = _kept_in_ragged_blocks(*case)
    qi, kj, _, flag = pallasex._flash_schedule(*case, by_column=by_column)
    listed = np.zeros_like(some)
    listed[qi, kj] = True
    assert len(set(zip(qi, kj))) == len(qi) and (listed >= some).all()
    empty = ~some.any(axis=0 if by_column else 1)
    assert ((listed & ~some).sum(axis=0 if by_column else 1) == empty).all()
    assert (((flag & pallasex._EDGE) != 0) == ~whole[qi, kj]).all()
    assert (((flag & pallasex._TAILQ) != 0) == ((qi + 1) * BQ > Tq)).all() and (flag & pallasex._TAILQ).any()
    assert (((flag & pallasex._TAILK) != 0) == ((kj + 1) * BK > Tk)).all()      # Tq < Tk: nothing attends the last column


# a whole prompt's flash call a cell and a prefill bucket (chipbench/traffic/*.json): head size as the kernel sees it
# (64 padded, a latent's 192 as it is since PR 54, a differential pair packed), the layers' windows, and a bucket's block
# beside the largest that divides it, which it had to be before PR 49
CELL_CALLS = {
    "offline-batch": (128, (4096,), {1024: (1024, 1024), 2048: (1024, 1024), 3072: (1024, 1024)}),
    "offline-longgen": (128, (None,), {1024: (1024, 1024), 2048: (1024, 1024), 2560: (1024, 512)}),
    "offline-longctx": (192, (None,), {4096: (1024, 1024), 6144: (1024, 1024), 8192: (1024, 1024)}),
    "offline-digest": (192, (None,), {5120: (1024, 1024), 6656: (1024, 512), 8192: (1024, 1024)}),
    "offline-wide": (128, (None,), {1024: (1024, 1024), 2048: (1024, 1024), 2560: (1024, 512)}),
    "offline-reason": (128, (None,), {2048: (1024, 1024), 3584: (1024, 512), 5120: (1024, 1024)}),
    "offline-reason/window": (128, (512,), {2048: (512, 512), 3584: (512, 512), 5120: (512, 512)}),
    "offline-rollouts": (128, (None,), {2048: (1024, 1024), 3584: (1024, 512), 5120: (1024, 1024)}),
    "offline-docqa": (128, (None, 2048), {3840: (1024, 256), 5888: (1024, 256), 7936: (1024, 256), 9984: (1024, 256)}),
    "seq8k": (128, (4096,), {8192: (1024, 1024)}),
    "seq8k-x2": (256, (None,), {8192: (1024, 1024)}),
}


@pytest.mark.parametrize("cell", CELL_CALLS)
def test_flash_blocks_at_every_cells_buckets(monkeypatch, cell):
    """Trinity-Mini's four buckets, odd multiples of 256, go in blocks of 1024
    with 256 rows of the last past the end; 2560 and 3584 (five and seven
    blocks of 512) go in three and four of 1024 with 512 past it, as the
    chip's two constants say (PERF.md, PR 49: 12% and 22% under the blocks of
    512); every other call keeps its block, and both train cells theirs."""
    monkeypatch.delenv("THUNDER_TPU_FLASH_BQ", raising=False)
    monkeypatch.delenv("THUNDER_TPU_FLASH_BK", raising=False)
    hs, windows, buckets = CELL_CALLS[cell]
    for (T, (want, was)), window in ((b, w) for b in buckets.items() for w in windows):
        bind = window if window is not None and T > window else None      # as _attn_with_cache passes it
        q = jax.ShapeDtypeStruct((4, T, hs), jnp.bfloat16)
        assert pallasex._flash_blocks(q, q, 1, bind) == (want, want), (T, window)
        wide = bind is None or bind >= 2048
        assert was == next(b for b in (1024, 512, 256, 128)[0 if wide else 1:] if T % b == 0)


# _flash_schedule(8192, 8192, B, B, True, window) at ac9f002 (PR 48), before a block could be ragged:
# sha256 of the four arrays' bytes, by rows and by columns (rep 4)
SCHEDULES_AT_PR48 = {
    (1024, 4096): ("5652a1cbcb377ece", "74a7b45bd93e53bb"),
    (1024, None): ("6f6c832a4845562e", "b9e8cf101dd2561a"),
    (512, 4096): ("56bbbf9ebce7a444", "fc305cdd6a95c3bf"),
    (512, None): ("dab5fa3589dae52f", "9c328265c8da7559"),
}


@pytest.mark.parametrize("block,window", SCHEDULES_AT_PR48)
def test_flash_schedule_at_8192_is_what_it_was_before_ragged_blocks(block, window):
    import hashlib

    def digest(**kw):
        arrays = pallasex._flash_schedule(8192, 8192, block, block, True, window, **kw)
        assert all(a.dtype == np.int32 for a in arrays)
        return hashlib.sha256(b"".join(a.tobytes() for a in arrays)).hexdigest()[:16]

    assert (digest(), digest(by_column=True, rep=4)) == SCHEDULES_AT_PR48[block, window]


def _traced_ops(jaxpr, into):
    for eqn in jaxpr.eqns:
        into[eqn.primitive.name] = into.get(eqn.primitive.name, 0) + 1
        for v in eqn.params.values():
            for sub in (v if isinstance(v, (tuple, list)) else (v,)):
                inner = getattr(sub, "jaxpr", sub)
                if hasattr(inner, "eqns"):
                    _traced_ops(inner, into)
    return into


# every operation the Mistral train cell's flash calls trace (T 8192, window 4096, blocks of 1024: two forms of
# a body, whole and edge), counted by primitive at ac9f002 (PR 48): _flash_fwd, then _flash_bwd's two kernels
OPS_AT_PR48 = (
    {"add": 7, "and": 4, "broadcast_in_dim": 8, "cond": 4, "convert_element_type": 8, "div": 1, "dot_general": 4, "exp": 4,
     "ge": 1, "get": 19, "gt": 1, "iota": 2, "jit": 1, "log": 1, "max": 2, "mul": 8, "ne": 3, "not": 1, "pallas_call": 1,
     "program_id": 1, "reduce_max": 2, "reduce_sum": 2, "select_n": 1, "sub": 5, "swap": 11, "transpose": 1},
    {"add": 10, "and": 8, "broadcast_in_dim": 5, "cond": 8, "convert_element_type": 21, "dot_general": 14, "exp": 4, "ge": 2,
     "get": 41, "gt": 2, "iota": 4, "jit": 2, "mul": 17, "ne": 6, "not": 2, "pallas_call": 2, "program_id": 2,
     "reduce_sum": 1, "reshape": 1, "select_n": 2, "sub": 10, "swap": 14, "transpose": 2},
)


# ... and the backward's one walk of those blocks (PR 63), in the backward pair's place: ten products for fourteen
OPS_ONE_WALK = {"add": 8, "and": 4, "broadcast_in_dim": 4, "cond": 6, "convert_element_type": 16, "dot_general": 10, "eq": 2,
                "exp": 2, "ge": 1, "get": 24, "gt": 1, "iota": 2, "jit": 1, "mul": 13, "multiple_of": 2, "ne": 3, "not": 1,
                "pallas_call": 1, "program_id": 1, "reduce_sum": 1, "reshape": 1, "select_n": 1, "sub": 5, "swap": 12}


@pytest.mark.parametrize("form", ["two_kernels", "one_walk"])
@pytest.mark.parametrize("T", [8192, 9984])
def test_the_tails_form_is_traced_only_where_a_block_is_ragged(monkeypatch, T, form):
    """At a length its block divides the kernels trace what they traced
    before a block could be ragged, operation for operation (the backward's
    one walk what PR 63 wrote); at 9984 each holds a third form of its body
    (two more products in the forward kernel, seven in the backward pair, five
    in the one walk) with the cut at the length beside the band's."""
    monkeypatch.delenv("THUNDER_TPU_FLASH_BQ", raising=False)
    monkeypatch.delenv("THUNDER_TPU_FLASH_BK", raising=False)
    q, k = (jax.ShapeDtypeStruct((n, T, 128), jnp.bfloat16) for n in (8, 2))
    lse = jax.ShapeDtypeStruct((8, 1, T), jnp.float32)
    fwd = jax.make_jaxpr(lambda q, k, v: pallasex._flash_fwd.__wrapped__(
        q, k, v, None, True, 128 ** -0.5, 8, 2, None, 1, 4096))(q, k, k)
    bwd = jax.make_jaxpr(lambda g, q, k, v, o, l: pallasex._flash_bwd.__wrapped__(
        g, q, k, v, o, l, None, True, 128 ** -0.5, 8, 2, None, 1, 4096, form=form))(q, q, k, k, q, lse)
    got = _traced_ops(fwd.jaxpr, {}), _traced_ops(bwd.jaxpr, {})
    want = (OPS_AT_PR48[0], OPS_AT_PR48[1] if form == "two_kernels" else OPS_ONE_WALK)
    assert pallasex.flash_schedule["block_q"] == 1024 and pallasex.flash_schedule["tail_rows"] == -T % 1024
    assert pallasex.flash_schedule["bwd_form"] == form
    if T == 8192:
        assert got == want
    else:
        assert [g["dot_general"] - w["dot_general"] for g, w in zip(got, want)] == [2, 7 if form == "two_kernels" else 5]
        assert all("lt" in g for g in got) and not any("lt" in w for w in want)
