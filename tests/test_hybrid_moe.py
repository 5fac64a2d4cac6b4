"""The hybrid sparse decoder (Gated-DeltaNet and gated-attention layers, an
expert share behind a router over all experts) against the benchmark's plain
float32 reference (``chipbench/models/hybrid_moe_decoder.py``: the recurrence
token by token, the experts by a mask), at tiny sizes on the CPU.

Program and reference are given the same float32 weights, so they agree to
rounding; the tolerance is that of float32 sums in another order.
"""
from __future__ import annotations

import dataclasses
import functools
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import thunder_tpu as tt  # noqa: E402
from chipbench import common  # noqa: E402
from thunder_tpu.executors import jaxex  # noqa: E402
from thunder_tpu.executors import pallasex as px  # noqa: E402
from thunder_tpu.models import llama  # noqa: E402

arch = common.load_module("models", "hybrid_moe_decoder")

T = 128
TINY = {
    "model_name": "tiny-hybrid", "hidden_size": 64, "head_dim": 32, "num_attention_heads": 4,
    "num_key_value_heads": 2, "partial_rotary_factor": 0.25, "rope_theta": 10000000,
    "linear_num_key_heads": 2, "linear_num_value_heads": 4, "linear_key_head_dim": 16,
    "linear_value_head_dim": 16, "linear_conv_kernel_dim": 4, "moe_intermediate_size": 48,
    "shared_expert_intermediate_size": 48, "num_experts": 16, "published_num_experts": 16,
    "num_experts_per_tok": 4, "vocab_size": 256, "num_hidden_layers": 4, "full_attention_interval": 4,
    "max_position_embeddings": 256, "rms_norm_eps": 1e-6, "initializer_range": 0.05,
}


def tiny(held: int = 16, first: int = 0, **over) -> dict:
    return {**TINY, "num_experts": held, "first_expert": first, **over}


def rel(a, b) -> float:
    a, b = jnp.asarray(a, jnp.float32), jnp.asarray(b, jnp.float32)
    return float(jnp.linalg.norm(a - b) / jnp.maximum(jnp.linalg.norm(b), 1e-30))


def _batch(hf, B=2, seed=1):
    toks = jax.random.randint(jax.random.PRNGKey(seed), (B, T + 1), 0, hf["vocab_size"])
    return toks[:, :-1], toks[:, 1:]


def _noisy(params, seed=7):
    """Norm weights, ``A_log`` and ``dt_bias`` off their initial values, so
    that a wrong ``1 + w`` or a dropped bias shows."""
    leaves, tree = jax.tree_util.tree_flatten(params)
    keys = jax.random.split(jax.random.PRNGKey(seed), len(leaves))
    return jax.tree_util.tree_unflatten(tree, [
        x + 0.1 * jax.random.normal(k, x.shape, x.dtype) if x.ndim == 1 else x for x, k in zip(leaves, keys)])


@functools.lru_cache(maxsize=None)
def _compared(held: int):
    """Program and reference on one batch: logits of a sequence, loss, and the
    gradient of every weight, as ``{path: (program, reference)}``."""
    hf = tiny(held)
    params = _noisy(arch.make_params(hf, common.seed_words(5), dtype=jnp.float32))
    cfg = llama.Config(**arch.program_config(hf))
    cos, sin = arch.rope_tables(hf, T)
    idx, tgt = _batch(hf)
    logits = tt.jit(lambda p, i, c, s: llama.gpt_forward(p, i, c, s, cfg))(params, idx, cos, sin)[0]
    loss, grads = tt.value_and_grad(lambda p, i, t, c, s: llama.gpt_loss(p, i, t, c, s, cfg))(
        params, idx, tgt, cos, sin)
    grads = grads[0] if isinstance(grads, (tuple, list)) else grads
    ref_loss, parts = arch.ref_loss_and_grads(hf, params, idx, tgt, every_leaf=True)
    pairs = {}
    for where, part in parts:
        got = grads
        for w in where:
            got = got[w]
        for path, leaf in jax.tree_util.tree_flatten_with_path(part)[0]:
            g = got
            for k in path:
                g = g[k.key]
            pairs["/".join(map(str, where)) + jax.tree_util.keystr(path)] = (g, leaf)
    return {"logits": (logits, arch.ref_logits(hf, params, idx[0], jnp.arange(T))),
            "loss": (float(loss), ref_loss), "grads": pairs}


HELD = (16, 8, 4)
GROUPS = {"gdn": "['gdn']", "attn": "['attn']", "experts": "['mlp']['", "shared": "['shared']",
          "norms": "['norm_", "embedding_head": None}


@pytest.mark.parametrize("held", HELD)
def test_logits_match_reference(held):
    got, ref = _compared(held)["logits"]
    assert rel(got, ref) < 2e-5


@pytest.mark.parametrize("held", HELD)
def test_loss_matches_reference(held):
    got, ref = _compared(held)["loss"]
    assert abs(got - ref) < 1e-5 * abs(ref)


@pytest.mark.parametrize("group", sorted(GROUPS))
@pytest.mark.parametrize("held", HELD)
def test_every_weights_gradient_matches_reference(held, group):
    pairs = {k: v for k, v in _compared(held)["grads"].items()
             if (not k.startswith("blocks") if group == "embedding_head" else GROUPS[group] in k)}
    if group == "experts":
        pairs = {k: v for k, v in pairs.items() if "shared" not in k}
    assert pairs, group
    worst = {k: rel(g, r) for k, (g, r) in pairs.items()}
    # A_log and dt_bias are sums over every token of terms that cancel: float32 in another order
    assert max(worst.values()) < (2e-3 if group == "gdn" else 2e-4), worst


def test_every_leaf_of_the_model_is_compared():
    hf = tiny(8)
    n = len(jax.tree_util.tree_leaves(arch.make_params(hf, common.seed_words(1))))
    assert len(_compared(8)["grads"]) == n == 3 * 17 + 16 + 3


# --------------------------------------------------------------------------
# the chunked gated delta rule against the recurrence, token by token
# --------------------------------------------------------------------------

def _recurrence_and_states(q, k, v, g, beta):
    """o, and the float32 state before every token, (B, Hv, T, dk, dv)."""
    rep = v.shape[1] // q.shape[1]
    q, k = jnp.repeat(q, rep, 1), jnp.repeat(k, rep, 1)

    def head(q, k, v, g, b):
        def step(S0, x):
            qt, kt, vt, gt, bt = x
            S = S0 * jnp.exp(gt)
            d = (vt - S.T @ kt) * bt
            S = S + jnp.outer(kt, d)
            return S, (S.T @ qt, S0)
        return jax.lax.scan(step, jnp.zeros((k.shape[-1], v.shape[-1])), (q, k, v, g, b))[1]

    return jax.vmap(jax.vmap(head))(q, k, v, g, beta)


def _recurrence(q, k, v, g, beta):
    return _recurrence_and_states(q, k, v, g, beta)[0]


def _scan_inputs(Tn, decay, B=2, Hk=2, Hv=4, dk=16, dv=24):
    ks = jax.random.split(jax.random.PRNGKey(3), 5)
    q = jax.random.normal(ks[0], (B, Hk, Tn, dk))
    k = jax.random.normal(ks[1], (B, Hk, Tn, dk))
    q = q / jnp.linalg.norm(q, axis=-1, keepdims=True) * dk ** -0.5
    k = k / jnp.linalg.norm(k, axis=-1, keepdims=True)
    v = jax.random.normal(ks[2], (B, Hv, Tn, dv))
    beta = jax.nn.sigmoid(jax.random.normal(ks[3], (B, Hv, Tn)))
    g = -decay * jax.nn.softplus(jax.random.normal(ks[4], (B, Hv, Tn)))
    return q, k, v, g, beta


DECAYS = {"g_near_0": 1e-3, "g_moderate": 1.0, "g_strongly_negative": 12.0}


@pytest.mark.parametrize("decay", sorted(DECAYS))
@pytest.mark.parametrize("Tn", [150, 192], ids=["T_not_whole_chunks", "T_whole_chunks"])
def test_chunked_xla_matches_recurrence_forward_and_backward(Tn, decay):
    args = _scan_inputs(Tn, DECAYS[decay])
    o, states = jaxex._gdn_chunked(*args, 64)
    assert rel(o, _recurrence(*args)) < 1e-5
    w = jax.random.normal(jax.random.PRNGKey(9), args[2].shape)
    got = jaxex._gdn_chunk_backward_impl(w, *args, states, 64)
    ref = jax.grad(lambda *a: jnp.sum(_recurrence(*a) * w), argnums=(0, 1, 2, 3, 4))(*args)
    assert max(rel(a, b) for a, b in zip(got, ref)) < 1e-4


@pytest.fixture
def interpreted(monkeypatch):
    monkeypatch.setenv("THUNDER_TPU_PALLAS_INTERPRET", "1")


@pytest.mark.parametrize("decay", sorted(DECAYS))
@pytest.mark.parametrize("Tn", [192, 1024], ids=["one_block", "two_blocks_state_carried"])
def test_pallas_gdn_chunk_fwd_matches_recurrence(interpreted, Tn, decay):
    args = _scan_inputs(Tn, DECAYS[decay], B=1)
    got = px.gdn_chunk(*args, 64)
    assert got is not None
    # the inverse and its products run in three bfloat16 passes: about 16 bits
    assert rel(got[0], _recurrence(*args)) < 5e-5


@pytest.mark.parametrize("decay", sorted(DECAYS))
@pytest.mark.parametrize("Tn", [192, 1024], ids=["one_block", "two_blocks_state_carried_back"])
def test_pallas_gdn_chunk_bwd_matches_recurrence(interpreted, Tn, decay):
    args = _scan_inputs(Tn, DECAYS[decay], B=1)
    w = jax.random.normal(jax.random.PRNGKey(9), args[2].shape)
    got = px.gdn_chunk_backward(w, *args, px.gdn_chunk(*args, 64)[1], 64)
    ref = jax.grad(lambda *a: jnp.sum(_recurrence(*a) * w), argnums=(0, 1, 2, 3, 4))(*args)
    assert got is not None and max(rel(a, b) for a, b in zip(got, ref)) < 1e-4


@pytest.mark.parametrize("decay", sorted(DECAYS))
@pytest.mark.parametrize("Tn", [192, 1024, 1536], ids=["one_block", "two_blocks", "three_blocks"])
@pytest.mark.parametrize("executor", ["pallas", "xla"])
def test_saved_states_are_the_recurrences_at_every_stride_boundary(interpreted, executor, Tn, decay):
    """What the forward pass keeps for the backward: the state a value head
    before each block of ``gdn_state_stride`` tokens, from both executors."""
    from thunder_tpu.core.prims import gdn_state_stride

    args = _scan_inputs(Tn, DECAYS[decay], B=1)
    stride = gdn_state_stride(Tn)
    assert stride == (Tn if Tn % 512 else 512)
    _, states = px.gdn_chunk(*args, 64) if executor == "pallas" else jaxex._gdn_chunked(*args, 64)
    assert states.shape == (1, 4, Tn // stride, 16, 24) and states.dtype == jnp.float32
    assert not states[:, :, 0].any(), "a sequence starts from an empty state"
    if Tn > stride:
        assert rel(states[:, :, 1:], _recurrence_and_states(*args)[1][:, :, stride::stride]) < 5e-5


@pytest.mark.parametrize("decay", sorted(DECAYS))
def test_backward_from_saved_states_over_three_blocks(interpreted, decay):
    """``gdn_chunk_backward`` takes the states of either executor's forward
    pass (the XLA scan's here) and holds the recurrence's gradients."""
    args = _scan_inputs(1536, DECAYS[decay], B=1)
    w = jax.random.normal(jax.random.PRNGKey(9), args[2].shape)
    got = px.gdn_chunk_backward(w, *args, jaxex._gdn_chunked(*args, 64)[1], 64)
    ref = jax.grad(lambda *a: jnp.sum(_recurrence(*a) * w), argnums=(0, 1, 2, 3, 4))(*args)
    assert got is not None and max(rel(a, b) for a, b in zip(got, ref)) < 1e-4


def test_backward_trace_holds_no_second_forward_and_the_states_are_saved(interpreted):
    """Through ``tt.jit`` with the default remat policy: the backward trace
    calls ``gdn_chunk_backward`` and no ``gdn_chunk``; ``states`` (an anchor's
    output: the prim is a ``MATMUL_OP``) is among what the forward pass saves;
    ``pallasex.gdn_schedule`` says what was built."""
    import thunder_tpu.torch as ltorch
    from thunder_tpu.core.transforms import flatten_to_prims

    args = _scan_inputs(1024, DECAYS["g_moderate"], B=1)
    w = jax.random.normal(jax.random.PRNGKey(9), args[2].shape)
    vg = tt.value_and_grad(lambda q, k, v, g, b, w_: ltorch.sum(ltorch.gated_delta_rule(
        ltorch.tanh(q), k, v, g, b) * w_), argnums=(0, 1, 2, 3, 4))
    loss, grads = vg(*args, w)
    fw, bw = tt.last_traces(vg)[-1], tt.last_backward_traces(vg)[-1]
    names = lambda trace: [b.sym.name for b in flatten_to_prims(trace.bound_symbols)]   # noqa: E731
    called = lambda trace, what: sum(n.endswith(what) for n in names(trace))   # noqa: E731
    assert called(fw, "gdn_chunk") == 1 and called(bw, "gdn_chunk_backward") == 1
    assert called(bw, "gdn_chunk") == 0, names(bw)
    saved = [p for p in bw.args if getattr(p, "shape", None) == (1, 4, 2, 16, 24)]
    assert len(saved) == 1 and "float32" in str(saved[0].dtype), [(p.name, p.shape) for p in bw.args]
    assert px.gdn_schedule == {"state_stride_tokens": 512, "states_saved_bytes": 4 * 4 * 2 * 16 * 24,
                               "forward_calls_in_backward": 0, "chunks_a_product": 1}
    qt = jnp.tanh(args[0])
    ref = jax.grad(lambda *a: jnp.sum(_recurrence(*a) * w), argnums=(0, 1, 2, 3, 4))(qt, *args[1:])
    assert max(rel(a, b) for a, b in zip(grads[1:], ref[1:])) < 1e-4
    assert rel(grads[0], ref[0] * (1 - qt ** 2)) < 1e-4


def test_the_train_step_claims_the_conv_kernels_a_layer_and_keeps_its_gradients(interpreted):
    """``make_train_step`` on the tiny model (conv width 2 x 2 x 16 + 4 x 16 =
    128, one lane tile): each of the three DeltaNet layers claims
    ``causal_conv1d_fwd`` in the forward pass, again where the remat pass makes
    the cone of q, k, v before the scan's backward, and ``causal_conv1d_bwd``
    once: the conv with its SiLU is one symbol of that cone where it was two,
    and the sum before the activation is nowhere saved.  The gradients, read
    from AdamW's first moment as the benchmark's check reads them, are the
    reference's."""
    import optax

    from thunder_tpu import distributed as dist

    hf = tiny(16)
    params = _noisy(arch.make_params(hf, common.seed_words(5), dtype=jnp.float32))
    cfg = llama.Config(**arch.program_config(hf))
    layers = sum(kind == "linear_attention" for kind in cfg.layer_types)
    assert layers == 3
    idx, tgt = _batch(hf)
    batch = (idx, tgt, *arch.rope_tables(hf, T))
    mesh = dist.make_mesh({"dp": 1}, devices=jax.devices()[:1])
    step = dist.make_train_step(lambda p, i, t, c, s: llama.gpt_loss(p, i, t, c, s, cfg),
                                optax.adamw(1e-5, b1=0.9), mesh, donate=False)
    opt_state = step.init_optimizer_state(params)
    before = px.stats.get("causal_conv", 0)
    _, opt_state, loss = step(params, opt_state, *batch)
    assert px.stats["causal_conv"] - before == layers * 3, "forward, made again, backward: a layer"
    assert px.conv_schedule["tile_c"] == 128 and px.conv_schedule["bytes_a_backward_call"] == 3 * 2 * T * 128 * 4
    ref_loss, parts = arch.ref_loss_and_grads(hf, params, idx, tgt, every_leaf=True)
    assert abs(float(loss) - ref_loss) < 1e-5 * abs(ref_loss)
    worst = {}
    for where, part in parts:
        got = opt_state[0].mu
        for k in where:
            got = got[k]
        for path, leaf in jax.tree_util.tree_flatten_with_path(part)[0]:
            g = got
            for k in path:
                g = g[k.key]
            worst["/".join(map(str, where)) + jax.tree_util.keystr(path)] = rel(10.0 * g, leaf)
    conv = {k: e for k, e in worst.items() if "conv_w" in k}
    assert len(conv) == layers and max(conv.values()) < 2e-4, conv
    # the limits of test_every_weights_gradient_matches_reference (A_log and dt_bias: sums that cancel)
    assert max(worst.values()) < 2e-3 and max(e for k, e in worst.items() if "['gdn']" not in k) < 2e-4, worst


@pytest.mark.parametrize("decay", ["g_near_0", "g_strongly_negative"])
def test_gated_delta_rule_prim_through_jit_and_its_backward_rule(interpreted, decay):
    """The prim traced by ``tt.jit`` and differentiated by its own backward
    rule, both passes claimed by the Pallas executor."""
    import thunder_tpu.torch as ltorch

    args = _scan_inputs(192, DECAYS[decay], B=1)
    w = jax.random.normal(jax.random.PRNGKey(9), args[2].shape)
    before = px.stats.get("gdn", 0)
    loss, grads = tt.value_and_grad(lambda q, k, v, g, b, w_: ltorch.sum(ltorch.gated_delta_rule(q, k, v, g, b) * w_),
                                    argnums=(0, 1, 2, 3, 4))(*args, w)
    assert px.stats.get("gdn", 0) > before, "the claim is counted in pallasex.stats"
    ref_loss, ref = jax.value_and_grad(lambda *a: jnp.sum(_recurrence(*a) * w), argnums=(0, 1, 2, 3, 4))(*args)
    assert abs(float(loss) - float(ref_loss)) < 1e-4 * abs(float(ref_loss)) + 1e-5
    assert max(rel(a, b) for a, b in zip(grads, ref)) < 1e-4


@pytest.mark.parametrize("head,dtype,fits", [(128, "bfloat16", True), (128, "float32", True), (256, "bfloat16", True),
                                             (256, "float32", False), (384, "bfloat16", False)])
def test_pallas_declines_a_block_whose_chunks_would_not_fit_vmem(interpreted, head, dtype, fits):
    """``gdn_chunk_bwd`` keeps a block's chunks in VMEM between its walks; the
    line is where Mosaic's own refusal sits (``tests/test_pallas_tpu_lowering.py``
    compiles the widest that pass)."""
    assert px._gdn_supported((1, 2, 1024, head), (1, 4, 1024, head), jnp.dtype(dtype), 64) is fits


def test_pallas_declines_what_it_cannot_tile_and_xla_pads(interpreted):
    args = _scan_inputs(150, 1.0)
    assert px.gdn_chunk(*args, 64) is None
    o, states = jaxex._gdn_chunk_impl(*args, 64)
    assert rel(o, _recurrence(*args)) < 1e-5 and states.shape == (2, 4, 1, 16, 24)


# --------------------------------------------------------------------------
# the expert share
# --------------------------------------------------------------------------

WAVE_TILES = 8


def _plan(idx, first, held, tile):
    """The whole sorted buffer: every wave's rows, one after another."""
    plan = jaxex.moe_plan(jnp.asarray(idx), jnp.ones(idx.shape), first, held, tile, WAVE_TILES)
    waves = plan["tile_group"].shape[0] // WAVE_TILES
    row_src = np.concatenate([np.asarray(jaxex.moe_wave_rows(plan, w, tile, WAVE_TILES)[0]) for w in range(waves)])
    return row_src, np.asarray(plan["tile_group"]), int(plan["tiles_used"])


def _first_wave(idx, first, held, tile, wave_tiles=64):
    plan = jaxex.moe_plan(jnp.asarray(idx), jnp.ones(idx.shape), first, held, tile, wave_tiles)
    row_src, _, _, tg, used = jaxex.moe_wave_rows(plan, 0, tile, wave_tiles)
    return row_src, tg, used.reshape(1)


def _gathered(x, row_src, k):
    return jnp.where((row_src >= 0)[:, None], jnp.take(x, jnp.maximum(row_src, 0) // k, axis=0), 0)


@pytest.mark.parametrize("first,held,tile", [(0, 16, 8), (4, 8, 8), (12, 4, 16), (0, 2, 128)])
def test_plan_places_every_held_assignment_once_and_drops_none(first, held, tile):
    rng = np.random.default_rng(first + held)
    N, k, E = 96, 4, 16
    idx = np.stack([rng.permutation(E)[:k] for _ in range(N)]).astype(np.int32)
    row_src, tile_group, used = _plan(idx, first, held, tile)
    flat = idx.reshape(-1)
    is_held = (flat >= first) & (flat < first + held)
    wave = WAVE_TILES * tile
    assert row_src.shape[0] % wave == 0 and row_src.shape[0] >= N * k + held * (tile - 1), "the worst case fits"
    rows = np.flatnonzero(row_src >= 0)
    assert sorted(row_src[rows]) == list(np.flatnonzero(is_held)), \
        "an assignment on a held expert has one row, no other has any"
    assert (flat[row_src[rows]] - first == tile_group[rows // tile]).all(), "a row lies in a tile of its expert"
    assert rows.max(initial=-1) < used * tile
    assert (np.diff(tile_group[:used]) >= 0).all(), "groups are contiguous and ascending"


def _expert_operands(seed=0, N=64, k=4, held=8, first=4, tile=8, C=128, I=128, dtype=jnp.float32):
    rng = np.random.default_rng(seed)
    idx = np.stack([rng.permutation(16)[:k] for _ in range(N)]).astype(np.int32)
    row_src, tg, tu = _first_wave(idx, first, held, tile)
    x = jnp.asarray(rng.standard_normal((N, C)), dtype)
    w = jnp.asarray(rng.standard_normal((held, C, I)), dtype)
    return _gathered(x, row_src, k), w, tg, tu


@pytest.mark.parametrize("which", ["rows_times_group", "rows_times_group_transposed", "group_x_t_dy"])
def test_pallas_grouped_products_match_ragged_dot(interpreted, monkeypatch, which):
    xb, w, tg, tu = _expert_operands()
    dy = jnp.asarray(np.random.default_rng(1).standard_normal((xb.shape[0], w.shape[2])), jnp.float32)
    if which == "group_x_t_dy":
        got = px.grouped_mm_dw(xb, dy, tg, tu, w.shape[0])
    else:
        t = which.endswith("transposed")
        got = px.grouped_mm(xb, jnp.swapaxes(w, 1, 2) if t else w, tg, tu, t)
    monkeypatch.setattr(jaxex, "_grouped_mm_fast_path", None)
    monkeypatch.setattr(jaxex, "_grouped_mm_dw_fast_path", None)
    ref = (jaxex._grouped_mm_dw_impl(xb, dy, tg, tu, w.shape[0]) if which == "group_x_t_dy"
           else jaxex._grouped_mm_impl(xb, w, tg, tu))
    assert got is not None and rel(got, ref) < 1e-5


@pytest.mark.parametrize("pallas", [False, True], ids=["xla", "pallas_interpreted"])
def test_the_grouped_product_and_its_two_backward_products(monkeypatch, pallas):
    """``jaxex._gmm``, the one differentiable grouped product (the expert
    share is made of it): forward and the two products of its backward rule
    against plain per-group products."""
    if pallas:
        monkeypatch.setenv("THUNDER_TPU_PALLAS_INTERPRET", "1")
    xb, w, tg, tu = _expert_operands()
    tile = xb.shape[0] // tg.shape[0]

    def plain(x_, w_):
        rows = jnp.repeat(tg, tile)
        out = jnp.einsum("rk,rkn->rn", x_, w_[rows])
        return jnp.where((jnp.arange(x_.shape[0]) < tu[0] * tile)[:, None], out, 0.0)

    c = jnp.asarray(np.random.default_rng(2).standard_normal((xb.shape[0], w.shape[2])), jnp.float32)
    loss, (dx, dw) = jax.value_and_grad(lambda x_, w_: jnp.sum(jaxex._gmm(x_, w_, tg, tu) * c), argnums=(0, 1))(xb, w)
    ref_loss, (rx, rw) = jax.value_and_grad(lambda x_, w_: jnp.sum(plain(x_, w_) * c), argnums=(0, 1))(xb, w)
    assert abs(float(loss) - float(ref_loss)) < 1e-4 * abs(float(ref_loss)) + 1e-4
    assert rel(dx, rx) < 1e-5 and rel(dw, rw) < 1e-5


def test_a_group_without_rows_gets_a_zero_gradient(interpreted):
    idx = jnp.tile(jnp.asarray([[4, 5, 0, 1]], jnp.int32), (32, 1))     # of experts 4..11 only 4 and 5 are picked
    row_src, tg, tu = _first_wave(idx, 4, 8, 8)
    xb = _gathered(jnp.ones((32, 128)), row_src, 4)
    dw = px.grouped_mm_dw(xb, jnp.ones((xb.shape[0], 128)), tg, tu, 8)
    assert float(jnp.abs(dw[2:]).max()) == 0.0 and float(dw[0].min()) == 32.0


# ``moe_grouped_mm``'s two layouts (``pallasex._gmm_blocks``): the whole matrix as the block, and
# column blocks inside a tile where VMEM does not hold the matrix twice.  Forced here at a small
# width by the VMEM the kernel may ask for.
GMM_K, GMM_N, GMM_TM, GMM_GROUPS = 128, 384, 8, 5
GMM_TILES = (0, 1, 2, 5)                 # tiles of the first four groups; four more tiles hold nothing
V5E_VMEM_CAP = 96 << 20                  # three quarters of a v5e core's 128 MiB


def _gmm_layout(monkeypatch, layout: str, itemsize: int = 4):
    """Makes the rule choose ``layout`` at ``(GMM_K, GMM_N)``."""
    monkeypatch.setattr(px, "_GMM_VMEM_MARGIN", 0)
    width = GMM_N if layout == "whole_matrix" else 128
    monkeypatch.setattr(px, "_gmm_vmem_cap", lambda: px._gmm_vmem(GMM_TM, GMM_K, width, itemsize))


def _gmm_tiles():
    """The plan names its last group for the tiles past the used ones; here that group drew no row."""
    tg = np.repeat(np.arange(len(GMM_TILES)), GMM_TILES).tolist()
    return jnp.asarray(tg + [GMM_GROUPS - 1] * 4, jnp.int32), jnp.asarray([len(tg)], jnp.int32)


@pytest.mark.parametrize("transpose_w", [False, True], ids=["w", "w_transposed"])
@pytest.mark.parametrize("layout", ["whole_matrix", "column_blocks"])
def test_grouped_product_matches_ragged_dot_in_every_layout(interpreted, monkeypatch, layout, transpose_w):
    """Groups of 0, 1, 2 and 5 tiles, four tiles past ``tiles_used``: both
    layouts give ``lax.ragged_dot``'s rows and zeros after them."""
    _gmm_layout(monkeypatch, layout)
    tg, used = _gmm_tiles()
    nt = tg.shape[0]
    assert px._gmm_blocks(GMM_K, GMM_N, 4, GMM_TM, nt)["col_blocks"] == {"whole_matrix": 1, "column_blocks": 3}[layout]
    rng = np.random.default_rng(3)
    x = jnp.asarray(rng.standard_normal((nt * GMM_TM, GMM_K)), jnp.float32)
    w = jnp.asarray(rng.standard_normal((GMM_GROUPS, GMM_K, GMM_N)), jnp.float32)
    got = px._moe_grouped_mm.__wrapped__(x, jnp.swapaxes(w, 1, 2) if transpose_w else w, tg, used,
                                         transpose_w=transpose_w)
    monkeypatch.setattr(jaxex, "_grouped_mm_fast_path", None)
    ref = jaxex._grouped_mm_impl(x, w, tg, used)
    assert rel(got, ref) < 1e-5
    assert float(jnp.abs(got[int(used[0]) * GMM_TM:]).max()) == 0.0 and float(jnp.abs(got[:GMM_TM]).min()) > 0.0


def _weight_fetches(plan, tg, used, transpose_w):
    """Walks the grid in order through the weight block's index map: how often
    the block index changes (each change is a copy; the first block is one)."""
    grid, (_, w_spec), _ = px._gmm_specs(GMM_TM, GMM_K, tg.shape[0], plan, transpose_w)
    seen, changes = None, 0
    for t in range(grid[0]):
        for j in range(grid[1]):
            block = tuple(int(i) for i in w_spec.index_map(t, j, tg, used))
            changes += block != seen
            seen = block
    return grid, changes


@pytest.mark.parametrize("transpose_w", [False, True], ids=["w", "w_transposed"])
@pytest.mark.parametrize("layout", ["whole_matrix", "column_blocks"])
def test_a_groups_weight_block_is_fetched_once_however_many_tiles_it_has(monkeypatch, layout, transpose_w):
    """Where the schedule says ``weight_fetches_a_group == 1`` the walk copies
    one block a used group, never one a tile; where the matrix went through
    in column blocks it says every tile may fetch, and every used tile does.
    The tiles past the used ones copy nothing in either, though the plan
    names a group for them that drew no row."""
    _gmm_layout(monkeypatch, layout)
    tg, used = (np.asarray(a) for a in _gmm_tiles())
    nt = tg.shape[0]
    plan = px._gmm_blocks(GMM_K, GMM_N, 4, GMM_TM, nt)
    grid, changes = _weight_fetches(plan, tg, used, transpose_w)
    used_groups, used_tiles, nj = sum(n > 0 for n in GMM_TILES), sum(GMM_TILES), plan["col_blocks"]
    assert grid == (nt, nj)
    if layout == "whole_matrix":
        assert plan["weight_fetches_a_group"] == 1 and changes == used_groups < used_tiles
    else:
        assert plan["weight_fetches_a_group"] == nt and changes == used_tiles * nj


def test_the_vmem_a_grouped_product_may_ask_for_comes_from_the_device(monkeypatch):
    """Three quarters of what the device says a core has; where jax knows no
    such device (the interpreter here), what a kernel gets without asking."""
    import types

    assert px._gmm_vmem_cap() == px._GMM_VMEM_DEFAULT
    monkeypatch.setattr(px.pltpu, "get_tpu_info", lambda: types.SimpleNamespace(vmem_capacity_bytes=128 << 20))
    assert px._gmm_vmem_cap() == V5E_VMEM_CAP


# K, N, rows a tile, tiles of a wave: the three configurations' products as their cells run them
GMM_CELLS = {
    "lfm2_decode_fc": (2048, 1792, 64, 56), "lfm2_decode_proj": (1792, 2048, 64, 56),
    "lfm2_prefill_fc": (2048, 1792, 128, 104), "lfm2_prefill_proj": (1792, 2048, 128, 104),
    "axk1_decode_fc": (7168, 2048, 16, 16), "axk1_prefill_fc": (7168, 2048, 128, 48),
    "axk1_prefill_proj": (2048, 7168, 128, 48),
    "hybrid_fc": (2048, 512, 128, 128), "hybrid_proj": (512, 2048, 128, 128),
}


@pytest.mark.parametrize("device", ["v5e", "unknown"])
@pytest.mark.parametrize("cell", sorted(GMM_CELLS))
def test_the_schedule_at_the_three_configurations_widths(monkeypatch, cell, device):
    """On a v5e the whole matrix is the block at every width a cell runs,
    fetched once a group, inside the VMEM the call states.  The hybrid
    trainer's 2 MiB block asks for nothing on any device: one whole block on
    a ``(tiles, 1)`` grid inside the default limit, as before.  On a device
    whose VMEM jax does not know, the serving widths go through in column
    blocks inside the default limit, and the schedule says they are fetched
    a tile."""
    K, N, TM, nt = GMM_CELLS[cell]
    if device == "v5e":
        monkeypatch.setattr(px, "_gmm_vmem_cap", lambda: V5E_VMEM_CAP)
    plan = px._gmm_blocks(K, N, 2, TM, nt)
    grid, (x_spec, w_spec), o_spec = px._gmm_specs(TM, K, nt, plan, False)
    if device == "v5e" or cell.startswith("hybrid"):
        assert plan["col_blocks"] == 1 and plan["weight_fetches_a_group"] == 1 and plan["weights_ahead"] == (TM == 128)
        assert plan["weight_block_bytes"] == K * N * 2 and grid == ((nt,) if TM == 128 else (nt, 1))
        # a prompt's and the trainer's weights stay whole in HBM for the kernel's own copies: no block of them
        assert (x_spec.block_shape, w_spec.block_shape, o_spec.block_shape) == ((TM, K), None if TM == 128 else (1, K, N), (TM, N))
    else:
        assert plan["col_blocks"] > 1 and plan["weight_fetches_a_group"] == nt and plan["vmem_limit_bytes"] == 0
        assert grid == (nt, plan["col_blocks"]) and w_spec.block_shape == (1, K, N // plan["col_blocks"])
    if cell.startswith("hybrid"):
        assert plan["vmem_limit_bytes"] == 0 and K * N * 2 == 2 << 20
    elif device == "v5e":
        assert 2 * K * N * 2 < plan["vmem_limit_bytes"] <= V5E_VMEM_CAP


def test_gmm_schedule_holds_the_last_grouped_product_built(interpreted):
    xb, w, tg, tu = _expert_operands()
    assert px.grouped_mm(xb, w, tg, tu) is not None
    assert px.gmm_schedule == px._gmm_blocks(128, 128, 4, 8, tg.shape[0])
    assert set(px.gmm_schedule) >= {"col_blocks", "weight_block_bytes", "vmem_limit_bytes", "weight_fetches_a_group"}
    assert all(isinstance(v, int) for v in px.gmm_schedule.values())


# ``moe_grouped_mm`` where it copies its own weights a group ahead (``pallasex._gmm_ahead_kernel``; a row tile of
# ``MOE_ROW_TILE``) against the ``BlockSpec`` form a decode step keeps.  Tiles a group, then ``tiles_used`` and the
# tiles the buffer holds past the groups' own (the plan names its last group for them).
AHEAD_TM, AHEAD_K, AHEAD_N = 128, 128, 256
AHEAD_ROUTINGS = {
    "an_empty_group_between_two_with_rows": ((2, 0, 3), 5, 3),
    "the_first_and_the_last_group_empty": ((0, 2, 1, 0), 3, 2),
    "one_tile_groups_beside_eight_tile_groups": ((1, 8, 1, 8), 18, 0),       # tiles_used == nt
    "no_tile_used": ((3, 3), 0, 2),
    "one_tile_used": ((3, 3), 1, 2),
    "the_used_tiles_end_inside_a_run": ((2, 3, 2), 4, 1),
    "the_used_tiles_end_where_a_run_would_open": ((2, 3, 2), 5, 1),
    "two_runs_the_buffers_swap_roles_once": ((2, 2), 4, 4),
    "an_odd_number_of_runs": ((1, 2, 1, 2, 1), 7, 1),
    "every_group_one_tile": ((1, 1, 1, 1, 1, 1), 6, 2),
}


def _ahead_tiles(case):
    tiles, used, past = AHEAD_ROUTINGS[case]
    tg = np.repeat(np.arange(len(tiles)), tiles).tolist() + [len(tiles) - 1] * past
    return jnp.asarray(tg, jnp.int32), jnp.asarray([used], jnp.int32), len(tiles)


def _blockspec_form(monkeypatch):
    """``moe_grouped_mm`` as a decode step builds it, at any row tile."""
    blocks = px._gmm_blocks
    monkeypatch.setattr(px, "_gmm_blocks", lambda *a: {**blocks(*a), "weights_ahead": 0})


@pytest.mark.parametrize("transpose_w", [False, True], ids=["w", "w_transposed"])
@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("case", list(AHEAD_ROUTINGS))
def test_the_product_that_copies_its_own_weights_has_the_blockspec_forms_bits(interpreted, monkeypatch, case, dtype,
                                                                              transpose_w):
    tg, used, groups = _ahead_tiles(case)
    nt = tg.shape[0]
    rng = np.random.default_rng(nt)
    K, N = (AHEAD_N, AHEAD_K) if transpose_w else (AHEAD_K, AHEAD_N)
    x = jnp.asarray(rng.standard_normal((nt * AHEAD_TM, K)), dtype)
    w = jnp.asarray(rng.standard_normal((groups, AHEAD_K, AHEAD_N)), dtype)       # (G, K, N), or (G, N, K) transposed
    assert px._gmm_blocks(K, N, x.dtype.itemsize, AHEAD_TM, nt)["weights_ahead"] == 1
    got = px._moe_grouped_mm.__wrapped__(x, w, tg, used, transpose_w=transpose_w)
    _blockspec_form(monkeypatch)
    want = px._moe_grouped_mm.__wrapped__(x, w, tg, used, transpose_w=transpose_w)
    assert got.dtype == want.dtype and got.shape == want.shape == (nt * AHEAD_TM, N)
    assert bool(jnp.all(got == want)), "bit for bit"
    n = int(used[0]) * AHEAD_TM
    assert float(jnp.abs(got[n:]).max(initial=0.0)) == 0.0 and (n == 0 or float(jnp.abs(got[:n]).min()) > 0.0)


@pytest.mark.parametrize("case", list(AHEAD_ROUTINGS))
def test_a_runs_weights_are_asked_for_at_the_first_tile_of_the_run_before_and_nothing_is_left_in_flight(case):
    """The kernel's walk over ``pallasex._gmm_runs``' three vectors, in Python:
    every used tile multiplies a buffer that holds its own group's weights and
    was waited for; a copy lands only in the buffer whose run has passed; one
    copy a run of used tiles, the first alone not ahead; none past ``tiles_used``."""
    tg, used, _ = _ahead_tiles(case)
    opens, parity, nxt = (np.asarray(v) for v in px._gmm_runs(tg))
    tg, used = np.asarray(tg), int(used[0])
    holds, flying, copies = [None, None], [None, None], 0
    for t in range(used):
        k = int(parity[t])
        if opens[t]:
            if t == 0:
                flying[0], copies = int(tg[0]), 1
            if nxt[t] < used:       # started before the wait for the run's own
                assert flying[1 - k] is None, "the other buffer's run has passed and nothing is on its way there"
                flying[1 - k], copies = int(tg[nxt[t]]), copies + 1
            assert flying[k] is not None, "a run waits for a copy that was started"
            holds[k], flying[k] = flying[k], None
        assert holds[k] == tg[t], "a tile multiplies its own group's weights"
    assert flying == [None, None], "nothing in flight when the call ends"
    runs = int(opens[:used].sum())
    assert copies == runs and (used == 0 or opens[0] == 1 and parity[0] == 0)
    assert (np.diff(parity[:used][opens[:used] == 1]) != 0).all(), "consecutive runs take the two buffers in turn"


@pytest.mark.parametrize("tile,layout,ahead", [(128, "whole_matrix", 1), (64, "whole_matrix", 0), (16, "whole_matrix", 0),
                                               (128, "column_blocks", 0)])
def test_only_a_prompts_row_tile_over_a_whole_matrix_copies_its_own_weights(interpreted, monkeypatch, tile, layout, ahead):
    """A decode step's tile (``generate.moe_row_tile`` gives 128 only from 64
    rows an expert up) builds the ``BlockSpec`` form, and so does a matrix that
    goes through in column blocks; ``gmm_schedule`` and the counter say which."""
    if layout == "column_blocks":
        monkeypatch.setattr(px, "_GMM_VMEM_MARGIN", 0)
        monkeypatch.setattr(px, "_gmm_vmem_cap", lambda: px._gmm_vmem(tile, GMM_K, 128, 4))
    tg, used = _gmm_tiles()
    x = jnp.ones((tg.shape[0] * tile, GMM_K), jnp.float32)
    w = jnp.ones((GMM_GROUPS, GMM_K, GMM_N), jnp.float32)
    before = (px.stats.get("grouped_mm", 0), px.stats.get("grouped_mm_ahead", 0))
    out = px.grouped_mm(x, w, tg, used)
    assert out is not None and float(out[0, 0]) == GMM_K
    assert px.gmm_schedule["weights_ahead"] == ahead and (px.gmm_schedule["col_blocks"] > 1) == (layout == "column_blocks")
    assert (px.stats["grouped_mm"], px.stats.get("grouped_mm_ahead", 0)) == (before[0] + 1, before[1] + ahead)


def test_a_decode_steps_row_tile_is_under_the_prompts_in_every_cell():
    """Rows an even routing sends a held expert in a decode step of the six serve cells with an expert layer
    (LFM2 256 x 4 / 32, A.X-K1 64 x 8 / 192, Xing4.0 32 x 4 / 64, Nemotron 128 x 22 / 512, Trinity 20 x 8 / 128,
    SmallThinker 64 x 6 / 64) and in their shortest prompt buckets: the shape that tells the two forms apart."""
    from thunder_tpu.core.prims import MOE_ROW_TILE
    from thunder_tpu.models.generate import moe_row_tile

    assert [moe_row_tile(r) for r in (32, 2.67, 2, 5.5, 1.25, 6)] == [64, 16, 16, 16, 16, 16] and MOE_ROW_TILE == 128
    assert all(moe_row_tile(r) == MOE_ROW_TILE for r in (64, 512 * 4 / 32, 2560 * 8 / 192, 2048 * 22 / 512, 2560 * 6 / 64))


def _expert_layer(hf, params_mlp, x):
    cfg = llama.Config(**arch.program_config(hf))
    return tt.jit(lambda mp, x_: llama.sparse_moe_mlp(mp, x_, cfg))(params_mlp, x)


def _without_shared(mp):
    zero = jax.tree_util.tree_map(jnp.zeros_like, mp["shared"])
    return {**mp, "shared": zero}


def test_the_eight_shares_and_the_shared_expert_once_add_up_to_the_uncut_layer():
    whole = tiny(16)
    mp = arch.make_params(whole, common.seed_words(3), dtype=jnp.float32)["blocks"][0]["mlp"]
    x = jax.random.normal(jax.random.PRNGKey(0), (1, T, whole["hidden_size"]))
    uncut = _expert_layer(whole, mp, x)
    C, I = whole["hidden_size"], whole["moe_intermediate_size"]
    total = None
    for share in range(8):
        first = 2 * share
        part = {**_without_shared(mp), "fc_1": mp["fc_1"][first * C:(first + 2) * C],
                "fc_2": mp["fc_2"][first * C:(first + 2) * C], "proj": mp["proj"][first * I:(first + 2) * I]}
        y = _expert_layer(tiny(2, first), part, x)
        total = y if total is None else total + y
    shared_once = _expert_layer(tiny(2, 0), {**mp, "fc_1": jnp.zeros_like(mp["fc_1"][:2 * C]),
                                             "fc_2": mp["fc_2"][:2 * C], "proj": mp["proj"][:2 * I]}, x)
    assert rel(total + shared_once, uncut) < 1e-5
    s = arch.sizes(whole)
    assert rel(uncut[0], arch._experts(x[0], None, mp, s, False)) < 1e-5, "and the reference agrees on the uncut layer"


@pytest.mark.parametrize("pallas", [False, True], ids=["xla", "pallas_interpreted"])
def test_no_token_is_dropped_when_one_held_expert_takes_over_half_of_the_rows(monkeypatch, pallas):
    if pallas:
        monkeypatch.setenv("THUNDER_TPU_PALLAS_INTERPRET", "1")
    hf = tiny(4, 2, hidden_size=128, moe_intermediate_size=128, shared_expert_intermediate_size=128)
    s = arch.sizes(hf)
    mp = arch.make_params(hf, common.seed_words(11), dtype=jnp.float32)["blocks"][0]["mlp"]
    d = jnp.ones((s["C"],)) / s["C"] ** 0.5
    x = jax.random.normal(jax.random.PRNGKey(2), (1, T, s["C"])) + 4.0 * d
    mp["gate"] = mp["gate"].at[3].set(2.0 * d)       # expert 3, held here, wins every token
    probs = jax.nn.softmax(x[0] @ mp["gate"].T, -1)
    _, top = jax.lax.top_k(probs, s["k"])
    on_held = (top >= 2) & (top < 6)
    assert int((top == 3).sum()) == T and int((top == 3).sum()) > 0.5 * int(on_held.sum())
    before = px.stats.get("grouped_mm", 0)
    got = _expert_layer(hf, mp, x)
    assert (px.stats.get("grouped_mm", 0) > before) == pallas
    assert rel(got[0], arch._experts(x[0], None, mp, s, False)) < 1e-5


# the share and the dense reference each one compiled call a side: eagerly the share's waves and the reference's
# experts dispatch an operation at a time (PR 55: 10 s a case so, 2.4 s compiled, the results 1e-8 apart)
_SHARE = jax.jit(jaxex._moe_share, static_argnums=(6, 7, 8))
_SHARE_BACK = jax.jit(jaxex._moe_expert_share_backward_impl, static_argnums=(7, 8, 9))


def _dense_back(dense):
    return jax.jit(lambda dy, *at: jax.vjp(dense, *at)[1](dy))


@pytest.mark.parametrize("skew", [False, True], ids=["spread", "every_token_on_one_held_expert"])
def test_rows_past_the_first_wave_are_computed_not_dropped(skew):
    """Told that its 8 experts are 8 of 256, the share sizes its waves for a
    thirty-second of the rows (8 tiles); the routing below, over 16 experts,
    needs 17 or 18: the later waves run only because the routing filled the
    earlier ones, forward and backward."""
    total = 256
    rng = np.random.default_rng(0)
    N, k, first, held, tile, C, I = 50, 4, 4, 8, 8, 6, 5
    idx = np.stack([rng.permutation(16)[:k] for _ in range(N)]).astype(np.int32)
    if skew:
        idx[:, 0] = 5
    tw, x = jnp.asarray(rng.random((N, k)), jnp.float32), jnp.asarray(rng.standard_normal((N, C)), jnp.float32)
    w1, w3 = (jnp.asarray(rng.standard_normal((held, C, I)), jnp.float32) for _ in range(2))
    w2 = jnp.asarray(rng.standard_normal((held, I, C)), jnp.float32)
    wave_tiles = jaxex.moe_wave_tiles(N * k, held, total, tile)
    assert wave_tiles == 8 and int(jaxex.moe_plan(jnp.asarray(idx), tw, first, held, tile, wave_tiles)["tiles_used"]) > 16

    def dense(x, tw, w1, w3, w2):
        return sum(jnp.sum(tw * (idx == e + first), axis=1)[:, None]
                   * ((jax.nn.silu(x @ w1[e]) * (x @ w3[e])) @ w2[e]) for e in range(held))

    assert rel(_SHARE(x, jnp.asarray(idx), tw, w1, w3, w2, first, total, tile), jax.jit(dense)(x, tw, w1, w3, w2)) < 1e-5
    dy = jnp.asarray(rng.standard_normal((N, C)), jnp.float32)
    got = _SHARE_BACK(dy, x, jnp.asarray(idx), tw, w1, w3, w2, first, total, tile)
    ref = _dense_back(dense)(dy, x, tw, w1, w3, w2)
    assert max(rel(a, b) for a, b in zip(got, ref)) < 1e-5


# the rows' way into the sorted buffer and back (PR 43): ``row_src`` and its inverse ``pos``
ROUTINGS = {
    "decode_256x4_of_32_at_tile_64": dict(N=256, k=4, first=0, held=32, total=32, tile=64),
    "held_12_of_192": dict(N=64, k=8, first=24, held=12, total=192, tile=16),
    "skew_fills_a_second_and_third_wave": dict(N=50, k=4, first=4, held=8, total=256, tile=8, over=16, onto=5),
    "an_expert_draws_no_row": dict(N=96, k=4, first=0, held=16, total=16, tile=8, never=3),
    "every_row_on_one_expert": dict(N=40, k=2, first=2, held=4, total=8, tile=8, onto=3, rest_outside=True),
    "ten_assignments_a_buffer_row": dict(N=512, k=8, first=24, held=12, total=192, tile=16),
}


def _routed(N, k, first, held, total, tile, over=None, onto=None, never=None, rest_outside=False, C=16, I=8, seed=0):
    """``top_idx``, weights, rows and the held experts' float32 weights of a case."""
    rng = np.random.default_rng(seed)
    pool = np.array([e for e in range(over or total) if e != never])
    idx = np.stack([rng.permutation(pool)[:k] for _ in range(N)]).astype(np.int32)
    if rest_outside:                      # every other slot on an expert that is not held
        outside = np.array([e for e in range(total) if not first <= e < first + held])
        idx = np.stack([rng.permutation(outside)[:k] for _ in range(N)]).astype(np.int32)
    if onto is not None:
        idx[:, 0] = onto
    tw = jnp.asarray(rng.random((N, k)), jnp.float32)
    x = jnp.asarray(rng.standard_normal((N, C)), jnp.float32)
    w1, w3 = (jnp.asarray(rng.standard_normal((held, C, I)), jnp.float32) for _ in range(2))
    w2 = jnp.asarray(rng.standard_normal((held, I, C)), jnp.float32)
    return jnp.asarray(idx), tw, x, w1, w3, w2


def _dense_share(idx, first, held):
    def dense(x, tw, w1, w3, w2):
        return sum(jnp.sum(tw * (idx == e + first), axis=1)[:, None]
                   * ((jax.nn.silu(x @ w1[e]) * (x @ w3[e])) @ w2[e]) for e in range(held))
    return dense


@pytest.mark.parametrize("case", list(ROUTINGS))
def test_pos_and_row_src_are_inverse_wherever_a_row_is_routed(case):
    c = ROUTINGS[case]
    idx, tw = _routed(**c)[:2]
    N, k, first, held, tile = (c[n] for n in ("N", "k", "first", "held", "tile"))
    wave_tiles = jaxex.moe_wave_tiles(N * k, held, c["total"], tile)
    plan = jaxex.moe_plan(idx, tw, first, held, tile, wave_tiles)
    row_src, row_w = np.asarray(plan["row_src"]), np.asarray(plan["row_w"])
    flat = np.asarray(idx).reshape(-1)
    is_held = (flat >= first) & (flat < first + held)
    rows = np.flatnonzero(row_src >= 0)
    assert sorted(row_src[rows]) == list(np.flatnonzero(is_held)), "an assignment on a held expert has one row, no other has any"
    assert (row_w[rows] == np.asarray(tw).reshape(-1)[row_src[rows]]).all() and (np.delete(row_w, rows) == 0).all(), \
        "the sort carried each row's weight along"
    waves = plan["tile_group"].shape[0] // wave_tiles
    assert (case == "skew_fills_a_second_and_third_wave") == (int(plan["tiles_used"]) > 2 * wave_tiles)
    if case == "ten_assignments_a_buffer_row":
        assert plan["pos"] is None, "many assignments a buffer row: the tokens are added to, no inverse is made"
        return
    pos = np.asarray(plan["pos"]).reshape(-1)
    assert (pos[row_src[rows]] == rows).all() and (pos[~is_held] == -1).all() and (row_src[pos[is_held]] == np.flatnonzero(is_held)).all()
    # a wave's own view: positions counted from its first row, -1 for what lies in another wave
    seen = np.zeros_like(pos)
    for w in range(waves):
        rs, pw, *_ = jaxex.moe_wave_rows(plan, w, tile, wave_tiles)
        rs, pw = np.asarray(rs), np.asarray(pw).reshape(-1)
        here = np.flatnonzero(pw >= 0)
        assert (rs[pw[here]] == here).all() and len(here) == (rs >= 0).sum()
        seen[here] += 1
    assert (seen == is_held).all(), "every held assignment lies in exactly one wave"


@pytest.mark.parametrize("case", list(ROUTINGS))
def test_the_share_and_its_backward_match_the_dense_reference(case):
    c = ROUTINGS[case]
    idx, tw, x, w1, w3, w2 = _routed(**c)
    first, held, total, tile = (c[n] for n in ("first", "held", "total", "tile"))
    dense = _dense_share(idx, first, held)
    assert rel(_SHARE(x, idx, tw, w1, w3, w2, first, total, tile), jax.jit(dense)(x, tw, w1, w3, w2)) < 1e-5
    dy = jnp.asarray(np.random.default_rng(1).standard_normal(x.shape), jnp.float32)
    got = _SHARE_BACK(dy, x, idx, tw, w1, w3, w2, first, total, tile)
    ref = _dense_back(dense)(dy, x, tw, w1, w3, w2)
    assert max(rel(a, b) for a, b in zip(got, ref) if float(jnp.abs(b).max()) > 0) < 1e-5
    assert all(float(jnp.abs(a).max()) == 0 for a, b in zip(got, ref) if float(jnp.abs(b).max()) == 0)


@pytest.mark.parametrize("case", list(ROUTINGS))
def test_a_tokens_result_is_the_same_bits_alone_and_among_the_others(case):
    """The solo contract at the share: a token's rows are added in buffer
    order, which its own routing fixes, so who else is in the batch changes no
    bit of its result, in either form of the way back.  (A routing skewed
    enough to spill into later waves adds a token's rows a wave at a time, and
    which wave holds a row depends on the batch: equal to a rounding there, as
    it was before the rows were gathered.)"""
    c = ROUTINGS[case]
    idx, tw, x, w1, w3, w2 = _routed(**c)
    first, total, tile = c["first"], c["total"], c["tile"]
    among = np.asarray(jaxex._moe_share(x, idx, tw, w1, w3, w2, first, total, tile))
    held_slots = np.asarray((idx >= first) & (idx < first + c["held"])).sum(axis=1)
    for n in (0, int(np.argmax(held_slots)), c["N"] - 1):
        alone = np.asarray(jaxex._moe_share(x[n:n + 1], idx[n:n + 1], tw[n:n + 1], w1, w3, w2, first, total, tile))
        if case == "skew_fills_a_second_and_third_wave":
            assert rel(alone[0], among[n]) < 1e-6
        else:
            assert (alone[0] == among[n]).all(), (n, held_slots[n])


CELL_SHARES = {      # tokens, k, held, all, tile, C, I; and whether the tokens gather their rows back
    "lfm2_decode": (256, 4, 32, 32, 64, 2048, 1792, True), "lfm2_prefill": (2048, 4, 32, 32, 128, 2048, 1792, True),
    "axk1_decode": (64, 8, 12, 192, 16, 7168, 2048, True), "axk1_prefill": (8192, 8, 12, 192, 128, 7168, 2048, False),
    "hybrid_train": (16384, 10, 32, 512, 128, 2048, 512, False),
    "xing4_prefill": (8192, 4, 64, 64, 128, 3584, 1024, True), "xing4_prefill_5k": (5120, 4, 64, 64, 128, 3584, 1024, True),
    "xing4_decode": (32, 4, 64, 64, 16, 3584, 1024, True), "trinity_prefill": (9984, 8, 16, 128, 128, 2048, 1024, False),
    "lfm2_prefill_3k": (3072, 4, 32, 32, 128, 2048, 1792, True),
}
# where ``moe_combine`` is asked (``jaxex._kernel_takes``; PERF.md, PR 58): wherever XLA would add to the tokens, and
# where it would gather from Xing4.0's prompts up; LFM2's prompts and every decode step keep XLA's gather
WALKED = {"axk1_prefill", "hybrid_train", "xing4_prefill", "xing4_prefill_5k", "trinity_prefill"}


def _share_shapes(N, k, held, C, I, dtype=jnp.bfloat16):
    sd = jax.ShapeDtypeStruct
    return (sd((N, C), dtype), sd((N, k), jnp.int32), sd((N, k), jnp.float32), sd((held, C, I), dtype),
            sd((held, C, I), dtype), sd((held, I, C), dtype))


def _scatters(jaxpr):
    """Every scatter of a jaxpr and of the jaxprs inside it: ``(primitive, dtype of the updates, their rank)``."""
    out = []
    for eqn in jaxpr.eqns:
        if eqn.primitive.name.startswith("scatter"):
            out.append((eqn.primitive.name, eqn.invars[2].aval.dtype, eqn.invars[2].aval.ndim))
        for v in eqn.params.values():
            for sub in (v if isinstance(v, (tuple, list)) else (v,)):
                inner = getattr(sub, "jaxpr", sub)
                if hasattr(inner, "eqns"):
                    out += _scatters(inner)
    return out


@pytest.fixture
def xla_combine(monkeypatch):
    """The tokens' rows come back by XLA's two forms: ``moe_combine`` is not asked."""
    monkeypatch.setattr(jaxex, "_tokens_of_rows_fast_path", None)


@pytest.fixture
def walked(interpreted, monkeypatch):
    """``moe_combine`` takes every call it can, a decode step's few tokens too,
    at the VMEM a v5e gives (the interpreter knows none: a cell's widths would
    not fit the default)."""
    monkeypatch.setattr(jaxex, "_TOKENS_A_KERNEL_CALL", 0)
    monkeypatch.setattr(jaxex, "_ASSIGNMENTS_GATHERED_A_KERNEL_CALL", 0)
    monkeypatch.setattr(px, "_gmm_vmem_cap", lambda: 96 << 20)


@pytest.mark.parametrize("cell", list(CELL_SHARES))
def test_no_floating_point_scatter_where_the_tokens_gather_their_rows(cell, xla_combine):
    """At the shapes the cells run, in XLA's forms: where a share holds a fair part of the
    experts, or the step is a decode step, neither the share nor its backward
    holds a scatter with floating-point updates; where ten assignments stand
    for a buffer row (a long prompt of a share of 12 of 192, the trainer's 32
    of 512) the rows are still added to their tokens, and no inverse is made."""
    N, k, held, total, tile, C, I, gathers = CELL_SHARES[cell]
    x, idx, tw, w1, w3, w2 = _share_shapes(N, k, held, C, I)
    wave_tiles = jaxex.moe_wave_tiles(N * k, held, total, tile)
    assert (jax.eval_shape(lambda i, w: jaxex.moe_plan(i, w, 0, held, tile, wave_tiles), idx, tw)["pos"] is not None) == gathers
    fwd = jax.make_jaxpr(lambda *a: jaxex._moe_share(*a, 0, total, tile))(x, idx, tw, w1, w3, w2)
    bwd = jax.make_jaxpr(lambda *a: jaxex._moe_expert_share_backward_impl(*a, 0, total, tile))(x, x, idx, tw, w1, w3, w2)
    floating = [s for s in _scatters(fwd.jaxpr) + _scatters(bwd.jaxpr) if jnp.issubdtype(s[1], jnp.floating)]
    assert (not floating) == gathers, floating


def _pallas_calls(jaxpr) -> list:
    """The ``name`` of every ``pallas_call`` of a jaxpr and of the jaxprs inside it."""
    out = []
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "pallas_call":
            out.append(eqn.params["name"] if "name" in eqn.params else eqn.params["name_and_src_info"].name)
        for v in eqn.params.values():
            for sub in (v if isinstance(v, (tuple, list)) else (v,)):
                inner = getattr(sub, "jaxpr", sub)
                if hasattr(inner, "eqns"):
                    out += _pallas_calls(inner)
    return out


@pytest.mark.parametrize("cell", list(CELL_SHARES))
def test_no_floating_point_scatter_anywhere_where_the_kernel_walks_the_tokens(cell, interpreted, monkeypatch, request):
    """The twin on the kernel's path, at the cells' own shapes (traced, not run):
    the share and its backward hold ``moe_combine`` wherever XLA would add to
    the tokens (a prompt of A.X-K1 or Trinity-Mini, the trainer's step) and at
    Xing4.0's prompts, and there no scatter of rows (the walk needs no ``pos``:
    the plan makes none where it made none, so the routing weights' gradient is
    still added to, a number a row); LFM2's prompts and every decode step keep
    XLA's gather and hold no ``moe_combine``."""
    monkeypatch.setattr(px, "_gmm_vmem_cap", lambda: 96 << 20)
    # the grouped products' jits keep what they traced, the interpreter's call with it: not for the compiles
    # of ``test_pallas_tpu_lowering.py`` at these very shapes, should this worker run them next
    request.addfinalizer(px._moe_grouped_mm.clear_cache)
    request.addfinalizer(px._moe_grouped_mm_dw.clear_cache)
    N, k, held, total, tile, C, I, _ = CELL_SHARES[cell]
    x, idx, tw, w1, w3, w2 = _share_shapes(N, k, held, C, I)
    wave_tiles = jaxex.moe_wave_tiles(N * k, held, total, tile)
    assert (jax.eval_shape(lambda i, w: jaxex.moe_plan(i, w, 0, held, tile, wave_tiles), idx, tw)["pos"] is not None) == CELL_SHARES[cell][-1]
    before = px.stats.get("moe_combine", 0)
    fwd = jax.make_jaxpr(lambda *a: jaxex._moe_share(*a, 0, total, tile))(x, idx, tw, w1, w3, w2)
    bwd = jax.make_jaxpr(lambda *a: jaxex._moe_expert_share_backward_impl(*a, 0, total, tile))(x, x, idx, tw, w1, w3, w2)
    takes = cell in WALKED
    assert takes == jaxex._kernel_takes((N, k, None, held), wave_tiles * tile)
    floating = [s for s in _scatters(fwd.jaxpr) + _scatters(bwd.jaxpr) if jnp.issubdtype(s[1], jnp.floating)]
    assert (not [s for s in floating if s[2] > 1]) == (takes or CELL_SHARES[cell][-1]), "no rows are scattered where the kernel walks"
    # where the plan makes no ``pos`` the routing weights' gradient is still added to, a number a row
    assert (not [s for s in floating if s[2] == 1]) == CELL_SHARES[cell][-1], floating
    assert ("moe_combine" in _pallas_calls(fwd.jaxpr)) == takes and ("moe_combine" in _pallas_calls(bwd.jaxpr)) == takes
    assert (px.stats.get("moe_combine", 0) > before) == takes
    if takes:
        assert px.combine_schedule["block_tokens"] == 128 and px.combine_schedule["streams"] == held
        assert px.combine_schedule["vmem_limit_bytes"] <= 96 << 20


def _kept_by_dispatch_and_combine(cell, pos: bool):
    N, k, held, total, tile, C, I, _ = CELL_SHARES[cell]
    tiles = jaxex.moe_wave_tiles(N * k, held, total, tile)
    R = tiles * tile
    sd, static = jax.ShapeDtypeStruct, (N, k, jnp.dtype(jnp.bfloat16), held)
    row_src, pos, tg = sd((R,), jnp.int32), sd((N, k), jnp.int32) if pos else None, sd((tiles,), jnp.int32)
    kept = [jax.eval_shape(functools.partial(jaxex._dispatch_fwd, static), sd((N, C), jnp.bfloat16), sd((N, k), jnp.float32),
                           row_src, pos, sd((R,), jnp.float32), tg)[1],
            jax.eval_shape(functools.partial(jaxex._combine_fwd, static), sd((R, C), jnp.bfloat16), row_src, pos, tg)[1]]
    return jax.tree_util.tree_leaves(kept), ((R,), (N, k)), (tiles,)


@pytest.mark.parametrize("cell", list(CELL_SHARES))
def test_dispatch_and_combine_keep_the_two_index_arrays_and_no_rows(cell, xla_combine):
    leaves, index_arrays, groups = _kept_by_dispatch_and_combine(cell, CELL_SHARES[cell][-1])
    assert leaves and all(leaf.dtype == jnp.int32 and leaf.shape in (*index_arrays, groups) for leaf in leaves), leaves
    assert sum(leaf.shape in index_arrays for leaf in leaves) == (3 if CELL_SHARES[cell][-1] else 2)


@pytest.mark.parametrize("cell", list(CELL_SHARES))
def test_on_the_kernels_path_they_keep_the_two_index_arrays_and_no_rows(cell, walked):
    """``moe_combine`` walks by ``row_src`` and reads which group a row tile is
    of: what the two keep for the backward pass is still ``row_src`` and, where
    the plan makes it, ``pos`` (the dispatch's both, the combine's
    ``row_src``), int32, and beside them the wave's ``tile_group`` (a number a
    row tile, which the grouped products keep already); no row of ``C`` numbers."""
    leaves, index_arrays, groups = _kept_by_dispatch_and_combine(cell, CELL_SHARES[cell][-1])
    assert all(leaf.dtype == jnp.int32 and leaf.shape in (*index_arrays, groups) for leaf in leaves), leaves
    assert sum(leaf.shape in index_arrays for leaf in leaves) == (3 if CELL_SHARES[cell][-1] else 2)
    assert sum(leaf.shape == groups for leaf in leaves) == 1


# ``moe_combine`` (PR 58) against XLA's two forms, to the bit: a rehearsal size of each cell's share
# (its k, its held of all, its tile; fewer tokens, a narrow C), and the routings that corner the walk
WALKS = {      # tokens, k, first, held, all, tile, C, and how the routing is bent
    "lfm2_decode": dict(N=256, k=4, first=0, held=32, total=32, tile=64, C=128),
    "lfm2_prefill": dict(N=640, k=4, first=0, held=32, total=32, tile=128, C=128),
    "axk1_decode": dict(N=64, k=8, first=24, held=12, total=192, tile=16, C=256),
    "axk1_prefill": dict(N=1100, k=8, first=24, held=12, total=192, tile=128, C=128),
    "hybrid_train": dict(N=1024, k=10, first=64, held=32, total=512, tile=128, C=128),
    "a_token_with_all_its_rows_and_most_with_none": dict(N=300, k=4, first=8, held=4, total=64, tile=16, C=128, all_on=(7, 299)),
    "every_expert_held_and_one_draws_no_row": dict(N=200, k=4, first=0, held=8, total=8, tile=16, C=128, never=3),
    "a_second_and_a_third_wave": dict(N=400, k=4, first=4, held=8, total=256, tile=16, C=128, over=16, onto=5),
    "a_share_that_draws_nothing": dict(N=130, k=2, first=6, held=2, total=8, tile=16, C=128, over=6),
}


def _walk_case(N, k, first, held, total, tile, C, over=None, onto=None, never=None, all_on=(), seed=0):
    rng = np.random.default_rng(seed)
    pool = np.array([e for e in range(over or total) if e != never])
    idx = np.stack([rng.permutation(pool)[:k] for _ in range(N)]).astype(np.int32)
    if onto is not None:
        idx[:, 0] = onto
    for n in all_on:
        idx[n] = first + rng.permutation(held)[:k]
    tw = jnp.asarray(rng.random((N, k)), jnp.float32)
    wave_tiles = jaxex.moe_wave_tiles(N * k, held, total, tile)
    return jnp.asarray(idx), tw, wave_tiles, jaxex.moe_plan(jnp.asarray(idx), tw, first, held, tile, wave_tiles)


@pytest.mark.parametrize("rows_dtype,sum_dtype", [("float32", "float32"), ("bfloat16", "float32"), ("bfloat16", "bfloat16")],
                         ids=["float32_rows", "bfloat16_rows_summed_in_float32", "bfloat16_rows_summed_at_16_bits"])
@pytest.mark.parametrize("case", list(WALKS))
def test_the_kernel_gives_the_bits_of_both_xla_forms(case, rows_dtype, sum_dtype, walked, monkeypatch):
    """Every wave of the buffer: ``moe_combine``, the scatter-add and the gather
    by ``pos`` add a token's rows lowest first in float32, so the three agree in
    every bit.  The sum of the rows' gradient comes back at the rows' own 16
    bits: rounded once from the float32 sum, as the chip's scatter-add and its
    fused adds round it (``tools/moe_tune.py --glue --check`` holds the kernel
    to their bits there; this CPU's XLA rounds after every row, which is not
    what the program runs on)."""
    c = WALKS[case]
    N, k, held, tile, C = (c[n] for n in ("N", "k", "held", "tile", "C"))
    idx, _, wave_tiles, plan = _walk_case(**c)
    counts = np.asarray((idx >= c["first"]) & (idx < c["first"] + held)).sum(axis=1)
    if "all_on" in c:
        assert (counts[list(c["all_on"])] == k).all() and (counts == 0).mean() > 0.5
    waves = plan["tile_group"].shape[0] // wave_tiles
    assert (case == "a_second_and_a_third_wave") == (int(plan["tiles_used"]) > 2 * wave_tiles)
    static = (N, k, jnp.dtype(rows_dtype), held)
    rng = np.random.default_rng(1)
    for w in range(min(waves, 3)):
        row_src, pos, _, tg, _ = jaxex.moe_wave_rows(plan, w, tile, wave_tiles)
        vb = jnp.asarray(rng.standard_normal((wave_tiles * tile, C)), rows_dtype)
        before = px.stats.get("moe_combine", 0)
        got = jaxex._tokens_of_rows(vb, row_src, pos, tg, static, sum_dtype)
        assert px.stats["moe_combine"] == before + 1 and "fallback" not in px.combine_schedule
        with monkeypatch.context() as m:
            m.setattr(jaxex, "_tokens_of_rows_fast_path", None)
            added = jaxex._tokens_of_rows(vb, row_src, None, tg, static, jnp.float32).astype(sum_dtype)
            gathered = added if pos is None else jaxex._tokens_of_rows(vb, row_src, pos, tg, static, jnp.float32).astype(sum_dtype)
        assert got.dtype == jnp.dtype(sum_dtype) and got.shape == (N, C)
        got, added, gathered = (np.asarray(a.astype(jnp.float32)) for a in (got, added, gathered))
        assert (got == added).all() and (got == gathered).all(), (w, np.abs(got - added).max())
        here = np.asarray(row_src)[np.asarray(row_src) >= 0] // k
        assert (got[np.setdiff1d(np.arange(N), here)] == 0).all(), "a token without a row in this wave gets zeros"


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("case", ["lfm2_prefill", "axk1_prefill", "hybrid_train", "a_second_and_a_third_wave"])
def test_the_share_and_its_gradients_are_the_xla_paths_through_the_kernel(case, dtype, walked, monkeypatch):
    """``_moe_share`` and ``jax.vjp`` of it with ``moe_combine`` on the way
    (the share's sum and, backward, the sum of the rows' gradient; the weights'
    gradient by ``pos`` where XLA's form adds) against XLA's forms: the share,
    ``dx``, the routing weights' and the three matrices' gradients, bit for bit
    (a 16-bit ``dx`` to its rounding: see the test above).
    (Where later waves run, XLA compiles each ``cond`` branch's ``y + wave`` with
    its own form's adds in it and is free to add a wave's rows to ``y`` one by
    one; the kernel's sum is a wave's alone.  Equal to a rounding there, as a
    token alone and among others is where waves differ.)"""
    c = WALKS[case]
    N, k, first, held, total, tile, C = (c[n] for n in ("N", "k", "first", "held", "total", "tile", "C"))
    idx, tw, *_ = _walk_case(**c)
    rng = np.random.default_rng(2)
    x, dy = (jnp.asarray(rng.standard_normal((N, C)), dtype) for _ in range(2))
    w1, w3 = (jnp.asarray(rng.standard_normal((held, C, 128)) * 0.1, dtype) for _ in range(2))
    w2 = jnp.asarray(rng.standard_normal((held, 128, C)) * 0.1, dtype)

    def run():
        y, vjp = jax.vjp(lambda *a: jaxex._moe_share(a[0], idx, *a[1:], first, total, tile), x, tw, w1, w3, w2)
        return (y, *vjp(dy))

    before = px.stats.get("moe_combine", 0)
    got = run()
    assert px.stats["moe_combine"] >= before + 2, "the share's sum and the rows' gradient's"
    with monkeypatch.context() as m:
        m.setattr(jaxex, "_tokens_of_rows_fast_path", None)
        want = run()
    for name, a, b in zip(("y", "dx", "dtop_w", "dfc_1", "dfc_2", "dproj"), got, want):
        assert a.dtype == b.dtype, name
        if case == "a_second_and_a_third_wave":
            assert rel(a, b) < (1e-6 if dtype == "float32" else 4e-3), name
        elif name == "dx" and dtype == "bfloat16":      # rounded once here; after every row by this CPU's XLA (not by the chip's)
            assert rel(a, b) < 4e-3, name
        else:
            assert (np.asarray(a.astype(jnp.float32)) == np.asarray(b.astype(jnp.float32))).all(), name


def test_a_token_alone_in_xla_has_the_bits_of_the_token_among_the_others_in_the_kernel(interpreted, monkeypatch):
    """The solo contract across the two: a request's last token alone is a call
    of one token, XLA's; in a prompt of 640 it is the kernel's."""
    monkeypatch.setattr(px, "_gmm_vmem_cap", lambda: 96 << 20)
    monkeypatch.setattr(jaxex, "_ASSIGNMENTS_GATHERED_A_KERNEL_CALL", 2048)
    idx, tw, x, w1, w3, w2 = _routed(N=640, k=4, first=0, held=8, total=16, tile=16, C=128, I=128)
    before = px.stats.get("moe_combine", 0)
    among = np.asarray(jaxex._moe_share(x, idx, tw, w1, w3, w2, 0, 16, 16))
    before, took = px.stats["moe_combine"], px.stats["moe_combine"] - before
    assert took >= 1
    for n in (0, 77, 639):
        alone = np.asarray(jaxex._moe_share(x[n:n + 1], idx[n:n + 1], tw[n:n + 1], w1, w3, w2, 0, 16, 16))
        assert (alone[0] == among[n]).all(), n
    assert px.stats["moe_combine"] == before, "a call of one token is XLA's"


@pytest.mark.parametrize("why", ["shape", "several devices", "dtype", "VMEM", "no Pallas", "few tokens"])
def test_where_the_kernel_declines_xla_runs_and_the_schedule_says_why(why, interpreted, monkeypatch):
    N, k, held, total, tile, C = 640, 4, 8, 16, 16, 128
    dtype = jnp.float32
    monkeypatch.setattr(jaxex, "_ASSIGNMENTS_GATHERED_A_KERNEL_CALL", 2048)
    if why == "shape":
        C = 192                              # not whole lane tiles
    elif why == "dtype":
        dtype = jnp.float16
    elif why == "VMEM":
        monkeypatch.setattr(px, "_gmm_vmem_cap", lambda: 1 << 20)
    elif why == "no Pallas":
        monkeypatch.delenv("THUNDER_TPU_PALLAS_INTERPRET")
    elif why == "few tokens":
        N = jaxex._TOKENS_A_KERNEL_CALL - 1
    idx, tw, x, w1, w3, w2 = _routed(N=N, k=k, first=0, held=held, total=total, tile=tile, C=C, I=128)
    x, w1, w3, w2 = (a.astype(dtype) for a in (x, w1, w3, w2))
    wave_tiles = jaxex.moe_wave_tiles(N * k, held, total, tile)
    px.combine_schedule.clear()
    before = px.stats.get("moe_combine", 0)
    mesh = jax.sharding.Mesh(np.array(jax.devices()[:2]), ("x",))
    with (px.mesh_context(mesh) if why == "several devices" else monkeypatch.context()):
        said = px.combine_declines(N, wave_tiles * tile, wave_tiles, C, held, dtype, jnp.float32)
        got = jaxex._moe_share(x, idx, tw, w1, w3, w2, 0, total, tile)
        assert px.stats.get("moe_combine", 0) == before
        schedule = dict(px.combine_schedule)
        with monkeypatch.context() as m:
            m.setattr(jaxex, "_tokens_of_rows_fast_path", None)
            want = jaxex._moe_share(x, idx, tw, w1, w3, w2, 0, total, tile)
    assert (np.asarray(got, np.float32) == np.asarray(want, np.float32)).all()
    if why == "few tokens":       # jaxex's own rule, beside the one that chooses between XLA's forms: the kernel is not asked
        assert said == "" and not schedule
    else:
        assert said == why and schedule == {"fallback": why, "tokens": N, "slots": k, "rows": wave_tiles * tile, "width": C}


# --------------------------------------------------------------------------
# what does not run this model, and what is untouched
# --------------------------------------------------------------------------

def _tiny_cfg_and_params():
    hf = tiny(8)
    return llama.Config(**arch.program_config(hf)), arch.make_params(hf, common.seed_words(1))


def test_generate_refuses_the_config_with_one_clear_error():
    """Since PR 32 the server runs linear_attention layers and since PR 59 an expert
    share routed by softmax; this model's zero-centred norms are what it still
    lacks, and the refusal names them."""
    from thunder_tpu.models import generate

    cfg, params = _tiny_cfg_and_params()
    with pytest.raises(NotImplementedError, match="cannot be served.*norm_zero_centered"):
        generate.generate(params, jnp.zeros((1, 4), jnp.int32), cfg, 2)


def test_serve_refuses_the_config_with_one_clear_error():
    cfg, params = _tiny_cfg_and_params()
    with pytest.raises(NotImplementedError, match="cannot be served.*norm_zero_centered"):
        tt.serve(None, params, cfg, num_blocks=8, max_batch=1)


def test_zero_centred_norms_are_refused_and_a_softmax_expert_share_and_linear_layers_are_not():
    from thunder_tpu.models.generate import require_servable

    cfg = llama.Config(name="moe-only", n_layer=2, n_head=4, n_embd=64, mlp_class="SparseMoE", n_expert=8,
                       n_expert_per_token=2, intermediate_size=32)
    require_servable(cfg)                                  # the softmax router is served since PR 59
    with pytest.raises(NotImplementedError, match="norm_zero_centered"):
        require_servable(dataclasses.replace(cfg, norm_zero_centered=True))
    require_servable(llama.Config.from_name("tiny-mistral-debug"))
    linear = llama.Config(name="linear-dense", n_layer=2, n_head=4, n_embd=64, intermediate_size=96,
                          layer_types=("linear_attention", "full_attention"), linear_num_key_heads=2,
                          linear_num_value_heads=2, linear_key_head_dim=8, linear_value_head_dim=16)
    assert linear.training_only is None
    require_servable(linear)
    # this model's zero-centred norms have no serving form (its gated attention has one since the window kind)
    gated = _tiny_cfg_and_params()[0]
    assert gated.attn_output_gate and gated.qk_norm and gated.norm_zero_centered


def test_init_params_builds_the_layout_the_reference_builds():
    cfg, params = _tiny_cfg_and_params()
    own = llama.init_params(cfg, jax.random.PRNGKey(0))
    shapes = lambda t: jax.tree_util.tree_map(lambda a: a.shape, t)  # noqa: E731
    assert shapes(own) == shapes(params)
    assert cfg.layer_types == ("linear_attention",) * 3 + ("full_attention",)


@pytest.mark.parametrize("name", ["tiny-llama-debug", "tiny-mistral-debug", "tiny-moe-debug", "tiny-pythia-debug"])
def test_presets_that_were_there_draw_the_weights_they_drew(name):
    """The new kinds take no random key from the presets' stream."""
    cfg = llama.Config.from_name(name)
    assert cfg.layer_types is None and cfg.training_only is None
    n = 3 + cfg.n_layer * (5 + 3 * max(1, cfg.n_expert))
    first = jax.random.split(jax.random.PRNGKey(0), n)[0]
    want = (jax.random.normal(first, (cfg.padded_vocab_size, cfg.n_embd), jnp.float32) * 0.02).astype(jnp.bfloat16)
    assert jnp.array_equal(llama.init_params(cfg, jax.random.PRNGKey(0))["wte"], want)


def test_layer_types_are_checked():
    with pytest.raises(AssertionError):
        llama.Config(n_layer=2, layer_types=("full_attention",))
    with pytest.raises(AssertionError):
        llama.Config(n_layer=1, layer_types=("linear_attention",))     # no head counts given
