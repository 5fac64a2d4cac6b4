"""A hybrid dense decoder (OLMo blocks, three Gated-DeltaNet layers to one
full-attention layer, no rotary embedding) on the serving path: the recurrent
state beside the paged KV.

Program and reference (``chipbench/models/hybrid_dense_decoder.py``: the
recurrence token by token, float32, no cache) are given the same float32
weights at tiny widths in the published ratio (``dk`` 12, ``dv`` 24, four
layers), so they agree to the rounding of float32 sums in another order; the
engine's tests (served tokens against solo ``generate()``, bit for bit) are in
``tests/test_hybrid_engine.py``.
"""
from __future__ import annotations

import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

import thunder_tpu as tt  # noqa: E402
from thunder_tpu.executors import jaxex  # noqa: E402
from thunder_tpu.executors import pallasex as px  # noqa: E402
from thunder_tpu.models import generate as G  # noqa: E402
from thunder_tpu.models import llama  # noqa: E402
from thunder_tpu.serving.kv_pool import (  # noqa: E402
    PagedKVPool, StatePool, gather_state, pack_state_heads, scatter_state, tiled_bytes, unpack_state_heads)
from thunder_tpu.serving.scheduler import Scheduler  # noqa: E402

from _hybrid_tiny import TINY, arch, tiny_model, tokens as _tokens  # noqa: E402
from conftest import compiled_forward  # noqa: E402

# float32 sums in another order (the chunked scan against the token-by-token
# recurrence, XLA's dots against the reference's): a head that remembers (alpha
# near 1, as the weights' decay is drawn) carries the difference along the
# sequence, and logits of size 1-6 agree to 1e-3 after 96 tokens (1e-4 after 10)
TOL = 3e-3


@pytest.fixture(autouse=True)
def interpreted(monkeypatch):
    monkeypatch.setenv("THUNDER_TPU_PALLAS_INTERPRET", "1")


@pytest.fixture(scope="module")
def model():
    return tiny_model()


# --------------------------------------------------------------------------
# the config and the map from a model layer to its cache layer
# --------------------------------------------------------------------------

def test_the_layer_map_lives_in_the_config(model):
    cfg, _ = model
    assert cfg.kv_layers == (3,) and cfg.linear_layers == (0, 1, 2)
    assert cfg.linear_qkv_width == 2 * 2 * 12 + 2 * 24
    assert G.cache_shape(cfg, 2, 64) == (1, 2, 4, 64, 12) and G.kv_block_shape(cfg, 16) == (1, 4, 16, 12)
    assert G.state_shapes(cfg, 2) == {"conv": (3, 2, 3, 96), "state": (3, 2, 2, 12, 24)}
    dense = llama.Config.from_name("tiny-llama-debug")
    assert dense.kv_layers == (0, 1) and dense.linear_layers == () and G.state_shapes(dense, 2) == {}
    assert G.cache_shape(dense, 1, 32)[0] == dense.n_layer


def test_linear_attention_is_served_and_so_is_a_softmax_expert_share(model):
    cfg, _ = model
    assert cfg.training_only is None
    G.require_servable(cfg)
    moe = llama.Config(name="moe-only", n_layer=2, n_head=4, n_embd=64, mlp_class="SparseMoE", n_expert=8,
                       n_expert_per_token=2, intermediate_size=32)
    assert moe.training_only is None                       # the softmax router is served since PR 59
    G.require_servable(moe)


@pytest.mark.parametrize("knob", ["attn_output_gate", "qk_norm", "norm_zero_centered"])
def test_attention_forms_the_server_lacks_are_refused_by_name(knob):
    cfg = llama.Config(name="gated", n_layer=1, n_head=2, n_embd=32, **{knob: True})
    if knob in ("qk_norm", "attn_output_gate"):     # served since the per-head norm, then the gate, went into generate._project_qkv
        assert cfg.training_only is None
        return G.require_servable(cfg)
    with pytest.raises(NotImplementedError, match=knob):
        G.require_servable(cfg)


def test_init_params_builds_the_layout_the_reference_builds(model):
    cfg, params = model
    shapes = lambda t: jax.tree_util.tree_map(lambda a: a.shape, t)  # noqa: E731
    assert shapes(llama.init_params(cfg, jax.random.PRNGKey(0))) == shapes(params)


# --------------------------------------------------------------------------
# program = reference
# --------------------------------------------------------------------------

def test_forward_through_the_compiler_matches_the_reference(model):
    cfg, params = model
    seq = _tokens(96)
    fwd = tt.jit(lambda p, idx, c, s: llama.gpt_forward(p, idx, c, s, cfg))
    got = fwd(params, jnp.asarray(seq[None]), *llama.build_rope_cache(cfg, 96))[0, :, :TINY["vocab_size"]]
    want = arch.ref_logits(TINY, params, jnp.asarray(seq), jnp.arange(96))
    assert float(jnp.max(jnp.abs(got - want))) < TOL


def test_the_model_trains_through_the_normal_path(model):
    cfg, params = model
    toks = jnp.asarray(_tokens(65)[None])
    cos, sin = llama.build_rope_cache(cfg, 64)
    loss, grads = tt.value_and_grad(lambda p, i, t, c, s: llama.gpt_loss(p, i, t, c, s, cfg))(
        params, toks[:, :-1], toks[:, 1:], cos, sin)

    def ref_loss(p):
        lg = arch.ref_logits(TINY, p, toks[0, :-1], jnp.arange(64))
        return jnp.mean(jax.nn.logsumexp(lg, -1) - jnp.take_along_axis(lg, toks[0, 1:, None], -1)[:, 0])

    want, ref = jax.value_and_grad(ref_loss)(params)
    assert abs(float(loss) - float(want)) < 1e-4
    got, ref = grads[0] if isinstance(grads, tuple) else grads, ref
    # the log decay's gradient is a difference of sums along the sequence, and in a
    # head that remembers the terms cancel: float32 leaves 3e-3 of it (in_proj_ba)
    for name in ("in_proj_ba", "out_proj", "A_log"):
        a, b = got["blocks"][1]["gdn"][name], ref["blocks"][1]["gdn"][name]
        assert float(jnp.linalg.norm(a - b) / jnp.linalg.norm(b)) < 6e-3, name
    a, b = got["blocks"][3]["attn"]["q_norm"], ref["blocks"][3]["attn"]["q_norm"]
    assert float(jnp.linalg.norm(a - b) / jnp.linalg.norm(b)) < 2e-3


@pytest.fixture(scope="module")
def walked(model):
    """Prefill of 40 tokens, then 20 decode steps through both caches, against
    the reference's full forward pass."""
    cfg, params = model
    seq = _tokens(60, seed=2)
    cache = G.init_cache(cfg, 1, 64, dtype=jnp.float32)
    cos, sin = llama.build_rope_cache(cfg, 64)
    lg, cache = compiled_forward(cfg)(params, jnp.asarray(seq[None, :40]), cache, cos, sin)
    rows, step = [lg[0]], compiled_forward(cfg, decode=True)
    for i in range(40, 60):
        lg, cache = step(params, jnp.asarray(seq[None, i:i + 1]), i, cache, cos, sin)
        rows.append(lg[0])
    got = jnp.concatenate(rows)[:, :TINY["vocab_size"]]
    return got, arch.ref_logits(TINY, params, jnp.asarray(seq), jnp.arange(60)), cache


@pytest.mark.parametrize("stretch", ["prefill", "decode"])
def test_prefill_then_decode_matches_the_reference_at_every_position(walked, stretch):
    got, want, _ = walked
    rows = slice(0, 40) if stretch == "prefill" else slice(40, 60)
    assert float(jnp.max(jnp.abs(got[rows] - want[rows]))) < TOL


@pytest.mark.parametrize("held", ["state", "k", "v"])
def test_the_caches_hold_what_the_reference_holds(model, walked, held):
    """After those 60 tokens: the delta rule's state of every linear layer and
    the keys and values of the full-attention layer against the reference's
    own (``ref_caches``, from a sequence padded past them: its tail must leave
    the reference's state alone too)."""
    _, params = model
    seq = np.concatenate([_tokens(60, seed=2), _tokens(4, seed=9)])
    layers = list(arch.ref_caches(TINY, params, jnp.asarray(seq), 60))
    assert [kind for kind, _ in layers] == ["state"] * 3 + ["kv"]
    want = (jnp.stack([s for kind, s in layers if kind == "state"]) if held == "state"
            else layers[3][1]["kv".index(held)][None])
    got = walked[2][held][:, 0]
    got = got if held == "state" else got[:, :, :60]
    assert got.shape == want.shape
    assert float(jnp.linalg.norm(got - want) / jnp.linalg.norm(want)) < 1e-4


@pytest.mark.parametrize("pos", ["traced", "static_zero"])
def test_a_padded_prompts_tail_leaves_state_and_conv_tail_untouched(model, pos):
    """A traced position scores the cache's 64 slots whatever T is, so padding
    leaves the real rows' logits exactly as they were.  The Python integer 0
    scores the prompt's own (T, T) triangle: rows of 37 and of 48 terms hold
    the same values and sum them in another order."""
    cfg, params = model
    seq = _tokens(48, seed=3)
    cos, sin = llama.build_rope_cache(cfg, 64)
    at = jnp.int32(0) if pos == "traced" else 0
    exact, c1 = G.forward_with_cache(params, jnp.asarray(seq[None, :37]), at,
                                     G.init_cache(cfg, 1, 64, dtype=jnp.float32), cos, sin, cfg)
    padded, c2 = G.forward_with_cache(params, jnp.asarray(seq[None]), at,
                                      G.init_cache(cfg, 1, 64, dtype=jnp.float32), cos, sin, cfg, n_real=37)
    assert jnp.array_equal(c1["state"], c2["state"]) and jnp.array_equal(c1["conv"], c2["conv"])
    if pos == "traced":
        assert jnp.array_equal(exact[0], padded[0, :37])
    else:
        np.testing.assert_allclose(exact[0], padded[0, :37], rtol=1e-5, atol=1e-5)


def test_generate_picks_the_references_tokens(model):
    cfg, params = model
    prompt = _tokens(40)
    out = np.asarray(G.generate(params, prompt[None], cfg, 12, T_max=64))[0]
    ref = arch.ref_logits(TINY, params, jnp.asarray(out), jnp.arange(39, 51))
    assert np.array_equal(np.asarray(jnp.argmax(ref, -1)), out[40:])


# --------------------------------------------------------------------------
# the two kernels
# --------------------------------------------------------------------------

def _scan_inputs(Tn, B=1, Hk=2, Hv=2, dk=12, dv=24, seed=3):
    ks = jax.random.split(jax.random.PRNGKey(seed), 6)
    unit = lambda x: x / jnp.linalg.norm(x, axis=-1, keepdims=True)  # noqa: E731
    q = unit(jax.random.normal(ks[0], (B, Hk, Tn, dk))) * dk ** -0.5
    k = unit(jax.random.normal(ks[1], (B, Hk, Tn, dk)))
    v = jax.random.normal(ks[2], (B, Hv, Tn, dv))
    beta = 2.0 * jax.nn.sigmoid(2.0 * jax.random.normal(ks[3], (B, Hv, Tn)))       # up to 2: negative eigenvalues
    g = -jax.nn.softplus(jax.random.normal(ks[4], (B, Hv, Tn)))
    return q, k, v, g, beta, jax.random.normal(ks[5], (B, Hv, dk, dv))


def _recurrence(q, k, v, g, beta, h0):
    rep = v.shape[1] // q.shape[1]
    q, k = jnp.repeat(q, rep, 1), jnp.repeat(k, rep, 1)

    def head(q, k, v, g, b, S0):
        def step(S, x):
            qt, kt, vt, gt, bt = x
            S = S * jnp.exp(gt)
            S = S + jnp.outer(kt, (vt - S.T @ kt) * bt)
            return S, S.T @ qt
        return jax.lax.scan(step, S0, (q, k, v, g, b))

    last, o = jax.vmap(jax.vmap(head))(q, k, v, g, beta, h0)
    return o, last


def rel(a, b) -> float:
    a, b = jnp.asarray(a, jnp.float32), jnp.asarray(b, jnp.float32)
    return float(jnp.linalg.norm(a - b) / jnp.maximum(jnp.linalg.norm(b), 1e-30))


@pytest.mark.parametrize("path", ["pallas", "xla"])
def test_scan_from_a_state_matches_the_recurrence_with_beta_up_to_two(path, monkeypatch):
    if path == "xla":
        monkeypatch.setattr(jaxex, "_gdn_state_fast_path", None)
    *args, h0 = _scan_inputs(1024)
    assert float(jnp.max(args[4])) > 1.9
    before = px.stats.get("gdn", 0)
    o, last = jaxex.gdn_chunk_state(*args, h0)
    assert px.stats.get("gdn", 0) - before == (1 if path == "pallas" else 0)
    want_o, want_last = _recurrence(*args, h0)
    assert rel(o, want_o) < 5e-5 and rel(last, want_last) < 5e-5


@pytest.mark.parametrize("path", ["pallas", "xla"])
def test_two_halves_chained_are_one_call(path, monkeypatch):
    if path == "xla":
        monkeypatch.setattr(jaxex, "_gdn_state_fast_path", None)
    *args, h0 = _scan_inputs(1024, seed=4)
    whole_o, whole_last = jaxex.gdn_chunk_state(*args, h0)
    half = lambda a, lo: a[:, :, lo:lo + 512]  # noqa: E731
    o1, mid = jaxex.gdn_chunk_state(*(half(a, 0) for a in args), h0)
    o2, last = jaxex.gdn_chunk_state(*(half(a, 512) for a in args), mid)
    assert rel(jnp.concatenate([o1, o2], axis=2), whole_o) < 1e-6 and rel(last, whole_last) < 1e-6


def test_the_trainers_call_passes_no_state_and_gets_the_same_scan():
    *args, _ = _scan_inputs(512)
    o, states = px.gdn_chunk(*args)
    o2, last = px.gdn_chunk_state(*args, jnp.zeros((1, 2, 12, 24)))
    assert jnp.array_equal(o, o2) and states.shape == (1, 2, 1, 12, 24)
    assert rel(last, _recurrence(*args, jnp.zeros((1, 2, 12, 24)))[1]) < 5e-5


def test_compiled_heads_are_padded_to_whole_lane_tiles(monkeypatch):
    assert px._gdn_lanes(96) == 96                       # the interpreter takes any width
    monkeypatch.setattr(px, "_interpret", lambda: False)
    assert (px._gdn_lanes(96), px._gdn_lanes(192), px._gdn_lanes(128)) == (128, 256, 128)
    assert px._gdn_supported((1, 30, 2560, 96), (1, 30, 2560, 192), jnp.dtype("bfloat16"), 64)


def _decode_inputs(rows=5, slots=6, L=3, Hk=2, Hv=4, dk=12, dv=24):
    """The arena as the pool lays it out, ``(slots + 1, L, dk, Hv dv)``: a matrix a head, the heads side by side."""
    ks = jax.random.split(jax.random.PRNGKey(11), 7)
    arena = pack_state_heads(jax.random.normal(ks[0], (slots + 1, L, Hv, dk, dv)))
    q, k = (jax.random.normal(key, (rows, Hk, dk)) * 0.3 for key in ks[1:3])
    v = jax.random.normal(ks[3], (rows, Hv, dv))
    g = -jax.nn.softplus(jax.random.normal(ks[4], (rows, Hv)))
    beta = 2.0 * jax.nn.sigmoid(jax.random.normal(ks[5], (rows, Hv)))
    return arena, q, k, v, g, beta


def test_decode_step_is_one_step_of_the_recurrence_in_place():
    arena, q, k, v, g, beta = _decode_inputs()
    slots = jnp.asarray([3, 1, 6, 0, 0], jnp.int32)       # two padding rows on the sink
    before = px.stats.get("gdn_decode", 0)
    o, new = px.gdn_decode_step(arena, slots, q, k, v, g, beta, layer=1)
    assert px.stats["gdn_decode"] == before + 1
    h0 = unpack_state_heads(arena[slots, 1], 4)
    want_o, want_S = _recurrence(*(a[:, :, None] for a in (q, k, v)), g[:, :, None], beta[:, :, None], h0)
    assert rel(o[:3], want_o[:3, :, 0]) < 1e-6
    for row, slot in enumerate([3, 1, 6]):
        assert rel(unpack_state_heads(new[slot, 1], 4), want_S[row]) < 1e-6
    # every other layer and slot keeps its bytes; the sink is anyone's
    untouched = np.ones(arena.shape[:2], bool)
    untouched[[3, 1, 6, 0], 1] = False
    assert jnp.array_equal(new[untouched], arena[untouched])


def test_decode_step_and_the_dense_caches_step_are_the_same_formulas():
    arena, q, k, v, g, beta = _decode_inputs(rows=3, Hk=2, Hv=2)
    slots = jnp.asarray([2, 5, 4], jnp.int32)
    o, new = px.gdn_decode_step(arena, slots, q, k, v, g, beta, layer=0)
    recur, box = G.gdn_recur_dense(unpack_state_heads(arena[slots, 0], 2))
    o2 = recur(q[:, :, None], k[:, :, None], v[:, :, None], g[:, :, None], beta[:, :, None])
    # one function, two compilations: XLA contracts a multiply-add here and not there
    assert rel(o, o2[:, :, 0]) < 1e-6 and rel(unpack_state_heads(new[slots, 0], 2), box[0]) < 1e-6


def test_decode_step_keeps_a_bfloat16_arena_in_bfloat16():
    arena, q, k, v, g, beta = _decode_inputs()
    o, new = px.gdn_decode_step(arena.astype(jnp.bfloat16), jnp.asarray([1, 2, 3, 4, 5], jnp.int32), q, k, v, g, beta, layer=2)
    assert new.dtype == jnp.bfloat16 and o.dtype == v.dtype


class _Ref:
    """A kernel's ref over a ``jax.numpy`` array, so that the body runs a line at a time."""

    def __init__(self, x):
        self.x = x

    shape = property(lambda self: self.x.shape)
    dtype = property(lambda self: self.x.dtype)

    def __getitem__(self, i):
        return self.x[i]

    def __setitem__(self, i, value):
        self.x = self.x.at[i].set(value)


# Hk, Hv, dk, dv: the cell's heads scaled down (two a group of three lane tiles), a head of half a tile (two a
# group of one), Qwen3-Next's (a head a group, two value heads a key head), an odd count and the rehearsal's
# (no group ends on a tile's edge: the whole width), the last with two value heads a key head too
@pytest.mark.parametrize("Hk,Hv,dk,dv,group,arena_dtype", [
    (6, 6, 24, 192, 2, jnp.float32), (4, 4, 16, 64, 2, jnp.float32), (2, 4, 16, 128, 1, jnp.float32),
    (3, 3, 24, 192, 3, jnp.float32), (2, 2, 12, 24, 2, jnp.float32), (2, 4, 12, 24, 4, jnp.float32),
    (6, 6, 24, 192, 2, jnp.bfloat16), (2, 4, 12, 24, 4, jnp.bfloat16)])
def test_a_head_beside_its_neighbours_keeps_the_bits_of_the_step_alone(Hk, Hv, dk, dv, group, arena_dtype):
    """The kernel's body on a row of heads side by side against ``gdn_step_math`` a
    head at a time, ``(dk, dv)`` with its own key and query columns: the same
    bits in ``o`` and in the state.  Both run a line at a time (``disable_jit``:
    compiled, this CPU's XLA contracts a multiply-add in one program and not in
    another, a last bit that is the compiler's and not the layout's)."""
    assert px._gdn_group(Hv, dv) == group
    f32, W, rep = jnp.float32, Hv * dv, Hv // Hk
    ks = jax.random.split(jax.random.PRNGKey(5), 6)
    heads = jax.random.normal(ks[0], (Hv, dk, dv)).astype(arena_dtype)
    q, k = (jax.random.normal(key, (Hk, dk)) * 0.3 for key in ks[1:3])
    v = jax.random.normal(ks[3], (Hv, dv))
    a = jnp.exp(-jax.nn.softplus(jax.random.normal(ks[4], (Hv,))))
    beta = 2.0 * jax.nn.sigmoid(jax.random.normal(ks[5], (Hv,)))
    lanes = lambda x: jnp.repeat(x, dv)[None, None]  # noqa: E731
    with jax.disable_jit():
        o, so = _Ref(jnp.zeros((1, 1, W), f32)), _Ref(jnp.zeros((1, 1, dk, W), arena_dtype))
        px._gdn_decode_kernel(None, _Ref(q.T[None]), _Ref(k.T[None]), _Ref(v.reshape(1, 1, W)), _Ref(lanes(a)),
                              _Ref(lanes(beta)), _Ref(pack_state_heads(heads)[None, None]), o, so, Hv=Hv, rep=rep)
        got_S = unpack_state_heads(so.x[0, 0], Hv)
        for h in range(Hv):
            want_o, want_S = px.gdn_step_math(heads[h].astype(f32), k[h // rep][:, None], q[h // rep][:, None], v[h][None],
                                              jnp.full((1, dv), a[h]), jnp.full((1, dv), beta[h]))
            assert jnp.array_equal(o.x[0, 0, h * dv:(h + 1) * dv], want_o[0]), h
            assert jnp.array_equal(got_S[h], want_S.astype(arena_dtype)), h


@pytest.mark.parametrize("Hk,Hv,dk,dv", [(4, 4, 24, 192), (3, 3, 24, 192)])
def test_the_compiled_walk_writes_its_rows_slots_and_no_other(Hk, Hv, dk, dv):
    """The whole call under the interpreter at heads of whole and of broken
    lane groups: the rows' slots take the recurrence's step, the sink is
    anyone's, and every slot and layer not named keeps its bytes."""
    arena, q, k, v, g, beta = _decode_inputs(rows=3, slots=4, L=2, Hk=Hk, Hv=Hv, dk=dk, dv=dv)
    slots = jnp.asarray([4, 2, 0], jnp.int32)
    o, new = px.gdn_decode_step(arena, slots, q, k, v, g, beta, layer=1)
    want_o, want_S = _recurrence(*(a[:, :, None] for a in (q, k, v)), g[:, :, None], beta[:, :, None],
                                 unpack_state_heads(arena[slots, 1], Hv))
    assert rel(o[:2], want_o[:2, :, 0]) < 1e-6 and rel(unpack_state_heads(new[slots[:2], 1], Hv), want_S[:2]) < 1e-6
    untouched = np.ones(arena.shape[:2], bool)
    untouched[[4, 2, 0], 1] = False
    assert jnp.array_equal(new[untouched], arena[untouched])


# --------------------------------------------------------------------------
# the state pool and the byte admission
# --------------------------------------------------------------------------

def test_state_pool_leases_frees_and_rebuilds(model):
    cfg, _ = model
    pool = PagedKVPool(cfg, num_blocks=8, block_size=16, dtype=jnp.float32, state_slots=3)
    st = pool.state
    assert isinstance(st, StatePool) and st.state.shape == (4, 3, 12, 2 * 24) and st.state.dtype == jnp.float32
    assert st.state_heads == 2
    assert st.conv.shape == (4, 3, 3, 96) and pool.k_arena.shape == (8, 1, 4, 16, 12)
    assert set(pool.arenas) == {"k", "v", "state", "conv"}
    got = [st.lease(), st.lease(), st.lease()]
    assert got == [1, 2, 3] and not st.can_lease() and st.snapshot()["fill_frac"] == 1.0
    with pytest.raises(Exception, match="no free state slot"):
        st.lease()
    st.free(2)
    with pytest.raises(ValueError, match="double free"):
        st.free(2)
    assert st.lease() == 2 and st.snapshot()["free_low_water"] == 0
    pool.set_arenas({**pool.arenas, "state": pool.arenas["state"] + 1.0})
    assert float(pool.state.state[1, 0, 0, 0]) == 1.0
    pool.rebuild_arenas()
    assert float(jnp.max(jnp.abs(pool.state.state))) == 0.0 and st.leased == 3      # slots survive a rebuild
    assert st.slot_bytes() == 3 * (2 * 12 * 24 * 4 + 3 * 96 * 4)
    assert pool.state_snapshot()["state"]["slots"] == 3 and pool.occupancy_snapshot()["state"]["leased"] == 3


def test_a_slots_state_goes_round_through_the_dense_caches_layout(model):
    """``gather_state`` hands a slot's rows over as the dense cache keeps them, a
    matrix a head, and ``scatter_state`` lays them side by side again: the same
    bytes back, the other slots untouched, a fresh row zeros whatever the slot held."""
    cfg, _ = model
    pool = PagedKVPool(cfg, num_blocks=8, block_size=16, dtype=jnp.float32, state_slots=3)
    heads = pool.state.state_heads
    ks = jax.random.split(jax.random.PRNGKey(2), 2)
    arenas = {"state": jax.random.normal(ks[0], pool.state.state.shape), "conv": jax.random.normal(ks[1], pool.state.conv.shape)}
    slots = jnp.asarray([2], jnp.int32)
    dense = gather_state(arenas, slots, jnp.asarray([False]), heads)
    assert {k: v.shape for k, v in dense.items()} == G.state_shapes(cfg, 1)
    for h in range(heads):       # head h: columns [h dv, (h + 1) dv) of the arena's row
        assert jnp.array_equal(dense["state"][:, 0, h], arenas["state"][2, :, :, h * 24:(h + 1) * 24])
    back = scatter_state({k: jnp.zeros_like(v) for k, v in arenas.items()}, dense, slots, heads)
    for name in ("state", "conv"):
        assert jnp.array_equal(back[name][2], arenas[name][2]) and not jnp.any(back[name][jnp.asarray([0, 1, 3])])
    fresh = gather_state(arenas, slots, jnp.asarray([True]), heads)
    assert not jnp.any(fresh["state"]) and not jnp.any(fresh["conv"])
    assert jnp.array_equal(unpack_state_heads(pack_state_heads(dense["state"]), heads), dense["state"])


def test_a_state_arena_counts_its_bytes_and_what_the_chip_lays_out_for_them(model):
    """Olmo-Hybrid's row a layer, thirty heads of ``(96, 192)`` side by side, is
    whole tiles both ways; a head a row is a third more (192 lanes lie as 256);
    16-bit rows come in sixteens.  The pool reports both numbers."""
    assert tiled_bytes((33, 12, 96, 5760), jnp.float32) == 33 * 12 * 96 * 5760 * 4
    assert tiled_bytes((33, 12, 30, 96, 192), jnp.float32) * 3 == 33 * 12 * 96 * 5760 * 4 * 4
    assert tiled_bytes((33, 12, 3, 11520), jnp.bfloat16) == 33 * 12 * 16 * 11520 * 2
    cfg, _ = model
    st = PagedKVPool(cfg, num_blocks=8, block_size=16, dtype=jnp.float32, state_slots=3).state
    snap = st.snapshot()
    assert snap["arena_bytes"] == st.arena_bytes() and snap["slot_bytes"] == st.arena_bytes() // 4
    assert snap["arena_laid_out_bytes"] == st.laid_out_bytes("state") + st.laid_out_bytes("conv") > snap["arena_bytes"]
    assert snap["slot_laid_out_bytes"] == snap["arena_laid_out_bytes"] // 4
    # the tiny state, (12, 48) a row a layer: two sublane tiles by one lane tile
    assert st.laid_out_bytes("state") == 4 * 3 * 16 * 128 * 4
    st.install({"conv": st.conv, "state": jnp.zeros((2, 1, 30, 96, 192), jnp.float32)})     # planted: a head a row
    assert st.laid_out_bytes("state") * 3 == int(st.state.nbytes) * 4


def test_a_mismatched_state_arena_is_refused_at_the_swap(model):
    from thunder_tpu.serving.kv_pool import ArenaMismatchError

    cfg, _ = model
    pool = PagedKVPool(cfg, num_blocks=8, block_size=16, dtype=jnp.float32, state_slots=2)
    with pytest.raises(ArenaMismatchError):
        pool.set_arenas({**pool.arenas, "state": pool.arenas["state"].astype(jnp.bfloat16)})
    with pytest.raises(ArenaMismatchError):
        pool.set_arenas({"k": pool.k_arena, "v": pool.v_arena})


def test_a_dense_pool_keeps_no_state_and_a_hybrid_pool_needs_its_slots(model):
    cfg, _ = model
    dense = PagedKVPool(llama.Config.from_name("tiny-llama-debug"), num_blocks=4, block_size=16)
    assert dense.state is None and set(dense.arenas) == {"k", "v"}
    with pytest.raises(ValueError, match="state_slots"):
        PagedKVPool(cfg, num_blocks=4, block_size=16)


def test_admission_reserves_blocks_and_a_state_slot(model):
    cfg, _ = model
    pool = PagedKVPool(cfg, num_blocks=32, block_size=16, dtype=jnp.float32, state_slots=2)
    sch = Scheduler(pool, max_batch=4, max_queue=8)
    reqs = [sch.submit(_tokens(20, seed=i), 10, key=np.zeros(2, np.uint32)) for i in range(3)]
    assert sch.bytes_needed(reqs[0]) == 2 * pool.block_bytes() + pool.state.slot_bytes()
    for want_slot in (1, 2):
        head = sch.next_admittable()
        sch.admit(head, pool.alloc(sch.blocks_needed(head)), 0)
        assert head.state_slot == want_slot
    assert sch.next_admittable() is None               # blocks and a batch slot are free; no state slot is
    sch.finish(reqs[0], "length")
    assert reqs[0].state_slot == 0 and pool.state.leased == 1 and sch.next_admittable() is reqs[2]
    sch.preempt(reqs[1])
    assert reqs[1].state_slot == 0 and pool.state.leased == 0
    assert sch.state_snapshot()["requests"][0]["state_slot"] == 0


