"""A model of single sublayers (Nemotron-H's kinds: a Mamba-2 mixer with a matrix
state a head, an attention mixer, a latent ungated expert feed-forward, each a
layer alone) in ``models.generate`` and through ``tt.serve``, at tiny widths in
float32 on seeded weights, against the benchmark's plain reference
(``chipbench/models/mamba2_latent_moe_decoder.py``, which imports nothing of the
program) and against solo ``generate()``.

Tolerances: the program and the reference compute the same float32 sums in
different orders (the scan a token at a time on the XLA side as in the
reference, by chunks of matrix products in the kernel; the experts by sorted
rows against a mask), so logits agree to 1e-4 of their spread and held arrays
to 1e-4 relative; a bfloat16 state (2^-9 relative) or an fp8 K/V (2^-4) would
read tens to hundreds of times that, and the tests that plant them say so.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import thunder_tpu as tt
from chipbench import common
from conftest import compiled_forward
from thunder_tpu.executors import jaxex
from thunder_tpu.executors import pallasex as px
from thunder_tpu.models import generate as G
from thunder_tpu.models import llama
from thunder_tpu.serving.engine import hybrid_unsupported

arch = common.load_module("models", "mamba2_latent_moe_decoder")

HF = dict(model_name="tiny-nemotron-h", hidden_size=64, hybrid_override_pattern="MEM*EME", num_hidden_layers=7,
          mamba_num_heads=4, mamba_head_dim=64, n_groups=2, ssm_state_size=16, conv_kernel=4,
          num_attention_heads=4, num_key_value_heads=2, head_dim=32, max_position_embeddings=512,
          n_routed_experts=4, published_n_routed_experts=16, expert_first=4, num_experts_per_tok=5, n_group=1,
          topk_group=1, moe_intermediate_size=32, moe_latent_size=48, moe_shared_expert_intermediate_size=40,
          n_shared_experts=1, routed_scaling_factor=5, norm_eps=1e-5, vocab_size=256, initializer_range=0.2,
          time_step_min=0.001, time_step_max=0.1, time_step_floor=1e-4)
KINDS = ("mamba2", "mlp", "mamba2", "full_attention", "mlp", "mamba2", "mlp")
ENGINE = dict(block_size=8, num_blocks=64, max_batch=4, prefill_buckets=[128], cache_dtype=jnp.float32)


@functools.cache
def model(**over):
    hf = {**HF, **over}
    cfg = llama.Config(**arch.program_config(hf))
    with jax.default_matmul_precision("highest"):
        params = arch.make_params(hf, common.seed_words(5), dtype=jnp.float32)
    return cfg, params


def prompt(n: int, seed: int = 0) -> np.ndarray:
    return np.random.default_rng(seed).integers(0, HF["vocab_size"], (n,)).astype(np.int32)


def dense_forward(cfg, params, toks, T_max=256, anew=False, **kw):
    """The whole prompt through the dense cache, compiled; traced ``anew`` for the test that
    counts the kernels' claims, which are made as a call is traced: a kept callable makes none."""
    cos, sin = llama.build_rope_cache(cfg, T_max)
    cache = G.init_cache(cfg, 1, T_max, jnp.float32)
    if anew:
        return jax.jit(lambda p, t, c: G.forward_with_cache(p, t, 0, c, cos, sin, cfg, **kw))(
            params, jnp.asarray(toks)[None], cache)
    return compiled_forward(cfg, **kw)(params, jnp.asarray(toks)[None], cache, cos, sin)


# the reference as it is, a layer's own compiled calls inside one compiled call: one program a length
ref_logits = jax.jit(functools.partial(arch.ref_logits, HF))


def rel(got, want) -> float:
    got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
    return float(np.sqrt(np.sum((got - want) ** 2) / np.sum(want ** 2)))


def padded(toks, to=256):
    return jnp.asarray(np.pad(toks, (0, to - len(toks))))


def test_the_config_names_the_kinds_and_what_each_keeps():
    cfg, _ = model()
    assert cfg.layer_types == KINDS and cfg.single_sublayer and not cfg.hybrid_decoder
    assert cfg.kv_layers == cfg.paged_kv_layers == (3,) and cfg.mamba2_layers == cfg.state_layers == (0, 2, 5)
    assert cfg.mamba_inner == 256 and cfg.mamba_conv_width == 256 + 2 * 2 * 16
    assert G.state_shapes(cfg, 3) == {"conv": (3, 3, 3, 320), "state": (3, 3, 16, 256)}
    assert G.cache_shape(cfg, 2, 64) == (1, 2, 2, 64, 32)
    for word in ("mamba2", "mlp"):
        assert word in llama.serving_only(cfg)
    assert cfg.training_only is None
    with pytest.raises(NotImplementedError, match="cannot be trained"):
        llama.block_forward({}, None, None, None, cfg)
    base = arch.program_config(HF)
    with pytest.raises(AssertionError, match="state slot holds one kind"):
        llama.Config(**{**base, "layer_types": ("mamba2", "mlp", "ssm", "full_attention", "mlp", "mamba2", "mlp")})
    with pytest.raises(AssertionError, match="mamba_heads"):
        llama.Config(**{**base, "mamba_groups": 3})
    with pytest.raises(AssertionError, match="single-sublayer blocks"):
        llama.Config(n_layer=2, n_head=2, n_embd=32, layer_types=("conv", "mlp"))
    with pytest.raises(AssertionError, match="SparseMoE layer's"):
        llama.Config(n_layer=2, n_head=2, n_embd=32, moe_latent_size=16)


def test_init_params_has_the_layout_the_reference_states():
    cfg, params = model()
    ours = llama.init_params(cfg, dtype=jnp.float32)
    shapes = lambda tree: jax.tree.map(lambda a: a.shape, tree)  # noqa: E731
    assert shapes(ours) == shapes(params)
    assert [sorted(b) for b in ours["blocks"]][:4] == [["mamba2", "norm_1"], ["mlp", "norm_1"], ["mamba2", "norm_1"],
                                                       ["attn", "norm_1"]]
    assert "fc_2" not in ours["blocks"][1]["mlp"] and "fc_2" not in ours["blocks"][1]["mlp"]["shared"]


@pytest.mark.parametrize("T,n_real", [(40, None), (128, None), (128, 77)], ids=["short", "whole_chunk", "padded"])
def test_every_kind_agrees_with_the_reference(T, n_real, attn_form):
    """(a), (b) on the dense cache: the full forward's logits, and what each layer
    that keeps something holds after the real tokens, layer by layer; with the
    interpreted kernels a prompt of 128 goes through ``ssd_chunk_fwd``."""
    cfg, params = model()
    toks = prompt(T)
    n = n_real or T
    before = px.stats.get("ssd_chunk", 0)
    with jax.default_matmul_precision("highest"):
        logits, cache = dense_forward(cfg, params, toks, anew=True, **({"n_real": n_real} if n_real else {}))
        want = ref_logits(params, jnp.asarray(toks), jnp.arange(n))
        held = arch.ref_caches(HF, params, padded(toks), n)
    assert (px.stats.get("ssd_chunk", 0) > before) == (attn_form == "interpreted" and T % 128 == 0)
    assert float(jnp.abs(logits[0, :n] - want).max()) < 1e-4 * float(jnp.abs(want).max())
    assert [k for k, _ in held] == ["mamba2", "mlp", "mamba2", "full_attention", "mlp", "mamba2", "mlp"]
    states = [ref for kind, ref in held if kind == "mamba2"]
    for j, (state, tail) in enumerate(states):
        assert rel(cache["state"][j, 0].T, state) < 1e-4 and rel(cache["conv"][j, 0], tail) < 1e-4
    (k, v), = [ref for kind, ref in held if kind == "full_attention"]
    assert rel(cache["k"][0, 0][:, :n], k) < 1e-4 and rel(cache["v"][0, 0][:, :n], v) < 1e-4


def test_prefill_then_decode_through_the_dense_cache_is_the_full_forward():
    cfg, params = model()
    toks = prompt(100, 5)
    full, _ = dense_forward(cfg, params, toks)
    cos, sin = llama.build_rope_cache(cfg, 256)
    lg, cache = dense_forward(cfg, params, toks[:60])
    errs = [float(jnp.abs(lg - full[:, :60]).max())]
    step = compiled_forward(cfg, decode=True)
    for t in range(60, 100):
        lg, cache = step(params, jnp.asarray(toks[t:t + 1])[None], jnp.int32(t), cache, cos, sin)
        errs.append(float(jnp.abs(lg[:, 0] - full[:, t]).max()))
    assert max(errs) < 2e-5


def served(eng, prompts, new):
    handles = [eng.submit(p, max_new_tokens=n) for p, n in zip(prompts, new)]
    while not all(h.done() for h in handles):
        eng.step()
    return [np.asarray(h.result(drive=False).tokens)[len(p):] for p, h in zip(prompts, handles)]


def test_served_tokens_are_solo_generates_and_the_references_best(attn_form):
    """(a) through the paged engine: three requests through the pools together,
    whole-prompt prefills padded to their bucket, then decode through the state
    slots and the one attention layer's blocks; bit for bit solo ``generate()``,
    and at every position the reference's best token."""
    kernels = attn_form == "interpreted"
    cfg, params = model()
    eng = tt.serve(None, params, cfg, **ENGINE)
    assert eng.stats()["attn"]["path"] == ("walk" if kernels else "xla")
    prompts, new = [prompt(40, 6), prompt(23, 7), prompt(128, 8)], [40, 30, 24]
    before = dict(px.stats)
    got = served(eng, prompts, new)
    for name in ("ssd", "ssd_decode", "ssd_chunk"):
        assert (px.stats.get(name, 0) > before.get(name, 0)) == kernels, name
    st = eng.stats()
    assert st["compile_counts"]["prefill_fresh"] == 1 and st["compile_counts"]["decode_paged"] >= 1
    assert st["compile_counts"]["prefill"] == 0 and st["pool_occupancy"]["state"]["arenas"] == ["conv", "state"]
    for p, n, toks in zip(prompts, new, got):
        solo = np.asarray(G.generate(params, p[None], cfg, n, T_max=256))[0, len(p):]
        np.testing.assert_array_equal(toks, solo)
        seq = np.concatenate([p, toks])
        with jax.default_matmul_precision("highest"):
            lg = ref_logits(params, padded(seq), jnp.arange(len(p) - 1, len(seq) - 1))
        short = np.asarray(jnp.max(lg, axis=-1) - jnp.take_along_axis(lg, jnp.asarray(toks)[:, None], axis=-1)[:, 0])
        assert float(short.max()) < 1e-3
    eng.shutdown(drain=False)


def test_what_the_engine_holds_is_the_references_and_no_other_requests():
    """(b): the slot's state and tail and the attention layer's blocks against
    ``ref_caches``; another request's slot or blocks read about 1; the expert
    layers keep nothing; slots and blocks go back at finish."""
    cfg, params = model()
    eng = tt.serve(None, params, cfg, **ENGINE)
    pool, state = eng.pool, eng.pool.state
    assert state.shapes["state"] == (5, 3, 16, 256) and state.shapes["conv"] == (5, 3, 3, 320)
    assert pool.k_arena.shape == (64, 1, 2, 8, 32)
    free0 = pool.num_free
    a = eng.submit(prompt(40, 9), max_new_tokens=60)
    b = eng.submit(prompt(90, 10), max_new_tokens=20)
    while min(len(a.tokens_so_far()), len(b.tokens_so_far())) < 12:
        eng.step()
    assert state.leased == 2 and a._req.state_slot != b._req.state_slot
    helds = {}
    for h in (a, b):
        held, r = jax.device_get(eng.held(h)), h._req
        n = held["tokens"]
        seq = np.concatenate([r.prompt, np.asarray(r.generated, np.int32)])[:n]
        with jax.default_matmul_precision("highest"):
            want = [ref for _, ref in arch.ref_caches(HF, params, padded(seq), n) if ref is not None]
        helds[h] = (held, want)
        assert sorted(held) == ["conv", "k", "state", "tokens", "v"]
        assert held["k"].shape == (1, 2, n, 32) and held["state"].shape == (3, 16, 256) and held["conv"].shape == (3, 3, 320)
        for j, ref in enumerate([w for w in want if w[0].shape == (256, 16)]):
            assert rel(held["state"][j].T, ref[0]) < 1e-4 and rel(held["conv"][j], ref[1]) < 1e-4
        k, v = want[2]
        assert rel(held["k"][0], k) < 1e-4 and rel(held["v"][0], v) < 1e-4
    (held_a, _), (_, want_b) = helds[a], helds[b]
    assert rel(held_a["state"][0].T, want_b[0][0]) > 0.5 and rel(held_a["conv"][0], want_b[0][1]) > 0.5
    assert rel(held_a["k"][0][:, :40], want_b[2][0][:, :40]) > 0.5
    while not (a.done() and b.done()):
        eng.step()
    assert pool.num_free == free0 and state.leased == 0
    eng.shutdown(drain=False)


def _scan_operands(T, H=4, P=64, G=2, N=16, seed=0, B=2):
    rng = np.random.default_rng(seed)
    f = lambda *s: jnp.asarray(rng.standard_normal(s), jnp.float32)  # noqa: E731
    dt = jnp.asarray(rng.uniform(0.001, 0.3, (B, T, H)), jnp.float32)
    return f(B, T, H * P), dt, f(B, T, G, N), f(B, T, G, N), -jnp.asarray(rng.uniform(1, 16, (H,)), jnp.float32)


def _recurrence(x, dt, Bm, Cm, A, h0):
    """The float32 recurrence a token at a time in the equations' own layout, ``S (B, H, P, N)``."""
    B, T, d = x.shape
    H, G = dt.shape[-1], Bm.shape[2]
    P, rep = d // H, dt.shape[-1] // Bm.shape[2]
    S = np.asarray(h0, np.float64).reshape(B, -1, H, P).transpose(0, 2, 3, 1)      # (B, N, d) -> (B, H, P, N)
    x, dt, Bm, Cm, A = (np.asarray(a, np.float64) for a in (x, dt, Bm, Cm, A))
    ys = []
    for t in range(T):
        b, c = np.repeat(Bm[:, t], rep, axis=1), np.repeat(Cm[:, t], rep, axis=1)     # (B, H, N)
        S = (np.exp(dt[:, t] * A)[..., None, None] * S
             + (dt[:, t, :, None] * x[:, t].reshape(B, H, P))[..., None] * b[:, :, None, :])
        ys.append(np.einsum("bhpn,bhn->bhp", S, c).reshape(B, d))
    return np.stack(ys, 1), S.transpose(0, 3, 1, 2).reshape(B, -1, d)


@pytest.mark.parametrize("T,P,from_state", [(128, 64, False), (256, 64, True), (384, 128, True), (128, 32, True)],
                         ids=["one_chunk", "two_chunks_from_a_state", "wide_heads", "four_heads_a_tile"])
def test_the_chunked_scan_is_the_recurrence(T, P, from_state, monkeypatch):
    """(c): ``ssd_chunk_fwd`` interpreted, and the XLA form, against the
    token-by-token float32 recurrence: the outputs and the last state, from zeros
    and from a state; padded tokens (dt = 0) leave the state as it was."""
    monkeypatch.setenv("THUNDER_TPU_PALLAS_INTERPRET", "1")
    H = 4 if P != 32 else 8
    x, dt, Bm, Cm, A = _scan_operands(T, H=H, P=P, seed=T + P)
    dt = dt.at[:, T - 20:].set(0.0)
    h0 = (jnp.asarray(np.random.default_rng(1).standard_normal((2, 16, H * P)), jnp.float32) if from_state
          else jnp.zeros((2, 16, H * P), jnp.float32))
    want_y, want_S = _recurrence(x, dt, Bm, Cm, A, h0)
    _, S_before_pad = _recurrence(x[:, :T - 20], dt[:, :T - 20], Bm[:, :T - 20], Cm[:, :T - 20], A, h0)
    np.testing.assert_allclose(want_S, S_before_pad, rtol=1e-12)
    before = px.stats.get("ssd_chunk", 0)
    y, S = px.ssd_chunk(x, dt, Bm, Cm, A, h0)
    assert px.stats["ssd_chunk"] == before + 1 and px.ssd_schedule["block_tokens"] in (128, 256, 384)
    y2, S2 = px.ssd_scan_xla(x, dt, Bm, Cm, A, h0)
    for got_y, got_S in ((y, S), (y2, S2)):
        assert rel(got_y, want_y) < 2e-5 and rel(got_S, want_S) < 2e-5
    # a sequence that is no whole chunk: the XLA form, unclaimed
    px.ssd_chunk(x[:, :100], dt[:, :100], Bm[:, :100], Cm[:, :100], A, h0)
    assert px.stats["ssd_chunk"] == before + 1


def test_the_decode_step_is_the_recurrence_in_place(monkeypatch):
    """(c): ``ssd_decode_step`` interpreted against one step of the recurrence:
    the rows' slots of the arena updated, every other slot and layer as it was."""
    monkeypatch.setenv("THUNDER_TPU_PALLAS_INTERPRET", "1")
    H, P, G, N, rows = 4, 64, 2, 16, 3
    rng = np.random.default_rng(3)
    arena = jnp.asarray(rng.standard_normal((6, 2, N, H * P)), jnp.float32)
    slots = jnp.asarray([4, 1, 0], jnp.int32)
    x, dt, Bm, Cm, A = _scan_operands(1, seed=4, B=rows)
    before = px.stats.get("ssd_decode", 0)
    y, out = px.ssd_decode_step(arena, slots, x[:, 0], dt[:, 0], Bm[:, 0], Cm[:, 0], A, layer=1)
    assert px.stats["ssd_decode"] == before + 1
    y2, out2 = px.ssd_decode_step_xla(arena, slots, x[:, 0], dt[:, 0], Bm[:, 0], Cm[:, 0], A, layer=1)
    want_y, want_S = _recurrence(x, dt, Bm, Cm, A, arena[slots, 1])
    for got_y, got in ((y, out), (y2, out2)):
        assert rel(got_y, want_y[:, 0]) < 1e-6 and rel(got[slots, 1], want_S) < 1e-6
        untouched = np.ones(6, bool)
        untouched[np.asarray(slots)] = False
        np.testing.assert_array_equal(got[untouched], arena[untouched])
        np.testing.assert_array_equal(got[:, 0], arena[:, 0])


def test_the_four_shares_of_an_expert_layer_add_up_to_the_uncut_layer():
    """(d): the parts of the routed sum that experts 0-3, 4-7, 8-11 and 12-15 give,
    with the two latent projections and the shared expert counted once, are the
    uncut reference layer's result; the program's share is its reference's."""
    rng = np.random.default_rng(11)
    u = jnp.asarray(rng.standard_normal((24, 64)), jnp.float32)
    whole_hf = {**HF, "n_routed_experts": 16, "expert_first": 0}
    with jax.default_matmul_precision("highest"):
        whole = arch.make_params(whole_hf, common.seed_words(5), dtype=jnp.float32)["blocks"][1]["mlp"]
        want = arch._experts(u, whole, arch.sizes(whole_hf))
        latent = 0.0
        for first in (0, 4, 8, 12):
            hf = {**HF, "expert_first": first}
            cfg, params = model(expert_first=first)
            mp = params["blocks"][1]["mlp"]
            np.testing.assert_array_equal(mp["fc_1"], whole["fc_1"][first * 48:(first + 4) * 48])   # expert e whatever the share
            np.testing.assert_array_equal(mp["latent_up"], whole["latent_up"])
            part = arch.routed_latent(u, mp, arch.sizes(hf))
            latent = latent + part
            # the program's share of this layer: its routed part through W_up, and the shared expert
            got = G.moe_share_mlp(mp, u[None], cfg)[0]
            np.testing.assert_allclose(got, arch._lin(part, mp["latent_up"]) + arch.shared_expert(u, mp), atol=2e-5)
        total = arch._lin(latent, whole["latent_up"]) + arch.shared_expert(u, whole)
    np.testing.assert_allclose(total, want, atol=2e-5)
    assert float(jnp.abs(want).max()) > 0.1


def test_the_router_chooses_on_score_plus_bias_and_weighs_by_score_alone():
    """(e): 22 of 512 (here 5 of 16) chosen on ``sigmoid + bias``; the weights the
    chosen scores over their sum, scaled by 5; the bias moves a choice and never a
    weight; the reference's router is the program's."""
    cfg, params = model()
    mp = params["blocks"][1]["mlp"]
    u = jnp.asarray(np.random.default_rng(12).standard_normal((40, 64)), jnp.float32)
    scores = jax.nn.sigmoid(u @ mp["gate"].T)
    w, idx = G.route_sigmoid_bias(scores, mp["expert_bias"], cfg)
    assert idx.shape == (40, 5) and cfg.n_expert == 16 and cfg.routed_scaling_factor == 5.0
    np.testing.assert_array_equal(np.sort(idx, -1), np.sort(np.argsort(-(scores + mp["expert_bias"]), -1)[:, :5], -1))
    chosen = np.take_along_axis(np.asarray(scores), np.asarray(idx), -1)
    np.testing.assert_allclose(w, 5.0 * chosen / (chosen.sum(-1, keepdims=True) + 1e-6), rtol=1e-6)
    np.testing.assert_allclose(np.asarray(w).sum(-1), 5.0, rtol=1e-4)
    unbiased = np.sort(np.argsort(-np.asarray(scores), -1)[:, :5], -1)
    assert (np.sort(idx, -1) != unbiased).any()                                       # the bias moved some choice
    rw, ridx = arch.route(u, mp["gate"], mp["expert_bias"], arch.sizes(HF))
    np.testing.assert_array_equal(ridx, idx)
    np.testing.assert_allclose(rw, w, rtol=1e-6)


def test_the_ungated_share_is_the_gated_shares_plan_and_products(monkeypatch):
    """The two-matrix form is an argument of the one sorted-rows path: the same
    plan, the same grouped products (``moe_grouped_mm`` claimed, two products a
    wave), and the plan's counts are what ``stats()["moe"]`` sums."""
    monkeypatch.setenv("THUNDER_TPU_PALLAS_INTERPRET", "1")
    rng = np.random.default_rng(13)
    x = jnp.asarray(rng.standard_normal((32, 48)), jnp.float32)
    idx = jnp.asarray(rng.integers(0, 16, (32, 5)), jnp.int32)
    w = jnp.asarray(rng.uniform(0.1, 1, (32, 5)), jnp.float32)
    fc_1 = jnp.asarray(rng.standard_normal((4, 48, 32)), jnp.float32) * 0.2
    proj = jnp.asarray(rng.standard_normal((4, 32, 48)), jnp.float32) * 0.2
    before = px.stats.get("grouped_mm", 0)
    y, plan = jaxex._moe_share_planned(x, idx, w, fc_1, None, proj, 4, 16, 16)
    assert px.stats.get("grouped_mm", 0) >= before + 2
    want = np.zeros((32, 48))
    for e in range(4):
        we = np.where(np.asarray(idx) == 4 + e, np.asarray(w), 0).sum(-1)
        want += we[:, None] * (np.square(np.maximum(np.asarray(x) @ np.asarray(fc_1[e]), 0)) @ np.asarray(proj[e]))
    np.testing.assert_allclose(y, want, atol=1e-4)
    np.testing.assert_array_equal(plan["cnt"], [(np.asarray(idx) == 4 + e).sum() for e in range(4)])


def test_stats_count_the_rows_that_land_on_held_experts():
    cfg, params = model()
    eng = tt.serve(None, params, cfg, **ENGINE)
    moe = eng.stats()["moe"]
    assert moe["expert_rows_per_step"] == {"mean": None, "spread": None} and moe["experts_hit_share"] is None
    served(eng, [prompt(40, 6), prompt(23, 7)], [12, 12])
    moe = eng.stats()["moe"]
    steps, rows, sq, hit = moe["row_sums"]
    assert steps == eng.stats()["decode_steps"] > 0 and moe["experts_held"] == 4 and moe["experts_published"] == 16
    # four rows a step (the batch bucket), five choices each over 16 experts, four held: about 5 land
    assert 0 < moe["expert_rows_per_step"]["mean"] == rows / steps <= 20 and 0 < moe["experts_hit_share"] <= 1
    assert moe["expert_rows_per_step"]["spread"] == pytest.approx(max(sq / steps - (rows / steps) ** 2, 0) ** 0.5)
    eng.shutdown(drain=False)


def test_what_the_kinds_cannot_do_yet_is_refused_by_name():
    cfg, params = model()
    for option, word in [(dict(prefix_sharing=True), "prefix_sharing"), (dict(sessions=True), "sessions"),
                         (dict(speculative=object()), "speculative"), (dict(mesh=object()), "mesh"),
                         (dict(prefill_chunk=32), "prefill_chunk"),
                         (dict(priorities=True), "priorities"), (dict(fault_plan=object()), "fault_plan"),
                         (dict(lora=object()), "lora")]:
        assert word in hybrid_unsupported(cfg, **option), option
    assert hybrid_unsupported(cfg) is None and hybrid_unsupported(cfg, kv_dtype="fp8") is None
    with pytest.raises(NotImplementedError, match="mamba2 layers.*prefill_chunk"):
        tt.serve(None, params, cfg, prefill_chunk=32, **ENGINE)
    eng = tt.serve(None, params, cfg, **ENGINE)
    from thunder_tpu.serving.paged_attention import forward_paged
    cos, sin = llama.build_rope_cache(cfg, 64)
    with pytest.raises(NotImplementedError, match="one token a row"):
        forward_paged(params, jnp.zeros((1, 8), jnp.int32), jnp.zeros((1,), jnp.int32), eng.pool.arenas,
                      jnp.zeros((1, 8), jnp.int32), cos, sin, cfg, cdtype=jnp.float32,
                      sslots=jnp.ones((1,), jnp.int32))
    eng.shutdown(drain=False)


def test_an_fp8_arena_and_a_bfloat16_state_are_told_from_the_program(monkeypatch):
    """The two storage controls the cell's check plants, at this size: the K/V
    of an fp8 arena and a state kept in bfloat16 read tens of times the
    program's own error against the reference."""
    cfg, params = model()
    from thunder_tpu.serving.kv_pool import StatePool

    def held_errors(**kw):
        eng = tt.serve(None, params, cfg, **{**ENGINE, **kw})
        h = eng.submit(prompt(60, 14), max_new_tokens=40)
        while len(h.tokens_so_far()) < 30:
            eng.step()
        held, r = jax.device_get(eng.held(h)), h._req
        n = held["tokens"]
        seq = np.concatenate([r.prompt, np.asarray(r.generated, np.int32)])[:n]
        with jax.default_matmul_precision("highest"):
            want = [ref for _, ref in arch.ref_caches(HF, params, padded(seq), n) if ref is not None]
        eng.shutdown(drain=False)
        return rel(held["state"][0].T, want[0][0]), rel(held["k"][0], want[2][0])

    state, kv = held_errors()
    assert state < 1e-4 and kv < 1e-4
    assert held_errors(kv_dtype="fp8")[1] > 20 * max(kv, 1e-4)
    monkeypatch.setattr(StatePool, "STATE_DTYPE", jnp.bfloat16)
    assert held_errors()[0] > 20 * max(state, 1e-5)
