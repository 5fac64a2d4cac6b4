"""A decoder-hybrid-decoder (Phi-4-mini-flash's kinds: selective scan, differential
attention over a window of its own, one global layer, gated memory units, cross
attention on the global layer's K and V) in ``models.generate`` and through
``tt.serve``, at tiny widths in float32 on seeded weights, against the benchmark's
plain reference (``chipbench/models/sambay_decoder.py``, which imports nothing of
the program) and against solo ``generate()``.

Tolerances: the program and the reference compute the same float32 sums in
different orders (a scan a token at a time on both sides; attention through a
masked softmax on both), so logits agree to 1e-4 of a spread of ~7 and held
arrays to 1e-4 relative; a bfloat16 state (2^-9 relative) or an fp8 K/V (2^-4)
would read hundreds of times that, and the tests that plant them say so.
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
from thunder_tpu.executors import pallasex as px
from thunder_tpu.models import generate as G
from thunder_tpu.models import llama
from thunder_tpu.serving import kv_pool
from thunder_tpu.serving.engine import hybrid_unsupported

arch = common.load_module("models", "sambay_decoder")

HF = dict(model_name="tiny-sambay", hidden_size=64, intermediate_size=128, layer_norm_eps=1e-5,
          max_position_embeddings=512, mb_per_layer=2, num_attention_heads=4, num_hidden_layers=8,
          num_key_value_heads=2, head_dim=64, sliding_window=16, vocab_size=256, initializer_range=0.2,
          mamba_dt_rank=4)
KINDS = ("ssm", "sliding_attention", "ssm", "sliding_attention", "ssm", "full_attention", "gmu", "cross_attention")
BS = 8                      # the pool's block: a window of 16 is two blocks, a ring three
ENGINE = dict(block_size=BS, num_blocks=64, max_batch=4, prefill_buckets=[32, 64, 96], cache_dtype=jnp.float32)


@functools.cache
def model():
    cfg = llama.Config(**arch.program_config(HF))
    with jax.default_matmul_precision("highest"):
        params = arch.make_params(HF, common.seed_words(5), dtype=jnp.float32)
    return cfg, params


def prompt(n: int, seed: int = 0) -> np.ndarray:
    return np.random.default_rng(seed).integers(0, HF["vocab_size"], (n,)).astype(np.int32)


def dense_forward(cfg, params, toks, T_max=128, **kw):
    cos, sin = llama.build_rope_cache(cfg, T_max)
    cache = G.init_cache(cfg, 1, T_max, jnp.float32)
    return compiled_forward(cfg, **kw)(params, jnp.asarray(toks)[None], cache, cos, sin)


# the reference as it is, a layer's own compiled calls inside one compiled call: one program a length
ref_logits = jax.jit(functools.partial(arch.ref_logits, HF))


def rel(got, want) -> float:
    got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
    return float(np.sqrt(np.sum((got - want) ** 2) / np.sum(want ** 2)))


def test_the_config_names_the_kinds_and_what_each_keeps():
    cfg, _ = model()
    assert cfg.layer_types == KINDS and cfg.hybrid_decoder and cfg.layer_window == 16 and cfg.sliding_window is None
    assert cfg.kv_layers == (1, 3, 5) and cfg.ring_layers == (1, 3) and cfg.paged_kv_layers == (5,)
    assert cfg.ssm_layers == cfg.state_layers == (0, 2, 4) and cfg.cross_from == 5 and cfg.gmu_source == 4
    assert G.state_shapes(cfg, 3) == {"conv": (3, 3, 3, 128), "state": (3, 3, 16, 128)}
    assert G.kv_block_shape(cfg, BS, 2) == (1, 1, BS, 128) and G.ring_block_shape(cfg, BS, 2) == (2, 1, BS, 128)
    assert G.ring_blocks(cfg, BS) == 3 and G.ring_blocks(llama.Config(n_layer=2, n_head=2, n_embd=32), BS) == 0
    assert "decoder-hybrid-decoder" in llama.serving_only(cfg)
    with pytest.raises(AssertionError, match="layer_window"):
        llama.Config(**{**arch.program_config(HF), "layer_window": None})
    with pytest.raises(AssertionError, match="model-wide"):
        llama.Config(**{**arch.program_config(HF), "sliding_window": 16})


def test_init_params_has_the_layout_the_reference_states():
    cfg, params = model()
    ours = llama.init_params(cfg, dtype=jnp.float32)
    for mine, theirs in zip(ours["blocks"], params["blocks"]):
        for group in ("ssm", "gmu", "attn", "mlp"):
            assert (group in mine) == (group in theirs)
            if group in mine:       # the reference's leaves beyond ours are its biases
                assert {k: v.shape for k, v in mine[group].items()}.items() <= {
                    k: v.shape for k, v in theirs[group].items()}.items()


@pytest.mark.parametrize("T", [40, 100])
def test_every_kind_agrees_with_the_reference(T):
    """The full forward's logits, and what each layer that keeps something holds
    after 77 tokens (contexts of several windows), layer by layer and kind by kind."""
    cfg, params = model()
    toks = prompt(T)
    with jax.default_matmul_precision("highest"):
        logits, _ = dense_forward(cfg, params, toks)
        want = ref_logits(params, jnp.asarray(toks), jnp.arange(T))
        assert float(jnp.abs(logits[0] - want).max()) < 1e-4 * float(jnp.abs(want).max())
        n = min(77, T - 3)
        _, cache = dense_forward(cfg, params, toks[:n])
        held = arch.ref_caches(HF, params, jnp.asarray(np.pad(toks, (0, 128 - T))), n)
    assert [k for k, _ in held] == list(KINDS)
    js = jk = 0
    for kind, ref in held:
        if kind == "ssm":
            assert rel(cache["state"][js, 0].T, ref[0]) < 1e-4 and rel(cache["conv"][js, 0], ref[1]) < 1e-4
            js += 1
        elif ref is not None:
            lo = n - ref[0].shape[1]
            assert lo == (max(0, n - 16) if kind == "sliding_attention" else 0)
            assert rel(cache["k"][jk, 0][:, lo:n], ref[0]) < 1e-4 and rel(cache["v"][jk, 0][:, lo:n], ref[1]) < 1e-4
            jk += 1


def test_a_prompts_cross_half_on_one_row_is_the_full_forwards_row():
    cfg, params = model()
    toks = prompt(50, 3)
    full, c_full = dense_forward(cfg, params, toks)
    for row in (49, 17):
        one, c_one = dense_forward(cfg, params, toks, logits_at=row)
        assert one.shape == (1, 1, HF["vocab_size"])
        np.testing.assert_allclose(one[0, 0], full[0, row], atol=2e-5)
        for name in c_full:     # the caches are the self-decoder's and the global layer's: every row's, both ways
            np.testing.assert_array_equal(c_one[name], c_full[name])


def test_a_padded_prompt_leaves_the_state_at_its_last_real_token():
    cfg, params = model()
    toks = prompt(64, 4)
    _, want = dense_forward(cfg, params, toks[:41])
    _, got = dense_forward(cfg, params, toks, n_real=41, logits_at=40)
    np.testing.assert_allclose(got["state"], want["state"], atol=5e-5)
    np.testing.assert_allclose(got["conv"], want["conv"], atol=5e-5)


def test_prefill_then_decode_through_the_dense_cache_is_the_full_forward():
    cfg, params = model()
    toks = prompt(100, 5)
    full, _ = dense_forward(cfg, params, toks)
    cos, sin = llama.build_rope_cache(cfg, 128)
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


@pytest.mark.parametrize("kernels", [False, True], ids=["xla_form", "interpreted_kernels"])
def test_served_tokens_are_solo_generates_and_the_references_best(kernels, monkeypatch):
    """Three requests through the pools together: whole-prompt prefills (the cross
    half on a row), then decode through the rings (window 16 = two blocks: a
    sequence of 100 tokens overwrites its ring's three blocks four times), the
    global layer's blocks and the slots; bit for bit solo ``generate()``, and at
    every position the reference's best token."""
    if kernels:
        monkeypatch.setenv("THUNDER_TPU_PALLAS_INTERPRET", "1")
    cfg, params = model()
    eng = tt.serve(None, params, cfg, **ENGINE)
    st = eng.stats()["attn"]
    assert st["path"] == ("walk" if kernels else "xla") and st["lane_pack"] == 2 and st["shared_kv_layers"] == 2
    prompts, new = [prompt(40, 6), prompt(23, 7), prompt(64, 8)], [60, 50, 36]
    before = dict(px.stats)
    got = served(eng, prompts, new)
    assert (px.stats.get("ssm_decode", 0) > before.get("ssm_decode", 0)) == kernels
    assert eng.stats()["attn"]["prefill_cross_rows"] == 3
    assert eng.stats()["compile_counts"]["prefill_fresh"] >= 1 and eng.stats()["compile_counts"]["decode_paged"] >= 1
    for p, n, toks in zip(prompts, new, got):
        solo = np.asarray(G.generate(params, p[None], cfg, n, T_max=128))[0, len(p):]
        np.testing.assert_array_equal(toks, solo)
        seq = np.concatenate([p, toks])
        with jax.default_matmul_precision("highest"):
            lg = ref_logits(params, jnp.asarray(np.pad(seq, (0, 128 - len(seq)))), jnp.arange(len(p) - 1, len(seq) - 1))
        short = np.asarray(jnp.max(lg, axis=-1) - jnp.take_along_axis(lg, jnp.asarray(toks)[:, None], axis=-1)[:, 0])
        assert float(short.max()) < 1e-3
    eng.shutdown(drain=False)


def test_the_allocator_keeps_a_table_and_a_reservation_a_layer_kind():
    """The window kind holds a ring of ``ceil(W / bs) + 1`` blocks a request whatever
    its length, with its state slot; the global kind its whole length; both go back
    at finish; and what another request's ring, blocks or slot hold is not this one's."""
    cfg, params = model()
    eng = tt.serve(None, params, cfg, **ENGINE)
    pool, state = eng.pool, eng.pool.state
    assert state.ring_blocks == 3 == -(-16 // BS) + 1
    assert state.shapes["k_ring"] == ((4 + 1) * 3, 2, 1, BS, 128) and pool.k_arena.shape == (64, 1, 1, BS, 128)
    free0 = pool.num_free
    a = eng.submit(prompt(40, 9), max_new_tokens=60)
    b = eng.submit(prompt(90, 10), max_new_tokens=20)
    while min(len(a.tokens_so_far()), len(b.tokens_so_far())) < 12:
        eng.step()
    ra, rb = a._req, b._req
    assert len(ra.block_table) == -(-100 // BS) and len(rb.block_table) == -(-110 // BS)       # the whole length
    assert free0 - pool.num_free == len(ra.block_table) + len(rb.block_table)
    assert state.leased == 2 and ra.state_slot != rb.state_slot
    occ = eng.stats()["pool_occupancy"]["state"]
    assert occ["ring_blocks"] == 3 and occ["ring_fill_frac"] == 0.5 and occ["ring_arena_bytes"] == 2 * 15 * 2 * BS * 128 * 4
    tabs = np.asarray(kv_pool.ring_tables(jnp.asarray([ra.state_slot, rb.state_slot]), 3, 14))
    assert set(tabs[0]) == {3 * ra.state_slot + i for i in range(3)} and not set(tabs[0]) & set(tabs[1])
    assert (tabs[0][:6] == tabs[0][[0, 1, 2, 0, 1, 2]]).all()                                   # block i in entry i % 3
    # what is held, by kind, against the reference; and against the other request's, which reads about 1
    helds = {}
    for h in (a, b):
        held, r = jax.device_get(eng.held(h)), h._req
        n = held["tokens"]
        seq = np.concatenate([r.prompt, np.asarray(r.generated, np.int32)])[:n]
        with jax.default_matmul_precision("highest"):
            want = arch.ref_caches(HF, params, jnp.asarray(np.pad(seq, (0, 128 - n))), n)
        helds[h] = (held, want)
        assert held["k"].shape == (1, 2, n, 64) and held["k_ring"].shape == (2, 2, 16, 64)
        assert held["state"].shape == (3, 16, 128) and held["conv"].shape == (3, 3, 128)
        seen = {"ssm": 0, "sliding_attention": 0, "full_attention": 0}
        for kind, ref in want:
            if ref is None:
                continue
            j = seen[kind]
            seen[kind] += 1
            if kind == "ssm":
                assert rel(held["state"][j].T, ref[0]) < 1e-4 and rel(held["conv"][j], ref[1]) < 1e-4
            elif kind == "sliding_attention":
                assert rel(held["k_ring"][j], ref[0]) < 1e-4 and rel(held["v_ring"][j], ref[1]) < 1e-4
            else:
                assert rel(held["k"][j], ref[0]) < 1e-4 and rel(held["v"][j], ref[1]) < 1e-4
    (held_a, _), (_, want_b) = helds[a], helds[b]
    first = {kind: ref for kind, ref in reversed(want_b) if ref is not None}
    assert rel(held_a["state"][0].T, first["ssm"][0]) > 0.5
    assert rel(held_a["k_ring"][0], first["sliding_attention"][0]) > 0.5
    while not (a.done() and b.done()):
        eng.step()
    assert pool.num_free == free0 and state.leased == 0
    eng.shutdown(drain=False)


def test_what_the_kinds_cannot_do_yet_is_refused_by_name():
    cfg, params = model()
    for option, word in [(dict(prefix_sharing=True), "prefix_sharing"), (dict(sessions=True), "sessions"),
                         (dict(speculative=object()), "speculative"), (dict(mesh=object()), "mesh"),
                         (dict(kv_dtype="fp8"), "kv_dtype"),
                         (dict(prefill_chunk=32), "prefill_chunk"), (dict(priorities=True), "priorities"),
                         (dict(fault_plan=object()), "fault_plan"),
                         (dict(lora=object()), "lora")]:
        assert word in hybrid_unsupported(cfg, **option), option
    assert hybrid_unsupported(cfg) is None          # a window of a layer kind beside a state: served
    with pytest.raises(TypeError, match="unexpected keyword argument 'attn'"):      # one decode program a job
        tt.serve(None, params, cfg, attn="gather", **ENGINE)
    with pytest.raises(NotImplementedError, match="kv_dtype"):
        tt.serve(None, params, cfg, kv_dtype="int8", **ENGINE)
    eng = tt.serve(None, params, cfg, **ENGINE)
    with pytest.raises(NotImplementedError, match="per-kind caches"):
        eng._program("prefill_chunk", 32, 8)
    eng.shutdown(drain=False)
    # the model-wide window beside a state stays refused
    olmo_like = llama.Config(n_layer=2, n_head=2, n_embd=32, layer_types=("linear_attention", "full_attention"),
                             linear_num_key_heads=1, linear_num_value_heads=1, linear_key_head_dim=16,
                             linear_value_head_dim=16, sliding_window=16)
    assert "sliding window" in hybrid_unsupported(olmo_like)
