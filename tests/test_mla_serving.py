"""A latent-attention model with an expert share, served: the program against
the benchmark's plain reference (``chipbench/models/latent_moe_decoder.py``)
at tiny widths in float32, seeded.

What is held: ``forward_with_cache`` (a whole prompt, a later piece, a token)
and the engine (whole-prompt prefill, then decode through the paged latents,
requests of different lengths together) against the reference's logits;
absorbed against expanded attention; the YaRN tables and the group-limited
router against closed forms; the decode kernel interpreted against its XLA
form (the walk's own cases are ``tests/test_mla_walk.py``); the expert shares adding up to the uncut layer, the shared expert counted
once; what the engine holds of a request; what is refused, with its reason.
"""
from __future__ import annotations

import dataclasses
import math
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import thunder_tpu as tt  # noqa: E402
from chipbench import common  # noqa: E402
from conftest import compiled_forward  # noqa: E402
from thunder_tpu.executors import pallasex as px  # noqa: E402
from thunder_tpu.models import generate as G  # noqa: E402
from thunder_tpu.models import llama  # noqa: E402
from thunder_tpu.serving import engine as engine_mod  # noqa: E402

arch = common.load_module("models", "latent_moe_decoder")

TINY = {
    "model_name": "tiny-latent-moe", "hidden_size": 64, "num_attention_heads": 4, "num_hidden_layers": 3,
    "vocab_size": 256, "max_position_embeddings": 512, "q_lora_rank": 32, "kv_lora_rank": 128,
    "qk_nope_head_dim": 16, "qk_rope_head_dim": 8, "v_head_dim": 16, "intermediate_size": 96,
    "moe_intermediate_size": 32, "n_shared_experts": 1, "n_routed_experts": 4, "published_n_routed_experts": 16,
    "expert_first": 4, "num_experts_per_tok": 4, "n_group": 4, "topk_group": 2, "routed_scaling_factor": 2.5,
    "first_k_dense_replace": 1, "rope_theta": 10000,
    "rope_scaling": {"beta_fast": 32, "beta_slow": 1, "factor": 32, "mscale": 1, "mscale_all_dim": 1,
                     "original_max_position_embeddings": 64, "type": "yarn"},
    "rms_norm_eps": 1e-6, "initializer_range": 0.2,
}
NEW = 10
LENGTHS = (40, 17, 33, 5)


def tokens(n, seed=0):
    return np.random.default_rng(seed).integers(0, TINY["vocab_size"], (n,)).astype(np.int32)


def tiny_model(hf=TINY):
    cfg = llama.Config(**arch.program_config(hf))
    params = arch.make_params(hf, common.seed_words(5), dtype=jnp.float32)
    # norms off their initial value: a dropped weight shows
    leaves, tree = jax.tree_util.tree_flatten(params)
    keys = jax.random.split(jax.random.PRNGKey(7), len(leaves))
    leaves = [x + 0.1 * jax.random.normal(k, x.shape, x.dtype) if x.ndim == 1 else x for x, k in zip(leaves, keys)]
    return cfg, jax.tree_util.tree_unflatten(tree, leaves)


@pytest.fixture(scope="module")
def model():
    return tiny_model()


@pytest.fixture(autouse=True)
def float32_products():
    with jax.default_matmul_precision("highest"):
        yield


def _engine(cfg, params, **kw):
    opts = dict(num_blocks=40, block_size=16, max_batch=4, prefill_buckets=(16, 32, 48))
    return tt.serve(None, params, cfg, **{**opts, **kw})


def _serve(eng, prompts, new=NEW):
    handles = [eng.submit(p, max_new_tokens=new) for p in prompts]
    while not all(h.done() for h in handles):
        eng.step()
    return [np.asarray(h.result(drive=False).new_tokens) for h in handles]


def _ref_next(hf, params, prompt, served):
    """The reference's greedy choice after each position that produced a served token."""
    seq = np.concatenate([prompt, served])
    lg = arch.ref_logits(hf, params, jnp.asarray(seq), jnp.arange(len(prompt) - 1, len(seq) - 1))
    return np.asarray(jnp.argmax(lg, axis=-1)), np.asarray(lg)


# --------------------------------------------------------------------------
# the configuration
# --------------------------------------------------------------------------

def test_init_params_builds_the_layout_the_reference_builds(model):
    cfg, params = model
    own = llama.init_params(cfg, jax.random.PRNGKey(0))
    shapes = lambda t: jax.tree_util.tree_map(lambda a: a.shape, t)  # noqa: E731
    assert shapes(own) == shapes(params)
    assert cfg.latent and cfg.latent_width == 136 and cfg.head_size == 24 and cfg.rope_n_elem == 8
    assert cfg.mlp_dense(0) and not cfg.mlp_dense(1)
    assert G.cache_shape(cfg, 2, 64) == (3, 2, 1, 64, 136)
    assert G.kv_block_shape(cfg, 16) == (3, 1, 16, 256)            # a row padded to whole lane tiles
    assert cfg.training_only is None
    assert arch.param_count(TINY) == sum(x.size for x in jax.tree_util.tree_leaves(params))


def test_yarn_tables_are_the_closed_form(model):
    cfg, _ = model
    r, dr, theta = TINY["rope_scaling"], TINY["qk_rope_head_dim"], TINY["rope_theta"]
    f = np.array([theta ** (-2 * i / dr) for i in range(dr // 2)])
    dim = lambda b: dr * math.log(r["original_max_position_embeddings"] / (2 * math.pi * b)) / (2 * math.log(theta))  # noqa: E731
    low, high = max(math.floor(dim(r["beta_fast"])), 0), min(math.ceil(dim(r["beta_slow"])), dr - 1)
    ramp = np.clip((np.arange(dr // 2) - low) / (high - low), 0, 1)
    inv = f * (1 - ramp) + f / r["factor"] * ramp
    assert 0 < ramp.sum() < dr // 2                                  # some dims keep their frequency, some stretch
    cos, sin = llama.build_rope_cache(cfg, 100)
    ang = np.arange(100)[:, None] * np.concatenate([inv, inv])[None, :]
    np.testing.assert_allclose(np.asarray(cos), np.cos(ang), atol=2e-5)
    np.testing.assert_allclose(np.asarray(sin), np.sin(ang), atol=2e-5)
    m = 0.1 * math.log(r["factor"]) + 1.0
    assert cfg.attn_scale == pytest.approx(24 ** -0.5 * m * m)
    plain = dataclasses.replace(cfg, rope_scaling_yarn=None)
    assert plain.attn_scale == pytest.approx(24 ** -0.5)
    ref_cos, ref_sin = arch.rope_tables(arch.sizes(TINY), 100)
    np.testing.assert_allclose(np.asarray(cos), np.asarray(ref_cos), atol=1e-6)
    # the published A.X-K1 numbers: factor 32 over 4096, 64 rotary dims
    big = llama.Config(n_layer=1, n_head=2, n_embd=32, q_lora_rank=8, kv_lora_rank=16, qk_nope_head_dim=8,
                       qk_rope_head_dim=64, v_head_dim=8,
                       rope_scaling_yarn={"factor": 32, "original_max_position_embeddings": 4096,
                                          "beta_fast": 32, "beta_slow": 1, "mscale": 1, "mscale_all_dim": 1})
    assert big.attn_scale == pytest.approx(72 ** -0.5 * 1.3466 ** 2, rel=1e-4)


def test_the_group_limited_router_is_the_closed_form(model):
    cfg, _ = model
    rng = np.random.default_rng(3)
    scores = rng.uniform(0.05, 0.95, (50, cfg.n_expert)).astype(np.float32)        # no ties at this seed
    top_w, top_idx = G.route_sigmoid_group(jnp.asarray(scores), cfg)
    per = cfg.n_expert // cfg.n_group
    for n in range(50):
        groups = scores[n].reshape(cfg.n_group, per)
        group_score = np.sort(groups, axis=1)[:, -2:].sum(axis=1)
        kept = np.argsort(-group_score)[:cfg.topk_group]
        allowed = np.concatenate([np.arange(g * per, (g + 1) * per) for g in kept])
        want = allowed[np.argsort(-scores[n][allowed])][:cfg.n_expert_per_token]
        assert set(np.asarray(top_idx[n]).tolist()) == set(want.tolist())
        w = scores[n][np.asarray(top_idx[n])]
        np.testing.assert_allclose(np.asarray(top_w[n]), w / w.sum() * cfg.routed_scaling_factor, rtol=1e-6)
    ref_w, ref_idx = arch.route(jnp.asarray(rng.normal(size=(20, 64)), jnp.float32),
                                jnp.asarray(rng.normal(size=(cfg.n_expert, 64)), jnp.float32), arch.sizes(TINY))
    assert ref_idx.shape == (20, cfg.n_expert_per_token) and float(jnp.min(ref_w)) > 0


# --------------------------------------------------------------------------
# the dense cache's forward against the reference
# --------------------------------------------------------------------------

def test_forward_with_cache_gives_the_references_logits(model):
    cfg, params = model
    T, split = 40, 24
    toks = jnp.asarray(tokens(T, 1))
    ref = np.asarray(arch.ref_logits(TINY, params, toks, jnp.arange(T)))
    cos, sin = llama.build_rope_cache(cfg, 64)
    fresh, later = compiled_forward(cfg), compiled_forward(cfg, decode=True)
    whole, _ = fresh(params, toks[None], G.init_cache(cfg, 1, 64, jnp.float32), cos, sin)
    np.testing.assert_allclose(np.asarray(whole[0]), ref, atol=2e-4)
    # a first piece, a later piece at a traced position (expanded over the cache), then tokens (absorbed)
    _, cache = fresh(params, toks[None, :16], G.init_cache(cfg, 1, 64, jnp.float32), cos, sin)
    piece, cache = later(params, toks[None, 16:split], jnp.int32(16), cache, cos, sin)
    np.testing.assert_allclose(np.asarray(piece[0]), ref[16:split], atol=2e-4)
    for t in range(split, T):
        one, cache = later(params, toks[None, t:t + 1], jnp.asarray([t], jnp.int32), cache, cos, sin)
        np.testing.assert_allclose(np.asarray(one[0, 0]), ref[t], atol=2e-4)
    want = np.asarray(next(iter(arch.ref_latents(TINY, params, toks, T))))
    np.testing.assert_allclose(np.asarray(cache["latent"][0, 0, 0, :T]), want, atol=2e-5)


def test_absorbed_attention_is_expanded_attention(model):
    cfg, params = model
    ap = params["blocks"][1]["attn"]
    rng = np.random.default_rng(4)
    B, S, nh = 2, 19, cfg.n_head
    latents = jnp.asarray(rng.normal(size=(B, S, cfg.latent_width)), jnp.float32)
    q_nope = jnp.asarray(rng.normal(size=(B, nh, 1, cfg.qk_nope_head_dim)), jnp.float32)
    q_rope = jnp.asarray(rng.normal(size=(B, nh, 1, cfg.qk_rope_head_dim)), jnp.float32)
    keep = (jnp.arange(S)[None, :] <= jnp.asarray([18, 7])[:, None])[:, None, None, :]
    absorbed = G.mla_unabsorb(ap, G.mla_attend_latents(G.mla_absorb(ap, q_nope, q_rope, cfg), latents, keep, cfg), cfg)
    k, v = G.mla_expand(ap, latents, cfg)
    s = jnp.einsum("bhqd,bhkd->bhqk", jnp.concatenate([q_nope, q_rope], -1), k) * cfg.attn_scale
    expanded = jnp.einsum("bhqk,bhkd->bhqd", jax.nn.softmax(jnp.where(keep, s, -jnp.inf), -1), v)
    np.testing.assert_allclose(np.asarray(absorbed), np.asarray(expanded), atol=1e-5)
    # rows as wide as the arena's: the padding scores nothing
    wide = jnp.pad(latents, ((0, 0), (0, 0), (0, 256 - cfg.latent_width)))
    padded = G.mla_unabsorb(ap, G.mla_attend_latents(G.mla_absorb(ap, q_nope, q_rope, cfg, 256), wide, keep, cfg), cfg)
    np.testing.assert_allclose(np.asarray(padded), np.asarray(absorbed), atol=1e-6)


def test_the_shares_add_up_to_the_uncut_layer(model):
    """Every share's part of an expert layer, the shared expert counted once,
    is the layer with all experts held: in the program and in the reference."""
    cfg, _ = model
    whole_hf = {**TINY, "n_routed_experts": 16, "expert_first": 0}
    whole_params = arch.make_params(whole_hf, common.seed_words(5), dtype=jnp.float32)
    whole_cfg = llama.Config(**arch.program_config(whole_hf))
    x = jnp.asarray(np.random.default_rng(6).normal(size=(1, 37, 64)), jnp.float32)
    mp = whole_params["blocks"][1]["mlp"]
    uncut = G.moe_share_mlp(mp, x, whole_cfg)
    np.testing.assert_allclose(np.asarray(uncut[0]), np.asarray(arch._expert_share(x[0], mp, arch.sizes(whole_hf))),
                               atol=2e-5)
    sp = mp["shared"]
    shared = (jax.nn.silu(x @ sp["fc_1"].T) * (x @ sp["fc_2"].T)) @ sp["proj"].T
    total = shared
    for first in range(0, 16, 4):
        hf = {**TINY, "expert_first": first}
        part = arch.make_params(hf, common.seed_words(5), dtype=jnp.float32)["blocks"][1]["mlp"]
        # an expert's weights follow from its number, whatever the share
        np.testing.assert_array_equal(np.asarray(part["fc_1"]), np.asarray(mp["fc_1"][first * 64:(first + 4) * 64]))
        share = G.moe_share_mlp(part, x, llama.Config(**arch.program_config(hf)))
        np.testing.assert_allclose(np.asarray(share[0]), np.asarray(arch._expert_share(x[0], part, arch.sizes(hf))),
                                   atol=2e-5)
        total = total + (share - shared)
    np.testing.assert_allclose(np.asarray(total), np.asarray(uncut), atol=5e-5)
    assert float(jnp.max(jnp.abs(uncut - shared))) > 0.01          # the routed experts add something


# --------------------------------------------------------------------------
# the engine
# --------------------------------------------------------------------------

@pytest.fixture(scope="module")
def prompts():
    return [tokens(n, 10 + i) for i, n in enumerate(LENGTHS)]


@pytest.fixture(scope="module")
def solo(model, prompts):
    cfg, params = model
    with jax.default_matmul_precision("highest"):
        return [np.asarray(G.generate(params, p[None], cfg, NEW))[0, len(p):] for p in prompts]


@pytest.fixture(scope="module")
def served(model, prompts):
    cfg, params = model
    with jax.default_matmul_precision("highest"):
        eng = _engine(cfg, params)
        out = _serve(eng, prompts)
    return eng, out


def test_served_together_is_the_references_greedy_choice(model, prompts, served):
    """Whole-prompt prefill, then decode through the paged latents, four
    requests of different lengths in one batch: every served token is the
    reference's best at its position, the served sequence teacher-forced."""
    _, params = model
    eng, out = served
    for p, got in zip(prompts, out):
        best, lg = _ref_next(TINY, params, p, got)
        top2 = np.sort(lg, axis=-1)[:, -2:]
        assert float(np.min(top2[:, 1] - top2[:, 0])) > 1e-4          # no tie decides a token at this seed
        np.testing.assert_array_equal(got, best)
    counts = eng.stats()["compile_counts"]
    assert counts["prefill_fresh"] >= 1 and counts["decode_paged"] >= 1 and "decode" not in counts


def test_served_is_solo_generate(served, solo):
    for got, want in zip(served[1], solo):
        np.testing.assert_array_equal(got, want)


@pytest.mark.parametrize("options", [{"prefill_chunk": 16}, {"async_step": False}], ids=["chunked", "sync"])
def test_the_other_program_kinds_serve_the_same_tokens(model, prompts, solo, options):
    cfg, params = model
    eng = _engine(cfg, params, **options)
    for got, want in zip(_serve(eng, prompts), solo):
        np.testing.assert_array_equal(got, want)
    st = eng.stats()
    if "prefill_chunk" in options:
        assert st["chunk_runs"] > 0 and st["attn"]["chunk"] == "gather"     # a piece attends its expanded keys


def test_a_shared_prefix_is_read_from_the_first_requests_blocks(model):
    cfg, params = model
    base = tokens(48, 30)
    a, b = np.concatenate([base[:32], tokens(7, 31)]), np.concatenate([base[:32], tokens(9, 32)])
    eng = _engine(cfg, params, prefix_sharing=True)
    first = eng.submit(a, max_new_tokens=NEW)
    while not first.tokens_so_far():
        eng.step()
    second = eng.submit(b, max_new_tokens=NEW)
    while not (first.done() and second.done()):
        eng.step()
    assert second._req.n_shared_blocks == 2
    for p, h in ((a, first), (b, second)):
        want = np.asarray(G.generate(params, p[None], cfg, NEW))[0, len(p):]
        np.testing.assert_array_equal(np.asarray(h.result(drive=False).new_tokens), want)


def test_a_sessions_second_turn_reads_the_first_turns_rows(model):
    cfg, params = model
    eng = _engine(cfg, params, sessions=True)
    first = tokens(20, 40)
    turn1 = eng.submit(first, max_new_tokens=6, session_id="chat").result()
    second = np.concatenate([np.asarray(turn1.tokens), tokens(9, 41)])
    turn2 = eng.submit(second, max_new_tokens=NEW, session_id="chat").result()
    want = np.asarray(G.generate(params, second[None], cfg, NEW))[0, len(second):]
    np.testing.assert_array_equal(np.asarray(turn2.new_tokens), want)
    assert eng.stats()["sessions"]["reattach_hits"] == 1


def test_a_recovery_rebuilds_the_latent_arena(model, prompts, solo):
    cfg, params = model
    eng = _engine(cfg, params)
    handles = [eng.submit(p, max_new_tokens=NEW) for p in prompts[:3]]
    for _ in range(4):
        eng.step()
    eng.recover()                                                  # the arena zeroed, then replayed
    while not all(h.done() for h in handles):
        eng.step()
    for h, want in zip(handles, solo):
        np.testing.assert_array_equal(np.asarray(h.result(drive=False).new_tokens), want)
    assert eng.stats()["recoveries"] == 1


def test_held_is_the_references_latents(model, prompts):
    cfg, params = model
    eng = _engine(cfg, params)
    handles = [eng.submit(p, max_new_tokens=NEW) for p in prompts[:3]]
    while min(len(h.tokens_so_far()) for h in handles) < 4:
        eng.step()
    for p, h in zip(prompts, handles):
        held = eng.held(h)
        n = held["tokens"]
        assert set(held) == {"tokens", "latent"} and held["latent"].shape == (cfg.n_layer, n, cfg.latent_width)
        fed = np.concatenate([p, np.asarray(h.tokens_so_far(), np.int32)])[:n]
        for layer, want in enumerate(arch.ref_latents(TINY, params, jnp.asarray(fed), n)):
            np.testing.assert_allclose(np.asarray(held["latent"][layer]), np.asarray(want), atol=2e-4)


def test_the_engine_says_what_its_arena_holds(served):
    eng, _ = served
    st = eng.stats()
    occ = st["pool_occupancy"]
    assert occ["kind"] == "latent"
    assert occ["token_bytes_counted"] == 3 * 136 * 4 and occ["token_bytes_laid_out"] == 3 * 256 * 4
    assert {k: st["moe"][k] for k in ("experts_held", "expert_first", "experts_published", "router")} == {
        "experts_held": 4, "expert_first": 4, "experts_published": 16, "router": "sigmoid_group"}
    assert set(st["moe"]) >= {"expert_rows_per_step", "experts_hit_share", "row_sums"}
    assert set(eng.pool.arenas) == {"latent"} and eng.pool.v_arena is None
    assert eng.pool.block_bytes() == 16 * 3 * 256 * 4
    assert eng._flight_state()["pool"]["kind"] == "latent"
    dense = llama.Config.from_name("tiny-llama-debug")
    plain = tt.serve(None, llama.init_params(dense, jax.random.PRNGKey(0)), dense, num_blocks=8, max_batch=1)
    assert plain.stats()["pool_occupancy"]["kind"] == "kv" and "moe" not in plain.stats()


# --------------------------------------------------------------------------
# the kernels
# --------------------------------------------------------------------------

def _decode_case(dtype, seed=0):
    rng = np.random.default_rng(seed)
    nb, L, bs, W, dc, nh, B, nbb = 40, 2, 16, 256, 128, 4, 4, 12
    arena = jnp.asarray(rng.normal(size=(nb, L, 1, bs, W)), dtype)
    q = jnp.asarray(rng.normal(size=(B, nh, W)), dtype)
    fresh = jnp.asarray(rng.normal(size=(B, W)), dtype)
    tables = jnp.asarray(rng.permutation(np.arange(1, nb))[:B * nbb // 2].reshape(B, nbb // 2), jnp.int32)
    tables = jnp.concatenate([tables, jnp.zeros_like(tables)], axis=1)          # sink-padded
    pos = jnp.asarray([0, 37, 95, 16], jnp.int32)                              # nothing cached; a block's edge
    return dict(q=q, arena=arena, fresh=fresh, tables=tables, pos=pos), dict(layer=1, dc=dc, scale=0.11)


@pytest.mark.parametrize("dtype,tol", [(jnp.float32, 1e-5), (jnp.bfloat16, 3e-2)], ids=["float32", "bfloat16"])
def test_the_decode_kernel_interpreted_is_its_xla_form(dtype, tol, monkeypatch):
    monkeypatch.setenv("THUNDER_TPU_PALLAS_INTERPRET", "1")
    monkeypatch.setattr(px, "_MLA_CHUNK_KEYS", 32)                  # several chunks, a ragged last one
    args, kw = _decode_case(dtype)
    before = px.stats.get("mla_decode", 0)
    got = px.mla_paged_decode(*args.values(), **kw)
    assert px.stats["mla_decode"] == before + 1
    want = px._mla_decode_xla(*args.values(), **kw)
    np.testing.assert_allclose(np.asarray(got, np.float32), np.asarray(want, np.float32), atol=tol)
    monkeypatch.delenv("THUNDER_TPU_PALLAS_INTERPRET")
    np.testing.assert_array_equal(np.asarray(px.mla_paged_decode(*args.values(), **kw), np.float32),
                                  np.asarray(want, np.float32))       # without Pallas: the XLA form itself
    assert px.stats["mla_decode"] == before + 1


def test_the_latent_write_lands_one_row_a_layer(monkeypatch):
    monkeypatch.setenv("THUNDER_TPU_PALLAS_INTERPRET", "1")
    from thunder_tpu.serving.paged_attention import write_fresh_kv

    args, _ = _decode_case(jnp.float32, 1)
    arena, tables, pos = args["arena"], args["tables"], args["pos"]
    rows = jnp.asarray(np.random.default_rng(2).normal(size=(4, 2, 1, 256)), jnp.float32)
    out = write_fresh_kv({"latent": arena}, {"latent": rows}, tables, pos, block_size=16)["latent"]
    want = np.array(arena)
    for i in range(4):
        want[int(tables[i, int(pos[i]) // 16]), :, 0, int(pos[i]) % 16] = np.asarray(rows[i, :, 0])
    np.testing.assert_array_equal(np.asarray(out), want)


def test_the_engine_claims_the_kernels_where_pallas_runs(model, prompts, solo, monkeypatch):
    """Under the interpreter the decode program calls ``mla_paged_decode`` once
    a layer and the expert share ``moe_grouped_mm``; the tokens are the XLA
    forms' tokens."""
    monkeypatch.setenv("THUNDER_TPU_PALLAS_INTERPRET", "1")
    cfg, params = model
    before = dict(px.stats)
    eng = _engine(cfg, params, batch_buckets=(4,), block_buckets=(4,))
    out = _serve(eng, prompts)
    for got, want in zip(out, solo):
        np.testing.assert_array_equal(got, want)
    programs = eng.stats()["compile_counts"]["decode_paged"]
    assert programs >= 1
    assert px.stats.get("mla_decode", 0) - before.get("mla_decode", 0) == cfg.n_layer * programs
    assert px.stats.get("grouped_mm", 0) > before.get("grouped_mm", 0)


# --------------------------------------------------------------------------
# what is refused, and why
# --------------------------------------------------------------------------

REFUSED = {
    "kv_dtype": (dict(kv_dtype="fp8"), "no dequant"),
    "int8": (dict(kv_dtype="int8"), "no dequant"),
}
GONE = {    # what no model can ask for any more: one decode program a job, a model a Config
    "attn_gather": (dict(attn="gather"), TypeError, "unexpected keyword argument 'attn'"),
    "model_fn": (dict(model_fn=lambda *a, **k: None), NotImplementedError, "llama.Config.*custom model_fn"),
}


@pytest.mark.parametrize("feature", [*REFUSED, *GONE, "speculative", "lora", "mesh"])
def test_each_refused_feature_raises_with_its_reason(model, feature):
    cfg, params = model
    if feature in GONE:
        options, error, reason = GONE[feature]
        options = dict(options)
        with pytest.raises(error, match=reason):
            tt.serve(options.pop("model_fn", None), params, cfg, num_blocks=8, max_batch=1, **options)
        return
    if feature in REFUSED:
        options, reason = REFUSED[feature]
        with pytest.raises(NotImplementedError, match=f"latent attention.*{reason}"):
            tt.serve(None, params, cfg, num_blocks=8, max_batch=1, **options)
        return
    reasons = {"speculative": "verify step attends several draft tokens", "lora": "latent projections",
               "mesh": "no heads axis shards"}
    why = engine_mod.latent_unsupported(cfg, **{feature: object()})
    assert reasons[feature] in why
    assert engine_mod.latent_unsupported(cfg) is None


def test_training_refuses_the_config_with_its_reason(model):
    cfg, params = model
    with pytest.raises(NotImplementedError, match="cannot be trained through tt.jit.*kv_lora_rank"):
        llama.block_forward(params["blocks"][0], jnp.zeros((1, 4, 64)), None, None, cfg)
    routed = llama.Config(name="grouped", n_layer=2, n_head=4, n_embd=64, mlp_class="SparseMoE", n_expert=8,
                          n_expert_per_token=2, intermediate_size=32, moe_router="sigmoid_group", n_group=2,
                          topk_group=1)
    assert "sigmoid_group" in llama.serving_only(routed) and routed.training_only is None
    softmax = dataclasses.replace(routed, moe_router="softmax")
    assert llama.serving_only(softmax) is None and softmax.training_only is None     # served and trained (PR 59)
    G.require_servable(softmax)
    zero_centred = dataclasses.replace(softmax, norm_zero_centered=True)
    with pytest.raises(NotImplementedError, match="norm_zero_centered"):
        G.require_servable(zero_centred)


def test_latent_attention_without_a_query_bottleneck_is_refused(model):
    cfg, _ = model
    with pytest.raises(AssertionError, match="needs q_lora_rank"):
        dataclasses.replace(cfg, q_lora_rank=0)


def test_a_multi_token_piece_through_the_paged_forward_is_refused(model):
    from thunder_tpu.serving.paged_attention import forward_paged

    cfg, params = model
    eng = _engine(cfg, params)
    cos, sin = llama.build_rope_cache(cfg, 64)
    with pytest.raises(NotImplementedError, match="one token a row"):
        forward_paged(params, jnp.zeros((1, 4), jnp.int32), jnp.zeros((1,), jnp.int32), eng.pool.arenas,
                      jnp.zeros((1, 4), jnp.int32), cos, sin, cfg, cdtype=jnp.float32)
