"""Device scopes: ``tt.scope`` names the operations traced inside it, and the
name reaches the lowered program.

What is held: a scope stamps the bound symbols recorded under it and the stamp
follows them through ``from_bsym``, a rewriting pass, the forward/backward
split and the XLA regions, with the user function's name where no scope is
open and ``unscoped/<symbol>`` where nothing is known; the train step and the
serving programs of a dense, a hybrid and a latent model lower with a group in
the ``op_name`` of every product and every kernel, ``bwd`` on the backward
trace, ``optimizer`` on the update; names are a function of the model alone
(two builds lower the same text) and change nothing that is computed; a trace
evaluated eagerly opens no scope.
"""
from __future__ import annotations

import functools
import os
import re
import sys

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import thunder_tpu as tt  # noqa: E402
from chipbench import common, op_scopes  # noqa: E402
from thunder_tpu import distributed as dist  # noqa: E402
from thunder_tpu import torch as ltorch  # noqa: E402
from thunder_tpu.core import prims  # noqa: E402
from thunder_tpu.core.symbol import provenance_inherited  # noqa: E402
from thunder_tpu.executors import pallasex as px  # noqa: E402
from thunder_tpu.executors import utils as exutils  # noqa: E402
from thunder_tpu.models import llama  # noqa: E402
from thunder_tpu.observability.events import GROUPS, scope  # noqa: E402

I32 = jnp.int32
PRODUCTS = ("dot_general", "convolution", "tpu_custom_call")


# --------------------------------------------------------------------------
# reading a lowered program: every operation with the name JAX gave it
# --------------------------------------------------------------------------

def named_ops(lowered) -> list:
    """``(operation, op_name)`` of a lowered program.  An operation inside a
    private function (an inner ``jax.jit``, lowered once for all its callers)
    carries the function's own stack only; XLA puts the call's before it when
    it inlines, and so does this: a function's operations are listed once a
    call site, under that site's stack."""
    text = lowered.as_text(debug_info=True)
    defs = dict(re.findall(r'^(#loc\d+) = (.*)$', text, re.M))

    def name(ref, depth=0):
        body = defs.get(ref, "")
        m = re.match(r'loc\("([^"]*)"', body)
        if m:
            return m.group(1)
        m = re.search(r'#loc\d+', body)
        return name(m.group(0), depth + 1) if m and depth < 8 else ""

    funcs, current = {}, None       # function -> [(operation or None for a call, callee, op_name)]
    for line in text.splitlines():
        m = re.match(r'\s*func\.func (?:public |private )?@([\w.$-]+)', line)
        if m:
            current = m.group(1)
            funcs[current] = []
            continue
        m = re.match(r'\s*(?:%[^=]*= )?"?((?:stablehlo|chlo|func)\.[\w.]+|call)"?(.*)loc\((#loc\d+)\)\s*$', line)
        if not m or current is None:
            continue
        op, rest, where = m.group(1), m.group(2), name(m.group(3))
        target = re.search(r'@([\w.$-]+)', rest)
        if op in ("func.call", "call") and target:
            funcs[current].append((None, target.group(1), where))
        else:
            if op == "stablehlo.custom_call" and target:
                op += ":" + target.group(1)
            funcs[current].append((op, None, where))

    def walk(fn: str, stack: str, depth: int = 0):
        for op, callee, where in funcs.get(fn, ()):
            full = f"{stack}/{where}" if stack else where
            if op is not None:
                yield op, full
            elif depth < 16:
                yield from walk(callee, full, depth + 1)

    return list(walk("main", ""))


def scope_paths(ops) -> set:
    """The scope paths of a program's operations, layers summed."""
    return {re.sub(r"(^|/)blk\d+", "", op_scopes.path_of(n)).lstrip("/") for _, n in ops} - {""}


def assert_paths(found: set, expected: set) -> None:
    """Every expected path is there, and every path found is an expected one or
    what JAX put under it (a kernel's name, ``while/body``, an einsum's spec)."""
    under = lambda p, e: p == e or p.startswith(e + "/")  # noqa: E731
    assert not {e for e in expected if not any(under(p, e) for p in found)}
    assert not {p for p in found if not any(under(p, e) for e in expected)}


def assert_products_grouped(ops) -> None:
    loose = [(op, n) for op, n in ops if any(k in op for k in PRODUCTS) and op_scopes.classify(n)[0] is None]
    assert not loose, loose[:5]


# --------------------------------------------------------------------------
# the toy models
# --------------------------------------------------------------------------

DENSE = dict(name="toy-dense", block_size=256, vocab_size=256, padded_vocab_size=256, n_layer=2, n_head=2,
             n_query_groups=1, n_embd=256, head_size=128, intermediate_size=256, mlp_class="LLaMAMLP",
             norm_class="RMSNorm", bias=False, parallel_residual=False)
HYBRID_MOE = {     # the trainer's hybrid: Gated DeltaNet and an expert share (tests/test_hybrid_moe.py's widths)
    "model_name": "toy-hybrid", "hidden_size": 64, "head_dim": 32, "num_attention_heads": 4,
    "num_key_value_heads": 2, "partial_rotary_factor": 0.25, "rope_theta": 10000000,
    "linear_num_key_heads": 2, "linear_num_value_heads": 4, "linear_key_head_dim": 16,
    "linear_value_head_dim": 16, "linear_conv_kernel_dim": 4, "moe_intermediate_size": 48,
    "shared_expert_intermediate_size": 48, "num_experts": 16, "published_num_experts": 16, "first_expert": 0,
    "num_experts_per_tok": 4, "vocab_size": 256, "num_hidden_layers": 2, "full_attention_interval": 2,
    "max_position_embeddings": 256, "rms_norm_eps": 1e-6, "initializer_range": 0.05,
}
LATENT = {         # the server's latent attention with an expert share (tests/test_mla_serving.py's widths)
    "model_name": "toy-latent", "hidden_size": 64, "num_attention_heads": 4, "num_hidden_layers": 2,
    "vocab_size": 256, "max_position_embeddings": 512, "q_lora_rank": 32, "kv_lora_rank": 128,
    "qk_nope_head_dim": 16, "qk_rope_head_dim": 8, "v_head_dim": 16, "intermediate_size": 96,
    "moe_intermediate_size": 32, "n_shared_experts": 1, "n_routed_experts": 4, "published_n_routed_experts": 16,
    "expert_first": 4, "num_experts_per_tok": 4, "n_group": 4, "topk_group": 2, "routed_scaling_factor": 2.5,
    "first_k_dense_replace": 1, "rope_theta": 10000,
    "rope_scaling": {"beta_fast": 32, "beta_slow": 1, "factor": 32, "mscale": 1, "mscale_all_dim": 1,
                     "original_max_position_embeddings": 64, "type": "yarn"},
    "rms_norm_eps": 1e-6, "initializer_range": 0.2,
}


def _arch_model(arch_name: str, hf: dict):
    arch = common.load_module("models", arch_name)
    cfg = llama.Config(**arch.program_config(hf))
    return cfg, functools.partial(arch.make_params, hf, dtype=jnp.float32), arch


def toy(kind: str):
    """``(cfg, make_params(seed words))`` of a toy model."""
    if kind == "dense":
        cfg = llama.Config(**DENSE)
        return cfg, lambda words: llama.init_params(cfg, jax.random.PRNGKey(0), dtype=jnp.float32)
    if kind == "hybrid_moe":
        return _arch_model("hybrid_moe_decoder", HYBRID_MOE)[:2]
    if kind == "hybrid":
        import _hybrid_tiny

        return llama.Config(**_hybrid_tiny.arch.program_config(_hybrid_tiny.TINY)), functools.partial(
            _hybrid_tiny.arch.make_params, _hybrid_tiny.TINY, dtype=jnp.float32)
    return _arch_model("latent_moe_decoder", LATENT)[:2]


# --------------------------------------------------------------------------
# (a) the train step
# --------------------------------------------------------------------------

BLOCK = {"mixer/norm", "mixer/residual", "mlp/norm", "mlp/residual"}
ATTENTION = {"mixer/qkv", "mixer/rope", "mixer/attn", "mixer/out"}
DELTANET = {"mixer/gdn/in_proj", "mixer/gdn/conv", "mixer/gdn/gates", "mixer/gdn/scan", "mixer/gdn/out"}
TRAIN_PATHS = {
    "dense": BLOCK | ATTENTION | {"embed", "mlp/up", "mlp/down", "head/norm", "head/logits", "head/loss"},
    "hybrid_moe": BLOCK | ATTENTION | DELTANET | {"embed", "mlp", "mlp/router", "mlp/experts", "mlp/shared",
                                                  "head/norm", "head/logits", "head/loss"},
}
# a sum's backward pass is no operation: residual sums and the embedding's forward leave no `bwd` twin
NO_BACKWARD = {"mixer/residual", "mlp/residual"}


def _train_step(kind: str, T: int = 32):
    cfg, make = toy(kind)
    params = make(common.seed_words(5))
    if kind == "dense":
        cos, sin = llama.build_rope_cache(cfg, T)
    else:
        cos, sin = common.load_module("models", "hybrid_moe_decoder").rope_tables(HYBRID_MOE, T)
    toks = jax.random.randint(jax.random.PRNGKey(1), (2, T + 1), 0, cfg.vocab_size)
    from jax.sharding import Mesh

    mesh = Mesh(np.array(jax.devices()[:1]).reshape(1), ("dp",))

    def loss_fn(p, i, t, c, s):
        return llama.gpt_loss(p, i, t, c, s, cfg)

    step = dist.make_train_step(loss_fn, optax.adamw(1e-3), mesh, donate=False)
    opt = step.init_optimizer_state(params)
    return step, (params, opt, toks[:, :-1], toks[:, 1:], cos, sin)


def _lower_step(step, args):
    params, opt, *batch = args
    batch = step._prepare(tuple(batch))
    with step._mesh_context():
        return step._get_jitted(params, opt, batch).lower(params, opt, *batch)


@pytest.mark.parametrize("kind", ["dense", "hybrid_moe"])
def test_a_train_step_lowers_with_its_scopes(kind):
    step, args = _train_step(kind, T=32 if kind == "dense" else 128)
    ops = named_ops(_lower_step(step, args))
    assert_products_grouped(ops)
    found = scope_paths(ops)
    forward = {p for p in found if not p.startswith(("bwd", "optimizer"))}
    backward = {p[len("bwd/"):] for p in found if p.startswith("bwd/")}
    assert_paths(forward, TRAIN_PATHS[kind])
    assert_paths(backward - {"unscoped/optimization_barrier"}, TRAIN_PATHS[kind] - NO_BACKWARD)
    assert any(p == "optimizer" or p.startswith("optimizer/") for p in found)
    # every symbol of the backward trace lowers after `bwd`, and nothing of the forward trace does
    stamped = lambda trace: {b.scope for top in trace.bound_symbols for b in (top.subsymbols or (top,))  # noqa: E731
                             if b.sym.id not in (prims.PrimIDs.RETURN, prims.PrimIDs.DEL)}
    assert not any("bwd" in (s or "").split("/") for s in stamped(step.fw_trace) | stamped(step.bw_trace))
    by_direction = {True: 0, False: 0}
    for _, n in ops:
        group, bwd = op_scopes.classify(n)
        if group not in (None, "optimizer"):
            by_direction[bwd] += 1
    assert by_direction[True] > by_direction[False] > 0
    # the optimizer's operations carry `optimizer` and no direction
    assert all(not op_scopes.classify(n)[1] for _, n in ops if op_scopes.classify(n)[0] == "optimizer")
    assert sum(op_scopes.classify(n)[0] == "optimizer" for _, n in ops) > 20


def test_two_builds_lower_the_same_text():
    texts = []
    for _ in range(2):
        step, args = _train_step("dense")
        texts.append(_lower_step(step, args).as_text(debug_info=True))
    assert texts[0] == texts[1]
    assert "blk1/mixer/qkv" in texts[0] and not re.search(r"0x[0-9a-f]{6,}", texts[0])


# --------------------------------------------------------------------------
# (a) the serving programs
# --------------------------------------------------------------------------

SERVE_BLOCK = {"embed", "mixer/norm", "mixer/cache", "mlp/norm", "head/norm", "head/logits", "head/sample"}
SERVE_PATHS = {
    "dense": SERVE_BLOCK | ATTENTION | {"mlp/residual", "mlp/up", "mlp/down"},
    "hybrid": SERVE_BLOCK | ATTENTION - {"mixer/rope"} | DELTANET | {"mlp/up", "mlp/down"},
    "latent": SERVE_BLOCK | {"mixer/mla/q", "mixer/mla/latent", "mixer/attn", "mixer/out", "mlp/residual", "mlp/up",
                             "mlp/down", "mlp/router", "mlp/experts", "mlp/shared"},
}
SERVE_ONLY = {      # what one of the two programs has and the other has not
    ("latent", "decode_paged"): {"mixer/mla/absorb", "mixer/mla/unabsorb"},
    ("latent", "prefill_fresh"): {"mixer/mla/expand"},
}


def _serve_program(kind: str, program: str, *, tpu: bool = False):
    cfg, make = toy(kind)
    params = jax.eval_shape(make, common.seed_words(5))
    # shapes of its own for the TPU's lowering: the jitted kernel wrappers keep what they traced under the interpreter
    bs, Tb, Bb, nbb = (16, 256, 4, 32) if tpu else (16, 128, 4, 16)
    eng = tt.serve(None, params, cfg, num_blocks=40, block_size=bs, max_batch=Bb, prefill_buckets=(Tb,))
    sds = lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype)  # noqa: E731
    one = lambda shape, dt=I32: jax.ShapeDtypeStruct(shape, dt)  # noqa: E731
    weights, arenas = jax.tree_util.tree_map(sds, params), jax.tree_util.tree_map(sds, eng.pool.arenas)
    if program == "prefill_fresh":
        prog = eng._build_prefill(Tb, Tb // bs, fresh=True)
        args = (weights, one((1, Tb)), one(()), arenas, one((Tb // bs,)), one((2,), jnp.uint32), {}, one((1,)),
                *([one((1,))] if eng._hybrid else []))
    else:
        prog = eng._build_decode_paged(Bb, nbb)
        args = (weights, one((Bb,)), one((Bb,)), one((Bb, nbb)), arenas, one((Bb, 2), jnp.uint32), {}, one((Bb,)),
                *([one((4,), jnp.float32)] if eng._moe_rows is not None else []),      # an expert share's running sums
                *([one((Bb,))] if eng._hybrid else []))
    traced = prog.trace(*args)
    return traced.lower(lowering_platforms=("tpu",)) if tpu else traced.lower()


@pytest.fixture
def interpreted(monkeypatch):
    """The Pallas kernels under the interpreter, as the server's paged programs need them on the CPU."""
    monkeypatch.setenv("THUNDER_TPU_PALLAS_INTERPRET", "1")


@pytest.mark.parametrize("program", ["prefill_fresh", "decode_paged"])
@pytest.mark.parametrize("kind", ["dense", "hybrid", "latent"])
def test_a_serving_program_lowers_with_its_scopes(kind, program, interpreted):
    ops = named_ops(_serve_program(kind, program))
    assert_products_grouped(ops)
    found = scope_paths(ops)
    assert not any(p.startswith("bwd") or p.startswith("optimizer") for p in found)
    expected = SERVE_PATHS[kind] | SERVE_ONLY.get((kind, program), set())
    if program == "prefill_fresh" and kind != "latent":
        expected = expected | {"mixer"}          # a post-norm block's residual; the zeros a fresh cache starts from
    assert_paths(found - {"mixer", "mlp"}, expected - {"mixer"})
    # what carries no group is the glue between the scopes: no product, and a small part of the program
    loose = [op for op, n in ops if op_scopes.classify(n)[0] is None and "constant" not in op]
    assert len(loose) < 0.2 * len(ops)


@pytest.mark.parametrize("program", ["prefill_fresh", "decode_paged"])
def test_every_kernel_call_carries_a_group(program, monkeypatch):
    """Lowered for the TPU, where a kernel is one custom call: the dense model at
    heads of 128, over weights that are shapes alone."""
    monkeypatch.setattr(px, "_enabled", lambda: True)
    monkeypatch.setattr(px, "_pallas_available", lambda: True)
    monkeypatch.setattr(px, "_interpret", lambda: False)
    ops = named_ops(_serve_program("dense", program, tpu=True))
    kernels = [(op, n) for op, n in ops if op.endswith("tpu_custom_call")]
    assert kernels
    assert_products_grouped(ops)
    want = {"prefill_fresh": {"_flash_fwd": "mixer/attn"},
            "decode_paged": {"paged_attn_decode": "mixer/attn", "paged_token_write": "mixer/cache"}}[program]
    for kernel, path in want.items():
        sites = [n for _, n in kernels if kernel in n]
        assert sites and all(path in n for n in sites), (kernel, sites[:2])


# --------------------------------------------------------------------------
# (b) names change nothing that is computed
# --------------------------------------------------------------------------

def _scoped_fn(x, w):
    with tt.scope("blk0"):
        with tt.scope("mlp"):
            h = ltorch.linear(x, w)
    return ltorch.sum(ltorch.silu(h))


def _regions(trace):
    return [fc for b in trace.bound_symbols for fc in (b._call_ctx or {}).values() if hasattr(fc, "bsyms")]


def test_a_regions_outputs_are_bit_equal_to_the_unscoped_lowering():
    jf = tt.jit(_scoped_fn)
    x, w = jax.random.normal(jax.random.PRNGKey(0), (8, 16)), jax.random.normal(jax.random.PRNGKey(1), (16, 16))
    jf(x, w)
    (region,) = _regions(tt.last_traces(jf)[-1])

    def parent_raw(*vals):      # the lowering before scopes: the same loop, no name
        env = dict(zip(region.input_names, vals))
        exutils.eval_bsyms(region.bsyms, env)
        return tuple(env[n] for n in region.output_names)

    got, want = region(x, w), jax.jit(parent_raw)(x, w)
    assert all(np.array_equal(np.asarray(a), np.asarray(b)) for a, b in zip(got, want))
    text = region._jitted.lower(x, w).as_text(debug_info=True)
    assert "blk0/mlp/dot_general" in text and "blk0/mlp" not in jax.jit(parent_raw).lower(x, w).as_text(debug_info=True)


# --------------------------------------------------------------------------
# (c) the stamp and its way through the passes
# --------------------------------------------------------------------------

def test_scope_stamps_a_bound_symbol_and_falls_back_to_the_function():
    jf = tt.jit(_scoped_fn)        # interpreted: `with tt.scope(...)` runs as an opaque call
    jf(jnp.ones((8, 16)), jnp.ones((16, 16)))
    first, last = tt.last_traces(jf)[0], tt.last_traces(jf)[-1]
    by_name = {b.sym.name: b.scope for b in first.bound_symbols}
    assert by_name["linear"] == "blk0/mlp"
    assert by_name["silu"] == by_name["sum"] == "_scoped_fn"         # under no scope: the user function's name
    (region,) = _regions(last)                                          # a region keeps its members' scopes
    assert not region.backward
    assert {b.sym.name: b.scope for b in region.bsyms}["linear"] == "blk0/mlp"
    linear = next(b for b in first.bound_symbols if b.sym.name == "linear")
    assert linear.from_bsym(args=linear.args).scope == "blk0/mlp"
    assert linear.from_bsym(scope="other").scope == "other"
    assert all(s.scope == "blk0/mlp" for s in linear.subsymbols)      # what a composite records is in its scope


def test_the_stamp_survives_a_rewriting_pass_and_the_backward_split():
    g = tt.grad(_scoped_fn)
    g(jnp.ones((8, 16)), jnp.ones((16, 16)))
    fw, bw = _regions(tt.last_traces(g)[-1]), _regions(tt.last_backward_traces(g)[-1])
    assert fw and bw and not any(r.backward for r in fw) and all(r.backward for r in bw)
    # the backward rule of `linear` records its products on the forward symbol's behalf
    products = [b for r in bw for b in r.bsyms if b.sym.name in ("matmul", "linear", "dot_general")]
    assert products and all(b.scope == "blk0/mlp" for b in products)
    # and the region lowers them after `bwd`
    from thunder_tpu.core import dtypes

    for top in tt.last_backward_traces(g)[-1].bound_symbols:
        for region in (top._call_ctx or {}).values():
            avals = [jax.ShapeDtypeStruct(tuple(p.shape), dtypes.to_jax_dtype(p.dtype)) for p in top.args]
            text = region._jitted.lower(*avals).as_text(debug_info=True)
            assert "/bwd/blk0/mlp/" in text and not re.search(r'loc\("jit\(_raw\)/(?!bwd/)', text)


def test_provenance_inherited_hands_the_scope_on():
    from thunder_tpu.core.trace import tracectx

    jf = tt.jit(_scoped_fn)
    jf(jnp.ones((8, 16)), jnp.ones((16, 16)))
    trace = tt.last_traces(jf)[0]
    linear = next(b for b in trace.bound_symbols if b.sym.name == "linear")
    with tracectx(trace), trace.push_scope() as recorded, provenance_inherited(linear):
        ltorch.silu(linear.output)
    assert recorded and all(b.scope == "blk0/mlp" for b in recorded)
    assert all(b.source_filename == linear.source_filename for b in recorded)


def test_a_symbol_with_neither_scope_nor_provenance_lowers_under_unscoped():
    jf = tt.jit(_scoped_fn)
    jf(jnp.ones((8, 16)), jnp.ones((16, 16)))
    (region,) = _regions(tt.last_traces(jf)[-1])
    bare = [b.from_bsym(scope=None, subsymbols=tuple(s.from_bsym(scope=None) for s in b.subsymbols))
            for b in region.bsyms]

    def raw(*vals):
        env = dict(zip(region.input_names, vals))
        exutils.lower_bsyms(bare, env)
        return tuple(env[n] for n in region.output_names)

    text = jax.jit(raw).lower(jnp.ones((8, 16)), jnp.ones((16, 16))).as_text(debug_info=True)
    assert "unscoped/linear" in text or "unscoped/matmul" in text or "unscoped/dot_general" in text


def test_outside_a_trace_a_scope_is_a_jax_named_scope():
    def f(x):
        with tt.scope("mixer"):
            with scope("attn"):
                return jnp.dot(x, x)

    text = jax.jit(f).lower(jnp.ones((4, 4))).as_text(debug_info=True)
    assert "jit(f)/mixer/attn/dot_general" in text

    @tt.scope("head/sample")        # the decorator form, a fresh context a call
    def pick(x):
        return jnp.argmax(x, -1)

    text = jax.jit(lambda x: pick(pick(x)[None].astype(jnp.float32))).lower(jnp.ones((4, 4))).as_text(debug_info=True)
    assert text.count('/head/sample"') >= 2 and "head/sample/head/sample" not in text
    assert GROUPS == op_scopes.GROUPS


# --------------------------------------------------------------------------
# (d) a trace evaluated eagerly pays nothing
# --------------------------------------------------------------------------

def test_eval_bsyms_called_eagerly_opens_no_scope(monkeypatch):
    jf = tt.jit(_scoped_fn)
    x, w = jnp.ones((8, 16)), jnp.ones((16, 16))
    jf(x, w)
    (region,) = _regions(tt.last_traces(jf)[-1])
    opened = []
    real = jax.named_scope

    def counting(name):
        opened.append(name)
        return real(name)

    monkeypatch.setattr(exutils.jax, "named_scope", counting)
    env = dict(zip(region.input_names, (x, w)))
    exutils.eval_bsyms(region.bsyms, env)
    assert not opened and all(n in env for n in region.output_names)
    env = dict(zip(region.input_names, (x, w)))
    exutils.lower_bsyms(region.bsyms, env, backward=True)
    assert opened and all(n.startswith("bwd/") for n in opened) and "bwd/blk0/mlp" in opened
