"""Reusable benchmark-class library (reference ``thunder/benchmarks/__init__.py:50-460``).

The reference ships ~25 benchmark classes sharing one contract — a
``Benchmark`` with a name, a ``make_batch`` (sample inputs), and an ``fn``
to time — plus harness functions that run any of them under any executor
and report wallclock stats.  The TPU-native analog here keeps that contract
but fences every timed loop with ``jax.block_until_ready``
(``timing.time_fn``) and compares the thunder_tpu pipeline against stock
``jax.jit`` instead of torch eager.

Tiers (mirroring the reference's spread):
- per-op      — gelu, cross_entropy, rms_norm, sdpa, swiglu (``op_benchmarks``)
- per-block   — MLP, causal self-attention, full transformer block
  (``block_benchmarks``; reference LitGPTMLP/CSA/Block classes, :584-698)
- per-model   — the llama family train step (``model_benchmarks``)

Every class is importable and pytest-runnable (``tests/test_bench_targets.py``)
and drivable standalone via ``python bench.py blocks``.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Callable

import jax
import jax.numpy as jnp

from thunder_tpu.benchmarks.timing import time_fn

__all__ = [
    "Benchmark",
    "BenchmarkResult",
    "run_benchmark",
    "op_benchmarks",
    "block_benchmarks",
    "model_benchmarks",
    "ablation_benchmarks",
    "jax_gpt_loss",
    "all_benchmarks",
]


@dataclasses.dataclass
class Benchmark:
    """One timeable workload: ``fn(*make_batch())`` under the thunder_tpu
    jit, ``baseline_fn`` (same math, plain jax) under stock ``jax.jit``."""

    name: str
    fn: Callable  # thunder_tpu-level callable (ltorch ops)
    baseline_fn: Callable | None  # plain-jax same-math callable (None: reuse fn)
    make_batch: Callable[[], tuple]  # () -> args
    tier: str = "op"  # op | block | model
    prejitted: bool = False  # fns already compiled (tt.grad / jax.grad pairs)
    # executor-ablation axis (reference's executor-zoo benchmarks,
    # benchmarks/__init__.py:699-975): e.g. {"executors": ["xla", "jax"]}
    # benches the same workload with pallas kernels disabled
    jit_kwargs: dict = dataclasses.field(default_factory=dict)


@dataclasses.dataclass
class BenchmarkResult:
    name: str
    tier: str
    thunder_ms: float
    baseline_ms: float | None
    speedup: float | None  # baseline / thunder

    def row(self) -> dict:
        out = {"name": self.name, "tier": self.tier,
               "thunder_ms": round(self.thunder_ms, 4)}
        if self.baseline_ms is not None:
            out["jax_ms"] = round(self.baseline_ms, 4)
            out["speedup"] = round(self.speedup, 3) if self.speedup else None
        return out


def run_benchmark(b: Benchmark, *, reps: int = 3) -> BenchmarkResult:
    """Times ``b`` (thunder pipeline vs stock jax.jit), pairwise-interleaved
    per rep with per-side min: each rep times both sides back-to-back, so a
    disturbance of the machine hits both."""
    import thunder_tpu as tt

    args = b.make_batch()
    tfn = b.fn if b.prejitted else tt.jit(b.fn, **b.jit_kwargs)
    if b.baseline_fn is None:
        jfn = None
    else:
        jfn = b.baseline_fn if b.prejitted else jax.jit(b.baseline_fn)
    t_vals, j_vals = [], []
    for _ in range(reps):
        t_vals.append(time_fn(tfn, *args))
        if jfn is not None:
            j_vals.append(time_fn(jfn, *args))
    t_ms = min(t_vals) * 1e3
    j_ms = min(j_vals) * 1e3 if j_vals else None
    return BenchmarkResult(b.name, b.tier, t_ms, j_ms, j_ms / t_ms if j_ms else None)


#
# Shape presets: "tpu" = the headline-scale shapes (v5e, bf16), "cpu" = toy
# dims for CI (the classes themselves are shape-agnostic)
#


def _shapes(on_tpu: bool) -> dict:
    if on_tpu:
        return dict(B=8, H=32, T=2048, hs=128, C=4096, V=32000, I=11008, dt=jnp.bfloat16)
    return dict(B=2, H=2, T=128, hs=32, C=128, V=512, I=344, dt=jnp.float32)


def op_benchmarks(on_tpu: bool) -> list[Benchmark]:
    """Per-op tier (reference targets.py:402-700 op benchmarks)."""
    import thunder_tpu.torch as ltorch

    s = _shapes(on_tpu)
    B, T, C, V, I, dt = s["B"], s["T"], s["C"], s["V"], s["I"], s["dt"]
    key = jax.random.PRNGKey(0)
    k = lambda i: jax.random.fold_in(key, i)
    N = B * T

    def batch_rows():
        return (jax.random.normal(k(0), (N, C), dtype=dt),)

    def batch_ce():
        return (jax.random.normal(k(1), (N, V), dtype=jnp.float32),
                jax.random.randint(k(2), (N,), 0, V))

    def batch_norm():
        return (jax.random.normal(k(0), (N, C), dtype=dt), jnp.ones((C,), dtype=dt))

    def batch_mlp():
        return (jax.random.normal(k(0), (N, C), dtype=dt),
                jax.random.normal(k(3), (I, C), dtype=dt) * 0.02,
                jax.random.normal(k(4), (I, C), dtype=dt) * 0.02,
                jax.random.normal(k(5), (C, I), dtype=dt) * 0.02)

    def plain_ce(l, t):
        lse = jax.nn.logsumexp(l, axis=-1)
        return (lse - jnp.take_along_axis(l, t[:, None], axis=1)[:, 0]).mean()

    def plain_rms(a, w):
        af = a.astype(jnp.float32)
        ms = jnp.mean(af * af, axis=-1, keepdims=True)
        return ((af * jax.lax.rsqrt(ms + 1e-5)) * w.astype(jnp.float32)).astype(a.dtype)

    return [
        Benchmark("gelu", lambda a: ltorch.gelu(a),
                  functools.partial(jax.nn.gelu, approximate=False), batch_rows),
        Benchmark("cross_entropy", lambda l, t: ltorch.cross_entropy(l, t), plain_ce, batch_ce),
        Benchmark("rms_norm", lambda a, w: ltorch.rms_norm(a, (C,), w), plain_rms, batch_norm),
        Benchmark("swiglu_mlp",
                  lambda x, a, b, c: ltorch.linear(ltorch.silu(ltorch.linear(x, a)) * ltorch.linear(x, b), c),
                  lambda x, a, b, c: (jax.nn.silu(x @ a.T) * (x @ b.T)) @ c.T, batch_mlp),
    ]


def block_benchmarks(on_tpu: bool) -> list[Benchmark]:
    """Per-block tier: MLP / causal self-attention / full transformer block
    through the framework vs the hand-written jax mirror (reference
    LitGPTMLP / LitGPTCSA / LitGPTBlock benchmark classes)."""
    from thunder_tpu.models import llama

    s = _shapes(on_tpu)
    B, dt = s["B"], s["dt"]
    if on_tpu:
        cfg = llama.Config.from_name("Llama-2-7b-hf", n_layer=1)
    else:
        cfg = llama.Config.from_name("tiny-llama-debug", n_layer=1)
    T = min(s["T"], cfg.block_size)
    key = jax.random.PRNGKey(0)
    params = llama.init_params(cfg, key, dtype=dt)
    bp = params["blocks"][0]
    cos, sin = llama.build_rope_cache(cfg, T, dtype=jnp.float32)
    x = jax.random.normal(jax.random.fold_in(key, 9), (B, T, cfg.n_embd), dtype=dt)

    # the hand-written jax mirrors (same math as models/llama, no tracing)
    def jax_rms(h, w):
        hf = h.astype(jnp.float32)
        ms = jnp.mean(hf * hf, axis=-1, keepdims=True)
        return ((hf * jax.lax.rsqrt(ms + cfg.norm_eps)) * w.astype(jnp.float32)).astype(h.dtype)

    def jax_rope(h, cos_, sin_):
        half = h.shape[-1] // 2
        rotated = jnp.concatenate([-h[..., half:], h[..., :half]], axis=-1)
        return (h * cos_ + rotated * sin_).astype(h.dtype)

    def jax_csa(ap, h):
        Bl, Tl, Cl = h.shape
        hs, nh, ng = cfg.head_size, cfg.n_head, cfg.n_query_groups
        q = (h @ ap["wq"].T).reshape(Bl, Tl, nh, hs).transpose(0, 2, 1, 3)
        kk = (h @ ap["wk"].T).reshape(Bl, Tl, ng, hs).transpose(0, 2, 1, 3)
        v = (h @ ap["wv"].T).reshape(Bl, Tl, ng, hs).transpose(0, 2, 1, 3)
        q, kk = jax_rope(q, cos, sin), jax_rope(kk, cos, sin)
        if ng != nh:
            kk = jnp.repeat(kk, nh // ng, axis=1)
            v = jnp.repeat(v, nh // ng, axis=1)
        sres = (q @ kk.transpose(0, 1, 3, 2)).astype(jnp.float32) / (hs ** 0.5)
        mask = jnp.tril(jnp.ones((Tl, Tl), dtype=bool))
        sres = jnp.where(mask, sres, -jnp.inf)
        y = (jax.nn.softmax(sres, axis=-1).astype(q.dtype) @ v)
        y = y.transpose(0, 2, 1, 3).reshape(Bl, Tl, nh * hs)
        return y @ ap["wo"].T

    def jax_mlp(mp, h):
        return (jax.nn.silu(h @ mp["fc_1"].T) * (h @ mp["fc_2"].T)) @ mp["proj"].T

    def jax_block(bp_, h):
        a = h + jax_csa(bp_["attn"], jax_rms(h, bp_["norm_1"]))
        return a + jax_mlp(bp_["mlp"], jax_rms(a, bp_["norm_2"]))

    # cos/sin travel as explicit args: the thunder jit proxies ARGUMENTS —
    # a closed-over concrete jax array inside ltorch ops is "not number-like"
    benches = [
        Benchmark("block_mlp", lambda mp, h: llama.mlp(mp, h, cfg),
                  jax_mlp, lambda: (bp["mlp"], x), tier="block"),
        Benchmark("block_csa",
                  lambda ap, h, c, s: llama.attention(ap, h, c, s, cfg),
                  lambda ap, h, c, s: jax_csa(ap, h), lambda: (bp["attn"], x, cos, sin),
                  tier="block"),
        Benchmark("transformer_block",
                  lambda bp_, h, c, s: llama.block_forward(bp_, h, c, s, cfg),
                  lambda bp_, h, c, s: jax_block(bp_, h), lambda: (bp, x, cos, sin),
                  tier="block"),
    ]

    # fwd+bwd tier (the reference benchmarks backward too): grads of a
    # scalarized block loss wrt the block params, framework VJP vs jax.grad
    import thunder_tpu as tt
    import thunder_tpu.torch as ltorch

    def t_block_loss(bp_, h, c, s):
        out = llama.block_forward(bp_, h, c, s, cfg)
        return ltorch.sum(out * out)

    def j_block_loss(bp_, h, c, s):
        out = jax_block(bp_, h)
        return jnp.sum((out * out).astype(jnp.float32))

    benches.append(Benchmark(
        "transformer_block_grad",
        tt.grad(t_block_loss, argnums=0),
        jax.jit(jax.grad(j_block_loss, argnums=0)),
        lambda: (bp, x, cos, sin), tier="block", prejitted=True,
    ))
    return benches


def jax_gpt_loss(cfg):
    """A config-parameterized PLAIN-JAX mirror of ``models/llama.gpt_loss``
    (same math, no tracing pipeline) so every model family benches against a
    stock ``jax.jit`` baseline — the reference benches LitGPT models against
    torch eager/compile the same way.  Handles every config switch the model
    zoo uses: RMS/layer norm, partial rope, GQA, sliding window, the four
    MLP classes (incl. dense MoE), parallel residual, learned positions,
    scaled/tied embeddings, and the -100-ignore CE."""

    def norm(h, w, b=None):
        hf = h.astype(jnp.float32)
        if cfg.norm_class == "RMSNorm":
            out = hf * jax.lax.rsqrt(jnp.mean(hf * hf, -1, keepdims=True) + cfg.norm_eps)
            out = out * w.astype(jnp.float32)
        else:
            mu = jnp.mean(hf, -1, keepdims=True)
            var = jnp.mean((hf - mu) ** 2, -1, keepdims=True)
            out = (hf - mu) * jax.lax.rsqrt(var + cfg.norm_eps) * w.astype(jnp.float32)
            if b is not None:
                out = out + b.astype(jnp.float32)
        return out.astype(h.dtype)

    def rope(h, cos, sin):
        half = h.shape[-1] // 2
        rotated = jnp.concatenate([-h[..., half:], h[..., :half]], -1)
        return (h * cos + rotated * sin).astype(h.dtype)

    def lin(x, w, b=None):
        y = x @ w.T
        return y if b is None else y + b

    def attn(ap, h, cos, sin):
        B, T, _ = h.shape
        hs, nh, ng = cfg.head_size, cfg.n_head, cfg.n_query_groups
        q = lin(h, ap["wq"], ap.get("bq")).reshape(B, T, nh, hs).transpose(0, 2, 1, 3)
        k = lin(h, ap["wk"], ap.get("bk")).reshape(B, T, ng, hs).transpose(0, 2, 1, 3)
        v = lin(h, ap["wv"], ap.get("bv")).reshape(B, T, ng, hs).transpose(0, 2, 1, 3)
        ne = cfg.rope_n_elem
        if ne > 0:
            q_r, k_r = rope(q[..., :ne], cos, sin), rope(k[..., :ne], cos, sin)
            q = jnp.concatenate([q_r, q[..., ne:]], -1) if ne < hs else q_r
            k = jnp.concatenate([k_r, k[..., ne:]], -1) if ne < hs else k_r
        if ng != nh:
            k = jnp.repeat(k, nh // ng, axis=1)
            v = jnp.repeat(v, nh // ng, axis=1)
        s = (q @ k.transpose(0, 1, 3, 2)).astype(jnp.float32) / (hs ** 0.5)
        rows = jnp.arange(T)[:, None]
        cols = jnp.arange(T)[None, :]
        mask = cols <= rows
        if cfg.sliding_window is not None:
            mask = mask & (cols > rows - cfg.sliding_window)
        s = jnp.where(mask, s, -jnp.inf)
        y = (jax.nn.softmax(s, axis=-1).astype(q.dtype) @ v)
        y = y.transpose(0, 2, 1, 3).reshape(B, T, nh * hs)
        return lin(y, ap["wo"], ap.get("bo"))

    def gelu(x):
        return jax.nn.gelu(x, approximate=cfg.gelu_approximate == "tanh")

    def mlp(mp, h):
        if cfg.mlp_class == "LLaMAMoE":
            E, kk = cfg.n_expert, cfg.n_expert_per_token
            router = h @ mp["gate"].T
            top_logits, top_idx = jax.lax.top_k(router, kk)
            probs = jax.nn.softmax(top_logits.astype(jnp.float32), -1)
            y = 0.0
            for e in range(E):
                w_e = jnp.sum(probs * (top_idx == e).astype(jnp.float32), -1)
                xe = lin(jax.nn.silu(lin(h, mp["fc_1"][e])) * lin(h, mp["fc_2"][e]), mp["proj"][e])
                y = y + xe * w_e[..., None].astype(h.dtype)
            return y
        if cfg.mlp_class == "LLaMAMLP":
            return lin(jax.nn.silu(lin(h, mp["fc_1"], mp.get("fc_1_b")))
                       * lin(h, mp["fc_2"], mp.get("fc_2_b")), mp["proj"], mp.get("proj_b"))
        if cfg.mlp_class == "GemmaMLP":
            return lin(gelu(lin(h, mp["fc_1"], mp.get("fc_1_b")))
                       * lin(h, mp["fc_2"], mp.get("fc_2_b")), mp["proj"], mp.get("proj_b"))
        return lin(gelu(lin(h, mp["fc"], mp.get("fc_b"))), mp["proj"], mp.get("proj_b"))

    def block(bp, h, cos, sin):
        n1 = norm(h, bp["norm_1"], bp.get("norm_1_b"))
        a = attn(bp["attn"], n1, cos, sin)
        if cfg.parallel_residual:
            n2 = n1 if cfg.shared_attention_norm else norm(h, bp["norm_2"], bp.get("norm_2_b"))
            return h + a + mlp(bp["mlp"], n2)
        h = h + a
        return h + mlp(bp["mlp"], norm(h, bp["norm_2"], bp.get("norm_2_b")))

    def loss(params, idx, targets, cos, sin):
        x = params["wte"][idx]
        if cfg.scale_embedding:
            x = x * (cfg.n_embd ** 0.5)
        if cfg.learned_pos_embedding:
            x = x + params["wpe"][: idx.shape[1]]
        for bp in params["blocks"]:
            x = block(bp, x, cos, sin)
        x = norm(x, params["ln_f"], params.get("ln_f_b"))
        head = params["wte"] if cfg.tie_embeddings else params["lm_head"]
        logits = lin(x, head, params.get("lm_head_b")).astype(jnp.float32)
        V = logits.shape[-1]
        lo, t = logits.reshape(-1, V), targets.reshape(-1)
        lse = jax.nn.logsumexp(lo, axis=-1)
        nll = lse - jnp.take_along_axis(lo, jnp.maximum(t, 0)[:, None], axis=1)[:, 0]
        valid = t != -100
        return jnp.sum(jnp.where(valid, nll, 0.0)) / jnp.maximum(jnp.sum(valid), 1)

    return loss


# model-family grid: (short, CPU debug config, TPU config + overrides,
# TPU batch override).  TPU configs are the real architectures depth-
# truncated to bench on one chip; wide-vocab families get smaller (B, T) —
# Gemma's 256k vocab at the shared B=8,T=2048 preset would materialize a
# 16.8 GB fp32 logits tensor alone (> v5e HBM)
_MODEL_FAMILIES = [
    ("llama2", "tiny-llama-debug", ("Llama-2-7b-hf", {"n_layer": 2}), {}),
    ("gpt2", "nanogpt-debug", ("gpt2-124m", {}), {}),
    ("mistral_sw", "tiny-mistral-debug", ("Mistral-7B-like", {"n_layer": 2}), {}),
    ("gemma", "tiny-gemma-debug", ("Gemma-7b-like", {"n_layer": 2}), {"B": 2, "T": 1024}),
    ("falcon", "tiny-falcon-debug", ("Falcon-7b-like", {"n_layer": 2}), {"B": 4, "T": 1024}),
    ("pythia", "tiny-pythia-debug", ("Pythia-6.9b-like", {"n_layer": 2}), {"B": 4, "T": 1024}),
    ("moe", "tiny-moe-debug", ("Mixtral-8x7B-like", {"n_layer": 1}), {"B": 4, "T": 1024}),
]


def _family_batch(cfg, on_tpu: bool, override: dict | None = None):
    from thunder_tpu.models import llama

    s = _shapes(on_tpu)
    s.update(override or {})
    B, dt = s["B"], s["dt"]
    T = min(s["T"], cfg.block_size)
    key = jax.random.PRNGKey(0)
    params = llama.init_params(cfg, key, dtype=dt)
    idx = jax.random.randint(jax.random.fold_in(key, 1), (B, T), 0, cfg.vocab_size)
    tgt = jax.random.randint(jax.random.fold_in(key, 2), (B, T), 0, cfg.vocab_size)
    cos, sin = llama.build_rope_cache(cfg, T, dtype=jnp.float32)
    return params, idx, tgt, cos, sin


def model_benchmarks(on_tpu: bool, families: list[str] | None = None) -> list[Benchmark]:
    """Per-model tier: every zoo family, forward+loss AND fwd+bwd, each with
    a plain-jax baseline (``jax_gpt_loss``).  ``families`` filters by short
    name (CI smokes one; ``bench.py blocks`` runs the grid).  Device arrays
    allocate LAZILY inside make_batch — eager construction would hold every
    family's multi-GB weights alive at once on TPU."""
    import thunder_tpu as tt
    from thunder_tpu.models import llama

    out = []
    for short, cpu_name, (tpu_name, tpu_kw), tpu_batch in _MODEL_FAMILIES:
        if families is not None and short not in families:
            continue
        cfg = (llama.Config.from_name(tpu_name, **tpu_kw) if on_tpu
               else llama.Config.from_name(cpu_name))
        jloss = jax_gpt_loss(cfg)
        mk = (lambda _c=cfg, _o=tpu_batch if on_tpu else None:
              _family_batch(_c, on_tpu, _o))

        def t_loss(p, i, t, c, s, _cfg=cfg):
            return llama.gpt_loss(p, i, t, c, s, _cfg)

        out.append(Benchmark(f"{short}_loss", t_loss, jloss, mk, tier="model"))
        out.append(Benchmark(
            f"{short}_grad",
            tt.grad(t_loss, argnums=0),
            jax.jit(jax.grad(jloss, argnums=0)),
            mk, tier="model", prejitted=True,
        ))
    return out


def ablation_benchmarks(on_tpu: bool) -> list[Benchmark]:
    """Executor-ablation axis (reference executor-zoo benchmarks,
    benchmarks/__init__.py:699-975): the SAME llama loss workload with one
    lever flipped per class, so a regression is attributable to the lever —
    pallas kernels off, fused head CE on, int8 quantized train step."""
    import optax

    import thunder_tpu as tt
    from thunder_tpu import distributed as dist
    from thunder_tpu.models import llama

    cfg = (llama.Config.from_name("Llama-2-7b-hf", n_layer=2) if on_tpu
           else llama.Config.from_name("tiny-llama-debug"))
    cfg_fused = llama.Config.from_name(cfg.name, n_layer=cfg.n_layer, fused_head_ce=True)
    mk = lambda: _family_batch(cfg, on_tpu)  # lazy: allocate when timed

    out = [
        Benchmark("ablate_no_pallas_loss",
                  lambda p, i, t, c, s_, _c=cfg: llama.gpt_loss(p, i, t, c, s_, _c),
                  None, mk, tier="ablation",
                  jit_kwargs={"executors": ["xla", "jax"]}),
        Benchmark("ablate_fused_ce_loss",
                  lambda p, i, t, c, s_, _c=cfg_fused: llama.gpt_loss(p, i, t, c, s_, _c),
                  None, mk, tier="ablation"),
    ]

    # quant on/off: the int8 train step vs the fp train step (same model,
    # same optimizer; donate=False so the timed args survive repeat calls).
    # Params + optimizer state also allocate lazily, inside make_batch; the
    # prejitted fn is the step itself over those args.
    mesh = dist.make_mesh({"dp": 1}, devices=jax.devices()[:1])

    def _mk(quant):
        step = dist.make_train_step(
            lambda p, i, t, c, s_: llama.gpt_loss(p, i, t, c, s_, cfg),
            optax.adamw(1e-4), mesh, donate=False, quant=quant,
        )

        def batch():
            params, idx, tgt, cos, sin = _family_batch(cfg, on_tpu)
            return (params, step.init_optimizer_state(params), idx, tgt, cos, sin)

        return Benchmark(f"ablate_train_step_{quant or 'fp'}",
                         lambda *a: step(*a), None, batch,
                         tier="ablation", prejitted=True)

    out.append(_mk(None))
    out.append(_mk("int8"))
    return out


def all_benchmarks(on_tpu: bool) -> list[Benchmark]:
    return (op_benchmarks(on_tpu) + block_benchmarks(on_tpu)
            + model_benchmarks(on_tpu) + ablation_benchmarks(on_tpu))
