"""Drives a training configuration: ``dist.make_train_step`` as
``train_cli.py`` builds it, warmed and checked through ``train_loop``, then
stepped back to back on one fixed batch for the window.

The rate has no whole-step quantisation: steps run until ``--seconds`` have
passed, and the tokens of those whole steps are divided by the time from
the first step's dispatch to the last step's ``block_until_ready``.
"""
from __future__ import annotations

import collections
import functools
import time

import jax
import jax.numpy as jnp
import numpy as np

from chipbench import common, traffic


STEPS_AHEAD = 2


def build(ctx: dict) -> dict:
    """Everything up to a built, warmed step: weights, batch, optimizer."""
    import optax

    from thunder_tpu import distributed as dist
    from thunder_tpu.models import llama

    config, mix, seed = ctx["config"], ctx["mix"], ctx["seed"]
    hf, arch, devices = config, ctx["arch"], ctx["devices"]
    opts = config["train"]
    cfg = llama.Config(**arch.program_config(hf))
    mesh = dist.make_mesh(dict(opts["mesh"]), devices=devices)
    rule = getattr(dist, opts["shardings"])
    params = common.init_on(functools.partial(arch.make_params, hf), common.seed_words(seed),
                            lambda s: rule(s, mesh))
    idx, tgt = traffic.train_batch(mix, seed, len(devices), hf["vocab_size"])
    cos, sin = arch.rope_tables(hf, mix["seq_len"])
    batch = (jnp.asarray(idx), jnp.asarray(tgt), cos, sin)

    def loss_fn(p, i, t, c, s):
        return llama.gpt_loss(p, i, t, c, s, cfg)

    step = dist.make_train_step(loss_fn, optax.adamw(**opts["adamw"]), mesh,
                                **opts.get("step_options", {}))
    opt_state = step.init_optimizer_state(params)
    return {"cfg": cfg, "mesh": mesh, "params": params, "opt_state": opt_state,
            "batch": batch, "step": step, "tokens_per_step": int(idx.size)}


HEAD_ROWS = "lm_head, first rows"


def _sample(leaf, rows: int):
    """The part of a weight's gradient that is compared: a norm's whole, a
    matrix's every k-th row, ``rows`` of them, so that every head and every
    stretch of the width is in it."""
    return leaf if leaf.ndim == 1 else leaf[::max(1, leaf.shape[0] // rows)][:rows]


@functools.partial(jax.jit, static_argnames=("rows", "head_rows"))
def _sampled(tree, scale, *, rows: int, head_rows: int) -> dict:
    """``{path: sample * scale}`` of a tree of gradients, in float32; the
    head's first ``head_rows`` rows are a sample of their own."""
    out = {jax.tree_util.keystr(path): _sample(leaf, rows).astype(jnp.float32) * scale
           for path, leaf in jax.tree_util.tree_flatten_with_path(tree)[0]}
    if "lm_head" in tree:
        out[HEAD_ROWS] = tree["lm_head"][:head_rows].astype(jnp.float32) * scale
    return out


@jax.jit
def _rel_errs(got: dict, ref: dict) -> dict:
    """For each sample, the Frobenius norm of the difference over the reference's."""
    return {k: jnp.linalg.norm(got[k] - g) / jnp.linalg.norm(g) for k, g in ref.items()}


def _reference(ctx: dict, st: dict, *, quant: bool = False) -> tuple[float, dict]:
    """The reference's loss and the samples of its gradient in every weight,
    by the plain backward pass."""
    hf, limits = ctx["config"], ctx["config"]["check"]
    loss, grads = ctx["arch"].ref_loss_and_grads(hf, st["params"], st["batch"][0], st["batch"][1],
                                                 quant=quant)
    samples = {}
    for where, part in grads:
        prefix = jax.tree_util.keystr(tuple(jax.tree_util.DictKey(k) for k in where))
        got = _sampled(part, 1.0, rows=limits["sample_rows"], head_rows=limits["head_grad_rows"])
        # one part at a time on the device too: a host that runs ahead has every
        # block's gradient allocated at once (14.8 GB in use against 11.3)
        jax.block_until_ready(got)
        samples.update({(k if k == HEAD_ROWS else prefix + k): v for k, v in got.items()})
    return loss, samples


def check(ctx: dict, st: dict, *, control: bool = False) -> dict:
    """The comparison that decides ``correct``, outside the window.  The
    reference runs the forward and the backward pass in float32 on the same
    weights and batch and gives the loss and the gradient of the loss in
    every weight.  The program takes one optimizer step through ``train_loop``
    (which also warms the step); AdamW's first moment after one step is
    ``(1 - b1)`` times the gradient, so the program's gradient is read from
    its optimizer state.  Two numbers are compared, each the Frobenius norm of
    the difference over the reference's norm: ``layer_grad_rel_err``, the
    largest such error over the weights of every block and the embedding (a
    sample of rows of each: the gradient reaches them through every block's
    backward pass, the attention kernels' included), and ``grad_rel_err``, the
    same in the head's first rows (which the forward pass alone decides).
    The loss is printed beside them (a mean over thousands of tokens, it hides
    rounding and is held to a loose limit only).  With ``control`` the
    reference with its blocks' matrix products in float8, forward and
    backward, stands in the program's place; it has to fail."""
    from thunder_tpu.executors import pallasex
    from thunder_tpu.train import train_loop

    hf, limits = ctx["config"], ctx["config"]["check"]
    t0 = time.perf_counter()
    ref_loss, ref = _reference(ctx, st)
    out = {"ref_loss": ref_loss, "reference_s": time.perf_counter() - t0}
    if control:
        out["loss0"], got = _reference(ctx, st, quant=True)
    else:
        claims0 = sum(pallasex.stats.values())
        res = train_loop(st["step"], st["params"], st["opt_state"],
                         lambda s: st["batch"], steps=1)
        st["params"], st["opt_state"] = res.params, res.opt_state
        got = _sampled(res.opt_state[0].mu, 1.0 / (1.0 - hf["train"]["adamw"]["b1"]),
                       rows=limits["sample_rows"], head_rows=limits["head_grad_rows"])
        out.update(loss0=float(res.losses[0]), restarts=res.restarts,
                   retries=res.retries, faults=len(res.faults),
                   flash_claims=sum(pallasex.stats.values()) - claims0)
    # an error that is not a number is the largest there is
    by_leaf = {k: float(np.nan_to_num(e, nan=np.inf))
               for k, e in jax.device_get(_rel_errs(got, ref)).items()}
    layers = {k: e for k, e in by_leaf.items() if "lm_head" not in k and "ln_f" not in k}
    worst = max(layers, key=layers.get)
    by_kind: dict[str, float] = {}
    for k, e in by_leaf.items():
        kind = k.rsplit("[", 1)[-1].strip("']")
        by_kind[kind] = max(by_kind.get(kind, 0.0), e)
    out.update(layer_grad_rel_err=layers[worst], layer_grad_rel_err_limit=limits["layer_grad_rel_err_limit"],
               layer_grad_worst_leaf=worst, leaves_compared=len(by_leaf) - 1,
               grad_rel_err_by_kind={k: round(e, 5) for k, e in by_kind.items()},
               grad_rel_err=by_leaf[HEAD_ROWS],
               grad_rel_err_limit=limits["grad_rel_err_limit"],
               loss_err=abs(out["loss0"] - out["ref_loss"]), loss_err_limit=limits["loss_err_limit"])
    ok = (out["layer_grad_rel_err"] <= limits["layer_grad_rel_err_limit"]
          and out["grad_rel_err"] <= limits["grad_rel_err_limit"]
          and out["loss_err"] <= limits["loss_err_limit"])
    if not control:
        ok = (ok and np.isfinite(out["loss0"])
              and (out["restarts"], out["retries"], out["faults"]) == (0, 0, 0)
              and out["flash_claims"] > 0)
    out["ok"] = bool(ok)
    return out


def run(ctx: dict) -> dict:
    from thunder_tpu.core import compile_cache

    st = build(ctx)
    chk = check(ctx, st)
    step, batch = st["step"], st["batch"]
    params, opt_state = st["params"], st["opt_state"]
    seconds, trace_s = ctx["seconds"], ctx["trace_s"]
    cc0 = compile_cache.stats()

    done_t: list[float] = []
    dispatch_ms: list[float] = []
    tracing, untraced_steps = False, 0
    in_flight: collections.deque = collections.deque()
    final_loss = None

    def finish_oldest():
        nonlocal final_loss
        with jax.profiler.TraceAnnotation("chipbench.wait_oldest_step"):
            final_loss = in_flight.popleft()
            final_loss.block_until_ready()
        done_t.append(time.perf_counter())

    setup_s = time.perf_counter() - ctx["t_process"]
    t_first = time.perf_counter()
    while not done_t or done_t[-1] - t_first < seconds:
        with jax.profiler.TraceAnnotation("chipbench.train_step_call"):
            t = time.perf_counter()
            params, opt_state, loss = step(params, opt_state, *batch)
            dispatch_ms.append((time.perf_counter() - t) * 1e3)
        in_flight.append(loss)
        # STEPS_AHEAD steps stay queued behind the one that runs, so a pause of
        # the host shorter than that many steps leaves the device no gap
        if len(in_flight) > STEPS_AHEAD:
            finish_oldest()
        if (ctx["trace_dir"] and not tracing
                and time.perf_counter() - t_first >= seconds - trace_s):
            # trace the window's last stretch: starting costs the host a
            # moment, which a device with steps queued does not feel
            untraced_steps = len(done_t)
            jax.profiler.start_trace(ctx["trace_dir"])
            tracing = True
    while in_flight:
        finish_oldest()
    if tracing:
        jax.profiler.stop_trace()
    final_loss = float(final_loss)
    steps = len(done_t)
    cc1 = compile_cache.stats()
    window_compiles = sum(cc1[k] - cc0[k] for k in ("persistent_cache_hits",
                                                     "persistent_cache_misses"))
    chk["final_loss"] = final_loss
    chk["ok"] = bool(chk["ok"] and np.isfinite(final_loss) and final_loss < chk["loss0"])
    window = done_t[-1] - t_first
    per_chip = st["tokens_per_step"] / len(ctx["devices"])
    return {
        "setup_s": setup_s,
        "end_to_end": {"train_tok_per_s_per_chip": common.whole_step_rate(t_first, done_t, per_chip)},
        "attempted": steps, "failed": 0,
        "check": chk,
        "memory": common.compiled_memory(ctx["devices"]),
        "correct": bool(chk["ok"] and window_compiles == 0),
        "host": {"steps": steps, "window_s": window, "dispatch_ms": dispatch_ms,
                 "tokens_per_step": st["tokens_per_step"],
                 "step_ms_host": window / steps * 1e3,
                 # the rate before the profiler started: starting it can stall
                 # the host for longer than the steps it had queued
                 "untraced_tok_per_s_per_chip": common.whole_step_rate(
                     t_first, done_t[:untraced_steps], per_chip) if untraced_steps else None},
        "counters": {"window_compiles": window_compiles,
                     "programs_built": cc1["persistent_cache_hits"] + cc1["persistent_cache_misses"],
                     "compile_cache": cc1},
    }
