"""Drives a serving configuration: ``tt.serve(None, params, cfg)`` with the
options the configuration and the mix state, warmed and checked on a sample
of the mix's own requests, then loaded by one single-threaded generator.

The loop is closed: the whole backlog is submitted before the window; the
window opens at an engine-step boundary once every slot is taken and closes
at the first boundary after ``--seconds``; output tokens a second are the
tokens the host saw between the two boundaries over the time between them.
"""
from __future__ import annotations

import functools
import heapq
import time

import jax
import jax.numpy as jnp
import numpy as np

from chipbench import common, traffic


class Client:
    """What one request's user sees: when each token arrived."""

    __slots__ = ("req", "submit_t", "times", "tokens", "handle")

    def __init__(self, req):
        self.req = req
        self.submit_t = None
        self.times: list[float] = []
        self.tokens: list[int] = []
        self.handle = None

    def on_token(self, tok):
        self.times.append(time.perf_counter())
        self.tokens.append(int(tok))


def build(ctx: dict, **engine_overrides) -> dict:
    import thunder_tpu as tt
    from thunder_tpu.models import llama

    config, mix, seed = ctx["config"], ctx["mix"], ctx["seed"]
    hf, arch = config, ctx["arch"]
    cfg = llama.Config(**arch.program_config(hf))
    with jax.default_device(ctx["devices"][0]):
        params = common.init_on(functools.partial(arch.make_params, hf), common.seed_words(seed))
    kw = {**config["engine"], **mix["engine"], **engine_overrides}
    eng = tt.serve(None, params, cfg, goodput=True, **kw)
    return {"cfg": cfg, "params": params, "engine": eng, "engine_kw": kw}


def submit(eng, client: Client, seed: int, vocab: int):
    r = client.req
    client.submit_t = time.perf_counter()
    client.handle = eng.submit(traffic.prompt_tokens(seed, r.index, r.prompt_len, vocab),
                               max_new_tokens=r.new_tokens, stream_cb=client.on_token)


def drain(eng, clients) -> None:
    while not all(c.handle.done() for c in clients):
        eng.step()


def check(ctx: dict, st: dict) -> dict:
    """The comparison that decides ``correct``, outside the window.  A sample
    of the mix's requests, one a prefill bucket, is served together (prefill,
    then decode through the paged cache at a batch above one); the reference
    then runs the full forward pass over each request's prompt and served
    tokens, and says how far below its own best logit the served token's
    logit lies at every position.  A served token is the engine's argmax, so
    a sound engine picks the reference's best token or a near tie, and one
    that computes in a lower precision, or reads a wrong cache slot, picks
    tokens the reference ranks clearly lower.  The mean of that shortfall is
    the number compared."""
    eng, hf, arch, seed = st["engine"], ctx["config"], ctx["arch"], ctx["seed"]
    spec = ctx["mix"]["check"]
    vocab = hf["vocab_size"]
    clients = [Client(traffic.Req(10_000_000 + i, p, n))
               for i, (p, n) in enumerate(spec["requests"])]
    t0 = time.perf_counter()
    for c in clients:
        submit(eng, c, seed, vocab)
    drain(eng, clients)
    served_s = time.perf_counter() - t0
    # every decode batch bucket the window can reach, warmed by that many
    # short requests at once
    for b in spec.get("warm_batches", []):
        extra = [Client(traffic.Req(20_000_000 + 1000 * b + i, *spec["warm_request"]))
                 for i in range(b)]
        for c in extra:
            submit(eng, c, seed, vocab)
        drain(eng, extra)
    t0 = time.perf_counter()
    pad = spec["reference_pad"]
    gaps, agree, finishes = [], 0, []
    for c in clients:
        res = c.handle.result(drive=False)
        finishes.append(res.finish_reason)
        r = c.req
        toks = np.concatenate([traffic.prompt_tokens(seed, r.index, r.prompt_len, vocab),
                               np.asarray(c.tokens, np.int32)])
        n = len(c.tokens)
        padded = np.zeros(-(-len(toks) // pad) * pad, np.int32)
        padded[:len(toks)] = toks
        positions = jnp.arange(r.prompt_len - 1, r.prompt_len - 1 + n)
        with jax.default_device(ctx["devices"][0]):
            lg = arch.ref_logits(hf, st["params"], jnp.asarray(padded), positions)
            best = jnp.max(lg, axis=-1)
            took = jnp.take_along_axis(lg, jnp.asarray(c.tokens)[:, None], axis=-1)[:, 0]
            g = np.asarray(best - took)
        gaps.extend(g.tolist())
        agree += int(np.sum(g == 0.0))
    mean_gap = float(np.mean(gaps))
    ok = (mean_gap <= spec["mean_logit_shortfall_limit"]
          and all(f == "length" for f in finishes)
          and all(len(c.tokens) == c.req.new_tokens for c in clients))
    return {"mean_logit_shortfall": mean_gap,
            "mean_logit_shortfall_limit": spec["mean_logit_shortfall_limit"],
            "max_logit_shortfall": float(np.max(gaps)),
            "tokens_compared": len(gaps), "argmax_agree_share": agree / len(gaps),
            "finish_reasons": sorted(set(finishes)), "served_s": served_s,
            "reference_s": time.perf_counter() - t0, "ok": bool(ok)}


def program_counts(eng) -> int:
    return sum(eng.stats()["compile_counts"].values())


def run(ctx: dict) -> dict:
    st = build(ctx)
    chk = check(ctx, st)
    out = measure(ctx, st, chk)
    st["engine"].shutdown(drain=False)
    return out


def measure(ctx: dict, st: dict, chk: dict) -> dict:
    """The lead-in and the window, on a warmed engine."""
    from thunder_tpu.core import compile_cache

    eng, mix, seed = st["engine"], ctx["mix"], ctx["seed"]
    vocab = ctx["config"]["vocab_size"]
    seconds, trace_s = ctx["seconds"], ctx["trace_s"]
    clients = [Client(r) for r in traffic.schedule(mix, seed)]
    for c in clients:
        submit(eng, c, seed, vocab)
    slots = st["engine_kw"]["max_batch"]
    # lead-in: until every slot holds a request that has its first token
    while sum(1 for c in clients if c.times) < slots:
        eng.step()
    for _ in range(int(mix.get("lead_in_steps", 0))):
        eng.step()

    stats0 = eng.stats()
    cc0 = compile_cache.stats()
    progs0 = program_counts(eng)
    setup_s = time.perf_counter() - ctx["t_process"]
    w0 = time.perf_counter()                 # the window opens
    emitted0 = sum(len(c.times) for c in clients)
    tracing, trace_t0 = False, 0.0
    step_ms: list[float] = []
    while True:
        now = time.perf_counter()
        if now - w0 >= seconds:
            break
        if ctx["trace_dir"] and not tracing and now - w0 >= seconds - trace_s:
            jax.profiler.start_trace(ctx["trace_dir"])
            tracing, trace_t0 = True, time.perf_counter()
        with jax.profiler.TraceAnnotation("chipbench.engine_step"):
            ts = time.perf_counter()
            eng.step()
            step_ms.append((time.perf_counter() - ts) * 1e3)
    w1 = time.perf_counter()
    emitted1 = sum(len(c.times) for c in clients)
    traced = {}
    if tracing:
        # what the traced stretch held, for the readers that divide device
        # time by work: context tokens the decode steps attended (a token
        # emitted as a request's k-th, k >= 1, read prompt_len + k of them)
        traced = {
            "traced_decode_context_tokens": sum(
                c.req.prompt_len + k for c in clients
                for k, t in enumerate(c.times) if k >= 1 and trace_t0 <= t <= w1),
            "traced_s": w1 - trace_t0}
        jax.profiler.stop_trace()
    stats1 = eng.stats()
    cc1 = compile_cache.stats()
    window_compiles = (program_counts(eng) - progs0) + sum(
        cc1[k] - cc0[k] for k in ("persistent_cache_hits", "persistent_cache_misses"))

    # what the users saw
    in_window = lambda t: w0 <= t <= w1  # noqa: E731
    gaps_ms = []
    finished = bad_finish = 0
    for c in clients:
        ts = c.times
        gaps_ms += [(b - a) * 1e3 for a, b in zip(ts, ts[1:]) if in_window(a) and in_window(b)]
        if c.handle.done():
            finished += 1
            res = c.handle.result(drive=False)
            if res.finish_reason != "length" or len(c.tokens) != c.req.new_tokens:
                bad_finish += 1
    attn = stats1["attn"]
    faults = {"window_compiles": window_compiles, "attn_fallback_steps": attn["fallback_steps"],
              "recoveries": stats1["recoveries"], "bad_finishes": bad_finish}
    window = w1 - w0
    return {
        "setup_s": setup_s,
        "end_to_end": {"serve_out_tok_per_s": (emitted1 - emitted0) / window},
        "attempted": len(clients), "failed": bad_finish,
        "check": {**chk, **faults},
        "memory": common.compiled_memory(ctx["devices"]),
        "correct": bool(chk["ok"] and not any(faults.values())),
        "host": {"window_s": window, "engine_step_ms": step_ms, "finished": finished,
                 "engine_step_max_ms": max(step_ms), "engine_steps": len(step_ms),
                 # where and how long: one step over a second among thousands of 17 ms is a pause
                 # of the machine, a run of slow ones is the program's
                 "slowest_steps": " ".join(f"{i}:{ms / 1e3:.4f}s" for i, ms in heapq.nlargest(
                     5, enumerate(step_ms), key=lambda step: step[1])),
                 "gap_percentiles_ms": {q: common.percentile(gaps_ms, q)
                                        for q in (50, 75, 90, 95, 97, 99)} if gaps_ms else {},
                 "tokens_in_window": emitted1 - emitted0, **traced,
                 "offered": traffic.totals([c.req for c in clients])},
        "counters": {"window_compiles": window_compiles,
                     "programs_built": cc1["persistent_cache_hits"] + cc1["persistent_cache_misses"],
                     "engine_programs": stats1["compile_counts"],
                     "compile_cache": cc1, "stats0": _slim(stats0), "stats1": _slim(stats1)},
    }


def _slim(stats: dict) -> dict:
    keep = ("decode_steps", "prefill_runs", "tokens_generated", "mean_batch_occupancy",
            "host_visits", "pool_utilization", "pool_occupancy", "goodput", "arena_bytes",
            "queue_depth", "running", "step_calls", "recoveries", "decode_rebuild", "prefill_fresh_runs")
    return {k: stats[k] for k in keep if k in stats}
