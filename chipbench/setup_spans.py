"""Set-up as a timeline: where ``setup_s`` went, from the program's event ring.

thunder_tpu keeps a bounded ring of Chrome-trace events
(``thunder_tpu.observability.events()``), stamped in microseconds of
``time.perf_counter``: the clock ``run.py``'s ``T_PROCESS`` and the drivers'
``setup_s`` are read on.  Since PR 51 the ring holds a process's start-up: one
``import`` event, a ``serve.compile`` [``kind``, ``bucket``] pair around the
first call of each program a serving engine built, ``compile`` around a
``TrainStep``'s build and ``xla_compile`` [``fn="train_step"``] around its first
call, ``train.snapshot`` around ``train_loop``'s host copy, and one complete
event for each stretch JAX itself timed (``jax.trace``, ``jax.lower``,
``jax.backend_compile`` [``fun_name``]).

:func:`split` puts every second between ``t_process`` and ``t_process +
setup_s`` into exactly one bucket.  On each thread the outermost marked stretch
decides (a ``jax.*`` event under a ``serve.compile`` span is that span's; one
under no span is ``other_programs_s``); a second two threads both spent is
counted once, for the main thread's stretch, and ``overlap_s`` says how many
such seconds there were.  What no stretch covers is ``unspanned_s``.

A program that leaves no ``import`` event (the parent of the PR that brought
it) keeps no such timeline, and a full ring has lost its oldest events: both
give ``None``, never a part sum, and every reader built on this file then
returns ``None``.

By hand, on a file ``tt.export_chrome_trace(path)`` wrote (the whole file is
taken as the set-up: from its first event to its last):

    python3 chipbench/setup_spans.py compile_trace.json
"""
from __future__ import annotations

PREFILL_KINDS = ("prefill", "spec_prefill")      # serve.compile's `kind` starts with one of these
JAX_EVENTS = ("jax.trace", "jax.lower", "jax.backend_compile")
BUCKETS = ("import_s", "prefill_programs_s", "decode_programs_s", "step_programs_s",
           "snapshot_s", "pipeline_s", "other_programs_s")


def bucket_of(name: str, args: dict) -> str | None:
    """The bucket a stretch of this name opens, or None for a span that marks
    something else (``lower``, ``transform:*``, ``serve.recover``, ...), which
    is looked through."""
    if name == "import":
        return "import_s"
    if name == "serve.compile":
        # every kind the engine builds is a prompt's program or a step's:
        # decode*, draft_decode and verify_paged are the second
        return ("prefill_programs_s" if str(args.get("kind", "")).startswith(PREFILL_KINDS)
                else "decode_programs_s")
    if name == "xla_compile":
        # a TrainStep's first call; a tt.jit fusion's first call is the pipeline's
        return "step_programs_s" if args.get("fn") == "train_step" else "pipeline_s"
    if name == "compile":
        return "pipeline_s"
    if name == "train.snapshot":
        return "snapshot_s"
    if name in JAX_EVENTS:
        return "other_programs_s"
    return None


def stretches(events: list) -> list:
    """``(tid, start_us, end_us, bucket, name, args)`` for every marked stretch
    of the ring: a ``B``/``E`` pair matched by name on its thread (the ``E``
    event's arguments laid over the ``B``'s), or one ``X`` event."""
    out, open_ = [], {}
    for e in events:
        ph, name = e.get("ph"), e.get("name")
        args = e.get("args") or {}
        thread = (e.get("pid"), e.get("tid"))
        if ph == "X":
            bucket = bucket_of(name, args)
            if bucket is not None:
                out.append((thread, e["ts"], e["ts"] + e.get("dur", 0.0), bucket, name, args))
        elif ph == "B":
            open_.setdefault(thread, []).append(e)
        elif ph == "E":
            stack = open_.get(thread, [])
            if stack and stack[-1]["name"] == name:
                begin = stack.pop()
                args = {**(begin.get("args") or {}), **args}
                bucket = bucket_of(name, args)
                if bucket is not None:
                    out.append((thread, begin["ts"], e["ts"], bucket, name, args))
    return out


def outermost(stretches_: list) -> list:
    """Of one thread's stretches, those that lie in no other: ``(start, end,
    bucket)`` in time order."""
    roots, covered_to = [], float("-inf")
    # a parent starts no later and ends no earlier; the longer first on a tie
    for _, start, end, bucket, _, _ in sorted(stretches_, key=lambda s: (s[1], -s[2])):
        if start >= covered_to:
            roots.append((start, end, bucket))
            covered_to = end
    return roots


def split(events: list, t0_us: float, t1_us: float, main_thread=None) -> dict | None:
    """Seconds of ``[t0_us, t1_us]`` by bucket, ``unspanned_s`` for the rest,
    ``overlap_s`` for what a second thread spent under a stretch while the
    main thread was under one too; ``by_program`` has the ``jax.*`` events'
    self seconds by ``fun_name`` under each bucket, largest first.  None
    where the ring holds no ``import`` event."""
    marked = stretches(events)
    imports = [s for s in marked if s[4] == "import"]
    if not imports:
        return None
    if main_thread is None:
        main_thread = imports[0][0]
    by_thread: dict = {}
    for s in marked:
        # clipped to the set-up: the window's own events are not its
        start, end = max(s[1], t0_us), min(s[2], t1_us)
        if end > start:
            by_thread.setdefault(s[0], []).append((s[0], start, end, *s[3:]))
    out = dict.fromkeys(BUCKETS, 0.0)
    taken: list = []            # what threads before this one covered: sorted, disjoint
    overlap = 0.0
    for thread in sorted(by_thread, key=lambda t: (t != main_thread, str(t))):
        mine = outermost(by_thread[thread])
        for start, end, bucket in mine:
            free = end - start
            for a, b in taken:
                free -= max(0.0, min(end, b) - max(start, a))
            out[bucket] += free / 1e6
            overlap += (end - start - free) / 1e6
        taken = _union(taken + [(a, b) for a, b, _ in mine])
    total = (t1_us - t0_us) / 1e6
    out["unspanned_s"] = total - sum(out.values())
    out["overlap_s"] = overlap
    out["setup_s"] = total
    out["ring_events"] = len(events)
    out["by_program"] = by_program(by_thread)
    return out


def _union(intervals: list) -> list:
    merged: list = []
    for a, b in sorted(intervals):
        if merged and a <= merged[-1][1]:
            merged[-1] = (merged[-1][0], max(merged[-1][1], b))
        else:
            merged.append((a, b))
    return merged


def by_program(by_thread: dict, rows: int = 8) -> dict:
    """``{bucket: {fun_name: self seconds}}`` of the ``jax.*`` events: each
    event less the ``jax.*`` events inside it, under the bucket of the
    outermost stretch around it; the ``rows`` largest of each bucket."""
    agg: dict = {}

    def close(stack: list, upto: float) -> None:
        while stack and stack[-1][0] <= upto:
            _, bucket, fun, self_us = stack.pop()
            if fun is not None:
                per = agg.setdefault(bucket, {})
                per[fun] = per.get(fun, 0.0) + max(0.0, self_us) / 1e6

    for mine in by_thread.values():
        stack: list = []        # the stretches open here: [end, outermost's bucket, fun_name, self_us]
        for _, start, end, bucket, name, args in sorted(mine, key=lambda s: (s[1], -s[2])):
            close(stack, start)
            fun = args.get("fun_name", "?") if name in JAX_EVENTS else None
            if stack and fun is not None and stack[-1][2] is not None:
                stack[-1][3] -= end - start
            stack.append([end, stack[0][1] if stack else bucket, fun, end - start])
        close(stack, float("inf"))
    return {bucket: dict(sorted(per.items(), key=lambda kv: -kv[1])[:rows])
            for bucket, per in agg.items()}


def of(ctx: dict) -> dict | None:
    """This run's split: read once, in process, from the program's ring, and
    kept in ``ctx`` for the next reader.  None where the ring is full (its
    oldest events are gone) or the program keeps no such timeline."""
    if "setup_split" not in ctx:
        from thunder_tpu import observability as obs

        events = obs.events()
        t0 = ctx["t_process"] * 1e6
        ctx["setup_split"] = (None if len(events) >= obs.event_buffer_capacity()
                              else split(events, t0, t0 + ctx["setup_s"] * 1e6))
    return ctx["setup_split"]


def value(ctx: dict, bucket: str):
    """One bucket of this run's split, or None where there is no split."""
    table = of(ctx)
    return None if table is None else table[bucket]


def main() -> None:
    import json
    import sys

    with open(sys.argv[1]) as f:
        events = [e for e in json.load(f)["traceEvents"] if e.get("ph") != "M"]
    timed = [e for e in events if "ts" in e]
    t0 = min(e["ts"] for e in timed)
    t1 = max(e["ts"] + e.get("dur", 0.0) for e in timed)
    print(json.dumps(split(events, t0, t1), indent=1))


if __name__ == "__main__":
    main()
