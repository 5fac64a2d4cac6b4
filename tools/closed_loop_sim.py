"""What a ``closed`` mix would read before any chip time is spent on it: the engine's
loop on the generator's own schedule, on the host, with three costs for the chip.

    python3 tools/closed_loop_sim.py --workload <cell> [--seeds 40] [--step-ms 13.4] [--ctx-ns 25] [--prefill-us 79]
                                     [--new-tokens 512:1472] [--vary 0.03]

An engine step admits one waiting request where a slot is free (a whole-prompt
prefill: ``prefill-us`` a token of its bucket) and then decodes every running row
(``step-ms`` and ``ctx-ns`` a token of the rows' contexts).  The window opens as
``drivers/serve.py`` opens it (every slot has its first token, then
``lead_in_steps`` more steps) and closes after the first step that ends past
``--seconds``.  A line a seed: output tokens a second, decode steps and prefills in
the window; then the spread over the seeds (the quartiles' distance over the
median, as the driver takes it).  ``--vary f`` repeats the seeds with the step's
and the prefill's cost each moved by ``-f, 0, +f``: a mix whose spread swings with
them has a request's end, and the prefill behind it, at the window's close.
``--new-tokens lo:hi`` puts 16 evenly spaced values in the place of the group's
(the same order).  The defaults are the fit to eight runs of
``trinity-mini-serve-1chip.offline-docqa`` at 512-1472 new tokens (PERF.md section
6, PR 48): every run's 22 or 23 prefills, its decode steps within 1.5%.  A count,
not a measurement: PERF.md takes rates from the chip alone."""
from __future__ import annotations

import argparse
import copy
import os
import statistics
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

from chipbench import common, traffic  # noqa: E402


def window(mix: dict, seed: int, seconds: float, step_s: float, ctx_s: float, prefill_s: float) -> tuple:
    """``(tokens a second, decode steps, prefills)`` of the window."""
    eng = mix["engine"]
    slots, buckets = eng["max_batch"], eng["prefill_buckets"]
    queue, rows = list(traffic.schedule(mix, seed)), []      # a row: [context, tokens still to decode]
    t, opened = 0.0, None
    first = after = tokens = steps = prefills = 0
    while True:
        if queue and len(rows) < slots:
            r = queue.pop(0)
            t += prefill_s * next(b for b in buckets if b >= r.prompt_len)
            rows.append([r.prompt_len + 1, r.new_tokens - 1])
            first += 1
            if opened is not None:
                tokens, prefills = tokens + 1, prefills + 1
        t += step_s + ctx_s * sum(c for c, _ in rows)
        n = len(rows)
        rows = [[c + 1, left - 1] for c, left in rows if left > 1]
        if opened is not None:
            tokens, steps = tokens + n, steps + 1
            if t - opened >= seconds:
                return tokens / (t - opened), steps, prefills
        elif first >= slots:
            after += 1
            if after >= mix.get("lead_in_steps", 0):
                opened = t


def spread(values: list) -> float:
    q = statistics.quantiles(values, n=4)
    return (q[2] - q[0]) / statistics.median(values)


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, default=40)
    ap.add_argument("--seconds", type=float, default=30.0)
    ap.add_argument("--step-ms", type=float, default=13.4)
    ap.add_argument("--ctx-ns", type=float, default=25.0)
    ap.add_argument("--prefill-us", type=float, default=79.0)
    ap.add_argument("--new-tokens", default="")
    ap.add_argument("--vary", type=float, default=0.0)
    args = ap.parse_args()
    _, _, mix = common.open_cell(args.workload)
    if args.new_tokens:
        lo, hi = (int(v) for v in args.new_tokens.split(":"))
        rank = {n: i for i, n in enumerate(sorted({n for _, n in mix["group"]}))}
        mix = copy.deepcopy(mix)
        mix["group"] = [[p, round(lo + (hi - lo) * rank[n] / (len(rank) - 1))] for p, n in mix["group"]]
    seeds = [1000 + 7 * i for i in range(args.seeds // 2)] + [3_000_000_000 + 13 * i for i in range(args.seeds - args.seeds // 2)]
    moves = (-args.vary, 0.0, args.vary) if args.vary else (0.0,)
    for ds in moves:
        for dp in moves:
            runs = [window(mix, s, args.seconds, args.step_ms * 1e-3 * (1 + ds), args.ctx_ns * 1e-9,
                           args.prefill_us * 1e-6 * (1 + dp)) for s in seeds]
            if not args.vary:
                for s, (rate, steps, prefills) in zip(seeds, runs):
                    print(f"seed {s}: {rate:.1f} tokens/s, {steps} decode steps, {prefills} prefills")
            rates = [r[0] for r in runs]
            print(f"step {ds:+.0%} prefill {dp:+.0%}: spread {spread(rates):.4f}, median {statistics.median(rates):.1f}, "
                  f"{min(rates):.1f}-{max(rates):.1f}, prefills {sorted({r[2] for r in runs})}")


if __name__ == "__main__":
    main()
