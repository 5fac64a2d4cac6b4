"""Bytes and operations of a hyper-connection's mixing (`models/generate.py`
`hc_open`, `hc_close`: the operations under the scopes `*/hc/*`), from shapes.
Plain `jax.numpy` today, so in a trace it is whatever XLA fused under those
scopes; a kernel that takes its place does the same work and is counted the same.

A sublayer's hyper-connection can do no better than read its tokens' stream once
(`n * C` elements a token: the norm, the three products and the sublayer's input
all come from that one read) and write the mixed stream once, and read its `phi`
and its flattened norm's weight once a program run: `2 * rows * n * C + (n (n + 2)
+ 1) * n * C` elements.  The maps themselves (`n (n + 2)` numbers a token) and
what the sublayer gives (`C` a token, read once more) are not counted: under a
tenth of it.  The products are `2 * rows * n C * n (n + 2)` operations, a
twentieth of the bytes' time at the v5e's ridge, so bytes bound it; the least time
is the greater of the two all the same.  Two sublayers a layer.
"""
from chipbench.models.latent_hc_moe_decoder import sizes

ELEM = 2  # bfloat16 stream and phi


def under_hc(parts: list) -> bool:
    """Whether an operation's scope path (`op_scopes.components`) lies under a hyper-connection's."""
    return "hc" in parts


def work(hf: dict, rows: float, runs: float) -> dict:
    """All layers, for program runs that passed `rows` tokens in all, `runs` of them."""
    s = sizes(hf)
    n, C, m = s["n"], s["C"], s["n"] * (s["n"] + 2)
    sublayers = 2 * s["L"]
    return {"bytes": sublayers * ELEM * n * C * (2 * rows + (m + 1) * runs),
            "flops": sublayers * 2.0 * rows * n * C * m}


def least_seconds(hf: dict, rows: float, runs: float, peaks: dict) -> float:
    w = work(hf, rows, runs)
    return max(w["bytes"] / peaks["hbm_bytes_per_sec"], w["flops"] / peaks["bf16_flops_per_sec"])


def by_program(ctx: dict) -> dict:
    """The traced stretch's hyper-connections by the program run they fall in:
    `{"prefill" | "decode": {"hc_s", "run_s", "rows", "runs"}}`.  A whole prompt's
    rows are the `tokens` of its `serve.prefill_dispatch` span (the bucket's padded
    tail is mixed too and not counted); a decode step's the engine's slots, which
    the cell's backlog keeps full.  The stretch's first and last decode run are
    cut by its ends and left out, as `decode_step_device_ms` leaves them."""
    import bisect

    from chipbench import op_scopes
    from chipbench import program_spans as ps

    idx, dev = op_scopes.of(ctx), ctx["trace"].devices[0]
    ops = sorted((o for o in dev.ops if under_hc(op_scopes.components(op_scopes.lookup(idx, o).tf_op))),
                 key=lambda o: o.start)
    starts = [o.start for o in ops]

    def inside(run):
        lo, hi = bisect.bisect_left(starts, run.start), bisect.bisect_right(starts, run.start + run.dur)
        return sum(o.dur for o in ops[lo:hi])

    out = {k: {"hc_s": 0.0, "run_s": 0.0, "rows": 0, "runs": 0} for k in ("prefill", "decode")}

    def count(kind, run, rows):
        took = inside(run)
        if took > 0 and rows:
            d = out[kind]
            d["hc_s"], d["run_s"], d["rows"], d["runs"] = d["hc_s"] + took, d["run_s"] + run.dur, d["rows"] + rows, d["runs"] + 1

    for run in [m for m in dev.modules if "decode" in m.name][1:-1]:
        count("decode", run, ctx["mix"]["engine"]["max_batch"])
    for sp, run in ps.prefill_pairs(ps.of(ctx), dev.modules):
        count("prefill", run, sp.args.get("tokens", 0))
    return out
