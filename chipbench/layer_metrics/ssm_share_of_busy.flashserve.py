"""Layer: serving forward.  Source: device_trace: the share of the operations'
seconds spent under the scopes `mixer/ssm/*` (a selective scan's `in_proj`,
`conv`, `scan`, `out` and its slot's reads and writes, `generate.ssm_mixer`; the
kernels `ssm_scan_fwd` and `ssm_decode_step` carry the scope of their call), read
from each instruction's `op_name` in the trace's metadata (`chipbench/op_scopes.py`).
A share of busy, against `mixer_share_of_busy`'s denominator; no peak.  `None`
where the program writes no such scope, as the parent of the PR that brought the
layer kind does not.  Moves serve_out_tok_per_s."""


def under(ctx, inside) -> float | None:
    """The share of the operations' seconds whose scope path `inside(parts)` accepts."""
    from chipbench import op_scopes
    idx, tr = op_scopes.of(ctx), ctx['trace']
    n = len(tr.devices) or 1
    got = total = 0.0
    for d in tr.devices:
        for o in d.ops:
            total += o.dur / n
            if inside(op_scopes.components(op_scopes.lookup(idx, o).tf_op)):
                got += o.dur / n
    return got / total if total > 0 and got > 0 else None


def pair(parts, a, b) -> bool:
    return any(x == a and y == b for x, y in zip(parts, parts[1:]))


def read(ctx):
    return under(ctx, lambda parts: pair(parts, 'mixer', 'ssm'))
