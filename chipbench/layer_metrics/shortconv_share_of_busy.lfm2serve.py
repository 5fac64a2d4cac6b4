"""Layer: serving forward.  Source: device_trace: the share of the operations'
seconds spent under the scopes `mixer/conv/*` (a gated short convolution's
`in_proj`, `gate`, `conv` and `out`, `generate.shortconv_mixer`), read from each
instruction's `op_name` in the trace's metadata (`chipbench/op_scopes.py`); the
tails' reads and writes (`mixer/cache`) and the block's norm are the mixer's and
not in it.  A share of busy, against `mixer_share_of_busy`'s denominator (the sum
over the operations line); no peak.  `None` where the program writes no such scope,
as the parent of the PR that brought the layer kind does not.  Moves
serve_out_tok_per_s."""


def read(ctx):
    from chipbench import op_scopes
    idx, tr = op_scopes.of(ctx), ctx['trace']
    n = len(tr.devices) or 1
    got = total = 0.0
    for d in tr.devices:
        for o in d.ops:
            total += o.dur / n
            parts = op_scopes.components(op_scopes.lookup(idx, o).tf_op)
            if any(a == 'mixer' and b == 'conv' for a, b in zip(parts, parts[1:])):
                got += o.dur / n
    return got / total if total > 0 and got > 0 else None
