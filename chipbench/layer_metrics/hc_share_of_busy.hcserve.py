"""Layer: serving forward.  Source: device_trace: the share of the operations'
seconds spent under the scopes `*/hc/*` (a hyper-connection's `open`: the norm
over the flattened streams, the three products, the Sinkhorn iterations and what
the sublayer reads; and its `close`: the streams mixed and written back;
`generate.hc_open`, `hc_close`), read from each instruction's `op_name` in the
trace's metadata (`chipbench/op_scopes.py`).  They lie inside the groups `mixer`
and `mlp` and count there too.  A share of busy, against `mixer_share_of_busy`'s
denominator (the sum over the operations line); no peak.  `None` where the program
writes no such scope, as the parent of the PR that brought the block does not.
Moves serve_out_tok_per_s."""


def read(ctx):
    from chipbench import op_scopes
    from chipbench.common import load_module
    under_hc = load_module('kernels', 'hc_mix').under_hc
    idx, tr = op_scopes.of(ctx), ctx['trace']
    n = len(tr.devices) or 1
    got = total = 0.0
    for d in tr.devices:
        for o in d.ops:
            total += o.dur / n
            if under_hc(op_scopes.components(op_scopes.lookup(idx, o).tf_op)):
                got += o.dur / n
    return got / total if total > 0 and got > 0 else None
