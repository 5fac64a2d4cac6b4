"""Layer: serving forward (`.hyb`: training step).  Source: device_trace: the share
of the operations' seconds spent in an expert share outside its kernel: the
operations under the scopes `mlp` -> `experts` (`generate.moe_share_mlp`,
`llama.sparse_moe_mlp`: the sort and the plan, the rows gathered into the sorted
buffer and back to their tokens, the SwiGLU's elementwise pass, the casts; in the
backward pass their gradients) whose name does not start with `moe_grouped_mm`,
read from each instruction's `op_name` in the trace's metadata
(`chipbench/op_scopes.py`).  A share of busy, against `mlp_share_of_busy`'s
denominator (the sum over the operations line); no peak.  `None` where the program
writes no such scope.  One reader for every `moe_glue_share_of_busy.<split>`."""


def read(ctx):
    from chipbench import op_scopes
    idx, tr = op_scopes.of(ctx), ctx['trace']
    n = len(tr.devices) or 1
    got = total = 0.0
    for d in tr.devices:
        for o in d.ops:
            total += o.dur / n
            if o.name.startswith('moe_grouped_mm'):
                continue
            parts = op_scopes.components(op_scopes.lookup(idx, o).tf_op)
            if any(a == 'mlp' and b == 'experts' for a, b in zip(parts, parts[1:])):
                got += o.dur / n
    return got / total if total > 0 and got > 0 else None
