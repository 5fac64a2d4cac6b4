"""Layer: serving forward / training step.  Source: device_trace: the share of the
operations' seconds spent under the scope groups `head` (last norm, logits, loss or
sampling) and `embed` (the tokens' rows and their positions' rows of the rope tables),
read from each instruction's `op_name` (`chipbench/op_scopes.py`; denominator as
`mixer_share_of_busy`).  One reader for every `head_share_of_busy.<split>`.  `None`
where the program writes no scopes."""


def read(ctx):
    from chipbench import op_scopes
    return op_scopes.share(ctx, 'head', 'embed')
