"""Layer: serving forward / training step.  Source: device_trace: the share of the
operations' seconds spent under the scope group `mlp` (norm, router, experts with
`moe_grouped_mm*`, shared expert, the dense FFN's products, the residual sum), read
from each instruction's `op_name` (`chipbench/op_scopes.py`; denominator as
`mixer_share_of_busy`).  One reader for every `mlp_share_of_busy.<split>`.  `None`
where the program writes no scopes."""


def read(ctx):
    from chipbench import op_scopes
    return op_scopes.share(ctx, 'mlp')
