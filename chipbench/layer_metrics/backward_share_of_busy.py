"""Layer: training step.  Source: device_trace: the share of the operations' seconds
whose `op_name` holds the component `bwd`: the backward trace of a train step, what
the remat pass makes again there included, across the groups (it is no part of the
partition).  `chipbench/op_scopes.py`; denominator as `mixer_share_of_busy`.  One reader
for every `backward_share_of_busy.<split>`.  `None` where the program writes no scopes."""


def read(ctx):
    from chipbench import op_scopes
    return op_scopes.share(ctx, op_scopes.BACKWARD)
