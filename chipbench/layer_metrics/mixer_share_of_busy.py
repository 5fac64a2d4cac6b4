"""Layer: serving forward / training step.  Source: device_trace: the share of the
operations' seconds spent under the scope group `mixer` (attention, the delta rule,
latent attention: projections, rope, cache writes and reads, kernels included), read
from each instruction's `op_name` in the trace's metadata (`chipbench/op_scopes.py`).
One reader for every `mixer_share_of_busy.<split>`; each moves its cells' end-to-end
metric.  The denominator is the sum over the operations line, which is the busy time
plus what nests on that line (a `conditional` and its branch's operations: 1%), so that
the five groups and `unscoped_share_of_busy` sum to one.  `None` where the program
writes no scopes."""


def read(ctx):
    from chipbench import op_scopes
    return op_scopes.share(ctx, 'mixer')
