"""Layer: kernels.  Source: device_trace for the time, `kernels/causal_conv1d.py`
for the bytes.  The least time the chip could take for the calls of the
DeltaNet layers' causal conv in the trace (`causal_conv1d_fwd`,
`causal_conv1d_bwd` by name; memory bound: x in and out a forward call, the
gradient and x in and dx out a backward call, over the chip's HBM rate) over
the time they took.  A fraction of 1.  `None` for a program without such
operations (before PR 37 the conv ran in XLA fusions).  One reader for every
`causal_conv_roofline_share.<split>`.  Moves train_tok_per_s_per_chip."""

SHARE_OF_PEAK = True


def read(ctx):
    from chipbench.common import load_module
    if ctx['peaks'] is None:
        return None
    tr, k = ctx['trace'], load_module('kernels', 'causal_conv1d')
    secs = tr.op_seconds(k.matches)
    if secs <= 0:
        return None
    least = k.least_seconds(ctx['config'], ctx['mix']['seq_len'], ctx['mix']['sequences_per_chip'],
                            ctx['peaks'], fwd_calls=tr.op_count(k.is_fwd), bwd_calls=tr.op_count(k.is_bwd))
    return least / secs
