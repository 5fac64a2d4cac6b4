"""Layer: kernels.  Source: device_trace for the time, `kernels/gdn_chunk.py` for
the operations and bytes.  The least time the chip could take for the calls of
the chunked gated delta rule in the trace (`gdn_chunk_fwd`, twice a layer a
training step, and `gdn_chunk_bwd`; the chunked algorithm's products and their
derivatives, the inverse counted as a substitution) over the time they took.
A fraction of 1.  Moves train_tok_per_s_per_chip."""

SHARE_OF_PEAK = True


def read(ctx):
    from chipbench.common import load_module
    if ctx['peaks'] is None:
        return None
    tr, k = ctx['trace'], load_module('kernels', 'gdn_chunk')
    secs = tr.op_seconds(k.matches)
    if secs <= 0:
        return None
    least = k.least_seconds(ctx['config'], ctx['mix']['seq_len'], ctx['mix']['sequences_per_chip'],
                            ctx['peaks'], fwd_calls=tr.op_count(k.is_fwd), bwd_calls=tr.op_count(k.is_bwd))
    return least / secs
