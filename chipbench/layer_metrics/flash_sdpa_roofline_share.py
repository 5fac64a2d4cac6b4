"""Layer: kernels.  Source: device_trace for the time, `kernels/flash_sdpa.py` for
the operations and bytes.  The least time the chip could take for the flash
attention calls in the trace (forward, dq and dkv together; compute bound at
this length) over the time they took.  A fraction of 1.
Moves train_tok_per_s_per_chip."""

SHARE_OF_PEAK = True


def read(ctx):
    from chipbench.common import load_module
    if ctx['peaks'] is None:
        return None
    tr, k = ctx['trace'], load_module('kernels', 'flash_sdpa')
    secs = tr.op_seconds(k.matches)
    if secs <= 0:
        return None
    per_chip_batch = ctx['mix']['sequences_per_chip']
    least = k.least_seconds(ctx['config'], ctx['mix']['seq_len'], per_chip_batch, ctx['peaks'],
                            fwd_calls=tr.op_count(k.is_fwd),
                            bwd_calls=tr.op_count(k.is_bwd) / k.BWD_KERNELS_PER_CALL)
    return least / secs
