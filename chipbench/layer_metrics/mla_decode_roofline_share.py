"""Layer: kernels.  Source: device_trace for the time, `kernels/mla_paged_decode.py`
for the bytes and operations.  The least time the chip could take to read, once
for all heads, the latent row of every context token the decode steps of the
traced stretch attended, or to do the heads' products over them (the greater of
the two: the kernel sits at the chip's ridge), over every layer, over the time
the operations named `mla_paged_decode` took.  Context tokens from the host's
count (`traced_decode_context_tokens`).  A fraction of 1.  One reader for every
`mla_decode_roofline_share.<split>`; `None` where the trace holds no such
operation."""

SHARE_OF_PEAK = True


def read(ctx):
    from chipbench.common import load_module
    if ctx['peaks'] is None or not hasattr(ctx['arch'], 'latent_bytes_per_token'):
        return None
    tr, k = ctx['trace'], load_module('kernels', 'mla_paged_decode')
    secs, ctx_tokens = tr.op_seconds(k.matches), ctx['host'].get('traced_decode_context_tokens')
    if secs <= 0 or not ctx_tokens:
        return None
    return k.least_seconds(ctx['config'], ctx_tokens, ctx['peaks']) / secs
