"""Layer: kernels.  Source: device_trace for the time, `kernels/paged_attn_decode.py`
for the bytes.  The least time the chip could take to stream the K and V of every
context token the decode steps of the traced stretch attended (memory bound) over
the time `paged_attn_decode` took.  A fraction of 1.  Moves serve_out_tok_per_s."""

SHARE_OF_PEAK = True


def read(ctx):
    from chipbench.common import load_module
    if ctx['peaks'] is None:
        return None
    tr, k = ctx['trace'], load_module('kernels', 'paged_attn_decode')
    secs, ctx_tokens = tr.op_seconds(k.matches), ctx['host'].get('traced_decode_context_tokens')
    if secs <= 0 or not ctx_tokens:
        return None
    return k.least_seconds(ctx['config'], ctx_tokens, ctx['peaks']) / secs
