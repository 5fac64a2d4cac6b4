"""Layer: kernels.  Source: device_trace for the time, the architecture's `sizes`
for the bytes.  As `paged_attn_decode_roofline_share.offline`, for a model whose
K and V live in its full-attention layers only: the least time the chip could
take to stream the K and V of every context token the decode steps of the
traced stretch attended, over `L_full` layers (4 of this configuration's 16;
`kernels/paged_attn_decode.py` multiplies by every layer of a dense decoder),
over the time `paged_attn_decode` took.  A fraction of 1.  Moves serve_out_tok_per_s."""

SHARE_OF_PEAK = True
ELEM = 2  # bfloat16 KV


def read(ctx):
    from chipbench.common import load_module
    sizes = getattr(ctx['arch'], 'sizes', None)
    if ctx['peaks'] is None or sizes is None:
        return None
    tr, k = ctx['trace'], load_module('kernels', 'paged_attn_decode')
    secs, ctx_tokens = tr.op_seconds(k.matches), ctx['host'].get('traced_decode_context_tokens')
    if secs <= 0 or not ctx_tokens:
        return None
    s = sizes(ctx['config'])
    layers = s.get('L_full', s['L'])
    nbytes = layers * ctx_tokens * 2 * s['ng'] * s['hs'] * ELEM
    flops = layers * ctx_tokens * 4 * s['nh'] * s['hs']
    return max(nbytes / ctx['peaks']['hbm_bytes_per_sec'], flops / ctx['peaks']['bf16_flops_per_sec']) / secs
