"""Layer: kernels.  Source: device_trace for the time, the architecture's `sizes`
for the bytes.  As `paged_attn_decode_roofline_share.hybserve`, for a model whose K
and V live in its `full_attention` layers only and whose heads are narrower than a
lane tile: the least time the chip could take to stream the K and V of every context
token the decode steps of the traced stretch attended, over `L_attn` layers (3 of
this configuration's 12), at the bytes as *counted* (`2 * ng * hs` elements a token a
layer: 6,144 B a token here, whatever the arena pads them to), over the seconds of
the decode attention's operations: the custom calls named `paged_attn_decode*`, the
walk, and `paged_attn_verify*`, which is what a decode step's attention is called
where it goes a block a grid step (the work is the operation's, whatever implements
it: that path reads low, not `None`).  The products (`4 * nh * hs` operations a
context token a layer, doubled on a lane-packed arena where half are on zeros, and
still a tenth of the bytes' time) are counted once.  A fraction of 1.  Moves
serve_out_tok_per_s."""

SHARE_OF_PEAK = True
ELEM = 2  # bfloat16 KV
NAMES = ('paged_attn_decode', 'paged_attn_verify')


def read(ctx):
    sizes = getattr(ctx['arch'], 'sizes', None)
    if ctx['peaks'] is None or sizes is None:
        return None
    secs = ctx['trace'].op_seconds(lambda op: op.name.startswith(NAMES))
    ctx_tokens = ctx['host'].get('traced_decode_context_tokens')
    s = sizes(ctx['config'])
    if secs <= 0 or not ctx_tokens or 'L_attn' not in s:
        return None
    nbytes = s['L_attn'] * ctx_tokens * 2 * s['ng'] * s['hs'] * ELEM
    flops = s['L_attn'] * ctx_tokens * 4 * s['nh'] * s['hs']
    return max(nbytes / ctx['peaks']['hbm_bytes_per_sec'], flops / ctx['peaks']['bf16_flops_per_sec']) / secs
