"""Layer: kernels.  Source: device_trace for the time, the architecture's
`sizes` for the bytes.  As `paged_attn_decode_roofline_share.lfm2serve`,
for a model whose K/V tables differ by the kind of layer and whose one global
layer's blocks are walked by several layers: the least time the chip could take
to stream what the decode steps of the traced stretch attended.  A layer-step
fetches the K and V it attends, so the global layer's context tokens count once
for that layer and once for each cross layer that walks them (`L_walk_full`
layer-steps: 8 here), at 5,120 B a token, and each window layer adds `min(context,
W)` tokens a row (every context of this mix is past the window: `W` tokens a row a
step, rows from the engine's mean batch occupancy, steps from the walk's calls
over the walks a step).  K and V are counted once a layer-step however many
softmaxes read them.  Over the seconds of the operations named
`paged_attn_decode*` (both kinds' walks).  The products (two softmaxes a head
pair over rows of 128 lanes, a tenth of the bytes' time) are counted once.  A
fraction of 1.  Moves serve_out_tok_per_s."""
SHARE_OF_PEAK = True
ELEM = 2  # bfloat16 KV
NAMES = ('paged_attn_decode', 'paged_attn_verify')


def read(ctx):
    from chipbench.common import load_module
    sizes = getattr(ctx['arch'], 'sizes', None)
    if ctx['peaks'] is None or sizes is None:
        return None
    tr = ctx['trace']
    match = lambda op: op.name.startswith(NAMES)  # noqa: E731
    secs, calls = tr.op_seconds(match), tr.op_count(match)
    ctx_tokens = ctx['host'].get('traced_decode_context_tokens')
    s = sizes(ctx['config'])
    rows = load_module('layer_metrics', 'gdn_decode_roofline_share').window_rows(ctx['counters'])
    if secs <= 0 or not calls or not ctx_tokens or not rows or 'L_walk_full' not in s:
        return None
    row_bytes = 2 * s['ng'] * s['hs'] * ELEM
    steps = calls / (s['L_walk_full'] + s['L_swa'])
    window_tokens = steps * rows * s['W']
    nbytes = (s['L_walk_full'] * ctx_tokens + s['L_swa'] * window_tokens) * row_bytes
    flops = (s['L_walk_full'] * ctx_tokens + s['L_swa'] * window_tokens) * 4 * s['nh'] * 2 * s['hs']
    return max(nbytes / ctx['peaks']['hbm_bytes_per_sec'], flops / ctx['peaks']['bf16_flops_per_sec']) / secs
