"""Layer: kernels.  Source: device_trace for the time, the engine's own count for
the bytes (`engine.stats()["attn"]["attended_tokens"]` before and after the window,
kept by `drivers/serve_window.py`).  As `paged_attn_decode_roofline_share.flashserve`,
for a mix whose contexts lie inside and past the window at once: that reader counts
`W` keys a window layer-step a row, which a request that never leaves its window
does not read.  Here a layer-step fetches the K and V its rows attend by the
engine's count: every key of the context in a global layer (`L_walk_full`
layer-steps), `min(context, W)` in a window layer (`L_swa`), 2,048 B a token a layer
at 4 KV heads of 128.  The count spans the window (and the lead-in's few steps);
the traced stretch's share of it is its decode steps (the walk's calls over the
walks a step) times the window's mean keys a step of each kind.  Over the seconds of
the operations named `paged_attn_decode*` (both kinds' walks).  The products (`4 nh
hs` operations a key: 7 query heads a KV head, a seventh of the bytes' time) are
counted once and the greater time taken.  A fraction of 1.  `None` where the engine
keeps no such count, as another architecture's and a parent without it do not, or
the trace holds no walk.  Moves serve_out_tok_per_s."""
SHARE_OF_PEAK = True
ELEM = 2  # bfloat16 KV
NAMES = ('paged_attn_decode', 'paged_attn_verify')


def window_keys_a_step(counters: dict):
    """``(global, window)``: the mean keys a decode step's rows attended in a layer of each kind, or None."""
    a, b = (counters.get(k, {}).get('attended_tokens') for k in ('stats0', 'stats1'))
    if not a or not b or b['steps'] <= a['steps']:
        return None
    steps = b['steps'] - a['steps']
    return tuple((b[kind] - a[kind]) / steps for kind in ('full_attention', 'sliding_attention'))


def read(ctx):
    sizes = getattr(ctx['arch'], 'sizes', None)
    keys = window_keys_a_step(ctx['counters'])
    if ctx['peaks'] is None or sizes is None or keys is None:
        return None
    s = sizes(ctx['config'])
    if 'L_walk_full' not in s or 'L_swa' not in s:
        return None
    tr = ctx['trace']
    match = lambda op: op.name.startswith(NAMES)  # noqa: E731
    secs, calls = tr.op_seconds(match), tr.op_count(match)
    if secs <= 0 or not calls:
        return None
    steps = calls / (s['L_walk_full'] + s['L_swa'])
    tokens = steps * (s['L_walk_full'] * keys[0] + s['L_swa'] * keys[1])
    nbytes = tokens * 2 * s['ng'] * s['hs'] * ELEM
    flops = tokens * 4 * s['nh'] * s['hs']
    return max(nbytes / ctx['peaks']['hbm_bytes_per_sec'], flops / ctx['peaks']['bf16_flops_per_sec']) / secs
