"""Layer: kernels.  Source: device_trace for the time, `kernels/gdn_decode_step.py`
for the bytes.  The least time the chip could take to read and write the
recurrent state of every row the decode steps of the traced stretch held (a
row a linear-attention layer a step; memory bound; rows from the engine's mean
batch occupancy over the window) over the time the operations named
`gdn_decode_step` took.  A fraction of 1.  One reader for every
`gdn_decode_roofline_share.<split>`; each moves its cells' end-to-end metric.
`None` where the trace holds no such operation."""

SHARE_OF_PEAK = True


def window_rows(counters: dict):
    """Mean rows a decode step held inside the window, from the engine's counts
    before and after it."""
    a, b = counters['stats0'], counters['stats1']
    steps = b['decode_steps'] - a['decode_steps']
    if steps <= 0:
        return None
    return (b['mean_batch_occupancy'] * b['decode_steps'] - a['mean_batch_occupancy'] * a['decode_steps']) / steps


def read(ctx):
    from chipbench.common import load_module
    sizes = getattr(ctx['arch'], 'sizes', None)
    if ctx['peaks'] is None or sizes is None:
        return None
    tr, k = ctx['trace'], load_module('kernels', 'gdn_decode_step')
    secs, calls, rows = tr.op_seconds(k.matches), tr.op_count(k.matches), window_rows(ctx['counters'])
    if secs <= 0 or not calls or not rows:
        return None
    return calls * k.least_seconds(ctx['config'], sizes(ctx['config']), rows, ctx['peaks']) / secs
