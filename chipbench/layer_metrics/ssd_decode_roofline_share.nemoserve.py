"""Layer: kernels.  Source: device_trace for the time, `kernels/ssd_decode_step.py`
(`call_work`) for the bytes.  The least time the chip could take to read and
write the Mamba-2 state of every row the decode steps of the traced stretch held
(a row a Mamba-2 layer a step: `2 d N` float32 = 8.39 MB at 128 heads of 64 and
128 states; memory bound; rows from the engine's mean batch occupancy over the
window) over the time the operations named `ssd_decode_step` took.  A fraction of
1.  `None` where the trace holds no such operation, as a parent without the layer
kind does not.  Moves serve_out_tok_per_s."""
SHARE_OF_PEAK = True


def read(ctx):
    from chipbench.common import load_module
    sizes = getattr(ctx['arch'], 'sizes', None)
    if ctx['peaks'] is None or sizes is None:
        return None
    tr, k = ctx['trace'], load_module('kernels', 'ssd_decode_step')
    rows = load_module('layer_metrics', 'gdn_decode_roofline_share').window_rows(ctx['counters'])
    secs, calls = tr.op_seconds(k.matches), tr.op_count(k.matches)
    if secs <= 0 or not calls or not rows:
        return None
    return calls * k.least_seconds(sizes(ctx['config']), rows, ctx['peaks']) / secs
