"""Layer: kernels.  Source: device_trace for the time, `kernels/ssm_scan.py`
(`step_work`) for the bytes.  The least time the chip could take to read and
write the scan's state of every row the decode steps of the traced stretch held
(a row an ssm layer a step: `2 d N` float32; memory bound; rows from the engine's
mean batch occupancy over the window) over the time the operations named
`ssm_decode_step` took.  A fraction of 1.  `None` where the trace holds no such
operation.  Moves serve_out_tok_per_s."""
SHARE_OF_PEAK = True


def read(ctx):
    from chipbench.common import load_module
    sizes = getattr(ctx['arch'], 'sizes', None)
    if ctx['peaks'] is None or sizes is None:
        return None
    tr, k = ctx['trace'], load_module('kernels', 'ssm_scan')
    rows = load_module('layer_metrics', 'gdn_decode_roofline_share').window_rows(ctx['counters'])
    secs, calls = tr.op_seconds(k.is_step), tr.op_count(k.is_step)
    if secs <= 0 or not calls or not rows:
        return None
    return calls * k.step_least_seconds(sizes(ctx['config']), rows, ctx['peaks']) / secs
