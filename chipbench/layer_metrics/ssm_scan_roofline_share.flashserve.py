"""Layer: kernels.  Source: device_trace for the time, `kernels/ssm_scan.py`
(`scan_work`) for the work.  The least time the chip could take for the scans of
the traced stretch's whole prefills (`ssm_scan_fwd`, one an ssm layer a prefill;
tokens a call the prefill bucket, from the `thunder_tpu.serve.prefill_dispatch`
span that started the run): the greater of a call's bytes over the HBM peak and
its vector operations over the vector unit's peak (a sixteenth of the matrix
peak; the scan has no matrix product), over the time those calls took.  A
fraction of 1.  `None` where the trace holds no such call, as a parent without
the layer kind does not.  Moves serve_out_tok_per_s."""
SHARE_OF_PEAK = True


def read(ctx):
    from chipbench import program_spans as ps
    from chipbench.common import load_module
    tr, sizes = ctx['trace'], getattr(ctx['arch'], 'sizes', None)
    if ctx['peaks'] is None or sizes is None or not tr.devices:
        return None
    k = load_module('kernels', 'ssm_scan')
    s, dev = sizes(ctx['config']), tr.devices[0]
    least = secs = 0.0
    for sp, run in ps.prefill_pairs(ps.of(ctx), dev.modules):
        calls = [o for o in dev.ops if k.is_scan(o) and run.start <= o.start <= run.start + run.dur]
        tokens = int(str(sp.args.get('bucket', '0x0')).split('x')[0])
        if calls and tokens:
            secs += sum(o.dur for o in calls)
            least += len(calls) * k.scan_least_seconds(s, tokens, ctx['peaks'])
    return least / secs if secs > 0 else None
