"""Layer: kernels.  Source: device_trace for the time, `kernels/ssd_chunk.py`
(`call_work`) for the work.  The least time the chip could take for the chunked
scans of the traced stretch's whole prefills (`ssd_chunk_fwd`, one a Mamba-2 layer
a prefill; tokens a call the prefill bucket, from the
`thunder_tpu.serve.prefill_dispatch` span that started the run; chunks of the
configuration's `chunk_size`): the greater of a call's products over the matrix
peak and its bytes over the HBM peak, over the time those calls took.  A fraction
of 1.  `None` where the trace holds no such call, as a parent without the layer
kind does not.  Moves serve_out_tok_per_s."""
SHARE_OF_PEAK = True


def read(ctx):
    from chipbench import program_spans as ps
    from chipbench.common import load_module
    tr, sizes = ctx['trace'], getattr(ctx['arch'], 'sizes', None)
    if ctx['peaks'] is None or sizes is None or not tr.devices:
        return None
    k = load_module('kernels', 'ssd_chunk')
    s, dev, chunk = sizes(ctx['config']), tr.devices[0], ctx['config'].get('chunk_size', 128)
    least = secs = 0.0
    for sp, run in ps.prefill_pairs(ps.of(ctx), dev.modules):
        calls = [o for o in dev.ops if k.matches(o) and run.start <= o.start <= run.start + run.dur]
        tokens = int(str(sp.args.get('bucket', '0x0')).split('x')[0])
        if calls and tokens:
            secs += sum(o.dur for o in calls)
            least += len(calls) * k.least_seconds(s, tokens, chunk, ctx['peaks'])
    return least / secs if secs > 0 else None
