"""Layer: serving forward.  Source: device_trace for the time (the runs of the
prefill programs on the modules line), the `tokens` argument of the
`thunder_tpu.serve.prefill_dispatch` spans for the work: device milliseconds a
thousand prompt tokens prefilled in the traced stretch, each span paired with the
run it started (`program_spans.prefill_pairs`).  One reader for every
`prefill_device_ms_per_ktok.<split>`; each moves its cells' end-to-end metric.
`None` where no whole prefill fell in the stretch, or the program opens no such
spans."""


def read(ctx):
    from chipbench import program_spans as ps
    tr = ctx['trace']
    if not tr.devices:
        return None
    pairs = ps.prefill_pairs(ps.of(ctx), tr.devices[0].modules)
    tokens = sum(sp.args.get('tokens', 0) for sp, _ in pairs)
    return 1e3 * sum(run.dur for _, run in pairs) / (tokens / 1e3) if tokens else None
