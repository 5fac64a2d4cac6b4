"""Layer: serving engine.  Source: program_span (`thunder_tpu.serve.*` in the trace).
The median over the traced engine steps of `serve.step` less its
`serve.harvest.wait` children: the host's own work a step (harvest, emit, expire,
dispatch, admit, gauges), whether the device hides it or not.  One reader for
every `engine_host_ms_per_step.<split>`; each moves its cells' end-to-end metric.
`None` where the program opens no such spans."""


def read(ctx):
    from chipbench import program_spans as ps
    return ps.median_ms(ps.host_seconds(ps.of(ctx)))
