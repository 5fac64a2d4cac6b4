"""Layer: serving engine.  Source: program_span (`thunder_tpu.serve.*` in the trace).
The median over the traced engine steps of the time from the end of the decode
record's `serve.harvest.wait` (the host has last step's tokens) to the end of the
same step's `serve.decode_dispatch.call` (the device has the next step): while it
lasts the device has nothing of the next decode step.  One reader for every
`engine_turnaround_ms.<split>`; each moves its cells' end-to-end metric.  `None`
where the program opens no such spans."""


def read(ctx):
    from chipbench import program_spans as ps
    return ps.median_ms(ps.turnarounds(ps.of(ctx)))
