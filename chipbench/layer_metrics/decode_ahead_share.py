"""Layer: serving engine.  Source: program_span (`thunder_tpu.serve.*` in the trace).
The share of the traced window's `serve.decode_dispatch` spans whose `ahead`
argument is 1: decode steps the engine dispatched before the host had the tokens
of the step before, so that the harvest ran under the device.  One reader for every
`decode_ahead_share.<split>`; each moves its cells' end-to-end metric.  `None`
where the program opens no such span or gives it no such argument."""


def read(ctx):
    from chipbench import program_spans as ps
    ahead = [int(s.args["ahead"]) for s in ps.named(ps.of(ctx), "serve.decode_dispatch")
             if "ahead" in s.args]
    return sum(ahead) / len(ahead) if ahead else None
