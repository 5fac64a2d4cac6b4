"""Layer: serving engine.  Source: program_span, read in process from the program's event
ring by `chipbench/setup_spans.py`, which puts every second of `setup_s` into one bucket.  This one:
wall seconds of the `serve.compile` spans whose `kind` starts with `prefill` or `spec_prefill`: JAX's trace,
the lowering and the compile or load of each whole-prompt or chunk program, on its first call.
`None` where the ring is full or the program leaves no `import` event (it keeps no set-up timeline).
Moves setup_s."""
from chipbench import setup_spans


def read(ctx):
    return setup_spans.value(ctx, "prefill_programs_s")
