"""Layer: serving engine.  Source: program_span, read in process from the program's event
ring by `chipbench/setup_spans.py`, which puts every second of `setup_s` into one bucket.  This one:
wall seconds of the `serve.compile` spans of every other `kind` (`decode*`, `draft_decode`, `verify_paged`):
JAX's trace, the lowering and the compile or load of each decode program, on its first call.
`None` where the ring is full or the program leaves no `import` event (it keeps no set-up timeline).
Moves setup_s."""
from chipbench import setup_spans


def read(ctx):
    return setup_spans.value(ctx, "decode_programs_s")
