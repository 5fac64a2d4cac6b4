"""Layer: entry points.  Source: program_span, read in process from the program's event
ring by `chipbench/setup_spans.py`, which puts every second of `setup_s` into one bucket.  This one:
self seconds of the `jax.trace`, `jax.lower` and `jax.backend_compile` events that lie under none of
`serve.compile`, `compile`, `xla_compile`: the benchmark's own programs (the reference's forward, the weights'
init) and the small eager programs of the engine's construction and step loop.  The check line's
`counters.compile_cache.by_program` names them.
`None` where the ring is full or the program leaves no `import` event (it keeps no set-up timeline).
Moves setup_s."""
from chipbench import setup_spans


def read(ctx):
    return setup_spans.value(ctx, "other_programs_s")
