"""Layer: entry points.  Source: program_span, read in process from the program's event
ring by `chipbench/setup_spans.py`, which puts every second of `setup_s` into one bucket.  This one:
the `import` event's duration: `import thunder_tpu` from the package's first line to its last, JAX's own import
(the event's `jax_s`) included.
`None` where the ring is full or the program leaves no `import` event (it keeps no set-up timeline).
Moves setup_s."""
from chipbench import setup_spans


def read(ctx):
    return setup_spans.value(ctx, "import_s")
