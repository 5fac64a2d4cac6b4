"""Layer: entry points.  Source: program_span, read in process from the program's event
ring by `chipbench/setup_spans.py`, which puts every second of `setup_s` into one bucket.  This one:
`setup_s` less the union of every marked stretch (`import`, `serve.compile`, `compile`, `xla_compile`,
`train.snapshot`, and the `jax.*` events outside them): claiming the chip, the device's own time in the check
and the lead-in, host work nobody marks.  What the measurement still cannot see, as a number.
`None` where the ring is full or the program leaves no `import` event (it keeps no set-up timeline).
Moves setup_s."""
from chipbench import setup_spans


def read(ctx):
    return setup_spans.value(ctx, "unspanned_s")
