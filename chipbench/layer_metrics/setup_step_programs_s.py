"""Layer: training step.  Source: program_span, read in process from the program's event
ring by `chipbench/setup_spans.py`, which puts every second of `setup_s` into one bucket.  This one:
wall seconds of the top-level `xla_compile` [`fn="train_step"`] spans: the first call of a `TrainStep` just
built, where JAX traces the whole step, lowers it and compiles or loads it.  `train.snapshot` is NOT in it:
`setup_spans.split` gives it a bucket of its own (`snapshot_s`), printed beside this one by
`python3 chipbench/setup_spans.py` and counted in no entry but `setup_s`.
`None` where the ring is full or the program leaves no `import` event (it keeps no set-up timeline).
Moves setup_s."""
from chipbench import setup_spans


def read(ctx):
    return setup_spans.value(ctx, "step_programs_s")
