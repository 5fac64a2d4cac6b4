"""Layer: entry points.  Source: program_counter (`compile_cache.stats()`, and the
engine's `compile_counts` where there is an engine).  Programs built inside the
measured window; anything but 0 makes the run not correct.  Moves setup_s."""


def read(ctx):
    return float(ctx['counters']['window_compiles'])
