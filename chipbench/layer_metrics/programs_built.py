"""Layer: entry points.  Source: program_counter (`compile_cache.stats()`).
Programs this process asked XLA for before and during the run: each is a
compilation on a cold cache and a load on a warm one.  Moves setup_s."""


def read(ctx):
    return float(ctx['counters']['programs_built'])
