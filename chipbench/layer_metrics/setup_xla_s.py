"""Layer: entry points.  Source: program_counter (`compile_cache.stats()`:
`backend_compile_s`, summed from JAX's own duration events): the seconds in the
backend for every program of the process, a compilation on a cold cache and a
load from it on a warm one.  `None` where the program keeps no such counter.
Moves setup_s."""


def read(ctx):
    return ctx['counters']['compile_cache'].get('backend_compile_s')
