"""Layer: entry points.  Source: program_counter (`compile_cache.stats()`:
`jaxpr_trace_s + lower_s`, summed from JAX's own duration events of every
`jax.jit` of the process): the seconds of Python tracing to jaxprs and of
lowering them to MLIR, over the layers unrolled in Python.  A jit traced inside
another is counted in both.  `None` where the program keeps no such counters.
Moves setup_s."""


def read(ctx):
    cc = ctx['counters']['compile_cache']
    if 'jaxpr_trace_s' not in cc or 'lower_s' not in cc:
        return None
    return cc['jaxpr_trace_s'] + cc['lower_s']
