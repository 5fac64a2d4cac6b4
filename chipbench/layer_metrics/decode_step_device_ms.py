"""Layer: serving forward.  Source: device_trace: the mean duration of the runs
of the decode program on the modules line, the trace's first and last run left
out (its start and stop cut them).  One reader for every
`decode_step_device_ms.<split>` without a file of its own name; each moves its
cells' end-to-end metric."""


def read(ctx):
    runs = ctx['trace'].module_runs('decode')[1:-1]
    return 1e3 * sum(runs) / len(runs) if runs else None
