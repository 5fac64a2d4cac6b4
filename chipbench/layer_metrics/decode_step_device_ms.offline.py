"""Layer: serving forward.  Source: device_trace: the mean duration of the runs
of the decode program on the modules line, the trace's first and last run left
out (its start and stop cut them).  Moves serve_out_tok_per_s."""


def read(ctx):
    runs = ctx['trace'].module_runs('decode')[1:-1]
    return 1e3 * sum(runs) / len(runs) if runs else None
