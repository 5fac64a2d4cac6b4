"""Layer: training step.  Source: device_trace: the median duration of the runs of
the program that takes most device time (the step) on the modules line (the
first run in a trace is cut by its start).
Moves train_tok_per_s_per_chip."""


def read(ctx):
    tr = ctx['trace']
    if not tr.devices or not tr.devices[0].modules:
        return None
    by = {}
    for m in tr.devices[0].modules:
        by.setdefault(m.name, []).append(m.dur)
    runs = max(by.values(), key=sum)
    import statistics
    return 1e3 * statistics.median(runs)
