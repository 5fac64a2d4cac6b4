"""Layer: compiler.  Source: host_clock around the step call's return, before any
block: the median over the window's steps of what the host spends to hand one
step to the device.  Moves train_tok_per_s_per_chip."""


def read(ctx):
    import statistics
    return statistics.median(ctx['host']['dispatch_ms'])
