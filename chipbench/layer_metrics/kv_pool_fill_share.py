"""Layer: serving engine.  Source: program_counter (`pool_utilization`): the share
of the KV arena's blocks leased when the window closed.  One reader for every
`kv_pool_fill_share.<split>` without a file of its own name."""


def read(ctx):
    return float(ctx['counters']['stats1']['pool_utilization'])
