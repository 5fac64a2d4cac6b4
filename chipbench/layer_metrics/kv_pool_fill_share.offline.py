"""Layer: serving engine.  Source: program_counter (`pool_occupancy`): the share
of the KV arena's blocks leased when the window closed.  Moves serve_out_tok_per_s."""


def read(ctx):
    return float(ctx['counters']['stats1']['pool_utilization'])
