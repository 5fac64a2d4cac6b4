"""Layer: serving engine.  Source: program_counter (`engine.stats()["pool_occupancy"]["state"]`,
the state pool's own snapshot): the share of the state arena's slots leased
when the window closed.  One reader for every `state_pool_fill_share.<split>`.
`None` where the engine keeps no recurrent state."""


def read(ctx):
    state = ctx['counters']['stats1'].get('pool_occupancy', {}).get('state')
    return float(state['fill_frac']) if state else None
