"""Layer: serving engine.  Source: program_counter
(`engine.stats()["pool_occupancy"]["state"]["ring_fill_frac"]`, the state pool's own
snapshot): the share of the window kind's table that was held when the window
closed: a request's window layers keep a ring of blocks with its state slot,
whatever its length, so the share is the slots leased over the slots there.
`None` where the engine keeps no ring, as a parent without the layer kind does not."""


def read(ctx):
    state = ctx['counters']['stats1'].get('pool_occupancy', {}).get('state') or {}
    return float(state['ring_fill_frac']) if 'ring_fill_frac' in state else None
