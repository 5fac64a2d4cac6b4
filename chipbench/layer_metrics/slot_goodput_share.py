"""Layer: serving engine.  Source: program_counter (`GoodputLedger.snapshot()`):
the share of dispatched token positions in the window that were committed to a
request, the rest being padding rows and padded prefill.  One reader for every
`slot_goodput_share.<split>` without a file of its own name."""


def read(ctx):
    a, b = ctx['counters']['stats0'].get('goodput'), ctx['counters']['stats1'].get('goodput')
    if not a or not b or b['positions'] == a['positions']:
        return None
    return (b['committed'] - a['committed']) / (b['positions'] - a['positions'])
