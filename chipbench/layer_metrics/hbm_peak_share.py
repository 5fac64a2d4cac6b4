"""Layer: device.  Source: program_counter: live bytes plus the largest compiled
program's temporaries (its own memory analysis), on the fullest chip, over
`bytes_limit`.  A fraction of 1.  One reader for every `hbm_peak_share.<split>`;
each moves its cells' end-to-end metric."""

SHARE_OF_PEAK = True


def read(ctx):
    m = ctx['memory']
    return m['memory_peak_bytes'] / m['bytes_limit'] if m['bytes_limit'] else None
