"""Layer: serving forward.  Source: program_counter (`engine.stats()["moe"]["row_sums"]`
before and after the window, kept by `drivers/serve_rows.py`): the rows of a
decode step that landed on an expert this chip holds, a mean over the expert
layers and over the window's steps (`slots * k` routed, 2,816 at 128 slots of 22;
an even routing sends `held / E` of them here, 704 at 128 of 512: 5.5 a held
expert).  Its spread over the steps is in the check line's `counters`.  `None`
where the engine counts no such rows, as a parent without the counter does not.
Moves serve_out_tok_per_s."""


def window_sums(ctx):
    """``[steps, rows, rows^2, hit share]`` summed over the window's decode steps, or None."""
    a, b = (ctx['counters'].get(k, {}).get('moe', {}).get('row_sums') for k in ('stats0', 'stats1'))
    if not a or not b or b[0] <= a[0]:
        return None
    return [y - x for x, y in zip(a, b)]


def read(ctx):
    sums = window_sums(ctx)
    return sums[1] / sums[0] if sums else None
