"""Layer: kernels.  Source: device_trace for the time, `kernels/hc_mix.py` for the
bytes and operations.  The least time the chip could take over the
hyper-connections of the traced stretch (a sublayer reads its tokens' stream once
and writes it once, and reads its `phi` once a run; bytes bound it), over the time
the operations under the scopes `*/hc/*` took, whole prompts' prefills and decode
steps together: the prompts' `tokens` from their `serve.prefill_dispatch` spans,
a decode step's rows the engine's slots.  The count follows from shapes alone, so
it reads the same work whatever implements the mixing.  A fraction of 1.  `None`
where the trace holds no such scope.  Moves serve_out_tok_per_s."""

SHARE_OF_PEAK = True


def read(ctx):
    from chipbench.common import load_module
    if ctx['peaks'] is None or not ctx['trace'].devices or 'hc_mult' not in ctx['config']:
        return None
    k = load_module('kernels', 'hc_mix')
    parts = k.by_program(ctx).values()
    secs = sum(p['hc_s'] for p in parts)
    if secs <= 0:
        return None
    return k.least_seconds(ctx['config'], sum(p['rows'] for p in parts), sum(p['runs'] for p in parts), ctx['peaks']) / secs
