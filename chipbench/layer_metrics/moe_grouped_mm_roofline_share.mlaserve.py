"""Layer: kernels.  Source: device_trace for the time, the architecture's `sizes`
for the work.  `moe_grouped_mm_roofline_share` for an expert share in a server
(the accepted reader takes its rows from a training mix).  The calls named
`moe_grouped_mm` are told apart by the program run they fall in:

- inside a decode run a step's few rows meet every held expert (2.7 rows an
  expert at 64 slots), so a layer's three products can do no better than read the
  held experts' weights once: `3 * held * C * Im` elements a layer a step; the
  rows' own bytes and products are not counted (under a hundredth of it);
- inside a whole prompt's prefill the rows are many: the *expected* rows of the
  prompt's real tokens (`tokens * k * held / E`; the padded tail of the bucket is
  routed and multiplied too and is not counted, nor is the padding of a group to
  whole row tiles), `2 * rows * C * Im` operations a product against the weights'
  and rows' bytes, the greater of the two times.

The least time over the time those calls took.  A fraction of 1.  Moves
serve_out_tok_per_s."""
import bisect

SHARE_OF_PEAK = True
ELEM = 2  # bfloat16


def read(ctx):
    from chipbench import program_spans as ps
    tr, sizes = ctx['trace'], getattr(ctx['arch'], 'sizes', None)
    if ctx['peaks'] is None or sizes is None or not tr.devices:
        return None
    s, dev, peaks = sizes(ctx['config']), tr.devices[0], ctx['peaks']
    if 'held' not in s:
        return None
    calls = sorted((o for o in dev.ops if o.name.startswith('moe_grouped_mm')), key=lambda o: o.start)
    if not calls:
        return None
    starts = [o.start for o in calls]

    def inside(run):
        lo, hi = bisect.bisect_left(starts, run.start), bisect.bisect_right(starts, run.start + run.dur)
        return sum(o.dur for o in calls[lo:hi])

    layers = s['L'] - s['dense']
    weights = s['held'] * s['C'] * s['Im'] * ELEM
    least = secs = 0.0
    for run in dev.modules:
        if 'decode' in run.name:
            took = inside(run)
            if took > 0:
                secs += took
                least += layers * 3 * weights / peaks['hbm_bytes_per_sec']
    for sp, run in ps.prefill_pairs(ps.of(ctx), dev.modules):
        took, tokens = inside(run), sp.args.get('tokens', 0)
        if took > 0 and tokens:
            rows = tokens * s['k'] * s['held'] / s['E']
            flops = 2.0 * rows * s['C'] * s['Im']
            nbytes = weights + ELEM * rows * (s['C'] + s['Im'])
            secs += took
            least += layers * 3 * max(flops / peaks['bf16_flops_per_sec'], nbytes / peaks['hbm_bytes_per_sec'])
    return least / secs if secs > 0 else None
