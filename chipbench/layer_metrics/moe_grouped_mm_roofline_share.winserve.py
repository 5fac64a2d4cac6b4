"""Layer: kernels.  Source: device_trace for the time, the architecture's `sizes`
for the work.  Listed for the Trinity-Mini cell since PR 50 (PR 48 wrote it when
the per-layer manifest was full).
`moe_grouped_mm_roofline_share` for a SwiGLU expert share at the model's width in
a server whose decode step reaches *some* of the held experts: `.lfm2serve` and
`.mlaserve` count every held expert's weights a step, which at 1.25 rows a held
expert (20 rows x 8 of 128 onto 16 held: 0.715 of them draw a row) would read over
1; this is `.nemoserve`'s arithmetic with three products an expert (`W1`, `W3`: `C x
Im`, `W2`: `Im x C`, 2048 x 1024 here) over the expert layers alone (`L - dense`:
30 of 32).  The calls named `moe_grouped_mm` are told apart by the program run they
fall in:

- inside a decode run a step's rows can do no better than read once the weights of
  the held experts that drew a row: `3 * held * C * Im` elements a layer a step
  (201 MB at 16 held) times the window's `experts_hit_share` (the engine's own
  count, kept by `drivers/serve_window.py`; 1 where the engine counts none); the
  rows' own bytes and products are not counted, nor the padding of a group to a
  whole row tile;
- inside a whole prompt's prefill the *expected* rows of the prompt's real tokens
  (`tokens * k * held / E`), `2 * rows * C * Im` operations a product against the
  weights' and rows' bytes, the greater of the two times.

The least time over the time those calls took.  A fraction of 1.  `None` where
the trace holds no such call or the architecture is another.  Moves
serve_out_tok_per_s."""
import bisect

SHARE_OF_PEAK = True
ELEM = 2  # bfloat16
PRODUCTS = 3


def read(ctx):
    from chipbench import program_spans as ps
    from chipbench.common import load_module
    tr, sizes = ctx['trace'], getattr(ctx['arch'], 'sizes', None)
    if ctx['peaks'] is None or sizes is None or not tr.devices:
        return None
    s, dev, peaks = sizes(ctx['config']), tr.devices[0], ctx['peaks']
    if 'L_swa' not in s or 'Im' not in s or 'held' not in s:
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
    sums = load_module('layer_metrics', 'expert_rows_per_step.nemoserve').window_sums(ctx)
    hit = sums[3] / sums[0] if sums else 1.0
    least = secs = 0.0
    for run in dev.modules:
        if 'decode' in run.name:
            took = inside(run)
            if took > 0:
                secs += took
                least += layers * PRODUCTS * hit * weights / peaks['hbm_bytes_per_sec']
    for sp, run in ps.prefill_pairs(ps.of(ctx), dev.modules):
        took, tokens = inside(run), sp.args.get('tokens', 0)
        if took > 0 and tokens:
            rows = tokens * s['k'] * s['held'] / s['E']
            flops = 2.0 * rows * s['C'] * s['Im']
            nbytes = weights + ELEM * rows * (s['C'] + s['Im'])
            secs += took
            least += layers * PRODUCTS * max(flops / peaks['bf16_flops_per_sec'], nbytes / peaks['hbm_bytes_per_sec'])
    return least / secs if secs > 0 else None
