"""Layer: kernels.  Source: device_trace for the time, the architecture's `sizes`
for the work.  `moe_grouped_mm_roofline_share` for a *latent, ungated* expert
share in a server: two products an expert (`W1`: `Cl x Im`, `W2`: `Im x Cl`, 1024 x
2688 here) where `.mlaserve` and `.lfm2serve` count a SwiGLU's three at the
model's width, over the `E` layers of the pattern alone (`sizes`' `L_e`: 5 of 11).
The calls named `moe_grouped_mm` are told apart by the program run they fall in:

- inside a decode run a step's rows (`slots * k * held / E`: 704 at 128 slots, 5.5
  an expert) can do no better than read once the weights of the held experts that
  drew a row: `2 * held * Cl * Im` elements a layer a step (1.41 GB at 128 held)
  times the window's `experts_hit_share` (the engine's own count, kept by
  `drivers/serve_rows.py`; 1 where the engine counts none: an even routing of 704
  rows misses an expert in 250); the rows' own bytes and products are not counted
  (a hundredth of it), nor the padding of a group to a whole row tile;
- inside a whole prompt's prefill the *expected* rows of the prompt's real tokens
  (`tokens * k * held / E`), `2 * rows * Cl * Im` operations a product against the
  weights' and rows' bytes, the greater of the two times.

The least time over the time those calls took.  A fraction of 1.  `None` where
the trace holds no such call.  Moves serve_out_tok_per_s."""
import bisect

SHARE_OF_PEAK = True
ELEM = 2  # bfloat16
PRODUCTS = 2


def read(ctx):
    from chipbench import program_spans as ps
    from chipbench.common import load_module
    tr, sizes = ctx['trace'], getattr(ctx['arch'], 'sizes', None)
    if ctx['peaks'] is None or sizes is None or not tr.devices:
        return None
    s, dev, peaks = sizes(ctx['config']), tr.devices[0], ctx['peaks']
    if 'Cl' not in s:
        return None
    calls = sorted((o for o in dev.ops if o.name.startswith('moe_grouped_mm')), key=lambda o: o.start)
    if not calls:
        return None
    starts = [o.start for o in calls]

    def inside(run):
        lo, hi = bisect.bisect_left(starts, run.start), bisect.bisect_right(starts, run.start + run.dur)
        return sum(o.dur for o in calls[lo:hi])

    weights = s['held'] * s['Cl'] * s['Im'] * ELEM
    sums = load_module('layer_metrics', 'expert_rows_per_step.nemoserve').window_sums(ctx)
    hit = sums[3] / sums[0] if sums else 1.0
    least = secs = 0.0
    for run in dev.modules:
        if 'decode' in run.name:
            took = inside(run)
            if took > 0:
                secs += took
                least += s['L_e'] * PRODUCTS * hit * weights / peaks['hbm_bytes_per_sec']
    for sp, run in ps.prefill_pairs(ps.of(ctx), dev.modules):
        took, tokens = inside(run), sp.args.get('tokens', 0)
        if took > 0 and tokens:
            rows = tokens * s['k'] * s['held'] / s['E']
            flops = 2.0 * rows * s['Cl'] * s['Im']
            nbytes = weights + ELEM * rows * (s['Cl'] + s['Im'])
            secs += took
            least += s['L_e'] * PRODUCTS * max(flops / peaks['bf16_flops_per_sec'], nbytes / peaks['hbm_bytes_per_sec'])
    return least / secs if secs > 0 else None
