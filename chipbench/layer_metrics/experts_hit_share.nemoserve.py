"""Layer: serving forward.  Source: program_counter (`engine.stats()["moe"]["row_sums"]`
before and after the window, kept by `drivers/serve_rows.py`): the share of the
experts this chip holds that a decode step sent at least one row, a mean over
the expert layers and over the window's steps.  An expert without a row costs
the step nothing (its weights are not fetched); one with a single row costs a
whole 16-row tile and the whole of its weights.  A fraction of 1.  `None` where
the engine counts no such rows.  Moves serve_out_tok_per_s."""
from chipbench.common import load_module


def read(ctx):
    sums = load_module('layer_metrics', 'expert_rows_per_step.nemoserve').window_sums(ctx)
    return sums[3] / sums[0] if sums else None
