"""Layer: kernels.  Source: device_trace for the time, the architecture's `sizes`
for the work.  `moe_grouped_mm_roofline_share` for a *whole* expert layer in a
server: every published expert held (`held == E`: 32 of 32), where `.mlaserve`
reads a share of 12 of 192.  The arithmetic is `.mlaserve`'s, read from this
architecture's `sizes` (one reader, two entries):

- a call inside a decode run counts the held experts' weights read once, `3 * 32
  * 2048 * 1792 * 2 B` a layer-step (705 MB): a step's rows (`slots * k`: 1,024 at
  256 slots, 32 an expert) meet every expert; the rows' own bytes and products
  are not counted (0.3% and a fifth of the weights' time), nor the padding of a
  group to whole row tiles;
- one inside a whole prompt's prefill counts the routed rows of the prompt's real
  tokens, `tokens * 4`, against weights and rows, the greater of the two times
  (the padded tail of the bucket is routed and multiplied too and is not counted).

A fraction of 1.  Moves serve_out_tok_per_s."""
from chipbench.common import load_module

SHARE_OF_PEAK = True
read = load_module('layer_metrics', 'moe_grouped_mm_roofline_share.mlaserve').read
