"""Layer: serving forward.  Source: device_trace: the share of the operations'
seconds spent under the scopes `mixer/mamba2/*` (a Mamba-2 layer's `in_proj`,
`conv`, `scan`, `norm`, `out` and its slot's reads and writes,
`generate.mamba2_mixer`; the kernels `ssd_chunk_fwd` and `ssd_decode_step` carry
the scope of their call), read from each instruction's `op_name` in the trace's
metadata (`chipbench/op_scopes.py`).  A share of busy, against
`mixer_share_of_busy`'s denominator; no peak.  `None` where the program writes no
such scope, as the parent of the PR that brought the layer kind does not.  Moves
serve_out_tok_per_s."""
from chipbench.common import load_module

_ssm = load_module('layer_metrics', 'ssm_share_of_busy.flashserve')


def read(ctx):
    return _ssm.under(ctx, lambda parts: _ssm.pair(parts, 'mixer', 'mamba2'))
