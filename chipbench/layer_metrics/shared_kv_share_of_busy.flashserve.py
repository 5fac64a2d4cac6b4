"""Layer: serving forward.  Source: device_trace: the share of the operations'
seconds spent on the one layer's K/V that several layers read: the scopes
`mixer/cross/*` (the cross_attention layers: their query projection, their walk
of the global layer's blocks, the differential form and `W_o`) and the global
layer's own walk (`blk<source>/mixer/attn/attn`, `source` from the architecture's
`sizes`), read from each instruction's `op_name` (`chipbench/op_scopes.py`).  A share
of busy, against `mixer_share_of_busy`'s denominator; no peak.  `None` where the
program writes no such scope.  Moves serve_out_tok_per_s."""


def read(ctx):
    from chipbench.common import load_module
    sizes = getattr(ctx['arch'], 'sizes', None)
    if sizes is None:
        return None
    share = load_module('layer_metrics', 'ssm_share_of_busy.flashserve')
    own = f"blk{sizes(ctx['config']).get('source')}"

    def inside(parts):
        return share.pair(parts, 'mixer', 'cross') or (
            own in parts and any(parts[i:i + 3] == ['mixer', 'attn', 'attn'] for i in range(len(parts))))

    return share.under(ctx, inside)
