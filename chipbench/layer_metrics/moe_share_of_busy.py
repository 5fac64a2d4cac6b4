"""Layer: kernels.  Source: device_trace: the share of the device's busy time spent
in an expert share's own operations: the grouped products (`moe_grouped_mm*` by
name) and the sorts and gathers that build and empty the sorted buffer (XLA's
`sort*` and `gather*` operations; the embedding lookup is a gather too, one a
step).  One reader for every `moe_share_of_busy.<split>`."""

XLA_PARTS = ("sort", "gather")


def read(ctx):
    from chipbench.common import load_module
    tr, k = ctx['trace'], load_module('kernels', 'moe_grouped_mm')
    if tr.op_seconds(k.matches) <= 0:
        return None
    busy = tr.busy_s()
    secs = tr.op_seconds(lambda o: k.matches(o) or o.name.startswith(XLA_PARTS))
    return secs / busy if busy > 0 else None
