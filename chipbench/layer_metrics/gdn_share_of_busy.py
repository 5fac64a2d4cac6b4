"""Layer: kernels.  Source: device_trace: the share of the device's busy time spent
in the kernels of the chunked gated delta rule (`gdn_chunk_fwd`, `gdn_chunk_bwd`,
by name).  The conv, the gates and the norms around them run in fusions that
carry no name of their own and are not in it.  One reader for every `gdn_share_of_busy.<split>`."""


def read(ctx):
    from chipbench.common import load_module
    tr = ctx['trace']
    busy, secs = tr.busy_s(), tr.op_seconds(load_module('kernels', 'gdn_chunk').matches)
    return secs / busy if busy > 0 and secs > 0 else None
