"""Layer: kernels.  Source: device_trace: the share of the device's busy time spent
in the delta rule's kernels as a server runs them: `gdn_chunk_fwd` (a prompt's
scan) and `gdn_decode_step` (a token's step), by name.  The conv, the gates and
the norms around them run in fusions that carry no name of their own and are
not in it.  Moves serve_out_tok_per_s."""


def read(ctx):
    tr = ctx['trace']
    busy = tr.busy_s()
    secs = tr.op_seconds(lambda op: op.name.startswith(('gdn_chunk', 'gdn_decode')))
    return secs / busy if busy > 0 and secs > 0 else None
