"""Layer: kernels.  Source: device_trace: the share of the device's busy time spent
in latent attention's own kernels as a server runs them: `mla_paged_decode` (a
token's attention over its row's latents) and `mla_latent_write` (the step's rows
into the arena), by name.  The projections around them (the queries' absorption,
the values' expansion) and a prompt's expanded attention (`_flash_fwd` on padded
heads) are not in it.  One reader for every `mla_share_of_busy.<split>`."""


def read(ctx):
    tr = ctx['trace']
    busy = tr.busy_s()
    secs = tr.op_seconds(lambda op: op.name.startswith(('mla_paged_decode', 'mla_latent_write')))
    return secs / busy if busy > 0 and secs > 0 else None
