"""Layer: kernels.  Source: device_trace: the share of the device's busy time spent
in Pallas kernels (Mosaic custom calls).  One reader for every
`pallas_share_of_busy.<split>`; each moves its cells' end-to-end metric."""


def read(ctx):
    tr = ctx['trace']
    busy = tr.busy_s()
    return tr.pallas_seconds() / busy if busy > 0 else None
