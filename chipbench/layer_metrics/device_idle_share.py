"""Layer: device.  Source: device_trace: 1 minus busy over the traced window.
One reader for every `device_idle_share.<split>`; each moves its cells'
end-to-end metric."""


def read(ctx):
    return ctx['trace'].idle_share()
