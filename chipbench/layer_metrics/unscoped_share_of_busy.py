"""Layer: device.  Source: device_trace: the share of the operations' seconds whose
`op_name` holds no scope group: what the instrumentation misses (lower is better), and
the operations whose line two programs hold under different groups.  With the five
groups' shares it sums to one (`chipbench/op_scopes.py`; `python3 chipbench/op_scopes.py
<xplane>` lists the largest by name).  One reader for every
`unscoped_share_of_busy.<split>`.  1.0 for a program that writes no scopes."""


def read(ctx):
    from chipbench import op_scopes
    return op_scopes.share(ctx, None)
