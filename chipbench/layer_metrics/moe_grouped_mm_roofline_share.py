"""Layer: kernels.  Source: device_trace for the time, `kernels/moe_grouped_mm.py`
for the operations and bytes.  The least time the chip could take for the
grouped products of the training steps in the trace (nine a layer a step: the
expected rows on the held experts against a group's weights, without the
padding to whole tiles, the forward's recomputation or the waves) over the time
the `moe_grouped_mm*` calls took.  The steps in the trace: its window over the
median run of the step program.  A fraction of 1.
Moves train_tok_per_s_per_chip."""

SHARE_OF_PEAK = True


def read(ctx):
    from chipbench.common import load_module, load_reader
    if ctx['peaks'] is None:
        return None
    tr, k = ctx['trace'], load_module('kernels', 'moe_grouped_mm')
    secs, step_ms = tr.op_seconds(k.matches), load_reader('train_step_device_ms').read(ctx)
    if secs <= 0 or not step_ms:
        return None
    least = k.least_seconds(ctx['config'], ctx['mix']['seq_len'], ctx['mix']['sequences_per_chip'],
                            ctx['peaks'], steps=tr.window_s() / (step_ms / 1e3))
    return least / secs
