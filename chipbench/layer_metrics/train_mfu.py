"""Layer: training step.  Source: host_clock rate times operations from shapes.
Tokens a second a chip over the window's steps before the profiler started
(starting it can stall the host past the steps it had queued; the whole
window's rate where the trace covers all of it), times the forward and
backward operations a token needs (`models/<arch>.train_flops_per_token`,
recomputation not counted), over the chip's bf16 peak.  A fraction of 1.
Moves train_tok_per_s_per_chip."""

SHARE_OF_PEAK = True


def read(ctx):
    if ctx['peaks'] is None:
        return None
    rate = (ctx['host'].get('untraced_tok_per_s_per_chip')
            or ctx['end_to_end']['train_tok_per_s_per_chip'])
    flops = ctx['arch'].train_flops_per_token(ctx['config'], ctx['mix']['seq_len'])
    return rate * flops / ctx['peaks']['bf16_flops_per_sec']
