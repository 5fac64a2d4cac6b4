"""Layer: kernels.  Source: device_trace for the time, `kernels/gdn_chunk.py`
(`chunk_macs`) for the operations.  The least time the chip could take for the
forward calls of the chunked gated delta rule in the traced stretch's whole
prefills (`gdn_chunk_fwd`, one a linear-attention layer a prefill; tokens a
call the prefill bucket, from the `thunder_tpu.serve.prefill_dispatch` span
that started the run; the chunked algorithm's products at the *published* head
widths, the inverse counted as a substitution; bytes: q, k, v, o, the decay and
beta once, the state in and out) over the time those calls took.  A fraction
of 1.  Moves serve_out_tok_per_s."""

SHARE_OF_PEAK = True
ELEM = 2  # bfloat16


def call_least(sizes: dict, tokens: int, peaks: dict, chunk: int, chunk_macs) -> float:
    nk, nv, dk, dv = sizes['nk'], sizes['nv'], sizes['dk'], sizes['dv']
    flops = 2.0 * tokens / chunk * nv * chunk_macs(chunk, dk, dv)
    nbytes = tokens * (ELEM * (2 * nk * dk + 2 * nv * dv) + 8 * nv) + 2 * 4 * nv * dk * dv
    return max(flops / peaks['bf16_flops_per_sec'], nbytes / peaks['hbm_bytes_per_sec'])


def read(ctx):
    from chipbench import program_spans as ps
    from chipbench.common import load_module
    tr, sizes = ctx['trace'], getattr(ctx['arch'], 'sizes', None)
    if ctx['peaks'] is None or sizes is None or not tr.devices:
        return None
    k = load_module('kernels', 'gdn_chunk')
    s, dev = sizes(ctx['config']), tr.devices[0]
    least = secs = 0.0
    for sp, run in ps.prefill_pairs(ps.of(ctx), dev.modules):
        calls = [o for o in dev.ops if k.is_fwd(o) and run.start <= o.start <= run.start + run.dur]
        tokens = int(str(sp.args.get('bucket', '0x0')).split('x')[0])
        if calls and tokens:
            secs += sum(o.dur for o in calls)
            least += len(calls) * call_least(s, tokens, ctx['peaks'], k.GDN_CHUNK, k.chunk_macs)
    return least / secs if secs > 0 else None
