"""Bytes of the Mamba-2 decode kernel (`executors/pallasex.py`
`_ssd_decode_kernel`), from shapes.  In a trace it is the custom call named
`ssd_decode_step` (`pallas_call(name=)`): one call a Mamba-2 layer a decode
step, every row of the decode program in it.

One step of one row of one layer reads the row's state once and writes it once:
`H P N` = `d N` elements each way in float32 (the state arena's width, which the
configuration states and the engine fixes): 2 x 4.19 MB at 128 heads of 64
channels and 128 states.  The token's `x`, `dt`, `B_t` and `C_t` are a thousandth
of that, and the arithmetic (a decay, an input and a read-out: 5 operations an
element of the state on the vector unit) is far under what the bytes take: the
kernel is bound by memory."""
STATE_ITEMSIZE = 4
OPS_AN_ELEMENT = 5.0
VECTOR_SHARE = 1.0 / 16     # the vector unit's peak as a share of the published matrix peak (`kernels/ssm_scan.py`)


def matches(op) -> bool:
    return op.name.startswith("ssd_decode_step")


def call_work(sizes: dict, rows: float) -> dict:
    """One call: one layer, `rows` rows."""
    state = sizes["d"] * sizes["N"]
    return {"bytes": rows * 2 * state * STATE_ITEMSIZE, "vector_ops": rows * OPS_AN_ELEMENT * state}


def least_seconds(sizes: dict, rows: float, peaks: dict) -> float:
    w = call_work(sizes, rows)
    return max(w["bytes"] / peaks["hbm_bytes_per_sec"], w["vector_ops"] / (peaks["bf16_flops_per_sec"] * VECTOR_SHARE))
