"""Operations and bytes of the selective scan's two kernels
(`executors/pallasex.py` `_ssm_scan_kernel`, `_ssm_decode_kernel`), from shapes.
In a trace they are the custom calls named `ssm_scan_fwd` (one an ssm layer a
whole-prompt prefill) and `ssm_decode_step` (one an ssm layer a decode step,
every row of the decode program in it; `pallas_call(name=)`).

One token of one layer moves a state of `d` channels by `N` states: the decay
`exp(dt A)` (one product and one exponential an element), the input `(dt u)
B^T` (one product an element; `dt u` is `d` more), the update (a product and a
sum), and the read-out `S C` (a product and a sum): 6 vector operations and one
exponential an element of the state, all in float32 on the vector unit, none
on the matrix unit.  The chip's vector peak is taken as its published bfloat16
matrix peak over 16 (`VECTOR_SHARE`: a v5e core's four 128 x 128 matrix units
do 2 x 4 x 16,384 operations a cycle, its vector unit 4 x 8 x 128 x 2: a
sixteenth, no published figure), an exponential as one operation.

A prompt's scan reads `u` (bfloat16), `dt` (float32), `B_t` and `C_t` (`N`
float32 each) and writes `y` (float32) once a token, the state once a call: its
operations outweigh its bytes 20 to 1, so the scan is bound by the vector unit.
A decode step reads and writes a row's state once (`2 d N` float32): the step is
bound by memory."""
VECTOR_SHARE = 1.0 / 16
STATE_ITEMSIZE = 4
OPS_AN_ELEMENT = 7.0      # six float32 operations and an exponential


def is_scan(op) -> bool:
    return op.name.startswith("ssm_scan_fwd")


def is_step(op) -> bool:
    return op.name.startswith("ssm_decode_step")


def matches(op) -> bool:
    return is_scan(op) or is_step(op)


def scan_work(sizes: dict, tokens: int) -> dict:
    """One layer's scan of `tokens` tokens."""
    d, N = sizes["d"], sizes["N"]
    return {"vector_ops": tokens * OPS_AN_ELEMENT * d * N,
            "bytes": tokens * (d * (2 + 4 + 4) + 2 * N * 4) + 2 * d * N * STATE_ITEMSIZE}


def scan_least_seconds(sizes: dict, tokens: int, peaks: dict) -> float:
    w = scan_work(sizes, tokens)
    return max(w["vector_ops"] / (peaks["bf16_flops_per_sec"] * VECTOR_SHARE), w["bytes"] / peaks["hbm_bytes_per_sec"])


def step_work(sizes: dict, rows: float) -> dict:
    """One call: one layer, `rows` rows."""
    d, N = sizes["d"], sizes["N"]
    return {"vector_ops": rows * OPS_AN_ELEMENT * d * N, "bytes": rows * 2 * d * N * STATE_ITEMSIZE}


def step_least_seconds(sizes: dict, rows: float, peaks: dict) -> float:
    w = step_work(sizes, rows)
    return max(w["vector_ops"] / (peaks["bf16_flops_per_sec"] * VECTOR_SHARE), w["bytes"] / peaks["hbm_bytes_per_sec"])
