"""Bytes of the delta rule's decode kernel (`executors/pallasex.py`
`_gdn_decode_kernel`), from shapes.  In a trace it is the custom call named
`gdn_decode_step` (`pallas_call(name=)`): one call a linear-attention layer a
decode step, every row of the decode program in it.

One step of one row of one layer reads the row's state once and writes it
once: `nv * dk * dv` elements each way, in float32 (the state arena's width,
which the configuration states and the engine fixes), at the *published* head
widths: what the chip pads a `(dk, dv)` tile to is the kernel's cost, not its
work.  The token's q, k, v, decay and beta are a thousandth of that, and the
products (`6 dk dv` operations a head) are far under the chip's peak: the
kernel is bound by memory."""


def matches(op) -> bool:
    return op.name.startswith("gdn_decode_step")


STATE_ITEMSIZE = 4


def call_work(hf: dict, sizes: dict, rows: float) -> dict:
    """One call: one layer, `rows` rows."""
    heads = sizes["nv"] * sizes["dk"] * sizes["dv"]
    return {"bytes": rows * 2 * heads * STATE_ITEMSIZE, "flops": rows * 6.0 * heads}


def least_seconds(hf: dict, sizes: dict, rows: float, peaks: dict) -> float:
    w = call_work(hf, sizes, rows)
    return max(w["bytes"] / peaks["hbm_bytes_per_sec"], w["flops"] / peaks["bf16_flops_per_sec"])
