"""Bytes of the DeltaNet layers' causal depthwise conv with its activation, from
shapes.  In a trace its two kernels are the custom calls named
`causal_conv1d_fwd` and `causal_conv1d_bwd` (`pallas_call(name=)`,
`executors/pallasex.py`).  A training step calls the forward kernel once a
layer in the forward pass and once more where the rematerialization pass makes
q, k, v again before the scan's backward, and the backward kernel once: the
calls are counted from the trace, not assumed.

The work is the operation's, whatever implements it: `out = act(conv(x))` reads
x `(B, T, C)` once and writes as much, `2 B T C` elements; its backward reads
the output's gradient and x and writes dx, `3 B T C` (the sum before the
activation is made again from x: seven multiply-adds an element, far under the
chip's ridge, so the bytes bound it).  The four taps' weights and their
gradient (`C x K`), the rows a tile reads of its neighbours, and the float32
partial rows of dw are the kernel's own and not counted.  C is q | k | v side
by side: two key widths and one value width of the configuration."""
from chipbench.models.hybrid_moe_decoder import sizes

ELEM = 2  # bfloat16


def is_fwd(op) -> bool:
    return op.name.startswith("causal_conv1d_fwd")


def is_bwd(op) -> bool:
    return op.name.startswith("causal_conv1d_bwd")


def matches(op) -> bool:
    return op.name.startswith("causal_conv1d")


def call_bytes(hf: dict, seq_len: int, batch: int) -> dict:
    """One layer's forward call, and its backward call, on `batch` sequences."""
    s = sizes(hf)
    elements = batch * seq_len * (2 * s["nk"] * s["dk"] + s["nv"] * s["dv"])
    return {"fwd": 2 * elements * ELEM, "bwd": 3 * elements * ELEM}


def least_seconds(hf, seq_len, batch, peaks, *, fwd_calls: int, bwd_calls: int = 0) -> float:
    b = call_bytes(hf, seq_len, batch)
    return (fwd_calls * b["fwd"] + bwd_calls * b["bwd"]) / peaks["hbm_bytes_per_sec"]
