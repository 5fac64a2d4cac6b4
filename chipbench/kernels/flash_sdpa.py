"""Operations and bytes of the flash attention kernels (`executors/pallasex.py`
`_fwd_kernel`, `_bwd_dq_kernel`, `_bwd_dkv_kernel`), from shapes.  In a trace
they are the custom calls named after their jitted wrappers: `_flash_fwd` and,
twice a backward call (dq, then dk and dv), `_flash_bwd`.

A causal query at position i attends min(i + 1, window) keys.  One pair of a
query and a key costs `2 * hs` operations a head in each matrix product.  The
forward pass needs two products (scores, weighted values); the backward pass
needs four (dV, dP, dQ, dK).  Recomputing the scores in the backward kernels
is the algorithm's own cost and is not counted."""
from chipbench.models.dense_decoder import attended_keys, sizes

BWD_KERNELS_PER_CALL = 2


def is_fwd(op) -> bool:
    return op.name.startswith("_flash_fwd")


def is_bwd(op) -> bool:
    return op.name.startswith("_flash_bwd")


def matches(op) -> bool:
    return is_fwd(op) or is_bwd(op)
ELEM = 2  # bfloat16


def call_work(hf: dict, seq_len: int, batch: int) -> dict:
    """One layer's call on `batch` sequences: forward and backward apart."""
    s = sizes(hf)
    pairs = batch * seq_len * attended_keys(seq_len, s["W"])
    unit = 2.0 * s["nh"] * s["hs"] * pairs          # one matrix product
    tok = batch * seq_len * s["hs"] * ELEM
    return {"fwd_flops": 2 * unit, "bwd_flops": 4 * unit,
            "fwd_bytes": (2 * s["nh"] + 2 * s["ng"]) * tok,      # q, o; k, v
            "bwd_bytes": (4 * s["nh"] + 4 * s["ng"]) * tok}      # q, o, do, dq; k, v, dk, dv


def least_seconds(hf, seq_len, batch, peaks, *, fwd_calls: int, bwd_calls: int) -> float:
    w = call_work(hf, seq_len, batch)
    one = lambda f, b: max(f / peaks["bf16_flops_per_sec"], b / peaks["hbm_bytes_per_sec"])  # noqa: E731
    return fwd_calls * one(w["fwd_flops"], w["fwd_bytes"]) + bwd_calls * one(w["bwd_flops"], w["bwd_bytes"])


def bound(hf, seq_len, batch, peaks) -> str:
    w = call_work(hf, seq_len, batch)
    return ("compute" if w["fwd_flops"] / peaks["bf16_flops_per_sec"]
            > w["fwd_bytes"] / peaks["hbm_bytes_per_sec"] else "memory")
