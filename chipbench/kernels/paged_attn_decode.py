"""Bytes of the paged decode attention kernel (`executors/pallasex.py`
`_paged_kernel`), from shapes.  In a trace it is the Mosaic custom call whose
first operand is the block table (int32, rows by blocks) and whose result has the
queries' four dimensions, not an arena's five;
it is named after the enclosing program, like the token-write kernel beside it.

One decode step of one layer reads the K and V of every token in every row's
context once: `2 * ng * hs` elements a token.  The queries, the outputs and
the block table are small beside that, and the products (`4 * nh * hs`
operations a context token) are far under the chip's peak: the kernel is
bound by memory."""
from chipbench.models.dense_decoder import sizes

import re

_CALL = re.compile(r"^\w+\[\d+(,\d+){3}\]\S* custom-call\(s32\[\d+,\d+\]")


def matches(op) -> bool:
    return 'custom_call_target="tpu_custom_call"' in op.meta and bool(_CALL.match(op.meta))
ELEM = 2  # bfloat16 KV


def work(hf: dict, context_tokens: float) -> dict:
    """All layers, for decode steps that attended `context_tokens` in all."""
    s = sizes(hf)
    return {"bytes": s["L"] * context_tokens * 2 * s["ng"] * s["hs"] * ELEM,
            "flops": s["L"] * context_tokens * 4 * s["nh"] * s["hs"]}


def least_seconds(hf: dict, context_tokens: float, peaks: dict) -> float:
    w = work(hf, context_tokens)
    return max(w["bytes"] / peaks["hbm_bytes_per_sec"], w["flops"] / peaks["bf16_flops_per_sec"])
