"""Bytes and operations of the latent decode attention kernel
(`executors/pallasex.py` `_mla_decode_kernel`), from shapes.  In a trace it is the
Mosaic custom call named `mla_paged_decode`; the write of a step's rows beside it
is `mla_latent_write`.

One decode step of one layer reads the latent row of every token in every row's
context once, for all heads: `kv_lora_rank + qk_rope_head_dim` elements a token
as counted (the arena's rows are padded to whole 128-lane tiles, 576 to 640: the
padding is the layout's own cost and is not counted).  Every head scores a row
(`2 (dc + dr)` operations) and sums it (`2 dc`): `2 nh (2 dc + dr)` operations a
context token, about 236 a byte at A.X-K1's widths, near the v5e's ridge of 240:
the kernel is bound by neither alone, so the least time is the greater of the
two."""
from chipbench.models.latent_moe_decoder import sizes

ELEM = 2  # bfloat16 rows


def matches(op) -> bool:
    return op.name.startswith("mla_paged_decode")


def is_write(op) -> bool:
    return op.name.startswith("mla_latent_write")


def work(hf: dict, context_tokens: float) -> dict:
    """All layers, for decode steps that attended `context_tokens` in all."""
    s = sizes(hf)
    return {"bytes": s["L"] * context_tokens * (s["dc"] + s["dr"]) * ELEM,
            "flops": s["L"] * context_tokens * 2 * s["nh"] * (2 * s["dc"] + s["dr"])}


def least_seconds(hf: dict, context_tokens: float, peaks: dict) -> float:
    w = work(hf, context_tokens)
    return max(w["bytes"] / peaks["hbm_bytes_per_sec"], w["flops"] / peaks["bf16_flops_per_sec"])
