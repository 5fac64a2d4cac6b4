"""Operations and bytes of the grouped matrix products over an expert share's
sorted rows (`executors/pallasex.py` `_gmm_kernel`, `_gmm_dw_kernel`), from
shapes and the rows an even routing sends (the expected rows: the rows a run
really routes are known on the device alone and differ by some percent, 9.6-10.7
thousand a layer against 10,240 in the cell, so the share swings with the seed
by as much).  In a trace they are the
custom calls named `moe_grouped_mm` (rows against their group's weights) and
`moe_grouped_mm_dw` (each group's `x^T dy`).  A layer's step needs nine such
products (`PRODUCTS_A_LAYER_STEP`: three forward, three for the rows' gradient,
three for the weights'); the program makes the forward's again in the backward
pass and works the rows through in waves, so the calls in a trace are more, and
how many follows the routing: the work is counted from the layer-steps in the
trace, not from its calls.

A call multiplies the rows that fell on the held experts, each padded to whole
row tiles; the padding is the kernel's own cost, so the work counted is that of
the expected rows, `tokens * experts_per_token * held / all`: `2 * rows * C * I`
operations a call.  Its bytes: the rows in and out once, and every held expert's
weights once."""
from chipbench.models.hybrid_moe_decoder import sizes

ELEM = 2  # bfloat16


def matches(op) -> bool:
    return op.name.startswith("moe_grouped_mm")


def call_work(hf: dict, seq_len: int, batch: int) -> dict:
    """One grouped product (any of the nine a layer): they all multiply the
    same rows by `C x I` a group."""
    s = sizes(hf)
    rows = batch * seq_len * s["k"] * s["E"] / s["E_all"]
    return {"flops": 2.0 * rows * s["C"] * s["I"],
            "bytes": ELEM * (rows * (s["C"] + s["I"]) + s["E"] * s["C"] * s["I"])}


PRODUCTS_A_LAYER_STEP = 9


def least_seconds(hf, seq_len, batch, peaks, *, steps: float) -> float:
    """For `steps` training steps (whole or not) of every layer."""
    w = call_work(hf, seq_len, batch)
    return (steps * hf["num_hidden_layers"] * PRODUCTS_A_LAYER_STEP
            * max(w["flops"] / peaks["bf16_flops_per_sec"], w["bytes"] / peaks["hbm_bytes_per_sec"]))
