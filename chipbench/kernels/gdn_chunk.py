"""Operations and bytes of the chunked gated delta rule's two kernels
(`executors/pallasex.py` `_gdn_fwd_kernel`, `_gdn_bwd_kernel`), from shapes.  In
a trace they are the custom calls named `gdn_chunk_fwd` and `gdn_chunk_bwd`
(`pallas_call(name=)`).  A training step calls the forward kernel twice a
layer (once in the forward pass, once more in the backward pass for the state
before every chunk) and the backward kernel once.

The chunked algorithm on one chunk of `C` tokens of one value head (key and
value heads `dk`, `dv` wide) is eight matrix products: `K K^T` and `Q K^T`
(`C C dk` multiply-adds each), the triangular inverse applied to `beta V` and
to `beta e^G K` (`C C dv`, `C C dk`), both against the carried state and the
read-out from it (`C dk dv` each, with the state's update three), `(Q K^T) D`
(`C C dv`), and the inverse itself, counted as a forward substitution (`C^3 /
3`).  The kernel inverts by squarings, which costs more; that is its own
choice and is not counted.  `models/hybrid_moe_decoder.gdn_scan_macs_per_token`
counts the same, a token.  The backward kernel is counted as the derivative
of those products alone, two products of the same size for each (twice the
forward's operations); that it makes the chunk's terms again first is its own
choice too.  Its bytes: what the forward reads, the output's gradient, and a
gradient for every operand; the float32 state before every chunk, which the
kernel reads (537 MB a call in the cell), is not counted: how many states are
kept and how many made again is the kernel's choice."""
from chipbench.models.hybrid_moe_decoder import GDN_CHUNK, sizes

ELEM = 2  # bfloat16


def is_fwd(op) -> bool:
    return op.name.startswith("gdn_chunk_fwd")


def is_bwd(op) -> bool:
    return op.name.startswith("gdn_chunk_bwd")


def matches(op) -> bool:
    return op.name.startswith("gdn_chunk")


def chunk_macs(C: int, dk: int, dv: int) -> float:
    return C * C * (3 * dk + 2 * dv) + 3 * C * dk * dv + C ** 3 / 3.0


def call_work(hf: dict, seq_len: int, batch: int) -> dict:
    """One layer's forward call, and its backward call, on `batch` sequences."""
    s = sizes(hf)
    tokens = batch * seq_len
    qk, vo = s["nk"] * s["dk"], s["nv"] * s["dv"]
    fwd_flops = 2.0 * tokens / GDN_CHUNK * s["nv"] * chunk_macs(GDN_CHUNK, s["dk"], s["dv"])
    return {"fwd_flops": fwd_flops,
            # q, k a key head; v, o a value head; the log-decay and beta in float32
            "fwd_bytes": tokens * (ELEM * (2 * qk + 2 * vo) + 8 * s["nv"]),
            "bwd_flops": 2.0 * fwd_flops,
            # q, k, v, do in and dq, dk, dv out; g, beta in and their gradients out
            "bwd_bytes": tokens * (ELEM * (4 * qk + 3 * vo) + 16 * s["nv"])}


def least_seconds(hf, seq_len, batch, peaks, *, fwd_calls: int, bwd_calls: int = 0) -> float:
    w = call_work(hf, seq_len, batch)
    least = lambda kind: max(w[kind + "_flops"] / peaks["bf16_flops_per_sec"],  # noqa: E731
                             w[kind + "_bytes"] / peaks["hbm_bytes_per_sec"])
    return fwd_calls * least("fwd") + bwd_calls * least("bwd")
