"""Operations and bytes of the Mamba-2 chunked scan (`executors/pallasex.py`
`_ssd_chunk_kernel`), from shapes.  In a trace it is the custom call named
`ssd_chunk_fwd` (`pallas_call(name=)`): one call a Mamba-2 layer a whole-prompt
prefill.

The chunked algorithm (state-space duality) on chunks of `Q` = `chunk_size`
tokens, `H` heads of `P` channels (`d = H P`) in `G` groups, `N` states; a chunk's
products, as the published algorithm has them (a multiply-add two operations):

- `C B^T`, one a group: `2 Q Q N` a group;
- the decayed scores times the inputs, a head: `2 Q Q P` a head (`2 Q Q d` a chunk);
- the read-out of the state before the chunk, `C S`: `2 Q N d`;
- the state's update, `B^T (x scale)`: `2 Q N d`.

So `2 Q (G Q N + Q d + 2 N d)` a chunk, `2 (G Q N + Q d + 2 N d)` a token: 6.6
MFLOP a token a layer at the cell's sizes.  The kernel itself multiplies more (a
lane tile's two heads of 64 by masked copies: the scores' product twice; the
update in two bfloat16 passes): that is its cost, not its work.  The elementwise
part (`Q Q` exponentials a head a chunk) is a hundredth of the products.

Bytes: `x` (bfloat16) and `B`, `C` (bfloat16) in, `y` (float32) out, `dt` and the
summed log-decay (float32, a head) in two layouts, once a token; the state in
and out once a call: 55 KB a token.  At the chip's peaks the bytes take twice
what the products do (0.36 ms against 0.17 at 5,120 tokens: 120 operations a
byte under the chip's 240), so the least time is the bytes': ISSUE 45 reckoned
the scan bound by compute, which it would be with `y` kept in VMEM for the gate
and the norm; `least_seconds` takes the greater of the two."""
X_ITEMSIZE, Y_ITEMSIZE, STATE_ITEMSIZE = 2, 4, 4


def matches(op) -> bool:
    return op.name.startswith("ssd_chunk_fwd")


def call_work(sizes: dict, tokens: int, chunk: int) -> dict:
    """One layer's scan of `tokens` tokens (a whole number of chunks)."""
    d, N, G, H = sizes["d"], sizes["N"], sizes["G"], sizes["H"]
    return {"flops": tokens * 2.0 * (G * chunk * N + chunk * d + 2 * N * d),
            "bytes": tokens * (d * (X_ITEMSIZE + Y_ITEMSIZE) + 2 * G * N * X_ITEMSIZE + 4 * H * 4)
            + 2 * d * N * STATE_ITEMSIZE}


def least_seconds(sizes: dict, tokens: int, chunk: int, peaks: dict) -> float:
    w = call_work(sizes, tokens, chunk)
    return max(w["flops"] / peaks["bf16_flops_per_sec"], w["bytes"] / peaks["hbm_bytes_per_sec"])
