"""Grid-search the flash-attention kernel block sizes on the chip.

Writes one line per (BQ, BK) config: fwd ms and fwd+bwd ms at the sweep's
headline attention shape.  Needs a TPU; exits non-zero if any geometry
failed."""
import sys, os
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
import jax, jax.numpy as jnp
import thunder_tpu as tt
import thunder_tpu.torch as ltorch
from bench import _best_ms, require_tpu

print(require_tpu("flash_tune"), flush=True)
B, H, T, hs = 8, 32, 2048, 128
key = jax.random.PRNGKey(0)
k2 = lambda i: jax.random.fold_in(key, i)
q = jax.random.normal(k2(0), (B, H, T, hs), dtype=jnp.bfloat16)
k = jax.random.normal(k2(1), (B, H, T, hs), dtype=jnp.bfloat16)
v = jax.random.normal(k2(2), (B, H, T, hs), dtype=jnp.bfloat16)

GRID = [(512, 512), (256, 512), (512, 256), (256, 256), (1024, 512),
        (512, 1024), (1024, 1024), (128, 512), (256, 1024), (2048, 512)]

def sdpa(q, k, v):
    return ltorch.scaled_dot_product_attention(q, k, v, is_causal=True)

failed = []
for BQ, BK in GRID:
    os.environ["THUNDER_TPU_FLASH_BQ"] = str(BQ)
    os.environ["THUNDER_TPU_FLASH_BK"] = str(BK)
    jax.clear_caches()
    try:
        ffn = tt.jit(sdpa)
        gfn = tt.grad(lambda q, k, v: sdpa(q, k, v).sum(), argnums=(0, 1, 2))
        fwd = _best_ms(ffn, q, k, v, reps=2)
        fb = _best_ms(gfn, q, k, v, reps=2)
        print(f"BQ={BQ:4d} BK={BK:4d}: fwd {fwd:7.3f} ms  fwd+bwd {fb:7.3f} ms", flush=True)
    except Exception as e:  # a geometry Mosaic refuses is a result of the search
        failed.append((BQ, BK))
        print(f"BQ={BQ:4d} BK={BK:4d}: FAILED {type(e).__name__}: {str(e)[:120]}", flush=True)
if failed:
    sys.exit(f"flash_tune: {len(failed)} of {len(GRID)} geometries failed: {failed}")
