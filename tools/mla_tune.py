"""Time the latent decode kernel alone on the chip, at the A.X-K1 cell's shape.

    python3 tools/mla_tune.py [--check]

One decode step's worth of ``mla_paged_decode``: 64 rows of 64 heads, rows of
640 (512 latent + 64 rotary + padding), blocks of 16 in the cell's arena of
32,768 blocks of six layers, a table 640 wide, one call a layer, contexts as
``chipbench/traffic/offline-longctx.json`` draws them (its sixteen pairs of
prompt and new tokens, four ages each: 2,624-9,480, mean 6,064).  One line: ms
a call by name from a device trace and the share of the counted roofline (a
context token's 576 numbers read once a layer for all heads, as
``chipbench/kernels/mla_paged_decode.py`` counts them), then the split that
says where a chunk's time goes: the same kernel with its products taken out
(the copies, the softmax and the loop are left), with one copy a chunk left of
its copies (the products, the softmax and the loop are left; a block's rows
are attended and the rest is whatever the buffer holds), and with every row
cut to one chunk (what a request costs beside its walk).  ``--check`` first compares the
compiled kernel with ``_mla_decode_xla``.  Needs a TPU; exits non-zero
without one, or if the check fails."""
import argparse
import json
import os
import sys
from contextlib import contextmanager

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)
import jax
import jax.numpy as jnp
import numpy as np

from thunder_tpu._platform import device_info
from thunder_tpu.executors import pallasex as px

# rows, heads, W (dc + dr padded to whole lane tiles), dc, block, layers, table width, pool blocks
CELL = dict(rows=64, nh=64, W=640, dc=512, bs=16, layers=6, table=640, pool=32768)
CONFIG = "axk1-serve-1chip.json"
REPS = 5


def cell_contexts(rows: int) -> np.ndarray:
    """A row's context as the cell's backlog leaves it in a slot: a pair of the
    mix's group, an eighth, three, five or seven eighths of its new tokens made."""
    with open(os.path.join(ROOT, "chipbench", "traffic", "offline-longctx.json")) as f:
        group = json.load(f)["group"]
    return np.asarray([group[r % len(group)][0] + group[r % len(group)][1] * (2 * (r // len(group) % 4) + 1) // 8
                       for r in range(rows)], np.int32)


def operands(contexts, *, nh, W, dc, bs, layers, table, pool, dtype=jnp.bfloat16, seed=0, **_):
    """``mla_paged_decode``'s operands: a row's blocks scattered over the pool
    (the allocator promises no runs), block 0 the sink, the table sink-padded."""
    rows = len(contexts)
    rng = np.random.default_rng(seed)
    need = [-(-int(c) // bs) for c in contexts]
    assert sum(need) < pool and max(need) <= table, (sum(need), pool, max(need), table)
    ids = rng.permutation(np.arange(1, pool))
    tables, at = np.zeros((rows, table), np.int32), 0
    for r, n in enumerate(need):
        tables[r, :n], at = ids[at:at + n], at + n
    ka, kq, kf = jax.random.split(jax.random.PRNGKey(seed), 3)
    arena = jax.random.normal(ka, (pool, layers, 1, bs, W), dtype)
    q = jax.random.normal(kq, (rows, nh, W), dtype) * W ** -0.5
    fresh = jax.random.normal(kf, (rows, W), dtype)
    return q, arena, fresh, jnp.asarray(tables), jnp.asarray(contexts, jnp.int32)


def all_layers(layers, dc, scale=1.0):
    """One decode step's calls: every layer's, each its own ``pallas_call``."""
    def step(q, arena, fresh, tables, pos):
        return sum(px.mla_paged_decode(q, arena, fresh, tables, pos, layer=l, dc=dc, scale=scale).astype(jnp.float32)
                   for l in range(layers))
    return step


def check(contexts, shape, dtype=jnp.bfloat16) -> float:
    """Largest difference between the kernel (compiled on a TPU, interpreted
    elsewhere) and its XLA form, over every layer, relative to the XLA form's
    largest element."""
    ops = operands(contexts, dtype=dtype, **shape)
    worst = 0.0
    for layer in range(shape["layers"]):
        kw = dict(layer=layer, dc=shape["dc"], scale=1.0)
        got = jax.jit(lambda *a: px.mla_paged_decode(*a, **kw))(*ops).astype(jnp.float32)      # noqa: B023
        want = jax.jit(lambda *a: px._mla_decode_xla(*a, **kw))(*ops).astype(jnp.float32)      # noqa: B023
        worst = max(worst, float(jnp.max(jnp.abs(got - want)) / jnp.max(jnp.abs(want))))
    return worst


@contextmanager
def taken_out(what: str):
    """The kernel with a part of its chunk loop replaced by nothing: the
    products by a broadcast of one row of the second operand, a chunk's copies
    by the first of them alone (with none the compiler hoists the rows' loads
    and both products out of the loop)."""
    saved = {name: getattr(px, name) for name in ("_mla_dot", "_mla_start_chunk", "_mla_wait_chunk")}

    def no_dot(a, b, dims):
        m, n = jax.eval_shape(lambda a, b: jax.lax.dot_general(a, b, dims), a, b).shape
        row = jnp.concatenate([b[:1]] * -(-n // b.shape[1]), axis=1)[:, :n]
        return jnp.broadcast_to(row.astype(jnp.float32), (m, n))

    if what == "products":
        px._mla_dot = no_dot
    elif what == "copies":
        px._mla_start_chunk = lambda *a, C, **k: saved["_mla_start_chunk"](*a, C=1, **k)   # one copy of C a chunk
        px._mla_wait_chunk = lambda *a, C: saved["_mla_wait_chunk"](*a, C=1)
    try:
        yield
    finally:
        for name, fn in saved.items():
            setattr(px, name, fn)


def timed(ops, shape, what=""):
    """ms a call of the ops named ``mla_paged_decode`` and of whatever runs beside them."""
    from tools.flash_tune import kernel_ms

    with taken_out(what):
        step = jax.jit(all_layers(shape["layers"], shape["dc"]))
        jax.block_until_ready(step(*ops))
    ms = kernel_ms(lambda: jax.block_until_ready(step(*ops)), REPS)
    own = sum(t for n, t in ms.items() if n.startswith("mla_paged_decode"))
    return own / shape["layers"], (sum(ms.values()) - own) / shape["layers"]


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--check", action="store_true")
    args = ap.parse_args()
    device = device_info()
    if device["platform"] != "tpu":
        sys.exit(f"mla_tune: times the kernel on a device and needs a TPU; jax found "
                 f"{device['platform']!r} ({device['kind']}).  Nothing was measured.")
    print(device, flush=True)
    from chipbench import common
    counted = common.load_module("kernels", "mla_paged_decode")

    contexts = cell_contexts(CELL["rows"])
    if args.check:
        worst = check(contexts, CELL)
        print(f"check: the compiled kernel and _mla_decode_xla differ by {worst:.5f} of the largest element", flush=True)
        if worst > 0.02:                       # bfloat16 probabilities: a few thousandths
            sys.exit("mla_tune: the compiled kernel disagrees with its XLA form")
    ops = operands(contexts, **CELL)
    keys = px.mla_chunk_keys(CELL["bs"], CELL["W"], ops[1].dtype.itemsize)
    chunks = int(sum(-(-int(c) // keys) for c in contexts))
    least_ms = counted.least_seconds(common.load_json("configs", CONFIG), float(contexts.sum()),
                                     common.peaks(device["kind"])) / CELL["layers"] * 1e3
    whole, beside = timed(ops, CELL)
    print(f"mla_paged_decode, {CELL['rows']} rows of {CELL['nh']} heads, contexts {contexts.min()}-{contexts.max()} "
          f"(mean {contexts.mean():.0f}), chunks of {keys} keys ({chunks} a call): {whole:.3f} ms a call, "
          f"{least_ms / whole:.3f} of the counted roofline ({least_ms:.3f} ms), {whole / chunks * 1e3:.2f} us a chunk; "
          f"beside it {beside:.3f} ms", flush=True)
    no_products, _ = timed(ops, CELL, "products")
    no_copies, _ = timed(ops, CELL, "copies")
    short = (*ops[:4], jnp.minimum(ops[4], keys))
    one_chunk, _ = timed(short, CELL)
    print(f"split: products out {no_products:.3f} ms ({(whole - no_products) / chunks * 1e3:.2f} us a chunk are the products'), "
          f"one copy a chunk {no_copies:.3f} ms ({(whole - no_copies) / chunks * 1e3:.2f} us a chunk are the other copies'), "
          f"one chunk a row {one_chunk:.3f} ms ({one_chunk / CELL['rows'] * 1e3:.2f} us a request, its one chunk in it)", flush=True)


if __name__ == "__main__":
    main()
