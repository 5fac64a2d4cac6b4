"""Time the paged decode walk alone on the chip, at the shapes three serve cells run it.

    python3 tools/paged_tune.py [--check] [--no-split] [--keys 128,256,1024] [--shapes wide,reason,reason-ring,rollouts,batch,longgen]

One decode step's worth of ``paged_attn_decode`` a shape, a call a layer that
walks:

- ``wide`` (``lfm2moe-serve-1chip.offline-wide``): 256 rows, 32 heads over 8 KV
  heads of 64 laid two to a 128-lane row, blocks of 16 in the cell's arena of
  47,104 blocks of three layers, a table 256 wide, contexts as
  ``chipbench/traffic/offline-wide.json``'s group leaves them in a slot;
- ``reason`` (``phi4flash-serve-1chip.offline-reason``, the global layer's blocks,
  which that layer and the seven cross layers walk: eight calls): 96 rows, 40
  heads over 20 of 64 lane-packed, differential (``packed_out``), 36,864 blocks,
  a table 552 wide; ``reason-ring``: the eight window layers' rings (33 blocks a
  slot, window 512);
- ``rollouts`` (``nemotron3super-serve-1chip.offline-rollouts``): 128 rows, 32
  heads over 2 KV heads of 128, 43,008 blocks, a table 496 wide, one layer;
- on request ``batch`` (``mistral7b-serve-1chip.offline-batch``: 32 rows, 8 KV heads
  of 128, sixteen layers, window 4,096) and ``longgen``
  (``olmo-hybrid-serve-1chip.offline-longgen``: 32 rows, 30 KV heads of 128, four layers).

A line a shape: ms a call by name from a device trace and the share of the
counted roofline (a context token's K and V read once a layer, ``2 ng hs``
bfloat16 numbers, over the chip's published bytes a second: what
``chipbench/layer_metrics/paged_attn_decode_roofline_share.*`` count; under a
window the tokens it keeps), then the split that says where a chunk's time goes:
the same kernel with its products taken out (the copies, the softmax and the loop
are left), with one entry's copies a chunk left of its ``C`` (the products, the
softmax and the loop are left; a block's keys are attended and the rest is
whatever the buffer holds), and with every row cut to one chunk (what a request
costs beside its walk).  The tool takes a part out by replacing
``pallasex._paged_dot``, ``_paged_start_chunk``, ``_paged_wait_chunk`` while it
traces.  ``--check`` first compares the compiled kernel with ``paged_attn_xla``.
Needs a TPU; exits non-zero without one, or if the check fails."""
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

# rows, query heads, KV heads, head size, block, layers in the arena, calls a step, table width, pool blocks
SHAPES = {
    "wide": dict(rows=256, nh=32, ng=8, hs=64, bs=16, layers=3, calls=3, table=256, pool=47104,
                 traffic="offline-wide"),
    "reason": dict(rows=96, nh=40, ng=20, hs=64, bs=16, layers=1, calls=8, table=552, pool=36864,
                   traffic="offline-reason", packed_out=True),
    "reason-ring": dict(rows=96, nh=40, ng=20, hs=64, bs=16, layers=8, calls=8, table=552, pool=97 * 33,
                        traffic="offline-reason", packed_out=True, window=512, ring=33),
    "rollouts": dict(rows=128, nh=32, ng=2, hs=128, bs=16, layers=1, calls=1, table=496, pool=43008,
                     traffic="offline-rollouts"),
    # the two cells whose slabs were near their bytes' time before PR 47 (32 and 120 KB a copy): not run unless asked for
    "batch": dict(rows=32, nh=32, ng=8, hs=128, bs=16, layers=16, calls=16, table=224, pool=6144,
                  traffic="offline-batch", window=4096),
    "longgen": dict(rows=32, nh=30, ng=30, hs=128, bs=16, layers=4, calls=4, table=208, pool=5120,
                    traffic="offline-longgen"),
}
DEFAULT = "wide,reason,reason-ring,rollouts"
REPS = 5
PARTS = ("_paged_dot", "_paged_start_chunk", "_paged_wait_chunk")


def cell_contexts(traffic: str, rows: int) -> np.ndarray:
    """A row's context as the cell's backlog leaves it in a slot: a pair of the
    mix's group, an eighth, three, five or seven eighths of its new tokens made."""
    with open(os.path.join(ROOT, "chipbench", "traffic", traffic + ".json")) as f:
        group = json.load(f)["group"]
    return np.asarray([group[r % len(group)][0] + group[r % len(group)][1] * (2 * (r // len(group) % 4) + 1) // 8
                       for r in range(rows)], np.int32)


def operands(contexts, *, nh, ng, hs, bs, layers, table, pool, ring=None, dtype=jnp.bfloat16, seed=0, **_):
    """``paged_attn_decode``'s operands: a row's blocks scattered over the pool
    (the allocator promises no runs), block 0 the sink, the table sink-padded;
    a head that divides 128 lane-packed, as the pool lays it out.  ``ring``: the
    tables of the window layers' rings instead, that many blocks a slot."""
    rows = len(contexts)
    rng = np.random.default_rng(seed)
    if ring is None:
        need = [-(-int(c) // bs) for c in contexts]
        assert sum(need) < pool and max(need) <= table, (sum(need), pool, max(need), table)
        ids = rng.permutation(np.arange(1, pool))
        tables, at = np.zeros((rows, table), np.int32), 0
        for r, n in enumerate(need):
            tables[r, :n], at = ids[at:at + n], at + n
        tables = jnp.asarray(tables)
    else:
        from thunder_tpu.serving.kv_pool import ring_tables
        tables = ring_tables(jnp.asarray(1 + rng.permutation(rows), jnp.int32), ring, table)
    P = max(1, 128 // hs)
    ka, va, kq, kf, kg = jax.random.split(jax.random.PRNGKey(seed), 5)
    shape = (pool, layers, ng // P, bs, P * hs)
    q = jax.random.normal(kq, (rows, nh, hs), dtype) * hs ** -0.5
    return (q, jax.random.normal(ka, shape, dtype), jax.random.normal(va, shape, dtype),
            jax.random.normal(kf, (rows, ng, hs), dtype), jax.random.normal(kg, (rows, ng, hs), dtype),
            tables, jnp.asarray(contexts, jnp.int32))


def one_step(shape):
    """One decode step's calls, each its own ``pallas_call``: the arena's layers in turn."""
    kw = dict(window=shape.get("window"), packed_out=shape.get("packed_out", False))

    def step(q, *ops):
        # a layer walked by several calls (the cross layers read the global layer's blocks) gets other queries a
        # call, or XLA keeps one call of them
        return sum(px.paged_attn_decode(q * (1 + c // shape["layers"]), *ops, layer=c % shape["layers"], **kw).astype(jnp.float32)
                   for c in range(shape["calls"]))
    return step


def check(ops, shape) -> float:
    """Largest difference between the compiled kernel and its XLA form, over the
    arena's layers, relative to the XLA form's largest element."""
    kw = dict(window=shape.get("window"), packed_out=shape.get("packed_out", False))
    worst = 0.0
    for layer in range(shape["layers"]):
        got = jax.jit(lambda *a: px.paged_attn_decode(*a, layer=layer, **kw))(*ops).astype(jnp.float32)   # noqa: B023
        want = jax.jit(lambda *a: jnp.squeeze(px.paged_attn_xla(                                           # noqa: B023
            a[0][:, :, None], a[1], a[2], a[3][:, :, None], a[4][:, :, None], a[5], a[6], layer=layer, **kw), -2)
        )(*ops).astype(jnp.float32).reshape(got.shape)
        worst = max(worst, float(jnp.max(jnp.abs(got - want)) / jnp.max(jnp.abs(want))))
    return worst


@contextmanager
def taken_out(what: str):
    """The kernel with a part of its chunk loop replaced by nothing: the
    products by a broadcast of one row of the second operand, a chunk's copies
    by the first entry's alone (with none the compiler hoists the keys' loads
    and both products out of the loop)."""
    if not what:            # nothing replaced: any tree's kernel, one before PR 47 among them
        yield
        return
    saved = {name: getattr(px, name) for name in PARTS}

    def no_dot(a, b, dims):
        g, m, n = jax.eval_shape(lambda a, b: jax.lax.dot_general(a, b, dims), a, b).shape
        row = jnp.concatenate([b[:, :1]] * -(-n // b.shape[2]), axis=2)[:, :, :n]
        return jnp.broadcast_to(row.astype(jnp.float32), (g, m, n))

    if what == "products":
        px._paged_dot = no_dot
    elif what == "copies":
        px._paged_start_chunk = lambda *a, C, **k: saved["_paged_start_chunk"](*a, C=1, **k)   # one entry of C a chunk
        px._paged_wait_chunk = lambda *a, C: saved["_paged_wait_chunk"](*a, C=1)
    try:
        yield
    finally:
        for name, fn in saved.items():
            setattr(px, name, fn)


def timed(ops, shape, what=""):
    """ms a call of the ops named ``paged_attn_decode`` and of whatever runs beside them."""
    from tools.flash_tune import kernel_ms

    with taken_out(what):
        step = jax.jit(one_step(shape))
        jax.block_until_ready(step(*ops))
    ms = kernel_ms(lambda: jax.block_until_ready(step(*ops)), REPS)
    own = sum(t for n, t in ms.items() if n.startswith("paged_attn_decode"))
    return own / shape["calls"], (sum(ms.values()) - own) / shape["calls"]


def one_shape(name, shape, args, bytes_a_second):
    """A shape's lines: the check, the whole kernel, other chunk caps, the split."""
    contexts = cell_contexts(shape["traffic"], shape["rows"])
    ops = operands(contexts, **shape)
    if args.check:
        worst = check(ops, shape)
        print(f"{name}: check: the compiled kernel and paged_attn_xla differ by {worst:.5f} of the largest element",
              flush=True)
        if worst > 0.02:                       # bfloat16 probabilities: a few thousandths
            sys.exit("paged_tune: the compiled kernel disagrees with its XLA form")
    lanes = ops[1].shape[-1]
    C = px.paged_kv_chunk_blocks(ops[1].shape[2], shape["bs"], lanes, ops[1].dtype.itemsize)
    keys = C * shape["bs"]
    kept = np.minimum(contexts, shape.get("window") or contexts.max())
    chunks = int(sum(-(-int(c) // keys) for c in kept))      # a window's first chunk may start inside one: about
    least_ms = float(kept.sum()) * 2 * shape["ng"] * shape["hs"] * 2 / bytes_a_second * 1e3
    whole, beside = timed(ops, shape)
    print(f"{name}: paged_attn_decode, {shape['rows']} rows of {shape['nh']} heads over {shape['ng']} of {shape['hs']}, "
          f"contexts {contexts.min()}-{contexts.max()} (mean {contexts.mean():.0f}; kept {kept.mean():.0f}), "
          f"chunks of {C} entries = {keys} keys ({chunks} a call, {2 * C * chunks} copies of "
          f"{ops[1].shape[2] * shape['bs'] * lanes * 2 // 1024} KB): {whole:.3f} ms a call, "
          f"{least_ms / whole:.3f} of the counted roofline ({least_ms:.3f} ms), {whole / chunks * 1e3:.2f} us a chunk, "
          f"{whole / (2 * C * chunks) * 1e6:.1f} ns a copy; beside it {beside:.3f} ms", flush=True)
    for cap in filter(None, args.keys.split(",")):
        keys_cap, _, mib = cap.partition(":")
        derived = px._PAGED_CHUNK_KEYS, px._PAGED_CHUNK_BYTES
        px._PAGED_CHUNK_KEYS, px._PAGED_CHUNK_BYTES = int(keys_cap), int(mib) << 20 if mib else derived[1]
        try:
            C_at = px.paged_kv_chunk_blocks(ops[1].shape[2], shape["bs"], lanes, ops[1].dtype.itemsize)
            at, _ = timed(ops, shape)
        finally:
            px._PAGED_CHUNK_KEYS, px._PAGED_CHUNK_BYTES = derived
        print(f"{name}: at most {keys_cap} keys a chunk in {mib or derived[1] >> 20} MiB ({C_at} entries): {at:.3f} ms a call, "
              f"{least_ms / at:.3f} of the counted roofline", flush=True)
    if args.no_split:
        return
    no_products, _ = timed(ops, shape, "products")
    no_copies, _ = timed(ops, shape, "copies")
    short = (*ops[:6], jnp.asarray(np.minimum(contexts, keys), jnp.int32))
    one_chunk, _ = timed(short, shape)
    print(f"{name}: split: products out {no_products:.3f} ms ({(whole - no_products) / chunks * 1e3:.2f} us a chunk are "
          f"the products'), one entry's copies a chunk {no_copies:.3f} ms ({(whole - no_copies) / chunks * 1e3:.2f} us a "
          f"chunk are the other copies'), one chunk a row {one_chunk:.3f} ms ({one_chunk / shape['rows'] * 1e3:.2f} us a "
          f"request, its one chunk in it)", flush=True)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--check", action="store_true")
    ap.add_argument("--shapes", default=DEFAULT)
    ap.add_argument("--no-split", action="store_true", help="the whole kernel's line alone (a tree before PR 47 has no parts)")
    ap.add_argument("--keys", default="", help="the whole kernel again at these values of pallasex._PAGED_CHUNK_KEYS (and, after a colon, MiB of "
                    "_PAGED_CHUNK_BYTES), e.g. 128,256,1024:4")
    args = ap.parse_args()
    device = device_info()
    if device["platform"] != "tpu":
        sys.exit(f"paged_tune: times the kernel on a device and needs a TPU; jax found "
                 f"{device['platform']!r} ({device['kind']}).  Nothing was measured.")
    print(device, flush=True)
    from chipbench import common
    bytes_a_second = common.peaks(device["kind"])["hbm_bytes_per_sec"]

    for name in args.shapes.split(","):
        one_shape(name, SHAPES[name], args, bytes_a_second)     # a shape's arenas go with its frame


if __name__ == "__main__":
    main()
