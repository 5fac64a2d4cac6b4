"""Do two trees lower the same programs for a TPU?  On the CPU, no chip.

    JAX_PLATFORMS=cpu python3 tools/lowered_same.py lower <tree root> <out dir> [cell ...]
    JAX_PLATFORMS=cpu python3 tools/lowered_same.py same <out dir> <other out dir>

``lower`` writes, from the tree given (its own ``thunder_tpu``, ``chipbench`` and
``tests``; run a copy of this file against a parent's checkout), the text a
cell's programs lower to with ``lowering_platforms=("tpu",)``, Pallas on and
nothing interpreted, over weights that are shapes alone: a ``prefill_fresh``
bucket of ``mistral7b-serve-1chip`` (3,072), ``lfm2moe-serve-1chip`` (2,560)
and ``phi4flash-serve-1chip`` (5,120) at the depths
``tests/test_pallas_tpu_lowering.py`` builds them, ``decode_paged`` at one batch
and block bucket of ``mistral7b-serve-1chip`` (K/V blocks), ``lfm2moe-serve-1chip``
(a tail beside packed rows), ``axk1-serve-1chip`` (latent rows) and
``smallthinker-serve-1chip`` (rings beside blocks, 64 experts at 6 rows each;
two rows of 688 blocks), and ``mistral7b-train-1chip``'s step at one layer, the
grouped product given the VMEM a v5e reports (96 MiB: what the chip lowers;
since PR 60 a prompt's product copies its own weights and a decode step's does
not, so ``lfm2moe-serve-1chip.prefill_fresh_2560`` differs across that commit
and no ``decode_paged`` does; across PR 63 ``mistral7b-train-1chip.step_1layer``
differs, one backward flash kernel for two, and no serve program does).  ``same``
compares two such directories file by file.  A
Mosaic kernel's body is bytecode that carries its source's path and line
numbers, so each is parsed and printed without locations first; everything
else is compared as it is.  Exits non-zero where a text differs."""
import base64
import hashlib
import os
import re
import sys

CELLS = ("mistral7b-serve-1chip", "lfm2moe-serve-1chip", "phi4flash-serve-1chip", "axk1-serve-1chip",
         "smallthinker-serve-1chip", "mistral7b-train-1chip")


def lower(root, out, cells):
    root = os.path.abspath(root)
    sys.path[:0] = [root, os.path.join(root, "tests")]
    os.chdir(root)
    import jax
    import jax.numpy as jnp

    from thunder_tpu.executors import pallasex as px

    assert px.__file__.startswith(root), px.__file__
    px._interpret = lambda: False
    px._pallas_available = px._enabled = lambda: True
    px._gmm_vmem_cap = lambda: 96 << 20
    os.makedirs(out, exist_ok=True)

    def keep(name, lowered):
        text = lowered.as_text()
        with open(os.path.join(out, name + ".mlir"), "w") as f:
            f.write(text)
        print(name, len(text), "bytes,", text.count("tpu_custom_call"), "kernels,",
              text.count('kernel_name = "_flash_fwd"'), "_flash_fwd", flush=True)

    sds = lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype)   # noqa: E731
    one = lambda shape, dt=jnp.int32: jax.ShapeDtypeStruct(shape, dt)   # noqa: E731

    def prefill(eng, params, Tb):
        nbb = Tb // eng.pool.block_size
        args = (jax.tree_util.tree_map(sds, params), one((1, Tb)), one(()), jax.tree_util.tree_map(sds, eng.pool.arenas),
                one((nbb,)), one((2,), jnp.uint32), {}, one((1,)), one((1,)))
        return eng._build_prefill(Tb, nbb, fresh=True).trace(*args).lower(lowering_platforms=("tpu",))

    def decode(eng, params, rows, width):
        """``decode_paged`` at ``(rows, width)`` with the operands the engine's dispatch hands it: after the adapter
        slots the expert share's running sums (a sparse model), then the state slots (a state a request)."""
        args = (jax.tree_util.tree_map(sds, params), one((rows,)), one((rows,)), one((rows, width)),
                jax.tree_util.tree_map(sds, eng.pool.arenas), one((rows, 2), jnp.uint32), {}, one((rows,)),
                *([one((4,), jnp.float32)] if eng._moe_rows is not None else []), *([one((rows,))] if eng._hybrid else []))
        return eng._build_decode_paged(rows, width).trace(*args).lower(lowering_platforms=("tpu",))

    if any("serve" in c for c in cells):
        import test_pallas_tpu_lowering as t

        assert t.__file__.startswith(root), t.__file__
    if "mistral7b-serve-1chip" in cells:
        keep("mistral7b-serve-1chip.prefill_fresh_3072",
             t._lower_prefill("mistral7b-serve-1chip.offline-batch", "prefill_fresh", None)[3])
        _, params, eng = t._cell_engine("mistral7b-serve-1chip.offline-batch")
        keep("mistral7b-serve-1chip.decode_paged_2x192", decode(eng, params, 2, 192))
    if "lfm2moe-serve-1chip" in cells:
        _, params, eng = t._lfm2_engine()
        keep("lfm2moe-serve-1chip.prefill_fresh_2560", prefill(eng, params, 2560))
        keep("lfm2moe-serve-1chip.decode_paged_256x256", decode(eng, params, 256, 256))
    if "phi4flash-serve-1chip" in cells:
        _, params, eng = t._flash_engine()
        keep("phi4flash-serve-1chip.prefill_fresh_5120", prefill(eng, params, 5120))
    if "axk1-serve-1chip" in cells:
        _, params, eng = t._mla_engine()
        keep("axk1-serve-1chip.decode_paged_64x640", decode(eng, params, 64, 640))
    if "smallthinker-serve-1chip" in cells:
        _, params, eng = t._prerouted_engine()
        keep("smallthinker-serve-1chip.decode_paged_2x688", decode(eng, params, 2, 688))
    if "mistral7b-train-1chip" in cells:
        from chipbench import common
        from chipbench.drivers import train

        _, config, mix = common.open_cell("mistral7b-train-1chip.seq8k")
        config = {**config, "num_hidden_layers": 1}
        built = train.build({"config": config, "mix": mix, "seed": 1, "arch": common.load_module("models", config["arch"]),
                             "devices": jax.devices()[:1]})
        step, state = built["step"], (built["params"], built["opt_state"])
        jax.lax.with_sharding_constraint = lambda x, s: x      # the step's constraint names the CPU mesh
        with step._mesh_context():
            fn = step._get_entry(*state, step._prepare(built["batch"]))["step"].__wrapped__
            shapes = jax.tree_util.tree_map(lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype), (*state, *built["batch"]))
            keep("mistral7b-train-1chip.step_1layer",
                 jax.jit(fn, donate_argnums=(0, 1)).trace(*shapes).lower(lowering_platforms=("tpu",)))


def plain(text):
    """``text`` with every Mosaic body replaced by a digest of its module printed without locations, and their count."""
    from jax._src.lib import tpu
    from jax._src.lib.mlir import ir

    def body(m):
        ctx = ir.Context()
        ctx.allow_unregistered_dialects = True
        tpu.register_dialect(ctx)
        with ctx:
            module = str(ir.Module.parse(base64.b64decode(m.group(1))))
        return '\\22body\\22: \\22' + hashlib.sha256(module.encode()).hexdigest() + '\\22'

    return re.subn(r'\\22body\\22: \\22([A-Za-z0-9+/=]+)\\22', body, text)


def same(a, b):
    differ = 0
    for name in sorted(os.listdir(a)):
        (ta, n), (tb, _) = (plain(open(os.path.join(d, name)).read()) for d in (a, b))
        differ += ta != tb
        print(name, "the same text" if ta == tb else "DIFFERENT", f"({len(ta)} bytes, {n} kernel bodies printed without locations)")
    return differ


if __name__ == "__main__":
    if sys.argv[1] == "lower":
        lower(sys.argv[2], sys.argv[3], sys.argv[4:] or CELLS)
    else:
        sys.exit(same(*sys.argv[2:4]))
