"""The XLA fusion executor: trace regions → single jax.jit-compiled programs.

Capability analog of the reference's nvFuser executor
(``thunder/executors/nvfuserex_impl.py``): it partitions the trace into
maximal fusible regions and compiles each into one callable.  On TPU the
"fusion backend" is XLA itself — a region becomes a pure-JAX function
(re-evaluating the region's bound symbols over jax values) wrapped in
``jax.jit``, so XLA performs fusion, layout assignment, and latency hiding.
Unlike nvFuser there is no bookending heuristic: XLA handles meta/shape ops
fine inside a program, so regions are as large as possible (ideally the whole
computation), which is exactly the TPU-idiomatic design.
"""
from __future__ import annotations

import time
from typing import Any, Callable, Sequence

import jax

from thunder_tpu.core.compile_data import get_compile_option
from thunder_tpu.core.prims import OpTags, PrimIDs
from thunder_tpu.core.proxies import Proxy, TensorProxy, unvariableify
from thunder_tpu.core.symbol import BoundSymbol, Symbol
from thunder_tpu.core.trace import TraceCtx, TraceProvenance, TraceTag, from_trace
from thunder_tpu.core.utils import consumers, producers
from thunder_tpu.extend import FusionExecutor, add_default_executor, register_executor
from thunder_tpu.executors.utils import Region, lower_bsyms
from thunder_tpu.observability.events import span as _phase_span

__all__ = ["XLAFusionExecutor", "ex", "xla_ex"]

_NONFUSIBLE_IDS = {
    PrimIDs.RETURN,
    PrimIDs.DEL,
    PrimIDs.COMMENT,
    PrimIDs.PRINT,
    PrimIDs.ITEM,
    PrimIDs.DEVICE_PUT,
    PrimIDs.GET_GRAD,
    PrimIDs.PUT_GRAD,
}


class FusionCallable:
    """A compiled region; keeps the sub-trace for inspection and re-lowering."""

    def __init__(self, name: str, bsyms: Sequence[BoundSymbol], inputs: Sequence[Proxy], outputs: Sequence[Proxy],
                 backward: bool = False):
        self.name = name
        self.bsyms = list(bsyms)
        #: a region of a backward trace: its operations' scopes start with ``bwd``
        self.backward = backward
        self.input_names = [p.name for p in inputs]
        self.output_names = [p.name for p in outputs]
        #: positions donated to XLA (set post-lowering by the donation pass —
        #: executors/donation.py — never at construction, so the donate=False
        #: path compiles the exact program it always did)
        self.donate_argnums: tuple[int, ...] = ()
        #: input name -> output name alias hints (introspection/metrics; the
        #: actual buffer aliasing is XLA's, via donate_argnums)
        self.out_aliases: dict[str, str] = {}
        self._jitted = jax.jit(self._raw)
        self._compiled_once = False

    def set_donation(self, argnums: Sequence[int], aliases: dict | None = None) -> None:
        """Re-arms the region with ``donate_argnums`` (donation pass only).
        The jit is rebuilt — it is lazy, so nothing recompiles until the next
        call — and the compile event re-fires for the donated program."""
        self.donate_argnums = tuple(sorted(argnums))
        self.out_aliases = dict(aliases or {})
        self._jitted = jax.jit(
            self._raw, donate_argnums=self.donate_argnums or None
        )
        self._compiled_once = False

    def _raw(self, *vals):
        env = dict(zip(self.input_names, vals))
        lower_bsyms(self.bsyms, env, backward=self.backward)
        return tuple(env[n] for n in self.output_names)

    def __call__(self, *vals):
        if self.donate_argnums:
            # a donated input from an EARLIER call may arrive here deleted
            # (donation consumes the caller's array); catch it before XLA
            # does so the error names the proxy and the source lines that
            # built the region, not just an anonymous deleted buffer
            for i in self.donate_argnums:
                v = vals[i] if i < len(vals) else None
                if getattr(v, "is_deleted", None) is not None and v.is_deleted():
                    from thunder_tpu.core.symbol import gather_provenance
                    from thunder_tpu.executors.donation import DonationError

                    prov = ""
                    for b in self.bsyms:
                        entries = gather_provenance(b)
                        if entries:
                            fname, pos = entries[0]
                            lineno = getattr(pos, "lineno", pos)
                            prov = f" (region built from {fname}:{lineno})"
                            break
                    raise DonationError(
                        f"input {self.input_names[i]!r} (position {i}) of fusion "
                        f"region {self.name} was donated by an earlier call and its "
                        f"buffer is gone{prov} — donated inputs are CONSUMED: pass a "
                        f"fresh array (feed the outputs forward) or compile with "
                        f"donate=False"
                    )
            # backends without donation (CPU) and declined donations warn per
            # execute; the shared helper silences exactly that message
            from thunder_tpu.executors.donation import suppress_unusable_donation_warnings

            with suppress_unusable_donation_warnings():
                return self._call_impl(*vals)
        return self._call_impl(*vals)

    def _call_impl(self, *vals):
        if not self._compiled_once:
            # the first call triggers XLA tracing+compilation (jax.jit is
            # lazy); record it as a pipeline event.  Shape-change recompiles
            # are not re-spanned — one flag check per call is the budget here
            self._compiled_once = True
            with _phase_span("xla_compile", fusion=self.name, ops=len(self.bsyms)):
                return self._jitted(*vals)
        return self._jitted(*vals)

    def lower_hlo(self, *abstract_vals) -> str:
        return self._jitted.lower(*abstract_vals).as_text()

    def __repr__(self):
        return f"<FusionCallable {self.name}: {len(self.bsyms)} ops>"


class XLAFusionExecutor(FusionExecutor):
    def __init__(self):
        super().__init__("xla", version=jax.__version__)

    def _is_fusible(self, bsym: BoundSymbol) -> bool:
        sym = bsym.sym
        if sym.id in _NONFUSIBLE_IDS:
            return False
        if getattr(sym, "_xla_fusible", False):
            return True
        from thunder_tpu.executors.jaxex import prim_impls

        if sym.id in prim_impls:
            return True
        if sym.tags and OpTags.UNPACK_OP in sym.tags or (sym.tags and OpTags.CHECK_OP in sym.tags):
            return False
        # composites whose subsymbols are all fusible
        if bsym.subsymbols:
            return all(self._is_fusible(s) for s in bsym.subsymbols)
        return False

    def can_fuse(self, bsym: BoundSymbol) -> bool:
        return self._is_fusible(bsym)

    def fuse(self, region_bsyms: list[BoundSymbol], fusion_counter: int, producers_map, consumers_map, return_proxies,
             backward: bool = False) -> BoundSymbol:
        region = Region(producers_map, consumers_map, region_bsyms)
        # tensors have runtime identity; numbers resolve statically UNLESS
        # their value is unknown at trace time (item() results) — those are
        # runtime scalars and must enter the region as inputs
        from thunder_tpu.core.proxies import NumberProxy

        inputs = [
            p
            for p in (unvariableify(v) for v in region.inputs)
            if isinstance(p, TensorProxy) or (isinstance(p, NumberProxy) and p.value is None)
        ]
        outputs = [unvariableify(v) for v in region.outputs]
        # proxies returned from the trace must also escape the fusion
        out_names = {p.name for p in outputs}
        for p in return_proxies:
            produced_here = any(p.name in (o.name for o in b.flat_proxy_outs) for b in region_bsyms)
            if produced_here and p.name not in out_names:
                outputs.append(p)
                out_names.add(p.name)

        name = f"XLA{fusion_counter}"
        fusion = FusionCallable(name, region_bsyms, inputs, outputs, backward=backward)
        sym = Symbol(name=name, meta=None, is_fusion=True, executor=self)
        bsym = sym.bind(
            *inputs,
            output=tuple(outputs),
            subsymbols=tuple(region_bsyms),
            _call_ctx={name: fusion},
        )
        # a fused region keeps the provenance LIST of every op it absorbed
        # (filename stays None: the list rides in source_positions, which
        # gather_provenance and the anomaly reporter understand) so the user
        # file:line survives even if a later pass drops the subsymbols
        from thunder_tpu.core.symbol import gather_provenance

        bsym.source_positions = list(gather_provenance(bsym))
        return bsym

    @_phase_span("lower:xla_fusion")
    def fusion_pass(self, trace: TraceCtx) -> TraceCtx:
        from thunder_tpu.core.trace import _execution_file

        if _execution_file.get() is not None:
            # execution-callback-file debugging: the dumped program must stay
            # hand-editable, and an XLA fusion's constants live inside an
            # opaque compiled callable — keep per-prim eager execution instead
            return trace
        start = time.perf_counter_ns()

        min_size = get_compile_option(
            "xla_min_fusion_size",
            "Minimum number of bound symbols in a region for it to be compiled as one XLA program (default 2).",
            default=2,
        )

        producers_map = producers(trace)
        consumers_map = consumers(trace)

        from thunder_tpu.core.prims import PrimIDs as _P

        return_proxies: list[Proxy] = []
        for bsym in trace.bound_symbols:
            if bsym.sym.id == _P.RETURN:
                return_proxies.extend(bsym.flat_proxy_args)

        # dataflow-aware partitioning (reference data_dependent_partition.py):
        # fusible islands regroup around non-fusible bsyms instead of being
        # split by them
        from thunder_tpu.executors.data_dependent_partition import fuse_bound_symbols

        groups = fuse_bound_symbols(trace.bound_symbols, self._is_fusible)

        def weight(bsym: BoundSymbol) -> int:
            # region size counts FLATTENED prims: one composite call (gelu,
            # softmax) is one top-level bsym but many ops — leaving it
            # unfused would decompose it to per-prim eager jax dispatch,
            # ~10× per-call overhead on small ops
            if not bsym.subsymbols:
                # a leaf prim whose jnp impl is itself a multi-op program
                # (fused sdpa/CE decompositions, matmul-class ops) is worth a
                # compiled region on its own — executing it eagerly pays one
                # dispatch per internal jnp op
                if bsym.sym.tags and OpTags.MATMUL_OP in bsym.sym.tags:
                    return 1_000
                return 1
            return sum(weight(s) for s in bsym.subsymbols)

        new_bsyms: list[BoundSymbol] = []
        fusion_counter = 0
        for g in groups:
            if (
                not g.fusible
                or sum(weight(b) for b in g.bsyms) < int(min_size)
                or not self.get_fuel()
            ):
                new_bsyms.extend(g.bsyms)
            else:
                new_bsyms.append(self.fuse(g.bsyms, fusion_counter, producers_map, consumers_map, return_proxies,
                                           backward=TraceTag.BACKWARD in trace.tags))
                fusion_counter += 1

        ntrace = from_trace(trace)
        ntrace.bound_symbols = new_bsyms
        elapsed = (time.perf_counter_ns() - start) // 1000000
        ntrace.set_provenance(TraceProvenance(f"XLA Fusion (took {elapsed} milliseconds)"))
        return ntrace


ex = XLAFusionExecutor()
register_executor(ex)
xla_ex = ex
add_default_executor(ex)
