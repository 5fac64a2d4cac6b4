"""Activation rematerialization over the forward/backward split.

Capability analog of the reference's ``thunder/core/rematerialization.py``
(igraph min-cut over fusion pairs, ``find_cut`` :230,
``rematerialize_forward_and_backward`` :567).  TPU-first redesign: there are
no fusion pairs to cut — XLA owns fusion — so rematerialisation operates
directly on the **saved-for-backward set** of the trace-level fw/bw split:

- *anchors* are expensive-to-recompute outputs (matmul/conv/attention
  (MATMUL_OP), reductions, RNG) plus trace inputs;
- every other saved proxy whose producer cone back to anchors consists of
  cheap ops (elementwise, shape, casts) is dropped from the saved set and its
  cone is re-executed at the top of the backward trace;
- a greedy byte-accounting step only drops a proxy when the recomputation
  leaves it adds are smaller than the proxy itself.

The effect matches the reference's min-cut intent (save small/expensive,
recompute cheap/large — e.g. norm outputs re-derived from (input, var, mean),
rope rotations from the q/k projections, dtype casts from their sources)
while XLA CSEs and fuses the re-emitted ops into the backward program.
"""
from __future__ import annotations

from typing import Any, Sequence

from thunder_tpu.core import dtypes
from thunder_tpu.core.baseutils import check
from thunder_tpu.core.codeutils import SigInfo
from thunder_tpu.core.prims import OpTags, PrimIDs
from thunder_tpu.core.proxies import Proxy, TensorProxy
from thunder_tpu.core.symbol import BoundSymbol
from thunder_tpu.core.trace import TraceCtx, from_trace, tracectx
from thunder_tpu.core.transform_common import dce

__all__ = ["rematerialize_forward_and_backward", "saved_bytes"]

# ops cheap enough to re-execute in backward rather than save their outputs
_CHEAP_IDS = {
    PrimIDs.CONVERT_ELEMENT_TYPE,
    PrimIDs.BROADCAST_IN_DIM,
    PrimIDs.RESHAPE,
    PrimIDs.TRANSPOSE,
    PrimIDs.SLICE,
    PrimIDs.SQUEEZE,
    PrimIDs.CAT,
    PrimIDs.PAD,
    PrimIDs.FLIP,
    PrimIDs.WHERE,
    PrimIDs.CLAMP,
    PrimIDs.FULL,
    PrimIDs.IOTA,
}


def _is_cheap(bsym: BoundSymbol) -> bool:
    sym = bsym.sym
    if sym.id in _CHEAP_IDS:
        return True
    tags = set(sym.tags or ())
    return bool(
        tags & {OpTags.ELEMENTWISE_UNARY_OP, OpTags.ELEMENTWISE_BINARY_OP, OpTags.SHAPE_OP}
    )


def _is_anchor(bsym: BoundSymbol) -> bool:
    tags = set(bsym.sym.tags or ())
    return (
        OpTags.MATMUL_OP in tags
        or OpTags.REDUCTION_OP in tags
        or OpTags.RANDOM_OP in tags
        or bsym.sym.id in (PrimIDs.EMBEDDING, PrimIDs.EMBEDDING_BACKWARD)
    )


def _bytes(p: Proxy) -> int:
    if not isinstance(p, TensorProxy):
        return 0
    import numpy as np

    n = 1
    for s in p.shape:
        n *= int(s)
    try:
        width = np.dtype(dtypes.to_jax_dtype(p.dtype)).itemsize
    except Exception:
        width = 4
    return n * width


def _place_late(recompute: list, body: list, trace: TraceCtx | None = None) -> list:
    """``body`` with every op of ``recompute`` (in forward order) inserted
    just before the first op that needs one of its outputs, its own needs
    first.  With ``trace`` (where new proxies are named), what such a group
    of ops reads from outside (saved residuals, weights) first passes an
    ``optimization_barrier`` together with a value the backward pass has
    just made and the reader takes too (the gradient arriving at that
    layer): the compiler's scheduler is free to hoist a recomputation that
    depends on residuals alone to the top of the backward pass, and does."""
    import thunder_tpu.core.prims as prims
    from thunder_tpu.core.proxies import variableify

    producer = {o.name: (i, b) for i, b in enumerate(recompute) for o in b.flat_proxy_outs}
    placed: set[int] = set()
    made_late: set[str] = set()
    out: list = []

    def need(name: str, group: list) -> None:
        i, b = producer.get(name, (None, None))
        if b is None or i in placed:
            return
        placed.add(i)
        for a in b.flat_proxy_args:
            need(a.name, group)
        group.append(b)

    for b in body:
        group: list = []
        for a in b.flat_proxy_args:
            need(a.name, group)
        gate = next((a for a in b.flat_proxy_args if isinstance(a, TensorProxy) and a.name in made_late), None)
        if group and gate is not None and trace is not None:
            leaves = {a.name: a for g in group for a in g.flat_proxy_args
                      if isinstance(a, TensorProxy) and a.name not in producer}
            if leaves:
                with tracectx(trace):
                    held = prims.optimization_barrier.meta(*leaves.values(), gate)
                    out.append(prims.optimization_barrier.bind(*leaves.values(), gate, output=held))
                swap = {variableify(old): new for old, new in zip(leaves.values(), held)}
                group = [g.from_bsym_swap_proxies(swap, skip_output=True) for g in group]
        out.extend(group)
        out.append(b)
        made_late.update(o.name for o in b.flat_proxy_outs)
    return out


def saved_bytes(fw_trace: TraceCtx) -> int:
    """Total bytes of the forward trace's saved-for-backward residuals
    (the second element of its RETURN) — the quantity remat shrinks."""
    for b in fw_trace.bound_symbols:
        if b.sym.id == PrimIDs.RETURN and len(b.args) == 2:
            return sum(_bytes(p) for p in b.args[1] if isinstance(p, TensorProxy))
    return 0


def rematerialize_forward_and_backward(
    fw_trace: TraceCtx, bw_trace: TraceCtx, *, max_cone: int = 64, aggressive: bool = False
) -> tuple[TraceCtx, TraceCtx]:
    """Shrinks saved_for_backward by re-executing cheap producer cones in the
    backward trace.  Returns updated ``(fw_trace, bw_trace)`` honoring the
    split contract (fw returns ``(output, saved)``; bw takes
    ``(*saved, *cotangents)``).

    ``aggressive`` (the ZeRO-3 / full-checkpoint mode, reference
    ``rematerialization.py:389`` regather-in-backward): cones may recompute
    *expensive* ops too (matmuls — and, under SPMD, the param all-gathers
    GSPMD attaches to them), bottoming out only at trace inputs and other
    saved values, so residual memory shrinks toward the inputs at the cost
    of backward recompute.  RANDOM-tagged ops are never recomputed.

    Each recomputed op is placed just before the backward op that first
    reads it, not at the top of the backward trace, and held there by an
    ``optimization_barrier`` (:func:`_place_late`), so the recomputed
    activations of different layers are never alive together (a model whose
    cones are hundreds of megabytes a layer does not fit otherwise).
    """
    # locate the fw return bsym: (output, saved)
    ret = None
    for b in fw_trace.bound_symbols:
        if b.sym.id == PrimIDs.RETURN:
            ret = b
    check(ret is not None and len(ret.args) == 2, lambda: "fw trace is not an augmented forward")
    output, saved = ret.args
    saved = list(saved)
    saved_names = [p.name for p in saved]

    # producer map over fw bsyms (prims level)
    producer_of: dict[str, tuple[int, BoundSymbol]] = {}
    for idx, b in enumerate(fw_trace.bound_symbols):
        if b.sym.id == PrimIDs.RETURN:
            continue
        for o in b.flat_proxy_outs:
            producer_of[o.name] = (idx, b)

    input_names = {p.name for p in fw_trace.args if isinstance(p, Proxy)}
    anchor_names = {
        o.name
        for _, b in producer_of.values()
        for o in b.flat_proxy_outs
        if _is_anchor(b)
    }

    def cone_for(p: Proxy, stop: set[str]) -> tuple[list[tuple[int, BoundSymbol]], set[str]] | None:
        """Cheap-op producer cone of ``p``; leaves are inputs/anchors/other
        saved proxies.  None if the cone hits a non-cheap producer or the
        size cap."""
        bsyms: dict[int, BoundSymbol] = {}
        leaves: set[str] = set()
        stack = [p.name]
        seen = set()
        while stack:
            name = stack.pop()
            if name in seen:
                continue
            seen.add(name)
            if name != p.name and name in stop:
                leaves.add(name)
                continue
            if name in input_names:
                leaves.add(name)
                continue
            prod = producer_of.get(name)
            if prod is None:  # constant/number: nothing to recompute
                continue
            idx, b = prod
            if not aggressive and name != p.name and name in anchor_names:
                leaves.add(name)
                continue
            if aggressive:
                if OpTags.RANDOM_OP in set(b.sym.tags or ()):
                    return None
            elif not _is_cheap(b):
                return None
            if idx not in bsyms:
                bsyms[idx] = b
                if len(bsyms) > max_cone:
                    return None
                for a in b.flat_proxy_args:
                    stack.append(a.name)
        return sorted(bsyms.items()), leaves

    # greedy, biggest savings first
    removable: dict[str, tuple[list, set]] = {}
    order = sorted(
        (p for p in saved if isinstance(p, TensorProxy)), key=_bytes, reverse=True
    )
    saved_set = set(saved_names)
    for p in order:
        if p.name in input_names or (not aggressive and p.name in anchor_names):
            continue
        res = cone_for(p, stop=saved_set - {p.name} - set(removable))
        if res is None:
            continue
        bsyms, leaves = res
        # every leaf must become a bw arg (bw receives only saved+cotangents);
        # input leaves cost nothing — params/batch stay alive regardless
        new_leaves = [n for n in leaves if n not in saved_set]
        name_to_proxy = {o.name: o for _, b in producer_of.values() for o in b.flat_proxy_outs}
        added = sum(
            _bytes(name_to_proxy[n])
            for n in new_leaves
            if n in name_to_proxy and n not in input_names
        )
        if added >= _bytes(p):
            continue
        removable[p.name] = (bsyms, leaves)
        saved_set.update(new_leaves)

    if not removable:
        return fw_trace, bw_trace

    # final saved set: previous minus removed, plus new anchor leaves;
    # anything recomputed by a prepended bsym must not also stay an arg
    recompute_bsyms: dict[int, BoundSymbol] = {}
    for bsyms, _ in removable.values():
        for idx, b in bsyms:
            recompute_bsyms[idx] = b
    recomputed_names = {
        o.name for b in recompute_bsyms.values() for o in b.flat_proxy_outs
    }

    name_to_proxy: dict[str, Proxy] = {}
    for p in fw_trace.args:
        if isinstance(p, Proxy):
            name_to_proxy[p.name] = p
    for _, b in producer_of.values():
        for o in b.flat_proxy_outs:
            name_to_proxy.setdefault(o.name, o)

    new_saved_names = [
        n for n in saved_names if n not in removable and n not in recomputed_names
    ]
    for n in sorted(saved_set - set(saved_names), key=lambda n: producer_of.get(n, (1 << 30,))[0]):
        if n not in recomputed_names and n not in new_saved_names:
            new_saved_names.append(n)
    new_saved = [name_to_proxy[n] for n in new_saved_names]

    # rebuild fw return
    import thunder_tpu.core.prims as prims

    new_fw = from_trace(fw_trace)
    new_fw.bound_symbols = [b for b in fw_trace.bound_symbols if b.sym.id != PrimIDs.RETURN]
    with tracectx(new_fw):
        new_fw.bound_symbols.append(prims.python_return.bind(output, tuple(new_saved), output=None))
    new_fw.set_provenance("Rematerialization (forward)")

    # rebuild bw: recompute cones first (fw order), then the original body
    cotangents = [p for p in bw_trace.args if p.name not in set(saved_names)]
    new_bw = from_trace(bw_trace)
    prepend = [b for _, b in sorted(recompute_bsyms.items())]
    body = [b for b in bw_trace.bound_symbols]
    new_bw.bound_symbols = _place_late(prepend, body, new_bw)
    bw_args = new_saved + cotangents
    new_bw.args = tuple(bw_args)
    new_bw.set_siginfo(SigInfo(name="backward", args=[(p.name, None) for p in bw_args]))
    new_bw.names = set(bw_trace.names) | {p.name for p in bw_args} | recomputed_names
    new_bw.set_provenance("Rematerialization (backward)")
    new_bw = dce(new_bw)

    return new_fw, new_bw
