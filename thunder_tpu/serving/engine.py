"""Serving engine front-end: ``tt.serve(...)`` → :class:`ServingEngine`.

Continuous (in-flight) batching over the compiled decode step: independent
requests share one bucketed decode program, join the batch the step after
their prefill, and leave it the step they finish — batch occupancy is a
scheduling property, not a caller-visible one.  The engine composes the
pieces the repo already has:

- ``models.generate.forward_with_cache`` is the model step of a prompt —
  the pool's gathered block views reassemble exactly the dense cache layout
  it consumes; ``serving.paged_attention.forward_paged`` is the same math a
  token a row, its attention straight off the block arena (one decode
  program a job: the kernel where it compiles, its XLA form elsewhere);
- the **paged pool** (:mod:`serving.kv_pool`) owns cache memory; every
  program donates the arenas so updates stay in place (PR 4);
- the **scheduler** (:mod:`serving.scheduler`) owns admission, FIFO order,
  deadlines, and the bucket sets that bound recompiles (absorbed by the
  PR-1 dispatch cache when the model fn is a ``tt.jit`` product);
- **observability** (PRs 2–3): queue/occupancy/pool gauges, TTFT/TPOT and
  tokens/sec histograms in the metrics registry, per-request JSONL records
  through :class:`observability.telemetry.StepLogger`;
- **multi-tenancy**: ``kv_dtype="int8"`` stores the arenas quantized
  (:mod:`serving.quant`), and ``lora=AdapterRegistry(...)`` routes each
  request through a per-request LoRA adapter (:mod:`serving.lora`) — both
  live inside the same bucket programs, keyed only by storage dtype and
  registry geometry.

Reproducibility contract: each request carries its own PRNG key chain and
splits it exactly like a solo ``generate()`` call (one split at prefill, one
per decode step; per-row sampling under ``vmap`` is bit-equivalent to the
unbatched call), so a request's tokens do not depend on what else shares
the batch — and greedy tokens match ``generate()`` exactly.

The drive loop is an explicit, threadless **event loop** with two lanes
(``async_step=True``, the default):

- the **decode lane** dispatches the jitted decode program for batch *k*
  and — exploiting JAX's async dispatch, which the CPU backend shares —
  returns to the host immediately; admissions, scheduling, chunk
  dispatches, and token streaming for batch *k−1* all run while the device
  computes, and the next ``step()`` harvests the in-flight tokens (the
  only host block, measured into ``serving.decode.stall_s`` and the
  ``serving.step.overlap_frac`` gauge).  It keeps **one decode step
  ahead** while the batch is steady: where host state alone says that step
  *k+1* runs on the batch of the in-flight step *k* (no window lets blocks
  go, no constraint mask, deadline or prefill piece is due, the
  decode-ready rows are the chain's: :meth:`ServingEngine._ahead_batch`),
  *k+1* is dispatched from the chained device state **before** *k* is
  harvested, so the fetch of *k*'s tokens, the emit walk and the stream
  callbacks run under the device and not beside it.  The chain goes
  **through a row's end by length**: where *k* is the step at which a row
  writes its last position (the host knows it from ``stop``) and another
  row of the chain outlives it, *k+1* still leaves ahead, on the chain's
  rows, the ended row among them as one dead row-step
  (``dead_scan_row``); the row's finish, its blocks' and slot's return
  and the client's callback run under *k+1*, and the harvest of *k* drops
  the chain, so the step its successor joins is the one turnover step a
  finished row costs.  Never a step for nobody (a chain whose every row
  ends at *k* stops there), never two steps past an end, never a token
  for the dead row (nothing emitted, its key and ``pos`` where the end
  left them).  A turnover step (a row joins, or anything else changed)
  keeps the order above; ``stats()["decode_ahead"]`` and the ``ahead``
  and ``ending`` arguments of the ``serve.decode_dispatch`` span say how
  often each happened;
- the **prefill lane** splits prompts longer than ``prefill_chunk`` into
  block-aligned pow-2 chunks (program kind ``prefill_chunk``, bounded by
  the same ``_table_widths``/bucket accounting) and dispatches at most one
  chunk per request per step, interleaved between decode dispatches — a
  long prompt can no longer stall TPOT for running requests.

``async_step=False`` keeps the original fully synchronous path
byte-identical (admit → prefill → one decode → block on host
materialization); either way ``step()`` runs one scheduler iteration and
``run()``/``drain()`` loop it.  Served tokens are bit-identical across the
two modes and to solo ``generate()`` — deferred materialization reorders
host work, never device math, and each request's PRNG chain still splits
exactly like the solo path.  No threads — integrate into any host loop.

Serving-plane observability (all off by default; the off path is an
``is None`` check per touch point):

- ``trace=True`` / ``THUNDER_TPU_TRACE_SERVING=1`` — per-request lifecycle
  spans (queued / prefill split into compile-or-dispatch + host / every
  decode step / finish) plus ``engine.step`` spans into the shared event
  ring; ``tt.export_chrome_trace`` merges them with the compile-pipeline
  rows into one Perfetto timeline (:mod:`observability.tracing`);
- ``slo={"ttft_s": ..., "tpot_s": ...}`` — windowed good/bad counters and
  burn-rate gauges per finished request, surfaced by
  :meth:`ServingEngine.slo_report` (:mod:`observability.slo`);
- ``flight_recorder=True`` / ``THUNDER_TPU_FLIGHT_RECORDER=1`` — bounded
  ring of engine events + scheduler/pool state, auto-dumped to JSON when
  ``step()`` raises, exportable any time via ``tt.flight_record(path)``
  (:mod:`observability.flight`).
"""
from __future__ import annotations

import contextlib
import time
from dataclasses import dataclass
from functools import partial
from typing import Any, Callable, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from thunder_tpu.models.generate import (
    build_rope_cache,
    forward_with_cache,
    sample_token,
    state_shapes,
)
from thunder_tpu.observability.config import (
    flight_recorder_env_enabled,
    serving_trace_env_enabled,
)
from thunder_tpu.observability.events import scope, span
from thunder_tpu.observability.flight import FlightRecorder
from thunder_tpu.observability.goodput import resolve_goodput
from thunder_tpu.observability.metrics import registry
from thunder_tpu.observability.slo import resolve_slo
from thunder_tpu.observability.tracing import RequestTracer
from thunder_tpu.serving.faults import (
    CLASS_REQUEST,
    CLASS_TRANSIENT,
    FP_DECODE,
    FP_HARVEST,
    FP_PREFILL,
    FP_SCATTER,
    RecoveryError,
    RetryPolicy,
    WatchdogTimeout,
    classify_fault,
    fault_cause,
    resolve_fault_plan,
)
from thunder_tpu.serving.kv_pool import (
    RING_ARENAS,
    gather_state,
    scatter_state,
    SINK_BLOCK,
    PagedKVPool,
    PrefixIndex,
    chunk_tables,
    gather_dense,
    gather_rows,
    ring_dest,
    ring_tables,
    scatter_blocks,
)
from thunder_tpu.serving.lora import gather_adapter_slots
from thunder_tpu.serving.quant import (
    gather_dense_q,
    scatter_blocks_q,
)
from thunder_tpu.serving.scheduler import (
    FINISH_DEADLINE,
    FINISH_EOS,
    FINISH_ERROR,
    FINISH_EVICTED,
    FINISH_LENGTH,
    AdmissionError,
    Request,
    Scheduler,
    pick_bucket,
)

__all__ = [
    "serve",
    "ServingEngine",
    "RequestHandle",
    "RequestResult",
    "AdmissionError",
    "EngineStalledError",
    "RecoveryError",
]


class EngineStalledError(RuntimeError):
    """``drain()``/``result()`` could not make progress: requests remain
    queued/running but ``step()`` did no work (e.g. blocks leaked outside
    the scheduler, or a queue head that can never fit the live pool).
    Carries the flight-recorder state snapshot — queued/running request
    rows, pool free/lease counts, compile log — as ``.state`` and inlines
    the headline numbers in the message so a stall is diagnosable from the
    traceback alone.  Under dp-replicated serving the router sets
    ``replica`` to the stalled engine's index and passes THAT replica's
    flight state, so a fleet stall names its culprit instead of assuming
    one engine."""

    def __init__(self, msg: str, state: dict | None = None, *,
                 replica: int | None = None):
        self.replica = replica
        if replica is not None:
            msg = f"replica {replica}: {msg}"
        self.state = state or {}
        sched = self.state.get("scheduler", {})
        pool = self.state.get("pool", {})
        rows = sched.get("requests", [])
        rids = {
            "queued": [r["rid"] for r in rows if r.get("state") == "queued"],
            "running": [r["rid"] for r in rows if r.get("state") == "running"],
        }
        detail = (
            f" [queued rids={rids['queued']} running rids={rids['running']} "
            f"pool free={pool.get('num_free')}/{pool.get('num_blocks')} "
            f"leased={pool.get('leased_blocks')} shared={pool.get('shared_blocks')}]"
            if self.state else ""
        )
        super().__init__(msg + detail)


@dataclass(frozen=True)
class RequestResult:
    """Structured outcome of one served request."""

    rid: int
    prompt: np.ndarray
    new_tokens: tuple[int, ...]
    finish_reason: str                      # length | eos | deadline | evicted | error
    ttft_s: float | None                    # submit → first token
    tpot_s: float | None                    # mean per-token after the first
    tokens_per_sec: float | None
    queue_s: float | None                   # submit → admission
    e2e_s: float | None                     # submit → finish wall time
    shared_prefix_blocks: int
    prefill_compiled: bool = False          # the prefill run paid an XLA compile
    error: dict | None = None               # structured cause when quarantined
    tokens_recomputed: int = 0              # prompt positions re-dispatched by replay
    recompute_causes: tuple = ()            # why (goodput waste-cause names)

    @property
    def tokens(self) -> np.ndarray:
        """Full sequence (prompt + generated), the solo ``generate()`` row."""
        return np.concatenate([self.prompt, np.asarray(self.new_tokens, dtype=np.int32)])


class RequestHandle:
    """Caller's view of a submitted request."""

    def __init__(self, engine: "ServingEngine", req: Request):
        self._engine = engine
        self._req = req

    @property
    def rid(self) -> int:
        return self._req.rid

    @property
    def state(self) -> str:
        return self._req.state

    def done(self) -> bool:
        return self._req.state == "finished"

    def tokens_so_far(self) -> tuple[int, ...]:
        return tuple(self._req.generated)

    def result(self, *, drive: bool = True) -> RequestResult:
        """The structured result; with ``drive`` (default) steps the engine
        until this request finishes."""
        while drive and not self.done():
            if not self._engine.step() and not self.done():
                raise EngineStalledError(
                    f"engine stalled with request {self.rid} still {self._req.state}",
                    self._engine._flight_state(),
                )
        if not self.done():
            raise RuntimeError(f"request {self.rid} is still {self._req.state}")
        return self._engine._result(self._req)


# jitted bucket programs, shared across engines with identical static
# configuration (the _generate_cache idiom): an engine restart — or a test
# suite full of small engines — reuses steady-state compiled programs
_program_cache: dict = {}


def hybrid_unsupported(cfg, *, prefix_sharing=None, sessions=None, speculative=None, lora=None,
                       mesh=None, kv_dtype=None, prefill_chunk=None,
                       priorities=None, fault_plan=None) -> str | None:
    """Why an engine with these options cannot serve a config that keeps a
    state a request beside its KV (a delta rule's recurrent state and conv
    tail, a short convolution's tail alone, a selective scan's state and
    tail, or a Mamba-2 scan's matrix state a head and tail), or None.  Each is a mechanism that is not built, not a shortcut that
    was skipped (ROADMAP Queue 2).  A window of a layer kind
    (``cfg.layer_window``) passes: its K/V live in the slot's ring; the
    model-wide ``sliding_window`` beside a state is still refused.  An ordinary
    decoder with sliding_attention layers (``cfg.ring_layers`` and no state)
    leases the slot for its rings alone and is refused the same options, each
    by what the ring lacks.  A
    decoder-hybrid-decoder (``cfg.hybrid_decoder``) has its paged decode
    program and whole-prompt prefills alone, and refuses what needs another;
    so does a model with mamba2 layers, whose paged forward steps one token a row.
    A looped model (``cfg.n_pass`` > 1) keeps no state but a K/V slab a layer a
    pass, the slab a traced operand of the loop's one body: what takes the layer
    as a constant, or was not carried through the loop, is refused here too."""
    if getattr(cfg, "n_pass", 1) > 1:
        if speculative is not None:
            return ("speculative= is unsupported: a draft's verify attends several tokens a row through a kernel "
                    "whose index maps take the layer as a constant, and a looped model's slab is traced")
        if lora is not None:
            return ("lora= is unsupported: the adapter arenas hold a delta a layer, and whether a pass shares "
                    "its layer's adapter with the other passes is not carried through the loop")
        if mesh is not None:
            return "mesh= is unsupported: the loop's traced slab is not carried into the walk's shard_map under a tp axis"
        if sessions is not None and sessions is not False:
            return ("sessions= is unsupported: a parked session's re-attach has not been carried through the loop "
                    "(its prefix holds a slab a pass)")
        return None
    if getattr(cfg, "ring_layers", ()) and not getattr(cfg, "state_layers", ()):
        # an ordinary decoder whose window layers keep a ring a request and nothing else
        if kv_dtype is not None:
            return ("kv_dtype= (an int8 or fp8 arena) is unsupported: the ring arenas have no scale rows (a slot's "
                    "ring is written and walked at the model's dtype; only the paged arenas have a quantised form)")
        if prefill_chunk is not None:
            return ("prefill_chunk= is unsupported: a piece of a prompt past position 0 has no program over a ring "
                    "(it would read the ring's older blocks while it overwrites them)")
        if priorities is not None:
            return "priorities= is unsupported: a preempted request resumes through the chunk programs, which are not built"
        if fault_plan is not None:
            return "fault_plan= is unsupported: re-prefill recovery replays through the chunk programs, which are not built"
        if prefix_sharing:
            return ("prefix_sharing=True is unsupported: a prefix's blocks of the full_attention layers can be shared, "
                    "the ring its window layers held at the prefix's end cannot (a ring keeps its owner's last window)")
        if sessions is not None and sessions is not False:
            return ("sessions= is unsupported: a parked session re-attaches through the shared-prefix path, and its "
                    "ring went back with its slot")
        if speculative is not None:
            return ("speculative= is unsupported: a draft's verify attends several tokens a row, and the ring is "
                    "walked one token a row; a rejected draft's tokens would have overwritten the ring's oldest block")
        if lora is not None and getattr(cfg, "attn_output_gate", False):
            return ("lora= is unsupported: the adapter arena's wq target is n_head * head_size wide, and a gated "
                    "layer's wq projects a head's query and its gate in one product of twice that")
        if mesh is not None:
            return "mesh= is unsupported: the ring arenas have no layout under a tp axis"
        return None
    if getattr(cfg, "hybrid_decoder", False):
        if kv_dtype is not None:
            return ("kv_dtype= (an int8 or fp8 arena) is unsupported: the ring arenas and the differential walk "
                    "(two K heads a 128-lane row, one scale a head) have no quantised form")
        if prefill_chunk is not None:
            return ("prefill_chunk= is unsupported: a piece of a prompt past position 0 has no program over "
                    "per-kind caches (the scan from a slot's state, the ring's older blocks)")
        if priorities is not None:
            return "priorities= is unsupported: a preempted request resumes through the chunk programs, which are not built"
        if fault_plan is not None:
            return "fault_plan= is unsupported: re-prefill recovery replays through the chunk programs, which are not built"
    if getattr(cfg, "mamba2_layers", ()):
        if prefill_chunk is not None:
            return ("prefill_chunk= is unsupported: a piece of a prompt past position 0 has no program for a Mamba-2 "
                    "layer (the chunked scan starts from a given state, but the paged program steps one token a row)")
        if priorities is not None:
            return "priorities= is unsupported: a preempted request resumes through the chunk programs, which are not built"
        if fault_plan is not None:
            return "fault_plan= is unsupported: re-prefill recovery replays through the chunk programs, which are not built"
    if prefix_sharing:
        return ("prefix_sharing=True is unsupported: a prefix's KV blocks can be shared, its "
                "recurrent state or conv tail cannot (no snapshot of the state at a block edge is kept)")
    if sessions is not None and sessions is not False:
        return ("sessions= is unsupported: a parked session re-attaches through the shared-prefix "
                "path, which has no state snapshot to resume from")
    if speculative is not None:
        return ("speculative= is unsupported: a rejected draft's tokens have already advanced the "
                "state, and there is no rollback")
    if lora is not None:
        return ("lora= is unsupported: the adapter arenas are a model layer's attention and MLP "
                "targets; the mixer's projections (in_proj_qkvz, in_proj, out_proj) have none")
    if mesh is not None:
        return "mesh= is unsupported: the state arena has no layout under a tp axis"
    if cfg.sliding_window is not None:
        return ("a sliding window is unsupported beside a recurrent state or conv tail "
                "(block expiry is untested with it)")
    return None

@scope("head/exit")
def _exit_rows(chosen, p):
    """A looped model's exit rule a row as one array ``(B, 1 + n_pass)`` float32: the pass
    chosen, then the exit probabilities (``generate.exit_rule``'s ``(B,)`` and ``(n_pass, B)``)."""
    return jnp.concatenate([chosen[:, None].astype(jnp.float32), p.T], axis=1)


def latent_unsupported(cfg, *, kv_dtype=None, cache_dtype=None, speculative=None, lora=None, mesh=None) -> str | None:
    """Why an engine with these options cannot serve a latent-attention config
    (one latent a token a layer in place of K and V), or None.  Each is a
    mechanism that is not built (ROADMAP Queue 2)."""
    from thunder_tpu.serving.quant import is_quantized_kv, resolve_kv_dtype

    dtype = cache_dtype if cache_dtype is not None else jnp.bfloat16
    if kv_dtype is not None and is_quantized_kv(resolve_kv_dtype(kv_dtype, dtype), dtype):
        return ("kv_dtype= (an int8 or fp8 arena) is unsupported: the latent decode kernel has no dequant, "
                "and a latent is read by every head, so its scale is not a head's")
    if mesh is not None:
        return ("mesh= is unsupported: the latent arena has one row for all heads, so no heads axis shards "
                "over tp, and the decode kernel has no partitioning rule")
    if speculative is not None:
        return ("speculative= is unsupported: the verify step attends several draft tokens a row, and the "
                "latent decode kernel takes one")
    if lora is not None:
        return ("lora= is unsupported: the adapter arenas target wq/wk/wv/wo; the latent projections "
                "(wq_a, wq_b, wkv_a, wkv_b) have none")
    return None


# one decode program's collective census per (mesh, static config, bucket):
# the census pays an extra AOT compile, so it is module-cached like programs
_collectives_cache: dict = {}


class ServingEngine:
    """Continuous-batching inference engine over a paged KV pool."""

    def __init__(
        self,
        params,
        cfg,
        *,
        block_size: int = 16,
        num_blocks: int = 64,
        max_batch: int = 8,
        max_queue: int = 64,
        temperature: float = 0.0,
        eos_id: int | None = None,
        quantized: bool = False,
        cache_dtype=None,
        kv_dtype=None,
        lora=None,
        prefix_sharing: bool | None = None,
        clock: Callable[[], float] | None = None,
        telemetry=None,
        batch_buckets: Sequence[int] | None = None,
        block_buckets: Sequence[int] | None = None,
        prefill_buckets: Sequence[int] | None = None,
        trace: bool | None = None,
        slo=None,
        flight_recorder=None,
        mesh=None,
        shardings=None,
        async_step: bool = True,
        prefill_chunk: int | None = None,
        fault_plan=None,
        retry: RetryPolicy | None = None,
        watchdog_timeout_s: float | None = None,
        speculative=None,
        replica_id: int | None = None,
        sessions=None,
        priorities=None,
        constraints=None,
        goodput=None,
    ):
        from thunder_tpu.models.generate import require_servable

        require_servable(cfg)
        # a model with linear_attention layers keeps a recurrent state a
        # request beside its KV, one with conv layers a conv tail; what such a
        # state cannot serve yet refuses here, with its reason
        self._hybrid = bool(getattr(cfg, "keeps_slot", False))
        self._passes = getattr(cfg, "n_pass", 1)
        if self._hybrid or self._passes > 1:
            why = hybrid_unsupported(
                cfg, prefix_sharing=prefix_sharing, sessions=sessions, speculative=speculative,
                lora=lora, mesh=mesh, kv_dtype=kv_dtype,
                prefill_chunk=prefill_chunk, priorities=priorities, fault_plan=fault_plan)
            if why:
                kind = (f"a stack run n_pass = {cfg.n_pass} times (a K/V slab a layer a pass)" if self._passes > 1
                        else "linear_attention layers (a recurrent state a request)" if cfg.linear_layers
                        else "conv layers (a conv tail a request)" if cfg.conv_layers
                        else "mamba2 layers (a Mamba-2 scan's matrix state a head a request)" if cfg.mamba2_layers
                        else "ssm layers (a selective scan's state a request) beside per-kind K/V" if cfg.ssm_layers
                        else "sliding_attention layers (a ring of layer_window tokens a request)")
                raise NotImplementedError(f"config {getattr(cfg, 'name', '?')!r} has {kind}: {why}")
            if self._hybrid:
                prefix_sharing = False
        # per-kind caches (a ring a slot for window layers, one layer's blocks read
        # by the cross layers): the paged decode program and whole prompts alone
        self._perkind = bool(getattr(cfg, "hybrid_decoder", False) or getattr(cfg, "ring_layers", ()))
        self._latent = bool(getattr(cfg, "latent", False))
        if self._latent:
            why = latent_unsupported(cfg, kv_dtype=kv_dtype, cache_dtype=cache_dtype, speculative=speculative,
                                     lora=lora, mesh=mesh)
            if why:
                raise NotImplementedError(
                    f"config {getattr(cfg, 'name', '?')!r} has latent attention (one latent a token a "
                    f"layer in place of K and V): {why}")
        if prefix_sharing is None:
            prefix_sharing = True
        if shardings is not None and mesh is None:
            raise ValueError("shardings= requires mesh= (param placement needs a mesh)")
        from thunder_tpu.core import compile_cache

        compile_cache.ensure_enabled()  # an engine warms tens of bucket programs
        self.async_step = bool(async_step)
        if prefill_chunk is not None and not self.async_step:
            raise ValueError(
                "prefill_chunk= requires async_step=True — the chunked "
                "prefill lane lives in the async event loop"
            )
        self.mesh = mesh
        if mesh is not None:
            # SPMD serving: place params once (tp_fsdp-style rules unless
            # the caller brings their own), shard the KV arenas heads-over-
            # tp, and compile every bucket program with explicit shardings
            from thunder_tpu.serving.mesh import mesh_fingerprint, place_params

            params = place_params(params, mesh, shardings)
            # the param placement is baked into every program's
            # in_shardings, so it is part of the program identity too
            self._mesh_key = (
                mesh_fingerprint(mesh),
                tuple(str(x.sharding.spec) for x in jax.tree_util.tree_leaves(params)),
            )
        else:
            self._mesh_key = None
        self._mesh_collectives: dict | None = None         # lazy decode census
        self.params = params
        self.cfg = cfg
        self.temperature = float(temperature)
        self.eos_id = eos_id
        self.quantized = bool(quantized)
        self.prefix_sharing = bool(prefix_sharing)
        dtype = cache_dtype if cache_dtype is not None else params["wte"].dtype
        self.pool = PagedKVPool(
            cfg, num_blocks=num_blocks, block_size=block_size, dtype=dtype,
            kv_dtype=kv_dtype, mesh=mesh,
            # a state slot a batch slot: a request that has one never waits for the other
            **({"state_slots": max_batch} if self._hybrid else {}),
            # a draft's verify attends several tokens a row through the per-block
            # kernel, which reads a head a row: speculation keeps that layout
            **({"lane_pack": 1} if speculative is not None else {}),
        )
        # the form the decode program's attention call takes here, and the keys
        # a step of its walk attends (a group), from the arena shard the kernel
        # is handed: the kernel's entry decides (pallasex.paged_decode_path), this
        # is what it will say.  "xla" counts every decode step in fallback_steps
        from thunder_tpu.executors.pallasex import _MLA_CHUNK_KEYS, paged_kv_chunk_blocks
        from thunder_tpu.serving.paged_attention import decode_path

        self.attn_fallback_steps = 0
        # a model with window layers: the keys a decode step's rows attend, a layer of each kind
        self._attended = ({"steps": 0, "full_attention": 0, "sliding_attention": 0}
                          if getattr(cfg, "ring_layers", ()) else None)
        # a looped model: the served tokens by the pass the exit rule chose and their exit
        # probabilities summed (both harvested with the tokens), and the keys its decode steps'
        # rows attended, a layer of a pass and once a slab-walk (stats()["passes"], ["attn"])
        self._pass_sums = None
        if self._passes > 1:
            self._pass_sums = {"exit": [0] * self._passes, "exit_mass": np.zeros(self._passes, np.float64)}
            self._attended = {"steps": 0, "full_attention": 0}
        arena = self.pool.k_arena
        _, _, ng, bs, lanes = arena.sharding.shard_shape(arena.shape)
        self._attn_path = decode_path(cfg, mesh, arena_lanes=lanes)     # a latent arena: mla_paged_decode's walk
        self._kv_chunk_tokens = (_MLA_CHUNK_KEYS if self._latent
                                 else bs * paged_kv_chunk_blocks(ng, bs, lanes, arena.dtype.itemsize))
        # multi-tenant LoRA: a bounded AdapterRegistry shared across engines;
        # its stacked factor arenas are program *arguments* (register/evict
        # are data writes), only its geometry enters the program identity
        self._registry = lora
        if lora is not None:
            for dim in ("n_layer", "n_head", "n_query_groups", "head_size", "n_embd"):
                if getattr(lora.cfg, dim) != getattr(cfg, dim):
                    raise ValueError(
                        f"lora registry was built for {dim}="
                        f"{getattr(lora.cfg, dim)} but the engine serves "
                        f"{dim}={getattr(cfg, dim)}"
                    )
            if mesh is not None:
                lora.place(mesh)   # placed once per mesh, like params
        # speculative serving: a draft KV block arena BESIDE the target
        # arena — its own PagedKVPool storage (same dtype/quantization/mesh
        # sharding), but block ids are allocated once per request from the
        # target pool and index both arenas (the draft pool's free list is
        # never consulted), so the allocator/prefix machinery stays single
        self.spec = speculative
        if speculative is not None:
            from thunder_tpu.serving.speculative import validate_spec

            validate_spec(speculative, cfg, sliding_window=cfg.sliding_window)
            if mesh is not None:
                from thunder_tpu.serving.mesh import place_params as _pp

                speculative.draft_params = _pp(speculative.draft_params, mesh, None)
            self.draft_pool = PagedKVPool(
                speculative.draft_cfg, num_blocks=num_blocks,
                block_size=block_size, dtype=dtype, lane_pack=1,
                # the draft arena may quantize independently of the target
                # (SpecConfig.draft_kv_dtype; None inherits kv_dtype)
                kv_dtype=(speculative.draft_kv_dtype
                          if speculative.draft_kv_dtype is not None else kv_dtype),
                mesh=mesh,
            )
        else:
            self.draft_pool = None
        self.scheduler = Scheduler(
            self.pool,
            max_batch=max_batch,
            max_queue=max_queue,
            clock=clock,
            batch_buckets=batch_buckets,
            block_buckets=block_buckets,
            prefill_buckets=prefill_buckets,
            sliding_window=cfg.sliding_window,
            prefill_chunk=prefill_chunk,
            # a speculative round's draft scan writes up to K slots past the
            # last committed token — admission must reserve that overshoot
            reserve_extra_tokens=speculative.K if speculative is not None else 0,
        )
        if getattr(cfg, "learned_pos_embedding", False):
            # wpe has block_size rows and dynamic_slice clamps silently past
            # them: cap the bucket sets so no program's dense capacity can
            # reach beyond the learned table
            sch = self.scheduler
            blk = tuple(
                b for b in sch.block_buckets
                if self.pool.capacity_tokens(b) <= cfg.block_size
            )
            assert blk, (
                f"block_size(cfg)={cfg.block_size} admits no pool bucket at "
                f"pool block_size={block_size} with learned position embeddings"
            )
            sch.block_buckets = blk
            sch.prefill_buckets = tuple(
                t for t in sch.prefill_buckets if t <= cfg.block_size
            ) or (cfg.block_size,)
            # a block-aligned resume point near block_size would push the
            # padded prefill window past the wpe table (dynamic_slice clamps
            # the start — real tokens would read shifted embeddings), so
            # suffix prefill is off the table for learned-pos models; that
            # rules out chunked prefill too (every chunk past the first is a
            # suffix resume)
            self.prefix_sharing = False
            sch.prefill_chunk = None
        self._table_widths = self._table_width_buckets()
        # a piece of a prompt has two programs, chosen once here from shapes
        # alone: the paged chunk writer lands whole (L, ng, bs, hs) block slabs
        # built from the chunk's fresh K/V alone, so every chunk boundary must
        # fall on a block edge — the chunk width and every prefill bucket must be
        # multiples of the pool block size (the FINAL piece runs the ``prefill``
        # kind and may stay ragged).  What the multi-query kernel has no form for
        # keeps the gather chunk: a sliding window, a latent cache, lane-packed
        # rows; and speculative engines keep ``spec_prefill_chunk`` (it writes
        # the draft arena too).  One kind an engine, so the bucket_bound formula
        # in stats() is untouched.
        sch = self.scheduler
        if self.spec is not None:
            chunk_why = "speculative prefill writes the draft arena (gather chunk)"
        elif cfg.sliding_window is not None:
            chunk_why = "sliding-window keep-mask is decode-only"
        elif self._latent:
            chunk_why = "a latent cache's piece attends its expanded keys (the dense form)"
        elif self._passes > 1:
            chunk_why = "a looped model's slab is a traced operand: a piece attends its gathered keys (the dense form)"
        elif self.pool.lane_pack > 1:
            chunk_why = (f"a lane-packed arena ({self.pool.lane_pack} KV heads a row) has no multi-query "
                         "kernel: a chunk attends its gathered keys")
        elif sch.prefill_chunk is not None and sch.prefill_chunk % block_size:
            chunk_why = (f"prefill_chunk={sch.prefill_chunk} not a multiple "
                         f"of block_size={block_size}")
        elif any(t % block_size for t in sch.prefill_buckets):
            chunk_why = (f"prefill_buckets={tuple(sch.prefill_buckets)} not "
                         f"all multiples of block_size={block_size}")
        else:
            chunk_why = None
        self.attn_chunk = "paged" if chunk_why is None else "gather"
        self._attn_chunk_why = chunk_why
        # fault tolerance: the chaos plan (None = unarmed — one `is None`
        # check per fault point, compiled programs byte-identical either
        # way), the retry/backoff policy, and the harvest watchdog on the
        # scheduler's (injectable) clock
        self._faults = resolve_fault_plan(fault_plan)
        self._retry = retry if retry is not None else RetryPolicy()
        self.watchdog_timeout_s = watchdog_timeout_s
        self._retry_streak = 0                             # consecutive transient faults
        self.recoveries = 0
        # telemetry: a StepLogger, a path for one, or None
        self._owns_telemetry = isinstance(telemetry, (str, bytes)) or hasattr(telemetry, "__fspath__")
        if self._owns_telemetry:
            from thunder_tpu.observability.telemetry import StepLogger

            telemetry = StepLogger(telemetry, meta={
                "kind": "serving", "block_size": block_size, "num_blocks": num_blocks,
                "max_batch": max_batch, "model": getattr(cfg, "name", "?"),
            })
        self.telemetry = telemetry
        self._handles: dict[int, RequestHandle] = {}
        # dp replication: which engine lane this is (None = solo); the
        # router stamps it into stats/flight/spans so every artifact of a
        # replicated fleet names its lane
        self.replica_id = replica_id
        self._prefix_index = PrefixIndex(self.pool.block_size)
        # stateful serving: resident-session table (parked prefix blocks),
        # priority gate (admission policy + preemption), and the
        # constrained-decoding knob.  All three are host policy/data —
        # only `constraints` touches program identity (one extra mask
        # argument), and it collapses to None on the off-path so default
        # engines share cached programs byte-identically.
        from thunder_tpu.serving.priority import resolve_priorities
        from thunder_tpu.serving.sessions import resolve_sessions

        self._sessions = resolve_sessions(sessions, self.pool, self._prefix_index)
        if self._sessions is not None and not self.prefix_sharing:
            raise ValueError(
                "sessions= requires prefix_sharing: session re-attach rides "
                "the shared-prefix admission path")
        self._priorities = resolve_priorities(priorities)
        self._constraints = bool(constraints)
        if self._constraints and speculative is not None:
            raise ValueError(
                "constraints= with speculative= is unsupported: the verify "
                "lane has no mask argument (use the plain decode lane)")
        # logit width every constraint mask must match (lm_head output)
        self._vocab = int(getattr(cfg, "padded_vocab_size", None)
                          or getattr(cfg, "vocab_size"))
        self._mask_ones: dict[tuple, np.ndarray] = {}
        self._hit_owner: int | None = None  # owner rid of the last live prefix hit
        self.preempted = 0
        self._programs: dict[tuple, Callable] = {}
        self._closed = False
        # drive-loop accounting (mirrored into the registry as it changes)
        self.decode_steps = 0
        self.decode_ahead_steps = 0     # of them, dispatched before the harvest of the step before
        self.decode_through_end_steps = 0   # of those, one step past a row's end by length
        self.prefill_runs = 0
        self.prefill_fresh_runs = 0     # of them, whole prompts at position 0
        self.chunk_runs = 0
        self.step_calls = 0
        self.tokens_generated = 0
        self._occupancy_sum = 0
        self.compile_counts = {"prefill": 0, "prefill_fresh": 0, "prefill_chunk": 0,
                               "prefill_chunk_paged": 0, "decode_paged": 0,
                               "spec_prefill": 0, "spec_prefill_chunk": 0,
                               "draft_decode": 0, "verify_paged": 0}
        # one host_visit per decode-lane harvest
        self.host_visits = 0
        self.decode_lane_tokens = 0
        # async lanes: the in-flight futures table — one deferred decode
        # record plus any deferred prefill-piece records, harvested at the
        # top of the next step (the only place the host blocks)
        self._inflight_decode: dict | None = None
        self._inflight_prefill: list[dict] = []
        self._stall_s_sum = 0.0
        self._overlap_frac_sum = 0.0
        self._overlap_obs = 0
        # chained decode inputs: while the batch and tables are unchanged,
        # each decode step consumes the previous step's device outputs
        # directly (no host->device transfer); see _decode_dispatch
        self._decode_state: dict | None = None
        # the host side of the last built decode inputs: its arrays and what
        # each row was built from.  It outlives the harvest that drops the
        # chain, and the next rebuild carries its standing rows over by
        # request (_decode_inputs); recovery drops it
        self._decode_host: dict | None = None
        self._rebuilds = {"rebuilds": 0, "full": 0, "rows_written": 0, "rows_carried": 0}
        # a SparseMoE model's decode steps sum how their rows fell on the held
        # experts, on the device: float32 [steps, rows, rows^2, hit share] (stats()["moe"])
        # (not under a mesh: the program's shardings there are a fixed list)
        self._moe_rows = (jax.device_put(np.zeros((4,), np.float32))        # a transfer: no program is built for it
                          if cfg.mlp_class == "SparseMoE" and mesh is None else None)
        # the speculative lane's chained round inputs (toks=y, pos+n_emit)
        # plus its acceptance accounting; see serving.speculative
        self._spec_state: dict | None = None
        self.spec_rounds = 0
        self.spec_draft_tokens = 0
        self.spec_accepted_tokens = 0
        self._spec_accept_hist = (
            np.zeros(speculative.K + 1, dtype=np.int64)
            if speculative is not None else None
        )
        # per-step metric handles resolved once (registry().reset() zeroes
        # values but keeps objects, so these survive observability resets)
        reg0 = registry()
        self._m_steps_decode = reg0.counter("serving.steps.decode")
        self._m_steps_ahead = reg0.counter("serving.steps.decode_ahead")
        self._m_occupancy = reg0.histogram("serving.batch_occupancy")
        self._m_tokens = reg0.counter("serving.tokens")
        self._m_queue_depth = reg0.gauge("serving.queue_depth")
        self._m_running = reg0.gauge("serving.running")
        self._m_pool_util = reg0.gauge("serving.pool.utilization")
        self._m_pool_free = reg0.gauge("serving.pool.free_blocks")
        self._m_pool_low_water = reg0.gauge("serving.pool.free_blocks_low_water")
        self._m_attn_fallback = reg0.counter("serving.attn.fallback_steps")
        self._m_host_visits = reg0.counter("serving.decode.host_visits")
        self._m_pool_occ = reg0.gauge("serving.pool.occupancy_frac")
        if self._hybrid:
            st = self.pool.state
            reg0.gauge("serving.state.slots").set(st.num_slots)
            reg0.gauge("serving.state.arena_bytes").set(st.arena_bytes())
            reg0.gauge("serving.state.arena_laid_out_bytes").set(st.laid_out_bytes())
            self._m_state_leased = reg0.gauge("serving.state.leased")
            self._m_state_low_water = reg0.gauge("serving.state.free_slots_low_water")
        if speculative is not None:
            self._m_spec_rounds = reg0.counter("serving.spec.rounds")
            self._m_spec_accepted = reg0.counter("serving.spec.accepted_tokens")
            self._m_spec_accept_len = reg0.histogram("serving.spec.accept_len")
        if self.async_step:
            self._m_stall = reg0.histogram("serving.decode.stall_s")
            self._m_overlap = reg0.gauge("serving.step.overlap_frac")
        self._compile_log: list[dict] = []               # per-bucket compile causes
        # serving-plane observability (all off by default; the off path is
        # one `is None` check per touch point)
        if trace is None:
            trace = serving_trace_env_enabled()
        self._tracer = RequestTracer() if trace else None
        self._slo = resolve_slo(slo)
        # goodput ledger (ISSUE 18): host-side classification of every
        # dispatched device token-position; never enters _static_key, so
        # goodput=True compiles zero additional programs
        self._goodput = resolve_goodput(goodput)
        if flight_recorder is None:
            flight_recorder = flight_recorder_env_enabled()
        if isinstance(flight_recorder, FlightRecorder):
            flight_recorder.state_provider = self._flight_state
            self._flight = flight_recorder
        else:
            self._flight = (
                FlightRecorder(state_provider=self._flight_state)
                if flight_recorder else None
            )
        if mesh is not None:
            # serving.mesh.* gauges: static facts land at construction; the
            # decode collective count follows once a decode program exists
            reg = registry()
            reg.gauge("serving.mesh.devices").set(int(mesh.devices.size))
            for a in mesh.axis_names:
                reg.gauge(f"serving.mesh.axis.{a}").set(int(mesh.shape[a]))
            reg.gauge("serving.mesh.arena_shard_bytes").set(self.pool.per_shard_bytes())

    #
    # public API
    #

    def submit(
        self,
        prompt,
        *,
        max_new_tokens: int,
        deadline: float | None = None,
        key=None,
        stream_cb: Callable[[int], Any] | None = None,
        adapter_id: str | None = None,
        session_id: str | None = None,
        priority: str | None = None,
        constraint=None,
    ) -> RequestHandle:
        """Enqueues one request; returns immediately with a handle.

        ``deadline`` is seconds from now; past it the request finishes with
        reason ``"deadline"`` wherever it is.  ``key`` seeds the request's
        private sampling chain (default ``PRNGKey(0)``, like ``generate``).
        ``stream_cb`` receives each generated token id, in order, as soon as
        the host sees it.  ``adapter_id`` routes the request through a LoRA
        adapter registered in the engine's ``lora=`` registry (resolved to
        its slot here, at admission time — an unknown id raises KeyError
        immediately, never a silent base fallback).  Raises
        :class:`AdmissionError` when the wait queue is full or the request
        can never fit the pool.

        ``session_id`` (needs ``sessions=``) parks the finished turn's
        prefix blocks so the next turn re-attaches them; ``priority``
        (``"high"``/``"normal"``/``"low"``, needs ``priorities=``) orders
        the queue, feeds the SLO admission gate, and marks preemption
        victims; ``constraint`` (a :class:`serving.constrain.Constraint`,
        needs ``constraints=True``) masks every sampled token through the
        request's host-side automaton."""
        if self._closed:
            raise RuntimeError("engine is shut down")
        if key is None:
            key = jax.random.PRNGKey(0)
        adapter_slot = 0
        if adapter_id is not None:
            if self._registry is None:
                raise ValueError(
                    f"adapter_id={adapter_id!r} requires an engine built with "
                    f"lora=AdapterRegistry(...)"
                )
            adapter_slot = self._registry.slot(adapter_id)
        if session_id is not None and self._sessions is None:
            raise ValueError(
                f"session_id={session_id!r} requires an engine built with "
                f"sessions= (e.g. sessions=True)")
        from thunder_tpu.serving.priority import priority_level

        if priority is not None and self._priorities is None:
            raise ValueError(
                f"priority={priority!r} requires an engine built with "
                f"priorities= (e.g. priorities=True)")
        priority_cls, level = priority_level(priority)
        if constraint is not None:
            if not self._constraints:
                raise ValueError(
                    "constraint= requires an engine built with constraints=True")
            if int(constraint.vocab_size) != self._vocab:
                raise ValueError(
                    f"constraint.vocab_size={constraint.vocab_size} != model "
                    f"logit width {self._vocab}")
        reg = registry()
        try:
            req = self.scheduler.submit(
                prompt, max_new_tokens, key=key, deadline_s=deadline, stream_cb=stream_cb,
                adapter_id=adapter_id, adapter_slot=adapter_slot,
                session_id=session_id, priority=level,
                priority_class=priority_cls, constraint=constraint,
            )
        except AdmissionError:
            reg.counter("serving.requests.rejected").inc()
            raise
        reg.counter("serving.requests.submitted").inc()
        reg.gauge("serving.queue_depth").set(len(self.scheduler.queue))
        if self._tracer is not None:
            self._tracer.register_request(req.rid)
            self._tracer.begin(req.rid, "queued",
                               prompt_tokens=req.prompt_len,
                               max_new_tokens=req.max_new_tokens)
        if self._flight is not None:
            self._flight.record("submit", rid=req.rid,
                                prompt_tokens=req.prompt_len,
                                max_new_tokens=req.max_new_tokens,
                                queue_depth=len(self.scheduler.queue))
        handle = RequestHandle(self, req)
        self._handles[req.rid] = handle
        return handle

    def step(self) -> bool:
        """One event-loop iteration.  Async (default): harvest the in-flight
        decode/prefill futures from step *k−1* (the one host block — the
        idle backoff of every drive loop is this wait on the futures table,
        never a busy poll), expire deadlines, dispatch decode for batch *k*,
        then admit + dispatch prefill pieces while the device computes.  In
        a steady batch the decode dispatch comes first, ahead of the harvest
        (:meth:`_step_async` has the overlap contract).
        Sync (``async_step=False``): the original expire → admit+prefill →
        one blocking decode.  Returns whether any work happened.  When a
        flight recorder is armed, any exception out of the step auto-dumps
        the flight record before propagating.  The step and its phases are
        spans (:meth:`_span`): ``serve.step`` and its children."""
        if self._closed:
            raise RuntimeError("engine is shut down")
        self.step_calls += 1
        # t_ns: the ring's clock at entry, on the profiler's timeline
        with self._span("serve.step", step=self.step_calls,
                        queued=len(self.scheduler.queue),
                        running=len(self.scheduler.running),
                        t_ns=time.perf_counter_ns()):
            try:
                worked = self._step_async() if self.async_step else self._step_inner()
                self._retry_streak = 0                     # a clean step resets the budget
            except Exception as e:
                # blast-radius containment: classified faults are absorbed —
                # quarantine / retry / recover — and the loop keeps serving;
                # anything unclassified keeps the crash-dump-and-raise contract
                try:
                    handled = self._absorb_fault(e)
                except Exception as e2:
                    if self._flight is not None:
                        self._flight.crash_dump(e2)
                    raise
                if not handled:
                    if self._flight is not None:
                        self._flight.crash_dump(e)
                    raise
                worked = True
        return worked

    def _span(self, name: str, *, rare: bool = False, **meta) -> span:
        """A span of the step loop (``observability.events.span``): always an annotation in a ``jax.profiler``
        trace; in the event ring, on the tracer's engine track, under ``trace=True``.  A ``rare`` one (once a
        program or a fault, never in a steady step) writes the ring always: the process's own track then."""
        tr = self._tracer
        if tr is None:
            return span(name, ring=rare, **meta)
        return span(name, track=tr.engine_track, **meta)

    def _compile_span(self, compiled: bool, kind: str, a: int, b: int):
        """``serve.compile`` around the first call of a program
        :meth:`_program` built fresh: the call that traces it and compiles
        or loads it.  Nothing around any later call."""
        if not compiled:
            return contextlib.nullcontext()
        return self._span("serve.compile", rare=True, kind=kind, bucket=f"{a}x{b}")

    def _expire_deadlines(self) -> bool:
        with self._span("serve.expire"):
            expired = self.scheduler.deadline_expired()
            for req in expired:
                self._finish(req, FINISH_DEADLINE)
        return bool(expired)

    def _admit(self) -> bool:
        with self._span("serve.admit") as sp:
            admitted = 0
            while self._try_admit():
                admitted += 1
            sp.set(admitted=admitted)
        return admitted > 0

    def _step_inner(self) -> bool:
        """The synchronous scheduler iteration (``async_step=False``):
        byte-identical to the pre-async engine."""
        worked = self._expire_deadlines()
        worked = self._admit() or worked
        for r in list(self.scheduler.running):
            if not r.generated and r.state == "running":
                # a request stranded without token 0 (its admission prefill
                # was absorbed as a fault, or recovery reset it): re-prefill
                # before the decode batch consumes generated[-1]
                self._harvest_inline(self._prefill_dispatch(r))
                worked = True
        if self.scheduler.running:
            self._decode_once()
            worked = True
        with self._span("serve.gauges"):
            self._update_gauges()
        return worked

    def _step_async(self) -> bool:
        """One event-loop turn.  Phase order is the overlap contract:

        0. **decode dispatch ahead** — where the host can tell without the
           in-flight step's tokens that the next step runs on the same batch
           (:meth:`_ahead_batch`), it is dispatched first, from the chained
           device state: the device goes from step *k* straight into *k+1*
           while the host does phases 1-2 for step *k*, and phase 3 is
           skipped.  That holds through a row's end by length: where *k* is
           some row's last step and another row outlives it, *k+1* goes out
           on the chain's rows with the ended row as one dead row-step, and
           phases 1, 2 and 4 (the row's finish, its successor's admission
           and prefill) run under it; the harvest drops the chain, so the
           next turn is the one turnover a finished row costs.  Never a step
           for nobody, never two steps past an end, never a token for the
           dead row.  Any other step (a turnover) keeps the order below;
        1. **harvest** — materialize the previous step's in-flight decode
           tokens and prefill pieces (stream callbacks, finishes, window
           expiry land here, one device-latency late but in order);
        2. expire deadlines (a request finished here is skipped by any
           in-flight record that still names it);
        3. **decode dispatch** for the decode-ready batch — the device
           starts on step *k* while the host continues.  Where the batch or
           a table changed (a turnover) the dispatch rebuilds the chain's
           inputs: the rows that stand are carried over from the build
           before by request and only the changed ones are written from
           Python (:meth:`_decode_inputs`); the call takes the host arrays
           as they are, and what the chain's later steps reuse of them goes
           to the device in one transfer after it, under the step (the
           deadline check of phase 2 looks at no request without one);
        4. admissions + chunked-prefill advancement — all host/dispatch
           work that overlaps the device's decode.

        Either way one decode record is in flight between two steps.
        """
        batch = self._ahead_batch()
        prev = None
        if batch is not None:
            # _inflight_decode stays step k's record until the dispatch has
            # gone through: a fault inside it loses neither
            prev = self._inflight_decode
            self._decode_once(batch)
        worked = self._harvest(prev)
        worked = self._expire_deadlines() or worked
        if prev is None and self.scheduler.decode_ready():
            self._decode_once()
            worked = True
        worked = self._admit() or worked
        if self._advance_prefills():
            worked = True
        with self._span("serve.gauges"):
            self._update_gauges()
        return worked

    def _harvest(self, prev: dict | None = None) -> bool:
        """Materializes every in-flight future (decode first: it was
        dispatched before the prefill pieces, so the device finishes it
        first).  This is where the host blocks — drive loops calling
        ``step()`` back off *inside* this wait instead of busy-polling.
        ``prev``: the decode record to harvest where the next one was
        dispatched ahead of it; that one stays in flight."""
        with self._span("serve.harvest"):
            return self._harvest_inflight(prev)

    def _harvest_inflight(self, prev: dict | None = None) -> bool:
        wd = self.watchdog_timeout_s
        if wd is not None:
            # the watchdog: an in-flight record that aged past the timeout
            # on the engine clock without being harvested is a hung step —
            # convert the silent stall into the recovery path
            now = self.scheduler.clock()
            inflight = list(self._inflight_prefill)
            if self._inflight_decode is not None:
                inflight.append(prev or self._inflight_decode)
            for wrec in inflight:
                age = now - wrec["t_clock"]
                if age > wd:
                    rids = ([r.rid for r in wrec["running"]]
                            if wrec["kind"] == "decode" else [wrec["req"].rid])
                    raise WatchdogTimeout(FP_HARVEST, rids, age_s=age)
        worked = False
        parked = None
        if prev is not None:
            rec, parked = prev, self._inflight_decode["parked"]
        else:
            rec, self._inflight_decode = self._inflight_decode, None
        if rec is not None:
            self._decode_harvest(rec)
            worked = True
        pending, self._inflight_prefill = self._inflight_prefill, []
        for prec in pending:
            self._prefill_harvest(prec)
            worked = True
        if worked:
            # every record above materialized at least one output of its
            # program, so all of last step's donated-arena consumers have
            # completed — dropping the parked handles is free now (doing it
            # at dispatch would block the host for the whole device step).
            # The handles the dispatch ahead parked are another matter: its
            # program is on the device now, so they wait for its own harvest
            with self._span("serve.harvest.emit"):
                self._release_retired(parked)
                self._sample_occupancy()
        if prev is not None:
            nxt = self._inflight_decode
            self._trace_decode_begin(nxt["running"], nxt["step"], nxt["compiled"], nxt["bucket"])
        return worked

    def _harvest_inline(self, rec: dict) -> None:
        """The synchronous loop's harvest of the record just dispatched."""
        with self._span("serve.harvest"):
            if rec["kind"] == "decode":
                self._decode_harvest(rec)
            else:
                self._prefill_harvest(rec)
            with self._span("serve.harvest.emit"):
                self._release_retired()     # outputs materialized: consumer done
                self._sample_occupancy()

    def _release_retired(self, upto: int | None = None) -> None:
        """Drops the parked donated-arena handles of every pool the engine
        owns (target always; the draft arena too under speculative
        serving — both are donated by the same harvested round).  ``upto``:
        the target pool's mark before a dispatch ahead, whose handles stay
        (the K/V, state, conv and ring arenas park as one entry; a
        speculative engine never dispatches ahead, so the draft pool has
        none to keep)."""
        self.pool.release_retired(upto)
        if self.draft_pool is not None:
            self.draft_pool.release_retired()

    def _advance_prefills(self) -> bool:
        """The prefill lane: dispatches the next chunk for every running
        request whose prompt is not yet resident and has no piece already
        in flight — at most one piece per request per step, so chunks
        interleave 1:1 with decode dispatches."""
        worked = False
        inflight = {rec["req"].rid for rec in self._inflight_prefill}
        for r in list(self.scheduler.running):
            if r.pos < r.prompt_len and r.rid not in inflight:
                self._inflight_prefill.append(self._prefill_dispatch(r))
                worked = True
        return worked

    def run(self, requests: Sequence, *, max_new_tokens: int | None = None) -> list[RequestResult]:
        """Convenience driver: submits every request (stepping through
        transient queue-full rejections) and drives to completion.  Each
        request is a prompt array or a dict of :meth:`submit` kwargs."""
        handles = []
        for r in requests:
            kw = dict(r) if isinstance(r, dict) else {"prompt": r}
            if "max_new_tokens" not in kw:
                if max_new_tokens is None:
                    raise ValueError("max_new_tokens missing (argument or per-request)")
                kw["max_new_tokens"] = max_new_tokens
            prompt = kw.pop("prompt")
            # transient queue-full backpressure is not a rejection: make room
            # by stepping instead of bouncing off submit() (which counts every
            # AdmissionError it raises in serving.requests.rejected)
            while len(self.scheduler.queue) >= self.scheduler.max_queue:
                if not self.step():
                    raise AdmissionError(
                        f"wait queue full ({self.scheduler.max_queue}) and the "
                        "engine cannot make progress"
                    )
            handles.append(self.submit(prompt, **kw))
        self.drain()
        return [h.result(drive=False) for h in handles]

    def drain(self) -> None:
        """Steps until every submitted request has finished.  Never a busy
        poll: when every request is blocked on device work, the next
        ``step()`` backs off *inside* the harvest of the in-flight futures
        table (a bounded number of ``step()`` calls per token, asserted by
        regression test).  A stall (work remains but no step can progress)
        raises :class:`EngineStalledError` carrying the flight-recorder
        state snapshot."""
        while self.scheduler.queue or self.scheduler.running:
            if not self.step():
                raise EngineStalledError(
                    "engine stalled during drain", self._flight_state()
                )

    def evict(self, handle: RequestHandle) -> None:
        """Administratively removes a queued/running request (finish reason
        ``"evicted"``); its blocks return to the pool immediately."""
        if not handle.done():
            self._finish(handle._req, FINISH_EVICTED)

    def held(self, handle: RequestHandle, layers: Sequence[int] | None = None) -> dict:
        """What the caches hold of a running request, read once nothing is
        in flight (an async engine harvests first): ``tokens``, how many of
        its prompt and generated tokens went in; ``k`` and ``v`` ``(L_kv, ng,
        tokens, hs)`` as attention reads them (a quantised arena comes
        dequantised, at the compute dtype), or, for a latent-attention model,
        ``latent (L, tokens, latent_width)`` in their place; and, for a model with
        linear_attention layers, its slot's ``state (L_lin, nv, dk, dv)`` (the
        arena keeps a row's heads side by side, ``(dk, nv dv)``: they come apart
        again) and ``conv (L_lin, K - 1, channels)`` as stored (conv layers: ``conv
        (L_conv, conv_kernel - 1, n_embd)`` alone; ssm layers: ``state (L_ssm,
        ssm_state, ssm_inner)`` and ``conv``; mamba2 layers: ``state (L_m,
        mamba_state, mamba_inner)``, a head's matrix transposed in its columns, and
        ``conv (L_m, K - 1, mamba_conv_width)``; a layer that is a feed-forward
        alone keeps nothing and is passed over).  A model with sliding_attention
        layers: ``k``/``v`` are the full_attention layers' and ``k_ring``/``v_ring
        (L_ring, ng, n, hs)`` the window layers' last ``n = min(tokens,
        layer_window)`` tokens, in order.  A lane-packed arena's
        heads come apart again.  ``layers``: the layers of the paged K/V arenas to
        return, in that order (all of them where None): a looped model's 192 slabs
        of a request do not fit beside its arena.  For the tests and for a
        comparison with a reference; changes nothing."""
        if self.async_step:
            self._harvest()
        req = handle._req
        if req.state != "running":
            raise RuntimeError(f"request {req.rid} is {req.state}: the caches hold a running request only")
        arenas = self.pool.arenas
        if layers is not None:      # a slice a layer: an index array would gather the arena through a copy of it
            pick = lambda a: jnp.stack([jax.lax.index_in_dim(a, l, axis=1, keepdims=False) for l in layers], axis=1)  # noqa: E731
            arenas = {name: pick(a) if name in ("k", "v", "k_scale", "v_scale") else a for name, a in arenas.items()}
        table = jnp.asarray([req.block_table[:self.pool.blocks_for_tokens(req.pos)]], dtype=jnp.int32)
        if self._latent:     # (L, tokens, latent_width): the rows without their lane padding
            rows = gather_rows(arenas["latent"], table)
            return {"tokens": req.pos, "latent": rows[:, 0, 0, :req.pos, :self.cfg.latent_width]}
        if self.pool.quantized_kv:
            k, v = gather_dense_q(arenas["k"], arenas["v"], arenas["k_scale"], arenas["v_scale"],
                                  table, self.pool.dtype)
        else:
            k, v = gather_dense(arenas["k"], arenas["v"], table, self.pool.lane_pack)
        out = {"tokens": req.pos, "k": k[:, 0, :, :req.pos], "v": v[:, 0, :, :req.pos]}
        if self._hybrid:
            state = self.pool.state
            out.update(state.slot_rows(req.state_slot))
            if state.ring_blocks:
                # the ring's tokens in order: the last ``layer_window`` (fewer for a
                # shorter sequence), ``[tokens - n, tokens)``, what the last query attended
                bs, n = self.pool.block_size, min(req.pos, self.cfg.layer_window)
                lo = (req.pos - n) // bs
                table = ring_tables(jnp.asarray([req.state_slot], jnp.int32), state.ring_blocks,
                                    self.pool.blocks_for_tokens(req.pos))[:, lo:]
                for name in RING_ARENAS:
                    rows = gather_rows(arenas[name], table, self.pool.lane_pack)[:, 0]
                    out[name] = rows[:, :, req.pos - n - lo * bs:req.pos - lo * bs]
        return out

    def shutdown(self, *, drain: bool = True) -> None:
        """Graceful stop: optionally drains, discards whatever is still in
        flight, evicts whatever remains, closes owned telemetry, and
        rejects further submits.  The in-flight discard matters on the
        non-drain path: an async decode/chunk future still on the device —
        and the donated-arena handles parked for it — must be dropped
        before the engine closes, or they leak past shutdown."""
        if self._closed:
            return
        if drain:
            self.drain()
        self._discard_inflight()
        for req in (*self.scheduler.running, *self.scheduler.queue):
            self._finish(req, FINISH_EVICTED)
        if self._sessions is not None:
            self._sessions.clear()
        self._closed = True
        if self._owns_telemetry and self.telemetry is not None:
            self.telemetry.close()

    def __enter__(self) -> "ServingEngine":
        return self

    def __exit__(self, *exc) -> None:
        self.shutdown(drain=exc == (None, None, None))

    def mesh_stats(self) -> dict | None:
        """Mesh-serving facts (``None`` on a single-device engine): mesh
        shape, per-shard arena bytes, and — once the first decode step has
        run its program census — the collective count of one compiled
        decode program."""
        if self.mesh is None:
            return None
        return {
            "devices": int(self.mesh.devices.size),
            "axes": {a: int(self.mesh.shape[a]) for a in self.mesh.axis_names},
            "arena_spec": str(self.pool.arena_sharding.spec),
            "arena_shard_bytes": self.pool.per_shard_bytes(),
            "arena_total_bytes": int(self.pool.k_arena.nbytes) * 2,   # a mesh serves K and V only
            "collectives_decode": self._mesh_collectives,  # None until censused
        }

    def stats(self) -> dict:
        """Host-side engine statistics (registry-independent)."""
        occ = (self._occupancy_sum / self.decode_steps) if self.decode_steps else 0.0
        mesh = self.mesh_stats()
        sch = self.scheduler
        # program kinds a bucket may instantiate: decode per batch bucket
        # (doubled under speculative serving: each round runs draft_decode
        # AND verify at the same bucket), prefill per prefill bucket, plus
        # the chunk kind when chunking is on — or once recovery has
        # replayed through the chunk programs; a prefill_fresh program reads
        # no table, so it is one a prefill bucket whatever the width
        kinds = len(sch.batch_buckets) * (
            2 if self.spec is not None else 1
        ) + len(sch.prefill_buckets) * (
            2 if (sch.prefill_chunk is not None or self.chunk_runs > 0) else 1
        )
        n = self._overlap_obs
        rebuilds = self._rebuilds
        rebuilt_rows = rebuilds["rows_written"] + rebuilds["rows_carried"]
        return {
            **({"replica": self.replica_id} if self.replica_id is not None else {}),
            **({"mesh": mesh} if mesh is not None else {}),
            **({"lora": self._registry.state_snapshot()} if self._registry is not None else {}),
            "queue_depth": len(sch.queue),
            "running": len(sch.running),
            "pool_free_blocks": self.pool.num_free,
            "pool_free_blocks_low_water": self.pool.free_blocks_low_water,
            "pool_utilization": self.pool.utilization(),
            "kv_dtype": str(self.pool.kv_dtype),
            "arena_bytes": self.pool.arena_bytes(),
            **({"state": self.pool.state.snapshot()} if self._hybrid else {}),
            **({"moe": {"experts_held": self.cfg.expert_held, "expert_first": self.cfg.expert_first,
                        "experts_published": self.cfg.n_expert, "router": self.cfg.moe_router,
                        **self._moe_rows_stats()}}
               if self.cfg.mlp_class == "SparseMoE" else {}),
            "async_step": self.async_step,
            "prefill_chunk": sch.prefill_chunk,
            "decode_steps": self.decode_steps,
            "decode_ahead": {
                "dispatches": self.decode_steps, "ahead": self.decode_ahead_steps,
                "share": self.decode_ahead_steps / self.decode_steps if self.decode_steps else 0.0,
                # of the steps ahead, those one step past a row's end by length:
                # there once one has gone (as a cause of the ledger's ``waste``)
                **({"through_end": self.decode_through_end_steps} if self.decode_through_end_steps else {}),
            },
            # rebuilds of the decode chain's inputs: how many carried nothing
            # over (the full build), and the rows written from Python against
            # the rows taken from the build before
            "decode_rebuild": {
                **rebuilds,
                "carried_share": rebuilds["rows_carried"] / rebuilt_rows if rebuilt_rows else 0.0,
            },
            "host_visits": self.host_visits,
            "tokens_per_host_visit": (
                self.decode_lane_tokens / self.host_visits
                if self.host_visits else None
            ),
            "prefill_runs": self.prefill_runs,
            "prefill_fresh_runs": self.prefill_fresh_runs,
            "chunk_runs": self.chunk_runs,
            "step_calls": self.step_calls,
            "tokens_generated": self.tokens_generated,
            "mean_batch_occupancy": occ,
            "decode_stall_s_mean": (self._stall_s_sum / n) if n else None,
            "overlap_frac_mean": (self._overlap_frac_sum / n) if n else None,
            "compile_counts": dict(self.compile_counts),
            **({"hc": self._hc_counts()} if self.cfg.hc_mult > 1 else {}),
            # a looped model: decode steps, the layers they applied (steps x passes x layers), the
            # served tokens by the pass the exit rule chose and their exit probabilities summed
            **({"passes": {"steps": self.decode_steps,
                           "layer_passes": self.decode_steps * self._passes * self.cfg.n_layer,
                           "exit": list(self._pass_sums["exit"]),
                           "exit_mass": [float(m) for m in self._pass_sums["exit_mass"]]}}
               if self._pass_sums is not None else {}),
            "attn": {
                # decode steps whose attention call took the XLA form (every one
                # or none: the form is the engine's, ``path``)
                "fallback_steps": self.attn_fallback_steps,
                "kv_chunk_tokens": self._kv_chunk_tokens,
                # the form the decode kernel's entry takes for this arena: the
                # chunked walk, a block a grid step, or its XLA form; and the KV
                # heads a row of the arena holds
                "path": self._attn_path,
                "lane_pack": self.pool.lane_pack,
                # a prompt piece's program ("paged" or "gather"), and why it is
                # the gather chunk where it is
                "chunk": self.attn_chunk,
                "chunk_why": self._attn_chunk_why,
                # per-kind caches: the layers that walk one layer's blocks (the
                # layer itself and the cross_attention layers after it), and the
                # rows whole prompts ran through those layers (one a prompt)
                **({"shared_kv_layers": 1 + sum(k == "cross_attention" for k in self.cfg.layer_types),
                    "prefill_cross_rows": self.prefill_fresh_runs}
                   if self._perkind and self.cfg.cross_from is not None else {}),
                # window layers: the keys the decode steps' rows attended, summed, in a
                # layer of each kind (``min(pos + 1, layer_window)`` a row against ``pos + 1``);
                # a looped model: in a layer of a pass, and once a slab-walk (every layer of every pass)
                **({"attended_tokens": {**self._attended, **({"slab_walks": self._attended["full_attention"] * self.cfg.kv_slabs}
                                                              if self._passes > 1 else {})}}
                   if self._attended is not None else {}),
            },
            "bucket_bound": kinds * len(self._table_widths) + (
                len(sch.prefill_buckets) if self.spec is None else 0),
            "prefix_lookups": self._prefix_lookups,
            "prefix_hits": self._prefix_hits,
            "recoveries": self.recoveries,
            "faults": self._faults.snapshot() if self._faults is not None else None,
            **({"spec": self._spec_stats()} if self.spec is not None else {}),
            **({"sessions": self._sessions.snapshot()}
               if self._sessions is not None else {}),
            **({"priority": {**self._priorities.snapshot(),
                             "preempted": self.preempted}}
               if self._priorities is not None else {}),
            **({"constrained": True} if self._constraints else {}),
            **({"goodput": self._goodput.snapshot()}
               if self._goodput is not None else {}),
            "pool_occupancy": self.pool.occupancy_snapshot(),
        }

    @staticmethod
    def _hc_counts() -> dict:
        """The hyper-connections' boundaries this process's programs were built with (trace time,
        ``pallasex.stats``): those that took ``hc_mix`` (a prompt's) and those that kept to XLA's
        fusions (a decode step's rows, a prompt's one-row last close, a mesh)."""
        from thunder_tpu.executors.pallasex import stats

        return {"fused": stats.get("hc_fused", 0), "fallback": stats.get("hc_fallback", 0)}

    def _moe_rows_stats(self) -> dict:
        """How the single-step decode program's rows fell on the held experts,
        summed on the device a step (``_build_decode_paged``) and fetched here,
        which waits for the step in flight: ``expert_rows_per_step``, the rows (of
        ``batch bucket x n_expert_per_token`` routed, padding rows' too) that
        landed on a held expert, a mean over the expert layers, as the steps'
        ``mean`` and ``spread`` (their standard deviation); ``experts_hit_share``,
        the share of the held experts that got at least one row, a mean over
        layers and steps; ``row_sums`` ``[steps, sum of rows, sum of rows^2, sum of
        hit shares]``, for a reader that wants a window's own (the difference of
        two calls).  Nones before the first such step."""
        n, rows, sq, hit = (float(v) for v in jax.device_get(self._moe_rows)) if self._moe_rows is not None else (0,) * 4
        if not n:
            return {"expert_rows_per_step": {"mean": None, "spread": None}, "experts_hit_share": None,
                    "row_sums": [0.0, 0.0, 0.0, 0.0]}
        mean = rows / n
        return {"expert_rows_per_step": {"mean": mean, "spread": max(sq / n - mean * mean, 0.0) ** 0.5},
                "experts_hit_share": hit / n, "row_sums": [n, rows, sq, hit]}

    def _spec_stats(self) -> dict:
        """Speculative-lane acceptance accounting: the histogram counts
        rounds by tokens emitted (1..K+1); acceptance_rate is accepted
        drafts / drafted tokens; tokens_per_round is the mean emission —
        the solo ``speculative_generate.last_tokens_per_round`` analogue."""
        hist = self._spec_accept_hist
        rounds = int(hist.sum())
        drafted = self.spec_draft_tokens
        return {
            "K": self.spec.K,
            "rounds": self.spec_rounds,
            "draft_tokens": drafted,
            "accepted_tokens": self.spec_accepted_tokens,
            "acceptance_rate": (self.spec_accepted_tokens / drafted) if drafted else None,
            "accept_len_hist": {i + 1: int(hist[i]) for i in range(len(hist))},
            "tokens_per_round": (
                sum((i + 1) * int(hist[i]) for i in range(len(hist))) / rounds
            ) if rounds else None,
        }

    def slo_report(self) -> dict:
        """Burn rates against the configured SLO targets (``slo=`` at
        construction; see :mod:`thunder_tpu.observability.slo`).  Without a
        configured SLO the report is ``{"enabled": False}`` — the engine
        carries no monitor and no per-request classification cost."""
        if self._slo is None:
            return {"enabled": False}
        return self._slo.report()

    def goodput_report(self) -> dict:
        """Full goodput-ledger report (``goodput=`` at construction; see
        :mod:`thunder_tpu.observability.goodput`): token-goodput fraction
        plus per-cause and per-program-kind breakdowns with device-time
        attribution.  ``{"enabled": False}`` when the ledger is off."""
        if self._goodput is None:
            return {"enabled": False}
        rep = self._goodput.report()
        if self.replica_id is not None:
            rep["replica"] = self.replica_id
        return rep

    def _flight_state(self) -> dict:
        """State snapshot the flight recorder embeds in every dump."""
        lookups = self._prefix_lookups
        dec = self._inflight_decode
        return {
            **({"replica": self.replica_id} if self.replica_id is not None else {}),
            "engine": self.stats(),                      # includes "mesh" when SPMD
            "scheduler": self.scheduler.state_snapshot(),
            "pool": self.pool.state_snapshot(),
            # what each lane was doing: the in-flight futures plus every
            # partially-prefilled request (a crash mid-overlap is
            # undiagnosable without knowing what was still on the device)
            "lanes": {
                "async_step": self.async_step,
                "decode_inflight": (
                    {"step": dec["step"], "bucket": dec["bucket"],
                     "rids": [r.rid for r in dec["running"]]}
                    if dec is not None else None
                ),
                "prefill_inflight": [
                    {"rid": rec["req"].rid, "kind": rec["kind"]}
                    for rec in self._inflight_prefill
                ],
                "prefilling": [
                    {"rid": r.rid, "pos": r.pos, "prompt_tokens": r.prompt_len}
                    for r in self.scheduler.running if r.pos < r.prompt_len
                ],
                "speculative": (
                    {"K": self.spec.K,
                     "chained": self._spec_state is not None,
                     "rounds": self.spec_rounds,
                     "acceptance_rate": self._spec_stats()["acceptance_rate"]}
                    if self.spec is not None else None
                ),
                "goodput": (self._goodput.brief()
                            if self._goodput is not None else None),
            },
            "prefix_share_hit_rate": (self._prefix_hits / lookups) if lookups else None,
            "compiles": list(self._compile_log),         # per-bucket compile causes
            "slo": self.slo_report(),
        }

    #
    # admission + prefill
    #

    def _table_width_buckets(self) -> tuple[int, ...]:
        """Every table width a compiled program may use: the scheduler's
        block buckets, shifted off any width whose gathered capacity equals
        ``sliding_window`` (which ``forward_with_cache`` would interpret as
        the ring layout — the pool always uses the plain slot-=-position
        layout; the window lives in the keep-mask), then extended so a
        shared-prefix or chunked-prefill resume point plus prefill-bucket
        padding past the largest block bucket still rounds up into the set.
        ``stats()``'s ``bucket_bound`` counts these widths, so :meth:`_nbb`
        may never produce one outside them."""
        sch, bs = self.scheduler, self.pool.block_size
        W = self.cfg.sliding_window
        chunk = sch.prefill_chunk

        def dodge(b: int) -> int:
            return b + 1 if W is not None and self.pool.capacity_tokens(b) == W else b

        widths = {dodge(b) for b in sch.block_buckets}
        # widest dense window a prefill piece can touch: the largest
        # block-aligned resume point (shared prefix OR an earlier chunk)
        # plus a padded prefill bucket.  Without chunking, prompts are
        # capped by the prefill buckets; with it, only by the admission
        # hard cap on blocks — but every piece is at most one chunk wide.
        cap_tokens = self.pool.capacity_tokens(
            min(self.pool.num_usable, sch.block_buckets[-1])
        )
        max_prompt = cap_tokens if chunk is not None else min(sch.prefill_buckets[-1], cap_tokens)
        resumes = self.prefix_sharing or chunk is not None
        max_resume = ((max_prompt - 1) // bs) * bs if resumes else 0
        piece = chunk if chunk is not None else pick_bucket(max_prompt, sch.prefill_buckets)
        need = -(-(max_resume + piece) // bs)
        # re-prefill recovery replays prompt + emitted tokens through the
        # chunk programs on ANY engine (chunked or not): its resume points
        # reach to one token short of the full reservation capacity, and
        # its pieces are the widest block-aligned prefill bucket — those
        # widths must be in the set too, or a recovery would mint a table
        # width bucket_bound never counted
        aligned = [t for t in sch.prefill_buckets if t % bs == 0]
        replay_piece = max(aligned) if aligned else sch.prefill_buckets[-1]
        replay_resume = ((cap_tokens - 1) // bs) * bs
        need = max(need, -(-(replay_resume + replay_piece) // bs))
        b = max(widths)
        while b < need:
            b *= 2
            widths.add(dodge(b))
        return tuple(sorted(widths))

    def _nbb(self, min_blocks: int) -> int:
        """Table-width bucket for ``min_blocks``, from the precomputed
        width set (see :meth:`_table_width_buckets`)."""
        return pick_bucket(min_blocks, self._table_widths)

    def _try_admit(self) -> bool:
        sch = self.scheduler
        if not sch.queue:
            return False
        head = sch.queue[0]
        gate = self._priorities
        if gate is not None and not gate.admit_ok(head.priority_class, self._slo):
            # SLO burn defers this class; more urgent arrivals jump the
            # queue (priority insertion), so holding the head is safe
            return False
        # a preempted victim re-admitting skips prefix sharing: its replay
        # rewrites from position 0, so leased shared blocks would be
        # co-owned write targets
        resume = bool(head.generated)
        shared = [] if resume else self._find_shared_prefix(head)
        req = sch.next_admittable(shared_blocks=len(shared))
        if req is None:
            return (gate is not None and self._maybe_preempt(head))
        if (shared and self._sessions is not None
                and self._hit_owner is not None and self._hit_owner < 0):
            self._sessions.note_reattach(self._hit_owner)
            entry = self._sessions.owner_entry(self._hit_owner)
            if entry is not None and entry.full_pos:
                # the parked turn had written full_pos cache slots; the
                # prompt positions below that watermark and past the shared
                # blocks are recomputation of the truncated tail
                req.replay_until = max(
                    req.replay_until, min(entry.full_pos, req.prompt_len))
                req.replay_cause = "replay_session_tail"
        n_needed = sch.blocks_needed(req)
        table = self.pool.share(shared) + self.pool.alloc(n_needed - len(shared))
        sch.admit(req, table, len(shared))
        if gate is not None:
            registry().counter(
                f"serving.priority.{req.priority_class}.admitted").inc()
        if self._tracer is not None:
            self._tracer.end(req.rid, "queued",
                             queue_s=req.admit_t - req.submit_t)
        if self._flight is not None:
            self._flight.record("admit", rid=req.rid, blocks=n_needed,
                                shared_blocks=len(shared),
                                pool_free=self.pool.num_free,
                                resume=resume)
        if resume:
            self._resume_replay(req)
        else:
            self._prefill(req)
        return True

    def _maybe_preempt(self, head: Request) -> bool:
        """Evict-and-resume: checkpoint the least-urgent running request so
        a strictly more urgent head can be funded.  The checkpoint is free
        — prompt, generated tokens and the PRNG key chain are host state
        that only advances at harvest — so preemption is unregister +
        release + re-queue; re-admission replays through the sampling-free
        ``prefill_chunk`` pieces (:meth:`_resume_replay`), bit-identical
        to an undisturbed run.  Unsupported beside the speculative lane
        (its harvest has no preemption epoch guard)."""
        if self.spec is not None:
            return False
        victim = self._priorities.pick_victim(self.scheduler.running, head.priority)
        if victim is None:
            return False
        self._unregister_prefix(victim)
        self.scheduler.preempt(victim)     # frees blocks, bumps preemptions
        self._decode_state = None
        self.preempted += 1
        registry().counter(
            f"serving.priority.{victim.priority_class}.preempted").inc()
        if self._tracer is not None:
            self._tracer.instant(victim.rid, "preempted",
                                 for_rid=head.rid,
                                 generated=len(victim.generated))
            self._tracer.begin(victim.rid, "queued",
                               preemptions=victim.preemptions)
        if self._flight is not None:
            self._flight.record("preempt", rid=victim.rid, for_rid=head.rid,
                                generated=len(victim.generated),
                                pool_free=self.pool.num_free)
        return True

    def _resume_replay(self, req: Request) -> None:
        """Re-admission path for a preempted request that already holds
        generated tokens: rebuild its KV through the ``prefill_chunk``
        replay (bucket-wide pieces, no sampling, no key split) and rejoin
        the decode lane at the identical position/key chain."""
        tr = self._tracer
        if tr is not None:
            tr.begin(req.rid, "resume", lane="prefill",
                     generated=len(req.generated))
        self._replay_request(req, cause="replay_preemption")
        self._register_prefix(req, upto=req.pos)
        if tr is not None:
            tr.end(req.rid, "resume", pos=req.pos)

    def _find_shared_prefix(self, req: Request) -> list[int]:
        """Longest block-aligned prompt prefix already resident in a live
        request's blocks (the last prompt token always re-prefills, so the
        share is capped one token short of the full prompt).  The index
        machinery itself lives in :class:`~thunder_tpu.serving.kv_pool.
        PrefixIndex` so the dp router can probe residency without touching
        engine internals."""
        if not self.prefix_sharing:
            return []
        self._hit_owner = None
        return self._prefix_index.find(req.prompt, self._prefix_alive)

    def _prefix_alive(self, hit: tuple[int, tuple[int, ...]]) -> bool:
        """A registered prefix is shareable only while its owner is still
        running AND every snapshot block id is still the live table entry
        (window expiry sinks leading entries without finishing the owner).
        Negative owner rids are parked sessions — their liveness is the
        session table's (the entry exists and still owns those blocks)."""
        rid, blocks = hit
        if rid < 0:
            ok = self._sessions is not None and self._sessions.alive(rid, blocks)
            if ok:
                self._hit_owner = rid
            return ok
        owner = next((r for r in self.scheduler.running if r.rid == rid), None)
        if owner is None or len(owner.block_table) < len(blocks):
            return False
        ok = all(t == b != SINK_BLOCK for t, b in zip(owner.block_table, blocks))
        if ok:
            self._hit_owner = rid
        return ok

    def _register_prefix(self, req: Request, upto: int | None = None) -> None:
        """Registers ``req``'s block-aligned prompt prefixes.  ``upto``
        bounds registration to tokens already *written* (a chunked prefill
        registers after each piece; a sharer's later-dispatched program is
        ordered behind the writes on the device stream, so it never gathers
        an unwritten block)."""
        if not self.prefix_sharing:
            return
        self._prefix_index.register(
            req.rid, req.prompt, req.block_table, self._prefix_alive, upto=upto)

    def _unregister_prefix(self, req: Request) -> None:
        self._prefix_index.unregister(req.rid)

    def probe_prefix(self, prompt) -> int:
        """Longest resident shared-prefix length (tokens) for ``prompt``,
        without counting a lookup or mutating the index — the dp router's
        affinity probe."""
        if not self.prefix_sharing:
            return 0
        return self._prefix_index.probe(prompt, self._prefix_alive)

    @property
    def _prefix_lookups(self) -> int:
        return self._prefix_index.lookups

    @property
    def _prefix_hits(self) -> int:
        return self._prefix_index.hits

    def _prefill(self, req: Request) -> None:
        """Admission-time prefill entry.  Sync: dispatch the whole prompt
        and materialize inline (the original path).  Async: dispatch the
        first piece (a chunk when the prompt exceeds ``prefill_chunk``,
        else the whole remainder) and defer the harvest to the next step."""
        rec = self._prefill_dispatch(req)
        if self.async_step:
            self._inflight_prefill.append(rec)
        else:
            self._harvest_inline(rec)

    def _state_args(self, state_slot: int, n_real: int) -> tuple:
        """What a chunk program of a model with a recurrent state takes
        beside the rest: the request's state slot and how many of the piece's
        tokens are real (the padded tail must leave the state alone)."""
        if not self._hybrid:
            return ()
        return jnp.asarray([state_slot], dtype=jnp.int32), jnp.int32(n_real)

    def _chunk_kind(self) -> str:
        """The non-speculative chunk program kind this engine dispatches —
        resolved once at construction (``self.attn_chunk``), so raggedness
        never changes program identity mid-flight."""
        return ("prefill_chunk_paged" if self.attn_chunk == "paged"
                else "prefill_chunk")

    def _prefill_dispatch(self, req: Request) -> dict:
        """Dispatches the next prefill piece for ``req`` and returns its
        in-flight record.  A piece is either a full ``prefill`` (samples
        token 0, splits the request key exactly like solo ``generate()``)
        or an intermediate ``prefill_chunk`` (writes KV only — no sampling,
        no key split, so the final piece's draw stays bit-identical to the
        unchunked prefill)."""
        with self._span("serve.prefill_dispatch", rid=req.rid) as sp:
            return self._prefill_piece(req, sp)

    def _prefill_piece(self, req: Request, sp: span) -> dict:
        self._fault_point(FP_PREFILL, (req.rid,))
        sch, pool = self.scheduler, self.pool
        bs = pool.block_size
        pos = req.pos                                      # block-aligned resume point
        remainder = req.prompt_len - pos
        chunk = sch.prefill_chunk
        final = chunk is None or remainder <= chunk
        n_real = remainder if final else chunk
        first = pos == req.n_shared_blocks * bs            # the admission piece
        Tb = sch.prefill_bucket(n_real)
        nbb = self._nbb(max(len(req.block_table), -(-(pos + Tb) // bs)))
        toks = np.zeros(Tb, dtype=np.int32)
        toks[:n_real] = req.prompt[pos:pos + n_real]
        # gather the whole table; scatter back only the freshly written
        # block range — everything else (shared prefix, earlier chunks,
        # bucket padding) sinks (chunk granularity, see kv_pool.chunk_tables)
        table, dest = chunk_tables(req.block_table, pos, Tb, nbb, bs)
        fresh = final and pos == 0 and self.spec is None
        if self.spec is not None:
            kind = "spec_prefill" if final else "spec_prefill_chunk"
        elif fresh:
            # a whole prompt at position 0 (no shared block, no earlier piece)
            # has nothing before it in the arenas: its program reads none of
            # them, and its table is the blocks the bucket fills
            kind = "prefill_fresh"
            nbb = -(-Tb // bs)
            dest = dest[:nbb]
        else:
            kind = "prefill" if final else self._chunk_kind()
        prog, compiled = self._program(kind, Tb, nbb)
        # tokens: this piece's prompt tokens, not the padded bucket
        sp.set(tokens=n_real, bucket=f"{Tb}x{nbb}", piece=kind)
        first_call = self._compile_span(compiled, kind, Tb, nbb)
        req.prefill_compiled = req.prefill_compiled or compiled
        # the dispatch phase is named by its dominant cost: a fresh program
        # pays the XLA compile here, a cached one only dispatches
        name = ("prefill.chunk" if not final
                else "prefill.compile" if compiled else "prefill.dispatch")
        tr = self._tracer
        if tr is not None:
            if first:
                tr.begin(req.rid, "prefill", compile=compiled, bucket=[Tb, nbb],
                         shared_blocks=req.n_shared_blocks, lane="prefill",
                         chunked=not final)
            tr.begin(req.rid, name, lane="prefill")
        darenas = None
        if final and self.spec is not None:
            with first_call:
                tok, arenas, darenas, key, qerr = prog(
                    self.params, self.spec.draft_params,
                    jnp.asarray(toks)[None], jnp.int32(pos), jnp.int32(n_real),
                    pool.arenas, self.draft_pool.arenas,
                    jnp.asarray(table), jnp.asarray(dest), jnp.asarray(req.key),
                    self._lora_arenas(), jnp.asarray([req.adapter_slot], dtype=jnp.int32),
                )
            rec = {"kind": "prefill", "req": req, "tok": tok, "key": key,
                   "qerr": qerr, "compiled": compiled, "span": name,
                   "epoch": req.preemptions, "t_clock": sch.clock()}
        elif final:
            args = (
                self.params, jnp.asarray(toks)[None],
                *(() if fresh else (jnp.int32(pos),)), jnp.int32(n_real), pool.arenas,
                *(() if fresh else (jnp.asarray(table),)), jnp.asarray(dest), jnp.asarray(req.key),
                self._lora_arenas(), jnp.asarray([req.adapter_slot], dtype=jnp.int32),
            )
            if self._hybrid:
                args += (jnp.asarray([req.state_slot], dtype=jnp.int32),)
            if self._constraints:
                # the final piece samples token 0: it must respect the
                # request's automaton exactly like every decode draw
                args += (jnp.asarray(req.constraint.mask()[None])
                         if req.constraint is not None
                         else self._ones_mask((1, self._vocab)),)
            with first_call:
                tok, arenas, key, qerr, *exit_rows = prog(*args)
            rec = {"kind": "prefill", "req": req, "tok": tok, "key": key,
                   "qerr": qerr, "compiled": compiled, "span": name,
                   "epoch": req.preemptions, "t_clock": sch.clock(),
                   **({"exit": exit_rows[0]} if exit_rows else {})}
        elif self.spec is not None:
            with first_call:
                arenas, darenas, qerr = prog(
                    self.params, self.spec.draft_params,
                    jnp.asarray(toks)[None], jnp.int32(pos),
                    pool.arenas, self.draft_pool.arenas,
                    jnp.asarray(table), jnp.asarray(dest),
                    self._lora_arenas(), jnp.asarray([req.adapter_slot], dtype=jnp.int32),
                )
            rec = {"kind": "chunk", "req": req, "qerr": qerr,
                   "compiled": compiled, "span": name,
                   "t_clock": sch.clock()}
        else:
            with first_call:
                arenas, qerr = prog(
                    self.params, jnp.asarray(toks)[None], jnp.int32(pos),
                    pool.arenas, jnp.asarray(table), jnp.asarray(dest),
                    self._lora_arenas(), jnp.asarray([req.adapter_slot], dtype=jnp.int32),
                    *self._state_args(req.state_slot, n_real),
                )
            rec = {"kind": "chunk", "req": req, "qerr": qerr,
                   "compiled": compiled, "span": name,
                   "t_clock": sch.clock()}
        # a fault here is past the point of no return: the program call
        # above consumed the donated arenas, so absorb routes to recovery
        self._fault_point(FP_SCATTER, (req.rid,))
        pool.set_arenas(arenas)
        if darenas is not None:
            self.draft_pool.set_arenas(darenas)
        req.pos = pos + n_real                             # written (device-ordered)
        self._register_prefix(req, upto=req.pos)
        if req.replay_until > pos:
            # recompute bookkeeping (host ints, replay paths only): these
            # positions were already dispatched once before the replay
            rn = min(req.replay_until, pos + n_real) - pos
            req.tokens_recomputed += rn
            if (req.replay_cause
                    and req.replay_cause not in req.recompute_causes):
                req.recompute_causes.append(req.replay_cause)
        if self._goodput is not None:
            rec["pkind"] = kind
            rec["t_disp"] = time.perf_counter()
            rec["goodput"] = self._account_prefill(req, kind, pos, n_real, Tb)
        reg = registry()
        if final:
            self.prefill_runs += 1
            reg.counter("serving.steps.prefill").inc()
            if fresh:
                self.prefill_fresh_runs += 1
                reg.counter("serving.steps.prefill_fresh").inc()
        else:
            self.chunk_runs += 1
            reg.counter("serving.steps.prefill_chunk").inc()
        if compiled:
            # cold-compile TTFT outliers must be distinguishable from queue
            # delay: count prefill RUNS that paid a compile (vs
            # serving.compiles.prefill, which counts program builds)
            reg.counter("serving.prefill.compiles").inc()
        if first and req.n_shared_blocks:
            reg.counter("serving.prefix.shared_blocks").inc(req.n_shared_blocks)
        if self._flight is not None:
            self._flight.record("prefill" if final else "prefill_chunk",
                                rid=req.rid, compiled=compiled,
                                bucket=[Tb, nbb], pos=pos,
                                shared_blocks=req.n_shared_blocks,
                                **({} if final else {"attn": self.attn_chunk}))
        return rec

    def _prefill_harvest(self, rec: dict) -> None:
        """Materializes one prefill-piece record: chunks only settle the
        measured quantization error; the final piece delivers token 0
        (TTFT stamps here — token availability, not dispatch)."""
        req, pool = rec["req"], self.pool
        self._fault_point(FP_HARVEST, (req.rid,))
        gp = self._goodput
        if gp is not None and "t_disp" in rec:
            gp.note_device_s(rec["pkind"], time.perf_counter() - rec["t_disp"])
        tr = self._tracer
        if rec["kind"] == "chunk":
            # the scalar fetch doubles as the fence on the chunk execution
            # (release_retired relies on every harvested record having
            # materialized an output of its program)
            with self._span("serve.harvest.wait", kind="prefill_chunk", rid=req.rid):
                qerr = float(np.asarray(rec["qerr"]))
            if pool.quantized_kv:
                registry().gauge("serving.kv_quant.rel_err").set(qerr)
            if tr is not None:
                tr.end(req.rid, rec["span"], lane="prefill")
            return
        if tr is not None:
            tr.end(req.rid, rec["span"])
            tr.begin(req.rid, "prefill.host")
        if req.state != "running" or req.preemptions != rec.get(
                "epoch", req.preemptions):
            # finished (deadline/evict) or preempted-and-resumed while the
            # piece was in flight: the sampled token was never promised (a
            # resumed request re-draws it against its rebuilt KV) — drop
            # it, close the span
            if tr is not None:
                tr.end(req.rid, "prefill.host")
                tr.end(req.rid, "prefill", aborted=True)
            return
        with self._span("serve.harvest.wait", kind="prefill", rid=req.rid):
            req.key = np.asarray(rec["key"])
            tok0 = int(np.asarray(rec["tok"])[0])          # blocks until the device delivers
        req.first_token_t = self.scheduler.clock()         # TTFT = token availability, not dispatch
        with self._span("serve.harvest.emit"):
            if tr is not None:
                tr.end(req.rid, "prefill.host")
                tr.end(req.rid, "prefill", compile=req.prefill_compiled)
            self.tokens_generated += 1                     # prefill samples token 0
            if "exit" in rec:
                self._note_exit(np.asarray(rec["exit"])[0])
            if gp is not None:
                gp.commit_tokens(1)                        # token 0 streams below
            reg = registry()
            reg.counter("serving.tokens").inc()
            if pool.quantized_kv:
                # measured quantization error of THIS prefill's written blocks
                # (sum|dq-x|/sum|x| over non-sink destinations)
                reg.gauge("serving.kv_quant.rel_err").set(float(np.asarray(rec["qerr"])))
            self._emit_token(req, tok0)

    #
    # goodput / occupancy accounting helpers
    #

    def _sample_occupancy(self) -> None:
        """One ``(free, shared, leased)`` sample into the pool's bounded
        occupancy ring per harvest, mirrored into the
        ``serving.pool.occupancy_frac`` gauge."""
        self.pool.sample_occupancy()
        self._m_pool_occ.set(self.pool.utilization())

    @staticmethod
    def _sunk_positions(block_table, pos: int, n: int, bs: int) -> int:
        """How many of the real positions ``[pos, pos + n)`` route their
        KV write to the sink block (window-expired table entries — the
        replayed work is recomputed but never attended)."""
        if n <= 0:
            return 0
        sunk = 0
        for bi in range(pos // bs, -(-(pos + n) // bs)):
            b = block_table[bi] if bi < len(block_table) else SINK_BLOCK
            if b == SINK_BLOCK:
                sunk += min(pos + n, (bi + 1) * bs) - max(pos, bi * bs)
        return sunk

    def _account_prefill(self, req: Request, kind: str, pos: int,
                         n_real: int, Tb: int) -> dict:
        """Classify one prefill-family dispatch (1 row x Tb positions):
        bucket padding, sink-routed (window-expired) slots, recompute
        below the request's replay watermark, and fresh committed KV
        work.  Returns the ledger's compact tag dict."""
        bs = self.pool.block_size
        sunk = self._sunk_positions(req.block_table, pos, n_real, bs)
        replay_n = min(max(req.replay_until - pos, 0), n_real)
        win = min(sunk, replay_n)          # sunk slots inside the watermark
        extra_sunk = sunk - win            # defensive: sunk fresh writes
        cause_n = replay_n - win
        waste = {}
        if Tb > n_real:
            waste["pad_prefill"] = Tb - n_real
        if sunk:
            waste["replay_window"] = sunk
        if cause_n:
            cause = req.replay_cause or "replay_recovery"
            waste[cause] = waste.get(cause, 0) + cause_n
        return self._goodput.account(
            kind, 1, Tb, committed=n_real - replay_n - extra_sunk, **waste)

    #
    # decode
    #

    def _decode_once(self, batch: tuple | None = None) -> None:
        """One decode-lane turn: dispatch the bucketed decode program for
        the decode-ready batch; sync harvests inline, async parks the
        record in the in-flight table for the next step's harvest.
        ``batch``: what :meth:`_ahead_batch` found, for the dispatch ahead
        of the in-flight record's harvest."""
        ahead = batch is not None
        with self._span("serve.decode_dispatch") as sp:
            if self.spec is not None:
                from thunder_tpu.serving.speculative import spec_decode_dispatch

                rec = spec_decode_dispatch(self)
            else:
                parked = self.pool.n_retired
                rec = self._decode_dispatch(batch)
                if ahead:
                    # what was parked before this dispatch is all the coming
                    # harvest may drop
                    rec["parked"] = parked
                    self.decode_ahead_steps += 1
                    self.decode_through_end_steps += rec["ending"] > 0
                    self._m_steps_ahead.inc()
            # steady: last step's device outputs were this step's inputs;
            # ahead: dispatched before the host had last step's tokens;
            # ending: of its rows, those past their end by length (a step
            # ahead through an end: dead row-steps the host knew of);
            # written: the rows a rebuild wrote from Python (the others it
            # carried over from the build before)
            sp.set(rows=len(rec["running"]), bucket="{}x{}".format(*rec["bucket"]),
                   steady=rec["steady"], ahead=int(ahead), ending=rec.get("ending", 0),
                   written=rec.get("written", 0))
            if self.async_step:
                self._inflight_decode = rec
        if not self.async_step:
            self._harvest_inline(rec)

    def _decode_batch(self, running: list) -> tuple:
        """A decode batch and its signature: the rows in order, the batch
        bucket and the table-width bucket.  While it stays the same the
        chained device state feeds the next step."""
        Bb, nbb = self.scheduler.decode_bucket(running)
        return running, (tuple(r.rid for r in running), Bb, self._nbb(nbb))

    def _ahead_batch(self) -> tuple | None:
        """The batch of the next decode step where it may be dispatched
        before the in-flight step *k* is harvested, else ``None`` (today's
        order).  All of it is host state, none of it a token of step *k*:

        - a decode record is in flight and the chain it left stands
          (``_decode_state``; a speculative round leaves none);
        - the chain has steps left (``ahead``: the least of what each row
          allows, reduced from per-row arrays at the chain's rebuild,
          :meth:`_ahead_steps`, and counted down a step since: no walk over
          the rows here): before a window lets blocks go at the harvest, and
          up to **one step past the first end by length** where a row of the
          chain outlives that end; no row of it is constrained;
        - no prefill piece is in flight whose harvest would change the
          batch, and no row's deadline has passed;
        - the decode-ready rows and their buckets are the chain's (an
          eviction, a resumed row or a quarantine changes them).

        A row that ended at step *k* makes step *k+1* one dead row-step
        (``dead_scan_row``): the host knew it beforehand (by length: the
        ``ending`` rows of the dispatch, ``stats()["decode_ahead"]
        ["through_end"]``) or not (``eos_id``); either way the harvest of
        *k* drops the chain, so no chain runs two steps past an end, the
        dead row's token is never emitted, its key and ``pos`` stay where
        the end left them, and the step after is a turnover step: its
        dispatch rebuilds the chain's inputs, carrying over the rows that
        stand (:meth:`_decode_inputs`).  A chain whose every row ends at
        *k* has no step ahead left at *k*: no step runs for nobody."""
        st = self._decode_state
        if (self._inflight_decode is None or st is None or st["ahead"] <= 0
                or self._inflight_prefill):
            return None
        if st["deadline"] is not None and self.scheduler.clock() >= st["deadline"]:
            return None
        running = self.scheduler.decode_ready()
        if not running:
            return None
        batch = self._decode_batch(running)
        return batch if batch[1] == st["sig"] else None

    @staticmethod
    def _row_facts(r: Request) -> tuple:
        """What a row of the decode inputs is built from, beside its first
        live block: a row whose facts stand is carried over by a rebuild."""
        return r.preemptions, len(r.generated), len(r.block_table), r.adapter_slot, r.state_slot

    def _decode_row(self, host: dict, i: int, r: Request) -> None:
        """Row ``i`` of the decode inputs, written from the request: the one
        per-row form (the full build is this for every row), and what the row
        was built from, which decides at the next rebuild whether it is
        carried over."""
        bt = r.block_table
        wpos = r.prompt_len + len(r.generated) - 1         # slot this step writes
        host["toks"][i] = r.generated[-1]
        host["host_pos"][i] = wpos
        host["tables"][i] = SINK_BLOCK
        host["tables"][i, : len(bt)] = bt
        host["keys"][i] = r.key
        host["slots"][i] = r.adapter_slot
        host["sslots"][i] = r.state_slot
        # the last position a row may write before FINISH_LENGTH
        host["stop"][i] = r.prompt_len + r.max_new_tokens - 2
        live = -1
        if self.scheduler.sliding_window is not None:
            live = next((j for j, b in enumerate(bt) if b != SINK_BLOCK), -1)
        host["facts"][i] = self._row_facts(r)
        host["live"][i] = live
        host["constrained"][i] = r.constraint is not None
        host["deadline"][i] = np.inf if r.deadline_t is None else r.deadline_t

    def _decode_inputs(self, running: list, sig: tuple, old: dict | None) -> dict:
        """The host arrays of a decode step's inputs for ``running``, and what
        each row was built from.  ``old``: the build before (``_decode_host``).

        With nothing to carry from (``old`` is None: the first step, after
        recovery; or it was built for another bucket) every row is
        written from its request (:meth:`_decode_row`): the **full build**,
        the reference.  Else the rows are mapped to the build before by rid
        (FIFO order shifts every row behind a finished one, so by request and
        not by index) and taken over in one indexed copy an array; written
        from Python are only the rows that are new or whose recorded facts
        differ from the request's: its ``preemptions`` epoch, how many tokens
        it has generated (a row the last harvest passed over), the length of
        its block table, its adapter and state slots, and under a sliding
        window whether its first live block still is one (the harvest sinks
        a prefix of the table).  ``toks``, ``host_pos`` and ``keys`` of a
        carried row are what the last harvest fetched (:meth:`_decode_emit`
        leaves them here).  Either way the arrays are, element for element,
        the full build's; padding rows keep the defaults (the base adapter
        slot, the sink state slot and block, ``stop`` -1: dead from step 0).
        A built array is never written again: the device copy may alias it.
        """
        rids, Bb, nbb = sig
        n = len(running)
        key0 = np.asarray(running[0].key)
        host = {
            "bucket": (Bb, nbb), "n": n, "at": dict(zip(rids, range(n))),
            "toks": np.zeros(Bb, dtype=np.int32),
            "host_pos": np.zeros(Bb, dtype=np.int32),
            "tables": np.full((Bb, nbb), SINK_BLOCK, dtype=np.int32),
            "keys": np.zeros((Bb, *key0.shape), dtype=key0.dtype),
            "slots": np.zeros(Bb, dtype=np.int32),
            "sslots": np.zeros(Bb, dtype=np.int32),
            "stop": np.full(Bb, -1, dtype=np.int32),
            # what each row was built from: epoch, tokens generated, table
            # length, adapter slot, state slot; its first live block under a
            # window (-1: none); whether an automaton constrains it; its deadline
            "facts": np.zeros((n, 5), dtype=np.int64),
            "live": np.full(n, -1, dtype=np.int64),
            "constrained": np.zeros(n, dtype=bool),
            "deadline": np.full(n, np.inf),
        }
        host["full"] = old is None or old["bucket"] != (Bb, nbb)
        if host["full"]:
            write = range(n)
        else:
            at = old["at"]
            src = np.fromiter((at.get(rid, -1) for rid in rids), dtype=np.intp, count=n)
            found = src >= 0
            src[~found] = 0
            facts = np.array([self._row_facts(r) for r in running], dtype=np.int64)
            keep = found & (facts == old["facts"][src]).all(axis=1)
            if self.scheduler.sliding_window is not None:
                for i in np.flatnonzero(keep).tolist():
                    live = old["live"][src[i]]
                    keep[i] = live >= 0 and running[i].block_table[live] != SINK_BLOCK
            dst = np.flatnonzero(keep)
            src = src[dst]
            for name in ("toks", "host_pos", "tables", "keys", "slots", "sslots", "stop",
                         "facts", "live", "constrained", "deadline"):
                host[name][dst] = old[name][src]
            write = np.flatnonzero(~keep).tolist()
        with self._span("serve.decode_dispatch.rebuild.write"):
            for i in write:
                self._decode_row(host, i, running[i])
        host["written"] = len(write)
        return host

    def _ahead_steps(self, host: dict) -> tuple[int, float | None]:
        """How many steps of a chain built from ``host`` may be dispatched
        ahead of the harvest before theirs (:meth:`_ahead_batch`), and the
        first deadline among its rows.  By length (``stop`` is a row's last
        write, ``host_pos`` this step's): the steps up to the first that ends
        a row, and **one more where a row outlives that end**: that step
        leaves with the ended row as a dead row-step, while the harvest that
        finishes the row runs under it.  One and never two: that harvest drops the
        chain.  None more where every row ends there: no step for nobody.
        Combined with, not in place of, what else bounds the chain: under a
        window the steps until a harvest frees a row's first live block, and
        none at all where the next dispatch needs a value from the host (a
        constrained row)."""
        n = host["n"]
        pos = host["host_pos"][:n].astype(np.int64)
        left = host["stop"][:n] - pos                      # steps before the one that ends each row
        first = int(left.min())
        ahead = first + int((left > first).any())
        W = self.scheduler.sliding_window
        if W is not None:
            # harvest m of the chain sees pos = wpos + m + 1 and frees the
            # first live block once (pos + 1 - W) // bs passes it
            live = host["live"]
            free_at = np.maximum(0, (live + 1) * self.pool.block_size + W - pos - 2)
            ahead = min(ahead, int(np.where(live >= 0, free_at, ahead).min()))
        if host["constrained"].any():
            ahead = 0
        deadline = float(host["deadline"].min())
        return ahead, (None if deadline == np.inf else deadline)

    def _decode_dispatch(self, batch: tuple | None = None) -> dict:
        sch, pool = self.scheduler, self.pool
        running, sig = batch or self._decode_batch(
            sch.decode_ready() if self.async_step
            else list(sch.running))                        # FIFO admission order
        self._fault_point(FP_DECODE, sig[0])
        _, Bb, nbb = sig
        bs = pool.block_size
        st = self._decode_state
        steady = st is not None and st["sig"] == sig
        if steady:
            # steady state: the batch composition and tables are unchanged
            # since the last step, so this step's inputs ARE the previous
            # step's device outputs (toks=nxt, keys=new_keys, pos=pos+1)
            # plus the cached tables/slots — zero host->device transfers
            toks_d, pos_d = st["toks"], st["pos"]
            tables_d, keys_d, slots_d = st["tables"], st["keys"], st["slots"]
            sslots_d = st.get("sslots")
            host_pos = st["host_pos"] + 1
            ahead_left, deadline = st["ahead"] - 1, st["deadline"]
            host = self._decode_host
        else:
            # a rebuild: the rows the build before still holds are carried
            # over by request, the others written (_decode_inputs).  The
            # host arrays are this call's operands as they are (the call
            # copies them in on its way to the device); what the chained
            # steps reuse of them goes to the device after it, under the step
            with self._span("serve.decode_dispatch.rebuild") as sp:
                host = self._decode_inputs(running, sig, self._decode_host)
                ahead_left, deadline = self._ahead_steps(host)
                sp.set(full=int(host["full"]))
            counts = self._rebuilds
            counts["rebuilds"] += 1
            counts["full"] += host["full"]
            counts["rows_written"] += host["written"]
            counts["rows_carried"] += host["n"] - host["written"]
            toks_d, pos_d = host["toks"], host["host_pos"]
            tables_d, keys_d, slots_d = host["tables"], host["keys"], host["slots"]
            sslots_d = host["sslots"] if self._hybrid else None
            host_pos = host["host_pos"]
        # constrained decoding: the per-row token masks are fresh host data
        # every dispatch (the automata advanced at the last harvest) — an
        # argument beside the chained device state, never part of it
        cmask_d = None
        if self._constraints:
            shape = (Bb, self._vocab)
            if any(r.constraint is not None for r in running):
                m = np.ones(shape, dtype=bool)
                for i, r in enumerate(running):
                    if r.constraint is not None:
                        m[i] = r.constraint.mask()
                cmask_d = jnp.asarray(m)
            else:
                cmask_d = self._ones_mask(shape)
        kind = "decode_paged"
        prog, compiled = self._program(kind, Bb, nbb)
        lora_arenas = self._lora_arenas()
        if self.mesh is not None and self._mesh_collectives is None:
            # census BEFORE the call: the arenas are donated by it
            ex = (self.params, toks_d, pos_d, tables_d, pool.arenas,
                  keys_d, lora_arenas, slots_d)
            if cmask_d is not None:
                ex = ex + (cmask_d,)
            self._mesh_collectives = self._collective_census(
                (kind, Bb, nbb), prog, ex,
            )
        self._note_attn_step()
        # rows this step writes past their last position: none but in the
        # step ahead through an end (_ahead_steps), where they hold no request
        # any more and count for none in the batch's occupancy
        past = host["stop"][:host["n"]] < host_pos[:host["n"]]
        ending = int(past.sum())
        if self._attended is not None:
            # the keys a layer of each kind attends this step (one token a row), over the
            # rows that hold a request: a window layer the last layer_window, the others all
            seen = np.asarray(host_pos, dtype=np.int64)[:len(running)][~past] + 1
            self._attended["full_attention"] += int(seen.sum())
            if "sliding_attention" in self._attended:
                self._attended["sliding_attention"] += int(np.minimum(seen, self.cfg.layer_window).sum())
            self._attended["steps"] += 1
        if self._goodput is not None and self._attn_path != "xla":
            # ragged-decode visibility: the bucket's tables span Bb x nbb
            # blocks per step but the kernel's walk streams only each row's
            # live range — per-row ceil(pos / bs) clamped to [1, nbb]
            # (padding rows collapse to one block, the sink); host ints
            # only, the dispatch itself is untouched
            hp = np.asarray(host_pos, dtype=np.int64)
            real = int(np.minimum(np.maximum(-(-hp // bs), 1), nbb).sum())
            self._goodput.note_blocks(kind, Bb * nbb, real)
        if batch is None:                   # ahead: once the step before is harvested
            self._trace_decode_begin(running, self.decode_steps, compiled, [Bb, nbb])
        call_args = (self.params, toks_d, pos_d, tables_d, pool.arenas,
                     keys_d, lora_arenas, slots_d)
        if self._moe_rows is not None:
            call_args = call_args + (self._moe_rows,)
        if sslots_d is not None:
            call_args = call_args + (sslots_d,)
        if cmask_d is not None:
            call_args = call_args + (cmask_d,)
        with self._span("serve.decode_dispatch.call"), \
                self._compile_span(compiled, kind, Bb, nbb):
            outs = prog(*call_args)
        nxt, new_keys, new_pos, arenas, *sums = outs
        exit_rows = sums.pop() if self._passes > 1 else None
        if sums:
            self._moe_rows = sums[0]
        # past the point of no return: the call consumed the donated arenas
        self._fault_point(FP_SCATTER, sig[0])
        pool.set_arenas(arenas)
        if not steady:
            # the operands the chain's later steps take as they are: one
            # transfer, while the device runs the step just dispatched
            with self._span("serve.decode_dispatch.put"):
                tables_d, slots_d, sslots_d = jax.device_put((tables_d, slots_d, sslots_d))
        self._decode_host = host
        self._decode_state = {
            "sig": sig, "toks": nxt, "pos": new_pos, "tables": tables_d,
            "keys": new_keys, "slots": slots_d, "host_pos": host_pos,
            "ahead": ahead_left, "deadline": deadline,
            **({"sslots": sslots_d} if sslots_d is not None else {}),
        }
        rec = {"kind": "decode", "running": running, "rids": sig[0], "nxt": nxt,
               "new_keys": new_keys, "pos": host_pos, "bucket": [Bb, nbb],
               "pkind": kind, "compiled": compiled, "step": self.decode_steps,
               "steady": steady, "host": host,
               "written": 0 if steady else host["written"],
               "ending": ending,
               "epochs": [r.preemptions for r in running],
               "t_disp": time.perf_counter(), "t_clock": sch.clock(),
               **({"exit": exit_rows} if exit_rows is not None else {})}
        self.decode_steps += 1
        self._occupancy_sum += len(running) - ending
        self._m_steps_decode.inc()
        self._m_occupancy.observe(len(running) - ending)
        return rec

    def _note_exit(self, row) -> None:
        """One served token of a looped model: ``row`` is ``[pass chosen, p_0, ..., p_last]``
        as its program returned it (``_exit_rows``)."""
        self._pass_sums["exit"][int(row[0])] += 1
        self._pass_sums["exit_mass"] += row[1:]

    def _note_attn_step(self) -> None:
        """One decode (or verify) dispatch: a fallback step where its attention
        call is the XLA form."""
        if self._attn_path == "xla":
            self.attn_fallback_steps += 1
            self._m_attn_fallback.inc()

    def _trace_decode_begin(self, running: list, step: int, compiled: bool, bucket: list) -> None:
        """Opens the rows' ``decode`` spans of one decode dispatch (its
        harvest closes them): at the dispatch, or, for a record dispatched
        ahead, once the step before it is harvested — the device begins it
        then, and a row has one ``decode`` span open at a time."""
        tr = self._tracer
        if tr is not None:
            for r in running:
                tr.begin(r.rid, "decode", step=step,
                         compile=compiled, bucket=bucket, lane="decode",
                         attn=self._attn_path)

    def _decode_harvest(self, rec: dict) -> None:
        if rec.get("spec"):
            from thunder_tpu.serving.speculative import spec_decode_harvest

            return spec_decode_harvest(self, rec)
        running = rec["running"]
        self._fault_point(FP_HARVEST, rec["rids"])
        t0 = time.perf_counter()
        with self._span("serve.harvest.wait", kind="decode", rows=len(running)):
            # the host block: (Bb,) tokens and keys
            nxt, new_keys = np.asarray(rec["nxt"]), np.asarray(rec["new_keys"])
            if "exit" in rec:
                rec["exit"] = np.asarray(rec["exit"])
        stall = time.perf_counter() - t0
        if self._inflight_decode is not None:
            # a record dispatched ahead of this harvest: the device took it up
            # when this one's step ended, so its share of the overlap
            # accounting begins here and not at its dispatch
            self._inflight_decode["t_dev"] = t0 + stall
        with self._span("serve.harvest.emit"):
            self._decode_emit(rec, t0, stall, nxt, new_keys)

    def _decode_emit(self, rec: dict, t0: float, stall: float, nxt, new_keys) -> None:
        sch = self.scheduler
        running = rec["running"]
        if self.async_step:
            # overlap accounting: host work since dispatch (since the device
            # took the step up, where it was dispatched ahead) vs the
            # residual device wait the materialization just paid
            overlapped = t0 - rec.get("t_dev", rec["t_disp"])
            frac = overlapped / (overlapped + stall) if (overlapped + stall) > 0 else 0.0
            self._stall_s_sum += stall
            self._overlap_frac_sum += frac
            self._overlap_obs += 1
            self._m_stall.observe(stall)
            self._m_overlap.set(frac)
        epochs = rec.get("epochs")
        gp, gtag = self._goodput, None
        if gp is not None:
            # exact pre-emit classification of this visit's Bb x 1 slots:
            # every non-skipped row streams exactly one token
            Bb = rec["bucket"][0]
            n_stale = n_dead = live = 0
            for i, r in enumerate(running):
                if epochs is not None and r.preemptions != epochs[i]:
                    n_stale += 1                           # preempted: chain re-derives it
                elif r.state != "running":
                    n_dead += 1                            # finished while in flight
                else:
                    live += 1
            waste = {}
            if Bb > len(running):
                waste["pad_row"] = Bb - len(running)
            if n_stale:
                waste["replay_preemption"] = n_stale
            if n_dead:
                waste["dead_scan_row"] = n_dead
            gtag = gp.account(rec["pkind"], Bb, 1, committed=live, **waste)
            gp.note_device_s(rec["pkind"],
                             time.perf_counter() - rec.get("t_dev", rec["t_disp"]))
        tr = self._tracer
        if tr is not None:                                 # tokens host-visible
            for r in running:
                tr.end(r.rid, "decode",
                       **({"goodput": gtag} if gtag is not None else {}))
        if self._flight is not None:
            self._flight.record("decode", step=rec["step"],
                                batch=len(running), bucket=rec["bucket"],
                                compiled=rec["compiled"],
                                rids=[r.rid for r in running],
                                **({"goodput": gtag}
                                   if gtag is not None else {}))
        pos = rec["pos"]
        emitted = 0
        invalidate = False
        windowed = sch.sliding_window is not None
        # Python ints once, not a numpy scalar a row
        tok_of, pos_of = nxt.tolist(), pos.tolist()
        for i, r in enumerate(running):
            if r.state != "running" or (
                    epochs is not None and r.preemptions != epochs[i]):
                # finished mid-flight (token never promised), or preempted
                # and already resumed: the resumed chain re-derives this
                # token against its rebuilt KV — applying the stale record
                # would advance the key twice
                invalidate = True
                continue
            r.key = new_keys[i]
            r.pos = pos_of[i] + 1
            released = sch.expire_window_blocks(r) if windowed else 0
            if released:
                # every registered prefix of r starts at its (just-sunk)
                # leading blocks — scrub before anyone can share them; the
                # cached device tables are stale too
                invalidate = True
                self._unregister_prefix(r)
                if self._flight is not None:
                    self._flight.record("window_expire", rid=r.rid,
                                        released=released)
            emitted += 1
            if "exit" in rec:
                self._note_exit(rec["exit"][i])
            self._emit_token(r, tok_of[i])
            if r.state != "running":
                invalidate = True                          # finished at this token
        self.tokens_generated += emitted
        self.decode_lane_tokens += emitted
        self.host_visits += 1
        self._m_host_visits.inc()
        if emitted:
            self._m_tokens.inc(emitted)
        if gp is not None:
            gp.commit_tokens(emitted)
        host = rec["host"]
        if host is self._decode_host:
            # what a rebuild carries over of the rows this harvest went
            # through (a row it passed over is a token short of its count,
            # and is written anew): the fetched arrays themselves
            host["toks"], host["keys"], host["host_pos"] = nxt, new_keys, pos + 1
            host["facts"][:, 1] += 1
        if invalidate:
            # the chained decode inputs assumed an unchanged batch/tables;
            # the next dispatch rebuilds them (_decode_inputs)
            self._decode_state = None

    #
    # finishing / results
    #

    def _emit_token(self, req: Request, tok: int) -> None:
        req.generated.append(tok)
        if req.constraint is not None:
            # the automaton advances exactly where the key chain does (at
            # harvest), so replay/resume never re-advances it
            req.constraint.advance(tok)
        if req.stream_cb is not None:
            req.stream_cb(tok)
        if self.eos_id is not None and tok == self.eos_id:
            self._finish(req, FINISH_EOS)
        elif len(req.generated) >= req.max_new_tokens:
            self._finish(req, FINISH_LENGTH)

    def _finish(self, req: Request, reason: str) -> None:
        never_admitted = req.admit_t is None
        if self._sessions is not None and req.session_id is not None:
            if reason in (FINISH_LENGTH, FINISH_EOS) and req.state == "running":
                # park the turn's block-aligned written prefix BEFORE the
                # scheduler frees the request's own references; the table
                # takes share() refs of its own, so the blocks stay leased
                self._park_session(req)
            else:
                # an abnormal turn (deadline/evicted/error) breaks the
                # deterministic continuation contract: release the session
                self._sessions.close(req.session_id)
        self._unregister_prefix(req)                       # before blocks free
        self.scheduler.finish(req, reason)
        reg = registry()
        reg.counter("serving.requests.completed").inc()
        reg.counter(f"serving.finish.{reason}").inc()
        res = self._result(req)
        if self._tracer is not None:
            if never_admitted:                             # died in the queue
                self._tracer.end(req.rid, "queued", finish_reason=reason)
            self._tracer.instant(
                req.rid, "finish", reason=reason,
                new_tokens=len(req.generated),
                **({"session_id": req.session_id} if req.session_id else {}),
                **({"priority": req.priority_class}
                   if self._priorities is not None else {}),
                **({"constrained": True} if req.constraint is not None else {}),
                **({"error": req.error_cause.get("type")}
                   if req.error_cause else {}),
            )
        if self._flight is not None:
            self._flight.record("finish", rid=req.rid, reason=reason,
                                new_tokens=len(req.generated))
        if self._slo is not None:
            self._slo.observe(res)
        if res.ttft_s is not None:
            reg.histogram("serving.ttft_s").observe(res.ttft_s)
        if res.tpot_s is not None:
            reg.histogram("serving.tpot_s").observe(res.tpot_s)
        if res.tokens_per_sec is not None:
            reg.histogram("serving.tokens_per_sec").observe(res.tokens_per_sec)
        if req.adapter_id is not None:
            # per-tenant accounting: which adapter consumed the tokens and
            # what latency its requests saw
            reg.counter(f"serving.tenant.{req.adapter_id}.tokens").inc(len(req.generated))
            reg.counter(f"serving.tenant.{req.adapter_id}.requests").inc()
            if res.ttft_s is not None:
                reg.histogram(f"serving.tenant.{req.adapter_id}.ttft_s").observe(res.ttft_s)
            if res.e2e_s is not None:
                reg.histogram(f"serving.tenant.{req.adapter_id}.e2e_s").observe(res.e2e_s)
        if self.telemetry is not None:
            self.telemetry.log_request(
                rid=req.rid,
                prompt_tokens=req.prompt_len,
                new_tokens=len(req.generated),
                finish_reason=reason,
                ttft_s=res.ttft_s,
                tpot_s=res.tpot_s,
                tokens_per_sec=res.tokens_per_sec,
                queue_s=res.queue_s,
                e2e_s=res.e2e_s,
                prefill_compiled=req.prefill_compiled,
                shared_prefix_blocks=req.n_shared_blocks,
                session_id=req.session_id,
                priority=(req.priority_class
                          if self._priorities is not None else None),
                constrained=(True if req.constraint is not None else None),
                preemptions=(req.preemptions or None),
                error=req.error_cause,
                tokens_recomputed=(req.tokens_recomputed or None),
                recompute_causes=(list(req.recompute_causes)
                                  if req.recompute_causes else None),
            )

    def _park_session(self, req: Request) -> None:
        """Park the finished turn's written block-aligned prefix.

        The resident KV covers positions ``[0, req.pos)`` of the full
        served sequence (prompt + generated; the last emitted token's KV
        is never written — it was sampled, not forwarded).  Sliding-window
        expiry may have sunk leading blocks, which truncates the parkable
        prefix to nothing (the park helper stops at the first sink)."""
        full = np.concatenate(
            [np.asarray(req.prompt, dtype=np.int64),
             np.asarray(req.generated, dtype=np.int64)])
        bs = self.pool.block_size
        nblk = min(req.pos // bs, len(req.block_table))
        entry = self._sessions.park(
            req.session_id, full[:nblk * bs], req.block_table[:nblk],
            adapter_slot=req.adapter_slot, full_pos=req.pos)
        if self._flight is not None:
            self._flight.record(
                "session_park", rid=req.rid, session_id=req.session_id,
                blocks=(len(entry.blocks) if entry is not None else 0),
                resident_blocks=self._sessions.resident_blocks)

    def close_session(self, session_id: str) -> int:
        """Release a session's parked blocks; returns how many were freed
        (0 when the session is unknown — closing twice is a no-op)."""
        if self._sessions is None:
            return 0
        freed = self._sessions.close(session_id)
        if freed and self._flight is not None:
            self._flight.record("session_close", session_id=session_id,
                                blocks=freed)
        return freed

    def session_resident(self, session_id: str) -> bool:
        """Does this engine's table hold the session's blocks?  (The dp
        router's session-affinity probe.)"""
        return self._sessions is not None and self._sessions.resident(session_id)

    def _ones_mask(self, shape: tuple) -> jnp.ndarray:
        """Cached device-resident all-``True`` constraint mask — the
        no-op mask unconstrained rows ride through a constrained program
        (``where(True, logits, -inf)`` is the identity, bit-exactly)."""
        m = self._mask_ones.get(shape)
        if m is None:
            m = jnp.ones(shape, dtype=bool)
            self._mask_ones[shape] = m
        return m

    def _result(self, req: Request) -> RequestResult:
        n = len(req.generated)
        ttft = (req.first_token_t - req.submit_t) if req.first_token_t is not None else None
        tpot = None
        tps = None
        if req.first_token_t is not None and req.finish_t is not None and n > 1:
            span = max(req.finish_t - req.first_token_t, 0.0)
            tpot = span / (n - 1)
        if req.finish_t is not None and n and (req.finish_t - req.submit_t) > 0:
            tps = n / (req.finish_t - req.submit_t)
        return RequestResult(
            rid=req.rid,
            prompt=req.prompt,
            new_tokens=tuple(req.generated),
            finish_reason=req.finish_reason or "?",
            ttft_s=ttft,
            tpot_s=tpot,
            tokens_per_sec=tps,
            queue_s=(req.admit_t - req.submit_t) if req.admit_t is not None else None,
            e2e_s=(req.finish_t - req.submit_t) if req.finish_t is not None else None,
            shared_prefix_blocks=req.n_shared_blocks,
            prefill_compiled=req.prefill_compiled,
            error=req.error_cause,
            tokens_recomputed=req.tokens_recomputed,
            recompute_causes=tuple(req.recompute_causes),
        )

    def _update_gauges(self) -> None:
        self._m_queue_depth.set(len(self.scheduler.queue))
        self._m_running.set(len(self.scheduler.running))
        self._m_pool_util.set(self.pool.utilization())
        self._m_pool_free.set(self.pool.num_free)
        # the post-mortem capacity floor: how close the pool ever came to
        # exhaustion (also in the flight-recorder pool snapshot)
        self._m_pool_low_water.set(self.pool.free_blocks_low_water)
        if self._hybrid:
            self._m_state_leased.set(self.pool.state.leased)
            self._m_state_low_water.set(self.pool.state.free_low_water)

    #
    # fault containment + re-prefill recovery
    #

    def _fault_point(self, point: str, rids: Sequence[int] = ()) -> None:
        """One injectable fault point (unarmed engines pay one ``is None``
        test — the compiled programs never see the plan)."""
        if self._faults is not None:
            self._faults.check(point, rids)

    def _absorb_fault(self, exc: Exception) -> bool:
        """Blast-radius containment for one classified step exception.
        Returns False for anything the recovery layer must not absorb
        (programming errors keep the crash-dump-and-raise contract).

        - **request** class: quarantine the offending rids (finish with
          ``"error"`` + structured cause, blocks freed, prefix scrubbed)
          and keep serving; a harvest/scatter fault additionally recovers
          (the step's tokens / donated arenas are already lost);
        - **transient** class: bounded retry with exponential backoff on
          the policy's injectable sleep; a *donated* failure (scatter /
          harvest) may have consumed its inputs, so it routes through
          recovery instead of re-submitting stale handles; retry
          exhaustion escalates to recovery;
        - **engine** class (OOM / hang / watchdog): straight to recovery.
        """
        cls = classify_fault(exc)
        if cls is None:
            return False
        cause = fault_cause(exc)
        point = cause.get("point")
        reg = registry()
        reg.counter("serving.faults.observed").inc()
        if self._flight is not None:
            self._flight.record("fault", fault_class=cls, cause=cause,
                                rids=cause.get("rids", []))
        lossy = point in (FP_HARVEST, FP_SCATTER)
        if cls == CLASS_REQUEST:
            for rid in cause.get("rids", ()):
                self._quarantine(rid, cause)
            if lossy:
                self._recover(cause)
        elif cls == CLASS_TRANSIENT:
            self._retry_streak += 1
            if self._retry_streak > self._retry.max_retries:
                self._retry_streak = 0
                self._recover(cause)
            else:
                reg.counter("serving.faults.retries").inc()
                self._retry.sleep(self._retry.backoff(self._retry_streak))
                if lossy:
                    self._recover(cause)
        else:
            self._recover(cause)
        return True

    def _quarantine(self, rid: int, cause: dict) -> None:
        """Finishes one poisoned request with ``finish_reason="error"`` and
        the structured cause; its blocks free and its prefix-index entries
        scrub through the normal ``_finish`` path, so the rest of the batch
        keeps serving."""
        req = next((r for r in (*self.scheduler.running, *self.scheduler.queue)
                    if r.rid == rid), None)
        if req is None or req.state == "finished":
            return
        req.error_cause = cause
        registry().counter("serving.faults.quarantined").inc()
        if self._flight is not None:
            self._flight.record("quarantine", rid=rid, cause=cause)
        self._finish(req, FINISH_ERROR)

    def recover(self) -> None:
        """Rebuilds the KV arenas and re-prefills every running request
        from its prompt + already-emitted tokens (the engine triggers this
        automatically on engine-class faults and retry exhaustion; it is
        public for operational use — e.g. after an external device reset).

        The recovery guarantee: a request's PRNG key advances only when a
        token is harvested, so the KV arena is *soft state* — replaying the
        already-known tokens through the sampling-free chunked-prefill
        program rebuilds exactly the cache an uninterrupted run would hold,
        and every subsequent draw is bit-identical."""
        self._recover({"type": "manual", "point": None, "kind": None,
                       "rids": [], "injected": False,
                       "message": "engine.recover()"})

    def _recover(self, cause: dict) -> None:
        reg = registry()
        t0 = time.perf_counter()
        if self._flight is not None:
            self._flight.record("recover", cause=cause,
                                rids=[r.rid for r in self.scheduler.running])
        with self._span("serve.recover", rare=True, cause=cause.get("type")):
            self._recover_until_sound()
        self.recoveries += 1
        self._retry_streak = 0
        dt = time.perf_counter() - t0
        reg.counter("serving.faults.recoveries").inc()
        reg.histogram("serving.recovery.duration_s").observe(dt)
        if self._flight is not None:
            self._flight.record("recovered", duration_s=dt,
                                rids=[r.rid for r in self.scheduler.running])

    def _recover_until_sound(self) -> None:
        attempts = 0
        while True:
            try:
                self._recover_once()
                return
            except Exception as e:
                ecls = classify_fault(e)
                if ecls is None:
                    raise
                if ecls == CLASS_REQUEST:
                    # a poison request resurfaced during its own replay:
                    # quarantining it IS progress, so it never consumes
                    # the bounded retry budget
                    ecause = fault_cause(e)
                    for rid in ecause.get("rids", ()):
                        self._quarantine(rid, ecause)
                    continue
                attempts += 1
                if attempts > self._retry.max_retries:
                    raise RecoveryError(
                        f"re-prefill recovery failed {attempts} times "
                        f"(last: {type(e).__name__}: {e})"
                    ) from e
                self._retry.sleep(self._retry.backoff(attempts))

    def _recover_once(self) -> None:
        """One recovery attempt: drop in-flight work, rebuild fresh zeroed
        arenas (allocator state — tables, refcounts, prefix sharing — is
        host-side and survives untouched), then replay every surviving
        request's known tokens back into its own blocks.  Requests still
        waiting on token 0 reset to pos=0 and re-run the normal prefill
        path (their key was never split, so token 0 is unchanged); shared-
        prefix blocks are rewritten by every co-owner with bit-identical
        content (the forward pass is deterministic)."""
        self._discard_inflight(cause="replay_recovery")
        self.pool.rebuild_arenas()
        if self.draft_pool is not None:
            # the draft arena is soft state too: the replay below rebuilds
            # it bit-identically (every attended slot holds the draft K/V
            # of the emitted token at that position)
            self.draft_pool.rebuild_arenas()
        if self._sessions is not None:
            # parked session KV is soft state like everything else in the
            # arenas: each entry records the exact tokens its blocks hold,
            # so the chunk replay rebuilds them bit-identically and turn
            # k+1 re-attaches as if the fault never happened.  Sessions
            # replay first: running sharers then overwrite any co-owned
            # block with identical content (deterministic forward).
            for entry in self._sessions.entries():
                self._replay_seq(entry.tokens, list(entry.blocks),
                                 entry.adapter_slot, len(entry.tokens),
                                 cause="replay_recovery")
        for req in list(self.scheduler.running):
            if req.pos and not req.generated:
                # token-0 requests re-run the normal prefill path from 0:
                # the positions their admission prefill already wrote are
                # recomputation chargeable to the recovery
                req.replay_until = max(req.replay_until, req.pos)
                req.replay_cause = "replay_recovery"
            req.pos = 0
            if req.generated:
                self._replay_request(req, cause="replay_recovery")
        if not self.async_step:
            # the sync loop has no prefill lane; re-prefill token-0
            # requests inline so the next decode batch has a history row
            # for every running request
            for req in list(self.scheduler.running):
                if req.state == "running" and not req.generated:
                    self._prefill_harvest(self._prefill_dispatch(req))
                    self._release_retired()

    def _replay_request(self, req: Request, *,
                        cause: str = "replay_recovery") -> None:
        """Replays ``req``'s known sequence (prompt + all but the last
        emitted token) into its blocks through the sampling-free
        ``prefill_chunk`` program.  After the replay the written KV covers
        exactly ``[0, prompt_len + n - 1)`` — the state an uninterrupted
        run holds before its next decode step — and the key chain is
        untouched, so the next draw is bit-identical.  Window-expired
        (sunk) table entries route their writes to the sink exactly like
        live padding; the keep-mask already excludes those positions."""
        n = len(req.generated)
        seq = np.concatenate([
            req.prompt, np.asarray(req.generated[:n - 1], dtype=np.int32),
        ])
        self._replay_seq(seq, req.block_table, req.adapter_slot,
                         req.prompt_len + n - 1, req=req, cause=cause)

    def _replay_seq(self, seq, block_table, adapter_slot: int,
                    target: int, *, req: Request | None = None,
                    cause: str = "replay_recovery") -> None:
        """The chunk-replay engine under :meth:`_replay_request` and the
        resident-session recovery replay: writes KV for ``seq[:target]``
        into ``block_table`` through the sampling-free ``prefill_chunk``
        programs, one fenced bucket-wide piece at a time."""
        sch, pool = self.scheduler, self.pool
        bs = pool.block_size
        seq = np.asarray(seq, dtype=np.int32)
        aligned = [t for t in sch.prefill_buckets if t % bs == 0]
        piece = max(aligned) if aligned else sch.prefill_buckets[-1]
        if getattr(self.cfg, "learned_pos_embedding", False):
            # suffix resume is off the table for learned-pos models (the
            # wpe dynamic_slice clamps past its rows); their capacity is
            # capped at cfg.block_size, so one piece from 0 always fits
            piece = max(piece, target)
        pos = 0
        while pos < target:
            t_disp = time.perf_counter() if self._goodput is not None else 0.0
            n_real = min(target - pos, piece)
            Tb = sch.prefill_bucket(n_real)
            nbb = self._nbb(max(len(block_table), -(-(pos + Tb) // bs)))
            toks = np.zeros(Tb, dtype=np.int32)
            toks[:n_real] = seq[pos:pos + n_real]
            table, dest = chunk_tables(block_table, pos, Tb, nbb, bs)
            if self.spec is not None:
                # the draft forward is deterministic, so the replay rebuilds
                # the draft arena bit-identically alongside the target's
                prog, _compiled = self._program("spec_prefill_chunk", Tb, nbb)
                arenas, darenas, qerr = prog(
                    self.params, self.spec.draft_params,
                    jnp.asarray(toks)[None], jnp.int32(pos),
                    pool.arenas, self.draft_pool.arenas,
                    jnp.asarray(table), jnp.asarray(dest),
                    self._lora_arenas(),
                    jnp.asarray([adapter_slot], dtype=jnp.int32),
                )
                self.draft_pool.set_arenas(darenas)
            else:
                prog, _compiled = self._program(self._chunk_kind(), Tb, nbb)
                arenas, qerr = prog(
                    self.params, jnp.asarray(toks)[None], jnp.int32(pos),
                    pool.arenas, jnp.asarray(table), jnp.asarray(dest),
                    self._lora_arenas(),
                    jnp.asarray([adapter_slot], dtype=jnp.int32),
                    *self._state_args(req.state_slot if req is not None else 0, n_real),
                )
            pool.set_arenas(arenas)
            if req is not None:
                # every real position of a replay piece is recomputation
                req.tokens_recomputed += n_real
                if cause not in req.recompute_causes:
                    req.recompute_causes.append(cause)
            gp = self._goodput
            if gp is not None:
                # replay pieces never stream: real positions are the given
                # replay cause, except sink-routed (window-expired) slots
                kind = ("spec_prefill_chunk" if self.spec is not None
                        else self._chunk_kind())
                sunk = self._sunk_positions(block_table, pos, n_real, bs)
                waste = {}
                if Tb > n_real:
                    waste["pad_prefill"] = Tb - n_real
                if sunk:
                    waste["replay_window"] = sunk
                if n_real > sunk:
                    waste[cause] = waste.get(cause, 0) + (n_real - sunk)
                gp.account(kind, 1, Tb, committed=0, **waste)
            pos = pos + n_real
            if req is not None:
                req.pos = pos
            float(np.asarray(qerr))        # fence this piece before the next
            if gp is not None:
                gp.note_device_s(
                    "spec_prefill_chunk" if self.spec is not None
                    else self._chunk_kind(), time.perf_counter() - t_disp)
            self._release_retired()
            self.chunk_runs += 1
            registry().counter("serving.steps.prefill_chunk").inc()

    def _discard_inflight(self, cause: str = "dead_scan_row") -> None:
        """Drops every in-flight future record (their tokens were never
        promised) plus the parked donated-arena handles: recovery and
        ``shutdown()`` must not leak futures or retired handles past the
        engine's life.  The derefs may block briefly until the consuming
        executions finish — this is the slow path, correctness over
        overlap.  ``cause`` classifies the discarded decode dispatch's
        device slots in the goodput ledger (``replay_recovery`` from
        recovery; the ``dead_scan_row`` default from shutdown)."""
        rec, self._inflight_decode = self._inflight_decode, None
        tr = self._tracer
        if rec is not None and tr is not None:
            for r in rec["running"]:
                tr.end(r.rid, "decode", aborted=True)
        gp = self._goodput
        if gp is not None and rec is not None:
            # the dispatch ran on device but will never be harvested: every
            # slot is waste (prefill pieces were accounted at dispatch)
            Bb = rec["bucket"][0]
            if rec.get("spec"):
                K = self.spec.K
                gp.account("draft_decode", Bb, K, **{cause: Bb * K})
                gp.account(rec["vkind"], Bb, K + 1, **{cause: Bb * (K + 1)})
            else:
                gp.account(rec["pkind"], Bb, 1, **{cause: Bb})
        pending, self._inflight_prefill = self._inflight_prefill, []
        if tr is not None:
            for prec in pending:
                tr.end(prec["req"].rid, prec["span"], aborted=True)
        self._decode_state = None
        self._decode_host = None
        self._spec_state = None
        self._release_retired()

    #
    # compiled bucket programs
    #

    def _lora_arenas(self) -> dict:
        """The registry's stacked factor arenas as a program argument
        ({} without a registry — an empty pytree, zero buffers).  Fetched
        per call so registrations/evictions land without recompiling."""
        return self._registry.arenas if self._registry is not None else {}

    def _static_key(self) -> tuple:
        """Global program-cache key for everything baked into a bucket
        program besides its bucket dims.  Mesh
        engines extend the key with the mesh fingerprint (axis layout +
        device ids), so programs compile once per (mesh, bucket) and a
        different device set never reuses a stale placement.  The LoRA
        component is the registry *geometry* only — adapter ids and factor
        values are program arguments, so a batch mixing tenants can never
        grow the program set."""
        import dataclasses

        from thunder_tpu.executors.pallasex import paged_available

        return (
            tuple(sorted(dataclasses.asdict(self.cfg).items())),
            self.pool.block_size, str(self.pool.dtype), str(self.pool.kv_dtype),
            self.temperature, self.quantized,
            self._registry.geometry if self._registry is not None else None,
            self._mesh_key,
            # the speculative component: K, the draft architecture, and the
            # draft arena's storage dtype are baked into every spec program
            # (draft params are arguments)
            (self.spec.K,
             str(self.draft_pool.kv_dtype),
             tuple(sorted(dataclasses.asdict(self.spec.draft_cfg).items())))
            if self.spec is not None else None,
            # constrained decoding: one boolean knob — schemas/automata are
            # mask ARGUMENTS (the LoRA idiom), so program identity never
            # sees a grammar; off collapses to None for cache sharing
            "constrained" if self._constraints else None,
            # the paged programs are built with or without Pallas (a kernel, or
            # its XLA form): which one is the program's
            paged_available(),
        )

    def _program(self, kind: str, a: int, b: int) -> tuple[Callable, bool]:
        """The bucket program for ``(kind, a, b)`` plus whether THIS lookup
        built it fresh — i.e. the imminent call pays the XLA compile (a
        cached program, per-engine or module-wide, was already traced and
        compiled by its first caller)."""
        key = (kind, a, b)
        prog = self._programs.get(key)
        if prog is not None:
            return prog, False
        gkey = (self._static_key(), kind, a, b)
        prog = _program_cache.get(gkey)
        compiled = prog is None
        if compiled and self._perkind and kind not in ("prefill_fresh", "decode_paged"):
            raise NotImplementedError(
                f"program kind {kind!r} is not built for per-kind caches (config {getattr(self.cfg, 'name', '?')!r}): "
                "a whole prompt at position 0 (prefill_fresh) and one token a row (decode_paged) are")
        if compiled:
            if kind in ("spec_prefill", "spec_prefill_chunk", "draft_decode", "verify_paged"):
                from thunder_tpu.serving import speculative as _spec_mod

                build = partial({
                    "spec_prefill": _spec_mod.build_spec_prefill,
                    "spec_prefill_chunk": _spec_mod.build_spec_prefill_chunk,
                    "draft_decode": _spec_mod.build_draft_decode,
                    "verify_paged": _spec_mod.build_verify_paged,
                }[kind], self)
            else:
                build = {"prefill": self._build_prefill,
                         "prefill_fresh": partial(self._build_prefill, fresh=True),
                         "prefill_chunk": self._build_prefill_chunk,
                         "prefill_chunk_paged": self._build_prefill_chunk_paged,
                         "decode_paged": self._build_decode_paged,
                         }[kind]
            prog = build(a, b)
            # a genuinely new program for this geometry: count the compile
            self.compile_counts[kind] += 1
            self._compile_log.append({"kind": kind, "bucket": [a, b],
                                      "cause": f"new {kind} geometry"})
            registry().counter(f"serving.compiles.{kind}").inc()
            # LRU-ish bound (the _generate_cache idiom).  64, not 32: a
            # multi-tenant deployment legitimately runs several static
            # configs at once (f32 + int8 pools, per-registry-geometry
            # LoRA variants), and evicting a live config's programs
            # re-pays its compiles on the next request
            if len(_program_cache) >= 64:
                _program_cache.pop(next(iter(_program_cache)))
            _program_cache[gkey] = prog
        self._programs[key] = prog
        return prog, compiled

    def _jit_kwargs(self, kind: str) -> dict:
        """Extra ``jax.jit`` kwargs for a bucket program: empty single-
        device; explicit in/out shardings under a mesh (params as placed,
        arenas per the pool's NamedSharding, host arrays replicated) so the
        compiled program is pjit-partitioned with per-shard arena donation."""
        if self.mesh is None:
            return {}
        from thunder_tpu.serving.mesh import program_shardings

        if self.spec is not None:
            return program_shardings(
                kind, self.params, self.mesh, self.pool.arena_sharding,
                draft_params=self.spec.draft_params,
                draft_arena_sh=self.draft_pool.arena_sharding,
            )
        kw = program_shardings(kind, self.params, self.mesh, self.pool.arena_sharding)
        if self._constraints and kind in ("prefill", "prefill_fresh", "decode_paged"):
            # the trailing constraint-mask argument is replicated like every
            # other small host-built per-step array
            from jax.sharding import NamedSharding, PartitionSpec

            repl = NamedSharding(self.mesh, PartitionSpec())
            kw["in_shardings"] = (*kw["in_shardings"], repl)
        return kw

    def _collective_census(self, bucket_key: tuple, prog, example_args) -> dict:
        """Collective count of one compiled decode program (mesh mode):
        how many cross-device ops one token step costs.  The census is an
        extra AOT compile, so it is cached module-wide next to the program
        cache — one census per (mesh, static config, bucket) per process —
        and mirrored into the ``serving.mesh.collectives.decode`` gauge."""
        gkey = ("collectives", self._static_key(), *bucket_key)
        got = _collectives_cache.get(gkey)
        if got is None:
            from thunder_tpu.serving.mesh import collective_counts

            got = _collectives_cache[gkey] = collective_counts(prog, *example_args)
        registry().gauge("serving.mesh.collectives.decode").set(got.get("total", 0))
        return got

    def _fwd_kwargs(self, lora_arenas, slots) -> dict:
        """The forward kwargs one bucket step adds on top of the base call:
        weight quantization (``quantized=``, PR-era int8 matmuls) plus the
        per-request LoRA factors gathered by slot — called inside the jit
        trace, so the gather is part of the compiled step."""
        kw = {"quantized": self.quantized}
        if self._registry is not None:
            kw["lora"] = gather_adapter_slots(lora_arenas, slots)
            kw["lora_scaling"] = self._registry.scaling
        return kw

    @scope("mixer/cache")
    def _dense_cache(self, arenas, tables, cdtype) -> dict:
        """The rows' blocks as the dense cache ``forward_with_cache`` takes
        (inside a program): K and V, dequantised from a quantised pool, or a
        latent-attention model's one ``latent``."""
        if self._latent:
            return {"latent": gather_rows(arenas["latent"], tables)}
        if self.pool.quantized_kv:
            kd, vd = gather_dense_q(
                arenas["k"], arenas["v"], arenas["k_scale"], arenas["v_scale"], tables, cdtype)
        else:
            kd, vd = gather_dense(arenas["k"], arenas["v"], tables, self.pool.lane_pack)
        return {"k": kd, "v": vd}

    @scope("mixer/cache")
    def _blocks_back(self, arenas, cache, dest, ring=None) -> tuple[dict, Any]:
        """A one-row dense cache's blocks back into their arenas at ``dest``
        (inside a program): the arenas written, and the quantisation error
        measured (0 for a pool stored at the compute dtype).  ``ring`` (a
        model with sliding_attention layers): the prompt's ``(state slot,
        n_real)``; those layers' last blocks go to the slot's ring
        (``kv_pool.ring_dest``), the other layers' to ``dest``."""
        cfg = self.cfg
        if getattr(cfg, "ring_layers", ()):
            sslot, n_real = ring
            bs, n_ring = self.pool.block_size, self.pool.state.ring_blocks
            # a kind's layers of the dense cache, by slices (an index array would gather the cache)
            of = lambda a, layers: jnp.stack([a[cfg.kv_layers.index(i)] for i in layers])  # noqa: E731
            n = min(n_ring, cache["k"].shape[3] // bs)
            start, rdest = ring_dest(sslot[0], n_real, n, n_ring, bs)
            out = {}
            for name in ("k", "v"):
                # a window layer's last blocks are cut out of the layer's own K (or V) before the layers are
                # stacked: the rest of a long prompt's keys is dead once its layer has attended them
                last = jnp.stack([jax.lax.dynamic_slice_in_dim(cache[name][cfg.kv_layers.index(i)], start * bs, n * bs, axis=2)
                                  for i in cfg.ring_layers])
                out[name + "_ring"] = scatter_blocks(arenas[name + "_ring"], last, rdest)
                out[name] = scatter_blocks(arenas[name], of(cache[name], cfg.paged_kv_layers), dest)
            return out, jnp.float32(0.0)
        if self._latent:
            return {"latent": scatter_blocks(arenas["latent"], cache["latent"], dest)}, jnp.float32(0.0)
        if self.pool.quantized_kv:
            k_arena, k_scale, k_err = scatter_blocks_q(
                arenas["k"], arenas["k_scale"], cache["k"], dest)
            v_arena, v_scale, v_err = scatter_blocks_q(
                arenas["v"], arenas["v_scale"], cache["v"], dest)
            return ({"k": k_arena, "v": v_arena, "k_scale": k_scale, "v_scale": v_scale},
                    0.5 * (k_err + v_err))
        return ({"k": scatter_blocks(arenas["k"], cache["k"], dest),
                 "v": scatter_blocks(arenas["v"], cache["v"], dest)}, jnp.float32(0.0))

    def _build_prefill(self, Tb: int, nbb: int, *, fresh: bool = False) -> Callable:
        """The program of a prompt's last piece: forward, sample token 0
        (splitting the key as solo ``generate()`` does), write the K/V out.

        ``fresh``: the ``prefill_fresh`` kind, a whole prompt at position 0.
        There is nothing before it, so the program takes neither ``pos`` nor a
        table and reads no arena: the forward gets the Python integer 0 (the
        prompt attends its own keys, ``generate._attn_with_cache``), the state
        starts from zeros, and ``dest`` names the ``nbb`` blocks the bucket's
        ``Tb`` positions fill (a cache as wide as the window reads as a ring
        there: for a prompt at 0 that fills it, the same slots).  Everything
        else is the ``prefill`` kind's.

        The forward projects row ``n_real - 1`` alone onto the vocabulary
        (``logits_at``) and, under a mesh, keeps the prompt's attention off the
        flash kernel (``sharded``)."""
        cfg, temp = self.cfg, self.temperature
        hybrid = self._hybrid
        state_heads = self.pool.state.state_heads if hybrid else 0
        cdtype = jnp.dtype(self.pool.dtype)
        cap = self.pool.capacity_tokens(nbb)
        # a fresh program's table is as wide as its bucket; its rope table is
        # built at the table width that holds it all the same, a shape the
        # other kinds' are built at: a bucket adds no eager program of its own
        cos_all, sin_all = build_rope_cache(
            cfg, self.pool.capacity_tokens(self._nbb(nbb)) if fresh else cap)
        sharded = self.mesh is not None

        def run(params, toks, pos, n_real, arenas, table, dest, key, lora, slot, cmask):
            held, more = {}, {}
            with scope("mixer/cache"):
                if fresh:
                    zeros = jnp.zeros(self.pool.dense_shape(1, nbb), cdtype)
                    dense = {name: zeros for name in (("latent",) if self._latent else ("k", "v"))}
                else:
                    dense = self._dense_cache(arenas, table[None, :], cdtype)
                if hybrid:
                    # the request's state slot rides before the constraint mask;
                    # a prompt's first piece starts from zeros, whatever the slot's
                    # last owner left, and the padded tail leaves the state alone
                    sslot, cmask = cmask[0], cmask[1:]
                    if fresh:       # zeros without a read
                        held = {name: jnp.zeros(shape, arenas[name].dtype)
                                for name, shape in state_shapes(cfg, 1).items()}
                    else:
                        held = gather_state(arenas, sslot, jnp.reshape(pos == 0, (1,)), state_heads)
                    more = {"n_real": n_real}
            exits = [] if self._passes > 1 else None
            logits, cache = forward_with_cache(
                params, toks, pos, {**dense, **held}, cos_all, sin_all, cfg,
                **self._fwd_kwargs(lora, slot), **more, logits_at=n_real - 1, sharded=sharded, exits=exits,
            )
            with scope("head/sample"):
                last = logits[:, 0]
                if cmask:
                    last = jnp.where(cmask[0], last, -jnp.inf)
                key, sub = jax.random.split(key)
            tok = sample_token(last, temp, sub)            # (1,) — solo-prefill parity
            with scope("mixer/cache"):
                kept = scatter_state(arenas, cache, sslot, state_heads) if hybrid else {}
            written, qerr = self._blocks_back(arenas, cache, dest, ring=(sslot, n_real) if hybrid else None)
            if exits:       # a looped model: the pass the head read and the exit probabilities, with the token
                chosen, p = exits[0]
                return tok, {**written, **kept}, key, qerr, _exit_rows(chosen[:, 0], p[:, :, 0])
            return tok, {**written, **kept}, key, qerr

        if fresh:
            @partial(jax.jit, donate_argnums=(3,), **self._jit_kwargs("prefill_fresh"))
            def prefill_fresh(params, toks, n_real, arenas, dest, key, lora, slot, *cmask):
                return run(params, toks, 0, n_real, arenas, None, dest, key, lora, slot, cmask)

            return prefill_fresh

        # Constrained engines pass one trailing ``(1, V)`` bool mask; plain
        # engines pass nothing, so the traced program (and its module-cache
        # entry) is byte-identical to a pre-constraints engine.
        @partial(jax.jit, donate_argnums=(4,), **self._jit_kwargs("prefill"))
        def prefill(params, toks, pos, n_real, arenas, table, dest, key, lora, slot,
                    *cmask):
            return run(params, toks, pos, n_real, arenas, table, dest, key, lora, slot, cmask)

        return prefill

    def _build_prefill_chunk(self, Tb: int, nbb: int) -> Callable:
        """An intermediate chunked-prefill piece: writes the chunk's KV into
        the arenas and nothing else — no sampling, no key split (the final
        ``prefill`` piece does both, so the request's draw stays
        bit-identical to an unchunked prefill).  The logits head is traced
        but unused, so XLA dead-code-eliminates the lm_head matmul — a
        chunk is strictly cheaper than a same-width prefill."""
        cfg = self.cfg
        hybrid = self._hybrid
        state_heads = self.pool.state.state_heads if hybrid else 0
        cdtype = jnp.dtype(self.pool.dtype)
        cap = self.pool.capacity_tokens(nbb)
        cos_all, sin_all = build_rope_cache(cfg, cap)

        # a model that keeps a recurrent state hands two things more: the
        # request's state slot and how many of the piece's tokens are real
        @partial(jax.jit, donate_argnums=(3,), **self._jit_kwargs("prefill_chunk"))
        def prefill_chunk(params, toks, pos, arenas, table, dest, lora, slot, *state_args):
            dense = self._dense_cache(arenas, table[None, :], cdtype)
            held, more = {}, {}
            if hybrid:
                sslot, n_real = state_args
                held = gather_state(arenas, sslot, jnp.reshape(pos == 0, (1,)), state_heads)
                more = {"n_real": n_real}
            _logits, cache = forward_with_cache(
                params, toks, pos, {**dense, **held}, cos_all, sin_all, cfg,
                **self._fwd_kwargs(lora, slot), **more,
            )
            kept = scatter_state(arenas, cache, sslot, state_heads) if hybrid else {}
            written, qerr = self._blocks_back(arenas, cache, dest)
            return {**written, **kept}, qerr

        return prefill_chunk

    def _build_prefill_chunk_paged(self, Tb: int, nbb: int) -> Callable:
        """The kernel twin of :meth:`_build_prefill_chunk`: same signature,
        same returns — but the chunk's attention runs the multi-query paged
        kernel straight off the arenas (earlier chunks' KV is read in block
        granules with the causal intra-chunk mask fused in-kernel) and the
        chunk's fresh K/V lands via the block-granule chunk writer, so with
        the kernel in it the compiled program contains zero arena
        gather/scatter primitives (the purity census asserts this with the
        gather chunk program as positive control).  Quantized pools take the fused absmax quantize-on-write
        epilogue; LoRA deltas run the fused kernel when meshless.  Only
        built when the construction-time chunk resolution picked "paged"
        (block-aligned chunk widths, no sliding window)."""
        from thunder_tpu.serving.paged_attention import (
            forward_paged,
            with_state,
            write_fresh_kv_chunk,
        )

        cfg = self.cfg
        hybrid = self._hybrid
        qkv = self.pool.quantized_kv
        cdtype = jnp.dtype(self.pool.dtype)
        kv_dtype = jnp.dtype(self.pool.kv_dtype) if qkv else None
        bs = self.pool.block_size
        cap = self.pool.capacity_tokens(nbb)
        cos_all, sin_all = build_rope_cache(cfg, cap)
        mesh = self.mesh

        @partial(jax.jit, donate_argnums=(3,),
                 **self._jit_kwargs("prefill_chunk_paged"))
        def prefill_chunk_paged(params, toks, pos, arenas, table, dest, lora,
                                slot, *state_args):
            pv = jnp.reshape(pos, (1,)).astype(jnp.int32)   # (B=1,) vec pos
            more = dict(zip(("sslots", "n_real"), state_args)) if hybrid else {}
            _logits, fresh = forward_paged(
                params, toks, pv, arenas, table[None, :], cos_all, sin_all,
                cfg, cdtype=cdtype, mesh=mesh, lora_fused=True,
                **self._fwd_kwargs(lora, slot), **more,
            )
            out, qerr = write_fresh_kv_chunk(
                arenas, fresh, dest, pv, block_size=bs,
                kv_dtype=kv_dtype, mesh=mesh)
            return with_state(out, fresh), qerr

        return prefill_chunk_paged

    def _build_decode_paged(self, Bb: int, nbb: int) -> Callable:
        """The decode step: one token a row, sampled per row (under ``vmap`` the
        unbatched ``generate()`` draw, each request on its own key chain).
        Attention runs the paged kernel straight off the arenas
        (scalar-prefetch block tables, in-kernel keep-mask + dequant) and the
        fresh token lands via the aliased write kernel, so with the kernel in it
        the compiled program contains zero gather/scatter primitives (tests
        assert this on the jaxpr) and no dense cache ever materializes; the
        kernel's XLA form gathers one layer's rows (``paged_attention``).

        The write's destination is derived inside the program (block =
        ``table[pos // bs]``, slot = ``pos % bs``) and the program returns
        ``pos + 1``, so a steady-state step consumes only its predecessor's
        device outputs plus the cached tables/slots: zero host->device transfers
        a step (the engine's ``_decode_state`` chain).  Padding rows carry
        all-sink tables.  Constrained engines pass one trailing ``(Bb, V)`` bool
        mask (all-True rows are a bit-exact no-op); plain engines pass nothing."""
        from thunder_tpu.serving.paged_attention import forward_paged, with_state, write_fresh_kv

        cfg, temp = self.cfg, self.temperature
        hybrid = self._hybrid
        qkv = self.pool.quantized_kv
        cdtype = jnp.dtype(self.pool.dtype)
        kv_dtype = jnp.dtype(self.pool.kv_dtype) if qkv else None
        bs = self.pool.block_size
        cap = self.pool.capacity_tokens(nbb)
        cos_all, sin_all = build_rope_cache(cfg, cap)
        mesh = self.mesh

        moe = self._moe_rows is not None

        @partial(jax.jit, donate_argnums=(4,), **self._jit_kwargs("decode_paged"))
        def decode_paged(params, toks, pos, tables, arenas, keys, lora, slots,
                         *cmask):
            more = {}
            if moe:
                # the expert share's running sums ride first, and come back last
                more, moe_sums, cmask = {"moe_rows": True}, cmask[0], cmask[1:]
            if hybrid:
                # the rows' state slots ride before the constraint mask
                more, cmask = {**more, "sslots": cmask[0]}, cmask[1:]
            logits, fresh = forward_paged(
                params, toks[:, None], pos, arenas, tables, cos_all, sin_all,
                cfg, cdtype=cdtype, mesh=mesh, lora_fused=True,
                **self._fwd_kwargs(lora, slots), **more,
            )
            with scope("head/sample"):
                sp = jax.vmap(jax.random.split)(keys)      # per-request key chains
                new_keys, subs = sp[:, 0], sp[:, 1]
                lg = logits[:, 0]
                if cmask:
                    lg = jnp.where(cmask[0], lg, -jnp.inf)
            nxt = jax.vmap(lambda l, k: sample_token(l[None], temp, k)[0])(
                lg, subs
            )
            arenas = with_state(
                write_fresh_kv(arenas, fresh, tables, pos, block_size=bs,
                               kv_dtype=kv_dtype, mesh=mesh), fresh)
            if moe:
                with scope("mlp/router"):       # this step's rows and hit share, a mean over the expert layers
                    rows, hit = jnp.mean(fresh["moe_rows"].astype(jnp.float32), axis=0)
                    moe_sums = moe_sums + jnp.stack([1.0, rows, rows * rows, hit / cfg.expert_held])
                return nxt, new_keys, pos + 1, arenas, moe_sums
            if "exit" in fresh:     # a looped model: a row's pass and exit probabilities, harvested with its token
                return nxt, new_keys, pos + 1, arenas, _exit_rows(*fresh["exit"])
            return nxt, new_keys, pos + 1, arenas

        return decode_paged


def serve(model_fn, params, cfg, **kwargs) -> ServingEngine:
    """Builds a :class:`ServingEngine` over the in-tree forward of ``cfg`` (a
    ``llama.Config`` is the model; ``model_fn`` must be ``None``).  See
    :class:`ServingEngine` for the knobs; nothing about constructing an
    engine touches any other compiled program (strictly additive).

    Mesh serving: ``serve(None, params, cfg, mesh=mesh)`` makes the whole
    engine SPMD — params are placed once (``shardings=`` overrides the
    default llama TP×FSDP rules), the paged K/V arenas shard their heads
    dim over ``tp`` (:func:`thunder_tpu.distributed.kv_cache_spec`), and
    every bucket program compiles once per (mesh, bucket) with explicit
    shardings and per-shard arena donation.  Served tokens stay
    bit-identical to solo ``generate(..., mesh=mesh)`` on the same mesh.

    Multi-tenant serving: ``kv_dtype="int8"`` stores the KV block arenas
    quantized (~``hs*itemsize/(hs+4)``x the resident requests per arena
    byte, quantize-on-scatter / dequant-on-gather inside the bucket
    programs, measured error in the ``serving.kv_quant.rel_err`` gauge);
    ``lora=AdapterRegistry(...)`` lets ``submit(..., adapter_id=...)``
    route each request through a registered LoRA adapter — batches freely
    mix tenants, and the compiled-program set grows only with the registry
    *geometry* (rank, slots, targets), never with adapter ids.

    Paged-attention decode: there is one decode program.  Its attention call
    is the Pallas flash-decoding kernel straight off the KV block arena
    (scalar-prefetch block tables, in-kernel keep-mask and int8/fp8 dequant,
    aliased in-place fresh-token write: zero gather/scatter primitives and no
    dense cache copy) wherever the kernel compiles: the TPU, or a CPU that
    opted into the interpreter with ``THUNDER_TPU_PALLAS_INTERPRET=1``.
    Elsewhere the same call is its XLA form, and every such decode step counts
    in ``stats()["attn"]["fallback_steps"]`` (``serving.attn.fallback_steps``);
    ``stats()["attn"]["path"]`` reads ``"walk"``, ``"by_blocks"`` or ``"xla"``.
    Served tokens are bit-identical either way.

    Async serving: ``async_step=True`` (default) runs ``step()`` as an
    event loop — decode for batch *k* is dispatched and the host admits,
    schedules, and streams batch *k−1*'s tokens before blocking
    (``serving.step.overlap_frac`` measures the win); ``prefill_chunk=N``
    additionally splits prompts longer than N into block-aligned chunks
    dispatched one per step between decodes, so a long prompt neither
    stalls running requests' TPOT nor hits the prompt-length admission cap.
    ``async_step=False`` keeps the original fully synchronous loop
    byte-identical; served tokens are bit-identical either way.

    Fault tolerance: a classified step exception no longer kills the
    engine — per-request anomalies quarantine just the offending request
    (``finish_reason="error"`` + structured cause, blocks freed, prefix
    index scrubbed), transient dispatch failures retry with exponential
    backoff (``retry=RetryPolicy(...)``), and engine-class faults (OOM,
    hangs caught by ``watchdog_timeout_s=...``, retry exhaustion) trigger
    **re-prefill recovery**: fresh arenas are rebuilt and every surviving
    request is replayed from its prompt + emitted tokens, after which the
    decode stream continues bit-identical to an uninterrupted run (the
    PRNG chain only advances at harvest, so the KV arena is soft state).
    ``fault_plan=FaultPlan(...)`` (or ``THUNDER_TPU_FAULT_PLAN`` JSON)
    injects deterministic seeded faults at the named fault points for
    chaos testing; ``fault_plan=None`` leaves every compiled program
    byte-identical — the plan lives purely on the host side.

    Speculative serving: ``speculative=SpecConfig(draft_params, draft_cfg,
    K=...)`` swaps each decode turn for a draft/verify round — a draft KV
    block arena rides beside the target arena (same block tables, same
    ``kv_dtype``/mesh treatment), K chained draft forwards propose tokens,
    one (K+1)-position target forward verifies them through the shared
    rejection rule (``models.speculative.accept_tokens``), and 1..K+1
    tokens emit per round.  PRNG keys advance only at harvest, so served
    tokens are bit-identical to solo ``speculative_generate()`` — greedy
    or sampled — and re-prefill recovery replays both arenas
    deterministically.  ``speculative=None`` (default) leaves every
    compiled program byte-identical to a spec-free engine.

    Data-parallel replication: a mesh with a ``dp`` axis (size > 1) — or
    an explicit ``replicas=N`` without a mesh — returns a
    :class:`~thunder_tpu.serving.router.ReplicatedEngine`: the device set
    splits into ``dp`` submeshes (each engine keeps every other axis, so
    ``(dp, tp)`` runs TP-sharded replicas), one async engine per replica
    with its own arena / lanes / program-cache entries, fronted by a
    single prefix-affinity router that keeps this exact API.  Faults stay
    replica-scoped; pass ``fault_plans=[...]`` (one entry per replica)
    instead of the solo ``fault_plan=``.  ``replicas=1`` / no-``dp``-axis
    returns a plain :class:`ServingEngine` whose compiled programs are
    byte-identical to today's (the module program cache is shared either
    way).  See :mod:`thunder_tpu.serving.router` for routing semantics
    and the multi-host (process-0) caveat."""
    if model_fn is not None:
        raise NotImplementedError(
            "tt.serve serves the in-tree forward of a llama.Config: describe the model as a Config "
            "(layer kinds, widths) and pass None here; a custom model_fn has no paged decode program")
    replicas = kwargs.pop("replicas", None)
    fault_plans = kwargs.pop("fault_plans", None)
    mesh = kwargs.get("mesh")
    dp = 0
    if mesh is not None and "dp" in mesh.axis_names:
        dp = int(mesh.shape["dp"])
        if replicas is not None and replicas != dp:
            raise ValueError(
                f"replicas={replicas} conflicts with the mesh dp axis of "
                f"size {dp} — pass one or the other"
            )
    n = replicas if replicas is not None else dp
    if n is not None and n > 1:
        from thunder_tpu.serving.router import ReplicatedEngine

        if mesh is not None and dp == 0:
            raise ValueError(
                f"replicas={n} with a mesh requires a 'dp' axis to split "
                f"on (axes: {mesh.axis_names})"
            )
        return ReplicatedEngine(params, cfg, replicas=n, fault_plans=fault_plans, **kwargs)
    if fault_plans is not None:
        raise ValueError(
            "fault_plans= is the per-replica form; a solo engine takes "
            "fault_plan="
        )
    return ServingEngine(params, cfg, **kwargs)
