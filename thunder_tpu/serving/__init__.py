"""Serving subsystem: continuous batching over a paged KV-cache pool.

Layer #10 of the stack — the request level.  ``models/generate.py`` turns a
compiled decode step into *one* fixed-batch generation; this package turns
it into a server: a FIFO request queue with admission control, a
block-granular KV pool shared by every in-flight request (with reference-
counted prefix sharing), bucketed batch shapes so the compiled-program set
stays bounded, and per-request deadlines, streaming, and telemetry.

Entry point: ``tt.serve(None, params, cfg, ...)`` (or construct
:class:`ServingEngine` directly).  Everything is strictly additive — no
other compiled program changes by importing or using this package.

The drive loop is an async event loop by default (``async_step=True``):
decode for batch *k* dispatches and the host admits/schedules/streams
batch *k−1* before blocking, and ``prefill_chunk=N`` splits long prompts
into block-aligned pieces interleaved between decode dispatches so they
stop stalling running requests.  Served tokens are bit-identical to the
synchronous path (``async_step=False``) and to solo ``generate()``.

With ``mesh=`` the engine is SPMD end to end (:mod:`serving.mesh`): params
placed once, the block arena's KV-heads dim sharded over ``tp`` via the
``distributed.kv_cache_spec`` rule, and every bucket program pjit-compiled
once per (mesh, bucket) — served tokens bit-identical to solo sharded
``generate()`` on the same mesh.

Multi-tenancy (:mod:`serving.quant` + :mod:`serving.lora`):
``kv_dtype="int8"`` / ``"fp8"`` stores the block arenas quantized
(per-token absmax scales, ~4x the resident requests per arena byte vs
f32), and
``lora=AdapterRegistry(...)`` + ``submit(..., adapter_id=...)`` serves many
LoRA fine-tunes off one base model — adapters are program *data*, so
batches mix tenants without recompiling and each request's tokens match
its solo single-adapter run bit-exactly.

Fault tolerance (:mod:`serving.faults`): ``fault_plan=FaultPlan(...)``
injects deterministic seeded faults at the engine's named fault points for
chaos testing; at runtime a classified step exception quarantines just the
offending request (``finish_reason="error"``), transient dispatch failures
retry with bounded exponential backoff, and engine-class faults trigger
re-prefill recovery — fresh arenas plus a sampling-free replay of every
surviving request's known tokens, after which streams continue
bit-identical to an uninterrupted run.

Data-parallel replication (:mod:`serving.router`): a ``mesh=`` with a
``dp`` axis — or an explicit ``replicas=N`` — returns a
:class:`ReplicatedEngine`: N engine lanes (one per submesh, each with its
own arena / scheduler / in-flight lanes) behind one prefix-affinity
router that keeps this exact submit/stream/drain/shutdown API.  Routing
is least-loaded with resident-prefix and routing-history affinity, so
request families stay co-located (prefix sharing — and the narrow decode
buckets it buys — keep working at fleet scale); token streams stay
bit-identical to a solo engine serving the same request.

Stateful serving (:mod:`serving.sessions` / :mod:`serving.priority` /
:mod:`serving.constrain`): ``sessions=True`` + ``submit(...,
session_id=)`` keeps a finished turn's prefix blocks resident in a
budgeted LRU table, so the next turn re-attaches through the existing
shared-prefix path and re-prefills only the unaligned tail;
``priorities=True`` + ``submit(..., priority=)`` adds class-ordered
queueing, SLO-burn-fed admission, and evict-and-resume preemption
(checkpoint = release blocks + re-queue; resume = sampling-free chunk
replay, streams bit-identical); ``constraints=True`` + ``submit(...,
constraint=)`` masks logits per request through ONE extra program
argument — schemas are data, never program identity.

Speculative continuous batching (:mod:`serving.speculative`):
``speculative=SpecConfig(draft_params, draft_cfg, K=...)`` adds a draft KV
block arena beside the target arena (same block tables) and swaps each
decode turn for a draft/verify round — K chained draft forwards propose, a
single (K+1)-position target forward verifies via the shared rejection
rule, and 1..K+1 tokens emit per round.  Served tokens stay bit-identical
to solo ``speculative_generate()``, greedy or sampled.
"""
from thunder_tpu.serving.engine import (  # noqa: F401
    EngineStalledError,
    RecoveryError,
    RequestHandle,
    RequestResult,
    ServingEngine,
    serve,
)
from thunder_tpu.serving.faults import (  # noqa: F401
    FaultError,
    FaultPlan,
    FaultSpec,
    RetryPolicy,
    DeviceOOMFault,
    HarvestHangFault,
    RequestAnomalyFault,
    TransientDispatchFault,
    WatchdogTimeout,
)
from thunder_tpu.serving.kv_pool import (  # noqa: F401
    ArenaMismatchError,
    PagedKVPool,
    PoolExhaustedError,
)
from thunder_tpu.serving.lora import (  # noqa: F401
    AdapterRegistry,
    RegistryFullError,
    make_lora_factors,
)
from thunder_tpu.serving.quant import (  # noqa: F401
    arena_block_bytes,
    blocks_for_arena_bytes,
)
from thunder_tpu.serving.router import (  # noqa: F401
    ReplicatedEngine,
    RoutedHandle,
)
from thunder_tpu.serving.scheduler import (  # noqa: F401
    AdmissionError,
    Request,
    Scheduler,
    pick_bucket,
    pow2_buckets,
)
from thunder_tpu.serving.constrain import (  # noqa: F401
    Constraint,
    DFAConstraint,
    TokenSetConstraint,
    sequence_constraint,
)
from thunder_tpu.serving.priority import (  # noqa: F401
    PRIORITY_HIGH,
    PRIORITY_LEVELS,
    PRIORITY_LOW,
    PRIORITY_NORMAL,
    PriorityConfig,
    PriorityGate,
)
from thunder_tpu.serving.sessions import (  # noqa: F401
    SessionConfig,
    SessionEntry,
    SessionTable,
)
from thunder_tpu.serving.speculative import SpecConfig  # noqa: F401

__all__ = [
    "serve",
    "ServingEngine",
    "ReplicatedEngine",
    "RoutedHandle",
    "RequestHandle",
    "RequestResult",
    "PagedKVPool",
    "PoolExhaustedError",
    "ArenaMismatchError",
    "EngineStalledError",
    "Scheduler",
    "Request",
    "AdmissionError",
    "AdapterRegistry",
    "RegistryFullError",
    "make_lora_factors",
    "arena_block_bytes",
    "blocks_for_arena_bytes",
    "pick_bucket",
    "pow2_buckets",
    "SpecConfig",
    "FaultPlan",
    "FaultSpec",
    "RetryPolicy",
    "FaultError",
    "TransientDispatchFault",
    "RequestAnomalyFault",
    "DeviceOOMFault",
    "HarvestHangFault",
    "WatchdogTimeout",
    "RecoveryError",
    "SessionConfig",
    "SessionEntry",
    "SessionTable",
    "PriorityConfig",
    "PriorityGate",
    "PRIORITY_HIGH",
    "PRIORITY_NORMAL",
    "PRIORITY_LOW",
    "PRIORITY_LEVELS",
    "Constraint",
    "TokenSetConstraint",
    "DFAConstraint",
    "sequence_constraint",
]
