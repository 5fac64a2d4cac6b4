"""The elastic training loop: classify faults, restore, replay, converge.

The serving plane already proved the recovery grammar (PR 12): injectable
deterministic faults, a blast-radius taxonomy
(:func:`thunder_tpu.serving.faults.classify_fault`), bounded retry with
backoff, and a differential guarantee (recovered output bit-identical to
the undisturbed run).  :func:`train_loop` is the training-plane instance:

- every optimizer step passes the ``train.step`` fault point (armed plans
  inject there; unarmed runs pay one ``is None`` check);
- a fault classified ``transient`` retries the SAME step after backoff
  (the fault fired before dispatch, so params/opt state are intact);
- ``engine``-class faults (OOM, hang, watchdog) trigger **elastic
  restart**: drain pending checkpoint saves, restore the newest committed
  checkpoint (torn ones are skipped with a structured warning), and replay
  from there;
- ``request``-class has no training analogue and escalates like
  unclassified exceptions: re-raise (programming errors keep the
  crash-dump contract).

Bit-identity: batches come from ``batch_for_step(step)`` — a pure function
of the step index — and checkpoints capture (params, opt_state) *after*
step ``s`` under the name ``s+1`` (steps completed).  A replay therefore
re-executes the exact program on the exact inputs, and the final loss
curve is bit-identical to the undisturbed run's (``tests/test_train_loop.py``
holds it).
"""
from __future__ import annotations

from dataclasses import dataclass, field
from typing import Callable, Sequence

import jax
import numpy as np

from thunder_tpu.observability.events import span
from thunder_tpu.observability.metrics import registry
from thunder_tpu.serving.faults import (
    CLASS_ENGINE,
    CLASS_TRANSIENT,
    FP_TRAIN_STEP,
    RecoveryError,
    RetryPolicy,
    classify_fault,
    fault_cause,
)
from thunder_tpu.train.checkpoint import AsyncCheckpointer, restore_latest

__all__ = ["TrainLoopResult", "train_loop"]


@dataclass
class TrainLoopResult:
    """What a (possibly fault-interrupted) run produced."""

    params: object
    opt_state: object
    losses: list = field(default_factory=list)   # loss per step index, final values
    steps_run: int = 0                           # total step executions incl. replays
    restarts: int = 0                            # elastic restarts taken
    retries: int = 0                             # transient same-step retries
    resumed_from: int | None = None              # checkpoint step a restart used (last)
    faults: list = field(default_factory=list)   # structured causes absorbed
    checkpoint_failures: list = field(default_factory=list)


def _snapshot(state):
    nbytes = sum(x.nbytes for x in jax.tree_util.tree_leaves(state) if isinstance(x, jax.Array))
    with span("train.snapshot", bytes=nbytes):
        return jax.tree_util.tree_map(
            lambda x: np.asarray(jax.device_get(x)) if isinstance(x, jax.Array) else x, state
        )


def _replace(template, host_state):
    t_leaves, treedef = jax.tree_util.tree_flatten(template)
    h_leaves = jax.tree_util.tree_leaves(host_state)
    placed = [
        jax.device_put(h, t.sharding) if isinstance(t, jax.Array) else h
        for h, t in zip(h_leaves, t_leaves)
    ]
    return jax.tree_util.tree_unflatten(treedef, placed)


def train_loop(
    step_fn: Callable,
    params,
    opt_state,
    batch_for_step: Callable[[int], Sequence],
    *,
    steps: int,
    start_step: int = 0,
    checkpointer: AsyncCheckpointer | None = None,
    checkpoint_every: int = 0,
    fault_plan=None,
    retry: RetryPolicy | None = None,
    max_restarts: int = 4,
    on_step: Callable[[int, float], None] | None = None,
) -> TrainLoopResult:
    """Runs ``steps`` optimizer steps with elastic fault recovery.

    ``step_fn(params, opt_state, *batch) -> (params, opt_state, loss)`` is
    typically a built :class:`~thunder_tpu.distributed.TrainStep`;
    ``batch_for_step(s)`` must be a pure function of ``s`` (that purity IS
    the bit-identical-resume contract).  ``checkpoint_every=k`` dispatches
    an async save after every k-th completed step; the loop's initial state
    is snapshotted to host once so a restart with no committed checkpoint
    can still replay from step ``start_step``."""
    retry = retry or RetryPolicy()
    res = TrainLoopResult(params=params, opt_state=opt_state,
                          losses=[None] * steps)
    # host-side seed state: the restart-of-last-resort when no checkpoint
    # has committed yet (donation consumes the device buffers, so a copy is
    # the only way back)
    seed_state = _snapshot({"params": params, "opt_state": opt_state})
    reg = registry()

    s = start_step
    attempt = 0
    while s < steps:
        batch = batch_for_step(s)
        try:
            if fault_plan is not None:
                fault_plan.check(FP_TRAIN_STEP, ())
            params, opt_state, loss = step_fn(params, opt_state, *batch)
        except Exception as e:  # noqa: BLE001 — classified below, else re-raised
            cls = classify_fault(e)
            if cls is None:
                raise
            res.faults.append(fault_cause(e))
            reg.counter("train.faults.absorbed").inc()
            if cls == CLASS_TRANSIENT:
                if attempt >= retry.max_retries:
                    raise RecoveryError(
                        f"step {s}: transient fault persisted past "
                        f"{retry.max_retries} retries"
                    ) from e
                attempt += 1
                res.retries += 1
                retry.sleep(retry.backoff(attempt))
                continue  # same step, params/opt intact (fault pre-dispatch)
            if cls != CLASS_ENGINE:
                raise  # request-class has no training analogue: escalate
            if res.restarts >= max_restarts:
                raise RecoveryError(
                    f"step {s}: restart budget ({max_restarts}) exhausted"
                ) from e
            # elastic restart: drain pending saves, then newest committed wins
            res.restarts += 1
            reg.counter("train.restarts").inc()
            restored = None
            if checkpointer is not None:
                for rec in checkpointer.wait():
                    if "error" in rec:
                        res.checkpoint_failures.append(rec)
                restored = restore_latest(
                    checkpointer.directory,
                    {"params": params, "opt_state": opt_state},
                    config=checkpointer.config,
                )
            if restored is not None:
                ck_step, state = restored
            else:
                ck_step, state = start_step, _replace(
                    {"params": params, "opt_state": opt_state}, seed_state
                )
            params, opt_state = state["params"], state["opt_state"]
            res.resumed_from = ck_step
            s = ck_step
            attempt = 0
            continue
        attempt = 0
        res.steps_run += 1
        res.losses[s] = loss
        if on_step is not None:
            on_step(s, loss)
        s += 1
        if checkpointer is not None and checkpoint_every > 0 and s % checkpoint_every == 0:
            checkpointer.dispatch(s, {"params": params, "opt_state": opt_state})
            for rec in checkpointer.harvest():
                if "error" in rec:
                    res.checkpoint_failures.append(rec)

    if checkpointer is not None:
        for rec in checkpointer.wait():
            if "error" in rec:
                res.checkpoint_failures.append(rec)
    res.params, res.opt_state = params, opt_state
    return res
