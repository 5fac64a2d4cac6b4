"""Priority classes, SLO-feedback admission, preemption (serving/priority.py).

The load-bearing guarantee is that preemption is a *checkpoint*, not a
restart: an evicted-and-resumed request's token stream is bit-identical to
an undisturbed run (host state — prompt, generated tokens, PRNG chain —
is exact because keys only advance at harvest; resume replays through the
sampling-free chunk programs, never token-by-token).  Scheduling policy
(class-ordered queue, burn-rate admission gate, victim choice) is tested
host-side; the off-path (``priorities=None``) leaves queue order and
program identity untouched.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import thunder_tpu as tt
from thunder_tpu.models import llama
from thunder_tpu.serving import (
    PRIORITY_HIGH,
    PRIORITY_LEVELS,
    PRIORITY_LOW,
    PRIORITY_NORMAL,
    PriorityConfig,
    PriorityGate,
)
from thunder_tpu.serving.priority import priority_level

MICRO = dict(
    n_layer=1, n_head=2, n_embd=16, intermediate_size=32, vocab_size=32,
    block_size=64,
)
BUCKETS = dict(batch_buckets=(1, 2), block_buckets=(4, 8), prefill_buckets=(8, 16))


@pytest.fixture(scope="module")
def micro():
    cfg = llama.Config.from_name("tiny-llama-debug", **MICRO)
    params = llama.init_params(cfg, jax.random.PRNGKey(0), dtype=jnp.float32)
    return cfg, params


def _engine(cfg, params, **kw):
    kw.setdefault("block_size", 4)
    kw.setdefault("num_blocks", 16)
    kw.setdefault("max_batch", 2)
    kw.setdefault("cache_dtype", jnp.float32)
    for k, v in BUCKETS.items():
        kw.setdefault(k, v)
    return tt.serve(None, params, cfg, **kw)


def _prompt(seed, n, cfg):
    return np.random.default_rng(seed).integers(
        1, cfg.vocab_size, (n,)).astype(np.int32)


class _StubSLO:
    """A monitor double: fixed burn rates per dimension."""

    def __init__(self, burns):
        self._dims = dict.fromkeys(burns)
        self._burns = burns

    def burn_rate(self, dim):
        return self._burns[dim]

    def observe(self, res):            # engine calls at finish; irrelevant here
        pass

    def report(self):
        return {"enabled": True}


#
# the gate (pure policy)
#


class TestPriorityGate:
    def test_levels_and_normalization(self):
        assert PRIORITY_LEVELS[PRIORITY_HIGH] < PRIORITY_LEVELS[PRIORITY_NORMAL]
        assert priority_level(None) == (PRIORITY_NORMAL, 1)
        assert priority_level("high") == ("high", 0)
        with pytest.raises(ValueError, match="priority"):
            priority_level("urgent")

    def test_config_validation(self):
        with pytest.raises(ValueError, match="unknown priority class"):
            PriorityConfig(burn_limits={"vip": 1.0})
        with pytest.raises(ValueError, match="max_preemptions"):
            PriorityConfig(max_preemptions=-1)

    def test_admit_gate_defers_on_burn(self):
        gate = PriorityGate(PriorityConfig(
            burn_limits={PRIORITY_LOW: 1.0, PRIORITY_NORMAL: 4.0}))
        hot = _StubSLO({"ttft": 2.5, "e2e": 0.1})
        assert not gate.admit_ok(PRIORITY_LOW, hot)        # 2.5 > 1.0
        assert gate.admit_ok(PRIORITY_NORMAL, hot)         # 2.5 < 4.0
        assert gate.admit_ok(PRIORITY_HIGH, hot)           # no limit ever
        assert gate.deferrals[PRIORITY_LOW] == 1
        cool = _StubSLO({"ttft": 0.2, "e2e": None})        # None = no data
        assert gate.admit_ok(PRIORITY_LOW, cool)
        assert gate.admit_ok(PRIORITY_LOW, None)           # slo=None: inert

    def test_pick_victim_least_urgent_most_recent(self):
        class R:
            def __init__(self, priority, admit_t, preemptions=0):
                self.priority, self.admit_t = priority, admit_t
                self.preemptions = preemptions

        gate = PriorityGate()
        low_old, low_new = R(2, 1.0), R(2, 2.0)
        normal = R(1, 3.0)
        running = [normal, low_old, low_new]
        assert gate.pick_victim(running, 0) is low_new     # least urgent, newest
        assert gate.pick_victim([normal], 0) is normal
        assert gate.pick_victim([normal], 1) is None       # strict urgency only
        worn = R(2, 9.0, preemptions=PriorityConfig().max_preemptions)
        assert gate.pick_victim([worn], 0) is None         # preemption-exempt
        off = PriorityGate(PriorityConfig(preempt=False))
        assert off.pick_victim(running, 0) is None


#
# queue ordering (scheduler policy, host-only)
#


class TestQueueOrdering:
    def test_class_ordered_fifo_within_class(self, micro):
        cfg, params = micro
        eng = _engine(cfg, params, priorities=True, max_batch=1, max_queue=8)
        # fill the single slot so everything else queues
        eng.submit(_prompt(1, 7, cfg), max_new_tokens=6)
        eng.step()
        hs = [eng.submit(_prompt(2 + i, 7, cfg), max_new_tokens=2, priority=p)
              for i, p in enumerate(["low", "normal", "high", "normal", "high"])]
        order = [r.priority_class for r in eng.scheduler.queue]
        assert order == ["high", "high", "normal", "normal", "low"]
        # FIFO within class: the first-submitted high is first
        assert eng.scheduler.queue[0].rid == hs[2]._req.rid
        eng.drain()
        eng.shutdown()

    def test_off_path_queue_is_fifo(self, micro):
        cfg, params = micro
        eng = _engine(cfg, params, max_batch=1, max_queue=8)
        eng.submit(_prompt(9, 7, cfg), max_new_tokens=6)
        eng.step()
        hs = [eng.submit(_prompt(10 + i, 7, cfg), max_new_tokens=2)
              for i in range(3)]
        assert [r.rid for r in eng.scheduler.queue] == [h._req.rid for h in hs]
        with pytest.raises(ValueError, match="priorit"):
            eng.submit(_prompt(20, 7, cfg), max_new_tokens=2, priority="high")
        eng.drain()
        eng.shutdown()


#
# preemption end-to-end: evict-and-resume bit-parity (the acceptance bar)
#


class TestPreemption:
    def _starve(self, cfg, params, **kw):
        """A pool sized so a second request cannot be funded while the
        first runs: preemption is the only way in."""
        kw.setdefault("num_blocks", 10)
        kw.setdefault("max_batch", 1)
        kw.setdefault("max_queue", 8)
        return _engine(cfg, params, priorities=True, **kw)

    def test_preempted_stream_bit_identical(self, micro):
        cfg, params = micro
        p_low, p_high = _prompt(31, 8, cfg), _prompt(32, 8, cfg)
        klow, khigh = jax.random.PRNGKey(3), jax.random.PRNGKey(5)
        eng = self._starve(cfg, params, temperature=0.7)
        h_low = eng.submit(p_low, max_new_tokens=8, key=klow, priority="low")
        for _ in range(5):
            eng.step()                  # low is mid-decode
        h_high = eng.submit(p_high, max_new_tokens=4, key=khigh,
                            priority="high")
        r_high = h_high.result()
        r_low = h_low.result()
        assert eng.preempted == 1
        assert eng.stats()["priority"]["preempted"] == 1
        # both streams match undisturbed solo-engine runs, bit-for-bit
        ref = _engine(cfg, params, num_blocks=10, max_batch=1, temperature=0.7)
        u_low = ref.submit(p_low, max_new_tokens=8, key=klow).result()
        u_high = ref.submit(p_high, max_new_tokens=4, key=khigh).result()
        assert r_low.new_tokens == u_low.new_tokens
        assert r_high.new_tokens == u_high.new_tokens
        ref.shutdown()
        eng.shutdown()

    def test_resume_replays_chunks_not_tokens(self, micro):
        """The victim's resume goes through the sampling-free chunk-replay
        programs (chunk_runs advances), never a token-by-token redo."""
        cfg, params = micro
        eng = self._starve(cfg, params)
        h_low = eng.submit(_prompt(33, 8, cfg), max_new_tokens=8,
                           priority="low")
        for _ in range(5):
            eng.step()
        assert eng.chunk_runs == 0
        eng.submit(_prompt(34, 8, cfg), max_new_tokens=3,
                   priority="high").result()
        h_low.result()
        assert eng.preempted == 1
        assert eng.chunk_runs > 0
        eng.shutdown()

    def test_victim_without_tokens_resumes_via_prefill(self, micro):
        """Preempting before the victim's first token just re-queues it:
        its key never split, so token 0 is unchanged."""
        cfg, params = micro
        eng = self._starve(cfg, params, async_step=False)
        p = _prompt(35, 8, cfg)
        h_low = eng.submit(p, max_new_tokens=4, priority="low")
        # no step yet: admit happens inside the high request's drive
        h_high = eng.submit(_prompt(36, 8, cfg), max_new_tokens=3,
                            priority="high")
        h_high.result()
        r = h_low.result()
        ref = _engine(cfg, params, num_blocks=10, max_batch=1)
        assert r.new_tokens == ref.submit(p, max_new_tokens=4).result().new_tokens
        ref.shutdown()
        eng.shutdown()

    def test_admission_gate_defers_low_under_burn(self, micro):
        cfg, params = micro
        eng = _engine(cfg, params, priorities=dict(
            burn_limits={PRIORITY_LOW: 1.0}))
        eng._slo = _StubSLO({"ttft": 5.0})       # hot window: low is locked out
        h = eng.submit(_prompt(37, 7, cfg), max_new_tokens=2, priority="low")
        for _ in range(3):
            eng.step()
        assert h.state == "queued"
        assert eng._priorities.deferrals[PRIORITY_LOW] > 0
        eng._slo = _StubSLO({"ttft": 0.1})       # window recovered
        assert h.result().finish_reason == "length"
        eng.shutdown()

    def test_scheduler_snapshot_and_result_fields(self, micro):
        cfg, params = micro
        eng = self._starve(cfg, params)
        h_low = eng.submit(_prompt(38, 8, cfg), max_new_tokens=8,
                           priority="low")
        for _ in range(5):
            eng.step()
        rows = eng.scheduler.state_snapshot()["requests"]
        assert rows[0]["priority"] == "low" and rows[0]["preemptions"] == 0
        eng.submit(_prompt(39, 8, cfg), max_new_tokens=3,
                   priority="high").result()
        h_low.result()
        assert eng._priorities.snapshot()["preempt"] is True
        snap = tt.metrics_snapshot()
        assert snap["serving.priority.high.admitted"] == 1
        assert snap["serving.priority.low.preempted"] == 1
        eng.shutdown()

    def test_preemption_disabled_on_speculative(self, micro):
        """Spec harvest has no preemption epoch guard, so spec engines
        never preempt — the head waits like plain pool pressure."""
        cfg, params = micro
        dcfg = llama.Config.from_name("tiny-llama-debug", **MICRO)
        dp = llama.init_params(dcfg, jax.random.PRNGKey(9), dtype=jnp.float32)
        from thunder_tpu.serving import SpecConfig

        eng = _engine(cfg, params, priorities=True, num_blocks=24,
                      max_batch=1, max_queue=8,
                      speculative=SpecConfig(dp, dcfg, K=2))
        h1 = eng.submit(_prompt(40, 8, cfg), max_new_tokens=4, priority="low")
        for _ in range(2):
            eng.step()
        h2 = eng.submit(_prompt(41, 8, cfg), max_new_tokens=3, priority="high")
        h2.result()
        h1.result()
        assert eng.preempted == 0
        eng.shutdown()


#
# the deadline check looks at the requests that carry a deadline, and at no other
#


class _NeverWalked(list):
    """A queue or running list that fails the test if it is iterated."""

    def __iter__(self):
        raise AssertionError("the deadline check walked a list with no deadline in it")


class TestDeadlineCheck:
    @pytest.mark.parametrize("case", ["none_anywhere", "queued_and_running", "finished_first", "preempted"])
    def test_deadline_expired_is_the_old_walk_over_fewer_requests(self, micro, case):
        """With no deadline anywhere neither list is iterated; with deadlines
        on queued and running requests the same requests expire in the same
        order as the walk over ``running + queue`` gave (running first, each
        list in its order), whatever order the deadlines were given in."""
        from thunder_tpu.serving.kv_pool import PagedKVPool
        from thunder_tpu.serving.scheduler import Scheduler

        clk = {"t": 0.0}
        pool = PagedKVPool(micro[0], num_blocks=64, block_size=4, dtype=jnp.float32)
        sch = Scheduler(pool, max_batch=3, max_queue=16, clock=lambda: clk["t"])

        def old_walk():
            now = sch.clock()
            return [r for r in (*sch.running, *sch.queue) if r.deadline_t is not None and now >= r.deadline_t]

        def submit(deadline, priority=1):
            return sch.submit(np.arange(5, dtype=np.int32), 4, key=np.zeros(2, np.uint32),
                              deadline_s=deadline, priority=priority)

        def admit():
            head = sch.queue[0]
            sch.admit(head, pool.alloc(sch.blocks_needed(head)), 0)
            return head

        if case == "none_anywhere":
            for _ in range(5):
                submit(None)
            admit(), admit()
            sch.queue, sch.running = _NeverWalked(sch.queue), _NeverWalked(sch.running)
            clk["t"] = 1e9
            assert sch.deadline_expired() == []
            return
        # deadlines out of order, on running and queued requests, some without
        reqs = [submit(d) for d in (7.0, None, 3.0, 9.0, None, 2.0, 5.0)]
        admit(), admit(), admit()                               # 7.0, None, 3.0 run; the rest wait
        if case == "finished_first":
            sch.finish(reqs[2], "evicted")                      # a deadline that no longer counts
            sch.finish(reqs[5], "evicted")
        elif case == "preempted":
            sch.preempt(reqs[0])                                # running -> queued, its deadline stands
        seen = []
        for t in (0.0, 2.5, 4.0, 6.0, 8.0, 20.0):
            clk["t"] = t
            got = sch.deadline_expired()
            assert got == old_walk() and all(a is b for a, b in zip(got, old_walk()))
            seen.append([r.rid for r in got])
        assert seen[0] == [] and len(seen[-1]) == (3 if case == "finished_first" else 5)
        for r in old_walk():
            sch.finish(r, "deadline")
        assert sch.deadline_expired() == [] and not sch._deadlined
