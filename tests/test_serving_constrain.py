"""Constrained decoding (serving/constrain.py, ISSUE 17).

The load-bearing guarantees: (1) every emitted token of a constrained
request lies in the automaton's allowed set — greedy and temperature,
gather and paged decode; (2) schemas are program
*arguments* (the LoRA idiom) — after an engine's geometry set is warm, a
brand-new constraint compiles ZERO programs; (3) unconstrained rows ride
through an all-True mask bit-identically, and ``constraints=None``
engines compile byte-identical module-cache entries to a world where the
subsystem does not exist.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import thunder_tpu as tt
from thunder_tpu.models import llama
from thunder_tpu.serving import (
    Constraint,
    DFAConstraint,
    TokenSetConstraint,
    sequence_constraint,
)

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


#
# automata (pure host state machines)
#


class TestConstraints:
    def test_token_set_mask_and_advance(self):
        c = TokenSetConstraint(64, [3, 4, 5])
        m = c.mask()
        assert m.shape == (64,) and m.sum() == 3 and m[3] and not m[0]
        c.advance(4)
        with pytest.raises(ValueError, match="violates"):
            c.advance(7)
        assert (c.mask() == m).all()           # stationary
        with pytest.raises(ValueError):
            TokenSetConstraint(64, [])
        with pytest.raises(ValueError):
            TokenSetConstraint(64, [64])

    def test_dfa_walk_and_violation(self):
        t = np.full((2, 8), -1)
        t[0, 1] = 1
        t[1, 2] = 0
        c = DFAConstraint(t)
        assert list(np.flatnonzero(c.mask())) == [1]
        c.advance(1)
        assert c.state == 1 and list(np.flatnonzero(c.mask())) == [2]
        with pytest.raises(ValueError, match="forbidden"):
            c.advance(5)
        c.reset()
        assert c.state == 0
        with pytest.raises(ValueError, match="transitions"):
            DFAConstraint(np.full((2, 8), 7))  # state out of range

    def test_a_sequence_constraint_is_its_steps_in_order(self):
        def walk(c, toks):
            seen = []
            for t in toks:
                seen.append(list(np.flatnonzero(c.mask())))
                c.advance(t)
            return seen

        c = sequence_constraint(8, [[1], [2, 3], [4]])
        assert walk(c, [1, 3, 4, 4]) == [[1], [2, 3], [4], [4]]     # last step repeats
        cyc = sequence_constraint(8, [[1], [2]], cycle=True)
        assert walk(cyc, [1, 2, 1]) == [[1], [2], [1]]
        # where the next mask hangs on the token drawn: state 0 -> {0, 1} with different allowed sets
        t = np.full((2, 8), -1)
        t[0, 1] = 1
        t[0, 2] = 0
        t[1, 3] = 1
        assert walk(DFAConstraint(t), [2, 1, 3]) == [[1, 2], [1, 2], [3]]

    def test_base_class_contract(self):
        c = Constraint(8)
        with pytest.raises(NotImplementedError):
            c.mask()

        class OneStep(Constraint):
            def mask(self):
                return np.ones(8, dtype=bool)

            def advance(self, token):
                pass

        assert OneStep(8).mask().shape == (8,) and OneStep(8).vocab_size == 8
        with pytest.raises(NotImplementedError):
            c.advance(0)


#
# engine end-to-end
#


class TestConstrainedServing:
    def test_tokens_stay_in_allowed_set(self, micro):
        cfg, params = micro
        V = cfg.padded_vocab_size
        eng = _engine(cfg, params, constraints=True, temperature=0.9)
        allowed = {3, 4, 5, 9}
        c = TokenSetConstraint(V, allowed)
        r = eng.submit(_prompt(1, 7, cfg), max_new_tokens=6,
                       key=jax.random.PRNGKey(2), constraint=c).result()
        assert set(r.new_tokens) <= allowed
        eng.shutdown()

    def test_dfa_forces_exact_shape(self, micro, attn_form):
        """In both forms of the decode program's attention: the mask is an
        argument beside the kernel's as it is beside the XLA form's."""
        cfg, params = micro
        V = cfg.padded_vocab_size
        eng = _engine(cfg, params, constraints=True)
        c = sequence_constraint(V, [[7], [1, 2], [9]])
        r = eng.submit(_prompt(2, 7, cfg), max_new_tokens=4,
                       constraint=c).result()
        assert r.new_tokens[0] == 7
        assert r.new_tokens[1] in (1, 2)
        assert r.new_tokens[2] == 9 and r.new_tokens[3] == 9
        eng.shutdown()

    def test_unconstrained_rows_bit_identical(self, micro):
        """An unconstrained request on a constrained engine — riding the
        all-True mask — matches the plain engine bit-for-bit, mixed into
        the same batch as a constrained neighbour."""
        cfg, params = micro
        V = cfg.padded_vocab_size
        p = _prompt(3, 7, cfg)
        key = jax.random.PRNGKey(5)
        plain = _engine(cfg, params, temperature=0.7)
        ref = plain.submit(p, max_new_tokens=5, key=key).result()
        plain.shutdown()
        eng = _engine(cfg, params, constraints=True, temperature=0.7)
        h1 = eng.submit(p, max_new_tokens=5, key=key)
        h2 = eng.submit(_prompt(4, 7, cfg), max_new_tokens=5,
                        constraint=TokenSetConstraint(V, [3]))
        eng.drain()
        assert h1.result(drive=False).new_tokens == ref.new_tokens
        assert set(h2.result(drive=False).new_tokens) == {3}
        eng.shutdown()

    def test_submit_validation(self, micro):
        cfg, params = micro
        V = cfg.padded_vocab_size
        eng = _engine(cfg, params)
        with pytest.raises(ValueError, match="constraints"):
            eng.submit(_prompt(7, 7, cfg), max_new_tokens=2,
                       constraint=TokenSetConstraint(V, [1]))
        eng.shutdown()
        eng = _engine(cfg, params, constraints=True)
        with pytest.raises(ValueError, match="vocab"):
            eng.submit(_prompt(8, 7, cfg), max_new_tokens=2,
                       constraint=TokenSetConstraint(V + 64, [1]))
        eng.shutdown()

    def test_constraint_survives_recovery(self, micro):
        """The automaton is host state that never lived on the device:
        recovery replay continues the constrained stream untouched."""
        cfg, params = micro
        V = cfg.padded_vocab_size
        eng = _engine(cfg, params, constraints=True)
        c = sequence_constraint(V, [[3], [4], [5], [6], [7], [8]])
        h = eng.submit(_prompt(9, 7, cfg), max_new_tokens=6, constraint=c)
        for _ in range(4):
            eng.step()
        eng._recover_once()
        r = h.result()
        assert r.new_tokens == (3, 4, 5, 6, 7, 8)
        eng.shutdown()


#
# program identity: zero compiles per schema; byte-identical off-path
#


class TestProgramIdentity:
    def test_new_schema_compiles_zero_programs(self, micro):
        """The acceptance criterion: once the geometry set is warm, a
        brand-new constraint — different automaton class, different
        allowed sets — adds ZERO compiled programs."""
        cfg, params = micro
        V = cfg.padded_vocab_size
        eng = _engine(cfg, params, constraints=True)
        eng.submit(_prompt(10, 7, cfg), max_new_tokens=4,
                   constraint=TokenSetConstraint(V, [1, 2])).result()
        warm = dict(eng.compile_counts)
        for c in (TokenSetConstraint(V, [9]),
                  sequence_constraint(V, [[5], [6, 7]]),
                  None):
            eng.submit(_prompt(11, 7, cfg), max_new_tokens=4,
                       constraint=c).result()
        assert dict(eng.compile_counts) == warm
        eng.shutdown()

    def test_off_path_is_byte_identical(self, micro):
        """constraints=None: the engine compiles the exact programs a
        constraint-free world compiles (module cache gains no entries on a
        second build) and the static key collapses to the shared entry."""
        from thunder_tpu.serving.engine import _program_cache

        cfg, params = micro
        p = _prompt(12, 7, cfg)

        def plain():
            return _engine(cfg, params)

        e1 = plain()
        ref = e1.submit(p, max_new_tokens=4).result().new_tokens
        n_progs = len(_program_cache)
        assert "constrained" not in e1.stats()
        e1.shutdown()
        e2 = plain()
        r = e2.submit(p, max_new_tokens=4).result()
        assert len(_program_cache) == n_progs      # same cache keys: all hits
        assert r.new_tokens == ref
        e2.shutdown()

    def test_constrained_engine_uses_distinct_cache_entries(self, micro):
        """The constrained static key must NOT collide with the plain one
        (its programs take an extra argument)."""
        cfg, params = micro
        e1 = _engine(cfg, params)
        k1 = e1._static_key()
        e1.shutdown()
        e2 = _engine(cfg, params, constraints=True)
        assert e2._static_key() != k1
        assert e2.stats()["constrained"] is True
        e2.shutdown()

    def test_speculative_plus_constraints_rejected(self, micro):
        cfg, params = micro
        dcfg = llama.Config.from_name("tiny-llama-debug", **MICRO)
        dp = llama.init_params(dcfg, jax.random.PRNGKey(9), dtype=jnp.float32)
        from thunder_tpu.serving import SpecConfig

        with pytest.raises(ValueError, match="speculative"):
            _engine(cfg, params, constraints=True,
                    speculative=SpecConfig(dp, dcfg, K=2))
