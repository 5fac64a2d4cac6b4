"""Constrained decoding: per-request logit masks as program *arguments*.

Structured output (JSON fields, grammar-limited tool calls, enum answers)
is implemented the same way LoRA adapters are: nothing about a *schema*
ever reaches program identity.  A constrained engine
(``serve(..., constraints=True)``) compiles decode/prefill programs with
ONE extra argument — a boolean token mask — and every schema, automaton,
or allow-list is pure data fed through that argument:

- the engine keeps the automaton **host-side** on the request
  (:class:`Constraint` instances are plain Python state machines);
- at every dispatch the host asks each constrained row for its mask
  over the next draw (:meth:`Constraint.mask`, the one call the engine
  makes) and ships a ``(B, V)`` bool tensor;
- inside the program the mask is applied as
  ``logits = where(mask, logits, -inf)`` immediately before
  :func:`sample_token`, so greedy argmax and temperature sampling both
  respect it;
- at harvest the engine advances the automaton with the emitted token
  (:meth:`Constraint.advance`), exactly where the PRNG key chain
  advances — so recovery replay and preemption resume need no special
  constraint handling: the automaton is host state that never lived on
  the device.

Unconstrained rows in a constrained batch get an all-``True`` mask;
``where(True, logits, -inf)`` returns the logits bit-identically, so
their sampled tokens match an unconstrained engine exactly.  The
``constraints=`` knob joins ``_static_key()`` as a component that
collapses to ``None`` when off — the off-path compiles byte-identical
programs (same module-cache entries) as an engine built before this
module existed.
"""

from __future__ import annotations

import numpy as np

__all__ = [
    "Constraint",
    "TokenSetConstraint",
    "DFAConstraint",
    "sequence_constraint",
]


class Constraint:
    """Base class for host-side decoding automata.

    Subclasses implement :meth:`mask` (allowed tokens *now*) and
    :meth:`advance` (consume one emitted token).  ``vocab_size`` must
    match the model's logit width (``padded_vocab_size``) — the engine
    checks at ``submit()``.
    """

    def __init__(self, vocab_size: int):
        self.vocab_size = int(vocab_size)

    # -- required interface -------------------------------------------------
    def mask(self) -> np.ndarray:
        """``(vocab_size,)`` bool — tokens permitted for the next draw."""
        raise NotImplementedError

    def advance(self, token: int) -> None:
        """Consume one emitted token, moving the automaton forward."""
        raise NotImplementedError


class TokenSetConstraint(Constraint):
    """A stationary allow-list: every draw must come from ``allowed_ids``.

    The simplest useful schema (digits only, yes/no, an enum of tool
    names).
    """

    def __init__(self, vocab_size: int, allowed_ids):
        super().__init__(vocab_size)
        ids = np.asarray(sorted(set(int(t) for t in allowed_ids)), dtype=np.int64)
        if ids.size == 0:
            raise ValueError("TokenSetConstraint needs at least one allowed id")
        if ids.min() < 0 or ids.max() >= self.vocab_size:
            raise ValueError(
                f"allowed ids must lie in [0, {self.vocab_size}), got "
                f"[{ids.min()}, {ids.max()}]")
        self._mask = np.zeros(self.vocab_size, dtype=bool)
        self._mask[ids] = True

    def mask(self) -> np.ndarray:
        return self._mask

    def advance(self, token: int) -> None:
        if not self._mask[int(token)]:
            raise ValueError(
                f"token {int(token)} violates TokenSetConstraint")


class DFAConstraint(Constraint):
    """A token-level DFA: ``transitions[state, token] -> next state | -1``.

    ``transitions`` is an ``(n_states, vocab_size)`` int array; ``-1``
    marks a forbidden token.  The grammar — a JSON skeleton, a CSV row
    shape, a tool-call syntax — is entirely in the table, which is plain
    data: registering a new grammar compiles nothing.
    """

    def __init__(self, transitions, start: int = 0):
        table = np.asarray(transitions, dtype=np.int64)
        if table.ndim != 2:
            raise ValueError("transitions must be (n_states, vocab_size)")
        super().__init__(table.shape[1])
        if not (0 <= start < table.shape[0]):
            raise ValueError(f"start state {start} out of range")
        bad = (table < -1) | (table >= table.shape[0])
        if bad.any():
            raise ValueError("transitions entries must be -1 or a valid state")
        self._table = table
        self._start = int(start)
        self.state = int(start)

    def mask(self) -> np.ndarray:
        return self._table[self.state] >= 0

    def advance(self, token: int) -> None:
        nxt = int(self._table[self.state, int(token)])
        if nxt < 0:
            raise ValueError(
                f"token {int(token)} forbidden in DFA state {self.state}")
        self.state = nxt

    def reset(self) -> None:
        self.state = self._start


def sequence_constraint(vocab_size: int, steps, *, cycle: bool = False) -> DFAConstraint:
    """Build a position-determined DFA from per-step allow-lists.

    ``steps`` is a sequence of token-id collections: draw ``k`` must come
    from ``steps[k]``; after the last step the automaton either repeats
    the final step forever (``cycle=False``) or wraps to step 0
    (``cycle=True`` — e.g. ``digit, comma, digit, comma, ...``).
    """
    steps = [sorted(set(int(t) for t in s)) for s in steps]
    if not steps or any(not s for s in steps):
        raise ValueError("steps must be non-empty allow-lists")
    n = len(steps)
    table = np.full((n, vocab_size), -1, dtype=np.int64)
    for k, allowed in enumerate(steps):
        nxt = (k + 1) % n if cycle else min(k + 1, n - 1)
        for t in allowed:
            if not (0 <= t < vocab_size):
                raise ValueError(f"token id {t} out of range")
            table[k, t] = nxt
    return DFAConstraint(table)
