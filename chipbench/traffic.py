"""The one traffic generator.  A mix is a data file (``traffic/<mix>.json``);
this module turns it and a seed into the requests of a run.

What a mix fixes, and the seed cannot move: the multiset of
``(prompt_len, new_tokens)`` pairs.  It is written as one *group*, and a run
is the group repeated.  What the seed moves: the order inside each group
(with ``stratum``, inside each run of that many requests of the group, so
that any stretch of a run holds nearly the same work whatever the seed), the
token ids and the weights.  So two seeds offer the same requests, prompt
tokens and new tokens in any whole number of groups, and differ in order
only.

Loops:
- ``steps``: no requests; a fixed batch of ``sequences_per_chip`` sequences
  of ``seq_len`` tokens a chip, for a training step run back to back.
- ``closed``: ``lead_in`` (one group, run first, fills the slots at
  staggered ages) then ``group`` repeated ``groups`` times, all submitted
  before the window: a backlog.

An open loop (arrivals at a fixed rate) is not here yet: PERF.md, Open
questions, has the mix that needs it.
"""
from __future__ import annotations

import dataclasses

import numpy as np


@dataclasses.dataclass(frozen=True)
class Req:
    index: int          # position in the run, which seeds its token ids
    prompt_len: int
    new_tokens: int


def _rng(seed: int, *stream: int) -> np.random.Generator:
    return np.random.default_rng([seed & 0xFFFFFFFF, seed >> 32, *stream])


def _shuffled(seed: int, stream: int, g: int, items: list, stratum: int = 0) -> list:
    """``items`` in a seeded order: shuffled whole, or, with ``stratum``,
    inside each run of that many consecutive items, the runs staying in place."""
    rng, n = _rng(seed, stream, g), len(items)
    step = stratum or n or 1
    return [items[lo + i] for lo in range(0, n, step)
            for i in rng.permutation(min(step, n - lo))]


def schedule(mix: dict, seed: int) -> list[Req]:
    """The run's requests in the order they are submitted."""
    if mix["loop"] != "closed":
        raise ValueError(f"traffic loop {mix['loop']!r} makes no requests")
    pairs = _shuffled(seed, 1, 0, mix["lead_in"])
    for g in range(mix["groups"]):
        pairs += _shuffled(seed, 2, g, mix["group"], mix.get("stratum", 0))
    return [Req(i, int(p), int(n)) for i, (p, n) in enumerate(pairs)]


def totals(reqs: list[Req]) -> dict:
    return {"requests": len(reqs), "prompt_tokens": sum(r.prompt_len for r in reqs),
            "new_tokens": sum(r.new_tokens for r in reqs)}


def prompt_tokens(seed: int, index: int, length: int, vocab: int) -> np.ndarray:
    return _rng(seed, 7, index).integers(0, vocab, (length,)).astype(np.int32)


def train_batch(mix: dict, seed: int, chips: int, vocab: int):
    """The fixed batch of a ``steps`` mix: inputs and next-token targets."""
    shape = (mix["sequences_per_chip"] * chips, mix["seq_len"] + 1)
    toks = _rng(seed, 11).integers(0, vocab, shape).astype(np.int32)
    return toks[:, :-1], toks[:, 1:]
