"""``tools/closed_loop_sim.py``: the engine's loop on the generator's schedule, on the
host.  Counts only: what it says of a mix is held to the chip's runs in PERF.md."""
import copy
import importlib.util
import os

import pytest

from chipbench import common

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
spec = importlib.util.spec_from_file_location("closed_loop_sim", os.path.join(ROOT, "tools", "closed_loop_sim.py"))
sim = importlib.util.module_from_spec(spec)
spec.loader.exec_module(sim)

COSTS = (13.4e-3, 25e-9, 79e-6)
SEEDS = [1000 + 7 * i for i in range(8)] + [3_000_000_000 + 13 * i for i in range(8)]


def mix_with(new_tokens=None):
    mix = copy.deepcopy(common.open_cell("trinity-mini-serve-1chip.offline-docqa")[2])
    if new_tokens:
        rank = {n: i for i, n in enumerate(sorted({n for _, n in mix["group"]}))}
        mix["group"] = [[p, new_tokens[rank[n]]] for p, n in mix["group"]]
    return mix


@pytest.mark.parametrize("seed", [3, 2**31 + 5])
def test_a_window_is_its_seeds_alone_and_counts_every_row(seed):
    mix = mix_with()
    rate, steps, prefills = sim.window(mix, seed, 30.0, *COSTS)
    assert (rate, steps, prefills) == sim.window(mix, seed, 30.0, *COSTS)
    slots = mix["engine"]["max_batch"]
    # every slot decodes at every step (the backlog keeps them full), a prefill gives its first token
    assert 30.0 <= (slots * steps + prefills) / rate < 31.0
    assert 20 <= prefills <= 26 and 1000 < steps < 1200


def test_a_backlog_that_outlasts_the_window_is_steady_and_one_that_turns_over_is_not():
    outlasting = mix_with([1296 + 16 * j for j in range(16)])
    runs = [sim.window(outlasting, s, 30.0, *COSTS) for s in SEEDS]
    assert {r[2] for r in runs} == {20} and sim.spread([r[0] for r in runs]) < 0.002
    turning = mix_with([512 + 64 * j for j in range(16)])
    runs = [sim.window(turning, s, 30.0, *COSTS) for s in SEEDS]
    assert len({r[2] for r in runs}) > 1                    # a seed's order decides the last prefill


def test_a_cheaper_prefill_gives_more_decode_steps():
    mix = mix_with()
    slow = sim.window(mix, 7, 30.0, *COSTS)
    fast = sim.window(mix, 7, 30.0, COSTS[0], COSTS[1], COSTS[2] / 2)
    assert fast[1] > slow[1] and fast[0] > slow[0]
