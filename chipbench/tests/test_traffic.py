"""The generator: what a mix offers does not move with the seed; its order does."""
import glob
import os

import pytest

from chipbench import common, traffic

MIXES = sorted(os.path.basename(p)[:-5] for p in glob.glob(os.path.join(common.HERE, "traffic", "*.json")))
REQUEST_MIXES = [m for m in MIXES if common.load_json("traffic", m + ".json")["loop"] != "steps"]
SEEDS = (1, 2, 3_000_000_019)


@pytest.mark.parametrize("name", REQUEST_MIXES)
def test_totals_equal_across_seeds_and_order_is_not(name):
    mix = common.load_json("traffic", name + ".json")
    runs = [traffic.schedule(mix, s) for s in SEEDS]
    totals = [traffic.totals(r) for r in runs]
    assert totals[0] == totals[1] == totals[2] and totals[0]["requests"] > 0
    orders = [[(r.prompt_len, r.new_tokens) for r in run] for run in runs]
    assert sorted(orders[0]) == sorted(orders[1]) == sorted(orders[2])
    assert orders[0] != orders[1] and orders[1] != orders[2]


def test_stratum_keeps_every_run_of_requests_equal_in_work():
    mix = common.load_json("traffic", "offline-batch.json")
    k, lead = mix["stratum"], len(mix["lead_in"])
    sums = set()
    for seed in SEEDS:
        run = traffic.schedule(mix, seed)[lead:]
        for lo in range(0, len(run), k):
            part = run[lo:lo + k]
            sums.add(sum(r.new_tokens for r in part))
            assert 7100 <= sum(r.prompt_len for r in part) <= 7200
    assert sums == {768}


def test_token_ids_and_train_batch_come_from_the_seed():
    a = traffic.prompt_tokens(5, 3, 64, 32000)
    assert (a == traffic.prompt_tokens(5, 3, 64, 32000)).all()
    assert (a != traffic.prompt_tokens(6, 3, 64, 32000)).any()
    assert (a != traffic.prompt_tokens(5, 4, 64, 32000)).any()
    mix = common.load_json("traffic", "seq8k.json")
    idx, tgt = traffic.train_batch(mix, 2**31 + 7, 4, 32000)
    assert idx.shape == tgt.shape == (4, 8192) and (idx[:, 1:] == tgt[:, :-1]).all()
