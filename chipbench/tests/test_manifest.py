"""BENCHMARK.json against the contract's rules of form, and against the files it names."""
import os
import re

import pytest

from chipbench import common

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
M = common.manifest()


def test_names_and_units_hold_only_the_allowed_characters():
    names = []
    for part in ("configs", "workloads", "end_to_end", "per_layer"):
        for e in M[part]:
            assert NAME.match(e["name"]), e["name"]
            names.append((part in ("end_to_end", "per_layer"), e["name"]))
    assert len(names) == len(set(names))
    for m in M["end_to_end"] + M["per_layer"]:
        assert UNIT.match(m["unit"]), m
        assert m["better"] in ("lower", "higher")
        assert m["source"] in ("device_trace", "program_span", "program_counter", "host_clock")
    for w in M["workloads"]:
        assert NAME.match(w["config"]) and NAME.match(w["traffic"]) and w["chips"] in (1, 4)
        assert w["name"] == f"{w['config']}.{w['traffic']}" and len(w["why"]) <= 200
    assert sum(w["chips"] == 4 for w in M["workloads"]) <= max(1, len(M["workloads"]) // 4)


def test_every_entry_has_its_file_and_every_bound_is_in_range():
    cells = {w["name"] for w in M["workloads"]}
    e2e = {m["name"]: m for m in M["end_to_end"]}
    assert "setup_s" in e2e and e2e["setup_s"]["bound"] <= 0.1
    for m in M["end_to_end"]:
        assert 0.01 <= m["bound"] <= 0.1 and m["source"] in ("host_clock", "device_trace")
        assert set(m.get("workloads", cells)) <= cells
    for c in M["configs"]:
        assert c["file"].startswith("chipbench/") and os.path.exists(os.path.join(common.ROOT, c["file"]))
        cfg = common.config_of({"config": c["name"]})
        assert sorted(c["reduced"]) == sorted(cfg["reduced"]) and cfg["source"] == c["source"]
        # what is cut is cut below the published value, which the file keeps beside it
        assert all(cfg[k] < cfg["published_" + k] for k in c["reduced"]), c["name"]
        for mod in ("models/" + cfg["arch"], "drivers/" + cfg["driver"]):
            assert os.path.exists(os.path.join(common.HERE, mod + ".py"))
    for w in M["workloads"]:
        assert os.path.exists(os.path.join(common.HERE, "traffic", w["traffic"] + ".json"))
    for m in M["per_layer"]:
        assert m["moves"] in e2e and set(m.get("workloads", cells)) <= cells
        reader = common.load_reader(m["name"])
        assert callable(reader.read), m["name"]
        # a share of a roofline or of a peak is a fraction of 1, and run.py holds it to 1.05
        if any(part in m["name"] for part in ("roofline", "mfu", "peak")):
            assert m["unit"] == "fraction" and reader.SHARE_OF_PEAK, m["name"]
        mover = set(e2e[m["moves"]].get("workloads", cells))
        assert set(m.get("workloads", mover)) <= mover, (m["name"], "moves a metric its cells do not report")
    for w in cells:
        assert any(w in m.get("workloads", cells) for m in M["end_to_end"] if m["name"] != "setup_s")
        assert any(w in m.get("workloads", cells) for m in M["per_layer"])


# the contract's widths: a hidden, intermediate, latent, state or projection size, a key that ends in
# `_dim` or `_rank`, a head size, an expansion factor, the experts a token
WIDTH = re.compile(r"hidden_size|intermediate|latent|state_size|proj|_dim$|_rank$|head_size|expan|per_tok|top_k")


def test_published_widths_are_untouched():
    for c in M["configs"]:
        cfg = common.config_of({"config": c["name"]})
        assert not [k for k in c["reduced"] if WIDTH.search(k)], c["name"]
        # a key the file keeps a published value of is a key the manifest says was cut
        assert {k[len("published_"):] for k in cfg if k.startswith("published_")} == set(c["reduced"])


# ---- one entry a quantity and the end-to-end metric it moves (PR 50) -----------------------------
PER_LAYER = {m["name"]: m for m in M["per_layer"]}
KIND_OF = {"train": "train_tok_per_s_per_chip", "serve": "serve_out_tok_per_s"}
CELLS = {kind: [w["name"] for w in M["workloads"]                         # in the manifest's order
                if w["name"] in next(m for m in M["end_to_end"] if m["name"] == e2e)["workloads"]]
         for kind, e2e in KIND_OF.items()}
# tests/test_op_scopes.py (tier-1; no `benchmark` PR may edit it) holds these six to their seven
# split names, so they are folded by the PR after the one that rewrites that test (PERF.md section 7)
HELD_BY_A_TIER1_TEST = ("mixer", "mlp", "head", "unscoped", "optimizer", "backward")
# what every cell of a kind reports
EVERY_CELL = {"train": ("pallas_share_of_busy", "device_idle_share", "hbm_peak_share"),
              "serve": ("pallas_share_of_busy", "device_idle_share", "hbm_peak_share", "decode_step_device_ms",
                        "prefill_device_ms_per_ktok", "slot_goodput_share", "kv_pool_fill_share",
                        "engine_turnaround_ms", "engine_host_ms_per_step", "decode_ahead_share")}


def _own_file(name):
    return os.path.exists(os.path.join(common.HERE, "layer_metrics", name + ".py"))


def test_every_reader_file_is_reached_by_an_entry_and_every_entry_by_a_file():
    reached = {n if _own_file(n) else n.rpartition(".")[0] for n in PER_LAYER}
    files = {f[:-3] for f in os.listdir(os.path.join(common.HERE, "layer_metrics")) if f.endswith(".py")}
    assert files - reached == set(), "readers no entry reaches"
    assert reached - files == set(), "entries no file serves"


def test_a_quantity_one_reader_serves_has_one_entry_an_end_to_end_metric():
    shared = {}                      # quantity -> the entries that fall back to `<quantity>.py`
    for n, m in PER_LAYER.items():
        q, dot, _ = n.rpartition(".")
        if dot and not _own_file(n):
            shared.setdefault(q, []).append(m)
    held = {f"{q}_share_of_busy" for q in HELD_BY_A_TIER1_TEST}
    for q, entries in shared.items():
        if q not in held:
            assert len({m["moves"] for m in entries}) == len(entries), (q, [m["name"] for m in entries])
    for kind, e2e in KIND_OF.items():
        for m in (m for n, m in PER_LAYER.items() if n.endswith("." + kind)):
            assert m["moves"] == e2e and set(m["workloads"]) <= set(CELLS[kind]), m["name"]
            assert m["workloads"] == sorted(m["workloads"], key=CELLS[kind].index), m["name"]
    assert held <= set(shared) and len(M["per_layer"]) <= 96          # 87 with the six unfolded, 65 without


@pytest.mark.parametrize("kind", sorted(KIND_OF))
def test_every_cell_is_in_the_list_of_each_quantity_its_kind_reports(kind):
    for q in EVERY_CELL[kind]:
        assert PER_LAYER[f"{q}.{kind}"]["workloads"] == CELLS[kind], q


def test_no_quantity_is_listed_twice_for_a_cell():
    for w in (w["name"] for w in M["workloads"]):
        quantities = [n.partition(".")[0] for n, m in PER_LAYER.items() if w in m.get("workloads", [w])]
        assert len(quantities) == len(set(quantities)), (w, sorted(q for q in quantities if quantities.count(q) > 1))
