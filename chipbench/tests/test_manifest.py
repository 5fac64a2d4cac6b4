"""BENCHMARK.json against the contract's rules of form, and against the files it names."""
import os
import re

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
        assert cfg["num_hidden_layers"] < cfg["published_num_hidden_layers"]
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


def test_published_widths_are_untouched():
    for c in M["configs"]:
        cfg = common.config_of({"config": c["name"]})
        assert (cfg["hidden_size"], cfg["intermediate_size"], cfg["num_attention_heads"],
                cfg["num_key_value_heads"], cfg["head_dim"], cfg["vocab_size"],
                cfg["sliding_window"]) == (4096, 14336, 32, 8, 128, 32000, 4096)
