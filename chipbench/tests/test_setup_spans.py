"""`chipbench/setup_spans.py` on a hand-written ring (`data/setup_ring.json`): the buckets, the
rules a stretch is put into one by, the six readers over it, and the table by hand from a file."""
import json

import pytest

from chipbench import common, setup_spans

DOC = common.load_json("tests", "data", "setup_ring.json")
T0 = DOC["t_process"] * 1e6
T1 = T0 + DOC["setup_s"] * 1e6
ENTRIES = {"setup_import_s": "import_s", "setup_prefill_programs_s": "prefill_programs_s",
           "setup_decode_programs_s": "decode_programs_s", "setup_step_programs_s": "step_programs_s",
           "setup_other_programs_s": "other_programs_s", "setup_unspanned_s": "unspanned_s"}


def test_every_second_of_set_up_is_in_one_bucket():
    table = setup_spans.split(DOC["events"], T0, T1)
    assert {k: table[k] for k in DOC["expected"]} == DOC["expected"]
    assert sum(table[b] for b in (*setup_spans.BUCKETS, "unspanned_s")) == table["setup_s"] == DOC["setup_s"]
    assert table["ring_events"] == len(DOC["events"])
    for bucket, rows in DOC["expected_by_program"].items():
        assert table["by_program"][bucket] == rows and list(table["by_program"][bucket]) == list(rows)


@pytest.mark.parametrize("name, args, bucket", [
    ("serve.compile", {"kind": "prefill_chunk_paged"}, "prefill_programs_s"),
    ("serve.compile", {"kind": "spec_prefill"}, "prefill_programs_s"),
    ("serve.compile", {"kind": "decode_multi_paged"}, "decode_programs_s"),
    ("serve.compile", {"kind": "draft_decode"}, "decode_programs_s"),
    ("serve.compile", {"kind": "verify_paged"}, "decode_programs_s"),
    ("xla_compile", {"fn": "train_step"}, "step_programs_s"),
    ("xla_compile", {"fusion": "XLA0", "ops": 4}, "pipeline_s"),
    ("compile", {}, "pipeline_s"),
    ("train.snapshot", {"bytes": 1}, "snapshot_s"),
    ("jax.lower", {"fun_name": "f"}, "other_programs_s"),
    ("serve.recover", {"cause": "manual"}, None), ("lower", {}, None), ("serve.step", {"step": 3}, None),
])
def test_a_stretch_opens_the_bucket_its_name_and_arguments_say(name, args, bucket):
    assert setup_spans.bucket_of(name, args) == bucket


def test_the_main_threads_stretch_takes_a_second_two_threads_spent():
    table = setup_spans.split(DOC["events"], T0, T1)
    # thread 2 compiled from 139 to 141: the main thread was under a stretch of the same bucket until 140
    assert table["overlap_s"] == 1.0
    other = setup_spans.split(DOC["events"], T0, T1, main_thread=(7, 2))
    assert other["overlap_s"] == 1.0 and other["other_programs_s"] == table["other_programs_s"]
    assert other["unspanned_s"] == table["unspanned_s"]


def test_the_window_clips_what_straddles_it_and_drops_what_lies_outside():
    early = setup_spans.split(DOC["events"], T0, T0 + 15e6)         # the window opens 15 s in
    assert early["import_s"] == 8.0 and early["prefill_programs_s"] == 5.0 and early["decode_programs_s"] == 0.0
    assert early["unspanned_s"] == 2.0 and early["setup_s"] == 15.0


@pytest.mark.parametrize("entry", sorted(ENTRIES))
def test_a_reader_reads_its_bucket_and_nothing_from_a_full_ring_or_a_ring_without_an_import(entry, monkeypatch):
    from thunder_tpu import observability as obs

    reader = common.load_reader(entry)
    monkeypatch.setattr(obs, "events", lambda: DOC["events"])
    ctx = {"t_process": DOC["t_process"], "setup_s": DOC["setup_s"]}
    assert reader.read(ctx) == DOC["expected"][ENTRIES[entry]] and "setup_split" in ctx
    monkeypatch.setattr(obs, "events", lambda: [e for e in DOC["events"] if e["name"] != "import"])
    assert reader.read({"t_process": DOC["t_process"], "setup_s": DOC["setup_s"]}) is None
    monkeypatch.setattr(obs, "events", lambda: DOC["events"])
    monkeypatch.setattr(obs, "event_buffer_capacity", lambda: len(DOC["events"]))
    assert reader.read({"t_process": DOC["t_process"], "setup_s": DOC["setup_s"]}) is None


def test_by_hand_a_chrome_trace_file_gives_the_same_table(tmp_path, monkeypatch, capsys):
    path = tmp_path / "compile_trace.json"
    meta = [{"ph": "M", "name": "process_name", "pid": 7, "tid": 0, "args": {"name": "thunder_tpu compile pipeline"}}]
    inside = [e for e in DOC["events"] if e["ts"] < T1 - 1e6]       # a file's set-up is the whole file
    path.write_text(json.dumps({"traceEvents": meta + inside, "displayTimeUnit": "ms"}))
    monkeypatch.setattr("sys.argv", ["setup_spans.py", str(path)])
    setup_spans.main()
    table = json.loads(capsys.readouterr().out)
    # from the import's start (100.5) to the last event (a request's async span opens at 143)
    assert table["setup_s"] == 42.5 and table["import_s"] == 8.0 and table["step_programs_s"] == 6.0
    assert table["other_programs_s"] == 4.25 and table["unspanned_s"] == 42.5 - 36.75
