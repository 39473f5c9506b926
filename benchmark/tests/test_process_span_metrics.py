"""The readers of the set-up's spans before and beside the root
(harness/process_spans.py and the three ``layer_metrics`` files built on it),
on a small recorded ledger (``data/recorded_process_ledger.jsonl``: a tiny CPU
``run_tuning.main`` in a process of its own, cut after its third
``program.call`` — control flow only, no device number), on the ledger of a
program from before these spans (``data/recorded_ledger.jsonl``), and the
rehearsal's line."""

import json
import os
import sys

import pytest

from benchmark.harness import process_spans, spans

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
RECORDED = os.path.join(HERE, "data", "recorded_process_ledger.jsonl")
PARENT = os.path.join(HERE, "data", "recorded_ledger.jsonl")
TUNE = "sd15-tune-8f.steps"
METRICS = ("setup_before_program_s.tune", "setup_import_s.tune",
           "setup_tensorboard_s.tune")


def _events(path=RECORDED):
    with open(path) as f:
        return [json.loads(line) for line in f]


def _ctx():
    return {"cell": {"name": TUNE}, "window": {"calls": [{}, {}]}}


@pytest.fixture
def ledger(tmp_path, monkeypatch):
    """``write(events)`` puts a ledger where the readers look for it."""
    path = tmp_path / "ledger.jsonl"
    monkeypatch.setattr(spans, "ledger_path", lambda ctx: str(path))
    monkeypatch.setattr(process_spans, "ledger_path", lambda ctx: str(path))

    def write(events):
        path.write_text("".join(json.dumps(e) + "\n" for e in events))

    return write


def _reader(metric):
    sys.path.insert(0, BENCH)
    import run as bench_run

    return bench_run.find_reader(metric)


def _read_all():
    return [_reader(m).read(_ctx()) for m in METRICS]


def _one(events, name):
    found = [e for e in events if e.get("event") == "span"
             and e["name"] == name]
    assert len(found) == 1, name
    return found[0]


def test_three_readers_on_the_recorded_ledger(ledger):
    events = _events()
    ledger(events)
    process = _one(events, "process")
    imported = _one(events, "process.import")
    writer = _one(events, "metrics.tensorboard_writer")
    assert process["anchor"] == "proc"
    assert imported["parent_id"] == process["span_id"]
    assert writer["parent_id"] == _one(events, "tune.metrics_logger")["span_id"]
    before, import_s, tensorboard = _read_all()
    assert before == pytest.approx(
        (imported["wall_ns"] - process["wall_ns"]) * 1e-9)
    assert import_s == pytest.approx(imported["duration_s"])
    assert tensorboard == pytest.approx(writer["duration_s"])
    assert min(before, import_s, tensorboard) > 0
    # `process` ends where the root starts: what the program's spans cover
    # of the set-up reaches back to the process's start
    root = _one(events, "tune.setup")
    end_ns = process["wall_ns"] + process["duration_s"] * 1e9
    assert end_ns == pytest.approx(root["wall_ns"], abs=1e6)


def test_none_never_zero_on_a_program_from_before_the_spans(ledger):
    assert _read_all() == [None] * 3  # no ledger file at all
    ledger(_events(PARENT))
    assert _read_all() == [None] * 3
    # one span missing: its metric is None, the others read
    ledger([e for e in _events() if e.get("name") != "process.import"])
    before, import_s, tensorboard = _read_all()
    assert before is None and import_s is None and tensorboard > 0
    ledger([e for e in _events()
            if e.get("name") != "metrics.tensorboard_writer"])
    assert _read_all()[2] is None and _read_all()[0] > 0


def test_an_import_anchor_has_no_time_before_the_program(ledger):
    """Where the program could not read the kernel's start it starts the
    span at its own first line: there is nothing before it to read."""
    events = []
    for e in _events():
        if e.get("name") == "process":
            e = dict(e, anchor="import")
        events.append(e)
    ledger(events)
    before, import_s, tensorboard = _read_all()
    assert before is None
    assert import_s > 0 and tensorboard > 0


def test_a_writer_outside_the_root_is_not_the_setups(ledger):
    events = _events()
    logger = _one(events, "tune.metrics_logger")
    ledger([dict(e, parent_id="0" * 16) if e is logger else e
            for e in events])
    assert _read_all()[2] is None


def test_the_names_read_are_the_programs_tuple():
    """The program keeps the same tuple (``BENCHMARK_PROCESS_SPAN_NAMES``);
    where this checkout's program has none (the parent of the PR that added
    the spans) there is nothing to hold it to."""
    pytest.importorskip("videop2p_tpu.obs.spans")
    from videop2p_tpu.obs import spans as program_spans

    names = getattr(program_spans, "BENCHMARK_PROCESS_SPAN_NAMES", None)
    if names is None:
        pytest.skip("this program has no process spans")
    assert tuple(names) == process_spans.READ_NAMES
    recorded = {e["name"] for e in _events() if e["event"] == "span"}
    assert set(process_spans.READ_NAMES) <= recorded


def test_rehearsal_prints_all_three(capsys):
    sys.path.insert(0, BENCH)
    import run as bench_run

    rc = bench_run.main(["--workload", TUNE, "--seed", "2147483659",
                         "--seconds", "1", "--trace", "1", "--rehearse"])
    err = capsys.readouterr().err
    assert rc == 3
    line = json.loads(err.split("REHEARSAL (no result): ")[-1]
                      .splitlines()[0])
    for metric in METRICS:
        assert line["metrics"][metric]["value"] > 0, metric
