"""The readers of the program's ``span`` events (harness/spans.py and the
seven ``layer_metrics`` files built on it), on a small recorded ledger
(``data/recorded_ledger.jsonl``: a tiny CPU rehearsal of the tune cell, cut
after the fifth ``program.call`` — control flow only, no device number), and
the rehearsal's line."""

import json
import os
import sys

import pytest

from benchmark.harness import spans

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
RECORDED = os.path.join(HERE, "data", "recorded_ledger.jsonl")
TUNE = "sd15-tune-8f.steps"
METRICS = {
    "setup_models_s.tune": "models",
    "setup_clip_s.tune": "clip",
    "setup_trace_lower_s.tune": "trace_lower",
    "setup_load_s.tune": "load",
    "setup_analysis_s.tune": "analysis",
    "setup_unattributed_s.tune": "unattributed",
}


def _events():
    with open(RECORDED) as f:
        return [json.loads(line) for line in f]


def _ctx(calls=3):
    return {"cell": {"name": TUNE}, "window": {"calls": [{}] * calls}}


@pytest.fixture
def ledger(tmp_path, monkeypatch):
    """``write(events)`` puts a ledger where the readers look for it."""
    path = tmp_path / "ledger.jsonl"
    monkeypatch.setattr(spans, "ledger_path", lambda ctx: str(path))

    def write(events):
        path.write_text("".join(json.dumps(e) + "\n" for e in events))

    return write


def _reader(metric):
    sys.path.insert(0, BENCH)
    import run as bench_run

    return bench_run.find_reader(metric)


def _by_name(events, name, parent=None):
    return [e for e in events if e.get("event") == "span"
            and e["name"] == name
            and (parent is None or e["parent_id"] == parent["span_id"])]


def test_ledger_path_is_the_drivers():
    want = os.path.join(os.path.dirname(BENCH), "outputs", "bench", TUNE,
                        "tune", "ledger.jsonl")
    assert spans.ledger_path(_ctx()) == want


def test_seven_readers_on_the_recorded_ledger(ledger):
    events = _events()
    ledger(events)
    root, = _by_name(events, "tune.setup")
    first = min((c for c in _by_name(events, "program.call", root)),
                key=lambda c: c["wall_ns"])

    def dur(name, parent):
        return sum(e["duration_s"] for e in _by_name(events, name, parent))

    want = {
        "models": dur("tune.build_models", root)
        + dur("tune.state_create", root),
        "clip": dur("tune.load_clip", root) + dur("tune.vae_encode", root)
        + dur("tune.text_encode", root),
        "trace_lower": dur("program.trace", first)
        + dur("program.lower", first),
        "load": dur("program.backend_compile", first),
        "analysis": dur("program.analysis", first),
    }
    got = {m: _reader(m).read(_ctx()) for m in METRICS}
    for metric, key in METRICS.items():
        assert got[metric] is not None and got[metric] > 0, metric
        if key in want:
            assert got[metric] == pytest.approx(want[key], rel=1e-6), metric
    # the six and the first call's execute make up tune.setup by construction
    parts = spans.setup_parts(_ctx())
    assert parts["execute"] == pytest.approx(dur("program.execute", first))
    assert sum(got.values()) + parts["execute"] == pytest.approx(
        root["duration_s"], rel=1e-9)
    gap = _reader("host_between_calls_ms.tune").read(_ctx())
    assert gap is not None and 0 < gap < 1e3


def test_none_never_zero_where_a_span_or_the_ledger_is_missing(ledger):
    ctx = _ctx()
    everything = list(METRICS) + ["host_between_calls_ms.tune"]
    # no ledger file at all
    assert [_reader(m).read(ctx) for m in everything] == [None] * 7
    # a ledger with no span in it: the program before it had any
    ledger([e for e in _events() if e["event"] != "span"])
    assert [_reader(m).read(ctx) for m in everything] == [None] * 7
    # one span missing: its metric and the remainder are None, the rest read
    ledger([e for e in _events() if e.get("name") != "tune.vae_encode"])
    assert _reader("setup_clip_s.tune").read(ctx) is None
    assert _reader("setup_unattributed_s.tune").read(ctx) is None
    assert _reader("setup_models_s.tune").read(ctx) > 0
    assert _reader("setup_load_s.tune").read(ctx) > 0
    # no root: nothing under it can be told from a later call's
    ledger([e for e in _events() if e.get("name") != "tune.setup"])
    assert [_reader(m).read(ctx) for m in METRICS] == [None] * 6
    # a torn last line and a malformed span are skipped, not raised on
    good = _events()
    ledger(good)
    with open(spans.ledger_path(ctx), "a") as f:
        f.write(json.dumps({"event": "span", "span_id": "x", "name": "y",
                            "wall_ns": None, "duration_s": 1.0}) + "\n")
        f.write('{"event": "span", "name": "tune.se')
    assert _reader("setup_models_s.tune").read(ctx) > 0


def test_spans_under_the_analysis_are_counted_there_and_nowhere_else(ledger):
    events = _events()
    root, = _by_name(events, "tune.setup")
    first = min(_by_name(events, "program.call", root),
                key=lambda c: c["wall_ns"])
    analysis, = _by_name(events, "program.analysis", first)
    inside = [e for e in events if e.get("parent_id") == analysis["span_id"]]
    assert {e["name"] for e in inside} >= {"program.trace", "program.lower",
                                           "program.backend_compile"}
    ledger(events)
    before = spans.setup_parts(_ctx())
    # ten seconds more of tracing and loading INSIDE the analysis
    grown = []
    for e in events:
        e = dict(e)
        if e.get("parent_id") == analysis["span_id"]:
            e["duration_s"] += 10.0
        grown.append(e)
    ledger(grown)
    after = spans.setup_parts(_ctx())
    assert after["trace_lower"] == before["trace_lower"]
    assert after["load"] == before["load"]
    assert after["analysis"] == before["analysis"]  # its own span's length
    # and the analysis pass's own length is what the metric reads
    assert before["analysis"] == pytest.approx(analysis["duration_s"])
    assert before["trace_lower"] < analysis["duration_s"] + before["load"]


def test_host_between_calls_reads_the_windows_calls_only(ledger):
    events = _events()
    ledger(events)
    calls = sorted((e for e in events if e.get("event") == "span"
                    and e["name"] == "program.call"),
                   key=lambda e: e["wall_ns"])
    assert len(calls) == 5  # set-up call, three in the window, one traced

    def gap(a, b):
        return (b["wall_ns"] - a["wall_ns"]) * 1e-9 - a["duration_s"]

    want = 1e3 * (gap(calls[1], calls[2]) + gap(calls[2], calls[3])) / 2
    assert spans.host_between_calls_ms(_ctx(3)) == pytest.approx(want)
    # the gap before the traced call (profiler start) is not in it
    assert spans.host_between_calls_ms(_ctx(4)) == pytest.approx(
        1e3 * (gap(calls[1], calls[2]) + gap(calls[2], calls[3])
               + gap(calls[3], calls[4])) / 3)
    assert spans.host_between_calls_ms(_ctx(1)) is None  # no gap in a window of one
    assert spans.host_between_calls_ms(_ctx(9)) is None  # fewer spans than calls


def test_union_and_self_time():
    def s(sid, parent, start, dur, name="x"):
        return {"span_id": sid, "parent_id": parent, "name": name,
                "wall_ns": int(start * 1e9), "duration_s": dur}

    spans_ = [s("r", None, 0.0, 10.0), s("a", "r", 1.0, 2.0),
              s("b", "r", 2.0, 3.0), s("c", "r", 7.0, 1.0)]
    assert spans.union_s(spans_[1:]) == pytest.approx(5.0)  # [1,5] + [7,8]
    tree = spans.Tree(spans_)
    assert tree.self_s(spans_[0]) == pytest.approx(5.0)
    assert tree.self_s(spans_[1]) == pytest.approx(2.0)


def test_the_names_read_are_the_programs_tuple():
    """The program keeps the same tuple (``BENCHMARK_SPAN_NAMES``); where
    this checkout has no such program (the parent of the PR that added the
    spans) there is nothing to hold it to."""
    pytest.importorskip("videop2p_tpu.obs.spans")
    from videop2p_tpu.obs import spans as program_spans

    names = getattr(program_spans, "BENCHMARK_SPAN_NAMES", None)
    if names is None:
        pytest.skip("this program has no span vocabulary")
    assert tuple(names) == spans.READ_NAMES


def test_rehearsal_prints_all_seven(capsys):
    sys.path.insert(0, BENCH)
    import run as bench_run

    rc = bench_run.main(["--workload", TUNE, "--seed", "11", "--seconds",
                         "1", "--trace", "1", "--rehearse"])
    err = capsys.readouterr().err
    assert rc == 3
    line = json.loads(err.split("REHEARSAL (no result): ")[-1]
                      .splitlines()[0])
    for metric in list(METRICS) + ["host_between_calls_ms.tune"]:
        assert line["metrics"][metric]["value"] is not None, metric
    values = {m: line["metrics"][m]["value"] for m in METRICS}
    assert all(v >= 0 for k, v in values.items()
               if k != "setup_unattributed_s.tune")
