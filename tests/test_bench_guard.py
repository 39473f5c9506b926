"""Driver-capture hardening: the bench and the multichip dryrun must produce
machine-readable artifacts even when the real TPU backend is down.

Round 4 lost BOTH driver artifacts to a transiently-unavailable chip (the
records were removed in PR 21): the bench exited 1 when backend init raised
at the first device op, with no JSON line emitted, and ``dryrun_multichip``
probed ``jax.devices()`` in the driver's process and hung with it (rc=124).
These tests pin
the round-5 guards: bounded backend retry with an error record in bench.py,
and a backend-blind re-exec decision in ``__graft_entry__``.
"""

import importlib.util
import json
import os
import subprocess
import sys
import types

import pytest

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _load_module(name, filename):
    spec = importlib.util.spec_from_file_location(name, os.path.join(_REPO, filename))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.fixture(scope="module")
def bench():
    return _load_module("bench_under_test", "bench.py")


@pytest.fixture(scope="module")
def graft():
    return _load_module("graft_under_test", "__graft_entry__.py")


# ---------------------------------------------------------------- bench.py --


def test_wait_for_backend_retries_then_succeeds(bench):
    calls = {"probe": 0, "slept": []}

    def probe():
        calls["probe"] += 1
        return calls["probe"] >= 3  # down for two probes, then healthy

    ok = bench.wait_for_backend(
        attempts=5, _probe=probe, _sleep=calls["slept"].append
    )
    assert ok
    assert calls["probe"] == 3
    # backed off once per failed probe, with the documented escalation
    assert calls["slept"] == [10.0, 20.0]


def test_wait_for_backend_gives_up_after_bounded_attempts(bench):
    calls = {"probe": 0, "slept": []}

    def probe():
        calls["probe"] += 1
        return False

    ok = bench.wait_for_backend(
        attempts=5, _probe=probe, _sleep=calls["slept"].append
    )
    assert not ok
    assert calls["probe"] == 5
    # no sleep after the final failure — the driver's clock is precious
    assert len(calls["slept"]) == 4
    # total backoff stays within the ~3-minute budget VERDICT r4 item 1 set
    assert sum(calls["slept"]) <= 200.0


def test_unavailable_backend_still_emits_one_parseable_line(bench, capsys):
    bench.emit_backend_unavailable()
    lines = [l for l in capsys.readouterr().out.splitlines() if l.strip()]
    assert len(lines) == 1
    rec = json.loads(lines[0])
    assert rec["error"] == "backend_unavailable"
    assert rec["metric"] == "fast_edit_e2e_wall"
    assert rec["value"] is None


def test_main_short_circuits_when_backend_unavailable(bench, capsys, monkeypatch):
    # main() must emit the error record and return WITHOUT touching jax —
    # a failed init can be cached for the life of the process. The only
    # extra work allowed after the error line is the CPU cost-analysis
    # capture (subprocess-isolated, ISSUE 3 satellite) — verified invoked.
    monkeypatch.setattr(bench, "wait_for_backend", lambda **kw: False)
    monkeypatch.setattr(
        bench, "build_fast_edit_working_point",
        lambda **kw: pytest.fail("touched the device after a failed probe"),
    )
    called = []
    monkeypatch.setattr(bench, "record_cpu_only_evidence",
                        lambda: called.append(True))
    bench.main()
    rec = json.loads(capsys.readouterr().out.strip())
    assert rec["error"] == "backend_unavailable"
    assert called == [True]


def test_cpu_only_evidence_records_analyses_and_verdicts(
    bench, tmp_path, monkeypatch
):
    """Backend-down evidence path: the subprocess capture's analyses land
    in bench_details.json with regression verdicts vs the previous
    record — no round is evidence-free (VERDICT r5 'What's missing' #1)."""
    details = tmp_path / "bench_details.json"
    # a previous record to regress against: e2e temp bytes grew 50%
    details.write_text(json.dumps({
        "breakdown": {"program_analysis": {
            "e2e_cached": {"flops": 1000, "temp_bytes": 100 * 2**20,
                           "hlo_fingerprint": "aa"},
        }},
    }))
    analyses = {
        "e2e_cached": {"flops": 1000, "temp_bytes": 150 * 2**20,
                       "hlo_fingerprint": "bb"},
        "invert_captured": {"flops": 500, "temp_bytes": 10,
                            "hlo_fingerprint": "cc"},
    }
    frontier = [{"steps": 50, "src_err": 0.0}, {"steps": 8, "src_err": 0.0}]
    monkeypatch.setattr(bench, "collect_cpu_analysis",
                        lambda *a, **kw: analyses)
    monkeypatch.setattr(bench, "collect_step_frontier",
                        lambda **kw: frontier)
    bench.record_cpu_only_evidence(repo_dir=str(tmp_path))
    doc = json.loads(details.read_text())
    bd = doc["breakdown"]
    assert bd["program_analysis"] == analyses
    assert bd["program_analysis_backend"] == "cpu"
    # the ISSUE-8 backend-down evidence rides along: the tiny CPU frontier
    # (disclosed backend) — the unit-flop record skips here because the
    # stubbed capture has no null_text_unit_* programs
    assert bd["latency_quality_frontier"] == frontier
    assert bd["latency_quality_frontier_backend"] == "cpu-tiny"
    assert "null_text_flops_reduction_amortized" not in bd
    # the per-call cost record skips quietly too: the stubbed capture has
    # no unet_unit_*/reuse_unit_* programs (ISSUE 15)
    assert "per_call_cost" not in bd
    v = bd["analysis_verdicts"]
    assert v["baseline"] == "bench_details.json"
    assert v["compared_programs"] == ["e2e_cached"]
    assert not v["pass"]
    regs = {r["metric"] for r in v["regressions"]}
    assert "temp_bytes" in regs
    assert all(r["fingerprint_changed"] for r in v["regressions"]
               if "fingerprint_changed" in r)


def test_cpu_only_evidence_skippable_and_failure_tolerant(
    bench, tmp_path, monkeypatch
):
    # kill-switch: no capture attempted
    monkeypatch.setenv("VIDEOP2P_BENCH_CPU_ANALYSIS", "0")
    monkeypatch.setattr(
        bench, "collect_cpu_analysis",
        lambda *a, **kw: pytest.fail("capture ran despite the kill-switch"),
    )
    bench.record_cpu_only_evidence(repo_dir=str(tmp_path))
    assert not (tmp_path / "bench_details.json").exists()
    # empty capture (timeout before any program finished): readable error
    monkeypatch.setenv("VIDEOP2P_BENCH_CPU_ANALYSIS", "1")
    monkeypatch.setattr(bench, "collect_cpu_analysis", lambda *a, **kw: {})
    monkeypatch.setattr(bench, "collect_step_frontier", lambda **kw: [])
    bench.record_cpu_only_evidence(repo_dir=str(tmp_path))
    doc = json.loads((tmp_path / "bench_details.json").read_text())
    assert "cpu_analysis_error" in doc["breakdown"]
    # an empty frontier records nothing rather than a fake empty table
    assert "latency_quality_frontier" not in doc["breakdown"]


def test_collect_cpu_analysis_parses_partial_output(bench, monkeypatch):
    """A timeout mid-capture keeps the programs whose JSON lines flushed."""
    payload = (
        json.dumps({"program": "invert_captured", "flops": 7}) + "\n"
        + '{"program": "e2e_cached", "flo'  # torn final line
    )

    def fake_run(cmd, **kw):
        raise subprocess.TimeoutExpired(cmd, kw.get("timeout"),
                                        output=payload.encode())

    monkeypatch.setattr(bench.subprocess, "run", fake_run)
    out = bench.collect_cpu_analysis(8, 50, timeout_s=1.0)
    assert out == {"invert_captured": {"flops": 7}}


def test_collect_step_frontier_parses_partial_output(bench, monkeypatch):
    """A timeout mid-frontier keeps the step counts whose lines flushed
    (same contract as collect_cpu_analysis)."""
    payload = (
        json.dumps({"steps": 50, "src_err": 0.0, "edit_s": 1.0}) + "\n"
        + json.dumps({"steps": 20, "src_err": 0.0, "edit_s": 0.5}) + "\n"
        + '{"steps": 8, "src_'  # torn final line
    )

    def fake_run(cmd, **kw):
        raise subprocess.TimeoutExpired(cmd, kw.get("timeout"),
                                        output=payload.encode())

    monkeypatch.setattr(bench.subprocess, "run", fake_run)
    out = bench.collect_step_frontier(timeout_s=1.0)
    assert [r["steps"] for r in out] == [50, 20]


def test_collect_step_frontier_serializes_student_variants(bench, monkeypatch):
    """ISSUE 16: 3-tuple (student_steps, quant, reuse) variants serialize
    to the tool's student:N+qm+rs grammar; 2-tuples stay qm+rs."""
    seen = {}

    def fake_run(cmd, **kw):
        seen["cmd"] = cmd
        return types.SimpleNamespace(stdout="", stderr="", returncode=0)

    monkeypatch.setattr(bench.subprocess, "run", fake_run)
    bench.collect_step_frontier(
        timeout_s=1.0,
        variants=(("w8", "uniform:2"), (2, "off", "off"),
                  (2, "w8", "uniform:2")),
    )
    i = seen["cmd"].index("--variants")
    assert seen["cmd"][i + 1] == (
        "w8+uniform:2,student:2+off+off,student:2+w8+uniform:2"
    )


def test_collect_served_latency_parses_record_and_tolerates_failure(
        bench, monkeypatch):
    """ISSUE 14 satellite: the served-latency capture parses the loadgen's
    final JSON record into the queueing-inclusive e2e percentiles (noise
    lines skipped), and every failure mode — timeout, bad exit, no record
    — degrades to None, never an exception."""
    record = {"requests": 6, "concurrency": 3, "done": 6, "store_hits": 5,
              "shed": 0, "throughput_rps": 1.5,
              "latency": {"blocked_p50_s": 0.1, "blocked_p99_s": 0.4,
                          "blocked_max_s": 0.4}}
    payload = "[loadgen] warming...\n" + json.dumps(record) + "\n"

    def fake_run(cmd, **kw):
        return types.SimpleNamespace(stdout=payload, stderr="",
                                     returncode=0)

    monkeypatch.setattr(bench.subprocess, "run", fake_run)
    out = bench.collect_served_latency(timeout_s=1.0)
    assert out["backend"] == "cpu-tiny" and out["done"] == 6
    assert out["e2e_p50_s"] == 0.1 and out["e2e_p99_s"] == 0.4
    assert "segments" not in out  # fake run wrote no span ledgers

    def fake_timeout(cmd, **kw):
        raise subprocess.TimeoutExpired(cmd, kw.get("timeout"))

    monkeypatch.setattr(bench.subprocess, "run", fake_timeout)
    assert bench.collect_served_latency(timeout_s=1.0) is None

    def fake_fail(cmd, **kw):
        return types.SimpleNamespace(stdout="no json here\n",
                                     stderr="boom", returncode=1)

    monkeypatch.setattr(bench.subprocess, "run", fake_fail)
    assert bench.collect_served_latency(timeout_s=1.0) is None


@pytest.mark.slow
def test_step_frontier_tool_end_to_end_tiny(bench):
    """The ISSUE 8 frontier acceptance, through the real subprocess at tiny
    scale: the 20- and 8-step cached fast-path variants run e2e from ONE
    50-step inversion (exact timestep subsets), the source replay stays
    exact at every step count, and each record carries the quality metrics
    (PSNR/SSIM vs the full-step edit) next to its wall-clock."""
    records = bench.collect_step_frontier(
        timeout_s=560.0, tiny=True, frames=2,
        base_steps=50, step_counts=(50, 20, 8),
        variants=(("w8", "uniform:2"), (2, "w8", "uniform:2")),
    )
    assert [r["steps"] for r in records] == [50, 20, 8, 50, 2]
    for r in records:
        assert r["base_steps"] == 50
        assert r["src_err"] == 0.0, r          # replay exact at any count
        assert r["backend"] == "cpu" and r["tiny"] is True
        assert r["edit_s"] is not None and r["edit_s"] > 0
    for r in records[1:]:  # subset+variant rows score against the full edit
        assert isinstance(r["vs_full_psnr_db"], float)
        assert isinstance(r["vs_full_ssim"], float)
        assert r["speedup_vs_full"] is not None
    # the ISSUE 15 variant row: quantized + reuse at full steps, replay
    # still exact (asserted above), knobs recorded on every row — and the
    # ISSUE 16 composed student row (student:2+w8+uniform:2) rides the
    # same frontier with the student flag recorded on every row
    assert [(r["quant_mode"], r["reuse_schedule"], r["student"])
            for r in records] == [
        ("off", "off", False), ("off", "off", False), ("off", "off", False),
        ("w8", "uniform:2", False), ("w8", "uniform:2", True),
    ]


@pytest.mark.slow
def test_null_text_unit_capture_yields_3x_flop_reduction(bench, tmp_path):
    """The ISSUE 8 flop acceptance, through the real subprocess at tiny
    scale: the straight-line unit analyses (one UNet forward, one inner
    Adam iteration) feed null_text_flop_records, and at the official
    defaults the amortized and hybrid inner-loop totals are ≥3× below the
    optimize baseline."""
    out = bench.collect_cpu_analysis(
        2, 2, tiny=True, timeout_s=560.0,
        programs=("null_text_unit_fwd", "null_text_unit_inner"),
    )
    assert set(out) == {"null_text_unit_fwd", "null_text_unit_inner"}
    fwd = out["null_text_unit_fwd"]["flops"]
    inner = out["null_text_unit_inner"]["flops"]
    assert inner >= fwd > 0  # a grad step costs at least a forward
    rec = bench.null_text_flop_records(fwd, inner)
    assert rec["null_text_flops_reduction_amortized"] >= 3.0
    assert rec["null_text_flops_reduction_hybrid"] >= 3.0


def test_load_analysis_baseline_precedence(bench, tmp_path):
    # nothing on disk: no baseline
    assert bench.load_analysis_baseline(str(tmp_path)) == (None, None)
    # bench_details.json record is the fallback baseline
    (tmp_path / "bench_details.json").write_text(json.dumps(
        {"breakdown": {"program_analysis": {"p": {"flops": 1}}}}
    ))
    section, source = bench.load_analysis_baseline(str(tmp_path))
    assert source == "bench_details.json" and section == {"p": {"flops": 1}}
    # an explicit BASELINE.json budget wins over it
    (tmp_path / "BASELINE.json").write_text(json.dumps(
        {"program_analysis": {"p": {"flops": 2}}}
    ))
    section, source = bench.load_analysis_baseline(str(tmp_path))
    assert source == "BASELINE.json" and section == {"p": {"flops": 2}}


def test_bench_analysis_verdicts_schema(bench):
    base = {"p": {"flops": 100, "temp_bytes": 100, "hlo_fingerprint": "x"}}
    same = bench.bench_analysis_verdicts(base, base, "BASELINE.json")
    assert same["pass"] and same["regressions"] == []
    assert same["compared_programs"] == ["p"]
    # first capture: no baseline → vacuous pass, still machine-readable
    first = bench.bench_analysis_verdicts(base, None, None)
    assert first["pass"] and first["baseline"] is None


def test_sub_floor_trace_span_is_recorded_suspect_not_floor_clamped(
    bench, monkeypatch
):
    """Advisor r4 (medium): when the trace's envelope span is itself below
    the FLOP floor, the reading must be the measured span flagged suspect —
    not the theoretical floor presented as a trusted measurement."""
    fake_px = types.SimpleNamespace(
        module_device_seconds=lambda tdir: 10.0,  # sum clears the floor...
        module_device_span_seconds=lambda tdir: 2.0,  # ...only via overlap
    )
    monkeypatch.setattr(bench, "_tools_import", lambda name: fake_px)
    monkeypatch.setattr(
        bench.jax.profiler, "start_trace",
        lambda *a, **kw: None, raising=False,
    )
    monkeypatch.setattr(
        bench.jax.profiler, "stop_trace", lambda: None, raising=False
    )

    r = bench.measure_with_floor(
        lambda x: bench.jnp.float32(x), [1.0], floor_s=5.0, what="test-phase"
    )
    assert r.source == "device_trace"
    assert r.seconds == pytest.approx(2.0)
    assert r.suspect


def test_above_floor_trace_span_is_trusted(bench, monkeypatch):
    fake_px = types.SimpleNamespace(
        module_device_seconds=lambda tdir: 10.0,
        module_device_span_seconds=lambda tdir: 6.0,
    )
    monkeypatch.setattr(bench, "_tools_import", lambda name: fake_px)
    monkeypatch.setattr(
        bench.jax.profiler, "start_trace",
        lambda *a, **kw: None, raising=False,
    )
    monkeypatch.setattr(
        bench.jax.profiler, "stop_trace", lambda: None, raising=False
    )

    r = bench.measure_with_floor(
        lambda x: bench.jnp.float32(x), [1.0], floor_s=5.0, what="test-phase"
    )
    assert r.source == "device_trace"
    assert r.seconds == pytest.approx(6.0)
    assert not r.suspect


def test_samples_mode_reports_median_and_spread(bench):
    """samples=3: the reading of record is the MEDIAN of three valid runs,
    with every valid reading recorded (discard-first/report-spread
    discipline on the headline phase)."""
    r = bench.measure_with_floor(
        lambda x: bench.jnp.float32(x), [1.0, 2.0, 3.0],
        floor_s=0.0, what="t", samples=3,
    )
    assert len(r.samples) == 3
    assert not r.suspect
    assert round(r.seconds, 3) == sorted(r.samples)[1]


def test_samples_mode_single_valid_still_returns(bench):
    """Fewer valid readings than requested samples: return what exists
    (bounded by the supplied fresh inputs) rather than failing."""
    r = bench.measure_with_floor(
        lambda x: bench.jnp.float32(x), [1.0],
        floor_s=0.0, what="t", samples=3,
    )
    assert len(r.samples) == 1
    assert round(r.seconds, 3) == r.samples[0]


def test_details_recorder_merges_and_flags_stale(bench, tmp_path):
    """bench_details.json survives partial runs: keys from a previous run
    are inherited but flagged stale until re-measured; re-recording
    freshens them; suspect propagation follows the Reading."""
    path = str(tmp_path / "details.json")
    rec1 = bench.DetailsRecorder(path, {"device": "t"}, [])
    r_ok = bench.Reading(None, 1.0, False, "wall", None)
    r_bad = bench.Reading(None, 2.0, True, "wall", None)
    rec1.record("a_s", 1.0, reading=r_ok)
    rec1.record("b_s", 2.0, reading=r_bad)
    saved = json.load(open(path))["breakdown"]
    assert saved["a_s"] == 1.0
    assert saved["suspect_measurements"] == ["b_s"]
    assert "stale_from_previous_run" not in saved

    # a later (partial) run inherits both, flags them stale, then
    # re-measures one — which must clear BOTH its stale and suspect marks
    rec2 = bench.DetailsRecorder(path, {"device": "t"}, [])
    assert set(rec2.stale) >= {"a_s", "b_s"}
    rec2.record("b_s", 2.5, reading=r_ok)
    saved = json.load(open(path))["breakdown"]
    assert saved["b_s"] == 2.5
    assert "b_s" not in saved.get("suspect_measurements", [])
    assert "b_s" not in saved.get("stale_from_previous_run", [])
    assert "a_s" in saved["stale_from_previous_run"]

    # derived values inherit suspicion from their constituents
    rec2.record("c_s", 3.0, derived=(r_bad,))
    saved = json.load(open(path))["breakdown"]
    assert "c_s" in saved["suspect_measurements"]

    # drop removes inherited keys entirely (e.g. a renamed metric)
    rec2.drop("a_s")
    saved = json.load(open(path))["breakdown"]
    assert "a_s" not in saved
    assert "a_s" not in saved.get("stale_from_previous_run", [])


# ------------------------------------------------- ledger/compile fields --


def test_ledger_bench_fields_schema(bench):
    """The bench breakdown's ledger/compile provenance fields (ISSUE 2):
    schema-stable and machine-readable, with the compile-vs-execute split
    explicit. Values may be null when unmeasured, keys never vanish."""
    rec = bench.ledger_bench_fields(
        "/tmp/bench_ledger.jsonl", [1.5, 2.25, 0.25], execute_s=8.0
    )
    assert rec == {
        "ledger_path": "/tmp/bench_ledger.jsonl",
        "compile_events": 3,
        "compile_total_s": 4.0,
        "execute_headline_s": 8.0,
        "compile_vs_execute": 0.5,
    }
    # unmeasured execute: keys stay, split is null (not a division crash)
    empty = bench.ledger_bench_fields("p", [], execute_s=None)
    assert empty["compile_events"] == 0
    assert empty["compile_total_s"] == 0.0
    assert empty["execute_headline_s"] is None
    assert empty["compile_vs_execute"] is None
    assert set(empty) == set(rec)


def _import_roots(path):
    """Every imported top-level module name in a file, comprehensions and
    function bodies included (AST walk — lazy imports don't hide)."""
    import ast

    with open(path) as f:
        tree = ast.parse(f.read(), filename=path)
    roots = set()
    for node in ast.walk(tree):
        if isinstance(node, ast.Import):
            roots.update(a.name.split(".")[0] for a in node.names)
        elif isinstance(node, ast.ImportFrom) and node.module and not node.level:
            roots.add(node.module.split(".")[0])
    return roots


def test_report_and_obs_import_only_stdlib_numpy_jax():
    """CI satellite (ISSUEs 4 + 7): tools/edit_report.py,
    videop2p_tpu/obs/ AND videop2p_tpu/serve/ must import only stdlib +
    numpy + jax (+ the package itself) — no matplotlib/PIL/imageio-only
    paths — so the report renders, the obs stack decodes, and the serving
    engine runs on any box, plotting stack or not."""
    import sys

    allowed = set(sys.stdlib_module_names) | {"numpy", "jax", "videop2p_tpu"}
    banned = {"matplotlib", "PIL", "imageio", "cv2", "torch", "torchvision",
              "pandas", "seaborn", "plotly", "scipy", "skimage",
              "tensorflow", "flax", "optax", "transformers"}
    files = [os.path.join(_REPO, "tools", "edit_report.py"),
             # ISSUE 17 pin: the fleet dashboard renders on any box the
             # collector runs on — stdlib+numpy SVG, no plotting stack
             os.path.join(_REPO, "tools", "fleet_dash.py"),
             # ISSUE 18 pin: the post-mortem renderer must open a bundle
             # anywhere — it ships in bug reports, not deployments
             os.path.join(_REPO, "tools", "incident_report.py"),
             # ISSUE 19 pin: the showback report ships in chargeback
             # emails — stdlib+numpy SVG bars, no plotting stack
             os.path.join(_REPO, "tools", "cost_report.py"),
             # ISSUE 20 pin: the correctness report ships in bug reports
             # too — stdlib+numpy SVG timelines, no plotting stack
             os.path.join(_REPO, "tools", "probe_report.py")]
    obs_dir = os.path.join(_REPO, "videop2p_tpu", "obs")
    obs_files = sorted(f for f in os.listdir(obs_dir) if f.endswith(".py"))
    # ISSUE 6 pins: the time-domain modules are IN the guarded set — the
    # stdlib xplane reader must never grow a tensorflow path, and the
    # latency reservoirs must stay stdlib
    # ISSUE 14 pins: the tracing/SLO/exposition tier joins — span
    # emission, budget math and the Prometheus renderer must run on any
    # box the engine does (no opentelemetry/prometheus_client deps)
    # ISSUE 17 pins: the telemetry plane joins — the time-series store
    # and the signal engine must never grow a prometheus_client/pandas
    # path; the fleet ships its own tsdb
    # ISSUE 18 pins: the incident plane joins — the flight recorder is
    # on the ledger hot path and the capture manager runs in every
    # serving process, so both stay stdlib(+numpy via the sidecar)
    # ISSUE 19 pins: the cost plane joins — the attribution model runs
    # inside every engine, so it stays stdlib+numpy
    # ISSUE 20 pins: the correctness plane joins — the known-answer
    # probe suite and the answer audit run inside every prober/engine
    # process, so they stay stdlib
    assert {"timing.py", "trace.py",
            "spans.py", "slo.py", "prom.py",
            "tsdb.py", "signals.py",
            "flight.py", "incident.py",
            "cost.py", "probe.py"} <= set(obs_files)
    files += [os.path.join(obs_dir, f) for f in obs_files]
    # ISSUE 7 pins: the serving subsystem is IN the guarded set — the
    # HTTP layer stays stdlib http.server/urllib (no flask/requests), and
    # the engine reaches models only through the package
    serve_dir = os.path.join(_REPO, "videop2p_tpu", "serve")
    serve_files = sorted(f for f in os.listdir(serve_dir) if f.endswith(".py"))
    # ISSUE 9 pin: the resilience layer (fault injection, breaker, retry)
    # joins the guarded set — chaos machinery must run anywhere the engine
    # does, so it stays stdlib
    # ISSUE 11 pin: the fleet tier (pluggable schedulers, the replica
    # supervisor and the router) joins too — the router must deploy on any
    # box with nothing beyond the stdlib HTTP stack
    # ISSUE 17 pin: the scrape loop joins — the collector must deploy on
    # any box the router does (stdlib urllib probes, no requests)
    # ISSUE 20 pin: the probing loop joins — the prober deploys next to
    # the router (stdlib urllib canaries, no requests)
    assert {"engine.py", "store.py", "batching.py", "programs.py",
            "http.py", "client.py", "faults.py", "sched.py", "replica.py",
            "router.py", "collector.py", "prober.py"} <= set(serve_files)
    files += [os.path.join(serve_dir, f) for f in serve_files]
    # ISSUE 12 pin: the streaming tier (window plan, resumable manifest,
    # job driver) joins the guarded set — resume/chaos machinery must run
    # anywhere the engine does, so it stays stdlib+numpy+jax
    stream_dir = os.path.join(_REPO, "videop2p_tpu", "stream")
    stream_files = sorted(f for f in os.listdir(stream_dir)
                          if f.endswith(".py"))
    assert {"windows.py", "manifest.py", "driver.py"} <= set(stream_files)
    files += [os.path.join(stream_dir, f) for f in stream_files]
    offenders = []
    for path in files:
        roots = _import_roots(path)
        for r in sorted(roots):
            if r in banned or r not in allowed:
                offenders.append(f"{path}: imports {r!r}")
    assert not offenders, (
        "stdlib+numpy+jax-only import contract violated:\n"
        + "\n".join(offenders)
    )


def test_quality_and_attn_ledger_event_schema(tmp_path):
    """Schema pin (ISSUE 4): the new `quality` and `attn_maps` ledger
    events carry their documented field sets — the report, the regression
    rules and ledger_summary all key on these names."""
    import numpy as np

    from videop2p_tpu.obs import RunLedger, read_ledger
    from videop2p_tpu.obs.attention import (
        ATTN_SUMMARY_FIELDS,
        summarize_attn_record,
    )
    from videop2p_tpu.obs.quality import (
        QUALITY_SUMMARY_FIELDS,
        edit_quality_record,
    )

    frames = np.random.RandomState(0).rand(2, 8, 8, 3).astype(np.float32)
    summary, curves = edit_quality_record(frames, frames, frames,
                                          mask=np.ones((2, 8, 8)))
    attn_summary = summarize_attn_record({
        "cross_heat": np.zeros((3, 1, 16, 16, 77), np.float32),
        "entropy": {"b/attn2": np.zeros(3)},
        "mask_cov": np.zeros((3, 2, 2)),
        "blend_active": np.zeros(3, np.int64),
    })
    path = str(tmp_path / "ledger.jsonl")
    with RunLedger(path) as led:
        led.event("quality", program="edit_quality", sidecar="sc.npz",
                  **summary)
        led.event("attn_maps", scope="edit", program="attn_edit",
                  sidecar="sc.npz", streams=[1], words=[], **attn_summary)
    by_kind = {e["event"]: e for e in read_ledger(path)}
    q = by_kind["quality"]
    assert set(QUALITY_SUMMARY_FIELDS) <= set(q)
    assert {"program", "sidecar", "background_psnr", "mask_coverage"} <= set(q)
    a = by_kind["attn_maps"]
    assert set(ATTN_SUMMARY_FIELDS) <= set(a)
    assert {"scope", "program", "sidecar", "streams", "words",
            "mask_cov_final", "blend_active_steps"} <= set(a)
    assert a["steps"] == 3 and a["sites"] == ["b/attn2"]
    # per-frame curves exist for the sidecar side of the contract
    assert {"recon_psnr_frames", "background_psnr_frames"} <= set(curves)


def test_comm_and_device_ledger_event_schema(tmp_path):
    """Schema pin (ISSUE 5): the ``comm_analysis`` / ``device_telemetry`` /
    per-device ``memory`` / ``divergence`` ledger events carry their
    documented field sets — obs/history.py rules, both tools and the HTML
    report key on these names."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import NamedSharding, PartitionSpec as P

    from videop2p_tpu.obs import RunLedger, read_ledger
    from videop2p_tpu.obs.comm import (
        COMM_ANALYSIS_FIELDS,
        DEVICE_TELEMETRY_FIELDS,
        comm_analysis_record,
        summarize_device_stats,
    )
    from videop2p_tpu.parallel import make_mesh

    # a minimal partitioned program: the sharded sum's partial results
    # meet in an all-reduce, so the record has real collectives in it
    mesh = make_mesh((1, 8, 1))
    sds = jax.ShapeDtypeStruct(
        (16, 16), jnp.float32, sharding=NamedSharding(mesh, P("frames"))
    )
    comm_rec = comm_analysis_record(jax.jit(lambda x: x.sum()).lower(sds).compile())
    assert comm_rec is not None
    assert set(COMM_ANALYSIS_FIELDS) <= set(comm_rec)
    assert comm_rec["num_partitions"] == 8
    assert comm_rec["collective_count"] >= 1

    dev_rec = summarize_device_stats({
        "device_abs_max": np.ones((3, 8)),
        "device_mean": np.zeros((3, 8)),
        "device_nan_count": np.zeros((3, 8)),
        "device_inf_count": np.zeros((3, 8)),
        "divergence": np.zeros(3),
    }, device_ids=list(range(8)))
    assert set(DEVICE_TELEMETRY_FIELDS) <= set(dev_rec)

    path = str(tmp_path / "ledger.jsonl")
    with RunLedger(path) as led:
        led.comm_analysis("p", comm_rec)
        led.device_telemetry("p", dev_rec)
        led.divergence("train_params", 0.0, axes=["data"])
        led.memory_snapshot(note="pin")
    by_kind = {e["event"]: e for e in read_ledger(path)}
    c = by_kind["comm_analysis"]
    assert set(COMM_ANALYSIS_FIELDS) <= set(c) and c["program"] == "p"
    assert set(DEVICE_TELEMETRY_FIELDS) <= set(by_kind["device_telemetry"])
    v = by_kind["divergence"]
    assert v["label"] == "train_params" and v["value"] == 0.0
    # memory snapshots list EVERY local device (8 on the virtual CPU mesh)
    # with a stable per-entry schema even where memory_stats is missing
    m = by_kind["memory"]
    assert len(m["devices"]) == len(jax.local_devices())
    for entry in m["devices"]:
        assert {"device", "coords", "process_index", "bytes_in_use",
                "peak_bytes_in_use", "bytes_limit", "live_bytes"} <= set(entry)


def test_execute_timing_and_trace_ledger_event_schema(tmp_path):
    """Schema pin (ISSUE 6): the ``execute_timing`` and ``trace_analysis``
    ledger events carry their documented field sets — TIMING_RULES, both
    tools and the HTML report's "Where time goes" section key on these
    names — and the reservoir summary matches the pin EXACTLY (drift in
    either direction fails)."""
    from videop2p_tpu.obs import (
        EXECUTE_TIMING_FIELDS,
        TRACE_ANALYSIS_FIELDS,
        LatencyReservoir,
        RunLedger,
        read_ledger,
    )
    from videop2p_tpu.obs.trace import analyze_events

    res = LatencyReservoir()
    for i in range(10):
        res.add(0.01 + i * 1e-4, 0.02 + i * 1e-4)
    assert set(res.summary()) == set(EXECUTE_TIMING_FIELDS)

    record, arrays = analyze_events(
        [("fusion.1", 0, 1_000_000), ("all-reduce.2", 500_000, 1_000_000)],
        [("jit_m", 0, 2_000_000)],
        name="w", trace_dir="/tmp/x",
    )
    path = str(tmp_path / "ledger.jsonl")
    with RunLedger(path) as led:
        led.record_execute("edit", 0.01, 0.02)
        led.flush_execute_timing()
        led.event("trace_analysis", sidecar="s.npz", **record)
    by_kind = {e["event"]: e for e in read_ledger(path)}
    et = by_kind["execute_timing"]
    assert set(EXECUTE_TIMING_FIELDS) <= set(et)
    assert et["program"] == "edit" and et["count"] == 1
    ta = by_kind["trace_analysis"]
    assert set(TRACE_ANALYSIS_FIELDS) <= set(ta)
    assert ta["sidecar"] == "s.npz" and ta["name"] == "w"
    assert 0.0 <= ta["overlap_fraction"] <= 1.0
    # the close() flush is idempotent over an already-flushed reservoir:
    # exactly one more event (same count), not a duplicate explosion
    events = read_ledger(path)
    assert [e["count"] for e in events
            if e["event"] == "execute_timing"] == [1, 1]


def test_fault_and_serve_health_ledger_event_schema(tmp_path):
    """Schema pin (ISSUE 9): the ``fault`` / ``breaker`` / ``serve_health``
    ledger events carry their documented field sets, FAULT_RULES ride in
    DEFAULT_RULES, and obs/history.py's reliability section extracts them
    — tools/obs_diff.py's reliability table and exit-1 teeth key on these
    names."""
    from videop2p_tpu.obs import RunLedger, read_ledger
    from videop2p_tpu.obs.history import (
        DEFAULT_RULES,
        FAULT_RULES,
        extract_run,
        split_runs,
    )
    from videop2p_tpu.serve.faults import (
        BREAKER_EVENT_FIELDS,
        FAULT_EVENT_FIELDS,
        SERVE_HEALTH_FIELDS,
    )

    assert all(r in DEFAULT_RULES for r in FAULT_RULES)
    assert {r.metric for r in FAULT_RULES} == {
        "error_rate", "shed_rate", "breaker_trips", "deadline_exceeded"}
    assert all(r.kind == "reliability" for r in FAULT_RULES)

    health = {k: 0 for k in SERVE_HEALTH_FIELDS}
    health.update(requests=3, done=2, errors=1, error_rate=round(1 / 3, 4))
    path = str(tmp_path / "ledger.jsonl")
    with RunLedger(path) as led:
        led.fault("backend_unavailable", detail="attempt=4")
        led.breaker("closed", "open", consecutive_failures=2, trips=1)
        led.event("serve_health", **health)
    by_kind = {e["event"]: e for e in read_ledger(path)}
    assert set(FAULT_EVENT_FIELDS) <= set(by_kind["fault"])
    assert by_kind["fault"]["kind"] == "backend_unavailable"
    assert set(BREAKER_EVENT_FIELDS) <= set(by_kind["breaker"])
    assert set(SERVE_HEALTH_FIELDS) <= set(by_kind["serve_health"])
    rec = extract_run(split_runs(read_ledger(path))[-1])
    rel = rec["reliability"]["serve"]
    assert set(SERVE_HEALTH_FIELDS) <= set(rel)
    assert rel["error_rate"] == round(1 / 3, 4)
    # pre-PR-9 ledgers extract an empty (but present) reliability section
    assert extract_run([{"event": "run_start"}])["reliability"] == {}


def test_span_and_slo_report_ledger_event_schema(tmp_path):
    """Schema pin (ISSUE 14): the ``span`` and ``slo_report`` ledger
    events carry their documented field sets, SLO_RULES + SEGMENT_RULES
    ride in DEFAULT_RULES (kinds "slo" / "segment"), and obs/history.py
    extracts both new sections — tools/obs_diff.py's SLO/segment tables
    and exit-1 teeth key on these names."""
    from videop2p_tpu.obs import RunLedger, read_ledger
    from videop2p_tpu.obs.history import (
        DEFAULT_RULES,
        SEGMENT_RULES,
        SLO_RULES,
        extract_run,
        split_runs,
    )
    from videop2p_tpu.obs.slo import (
        DEFAULT_SLOS,
        SLO_REPORT_FIELDS,
        emit_slo_reports,
    )
    from videop2p_tpu.obs.spans import (
        SPAN_EVENT_FIELDS,
        SPAN_SEGMENTS,
        Tracer,
        make_span_id,
        make_trace_id,
    )

    assert all(r in DEFAULT_RULES for r in SLO_RULES + SEGMENT_RULES)
    assert {r.metric for r in SLO_RULES} == {"budget_burn", "compliant"}
    assert all(r.kind == "slo" for r in SLO_RULES)
    assert {r.metric for r in SEGMENT_RULES} == {"p50_s", "p99_s"}
    assert all(r.kind == "segment" for r in SEGMENT_RULES)
    # the default objectives cover the serving AND streaming tiers
    assert {s.name for s in DEFAULT_SLOS} == {
        "availability", "deadline_miss_rate", "served_p99_latency",
        "seam_min_psnr"}

    path = str(tmp_path / "ledger.jsonl")
    with RunLedger(path) as led:
        tracer = Tracer(led, enabled=True)
        tid = make_trace_id()
        tracer.emit("serve.dispatch", trace_id=tid, span_id=make_span_id(),
                    duration_s=0.25, batch_size=2)
        emit_slo_reports(led, {
            "reliability": {"serve": {"error_rate": 0.005, "requests": 10,
                                      "deadline_exceeded": 0}},
        })
    by_kind = {}
    for e in read_ledger(path):
        by_kind.setdefault(e["event"], e)
    assert set(SPAN_EVENT_FIELDS) <= set(by_kind["span"])
    assert by_kind["span"]["name"] in SPAN_SEGMENTS
    assert set(SLO_REPORT_FIELDS) <= set(by_kind["slo_report"])
    rec = extract_run(split_runs(read_ledger(path))[-1])
    assert rec["segments"]["dispatch"]["count"] == 1.0
    assert rec["segments"]["dispatch"]["p99_s"] == 0.25
    assert rec["slo"]["availability"]["budget_burn"] == pytest.approx(0.5)
    assert rec["slo"]["availability"]["compliant"] == 1.0
    # pre-PR-14 ledgers extract empty (but present) sections
    old = extract_run([{"event": "run_start"}])
    assert old["segments"] == {} and old["slo"] == {}


def test_fleet_signals_and_series_ledger_event_schema(tmp_path):
    """Schema pin (ISSUE 17): the ``fleet_signals`` and ``fleet_series``
    ledger events carry their documented field sets, SIGNAL_RULES ride in
    DEFAULT_RULES (kind "signal"), and obs/history.py extracts the new
    `signals` section — tools/obs_diff.py's fleet table and exit-1 teeth
    key on these names."""
    from videop2p_tpu.obs import RunLedger, read_ledger
    from videop2p_tpu.obs.history import (
        DEFAULT_RULES,
        SIGNAL_RULES,
        extract_run,
        split_runs,
    )
    from videop2p_tpu.obs.signals import (
        FLEET_SIGNALS_FIELDS,
        FLEET_TENANT_FIELDS,
        S_IN_FLIGHT,
        S_QUEUE_DEPTH,
        S_REQUESTS,
        S_TENANT,
        S_UP,
        SignalEngine,
    )
    from videop2p_tpu.obs.tsdb import FLEET_SERIES_FIELDS, TimeSeriesStore

    assert all(r in DEFAULT_RULES for r in SIGNAL_RULES)
    assert all(r.kind == "signal" for r in SIGNAL_RULES)
    assert {r.metric for r in SIGNAL_RULES} == {
        "burn_alerts", "scrape_error_rate", "saturation"}

    # a minimal degraded fleet: one replica, 50% of finished requests
    # erroring — both burn windows blow the 1% objective, alert fires
    ts = TimeSeriesStore(capacity=64)
    eng = SignalEngine(ts, window_scale=0.01)  # fast 3 s / slow 36 s
    lab = {"replica": "replica0"}
    for i in range(6):
        t = float(i)
        ts.add(S_UP, t, 1.0, lab)
        ts.add(S_QUEUE_DEPTH, t, 1.0, lab)
        ts.add(S_IN_FLIGHT, t, 1.0, lab)
        ts.add(S_REQUESTS, t, float(i), {**lab, "status": "done"})
        ts.add(S_REQUESTS, t, float(i), {**lab, "status": "error"})
        ts.add(S_TENANT, t, float(i),
               {**lab, "tenant": "A", "field": "submitted"})
        ts.add(S_TENANT, t, float(i), {**lab, "tenant": "A", "field": "done"})
    # ISSUE 18 satellite: reservoir trace-id exemplars thread into the
    # evaluation record and the burn-alert reason NAMES a trace
    eng.set_exemplars({"edit": {"p99_trace_id": "tid-p99",
                                "max_trace_id": "tid-max"}})
    path = str(tmp_path / "ledger.jsonl")
    with RunLedger(path) as led:
        rec = eng.evaluate(5.5, ledger=led)
        ts.snapshot(led, label="fleet",
                    sidecar_path=str(tmp_path / "series.npz"))
    assert set(rec) == set(FLEET_SIGNALS_FIELDS)
    assert rec["burn_alert"] is True and rec["scale_advice"] == "grow"
    assert set(rec["tenants"]["A"]) == set(FLEET_TENANT_FIELDS)
    assert rec["exemplars"]["edit"]["p99_trace_id"] == "tid-p99"
    assert any("tid-p99" in r for r in rec["reasons"])
    by_kind = {e["event"]: e for e in read_ledger(path)}
    assert set(FLEET_SIGNALS_FIELDS) <= set(by_kind["fleet_signals"])
    assert set(FLEET_SERIES_FIELDS) <= set(by_kind["fleet_series"])
    run = extract_run(split_runs(read_ledger(path))[-1])
    sig = run["signals"]
    assert sig["fleet"]["burn_alerts"] == 1.0
    assert sig["fleet"]["advice_grow"] == 1.0
    assert sig["fleet:tenant:A"]["submitted_rate"] > 0.0
    assert sig["fleet:series"]["samples"] > 0.0
    # pre-PR-17 ledgers extract an empty (but present) signals section
    assert extract_run([{"event": "run_start"}])["signals"] == {}


def test_incident_ledger_event_schema(tmp_path):
    """Schema pin (ISSUE 18): the ``incident`` ledger event carries
    INCIDENT_FIELDS, INCIDENT_RULES ride in DEFAULT_RULES (kind
    "incident", any-increase), and obs/history.py extracts the
    ``incidents`` section with the overall label SEEDED at zero — a
    healthy baseline must hold the label so a chaos run's first bundle
    regresses against it with obs_diff exit-1 teeth."""
    from videop2p_tpu.obs import RunLedger, read_ledger
    from videop2p_tpu.obs.history import (
        DEFAULT_RULES,
        INCIDENT_RULES,
        evaluate_rules,
        extract_run,
        split_runs,
    )
    from videop2p_tpu.obs.incident import (
        INCIDENT_FIELDS,
        INCIDENT_TRIGGERS,
        IncidentManager,
    )

    assert all(r in DEFAULT_RULES for r in INCIDENT_RULES)
    assert all(r.kind == "incident" for r in INCIDENT_RULES)
    assert {r.metric for r in INCIDENT_RULES} == {"count", "suppressed"}
    assert all(r.threshold_pct == 0.0 for r in INCIDENT_RULES)
    assert set(INCIDENT_TRIGGERS) == {
        "burn_alert", "breaker_open", "deadline_exceeded",
        "window_poisoned", "crash", "sigusr1", "probe_failed"}

    path = str(tmp_path / "ledger.jsonl")
    mgr = IncidentManager(str(tmp_path / "inc"), cooldown_s=3600.0,
                          crash_hooks=False)
    with RunLedger(path) as led:
        mgr.attach_ledger(led)
        led.event("fault", kind="dispatch_error", error="boom")
        bundle = mgr.trigger("breaker_open", detail="closed->open")
        assert mgr.trigger("breaker_open", detail="flap") is None  # debounced
    assert bundle is not None and os.path.isdir(bundle)
    by_kind = {e["event"]: e for e in read_ledger(path)}
    assert set(INCIDENT_FIELDS) <= set(by_kind["incident"])
    assert by_kind["incident"]["trigger"] == "breaker_open"

    run = extract_run(split_runs(read_ledger(path))[-1])
    assert run["incidents"]["incident"]["count"] == 1.0
    assert run["incidents"]["incident:breaker_open"]["count"] == 1.0
    # a run with NO incident events still extracts the seeded zero label
    healthy = extract_run([{"event": "run_start"}])
    assert healthy["incidents"] == {
        "incident": {"count": 0.0, "suppressed": 0.0, "events": 0.0}}
    # verdict teeth: healthy vs incident regresses; self-compare passes
    assert not evaluate_rules(healthy, run)["pass"]
    assert evaluate_rules(run, run)["pass"]
    assert evaluate_rules(healthy, healthy)["pass"]
    mgr.close()


def test_router_and_tenant_ledger_event_schema(tmp_path):
    """Schema pin (ISSUE 11): the ``router_health`` event and the
    per-tenant ``serve_health`` sub-records carry their documented field
    sets, and obs/history.py flattens both into the reliability section —
    the fleet's obs_diff gates key on these names."""
    from videop2p_tpu.obs import RunLedger, read_ledger
    from videop2p_tpu.obs.history import extract_run, split_runs
    from videop2p_tpu.serve.faults import (
        SERVE_HEALTH_FIELDS,
        SERVE_TENANT_FIELDS,
    )
    from videop2p_tpu.serve.router import ROUTER_HEALTH_FIELDS

    health = {k: 0 for k in SERVE_HEALTH_FIELDS}
    health.update(requests=4, done=3, errors=1, error_rate=0.25)
    tenants = {
        "A": {k: 0 for k in SERVE_TENANT_FIELDS},
        "B": {**{k: 0 for k in SERVE_TENANT_FIELDS},
              "shed": 2, "shed_rate": 0.5},
    }
    router = {k: 0 for k in ROUTER_HEALTH_FIELDS}
    router.update(replicas=2, healthy=1, routed_around=3,
                  per_replica={"replica0": 1, "replica1": 3})
    path = str(tmp_path / "ledger.jsonl")
    with RunLedger(path) as led:
        led.event("serve_health", tenants=tenants, **health)
        led.event("router_health", **router)
    by_kind = {e["event"]: e for e in read_ledger(path)}
    assert set(SERVE_TENANT_FIELDS) <= set(by_kind["serve_health"]["tenants"]["A"])
    assert set(ROUTER_HEALTH_FIELDS) <= set(by_kind["router_health"])
    rec = extract_run(split_runs(read_ledger(path))[-1])
    rel = rec["reliability"]
    # the fleet summary and every tenant lane get their own labels, so
    # FAULT_RULES (error_rate/shed_rate/...) gate each one independently
    assert {"serve", "serve:tenant:A", "serve:tenant:B", "router"} <= set(rel)
    assert set(SERVE_TENANT_FIELDS) <= set(rel["serve:tenant:B"])
    assert rel["serve:tenant:B"]["shed_rate"] == 0.5
    assert set(ROUTER_HEALTH_FIELDS) <= set(rel["router"])
    assert rel["router"]["routed_around"] == 3.0
    # engine-side constants agree with the ledger surface: the engine's
    # per-tenant records carry exactly the pinned keys
    from videop2p_tpu.serve.engine import EditEngine

    # ISSUE 19: the chargeback fields ride the same records — counters
    # plus rates plus the measured cost-plane columns cover the pin
    assert set(EditEngine._TENANT_COUNTER_KEYS) | {
        "error_rate", "shed_rate", "device_seconds",
        "saved_device_seconds"} == set(SERVE_TENANT_FIELDS)


def test_cost_plane_schema_pins_and_extraction(tmp_path):
    """Schema pin (ISSUE 19): the cost plane's field tuples are pinned
    byte-for-byte — terminal request ``cost`` vectors, the
    ``cost_attribution`` chargeback rows, the engine capacity roll-up —
    COST_RULES ride in DEFAULT_RULES (kind "cost", teeth for
    cost_per_request/utilization/padding-waste regressions), and
    obs/history.py flattens attribution rows into the ``cost`` section
    under the serve / serve:tenant:X / serve:program:Y label scheme."""
    from videop2p_tpu.obs import RunLedger, read_ledger
    from videop2p_tpu.obs.cost import (
        CAPACITY_FIELDS,
        COST_ATTRIBUTION_FIELDS,
        REQUEST_COST_FIELDS,
    )
    from videop2p_tpu.obs.history import (
        COST_RULES,
        DEFAULT_RULES,
        extract_run,
        split_runs,
    )

    assert REQUEST_COST_FIELDS == (
        "program", "device_seconds", "flops", "hbm_byte_seconds",
        "queue_seconds", "padding_share", "saved_device_seconds",
        "saved_flops")
    assert COST_ATTRIBUTION_FIELDS == (
        "scope", "name", "requests", "store_hits", "device_seconds",
        "flops", "hbm_byte_seconds", "queue_seconds",
        "saved_device_seconds", "saved_flops", "cost_per_request_s")
    assert CAPACITY_FIELDS == (
        "uptime_s", "busy_seconds", "attributed_seconds",
        "padding_seconds", "idle_seconds", "busy_fraction",
        "idle_fraction", "padding_waste", "occupancy", "dispatches",
        "real_slots", "padded_slots", "requests_costed",
        "cost_per_request_s", "conservation_residual_s")
    # the rules gate by default, all kind "cost", utilization pointing
    # the economic way (busy_fraction regresses by DECREASING)
    assert set(COST_RULES) <= set(DEFAULT_RULES)
    assert all(r.kind == "cost" for r in COST_RULES)
    by_metric = {r.metric: r for r in COST_RULES}
    assert set(by_metric) == {"cost_per_request_s", "busy_fraction",
                              "padding_waste", "idle_fraction"}
    assert by_metric["busy_fraction"].direction == "decrease"
    # extraction: engine/tenant/program rows land under the documented
    # label scheme; a pre-cost-plane ledger extracts an empty section
    path = str(tmp_path / "ledger.jsonl")
    with RunLedger(path) as led:
        led.event("cost_attribution", label="serve", scope="engine",
                  name="serve", busy_fraction=0.5, cost_per_request_s=0.2)
        led.event("cost_attribution", label="serve", scope="tenant",
                  name="A", requests=3, device_seconds=0.6)
        led.event("cost_attribution", label="serve", scope="program",
                  name="serve_edit", requests=3, flops=9.0)
    rec = extract_run(split_runs(read_ledger(path))[-1])
    assert set(rec["cost"]) == {"serve", "serve:tenant:A",
                                "serve:program:serve_edit"}
    assert rec["cost"]["serve"]["busy_fraction"] == 0.5
    assert rec["cost"]["serve:tenant:A"]["device_seconds"] == 0.6
    empty = str(tmp_path / "old.jsonl")
    with RunLedger(empty) as led:
        led.event("serve_health", requests=1)
    assert extract_run(split_runs(read_ledger(empty))[-1])["cost"] == {}


def test_stream_health_ledger_event_schema_and_seam_rules(tmp_path):
    """Schema pin (ISSUE 12): the ``stream_health`` summary carries its
    documented field set, SEAM_RULES ride in DEFAULT_RULES (kind
    "stream"), obs/history.py extracts the event into the `stream`
    section — and the gate semantics hold: identical runs self-compare
    clean, a seam-PSNR drop / a new passthrough / a nonzero src_err_max
    regress with obs_diff exit-1 teeth."""
    from videop2p_tpu.obs import RunLedger, read_ledger
    from videop2p_tpu.obs.history import (
        DEFAULT_RULES,
        SEAM_RULES,
        evaluate_rules,
        extract_run,
        split_runs,
    )
    from videop2p_tpu.stream.driver import (
        STREAM_HEALTH_FIELDS,
        STREAM_SEAM_FIELDS,
        STREAM_WINDOW_FIELDS,
    )

    assert all(r in DEFAULT_RULES for r in SEAM_RULES)
    assert all(r.kind == "stream" for r in SEAM_RULES)
    assert {r.metric for r in SEAM_RULES} == {
        "seam_min_psnr", "seam_mean_psnr", "windows_failed",
        "windows_passthrough", "manifest_corrupt", "src_err_max"}

    health = {k: 0 for k in STREAM_HEALTH_FIELDS}
    health.update(windows_total=4, windows_done=4, seams=3,
                  seam_min_psnr=24.0, seam_mean_psnr=30.0,
                  source_seam_min_psnr=26.0, src_err_max=0.0)
    path = str(tmp_path / "ledger.jsonl")
    with RunLedger(path) as led:
        led.event("stream_window", index=0, key="k", status="done",
                  attempts=1, store_source="fresh", src_err=0.0,
                  window_s=0.5)
        led.event("stream_seam", left=0, right=1, start=3, stop=4,
                  seam_psnr=24.0, source_psnr=26.0)
        led.event("stream_health", **health)
    by_kind = {e["event"]: e for e in read_ledger(path)}
    assert set(STREAM_WINDOW_FIELDS) <= set(by_kind["stream_window"])
    assert set(STREAM_SEAM_FIELDS) <= set(by_kind["stream_seam"])
    assert set(STREAM_HEALTH_FIELDS) <= set(by_kind["stream_health"])
    rec = extract_run(split_runs(read_ledger(path))[-1])
    assert set(STREAM_HEALTH_FIELDS) <= set(rec["stream"]["stream"])
    # pre-PR-12 ledgers extract an empty (but present) stream section
    assert extract_run([{"event": "run_start"}])["stream"] == {}

    # gate semantics: self-compare clean; seam drop / new passthrough /
    # nonzero src_err_max regress
    assert evaluate_rules(rec, rec, SEAM_RULES)["pass"]
    worse = {**rec, "stream": {"stream": {
        **rec["stream"]["stream"],
        "seam_min_psnr": 12.0, "windows_passthrough": 1.0,
    }}}
    result = evaluate_rules(rec, worse, SEAM_RULES)
    assert not result["pass"]
    assert {v["metric"] for v in result["regressions"]} == {
        "seam_min_psnr", "windows_passthrough"}
    # src_err_max is an exactness invariant: nonzero fails SELF-compare
    diverged = {**rec, "stream": {"stream": {
        **rec["stream"]["stream"], "src_err_max": 1e-6,
    }}}
    assert not evaluate_rules(diverged, diverged, SEAM_RULES)["pass"]
    # inf→inf (a single-window job with no seams) passes clean
    no_seams = {**rec, "stream": {"stream": {
        **rec["stream"]["stream"],
        "seam_min_psnr": float("inf"), "seam_mean_psnr": float("inf"),
    }}}
    assert evaluate_rules(no_seams, no_seams, SEAM_RULES)["pass"]


def test_streaming_window_record_schema(bench):
    """Schema pin (ISSUE 12): `streaming_window_records` turns one
    per-window analysis into the 128f/480f streaming evidence rows —
    exact window counts from the REAL planner, linear flop/store
    scaling, every record carrying exactly STREAMING_WINDOW_FIELDS."""
    records = bench.streaming_window_records(
        {"e2e_cached": {"flops": 2.0e13, "temp_bytes": 1}}
    )
    assert [r["total_frames"] for r in records] == [128, 480]
    by_total = {r["total_frames"]: r for r in records}
    # the planner's counts: stride 6 with the final window end-anchored
    assert by_total[128]["windows"] == 21
    assert by_total[480]["windows"] == 80
    for r in records:
        assert set(r) == set(bench.STREAMING_WINDOW_FIELDS), r
        assert r["window"] == bench.BENCH_FRAMES
        assert r["flops_per_window"] == 2.0e13
        assert r["flops_total"] == 2.0e13 * r["windows"]
        assert r["store_bytes_total"] == \
            r["store_bytes_per_window"] * r["windows"]
        assert r["frames_processed"] == r["windows"] * r["window"]
        assert r["overlap_overhead"] == pytest.approx(
            r["frames_processed"] / r["total_frames"] - 1.0, abs=1e-3)
        # one fp32 trajectory of steps+1 latent stacks per window
        assert r["store_bytes_per_window"] == \
            (bench.BENCH_STEPS + 1) * r["window"] * 64 * 64 * 4 * 4
    # an incomplete capture still records the static plan geometry
    no_flops = bench.streaming_window_records({})
    assert all(r["flops_per_window"] is None and r["flops_total"] is None
               for r in no_flops)
    assert [r["windows"] for r in no_flops] == [21, 80]


def test_no_wall_clock_in_timed_regions():
    """Satellite guard (ISSUE 2): every timed region in the package uses
    the monotonic clock — ``time.time()`` steps under NTP adjustment and
    corrupted phase records. Grep-based so a reintroduction anywhere in
    videop2p_tpu/ fails loudly with the offending lines."""
    offenders = []
    pkg = os.path.join(_REPO, "videop2p_tpu")
    for root, _, files in os.walk(pkg):
        for name in files:
            if not name.endswith(".py"):
                continue
            path = os.path.join(root, name)
            with open(path) as f:
                for lineno, line in enumerate(f, 1):
                    if "time.time()" in line:
                        offenders.append(f"{path}:{lineno}: {line.strip()}")
    assert not offenders, (
        "time.time() reintroduced in a timed region — use "
        "time.perf_counter():\n" + "\n".join(offenders)
    )


# ---------------------------------------------------- __graft_entry__.py --


def test_dryrun_decision_never_probes_the_real_backend(graft, monkeypatch):
    """With JAX_PLATFORMS pointing anywhere but cpu, dryrun_multichip must
    re-exec a CPU subprocess without ever calling jax.devices() in the
    parent — that probe takes the chip, and hangs on an unhealthy one."""
    monkeypatch.setenv("JAX_PLATFORMS", "tpu")

    def poisoned_devices(*a, **kw):
        pytest.fail("dryrun_multichip touched the parent's backend")

    monkeypatch.setattr(graft.jax, "devices", poisoned_devices)

    seen = {}

    def fake_run(cmd, **kw):
        seen["cmd"], seen["env"] = cmd, kw.get("env", {})
        seen["timeout"] = kw.get("timeout")
        return types.SimpleNamespace(returncode=0, stdout="ok\n", stderr="")

    monkeypatch.setattr(graft.subprocess, "run", fake_run)
    graft.dryrun_multichip(8)

    assert seen["env"]["JAX_PLATFORMS"] == "cpu"
    assert "--xla_force_host_platform_device_count=8" in seen["env"]["XLA_FLAGS"]
    assert seen["timeout"] is not None  # a wedged child cannot hang the driver
    assert "dryrun" in seen["cmd"]


def test_dryrun_subprocess_failure_is_a_readable_error(graft, monkeypatch):
    monkeypatch.setenv("JAX_PLATFORMS", "tpu")
    monkeypatch.setattr(
        graft.jax, "devices",
        lambda *a, **kw: pytest.fail("touched the parent's backend"),
    )
    monkeypatch.setattr(
        graft.subprocess, "run",
        lambda cmd, **kw: types.SimpleNamespace(
            returncode=3, stdout="", stderr="boom"
        ),
    )
    with pytest.raises(RuntimeError, match="rc=3"):
        graft.dryrun_multichip(8)


def test_dryrun_subprocess_timeout_is_a_readable_error(graft, monkeypatch):
    monkeypatch.setenv("JAX_PLATFORMS", "tpu")
    monkeypatch.setattr(
        graft.jax, "devices",
        lambda *a, **kw: pytest.fail("touched the parent's backend"),
    )

    def raise_timeout(cmd, **kw):
        raise subprocess.TimeoutExpired(cmd, kw.get("timeout", 0), stderr=b"slow")

    monkeypatch.setattr(graft.subprocess, "run", raise_timeout)
    with pytest.raises(RuntimeError, match="exceeded"):
        graft.dryrun_multichip(8, timeout_s=1.0)


def test_dryrun_reexecs_when_config_overrides_cpu_env(graft, monkeypatch):
    """A jax_platforms value set through jax.config (here 'tpu,cpu') beats
    the JAX_PLATFORMS env var — so env=cpu alone is NOT proof that
    jax.devices() can't init the real backend. The decision must consult
    the effective config value."""
    monkeypatch.setenv("JAX_PLATFORMS", "cpu")
    monkeypatch.setattr(
        type(graft.jax.config), "jax_platforms",
        property(lambda self: "tpu,cpu"), raising=False,
    )
    monkeypatch.setattr(
        graft.jax, "devices",
        lambda *a, **kw: pytest.fail("touched the parent's backend"),
    )
    seen = {}
    monkeypatch.setattr(
        graft.subprocess, "run",
        lambda cmd, **kw: seen.update(env=kw.get("env", {})) or
        types.SimpleNamespace(returncode=0, stdout="", stderr=""),
    )
    graft.dryrun_multichip(8)
    assert seen["env"]["JAX_PLATFORMS"] == "cpu"


def test_dryrun_runs_inline_when_already_on_a_big_cpu_mesh(graft, monkeypatch):
    """When the process is already pinned to cpu with enough devices (the
    test-suite configuration), no subprocess indirection should happen."""
    monkeypatch.setenv("JAX_PLATFORMS", "cpu")
    monkeypatch.setattr(graft.jax, "devices", lambda *a, **kw: list(range(8)))
    monkeypatch.setattr(
        graft.subprocess, "run",
        lambda *a, **kw: pytest.fail("re-exec'd despite a sufficient cpu mesh"),
    )
    ran = {}
    monkeypatch.setattr(graft, "_dryrun_impl", lambda n: ran.setdefault("n", n))
    graft.dryrun_multichip(8)
    assert ran["n"] == 8


@pytest.mark.slow
def test_dryrun_writes_obs_ledger_acceptance(graft, tmp_path, monkeypatch):
    """The ISSUE 5 acceptance criterion, end to end on the in-process
    8-device CPU mesh: the dryrun writes dryrun_ledger.jsonl with ≥1
    comm_analysis event carrying nonzero collective bytes, a per-device
    memory snapshot, and passing divergence verdicts; obs_diff self-compare
    exits 0 and an injected +20% collective-bytes delta exits 1 with a
    machine-readable comm verdict."""
    ledger = str(tmp_path / "dryrun_ledger.jsonl")
    monkeypatch.setenv("VIDEOP2P_DRYRUN_LEDGER", ledger)
    graft._dryrun_impl(8)

    events = [json.loads(l) for l in open(ledger) if l.strip()]
    comm = [e for e in events if e["event"] == "comm_analysis"]
    assert any(e["collective_bytes"] > 0 for e in comm)
    assert any(e["event"] == "memory" and e.get("devices") for e in events)
    divs = [e for e in events if e["event"] == "divergence"]
    assert divs and all(e["value"] == 0.0 for e in divs)
    dev = [e for e in events if e["event"] == "device_telemetry"]
    assert dev and all(e["divergence_max"] == 0.0 for e in dev)

    obs_diff = _load_module("obs_diff_under_graft_test", "tools/obs_diff.py")
    assert obs_diff.main(["obs_diff.py", ledger, ledger]) == 0
    # inject +20% collective bytes into a copy → nonzero exit + verdict
    perturbed = str(tmp_path / "perturbed.jsonl")
    with open(perturbed, "w") as f:
        for e in events:
            if e["event"] == "comm_analysis":
                e = dict(e, collective_bytes=int(e["collective_bytes"] * 1.2))
            f.write(json.dumps(e) + "\n")
    assert obs_diff.main(["obs_diff.py", ledger, perturbed]) == 1


@pytest.mark.slow
def test_cpu_cost_capture_tool_end_to_end_tiny(bench, tmp_path):
    """The real subprocess path at tiny scale: the tool builds the bench
    programs abstractly, compiles them on CPU, and emits one JSON record
    per program plus program_analysis ledger events."""
    ledger = str(tmp_path / "capture_ledger.jsonl")
    out = bench.collect_cpu_analysis(2, 2, tiny=True, timeout_s=560.0,
                                     ledger_path=ledger)
    assert set(out) == {"invert_captured", "edit_cached", "e2e_cached"}
    for name, rec in out.items():
        assert rec["flops"] > 0, name
        assert rec["peak_hbm_bytes"] > 0, name
        assert len(rec["hlo_fingerprint"]) == 16, name
        assert rec["backend"] == "cpu" and rec["steps"] == 2
    events = [json.loads(l) for l in open(ledger) if l.strip()]
    pa = {e["program"] for e in events if e["event"] == "program_analysis"}
    assert pa == set(out)


def test_frame_scaling_record_schema(bench):
    """ISSUE 10: the per-frame-count scale-out records are schema-pinned —
    every ring record carries exactly FRAME_SCALING_FIELDS with the
    vs-serial ratios, the tp pairing exactly TP_PAIRING_FIELDS, and
    degenerate inputs yield empty/None instead of raising."""
    analyses = {
        "ring_unit_serial_f64": {"collective_permute_count": 16,
                                 "collective_permute_bytes": 8192,
                                 "flops": 100, "shards": 8},
        "ring_unit_overlap_f64": {"collective_permute_count": 14,
                                  "collective_permute_bytes": 7168,
                                  "flops": 90, "shards": 8},
        "ring_unit_bidir_f64": {"collective_permute_count": 28,
                                "collective_permute_bytes": 7168,
                                "flops": 95, "shards": 8},
        "ring_unit_overlap_f8": {"collective_permute_count": 14,
                                 "collective_permute_bytes": 896,
                                 "flops": 9, "shards": 8},
        "tp_unit_gspmd": {"all_reduce_bytes": 32768, "flops": 7, "shards": 8},
        "tp_unit_scatter": {"reduce_scatter_bytes": 4096, "flops": 7,
                            "shards": 8},
        "not_a_ring_unit": {"flops": 1},
    }
    records = bench.frame_scaling_records(analyses)
    assert [r["frames"] for r in records] == [8, 64, 64, 64]
    for r in records:
        assert set(r) == set(bench.FRAME_SCALING_FIELDS), r
    by = {(r["frames"], r["variant"]): r for r in records}
    assert by[(64, "overlap")]["permute_count_vs_serial"] == round(14 / 16, 3)
    assert by[(64, "overlap")]["permute_bytes_vs_serial"] == 0.875
    assert by[(64, "bidir")]["bytes_per_permute"] == 7168 // 28
    # the 8-frame group has no serial record → ratios None, shape stable
    assert by[(8, "overlap")]["permute_count_vs_serial"] is None

    tp = bench.tp_pairing_record(analyses)
    assert set(tp) == set(bench.TP_PAIRING_FIELDS)
    assert tp["bytes_reduction"] == 8.0
    assert bench.frame_scaling_records({}) == []
    assert bench.tp_pairing_record({}) is None
    assert bench.tp_pairing_record({"tp_unit_gspmd": {"all_reduce_bytes": 1,
                                                      "shards": 8}}) is None


def test_per_call_cost_record_schema(bench):
    """ISSUE 15: the per-UNet-call cost records are schema-pinned — every
    row carries exactly PER_CALL_COST_FIELDS, quant rows normalize against
    ONE fp call, reuse rows against K fp calls for flops/bytes but ONE for
    argument bytes (weights are passed once however many steps read them),
    a missing fp unit yields None ratios, and no unit analyses yield []."""
    analyses = {
        "unet_unit_fp": {"flops": 1000, "bytes_accessed": 2000,
                         "argument_bytes": 400, "peak_hbm_bytes": 50},
        "unet_unit_w8": {"flops": 1010, "bytes_accessed": 1900,
                         "argument_bytes": 100, "peak_hbm_bytes": 40},
        "unet_unit_w8a8": {"flops": 1100, "bytes_accessed": 2100,
                           "argument_bytes": 100, "peak_hbm_bytes": 40},
        "reuse_unit_2": {"flops": 1600, "bytes_accessed": 3400,
                         "argument_bytes": 410, "peak_hbm_bytes": 60},
        "reuse_unit_5": {"flops": 3750, "bytes_accessed": 8000,
                         "argument_bytes": 430, "peak_hbm_bytes": 65},
        "reuse_unit_x": {"flops": 1},   # malformed suffix: ignored
        "distill_unit_fp": {"flops": 1004, "bytes_accessed": 2010,
                            "argument_bytes": 404, "peak_hbm_bytes": 51},
        "distill_unit_2": {"flops": 2008, "bytes_accessed": 4020,
                           "argument_bytes": 414, "peak_hbm_bytes": 62},
        "distill_unit_x": {"flops": 1},  # malformed suffix: ignored
        "e2e_cached": {"flops": 9},     # not a per-call unit: ignored
    }
    records = bench.per_call_cost_records(analyses)
    assert [r["program"] for r in records] == [
        "unet_unit_fp", "unet_unit_w8", "unet_unit_w8a8",
        "reuse_unit_2", "reuse_unit_5",
        "distill_unit_fp", "distill_unit_2",
    ]
    for r in records:
        assert set(r) == set(bench.PER_CALL_COST_FIELDS), r
    by = {r["program"]: r for r in records}
    assert by["unet_unit_fp"]["flops_vs_full"] == 1.0
    assert by["unet_unit_fp"]["calls"] == 1
    assert by["unet_unit_w8"]["quant_mode"] == "w8"
    assert by["unet_unit_w8"]["argument_bytes_vs_full"] == 0.25
    assert by["unet_unit_w8a8"]["quant_mode"] == "w8a8"
    assert by["reuse_unit_2"]["reuse_schedule"] == "uniform:2"
    assert by["reuse_unit_2"]["calls"] == 2
    assert by["reuse_unit_2"]["flops_vs_full"] == 0.8    # 1600 / (2*1000)
    assert by["reuse_unit_5"]["flops_vs_full"] == 0.75   # 3750 / (5*1000)
    assert by["reuse_unit_5"]["bytes_vs_full"] == 0.8    # 8000 / (5*2000)
    assert by["reuse_unit_5"]["argument_bytes_vs_full"] == round(430 / 400, 3)
    # ISSUE 16: the student units — distill_unit_fp's flops_vs_full IS the
    # time-head overhead over one teacher call; distill_unit_<N> normalizes
    # against N teacher calls (per-step student-vs-teacher ratio)
    assert by["distill_unit_fp"]["calls"] == 1
    assert by["distill_unit_fp"]["flops_vs_full"] == 1.004  # 1004 / 1000
    assert by["distill_unit_2"]["calls"] == 2
    assert by["distill_unit_2"]["flops_vs_full"] == 1.004   # 2008 / (2*1000)
    # fp unit missing → ratios None but rows still land, shape stable
    partial = bench.per_call_cost_records(
        {k: v for k, v in analyses.items() if k != "unet_unit_fp"}
    )
    assert all(r["flops_vs_full"] is None for r in partial)
    assert all(set(r) == set(bench.PER_CALL_COST_FIELDS) for r in partial)
    assert bench.per_call_cost_records({}) == []
    assert bench.per_call_cost_records(None) == []


def test_bench_cost_records_schema(bench):
    """ISSUE 19: bench's cost rows are schema-pinned — every analyzed
    program lands with exactly BENCH_COST_FIELDS, measured seconds price
    an achieved flops/s, static-only rows (backend down: no timings)
    carry None for both measured columns, and malformed/empty analyses
    yield []."""
    assert bench.BENCH_COST_FIELDS == (
        "program", "flops", "argument_bytes", "peak_hbm_bytes",
        "measured_s", "achieved_flops_per_s")
    analyses = {
        "invert_captured": {"flops": 1000.0, "argument_bytes": 64,
                            "temp_bytes": 8, "peak_hbm_bytes": 128,
                            "bytes_accessed": 256},
        "edit_cached": {"flops": 500.0, "argument_bytes": 32,
                        "peak_hbm_bytes": 100, "bytes_accessed": 90},
        "bogus": "not-a-dict",   # ignored, never raises
    }
    rows = bench.bench_cost_records(analyses,
                                    {"invert_captured": 2.0,
                                     "edit_cached": 0})   # 0 s: unusable
    assert [r["program"] for r in rows] == ["edit_cached",
                                            "invert_captured"]
    for r in rows:
        assert set(r) == set(bench.BENCH_COST_FIELDS), r
    by = {r["program"]: r for r in rows}
    assert by["invert_captured"]["measured_s"] == 2.0
    assert by["invert_captured"]["achieved_flops_per_s"] == 500.0
    assert by["edit_cached"]["measured_s"] is None
    assert by["edit_cached"]["achieved_flops_per_s"] is None
    # static-only path (record_cpu_only_evidence: backend down)
    static = bench.bench_cost_records(analyses)
    assert all(r["measured_s"] is None for r in static)
    assert bench.bench_cost_records({}) == []
    assert bench.bench_cost_records(None) == []


@pytest.mark.slow
def test_dryrun_longvideo_obs_acceptance(graft, tmp_path):
    """The ISSUE 10 acceptance criterion end to end on the in-process
    8-device CPU mesh: the 64-frame dryrun section completes its float8
    sharded cached edit with src_err == 0.0, lands per-frame-count
    frame_scaling events and the ring/tp comm evidence in the ledger, and
    the ring before/after pair gates through tools/obs_diff.py — exit 0 in
    the engineered direction (collective count/bytes DROP), exit 0 on
    self-compare, exit 1 on an injected collective-bytes bump."""
    from videop2p_tpu.obs.ledger import RunLedger

    ledger_path = str(tmp_path / "longvideo_ledger.jsonl")
    led = RunLedger(ledger_path, mesh="1,8,1",
                    meta={"cli": "longvideo_acceptance"}).activate()
    try:
        res = graft._dryrun_longvideo_impl(8, led)
    finally:
        led.close()
    assert res["src_err_64f"] == 0.0
    assert res["ring"]["overlap"]["collective_permute_count"] == 14
    assert res["ring"]["serial"]["collective_permute_count"] == 16

    events = [json.loads(l) for l in open(ledger_path) if l.strip()]
    fs = [e for e in events if e["event"] == "frame_scaling"]
    assert {e["frames"] for e in fs} >= {8, 32, 64}
    edit = [e for e in fs if e["variant"] == "edit"]
    assert edit and edit[0]["src_err"] == 0.0
    assert edit[0]["temporal_maps_dtype"] == "float8_e4m3fn"
    comm = [e for e in events if e["event"] == "comm_analysis"]
    assert any(e["program"] == "sharded_edit_64f" for e in comm)
    assert any(e["program"] == "tp_out_scatter" for e in comm)

    obs_diff = _load_module("obs_diff_under_longvideo_test", "tools/obs_diff.py")
    assert obs_diff.main(
        ["obs_diff.py", res["ring_before"], res["ring_after"]]
    ) == 0
    assert obs_diff.main(["obs_diff.py", ledger_path, ledger_path]) == 0
    perturbed = str(tmp_path / "perturbed.jsonl")
    with open(perturbed, "w") as f:
        for e in events:
            if e["event"] == "comm_analysis":
                e = dict(e, collective_bytes=int(e["collective_bytes"] * 1.2))
            f.write(json.dumps(e) + "\n")
    assert obs_diff.main(["obs_diff.py", ledger_path, perturbed]) == 1


@pytest.mark.slow
def test_cpu_cost_capture_ring_tp_units(bench):
    """The real subprocess path for the distributed unit programs: one
    JSON record per ring variant × frame count (true unrolled counts,
    frames overriding the global flag) plus the tp pairing units."""
    out = bench.collect_cpu_analysis(
        2, 2, tiny=True, timeout_s=560.0,
        programs=("ring_unit_serial_f64", "ring_unit_overlap_f64",
                  "ring_unit_bidir_f64", "tp_unit_gspmd", "tp_unit_scatter"),
    )
    assert set(out) == {"ring_unit_serial_f64", "ring_unit_overlap_f64",
                        "ring_unit_bidir_f64", "tp_unit_gspmd",
                        "tp_unit_scatter"}
    assert out["ring_unit_serial_f64"]["collective_permute_count"] == 16
    assert out["ring_unit_overlap_f64"]["collective_permute_count"] == 14
    assert out["ring_unit_bidir_f64"]["collective_permute_count"] == 28
    assert all(out[p]["frames"] == 64 for p in out if p.startswith("ring"))
    assert (out["tp_unit_scatter"]["reduce_scatter_bytes"]
            == out["tp_unit_gspmd"]["all_reduce_bytes"] // 8)
    records = bench.frame_scaling_records(out)
    assert {r["variant"] for r in records} == {"serial", "overlap", "bidir"}
    assert bench.tp_pairing_record(out)["bytes_reduction"] == 8.0
