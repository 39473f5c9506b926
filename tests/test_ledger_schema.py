"""Schema pins of the run ledger's events and of the observability stack's
import and clock contracts: what `tools/obs_diff.py`, `tools/ledger_summary.py`
and `obs/history.py` read by name must keep its fields.
"""

import os

import pytest

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


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
