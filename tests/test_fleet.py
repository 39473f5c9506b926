"""Fleet telemetry plane tests (ISSUE 17).

Layers under test, bottom up:

  * ``obs/tsdb.py`` — the bounded ring-buffer store: caller-injected
    monotonic clocks, explicit NaN gaps, aligned-window queries,
    counter-reset-aware rates, snapshot/sidecar round-trip;
  * ``obs/prom.py`` — ``parse_prometheus`` round-trips our own
    exposition (and the live engine's/router's) back to the exact
    ``/metrics`` JSON scalars;
  * ``obs/signals.py`` — the multi-window burn-rate semantics (fast-only
    must NOT page), Theil–Sen trends, saturation, EWMA anomalies,
    per-tenant demand metering, grow/hold/shrink advice;
  * ``serve/collector.py`` — the scrape loop against live HTTP targets
    in both formats, and dead-target gap recording;
  * the verdict/rendering plumbing — SIGNAL_RULES obs_diff teeth,
    rotation x history cross-segment extraction, tools/fleet_dash.py;
  * THE acceptance: a 2-replica fleet under loadgen with the collector
    riding along — healthy run holds with zero burn alerts, a chaos run
    burns both windows, flips the advice to grow and regresses against
    the healthy baseline through obs_diff.
"""

import importlib.util
import json
import math
import os
import threading
import time

import pytest

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _load_tool(name):
    spec = importlib.util.spec_from_file_location(
        f"{name}_under_fleet_test", os.path.join(_REPO, "tools", f"{name}.py")
    )
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


# ------------------------------------------------------------- tsdb -----


def test_tsdb_monotonic_clock_gaps_and_window_queries():
    from videop2p_tpu.obs.tsdb import TimeSeriesStore

    ts = TimeSeriesStore(capacity=8)
    lab = {"replica": "replica0"}
    assert ts.add("queue_depth", 1.0, 2.0, lab)
    assert ts.add("queue_depth", 2.0, 4.0, lab)
    # non-monotonic timestamps are DROPPED and counted, never reordered
    assert not ts.add("queue_depth", 2.0, 9.0, lab)   # equal t
    assert not ts.add("queue_depth", 1.5, 9.0, lab)   # backwards t
    assert not ts.add("queue_depth", 3.0, "nope", lab)  # unfloatable
    assert ts.dropped == 3
    assert ts.series("queue_depth", lab) == [(1.0, 2.0), (2.0, 4.0)]
    # an explicit gap keeps the time axis honest but is skipped by queries
    assert ts.gap("queue_depth", 3.0, lab)
    assert ts.add("queue_depth", 4.0, 6.0, lab)
    assert ts.gaps == 1
    assert ts.window("queue_depth", 4.0, 3.5, lab) == [
        (1.0, 2.0), (2.0, 4.0), (4.0, 6.0)]
    # window alignment is (now - w, now]: t=1.0 falls OUT at window 3.0
    assert ts.window("queue_depth", 4.0, 3.0, lab) == [(2.0, 4.0), (4.0, 6.0)]
    assert ts.mean("queue_depth", 4.0, 3.0, lab) == pytest.approx(5.0)
    assert ts.vmax("queue_depth", 4.0, 3.0, lab) == 6.0
    # latest skips trailing gaps; empty windows are None, never 0
    ts.gap("queue_depth", 5.0, lab)
    assert ts.latest("queue_depth", lab) == (4.0, 6.0)
    assert ts.mean("queue_depth", 100.0, 1.0, lab) is None
    # label identity: same name, different labels = a different series
    ts.add("queue_depth", 1.0, 7.0, {"replica": "replica1"})
    assert len(ts) == 2
    assert ts.labelsets("queue_depth") == [
        {"replica": "replica0"}, {"replica": "replica1"}]
    # the ring is bounded: capacity 8 evicts the oldest, samples stay flat
    for i in range(20):
        ts.add("queue_depth", 10.0 + i, 1.0, lab)
    assert len(ts.series("queue_depth", lab)) == 8


def test_tsdb_counter_reset_rate_and_nearest_rank_quantile():
    from videop2p_tpu.obs.tsdb import TimeSeriesStore

    ts = TimeSeriesStore()
    # a counter that restarts mid-window: 10 -> 14 (+4), reset to 3 (+3
    # post-reset, the Prometheus treatment), 3 -> 8 (+5) = 12 total
    for t, v in [(1.0, 10.0), (2.0, 14.0), (3.0, 3.0), (4.0, 8.0)]:
        ts.add("requests_total", t, v)
    assert ts.increase("requests_total", 4.0, 10.0) == pytest.approx(12.0)
    assert ts.rate("requests_total", 4.0, 10.0) == pytest.approx(12.0 / 3.0)
    # < 2 samples in window -> None (no fake zero-rates)
    assert ts.increase("requests_total", 4.0, 0.5) is None
    # nearest-rank quantiles over the window
    ts2 = TimeSeriesStore()
    for i, v in enumerate([5.0, 1.0, 3.0, 2.0, 4.0]):
        ts2.add("lat", float(i), v)
    assert ts2.quantile("lat", 10.0, 20.0, 50) == 3.0
    assert ts2.quantile("lat", 10.0, 20.0, 100) == 5.0
    assert ts2.quantile("lat", 10.0, 20.0, 0) == 1.0


def test_tsdb_snapshot_sidecar_roundtrip_and_restore(tmp_path):
    from videop2p_tpu.obs import RunLedger, read_ledger
    from videop2p_tpu.obs.tsdb import (
        FLEET_SERIES_FIELDS,
        TimeSeriesStore,
        load_series_sidecar,
        restore_store,
    )

    ts = TimeSeriesStore(capacity=512)
    for i in range(10):
        ts.add("up", float(i), 1.0, {"replica": "replica0"})
        ts.add("queue_depth", float(i), float(i % 3), {"replica": "replica0"})
    ts.gap("queue_depth", 10.0, {"replica": "replica0"})
    path = str(tmp_path / "ledger.jsonl")
    sidecar = str(tmp_path / "series.npz")
    with RunLedger(path) as led:
        rec = ts.snapshot(led, label="fleet", sidecar_path=sidecar)
    assert set(rec) == set(FLEET_SERIES_FIELDS)
    assert rec["series"] == 2 and rec["gaps"] == 1
    assert rec["t_first"] == 0.0 and rec["t_last"] == 10.0
    events = [e for e in read_ledger(path) if e["event"] == "fleet_series"]
    assert len(events) == 1 and events[0]["sidecar"] == rec["sidecar"]
    # sidecar round-trip preserves every sample INCLUDING the NaN gap
    series = load_series_sidecar(rec["sidecar"])
    key = 'queue_depth{replica="replica0"}'
    assert key in series and len(series[key]) == 11
    assert math.isnan(series[key][-1][1])
    # and restore_store rebuilds a queryable store offline
    ts2 = restore_store(rec["sidecar"])
    assert ts2.latest("queue_depth", {"replica": "replica0"}) == (9.0, 0.0)
    assert ts2.samples == ts.samples
    # downsampling keeps the NEWEST sample exactly
    big = TimeSeriesStore(capacity=600)
    for i in range(600):
        big.add("x", float(i), float(i))
    arrays, keys = big.snapshot_arrays(max_points=100)
    assert keys == ["x"] and len(arrays["s0_v"]) <= 100
    assert arrays["s0_t"][-1] == 599.0 and arrays["s0_v"][-1] == 599.0


def test_theil_sen_slope_robust_to_outliers():
    from videop2p_tpu.obs.signals import theil_sen_slope

    pts = [(float(i), 2.0 * i + 1.0) for i in range(20)]
    assert theil_sen_slope(pts) == pytest.approx(2.0)
    # one wild outlier scrape cannot fake (or hide) the trend
    spiked = list(pts)
    spiked[10] = (10.0, 1e6)
    assert theil_sen_slope(spiked) == pytest.approx(2.0, abs=0.2)
    assert theil_sen_slope([]) == 0.0
    assert theil_sen_slope([(1.0, 5.0)]) == 0.0
    assert theil_sen_slope([(1.0, 5.0), (1.0, 9.0)]) == 0.0  # dt <= 0 only


# ------------------------------------------------- prometheus parse -----


def test_parse_prometheus_roundtrip_escapes_and_nonfinite():
    from videop2p_tpu.obs.prom import (
        parse_prometheus,
        render_prometheus,
        samples_by_name,
    )

    metrics = {
        "queue_depth": 3,
        "store": {"hit_rate": 0.75},
        "requests": {"done": 9, "error": 1},
        "tenants": {"team a": {"submitted": 4}},   # space in label value
        "nan_gauge": float("nan"),
        "inf_gauge": float("inf"),
    }
    text = render_prometheus(metrics)
    parsed = parse_prometheus(text)
    by = samples_by_name(parsed)
    assert by["videop2p_queue_depth"][0]["value"] == 3.0
    assert by["videop2p_store_hit_rate"][0]["value"] == 0.75
    done = [s for s in by["videop2p_requests_total"]
            if s["labels"] == {"status": "done"}]
    assert done[0]["value"] == 9.0
    assert by["videop2p_tenant_submitted"][0]["labels"] == {
        "tenant": "team a"}
    assert math.isnan(by["videop2p_nan_gauge"][0]["value"])
    assert by["videop2p_inf_gauge"][0]["value"] == float("inf")
    # HELP/TYPE comments are collected per metric (format conformance)
    assert parsed["types"]["videop2p_queue_depth"] == "gauge"
    assert "gauge" in parsed["help"]["videop2p_queue_depth"]
    # label ESCAPES round-trip: backslash, quote, newline
    tricky = 'm{k="a\\\\b\\"c\\nd"} 1\n'
    s = parse_prometheus(tricky)["samples"][0]
    assert s["labels"]["k"] == 'a\\b"c\nd'
    # malformed lines raise — a half-parsed scrape must not drop gauges
    with pytest.raises(ValueError):
        parse_prometheus("not a metric line at all{\n")


# ---------------------------------------------------------- signals -----


def _seed_requests(ts, now, *, errors_recent=0, errors_old=0, done=20):
    """A replica whose done-counter rises 1/s for `now` seconds; error
    counter rises `errors_old` early and `errors_recent` in the last 2s."""
    from videop2p_tpu.obs.signals import S_REQUESTS, S_UP

    lab = {"replica": "replica0"}
    err = 0.0
    for i in range(int(now) + 1):
        t = float(i)
        ts.add(S_UP, t, 1.0, lab)
        ts.add(S_REQUESTS, t, min(float(i), float(done)),
               {**lab, "status": "done"})
        if i < 3:
            err += errors_old / 3.0
        if i > now - 2:
            err += errors_recent / 2.0
        ts.add(S_REQUESTS, t, err, {**lab, "status": "error"})


def test_burn_alert_requires_both_windows():
    """THE multi-window semantic: a fast-window error spike alone (noisy)
    must NOT page; sustained errors burn both windows and do."""
    from videop2p_tpu.obs.signals import SignalEngine
    from videop2p_tpu.obs.tsdb import TimeSeriesStore

    # window_scale 0.01 -> fast 3 s, slow 36 s
    ts = TimeSeriesStore()
    eng = SignalEngine(ts, window_scale=0.01)
    # 30 s of history: clean everywhere except 2 errors in the last 2 s —
    # fast window burns hard, slow window only mildly (2/30 ≈ 6.7% > 1%
    # would still burn... use a tighter spike: 0.2 errors => slow 0.7%)
    _seed_requests(ts, 30, errors_recent=0.2)
    rec = eng.evaluate(30.0)
    assert rec["burn_fast"] > 1.0          # the spike floods the fast window
    assert rec["burn_slow"] < 1.0          # but the hour-equivalent shrugs
    assert rec["burn_alert"] is False      # -> nobody is paged
    assert rec["burn_alerts"] == 0
    assert rec["scale_advice"] == "hold"
    # sustained failure: errors throughout -> both windows burn -> alert
    ts2 = TimeSeriesStore()
    eng2 = SignalEngine(ts2, window_scale=0.01)
    _seed_requests(ts2, 30, errors_recent=2, errors_old=6)
    rec2 = eng2.evaluate(30.0)
    assert rec2["burn_fast"] > 1.0 and rec2["burn_slow"] > 1.0
    assert rec2["burn_alert"] is True and rec2["burn_alerts"] == 1
    assert rec2["scale_advice"] == "grow"
    assert any("slo-burn" in r for r in rec2["reasons"])
    # cumulative across evaluations (the run roll-up obs_diff gates)
    rec3 = eng2.evaluate(30.5)
    assert rec3["burn_alerts"] == 2
    assert eng2.summary()["burn_alerts"] == 2
    assert eng2.summary()["advice"]["grow"] == 2


def test_advice_shrink_only_when_fully_idle_and_down_replica_grows():
    from videop2p_tpu.obs.signals import (
        S_IN_FLIGHT,
        S_QUEUE_DEPTH,
        S_UP,
        SignalEngine,
    )
    from videop2p_tpu.obs.tsdb import TimeSeriesStore

    ts = TimeSeriesStore()
    eng = SignalEngine(ts, window_scale=0.01)
    for i in range(10):
        t = float(i)
        for r in ("replica0", "replica1"):
            lab = {"replica": r}
            ts.add(S_UP, t, 1.0, lab)
            ts.add(S_QUEUE_DEPTH, t, 0.0, lab)
            ts.add(S_IN_FLIGHT, t, 0.0, lab)
    rec = eng.evaluate(9.0)
    assert rec["replicas_up"] == 2 and rec["replicas_total"] == 2
    assert rec["scale_advice"] == "shrink"      # idle across the slow window
    assert any("idle" in r for r in rec["reasons"])
    # ONE in-flight sample anywhere in the window blocks the shrink
    ts.add(S_IN_FLIGHT, 9.5, 1.0, {"replica": "replica1"})
    assert eng.evaluate(9.6)["scale_advice"] == "hold"
    # a replica going dark (trailing gaps) counts DOWN and advises grow
    ts.gap(S_UP, 10.0, {"replica": "replica0"})
    ts.add(S_UP, 10.0, 1.0, {"replica": "replica1"})
    rec = eng.evaluate(10.1)
    assert rec["replicas_up"] == 1 and rec["replicas_total"] == 2
    assert rec["scale_advice"] == "grow"
    assert any("replicas down 1/2" in r for r in rec["reasons"])


def test_saturation_tenant_demand_and_ewma_anomaly():
    from videop2p_tpu.obs.signals import (
        S_DISPATCH_P50,
        S_LATENCY_P99,
        S_QUEUE_WAIT_P99,
        S_TENANT,
        S_UP,
        SignalEngine,
    )
    from videop2p_tpu.obs.tsdb import TimeSeriesStore

    ts = TimeSeriesStore()
    eng = SignalEngine(ts, window_scale=0.01)
    lab = {"replica": "replica0"}
    for i in range(12):
        t = float(i)
        ts.add(S_UP, t, 1.0, lab)
        ts.add(S_DISPATCH_P50, t, 0.1, lab)
        # queue-wait p99 6x the dispatch p50 -> saturation 6 > threshold 5
        ts.add(S_QUEUE_WAIT_P99, t, 0.6, lab)
        ts.add(S_LATENCY_P99, t, 0.5, lab)
        # tenant A: submitted/done climb 2/s, 1/s; 3 sheds total
        ts.add(S_TENANT, t, 2.0 * i, {**lab, "tenant": "A",
                                      "field": "submitted"})
        ts.add(S_TENANT, t, 1.0 * i, {**lab, "tenant": "A", "field": "done"})
        ts.add(S_TENANT, t, min(float(i), 3.0), {**lab, "tenant": "A",
                                                 "field": "shed"})
    rec = eng.evaluate(11.0)
    assert rec["saturation"] == pytest.approx(6.0)
    assert rec["scale_advice"] == "grow"
    assert any("saturation" in r for r in rec["reasons"])
    lane = rec["tenants"]["A"]
    assert lane["submitted_rate"] == pytest.approx(2.0)
    assert lane["served_rate"] == pytest.approx(1.0)
    assert lane["shed_rate"] > 0.0
    # device-seconds = served increase x dispatch p50 = 11 * 0.1
    assert lane["device_seconds"] == pytest.approx(1.1)
    # EWMA anomaly: a stable latency baseline, then a 10x step -> flagged
    # exactly at the step (flag-then-update, >= 3 warmup observations)
    flags = []
    for i in range(8):
        ts.add(S_LATENCY_P99, 12.0 + i, 0.5 if i < 6 else 5.0, lab)
        flags.append(eng.evaluate(12.0 + i)["latency_anomaly"])
    assert flags[:6] == [False] * 6
    assert flags[6] is True


# -------------------------------------------------------- collector -----


class _FakeEngineMetrics:
    """A stdlib HTTP stand-in for an engine's /healthz + /metrics (both
    formats) — lets the collector tests drive scrapes deterministically
    and then KILL the target to pin gap recording."""

    def __init__(self):
        import http.server

        self.metrics = {
            "queue_depth": 2,
            "in_flight": 1,
            "request_latency": {"blocked_p50_s": 0.2, "blocked_p99_s": 0.9},
            "programs": {"serve_queue_wait": {"blocked_p99_s": 0.3},
                         "serve_dispatch": {"blocked_p50_s": 0.15}},
            "store": {"hit_rate": 0.5},
            "requests": {"done": 7, "error": 1},
            "tenants": {"A": {"submitted": 5, "done": 4, "shed": 1}},
        }
        outer = self

        class Handler(http.server.BaseHTTPRequestHandler):
            def log_message(self, fmt, *args):
                pass

            def do_GET(self):  # noqa: N802
                if self.path == "/healthz":
                    body = json.dumps({"ok": True}).encode()
                    ctype = "application/json"
                elif self.path.startswith("/metrics"):
                    if "format=prometheus" in self.path:
                        from videop2p_tpu.obs.prom import render_prometheus

                        body = render_prometheus(outer.metrics).encode()
                        ctype = "text/plain"
                    else:
                        body = json.dumps(outer.metrics).encode()
                        ctype = "application/json"
                else:
                    self.send_response(404)
                    self.end_headers()
                    return
                self.send_response(200)
                self.send_header("Content-Type", ctype)
                self.send_header("Content-Length", str(len(body)))
                self.end_headers()
                self.wfile.write(body)

        self.server = http.server.ThreadingHTTPServer(("127.0.0.1", 0),
                                                      Handler)
        self.url = f"http://127.0.0.1:{self.server.server_address[1]}"
        self.thread = threading.Thread(target=self.server.serve_forever,
                                       daemon=True)
        self.thread.start()

    def close(self):
        self.server.shutdown()
        self.server.server_close()
        self.thread.join(timeout=5.0)


def test_collector_json_prometheus_equivalence_and_dead_target_gaps():
    """Both scrape formats land IDENTICAL scalars in the tsdb, and a
    target dying mid-run records up=0 plus explicit gaps in every series
    it previously produced — never interpolated values."""
    from videop2p_tpu.obs.signals import S_QUEUE_DEPTH, S_SCRAPE_ERRORS, S_UP
    from videop2p_tpu.serve.collector import FleetCollector

    fake = _FakeEngineMetrics()
    try:
        stores = {}
        for fmt in ("json", "prometheus"):
            col = FleetCollector([("replica0", fake.url)], fmt=fmt,
                                 probe_timeout_s=5.0)
            assert col.scrape_once(now=1.0) == 1
            stores[fmt] = col.tsdb
        jkeys = stores["json"].keys()
        assert jkeys == stores["prometheus"].keys()
        assert len(jkeys) >= 12  # gauges + statuses + tenant fields + meta
        for key in jkeys:
            name, items = key
            jv = stores["json"].latest(name, dict(items))
            pv = stores["prometheus"].latest(name, dict(items))
            assert jv[1] == pv[1], (key, jv, pv)
        # now the outage: scrape ok at t=1..2, target dies, scrape at t=3
        col = FleetCollector([("replica0", fake.url)], probe_timeout_s=5.0)
        assert col.scrape_once(now=1.0) == 1
        assert col.scrape_once(now=2.0) == 1
        seen_before = dict(col.tsdb._series)
        fake.close()
        assert col.scrape_once(now=3.0) == 0
        lab = {"replica": "replica0"}
        assert col.tsdb.series(S_UP, lab)[-1] == (3.0, 0.0)
        # every previously-produced series got an explicit NaN gap
        q = col.tsdb.series(S_QUEUE_DEPTH, lab)
        assert q[-1][0] == 3.0 and math.isnan(q[-1][1])
        gapped = sum(1 for key, ring in col.tsdb._series.items()
                     if key in seen_before and math.isnan(ring[-1][1]))
        assert gapped == len(col.targets[0].seen) >= 10
        # scrape-health counters are first-class series the signals read
        assert col.tsdb.latest(S_SCRAPE_ERRORS, lab)[1] == 1.0
        assert col.scrape_errors == 1 and col.stats()["gaps"] >= 10
        # the signal pass sees the fleet degraded: replica down -> grow
        rec = col.evaluate(now=3.1)
        assert rec["replicas_up"] == 0 and rec["replicas_total"] == 1
        assert rec["scale_advice"] == "grow"
        assert rec["scrape_error_rate"] > 0.0
        assert list(col.history)[-1] is rec
    finally:
        fake.close()


def test_collector_background_thread_scrapes_on_interval():
    from videop2p_tpu.serve.collector import FleetCollector

    fake = _FakeEngineMetrics()
    try:
        col = FleetCollector([("replica0", fake.url)], interval_s=0.02,
                             window_scale=0.001, probe_timeout_s=5.0)
        col.start()
        deadline = time.perf_counter() + 10.0
        while col.scrapes < 3 and time.perf_counter() < deadline:
            time.sleep(0.02)
        col.stop(final_evaluate=True)
        assert col.scrapes >= 3 and col.scrape_errors == 0
        assert col.signals.evaluations >= 1 and len(col.history) >= 1
        # wall-clock scrapes: strictly monotonic timestamps per series
        up = col.tsdb.series("up", {"replica": "replica0"})
        assert all(a[0] < b[0] for a, b in zip(up, up[1:]))
    finally:
        fake.close()


def test_collector_rejects_unknown_format():
    from videop2p_tpu.serve.collector import FleetCollector

    with pytest.raises(ValueError, match="json.*prometheus"):
        FleetCollector([("a", "http://127.0.0.1:1")], fmt="xml")


# ------------------------------------- verdicts, rotation, dashboard ----


def _signals_ledger(path, label="fleet", *, alerts=0, saturation=0.5,
                    advice="hold"):
    """A minimal collector-shaped ledger: N fleet_signals evaluations
    whose LAST event carries the run roll-up obs_diff extracts."""
    from videop2p_tpu.obs import RunLedger
    from videop2p_tpu.obs.signals import FLEET_SIGNALS_FIELDS

    base = {k: 0.0 for k in FLEET_SIGNALS_FIELDS}
    base.update(label=label, window_scale=0.01, fast_window_s=3.0,
                slow_window_s=36.0, burn_alert=False, latency_anomaly=False,
                store_hit_anomaly=False, replicas_up=2, replicas_total=2,
                tenants={}, scale_advice="hold", reasons=[])
    with RunLedger(path) as led:
        # only the last event is the roll-up; earlier ones are superseded
        for i in range(3):
            rec = dict(base, t=float(i), burn_alerts=min(i, alerts),
                       saturation=saturation,
                       scale_advice=advice if i == 2 else "hold",
                       burn_alert=bool(alerts) and i == 2)
            led.event("fleet_signals", **rec)
    return path


def test_obs_diff_signal_rules_teeth(tmp_path, capsys):
    """SIGNAL_RULES gate: self-compare exits 0, a burn-alert appearing
    (0 -> 1) or saturation doubling regresses with exit 1 and a
    machine-readable verdict naming the signal."""
    healthy = _signals_ledger(str(tmp_path / "healthy.jsonl"))
    burned = _signals_ledger(str(tmp_path / "burned.jsonl"), alerts=1,
                             advice="grow")
    saturated = _signals_ledger(str(tmp_path / "sat.jsonl"), saturation=2.0)
    obs_diff = _load_tool("obs_diff")
    assert obs_diff.main(["obs_diff.py", healthy, healthy]) == 0
    capsys.readouterr()
    assert obs_diff.main(["obs_diff.py", healthy, burned]) == 1
    out = capsys.readouterr().out
    assert "burn_alerts" in out
    assert obs_diff.main(["obs_diff.py", healthy, saturated]) == 1
    assert "saturation" in capsys.readouterr().out
    # teeth point the right way: burning -> healthy is an improvement
    assert obs_diff.main(["obs_diff.py", burned, healthy]) == 0


def test_rotation_history_cross_segment_signals_extraction(tmp_path):
    """ISSUE 17 satellite: a rotated collector ledger (PR-14 segments)
    still extracts one coherent run — events stranded in .N.jsonl
    segments (the early serve_health, the first evaluations) replay
    through the chain, and the LAST fleet_signals event wins."""
    from videop2p_tpu.obs import RunLedger, read_ledger
    from videop2p_tpu.obs.history import RunHistory, extract_run, split_runs
    from videop2p_tpu.obs.signals import FLEET_SIGNALS_FIELDS

    path = str(tmp_path / "collector.jsonl")
    base = {k: 0.0 for k in FLEET_SIGNALS_FIELDS}
    base.update(label="fleet", burn_alert=False, latency_anomaly=False,
                store_hit_anomaly=False, tenants={}, scale_advice="hold",
                reasons=[], replicas_up=2, replicas_total=2)
    with RunLedger(path, max_bytes=2000) as led:
        led.event("serve_health", requests=8, done=8, errors=0,
                  error_rate=0.0)   # early event -> oldest segment
        for i in range(30):
            led.event("fleet_signals", **dict(
                base, t=float(i), burn_alerts=float(i),
                tenants={"A": {"submitted_rate": float(i), "served_rate": 0.0,
                               "shed_rate": 0.0, "device_seconds": 0.0}}))
    rotated = sorted(tmp_path.glob("collector.*.jsonl"))
    assert rotated, "no rotation happened — lower max_bytes"
    # the chain replays as ONE run...
    hist = RunHistory.scan(str(tmp_path))
    assert len(hist.runs) == 1
    events = read_ledger(path)
    assert sum(e["event"] == "fleet_signals" for e in events) == 30
    rec = extract_run(split_runs(events)[-1])
    # ...with the LAST evaluation (written to the LIVE file) as the
    # roll-up AND the rotated-out early serve_health still extracted
    assert rec["signals"]["fleet"]["burn_alerts"] == 29.0
    assert rec["signals"]["fleet:tenant:A"]["submitted_rate"] == 29.0
    assert rec["reliability"]["serve"]["requests"] == 8.0


def test_fleet_dash_renders_self_contained_html(tmp_path):
    from videop2p_tpu.obs import RunLedger
    from videop2p_tpu.obs.signals import (
        S_IN_FLIGHT,
        S_QUEUE_DEPTH,
        S_REQUESTS,
        S_UP,
        SignalEngine,
    )
    from videop2p_tpu.obs.tsdb import TimeSeriesStore

    fleet_dash = _load_tool("fleet_dash")
    path = str(tmp_path / "collector.jsonl")
    ts = TimeSeriesStore()
    eng = SignalEngine(ts, window_scale=0.01)
    with RunLedger(path) as led:
        for i in range(20):
            t = float(i)
            for r in ("replica0", "replica1"):
                lab = {"replica": r}
                if r == "replica1" and 8 <= i < 14:
                    ts.gap(S_UP, t, lab)       # an outage window
                else:
                    ts.add(S_UP, t, 1.0, lab)
                ts.add(S_QUEUE_DEPTH, t, float(i % 4), lab)
                ts.add(S_IN_FLIGHT, t, 1.0, lab)
                ts.add(S_REQUESTS, t, float(i), {**lab, "status": "done"})
                ts.add(S_REQUESTS, t, float(i) * 0.5,
                       {**lab, "status": "error"})
            if i % 4 == 3:
                eng.evaluate(t, ledger=led)
        ts.snapshot(led, label="fleet",
                    sidecar_path=str(tmp_path / "series.npz"))
    out = fleet_dash.write_dash(path)
    assert out.endswith("_fleet.html") and os.path.isfile(out)
    html_text = open(out).read()
    assert html_text.startswith("<!doctype html>")
    for marker in ("Burn gauges", "Scale advice", "Series", "<svg", "gaps"):
        assert marker in html_text, marker
    # the sidecar sparklines made it in (one row per stored series)
    assert html_text.count('queue_depth{replica=') == 2
    # the CLI wrapper and --out/--title flags
    custom = str(tmp_path / "custom.html")
    assert fleet_dash.main(["fleet_dash", path, "--out", custom,
                            "--title", "My fleet"]) == 0
    assert "<h1>My fleet</h1>" in open(custom).read()
    assert fleet_dash.main(["fleet_dash"]) == 2          # usage error
    assert fleet_dash.main(["fleet_dash", str(tmp_path / "nope.jsonl")]) == 2
    # a signals-only ledger (no snapshot) and an empty ledger both render
    from videop2p_tpu.obs import RunLedger as _RL

    bare = str(tmp_path / "bare.jsonl")
    with _RL(bare) as led:
        eng.evaluate(99.0, ledger=led)
    assert "Burn gauges" in fleet_dash.render_dash(
        __import__("videop2p_tpu.obs.ledger", fromlist=["read_ledger"]
                   ).read_ledger(bare))
    empty = str(tmp_path / "empty.jsonl")
    with _RL(empty):
        pass
    assert "no fleet_signals" in fleet_dash.render_dash(
        __import__("videop2p_tpu.obs.ledger", fromlist=["read_ledger"]
                   ).read_ledger(empty))


def test_loadgen_collector_flag_validation():
    loadgen = _load_tool("serve_loadgen")
    with pytest.raises(SystemExit):
        loadgen.main(["--inproc", "--collector"])


# ------------------------------------------- live fleet (tiny, CPU) -----

_SPEC_KW = dict(checkpoint=None, tiny=True, width=16, video_len=2, steps=2)
_PROMPTS = ("a rabbit is jumping", "a origami rabbit is jumping")


@pytest.fixture(scope="module")
def programs():
    """One warm tiny ProgramSet shared by every fleet in this module."""
    from videop2p_tpu.serve import ProgramSet, ProgramSpec

    ps = ProgramSet(ProgramSpec(**_SPEC_KW))
    ps.warm(_PROMPTS, batch_sizes=(2,))
    return ps


def _request(**overrides):
    from videop2p_tpu.serve import EditRequest

    kw = dict(image_path="data/rabbit", prompt=_PROMPTS[0],
              prompts=list(_PROMPTS), save_name="fleet")
    kw.update(overrides)
    return EditRequest(**kw)


def _fleet_loadgen_run(programs, root, *, faults=None, seed=71):
    """A 2-replica fleet + router + riding FleetCollector, driven by the
    loadgen closed loop — the exact composition tools/serve_loadgen.py
    --router N --collector wires up."""
    from videop2p_tpu.serve import ReplicaSupervisor, Router, RouterServer
    from videop2p_tpu.serve.collector import FleetCollector

    loadgen = _load_tool("serve_loadgen")
    sup = ReplicaSupervisor(
        programs.spec, 2, out_dir=root, programs=programs,
        warm_prompts=_PROMPTS,
        engine_kwargs=dict(max_retries=0, breaker_threshold=1,
                           breaker_open_s=60.0),
        faults=faults or {},
    )
    sup.start()
    router = Router(sup.urls, probe_ttl_s=0.05, suspend_s=5.0)
    server = RouterServer(router).start()
    # tiny CPU engines legitimately run queue-wait p99 tens of times the
    # dispatch p50 under a closed loop, and a 10 s burst makes any queue
    # trend pure noise — with the default thresholds every run would pin
    # "grow" and mask the burn/advice teeth this acceptance is about, so
    # raise both policy knobs out of the way (saturation read 114 on a
    # healthy fleet beside five busy xdist workers: 100 was not out of it)
    collector = FleetCollector(
        [(r.name, r.url) for r in sup.replicas] + [("router", server.url)],
        interval_s=0.05, window_scale=0.02,   # fast 6 s / slow 72 s
        signal_kwargs=dict(saturation_threshold=1e4,
                           queue_slope_threshold=10.0),
    )
    collector.start()
    ledger_path = os.path.join(root, "loadgen.jsonl")
    try:
        def collect_extra(record):
            events = []
            for r in sup.replicas:
                events += [dict(e) for e in r.engine.fault_log]
                events.append({"event": "serve_health", "label": r.name,
                               **r.engine.health_record()})
                events += [{"event": "cost_attribution", "label": r.name,
                            **row} for row in r.engine.cost_records()]
            events.append({"event": "router_health",
                           **router.health_record()})
            collector.stop(final_evaluate=True)
            events += [{"event": "fleet_signals", **rec}
                       for rec in collector.history]
            snap = collector.snapshot(
                label="fleet",
                sidecar_path=os.path.join(root, "fleet_series.npz"))
            events.append({"event": "fleet_series", **snap})
            record["signals"] = {**collector.signals.summary(),
                                 **collector.stats()}
            return events

        record = loadgen.run_loadgen(
            loadgen._HttpTarget(server.url, timeout_s=300.0),
            _request(seed=seed).to_dict(),
            requests=8, concurrency=2, ledger_path=ledger_path,
            meta={"target": "fleet-collector"}, collect_extra=collect_extra,
        )
    finally:
        collector.stop(final_evaluate=False)
        server.close()
        sup.stop()
    return record, ledger_path


def test_live_exposition_roundtrip_and_probe_age(programs, tmp_path):
    """ISSUE 17 satellites on LIVE surfaces: the engine's and router's
    prometheus expositions parse back to the exact /metrics JSON scalars
    (# HELP/# TYPE conformance included), and the router's per-replica
    metrics carry the probe_age_s staleness stamp."""
    from videop2p_tpu.obs.prom import parse_prometheus, samples_by_name
    from videop2p_tpu.serve import ReplicaSupervisor, Router, RouterServer
    from videop2p_tpu.serve.client import EngineClient

    sup = ReplicaSupervisor(programs.spec, 1, out_dir=str(tmp_path),
                            programs=programs, warm_prompts=_PROMPTS)
    sup.start()
    router = Router(sup.urls, probe_ttl_s=0.05)
    server = RouterServer(router).start()
    try:
        eng = sup.replicas[0].engine
        rec = eng.result(eng.submit(_request(seed=70)), wait_s=300.0)
        assert rec["status"] == "done", rec.get("error")
        client = EngineClient(sup.replicas[0].url)
        metrics = client.metrics()
        parsed = parse_prometheus(client.metrics_prometheus())
        by = samples_by_name(parsed)
        assert by["videop2p_queue_depth"][0]["value"] == float(
            metrics["queue_depth"])
        assert by["videop2p_store_hit_rate"][0]["value"] == float(
            metrics["store"]["hit_rate"])
        done = [s for s in by["videop2p_requests_total"]
                if s["labels"] == {"status": "done"}]
        assert done[0]["value"] == float(metrics["requests"]["done"])
        # every rendered metric is HELP/TYPE-annotated
        for name in by:
            assert parsed["types"][name] == "gauge"
            assert name in parsed["help"]
        # the router: same round-trip + the probe staleness stamp
        rclient = EngineClient(server.url)
        rclient.healthz()   # force a probe so the cache has an age
        rmetrics = rclient.metrics()
        view = rmetrics["replicas"]["replica0"]
        assert "probe_age_s" in view
        assert view["probe_age_s"] is not None and view["probe_age_s"] >= 0.0
        rby = samples_by_name(parse_prometheus(rclient.metrics_prometheus()))
        assert rby["videop2p_replica_probe_age_s"][0]["labels"] == {
            "replica": "replica0"}
    finally:
        server.close()
        sup.stop()


def test_fleet_collector_acceptance_healthy_vs_chaos(programs, tmp_path):
    """THE ISSUE 17 acceptance: a healthy 2-replica loadgen run records
    ZERO burn alerts and holds; the same run with replica 0 in an
    unavailable fault window fires fast+slow burn, flips the advice to
    grow while degraded, and REGRESSES against the healthy baseline
    through obs_diff's SIGNAL_RULES; both ledgers render to HTML
    dashboards."""
    from videop2p_tpu.obs import read_ledger
    from videop2p_tpu.obs.history import extract_run, split_runs

    healthy_root = str(tmp_path / "healthy")
    chaos_root = str(tmp_path / "chaos")
    os.makedirs(healthy_root)
    os.makedirs(chaos_root)
    h_record, h_ledger = _fleet_loadgen_run(programs, healthy_root, seed=71)
    c_record, c_ledger = _fleet_loadgen_run(
        programs, chaos_root, faults={0: "unavail@1-999"}, seed=72)

    # healthy: everything served, no burn, the final advice is hold
    assert h_record["done"] == 8 and h_record["errors"] == 0
    assert h_record["signals"]["evaluations"] >= 2
    assert h_record["signals"]["burn_alerts"] == 0
    h_events = [e for e in read_ledger(h_ledger)
                if e["event"] == "fleet_signals"]
    assert h_events and h_events[-1]["scale_advice"] == "hold", (
        h_events[-1].get("reasons"))
    assert all(not e["burn_alert"] for e in h_events)
    # the scrape loop genuinely watched all three surfaces
    assert h_record["signals"]["targets"] == 3
    assert h_record["signals"]["scrape_errors"] == 0
    assert h_events[-1]["replicas_up"] == 2
    # ISSUE 19: the scraped cost plane PRICED the advice — the roll-up
    # carries measured utilization and at least one evaluation cites an
    # economic reason (hold/shrink annotations or a priced grow)
    assert h_events[-1]["utilization"] is not None
    assert any(("economics" in r) or ("shrink-is-cheap" in r)
               for e in h_events for r in e["reasons"])
    # the replicas' chargeback rows rode collect_extra into the ledger
    h_costs = [e for e in read_ledger(h_ledger)
               if e.get("event") == "cost_attribution"]
    assert {e["scope"] for e in h_costs} >= {"engine", "tenant"}

    # chaos: replica 0's doomed dispatches burned BOTH windows at least
    # once and the advice flipped to grow while degraded
    assert c_record["errors"] >= 1
    assert c_record["signals"]["burn_alerts"] >= 1
    c_events = [e for e in read_ledger(c_ledger)
                if e["event"] == "fleet_signals"]
    burned = [e for e in c_events if e["burn_alert"]]
    assert burned, "no evaluation saw both windows burn"
    assert burned[0]["burn_fast"] > 1.0 and burned[0]["burn_slow"] > 1.0
    assert burned[0]["scale_advice"] == "grow"
    assert any("slo-burn" in r for e in burned for r in e["reasons"])
    # the run roll-up (LAST event) carries the cumulative alert count
    assert c_events[-1]["burn_alerts"] == c_record["signals"]["burn_alerts"]

    # gates: self-compare clean, chaos-vs-healthy regresses on SIGNAL_RULES
    obs_diff = _load_tool("obs_diff")
    assert obs_diff.main(["obs_diff.py", h_ledger, h_ledger]) == 0
    assert obs_diff.main(["obs_diff.py", h_ledger, c_ledger]) == 1
    sig = extract_run(split_runs(read_ledger(c_ledger))[-1])["signals"]
    assert sig["fleet"]["burn_alerts"] >= 1.0
    assert extract_run(split_runs(read_ledger(h_ledger))[-1])[
        "signals"]["fleet"]["burn_alerts"] == 0.0

    # both runs render through the dashboard to self-contained HTML
    fleet_dash = _load_tool("fleet_dash")
    for ledger in (h_ledger, c_ledger):
        out = fleet_dash.write_dash(ledger)
        text = open(out).read()
        assert text.startswith("<!doctype html>")
        assert "Burn gauges" in text and "Series" in text
