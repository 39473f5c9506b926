"""Cost & capacity plane tests (ISSUE 19): the fair-share CostModel
(attribution + conservation by construction, store-hit savings pricing),
the utilization/headroom economics in the signal engine, the measured
per-tenant device-seconds consistency between the engine and fleet
planes, the obs_diff COST_RULES teeth, the showback report, and the
tools/ CLI contract smoke (every entry point helps with exit 0 and
fails missing input with exit 2).
"""

import importlib.util
import inspect
import os
import sys

import pytest

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

_TOOLS = sorted(
    f[:-3] for f in os.listdir(os.path.join(_REPO, "tools"))
    if f.endswith(".py")
)


def _load_tool(name):
    spec = importlib.util.spec_from_file_location(
        f"{name}_under_cost_test", os.path.join(_REPO, "tools", f"{name}.py")
    )
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


# ---------------------------------------------------------- CostModel ----


def test_price_dispatch_fair_share_and_conservation():
    """The attribution core: a dispatch splits evenly over padded slots,
    real slots charge requests and pad slots charge padding waste, the
    static program facts scale per slot — and the capacity books conserve
    BY CONSTRUCTION (attributed + padding = busy, residual 0)."""
    from videop2p_tpu.obs.cost import CostModel

    model = CostModel()
    model.observe_program("serve_edit_b4", {"flops": 800.0,
                                            "peak_hbm_bytes": 100.0,
                                            "argument_bytes": 64.0})
    slot = model.price_dispatch(2.0, real=3, padded=4,
                                program="serve_edit_b4",
                                singleton="serve_edit")
    assert slot["program"] == "serve_edit"
    assert slot["device_seconds"] == pytest.approx(0.5)   # 2.0 / 4 slots
    assert slot["flops"] == pytest.approx(200.0)          # 800 / 4
    assert slot["hbm_byte_seconds"] == pytest.approx(50.0)  # 100*2/4
    assert slot["padding_share"] == pytest.approx(0.25)
    cap = model.capacity(10.0)
    assert cap["busy_seconds"] == pytest.approx(2.0)
    assert cap["attributed_seconds"] == pytest.approx(1.5)
    assert cap["padding_seconds"] == pytest.approx(0.5)
    assert cap["idle_seconds"] == pytest.approx(8.0)
    assert cap["busy_fraction"] == pytest.approx(0.2)
    assert cap["padding_waste"] == pytest.approx(0.25)    # 0.5 / 2.0 busy
    assert cap["occupancy"] == pytest.approx(0.75)
    assert cap["conservation_residual_s"] == 0.0
    # singleton fallback: no static under the batched label -> the
    # singleton's statics already ARE one slot's (divide by 1)
    m2 = CostModel()
    m2.observe_program("serve_edit", {"flops": 200.0})
    s2 = m2.price_dispatch(1.0, real=1, padded=2,
                           program="serve_edit_b2", singleton="serve_edit")
    assert s2["flops"] == pytest.approx(200.0)
    assert s2["device_seconds"] == pytest.approx(0.5)
    # degenerate inputs clamp instead of raising (obs never takes the
    # engine down): padded 0 -> 1 slot, negative seconds -> 0
    s3 = CostModel().price_dispatch(-1.0, real=0, padded=0)
    assert s3["device_seconds"] == 0.0 and s3["padding_share"] == 1.0
    # junk static records are ignored, never raised on
    m3 = CostModel()
    m3.observe_program("x", None)
    m3.observe_program("x", {"flops": "bogus"})
    assert m3.static_cost("x") is None


def test_savings_measured_mean_then_static_fallback():
    """A store hit's avoided spend: the measured mean fresh-inversion
    seconds when any ran in-process, else the static serve_invert flops
    priced at the observed dispatch throughput, else 0."""
    from videop2p_tpu.obs.cost import CostModel

    model = CostModel()
    assert model.savings() == {"saved_device_seconds": 0.0,
                               "saved_flops": 0.0}
    model.note_fresh_inversion(2.0)
    model.note_fresh_inversion(4.0)
    assert model.savings()["saved_device_seconds"] == pytest.approx(3.0)
    assert model.savings()["saved_flops"] == 0.0   # no analysis landed
    model.observe_program("serve_invert", {"flops": 1000.0})
    assert model.savings()["saved_flops"] == 1000.0
    # static fallback: no measured inversion but a throughput observation
    m2 = CostModel()
    m2.observe_program("serve_invert", {"flops": 1000.0})
    m2.observe_program("serve_edit", {"flops": 500.0})
    m2.price_dispatch(1.0, real=1, padded=1, program="serve_edit")
    # throughput = 500 flops / 1 busy second -> 1000 flops cost 2 s
    assert m2.savings()["saved_device_seconds"] == pytest.approx(2.0)


def test_account_request_program_split_and_attribution_records():
    """Terminal accounting: the tenant lane gets the whole cost vector,
    an optional program split books the dispatch slot and the fresh
    inversion under their own labels, and attribution_records emits the
    engine roll-up first then tenants/programs sorted, schema-exact."""
    from videop2p_tpu.obs.cost import COST_ATTRIBUTION_FIELDS, CostModel

    model = CostModel()
    edit_part = {"device_seconds": 0.5, "flops": 100.0,
                 "hbm_byte_seconds": 5.0}
    inv_part = {"device_seconds": 1.5, "flops": 900.0,
                "hbm_byte_seconds": 9.0}
    cost = {"program": "serve_edit", "device_seconds": 2.0,
            "flops": 1000.0, "hbm_byte_seconds": 14.0,
            "queue_seconds": 0.25, "saved_device_seconds": 0.0,
            "saved_flops": 0.0}
    model.account_request(tenant="acme", cost=cost,
                          programs=[("serve_edit", edit_part),
                                    ("serve_invert", inv_part)])
    hit = dict(cost, device_seconds=0.5, flops=100.0, hbm_byte_seconds=5.0,
               saved_device_seconds=1.5, saved_flops=900.0)
    model.account_request(tenant="acme", cost=hit, store_hit=True)
    rows = model.attribution_records(10.0)
    assert rows[0]["scope"] == "engine" and rows[0]["name"] == "serve"
    by = {(r["scope"], r["name"]): r for r in rows[1:]}
    for r in rows[1:]:
        assert set(r) == set(COST_ATTRIBUTION_FIELDS)
    acme = by[("tenant", "acme")]
    assert acme["requests"] == 2.0 and acme["store_hits"] == 1.0
    assert acme["device_seconds"] == pytest.approx(2.5)
    assert acme["saved_device_seconds"] == pytest.approx(1.5)
    assert acme["cost_per_request_s"] == pytest.approx(1.25)
    # the split: serve_invert carries ONLY the inversion part, and the
    # program parts sum back to the tenant total (nothing double-booked)
    assert by[("program", "serve_invert")]["device_seconds"] == \
        pytest.approx(1.5)
    assert by[("program", "serve_edit")]["device_seconds"] == \
        pytest.approx(1.0)   # 0.5 cold slot + 0.5 hit slot
    prog_total = sum(r["device_seconds"] for (s, _), r in by.items()
                     if s == "program")
    assert prog_total == pytest.approx(acme["device_seconds"])


# --------------------------------------------- signals economics ---------


def _idle_fleet_tsdb(replicas=("replica0", "replica1"), *, capacity=True,
                     busy=0.2, cpr=0.2, waste=0.1):
    """An idle 2-replica fleet trace; optionally with the scraped
    cost-plane gauges riding along."""
    from videop2p_tpu.obs.signals import (
        S_BUSY_FRACTION,
        S_COST_PER_REQUEST,
        S_IN_FLIGHT,
        S_PADDING_WASTE,
        S_QUEUE_DEPTH,
        S_UP,
    )
    from videop2p_tpu.obs.tsdb import TimeSeriesStore

    ts = TimeSeriesStore()
    for i in range(10):
        t = float(i)
        for r in replicas:
            lab = {"replica": r}
            ts.add(S_UP, t, 1.0, lab)
            ts.add(S_QUEUE_DEPTH, t, 0.0, lab)
            ts.add(S_IN_FLIGHT, t, 0.0, lab)
            if capacity:
                ts.add(S_BUSY_FRACTION, t, busy, lab)
                ts.add(S_PADDING_WASTE, t, waste, lab)
                ts.add(S_COST_PER_REQUEST, t, cpr, lab)
    return ts


def test_capacity_signals_price_the_advice():
    """ISSUE 19: with the cost plane scraped, an idle-fleet shrink cites
    shrink-is-cheap with the idle fraction and cost-per-request; the
    record carries utilization/headroom economics; WITHOUT the cost
    plane every economic field is None and the reasons are exactly the
    pre-cost-plane ones."""
    from videop2p_tpu.obs.signals import SignalEngine

    eng = SignalEngine(_idle_fleet_tsdb(), window_scale=0.01)
    rec = eng.evaluate(9.0)
    assert rec["scale_advice"] == "shrink"
    assert rec["utilization"] == pytest.approx(0.2)
    assert rec["idle_fraction"] == pytest.approx(0.8)
    assert rec["padding_waste"] == pytest.approx(0.1)
    assert rec["cost_per_request_s"] == pytest.approx(0.2)
    # 2 up replicas at 0.2 s/request -> 10 requests/s of capacity, all
    # of it headroom (no demand)
    assert rec["capacity_rps"] == pytest.approx(10.0)
    assert rec["headroom_rps"] == pytest.approx(10.0)
    assert rec["utilization_slope"] == pytest.approx(0.0)
    assert rec["utilization_forecast"] == pytest.approx(0.2)
    assert any("shrink-is-cheap" in r and "cost_per_request" in r
               for r in rec["reasons"])
    # absent cost plane: identical advice, all-None economics, and NO
    # economic reason — pre-ISSUE-19 fleets evaluate exactly as before
    bare = SignalEngine(_idle_fleet_tsdb(capacity=False),
                        window_scale=0.01)
    rec2 = bare.evaluate(9.0)
    assert rec2["scale_advice"] == "shrink"
    for k in ("utilization", "idle_fraction", "padding_waste",
              "cost_per_request_s", "capacity_rps", "headroom_rps",
              "utilization_slope", "utilization_forecast"):
        assert rec2[k] is None, k
    assert not any("economics" in r or "shrink-is-cheap" in r
                   for r in rec2["reasons"])


def test_tenant_device_seconds_measured_plane_agrees_with_engine():
    """ISSUE 19 satellite: the fleet's per-tenant device-seconds are the
    MEASURED cost-plane counter when the collector meters it — and on a
    deterministic trace they agree exactly with the engine-side
    CostModel aggregate the counter was scraped from; without the
    series the lane falls back to the served x dispatch-p50 estimate."""
    from videop2p_tpu.obs.cost import CostModel
    from videop2p_tpu.obs.signals import (
        S_DISPATCH_P50,
        S_TENANT,
        S_UP,
        SignalEngine,
    )
    from videop2p_tpu.obs.tsdb import TimeSeriesStore

    # engine plane: tenant A finishes one 0.3 s request per tick
    model = CostModel()
    cum = []
    for _ in range(11):
        model.account_request(tenant="A",
                              cost={"program": "serve_edit",
                                    "device_seconds": 0.3})
        cum.append(model.tenant_costs()["A"]["device_seconds"])
    # fleet plane: the scraped counter is exactly that aggregate
    ts = TimeSeriesStore()
    lab = {"replica": "replica0"}
    for i in range(11):
        t = float(i)
        ts.add(S_UP, t, 1.0, lab)
        ts.add(S_DISPATCH_P50, t, 0.5, lab)
        ts.add(S_TENANT, t, float(i + 1),
               {**lab, "tenant": "A", "field": "done"})
        ts.add(S_TENANT, t, cum[i],
               {**lab, "tenant": "A", "field": "device_seconds"})
    eng = SignalEngine(ts, window_scale=0.01)
    lane = eng.evaluate(10.0)["tenants"]["A"]
    # measured: the counter's increase over the window == the engine-side
    # spend over the same requests (NOT served x p50 = 10 x 0.5 = 5.0)
    assert lane["device_seconds"] == pytest.approx(cum[-1] - cum[0])
    assert lane["device_seconds"] == pytest.approx(3.0)
    # fallback: same trace without the measured series -> the estimate
    ts2 = TimeSeriesStore()
    for i in range(11):
        t = float(i)
        ts2.add(S_UP, t, 1.0, lab)
        ts2.add(S_DISPATCH_P50, t, 0.5, lab)
        ts2.add(S_TENANT, t, float(i + 1),
                {**lab, "tenant": "A", "field": "done"})
    lane2 = SignalEngine(ts2, window_scale=0.01).evaluate(
        10.0)["tenants"]["A"]
    assert lane2["device_seconds"] == pytest.approx(10 * 0.5)


# --------------------------------------------- obs_diff COST_RULES ------


def _cost_ledger(path, *, cpr=0.2, busy=0.5, padding=0.1, idle=0.45):
    """A minimal serve-shaped ledger whose cost_attribution rows obs_diff
    extracts into the `cost` section COST_RULES gate."""
    from videop2p_tpu.obs import RunLedger

    with RunLedger(path) as led:
        led.event("cost_attribution", label="serve", scope="engine",
                  name="serve", uptime_s=10.0, busy_seconds=busy * 10,
                  attributed_seconds=busy * 10 * (1 - padding),
                  padding_seconds=busy * 10 * padding,
                  idle_seconds=idle * 10, busy_fraction=busy,
                  idle_fraction=idle, padding_waste=padding,
                  occupancy=1.0 - padding, dispatches=10, real_slots=18,
                  padded_slots=20, requests_costed=20.0,
                  cost_per_request_s=cpr, conservation_residual_s=0.0)
        led.event("cost_attribution", label="serve", scope="tenant",
                  name="A", requests=20.0, store_hits=10.0,
                  device_seconds=cpr * 20, flops=100.0,
                  hbm_byte_seconds=1.0, queue_seconds=0.5,
                  saved_device_seconds=1.0, saved_flops=50.0,
                  cost_per_request_s=cpr)
    return path


def test_obs_diff_cost_rules_teeth(tmp_path, capsys):
    """THE cost gate: self-compare exits 0; cost-per-request +50% or the
    busy fraction collapsing (utilization direction=decrease) or padding
    waste doubling all regress with exit 1 and a machine-readable verdict
    naming the metric; the improvement direction stays clean."""
    healthy = _cost_ledger(str(tmp_path / "healthy.jsonl"))
    pricier = _cost_ledger(str(tmp_path / "pricier.jsonl"), cpr=0.3)
    idler = _cost_ledger(str(tmp_path / "idler.jsonl"), busy=0.2,
                         idle=0.75)
    wasteful = _cost_ledger(str(tmp_path / "wasteful.jsonl"), padding=0.3)
    obs_diff = _load_tool("obs_diff")
    assert obs_diff.main(["obs_diff.py", healthy, healthy]) == 0
    capsys.readouterr()
    assert obs_diff.main(["obs_diff.py", healthy, pricier]) == 1
    assert "cost_per_request_s" in capsys.readouterr().out
    assert obs_diff.main(["obs_diff.py", healthy, idler]) == 1
    out = capsys.readouterr().out
    assert "busy_fraction" in out or "idle_fraction" in out
    assert obs_diff.main(["obs_diff.py", healthy, wasteful]) == 1
    assert "padding_waste" in capsys.readouterr().out
    # teeth point the economic way: getting cheaper is never a regression
    assert obs_diff.main(["obs_diff.py", pricier, healthy]) == 0


# ------------------------------------------------- showback report -------


def _showback_events():
    return [
        {"event": "run_start", "run_id": "r1", "t": 0.0},
        {"event": "program_analysis", "program": "serve_edit",
         "flops": 100.0, "argument_bytes": 8.0},
        {"event": "program_analysis", "program": "serve_invert",
         "flops": 900.0, "argument_bytes": 8.0},
        {"event": "cost_attribution", "label": "serve", "scope": "engine",
         "name": "serve", "uptime_s": 10.0, "busy_seconds": 4.0,
         "attributed_seconds": 3.5, "padding_seconds": 0.5,
         "idle_seconds": 6.0, "busy_fraction": 0.4, "idle_fraction": 0.6,
         "padding_waste": 0.125, "occupancy": 0.875, "dispatches": 4,
         "real_slots": 7, "padded_slots": 8, "requests_costed": 4.0,
         "cost_per_request_s": 0.875, "conservation_residual_s": 0.0},
        {"event": "cost_attribution", "label": "serve", "scope": "tenant",
         "name": "acme", "requests": 3.0, "store_hits": 2.0,
         "device_seconds": 2.625, "flops": 300.0, "hbm_byte_seconds": 2.0,
         "queue_seconds": 0.25, "saved_device_seconds": 3.125,
         "saved_flops": 1800.0, "cost_per_request_s": 0.875},
        {"event": "cost_attribution", "label": "serve", "scope": "tenant",
         "name": "default", "requests": 1.0, "store_hits": 0.0,
         "device_seconds": 0.875, "flops": 100.0, "hbm_byte_seconds": 1.0,
         "queue_seconds": 0.1, "saved_device_seconds": 0.0,
         "saved_flops": 0.0, "cost_per_request_s": 0.875},
        {"event": "cost_attribution", "label": "serve", "scope": "program",
         "name": "serve_edit", "requests": 4.0, "store_hits": 2.0,
         "device_seconds": 2.0, "flops": 400.0, "hbm_byte_seconds": 3.0,
         "queue_seconds": 0.35, "saved_device_seconds": 3.125,
         "saved_flops": 1800.0, "cost_per_request_s": 0.5},
    ]


def test_cost_report_renders_chargeback_and_savings(tmp_path):
    """The showback page: conservation sentence and waste bars for the
    engine scope, the per-tenant chargeback table sorted by spend with
    share-%% and the CACHE SAVINGS column (the amortization pin's human
    face), the per-program achieved-vs-static join — and a pre-cost-plane
    ledger renders the empty state, exit 0 end to end."""
    from videop2p_tpu.obs import RunLedger

    cost_report = _load_tool("cost_report")
    text = cost_report.render_report(_showback_events())
    assert text.startswith("<!doctype html>")
    assert "conservation" in text and "never" in text
    assert "padding waste" in text and "idle" in text
    assert "Per-tenant chargeback" in text
    # acme first (biggest spender), with its share of the attributed
    # total and the avoided device-seconds a store hit didn't re-burn
    assert text.index("acme") < text.index("default")
    assert "75.0%" in text          # 2.625 of 3.5 attributed
    assert "3.125" in text          # saved_device_seconds rendered
    assert "Per-program achieved vs static" in text
    assert "1.00x" in text          # 400 flops / 4 requests vs static 100
    # ledger -> file round-trip through main()
    path = str(tmp_path / "serve.jsonl")
    with RunLedger(path) as led:
        for e in _showback_events():
            if e["event"] != "run_start":
                led.event(e.pop("event"), **e)
    out = str(tmp_path / "showback.html")
    assert cost_report.main(["cost_report.py", path, "--out", out]) == 0
    assert "chargeback" in open(out).read()
    # pre-cost-plane ledgers: empty state, still exit 0
    empty = str(tmp_path / "old.jsonl")
    with RunLedger(empty) as led:
        led.event("serve_health", requests=1)
    assert cost_report.main(["cost_report.py", empty]) == 0
    assert "no cost_attribution" in open(
        str(tmp_path / "old_cost.html")).read()


# ------------------------------------------- tools CLI contract ----------


def test_tools_inventory_is_complete():
    """The smoke below covers every entry point: pin the inventory so a
    new tool must join the contract."""
    assert len(_TOOLS) == 14
    assert {"cost_report", "fleet_dash", "incident_report",
            "ledger_summary", "obs_diff", "probe_report",
            "serve_loadgen"} <= set(_TOOLS)


@pytest.mark.parametrize("tool", _TOOLS)
def test_tool_help_contract(tool, monkeypatch, capsys):
    """ISSUE 19 satellite: EVERY tools/*.py entry point answers --help
    with exit 0 and usage text — none of them starts a benchmark, opens
    a ledger, or crashes on the help path."""
    mod = _load_tool(tool)
    monkeypatch.setattr(sys, "argv", [f"{tool}.py", "--help"])
    sig = inspect.signature(mod.main)
    required = [p for p in sig.parameters.values()
                if p.default is inspect.Parameter.empty
                and p.kind in (p.POSITIONAL_ONLY, p.POSITIONAL_OR_KEYWORD)]
    try:
        rc = mod.main(sys.argv) if required else mod.main()
    except SystemExit as e:   # argparse's --help path
        rc = e.code
    assert rc in (0, None)
    out = capsys.readouterr().out
    assert out.strip(), f"{tool} --help printed nothing"


@pytest.mark.parametrize("tool,argv_tail", [
    ("cost_report", ["nope.jsonl"]),
    ("edit_report", ["nope.jsonl"]),
    ("fleet_dash", ["nope.jsonl"]),
    ("incident_report", ["nope.bundle"]),
    ("ledger_summary", ["nope.jsonl"]),
    ("obs_diff", ["nope.jsonl", "nope.jsonl"]),
    ("probe_report", ["nope.jsonl"]),
    ("trace_view", ["nope.jsonl"]),
])
def test_tool_missing_input_exits_2(tool, argv_tail, tmp_path,
                                    monkeypatch, capsys):
    """ISSUE 19 satellite: every ledger/trace-consuming tool fails a
    missing input with exit code 2 and a diagnostic (never a traceback,
    never a zero)."""
    mod = _load_tool(tool)
    argv = [f"{tool}.py"] + [str(tmp_path / a) for a in argv_tail]
    monkeypatch.setattr(sys, "argv", argv)
    sig = inspect.signature(mod.main)
    required = [p for p in sig.parameters.values()
                if p.default is inspect.Parameter.empty
                and p.kind in (p.POSITIONAL_ONLY, p.POSITIONAL_OR_KEYWORD)]
    try:
        rc = mod.main(argv) if required else mod.main()
    except SystemExit as e:
        rc = e.code
    assert rc == 2
    assert capsys.readouterr().err.strip(), f"{tool} failed silently"
