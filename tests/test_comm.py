"""Distributed observability (ISSUE 5, videop2p_tpu/obs/comm.py): collective
accounting, the per-device divergence probe, and the comm regression gates
— exercised on the virtual 8-device CPU mesh conftest.py sets up.

Fast tests cover the pure host-side pieces (HLO text mining, rule
semantics, tool rendering/exit codes, backward compat with pre-comm
ledgers); the mesh-compiling tests are marked slow like the rest of
tests/test_parallel.py.
"""

import importlib.util
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import NamedSharding, PartitionSpec as P

from videop2p_tpu.obs.comm import (
    COMM_ANALYSIS_FIELDS,
    DEVICE_TELEMETRY_FIELDS,
    collective_summary,
    comm_analysis_record,
    make_device_probe,
    replica_divergence,
    split_device_stats,
    summarize_device_stats,
    tree_replica_divergence,
)
from videop2p_tpu.obs.history import (
    COMM_RULES,
    evaluate_rules,
    extract_run,
    split_runs,
)
from videop2p_tpu.obs.ledger import RunLedger, read_ledger
from videop2p_tpu.parallel import make_mesh

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _load_tool(name):
    spec = importlib.util.spec_from_file_location(
        f"{name}_under_comm_test", os.path.join(_REPO, "tools", f"{name}.py")
    )
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


# ----------------------------------------------------- collective mining --


_SYNTHETIC_HLO = """\
HloModule jit_fn, is_scheduled=true, entry_computation_layout={(f32[8,16]{1,0})->f32[8,16]{1,0}}, num_partitions=4

ENTRY main {
  %p0 = f32[8,16]{1,0} parameter(0)
  %ar = f32[8,16]{1,0} all-reduce(%p0), replica_groups={}, to_apply=%add
  %cps = (f32[2,16]{1,0}, f32[2,16]{1,0}) collective-permute-start(%p0), source_target_pairs={{0,1},{1,0}}
  %cpd = f32[2,16]{1,0} collective-permute-done(%cps)
  %ag = f32[32,16]{1,0} all-gather(%p0), dimensions={0}
  ROOT %out = f32[8,16]{1,0} add(%ar, %ar)
}
"""


def test_collective_summary_counts_and_bytes():
    """Synthetic optimized-HLO text: per-kind counts and result-shape
    bytes, with the -done half of an async pair skipped so start/done
    counts once (at the start's tuple result)."""
    rec = collective_summary(_SYNTHETIC_HLO)
    assert set(rec["per_kind"]) == {
        "all-reduce", "collective-permute", "all-gather"
    }
    assert rec["per_kind"]["all-reduce"] == {"count": 1, "bytes": 8 * 16 * 4}
    # the start's TUPLE result sums both components; done contributes 0
    assert rec["per_kind"]["collective-permute"] == {
        "count": 1, "bytes": 2 * (2 * 16 * 4)
    }
    assert rec["per_kind"]["all-gather"] == {"count": 1, "bytes": 32 * 16 * 4}
    assert rec["collective_count"] == 3
    assert rec["collective_bytes"] == sum(
        s["bytes"] for s in rec["per_kind"].values()
    )
    # a module with no collectives reports clean zeros, not absence
    empty = collective_summary("ENTRY main { ROOT %x = f32[4] parameter(0) }")
    assert empty == {"collective_count": 0, "collective_bytes": 0,
                     "per_kind": {}}


# --------------------------------------------------------- rule semantics --


def _comm_run(run_id, *, bytes_=1000, count=10, divergence=0.0, peak=None):
    rec = {
        "run_id": run_id, "programs": {}, "compiles": {}, "phases": {},
        "dispatch": {}, "quality": {},
        "comm": {"edit": {"collective_bytes": bytes_,
                          "collective_count": count, "num_partitions": 8}},
        "device_memory": ({"device0": peak} if peak is not None else {}),
        "divergence": {"edit": divergence},
    }
    return rec


def test_comm_rules_gate_bytes_count_and_divergence():
    base = _comm_run("a")
    # identical runs: clean pass (divergence 0.0 passes with zero floor)
    assert evaluate_rules(base, base, COMM_RULES)["pass"]
    # +20% collective bytes trips the 15% rule; count within its 25%
    grown = _comm_run("b", bytes_=1200, count=11)
    res = evaluate_rules(base, grown, COMM_RULES)
    regs = {(v["rule"], v["program"]) for v in res["regressions"]}
    assert regs == {("comm:collective_bytes+15%", "edit")}
    # nonzero divergence fails even on SELF-compare — no baseline excuses it
    bad = _comm_run("c", divergence=1e-6)
    res = evaluate_rules(bad, bad, COMM_RULES)
    assert not res["pass"]
    [v] = res["regressions"]
    assert v["rule"] == "divergence:value!=0" and v["new"] == 1e-6
    # per-device peak HBM: +15% over the 10% threshold + 1MiB floor
    m_base = _comm_run("d", peak=100 * 2**20)
    m_new = _comm_run("e", peak=115 * 2**20)
    res = evaluate_rules(m_base, m_new, COMM_RULES)
    assert {v["rule"] for v in res["regressions"]} == {
        "device_memory:peak_bytes_in_use+10%"
    }


def test_extract_run_reads_comm_memory_divergence_events(tmp_path):
    path = str(tmp_path / "ledger.jsonl")
    with RunLedger(path, device_info=False) as led:
        led.comm_analysis("edit", {"collective_bytes": 512,
                                   "collective_count": 3,
                                   "num_partitions": 8,
                                   "per_kind": {"all-reduce": {"count": 3,
                                                               "bytes": 512}}})
        led.event("memory", supported=True, devices=[
            {"device": 0, "peak_bytes_in_use": 100},
            {"device": 1, "peak_bytes_in_use": 250},
            {"device": 1, "peak_bytes_in_use": 200},  # keep the worst
        ])
        led.divergence("train_params", 0.0)
        led.device_telemetry("edit", {"devices": 8, "divergence_max": 0.5,
                                      "divergence_final": 0.0})
    rec = extract_run(split_runs(read_ledger(path))[-1])
    assert rec["comm"]["edit"]["collective_bytes"] == 512
    # per_kind is a nested dict — only flat numerics are rule targets
    assert "per_kind" not in rec["comm"]["edit"]
    assert rec["device_memory"] == {"device0": 100.0, "device1": 250.0}
    # divergence keeps the WORST value per label across event kinds
    assert rec["divergence"] == {"train_params": 0.0, "edit": 0.5}
    res = evaluate_rules(rec, rec)
    assert not res["pass"]  # the diverged edit probe fails self-compare
    assert {v["program"] for v in res["regressions"]} == {"edit"}


def test_pre_comm_ledgers_stay_clean(tmp_path):
    """Backward compat: a pre-PR-5 ledger (no comm/memory/divergence
    events) extracts empty distributed sections and evaluates to a clean
    pass — the new rules never fire on absent data."""
    path = str(tmp_path / "old.jsonl")
    with RunLedger(path, device_info=False) as led:
        led.program_analysis("edit", {"flops": 100, "temp_bytes": 10,
                                      "hlo_fingerprint": "aa"})
        led.phase("edit_phase", 1.0)
    rec = extract_run(split_runs(read_ledger(path))[-1])
    assert rec["comm"] == {} and rec["device_memory"] == {}
    assert rec["divergence"] == {}
    assert evaluate_rules(rec, rec)["pass"]
    # extract_run of a record that predates the keys entirely (synthetic
    # old extracted dicts) — evaluate_rules tolerates missing sections
    legacy = {k: v for k, v in rec.items()
              if k not in ("comm", "device_memory", "divergence")}
    assert evaluate_rules(legacy, legacy)["pass"]


# --------------------------------------------------------------- decoders --


def test_summarize_and_split_device_stats():
    stats = {
        "device_abs_max": np.array([[1.0, 2.0], [3.0, 0.5]]),  # (steps, dev)
        "device_mean": np.array([[0.1, 0.2], [0.3, 0.4]]),
        "device_nan_count": np.array([[0, 1], [2, 0]]),
        "device_inf_count": np.array([[0, 0], [0, 0]]),
        "divergence": np.array([0.0, 0.25]),
        "abs_max": np.array([9.0, 9.0]),  # a plain telemetry channel
    }
    rest, dev = split_device_stats(stats)
    assert set(rest) == {"abs_max"}
    assert set(dev) == set(stats) - {"abs_max"}
    rec = summarize_device_stats(dev, device_ids=[0, 1])
    assert set(DEVICE_TELEMETRY_FIELDS) <= set(rec)
    assert rec["devices"] == 2
    assert rec["per_device_abs_max_peak"] == [3.0, 2.0]
    assert rec["per_device_nan_total"] == [2, 1]
    assert rec["nan_total"] == 3
    assert rec["divergence_max"] == 0.25 and rec["divergence_final"] == 0.25
    assert rec["device_ids"] == [0, 1]
    # degenerate input (killed run, empty stats): zeros, never a raise
    empty = summarize_device_stats({})
    assert empty["devices"] == 0 and empty["divergence_max"] == 0.0


# ------------------------------------------------------------ tool surface --


def _write_comm_ledger(path, run_id, *, bytes_=1000, divergence=0.0):
    with RunLedger(path, run_id=run_id, device_info=False) as led:
        led.program_analysis("edit", {"flops": 100, "temp_bytes": 10,
                                      "hlo_fingerprint": "aa"})
        led.comm_analysis("edit", {
            "collective_bytes": bytes_, "collective_count": 10,
            "num_partitions": 8,
            "per_kind": {"collective-permute": {"count": 10, "bytes": bytes_}},
        })
        led.event("memory", supported=True,
                  devices=[{"device": 0, "peak_bytes_in_use": 100 * 2**20}])
        led.divergence("edit_out", divergence)


def test_obs_diff_comm_acceptance(tmp_path, capsys):
    """The ISSUE acceptance gate: self-compare of a comm-bearing ledger
    exits 0; an injected +20% collective-bytes delta exits 1 with a
    machine-readable comm verdict; a diverged run fails even self-compare."""
    mod = _load_tool("obs_diff")
    a = str(tmp_path / "a.jsonl")
    b = str(tmp_path / "b.jsonl")
    _write_comm_ledger(a, "a")
    _write_comm_ledger(b, "b", bytes_=1200)
    assert mod.main(["obs_diff.py", a, a]) == 0
    capsys.readouterr()
    assert mod.main(["obs_diff.py", "--json", a, b]) == 1
    out = capsys.readouterr().out
    verdict = json.loads(out)
    assert not verdict["pass"]
    [reg] = verdict["regressions"]
    assert reg["rule"] == "comm:collective_bytes+15%"
    assert reg["kind"] == "comm" and reg["program"] == "edit"
    assert reg["base"] == 1000 and reg["new"] == 1200
    assert reg["delta_pct"] == 20.0
    # divergence: nonzero fails self-compare (direction="nonzero" survives
    # the tool's threshold-scaling rule rebuild)
    c = str(tmp_path / "c.jsonl")
    _write_comm_ledger(c, "c", divergence=0.125)
    assert mod.main(["obs_diff.py", "--threshold-scale", "10.0", c, c]) == 1
    text = capsys.readouterr().out
    assert "DIVERGED" in text


def test_ledger_summary_renders_comm_sections(tmp_path, capsys):
    mod = _load_tool("ledger_summary")
    path = str(tmp_path / "ledger.jsonl")
    _write_comm_ledger(path, "r")
    with RunLedger(path, run_id="r2", device_info=False) as led:
        led.device_telemetry("edit", {
            "devices": 8, "divergence_max": 0.0, "divergence_final": 0.0,
            "nan_total": 0, "per_device_abs_max_peak": [1.0] * 8,
        })
        led.event("host_phase", name="edit", seconds=2.0, process_index=0,
                  process_count=2)
        led.event("host_phase", name="edit", seconds=3.5, process_index=1,
                  process_count=2)
        led.event("program_analysis_skipped", program="vae", reason="disabled")
    assert mod.main(["ledger_summary.py", path]) == 0
    out = capsys.readouterr().out
    assert "collectives" in out and "collective-permute×10" in out
    assert "divergence max 0.0" in out
    assert "per-host phase skew" in out and "1.50" in out  # skew 3.5-2.0
    assert "program analysis skipped" in out and "vae: disabled" in out
    # a pre-comm ledger renders with none of the new sections
    old = str(tmp_path / "old.jsonl")
    with RunLedger(old, device_info=False) as led:
        led.phase("p", 1.0)
    assert mod.main(["ledger_summary.py", old]) == 0
    out = capsys.readouterr().out
    assert "collectives" not in out and "phase skew" not in out


def test_report_comm_section(tmp_path):
    from videop2p_tpu.obs.report import render_report

    events = [
        {"event": "run_start", "run_id": "r"},
        {"event": "comm_analysis", "program": "edit", "num_partitions": 8,
         "collective_count": 4, "collective_bytes": 2048,
         "per_kind": {"all-reduce": {"count": 4, "bytes": 2048}}},
        {"event": "device_telemetry", "program": "edit", "devices": 8,
         "divergence_max": 0.0, "nan_total": 0},
        {"event": "divergence", "label": "train_params", "value": 0.5},
        {"event": "host_phase", "name": "edit", "seconds": 1.0,
         "process_index": 0},
        {"event": "host_phase", "name": "edit", "seconds": 2.0,
         "process_index": 1},
    ]
    html_text = render_report(events, {})
    assert "Distributed / communication" in html_text
    assert "all-reduce×4" in html_text
    assert "DIVERGED" in html_text  # the nonzero train_params row
    assert "Per-host phase skew" in html_text
    # without the events the section is absent entirely
    assert "Distributed" not in render_report(
        [{"event": "run_start", "run_id": "r"}], {}
    )


def test_phase_skew_and_host_record():
    from videop2p_tpu.parallel import host_phase_record, phase_skew

    rec = host_phase_record("edit", 1.234567)
    assert rec["name"] == "edit" and rec["seconds"] == 1.2346
    assert rec["process_index"] == 0 and rec["process_count"] == 1
    assert isinstance(rec["hostname"], str)
    skew = phase_skew([
        {"event": "host_phase", "name": "edit", "seconds": 1.0,
         "process_index": 0},
        {"event": "host_phase", "name": "edit", "seconds": 1.5,
         "process_index": 0},  # same host: accumulates to 2.5
        {"event": "host_phase", "name": "edit", "seconds": 4.0,
         "process_index": 1},
        {"event": "phase", "name": "edit", "seconds": 99.0},  # ignored
        {"event": "host_phase", "seconds": 1.0},  # torn: no name
    ])
    assert skew == {"edit": {"hosts": 2, "min_s": 2.5, "max_s": 4.0,
                             "skew_s": 1.5, "slowest_process": 1}}


# ------------------------------------------------ mesh-compiling (slow) --


@pytest.fixture(scope="module")
def mesh8():
    return make_mesh((1, 8, 1))


@pytest.mark.slow
def test_comm_analysis_record_ring_program(mesh8):
    """The ring-attention ppermute chain becomes a measured quantity:
    nonzero collective-permute count/bytes, the partition count, and the
    schema-stable field set (COMM_ANALYSIS_FIELDS)."""
    from videop2p_tpu.parallel import ring_attention_sharded

    B, H, S, D = 1, 2, 16, 8
    spec = NamedSharding(mesh8, P(None, None, "frames", None))
    sds = jax.ShapeDtypeStruct((B, H, S, D), jnp.float32, sharding=spec)
    jitted = jax.jit(
        lambda q, k, v: ring_attention_sharded(q, k, v, mesh8)
    )
    rec = comm_analysis_record(jitted.lower(sds, sds, sds).compile())
    assert rec is not None
    assert set(COMM_ANALYSIS_FIELDS) <= set(rec)
    assert rec["num_partitions"] == 8
    assert rec["collective_permute_count"] > 0
    assert rec["collective_permute_bytes"] > 0
    assert rec["collective_bytes"] >= rec["collective_permute_bytes"]
    assert len(rec["hlo_fingerprint"]) == 16
    assert rec["arg_shardings"]  # the PartitionSpec renderings


@pytest.mark.slow
def test_instrumented_jit_sharded_emits_comm_analysis(tmp_path, mesh8):
    """Sharded calls are first-class obs citizens now: a cache miss on a
    sharded program emits BOTH program_analysis (the signature asked for
    keeps the shardings, so it is the partitioned program) and comm_analysis
    — where the pre-PR-5 code silently skipped."""
    from videop2p_tpu.obs import instrumented_jit
    from videop2p_tpu.parallel import ring_attention_sharded

    B, H, S, D = 1, 2, 16, 8
    q = jax.device_put(
        jax.random.normal(jax.random.key(0), (B, H, S, D)),
        NamedSharding(mesh8, P(None, None, "frames", None)),
    )
    f = instrumented_jit(
        lambda q, k, v: ring_attention_sharded(q, k, v, mesh8),
        program="ring_probe",
    )
    path = str(tmp_path / "ledger.jsonl")
    with RunLedger(path, device_info=False):
        f(q, q, q)
        f(q, q, q)  # cache hit: no second analysis
    events = read_ledger(path)
    pa = [e for e in events if e["event"] == "program_analysis"]
    ca = [e for e in events if e["event"] == "comm_analysis"]
    skipped = [e for e in events if e["event"] == "program_analysis_skipped"]
    assert len(pa) == 1 and pa[0]["program"] == "ring_probe"
    assert len(ca) == 1 and ca[0]["program"] == "ring_probe"
    assert ca[0]["num_partitions"] == 8
    assert ca[0]["collective_permute_bytes"] > 0
    assert not skipped


def test_a_sharded_miss_reads_the_partitioned_program_it_ran(tmp_path, mesh8):
    """Tier-1's guard on the sharded path of the analysis: the call's
    signature carries its shardings, so the analysis is handed the
    partitioned program the call built (``rebuilt`` false: one lowering,
    one backend compile in all) and ``comm_analysis`` is still emitted —
    eight partitions, the all-reduce of the sum."""
    from videop2p_tpu.obs import instrumented_jit

    x = jax.device_put(jnp.arange(64.0).reshape(2, 8, 4),
                       NamedSharding(mesh8, P(None, "frames", None)))
    f = instrumented_jit(lambda x, scale: jnp.sum(jnp.tanh(x)) * scale,
                         program="sharded_sum")
    path = str(tmp_path / "ledger.jsonl")
    with RunLedger(path, device_info=False):
        f(x, jnp.asarray(2.0))
    events = read_ledger(path)
    spans = [e for e in events if e["event"] == "span"]

    def kids(parent):
        return [s for s in spans if s["parent_id"] == parent["span_id"]]

    call, = [s for s in spans if s["name"] == "program.call"]
    analysis, = [s for s in kids(call) if s["name"] == "program.analysis"]
    assert analysis["rebuilt"] is False
    assert not {"program.lower", "program.backend_compile"} & {
        s["name"] for s in kids(analysis)}
    assert [s["name"] for s in kids(call)].count("program.lower") == 1
    assert [s["name"] for s in kids(call)].count("program.backend_compile") == 1
    pa, = [e for e in events if e["event"] == "program_analysis"]
    ca, = [e for e in events if e["event"] == "comm_analysis"]
    assert ca["program"] == pa["program"] == "sharded_sum"
    assert ca["num_partitions"] == 8 and ca["all_reduce_count"] >= 1
    assert ca["hlo_fingerprint"] == pa["hlo_fingerprint"]
    assert not [e for e in events if e["event"] == "program_analysis_skipped"]


@pytest.mark.slow
def test_replica_divergence_detects_injected_perturbation(mesh8):
    mesh = make_mesh((2, 4, 1))
    x = jnp.zeros((8,))
    # truly replicated over the data axis: divergence exactly 0.0
    div0 = replica_divergence(
        jax.device_put(x, NamedSharding(mesh, P("frames"))),
        mesh, axes=("data",), spec=P("frames"),
    )
    assert float(div0) == 0.0
    # inject a per-data-replica offset UNDER shard_map (out_specs claims
    # replication over data, the values say otherwise — exactly the bug
    # class the probe exists to catch)
    perturbed = jax.shard_map(
        lambda v: v + jax.lax.axis_index("data").astype(jnp.float32) * 0.25,
        mesh=mesh, in_specs=(P("frames"),), out_specs=P("frames"),
        check_vma=False,
    )(x)
    div = replica_divergence(perturbed, mesh, axes=("data",), spec=P("frames"))
    assert float(div) == 0.25
    # no axes to check: constant 0.0 (single-replica meshes)
    assert float(replica_divergence(x, mesh, axes=())) == 0.0
    # tree form takes the worst leaf
    tree = {"a": x, "b": perturbed}
    tdiv = tree_replica_divergence(tree, mesh, axes=("data",))
    assert float(tdiv) == 0.25


@pytest.mark.slow
def test_edit_sample_device_probe_bit_exact_and_cached_replay(mesh8):
    """The probe rides the fused edit scan with the telemetry contract:
    probe-on latents are BIT-EXACT vs probe-off (sharded), divergence is
    0.0 for the replicated working point, and the cached-source replay
    keeps src_err == 0.0 with the probe active."""
    from videop2p_tpu.core import DDIMScheduler
    from videop2p_tpu.models import UNet3DConditionModel, UNet3DConfig
    from videop2p_tpu.parallel import latent_sharding, param_shardings, replicated
    from videop2p_tpu.pipelines import make_unet_fn
    from videop2p_tpu.pipelines.fast import cached_fast_edit

    mesh = make_mesh((1, 4, 2))
    cfg = UNet3DConfig.tiny()
    model = UNet3DConditionModel(config=cfg)
    F, STEPS = 4, 2
    x0 = jax.random.normal(jax.random.key(0), (1, F, 8, 8, 4))
    cond = jax.random.normal(jax.random.key(1), (2, 77, cfg.cross_attention_dim))
    uncond = jnp.zeros((77, cfg.cross_attention_dim))
    params = jax.jit(model.init)(jax.random.key(2), x0, jnp.asarray(5), cond[:1])
    fn = make_unet_fn(model)
    sched = DDIMScheduler.create_sd()
    probe = make_device_probe(mesh)
    assert probe.divergence_axes == ("tensor",)

    s_params = jax.device_put(
        params, param_shardings(mesh, params, tensor_parallel=True)
    )
    s_x0 = jax.device_put(x0, latent_sharding(mesh))
    s_cond = jax.device_put(cond, replicated(mesh))
    s_uncond = jax.device_put(uncond, replicated(mesh))

    def run(p, x, dp):
        return cached_fast_edit(
            fn, p, sched, x, cond[:1], s_cond, s_uncond, None,
            num_inference_steps=STEPS, device_probe=dp,
        )

    traj_off, out_off = jax.jit(lambda p, x: run(p, x, None))(s_params, s_x0)
    traj_on, out_on, dev = jax.jit(lambda p, x: run(p, x, probe))(
        s_params, s_x0
    )
    np.testing.assert_array_equal(np.asarray(out_off), np.asarray(out_on))
    np.testing.assert_array_equal(np.asarray(traj_off), np.asarray(traj_on))
    # the cached replay's exactness pedestal survives the probe
    src_err = float(jnp.max(jnp.abs(out_on[0] - s_x0[0])))
    assert src_err == 0.0
    host_dev = jax.device_get(dev)
    assert host_dev["device_abs_max"].shape == (STEPS, mesh.size)
    assert float(np.max(host_dev["divergence"])) == 0.0
    rec = summarize_device_stats(host_dev, probe.device_ids)
    assert rec["devices"] == mesh.size
    assert rec["divergence_max"] == 0.0 and rec["nan_total"] == 0


@pytest.mark.slow
def test_ring_variant_collective_counts_pinned(mesh8):
    """ISSUE 10 satellite 1: the unrolled rotation loop makes the static
    collective-permute counts TRUE per-pass counts, and the engineered
    schedules are pinned against the serial baseline — overlap issues
    exactly n−1 rotations (the dead final pair is gone), bidir the same
    total bytes at HALF the per-permute payload on both ICI directions."""
    from videop2p_tpu.parallel import ring_attention_sharded

    n = 8
    B, H, S, D = 1, 2, 64, 8
    spec = NamedSharding(mesh8, P(None, None, "frames", None))
    sds = jax.ShapeDtypeStruct((B, H, S, D), jnp.float32, sharding=spec)
    recs = {}
    for variant in ("serial", "overlap", "bidir"):
        jitted = jax.jit(
            lambda q, k, v, _v=variant: ring_attention_sharded(
                q, k, v, mesh8, variant=_v
            )
        )
        recs[variant] = comm_analysis_record(
            jitted.lower(sds, sds, sds).compile()
        )
    serial, overlap, bidir = (recs[v] for v in ("serial", "overlap", "bidir"))
    blk = (B * H * (S // n) * D) * 4  # one K or V block per shard, f32
    assert serial["collective_permute_count"] == 2 * n
    assert serial["collective_permute_bytes"] == 2 * n * blk
    assert overlap["collective_permute_count"] == 2 * (n - 1)
    assert overlap["collective_permute_bytes"] == 2 * (n - 1) * blk
    assert bidir["collective_permute_count"] == 4 * (n - 1)
    assert bidir["collective_permute_bytes"] == overlap["collective_permute_bytes"]
    # per-permute payload halves: both directions carry half blocks
    assert (bidir["collective_permute_bytes"] // bidir["collective_permute_count"]
            == blk // 2)


@pytest.mark.slow
def test_ring_ab_obs_diff_exit_codes(mesh8, tmp_path):
    """The ring before/after comm evidence is obs_diff-gateable: the
    serial→overlap direction passes (counts and bytes DROP), and an
    injected +20% collective-bytes bump on the same label exits 1 with a
    machine-readable comm verdict."""
    from videop2p_tpu.parallel import ring_attention_sharded

    spec = NamedSharding(mesh8, P(None, None, "frames", None))
    sds = jax.ShapeDtypeStruct((1, 2, 64, 8), jnp.float32, sharding=spec)
    recs = {}
    for variant in ("serial", "overlap"):
        jitted = jax.jit(
            lambda q, k, v, _v=variant: ring_attention_sharded(
                q, k, v, mesh8, variant=_v
            )
        )
        recs[variant] = comm_analysis_record(
            jitted.lower(sds, sds, sds).compile()
        )

    def write(path, rec):
        led = RunLedger(str(path), device_info=False)
        led.comm_analysis("ring_attention", rec)
        led.close()

    before, after = tmp_path / "before.jsonl", tmp_path / "after.jsonl"
    write(before, recs["serial"])
    write(after, recs["overlap"])
    obs_diff = _load_tool("obs_diff")
    assert obs_diff.main(["obs_diff.py", str(before), str(after)]) == 0
    bumped = tmp_path / "bumped.jsonl"
    write(bumped, dict(recs["serial"],
                       collective_bytes=int(recs["serial"]["collective_bytes"] * 1.2)))
    assert obs_diff.main(["obs_diff.py", str(before), str(bumped)]) == 1


@pytest.mark.slow
def test_tp_pairing_unit_halves_reduction_bytes(mesh8):
    """The Megatron row-parallel output unit: the explicit psum_scatter
    seam's reduce-scatter result bytes are the declarative all-reduce's ÷
    tp, at (near-)identical flops — the per-attention-block byte
    reduction of the pairing, measured."""
    import importlib.util as _ilu

    spec = _ilu.spec_from_file_location(
        "graft_under_comm_test", os.path.join(_REPO, "__graft_entry__.py")
    )
    graft = _ilu.module_from_spec(spec)
    spec.loader.exec_module(graft)

    mesh_tp = make_mesh((1, 1, 8))
    recs = graft._tp_unit_records(mesh_tp)
    g, s = recs["gspmd"], recs["scatter"]
    assert g["all_reduce_count"] == 1 and g["all_reduce_bytes"] > 0
    assert s["reduce_scatter_count"] == 1
    assert s["reduce_scatter_bytes"] == g["all_reduce_bytes"] // 8
    assert s["collective_bytes"] < g["collective_bytes"]
    assert g["hlo_fingerprint"] != s["hlo_fingerprint"]
