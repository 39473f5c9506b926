"""Observability subsystem (videop2p_tpu/obs): in-program telemetry + the
unified run ledger (ISSUE 2).

CPU gates for the tentpole's contracts:

  * telemetry buffers are fixed-shape and shape-stable under jit — NaNs in
    the data change values, never shapes;
  * telemetry OFF leaves the fused programs' outputs bit-exact (null-text
    fused, the controlled edit, the cached replay — whose source stream
    must stay exactly the inversion input);
  * the ledger JSONL schema round-trips, compile events are captured on
    CPU with program attribution, phase_timer emits into the active
    ledger, and tools/ledger_summary.py renders a real event stream;
  * the telemetry-on overhead of the fused null-text program is measured
    on a compute-dominated smoke workload and recorded in a ledger.

Fake denoisers keep everything eager-CPU-fast (the SURVEY §4 strategy).
"""

import importlib.util
import json
import os
import threading

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from videop2p_tpu.core import DDIMScheduler
from videop2p_tpu.obs import (
    RunLedger,
    current_ledger,
    decode_null_text_stats,
    decode_step_stats,
    instrumented_jit,
    latent_stats,
    read_ledger,
    sparkline,
    summarize_step_stats,
    telemetry_overhead_record,
)
from videop2p_tpu.obs.timing import measure_overhead_p50
from videop2p_tpu.pipelines import (
    ddim_inversion,
    edit_sample,
    null_text_optimization,
    null_text_optimization_fused,
)

STEPS = 6
SHAPE = (1, 2, 8, 8, 4)

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(scope="module")
def sched():
    return DDIMScheduler.create_sd()


def text_unet():
    def fn(params, sample, t, text, control=None):
        bias = jnp.mean(text, axis=(1, 2))
        return 0.1 * sample + bias[:, None, None, None, None], {}

    return fn


@pytest.fixture(scope="module")
def problem(sched):
    fn = text_unet()
    x0 = jax.random.normal(jax.random.key(0), SHAPE)
    cond = 0.3 * jnp.ones((1, 77, 8))
    uncond = jnp.zeros((1, 77, 8))
    traj = ddim_inversion(fn, None, sched, x0, cond, num_inference_steps=STEPS)
    return fn, x0, cond, uncond, traj


# ------------------------------------------------------------- telemetry --


def test_latent_stats_shape_stable_under_jit():
    """The probe returns SCALARS whatever the data holds — a scan stacking
    it yields (num_steps,) vectors, and NaN inputs change values only."""

    def scan_stats(x):
        def body(c, _):
            return c * 2.0, latent_stats(c)

        _, ys = jax.lax.scan(body, x, None, length=5)
        return ys

    clean = jax.jit(scan_stats)(jnp.ones((2, 3, 4)))
    dirty = jax.jit(scan_stats)(
        jnp.array([[1.0, jnp.nan], [jnp.inf, -2.0]])
    )
    for ys in (clean, dirty):
        assert set(ys) == {"abs_max", "mean", "nan_count", "inf_count"}
        for k, v in ys.items():
            assert v.shape == (5,), k
    assert int(dirty["nan_count"][0]) == 1
    assert int(dirty["inf_count"][0]) == 1
    # finite-masked stats: the NaN/inf never poison the curve
    assert float(dirty["abs_max"][0]) == 2.0
    assert np.isfinite(np.asarray(dirty["mean"])).all()
    assert int(clean["nan_count"].sum()) == 0


def test_null_text_fused_telemetry_off_is_bit_exact(problem, sched):
    fn, _, cond, uncond, traj = problem
    kw = dict(num_inference_steps=STEPS, num_inner_steps=3, return_stats=True)
    seq_off, stats_off = null_text_optimization_fused(
        fn, None, sched, traj, cond, uncond, **kw
    )
    seq_on, stats_on = null_text_optimization_fused(
        fn, None, sched, traj, cond, uncond, telemetry=True, **kw
    )
    assert np.array_equal(np.asarray(seq_off), np.asarray(seq_on))
    assert np.array_equal(np.asarray(stats_off["final_loss"]),
                          np.asarray(stats_on["final_loss"]))
    tel = stats_on["latent_stats"]
    assert {k: np.asarray(v).shape for k, v in tel.items()} == {
        "abs_max": (STEPS,), "mean": (STEPS,),
        "nan_count": (STEPS,), "inf_count": (STEPS,),
    }
    assert int(np.asarray(tel["nan_count"]).sum()) == 0
    # the decoded record is ledger-ready: loss curve + inner steps + latent
    rec = decode_null_text_stats(stats_on)
    assert len(rec["loss_curve"]) == STEPS
    assert rec["inner_steps_total"] == sum(rec["inner_steps"])
    assert rec["latent"]["nan_total"] == 0


def test_null_text_telemetry_requires_stats(problem, sched):
    fn, _, cond, uncond, traj = problem
    with pytest.raises(ValueError, match="return_stats"):
        null_text_optimization_fused(
            fn, None, sched, traj, cond, uncond,
            num_inference_steps=STEPS, telemetry=True,
        )


def test_null_text_chunked_telemetry_matches_fused(problem, sched):
    """The host-chunked watchdog fallback stacks the same telemetry as the
    fused program (chunk boundaries concatenate, values identical)."""
    fn, _, cond, uncond, traj = problem
    kw = dict(num_inference_steps=STEPS, num_inner_steps=2)
    _, stats = null_text_optimization_fused(
        fn, None, sched, traj, cond, uncond,
        return_stats=True, telemetry=True, **kw,
    )
    seq_c, tel_c = null_text_optimization(
        fn, None, sched, traj, cond, uncond,
        outer_chunk=2, telemetry=True, **kw,
    )
    assert seq_c.shape[0] == STEPS
    for k, v in stats["latent_stats"].items():
        np.testing.assert_allclose(
            np.asarray(tel_c[k]), np.asarray(v), rtol=0, atol=0, err_msg=k
        )


def test_edit_sample_telemetry_off_is_bit_exact(problem, sched):
    fn, _, cond, uncond, traj = problem
    cond2 = jnp.concatenate([cond, 0.5 * jnp.ones((1, 77, 8))], axis=0)

    out_off = jax.jit(
        lambda xt: edit_sample(fn, None, sched, xt, cond2, uncond[0],
                               num_inference_steps=STEPS)
    )(traj[-1])
    out_on, tel = jax.jit(
        lambda xt: edit_sample(fn, None, sched, xt, cond2, uncond[0],
                               num_inference_steps=STEPS, telemetry=True)
    )(traj[-1])
    assert np.array_equal(np.asarray(out_off), np.asarray(out_on))
    assert set(tel) == {"abs_max", "mean", "nan_count", "inf_count",
                        "cross_gate_mean", "self_edit_active"}
    for v in tel.values():
        assert np.asarray(v).shape == (STEPS,)
    # no controller: the edit-gate channels are identically zero
    assert float(np.asarray(tel["cross_gate_mean"]).sum()) == 0.0
    assert int(np.asarray(tel["self_edit_active"]).sum()) == 0
    summary = summarize_step_stats(tel)
    assert summary["steps"] == STEPS and summary["nan_total"] == 0
    assert len(decode_step_stats(tel)) == STEPS


def test_cached_edit_telemetry_keeps_exact_replay(problem, sched):
    """Telemetry through the cached-source path: outputs bit-exact vs
    telemetry-off, and stream 0 stays the EXACT inversion input — the
    src_err == 0.0 guarantee the multichip dryrun reports."""
    from videop2p_tpu.pipelines import cached_fast_edit

    fn, x0, cond, uncond, _ = problem
    cond2 = jnp.concatenate([cond, 0.5 * jnp.ones((1, 77, 8))], axis=0)
    kw = dict(num_inference_steps=STEPS, cross_len=0, self_window=(0, 0))
    traj_off, edited_off = jax.jit(
        lambda x: cached_fast_edit(fn, None, sched, x, cond, cond2,
                                   uncond[0], None, **kw)
    )(x0)
    traj_on, edited_on, tel = jax.jit(
        lambda x: cached_fast_edit(fn, None, sched, x, cond, cond2,
                                   uncond[0], None, telemetry=True, **kw)
    )(x0)
    assert np.array_equal(np.asarray(edited_off), np.asarray(edited_on))
    assert np.array_equal(np.asarray(traj_off), np.asarray(traj_on))
    src_err = float(jnp.max(jnp.abs(edited_on[0] - x0[0])))
    assert src_err == 0.0
    assert np.asarray(tel["abs_max"]).shape == (STEPS,)
    assert int(np.asarray(tel["nan_count"]).sum()) == 0


@pytest.mark.slow
def test_train_steps_telemetry_grad_norms():
    """Training telemetry: same losses bit-exact, plus finite per-step
    pre-clip global gradient norms stacked by the same scan.

    slow: the only remaining >10 s test in the r6 wall-clock audit (11.4 s
    — it compiles the train scan twice, telemetry off and on); tier-1
    keeps the telemetry bit-exactness pins via the other train test
    (test_train.py) and the fused-pipeline off-paths above."""
    from videop2p_tpu.core import DDPMScheduler
    from videop2p_tpu.models import UNet3DConditionModel, UNet3DConfig
    from videop2p_tpu.pipelines import make_unet_fn
    from videop2p_tpu.train import (
        TrainState, TuneConfig, make_optimizer, train_steps,
    )

    cfg = UNet3DConfig.tiny()
    model = UNet3DConditionModel(config=cfg)
    latents = 0.3 * jax.random.normal(jax.random.key(0), (1, 2, 8, 8, 4))
    text = jax.random.normal(jax.random.key(1), (1, 7, cfg.cross_attention_dim))
    variables = jax.jit(model.init)(jax.random.key(2), latents, jnp.asarray(0), text)
    fn = make_unet_fn(model)
    tune_cfg = TuneConfig(max_train_steps=3)
    tx = make_optimizer(tune_cfg)
    noise_sched = DDPMScheduler.create_sd()
    key = jax.random.key(3)

    state0 = TrainState.create(dict(variables)["params"], tx)
    _, losses = train_steps(fn, tx, state0, noise_sched, latents, text, key,
                            num_steps=3)
    state1 = TrainState.create(dict(variables)["params"], tx)
    _, losses_t, gnorms = train_steps(fn, tx, state1, noise_sched, latents,
                                      text, key, num_steps=3, telemetry=True)
    np.testing.assert_array_equal(np.asarray(losses), np.asarray(losses_t))
    g = np.asarray(gnorms)
    assert g.shape == (3,) and np.isfinite(g).all() and (g > 0).all()


# ---------------------------------------------------------------- ledger --


def test_ledger_schema_round_trips(tmp_path):
    path = str(tmp_path / "ledger.jsonl")
    with RunLedger(path, run_id="t1", meta={"cli": "test"}) as led:
        assert current_ledger() is led
        led.phase("p", 1.25, count=3, unit="it")
        led.telemetry("prog", {"loss_curve": [1.0, 0.5], "loss_final": 0.5})
        led.memory_snapshot(note="now")
        led.event("custom", answer=42)
    assert current_ledger() is None
    events = read_ledger(path)
    by_kind = {e["event"]: e for e in events}
    start = by_kind["run_start"]
    assert start["run_id"] == "t1" and start["cli"] == "test"
    assert start["jax_version"] == jax.__version__
    assert "backend" in start
    assert by_kind["phase"]["name"] == "p"
    assert by_kind["phase"]["seconds"] == 1.25
    assert by_kind["telemetry"]["program"] == "prog"
    assert by_kind["memory"]["supported"] in (True, False)
    assert by_kind["custom"]["answer"] == 42
    assert events[-1]["event"] == "run_end"
    # every event is one JSON object per line with a monotonic t
    raw = [json.loads(l) for l in open(path) if l.strip()]
    assert [e["event"] for e in raw] == [e["event"] for e in events]
    ts = [e["t"] for e in events]
    assert ts == sorted(ts)


def test_compile_events_captured_on_cpu(tmp_path):
    """The jax.monitoring listener lands backend-compile durations in the
    active ledger, attributed to the instrumented program; a cache hit
    records a program_call with cache_miss=False and no new compile."""
    path = str(tmp_path / "ledger.jsonl")
    with RunLedger(path) as led:
        f = instrumented_jit(lambda x: x * 3 + 1, program="triple")
        f(jnp.ones((4, 4)))
        n_compiles_after_first = len(led.compile_seconds)
        f(jnp.ones((4, 4)))
    events = read_ledger(path)
    compiles = [e for e in events if e["event"] == "compile"
                and e.get("program") == "triple"]
    assert len(compiles) >= 1
    assert all(e["seconds"] > 0 for e in compiles)
    calls = [e for e in events if e["event"] == "program_call"]
    assert [c["cache_miss"] for c in calls] == [True, False]
    # the second (hit) call triggered no further compile
    assert len(led.compile_seconds) == n_compiles_after_first


def test_phase_timer_emits_into_active_ledger(tmp_path, capsys):
    from videop2p_tpu.utils.profiling import phase_records, phase_timer, reset

    reset()
    path = str(tmp_path / "ledger.jsonl")
    with RunLedger(path):
        with phase_timer("ledgered_phase", count=2, unit="u"):
            pass
    with phase_timer("unledgered_phase", verbose=False):
        pass
    events = [e for e in read_ledger(path) if e["event"] == "phase"]
    assert [e["name"] for e in events] == ["ledgered_phase"]
    assert events[0]["count"] == 2 and events[0]["unit"] == "u"
    # the process-local records caught both, and reset clears them
    recs = phase_records()
    assert set(recs) == {"ledgered_phase", "unledgered_phase"}
    reset()
    assert phase_records() == {}


def test_phase_timer_records_from_worker_threads():
    """phase_timer regions can close on worker threads: the process-local
    records are guarded by a lock and catch every one."""
    from videop2p_tpu.utils.profiling import phase_records, phase_timer, reset

    reset()

    def work(i):
        for _ in range(50):
            with phase_timer(f"thread_{i}", verbose=False):
                pass

    threads = [threading.Thread(target=work, args=(i,)) for i in range(4)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    recs = phase_records()
    assert set(recs) == {f"thread_{i}" for i in range(4)}
    reset()


def test_metrics_logger_flushes_and_survives_abrupt_close(tmp_path):
    """Satellite: scalars must survive an abrupt close — the JSONL line
    buffer holds every step immediately, and the TensorBoard event file's
    records are on disk every ``flush_every`` logs, before any close."""
    import struct

    from videop2p_tpu.utils.metrics import MetricsLogger

    def records_on_disk():
        with open(logger._tb.path, "rb") as f:
            data = f.read()
        n = at = 0
        while at < len(data):
            at += 16 + struct.unpack("<Q", data[at:at + 8])[0]
            n += 1
        assert at == len(data)  # whole records only
        return n

    logger = MetricsLogger(str(tmp_path), flush_every=2)
    assert records_on_disk() == 1  # the version record, at once
    for step in range(1, 6):
        logger.log(step, {"train_loss": 1.0 / step})
    # JSONL survives WITHOUT close: line-buffered append
    lines = [json.loads(l) for l in open(logger.path)]
    assert [l["step"] for l in lines] == [1, 2, 3, 4, 5]
    assert all("wall_s" in l for l in lines)
    assert records_on_disk() == 1 + 4  # logs 1-4 flushed, log 5 buffered
    logger.close()
    assert records_on_disk() == 1 + 5 and logger._tb.records == 6


def test_metrics_logger_is_a_ledger_view(tmp_path):
    from videop2p_tpu.utils.metrics import MetricsLogger

    path = str(tmp_path / "ledger.jsonl")
    with RunLedger(path):
        with MetricsLogger(str(tmp_path / "run"), use_tensorboard=False) as m:
            m.log(1, {"train_loss": 0.5, "lr": 1e-4})
    metric = [e for e in read_ledger(path) if e["event"] == "metric"]
    assert len(metric) == 1
    assert metric[0]["step"] == 1 and metric[0]["train_loss"] == 0.5


def test_instrumented_jit_passthrough_without_ledger():
    f = instrumented_jit(lambda x: x + 1, program="noop")
    assert current_ledger() is None
    assert float(f(jnp.asarray(1.0))) == 2.0


# -------------------------------------------------------- ledger summary --


def _load_tool(name):
    spec = importlib.util.spec_from_file_location(
        f"{name}_under_test",
        os.path.join(_REPO, "tools", f"{name}.py"),
    )
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _load_summary_tool():
    return _load_tool("ledger_summary")


def test_ledger_summary_renders_real_stream(tmp_path, problem, sched):
    """End-to-end: a ledger produced by real instrumented programs renders
    without error and shows phases, programs, and the loss sparkline."""
    from videop2p_tpu.utils.profiling import phase_timer

    fn, _, cond, uncond, traj = problem
    path = str(tmp_path / "ledger.jsonl")
    with RunLedger(path, run_id="render") as led:
        with phase_timer("null_text", verbose=False):
            _, stats = null_text_optimization_fused(
                fn, None, sched, traj, cond, uncond,
                num_inference_steps=STEPS, num_inner_steps=2,
                return_stats=True, telemetry=True,
            )
        led.telemetry("null_text_fused", decode_null_text_stats(stats))
        led.memory_snapshot()
    mod = _load_summary_tool()
    text = mod.render(read_ledger(path))
    assert "run render" in text
    assert "null_text" in text
    assert "loss" in text and "inner steps" in text
    # sparkline characters (or the flat-series bar) present
    assert any(c in text for c in "▁▂▃▄▅▆▇█")


def test_sparkline_handles_degenerate_series():
    assert sparkline([]) == ""
    assert sparkline([1.0, 1.0, 1.0]) == "▄▄▄"
    assert "!" in sparkline([1.0, float("nan"), 2.0])
    assert len(sparkline(list(range(500)), width=50)) == 50
    # inf values render as '!' too; an all-non-finite series is all '!'
    assert sparkline([1.0, float("inf"), 2.0])[1] == "!"
    assert sparkline([float("nan"), float("inf")]) == "!!"


def test_decode_helpers_degenerate_inputs():
    """Satellite (ISSUE 4): the decode helpers must survive empty stats
    trees, zero-length curves, and NaN/inf VALUES (not just counts) — a
    killed run's partial telemetry still has to land in the ledger."""
    assert decode_step_stats({}) == []
    assert summarize_step_stats({}) == {"steps": 0}
    empty = {"abs_max": np.zeros((0,)), "mean": np.zeros((0,))}
    assert decode_step_stats(empty) == []
    assert summarize_step_stats(empty) == {"steps": 0}

    weird = {
        "abs_max": np.array([1.0, np.nan, np.inf]),
        "mean": np.array([0.0, np.nan, 5.0]),
        "nan_count": np.array([0, 1, 0]),
        "inf_count": np.array([0, 0, 1]),
    }
    recs = decode_step_stats(weird)
    assert [r["step"] for r in recs] == [0, 1, 2]
    assert np.isnan(recs[1]["abs_max"]) and recs[2]["abs_max"] == np.inf
    s = summarize_step_stats(weird)
    assert s["steps"] == 3
    assert s["nan_total"] == 1 and s["first_nan_step"] == 1
    assert s["inf_total"] == 1 and s["first_inf_step"] == 2
    assert s["mean_final"] == 5.0


def test_ledger_summary_tolerates_empty_and_truncated(tmp_path, capsys):
    """Satellite: the renderer must survive empty ledgers and torn/partial
    JSONL lines (a killed run's tail) instead of crashing."""
    mod = _load_summary_tool()
    # empty file
    empty = tmp_path / "empty.jsonl"
    empty.write_text("")
    assert mod.main(["ledger_summary.py", str(empty)]) == 0
    assert "empty ledger" in capsys.readouterr().out
    # torn + partial lines: valid prefix renders, junk is skipped, events
    # missing payload fields degrade to placeholders
    torn = tmp_path / "torn.jsonl"
    torn.write_text("\n".join([
        json.dumps({"event": "run_start", "run_id": "torn", "t": 0}),
        json.dumps({"event": "phase"}),                      # no name/seconds
        json.dumps({"event": "compile", "seconds": None}),   # null seconds
        json.dumps({"event": "program_call", "program": "p",
                    "dispatch_s": "garbage"}),
        json.dumps({"event": "telemetry", "program": "p",
                    "loss_curve": [1.0, None]}),             # junk curve
        json.dumps({"event": "memory", "supported": True, "devices": None}),
        json.dumps({"event": "program_analysis", "program": "q"}),
        '{"event": "phase", "name": "tail", "secon',         # torn line
    ]) + "\n")
    assert mod.main(["ledger_summary.py", str(torn)]) == 0
    out = capsys.readouterr().out
    assert "run torn" in out
    # missing file: usage-style error code, no traceback
    assert mod.main(["ledger_summary.py", str(tmp_path / "nope.jsonl")]) == 2
    # wrong argc prints usage
    assert mod.main(["ledger_summary.py"]) == 2


def test_ledger_summary_renders_program_analysis_and_hbm_check(tmp_path):
    """The new program_analysis table + the predicted-vs-measured peak-HBM
    line (the run_videop2p HBM-gate sanity check)."""
    mod = _load_summary_tool()
    events = [
        {"event": "run_start", "run_id": "pa", "t": 0},
        {"event": "program_analysis", "program": "cached_invert_edit",
         "flops": 6.5e12, "bytes_accessed": 3 * 2**30,
         "temp_bytes": 2 * 2**30, "peak_hbm_bytes": 4 * 2**30,
         "hlo_instructions": 1234, "hlo_fingerprint": "deadbeefcafef00d"},
        {"event": "memory", "supported": True,
         "devices": [{"device": 0, "peak_bytes_in_use": 5 * 2**30}]},
    ]
    text = mod.render(events)
    assert "program analysis" in text
    assert "cached_invert_edit" in text and "deadbeefcafef00d" in text
    assert "predicted peak-HBM" in text
    assert "1.25× predicted" in text


# ------------------------------------------------- overhead (CPU smoke) --


def test_telemetry_overhead_recorded_and_small(tmp_path, sched):
    """The acceptance smoke: telemetry-on overhead of the fused null-text
    program on a COMPUTE-DOMINATED workload (a matmul-heavy denoiser over a
    small latent — the real UNet's FLOPs-per-latent-byte ratio is even more
    extreme), recorded in a ledger. The stats are four scalar reductions
    per outer step; once forwards dominate, their cost vanishes.

    The denoiser is sized so the fused program runs ~20 ms: the r6 audit
    caught the original ~1.3 ms version flaking in full-suite runs, where
    0.1 ms of host jitter reads as a fake double-digit 'overhead'.

    ISSUE 6 de-flake: the comparison rides obs/timing.py percentile
    reservoirs (measure_overhead_p50 — interleaved off/on sampling,
    nearest-rank p50s) instead of one median-of-5 wall-clock delta.

    ISSUE 11 de-flake: even the p50-of-9 (retry p50-of-13) flaked once
    in-suite in BOTH the r4 and r5 rounds — host scheduling jitter on a
    loaded CI box is not a property of this repo's code, so the overhead
    percentage is now RECORDED (ledger `telemetry` event, where cross-run
    obs_diff/TIMING_RULES gates drift against a baseline measured on the
    SAME box) rather than asserted against a fixed in-suite threshold.
    The hard assertions keep what host load cannot fake: the measurement
    ran, both timings are real, and the record schema holds."""
    W = 0.02 * jax.random.normal(jax.random.key(9), (1024, 1024))

    def heavy_fn(params, sample, t, text, control=None):
        h = sample.reshape(1, -1)
        h = jnp.pad(h, ((0, 0), (0, 1024 - h.shape[1])))
        for _ in range(24):
            h = jnp.tanh(h @ W)
        bias = jnp.mean(text, axis=(1, 2)) + jnp.mean(h)
        return 0.1 * sample + bias[:, None, None, None, None], {}

    x0 = jax.random.normal(jax.random.key(0), SHAPE)
    cond = 0.3 * jnp.ones((1, 77, 8))
    uncond = jnp.zeros((1, 77, 8))
    traj = ddim_inversion(heavy_fn, None, sched, x0, cond,
                          num_inference_steps=STEPS)
    kw = dict(num_inference_steps=STEPS, num_inner_steps=4,
              early_stop=False, return_stats=True)

    def run_off():
        jax.block_until_ready(null_text_optimization_fused(
            heavy_fn, None, sched, traj, cond, uncond, **kw)[0])

    def run_on():
        jax.block_until_ready(null_text_optimization_fused(
            heavy_fn, None, sched, traj, cond, uncond, telemetry=True, **kw)[0])

    rec = measure_overhead_p50(run_off, run_on, repeats=9)
    if rec["telemetry_overhead_pct"] > 5.0:  # one retry absorbs a CI blip
        rec = measure_overhead_p50(run_off, run_on, repeats=13)
    path = str(tmp_path / "ledger.jsonl")
    with RunLedger(path) as led:
        led.telemetry("null_text_fused_overhead", rec)
    saved = [e for e in read_ledger(path) if e["event"] == "telemetry"][0]
    assert saved["telemetry_overhead_pct"] == rec["telemetry_overhead_pct"]
    assert set(rec) == {"telemetry_off_s", "telemetry_on_s",
                        "telemetry_overhead_pct"}
    # both arms genuinely ran a ~20 ms program (a broken measurement
    # reads ~0); the PERCENTAGE is recorded, not asserted — see docstring
    assert rec["telemetry_off_s"] > 1e-4 and rec["telemetry_on_s"] > 1e-4
    if rec["telemetry_overhead_pct"] > 5.0:
        import warnings

        warnings.warn(
            f"telemetry overhead p50 measured {rec['telemetry_overhead_pct']}"
            "% (> the 5% design budget) — recorded in the ledger, not "
            "asserted; investigate only if it reproduces on an idle host",
            stacklevel=1,
        )


def test_telemetry_overhead_record_schema():
    rec = telemetry_overhead_record(2.0, 2.05)
    assert rec == {"telemetry_off_s": 2.0, "telemetry_on_s": 2.05,
                   "telemetry_overhead_pct": 2.5}


# ------------------------------------- program introspection (ISSUE 3) --


def _tanh_matmul():
    # module-level name keeps the HLO module name (and so the fingerprint)
    # identical across fresh jit wrappers
    def cost_probe(x):
        return jnp.tanh(x @ x) + 1

    return cost_probe


def test_analyze_jitted_schema_and_determinism():
    """The acceptance pin: the analysis record is shape-stable and
    DETERMINISTIC across two independent compiles of the same program on
    CPU — fingerprints, flops, histograms, everything."""
    from videop2p_tpu.obs import analyze_jitted

    sds = jax.ShapeDtypeStruct((16, 16), jnp.float32)
    rec1 = analyze_jitted(jax.jit(_tanh_matmul()), sds)
    jax.clear_caches()
    rec2 = analyze_jitted(jax.jit(_tanh_matmul()), sds)
    assert rec1 == rec2
    for key in ("flops", "transcendentals", "bytes_accessed",
                "argument_bytes", "output_bytes", "temp_bytes",
                "alias_bytes", "generated_code_bytes", "peak_hbm_bytes",
                "hlo_fingerprint", "hlo_instructions", "hlo_histogram"):
        assert key in rec1, key
    assert rec1["flops"] > 0
    assert rec1["peak_hbm_bytes"] == (
        rec1["argument_bytes"] + rec1["output_bytes"] + rec1["temp_bytes"]
        + rec1["generated_code_bytes"] - rec1["alias_bytes"]
    )
    assert sum(rec1["hlo_histogram"].values()) == rec1["hlo_instructions"]
    assert "dot" in rec1["hlo_histogram"]
    # a different program fingerprints differently
    rec3 = analyze_jitted(jax.jit(lambda x: x + 1), sds)
    assert rec3["hlo_fingerprint"] != rec1["hlo_fingerprint"]
    # analysis is best-effort: garbage in → None, not an exception
    assert analyze_jitted(jax.jit(lambda x: x.bad_attr), sds) is None


def test_instrumented_jit_emits_analysis_on_miss_only(tmp_path):
    """One program_analysis event per compile (cache miss), none on hits,
    attributed to the program label, with the numeric metrics identical
    across two runs of the same program."""
    recs = []
    for i in range(2):
        path = str(tmp_path / f"ledger{i}.jsonl")
        f = instrumented_jit(_tanh_matmul(), program="cost_probe")
        with RunLedger(path):
            f(jnp.ones((16, 16)))
            f(jnp.ones((16, 16)))  # hit: no second analysis
        events = read_ledger(path)
        pa = [e for e in events if e["event"] == "program_analysis"]
        assert len(pa) == 1
        assert pa[0]["program"] == "cost_probe"
        recs.append({k: v for k, v in pa[0].items() if k != "t"})
        jax.clear_caches()
    assert recs[0] == recs[1]


def test_program_analysis_kill_switch_and_ledger_off(tmp_path, monkeypatch):
    f = instrumented_jit(lambda x: x * 2, program="doubler")
    # no active ledger: plain passthrough, nothing recorded anywhere
    assert float(f(jnp.asarray(2.0))) == 4.0
    # active ledger + kill-switch: program_call still recorded, analysis not
    monkeypatch.setenv("VIDEOP2P_OBS_NO_ANALYSIS", "1")
    path = str(tmp_path / "ledger.jsonl")
    g = instrumented_jit(lambda x: x * 3, program="tripler")
    with RunLedger(path):
        g(jnp.asarray(2.0))
    kinds = [e["event"] for e in read_ledger(path)]
    assert "program_call" in kinds
    assert "program_analysis" not in kinds


def test_program_analysis_skip_is_an_event_not_silence(tmp_path, monkeypatch):
    """ISSUE 5 satellite: when the automatic analysis is disabled or cannot
    run, the ledger records a program_analysis_skipped event with the
    reason — a missing record is a statement, never a silent drop."""
    path = str(tmp_path / "ledger.jsonl")
    f = instrumented_jit(lambda x: x + 1, program="adder", analyze=False)
    with RunLedger(path):
        f(jnp.asarray(1.0))

    def skips(p):
        return [(e["program"], e["reason"]) for e in read_ledger(p)
                if e["event"] == "program_analysis_skipped"]

    assert skips(path) == [("adder", "analyze_false")]
    # the process-wide kill-switch states its reason too
    monkeypatch.setenv("VIDEOP2P_OBS_NO_ANALYSIS", "1")
    path2 = str(tmp_path / "ledger2.jsonl")
    g = instrumented_jit(lambda x: x + 2, program="adder2")
    with RunLedger(path2):
        g(jnp.asarray(1.0))
    assert skips(path2) == [("adder2", "disabled")]
    monkeypatch.delenv("VIDEOP2P_OBS_NO_ANALYSIS")
    # a failing lower/compile behind an otherwise-working call: the call
    # succeeds, the skip event lands with the failure reason
    from videop2p_tpu.obs import introspect as introspect_mod

    path3 = str(tmp_path / "ledger3.jsonl")
    h = instrumented_jit(lambda x: x * 2, program="flaky")
    with monkeypatch.context() as m:
        m.setattr(introspect_mod, "compile_abstract", lambda *a, **kw: None)
        with RunLedger(path3):
            out = h(jnp.asarray(3.0))
    assert float(out) == 6.0
    assert skips(path3) == [("flaky", "lower_or_compile_failed")]
    # skipped events never fire on a healthy analyzed program
    path4 = str(tmp_path / "ledger4.jsonl")
    k = instrumented_jit(lambda x: x * 3, program="ok")
    with RunLedger(path4):
        k(jnp.asarray(1.0))
    assert skips(path4) == []
    assert any(e["event"] == "program_analysis" for e in read_ledger(path4))


def test_null_text_programs_emit_analysis(problem, sched, tmp_path):
    """The pipelines' internal jits (fused + chunked null-text) are
    instrumented where the CLI's wrappers cannot reach — both land
    program_analysis events with distinct fingerprints."""
    fn, _, cond, uncond, traj = problem
    path = str(tmp_path / "ledger.jsonl")
    with RunLedger(path):
        null_text_optimization_fused(
            fn, None, sched, traj, cond, uncond,
            num_inference_steps=STEPS, num_inner_steps=2,
        )
        null_text_optimization(
            fn, None, sched, traj, cond, uncond,
            num_inference_steps=STEPS, num_inner_steps=2, outer_chunk=3,
        )
    pa = {e["program"]: e for e in read_ledger(path)
          if e["event"] == "program_analysis"}
    assert set(pa) == {"null_text_fused", "null_text_chunked"}
    for e in pa.values():
        assert e["flops"] > 0 and len(e["hlo_fingerprint"]) == 16
    assert (pa["null_text_fused"]["hlo_fingerprint"]
            != pa["null_text_chunked"]["hlo_fingerprint"])


# -------------------------------------------- run history + regression --


def _write_run(path, run_id, wall_time, analyses, phases=()):
    """Synthetic ledger run: program_analysis + phase events with
    controlled values (RunLedger stamps run_start/run_end around them)."""
    led = RunLedger(path, run_id=run_id, device_info=False)
    # overwrite the auto wall_time for deterministic ordering
    led.event("run_start_patch")  # no-op marker; ordering uses run_start
    for prog, rec in analyses.items():
        led.program_analysis(prog, rec)
    for name, secs in phases:
        led.phase(name, secs)
    led.close()
    # rewrite wall_time in-place (the ledger stamped now())
    import json as _json

    lines = []
    for line in open(path):
        e = _json.loads(line)
        if e.get("event") == "run_start" and e.get("run_id") == run_id:
            e["wall_time"] = wall_time
        lines.append(_json.dumps(e))
    with open(path, "w") as f:
        f.write("\n".join(lines) + "\n")


_ANALYSIS_A = {"flops": 1000, "bytes_accessed": 10 * 2**20,
               "temp_bytes": 100 * 2**20, "peak_hbm_bytes": 200 * 2**20,
               "hlo_instructions": 500, "hlo_fingerprint": "aaaa"}


def test_run_history_scan_series_and_baseline(tmp_path):
    from videop2p_tpu.obs import RunHistory

    d = str(tmp_path)
    _write_run(os.path.join(d, "r1.jsonl"), "r1", "2026-08-01T00:00:00Z",
               {"edit": _ANALYSIS_A}, phases=[("edit_phase", 10.0)])
    # two runs APPENDED into one file (ledgers open append-mode)
    p2 = os.path.join(d, "r2.jsonl")
    _write_run(p2, "r2", "2026-08-02T00:00:00Z", {"edit": _ANALYSIS_A})
    _write_run(p2, "r3", "2026-08-03T00:00:00Z",
               {"edit": {**_ANALYSIS_A, "temp_bytes": 120 * 2**20}})
    hist = RunHistory.scan(d)
    assert [r["run_id"] for r in hist.runs] == ["r1", "r2", "r3"]
    series = hist.series("temp_bytes")
    # keyed by (label, fingerprint): same program+fingerprint = one series
    assert set(series) == {("edit", "aaaa")}
    assert [v for _, v in series[("edit", "aaaa")]] == [
        100 * 2**20, 100 * 2**20, 120 * 2**20]
    latest = hist.latest()
    assert latest["run_id"] == "r3"
    base = hist.baseline_for(latest)
    assert base["run_id"] == "r2"


def test_regression_rules_flag_injected_regression(tmp_path):
    from videop2p_tpu.obs import evaluate_rules, extract_run, split_runs

    d = str(tmp_path)
    _write_run(os.path.join(d, "a.jsonl"), "a", "2026-08-01T00:00:00Z",
               {"edit": _ANALYSIS_A}, phases=[("p", 10.0)])
    _write_run(os.path.join(d, "b.jsonl"), "b", "2026-08-02T00:00:00Z",
               {"edit": {**_ANALYSIS_A,
                         "temp_bytes": int(_ANALYSIS_A["temp_bytes"] * 1.2),
                         "hlo_fingerprint": "bbbb"}},
               phases=[("p", 10.1)])
    base = extract_run(split_runs(read_ledger(os.path.join(d, "a.jsonl")))[0])
    new = extract_run(split_runs(read_ledger(os.path.join(d, "b.jsonl")))[0])
    # self-compare: always clean
    assert evaluate_rules(base, base)["pass"]
    res = evaluate_rules(base, new)
    assert not res["pass"]
    regs = {(v["metric"], v["program"]) for v in res["regressions"]}
    assert regs == {("temp_bytes", "edit")}  # +20% temp, phases within noise
    [v] = res["regressions"]
    assert v["delta_pct"] == 20.0
    assert v["fingerprint_changed"] is True
    # the phase verdict exists but is under threshold
    phase_v = [x for x in res["verdicts"] if x["kind"] == "phase"]
    assert phase_v and not phase_v[0]["regressed"]


def test_extract_run_tolerates_partial_events(tmp_path):
    """A torn tail (killed run) can leave half-records: extraction and
    rendering must survive events missing their payload fields."""
    from videop2p_tpu.obs import extract_run

    rec = extract_run([
        {"event": "phase"},  # no name/seconds
        {"event": "compile", "seconds": "junk"},
        {"event": "program_call", "program": "x"},
        {"event": "program_analysis"},  # no program/metrics
        {"not_even": "an event"},
    ])
    assert rec["run_id"] is None
    assert rec["phases"]["?"]["calls"] == 1
    assert "(unattributed)" in rec["programs"]


def test_obs_diff_cli_self_zero_and_regression_nonzero(tmp_path, capsys):
    """The acceptance gate: obs_diff exits 0 comparing a ledger against
    itself and nonzero on a synthetically injected +20% temp-bytes
    regression; --history mode agrees."""
    mod = _load_tool("obs_diff")
    d = str(tmp_path)
    a = os.path.join(d, "a.jsonl")
    b = os.path.join(d, "b.jsonl")
    _write_run(a, "a", "2026-08-01T00:00:00Z", {"edit": _ANALYSIS_A})
    _write_run(b, "b", "2026-08-02T00:00:00Z",
               {"edit": {**_ANALYSIS_A,
                         "temp_bytes": int(_ANALYSIS_A["temp_bytes"] * 1.2)}})
    assert mod.main(["obs_diff.py", a, a]) == 0
    out = capsys.readouterr().out
    assert "no regressions" in out
    assert mod.main(["obs_diff.py", a, b]) == 1
    out = capsys.readouterr().out
    assert "REGRESSIONS" in out and "temp_bytes" in out
    # --history picks the prior run as baseline for the latest
    assert mod.main(["obs_diff.py", "--history", d]) == 1
    # threshold scaling can wave it through
    assert mod.main(["obs_diff.py", "--threshold-scale", "3.0", a, b]) == 0
    # unreadable input is usage error, not a crash
    assert mod.main(["obs_diff.py", a, os.path.join(d, "missing.jsonl")]) == 2


def test_obs_diff_json_output_is_machine_readable(tmp_path, capsys):
    mod = _load_tool("obs_diff")
    a = str(tmp_path / "a.jsonl")
    _write_run(a, "a", "2026-08-01T00:00:00Z", {"edit": _ANALYSIS_A})
    assert mod.main(["obs_diff.py", "--json", a, a]) == 0
    verdict = json.loads(capsys.readouterr().out)
    assert verdict["pass"] is True and verdict["regressions"] == []


# --------------------------------------------------------- CLI e2e (slow) --


@pytest.mark.slow
def test_cli_full_mode_writes_acceptance_ledger(tmp_path):
    """The acceptance run: a full-mode (null-text) CLI edit with
    --telemetry/--ledger writes a JSONL holding ≥1 compile event, ≥1 phase
    event, and the decoded fused-null-text telemetry (loss curve +
    inner-steps); ledger_summary renders it without error."""
    from videop2p_tpu.cli.run_videop2p import main as p2p

    ledger_path = str(tmp_path / "acceptance_ledger.jsonl")
    inv_gif, edit_gif = p2p(
        pretrained_model_path=str(tmp_path / "no_ckpt"),
        image_path="data/rabbit",
        prompt="a rabbit is jumping",
        prompts=["a rabbit is jumping", "a origami rabbit is jumping"],
        save_name="origami", is_word_swap=False,
        video_len=2, fast=False, tiny=True, num_inner_steps=2,
        telemetry=True, ledger=ledger_path, reuse_inversion=False,
    )
    assert os.path.isfile(inv_gif) and os.path.isfile(edit_gif)
    events = read_ledger(ledger_path)
    kinds = {e["event"] for e in events}
    assert {"run_start", "compile", "phase", "telemetry", "memory",
            "run_end"} <= kinds
    null_tel = [e for e in events if e["event"] == "telemetry"
                and e["program"] == "null_text_fused"]
    assert null_tel, "fused null-text telemetry missing from the ledger"
    rec = null_tel[0]
    assert len(rec["loss_curve"]) == 50
    assert len(rec["inner_steps"]) == 50
    assert rec["inner_steps_total"] >= 50  # ≥1 inner Adam step per outer
    assert rec["latent"]["nan_total"] == 0
    phases = [e["name"] for e in events if e["event"] == "phase"]
    assert "null_text_optimization" in phases
    # ISSUE 3: every instrumented program's compile was mined into a
    # program_analysis event — including the pipeline-internal fused
    # null-text jit the CLI wrappers cannot reach
    pa = {e["program"]: e for e in events
          if e["event"] == "program_analysis"}
    assert "null_text_fused" in pa and "vae_encode" in pa
    for e in pa.values():
        assert e["flops"] > 0 and len(e["hlo_fingerprint"]) == 16
    mod = _load_summary_tool()
    text = mod.render(events)
    assert "null_text_fused" in text and "inner steps" in text
    assert "program analysis" in text
