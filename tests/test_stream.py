"""Streaming long-video tests (ISSUE 12): the deterministic window plan +
crossfade assembly, the atomic resumable job manifest (incl. torn-manifest
recovery from sidecars), and the streaming driver's robustness contract —
per-window fault isolation (transient chaos retried, poisoned windows
degrade to recorded passthroughs), checkpoint-then-exit, resume that skips
completed windows with zero re-inversions/compiles, and the SIGKILL
kill-and-resume acceptance with bit-identical final frames.
"""

import json
import os
import subprocess
import sys
import threading
import time

import numpy as np
import pytest

from videop2p_tpu.stream.manifest import JobManifest
from videop2p_tpu.stream.windows import (
    Window,
    assemble_video,
    blend_weights,
    plan_windows,
    seam_spans,
    synthetic_clip,
    window_key,
)

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


# ------------------------------------------------------------- windows ---


def test_plan_windows_geometry_and_validation():
    # marching stride with the final window anchored at total - window
    plan = plan_windows(14, 4, 1)
    assert [(w.start, w.stop) for w in plan] == \
        [(0, 4), (3, 7), (6, 10), (9, 13), (10, 14)]
    assert [w.index for w in plan] == [0, 1, 2, 3, 4]
    assert all(w.frames == 4 for w in plan)
    # the minute-of-footage counts (window 8, overlap 2)
    assert len(plan_windows(128, 8, 2)) == 21
    assert len(plan_windows(480, 8, 2)) == 80
    # one-window degenerate case
    assert plan_windows(8, 8, 2) == [Window(0, 0, 8)]
    with pytest.raises(ValueError, match="shorter than one window"):
        plan_windows(6, 8, 2)
    with pytest.raises(ValueError, match="overlap"):
        plan_windows(16, 4, 4)
    with pytest.raises(ValueError, match="window"):
        plan_windows(16, 1, 0)


def test_blend_weights_and_assembly_crossfade():
    # the ramp never reaches 0 or 1 inside the overlap
    w = blend_weights(3)
    assert np.allclose(w, [0.25, 0.5, 0.75])
    assert blend_weights(0).shape == (0,)
    plan = plan_windows(6, 4, 2)  # [0,4) + [2,6), overlap [2,4)
    a = np.zeros((4, 2, 2, 3), np.float32)
    b = np.ones((4, 2, 2, 3), np.float32)
    out = assemble_video(plan, {0: a, 1: b}, 6)
    # outside the overlap each window owns its frames; inside, the
    # closed-form crossfade (1-r)*a + r*b with r = (1/3, 2/3)
    assert np.all(out[:2] == 0.0) and np.all(out[4:] == 1.0)
    assert np.allclose(out[2], 1.0 / 3.0) and np.allclose(out[3], 2.0 / 3.0)
    with pytest.raises(ValueError, match="missing window outputs"):
        assemble_video(plan, {0: a}, 6)
    spans = seam_spans(plan)
    assert spans == [{"left": 0, "right": 1, "start": 2, "stop": 4}]


def test_synthetic_clip_deterministic_across_calls():
    a = synthetic_clip(10, 8, seed=3)
    b = synthetic_clip(10, 8, seed=3)
    assert a.shape == (10, 8, 8, 3) and a.dtype == np.uint8
    assert np.array_equal(a, b)
    assert not np.array_equal(a, synthetic_clip(10, 8, seed=4))


def test_window_key_content_addressed():
    frames = synthetic_clip(4, 8, seed=0)
    k = window_key("specfp", frames, ["a", "b"], seed=0)
    assert k == window_key("specfp", frames.copy(), ["a", "b"], seed=0)
    assert k != window_key("specfp2", frames, ["a", "b"], seed=0)
    assert k != window_key("specfp", frames[::-1], ["a", "b"], seed=0)
    assert k != window_key("specfp", frames, ["a", "c"], seed=0)
    assert k != window_key("specfp", frames, ["a", "b"], seed=1)
    assert k != window_key("specfp", frames, ["a", "b"], seed=0,
                           extra={"blend_word": ["a", "b"]})


# ------------------------------------------------------------ manifest ---


def _identity(**over):
    base = {"spec_fingerprint": "fp", "clip_sha": "c", "prompts": ["a", "b"],
            "seed": 0, "request": {}, "total_frames": 6, "window": 4,
            "overlap": 2}
    base.update(over)
    return base


def test_manifest_roundtrip_atomic_and_identity_guard(tmp_path):
    m = JobManifest(str(tmp_path / "job"), _identity())
    frames = np.random.RandomState(0).rand(4, 2, 2, 3).astype(np.float32)
    m.complete_window(0, "k0", frames, status="done", src_err=0.0,
                      store_source="fresh")
    # a fresh manifest over the same dir + identity loads the entry and
    # validates the sidecar bit-for-bit
    m2 = JobManifest(str(tmp_path / "job"), _identity())
    assert m2.load() and list(m2.entries) == [0]
    out = m2.valid_output(0)
    assert out is not None and np.array_equal(out, frames)
    # no stale temp files survive the atomic writes
    leftovers = [f for f in os.listdir(str(tmp_path / "job")) if ".tmp" in f]
    assert leftovers == []
    # a DIFFERENT identity never resumes into this job: the manifest is
    # treated as corrupt-for-this-job and the alien sidecars are rejected
    m3 = JobManifest(str(tmp_path / "job"), _identity(seed=1))
    assert not m3.load()
    assert m3.corrupt_detected == 1 and m3.entries == {}


def test_manifest_torn_file_recovers_from_sidecars(tmp_path):
    job = str(tmp_path / "job")
    m = JobManifest(job, _identity())
    frames = np.random.RandomState(1).rand(4, 2, 2, 3).astype(np.float32)
    m.complete_window(0, "k0", frames, status="done", src_err=0.0)
    m.complete_window(1, "k1", frames + 1, status="passthrough", attempts=3)
    # tear the manifest mid-document — the artifact a kill inside a
    # non-atomic writer would leave
    doc = open(m.path).read()
    with open(m.path, "w") as f:
        f.write(doc[: len(doc) // 2])
    m2 = JobManifest(job, _identity())
    assert m2.load()
    assert m2.corrupt_detected == 1 and m2.recovered_entries == 2
    assert m2.entries[0]["status"] == "done"
    assert m2.entries[1]["status"] == "passthrough"
    assert np.array_equal(m2.valid_output(0), frames)
    # recovery re-persisted a VALID manifest
    m3 = JobManifest(job, _identity())
    assert m3.load() and m3.corrupt_detected == 0


def test_manifest_bad_sidecar_forces_recompute(tmp_path):
    job = str(tmp_path / "job")
    m = JobManifest(job, _identity())
    frames = np.random.RandomState(2).rand(4, 2, 2, 3).astype(np.float32)
    entry = m.complete_window(0, "k0", frames, status="done")
    # corrupt the sidecar bytes: sha mismatch -> entry dropped, recompute
    path = os.path.join(job, entry["output"])
    with open(path, "r+b") as f:
        f.seek(200)
        f.write(b"\xff" * 32)
    m2 = JobManifest(job, _identity())
    assert m2.load()
    assert m2.valid_output(0) is None
    assert 0 not in m2.entries
    # a missing sidecar likewise
    entry = m.complete_window(1, "k1", frames, status="done")
    os.remove(os.path.join(job, entry["output"]))
    m3 = JobManifest(job, _identity())
    m3.load()
    assert m3.valid_output(1) is None


def test_manifest_corrupt_directive_tears_every_save(tmp_path):
    from videop2p_tpu.serve.faults import FaultPlan

    plan = FaultPlan.parse("corrupt:manifest")
    m = JobManifest(str(tmp_path / "job"), _identity(), faults=plan)
    frames = np.zeros((4, 2, 2, 3), np.float32)
    m.complete_window(0, "k0", frames, status="done")
    with pytest.raises(ValueError):
        json.load(open(m.path))
    assert any(i["kind"] == "store_corrupt" for i in plan.injected)
    # ...and the recovery path rebuilds from the (untorn) sidecars
    m2 = JobManifest(str(tmp_path / "job"), _identity())
    assert m2.load()
    assert m2.corrupt_detected == 1 and m2.recovered_entries == 1


# ----------------------------------------------------- streaming driver --

_SPEC_KW = dict(checkpoint=None, tiny=True, width=16, video_len=2, steps=2)
_PROMPTS = ["a rabbit is jumping", "a origami rabbit is jumping"]


def _make_engine(root, name, **over):
    from videop2p_tpu.serve import EditEngine, ProgramSpec

    kw = dict(
        out_dir=os.path.join(str(root), f"{name}_out"),
        persist_dir=os.path.join(str(root), "inv_store"),
        ledger_path=os.path.join(str(root), f"{name}_ledger.jsonl"),
        keep_videos=True,
        max_batch=2,
        max_wait_s=0.05,
    )
    kw.update(over)
    eng = EditEngine(ProgramSpec(**_SPEC_KW), **kw)
    eng.warm(tuple(_PROMPTS), batch_sizes=(2,))
    return eng


@pytest.fixture(scope="module")
def stream_root(tmp_path_factory):
    return tmp_path_factory.mktemp("stream")


@pytest.fixture(scope="module")
def engine(stream_root):
    eng = _make_engine(stream_root, "main")
    yield eng
    eng.close()


@pytest.fixture(scope="module")
def clip():
    return synthetic_clip(5, 16, seed=1)  # 4 windows at window=2, overlap=1


def test_stream_job_end_to_end_ledger_and_full_skip_resume(
    engine, clip, stream_root
):
    """The streaming tentpole acceptance: a 4-window job completes with
    every window edited (src_err == 0.0 throughout), per-window /
    per-seam / job-level evidence lands in the run ledger (extracted into
    the `stream` section SEAM_RULES gate), and rerunning over the same
    job dir SKIPS every window — zero requests, zero new inversions,
    bit-identical final frames."""
    from videop2p_tpu.obs import read_ledger
    from videop2p_tpu.obs.history import extract_run
    from videop2p_tpu.stream import run_stream_job

    job = str(stream_root / "job_e2e")
    res = run_stream_job(engine, clip, _PROMPTS, job_dir=job, overlap=1,
                         max_inflight=2)
    h = res.health
    assert res.complete and res.video.shape == (5, 16, 16, 3)
    assert h["windows_total"] == 4 and h["windows_done"] == 4
    assert h["windows_passthrough"] == 0 and h["windows_failed"] == 0
    assert h["src_err_max"] == 0.0
    assert h["seams"] == 3 and np.isfinite(h["seam_min_psnr"])
    assert os.path.isfile(os.path.join(job, "final.npy"))
    events = read_ledger(engine.ledger.path)
    by_kind = {}
    for e in events:
        by_kind.setdefault(e.get("event"), []).append(e)
    assert len(by_kind["stream_window"]) >= 4
    assert len(by_kind["stream_seam"]) >= 3
    assert by_kind["stream_health"][-1]["windows_done"] == 4
    rec = extract_run(events)
    assert rec["stream"]["stream"]["seam_min_psnr"] == pytest.approx(
        h["seam_min_psnr"]
    )

    # resume: every window validated off the manifest, nothing recomputed
    before = len(engine._requests)
    res2 = run_stream_job(engine, clip, _PROMPTS, job_dir=job, overlap=1)
    assert res2.health["windows_skipped"] == 4
    assert res2.health["windows_done"] == 0
    assert res2.health["fresh_inversions"] == 0
    assert len(engine._requests) == before  # zero engine requests
    assert np.array_equal(res.video, res2.video)


def test_stream_resume_missing_sidecar_rehydrates_zero_compiles(
    engine, clip, stream_root
):
    """The crash-recovery acceptance (disk store hits, zero new
    inversions, zero compiles): lose one window's output sidecar and
    resume on a FRESH engine sharing the disk store — the window
    recomputes through warm programs from the persisted trajectory
    (store_source == "disk"), with no new inversion-from-frames, no
    compile, and a bit-identical final video."""
    from videop2p_tpu.stream import run_stream_job

    job = str(stream_root / "job_rehydrate")
    res = run_stream_job(engine, clip, _PROMPTS, job_dir=job, overlap=1)
    assert res.complete
    os.remove(os.path.join(job, "windows", "w0001.npz"))

    eng2 = _make_engine(stream_root, "rehydrate")
    try:
        compiles_before = len(eng2.ledger.compile_seconds)
        res2 = run_stream_job(eng2, clip, _PROMPTS, job_dir=job, overlap=1)
        h = res2.health
        assert h["windows_skipped"] == 3 and h["windows_done"] == 1
        assert h["store_disk_hits"] == 1
        assert h["fresh_inversions"] == 0
        assert h["src_err_max"] == 0.0
        assert len(eng2.ledger.compile_seconds) == compiles_before
        assert np.array_equal(res.video, res2.video)
    finally:
        eng2.close()


def test_stream_chaos_fail2_engine_retry_completes(clip, stream_root):
    """Chaos acceptance: `fail@2` injects a transient dispatch failure
    under window 2 — the engine's RetryPolicy absorbs it and the job
    completes with every window edited, the retry on the books."""
    from videop2p_tpu.serve.faults import FaultPlan
    from videop2p_tpu.stream import run_stream_job

    plan = FaultPlan.parse("fail@2")
    eng = _make_engine(stream_root, "fail2", faults=plan, max_retries=2)
    try:
        res = run_stream_job(eng, clip, _PROMPTS,
                             job_dir=str(stream_root / "job_fail2"),
                             overlap=1, max_inflight=1)
        h = res.health
        assert res.complete and h["windows_done"] == 4
        assert h["windows_passthrough"] == 0
        assert h["src_err_max"] == 0.0
        assert eng.counters["retries"] >= 1
        assert [i["kind"] for i in plan.injected] == ["dispatch_fail"]
    finally:
        eng.close()


def test_stream_poisoned_windows_degrade_to_passthrough(clip, stream_root):
    """A window that keeps failing (an unavailable window past the
    engine's retry budget) degrades to a RECORDED passthrough — the job
    completes instead of dying, the degradations land in stream_health,
    and degrade=False makes the same poisoning fatal."""
    from videop2p_tpu.serve.faults import FaultPlan
    from videop2p_tpu.stream import run_stream_job

    eng = _make_engine(
        stream_root, "poison", faults=FaultPlan.parse("unavail@3-999"),
        max_retries=0, breaker_threshold=1000,
    )
    try:
        res = run_stream_job(eng, clip, _PROMPTS,
                             job_dir=str(stream_root / "job_poison"),
                             overlap=1, max_inflight=1, window_retries=1)
        h = res.health
        assert res.complete  # the job survives its poisoned windows
        assert h["windows_done"] == 2
        assert h["windows_passthrough"] == 2
        assert h["windows_failed"] == 2
        assert h["retries"] >= 2
        entries = res.manifest.entries
        assert sorted(e["status"] for e in entries.values()) == \
            ["done", "done", "passthrough", "passthrough"]
        # passthrough windows carry the SOURCE frames
        pt = [i for i, e in entries.items() if e["status"] == "passthrough"]
        out = res.manifest.valid_output(pt[0])
        w = [win for win in plan_windows(5, 2, 1) if win.index == pt[0]][0]
        assert np.array_equal(
            out, clip[w.start:w.stop].astype(np.float32) / 255.0
        )
        # degrade=False: the same poisoning is fatal
        with pytest.raises(RuntimeError, match="poisoned"):
            run_stream_job(eng, clip, _PROMPTS,
                           job_dir=str(stream_root / "job_poison_fatal"),
                           overlap=1, max_inflight=1, window_retries=0,
                           degrade=False)
    finally:
        eng.close()


def test_stream_manifest_corrupt_chaos_resume_recovers(
    engine, clip, stream_root
):
    """corrupt:manifest chaos tears EVERY manifest write; the next run
    detects the corruption, rebuilds the entries from the sidecars, skips
    every completed window and produces bit-identical output."""
    from videop2p_tpu.serve.faults import FaultPlan
    from videop2p_tpu.stream import run_stream_job

    job = str(stream_root / "job_corrupt")
    res = run_stream_job(engine, clip, _PROMPTS, job_dir=job, overlap=1,
                         faults=FaultPlan.parse("corrupt:manifest"))
    assert res.complete
    with pytest.raises(ValueError):
        json.load(open(os.path.join(job, "manifest.json")))
    res2 = run_stream_job(engine, clip, _PROMPTS, job_dir=job, overlap=1)
    h = res2.health
    assert h["manifest_corrupt"] == 1
    assert h["manifest_recovered"] == 4
    assert h["windows_skipped"] == 4 and h["fresh_inversions"] == 0
    assert np.array_equal(res.video, res2.video)


def test_stream_checkpoint_then_exit_and_resume(engine, clip, stream_root):
    """SIGTERM contract (in-process half): a stop event raised mid-job
    stops new submissions, what landed stays persisted, the health
    summary says interrupted — and the rerun completes from the
    manifest."""
    from videop2p_tpu.stream import run_stream_job

    job = str(stream_root / "job_interrupt")
    manifest_path = os.path.join(job, "manifest.json")
    stop = threading.Event()

    def watcher():
        deadline = time.perf_counter() + 60.0
        while time.perf_counter() < deadline and not stop.is_set():
            try:
                doc = json.load(open(manifest_path))
                if any(w["status"] in ("done", "passthrough")
                       for w in doc["windows"]):
                    stop.set()
                    return
            except (OSError, ValueError):
                pass
            time.sleep(0.005)

    t = threading.Thread(target=watcher, daemon=True)
    t.start()
    res = run_stream_job(engine, clip, _PROMPTS, job_dir=job, overlap=1,
                         max_inflight=1, stop_event=stop)
    t.join(timeout=5)
    completed = res.health["windows_done"] + res.health["windows_skipped"]
    assert completed >= 1
    if res.health["interrupted"]:
        assert res.video is None
    # the rerun finishes the job (store hits make it cheap)
    res2 = run_stream_job(engine, clip, _PROMPTS, job_dir=job, overlap=1)
    assert res2.complete
    assert res2.health["windows_skipped"] >= completed


def test_stream_driver_validation(engine, clip, stream_root):
    from videop2p_tpu.stream import run_stream_job

    no_keep = type("E", (), {"keep_videos": False})()
    with pytest.raises(ValueError, match="keep_videos"):
        run_stream_job(no_keep, clip, _PROMPTS,
                       job_dir=str(stream_root / "nokeep"))
    with pytest.raises(ValueError, match="frames must be"):
        run_stream_job(engine, clip[..., 0], _PROMPTS,
                       job_dir=str(stream_root / "badshape"))


def test_obs_diff_gates_seam_quality_drop(tmp_path):
    """The acceptance teeth: a healthy stream ledger self-compares exit 0
    through tools/obs_diff.py; an injected seam-quality drop (and a new
    passthrough degradation) exits 1 with machine-readable SEAM_RULES
    verdicts."""
    import importlib.util

    from videop2p_tpu.obs import RunLedger
    from videop2p_tpu.stream.driver import STREAM_HEALTH_FIELDS

    spec = importlib.util.spec_from_file_location(
        "obs_diff_under_stream_test",
        os.path.join(_REPO, "tools", "obs_diff.py"),
    )
    obs_diff = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(obs_diff)

    def write_ledger(name, **over):
        health = {k: 0 for k in STREAM_HEALTH_FIELDS}
        health.update(windows_total=6, windows_done=6, seams=5,
                      seam_min_psnr=24.0, seam_mean_psnr=30.0,
                      source_seam_min_psnr=26.0, src_err_max=0.0)
        health.update(over)
        path = str(tmp_path / name)
        with RunLedger(path) as led:
            led.event("stream_health", **health)
        return path

    healthy = write_ledger("healthy.jsonl")
    assert obs_diff.main(["obs_diff.py", healthy, healthy]) == 0
    degraded = write_ledger("degraded.jsonl", seam_min_psnr=12.0,
                            seam_mean_psnr=15.0, windows_done=5,
                            windows_passthrough=1, windows_failed=1)
    assert obs_diff.main(["obs_diff.py", healthy, degraded]) == 1
    # the drop direction matters: a seam IMPROVING never regresses
    better = write_ledger("better.jsonl", seam_min_psnr=40.0,
                          seam_mean_psnr=45.0)
    assert obs_diff.main(["obs_diff.py", healthy, better]) == 0


# ------------------------------------------------ kill-and-resume e2e ----


@pytest.mark.slow
def test_stream_sigkill_resume_bit_identical(tmp_path):
    """THE chaos acceptance (ISSUE 12): SIGKILL the streaming driver
    mid-window; the resumed job skips every completed window (no
    re-inversions of them) and the final frames are BIT-IDENTICAL to an
    uninterrupted run's."""
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               PYTHONPATH=_REPO + os.pathsep + os.environ.get("PYTHONPATH", ""))

    def drive(job_dir, ledger):
        return [sys.executable, os.path.join(_REPO, "tools", "stream_drive.py"),
                "--frames", "7", "--video_len", "2", "--overlap", "1",
                "--steps", "2", "--width", "16",
                "--job_dir", job_dir, "--ledger", ledger]

    kill_job = str(tmp_path / "kill_job")
    proc = subprocess.Popen(
        drive(kill_job, str(tmp_path / "led1.jsonl")), env=env,
        stdout=subprocess.PIPE, stderr=subprocess.PIPE,
    )
    manifest = os.path.join(kill_job, "manifest.json")
    deadline = time.perf_counter() + 540.0
    killed = False
    while time.perf_counter() < deadline:
        if proc.poll() is not None:
            break
        try:
            doc = json.load(open(manifest))
            done = sum(1 for w in doc["windows"] if w["status"] == "done")
        except (OSError, ValueError):
            done = 0
        if done >= 2:
            proc.kill()  # SIGKILL — no cleanup, no atexit, nothing
            killed = True
            break
        time.sleep(0.1)
    proc.wait(timeout=60)
    assert killed, "driver finished before the kill window — slow the clip down"
    persisted = json.load(open(manifest))
    persisted_done = sum(1 for w in persisted["windows"]
                         if w["status"] == "done")
    assert persisted_done >= 2  # the manifest survived the SIGKILL intact

    # resume over the same job dir
    out = subprocess.run(drive(kill_job, str(tmp_path / "led2.jsonl")),
                         env=env, capture_output=True, text=True,
                         timeout=540)
    assert out.returncode == 0, out.stderr[-2000:]
    health = json.loads(out.stdout.strip().splitlines()[-1])["stream_health"]
    assert health["windows_skipped"] >= persisted_done
    # zero re-inversions of completed windows: every recomputed window is
    # accounted for by the remainder, and any whose trajectory the killed
    # run already wrote through is a DISK hit, not a re-inversion
    recomputed = health["windows_total"] - health["windows_skipped"]
    assert health["fresh_inversions"] <= recomputed
    assert (health["fresh_inversions"] + health["store_disk_hits"]
            + health["store_memory_hits"]) == recomputed
    assert health["src_err_max"] == 0.0

    # uninterrupted reference run -> bit-identical final frames
    ref_job = str(tmp_path / "ref_job")
    out = subprocess.run(drive(ref_job, str(tmp_path / "led3.jsonl")),
                         env=env, capture_output=True, text=True,
                         timeout=540)
    assert out.returncode == 0, out.stderr[-2000:]
    resumed = np.load(os.path.join(kill_job, "final.npy"))
    reference = np.load(os.path.join(ref_job, "final.npy"))
    assert np.array_equal(resumed, reference)
