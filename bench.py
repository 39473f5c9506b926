"""Benchmark of record: Stage-2 edit wall-clock on real hardware.

Measures the reference's headline scenario (README.md:56-57): an 8-frame
512×512 (64×64-latent) video edit with 50 DDIM steps in --fast mode — DDIM
inversion (cond-only) + the attention-controlled CFG denoise with
refine+reweight controllers and LocalBlend — on whatever accelerator is
attached (one TPU v5e chip). Weights are random-init: wall-clock
of the jitted compute is weight-value-independent, and no SD checkpoint ships
in this image.

Prints ONE JSON line to stdout immediately after the fast phase:
  {"metric": "fast_edit_e2e_wall", "value": <seconds>, "unit": "s",
   "vs_baseline": <V100_baseline / ours>,   # >1 ⇒ faster than the reference
   "breakdown": {...per-phase seconds, per-step ms, frames/sec, MFU...}}

Unless ``VIDEOP2P_BENCH_FAST_ONLY=1``, it then also measures null-text
inversion wall-clock (the official mode's dominant phase, README.md:59-60
"~10 min on V100"; a declared metric of record in BASELINE.json), the
official-mode edit, and a Stage-1 tuning step — another ~25 minutes of
compiles and runs — writing the extended breakdown to stderr and
``bench_details.json`` so the primary line survives any harness timeout.
"""

from __future__ import annotations

import json
import math
import os
import shutil
import subprocess
import sys
import tempfile
import time
from typing import NamedTuple

import jax
import jax.numpy as jnp

# TPU executables are content-addressed-cacheable; persisting them across
# bench invocations cuts the multi-minute compile budget (the null-text remat
# grad program alone) out of the driver's timeout window on re-runs.
from videop2p_tpu.cli.common import enable_compile_cache  # noqa: E402
from videop2p_tpu.utils import profiling  # noqa: E402

enable_compile_cache()

V100_FAST_EDIT_S = 60.0  # reference: "~1 min on V100" (README.md:56-57)
V100_OFFICIAL_EDIT_S = 600.0  # reference: "~10 min on V100" (README.md:59-60)
# XLA cost_analysis of the jitted UNet forward (tools/profile_edit.py on
# v5e): 6.56 TF for a cond-only 8-frame batch-1 forward — 0.82 TF per
# frame-forward, linear in streams×frames at this config.
FLOPS_PER_FRAME_FWD = 0.82e12
# bf16 peak per chip; longest-prefix match on device_kind
PEAK_FLOPS = {
    "tpu v5 lite": 197e12,  # v5e
    "tpu v5p": 459e12,
    "tpu v4": 275e12,
    "tpu v6 lite": 918e12,  # v6e (Trillium)
}


def wait_for_backend(
    *,
    attempts: int = 5,
    probe_timeouts_s: tuple = (120.0, 60.0, 60.0, 60.0, 60.0),
    backoffs_s: tuple = (10.0, 20.0, 40.0, 60.0),
    _probe=None,
    _sleep=time.sleep,
) -> bool:
    """Bounded retry until the configured JAX backend is healthy.

    A backend can fail its FIRST device op on a transiently-down chip. The
    probe runs ``jax.devices()`` in a SUBPROCESS, for two reasons: a hung
    backend init blocks forever in-process (a timeout needs a killable
    child), and a *failed* init can be cached by the parent's jax for the
    life of the process, so the parent must only ever attempt it once the
    child has proven the backend healthy. (The child takes the chip and
    releases it before the parent asks: legal, and of no use on a machine
    whose chip is local — see .claude/skills/verify/SKILL.md.)

    Returns True once a probe succeeds; False after ``attempts`` failures.
    Total budget at the defaults: ~2.5 min when the backend FAILS fast
    (five quick rc≠0 probes + 130 s of backoff), ~8 min worst case when it
    HANGS (every probe burns its full timeout: 120+4×60 s + backoff — the
    first probe gets the long leash because a *healthy* cold init can take
    tens of seconds). Either way the bench then still emits its
    machine-readable error line. Never raises.
    """

    def default_probe(timeout_s: float) -> bool:
        try:
            proc = subprocess.run(
                [sys.executable, "-c",
                 "import jax, jax.numpy as jnp; "
                 "jax.block_until_ready(jnp.zeros(8) + 1); "
                 "print(jax.devices()[0].platform)"],
                capture_output=True,
                text=True,
                timeout=timeout_s,
            )
        except (subprocess.TimeoutExpired, OSError):
            return False
        return proc.returncode == 0

    for i in range(attempts):
        if _probe is not None:
            ok = _probe()
        else:
            ok = default_probe(probe_timeouts_s[min(i, len(probe_timeouts_s) - 1)])
        if ok:
            return True
        if i < attempts - 1:
            wait = backoffs_s[min(i, len(backoffs_s) - 1)]
            print(
                f"[bench] backend probe {i + 1}/{attempts} failed — "
                f"retrying in {wait:.0f}s",
                file=sys.stderr,
                flush=True,
            )
            _sleep(wait)
    return False


def emit_backend_unavailable() -> None:
    """The machine-readable record of a bench that could not run: the driver
    parses the single stdout JSON line, so an unreachable backend must still
    produce one (r4 produced only a traceback, leaving parsed:null)."""
    print(
        json.dumps(
            {
                "metric": "fast_edit_e2e_wall",
                "value": None,
                "unit": "s",
                "vs_baseline": None,
                "error": "backend_unavailable",
            }
        ),
        flush=True,
    )


def _peak_flops() -> float:
    kind = jax.devices()[0].device_kind.lower()
    for prefix in sorted(PEAK_FLOPS, key=len, reverse=True):
        if kind.startswith(prefix):
            return PEAK_FLOPS[prefix]
    return float("nan")


def _tools_import(name: str):
    """Import a module from the repo's tools/ directory (bench.py runs as a
    top-level script, so tools/ is reached by path, not package)."""
    tools_dir = os.path.join(os.path.dirname(os.path.abspath(__file__)), "tools")
    if tools_dir not in sys.path:
        sys.path.insert(0, tools_dir)
    import importlib

    return importlib.import_module(name)


def _hard_sync(out) -> None:
    """Fetch real bytes from the output — a barrier an async/early-returning
    dispatch path cannot fake.

    Transferring output VALUES to the host cannot complete until the
    producing programs have actually run.

    ONE leaf's value is fetched: every ``measure_with_floor`` call times a
    single jitted program, whose outputs all come from the same execution —
    one value proves the whole program ran. A per-leaf fetch adds a host
    round trip per leaf (35 on the captured-inversion output) inside the
    timing window it was supposed to protect.
    """
    for leaf in jax.tree.leaves(out):
        if hasattr(leaf, "ravel"):
            float(jnp.asarray(leaf).ravel()[0].astype(jnp.float32))
            return


def hard_block(out):
    """``block_until_ready`` plus the :func:`_hard_sync` value fetch; use for
    warm-ups so no async leftover can bleed into the next measurement."""
    jax.block_until_ready(out)
    _hard_sync(out)
    return out


class Reading(NamedTuple):
    out: object
    seconds: float
    suspect: bool
    source: str  # "wall" | "device_trace"
    x_used: object  # the input of the accepted (or max) attempt
    samples: tuple = ()  # all valid readings, when samples>1 was requested


def measure_with_floor(call, fresh_inputs, floor_s: float, what: str,
                       samples: int = 1) -> Reading:
    """Wall-clock ``call(x)`` and validate it against a physical floor.

    Every attempt ends with a
    :func:`_hard_sync` value fetch, and any reading below ``floor_s`` — the
    MFU=1 bound from the phase's FLOP count — is rejected and re-measured on
    the next fresh input. The LAST attempt runs under ``jax.profiler`` and,
    when its wall-clock is still sub-floor, the summed "XLA Modules"
    device-event time stands in (``tools.profile_xplane.module_device_seconds``:
    the device's own execution records). ``suspect`` is True only when no source cleared the floor — the
    max wall reading is then reported, paired with its own output and input.
    A NaN floor (unknown-peak device) accepts the first reading.

    ``samples > 1``: instead of accepting the FIRST above-floor reading
    (which carries whatever residual first-run bias the warm-up missed),
    keep measuring until ``samples`` valid readings exist (bounded by the
    fresh inputs supplied) and report the MEDIAN one, with every valid
    reading recorded in ``Reading.samples`` — the discard-first /
    report-spread discipline the shard proxy uses, applied to the phases
    of record (VERDICT r4 weak #7).
    """
    best = None  # (out, dt, x) of the max-dt attempt, kept together
    valid = []  # (out, dt, x) of every above-floor attempt (samples mode)
    n = len(fresh_inputs)
    for i, x in enumerate(fresh_inputs):
        # the trace machinery is strictly best-effort: any profiler or parser
        # failure must degrade to the wall reading, never lose the phase;
        # in samples mode a valid reading already exists by the last
        # attempt in the healthy case — don't contaminate it with tracer
        # overhead (the trace is the all-sub-floor forensic path)
        trace_this = i == n - 1 and floor_s == floor_s and not valid
        tdir = None
        try:
            if trace_this:
                try:
                    tdir = tempfile.mkdtemp(prefix="bench_trace_")
                    opts = jax.profiler.ProfileOptions()
                    opts.enable_hlo_proto = False
                    opts.host_tracer_level = 0
                    opts.python_tracer_level = 0
                    jax.profiler.start_trace(tdir, profiler_options=opts)
                except Exception as e:  # noqa: BLE001
                    print(f"[bench] {what}: trace start failed ({e}) — wall only",
                          file=sys.stderr, flush=True)
                    tracing = False
                else:
                    tracing = True
            else:
                tracing = False
            t0 = time.time()
            try:
                out = call(x)
                jax.block_until_ready(out)
                _hard_sync(out)
                dt = time.time() - t0
            finally:
                if tracing:
                    try:
                        jax.profiler.stop_trace()
                    except Exception:  # noqa: BLE001
                        pass
            if best is None or dt > best[1]:
                best = (out, dt, x)
            if floor_s != floor_s or dt >= floor_s:
                if samples <= 1:
                    return Reading(out, dt, False, "wall", x)
                valid.append((out, dt, x))
                if len(valid) >= samples or i == n - 1:
                    valid.sort(key=lambda v: v[1])
                    o, d, xu = valid[len(valid) // 2]
                    return Reading(o, d, False, "wall", xu,
                                   tuple(round(v[1], 3) for v in valid))
                continue
            print(
                f"[bench] {what}: {dt:.3f}s is below the physical floor "
                f"{floor_s:.2f}s — "
                + ("checking the device trace" if tracing
                   else "re-measuring on a fresh input"),
                file=sys.stderr,
                flush=True,
            )
            if tracing:
                try:
                    px = _tools_import("profile_xplane")
                    dev_s = px.module_device_seconds(tdir)
                    span_s = px.module_device_span_seconds(tdir)
                except Exception as e:  # noqa: BLE001
                    print(f"[bench] {what}: device-trace readout failed ({e})",
                          file=sys.stderr, flush=True)
                    dev_s = span_s = 0.0
                if dev_s >= floor_s:
                    # the summed module durations clear the floor (programs
                    # really executed), but overlapping async programs can
                    # make the SUM exceed wall-clock — report the envelope
                    # span (first start → last end), which cannot
                    print(
                        f"[bench] {what}: device trace records {dev_s:.3f}s of "
                        f"program execution over a {span_s:.3f}s span — using "
                        "the span as the reading",
                        file=sys.stderr,
                        flush=True,
                    )
                    if span_s >= floor_s:
                        return Reading(out, span_s, False, "device_trace", x)
                    # the envelope span ITSELF is sub-floor: the sum cleared
                    # the floor only via overlapping programs, so no single
                    # trusted measurement of this phase exists. Report the
                    # span as measured but SUSPECT — substituting the
                    # theoretical floor here would record a number nothing
                    # ever measured (round-4 advisor finding).
                    print(
                        f"[bench] {what}: trace span {span_s:.3f}s is itself "
                        f"below the floor {floor_s:.2f}s — recording the span, "
                        "flagged suspect",
                        file=sys.stderr,
                        flush=True,
                    )
                    return Reading(out, span_s, True, "device_trace", x)
                print(
                    f"[bench] {what}: device trace total {dev_s:.3f}s is also "
                    f"sub-floor — flagging the reading as suspect",
                    file=sys.stderr,
                    flush=True,
                )
        finally:
            if tdir:
                shutil.rmtree(tdir, ignore_errors=True)
    if valid:
        # samples mode, loop exhausted by a sub-floor LAST attempt: the
        # already-collected valid readings are still trustworthy — report
        # their median, not a suspect max-wall (the flake consumed a retry,
        # it must not poison the phase)
        valid.sort(key=lambda v: v[1])
        o, d, xu = valid[len(valid) // 2]
        return Reading(o, d, False, "wall", xu,
                       tuple(round(v[1], 3) for v in valid))
    return Reading(best[0], best[1], True, "wall", best[2])


class DetailsRecorder:
    """Incrementally-persisted extended-bench record.

    Every ``record()`` rewrites ``bench_details.json`` atomically, so a
    driver timeout mid-run can never again lose already-measured phases
    (round 2 lost all extended numbers to an end-only write + rc=124).
    """

    def __init__(self, path: str, breakdown: dict, suspect: list):
        self.path = path
        self.breakdown = breakdown
        self.suspect = suspect
        # seed from the existing record so a partial run (fast-only, or a
        # timeout before a later phase) never erases phases measured by a
        # previous run; inherited keys are flagged until re-measured
        if os.path.exists(path):
            try:
                with open(path) as f:
                    old = json.load(f).get("breakdown", {})
            except (OSError, ValueError):
                old = {}
            old_suspect = old.pop("suspect_measurements", [])
            old.pop("stale_from_previous_run", None)
            for key, value in old.items():
                self.breakdown.setdefault(key, value)
            self.suspect.extend(k for k in old_suspect if k not in self.suspect)
            self.stale = [k for k in old if k not in ("device", "measurement_sources")]
        else:
            self.stale = []

    def _freshen(self, key: str):
        if key in self.stale:
            self.stale.remove(key)
        if key in self.suspect:
            self.suspect.remove(key)

    def record(self, key: str, value, *, reading: Reading | None = None,
               derived: tuple = ()):
        """``reading``: the measurement behind a directly-measured key.
        ``derived``: the Readings a computed key was built from — a value
        derived from an untrusted constituent is itself untrusted."""
        self._freshen(key)
        self.breakdown[key] = value
        self.breakdown.get("measurement_sources", {}).pop(key, None)
        if reading is not None:
            if reading.suspect:
                self.suspect.append(key)
            if reading.source != "wall":
                self.breakdown.setdefault("measurement_sources", {})[key] = reading.source
        if any(r.suspect for r in derived):
            self.suspect.append(key)
        self.flush()

    def drop(self, key: str):
        """Remove a (possibly inherited) key — e.g. a previous run's
        ``extended_error`` once the extended phases complete cleanly."""
        self.breakdown.pop(key, None)
        self.breakdown.get("measurement_sources", {}).pop(key, None)
        self._freshen(key)
        self.flush()

    def flush(self):
        if self.suspect:
            self.breakdown["suspect_measurements"] = self.suspect
        else:
            self.breakdown.pop("suspect_measurements", None)
        if self.stale:
            self.breakdown["stale_from_previous_run"] = self.stale
        else:
            self.breakdown.pop("stale_from_previous_run", None)
        details = {
            "extended_of": "fast_edit_e2e_wall",
            "measured_at": time.strftime("%Y-%m-%dT%H:%M:%SZ", time.gmtime()),
            "breakdown": self.breakdown,
        }
        tmp = self.path + ".tmp"
        with open(tmp, "w") as f:
            json.dump(details, f, indent=2)
        os.replace(tmp, self.path)
        return details


def ledger_bench_fields(ledger_path, compile_seconds, execute_s=None):
    """Schema-stable ledger/compile fields for the bench breakdown.

    ``compile_seconds``: the per-event XLA backend-compile durations the run
    ledger captured (``RunLedger.compile_seconds``). ``execute_s``: the
    headline measured execution, so the record carries the compile-vs-execute
    split explicitly — three rounds of perf claims were builder-recorded
    only, and this is the machine-readable provenance VERDICT r5 asked for.
    Pure + CPU-tested (tests/test_bench_guard.py) so the shape cannot drift.
    """
    compile_seconds = [float(s) for s in (compile_seconds or [])]
    total = round(sum(compile_seconds), 3)
    return {
        "ledger_path": ledger_path,
        "compile_events": len(compile_seconds),
        "compile_total_s": total,
        "execute_headline_s": (
            None if execute_s is None else round(float(execute_s), 3)
        ),
        "compile_vs_execute": (
            None if not execute_s else round(total / float(execute_s), 2)
        ),
    }


def collect_cpu_analysis(frames, steps, *, timeout_s=900.0, tiny=False,
                         ledger_path=None, programs=None):
    """Run ``tools/cpu_cost_capture.py`` in a SUBPROCESS and parse its
    per-program JSON lines into ``{program: analysis_record}``.

    A subprocess for the same reason as :func:`wait_for_backend`'s probe:
    this runs when the parent's configured backend is DOWN, and the
    parent's jax may hold a poisoned/hung backend init — the child pins
    ``jax_platforms=cpu`` before any device use. The tool flushes one line
    per program, so a timeout keeps every program that finished (partial
    evidence beats none — the whole point of this path). Never raises.
    """
    repo = os.path.dirname(os.path.abspath(__file__))
    cmd = [sys.executable, os.path.join(repo, "tools", "cpu_cost_capture.py"),
           "--frames", str(frames), "--steps", str(steps)]
    if tiny:
        cmd.append("--tiny")
    if ledger_path:
        cmd += ["--ledger", ledger_path]
    if programs:
        cmd += ["--programs", ",".join(programs)]
    env = dict(os.environ)
    env["PYTHONPATH"] = repo + os.pathsep + env.get("PYTHONPATH", "")
    stdout = ""
    try:
        proc = subprocess.run(cmd, capture_output=True, text=True,
                              timeout=timeout_s, env=env)
        stdout = proc.stdout or ""
        if proc.returncode != 0:
            print(f"[bench] cpu cost capture rc={proc.returncode}: "
                  f"{(proc.stderr or '')[-300:]}", file=sys.stderr, flush=True)
    except subprocess.TimeoutExpired as e:
        stdout = (e.stdout.decode() if isinstance(e.stdout, bytes)
                  else e.stdout) or ""
        print(f"[bench] cpu cost capture timed out after {timeout_s:.0f}s — "
              "keeping the programs that finished", file=sys.stderr, flush=True)
    except OSError as e:
        print(f"[bench] cpu cost capture failed to launch: {e}",
              file=sys.stderr, flush=True)
    out = {}
    for line in stdout.splitlines():
        try:
            rec = json.loads(line)
        except ValueError:
            continue
        if isinstance(rec, dict) and rec.get("program"):
            out[rec.pop("program")] = rec
    return out


def load_analysis_baseline(repo_dir):
    """(baseline ``{program: analysis}``, source name) for the regression
    verdicts: a ``program_analysis`` section in BASELINE.json wins (the
    declared budget); else the PREVIOUS bench_details.json record (the
    cross-run check); else (None, None) — first capture, nothing to diff."""
    for fname, key in (("BASELINE.json", "program_analysis"),
                       ("bench_details.json", None)):
        path = os.path.join(repo_dir, fname)
        try:
            with open(path) as f:
                doc = json.load(f)
        except (OSError, ValueError):
            continue
        section = (doc.get(key) if key
                   else doc.get("breakdown", {}).get("program_analysis"))
        if isinstance(section, dict) and section:
            return section, fname
    return None, None


def bench_analysis_verdicts(analyses, baseline_analyses, source):
    """Machine-readable regression verdicts of this run's program analyses
    against a baseline set (obs/history.py DEFAULT_RULES, program rules
    only — there are no phases/compiles in these records). Pure +
    CPU-tested so the verdict schema cannot drift."""
    from videop2p_tpu.obs.history import evaluate_rules

    empty = {"phases": {}, "compiles": {}, "dispatch": {}}
    res = evaluate_rules({"programs": baseline_analyses or {}, **empty},
                         {"programs": analyses or {}, **empty})
    return {
        "baseline": source,
        "compared_programs": sorted(set(baseline_analyses or {})
                                    & set(analyses or {})),
        "pass": res["pass"],
        "regressions": res["regressions"],
    }


def record_program_analyses(rec, analyses, *, backend, baseline_dir=None):
    """Persist ``{program: analysis}`` into the bench breakdown and attach
    regression verdicts vs the baseline (BASELINE.json section or the
    previous bench_details.json record — read BEFORE this record lands).
    Returns the verdict object (also printed to stderr on regression)."""
    if not analyses:
        return None
    baseline_dir = baseline_dir or os.path.dirname(os.path.abspath(__file__))
    baseline, source = load_analysis_baseline(baseline_dir)
    rec.record("program_analysis", analyses)
    rec.record("program_analysis_backend", backend)
    verdicts = bench_analysis_verdicts(analyses, baseline, source)
    rec.record("analysis_verdicts", verdicts)
    if not verdicts["pass"]:
        print("[bench] PROGRAM-ANALYSIS REGRESSIONS vs "
              f"{source}: " + "; ".join(
                  f"{v['program']} {v['rule']} {v['base']}→{v['new']}"
                  for v in verdicts["regressions"]),
              file=sys.stderr, flush=True)
    return verdicts


def official_e2e_records(inv_s, edit_s, *, null_fp32_s=None, null_mixed_s=None,
                         null_amortized_s=None, null_hybrid_s=None,
                         inner_steps=None, baseline_s=V100_OFFICIAL_EDIT_S):
    """The official-mode e2e record schema across the null-text variants
    (precision: fp32/mixed; mode: amortized/hybrid — ISSUE 8): each variant
    carries its e2e seconds and vs-V100-baseline ratio, the Adam-loop
    precisions additionally their per-inner-step ms (the amortized mode has
    ZERO inner Adam steps — a per-inner-step figure would be meaningless).
    Any constituent may be None (off-TPU, or a variant not measured this
    run) — the keys are still emitted with null values so the record SHAPE
    is stable and machine-readable (tests/test_null_text_precision.py
    exercises the schema on CPU)."""

    def e2e(null_s):
        if inv_s is None or edit_s is None or null_s is None:
            return None
        return round(inv_s + null_s + edit_s, 3)

    def per_inner(null_s):
        if null_s is None or not inner_steps:
            return None
        return round(null_s / inner_steps * 1e3, 1)

    def vs(null_s):
        total = e2e(null_s)
        return None if total is None else round(baseline_s / total, 2)

    return {
        "official_edit_e2e_fp32_s": e2e(null_fp32_s),
        "official_edit_e2e_mixed_s": e2e(null_mixed_s),
        "official_edit_e2e_amortized_s": e2e(null_amortized_s),
        "official_edit_e2e_hybrid_s": e2e(null_hybrid_s),
        "null_text_inner_step_fp32_ms": per_inner(null_fp32_s),
        "null_text_inner_step_mixed_ms": per_inner(null_mixed_s),
        "official_vs_baseline_fp32": vs(null_fp32_s),
        "official_vs_baseline_mixed": vs(null_mixed_s),
        "official_vs_baseline_amortized": vs(null_amortized_s),
        "official_vs_baseline_hybrid": vs(null_hybrid_s),
    }


# the official CLI defaults the flop accounting below is stated at:
# 50 outer steps × 10 inner Adam steps (run_videop2p.py), hybrid K=3
NULL_TEXT_FLOP_DEFAULTS = dict(num_steps=50, num_inner_steps=10,
                               hybrid_inner_steps=3)


def null_text_flop_records(unit_fwd_flops, unit_inner_flops, *,
                           num_steps=50, num_inner_steps=10,
                           hybrid_inner_steps=3):
    """Total inner-loop flops per null-text mode, from the two STRAIGHT-LINE
    unit analyses (``null_text_unit_fwd`` = one UNet forward,
    ``null_text_unit_inner`` = one inner Adam iteration: loss forward +
    backward + update — tools/cpu_cost_capture.py builds both).

    XLA's ``cost_analysis`` counts a ``scan``/``while`` body ONCE (the
    static-count convention docs/PERF_ANALYSIS.md discloses), so the fused
    null-text programs' own analyses cannot be compared across modes — the
    optimize mode hides 50×10 inner iterations inside loops while the
    hybrid mode materializes its step batch. The unit programs contain no
    loops, so their static counts ARE their true flops; the per-mode totals
    then follow from the loop structure, which is exact and disclosed:

      optimize  = N·(2·fwd + I·inner)   (cond + final-uncond forwards, I
                                         inner Adam iterations per step)
      amortized = N·fwd                 (closed form: one forward per step)
      hybrid    = N·(fwd + K·inner)     (cond forward + K joint iterations)

    Returns the machine-readable record bench_details.json carries,
    including the ≥3× reduction ratios the ISSUE-8 acceptance gates (with
    I=10, K=3 the hybrid ratio is ≥3 for ANY inner/fwd cost ratio ≥1)."""
    f, i = float(unit_fwd_flops), float(unit_inner_flops)
    n = int(num_steps)
    opt = n * (2 * f + num_inner_steps * i)
    amo = n * f
    hyb = n * (f + hybrid_inner_steps * i)
    return {
        "null_text_unit_fwd_flops": f,
        "null_text_unit_inner_flops": i,
        "null_text_flop_params": {
            "num_steps": n, "num_inner_steps": int(num_inner_steps),
            "hybrid_inner_steps": int(hybrid_inner_steps),
        },
        "null_text_total_flops_optimize": opt,
        "null_text_total_flops_amortized": amo,
        "null_text_total_flops_hybrid": hyb,
        "null_text_flops_reduction_amortized": round(opt / amo, 2),
        "null_text_flops_reduction_hybrid": round(opt / hyb, 2),
    }


def record_null_text_flops(rec, *, tiny=False, timeout_s=None,
                           frames=None, steps=None) -> None:
    """Capture the two null-text unit analyses (CPU subprocess — flop
    counts are backend-independent and need no healthy accelerator) and
    persist the per-mode totals + reduction ratios. Best-effort: a failed
    capture records nothing rather than killing the round."""
    timeout_s = timeout_s if timeout_s is not None else float(os.environ.get(
        "VIDEOP2P_BENCH_CPU_ANALYSIS_TIMEOUT", "900"))
    analyses = collect_cpu_analysis(
        frames if frames is not None else BENCH_FRAMES,
        steps if steps is not None else BENCH_STEPS,
        timeout_s=timeout_s, tiny=tiny,
        programs=("null_text_unit_fwd", "null_text_unit_inner"),
    )
    fwd = analyses.get("null_text_unit_fwd", {}).get("flops")
    inner = analyses.get("null_text_unit_inner", {}).get("flops")
    if not fwd or not inner:
        print("[bench] null-text unit flop capture incomplete "
              f"(have {sorted(analyses)}) — skipping the mode flop record",
              file=sys.stderr, flush=True)
        return
    for k, v in null_text_flop_records(
        fwd, inner, **NULL_TEXT_FLOP_DEFAULTS
    ).items():
        rec.record(k, v)


# the measured-scale-out evidence grid (ISSUE 10): ring comm+flop records
# per frame count over this many sequence shards, plus the Megatron tp
# pairing — static XLA counts, backend-independent, captured every round
FRAME_SCALING_COUNTS = (8, 32, 64)
FRAME_SCALING_SHARDS = 8
# schema-stable per-record field set (tests/test_bench_guard.py pins it)
FRAME_SCALING_FIELDS = (
    "frames", "shards", "variant", "collective_permute_count",
    "collective_permute_bytes", "bytes_per_permute", "flops",
    "permute_count_vs_serial", "permute_bytes_vs_serial",
)
TP_PAIRING_FIELDS = (
    "shards", "all_reduce_bytes", "reduce_scatter_bytes",
    "bytes_reduction", "flops",
)


def frame_scaling_records(analyses, *, shards=FRAME_SCALING_SHARDS):
    """Per-frame-count ring comm/flop records from the
    ``ring_unit_<variant>_f<F>`` unit analyses
    (tools/cpu_cost_capture.py): one record per (frames, variant) with the
    TRUE static collective-permute counts (the rotation loop is unrolled —
    parallel/ring.py) and the vs-serial ratios that state the engineered
    win machine-readably (overlap: (n−1)/n counts AND bytes; bidir: same
    bytes at half the per-permute payload). Pure + CPU-tested so the
    record shape cannot drift; every record carries exactly
    ``FRAME_SCALING_FIELDS``."""
    by_frames = {}
    for name, a in (analyses or {}).items():
        if not isinstance(a, dict) or not name.startswith("ring_unit_"):
            continue
        variant, _, fpart = name[len("ring_unit_"):].rpartition("_f")
        if not variant or not fpart.isdigit():
            continue
        by_frames.setdefault(int(fpart), {})[variant] = a
    records = []
    for frames in sorted(by_frames):
        variants = by_frames[frames]
        serial = variants.get("serial") or {}
        s_count = int(serial.get("collective_permute_count") or 0)
        s_bytes = int(serial.get("collective_permute_bytes") or 0)
        for variant in ("serial", "overlap", "bidir"):
            a = variants.get(variant)
            if a is None:
                continue
            count = int(a.get("collective_permute_count") or 0)
            nbytes = int(a.get("collective_permute_bytes") or 0)
            records.append({
                "frames": frames,
                "shards": int(a.get("shards") or shards),
                "variant": variant,
                "collective_permute_count": count,
                "collective_permute_bytes": nbytes,
                "bytes_per_permute": (nbytes // count) if count else None,
                "flops": a.get("flops"),
                "permute_count_vs_serial": (
                    round(count / s_count, 3) if s_count else None
                ),
                "permute_bytes_vs_serial": (
                    round(nbytes / s_bytes, 3) if s_bytes else None
                ),
            })
    return records


def tp_pairing_record(analyses, *, shards=FRAME_SCALING_SHARDS):
    """The Megatron pairing evidence from the ``tp_unit_{gspmd,scatter}``
    unit analyses: declarative all-reduce result bytes vs the explicit
    ``psum_scatter`` seam's reduce-scatter bytes (= all-reduce ÷ tp).
    None when either unit is missing; carries exactly
    ``TP_PAIRING_FIELDS``."""
    g = (analyses or {}).get("tp_unit_gspmd")
    s = (analyses or {}).get("tp_unit_scatter")
    if not isinstance(g, dict) or not isinstance(s, dict):
        return None
    ar = int(g.get("all_reduce_bytes") or 0)
    rs = int(s.get("reduce_scatter_bytes") or 0)
    return {
        "shards": int(g.get("shards") or shards),
        "all_reduce_bytes": ar,
        "reduce_scatter_bytes": rs,
        "bytes_reduction": round(ar / rs, 2) if rs else None,
        "flops": g.get("flops"),
    }


def record_frame_scaling(rec, *, timeout_s=None,
                         frame_counts=FRAME_SCALING_COUNTS,
                         shards=FRAME_SCALING_SHARDS) -> None:
    """Capture the ring/tp unit analyses (CPU subprocess — static comm
    counts and flops are backend-independent) and persist the
    per-frame-count scale-out records. Best-effort: a failed capture
    records nothing rather than killing the round."""
    timeout_s = timeout_s if timeout_s is not None else float(os.environ.get(
        "VIDEOP2P_BENCH_CPU_ANALYSIS_TIMEOUT", "900"))
    programs = [f"ring_unit_{v}_f{f}" for f in frame_counts
                for v in ("serial", "overlap", "bidir")]
    programs += ["tp_unit_gspmd", "tp_unit_scatter"]
    analyses = collect_cpu_analysis(
        BENCH_FRAMES, BENCH_STEPS, timeout_s=timeout_s, programs=programs,
    )
    records = frame_scaling_records(analyses, shards=shards)
    if not records:
        print("[bench] frame-scaling unit capture incomplete "
              f"(have {sorted(analyses)}) — skipping the record",
              file=sys.stderr, flush=True)
        return
    rec.record("frame_scaling", records)
    rec.record("frame_scaling_backend", "cpu-static")
    tp = tp_pairing_record(analyses, shards=shards)
    if tp is not None:
        rec.record("tp_pairing", tp)


# the streaming long-video evidence grid (ISSUE 12, ROADMAP item 5): the
# windowed tier's static cost model past the 64-frame sharded ceiling —
# window counts, overlap-redundancy overhead, total flops (one window's
# measured analysis × window count) and the content-addressed store
# footprint per window, at the minute-of-footage frame counts. The
# per-window numbers ARE the streaming claim: device residency and store
# bytes stay flat per window while total work grows linearly.
STREAMING_FRAME_COUNTS = (128, 480)
STREAMING_OVERLAP = 2
# schema-stable per-record field set (tests/test_bench_guard.py pins it)
STREAMING_WINDOW_FIELDS = (
    "total_frames", "window", "overlap", "stride", "windows",
    "frames_processed", "overlap_overhead", "flops_per_window",
    "flops_total", "store_bytes_per_window", "store_bytes_total",
)


def streaming_window_records(analyses, *, frame_counts=STREAMING_FRAME_COUNTS,
                             window=None, overlap=STREAMING_OVERLAP,
                             steps=None, latent_size=64):
    """Per-total-frame-count streaming plan records
    (``videop2p_tpu.stream.windows.streaming_plan_record``): the window
    plan is the SAME pure planner the streaming driver executes, so the
    recorded window counts are the counts a real job runs.
    ``flops_per_window`` comes from the ``e2e_cached`` analysis (the
    full invert+edit pipeline at exactly one window's frame count — the
    headline capture's geometry) and scales linearly to ``flops_total``;
    None when the capture is incomplete. Every record carries exactly
    ``STREAMING_WINDOW_FIELDS``; pure + CPU-tested so the shape cannot
    drift."""
    from videop2p_tpu.stream.windows import streaming_plan_record

    window = int(window) if window else BENCH_FRAMES
    steps = int(steps) if steps else BENCH_STEPS
    flops = None
    a = (analyses or {}).get("e2e_cached")
    if isinstance(a, dict) and a.get("flops"):
        flops = float(a["flops"])
    return [
        streaming_plan_record(
            total, window, overlap, steps=steps, latent_size=latent_size,
            flops_per_window=flops,
        )
        for total in frame_counts
    ]


def record_streaming_scaling(rec, *, analyses=None, timeout_s=None) -> None:
    """Persist the streaming-window evidence (``streaming_scaling``) —
    every round, backend up or down. ``analyses`` reuses an already-run
    CPU capture (record_cpu_only_evidence hands its own in); absent that,
    one ``e2e_cached`` unit capture runs in the bounded subprocess.
    Best-effort: a failed capture still records the plan geometry (window
    counts and store bytes are static host math), with flops fields
    None."""
    if analyses is None or "e2e_cached" not in analyses:
        timeout_s = timeout_s if timeout_s is not None else float(
            os.environ.get("VIDEOP2P_BENCH_CPU_ANALYSIS_TIMEOUT", "900"))
        analyses = collect_cpu_analysis(
            BENCH_FRAMES, BENCH_STEPS, timeout_s=timeout_s,
            programs=("e2e_cached",),
        )
    try:
        records = streaming_window_records(analyses)
    except Exception as e:  # noqa: BLE001 — evidence is best-effort, never kills a round
        print(f"[bench] streaming-window record failed: {e}",
              file=sys.stderr, flush=True)
        return
    rec.record("streaming_scaling", records)
    rec.record("streaming_scaling_backend", "cpu-static")


# the per-UNet-call cost evidence (ISSUE 15): quantization shrinks the
# bytes a call must move (argument_bytes IS the weight footprint — int8
# weights enter the program as 1-byte inputs and upcast inside the
# trace), reuse shrinks the flops a K-step span must spend (shallow
# steps skip the down/mid stack). Both claims come from loop-free
# straight-line unit programs (tools/cpu_cost_capture.py
# ``unet_unit_{fp,w8,w8a8}`` / ``reuse_unit_<K>``) because XLA's static
# cost analysis counts scan bodies once and lax.cond both-branches —
# the fused edit scan can't testify for either knob.
PER_CALL_COST_KS = (2, 5)
# schema-stable per-record field set (tests/test_bench_guard.py pins it)
PER_CALL_COST_FIELDS = (
    "program", "quant_mode", "reuse_schedule", "calls", "flops",
    "bytes_accessed", "argument_bytes", "peak_hbm_bytes",
    "flops_vs_full", "bytes_vs_full", "argument_bytes_vs_full",
)


def per_call_cost_records(analyses):
    """Per-variant UNet-call cost records from the ``unet_unit_*`` /
    ``reuse_unit_<K>`` unit analyses: each row normalizes its static
    flops / bytes-accessed / argument-bytes against the SAME number of
    full-precision full-path calls (``calls`` × ``unet_unit_fp`` for
    flops/bytes; 1× for argument_bytes — weights are passed once however
    many steps read them). ``unet_unit_fp`` missing → the vs-full ratios
    are None; no unit analyses at all → ``[]``. Pure + CPU-tested so the
    record shape cannot drift; every record carries exactly
    ``PER_CALL_COST_FIELDS``."""
    fp = (analyses or {}).get("unet_unit_fp")
    fp_flops = float(fp["flops"]) if isinstance(fp, dict) and fp.get(
        "flops") else None
    fp_bytes = float(fp["bytes_accessed"]) if isinstance(fp, dict) and fp.get(
        "bytes_accessed") else None
    fp_args = float(fp["argument_bytes"]) if isinstance(fp, dict) and fp.get(
        "argument_bytes") else None

    def row(name, a, *, quant_mode, reuse_schedule, calls):
        flops = a.get("flops")
        nbytes = a.get("bytes_accessed")
        args = a.get("argument_bytes")
        return {
            "program": name,
            "quant_mode": quant_mode,
            "reuse_schedule": reuse_schedule,
            "calls": calls,
            "flops": flops,
            "bytes_accessed": nbytes,
            "argument_bytes": args,
            "peak_hbm_bytes": a.get("peak_hbm_bytes"),
            "flops_vs_full": (
                round(float(flops) / (calls * fp_flops), 3)
                if flops and fp_flops else None
            ),
            "bytes_vs_full": (
                round(float(nbytes) / (calls * fp_bytes), 3)
                if nbytes and fp_bytes else None
            ),
            "argument_bytes_vs_full": (
                round(float(args) / fp_args, 3)
                if args and fp_args else None
            ),
        }

    records = []
    for name, qm in (("unet_unit_fp", "off"), ("unet_unit_w8", "w8"),
                     ("unet_unit_w8a8", "w8a8")):
        a = (analyses or {}).get(name)
        if isinstance(a, dict):
            records.append(row(name, a, quant_mode=qm,
                               reuse_schedule="off", calls=1))
    reuse = []
    for name, a in (analyses or {}).items():
        if (isinstance(a, dict) and name.startswith("reuse_unit_")
                and name[len("reuse_unit_"):].isdigit()):
            reuse.append((int(name[len("reuse_unit_"):]), name, a))
    for k, name, a in sorted(reuse):
        records.append(row(name, a, quant_mode="off",
                           reuse_schedule=f"uniform:{k}", calls=k))
    # the student cost units (ISSUE 16): distill_unit_fp is ONE student
    # forward (UNet + time head), so its flops_vs_full IS the head's
    # overhead ratio over the teacher forward; distill_unit_<N> is an
    # N-step loop-free student walk, so flops_vs_full against N teacher
    # calls isolates the per-step student-vs-teacher flop ratio — the
    # latency claim "2-step student ≈ 2/50 of the teacher walk" rests on
    # this landing every round, even backend_unavailable
    d = (analyses or {}).get("distill_unit_fp")
    if isinstance(d, dict):
        records.append(row("distill_unit_fp", d, quant_mode="off",
                           reuse_schedule="off", calls=1))
    distill = []
    for name, a in (analyses or {}).items():
        if (isinstance(a, dict) and name.startswith("distill_unit_")
                and name[len("distill_unit_"):].isdigit()):
            distill.append((int(name[len("distill_unit_"):]), name, a))
    for n, name, a in sorted(distill):
        records.append(row(name, a, quant_mode="off",
                           reuse_schedule="off", calls=n))
    return records


def record_per_call_cost(rec, *, timeout_s=None, ks=PER_CALL_COST_KS) -> None:
    """Capture the per-call quant/reuse unit analyses (CPU subprocess —
    static flop/byte counts are backend-independent) and persist the
    normalized cost records (``per_call_cost``). Best-effort: an
    incomplete capture records nothing rather than killing the round."""
    timeout_s = timeout_s if timeout_s is not None else float(os.environ.get(
        "VIDEOP2P_BENCH_CPU_ANALYSIS_TIMEOUT", "900"))
    programs = ["unet_unit_fp", "unet_unit_w8", "unet_unit_w8a8"]
    programs += [f"reuse_unit_{int(k)}" for k in ks]
    # student units (ISSUE 16): one student forward + a 2-step student walk
    programs += ["distill_unit_fp", "distill_unit_2"]
    analyses = collect_cpu_analysis(
        BENCH_FRAMES, BENCH_STEPS, timeout_s=timeout_s, programs=programs,
    )
    records = per_call_cost_records(analyses)
    if not records:
        print("[bench] per-call cost unit capture incomplete "
              f"(have {sorted(analyses)}) — skipping the record",
              file=sys.stderr, flush=True)
        return
    rec.record("per_call_cost", records)
    rec.record("per_call_cost_backend", "cpu-static")


# the cost-plane evidence (ISSUE 19): the bench round's program analyses
# run through the SAME CostModel the serving engine prices dispatches
# with, so every round — including backend-down rounds, where the
# analyses come from the cpu_cost_capture subprocess — records the cost
# plane's static inputs and (when the backend was up) the achieved
# flops/s against them. Schema pinned by tests/test_bench_guard.py.
BENCH_COST_FIELDS = (
    "program", "flops", "argument_bytes", "peak_hbm_bytes",
    "measured_s", "achieved_flops_per_s",
)


def bench_cost_records(analyses, measured=None):
    """Per-program static cost vectors through
    :class:`videop2p_tpu.obs.cost.CostModel` (the serving engine's
    pricing model), joined with this round's measured headline seconds
    when the backend executed them. ``measured`` absent/None → the
    static columns alone (the backend-down shape). Pure + CPU-tested;
    every record carries exactly ``BENCH_COST_FIELDS``."""
    from videop2p_tpu.obs.cost import CostModel

    model = CostModel()
    rows = []
    for program in sorted(analyses or {}):
        a = analyses[program]
        if not isinstance(a, dict):
            continue
        model.observe_program(str(program), a)
        st = model.static_cost(str(program))
        if not st:
            continue
        s = (measured or {}).get(program)
        s = float(s) if isinstance(s, (int, float)) and s > 0 else None
        flops = st.get("flops")
        rows.append({
            "program": str(program),
            "flops": flops,
            "argument_bytes": st.get("argument_bytes"),
            "peak_hbm_bytes": st.get("peak_hbm_bytes"),
            "measured_s": None if s is None else round(s, 3),
            "achieved_flops_per_s": (
                round(float(flops) / s, 3) if s and flops else None),
        })
    return rows


def record_bench_costs(rec, analyses, *, measured=None,
                       backend="cpu-static") -> None:
    """Persist the cost-plane evidence (``cost_model``) — every round,
    backend up or down. Best-effort: no analyses records nothing rather
    than killing the round."""
    records = bench_cost_records(analyses, measured)
    if not records:
        return
    rec.record("cost_model", records)
    rec.record("cost_model_backend", backend)


def build_fast_edit_working_point(*, num_frames: int = 8, num_steps: int = 50,
                                  frame_attention: str = "auto",
                                  group_norm: str = "auto",
                                  cached: bool = False,
                                  temporal_maps_dtype=None):
    """The reference's headline scenario, shared by the bench phases and the
    xplane profiler (tools/profile_xplane.py): rabbit-jump-p2p refine +
    reweight + LocalBlend at ``num_frames`` × 64×64 latents, ``num_steps``
    DDIM, fast mode.

    Returns a namespace with the jitted ``invert``/``edit`` plus every
    intermediate the extended phases need (fn, params, sched, ctx, cond,
    uncond, x0, x_warm, base key). Inputs are seeded from runtime entropy,
    and the warm-up input differs from the measured one.

    ``cached=True`` additionally builds the cached-source pair
    (``invert_captured``/``edit_cached``, pipelines/cached.py): capture
    windows follow the CLI's gate rule (cross 0.2 → 10 steps, self 0.5 →
    (0, 25) at 50 steps; ~3.1 GiB of maps at 8 frames).
    """
    from types import SimpleNamespace

    from videop2p_tpu.control import make_controller
    from videop2p_tpu.core import DDIMScheduler
    from videop2p_tpu.models import UNet3DConditionModel, UNet3DConfig
    from videop2p_tpu.pipelines import (
        ddim_inversion,
        ddim_inversion_captured,
        edit_sample,
        make_unet_fn,
    )
    from videop2p_tpu.utils.tokenizers import WordTokenizer

    model = UNet3DConditionModel(
        config=UNet3DConfig.sd15(frame_attention=frame_attention,
                                 group_norm=group_norm),
        dtype=jnp.bfloat16,
    )
    base = jax.random.key(time.time_ns() % (2**31))
    k0, k1, k2, k7 = jax.random.split(base, 4)
    x0 = jax.random.normal(k0, (1, num_frames, 64, 64, 4), jnp.bfloat16)
    cond = jax.random.normal(k1, (2, 77, 768), jnp.bfloat16)
    uncond = jnp.zeros((77, 768), jnp.bfloat16)
    params = jax.jit(model.init)(k2, x0[:, :8], jnp.asarray(10), cond[:1])
    # bf16 weights: halves HBM and skips the per-use f32→bf16 kernel converts
    # (wall-clock is weight-value-independent; no f32 masters needed here)
    params = jax.tree.map(lambda x: x.astype(jnp.bfloat16), params)
    fn = make_unet_fn(model)
    sched = DDIMScheduler.create_sd()
    # rabbit-jump-p2p working point: refine + reweight + LocalBlend
    # (configs/rabbit-jump-p2p.yaml)
    ctx = make_controller(
        ["a rabbit is jumping on the grass",
         "a origami rabbit is jumping on the grass"],
        WordTokenizer(),
        num_steps=num_steps,
        is_replace_controller=False,
        cross_replace_steps=0.2,
        self_replace_steps=0.5,
        blend_words=(["rabbit"], ["rabbit"]),
        equalizer_params={"words": ["origami"], "values": [2.0]},
    )
    invert = jax.jit(
        lambda p, x: ddim_inversion(
            fn, p, sched, x, cond[:1], num_inference_steps=num_steps
        )
    )
    edit = jax.jit(
        lambda p, xt: edit_sample(
            fn, p, sched, xt, cond, uncond,
            num_inference_steps=num_steps, ctx=ctx, source_uses_cfg=False,
        )
    )
    x_warm = jax.random.normal(k7, x0.shape, x0.dtype)

    invert_captured = edit_cached = e2e_cached = None
    if cached:
        from videop2p_tpu.pipelines.cached import capture_windows

        cross_len, self_window = capture_windows(ctx, num_steps)
        invert_captured = jax.jit(
            lambda p, x: ddim_inversion_captured(
                fn, p, sched, x, cond[:1], num_inference_steps=num_steps,
                cross_len=cross_len, self_window=self_window, capture_blend=True,
                temporal_maps_dtype=temporal_maps_dtype,
            )
        )
        edit_cached = jax.jit(
            lambda p, xt, cch: edit_sample(
                fn, p, sched, xt, cond, uncond,
                num_inference_steps=num_steps, ctx=ctx, source_uses_cfg=False,
                cached_source=cch,
            )
        )

        # the CLI's actual cached fast path: the SHARED fused program
        # (pipelines.cached_fast_edit — cli/run_videop2p.py jits the same
        # function), so the benchmarked program cannot drift from the one
        # users run; one host dispatch, capture trees never leave the device
        from videop2p_tpu.pipelines import cached_fast_edit

        e2e_cached = jax.jit(
            lambda p, x: cached_fast_edit(
                fn, p, sched, x, cond[:1], cond, uncond, ctx,
                num_inference_steps=num_steps,
                cross_len=cross_len, self_window=self_window,
                temporal_maps_dtype=temporal_maps_dtype,
            )[1]
        )

    return SimpleNamespace(
        invert=invert, edit=edit, fn=fn, params=params, sched=sched, ctx=ctx,
        cond=cond, uncond=uncond, x0=x0, x_warm=x_warm, base=base,
        invert_captured=invert_captured, edit_cached=edit_cached,
        e2e_cached=e2e_cached,
    )


def run_step_frontier(fn, params, sched, cond, uncond, x0, *,
                      base_steps=50, step_counts=(50, 20, 8), timed=True,
                      guidance_scale=7.5, variants=(), student_head=None):
    """The latency-vs-quality step frontier (ISSUE 8 / ROADMAP item 3):
    from ONE ``base_steps`` captured inversion, run the cached fast edit at
    every requested step count via exact timestep-subset schedules
    (core/ddim.py ``subset_positions``) and score each variant against the
    base-steps edit with the obs/quality metrics (PSNR / SSIM /
    background-preservation outside the capture's LocalBlend mask /
    adjacent-frame consistency). The source replay stays EXACT at every
    step count (``src_err`` must read 0.0 — stream 0 is the trajectory's
    x_0 by construction, steps or no steps).

    ``variants``: extra ``(quant_mode, reuse_schedule)`` rows (ISSUE 15) —
    each runs the SAME cached edit at ``base_steps`` with int8
    weight-quantized params (``models/convert.quantize_unet_params``,
    dequantized inside the trace) and/or a DeepCache reuse schedule
    (``pipelines/reuse.py``), scored against the full-precision full-step
    edit exactly like the subset rows. ``quant_mode`` here is limited to
    ``off``/``w8`` (the a8 activation seam needs the model rebuilt with
    ``act_quant_fn`` — that evidence comes from the ``unet_unit_w8a8``
    cost unit instead). The source replay must stay exact under BOTH
    knobs: stream 0 is replayed from the cached trajectory, never
    recomputed, so ``src_err`` reads 0.0 regardless of eps precision.

    A variant may also be a 3-tuple ``(student_steps, quant_mode,
    reuse_schedule)`` (ISSUE 16): the consistency-distilled student row —
    the cached edit runs at ``student_steps`` subset steps with
    ``student_head`` (train/distill.py) modulating ε, COMPOSED with the
    quant/reuse knobs on the same program. Requires ``student_head``
    (identity-init for the untrained-student baseline, or a distilled
    head); the source replay stays exact here too.

    Returns ``(records, outputs)`` — one JSON-safe record per step count
    (non-finite metric values become null) in base-steps-first order,
    variant rows last; every record carries ``quant_mode``,
    ``reuse_schedule`` (``"off"`` on the plain step rows) and ``student``
    (False except on student rows).
    """
    import math

    from videop2p_tpu.control import make_controller
    from videop2p_tpu.control.local_blend import blend_mask
    from videop2p_tpu.obs.quality import (
        adjacent_frame_psnr,
        masked_psnr,
        psnr,
        ssim,
    )
    from videop2p_tpu.pipelines import ddim_inversion_captured, edit_sample
    from videop2p_tpu.pipelines.cached import capture_windows
    from videop2p_tpu.utils.tokenizers import WordTokenizer

    def _jf(v, nd=2):
        v = float(v)
        return round(v, nd) if math.isfinite(v) else None

    prompts = ["a rabbit is jumping on the grass",
               "a origami rabbit is jumping on the grass"]

    def ctl(steps):
        # the bench working point's controller, rebuilt per step count —
        # subset edits gate in their OWN step space
        return make_controller(
            prompts, WordTokenizer(), num_steps=steps,
            is_replace_controller=False,
            cross_replace_steps=0.2, self_replace_steps=0.5,
            blend_words=(["rabbit"], ["rabbit"]),
            equalizer_params={"words": ["origami"], "values": [2.0]},
        )

    base_steps = int(base_steps)
    ctx_base = ctl(base_steps)
    cross_len, self_window = capture_windows(ctx_base, base_steps)
    traj, cached = jax.jit(
        lambda p, x: ddim_inversion_captured(
            fn, p, sched, x, cond[:1], num_inference_steps=base_steps,
            cross_len=cross_len, self_window=self_window, capture_blend=True,
        )
    )(params, x0)
    x_t = traj[-1]
    x0_f = jnp.asarray(x0[0], jnp.float32)
    span = float(jnp.max(x0_f) - jnp.min(x0_f))
    # the LocalBlend mask the capture implies (the source's summed per-step
    # blend contributions): background-preservation scores its complement
    mask = None
    if cached.blend_seq is not None:
        maps_sum = jnp.sum(cached.blend_seq.astype(jnp.float32), axis=0)
        mask = blend_mask(maps_sum, ctx_base.blend, x0.shape[2:4])[0]

    counts = [base_steps] + [int(s) for s in step_counts
                             if int(s) != base_steps]
    records, outputs = [], {}
    base_edit, base_wall = None, None
    for steps in counts:
        positions = (None if steps == base_steps else tuple(
            int(i) for i in sched.subset_positions(base_steps, steps)
        ))
        ctx_s = ctx_base if steps == base_steps else ctl(steps)
        prog = jax.jit(
            lambda p, xt, cch, _ctx=ctx_s, _n=steps, _pos=positions:
            edit_sample(
                fn, p, sched, xt, cond, uncond,
                num_inference_steps=_n, guidance_scale=guidance_scale,
                ctx=_ctx, source_uses_cfg=False, cached_source=cch,
                step_positions=_pos,
            )
        )
        out = hard_block(prog(params, x_t, cached))  # compile + scored output
        edit_s = None
        if timed:
            # timing run on a value-perturbed x_T
            t0 = time.perf_counter()
            hard_block(prog(params, x_t * (1.0 + 1e-6), cached))
            edit_s = round(time.perf_counter() - t0, 3)
        edit = out[1].astype(jnp.float32)
        rec = {
            "steps": steps,
            "base_steps": base_steps,
            "quant_mode": "off",
            "reuse_schedule": "off",
            "student": False,
            "edit_s": edit_s,
            "src_err": float(jnp.max(jnp.abs(
                out[0].astype(jnp.float32) - x0_f
            ))),
            "edit_adjacent_psnr_db": _jf(jnp.mean(
                adjacent_frame_psnr(edit, data_range=span)
            )),
        }
        if steps == base_steps:
            base_edit, base_wall = edit, edit_s
            rec.update(vs_full_psnr_db=None, vs_full_ssim=None,
                       speedup_vs_full=None)
        else:
            rec["vs_full_psnr_db"] = _jf(psnr(edit, base_edit, data_range=span))
            rec["vs_full_ssim"] = _jf(ssim(edit, base_edit, data_range=span), 4)
            rec["speedup_vs_full"] = (
                round(base_wall / edit_s, 2)
                if timed and base_wall and edit_s else None
            )
        if mask is not None:
            bg = (1.0 - mask.astype(jnp.float32))[..., None]
            rec["background_psnr_db"] = _jf(
                masked_psnr(edit, x0_f, bg, data_range=span)
            )
            rec["mask_coverage"] = _jf(jnp.mean(mask.astype(jnp.float32)), 4)
        else:
            rec["background_psnr_db"] = None
            rec["mask_coverage"] = None
        records.append(rec)
        outputs[steps] = out

    for v in variants:
        if len(v) == 3:
            stu_steps, qm, rs = int(v[0]), str(v[1]), str(v[2])
        else:
            stu_steps, (qm, rs) = 0, (str(v[0]), str(v[1]))
        if qm not in ("off", "w8"):
            raise ValueError(
                f"frontier quant_mode must be 'off' or 'w8', got {qm!r} "
                "(w8a8 needs the model rebuilt with act_quant_fn — see the "
                "unet_unit_w8a8 cost unit)"
            )
        if stu_steps:
            if student_head is None:
                raise ValueError(
                    f"student variant student:{stu_steps}+{qm}+{rs} needs "
                    "student_head (train/distill.py init_time_head for the "
                    "untrained-student baseline, or a distilled head)"
                )
            if not 1 <= stu_steps <= base_steps:
                raise ValueError(
                    f"student steps {stu_steps} outside [1, {base_steps}]"
                )
        elif qm == "off" and rs == "off":
            continue  # identical to the base row
        steps_v = stu_steps or base_steps
        positions_v = (None if steps_v == base_steps else tuple(
            int(i) for i in sched.subset_positions(base_steps, steps_v)
        ))
        ctx_v = ctx_base if steps_v == base_steps else ctl(steps_v)
        head_v = student_head if stu_steps else None
        p_v = params
        if qm == "w8":
            from videop2p_tpu.models.convert import quantize_unet_params
            p_v = quantize_unet_params(params, mode=qm)
        prog = jax.jit(
            lambda p, xt, cch, _rs=(None if rs == "off" else rs),
            _ctx=ctx_v, _n=steps_v, _pos=positions_v, _head=head_v:
            edit_sample(
                fn, p, sched, xt, cond, uncond,
                num_inference_steps=_n,
                guidance_scale=guidance_scale, ctx=_ctx,
                source_uses_cfg=False, cached_source=cch,
                step_positions=_pos, reuse_schedule=_rs,
                student_head=_head,
            )
        )
        out = hard_block(prog(p_v, x_t, cached))
        edit_s = None
        if timed:
            t0 = time.perf_counter()
            hard_block(prog(p_v, x_t * (1.0 + 1e-6), cached))
            edit_s = round(time.perf_counter() - t0, 3)
        edit = out[1].astype(jnp.float32)
        rec = {
            "steps": steps_v,
            "base_steps": base_steps,
            "quant_mode": qm,
            "reuse_schedule": rs,
            "student": bool(stu_steps),
            "edit_s": edit_s,
            "src_err": float(jnp.max(jnp.abs(
                out[0].astype(jnp.float32) - x0_f
            ))),
            "edit_adjacent_psnr_db": _jf(jnp.mean(
                adjacent_frame_psnr(edit, data_range=span)
            )),
            "vs_full_psnr_db": _jf(psnr(edit, base_edit, data_range=span)),
            "vs_full_ssim": _jf(ssim(edit, base_edit, data_range=span), 4),
            "speedup_vs_full": (
                round(base_wall / edit_s, 2)
                if timed and base_wall and edit_s else None
            ),
        }
        if mask is not None:
            bg = (1.0 - mask.astype(jnp.float32))[..., None]
            rec["background_psnr_db"] = _jf(
                masked_psnr(edit, x0_f, bg, data_range=span)
            )
            rec["mask_coverage"] = _jf(jnp.mean(mask.astype(jnp.float32)), 4)
        else:
            rec["background_psnr_db"] = None
            rec["mask_coverage"] = None
        records.append(rec)
        outputs[(f"student:{stu_steps}+{qm}+{rs}" if stu_steps
                 else f"{qm}+{rs}")] = out
    return records, outputs


def collect_step_frontier(*, timeout_s=900.0, tiny=True, frames=2,
                          base_steps=50, step_counts=(50, 20, 8),
                          variants=()):
    """Run ``tools/step_frontier.py`` in a CPU SUBPROCESS (same isolation
    rationale as :func:`collect_cpu_analysis`: this is the backend-down
    path, and the parent's jax may hold a poisoned backend init) and parse
    its one-JSON-line-per-step-count output. A timeout keeps the step
    counts that finished. Never raises."""
    repo = os.path.dirname(os.path.abspath(__file__))
    cmd = [sys.executable, os.path.join(repo, "tools", "step_frontier.py"),
           "--frames", str(frames), "--base_steps", str(base_steps),
           "--steps", ",".join(str(s) for s in step_counts)]
    if variants:
        cmd += ["--variants", ",".join(
            (f"student:{int(v[0])}+{v[1]}+{v[2]}" if len(v) == 3
             else f"{v[0]}+{v[1]}")
            for v in variants
        )]
    if tiny:
        cmd.append("--tiny")
    env = dict(os.environ)
    env["PYTHONPATH"] = repo + os.pathsep + env.get("PYTHONPATH", "")
    stdout = ""
    try:
        proc = subprocess.run(cmd, capture_output=True, text=True,
                              timeout=timeout_s, env=env)
        stdout = proc.stdout or ""
        if proc.returncode != 0:
            print(f"[bench] step frontier rc={proc.returncode}: "
                  f"{(proc.stderr or '')[-300:]}", file=sys.stderr, flush=True)
    except subprocess.TimeoutExpired as e:
        stdout = (e.stdout.decode() if isinstance(e.stdout, bytes)
                  else e.stdout) or ""
        print(f"[bench] step frontier timed out after {timeout_s:.0f}s — "
              "keeping the step counts that finished", file=sys.stderr,
              flush=True)
    except OSError as e:
        print(f"[bench] step frontier failed to launch: {e}",
              file=sys.stderr, flush=True)
    records = []
    for line in stdout.splitlines():
        try:
            rec = json.loads(line)
        except ValueError:
            continue
        if isinstance(rec, dict) and "steps" in rec:
            records.append(rec)
    return records


def collect_served_latency(*, timeout_s=600.0, requests=6, concurrency=3):
    """Measured SERVED latency: drive ``tools/serve_loadgen.py`` against an
    in-process tiny engine in a CPU subprocess (same isolation rationale as
    :func:`collect_step_frontier`) with ``--tracing`` on, then join the
    run's span ledgers into the critical-path segment split. The record is
    queueing-INCLUSIVE — client-observed p50/p99 under concurrency, not a
    bare dispatch wall — with the queue/resolve/dispatch/decode attribution
    alongside it (ISSUE 14). CPU-tiny scale, disclosed as such, never a TPU
    claim. Never raises."""
    repo = os.path.dirname(os.path.abspath(__file__))
    out_dir = tempfile.mkdtemp(prefix="bench_served_")
    cmd = [sys.executable, os.path.join(repo, "tools", "serve_loadgen.py"),
           "--inproc", "--tiny", "--steps", "2", "--video_len", "2",
           "--requests", str(requests), "--concurrency", str(concurrency),
           "--tracing", "--out_dir", out_dir,
           "--ledger", os.path.join(out_dir, "loadgen_ledger.jsonl")]
    env = dict(os.environ)
    env["PYTHONPATH"] = repo + os.pathsep + env.get("PYTHONPATH", "")
    env["JAX_PLATFORMS"] = "cpu"
    rec = None
    try:
        proc = subprocess.run(cmd, capture_output=True, text=True,
                              timeout=timeout_s, env=env)
        for line in (proc.stdout or "").splitlines():
            try:
                obj = json.loads(line)
            except ValueError:
                continue
            if isinstance(obj, dict) and "latency" in obj:
                rec = obj
        if rec is None:
            print(f"[bench] served-latency loadgen rc={proc.returncode}: "
                  f"{(proc.stderr or '')[-300:]}", file=sys.stderr,
                  flush=True)
    except (subprocess.TimeoutExpired, OSError) as e:
        print(f"[bench] served-latency loadgen failed ({type(e).__name__})",
              file=sys.stderr, flush=True)
    if rec is None:
        shutil.rmtree(out_dir, ignore_errors=True)
        return None
    lat = rec.get("latency") or {}
    result = {
        "backend": "cpu-tiny",
        "requests": rec.get("requests"),
        "concurrency": rec.get("concurrency"),
        "done": rec.get("done"),
        "store_hits": rec.get("store_hits"),
        "throughput_rps": rec.get("throughput_rps"),
        "e2e_p50_s": lat.get("blocked_p50_s"),
        "e2e_p99_s": lat.get("blocked_p99_s"),
        "e2e_max_s": lat.get("blocked_max_s"),
    }
    # trace-derived critical-path split: every span the run's ledgers
    # recorded (loadgen + the inproc engine's serve ledger), bucketed by
    # the obs/spans.py segment naming
    from videop2p_tpu.obs import SPAN_SEGMENTS

    durs: dict = {}
    for root, _dirs, files in os.walk(out_dir):
        for fn in files:
            if not fn.endswith(".jsonl"):
                continue
            try:
                with open(os.path.join(root, fn)) as f:
                    for line in f:
                        try:
                            ev = json.loads(line)
                        except ValueError:
                            continue
                        seg = SPAN_SEGMENTS.get(ev.get("name"))
                        if ev.get("event") == "span" and seg:
                            durs.setdefault(seg, []).append(
                                float(ev.get("duration_s") or 0.0))
            except OSError:
                continue
    segments = {}
    for seg, vals in sorted(durs.items()):
        vals.sort()
        n = len(vals)
        segments[seg] = {
            "count": n,
            "p50_s": round(vals[max(math.ceil(50 * n / 100), 1) - 1], 6),
            "p99_s": round(vals[max(math.ceil(99 * n / 100), 1) - 1], 6),
            "max_s": round(vals[-1], 6),
        }
    if segments:
        result["segments"] = segments
    shutil.rmtree(out_dir, ignore_errors=True)
    return result


_GN_PROBE_SCRIPT = """
import jax, jax.numpy as jnp
from videop2p_tpu.ops.groupnorm import fused_group_norm
# every (rows, C) slab class the VMEM gate admits across the bench's model
# shapes, in BOTH site configurations: the transformer-entry GN
# (act='none', eps=1e-6 — attention.py) and the resnet GN+SiLU
# (act='silu', eps=1e-5 — layers.py)
for rows, c in ((4096, 320), (1024, 640), (256, 1280),
                (512, 1280), (1024, 1280)):
    for act, eps in (("none", 1e-6), ("silu", 1e-5)):
        out = jax.jit(
            lambda x, ch=c, a=act, e=eps: fused_group_norm(
                x, jnp.ones((ch,)), jnp.zeros((ch,)),
                num_groups=32, act=a, eps=e,
            )
        )(jnp.ones((1, rows, c), jnp.bfloat16))
        # value fetch: a hung dispatch must hang HERE, inside the timeout
        float(jnp.asarray(out).ravel()[0].astype(jnp.float32))
print("GN_PROBE_OK")
"""


def _fused_gn_probe_ok(timeout_s: float = 420.0) -> bool:
    """Compile+run the fused GroupNorm kernel at every slab class the bench
    will embed it in — in a SUBPROCESS with a timeout: a Mosaic regression
    can HANG the chip, not just raise, and a hang in the parent would cost
    the round its driver artifact (the r4 failure class). Any failure mode
    demotes the whole bench to the XLA two-pass path."""
    try:
        env = dict(os.environ)
        repo = os.path.dirname(os.path.abspath(__file__))
        env["PYTHONPATH"] = repo + os.pathsep + env.get("PYTHONPATH", "")
        proc = subprocess.run(
            [sys.executable, "-c", _GN_PROBE_SCRIPT],
            capture_output=True, text=True, timeout=timeout_s, env=env,
        )
    except (subprocess.TimeoutExpired, OSError) as e:
        print(f"[bench] fused-GroupNorm probe timed out/failed to launch "
              f"({type(e).__name__}) — group_norm='xla'",
              file=sys.stderr, flush=True)
        return False
    if proc.returncode != 0 or "GN_PROBE_OK" not in proc.stdout:
        print(f"[bench] fused-GroupNorm probe failed (rc={proc.returncode}): "
              f"{proc.stderr[-300:]} — group_norm='xla'",
              file=sys.stderr, flush=True)
        return False
    return True


BENCH_FRAMES, BENCH_STEPS = 8, 50


def record_cpu_only_evidence(repo_dir=None) -> None:
    """The backend is down: capture what CAN be captured — XLA's CPU
    cost/memory analyses of the bench programs — so the round still
    records machine-readable per-program evidence (flops / bytes /
    temp-HBM / HLO fingerprints) plus regression verdicts against the
    previous record, instead of only ``value: null`` (the VERDICT r5
    failure mode). Skippable via ``VIDEOP2P_BENCH_CPU_ANALYSIS=0``;
    subprocess-isolated and time-bounded, never raises."""
    if os.environ.get("VIDEOP2P_BENCH_CPU_ANALYSIS", "1") != "1":
        return
    repo = repo_dir or os.path.dirname(os.path.abspath(__file__))
    timeout_s = float(os.environ.get(
        "VIDEOP2P_BENCH_CPU_ANALYSIS_TIMEOUT", "900"))
    analyses = collect_cpu_analysis(
        BENCH_FRAMES, BENCH_STEPS, timeout_s=timeout_s,
        ledger_path=os.path.join(repo, "bench_ledger.jsonl"),
    )
    rec = DetailsRecorder(os.path.join(repo, "bench_details.json"), {}, [])
    if not analyses:
        rec.record("cpu_analysis_error",
                   "cpu cost capture produced no programs")
    else:
        record_program_analyses(rec, analyses, backend="cpu",
                                baseline_dir=repo)
        print(f"[bench] backend down — recorded CPU cost/memory analyses "
              f"for {sorted(analyses)} in bench_details.json",
              file=sys.stderr, flush=True)
    # the ISSUE-8 evidence survives a dead chip too: per-mode null-text
    # inner-loop flop totals from the straight-line unit analyses, and the
    # tiny-scale CPU step frontier (executed — quality metrics per step
    # count, wall-clock disclosed as CPU-tiny, never a TPU claim)
    record_null_text_flops(rec, timeout_s=timeout_s)
    # the measured-scale-out evidence (ISSUE 10): per-frame-count ring
    # comm/flop records + the Megatron tp pairing, static and CPU-cheap
    record_frame_scaling(rec, timeout_s=timeout_s)
    # the streaming-window evidence (ISSUE 12): 128f/480f window counts,
    # flops and store bytes per window — reuses the capture above (it
    # already holds e2e_cached, the per-window program)
    record_streaming_scaling(rec, analyses=analyses)
    # the cost-plane evidence (ISSUE 19): the same capture through the
    # serving engine's CostModel — backend down, so static columns only
    record_bench_costs(rec, analyses)
    # the per-call cost evidence (ISSUE 15): quantized weight-footprint
    # and reuse flop-fraction from loop-free unit programs, plus the
    # quant/reuse variant rows on the executed tiny frontier below
    record_per_call_cost(rec, timeout_s=timeout_s)
    frontier = collect_step_frontier(
        timeout_s=timeout_s, tiny=True,
        variants=(("w8", "off"), ("off", "uniform:2"), ("w8", "uniform:2"),
                  # composed student rows (ISSUE 16): identity-init student
                  # at 2 subset steps, plain and × quant × reuse
                  (2, "off", "off"), (2, "w8", "uniform:2")),
    )
    if frontier:
        rec.record("latency_quality_frontier", frontier)
        rec.record("latency_quality_frontier_backend", "cpu-tiny")
    # the serving-path evidence (ISSUE 14): queueing-inclusive served
    # p50/p99 through the real loadgen + engine stack with the
    # trace-derived queue/resolve/dispatch/decode split — survives a dead
    # chip because the whole stack runs tiny on CPU anyway
    served = collect_served_latency(timeout_s=timeout_s)
    if served:
        rec.record("served_latency", served)


def main() -> None:
    if not wait_for_backend():
        emit_backend_unavailable()
        record_cpu_only_evidence()
        return
    from videop2p_tpu.models import UNet3DConditionModel, UNet3DConfig
    from videop2p_tpu.obs import RunLedger
    from videop2p_tpu.pipelines import (
        edit_sample,
        make_unet_fn,
        null_text_optimization,
        null_text_optimization_fused,
    )

    # every compile this process performs lands in the run ledger as a
    # `compile` event (jax.monitoring listener), and the breakdown carries
    # the ledger path + compile/execute split (ledger_bench_fields)
    ledger_path = os.path.join(
        os.path.dirname(os.path.abspath(__file__)), "bench_ledger.jsonl"
    )
    bench_ledger = RunLedger(ledger_path, meta={"tool": "bench"}).activate()

    F, STEPS = BENCH_FRAMES, BENCH_STEPS
    # GroupNorm implementation for the whole bench: the fused one-pass
    # kernel by default (r5), demoted to the XLA two-pass math if the
    # kernel fails a dispatch-level probe on this chip — a Mosaic
    # regression must degrade the numbers, never cost the round its driver
    # artifact. The probe compiles and runs the kernel at every (rows, C)
    # slab class the VMEM gate admits across the bench's model shapes
    # (SD-1.5 per-frame sites, the 8² frame-pooled sites, SDXL's 32²
    # site), so any later program embedding the kernel has had its exact
    # kernel shapes proven first. Overridable via VIDEOP2P_BENCH_GROUP_NORM.
    gn_impl = os.environ.get("VIDEOP2P_BENCH_GROUP_NORM", "auto")
    if gn_impl not in ("auto", "xla", "interpret"):
        print(f"[bench] unknown VIDEOP2P_BENCH_GROUP_NORM={gn_impl!r} "
              "(valid: auto/xla/interpret) — using 'auto'",
              file=sys.stderr, flush=True)
        gn_impl = "auto"
    if gn_impl == "auto" and not _fused_gn_probe_ok():
        gn_impl = "xla"
    wp = build_fast_edit_working_point(
        num_frames=F, num_steps=STEPS, cached=True, group_norm=gn_impl
    )

    # headline = the cached-source fast mode (the CLI default,
    # pipelines/cached.py): the inversion walk captures the controlled-site
    # maps + blend contributions, and the edit then runs only TWO UNet
    # streams — the source stream replays the trajectory exactly. The
    # headline number is the FUSED single-dispatch program (capture + edit
    # in one jit, as the CLI runs it): the separate phases below measured
    # 12.25–13.0 s summed while the fused call reads 11.8 s (round-4
    # record) — one dispatch per program, and fusing drops one.
    # warm-up (compile) on a DIFFERENT input than the measured run
    warm_traj, warm_cached = wp.invert_captured(wp.params, wp.x_warm)
    out = hard_block(wp.edit_cached(wp.params, warm_traj[-1], warm_cached))

    invert, edit, params = wp.invert, wp.edit, wp.params
    fn, sched, ctx = wp.fn, wp.sched, wp.ctx
    cond, uncond, x0, x_warm, base = wp.cond, wp.uncond, wp.x0, wp.x_warm, wp.base
    # null-text differentiates through the UNet — per-block rematerialization
    # keeps the backward under one chip's HBM (dense backward OOMs at 16 GB)
    model_remat = UNet3DConditionModel(
        config=UNet3DConfig.sd15(
            gradient_checkpointing=True, group_norm=gn_impl
        ),
        dtype=jnp.bfloat16,
    )
    fn_remat = make_unet_fn(model_remat)
    hard_block(wp.e2e_cached(params, x_warm + 0.001))

    peak = _peak_flops()
    # inversion is 1 cond stream (map capture adds HBM writes, no FLOPs); the
    # cached edit batch is 2 streams (edit uncond + edit cond — the source
    # stream is replayed, not recomputed)
    inv_flops = FLOPS_PER_FRAME_FWD * 1 * F * STEPS
    edit_flops = FLOPS_PER_FRAME_FWD * 2 * F * STEPS
    suspect = []

    k_r1, k_r2 = jax.random.split(jax.random.fold_in(base, 7))
    r_inv = measure_with_floor(
        lambda x: wp.invert_captured(params, x),
        [x0] + [jax.random.normal(k, x0.shape, x0.dtype) for k in (k_r1, k_r2)],
        inv_flops / peak,
        "inversion",
    )
    (traj, cached_src), inv_s = r_inv.out, r_inv.seconds
    r_edit = measure_with_floor(
        lambda xt: wp.edit_cached(params, xt, cached_src),
        # value-fresh x_T per attempt (wall-clock is value-independent)
        [traj[-1], traj[-1] + 0.001, traj[-1] - 0.001],
        edit_flops / peak,
        "edit",
    )
    out, edit_s = r_edit.out, r_edit.seconds
    r_e2e = measure_with_floor(
        lambda x: wp.e2e_cached(params, x),
        # 5 fresh inputs for 3 samples: sub-floor readings consume
        # retries without starving the median
        [jax.random.normal(jax.random.fold_in(base, k), x0.shape, x0.dtype)
         for k in (11, 12, 13, 14, 15)],
        (inv_flops + edit_flops) / peak,
        "fused e2e",
        # the HEADLINE number: median of three valid runs with the spread
        # recorded, not first-accepted (VERDICT r4 weak #7 discipline)
        samples=3,
    )
    elapsed = r_e2e.seconds

    assert bool(jnp.isfinite(out.astype(jnp.float32)).all()), "non-finite output"
    assert bool(jnp.isfinite(r_e2e.out.astype(jnp.float32)).all()), "non-finite e2e"
    # exactness of the HEADLINE program itself: the fused edit's stream 0 is
    # the inversion input bit-for-bit (the input IS x_0 here)
    e2e_src_err = float(jnp.max(jnp.abs(
        r_e2e.out[0].astype(jnp.float32) - r_e2e.x_used[0].astype(jnp.float32)
    )))
    assert e2e_src_err == 0.0, f"fused cached replay not exact: {e2e_src_err}"
    # the cached replay guarantee, checked on-chip: the edit's source stream
    # IS the inversion input (max |out[0] − x_0| must be exactly 0)
    src_err = float(
        jnp.max(jnp.abs(out[0].astype(jnp.float32) - traj[0][0].astype(jnp.float32)))
    )
    assert src_err == 0.0, f"cached source replay not exact: {src_err}"

    breakdown = {
        "device": jax.devices()[0].device_kind,
        "group_norm": gn_impl,
    }
    rec = DetailsRecorder(
        os.path.join(os.path.dirname(os.path.abspath(__file__)), "bench_details.json"),
        breakdown,
        suspect,
    )
    rec.record("inversion_s", round(inv_s, 3), reading=r_inv)
    rec.record("edit_s", round(edit_s, 3), reading=r_edit)
    # the headline: one fused dispatch (the phase sum adds one more)
    rec.record("fast_edit_e2e_fused_s", round(elapsed, 3), reading=r_e2e)
    if r_e2e.samples:
        rec.record("fast_edit_e2e_fused_samples", list(r_e2e.samples),
                   derived=(r_e2e,))
    rec.record("inversion_step_ms", round(inv_s / STEPS * 1e3, 1), derived=(r_inv,))
    rec.record("edit_step_ms", round(edit_s / STEPS * 1e3, 1), derived=(r_edit,))
    rec.record("frames_per_sec", round(F / elapsed, 3), derived=(r_e2e,))
    if peak == peak:  # known peak-FLOPs device only (NaN is not valid JSON)
        rec.record("mfu_inversion", round(inv_flops / inv_s / peak, 3), derived=(r_inv,))
        rec.record("mfu_edit", round(edit_flops / edit_s / peak, 3), derived=(r_edit,))
    # compile-vs-execute provenance of the headline: the ledger captured
    # every backend compile this process ran before the measured executions
    for k, v in ledger_bench_fields(
        ledger_path, bench_ledger.compile_seconds, execute_s=elapsed
    ).items():
        rec.record(k, v)
    bench_ledger.memory_snapshot(note="after_fast_phase")

    # print the metric of record NOW: the extended phases below (null-text,
    # official mode, tuning step) take ~25 more minutes of compiles and
    # measured runs, and the primary line must survive a harness timeout
    print(
        json.dumps(
            {
                "metric": "fast_edit_e2e_wall",
                "value": round(elapsed, 3),
                "unit": "s",
                "vs_baseline": round(V100_FAST_EDIT_S / elapsed, 2),
                "breakdown": breakdown,
            }
        ),
        flush=True,
    )

    # compiled-program introspection of the measured headline programs
    # (obs/introspect.py): what XLA actually built this round — flops,
    # bytes, temp-HBM, HLO fingerprints — persisted next to the wall-clock
    # numbers and diffed against the previous record (regression verdicts).
    # AFTER the primary print: evidence capture must never delay or risk
    # the metric of record. The executables are already built, so with the
    # persistent compile cache the AOT re-lowering is cheap.
    if os.environ.get("VIDEOP2P_BENCH_CPU_ANALYSIS", "1") == "1":
        try:
            from videop2p_tpu.obs.comm import comm_analysis_record
            from videop2p_tpu.obs.introspect import (
                analyze_compiled,
                compile_abstract,
            )
            from videop2p_tpu.obs.ledger import suppress_compile_events

            analyses = {}
            comm_records = {}
            with suppress_compile_events():
                for name, (fn_j, a) in {
                    "invert_captured": (wp.invert_captured, (params, x0)),
                    "edit_cached": (wp.edit_cached,
                                    (params, traj[-1], cached_src)),
                    "e2e_cached": (wp.e2e_cached, (params, x0)),
                }.items():
                    compiled = compile_abstract(fn_j, *a)
                    if compiled is None:
                        continue
                    a_rec = analyze_compiled(compiled)
                    if a_rec:
                        analyses[name] = a_rec
                        bench_ledger.program_analysis(name, a_rec)
                    # collective accounting (obs/comm.py) — meaningful only
                    # for partitioned programs; single-chip benches record
                    # nothing here (no collectives, one partition)
                    c_rec = comm_analysis_record(compiled)
                    if c_rec is not None and (
                        c_rec.get("num_partitions", 1) > 1
                        or c_rec.get("collective_count", 0)
                    ):
                        comm_records[name] = c_rec
                        bench_ledger.comm_analysis(name, c_rec)
            record_program_analyses(
                rec, analyses, backend=jax.devices()[0].platform
            )
            if comm_records:
                rec.record("comm_analysis", comm_records)
            # cost-plane evidence (ISSUE 19): static pricing + this
            # round's measured headline seconds → achieved flops/s
            record_bench_costs(
                rec, analyses,
                measured={"invert_captured": r_inv.seconds,
                          "edit_cached": r_edit.seconds,
                          "e2e_cached": r_e2e.seconds},
                backend=jax.devices()[0].platform,
            )
        except Exception as e:  # noqa: BLE001 — evidence, never the record
            print(f"[bench] program analysis failed: {e}", file=sys.stderr,
                  flush=True)

    # time-domain evidence (ISSUE 6): the headline programs' measured
    # readings become execute_timing distribution events (every valid
    # sample, not just the reading of record — the spread IS the
    # evidence), and one live cached-pair execution is traced and mined
    # into a trace_analysis event + bench_details record. Best-effort
    # and AFTER the primary print — never risks the metric of record.
    try:
        for prog, reading in (("invert_captured", r_inv),
                              ("edit_cached", r_edit),
                              ("e2e_cached", r_e2e)):
            for s in (reading.samples or (reading.seconds,)):
                # bench calls block to completion, so dispatch == blocked
                bench_ledger.record_execute(prog, float(s), float(s))
        bench_ledger.flush_execute_timing()
    except Exception as e:  # noqa: BLE001
        print(f"[bench] execute-timing record failed: {e}", file=sys.stderr,
              flush=True)
    if os.environ.get("VIDEOP2P_BENCH_TRACE", "1") == "1":
        try:
            from videop2p_tpu.obs.trace import analyze_trace_dir, trace_window

            with trace_window("bench_cached_pair") as trace_target:
                b_traj, b_cc = wp.invert_captured(params, x_warm)
                hard_block(wp.edit_cached(params, b_traj[-1], b_cc))
            t_rec, _ = analyze_trace_dir(trace_target, name="bench_cached_pair")
            rec.record("trace_analysis", {
                k: t_rec.get(k) for k in (
                    "device_total_s", "compute_s", "collective_s",
                    "overlap_fraction", "span_s", "idle_s", "num_events",
                )
            })
            del b_traj, b_cc
        except Exception as e:  # noqa: BLE001
            print(f"[bench] trace-analysis capture failed: {e}",
                  file=sys.stderr, flush=True)

    if os.environ.get("VIDEOP2P_BENCH_FAST_ONLY", "0") != "1":
        # Any extended-phase failure (OOM, a device error) must not cost the
        # round its primary record: partial breakdown still gets written.
        try:
            from videop2p_tpu.core import DDPMScheduler
            from videop2p_tpu.train import (
                TrainState,
                TuneConfig,
                make_optimizer,
                train_steps,
            )

            # ---- live-source A/B: the reference-faithful fast mode (live
            # 3-stream edit) against the cached headline above — the bench
            # line VERDICT r3 item 1 asks for ----------------------------
            x_t = traj[-1]
            # actually release the ~3.1 GiB capture tree: the Reading tuples
            # keep r_inv.out/r_edit.out alive through the whole extended
            # section, so dropping the locals alone frees nothing
            r_inv = r_inv._replace(out=None)
            r_edit = r_edit._replace(out=None)
            del out, warm_traj, warm_cached, cached_src
            jax.clear_caches()
            profiling.reset()  # fresh phase records per configuration
            hard_block(wp.edit(params, wp.invert(params, x_warm)[-1]))
            r_linv = measure_with_floor(
                lambda x: wp.invert(params, x),
                [x0 + 0.002, x0 - 0.002],
                inv_flops / peak,
                "inversion (live)",
            )
            r_ledit = measure_with_floor(
                lambda xt: wp.edit(params, xt),
                [x_t, x_t + 0.001],
                FLOPS_PER_FRAME_FWD * 3 * F * STEPS / peak,
                "edit (live)",
            )
            inv_live_s, edit_live_s = r_linv.seconds, r_ledit.seconds
            rec.record("inversion_live_s", round(inv_live_s, 3), reading=r_linv)
            rec.record("edit_live_s", round(edit_live_s, 3), reading=r_ledit)
            rec.record("fast_edit_e2e_live_s", round(inv_live_s + edit_live_s, 3),
                       derived=(r_linv, r_ledit))
            # what the map capture adds to the inversion walk — the cost side
            # of the cached mode's 3→2-stream edit saving
            rec.record("capture_overhead_s", round(inv_s - inv_live_s, 3),
                       derived=(r_inv, r_linv))
            if peak == peak:
                rec.record(
                    "mfu_edit_live",
                    round(FLOPS_PER_FRAME_FWD * 3 * F * STEPS / edit_live_s / peak, 3),
                    derived=(r_ledit,),
                )

            # ---- cached-vs-live output delta (VERDICT r4 item 2): the ONE
            # quantified number for the cached mode's disclosed
            # approximation (pipelines/cached.py:27-33 — the captured base
            # maps come from the inversion trajectory's positions, one
            # trajectory's worth off the live source stream's). Same input
            # through both paths at the bench working point; the EDITED
            # stream's latent delta is the metric (stream 0 differs by
            # design: cached replays exactly, live only approximately
            # reconstructs). Weights are random-init — the architecture and
            # shapes are the working point's; a checkpoint-weighted delta
            # would need SD weights this image doesn't ship (disclosed). --
            x_cmp = jax.random.normal(jax.random.fold_in(base, 91), x0.shape, x0.dtype)
            out_live_cmp = hard_block(wp.edit(params, wp.invert(params, x_cmp)[-1]))
            out_cch_cmp = hard_block(wp.e2e_cached(params, x_cmp))
            dl = jnp.abs(out_cch_cmp[1].astype(jnp.float32)
                         - out_live_cmp[1].astype(jnp.float32))
            ref_scale = float(jnp.mean(jnp.abs(out_live_cmp[1].astype(jnp.float32))))
            rec.record("cached_vs_live_edit_max_abs_delta",
                       round(float(jnp.max(dl)), 4))
            rec.record("cached_vs_live_edit_mean_abs_delta",
                       round(float(jnp.mean(dl)), 5))
            rec.record("cached_vs_live_edit_mean_abs_latent", round(ref_scale, 4))
            ds = jnp.abs(out_cch_cmp[0].astype(jnp.float32)
                         - out_live_cmp[0].astype(jnp.float32))
            # stream 0: cached is bit-exact to x_0; this delta IS the live
            # path's reconstruction drift, recorded for context
            rec.record("cached_vs_live_source_max_abs_delta",
                       round(float(jnp.max(ds)), 4))
            # decoded-pixel delta (VERDICT r4 item 2 asks for both latent
            # and pixel space): a random-init SD-shaped VAE decoder maps
            # both edited latents to 512² pixels in [-1, 1]; never fatal
            try:
                from videop2p_tpu.models import decode_video
                from videop2p_tpu.models.vae import AutoencoderKL, VAEConfig

                vae = AutoencoderKL(config=VAEConfig(), dtype=jnp.bfloat16)
                vp = jax.jit(
                    lambda k, z: vae.init(k, z, method=vae.decode)
                )(jax.random.key(0), jnp.zeros((1, 64, 64, 4), jnp.bfloat16))
                dec = jax.jit(
                    lambda p, z: decode_video(
                        vae, p, z.astype(jnp.bfloat16), sequential=True
                    )
                )
                px_c = hard_block(dec(vp, out_cch_cmp[1:2]))
                px_l = hard_block(dec(vp, out_live_cmp[1:2]))
                dp = jnp.abs(px_c.astype(jnp.float32) - px_l.astype(jnp.float32))
                rec.record("cached_vs_live_edit_pixel_max_abs_delta",
                           round(float(jnp.max(dp)), 4))
                rec.record("cached_vs_live_edit_pixel_mean_abs_delta",
                           round(float(jnp.mean(dp)), 5))
                del vae, vp, dec, px_c, px_l, dp
            except Exception as e:  # noqa: BLE001
                print(f"[bench] pixel-delta decode failed: {e}",
                      file=sys.stderr, flush=True)
            del out_live_cmp, out_cch_cmp, dl, ds

            # The BASELINE.json north-star (<10 s) is a v5e-4 slice; this
            # harness has ONE chip. The projection models the LIVE sharded
            # path (the cached capture is single-chip for now), so it feeds
            # on the live A/B numbers; the shard-measured refinement below
            # overrides it.
            try:
                project = _tools_import("projection").project
                proj = project(inv_live_s, edit_live_s, steps=STEPS, frames=F)
                rec.record("projected_v5e4_s", proj["projected_v5e4_s"],
                           derived=(r_linv, r_ledit))
                rec.record("projected_v5e4_range_s", proj["projected_v5e4_range_s"],
                           derived=(r_linv, r_ledit))
                rec.record("projected_v5e4_efficiency", proj["parallel_efficiency"],
                           derived=(r_linv, r_ledit))
                rec.record("projected_v5e4_model",
                           proj["assumptions"]["compute_scaling"],
                           derived=(r_linv, r_ledit))
            except Exception as e:  # noqa: BLE001 — derived, never fatal
                print(f"[bench] projection model failed: {e}", file=sys.stderr,
                      flush=True)

            # ---- on-TPU fused-vs-chunked exactness gate (VERDICT r3 item
            # 5): same math, different kernels, at the 64²-edit site shape.
            # A Mosaic/layout regression would corrupt outputs while perf
            # still looks fine — this fails loudly instead. (Chunked is the
            # dense math scanned over query blocks; the full dense score
            # tensor at this shape is 4.3 GB and needless.) --------------
            from videop2p_tpu.ops.attention import (
                chunked_frame_attention,
                fused_frame_attention,
            )

            kg = jax.random.fold_in(base, 31)
            gq = jax.random.normal(kg, (1, F, 8, 4096, 40), jnp.bfloat16)
            gk = jax.random.normal(jax.random.fold_in(base, 32), (1, 8, 4096, 40),
                                   jnp.bfloat16)
            gv = jax.random.normal(jax.random.fold_in(base, 33), (1, 8, 4096, 40),
                                   jnp.bfloat16)
            gate = jax.jit(
                lambda q, k, v: jnp.max(jnp.abs(
                    fused_frame_attention(q, k, v, 256).astype(jnp.float32)
                    - chunked_frame_attention(q, k, v).astype(jnp.float32)
                ))
            )
            gate_diff = float(hard_block(gate(gq, gk, gv)))
            rec.record("fused_kernel_maxdiff_vs_chunked", round(gate_diff, 6))
            assert gate_diff < 0.05, (
                f"fused kernel diverges from chunked math on-chip: {gate_diff}"
            )
            del gq, gk, gv

            # refine the v5e-4 projection with a MEASURED per-chip shard:
            # the F/sp=2-frame working point is exactly what one chip of the
            # (1,4,1) mesh computes per step (minus collectives), capturing
            # small-batch efficiency loss a bare /4 would hide
            F_SHARD = F // 4
            profiling.reset()  # shard-proxy config: fresh phase records
            ws = build_fast_edit_working_point(num_frames=F_SHARD, num_steps=STEPS,
                                               group_norm=gn_impl)
            hard_block(ws.edit(ws.params, ws.invert(ws.params, ws.x_warm)[-1]))
            # the proxy phases are short (~2-4 s) and carry host timing
            # noise that wobbled the projection ±15 % between rounds —
            # median of three valid samples per phase (VERDICT r3 item 6),
            # via measure_with_floor's samples mode with retry headroom
            r_sinv = measure_with_floor(
                lambda x: ws.invert(ws.params, x),
                [ws.x0 + 1e-3 * k for k in range(1, 6)],
                FLOPS_PER_FRAME_FWD * F_SHARD * STEPS / peak,
                "shard inversion",
                samples=3,
            )
            r_sedit = measure_with_floor(
                lambda xt: ws.edit(ws.params, xt),
                [r_sinv.out[-1] + 1e-3 * k for k in range(5)],
                FLOPS_PER_FRAME_FWD * 3 * F_SHARD * STEPS / peak,
                "shard edit",
                samples=3,
            )
            rec.record("shard2_inversion_s", round(r_sinv.seconds, 3), reading=r_sinv)
            rec.record("shard2_edit_s", round(r_sedit.seconds, 3), reading=r_sedit)
            rec.record("shard2_samples", {
                "inversion_s": list(r_sinv.samples),
                "edit_s": list(r_sedit.samples),
            })
            try:
                _project = _tools_import("projection").project
                proj = _project(inv_live_s, edit_live_s, steps=STEPS, frames=F,
                                shard_inv_s=r_sinv.seconds,
                                shard_edit_s=r_sedit.seconds)
                rec.record("projected_v5e4_s", proj["projected_v5e4_s"],
                           derived=(r_linv, r_ledit, r_sinv, r_sedit))
                rec.record("projected_v5e4_range_s", proj["projected_v5e4_range_s"],
                           derived=(r_linv, r_ledit, r_sinv, r_sedit))
                rec.record("projected_v5e4_efficiency", proj["parallel_efficiency"],
                           derived=(r_linv, r_ledit, r_sinv, r_sedit))
                rec.record("projected_v5e4_model",
                           proj["assumptions"]["compute_scaling"],
                           derived=(r_linv, r_ledit, r_sinv, r_sedit))
            except Exception as e:  # noqa: BLE001
                print(f"[bench] shard projection failed: {e}", file=sys.stderr, flush=True)
            del ws, r_sinv, r_sedit
            jax.clear_caches()

            # warm inversion input for the null phases — plus a spare
            # trajectory as the value-fresh retry input for the floor check —
            # while the inversion executable is still loaded, then drop the
            # fast-phase programs: later phases need the HBM close to free
            warm_traj = hard_block(invert(params, x_warm))
            x_extra = jax.random.normal(jax.random.fold_in(base, 55), x0.shape, x0.dtype)
            traj_extra = hard_block(invert(params, x_extra))
            warm_last = warm_traj[-1]
            jax.clear_caches()

            # null-text inversion, FIXED-WORK variant (VERDICT r3 item 3):
            # exactly 3 inner Adam steps per outer step, no early stop — the
            # work is weight-independent, so this wall-clock is stable where
            # the reference-faithful early-stopped run (measured LAST, below)
            # spreads 157–418 s with the random stop point. The per-inner-
            # step ms includes the 2 per-outer forwards (cond + final uncond)
            # smeared in — disclosed, and constant across runs.
            INNER_FIXED = 3

            def null_opt(p, tr, *, inner, early_stop):
                # return_losses: the final inner-loop reconstruction loss per
                # outer step is the optimization objective itself — the
                # direct parity metric between this fixed-work variant and
                # the reference-faithful early-stopped run measured LAST
                return null_text_optimization(
                    fn_remat, p, sched, tr, cond[:1], uncond[None],
                    num_inference_steps=STEPS, guidance_scale=7.5, outer_chunk=10,
                    num_inner_steps=inner, early_stop=early_stop,
                    return_losses=True,
                )

            # no separate warm run: the chunk program loads from the
            # persistent compile cache inside the first measured call (a few
            # seconds of over-statement on a ~60 s reading, disclosed here;
            # a second full execution would cost the driver's budget more)
            r_nfix = measure_with_floor(
                lambda tr: null_opt(params, tr, inner=INNER_FIXED, early_stop=False),
                [traj, traj_extra],
                # per outer step: 2 forwards + INNER_FIXED × (forward + a
                # backward that is ≥ 2 forward-equivalents)
                (2 + 3 * INNER_FIXED) * STEPS * F * FLOPS_PER_FRAME_FWD / peak,
                "null-text fixed",
            )
            (null_seq, nfix_losses), nfix_s = r_nfix.out, r_nfix.seconds
            rec.record("null_text_fixed3_s", round(nfix_s, 3), reading=r_nfix)
            rec.record("null_text_inner_step_ms",
                       round(nfix_s / (STEPS * INNER_FIXED) * 1e3, 1),
                       derived=(r_nfix,))
            # reconstruction-parity evidence, part 1: the final inner-loop
            # loss per outer step IS the optimization objective
            # (‖x̂_{t-1} − x_{t-1}‖², run_videop2p.py:596) — comparable to
            # the early-stopped variant's losses recorded at the end
            nfl = nfix_losses.astype(jnp.float32)
            rec.record("null_fixed3_recon_loss_mean",
                       float(jnp.mean(nfl)), derived=(r_nfix,))
            rec.record("null_fixed3_recon_loss_max",
                       float(jnp.max(nfl)), derived=(r_nfix,))
            null_traj_last = r_nfix.x_used[-1]
            null_traj_x0 = r_nfix.x_used[0]  # trajectory[0] is x_0
            jax.clear_caches()

            # official-mode controlled edit (full CFG + per-step null
            # injection), driven by the fixed-3 embeddings — the e2e of
            # record is summed right below; the early-stopped variant at
            # the end contributes only the A/B comparison
            edit_official = jax.jit(
                lambda p, xt, ns: edit_sample(
                    fn, p, sched, xt, cond, uncond,
                    num_inference_steps=STEPS, ctx=ctx, source_uses_cfg=True,
                    null_uncond_embeddings=ns,
                )
            )
            hard_block(edit_official(params, warm_last, null_seq))
            r_off = measure_with_floor(
                lambda xt: edit_official(params, xt, null_seq),
                # value-fresh x_T per attempt
                [null_traj_last, warm_last + 0.001],
                4 * F * STEPS * FLOPS_PER_FRAME_FWD / peak,  # full CFG: 4 streams
                "official edit",
            )
            out_off, edit_off_s = r_off.out, r_off.seconds
            rec.record("official_edit_s", round(edit_off_s, 3), reading=r_off)
            # reconstruction-parity evidence, part 2: the official edit's
            # stream 0 is the CFG reconstruction driven by the fixed-3 null
            # embeddings — its MSE/PSNR against the inversion input x_0 is
            # the end-to-end reconstruction quality of the fixed-work
            # variant. Only valid when the ACCEPTED attempt ran on the
            # fixed-3 trajectory's own x_T (measure_with_floor can accept a
            # retry on warm_last+0.001, whose x_0 is a different latent —
            # the MSE would then compare unrelated reconstructions); the
            # sub-floor-retry case recomputes on the right input outside
            # the timing window.
            if r_off.x_used is null_traj_last:
                recon = out_off[0]
            else:
                recon = hard_block(
                    edit_official(params, null_traj_last, null_seq)
                )[0]
            rec_mse = float(jnp.mean(
                (recon.astype(jnp.float32)
                 - null_traj_x0[0].astype(jnp.float32)) ** 2
            ))
            rec.record("official_fixed3_recon_mse", round(rec_mse, 6),
                       derived=(r_off, r_nfix))
            import math as _math

            span = float(
                jnp.max(null_traj_x0.astype(jnp.float32))
                - jnp.min(null_traj_x0.astype(jnp.float32))
            )
            rec.record(
                "official_fixed3_recon_psnr_db",
                round(10 * _math.log10(span * span / max(rec_mse, 1e-12)), 2),
                derived=(r_off, r_nfix),
            )
            del recon
            # the official-mode number OF RECORD uses the fixed-work null
            # variant: deterministic wall-clock (the early-stopped run
            # spread 157–418 s with the weight-dependent stop point across
            # r3/r4 records) with the parity evidence above and the
            # early-stop A/B below. VERDICT r4 item 4.
            official_fixed = inv_live_s + nfix_s + edit_off_s
            rec.record("official_edit_e2e_s", round(official_fixed, 3),
                       derived=(r_linv, r_nfix, r_off))
            rec.record("official_null_variant",
                       f"fixed {INNER_FIXED} inner steps, no early stop")
            rec.record("official_vs_baseline",
                       round(V100_OFFICIAL_EDIT_S / official_fixed, 2),
                       derived=(r_linv, r_nfix, r_off))

            # mixed-precision null variant, same fixed-3 work, through the
            # FUSED single-dispatch donated-carry program (the
            # inversion.py tentpole path): bf16 UNet forwards, fp32
            # scheduler/Adam/loss islands. The fp32 variant above keeps the
            # host-chunked program (continuity with r3-r5 records AND the
            # watchdog-safe path for the slow fp32 inner loop); the mixed
            # program is ~3-4x shorter per dispatch, which is what makes
            # the single device call viable.
            del out_off
            jax.clear_caches()

            def null_opt_mixed(p, tr):
                return null_text_optimization_fused(
                    fn_remat, p, sched, tr, cond[:1], uncond[None],
                    num_inference_steps=STEPS, guidance_scale=7.5,
                    num_inner_steps=INNER_FIXED, early_stop=False,
                    null_text_precision="mixed",
                    # traj/traj_extra feed the early-stop phase below — the
                    # trajectory buffers must survive this program
                    donate=False,
                    return_stats=True,
                )

            r_nmix = measure_with_floor(
                lambda tr: null_opt_mixed(params, tr),
                [traj, traj_extra],
                # same FLOP count as the fp32 fixed-3 phase; bf16 raises
                # achievable MFU, not the MFU=1 floor
                (2 + 3 * INNER_FIXED) * STEPS * F * FLOPS_PER_FRAME_FWD / peak,
                "null-text fixed mixed",
            )
            (_, nmix_stats), nmix_s = r_nmix.out, r_nmix.seconds
            rec.record("null_text_fixed3_mixed_s", round(nmix_s, 3),
                       reading=r_nmix)
            # parity evidence on the SAME objective: the mixed loss mean
            # vs the fp32 loss mean is the disclosed precision cost
            nml = nmix_stats["final_loss"].astype(jnp.float32)
            rec.record("null_mixed_recon_loss_mean",
                       float(jnp.mean(nml)), derived=(r_nmix,))
            rec.record("null_recon_loss_ratio_mixed_vs_fp32",
                       round(float(jnp.mean(nml)
                                   / jnp.maximum(jnp.mean(nfl), 1e-12)), 3),
                       derived=(r_nmix, r_nfix))
            # structural null-text variants (ISSUE 8): the closed-form
            # amortized substitute (zero inner Adam steps, one forward per
            # outer step) and the joint-refinement hybrid (K=3 batched
            # across all outer steps), both through the same fused program
            # path and both with reconstruction parity recorded against the
            # SAME x_0 via the already-compiled official edit
            mode_seconds = {}
            for mode, floor_fwd_eq in (("amortized", 1), ("hybrid", 1 + 3 * 3)):
                jax.clear_caches()

                def null_opt_mode(p, tr, _m=mode):
                    return null_text_optimization_fused(
                        fn_remat, p, sched, tr, cond[:1], uncond[None],
                        num_inference_steps=STEPS, guidance_scale=7.5,
                        null_text_mode=_m, hybrid_inner_steps=3,
                        donate=False, return_stats=True,
                    )

                r_m = measure_with_floor(
                    lambda tr: null_opt_mode(params, tr),
                    [traj, traj_extra],
                    floor_fwd_eq * STEPS * F * FLOPS_PER_FRAME_FWD / peak,
                    f"null-text {mode}",
                )
                (null_seq_m, m_stats), m_s = r_m.out, r_m.seconds
                rec.record(f"null_text_{mode}_s", round(m_s, 3), reading=r_m)
                rec.record(
                    f"null_{mode}_recon_loss_mean",
                    float(jnp.mean(m_stats["final_loss"].astype(jnp.float32))),
                    derived=(r_m,),
                )
                # parity evidence on the END-TO-END reconstruction: the CFG
                # replay driven by this mode's embeddings vs the same x_0
                # the fixed-3 record used (official_fixed3_recon_psnr_db)
                recon_m = hard_block(
                    edit_official(params, null_traj_last, null_seq_m)
                )[0]
                mse_m = float(jnp.mean(
                    (recon_m.astype(jnp.float32)
                     - null_traj_x0[0].astype(jnp.float32)) ** 2
                ))
                rec.record(
                    f"official_{mode}_recon_psnr_db",
                    round(10 * _math.log10(span * span / max(mse_m, 1e-12)), 2),
                    derived=(r_m, r_off),
                )
                mode_seconds[mode] = m_s
                del null_seq_m, m_stats, recon_m, r_m

            # all four variants' e2e + per-inner-step + vs-baseline in one
            # schema (CPU-tested, so the record shape cannot drift)
            for k, v in official_e2e_records(
                inv_live_s, edit_off_s,
                null_fp32_s=nfix_s, null_mixed_s=nmix_s,
                null_amortized_s=mode_seconds.get("amortized"),
                null_hybrid_s=mode_seconds.get("hybrid"),
                inner_steps=STEPS * INNER_FIXED,
            ).items():
                rec.record(k, v, derived=(r_linv, r_nfix, r_nmix, r_off))
            # per-mode inner-loop flop totals from the straight-line unit
            # analyses (CPU subprocess — flop counts are backend-blind);
            # the ISSUE-8 ≥3× acceptance reads these reduction ratios
            record_null_text_flops(rec)
            # per-frame-count ring comm/flop records + the Megatron tp
            # pairing (ISSUE 10) — static counts, recorded on-TPU rounds
            # too so the scale-out evidence never skips a round
            record_frame_scaling(rec)
            # streaming-window evidence (ISSUE 12) — likewise every round
            record_streaming_scaling(rec)
            del nmix_stats, r_nmix

            # Stage-1 tuning step on a cleared chip (its grad program +
            # optimizer state need the HBM to themselves)
            del null_seq
            jax.clear_caches()
            tune_cfg = TuneConfig()
            tx = make_optimizer(tune_cfg)
            # the real Stage-1 configuration: per-block remat AND the chunked
            # frame-attention kernel — a dense N² attention backward OOMs
            # (cli/run_tuning.py builds the same)
            model_train = UNet3DConditionModel(
                config=UNet3DConfig.sd15(
                    gradient_checkpointing=True, frame_attention="chunked",
                    group_norm=gn_impl,
                ),
                dtype=jnp.bfloat16,
            )
            fn_r = make_unet_fn(model_train)
            # the state's param buffers must be COPIES: steps_fn donates its
            # input state, and the original `params` tree is still used by
            # the long-video and early-stop phases below — donating shared
            # buffers would invalidate them
            state = TrainState.create(
                jax.tree.map(jnp.copy, {k: v for k, v in params["params"].items()}),
                tx, tune_cfg.trainable_modules,
            )
            ddpm = DDPMScheduler.create_sd()
            k3, k4, k5 = jax.random.split(jax.random.fold_in(base, 99), 3)
            lat_train = jax.random.normal(k3, (1, F, 64, 64, 4))
            # the production path (cli/run_tuning.py, steps_per_call=100):
            # TRAIN_STEPS steps as ONE scanned device program: one dispatch
            # per program instead of one per step (round-4 record: 384
            # ms/step device vs 456-794 ms wall as a Python loop). The state is
            # DONATED: the carry tree (params + Adam moments) would
            # otherwise be held twice (in + out) and copied.
            TRAIN_STEPS = 100
            steps_fn = jax.jit(
                lambda s, k: train_steps(
                    fn_r, tx, s, ddpm, lat_train, cond[:1], k,
                    num_steps=TRAIN_STEPS,
                ),
                donate_argnums=(0,),
            )
            state, _ = steps_fn(state, k4)  # compile + first chunk
            hard_block(state.trainable)
            holder = {"state": state, "off": 0}

            def tune_loop(_):
                # the evolving state + per-attempt key offset keep every
                # chunk's args value-fresh across retries
                s, chunk_losses = steps_fn(
                    holder["state"], jax.random.fold_in(k5, holder["off"])
                )
                holder["state"], holder["off"] = s, holder["off"] + 1
                return chunk_losses[-1]

            # per-step floor: forward + backward ≥ 3 forward-equivalents (remat
            # recompute adds more; 3× is the conservative bound)
            r_tune = measure_with_floor(
                tune_loop,
                [None, None],
                TRAIN_STEPS * 3 * F * FLOPS_PER_FRAME_FWD / peak,
                "tune steps",
            )
            loss_tr, tune_s = r_tune.out, r_tune.seconds
            rec.record("tune_step_ms", round(tune_s / TRAIN_STEPS * 1e3, 1), reading=r_tune)
            # divide by the raw reading: the rounded dict entry is 0.0 exactly in
            # the degraded-measurement case the suspect flag exists to survive
            rec.record("tune_step_vs_t4", round(4.0 * TRAIN_STEPS / max(tune_s, 1e-9), 1),
                       derived=(r_tune,))
            assert bool(jnp.isfinite(loss_tr)), "non-finite train loss"
            del state, holder
            jax.clear_caches()

            # Long-video working point (BASELINE configs 3/5: tiger-forest is
            # 24 frames; the 32-frame edit is the v5e-8 case): 24-frame fast
            # edit on ONE chip with the fused Pallas kernel (dense frame
            # attention cannot run here — the 64²-site scores alone are
            # 3·24·8·4096² bf16 ≈ 19 GB > HBM). Measured for REAL at 50
            # steps (VERDICT r4 item 5 — r4's 10-step extrapolation must not
            # replace a measurement of record), CACHED mode first. The
            # capture is NOT linear in frames: the temporal tree holds an
            # F×F map per spatial position (8f: 0.6 GiB → 24f: 5.8 GiB;
            # cross maps are linear, 2.5 → 7.4 GiB), so bf16 24f maps are
            # ~13 GiB — over one chip next to the params; the escalating
            # budget rule below lands on float8 temporal storage
            # (~10.3 GiB). A RESOURCE_EXHAUSTED falls back to the live
            # 3-stream path, and the record says which mode and storage
            # dtype ran.
            F_LONG = 24
            profiling.reset()  # long-video config: fresh phase records
            long_mode = "cached"
            try:
                # escalating per-chip budget rule (same helper as the CLI);
                # the probe is shape-only — eval_shape params, no device init
                from videop2p_tpu.models import (
                    UNet3DConditionModel as _UNet,
                    UNet3DConfig as _UCfg,
                )
                from videop2p_tpu.pipelines import make_unet_fn as _mk_fn
                from videop2p_tpu.pipelines.cached import (
                    capture_windows as _cap_windows,
                )
                from videop2p_tpu.pipelines.fast import (
                    capture_shapes as _cap_shapes,
                    choose_cached_maps as _choose_maps,
                )

                _pm = _UNet(config=_UCfg.sd15(), dtype=jnp.bfloat16)
                _pfn = _mk_fn(_pm)
                _px = jnp.zeros((1, F_LONG, 64, 64, 4), jnp.bfloat16)
                _pc = jnp.zeros((1, 77, 768), jnp.bfloat16)
                _pshapes = jax.eval_shape(
                    _pm.init, jax.random.key(0), _px[:, :2], jnp.asarray(10), _pc
                )
                _cw_l24 = _cap_windows(ctx, STEPS)
                long_budget = float(os.environ.get(
                    "VIDEOP2P_BENCH_LONG24_MAPS_BUDGET_GB", "11"))
                _fits, _tm_dtype, _map_gb, _ = _choose_maps(
                    lambda dt: _cap_shapes(
                        _pfn, _pshapes, sched, _px, _pc, ctx,
                        num_inference_steps=STEPS,
                        cross_len=_cw_l24[0], self_window=_cw_l24[1],
                        temporal_maps_dtype=dt,
                    )[1],
                    budget_gb=long_budget,
                )
                if not _fits:
                    raise MemoryError(
                        f"24f capture maps {_map_gb:.1f} GiB exceed the "
                        f"{long_budget:.1f} GiB single-chip budget"
                    )
                rec.record("long24_maps_gb", round(_map_gb, 2))
                rec.record(
                    "long24_temporal_maps_dtype",
                    jnp.dtype(_tm_dtype).name if _tm_dtype is not None
                    else "bfloat16",
                )
                wl = build_fast_edit_working_point(
                    num_frames=F_LONG, num_steps=STEPS, cached=True,
                    temporal_maps_dtype=_tm_dtype, group_norm=gn_impl,
                )
                hard_block(wl.e2e_cached(wl.params, wl.x_warm))
                r_long = measure_with_floor(
                    lambda x: wl.e2e_cached(wl.params, x),
                    [wl.x0, wl.x0 + 0.001],  # value-fresh per attempt
                    # 1-stream capture inversion + 2-stream cached edit
                    3 * F_LONG * STEPS * FLOPS_PER_FRAME_FWD / peak,
                    "long24 cached e2e",
                )
            except Exception as e:  # noqa: BLE001 — OOM → live fallback
                print(f"[bench] long24 cached mode failed ({type(e).__name__}) "
                      "— measuring the live path", file=sys.stderr, flush=True)
                long_mode = "live"
                jax.clear_caches()
                wl = build_fast_edit_working_point(
                    num_frames=F_LONG, num_steps=STEPS, frame_attention="auto",
                    group_norm=gn_impl,
                )
                hard_block(wl.edit(wl.params, wl.invert(wl.params, wl.x_warm)[-1]))
                r_long = measure_with_floor(
                    lambda x: wl.edit(wl.params, wl.invert(wl.params, x)[-1]),
                    [wl.x0, wl.x0 + 0.001],
                    4 * F_LONG * STEPS * FLOPS_PER_FRAME_FWD / peak,  # 1+3 streams
                    "long24 live e2e",
                )
            out_long, long_s = r_long.out, r_long.seconds
            assert bool(jnp.isfinite(out_long.astype(jnp.float32)).all())
            rec.record("long24_fast_edit_e2e_s", round(long_s, 3), reading=r_long)
            rec.record("long24_mode", long_mode)
            rec.record("long24_frames_per_sec", round(F_LONG / long_s, 3),
                       derived=(r_long,))
            rec.drop("long24_fast_edit_e2e_s_extrapolated")  # measured now
            rec.drop("long24_fast_edit_10step_s")
            r_long = r_long._replace(out=None)
            del out_long, wl
            jax.clear_caches()

            # SDXL-shaped inflation stress (BASELINE config 4): one denoiser
            # forward at 8 frames × 128² latents (1024² pixels), 2048-dim
            # text context, ~3B params. The tree is initialized DIRECTLY in
            # bf16 from its eval_shape skeleton — round 2's
            # f32-init-then-donated-cast still transiently held ~18 GB and
            # died RESOURCE_EXHAUSTED on the 16 GB chip. Wall-clock is
            # weight-value-independent, so the leaves don't need flax's exact
            # initializers — only finite activations (ones for norm scales,
            # zeros for biases, small normals elsewhere).
            from videop2p_tpu.models import UNet3DConditionModel, UNet3DConfig
            from videop2p_tpu.pipelines import make_unet_fn

            # fused kernel: SDXL's 64-wide heads fit its VMEM tiles with no
            # padding waste (on-chip readings: fused 723-756 ms vs chunked
            # 837-894 ms across runs)
            sx_model = UNet3DConditionModel(
                config=UNet3DConfig.sdxl(frame_attention="auto",
                                         group_norm=gn_impl),
                dtype=jnp.bfloat16,
            )
            ks0, ks1, ks2, ks3 = jax.random.split(jax.random.fold_in(base, 77), 4)
            sx = jax.random.normal(ks0, (1, F, 128, 128, 4), jnp.bfloat16)
            sx_txt = jax.random.normal(ks1, (1, 77, 2048), jnp.bfloat16)
            sx_shapes = jax.eval_shape(
                sx_model.init, jax.random.key(0), sx[:, :2], jnp.asarray(10), sx_txt
            )
            sx_leaves, sx_treedef = jax.tree_util.tree_flatten_with_path(sx_shapes)

            def _init_bf16(key):
                leaves = []
                for i, (path, s) in enumerate(sx_leaves):
                    name = str(path[-1])
                    if "scale" in name:
                        leaves.append(jnp.ones(s.shape, jnp.bfloat16))
                    elif "bias" in name:
                        leaves.append(jnp.zeros(s.shape, jnp.bfloat16))
                    else:
                        leaves.append(0.02 * jax.random.normal(
                            jax.random.fold_in(key, i), s.shape, jnp.bfloat16))
                return jax.tree_util.tree_unflatten(sx_treedef, leaves)

            sx_params = jax.jit(_init_bf16)(ks2)
            sx_fn = make_unet_fn(sx_model)
            sx_fwd = jax.jit(lambda p, s: sx_fn(p, s, jnp.asarray(500), sx_txt)[0])
            hard_block(sx_fwd(sx_params, jax.random.normal(ks3, sx.shape, sx.dtype)))
            # floor from a safe FLOP lower bound: SDXL-base 2-D is ~2.6 TF
            # per image at 128² latents, and the 3-D variant adds frame +
            # temporal attention on top — so ≥ 2.6 TF/frame-forward
            r_sx = measure_with_floor(
                lambda s: sx_fwd(sx_params, s),
                [sx, sx + 0.001],
                8 * 2.6e12 / peak,
                "sdxl forward",
            )
            sx_out, sx_s = r_sx.out, r_sx.seconds
            assert bool(jnp.isfinite(sx_out.astype(jnp.float32)).all())
            rec.record("sdxl_fwd_ms", round(sx_s * 1e3, 0), reading=r_sx)
            rec.record("sdxl_params_b", round(
                sum(s.size for _, s in sx_leaves) / 1e9, 2
            ))
            del sx_out

            # SDXL CONTROLLED edit step (VERDICT r3 item 8): one refine +
            # equalizer step through the fast-mode 3-stream batch at 128²
            # latents / 2048-dim context — the controlled sites' materialized
            # probabilities at this shape are the actual memory risk BASELINE
            # config 4 stresses (the biggest, a 64²-query site, holds
            # B·F×H×4096×77 per instance).
            from videop2p_tpu.control import make_controller
            from videop2p_tpu.utils.tokenizers import WordTokenizer

            sx_ctx = make_controller(
                ["a rabbit is jumping on the grass",
                 "a origami rabbit is jumping on the grass"],
                WordTokenizer(), num_steps=1,
                is_replace_controller=False,
                cross_replace_steps=1.0, self_replace_steps=1.0,
                equalizer_params={"words": ["origami"], "values": [2.0]},
            )
            sx_cond2 = jax.random.normal(
                jax.random.fold_in(base, 78), (2, 77, 2048), jnp.bfloat16
            )
            sx_unc = jnp.zeros((77, 2048), jnp.bfloat16)
            sx_edit1 = jax.jit(
                lambda p, xt: edit_sample(
                    sx_fn, p, sched, xt, sx_cond2, sx_unc,
                    num_inference_steps=1, ctx=sx_ctx, source_uses_cfg=False,
                )
            )
            hard_block(sx_edit1(sx_params, sx + 0.002))
            r_sxc = measure_with_floor(
                lambda xt: sx_edit1(sx_params, xt),
                [sx, sx + 0.001],
                3 * 8 * 2.6e12 / peak,  # 3 streams × 8 frames × SDXL-fwd bound
                "sdxl controlled step",
            )
            assert bool(jnp.isfinite(r_sxc.out.astype(jnp.float32)).all())
            rec.record("sdxl_ctrl_step_ms", round(r_sxc.seconds * 1e3, 0),
                       reading=r_sxc)
            del sx_params, r_sxc
            jax.clear_caches()

            # latency-vs-quality step frontier (ISSUE 8 / ROADMAP item 3):
            # 20- and 8-step cached fast-path variants run e2e from ONE
            # 50-step inversion via exact timestep subsets, each scored
            # against the full-step edit with the obs/quality metrics —
            # the frontier table docs/PERF_ANALYSIS.md renders
            # student rows ride the same frontier (ISSUE 16): identity-init
            # head = the untrained-student baseline, composed with w8+reuse
            from videop2p_tpu.models import UNet3DConfig
            from videop2p_tpu.train.distill import init_time_head

            frontier, _ = run_step_frontier(
                fn, params, sched, cond, uncond, x0,
                base_steps=STEPS, step_counts=(STEPS, 20, 8),
                variants=((2, "off", "off"), (2, "w8", "uniform:2")),
                student_head=init_time_head(
                    jax.random.key(0), UNet3DConfig.sd15()
                ),
            )
            assert all(r["src_err"] == 0.0 for r in frontier), frontier
            rec.record("latency_quality_frontier", frontier)
            rec.record("latency_quality_frontier_backend",
                       jax.devices()[0].platform)
            jax.clear_caches()

            # measured served latency (ISSUE 14): the loadgen + engine
            # stack end to end — queueing-inclusive client p50/p99 with the
            # trace-derived segment split; a CPU subprocess on purpose (the
            # serving path's contention story, not this chip's step wall)
            served = collect_served_latency(timeout_s=600.0)
            if served:
                rec.record("served_latency", served)

            # reference-faithful null-text inversion LAST (50 outer × ≤10
            # early-stopped inner steps, run_videop2p.py:580-612): its
            # weight-dependent 157–418 s spread is disclosed in README; the
            # stable number of record is null_text_fixed3_s above. Last so a
            # driver timeout costs only this tail, not the whole record.
            r_null = measure_with_floor(
                lambda tr: null_opt(params, tr, inner=10, early_stop=True),
                [traj, traj_extra],
                # even if every inner loop stops at 0 iterations, each outer
                # step runs 2 forwards (cond + final uncond)
                2 * STEPS * F * FLOPS_PER_FRAME_FWD / peak,
                "null-text",
            )
            (_, es_losses), null_s = r_null.out, r_null.seconds
            rec.record("null_text_wall_s", round(null_s, 3), reading=r_null)
            # no warm execution precedes this phase (a second full run costs
            # 157–418 s of driver budget): on a cold compile cache the
            # early-stop chunk program's compile/load lands INSIDE the
            # reading. That only overstates our time (conservative for every
            # derived speedup); recorded so the provenance is machine-readable
            rec.record("null_text_warm", "none — may include compile-cache load")
            # reconstruction-parity evidence, part 3: the early-stopped
            # variant's final losses on the SAME objective — the ratio to
            # the fixed-3 losses is the disclosed parity bound of the
            # official-mode record above
            esl = es_losses.astype(jnp.float32)
            rec.record("null_earlystop_recon_loss_mean",
                       float(jnp.mean(esl)), derived=(r_null,))
            rec.record("null_recon_loss_ratio_fixed3_vs_earlystop",
                       round(float(jnp.mean(nfl) / jnp.maximum(jnp.mean(esl), 1e-12)), 3),
                       derived=(r_nfix, r_null))
            official_es = inv_live_s + null_s + edit_off_s
            rec.record("official_edit_e2e_earlystop_s", round(official_es, 3),
                       derived=(r_linv, r_null, r_off))
            # the early-stopped variant must carry a vs-baseline ratio too —
            # a reader comparing against the V100 official number must not
            # see only the (faster) fixed-work variant's ratio (ADVICE r5
            # item 5)
            rec.record("official_vs_baseline_earlystop",
                       round(V100_OFFICIAL_EDIT_S / official_es, 2),
                       derived=(r_linv, r_null, r_off))
            del r_null, traj, warm_traj, traj_extra
            jax.clear_caches()
            rec.drop("extended_error")  # this run's extended phases all passed

        except Exception as e:  # noqa: BLE001 — record, don't die
            rec.record("extended_error", f"{type(e).__name__}: {e}"[:300])
            print(f"[bench] extended phase failed: {e}", file=sys.stderr, flush=True)

        # refresh the compile provenance with the extended phases' compiles
        # (the pre-headline record only covered the fast phase)
        for k, v in ledger_bench_fields(
            ledger_path, bench_ledger.compile_seconds, execute_s=elapsed
        ).items():
            rec.record(k, v)

        # the full extended record also goes to stderr once (stdout stays the
        # single primary JSON line); bench_details.json was kept current
        # after every phase by DetailsRecorder
        print(json.dumps(rec.flush()), file=sys.stderr, flush=True)
    bench_ledger.close()


if __name__ == "__main__":
    main()
