"""RunLedger: one JSONL event stream per run.

Unifies what previously lived in three places (phase_timer prints,
MetricsLogger's metrics.jsonl, and nothing at all for compiles) into a
single machine-readable record of what a run compiled,
executed, and measured:

  * ``run_start`` — run_id, git sha, jax version, backend/device/mesh
    shape, caller metadata;
  * ``phase`` — emitted by ``utils.profiling.phase_timer`` whenever a
    ledger is active (no caller changes needed);
  * ``compile`` — XLA backend-compile durations via a process-wide
    ``jax.monitoring`` listener, attributed to the program label active
    at compile time (:func:`program_label` / :func:`instrumented_jit`);
  * ``program_call`` — per-jitted-program cache hit/miss + dispatch
    wall-clock from :func:`instrumented_jit`;
  * ``span`` — every timed region (:class:`videop2p_tpu.obs.spans.span`):
    phases, ``program.call`` with its ``program.trace`` / ``program.lower``
    / ``program.backend_compile`` / ``program.execute`` /
    ``program.analysis`` children, and the CLI's own sites;
  * ``telemetry`` — decoded in-program telemetry summaries
    (:mod:`videop2p_tpu.obs.telemetry`);
  * ``memory`` — per-device ``memory_stats()`` snapshots where the
    backend supports them (TPU yes, CPU records ``supported: false``).

Events append line-buffered, so a killed run keeps everything written so
far. ``tools/ledger_summary.py`` renders a ledger file as a table.
"""

from __future__ import annotations

import contextlib
import contextvars
import json
import os
import socket
import subprocess
import threading
import time
import uuid
from typing import Any, Dict, Iterator, List, Optional

import jax

from videop2p_tpu.obs.spans import Tracer, current_span, make_trace_id, span

__all__ = [
    "RunLedger",
    "current_ledger",
    "program_label",
    "instrumented_jit",
    "read_ledger",
    "analysis_enabled",
    "suppress_compile_events",
]

# the active-ledger stack: a run's one ledger; nested ones (tests) shadow it
_ACTIVE: List["RunLedger"] = []
_ACTIVE_LOCK = threading.Lock()

# program label attributed to compile events fired while it is set
_PROGRAM: contextvars.ContextVar[Optional[str]] = contextvars.ContextVar(
    "videop2p_obs_program", default=None
)

# set while the analysis asks for the call's executable: a backend compile
# fired there means jax did NOT hand back the call's build (`rebuilt`), and
# that second compile is the analysis', not the run's own work — recording
# it would double a run's compile totals
_SUPPRESS_COMPILE: contextvars.ContextVar[bool] = contextvars.ContextVar(
    "videop2p_obs_suppress_compile", default=False
)

_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
# jax's duration events → the child span each becomes under the open
# `program.call` / `program.analysis` (start = now − duration)
_COMPILE_SPANS = {
    "/jax/core/compile/jaxpr_trace_duration": "program.trace",
    "/jax/core/compile/jaxpr_to_mlir_module_duration": "program.lower",
    _COMPILE_EVENT: "program.backend_compile",
}
_CACHE_RETRIEVAL_EVENT = "/jax/compilation_cache/cache_retrieval_time_sec"
_CACHE_REQUEST_EVENT = "/jax/compilation_cache/compile_requests_use_cache"
_CACHE_HIT_EVENT = "/jax/compilation_cache/cache_hits"
# what the persistent cache said since this thread's last backend compile:
# the events fire inside the compile they belong to, just before its duration
_CACHE_SEEN = threading.local()
_LISTENER_INSTALLED = False

# kill-switch for the automatic compiled-program introspection (the reading
# of the executable behind every instrumented cache miss); the CLIs expose
# it as --no_program_analysis
_ANALYSIS_ENV = "VIDEOP2P_OBS_NO_ANALYSIS"


def analysis_enabled() -> bool:
    return os.environ.get(_ANALYSIS_ENV, "0") != "1"


def current_ledger() -> Optional["RunLedger"]:
    """The innermost active ledger, or None (the default — everything in
    this module is a no-op until a RunLedger is activated)."""
    with _ACTIVE_LOCK:
        return _ACTIVE[-1] if _ACTIVE else None


@contextlib.contextmanager
def program_label(name: str) -> Iterator[None]:
    """Attribute compile events fired inside this block to ``name`` —
    for programs that jit internally (the fused null-text program cache)
    where :func:`instrumented_jit` cannot wrap the jit call itself."""
    token = _PROGRAM.set(name)
    try:
        yield
    finally:
        _PROGRAM.reset(token)


@contextlib.contextmanager
def suppress_compile_events() -> Iterator[None]:
    """Compile events fired inside this block are NOT recorded — for an
    analysis that had to build the program again (``rebuilt``), which would
    otherwise double a run's compile totals."""
    token = _SUPPRESS_COMPILE.set(True)
    try:
        yield
    finally:
        _SUPPRESS_COMPILE.reset(token)


def _compile_sink() -> Optional[span]:
    """The open span a compile event becomes a child of: the innermost live
    one, past ``program.execute`` — which then starts over, since what ran
    before the compile ended was not the execution."""
    cur = current_span()
    if cur is None or cur.name != "program.execute":
        return cur
    cur.restart()
    return cur.parent


def _install_compile_listener() -> None:
    """Register the ONE process-wide pair of jax.monitoring listeners.

    Backend-compile durations go to the active ledger's ``compile`` events
    and totals, as ever. Besides, trace / lower / backend-compile durations
    fired under a program label become child spans of the open span
    (``program.trace``, ``program.lower``, ``program.backend_compile`` with
    ``cache_hit`` and ``cache_retrieval_s`` from the persistent cache's own
    events); fired with no label — every eager ``jnp`` op does — they are
    summed into ``unspanned_*`` counters of the open span, or of the ledger
    where none is open (``run_end`` carries those), never one line each. A
    lowering or a backend compile fired under ``program.analysis`` sets its
    ``rebuilt``: the analysis did not get the call's own build.
    jax has no per-listener unregister, so the listeners are permanent
    no-ops when no ledger is active rather than something we add/remove per
    run."""
    global _LISTENER_INSTALLED
    if _LISTENER_INSTALLED:
        return

    def on_duration(event: str, duration: float, **kw) -> None:
        if event == _CACHE_RETRIEVAL_EVENT:
            _CACHE_SEEN.retrieval_s = duration
            return
        name = _COMPILE_SPANS.get(event)
        if name is None:
            return
        led = current_ledger()
        if led is None:
            return
        program = _PROGRAM.get()
        backend = event == _COMPILE_EVENT
        if backend and not _SUPPRESS_COMPILE.get():
            led._on_compile(duration, program)
        attrs = {}
        if backend:  # popped either way: they belong to this compile only
            attrs["cache_hit"] = _CACHE_SEEN.__dict__.pop("hit", None)
            retrieval = _CACHE_SEEN.__dict__.pop("retrieval_s", None)
            if retrieval is not None:
                attrs["cache_retrieval_s"] = round(retrieval, 6)
        if not led.tracer.enabled:
            return
        sink = _compile_sink()
        if (sink is not None and sink.name == "program.analysis"
                and name != "program.trace"):
            sink.set(rebuilt=True)
        if program is not None and sink is not None:
            sink.child(name, time.time_ns() - int(duration * 1e9), duration,
                       **attrs)
            return
        counters = sink if sink is not None else led
        if backend:
            counters.count("unspanned_backend_compile_s", duration)
            counters.count("unspanned_backend_compiles")
        else:
            counters.count("unspanned_trace_lower_events")

    def on_event(event: str, **kw) -> None:
        if event == _CACHE_REQUEST_EVENT:
            _CACHE_SEEN.hit = False
        elif event == _CACHE_HIT_EVENT:
            _CACHE_SEEN.hit = True

    try:
        jax.monitoring.register_event_duration_secs_listener(on_duration)
        jax.monitoring.register_event_listener(on_event)
    except Exception:  # noqa: BLE001 — observability must never break a run
        return
    _LISTENER_INSTALLED = True


def _git_sha() -> Optional[str]:
    try:
        out = subprocess.run(
            ["git", "rev-parse", "--short", "HEAD"],
            capture_output=True, text=True, timeout=5.0,
            cwd=os.path.dirname(os.path.abspath(__file__)),
        )
    except (OSError, subprocess.TimeoutExpired):
        return None
    return out.stdout.strip() or None if out.returncode == 0 else None


class RunLedger:
    """Append-only JSONL event stream for one run.

    Use as a context manager (activates on enter, closes on exit) or call
    :meth:`activate` / :meth:`close` explicitly from long CLI mains. Every
    event carries ``t`` (seconds since run start, monotonic) and the
    ``run_start`` event anchors it to wall-clock.
    """

    def __init__(
        self,
        path: str,
        *,
        run_id: Optional[str] = None,
        mesh: Optional[Any] = None,
        meta: Optional[Dict[str, Any]] = None,
        device_info: bool = True,
        latency: bool = False,
        max_bytes: Optional[int] = None,
    ):
        self._opened_ns = time.time_ns()  # `process.ledger_open` starts
        self.path = path
        self.run_id = run_id or uuid.uuid4().hex[:12]
        os.makedirs(os.path.dirname(os.path.abspath(path)), exist_ok=True)
        self._fh = open(path, "a", buffering=1)  # line-buffered: kill-safe
        # size-aware rotation (ISSUE 14): streaming jobs append one JSONL
        # without limit — with max_bytes set, a write that would cross the
        # bound first shifts the file to <stem>.1.jsonl (older segments
        # shift up) and the fresh file opens with a ledger_rotated marker.
        # read_ledger() reads the chain back oldest-first.
        self.max_bytes = int(max_bytes) if max_bytes else None
        try:
            self._bytes = os.path.getsize(path)
        except OSError:
            self._bytes = 0
        self._rotations = 0
        self._lock = threading.Lock()
        # optional flight-recorder tee (obs/flight.py): an attached
        # FlightRecorder's bounded ring ALSO gets every event record; with
        # flight=None (the default) the written stream is bit-exact.
        self.flight: Optional[Any] = None
        # program-analysis observers (ISSUE 19): callbacks fired with
        # (program, record) on every program_analysis event (the serving
        # CostModel's); they never raise into the ledger.
        self.analysis_observers: List[Any] = []
        self._t0 = time.perf_counter()
        self._closed = False
        self._activated = False
        self.compile_seconds: List[float] = []  # drained by bench records
        # spans (obs/spans.py): every `span(...)` that finds this ledger
        # active writes through this tracer, under this run's trace id. The
        # serving engine puts its own tracer here, so that its `tracing`
        # switch governs every span of its ledger.
        self.trace_id = make_trace_id()
        self.tracer = Tracer(self, enabled=True)
        # compile events fired with no program label and no span open
        # (count()); `run_end` carries them
        self.counters: Dict[str, float] = {}
        # per-dispatch execute-timing reservoirs (obs/timing.py): opt-in
        # via the constructor (the CLIs' --latency) or the process-wide
        # VIDEOP2P_OBS_LATENCY env var; summaries flush as execute_timing
        # events on close (or explicitly via flush_execute_timing)
        self.latency = bool(latency)
        self._timing: Dict[str, Any] = {}
        self._timing_lock = threading.Lock()
        _install_compile_listener()

        start: Dict[str, Any] = {
            "run_id": self.run_id,
            "git_sha": _git_sha(),
            "jax_version": jax.__version__,
            "hostname": socket.gethostname(),
            "pid": os.getpid(),
            "wall_time": time.strftime("%Y-%m-%dT%H:%M:%SZ", time.gmtime()),
            "mesh": (list(getattr(mesh, "shape", mesh).values())
                     if hasattr(getattr(mesh, "shape", None), "values")
                     else mesh if mesh is None or isinstance(mesh, (str, list))
                     else str(mesh)),
        }
        if device_info:
            # a run that cannot name its device has no device: a failed
            # jax.devices() raises here instead of recording backend=None
            devs = jax.devices()
            start["backend"] = devs[0].platform
            start["device_count"] = len(devs)
            start["device_kind"] = devs[0].device_kind
        if meta:
            start.update(meta)
        self.event("run_start", **start)

    # ---- event writing ---------------------------------------------------

    def event(self, kind: str, /, **fields: Any) -> None:
        """Append one event; never raises (a full disk or closed handle
        must not take the run down with it). ``kind`` is positional-only
        so a field may itself be named ``kind`` (the ``fault`` events)."""
        rec = {"event": kind, "t": round(time.perf_counter() - self._t0, 4)}
        rec.update(fields)
        flight = self.flight
        if flight is not None:
            flight.record(rec)  # bounded ring append; never raises
        try:
            line = json.dumps(rec, default=str)
        except (TypeError, ValueError):
            line = json.dumps({"event": "encode_error", "kind": kind})
        data = line + "\n"
        with self._lock:
            if self._closed:
                return
            if (self.max_bytes is not None and self._bytes > 0
                    and self._bytes + len(data) > self.max_bytes):
                self._rotate_locked()
            try:
                self._fh.write(data)
                self._bytes += len(data)
            except (OSError, ValueError):
                pass

    def _rotate_locked(self) -> None:
        """Shift the full file aside and reopen fresh (caller holds the
        lock). ``<stem>.1.jsonl`` is the newest rotated segment; existing
        segments shift up first, logrotate-style. The new file opens with
        a ``ledger_rotated`` marker so readers (and humans) see the seam."""
        try:
            self._fh.close()
        except OSError:
            pass
        stem = (self.path[:-len(".jsonl")]
                if self.path.endswith(".jsonl") else self.path)
        try:
            n = 1
            while os.path.exists(f"{stem}.{n}.jsonl"):
                n += 1
            for i in range(n - 1, 0, -1):
                os.replace(f"{stem}.{i}.jsonl", f"{stem}.{i + 1}.jsonl")
            os.replace(self.path, f"{stem}.1.jsonl")
        except OSError:
            pass
        self._rotations += 1
        rotated_bytes, self._bytes = self._bytes, 0
        try:
            self._fh = open(self.path, "a", buffering=1)
        except OSError:
            return  # writes degrade to the event() guard's silent drop
        marker = {
            "event": "ledger_rotated",
            "t": round(time.perf_counter() - self._t0, 4),
            "run_id": self.run_id,
            "previous": f"{stem}.1.jsonl",
            "rotated_bytes": rotated_bytes,
            "index": self._rotations,
        }
        try:
            data = json.dumps(marker) + "\n"
            self._fh.write(data)
            self._bytes += len(data)
        except (OSError, ValueError):
            pass

    def phase(self, name: str, seconds: float, **fields: Any) -> None:
        self.event("phase", name=name, seconds=round(float(seconds), 4), **fields)
        # multi-host runs: additionally tag the measurement with the process
        # identity (host_phase events) so merged ledgers expose per-host
        # straggler skew (parallel/distributed.phase_skew). Single-host runs
        # skip it — the skew is trivially 0 and the events would only bloat.
        try:
            if jax.process_count() > 1:
                from videop2p_tpu.parallel.distributed import host_phase_record

                self.event("host_phase", **host_phase_record(name, seconds))
        except Exception:  # noqa: BLE001 — observability never breaks timing
            pass

    def telemetry(self, program: str, record: Dict[str, Any]) -> None:
        self.event("telemetry", program=program, **record)

    def program_analysis(self, program: str, record: Dict[str, Any]) -> None:
        """Record one compiled-program introspection record
        (obs.introspect.analyze_compiled/analyze_jitted) for ``program``.
        Registered ``analysis_observers`` (the serving CostModel) see the
        same (program, record) pair; an observer raising never blocks the
        event write."""
        if self.analysis_observers:
            for cb in list(self.analysis_observers):
                try:
                    cb(program, record)
                except Exception:  # noqa: BLE001 — obs never raises
                    pass
        self.event("program_analysis", program=program, **record)

    def comm_analysis(self, program: str, record: Dict[str, Any]) -> None:
        """Record one collective-communication accounting record
        (obs.comm.comm_analysis_record) for a sharded ``program``."""
        self.event("comm_analysis", program=program, **record)

    def device_telemetry(self, program: str, record: Dict[str, Any]) -> None:
        """Record a decoded per-device telemetry summary
        (obs.comm.summarize_device_stats) for ``program``."""
        self.event("device_telemetry", program=program, **record)

    def divergence(self, label: str, value: float, **fields: Any) -> None:
        """Record one cross-replica divergence measurement
        (obs.comm.replica_divergence) — must be 0.0; the COMM_RULES
        verdict has a zero noise floor."""
        self.event("divergence", label=label, value=float(value), **fields)

    def fault(self, kind: str, **fields: Any) -> None:
        """Record one fault observation (ISSUE 9): an injected fault
        firing (serve/faults.py FaultPlan), a retry, a watchdog timeout —
        anything the resilience layer absorbed or failed on. The
        end-of-run ``serve_health`` summary is what FAULT_RULES gate;
        these events are the per-incident trail."""
        self.event("fault", kind=kind, **fields)

    def breaker(self, state_from: str, state_to: str, **fields: Any) -> None:
        """Record one circuit-breaker transition (closed → open →
        half-open; serve/faults.py CircuitBreaker)."""
        self.event("breaker", state_from=state_from, state_to=state_to,
                   **fields)

    def timing_enabled(self) -> bool:
        """True when per-dispatch execute timing is on for this run —
        the constructor flag (--latency) or the process-wide env var."""
        from videop2p_tpu.obs.timing import latency_enabled

        return self.latency or latency_enabled()

    def record_execute(self, program: str, dispatch_s: float,
                       blocked_s: float,
                       trace_id: Optional[str] = None) -> None:
        """Accumulate one dispatch's (dispatch-return, block-until-ready)
        latencies into the program's bounded reservoir (obs/timing.py).
        ``trace_id`` (tracing on) links the reservoir's max/p99 exemplars
        back to the offending trace. Nothing is written until
        :meth:`flush_execute_timing` / close."""
        from videop2p_tpu.obs.timing import LatencyReservoir

        with self._timing_lock:
            res = self._timing.get(program)
            if res is None:
                res = self._timing[program] = LatencyReservoir()
        res.add(dispatch_s, blocked_s, trace_id)

    def execute_timing_summary(self) -> Dict[str, Dict[str, float]]:
        """Live per-program reservoir summaries WITHOUT writing events —
        what a serving ``/metrics`` endpoint polls between flushes.
        Programs with no recorded dispatches are omitted."""
        with self._timing_lock:
            items = sorted(self._timing.items())
        out: Dict[str, Dict[str, float]] = {}
        for program, res in items:
            try:
                summary = res.summary()
            except Exception:  # noqa: BLE001 — obs never kills a run
                continue
            if summary:
                out[program] = summary
        return out

    def flush_execute_timing(self) -> None:
        """One ``execute_timing`` event per program with recorded
        dispatches (count, dispatch/blocked p50/p95/p99/max, the
        dispatch-vs-blocked split). Reservoirs keep accumulating — a
        later flush supersedes (extract_run keeps the last event)."""
        for program, summary in self.execute_timing_summary().items():
            self.event("execute_timing", program=program, **summary)

    def count(self, key: str, n: float = 1) -> None:
        with self._timing_lock:
            self.counters[key] = self.counters.get(key, 0) + n

    def _on_compile(self, seconds: float, program: Optional[str]) -> None:
        self.compile_seconds.append(float(seconds))
        self.event("compile", seconds=round(float(seconds), 4),
                   program=program, metric="backend_compile")

    def memory_snapshot(self, note: Optional[str] = None) -> None:
        """Per-device memory_stats + live-buffer census.

        Every local device gets an entry keyed by id/coords/process (TPU
        coords; None on CPU) so sharded runs see per-chip residency, not
        just a process total. Where the backend has no ``memory_stats``
        (CPU) the stats fields are None, ``supported`` is false, and the
        per-device ``live_bytes`` census (summed over each array's
        addressable shards) still distinguishes the devices — the schema
        stays stable across backends."""
        per_dev_live: Dict[int, int] = {}
        live = None
        try:
            arrs = jax.live_arrays()
            live = {"count": len(arrs),
                    "bytes": int(sum(a.nbytes for a in arrs))}
            for a in arrs:
                try:
                    for sh in a.addressable_shards:
                        did = sh.device.id
                        per_dev_live[did] = (
                            per_dev_live.get(did, 0) + int(sh.data.nbytes)
                        )
                except Exception:  # noqa: BLE001
                    continue
        except Exception:  # noqa: BLE001
            pass
        devices = []
        supported = False
        try:
            for d in jax.local_devices():
                try:
                    ms = d.memory_stats()
                except Exception:  # noqa: BLE001
                    ms = None
                supported = supported or bool(ms)
                coords = getattr(d, "coords", None)
                devices.append({
                    "device": d.id,
                    "coords": list(coords) if coords is not None else None,
                    "process_index": getattr(d, "process_index", None),
                    "bytes_in_use": (ms or {}).get("bytes_in_use"),
                    "peak_bytes_in_use": (ms or {}).get("peak_bytes_in_use"),
                    "bytes_limit": (ms or {}).get("bytes_limit"),
                    "live_bytes": per_dev_live.get(d.id),
                })
        except Exception:  # noqa: BLE001
            pass
        self.event("memory", note=note, supported=supported,
                   devices=devices, live_arrays=live)

    # ---- lifecycle -------------------------------------------------------

    def activate(self) -> "RunLedger":
        """Push onto the active stack so phase_timer / the compile listener
        / instrumented_jit find this ledger."""
        with _ACTIVE_LOCK:
            if not self._activated:
                _ACTIVE.append(self)
                self._activated = True
                if self._opened_ns is not None:  # the first activation only
                    self.tracer.process_pending = (self._opened_ns,
                                                   time.time_ns())
                    self._opened_ns = None
        return self

    def close(self) -> None:
        with _ACTIVE_LOCK:
            if self in _ACTIVE:
                _ACTIVE.remove(self)
            self._activated = False
        with self._lock:
            if self._closed:
                return
        try:
            self.flush_execute_timing()
        except Exception:  # noqa: BLE001 — closing must always succeed
            pass
        self.event("run_end", compile_events=len(self.compile_seconds),
                   **self.counters)
        with self._lock:
            self._closed = True
            try:
                self._fh.close()
            except OSError:
                pass

    def __enter__(self) -> "RunLedger":
        return self.activate()

    def __exit__(self, *exc) -> None:
        self.close()

    def __del__(self):  # best-effort: events were line-flushed already
        try:
            if not self._closed:
                self.close()
        except Exception:  # noqa: BLE001
            pass


def _analyze_into_ledger(led: "RunLedger", jitted, program: str,
                         abstract_args, abstract_kwargs) -> None:
    """Read the program the call just built into ``program_analysis`` (cost/
    memory analysis, HLO fingerprint, instruction histogram) and — for
    sharded programs — ``comm_analysis`` (collective counts/bytes and
    sharding specs, obs/comm.py) events.

    ``lower(...).compile()`` on ABSTRACT arguments (the executed call may
    have donated its buffers) that are the call's exact signature
    (``introspect.abstractify_args``: weak types, committed shardings), so
    jax hands back the lowering and the executable the call made — the
    program that RAN, sharded or not — and nothing is traced, lowered or
    loaded a second time. Should it build all the same (the span's
    ``rebuilt``), that compile stays out of the run's compile totals. The
    module's text is printed once, for both records. A failed lower/compile
    emits a ``program_analysis_skipped`` event with the reason instead of
    dropping the record on the floor; nothing here ever breaks the call
    that triggered it.
    """
    from videop2p_tpu.obs import comm, introspect

    with suppress_compile_events():
        compiled = introspect.compile_abstract(
            jitted, *abstract_args, **abstract_kwargs
        )
    if compiled is None:
        led.event("program_analysis_skipped", program=program,
                  reason="lower_or_compile_failed")
        return
    try:
        with span("analysis.text"):
            text = compiled.as_text()
    except Exception:  # noqa: BLE001 — each reader then tries for itself
        text = None
    rec = introspect.analyze_compiled(compiled, hlo_text=text)
    if rec:
        led.program_analysis(program, rec)
    with span("analysis.comm"):
        comm_rec = comm.comm_analysis_record(compiled, hlo_text=text)
    if comm_rec is not None and (
        comm_rec.get("num_partitions", 1) > 1
        or comm_rec.get("collective_count", 0)
    ):
        led.comm_analysis(program, comm_rec)


def instrumented_jit(fun, *, program: str, analyze: bool = True,
                     span_attrs=None, **jit_kwargs):
    """``jax.jit`` plus ledger instrumentation.

    Each call through the wrapper runs under a ``program.call`` span
    (``program``, ``cache_miss``, and whatever ``span_attrs(*args,
    **kwargs)`` returns — the tuning CLI's ``steps``) with the children
    ``program.trace`` / ``program.lower`` / ``program.backend_compile``
    (jax's own durations, on a miss), ``program.execute`` and, on a miss,
    ``program.analysis``; and records a ``program_call`` event with the
    program label, whether the call MISSED the jit cache (compiled), and
    the dispatch wall-clock; compile events fired inside the call are
    attributed to the label. On a cache miss (with ``analyze=True``, the
    default) the executable that call built is read into a
    ``program_analysis`` event — XLA's cost/memory analysis, a stable
    optimized-HLO fingerprint, and an instruction histogram
    (obs/introspect.py) — which is what ``obs/history.py`` and
    ``tools/obs_diff.py`` diff across runs. The program is built ONCE: the
    analysis asks jax for the call's exact signature (the arguments'
    abstract values, taken before the call deletes what it donates) and is
    handed the call's own lowering and executable; ``program.analysis``
    says ``rebuilt: false``, and ``true`` where a lowering or a backend
    compile fired under it after all. A sharded call's signature carries
    its shardings, so the analysis describes the partitioned SPMD program
    and additionally emits a ``comm_analysis`` event with per-kind
    collective counts/bytes (obs/comm.py). When the analysis is
    disabled or cannot run, a ``program_analysis_skipped`` event records
    the reason — a missing record is a statement, never silence. Disable
    process-wide with ``VIDEOP2P_OBS_NO_ANALYSIS=1`` (the CLIs'
    ``--no_program_analysis``). With no active ledger the wrapper adds one
    attribute lookup and nothing else — the jitted callable is returned
    straight through.
    """
    jitted = jax.jit(fun, **jit_kwargs)

    def wrapper(*args, **kwargs):
        led = current_ledger()
        if led is None:
            return jitted(*args, **kwargs)
        try:
            before = jitted._cache_size()
        except Exception:  # noqa: BLE001 — private API; degrade gracefully
            before = None
        skip_reason = None
        if not analyze:
            skip_reason = "analyze_false"
        elif not analysis_enabled():
            skip_reason = "disabled"
        elif before is None:
            skip_reason = "cache_introspection_unavailable"
        if skip_reason is None:
            # abstractify BEFORE the call: donated buffers are deleted by it
            from videop2p_tpu.obs.introspect import abstractify_args

            try:
                abs_args, abs_kwargs = abstractify_args(args, kwargs)
            except Exception:  # noqa: BLE001
                skip_reason = "abstractify_failed"
        try:
            attrs = span_attrs(*args, **kwargs) if span_attrs else {}
        except Exception:  # noqa: BLE001 — obs never kills a run
            attrs = {}
        with program_label(program), \
                span("program.call", program=program, **attrs) as call:
            # program.execute: dispatch → ready where the run blocks anyway
            # (--latency); the dispatch alone, `blocked: false`, where it
            # does not — the span never adds a sync of its own. A compile
            # at the head of the call is not part of it (_compile_sink).
            with span("program.execute") as execute:
                out = jitted(*args, **kwargs)
                dt = call.elapsed()
                blocked_dt = None
                if led.timing_enabled():
                    # opt-in only: blocking here trades away async-dispatch
                    # overlap for a measured end-to-end latency — values
                    # are untouched either way (host-side timing cannot
                    # change device results), so the off path stays
                    # bit-exact AND overlap-preserving
                    try:
                        jax.block_until_ready(out)
                        blocked_dt = call.elapsed()
                        led.record_execute(program, dt, blocked_dt)
                    except Exception:  # noqa: BLE001 — obs never kills a run
                        blocked_dt = None
                if blocked_dt is None:
                    execute.set(blocked=False)
            miss = None
            if before is not None:
                try:
                    miss = jitted._cache_size() > before
                except Exception:  # noqa: BLE001
                    miss = None
            call.set(cache_miss=miss)
            call_fields = {"program": program, "cache_miss": miss,
                           "dispatch_s": round(dt, 4)}
            if blocked_dt is not None:
                call_fields["blocked_s"] = round(blocked_dt, 4)
            led.event("program_call", **call_fields)
            if miss:
                if skip_reason is None:
                    try:
                        with span("program.analysis", rebuilt=False):
                            _analyze_into_ledger(
                                led, jitted, program, abs_args, abs_kwargs
                            )
                    except Exception:  # noqa: BLE001 — obs never kills a run
                        led.event("program_analysis_skipped",
                                  program=program, reason="analysis_error")
                else:
                    led.event("program_analysis_skipped", program=program,
                              reason=skip_reason)
        return out

    wrapper._jitted = jitted  # escape hatch (lower/compile introspection)
    wrapper.__name__ = f"instrumented[{program}]"
    return wrapper


def read_ledger(path: str) -> List[Dict[str, Any]]:
    """Parse a ledger JSONL file back into event dicts (skips any torn
    final line from a killed run).

    Rotation-aware: when ``RunLedger(max_bytes=...)`` rotated the file,
    the predecessors ``<stem>.N.jsonl`` … ``<stem>.1.jsonl`` are read
    first (oldest first) so ``split_runs``/``extract_run`` see the whole
    run as one stream, ``ledger_rotated`` markers included."""
    stem = path[:-len(".jsonl")] if path.endswith(".jsonl") else path
    n = 1
    while os.path.exists(f"{stem}.{n}.jsonl"):
        n += 1
    paths = [f"{stem}.{i}.jsonl" for i in range(n - 1, 0, -1)] + [path]
    events = []
    for p in paths:
        with open(p) as f:
            for line in f:
                line = line.strip()
                if not line:
                    continue
                try:
                    events.append(json.loads(line))
                except ValueError:
                    continue
    return events
