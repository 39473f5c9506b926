"""Request-scoped distributed tracing: spans as ledger events (ISSUE 14).

The Dapper model (Sigelman et al., 2010) shrunk to the house rules: a span
is one `span` line in a :class:`~videop2p_tpu.obs.ledger.RunLedger` — a
128-bit ``trace_id`` shared by every hop of one request, a 64-bit
``span_id``, a ``parent_id`` link, a wall-clock anchor (``time.time_ns()``,
so spans from a router ledger and N replica ledgers order into ONE causal
tree without any shared monotonic epoch), and a measured ``duration_s``
(monotonic, like every other timed region in the package).

Cross-process propagation uses a W3C-trace-context-style ``traceparent``
HTTP header (``00-<32hex trace>-<16hex span>-01``): the client stamps it,
``serve/router.py`` re-parents it onto its proxy span, ``serve/http.py``
hands it to the engine, and ``tools/trace_view.py`` joins the resulting
ledgers back into the tree.

House pattern: tracing is OFF by default. A disabled :class:`Tracer` is
inert — no ids are minted, no events written, the serving path stays
bit-exact (pinned by tests/test_tracing.py). Stdlib + jax only; the
import-guard test walks this module.

:class:`span` (ISSUE 26) is the ONE way the program times a region: a
context manager that writes one such ``span`` event when it closes, through
the :class:`Tracer` of the active ledger, and enters a
``jax.profiler.TraceAnnotation`` of the same name for the same interval, so
that an open profiler session shows the region in the xplane's host plane
on the clock the device ops are on. ``utils.profiling.phase_timer`` and
``obs.ledger.instrumented_jit`` are thin callers of it.

The ``process`` span reaches back from a ledger's first live span to the
process's own start, as the kernel records it, so that what ran before the
ledger existed (the interpreter, ``import jax``, the backend's start-up, the
package's import, the ledger's construction) is on the same record.
"""

from __future__ import annotations

import contextvars
import os
import threading
import time
import uuid
from typing import Any, Dict, List, Optional, Tuple

import jax

import videop2p_tpu

__all__ = [
    "BENCHMARK_PROCESS_SPAN_NAMES",
    "BENCHMARK_SPAN_NAMES",
    "SPAN_EVENT_FIELDS",
    "SPAN_SEGMENTS",
    "Tracer",
    "current_span",
    "entry_imported",
    "format_traceparent",
    "make_span_id",
    "make_trace_id",
    "parse_traceparent",
    "process_start_ns",
    "span",
]

# Schema pin: every `span` ledger event carries AT LEAST these keys
# (extra span attributes ride along as additional top-level fields).
# `wall_ns` anchors the span start to the wall clock — the only clock two
# processes share — while `duration_s` is measured on the monotonic clock.
SPAN_EVENT_FIELDS = (
    "trace_id",    # 32 hex chars — shared by every span of one request
    "span_id",     # 16 hex chars — this span
    "parent_id",   # 16 hex chars or None — the causal parent
    "name",        # dotted naming scheme: serve.request, serve.dispatch, ...
    "wall_ns",     # int epoch nanoseconds at span start (time.time_ns())
    "duration_s",  # float seconds, monotonic-measured
    "status",      # "ok" | terminal request status | "cached"
)

# The critical-path naming scheme: span name → segment label. obs/history.py
# aggregates per-trace durations under these labels into the `segments`
# section (queue/resolve/dispatch/decode p50/p99), and trace_view renders
# the same split per trace.
SPAN_SEGMENTS = {
    "serve.queue": "queue",
    "serve.resolve": "resolve",
    "serve.dispatch": "dispatch",
    "serve.decode": "decode",
    "serve.gif_write": "gif_write",
}

# The span names `benchmark/layer_metrics/` reads from the tune path's
# ledger (benchmark/harness/spans.py keeps the same tuple as READ_NAMES; it
# may import nothing of the program). tests/test_spans.py holds a tiny
# `run_tuning.main` to emitting each of them: a rename here fails a test
# before it turns a listed metric to null.
BENCHMARK_SPAN_NAMES = (
    "tune.setup",
    "tune.build_models",
    "tune.load_clip",
    "tune.vae_encode",
    "tune.text_encode",
    "tune.state_create",
    "program.call",
    "program.trace",
    "program.lower",
    "program.backend_compile",
    "program.analysis",
    "program.execute",
)

# The set-up's spans before and beside the root that `benchmark/layer_metrics/`
# reads (benchmark/harness/process_spans.py keeps the same tuple as
# READ_NAMES; tests/test_spans.py holds the two equal and a tiny `main` to
# emitting each)
BENCHMARK_PROCESS_SPAN_NAMES = (
    "process",
    "process.import",
    "metrics.tensorboard_writer",
)

# the kernel's record of this process; its 22nd field is the start, in clock
# ticks since boot
_PROC_STAT = "/proc/self/stat"

# wall clock at the end of the entry CLI's module-level imports: where
# `process.import` ends (it starts at videop2p_tpu.IMPORT_NS)
_ENTRY_IMPORTED_NS: Optional[int] = None
_PROCESS_LOCK = threading.Lock()


def entry_imported() -> None:
    """Stamp the end of the entry module's imports; each CLI that opens a
    ledger calls it at the foot of its import block."""
    global _ENTRY_IMPORTED_NS
    _ENTRY_IMPORTED_NS = time.time_ns()


def process_start_ns() -> Tuple[int, str]:
    """``(wall_ns, anchor)`` of this process's start: the kernel's start
    time (``anchor`` ``"proc"``, good to a clock tick, 10 ms), set against
    the boot clock and the wall clock read together; where ``/proc`` cannot
    be read, the package's first line (``"import"``)."""
    try:
        with open(_PROC_STAT) as f:
            stat = f.read()
        # the command name in field 2 may hold spaces and parentheses
        ticks = int(stat[stat.rindex(")") + 1:].split()[19])
        age_s = (time.clock_gettime(time.CLOCK_BOOTTIME)
                 - ticks / os.sysconf("SC_CLK_TCK"))
        return time.time_ns() - int(age_s * 1e9), "proc"
    except (OSError, ValueError, IndexError, AttributeError):
        return videop2p_tpu.IMPORT_NS, "import"


def make_trace_id() -> str:
    """A fresh 128-bit trace id (32 lowercase hex chars)."""
    return uuid.uuid4().hex


def make_span_id() -> str:
    """A fresh 64-bit span id (16 lowercase hex chars)."""
    return uuid.uuid4().hex[:16]


def format_traceparent(trace_id: str, span_id: str) -> str:
    """The W3C-style propagation header: ``00-<trace>-<span>-01``."""
    return f"00-{trace_id}-{span_id}-01"


def parse_traceparent(header: Optional[str]) -> Optional[Tuple[str, str]]:
    """``(trace_id, span_id)`` from a traceparent header, or None.

    Tolerant by design — a malformed header from a foreign client must
    degrade to "start a fresh trace", never to a 500.
    """
    if not header or not isinstance(header, str):
        return None
    parts = header.strip().lower().split("-")
    if len(parts) < 4:
        return None
    version, trace_id, span_id = parts[0], parts[1], parts[2]
    if len(version) != 2 or len(trace_id) != 32 or len(span_id) != 16:
        return None
    try:
        int(trace_id, 16), int(span_id, 16)
    except ValueError:
        return None
    if trace_id == "0" * 32 or span_id == "0" * 16:
        return None
    return trace_id, span_id


class Tracer:
    """Span emission bound to one ledger, gated on one ``enabled`` bit.

    Disabled (the default) it is inert: ``emit`` returns immediately and
    the hot path pays one attribute read — no ids minted, no dict built,
    no ledger write. Enabled, every ``emit`` is one ``span`` ledger event;
    :meth:`RunLedger.event` already serializes under the ledger lock, so
    concurrent spans from handler threads never tear (pinned by the
    concurrent-span test).
    """

    def __init__(self, ledger=None, *, enabled: bool = False):
        self.ledger = ledger
        self.enabled = bool(enabled) and ledger is not None
        # (start, end) wall ns of the ledger's construction, set when the
        # ledger activates, until its first live span writes `process`
        self.process_pending: Optional[Tuple[int, int]] = None

    def emit(self, name: str, *, trace_id: str, span_id: str,
             parent_id: Optional[str] = None,
             wall_ns: Optional[int] = None, duration_s: float = 0.0,
             status: str = "ok", **attrs: Any) -> Optional[Dict[str, Any]]:
        """Record one completed span. Returns the event fields (for tests
        and buffering callers), or None when disabled."""
        if not self.enabled:
            return None
        fields: Dict[str, Any] = {
            "trace_id": trace_id,
            "span_id": span_id,
            "parent_id": parent_id,
            "name": name,
            "wall_ns": int(time.time_ns() if wall_ns is None else wall_ns),
            "duration_s": round(float(duration_s), 6),
            "status": status,
        }
        fields.update(attrs)
        self.ledger.event("span", **fields)
        return fields

    def write_process(self, end_ns: int) -> None:
        """The ``process`` span, from the process's start to ``end_ns`` (where
        the ledger's first live span opens), with its children
        ``process.import`` (the package's first line to the end of the entry
        CLI's imports, where a CLI stamped it) and ``process.ledger_open``.
        What lies between them is the caller's: left as gaps."""
        with _PROCESS_LOCK:  # two threads' first spans write it once
            opened, self.process_pending = self.process_pending, None
        if opened is None:
            return
        start_ns, anchor = process_start_ns()
        trace_id, span_id = self.ledger.trace_id, make_span_id()
        children = [("process.ledger_open",) + opened]
        if _ENTRY_IMPORTED_NS is not None:
            children.insert(0, ("process.import", videop2p_tpu.IMPORT_NS,
                                _ENTRY_IMPORTED_NS))
        for name, begin, end in children:
            self.emit(name, trace_id=trace_id, span_id=make_span_id(),
                      parent_id=span_id, wall_ns=begin,
                      duration_s=(end - begin) * 1e-9)
        self.emit("process", trace_id=trace_id, span_id=span_id,
                  wall_ns=start_ns, duration_s=(end_ns - start_ns) * 1e-9,
                  anchor=anchor)


# the innermost open LIVE span of this thread / context: a child's
# `parent_id` is its `span_id`, and a trace id given to it (the engine's
# request) is inherited. A plain `threading.Thread` starts with an empty
# context, so its spans are roots; `contextvars.copy_context().run` carries
# the parent across.
_CURRENT: contextvars.ContextVar[Optional["span"]] = contextvars.ContextVar(
    "videop2p_obs_span", default=None
)

# nested intervals closer than this to their enclosing one count as inside it
_NEST_EPS_S = 1e-4


def current_span() -> Optional["span"]:
    """The innermost open live span of this context, or None."""
    return _CURRENT.get()


_current_ledger = None  # obs.ledger.current_ledger, bound at first use


def _active_tracer() -> Optional[Tracer]:
    global _current_ledger
    if _current_ledger is None:
        # lazy: obs.ledger imports this module at its top
        from videop2p_tpu.obs.ledger import current_ledger

        _current_ledger = current_ledger
    led = _current_ledger()
    return None if led is None else led.tracer


class span:
    """Time a region: ``with span("tune.load_clip", frames=8): ...``.

    LIVE (an active ledger whose tracer is enabled, or an enabled
    ``tracer=`` handed in — the engine's, since several in-process engines
    each own a ledger): mints a ``span_id``, takes ``parent_id`` and the
    trace id from the enclosing live span (else ``trace_id=`` /
    ``parent_id=``, else the ledger's own ``trace_id``: one per run), reads
    ``wall_ns`` at entry and a monotonic ``duration_s``, and writes ONE
    ``span`` event **when it closes** — not at ``RunLedger.close()``: a run
    that ends by an exception unwinding through ``main`` never gets there,
    and the span is on disk all the same (``status`` ``"error"``).

    Not live (no ledger, or tracing off): nothing but the
    ``TraceAnnotation`` and one clock read — no id minted, no event.

    For the one region that is not lexical, the same object is a handle:
    ``.end()`` closes it early (a later ``.end()`` / ``__exit__`` does
    nothing). ``.set(**attrs)`` adds fields, ``.count(key, n)`` adds to a
    counter field, ``.child(...)`` records a child measured by someone else
    (jax's compile listeners): children nested in another collapse into
    the outermost, and all are written just before the parent.
    """

    __slots__ = ("name", "attrs", "span_id", "parent", "duration_s",
                 "_trace_id", "_parent_id", "_tracer", "_wall_ns", "_t0",
                 "_token", "_annotation", "_children", "_open")

    def __init__(self, name: str, *, trace_id: Optional[str] = None,
                 parent_id: Optional[str] = None,
                 tracer: Optional[Tracer] = None, **attrs: Any):
        self.name = name
        self.attrs = attrs
        self.span_id: Optional[str] = None
        self.parent: Optional["span"] = None
        self.duration_s: Optional[float] = None
        self._trace_id = trace_id
        self._parent_id = parent_id
        self._tracer = tracer
        self._children: Optional[List[Tuple[str, int, float, Dict]]] = None
        self._open = False

    @property
    def live(self) -> bool:
        return self.span_id is not None

    def __enter__(self) -> "span":
        tracer = self._tracer if self._tracer is not None else _active_tracer()
        self._tracer = tracer if tracer is not None and tracer.enabled else None
        if self._tracer is not None:
            parent = self.parent = _CURRENT.get()
            if parent is not None:
                if self._parent_id is None:
                    self._parent_id = parent.span_id
                if self._trace_id is None:
                    self._trace_id = parent._trace_id
            self.span_id = make_span_id()
            self._token = _CURRENT.set(self)
            self._wall_ns = time.time_ns()
            if self._tracer.process_pending is not None:
                self._tracer.write_process(self._wall_ns)
        self._annotation = jax.profiler.TraceAnnotation(self.name)
        self._annotation.__enter__()
        self._open = True
        self._t0 = time.perf_counter()
        return self

    def __exit__(self, exc_type, exc, tb) -> None:
        self.end(status="ok" if exc_type is None else "error")

    def elapsed(self) -> float:
        """Seconds since entry (or since :meth:`restart`)."""
        return time.perf_counter() - self._t0

    def restart(self) -> None:
        """Move the start to now: what ran since entry was someone else's
        (``program.execute`` after the compile its call began with)."""
        self._t0 = time.perf_counter()
        if self.live:
            self._wall_ns = time.time_ns()

    def set(self, **attrs: Any) -> None:
        self.attrs.update(attrs)

    def count(self, key: str, n: float = 1) -> None:
        self.attrs[key] = self.attrs.get(key, 0) + n

    def child(self, name: str, wall_ns: int, duration_s: float,
              **attrs: Any) -> None:
        if self.live:
            if self._children is None:
                self._children = []
            self._children.append((name, int(wall_ns), float(duration_s),
                                   attrs))

    def end(self, status: str = "ok") -> None:
        if not self._open:
            return
        self._open = False
        self.duration_s = time.perf_counter() - self._t0
        self._annotation.__exit__(None, None, None)
        if not self.live:
            return
        try:
            _CURRENT.reset(self._token)
        except ValueError:  # closed in another context than it opened in
            pass
        for key, value in self.attrs.items():
            if isinstance(value, float):
                self.attrs[key] = round(value, 6)
        trace_id = self._trace_id or self._tracer.ledger.trace_id
        for name, wall_ns, dur, child_attrs in self._outermost_children():
            self._tracer.emit(
                name, trace_id=trace_id, span_id=make_span_id(),
                parent_id=self.span_id, wall_ns=wall_ns, duration_s=dur,
                **child_attrs)
        self._tracer.emit(
            self.name, trace_id=trace_id, span_id=self.span_id,
            parent_id=self._parent_id, wall_ns=self._wall_ns,
            duration_s=self.duration_s, status=status, **self.attrs)

    def _outermost_children(self):
        """Children in start order; one that lies inside an earlier one
        (jax traces the inner jits of a program inside the outer trace, and
        helper functions inside the lowering, and reports each) is counted
        on that one (``nested``) and not written."""
        kept: List[list] = []
        reach = None  # (end, row) of the last child written
        for name, wall_ns, dur, attrs in sorted(
                self._children or (), key=lambda c: (c[1], -c[2])):
            end = wall_ns * 1e-9 + dur
            if reach is not None and end <= reach[0] + _NEST_EPS_S:
                reach[1][3]["nested"] = reach[1][3].get("nested", 0) + 1
                continue
            row = [name, wall_ns, dur, dict(attrs)]
            kept.append(row)
            reach = (end, row)
        return kept
