"""Tracing/profiling hooks (SURVEY §5.1 — the reference has none; the
north-star metric is wall-clock, so per-phase timing is first-class here).

``phase_timer`` prints wall-clock per named phase and keeps a process-local
record for reporting — with ``count`` it also reports per-unit time (e.g.
ms per null-text inner Adam step, the official mode's dominant unit of
work).

All timing uses ``time.perf_counter`` (monotonic): ``time.time`` is
wall-clock and steps under NTP adjustment, which corrupted phase records.
Every phase is a :class:`videop2p_tpu.obs.spans.span` of its name: when a
:class:`videop2p_tpu.obs.ledger.RunLedger` is active it lands in the ledger
as a ``span`` event beside its ``phase`` event, and in an open profiler
session as a host-plane ``TraceAnnotation`` — callers need no changes.
"""

from __future__ import annotations

import contextlib
import threading
import time
from typing import Dict, Iterator, List, Optional, Tuple

__all__ = [
    "phase_timer",
    "phase_records",
    "last_phase_seconds",
    "reset",
]

# guarded by _RECORDS_LOCK: phase_timer regions can close on worker threads
# (the UI trainer, future async pipelines)
_RECORDS: List[Tuple[str, float]] = []
_RECORDS_LOCK = threading.Lock()


def phase_records() -> Dict[str, float]:
    """Total seconds per phase name, accumulated since the last reset."""
    out: Dict[str, float] = {}
    with _RECORDS_LOCK:
        records = list(_RECORDS)
    for name, dt in records:
        out[name] = out.get(name, 0.0) + dt
    return out


def last_phase_seconds(name: str) -> Optional[float]:
    """The most recent recorded duration of a named phase (None if the
    phase never ran) — lets callers derive per-unit metrics from a region
    they timed with :func:`phase_timer` without re-measuring."""
    with _RECORDS_LOCK:
        records = list(_RECORDS)
    for rec_name, dt in reversed(records):
        if rec_name == name:
            return dt
    return None


def reset() -> None:
    """Drop all accumulated phase records. Long-lived processes (bench
    sweeps, the demo UI) call this between configurations — the record
    list otherwise grows unboundedly and mixes configurations' timings."""
    with _RECORDS_LOCK:
        _RECORDS.clear()


@contextlib.contextmanager
def phase_timer(
    name: str,
    *,
    verbose: bool = True,
    count: Optional[int] = None,
    unit: str = "it",
) -> Iterator[None]:
    """Time a region; ``count`` divides the wall-clock into per-unit ms in
    the printed line (``[phase] null_text_optimization: 207.10s
    (414.2 ms/inner-step)``) — an upper bound when the region early-stops
    below ``count`` units."""
    extra = {"count": count, "unit": unit} if count else {}
    # lazy import: utils must stay importable without obs (and obs
    # imports nothing from here — no cycle either way)
    try:
        from videop2p_tpu.obs.ledger import current_ledger
        from videop2p_tpu.obs.spans import span

        region = span(name, **extra)
    except ImportError:  # utils without obs: the print and the record stay
        current_ledger = lambda: None  # noqa: E731
        region = contextlib.nullcontext()
    with region:
        t0 = time.perf_counter()
        try:
            yield
        finally:
            dt = time.perf_counter() - t0
            with _RECORDS_LOCK:
                _RECORDS.append((name, dt))
            led = current_ledger()
            if led is not None:
                led.phase(name, dt, **extra)
            if verbose:
                per = f" ({dt / count * 1e3:.1f} ms/{unit})" if count else ""
                print(f"[phase] {name}: {dt:.2f}s{per}")
