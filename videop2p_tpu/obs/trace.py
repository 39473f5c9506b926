"""Device-trace mining without tensorflow: a stdlib reader for the
``*.xplane.pb`` protos ``jax.profiler`` writes, plus the timeline
analyses the time-domain obs layer ledgers.

Reading the xplane proto through the tensorflow protobuf package needs
an import this image only satisfies with
``PROTOCOL_BUFFERS_PYTHON_IMPLEMENTATION=python`` and a tensorflow
install. This module decodes the
protobuf **wire format** directly (varints + length-delimited fields;
the xplane schema is stable and shallow), so the import closure stays
stdlib+numpy — the obs import-guard test walks this file, and the HTML
report can mine traces on any box the ledger was copied to.

Decoded structure (the subset the analyses need)::

    XSpace { planes: [XPlane] }
    XPlane { name, lines: [XLine],
             event_metadata: {id: name}, stat_metadata: {id: name} }
    XLine  { name, timestamp_ns, events: [XEvent] }
    XEvent { metadata_id, offset_ps, duration_ps }

Analyses (:func:`analyze_trace_dir` → a ``trace_analysis`` ledger event
+ ``.npz`` sidecar arrays):

  * per-op-family device time and the top-N ops by device time;
  * total compute vs collective device time (union lengths — seconds
    the device spent in each class, overlaps not double-counted);
  * the **compute/collective overlap fraction**: the length of
    ``union(compute windows) ∩ union(collective windows)`` divided by
    the collective union length — 0.0 means every collective ran with
    compute stalled (the ring-attention ppermute chain fully exposed),
    1.0 means the collectives were entirely hidden under compute. This
    is the number ROADMAP item 4's overlap work is gated on
    (``TIMING_RULES`` regresses it with ``direction="decrease"``);
  * idle gaps: seconds of the trace span with NO device event running,
    plus the largest single gap (dispatch stalls between steps).

:func:`trace_window` wraps a region in a ``jax.profiler`` capture and
emits the analysis into the active ledger — the CLIs' ``--trace_analysis``
flag goes through it. jax is imported lazily there; importing this module
never touches it.
"""

from __future__ import annotations

import contextlib
import glob
import os
import re
import tempfile
from typing import Any, Dict, Iterable, Iterator, List, Optional, Sequence, Tuple

import numpy as np

__all__ = [
    "TRACE_ANALYSIS_FIELDS",
    "parse_xspace",
    "load_xplanes",
    "is_device_plane",
    "iter_line_events",
    "op_family",
    "is_collective_op",
    "interval_union",
    "union_length",
    "overlap_fraction",
    "analyze_events",
    "analyze_trace_dir",
    "trace_window",
]

# schema-stable numeric/string field set of the trace_analysis ledger
# event (test_ledger_schema pins it; TIMING_RULES reference these names)
TRACE_ANALYSIS_FIELDS = (
    "name",
    "trace_dir",
    "device_total_s",
    "compute_s",
    "collective_s",
    "overlap_fraction",
    "span_s",
    "idle_s",
    "idle_max_s",
    "num_events",
    "num_ops",
    "module_total_s",
    "module_span_s",
)

# mirror of obs.comm.COLLECTIVE_KINDS, duplicated so this module's
# import closure stays stdlib+numpy (comm.py imports jax at module load)
_COLLECTIVE_PREFIXES = (
    "all-reduce",
    "all-gather",
    "reduce-scatter",
    "collective-permute",
    "all-to-all",
    "collective-broadcast",
)


# ------------------------------------------- protobuf wire primitives --


def _varint(buf: bytes, pos: int) -> Tuple[int, int]:
    """Decode one base-128 varint at ``pos`` → (value, next_pos)."""
    result = 0
    shift = 0
    while True:
        if pos >= len(buf):
            raise ValueError("truncated varint")
        b = buf[pos]
        pos += 1
        result |= (b & 0x7F) << shift
        if not b & 0x80:
            return result, pos
        shift += 7
        if shift > 63:
            raise ValueError("varint exceeds 64 bits")


def _signed64(v: int) -> int:
    """Reinterpret an unsigned varint as the two's-complement int64 the
    proto ``int64`` fields encode (negative values use all 10 bytes)."""
    return v - (1 << 64) if v >= (1 << 63) else v


def _iter_fields(buf: bytes) -> Iterator[Tuple[int, int, Any]]:
    """Walk one message's fields → (field_number, wire_type, payload).

    Payloads: wire 0 → int, wire 1/5 → raw 8/4 bytes, wire 2 → bytes
    slice. Unknown/group wire types raise — better a loud parse error
    than silently misaligned events.
    """
    pos = 0
    n = len(buf)
    while pos < n:
        tag, pos = _varint(buf, pos)
        field, wire = tag >> 3, tag & 0x07
        if wire == 0:
            val, pos = _varint(buf, pos)
        elif wire == 1:
            val, pos = buf[pos:pos + 8], pos + 8
        elif wire == 2:
            size, pos = _varint(buf, pos)
            if pos + size > n:
                raise ValueError("truncated length-delimited field")
            val, pos = buf[pos:pos + size], pos + size
        elif wire == 5:
            val, pos = buf[pos:pos + 4], pos + 4
        else:
            raise ValueError(f"unsupported wire type {wire}")
        yield field, wire, val


# ------------------------------------------------ xplane schema walk --


def _parse_event(buf: bytes) -> Dict[str, int]:
    ev = {"metadata_id": 0, "offset_ps": 0, "duration_ps": 0}
    for field, wire, val in _iter_fields(buf):
        if wire != 0:
            continue
        if field == 1:
            ev["metadata_id"] = val
        elif field == 2:
            ev["offset_ps"] = _signed64(val)
        elif field == 3:
            ev["duration_ps"] = _signed64(val)
    return ev


def _parse_line(buf: bytes) -> Dict[str, Any]:
    line: Dict[str, Any] = {"name": "", "timestamp_ns": 0, "events": []}
    for field, wire, val in _iter_fields(buf):
        if field == 2 and wire == 2:
            line["name"] = val.decode("utf-8", "replace")
        elif field == 3 and wire == 0:
            line["timestamp_ns"] = _signed64(val)
        elif field == 4 and wire == 2:
            line["events"].append(_parse_event(val))
    return line


def _parse_metadata_entry(buf: bytes) -> Tuple[int, str]:
    """One map<int64, X*Metadata> entry → (id, name). The map key and the
    message's own ``id`` field agree in practice; the key wins."""
    key = 0
    name = ""
    for field, wire, val in _iter_fields(buf):
        if field == 1 and wire == 0:
            key = _signed64(val)
        elif field == 2 and wire == 2:
            for mfield, mwire, mval in _iter_fields(val):
                if mfield == 2 and mwire == 2:  # X{Event,Stat}Metadata.name
                    name = mval.decode("utf-8", "replace")
    return key, name


def _parse_plane(buf: bytes) -> Dict[str, Any]:
    plane: Dict[str, Any] = {
        "name": "", "lines": [], "event_metadata": {}, "stat_metadata": {},
    }
    for field, wire, val in _iter_fields(buf):
        if field == 2 and wire == 2:
            plane["name"] = val.decode("utf-8", "replace")
        elif field == 3 and wire == 2:
            plane["lines"].append(_parse_line(val))
        elif field == 4 and wire == 2:
            k, name = _parse_metadata_entry(val)
            plane["event_metadata"][k] = name
        elif field == 5 and wire == 2:
            k, name = _parse_metadata_entry(val)
            plane["stat_metadata"][k] = name
    return plane


def parse_xspace(data: bytes) -> Dict[str, Any]:
    """One ``*.xplane.pb`` file's bytes → ``{"planes": [...]}``."""
    planes = []
    for field, wire, val in _iter_fields(data):
        if field == 1 and wire == 2:
            planes.append(_parse_plane(val))
    return {"planes": planes}


def load_xplanes(trace_dir: str) -> List[Dict[str, Any]]:
    """Every plane from every ``*.xplane.pb`` under ``trace_dir``
    (recursive — jax nests them under ``plugins/profile/<ts>/``)."""
    planes: List[Dict[str, Any]] = []
    for path in sorted(glob.glob(
        os.path.join(trace_dir, "**", "*.xplane.pb"), recursive=True
    )):
        with open(path, "rb") as f:
            planes.extend(parse_xspace(f.read())["planes"])
    return planes


def is_device_plane(name: str) -> bool:
    """Accelerator planes carry the device timeline ("/device:TPU:0"
    etc.); host planes carry python/runtime threads."""
    return "TPU" in name or "/device" in name.lower()


def iter_line_events(
    planes: Iterable[Dict[str, Any]],
    line_name: str,
    *,
    device_only: bool = True,
) -> Iterator[Tuple[str, int, int]]:
    """Yield ``(op_name, start_ps, duration_ps)`` for every event on a
    ``line_name`` line, starts on the trace's absolute ps timeline
    (line timestamp + event offset)."""
    for plane in planes:
        if device_only and not is_device_plane(plane.get("name", "")):
            continue
        ev_names = plane.get("event_metadata", {})
        for line in plane.get("lines", []):
            if line.get("name") != line_name:
                continue
            base_ps = int(line.get("timestamp_ns", 0)) * 1000
            for ev in line.get("events", []):
                yield (
                    ev_names.get(ev["metadata_id"], "?"),
                    base_ps + int(ev["offset_ps"]),
                    int(ev["duration_ps"]),
                )


# --------------------------------------------------- timeline algebra --


def op_family(name: str) -> str:
    """Bucket an XLA op name into a coarse family."""
    base = name.split(".")[0].split("%")[-1]
    for fam in (
        "convolution", "dot", "fusion", "copy", "transpose", "reshape",
        "reduce", "broadcast", "convert", "all-gather", "all-reduce",
        "reduce-scatter", "collective-permute", "all-to-all",
        "collective-broadcast", "dynamic-slice", "dynamic-update-slice",
        "scatter", "gather", "custom-call", "rng", "iota", "slice",
        "concatenate", "pad",
    ):
        if base.startswith(fam):
            return fam
    return re.sub(r"[-_.]?\d+$", "", base) or base


def is_collective_op(name: str) -> bool:
    base = name.split(".")[0].split("%")[-1]
    return base.startswith(_COLLECTIVE_PREFIXES)


def interval_union(
    intervals: Iterable[Tuple[int, int]]
) -> List[Tuple[int, int]]:
    """Merge ``(start, end)`` intervals into a sorted disjoint union.
    Zero/negative-length inputs are dropped."""
    ivs = sorted((s, e) for s, e in intervals if e > s)
    out: List[Tuple[int, int]] = []
    for s, e in ivs:
        if out and s <= out[-1][1]:
            out[-1] = (out[-1][0], max(out[-1][1], e))
        else:
            out.append((s, e))
    return out


def union_length(intervals: Iterable[Tuple[int, int]]) -> int:
    return sum(e - s for s, e in interval_union(intervals))


def _intersect_length(a: Sequence[Tuple[int, int]],
                      b: Sequence[Tuple[int, int]]) -> int:
    """Total length of the intersection of two DISJOINT-SORTED interval
    lists (two-pointer sweep)."""
    i = j = total = 0
    while i < len(a) and j < len(b):
        lo = max(a[i][0], b[j][0])
        hi = min(a[i][1], b[j][1])
        if hi > lo:
            total += hi - lo
        if a[i][1] <= b[j][1]:
            i += 1
        else:
            j += 1
    return total


def overlap_fraction(
    compute: Iterable[Tuple[int, int]],
    collective: Iterable[Tuple[int, int]],
) -> Optional[float]:
    """``|union(compute) ∩ union(collective)| / |union(collective)|``.

    Closed forms the tests pin: disjoint → 0.0; collectives entirely
    inside compute → 1.0; half of the collective time under compute →
    0.5. Returns None when there is no collective time at all (nothing
    to overlap — distinct from a measured 0.0, which means the chain is
    fully exposed).
    """
    coll = interval_union(collective)
    denom = sum(e - s for s, e in coll)
    if denom <= 0:
        return None
    comp = interval_union(compute)
    return _intersect_length(comp, coll) / denom


# -------------------------------------------------------- analyses --


def analyze_events(
    op_events: Sequence[Tuple[str, int, int]],
    module_events: Sequence[Tuple[str, int, int]] = (),
    *,
    name: str = "trace",
    trace_dir: Optional[str] = None,
    top_n: int = 12,
) -> Tuple[Dict[str, Any], Dict[str, np.ndarray]]:
    """Mine ``(op_name, start_ps, duration_ps)`` events into the
    ``trace_analysis`` record + the ``.npz`` sidecar arrays.

    ``device_total_s`` is the plain duration sum (async-overlapping ops
    can push it past wall-clock); ``compute_s``/``collective_s`` are union
    lengths (true device-busy seconds per class); idle is measured
    against the union of ALL device events over the span.
    """
    fam_ps: Dict[str, int] = {}
    op_ps: Dict[str, List[int]] = {}
    comp_iv: List[Tuple[int, int]] = []
    coll_iv: List[Tuple[int, int]] = []
    total_ps = 0
    for op, start, dur in op_events:
        total_ps += dur
        fam_ps[op_family(op)] = fam_ps.get(op_family(op), 0) + dur
        op_ps.setdefault(op, [0, 0])
        op_ps[op][0] += dur
        op_ps[op][1] += 1
        (coll_iv if is_collective_op(op) else comp_iv).append(
            (start, start + dur)
        )
    all_iv = interval_union(comp_iv + coll_iv)
    span_ps = (all_iv[-1][1] - all_iv[0][0]) if all_iv else 0
    busy_ps = sum(e - s for s, e in all_iv)
    gaps = [all_iv[k + 1][0] - all_iv[k][1] for k in range(len(all_iv) - 1)]
    module_iv = interval_union(
        (s, s + d) for _, s, d in module_events
    )
    top = sorted(op_ps.items(), key=lambda kv: -kv[1][0])[:top_n]
    record: Dict[str, Any] = {
        "name": name,
        "trace_dir": trace_dir,
        "device_total_s": round(total_ps / 1e12, 9),
        "compute_s": round(union_length(comp_iv) / 1e12, 9),
        "collective_s": round(union_length(coll_iv) / 1e12, 9),
        "overlap_fraction": (
            None if (of := overlap_fraction(comp_iv, coll_iv)) is None
            else round(of, 4)
        ),
        "span_s": round(span_ps / 1e12, 9),
        "idle_s": round((span_ps - busy_ps) / 1e12, 9),
        "idle_max_s": round(max(gaps, default=0) / 1e12, 9),
        "num_events": len(op_events),
        "num_ops": len(op_ps),
        "module_total_s": round(
            sum(d for _, _, d in module_events) / 1e12, 6
        ),
        "module_span_s": round(
            (module_iv[-1][1] - module_iv[0][0]) / 1e12 if module_iv
            else 0.0, 6
        ),
        "families": {
            fam: round(ps / 1e12, 9)
            for fam, ps in sorted(fam_ps.items(), key=lambda kv: -kv[1])
        },
        "top_ops": [
            {"op": op, "seconds": round(ps / 1e12, 9), "count": cnt}
            for op, (ps, cnt) in top
        ],
    }
    key = f"trace_{name}"
    arrays: Dict[str, np.ndarray] = {
        f"{key}/op_start_ps": np.asarray(
            [s for _, s, _ in op_events], np.int64
        ),
        f"{key}/op_dur_ps": np.asarray(
            [d for _, _, d in op_events], np.int64
        ),
        f"{key}/op_is_collective": np.asarray(
            [is_collective_op(op) for op, _, _ in op_events], bool
        ),
        f"{key}/module_start_ps": np.asarray(
            [s for _, s, _ in module_events], np.int64
        ),
        f"{key}/module_dur_ps": np.asarray(
            [d for _, _, d in module_events], np.int64
        ),
    }
    return record, arrays


def analyze_trace_dir(
    trace_dir: str,
    *,
    name: str = "trace",
    top_n: int = 12,
) -> Tuple[Dict[str, Any], Dict[str, np.ndarray]]:
    """Load + mine every xplane proto under ``trace_dir``.

    Device planes' "XLA Ops" lines carry the per-op timeline and
    "XLA Modules" the per-program envelopes (TPU). A trace with neither
    (a CPU capture — host planes only) still yields a well-formed
    record: zeros, ``overlap_fraction`` None, ``num_events`` 0 — the
    schema is the contract, the values state what the trace held.
    """
    planes = load_xplanes(trace_dir)
    op_events = list(iter_line_events(planes, "XLA Ops"))
    module_events = list(iter_line_events(planes, "XLA Modules"))
    return analyze_events(
        op_events, module_events, name=name, trace_dir=trace_dir,
        top_n=top_n,
    )


@contextlib.contextmanager
def trace_window(
    name: str,
    *,
    trace_dir: Optional[str] = None,
    sidecar: bool = True,
    top_n: int = 12,
) -> Iterator[str]:
    """Capture a ``jax.profiler`` trace around the region and mine it.

    On exit the raw xplane protos are decoded (stdlib reader above) and
    the analysis lands in the active ledger as a ``trace_analysis``
    event, with the per-event arrays in ``<trace_dir>/trace_<name>.npz``
    (``sidecar=False`` skips the arrays). Everything after the region
    body is best-effort: a profiler or parser failure degrades to a
    ``trace_analysis_skipped`` event, never an exception into the
    traced code. jax is imported lazily — module import stays
    stdlib+numpy.
    """
    import jax

    target = trace_dir or tempfile.mkdtemp(prefix=f"videop2p_trace_{name}_")
    started = False
    try:
        jax.profiler.start_trace(target)
        started = True
    except Exception:  # noqa: BLE001 — a second active trace is not fatal
        pass
    try:
        yield target
    finally:
        from videop2p_tpu.obs.ledger import current_ledger

        led = current_ledger()
        if started:
            try:
                jax.profiler.stop_trace()
            except Exception:  # noqa: BLE001
                started = False
        if not started:
            if led is not None:
                led.event("trace_analysis_skipped", name=name,
                          reason="profiler_unavailable")
        else:
            try:
                record, arrays = analyze_trace_dir(
                    target, name=name, top_n=top_n
                )
                sidecar_path = None
                if sidecar and arrays:
                    sidecar_path = os.path.join(target, f"trace_{name}.npz")
                    np.savez_compressed(sidecar_path, **arrays)
                if led is not None:
                    led.event("trace_analysis", sidecar=sidecar_path,
                              **record)
            except Exception:  # noqa: BLE001 — mining must never kill a run
                if led is not None:
                    led.event("trace_analysis_skipped", name=name,
                              reason="analysis_error")
