"""Op-family breakdown of the jitted fast-edit phases on the real chip.

Runs the 50-step inversion + controlled edit (the exact bench working point —
shared via ``bench.build_fast_edit_working_point``) under ``jax.profiler.trace``
and sums per-op device time from the raw ``*.xplane.pb``.

The proto walk now lives in :mod:`videop2p_tpu.obs.trace` — a **stdlib
wire-format reader**, so this tool no longer needs
``PROTOCOL_BUFFERS_PYTHON_IMPLEMENTATION=python`` or an installed
tensorflow, and the same parser feeds the ledger's ``trace_analysis``
events. Set ``VIDEOP2P_XPLANE_TF=1`` to force the legacy
tensorflow-proto path (the only reason: validating the stdlib reader
against the reference decoder on a box that has tensorflow).

Usage:  python tools/profile_xplane.py [trace_dir]
"""

from __future__ import annotations

import collections
import glob
import os
import sys
import tempfile

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if _REPO not in sys.path:
    sys.path.insert(0, _REPO)

from videop2p_tpu.obs.trace import op_family as _op_family  # noqa: E402


def iter_device_events(trace_dir: str, line_name: str = "XLA Ops"):
    """Yield ``(op_name, duration_ps)`` for every ``line_name`` line event on
    a device plane of every xplane proto under ``trace_dir``."""
    for name, _, dur in iter_device_event_windows(trace_dir, line_name):
        yield name, dur


def iter_device_event_windows(trace_dir: str, line_name: str = "XLA Ops"):
    """Yield ``(op_name, start_ps, duration_ps)`` for every ``line_name``
    line event on a device plane, with starts on the trace's absolute
    timeline (line timestamp + event offset).

    Decodes the protos with the stdlib reader (obs/trace.py); the
    tensorflow-proto fallback survives behind ``VIDEOP2P_XPLANE_TF=1``
    for cross-validation only.
    """
    if os.environ.get("VIDEOP2P_XPLANE_TF", "0") == "1":
        yield from _iter_device_event_windows_tf(trace_dir, line_name)
        return
    from videop2p_tpu.obs.trace import iter_line_events, load_xplanes

    yield from iter_line_events(load_xplanes(trace_dir), line_name)


def _iter_device_event_windows_tf(trace_dir: str, line_name: str):
    """Legacy decoder through the tensorflow protobuf package (requires
    tensorflow + the pure-Python protobuf implementation)."""
    os.environ.setdefault("PROTOCOL_BUFFERS_PYTHON_IMPLEMENTATION", "python")
    from tensorflow.tsl.profiler.protobuf import xplane_pb2

    for path in glob.glob(
        os.path.join(trace_dir, "**", "*.xplane.pb"), recursive=True
    ):
        xspace = xplane_pb2.XSpace()
        with open(path, "rb") as f:
            xspace.ParseFromString(f.read())
        for plane in xspace.planes:
            if "TPU" not in plane.name and "/device" not in plane.name.lower():
                continue
            ev_names = {k: v.name for k, v in plane.event_metadata.items()}
            for line in plane.lines:
                if line.name != line_name:
                    continue
                base_ps = line.timestamp_ns * 1000
                for ev in line.events:
                    yield (
                        ev_names.get(ev.metadata_id, "?"),
                        base_ps + ev.offset_ps,
                        ev.duration_ps,
                    )


def module_device_seconds(trace_dir: str) -> float:
    """Total device execution time (seconds) of every XLA program run during
    the trace, summed from the "XLA Modules" line (one event per executed
    program, carrying its true device duration).

    This is the measurement source ``bench.measure_with_floor`` falls back
    to when a host wall-clock reads unphysically fast: if the programs
    really ran during the traced window, their module events carry the real
    device duration; otherwise the line is (near-)empty and the reading
    stays suspect.
    """
    return sum(
        ps for _, ps in iter_device_events(trace_dir, "XLA Modules")
    ) / 1e12


def module_device_span_seconds(trace_dir: str) -> float:
    """Envelope span (first program start → last program end, seconds) of the
    "XLA Modules" events. With async dispatch several programs can overlap on
    device, so the summed durations (:func:`module_device_seconds`) can
    EXCEED true wall-clock; the span cannot, making it the honest reading
    when the host-side wall-clock is untrusted. Returns 0.0 when the trace
    recorded no module events."""
    starts_ends = [
        (start, start + dur)
        for _, start, dur in iter_device_event_windows(trace_dir, "XLA Modules")
    ]
    if not starts_ends:
        return 0.0
    return (max(e for _, e in starts_ends) - min(s for s, _ in starts_ends)) / 1e12


def collect(trace_dir: str) -> dict:
    fams = collections.Counter()
    total_ps = 0
    for name, ps in iter_device_events(trace_dir):
        fams[_op_family(name)] += ps
        total_ps += ps
    return {"families": fams, "total_ps": total_ps}


def main() -> None:
    if any(a in ("-h", "--help") for a in sys.argv[1:]):
        print(__doc__.strip())
        return
    # jax only here: iter_device_events stays import-light for the
    # proto-parsing CLIs that share it (xplane_top_ops.py)
    import jax

    from bench import build_fast_edit_working_point

    # profile the CACHED pair (the headline path) unless VIDEOP2P_PROFILE_LIVE=1
    live = os.environ.get("VIDEOP2P_PROFILE_LIVE", "0") == "1"
    wp = build_fast_edit_working_point(cached=not live)
    # compile + warm on a different input than the traced call
    if live:
        jax.block_until_ready(wp.edit(wp.params, wp.invert(wp.params, wp.x_warm)[-1]))
    else:
        wtr, wcc = wp.invert_captured(wp.params, wp.x_warm)
        jax.block_until_ready(wp.edit_cached(wp.params, wtr[-1], wcc))

    trace_dir = sys.argv[1] if len(sys.argv) > 1 else tempfile.mkdtemp(
        prefix="videop2p_xplane_"
    )
    with jax.profiler.trace(trace_dir):
        if live:
            traj = wp.invert(wp.params, wp.x0)
            out = wp.edit(wp.params, traj[-1])
        else:
            traj, cc = wp.invert_captured(wp.params, wp.x0)
            out = wp.edit_cached(wp.params, traj[-1], cc)
        jax.block_until_ready(out)

    res = collect(trace_dir)
    total = res["total_ps"] / 1e12
    print(f"trace: {trace_dir}")
    print(f"device op time total: {total:.3f} s")
    for fam, ps in res["families"].most_common(20):
        print(f"  {fam:24s} {ps/1e12:8.3f} s  {ps/res['total_ps']*100:5.1f}%")
    # the full time-domain record (obs/trace.py): compute vs collective
    # union seconds, the overlap fraction, idle gaps
    from videop2p_tpu.obs.trace import analyze_trace_dir

    record, _ = analyze_trace_dir(trace_dir, name="profile_xplane")
    ov = record["overlap_fraction"]
    print(
        f"compute {record['compute_s']:.3f} s / collective "
        f"{record['collective_s']:.3f} s, overlap "
        + ("n/a (no collectives)" if ov is None else f"{ov:.2f}")
        + f", idle {record['idle_s']:.3f} s over a {record['span_s']:.3f} s span"
    )


if __name__ == "__main__":
    main()
