"""Profiler capture and the reduction from a trace to numbers.

The arithmetic (interval union, idle over the window, op families) is copied
from ``videop2p_tpu/obs/trace.py``; the trace is read with
``jax.profiler.ProfileData`` and idle is taken over the TRACED WINDOW (host
clock from ``start`` to ``stop``), not over the span of the events.

Device planes are ``/device:TPU:<n>``; the line ``XLA Ops`` holds one event
per executed HLO op (control-flow ops such as ``while`` enclose their
bodies' events, so times are SELF times: an event's duration minus its
children's). A Pallas kernel's event is named after its HLO custom-call;
``kernel_of`` finds the kernel's own name in the event's name or stats."""

from __future__ import annotations

import glob
import os
import re

_FAMILIES = (
    "convolution", "dot", "fusion", "copy", "transpose", "reshape", "reduce",
    "broadcast", "convert", "all-gather", "all-reduce", "reduce-scatter",
    "collective-permute", "all-to-all", "collective-broadcast",
    "dynamic-slice", "dynamic-update-slice", "scatter", "gather",
    "custom-call", "rng", "iota", "slice", "concatenate", "pad",
)
KERNELS = ("fused_frame_attention", "fused_group_norm")


def short_name(name: str) -> str:
    """``%fusion.12 = bf16[..] fusion(...)`` -> ``fusion.12``: on the TPU an
    event's name is the whole HLO instruction."""
    return name.split(" = ", 1)[0].lstrip("%")[:80]


_ARRAY = re.compile(r"\b(pred|[a-z]+[0-9]+[a-z0-9]*)\[([0-9,]*)\]")
_BYTES = {"pred": 1, "s8": 1, "u8": 1, "f8e4m3fn": 1, "f8e5m2": 1,
          "bf16": 2, "f16": 2, "s16": 2, "u16": 2, "f32": 4, "s32": 4,
          "u32": 4, "f64": 8, "s64": 8, "u64": 8}


def output_arrays(name: str) -> list:
    """``[(dtype, elements)]`` of an HLO instruction's result, from its
    text (``%x = bf16[2,4096,320]{...} custom-call(...)``)."""
    if " = " not in name:
        return []
    rest = name.split(" = ", 1)[1]
    m = re.match(r"(.*?)\s[a-z][a-z0-9\-]*\(", rest)
    head = m.group(1) if m else rest
    out = []
    for dt, dims in _ARRAY.findall(head):
        n = 1
        for d in dims.split(","):
            if d:
                n *= int(d)
        out.append((dt, n))
    return out


def op_family(name: str) -> str:
    base = short_name(name).split(".")[0]
    if base == "fusion":
        # on the TPU convolutions and matmuls sit inside output fusions
        m = re.search(r", kind=k([A-Za-z]+)", name)
        return f"fusion.{m.group(1)}" if m else "fusion"
    for fam in _FAMILIES:
        if base.startswith(fam):
            return fam
    return re.sub(r"[-_.]?\d+$", "", base) or base


def interval_union(intervals):
    ivs = sorted((s, e) for s, e in intervals if e > s)
    out = []
    for s, e in ivs:
        if out and s <= out[-1][1]:
            out[-1] = (out[-1][0], max(out[-1][1], e))
        else:
            out.append((s, e))
    return out


def start(trace_dir: str) -> None:
    import jax

    os.makedirs(trace_dir, exist_ok=True)
    jax.profiler.start_trace(trace_dir)


def stop() -> None:
    import jax

    jax.profiler.stop_trace()


def kernel_of(name: str, stats: dict):
    """The Pallas kernel an event belongs to, or None."""
    for k in KERNELS:
        if k in name:
            return k
    for v in stats.values():
        if isinstance(v, str):
            for k in KERNELS:
                if k in v:
                    return k
    return None


def self_times(events):
    """``[(name, start, end, self_ns, is_leaf, stats)]`` from one line's
    events ``(name, start, dur, stats)``: nesting resolved by a stack."""
    evs = sorted(events, key=lambda e: (e[1], -e[2]))
    out, stack = [], []  # stack of indices into out
    for name, s, d, stats in evs:
        e = s + d
        while stack and out[stack[-1]][2] <= s:
            stack.pop()
        if stack:
            parent = out[stack[-1]]
            parent[3] -= d
            parent[4] = False
        out.append([name, s, e, d, True, stats])
        stack.append(len(out) - 1)
    return out


def reduce_events(per_device_events, window_s: float) -> dict:
    """The reduction proper, on ``{device: [(name, start_ns, dur_ns,
    stats)]}`` — what the test drives with a small recorded trace."""
    busy_ns, op_ns, fam_ns, kernel_ns, kernel_calls, gaps = [], {}, {}, {}, {}, []
    kernel_slabs = {}  # kernel -> {(dtype, elements): [calls, ns]}
    n_events = 0
    for dev, events in per_device_events.items():
        rows = self_times(events)
        n_events += len(rows)
        leaves = [(r[1], r[2]) for r in rows if r[4]]
        union = interval_union(leaves)
        busy_ns.append(sum(e - s for s, e in union))
        for name, s, e, self_ns, leaf, stats in rows:
            kern = kernel_of(name, stats)
            key = kern or short_name(name)
            op_ns[key] = op_ns.get(key, 0) + max(self_ns, 0)
            fam = kern or op_family(name)
            fam_ns[fam] = fam_ns.get(fam, 0) + max(self_ns, 0)
            if kern:
                kernel_ns[kern] = kernel_ns.get(kern, 0) + max(self_ns, 0)
                kernel_calls[kern] = kernel_calls.get(kern, 0) + 1
                arrays = output_arrays(name)
                slab = max(arrays, key=lambda a: a[1]) if arrays else ("?", 0)
                row = kernel_slabs.setdefault(kern, {}).setdefault(
                    slab, [0, 0])
                row[0] += 1
                row[1] += max(self_ns, 0)
        ends = {e: short_name(n) for n, s, e, _, leaf, _ in rows if leaf}
        starts = {s: short_name(n) for n, s, e, _, leaf, _ in rows if leaf}
        for (s0, e0), (s1, e1) in zip(union, union[1:]):
            gaps.append((f"after {ends.get(e0, '?')} before "
                         f"{starts.get(s1, '?')}", (s1 - e0) / 1e9))
    n_dev = max(len(per_device_events), 1)
    busy_s = sum(busy_ns) / 1e9 / n_dev
    top = lambda d, n: [[k, v / 1e9 / n_dev] for k, v in  # noqa: E731
                        sorted(d.items(), key=lambda kv: -kv[1])[:n]]
    gaps.sort(key=lambda g: -g[1])
    return {
        "window_s": float(window_s), "busy_s": busy_s, "events": n_events,
        "kernel_s": {k: v / 1e9 / n_dev for k, v in kernel_ns.items()},
        "kernel_calls": {k: v // n_dev for k, v in kernel_calls.items()},
        "family_s": {k: v / 1e9 / n_dev for k, v in fam_ns.items()},
        "kernel_slabs": {
            k: [[dt, n, calls, ns / 1e9] for (dt, n), (calls, ns)
                in sorted(v.items(), key=lambda kv: -kv[1][1])]
            for k, v in kernel_slabs.items()},
        "breakdown": {
            "device_ops": top(fam_ns, 10),
            "idle_gaps": [[n, s] for n, s in gaps[:10]],
        },
        "top_ops": top(op_ns, 25),
    }


def read_xplane(trace_dir: str, n_devices: int) -> dict:
    """``{device plane: [(name, start_ns, dur_ns, stats)]}`` of the newest
    ``*.xplane.pb`` under ``trace_dir``."""
    from jax.profiler import ProfileData

    paths = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not paths:
        raise FileNotFoundError(f"no xplane under {trace_dir}")
    data = ProfileData.from_file(paths[-1])
    out = {}
    described = []
    for plane in data.planes:
        lines = list(plane.lines)
        described.append({"plane": plane.name,
                          "lines": [ln.name for ln in lines]})
        if not plane.name.startswith("/device:TPU:"):
            continue
        for line in lines:
            if line.name != "XLA Ops":
                continue
            evs = []
            for ev in line.events:
                stats = {}
                try:
                    for k, v in ev.stats:
                        stats[k] = v
                except Exception:  # noqa: BLE001 — stats are optional
                    pass
                evs.append((ev.name, int(ev.start_ns),
                            int(ev.duration_ns), stats))
            out[plane.name] = evs
    if len(out) < n_devices:
        raise RuntimeError(f"trace holds {sorted(out)}; {n_devices} device "
                           f"plane(s) with an 'XLA Ops' line expected "
                           f"(planes: {described})")
    return out


def reduce(trace_dir: str, window_s: float, n_devices: int,
           allow_empty: bool = False):
    """``allow_empty`` is for the CPU rehearsal, whose trace has no device
    plane: it returns None, and no trace metric is reported."""
    try:
        events = read_xplane(trace_dir, n_devices)
    except RuntimeError:
        if allow_empty:
            return None
        raise
    return reduce_events(events, window_s)
