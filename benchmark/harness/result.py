"""The contract's last line, the device record, and the compile-cache
counter. The only place a result line is made; it refuses anything but a
TPU with the chips the cell asks for."""

from __future__ import annotations

import json
import sys


def device_record(devices) -> dict:
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use")
             for d in devices]
    peaks = [p for p in peaks if p is not None]
    return {"platform": devices[0].platform, "kind": devices[0].device_kind,
            "count": len(devices),
            "memory_peak_bytes": max(peaks) if peaks else None}


class CacheCounter:
    """Persistent-compile-cache traffic from jax's monitoring events
    (copied from chip_smoke.CacheCounter): ``requests`` consulted the
    cache, ``hits`` were read back, ``writes`` were fresh compiles."""

    EVENTS = {
        "/jax/compilation_cache/compile_requests_use_cache": "requests",
        "/jax/compilation_cache/cache_hits": "hits",
        "/jax/compilation_cache/cache_misses": "writes",
    }

    def __init__(self):
        import jax

        self.counts = {"requests": 0, "hits": 0, "writes": 0}
        jax.monitoring.register_event_listener(self._on_event)

    def _on_event(self, event: str, **kw) -> None:
        key = self.EVENTS.get(event)
        if key:
            self.counts[key] += 1

    def snapshot(self) -> dict:
        return dict(self.counts)

    def since(self, before: dict) -> dict:
        return {k: self.counts[k] - before[k] for k in self.counts}


def compared_lines(compared: dict) -> list:
    """One plain line per number compared: name, number, limit, verdict."""
    lines = []
    for name, c in compared.items():
        ok = c["value"] is not None and c["value"] <= c["limit"]
        lines.append(f"compared {name} = {c['value']!r} limit {c['limit']!r} "
                     f"{'ok' if ok else 'FAIL'}")
    return lines


def verdict(compared: dict) -> bool:
    return bool(compared) and all(
        c["value"] is not None and c["value"] == c["value"]
        and c["value"] <= c["limit"] for c in compared.values())


def emit_result(*, device: dict, chips: int, attempted: int, failed: int,
                metrics: dict, compared: dict, breakdown=None,
                rehearse: bool = False) -> int:
    """Print the compared numbers last on stderr and the result line last on
    stdout. Returns the exit code."""
    correct = verdict(compared) and failed == 0 and attempted > 0
    line = {"correct": correct, "attempted": attempted, "failed": failed,
            "metrics": metrics, "device": device}
    if breakdown is not None:
        line["breakdown"] = breakdown
    line["compared"] = compared
    for text in compared_lines(compared):
        print(text, file=sys.stderr)
    sys.stderr.flush()
    if rehearse:
        # a rehearsal can never print a result line
        print("REHEARSAL (no result): " + json.dumps(line), file=sys.stderr)
        return 3
    if device["platform"] != "tpu" or device["count"] < chips:
        raise RuntimeError(f"not the chips asked for: {device} — no result")
    print(json.dumps(line), flush=True)
    return 0
