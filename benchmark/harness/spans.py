"""The program's own ``span`` events, read from its run ledger.

The tuning CLI writes one JSONL ledger a run
(``<root>/outputs/bench/<cell>/tune/ledger.jsonl``, traced or not); every
timed region of the program is one ``span`` line in it (``name``,
``span_id``, ``parent_id``, ``wall_ns`` at entry, a monotonic
``duration_s``), written when the region closes. This module parses the
file itself and imports nothing of the program; the set-up readers under
``benchmark/layer_metrics/`` are thin callers of :func:`setup_parts`.

Every function returns ``None`` — never 0 — where the ledger or a span it
needs is missing: a program that has no such span (the parent of the PR that
added them) then leaves the metric out of its result line.
"""

from __future__ import annotations

import json
import os

# the span names read here; videop2p_tpu/obs/spans.py keeps the same tuple
# (BENCHMARK_SPAN_NAMES) and a test of the program holds the two equal
READ_NAMES = (
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

PROGRAM = "train_steps"


def ledger_path(ctx: dict) -> str:
    root = os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    return os.path.join(root, "outputs", "bench", ctx["cell"]["name"],
                        "tune", "ledger.jsonl")


def read_spans(path: str):
    """The ``span`` events of a ledger file in file order, or None where
    there is no file or no span in it. A torn last line is skipped."""
    if not os.path.isfile(path):
        return None
    spans = []
    with open(path) as f:
        for line in f:
            try:
                rec = json.loads(line)
            except ValueError:
                continue
            if (isinstance(rec, dict) and rec.get("event") == "span"
                    and rec.get("span_id") and rec.get("name")
                    and isinstance(rec.get("wall_ns"), int)
                    and isinstance(rec.get("duration_s"), (int, float))):
                spans.append(rec)
    return spans or None


def _interval_ns(s: dict):
    """(start, end) in whole nanoseconds: seconds since the epoch as a float
    would lose the last half microsecond."""
    start = int(s["wall_ns"])
    return start, start + int(round(float(s["duration_s"]) * 1e9))


def union_s(spans) -> float:
    """Seconds covered by at least one of the spans."""
    total, reach = 0, None
    for start, end in sorted(_interval_ns(s) for s in spans):
        if reach is None or start > reach:
            total += end - start
            reach = end
        elif end > reach:
            total += end - reach
            reach = end
    return total * 1e-9


class Tree:
    """Spans by ``parent_id``."""

    def __init__(self, spans):
        self.spans = spans
        self.kids = {}
        for s in spans:
            self.kids.setdefault(s.get("parent_id"), []).append(s)

    def children(self, s: dict, name: str = None):
        return [c for c in self.kids.get(s["span_id"], [])
                if name is None or c["name"] == name]

    def named(self, name: str, **attrs):
        return [s for s in self.spans if s["name"] == name
                and all(s.get(k) == v for k, v in attrs.items())]

    def self_s(self, s: dict) -> float:
        """A span's own time: its duration less the union of its children."""
        return float(s["duration_s"]) - union_s(self.children(s))


def setup_parts(ctx: dict):
    """The tune path's set-up by span, in seconds::

        tune.setup = models + clip + trace_lower + load + analysis
                     + execute + unattributed

    ``trace_lower`` / ``load`` are the first ``program.call[train_steps]``'s
    own ``program.trace`` + ``program.lower`` / ``program.backend_compile``
    children; what the analysis pass traced, lowered and compiled lies under
    ``program.analysis`` and is counted there and nowhere else. A part whose
    span is missing is None, and so is ``unattributed`` then."""
    spans = read_spans(ledger_path(ctx))
    if spans is None:
        return None
    tree = Tree(spans)
    roots = tree.named("tune.setup")
    if len(roots) != 1:
        return None
    root = roots[0]

    def under_root(*names):
        found = [tree.children(root, n) for n in names]
        if not all(found):
            return None
        return sum(float(s["duration_s"]) for group in found for s in group)

    parts = {
        "setup": float(root["duration_s"]),
        "models": under_root("tune.build_models", "tune.state_create"),
        "clip": under_root("tune.load_clip", "tune.vae_encode",
                           "tune.text_encode"),
        "trace_lower": None, "load": None, "analysis": None,
        "execute": None, "unattributed": None,
    }
    calls = [c for c in tree.children(root, "program.call")
             if c.get("program") == PROGRAM]
    if calls:
        first = min(calls, key=lambda c: int(c["wall_ns"]))
        trace_lower = (tree.children(first, "program.trace")
                       + tree.children(first, "program.lower"))
        load = tree.children(first, "program.backend_compile")
        analysis = tree.children(first, "program.analysis")
        execute = tree.children(first, "program.execute")
        if trace_lower:
            parts["trace_lower"] = union_s(trace_lower)
        if load:
            parts["load"] = union_s(load)
        if analysis:
            parts["analysis"] = union_s(analysis)
        if execute:
            parts["execute"] = union_s(execute)
    named = [parts[k] for k in ("models", "clip", "trace_lower", "load",
                                "analysis", "execute")]
    if all(v is not None for v in named):
        parts["unattributed"] = parts["setup"] - sum(named)
    return parts


def setup_part(ctx: dict, key: str):
    parts = setup_parts(ctx)
    return None if parts is None else parts[key]


def host_between_calls_ms(ctx: dict):
    """Mean over the window's calls of (start of ``program.call`` n+1 − end
    of ``program.call`` n), in ms: what the host does between two dispatches
    of ``train_steps``. The window's calls are the ``len(window["calls"])``
    that follow the first (set-up) call; a traced run's extra call after the
    window is not among them."""
    spans = read_spans(ledger_path(ctx))
    n = len(ctx["window"].get("calls") or [])
    if spans is None or n < 2:
        return None
    calls = sorted((s for s in spans if s["name"] == "program.call"
                    and s.get("program") == PROGRAM),
                   key=lambda s: int(s["wall_ns"]))
    window = calls[1:1 + n]
    if len(window) < n:
        return None
    gaps_ns = [_interval_ns(b)[0] - _interval_ns(a)[1]
               for a, b in zip(window, window[1:])]
    return 1e-6 * sum(gaps_ns) / len(gaps_ns)
