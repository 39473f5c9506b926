"""Streaming long-video editing (ISSUE 12, ROADMAP item 5).

Minutes of footage edited as a sequence of overlapping fixed-size
temporal windows through the warm serving engine — resumable via the
per-window job manifest, fault-isolated per window, seam-quality gated.

  * :mod:`videop2p_tpu.stream.windows` — the deterministic window plan,
    crossfade assembly, content-addressed window keys, static cost model;
  * :mod:`videop2p_tpu.stream.manifest` — atomic per-window persistence
    + corrupt-manifest recovery;
  * :mod:`videop2p_tpu.stream.driver` — the job driver
    (:func:`run_stream_job`): retries, passthrough degradation,
    checkpoint-then-exit, ``stream_health`` ledger evidence.

Entry points: ``python -m videop2p_tpu.cli.stream`` (user-facing) and
``tools/stream_drive.py`` (the CPU closed-loop CI driver).
"""

from videop2p_tpu.stream.driver import (
    STREAM_HEALTH_FIELDS,
    STREAM_SEAM_FIELDS,
    STREAM_WINDOW_FIELDS,
    StreamJobResult,
    run_stream_job,
)
from videop2p_tpu.stream.manifest import JobManifest, WINDOW_STATUSES
from videop2p_tpu.stream.windows import (
    Window,
    assemble_video,
    blend_weights,
    plan_windows,
    seam_spans,
    synthetic_clip,
    window_key,
)

__all__ = [
    "run_stream_job",
    "StreamJobResult",
    "STREAM_HEALTH_FIELDS",
    "STREAM_WINDOW_FIELDS",
    "STREAM_SEAM_FIELDS",
    "JobManifest",
    "WINDOW_STATUSES",
    "Window",
    "plan_windows",
    "blend_weights",
    "assemble_video",
    "seam_spans",
    "window_key",
    "synthetic_clip",
]
