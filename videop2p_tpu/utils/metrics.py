"""Training metrics: JSONL log + optional TensorBoard + ledger view.

The reference tracks training through HF Accelerate —
``accelerator.init_trackers("text2video-fine-tune")`` and per-step
``accelerator.log({"train_loss": ...})`` plus a tqdm postfix with
``step_loss``/``lr`` (/root/reference/run_tuning.py:234,337,377-378). Here a
:class:`MetricsLogger` appends one JSON object per logged step to
``<run_dir>/metrics.jsonl`` (machine-readable) and, when
the ``tensorboard`` package is importable, mirrors scalars into
``<run_dir>/tb/`` for the usual dashboard.

When a :class:`~videop2p_tpu.obs.ledger.RunLedger` is attached (``ledger=``
or the process-active one), every logged step also lands in the run ledger
as a ``metric`` event — the logger is then a VIEW over the ledger stream,
and the unified record holds training metrics next to phase/compile events.

Elapsed time uses ``time.perf_counter`` (monotonic; ``time.time`` steps
under NTP adjustment). The TensorBoard writer buffers scalars in memory
and a killed run lost them — scalars now flush every ``flush_every`` logs
and on close.
"""

from __future__ import annotations

import json
import os
import time
from typing import Dict, Optional

__all__ = ["MetricsLogger"]


class MetricsLogger:
    def __init__(self, run_dir: str, *, project: str = "text2video-fine-tune",
                 use_tensorboard: bool = True, flush_every: int = 20,
                 ledger=None):
        self.run_dir = run_dir
        os.makedirs(run_dir, exist_ok=True)
        self.path = os.path.join(run_dir, "metrics.jsonl")
        self._fh = open(self.path, "a", buffering=1)  # line-buffered
        self._t0 = time.perf_counter()
        self._flush_every = max(int(flush_every), 1)
        self._since_flush = 0
        self._ledger = ledger
        self._tb = None
        if use_tensorboard:
            from videop2p_tpu.obs.spans import span

            try:
                # the writer's import pulls in torch (and TensorFlow where
                # installed): seconds of a run's set-up
                with span("metrics.tensorboard_writer",
                          tracer=getattr(ledger, "tracer", None)):
                    from torch.utils.tensorboard import SummaryWriter

                    self._tb = SummaryWriter(
                        log_dir=os.path.join(run_dir, "tb"), comment=project
                    )
            except Exception:
                self._tb = None  # tensorboard optional; JSONL always written

    def _active_ledger(self):
        if self._ledger is not None:
            return self._ledger
        try:
            from videop2p_tpu.obs.ledger import current_ledger

            return current_ledger()
        except Exception:  # noqa: BLE001
            return None

    def log(self, step: int, scalars: Dict[str, float]) -> None:
        rec = {"step": int(step),
               "wall_s": round(time.perf_counter() - self._t0, 3)}
        rec.update({k: float(v) for k, v in scalars.items()})
        self._fh.write(json.dumps(rec) + "\n")
        led = self._active_ledger()
        if led is not None:
            led.event("metric", **rec)
        if self._tb is not None:
            for k, v in scalars.items():
                self._tb.add_scalar(k, float(v), int(step))
            self._since_flush += 1
            if self._since_flush >= self._flush_every:
                self._tb.flush()
                self._since_flush = 0

    def close(self) -> None:
        self._fh.close()
        if self._tb is not None:
            # flush BEFORE close: SummaryWriter.close() flushes too, but an
            # explicit flush survives writers whose close() raises mid-way
            self._tb.flush()
            self._tb.close()

    def __enter__(self) -> "MetricsLogger":
        return self

    def __exit__(self, *exc) -> Optional[bool]:
        self.close()
        return None
