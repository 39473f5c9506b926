"""Training metrics: JSONL log + TensorBoard event file + ledger view.

The reference tracks training through HF Accelerate —
``accelerator.init_trackers("text2video-fine-tune")`` and per-step
``accelerator.log({"train_loss": ...})`` plus a tqdm postfix with
``step_loss``/``lr`` (/root/reference/run_tuning.py:234,337,377-378). Here a
:class:`MetricsLogger` appends one JSON object per logged step to
``<run_dir>/metrics.jsonl`` (machine-readable) and mirrors the scalars into
``<run_dir>/tb/events.out.tfevents.*`` for the usual dashboard.

The event file is written by :class:`EventFileWriter`, in this module, with
the standard library alone: no torch, no ``tensorboard``, no TensorFlow, no
protobuf (importing torch's ``SummaryWriter`` for it loaded TensorFlow and
took 20 s of a tuning run's set-up on a TPU v5e host). The format is
TensorBoard's: TFRecord framing (little-endian ``uint64`` length, its masked
CRC-32C, the payload, the payload's masked CRC-32C) around hand-encoded
``Event`` protos — first ``file_version`` ``"brain.Event:2"``, then one
``Event`` a scalar holding a ``Summary`` with one ``simple_value``, the bytes
``SummaryWriter.add_scalar`` writes.

When a :class:`~videop2p_tpu.obs.ledger.RunLedger` is attached (``ledger=``
or the process-active one), every logged step also lands in the run ledger
as a ``metric`` event — the logger is then a VIEW over the ledger stream,
and the unified record holds training metrics next to phase/compile events;
``close()`` adds one ``tensorboard_events`` event with the records and
bytes the event file holds.

Elapsed time uses ``time.perf_counter`` (monotonic; ``time.time`` steps
under NTP adjustment). The event file is buffered: its records reach the
disk every ``flush_every`` logs and on close, so a killed run loses at most
``flush_every`` logs of it.
"""

from __future__ import annotations

import itertools
import json
import os
import socket
import struct
import time
from typing import Dict, Optional

__all__ = ["EventFileWriter", "MetricsLogger", "crc32c", "masked_crc32c"]


def _crc32c_table():
    # CRC-32C (Castagnoli), reflected polynomial 0x82F63B78: TFRecord's
    # checksum (zlib.crc32 is the IEEE polynomial, another checksum)
    table = []
    for n in range(256):
        for _ in range(8):
            n = (n >> 1) ^ 0x82F63B78 if n & 1 else n >> 1
        table.append(n)
    return tuple(table)


_CRC32C_TABLE = _crc32c_table()


def crc32c(data: bytes) -> int:
    crc = 0xFFFFFFFF
    for byte in data:
        crc = _CRC32C_TABLE[(crc ^ byte) & 0xFF] ^ (crc >> 8)
    return crc ^ 0xFFFFFFFF


def masked_crc32c(data: bytes) -> int:
    """TFRecord's masked checksum: the CRC rotated right by 15, plus a
    constant."""
    crc = crc32c(data)
    return (((crc >> 15) | (crc << 17)) + 0xA282EAD8) & 0xFFFFFFFF


def _varint(n: int) -> bytes:
    n &= (1 << 64) - 1  # a negative int64 takes ten bytes, as in protobuf
    out = bytearray()
    while n > 0x7F:
        out.append(n & 0x7F | 0x80)
        n >>= 7
    out.append(n)
    return bytes(out)


def _length_delimited(field: int, payload: bytes) -> bytes:
    return _varint(field << 3 | 2) + _varint(len(payload)) + payload


def _float32(value: float) -> bytes:
    try:
        return struct.pack("<f", value)
    except OverflowError:  # past float32's range: infinity, as protobuf
        return struct.pack("<f", float("inf") if value > 0 else float("-inf"))


def _event(wall_time: float, *, step: int = 0, file_version: str = "",
           summary: bytes = b"") -> bytes:
    """An ``Event`` proto's bytes: ``wall_time`` (1, double), ``step`` (2,
    varint), ``file_version`` (3, string), ``summary`` (5); proto3 leaves a
    field at its default out."""
    out = b"\x09" + struct.pack("<d", wall_time)
    if step:
        out += b"\x10" + _varint(step)
    if file_version:
        out += _length_delimited(3, file_version.encode())
    if summary:
        out += _length_delimited(5, summary)
    return out


def _scalar_summary(tag: str, value: float) -> bytes:
    """A ``Summary`` of one ``Summary.Value`` (1): ``tag`` (1, string) and
    ``simple_value`` (2, float32; in a oneof, so written even when 0)."""
    value_bytes = _length_delimited(1, tag.encode()) + b"\x15" + _float32(value)
    return _length_delimited(1, value_bytes)


def _wall_time() -> float:
    """Seconds since the epoch: an Event's ``wall_time``, a timestamp and
    not a duration (durations here use ``time.perf_counter``)."""
    return time.time_ns() * 1e-9


# the file name's last part counts the writers of this process, as torch's
# SummaryWriter counts its own
_WRITER_UIDS = itertools.count()


class EventFileWriter:
    """Scalars to ``<log_dir>/events.out.tfevents.<time>.<host>.<pid>.<n>``,
    the file TensorBoard reads. ``add_scalar`` / ``flush`` / ``close`` are
    ``SummaryWriter``'s; the records are buffered in the open file until a
    flush. ``records`` and ``bytes`` count what was written, the version
    record included."""

    def __init__(self, log_dir: str):
        os.makedirs(log_dir, exist_ok=True)
        self.path = os.path.join(
            log_dir, "events.out.tfevents.%010d.%s.%s.%s" % (
                time.time_ns() // 10 ** 9, socket.gethostname(), os.getpid(),
                next(_WRITER_UIDS)))
        self._fh = open(self.path, "wb")
        self.records = 0
        self.bytes = 0
        self._write(_event(_wall_time(), file_version="brain.Event:2"))
        self.flush()

    def _write(self, payload: bytes) -> None:
        length = struct.pack("<Q", len(payload))
        record = (length + struct.pack("<I", masked_crc32c(length)) + payload
                  + struct.pack("<I", masked_crc32c(payload)))
        self._fh.write(record)
        self.records += 1
        self.bytes += len(record)

    def add_scalar(self, tag: str, value: float, step: int) -> None:
        self._write(_event(_wall_time(), step=int(step),
                           summary=_scalar_summary(tag, float(value))))

    def flush(self) -> None:
        self._fh.flush()

    def close(self) -> None:
        self._fh.close()


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
                with span("metrics.tensorboard_writer",
                          tracer=getattr(ledger, "tracer", None),
                          format="tfevents"):
                    self._tb = EventFileWriter(os.path.join(run_dir, "tb"))
            except Exception:
                self._tb = None  # the JSONL is always written

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
            self._tb.close()
            led = self._active_ledger()
            if led is not None:
                led.event("tensorboard_events", path=self._tb.path,
                          records=self._tb.records, bytes=self._tb.bytes)

    def __enter__(self) -> "MetricsLogger":
        return self

    def __exit__(self, *exc) -> Optional[bool]:
        self.close()
        return None
