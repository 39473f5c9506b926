"""The metrics logger's TensorBoard event file (``videop2p_tpu/utils/metrics.py``).

The writer is the package's own, standard library only: TFRecord framing
with masked CRC-32C around hand-encoded ``Event`` protos. These tests read
the file back record by record and decode it with TensorBoard's own proto
(``tensorboard.compat.proto``, which imports neither torch nor TensorFlow),
and hold building a logger to importing neither.
"""

import math
import os
import re
import socket
import struct
import subprocess
import sys

import pytest

from videop2p_tpu.obs import RunLedger, read_ledger
from videop2p_tpu.utils.metrics import MetricsLogger, crc32c, masked_crc32c

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _records(path):
    """Each record's payload, its length and both masked CRCs checked."""
    with open(path, "rb") as f:
        data = f.read()
    payloads, at = [], 0
    while at < len(data):
        length = data[at:at + 8]
        n, = struct.unpack("<Q", length)
        assert struct.unpack("<I", data[at + 8:at + 12])[0] == masked_crc32c(length)
        payload = data[at + 12:at + 12 + n]
        assert len(payload) == n
        assert (struct.unpack("<I", data[at + 12 + n:at + 16 + n])[0]
                == masked_crc32c(payload))
        payloads.append(payload)
        at += 16 + n
    assert at == len(data)
    return payloads


def _events(path):
    """The file's records as TensorBoard's ``Event`` protos; each payload is
    the bytes protobuf itself writes for that Event."""
    event_pb2 = pytest.importorskip("tensorboard.compat.proto.event_pb2")
    events = []
    for payload in _records(path):
        event = event_pb2.Event.FromString(payload)
        assert event.SerializeToString() == payload
        events.append(event)
    return events


def _scalars(events):
    """``(tag, step, simple_value)`` of every scalar, in file order, after the
    version record."""
    head, *rest = events
    assert head.file_version == "brain.Event:2" and head.wall_time > 0
    out = []
    for event in rest:
        assert event.wall_time >= head.wall_time
        value, = event.summary.value
        assert value.WhichOneof("value") == "simple_value"
        out.append((value.tag, event.step, value.simple_value))
    return out


def _float32(v):
    """``v`` as a float32 reads it back; past its range, infinity."""
    try:
        return struct.unpack("<f", struct.pack("<f", v))[0]
    except OverflowError:
        return math.copysign(math.inf, v)


def test_crc32c_known_answer_and_mask():
    assert crc32c(b"123456789") == 0xE3069283
    assert masked_crc32c(b"123456789") == 0xC78AB0E5
    assert crc32c(b"") == 0


def test_event_file_round_trips_through_tensorboards_proto(tmp_path):
    """Several steps of several scalars come back tag for tag and step for
    step; step 0 and a value of 0 (which proto3 encodes differently) and a
    value past float32's range (infinity, as protobuf writes it) among
    them."""
    logged = [(step, {"train_loss": 1.0 / (step + 1), "lr": 3e-5 * step,
                      "grad_norm": -2.5 * step})
              for step in range(5)]
    logged.append((7, {"big": 1e40, "train_loss": 0.0}))
    with MetricsLogger(str(tmp_path)) as logger:
        for step, scalars in logged:
            logger.log(step, scalars)
    files = os.listdir(tmp_path / "tb")
    assert len(files) == 1
    assert re.fullmatch(r"events\.out\.tfevents\.\d{10}\.%s\.%d\.\d+"
                        % (re.escape(socket.gethostname()), os.getpid()),
                        files[0])
    events = _events(str(tmp_path / "tb" / files[0]))
    want = [(tag, step, _float32(value))
            for step, scalars in logged for tag, value in scalars.items()]
    assert want[-2][2] == float("inf")
    assert _scalars(events) == want
    assert logger._tb.records == len(events)


def test_close_records_the_event_files_counts_in_the_ledger(tmp_path):
    path = str(tmp_path / "ledger.jsonl")
    with RunLedger(path) as led:
        logger = MetricsLogger(str(tmp_path / "run"), ledger=led)
        for step in range(1, 4):
            logger.log(step, {"train_loss": 0.5, "lr": 1e-4})
        logger.close()
    written, = [e for e in read_ledger(path)
                if e["event"] == "tensorboard_events"]
    assert written["path"] == logger._tb.path
    assert written["records"] == 1 + 3 * 2
    assert written["bytes"] == os.path.getsize(logger._tb.path)


def test_building_a_logger_imports_neither_torch_nor_tensorflow(tmp_path):
    """In a fresh process (other tests import torch): the tuning CLI's import,
    a logger built and one step logged leave both out of ``sys.modules``."""
    script = (
        "import sys\n"
        "import videop2p_tpu.cli.run_tuning\n"
        "from videop2p_tpu.utils.metrics import MetricsLogger\n"
        "with MetricsLogger(sys.argv[1]) as logger:\n"
        "    logger.log(1, {'train_loss': 0.25})\n"
        "assert logger._tb is not None\n"
        "print(sorted(m for m in ('torch', 'tensorflow', 'tensorboard')\n"
        "             if m in sys.modules))\n")
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    out = subprocess.run([sys.executable, "-c", script, str(tmp_path)],
                         cwd=_REPO, env=env, capture_output=True, text=True,
                         timeout=300)
    assert out.returncode == 0, out.stderr
    assert out.stdout.strip().splitlines()[-1] == "[]"


def test_a_tune_run_leaves_one_event_file_and_its_counts(tmp_path):
    """A tiny ``run_tuning.main`` run to its end: ``<output_dir>/tb/`` holds
    one event file that decodes into every logged step's scalars, tag for
    tag and step for step, and the run's ledger holds its record and byte
    counts."""
    from videop2p_tpu.cli import run_tuning
    from videop2p_tpu.cli.common import load_config

    config = load_config(os.path.join(_REPO, "configs",
                                      "deepseek-v32-s16-tune.yaml"))
    out, path = tmp_path / "run", str(tmp_path / "ledger.jsonl")
    config.update(output_dir=str(out),
                  train_data={"n_tokens": 32, "document_seed": 1},
                  max_train_steps=4, steps_per_call=2, log_every=2,
                  checkpointing_steps=0, validation_steps=0)
    run_tuning.main(**config, tiny=True, ledger=path)
    events = read_ledger(path)
    written, = [e for e in events if e["event"] == "tensorboard_events"]
    tb = written["path"]
    tb_dir, name = os.path.split(tb)
    # main suffixes its output_dir with the run's settings
    assert tb_dir.startswith(str(out)) and os.path.basename(tb_dir) == "tb"
    assert os.listdir(tb_dir) == [name]
    assert written["bytes"] == os.path.getsize(tb)
    scalars = _scalars(_events(tb))
    assert written["records"] == 1 + len(scalars)
    logged = [e for e in events if e["event"] == "metric"]
    assert [e["step"] for e in logged] == [1, 2, 3, 4]
    assert scalars == [(tag, e["step"], _float32(value)) for e in logged
                       for tag, value in e.items()
                       if tag not in ("event", "t", "step", "wall_s")]
