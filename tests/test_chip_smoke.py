"""``chip_smoke.py``'s contract, the cheap part: no model is built here.

The script is the proof a driver takes that the system starts on the chip,
so what it may print is pinned: without a TPU it exits non-zero and prints
no result, rehearsal switch or not; a phase that raises ends the run with no
last line; and the last line, on a TPU, is exactly the contract's object.
The full tiny rehearsal (tune → edit → two served requests) takes minutes
and is run by hand (``python chip_smoke.py --rehearse``, CHANGES.md PR 21).
"""

import importlib.util
import json
import os
import subprocess
import sys
import types

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SCRIPT = os.path.join(REPO, "chip_smoke.py")


@pytest.fixture()
def smoke(monkeypatch, tmp_path):
    spec = importlib.util.spec_from_file_location("chip_smoke", SCRIPT)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    # phases are replaced in every case below; keep the run off outputs/
    # and off the suite's compile-cache settings
    monkeypatch.setattr(mod, "OUT", str(tmp_path / "out"))
    monkeypatch.setattr("videop2p_tpu.cli.common.enable_compile_cache",
                        lambda: str(tmp_path / "cache"))
    # (the real one re-points UNet3DConfig.sd15 for the life of the process)
    monkeypatch.setattr(mod, "cut_depth", lambda: {})
    return mod


def _fake_tpu(n=1):
    return [types.SimpleNamespace(platform="tpu", device_kind="TPU v5 lite",
                                  memory_stats=lambda: None)
            for _ in range(n)]


def _ok_phase(name):
    def phase(ctx):
        return {"phase": name}
    return phase


def test_no_tpu_exits_nonzero_and_prints_no_result():
    """As the driver runs it in the sandbox: a CPU-only process."""
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run([sys.executable, SCRIPT], capture_output=True,
                          text=True, timeout=120, env=env, cwd=REPO)
    assert proc.returncode not in (0, None)
    assert proc.stdout.strip() == ""
    assert "no TPU" in proc.stderr


@pytest.mark.parametrize("argv", [[], ["--chips", "4"], ["--rehearse"],
                                  ["--rehearse", "--chips", "4"]],
                         ids=lambda a: " ".join(a) or "default")
def test_cpu_never_prints_ok_true(smoke, monkeypatch, capsys, argv):
    """On the CPU no switch leads to a result: the plain run refuses to
    start, and a rehearsal that passes every phase still exits 3 with
    ``"ok": false``."""
    monkeypatch.setattr(smoke, "PHASES_ONE_CHIP", (_ok_phase("a"),))
    monkeypatch.setattr(smoke, "PHASES_FOUR_CHIPS", (_ok_phase("b"),))
    rc = smoke.main(argv)
    out = capsys.readouterr().out
    assert rc != 0
    assert '"ok": true' not in out
    if "--rehearse" in argv:
        assert rc == 3
        assert json.loads(out.strip().splitlines()[-1])["ok"] is False


def test_failed_phase_gives_no_last_line(smoke, monkeypatch, capsys):
    import jax

    def boom(ctx):
        raise RuntimeError("impossible mesh")

    monkeypatch.setattr(jax, "devices", lambda *a, **k: _fake_tpu())
    monkeypatch.setattr(smoke, "PHASES_ONE_CHIP", (_ok_phase("a"), boom,
                                                   _ok_phase("never")))
    with pytest.raises(RuntimeError, match="impossible mesh"):
        smoke.main([])
    out = capsys.readouterr().out
    assert '"ok"' not in out
    assert '"never"' not in out  # no carrying on after a failure


@pytest.mark.parametrize("chips", [1, 4])
def test_last_line_is_the_contracts_object(smoke, monkeypatch, capsys, chips):
    import jax

    monkeypatch.setattr(jax, "devices", lambda *a, **k: _fake_tpu(chips))
    monkeypatch.setattr(smoke, "PHASES_ONE_CHIP", (_ok_phase("a"),))
    monkeypatch.setattr(smoke, "PHASES_FOUR_CHIPS", (_ok_phase("b"),))
    assert smoke.main(["--chips", str(chips)]) == 0
    lines = capsys.readouterr().out.strip().splitlines()
    assert lines[-1] == json.dumps({"ok": True, "device": {
        "platform": "tpu", "kind": "TPU v5 lite", "count": chips}})
    assert all('"ok"' not in line for line in lines[:-1])
    # the four-chip switch runs its own phase and no other
    names = [json.loads(line).get("phase") for line in lines[:-1]]
    assert names == ["start", "b" if chips == 4 else "a", "end"]


def test_result_line_refuses_anything_but_a_tpu(smoke):
    cpu = [types.SimpleNamespace(platform="cpu", device_kind="cpu")]
    with pytest.raises(RuntimeError, match="not a TPU"):
        smoke.result_line(cpu)


def test_four_chips_needs_four_devices(smoke, monkeypatch, capsys):
    import jax

    monkeypatch.setattr(jax, "devices", lambda *a, **k: _fake_tpu(1))
    assert smoke.main(["--chips", "4"]) == 2
    assert capsys.readouterr().out.strip() == ""
