"""The multichip dry run's guard: ``__graft_entry__.dryrun_multichip`` must
produce a machine-readable artifact even when the real TPU backend is down.

Round 4 lost the driver's dry-run artifact to a transiently-unavailable chip
(the record was removed in PR 21): ``dryrun_multichip`` probed
``jax.devices()`` in the driver's process and hung with it (rc=124). These
tests pin the round-5 guard: a backend-blind re-exec decision in
``__graft_entry__``.
"""

import importlib.util
import json
import os
import subprocess
import types

import pytest

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _load_module(name, filename):
    spec = importlib.util.spec_from_file_location(name, os.path.join(_REPO, filename))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.fixture(scope="module")
def graft():
    return _load_module("graft_under_test", "__graft_entry__.py")


def test_dryrun_decision_never_probes_the_real_backend(graft, monkeypatch):
    """With JAX_PLATFORMS pointing anywhere but cpu, dryrun_multichip must
    re-exec a CPU subprocess without ever calling jax.devices() in the
    parent — that probe takes the chip, and hangs on an unhealthy one."""
    monkeypatch.setenv("JAX_PLATFORMS", "tpu")

    def poisoned_devices(*a, **kw):
        pytest.fail("dryrun_multichip touched the parent's backend")

    monkeypatch.setattr(graft.jax, "devices", poisoned_devices)

    seen = {}

    def fake_run(cmd, **kw):
        seen["cmd"], seen["env"] = cmd, kw.get("env", {})
        seen["timeout"] = kw.get("timeout")
        return types.SimpleNamespace(returncode=0, stdout="ok\n", stderr="")

    monkeypatch.setattr(graft.subprocess, "run", fake_run)
    graft.dryrun_multichip(8)

    assert seen["env"]["JAX_PLATFORMS"] == "cpu"
    assert "--xla_force_host_platform_device_count=8" in seen["env"]["XLA_FLAGS"]
    assert seen["timeout"] is not None  # a wedged child cannot hang the driver
    assert "dryrun" in seen["cmd"]


def test_dryrun_subprocess_failure_is_a_readable_error(graft, monkeypatch):
    monkeypatch.setenv("JAX_PLATFORMS", "tpu")
    monkeypatch.setattr(
        graft.jax, "devices",
        lambda *a, **kw: pytest.fail("touched the parent's backend"),
    )
    monkeypatch.setattr(
        graft.subprocess, "run",
        lambda cmd, **kw: types.SimpleNamespace(
            returncode=3, stdout="", stderr="boom"
        ),
    )
    with pytest.raises(RuntimeError, match="rc=3"):
        graft.dryrun_multichip(8)


def test_dryrun_subprocess_timeout_is_a_readable_error(graft, monkeypatch):
    monkeypatch.setenv("JAX_PLATFORMS", "tpu")
    monkeypatch.setattr(
        graft.jax, "devices",
        lambda *a, **kw: pytest.fail("touched the parent's backend"),
    )

    def raise_timeout(cmd, **kw):
        raise subprocess.TimeoutExpired(cmd, kw.get("timeout", 0), stderr=b"slow")

    monkeypatch.setattr(graft.subprocess, "run", raise_timeout)
    with pytest.raises(RuntimeError, match="exceeded"):
        graft.dryrun_multichip(8, timeout_s=1.0)


def test_dryrun_reexecs_when_config_overrides_cpu_env(graft, monkeypatch):
    """A jax_platforms value set through jax.config (here 'tpu,cpu') beats
    the JAX_PLATFORMS env var — so env=cpu alone is NOT proof that
    jax.devices() can't init the real backend. The decision must consult
    the effective config value."""
    monkeypatch.setenv("JAX_PLATFORMS", "cpu")
    monkeypatch.setattr(
        type(graft.jax.config), "jax_platforms",
        property(lambda self: "tpu,cpu"), raising=False,
    )
    monkeypatch.setattr(
        graft.jax, "devices",
        lambda *a, **kw: pytest.fail("touched the parent's backend"),
    )
    seen = {}
    monkeypatch.setattr(
        graft.subprocess, "run",
        lambda cmd, **kw: seen.update(env=kw.get("env", {})) or
        types.SimpleNamespace(returncode=0, stdout="", stderr=""),
    )
    graft.dryrun_multichip(8)
    assert seen["env"]["JAX_PLATFORMS"] == "cpu"


def test_dryrun_runs_inline_when_already_on_a_big_cpu_mesh(graft, monkeypatch):
    """When the process is already pinned to cpu with enough devices (the
    test-suite configuration), no subprocess indirection should happen."""
    monkeypatch.setenv("JAX_PLATFORMS", "cpu")
    monkeypatch.setattr(graft.jax, "devices", lambda *a, **kw: list(range(8)))
    monkeypatch.setattr(
        graft.subprocess, "run",
        lambda *a, **kw: pytest.fail("re-exec'd despite a sufficient cpu mesh"),
    )
    ran = {}
    monkeypatch.setattr(graft, "_dryrun_impl", lambda n: ran.setdefault("n", n))
    graft.dryrun_multichip(8)
    assert ran["n"] == 8


@pytest.mark.slow
def test_dryrun_writes_obs_ledger_acceptance(graft, tmp_path, monkeypatch):
    """The ISSUE 5 acceptance criterion, end to end on the in-process
    8-device CPU mesh: the dryrun writes dryrun_ledger.jsonl with ≥1
    comm_analysis event carrying nonzero collective bytes, a per-device
    memory snapshot, and passing divergence verdicts; obs_diff self-compare
    exits 0 and an injected +20% collective-bytes delta exits 1 with a
    machine-readable comm verdict."""
    ledger = str(tmp_path / "dryrun_ledger.jsonl")
    monkeypatch.setenv("VIDEOP2P_DRYRUN_LEDGER", ledger)
    graft._dryrun_impl(8)

    events = [json.loads(l) for l in open(ledger) if l.strip()]
    comm = [e for e in events if e["event"] == "comm_analysis"]
    assert any(e["collective_bytes"] > 0 for e in comm)
    assert any(e["event"] == "memory" and e.get("devices") for e in events)
    divs = [e for e in events if e["event"] == "divergence"]
    assert divs and all(e["value"] == 0.0 for e in divs)
    dev = [e for e in events if e["event"] == "device_telemetry"]
    assert dev and all(e["divergence_max"] == 0.0 for e in dev)

    obs_diff = _load_module("obs_diff_under_graft_test", "tools/obs_diff.py")
    assert obs_diff.main(["obs_diff.py", ledger, ledger]) == 0
    # inject +20% collective bytes into a copy → nonzero exit + verdict
    perturbed = str(tmp_path / "perturbed.jsonl")
    with open(perturbed, "w") as f:
        for e in events:
            if e["event"] == "comm_analysis":
                e = dict(e, collective_bytes=int(e["collective_bytes"] * 1.2))
            f.write(json.dumps(e) + "\n")
    assert obs_diff.main(["obs_diff.py", ledger, perturbed]) == 1


@pytest.mark.slow
def test_dryrun_longvideo_obs_acceptance(graft, tmp_path):
    """The ISSUE 10 acceptance criterion end to end on the in-process
    8-device CPU mesh: the 64-frame dryrun section completes its float8
    sharded cached edit with src_err == 0.0, lands per-frame-count
    frame_scaling events and the ring/tp comm evidence in the ledger, and
    the ring before/after pair gates through tools/obs_diff.py — exit 0 in
    the engineered direction (collective count/bytes DROP), exit 0 on
    self-compare, exit 1 on an injected collective-bytes bump."""
    from videop2p_tpu.obs.ledger import RunLedger

    ledger_path = str(tmp_path / "longvideo_ledger.jsonl")
    led = RunLedger(ledger_path, mesh="1,8,1",
                    meta={"cli": "longvideo_acceptance"}).activate()
    try:
        res = graft._dryrun_longvideo_impl(8, led)
    finally:
        led.close()
    assert res["src_err_64f"] == 0.0
    assert res["ring"]["overlap"]["collective_permute_count"] == 14
    assert res["ring"]["serial"]["collective_permute_count"] == 16

    events = [json.loads(l) for l in open(ledger_path) if l.strip()]
    fs = [e for e in events if e["event"] == "frame_scaling"]
    assert {e["frames"] for e in fs} >= {8, 32, 64}
    edit = [e for e in fs if e["variant"] == "edit"]
    assert edit and edit[0]["src_err"] == 0.0
    assert edit[0]["temporal_maps_dtype"] == "float8_e4m3fn"
    comm = [e for e in events if e["event"] == "comm_analysis"]
    assert any(e["program"] == "sharded_edit_64f" for e in comm)
    assert any(e["program"] == "tp_out_scatter" for e in comm)

    obs_diff = _load_module("obs_diff_under_longvideo_test", "tools/obs_diff.py")
    assert obs_diff.main(
        ["obs_diff.py", res["ring_before"], res["ring_after"]]
    ) == 0
    assert obs_diff.main(["obs_diff.py", ledger_path, ledger_path]) == 0
    perturbed = str(tmp_path / "perturbed.jsonl")
    with open(perturbed, "w") as f:
        for e in events:
            if e["event"] == "comm_analysis":
                e = dict(e, collective_bytes=int(e["collective_bytes"] * 1.2))
            f.write(json.dumps(e) + "\n")
    assert obs_diff.main(["obs_diff.py", ledger_path, perturbed]) == 1
