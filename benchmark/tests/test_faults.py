"""Drives the rest of a run — everything but the harness's look for a chip
(``--rehearse``: tiny size, any backend, never a result line) — with the
timed path broken underneath, and sees ``correct`` come out false; and true
on the sound path, under the limits the cells commit.

Slow for a unit test (each case builds and compiles the tiny models: about
two minutes on this sandbox's CPU); not part of tier-1."""

import json
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
sys.path.insert(0, BENCH)

import run as bench_run  # noqa: E402


def rehearse(capsys, workload, seed=7, seconds=1.0):
    rc = bench_run.main(["--workload", workload, "--seed", str(seed),
                         "--seconds", str(seconds), "--trace", "0",
                         "--rehearse"])
    out = capsys.readouterr()
    assert rc == 3 and out.out == ""
    line = [ln for ln in out.err.splitlines()
            if ln.startswith("REHEARSAL (no result): ")][-1]
    return json.loads(line.split(": ", 1)[1])


TUNE = "sd15-tune-8f.steps"
SERVE = "sd15-edit-8f.serve-resident"


def _break_train_steps(monkeypatch, make_broken):
    from videop2p_tpu.cli import run_tuning

    real = run_tuning.train_steps
    monkeypatch.setattr(run_tuning, "train_steps", make_broken(real))


def test_tune_sound_run_is_correct(capsys):
    line = rehearse(capsys, TUNE)
    assert line["correct"] is True, line["compared"]
    assert line["attempted"] >= 1 and line["failed"] == 0


def test_tune_step_that_returns_its_state_unchanged(capsys, monkeypatch):
    def make(real):
        def broken(unet_fn, tx, s, *a, **kw):
            out = real(unet_fn, tx, s, *a, **kw)
            return (s,) + tuple(out[1:])
        return broken

    _break_train_steps(monkeypatch, make)
    line = rehearse(capsys, TUNE)
    assert line["correct"] is False
    c = line["compared"]["change_gap_worst"]
    assert abs(c["value"] - 1.0) < 1e-3 and c["value"] > c["limit"]


def test_tune_half_of_the_batch_left_out(capsys, monkeypatch):
    def make(real):
        def broken(unet_fn, tx, s, sched, latents, text, k, **kw):
            half = latents[:, : max(latents.shape[1] // 2, 1)]
            return real(unet_fn, tx, s, sched, half, text, k, **kw)
        return broken

    _break_train_steps(monkeypatch, make)
    line = rehearse(capsys, TUNE)
    assert line["correct"] is False
    failing = [k for k, c in line["compared"].items()
               if c["value"] > c["limit"]]
    assert failing, line["compared"]


def test_tune_answer_altered_where_it_is_produced(capsys, monkeypatch):
    def make(real):
        def broken(*a, **kw):
            out = real(*a, **kw)
            return (out[0], out[1] * 1.05) + tuple(out[2:])
        return broken

    _break_train_steps(monkeypatch, make)
    line = rehearse(capsys, TUNE)
    assert line["correct"] is False
    c = line["compared"]["loss_gap_first"]
    assert c["value"] > c["limit"]


def test_serve_sound_run_holds_all_but_the_chain(capsys, serve_listed):
    """The served-edit cell is not listed in ``BENCHMARK.json``: its check
    has no reference of the edit chain yet, says so as ``edit_chain_gap``
    None, and so can never come out correct."""
    line = rehearse(capsys, SERVE)
    c = line["compared"]
    assert c.pop("edit_chain_gap") == {"value": None, "limit": None}
    assert all(v["value"] <= v["limit"] for v in c.values()), c
    assert line["correct"] is False and line["failed"] == 0


def test_serve_answer_altered_where_it_is_produced(capsys, monkeypatch,
                                                   serve_listed):
    """The engine's own wrong-answer seam (``--faults wrong:*``) flips the
    served frames' channels after the device program."""
    real = bench_run.load_json

    def with_fault(path):
        d = real(path)
        if path.endswith(SERVE + ".json"):
            d["rehearse_engine_args"] = d["rehearse_engine_args"] + [
                "--faults", "wrong:*"]
        return d

    monkeypatch.setattr(bench_run, "load_json", with_fault)
    line = rehearse(capsys, SERVE)
    assert line["correct"] is False
    c = line["compared"]["vae_decode_gap"]
    assert c["value"] > c["limit"]
