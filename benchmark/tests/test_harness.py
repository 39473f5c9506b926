"""Peaks table, the result line, traffic, and discovery by name."""

import json
import os
import shutil
import subprocess
import sys

import pytest

from benchmark.harness import peaks, result, traffic
from conftest import with_serve_entries

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
ROOT = os.path.dirname(BENCH)


def _load(path):
    with open(path) as f:
        return json.load(f)


def test_peaks_table_refuses_unknown_kind():
    assert peaks.peaks_for("TPU v5 lite")["bf16_flops"] == 197e12
    assert peaks.peaks_for("TPU v5 lite")["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError, match="no published peaks"):
        peaks.peaks_for("TPU v9 imaginary")
    with pytest.raises(KeyError):
        peaks.peaks_for("cpu")


def test_result_line_refuses_without_tpu(capsys):
    dev = {"platform": "cpu", "kind": "cpu", "count": 1,
           "memory_peak_bytes": None}
    with pytest.raises(RuntimeError, match="no result"):
        result.emit_result(device=dev, chips=1, attempted=1, failed=0,
                           metrics={}, compared={"x": {"value": 0,
                                                       "limit": 0}})
    assert capsys.readouterr().out == ""
    # a rehearsal exits 3 and keeps stdout empty
    rc = result.emit_result(device=dev, chips=1, attempted=1, failed=0,
                            metrics={}, compared={"x": {"value": 0,
                                                        "limit": 0}},
                            rehearse=True)
    assert rc == 3 and capsys.readouterr().out == ""


def test_result_line_shape_and_verdict(capsys):
    dev = {"platform": "tpu", "kind": "TPU v5 lite", "count": 1,
           "memory_peak_bytes": 5}
    compared = {"a": {"value": 0.5, "limit": 1.0},
                "b": {"value": 2.0, "limit": 1.0}}
    rc = result.emit_result(device=dev, chips=1, attempted=3, failed=0,
                            metrics={"m": {"value": 1.0, "unit": "s"}},
                            compared=compared)
    out = capsys.readouterr()
    line = json.loads(out.out.strip().splitlines()[-1])
    assert rc == 0 and line["correct"] is False
    assert list(line)[-1] == "compared" and list(line)[:5] == [
        "correct", "attempted", "failed", "metrics", "device"]
    assert out.err.strip().splitlines()[-1].startswith("compared b = 2.0")
    assert result.verdict({"a": {"value": 0.5, "limit": 1.0}})
    assert not result.verdict({"a": {"value": None, "limit": 1.0}})
    assert not result.verdict({"a": {"value": float("nan"), "limit": 1.0}})
    assert not result.verdict({})


def test_run_refuses_without_tpu():
    """No TPU here: exit 2 and nothing on stdout."""
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run(
        [sys.executable, os.path.join(BENCH, "run.py"), "--workload",
         "sd15-tune-8f.steps", "--seed", "1", "--seconds", "1",
         "--trace", "0"], capture_output=True, text=True, env=env, cwd=ROOT)
    assert p.returncode == 2, p.stderr[-2000:]
    assert p.stdout == ""
    assert "Nothing was run" in p.stderr


def test_run_refuses_without_the_program(tmp_path):
    """A directory with only BENCHMARK.json and the benchmark's paths."""
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
    shutil.copytree(BENCH, tmp_path / "benchmark",
                    ignore=shutil.ignore_patterns("__pycache__"))
    p = subprocess.run(
        [sys.executable, "benchmark/run.py", "--workload",
         "sd15-tune-8f.steps", "--seed", "1", "--seconds", "1",
         "--trace", "0"], capture_output=True, text=True, cwd=tmp_path,
        env=dict(os.environ, JAX_PLATFORMS="cpu"))
    assert p.returncode != 0 and p.stdout == ""


def test_traffic_same_set_every_seed():
    cell = _load(os.path.join(BENCH, "workloads",
                              "sd15-edit-8f.serve-resident.json"))
    n = len(cell["insert_words"]) * len(cell["eq_values"])

    def first(seed):
        g = traffic.edit_requests(cell, ROOT, seed)
        return [next(g) for _ in range(n)]

    key = lambda r: (r["prompts"][1], r["eq_params"]["values"][0])  # noqa: E731
    a, b = first(1), first(2 ** 31 + 11)
    assert sorted(map(key, a)) == sorted(map(key, b))
    assert list(map(key, a)) != list(map(key, b))
    assert list(map(key, a)) == list(map(key, first(1)))
    r = a[0]
    assert r["prompt"] == cell["source_prompt"] == r["prompts"][0]
    assert r["blend_word"] == ["rabbit", "rabbit"] and not r["is_word_swap"]
    word = r["eq_params"]["words"][0]
    assert r["prompts"][1].split().index(word) + 1 == \
        r["prompts"][1].split().index("rabbit")


@pytest.mark.parametrize("listed", ["as_committed", "with_serve_cell"])
def test_manifest_names_resolve_to_files(listed):
    sys.path.insert(0, BENCH)
    import run as bench_run

    manifest = _load(os.path.join(ROOT, "BENCHMARK.json"))
    if listed == "with_serve_cell":
        manifest = with_serve_entries(manifest)
    reported = {}
    for w in manifest["workloads"]:
        entry, cell, cfg_entry, config = bench_run.find_cell(
            manifest, w["name"])
        assert cell["config"] == w["config"] == cfg_entry["name"]
        assert cell["chips"] == w["chips"] and cell["why"] == w["why"]
        assert os.path.isfile(os.path.join(BENCH, "drivers",
                                           cell["driver"] + ".py"))
        assert sorted(config["reduced"]) == sorted(cfg_entry["reduced"])
        e2e = {m["name"] for m in bench_run.metrics_for(
            manifest, "end_to_end", w["name"], set())}
        assert "setup_s" in e2e and len(e2e) >= 2
        layer = bench_run.metrics_for(manifest, "per_layer", w["name"], e2e)
        assert layer
        for m in layer:
            assert m["moves"] in e2e
            assert hasattr(bench_run.find_reader(m["name"]), "read")
        reported[w["name"]] = e2e


def test_a_new_cell_is_files_and_entries_only(tmp_path):
    """A temporary cell that reuses ``serve_closed_loop`` is found by name
    without touching a file that is there. (The served-edit cell it copies
    is built but not listed yet: its manifest entries come from the tests'
    data.)"""
    sys.path.insert(0, BENCH)
    import run as bench_run

    bench = tmp_path / "benchmark"
    shutil.copytree(BENCH, bench,
                    ignore=shutil.ignore_patterns("__pycache__"))
    before = {p: os.path.getmtime(os.path.join(dp, p))
              for dp, _, fs in os.walk(bench) for p in fs}
    cell = _load(os.path.join(BENCH, "workloads",
                              "sd15-edit-8f.serve-resident.json"))
    cell.update(name="sd15-edit-8f.serve-new-words", traffic="serve-new-words",
                insert_words=["velvet", "copper"])
    with open(bench / "workloads" / "sd15-edit-8f.serve-new-words.json",
              "w") as f:
        json.dump(cell, f)
    manifest = with_serve_entries(_load(os.path.join(ROOT, "BENCHMARK.json")))
    manifest["workloads"].append({
        "name": cell["name"], "config": "sd15-edit-8f",
        "traffic": cell["traffic"], "chips": 1, "why": cell["why"]})
    for m in manifest["end_to_end"] + manifest["per_layer"]:
        if "sd15-edit-8f.serve-resident" in m.get("workloads", []):
            m["workloads"].append(cell["name"])
    entry, found, cfg_entry, config = bench_run.find_cell(
        manifest, cell["name"], bench_dir=str(bench))
    assert found["driver"] == "serve_closed_loop"
    assert config["name"] == "sd15-edit-8f"
    e2e = {m["name"] for m in bench_run.metrics_for(
        manifest, "end_to_end", cell["name"], set())}
    assert e2e == {"edit_s", "setup_s"}
    names = [m["name"] for m in bench_run.metrics_for(
        manifest, "per_layer", cell["name"], e2e)]
    assert "unet_mfu.edit" in names
    for n in names:
        bench_run.find_reader(n, bench_dir=str(bench))
    reqs = traffic.edit_requests(found, ROOT, 5)
    assert next(reqs)["eq_params"]["words"][0] in ("velvet", "copper")
    after = {p: os.path.getmtime(os.path.join(dp, p))
             for dp, _, fs in os.walk(bench) for p in fs}
    assert {k: v for k, v in after.items() if k in before} == before
