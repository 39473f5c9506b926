"""Falcon-H1-34B's cell's own pieces on the CPU: the file states the cut, the
CLI config states the same model, the operation count hand-checked against
the configuration's shapes, the readers on what a traced run hands them (and
on a program that has none of it: None, never 0), the counters' gaps, and a
``--rehearse`` run of the cell with each planted fault at rehearsal size."""

import dataclasses
import json
import os
import subprocess
import sys

import numpy as np
import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
ROOT = os.path.dirname(BENCH)
sys.path.insert(0, BENCH)

import run as bench_run  # noqa: E402

from benchmark.harness import flops, flops_falcon  # noqa: E402

CELL = "falcon-h1-34b-s1-tune.doc32k-steps"
LAYER = "model step (models/falcon_h1.py)"
NEW_METRICS = ("falcon_h1_mfu.tune", "dense_ffn_ms.tune")
APPENDED = ("device_idle.tune", "device_peak_gib.tune", "setup_models_s.tune",
            "setup_trace_lower_s.tune", "setup_load_s.tune",
            "setup_analysis_s.tune", "setup_before_program_s.tune",
            "setup_import_s.tune", "setup_tensorboard_s.tune",
            "setup_document_s.tune", "host_between_calls_ms.tune",
            "ssd_scan_ms.tune", "mamba_proj_ms.tune", "attention_ms.tune",
            "ssd_state_rms.tune")
T = 32768


@pytest.fixture(scope="module")
def found():
    manifest = bench_run.load_json(os.path.join(ROOT, "BENCHMARK.json"))
    return (manifest,) + bench_run.find_cell(manifest, CELL)


def test_cell_is_found_by_name_and_states_the_cut(found):
    manifest, entry, cell, cfg_entry, config = found
    assert entry["chips"] == 1 and cell["driver"] == "tune_falcon_steps"
    assert os.path.isfile(os.path.join(BENCH, "drivers", cell["driver"] + ".py"))
    assert cell["why"] == entry["why"] and len(entry["why"]) <= 200
    assert len(cfg_entry["why"]) <= 200
    assert entry["traffic"] == cell["traffic"] == "doc32k-steps"
    assert config["geometry"] == {"tokens": 32768, "batch": 1}
    assert cell["cli_overrides"]["steps_per_call"] == 5
    names = [w["name"] for w in manifest["workloads"]]
    assert names.index(CELL) > names.index("keye-vl2-30b-a3b-s1-tune.doc16k-steps")
    dep = config["deployment"]
    assert sorted(cfg_entry["reduced"]) == sorted(config["reduced"]) == [
        "num_hidden_layers", "vocab_size"]
    for key in cfg_entry["reduced"]:
        assert config[key] != dep[key + "_published"], key
    assert (dep["chips_a_layer"], dep["pipeline_stages"], dep["layers_a_stage"],
            dep["vocabulary_shards"], dep["chips"]) == (1, 18, 4, 8, 72)
    assert 8 * config["vocab_size"] == dep["vocab_size_published"] == 261120
    assert (dep["num_hidden_layers_published"] == 72
            == dep["pipeline_stages"] * dep["layers_a_stage"])
    assert config["num_hidden_layers"] == 4 == dep["layers_a_stage"]
    # no width is cut, and every multiplier is the published one
    widths = dict(hidden_size=5120, intermediate_size=21504, head_dim=128,
                  num_attention_heads=20, num_key_value_heads=4,
                  mamba_d_ssm=4096, mamba_n_heads=32, mamba_d_head=128,
                  mamba_n_groups=2, mamba_d_state=256, mamba_d_conv=4,
                  mamba_chunk_size=128, rope_theta=100000000000,
                  ssm_in_multiplier=0.25, attention_out_multiplier=0.0375,
                  lm_head_multiplier=0.0078125)
    assert {k: config[k] for k in widths} == widths
    assert config["ssm_multipliers"] == [0.3535533905932738, 0.25,
                                         0.1767766952966369, 0.5,
                                         0.3535533905932738]
    assert config["mlp_multipliers"] == [0.1767766952966369,
                                         0.011160714285714284]
    assert set(config["assumed"]) >= {"multipliers", "ssm_multipliers_order",
                                      "gated_norm", "mamba_leaves", "rotary",
                                      "mlp_expansion_factor"}
    assert "4.11 GB frozen" in dep["what"] or "4.11 GB" in dep["what"]


def test_configuration_holds_every_catalog_number_or_lists_it_reduced(found):
    """Against the published-architecture catalog's row, where
    ``MODEL_CATALOG`` names that JSONL file; the row's numbers are also
    pinned above, so a run without it loses nothing."""
    catalog = os.environ.get("MODEL_CATALOG", "")
    if not os.path.isfile(catalog):
        pytest.skip("no catalog here")
    _, _, _, cfg_entry, config = found
    with open(catalog) as f:
        row = next(r for r in map(json.loads, f)
                   if r["name"] == "Falcon-H1-34B-Instruct")
    assert cfg_entry["source"] == config["source"] == row["source_url"]
    for key, value in row["config"].items():
        if key in cfg_entry["reduced"]:
            assert config["deployment"][key + "_published"] == value, key
        else:
            assert config[key] == value, key


def test_the_cli_config_states_the_same_model(found):
    from videop2p_tpu.cli.common import MODEL_FAMILIES, load_config
    from videop2p_tpu.models.falcon_h1 import FalconH1Config

    _, _, cell, _, config = found
    driver = bench_run.load_module(os.path.join(BENCH, "drivers",
                                                "tune_falcon_steps.py"))
    model = driver.model_from_config(config)
    cli = load_config(os.path.join(ROOT, cell["cli_config"]))
    assert cli["model_family"] == config["model_type"] == "falcon_h1"
    assert "falcon_h1" in MODEL_FAMILIES
    assert cli["model"] == model
    assert list(cli["trainable_modules"]) == config["training"]["trainable_modules"]
    assert cli["train_data"]["n_tokens"] == config["geometry"]["tokens"]
    built = FalconH1Config.from_dict(model)
    built.check()
    assert (built.num_hidden_layers, built.vocab_size) == (4, 32640)
    # the reference reads the same numbers
    from benchmark.reference.falcon_h1 import arch_from_config

    arch = arch_from_config(config)
    for k, v in arch.items():
        assert getattr(built, k) == v, k


def test_tiny_arch_of_the_check_is_the_programs_tiny_preset():
    from videop2p_tpu.models.falcon_h1 import FalconH1Config

    from benchmark.reference import falcon_h1, tune_falcon_check

    tiny = dataclasses.asdict(FalconH1Config.tiny())
    assert set(tune_falcon_check.TINY_ARCH) == set(falcon_h1.ARCH_KEYS)
    for k, v in tune_falcon_check.TINY_ARCH.items():
        assert tiny[k] == v, k


def test_per_layer_metrics_of_the_cell_have_readers(found):
    manifest = found[0]
    reported = {m["name"] for m in bench_run.metrics_for(
        manifest, "end_to_end", CELL, set())}
    assert reported == {"tune_step_ms", "setup_s"}
    by_name = {m["name"]: m for m in bench_run.metrics_for(
        manifest, "per_layer", CELL, reported)}
    assert set(NEW_METRICS) | set(APPENDED) == set(by_name)
    for n in NEW_METRICS:
        assert by_name[n]["workloads"] == [CELL]
        assert by_name[n]["layer"] == LAYER
        assert by_name[n]["moves"] == "tune_step_ms"
        assert os.path.isfile(os.path.join(BENCH, "layer_metrics",
                                           n.rsplit(".", 1)[0] + ".py"))
    for n in by_name:
        assert hasattr(bench_run.find_reader(n), "read"), n
    tune_step = next(m for m in manifest["end_to_end"]
                     if m["name"] == "tune_step_ms")
    assert CELL in tune_step["workloads"]


def test_operation_count_by_hand(found):
    """A layer and the whole step, from the shapes, by hand (TFLOP of the
    forward): the feed-forward 21.65 a layer, the attention products 5.50
    a layer (20 heads of 128 at T (T + 1) / 2 causal pairs, two products),
    the in-projection 2.93 + its C columns 0.17, the out-projection 1.37, q / k / v / o
    2.06, the scan's four products 0.16 (C B^T per group, the masked product
    per head inside the 256 chunks of 128, the chunk states and the carried
    state per head), the head 10.95 / 1.00 of the vocabulary slice: 309.35
    TFLOP a tuning step."""
    config = found[4]
    h, pairs = 5120, T * (T + 1) / 2
    ops = {o["site"]: o for o in flops_falcon.falcon_ops(config, T)}
    assert ops["layers_1.q_proj"]["fwd"] == 2 * T * h * 20 * 128
    assert ops["layers_1.k_proj"]["fwd"] == 2 * T * h * 4 * 128
    assert ops["layers_1.attn.qk"]["fwd"] == 2 * 20 * 128 * pairs
    assert ops["layers_1.in_proj"]["fwd"] == 2 * T * h * 9248 - 2 * T * h * 512
    assert ops["layers_1.in_proj_c"]["fwd"] == 2 * T * h * 512
    chunk_pairs = 256 * 128 * 129 / 2
    assert ops["layers_1.ssd.cb"]["fwd"] == 2 * 2 * 256 * chunk_pairs
    assert ops["layers_1.ssd.inside"]["fwd"] == 2 * 32 * 128 * chunk_pairs
    assert ops["layers_1.ssd.chunk_state"]["fwd"] == 2 * T * 32 * 128 * 256
    assert ops["layers_1.mlp.up_proj"]["fwd"] == 2 * T * h * 21504
    assert ops["head"]["fwd"] == 2 * T * h * 32640
    # trainable leaves get a weight gradient; layer 0's input is the frozen
    # embedding: of its branches only the queries and C carry a gradient
    q0, q1 = ops["layers_0.q_proj"], ops["layers_1.q_proj"]
    assert q0["weight_grad"] and q0["act_operands_with_grad"] == 0
    assert q1["weight_grad"] and q1["act_operands_with_grad"] == 1
    assert ops["layers_0.in_proj_c"]["weight_grad"]
    assert not ops["layers_0.in_proj"]["weight_grad"]
    assert ops["layers_0.attn.qk"]["act_operands_with_grad"] == 1
    assert ops["layers_1.attn.qk"]["act_operands_with_grad"] == 2
    assert ops["layers_0.ssd.chunk_state"]["act_operands_with_grad"] == 0
    assert ops["layers_0.mlp.gate_proj"]["act_operands_with_grad"] == 1
    tf = lambda *parts: sum(o["fwd"] for s, o in ops.items()  # noqa: E731
                            if any(s.startswith(p) for p in parts)) / 1e12
    assert round(tf("layers_0.mlp"), 2) == 21.65
    assert round(tf("layers_0.attn"), 2) == 5.50
    assert round(ops["layers_0.in_proj"]["fwd"] / 1e12, 2) == 2.93
    assert round(ops["layers_0.in_proj_c"]["fwd"] / 1e12, 2) == 0.17
    assert round(tf("layers_0.out_proj"), 2) == 1.37
    assert round(tf("layers_0.q_proj", "layers_0.k_proj", "layers_0.v_proj",
                    "layers_0.o_proj"), 2) == 2.06
    assert round(tf("layers_0.ssd"), 2) == 0.16
    assert round(ops["head"]["fwd"] / 1e12, 2) == 10.95
    step = flops.tune_step_flops(list(ops.values()))
    assert round(step / 1e12, 2) == 309.35
    # the mixer's and the feed-forward's shares of the step (ISSUE: ≈ 39 %
    # and ≈ 54 % counted a little differently)
    share = lambda *parts: flops.tune_step_flops(  # noqa: E731
        [o for s, o in ops.items() if any(p in s for p in parts)]) / step
    assert 0.55 < share(".mlp.") < 0.57
    assert 0.31 < share(".in_proj", ".conv", ".ssd.", ".out_proj", ".attn.",
                        ".q_proj", ".k_proj", ".v_proj", ".o_proj") < 0.38


def _ctx(metric, found, **over):
    _, _, cell, _, config = found
    ctx = {"metric": metric, "cell": cell, "config": config,
           "device": {"kind": "TPU v5 lite", "count": 1},
           "window": {"kind": "tune_falcon", "tokens": T, "batch": 1,
                      "traced_steps": 5,
                      "counters": {"ssd_state_rms": 3e-5,
                                   "branch_rms_ratio": 0.4}},
           "trace": {"busy_s": 20.0, "window_s": 20.2,
                     "scope_s": {"lm.dense_ffn": 8.0, "lm.attention": 4.0,
                                 "lm.ssd": 3.0, "lm.mamba_proj": 2.0}}}
    ctx.update(over)
    return ctx


def test_readers_read_what_the_traced_run_hands_them(found):
    read = lambda m, **o: bench_run.find_reader(m).read(_ctx(m, found, **o))  # noqa: E731
    step = flops.tune_step_flops(flops_falcon.falcon_ops(found[4], T))
    assert read("falcon_h1_mfu.tune") == pytest.approx(
        100 * 5 * step / (20 * 197e12), rel=1e-9)
    assert 0 < read("falcon_h1_mfu.tune") < 100
    assert read("dense_ffn_ms.tune") == pytest.approx(1600.0)
    assert read("attention_ms.tune") == pytest.approx(800.0)
    assert read("ssd_scan_ms.tune") == pytest.approx(600.0)
    assert read("mamba_proj_ms.tune") == pytest.approx(400.0)
    assert read("ssd_state_rms.tune") == 3e-5


@pytest.mark.parametrize("metric", NEW_METRICS)
def test_readers_return_none_where_there_is_nothing_to_read(found, metric):
    """A program without the scope (the parent, another family's window), or
    an untraced run: the metric is left out, never 0."""
    read = bench_run.find_reader(metric).read
    other = {"window": {"kind": "tune_hybrid", "traced_steps": 5, "batch": 1,
                        "tokens": T, "counters": {}},
             "trace": {"busy_s": 6.0, "window_s": 6.1,
                       "scope_s": {"lm.experts": 1.0}}}
    assert read(_ctx(metric, found, **other)) is None
    assert read(_ctx(metric, found, trace=None, window={
        "kind": "tune_falcon", "traced_steps": None})) is None


def test_counter_gaps_read_each_side_against_the_other():
    from benchmark.reference.tune_falcon_check import counter_gaps

    readings = [{"state_rms": 1.0, "branch_rms_ratio": 0.3},
                {"state_rms": 3.0, "branch_rms_ratio": 0.5}]
    g = counter_gaps({"ssd_state_rms": 2.2, "branch_rms_ratio": 0.44}, readings)
    assert g["state_rms_gap"] == pytest.approx(0.2 / 2.2)
    assert g["branch_ratio_gap"] == pytest.approx(0.1)
    # a side that hands nothing on reads 0: the gap is 1
    g = counter_gaps({"ssd_state_rms": 0.0, "branch_rms_ratio": 0.4}, readings)
    assert g["state_rms_gap"] == 1.0


# ------------------------------------------------ the cell, at rehearsal size


@pytest.fixture(scope="module")
def rehearsal():
    """ONE process: the cell's own driver at rehearsal size (the tiny
    preset, 64 tokens, ``run_tuning.main``), then the check once sound,
    once under the float8 control and once under every planted fault."""
    from benchmark.reference.falcon_h1 import FAULTS

    hows = ",".join(("sound", "float8_e4m3fn") + FAULTS)
    p = subprocess.run(
        [sys.executable, os.path.join(HERE, "read_limits_falcon.py"),
         "--workload", CELL, "--seed", str(2 ** 31 + 40), "--seconds", "0.3",
         "--trace", "0", "--rehearse", "--hows", hows],
        capture_output=True, text=True, cwd=ROOT,
        env=dict(os.environ, JAX_PLATFORMS="cpu"))
    assert p.returncode == 0, p.stderr[-3000:]
    lines = [json.loads(line) for line in p.stdout.splitlines() if line]
    return {r["reading"]: r for r in lines}


def test_rehearsal_runs_the_cell_through_main(rehearsal):
    prog = rehearsal["program"]
    assert prog["summary"]["failed"] == 0 and prog["summary"]["steps"] >= 5
    assert prog["end_to_end"]["tune_step_ms"] > 0
    assert set(prog["counters"]) == {"ssd_state_rms", "branch_rms_ratio"}
    sound = rehearsal["sound"]
    assert sound["correct_under_committed_limits"], sound["compared"]


# (fault, a number it moves at rehearsal size)
FAULT_MOVES = [
    ("float8_e4m3fn", "mu_gap_worst"), ("one_group", "change_diff_worst"),
    ("norm_ungrouped", "change_diff_worst"), ("no_carry", "state_rms_gap"),
    ("sequential", "branch_ratio_gap"), ("mup_off", "branch_ratio_gap"),
    ("mup_off", "change_diff_worst"), ("attn_out_one", "branch_ratio_gap")]


@pytest.mark.parametrize("fault,number", FAULT_MOVES)
def test_each_planted_fault_moves_a_number_at_rehearsal_size(rehearsal, fault,
                                                             number):
    """Every planted fault and the float8 control move the number named to
    over twice its sound reading at rehearsal size (which of the committed
    limits each breaks at the cell's size is the chip's reading: the cell's
    ``limits_from``)."""
    sound, faulty = rehearsal["sound"]["compared"], rehearsal[fault]["compared"]
    value = faulty[number]
    assert value is None or not np.isfinite(value) or value > 2 * max(
        sound[number], 1e-6), (fault, number, value, sound[number])
