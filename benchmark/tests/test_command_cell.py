"""The third token family's cell's own pieces on the CPU: the file states the
cut, the CLI config states the same model, the operation count and the
windowed pair's roofline count hand-checked against the configuration's
shapes, the readers on what a traced run hands them (and on a program that
has none of it: None, never 0), the kernel-time reducer, the levelling pass,
and a ``--rehearse`` run of the cell with each planted fault at rehearsal
size."""

import json
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
ROOT = os.path.dirname(BENCH)
sys.path.insert(0, BENCH)

import run as bench_run  # noqa: E402

from benchmark.harness import flops, flops_command, weights_command  # noqa: E402
from benchmark.harness import weights_hybrid  # noqa: E402

CELL = "command-a-plus-s8-tune.doc32k-steps"
LAYER = "model step (models/cohere2_moe.py)"
KERNELS = ("kernels (ops/attention.py, ops/groupnorm.py, "
           "ops/selected_attention.py, ops/grouped_experts.py)")
NEW_METRICS = {"command_a_mfu.tune": LAYER, "window_attention_ms.tune": LAYER,
               "shared_experts_ms.tune": LAYER,
               "window_attention_roofline.tune": KERNELS,
               "window_tile_share.tune": LAYER}
APPENDED = ("device_idle.tune", "device_peak_gib.tune", "setup_models_s.tune",
            "setup_trace_lower_s.tune", "setup_load_s.tune",
            "setup_analysis_s.tune", "setup_document_s.tune",
            "host_between_calls_ms.tune", "experts_ms.tune",
            "expert_load_max_over_mean.tune", "attention_ms.tune")


@pytest.fixture(scope="module")
def found():
    manifest = bench_run.load_json(os.path.join(ROOT, "BENCHMARK.json"))
    return (manifest,) + bench_run.find_cell(manifest, CELL)


def test_cell_is_found_by_name_and_states_the_cut(found):
    manifest, entry, cell, cfg_entry, config = found
    assert entry["chips"] == 1 and cell["driver"] == "tune_command_steps"
    assert os.path.isfile(os.path.join(BENCH, "drivers", cell["driver"] + ".py"))
    assert cell["why"] == entry["why"] and len(entry["why"]) <= 200
    assert len(cfg_entry["why"]) <= 200
    assert entry["traffic"] == cell["traffic"] == "doc32k-steps"
    assert config["geometry"] == {"tokens": 32768, "batch": 1}
    assert cell["document_seed"] == 32768
    assert cell["cli_overrides"]["steps_per_call"] == 5
    assert len(manifest["workloads"]) == 4
    # every key listed as reduced differs from the published value, and the
    # file states the published one beside it
    dep = config["deployment"]
    assert sorted(cfg_entry["reduced"]) == sorted(config["reduced"])
    for key in cfg_entry["reduced"]:
        assert config[key] != dep[key + "_published"], key
    assert (dep["chips_sharing_a_layer"], dep["pipeline_stages"],
            dep["chips"]) == (8, 8, 64)
    for key in ("num_experts", "num_attention_heads", "num_key_value_heads",
                "num_shared_experts", "vocab_size", "num_hidden_layers"):
        assert 8 * config[key] == dep[key + "_published"], key
    assert 8 * dep["shared_columns_held"][1] == dep["shared_columns_published"]
    assert dep["shared_columns_published"] == 4 * config["intermediate_size"]
    assert config["layer_types"] == dep["layer_types_published"][:4]
    assert dep["layer_types_published"] == config["layer_types"] * 8
    # guide section 4's floors: a whole period (four layers), >= 8 experts,
    # >= 1/8 of the vocabulary
    assert config["layer_types"] == ["sliding_attention"] * 3 + ["full_attention"]
    assert config["num_experts"] >= 8
    # no width is cut
    widths = dict(hidden_size=4096, intermediate_size=4096, head_dim=128,
                  num_experts_per_tok=8, sliding_window=4096, rope_theta=50000,
                  rotary_pct=1, layer_norm_eps=1e-5, logit_scale=1)
    assert {k: config[k] for k in widths} == widths
    assert set(config["assumed"]) >= {"shared_average_is_added", "window_edge",
                                      "intermediate_size", "router_rows"}
    assert "vision_tower" in config["departures"]


def test_configuration_holds_every_catalog_number_or_lists_it_reduced(found):
    """Against the catalog row where the guides are installed; the row's
    numbers are also pinned above, so a sandbox without it loses nothing."""
    catalog = "/opt/skills/guides/model-configs/architectures.jsonl"
    if not os.path.isfile(catalog):
        pytest.skip("no catalog here")
    _, _, _, cfg_entry, config = found
    with open(catalog) as f:
        row = next(r for r in map(json.loads, f)
                   if r["name"] == "command-a-plus-05-2026")
    assert cfg_entry["source"] == config["source"] == row["source_url"]
    for key, value in row["config"].items():
        if key in cfg_entry["reduced"]:
            assert config["deployment"][key + "_published"] == value, key
        else:
            assert config[key] == value, key


def test_the_cli_config_states_the_same_model(found):
    from videop2p_tpu.cli.common import MODEL_FAMILIES, load_config
    from videop2p_tpu.models.cohere2_moe import Cohere2MoeConfig

    _, _, cell, _, config = found
    driver = bench_run.load_module(os.path.join(BENCH, "drivers",
                                                "tune_command_steps.py"))
    model = driver.model_from_config(config)
    cli = load_config(os.path.join(ROOT, cell["cli_config"]))
    assert cli["model_family"] == config["model_type"] in MODEL_FAMILIES
    # the one thing the cell adds: the loss hands out what every layer chose
    assert model.pop("hand_out_choices") is True
    assert cli["model"] == model
    assert list(cli["trainable_modules"]) == config["training"]["trainable_modules"]
    assert cli["train_data"]["n_tokens"] == config["geometry"]["tokens"]
    built = Cohere2MoeConfig.from_dict(model)
    built.check()
    assert built.kv_heads_held == tuple(config["deployment"]["kv_heads_held"])
    assert built.shared_columns_held == (0, 2048)
    # the reference is told the same share
    from benchmark.reference.cohere2_moe import arch_from_config

    arch = arch_from_config(config)
    assert (arch["experts_held"], arch["heads_held"], arch["kv_heads_held"],
            arch["shared_columns_held"]) == ((0, 16), (0, 16), (0, 1), (0, 2048))
    assert (arch["num_experts"], arch["num_shared_experts"]) == (128, 4)


def test_per_layer_metrics_of_the_cell_have_readers(found):
    manifest = found[0]
    reported = {m["name"] for m in bench_run.metrics_for(
        manifest, "end_to_end", CELL, set())}
    assert reported == {"tune_step_ms", "setup_s"}
    by_name = {m["name"]: m for m in bench_run.metrics_for(
        manifest, "per_layer", CELL, reported)}
    assert set(NEW_METRICS) | set(APPENDED) == set(by_name)
    for n, layer in NEW_METRICS.items():
        assert by_name[n]["workloads"] == [CELL]
        assert by_name[n]["layer"] == layer
        assert by_name[n]["moves"] == "tune_step_ms"
        assert os.path.isfile(os.path.join(BENCH, "layer_metrics",
                                           n.rsplit(".", 1)[0] + ".py"))
    for n in APPENDED:  # appended at the end of the accepted lists
        assert by_name[n]["workloads"][-1] == CELL
    for n in by_name:
        assert hasattr(bench_run.find_reader(n), "read"), n
    # the new entries sit at the end of their lists
    assert [m["name"] for m in manifest["per_layer"]][-5:] == list(NEW_METRICS)
    assert manifest["workloads"][-1]["name"] == CELL
    assert manifest["configs"][-1]["name"] == "command-a-plus-s8-tune"


def test_operation_count_by_hand(found):
    """A sliding layer, the full layer, an expert layer and the whole step,
    from the shapes, by hand."""
    config = found[4]
    t, h = 32768, 4096
    ops = {o["site"]: o for o in flops_command.command_ops(config, t)}
    assert ops["layers_1.q_proj"]["fwd"] == 2 * t * h * 16 * 128
    assert ops["layers_1.k_proj"]["fwd"] == 2 * t * h * 128
    assert ops["layers_1.o_proj"]["fwd"] == 2 * t * 16 * 128 * h
    # a sliding layer at the pairs 0 <= t - s < 4096: the first 4096 queries
    # see s <= t, every later one 4096 keys
    band = 4096 * 4097 / 2 + (t - 4096) * 4096
    assert flops_command.banded_pairs(t, 4096) == band == 125831168
    assert ops["layers_1.attn.qk"]["fwd"] == 2 * 16 * 128 * band
    assert ops["layers_1.attn.pv"]["fwd"] == ops["layers_1.attn.qk"]["fwd"]
    # the full layer at the causal pairs
    assert ops["layers_3.attn.qk"]["fwd"] == 2 * 16 * 128 * t * (t + 1) / 2
    assert 0.234 < band / (t * (t + 1) / 2) < 0.235
    # routed experts at the uniform share of pairs: T * 8 * 16 / 128 rows
    assert ops["layers_2.experts.up_proj"]["fwd"] == 2 * (t * 8 / 8) * h * 4096
    # the shared experts at the 2048 columns held
    assert ops["layers_2.shared.down_proj"]["fwd"] == 2 * t * h * 2048
    assert ops["layers_2.router"]["fwd"] == 2 * t * h * 128
    assert ops["head"]["fwd"] == 2 * t * h * 32768
    # trainable leaves get a weight gradient; ONE norm feeds the whole
    # parallel block, so of layer 0 only the queries carry a gradient
    q0, q1 = ops["layers_0.q_proj"], ops["layers_1.q_proj"]
    assert q0["weight_grad"] and q0["act_operands_with_grad"] == 0
    assert q1["weight_grad"] and q1["act_operands_with_grad"] == 1
    assert not ops["layers_1.k_proj"]["weight_grad"]
    assert ops["layers_0.attn.qk"]["act_operands_with_grad"] == 1
    assert ops["layers_1.attn.qk"]["act_operands_with_grad"] == 2
    assert ops["layers_0.experts.up_proj"]["act_operands_with_grad"] == 0
    assert ops["layers_0.shared.up_proj"]["act_operands_with_grad"] == 0
    assert ops["layers_1.experts.up_proj"]["act_operands_with_grad"] == 1
    assert ops["layers_0.o_proj"]["act_operands_with_grad"] == 1
    fwd = flops.forward_flops(list(ops.values()))
    step = flops.tune_step_flops(list(ops.values()))
    # ISSUE 34's forward by kind, TFLOP: window 3 x 1.03, full 4.40, routed
    # 4 x 3.30, shared 4 x 1.65, projections 4 x 1.17, head 8.80
    tf = lambda *parts: sum(o["fwd"] for s, o in ops.items()  # noqa: E731
                            if any(p in s for p in parts)) / 1e12
    assert round(tf("layers_0.attn"), 2) == 1.03
    assert round(tf("layers_3.attn"), 2) == 4.40
    assert round(tf("layers_0.experts"), 2) == 3.30
    assert round(tf("layers_0.shared"), 2) == 1.65
    assert round(tf("layers_0.q_proj", "layers_0.k_proj", "layers_0.v_proj",
                    "layers_0.o_proj"), 2) == 1.17
    assert round(ops["head"]["fwd"] / 1e12, 2) == 8.80
    assert 40.8e12 < fwd < 41.0e12 and 84.5e12 < step < 85.2e12
    # the program's counter in place of the uniform share
    more = flops_command.command_ops(config, t, held_pair_share=0.15)
    assert (flops.forward_flops(more) - fwd == pytest.approx(
        4 * 3 * 2 * (t * 8 * 0.025) * h * 4096))


def test_roofline_count_by_hand(found):
    """The windowed pair's useful operations a step: three sliding layers x
    (2 forward + 4 backward products) x 2 x 16 heads x 128 x the banded
    pairs — the recompute of a layer's forward and the scores' recompute
    inside the backward kernel are not in it."""
    config = found[4]
    band = 125831168
    assert flops_command.window_attention_pair_flops(config, 32768) == (
        3 * 6 * 2 * 16 * 128 * band)
    assert flops_command.banded_pairs(8, 3) == 1 + 2 + 3 * 6
    assert flops_command.banded_pairs(8, 100) == flops_command.causal_pairs(8) == 36
    # against the tiles the pair walks at this shape (540 of 512 x 512): a
    # walked tile is 89 % band
    assert 0.88 < band / (540 * 512 * 512) < 0.89


def _ctx(metric, found, **over):
    _, _, cell, _, config = found
    ctx = {"metric": metric, "cell": cell, "config": config,
           "device": {"kind": "TPU v5 lite", "count": 1},
           "window": {"kind": "tune_command", "tokens": 32768, "batch": 1,
                      "traced_steps": 5,
                      "counters": {"held_pair_share": 0.125,
                                   "expert_load_max_over_mean": 1.07,
                                   "window_tile_share": 540 / 2080}},
           "trace": {"busy_s": 6.0, "window_s": 6.1,
                     "attention_kernel_s": {"lm.window_attention": 0.55},
                     "scope_s": {"lm.window_attention": 1.1,
                                 "lm.attention": 0.85, "lm.experts": 2.0,
                                 "lm.shared_expert": 0.5}}}
    ctx.update(over)
    return ctx


def test_readers_read_what_the_traced_run_hands_them(found):
    read = lambda m, **o: bench_run.find_reader(m).read(_ctx(m, found, **o))  # noqa: E731
    step = flops.tune_step_flops(flops_command.command_ops(found[4], 32768, 0.125))
    assert read("command_a_mfu.tune") == pytest.approx(
        100 * 5 * step / (6 * 197e12), rel=1e-9)
    assert 0 < read("command_a_mfu.tune") < 100
    assert read("window_attention_ms.tune") == pytest.approx(220.0)
    assert read("shared_experts_ms.tune") == pytest.approx(100.0)
    assert read("attention_ms.tune") == pytest.approx(170.0)
    assert read("experts_ms.tune") == pytest.approx(400.0)
    assert read("window_tile_share.tune") == 540 / 2080
    assert read("expert_load_max_over_mean.tune") == 1.07
    # 9.277 TFLOP a step at 197 TFLOP/s = 47.09 ms of the pair's 110
    assert read("window_attention_roofline.tune") == pytest.approx(
        100 * (5 * 3 * 6 * 2 * 16 * 128 * 125831168 / 197e12) / 0.55, rel=1e-9)
    assert 40 < read("window_attention_roofline.tune") < 45


@pytest.mark.parametrize("metric", NEW_METRICS)
def test_readers_return_none_where_there_is_nothing_to_read(found, metric):
    """A program without the scopes or the counters (the parent, another
    family's window), or an untraced run: the metric is left out, never 0."""
    read = bench_run.find_reader(metric).read
    other = {"window": {"kind": "tune_hybrid", "traced_steps": 5, "batch": 1,
                        "tokens": 32768,
                        "counters": {"held_pair_share": 0.25}},
             "trace": {"busy_s": 6.0, "window_s": 6.1,
                       "scope_s": {"lm.attention": 1.0}}}
    assert read(_ctx(metric, found, **other)) is None
    assert read(_ctx(metric, found, trace=None, window={
        "kind": "tune_command", "traced_steps": None})) is None


def test_kernel_seconds_sums_the_pair_under_its_scope(monkeypatch):
    """The roofline's denominator: self time of the attention kernels' own
    events by their innermost ``lm.`` scope — not the
    projections beside them, not the full layers' kernels, not the expert
    kernels."""
    driver = bench_run.load_module(os.path.join(BENCH, "drivers",
                                                "tune_command_steps.py"))
    lm_driver = bench_run.load_module(os.path.join(BENCH, "drivers",
                                                   "tune_lm_steps.py"))
    op = "jit(program)/while/body/train.loss/"
    events = [
        ("%fwd", 0, 100, {"text": op + "jvp(lm.window_attention)/"
                          "lm.window_attention/lm_selected_attention/pallas_call"}),
        ("%bwd", 100, 300, {"text": op + "transpose(jvp())/lm.window_attention/"
                            "lm.window_attention/lm_selected_attention_bwd/pallas_call"}),
        ("%dot", 400, 50, {"text": op + "jvp(lm.window_attention)/dot_general"}),
        ("%full", 450, 700, {"text": op + "jvp(lm.attention)/lm.attention/"
                             "lm_selected_attention/pallas_call"}),
        ("%experts", 1150, 900, {"text": op + "jvp(lm.experts)/"
                                 "lm_grouped_experts/pallas_call"}),
    ]
    import benchmark.drivers.tune_lm_steps as shared

    monkeypatch.setattr(shared, "device_events_with_scope_text",
                        lambda d: {"/device:TPU:0": events})
    assert driver.kernel_seconds("unused", 1) == {
        "lm.window_attention": pytest.approx(400e-9),
        "lm.attention": pytest.approx(700e-9)}
    assert lm_driver._SCOPE.findall(events[1][3]["text"]) == [
        "train.loss", "lm.window_attention", "lm.window_attention"]
    events.clear()
    assert driver.kernel_seconds("unused", 1) == {}


def test_choice_gaps_count_what_differs():
    from benchmark.reference.tune_command_check import choice_gaps

    experts = jnp.asarray([[0, 1], [2, 3], [4, 5], [6, 7]])
    same = [{"experts": experts, "routed_over_shared": 0.50},
            {"experts": experts, "routed_over_shared": 0.25}]
    assert choice_gaps(same, same, same) == {
        "expert_choice_diff": 0.0, "routed_share_gap": 0.0}
    other = [{"experts": experts.at[0, 0].set(5)[:, ::-1],
              "routed_over_shared": 0.40}, same[1]]
    g = choice_gaps(same, other, same)
    assert g["routed_share_gap"] == 0.0
    assert g["expert_choice_diff"] == pytest.approx(1 / 16)  # order is no difference
    assert choice_gaps(same, same, other)["routed_share_gap"] == pytest.approx(0.25)
    seven = [{**c, "experts": c["experts"][:, :1]} for c in same]
    assert choice_gaps(same, seven, same)["expert_choice_diff"] == 0.5


def test_tiny_arch_of_the_check_is_the_programs_tiny_preset():
    import dataclasses

    from videop2p_tpu.models.cohere2_moe import Cohere2MoeConfig

    from benchmark.reference.tune_command_check import TINY_ARCH

    cfg = Cohere2MoeConfig.tiny()
    tiny = dict(dataclasses.asdict(cfg), kv_heads_held=cfg.kv_heads_held)
    for k, v in TINY_ARCH.items():
        assert (tuple(v) == tuple(tiny[k]) if isinstance(v, tuple)
                else v == tiny[k]), k


def test_levelled_router_rows_spread_the_document_over_the_experts():
    """``level_router_rows`` gives each layer a router kernel on the document
    with the plain reference's float32 layers — nothing of the program —:
    the rows made orthogonal to the stretches' means of the normed input,
    then the hybrid cell's ``fit_rows`` as it is, which stops once the
    busiest held expert sees at most ``LEVEL_AT`` times the held mean: the
    load over the mean falls to about that as the PROGRAM then counts it,
    the same call gives the same kernels, and ``with_router_kernels`` puts
    them into a set of weights (every other leaf as drawn)."""
    from videop2p_tpu.models import cohere2_moe as cm

    from benchmark.harness import weights_lm
    from benchmark.harness.weights import flatten_named
    from benchmark.reference.tune_command_check import TINY_ARCH

    cfg = cm.Cohere2MoeConfig.tiny()
    ids = jnp.asarray(weights_lm.document(3, 256, cfg.vocab_size))
    real_init = cm.init_params
    try:
        weights_command.steer_init()
        drawn = weights_command.regenerate(7, cfg)
        rows = weights_command.level_router_rows(flatten_named(drawn),
                                                 TINY_ARCH, ids)
        assert sorted(rows) == [f"layers_{i}" for i in range(4)]
        assert all(isinstance(r, np.ndarray) and r.shape == (64, 8)
                   for r in rows.values())
        again = weights_command.level_router_rows(flatten_named(drawn),
                                                  TINY_ARCH, ids)
        assert all(np.array_equal(rows[k], again[k]) for k in rows)
        levelled = weights_command.regenerate(7, rows=rows)
    finally:
        cm.init_params = real_init
    a, b = flatten_named(drawn), flatten_named(levelled)
    moved = sorted(k for k in a if not bool(jnp.array_equal(a[k], b[k])))
    assert moved == [f"params/layers_{i}/router/kernel" for i in range(4)]
    assert np.array_equal(np.asarray(b[moved[0]], np.float32),
                          np.asarray(rows["layers_0"], np.float32))
    load = lambda p: float(jax.jit(lambda p: cm.forward_loss(  # noqa: E731
        p, cfg, ids)[1]["expert_load_max_over_mean"])(p["params"]))
    assert 1.0 < load(levelled) <= weights_hybrid.LEVEL_AT + 0.03 < load(drawn)


def test_centred_rows_do_not_route_on_what_a_stretch_shares():
    """Tokens that share a large component stretch by stretch (what a
    low-pass attention leaves of a random model's residual stream): as drawn
    a few experts take most tokens and some none; with the rows made
    orthogonal to the stretches' means the loads are those of independent
    tokens, and the hybrid cell's ``fit_rows`` then reaches its rule as it
    is: the busiest held expert at most ``LEVEL_AT`` times the held mean
    (one-sided: an expert under the mean stays there), the held share
    pinned."""
    from benchmark.reference import cohere2_moe as ref

    nx, k, n, h = ref._Nx("float32"), 4, 32, 256
    ks = jax.random.split(jax.random.key(11), 4)
    # what every token shares, and what the tokens of one stretch share
    shared = (2.0 * jax.random.normal(ks[3], (1, h))
              + jnp.repeat(jax.random.normal(ks[0], (32, h)), 128, axis=0))
    u = shared + jax.random.normal(ks[1], (4096, h))
    kernel = (jax.random.normal(ks[2], (h, n)) / h ** 0.5).astype(jnp.bfloat16)

    def loads(kernel):
        chosen = jax.lax.top_k(nx.mm(u, kernel), k)[1]
        return np.bincount(np.asarray(chosen).ravel(), minlength=n)

    drawn = loads(kernel)
    centred = weights_command.centred(u, kernel)
    level = loads(centred)
    assert drawn.max() > 2.0 * drawn.mean() and level.max() < 1.3 * level.mean()
    means = jnp.mean(u.reshape(32, 128, h), axis=1)
    assert float(jnp.max(jnp.abs(means @ centred.astype(jnp.float32)))) < 0.05
    assert weights_command.fit_rows is weights_hybrid.fit_rows
    scale = jax.jit(lambda u: weights_hybrid.fit_rows(nx, u, centred, k, (0, 8)))(u)
    fit = loads(weights_hybrid._scaled(centred, scale))[:8]
    assert fit.max() <= weights_hybrid.LEVEL_AT * fit.mean()
    assert abs(fit.sum() / (4096 * k) / 0.25 - 1) <= weights_hybrid.SHARE_TOL


# ------------------------------------------------ the cell, at rehearsal size


@pytest.fixture(scope="module")
def rehearsal():
    """ONE process: the cell's own driver at rehearsal size (the tiny
    preset, 64 tokens, ``run_tuning.main``), then the check once sound,
    once under the float8 control and once under every planted fault."""
    from benchmark.reference.cohere2_moe import FAULTS

    hows = ",".join(("sound", "float8_e4m3fn") + FAULTS)
    p = subprocess.run(
        [sys.executable, os.path.join(HERE, "read_limits_command.py"),
         "--workload", CELL, "--seed", str(2 ** 31 + 34), "--seconds", "0.3",
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
    assert set(prog["counters"]) == {
        "expert_load_max_over_mean", "held_pair_share", "routed_over_shared",
        "window_tile_share"}
    sound = rehearsal["sound"]
    assert sound["correct_under_committed_limits"], sound["compared"]


@pytest.mark.parametrize("how,window,low,high", [
    ("sound", 8, 0.0, 0.01),
    ("reference_one_key_short", 8, 0.3, 1.5),
    ("reference_without_a_window", 8, 1.0, 10.0),
    ("program_one_key_short", 7, 1.0, 3.0),
    ("program_one_key_long", 9, 1.0, 3.0),
])
def test_the_probe_reads_the_windows_edge_in_units_of_one_key(how, window,
                                                              low, high):
    """``window_edge_gap``: on the probe (token t the unit vector of its
    class t mod window, no query projection, +-1 value and output
    projections whose classes sum to 0) a window of exactly
    ``sliding_window`` keys reads 0 past the first window, and one key too
    few or too many — on the reference's side or the program's — reads
    that key's part, the unit."""
    from videop2p_tpu.models.cohere2_moe import Cohere2MoeConfig

    from benchmark.reference import tune_command_check as tc

    u, leaves = tc.edge_probe(tc.TINY_ARCH, 64)
    assert (u.sum(-1) == 1).all() and (u.argmax(-1) == np.arange(64) % 8).all()
    assert not leaves["q_proj/kernel"].any()
    assert (leaves["v_proj/kernel"][:8].sum(0) == 0).all()
    assert (leaves["o_proj/kernel"][:, :8].sum(1) == 0).all()
    fault = {"reference_one_key_short": "window_4095",
             "reference_without_a_window": "no_window"}.get(how)
    gap = tc.window_edge_gap(Cohere2MoeConfig.tiny(sliding_window=window),
                             tc.TINY_ARCH, 64, fault=fault)
    assert low <= gap < high, gap


# (fault, a number it moves at rehearsal size)
FAULT_MOVES = [
    ("float8_e4m3fn", "mu_gap_worst"), ("no_window", "change_diff_worst"),
    ("window_4095", "change_diff_worst"), ("window_4095", "window_edge_gap"),
    ("no_window", "window_edge_gap"),
    ("rope_on_full_layers", "change_diff_worst"),
    ("rope_halves_not_pairs", "change_diff_worst"),
    ("sequential_block", "change_diff_worst"),
    ("rms_norm", "change_diff_worst"),
    ("shared_sum_not_mean", "routed_share_gap"),
    ("gates_not_normalised", "routed_share_gap"), ("top7", "routed_share_gap")]


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
