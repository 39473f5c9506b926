"""Keye-VL-2.0's cell's own pieces on the CPU: the file states the cut, the
CLI config states the same model, the operation count and the selection
pair's roofline count hand-checked against the configuration's shapes, the
readers on what a traced run hands them (and on a program that has none of
it: None, never 0), the kernel-time reducer, the levelling pass, and a
``--rehearse`` run of the cell with each planted fault at rehearsal size."""

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

from benchmark.harness import flops, flops_keye, weights_hybrid, weights_keye  # noqa: E402

CELL = "keye-vl2-30b-a3b-s1-tune.doc16k-steps"
LAYER = "model step (models/keye_vl2.py)"
KERNELS = ("kernels (ops/attention.py, ops/groupnorm.py, "
           "ops/selected_attention.py, ops/grouped_experts.py)")
NEW_METRICS = {"keye_mfu.tune": LAYER, "attn_proj_ms.tune": LAYER,
               "selected_attention_roofline.tune": KERNELS}
APPENDED = ("device_idle.tune", "device_peak_gib.tune", "setup_models_s.tune",
            "setup_trace_lower_s.tune", "setup_load_s.tune",
            "setup_analysis_s.tune", "setup_before_program_s.tune",
            "setup_import_s.tune", "setup_tensorboard_s.tune",
            "setup_document_s.tune", "host_between_calls_ms.tune",
            "indexer_ms.tune", "sparse_attention_ms.tune", "experts_ms.tune",
            "expert_load_max_over_mean.tune")
T = 16384
# the selected (t, s) pairs at 16384 tokens and 2048 keys a query
SELECTED = 2048 * 2049 / 2 + (T - 2048) * 2048


@pytest.fixture(scope="module")
def found():
    manifest = bench_run.load_json(os.path.join(ROOT, "BENCHMARK.json"))
    return (manifest,) + bench_run.find_cell(manifest, CELL)


def test_cell_is_found_by_name_and_states_the_cut(found):
    manifest, entry, cell, cfg_entry, config = found
    assert entry["chips"] == 1 and cell["driver"] == "tune_keye_steps"
    assert os.path.isfile(os.path.join(BENCH, "drivers", cell["driver"] + ".py"))
    assert cell["why"] == entry["why"] and len(entry["why"]) <= 200
    assert len(cfg_entry["why"]) <= 200
    assert entry["traffic"] == cell["traffic"] == "doc16k-steps"
    assert config["geometry"] == {"tokens": 16384, "batch": 1}
    assert cell["document_seed"] == 16384
    assert cell["cli_overrides"]["steps_per_call"] == 5
    assert [w["name"] for w in manifest["workloads"]][-1] == CELL
    # every key listed as reduced differs from the published value, and the
    # file states the published one beside it
    dep = config["deployment"]
    assert sorted(cfg_entry["reduced"]) == sorted(config["reduced"]) == [
        "num_hidden_layers", "vocab_size"]
    for key in cfg_entry["reduced"]:
        assert config[key] != dep[key + "_published"], key
    assert (dep["chips_a_layer"], dep["pipeline_stages"], dep["layers_a_stage"],
            dep["vocabulary_shards"], dep["chips"]) == (1, 8, 6, 8, 8)
    assert 8 * config["vocab_size"] == dep["vocab_size_published"] == 151936
    assert dep["num_hidden_layers_published"] == 48 == 8 * dep["layers_a_stage"]
    assert config["num_hidden_layers"] == 4 <= dep["layers_a_stage"]
    assert dep["experts_held"] == [0, 128] == [0, dep["num_experts_published"]]
    # no width is cut
    widths = dict(hidden_size=2048, head_dim=128, moe_intermediate_size=768,
                  num_attention_heads=32, num_key_value_heads=4,
                  num_experts=128, num_experts_per_tok=8, rope_theta=10000000,
                  rms_norm_eps=1e-6)
    assert {k: config[k] for k in widths} == widths
    assert config["sa_config"] == {
        "indexer_head_dim": 64, "indexer_num_heads": 16,
        "indexer_num_kv_heads": 1, "kv_chunk_size": 512, "q_chunk_size": 512,
        "topk": 2048}
    assert config["rope_scaling"]["mrope_section"] == [16, 24, 24]
    assert set(config["assumed"]) >= {"qk_norm", "indexer_query", "indexer_key",
                                      "indexer_rotary", "chunk_sizes", "ties",
                                      "router_rows"}
    assert "vision_tower" in config["departures"]


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
                   if r["name"] == "Keye-VL-2.0-30B-A3B")
    assert cfg_entry["source"] == config["source"] == row["source_url"]
    for key, value in row["config"].items():
        if key in cfg_entry["reduced"]:
            assert config["deployment"][key + "_published"] == value, key
        else:
            assert config[key] == value, key


def test_the_cli_config_states_the_same_model(found):
    from videop2p_tpu.cli.common import MODEL_FAMILIES, load_config
    from videop2p_tpu.models.keye_vl2 import KeyeVL2Config

    _, _, cell, _, config = found
    driver = bench_run.load_module(os.path.join(BENCH, "drivers",
                                                "tune_keye_steps.py"))
    model = driver.model_from_config(config)
    cli = load_config(os.path.join(ROOT, cell["cli_config"]))
    assert cli["model_family"] == "keye_vl2" in MODEL_FAMILIES
    assert config["model_type"] == "KeyeVL2"
    # the one thing the cell adds: the loss hands out what every layer chose
    assert model.pop("hand_out_choices") is True
    assert cli["model"] == model
    assert list(cli["trainable_modules"]) == config["training"]["trainable_modules"]
    assert cli["train_data"]["n_tokens"] == config["geometry"]["tokens"]
    built = KeyeVL2Config.from_dict(model)
    built.check()
    assert (built.num_hidden_layers, built.vocab_size, built.index_topk) == (
        4, 18992, 2048)
    # the reference is told the same share and the same scorer
    from benchmark.reference.keye_vl2 import arch_from_config

    arch = arch_from_config(config)
    assert arch["experts_held"] == (0, 128)
    assert (arch["indexer_num_heads"], arch["indexer_head_dim"], arch["topk"],
            arch["mrope_section"]) == (16, 64, 2048, (16, 24, 24))


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
    tune_step = next(m for m in manifest["end_to_end"]
                     if m["name"] == "tune_step_ms")
    assert tune_step["workloads"][-1] == CELL
    # the new entries sit at the end of their lists
    assert [m["name"] for m in manifest["per_layer"]][-3:] == list(NEW_METRICS)
    assert manifest["configs"][-1]["name"] == "keye-vl2-30b-a3b-s1-tune"


def test_operation_count_by_hand(found):
    """A layer and the whole step, from the shapes, by hand: the
    forward by kind, TFLOP — the selected attention 0.52 a layer, the
    scorer's logits 0.27, the experts 1.24, the projections 0.62, the head
    1.27."""
    config = found[4]
    h = 2048
    ops = {o["site"]: o for o in flops_keye.keye_ops(config, T)}
    assert ops["layers_1.q_proj"]["fwd"] == 2 * T * h * 32 * 128
    assert ops["layers_1.k_proj"]["fwd"] == 2 * T * h * 4 * 128
    assert ops["layers_1.o_proj"]["fwd"] == 2 * T * 32 * 128 * h
    assert flops_keye._pairs(config, T) == SELECTED == 31_458_304
    assert SELECTED / T == pytest.approx(1920.0625)
    assert ops["layers_1.attn.qk"]["fwd"] == 2 * 32 * 128 * SELECTED
    assert ops["layers_1.attn.pv"]["fwd"] == ops["layers_1.attn.qk"]["fwd"]
    assert ops["layers_1.indexer.scores"]["fwd"] == 2 * 16 * 64 * T * (T + 1) / 2
    # every expert is held: T * 8 rows, 1024 tokens an expert
    assert ops["layers_2.experts.up_proj"]["fwd"] == 2 * (T * 8) * h * 768
    assert T * 8 / 128 == 1024
    assert ops["layers_2.router"]["fwd"] == 2 * T * h * 128
    assert ops["head"]["fwd"] == 2 * T * h * 18992
    # trainable leaves get a weight gradient; layer 0's input is the frozen
    # embedding, but its expert layer reads x after the attention
    q0, q1 = ops["layers_0.q_proj"], ops["layers_1.q_proj"]
    assert q0["weight_grad"] and q0["act_operands_with_grad"] == 0
    assert q1["weight_grad"] and q1["act_operands_with_grad"] == 1
    assert not ops["layers_1.k_proj"]["weight_grad"]
    assert ops["layers_0.k_proj"]["act_operands_with_grad"] == 0
    assert ops["layers_0.attn.qk"]["act_operands_with_grad"] == 1
    assert ops["layers_1.attn.qk"]["act_operands_with_grad"] == 2
    assert ops["layers_0.experts.up_proj"]["act_operands_with_grad"] == 1
    assert ops["layers_1.indexer.scores"]["act_operands_with_grad"] == 0
    tf = lambda *parts: sum(o["fwd"] for s, o in ops.items()  # noqa: E731
                            if any(p in s for p in parts)) / 1e12
    assert round(tf("layers_0.attn"), 2) == 0.52
    assert round(tf("layers_0.indexer.scores"), 2) == 0.27
    assert round(tf("layers_0.experts"), 2) == 1.24
    assert round(tf("layers_0.q_proj", "layers_0.k_proj", "layers_0.v_proj",
                    "layers_0.o_proj"), 2) == 0.62
    assert round(ops["head"]["fwd"] / 1e12, 2) == 1.27
    fwd = flops.forward_flops(list(ops.values()))
    step = flops.tune_step_flops(list(ops.values()))
    assert 12.1e12 < fwd < 12.3e12 and 25.1e12 < step < 25.5e12
    # the program's counters in place of the shapes' counts
    more = flops_keye.keye_ops(config, T, keys_selected_mean=2000.0)
    assert (flops.forward_flops(more) - fwd == pytest.approx(
        4 * 2 * 2 * 32 * 128 * (2000.0 * T - SELECTED)))


def test_roofline_count_by_hand(found):
    """The selection pair's useful operations a step: four layers x (2
    forward + 4 backward products) x 2 x 32 heads x 128 x the selected
    pairs — 6.18 TFLOP, 31.4 ms at the v5e's peak; the pair walks every
    causal tile, 528 of 512 x 512, 4.4 x the selected pairs."""
    config = found[4]
    work = flops_keye.selected_attention_pair_flops(config, T)
    assert work == 4 * 6 * 2 * 32 * 128 * SELECTED
    assert round(work / 1e12, 2) == 6.18
    assert round(1e3 * work / 197e12, 1) == 31.4
    assert 4.39 < 528 * 512 * 512 / SELECTED < 4.41
    assert flops_keye.selected_attention_pair_flops(
        config, T, keys_selected_mean=1920.0625) == pytest.approx(work)


def _ctx(metric, found, **over):
    _, _, cell, _, config = found
    ctx = {"metric": metric, "cell": cell, "config": config,
           "device": {"kind": "TPU v5 lite", "count": 1},
           "window": {"kind": "tune_keye", "tokens": T, "batch": 1,
                      "traced_steps": 5,
                      "counters": {"held_pair_share": 1.0,
                                   "expert_load_max_over_mean": 1.08,
                                   "keys_selected_mean": SELECTED / T}},
           "trace": {"busy_s": 4.0, "window_s": 4.1,
                     "attention_kernel_s": {"lm.sparse_attention": 1.5},
                     "scope_s": {"lm.attn_proj": 0.3, "lm.indexer": 0.4,
                                 "lm.sparse_attention": 1.6, "lm.experts": 1.0}}}
    ctx.update(over)
    return ctx


def test_readers_read_what_the_traced_run_hands_them(found):
    read = lambda m, **o: bench_run.find_reader(m).read(_ctx(m, found, **o))  # noqa: E731
    step = flops.tune_step_flops(flops_keye.keye_ops(found[4], T, 1.0,
                                                     SELECTED / T))
    assert read("keye_mfu.tune") == pytest.approx(
        100 * 5 * step / (4 * 197e12), rel=1e-9)
    assert 0 < read("keye_mfu.tune") < 100
    assert read("attn_proj_ms.tune") == pytest.approx(60.0)
    assert read("indexer_ms.tune") == pytest.approx(80.0)
    assert read("sparse_attention_ms.tune") == pytest.approx(320.0)
    assert read("experts_ms.tune") == pytest.approx(200.0)
    assert read("expert_load_max_over_mean.tune") == 1.08
    # 6.185 TFLOP a step at 197 TFLOP/s = 31.4 ms of the pair's 300
    assert read("selected_attention_roofline.tune") == pytest.approx(
        100 * (5 * 4 * 6 * 2 * 32 * 128 * SELECTED / 197e12) / 1.5, rel=1e-9)
    assert 10 < read("selected_attention_roofline.tune") < 11


@pytest.mark.parametrize("metric", NEW_METRICS)
def test_readers_return_none_where_there_is_nothing_to_read(found, metric):
    """A program without the scopes or the counters (the parent, another
    family's window), or an untraced run: the metric is left out, never 0."""
    read = bench_run.find_reader(metric).read
    other = {"window": {"kind": "tune_lm", "traced_steps": 5, "batch": 1,
                        "tokens": T, "counters": {"held_pair_share": 0.0625}},
             "trace": {"busy_s": 6.0, "window_s": 6.1,
                       "scope_s": {"lm.mla_proj": 1.0}}}
    assert read(_ctx(metric, found, **other)) is None
    assert read(_ctx(metric, found, trace=None, window={
        "kind": "tune_keye", "traced_steps": None})) is None


def test_kernel_seconds_sums_the_pair_under_its_scope(monkeypatch):
    """The roofline's denominator: self time of the attention kernels' own
    events under ``lm.sparse_attention`` — not the projections beside them,
    not the scorer, not the expert kernels."""
    driver = bench_run.load_module(os.path.join(BENCH, "drivers",
                                                "tune_command_steps.py"))
    op = "jit(program)/while/body/train.loss/"
    events = [
        ("%fwd", 0, 100, {"text": op + "jvp(lm.sparse_attention)/"
                          "lm.sparse_attention/lm_selected_attention/pallas_call"}),
        ("%bwd", 100, 300, {"text": op + "transpose(jvp())/lm.sparse_attention/"
                            "lm.sparse_attention/lm_selected_attention_bwd/pallas_call"}),
        ("%dot", 400, 50, {"text": op + "jvp(lm.attn_proj)/dot_general"}),
        ("%sort", 450, 70, {"text": op + "jvp(lm.select)/reduce"}),
        ("%experts", 520, 900, {"text": op + "jvp(lm.experts)/"
                                "lm_grouped_experts/pallas_call"}),
    ]
    import benchmark.drivers.tune_lm_steps as shared

    monkeypatch.setattr(shared, "device_events_with_scope_text",
                        lambda d: {"/device:TPU:0": events})
    assert driver.kernel_seconds("unused", 1) == {
        "lm.sparse_attention": pytest.approx(400e-9)}


def test_choice_gaps_count_what_differs():
    from benchmark.reference.tune_keye_check import choice_gaps

    experts = jnp.asarray([[0, 1], [2, 3], [4, 5], [6, 7]])
    mask = jnp.packbits(jnp.tril(jnp.ones((8, 8), bool)), axis=-1)
    same = [{"mask": mask, "experts": experts}] * 2
    assert choice_gaps(same, same) == {"selected_key_diff": 0.0,
                                       "expert_choice_diff": 0.0}
    fewer = jnp.packbits(jnp.eye(8, dtype=bool), axis=-1)
    other = [{"mask": fewer, "experts": experts.at[0, 0].set(5)[:, ::-1]},
             same[1]]
    g = choice_gaps(same, other)
    assert g["selected_key_diff"] == pytest.approx((36 - 8) / 72)
    assert g["expert_choice_diff"] == pytest.approx(1 / 16)  # order is no difference
    assert choice_gaps(other, same)["selected_key_diff"] == 0.0


def test_tiny_arch_of_the_check_is_the_programs_tiny_preset():
    from videop2p_tpu.models.keye_vl2 import KeyeVL2Config

    from benchmark.reference.tune_keye_check import TINY_ARCH

    cfg = KeyeVL2Config.tiny()
    names = {"topk": "index_topk"}
    for k, v in TINY_ARCH.items():
        mine = getattr(cfg, names.get(k, k))
        assert (tuple(v) == tuple(mine) if isinstance(v, tuple) else v == mine), k


def test_levelled_router_rows_spread_the_document_over_the_experts():
    """``level_router_rows`` gives each layer a router kernel on the document
    with the plain reference's float32 layers — nothing of the program —:
    the hybrid cell's ``fit_rows`` as it is (from the rows as drawn, or from
    the rows centred first where those do not level), which stops once the
    busiest expert sees at most ``LEVEL_AT`` times the mean: the load over
    the mean falls to about that as the PROGRAM then counts it, the same call
    gives the same kernels, and ``with_router_kernels`` puts them into a set
    of weights (every other leaf as drawn)."""
    from videop2p_tpu.models import deepseek as ds
    from videop2p_tpu.models import keye_vl2 as ky

    from benchmark.harness import weights_lm
    from benchmark.harness.weights import flatten_named
    from benchmark.reference.tune_keye_check import TINY_ARCH

    cfg = ky.KeyeVL2Config.tiny()
    ids = jnp.asarray(weights_lm.document(3, 256, cfg.vocab_size))
    real_init = ky.init_params
    try:
        weights_keye.steer_init()
        drawn = weights_keye.regenerate(7, cfg)
        centred = []
        rows = weights_keye.level_router_rows(flatten_named(drawn), TINY_ARCH,
                                              ids, centred_layers=centred)
        assert sorted(rows) == ["layers_0", "layers_1"]
        assert set(centred) <= set(rows)
        assert all(isinstance(r, np.ndarray) and r.shape == (64, 8)
                   for r in rows.values())
        again = weights_keye.level_router_rows(flatten_named(drawn), TINY_ARCH,
                                               ids)
        assert all(np.array_equal(rows[k], again[k]) for k in rows)
        levelled = weights_keye.regenerate(7, rows=rows)
    finally:
        ky.init_params = real_init
    a, b = flatten_named(drawn), flatten_named(levelled)
    moved = sorted(k for k in a if not bool(jnp.array_equal(a[k], b[k])))
    assert moved == [f"params/layers_{i}/router/kernel" for i in range(2)]
    assert np.array_equal(np.asarray(b[moved[0]], np.float32),
                          np.asarray(rows["layers_0"], np.float32))
    saved = ds.EXPERT_BLOCK, ds.Q_CHUNK
    ds.EXPERT_BLOCK, ds.Q_CHUNK = 8, 64
    try:
        load = lambda p: float(jax.jit(lambda p: ky.forward_loss(  # noqa: E731
            p, cfg, ids)[1]["expert_load_max_over_mean"])(p["params"]))
        assert 1.0 < load(levelled) <= weights_hybrid.LEVEL_AT + 0.05 < load(drawn)
    finally:
        ds.EXPERT_BLOCK, ds.Q_CHUNK = saved


def test_level_kernel_centres_only_where_the_rows_alone_do_not_level():
    """Tokens that share a large component stretch by stretch: as drawn a
    few experts take most tokens and rescaling cannot lift those under the
    offset, so the rows are centred first; independent tokens are levelled
    from the rows as drawn."""
    from benchmark.reference import keye_vl2 as ref

    nx, k, n, h = ref._Nx("float32"), 4, 32, 256
    ks = jax.random.split(jax.random.key(11), 4)
    kernel = (jax.random.normal(ks[2], (h, n)) / h ** 0.5).astype(jnp.bfloat16)
    plain = jax.random.normal(ks[1], (4096, h))
    stretches = (2.0 * jax.random.normal(ks[3], (1, h))
                 + jnp.repeat(jax.random.normal(ks[0], (32, h)), 128, axis=0))
    fit = jax.jit(lambda u: weights_keye.level_kernel(nx, u, kernel, k, (0, n)))
    for u, want in ((plain, False), (plain + stretches, True)):
        levelled, was_centred = fit(u)
        assert bool(was_centred) is want
        chosen = jax.lax.top_k(nx.mm(u, levelled), k)[1]
        load = np.bincount(np.asarray(chosen).ravel(), minlength=n)
        assert load.max() <= weights_hybrid.LEVEL_AT * load.mean() + 1e-9


# ------------------------------------------------ the cell, at rehearsal size


@pytest.fixture(scope="module")
def rehearsal():
    """ONE process: the cell's own driver at rehearsal size (the tiny
    preset, 64 tokens, ``run_tuning.main``), then the check once sound,
    once under the float8 control and once under every planted fault."""
    from benchmark.reference.keye_vl2 import FAULTS

    hows = ",".join(("sound", "float8_e4m3fn") + FAULTS)
    p = subprocess.run(
        [sys.executable, os.path.join(HERE, "read_limits_keye.py"),
         "--workload", CELL, "--seed", str(2 ** 31 + 38), "--seconds", "0.3",
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
        "expert_load_max_over_mean", "held_pair_share", "keys_selected_mean"}
    sound = rehearsal["sound"]
    assert sound["correct_under_committed_limits"], sound["compared"]


# (fault, a number it moves at rehearsal size)
FAULT_MOVES = [
    ("float8_e4m3fn", "mu_gap_worst"), ("no_qk_norm", "change_diff_worst"),
    ("kv_head_mod_not_div", "change_diff_worst"),
    ("dense_not_selected", "change_diff_worst"),
    ("topk_1024", "selected_key_diff"),
    ("gates_not_normalised", "change_diff_worst"),
    ("top7", "expert_choice_diff"), ("top7", "change_diff_worst"),
    ("rope_pairs_not_halves", "change_diff_worst"),
    ("rope_theta_1e6", "change_diff_worst"),
    ("indexer_no_relu", "selected_key_diff")]


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
