"""The hybrid token-model cell's own pieces on the CPU: the file states the
cut, the CLI config states the same model, the operation count hand-checked
against the configuration's shapes, the readers on what a traced run hands
them (and on a program that has none of it: None, never 0), the seeded
per-head leaves, the choice comparison, and a ``--rehearse`` run of the cell
with each planted fault at rehearsal size."""

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

from benchmark.harness import flops, flops_hybrid, weights_hybrid  # noqa: E402

CELL = "granite-4.0-h-small-s4-tune.doc32k-steps"
NEW_METRICS = ("granite_mfu.tune", "ssd_scan_ms.tune", "mamba_proj_ms.tune",
               "attention_ms.tune", "ssd_state_rms.tune")


@pytest.fixture(scope="module")
def found():
    manifest = bench_run.load_json(os.path.join(ROOT, "BENCHMARK.json"))
    return (manifest,) + bench_run.find_cell(manifest, CELL)


def test_cell_is_found_by_name_and_states_the_cut(found):
    manifest, entry, cell, cfg_entry, config = found
    assert entry["chips"] == 1 and cell["driver"] == "tune_hybrid_steps"
    assert os.path.isfile(os.path.join(BENCH, "drivers", cell["driver"] + ".py"))
    assert cell["why"] == entry["why"] and len(entry["why"]) <= 200
    assert entry["traffic"] == cell["traffic"] == "doc32k-steps"
    assert config["geometry"] == {"tokens": 32768, "batch": 1}
    assert cell["document_seed"] == 32768
    assert cell["cli_overrides"]["steps_per_call"] == 5
    # every key listed as reduced differs from the published value, and the
    # file states the published one beside it
    dep = config["deployment"]
    assert sorted(cfg_entry["reduced"]) == sorted(config["reduced"])
    for key in cfg_entry["reduced"]:
        assert config[key] != dep[key + "_published"], key
    assert dep["chips_sharing_a_layer"] == 4 and dep["pipeline_stages"] == 4
    for key in ("num_local_experts", "num_attention_heads",
                "num_key_value_heads", "mamba_n_heads", "vocab_size",
                "num_hidden_layers"):
        assert 4 * config[key] == dep[key + "_published"], key
    assert config["layer_types"] == dep["layer_types_published"][:10]
    assert dep["layer_types_published"] == config["layer_types"] * 4
    # guide section 4's floors: a whole period, >= 8 experts, >= 1/8 vocabulary
    assert config["layer_types"].count("attention") == 1
    assert config["num_local_experts"] >= 8
    # no width is cut
    widths = dict(hidden_size=4096, intermediate_size=768,
                  shared_intermediate_size=1536, mamba_d_head=64,
                  mamba_d_state=128, mamba_d_conv=4, mamba_expand=2,
                  mamba_chunk_size=256, mamba_n_groups=1,
                  num_experts_per_tok=10, attention_multiplier=0.0078125,
                  embedding_multiplier=12, residual_multiplier=0.22,
                  logits_scaling=16)
    assert {k: config[k] for k in widths} == widths


def test_configuration_holds_every_catalog_number_or_lists_it_reduced(found):
    """Against the catalog row where the guides are installed; the row's
    numbers are also pinned above, so a sandbox without it loses nothing."""
    catalog = "/opt/skills/guides/model-configs/architectures.jsonl"
    if not os.path.isfile(catalog):
        pytest.skip("no catalog here")
    _, _, _, cfg_entry, config = found
    with open(catalog) as f:
        row = next(r for r in map(json.loads, f)
                   if r["name"] == "granite-4.0-h-small")
    assert cfg_entry["source"] == config["source"] == row["source_url"]
    for key, value in row["config"].items():
        if key in cfg_entry["reduced"]:
            assert config["deployment"][key + "_published"] == value, key
        else:
            assert config[key] == value, key


def test_the_cli_config_states_the_same_model(found):
    from videop2p_tpu.cli.common import MODEL_FAMILIES, load_config
    from videop2p_tpu.models.granite_hybrid import GraniteHybridConfig

    _, _, cell, _, config = found
    driver = bench_run.load_module(os.path.join(BENCH, "drivers",
                                                "tune_hybrid_steps.py"))
    model = driver.model_from_config(config)
    cli = load_config(os.path.join(ROOT, cell["cli_config"]))
    assert cli["model_family"] == config["model_type"] in MODEL_FAMILIES
    # the one thing the cell adds: the loss hands out what every layer chose
    assert model.pop("hand_out_choices") is True
    assert cli["model"] == model
    assert list(cli["trainable_modules"]) == config["training"]["trainable_modules"]
    assert cli["train_data"]["n_tokens"] == config["geometry"]["tokens"]
    built = GraniteHybridConfig.from_dict(model)
    built.check()
    assert built.kv_heads_held == tuple(config["deployment"]["kv_heads_held"])
    assert built.head_dim == 128


def test_per_layer_metrics_of_the_cell_have_readers(found):
    manifest = found[0]
    reported = {m["name"] for m in bench_run.metrics_for(
        manifest, "end_to_end", CELL, set())}
    assert reported == {"tune_step_ms", "setup_s"}
    by_name = {m["name"]: m for m in bench_run.metrics_for(
        manifest, "per_layer", CELL, reported)}
    assert set(NEW_METRICS) | {
        "experts_ms.tune", "expert_load_max_over_mean.tune",
        "setup_document_s.tune", "device_idle.tune", "device_peak_gib.tune",
        "host_between_calls_ms.tune", "setup_models_s.tune",
        "setup_trace_lower_s.tune", "setup_load_s.tune",
        "setup_analysis_s.tune"} == set(by_name)
    for n in NEW_METRICS:
        assert by_name[n]["workloads"] == [CELL]
        assert by_name[n]["layer"] == "model step (models/granite_hybrid.py)"
        assert by_name[n]["moves"] == "tune_step_ms"
    for n in by_name:
        assert hasattr(bench_run.find_reader(n), "read"), n


def test_operation_count_by_hand(found):
    """One Mamba layer, the attention layer, an expert layer and the whole
    step, from the shapes, by hand."""
    config = found[4]
    t, h = 32768, 4096
    ops = {o["site"]: o for o in flops_hybrid.hybrid_ops(config, t)}
    d = 32 * 64                                    # inner channels held
    assert ops["layers_1.in_proj"]["fwd"] == 2 * t * h * (2 * d + 128 + 32)
    assert ops["layers_1.in_proj_c"]["fwd"] == 2 * t * h * 128
    assert ops["layers_1.conv"]["fwd"] == 2 * t * 4 * (d + 256)
    assert ops["layers_1.out_proj"]["fwd"] == 2 * t * d * h
    # the scan's four products a chunk of 256: the two inside the chunk at
    # its causal pairs (C B^T once for all heads), the chunk's state, the
    # carried state's contribution
    pairs = (t // 256) * 256 * 257 / 2
    assert ops["layers_1.ssd.cb"]["fwd"] == 2 * 128 * pairs
    assert ops["layers_1.ssd.inside"]["fwd"] == 2 * 32 * 64 * pairs
    assert ops["layers_1.ssd.chunk_state"]["fwd"] == 2 * t * 32 * 64 * 128
    assert ops["layers_1.ssd.carried"]["fwd"] == 2 * t * 32 * 64 * 128
    # attention at the causal pairs, 8 query heads of 128, no selection
    assert ops["layers_5.attn.qk"]["fwd"] == 2 * 8 * 128 * t * (t + 1) / 2
    assert ops["layers_5.attn.pv"]["fwd"] == ops["layers_5.attn.qk"]["fwd"]
    assert ops["layers_5.k_proj"]["fwd"] == 2 * t * h * 2 * 128
    assert "layers_5.in_proj" not in ops and "layers_4.q_proj" not in ops
    # routed experts at the uniform share of pairs: T * 10 * 18 / 72 rows
    assert ops["layers_3.experts.up_proj"]["fwd"] == 2 * (t * 10 / 4) * h * 768
    assert ops["layers_3.shared.up_proj"]["fwd"] == 2 * t * h * 1536
    assert ops["layers_3.router"]["fwd"] == 2 * t * h * 72
    assert ops["head"]["fwd"] == 2 * t * h * 25088
    # trainable leaves get a weight gradient; of layer 0's mixer only C and
    # what C feeds carry an activation gradient (its input is the embedding)
    c0, c1 = ops["layers_0.in_proj_c"], ops["layers_1.in_proj_c"]
    assert c0["weight_grad"] and c0["act_operands_with_grad"] == 0
    assert c1["weight_grad"] and c1["act_operands_with_grad"] == 1
    assert ops["layers_0.in_proj"]["act_operands_with_grad"] == 0
    assert ops["layers_0.ssd.cb"]["act_operands_with_grad"] == 1
    assert ops["layers_0.ssd.chunk_state"]["act_operands_with_grad"] == 0
    assert ops["layers_1.ssd.cb"]["act_operands_with_grad"] == 2
    assert ops["layers_0.out_proj"]["act_operands_with_grad"] == 1
    assert ops["layers_5.q_proj"]["weight_grad"]
    assert not ops["layers_5.k_proj"]["weight_grad"]
    fwd = flops.forward_flops(list(ops.values()))
    step = flops.tune_step_flops(list(ops.values()))
    # ISSUE 32: forward 1.64 GFLOP a token
    assert 1.63e9 < fwd / t < 1.65e9 and 1.08e14 < step < 1.10e14
    scan = sum(o["fwd"] for s, o in ops.items() if ".ssd." in s)
    assert 0.005 < scan / fwd < 0.015  # tiny products: the scan is HBM's
    # the program's counter in place of the uniform share
    more = flops_hybrid.hybrid_ops(config, t, held_pair_share=0.3)
    assert (flops.forward_flops(more) - fwd == pytest.approx(
        10 * 3 * 2 * (t * 10 * 0.05) * h * 768))


def _ctx(metric, found, **over):
    _, _, cell, _, config = found
    ctx = {"metric": metric, "cell": cell, "config": config,
           "device": {"kind": "TPU v5 lite", "count": 1},
           "window": {"kind": "tune_hybrid", "tokens": 32768, "batch": 1,
                      "traced_steps": 5,
                      "counters": {"held_pair_share": 0.25,
                                   "expert_load_max_over_mean": 1.07,
                                   "ssd_state_rms": 0.031}},
           "trace": {"busy_s": 12.0, "window_s": 12.1,
                     "scope_s": {"lm.ssd": 1.5, "lm.mamba_proj": 2.0,
                                 "lm.attention": 0.5, "lm.experts": 3.0}}}
    ctx.update(over)
    return ctx


def test_readers_read_what_the_traced_run_hands_them(found):
    read = lambda m, **o: bench_run.find_reader(m).read(_ctx(m, found, **o))  # noqa: E731
    step = flops.tune_step_flops(flops_hybrid.hybrid_ops(found[4], 32768, 0.25))
    assert read("granite_mfu.tune") == pytest.approx(
        100 * 5 * step / (12 * 197e12), rel=1e-9)
    assert 0 < read("granite_mfu.tune") < 100
    assert read("ssd_scan_ms.tune") == pytest.approx(300.0)
    assert read("mamba_proj_ms.tune") == pytest.approx(400.0)
    assert read("attention_ms.tune") == pytest.approx(100.0)
    assert read("experts_ms.tune") == pytest.approx(600.0)
    assert read("ssd_state_rms.tune") == 0.031
    assert read("expert_load_max_over_mean.tune") == 1.07


@pytest.mark.parametrize("metric", NEW_METRICS)
def test_readers_return_none_where_there_is_nothing_to_read(found, metric):
    """A program without the scopes or the counters (the parent, another
    family's window), or an untraced run: the metric is left out, never 0."""
    read = bench_run.find_reader(metric).read
    other = {"window": {"kind": "tune_lm", "traced_steps": 5, "batch": 1,
                        "tokens": 16384,
                        "counters": {"held_pair_share": 1 / 16}},
             "trace": {"busy_s": 6.0, "window_s": 6.1,
                       "scope_s": {"lm.sparse_attention": 1.0}}}
    assert read(_ctx(metric, found, **other)) is None
    assert read(_ctx(metric, found, trace=None, window={
        "kind": "tune_hybrid", "traced_steps": None})) is None


def test_scope_reducer_takes_the_new_scopes():
    driver = bench_run.load_module(os.path.join(BENCH, "drivers",
                                                "tune_lm_steps.py"))
    find = driver._SCOPE.findall
    op = ("jit(program)/while/body/train.loss/transpose(jvp(lm.mamba_proj))/"
          "lm.ssd/checkpoint/dot_general")
    assert find(op) == ["train.loss", "lm.mamba_proj", "lm.ssd"]
    assert find("jit(program)/train.loss/lm.attention/pallas_call") == [
        "train.loss", "lm.attention"]


def test_seeded_per_head_leaves_follow_the_assumed_distributions():
    """``A_log = log U[1, 16]``, ``dt_bias = softplus^-1(U[1e-3, 1e-1])``,
    ``D = 1``; every other leaf as ``weights_lm`` draws it, from the seed."""
    shapes = {"params": {"mamba": {
        "A_log": jax.ShapeDtypeStruct((4096,), jnp.bfloat16),
        "dt_bias": jax.ShapeDtypeStruct((4096,), jnp.bfloat16),
        "D": jax.ShapeDtypeStruct((4096,), jnp.bfloat16),
        "in_proj_c": {"kernel": jax.ShapeDtypeStruct((256, 64), jnp.bfloat16)},
        "conv": {"kernel": jax.ShapeDtypeStruct((4, 512), jnp.bfloat16),
                 "bias": jax.ShapeDtypeStruct((512,), jnp.bfloat16)}}}}
    make = jax.jit(lambda w: weights_hybrid.make_hybrid_weights(shapes, w))
    a = jax.tree.map(lambda x: np.asarray(x, np.float32),
                     make(jnp.asarray([1, 2], jnp.uint32))["params"]["mamba"])
    rate = np.exp(a["A_log"])
    assert 0.99 <= rate.min() < 1.5 and 15.0 < rate.max() <= 16.1
    assert rate.mean() == pytest.approx(8.5, rel=0.05)
    step = np.log1p(np.exp(a["dt_bias"]))
    assert 0.9e-3 < step.min() < 3e-3 and 0.09 < step.max() < 0.102
    assert step.mean() == pytest.approx(0.0505, rel=0.05)
    assert np.all(a["D"] == 1.0)
    assert a["in_proj_c"]["kernel"].std() == pytest.approx(256 ** -0.5, rel=0.05)
    assert a["conv"]["kernel"].std() == pytest.approx(0.5, rel=0.1)
    assert a["conv"]["bias"].std() == pytest.approx(0.02, rel=0.15)
    b = make(jnp.asarray([1, 3], jnp.uint32))["params"]["mamba"]
    assert not np.array_equal(a["A_log"], np.asarray(b["A_log"], np.float32))


def test_choice_gaps_count_what_differs():
    from benchmark.reference.tune_hybrid_check import choice_gaps

    experts = jnp.asarray([[0, 1], [2, 3], [4, 5], [6, 7]])
    same = [{"experts": experts, "routed_over_shared": 0.50, "state_rms": 0.2},
            {"experts": experts, "routed_over_shared": 0.25, "state_rms": None}]
    assert choice_gaps(same, same, same, 0.2) == {
        "expert_choice_diff": 0.0, "routed_share_gap": 0.0,
        "state_rms_gap": 0.0}
    other = [{"experts": experts.at[0, 0].set(5)[:, ::-1],
              "routed_over_shared": 0.40, "state_rms": 0.25}, same[1]]
    # the choices against the reference's own; the routed share and the
    # state against the reference's GIVEN the program's choices
    g = choice_gaps(same, other, same, 0.2)
    assert g["routed_share_gap"] == 0.0 and g["state_rms_gap"] == 0.0
    assert g["expert_choice_diff"] == pytest.approx(1 / 16)  # order is no difference
    g = choice_gaps(same, same, other, 0.2)
    assert g["routed_share_gap"] == pytest.approx(0.25)
    assert g["state_rms_gap"] == pytest.approx(0.2)
    # nine of ten: a tenth of the program's choices are not among them
    nine = [{**c, "experts": c["experts"][:, :1]} for c in same]
    assert choice_gaps(same, nine, same, 0.2)["expert_choice_diff"] == 0.5
    # chunks that hand nothing on: the counter reads 0
    assert choice_gaps(same, same, same, 0.0)["state_rms_gap"] == 1.0


def test_tiny_arch_of_the_check_is_the_programs_tiny_preset():
    import dataclasses

    from videop2p_tpu.models.granite_hybrid import GraniteHybridConfig

    from benchmark.reference.tune_hybrid_check import TINY_ARCH

    cfg = GraniteHybridConfig.tiny()
    tiny = dict(dataclasses.asdict(cfg), head_dim=cfg.head_dim,
                kv_heads_held=cfg.kv_heads_held)
    for k, v in TINY_ARCH.items():
        assert (tuple(v) == tuple(tiny[k]) if isinstance(v, tuple)
                else v == tiny[k]), k


def test_levelled_router_rows_spread_the_document_over_the_experts():
    """``level_router_rows`` rescales each layer's router rows on the
    document with the plain reference's float32 layers — nothing of the
    program — and only until the busiest held expert sees at most
    ``LEVEL_AT`` times the mean: the load over the mean falls (1.29 as
    drawn at this size) to just under that as the PROGRAM then counts it
    and no further, the same call gives the same rows, and ``with_router_rows`` puts them
    into a set of weights (every other leaf as drawn)."""
    from videop2p_tpu.models import granite_hybrid as gh

    from benchmark.harness import weights_lm
    from benchmark.harness.weights import flatten_named
    from benchmark.reference.tune_hybrid_check import TINY_ARCH

    cfg = gh.GraniteHybridConfig.tiny()
    ids = jnp.asarray(weights_lm.document(3, 256, cfg.vocab_size))
    real_init = gh.init_params
    try:
        weights_hybrid.steer_init()
        drawn = weights_hybrid.regenerate(7, cfg)
        rows = weights_hybrid.level_router_rows(flatten_named(drawn),
                                                TINY_ARCH, ids)
        assert sorted(rows) == ["layers_0", "layers_1", "layers_2"]
        assert all(isinstance(r, np.ndarray) and r.shape == (8,) and r.min() > 0
                   for r in rows.values())
        again = weights_hybrid.level_router_rows(flatten_named(drawn),
                                                 TINY_ARCH, ids)
        assert all(np.array_equal(rows[k], again[k]) for k in rows)
        levelled = weights_hybrid.regenerate(7, rows=rows)
    finally:
        gh.init_params = real_init
    a, b = flatten_named(drawn), flatten_named(levelled)
    moved = sorted(k for k in a if not bool(jnp.array_equal(a[k], b[k])))
    assert moved == [f"params/layers_{i}/router/kernel" for i in range(3)]
    k0 = np.asarray(a[moved[0]], np.float32) * rows["layers_0"][None, :]
    assert np.array_equal(np.asarray(b[moved[0]], np.float32),
                          np.asarray(jnp.asarray(k0, jnp.bfloat16), np.float32))
    load = lambda p: float(jax.jit(lambda p: gh.forward_loss(  # noqa: E731
        p, cfg, ids)[1]["expert_load_max_over_mean"])(p["params"]))
    assert 1.01 < load(levelled) <= weights_hybrid.LEVEL_AT + 0.02 < 1.2 < load(drawn)


def test_fit_rows_stops_at_the_imbalance_and_pins_the_held_share():
    """A quarter of sixteen experts held, tokens with a common component (so
    that the loads as drawn are far from level): the refit stops once the
    busiest held expert is within ``LEVEL_AT`` of the held mean — the loads
    keep an imbalance — and the held share of the pairs ends at held /
    published within ``SHARE_TOL`` of it."""
    from benchmark.reference import granite_moe_hybrid as ref

    nx, k, held, n = ref._Nx("float32"), 3, (4, 4), 16
    ks = jax.random.split(jax.random.key(5), 3)
    y = (jax.random.normal(ks[0], (8192, 32))
         + 0.5 * jax.random.normal(ks[1], (1, 32)))
    kernel = (jax.random.normal(ks[2], (32, n)) / 32 ** 0.5).astype(jnp.bfloat16)

    def loads(scale):
        chosen = jax.lax.top_k(nx.mm(y, weights_hybrid._scaled(kernel, scale)), k)[1]
        return np.bincount(np.asarray(chosen).ravel(), minlength=n)

    scale = jax.jit(lambda y: weights_hybrid.fit_rows(nx, y, kernel, k, held))(y)
    drawn, fit = loads(jnp.ones((n,))), loads(scale)
    on_held = lambda load: load[held[0]:held[0] + held[1]]  # noqa: E731
    assert on_held(drawn).max() > 1.3 * on_held(drawn).mean()
    assert abs(on_held(drawn).sum() / drawn.sum() - 0.25) > 0.01
    assert (1.005 * on_held(fit).mean() < on_held(fit).max()
            <= weights_hybrid.LEVEL_AT * on_held(fit).mean())
    assert abs(on_held(fit).sum() / fit.sum() / 0.25 - 1) <= weights_hybrid.SHARE_TOL


# ------------------------------------------------ the cell, at rehearsal size


@pytest.fixture(scope="module")
def rehearsal():
    """ONE process: the cell's own driver at rehearsal size (the tiny
    preset, 64 tokens, ``run_tuning.main``), then the check once sound,
    once under the float8 control and once under every planted fault."""
    from benchmark.reference.granite_moe_hybrid import FAULTS

    hows = ",".join(("sound", "float8_e4m3fn") + FAULTS)
    p = subprocess.run(
        [sys.executable, os.path.join(HERE, "read_limits_hybrid.py"),
         "--workload", CELL, "--seed", str(2 ** 31 + 32), "--seconds", "0.3",
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
        "ssd_state_rms"}
    assert prog["counters"]["ssd_state_rms"] > 0
    sound = rehearsal["sound"]
    assert sound["correct_under_committed_limits"], sound["compared"]


# (fault, a number it moves at rehearsal size): none is chip-only
FAULT_MOVES = [
    ("float8_e4m3fn", "mu_gap_worst"), ("no_carry", "change_diff_worst"),
    ("no_carry", "state_rms_gap"),
    ("no_conv", "change_diff_worst"), ("no_softplus", "loss_gap_first"),
    ("top9", "routed_share_gap"), ("gates_over_all", "routed_share_gap"),
    ("residual_one", "change_diff_worst"),
    ("half_document", "change_diff_worst")]


@pytest.mark.parametrize("fault,number", FAULT_MOVES)
def test_each_planted_fault_breaks_a_limit_at_rehearsal_size(rehearsal, fault,
                                                             number):
    """Every planted fault and the float8 control turn the rehearsal's
    verdict to not correct, by the number named."""
    sound, faulty = rehearsal["sound"]["compared"], rehearsal[fault]["compared"]
    assert not rehearsal[fault]["correct_under_committed_limits"], faulty
    value = faulty[number]
    assert value is None or not np.isfinite(value) or value > 3 * max(
        sound[number], 1e-6), (fault, number, value, sound[number])
    if number == "state_rms_gap":
        # a recurrence that hands nothing on reads 0 against the program's
        assert value == 1.0
