"""The token-model cell's own pieces on the CPU: the operation count
hand-checked against the configuration's shapes, the readers on what a
traced run hands them (and on a program that has none of it: None, never 0),
the manifest entries, the seeded weights, and the choice comparison."""

import json
import os
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

from benchmark.harness import flops, flops_lm, weights_lm  # noqa: E402

CELL = "deepseek-v32-s16-tune.doc16k-steps"


@pytest.fixture(scope="module")
def found():
    manifest = bench_run.load_json(os.path.join(ROOT, "BENCHMARK.json"))
    return (manifest,) + bench_run.find_cell(manifest, CELL)


def test_cell_is_found_by_name_and_states_the_cut(found):
    manifest, entry, cell, cfg_entry, config = found
    assert entry["chips"] == 1 and cell["driver"] == "tune_lm_steps"
    assert os.path.isfile(os.path.join(BENCH, "drivers", cell["driver"] + ".py"))
    assert cell["why"] == entry["why"] and len(entry["why"]) <= 200
    # every key listed as reduced differs from the published count, and the
    # file states the published one beside it
    dep = config["deployment"]
    for key in cfg_entry["reduced"]:
        assert key in config["reduced"], key
        assert config[key] != dep[key + "_published"], key
    assert dep["chips_sharing_a_layer"] * config["n_routed_experts"] == 256
    assert dep["chips_sharing_a_layer"] * config["num_attention_heads"] == 128
    assert config["vocab_size"] * 8 == dep["vocab_size_published"]
    # no width is cut
    widths = dict(hidden_size=7168, intermediate_size=18432,
                  moe_intermediate_size=2048, q_lora_rank=1536,
                  kv_lora_rank=512, qk_nope_head_dim=128, qk_rope_head_dim=64,
                  v_head_dim=128, index_n_heads=64, index_head_dim=128,
                  index_topk=2048, num_experts_per_tok=8, n_group=8,
                  topk_group=4)
    assert {k: config[k] for k in widths} == widths


def test_the_cli_config_states_the_same_model(found):
    from videop2p_tpu.cli.common import load_config

    _, _, cell, _, config = found
    driver = bench_run.load_module(os.path.join(BENCH, "drivers",
                                                "tune_lm_steps.py"))
    model = driver.model_from_config(config)
    cli = load_config(os.path.join(ROOT, cell["cli_config"]))
    # the one thing the cell adds: the loss hands out what every layer chose
    assert model.pop("hand_out_choices") is True
    assert cli["model"] == model
    assert list(cli["trainable_modules"]) == config["training"]["trainable_modules"]


def test_per_layer_metrics_of_the_cell_have_readers(found):
    manifest = found[0]
    reported = {m["name"] for m in bench_run.metrics_for(
        manifest, "end_to_end", CELL, set())}
    assert reported == {"tune_step_ms", "setup_s"}
    names = [m["name"] for m in bench_run.metrics_for(
        manifest, "per_layer", CELL, reported)]
    assert {"lm_mfu.tune", "indexer_ms.tune", "sparse_attention_ms.tune",
            "experts_ms.tune", "expert_load_max_over_mean.tune",
            "setup_document_s.tune", "device_idle.tune",
            "device_peak_gib.tune"} <= set(names)
    assert "unet_mfu.tune" not in names
    for n in names:
        assert hasattr(bench_run.find_reader(n), "read"), n


def test_operation_count_by_hand(found):
    """One expert layer and the whole step, from the shapes, by hand."""
    config = found[4]
    t, h = 16384, 7168
    ops = {o["site"]: o for o in flops_lm.lm_ops(config, t)}
    # attention at the keys SELECTED: sum_t min(t + 1, 2048)
    pairs = 2048 * 2049 / 2 + (t - 2048) * 2048
    assert flops_lm.selected_pairs(t, 2048) == pairs
    assert ops["layers_1.attn.qk"]["fwd"] == 2 * 8 * 192 * pairs
    assert ops["layers_1.attn.pv"]["fwd"] == 2 * 8 * 128 * pairs
    # the scorer at the causal pairs, forward only
    scores = ops["layers_1.indexer.scores"]
    assert scores["fwd"] == 2 * 64 * 128 * t * (t + 1) / 2
    assert scores["act_operands_with_grad"] == 0 and not scores["weight_grad"]
    # routed experts at the uniform share of pairs: T * 8 * 16 / 256 rows
    assert ops["layers_1.experts.up_proj"]["fwd"] == 2 * (t * 8 / 16) * h * 2048
    assert ops["layers_1.shared.up_proj"]["fwd"] == 2 * t * h * 2048
    assert ops["layers_0.mlp.down_proj"]["fwd"] == 2 * t * h * 18432
    assert ops["head"]["fwd"] == 2 * t * h * 16160
    # trainable leaves get a weight gradient; nothing upstream of the first
    # one carries an activation gradient
    a0, a1 = ops["layers_0.q_a_proj"], ops["layers_1.q_a_proj"]
    assert a0["weight_grad"] and a0["act_operands_with_grad"] == 0
    assert a1["weight_grad"] and a1["act_operands_with_grad"] == 1
    assert ops["layers_0.kv_b_proj"]["act_operands_with_grad"] == 0
    assert ops["layers_0.attn.qk"]["act_operands_with_grad"] == 1
    assert ops["layers_1.attn.qk"]["act_operands_with_grad"] == 2
    fwd = flops.forward_flops(list(ops.values()))
    step = flops.tune_step_flops(list(ops.values()))
    assert 43.9e12 < fwd < 44.1e12 and 76.9e12 < step < 77.2e12
    dense = sum(o["fwd"] for s, o in ops.items() if s.startswith("layers_0."))
    assert 0.36 < dense / fwd < 0.40
    # the program's counter in place of the uniform share
    more = flops_lm.lm_ops(config, t, held_pair_share=1 / 8)
    assert (flops.forward_flops(more) - fwd
            == pytest.approx(4 * 3 * 2 * (t * 8 / 16) * h * 2048))


def _ctx(metric, found, **over):
    _, _, cell, _, config = found
    ctx = {"metric": metric, "cell": cell, "config": config,
           "device": {"kind": "TPU v5 lite", "count": 1},
           "window": {"kind": "tune_lm", "tokens": 16384, "batch": 1,
                      "traced_steps": 5,
                      "counters": {"held_pair_share": 1 / 16,
                                   "expert_load_max_over_mean": 1.25}},
           "trace": {"busy_s": 10.0, "window_s": 10.1,
                     "scope_s": {"lm.indexer": 1.5, "lm.experts": 0.5,
                                 "lm.sparse_attention": 2.5}}}
    ctx.update(over)
    return ctx


def test_readers_read_what_the_traced_run_hands_them(found):
    read = lambda m, **o: bench_run.find_reader(m).read(_ctx(m, found, **o))  # noqa: E731
    # 5 steps x 77.05 TFLOP over 10 busy seconds of a 197 TFLOP/s chip
    assert read("lm_mfu.tune") == pytest.approx(100 * 5 * 77.046e12 / (10 * 197e12), rel=1e-4)
    assert read("lm_mfu.tune") < 100
    assert read("indexer_ms.tune") == pytest.approx(300.0)
    assert read("sparse_attention_ms.tune") == pytest.approx(500.0)
    assert read("experts_ms.tune") == pytest.approx(100.0)
    assert read("expert_load_max_over_mean.tune") == 1.25


def test_readers_return_none_where_there_is_nothing_to_read(found):
    """A program without the scopes or the counters (the parent), or an
    untraced run: the metric is left out, never 0."""
    bare = {"window": {"kind": "tune", "traced_steps": 25, "batch": 1,
                       "frames": 8},
            "trace": {"busy_s": 6.0, "window_s": 6.1}}
    for m in ("lm_mfu.tune", "indexer_ms.tune", "sparse_attention_ms.tune",
              "experts_ms.tune", "expert_load_max_over_mean.tune"):
        assert bench_run.find_reader(m).read(_ctx(m, found, **bare)) is None, m
        assert bench_run.find_reader(m).read(
            _ctx(m, found, trace=None,
                 window={"kind": "tune_lm", "traced_steps": None})) is None, m


def test_document_span_reader(tmp_path, monkeypatch):
    """``setup_document_s.tune`` reads ``tune.load_document`` under the
    root span; a ledger without it (a clip's tune, the parent) gives None."""
    from benchmark.harness import spans

    path = tmp_path / "ledger.jsonl"
    monkeypatch.setattr(spans, "ledger_path", lambda ctx: str(path))
    read = bench_run.find_reader("setup_document_s.tune").read
    ctx = {"cell": {"name": CELL}}
    assert read(ctx) is None  # no ledger
    span = lambda i, parent, name, d: {  # noqa: E731
        "event": "span", "span_id": i, "parent_id": parent, "name": name,
        "wall_ns": 10 ** 9 * i, "duration_s": d}
    lines = [span(2, 1, "tune.build_models", 11.0),
             span(3, 1, "tune.load_document", 0.25),
             span(4, 9, "tune.load_document", 5.0),  # not under the root
             span(1, None, "tune.setup", 100.0)]
    path.write_text("".join(json.dumps(e) + "\n" for e in lines))
    assert read(ctx) == 0.25
    path.write_text("".join(json.dumps(e) + "\n" for e in lines
                            if e["name"] != "tune.load_document"))
    assert read(ctx) is None


def test_scope_of_an_event_is_its_innermost_lm_scope():
    driver = bench_run.load_module(os.path.join(BENCH, "drivers",
                                                "tune_lm_steps.py"))
    find = driver._SCOPE.findall
    op = ("jit(program)/while/body/train.loss/transpose(jvp(lm.mla_proj))/"
          "lm.sparse_attention/dot_general")
    assert find(op) == ["train.loss", "lm.mla_proj", "lm.sparse_attention"]
    assert find("jit(program)/while/body/train.optimizer/mul") == ["train.optimizer"]
    assert find("jit(program)/while/body/add") == []


def test_seeded_weights_take_the_fan_in_of_a_stacked_leaf():
    shapes = {"params": {
        "experts": {"up_proj": {"kernel": jax.ShapeDtypeStruct(
            (4, 256, 64), jnp.bfloat16)}},
        "norm": {"scale": jax.ShapeDtypeStruct((256,), jnp.bfloat16)},
        "embed": {"embedding": jax.ShapeDtypeStruct((512, 256), jnp.bfloat16)},
    }}
    make = jax.jit(lambda w: weights_lm.make_lm_weights(shapes, w))
    a = make(jnp.asarray([1, 2], jnp.uint32))["params"]
    b = make(jnp.asarray([1, 3], jnp.uint32))["params"]
    k = a["experts"]["up_proj"]["kernel"].astype(jnp.float32)
    assert k.dtype == jnp.float32 and a["norm"]["scale"].dtype == jnp.bfloat16
    assert float(jnp.std(k)) == pytest.approx(256 ** -0.5, rel=0.05)  # not (4*256)^-1/2
    assert abs(float(jnp.mean(a["norm"]["scale"].astype(jnp.float32))) - 1) < 0.02
    assert float(jnp.std(a["embed"]["embedding"].astype(jnp.float32))) == pytest.approx(0.02, rel=0.05)
    assert not bool(jnp.array_equal(k, b["experts"]["up_proj"]["kernel"]))
    again = make(jnp.asarray([1, 2], jnp.uint32))["params"]
    assert bool(jnp.array_equal(again["embed"]["embedding"], a["embed"]["embedding"]))


def test_fingerprints_tell_a_moved_leaf():
    x = jax.random.normal(jax.random.key(0), (64, 33)).astype(jnp.bfloat16)
    y = x.at[5, 7].set(x[5, 7] + jnp.bfloat16(0.25))
    swapped = x.at[0].set(x[1]).at[1].set(x[0])
    f = weights_lm.fingerprints({"a": x, "b": y, "c": swapped, "d": x + 0})
    assert f["a"] == f["d"] and f["a"] != f["b"] and f["a"] != f["c"]


def test_document_is_fixed_by_its_own_seed():
    a = weights_lm.document(16384, 256, 16160)
    assert a.dtype == np.int32 and a.shape == (256,) and a.max() < 16160
    assert np.array_equal(a, weights_lm.document(16384, 256, 16160))
    assert not np.array_equal(a, weights_lm.document(16385, 256, 16160))


def test_choice_gaps_count_what_differs():
    from benchmark.reference.tune_lm_check import choice_gaps

    def pack_choices(choices):  # eight keys a byte, as both sides hand out
        return [{**c, "mask": jnp.packbits(c["mask"], axis=-1)} for c in choices]

    mask = jnp.tril(jnp.ones((8, 8), bool))
    experts = jnp.asarray([[0, 1], [2, 3], [4, 5], [6, 7]])
    same = pack_choices([
        {"mask": mask, "experts": None, "routed_over_shared": None},
        {"mask": mask, "experts": experts, "routed_over_shared": 0.50}])
    assert same[0]["mask"].shape == (8, 1) and same[0]["mask"].dtype == jnp.uint8
    assert choice_gaps(same, same, same) == {"selected_key_diff": 0.0,
                                             "expert_choice_diff": 0.0,
                                             "routed_share_gap": 0.0}
    other = pack_choices([
        {"mask": mask.at[7, 0].set(False), "experts": None,
         "routed_over_shared": None},
        {"mask": mask, "experts": experts.at[0, 0].set(5)[:, ::-1],
         "routed_over_shared": 0.40}])
    # the choices against the reference's own; the routed share against the
    # reference's GIVEN the program's choices
    assert choice_gaps(same, other, same)["routed_share_gap"] == 0.0
    g = choice_gaps(same, other, other)
    assert g["routed_share_gap"] == pytest.approx(0.25)  # against the reference's
    assert g["selected_key_diff"] == pytest.approx(1 / 72)
    assert g["expert_choice_diff"] == pytest.approx(1 / 8)  # order is no difference
    # half the experts a token: half of the program's choices are not among them
    half = [other[0], {"mask": same[1]["mask"], "experts": experts[:, :1],
                       "routed_over_shared": 0.5}]
    assert choice_gaps(same, half, same)["expert_choice_diff"] == pytest.approx(0.5)


def test_tiny_arch_of_the_check_is_the_programs_tiny_preset():
    import dataclasses

    from videop2p_tpu.models.deepseek import DeepSeekV32Config

    from benchmark.reference.tune_lm_check import TINY_ARCH

    tiny = dataclasses.asdict(DeepSeekV32Config.tiny())
    for k, v in TINY_ARCH.items():
        if k == "rope_scaling":
            assert v["factor"] == tiny["rope_factor"]
            assert v["original_max_position_embeddings"] == tiny["rope_original_max"]
        else:
            assert tuple(v) == tuple(tiny[k]) if isinstance(v, tuple) else v == tiny[k], k


def test_balanced_selection_bias_spreads_the_document_over_the_experts():
    """``balance_routers`` refits each expert layer's selection bias on the
    document with the plain reference's float32 routing — nothing of the
    program: the busiest expert's load over the mean falls (1.7-2.1 as drawn
    at this size, under 1.3 refit) as the PROGRAM then counts it, the same
    call gives the same biases, and ``with_biases`` puts them into a set of
    weights (the router's kernel as drawn)."""
    from videop2p_tpu.models import deepseek as ds

    from benchmark.harness.weights import flatten_named
    from benchmark.reference.tune_lm_check import TINY_ARCH

    cfg = ds.DeepSeekV32Config.tiny()
    ids = jnp.asarray(weights_lm.document(3, 128, cfg.vocab_size))
    real_init = ds.init_params
    try:
        weights_lm.steer_init()
        drawn = weights_lm.regenerate(7, cfg)
        biases = weights_lm.balance_routers(flatten_named(drawn), TINY_ARCH, ids)
        assert sorted(biases) == ["layers_2", "layers_3"]
        assert all(isinstance(b, np.ndarray) and b.shape == (8,)
                   for b in biases.values())
        again = weights_lm.balance_routers(flatten_named(drawn), TINY_ARCH, ids)
        assert all(np.array_equal(biases[k], again[k]) for k in biases)
        refit = weights_lm.regenerate(7, biases=biases)
    finally:
        ds.init_params = real_init
    for name in biases:
        a, b = drawn["params"][name]["router"], refit["params"][name]["router"]
        assert b["kernel"] is a["kernel"] or bool(
            jnp.array_equal(a["kernel"], b["kernel"]))
        assert np.array_equal(np.asarray(b["bias"], np.float32),
                              np.asarray(jnp.asarray(biases[name], jnp.bfloat16),
                                         np.float32))
    load = lambda p: float(jax.jit(lambda p: ds.forward_loss(  # noqa: E731
        p, cfg, ids)[1]["expert_load_max_over_mean"])(p["params"]))
    assert load(refit) < 1.3 < load(drawn)


def test_bfloat16_copies_of_the_masters_lag_the_first_updates():
    """Why the cell's sound ``loss_gap_worst`` reads 0.018 and not 0.001:
    Adam's first steps move every float32 master by the learning rate along
    its gradient's sign, and 3e-5 is under half a bfloat16 step (2^-15 =
    3.05e-5) of every weight of size 2^-7 and more — the copy the matmul
    sees has not moved. After one update the copies have moved for half of
    ``q_a_proj`` (N(0, 1/7168)) and a quarter of ``q_b_proj`` (N(0, 1/1536)),
    after three for nearly all: the program's loss lags the float32
    reference's for two steps and catches up (PERF.md section 6, PR 28)."""
    import ml_dtypes

    rng = np.random.default_rng(0)
    seen_moving = {}
    for name, fan_in in (("q_a_proj", 7168), ("q_b_proj", 1536)):
        w0 = (rng.standard_normal(400_000) / np.sqrt(fan_in)).astype(
            ml_dtypes.bfloat16).astype(np.float32)  # the checkpoint's dtype
        sign = rng.choice([-1.0, 1.0], w0.shape).astype(np.float32)
        master, shares = w0.copy(), []
        for _ in range(3):
            master = master - np.float32(3e-5) * sign
            copy = master.astype(ml_dtypes.bfloat16).astype(np.float32)
            shares.append(float(np.mean(copy != w0)))
        seen_moving[name] = shares
    assert seen_moving["q_a_proj"][0] == pytest.approx(0.49, abs=0.02)
    assert seen_moving["q_b_proj"][0] == pytest.approx(0.24, abs=0.02)
    assert seen_moving["q_a_proj"][2] > 0.98 and seen_moving["q_b_proj"][2] > 0.75
