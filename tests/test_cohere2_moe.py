"""The third token family (Command A+'s block, ``models/cohere2_moe.py``)
against its plain float32 reference (``benchmark/reference/cohere2_moe.py``:
explicit masks, the shared experts one by one, a dense expert loop) on seeded
weights at a small size — 64 wide, sliding x 3 + full, 8 query heads of 16 on
2 key / value heads, a window of 8, 8 experts of 32 top-3, 2 shared experts,
64 tokens — through the shared tuner, and share by share against the uncut
layer.

The program runs in float32 here, so that what is compared is the
mathematics (banded, grouped, sorted, looped) and not bfloat16 rounding.
"""

import dataclasses
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from flax import traverse_util

from videop2p_tpu.models import cohere2_moe as cm
from videop2p_tpu.models import deepseek as ds
from videop2p_tpu.models import granite_hybrid as gh
from videop2p_tpu.ops import selected_attention as sa
from videop2p_tpu.ops.grouped_experts import ExpertTiles
from videop2p_tpu.train import (
    TrainState,
    TuneConfig,
    loss_steps,
    make_optimizer,
    next_token_loss,
)

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

from benchmark.reference import cohere2_moe as ref  # noqa: E402

TRAINABLE = ("q_proj",)
T = 64


def arch_of(cfg: cm.Cohere2MoeConfig) -> dict:
    """The reference's ``arch`` for a program configuration."""
    d = dataclasses.asdict(cfg)
    arch = {k: d[k] for k in ref.ARCH_KEYS}
    arch.update(num_experts=cfg.num_experts,
                num_shared_experts=cfg.num_shared_experts,
                experts_held=cfg.experts_held, heads_held=cfg.heads_held,
                kv_heads_held=cfg.kv_heads_held,
                shared_columns_held=cfg.shared_columns_held)
    return arch


def named(params) -> dict:
    return {"params/" + "/".join(k): v
            for k, v in traverse_util.flatten_dict(params).items()}


@pytest.fixture(autouse=True)
def small_blocks(monkeypatch):
    """Several row blocks (each reads 16 + 7 keys of the 64), expert blocks
    and loss chunks at 64 tokens."""
    monkeypatch.setattr(gh, "ATTN_ROWS", 16)
    for name, value in dict(FFN_ROWS=32, EXPERT_BLOCK=8, LOSS_CHUNK=32).items():
        monkeypatch.setattr(ds, name, value)


@pytest.fixture(scope="module")
def model():
    cfg = cm.Cohere2MoeConfig.tiny()
    # bfloat16-exact values (the checkpoint's dtype), held in float32
    params = jax.jit(lambda k: cm.init_params(k, cfg))(jax.random.key(5))
    params = jax.tree.map(lambda x: x.astype(jnp.float32), params["params"])
    ids = jax.random.randint(jax.random.key(1), (T,), 0, cfg.vocab_size)
    return cfg, params, ids


def _sizes(cfg):
    return {"/".join(str(getattr(k, "key", k)) for k in path): int(np.prod(s[0]))
            for path, s in jax.tree_util.tree_flatten_with_path(
                cm.param_shapes(cfg), is_leaf=cm._is_spec)[0]}


def test_published_defaults_and_param_count():
    """The defaults are the published config.json: 344 M values a layer
    beside the routed experts, 50.3 M an expert; the benchmark's cut (one
    chip of the 8 that share each layer, one period of layer_types) holds
    3.53 B values, 33.6 M of them trainable (ISSUE 34's arithmetic)."""
    cfg = cm.Cohere2MoeConfig()
    cfg.check()
    assert (cfg.hidden_size, cfg.intermediate_size, cfg.head_dim) == (4096,) * 2 + (128,)
    assert (cfg.num_attention_heads, cfg.num_key_value_heads) == (128, 8)
    assert (cfg.num_experts, cfg.num_experts_per_tok, cfg.num_shared_experts) == (
        128, 8, 4)
    assert (cfg.sliding_window, cfg.rope_theta, cfg.layer_norm_eps) == (
        4096, 50000, 1e-5)
    assert [i for i, k in enumerate(cfg.layer_types)
            if k == "full_attention"] == list(range(3, 32, 4))
    assert cfg.first_k_dense_replace == 0 and cfg.kv_heads_held == (0, 8)
    layer = {k.split("/", 2)[2]: n for k, n in _sizes(cfg).items()
             if k.startswith("params/layers_0/")}
    experts = sum(n for k, n in layer.items() if k.startswith("experts/"))
    assert round(experts / 128 / 1e6, 1) == 50.3
    assert round((sum(layer.values()) - experts) / 1e6) == 344
    cut = dataclasses.replace(
        cfg, num_hidden_layers=4, layer_types=cfg.layer_types[:4],
        experts_held=(0, 16), heads_held=(0, 16), shared_columns_held=(0, 2048),
        vocab_size=32768)
    assert cut.kv_heads_held == (0, 1)
    sizes = _sizes(cut)
    assert round(sum(sizes.values()) / 1e6) == 3530
    assert round(sum(n for k, n in sizes.items() if "/q_proj/" in k) / 1e6, 1) == 33.6


@pytest.mark.parametrize("key", ["attention_kernel", "use_pallas", "attn_rows",
                                 "expert_block", "window_tiles"])
def test_config_from_dict_rejects_unknown_keys(key):
    """How the work is cut is not configuration (module constants), and
    neither is which code attends."""
    with pytest.raises(ValueError, match="unknown Cohere2MoeConfig keys"):
        cm.Cohere2MoeConfig.from_dict({"hidden_size": 64, key: 4})


@pytest.mark.parametrize("field,value", [
    ("expert_selection_fn", "softmax"), ("norm_topk_prob", False),
    ("first_k_dense_replace", 1),
    ("shared_expert_combination_strategy", "sum"), ("use_parallel_block", False),
    ("use_qk_norm", True), ("position_embedding_type", "rope_neox"),
    ("tie_word_embeddings", False), ("rotary_pct", 0.5),
    ("heads_held", (2, 4)), ("shared_columns_held", (32, 64)),
    ("layer_types", ("sliding_attention", "mamba") * 2)])
def test_config_check_refuses_what_is_not_built(field, value):
    with pytest.raises(AssertionError):
        cm.Cohere2MoeConfig.tiny(**{field: value}).check()


# ------------------------------------------------------- program / reference


def test_logits_and_loss_match_reference(model):
    """Float32 on both sides: what differs is the order of float32 sums
    (row blocks that read 23 keys against full rows under a mask, blocks of
    one expert's rows), so 1e-4 of the logits' scale and 1e-5 on the loss."""
    cfg, params, ids = model
    arch, flat = arch_of(cfg), named(params)
    want = jax.jit(lambda f: ref.logits(f, arch, ids))(flat)
    got = jax.jit(lambda p: cm.forward_logits(p, cfg, ids, jnp.float32))(params)
    assert float(jnp.max(jnp.abs(got - want))) < 1e-4 * float(jnp.max(jnp.abs(want)))
    loss, aux = jax.jit(lambda p: cm.forward_loss(p, cfg, ids, jnp.float32))(params)
    nll = jax.nn.logsumexp(want, -1) - jnp.take_along_axis(
        want, jnp.roll(ids, -1)[:, None], -1)[:, 0]
    assert abs(float(loss) - float(jnp.mean(nll[:-1]))) < 1e-5 * float(loss)
    assert set(aux) == set(cm.COUNTERS)
    assert float(aux["held_pair_share"]) == 1.0
    # the sliding layers' row blocks read 16 + 8 - 1 of the 64 keys
    assert float(aux["window_tile_share"]) == pytest.approx(23 / 64)


def _reference_first_step(cfg, params, ids, **how):
    flat = named(params)
    trainable = {k: v for k, v in flat.items() if ref.is_trainable(k, TRAINABLE)}
    frozen = {k: v for k, v in flat.items() if k not in trainable}
    grads, _ = ref.layerwise_grads(arch_of(cfg), **how)
    return grads(trainable, frozen, ids)


def test_gradients_of_the_trainable_leaves_match_reference(model):
    """``q_proj`` of all four layers (layer 0 holds one, so the backward
    crosses every layer, both kinds of attention and the parallel residual),
    the program's whole-function gradient under remat against the
    reference's chain rule layer by layer; and what the two sides chose."""
    cfg, params, ids = model
    loss_ref, choices, want = _reference_first_step(cfg, params, ids)
    (loss, aux), got = jax.jit(jax.value_and_grad(
        lambda p: cm.forward_loss(p, dataclasses.replace(
            cfg, hand_out_choices=True), ids, jnp.float32), has_aux=True))(params)
    assert abs(float(loss) - float(loss_ref)) < 1e-5 * float(loss_ref)
    got = named(got)
    assert sorted(want) == sorted(k for k in got if ref.is_trainable(k, TRAINABLE))
    assert len(want) == 4
    for k, w in want.items():
        scale = float(jnp.max(jnp.abs(w)))
        assert scale > 0, k
        assert float(jnp.max(jnp.abs(got[k] - w))) < 2e-4 * scale, k
    for mine, theirs in zip(aux["choices"], choices):
        assert np.array_equal(np.sort(mine["experts"], -1),
                              np.sort(theirs["experts"], -1))
        assert abs(float(mine["routed_over_shared"])
                   - float(theirs["routed_over_shared"])) < 1e-4


@pytest.mark.parametrize("fault", ref.FAULTS)
def test_every_planted_fault_moves_the_reference(model, fault):
    """Each fault of the reference changes the first step's loss or
    gradient at this size."""
    cfg, params, ids = model
    loss0, _, g0 = _reference_first_step(cfg, params, ids)
    loss1, _, g1 = _reference_first_step(cfg, params, ids, fault=fault)
    moved = max(float(jnp.max(jnp.abs(g1[k] - g0[k]))
                      / jnp.max(jnp.abs(g0[k]))) for k in g0)
    assert moved > 1e-3 or abs(
        float(loss1) - float(loss0)) > 1e-3 * float(loss0), (fault, moved)


def test_bfloat16_forward_is_close(model):
    """The dtype the cell runs in: bfloat16 operands against the float32
    reference at this size — a loss within 2 %, no more is claimed here."""
    cfg, params, ids = model
    loss_ref = _reference_first_step(cfg, params, ids)[0]
    loss, aux = jax.jit(lambda p: cm.forward_loss(
        jax.tree.map(lambda x: x.astype(jnp.bfloat16), p), cfg, ids))(params)
    assert abs(float(loss) - float(loss_ref)) < 0.02 * float(loss_ref)
    assert all(np.isfinite(float(aux[k])) for k in cm.COUNTERS)


# ------------------------------------------------------- the shares add up


def _share_of(params, cfg, s, n_shares):
    """Share ``s`` of ``n_shares`` of the uncut ``params``: its experts, its
    query heads (and the key / value head they read), its columns of the
    shared experts laid side by side; the router and the norm whole."""
    en = cfg.num_experts // n_shares
    hq = cfg.num_attention_heads // n_shares
    cols = cfg.num_shared_experts * cfg.intermediate_size // n_shares
    share = dataclasses.replace(
        cfg, experts_held=(s * en, en), heads_held=(s * hq, hq),
        shared_columns_held=(s * cols, cols))
    share.check()
    hd = cfg.head_dim
    kv0, hkv = share.kv_heads_held
    q = slice(s * hq * hd, (s + 1) * hq * hd)
    kv = slice(kv0 * hd, (kv0 + hkv) * hd)
    c = slice(s * cols, (s + 1) * cols)

    def cut(layer):
        a, sh = layer["attn"], layer["shared"]
        return dict(
            layer,
            experts=jax.tree.map(lambda w: w[s * en:(s + 1) * en],
                                 layer["experts"]),
            attn={"q_proj": {"kernel": a["q_proj"]["kernel"][:, q]},
                  "k_proj": {"kernel": a["k_proj"]["kernel"][:, kv]},
                  "v_proj": {"kernel": a["v_proj"]["kernel"][:, kv]},
                  "o_proj": {"kernel": a["o_proj"]["kernel"][q]}},
            shared={"gate_proj": {"kernel": sh["gate_proj"]["kernel"][:, c]},
                    "up_proj": {"kernel": sh["up_proj"]["kernel"][:, c]},
                    "down_proj": {"kernel": sh["down_proj"]["kernel"][c]}})

    return share, {k: cut(v) if k.startswith("layers_") else v
                   for k, v in params.items()}


@pytest.mark.parametrize("part", ["window_attention", "full_attention",
                                  "routed_experts", "shared_columns",
                                  "whole_layer"])
def test_the_eight_shares_add_up_to_the_uncut_layer(model, part):
    """The parts the eight shares give — one expert, one query head, eight
    of the 64 shared columns each; the norm and the router computed by every
    share alike and counted once — add up to what the uncut REFERENCE gives:
    both kinds of attention, the routed sum, the shared AVERAGE (a column
    slice of the experts side by side, so no share computes it eight times)
    and a whole layer's x + A + F."""
    cfg, params, _ = model
    arch, nx = arch_of(cfg), ref._Nx("float32")
    x = jax.random.normal(jax.random.key(2), (T, cfg.hidden_size), jnp.float32)
    shares = [_share_of(params, cfg, s, 8) for s in range(8)]
    i = 3 if part == "full_attention" else 0
    kind = cfg.layer_types[i]
    W = ref.Weights(named(params), f"params/layers_{i}/")
    angles = cm.rope_angles(cfg, jnp.arange(T)) if kind == ref.SLIDING else None
    with jax.default_matmul_precision("highest"):
        if part == "whole_layer":
            whole = ref.layer(W, arch, nx, x, kind)[0]
            outs = [cm._layer(c, p[f"layers_{i}"], x, angles)[0] - x
                    for c, p in shares]
            parts = x + sum(outs)
        else:
            u = ref._layer_norm(x, W("input_norm/scale"), cfg.layer_norm_eps)
            experts, gates = cm.route(params[f"layers_{i}"]["router"], cfg, u)
            routed, shared, _ = ref.moe_parts(W, arch, nx, u)
            outs = [ds.held_expert_ffn(p[f"layers_{i}"], u, experts, gates,
                                       c.experts_held)
                    for c, p in shares]
            if part == "routed_experts":
                whole, parts = routed, sum(o[0] for o in outs)
                assert abs(sum(float(o[2]["held_pair_share"]) for o in outs)
                           - 1.0) < 1e-6
            elif part == "shared_columns":
                # the columns' sums over the experts' count, as ``_layer``
                # averages them (the whole layer's case holds that line)
                n = cfg.num_shared_experts
                whole, parts = shared, sum(o[1] for o in outs) / n
                # one share alone is an eighth of the work, not the average
                assert float(jnp.max(jnp.abs(outs[0][1] / n - whole))) > 0.1 * float(
                    jnp.max(jnp.abs(whole)))
            else:
                whole = ref.attention_part(W.at("attn"), arch, nx, u, kind)
                parts = sum(cm.attention(p[f"layers_{i}"]["attn"], c, u,
                                         angles)[0] for c, p in shares)
    assert float(jnp.max(jnp.abs(parts - whole))) < 1e-4 * float(
        jnp.max(jnp.abs(whole)))


# ------------------------------------------ attention: which code, what band


def _walked(cfg, t_len, sliding=True):
    """What ``attention`` says its branch walked, from a trace of it at
    ``t_len`` tokens (nothing is computed)."""
    shapes = cm.param_shapes(cfg)["params"]["layers_0"]["attn"]
    p = jax.tree.map(lambda s: jax.ShapeDtypeStruct(s[0], jnp.bfloat16),
                     shapes, is_leaf=cm._is_spec)
    u = jax.ShapeDtypeStruct((t_len, cfg.hidden_size), jnp.bfloat16)
    angles = cm.rope_angles(cfg, jnp.arange(t_len)) if sliding else None
    said = []

    def run(p, u):
        y, walked = cm.attention(p, cfg, u, angles)
        said.append(walked)
        return y

    jax.eval_shape(run, p, u)
    return said[0]


def test_attention_takes_the_kernel_on_the_tpu_only(monkeypatch):
    """The counter comes from the branch that attends: the cell's share of
    a layer (16 query heads of 128 on one key / value head), 32768 tokens."""
    cfg = cm.Cohere2MoeConfig(num_hidden_layers=4,
                              layer_types=cm._PUBLISHED_LAYERS[:4],
                              heads_held=(0, 16))
    q = jax.ShapeDtypeStruct((32768, 16, 128), jnp.bfloat16)
    assert not cm._kernel_applies(q)  # the CPU
    # as XLA the sliding layers' row blocks read 16 + 4095 of 32768 keys
    assert _walked(cfg, 32768) == (16 + 4095) / 32768
    assert _walked(cfg, 32768, sliding=False) == 1.0
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert cm._kernel_applies(q)
    # the cell's shape: 540 of the 2080 causal pairs of 512 x 512 tiles
    assert _walked(cfg, 32768) == 540 / 2080
    assert _walked(cfg, 32768, sliding=False) == 1.0
    assert _walked(dataclasses.replace(cfg, sliding_window=32768), 32768) == 1.0
    assert not cm._kernel_applies(jax.ShapeDtypeStruct((500, 16, 128), jnp.bfloat16))
    assert not cm._kernel_applies(jax.ShapeDtypeStruct((512, 8, 16), jnp.bfloat16))


@pytest.mark.parametrize("kind", ["sliding_attention", "full_attention"])
def test_the_layer_on_the_kernel_pair_equals_the_xla_path(monkeypatch, kind):
    """``attention`` as the TPU dispatches it (the backend is what the test
    says, the pair in interpret mode, 128-row tiles) against the XLA path it
    takes here: 4 query heads of 128 on 1 key / value head, 256 tokens, a
    window of 100 — values and the gradient of ``q_proj``."""
    cfg = cm.Cohere2MoeConfig.tiny(
        head_dim=128, num_attention_heads=4, num_key_value_heads=1,
        heads_held=(0, 4), sliding_window=100)
    shapes = cm.param_shapes(cfg)["params"]["layers_0"]["attn"]
    ks = iter(jax.random.split(jax.random.key(3), 8))
    p = jax.tree.map(lambda s: jax.random.normal(next(ks), s[0], jnp.float32)
                     / s[0][0] ** 0.5, shapes, is_leaf=cm._is_spec)
    u = jax.random.normal(next(ks), (256, cfg.hidden_size), jnp.float32)
    angles = (cm.rope_angles(cfg, jnp.arange(256))
              if kind == "sliding_attention" else None)

    def run():
        return jax.value_and_grad(lambda p: jnp.sum(
            cm.attention(p, cfg, u, angles)[0] ** 2))(p)

    with jax.default_matmul_precision("highest"):
        want, want_g = run()
        monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
        monkeypatch.setattr(sa, "_TILES", ((128, 128),))
        real, calls = sa.causal_attention, []

        def interpreted(q, k, v, scale, window=None):
            calls.append(window)
            return real(q, k, v, scale, True, window)

        monkeypatch.setattr(cm, "causal_attention", interpreted)
        got, got_g = run()
    assert calls == [100 if kind == "sliding_attention" else None]
    assert abs(float(got) - float(want)) < 1e-4 * float(want)
    w = want_g["q_proj"]["kernel"]
    assert float(jnp.max(jnp.abs(got_g["q_proj"]["kernel"] - w))) < 1e-3 * float(
        jnp.max(jnp.abs(w)))


# ------------------------------------------------------------ the tuner


def test_the_shared_tuner_steps_on_this_loss(model):
    """``TrainState`` / ``loss_steps`` / ``next_token_loss`` as they are:
    four leaves train (every layer's ``q_proj``), the loss falls, the frozen
    leaves do not move, and the step hands out the four scalars."""
    cfg, params, ids = model
    tx = make_optimizer(TuneConfig(learning_rate=1e-2))
    state = TrainState.create(params, tx, TRAINABLE, master_dtype=jnp.float32)
    assert len(jax.tree.leaves(state.trainable)) == 4
    step_loss = next_token_loss(
        lambda p, doc: cm.forward_loss(p, cfg, doc, jnp.float32), ids[None])
    new, losses, aux = jax.jit(
        lambda s, k: loss_steps(step_loss, tx, s, k, num_steps=4))(
            state, jax.random.key(0))
    assert float(losses[-1]) < float(losses[0])
    assert set(aux) == set(cm.COUNTERS)
    assert all(v.shape == (4,) for v in aux.values())
    for a, b in zip(jax.tree.leaves(new.frozen), jax.tree.leaves(state.frozen)):
        assert np.array_equal(a, b)


def test_expert_rows_packed_survives_the_layers_mean(model, monkeypatch):
    """``expert_rows_packed`` reads 0 here, where the XLA loop runs, and the
    1 that ``held_expert_ffn`` hands out where the pair takes packed rows
    comes through the family's counters as it is (the
    rescale of ``routed_over_shared`` leaves it alone) — steered from the
    test: the dispatch reports packed tiles and the XLA loop stands in for
    the kernels, so the loss is the same number."""
    cfg, params, ids = model

    def run():
        return jax.jit(lambda p: cm.forward_loss(p, cfg, ids, jnp.float32))(
            params)

    loss, aux = run()
    assert float(aux["expert_rows_packed"]) == 0.0
    monkeypatch.setattr(ds, "_experts_kernel_tiles",
                        lambda *a: ExpertTiles(128, 0, 0, True))
    monkeypatch.setattr(ds, "grouped_experts_forward", ds._grouped_loop_fwd)
    steered, aux = run()
    assert float(aux["expert_rows_packed"]) == 1.0
    assert float(steered) == float(loss)
