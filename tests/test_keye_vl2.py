"""The fourth token family (Keye-VL-2.0-30B-A3B's language model,
``models/keye_vl2.py``) against its plain float32 reference
(``benchmark/reference/keye_vl2.py``: explicit selection masks from a full
sort, mRoPE from three position rows, a dense expert loop) on seeded weights
at a small size — 64 wide, 2 layers, 8 query heads of 16 on 2 key / value
heads, 4 index heads of 8 picking 16 keys, 8 experts of 32 top-3, 64
tokens — and through the shared tuner.

The program runs in float32 here, so that what is compared is the
mathematics (chunked, grouped, sorted, looped) and not bfloat16 rounding;
the one bfloat16 case has its own, looser tolerance.
"""

import dataclasses
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from flax import traverse_util

from videop2p_tpu.models import deepseek as ds
from videop2p_tpu.models import keye_vl2 as ky
from videop2p_tpu.ops import selected_attention as sa
from videop2p_tpu.train import (
    TrainState,
    TuneConfig,
    loss_steps,
    make_optimizer,
    next_token_loss,
)

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

from benchmark.reference import keye_vl2 as ref  # noqa: E402

TRAINABLE = ("q_proj",)
T = 64


def arch_of(cfg: ky.KeyeVL2Config) -> dict:
    """The reference's ``arch`` for a program configuration."""
    d = dataclasses.asdict(cfg)
    arch = {k: d[k] for k in ref.ARCH_KEYS}
    arch.update(mrope_section=cfg.mrope_section,
                indexer_num_heads=cfg.indexer_num_heads,
                indexer_head_dim=cfg.indexer_head_dim, topk=cfg.index_topk,
                experts_held=cfg.experts_held)
    return arch


def named(params) -> dict:
    return {"params/" + "/".join(k): v
            for k, v in traverse_util.flatten_dict(params).items()}


@pytest.fixture(autouse=True)
def small_chunks(monkeypatch):
    """Two scorer / attention chunks, several row blocks, expert blocks and
    loss chunks at 64 tokens: the module's sizes are for 16384."""
    for name, value in dict(Q_CHUNK=32, ATTN_ROWS=16, INDEX_ROWS=8,
                            EXPERT_BLOCK=8, LOSS_CHUNK=32).items():
        monkeypatch.setattr(ds, name, value)


@pytest.fixture(scope="module")
def model():
    cfg = ky.KeyeVL2Config.tiny()
    # bfloat16-exact values (the checkpoint's dtype), held in float32
    params = jax.jit(lambda k: ky.init_params(k, cfg))(jax.random.key(5))
    params = jax.tree.map(lambda x: x.astype(jnp.float32), params["params"])
    ids = jax.random.randint(jax.random.key(1), (T,), 0, cfg.vocab_size)
    return cfg, params, ids


def _sizes(cfg):
    return {"/".join(str(getattr(k, "key", k)) for k in path): int(np.prod(s[0]))
            for path, s in jax.tree_util.tree_flatten_with_path(
                ky.param_shapes(cfg), is_leaf=ky._is_spec)[0]}


def test_published_defaults_and_param_count():
    """The defaults are the published config.json: 625.38 M values a layer
    (attention 18.87 M, scorer 2.26 M, router 0.26 M, 128 experts of
    4.72 M); the benchmark's cut (4 whole layers, 1/8 of the vocabulary)
    holds 2.579 B values, 33.6 M of them trainable."""
    cfg = ky.KeyeVL2Config()
    cfg.check()
    assert (cfg.hidden_size, cfg.head_dim, cfg.moe_intermediate_size) == (2048, 128, 768)
    assert (cfg.num_attention_heads, cfg.num_key_value_heads) == (32, 4)
    assert (cfg.num_experts, cfg.num_experts_per_tok, cfg.num_hidden_layers) == (
        128, 8, 48)
    assert (cfg.indexer_num_heads, cfg.indexer_head_dim, cfg.index_topk) == (16, 64, 2048)
    assert (cfg.rope_theta, cfg.mrope_section, cfg.vocab_size) == (
        1e7, (16, 24, 24), 151936)
    layer = {k.split("/", 2)[2]: n for k, n in _sizes(cfg).items()
             if k.startswith("params/layers_0/")}
    part = lambda pre: sum(n for k, n in layer.items()  # noqa: E731
                           if k.startswith(pre))
    assert round(part("experts/") / 128 / 1e6, 2) == 4.72
    assert round(sum(layer.values()) / 1e6, 2) == 625.38
    assert round(part("attn/indexer/") / 1e6, 2) == 2.26
    assert round((part("attn/") - part("attn/indexer/")) / 1e6, 2) == 18.87
    assert round(part("router/") / 1e6, 2) == 0.26
    cut = dataclasses.replace(cfg, num_hidden_layers=4, vocab_size=18992)
    sizes = _sizes(cut)
    assert round(sum(sizes.values()) / 1e9, 3) == 2.579
    assert round(sum(n for k, n in sizes.items() if "/q_proj/" in k) / 1e6, 1) == 33.6


@pytest.mark.parametrize("key", ["attention_kernel", "use_pallas", "attn_rows",
                                 "expert_block", "n_shared_experts"])
def test_config_from_dict_rejects_unknown_keys(key):
    """How the work is cut is not configuration (module constants), and
    neither is which code attends."""
    with pytest.raises(ValueError, match="unknown KeyeVL2Config keys"):
        ky.KeyeVL2Config.from_dict({"hidden_size": 64, key: 4})
    with pytest.raises(ValueError, match="unknown KeyeVL2Config keys"):
        ky.KeyeVL2Config.from_dict({"sa_config": {"topk": 8, key: 4}})


@pytest.mark.parametrize("field,value", [
    ("decoder_sparse_step", 2), ("mlp_only_layers", (0,)),
    ("indexer_num_kv_heads", 2), ("norm_topk_prob", False),
    ("tie_word_embeddings", True), ("attention_bias", True),
    ("use_sliding_window", True), ("rope_type", "yarn"),
    ("mrope_section", (2, 3, 4)), ("num_local_experts", 16),
    ("experts_held", (4, 8)), ("num_key_value_heads", 3)])
def test_config_check_refuses_what_is_not_built(field, value):
    with pytest.raises(AssertionError):
        ky.KeyeVL2Config.tiny(**{field: value}).check()


def test_from_dict_reads_the_nested_groups_as_published():
    cfg = ky.KeyeVL2Config.from_dict({
        "rope_scaling": {"mrope_section": [16, 24, 24], "rope_type": "default",
                         "type": "default"},
        "sa_config": {"indexer_head_dim": 64, "indexer_num_heads": 16,
                      "indexer_num_kv_heads": 1, "kv_chunk_size": 512,
                      "q_chunk_size": 512, "topk": 2048}})
    assert cfg == ky.KeyeVL2Config()


# ------------------------------------------------------------------ mRoPE


def test_mrope_at_equal_positions_is_the_ordinary_rotary_to_the_bit():
    """A text token's three position rows are equal: the program's angles
    from three rows, from one, and ``position * theta^(-2 i / D)`` are the
    same bits, and so is the reference's (three sections of its own
    rows); at unequal rows each section turns with its own row."""
    cfg = ky.KeyeVL2Config()
    pos = jnp.arange(4096)
    freqs = 1.0 / (cfg.rope_theta ** (jnp.arange(0, 128, 2, dtype=jnp.float32) / 128))
    plain = pos.astype(jnp.float32)[:, None] * freqs[None, :]
    three = ky.rope_angles(cfg, jnp.stack([pos] * 3))
    assert bool(jnp.array_equal(three, plain))
    assert bool(jnp.array_equal(ky.rope_angles(cfg, pos), plain))
    x = jax.random.normal(jax.random.key(0), (4096, 4, 128), jnp.float32)
    assert bool(jnp.array_equal(ds._rotate(x, three, interleaved=False),
                                ds._rotate(x, plain, interleaved=False)))
    mine = ref.rope_angles({"head_dim": 128, "rope_theta": cfg.rope_theta,
                            "mrope_section": cfg.mrope_section}, 4096)
    assert float(jnp.max(jnp.abs(mine - plain) / (plain + 1))) < 1e-6
    rows = jnp.stack([pos, 2 * pos, 3 * pos])
    apart = ky.rope_angles(cfg, rows)
    for lo, hi, row in ((0, 16, 1), (16, 40, 2), (40, 64, 3)):
        own = (row * pos).astype(jnp.float32)[:, None] * freqs[None, lo:hi]
        assert bool(jnp.array_equal(apart[:, lo:hi], own))


# ------------------------------------------------------- program / reference


def _reference_first_step(cfg, params, ids, **how):
    flat = named(params)
    trainable = {k: v for k, v in flat.items() if ref.is_trainable(k, TRAINABLE)}
    frozen = {k: v for k, v in flat.items() if k not in trainable}
    grads, _ = ref.layerwise_grads(arch_of(cfg), **how)
    return grads(trainable, frozen, ids)


def test_logits_loss_and_gradients_match_reference(model):
    """Float32 on both sides: the two sides select the same keys and the
    same experts (ties with the 16th score kept on both), and then differ by
    the order of float32 sums — 1e-4 of the logits' scale, 1e-5 on the
    loss, 2e-4 of each trainable leaf's gradient (both layers' ``q_proj``:
    the backward crosses the experts and the selected attention)."""
    cfg, params, ids = model
    arch, flat = arch_of(cfg), named(params)
    loss_ref, choices, want = _reference_first_step(cfg, params, ids)
    (loss, aux), got = jax.jit(jax.value_and_grad(
        lambda p: ky.forward_loss(p, dataclasses.replace(
            cfg, hand_out_choices=True), ids, jnp.float32), has_aux=True))(params)
    for mine, theirs in zip(aux["choices"], choices):
        assert np.array_equal(mine["mask"], theirs["mask"])
        assert np.array_equal(np.sort(mine["experts"], -1),
                              np.sort(theirs["experts"], -1))
    assert abs(float(loss) - float(loss_ref)) < 1e-5 * float(loss_ref)
    got = named(got)
    assert sorted(want) == sorted(k for k in got if ref.is_trainable(k, TRAINABLE))
    assert len(want) == 2
    for k, w in want.items():
        scale = float(jnp.max(jnp.abs(w)))
        assert scale > 0, k
        assert float(jnp.max(jnp.abs(got[k] - w))) < 2e-4 * scale, k
    logits = jax.jit(lambda f: ref.logits(f, arch, ids))(flat)
    mine = jax.jit(lambda p: ky.forward_logits(p, cfg, ids, jnp.float32))(params)
    assert float(jnp.max(jnp.abs(mine - logits))) < 1e-4 * float(
        jnp.max(jnp.abs(logits)))
    assert set(aux) == set(ky.COUNTERS) | {"choices"}
    assert float(aux["held_pair_share"]) == 1.0
    # 16 keys of up to 64: the first 16 queries keep every earlier key
    assert float(aux["keys_selected_mean"]) >= (16 * 17 / 2 + 48 * 16) / 64


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
    loss, aux = jax.jit(lambda p: ky.forward_loss(
        jax.tree.map(lambda x: x.astype(jnp.bfloat16), p), cfg, ids))(params)
    assert abs(float(loss) - float(loss_ref)) < 0.02 * float(loss_ref)
    assert all(np.isfinite(float(aux[k])) for k in ky.COUNTERS)


# ------------------------------------------------ attention: which code


def test_attention_takes_the_kernel_on_the_tpu_only(monkeypatch):
    """The cell's layer: 32 query heads of 128 on 4 key / value heads,
    16384 tokens, is the kernel pair's shape; the CPU, a length no tile
    divides and the tiny widths are the XLA path's."""
    q = jax.ShapeDtypeStruct((16384, 32, 128), jnp.bfloat16)
    assert not ky._kernel_applies(q)  # the CPU
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert ky._kernel_applies(q)
    assert not ky._kernel_applies(jax.ShapeDtypeStruct((500, 32, 128), jnp.bfloat16))
    assert not ky._kernel_applies(jax.ShapeDtypeStruct((512, 8, 16), jnp.bfloat16))


def test_the_layer_on_the_kernel_pair_equals_the_xla_path(monkeypatch):
    """``attention`` as the TPU dispatches it (the backend is what the test
    says, the pair in interpret mode, 128-row tiles) against the XLA path it
    takes here: 8 query heads of 128 on 2 key / value heads, 256 tokens, a
    selection of 40 keys a query — values and the gradient of ``q_proj``."""
    cfg = ky.KeyeVL2Config.tiny(head_dim=128, mrope_section=(16, 24, 24))
    shapes = ky.param_shapes(cfg)["params"]["layers_0"]["attn"]
    ks = iter(jax.random.split(jax.random.key(3), 16))
    p = jax.tree.map(lambda s: jax.random.normal(next(ks), s[0], jnp.float32)
                     / s[0][0] ** 0.5, shapes, is_leaf=ky._is_spec)
    u = jax.random.normal(next(ks), (256, cfg.hidden_size), jnp.float32)
    angles = ky.rope_angles(cfg, jnp.arange(256))
    score = jax.random.normal(next(ks), (256, 256))
    causal = jnp.tril(jnp.ones((256, 256), bool))
    score = jnp.where(causal, score, -jnp.inf)
    mask = causal & (score >= jnp.sort(score, -1)[:, -40][:, None])
    monkeypatch.setattr(ds, "Q_CHUNK", 128)
    monkeypatch.setattr(ds, "ATTN_ROWS", 64)

    def run():
        return jax.value_and_grad(lambda p: jnp.sum(
            ky.attention(p, cfg, u, angles, mask) ** 2))(p)

    with jax.default_matmul_precision("highest"):
        want, want_g = run()
        monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
        monkeypatch.setattr(sa, "_TILES", ((128, 128),))
        real, calls = sa.grouped_selected_attention, []

        def interpreted(q, k, v, mask, scale):
            calls.append(q.shape)
            return real(q, k, v, mask, scale, True)

        monkeypatch.setattr(ky, "grouped_selected_attention", interpreted)
        got, got_g = run()
    assert calls == [(256, 8, 128)]
    assert abs(float(got) - float(want)) < 1e-4 * float(want)
    w = want_g["q_proj"]["kernel"]
    assert float(jnp.max(jnp.abs(got_g["q_proj"]["kernel"] - w))) < 1e-3 * float(
        jnp.max(jnp.abs(w)))


# ------------------------------------------------------------ the tuner


def test_the_shared_tuner_steps_on_this_loss(model):
    """``TrainState`` / ``loss_steps`` / ``next_token_loss`` as they are:
    two leaves train (every layer's ``q_proj``, not the scorer's ``wq_b``),
    the loss falls, the frozen leaves do not move, and the step hands out
    the three scalars."""
    cfg, params, ids = model
    tx = make_optimizer(TuneConfig(learning_rate=1e-2))
    state = TrainState.create(params, tx, TRAINABLE, master_dtype=jnp.float32)
    assert len(jax.tree.leaves(state.trainable)) == 2
    step_loss = next_token_loss(
        lambda p, doc: ky.forward_loss(p, cfg, doc, jnp.float32), ids[None])
    new, losses, aux = jax.jit(
        lambda s, k: loss_steps(step_loss, tx, s, k, num_steps=4))(
            state, jax.random.key(0))
    assert float(losses[-1]) < float(losses[0])
    assert set(aux) == set(ky.COUNTERS)
    assert all(v.shape == (4,) for v in aux.values())
    for a, b in zip(jax.tree.leaves(new.frozen), jax.tree.leaves(state.frozen)):
        assert np.array_equal(a, b)
