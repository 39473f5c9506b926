"""The fifth token family (Falcon-H1-34B's parallel hybrid block,
``models/falcon_h1.py``) against its plain float32 reference
(``benchmark/reference/falcon_h1.py``: the state-space recurrence token by
token per group, full-row attention, every multiplier where the published
code puts it) on seeded weights at a small size — 64 wide, 2 layers, 4
query heads of 16 on 2 key / value heads, 8 Mamba heads of 8 in 2 groups
with a state of 16 in chunks of 8, a feed-forward of 96, 64 tokens — and
through the shared tuner.

The program runs in float32 here, so that what is compared is the
mathematics (chunked, grouped, folded multipliers) and not bfloat16
rounding; the one bfloat16 case has its own, looser tolerance.
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
from videop2p_tpu.models import falcon_h1 as fh
from videop2p_tpu.models import granite_hybrid as gh
from videop2p_tpu.train import (
    TrainState,
    TuneConfig,
    loss_steps,
    make_optimizer,
    next_token_loss,
)

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

from benchmark.reference import falcon_h1 as ref  # noqa: E402

TRAINABLE = ("q_proj", "in_proj_c")
HP = {"trainable_modules": list(TRAINABLE), "learning_rate": 1e-3,
      "adam_beta1": 0.9, "adam_beta2": 0.999, "adam_epsilon": 1e-8,
      "adam_weight_decay": 0.01, "max_grad_norm": 1.0}
T = 64


def arch_of(cfg: fh.FalconH1Config) -> dict:
    """The reference's ``arch`` for a program configuration."""
    d = dataclasses.asdict(cfg)
    return {k: d[k] for k in ref.ARCH_KEYS}


def named(params) -> dict:
    return {"params/" + "/".join(k): v
            for k, v in traverse_util.flatten_dict(params).items()}


@pytest.fixture(autouse=True)
def small_blocks(monkeypatch):
    """Several scan groups, attention row blocks, feed-forward blocks and
    loss chunks at 64 tokens: the modules' sizes are for 32768."""
    monkeypatch.setattr(gh, "SSD_GROUP", 2)
    monkeypatch.setattr(gh, "ATTN_ROWS", 16)
    monkeypatch.setattr(ds, "FFN_ROWS", 16)
    monkeypatch.setattr(ds, "LOSS_CHUNK", 32)


@pytest.fixture(scope="module")
def model():
    cfg = fh.FalconH1Config.tiny()
    # bfloat16-exact values (the checkpoint's dtype), held in float32
    params = jax.jit(lambda k: fh.init_params(k, cfg))(jax.random.key(5))
    params = jax.tree.map(lambda x: x.astype(jnp.float32), params["params"])
    ids = jax.random.randint(jax.random.key(1), (T,), 0, cfg.vocab_size)
    return cfg, params, ids


def _sizes(cfg):
    return {"/".join(str(getattr(k, "key", k)) for k in path): int(np.prod(s[0]))
            for path, s in jax.tree_util.tree_flatten_with_path(
                fh.param_shapes(cfg), is_leaf=fh._is_spec)[0]}


def test_published_defaults_and_param_count():
    """The defaults are the published config.json: 430.12 M values a layer
    (attention 31.46 M, the Mamba mixer 68.35 M, the feed-forward
    330.30 M); the benchmark's cut (4 whole layers, 1/8 of the vocabulary
    for the embedding and the untied head) holds 2.055 B values, 62.9 M of
    them trainable (``q_proj`` and ``in_proj_c``)."""
    cfg = fh.FalconH1Config()
    cfg.check()
    assert (cfg.hidden_size, cfg.head_dim, cfg.intermediate_size) == (5120, 128, 21504)
    assert (cfg.num_attention_heads, cfg.num_key_value_heads) == (20, 4)
    assert (cfg.mamba_n_heads, cfg.mamba_d_head, cfg.mamba_d_ssm) == (32, 128, 4096)
    assert (cfg.mamba_n_groups, cfg.mamba_d_state, cfg.mamba_chunk_size) == (2, 256, 128)
    assert (cfg.rope_theta, cfg.num_hidden_layers, cfg.vocab_size) == (1e11, 72, 261120)
    layer = {k.split("/", 2)[2]: n for k, n in _sizes(cfg).items()
             if k.startswith("params/layers_0/")}
    part = lambda pre: sum(n for k, n in layer.items()  # noqa: E731
                           if k.startswith(pre))
    assert round(part("attn/") / 1e6, 2) == 31.46
    assert round(part("mamba/") / 1e6, 2) == 68.35
    assert round(part("mlp/") / 1e6, 2) == 330.30
    assert round(sum(layer.values()) / 1e6, 2) == 430.12
    cut = dataclasses.replace(cfg, num_hidden_layers=4, vocab_size=32640)
    sizes = _sizes(cut)
    assert round(sum(sizes.values()) / 1e9, 3) == 2.055
    trained = sum(n for k, n in sizes.items()
                  if "/q_proj/" in k or "/in_proj_c/" in k)
    assert round(trained / 1e6, 1) == 62.9


@pytest.mark.parametrize("key", ["ssd_group", "ffn_rows", "use_pallas",
                                 "n_group", "experts_held"])
def test_config_from_dict_rejects_unknown_keys(key):
    """How the work is cut is not configuration (module constants), and
    neither is which code attends."""
    with pytest.raises(ValueError, match="unknown FalconH1Config keys"):
        fh.FalconH1Config.from_dict({"hidden_size": 64, key: 4})


@pytest.mark.parametrize("field,value", [
    ("mamba_norm_before_gate", True), ("mamba_rms_norm", False),
    ("mamba_use_mlp", False), ("mamba_conv_bias", False),
    ("mamba_proj_bias", True), ("attention_bias", True), ("mlp_bias", True),
    ("projectors_bias", True), ("tie_word_embeddings", True),
    ("attn_layer_indices", (0,)), ("rope_scaling", {"type": "yarn"}),
    ("mamba_n_groups", 3), ("mamba_d_ssm", 72), ("num_key_value_heads", 3),
    ("hand_out_choices", True)])
def test_config_check_refuses_what_is_not_built(field, value):
    with pytest.raises(AssertionError):
        fh.FalconH1Config.tiny(**{field: value}).check()


# ------------------------------------------------------------ the grouped scan


@pytest.mark.parametrize("t_len", [8, 24, 64])
def test_grouped_scan_equals_the_recurrence(t_len):
    """``granite_hybrid.ssd_scan`` with B / C in 2 groups (heads 0-3 read
    group 0, heads 4-7 group 1) against the reference's token-by-token
    recurrence, for one chunk and for several whose states are handed on:
    the outputs to float32 rounding, the last state against the recurrence's
    own, and the handed-over term's mean square against the outputs less
    the same tokens' from a zero state."""
    heads, width, n, groups, chunk = 8, 8, 16, 2, 8
    ks = iter(jax.random.split(jax.random.key(7), 8))
    x = jax.random.normal(next(ks), (t_len, heads, width), jnp.float32)
    dt = jax.nn.softplus(jax.random.normal(next(ks), (t_len, heads)) - 1.0)
    a = -jnp.exp(jax.random.uniform(next(ks), (heads,), minval=0.0, maxval=1.0))
    b = jax.random.normal(next(ks), (t_len, groups, n), jnp.float32)
    c = jax.random.normal(next(ks), (t_len, groups, n), jnp.float32)
    with jax.default_matmul_precision("highest"):
        y, last, handed_sq = jax.jit(gh.ssd_scan, static_argnums=5)(
            x, dt, a, b, c, chunk)
        group_of = jnp.arange(heads) // (heads // groups)
        scanned = (x * dt[..., None], dt * a[None, :], b, c)
        want = ref.recurrence(*scanned, group_of)
        tail = t_len - chunk
        handed = want[tail:] - ref.recurrence(
            *(s[tail:] for s in scanned), group_of)
    scale = float(jnp.max(jnp.abs(want)))
    assert float(jnp.max(jnp.abs(y - want))) < 1e-5 * scale
    # the last state: h_T = sum_t exp(sum_{s > t} dt_s a) dt_t x_t (x) b_t
    decay = jnp.exp(jnp.cumsum((dt * a)[::-1], axis=0)[::-1] - dt * a)
    b_heads = b[:, group_of]
    h_last = jnp.einsum("th,thp,thn->hpn", decay * dt, x, b_heads,
                        precision="highest")
    assert float(jnp.max(jnp.abs(last - h_last))) < 1e-5 * float(
        jnp.max(jnp.abs(h_last)))
    if t_len == chunk:
        assert float(handed_sq) == 0.0
    else:
        got, want_sq = float(handed_sq), float(jnp.mean(handed ** 2))
        assert want_sq > 0 and abs(got - want_sq) < 1e-4 * want_sq
    # each head reads its own group: the one-group scan of a group's heads
    for g in range(groups):
        own = slice(g * heads // groups, (g + 1) * heads // groups)
        with jax.default_matmul_precision("highest"):
            y1 = gh.ssd_scan(x[:, own], dt[:, own], a[own], b[:, g], c[:, g],
                             chunk)[0]
        assert float(jnp.max(jnp.abs(y1 - y[:, own]))) < 1e-5 * scale


def test_gated_norm_takes_each_groups_mean_square():
    """``mamba_out_part`` given (T, 2) mean squares normalises each half of
    the channels by its own: the same as normalising the halves apart, and
    not the same as one mean square over all channels (unless the two
    agree)."""
    cfg = fh.FalconH1Config.tiny()
    d, h = cfg.mamba_d_ssm, cfg.hidden_size
    ks = iter(jax.random.split(jax.random.key(2), 4))
    g = jax.random.normal(next(ks), (16, d)) * jnp.concatenate(
        [jnp.ones(d // 2), 3.0 * jnp.ones(d // 2)])
    p = {"norm": {"scale": 1.0 + 0.1 * jax.random.normal(next(ks), (d,))},
         "out_proj": {"kernel": jax.random.normal(next(ks), (d, h))}}
    halves = g.reshape(16, 2, d // 2)
    ms = jnp.mean(halves ** 2, axis=-1)
    with jax.default_matmul_precision("highest"):
        got = gh.mamba_out_part(p, cfg, g, ms)
        normed = (halves * jax.lax.rsqrt(ms[..., None] + cfg.rms_norm_eps)
                  ).reshape(16, d) * p["norm"]["scale"]
        want = normed @ p["out_proj"]["kernel"]
        whole = gh.mamba_out_part(p, cfg, g, jnp.mean(g ** 2, -1, keepdims=True))
    assert float(jnp.max(jnp.abs(got - want))) < 1e-5 * float(jnp.max(jnp.abs(want)))
    assert float(jnp.max(jnp.abs(whole - want))) > 0.1 * float(jnp.max(jnp.abs(want)))


# ------------------------------------------------------- program / reference


def _reference_first_step(cfg, params, ids, **how):
    return ref.loss_and_grads(named(params), arch_of(cfg), TRAINABLE, ids, **how)


def test_logits_loss_gradients_and_counters_match_reference(model):
    """Float32 on both sides, differing by the order of float32 sums and
    by the chunked scan against the recurrence: 1e-4 of the logits' scale,
    1e-5 of the loss, 2e-4 of each trainable leaf's largest gradient (both
    layers' ``q_proj`` and ``in_proj_c``: the backward crosses the
    feed-forward, both branches and the scan), and 1e-4 on the two
    counters."""
    cfg, params, ids = model
    arch, flat = arch_of(cfg), named(params)
    loss_ref, readings, want = _reference_first_step(cfg, params, ids)
    (loss, aux), got = jax.jit(jax.value_and_grad(
        lambda p: fh.forward_loss(p, cfg, ids, jnp.float32), has_aux=True))(params)
    assert abs(float(loss) - float(loss_ref)) < 1e-5 * float(loss_ref)
    got = named(got)
    assert sorted(want) == sorted(k for k in got if ref.is_trainable(k, TRAINABLE))
    assert len(want) == 4
    for k, w in want.items():
        scale = float(jnp.max(jnp.abs(w)))
        assert scale > 0, k
        assert float(jnp.max(jnp.abs(got[k] - w))) < 2e-4 * scale, k
    logits = jax.jit(lambda f: ref.logits(f, arch, ids))(flat)
    mine = jax.jit(lambda p: fh.forward_logits(p, cfg, ids, jnp.float32))(params)
    assert float(jnp.max(jnp.abs(mine - logits))) < 1e-4 * float(
        jnp.max(jnp.abs(logits)))
    assert set(aux) == set(fh.COUNTERS)
    for name, key in (("ssd_state_rms", "state_rms"),
                      ("branch_rms_ratio", "branch_rms_ratio")):
        theirs = float(np.mean([r[key] for r in readings]))
        assert theirs > 0 and abs(float(aux[name]) - theirs) < 1e-4 * theirs, name


def test_one_step_new_state_matches_reference(model):
    """One clipped AdamW step through the shared tuner against the
    reference's ``tune``: every trainable leaf's new value within 1e-3 of
    its change (Adam normalises each coordinate's step, so rounding in a
    tiny gradient shows at the step's own scale)."""
    cfg, params, ids = model
    tx = make_optimizer(TuneConfig(learning_rate=HP["learning_rate"]))
    state = TrainState.create(params, tx, TRAINABLE, master_dtype=jnp.float32)
    step_loss = next_token_loss(
        lambda p, doc: fh.forward_loss(p, cfg, doc, jnp.float32), ids[None])
    new, losses, _ = jax.jit(
        lambda s, k: loss_steps(step_loss, tx, s, k, num_steps=1))(
            state, jax.random.key(0))
    theirs = ref.tune(dict(named(params)), arch_of(cfg), HP, ids, 1)
    mine = named(new.trainable)
    init = named(params)
    assert abs(float(losses[0]) - float(theirs["losses"][0])) < 1e-5 * float(
        theirs["losses"][0])
    for k, v in theirs["trainable"].items():
        change = float(jnp.max(jnp.abs(v - init[k])))
        assert change > 0, k
        assert float(jnp.max(jnp.abs(mine[k] - v))) < 1e-3 * change, k


@pytest.mark.parametrize("silent", ["attention_out_multiplier",
                                    "ssm_out_multiplier"])
def test_a_silenced_branch_leaves_the_other_alone(model, silent):
    """The two branches read the same normed input side by side: with one
    out-multiplier 0 a layer is x + the other branch + the feed-forward of
    what that leaves, to float32 rounding."""
    cfg, params, ids = model
    cfg = dataclasses.replace(cfg, **{silent: 0.0})
    p = params["layers_0"]
    x = jax.random.normal(jax.random.key(4), (T, cfg.hidden_size), jnp.float32)
    angles = fh.rope_angles(cfg, jnp.arange(T))

    def alone(p, x):
        u = ds._rms_norm(x, p["input_norm"]["scale"], cfg.rms_norm_eps)
        other = (fh.attention(p["attn"], cfg, u, angles)
                 if silent == "ssm_out_multiplier"
                 else fh.mixer(p["mamba"], cfg, u)[0])
        x = x + other
        y = ds._rms_norm(x, p["pre_ff_norm"]["scale"], cfg.rms_norm_eps)
        return x + fh.dense_ffn(p["mlp"], cfg, y)

    with jax.default_matmul_precision("highest"):
        got = jax.jit(lambda p, x: fh._layer(cfg, p, x, angles)[0])(p, x)
        want = jax.jit(alone)(p, x)
    assert float(jnp.max(jnp.abs(got - want))) < 1e-5 * float(jnp.max(jnp.abs(want)))


def test_the_vocabulary_slice_is_the_whole_vocabulary_here(model):
    """A sliced vocabulary is a smaller one: ids are drawn from the slice,
    the logits are (T, rows held), and the loss is the cross-entropy over
    those rows alone."""
    cfg, params, ids = model
    logits = jax.jit(lambda p: fh.forward_logits(p, cfg, ids, jnp.float32))(params)
    assert logits.shape == (T, cfg.vocab_size) == (T, params["head"]["kernel"].shape[1])
    assert int(jnp.max(ids)) < cfg.vocab_size
    nll = (jax.nn.logsumexp(logits, -1)
           - jnp.take_along_axis(logits, jnp.roll(ids, -1)[:, None], -1)[:, 0])
    loss, _ = jax.jit(lambda p: fh.forward_loss(p, cfg, ids, jnp.float32))(params)
    assert abs(float(jnp.mean(nll[:-1])) - float(loss)) < 1e-5 * float(loss)


@pytest.mark.parametrize("fault", ref.FAULTS)
def test_every_planted_fault_moves_the_reference(model, fault):
    """Each fault of the reference changes the first step's loss, gradient
    or counters at this size."""
    cfg, params, ids = model
    loss0, read0, g0 = _reference_first_step(cfg, params, ids)
    loss1, read1, g1 = _reference_first_step(cfg, params, ids, fault=fault)
    moved = max(float(jnp.max(jnp.abs(g1[k] - g0[k]))
                      / jnp.max(jnp.abs(g0[k]))) for k in g0)
    counters = max(abs(r1[k] - r0[k]) / r0[k]
                   for r0, r1 in zip(read0, read1) for k in r0)
    assert (moved > 1e-3 or counters > 1e-3
            or abs(float(loss1) - float(loss0)) > 1e-3 * float(loss0)), fault


def test_bfloat16_forward_is_close(model):
    """The dtype the cell runs in: bfloat16 operands against the float32
    reference at this size — a loss within 2 %, no more is claimed here."""
    cfg, params, ids = model
    loss_ref = _reference_first_step(cfg, params, ids)[0]
    loss, aux = jax.jit(lambda p: fh.forward_loss(
        jax.tree.map(lambda x: x.astype(jnp.bfloat16), p), cfg, ids))(params)
    assert abs(float(loss) - float(loss_ref)) < 0.02 * float(loss_ref)
    assert all(np.isfinite(float(aux[k])) for k in fh.COUNTERS)


def test_attention_takes_the_kernel_on_the_tpu_only(monkeypatch):
    """The cell's layer — 20 query heads of 128 on 4 key / value heads,
    32768 tokens — is the kernel pair's shape; the CPU is the XLA path's."""
    q = jax.ShapeDtypeStruct((32768, 20, 128), jnp.bfloat16)
    assert not gh._kernel_applies(q)
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert gh._kernel_applies(q)


def test_the_shared_tuner_steps_on_this_loss(model):
    """``TrainState`` / ``loss_steps`` / ``next_token_loss`` as they are:
    four leaves train (every layer's ``q_proj`` and ``in_proj_c``), the loss
    falls, the frozen leaves do not move, and the step hands out the two
    counters."""
    cfg, params, ids = model
    tx = make_optimizer(TuneConfig(learning_rate=1e-2))
    state = TrainState.create(params, tx, TRAINABLE, master_dtype=jnp.float32)
    assert len(jax.tree.leaves(state.trainable)) == 4
    step_loss = next_token_loss(
        lambda p, doc: fh.forward_loss(p, cfg, doc, jnp.float32), ids[None])
    new, losses, aux = jax.jit(
        lambda s, k: loss_steps(step_loss, tx, s, k, num_steps=4))(
            state, jax.random.key(0))
    assert float(losses[-1]) < float(losses[0])
    assert set(aux) == set(fh.COUNTERS)
    assert all(v.shape == (4,) for v in aux.values())
    for a, b in zip(jax.tree.leaves(new.frozen), jax.tree.leaves(state.frozen)):
        assert np.array_equal(a, b)
