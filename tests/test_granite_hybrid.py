"""The hybrid token model (Granite-4.0-H-Small's block,
``models/granite_hybrid.py``) against its plain float32 reference
(``benchmark/reference/granite_moe_hybrid.py``: the recurrence token by
token, no chunk) on seeded weights at a small size — 64 wide, mamba /
attention / mamba, 16 Mamba heads of 8 with a state of 16 in chunks of 8,
8 query heads on 4 key / value heads, 8 experts, top-3, 64 tokens — through
the shared tuner, and share by share against the uncut layer.

The program runs in float32 here, so that what is compared is the
mathematics (chunked, grouped, sorted, looped) and not bfloat16 rounding.
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

from benchmark.reference import granite_moe_hybrid as ref  # noqa: E402

TRAINABLE = ("q_proj", "in_proj_c")
T = 64


def arch_of(cfg: gh.GraniteHybridConfig) -> dict:
    """The reference's ``arch`` for a program configuration."""
    d = dataclasses.asdict(cfg)
    arch = {k: d[k] for k in ref.ARCH_KEYS}
    arch.update(num_local_experts=cfg.num_local_experts, head_dim=cfg.head_dim,
                experts_held=cfg.experts_held, heads_held=cfg.heads_held,
                kv_heads_held=cfg.kv_heads_held,
                mamba_heads_held=cfg.mamba_heads_held)
    return arch


def named(params) -> dict:
    return {"params/" + "/".join(k): v
            for k, v in traverse_util.flatten_dict(params).items()}


@pytest.fixture(autouse=True)
def small_blocks(monkeypatch):
    """Several groups, row blocks and expert blocks at 64 tokens."""
    monkeypatch.setattr(gh, "SSD_GROUP", 2)
    monkeypatch.setattr(gh, "ATTN_ROWS", 16)
    for name, value in dict(FFN_ROWS=32, EXPERT_BLOCK=8, LOSS_CHUNK=32).items():
        monkeypatch.setattr(ds, name, value)


@pytest.fixture(scope="module")
def model():
    cfg = gh.GraniteHybridConfig.tiny()
    # bfloat16-exact values (the checkpoint's dtype), held in float32
    params = jax.jit(lambda k: gh.init_params(k, cfg))(jax.random.key(5))
    params = jax.tree.map(lambda x: x.astype(jnp.float32), params["params"])
    ids = jax.random.randint(jax.random.key(1), (T,), 0, cfg.vocab_size)
    return cfg, params, ids


def test_published_defaults_and_param_count():
    """The defaults are the published config.json; the benchmark's cut (one
    chip of the 4 that share each layer, one period of layer_types) holds
    2.241 B values (ISSUE 32's arithmetic)."""
    cfg = gh.GraniteHybridConfig()
    cfg.check()
    assert (cfg.hidden_size, cfg.intermediate_size,
            cfg.shared_intermediate_size) == (4096, 768, 1536)
    assert (cfg.mamba_n_heads, cfg.mamba_d_head, cfg.mamba_d_state,
            cfg.mamba_d_conv, cfg.mamba_chunk_size) == (128, 64, 128, 4, 256)
    assert (cfg.num_local_experts, cfg.num_experts_per_tok) == (72, 10)
    assert cfg.layer_types.count("attention") == 4 and cfg.head_dim == 128
    assert [i for i, k in enumerate(cfg.layer_types) if k == "attention"] == [
        5, 15, 25, 35]
    cut = dataclasses.replace(
        cfg, num_hidden_layers=10, layer_types=cfg.layer_types[:10],
        experts_held=(0, 18), heads_held=(0, 8), mamba_heads_held=(0, 32),
        vocab_size=25088)
    assert cut.kv_heads_held == (0, 2)
    sizes = {"/".join(str(getattr(k, "key", k)) for k in path): int(np.prod(s[0]))
             for path, s in jax.tree_util.tree_flatten_with_path(
                 gh.param_shapes(cut), is_leaf=gh._is_spec)[0]}
    assert round(sum(sizes.values()) / 1e6) == 2241
    trainable = sum(n for k, n in sizes.items()
                    if "/q_proj/" in k or "/in_proj_c/" in k)
    assert round(trainable / 1e6, 1) == 8.9


@pytest.mark.parametrize("key", ["ssd_group", "attention_kernel", "use_pallas",
                                 "expert_block", "scan_kernel"])
def test_config_from_dict_rejects_unknown_keys(key):
    """How the work is cut is not configuration (module constants), and
    neither is which code attends or scans."""
    with pytest.raises(ValueError, match="unknown GraniteHybridConfig keys"):
        gh.GraniteHybridConfig.from_dict({"hidden_size": 64, key: 4})


@pytest.mark.parametrize("field,value", [
    ("mamba_n_groups", 2), ("position_embedding_type", "rope"),
    ("tie_word_embeddings", False), ("mamba_proj_bias", True),
    ("heads_held", (1, 2))])
def test_config_check_refuses_what_is_not_built(field, value):
    with pytest.raises(AssertionError):
        gh.GraniteHybridConfig.tiny(**{field: value}).check()


# ------------------------------------------------------------------ the scan


def _scan_inputs(t_len, heads=3, width=4, state=5, seed=0):
    ks = jax.random.split(jax.random.key(seed), 5)
    x = jax.random.normal(ks[0], (t_len, heads, width), jnp.float32)
    # slow decays: exp(dt a) near 0.98 a token, so that what a chunk starts
    # from is most of what it puts out
    dt = 0.02 * jax.nn.softplus(jax.random.normal(ks[1], (t_len, heads)))
    a = -jnp.exp(jax.random.uniform(ks[2], (heads,), minval=-0.5, maxval=0.5))
    b = jax.random.normal(ks[3], (t_len, state), jnp.float32)
    c = jax.random.normal(ks[4], (t_len, state), jnp.float32)
    return x, dt, a, b, c


@pytest.mark.parametrize("chunks", [1, 2, 3])
def test_chunked_scan_matches_token_by_token_recurrence(chunks, monkeypatch):
    """Outputs, last state and the gradients of every input, for 1, 2 and 3
    chunks of 8 tokens (one group a chunk at 3, so groups and chunks both
    hand a state on)."""
    monkeypatch.setattr(gh, "SSD_GROUP", 1 if chunks == 3 else 2)
    chunk = 8
    x, dt, a, b, c = _scan_inputs(chunks * chunk)

    def chunked(x, dt, a, b, c):
        return gh.ssd_scan(x, dt, a, b, c, chunk)

    def stepwise(x, dt, a, b, c):
        return ref.recurrence(x * dt[..., None], dt * a[None, :], b, c,
                              scan_block=4)

    (y, last, handed_sq), (y_ref, last_ref) = (chunked(x, dt, a, b, c),
                                              stepwise(x, dt, a, b, c))
    np.testing.assert_allclose(y, y_ref, rtol=2e-5, atol=2e-5)
    np.testing.assert_allclose(last, last_ref, rtol=2e-5, atol=2e-5)
    # the reading: what the last chunk's outputs owe to the state it was
    # handed = the recurrence's outputs less the same tokens' from no state
    tail = (chunks - 1) * chunk
    owed = y_ref[tail:] - stepwise(x[tail:], dt[tail:], a, b[tail:], c[tail:])[0]
    np.testing.assert_allclose(handed_sq, jnp.mean(owed ** 2), rtol=1e-4,
                               atol=1e-12)
    assert (float(handed_sq) > 0) == (chunks > 1)
    if chunks > 1:
        # nothing handed on (the earlier chunks leave no state): it reads 0
        quiet = x.at[:tail].set(0.0)
        assert float(chunked(quiet, dt, a, b, c)[2]) == 0.0
        # the carried state matters: without it the later chunks read wrong
        cold = gh.ssd_scan(x[chunk:], dt[chunk:], a, b[chunk:], c[chunk:], chunk)[0]
        assert float(jnp.max(jnp.abs(cold - y_ref[chunk:]))) > 0.2 * float(
            jnp.max(jnp.abs(y_ref)))
    probe = jax.random.normal(jax.random.key(9), y.shape)
    loss = lambda f: lambda *args: (  # noqa: E731
        jnp.sum(f(*args)[0] * probe) + jnp.sum(f(*args)[1]))
    got = jax.grad(loss(chunked), argnums=(0, 1, 2, 3, 4))(x, dt, a, b, c)
    want = jax.grad(loss(stepwise), argnums=(0, 1, 2, 3, 4))(x, dt, a, b, c)
    for g, w in zip(got, want):
        np.testing.assert_allclose(g, w, rtol=1e-4,
                                   atol=1e-4 * float(jnp.max(jnp.abs(w))))


def test_scan_of_a_short_document_is_one_chunk():
    x, dt, a, b, c = _scan_inputs(5)
    y, last, handed_sq = gh.ssd_scan(x, dt, a, b, c, 8)
    assert float(handed_sq) == 0.0
    y_ref, last_ref = ref.recurrence(x * dt[..., None], dt * a[None, :], b, c)
    np.testing.assert_allclose(y, y_ref, rtol=2e-5, atol=2e-5)
    np.testing.assert_allclose(last, last_ref, rtol=2e-5, atol=2e-5)


def test_reference_fault_no_carry_resets_the_state():
    x, dt, a, b, c = _scan_inputs(16)
    args = (x * dt[..., None], dt * a[None, :], b, c)
    whole, _ = ref.recurrence(*args)
    reset, last = ref.recurrence(*args, reset_every=8)
    np.testing.assert_allclose(reset[:8], whole[:8], rtol=1e-5, atol=1e-7)
    assert float(jnp.max(jnp.abs(reset[8:] - whole[8:]))) > 0.2 * float(
        jnp.max(jnp.abs(whole)))
    np.testing.assert_allclose(
        last, ref.recurrence(*(v[8:] for v in args))[1], rtol=1e-5, atol=1e-7)


# ------------------------------------------------------- program / reference


def test_logits_and_loss_match_reference(model):
    """Float32 on both sides: what differs is the order of float32 sums
    (chunks against single tokens, blocks of one expert's rows), so 1e-4 of
    the logits' scale and 1e-5 on the loss."""
    cfg, params, ids = model
    arch, flat = arch_of(cfg), named(params)
    want = jax.jit(lambda f: ref.logits(f, arch, ids))(flat)
    got = jax.jit(lambda p: gh.forward_logits(p, cfg, ids, jnp.float32))(params)
    assert float(jnp.max(jnp.abs(got - want))) < 1e-4 * float(jnp.max(jnp.abs(want)))
    loss, aux = jax.jit(lambda p: gh.forward_loss(p, cfg, ids, jnp.float32))(params)
    nll = jax.nn.logsumexp(want, -1) - jnp.take_along_axis(
        want, jnp.roll(ids, -1)[:, None], -1)[:, 0]
    assert abs(float(loss) - float(jnp.mean(nll[:-1]))) < 1e-5 * float(loss)
    assert set(aux) == set(gh.COUNTERS)
    assert float(aux["held_pair_share"]) == 1.0 and float(aux["ssd_state_rms"]) > 0


def _reference_first_step(cfg, params, ids, **how):
    flat = named(params)
    trainable = {k: v for k, v in flat.items() if ref.is_trainable(k, TRAINABLE)}
    frozen = {k: v for k, v in flat.items() if k not in trainable}
    grads, _ = ref.layerwise_grads(arch_of(cfg), **how)
    return grads(trainable, frozen, ids)


def test_gradients_of_the_trainable_leaves_match_reference(model):
    """``q_proj`` of the attention layer and ``in_proj_c`` of both Mamba
    layers (layer 0 holds one, so the backward crosses every layer and both
    scans), the program's whole-function gradient under remat against the
    reference's chain rule layer by layer; and the counters the two sides
    report for themselves."""
    cfg, params, ids = model
    loss_ref, choices, want = _reference_first_step(cfg, params, ids)
    (loss, aux), got = jax.jit(jax.value_and_grad(
        lambda p: gh.forward_loss(p, dataclasses.replace(
            cfg, hand_out_choices=True), ids, jnp.float32), has_aux=True))(params)
    assert abs(float(loss) - float(loss_ref)) < 1e-5 * float(loss_ref)
    got = named(got)
    assert sorted(want) == sorted(k for k in got if ref.is_trainable(k, TRAINABLE))
    assert len(want) == 3
    for k, w in want.items():
        scale = float(jnp.max(jnp.abs(w)))
        assert scale > 0, k
        assert float(jnp.max(jnp.abs(got[k] - w))) < 2e-4 * scale, k
    states = [c["state_rms"] for c in choices if c["state_rms"] is not None]
    assert abs(float(aux["ssd_state_rms"]) - float(np.mean(states))) < 1e-5
    for mine, theirs in zip(aux["choices"], choices):
        assert np.array_equal(np.sort(mine["experts"], -1),
                              np.sort(theirs["experts"], -1))
        assert abs(float(mine["routed_over_shared"])
                   - float(theirs["routed_over_shared"])) < 1e-4


@pytest.mark.parametrize("fault", ref.FAULTS)
def test_every_planted_fault_moves_the_reference(model, fault):
    """Each fault of the reference changes the first step's loss or
    gradient at this size (``no_softplus`` overflows: that is a change)."""
    cfg, params, ids = model
    loss0, _, g0 = _reference_first_step(cfg, params, ids)
    loss1, _, g1 = _reference_first_step(cfg, params, ids, fault=fault)
    moved = max(float(jnp.max(jnp.abs(g1[k] - g0[k]))
                      / jnp.max(jnp.abs(g0[k]))) for k in g0)
    assert not np.isfinite(moved) or moved > 1e-3 or abs(
        float(loss1) - float(loss0)) > 1e-3 * float(loss0), (fault, moved)


def test_bfloat16_forward_is_close(model):
    """The dtype the cell runs in: bfloat16 operands against the float32
    reference at this size — a loss within 2 %, no more is claimed here."""
    cfg, params, ids = model
    loss_ref = _reference_first_step(cfg, params, ids)[0]
    loss, aux = jax.jit(lambda p: gh.forward_loss(
        jax.tree.map(lambda x: x.astype(jnp.bfloat16), p), cfg, ids))(params)
    assert abs(float(loss) - float(loss_ref)) < 0.02 * float(loss_ref)
    assert np.isfinite(float(aux["ssd_state_rms"]))


# ------------------------------------------------------- the shares add up


def _share_of(params, cfg, s, n_shares):
    """Share ``s`` of ``n_shares`` of the uncut ``params``: its experts, its
    query and key / value heads, its Mamba heads' columns, rows and
    per-head leaves; B, C, the router, the shared expert and the norms
    whole."""
    en = cfg.num_local_experts // n_shares
    hq = cfg.num_attention_heads // n_shares
    mh = cfg.mamba_n_heads // n_shares
    share = dataclasses.replace(
        cfg, experts_held=(s * en, en), heads_held=(s * hq, hq),
        mamba_heads_held=(s * mh, mh))
    hd, hp, n = cfg.head_dim, cfg.mamba_d_head, cfg.mamba_d_state
    d_all, d = cfg.mamba_n_heads * hp, mh * hp
    hkv = share.kv_heads_held[1]
    ch = slice(s * d, (s + 1) * d)       # inner channels held
    heads = slice(s * mh, (s + 1) * mh)

    def cut(layer):
        out = dict(layer)
        out["experts"] = jax.tree.map(lambda w: w[s * en:(s + 1) * en],
                                      layer["experts"])
        if "attn" in layer:
            a = layer["attn"]
            q = slice(s * hq * hd, (s + 1) * hq * hd)
            kv = slice(s * hkv * hd, (s + 1) * hkv * hd)
            out["attn"] = {"q_proj": {"kernel": a["q_proj"]["kernel"][:, q]},
                           "k_proj": {"kernel": a["k_proj"]["kernel"][:, kv]},
                           "v_proj": {"kernel": a["v_proj"]["kernel"][:, kv]},
                           "o_proj": {"kernel": a["o_proj"]["kernel"][q]}}
        if "mamba" in layer:
            m = layer["mamba"]
            w, conv = m["in_proj"]["kernel"], m["conv"]
            bc = slice(d_all, d_all + 2 * n)
            out["mamba"] = {
                "in_proj": {"kernel": jnp.concatenate(
                    [w[:, ch], w[:, d_all:][:, ch],
                     w[:, 2 * d_all:2 * d_all + n],
                     w[:, 2 * d_all + n:][:, heads]], axis=-1)},
                "in_proj_c": m["in_proj_c"],
                "conv": {"kernel": jnp.concatenate(
                    [conv["kernel"][:, ch], conv["kernel"][:, bc]], axis=-1),
                    "bias": jnp.concatenate(
                        [conv["bias"][ch], conv["bias"][bc]])},
                "A_log": m["A_log"][heads], "D": m["D"][heads],
                "dt_bias": m["dt_bias"][heads],
                "norm": {"scale": m["norm"]["scale"][ch]},
                "out_proj": {"kernel": m["out_proj"]["kernel"][ch]}}
        return out

    return share, {k: cut(v) if k.startswith("layers_") else v
                   for k, v in params.items()}


def test_the_four_shares_add_up_to_the_uncut_layer(model):
    """One Mamba layer (the gated norm's mean squares summed across shares
    at the function boundary), the attention layer and an expert layer: the
    parts the four shares give — B / C, the router and the shared expert
    computed by every share alike and counted once — add up to what the
    uncut REFERENCE gives for the whole layer."""
    cfg, params, ids = model
    arch, nx = arch_of(cfg), ref._Nx("float32")
    u = jax.random.normal(jax.random.key(2), (T, cfg.hidden_size), jnp.float32)
    shares = [_share_of(params, cfg, s, 4) for s in range(4)]
    with jax.default_matmul_precision("highest"):
        # Mamba layer 0
        W = ref.Weights(named(params), "params/layers_0/mamba/")
        g, sum_sq, _ = ref.mamba_scan(W, arch, nx, u)
        whole = ref.mamba_out(W, arch, nx, g, sum_sq / g.shape[-1])
        halves = [gh.mamba_scan_part(p["layers_0"]["mamba"], c, u)
                  for c, p in shares]
        mean_square = sum(h[1] for h in halves) / (
            cfg.mamba_n_heads * cfg.mamba_d_head)
        parts = sum(gh.mamba_out_part(p["layers_0"]["mamba"], c, h[0], mean_square)
                    for (c, p), h in zip(shares, halves))
        assert float(jnp.max(jnp.abs(parts - whole))) < 1e-4 * float(
            jnp.max(jnp.abs(whole)))
        # a share's own statistic is another number: the exchange is real
        own = gh.mamba_mixer(shares[0][1]["layers_0"]["mamba"], shares[0][0], u)[0]
        given = gh.mamba_out_part(shares[0][1]["layers_0"]["mamba"],
                                  shares[0][0], halves[0][0], mean_square)
        assert float(jnp.max(jnp.abs(own - given))) > 1e-3 * float(
            jnp.max(jnp.abs(given)))
        # the attention layer
        whole = ref.attention_part(ref.Weights(named(params),
                                               "params/layers_1/attn/"),
                                   arch, nx, u)
        parts = sum(gh.attention(p["layers_1"]["attn"], c, u) for c, p in shares)
        assert float(jnp.max(jnp.abs(parts - whole))) < 1e-4 * float(
            jnp.max(jnp.abs(whole)))
        # the expert layer of layer 2: the shared expert counted once
        routed, shared, _ = ref.moe_parts(
            ref.Weights(named(params), "params/layers_2/"), arch, nx, u)
        experts, gates = gh.route(params["layers_2"]["router"], cfg, u)
        outs = [ds.held_expert_ffn(p["layers_2"], u, experts, gates,
                                   c.experts_held) for c, p in shares]
        parts = sum(o[0] for o in outs) + outs[0][1]
        whole = routed + shared
        assert float(jnp.max(jnp.abs(parts - whole))) < 1e-4 * float(
            jnp.max(jnp.abs(whole)))
        assert abs(sum(float(o[2]["held_pair_share"]) for o in outs) - 1.0) < 1e-6


# ------------------------------------------- attention: the pair, unselected


@pytest.mark.parametrize("t_len", [256, 384])
def test_causal_kernel_pair_matches_the_xla_path(t_len):
    """``causal_attention`` (the Pallas pair with no selection operand, in
    interpret mode) against ``_chunked_causal_attend``: 8 query heads on 2
    key / value heads of 128, output and the gradients of q, k and v."""
    ks = jax.random.split(jax.random.key(4), 4)
    q = jax.random.normal(ks[0], (t_len, 8, 128), jnp.float32)
    k = jax.random.normal(ks[1], (t_len, 2, 128), jnp.float32)
    v = jax.random.normal(ks[2], (t_len, 2, 128), jnp.float32)
    probe = jax.random.normal(ks[3], (t_len, 8, 128), jnp.float32)
    assert sa.selected_attention_tiles(t_len, 8, 128, 0, 128, q.dtype) is not None

    def run(fn):
        return jax.value_and_grad(
            lambda q, k, v: jnp.sum(fn(q, k, v) * probe), argnums=(0, 1, 2))(q, k, v)

    with jax.default_matmul_precision("highest"):
        out = sa.causal_attention(q, k, v, 0.0078125 * 8, True)
        want = gh._chunked_causal_attend(q, k, v, 0.0078125 * 8)
        np.testing.assert_allclose(out, want, rtol=1e-4, atol=1e-5)
        (_, got), (_, ref_g) = (run(lambda q, k, v: sa.causal_attention(
            q, k, v, 0.0078125 * 8, True)), run(lambda q, k, v: (
                gh._chunked_causal_attend(q, k, v, 0.0078125 * 8))))
    for g, w in zip(got, ref_g):
        np.testing.assert_allclose(g, w, rtol=1e-3,
                                   atol=1e-4 * float(jnp.max(jnp.abs(w))))


def test_attention_takes_the_kernel_on_the_tpu_only(monkeypatch):
    q = jnp.zeros((512, 8, 128), jnp.bfloat16)
    assert not gh._kernel_applies(q)  # the CPU
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert gh._kernel_applies(q)
    assert not gh._kernel_applies(jnp.zeros((500, 8, 128), jnp.bfloat16))
    assert not gh._kernel_applies(jnp.zeros((512, 4, 16), jnp.bfloat16))


# ------------------------------------------------------------ the tuner


def test_the_shared_tuner_steps_on_the_hybrid_loss(model):
    """``TrainState`` / ``loss_steps`` / ``next_token_loss`` as they are:
    three leaves train (``q_proj``, two ``in_proj_c``), the loss falls, the
    frozen leaves do not move, and the step hands out the four scalars."""
    cfg, params, ids = model
    tx = make_optimizer(TuneConfig(learning_rate=1e-2))
    state = TrainState.create(params, tx, TRAINABLE, master_dtype=jnp.float32)
    assert len(jax.tree.leaves(state.trainable)) == 3
    step_loss = next_token_loss(
        lambda p, doc: gh.forward_loss(p, cfg, doc, jnp.float32), ids[None])
    new, losses, aux = jax.jit(
        lambda s, k: loss_steps(step_loss, tx, s, k, num_steps=4))(
            state, jax.random.key(0))
    assert float(losses[-1]) < float(losses[0])
    assert set(aux) == set(gh.COUNTERS)
    assert all(v.shape == (4,) for v in aux.values())
    for a, b in zip(jax.tree.leaves(new.frozen), jax.tree.leaves(state.frozen)):
        assert np.array_equal(a, b)


# ------------------------------------------------- the shared scan at G = 1


def _normalised_jaxpr_digest(fn, *args) -> str:
    """sha256 of ``fn``'s jaxpr text with addresses and source lines taken
    out: what the program computes, not where its code sits."""
    import hashlib
    import re

    text = str(jax.make_jaxpr(fn)(*args))
    text = re.sub(r"0x[0-9a-f]+", "0x", text)
    text = re.sub(r"[\w/\.-]+\.py:\d+(:\d+)?", "SRC", text)
    return hashlib.sha256(text.encode()).hexdigest()


# the digest of the loss-and-gradient below as the code read before the scan
# and the gated norm took B / C in groups (Falcon-H1): one group must lower
# to the same program, equation for equation. It moved once since, for the
# counter ``expert_rows_packed`` (the layers' mean of a constant); without
# it the digest was a1411bceb3e0e4baf6e8c8a283ea6b7657570fa7a3908c3aa79cd14acdefe343.
ONE_GROUP_DIGEST = (
    "e22e2a4eb306ebd52d3969fc9f7869dbdddb0104375c3c97595cd8c0c69f5d3f")


def test_one_group_lowers_to_the_program_it_was_before_groups():
    """Granite-4.0-H's loss and its gradient (tiny, bfloat16, 64 tokens, the
    scan in 8 chunks of 8, 2 a group) trace to the same jaxpr as before
    ``ssd_scan`` / ``mamba_scan_part`` / ``mamba_out_part`` took B / C in
    groups and in-projection multipliers: the one-group path is the old
    code, to the equation."""
    cfg = gh.GraniteHybridConfig.tiny()
    params = gh.abstract_params(cfg)["params"]
    ids = jax.ShapeDtypeStruct((64,), jnp.int32)
    fn = jax.value_and_grad(lambda p, i: gh.forward_loss(p, cfg, i)[0])
    assert _normalised_jaxpr_digest(fn, params, ids) == ONE_GROUP_DIGEST


# the same digest at the Granite cell's own size (its CLI config: 4 layers,
# 32768 tokens) on the chip's branch (``jax.default_backend`` reads "tpu",
# so the attention, the expert loop and the scan take the paths the chip
# runs): the program with the scan on its Pallas pair (``ops/ssd_scan.py``),
# whose kernels' bodies are part of the jaxpr. Before the pair it read
# 648fe4728d936cf46d04c43fba607b7dc7ea66371768b714c017ed859d84e6cc (the XLA
# scan, as the code read before groups); before the expert pair took its
# bfloat16 rows packed and the counter ``expert_rows_packed`` came,
# f09326175d9edfbdf6b6dd8a87015f62e3a875775b9c9ea96c26e166b73610ee (the
# same with both taken out again).
CELL_ONE_GROUP_DIGEST = (
    "c8ce0455da254d9311c6ca48fe6b985651e7c8ba362613257711ad1ef598d2d3")


def test_one_group_at_the_cells_size_traces_to_the_program_it_was(
        monkeypatch):
    """Granite-4.0-H's loss and gradient at the cell's widths and length,
    traced abstractly (no array is made) on the TPU branch: the jaxpr the
    chip lowers is the one pinned above, kernels and all — a change to what
    the chip runs shows here."""
    from videop2p_tpu.cli.common import load_config

    monkeypatch.undo()  # the program's own block sizes, not ``small_blocks``
    tune = load_config("configs/granite-4.0-h-small-s4-tune.yaml")
    cfg = gh.GraniteHybridConfig.from_dict(tune["model"])
    params = gh.abstract_params(cfg)["params"]
    ids = jax.ShapeDtypeStruct((tune["train_data"]["n_tokens"],), jnp.int32)
    fn = jax.value_and_grad(lambda p, i: gh.forward_loss(p, cfg, i)[0])
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert _normalised_jaxpr_digest(fn, params, ids) == CELL_ONE_GROUP_DIGEST


def test_expert_rows_packed_survives_the_layers_mean(model, monkeypatch):
    """``expert_rows_packed`` reads 0 here, where the XLA loop runs, and the
    1 that ``held_expert_ffn`` hands out where the pair takes packed rows
    comes through the family's counters as it is — steered from the
    test: the dispatch reports packed tiles and the XLA loop stands in for
    the kernels, so the loss is the same number."""
    cfg, params, ids = model

    def run():
        return jax.jit(lambda p: gh.forward_loss(p, cfg, ids, jnp.float32))(
            params)

    loss, aux = run()
    assert float(aux["expert_rows_packed"]) == 0.0
    monkeypatch.setattr(ds, "_experts_kernel_tiles",
                        lambda *a: ExpertTiles(128, 0, 0, True))
    monkeypatch.setattr(ds, "grouped_experts_forward", ds._grouped_loop_fwd)
    steered, aux = run()
    assert float(aux["expert_rows_packed"]) == 1.0
    assert float(steered) == float(loss)
