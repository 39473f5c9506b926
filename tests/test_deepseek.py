"""The token model (DeepSeek-V3.2's block, ``models/deepseek.py``) against
its plain float32 reference (``benchmark/reference/deepseek_v32.py``: the
repo keeps ONE, written from the published description) on seeded
weights at a small size — 4 heads, 8 experts in 2 groups, top-2, 2 + 2
layers, 64 wide, 128 tokens, top-k 16 — and through the shared tuner.

The program is run in float32 here, so that what is compared is the
mathematics (chunked, sorted, looped) and not bfloat16 rounding; the one
bfloat16 case has its own, looser tolerances. Index scores tie exactly at 0
at this size (4 index heads: all four ReLUs are 0 for one pair in 16), so
both sides keep ties (the module's docstring), and a tie is no difference.
"""

import dataclasses
import functools
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from flax import traverse_util

from videop2p_tpu.models import deepseek as ds
from videop2p_tpu.train import (
    TrainState,
    TuneConfig,
    loss_steps,
    make_optimizer,
    next_token_loss,
)

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

from benchmark.reference import deepseek_v32 as ref  # noqa: E402

TRAINABLE = ("q_a_proj", "q_b_proj")
T = 128


def arch_of(cfg: ds.DeepSeekV32Config) -> dict:
    """The reference's ``arch`` for a program configuration."""
    d = dataclasses.asdict(cfg)
    arch = {k: d[k] for k in ref.ARCH_KEYS if k != "rope_scaling"}
    arch["rope_scaling"] = {
        "factor": cfg.rope_factor, "beta_fast": cfg.rope_beta_fast,
        "beta_slow": cfg.rope_beta_slow, "mscale": 1,
        "mscale_all_dim": cfg.rope_mscale_all_dim,
        "original_max_position_embeddings": cfg.rope_original_max,
        "type": "yarn"}
    arch.update(experts_held=cfg.experts_held, heads_held=cfg.heads_held)
    return arch


def named(params) -> dict:
    return {"params/" + "/".join(k): v
            for k, v in traverse_util.flatten_dict(params).items()}


@pytest.fixture(autouse=True)
def small_chunks(monkeypatch):
    """Several chunks, blocks and rows at 128 tokens: the module's sizes are
    for 16384."""
    for name, value in dict(Q_CHUNK=32, ATTN_ROWS=16, INDEX_ROWS=8,
                            FFN_ROWS=64, EXPERT_BLOCK=8, LOSS_CHUNK=32).items():
        monkeypatch.setattr(ds, name, value)


@pytest.fixture(scope="module")
def model():
    cfg = ds.DeepSeekV32Config.tiny()
    # bfloat16-exact values (the checkpoint's dtype), held in float32
    params = jax.jit(lambda k: ds.init_params(k, cfg))(jax.random.key(3))
    params = jax.tree.map(lambda x: x.astype(jnp.float32), params["params"])
    ids = jax.random.randint(jax.random.key(1), (T,), 0, cfg.vocab_size)
    return cfg, params, ids


def test_published_defaults_and_param_count():
    """The defaults are the published config.json; the benchmark's cut (one
    chip of 16) holds 3.83 B values: ISSUE 28's arithmetic."""
    cfg = ds.DeepSeekV32Config()
    assert (cfg.hidden_size, cfg.q_lora_rank, cfg.kv_lora_rank) == (7168, 1536, 512)
    assert (cfg.index_n_heads, cfg.index_head_dim, cfg.index_topk) == (64, 128, 2048)
    assert (cfg.n_routed_experts, cfg.n_group, cfg.topk_group,
            cfg.num_experts_per_tok) == (256, 8, 4, 8)
    assert abs(cfg.softmax_scale - 192 ** -0.5 * (0.1 * np.log(40) + 1) ** 2) < 1e-12
    cut = dataclasses.replace(cfg, num_hidden_layers=5, first_k_dense_replace=1,
                              experts_held=(0, 16), heads_held=(0, 8),
                              vocab_size=16160)
    n = sum(int(np.prod(s[0])) for s in jax.tree.leaves(
        ds.param_shapes(cut), is_leaf=ds._is_spec))
    assert round(n / 1e6) == 3829


@pytest.mark.parametrize("key", ["n_heads", "q_chunk", "expert_block",
                                 "attention_kernel", "use_pallas",
                                 "selected_attention_tiles"])
def test_config_from_dict_rejects_unknown_keys(key):
    """How the work is cut is not configuration (module constants), and
    neither is which code attends: the Pallas pair or the chunked XLA path
    is chosen from the backend and the shapes (``_kernel_applies``)."""
    with pytest.raises(ValueError, match="unknown DeepSeekV32Config keys"):
        ds.DeepSeekV32Config.from_dict({"hidden_size": 64, key: 4})


def test_logits_and_loss_match_reference(model):
    """Float32 on both sides: what differs is the order of float32 sums
    (chunks, blocks of one expert's rows, a scatter-add per block), so
    1e-4 of the logits' scale (~1) and 1e-5 on the loss."""
    cfg, params, ids = model
    arch, flat = arch_of(cfg), named(params)
    want = jax.jit(lambda f: ref.logits(f, arch, ids))(flat)
    got = jax.jit(lambda p: ds.forward_logits(p, cfg, ids, jnp.float32))(params)
    assert float(jnp.max(jnp.abs(got - want))) < 1e-4 * float(jnp.max(jnp.abs(want)))
    loss_ref = jax.jit(lambda f: ref.forward(f, arch, ids)[0])(flat)
    loss, aux = jax.jit(lambda p: ds.forward_loss(p, cfg, ids, jnp.float32))(params)
    assert abs(float(loss) - float(loss_ref)) < 1e-5 * float(loss_ref)
    assert float(aux["held_pair_share"]) == 1.0  # every expert is held
    assert 1.0 <= float(aux["expert_load_max_over_mean"]) <= cfg.n_routed_experts
    assert float(aux["expert_rows_packed"]) == 0.0  # the XLA loop ran


def test_choices_match_reference(model):
    """The same keys and the same experts, exactly: float32 scores differ in
    the last bits only and no near-tie sits on a boundary for this seed."""
    cfg, params, ids = model
    want = jax.jit(lambda f: ref.forward(f, arch_of(cfg), ids, packed=True)[1])(
        named(params))
    handing = dataclasses.replace(cfg, hand_out_choices=True)
    got = jax.jit(lambda p: ds.forward_loss(p, handing, ids, jnp.float32))(
        params)[1]["choices"]
    for g, w in zip(got, want):
        assert g["mask"].dtype == jnp.uint8 and g["mask"].shape == (T, T // 8)
        assert bool(jnp.array_equal(g["mask"], w["mask"]))
        assert (g["experts"] is None) == (w["experts"] is None)
        if w["experts"] is not None:
            assert bool(jnp.array_equal(jnp.sort(g["experts"], -1),
                                        jnp.sort(w["experts"], -1)))
            assert float(g["routed_over_shared"]) == pytest.approx(
                float(w["routed_over_shared"]), rel=1e-4)
    # more than top-k keys a query only through exact ties
    per_query = np.asarray(jnp.sum(jnp.unpackbits(got[0]["mask"], axis=-1), -1))
    assert per_query[:16].tolist() == list(range(1, 17))
    assert per_query.min() >= 1 and np.median(per_query[16:]) == cfg.index_topk


def test_trainable_gradients_match_reference(model):
    """Gradients of the trainable leaves, float32 both sides: 1e-4 of each
    leaf's norm (measured 3e-6; a wrong backward of the expert loop or of
    the gates reads 1e-1 and more). The frozen expert matrices get a zero
    cotangent by design (``_grouped_swiglu``)."""
    cfg, params, ids = model
    arch, flat = arch_of(cfg), named(params)
    tr = {k: v for k, v in flat.items() if ref.is_trainable(k, TRAINABLE)}
    fr = {k: v for k, v in flat.items() if k not in tr}
    assert len(tr) == 2 * cfg.num_hidden_layers
    want = jax.jit(jax.grad(lambda t: ref.forward(
        {**fr, **t}, arch, ids, remat=True, row_block=32)[0]))(tr)
    got = named(jax.jit(jax.grad(lambda p: ds.forward_loss(
        p, cfg, ids, jnp.float32)[0]))(params))
    for k, w in want.items():
        assert float(jnp.linalg.norm(got[k] - w)) < 1e-4 * float(jnp.linalg.norm(w)), k
    assert all(float(jnp.abs(v).max()) == 0.0
               for k, v in got.items() if "/experts/" in k)


def test_bfloat16_program_stays_near_reference(model):
    """The program as the cell runs it (bfloat16 compute): 64-wide sums round
    coarsely, and a flipped top-k choice moves a whole token, so 2 % on the
    loss — a missing gate scale or half the experts reads 5 % and more."""
    cfg, params, ids = model
    loss_ref = jax.jit(lambda f: ref.forward(f, arch_of(cfg), ids)[0])(named(params))
    loss, _ = jax.jit(lambda p: ds.forward_loss(p, cfg, ids, jnp.bfloat16))(params)
    assert abs(float(loss) - float(loss_ref)) < 2e-2 * float(loss_ref)


@pytest.mark.parametrize("fault", ref.FAULTS)
def test_reference_faults_change_the_result(model, fault):
    cfg, params, ids = model
    arch, flat = arch_of(cfg), named(params)
    sound, chosen = jax.jit(lambda f: ref.forward(f, arch, ids))(flat)
    broken, chosen_b = jax.jit(lambda f: ref.forward(f, arch, ids, fault=fault))(flat)
    assert abs(float(broken) - float(sound)) > 1e-4 * float(sound)
    if fault == "first_keys":
        assert not bool(jnp.array_equal(chosen[0]["mask"], chosen_b[0]["mask"]))
    if fault == "four_experts":
        assert chosen_b[-1]["experts"].shape[-1] == cfg.num_experts_per_tok // 2


@pytest.fixture
def lane_wide():
    """The tiny model with the published head widths (128 / 64 / 128) on two
    heads and 1 + 1 layers: a shape the kernel pair's fit test takes at 256
    tokens."""
    cfg = ds.DeepSeekV32Config.tiny(
        num_hidden_layers=2, first_k_dense_replace=1, num_attention_heads=2,
        heads_held=(0, 2), qk_nope_head_dim=128, qk_rope_head_dim=64,
        v_head_dim=128, index_head_dim=128)
    params = jax.jit(lambda k: ds.init_params(k, cfg))(jax.random.key(5))
    params = jax.tree.map(lambda x: x.astype(jnp.float32), params["params"])
    ids = jax.random.randint(jax.random.key(2), (256,), 0, cfg.vocab_size)
    return cfg, params, ids


@pytest.fixture
def as_on_the_tpu(monkeypatch):
    """What ``attention()`` observes on the chip, with the kernels in
    interpret mode: steered from the test, not through an option of the
    program. 128 x 128 tiles, so 256 tokens are two by two."""
    from videop2p_tpu.ops import selected_attention as sa

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    monkeypatch.setattr(sa, "_TILES", ((128, 128),))
    monkeypatch.setattr(ds, "selected_key_attention", functools.partial(
        sa.selected_key_attention, interpret=True))


def _has_kernel(fn, *args) -> bool:
    return "pallas_call" in str(jax.make_jaxpr(fn)(*args))


def test_kernel_path_equals_the_xla_path(lane_wide, as_on_the_tpu, monkeypatch):
    """``attention()`` and the trainable leaves' gradients of ``forward_loss``
    through the Pallas pair (interpret mode) against the chunked XLA path on
    the same weights, float32 both: 1e-4 of the output's scale and of each
    leaf's norm, this file's tolerances for sums in another order."""
    cfg, params, ids = lane_wide
    x = jax.random.normal(jax.random.key(7), (256, cfg.hidden_size), jnp.float32)
    angles = ds.rope_angles(cfg, jnp.arange(256))
    attn = params["layers_1"]["attn"]

    def fns():
        # fresh function objects a path: a traced function is cached by identity
        def attend(p, x):
            return ds.attention(p, cfg, x, angles)

        def grads(p):
            got = named(jax.grad(
                lambda p: ds.forward_loss(p, cfg, ids, jnp.float32)[0])(p))
            return {k: v for k, v in got.items() if ref.is_trainable(k, TRAINABLE)}

        return attend, grads

    attend, grads = fns()
    assert _has_kernel(attend, attn, x)
    out_kernel, mask_kernel = jax.jit(attend)(attn, x)
    grads_kernel = jax.jit(grads)(params)
    with monkeypatch.context() as cpu:  # the chunked XLA path
        cpu.setattr(jax, "default_backend", lambda: "cpu")
        attend, grads = fns()
        assert not _has_kernel(attend, attn, x)
        out_xla, mask_xla = jax.jit(attend)(attn, x)
        grads_xla = jax.jit(grads)(params)
    assert bool(jnp.array_equal(mask_kernel, mask_xla))
    assert float(jnp.max(jnp.abs(out_kernel - out_xla))) < 1e-4 * float(
        jnp.max(jnp.abs(out_xla)))
    assert len(grads_xla) == 2 * cfg.num_hidden_layers
    for k, w in grads_xla.items():
        assert float(jnp.linalg.norm(grads_kernel[k] - w)) < 1e-4 * float(
            jnp.linalg.norm(w)), k


def test_a_shape_the_fit_test_refuses_keeps_the_xla_path(model, as_on_the_tpu):
    """The ``tiny`` size's 16 / 8 / 16-wide heads are off the lane tiles: on
    the TPU too it is the chunked path, and the same numbers."""
    cfg, params, ids = model
    assert not _has_kernel(
        lambda p: ds.forward_loss(p, cfg, ids, jnp.float32)[0], params)
    odd = ids[:96]  # 96 tokens: no tile divides them, whatever the widths
    assert not ds._kernel_applies(jnp.zeros((96, 2, 128)), jnp.zeros((96, 2, 64)),
                                  jnp.zeros((96, 2, 128)))
    assert ds._kernel_applies(jnp.zeros((256, 2, 128)), jnp.zeros((256, 2, 64)),
                              jnp.zeros((256, 2, 128)))
    loss_tpu, _ = jax.jit(lambda p: ds.forward_loss(p, cfg, odd, jnp.float32))(params)
    assert bool(jnp.isfinite(loss_tpu))


def test_on_the_cpu_train_steps_holds_no_kernel(lane_wide):
    """Off the TPU the program is XLA alone, whatever the shapes: the
    ledger's ``program_analysis`` counts of ``train_steps`` are empty."""
    from videop2p_tpu.obs.introspect import tpu_custom_call_counts

    cfg, params, ids = lane_wide
    tx = make_optimizer(TuneConfig(trainable_modules=TRAINABLE))
    state = TrainState.create(params, tx, TRAINABLE, master_dtype=jnp.float32)
    step_loss = next_token_loss(
        lambda p, doc: ds.forward_loss(p, cfg, doc, jnp.float32), ids[None])
    program = jax.jit(lambda s, k: loss_steps(step_loss, tx, s, k, num_steps=2))
    assert not _has_kernel(program, state, jax.random.key(0))
    text = program.lower(state, jax.random.key(0)).compile().as_text()
    assert tpu_custom_call_counts(text) == {}


def _share(params, cfg, e0, en, h0, hn):
    """One chip's leaves of a whole layer's: its experts, its head slices."""
    qk = cfg.qk_nope_head_dim + cfg.qk_rope_head_dim
    kvb = cfg.qk_nope_head_dim + cfg.v_head_dim
    p = jax.tree.map(lambda x: x, params)
    a = dict(p["attn"])
    a["q_b_proj"] = {"kernel": a["q_b_proj"]["kernel"][:, h0 * qk:(h0 + hn) * qk]}
    a["kv_b_proj"] = {"kernel": a["kv_b_proj"]["kernel"][:, h0 * kvb:(h0 + hn) * kvb]}
    a["o_proj"] = {"kernel": a["o_proj"]["kernel"][
        h0 * cfg.v_head_dim:(h0 + hn) * cfg.v_head_dim]}
    p["attn"] = a
    p["experts"] = jax.tree.map(lambda x: x[e0:e0 + en], p["experts"])
    return p


def test_the_shares_add_up(model):
    """The parts every (expert share x head share) gives — 2 expert shares of
    4, 2 head shares of 2 — with the shared expert and the replicated parts
    (norms, latents, scorer, router) counted once, sum to the UNCUT
    reference's layer output: attention is linear in the head slices of W_o
    and the routed sum in the experts. Float32; 1e-4 of the output's scale."""
    cfg, params, ids = model
    arch = arch_of(cfg)
    layer = params["layers_2"]  # an expert layer
    x = jax.random.normal(jax.random.key(7), (T, cfg.hidden_size), jnp.float32)
    flat = named({"layers_2": layer})
    angles = ref.rope_angles(arch, T)
    with jax.default_matmul_precision("highest"):
        want, _, _, _ = ref.layer(ref.Weights(flat).at("layers_2"), arch,
                               ref._Nx("float32"), x, angles)

    eps = cfg.rms_norm_eps
    attn_sum = 0.0
    for h0 in (0, 2):
        c = dataclasses.replace(cfg, heads_held=(h0, 2))
        part, _ = ds.attention(
            _share(layer, cfg, 0, 8, h0, 2)["attn"], c,
            ds._rms_norm(x, layer["input_norm"]["scale"], eps),
            ds.rope_angles(cfg, jnp.arange(T)))
        attn_sum = attn_sum + part
    x1 = x + attn_sum  # what every chip holds after the attention's exchange
    y = ds._rms_norm(x1, layer["post_norm"]["scale"], eps)
    routed_sum, shares = 0.0, []
    for e0 in (0, 4):
        c = dataclasses.replace(cfg, experts_held=(e0, 4))
        routed, shared, counters, _ = ds.expert_ffn(
            _share(layer, cfg, e0, 4, 0, 4), c, y)
        routed_sum = routed_sum + routed
        shares.append(float(counters["held_pair_share"]))
    got = x1 + routed_sum + shared  # the shared expert counted once
    assert abs(sum(shares) - 1.0) < 1e-6  # every routed pair lands on one chip
    assert float(jnp.max(jnp.abs(got - want))) < 1e-4 * float(jnp.max(jnp.abs(want)))


def test_kth_largest_is_the_sorted_kth():
    x = jax.random.normal(jax.random.key(0), (37, 200), jnp.float32)
    x = x.at[:, 150:].set(-jnp.inf).at[3, :10].set(0.0).at[5, 0].set(-0.0)
    for k in (1, 16, 150, 190):
        want = jnp.sort(x, axis=-1)[:, -k]
        assert bool(jnp.array_equal(ds._kth_largest(x, k), want)), k


def test_train_steps_on_the_new_loss_move_only_the_trainable_leaves(model):
    cfg, params, ids = model
    tx = make_optimizer(TuneConfig(trainable_modules=TRAINABLE))
    as_served = jax.tree.map(lambda x: x.astype(jnp.bfloat16), params)
    state = TrainState.create(as_served, tx, TRAINABLE, master_dtype=jnp.float32)
    assert all(x.dtype == jnp.float32 for x in jax.tree.leaves(state.trainable))
    assert all(x.dtype == jnp.bfloat16 for x in jax.tree.leaves(state.frozen))
    step_loss = next_token_loss(
        lambda p, doc: ds.forward_loss(p, cfg, doc, jnp.float32), ids[None])
    new, losses, aux = jax.jit(lambda s, k: loss_steps(
        step_loss, tx, s, k, num_steps=3))(state, jax.random.key(0))
    assert losses.shape == (3,) and bool(jnp.isfinite(losses).all())
    assert float(losses[-1]) < float(losses[0])  # the same document every step
    assert set(aux) == {"expert_load_max_over_mean", "held_pair_share",
                        "routed_over_shared", "keys_selected_mean",
                        "expert_rows_packed"}
    assert all(v.shape == (3,) for v in aux.values())
    moved = {k for k, v in named(new.trainable).items()
             if not bool(jnp.array_equal(v, named(state.trainable)[k]))}
    assert moved == set(named(state.trainable)) and len(moved) == 8
    assert all(k.rsplit("/", 2)[-2] in TRAINABLE for k in moved)
    for k, v in named(new.frozen).items():
        assert bool(jnp.array_equal(v, named(state.frozen)[k])), k
    assert int(new.step) == 3


def test_choices_ride_the_scan_when_asked_for(model):
    """``hand_out_choices``: every step's choices of every layer come out of
    ``loss_steps`` beside the scalars, stacked over steps and documents —
    and are what that step's forward pass used (the first step's equal a
    forward pass at the initial weights)."""
    cfg, params, ids = model
    handing = dataclasses.replace(cfg, hand_out_choices=True)
    tx = make_optimizer(TuneConfig(trainable_modules=TRAINABLE))
    state = TrainState.create(params, tx, TRAINABLE, master_dtype=jnp.float32)
    step_loss = next_token_loss(
        lambda p, doc: ds.forward_loss(p, handing, doc, jnp.float32), ids[None])
    _, losses, aux = jax.jit(lambda s, k: loss_steps(
        step_loss, tx, s, k, num_steps=2))(state, jax.random.key(0))
    chosen = aux.pop("choices")
    assert all(v.shape == (2,) for v in aux.values()) and losses.shape == (2,)
    assert len(chosen) == cfg.num_hidden_layers
    alone = jax.jit(lambda p: ds.forward_loss(p, handing, ids, jnp.float32))(
        params)[1]["choices"]
    for layer, first in zip(chosen, alone):
        assert layer["mask"].shape == (2, 1, T, T // 8)
        assert bool(jnp.array_equal(layer["mask"][0, 0], first["mask"]))
        if first["experts"] is None:
            assert layer["experts"] is None
        else:
            assert layer["experts"].shape == (2, 1, T, cfg.num_experts_per_tok)
            assert bool(jnp.array_equal(layer["experts"][0, 0], first["experts"]))


def test_given_the_programs_choices_the_reference_follows_the_program(model):
    """Why the benchmark's check hands the reference the program's choices:
    the bfloat16 program flips near-ties of the top-k (a tenth of the
    choices here), and a flipped key is another function of the weights —
    against the reference's OWN choices the gradients of the trainable
    leaves differ by half their norm and more, GIVEN the program's they
    agree to a few per cent (bfloat16 arithmetic alone). Given its own
    choices the reference is itself."""
    cfg, params, ids = model
    arch, flat = arch_of(cfg), named(params)
    tr = {k: v for k, v in flat.items() if ref.is_trainable(k, TRAINABLE)}
    fr = {k: v for k, v in flat.items() if k not in tr}
    handing = dataclasses.replace(cfg, hand_out_choices=True)
    (_, aux), got = jax.jit(jax.value_and_grad(
        lambda p: ds.forward_loss(p, handing, ids, jnp.bfloat16),
        has_aux=True))(params)
    got = named(got)
    grads, choose = ref.whole_grads(arch)
    loss_own, own, want_own = grads(tr, fr, ids)
    loss_given, used, want_given = grads(tr, fr, ids, aux["choices"])
    assert all(bool(jnp.array_equal(u["mask"], c["mask"]))
               for u, c in zip(used, aux["choices"]))
    assert not all(bool(jnp.array_equal(o["mask"], c["mask"]))
                   for o, c in zip(own, aux["choices"]))

    def diff(want):
        return max(float(jnp.linalg.norm(got[k] - w) / jnp.linalg.norm(w))
                   for k, w in want.items())

    assert diff(want_given) < 0.15 < 0.4 < diff(want_own), (
        diff(want_given), diff(want_own))
    loss_again, _, want_again = grads(tr, fr, ids, own)
    assert float(loss_again) == pytest.approx(float(loss_own), rel=1e-6)
    assert all(float(jnp.abs(want_again[k] - w).max())
               <= 1e-5 * float(jnp.abs(w).max()) for k, w in want_own.items())
    assert all(bool(jnp.array_equal(a["mask"], b["mask"]))
               for a, b in zip(choose(tr, fr, ids), own))


def test_token_document(tmp_path):
    from videop2p_tpu.data import TokenDocument

    drawn = TokenDocument(n_tokens=64, vocab_size=100, document_seed=5).load()
    again = TokenDocument(n_tokens=64, vocab_size=100, document_seed=5).load()
    assert drawn.dtype == np.int32 and drawn.shape == (64,)
    assert np.array_equal(drawn, again) and drawn.max() < 100 and len(set(drawn)) > 10
    path = str(tmp_path / "doc.npy")
    np.save(path, np.arange(80).reshape(8, 10))
    assert TokenDocument(n_tokens=64, vocab_size=100, document_path=path).load(
        ).tolist() == list(range(64))
    with pytest.raises(ValueError, match="outside the vocabulary"):
        TokenDocument(n_tokens=64, vocab_size=50, document_path=path).load()
    with pytest.raises(ValueError, match="integer ids needed"):
        TokenDocument(n_tokens=128, vocab_size=100, document_path=path).load()


def test_reference_layer_by_layer_is_the_whole_reference(model):
    """The reference's chain rule applied layer by layer in Python, with the
    frozen leaves on the host (how the published widths fit one chip), gives
    what differentiating the whole loss gives: float32 sums in another
    order, 1e-5 of the largest moment."""
    cfg, params, ids = model
    arch, flat = arch_of(cfg), named(params)
    hp = dict(learning_rate=3e-5, adam_beta1=0.9, adam_beta2=0.999,
              adam_epsilon=1e-8, adam_weight_decay=0.01, max_grad_norm=1.0,
              trainable_modules=list(TRAINABLE))
    whole = ref.tune(dict(flat), arch, hp, ids, 2)
    taken = dict(flat)
    layered = ref.tune(taken, arch, hp, ids, 2, layerwise=True, remat=True,
                       row_block=32)
    assert taken == {}  # the weights were taken over, for the host
    assert np.allclose(whole["losses"], layered["losses"], rtol=1e-6)
    for k, mu in whole["mu"].items():
        assert float(jnp.abs(mu - layered["mu"][k]).max()) < 1e-5 * float(jnp.abs(mu).max()), k
    for a, b in zip(whole["chosen"], layered["chosen"]):
        assert a["mask"].dtype == jnp.uint8 and bool(jnp.array_equal(a["mask"], b["mask"]))


def test_select_keys_takes_the_scorer_from_its_caller(model):
    """``select_keys`` reads its query input, widths, rotary width and top-k
    from its caller: handed this family's (``scorer_widths``: 4 index heads
    of 16, the rotary's 8 dims, 16 keys) it gives the reference's selection
    of the family, key for key, on layer 0's normed input, as the layer's
    own ``_layer_mask`` does; a smaller top-k is another selection."""
    cfg, params, ids = model
    assert ds.scorer_widths(cfg) == dict(heads=4, head_dim=16, rope_dim=8,
                                         topk=16)
    p = params["layers_0"]
    angles = ds.rope_angles(cfg, jnp.arange(T))
    x = ds._rms_norm(params["embed"]["embedding"][ids], p["input_norm"]["scale"],
                     cfg.rms_norm_eps)
    c_q = ds._rms_norm(ds._dense(x, p["attn"]["q_a_proj"]["kernel"]),
                       p["attn"]["q_a_norm"]["scale"], cfg.rms_norm_eps)

    def mask(**widths):
        return jax.jit(lambda: ds.select_keys(p["attn"]["indexer"], x, c_q,
                                              angles, **widths))()

    arch = arch_of(cfg)
    W = ref.Weights(named(params), "params/layers_0/attn/indexer/")
    with jax.default_matmul_precision("highest"):
        want = jax.jit(lambda: ref.selection(
            W, arch, ref._Nx("float32"), x, c_q, ref.rope_angles(arch, T)))()
    got = mask(**ds.scorer_widths(cfg))
    assert bool(jnp.array_equal(got, want))
    assert bool(jnp.array_equal(got, ds._layer_mask(p, cfg, params["embed"][
        "embedding"][ids], angles)))
    assert not bool(jnp.array_equal(got, mask(**dict(
        ds.scorer_widths(cfg), topk=8))))
