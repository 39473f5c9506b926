"""The token model's selected-key attention as a Pallas pair
(``ops/selected_attention.py``), in interpret mode on the CPU, against the
XLA code it stands in for (``models.deepseek._attend``): the output and all
five gradients, in float32 so that what is compared is the mathematics
(tiles, the online softmax, the causal walk) and not bfloat16 rounding; one
bfloat16 case with its own tolerance; and the fit test's arithmetic. What
the chip's compiler says of the kernels is ``tests/test_tpu_compile.py``'s.
"""

import collections

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from videop2p_tpu.models import deepseek as ds
from videop2p_tpu.ops import selected_attention as sa

NOPE, ROPE, VDIM = 128, 64, 128
SCALE = 192 ** -0.5 * 1.87


def operands(t_len, heads, dtype, seed=0):
    ks = jax.random.split(jax.random.key(seed), 6)
    shapes = [(t_len, heads, NOPE), (t_len, heads, ROPE), (t_len, heads, NOPE),
              (t_len, ROPE), (t_len, heads, VDIM)]
    ops = [jax.random.normal(k, s, jnp.float32).astype(dtype)
           for k, s in zip(ks, shapes)]
    return ops, jax.random.normal(ks[5], shapes[4], jnp.float32)


def selection(t_len, keys, seed=1, hole=None):
    """A causal top-``keys`` selection of random scores, every query keeping
    itself; ``hole = (rows, upto)``: those queries select no key before
    ``upto`` — their first key tiles hold none of their keys."""
    pos = np.arange(t_len)
    score = np.random.default_rng(seed).normal(size=(t_len, t_len))
    causal = pos[None, :] <= pos[:, None]
    score = np.where(causal, score, -np.inf)
    thr = np.sort(score, axis=-1)[:, -keys]
    mask = (causal & (score >= thr[:, None])) | np.eye(t_len, dtype=bool)
    if hole is not None:
        rows, upto = hole
        mask[rows, :upto] = False
    return jnp.asarray(mask)


def pair(attend, ops, mask, w):
    """The output and the five gradients of sum(w * o)."""
    out, vjp = jax.vjp(lambda *a: attend(*a, mask, SCALE), *ops)
    return (out,) + vjp(w.astype(out.dtype))


def kernel(*a):
    return sa.selected_key_attention(*a, True)


NAMES = ("o", "dq_nope", "dq_rope", "dk_nope", "dk_rope", "dv")
CASES = {
    # tokens, heads, tiles, heads a cell, keys a query, the hole
    "one_tile": (256, 2, (256, 256), (2, 1), 40, None),
    "first_key_tile_empty": (256, 2, (128, 128), (2, 1), 24,
                             (slice(130, 256, 2), 128)),
    "plain_causal": (256, 2, (128, 128), (2, 1), 256, None),
    "two_query_tiles_three_key_tiles": (768, 1, (384, 256), (1,), 100, None),
    "three_by_three_two_cells_of_heads": (384, 4, (128, 128), (2, 1), 64,
                                          (slice(300, 384), 256)),
}


@pytest.mark.parametrize("case", CASES)
def test_pair_equals_attend_in_float32(monkeypatch, case):
    """Float32 on both sides: sums in another order, 1e-5 of each result's
    largest entry (measured 1e-6)."""
    t_len, heads, tiles, cell_heads, keys, hole = CASES[case]
    monkeypatch.setattr(sa, "_TILES", (tiles,))
    monkeypatch.setattr(sa, "_HEADS", cell_heads)
    got_tiles = sa.selected_attention_tiles(t_len, heads, NOPE, ROPE, VDIM,
                                            jnp.float32)
    assert got_tiles[:2] == tiles and got_tiles.fwd_heads == cell_heads[0]
    ops, w = operands(t_len, heads, jnp.float32)
    mask = selection(t_len, keys, hole=hole)
    if case == "plain_causal":
        assert bool(jnp.array_equal(mask, jnp.tril(jnp.ones_like(mask))))
    want = jax.jit(lambda: pair(ds._attend, ops, mask, w))()
    got = jax.jit(lambda: pair(kernel, ops, mask, w))()
    for name, g, x in zip(NAMES, got, want):
        assert g.shape == x.shape and g.dtype == x.dtype, name
        assert bool(jnp.isfinite(g).all()), name
        assert float(jnp.max(jnp.abs(g - x))) < 1e-5 * float(jnp.max(jnp.abs(x))), name


def test_bfloat16_pair_stays_within_two_per_cent():
    """As the cell runs it: bfloat16 operands, float32 statistics. ``_attend``
    rounds the normalised probabilities, the kernel the unnormalised ones and
    divides the float32 accumulator: 2 % of each result's largest entry
    (measured 0.7 %)."""
    ops, w = operands(256, 2, jnp.bfloat16)
    mask = selection(256, 40)
    want = jax.jit(lambda: pair(ds._attend, ops, mask, w))()
    got = jax.jit(lambda: pair(kernel, ops, mask, w))()
    for name, g, x in zip(NAMES, got, want):
        assert g.dtype == jnp.bfloat16, name
        g, x = g.astype(jnp.float32), x.astype(jnp.float32)
        assert float(jnp.max(jnp.abs(g - x))) < 2e-2 * float(jnp.max(jnp.abs(x))), name


def test_a_query_without_any_key_reads_zero_not_nan():
    """Not what ``select_keys`` hands over (a query always keeps keys), but
    the running max must stay finite through it: ``o`` is 0 there and every
    gradient finite."""
    ops, w = operands(256, 1, jnp.float32)
    mask = selection(256, 16).at[200].set(False)
    got = jax.jit(lambda: pair(kernel, ops, mask, w))()
    assert all(bool(jnp.isfinite(g).all()) for g in got)
    assert float(jnp.abs(got[0][200]).max()) == 0.0
    assert float(jnp.abs(got[0][199]).max()) > 0.0


def test_fit_test_at_the_cells_shape():
    """16384 tokens, 8 heads, 128 / 64 / 128, bfloat16: 512 x 512 tiles, all
    heads a forward cell, two a backward cell — the resident dQ is
    2 buffers x 2 heads x 192 x 16384 x 4 B = 50.3 MB of the backward's 64.6 MB,
    and four heads (122 MB) are over the 96 MiB budget."""
    args = (16384, 8, NOPE, ROPE, VDIM, jnp.bfloat16)
    assert sa.selected_attention_tiles(*args) == sa.Tiles(512, 512, 8, 2)
    assert sa._bwd_vmem_bytes(2, 16384, 512, 512, 192, 128, 2) == 64_618_496
    assert 2 * 2 * 192 * 16384 * 4 == 50_331_648
    assert sa._bwd_vmem_bytes(4, 16384, 512, 512, 192, 128, 2) > sa._VMEM_BUDGET
    assert sa._fwd_vmem_bytes(8, 512, 512, 192, 128, 2) == 20_447_232
    # the causal walk: 32 x 33 / 2 tile pairs, each query tile's key tiles in a row
    qi, ki = sa._causal_steps(16384, 512, 512)
    assert len(qi) == 528 and qi[:3].tolist() == [0, 1, 1] and ki[:3].tolist() == [0, 0, 1]
    qi, ki = sa._causal_steps(16384, 512, 512, key_major=True)
    assert ki[:33].tolist() == [0] * 32 + [1] and qi[:33].tolist() == list(range(32)) + [1]
    assert sa.selected_attention_tiles(4096, 8, NOPE, ROPE, VDIM, jnp.bfloat16) == (
        sa.Tiles(512, 512, 8, 8))


@pytest.mark.parametrize("why,args", {
    "tiny_heads_off_the_lane_tiles": (128, 4, 16, 8, 16),
    "rope_off_the_sublane_tiles": (1024, 8, 128, 8, 128),
    "no_tile_divides_the_tokens": (1000, 8, NOPE, ROPE, VDIM),
    "backward_over_vmem": (131072, 8, NOPE, ROPE, VDIM),
}.items())
def test_fit_test_refuses(why, args):
    assert sa.selected_attention_tiles(*args, jnp.bfloat16) is None
    t_len, heads, nope, rope, v_dim = args
    if t_len <= 1024:
        z = lambda *s: jnp.zeros(s, jnp.bfloat16)  # noqa: E731
        with pytest.raises(ValueError, match="selected_attention_tiles first"):
            sa.selected_key_attention(
                z(t_len, heads, nope), z(t_len, heads, rope),
                z(t_len, heads, nope), z(t_len, rope), z(t_len, heads, v_dim),
                jnp.ones((t_len, t_len), bool), SCALE, True)


# ------------------------------------------------ no selection: causal, a band

WINDOW_CASES = {
    # tokens, (query heads, key / value heads), tiles, heads a cell, window
    "window_under_a_tile": (384, (2, 2), (128, 128), (2, 1), 50),
    "window_a_multiple_of_the_tile": (512, (2, 1), (128, 128), (2, 1), 256),
    "window_not_a_multiple": (512, (4, 2), (128, 128), (2, 1), 200),
    "query_tile_wider_than_key_tile": (768, (2, 1), (384, 128), (1,), 300),
    "window_reaches_every_key": (384, (2, 1), (128, 128), (2, 1), 384),
    "grouped_heads_16_to_1": (256, (16, 1), (128, 128), (8, 4, 2, 1), 130),
}


def _banded_dense(q, k, v, scale, window):
    """Masked dense XLA, the whole (T, T) score matrix: the oracle."""
    t_len, group = q.shape[0], q.shape[1] // k.shape[1]
    pos = jnp.arange(t_len)
    seen = (pos[None, :] <= pos[:, None]) & (pos[None, :] > pos[:, None] - window)
    s = jnp.einsum("qhd,khd->hqk", q, jnp.repeat(k, group, axis=1),
                   preferred_element_type=jnp.float32)
    prob = jax.nn.softmax(jnp.where(seen[None], s * scale, -jnp.inf), axis=-1)
    return jnp.einsum("hqk,khd->qhd", prob, jnp.repeat(v, group, axis=1))


@pytest.mark.parametrize("case", WINDOW_CASES)
def test_windowed_pair_equals_the_xla_path(monkeypatch, case):
    """``causal_attention`` with a window, in interpret mode, against masked
    dense XLA and against the row-blocked XLA path that reads only the keys
    a block can see (``granite_hybrid._chunked_causal_attend``): the values
    and dq, dk, dv, float32 on both sides; the step tables hold the band's
    tile pairs and no others; a window that reaches every key is the causal
    call to the bit."""
    from videop2p_tpu.models import granite_hybrid as gh

    t_len, (hq, hkv), tiles, cell_heads, window = WINDOW_CASES[case]
    monkeypatch.setattr(sa, "_TILES", (tiles,))
    monkeypatch.setattr(sa, "_HEADS", cell_heads)
    monkeypatch.setattr(gh, "ATTN_ROWS", 128)
    got_tiles = sa.selected_attention_tiles(t_len, hq, 128, 0, 128, jnp.float32)
    assert got_tiles[:2] == tiles
    bq, bk = tiles
    qi, ki = sa._causal_steps(t_len, bq, bk, False, sa._band(t_len, window))
    band = {(a, b) for a in range(t_len // bq) for b in range(t_len // bk)
            if any(0 <= t - s < window for t in (a * bq, (a + 1) * bq - 1)
                   for s in (b * bk, (b + 1) * bk - 1))
            or (b * bk <= a * bq and (a + 1) * bq - 1 <= (b + 1) * bk - 1)
            or (a * bq <= b * bk and (b + 1) * bk - 1 <= (a + 1) * bq - 1
                and (b + 1) * bk - 1 > a * bq - window)}
    assert set(zip(qi.tolist(), ki.tolist())) == band
    assert sa.causal_tile_pairs(t_len, got_tiles, window) == len(band)
    kq, kk = sa._causal_steps(t_len, bq, bk, True, sa._band(t_len, window))
    assert sorted(zip(kq.tolist(), kk.tolist())) == sorted(band)
    assert kk.tolist() == sorted(kk.tolist())
    ks = jax.random.split(jax.random.key(7), 4)
    q = jax.random.normal(ks[0], (t_len, hq, 128), jnp.float32)
    k = jax.random.normal(ks[1], (t_len, hkv, 128), jnp.float32)
    v = jax.random.normal(ks[2], (t_len, hkv, 128), jnp.float32)
    w = jax.random.normal(ks[3], (t_len, hq, 128), jnp.float32)
    scale = 128 ** -0.5

    def pair(attend):
        out, vjp = jax.vjp(attend, q, k, v)
        return (out,) + vjp(w)

    with jax.default_matmul_precision("highest"):
        got = jax.jit(lambda: pair(lambda q, k, v: sa.causal_attention(
            q, k, v, scale, True, window)))()
        dense = jax.jit(lambda: pair(lambda q, k, v: _banded_dense(
            q, k, v, scale, window)))()
        blocked = jax.jit(lambda: pair(lambda q, k, v: gh._chunked_causal_attend(
            q, k, v, scale, window)))()
        causal = jax.jit(lambda: pair(lambda q, k, v: sa.causal_attention(
            q, k, v, scale, True)))()
    for name, g, x, b, c in zip(("o", "dq", "dk", "dv"), got, dense, blocked,
                                causal):
        assert g.shape == x.shape and bool(jnp.isfinite(g).all()), name
        top = float(jnp.max(jnp.abs(x)))
        assert float(jnp.max(jnp.abs(g - x))) < 1e-5 * top, name
        assert float(jnp.max(jnp.abs(b - x))) < 1e-5 * top, name
        if window >= t_len:
            assert bool(jnp.array_equal(g, c)), name
        else:
            assert float(jnp.max(jnp.abs(c - x))) > 1e-3 * top, name


def test_the_band_at_the_cells_shape():
    """32768 tokens, 16 query heads on one key / value head, a window of
    4096, 512 x 512 tiles: nine key tiles a query tile once the band is
    full, 540 of the 2080 causal pairs; the forward's first key tile of a
    query tile is the one its first query's first key lies in."""
    tiles = sa.selected_attention_tiles(32768, 16, 128, 0, 128, jnp.bfloat16)
    assert tiles == sa.Tiles(512, 512, 8, 2)
    assert sa.causal_tile_pairs(32768, tiles) == 64 * 65 // 2 == 2080
    assert sa.causal_tile_pairs(32768, tiles, 4096) == 36 + 56 * 9 == 540
    assert sa.causal_tile_pairs(32768, tiles, 32768) == 2080
    qi, ki = sa._causal_steps(32768, 512, 512, False, 4096)
    for a in (0, 7, 8, 9, 63):
        mine = ki[qi == a]
        assert mine.min() == max(a * 512 - 4095, 0) // 512 and mine.max() == a


# ------------------------------- what a layer's recompute keeps of the pair

def _pallas_calls(jaxpr) -> dict:
    """Pallas calls of a jaxpr by kernel name, sub-jaxprs included."""
    counts = collections.Counter(
        eqn.params["name"] for eqn in jaxpr.eqns
        if eqn.primitive.name == "pallas_call")
    for eqn in jaxpr.eqns:
        for sub in jax.core.jaxprs_in_params(eqn.params):
            counts.update(_pallas_calls(sub))
    return dict(counts)


def _causal_layer(window):
    def layer(x):  # (T, 2, 128): two query heads on one key / value head
        kv = x[:, :1]
        return x + jnp.tanh(sa.causal_attention(x, kv, kv, 128 ** -0.5, True,
                                                window))
    return layer


def _selected_layer(x):  # (T, 2, 128) on a fixed selection
    return x + jnp.tanh(sa.selected_key_attention(
        x, x[..., :ROPE], x, x[:, 0, :ROPE], x, selection(256, 40), SCALE, True))


@pytest.mark.parametrize("layer", {
    "causal": _causal_layer(None), "window": _causal_layer(100),
    "selected": _selected_layer}.items(), ids=lambda c: c[0])
def test_the_policy_keeps_the_forward_kernel_out_of_the_recompute(monkeypatch,
                                                                  layer):
    """Two checkpointed layers over the pair, in interpret mode: with
    ``keep_attention_outputs`` the gradient holds each layer's forward kernel
    ONCE (a bare ``jax.checkpoint`` runs it again in the recompute: the names
    sit inside the forward rule or this fails), the backward kernel once
    either way, and the gradient is the bare checkpoint's and the
    uncheckpointed stack's to the bit."""
    monkeypatch.setattr(sa, "_TILES", ((128, 128),))
    layer = layer[1]
    x = jax.random.normal(jax.random.key(11), (256, 2, 128), jnp.float32)

    def grad(wrap):
        return jax.grad(lambda x: jnp.sum(wrap(layer)(wrap(layer)(x)) ** 2))

    kept = grad(lambda f: jax.checkpoint(f, policy=sa.keep_attention_outputs))
    bare = grad(jax.checkpoint)
    assert _pallas_calls(jax.make_jaxpr(kept)(x).jaxpr) == {
        "lm_selected_attention": 2, "lm_selected_attention_bwd": 2}
    assert _pallas_calls(jax.make_jaxpr(bare)(x).jaxpr) == {
        "lm_selected_attention": 4, "lm_selected_attention_bwd": 2}
    want = jax.jit(grad(lambda f: f))(x)
    assert float(jnp.abs(want).max()) > 0
    assert bool(jnp.array_equal(jax.jit(kept)(x), want))
    assert bool(jnp.array_equal(jax.jit(bare)(x), want))


def test_a_name_outside_a_checkpoint_is_an_identity():
    a, b = jnp.arange(3.0), jnp.ones(2)
    got = sa._kept(a, b)
    assert bool(jnp.array_equal(got[0], a)) and bool(jnp.array_equal(got[1], b))
    names = [e.params["name"] for e in jax.make_jaxpr(sa._kept)(a, b).jaxpr.eqns]
    assert tuple(names) == sa.KEPT_NAMES


@pytest.mark.parametrize("family", ["deepseek_v32", "granitemoehybrid",
                                    "cohere2_moe"])
def test_on_the_cpu_path_the_policy_keeps_nothing(monkeypatch, family):
    """Each family's ``forward_loss`` off the TPU: the XLA attention carries
    no name, so under the policy the layers' recompute is the bare
    checkpoint's — no ``name`` equation in the loss, and the gradient of the
    trainable-sized leaves equal to the bit with the policy taken away."""
    from videop2p_tpu.cli.common import _token_families

    module, config_cls = _token_families()[family]
    cfg = config_cls.tiny()
    params = jax.jit(lambda k: module.init_params(k, cfg))(
        jax.random.key(5))["params"]
    params = jax.tree.map(lambda x: x.astype(jnp.float32), params)
    ids = jax.random.randint(jax.random.key(1), (64,), 0, cfg.vocab_size)

    def loss(p):
        return module.forward_loss(p, cfg, ids, jnp.float32)[0]

    def names(jaxpr):
        return sum((e.primitive.name == "name")
                   + sum(names(s) for s in jax.core.jaxprs_in_params(e.params))
                   for e in jaxpr.eqns)

    assert module.keep_attention_outputs is sa.keep_attention_outputs
    assert names(jax.make_jaxpr(loss)(params).jaxpr) == 0
    got = jax.jit(jax.grad(loss))(params)
    monkeypatch.setattr(module, "keep_attention_outputs", None)
    want = jax.jit(jax.grad(loss))(params)
    moved = 0
    for g, w in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        assert bool(jnp.array_equal(g, w))
        moved += float(jnp.abs(w).max()) > 0
    assert moved > 4
