"""The token models' grouped expert loop as a Pallas pair
(``ops/grouped_experts.py``), in interpret mode on the CPU, against the XLA
loop it stands in for (``models.deepseek._grouped_swiglu`` off the TPU) and
against a per-token dense reference written here: the output, ``dx``,
``dgate`` and the matrices' zero cotangent, in float32 so that what is
compared is the mathematics (the table's walk, the row copies, the in-place
accumulation, the inner tiles) and not bfloat16 rounding; one bfloat16 case
with its own tolerance; bfloat16 rows packed two to a word (hidden 2048 and
4096) against the float32 rows, to the bit; the fit test's arithmetic and
row form; the program at widths that keep float32 rows, pinned; the counter
``expert_rows_packed``; and the property the in-place accumulation rests on
— a block's tokens are distinct. What the chip's compiler says of the
kernels is ``tests/test_tpu_compile.py``'s.
"""

import functools
import hashlib
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from videop2p_tpu.models import deepseek as ds
from videop2p_tpu.ops import grouped_experts as ge

HIDDEN, TOP_K = 1024, 8  # one row = eight lane tiles; T * K in whole 1024s


def routing(t_len, n_experts, seed, load=None):
    """``(experts (T, K), gates (T, K))``: K distinct experts a token, drawn
    with the weights ``load`` (level where None) — what a router's top-k
    hands ``held_expert_ffn``."""
    rng = np.random.default_rng(seed)
    p = np.ones(n_experts) if load is None else np.asarray(load, float)
    experts = np.stack([rng.choice(n_experts, TOP_K, replace=False, p=p / p.sum())
                        for _ in range(t_len)])
    gates = rng.uniform(0.2, 1.5, (t_len, TOP_K))
    return jnp.asarray(experts, jnp.int32), jnp.asarray(gates, jnp.float32)


def operands(t_len, held, inner, dtype, seed=0):
    ks = jax.random.split(jax.random.key(seed), 5)
    x = jax.random.normal(ks[0], (t_len, HIDDEN), jnp.float32).astype(dtype)
    wg, wu = (jax.random.normal(k, (held, HIDDEN, inner), jnp.float32)
              .astype(dtype) * HIDDEN ** -0.5 for k in ks[1:3])
    wd = (jax.random.normal(ks[3], (held, inner, HIDDEN), jnp.float32)
          .astype(dtype) * inner ** -0.5)
    return x, wg, wu, wd, jax.random.normal(ks[4], (t_len, HIDDEN), jnp.float32)


def dense(x, experts, gates, held, wg, wu, wd):
    """Token by token, expert by expert, float32: the definition."""
    e0, en = held
    x, wg, wu, wd = (a.astype(jnp.float32) for a in (x, wg, wu, wd))
    y = jnp.zeros(x.shape, jnp.float32)
    for e in range(en):
        g = jnp.sum(jnp.where(experts == e0 + e, gates, 0.0), axis=-1)
        y = y + g[:, None] * ((jax.nn.silu(x @ wg[e]) * (x @ wu[e])) @ wd[e])
    return y


def pair(loop, x, gates, w, experts, held, block, wg, wu, wd):
    """The output and the cotangents of sum(w * y) in ``x``, the gates (T, K)
    and the three matrices, through the table ``held_expert_ffn`` builds."""

    def fn(x, gates, wg, wu, wd):
        tok, gate, tbl, _ = ds.expert_blocks(experts, gates, held, block)
        return loop(x, tok, gate, tbl, wg, wu, wd, block)

    out, vjp = jax.vjp(fn, x, gates, wg, wu, wd)
    return (out,) + vjp(w.astype(out.dtype))


@pytest.fixture
def as_on_the_tpu(monkeypatch):
    """What ``_grouped_swiglu`` observes on the chip, with the kernels in
    interpret mode: steered from the test, not through an option."""
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    monkeypatch.setattr(ds, "grouped_experts_forward", functools.partial(
        ge.grouped_experts_forward, interpret=True))
    monkeypatch.setattr(ds, "grouped_experts_backward", functools.partial(
        ge.grouped_experts_backward, interpret=True))


NAMES = ("y", "dx", "dgates", "dwg", "dwu", "dwd")
CASES = {
    # tokens, all experts, held (first, count), inner, inner tile, block, load
    # level loads, every expert held: ragged counts, every token under
    # eight held experts, each expert's last block past its end
    "all_held_ragged": (256, 16, (0, 16), 128, 128, 16, None),
    # a quarter held: n far under the table's length (2048 / 8 + 4 blocks)
    "quarter_held_n_small": (256, 16, (4, 4), 256, 256, 8, None),
    # held experts 1 and 3 of four are never chosen: no rows, no block
    "experts_with_no_rows": (256, 16, (2, 4), 128, 128, 16,
                             [1, 1, 1, 0, 1, 0] + [1] * 10),
    # one expert takes every token: many full blocks of one expert in a row
    "one_busy_expert_inner_tiled": (256, 12, (0, 3), 384, 128, 16,
                                    [50] + [1] * 11),
    # the inner dimension in two tiles, blocks wider than most experts' rows
    "inner_in_two_tiles_wide_block": (256, 32, (8, 8), 256, 128, 64, None),
    # 8192 sorted rows, eight groups of 1024: the aligned ``tok`` / ``gate``
    # group a block reads moves from block to block, blocks straddle two
    # groups, the last ones are clamped to the arrays' end, and the three
    # group buffers hold different rows in turn (the cases above fit ONE
    # group, where a group overwritten too early reads the same)
    "rows_in_eight_groups": (1024, 16, (0, 16), 256, 128, 64,
                             [3, 1, 2, 1] * 4),
}


@pytest.mark.parametrize("case", CASES)
def test_pair_equals_the_loop_and_the_definition(monkeypatch, as_on_the_tpu, case):
    """Float32 on both sides: sums in another order, 1e-5 of each result's
    largest entry against the XLA loop, 1e-4 against the dense definition."""
    t_len, n_experts, held, inner, tile, block, load = CASES[case]
    # the budget of exactly this tile: the widest that fits is the one wanted
    monkeypatch.setattr(ge, "_VMEM_BUDGET",
                        ge._vmem_bytes(HIDDEN, tile, block, 4, backward=True))
    assert ge.grouped_expert_tiles(t_len * TOP_K, HIDDEN, inner, block,
                                   jnp.float32).inner == tile
    experts, gates = routing(t_len, n_experts, seed=len(case), load=load)
    x, wg, wu, wd, w = operands(t_len, held[1], inner, jnp.float32)
    args = (x, gates, w, experts, held, block, wg, wu, wd)
    assert "pallas_call" in str(jax.make_jaxpr(
        lambda *a: pair(ds._grouped_swiglu, *a, experts, held, block, wg, wu, wd)
    )(x, gates, w))
    got = jax.jit(lambda x, g, w: pair(ds._grouped_swiglu, x, g, w, *args[3:]))(
        x, gates, w)
    with monkeypatch.context() as cpu:
        cpu.setattr(jax, "default_backend", lambda: "cpu")
        want = jax.jit(lambda x, g, w: pair(
            lambda *a: ds._grouped_swiglu(*a), x, g, w, *args[3:]))(x, gates, w)
    y_def, vjp = jax.vjp(lambda x, g: dense(x, experts, g, held, wg, wu, wd),
                         x, gates)
    definition = (y_def,) + vjp(w)
    for name, a, b in zip(NAMES, got, want):
        if name.startswith("dw"):  # frozen under this op
            assert not np.any(np.asarray(a)) and not np.any(np.asarray(b)), name
            continue
        scale = float(jnp.max(jnp.abs(b)))
        assert scale > 0, name
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=0,
                                   atol=1e-5 * scale, err_msg=name)
    for name, a, b in zip(NAMES[:3], got, definition):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=0,
                                   atol=1e-4 * float(jnp.max(jnp.abs(b))),
                                   err_msg=name)


def test_pair_in_bfloat16_is_the_loop_in_bfloat16(monkeypatch, as_on_the_tpu):
    """bfloat16 operands, float32 accumulation, one cast at the end, on both
    sides: the float32 sums differ in order only, so the casts agree but for
    a few last bits — 2**-7 of the largest entry; ``dgate`` stays float32."""
    t_len, held, block = 256, (0, 4), 16
    experts, gates = routing(t_len, 8, seed=5)
    x, wg, wu, wd, w = operands(t_len, held[1], 256, jnp.bfloat16)
    args = (experts, held, block, wg, wu, wd)
    got = jax.jit(lambda x, g, w: pair(ds._grouped_swiglu, x, g, w, *args))(
        x, gates, w)
    with monkeypatch.context() as cpu:
        cpu.setattr(jax, "default_backend", lambda: "cpu")
        want = jax.jit(lambda x, g, w: pair(
            lambda *a: ds._grouped_swiglu(*a), x, g, w, *args))(x, gates, w)
    assert got[0].dtype == got[1].dtype == jnp.bfloat16
    assert got[2].dtype == jnp.float32
    for name, a, b in zip(NAMES[:3], got, want):
        a, b = np.asarray(a, np.float32), np.asarray(b, np.float32)
        np.testing.assert_allclose(a, b, rtol=0, atol=2.0 ** -7 * np.abs(b).max(),
                                   err_msg=name)


# hidden, block: a row of one (8, 128) tile of words, and of two
PACKED_CASES = {"hidden_2048": (2048, 16), "hidden_4096": (4096, 64)}


@pytest.mark.parametrize("case", PACKED_CASES)
def test_packed_rows_are_the_float32_rows_to_the_bit(case):
    """bfloat16 ``x`` / ``dy`` at a hidden width of whole 2048s travel packed
    two to a word; handed the same values widened to float32, the pair takes
    float32 rows (the form before packing). Forward and backward, ``y``,
    ``dx`` and ``dgate`` are equal to the bit: the unpacked words are the
    values, every product sees the same operands in the same order. Against
    the XLA loop, the bfloat16 case's tolerance (the float32 rows' sums run
    in another order there)."""
    hidden, block = PACKED_CASES[case]
    t_len, held, inner, bf16 = 256, (0, 4), 128, jnp.bfloat16
    experts, gates = routing(t_len, 16, seed=hidden)
    ks = jax.random.split(jax.random.key(hidden), 5)
    x, dy = (jax.random.normal(k, (t_len, hidden), bf16) for k in ks[:2])
    wg, wu = (jax.random.normal(k, (held[1], hidden, inner), bf16)
              * hidden ** -0.5 for k in ks[2:4])
    wd = jax.random.normal(ks[4], (held[1], inner, hidden), bf16) * inner ** -0.5
    tok, gate, tbl, _ = ds.expert_blocks(experts, gates, held, block)
    for x_dtype, packed in ((bf16, True), (jnp.float32, False)):
        assert ge.grouped_expert_tiles(tok.shape[0], hidden, inner, block, bf16,
                                       x_dtype).packed is packed
    ws = (wg, wu, wd)

    @jax.jit
    def kernels(x, dy):
        y = ge.grouped_experts_forward(x, tok, gate, tbl, *ws, block,
                                       interpret=True)
        return (y,) + ge.grouped_experts_backward(x, dy, tok, gate, tbl, *ws,
                                                  block, interpret=True)

    got = kernels(x, dy)
    wide = kernels(x.astype(jnp.float32), dy.astype(jnp.float32))
    want = jax.jit(lambda x, dy: (ds._grouped_loop_fwd(x, tok, gate, tbl, *ws,
                                                       block),)
                   + ds._grouped_loop_bwd(x, dy, tok, gate, tbl, *ws, block))(
                       x, dy)
    assert [a.dtype for a in got] == [bf16, bf16, jnp.float32]
    for name, a, b, c in zip(("y", "dx", "dgate"), got, wide, want):
        b = b.astype(a.dtype)  # float32 x gets y / dx back in float32
        a, b, c = (np.asarray(v, np.float32) for v in (a, b, c))
        assert np.any(a), name
        np.testing.assert_array_equal(a, b, err_msg=name)
        np.testing.assert_allclose(a, c, rtol=0, atol=2.0 ** -7 * np.abs(c).max(),
                                   err_msg=name)


def _normalised_jaxpr_digest(fn, *args) -> str:
    """sha256 of ``fn``'s jaxpr text with addresses and source lines taken
    out: what the program computes, not where its code sits."""
    text = str(jax.make_jaxpr(fn)(*args))
    text = re.sub(r"0x[0-9a-f]+", "0x", text)
    text = re.sub(r"[\w/\.-]+\.py:\d+(:\d+)?", "SRC", text)
    return hashlib.sha256(text.encode()).hexdigest()


# the pair's forward and its vjp through ``_grouped_swiglu`` on the chip's
# branch, the kernels' bodies included, as the code read before bfloat16 rows
# were packed: at widths that are no multiple of 2048 the rows stay float32
# and the program must be that one, equation for equation — tokens, hidden,
# all experts, held, inner, top-k: the DeepSeek cell's layer, and a 3072-wide one
FLOAT32_ROW_DIGESTS = {
    "deepseek_7168": ((16384, 7168, 256, 16, 2048, 8),
                      "98cdeff8761bddcc71110fb726fef336"
                      "a273ba68a6cec97bfd629dcfc9c16625"),
    "hidden_3072": ((2048, 3072, 16, 4, 256, 8),
                    "a5b662c347643021fde284a8c5078ada"
                    "9ebc6ef80f466810bb9abb46ff13058e"),
}


@pytest.mark.parametrize("layer", FLOAT32_ROW_DIGESTS)
def test_float32_rows_trace_to_the_program_they_were(monkeypatch, layer):
    """Traced abstractly (no array is made) with ``jax.default_backend``
    reading "tpu" and the model's ``EXPERT_BLOCK``: the expert pair at a
    width the packed form does not take is the program pinned above."""
    (t_len, hidden, _, held, inner, k), digest = FLOAT32_ROW_DIGESTS[layer]
    block, bf16 = ds.EXPERT_BLOCK, jnp.bfloat16
    assert not ge.grouped_expert_tiles(t_len * k, hidden, inner, block,
                                       bf16).packed
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")

    def fn(x, experts, gates, wg, wu, wd, w):
        def f(x, gates):
            tok, gate, tbl, _ = ds.expert_blocks(experts, gates, (0, held), block)
            return ds._grouped_swiglu(x, tok, gate, tbl, wg, wu, wd, block)

        y, vjp = jax.vjp(f, x, gates)
        return (y,) + vjp(w)

    S = jax.ShapeDtypeStruct
    assert _normalised_jaxpr_digest(
        fn, S((t_len, hidden), bf16), S((t_len, k), jnp.int32),
        S((t_len, k), jnp.float32), S((held, hidden, inner), bf16),
        S((held, hidden, inner), bf16), S((held, inner, hidden), bf16),
        S((t_len, hidden), bf16)) == digest


@pytest.mark.parametrize("hidden,packed,on_the_tpu", [
    (2048, 1.0, True), (1024, 0.0, True), (2048, 0.0, False)])
def test_expert_rows_packed_says_which_rows_the_pair_took(monkeypatch, hidden,
                                                          packed, on_the_tpu):
    """``held_expert_ffn``'s counter: 1 where the dispatch gives the pair
    packed rows (bfloat16 at a width of whole 2048s, on the TPU), 0 where it
    gives float32 rows or the XLA loop runs (the CPU). The kernels are
    steered out — the XLA loop stands in for them — so only the dispatch's
    choice is read."""
    t_len, k, held, inner, bf16 = 256, 8, 4, 128, jnp.bfloat16
    monkeypatch.setattr(ds, "EXPERT_BLOCK", 16)
    if on_the_tpu:
        monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    monkeypatch.setattr(ds, "grouped_experts_forward", ds._grouped_loop_fwd)
    experts, gates = routing(t_len, 16, seed=1)
    ks = jax.random.split(jax.random.key(2), 4)
    swiglu = {"gate_proj": {"kernel": jax.random.normal(ks[0], (held, hidden,
                                                                inner), bf16)},
              "up_proj": {"kernel": jax.random.normal(ks[1], (held, hidden,
                                                              inner), bf16)},
              "down_proj": {"kernel": jax.random.normal(ks[2], (held, inner,
                                                                hidden), bf16)}}
    x = jax.random.normal(ks[3], (t_len, hidden), bf16)
    tiles = ds._experts_kernel_tiles(x, jnp.zeros((t_len * k,), jnp.int32),
                                     swiglu["gate_proj"]["kernel"], 16)
    assert (tiles is not None) is on_the_tpu
    counters = jax.jit(lambda x: ds.held_expert_ffn(
        {"experts": swiglu}, x, experts, gates, (0, held))[2])(x)
    assert float(counters["expert_rows_packed"]) == packed


@pytest.mark.parametrize("seed", range(4))
def test_an_experts_tokens_are_distinct(seed):
    """What the in-place accumulation rests on: whatever top-k routing
    ``held_expert_ffn`` is handed (K distinct experts a token), the valid rows
    of every live block name distinct tokens (and so do all the blocks of one
    expert together), blocks come in ascending expert order, every held
    (token, expert) pair lies in exactly one block, and the rows past a
    block's end in none."""
    t_len, n_experts, held, block = 96, 12, (3, 5), 8
    rng = np.random.default_rng(seed)
    experts, gates = routing(t_len, n_experts, seed,
                             load=rng.uniform(0.05, 4.0, n_experts))
    tok, gate, tbl, count = jax.tree.map(
        np.asarray, ds.expert_blocks(experts, gates, held, block))
    assert tbl["expert"].shape == (t_len * TOP_K // block + held[1],)
    assert tbl["n"] == np.sum(-(-count // block)) <= tbl["expert"].shape[0]
    live = tbl["expert"][:int(tbl["n"])]
    assert np.all(np.diff(live) >= 0)
    seen, of_expert = set(), {}
    for b in range(int(tbl["n"])):
        rows = np.arange(tbl["start"][b], min(tbl["start"][b] + block, tbl["end"][b]))
        assert 0 < len(rows) <= block
        assert len(set(tok[rows])) == len(rows), f"block {b} repeats a token"
        e = held[0] + tbl["expert"][b]
        before = of_expert.setdefault(e, set())
        assert not before & set(tok[rows]), f"expert {e} sees a token twice"
        before |= set(tok[rows])
        for r in rows:
            k = list(np.asarray(experts[tok[r]])).index(e)  # the token chose e
            assert gate[r] == gates[tok[r], k]
            assert (tok[r], e) not in seen
            seen.add((tok[r], e))
    in_held = (np.asarray(experts) >= held[0]) & (np.asarray(experts) < sum(held))
    assert len(seen) == in_held.sum() == count.sum()


def test_the_fit_test_reads_shapes_only():
    """Both cells' shapes fit, the whole expert where it can stay resident
    and a tile of the inner dimension where it cannot; a shape off the tiling
    is refused, and the calls raise rather than run it."""
    bf16 = jnp.bfloat16
    hybrid = ge.grouped_expert_tiles(32768 * 10, 4096, 768, 384, bf16)
    other = ge.grouped_expert_tiles(16384 * 8, 7168, 2048, 384, bf16)
    keye = ge.grouped_expert_tiles(16384 * 8, 2048, 768, 384, bf16)
    command = ge.grouped_expert_tiles(32768 * 8, 4096, 4096, 384, bf16)
    assert hybrid.inner == keye.inner == 768  # resident across an expert's blocks
    assert other.inner < 2048 and 2048 % other.inner == 0
    # 3 x 4096 x 2048 x 2 bytes, double-buffered, is past the budget alone
    assert command.inner == 1024
    assert [t.packed for t in (hybrid, other, keye, command)] == [
        True, False, True, True]
    for tiles in (hybrid, other, keye, command):
        assert max(tiles.fwd_vmem, tiles.bwd_vmem) <= ge._VMEM_BUDGET
    refused = [
        (2048, 64, 128, 8, jnp.float32),      # the tiny size: 64 wide
        (2048, 1024, 96, 8, jnp.float32),     # inner off the lane tiles
        (2048, 1024, 128, 8, bf16),           # bfloat16 rows pack 16 a tile
        (2048, 1024, 128, 12, jnp.float32),   # block off the sublane tile
        (2000, 1024, 128, 8, jnp.float32),    # rows not in whole 1024s
        (1024, 1024, 128, 8, jnp.float32),    # fewer rows than two groups
        (2048, 1536, 128, 8, jnp.float32),    # a row of 12 lane tiles
    ]
    assert ge.grouped_expert_tiles(2048, 1024, 128, 8, jnp.float32) is not None
    for shape in refused:
        assert ge.grouped_expert_tiles(*shape) is None, shape
    x = jnp.zeros((128, 64))
    tok, gate = jnp.zeros((1024,), jnp.int32), jnp.zeros((1024,))
    tbl = {k: jnp.zeros((132,), jnp.int32) for k in ("expert", "start", "end")}
    w = jnp.zeros((2, 64, 128))
    with pytest.raises(ValueError, match="do not apply"):
        ge.grouped_experts_forward(x, tok, gate, dict(tbl, n=jnp.int32(0)), w, w,
                                   jnp.zeros((2, 128, 64)), 8)


@pytest.mark.parametrize("hidden,packed", [
    (1024, False), (2048, True), (3072, False), (4096, True), (7168, False)])
def test_the_row_form_reads_the_width_and_the_dtype(hidden, packed):
    """bfloat16 rows travel packed where a row of words is whole (8, 128)
    tiles — ``hidden`` a multiple of 2048 — and as float32 elsewhere;
    float32 ``x`` never packs. The choice is a function of the shapes and
    dtypes handed in (plain numbers here, no array), and the packed form's
    VMEM is the float32 form's less half of the ``x`` / ``dy`` buffers."""
    bf16 = jnp.bfloat16
    rows, inner, block = 4096 * 8, 256, 384
    tiles = ge.grouped_expert_tiles(rows, hidden, inner, block, bf16)
    assert tiles.packed is packed
    assert tiles == ge.grouped_expert_tiles(rows, hidden, inner, block, bf16,
                                            bf16)
    wide = ge.grouped_expert_tiles(rows, hidden, inner, block, bf16,
                                   jnp.float32)
    assert not wide.packed and wide.inner == tiles.inner
    slab = block * hidden * 4
    assert (wide.fwd_vmem - tiles.fwd_vmem,
            wide.bwd_vmem - tiles.bwd_vmem) == ((slab, 2 * slab) if packed
                                                else (0, 0))
