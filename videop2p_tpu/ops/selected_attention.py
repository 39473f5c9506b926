"""Selected-key attention of the token model: a Pallas forward / backward pair.

``models/deepseek.py`` attends each query to the keys its index scorer
selected — a (T, T) bool mask, causal, 2048 keys a row. As masked dense XLA
the float32 ``(heads, rows, keys)`` score tile goes through HBM about seven
times a forward (PERF.md §6, PR 29). Here it never leaves VMEM:

  * **forward** ``lm_selected_attention`` — flash-style: for a query tile the
    key tiles up to its causal diagonal stream through, running max / sum and
    the output accumulator in float32 scratch; outputs ``o`` and the row
    log-sum-exp.
  * **backward** ``lm_selected_attention_bwd`` — one kernel: the
    probabilities are recomputed from the log-sum-exp, dK / dV accumulate in
    float32 in their output blocks (resident while a key tile's query tiles
    stream), dQ in a float32 output block that holds every query tile of the
    cell's heads for the whole call.

Both hold the score tile TRANSPOSED, (keys, queries), as
``ops/attention._fused_bwd_kernel`` does: every product is a plain or a
transposed-RHS matmul, the softmax reduces over sublanes and the row
statistics are (1, queries) rows. The grid's second axis walks only the
(query tile, key tile) pairs on or under the causal diagonal (two
scalar-prefetched tables), so no step is spent on a skipped tile, and the
selection tile — one byte a pair — is read once for all the heads of a cell.

The selection is optional: ``causal_attention`` (grouped-query heads, no
mask operand) runs the same two kernels with the causal rule taken from the
tile's own position — the other token families' attention layers, under the
scope ``lm.attention``. With a ``window`` (key s is seen by query t iff
0 <= t - s < window) the step tables hold only the tile pairs that
intersect that band, the in-tile rule is the band's, and the scope is
``lm.window_attention``; without one the program is the causal one, as it
was before the window existed. ``grouped_selected_attention`` hands the
grouped-query heads the selection tile again (one selection for every head,
scope ``lm.sparse_attention``): the MLA-shaped call's mask with the grouped
calls' spread of the key / value heads and sum over a group.

Precision as ``models.deepseek._attend``: float32 scores, statistics, dS and
accumulators; the operands' dtype only as matmul operands. No key is
dropped: the mask is consumed as handed over, but it must be causal
(``mask[t, s]`` False for s > t), because tiles above the diagonal are not
visited.
"""

from __future__ import annotations

import functools
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.ad_checkpoint import checkpoint_name

__all__ = ["selected_key_attention", "causal_attention",
           "grouped_selected_attention", "selected_attention_tiles",
           "causal_tile_pairs", "Tiles", "KEPT_NAMES", "keep_attention_outputs"]

_SCOPE = "lm.sparse_attention"

# (queries, keys) a tile, first that divides the token count wins. On the v5e
# at 16384 tokens, 8 heads, 128 / 64 / 128 (PERF.md §6, PR 29, has the
# readings per tile); tools/bench_attention.py ``selected`` repeats them.
_TILES = ((512, 512), (256, 256), (128, 128))
_HEADS = (8, 4, 2, 1)  # heads a grid cell, largest that divides and fits
# A v5e core has 128 MiB of VMEM. The backward's resident dQ is most of what
# it holds; a shape over the budget with one head a cell is refused.
_VMEM_BUDGET = 96 * 1024 * 1024
_STEP_TABLE_BYTES = 256 * 1024  # the two int32 tables live in SMEM
# the running max starts here, not at -inf: a query whose first key tiles
# hold none of its keys would otherwise read exp(-inf - -inf)
_FLOOR = -1e30

_NT = (((1,), (1,)), ((), ()))
_NN = (((1,), (0,)), ((), ()))

# What the forward kernel hands the backward one, by name: oᵀ and the row
# log-sum-exp, tagged INSIDE the forward rules (outside them the residual
# the backward reads is the untagged value). A layer wrapped in
# ``jax.checkpoint(layer, policy=keep_attention_outputs)`` keeps the two
# across its recompute and nothing else, so the forward kernel runs once a
# layer and step; where the pair did not run no value carries the names and
# the policy keeps nothing.
KEPT_NAMES = ("lm_selected_attention_ot", "lm_selected_attention_lse")
keep_attention_outputs = jax.checkpoint_policies.save_only_these_names(
    *KEPT_NAMES)


def _kept(ot, lse):
    return tuple(map(checkpoint_name, (ot, lse), KEPT_NAMES))


class Tiles(NamedTuple):
    q: int          # queries a tile
    k: int          # keys a tile
    fwd_heads: int  # heads a grid cell of the forward
    bwd_heads: int  # heads a grid cell of the backward


def _lanes(n: int) -> int:
    return -(-n // 128) * 128


def _fwd_vmem_bytes(heads: int, bq: int, bk: int, qk: int, vd: int,
                    itemsize: int) -> int:
    """VMEM of one forward grid cell, from its shapes."""
    streams = 2 * heads * ((bq + bk) * _lanes(qk) + 2 * vd * max(bq, bk)) * itemsize
    stats = 2 * 2 * heads * 8 * bq * 4  # log-sum-exp out; m, l scratch
    acc = heads * vd * bq * 4
    # S, P in float32, P as operand, the selection as int32 and as a mask
    tiles = bq * bk * (4 * 4 + itemsize)
    return streams + stats + acc + 2 * bq * bk + tiles


def _bwd_vmem_bytes(heads: int, t_len: int, bq: int, bk: int, qk: int, vd: int,
                    itemsize: int) -> int:
    """VMEM of one backward grid cell, from its shapes."""
    dq = 2 * heads * qk * t_len * 4  # resident for the whole call
    streams = 2 * heads * ((bq + 2 * bk) * _lanes(qk)
                           + (bq + bk) * _lanes(vd)) * itemsize
    stats = 2 * 2 * heads * 8 * bq * 4
    acc = 2 * heads * bk * (_lanes(qk) + _lanes(vd)) * 4  # dK, dV
    # P, dP, dS in float32, P and dS as operands, the selection
    tiles = bq * bk * (5 * 4 + 2 * itemsize)
    return dq + streams + stats + acc + 2 * bq * bk + tiles


def selected_attention_tiles(t_len: int, heads: int, nope: int, rope: int,
                             v_dim: int, dtype) -> Optional[Tiles]:
    """The tiles of the kernel pair for ``t_len`` tokens of ``heads`` heads
    (widths ``nope`` + ``rope`` and ``v_dim``), or None where it does not
    apply: head widths off the lane tiles, a token count no tile divides, or
    a backward over the VMEM budget. The one fit test the model's dispatch
    and the calls' ``vmem_limit_bytes`` share."""
    if nope % 128 or v_dim % 128 or rope % 16:
        return None
    qk, itemsize = nope + rope, jnp.dtype(dtype).itemsize
    for bq, bk in _TILES:
        if t_len % bq or t_len % bk:
            continue
        if 8 * len(_causal_steps(t_len, bq, bk)[0]) > _STEP_TABLE_BYTES:
            continue

        def heads_that_fit(vmem_bytes):
            return next((h for h in _HEADS if heads % h == 0
                         and vmem_bytes(h) <= _VMEM_BUDGET), None)

        fwd = heads_that_fit(
            lambda h: _fwd_vmem_bytes(h, bq, bk, qk, v_dim, itemsize))
        bwd = heads_that_fit(
            lambda h: _bwd_vmem_bytes(h, t_len, bq, bk, qk, v_dim, itemsize))
        if fwd and bwd:
            return Tiles(bq, bk, fwd, bwd)
    return None


@functools.lru_cache(maxsize=None)
def _causal_steps(t_len: int, bq: int, bk: int, key_major: bool = False,
                  window: Optional[int] = None):
    """(query tile, key tile) of every pair on or under the causal diagonal
    — with a ``window``, of those that hold a pair 0 <= t - s < window:
    query-major (a query tile's key tiles in a row) or key-major."""
    reach = t_len if window is None else window
    pairs = [(qi, ki) for qi in range(t_len // bq) for ki in range(t_len // bk)
             if ki * bk < (qi + 1) * bq and (ki + 1) * bk - 1 > qi * bq - reach]
    if key_major:
        pairs.sort(key=lambda p: (p[1], p[0]))
    qi, ki = np.asarray(pairs, np.int32).T
    return qi, ki


def causal_tile_pairs(t_len: int, tiles: "Tiles",
                      window: Optional[int] = None) -> int:
    """How many (query tile, key tile) steps a call of the pair walks a cell
    of heads: the causal pairs, or with a ``window`` those in its band."""
    return len(_causal_steps(t_len, tiles.q, tiles.k, False,
                             _band(t_len, window))[0])


def _band(t_len: int, window: Optional[int]) -> Optional[int]:
    """A window that reaches every earlier key is no window: the causal
    program, to the bit."""
    return None if window is None or window >= t_len else int(window)


def _keep(mask_ref, qi, ki, bq: int, bk: int, window: Optional[int] = None):
    """(bk, bq) bool: the keys of tile ``ki`` each query of tile ``qi``
    attends — the selection tile as handed over, or, with no selection, the
    causal rule from the tile's position (inside a ``window``:
    queries - window < keys <= queries)."""
    if mask_ref is not None:
        return mask_ref[...].astype(jnp.int32) != 0
    keys = ki * bk + lax.broadcasted_iota(jnp.int32, (bk, bq), 0)
    queries = qi * bq + lax.broadcasted_iota(jnp.int32, (bk, bq), 1)
    if window is None:
        return keys <= queries
    return (keys <= queries) & (keys > queries - window)


def _fwd_kernel(qi_ref, ki_ref, q_ref, k_ref, vt_ref, *refs, scale: float,
                bq: int, bk: int, masked: bool, window: Optional[int] = None):
    """One (query tile, key tile) of every head of the cell: the online
    softmax's update of the running max ``m``, sum ``l`` and output
    ``acc`` (transposed, (v_dim, queries)); the last key tile of a query
    tile writes ``o`` and the log-sum-exp. A query tile's first key tile is
    tile 0, or with a ``window`` the one that holds the first key its first
    query sees; its last is the diagonal's either way."""
    from jax.experimental import pallas as pl

    mask_ref = refs[0] if masked else None
    ot_ref, lse_ref, m_ref, l_ref, acc_ref = refs[int(masked):]
    step = pl.program_id(1)
    qi, ki = qi_ref[step], ki_ref[step]
    first = 0 if window is None else lax.div(
        jnp.maximum(qi * bq - (window - 1), 0), bk)

    @pl.when(ki == first)
    def _():
        m_ref[...] = jnp.full_like(m_ref, _FLOOR)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    keep = _keep(mask_ref, qi, ki, bq, bk, window)  # (bk, bq)
    for h in range(q_ref.shape[0]):
        st = lax.dot_general(k_ref[h], q_ref[h], _NT,
                             preferred_element_type=jnp.float32) * scale
        st = jnp.where(keep, st, -jnp.inf)
        m_prev = m_ref[h]  # (1, bq)
        m_new = jnp.maximum(m_prev, jnp.max(st, axis=0, keepdims=True))
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.exp(st - m_new)
        l_ref[h] = alpha * l_ref[h] + jnp.sum(p, axis=0, keepdims=True)
        acc_ref[h] = alpha * acc_ref[h] + lax.dot_general(
            vt_ref[h], p.astype(vt_ref.dtype), _NN,
            preferred_element_type=jnp.float32)
        m_ref[h] = m_new

    @pl.when(ki == ((qi + 1) * bq - 1) // bk)
    def _():
        l = l_ref[...]
        l = jnp.where(l == 0.0, 1.0, l)  # a query with no key at all: o = 0
        ot_ref[...] = (acc_ref[...] / l).astype(ot_ref.dtype)
        lse_ref[...] = m_ref[...] + jnp.log(l)


def _forward_call(q, k, v, mask_t, tiles: Tiles, scale: float, interpret: bool,
                  window: Optional[int] = None):
    """q, k (H, T, qk); v (H, T, v_dim); mask_t (T keys, T queries) int8, or
    None (causal, inside ``window`` where one is given) → oᵀ (H, v_dim, T) in
    ``v``'s dtype, log-sum-exp (H, 1, T) float32."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    heads, t_len, qk = q.shape
    vd = v.shape[2]
    bq, bk, hb, _ = tiles
    qi, ki = _causal_steps(t_len, bq, bk, False, window)
    masked = mask_t is not None
    # the selection tile and its operand: there, or left out of the call
    mask_spec = [pl.BlockSpec((bk, bq), lambda g, s, qi, ki: (ki[s], qi[s]))
                 ] * masked
    mask_operand = [mask_t] * masked
    return pl.pallas_call(
        functools.partial(_fwd_kernel, scale=scale, bq=bq, bk=bk,
                          masked=masked, window=window),
        # explicit name: the compiled program's custom call and the trace
        # events carry it (obs/introspect.tpu_custom_call_counts)
        name="lm_selected_attention",
        out_shape=(jax.ShapeDtypeStruct((heads, vd, t_len), v.dtype),
                   jax.ShapeDtypeStruct((heads, 1, t_len), jnp.float32)),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(heads // hb, len(qi)),
            in_specs=[
                pl.BlockSpec((hb, bq, qk), lambda g, s, qi, ki: (g, qi[s], 0)),
                pl.BlockSpec((hb, bk, qk), lambda g, s, qi, ki: (g, ki[s], 0)),
                pl.BlockSpec((hb, vd, bk), lambda g, s, qi, ki: (g, 0, ki[s])),
                *mask_spec,
            ],
            # constant over a query tile's key tiles: written back once
            out_specs=(
                pl.BlockSpec((hb, vd, bq), lambda g, s, qi, ki: (g, 0, qi[s])),
                pl.BlockSpec((hb, 1, bq), lambda g, s, qi, ki: (g, 0, qi[s])),
            ),
            scratch_shapes=[pltpu.VMEM((hb, 1, bq), jnp.float32),
                            pltpu.VMEM((hb, 1, bq), jnp.float32),
                            pltpu.VMEM((hb, vd, bq), jnp.float32)],
        ),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
            vmem_limit_bytes=_fwd_vmem_bytes(hb, bq, bk, qk, vd, q.dtype.itemsize),
        ),
        interpret=interpret,  # CPU-testable (tests/test_selected_attention.py)
    )(jnp.asarray(qi), jnp.asarray(ki), q, k, v.transpose(0, 2, 1),
      *mask_operand)


def _bwd_kernel(qi_ref, ki_ref, q_ref, do_ref, k_ref, kt_ref, v_ref, lse_ref,
                delta_ref, *refs, scale: float, bq: int, bk: int,
                masked: bool, window: Optional[int] = None):
    """One (key tile, query tile) of every head of the cell: Pᵀ from the saved
    log-sum-exp, dSᵀ = Pᵀ ∘ (dPᵀ − delta), and its share of dV, dK (their
    blocks stay resident over the key tile's query tiles) and dQᵀ (its block
    holds every query tile for the whole call). ``scale`` on dQ and dK is
    the caller's. A key tile's first query tile is the diagonal's, with a
    ``window`` or without; the window only ends its walk sooner."""
    from jax.experimental import pallas as pl

    mask_ref = refs[0] if masked else None
    dqt_ref, dk_ref, dv_ref = refs[int(masked):]
    step = pl.program_id(1)
    qi, ki = qi_ref[step], ki_ref[step]

    @pl.when(step == 0)
    def _():
        dqt_ref[...] = jnp.zeros_like(dqt_ref)

    @pl.when(qi == (ki * bk) // bq)
    def _():
        dk_ref[...] = jnp.zeros_like(dk_ref)
        dv_ref[...] = jnp.zeros_like(dv_ref)

    keep = _keep(mask_ref, qi, ki, bq, bk, window)  # (bk, bq)
    for h in range(q_ref.shape[0]):
        q, do = q_ref[h], do_ref[h]  # (bq, qk), (bq, v_dim)
        st = lax.dot_general(k_ref[h], q, _NT,
                             preferred_element_type=jnp.float32) * scale
        pt = jnp.exp(jnp.where(keep, st, -jnp.inf) - lse_ref[h])  # Pᵀ (bk, bq)
        dv_ref[h] += lax.dot_general(pt.astype(do.dtype), do, _NN,
                                     preferred_element_type=jnp.float32)
        dpt = lax.dot_general(v_ref[h], do, _NT,
                              preferred_element_type=jnp.float32)
        # dSᵀ, rounded as an operand only
        dst = (pt * (dpt - delta_ref[h])).astype(q.dtype)
        dk_ref[h] += lax.dot_general(dst, q, _NN,
                                     preferred_element_type=jnp.float32)
        dqt_ref[h, qi] += lax.dot_general(kt_ref[h], dst, _NN,
                                          preferred_element_type=jnp.float32)


def _backward_call(q, do, k, v, lse, delta, mask_t, tiles: Tiles,
                   scale: float, interpret: bool,
                   window: Optional[int] = None):
    """q, k (H, T, qk); do, v (H, T, v_dim); lse, delta (H, 1, T); mask_t as
    the forward's →
    dQᵀ (H, T / bq, qk, bq), dK (H, T, qk), dV (H, T, v_dim), float32 and
    unscaled (the caller scales and rounds them)."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    heads, t_len, qk = q.shape
    vd = v.shape[2]
    bq, bk, _, hb = tiles
    qi, ki = _causal_steps(t_len, bq, bk, True, window)

    def rows_q(width):
        return pl.BlockSpec((hb, bq, width), lambda g, s, qi, ki: (g, qi[s], 0))

    def rows_k(width):
        return pl.BlockSpec((hb, bk, width), lambda g, s, qi, ki: (g, ki[s], 0))

    stat = pl.BlockSpec((hb, 1, bq), lambda g, s, qi, ki: (g, 0, qi[s]))
    masked = mask_t is not None
    # the selection tile and its operand: there, or left out of the call
    mask_spec = [pl.BlockSpec((bk, bq), lambda g, s, qi, ki: (ki[s], qi[s]))
                 ] * masked
    mask_operand = [mask_t] * masked
    return pl.pallas_call(
        functools.partial(_bwd_kernel, scale=scale, bq=bq, bk=bk,
                          masked=masked, window=window),
        name="lm_selected_attention_bwd",
        out_shape=(
            jax.ShapeDtypeStruct((heads, t_len // bq, qk, bq), jnp.float32),
            jax.ShapeDtypeStruct((heads, t_len, qk), jnp.float32),
            jax.ShapeDtypeStruct((heads, t_len, vd), jnp.float32),
        ),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(heads // hb, len(qi)),
            in_specs=[
                rows_q(qk), rows_q(vd), rows_k(qk),
                pl.BlockSpec((hb, qk, bk), lambda g, s, qi, ki: (g, 0, ki[s])),
                rows_k(vd), stat, stat, *mask_spec,
            ],
            out_specs=(
                # constant along the step axis: one resident accumulator of
                # every query tile, written back once per cell of heads
                pl.BlockSpec((hb, t_len // bq, qk, bq),
                             lambda g, s, qi, ki: (g, 0, 0, 0)),
                # constant over a key tile's query tiles
                rows_k(qk), rows_k(vd),
            ),
        ),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
            vmem_limit_bytes=_bwd_vmem_bytes(hb, t_len, bq, bk, qk, vd,
                                             q.dtype.itemsize),
        ),
        interpret=interpret,
    )(jnp.asarray(qi), jnp.asarray(ki), q, do, k, k.transpose(0, 2, 1), v,
      lse, delta, *mask_operand)


def _tiles_of(q_nope, q_rope, v) -> Tiles:
    t_len, heads, nope = q_nope.shape
    tiles = selected_attention_tiles(t_len, heads, nope, q_rope.shape[-1],
                                     v.shape[-1], q_nope.dtype)
    if tiles is None:
        raise ValueError(
            "selected_key_attention does not apply to q_nope "
            f"{q_nope.shape}, q_rope {q_rope.shape}, v {v.shape}: ask "
            "selected_attention_tiles first and keep the XLA path where it "
            "returns None")
    return tiles


def _heads_first(q_nope, q_rope, k_nope, k_rope, v):
    """(T, H, ·) operands → q, k (H, T, nope + rope) with the one rotary key
    handed to every head, v (H, T, v_dim)."""
    q = jnp.concatenate([q_nope, q_rope], axis=-1)
    k = jnp.concatenate(
        [k_nope, jnp.broadcast_to(k_rope[:, None], q_rope.shape)], axis=-1)
    return q.transpose(1, 0, 2), k.transpose(1, 0, 2), v.transpose(1, 0, 2)


@functools.partial(jax.custom_vjp, nondiff_argnums=(6, 7))
def selected_key_attention(q_nope, q_rope, k_nope, k_rope, v, mask,
                           scale: float, interpret: bool = False):
    """softmax over the selected keys, for every head: ``q_nope``, ``k_nope``
    (T, H, nope), ``q_rope`` (T, H, rope), ``k_rope`` (T, rope) shared by the
    heads, ``v`` (T, H, v_dim), ``mask`` (T, T) bool and causal → (T, H,
    v_dim). Equal to ``models.deepseek._attend`` on the same operands;
    differentiable in all but the mask. Raises where
    :func:`selected_attention_tiles` refuses the shape."""
    return _attention_fwd(q_nope, q_rope, k_nope, k_rope, v, mask, scale,
                          interpret)[0]


def _attention_fwd(q_nope, q_rope, k_nope, k_rope, v, mask, scale, interpret):
    tiles = _tiles_of(q_nope, q_rope, v)
    with jax.named_scope(_SCOPE):
        q, k, vh = _heads_first(q_nope, q_rope, k_nope, k_rope, v)
        mask_t = mask.T.astype(jnp.int8)
        ot, lse = _kept(*_forward_call(q, k, vh, mask_t, tiles, scale,
                                       interpret))
        o = ot.transpose(2, 0, 1)
    return o, (q_nope, q_rope, k_nope, k_rope, v, mask_t, o, lse)


def _attention_bwd(scale, interpret, res, g):
    q_nope, q_rope, k_nope, k_rope, v, mask_t, o, lse = res
    tiles = _tiles_of(q_nope, q_rope, v)
    t_len, heads, nope = q_nope.shape
    # its own scope: the transposed ops of a custom_vjp do not inherit the
    # caller's, and sparse_attention_ms.tune would read the forward only
    with jax.named_scope(_SCOPE):
        q, k, vh = _heads_first(q_nope, q_rope, k_nope, k_rope, v)
        delta = jnp.sum(o.astype(jnp.float32) * g.astype(jnp.float32), axis=-1)
        dqt, dk, dv = _backward_call(
            q, g.transpose(1, 0, 2), k, vh, lse, delta.T[:, None, :], mask_t,
            tiles, scale, interpret)
        dq = dqt.transpose(1, 3, 0, 2).reshape(t_len, heads, -1) * scale
        dk = dk.transpose(1, 0, 2) * scale
        return (dq[..., :nope].astype(q_nope.dtype),
                dq[..., nope:].astype(q_rope.dtype),
                dk[..., :nope].astype(k_nope.dtype),
                jnp.sum(dk[..., nope:], axis=1).astype(k_rope.dtype),
                dv.transpose(1, 0, 2).astype(v.dtype), None)


selected_key_attention.defvjp(_attention_fwd, _attention_bwd)


# ------------------------------------------------- no selection: causal, GQA

_CAUSAL_SCOPE = "lm.attention"
_WINDOW_SCOPE = "lm.window_attention"


def _causal_tiles(q) -> Tiles:
    t_len, heads, width = q.shape
    tiles = selected_attention_tiles(t_len, heads, width, 0, width, q.dtype)
    if tiles is None:
        raise ValueError(
            f"causal_attention does not apply to q {q.shape}: ask "
            "selected_attention_tiles first and keep the XLA path where it "
            "returns None")
    return tiles


def _grouped_heads_first(q, k, v):
    """(T, H, D) queries and (T, H_kv, D) keys / values → (H, T, D) each,
    every key / value head handed to the H / H_kv query heads it serves."""
    group = q.shape[1] // k.shape[1]
    spread = lambda a: jnp.repeat(a.transpose(1, 0, 2), group, axis=0)  # noqa: E731
    return q.transpose(1, 0, 2), spread(k), spread(v)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5))
def causal_attention(q, k, v, scale: float, interpret: bool = False,
                     window: Optional[int] = None):
    """Causal softmax attention with grouped-query heads and NO selection:
    ``q`` (T, H, D), ``k``, ``v`` (T, H_kv, D) with H a multiple of H_kv
    (query head ``i`` reads key / value head ``i // (H / H_kv)``) → (T, H,
    D). The kernel pair above without its mask operand; differentiable in
    all three. With a ``window`` key s is seen by query t iff
    0 <= t - s < window: only the tile pairs that intersect that band are
    walked, forward and backward, and the ops carry the scope
    ``lm.window_attention``; a window that reaches every earlier key gives
    the causal result to the bit. Raises where
    :func:`selected_attention_tiles` refuses the shape (asked as ``(T, H, D,
    0, D)``)."""
    return _causal_fwd(q, k, v, scale, interpret, window)[0]


def _grouped_fwd(q, k, v, mask, scale, interpret, window, scope):
    """The forward of both grouped-head calls: ``mask`` (T, T) bool or None
    → o (T, H, D), the log-sum-exp and the selection as the kernels take it
    (None without one)."""
    tiles = _causal_tiles(q)
    with jax.named_scope(scope):
        mask_t = None if mask is None else mask.T.astype(jnp.int8)
        qh, kh, vh = _grouped_heads_first(q, k, v)
        ot, lse = _kept(*_forward_call(qh, kh, vh, mask_t, tiles, scale,
                                       interpret, _band(q.shape[0], window)))
        o = ot.transpose(2, 0, 1)
    return o, lse, mask_t


def _grouped_bwd(q, k, v, o, lse, mask_t, g, scale, interpret, window, scope):
    """(dq, dk, dv) of :func:`_grouped_fwd`: the backward kernel over every
    query head, then each key / value head's group summed."""
    tiles = _causal_tiles(q)
    t_len, heads, width = q.shape
    kv_heads = k.shape[1]
    with jax.named_scope(scope):
        qh, kh, vh = _grouped_heads_first(q, k, v)
        delta = jnp.sum(o.astype(jnp.float32) * g.astype(jnp.float32), axis=-1)
        dqt, dk, dv = _backward_call(
            qh, g.transpose(1, 0, 2), kh, vh, lse, delta.T[:, None, :], mask_t,
            tiles, scale, interpret, _band(t_len, window))
        dq = dqt.transpose(1, 3, 0, 2).reshape(t_len, heads, width) * scale

        def gathered(d):  # (H, T, D) → (T, H_kv, D): a group's heads summed
            return jnp.sum(d.reshape(kv_heads, heads // kv_heads, t_len, width),
                           axis=1).transpose(1, 0, 2)

        return (dq.astype(q.dtype), (gathered(dk) * scale).astype(k.dtype),
                gathered(dv).astype(v.dtype))


def _causal_fwd(q, k, v, scale, interpret, window):
    o, lse, _ = _grouped_fwd(q, k, v, None, scale, interpret, window,
                             _CAUSAL_SCOPE if window is None else _WINDOW_SCOPE)
    return o, (q, k, v, o, lse)


def _causal_bwd(scale, interpret, window, res, g):
    return _grouped_bwd(*res, None, g, scale, interpret, window,
                        _CAUSAL_SCOPE if window is None else _WINDOW_SCOPE)


causal_attention.defvjp(_causal_fwd, _causal_bwd)


# --------------------------------------- a selection over grouped-query heads


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5))
def grouped_selected_attention(q, k, v, mask, scale: float,
                               interpret: bool = False):
    """Softmax over the SELECTED keys with grouped-query heads: ``q`` (T, H,
    D), ``k``, ``v`` (T, H_kv, D) with H a multiple of H_kv (query head ``i``
    reads key / value head ``i // (H / H_kv)``), ``mask`` (T, T) bool and
    causal, the same for every head → (T, H, D). The kernel pair with its
    mask operand, the heads spread and gathered as :func:`causal_attention`
    does; differentiable in all but the mask, under the scope
    ``lm.sparse_attention``. Raises where :func:`selected_attention_tiles`
    refuses the shape (asked as ``(T, H, D, 0, D)``)."""
    return _grouped_fwd(q, k, v, mask, scale, interpret, None, _SCOPE)[0]


def _selected_fwd(q, k, v, mask, scale, interpret):
    o, lse, mask_t = _grouped_fwd(q, k, v, mask, scale, interpret, None, _SCOPE)
    return o, (q, k, v, o, lse, mask_t)


def _selected_bwd(scale, interpret, res, g):
    return _grouped_bwd(*res, g, scale, interpret, None, _SCOPE) + (None,)


grouped_selected_attention.defvjp(_selected_fwd, _selected_bwd)
