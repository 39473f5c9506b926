"""Ring attention over a sharded sequence axis (flash-style online softmax +
``ppermute``), with ENGINEERED comm/compute overlap.

The reference has no sequence parallelism — its "sequence" is the frame axis
and it relies on architectural sparsity instead (SURVEY §5.7). For long-video
TPU runs the frame axis shards over the ``frames`` mesh axis, and the dense
f×f temporal attention (/root/reference/tuneavideo/models/attention.py:262-268)
becomes a ring pass: each shard holds its local Q block and rotates K/V blocks
around the ring with ``lax.ppermute``, maintaining flash-attention running
max/denominator so nothing materializes beyond one block pair per step.

Overlap is **explicit, not assumed**. The first version of this module
computed on a block and *then* permuted it inside a ``lax.scan``, claiming
"XLA schedules the ppermute asynchronously" — it does not have the freedom
to: the permute was data-dependent *after* the einsum in the loop body, so
the ICI transfer serialized behind the compute, and the scan issued ``n``
rotations where ``n−1`` suffice (the final pair's payload was discarded).
The rewrite double-buffers the ring the way Ring Attention (Liu et al.,
2023) prescribes:

  * the ``ppermute`` moving block *i+1* is issued **before** the einsum on
    block *i*, so the transfer depends only on the previous hop and XLA's
    async collective pass (``collective-permute-start``/``-done``) can hide
    it under the matmuls;
  * exactly ``n−1`` rotations are issued — the dead final permute pair is
    gone;
  * the rotation loop is **unrolled** (the shard count is static), so the
    scheduler can software-pipeline hops across iterations AND the static
    collective counts the obs layer mines (``obs/comm.py``) are the true
    per-pass counts instead of a scan body counted once.

Variants (``variant=`` / ``VIDEOP2P_RING_VARIANT``):

  * ``"overlap"`` (default) — double-buffered unidirectional ring: ``n−1``
    rotations, 2·(n−1) collective-permutes per pass (K and V), each carrying
    one full K/V block.
  * ``"bidir"`` — bidirectional ring: the local K/V block is split into two
    sequence halves that rotate in OPPOSITE directions, so every hop moves
    half the payload per direction and both ICI directions carry traffic
    concurrently — per-rotation transfer time halves on full-duplex links.
    Same total bytes as ``"overlap"`` (4·(n−1) permutes at half size),
    exact same math (online softmax is order-invariant up to fp rounding).
  * ``"serial"`` — the pre-rewrite schedule (compute-then-permute, ``n``
    rotations including the dead final pair), kept ONLY as the measurable
    baseline for the comm-accounting A/B in the multichip dryrun; never
    the default.

``ring_attention`` is the shard_map-level primitive; ``ring_attention_sharded``
wraps it for callers holding globally-sharded arrays.
"""

from __future__ import annotations

import functools
import os
from typing import Optional

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

__all__ = [
    "RING_VARIANTS",
    "default_ring_variant",
    "ring_attention",
    "ring_attention_sharded",
    "make_ring_temporal_fn",
]

RING_VARIANTS = ("overlap", "bidir", "serial")


def default_ring_variant() -> str:
    """The process-wide default ring schedule: ``VIDEOP2P_RING_VARIANT``
    (one of ``overlap``/``bidir``/``serial``), else ``overlap``."""
    v = os.environ.get("VIDEOP2P_RING_VARIANT", "overlap").strip().lower()
    return v if v in RING_VARIANTS else "overlap"


def _block_update(q32, k_blk, v_blk, scale, m, l, o):
    """One online-softmax accumulation step against a K/V block (exact
    flash-attention rescaling, fp32 accumulators)."""
    s = jnp.einsum("...qd,...kd->...qk", q32, k_blk.astype(jnp.float32)) * scale
    m_new = jnp.maximum(m, s.max(axis=-1))
    p = jnp.exp(s - m_new[..., None])
    corr = jnp.exp(m - m_new)
    l = l * corr + p.sum(axis=-1)
    o = o * corr[..., None] + jnp.einsum(
        "...qk,...kd->...qd", p, v_blk.astype(jnp.float32)
    )
    return m_new, l, o


def ring_attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    *,
    axis_name: str,
    scale: Optional[float] = None,
    variant: Optional[str] = None,
) -> jax.Array:
    """Attention where Q/K/V are sharded on their sequence axis.

    Per-shard shapes (inside ``shard_map``): q (..., Sq, D), k/v (..., Sk, D)
    with the global sequence split over ``axis_name``. Returns the local
    output block (..., Sq, D). Numerically identical to softmax(QKᵀ·scale)V
    over the gathered sequence (online-softmax rescaling is exact; block
    order only moves fp rounding). ``variant`` selects the rotation
    schedule (module docstring); None reads :func:`default_ring_variant`.
    """
    if scale is None:
        scale = q.shape[-1] ** -0.5
    variant = variant if variant is not None else default_ring_variant()
    if variant not in RING_VARIANTS:
        raise ValueError(
            f"ring variant {variant!r} not in {RING_VARIANTS}"
        )
    n = jax.lax.psum(1, axis_name)  # static: the shard count
    fwd = [(i, (i + 1) % n) for i in range(n)]
    bwd = [((i + 1) % n, i) for i in range(n)]

    q32 = q.astype(jnp.float32)
    m = jnp.full(q.shape[:-1], -jnp.inf, jnp.float32)
    l = jnp.zeros(q.shape[:-1], jnp.float32)
    o = jnp.zeros(q32.shape, jnp.float32)

    # a 1-wide ring or a local K/V too small to split degenerates: bidir
    # needs two nonempty sequence halves to rotate
    if variant == "bidir" and (n < 2 or k.shape[-2] < 2):
        variant = "overlap"

    if variant == "serial":
        # the pre-rewrite schedule, kept as the measured baseline: compute
        # FIRST, then permute — the transfer serializes behind the einsum —
        # and n rotations are issued, the last pair's payload discarded.
        # The original lax.scan CARRIED the dead pair out of the loop, so
        # the final transfer executed; unrolled, XLA's DCE would silently
        # delete it and grant this baseline the n−1 fix it exists to
        # measure against. The 0·sum tie keeps the pair live the way the
        # scan carry did (XLA cannot fold 0·x without proving x finite);
        # numerically it adds an exact +0.0.
        k_blk, v_blk = k, v
        for _ in range(n):
            m, l, o = _block_update(q32, k_blk, v_blk, scale, m, l, o)
            k_blk = jax.lax.ppermute(k_blk, axis_name, fwd)
            v_blk = jax.lax.ppermute(v_blk, axis_name, fwd)
        o = o + 0.0 * (
            k_blk.astype(jnp.float32).sum() + v_blk.astype(jnp.float32).sum()
        )
    elif variant == "overlap":
        # double-buffered: hop t+1 is issued BEFORE the einsum on block t
        # (the permute depends only on the previous hop, never on compute),
        # and only n−1 hops exist — the final block computes, no dead pair
        k_blk, v_blk = k, v
        for t in range(n):
            if t < n - 1:
                k_nxt = jax.lax.ppermute(k_blk, axis_name, fwd)
                v_nxt = jax.lax.ppermute(v_blk, axis_name, fwd)
            m, l, o = _block_update(q32, k_blk, v_blk, scale, m, l, o)
            if t < n - 1:
                k_blk, v_blk = k_nxt, v_nxt
    else:  # bidir
        # the local block splits into two sequence halves rotating in
        # opposite directions: after t hops this shard holds the A-half of
        # block (i−t) and the B-half of block (i+t) — over n−1 hops every
        # half of every block is visited exactly once. Each hop moves HALF
        # the payload per direction, both ICI directions concurrently.
        half = k.shape[-2] // 2
        ka, kb = k[..., :half, :], k[..., half:, :]
        va, vb = v[..., :half, :], v[..., half:, :]
        for t in range(n):
            if t < n - 1:
                ka_n = jax.lax.ppermute(ka, axis_name, fwd)
                va_n = jax.lax.ppermute(va, axis_name, fwd)
                kb_n = jax.lax.ppermute(kb, axis_name, bwd)
                vb_n = jax.lax.ppermute(vb, axis_name, bwd)
            m, l, o = _block_update(q32, ka, va, scale, m, l, o)
            m, l, o = _block_update(q32, kb, vb, scale, m, l, o)
            if t < n - 1:
                ka, va, kb, vb = ka_n, va_n, kb_n, vb_n
    return (o / l[..., None]).astype(q.dtype)


def ring_attention_sharded(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    mesh: Mesh,
    *,
    axis_name: str = "frames",
    seq_axis: int = -2,
    variant: Optional[str] = None,
) -> jax.Array:
    """shard_map wrapper: q/k/v are global arrays whose ``seq_axis`` is (or
    will be) sharded over ``axis_name``; batch-like leading axes replicate."""
    ndim = q.ndim
    seq_axis = seq_axis % ndim
    spec_parts = [None] * ndim
    spec_parts[seq_axis] = axis_name
    spec = P(*spec_parts)

    fn = functools.partial(ring_attention, axis_name=axis_name, variant=variant)
    # replication checking stays off: the ring's collectives confuse it
    return jax.shard_map(
        fn, mesh=mesh, in_specs=(spec, spec, spec), out_specs=spec,
        check_vma=False,
    )(q, k, v)


def make_ring_temporal_fn(
    mesh: Mesh, *, axis_name: str = "frames", variant: Optional[str] = None
):
    """Temporal-attention kernel for the UNet's ``temporal_attention_fn`` seam
    (models/attention.py): (q, k, v) of shape (B·N, H, F, D) with the frame
    axis sharded over ``axis_name`` → ring attention instead of the all-gather
    GSPMD would otherwise insert for the dense f×f site. Uncontrolled passes
    only (training / inversion / plain sampling); controlled sites materialize
    probabilities and stay dense. ``variant`` pins the rotation schedule
    (None → :func:`default_ring_variant` at call time)."""

    def fn(q: jax.Array, k: jax.Array, v: jax.Array) -> jax.Array:
        return ring_attention_sharded(
            q, k, v, mesh, axis_name=axis_name, seq_axis=-2, variant=variant
        )

    return fn
