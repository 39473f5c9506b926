"""One-pass fused GroupNorm(+SiLU) for TPU.

XLA lowers a GroupNorm as two slab
traversals plus a write: a stats pass (read x, convert bf16→f32, reduce)
and an apply pass (read x again, normalize, write y). When one sample's
(rows, channels) slab fits VMEM, a Pallas kernel can keep the slab
resident and do both in ONE traversal — read once, write once — removing
a third of the site's HBM traffic, and fusing the activation for free.

Reference semantics (torch ``nn.GroupNorm`` used all over
/root/reference/tuneavideo/models/resnet.py:147-152 and attention.py:94):
per-sample, per-group mean/variance over (rows × channels-in-group),
biased variance, f32 statistics regardless of activation dtype.

The kernel covers the sites whose slab fits the 3 MiB
``_DEFAULT_MAX_SLAB_BYTES`` gate (well inside the ~16 MB/core VMEM, with
pipelining headroom):

* every per-frame transformer-entry GN (frames folded into batch —
  attention.py:361-368): 64²×320 = 2.6 MB … 16²×1280 = 0.65 MB;
* the 8-frame frame-pooled resnet GN at 8² (1.3 MB).

Above the gate the XLA path runs: the frame-pooled 16² slab (5.2 MB) and
the 24-frame pooled 8² slab (~3.9 MB) exceed 3 MiB and always take
two-pass XLA — raise ``max_slab_bytes`` deliberately if a deployment wants
to trade VMEM pressure for fusing them.

The big frame-pooled resnet slabs (64²: 21–63 MB, 32²: 10–31 MB) CANNOT be
single-pass on this hardware: statistics need the full slab before the
first normalized element can be written, and a slab larger than VMEM
therefore must be read twice — once for stats, once for apply — which is
exactly XLA's schedule. Those sites are already at their traversal floor.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax

__all__ = [
    "fused_group_norm",
    "group_norm_reference",
    "fits_fused_group_norm",
]

# input-resident slab budget: in + out blocks, double-buffered by the
# pipeline, plus per-tile f32 temporaries must stay inside ~16 MB VMEM
_DEFAULT_MAX_SLAB_BYTES = 3 * 1024 * 1024
_ROW_TILE = 256


def fits_fused_group_norm(
    rows: int, channels: int, dtype=jnp.bfloat16,
    max_slab_bytes: int = _DEFAULT_MAX_SLAB_BYTES,
) -> bool:
    """Whether one sample's (rows, channels) slab is VMEM-resident-able."""
    return (
        rows % _ROW_TILE == 0
        and rows * channels * jnp.dtype(dtype).itemsize <= max_slab_bytes
    )


def _gn_kernel(x_ref, scale_ref, bias_ref, gmat_ref, o_ref, *,
               eps: float, rows: int, act: str):
    """One grid cell = one statistics sample. The (rows, C) slab sits
    resident in VMEM; stats accumulate in f32 over row tiles, group
    reduction and the channel broadcast-back both ride tiny matmuls with
    the (C, G) one-hot group matrix (layout-friendly on Mosaic — no
    (G, C/G) reshapes of non-lane-aligned widths), then the apply streams
    row tiles back out with the activation fused."""
    from jax.experimental import pallas as pl

    c = x_ref.shape[-1]
    n_tiles = rows // _ROW_TILE

    def pl_dslice(i):
        return pl.dslice(i * _ROW_TILE, _ROW_TILE)

    # f32 per-channel accumulators over row tiles (bf16 converts happen
    # in-register per tile — the f32 slab never materializes)
    def body(i, carry):
        s, sq = carry
        xt = x_ref[0, pl_dslice(i)].astype(jnp.float32)  # (tile, C)
        s = s + jnp.sum(xt, axis=0, keepdims=True)
        sq = sq + jnp.sum(xt * xt, axis=0, keepdims=True)
        return s, sq

    s0 = jnp.zeros((1, c), jnp.float32)
    s, sq = lax.fori_loop(0, n_tiles, body, (s0, s0))

    gmat = gmat_ref[...]  # (C, G) one-hot, f32
    cnt = rows * (c // gmat.shape[1])
    gs = lax.dot_general(s, gmat, (((1,), (0,)), ((), ())),
                         preferred_element_type=jnp.float32)  # (1, G)
    gsq = lax.dot_general(sq, gmat, (((1,), (0,)), ((), ())),
                          preferred_element_type=jnp.float32)
    mean = gs / cnt
    var = gsq / cnt - mean * mean  # biased, torch/flax "fast variance"
    inv = lax.rsqrt(var + eps)
    # broadcast group stats back to channels via the transposed one-hot
    mean_c = lax.dot_general(mean, gmat, (((1,), (1,)), ((), ())),
                             preferred_element_type=jnp.float32)  # (1, C)
    inv_c = lax.dot_general(inv, gmat, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32)
    scale = scale_ref[...].astype(jnp.float32)  # (1, C)
    bias = bias_ref[...].astype(jnp.float32)
    eff_scale = inv_c * scale
    eff_bias = bias - mean_c * eff_scale

    def apply_body(i, _):
        xt = x_ref[0, pl_dslice(i)].astype(jnp.float32)
        y = xt * eff_scale + eff_bias
        if act == "silu":
            y = y * jax.nn.sigmoid(y)
        o_ref[0, pl_dslice(i)] = y.astype(o_ref.dtype)
        return 0

    lax.fori_loop(0, n_tiles, apply_body, 0)


def fused_group_norm(
    x: jax.Array,
    scale: jax.Array,
    bias: jax.Array,
    *,
    num_groups: int,
    eps: float = 1e-5,
    act: str = "none",
    interpret: bool = False,
) -> jax.Array:
    """One-pass GroupNorm(+activation) over ``x`` of shape (N, rows, C).

    Statistics are per (sample n, group g) over rows × C/G channels, f32
    accumulation, biased variance — torch/flax GroupNorm semantics. The
    caller is responsible for the slab-size gate
    (:func:`fits_fused_group_norm`); an unfittable shape raises at trace
    time rather than silently spilling VMEM. Differentiation recomputes
    through :func:`group_norm_reference` (same convention as the fused
    attention kernel — the Pallas body itself is inference-path).
    """
    with jax.named_scope("ops.group_norm"):
        return _fused_gn(x, scale, bias, num_groups, eps, act, interpret)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6))
def _fused_gn(
    x: jax.Array,
    scale: jax.Array,
    bias: jax.Array,
    num_groups: int,
    eps: float,
    act: str,
    interpret: bool,
) -> jax.Array:
    from jax.experimental import pallas as pl

    n, rows, c = x.shape
    if rows % _ROW_TILE != 0:
        raise ValueError(
            f"fused_group_norm needs rows % {_ROW_TILE} == 0, got {rows}"
        )
    if c % num_groups != 0:
        raise ValueError(f"channels {c} not divisible by groups {num_groups}")
    gmat = (
        jnp.arange(c)[:, None] // (c // num_groups)
        == jnp.arange(num_groups)[None, :]
    ).astype(jnp.float32)
    # scale/bias ride as (1, C) — rank-1 operands hit Mosaic layout
    # restrictions that rank-2 lane-major vectors don't
    return pl.pallas_call(
        functools.partial(_gn_kernel, eps=eps, rows=rows, act=act),
        # explicit name: trace events otherwise carry only the flax scope
        # (norm1/norm2/…), making the kernel indistinguishable from the
        # XLA-path ops in a profile
        name="fused_group_norm",
        out_shape=jax.ShapeDtypeStruct((n, rows, c), x.dtype),
        grid=(n,),
        in_specs=[
            pl.BlockSpec((1, rows, c), lambda i: (i, 0, 0)),
            pl.BlockSpec((1, c), lambda i: (0, 0)),
            pl.BlockSpec((1, c), lambda i: (0, 0)),
            pl.BlockSpec((c, num_groups), lambda i: (0, 0)),
        ],
        out_specs=pl.BlockSpec((1, rows, c), lambda i: (i, 0, 0)),
        interpret=interpret,
    )(x, scale.reshape(1, c), bias.reshape(1, c), gmat)


def _fused_gn_fwd(x, scale, bias, num_groups, eps, act, interpret):
    out = _fused_gn(x, scale, bias, num_groups, eps, act, interpret)
    return out, (x, scale, bias)


def _fused_gn_bwd(num_groups, eps, act, interpret, res, g):
    x, scale, bias = res
    _, vjp = jax.vjp(
        lambda xx, ss, bb: group_norm_reference(
            xx, ss, bb, num_groups=num_groups, eps=eps, act=act
        ),
        x, scale, bias,
    )
    return vjp(g)


_fused_gn.defvjp(_fused_gn_fwd, _fused_gn_bwd)


@jax.named_scope("ops.group_norm")
def group_norm_reference(
    x: jax.Array,
    scale: jax.Array,
    bias: jax.Array,
    *,
    num_groups: int,
    eps: float = 1e-5,
    act: str = "none",
) -> jax.Array:
    """The same math in plain XLA (stats pass + apply pass) — the fallback
    for slabs over the VMEM gate and the CPU path; numerically equivalent
    to flax ``nn.GroupNorm`` with ``use_fast_variance`` (and to the torch
    GroupNorm the reference uses)."""
    n, rows, c = x.shape
    g = num_groups
    xf = x.astype(jnp.float32).reshape(n, rows, g, c // g)
    mean = jnp.mean(xf, axis=(1, 3), keepdims=True)
    var = jnp.mean(xf * xf, axis=(1, 3), keepdims=True) - mean * mean
    y = (xf - mean) * lax.rsqrt(var + eps)
    y = y.reshape(n, rows, c) * scale.astype(jnp.float32) + bias.astype(jnp.float32)
    if act == "silu":
        y = y * jax.nn.sigmoid(y)
    return y.astype(x.dtype)
