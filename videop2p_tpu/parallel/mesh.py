"""Device mesh and sharding layout — the framework's "communication backend".

The reference's only distributed machinery is HF Accelerate wrapping
torch.distributed/NCCL (run_tuning.py:85-88,210-212,322; SURVEY §2.2/§5.8).
The TPU-native equivalent is declarative: one ``jax.sharding.Mesh`` with named
axes, ``NamedSharding`` annotations on params/activations, and XLA inserting
the collectives (psum for the loss-gather parity, all-gathers for frame-0 KV
broadcast) over ICI/DCN.

Axes:
  * ``data``   — batch/video axis (the reference's vestigial DDP axis);
  * ``frames`` — the frame/sequence axis: sequence parallelism for long
    videos (SURVEY §5.7 — a 32-frame edit across a v5e-8 is a mesh change);
  * ``tensor`` — reserved for tensor parallelism of attention heads / FF
    (not needed for SD-1.x parity; used by SDXL-scale configs).

Convention: activations (B, F, h, w, C) shard as P(("data",), ("frames",));
parameters replicate by default (the UNet is ~1 GB in bf16 — far below one
chip's HBM) with optional tensor sharding for the big Dense kernels.
"""

from __future__ import annotations

from typing import Optional, Sequence, Tuple

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

__all__ = [
    "AXIS_DATA",
    "AXIS_FRAMES",
    "AXIS_TENSOR",
    "TP_COLLECTIVES",
    "make_mesh",
    "latent_sharding",
    "text_sharding",
    "replicated",
    "param_shardings",
    "make_megatron_out_dot",
    "make_sharded_frame_attention_fn",
    "make_sharded_group_norm_fn",
    "shard_array",
]

AXIS_DATA = "data"
AXIS_FRAMES = "frames"
AXIS_TENSOR = "tensor"

# how the Megatron row-parallel output projections reduce their partial
# sums on a tensor-parallel mesh: "gspmd" = declarative (XLA inserts an
# all-reduce), "psum_scatter" = the explicit reduce-scatter seam
# (make_megatron_out_dot) — half the per-chip result bytes per attention
# block, the all-gather deferred to wherever GSPMD actually needs the
# full token axis again
TP_COLLECTIVES = ("gspmd", "psum_scatter")


def make_mesh(
    shape: Tuple[int, ...] = (1, 1, 1),
    axis_names: Tuple[str, ...] = (AXIS_DATA, AXIS_FRAMES, AXIS_TENSOR),
    devices: Optional[Sequence[jax.Device]] = None,
) -> Mesh:
    """Mesh over the available devices; ``shape`` must multiply to the device
    count. ``make_mesh((1, 8, 1))`` = pure sequence parallelism over 8 chips."""
    devices = list(devices if devices is not None else jax.devices())
    n = int(np.prod(shape))
    if n != len(devices):
        raise ValueError(f"mesh shape {shape} needs {n} devices, have {len(devices)}")
    dev_array = np.asarray(devices).reshape(shape)
    return Mesh(dev_array, axis_names)


def latent_sharding(mesh: Mesh) -> NamedSharding:
    """(B, F, h, w, C) video/latent tensors: batch over ``data``, frames over
    ``frames`` (the sequence-parallel axis)."""
    return NamedSharding(mesh, P(AXIS_DATA, AXIS_FRAMES))


def text_sharding(mesh: Mesh) -> NamedSharding:
    """(B, L, D) text embeddings: batch over ``data``, rest replicated."""
    return NamedSharding(mesh, P(AXIS_DATA))


def replicated(mesh: Mesh) -> NamedSharding:
    return NamedSharding(mesh, P())


def make_sharded_frame_attention_fn(mesh: Mesh, impl: str = "auto"):
    """Frame-attention kernel for the UNet's ``frame_attention_fn`` seam on a
    device mesh: queries shard over ``frames`` (and batch/heads over
    ``data``/``tensor``), the frame-0 K/V replicate across the frame axis —
    the one broadcast the reference's shared-KV design needs (SURVEY §5.7).

    Inside ``shard_map`` each chip runs the single-chip kernel on its local
    frames — softmax rows are per-query, so the frame split is exact. This is
    how the SHARDED path reaches the fused Pallas kernel: pjit/GSPMD cannot
    partition a Pallas custom call on its own, but under shard_map the kernel
    only ever sees local shards. ``impl`` resolves through
    :func:`videop2p_tpu.ops.make_frame_attention_fn` per backend ("auto" →
    fused on TPU, dense on CPU test meshes).
    """
    from videop2p_tpu.ops import dense_frame_attention, make_frame_attention_fn

    resolved = make_frame_attention_fn(impl)
    inner = resolved or dense_frame_attention

    def fn(q: jax.Array, k: jax.Array, v: jax.Array) -> jax.Array:
        # q (B, F, H, N, D); k/v (B, H, N, D) — frame-0 KV has no frame axis,
        # so it replicates across the frames mesh axis (the shared-KV
        # broadcast). Batch/head axes shard only when they divide the mesh
        # axis (the Stage-2 edit batch is 3 CFG streams, which an even data
        # axis cannot split — those axes then replicate instead).
        b, f, h = q.shape[0], q.shape[1], q.shape[2]
        ax_d = AXIS_DATA if b % mesh.shape[AXIS_DATA] == 0 else None
        ax_t = AXIS_TENSOR if h % mesh.shape[AXIS_TENSOR] == 0 else None
        if f % mesh.shape[AXIS_FRAMES] != 0:
            raise ValueError(
                f"'{AXIS_FRAMES}' mesh axis size {mesh.shape[AXIS_FRAMES]} "
                f"must divide the frame axis {f}"
            )
        qspec = P(ax_d, AXIS_FRAMES, ax_t, None, None)
        kvspec = P(ax_d, ax_t, None, None)
        return jax.shard_map(
            inner, mesh=mesh, in_specs=(qspec, kvspec, kvspec),
            out_specs=qspec, check_vma=False,
        )(q, k, v)

    return fn


def make_sharded_group_norm_fn(mesh: Mesh, impl: str = "auto"):
    """Fused one-pass GroupNorm (ops/groupnorm.py) for sharded meshes, via
    the same shard_map wrapper pattern as
    :func:`make_sharded_frame_attention_fn`: pjit/GSPMD cannot partition a
    Pallas custom call, but GroupNorm statistics are strictly per-sample
    (dim 0 of the ``(N, rows, C)`` slab), so splitting the sample axis over
    ``data × frames`` keeps every statistics sample whole on one chip and
    the single-chip kernel runs on its local slab unchanged.

    Returns ``fn(x2, scale, bias, *, num_groups, eps, act) -> y | None``
    for the :class:`~videop2p_tpu.models.layers.TpuGroupNorm`
    ``group_norm_fn`` seam. ``None`` means "site not covered" — slab over
    the VMEM gate, sample axis not divisible by the ``dp·sp`` shard count
    (the frame-POOLED resnet slabs, whose statistics cross frame shards),
    or no kernel on this backend — and the caller falls back to the
    two-pass XLA math, which GSPMD partitions exactly as before. The
    covered sites are the frames-folded per-frame GNs (the
    Transformer3DModel entry norms), whose slabs are local on every shard.

    ``impl``: "auto" (kernel on TPU), "interpret" (Pallas interpret mode —
    the CPU-mesh tests), anything else disables the kernel.
    """
    from videop2p_tpu.ops.groupnorm import fits_fused_group_norm, fused_group_norm

    shards = mesh.shape[AXIS_DATA] * mesh.shape[AXIS_FRAMES]

    def fn(x2: jax.Array, scale: jax.Array, bias: jax.Array, *,
           num_groups: int, eps: float, act: str):
        interpret = impl == "interpret"
        if not interpret and not (
            impl == "auto" and jax.default_backend() == "tpu"
        ):
            return None
        n, rows, c = x2.shape
        if n % shards != 0 or not fits_fused_group_norm(rows, c, x2.dtype):
            return None
        import functools

        inner = functools.partial(
            fused_group_norm, num_groups=num_groups, eps=eps, act=act,
            interpret=interpret,
        )
        sample_spec = P((AXIS_DATA, AXIS_FRAMES), None, None)
        return jax.shard_map(
            inner, mesh=mesh,
            in_specs=(sample_spec, P(None), P(None)),
            out_specs=sample_spec, check_vma=False,
        )(x2, scale, bias)

    return fn


def param_shardings(mesh: Mesh, params, *, tensor_parallel: bool = False):
    """Sharding pytree for the UNet params.

    Default: fully replicated. With ``tensor_parallel``, the attention/FF
    Dense kernels shard their output features over ``tensor`` (column
    parallel, (in, out) → P(None, "tensor")) and ``to_out``/``proj_out``
    kernels shard input features (row parallel, P("tensor", None)) — the
    Megatron pairing that keeps each attention block to one psum. By
    default the reduction stays declarative (GSPMD inserts an all-reduce
    behind each row-parallel matmul); :func:`make_megatron_out_dot` makes
    it explicit — a ``psum_scatter`` over the token axis — when the
    ``tp_collectives="psum_scatter"`` knob is on.
    """

    def spec(path, leaf):
        if not tensor_parallel or getattr(leaf, "ndim", 0) != 2:
            return NamedSharding(mesh, P())
        keys = [str(getattr(p, "key", "")) for p in path]
        joined = "/".join(keys)
        if "attn" in joined or "ff" in joined:
            if any(k in ("to_out", "proj_out") for k in keys):
                return NamedSharding(mesh, P(AXIS_TENSOR, None))
            if any(k in ("to_q", "to_k", "to_v", "proj_geglu", "proj_in") for k in keys):
                return NamedSharding(mesh, P(None, AXIS_TENSOR))
        return NamedSharding(mesh, P())

    return jax.tree_util.tree_map_with_path(spec, params)


def make_megatron_out_dot(mesh: Mesh):
    """Explicit Megatron row-parallel output projection: a ``dot_general``
    replacement for the ``to_out``/``proj_out`` Denses (the
    ``row_parallel_dot`` seam in models/attention.py).

    With the kernel's rows sharded over ``tensor`` (``param_shardings``),
    the declarative form leaves a partial-sum matmul behind which GSPMD
    inserts an **all-reduce** of the FULL (…, tokens, C) result on every
    chip. The explicit form computes the local partial inside ``shard_map``
    (manual over ``tensor`` only, ``axis_names={"tensor"}`` —
    ``data``/``frames`` stay in GSPMD's hands) and reduces with
    ``lax.psum_scatter`` along the
    token axis: each chip receives 1/tp of the result bytes (the
    reduce-scatter half of the all-reduce), and the all-gather half is
    deferred to wherever the partitioner actually needs the full token
    axis again — often past the residual/LayerNorm elementwise ops, which
    is the overlap-via-collective-matmul decomposition (Wang et al., 2023)
    expressed at the seam. ``obs/comm.py`` sees the swap directly:
    ``all_reduce_count`` drops, ``reduce_scatter_bytes`` is the all-reduce
    bytes ÷ tp.

    The returned callable falls back to the plain ``dot_general`` whenever
    the pattern is not the row-parallel Dense matmul it models (batched
    dims, non-2D kernel, token/feature axes not divisible by tp, tp == 1)
    — so it is always safe to thread.
    """
    tp = mesh.shape[AXIS_TENSOR]

    def dot(lhs, rhs, dimension_numbers, precision=None,
            preferred_element_type=None, **kwargs):
        def plain(l, r):
            return jax.lax.dot_general(
                l, r, dimension_numbers, precision=precision,
                preferred_element_type=preferred_element_type, **kwargs,
            )

        (lc, rc), (lb, rb) = dimension_numbers
        if (
            tp <= 1
            or lb or rb
            or getattr(rhs, "ndim", 0) != 2
            or getattr(lhs, "ndim", 0) < 2
            or tuple(lc) != (lhs.ndim - 1,)
            or tuple(rc) != (0,)
            or lhs.shape[-1] % tp
            or lhs.shape[lhs.ndim - 2] % tp
        ):
            return plain(lhs, rhs)
        tok = lhs.ndim - 2

        def local(l, r):
            part = plain(l, r)
            return jax.lax.psum_scatter(
                part, AXIS_TENSOR, scatter_dimension=tok, tiled=True
            )

        lhs_spec = P(*([None] * (lhs.ndim - 1)), AXIS_TENSOR)
        out_parts = [None] * lhs.ndim
        out_parts[tok] = AXIS_TENSOR
        # partial-manual shard_map has no eager form; the jit wrapper is
        # inlined under a surrounding trace and makes an eager call work
        return jax.jit(jax.shard_map(
            local, mesh=mesh,
            in_specs=(lhs_spec, P(AXIS_TENSOR, None)),
            out_specs=P(*out_parts),
            axis_names={AXIS_TENSOR}, check_vma=False,
        ))(lhs, rhs)

    return dot


def shard_array(x: jax.Array, sharding: NamedSharding) -> jax.Array:
    return jax.device_put(x, sharding)
