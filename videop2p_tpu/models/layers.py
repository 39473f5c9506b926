"""Base layers for the video UNet: pseudo-3D convs, resnet blocks, resampling,
timestep embeddings.

TPU-native re-design of /root/reference/tuneavideo/models/resnet.py. Layout is
channels-last ``(batch, frames, height, width, chan)`` — XLA's preferred conv
layout on TPU — instead of the reference's ``(b, c, f, h, w)``. The reference's
``InflatedConv3d`` (resnet.py:11-19) is a 2-D conv applied per frame via
rearrange; here the frame axis is folded into batch around a plain ``nn.Conv``,
which XLA lowers to one large MXU conv over ``B·F`` images.
"""

from __future__ import annotations

from typing import Callable, Optional, Tuple

import jax
import jax.numpy as jnp
from flax import linen as nn

__all__ = [
    "get_timestep_embedding",
    "TimestepEmbedding",
    "TpuGroupNorm",
    "InflatedConv",
    "Upsample3D",
    "Downsample3D",
    "ResnetBlock3D",
]

Dtype = jnp.dtype


class TpuGroupNorm(nn.Module):
    """GroupNorm with an optional fused activation and a one-pass Pallas
    path (ops/groupnorm.py) on TPU where one statistics sample's slab fits
    VMEM, in place of the stats+apply two-traversal structure XLA lowers
    GroupNorm to.

    Drop-in for ``nn.GroupNorm``: identical parameter tree ('scale'/'bias'
    of shape (C,)), identical statistics semantics (per-sample per-group,
    f32 accumulation, biased variance — torch GroupNorm, which the
    reference uses throughout resnet.py / attention.py). Statistics pool
    over EVERY non-batch, non-channel axis of the input — frame-pooled on
    (B, F, H, W, C), per-frame when the caller folds frames into batch
    first (the Transformer3DModel rule, attention.py:361-368).

    ``impl``: "auto" (Pallas on TPU when the slab fits, else the XLA
    two-pass math), "xla" (always two-pass — the CPU path), "interpret"
    (kernel in interpret mode — CPU tests only).

    ``group_norm_fn``: the sharded-mesh seam
    (:func:`videop2p_tpu.parallel.make_sharded_group_norm_fn`). When set
    it OWNS the kernel decision: it is tried first with the flattened
    ``(N, rows, C)`` slab, and a ``None`` return (site not covered by the
    shard_map-wrapped kernel) falls back to the two-pass XLA math — never
    to the naked Pallas path, which pjit cannot partition.
    """

    num_groups: int = 32
    epsilon: float = 1e-5
    dtype: Dtype = jnp.float32
    act: str = "none"  # "silu" fuses the activation into the norm
    impl: str = "auto"
    group_norm_fn: Optional[Callable] = None

    @nn.compact
    def __call__(self, x: jax.Array) -> jax.Array:
        from videop2p_tpu.ops.groupnorm import (
            fits_fused_group_norm,
            fused_group_norm,
            group_norm_reference,
        )

        if self.impl not in ("auto", "xla", "interpret"):
            # a typo (e.g. 'pallas') must not silently select the XLA
            # fallback and change the performance path without a trace
            raise ValueError(
                f"TpuGroupNorm impl {self.impl!r} not in "
                "{'auto', 'xla', 'interpret'}"
            )
        c = x.shape[-1]
        scale = self.param("scale", nn.initializers.ones, (c,), jnp.float32)
        bias = self.param("bias", nn.initializers.zeros, (c,), jnp.float32)
        n = x.shape[0]
        rows = 1
        for d in x.shape[1:-1]:
            rows *= d
        x2 = x.astype(self.dtype).reshape(n, rows, c)
        if self.group_norm_fn is not None:
            y = self.group_norm_fn(
                x2, scale, bias, num_groups=self.num_groups,
                eps=self.epsilon, act=self.act,
            )
            if y is None:
                y = group_norm_reference(
                    x2, scale, bias, num_groups=self.num_groups,
                    eps=self.epsilon, act=self.act,
                )
            return y.reshape(x.shape).astype(self.dtype)
        fits = fits_fused_group_norm(rows, c, x2.dtype)
        use_kernel = self.impl == "interpret" and fits or (
            self.impl == "auto" and fits and jax.default_backend() == "tpu"
        )
        if use_kernel:
            y = fused_group_norm(
                x2, scale, bias, num_groups=self.num_groups, eps=self.epsilon,
                act=self.act, interpret=self.impl == "interpret",
            )
        else:
            y = group_norm_reference(
                x2, scale, bias, num_groups=self.num_groups, eps=self.epsilon,
                act=self.act,
            )
        return y.reshape(x.shape).astype(self.dtype)


def get_timestep_embedding(
    timesteps: jax.Array,
    embedding_dim: int,
    *,
    flip_sin_to_cos: bool = True,
    downscale_freq_shift: float = 0.0,
    max_period: int = 10000,
) -> jax.Array:
    """Sinusoidal timestep embedding, matching the diffusers ``Timesteps``
    semantics the reference UNet is configured with (unet.py:120-124:
    ``flip_sin_to_cos=True, freq_shift=0``).

    ``timesteps``: () or (B,) integer/float array → (B, embedding_dim) float32.
    """
    timesteps = jnp.atleast_1d(jnp.asarray(timesteps))
    half_dim = embedding_dim // 2
    exponent = -jnp.log(float(max_period)) * jnp.arange(half_dim, dtype=jnp.float32)
    exponent = exponent / (half_dim - downscale_freq_shift)
    emb = timesteps.astype(jnp.float32)[:, None] * jnp.exp(exponent)[None, :]
    sin, cos = jnp.sin(emb), jnp.cos(emb)
    emb = jnp.concatenate([cos, sin] if flip_sin_to_cos else [sin, cos], axis=-1)
    if embedding_dim % 2 == 1:
        emb = jnp.pad(emb, ((0, 0), (0, 1)))
    return emb


class TimestepEmbedding(nn.Module):
    """Two-layer SiLU MLP lifting the sinusoidal embedding to ``time_embed_dim``
    (the diffusers ``TimestepEmbedding`` the reference constructs at
    unet.py:125)."""

    time_embed_dim: int
    dtype: Dtype = jnp.float32

    @nn.compact
    def __call__(self, emb: jax.Array) -> jax.Array:
        emb = nn.Dense(self.time_embed_dim, dtype=self.dtype, name="linear_1")(emb)
        emb = nn.silu(emb)
        emb = nn.Dense(self.time_embed_dim, dtype=self.dtype, name="linear_2")(emb)
        return emb


class InflatedConv(nn.Module):
    """2-D convolution applied independently to every frame
    (reference ``InflatedConv3d``, resnet.py:11-19).

    Input/output: (B, F, H, W, C). Frames fold into the batch so XLA sees one
    conv over B·F images — not a real 3-D conv, by design (temporal mixing
    happens only in temporal attention).
    """

    features: int
    kernel_size: Tuple[int, int] = (3, 3)
    strides: Tuple[int, int] = (1, 1)
    padding: int = 1
    use_bias: bool = True
    dtype: Dtype = jnp.float32

    @nn.compact
    def __call__(self, x: jax.Array) -> jax.Array:
        b, f = x.shape[:2]
        x = x.reshape((b * f,) + x.shape[2:])
        x = nn.Conv(
            self.features,
            self.kernel_size,
            strides=self.strides,
            padding=[(self.padding, self.padding)] * 2,
            use_bias=self.use_bias,
            dtype=self.dtype,
            name="conv",
        )(x)
        return x.reshape((b, f) + x.shape[1:])


class Upsample3D(nn.Module):
    """Nearest ×2 spatial upsample per frame, then 3×3 conv
    (reference Upsample3D, resnet.py:22-74: scale ``[1, 2, 2]``, mode nearest)."""

    features: int
    dtype: Dtype = jnp.float32

    @nn.compact
    def __call__(self, x: jax.Array) -> jax.Array:
        b, f, h, w, c = x.shape
        x = jax.image.resize(x, (b, f, h * 2, w * 2, c), method="nearest")
        return InflatedConv(self.features, dtype=self.dtype, name="conv")(x)


class Downsample3D(nn.Module):
    """Stride-2 3×3 conv per frame (reference Downsample3D, resnet.py:77-108)."""

    features: int
    dtype: Dtype = jnp.float32

    @nn.compact
    def __call__(self, x: jax.Array) -> jax.Array:
        return InflatedConv(
            self.features, strides=(2, 2), padding=1, dtype=self.dtype, name="conv"
        )(x)


class ResnetBlock3D(nn.Module):
    """GN → SiLU → conv → (+time emb) → GN → SiLU → conv, with a 1×1 shortcut
    when channels change (reference ResnetBlock3D, resnet.py:111-205;
    ``time_embedding_norm="default"``: the time embedding is *added* after the
    first conv, broadcast over frames and space, resnet.py:181-184)."""

    features: int
    groups: int = 32
    eps: float = 1e-5
    dropout: float = 0.0
    dtype: Dtype = jnp.float32
    gn_impl: str = "auto"
    group_norm_fn: Optional[Callable] = None

    @nn.compact
    def __call__(
        self, x: jax.Array, temb: Optional[jax.Array] = None, deterministic: bool = True
    ) -> jax.Array:
        in_features = x.shape[-1]
        h = TpuGroupNorm(
            num_groups=self.groups, epsilon=self.eps, dtype=self.dtype,
            act="silu", impl=self.gn_impl, group_norm_fn=self.group_norm_fn,
            name="norm1",
        )(x)
        h = InflatedConv(self.features, dtype=self.dtype, name="conv1")(h)

        if temb is not None:
            temb = nn.Dense(self.features, dtype=self.dtype, name="time_emb_proj")(nn.silu(temb))
            h = h + temb[:, None, None, None, :]

        h = TpuGroupNorm(
            num_groups=self.groups, epsilon=self.eps, dtype=self.dtype,
            act="silu", impl=self.gn_impl, group_norm_fn=self.group_norm_fn,
            name="norm2",
        )(h)
        h = nn.Dropout(self.dropout)(h, deterministic=deterministic)
        h = InflatedConv(self.features, dtype=self.dtype, name="conv2")(h)

        if in_features != self.features:
            x = InflatedConv(
                self.features, kernel_size=(1, 1), padding=0, dtype=self.dtype,
                name="conv_shortcut",
            )(x)
        return x + h
