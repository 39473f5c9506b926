"""Macro-blocks of the video UNet (reference
/root/reference/tuneavideo/models/unet_blocks.py).

Each block is a linen module over (B, F, H, W, C) activations; cross-attention
blocks thread the text context and the functional attention control. Down
blocks return their per-layer outputs for the skip connections; up blocks
consume them via channel concat (unet_blocks.py:486-488).

Gradient checkpointing is applied by the parent UNet via :func:`remat_block`
around these blocks (the reference checkpoints per resnet/attn pair inside
each block, unet_blocks.py:290-306 — block-level remat is the XLA-friendly
equivalent: coarser boundaries, same activation-memory/recompute trade).
"""

from __future__ import annotations

from typing import Callable, Optional, Tuple

import jax
import jax.numpy as jnp
from flax import linen as nn

from videop2p_tpu.models.attention import AttnControl, Transformer3DModel
from videop2p_tpu.models.layers import Downsample3D, ResnetBlock3D, Upsample3D
from videop2p_tpu.ops.attention import keep_frame_attention_output

__all__ = [
    "CrossAttnDownBlock3D",
    "DownBlock3D",
    "UNetMidBlock3DCrossAttn",
    "CrossAttnUpBlock3D",
    "UpBlock3D",
    "get_down_block",
    "get_up_block",
]

Dtype = jnp.dtype


class CrossAttnDownBlock3D(nn.Module):
    """[Resnet → Transformer3D] × layers, then optional downsample
    (unet_blocks.py:209-319)."""

    out_channels: int
    num_layers: int = 2
    transformer_depth: int = 1
    attn_heads: int = 8
    add_downsample: bool = True
    norm_groups: int = 32
    gn_impl: str = "auto"
    group_norm_fn: Optional[Callable] = None
    dtype: Dtype = jnp.float32
    frame_attention_fn: Optional[Callable] = None
    temporal_attention_fn: Optional[Callable] = None
    row_parallel_dot: Optional[Callable] = None
    # activation fake-quant at the transformer Dense boundaries (w8a8
    # quant mode — models/quant.py); None → byte-identical off path
    act_quant_fn: Optional[Callable] = None

    @nn.compact
    def __call__(
        self,
        x: jax.Array,
        temb: jax.Array,
        context: jax.Array,
        control: Optional[AttnControl] = None,
    ) -> Tuple[jax.Array, Tuple[jax.Array, ...]]:
        outputs = []
        for i in range(self.num_layers):
            x = ResnetBlock3D(
                self.out_channels, groups=self.norm_groups, dtype=self.dtype,
                gn_impl=self.gn_impl, group_norm_fn=self.group_norm_fn,
                name=f"resnets_{i}",
            )(x, temb)
            x = Transformer3DModel(
                heads=self.attn_heads,
                dim_head=self.out_channels // self.attn_heads,
                depth=self.transformer_depth,
                norm_groups=self.norm_groups,
                gn_impl=self.gn_impl,
                group_norm_fn=self.group_norm_fn,
                dtype=self.dtype,
                frame_attention_fn=self.frame_attention_fn,
                temporal_attention_fn=self.temporal_attention_fn,
                row_parallel_dot=self.row_parallel_dot,
                act_quant_fn=self.act_quant_fn,
                name=f"attentions_{i}",
            )(x, context=context, control=control)
            outputs.append(x)
        if self.add_downsample:
            x = Downsample3D(self.out_channels, dtype=self.dtype, name="downsample")(x)
            outputs.append(x)
        return x, tuple(outputs)


class DownBlock3D(nn.Module):
    """Resnet-only down block (unet_blocks.py:322-398)."""

    out_channels: int
    num_layers: int = 2
    add_downsample: bool = True
    norm_groups: int = 32
    gn_impl: str = "auto"
    group_norm_fn: Optional[Callable] = None
    dtype: Dtype = jnp.float32

    @nn.compact
    def __call__(
        self, x: jax.Array, temb: jax.Array
    ) -> Tuple[jax.Array, Tuple[jax.Array, ...]]:
        outputs = []
        for i in range(self.num_layers):
            x = ResnetBlock3D(
                self.out_channels, groups=self.norm_groups, dtype=self.dtype,
                gn_impl=self.gn_impl, group_norm_fn=self.group_norm_fn,
                name=f"resnets_{i}",
            )(x, temb)
            outputs.append(x)
        if self.add_downsample:
            x = Downsample3D(self.out_channels, dtype=self.dtype, name="downsample")(x)
            outputs.append(x)
        return x, tuple(outputs)


class UNetMidBlock3DCrossAttn(nn.Module):
    """Resnet → [Transformer3D → Resnet] × layers (unet_blocks.py:125-206)."""

    channels: int
    num_layers: int = 1
    transformer_depth: int = 1
    attn_heads: int = 8
    norm_groups: int = 32
    gn_impl: str = "auto"
    group_norm_fn: Optional[Callable] = None
    dtype: Dtype = jnp.float32
    frame_attention_fn: Optional[Callable] = None
    temporal_attention_fn: Optional[Callable] = None
    row_parallel_dot: Optional[Callable] = None
    # activation fake-quant at the transformer Dense boundaries (w8a8
    # quant mode — models/quant.py); None → byte-identical off path
    act_quant_fn: Optional[Callable] = None

    @nn.compact
    def __call__(
        self,
        x: jax.Array,
        temb: jax.Array,
        context: jax.Array,
        control: Optional[AttnControl] = None,
    ) -> jax.Array:
        x = ResnetBlock3D(
            self.channels, groups=self.norm_groups, dtype=self.dtype,
            gn_impl=self.gn_impl, group_norm_fn=self.group_norm_fn,
            name="resnets_0"
        )(x, temb)
        for i in range(self.num_layers):
            x = Transformer3DModel(
                heads=self.attn_heads,
                dim_head=self.channels // self.attn_heads,
                depth=self.transformer_depth,
                norm_groups=self.norm_groups,
                gn_impl=self.gn_impl,
                group_norm_fn=self.group_norm_fn,
                dtype=self.dtype,
                frame_attention_fn=self.frame_attention_fn,
                temporal_attention_fn=self.temporal_attention_fn,
                row_parallel_dot=self.row_parallel_dot,
                act_quant_fn=self.act_quant_fn,
                name=f"attentions_{i}",
            )(x, context=context, control=control)
            x = ResnetBlock3D(
                self.channels, groups=self.norm_groups, dtype=self.dtype,
                gn_impl=self.gn_impl, group_norm_fn=self.group_norm_fn,
                name=f"resnets_{i + 1}",
            )(x, temb)
        return x


class CrossAttnUpBlock3D(nn.Module):
    """[skip-concat → Resnet → Transformer3D] × layers, then optional upsample
    (unet_blocks.py:401-515)."""

    out_channels: int
    num_layers: int = 3
    transformer_depth: int = 1
    attn_heads: int = 8
    add_upsample: bool = True
    norm_groups: int = 32
    gn_impl: str = "auto"
    group_norm_fn: Optional[Callable] = None
    dtype: Dtype = jnp.float32
    frame_attention_fn: Optional[Callable] = None
    temporal_attention_fn: Optional[Callable] = None
    row_parallel_dot: Optional[Callable] = None
    # activation fake-quant at the transformer Dense boundaries (w8a8
    # quant mode — models/quant.py); None → byte-identical off path
    act_quant_fn: Optional[Callable] = None

    @nn.compact
    def __call__(
        self,
        x: jax.Array,
        res_samples: Tuple[jax.Array, ...],
        temb: jax.Array,
        context: jax.Array,
        control: Optional[AttnControl] = None,
    ) -> jax.Array:
        for i in range(self.num_layers):
            x = jnp.concatenate([x, res_samples[-(i + 1)]], axis=-1)
            x = ResnetBlock3D(
                self.out_channels, groups=self.norm_groups, dtype=self.dtype,
                gn_impl=self.gn_impl, group_norm_fn=self.group_norm_fn,
                name=f"resnets_{i}",
            )(x, temb)
            x = Transformer3DModel(
                heads=self.attn_heads,
                dim_head=self.out_channels // self.attn_heads,
                depth=self.transformer_depth,
                norm_groups=self.norm_groups,
                gn_impl=self.gn_impl,
                group_norm_fn=self.group_norm_fn,
                dtype=self.dtype,
                frame_attention_fn=self.frame_attention_fn,
                temporal_attention_fn=self.temporal_attention_fn,
                row_parallel_dot=self.row_parallel_dot,
                act_quant_fn=self.act_quant_fn,
                name=f"attentions_{i}",
            )(x, context=context, control=control)
        if self.add_upsample:
            x = Upsample3D(self.out_channels, dtype=self.dtype, name="upsample")(x)
        return x


class UpBlock3D(nn.Module):
    """Resnet-only up block (unet_blocks.py:518-589)."""

    out_channels: int
    num_layers: int = 3
    add_upsample: bool = True
    norm_groups: int = 32
    gn_impl: str = "auto"
    group_norm_fn: Optional[Callable] = None
    dtype: Dtype = jnp.float32

    @nn.compact
    def __call__(
        self,
        x: jax.Array,
        res_samples: Tuple[jax.Array, ...],
        temb: jax.Array,
    ) -> jax.Array:
        for i in range(self.num_layers):
            x = jnp.concatenate([x, res_samples[-(i + 1)]], axis=-1)
            x = ResnetBlock3D(
                self.out_channels, groups=self.norm_groups, dtype=self.dtype,
                gn_impl=self.gn_impl, group_norm_fn=self.group_norm_fn,
                name=f"resnets_{i}",
            )(x, temb)
        if self.add_upsample:
            x = Upsample3D(self.out_channels, dtype=self.dtype, name="upsample")(x)
        return x


_ATTN_ONLY_KWARGS = (
    "transformer_depth", "attn_heads", "frame_attention_fn", "temporal_attention_fn",
    "row_parallel_dot", "act_quant_fn",
)


def remat_block(mod_cls):
    """``mod_cls`` recomputed in the backward pass, all but the frame-attention
    kernel's output (``ops.attention.keep_frame_attention_output``): the
    backward kernel reads only q / k / v, so the forward kernel runs once a
    site and step; where the kernel did not run nothing is kept."""
    return nn.remat(mod_cls, policy=keep_frame_attention_output)


def _make(mod_cls, remat: bool, kwargs):
    if remat:
        mod_cls = remat_block(mod_cls)
    return mod_cls(**kwargs)


def get_down_block(block_type: str, *, remat: bool = False, **kwargs):
    """Factory mirroring unet_blocks.py:11-65; raises on unknown types."""
    if block_type == "CrossAttnDownBlock3D":
        return _make(CrossAttnDownBlock3D, remat, kwargs)
    if block_type == "DownBlock3D":
        kwargs = {k: v for k, v in kwargs.items() if k not in _ATTN_ONLY_KWARGS}
        return _make(DownBlock3D, remat, kwargs)
    raise ValueError(f"unknown down block type: {block_type!r}")


def get_up_block(block_type: str, *, remat: bool = False, **kwargs):
    """Factory mirroring unet_blocks.py:68-122; raises on unknown types."""
    if block_type == "CrossAttnUpBlock3D":
        return _make(CrossAttnUpBlock3D, remat, kwargs)
    if block_type == "UpBlock3D":
        kwargs = {k: v for k, v in kwargs.items() if k not in _ATTN_ONLY_KWARGS}
        return _make(UpBlock3D, remat, kwargs)
    raise ValueError(f"unknown up block type: {block_type!r}")
