"""The 3-D (video) conditional UNet.

TPU-native re-design of /root/reference/tuneavideo/models/unet.py
(``UNet3DConditionModel``). Same topology as the inflated Stable-Diffusion 1.x
denoiser — 3 cross-attn down blocks + 1 plain down block, cross-attn mid, the
mirrored up path (unet.py:50-64) — expressed as a config-driven linen module
over channels-last (B, F, H, W, C) activations.

The topology is entirely config-driven (block types, widths, per-block
transformer depth and head counts) so larger inflations (e.g. SDXL-shaped
UNets at 1024²) are a config change, not a code change — the stress case
SURVEY §7 calls out.

Weight inflation from 2-D checkpoints (the reference's ``from_pretrained_2d``,
unet.py:417-448) lives in :mod:`videop2p_tpu.models.convert`; the
``'_temp.'``-keys-keep-init rule maps to the temporal attention's
zero-initialized output projection here (models/attention.py).
"""

from __future__ import annotations

import dataclasses
from typing import Callable, Optional, Tuple, Union

import jax
import jax.numpy as jnp
from flax import linen as nn

from videop2p_tpu.models.attention import AttnControl
from videop2p_tpu.models.layers import (
    InflatedConv,
    TimestepEmbedding,
    TpuGroupNorm,
    get_timestep_embedding,
)
from videop2p_tpu.models import unet_blocks
from videop2p_tpu.ops.attention import make_frame_attention_fn

__all__ = ["UNet3DConfig", "UNet3DConditionModel"]


def _per_block(value: Union[int, Tuple[int, ...]], num_blocks: int) -> Tuple[int, ...]:
    if isinstance(value, int):
        return (value,) * num_blocks
    if len(value) != num_blocks:
        raise ValueError(f"per-block value {value} does not match {num_blocks} blocks")
    return tuple(value)


@dataclasses.dataclass(frozen=True)
class UNet3DConfig:
    """Static architecture config (the reference's config-registered kwargs,
    unet.py:42-79). Defaults are the SD-1.x shape."""

    sample_size: int = 64
    in_channels: int = 4
    out_channels: int = 4
    down_block_types: Tuple[str, ...] = (
        "CrossAttnDownBlock3D",
        "CrossAttnDownBlock3D",
        "CrossAttnDownBlock3D",
        "DownBlock3D",
    )
    up_block_types: Tuple[str, ...] = (
        "UpBlock3D",
        "CrossAttnUpBlock3D",
        "CrossAttnUpBlock3D",
        "CrossAttnUpBlock3D",
    )
    block_out_channels: Tuple[int, ...] = (320, 640, 1280, 1280)
    layers_per_block: int = 2
    # int, or per-block tuple (SDXL-style deep upper blocks)
    transformer_depth: Union[int, Tuple[int, ...]] = 1
    attention_head_dim: Union[int, Tuple[int, ...]] = 8  # = num heads (diffusers-0.11 naming)
    cross_attention_dim: int = 768
    norm_num_groups: int = 32
    flip_sin_to_cos: bool = True
    freq_shift: float = 0.0
    # remat every block: recompute all but the frame-attention kernel's
    # output in the backward pass (unet_blocks.remat_block)
    gradient_checkpointing: bool = False
    # frame-attention kernel: "auto" (the Pallas forward / backward pair on
    # TPU, dense elsewhere), "fused", "dense", "chunked" (memory-bounded
    # backward: what training takes off the TPU); see ops/attention.py
    frame_attention: str = "auto"
    # GroupNorm implementation: "auto" = one-pass fused Pallas kernel on TPU
    # at VMEM-fitting sites (ops/groupnorm.py), "xla" = always the two-pass
    # XLA math, "interpret" = kernel in interpret mode (CPU tests). Sharded
    # meshes reach the kernel through the model's group_norm_fn seam
    # (parallel.make_sharded_group_norm_fn) instead of this knob — pjit
    # cannot partition a Pallas custom call, shard_map can
    group_norm: str = "auto"

    @classmethod
    def sd15(cls, **overrides) -> "UNet3DConfig":
        return cls(**overrides)

    @classmethod
    def sdxl(cls, **overrides) -> "UNet3DConfig":
        """SDXL-shaped inflation stress config (BASELINE config 4; SURVEY §7
        hard-part 6): 3 levels, deep upper transformer stacks (depth 2/10),
        64-wide heads, 2048-dim text context, 128² latents (1024² pixels).
        The first level carries no attention (SDXL's DownBlock2D) — its depth
        entry is unused. SDXL's addition embeddings (text_embeds/time_ids
        micro-conditioning) are out of scope: the stress case is the per-block
        topology, which is config-driven here."""
        cfg = dict(
            sample_size=128,
            down_block_types=(
                "DownBlock3D",
                "CrossAttnDownBlock3D",
                "CrossAttnDownBlock3D",
            ),
            up_block_types=(
                "CrossAttnUpBlock3D",
                "CrossAttnUpBlock3D",
                "UpBlock3D",
            ),
            block_out_channels=(320, 640, 1280),
            layers_per_block=2,
            transformer_depth=(1, 2, 10),
            attention_head_dim=(5, 10, 20),  # 64-wide heads per level
            cross_attention_dim=2048,
        )
        cfg.update(overrides)
        return cls(**cfg)

    @classmethod
    def tiny(cls, **overrides) -> "UNet3DConfig":
        """Miniature config for tests: two levels, 8-wide, 2 heads."""
        cfg = dict(
            sample_size=8,
            down_block_types=("CrossAttnDownBlock3D", "DownBlock3D"),
            up_block_types=("UpBlock3D", "CrossAttnUpBlock3D"),
            block_out_channels=(8, 16),
            layers_per_block=1,
            attention_head_dim=2,
            cross_attention_dim=16,
            norm_num_groups=4,
        )
        cfg.update(overrides)
        return cls(**cfg)


class UNet3DConditionModel(nn.Module):
    """Video denoiser ε_θ(x_t, t, text) (reference forward: unet.py:279-415).

    ``__call__(sample, timesteps, encoder_hidden_states, control=None)``:
      * ``sample``: (B, F, H, W, in_channels) latents;
      * ``timesteps``: () or (B,) int;
      * ``encoder_hidden_states``: (B, L, cross_attention_dim) text states, or
        (B, F, L, D) for per-frame embeddings;
      * ``control``: optional :class:`AttnControl` — threads the P2P edit into
        every text-cross / temporal attention site.

    Run with ``mutable=["attn_store"]`` to also collect head-averaged
    attention maps from every controlled site with ≤32² queries (the
    reference's ``AttentionStore``).
    """

    config: UNet3DConfig
    dtype: jnp.dtype = jnp.float32
    frame_attention_fn: Optional[Callable] = None
    # sequence-parallel temporal kernel (e.g. parallel.make_ring_temporal_fn
    # over a frame-sharded mesh); uncontrolled passes only — controlled sites
    # keep dense probabilities for the P2P edit
    temporal_attention_fn: Optional[Callable] = None
    # sharded-mesh GroupNorm seam (parallel.make_sharded_group_norm_fn):
    # carries the fused one-pass kernel onto device meshes via shard_map —
    # sites it does not cover fall back to the two-pass XLA math, never to
    # the naked Pallas path pjit cannot partition
    group_norm_fn: Optional[Callable] = None
    # explicit Megatron row-parallel output projections
    # (parallel.make_megatron_out_dot): replaces the to_out/proj_out
    # matmuls' all-reduce with a psum_scatter over the token axis on
    # tensor-parallel meshes; None → declarative GSPMD (the default)
    row_parallel_dot: Optional[Callable] = None
    # activation fake-quant at the transformer Dense boundaries (w8a8 quant
    # mode — models/quant.py fake_quant_act, wired by ProgramSet/CLIs via
    # clone, same pattern as the seams above); None → byte-identical off path
    act_quant_fn: Optional[Callable] = None

    @nn.compact
    def __call__(
        self,
        sample: jax.Array,
        timesteps: jax.Array,
        encoder_hidden_states: jax.Array,
        control: Optional[AttnControl] = None,
        deep_mode: str = "full",
        deep_feature: Optional[jax.Array] = None,
    ) -> jax.Array:
        """``deep_mode`` (static) is the DeepCache cross-step reuse seam
        (pipelines/reuse.py):

          * ``"full"``    — the whole UNet; returns ``eps`` (unchanged
            contract, byte-identical program — pinned).
          * ``"capture"`` — the whole UNet, additionally returning the deep
            feature: the input to the FINAL up block (the output of up
            block n−2, full spatial resolution). Returns ``(eps, deep)``.
          * ``"shallow"`` — skip every deep stage: conv_in → down block 0
            (no downsample) → the final up block seeded with
            ``deep_feature`` (a previous step's capture) → out convs.
            Adjacent diffusion steps' deep features are nearly identical
            (Ma et al., 2023), so this trades the deep stack's cost for
            one cached activation carried in the sampling scan's state.

        ``capture``/``shallow`` need ≥ 2 resolution levels — the split
        point is the boundary between the last two up blocks.
        """
        cfg = self.config
        n_blocks = len(cfg.block_out_channels)
        if deep_mode not in ("full", "capture", "shallow"):
            raise ValueError(
                f"deep_mode={deep_mode!r} is not 'full', 'capture' or 'shallow'"
            )
        if deep_mode != "full" and n_blocks < 2:
            raise ValueError(
                "deep-feature reuse needs >= 2 resolution levels — "
                f"this config has {n_blocks}"
            )
        if deep_mode == "shallow" and deep_feature is None:
            raise ValueError("deep_mode='shallow' requires deep_feature")
        depths = _per_block(cfg.transformer_depth, n_blocks)
        heads = _per_block(cfg.attention_head_dim, n_blocks)
        frame_attention_fn = (
            self.frame_attention_fn
            if self.frame_attention_fn is not None
            else make_frame_attention_fn(cfg.frame_attention)
        )

        # --- time embedding (unet.py:324-346) ---
        timesteps = jnp.asarray(timesteps)
        if timesteps.ndim == 0:
            timesteps = jnp.broadcast_to(timesteps, (sample.shape[0],))
        temb = get_timestep_embedding(
            timesteps,
            cfg.block_out_channels[0],
            flip_sin_to_cos=cfg.flip_sin_to_cos,
            downscale_freq_shift=cfg.freq_shift,
        ).astype(self.dtype)
        temb = TimestepEmbedding(
            cfg.block_out_channels[0] * 4, dtype=self.dtype, name="time_embedding"
        )(temb)

        # --- down path (unet.py:359-374) ---
        x = InflatedConv(cfg.block_out_channels[0], dtype=self.dtype, name="conv_in")(sample)
        res_stack = [x]
        down_types = (cfg.down_block_types[:1] if deep_mode == "shallow"
                      else cfg.down_block_types)
        for i, block_type in enumerate(down_types):
            is_final = i == n_blocks - 1
            block = unet_blocks.get_down_block(
                block_type,
                remat=cfg.gradient_checkpointing,
                out_channels=cfg.block_out_channels[i],
                num_layers=cfg.layers_per_block,
                transformer_depth=depths[i],
                attn_heads=heads[i],
                # the shallow path never descends: the downsample conv's
                # output only feeds the deep stages being skipped, so the
                # block is built without it (params bind by name — the
                # unvisited downsample kernel is simply not looked up)
                add_downsample=not is_final and deep_mode != "shallow",
                norm_groups=cfg.norm_num_groups,
                gn_impl=cfg.group_norm,
                group_norm_fn=self.group_norm_fn,
                dtype=self.dtype,
                frame_attention_fn=frame_attention_fn,
                temporal_attention_fn=self.temporal_attention_fn,
                row_parallel_dot=self.row_parallel_dot,
                act_quant_fn=self.act_quant_fn,
                name=f"down_blocks_{i}",
            )
            if block_type == "CrossAttnDownBlock3D":
                x, res = block(x, temb, encoder_hidden_states, control)
            else:
                x, res = block(x, temb)
            res_stack.extend(res)

        if deep_mode != "shallow":
            # --- mid (unet.py:377) ---
            mid_cls = (
                unet_blocks.remat_block(unet_blocks.UNetMidBlock3DCrossAttn)
                if cfg.gradient_checkpointing
                else unet_blocks.UNetMidBlock3DCrossAttn
            )
            x = mid_cls(
                channels=cfg.block_out_channels[-1],
                transformer_depth=depths[-1],
                attn_heads=heads[-1],
                norm_groups=cfg.norm_num_groups,
                gn_impl=cfg.group_norm,
                group_norm_fn=self.group_norm_fn,
                dtype=self.dtype,
                frame_attention_fn=frame_attention_fn,
                temporal_attention_fn=self.temporal_attention_fn,
                row_parallel_dot=self.row_parallel_dot,
                act_quant_fn=self.act_quant_fn,
                name="mid_block",
            )(x, temb, encoder_hidden_states, control)

        # --- up path (unet.py:382-405) ---
        rev_channels = tuple(reversed(cfg.block_out_channels))
        rev_heads = tuple(reversed(heads))
        rev_depths = tuple(reversed(depths))
        deep = None
        up_indices = ([n_blocks - 1] if deep_mode == "shallow"
                      else range(len(cfg.up_block_types)))
        if deep_mode == "shallow":
            # seed the final up block with the cached deep feature; the
            # skip connections it concatenates ([conv_in, down block 0's
            # resnet outputs]) were just recomputed above
            x = deep_feature.astype(self.dtype)
        for i in up_indices:
            block_type = cfg.up_block_types[i]
            is_final = i == n_blocks - 1
            num_layers = cfg.layers_per_block + 1
            res = tuple(res_stack[-num_layers:])
            del res_stack[-num_layers:]
            if is_final and deep_mode == "capture":
                # the DeepCache split point: everything above this input
                # (deep down blocks, mid, up blocks 0..n−2) is what a
                # shallow step skips
                deep = x
            block = unet_blocks.get_up_block(
                block_type,
                remat=cfg.gradient_checkpointing,
                out_channels=rev_channels[i],
                num_layers=num_layers,
                transformer_depth=rev_depths[i],
                attn_heads=rev_heads[i],
                add_upsample=not is_final,
                norm_groups=cfg.norm_num_groups,
                gn_impl=cfg.group_norm,
                group_norm_fn=self.group_norm_fn,
                dtype=self.dtype,
                frame_attention_fn=frame_attention_fn,
                temporal_attention_fn=self.temporal_attention_fn,
                row_parallel_dot=self.row_parallel_dot,
                act_quant_fn=self.act_quant_fn,
                name=f"up_blocks_{i}",
            )
            if block_type == "CrossAttnUpBlock3D":
                x = block(x, res, temb, encoder_hidden_states, control)
            else:
                x = block(x, res, temb)

        # --- out (unet.py:407-409) ---
        x = TpuGroupNorm(
            num_groups=cfg.norm_num_groups, epsilon=1e-5, dtype=self.dtype,
            act="silu", impl=cfg.group_norm,
            group_norm_fn=self.group_norm_fn, name="conv_norm_out",
        )(x)
        x = InflatedConv(cfg.out_channels, dtype=self.dtype, name="conv_out")(x)
        if deep_mode == "capture":
            return x, deep
        return x
