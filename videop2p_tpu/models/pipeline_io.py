"""Diffusers-layout pipeline directory I/O — the Stage-1 → Stage-2 contract.

The reference's two stages communicate via the filesystem: Stage 1 ends with
``pipeline.save_pretrained(output_dir)`` (/root/reference/run_tuning.py:387-393)
and Stage 2 loads that directory as ``pretrained_model_path``
(run_videop2p.py:101-114). This module reads and writes the same layout::

    <dir>/
      model_index.json
      unet/   config.json + diffusion_pytorch_model.safetensors
      vae/    config.json + diffusion_pytorch_model.safetensors
      text_encoder/ config.json + model.safetensors
      tokenizer/    (CLIP BPE files — copied through, never rewritten)
      scheduler/    scheduler_config.json

so a checkpoint produced by the reference (or any diffusers SD-1.x dump)
loads here, and a Stage-1 checkpoint written here loads in the reference.
Weights cross the boundary through :mod:`videop2p_tpu.models.convert`.
"""

from __future__ import annotations

import json
import os
import shutil
from dataclasses import dataclass
from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np

from videop2p_tpu.models import convert
from videop2p_tpu.models.clip import CLIPTextConfig, CLIPTextEncoder
from videop2p_tpu.models.unet import UNet3DConditionModel, UNet3DConfig
from videop2p_tpu.models.vae import AutoencoderKL, VAEConfig

__all__ = ["LoadedPipeline", "load_pipeline", "save_pipeline"]

_WEIGHT_NAMES = (
    "diffusion_pytorch_model.safetensors",
    "diffusion_pytorch_model.bin",
    "model.safetensors",
    "pytorch_model.bin",
)


def _find_weights(subdir: str) -> Optional[str]:
    for name in _WEIGHT_NAMES:
        p = os.path.join(subdir, name)
        if os.path.exists(p):
            return p
    return None


def _read_json(path: str) -> Dict[str, Any]:
    with open(path) as f:
        return json.load(f)


@dataclass
class LoadedPipeline:
    unet: UNet3DConditionModel
    unet_params: Dict
    vae: Optional[AutoencoderKL]
    vae_params: Optional[Dict]
    text_encoder: Optional[CLIPTextEncoder]
    text_params: Optional[Dict]
    tokenizer_dir: Optional[str]
    scheduler_config: Dict[str, Any]
    inflation_report: Dict[str, list]


def _unet_config_from_diffusers(cfg: Dict[str, Any], **overrides) -> UNet3DConfig:
    """Map a diffusers UNet2D/3D config.json to :class:`UNet3DConfig`
    (the reference rewrites 2-D block types to 3-D the same way,
    unet.py:427-438)."""
    def threed(name: str) -> str:
        return name.replace("2D", "3D")

    kw = dict(
        sample_size=cfg.get("sample_size", 64),
        in_channels=cfg.get("in_channels", 4),
        out_channels=cfg.get("out_channels", 4),
        down_block_types=tuple(threed(b) for b in cfg["down_block_types"]),
        up_block_types=tuple(threed(b) for b in cfg["up_block_types"]),
        block_out_channels=tuple(cfg["block_out_channels"]),
        layers_per_block=cfg.get("layers_per_block", 2),
        attention_head_dim=(
            tuple(a) if isinstance(cfg.get("attention_head_dim", 8), (list, tuple))
            else cfg.get("attention_head_dim", 8)
        ),
        cross_attention_dim=cfg.get("cross_attention_dim", 768),
        norm_num_groups=cfg.get("norm_num_groups", 32),
        flip_sin_to_cos=cfg.get("flip_sin_to_cos", True),
        freq_shift=cfg.get("freq_shift", 0),
    )
    kw.update(overrides)
    return UNet3DConfig(**kw)


def load_pipeline(
    path: str,
    *,
    dtype: jnp.dtype = jnp.float32,
    load_vae: bool = True,
    load_text_encoder: bool = True,
    init_key: Optional[jax.Array] = None,
    **unet_overrides,
) -> LoadedPipeline:
    """Load a diffusers-layout SD/Tune-A-Video checkpoint directory into flax
    models + params (2-D checkpoints inflate; tuned 3-D ones load fully)."""
    if init_key is None:
        init_key = jax.random.key(0)

    unet_dir = os.path.join(path, "unet")
    unet_cfg = _unet_config_from_diffusers(
        _read_json(os.path.join(unet_dir, "config.json")), **unet_overrides
    )
    unet = UNet3DConditionModel(config=unet_cfg, dtype=dtype)
    sample = jnp.zeros((1, 2, unet_cfg.sample_size, unet_cfg.sample_size, unet_cfg.in_channels))
    text = jnp.zeros((1, 77, unet_cfg.cross_attention_dim))
    abstract = jax.eval_shape(
        lambda: unet.init(init_key, sample, jnp.asarray(0), text)
    )["params"]
    sd = convert.load_state_dict(_find_weights(unet_dir))
    unet_params, report = convert.unet3d_params_from_torch(sd, abstract)
    if report["kept_init"]:
        # a 2-D checkpoint: the temporal params it does not carry keep a
        # fresh init — only then is one materialized (at SD width the init
        # is 3.4 GB computed, fetched and thrown away for a tuned 3-D dir)
        init_params = jax.jit(unet.init)(
            init_key, sample, jnp.asarray(0), text
        )["params"]
        unet_params, report = convert.unet3d_params_from_torch(sd, init_params)

    vae = vae_params = None
    vae_dir = os.path.join(path, "vae")
    if load_vae and os.path.isdir(vae_dir):
        vcfg_raw = _read_json(os.path.join(vae_dir, "config.json"))
        vcfg = VAEConfig(
            in_channels=vcfg_raw.get("in_channels", 3),
            out_channels=vcfg_raw.get("out_channels", 3),
            latent_channels=vcfg_raw.get("latent_channels", 4),
            block_out_channels=tuple(vcfg_raw.get("block_out_channels", (128, 256, 512, 512))),
            layers_per_block=vcfg_raw.get("layers_per_block", 2),
            norm_num_groups=vcfg_raw.get("norm_num_groups", 32),
            scaling_factor=vcfg_raw.get("scaling_factor", 0.18215),
        )
        vae = AutoencoderKL(config=vcfg, dtype=dtype)
        probe = jnp.zeros((1, 32, 32, vcfg.in_channels))
        v_init = jax.jit(vae.init)(init_key, probe, init_key)["params"]
        v_sd = convert.load_state_dict(_find_weights(vae_dir))
        vae_params = {"params": convert.vae_params_from_torch(v_sd, v_init)}

    text_encoder = text_params = None
    te_dir = os.path.join(path, "text_encoder")
    if load_text_encoder and os.path.isdir(te_dir):
        tcfg_raw = _read_json(os.path.join(te_dir, "config.json"))
        tcfg = CLIPTextConfig(
            vocab_size=tcfg_raw.get("vocab_size", 49408),
            hidden_size=tcfg_raw.get("hidden_size", 768),
            intermediate_size=tcfg_raw.get("intermediate_size", 3072),
            num_hidden_layers=tcfg_raw.get("num_hidden_layers", 12),
            num_attention_heads=tcfg_raw.get("num_attention_heads", 12),
            max_position_embeddings=tcfg_raw.get("max_position_embeddings", 77),
        )
        text_encoder = CLIPTextEncoder(config=tcfg, dtype=dtype)
        t_init = jax.jit(text_encoder.init)(
            init_key, jnp.zeros((1, 8), jnp.int32)
        )["params"]
        t_sd = convert.load_state_dict(_find_weights(te_dir))
        text_params = {"params": convert.clip_params_from_torch(t_sd, t_init)}

    tok_dir = os.path.join(path, "tokenizer")
    sched_cfg_path = os.path.join(path, "scheduler", "scheduler_config.json")
    return LoadedPipeline(
        unet=unet,
        unet_params={"params": unet_params},
        vae=vae,
        vae_params=vae_params,
        text_encoder=text_encoder,
        text_params=text_params,
        tokenizer_dir=tok_dir if os.path.isdir(tok_dir) else None,
        scheduler_config=_read_json(sched_cfg_path) if os.path.exists(sched_cfg_path) else {},
        inflation_report=report,
    )


def save_pipeline(
    path: str,
    unet_config: UNet3DConfig,
    unet_params: Dict,
    *,
    source_dir: Optional[str] = None,
    scheduler_config: Optional[Dict[str, Any]] = None,
) -> None:
    """Write a diffusers-layout pipeline dir (run_tuning.py:387-393).

    The tuned UNet is exported through the torch-layout name map; frozen
    components (vae / text_encoder / tokenizer / scheduler) are copied
    through from ``source_dir`` when given, since tuning never touches them.
    """
    from safetensors.numpy import save_file

    os.makedirs(path, exist_ok=True)
    unet_dir = os.path.join(path, "unet")
    os.makedirs(unet_dir, exist_ok=True)
    params = unet_params.get("params", unet_params)
    sd = convert.unet3d_params_to_torch(params)
    save_file({k: np.ascontiguousarray(v) for k, v in sd.items()},
              os.path.join(unet_dir, "diffusion_pytorch_model.safetensors"))
    cfg = unet_config
    with open(os.path.join(unet_dir, "config.json"), "w") as f:
        json.dump(
            {
                "_class_name": "UNet3DConditionModel",
                "sample_size": cfg.sample_size,
                "in_channels": cfg.in_channels,
                "out_channels": cfg.out_channels,
                "down_block_types": list(cfg.down_block_types),
                "up_block_types": list(cfg.up_block_types),
                "block_out_channels": list(cfg.block_out_channels),
                "layers_per_block": cfg.layers_per_block,
                "attention_head_dim": (
                    list(cfg.attention_head_dim)
                    if isinstance(cfg.attention_head_dim, tuple)
                    else cfg.attention_head_dim
                ),
                "cross_attention_dim": cfg.cross_attention_dim,
                "norm_num_groups": cfg.norm_num_groups,
                "flip_sin_to_cos": cfg.flip_sin_to_cos,
                "freq_shift": cfg.freq_shift,
            },
            f,
            indent=2,
        )
    if scheduler_config:
        sdir = os.path.join(path, "scheduler")
        os.makedirs(sdir, exist_ok=True)
        with open(os.path.join(sdir, "scheduler_config.json"), "w") as f:
            json.dump(scheduler_config, f, indent=2)
    if source_dir:
        for sub in ("vae", "text_encoder", "tokenizer", "scheduler"):
            src = os.path.join(source_dir, sub)
            dst = os.path.join(path, sub)
            if os.path.isdir(src) and not os.path.isdir(dst):
                shutil.copytree(src, dst)
    index = {
        "_class_name": "TuneAVideoPipeline",
        "unet": ["videop2p_tpu", "UNet3DConditionModel"],
        "vae": ["diffusers", "AutoencoderKL"],
        "text_encoder": ["transformers", "CLIPTextModel"],
        "tokenizer": ["transformers", "CLIPTokenizer"],
        "scheduler": ["diffusers", "DDIMScheduler"],
    }
    with open(os.path.join(path, "model_index.json"), "w") as f:
        json.dump(index, f, indent=2)
