"""Weight interop: diffusers/transformers torch checkpoints ↔ flax params.

Three jobs (SURVEY §7 step 3 and §5.4):

  * **2D→3D inflation** (``unet3d_params_from_torch``) — load a Stable
    Diffusion UNet2DConditionModel state dict into the video UNet. Parameters
    with no 2-D counterpart (``attn_temp``/``norm_temp``) keep their fresh
    init — the reference's ``'_temp.'``-keys rule
    (/root/reference/tuneavideo/models/unet.py:446-448); the zero-initialized
    temporal output projection then makes inflation an identity.
    A *tuned* 3-D checkpoint (which does contain ``attn_temp`` keys, as saved
    by Stage 1) loads through the same path.
  * **export** (``unet3d_params_to_torch``) — the inverse mapping, producing
    a reference-compatible (Tune-A-Video layout) state dict so Stage-1 output
    remains consumable by the original codebase (the Stage-1→Stage-2 contract,
    run_tuning.py:387-393).
  * **VAE / CLIP import** (``vae_params_from_torch``,
    ``clip_params_from_torch``) — diffusers ``AutoencoderKL`` and transformers
    ``CLIPTextModel`` state dicts into the flax implementations; CLIP import
    is validated numerically against the torch model in tests/test_convert.py.

All functions take a plain ``{name: numpy array}`` dict — use
``load_state_dict`` for ``.safetensors``/``.bin`` files — so torch is only
touched at the file boundary.
"""

from __future__ import annotations

import os
from typing import Dict, Optional, Tuple

import jax
import numpy as np
from flax import traverse_util

__all__ = [
    "load_state_dict",
    "unet3d_params_from_torch",
    "unet3d_params_to_torch",
    "quantize_unet_params",
    "vae_params_from_torch",
    "clip_params_from_torch",
]

Array = np.ndarray
StateDict = Dict[str, Array]


def load_state_dict(path: str) -> StateDict:
    """Read a ``.safetensors`` or torch ``.bin`` file into numpy arrays."""
    if path.endswith(".safetensors"):
        from safetensors.numpy import load_file

        return dict(load_file(path))
    import torch

    sd = torch.load(path, map_location="cpu", weights_only=True)
    return {k: v.numpy() for k, v in sd.items()}


# --------------------------------------------------------------------- #
# flax-path → torch-key translation (UNet)
# --------------------------------------------------------------------- #

_SEG_MAP = {
    "downsample": "downsamplers.0",
    "upsample": "upsamplers.0",
    "proj_geglu": "net.0.proj",
}
_INDEXED = ("down_blocks", "up_blocks", "attentions", "resnets", "blocks_")


def _flax_path_to_torch(path: Tuple[str, ...]) -> Tuple[str, str]:
    """(torch key prefix, kind) for one flax param path (sans leaf).

    kind ∈ {"conv", "dense", "norm", "raw"} drives the tensor transform.
    """
    segs = []
    kind = "raw"
    toks = list(path)
    leaf = toks[-1]
    body = toks[:-1]
    # InflatedConv wraps an nn.Conv named "conv": drop that segment; only the
    # kernel needs the conv layout transform (biases are 1-D pass-through)
    if body and body[-1] == "conv":
        body = body[:-1]
        if leaf == "kernel":
            kind = "conv"
    for t in body:
        if t.startswith("blocks_"):
            segs.append(f"transformer_blocks.{t.split('_')[1]}")
        elif (
            t.startswith("down_blocks_")
            or t.startswith("up_blocks_")
            or t.startswith("attentions_")
            or t.startswith("resnets_")
            or t.startswith("layers_")
        ):
            base, i = t.rsplit("_", 1)
            segs.append(f"{base}.{i}")
        elif t in _SEG_MAP:
            segs.append(_SEG_MAP[t])
        elif t == "proj_out" and segs and segs[-1] == "ff":
            segs.append("net.2")
        elif t == "to_out":
            segs.append("to_out.0")
        else:
            segs.append(t)
    key = ".".join(segs)
    if kind != "conv":
        if leaf == "kernel":
            kind = "dense"
        elif leaf == "scale":
            kind = "norm"
        elif leaf == "embedding":
            kind = "raw"
    torch_leaf = {"kernel": "weight", "scale": "weight", "bias": "bias", "embedding": "weight"}[
        leaf
    ]
    return f"{key}.{torch_leaf}", kind


def _to_flax_tensor(t: Array, kind: str, target_shape: Tuple[int, ...]) -> Array:
    if kind == "conv":
        if t.ndim == 4:
            return np.transpose(t, (2, 3, 1, 0))
        raise ValueError(f"expected 4-D conv weight, got {t.shape}")
    if kind == "dense":
        if t.ndim == 4 and t.shape[2] == t.shape[3] == 1:
            # 1×1 conv in torch ↔ Dense in channels-last flax
            t = t[:, :, 0, 0]
        if t.ndim == 2:
            return np.transpose(t)
        raise ValueError(f"expected 2-D linear weight, got {t.shape}")
    return t


def _from_flax_tensor(t: Array, kind: str, conv1x1: bool = False) -> Array:
    if kind == "conv":
        return np.transpose(t, (3, 2, 0, 1))
    if kind == "dense":
        w = np.transpose(t)
        if conv1x1:
            w = w[:, :, None, None]
        return w
    return t


def unet3d_params_from_torch(
    state_dict: StateDict,
    abstract_params,
    *,
    strict_missing: bool = False,
) -> Tuple[Dict, Dict[str, list]]:
    """Map a diffusers UNet2D (or saved Tune-A-Video UNet3D) state dict onto
    the video UNet's param tree.

    ``abstract_params``: the target "params" tree (real or ShapeDtypeStruct
    leaves) defining structure and shapes. Returns ``(params, report)`` where
    report lists ``kept_init`` (our params with no torch key — must be
    temporal-only unless ``strict_missing``) and ``unused`` torch keys.
    """
    flat = traverse_util.flatten_dict(abstract_params)
    out = {}
    kept_init, used = [], set()
    for path, leaf in flat.items():
        torch_key, kind = _flax_path_to_torch(path)
        src = state_dict.get(torch_key)
        if src is None and kind == "dense":
            # proj_in/proj_out may be stored as 1×1 convs (SD1.x) — same key,
            # handled by _to_flax_tensor; nothing else to try
            pass
        if src is None:
            path_str = "/".join(path)
            if not strict_missing and ("attn_temp" in path_str or "norm_temp" in path_str):
                # 2D checkpoint: temporal params keep their fresh init
                # (unet.py:446-448)
                out[path] = np.asarray(leaf) if hasattr(leaf, "__array__") else leaf
                kept_init.append(path_str)
                continue
            raise KeyError(
                f"no torch key {torch_key!r} for param {path_str!r} "
                f"(and it is not a temporal-inflation param)"
            )
        arr = _to_flax_tensor(np.asarray(src), kind, getattr(leaf, "shape", None))
        if tuple(arr.shape) != tuple(leaf.shape):
            raise ValueError(
                f"shape mismatch for {torch_key!r}: torch {arr.shape} vs "
                f"flax {tuple(leaf.shape)}"
            )
        out[path] = arr.astype(leaf.dtype)  # (no np.asarray: that fetches the leaf)
        used.add(torch_key)
    unused = [k for k in state_dict if k not in used]
    return traverse_util.unflatten_dict(out), {"kept_init": kept_init, "unused": unused}


def unet3d_params_to_torch(params) -> StateDict:
    """Inverse mapping: flax video-UNet params → Tune-A-Video-layout state
    dict (numpy). ``proj_in``/``proj_out`` of the transformer are written as
    1×1 convs, matching the reference module (attention.py:74-88)."""
    flat = traverse_util.flatten_dict(params)
    out: StateDict = {}
    for path, leaf in flat.items():
        torch_key, kind = _flax_path_to_torch(path)
        conv1x1 = kind == "dense" and path[-1] == "kernel" and (
            path[-2] in ("proj_in", "proj_out") and "blocks_0" not in path
        )
        out[torch_key] = _from_flax_tensor(np.asarray(leaf), kind, conv1x1=conv1x1)
    return out


def quantize_unet_params(params, mode: str = "w8", weight_dtype: str = "int8"):
    """Post-training quantization of a flax video-UNet param tree at load
    time (ISSUE 15): every matmul kernel outside the first/last-layer
    precision islands becomes a :class:`~videop2p_tpu.models.quant
    .QuantizedTensor` (int8 or fp8-e4m3 storage + per-output-channel fp32
    scales). The low-precision tree feeds the SAME ``make_unet_fn``
    programs — the adapter dequantizes inside the trace, so the 1-byte
    weights stay the program inputs. ``mode="off"`` returns ``params``
    unchanged (the pinned bit-exact path); ``w8`` and ``w8a8`` quantize
    identically here (the a8 half is the model's ``act_quant_fn`` seam,
    wired by the caller). Works on either the bare ``{"params": ...}``
    collection dict or its inner tree.
    """
    from videop2p_tpu.models.quant import quantize_tree, quant_weight_dtype, \
        validate_quant_mode

    mode = validate_quant_mode(mode)
    if mode == "off":
        return params
    dtype = quant_weight_dtype(weight_dtype)
    if isinstance(params, dict) and "params" in params:
        out = dict(params)
        out["params"] = quantize_tree(params["params"], dtype=dtype)
        return out
    return quantize_tree(params, dtype=dtype)


# --------------------------------------------------------------------- #
# VAE
# --------------------------------------------------------------------- #

_VAE_ATTN_ALIASES = {
    # diffusers ≥0.15 name : 0.11-era name
    "to_q": "query",
    "to_k": "key",
    "to_v": "value",
    "to_out.0": "proj_attn",
}


def _vae_flax_to_torch(path: Tuple[str, ...]) -> Tuple[str, str]:
    toks = list(path)
    leaf = toks.pop()
    segs = []
    for t in toks:
        if t.startswith("down_") and t.split("_")[1].isdigit():
            parts = t.split("_")  # down_{i}_resnets_{j} | down_{i}_downsample
            if parts[2] == "downsample":
                segs.append(f"down_blocks.{parts[1]}.downsamplers.0.conv")
            else:
                segs.append(f"down_blocks.{parts[1]}.{parts[2]}.{parts[3]}")
        elif t.startswith("up_") and t.split("_")[1].isdigit():
            parts = t.split("_")  # up_{i}_resnets_{j} | up_{i}_upsample
            if parts[2] == "upsample":
                segs.append(f"up_blocks.{parts[1]}.upsamplers.0.conv")
            else:
                segs.append(f"up_blocks.{parts[1]}.{parts[2]}.{parts[3]}")
        elif t.startswith("mid_resnets_"):
            segs.append(f"mid_block.resnets.{t.rsplit('_', 1)[1]}")
        elif t == "mid_attn":
            segs.append("mid_block.attentions.0")
        elif t == "to_out":
            segs.append("to_out.0")
        else:
            segs.append(t)
    kind = "norm" if leaf == "scale" else ("dense" if leaf == "kernel" else "raw")
    torch_leaf = {"kernel": "weight", "scale": "weight", "bias": "bias"}[leaf]
    return ".".join(segs) + "." + torch_leaf, kind


def vae_params_from_torch(state_dict: StateDict, abstract_params) -> Dict:
    """diffusers AutoencoderKL state dict → flax params. Handles both
    downsample naming eras and both attention naming eras."""
    flat = traverse_util.flatten_dict(abstract_params)
    out = {}
    for path, leaf in flat.items():
        torch_key, kind = _vae_flax_to_torch(path)
        # our conv modules are plain nn.Conv (kernel 4-D): fix the kind
        if kind == "dense" and len(getattr(leaf, "shape", ())) == 4:
            kind = "conv"
        cands = [torch_key]
        if "downsample" in torch_key:
            cands.append(torch_key.replace("downsample.", "downsamplers.0.conv."))
        if "_downsample" in torch_key:  # encoder down_{i}_downsample
            pass
        for new, old in _VAE_ATTN_ALIASES.items():
            if f".{new}." in torch_key:
                cands.append(torch_key.replace(f".{new}.", f".{old}."))
        src = next((state_dict[c] for c in cands if c in state_dict), None)
        if src is None:
            raise KeyError(f"no torch key for VAE param {'/'.join(path)} (tried {cands})")
        arr = np.asarray(src)
        if kind == "dense" and arr.ndim == 2 and len(leaf.shape) == 2:
            arr = np.transpose(arr)
        elif arr.ndim == 4:
            arr = np.transpose(arr, (2, 3, 1, 0))
        elif kind == "dense" and arr.ndim == 4:
            arr = np.transpose(arr[:, :, 0, 0])
        if tuple(arr.shape) != tuple(leaf.shape):
            raise ValueError(f"VAE shape mismatch at {torch_key}: {arr.shape} vs {leaf.shape}")
        out[path] = arr
    return traverse_util.unflatten_dict(out)


# --------------------------------------------------------------------- #
# CLIP text encoder
# --------------------------------------------------------------------- #


def clip_params_from_torch(state_dict: StateDict, abstract_params) -> Dict:
    """transformers CLIPTextModel state dict → flax CLIPTextEncoder params."""
    pre = "text_model."
    sd = {
        (k[len(pre):] if k.startswith(pre) else k): np.asarray(v)
        for k, v in state_dict.items()
    }
    flat = traverse_util.flatten_dict(abstract_params)
    out = {}
    for path, leaf in flat.items():
        toks = list(path)
        leaf_name = toks.pop()
        if toks == ["token_embedding"] and leaf_name == "embedding":
            arr = sd["embeddings.token_embedding.weight"]
        elif not toks and leaf_name == "position_embedding":
            arr = sd["embeddings.position_embedding.weight"]
        elif toks and toks[0] == "final_layer_norm":
            arr = sd[f"final_layer_norm.{'weight' if leaf_name == 'scale' else 'bias'}"]
        else:
            # layers_{i}/(self_attn|layer_norm1|layer_norm2|fc1|fc2)/...
            i = toks[0].rsplit("_", 1)[1]
            rest = toks[1:]
            if rest and rest[0] in ("fc1", "fc2"):
                name = f"encoder.layers.{i}.mlp.{rest[0]}"
            elif rest and rest[0] == "self_attn":
                name = f"encoder.layers.{i}.self_attn.{rest[1]}"
            else:
                name = f"encoder.layers.{i}.{rest[0]}"
            arr = sd[f"{name}.{'weight' if leaf_name in ('kernel', 'scale') else 'bias'}"]
        if leaf_name == "kernel":
            arr = np.transpose(arr)
        if tuple(arr.shape) != tuple(leaf.shape):
            raise ValueError(f"CLIP shape mismatch at {'/'.join(path)}: {arr.shape} vs {leaf.shape}")
        out[path] = arr
    return traverse_util.unflatten_dict(out)
