"""Plain float32 decoder of the SD AutoencoderKL, applied per frame:
latents (B, F, h, w, 4) (already x0.18215) -> frames in [-1, 1]."""

from __future__ import annotations

import jax
import jax.numpy as jnp

from benchmark.reference.numerics import Numerics, Weights, group_norm, silu

SCALING = 0.18215


def _resnet(nx, w: Weights, x, groups):
    h = silu(group_norm(x, w("norm1/scale"), w("norm1/bias"), groups, 1e-6))
    h = nx.conv(h, w("conv1/kernel"), w("conv1/bias"))
    h = silu(group_norm(h, w("norm2/scale"), w("norm2/bias"), groups, 1e-6))
    h = nx.conv(h, w("conv2/kernel"), w("conv2/bias"))
    if w.has("conv_shortcut/kernel"):
        x = nx.conv(x, w("conv_shortcut/kernel"), w("conv_shortcut/bias"),
                    padding=0)
    return x + h


def _attn(nx, w: Weights, x, groups):
    b, hh, ww, c = x.shape
    h = group_norm(x, w("group_norm/scale"), w("group_norm/bias"), groups,
                   1e-6).reshape(b, hh * ww, c)
    q = nx.dense(h, w("to_q/kernel"), w("to_q/bias"))
    k = nx.dense(h, w("to_k/kernel"), w("to_k/bias"))
    v = nx.dense(h, w("to_v/kernel"), w("to_v/bias"))
    sim = nx.einsum("bqc,bkc->bqk", q, k) * (c ** -0.5)
    out = nx.einsum("bqk,bkc->bqc", jax.nn.softmax(sim, axis=-1), v)
    out = nx.dense(out, w("to_out/kernel"), w("to_out/bias"))
    return x + out.reshape(b, hh, ww, c)


def decode_frames(flat: dict, vae: dict, z, *, nx: Numerics = None):
    """z (N, h, w, 4) scaled latents -> (N, 8h, 8w, 3) in [-1, 1]."""
    nx = nx or Numerics()
    w = Weights(flat, "params/")
    groups = vae["norm_num_groups"]
    rev = tuple(reversed(vae["block_out_channels"]))
    z = z.astype(jnp.float32) / SCALING
    x = nx.conv(z, w("post_quant_conv/kernel"), w("post_quant_conv/bias"),
                padding=0)
    d = w.at("decoder")
    x = nx.conv(x, d("conv_in/kernel"), d("conv_in/bias"))
    x = _resnet(nx, d.at("mid_resnets_0"), x, groups)
    x = _attn(nx, d.at("mid_attn"), x, groups)
    x = _resnet(nx, d.at("mid_resnets_1"), x, groups)
    for i in range(len(rev)):
        for j in range(vae["layers_per_block"] + 1):
            x = _resnet(nx, d.at(f"up_{i}_resnets_{j}"), x, groups)
        if i < len(rev) - 1:
            x = jnp.repeat(jnp.repeat(x, 2, axis=1), 2, axis=2)
            x = nx.conv(x, d(f"up_{i}_upsample/kernel"),
                        d(f"up_{i}_upsample/bias"))
    x = silu(group_norm(x, d("conv_norm_out/scale"), d("conv_norm_out/bias"),
                        groups, 1e-6))
    return nx.conv(x, d("conv_out/kernel"), d("conv_out/bias"))
