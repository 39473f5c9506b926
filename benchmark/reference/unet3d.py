"""Plain float32 ``jax.numpy`` forward of the video UNet: the SD-1.5
denoiser inflated as Tune-A-Video / Video-P2P describe it.

Written from the published description (and ``tests/torch_ref.py`` for the
order of operations); imports nothing of the program. Layout
``(B, F, H, W, C)``, channels last.

  * convolutions are 2-D, applied to every frame (pseudo-3D);
  * resnet GroupNorms pool their statistics over frames, the transformer's
    GroupNorm is per frame (frames are folded into the batch first);
  * ``attn1`` frame attention: queries from every frame, keys and values
    from frame 0 only; ``attn2`` text cross attention; GEGLU feed-forward
    (tanh GELU); ``attn_temp`` attention over the frame axis at every
    spatial position;
  * every block is residual and pre-LayerNorm (eps 1e-6 as flax has it).

Departures from a textbook forward, both for memory only: the frame
attention is computed one frame at a time (``lax.map``), and with
``remat=True`` each resnet and transformer block is a ``jax.checkpoint``.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp
from jax import lax

from benchmark.reference.numerics import (Numerics, Weights, group_norm,
                                          layer_norm, silu)

DOWN = ("CrossAttnDownBlock3D", "CrossAttnDownBlock3D",
        "CrossAttnDownBlock3D", "DownBlock3D")
UP = ("UpBlock3D", "CrossAttnUpBlock3D", "CrossAttnUpBlock3D",
      "CrossAttnUpBlock3D")


def arch_from_config(config: dict) -> dict:
    """The architecture the reference builds, from the configuration's file
    (the published ``unet/config.json`` keys)."""
    return {
        "block_out_channels": tuple(config["block_out_channels"]),
        "layers_per_block": int(config["layers_per_block"]),
        "heads": int(config["attention_head_dim"]),  # diffusers-0.11 naming
        "groups": int(config["norm_num_groups"]),
        "down": tuple(config.get("down_block_types", DOWN)),
        "up": tuple(config.get("up_block_types", UP)),
        "in_channels": int(config.get("in_channels", 4)),
        "out_channels": int(config.get("out_channels", 4)),
    }


def timestep_embedding(t, dim: int):
    half = dim // 2
    freqs = jnp.exp(-math.log(10000.0)
                    * jnp.arange(half, dtype=jnp.float32) / half)
    ang = t.astype(jnp.float32)[:, None] * freqs[None, :]
    return jnp.concatenate([jnp.cos(ang), jnp.sin(ang)], axis=-1)


def frame_conv(nx: Numerics, w: Weights, x, **kw):
    b, f = x.shape[:2]
    y = nx.conv(x.reshape((b * f,) + x.shape[2:]),
                w("conv/kernel"), w("conv/bias"), **kw)
    return y.reshape((b, f) + y.shape[1:])


def resnet(nx: Numerics, w: Weights, x, temb, groups: int):
    h = group_norm(x, w("norm1/scale"), w("norm1/bias"), groups, 1e-5, True)
    h = frame_conv(nx, w.at("conv1"), h)
    t = nx.dense(silu(temb), w("time_emb_proj/kernel"),
                 w("time_emb_proj/bias"))
    h = h + t[:, None, None, None, :]
    h = group_norm(h, w("norm2/scale"), w("norm2/bias"), groups, 1e-5, True)
    h = frame_conv(nx, w.at("conv2"), h)
    if w.has("conv_shortcut/conv/kernel"):
        x = frame_conv(nx, w.at("conv_shortcut"), x, padding=0)
    return x + h


def _heads(x, heads: int):
    b, n, c = x.shape
    return x.reshape(b, n, heads, c // heads).transpose(0, 2, 1, 3)


def _merge(x):
    b, h, n, d = x.shape
    return x.transpose(0, 2, 1, 3).reshape(b, n, h * d)


def frame_attention(nx: Numerics, w: Weights, x, heads: int):
    """x (B, F, N, C); K and V from frame 0."""
    b, f, n, c = x.shape
    d = c // heads
    q = nx.dense(x, w("to_q/kernel"))
    k = _heads(nx.dense(x[:, 0], w("to_k/kernel")), heads)   # (B, H, N, D)
    v = _heads(nx.dense(x[:, 0], w("to_v/kernel")), heads)
    q = q.reshape(b, f, n, heads, d).transpose(1, 0, 3, 2, 4)  # (F,B,H,N,D)

    @jax.checkpoint
    def one_frame(qf):
        sim = nx.einsum("bhqd,bhkd->bhqk", qf, k) * (d ** -0.5)
        return nx.einsum("bhqk,bhkd->bhqd", jax.nn.softmax(sim, axis=-1), v)

    out = lax.map(one_frame, q)                                # (F,B,H,N,D)
    out = out.transpose(1, 0, 3, 2, 4).reshape(b, f, n, c)
    return nx.dense(out, w("to_out/kernel"), w("to_out/bias"))


def attention(nx: Numerics, w: Weights, x, context, heads: int):
    """Plain multi-head attention; x (B, N, C), context (B, L, Cc)."""
    d = x.shape[-1] // heads
    q = _heads(nx.dense(x, w("to_q/kernel")), heads)
    k = _heads(nx.dense(context, w("to_k/kernel")), heads)
    v = _heads(nx.dense(context, w("to_v/kernel")), heads)
    sim = nx.einsum("bhqd,bhkd->bhqk", q, k) * (d ** -0.5)
    out = nx.einsum("bhqk,bhkd->bhqd", jax.nn.softmax(sim, axis=-1), v)
    return nx.dense(_merge(out), w("to_out/kernel"), w("to_out/bias"))


def transformer_block(nx: Numerics, w: Weights, x, text, heads: int):
    """x (B, F, N, C); text (B, L, D)."""
    b, f, n, c = x.shape
    h = layer_norm(x, w("norm1/scale"), w("norm1/bias"))
    x = x + frame_attention(nx, w.at("attn1"), h, heads)

    h = layer_norm(x, w("norm2/scale"), w("norm2/bias")).reshape(b * f, n, c)
    ctx = jnp.repeat(text, f, axis=0)
    x = x + attention(nx, w.at("attn2"), h, ctx, heads).reshape(b, f, n, c)

    h = layer_norm(x, w("norm3/scale"), w("norm3/bias"))
    h = nx.dense(h, w("ff/proj_geglu/kernel"), w("ff/proj_geglu/bias"))
    h, gate = jnp.split(h, 2, axis=-1)
    h = h * jax.nn.gelu(gate, approximate=True)
    x = x + nx.dense(h, w("ff/proj_out/kernel"), w("ff/proj_out/bias"))

    h = layer_norm(x, w("norm_temp/scale"), w("norm_temp/bias"))
    h = h.transpose(0, 2, 1, 3).reshape(b * n, f, c)
    h = attention(nx, w.at("attn_temp"), h, h, heads)
    return x + h.reshape(b, n, f, c).transpose(0, 2, 1, 3)


def transformer3d(nx: Numerics, w: Weights, x, text, heads: int, groups: int):
    b, f, hh, ww, c = x.shape
    h = group_norm(x.reshape(b * f, hh, ww, c), w("norm/scale"),
                   w("norm/bias"), groups, 1e-6).reshape(b, f, hh * ww, c)
    h = nx.dense(h, w("proj_in/kernel"), w("proj_in/bias"))
    i = 0
    while w.has(f"blocks_{i}/norm1/scale"):
        h = transformer_block(nx, w.at(f"blocks_{i}"), h, text, heads)
        i += 1
    h = nx.dense(h, w("proj_out/kernel"), w("proj_out/bias"))
    return h.reshape(b, f, hh, ww, c) + x


def unet3d(flat: dict, arch: dict, sample, timesteps, text, *,
           nx: Numerics = None, remat: bool = False):
    """eps(sample (B,F,H,W,4), timesteps (B,), text (B,L,D)) in float32.
    ``flat``: the ``params/...`` leaves by name."""
    nx = nx or Numerics()
    w = Weights(flat, "params/")
    ch, groups, heads = (arch["block_out_channels"], arch["groups"],
                         arch["heads"])
    layers = arch["layers_per_block"]
    ck = jax.checkpoint if remat else (lambda f: f)

    def res(wb, x, temb):
        return ck(lambda x_, t_: resnet(nx, wb, x_, t_, groups))(x, temb)

    def attn(wb, x):
        return ck(lambda x_: transformer3d(nx, wb, x_, text, heads,
                                           groups))(x)

    sample = sample.astype(jnp.float32)
    text = text.astype(jnp.float32)
    temb = timestep_embedding(jnp.broadcast_to(
        jnp.asarray(timesteps), (sample.shape[0],)), ch[0])
    te = w.at("time_embedding")
    temb = nx.dense(temb, te("linear_1/kernel"), te("linear_1/bias"))
    temb = nx.dense(silu(temb), te("linear_2/kernel"), te("linear_2/bias"))

    x = frame_conv(nx, w.at("conv_in"), sample)
    skips = [x]
    for i, kind in enumerate(arch["down"]):
        wb = w.at(f"down_blocks_{i}")
        for j in range(layers):
            x = res(wb.at(f"resnets_{j}"), x, temb)
            if kind.startswith("CrossAttn"):
                x = attn(wb.at(f"attentions_{j}"), x)
            skips.append(x)
        if i < len(ch) - 1:
            x = frame_conv(nx, wb.at("downsample/conv"), x, stride=2)
            skips.append(x)

    wb = w.at("mid_block")
    x = res(wb.at("resnets_0"), x, temb)
    x = attn(wb.at("attentions_0"), x)
    x = res(wb.at("resnets_1"), x, temb)

    for i, kind in enumerate(arch["up"]):
        wb = w.at(f"up_blocks_{i}")
        for j in range(layers + 1):
            x = jnp.concatenate([x, skips.pop()], axis=-1)
            x = res(wb.at(f"resnets_{j}"), x, temb)
            if kind.startswith("CrossAttn"):
                x = attn(wb.at(f"attentions_{j}"), x)
        if i < len(ch) - 1:
            b, f, hh, ww, c = x.shape
            x = jnp.repeat(jnp.repeat(x, 2, axis=2), 2, axis=3)
            x = frame_conv(nx, wb.at("upsample/conv"), x)

    x = group_norm(x, w("conv_norm_out/scale"), w("conv_norm_out/bias"),
                   groups, 1e-5, True)
    return frame_conv(nx, w.at("conv_out"), x)
