"""Falcon-H1-34B's parallel hybrid block as a token model the Stage-1 tuner
can train.

Source: https://huggingface.co/tiiuae/Falcon-H1-34B-Instruct/blob/main/config.json
(``model_type: falcon_h1``). Pure functions over a nested ``params`` dict, as
``models/deepseek.py`` (whose norm, rotary and chunked head-and-loss this
family calls) and ``models/granite_hybrid.py`` (whose Mamba-2 mixer, chunked
scan and causal attention it calls): the training forward only. Every
multiplier is the published config's, placed where the published modelling
code (``falcon_h1``) places it:

  embedding  h0 = embedding_multiplier * E[ids]; logits = RMSNorm_f(x) W_head
             * lm_head_multiplier, untied.
  layer      u = RMSNorm(x); x += ssm_out_multiplier * M(ssm_in_multiplier
             * u) + attention_out_multiplier * A(attention_in_multiplier * u)
             — the mixer and attention SIDE BY SIDE on the same normed input,
             added to the residual together; then x += F(RMSNorm(x)).
             eps ``rms_norm_eps``; every layer has all three.
  mixer M    Mamba-2 in ``mamba_n_groups`` groups: [z | x | B | C | dt] =
             W_in u, each segment times its ``ssm_multipliers`` entry (z, x,
             B, C, dt); (x, B, C) <- silu(depthwise causal conv(x, B, C) +
             b), width ``mamba_d_conv``; per head (P = ``mamba_d_head``, N =
             ``mamba_d_state``; head h reads group h // (H / G)): dt =
             softplus(dt + dt_bias), A = -exp(A_log), h_t = exp(dt_t A)
             h_{t-1} + dt_t x_t (x) B_t, y_t = h_t C_t + D x_t; y <-
             RMSNorm_w(y * silu(z)) with its mean square per GROUP of
             ``mamba_d_ssm / G`` channels (``mamba_norm_before_gate``
             false: the gate multiplies first); out W_out y.
  attention  q = W_q u, k = key_multiplier * W_k u, v = W_v u
             (``num_attention_heads`` / ``num_key_value_heads`` x
             ``head_dim``; query head i reads key / value head i // group);
             rotary, rotate-half over the whole head at ``rope_theta``;
             causal softmax(q . k / sqrt(head_dim)) v; W_o.
  F          W_down (silu(gate_mult * W_gate y) * W_up y) * down_mult,
             (gate_mult, down_mult) = ``mlp_multipliers``, width
             ``intermediate_size``.

**The chip's share.** Whole layers: every head, both groups, the whole
feed-forward; ``vocab_size`` is the slice of the embedding and head rows
held. The layers held are a pipeline stage's; nothing stands in for the
other stages or vocabulary slices.

Departures from the published code, none of them in the mathematics:
``in_proj`` is stored as [z | x | B | dt] with the C columns a leaf of their
own (``in_proj_c``: in the state-space duality C is the query, and Stage 1
trains query projections; ``granite_hybrid``'s layout); the input
multipliers (``ssm_in_multiplier``, ``attention_in_multiplier``,
``key_multiplier``) scale the float32 products of the projections they feed,
which is the same linear map rounded once. Not in the catalog row and so
``assumed``: the multipliers' placement above, the ``ssm_multipliers``
order, the per-group gated norm, ``A_log`` / ``dt_bias`` / ``D`` by the
Mamba-2 convention (``granite_hybrid.leaf_from_normal``), no clamp on dt.

Device ops carry the named scopes ``lm.mamba_proj`` (projections, conv, gate
and norm), ``lm.ssd`` (the chunked scan), ``lm.attention`` (projections,
rotary and the pair), ``lm.dense_ffn`` and ``lm.head_loss``.

**Which code attends.** On the TPU, where its fit test takes the shape, the
Pallas pair of ``ops/selected_attention.py`` with no selection
(``causal_attention``); elsewhere ``granite_hybrid._chunked_causal_attend``.
Chosen from the backend and the shapes. The scan is XLA everywhere.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from videop2p_tpu.models import deepseek
from videop2p_tpu.models.deepseek import (
    _dense,
    _is_spec,
    _rms_norm,
    _rotate,
    head_loss,
    seeded_params,
)
from videop2p_tpu.models.granite_hybrid import (
    _chunked_causal_attend,
    _kernel_applies,
    _scaled_dense,
    leaf_from_normal,
    mamba_out_part,
    mamba_scan_part,
)
from videop2p_tpu.ops.selected_attention import (
    causal_attention,
    keep_attention_outputs,
)

__all__ = [
    "FalconH1Config",
    "init_params",
    "mixer",
    "attention",
    "dense_ffn",
    "forward_loss",
    "forward_logits",
]

# The named scopes of this family's device ops and the scalars a step hands
# out beside the loss: what benchmark/layer_metrics/ reads by NAME
# (tests/test_spans.py holds the lowered loss and a tiny ``main`` to them).
SCOPES = ("lm.mamba_proj", "lm.ssd", "lm.attention", "lm.dense_ffn",
          "lm.head_loss")
COUNTERS = ("ssd_state_rms", "branch_rms_ratio")


@dataclasses.dataclass(frozen=True)
class FalconH1Config:
    """The published ``config.json`` keys (defaults as published)."""

    hidden_size: int = 5120
    intermediate_size: int = 21504
    mlp_expansion_factor: int = 8         # not read: intermediate_size rules
    num_hidden_layers: int = 72
    num_attention_heads: int = 20
    num_key_value_heads: int = 4
    head_dim: int = 128
    rope_theta: float = 1e11
    rope_scaling: Optional[Dict[str, Any]] = None
    max_position_embeddings: int = 262144
    attention_in_multiplier: float = 1.0
    attention_out_multiplier: float = 0.0375
    key_multiplier: float = 0.011048543456039804
    embedding_multiplier: float = 5.656854249492381
    lm_head_multiplier: float = 0.0078125
    mlp_multipliers: Tuple[float, float] = (0.1767766952966369,
                                            0.011160714285714284)
    ssm_in_multiplier: float = 0.25
    ssm_multipliers: Tuple[float, ...] = (
        0.3535533905932738, 0.25, 0.1767766952966369, 0.5, 0.3535533905932738)
    ssm_out_multiplier: float = 0.08838834764831845
    mamba_chunk_size: int = 128
    mamba_conv_bias: bool = True
    mamba_d_conv: int = 4
    mamba_d_head: int = 128
    mamba_d_ssm: int = 4096
    mamba_d_state: int = 256
    mamba_expand: int = 2                 # not read: mamba_d_ssm rules
    mamba_n_groups: int = 2
    mamba_n_heads: int = 32
    mamba_norm_before_gate: bool = False
    mamba_proj_bias: bool = False
    mamba_rms_norm: bool = True
    mamba_use_mlp: bool = True
    attention_bias: bool = False
    mlp_bias: bool = False
    projectors_bias: bool = False
    hidden_act: str = "silu"
    attn_layer_indices: Optional[Tuple[int, ...]] = None
    num_logits_to_keep: int = 1           # generation only
    rms_norm_eps: float = 1e-5
    tie_word_embeddings: bool = False
    vocab_size: int = 261120
    # recompute each layer in the backward pass; kept across it: the output
    # and log-sum-exp of the attention kernel pair where it ran, nothing else
    # (``ops.selected_attention.keep_attention_outputs``)
    remat: bool = True
    # the token families' builder sets it; this one makes no discrete choice
    # to hand out, so it stays False
    hand_out_choices: bool = False

    @classmethod
    def from_dict(cls, d: Dict[str, Any]) -> "FalconH1Config":
        """From a ``config.json``-shaped dict; unknown keys are an error."""
        names = {f.name for f in dataclasses.fields(cls)}
        kw = {k: (tuple(v) if isinstance(v, list) else v) for k, v in d.items()}
        unknown = sorted(set(kw) - names)
        if unknown:
            raise ValueError(f"unknown FalconH1Config keys {unknown}; "
                             f"known: {sorted(names)}")
        return cls(**kw)

    @classmethod
    def tiny(cls, **kw) -> "FalconH1Config":
        """The CPU tests' size: 64 wide, 2 layers, 4 query heads of 16 on 2
        key / value heads, 8 Mamba heads of 8 in 2 groups with a state of
        16 in chunks of 8, a feed-forward of 96."""
        base = dict(
            hidden_size=64, intermediate_size=96, num_hidden_layers=2,
            num_attention_heads=4, num_key_value_heads=2, head_dim=16,
            rope_theta=1e4, mamba_chunk_size=8, mamba_d_head=8,
            mamba_d_ssm=64, mamba_d_state=16, mamba_n_heads=8,
            vocab_size=256)
        return cls(**{**base, **kw})

    @property
    def mamba_heads_held(self) -> Tuple[int, int]:
        return 0, self.mamba_n_heads      # whole layers: every head

    def check(self) -> None:
        assert self.mamba_n_heads * self.mamba_d_head == self.mamba_d_ssm
        assert self.mamba_n_heads % self.mamba_n_groups == 0
        assert self.num_attention_heads % self.num_key_value_heads == 0
        assert self.head_dim % 2 == 0 and len(self.ssm_multipliers) == 5
        # what this file does not build
        assert self.mamba_rms_norm and not self.mamba_norm_before_gate
        assert self.mamba_use_mlp and self.mamba_conv_bias
        assert not (self.mamba_proj_bias or self.attention_bias
                    or self.mlp_bias or self.projectors_bias)
        assert self.hidden_act == "silu" and self.rope_scaling is None
        assert self.attn_layer_indices is None  # attention in every layer
        assert not self.tie_word_embeddings and not self.hand_out_choices


# ---------------------------------------------------------------- weights


def param_shapes(cfg: FalconH1Config) -> Dict[str, Any]:
    """``{"params": {...}}`` of ``(shape, fan_in)`` leaves, the layout of
    ``models/deepseek.py``: a matrix is ``kernel`` with its input features
    second to last."""
    cfg.check()
    h, hd = cfg.hidden_size, cfg.head_dim
    hq, hkv = cfg.num_attention_heads, cfg.num_key_value_heads
    mh, d = cfg.mamba_n_heads, cfg.mamba_d_ssm
    n = cfg.mamba_n_groups * cfg.mamba_d_state

    def mat(i, o):
        return {"kernel": ((i, o), i)}

    layer = {
        "input_norm": {"scale": ((h,), None)},
        "pre_ff_norm": {"scale": ((h,), None)},
        "mamba": {
            "in_proj": mat(h, 2 * d + n + mh),   # [z | x | B | dt]
            "in_proj_c": mat(h, n),
            "conv": {"kernel": ((cfg.mamba_d_conv, d + 2 * n), cfg.mamba_d_conv),
                     "bias": ((d + 2 * n,), None)},  # [x | B | C]
            "A_log": ((mh,), None), "D": ((mh,), None),
            "dt_bias": ((mh,), None),
            "norm": {"scale": ((d,), None)},
            "out_proj": mat(d, h)},
        "attn": {
            "q_proj": mat(h, hq * hd), "k_proj": mat(h, hkv * hd),
            "v_proj": mat(h, hkv * hd), "o_proj": mat(hq * hd, h)},
        "mlp": {"gate_proj": mat(h, cfg.intermediate_size),
                "up_proj": mat(h, cfg.intermediate_size),
                "down_proj": mat(cfg.intermediate_size, h)},
    }
    return {"params": {
        "embed": {"embedding": ((cfg.vocab_size, h), None)},
        **{f"layers_{i}": layer for i in range(cfg.num_hidden_layers)},
        "final_norm": {"scale": ((h,), None)},
        "head": mat(h, cfg.vocab_size),
    }}


def abstract_params(cfg: FalconH1Config, dtype=jnp.bfloat16):
    return jax.tree.map(lambda s: jax.ShapeDtypeStruct(s[0], dtype),
                        param_shapes(cfg), is_leaf=_is_spec)


def init_params(key: jax.Array, cfg: FalconH1Config, dtype=jnp.bfloat16):
    """Seeded random weights in the checkpoint's dtype (every leaf
    bfloat16), ``granite_hybrid.leaf_from_normal`` leaf by leaf."""
    return seeded_params(key, param_shapes(cfg), dtype, leaf_from_normal)


def rope_angles(cfg: FalconH1Config, positions) -> jax.Array:
    """(T, head_dim / 2) rotary angles: position * theta^(-2 i / head_dim),
    the frequencies computed in float64 on the host."""
    dim = cfg.head_dim
    freqs = 1.0 / float(cfg.rope_theta) ** (np.arange(0, dim, 2) / dim)
    return (positions.astype(jnp.float32)[:, None]
            * jnp.asarray(freqs, jnp.float32)[None, :])


# ------------------------------------------------------------------ layers


def mixer(p, cfg: FalconH1Config, u):
    """The Mamba-2 branch's contribution (T, h) for the normed input ``u``,
    after ``ssm_out_multiplier``, and the scan's reading of what its last
    chunk was handed. ``ssm_in_multiplier`` scales the in-projection's
    products with the segments' own multipliers."""
    g, sum_sq, handed_sq = mamba_scan_part(
        p, cfg, u, tuple(cfg.ssm_in_multiplier * m for m in cfg.ssm_multipliers))
    out = mamba_out_part(p, cfg, g, sum_sq / (g.shape[-1] // sum_sq.shape[-1]))
    return out * cfg.ssm_out_multiplier, handed_sq


def attention(p, cfg: FalconH1Config, u, angles):
    """The attention branch's contribution (T, h) for the normed input
    ``u``, after ``attention_out_multiplier``."""
    t_len = u.shape[0]
    hq, hkv, hd = cfg.num_attention_heads, cfg.num_key_value_heads, cfg.head_dim
    a_in = cfg.attention_in_multiplier
    with jax.named_scope("lm.attention"):
        q = _scaled_dense(u, p["q_proj"]["kernel"], a_in).reshape(t_len, hq, hd)
        k = _scaled_dense(u, p["k_proj"]["kernel"],
                          a_in * cfg.key_multiplier).reshape(t_len, hkv, hd)
        v = _scaled_dense(u, p["v_proj"]["kernel"], a_in).reshape(t_len, hkv, hd)
        q = _rotate(q, angles, interleaved=False)
        k = _rotate(k, angles, interleaved=False)
        scale = hd ** -0.5
        if _kernel_applies(q):
            o = causal_attention(q, k, v, scale)
        else:
            o = _chunked_causal_attend(q, k, v, scale)
        out = _dense(o.reshape(t_len, hq * hd), p["o_proj"]["kernel"])
        return out * cfg.attention_out_multiplier


@functools.partial(jax.checkpoint, static_argnums=(4,))
def _ffn_block(y, wg, wu, wd, multipliers):
    gate_mult, down_mult = multipliers
    hidden = (jax.nn.silu(_scaled_dense(y, wg, gate_mult))
              * _dense(y, wu))
    return _scaled_dense(hidden, wd, down_mult)


def dense_ffn(p, cfg: FalconH1Config, y):
    """The feed-forward over blocks of ``deepseek.FFN_ROWS`` tokens, each
    recomputed in the backward pass (its (rows, intermediate_size)
    intermediates are the layer's largest). At the cell's 32768 tokens the
    compiler counts the 5-step tuning program at 11.97 GiB for a v5e chip;
    unblocked, at 14.53 of the chip's 15.75, for the same operations (the
    layer's own remat recomputes gate and up either way), leaving no room
    for a pipeline stage's activation buffers."""
    w = [p[n]["kernel"] for n in ("gate_proj", "up_proj", "down_proj")]
    rows = deepseek.FFN_ROWS
    with jax.named_scope("lm.dense_ffn"):
        return jnp.concatenate(
            [_ffn_block(y[i:i + rows], *w, tuple(cfg.mlp_multipliers))
             for i in range(0, y.shape[0], rows)], axis=0)


def _rms(v):
    return jnp.sqrt(jnp.mean(jnp.square(v.astype(jnp.float32))))


def _layer(cfg: FalconH1Config, p, x, angles):
    u = _rms_norm(x, p["input_norm"]["scale"], cfg.rms_norm_eps)
    mixed, handed_sq = mixer(p["mamba"], cfg, u)
    attended = attention(p["attn"], cfg, u, angles)
    x = x + (mixed + attended)
    y = _rms_norm(x, p["pre_ff_norm"]["scale"], cfg.rms_norm_eps)
    x = x + dense_ffn(p["mlp"], cfg, y)
    ratio = lax.stop_gradient(_rms(attended) / _rms(mixed))
    return x, handed_sq, ratio


def _forward(params, cfg: FalconH1Config, ids, dtype):
    cfg.check()
    angles = rope_angles(cfg, jnp.arange(ids.shape[0]))
    x = (cfg.embedding_multiplier
         * params["embed"]["embedding"].astype(dtype)[ids]).astype(dtype)
    layer = functools.partial(_layer, cfg)
    if cfg.remat:
        layer = jax.checkpoint(layer, policy=keep_attention_outputs)
    states, ratios = [], []
    for i in range(cfg.num_hidden_layers):
        x, handed_sq, ratio = layer(params[f"layers_{i}"], x, angles)
        states.append(jnp.sqrt(handed_sq))
        ratios.append(ratio)
    # root mean square, over the last chunk's outputs, of the term the state
    # handed to that chunk adds (0 if chunks stop handing their state on);
    # and the attention branch's contribution over the mixer's in root mean
    # square: both means over the layers
    return x, {"ssd_state_rms": jnp.mean(jnp.stack(states)),
               "branch_rms_ratio": jnp.mean(jnp.stack(ratios))}


def forward_loss(params, cfg: FalconH1Config, ids, dtype=jnp.bfloat16):
    """``(loss, aux)`` for one document ``ids`` (T,): the mean next-token
    cross-entropy over the vocabulary slice, and the step's counters
    (scalars, means over the layers: ``ssd_state_rms``,
    ``branch_rms_ratio``)."""
    x, aux = _forward(params, cfg, ids, dtype)
    return head_loss(params["final_norm"]["scale"], params["head"]["kernel"],
                     x, ids, cfg.rms_norm_eps,
                     logit_scale=cfg.lm_head_multiplier), aux


def forward_logits(params, cfg: FalconH1Config, ids, dtype=jnp.bfloat16):
    """(T, vocabulary held) float32 logits of one document, unchunked (for
    tests and small sizes)."""
    x, _ = _forward(params, cfg, ids, dtype)
    y = _rms_norm(x, params["final_norm"]["scale"], cfg.rms_norm_eps)
    return jnp.matmul(y, params["head"]["kernel"].astype(y.dtype),
                      preferred_element_type=jnp.float32) * cfg.lm_head_multiplier
