"""Granite-4.0-H-Small's hybrid block as a token model the Stage-1 tuner can
train.

Source: https://huggingface.co/ibm-granite/granite-4.0-h-small/blob/main/config.json
(``model_type: granitemoehybrid``). Pure functions over a nested ``params``
dict, as ``models/deepseek.py`` (whose expert dispatch, norms, dense SwiGLU
and chunked head-and-loss this family calls): the training forward only.

  embedding  h0 = embedding_multiplier * E[ids]; logits = RMSNorm(x) E^T /
             logits_scaling (one tied matrix).
  layer i    x += residual_multiplier * Mixer_i(RMSNorm(x)), the mixer
             ``layer_types[i]``; then y = RMSNorm(x),
             x += residual_multiplier * (Experts(y) + Shared(y)). eps 1e-5.
  mamba      Mamba-2, one B / C group: [z | x | B | dt] = W_in u and
             C = W_c u; (x, B, C) <- silu(depthwise causal conv(x, B, C) +
             b), width ``mamba_d_conv``; per head (P = ``mamba_d_head``
             channels, N = ``mamba_d_state``): dt = softplus(dt + dt_bias),
             A = -exp(A_log), h_t = exp(dt_t A) h_{t-1} + dt_t x_t (x) B_t,
             y_t = h_t C_t + D x_t; y <- RMSNorm_w(y * silu(z)) over the
             inner channels; out W_out y.
  attention  grouped-query, causal, NO positional encoding:
             softmax(q . k * attention_multiplier) v, W_o.
  experts    l = W_r y in float32; the ``num_experts_per_tok`` largest
             logits; gates = softmax over those; sum gate_e E_e(y), E a
             SwiGLU of width ``intermediate_size``; a shared SwiGLU of width
             ``shared_intermediate_size`` on every token. No token dropped.

**The state-space scan is chunked** (``ssd_scan``; chunks of
``mamba_chunk_size`` tokens): inside a chunk the recurrence is the masked
product (C B^T ∘ L) (dt x), L the decays between two positions; a chunk's
tokens leave a state behind, the states are carried from chunk to chunk by
a short scan, and the state a chunk starts from adds C_t h exp(cum_t) to
its outputs. Log-decays, their cumulative sums and the states are float32;
bfloat16 only as matmul operands. The backward is autodiff's, with groups
of ``SSD_GROUP`` chunks recomputed so that the float32 (heads, chunk, chunk)
decay tiles are not kept. The scan and the mixer's two halves also take B / C
in several groups (``models/falcon_h1.py``).

**Which code scans.** On the TPU, where its fit test takes the shape, the
Pallas pair of ``ops/ssd_scan.py`` (``lm_ssd_scan`` / ``_bwd``: a block of
heads' state stays in VMEM from chunk to chunk, the groups a grid
coordinate); elsewhere the XLA code of ``ssd_scan``, the tests' oracle.
Chosen from the backend and the shapes.

**The chip's share.** ``experts_held``, ``heads_held`` (query heads; the key
/ value heads follow from the grouping) and ``mamba_heads_held`` are
``(first, count)`` ranges; ``vocab_size`` is the slice held. Of a Mamba
layer the share holds its heads' z / x / dt columns of ``in_proj``, their
conv channels, ``A_log``, ``D``, ``dt_bias``, the gated norm's scale and the
rows of ``out_proj``; B and C (one group), the router, the shared expert
and the layer norms are held whole by every chip. What absent experts and
heads would add is left out; nothing stands in for the other chips. **The
one statistic that crosses shares** is the gated norm's mean square over
all inner channels: ``mamba_scan_part`` returns the held channels and their
sum of squares, ``mamba_out_part`` takes a mean square — one chip hands it
its own (over the channels it holds), a test sums the four shares'.

Departures from the published code, none of them in the mathematics:
``in_proj`` is stored as [z | x | B | dt] with the C columns as a leaf of
their own (``in_proj_c``: in the state-space duality C is the query, and
Stage 1 trains query projections); the conv's channels are [x | B | C];
``kernel[j]`` of the conv multiplies token t - (width - 1) + j; an expert's
fused input matrix is stored as ``gate_proj`` / ``up_proj``. Not in the
catalog row and so ``assumed``: ``A_log = log U[1, 16]``, ``dt_bias =
softplus^-1(U[1e-3, 1e-1])``, ``D = 1``, no clamp on dt, and the gated norm
multiplies by silu(z) BEFORE it normalises.

Device ops carry the named scopes ``lm.mamba_proj`` (projections, conv,
gate and norm), ``lm.ssd`` (all of the chunked scan), ``lm.attention``,
``lm.router``, ``lm.experts``, ``lm.shared_expert`` and ``lm.head_loss``.

**Which code attends.** On the TPU, where its fit test takes the shape, the
Pallas pair of ``ops/selected_attention.py`` with no selection
(``causal_attention``); elsewhere ``_chunked_causal_attend``, plain XLA in
row blocks, the tests' oracle. Chosen from the backend and the shapes.

**Which code runs the expert loop.** ``deepseek.held_expert_ffn``, the same
for both families: on the TPU at shapes on its tiling (this cell's 4096 x 768
is) the Pallas pair of ``ops/grouped_experts.py`` — an expert's three
matrices stay in VMEM over its blocks —, elsewhere the XLA loop.
"""

from __future__ import annotations

import dataclasses
import functools
import math
from typing import Any, Dict, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.scipy.special import ndtr

from videop2p_tpu.models.deepseek import (
    _dense,
    _is_spec,
    _rms_norm,
    _swiglu,
    head_loss,
    held_expert_ffn,
    seeded_leaf,
    seeded_params,
)
from videop2p_tpu.ops.selected_attention import (
    causal_attention,
    keep_attention_outputs,
    selected_attention_tiles,
)
from videop2p_tpu.ops.ssd_scan import ssd_scan_kernel, ssd_scan_plan

__all__ = [
    "GraniteHybridConfig",
    "init_params",
    "ssd_scan",
    "mamba_scan_part",
    "mamba_out_part",
    "attention",
    "route",
    "forward_loss",
    "forward_logits",
]

# The named scopes of this family's device ops and the scalars a step hands
# out beside the loss: what benchmark/layer_metrics/ reads by NAME
# (tests/test_spans.py holds the lowered loss and a tiny ``main`` to them).
SCOPES = ("lm.mamba_proj", "lm.ssd", "lm.attention", "lm.router",
          "lm.experts", "lm.shared_expert", "lm.head_loss")
COUNTERS = ("expert_load_max_over_mean", "held_pair_share",
            "routed_over_shared", "ssd_state_rms", "expert_rows_packed")

# How the work is cut (no effect on the mathematics). Not configuration:
# one value is in use, tests patch them.
SSD_GROUP = 16      # chunks whose decay tiles are live at once (and are
                    # recomputed together in the backward pass)
ATTN_ROWS = 512     # queries attended at once where attention runs as XLA

_PUBLISHED_LAYERS = (("mamba",) * 5 + ("attention",) + ("mamba",) * 4) * 4


@dataclasses.dataclass(frozen=True)
class GraniteHybridConfig:
    """The published ``config.json`` keys (defaults as published) and the
    chip's share."""

    hidden_size: int = 4096
    intermediate_size: int = 768          # one routed expert's width
    shared_intermediate_size: int = 1536
    num_hidden_layers: int = 40
    layer_types: Tuple[str, ...] = _PUBLISHED_LAYERS
    num_attention_heads: int = 32
    num_key_value_heads: int = 8
    attention_multiplier: float = 0.0078125
    embedding_multiplier: float = 12.0
    residual_multiplier: float = 0.22
    logits_scaling: float = 16.0
    mamba_n_heads: int = 128
    mamba_d_head: int = 64
    mamba_d_state: int = 128
    mamba_d_conv: int = 4
    mamba_expand: int = 2
    mamba_n_groups: int = 1
    mamba_chunk_size: int = 256
    mamba_conv_bias: bool = True
    mamba_proj_bias: bool = False
    num_local_experts: int = 72
    num_experts_per_tok: int = 10
    vocab_size: int = 100352
    rms_norm_eps: float = 1e-5
    attention_bias: bool = False
    tie_word_embeddings: bool = True
    position_embedding_type: str = "nope"
    # the chip's share: (first, count) of the routed experts, of the query
    # heads and of the Mamba heads
    experts_held: Tuple[int, int] = (0, 72)
    heads_held: Tuple[int, int] = (0, 32)
    mamba_heads_held: Tuple[int, int] = (0, 128)
    # recompute each layer in the backward pass; kept across it: the output
    # and log-sum-exp of the attention kernel pair where it ran, nothing else
    # (``ops.selected_attention.keep_attention_outputs``)
    remat: bool = True
    # the loss hands out, beside its scalars, the experts every layer CHOSE
    # for every token: what a check against a reference takes as data
    hand_out_choices: bool = False

    @classmethod
    def from_dict(cls, d: Dict[str, Any]) -> "GraniteHybridConfig":
        """From a ``config.json``-shaped dict; unknown keys are an error."""
        names = {f.name for f in dataclasses.fields(cls)}
        kw = {k: (tuple(v) if isinstance(v, list) else v) for k, v in d.items()}
        unknown = sorted(set(kw) - names)
        if unknown:
            raise ValueError(f"unknown GraniteHybridConfig keys {unknown}; "
                             f"known: {sorted(names)}")
        return cls(**kw)

    @classmethod
    def tiny(cls, **kw) -> "GraniteHybridConfig":
        """The CPU tests' size: 64 wide, 3 layers (mamba, attention, mamba),
        8 query heads on 4 key / value heads, 16 Mamba heads of 8 with a
        state of 16 in chunks of 8, 8 experts, top-3."""
        base = dict(
            hidden_size=64, intermediate_size=32, shared_intermediate_size=48,
            num_hidden_layers=3, layer_types=("mamba", "attention", "mamba"),
            num_attention_heads=8, num_key_value_heads=4,
            attention_multiplier=0.25, mamba_n_heads=16, mamba_d_head=8,
            mamba_d_state=16, mamba_chunk_size=8, num_local_experts=8,
            num_experts_per_tok=3, vocab_size=256, experts_held=(0, 8),
            heads_held=(0, 8), mamba_heads_held=(0, 16))
        return cls(**{**base, **kw})

    @property
    def head_dim(self) -> int:
        return self.hidden_size // self.num_attention_heads

    @property
    def kv_heads_held(self) -> Tuple[int, int]:
        group = self.num_attention_heads // self.num_key_value_heads
        return self.heads_held[0] // group, self.heads_held[1] // group

    def check(self) -> None:
        for (first, count), total in (
                (self.experts_held, self.num_local_experts),
                (self.heads_held, self.num_attention_heads),
                (self.mamba_heads_held, self.mamba_n_heads)):
            assert 0 <= first and count >= 1 and first + count <= total
        group = self.num_attention_heads // self.num_key_value_heads
        assert self.num_attention_heads % self.num_key_value_heads == 0
        assert self.heads_held[0] % group == 0 and self.heads_held[1] % group == 0
        assert len(self.layer_types) == self.num_hidden_layers
        assert set(self.layer_types) <= {"mamba", "attention"}
        assert (self.mamba_n_heads * self.mamba_d_head
                == self.mamba_expand * self.hidden_size)
        # what this file does not build
        assert self.mamba_n_groups == 1 and self.mamba_conv_bias
        assert not self.mamba_proj_bias and not self.attention_bias
        assert self.tie_word_embeddings
        assert self.position_embedding_type == "nope"


# ---------------------------------------------------------------- weights


def param_shapes(cfg: GraniteHybridConfig) -> Dict[str, Any]:
    """``{"params": {...}}`` of ``(shape, fan_in)`` leaves, the layout of
    ``models/deepseek.py``: a matrix is ``kernel`` with its input features
    second to last, expert matrices stacked over the experts held."""
    cfg.check()
    h, en = cfg.hidden_size, cfg.experts_held[1]
    hq, hkv, hd = cfg.heads_held[1], cfg.kv_heads_held[1], cfg.head_dim
    mh, n = cfg.mamba_heads_held[1], cfg.mamba_d_state
    d = mh * cfg.mamba_d_head

    def mat(i, o, *lead):
        return {"kernel": (tuple(lead) + (i, o), i)}

    def mlp(width, *lead):
        return {"gate_proj": mat(h, width, *lead),
                "up_proj": mat(h, width, *lead),
                "down_proj": mat(width, h, *lead)}

    mixers = {
        "mamba": {"mamba": {
            "in_proj": mat(h, 2 * d + n + mh),   # [z | x | B | dt]
            "in_proj_c": mat(h, n),
            "conv": {"kernel": ((cfg.mamba_d_conv, d + 2 * n), cfg.mamba_d_conv),
                     "bias": ((d + 2 * n,), None)},  # [x | B | C]
            "A_log": ((mh,), None), "D": ((mh,), None),
            "dt_bias": ((mh,), None),
            "norm": {"scale": ((d,), None)},
            "out_proj": mat(d, h)}},
        "attention": {"attn": {
            "q_proj": mat(h, hq * hd), "k_proj": mat(h, hkv * hd),
            "v_proj": mat(h, hkv * hd), "o_proj": mat(hq * hd, h)}},
    }
    layers = {}
    for i, kind in enumerate(cfg.layer_types):
        layers[f"layers_{i}"] = {
            "input_norm": {"scale": ((h,), None)},
            "post_norm": {"scale": ((h,), None)},
            **mixers[kind],
            "router": mat(h, cfg.num_local_experts),
            "experts": mlp(cfg.intermediate_size, en),
            "shared": mlp(cfg.shared_intermediate_size),
        }
    return {"params": {
        "embed": {"embedding": ((cfg.vocab_size, h), None)},
        **layers,
        "final_norm": {"scale": ((h,), None)},
    }}


def abstract_params(cfg: GraniteHybridConfig, dtype=jnp.bfloat16):
    return jax.tree.map(lambda s: jax.ShapeDtypeStruct(s[0], dtype),
                        param_shapes(cfg), is_leaf=_is_spec)


def leaf_from_normal(name: str, z, fan_in):
    """``deepseek.seeded_leaf`` with the three per-head leaves of a Mamba-2
    mixer: ``A_log = log U[1, 16]``, ``dt_bias = softplus^-1(U[1e-3, 1e-1])``
    (uniforms from the draws' normal distribution function), ``D = 1``."""
    uniform = lambda lo, hi: lo + (hi - lo) * ndtr(z)  # noqa: E731
    if name == "A_log":
        return jnp.log(uniform(1.0, 16.0))
    if name == "dt_bias":
        return jnp.log(jnp.expm1(uniform(1e-3, 1e-1)))
    if name == "D":
        return jnp.ones_like(z)
    return seeded_leaf(name, z, fan_in)


def init_params(key: jax.Array, cfg: GraniteHybridConfig, dtype=jnp.bfloat16):
    """Seeded random weights in the checkpoint's dtype (every leaf
    bfloat16), :func:`leaf_from_normal` leaf by leaf."""
    return seeded_params(key, param_shapes(cfg), dtype, leaf_from_normal)


# ------------------------------------------------------------- Mamba-2 mixer


def _causal_conv(x, kernel, bias):
    """Depthwise causal conv over tokens: ``x`` (T, C), ``kernel`` (W, C),
    out[t] = sum_j kernel[j] * x[t - (W - 1) + j] + bias, float32 sums."""
    width, t_len = kernel.shape[0], x.shape[0]
    padded = jnp.pad(x.astype(jnp.float32), ((width - 1, 0), (0, 0)))
    k32 = kernel.astype(jnp.float32)
    out = sum(k32[j] * padded[j:j + t_len] for j in range(width))
    return out + bias.astype(jnp.float32)


def _chunk_outputs(x_dt, b, c, cum, h_prev):
    """The outputs of a group of chunks: ``x_dt`` (G, Q, H, P) = dt x as an
    operand, ``b``, ``c`` (G, Q, N), ``cum`` (G, Q, H) float32 cumulative
    log-decays inside each chunk, ``h_prev`` (G, H, P, N) the state each
    chunk starts from → (G, Q, H, P) float32, and per chunk (G,) the mean
    square of the part of it that came through ``h_prev`` (a reading, cut
    off from the gradient)."""
    q_len = cum.shape[1]
    # L[g, h, i, j] = exp(cum_i - cum_j) for j <= i: masked BEFORE the exp
    by_head = cum.transpose(0, 2, 1)
    seg = by_head[:, :, :, None] - by_head[:, :, None, :]
    lower = jnp.arange(q_len)[:, None] >= jnp.arange(q_len)[None, :]
    decay = jnp.exp(jnp.where(lower, seg, -jnp.inf))
    cb = jnp.einsum("gin,gjn->gij", c, b, preferred_element_type=jnp.float32)
    inside = jnp.einsum("ghij,gjhp->gihp",
                        (cb[:, None] * decay).astype(x_dt.dtype), x_dt,
                        preferred_element_type=jnp.float32)
    carried = jnp.einsum("gin,ghpn->gihp", c, h_prev.astype(c.dtype),
                         preferred_element_type=jnp.float32)
    handed = carried * jnp.exp(cum)[..., None]
    return inside + handed, lax.stop_gradient(
        jnp.mean(jnp.square(handed), axis=(1, 2, 3)))


def _scan_kernel_applies(x, b, chunk: int) -> bool:
    """Whether the scan runs as the Pallas pair: on the TPU, where its fit
    test takes the shape. Chosen from what the input is, never by an
    option."""
    if jax.default_backend() != "tpu":
        return False
    t_len, heads, width = x.shape
    groups = 1 if b.ndim == 2 else b.shape[1]
    return ssd_scan_plan(t_len, heads, width, b.shape[-1], chunk, groups,
                         x.dtype) is not None


def ssd_scan(x, dt, a, b, c, chunk: int):
    """The selective state-space recurrence, chunked: ``x`` (T, H, P),
    ``dt`` (T, H) float32 step sizes, ``a`` (H,) float32 negative rates,
    ``b``, ``c`` (T, N) → ``y`` (T, H, P) float32 with y_t = h_t c_t,
    h_t = exp(dt_t a) h_{t-1} + dt_t x_t (x) b_t, h_{-1} = 0, the last
    state (H, P, N) float32, and the mean square over the LAST chunk's
    outputs of the term the state handed to that chunk adds (0 for a single
    chunk, and 0 if chunks stop handing their state on). T must be a
    multiple of ``chunk`` or below it.

    ``b``, ``c`` (T, G, N) are G groups: head h reads group h // (H / G),
    the heads of a group contiguous (Mamba-2's ``ngroups``). Each group is
    the one-group scan above over its own heads, one group at a time and
    each recomputed in the backward pass: a group's float32 chunk states
    (chunks, H / G, P, N) are what the scan holds most of.

    Where :func:`_scan_kernel_applies` — the TPU at shapes the kernels'
    fit test takes — the scan is ``ops.ssd_scan.ssd_scan_kernel`` (all
    groups in one call), the same mathematics and rounding points;
    everywhere else the XLA code below."""
    if _scan_kernel_applies(x, b, chunk):
        return ssd_scan_kernel(x, dt, a, b, c, chunk)
    if b.ndim == 3:
        groups = b.shape[1]
        split = lambda v: v.reshape(v.shape[:1] + (groups, -1) + v.shape[2:])  # noqa: E731
        first = lambda v: jnp.moveaxis(v, 1, 0)  # noqa: E731
        y, last, handed_sq = lax.map(
            jax.checkpoint(lambda g: ssd_scan(*g, chunk)),
            (first(split(x)), first(split(dt)), a.reshape(groups, -1),
             first(b), first(c)))
        return (jnp.moveaxis(y, 0, 1).reshape(x.shape),
                last.reshape((-1,) + last.shape[2:]), jnp.mean(handed_sq))
    t_len, heads, width = x.shape
    q_len = min(chunk, t_len)
    assert t_len % q_len == 0, (t_len, q_len)
    n_chunks = t_len // q_len
    group = math.gcd(SSD_GROUP, n_chunks)
    cut = lambda v: v.reshape((n_chunks, q_len) + v.shape[1:])  # noqa: E731
    cum = jnp.cumsum(cut(dt * a[None, :]), axis=1)              # (C, Q, H)
    x_dt32 = cut(x).astype(jnp.float32) * cut(dt)[..., None]
    x_dt, b_c, c_c = x_dt32.astype(x.dtype), cut(b), cut(c)
    # what each chunk's own tokens leave behind at its end
    to_end = jnp.exp(cum[:, -1:, :] - cum)
    left = jnp.einsum("cqhp,cqn->chpn",
                      (x_dt32 * to_end[..., None]).astype(x.dtype), b_c,
                      preferred_element_type=jnp.float32)

    def carry(h, inp):
        own, decay = inp
        return decay[:, None, None] * h + own, h

    last, h_prev = lax.scan(carry, jnp.zeros(left.shape[1:], jnp.float32),
                            (left, jnp.exp(cum[:, -1, :])))
    grouped = lambda v: v.reshape((n_chunks // group, group) + v.shape[1:])  # noqa: E731
    y, handed_sq = lax.map(lambda g: jax.checkpoint(_chunk_outputs)(*g),
                           tuple(map(grouped, (x_dt, b_c, c_c, cum, h_prev))))
    return y.reshape(t_len, heads, width), last, handed_sq.reshape(-1)[-1]


def mamba_scan_part(p, cfg: GraniteHybridConfig, u, multipliers=None):
    """The first half of the mixer on the normed input ``u`` (T, h): the
    held heads' gated scan outputs ``g = y * silu(z)`` (T, d held) float32,
    their sum of squares (T, G) over each group's channels (G =
    ``mamba_n_groups``; G = 1: (T, 1) over all of them) — this share's part
    of the gated norm's statistic — and :func:`ssd_scan`'s mean square of
    what the last chunk's outputs got through the state it was handed.
    ``multipliers`` (z, x, B, C, dt): scales of the in-projection's
    segments, applied to its float32 products (Falcon-H1's μP
    ``ssm_multipliers``); None: none."""
    t_len = u.shape[0]
    mh, hp = cfg.mamba_heads_held[1], cfg.mamba_d_head
    groups = cfg.mamba_n_groups
    n = groups * cfg.mamba_d_state
    d = mh * hp
    if groups > 1:  # the groups are read whole: every head is held
        assert cfg.mamba_heads_held == (0, cfg.mamba_n_heads)
    with jax.named_scope("lm.mamba_proj"):
        if multipliers is None:
            zxbdt = _dense(u, p["in_proj"]["kernel"])
        else:
            mz, mx, mb, mc, mdt = multipliers
            zxbdt = _scaled_dense(u, p["in_proj"]["kernel"], np.repeat(
                np.float32([mz, mx, mb, mdt]), [d, d, n, mh]))
        z, dt_raw = zxbdt[:, :d], zxbdt[:, 2 * d + n:]
        xb = zxbdt[:, d:2 * d + n]
        c_in = (_dense(u, p["in_proj_c"]["kernel"]) if multipliers is None
                else _scaled_dense(u, p["in_proj_c"]["kernel"], np.float32(mc)))
        xbc = jnp.concatenate([xb, c_in], axis=-1)
        xbc = jax.nn.silu(_causal_conv(xbc, p["conv"]["kernel"],
                                       p["conv"]["bias"])).astype(u.dtype)
        x = xbc[:, :d].reshape(t_len, mh, hp)
        b, c = xbc[:, d:d + n], xbc[:, d + n:]
        if groups > 1:
            b, c = (v.reshape(t_len, groups, -1) for v in (b, c))
    with jax.named_scope("lm.ssd"):
        dt = jax.nn.softplus(dt_raw.astype(jnp.float32)
                             + p["dt_bias"].astype(jnp.float32))
        a = -jnp.exp(p["A_log"].astype(jnp.float32))
        y, _, handed_sq = ssd_scan(x, dt, a, b, c, cfg.mamba_chunk_size)
        y = y + p["D"].astype(jnp.float32)[None, :, None] * x.astype(jnp.float32)
    with jax.named_scope("lm.mamba_proj"):
        g = y.reshape(t_len, d) * jax.nn.silu(z.astype(jnp.float32))
        sq = g * g if groups == 1 else jnp.square(g.reshape(t_len, groups, -1))
        return g, jnp.sum(sq, axis=-1, keepdims=groups == 1), handed_sq


def _scaled_dense(x, kernel, scale):
    """``x kernel``, its float32 products times ``scale`` (a column's or
    one), rounded once to ``x``'s dtype."""
    y = jnp.matmul(x, kernel.astype(x.dtype), preferred_element_type=jnp.float32)
    return (y * scale).astype(x.dtype)


def mamba_out_part(p, cfg: GraniteHybridConfig, g, mean_square):
    """The second half: the gated norm of ``g`` GIVEN the mean square (T, G)
    over each group's inner channels (G = 1: over ALL of them), and the held
    rows of ``out_proj`` → (T, h)."""
    with jax.named_scope("lm.mamba_proj"):
        groups = mean_square.shape[-1]
        if groups == 1:
            y = (g * lax.rsqrt(mean_square + cfg.rms_norm_eps)
                 * p["norm"]["scale"].astype(jnp.float32))
        else:  # a group's channels by their own statistic
            y = (g.reshape(g.shape[0], groups, -1)
                 * lax.rsqrt(mean_square[..., None] + cfg.rms_norm_eps)
                 ).reshape(g.shape) * p["norm"]["scale"].astype(jnp.float32)
        kernel = p["out_proj"]["kernel"]
        return _dense(y.astype(kernel.dtype), kernel)


def mamba_mixer(p, cfg: GraniteHybridConfig, u):
    """This chip's Mamba heads' part of the mixer output, normalised by the
    mean square over the channels it holds, and the scan's reading of what
    its last chunk was handed."""
    g, sum_sq, handed_sq = mamba_scan_part(p, cfg, u)
    return mamba_out_part(p, cfg, g, sum_sq / g.shape[-1]), handed_sq


# ---------------------------------------------------------------- attention


def _causal_attend(q, k, v, first, scale, window=None, key_first=0):
    """``q`` (R, H_kv, G, D) rows from position ``first`` against the keys
    ``k``, ``v`` (S, H_kv, D) that sit at positions ``key_first`` onwards
    (all of them, from 0, unless a caller cut them); inside a ``window`` key
    s is seen by query t iff 0 <= t - s < window."""
    s = jnp.einsum("rkgd,tkd->kgrt", q, k, preferred_element_type=jnp.float32)
    keys = jnp.arange(k.shape[0])[None, :]
    rows = (first + jnp.arange(q.shape[0]))[:, None]
    if window is None:
        causal = keys <= rows
    else:
        keys = key_first + keys
        causal = (keys <= rows) & (keys > rows - window)
    prob = jax.nn.softmax(jnp.where(causal, s * scale, -jnp.inf), axis=-1)
    return jnp.einsum("kgrt,tkd->rkgd", prob.astype(v.dtype), v)


def _chunked_causal_attend(q, k, v, scale, window=None):
    """Causal grouped-query attention as XLA: ``ATTN_ROWS`` queries at a
    time against all keys — with a ``window``, against the
    ``ATTN_ROWS + window - 1`` keys a row block can see and no others —
    each row block recomputed in the backward pass. ``q`` (T, H, D); ``k``,
    ``v`` (T, H_kv, D) → (T, H, D)."""
    t_len, heads, width = q.shape
    kv_heads = k.shape[1]
    rows = math.gcd(ATTN_ROWS, t_len)
    span = t_len if window is None else min(t_len, rows + window - 1)
    if span == t_len:
        attend = functools.partial(_causal_attend, scale=scale, window=window)
    else:
        def attend(q_rows, k, v, first):
            # the last key a row block sees is its own last row's
            at = jnp.clip(first + rows - span, 0, t_len - span)
            return _causal_attend(
                q_rows, lax.dynamic_slice_in_dim(k, at, span),
                lax.dynamic_slice_in_dim(v, at, span), first, scale, window, at)

    attend = jax.checkpoint(attend)
    out = lax.map(
        lambda a: attend(a[0], k, v, a[1]),
        (q.reshape(t_len // rows, rows, kv_heads, heads // kv_heads, width),
         rows * jnp.arange(t_len // rows)))
    return out.reshape(t_len, heads, width)


def _kernel_applies(q) -> bool:
    """Whether the attention runs as the Pallas pair: on the TPU, where its
    fit test takes the shape. Chosen from what the input is, never by an
    option."""
    if jax.default_backend() != "tpu":
        return False
    t_len, heads, width = q.shape
    return selected_attention_tiles(t_len, heads, width, 0, width,
                                    q.dtype) is not None


def attention(p, cfg: GraniteHybridConfig, u):
    """This chip's query heads' part of the attention output for the normed
    input ``u`` (T, h)."""
    t_len = u.shape[0]
    hq, hkv, hd = cfg.heads_held[1], cfg.kv_heads_held[1], cfg.head_dim
    with jax.named_scope("lm.attention"):
        q = _dense(u, p["q_proj"]["kernel"]).reshape(t_len, hq, hd)
        k = _dense(u, p["k_proj"]["kernel"]).reshape(t_len, hkv, hd)
        v = _dense(u, p["v_proj"]["kernel"]).reshape(t_len, hkv, hd)
        if _kernel_applies(q):
            o = causal_attention(q, k, v, cfg.attention_multiplier)
        else:
            o = _chunked_causal_attend(q, k, v, cfg.attention_multiplier)
        return _dense(o.reshape(t_len, hq * hd), p["o_proj"]["kernel"])


# ------------------------------------------------------------- expert layer


def route(p, cfg: GraniteHybridConfig, y):
    """(experts (T, K) int32, gates (T, K) float32) over ALL routed experts:
    the K largest float32 logits, gates a softmax over those."""
    logits = jnp.matmul(y, p["kernel"].astype(y.dtype),
                        preferred_element_type=jnp.float32)
    top, experts = lax.top_k(logits, cfg.num_experts_per_tok)
    return experts.astype(jnp.int32), jax.nn.softmax(top, axis=-1)


# ------------------------------------------------------------------ forward


def _layer(cfg: GraniteHybridConfig, p, x):
    u = _rms_norm(x, p["input_norm"]["scale"], cfg.rms_norm_eps)
    if "mamba" in p:
        mixed, state_sq = mamba_mixer(p["mamba"], cfg, u)
    else:
        mixed, state_sq = attention(p["attn"], cfg, u), None
    x = x + (cfg.residual_multiplier * mixed).astype(x.dtype)
    y = _rms_norm(x, p["post_norm"]["scale"], cfg.rms_norm_eps)
    with jax.named_scope("lm.router"):
        experts, gates = route(p["router"], cfg, y)
    routed, shared, counters = held_expert_ffn(p, y, experts, gates,
                                               cfg.experts_held)
    x = x + (cfg.residual_multiplier * (routed + shared)).astype(x.dtype)
    return x, counters, experts, state_sq


def _forward(params, cfg: GraniteHybridConfig, ids, dtype):
    cfg.check()
    x = (cfg.embedding_multiplier
         * params["embed"]["embedding"].astype(dtype)[ids]).astype(dtype)
    layer = functools.partial(_layer, cfg)
    if cfg.remat:
        layer = jax.checkpoint(layer, policy=keep_attention_outputs)
    counters, states, choices = [], [], []
    for i in range(cfg.num_hidden_layers):
        x, c, experts, state_sq = layer(params[f"layers_{i}"], x)
        counters.append(c)
        if state_sq is not None:
            states.append(jnp.sqrt(state_sq))
        if cfg.hand_out_choices:
            choices.append({"experts": experts,
                            "routed_over_shared": c["routed_over_shared"]})
    aux = {k: jnp.mean(jnp.stack([c[k] for c in counters]))
           for k in counters[0]}
    # root mean square, over the last chunk's outputs, of the term the state
    # handed to that chunk adds; mean over the Mamba layers: 0 if chunks
    # stop handing their state on
    aux["ssd_state_rms"] = (jnp.mean(jnp.stack(states)) if states
                            else jnp.zeros((), jnp.float32))
    if cfg.hand_out_choices:
        aux["choices"] = choices
    return x, aux


def forward_loss(params, cfg: GraniteHybridConfig, ids, dtype=jnp.bfloat16):
    """``(loss, aux)`` for one document ``ids`` (T,): the mean next-token
    cross-entropy over the vocabulary slice, and the step's counters
    (scalars: the expert layers' means, ``ssd_state_rms``). Under
    ``cfg.hand_out_choices`` ``aux["choices"]`` holds, per layer, the
    ``experts`` (T, K) THIS pass chose and its ``routed_over_shared``."""
    x, aux = _forward(params, cfg, ids, dtype)
    return head_loss(params["final_norm"]["scale"],
                     params["embed"]["embedding"], x, ids, cfg.rms_norm_eps,
                     tied=True, logit_scale=1.0 / cfg.logits_scaling), aux


def forward_logits(params, cfg: GraniteHybridConfig, ids, dtype=jnp.bfloat16):
    """(T, vocabulary held) float32 logits of one document, unchunked (for
    tests and small sizes)."""
    x, _ = _forward(params, cfg, ids, dtype)
    y = _rms_norm(x, params["final_norm"]["scale"], cfg.rms_norm_eps)
    return jnp.matmul(y, params["embed"]["embedding"].astype(y.dtype).T,
                      preferred_element_type=jnp.float32) / cfg.logits_scaling
