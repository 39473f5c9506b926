"""Command A+'s block as a token model the Stage-1 tuner can train.

Source: https://huggingface.co/CohereLabs/command-a-plus-05-2026/blob/main/config.json
(``model_type: cohere2_moe``). Pure functions over a nested ``params`` dict,
as ``models/granite_hybrid.py`` (whose row-blocked XLA attention this family
calls, with ``models/deepseek.py``'s expert dispatch, rotary, dense SwiGLU
and chunked head-and-loss): the training forward of the text path only.

  embedding  h0 = E[ids]; logits = LN_f(x) E^T * logit_scale (one tied
             matrix).
  layer i    u = LN(x): mean-centred LayerNorm, a scale and no bias, eps
             ``layer_norm_eps``. ONE parallel residual: x += A(u) + F(u).
  attention  A = W_o softmax(q k^T / sqrt(head_dim) + mask) v, grouped-query
             (``num_attention_heads`` on ``num_key_value_heads``), no bias, no
             q / k norm. ``layer_types[i] == "sliding_attention"``: rotary on
             q and k, adjacent pairs of dims (``rope_gptj``), theta
             ``rope_theta``, the whole head width; key s is seen by query t
             iff 0 <= t - s < ``sliding_window``. ``"full_attention"``: NO
             positional encoding, s <= t.
  experts    s = sigmoid(W_r u) in float32 over ``num_experts``; the
             ``num_experts_per_tok`` largest s; gates s_sel / sum s_sel
             (``norm_topk_prob``); no groups, no bias, no scaling factor.
             F = sum gate_e E_e(u) + (1 / num_shared_experts) sum_j S_j(u):
             E_e, S_j gated silu feed-forwards of width ``intermediate_size``;
             the shared outputs are AVERAGED and added, unweighted, to the
             routed sum. No token dropped.

**The chip's share.** ``experts_held`` and ``heads_held`` (query heads; the
key / value heads follow from the grouping) are ``(first, count)`` ranges,
``shared_columns_held`` a range of the ``num_shared_experts *
intermediate_size`` inner columns of the shared experts laid side by side
(``p["shared"]`` is one SwiGLU over the columns held: the average over the
experts is a sum over those columns divided by their count of experts, so
the shares' parts add up to it exactly, as tensor parallelism slices a
feed-forward); ``vocab_size`` is the slice held. The router and the norms
are held whole. What absent experts, heads and columns would add is left
out; nothing stands in for the other chips.

Not in the catalog row and so ``assumed``: that the shared average is ADDED,
unweighted, to the routed sum; the window's edge (``sliding_window`` keys,
the query's own among them); ``intermediate_size`` read as one expert's
width, routed and shared. The vision tower is not in the row: image inputs
cannot be run (no tower, no projector in ``models/``).

Device ops carry the named scopes ``lm.window_attention`` (projections,
rotary and the attention of the sliding layers), ``lm.attention`` (the full
layers'), ``lm.router``, ``lm.experts``, ``lm.shared_expert`` and
``lm.head_loss``.

**Which code attends.** On the TPU, where its fit test takes the shape, the
Pallas pair of ``ops/selected_attention.py`` with no selection
(``causal_attention``): a full layer walks the causal tile pairs, a sliding
layer only those that intersect its band. Elsewhere
``granite_hybrid._chunked_causal_attend``, plain XLA in row blocks that read
only the keys a block can see. Chosen from the backend and the shapes; the
counter ``window_tile_share`` comes from the branch that ran: what it walked
for a sliding layer over what it walks for a full one.

**Which code runs the expert loop.** ``deepseek.held_expert_ffn``, the same
for every token family: on the TPU at shapes on its tiling (this cell's 4096
x 4096 is: inner tiles of 1024) the Pallas pair of ``ops/grouped_experts.py``,
elsewhere the XLA loop.
"""

from __future__ import annotations

import dataclasses
import functools
import math
from typing import Any, Dict, Tuple

import jax
import jax.numpy as jnp
from jax import lax

from videop2p_tpu.models import granite_hybrid
from videop2p_tpu.models.deepseek import (
    _dense,
    _is_spec,
    _rotate,
    head_loss,
    held_expert_ffn,
    seeded_params,
)
from videop2p_tpu.models.granite_hybrid import (
    _chunked_causal_attend,
    _kernel_applies,
)
from videop2p_tpu.ops.selected_attention import (
    causal_attention,
    causal_tile_pairs,
    keep_attention_outputs,
    selected_attention_tiles,
)

__all__ = [
    "Cohere2MoeConfig",
    "init_params",
    "attention",
    "route",
    "forward_loss",
    "forward_logits",
]

# The named scopes of this family's device ops and the scalars a step hands
# out beside the loss: what benchmark/layer_metrics/ reads by NAME
# (tests/test_spans.py holds the lowered loss and a tiny ``main`` to them).
SCOPES = ("lm.window_attention", "lm.attention", "lm.router", "lm.experts",
          "lm.shared_expert", "lm.head_loss")
COUNTERS = ("expert_load_max_over_mean", "held_pair_share",
            "routed_over_shared", "window_tile_share", "expert_rows_packed")

_SLIDING, _FULL = "sliding_attention", "full_attention"
_PUBLISHED_LAYERS = ((_SLIDING,) * 3 + (_FULL,)) * 8


@dataclasses.dataclass(frozen=True)
class Cohere2MoeConfig:
    """The published ``config.json`` keys (defaults as published) and the
    chip's share."""

    hidden_size: int = 4096
    intermediate_size: int = 4096         # one expert's width, routed or shared
    head_dim: int = 128
    num_hidden_layers: int = 32
    layer_types: Tuple[str, ...] = _PUBLISHED_LAYERS
    num_attention_heads: int = 128
    num_key_value_heads: int = 8
    sliding_window: int = 4096
    rope_theta: float = 50000.0
    rotary_pct: float = 1.0
    position_embedding_type: str = "rope_gptj"
    num_experts: int = 128
    num_experts_per_tok: int = 8
    num_shared_experts: int = 4
    expert_selection_fn: str = "sigmoid"
    norm_topk_prob: bool = True
    shared_expert_combination_strategy: str = "average"
    first_k_dense_replace: int = 0
    use_parallel_block: bool = True
    use_gated_activation: bool = True
    use_qk_norm: bool = False
    attention_bias: bool = False
    hidden_act: str = "silu"
    layer_norm_eps: float = 1e-5
    logit_scale: float = 1.0
    tie_word_embeddings: bool = True
    vocab_size: int = 262144
    # the chip's share: (first, count) of the routed experts, of the query
    # heads, and of the shared experts' inner columns laid side by side
    experts_held: Tuple[int, int] = (0, 128)
    heads_held: Tuple[int, int] = (0, 128)
    shared_columns_held: Tuple[int, int] = (0, 16384)
    # recompute each layer in the backward pass; kept across it: the output
    # and log-sum-exp of the attention kernel pair where it ran, nothing else
    # (``ops.selected_attention.keep_attention_outputs``)
    remat: bool = True
    # the loss hands out, beside its scalars, the experts every layer CHOSE
    # for every token: what a check against a reference takes as data
    hand_out_choices: bool = False

    @classmethod
    def from_dict(cls, d: Dict[str, Any]) -> "Cohere2MoeConfig":
        """From a ``config.json``-shaped dict; unknown keys are an error."""
        names = {f.name for f in dataclasses.fields(cls)}
        kw = {k: (tuple(v) if isinstance(v, list) else v) for k, v in d.items()}
        unknown = sorted(set(kw) - names)
        if unknown:
            raise ValueError(f"unknown Cohere2MoeConfig keys {unknown}; "
                             f"known: {sorted(names)}")
        return cls(**kw)

    @classmethod
    def tiny(cls, **kw) -> "Cohere2MoeConfig":
        """The CPU tests' size: 64 wide, 4 layers (sliding x 3, full), 8
        query heads of 16 on 2 key / value heads, a window of 8, 8 experts
        of 32, top-3, 2 shared experts."""
        base = dict(
            hidden_size=64, intermediate_size=32, head_dim=16,
            num_hidden_layers=4, layer_types=(_SLIDING,) * 3 + (_FULL,),
            num_attention_heads=8, num_key_value_heads=2, sliding_window=8,
            num_experts=8, num_experts_per_tok=3, num_shared_experts=2,
            vocab_size=256, experts_held=(0, 8), heads_held=(0, 8),
            shared_columns_held=(0, 64))
        return cls(**{**base, **kw})

    @property
    def kv_heads_held(self) -> Tuple[int, int]:
        group = self.num_attention_heads // self.num_key_value_heads
        return self.heads_held[0] // group, -(-self.heads_held[1] // group)

    def check(self) -> None:
        for (first, count), total in (
                (self.experts_held, self.num_experts),
                (self.heads_held, self.num_attention_heads),
                (self.shared_columns_held,
                 self.num_shared_experts * self.intermediate_size)):
            assert 0 <= first and count >= 1 and first + count <= total
        group = self.num_attention_heads // self.num_key_value_heads
        assert self.num_attention_heads % self.num_key_value_heads == 0
        # the heads held are whole groups, or lie inside one
        first, count = self.heads_held
        assert (first % group == 0 and count % group == 0) or (
            first // group == (first + count - 1) // group)
        assert len(self.layer_types) == self.num_hidden_layers
        assert set(self.layer_types) <= {_SLIDING, _FULL}
        assert self.sliding_window >= 1 and self.head_dim % 2 == 0
        # what this file does not build
        assert self.expert_selection_fn == "sigmoid" and self.norm_topk_prob
        assert self.shared_expert_combination_strategy == "average"
        assert self.first_k_dense_replace == 0    # no dense leading layer
        assert self.use_parallel_block and self.use_gated_activation
        assert self.hidden_act == "silu"
        assert not self.use_qk_norm and not self.attention_bias
        assert self.tie_word_embeddings
        assert self.position_embedding_type == "rope_gptj"
        assert self.rotary_pct == 1


# ---------------------------------------------------------------- weights


def param_shapes(cfg: Cohere2MoeConfig) -> Dict[str, Any]:
    """``{"params": {...}}`` of ``(shape, fan_in)`` leaves, the layout of
    ``models/deepseek.py``: a matrix is ``kernel`` with its input features
    second to last, expert matrices stacked over the experts held, the
    shared experts' held columns as one SwiGLU."""
    cfg.check()
    h, en = cfg.hidden_size, cfg.experts_held[1]
    hq, hkv, hd = cfg.heads_held[1], cfg.kv_heads_held[1], cfg.head_dim

    def mat(i, o, *lead):
        return {"kernel": (tuple(lead) + (i, o), i)}

    def mlp(width, *lead):
        return {"gate_proj": mat(h, width, *lead),
                "up_proj": mat(h, width, *lead),
                "down_proj": mat(width, h, *lead)}

    layer = {
        "input_norm": {"scale": ((h,), None)},
        "attn": {"q_proj": mat(h, hq * hd), "k_proj": mat(h, hkv * hd),
                 "v_proj": mat(h, hkv * hd), "o_proj": mat(hq * hd, h)},
        "router": mat(h, cfg.num_experts),
        "experts": mlp(cfg.intermediate_size, en),
        "shared": mlp(cfg.shared_columns_held[1]),
    }
    return {"params": {
        "embed": {"embedding": ((cfg.vocab_size, h), None)},
        **{f"layers_{i}": layer for i in range(cfg.num_hidden_layers)},
        "final_norm": {"scale": ((h,), None)},
    }}


def abstract_params(cfg: Cohere2MoeConfig, dtype=jnp.bfloat16):
    return jax.tree.map(lambda s: jax.ShapeDtypeStruct(s[0], dtype),
                        param_shapes(cfg), is_leaf=_is_spec)


def init_params(key: jax.Array, cfg: Cohere2MoeConfig, dtype=jnp.bfloat16):
    """Seeded random weights in the checkpoint's dtype (every leaf
    bfloat16), ``deepseek.seeded_leaf`` leaf by leaf."""
    return seeded_params(key, param_shapes(cfg), dtype)


# ------------------------------------------------------------ small pieces


def _layer_norm(x, scale, eps):
    """Mean-centred LayerNorm with a scale and no bias, float32 statistics."""
    x32 = x.astype(jnp.float32)
    centred = x32 - jnp.mean(x32, axis=-1, keepdims=True)
    y = centred * lax.rsqrt(jnp.mean(centred * centred, axis=-1, keepdims=True)
                            + eps)
    return (y * scale.astype(jnp.float32)).astype(x.dtype)


def rope_angles(cfg: Cohere2MoeConfig, positions) -> jax.Array:
    """(T, head_dim / 2) rotary angles: position * theta^(-2 i / head_dim)."""
    dim = cfg.head_dim
    freqs = 1.0 / (cfg.rope_theta
                   ** (jnp.arange(0, dim, 2, dtype=jnp.float32) / dim))
    return positions.astype(jnp.float32)[:, None] * freqs[None, :]


# ---------------------------------------------------------------- attention


def attention(p, cfg: Cohere2MoeConfig, u, angles=None):
    """``(this chip's query heads' part of the attention output, walked)``
    for the normed input ``u`` (T, h): a sliding layer where rotary
    ``angles`` are handed in (rotary on q and k, the window), a full layer
    without them (no positional encoding, every earlier key). ``walked`` is
    what the branch that ran walks over what the SAME branch walks for a
    full layer: the (query tile, key tile) steps of the kernel pair inside
    the band over the causal ones, or, as XLA, the keys a row block reads
    over all of them; 1.0 for a full layer."""
    t_len = u.shape[0]
    hq, hkv, hd = cfg.heads_held[1], cfg.kv_heads_held[1], cfg.head_dim
    sliding = angles is not None
    window = cfg.sliding_window if sliding else None
    with jax.named_scope("lm.window_attention" if sliding else "lm.attention"):
        q = _dense(u, p["q_proj"]["kernel"]).reshape(t_len, hq, hd)
        k = _dense(u, p["k_proj"]["kernel"]).reshape(t_len, hkv, hd)
        v = _dense(u, p["v_proj"]["kernel"]).reshape(t_len, hkv, hd)
        if sliding:
            q = _rotate(q, angles, interleaved=True)
            k = _rotate(k, angles, interleaved=True)
        scale = hd ** -0.5
        if _kernel_applies(q):
            o = causal_attention(q, k, v, scale, window=window)
            tiles = selected_attention_tiles(t_len, hq, hd, 0, hd, q.dtype)
            walked = (causal_tile_pairs(t_len, tiles, window)
                      / causal_tile_pairs(t_len, tiles))
        else:
            o = _chunked_causal_attend(q, k, v, scale, window)
            rows = math.gcd(granite_hybrid.ATTN_ROWS, t_len)
            walked = (1.0 if window is None
                      else min(t_len, rows + window - 1) / t_len)
        return _dense(o.reshape(t_len, hq * hd), p["o_proj"]["kernel"]), walked


# ------------------------------------------------------------- expert layer


def route(p, cfg: Cohere2MoeConfig, u):
    """(experts (T, K) int32, gates (T, K) float32) over ALL routed experts:
    float32 sigmoid scores, the K largest, gates the scores over their sum
    (``norm_topk_prob``)."""
    logits = jnp.matmul(u, p["kernel"].astype(u.dtype),
                        preferred_element_type=jnp.float32)
    top, experts = lax.top_k(jax.nn.sigmoid(logits), cfg.num_experts_per_tok)
    return (experts.astype(jnp.int32),
            top / jnp.sum(top, axis=-1, keepdims=True))


# ------------------------------------------------------------------ forward


def _layer(cfg: Cohere2MoeConfig, p, x, angles):
    u = _layer_norm(x, p["input_norm"]["scale"], cfg.layer_norm_eps)
    attended, walked = attention(p["attn"], cfg, u, angles)
    with jax.named_scope("lm.router"):
        experts, gates = route(p["router"], cfg, u)
    routed, shared, counters = held_expert_ffn(p, u, experts, gates,
                                               cfg.experts_held)
    # ``p["shared"]`` is the held columns of ALL the shared experts side by
    # side as one SwiGLU: their AVERAGE is that sum over their count (the
    # shares' parts then add up to the mean), and the counter is of it
    n = cfg.num_shared_experts
    with jax.named_scope("lm.shared_expert"):
        shared = (shared.astype(jnp.float32) / n).astype(shared.dtype)
    counters = {**counters,
                "routed_over_shared": counters["routed_over_shared"] * n,
                "window_tile_share": jnp.asarray(walked, jnp.float32)}
    # one parallel residual: a single add of everything the one norm fed
    return x + (attended + routed + shared).astype(x.dtype), counters, experts


def _forward(params, cfg: Cohere2MoeConfig, ids, dtype):
    cfg.check()
    t_len = ids.shape[0]
    x = params["embed"]["embedding"].astype(dtype)[ids]
    angles = rope_angles(cfg, jnp.arange(t_len))
    layer = functools.partial(_layer, cfg)
    if cfg.remat:
        layer = jax.checkpoint(layer, policy=keep_attention_outputs)
    counters, choices = [], []
    for i, kind in enumerate(cfg.layer_types):
        x, c, experts = layer(params[f"layers_{i}"], x,
                              angles if kind == _SLIDING else None)
        counters.append(c)
        if cfg.hand_out_choices:
            choices.append({"experts": experts,
                            "routed_over_shared": c["routed_over_shared"]})
    aux = {k: jnp.mean(jnp.stack([c[k] for c in counters]))
           for k in counters[0]}
    # of the SLIDING layers alone (a full layer says 1.0: so does their lack)
    sliding = [c["window_tile_share"]
               for c, kind in zip(counters, cfg.layer_types) if kind == _SLIDING]
    if sliding:
        aux["window_tile_share"] = jnp.mean(jnp.stack(sliding))
    if cfg.hand_out_choices:
        aux["choices"] = choices
    return x, aux


def forward_loss(params, cfg: Cohere2MoeConfig, ids, dtype=jnp.bfloat16):
    """``(loss, aux)`` for one document ``ids`` (T,): the mean next-token
    cross-entropy over the vocabulary slice, and the step's counters
    (scalars: the expert layers' means, ``window_tile_share``). Under
    ``cfg.hand_out_choices`` ``aux["choices"]`` holds, per layer, the
    ``experts`` (T, K) THIS pass chose and its ``routed_over_shared``."""
    x, aux = _forward(params, cfg, ids, dtype)
    return head_loss(params["final_norm"]["scale"],
                     params["embed"]["embedding"], x, ids, cfg.layer_norm_eps,
                     tied=True, logit_scale=cfg.logit_scale,
                     norm=_layer_norm), aux


def forward_logits(params, cfg: Cohere2MoeConfig, ids, dtype=jnp.bfloat16):
    """(T, vocabulary held) float32 logits of one document, unchunked (for
    tests and small sizes)."""
    x, _ = _forward(params, cfg, ids, dtype)
    y = _layer_norm(x, params["final_norm"]["scale"], cfg.layer_norm_eps)
    return cfg.logit_scale * jnp.matmul(
        y, params["embed"]["embedding"].astype(y.dtype).T,
        preferred_element_type=jnp.float32)
