"""Keye-VL-2.0-30B-A3B's language model as a token model the Stage-1 tuner
can train.

Source: https://huggingface.co/Kwai-Keye/Keye-VL-2.0-30B-A3B/blob/main/config.json
(``model_type: KeyeVL2``). Pure functions over a nested ``params`` dict, as
``models/deepseek.py`` (whose index scorer, expert dispatch, rotary and
chunked head-and-loss this family calls): the training forward of the text
path only.

  layer      x += W_o Attn(RMSNorm(x)); x += MoE(RMSNorm(x)), eps
             ``rms_norm_eps``; every layer an expert layer
             (``decoder_sparse_step`` 1, ``mlp_only_layers`` empty).
  attention  q = W_q u (``num_attention_heads`` x ``head_dim``), k, v = W_k u,
             W_v u (``num_key_value_heads`` x ``head_dim``): query head i
             reads key / value head i // group; no bias. QK-norm: a per-head
             RMSNorm over the head's dims of q and of k (one scale each,
             shared by the heads), then rotary, rotate-half, theta
             ``rope_theta``, the 64 frequency pairs split over (time,
             height, width) positions by ``mrope_section``; a text token has
             one position on all three, so the ordinary rotary. softmax(q .
             k / sqrt(head_dim)) over S_t only.
  scorer     ``sa_config``: qI = W_qI u (``indexer_num_heads`` x
             ``indexer_head_dim``), ONE key kI = LayerNorm(W_kI u), w = W_w u;
             rotary (halves) on the first half of qI and kI;
             I[t,s] = sum_j w[t,j] ReLU(qI[t,j] . kI[s]); S_t = the ``topk``
             largest I[t,s] over s <= t. No gradient flows through it
             (``deepseek.select_keys``, given this family's widths).
  experts    p = softmax(W_r y) in float32 over ``num_experts``; the
             ``num_experts_per_tok`` largest, gates p_sel / sum p_sel
             (``norm_topk_prob``: the softmax over the chosen logits,
             ``granite_hybrid.route``); no groups, no bias, NO shared
             expert; E_e a SwiGLU of width ``moe_intermediate_size``. No
             token dropped.
  head       logits = RMSNorm_f(x) W_head, untied.

**The chip's share.** Whole layers: every head, every expert
(``experts_held``), the scorer, the router and the norms; ``vocab_size`` is
the slice of the embedding and head rows held. The layers held are a
pipeline stage's; nothing stands in for the other stages or the other
vocabulary slices.

Not in the catalog row and so ``assumed``: QK-norm by the convention of the
Qwen3 expert models whose widths this row repeats; the scorer's query from
the normed hidden state (no query latent); its key LayerNorm with a scale
and a bias; rotary on half its width at this row's theta; ties with the
``topk``-th score kept. The vision tower is not in the row: image inputs
cannot be run.

Device ops carry the named scopes ``lm.attn_proj`` (q / k / v / o, QK-norm
and rotary), ``lm.indexer``, ``lm.select``, ``lm.sparse_attention``,
``lm.router``, ``lm.experts`` and ``lm.head_loss``.

**Which code attends.** On the TPU, where its fit test takes the shape, the
Pallas pair of ``ops/selected_attention.py`` with the selection over
grouped-query heads (``grouped_selected_attention``); elsewhere
``_chunked_selected_attend``, plain XLA in chunks (``deepseek.Q_CHUNK``)
and row blocks (``deepseek.ATTN_ROWS``), the tests' oracle. Chosen from the
backend and the shapes.

**Which code runs the expert loop.** ``deepseek.held_expert_ffn`` without a
shared expert: on the TPU at shapes on its tiling (this cell's 2048 x 768
is) the Pallas pair of ``ops/grouped_experts.py``, elsewhere the XLA loop.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Any, Dict, Tuple

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
    held_expert_ffn,
    seeded_params,
    select_keys,
)
from videop2p_tpu.models.granite_hybrid import route
from videop2p_tpu.ops.selected_attention import (
    grouped_selected_attention,
    keep_attention_outputs,
    selected_attention_tiles,
)

__all__ = [
    "KeyeVL2Config",
    "init_params",
    "attention",
    "layer_mask",
    "forward_loss",
    "forward_logits",
]

# The named scopes of this family's device ops and the scalars a step hands
# out beside the loss: what benchmark/layer_metrics/ reads by NAME
# (tests/test_spans.py holds the lowered loss and a tiny ``main`` to them).
SCOPES = ("lm.attn_proj", "lm.indexer", "lm.select", "lm.sparse_attention",
          "lm.router", "lm.experts", "lm.head_loss")
COUNTERS = ("keys_selected_mean", "expert_load_max_over_mean",
            "held_pair_share", "expert_rows_packed")


@dataclasses.dataclass(frozen=True)
class KeyeVL2Config:
    """The published ``config.json`` keys (defaults as published;
    ``rope_scaling`` and ``sa_config`` flattened) and the chip's share."""

    hidden_size: int = 2048
    intermediate_size: int = 6144         # a dense layer's width: none here
    moe_intermediate_size: int = 768
    head_dim: int = 128
    num_hidden_layers: int = 48
    num_attention_heads: int = 32
    num_key_value_heads: int = 4
    num_experts: int = 128
    num_local_experts: int = 128
    num_experts_per_tok: int = 8
    norm_topk_prob: bool = True
    decoder_sparse_step: int = 1
    mlp_only_layers: Tuple[int, ...] = ()
    rms_norm_eps: float = 1e-6
    rope_theta: float = 1e7
    mrope_section: Tuple[int, ...] = (16, 24, 24)
    rope_type: str = "default"
    indexer_num_heads: int = 16
    indexer_head_dim: int = 64
    indexer_num_kv_heads: int = 1
    index_topk: int = 2048
    q_chunk_size: int = 512               # the scorer's tiles, not a unit
    kv_chunk_size: int = 512              # of selection
    attention_bias: bool = False
    hidden_act: str = "silu"
    tie_word_embeddings: bool = False
    use_sliding_window: bool = False
    vocab_size: int = 151936
    # the chip's share: (first, count) of the routed experts
    experts_held: Tuple[int, int] = (0, 128)
    # recompute each layer in the backward pass; kept across it: the output
    # and log-sum-exp of the attention kernel pair where it ran, nothing else
    # (``ops.selected_attention.keep_attention_outputs``)
    remat: bool = True
    # the loss hands out, beside its scalars, what every layer CHOSE (the
    # selection eight keys a byte, the experts a token): what a check against
    # a reference takes as data. 134 MB a step at 16384 tokens and 4 layers
    hand_out_choices: bool = False

    @classmethod
    def from_dict(cls, d: Dict[str, Any]) -> "KeyeVL2Config":
        """From a ``config.json``-shaped dict (``rope_scaling`` and
        ``sa_config`` nested as published); unknown keys are an error."""
        d = dict(d)
        rope = d.pop("rope_scaling", None) or {}
        sa = d.pop("sa_config", None) or {}
        names = {f.name for f in dataclasses.fields(cls)}
        kw = {k: (tuple(v) if isinstance(v, list) else v) for k, v in d.items()}
        if rope:
            assert rope.get("type", rope["rope_type"]) == rope["rope_type"]
            kw.update(mrope_section=tuple(rope["mrope_section"]),
                      rope_type=rope["rope_type"])
        # the scorer's keys keep their names, but ``topk``
        kw.update({("index_topk" if k == "topk" else k): v
                   for k, v in sa.items()})
        unknown = sorted(set(kw) - names)
        if unknown:
            raise ValueError(f"unknown KeyeVL2Config keys {unknown}; "
                             f"known: {sorted(names)}")
        return cls(**kw)

    @classmethod
    def tiny(cls, **kw) -> "KeyeVL2Config":
        """The CPU tests' size: 64 wide, 2 layers, 8 query heads of 16 on 2
        key / value heads (rotary sections 2 : 3 : 3), 4 index heads of 8
        picking 16 keys, 8 experts of 32, top-3."""
        base = dict(
            hidden_size=64, intermediate_size=96, moe_intermediate_size=32,
            head_dim=16, num_hidden_layers=2, num_attention_heads=8,
            num_key_value_heads=2, num_experts=8, num_local_experts=8,
            num_experts_per_tok=3, mrope_section=(2, 3, 3),
            indexer_num_heads=4, indexer_head_dim=8, index_topk=16,
            vocab_size=256, experts_held=(0, 8))
        return cls(**{**base, **kw})

    def check(self) -> None:
        first, count = self.experts_held
        assert 0 <= first and count >= 1 and first + count <= self.num_experts
        assert self.num_attention_heads % self.num_key_value_heads == 0
        assert sum(self.mrope_section) * 2 == self.head_dim
        assert self.indexer_head_dim % 4 == 0
        # what this file does not build
        assert self.num_local_experts == self.num_experts
        assert self.decoder_sparse_step == 1 and not self.mlp_only_layers
        assert self.indexer_num_kv_heads == 1
        assert self.norm_topk_prob and self.hidden_act == "silu"
        assert not self.attention_bias and not self.tie_word_embeddings
        assert not self.use_sliding_window and self.rope_type == "default"


# ---------------------------------------------------------------- weights


def param_shapes(cfg: KeyeVL2Config) -> Dict[str, Any]:
    """``{"params": {...}}`` of ``(shape, fan_in)`` leaves, the layout of
    ``models/deepseek.py``: a matrix is ``kernel`` with its input features
    second to last, expert matrices stacked over the experts held."""
    cfg.check()
    h, en, hd = cfg.hidden_size, cfg.experts_held[1], cfg.head_dim
    hq, hkv = cfg.num_attention_heads, cfg.num_key_value_heads
    ih = cfg.indexer_head_dim

    def mat(i, o, *lead):
        return {"kernel": (tuple(lead) + (i, o), i)}

    width = cfg.moe_intermediate_size
    layer = {
        "input_norm": {"scale": ((h,), None)},
        "post_norm": {"scale": ((h,), None)},
        "attn": {
            "q_proj": mat(h, hq * hd), "k_proj": mat(h, hkv * hd),
            "v_proj": mat(h, hkv * hd), "o_proj": mat(hq * hd, h),
            "q_norm": {"scale": ((hd,), None)},
            "k_norm": {"scale": ((hd,), None)},
            "indexer": {
                "wq_b": mat(h, cfg.indexer_num_heads * ih),
                "wk": mat(h, ih),
                "k_norm": {"scale": ((ih,), None), "bias": ((ih,), None)},
                "weights_proj": mat(h, cfg.indexer_num_heads),
            },
        },
        "router": mat(h, cfg.num_experts),
        "experts": {"gate_proj": mat(h, width, en),
                    "up_proj": mat(h, width, en),
                    "down_proj": mat(width, h, en)},
    }
    return {"params": {
        "embed": {"embedding": ((cfg.vocab_size, h), None)},
        **{f"layers_{i}": layer for i in range(cfg.num_hidden_layers)},
        "final_norm": {"scale": ((h,), None)},
        "head": mat(h, cfg.vocab_size),
    }}


def abstract_params(cfg: KeyeVL2Config, dtype=jnp.bfloat16):
    return jax.tree.map(lambda s: jax.ShapeDtypeStruct(s[0], dtype),
                        param_shapes(cfg), is_leaf=_is_spec)


def init_params(key: jax.Array, cfg: KeyeVL2Config, dtype=jnp.bfloat16):
    """Seeded random weights in the checkpoint's dtype (every leaf
    bfloat16), ``deepseek.seeded_leaf`` leaf by leaf."""
    return seeded_params(key, param_shapes(cfg), dtype)


# ------------------------------------------------------------ small pieces


def rope_angles(cfg: KeyeVL2Config, positions) -> jax.Array:
    """(T, head_dim / 2) rotary angles. ``positions`` (3, T) holds the
    (time, height, width) rows of mRoPE, or (T,) the one position of a text
    token on all three: frequency pair i = theta^(-2 i / head_dim) turns
    with the row its ``mrope_section`` names."""
    dim = cfg.head_dim
    freqs = 1.0 / (cfg.rope_theta
                   ** (jnp.arange(0, dim, 2, dtype=jnp.float32) / dim))
    rows = jnp.broadcast_to(positions, (3,) + jnp.shape(positions)[-1:])
    which = np.repeat(np.arange(3), cfg.mrope_section)
    return rows.astype(jnp.float32)[which].T * freqs[None, :]


def indexer_angles(cfg: KeyeVL2Config, positions) -> jax.Array:
    """(T, indexer_head_dim / 4) rotary angles of the scorer's rotated half:
    position * theta^(-2 i / (indexer_head_dim / 2))."""
    dim = cfg.indexer_head_dim // 2
    freqs = 1.0 / (cfg.rope_theta
                   ** (jnp.arange(0, dim, 2, dtype=jnp.float32) / dim))
    return positions.astype(jnp.float32)[:, None] * freqs[None, :]


def _scorer(cfg: KeyeVL2Config) -> Dict[str, int]:
    return dict(heads=cfg.indexer_num_heads, head_dim=cfg.indexer_head_dim,
                rope_dim=cfg.indexer_head_dim // 2, topk=cfg.index_topk)


# ---------------------------------------------------------------- attention


def _attend_selected(q, k, v, mask, scale):
    """``q`` (R, H_kv, G, D) rows against the keys ``k``, ``v`` (S, H_kv, D)
    under ``mask`` (R, S)."""
    s = jnp.einsum("rkgd,tkd->kgrt", q, k, preferred_element_type=jnp.float32)
    prob = jax.nn.softmax(jnp.where(mask, s * scale, -jnp.inf), axis=-1)
    return jnp.einsum("kgrt,tkd->rkgd", prob.astype(v.dtype), v)


def _chunked_selected_attend(q, k, v, mask, scale):
    """Softmax over the selected keys with grouped-query heads as XLA: chunk
    ``c`` of ``deepseek.Q_CHUNK`` queries against the keys up to its own end,
    ``deepseek.ATTN_ROWS`` queries at a time, each row block recomputed in
    the backward pass. ``q`` (T, H, D); ``k``, ``v`` (T, H_kv, D); ``mask``
    (T, T) bool → (T, H, D)."""
    t_len, heads, width = q.shape
    kv_heads = k.shape[1]
    qc = min(deepseek.Q_CHUNK, t_len)
    ar = min(deepseek.ATTN_ROWS, qc)
    assert t_len % qc == 0 and qc % ar == 0, (t_len, qc, ar)
    attend = jax.checkpoint(functools.partial(_attend_selected, scale=scale))
    out = []
    for c in range(t_len // qc):
        sl, kb = slice(c * qc, (c + 1) * qc), (c + 1) * qc
        out.append(lax.map(
            lambda a, kb=kb: attend(a[0], k[:kb], v[:kb], a[1]),
            (q[sl].reshape(qc // ar, ar, kv_heads, heads // kv_heads, width),
             mask[sl, :kb].reshape(qc // ar, ar, kb))))
    return jnp.concatenate(out, axis=0).reshape(t_len, heads, width)


def _kernel_applies(q) -> bool:
    """Whether the attention runs as the Pallas pair: on the TPU, where its
    fit test takes the shape. Chosen from what the input is, never by an
    option."""
    if jax.default_backend() != "tpu":
        return False
    t_len, heads, width = q.shape
    return selected_attention_tiles(t_len, heads, width, 0, width,
                                    q.dtype) is not None


def attention(p, cfg: KeyeVL2Config, u, angles, mask):
    """The attention output (before the residual) for the normed input ``u``
    (T, h) GIVEN the selection ``mask`` (T, T)."""
    t_len = u.shape[0]
    hq, hkv, hd = cfg.num_attention_heads, cfg.num_key_value_heads, cfg.head_dim
    with jax.named_scope("lm.attn_proj"):
        q = _dense(u, p["q_proj"]["kernel"]).reshape(t_len, hq, hd)
        k = _dense(u, p["k_proj"]["kernel"]).reshape(t_len, hkv, hd)
        v = _dense(u, p["v_proj"]["kernel"]).reshape(t_len, hkv, hd)
        q = _rms_norm(q, p["q_norm"]["scale"], cfg.rms_norm_eps)
        k = _rms_norm(k, p["k_norm"]["scale"], cfg.rms_norm_eps)
        q = _rotate(q, angles, interleaved=False)
        k = _rotate(k, angles, interleaved=False)
    scale = hd ** -0.5
    with jax.named_scope("lm.sparse_attention"):
        if _kernel_applies(q):
            o = grouped_selected_attention(q, k, v, mask, scale)
        else:
            o = _chunked_selected_attend(q, k, v, mask, scale)
    with jax.named_scope("lm.attn_proj"):
        return _dense(o.reshape(t_len, hq * hd), p["o_proj"]["kernel"])


def layer_mask(p, cfg: KeyeVL2Config, x, angles):
    """The layer's selection from detached inputs — computed outside the
    rematerialised layer so that the backward pass does not score again."""
    with jax.named_scope("lm.indexer"):
        u = lax.stop_gradient(
            _rms_norm(x, p["input_norm"]["scale"], cfg.rms_norm_eps))
    indexer = jax.tree.map(lax.stop_gradient, p["attn"]["indexer"])
    return select_keys(indexer, u, u, angles, **_scorer(cfg))


# ------------------------------------------------------------------ forward


def _layer(cfg: KeyeVL2Config, p, x, angles, mask):
    u = _rms_norm(x, p["input_norm"]["scale"], cfg.rms_norm_eps)
    x = x + attention(p["attn"], cfg, u, angles, mask)
    y = _rms_norm(x, p["post_norm"]["scale"], cfg.rms_norm_eps)
    with jax.named_scope("lm.router"):
        experts, gates = route(p["router"], cfg, y)
    routed, _, counters = held_expert_ffn(p, y, experts, gates,
                                          cfg.experts_held)
    return x + routed, counters, experts


def _forward(params, cfg: KeyeVL2Config, ids, dtype):
    cfg.check()
    t_len = ids.shape[0]
    positions = jnp.arange(t_len)
    angles = rope_angles(cfg, positions)
    scorer_angles = indexer_angles(cfg, positions)
    x = params["embed"]["embedding"].astype(dtype)[ids]
    layer = functools.partial(_layer, cfg)
    if cfg.remat:
        layer = jax.checkpoint(layer, policy=keep_attention_outputs)
    counters, selected, choices = [], [], []
    for i in range(cfg.num_hidden_layers):
        p = params[f"layers_{i}"]
        mask = layer_mask(p, cfg, x, scorer_angles)
        x, c, experts = layer(p, x, angles, mask)
        counters.append(c)
        selected.append(jnp.sum(mask, dtype=jnp.float32) / t_len)
        if cfg.hand_out_choices:
            choices.append({"mask": jnp.packbits(mask, axis=-1),
                            "experts": experts})
    aux = {k: jnp.mean(jnp.stack([c[k] for c in counters]))
           for k in counters[0]}
    # mean keys selected a query, over all layers
    aux["keys_selected_mean"] = jnp.mean(jnp.stack(selected))
    if cfg.hand_out_choices:
        aux["choices"] = choices
    return x, aux


def forward_loss(params, cfg: KeyeVL2Config, ids, dtype=jnp.bfloat16):
    """``(loss, aux)`` for one document ``ids`` (T,): the mean next-token
    cross-entropy over the vocabulary slice, and the step's counters
    (scalars: the layers' means). Under ``cfg.hand_out_choices``
    ``aux["choices"]`` holds, per layer, what THIS pass chose: ``mask`` (T,
    T / 8) uint8, the selection eight keys a byte, and ``experts`` (T, K)."""
    x, aux = _forward(params, cfg, ids, dtype)
    return head_loss(params["final_norm"]["scale"], params["head"]["kernel"],
                     x, ids, cfg.rms_norm_eps), aux


def forward_logits(params, cfg: KeyeVL2Config, ids, dtype=jnp.bfloat16):
    """(T, vocabulary held) float32 logits of one document, unchunked (for
    tests and small sizes)."""
    x, _ = _forward(params, cfg, ids, dtype)
    y = _rms_norm(x, params["final_norm"]["scale"], cfg.rms_norm_eps)
    return jnp.matmul(y, params["head"]["kernel"].astype(y.dtype),
                      preferred_element_type=jnp.float32)
