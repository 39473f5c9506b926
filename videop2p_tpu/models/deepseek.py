"""DeepSeek-V3.2's decoder block as a token model the Stage-1 tuner can train.

Source: https://huggingface.co/deepseek-ai/DeepSeek-V3.2/blob/main/config.json
(``model_type: deepseek_v32``). Pure functions over a nested ``params`` dict;
no flax module, no cache, no token loop — this is the training forward only.

  layer      x += Attn(RMSNorm(x)); x += FFN(RMSNorm(x)), eps 1e-6. The first
             ``first_k_dense_replace`` layers' FFN is a dense SwiGLU, the rest
             are expert layers.
  attention  latent attention (MLA): c_q = RMSNorm(W_qa x); per head
             [q_nope; q_rope] = W_qb c_q; [c_kv; k_rope] = W_kva x, c_kv
             normed; per head [k_nope; v] = W_kvb c_kv; YaRN rotary on q_rope
             and on the one k_rope all heads share (adjacent pairs of dims);
             softmax over the keys the index scorer selected.
  scorer     I[t,s] = sum_j w[t,j] ReLU(qI[t,j] . kI[s]) over ``index_n_heads``
             heads (qI from c_q, one LayerNormed key for all heads, rotary on
             the first rope dims of both, split in halves); S_t = the
             ``index_topk`` largest I[t,s] over s <= t. No gradient flows
             through it or into its inputs (the published recipe detaches).
  experts    s = sigmoid(W_g x); selection on s + b: groups scored by their
             two largest, ``topk_group`` groups kept, then the
             ``num_experts_per_tok`` largest; gates = scale * s_i / sum s.
             A shared expert runs on every token. No token is dropped.

**The chip's share.** ``experts_held`` and ``heads_held`` are ``(first,
count)`` ranges: the layer routes over all ``n_routed_experts`` and computes
the part of the result that its own experts give; the head-sliced
projections (``q_b_proj``, ``kv_b_proj``, ``o_proj``) hold ``count`` heads.
What the absent experts and heads would have added is left out, and that
partial result goes on to the next layer. Nothing here stands in for the
absent chips; with the full ranges this is the whole layer.

Departures from the published inference code: the scorer's Hadamard rotation
(it leaves the products unchanged) and its FP8 quantisation are left out;
the selection keeps every key that ties with the k-th largest score.

Device ops carry the named scopes ``lm.mla_proj``, ``lm.indexer``,
``lm.select``, ``lm.sparse_attention``, ``lm.router``, ``lm.experts``,
``lm.shared_expert``, ``lm.dense_ffn`` and ``lm.head_loss``.

**Which code attends.** On the TPU, where the shapes pass its fit test (the
token count a multiple of a tile, head widths on lane tiles), the softmax
over the selected keys is the Pallas pair of ``ops/selected_attention.py``
(``lm_selected_attention`` / ``lm_selected_attention_bwd``): the float32
score tile stays in VMEM, forward and backward. Everywhere else — the CPU,
the ``tiny`` size, odd lengths — it is ``_chunked_attend``, masked dense XLA
in chunks, which is also the tests' oracle. ``_kernel_applies`` chooses from
the backend and the shapes; no option does.
"""

from __future__ import annotations

import dataclasses
import functools
import math
from typing import Any, Dict, Tuple

import jax
import jax.numpy as jnp
from jax import lax

from videop2p_tpu.ops.selected_attention import (
    selected_attention_tiles,
    selected_key_attention,
)

__all__ = [
    "DeepSeekV32Config",
    "init_params",
    "attention",
    "select_keys",
    "route",
    "select_experts",
    "held_expert_ffn",
    "expert_ffn",
    "head_loss",
    "forward_loss",
    "forward_logits",
]

# How the work is cut (no effect on the mathematics; each is clipped to the
# input's length). Not configuration: one value is in use, tests patch them.
Q_CHUNK = 2048      # queries per causal chunk: chunk c sees the keys up to
                    # its own end only (the scorer, and attention as XLA)
ATTN_ROWS = 512     # queries attended at once inside a chunk where the
                    # attention runs as XLA (_chunked_attend); the Pallas
                    # pair has its own tiles (ops/selected_attention.py)
INDEX_ROWS = 256    # queries scored at once inside a chunk
FFN_ROWS = 4096     # tokens per dense feed-forward block
# Rows of one expert's tokens per matmul. With the published layout at 16384
# tokens an expert's mean load is 512: at 384 it takes two blocks anywhere
# between 385 and 768 tokens, where at 256 the mean sits on a block's edge and
# the step's time jumps with a few tokens more or less (1 % of the step in
# padding at level loads; PERF.md section 6, PR 28).
EXPERT_BLOCK = 384
LOSS_CHUNK = 2048   # tokens per head-and-loss chunk


@dataclasses.dataclass(frozen=True)
class DeepSeekV32Config:
    """The published ``config.json`` keys (defaults as published) and the
    chip's share."""

    hidden_size: int = 7168
    intermediate_size: int = 18432
    moe_intermediate_size: int = 2048
    num_hidden_layers: int = 61
    first_k_dense_replace: int = 3
    num_attention_heads: int = 128
    q_lora_rank: int = 1536
    kv_lora_rank: int = 512
    qk_nope_head_dim: int = 128
    qk_rope_head_dim: int = 64
    v_head_dim: int = 128
    index_n_heads: int = 64
    index_head_dim: int = 128
    index_topk: int = 2048
    n_routed_experts: int = 256
    n_shared_experts: int = 1
    n_group: int = 8
    topk_group: int = 4
    num_experts_per_tok: int = 8
    routed_scaling_factor: float = 2.5
    vocab_size: int = 129280
    rms_norm_eps: float = 1e-6
    rope_theta: float = 10000.0
    rope_factor: float = 40.0
    rope_original_max: int = 4096
    rope_beta_fast: float = 32.0
    rope_beta_slow: float = 1.0
    rope_mscale_all_dim: float = 1.0
    # the chip's share: (first, count) of the routed experts and of the heads
    experts_held: Tuple[int, int] = (0, 256)
    heads_held: Tuple[int, int] = (0, 128)
    remat: bool = True         # recompute each layer in the backward pass
    # the loss hands out, beside its scalars, what every layer CHOSE (the
    # selection eight keys a byte, the experts a token): what a check against
    # a reference takes as data. 168 MB a step at 16384 tokens and 5 layers,
    # so not for a long tune.
    hand_out_choices: bool = False

    @classmethod
    def from_dict(cls, d: Dict[str, Any]) -> "DeepSeekV32Config":
        """From a ``config.json``-shaped dict (``rope_scaling`` nested as
        published); unknown keys are an error, not ignored."""
        d = dict(d)
        rope = d.pop("rope_scaling", None) or {}
        names = {f.name for f in dataclasses.fields(cls)}
        kw = {k: (tuple(v) if isinstance(v, list) else v)
              for k, v in d.items()}
        if rope:
            kw.update(rope_factor=float(rope["factor"]),
                      rope_original_max=int(
                          rope["original_max_position_embeddings"]),
                      rope_beta_fast=float(rope["beta_fast"]),
                      rope_beta_slow=float(rope["beta_slow"]),
                      rope_mscale_all_dim=float(rope["mscale_all_dim"]))
        unknown = sorted(set(kw) - names)
        if unknown:
            raise ValueError(f"unknown DeepSeekV32Config keys {unknown}; "
                             f"known: {sorted(names)}")
        return cls(**kw)

    @classmethod
    def tiny(cls, **kw) -> "DeepSeekV32Config":
        """The CPU tests' size: 4 heads, 8 experts in 2 groups, top-2,
        2 + 2 layers, 64 wide, top-k 16."""
        base = dict(
            hidden_size=64, intermediate_size=128, moe_intermediate_size=32,
            num_hidden_layers=4, first_k_dense_replace=2,
            num_attention_heads=4, q_lora_rank=32, kv_lora_rank=16,
            qk_nope_head_dim=16, qk_rope_head_dim=8, v_head_dim=16,
            index_n_heads=4, index_head_dim=16, index_topk=16,
            n_routed_experts=8, n_group=2, topk_group=1,
            num_experts_per_tok=2, vocab_size=256, experts_held=(0, 8),
            heads_held=(0, 4))
        return cls(**{**base, **kw})

    @property
    def softmax_scale(self) -> float:
        mscale = 0.1 * self.rope_mscale_all_dim * math.log(self.rope_factor) + 1.0
        return (self.qk_nope_head_dim + self.qk_rope_head_dim) ** -0.5 * mscale ** 2

    def check(self) -> None:
        e0, en = self.experts_held
        h0, hn = self.heads_held
        assert 0 <= e0 and en >= 1 and e0 + en <= self.n_routed_experts
        assert 0 <= h0 and hn >= 1 and h0 + hn <= self.num_attention_heads
        assert self.n_routed_experts % self.n_group == 0
        assert 0 <= self.first_k_dense_replace <= self.num_hidden_layers


# ---------------------------------------------------------------- weights


def param_shapes(cfg: DeepSeekV32Config) -> Dict[str, Any]:
    """``{"params": {...}}`` of ``(shape, fan_in)`` leaves. Every matrix is
    ``kernel`` with its input features second to last; expert matrices are
    stacked over the experts held."""
    cfg.check()
    h, hn, en = cfg.hidden_size, cfg.heads_held[1], cfg.experts_held[1]
    qk = cfg.qk_nope_head_dim + cfg.qk_rope_head_dim
    kvb = cfg.qk_nope_head_dim + cfg.v_head_dim

    def mat(i, o, *lead):
        return {"kernel": (tuple(lead) + (i, o), i)}

    def mlp(width, *lead):
        return {"gate_proj": mat(h, width, *lead),
                "up_proj": mat(h, width, *lead),
                "down_proj": mat(width, h, *lead)}

    attn = {
        "q_a_proj": mat(h, cfg.q_lora_rank),
        "q_a_norm": {"scale": ((cfg.q_lora_rank,), None)},
        "q_b_proj": mat(cfg.q_lora_rank, hn * qk),
        "kv_a_proj": mat(h, cfg.kv_lora_rank + cfg.qk_rope_head_dim),
        "kv_a_norm": {"scale": ((cfg.kv_lora_rank,), None)},
        "kv_b_proj": mat(cfg.kv_lora_rank, hn * kvb),
        "o_proj": mat(hn * cfg.v_head_dim, h),
        "indexer": {
            "wq_b": mat(cfg.q_lora_rank,
                        cfg.index_n_heads * cfg.index_head_dim),
            "wk": mat(h, cfg.index_head_dim),
            "k_norm": {"scale": ((cfg.index_head_dim,), None),
                       "bias": ((cfg.index_head_dim,), None)},
            "weights_proj": mat(h, cfg.index_n_heads),
        },
    }
    layers = {}
    for i in range(cfg.num_hidden_layers):
        layer = {"input_norm": {"scale": ((h,), None)},
                 "post_norm": {"scale": ((h,), None)}, "attn": attn}
        if i < cfg.first_k_dense_replace:
            layer["mlp"] = mlp(cfg.intermediate_size)
        else:
            layer["router"] = {"kernel": ((h, cfg.n_routed_experts), h),
                               "bias": ((cfg.n_routed_experts,), None)}
            layer["experts"] = mlp(cfg.moe_intermediate_size, en)
            layer["shared"] = mlp(cfg.moe_intermediate_size
                                  * cfg.n_shared_experts)
        layers[f"layers_{i}"] = layer
    return {"params": {
        "embed": {"embedding": ((cfg.vocab_size, h), None)},
        **layers,
        "final_norm": {"scale": ((h,), None)},
        "head": mat(h, cfg.vocab_size),
    }}


def _is_spec(x) -> bool:
    return isinstance(x, tuple) and len(x) == 2 and isinstance(x[0], tuple)


def abstract_params(cfg: DeepSeekV32Config, dtype=jnp.bfloat16):
    return jax.tree.map(lambda s: jax.ShapeDtypeStruct(s[0], dtype),
                        param_shapes(cfg), is_leaf=_is_spec)


def seeded_leaf(name: str, z, fan_in):
    """A leaf's seeded value from standard normal draws ``z`` of its shape,
    by its last name: kernels N(0, 1/fan_in), norm scales 1 + N(0, 0.05^2),
    embedding and the rest N(0, 0.02^2)."""
    if name == "kernel":
        return z * (1.0 / math.sqrt(fan_in))
    if name == "scale":
        return 1.0 + 0.05 * z
    return 0.02 * z


def seeded_params(key: jax.Array, specs, dtype, leaf=seeded_leaf):
    """``specs`` (a tree of ``(shape, fan_in)``) filled leaf by leaf with
    ``leaf(last name, normal draws, fan_in)`` in ``dtype``, leaf ``i`` from
    ``fold_in(key, i)``. Meant to run under one ``jax.jit``."""
    flat, treedef = jax.tree_util.tree_flatten_with_path(specs, is_leaf=_is_spec)
    leaves = []
    for i, (path, (shape, fan_in)) in enumerate(flat):
        z = jax.random.normal(jax.random.fold_in(key, i), shape, jnp.float32)
        last = str(getattr(path[-1], "key", path[-1]))
        leaves.append(leaf(last, z, fan_in).astype(dtype))
    return jax.tree_util.tree_unflatten(treedef, leaves)


def init_params(key: jax.Array, cfg: DeepSeekV32Config, dtype=jnp.bfloat16):
    """Seeded random weights in the checkpoint's dtype (every leaf bfloat16
    as published), :func:`seeded_leaf` leaf by leaf."""
    return seeded_params(key, param_shapes(cfg), dtype)


# ------------------------------------------------------------ small pieces


def _rms_norm(x, scale, eps):
    x32 = x.astype(jnp.float32)
    y = x32 * lax.rsqrt(jnp.mean(x32 * x32, axis=-1, keepdims=True) + eps)
    return (y * scale.astype(jnp.float32)).astype(x.dtype)


def _layer_norm(x, scale, bias, eps=1e-6):
    x32 = x.astype(jnp.float32)
    mean = jnp.mean(x32, axis=-1, keepdims=True)
    var = jnp.mean((x32 - mean) ** 2, axis=-1, keepdims=True)
    y = (x32 - mean) * lax.rsqrt(var + eps)
    return (y * scale.astype(jnp.float32) + bias.astype(jnp.float32)).astype(x.dtype)


def _dense(x, kernel):
    return jnp.matmul(x, kernel.astype(x.dtype))


@jax.checkpoint
def _swiglu_block(x, wg, wu, wd):
    return _dense(jax.nn.silu(_dense(x, wg)) * _dense(x, wu), wd)


def _swiglu(p, x):
    """A dense SwiGLU over blocks of ``FFN_ROWS`` tokens, each recomputed in
    the backward pass (its (rows, width) intermediates are the layer's
    largest)."""
    w = [p[n]["kernel"] for n in ("gate_proj", "up_proj", "down_proj")]
    return jnp.concatenate([_swiglu_block(x[i:i + FFN_ROWS], *w)
                            for i in range(0, x.shape[0], FFN_ROWS)], axis=0)


def rope_angles(cfg: DeepSeekV32Config, positions) -> jax.Array:
    """(T, rope_dim / 2) YaRN angles: high-frequency dims keep their
    frequency, low-frequency dims are slowed by ``rope_factor``, with a
    linear ramp between the dims that turn ``beta_fast`` and ``beta_slow``
    times over the original context."""
    dim, base = cfg.qk_rope_head_dim, cfg.rope_theta
    freqs = 1.0 / (base ** (jnp.arange(0, dim, 2, dtype=jnp.float32) / dim))

    def correction_dim(rotations):
        return (dim * math.log(cfg.rope_original_max / (rotations * 2 * math.pi))
                / (2 * math.log(base)))

    low = max(math.floor(correction_dim(cfg.rope_beta_fast)), 0)
    high = min(math.ceil(correction_dim(cfg.rope_beta_slow)), dim - 1)
    if low == high:
        high += 0.001
    ramp = jnp.clip((jnp.arange(dim // 2, dtype=jnp.float32) - low)
                    / (high - low), 0.0, 1.0)
    smooth = 1.0 - ramp
    freqs = freqs / cfg.rope_factor * (1.0 - smooth) + freqs * smooth
    return positions.astype(jnp.float32)[:, None] * freqs[None, :]


def _rotate(x, angles, *, interleaved: bool):
    """Rotary on the last axis of ``x`` (T, ..., D): pairs are adjacent dims
    (``interleaved``, the attention) or the two halves (the scorer)."""
    x32 = x.astype(jnp.float32)
    shape = (angles.shape[0],) + (1,) * (x.ndim - 2) + (angles.shape[1],)
    cos, sin = jnp.cos(angles).reshape(shape), jnp.sin(angles).reshape(shape)
    if interleaved:
        pairs = x32.reshape(x.shape[:-1] + (-1, 2))
        a, b = pairs[..., 0], pairs[..., 1]
        out = jnp.stack([a * cos - b * sin, a * sin + b * cos], axis=-1)
        out = out.reshape(x.shape)
    else:
        a, b = jnp.split(x32, 2, axis=-1)
        out = jnp.concatenate([a * cos - b * sin, a * sin + b * cos], axis=-1)
    return out.astype(x.dtype)


# ------------------------------------------------- the scorer and selection


def _kth_largest(scores, k: int):
    """Per row of float32 ``scores`` (-inf where masked) the k-th largest
    value, by a binary search over the bits of an order-preserving integer
    key: 32 compare-and-count passes, no sort."""
    bits = lax.bitcast_convert_type(scores, jnp.uint32)
    sign = bits >> 31
    keys = jnp.where(sign == 1, ~bits, bits | jnp.uint32(1 << 31))

    def step(i, thr):
        cand = thr | (jnp.uint32(1) << (jnp.uint32(31) - i.astype(jnp.uint32)))
        n = jnp.sum(keys >= cand[:, None], axis=-1, dtype=jnp.int32)
        return jnp.where(n >= k, cand, thr)

    thr = lax.fori_loop(0, 32, step, jnp.zeros(scores.shape[:1], jnp.uint32))
    back = jnp.where(thr >> 31 == 1, thr & jnp.uint32((1 << 31) - 1), ~thr)
    return lax.bitcast_convert_type(back, jnp.float32)


def select_keys(p, cfg: DeepSeekV32Config, x, c_q, angles):
    """(T, T) bool: ``mask[t, s]`` is True where key ``s`` is in S_t. Query
    chunk ``c`` scores the keys up to its own end only."""
    t_len = x.shape[0]
    qc = min(Q_CHUNK, t_len)
    assert t_len % qc == 0, (t_len, qc)
    nh, hd, rd = cfg.index_n_heads, cfg.index_head_dim, cfg.qk_rope_head_dim
    with jax.named_scope("lm.indexer"):
        q = _dense(c_q, p["wq_b"]["kernel"]).reshape(t_len, nh, hd)
        q = jnp.concatenate(
            [_rotate(q[..., :rd], angles, interleaved=False), q[..., rd:]], -1)
        k = _layer_norm(_dense(x, p["wk"]["kernel"]), p["k_norm"]["scale"],
                        p["k_norm"]["bias"])
        k = jnp.concatenate(
            [_rotate(k[:, :rd], angles, interleaved=False), k[:, rd:]], -1)
        w = (_dense(x, p["weights_proj"]["kernel"]).astype(jnp.float32)
             * (nh ** -0.5 * hd ** -0.5))
    ir = min(INDEX_ROWS, qc)
    assert qc % ir == 0, (qc, ir)

    def select(q_rows, w_rows, first, keys):
        """``ir`` queries from position ``first`` against ``keys``."""
        kb = keys.shape[0]
        causal = jnp.arange(kb)[None, :] <= (first + jnp.arange(ir))[:, None]
        if kb <= cfg.index_topk:
            return causal
        with jax.named_scope("lm.indexer"):
            # float32 logits: rounded to bfloat16 (0.4 % each) they flip a
            # tenth of the selected keys against the float32 scores
            logits = jnp.einsum("qjd,sd->qjs", q_rows, keys,
                                preferred_element_type=jnp.float32)
            score = jnp.sum(jax.nn.relu(logits) * w_rows[:, :, None], axis=1)
        with jax.named_scope("lm.select"):
            score = jnp.where(causal, score, -jnp.inf)
            thr = _kth_largest(score, cfg.index_topk)
            return causal & (score >= thr[:, None])

    rows = []
    for c in range(t_len // qc):
        sl, kb = slice(c * qc, (c + 1) * qc), (c + 1) * qc
        picked = lax.map(
            lambda a, keys=k[:kb]: select(*a, keys),
            (q[sl].reshape(qc // ir, ir, nh, hd),
             w[sl].reshape(qc // ir, ir, nh),
             c * qc + ir * jnp.arange(qc // ir)))
        rows.append(jnp.pad(picked.reshape(qc, kb), ((0, 0), (0, t_len - kb))))
    return jnp.concatenate(rows, axis=0)


# ---------------------------------------------------------------- attention


def _attend(q_nope, q_rope, k_nope, k_rope, v, mask, scale):
    s = (jnp.einsum("qhd,khd->hqk", q_nope, k_nope,
                    preferred_element_type=jnp.float32)
         + jnp.einsum("qhd,kd->hqk", q_rope, k_rope,
                      preferred_element_type=jnp.float32))
    s = jnp.where(mask[None], s * scale, -jnp.inf)
    prob = jax.nn.softmax(s, axis=-1).astype(v.dtype)
    return jnp.einsum("hqk,khd->qhd", prob, v)


def _chunked_attend(q_nope, q_rope, k_nope, k_rope, v, mask, scale):
    """:func:`_attend` as XLA: chunk ``c`` of ``Q_CHUNK`` queries against the
    keys up to its own end, ``ATTN_ROWS`` queries at a time, each row block
    recomputed in the backward pass (its float32 ``(heads, rows, keys)``
    scores are what the layer could not keep)."""
    t_len = q_nope.shape[0]
    qc = min(Q_CHUNK, t_len)
    ar = min(ATTN_ROWS, qc)
    assert t_len % qc == 0 and qc % ar == 0, (t_len, qc, ar)
    attend = jax.checkpoint(functools.partial(_attend, scale=scale))
    out = []
    for c in range(t_len // qc):
        sl, kb = slice(c * qc, (c + 1) * qc), (c + 1) * qc
        keys = (k_nope[:kb], k_rope[:kb], v[:kb])
        out.append(lax.map(
            lambda a, keys=keys: attend(a[0], a[1], *keys, a[2]),
            (q_nope[sl].reshape((qc // ar, ar) + q_nope.shape[1:]),
             q_rope[sl].reshape((qc // ar, ar) + q_rope.shape[1:]),
             mask[sl, :kb].reshape(qc // ar, ar, kb))))
    return jnp.concatenate(out, axis=0).reshape((t_len,) + v.shape[1:])


def _kernel_applies(q_nope, q_rope, v) -> bool:
    """Whether the selected-key attention runs as the Pallas pair
    (``ops/selected_attention.py``): on the TPU, where its fit test takes the
    shape — the token count a multiple of a tile, head widths on lane tiles,
    the backward within VMEM. Elsewhere (the CPU, the ``tiny`` size, odd
    lengths) it is :func:`_chunked_attend`. Chosen from what the input is,
    never by an option."""
    if jax.default_backend() != "tpu":
        return False
    t_len, heads, nope = q_nope.shape
    return selected_attention_tiles(t_len, heads, nope, q_rope.shape[-1],
                                    v.shape[-1], q_nope.dtype) is not None


def attention(p, cfg: DeepSeekV32Config, x, angles, mask=None):
    """This chip's heads' part of the attention output (before the
    residual), and the selection it used. ``x`` is the normed input."""
    t_len = x.shape[0]
    hn = cfg.heads_held[1]
    nd, rd, vd = cfg.qk_nope_head_dim, cfg.qk_rope_head_dim, cfg.v_head_dim
    with jax.named_scope("lm.mla_proj"):
        c_q = _rms_norm(_dense(x, p["q_a_proj"]["kernel"]),
                        p["q_a_norm"]["scale"], cfg.rms_norm_eps)
        q = _dense(c_q, p["q_b_proj"]["kernel"]).reshape(t_len, hn, nd + rd)
        q_nope = q[..., :nd]
        q_rope = _rotate(q[..., nd:], angles, interleaved=True)
        kv = _dense(x, p["kv_a_proj"]["kernel"])
        c_kv = _rms_norm(kv[:, :cfg.kv_lora_rank], p["kv_a_norm"]["scale"],
                         cfg.rms_norm_eps)
        k_rope = _rotate(kv[:, cfg.kv_lora_rank:], angles, interleaved=True)
        kvb = _dense(c_kv, p["kv_b_proj"]["kernel"]).reshape(t_len, hn, nd + vd)
        k_nope, v = kvb[..., :nd], kvb[..., nd:]
    if mask is None:
        # no gradient through the scorer or into its inputs
        mask = select_keys(p["indexer"], cfg, lax.stop_gradient(x),
                           lax.stop_gradient(c_q), angles)
    with jax.named_scope("lm.sparse_attention"):
        if _kernel_applies(q_nope, q_rope, v):
            o = selected_key_attention(q_nope, q_rope, k_nope, k_rope, v, mask,
                                       cfg.softmax_scale)
        else:
            o = _chunked_attend(q_nope, q_rope, k_nope, k_rope, v, mask,
                                cfg.softmax_scale)
    with jax.named_scope("lm.mla_proj"):
        return _dense(o.reshape(t_len, hn * vd), p["o_proj"]["kernel"]), mask


# ------------------------------------------------------------- expert layer


def route(p, cfg: DeepSeekV32Config, x):
    """(experts (T, K) int32, gates (T, K) float32) over ALL routed experts:
    sigmoid scores, then :func:`select_experts`."""
    logits = jnp.matmul(x, p["kernel"].astype(x.dtype),
                        preferred_element_type=jnp.float32)
    return select_experts(jax.nn.sigmoid(logits), p["bias"], cfg)


def select_experts(s, bias, cfg: DeepSeekV32Config):
    """From the scores ``s`` (T, n_routed_experts): selection on score +
    bias, group-limited; gates from the scores alone, normalised and
    scaled."""
    n, g, k = cfg.n_routed_experts, cfg.n_group, cfg.num_experts_per_tok
    sel = s + bias.astype(jnp.float32)
    grouped = sel.reshape(-1, g, n // g)
    group_score = jnp.sum(lax.top_k(grouped, 2)[0], axis=-1)
    keep = lax.top_k(group_score, cfg.topk_group)[1]
    in_kept = jnp.any(keep[:, :, None] == jnp.arange(g)[None, None, :], axis=1)
    sel = jnp.where(jnp.repeat(in_kept, n // g, axis=-1), sel, -jnp.inf)
    experts = lax.top_k(sel, k)[1]
    picked = jnp.take_along_axis(s, experts, axis=-1)
    gates = (cfg.routed_scaling_factor * picked
             / jnp.sum(picked, axis=-1, keepdims=True))
    return experts.astype(jnp.int32), gates


def _block_rows(b, tok, gate, tbl, block):
    """Token ids, gates and validity of block ``b``'s rows."""
    rows = tbl["start"][b] + jnp.arange(block, dtype=jnp.int32)
    valid = rows < tbl["end"][b]
    rows = jnp.minimum(rows, tok.shape[0] - 1)
    return (rows, valid, jnp.where(valid, tok[rows], 0),
            jnp.where(valid, gate[rows], 0.0))


def _expert_block(xb, e, wg, wu, wd):
    a = jnp.matmul(xb, wg[e].astype(xb.dtype), preferred_element_type=jnp.float32)
    u = jnp.matmul(xb, wu[e].astype(xb.dtype), preferred_element_type=jnp.float32)
    mid = (jax.nn.silu(a) * u).astype(xb.dtype)
    out = jnp.matmul(mid, wd[e].astype(xb.dtype), preferred_element_type=jnp.float32)
    return a, u, out


@functools.partial(jax.custom_vjp, nondiff_argnums=(7,))
def _grouped_swiglu(x, tok, gate, tbl, wg, wu, wd, block):
    """y[t] = sum over the rows r of token t of gate[r] * E_{e(r)}(x[t]),
    rows sorted by expert and cut into blocks of one expert each (``tbl``);
    a loop of ``tbl["n"]`` blocks, so no row is dropped and no block of
    padding is computed. Differentiable in ``x`` and ``gate``; the expert
    matrices are frozen under this op (zero cotangent)."""

    def body(b, y):
        _, _, t, g = _block_rows(b, tok, gate, tbl, block)
        _, _, out = _expert_block(x[t], tbl["expert"][b], wg, wu, wd)
        return y.at[t].add(g[:, None] * out)

    y = lax.fori_loop(0, tbl["n"], body, jnp.zeros(x.shape, jnp.float32))
    return y.astype(x.dtype)


def _grouped_fwd(x, tok, gate, tbl, wg, wu, wd, block):
    return (_grouped_swiglu(x, tok, gate, tbl, wg, wu, wd, block),
            (x, tok, gate, tbl, wg, wu, wd))


def _grouped_bwd(block, res, dy):
    x, tok, gate, tbl, wg, wu, wd = res

    def body(b, carry):
        dx, dgate = carry
        rows, valid, t, g = _block_rows(b, tok, gate, tbl, block)
        e = tbl["expert"][b]
        xb = x[t]
        a, u, out = _expert_block(xb, e, wg, wu, wd)
        dyb = dy[t].astype(jnp.float32)
        dg = jnp.where(valid, jnp.sum(dyb * out, axis=-1), 0.0)
        dout = (g[:, None] * dyb).astype(xb.dtype)
        dmid = jnp.matmul(dout, wd[e].astype(xb.dtype).T,
                          preferred_element_type=jnp.float32)
        sig = jax.nn.sigmoid(a)
        da = (dmid * u * sig * (1.0 + a * (1.0 - sig))).astype(xb.dtype)
        du = (dmid * a * sig).astype(xb.dtype)
        dxb = (jnp.matmul(da, wg[e].astype(xb.dtype).T,
                          preferred_element_type=jnp.float32)
               + jnp.matmul(du, wu[e].astype(xb.dtype).T,
                            preferred_element_type=jnp.float32))
        return dx.at[t].add(dxb), dgate.at[rows].add(dg)

    dx, dgate = lax.fori_loop(
        0, tbl["n"], body,
        (jnp.zeros(x.shape, jnp.float32), jnp.zeros(gate.shape, jnp.float32)))
    return dx.astype(x.dtype), None, dgate, None, None, None, None


_grouped_swiglu.defvjp(_grouped_fwd, _grouped_bwd)


def held_expert_ffn(p, x, experts, gates, held: Tuple[int, int]):
    """``(routed part, shared expert, counters)`` of one expert layer on the
    normed input ``x`` (T, h), GIVEN the routing: ``experts`` (T, K) int32
    over ALL routed experts and their ``gates`` (T, K) float32. ``held`` is
    the ``(first, count)`` range of experts whose stacked matrices
    ``p["experts"]`` holds: the rows routed to them are sorted by expert and
    cut into ``EXPERT_BLOCK``-row blocks of one expert each. ``p["shared"]``
    runs on every token. Every token family's expert layer calls this with
    its own router's ``(experts, gates)``."""
    t_len, k = experts.shape
    e0, en = held
    block = EXPERT_BLOCK
    with jax.named_scope("lm.router"):
        local = experts.reshape(-1) - e0
        key = jnp.where((local >= 0) & (local < en), local, en)
        order = jnp.argsort(key, stable=True)
        tok = (order // k).astype(jnp.int32)
        gate = gates.reshape(-1)[order]
        count = jnp.sum(key[:, None] == jnp.arange(en)[None, :], axis=0,
                        dtype=jnp.int32)
        end = jnp.cumsum(count)
        n_blk = -(-count // block)
        blk_end = jnp.cumsum(n_blk)
        b = jnp.arange(t_len * k // block + en, dtype=jnp.int32)
        e_b = jnp.minimum(
            jnp.searchsorted(blk_end, b, side="right", method="compare_all"),
            en - 1).astype(jnp.int32)
        tbl = {"n": blk_end[-1], "expert": e_b, "end": end[e_b],
               "start": (end - count)[e_b] + (b - (blk_end - n_blk)[e_b]) * block}
    with jax.named_scope("lm.experts"):
        ex = p["experts"]
        routed = _grouped_swiglu(x, tok, gate, tbl, ex["gate_proj"]["kernel"],
                                 ex["up_proj"]["kernel"],
                                 ex["down_proj"]["kernel"], block)
    with jax.named_scope("lm.shared_expert"):
        shared = _swiglu(p["shared"], x)
    held_pairs = jnp.sum(count).astype(jnp.float32)
    square = lambda a: jnp.sum(jnp.square(a.astype(jnp.float32)))  # noqa: E731
    counters = {
        # tokens to the busiest held expert over the mean over held experts
        "expert_load_max_over_mean":
            jnp.max(count) * en / jnp.maximum(held_pairs, 1.0),
        # share of routed (token, expert) pairs that land on held experts
        "held_pair_share": held_pairs / (t_len * k),
        # the held experts' part over the shared expert's, root mean square:
        # it carries the gates' scale whatever tokens were chosen
        "routed_over_shared": jnp.sqrt(square(routed) / square(shared)),
    }
    return routed, shared, counters


def expert_ffn(p, cfg: DeepSeekV32Config, x):
    """``(routed part, shared expert, counters, experts chosen)`` of one
    expert layer on the normed input ``x`` (T, h): routing over all experts,
    the held experts' part of the sum, and the shared expert."""
    with jax.named_scope("lm.router"):
        experts, gates = route(p["router"], cfg, x)
    routed, shared, counters = held_expert_ffn(p, x, experts, gates,
                                               cfg.experts_held)
    return routed, shared, counters, experts


# ------------------------------------------------------------------ forward


def _layer(cfg: DeepSeekV32Config, p, x, angles, mask):
    a, _ = attention(p["attn"], cfg,
                     _rms_norm(x, p["input_norm"]["scale"], cfg.rms_norm_eps),
                     angles, mask)
    x = x + a
    y = _rms_norm(x, p["post_norm"]["scale"], cfg.rms_norm_eps)
    if "mlp" in p:
        with jax.named_scope("lm.dense_ffn"):
            return x + _swiglu(p["mlp"], y), {}, None
    routed, shared, counters, experts = expert_ffn(p, cfg, y)
    return x + routed + shared, counters, experts


def _layer_mask(p, cfg, x, angles):
    """The layer's selection, from detached inputs — computed outside the
    rematerialised layer so that the backward pass does not score again."""
    x = lax.stop_gradient(
        _rms_norm(x, p["input_norm"]["scale"], cfg.rms_norm_eps))
    a = jax.tree.map(lax.stop_gradient, p["attn"])
    with jax.named_scope("lm.mla_proj"):
        c_q = _rms_norm(_dense(x, a["q_a_proj"]["kernel"]),
                        a["q_a_norm"]["scale"], cfg.rms_norm_eps)
    return select_keys(a["indexer"], cfg, x, c_q, angles)


def head_loss(norm_scale, matrix, x, ids, eps, *, tied: bool = False,
              logit_scale: float = 1.0):
    """Mean next-token cross-entropy in float32, in chunks of tokens:
    logits = RMSNorm(x) W * ``logit_scale``, W = ``matrix`` (h, V), or its
    transpose where the head is ``tied`` to the embedding (V, h)."""
    t_len = x.shape[0]
    lc = min(LOSS_CHUNK, t_len)
    assert t_len % lc == 0, (t_len, lc)
    target = jnp.concatenate([ids[1:], ids[:1]])
    weight = (jnp.arange(t_len) < t_len - 1).astype(jnp.float32)
    contract = (((1,), (1 if tied else 0,)), ((), ()))

    @jax.checkpoint
    def chunk(scale, kernel, xs, ts, ws):
        y = _rms_norm(xs, scale, eps)
        logits = lax.dot_general(y, kernel.astype(y.dtype), contract,
                                 preferred_element_type=jnp.float32)
        if logit_scale != 1.0:
            logits = logits * logit_scale
        nll = (jax.nn.logsumexp(logits, axis=-1)
               - jnp.take_along_axis(logits, ts[:, None], axis=-1)[:, 0])
        return jnp.sum(nll * ws)

    with jax.named_scope("lm.head_loss"):
        total = sum(chunk(norm_scale, matrix, x[i:i + lc], target[i:i + lc],
                          weight[i:i + lc])
                    for i in range(0, t_len, lc))
        return total / (t_len - 1)


def _forward(params, cfg: DeepSeekV32Config, ids, dtype):
    cfg.check()
    t_len = ids.shape[0]
    angles = rope_angles(cfg, jnp.arange(t_len))
    x = params["embed"]["embedding"].astype(dtype)[ids]
    layer = functools.partial(_layer, cfg)
    if cfg.remat:
        layer = jax.checkpoint(layer)
    counters, selected, choices = [], [], []
    for i in range(cfg.num_hidden_layers):
        p = params[f"layers_{i}"]
        mask = _layer_mask(p, cfg, x, angles)
        x, c, experts = layer(p, x, angles, mask)
        selected.append(jnp.sum(mask, dtype=jnp.float32) / t_len)
        if c:
            counters.append(c)
        if cfg.hand_out_choices:
            choices.append({"mask": jnp.packbits(mask, axis=-1),
                            "experts": experts,
                            "routed_over_shared": c.get("routed_over_shared")})
    aux = {k: jnp.mean(jnp.stack([c[k] for c in counters]))
           for k in (counters[0] if counters else {})}
    # mean keys selected a query, over all layers
    aux["keys_selected_mean"] = jnp.mean(jnp.stack(selected))
    if cfg.hand_out_choices:
        aux["choices"] = choices
    return x, aux


def forward_loss(params, cfg: DeepSeekV32Config, ids, dtype=jnp.bfloat16):
    """``(loss, aux)`` for one document ``ids`` (T,): the mean next-token
    cross-entropy over the vocabulary slice, and the step's counters
    (scalars, means over the expert layers; ``keys_selected_mean`` over all
    layers). Under ``cfg.hand_out_choices`` ``aux["choices"]`` holds, per
    layer, what THIS pass chose: ``mask`` (T, T / 8) uint8, the selection
    eight keys a byte; ``experts`` (T, K) and ``routed_over_shared`` (both
    None in a dense layer)."""
    x, aux = _forward(params, cfg, ids, dtype)
    return head_loss(params["final_norm"]["scale"], params["head"]["kernel"],
                     x, ids, cfg.rms_norm_eps), aux


def forward_logits(params, cfg: DeepSeekV32Config, ids, dtype=jnp.bfloat16):
    """(T, vocabulary held) float32 logits of one document, unchunked (for
    tests and small sizes)."""
    x, _ = _forward(params, cfg, ids, dtype)
    y = _rms_norm(x, params["final_norm"]["scale"], cfg.rms_norm_eps)
    return jnp.matmul(y, params["head"]["kernel"].astype(y.dtype),
                      preferred_element_type=jnp.float32)
