"""Plain float32 reference of DeepSeek-V3.2's decoder block, its next-token
loss and the Stage-1 tuning step — written from the published description
(https://huggingface.co/deepseek-ai/DeepSeek-V3.2, ``config.json`` and the
model card's equations), importing nothing of the program.

Straightforward ``jax.numpy`` under ``jax.default_matmul_precision("highest")``:
dense masked attention over the selected set S_t, one index head, one
attention head and one expert at a time (``lax.fori_loop`` / ``lax.map``: a
loop the compiler sees once, where a Python loop would be compiled 64, 8 and
16 times over), every expert applied to every token and weighed by its gate
(0 where the token was not routed to it), a sort for the top-k. No kernels
and no chunking of the mathematics; ``row_block`` cuts the work into blocks
of rows — tokens for the per-token parts (feed-forward, experts, head),
queries for the scorer and the attention, each against ALL keys — and
``remat`` recomputes pieces in the backward pass, so that the published
widths fit one chip; neither changes a number's definition.

It takes the chip's share as data: ``arch["experts_held"]`` / ``["heads_held"]``
``(first, count)``; weights by name (``params/layers_3/attn/q_b_proj/kernel``),
expert matrices stacked over the experts held. What the absent experts and
heads would add is left out.

  c_q = RMSNorm(W_qa x); [q_nope; q_rope] = W_qb c_q per head
  [c_kv; k_rope] = W_kva x; c_kv = RMSNorm(c_kv); [k_nope; v] = W_kvb c_kv
  rotary (YaRN) on q_rope, k_rope: adjacent pairs of dims
  scorer: qI = W_Iq c_q (64 x 128), kI = LayerNorm(W_Ik x), rotary on the
      first 64 dims of both (halves), w = W_w x / sqrt(64 * 128),
      I[t,s] = sum_j w[t,j] ReLU(qI[t,j] . kI[s]);
      S_t = {s <= t : I[t,s] >= the k-th largest over s <= t} (all s <= t
      while t < k); scorer inputs detached
  score = (q_nope.k_nope + q_rope.k_rope) * scale on S_t, softmax, o = p v
  experts: s = sigmoid(W_g x); select on s + b (groups by the sum of their
      two largest, keep topk_group, then the top-k inside); gates =
      routed_scaling_factor * s_i / sum_sel s; + shared expert
  loss = mean next-token cross-entropy

``operand`` below float32 is the CONTROL: both operands of every matrix
product are rounded to that dtype first. ``fault`` plants one of
``first_keys`` (the scorer replaced by "the first k keys"), ``four_experts``
(half the experts a token), ``no_gate_scale`` (the gate scale left out),
``half_document`` (the second half of the document left out of the loss).

``given`` hands the reference a run's DISCRETE CHOICES as data — per layer
the selection (eight keys a byte) and the experts a token — in place of its
own top-k. Top-k is discontinuous: a near-tie flips under bfloat16, a tenth
of the keys and a sixth of the experts at the published widths, and a
flipped key is another function of the weights. Given the same choices, what
is left to differ is arithmetic, and the state after the steps can be held
to a close limit; the choices themselves are compared on their own, against
``tune``'s ``chosen_own`` (what the reference chooses for itself at the
initial weights).
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np

FAULTS = ("first_keys", "four_experts", "no_gate_scale", "half_document")
ARCH_KEYS = (
    "hidden_size", "intermediate_size", "moe_intermediate_size",
    "num_hidden_layers", "first_k_dense_replace", "q_lora_rank",
    "kv_lora_rank", "qk_nope_head_dim", "qk_rope_head_dim", "v_head_dim",
    "index_n_heads", "index_head_dim", "index_topk", "n_routed_experts",
    "n_group", "topk_group", "num_experts_per_tok", "routed_scaling_factor",
    "vocab_size", "rms_norm_eps", "rope_theta", "rope_scaling",
)


def arch_from_config(config: dict) -> dict:
    """The keys the reference reads, from a configuration file: the
    published ``config.json`` keys at the top level (held counts in place of
    ``n_routed_experts`` / ``num_attention_heads``) and its ``deployment``."""
    arch = {k: config[k] for k in ARCH_KEYS}
    dep = config["deployment"]
    arch["n_routed_experts"] = dep["n_routed_experts_published"]
    arch["experts_held"] = tuple(dep["experts_held"])
    arch["heads_held"] = tuple(dep["heads_held"])
    assert arch["experts_held"][1] == config["n_routed_experts"]
    assert arch["heads_held"][1] == config["num_attention_heads"]
    return arch


class _Nx:
    def __init__(self, operand: str):
        self.dt = None if operand == "float32" else jnp.dtype(operand)

    def r(self, x):
        x = x.astype(jnp.float32)
        return x if self.dt is None else x.astype(self.dt).astype(jnp.float32)

    def mm(self, a, b):
        return jnp.matmul(self.r(a), self.r(b))


def _rms_norm(x, scale, eps):
    return (x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps)
            * scale.astype(jnp.float32))


def _layer_norm(x, scale, bias, eps=1e-6):
    mean = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean((x - mean) ** 2, axis=-1, keepdims=True)
    return ((x - mean) * jax.lax.rsqrt(var + eps) * scale.astype(jnp.float32)
            + bias.astype(jnp.float32))


def _silu(x):
    return x * jax.nn.sigmoid(x)


def rope_angles(arch: dict, t_len: int):
    dim, base = arch["qk_rope_head_dim"], float(arch["rope_theta"])
    rs = arch["rope_scaling"]
    freqs = 1.0 / base ** (np.arange(0, dim, 2, dtype=np.float64) / dim)

    def correction_dim(rotations):
        return (dim * math.log(rs["original_max_position_embeddings"]
                               / (rotations * 2 * math.pi))
                / (2 * math.log(base)))

    low = max(math.floor(correction_dim(rs["beta_fast"])), 0)
    high = min(math.ceil(correction_dim(rs["beta_slow"])), dim - 1)
    if low == high:
        high += 0.001
    ramp = np.clip((np.arange(dim // 2) - low) / (high - low), 0.0, 1.0)
    freqs = freqs / rs["factor"] * ramp + freqs * (1.0 - ramp)
    return jnp.asarray(np.arange(t_len)[:, None] * freqs[None, :], jnp.float32)


def softmax_scale(arch: dict) -> float:
    rs = arch["rope_scaling"]
    mscale = 0.1 * rs["mscale_all_dim"] * math.log(rs["factor"]) + 1.0
    return ((arch["qk_nope_head_dim"] + arch["qk_rope_head_dim"]) ** -0.5
            * mscale * mscale)


def _rotate_pairs(x, angles):
    """(T, D): dims (0,1), (2,3), ... are the rotated pairs."""
    a, b = x[:, 0::2], x[:, 1::2]
    cos, sin = jnp.cos(angles), jnp.sin(angles)
    return jnp.stack([a * cos - b * sin, a * sin + b * cos],
                     axis=-1).reshape(x.shape)


def _rotate_halves(x, angles):
    """(T, D): dims (i, i + D/2) are the rotated pairs."""
    half = x.shape[-1] // 2
    a, b = x[:, :half], x[:, half:]
    cos, sin = jnp.cos(angles), jnp.sin(angles)
    return jnp.concatenate([a * cos - b * sin, a * sin + b * cos], axis=-1)


def _maybe_remat(fn, remat):
    return jax.checkpoint(fn) if remat else fn


def _row_blocks(fn, arrays, row_block, remat=False):
    """``fn(*blocks)`` over blocks of ``row_block`` leading rows of every
    array, one block at a time (``lax.map``: the blocks cannot overlap in
    memory), each recomputed in the backward pass under ``remat``; the
    results joined."""
    n = arrays[0].shape[0]
    rb = row_block if row_block and row_block < n else n
    assert n % rb == 0, (n, rb)
    cut = tuple(a.reshape((n // rb, rb) + a.shape[1:]) for a in arrays)
    out = jax.lax.map(lambda a: _maybe_remat(fn, remat)(*a), cut)
    return out.reshape((n,) + out.shape[2:])


def selection(W, arch, nx, x, c_q, angles, fault=None, row_block=None,
              given=None):
    """(T, T) bool mask of S_t, from detached inputs; ``given`` (packed,
    eight keys a byte) is taken in its place."""
    t_len = x.shape[0]
    if given is not None:
        return jnp.unpackbits(given, axis=-1, count=t_len).astype(bool)
    k = int(arch["index_topk"])
    pos = jnp.arange(t_len)
    causal = pos[None, :] <= pos[:, None]
    if fault == "first_keys":
        return causal & (pos[None, :] < k)
    if t_len <= k:
        return causal
    nh, hd, rd = arch["index_n_heads"], arch["index_head_dim"], arch["qk_rope_head_dim"]
    x, c_q = jax.lax.stop_gradient(x), jax.lax.stop_gradient(c_q)
    q = nx.mm(c_q, W("wq_b/kernel")).reshape(t_len, nh, hd)
    key = _layer_norm(nx.mm(x, W("wk/kernel")), W("k_norm/scale"),
                      W("k_norm/bias"))
    key = jnp.concatenate([_rotate_halves(key[:, :rd], angles), key[:, rd:]], -1)
    w = nx.mm(x, W("weights_proj/kernel")) * (nh ** -0.5 * hd ** -0.5)

    def queries(q, w, angles, causal):
        def one_head(j, score):
            qj = jax.lax.dynamic_index_in_dim(q, j, axis=1, keepdims=False)
            qj = jnp.concatenate([_rotate_halves(qj[:, :rd], angles),
                                  qj[:, rd:]], -1)
            wj = jax.lax.dynamic_index_in_dim(w, j, axis=1, keepdims=True)
            return score + wj * jax.nn.relu(nx.mm(qj, key.T))

        score = jax.lax.fori_loop(0, nh, one_head,
                                  jnp.zeros(causal.shape, jnp.float32))
        score = jnp.where(causal, score, -jnp.inf)
        kth = jnp.sort(score, axis=-1)[:, -k]
        return causal & (score >= kth[:, None])

    return _row_blocks(queries, (q, w, angles, causal), row_block)


def attention_part(W, arch, nx, x, angles, *, fault=None, remat=False,
                   row_block=None, given=None):
    """The held heads' part of the attention output for the normed input
    ``x`` (T, h), and the mask used."""
    t_len = x.shape[0]
    _, hn = arch["heads_held"]
    nd, rd, vd = arch["qk_nope_head_dim"], arch["qk_rope_head_dim"], arch["v_head_dim"]
    lat, eps = arch["kv_lora_rank"], arch["rms_norm_eps"]
    scale = softmax_scale(arch)
    c_q = _rms_norm(nx.mm(x, W("q_a_proj/kernel")), W("q_a_norm/scale"), eps)
    q = nx.mm(c_q, W("q_b_proj/kernel")).reshape(t_len, hn, nd + rd)
    kv = nx.mm(x, W("kv_a_proj/kernel"))
    c_kv = _rms_norm(kv[:, :lat], W("kv_a_norm/scale"), eps)
    k_rope = _rotate_pairs(kv[:, lat:], angles)
    kvb = nx.mm(c_kv, W("kv_b_proj/kernel")).reshape(t_len, hn, nd + vd)
    mask = selection(W.at("indexer"), arch, nx, x, c_q, angles, fault,
                     row_block, given)

    kv_heads = kvb.transpose(1, 0, 2)  # (heads, T, nope + v)

    def queries(q, angles, mask):
        """A block of queries (rows, heads, nope + rope) against all keys,
        one head at a time."""
        def head(qh, kvh):
            q_rope = _rotate_pairs(qh[:, nd:], angles)
            s = nx.mm(qh[:, :nd], kvh[:, :nd].T) + nx.mm(q_rope, k_rope.T)
            p = jax.nn.softmax(jnp.where(mask, s * scale, -jnp.inf), axis=-1)
            return nx.mm(p, kvh[:, nd:])

        o = jax.lax.map(lambda a: _maybe_remat(head, remat)(*a),
                        (q.transpose(1, 0, 2), kv_heads))
        return o.transpose(1, 0, 2).reshape(q.shape[0], hn * vd)

    o = _row_blocks(queries, (q, angles, mask), row_block, remat)
    return nx.mm(o, W("o_proj/kernel")), mask


def choose_experts(s, bias, arch, k):
    """(T, k) experts from the scores ``s`` (T, n): selection on score +
    bias, groups by the sum of their two largest, ``topk_group`` groups
    kept, then the k largest inside them."""
    n, g = arch["n_routed_experts"], arch["n_group"]
    sel = s + bias.astype(jnp.float32)
    grouped = sel.reshape(-1, g, n // g)
    group_score = jnp.sort(grouped, axis=-1)[..., -2:].sum(-1)
    kept = jnp.argsort(-group_score, axis=-1)[:, :arch["topk_group"]]
    in_kept = (kept[:, :, None] == jnp.arange(g)[None, None, :]).any(1)
    sel = jnp.where(jnp.repeat(in_kept, n // g, axis=-1), sel, -jnp.inf)
    return jnp.argsort(-sel, axis=-1)[:, :k]


def routing(W, arch, nx, x, fault=None, given=None):
    """(experts (T, K), gates (T, K)) over all routed experts; ``given``
    experts are taken in place of the selection, their gates from the
    scores here."""
    k = arch["num_experts_per_tok"] // (2 if fault == "four_experts" else 1)
    s = jax.nn.sigmoid(nx.mm(x, W("kernel")))
    experts = (choose_experts(s, W("bias"), arch, k) if given is None
               else given[:, :k])
    picked = jnp.take_along_axis(s, experts, axis=-1)
    scale = 1.0 if fault == "no_gate_scale" else arch["routed_scaling_factor"]
    return experts, scale * picked / picked.sum(-1, keepdims=True)


def _swiglu(nx, x, wg, wu, wd):
    return nx.mm(_silu(nx.mm(x, wg)) * nx.mm(x, wu), wd)


def moe_parts(W, arch, nx, x, *, fault=None, remat=False, row_block=None,
              given=None):
    """(held experts' part, shared expert's part, experts chosen) for the
    normed input ``x``."""
    e0, en = arch["experts_held"]
    experts, gates = routing(W.at("router"), arch, nx, x, fault, given)
    wg, wu, wd = (W(f"experts/{n}/kernel")
                  for n in ("gate_proj", "up_proj", "down_proj"))

    # gate[t, e]: expert e's gate for token t, 0 where t was not routed to it
    gate = jnp.sum(jnp.where(
        experts[:, None, :] == (e0 + jnp.arange(en))[None, :, None],
        gates[:, None, :], 0.0), axis=-1)

    def block(xb, gate_b):
        """Every held expert on a block of tokens, one expert at a time."""
        def one(e, acc):
            return acc + gate_b[:, e, None] * _swiglu(nx, xb, wg[e], wu[e], wd[e])

        return jax.lax.fori_loop(0, en, _maybe_remat(one, remat),
                                 jnp.zeros_like(xb))

    routed = _row_blocks(block, (x, gate), row_block, remat)
    sh = W.at("shared")
    shared = _row_blocks(lambda xb: _swiglu(
        nx, xb, sh("gate_proj/kernel"), sh("up_proj/kernel"),
        sh("down_proj/kernel")), (x,), row_block, remat)
    return routed, shared, experts


class Weights:
    """``{"a/b/kernel": array}`` with a moving prefix."""

    def __init__(self, flat: dict, prefix: str = "params/"):
        self.flat, self.prefix = flat, prefix

    def at(self, name: str) -> "Weights":
        return Weights(self.flat, f"{self.prefix}{name}/")

    def __call__(self, name: str):
        return self.flat[self.prefix + name]

    def has(self, name: str) -> bool:
        return (self.prefix + name) in self.flat


def layer(W, arch, nx, x, angles, *, fault=None, remat=False, row_block=None,
          given=None):
    """One decoder layer: ``(x_out, mask, experts chosen, the held experts'
    part over the shared expert's in root mean square)``, the last two None
    in a dense layer. ``given`` = ``{"mask": packed, "experts": (T, K) or
    None}``: the layer's discrete choices taken as data."""
    eps = arch["rms_norm_eps"]
    given = given or {}
    a, mask = attention_part(W.at("attn"), arch, nx,
                             _rms_norm(x, W("input_norm/scale"), eps), angles,
                             fault=fault, remat=remat, row_block=row_block,
                             given=given.get("mask"))
    x = x + a
    y = _rms_norm(x, W("post_norm/scale"), eps)
    if W.has("mlp/gate_proj/kernel"):
        m = W.at("mlp")
        ffn = _row_blocks(lambda yb: _swiglu(
            nx, yb, m("gate_proj/kernel"), m("up_proj/kernel"),
            m("down_proj/kernel")), (y,), row_block, remat)
        return x + ffn, mask, None, None
    routed, shared, experts = moe_parts(W, arch, nx, y, fault=fault,
                                        remat=remat, row_block=row_block,
                                        given=given.get("experts"))
    ratio = jnp.sqrt(jnp.sum(routed ** 2) / jnp.sum(shared ** 2))
    return x + routed + shared, mask, experts, ratio


def _hidden(W, arch, nx, ids, fault, remat, row_block, packed=False,
            given=None):
    """The last layer's output (T, h) and, per layer, what was chosen (the
    mask eight keys a byte under ``packed``: five (16384, 16384) masks are
    1.3 GB as booleans)."""
    angles = rope_angles(arch, ids.shape[0])
    x = W("embed/embedding").astype(jnp.float32)[ids]
    choices = []
    for i in range(arch["num_hidden_layers"]):
        step = _maybe_remat(
            lambda x, i=i: layer(W.at(f"layers_{i}"), arch, nx, x, angles,
                                 fault=fault, remat=remat, row_block=row_block,
                                 given=given[i] if given else None), remat)
        x, mask, experts, ratio = step(x)
        choices.append({"mask": jnp.packbits(mask, axis=-1) if packed else mask,
                        "experts": experts, "routed_over_shared": ratio})
    return x, choices


def logits(flat: dict, arch: dict, ids, *, operand="float32", fault=None):
    """(T, vocabulary held) logits of one document."""
    nx = _Nx(operand)
    with jax.default_matmul_precision("highest"):
        W = Weights(flat)
        x, _ = _hidden(W, arch, nx, ids, fault, False, None)
        return nx.mm(_rms_norm(x, W("final_norm/scale"), arch["rms_norm_eps"]),
                     W("head/kernel"))


def forward(flat: dict, arch: dict, ids, *, operand="float32", fault=None,
            remat=False, row_block=None, packed=False, given=None):
    """``(loss, choices)``: the mean next-token cross-entropy of one
    document ``ids`` (T,) and, per layer, the selection mask (``packed``:
    eight keys a byte) and the experts chosen."""
    assert fault is None or fault in FAULTS, fault
    nx = _Nx(operand)
    with jax.default_matmul_precision("highest"):
        W = Weights(flat)
        x, choices = _hidden(W, arch, nx, ids, fault, remat, row_block, packed,
                             given)
        return _next_token_nll(W, arch, nx, x, ids, remat, row_block,
                               fault), choices


def is_trainable(name: str, patterns) -> bool:
    """A leaf trains when a pattern's dotted tokens appear consecutively in
    its path (``q_a_proj`` matches ``.../attn/q_a_proj/kernel``)."""
    toks = name.split("/")
    for pat in patterns:
        p = pat.split(".")
        if any(toks[i:i + len(p)] == p for i in range(len(toks) - len(p) + 1)):
            return True
    return False


def _next_token_nll(W, arch, nx, x, ids, remat, row_block, fault=None):
    """Mean next-token cross-entropy from the last layer's output."""
    def nll(xb, target):
        y = _rms_norm(xb, W("final_norm/scale"), arch["rms_norm_eps"])
        logits = nx.mm(y, W("head/kernel"))
        return (jax.nn.logsumexp(logits, axis=-1)
                - jnp.take_along_axis(logits, target[:, None], -1)[:, 0])

    per_token = _row_blocks(nll, (x, jnp.roll(ids, -1)), row_block, remat)
    if fault == "half_document":
        return jnp.mean(per_token[:ids.shape[0] // 2])
    return jnp.mean(per_token[:-1])


def whole_grads(arch: dict, *, operand="float32", fault=None, remat=False,
                row_block=None):
    """Jitted ``(trainable, frozen, ids, given) -> (loss, choices, grads)``:
    the loss differentiated as one function of the trainable leaves; and
    ``(trainable, frozen, ids) -> choices``, the forward pass alone."""
    def grads(trainable, frozen, ids, given=None):
        def loss_fn(tr):
            return forward({**frozen, **tr}, arch, ids, operand=operand,
                           fault=fault, remat=remat, row_block=row_block,
                           packed=True, given=given)

        (loss, choices), g = jax.value_and_grad(loss_fn, has_aux=True)(trainable)
        return loss, choices, g

    def choose(trainable, frozen, ids):
        return forward({**frozen, **trainable}, arch, ids, operand=operand,
                       fault=fault, row_block=row_block, packed=True)[1]

    return jax.jit(grads), jax.jit(choose)


def layerwise_grads(arch: dict, *, operand="float32", fault=None, remat=False,
                    row_block=None):
    """The same ``(trainable, frozen, ids, given) -> (loss, choices, grads)`` with
    the chain rule applied layer by layer in Python: every layer is the same
    ``layer`` function, jitted once for the dense and once for the expert
    layers, and ``frozen`` may live on the HOST (numpy arrays) — a layer's
    weights are on the device only while it runs, forward and backward. At
    the published widths the frozen share is 7.7 GB: beside it the float32
    activations of a 16384-token document and what the program handed over
    for the comparison do not fit one chip."""
    nx = _Nx(operand)
    kw = dict(fault=fault, remat=remat, row_block=row_block)

    def run_layer(tr, fr, x, given):
        with jax.default_matmul_precision("highest"):
            return layer(Weights({**fr, **tr}, ""), arch, nx, x,
                         rope_angles(arch, x.shape[0]), given=given, **kw)

    @jax.jit
    def fwd(tr, fr, x, given):
        x, mask, experts, ratio = run_layer(tr, fr, x, given)
        return x, {"mask": jnp.packbits(mask, axis=-1), "experts": experts,
                   "routed_over_shared": ratio}

    @jax.jit
    def bwd(tr, fr, x, dx_out, given):
        _, pull = jax.vjp(lambda tr, x: run_layer(tr, fr, x, given)[0], tr, x)
        return pull(dx_out)

    @jax.jit
    def head(fr, x, ids):
        def loss_fn(x):
            with jax.default_matmul_precision("highest"):
                return _next_token_nll(Weights(fr, ""), arch, nx, x, ids,
                                       remat, row_block, fault)

        return jax.value_and_grad(loss_fn)(x)

    def part(tree, prefix):
        return {k[len(prefix):]: v for k, v in tree.items()
                if k.startswith(prefix)}

    n = arch["num_hidden_layers"]

    def embed(frozen, ids):
        return jnp.asarray(
            frozen["params/embed/embedding"])[ids].astype(jnp.float32)

    def choose(trainable, frozen, ids):
        x, choices = embed(frozen, ids), []
        for i in range(n):
            pre = f"params/layers_{i}/"
            x, chosen = fwd(part(trainable, pre), part(frozen, pre), x, None)
            choices.append(chosen)
        return choices

    def grads(trainable, frozen, ids, given=None):
        given = given or [None] * n
        x, xs, choices = embed(frozen, ids), [], []
        for i in range(n):
            pre = f"params/layers_{i}/"
            xs.append(x)
            x, chosen = fwd(part(trainable, pre), part(frozen, pre), x,
                            given[i])
            choices.append(chosen)
        top = {k: frozen["params/" + k] for k in ("final_norm/scale",
                                                  "head/kernel")}
        loss, dx = head(top, x, ids)
        g = {}
        for i in reversed(range(n)):
            pre = f"params/layers_{i}/"
            dtr, dx = bwd(part(trainable, pre), part(frozen, pre), xs.pop(),
                          dx, given[i])
            g.update({pre + k: v for k, v in dtr.items()})
        return loss, choices, g

    return grads, choose


def make_update(hp: dict):
    """Jitted ``(trainable, mu, nu, i, grads) -> (trainable, mu, nu,
    grad_norm)``: global-norm clipping, then AdamW (decoupled decay, bias
    correction), as ``torch.optim.AdamW`` defines them."""
    lr, b1, b2 = hp["learning_rate"], hp["adam_beta1"], hp["adam_beta2"]
    eps, wd, max_norm = (hp["adam_epsilon"], hp["adam_weight_decay"],
                         hp["max_grad_norm"])

    def update(trainable, mu, nu, i, g):
        gnorm = jnp.sqrt(sum(jnp.sum(v ** 2) for v in g.values()))
        clip = jnp.where(gnorm < max_norm, 1.0, max_norm / gnorm)
        g = {k: v * clip for k, v in g.items()}
        c = (i + 1).astype(jnp.float32)
        mu = {k: b1 * mu[k] + (1 - b1) * g[k] for k in g}
        nu = {k: b2 * nu[k] + (1 - b2) * g[k] ** 2 for k in g}
        new = {}
        for k in g:
            m_hat = mu[k] / (1 - b1 ** c)
            v_hat = nu[k] / (1 - b2 ** c)
            upd = m_hat / (jnp.sqrt(v_hat) + eps) + wd * trainable[k]
            new[k] = trainable[k] - lr * upd
        return new, mu, nu, gnorm

    return jax.jit(update, donate_argnums=(0, 1, 2))


def tune(flat: dict, arch: dict, hp: dict, ids, n_steps: int, *,
         layerwise=False, given=None, **how) -> dict:
    """Follow the first ``n_steps`` steps from the initial weights: the same
    document every step. ``given``: per step, per layer, the discrete
    choices to take as data (``layer``). ``chosen`` is what the FIRST step's
    forward used (at the initial weights; the given ones under ``given``),
    masks packed; ``chosen_own`` what the reference chooses for itself
    there. ``layerwise`` moves the frozen
    leaves to the host and EMPTIES ``flat`` (so that the caller's copy on
    the device is freed), then takes the gradient layer by layer."""
    pats = hp["trainable_modules"]
    trainable = {k: jnp.array(v, jnp.float32) for k, v in flat.items()
                 if is_trainable(k, pats)}  # copies: the update donates them
    if layerwise:
        frozen = {}
        for k in list(flat):
            v = flat.pop(k)
            if k not in trainable:
                frozen[k] = np.asarray(v)
        grads, choose = layerwise_grads(arch, **how)
    else:
        frozen = {k: v for k, v in flat.items() if k not in trainable}
        grads, choose = whole_grads(arch, **how)
    chosen_own = choose(trainable, frozen, ids) if given else None
    mu = {k: jnp.zeros_like(v) for k, v in trainable.items()}
    nu = {k: jnp.zeros_like(v) for k, v in trainable.items()}
    update = make_update(hp)
    losses, gnorms, chosen = [], [], None
    for i in range(n_steps):
        loss, choices, g = grads(trainable, frozen, ids,
                                 given[i] if given else None)
        trainable, mu, nu, gnorm = update(trainable, mu, nu,
                                          jnp.asarray(i, jnp.int32), g)
        chosen = choices if chosen is None else chosen
        del choices, g
        losses.append(loss)
        gnorms.append(gnorm)
    return {"trainable": trainable, "mu": mu, "nu": nu, "chosen": chosen,
            "chosen_own": chosen_own if given else chosen,
            "losses": np.asarray(jax.device_get(jnp.stack(losses))),
            "grad_norms": np.asarray(jax.device_get(jnp.stack(gnorms)))}
