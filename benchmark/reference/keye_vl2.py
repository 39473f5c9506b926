"""Plain float32 reference of Keye-VL-2.0-30B-A3B's language model
(``model_type: KeyeVL2``, the text path), its next-token loss and the
Stage-1 tuning step — written from the published configuration
(https://huggingface.co/Kwai-Keye/Keye-VL-2.0-30B-A3B/blob/main/config.json,
``config`` and ``sa_config``), importing nothing of the program.

Straightforward ``jax.numpy`` under ``jax.default_matmul_precision("highest")``.
The selection is an explicit (rows, keys) mask from a full sort of every
query's index scores over ALL keys; attention is a full-row softmax under
that mask, one head at a time, in blocks of query rows: no tile is skipped.
mRoPE is computed from its three position rows (time, height, width) and
``mrope_section``. The experts are a dense loop over the experts held, every
expert applied to every token and weighed by its gate (0 where the token was
not routed to it); a sort for the top-k. ``row_block`` cuts per-token work
into blocks of rows and ``remat`` recomputes pieces in the backward pass, so
that the published widths fit one chip; neither changes a number's
definition.

  h0 = E[ids]
  layer i: u = RMSNorm(x);
      scorer (detached): qI = W_qI u (heads x dim), kI = LayerNorm(W_kI u),
          rotary (halves) on the first half of both, w = W_w u / sqrt(heads
          dim), I[t,s] = sum_j w[t,j] ReLU(qI[t,j] . kI[s]); S_t = {s <= t :
          I[t,s] >= the topk-th largest over s <= t}
      q, k = RMSNorm_head(W_q u), RMSNorm_head(W_k u) (QK-norm), rotary
          (halves, mRoPE), v = W_v u; query head i on key / value head
          i // group; o = softmax over S_t of q.k / sqrt(head_dim), v;
          x <- x + W_o o
      y = RMSNorm(x); p = softmax(W_r y); the K largest, g = p_sel / sum
          p_sel; x <- x + sum g_e E_e(y), E a gated silu feed-forward
  logits = RMSNorm_f(x) W_head; loss = mean next-token cross-entropy

It takes the chip's share as data: ``arch["experts_held"]`` ``(first,
count)``; weights by name, expert matrices stacked over the experts held.

``operand`` below float32 is the CONTROL: both operands of every matrix
product are rounded to that dtype first. ``fault`` plants one of ``FAULTS``.
``given`` hands the reference a run's discrete choices as data — per layer
the selection (eight keys a byte) and the experts a token — in place of its
own top-k (a near-tie flips under bfloat16 and a flipped key or expert is
another function of the weights); gates are the reference's own.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from benchmark.reference.deepseek_v32 import (
    Weights,
    _layer_norm,
    _maybe_remat,
    _next_token_nll,
    _Nx,
    _rms_norm,
    _rotate_halves,
    _rotate_pairs,
    _row_blocks,
    _swiglu,
    is_trainable,
    make_update,
)

FAULTS = (
    "no_qk_norm",             # q and k not normed per head
    "kv_head_mod_not_div",    # query head i reads key / value head i % 4
    "dense_not_selected",     # attention over every earlier key, no selection
    "topk_1024",              # the scorer keeps half its keys: 1024 of 2048
    "gates_not_normalised",   # gates the softmax probabilities as they are
    "top7",                   # one expert a token fewer
    "rope_pairs_not_halves",  # rotary pairs dim 2i with 2i + 1
    "rope_theta_1e6",         # rotary at theta 1e6, not 1e7
    "indexer_no_relu",        # the scorer's head logits without the ReLU
)
ARCH_KEYS = (
    "hidden_size", "moe_intermediate_size", "head_dim", "num_hidden_layers",
    "num_attention_heads", "num_key_value_heads", "num_experts",
    "num_experts_per_tok", "rms_norm_eps", "rope_theta", "vocab_size",
)


def arch_from_config(config: dict) -> dict:
    """The keys the reference reads, from a configuration file: the
    published keys at the top level (HELD counts where ``reduced`` says so),
    ``rope_scaling.mrope_section``, ``sa_config`` and the ``deployment``."""
    arch = {k: config[k] for k in ARCH_KEYS}
    sa = config["sa_config"]
    arch.update(mrope_section=tuple(config["rope_scaling"]["mrope_section"]),
                indexer_num_heads=sa["indexer_num_heads"],
                indexer_head_dim=sa["indexer_head_dim"], topk=sa["topk"],
                experts_held=tuple(config["deployment"]["experts_held"]))
    return arch


# ------------------------------------------------------------------ rotary


def rope_angles(arch: dict, t_len: int, fault=None):
    """(T, head_dim / 2): frequency pair i = theta^(-2 i / head_dim) turns
    with the position row its mRoPE section names — (time, height, width),
    ``mrope_section`` pairs each; a text token's three rows are equal."""
    dim = arch["head_dim"]
    theta = 1e6 if fault == "rope_theta_1e6" else float(arch["rope_theta"])
    freqs = 1.0 / theta ** (np.arange(0, dim, 2, dtype=np.float64) / dim)
    rows = np.stack([np.arange(t_len)] * 3)           # time, height, width
    section = np.concatenate([np.full(n, j) for j, n in
                              enumerate(arch["mrope_section"])])
    assert section.shape == (dim // 2,)
    return jnp.asarray(rows[section].T * freqs[None, :], jnp.float32)


def indexer_angles(arch: dict, t_len: int, fault=None):
    """(T, indexer_head_dim / 4): the scorer's rotated half."""
    dim = arch["indexer_head_dim"] // 2
    theta = 1e6 if fault == "rope_theta_1e6" else float(arch["rope_theta"])
    freqs = 1.0 / theta ** (np.arange(0, dim, 2, dtype=np.float64) / dim)
    return jnp.asarray(np.arange(t_len)[:, None] * freqs[None, :], jnp.float32)


# ---------------------------------------------------------------- selection


def selection(W, arch, nx, u, angles, *, fault=None, row_block=None,
              given=None):
    """(T, T) bool mask of S_t from the detached normed input ``u``;
    ``given`` (packed, eight keys a byte) is taken in its place."""
    t_len = u.shape[0]
    pos = jnp.arange(t_len)
    causal = pos[None, :] <= pos[:, None]
    if fault == "dense_not_selected":
        return causal
    if given is not None:
        return jnp.unpackbits(given, axis=-1, count=t_len).astype(bool)
    k = int(arch["topk"]) // (2 if fault == "topk_1024" else 1)
    nh, hd = arch["indexer_num_heads"], arch["indexer_head_dim"]
    rd = hd // 2
    u = jax.lax.stop_gradient(u)
    q = nx.mm(u, W("wq_b/kernel")).reshape(t_len, nh, hd)
    key = _layer_norm(nx.mm(u, W("wk/kernel")), W("k_norm/scale"),
                      W("k_norm/bias"))
    key = jnp.concatenate([_rotate_halves(key[:, :rd], angles), key[:, rd:]], -1)
    w = nx.mm(u, W("weights_proj/kernel")) * (nh ** -0.5 * hd ** -0.5)
    relu = (lambda a: a) if fault == "indexer_no_relu" else jax.nn.relu

    def queries(q, w, angles, causal):
        def one_head(j, score):
            qj = jax.lax.dynamic_index_in_dim(q, j, axis=1, keepdims=False)
            qj = jnp.concatenate([_rotate_halves(qj[:, :rd], angles),
                                  qj[:, rd:]], -1)
            wj = jax.lax.dynamic_index_in_dim(w, j, axis=1, keepdims=True)
            return score + wj * relu(nx.mm(qj, key.T))

        score = jax.lax.fori_loop(0, nh, one_head,
                                  jnp.zeros(causal.shape, jnp.float32))
        score = jnp.where(causal, score, -jnp.inf)
        kth = jnp.sort(score, axis=-1)[:, -min(k, t_len)]
        return causal & (score >= kth[:, None])

    return _row_blocks(queries, (q, w, angles, causal), row_block)


# ---------------------------------------------------------------- attention


def attention_part(W, arch, nx, u, angles, mask, *, fault=None, remat=False,
                   row_block=None):
    """The attention output for the normed ``u`` under the selection."""
    t_len = u.shape[0]
    hq, hkv, hd = (arch["num_attention_heads"], arch["num_key_value_heads"],
                   arch["head_dim"])
    group, scale, eps = hq // hkv, hd ** -0.5, arch["rms_norm_eps"]
    rotate = _rotate_pairs if fault == "rope_pairs_not_halves" else _rotate_halves
    q = nx.mm(u, W("q_proj/kernel")).reshape(t_len, hq, hd)
    k = nx.mm(u, W("k_proj/kernel")).reshape(t_len, hkv, hd)
    v = nx.mm(u, W("v_proj/kernel")).reshape(t_len, hkv, hd).transpose(1, 0, 2)
    if fault != "no_qk_norm":
        q = _rms_norm(q, W("q_norm/scale"), eps)
        k = _rms_norm(k, W("k_norm/scale"), eps)
    k = jax.vmap(lambda kh: rotate(kh, angles))(k.transpose(1, 0, 2))

    def kv_head(i):
        return i % hkv if fault == "kv_head_mod_not_div" else i // group

    def queries(q, angles, mask):
        """A block of query rows against all keys, one head at a time."""
        def head(qh, i):
            s = nx.mm(rotate(qh, angles), k[kv_head(i)].T) * scale
            p = jax.nn.softmax(jnp.where(mask, s, -jnp.inf), axis=-1)
            return nx.mm(p, v[kv_head(i)])

        o = jax.lax.map(lambda a: _maybe_remat(head, remat)(*a),
                        (q.transpose(1, 0, 2), jnp.arange(hq)))
        return o.transpose(1, 0, 2).reshape(q.shape[0], hq * hd)

    o = _row_blocks(queries, (q, angles, mask), row_block, remat)
    return nx.mm(o, W("o_proj/kernel"))


# ------------------------------------------------------------- expert layer


def routing(W, arch, nx, y, fault=None, given=None):
    """(experts (T, K), gates (T, K)) over all experts; ``given`` experts
    are taken in place of the selection, their gates from the
    probabilities here."""
    k = arch["num_experts_per_tok"] - (1 if fault == "top7" else 0)
    p = jax.nn.softmax(nx.mm(y, W("kernel")), axis=-1)
    experts = (jnp.argsort(-p, axis=-1)[:, :k] if given is None
               else given[:, :k])
    picked = jnp.take_along_axis(p, experts, axis=-1)
    if fault == "gates_not_normalised":
        return experts, picked
    return experts, picked / jnp.sum(picked, axis=-1, keepdims=True)


def moe_part(W, arch, nx, y, *, fault=None, remat=False, row_block=None,
             given=None):
    """(the held experts' part, experts chosen) for the normed ``y``."""
    e0, en = arch["experts_held"]
    experts, gates = routing(W.at("router"), arch, nx, y, fault, given)
    wg, wu, wd = (W(f"experts/{n}/kernel")
                  for n in ("gate_proj", "up_proj", "down_proj"))
    # gate[t, e]: expert e's gate for token t, 0 where t was not routed to it
    gate = jnp.sum(jnp.where(
        experts[:, None, :] == (e0 + jnp.arange(en))[None, :, None],
        gates[:, None, :], 0.0), axis=-1)

    def block(yb, gate_b):
        def one(e, acc):
            return acc + gate_b[:, e, None] * _swiglu(nx, yb, wg[e], wu[e], wd[e])

        return jax.lax.fori_loop(0, en, _maybe_remat(one, remat),
                                 jnp.zeros_like(yb))

    return _row_blocks(block, (y, gate), row_block, remat), experts


# ------------------------------------------------------------------ forward


def layer(W, arch, nx, x, t_len, *, fault=None, remat=False, row_block=None,
          given=None):
    """One layer: ``(x_out, selection mask, experts chosen)``. ``given`` =
    ``{"mask": packed, "experts": (T, K)}``, either may be missing."""
    given = given or {}
    eps = arch["rms_norm_eps"]
    u = _rms_norm(x, W("input_norm/scale"), eps)
    mask = selection(W.at("attn/indexer"), arch, nx, u,
                     indexer_angles(arch, t_len, fault), fault=fault,
                     row_block=row_block, given=given.get("mask"))
    x = x + attention_part(W.at("attn"), arch, nx, u,
                           rope_angles(arch, t_len, fault), mask, fault=fault,
                           remat=remat, row_block=row_block)
    y = _rms_norm(x, W("post_norm/scale"), eps)
    routed, experts = moe_part(W, arch, nx, y, fault=fault, remat=remat,
                               row_block=row_block, given=given.get("experts"))
    return x + routed, mask, experts


def _embed(W, ids):
    return jnp.asarray(W("embed/embedding"))[ids].astype(jnp.float32)


def logits(flat: dict, arch: dict, ids, *, operand="float32", fault=None):
    """(T, vocabulary held) logits of one document."""
    nx = _Nx(operand)
    with jax.default_matmul_precision("highest"):
        W = Weights(flat)
        x = _embed(W, ids)
        for i in range(arch["num_hidden_layers"]):
            x = layer(W.at(f"layers_{i}"), arch, nx, x, ids.shape[0],
                      fault=fault)[0]
        y = _rms_norm(x, W("final_norm/scale"), arch["rms_norm_eps"])
        return nx.mm(y, W("head/kernel"))


def layerwise_grads(arch: dict, *, operand="float32", fault=None, remat=False,
                    row_block=None):
    """``grads(trainable, frozen, ids, given) -> (loss, choices, grads)``
    and ``choose(trainable, frozen, ids) -> choices`` with the chain rule
    applied layer by layer in Python: ``layer`` is jitted once, and
    ``frozen`` may live on the HOST (numpy arrays) — a layer's weights are
    on the device only while it runs. The backward pass of a layer takes the
    selection its forward used as data (the scorer is not run again).
    ``choices``: per layer ``{"mask": packed, "experts"}``."""
    assert fault is None or fault in FAULTS, fault
    nx = _Nx(operand)
    kw = dict(fault=fault, remat=remat, row_block=row_block)

    def run_layer(tr, fr, x, given):
        with jax.default_matmul_precision("highest"):
            return layer(Weights({**fr, **tr}, ""), arch, nx, x, x.shape[0],
                         given=given, **kw)

    @jax.jit
    def fwd(tr, fr, x, given):
        x, mask, experts = run_layer(tr, fr, x, given)
        return x, {"mask": jnp.packbits(mask, axis=-1), "experts": experts}

    @jax.jit
    def bwd(tr, fr, x, dx_out, given):
        _, pull = jax.vjp(lambda tr, x: run_layer(tr, fr, x, given)[0], tr, x)
        return pull(dx_out)

    @jax.jit
    def head(fr, x, ids):
        def loss_fn(x):
            with jax.default_matmul_precision("highest"):
                return _next_token_nll(Weights(fr, ""), arch, nx, x, ids,
                                       remat, row_block)

        return jax.value_and_grad(loss_fn)(x)

    def part(tree, prefix):
        return {k[len(prefix):]: v for k, v in tree.items()
                if k.startswith(prefix)}

    n = arch["num_hidden_layers"]

    def forward(trainable, frozen, ids, given):
        x, xs, choices = _embed(Weights(frozen), ids), [], []
        for i in range(n):
            pre = f"params/layers_{i}/"
            xs.append(x)
            x, chosen = fwd(part(trainable, pre), part(frozen, pre), x,
                            given[i] if given else {})
            choices.append(chosen)
        return x, xs, choices

    def choose(trainable, frozen, ids):
        return forward(trainable, frozen, ids, None)[2]

    def grads(trainable, frozen, ids, given=None):
        x, xs, choices = forward(trainable, frozen, ids, given)
        top = {k: frozen["params/" + k] for k in ("final_norm/scale",
                                                  "head/kernel")}
        loss, dx = head(top, x, ids)
        g = {}
        for i in reversed(range(n)):
            pre = f"params/layers_{i}/"
            dtr, dx = bwd(part(trainable, pre), part(frozen, pre), xs.pop(),
                          dx, choices[i])
            g.update({pre + k: v for k, v in dtr.items()})
        return loss, choices, g

    return grads, choose


def loss_and_grads(flat: dict, arch: dict, patterns, ids, **how):
    """``(loss, {leaf: gradient})`` of the trainable leaves at the weights
    ``flat`` (left as they are), for tests and small sizes."""
    trainable = {k: jnp.asarray(v, jnp.float32) for k, v in flat.items()
                 if is_trainable(k, patterns)}
    frozen = {k: v for k, v in flat.items() if k not in trainable}
    loss, _, g = layerwise_grads(arch, **how)[0](trainable, frozen, ids)
    return loss, g


def tune(flat: dict, arch: dict, hp: dict, ids, n_steps: int, *, given=None,
         **how) -> dict:
    """Follow the first ``n_steps`` steps from the initial weights, the same
    document every step: loss, its gradient in the trainable leaves,
    global-norm clipping and AdamW (``deepseek_v32.make_update``, the other
    token cells'). ``given``: per step, per layer, ``{"mask", "experts"}``
    to take as data. ``chosen`` is what the FIRST step's forward used,
    ``chosen_own`` what the reference chooses for itself at the initial
    weights. The frozen leaves move to the host and ``flat`` is EMPTIED (so
    that the caller's copy on the device is freed)."""
    pats = hp["trainable_modules"]
    trainable = {k: jnp.array(v, jnp.float32) for k, v in flat.items()
                 if is_trainable(k, pats)}  # copies: the update donates them
    frozen = {}
    for k in list(flat):
        v = flat.pop(k)
        if k not in trainable:
            frozen[k] = np.asarray(v)
    grads, choose = layerwise_grads(arch, **how)
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
