"""Plain float32 reference of Command A+'s block (``model_type:
cohere2_moe``), its next-token loss and the Stage-1 tuning step — written
from the published description
(https://huggingface.co/CohereLabs/command-a-plus-05-2026/blob/main/config.json),
importing nothing of the program.

Straightforward ``jax.numpy`` under ``jax.default_matmul_precision("highest")``.
Attention is a full-row softmax over ALL keys under an explicit (rows, keys)
mask — ``s <= t``, and in a sliding layer ``s > t - window`` — one head at a
time, in blocks of query rows: no tile is skipped, no band is walked. The
four shared experts are computed one by one and averaged; the routed experts
are a dense loop over the held experts, every expert applied to every token
and weighed by its gate (0 where the token was not routed to it); a sort for
the top-k. ``row_block`` cuts per-token work into blocks of rows and
``remat`` recomputes pieces in the backward pass, so that the published
widths fit one chip; neither changes a number's definition.

  h0 = E[ids]
  layer i: u = LN(x) (mean-centred, a scale, no bias);
      A = W_o softmax(q k^T / sqrt(head_dim) + mask) v, query head i on key /
      value head i // group; ``sliding_attention``: rotary on q and k
      (adjacent pairs of dims, theta ``rope_theta``, the whole head) and
      0 <= t - s < ``sliding_window``; ``full_attention``: no positions, s <= t;
      s = sigmoid(W_r u), the K largest, g = s_sel / sum s_sel;
      F = sum g_e E_e(u) + (1 / n_shared) sum_j S_j(u), E and S gated silu
      feed-forwards; x <- x + A + F (ONE residual add)
  logits = LN_f(x) E^T * logit_scale; loss = mean next-token cross-entropy

It takes the chip's share as data: ``arch["experts_held"]`` /
``["heads_held"]`` / ``["kv_heads_held"]`` / ``["shared_columns_held"]``
``(first, count)``; weights by name, expert matrices stacked over the experts
held, ``shared/*`` holding the held columns of the shared experts' inner
width laid side by side (expert j owns columns j * width .. (j + 1) * width:
the reference cuts the held range at those edges and runs each expert's part
on its own).

``operand`` below float32 is the CONTROL: both operands of every matrix
product are rounded to that dtype first. ``fault`` plants one of ``FAULTS``.
``given`` hands the reference a run's experts a token as data, in place of
its own top-k (a near-tie flips under bfloat16 and a flipped expert is
another function of the weights); its gates are the reference's own.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from benchmark.reference.deepseek_v32 import (
    Weights,
    _maybe_remat,
    _Nx,
    _rotate_halves,
    _rotate_pairs,
    _row_blocks,
    _swiglu,
    is_trainable,
    make_update,
)

FAULTS = (
    "no_window",              # a sliding layer sees every earlier key
    "window_4095",            # the window one key short
    "rope_on_full_layers",    # rotary on the full layers too
    "rope_halves_not_pairs",  # rotary pairs dim i with i + head_dim / 2
    "sequential_block",       # x += A(LN(x)); then x += F(LN(x)): two residuals
    "rms_norm",               # no mean-centring in the norms
    "shared_sum_not_mean",    # the shared experts' outputs summed
    "gates_not_normalised",   # gates the sigmoid scores as they are
    "top7",                   # one expert a token fewer
)
ARCH_KEYS = (
    "hidden_size", "intermediate_size", "head_dim", "num_hidden_layers",
    "layer_types", "sliding_window", "rope_theta", "num_experts_per_tok",
    "layer_norm_eps", "logit_scale", "vocab_size",
)
SLIDING = "sliding_attention"


def arch_from_config(config: dict) -> dict:
    """The keys the reference reads, from a configuration file: the
    published keys at the top level (HELD counts where ``reduced`` says so)
    and its ``deployment``."""
    arch = {k: config[k] for k in ARCH_KEYS}
    dep = config["deployment"]
    arch["num_experts"] = dep["num_experts_published"]
    arch["num_shared_experts"] = dep["num_shared_experts_published"]
    for key, held in (("experts_held", "num_experts"),
                      ("heads_held", "num_attention_heads"),
                      ("kv_heads_held", "num_key_value_heads")):
        arch[key] = tuple(dep[key])
        assert arch[key][1] == config[held], key
    arch["shared_columns_held"] = tuple(dep["shared_columns_held"])
    return arch


def _layer_norm(x, scale, eps, fault=None):
    if fault != "rms_norm":
        x = x - jnp.mean(x, axis=-1, keepdims=True)
    return (x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps)
            * scale.astype(jnp.float32))


def rope_angles(arch: dict, t_len: int):
    dim = arch["head_dim"]
    freqs = 1.0 / float(arch["rope_theta"]) ** (
        np.arange(0, dim, 2, dtype=np.float64) / dim)
    return jnp.asarray(np.arange(t_len)[:, None] * freqs[None, :], jnp.float32)


# ---------------------------------------------------------------- attention


def attention_part(W, arch, nx, u, kind, *, fault=None, remat=False,
                   row_block=None):
    """The held query heads' part of the attention output of a layer of
    ``kind`` for the normed ``u``."""
    t_len = u.shape[0]
    hq, hkv, hd = arch["heads_held"][1], arch["kv_heads_held"][1], arch["head_dim"]
    group, scale = hq // hkv, hd ** -0.5
    sliding = kind == SLIDING
    roped = sliding or fault == "rope_on_full_layers"
    window = None
    if sliding and fault != "no_window":
        window = arch["sliding_window"] - (1 if fault == "window_4095" else 0)
    rotate = _rotate_halves if fault == "rope_halves_not_pairs" else _rotate_pairs
    angles = rope_angles(arch, t_len)
    q = nx.mm(u, W("q_proj/kernel")).reshape(t_len, hq, hd)
    k = nx.mm(u, W("k_proj/kernel")).reshape(t_len, hkv, hd).transpose(1, 0, 2)
    v = nx.mm(u, W("v_proj/kernel")).reshape(t_len, hkv, hd).transpose(1, 0, 2)
    if roped:
        k = jax.vmap(lambda kh: rotate(kh, angles))(k)

    def queries(q, pos):
        """A block of query rows against all keys, one head at a time."""
        keys = jnp.arange(t_len)[None, :]
        seen = keys <= pos[:, None]
        if window is not None:
            seen = seen & (keys > pos[:, None] - window)

        def head(qh, i):
            if roped:
                qh = rotate(qh, angles[pos])
            s = nx.mm(qh, k[i // group].T) * scale
            p = jax.nn.softmax(jnp.where(seen, s, -jnp.inf), axis=-1)
            return nx.mm(p, v[i // group])

        o = jax.lax.map(lambda a: _maybe_remat(head, remat)(*a),
                        (q.transpose(1, 0, 2), jnp.arange(hq)))
        return o.transpose(1, 0, 2).reshape(q.shape[0], hq * hd)

    o = _row_blocks(queries, (q, jnp.arange(t_len)), row_block, remat)
    return nx.mm(o, W("o_proj/kernel"))


# ------------------------------------------------------------- expert layer


def routing(W, arch, nx, u, fault=None, given=None):
    """(experts (T, K), gates (T, K)) over all routed experts; ``given``
    experts are taken in place of the selection, their gates from the
    scores here."""
    k = arch["num_experts_per_tok"] - (1 if fault == "top7" else 0)
    s = jax.nn.sigmoid(nx.mm(u, W("kernel")))
    experts = (jnp.argsort(-s, axis=-1)[:, :k] if given is None
               else given[:, :k])
    picked = jnp.take_along_axis(s, experts, axis=-1)
    if fault == "gates_not_normalised":
        return experts, picked
    return experts, picked / jnp.sum(picked, axis=-1, keepdims=True)


def shared_part(W, arch, nx, u, *, fault=None, remat=False, row_block=None):
    """The held columns' part of the shared experts' average: each shared
    expert's own columns on their own, one expert after the other, summed
    and divided by the number of shared experts."""
    width, n = arch["intermediate_size"], arch["num_shared_experts"]
    c0, cn = arch["shared_columns_held"]
    wg, wu, wd = (W(f"shared/{name}/kernel")
                  for name in ("gate_proj", "up_proj", "down_proj"))
    total = jnp.zeros(u.shape, jnp.float32)
    for j in range(n):
        lo, hi = max(j * width, c0) - c0, min((j + 1) * width, c0 + cn) - c0
        if hi <= lo:
            continue  # this share holds none of expert j's columns
        total = total + _row_blocks(
            lambda ub, lo=lo, hi=hi: _swiglu(nx, ub, wg[:, lo:hi],
                                             wu[:, lo:hi], wd[lo:hi]),
            (u,), row_block, remat)
    return total if fault == "shared_sum_not_mean" else total / n


def moe_parts(W, arch, nx, u, *, fault=None, remat=False, row_block=None,
              given=None):
    """(held experts' part, shared experts' part, experts chosen)."""
    e0, en = arch["experts_held"]
    experts, gates = routing(W.at("router"), arch, nx, u, fault, given)
    wg, wu, wd = (W(f"experts/{n}/kernel")
                  for n in ("gate_proj", "up_proj", "down_proj"))
    # gate[t, e]: expert e's gate for token t, 0 where t was not routed to it
    gate = jnp.sum(jnp.where(
        experts[:, None, :] == (e0 + jnp.arange(en))[None, :, None],
        gates[:, None, :], 0.0), axis=-1)

    def block(ub, gate_b):
        def one(e, acc):
            return acc + gate_b[:, e, None] * _swiglu(nx, ub, wg[e], wu[e], wd[e])

        return jax.lax.fori_loop(0, en, _maybe_remat(one, remat),
                                 jnp.zeros_like(ub))

    routed = _row_blocks(block, (u, gate), row_block, remat)
    shared = shared_part(W, arch, nx, u, fault=fault, remat=remat,
                         row_block=row_block)
    return routed, shared, experts


# ------------------------------------------------------------------ forward


def layer(W, arch, nx, x, kind, *, fault=None, remat=False, row_block=None,
          given=None):
    """One layer of ``kind``: ``(x_out, experts chosen, the held experts'
    part over the shared experts' in root mean square)``."""
    norm = lambda v: _layer_norm(v, W("input_norm/scale"),  # noqa: E731
                                 arch["layer_norm_eps"], fault)
    u = norm(x)
    attended = attention_part(W.at("attn"), arch, nx, u, kind, fault=fault,
                              remat=remat, row_block=row_block)
    if fault == "sequential_block":
        x = x + attended
        u, attended = norm(x), 0.0
    routed, shared, experts = moe_parts(W, arch, nx, u, fault=fault,
                                        remat=remat, row_block=row_block,
                                        given=given)
    ratio = jnp.sqrt(jnp.sum(routed ** 2) / jnp.sum(shared ** 2))
    return x + attended + routed + shared, experts, ratio


def _final_logits(W, arch, nx, x, fault=None):
    y = _layer_norm(x, W("final_norm/scale"), arch["layer_norm_eps"], fault)
    return nx.mm(y, W("embed/embedding").T) * arch["logit_scale"]


def _next_token_nll(W, arch, nx, x, ids, remat, row_block, fault=None):
    """Mean next-token cross-entropy from the last layer's output, through
    the tied matrix."""
    def nll(xb, target):
        logits = _final_logits(W, arch, nx, xb, fault)
        return (jax.nn.logsumexp(logits, axis=-1)
                - jnp.take_along_axis(logits, target[:, None], -1)[:, 0])

    per_token = _row_blocks(nll, (x, jnp.roll(ids, -1)), row_block, remat)
    return jnp.mean(per_token[:-1])


def _embed(W, ids):
    return jnp.asarray(W("embed/embedding"))[ids].astype(jnp.float32)


def logits(flat: dict, arch: dict, ids, *, operand="float32", fault=None):
    """(T, vocabulary held) logits of one document."""
    nx = _Nx(operand)
    with jax.default_matmul_precision("highest"):
        W = Weights(flat)
        x = _embed(W, ids)
        for i, kind in enumerate(arch["layer_types"]):
            x = layer(W.at(f"layers_{i}"), arch, nx, x, kind, fault=fault)[0]
        return _final_logits(W, arch, nx, x, fault)


def layerwise_grads(arch: dict, *, operand="float32", fault=None, remat=False,
                    row_block=None):
    """``grads(trainable, frozen, ids, given) -> (loss, choices, grads)``
    and ``choose(trainable, frozen, ids) -> choices`` with the chain rule
    applied layer by layer in Python: ``layer`` is jitted once per kind of
    layer, and ``frozen`` may live on the HOST (numpy arrays) — a layer's
    weights are on the device only while it runs. ``choices``: per layer
    ``{"experts", "routed_over_shared"}``."""
    assert fault is None or fault in FAULTS, fault
    nx = _Nx(operand)
    kw = dict(fault=fault, remat=remat, row_block=row_block)

    def run_layer(tr, fr, x, given, kind):
        with jax.default_matmul_precision("highest"):
            return layer(Weights({**fr, **tr}, ""), arch, nx, x, kind,
                         given=given, **kw)

    @functools.partial(jax.jit, static_argnums=4)
    def fwd(tr, fr, x, given, kind):
        x, experts, ratio = run_layer(tr, fr, x, given, kind)
        return x, {"experts": experts, "routed_over_shared": ratio}

    @functools.partial(jax.jit, static_argnums=5)
    def bwd(tr, fr, x, dx_out, given, kind):
        _, pull = jax.vjp(lambda tr, x: run_layer(tr, fr, x, given, kind)[0],
                          tr, x)
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

    kinds = tuple(arch["layer_types"])

    def forward(trainable, frozen, ids, given):
        x, xs, choices = _embed(Weights(frozen), ids), [], []
        for i, kind in enumerate(kinds):
            pre = f"params/layers_{i}/"
            xs.append(x)
            x, chosen = fwd(part(trainable, pre), part(frozen, pre), x,
                            given[i]["experts"] if given else None, kind)
            choices.append(chosen)
        return x, xs, choices

    def choose(trainable, frozen, ids):
        return forward(trainable, frozen, ids, None)[2]

    def grads(trainable, frozen, ids, given=None):
        x, xs, choices = forward(trainable, frozen, ids, given)
        top = {k: frozen["params/" + k] for k in ("final_norm/scale",
                                                  "embed/embedding")}
        loss, dx = head(top, x, ids)
        g = {}
        for i in reversed(range(len(kinds))):
            pre = f"params/layers_{i}/"
            dtr, dx = bwd(part(trainable, pre), part(frozen, pre), xs.pop(),
                          dx, given[i]["experts"] if given else None, kinds[i])
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
    token cells'). ``given``: per step, per layer, ``{"experts": (T, K)}``
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
