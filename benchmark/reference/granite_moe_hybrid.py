"""Plain float32 reference of Granite-4.0-H-Small's hybrid block
(``model_type: granitemoehybrid``), its next-token loss and the Stage-1
tuning step — written from the published description
(https://huggingface.co/ibm-granite/granite-4.0-h-small/blob/main/config.json
and the Mamba-2 recurrence), importing nothing of the program.

Straightforward ``jax.numpy`` under ``jax.default_matmul_precision("highest")``.
**The state-space layer is the recurrence itself, token by token**
(``lax.scan`` over t: h_t = exp(dt_t A) h_{t-1} + dt_t x_t (x) B_t,
y_t = h_t C_t + D x_t) — no chunk, no matrix form, so it shares no
algorithm with the program's chunked scan; it is rematerialised in blocks of
``scan_block`` tokens so that its backward fits. Attention is a full-row
softmax over all earlier keys, one head at a time, in blocks of query rows;
the experts are a dense loop over the held experts, every expert applied to
every token and weighed by its gate (0 where the token was not routed to
it); a sort for the top-k. ``row_block`` cuts per-token work into blocks of
rows and ``remat`` recomputes pieces in the backward pass, so that the
published widths fit one chip; neither changes a number's definition.

  h0 = embedding_multiplier * E[ids]
  layer i (kind layer_types[i]): x += residual_multiplier * Mixer(RMSNorm(x));
      y = RMSNorm(x); x += residual_multiplier * (Experts(y) + Shared(y))
  mamba: [z | x | B | dt] = W_in u, C = W_c u; (x, B, C) <- silu(conv4 + b);
      dt = softplus(dt + dt_bias); A = -exp(A_log); the recurrence above per
      head; g = y * silu(z); out = W_out (g * rsqrt(mean g^2 + eps) * w)
  attention: softmax(q . k * attention_multiplier) v over keys <= t, no
      positional encoding; query head i reads key / value head i // group
  experts: l = W_r y; the K largest; gates = softmax over those K;
      sum gate_e W_down,e (silu(W_gate,e y) * W_up,e y); + the shared expert
  logits = RMSNorm(x) E^T / logits_scaling; loss = mean next-token
      cross-entropy

It takes the chip's share as data: ``arch["experts_held"]`` /
``["heads_held"]`` / ``["mamba_heads_held"]`` ``(first, count)``; weights by
name, expert matrices stacked over the experts held, the Mamba leaves
holding the held heads' columns. The gated norm's mean square is taken over
the channels HELD (what one chip of the deployment has without an exchange);
``mamba_scan`` / ``mamba_out`` split at that statistic so that a test can
hand every share the sum over all of them.

``operand`` below float32 is the CONTROL: both operands of every matrix
product are rounded to that dtype first (in the recurrence: dt x, B and C).
``fault`` plants one of ``FAULTS``. ``given`` hands the reference a run's
experts a token as data, in place of its own top-k (a near-tie flips under
bfloat16 and a flipped expert is another function of the weights); its
gates are the reference's own, from its logits.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from benchmark.reference.deepseek_v32 import (
    Weights,
    _maybe_remat,
    _Nx,
    _rms_norm,
    _row_blocks,
    _silu,
    _swiglu,
    is_trainable,
    make_update,
)

FAULTS = (
    "no_carry",        # the state is reset at every chunk's first token
    "no_conv",         # the depthwise conv left out: silu(x + b)
    "no_softplus",     # dt + dt_bias taken as the step size
    "top9",            # nine experts a token
    "gates_over_all",  # gates from a softmax over all 72 logits
    "residual_one",    # residual_multiplier 1
    "half_document",   # the second half of the document left out of the loss
)
ARCH_KEYS = (
    "hidden_size", "intermediate_size", "shared_intermediate_size",
    "num_hidden_layers", "layer_types", "attention_multiplier",
    "embedding_multiplier", "residual_multiplier", "logits_scaling",
    "mamba_d_head", "mamba_d_state", "mamba_d_conv", "mamba_chunk_size",
    "num_experts_per_tok", "vocab_size", "rms_norm_eps",
)
SCAN_BLOCK = 256  # tokens of the recurrence recomputed together


def arch_from_config(config: dict) -> dict:
    """The keys the reference reads, from a configuration file: the
    published keys at the top level (HELD counts where ``reduced`` says so)
    and its ``deployment``."""
    arch = {k: config[k] for k in ARCH_KEYS}
    dep = config["deployment"]
    arch["num_local_experts"] = dep["num_local_experts_published"]
    arch["head_dim"] = (config["hidden_size"]
                        // dep["num_attention_heads_published"])
    for key, held in (("experts_held", "num_local_experts"),
                      ("heads_held", "num_attention_heads"),
                      ("kv_heads_held", "num_key_value_heads"),
                      ("mamba_heads_held", "mamba_n_heads")):
        arch[key] = tuple(dep[key])
        assert arch[key][1] == config[held], key
    return arch


# ------------------------------------------------------------- Mamba-2 mixer


def recurrence(x_dt, decay_log, b, c, *, reset_every=None,
               scan_block=SCAN_BLOCK):
    """y_t = h_t c_t with h_t = exp(decay_log_t) h_{t-1} + x_dt_t (x) b_t,
    h_{-1} = 0, one token at a time: ``x_dt`` (T, H, P), ``decay_log``
    (T, H), ``b``, ``c`` (T, N) → ``y`` (T, H, P), last state (H, P, N).
    ``reset_every``: the planted fault — the state is zeroed before every
    token whose position is a multiple of it."""
    t_len, heads, width = x_dt.shape
    blk = scan_block if t_len % scan_block == 0 else t_len
    pos = jnp.arange(t_len)

    def step(h, inp):
        xt, at, bt, ct, t = inp
        if reset_every:
            h = jnp.where(t % reset_every == 0, 0.0, h)
        h = (jnp.exp(at)[:, None, None] * h
             + xt[:, :, None] * bt[None, None, :])
        return h, jnp.sum(h * ct[None, None, :], axis=-1)

    @jax.checkpoint
    def block(h, inp):
        return jax.lax.scan(step, h, inp)

    cut = lambda v: v.reshape((t_len // blk, blk) + v.shape[1:])  # noqa: E731
    last, y = jax.lax.scan(
        block, jnp.zeros((heads, width, b.shape[-1]), jnp.float32),
        tuple(map(cut, (x_dt, decay_log, b, c, pos))))
    return y.reshape(t_len, heads, width), last


def causal_conv(x, kernel, bias):
    """out[t] = sum_j kernel[j] x[t - (W - 1) + j] + bias, x[<0] = 0."""
    width, t_len = kernel.shape[0], x.shape[0]
    padded = jnp.pad(x, ((width - 1, 0), (0, 0)))
    return sum(kernel[j].astype(jnp.float32) * padded[j:j + t_len]
               for j in range(width)) + bias.astype(jnp.float32)


def mamba_scan(W, arch, nx, u, fault=None):
    """The held heads' gated scan outputs ``g = y * silu(z)`` (T, d held),
    their sum of squares (T, 1) and, for the last ``mamba_chunk_size``
    tokens, the part of the scan's outputs that comes from the state before
    them (tokens, H, P), for the normed ``u``."""
    t_len = u.shape[0]
    mh, hp, n = arch["mamba_heads_held"][1], arch["mamba_d_head"], arch["mamba_d_state"]
    d = mh * hp
    zxbdt = nx.mm(u, W("in_proj/kernel"))
    z, dt = zxbdt[:, :d], zxbdt[:, 2 * d + n:]
    xbc = jnp.concatenate([zxbdt[:, d:2 * d + n],
                           nx.mm(u, W("in_proj_c/kernel"))], axis=-1)
    if fault == "no_conv":
        xbc = _silu(xbc + W("conv/bias").astype(jnp.float32))
    else:
        xbc = _silu(causal_conv(xbc, W("conv/kernel"), W("conv/bias")))
    x = xbc[:, :d].reshape(t_len, mh, hp)
    b, c = xbc[:, d:d + n], xbc[:, d + n:]
    dt = dt + W("dt_bias").astype(jnp.float32)
    if fault != "no_softplus":
        dt = jax.nn.softplus(dt)
    a = -jnp.exp(W("A_log").astype(jnp.float32))
    scanned = (nx.r(x * dt[..., None]), dt * a[None, :], nx.r(b), nx.r(c))
    y, _ = recurrence(
        *scanned,
        reset_every=arch["mamba_chunk_size"] if fault == "no_carry" else None)
    # what the last mamba_chunk_size tokens' outputs owe to the state before
    # them: the recurrence is linear in its state, so the same tokens from a
    # zero state give the rest (all of it, exactly, where the state is reset)
    tail = max(t_len - arch["mamba_chunk_size"], 0)
    handed = y[tail:] - recurrence(*(v[tail:] for v in scanned))[0]
    y = y + W("D").astype(jnp.float32)[None, :, None] * x
    g = y.reshape(t_len, d) * _silu(z)
    return g, jnp.sum(g * g, axis=-1, keepdims=True), handed


def mamba_out(W, arch, nx, g, mean_square):
    """The gated norm GIVEN the mean square over all inner channels, then
    the held rows of ``out_proj``."""
    y = (g * jax.lax.rsqrt(mean_square + arch["rms_norm_eps"])
         * W("norm/scale").astype(jnp.float32))
    return nx.mm(y, W("out_proj/kernel"))


# ---------------------------------------------------------------- attention


def attention_part(W, arch, nx, u, *, remat=False, row_block=None):
    """The held query heads' part of the attention output."""
    t_len = u.shape[0]
    hq, hkv, hd = arch["heads_held"][1], arch["kv_heads_held"][1], arch["head_dim"]
    group, scale = hq // hkv, arch["attention_multiplier"]
    q = nx.mm(u, W("q_proj/kernel")).reshape(t_len, hq, hd)
    k = nx.mm(u, W("k_proj/kernel")).reshape(t_len, hkv, hd).transpose(1, 0, 2)
    v = nx.mm(u, W("v_proj/kernel")).reshape(t_len, hkv, hd).transpose(1, 0, 2)

    def queries(q, pos):
        """A block of query rows against all keys, one head at a time."""
        causal = jnp.arange(t_len)[None, :] <= pos[:, None]

        def head(qh, i):
            s = nx.mm(qh, k[i // group].T) * scale
            p = jax.nn.softmax(jnp.where(causal, s, -jnp.inf), axis=-1)
            return nx.mm(p, v[i // group])

        o = jax.lax.map(lambda a: _maybe_remat(head, remat)(*a),
                        (q.transpose(1, 0, 2), jnp.arange(hq)))
        return o.transpose(1, 0, 2).reshape(q.shape[0], hq * hd)

    o = _row_blocks(queries, (q, jnp.arange(t_len)), row_block, remat)
    return nx.mm(o, W("o_proj/kernel"))


# ------------------------------------------------------------- expert layer


def routing(W, arch, nx, y, fault=None, given=None):
    """(experts (T, K), gates (T, K)) over all routed experts; ``given``
    experts are taken in place of the selection, their gates from the
    logits here."""
    k = arch["num_experts_per_tok"] - (1 if fault == "top9" else 0)
    logits = nx.mm(y, W("kernel"))
    experts = (jnp.argsort(-logits, axis=-1)[:, :k] if given is None
               else given[:, :k])
    if fault == "gates_over_all":
        return experts, jnp.take_along_axis(jax.nn.softmax(logits, axis=-1),
                                            experts, axis=-1)
    return experts, jax.nn.softmax(
        jnp.take_along_axis(logits, experts, axis=-1), axis=-1)


def moe_parts(W, arch, nx, y, *, fault=None, remat=False, row_block=None,
              given=None):
    """(held experts' part, shared expert's part, experts chosen)."""
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

    routed = _row_blocks(block, (y, gate), row_block, remat)
    sh = W.at("shared")
    shared = _row_blocks(lambda yb: _swiglu(
        nx, yb, sh("gate_proj/kernel"), sh("up_proj/kernel"),
        sh("down_proj/kernel")), (y,), row_block, remat)
    return routed, shared, experts


# ------------------------------------------------------------------ forward


def mixer(W, arch, nx, x, *, fault=None, remat=False, row_block=None):
    """The first half of a layer (its kind from the leaves it holds):
    ``x + residual_multiplier * Mixer(RMSNorm(x))`` and the root mean square
    of what the scan's last ``mamba_chunk_size`` outputs owe to the state
    before them (None in an attention layer)."""
    rm = 1.0 if fault == "residual_one" else arch["residual_multiplier"]
    u = _rms_norm(x, W("input_norm/scale"), arch["rms_norm_eps"])
    if W.has("mamba/in_proj/kernel"):
        M = W.at("mamba")
        g, sum_sq, handed = mamba_scan(M, arch, nx, u, fault)
        mixed = mamba_out(M, arch, nx, g, sum_sq / g.shape[-1])
        return x + rm * mixed, jnp.sqrt(jnp.mean(handed ** 2))
    return x + rm * attention_part(W.at("attn"), arch, nx, u, remat=remat,
                                   row_block=row_block), None


def layer(W, arch, nx, x, *, fault=None, remat=False, row_block=None,
          given=None):
    """One layer: ``(x_out, experts chosen, the held experts' part over the
    shared expert's in root mean square, :func:`mixer`'s root mean square
    of the handed-over part or None)``."""
    rm = 1.0 if fault == "residual_one" else arch["residual_multiplier"]
    x, state_rms = mixer(W, arch, nx, x, fault=fault, remat=remat,
                         row_block=row_block)
    y = _rms_norm(x, W("post_norm/scale"), arch["rms_norm_eps"])
    routed, shared, experts = moe_parts(W, arch, nx, y, fault=fault,
                                        remat=remat, row_block=row_block,
                                        given=given)
    ratio = jnp.sqrt(jnp.sum(routed ** 2) / jnp.sum(shared ** 2))
    return x + rm * (routed + shared), experts, ratio, state_rms


def _next_token_nll(W, arch, nx, x, ids, remat, row_block, fault=None):
    """Mean next-token cross-entropy from the last layer's output, through
    the tied matrix."""
    def nll(xb, target):
        y = _rms_norm(xb, W("final_norm/scale"), arch["rms_norm_eps"])
        logits = nx.mm(y, W("embed/embedding").T) / arch["logits_scaling"]
        return (jax.nn.logsumexp(logits, axis=-1)
                - jnp.take_along_axis(logits, target[:, None], -1)[:, 0])

    per_token = _row_blocks(nll, (x, jnp.roll(ids, -1)), row_block, remat)
    if fault == "half_document":
        return jnp.mean(per_token[:ids.shape[0] // 2])
    return jnp.mean(per_token[:-1])


def _embed(W, arch, ids):
    return (arch["embedding_multiplier"]
            * jnp.asarray(W("embed/embedding"))[ids].astype(jnp.float32))


def logits(flat: dict, arch: dict, ids, *, operand="float32", fault=None):
    """(T, vocabulary held) logits of one document."""
    nx = _Nx(operand)
    with jax.default_matmul_precision("highest"):
        W = Weights(flat)
        x = _embed(W, arch, ids)
        for i in range(arch["num_hidden_layers"]):
            x = layer(W.at(f"layers_{i}"), arch, nx, x, fault=fault)[0]
        y = _rms_norm(x, W("final_norm/scale"), arch["rms_norm_eps"])
        return nx.mm(y, W("embed/embedding").T) / arch["logits_scaling"]


def layerwise_grads(arch: dict, *, operand="float32", fault=None, remat=False,
                    row_block=None):
    """``grads(trainable, frozen, ids, given) -> (loss, choices, grads)``
    and ``choose(trainable, frozen, ids) -> choices`` with the chain rule
    applied layer by layer in Python: ``layer`` is jitted once per kind of
    layer, and ``frozen`` may live on the HOST (numpy arrays) — a layer's
    weights are on the device only while it runs. ``choices``: per layer
    ``{"experts", "routed_over_shared", "state_rms"}``."""
    assert fault is None or fault in FAULTS, fault
    nx = _Nx(operand)
    kw = dict(fault=fault, remat=remat, row_block=row_block)

    def run_layer(tr, fr, x, given):
        with jax.default_matmul_precision("highest"):
            return layer(Weights({**fr, **tr}, ""), arch, nx, x, given=given,
                         **kw)

    @jax.jit
    def fwd(tr, fr, x, given):
        x, experts, ratio, state_rms = run_layer(tr, fr, x, given)
        return x, {"experts": experts, "routed_over_shared": ratio,
                   "state_rms": state_rms}

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

    def forward(trainable, frozen, ids, given):
        x, xs, choices = _embed(Weights(frozen), arch, ids), [], []
        for i in range(n):
            pre = f"params/layers_{i}/"
            xs.append(x)
            x, chosen = fwd(part(trainable, pre), part(frozen, pre), x,
                            given[i]["experts"] if given else None)
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
        for i in reversed(range(n)):
            pre = f"params/layers_{i}/"
            dtr, dx = bwd(part(trainable, pre), part(frozen, pre), xs.pop(),
                          dx, given[i]["experts"] if given else None)
            g.update({pre + k: v for k, v in dtr.items()})
        return loss, choices, g

    return grads, choose


def tune(flat: dict, arch: dict, hp: dict, ids, n_steps: int, *, given=None,
         **how) -> dict:
    """Follow the first ``n_steps`` steps from the initial weights, the same
    document every step: loss, its gradient in the trainable leaves,
    global-norm clipping and AdamW (``deepseek_v32.make_update``, the other
    token cell's). ``given``: per step, per layer, ``{"experts": (T, K)}``
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
