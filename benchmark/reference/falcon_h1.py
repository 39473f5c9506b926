"""Plain float32 reference of Falcon-H1-34B's parallel hybrid block
(``model_type: falcon_h1``), its next-token loss and the Stage-1 tuning step
— written from the published configuration
(https://huggingface.co/tiiuae/Falcon-H1-34B-Instruct/blob/main/config.json)
and the Mamba-2 recurrence, importing nothing of the program.

Straightforward ``jax.numpy`` under ``jax.default_matmul_precision("highest")``.
**The state-space layer is the recurrence itself, token by token, per
group** (``lax.scan`` over t: h_t = exp(dt_t A) h_{t-1} + dt_t x_t (x)
B_{t,g(h)}, y_t = h_t C_{t,g(h)} + D x_t, head h reading group g(h) = h //
(H / G)) — no chunk, no matrix form; it is rematerialised in blocks of
``SCAN_BLOCK`` tokens so that its backward fits. Attention is a full-row
softmax over all earlier keys, one head at a time, in blocks of query rows;
the feed-forward runs in blocks of rows. ``row_block`` and ``remat`` cut the
work so that the published widths fit one chip; neither changes a number's
definition. Every multiplier multiplies where the published modelling code
puts it, literally:

  h0 = embedding_multiplier * E[ids]
  layer: u = RMSNorm(x)
      M = ssm_out_multiplier * Mixer(ssm_in_multiplier * u)
      A = attention_out_multiplier * Attn(attention_in_multiplier * u)
      x <- x + M + A; x <- x + F(RMSNorm(x))
  mixer: [z | x | B | dt] = W_in v, C = W_c v, each times its
      ssm_multipliers entry (z, x, B, C, dt); (x, B, C) <- silu(conv4 + b);
      dt = softplus(dt + dt_bias); A = -exp(A_log); the recurrence above;
      g = y * silu(z); out = W_out (g * rsqrt(mean over the group's
      d_ssm / G channels of g^2 + eps) * w)
  attention: q = W_q v; k = key_multiplier * W_k v; v = W_v v; rotary
      (rotate-half, theta rope_theta) on q and k; softmax(q . k /
      sqrt(head_dim)) over keys <= t; query head i reads key / value head
      i // group; W_o
  F(y) = down_mult * W_down (silu(gate_mult * W_gate y) * W_up y)
  logits = lm_head_multiplier * RMSNorm_f(x) W_head; loss = mean next-token
      cross-entropy

``operand`` below float32 is the CONTROL: both operands of every matrix
product are rounded to that dtype first (in the recurrence: dt x, B and C).
``fault`` plants one of ``FAULTS``.
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
    is_trainable,
    make_update,
)
from benchmark.reference.granite_moe_hybrid import causal_conv

FAULTS = (
    "one_group",       # every head reads group 0's B and C
    "norm_ungrouped",  # the gated norm's mean square over all d_ssm channels
    "no_carry",        # the state is reset at every chunk's first token
    "sequential",      # attention reads the residual after the mixer
    "mup_off",         # the in-projection's segments unscaled
    "attn_out_one",    # attention_out_multiplier 1
)
ARCH_KEYS = (
    "hidden_size", "intermediate_size", "num_hidden_layers",
    "num_attention_heads", "num_key_value_heads", "head_dim", "rope_theta",
    "attention_in_multiplier", "attention_out_multiplier", "key_multiplier",
    "embedding_multiplier", "lm_head_multiplier", "mlp_multipliers",
    "ssm_in_multiplier", "ssm_multipliers", "ssm_out_multiplier",
    "mamba_chunk_size", "mamba_d_conv", "mamba_d_head", "mamba_d_ssm",
    "mamba_d_state", "mamba_n_groups", "mamba_n_heads", "rms_norm_eps",
    "vocab_size",
)
SCAN_BLOCK = 256  # tokens of the recurrence recomputed together


def arch_from_config(config: dict) -> dict:
    """The keys the reference reads, from a configuration file (the
    published keys; the layers and vocabulary rows HELD)."""
    return {k: (tuple(config[k]) if isinstance(config[k], list) else config[k])
            for k in ARCH_KEYS}


# ------------------------------------------------------------- Mamba-2 mixer


def recurrence(x_dt, decay_log, b, c, group_of, *, reset_every=None,
               scan_block=SCAN_BLOCK):
    """y_t = h_t c_{t,g(h)} with h_t = exp(decay_log_t) h_{t-1} + x_dt_t (x)
    b_{t,g(h)}, h_{-1} = 0, one token at a time: ``x_dt`` (T, H, P),
    ``decay_log`` (T, H), ``b``, ``c`` (T, G, N), ``group_of`` (H,) the
    group each head reads → ``y`` (T, H, P). ``reset_every``: the planted
    fault — the state is zeroed before every token whose position is a
    multiple of it."""
    t_len, heads, width = x_dt.shape
    blk = scan_block if t_len % scan_block == 0 else t_len
    pos = jnp.arange(t_len)

    def step(h, inp):
        xt, at, bt, ct, t = inp
        if reset_every:
            h = jnp.where(t % reset_every == 0, 0.0, h)
        h = (jnp.exp(at)[:, None, None] * h
             + xt[:, :, None] * bt[group_of][:, None, :])
        return h, jnp.sum(h * ct[group_of][:, None, :], axis=-1)

    @jax.checkpoint
    def block(h, inp):
        return jax.lax.scan(step, h, inp)

    cut = lambda v: v.reshape((t_len // blk, blk) + v.shape[1:])  # noqa: E731
    _, y = jax.lax.scan(
        block, jnp.zeros((heads, width, b.shape[-1]), jnp.float32),
        tuple(map(cut, (x_dt, decay_log, b, c, pos))))
    return y.reshape(t_len, heads, width)


def mixer_part(W, arch, nx, u, fault=None):
    """The mixer branch's contribution (T, h) after ``ssm_out_multiplier``
    and the root mean square of what the last ``mamba_chunk_size`` outputs
    of the scan owe to the state before them."""
    t_len = u.shape[0]
    d, mh, hp = arch["mamba_d_ssm"], arch["mamba_n_heads"], arch["mamba_d_head"]
    groups, n = arch["mamba_n_groups"], arch["mamba_d_state"]
    gn = groups * n
    mz, mx, mb, mc, mdt = ((1.0,) * 5 if fault == "mup_off"
                           else arch["ssm_multipliers"])
    v = u * arch["ssm_in_multiplier"]
    zxbdt = nx.mm(v, W("in_proj/kernel"))
    z = zxbdt[:, :d] * mz
    xbc = jnp.concatenate([zxbdt[:, d:2 * d] * mx,
                           zxbdt[:, 2 * d:2 * d + gn] * mb,
                           nx.mm(v, W("in_proj_c/kernel")) * mc], axis=-1)
    dt = zxbdt[:, 2 * d + gn:] * mdt
    xbc = _silu(causal_conv(xbc, W("conv/kernel"), W("conv/bias")))
    x = xbc[:, :d].reshape(t_len, mh, hp)
    b = xbc[:, d:d + gn].reshape(t_len, groups, n)
    c = xbc[:, d + gn:].reshape(t_len, groups, n)
    group_of = (jnp.zeros((mh,), jnp.int32) if fault == "one_group"
                else jnp.arange(mh) // (mh // groups))
    dt = jax.nn.softplus(dt + W("dt_bias").astype(jnp.float32))
    a = -jnp.exp(W("A_log").astype(jnp.float32))
    scanned = (nx.r(x * dt[..., None]), dt * a[None, :], nx.r(b), nx.r(c))
    chunk = arch["mamba_chunk_size"]
    y = recurrence(*scanned, group_of,
                   reset_every=chunk if fault == "no_carry" else None)
    # what the last chunk's outputs owe to the state before them: the
    # recurrence is linear in its state, so the same tokens from a zero
    # state give the rest
    tail = max(t_len - chunk, 0)
    handed = y[tail:] - recurrence(*(s[tail:] for s in scanned), group_of)
    y = y + W("D").astype(jnp.float32)[None, :, None] * x
    g = y.reshape(t_len, d) * _silu(z)
    norm_groups = 1 if fault == "norm_ungrouped" else groups
    gg = g.reshape(t_len, norm_groups, d // norm_groups)
    g = (gg * jax.lax.rsqrt(jnp.mean(gg * gg, axis=-1, keepdims=True)
                            + arch["rms_norm_eps"])).reshape(t_len, d)
    out = nx.mm(g * W("norm/scale").astype(jnp.float32), W("out_proj/kernel"))
    return arch["ssm_out_multiplier"] * out, jnp.sqrt(jnp.mean(handed ** 2))


# ---------------------------------------------------------------- attention


def rope_angles(arch: dict, t_len: int):
    """(T, head_dim / 2): position * theta^(-2 i / head_dim), in float64."""
    dim = arch["head_dim"]
    freqs = 1.0 / float(arch["rope_theta"]) ** (
        np.arange(0, dim, 2, dtype=np.float64) / dim)
    return jnp.asarray(np.arange(t_len)[:, None] * freqs[None, :], jnp.float32)


def _rotate_halves(x, angles):
    """(T, H, D): dims (i, i + D/2) of every head are the rotated pairs."""
    half = x.shape[-1] // 2
    a, b = x[..., :half], x[..., half:]
    cos, sin = jnp.cos(angles)[:, None, :], jnp.sin(angles)[:, None, :]
    return jnp.concatenate([a * cos - b * sin, a * sin + b * cos], axis=-1)


def attention_part(W, arch, nx, u, angles, *, fault=None, remat=False,
                   row_block=None):
    """The attention branch's contribution (T, h) after
    ``attention_out_multiplier``."""
    t_len = u.shape[0]
    hq, hkv, hd = (arch["num_attention_heads"], arch["num_key_value_heads"],
                   arch["head_dim"])
    group, scale = hq // hkv, hd ** -0.5
    v_in = u * arch["attention_in_multiplier"]
    q = _rotate_halves(nx.mm(v_in, W("q_proj/kernel")).reshape(t_len, hq, hd),
                       angles)
    k = arch["key_multiplier"] * nx.mm(v_in, W("k_proj/kernel")).reshape(
        t_len, hkv, hd)
    k = _rotate_halves(k, angles).transpose(1, 0, 2)
    v = nx.mm(v_in, W("v_proj/kernel")).reshape(t_len, hkv, hd).transpose(1, 0, 2)

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
    out_mult = 1.0 if fault == "attn_out_one" else arch["attention_out_multiplier"]
    return out_mult * nx.mm(o, W("o_proj/kernel"))


# ------------------------------------------------------------------ forward


def feed_forward(W, arch, nx, y, *, remat=False, row_block=None):
    gate_mult, down_mult = arch["mlp_multipliers"]

    def block(yb):
        hidden = (_silu(gate_mult * nx.mm(yb, W("gate_proj/kernel")))
                  * nx.mm(yb, W("up_proj/kernel")))
        return down_mult * nx.mm(hidden, W("down_proj/kernel"))

    return _row_blocks(block, (y,), row_block, remat)


def layer(W, arch, nx, x, angles, *, fault=None, remat=False, row_block=None):
    """One layer: ``(x_out, the scan's handed-over root mean square, the
    attention branch's contribution over the mixer's in root mean
    square)``."""
    eps = arch["rms_norm_eps"]
    u = _rms_norm(x, W("input_norm/scale"), eps)
    m, state_rms = mixer_part(W.at("mamba"), arch, nx, u, fault)
    if fault == "sequential":
        u = _rms_norm(x + m, W("input_norm/scale"), eps)
    a = attention_part(W.at("attn"), arch, nx, u, angles, fault=fault,
                       remat=remat, row_block=row_block)
    x = x + m + a
    y = _rms_norm(x, W("pre_ff_norm/scale"), eps)
    x = x + feed_forward(W.at("mlp"), arch, nx, y, remat=remat,
                         row_block=row_block)
    ratio = jnp.sqrt(jnp.mean(a ** 2)) / jnp.sqrt(jnp.mean(m ** 2))
    return x, state_rms, ratio


def _next_token_nll(W, arch, nx, x, ids, remat, row_block):
    """Mean next-token cross-entropy from the last layer's output."""
    def nll(xb, target):
        y = _rms_norm(xb, W("final_norm/scale"), arch["rms_norm_eps"])
        logits = arch["lm_head_multiplier"] * nx.mm(y, W("head/kernel"))
        return (jax.nn.logsumexp(logits, axis=-1)
                - jnp.take_along_axis(logits, target[:, None], -1)[:, 0])

    per_token = _row_blocks(nll, (x, jnp.roll(ids, -1)), row_block, remat)
    return jnp.mean(per_token[:-1])


def _embed(W, arch, ids):
    return (arch["embedding_multiplier"]
            * jnp.asarray(W("embed/embedding"))[ids].astype(jnp.float32))


def logits(flat: dict, arch: dict, ids, *, operand="float32", fault=None):
    """(T, vocabulary held) logits of one document."""
    nx = _Nx(operand)
    with jax.default_matmul_precision("highest"):
        W = Weights(flat)
        angles = rope_angles(arch, ids.shape[0])
        x = _embed(W, arch, ids)
        for i in range(arch["num_hidden_layers"]):
            x = layer(W.at(f"layers_{i}"), arch, nx, x, angles, fault=fault)[0]
        y = _rms_norm(x, W("final_norm/scale"), arch["rms_norm_eps"])
        return arch["lm_head_multiplier"] * nx.mm(y, W("head/kernel"))


def layerwise_grads(arch: dict, *, operand="float32", fault=None, remat=False,
                    row_block=None):
    """``grads(trainable, frozen, ids) -> (loss, readings, grads)`` with the
    chain rule applied layer by layer in Python: ``layer`` is jitted once,
    and ``frozen`` may live on the HOST (numpy arrays) — a layer's weights
    are on the device only while it runs, and each layer's input waits on
    the host for its backward (a float32 (T, hidden) is 640 MiB at the
    cell's size). ``readings``: per layer ``{"state_rms",
    "branch_rms_ratio"}``."""
    assert fault is None or fault in FAULTS, fault
    nx = _Nx(operand)
    kw = dict(fault=fault, remat=remat, row_block=row_block)

    def run_layer(tr, fr, x):
        with jax.default_matmul_precision("highest"):
            return layer(Weights({**fr, **tr}, ""), arch, nx, x,
                         rope_angles(arch, x.shape[0]), **kw)

    @jax.jit
    def fwd(tr, fr, x):
        x, state_rms, ratio = run_layer(tr, fr, x)
        return x, {"state_rms": state_rms, "branch_rms_ratio": ratio}

    @jax.jit
    def bwd(tr, fr, x, dx_out):
        _, pull = jax.vjp(lambda tr, x: run_layer(tr, fr, x)[0], tr, x)
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

    def grads(trainable, frozen, ids):
        x, xs, readings = _embed(Weights(frozen), arch, ids), [], []
        for i in range(n):
            pre = f"params/layers_{i}/"
            xs.append(np.asarray(x))  # on the host until its backward
            x, read = fwd(part(trainable, pre), part(frozen, pre), x)
            readings.append(read)
        top = {k: frozen["params/" + k] for k in ("final_norm/scale",
                                                  "head/kernel")}
        loss, dx = head(top, x, ids)
        g = {}
        for i in reversed(range(n)):
            pre = f"params/layers_{i}/"
            dtr, dx = bwd(part(trainable, pre), part(frozen, pre), xs.pop(), dx)
            g.update({pre + k: v for k, v in dtr.items()})
        return loss, readings, g

    return grads


def loss_and_grads(flat: dict, arch: dict, patterns, ids, **how):
    """``(loss, readings, {leaf: gradient})`` of the trainable leaves at the
    weights ``flat`` (left as they are), for tests and small sizes."""
    trainable = {k: jnp.asarray(v, jnp.float32) for k, v in flat.items()
                 if is_trainable(k, patterns)}
    frozen = {k: v for k, v in flat.items() if k not in trainable}
    return layerwise_grads(arch, **how)(trainable, frozen, ids)


def tune(flat: dict, arch: dict, hp: dict, ids, n_steps: int, **how) -> dict:
    """Follow the first ``n_steps`` steps from the initial weights, the same
    document every step: loss, its gradient in the trainable leaves,
    global-norm clipping and AdamW (``deepseek_v32.make_update``, the other
    token cells'). ``readings`` are the FIRST step's, per layer. The frozen
    leaves move to the host and ``flat`` is EMPTIED (so that the caller's
    copy on the device is freed)."""
    pats = hp["trainable_modules"]
    trainable = {k: jnp.array(v, jnp.float32) for k, v in flat.items()
                 if is_trainable(k, pats)}  # copies: the update donates them
    frozen = {}
    for k in list(flat):
        v = flat.pop(k)
        if k not in trainable:
            frozen[k] = np.asarray(v)
    grads = layerwise_grads(arch, **how)
    mu = {k: jnp.zeros_like(v) for k, v in trainable.items()}
    nu = {k: jnp.zeros_like(v) for k, v in trainable.items()}
    update = make_update(hp)
    losses, gnorms, readings = [], [], None
    for i in range(n_steps):
        loss, read, g = grads(trainable, frozen, ids)
        trainable, mu, nu, gnorm = update(trainable, mu, nu,
                                          jnp.asarray(i, jnp.int32), g)
        readings = read if readings is None else readings
        del g
        losses.append(loss)
        gnorms.append(gnorm)
    return {"trainable": trainable, "mu": mu, "nu": nu,
            "readings": [{k: float(v) for k, v in r.items()} for r in readings],
            "losses": np.asarray(jax.device_get(jnp.stack(losses))),
            "grad_norms": np.asarray(jax.device_get(jnp.stack(gnorms)))}
