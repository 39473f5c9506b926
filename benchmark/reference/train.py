"""Plain float32 Stage-1 tuning steps: the epsilon-prediction MSE loss of
the video UNet on one clip, its gradient w.r.t. the trainable leaves
(``attn1.to_q``, ``attn2.to_q``, ``attn_temp``), global-norm clipping and
AdamW — written from the published recipe (Tune-A-Video), importing nothing
of the program.

The inputs are the timed call's own: the clip's latents and text states as
set-up computed them, and the run key from which step ``i`` draws its noise
and timestep (``fold_in(key, i)`` then one split: noise, timestep) — the
feed of the program's window, so that both sides see the same rows."""

from __future__ import annotations

import json

import jax
import jax.numpy as jnp
import numpy as np

from benchmark.reference.numerics import Numerics
from benchmark.reference.unet3d import unet3d


def is_trainable(name: str, patterns) -> bool:
    """A leaf trains when a pattern's dotted tokens appear consecutively in
    its path (``attn1.to_q`` matches ``.../attn1/to_q/kernel``)."""
    toks = name.split("/")
    for pat in patterns:
        p = pat.split(".")
        if any(toks[i:i + len(p)] == p for i in range(len(toks) - len(p) + 1)):
            return True
    return False


def alphas_cumprod(hp: dict):
    n = int(hp["num_train_timesteps"])
    assert hp["beta_schedule"] == "scaled_linear"
    betas = np.linspace(hp["beta_start"] ** 0.5, hp["beta_end"] ** 0.5, n,
                        dtype=np.float64) ** 2
    return jnp.asarray(np.cumprod(1.0 - betas).astype(np.float32))


_STEPS = {}  # one jitted step per (arch, hp, numerics): traced once


def make_step(arch: dict, hp: dict, nx: Numerics):
    key = json.dumps([arch, hp, nx.operand], sort_keys=True, default=list)
    if key not in _STEPS:
        _STEPS[key] = _make_step(arch, hp, nx)
    return _STEPS[key]


def _make_step(arch: dict, hp: dict, nx: Numerics):
    """One jitted tuning step ``(trainable, mu, nu, step, frozen, latents,
    text, run_key, frame_weight) -> (trainable, mu, nu, loss, grad_norm)``.
    ``frame_weight`` (F,) weighs each frame's squared error in the mean:
    ones is the recipe; zeros on some frames is the planted fault that leaves
    part of the clip out and takes the mean over the rest (traced data, so
    the fault runs the reference's own compiled step)."""
    ac = alphas_cumprod(hp)
    lr, b1, b2 = hp["learning_rate"], hp["adam_beta1"], hp["adam_beta2"]
    eps, wd, max_norm = (hp["adam_epsilon"], hp["adam_weight_decay"],
                         hp["max_grad_norm"])

    def step(trainable, mu, nu, i, frozen, latents, text, run_key,
             frame_weight):
        latents = latents.astype(jnp.float32)
        noise_key, t_key = jax.random.split(jax.random.fold_in(run_key, i))
        noise = jax.random.normal(noise_key, latents.shape, latents.dtype)
        t = jax.random.randint(t_key, (latents.shape[0],), 0,
                               int(hp["num_train_timesteps"]))
        a = jnp.sqrt(ac[t]).reshape(-1, 1, 1, 1, 1)
        b = jnp.sqrt(1.0 - ac[t]).reshape(-1, 1, 1, 1, 1)
        noisy = a * latents + b * noise

        def loss_fn(tr):
            pred = unet3d({**frozen, **tr}, arch, noisy, t, text, nx=nx,
                          remat=True)
            w = frame_weight.reshape(1, -1, 1, 1, 1)
            per_frame = pred.size / pred.shape[1]
            return jnp.sum(w * (pred - noise) ** 2) / (jnp.sum(w) * per_frame)

        loss, g = jax.value_and_grad(loss_fn)(trainable)
        gnorm = jnp.sqrt(sum(jnp.sum(v ** 2) for v in g.values()))
        scale = jnp.where(gnorm < max_norm, 1.0, max_norm / gnorm)
        g = {k: v * scale for k, v in g.items()}
        c = (i + 1).astype(jnp.float32)
        mu = {k: b1 * mu[k] + (1 - b1) * g[k] for k in g}
        nu = {k: b2 * nu[k] + (1 - b2) * g[k] ** 2 for k in g}
        new = {}
        for k in g:
            m_hat = mu[k] / (1 - b1 ** c)
            v_hat = nu[k] / (1 - b2 ** c)
            upd = m_hat / (jnp.sqrt(v_hat) + eps) + wd * trainable[k]
            new[k] = trainable[k] - lr * upd
        return new, mu, nu, loss, gnorm

    return jax.jit(step)


def tune(flat: dict, arch: dict, hp: dict, latents, text, run_key,
         n_steps: int, *, nx: Numerics = None, frame_weight=None) -> dict:
    """Follow the first ``n_steps`` steps from the initial weights."""
    nx = nx or Numerics()
    pats = hp["trainable_modules"]
    trainable = {k: v.astype(jnp.float32) for k, v in flat.items()
                 if is_trainable(k, pats)}
    frozen = {k: v for k, v in flat.items() if k not in trainable}
    mu = {k: jnp.zeros_like(v) for k, v in trainable.items()}
    nu = {k: jnp.zeros_like(v) for k, v in trainable.items()}
    step = make_step(arch, hp, nx)
    if frame_weight is None:
        frame_weight = jnp.ones((latents.shape[1],), jnp.float32)
    frame_weight = jnp.asarray(frame_weight, jnp.float32)
    losses, gnorms = [], []
    for i in range(n_steps):
        trainable, mu, nu, loss, gnorm = step(
            trainable, mu, nu, jnp.asarray(i, jnp.int32), frozen, latents,
            text, run_key, frame_weight)
        losses.append(loss)
        gnorms.append(gnorm)
    return {"trainable": trainable, "mu": mu, "nu": nu,
            "losses": np.asarray(jax.device_get(jnp.stack(losses))),
            "grad_norms": np.asarray(jax.device_get(jnp.stack(gnorms)))}
