"""Stage-1 one-shot tuning: optimizer, train state and the pure train step.

TPU-native re-design of the reference trainer
(/root/reference/run_tuning.py:44-395). The torch/Accelerate loop becomes a
pure jittable step over an explicit :class:`TrainState` and a
:class:`StepLoss` (``loss_step`` / ``loss_steps``: the partition, the clipped
AdamW, the scan and the ``fold_in(key, step)`` rule are shared by every
model; ``diffusion_loss`` is the video UNet's, ``next_token_loss`` a token
model's; ``train_step`` / ``train_steps`` are the UNet's spelling of them):

  * partitioned AdamW — only ``attn1.to_q / attn2.to_q / attn_temp`` are in
    the differentiated/optimized subtree (run_tuning.py:137-141,157-176);
    the frozen ~90% of the UNet never materializes gradients or moments;
  * gradient clipping (run_tuning.py:328) and accumulation
    (``optax.MultiSteps``, the reference's ``accelerator.accumulate``);
  * iid or temporally-dependent training noise (run_tuning.py:290-294);
  * one random timestep per video (run_tuning.py:298), ε- or v-target
    (run_tuning.py:310-315), MSE in float32 (run_tuning.py:318-319);
  * lr schedules by name mirroring diffusers ``get_scheduler``
    (run_tuning.py:202-207).

The step is mesh-agnostic: under ``jit`` with sharded inputs the same code is
the distributed trainer (collectives are compiler-inserted; loss averaging is
the implicit psum the reference does explicitly via ``accelerator.gather``,
run_tuning.py:322).
"""

from __future__ import annotations

import dataclasses
from typing import Any, Callable, Dict, NamedTuple, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import optax
from flax import struct

from videop2p_tpu.core.ddpm import DDPMScheduler
from videop2p_tpu.core.noise import DependentNoiseSampler
from videop2p_tpu.pipelines.sampling import UNetFn
from videop2p_tpu.train.masking import (
    DEFAULT_TRAINABLE,
    merge_params,
    partition_params,
)

__all__ = [
    "TuneConfig",
    "TrainState",
    "make_optimizer",
    "make_lr_schedule",
    "StepLoss",
    "diffusion_loss",
    "next_token_loss",
    "loss_step",
    "loss_steps",
    "train_step",
    "train_steps",
]


@dataclasses.dataclass(frozen=True)
class TuneConfig:
    """Training hyperparameters (reference defaults: run_tuning.py:44-83,
    configs/rabbit-jump-tune.yaml:24-38)."""

    learning_rate: float = 3e-5
    scale_lr: bool = False
    lr_scheduler: str = "constant"
    lr_warmup_steps: int = 0
    max_train_steps: int = 500
    adam_beta1: float = 0.9
    adam_beta2: float = 0.999
    adam_weight_decay: float = 1e-2
    adam_epsilon: float = 1e-8
    max_grad_norm: float = 1.0
    gradient_accumulation_steps: int = 1
    # consumed by TrainState.create(params, tx, cfg.trainable_modules) —
    # callers must pass it through; make_optimizer itself is partition-blind
    trainable_modules: Tuple[str, ...] = DEFAULT_TRAINABLE
    train_batch_size: int = 1
    num_processes: int = 1  # for scale_lr parity (run_tuning.py:152-155)


def make_lr_schedule(cfg: TuneConfig) -> optax.Schedule:
    """Diffusers-style schedules by name (run_tuning.py:202-207)."""
    lr = cfg.learning_rate
    if cfg.scale_lr:
        # run_tuning.py:152-155
        lr = lr * cfg.gradient_accumulation_steps * cfg.train_batch_size * cfg.num_processes
    total = max(cfg.max_train_steps, 1)
    warmup = cfg.lr_warmup_steps
    if cfg.lr_scheduler == "constant":
        base = optax.constant_schedule(lr)
    elif cfg.lr_scheduler == "constant_with_warmup":
        return optax.join_schedules(
            [optax.linear_schedule(0.0, lr, max(warmup, 1)), optax.constant_schedule(lr)],
            [warmup],
        )
    elif cfg.lr_scheduler == "linear":
        return optax.join_schedules(
            [
                optax.linear_schedule(0.0, lr, max(warmup, 1)),
                optax.linear_schedule(lr, 0.0, max(total - warmup, 1)),
            ],
            [warmup],
        )
    elif cfg.lr_scheduler == "cosine":
        return optax.join_schedules(
            [
                optax.linear_schedule(0.0, lr, max(warmup, 1)),
                optax.cosine_decay_schedule(lr, max(total - warmup, 1)),
            ],
            [warmup],
        )
    else:
        raise ValueError(f"unknown lr_scheduler: {cfg.lr_scheduler!r}")
    return base


def make_optimizer(cfg: TuneConfig) -> optax.GradientTransformation:
    """Clipped, accumulating AdamW — applied to the trainable subtree only
    (freezing is by partition, not masking: see masking.partition_params)."""
    tx = optax.chain(
        optax.clip_by_global_norm(cfg.max_grad_norm),
        optax.adamw(
            learning_rate=make_lr_schedule(cfg),
            b1=cfg.adam_beta1,
            b2=cfg.adam_beta2,
            eps=cfg.adam_epsilon,
            weight_decay=cfg.adam_weight_decay,
        ),
    )
    if cfg.gradient_accumulation_steps > 1:
        tx = optax.MultiSteps(tx, every_k_schedule=cfg.gradient_accumulation_steps)
    return tx


class TrainState(struct.PyTreeNode):
    """Trainable/frozen split train state. ``trainable`` ∪ ``frozen`` is the
    UNet's full "params" collection (masking.merge_params)."""

    step: jax.Array
    trainable: Any
    frozen: Any
    opt_state: Any

    @classmethod
    def create(
        cls,
        params: Any,
        tx: optax.GradientTransformation,
        trainable_modules: Sequence[str] = DEFAULT_TRAINABLE,
        master_dtype: Optional[Any] = None,
    ) -> "TrainState":
        """``master_dtype``: the trainable leaves (and so their moments) are
        kept in this dtype — float32 masters of a bfloat16 checkpoint; the
        frozen leaves stay as they were given."""
        trainable, frozen = partition_params(params, trainable_modules)
        if master_dtype is not None:
            trainable = jax.tree.map(lambda x: x.astype(master_dtype), trainable)
        return cls(
            step=jnp.asarray(0),
            trainable=trainable,
            frozen=frozen,
            opt_state=tx.init(trainable),
        )

    @property
    def params(self) -> Any:
        """The merged full parameter tree (for validation/export)."""
        return merge_params(self.trainable, self.frozen)


class StepLoss(NamedTuple):
    """What one tuning step minimises, as two functions so that every model
    shares :func:`loss_step`: ``draw(key)`` makes what the step draws from
    its key (outside the gradient), ``loss(params, drawn)`` returns
    ``(scalar loss, aux)`` for the MERGED parameter tree — ``aux`` a dict of
    scalars the step hands out beside the loss (empty for the UNet)."""

    draw: Callable[[jax.Array], Any]
    loss: Callable[[Any, Any], Tuple[jax.Array, Dict[str, jax.Array]]]


def diffusion_loss(
    unet_fn: UNetFn,
    scheduler: DDPMScheduler,
    latents: jax.Array,
    text_embeddings: jax.Array,
    *,
    dependent_sampler: Optional[DependentNoiseSampler] = None,
) -> StepLoss:
    """The video UNet's epsilon / v MSE on VAE-encoded latents
    (run_tuning.py:280-319). ``latents``: (B, F, h, w, C) clean latents
    (already x0.18215); ``text_embeddings``: (B, L, D)."""

    def draw(key):
        with jax.named_scope("train.noise"):
            noise_key, t_key = jax.random.split(key)
            if dependent_sampler is not None:
                noise = dependent_sampler.sample_like(noise_key, latents)
            else:
                noise = jax.random.normal(noise_key, latents.shape, latents.dtype)
            timesteps = jax.random.randint(
                t_key, (latents.shape[0],), 0, scheduler.num_train_timesteps
            )
            noisy = scheduler.add_noise(latents, noise, timesteps)
            target = scheduler.training_target(latents, noise, timesteps)
        return noisy, timesteps, target

    def loss(params, drawn):
        noisy, timesteps, target = drawn
        pred, _ = unet_fn({"params": params}, noisy, timesteps, text_embeddings, None)
        return jnp.mean(
            (pred.astype(jnp.float32) - target.astype(jnp.float32)) ** 2
        ), {}

    return StepLoss(draw, loss)


def next_token_loss(
    loss_fn: Callable[[Any, jax.Array], Tuple[jax.Array, Dict[str, jax.Array]]],
    ids: jax.Array,
) -> StepLoss:
    """A token model's mean next-token cross-entropy over the documents
    ``ids`` (B, T) — the same documents every step, so a step draws nothing.
    ``loss_fn(params, doc)`` is the model's ``(loss, aux)`` for one document
    (``models/deepseek.forward_loss``); the batch's is their mean, and what
    ``aux`` holds besides scalars (arrays a document) is stacked."""

    def loss(params, _):
        per_doc = [loss_fn(params, ids[b]) for b in range(ids.shape[0])]
        return jax.tree.map(
            lambda *x: jnp.mean(jnp.stack(x)) if x[0].ndim == 0 else jnp.stack(x),
            *per_doc)

    return StepLoss(lambda key: None, loss)


def loss_step(
    step_loss: StepLoss,
    tx: optax.GradientTransformation,
    state: TrainState,
    key: jax.Array,
) -> Tuple[TrainState, jax.Array, jax.Array, Dict[str, jax.Array]]:
    """One tuning step on any :class:`StepLoss`: the gradient in the
    trainable subtree only, the clipped AdamW update, a new state. Returns
    (new_state, loss, PRE-clip global gradient norm, aux)."""
    # the named scopes are metadata on the ops (the profiler's device events
    # carry them, forward and backward); they add no device work
    drawn = step_loss.draw(key)

    def loss_fn(trainable):
        # differentiate only the trainable subtree; the model takes the full
        # parameter tree
        return step_loss.loss(merge_params(trainable, state.frozen), drawn)

    with jax.named_scope("train.loss"):
        (loss, aux), grads = jax.value_and_grad(loss_fn, has_aux=True)(
            state.trainable)
    with jax.named_scope("train.optimizer"):
        updates, opt_state = tx.update(grads, state.opt_state, state.trainable)
        trainable = optax.apply_updates(state.trainable, updates)
    new_state = TrainState(
        step=state.step + 1,
        trainable=trainable,
        frozen=state.frozen,
        opt_state=opt_state,
    )
    return new_state, loss, optax.global_norm(grads), aux


def loss_steps(
    step_loss: StepLoss,
    tx: optax.GradientTransformation,
    state: TrainState,
    key: jax.Array,
    *,
    num_steps: int,
    telemetry: bool = False,
):
    """``num_steps`` tuning steps as ONE ``lax.scan`` — one device program
    instead of per-step host dispatches: one dispatch per program. A round-4
    device trace put the step itself at ~384 ms while the per-dispatch loop
    measured 456–794 ms (record, not re-measured on today's code) — the
    scan recovers that gap for the real Stage-1 loop, not just a bench.

    Stage-1 trains on a SINGLE clip or document (dataset length 1,
    run_tuning.py:179), so the batch is the same every step and scanning
    over steps changes nothing but the per-step PRNG key. Only (step,
    trainable, opt_state) ride the scan carry — the frozen majority of the
    model enters as a closure constant, since a carried tree is held twice
    in the executable (carry-in + carry-out) and would double its HBM.

    ``key`` is the RUN's base key, constant across chunks: each step's key
    is ``fold_in(key, absolute_step)``, so what a step draws depends only
    on (seed, step index) — chunk boundaries (logging/checkpoint cadence,
    ``steps_per_call``) and resume points cannot change the trained model.

    Returns (state, per-step losses (num_steps,)), then — with
    ``telemetry=True`` — the per-step PRE-clip global gradient norms stacked
    by the same scan (zero extra dispatches; the norm's reductions are
    already computed inside the clipping transform), then — where the loss
    hands out ``aux`` — a dict of its per-step scalars."""
    frozen = state.frozen

    def body(carry, _):
        step, trainable, opt_state = carry
        s = TrainState(step=step, trainable=trainable, frozen=frozen,
                       opt_state=opt_state)
        s, loss, grad_norm, aux = loss_step(
            step_loss, tx, s, jax.random.fold_in(key, step))
        ys = (loss,) + ((grad_norm,) if telemetry else ()) + ((aux,) if aux else ())
        return (s.step, s.trainable, s.opt_state), ys

    (step, trainable, opt_state), ys = jax.lax.scan(
        body, (state.step, state.trainable, state.opt_state), None,
        length=num_steps,
    )
    state = TrainState(step=step, trainable=trainable, frozen=frozen,
                       opt_state=opt_state)
    return (state,) + ys


def train_step(
    unet_fn: UNetFn,
    tx: optax.GradientTransformation,
    state: TrainState,
    scheduler: DDPMScheduler,
    latents: jax.Array,
    text_embeddings: jax.Array,
    key: jax.Array,
    *,
    dependent_sampler: Optional[DependentNoiseSampler] = None,
    return_grad_norm: bool = False,
) -> Tuple[TrainState, jax.Array]:
    """One tuning step of the video UNet (run_tuning.py:280-331):
    :func:`loss_step` on :func:`diffusion_loss`. Returns (new_state, loss) —
    or (new_state, loss, grad_norm) with ``return_grad_norm=True``: the
    PRE-clip global gradient norm (the quantity ``max_grad_norm`` gates),
    the standard training-health telemetry signal.
    """
    new_state, loss, grad_norm, _ = loss_step(
        diffusion_loss(unet_fn, scheduler, latents, text_embeddings,
                       dependent_sampler=dependent_sampler),
        tx, state, key)
    if return_grad_norm:
        return new_state, loss, grad_norm
    return new_state, loss


def train_steps(
    unet_fn: UNetFn,
    tx: optax.GradientTransformation,
    state: TrainState,
    scheduler: DDPMScheduler,
    latents: jax.Array,
    text_embeddings: jax.Array,
    key: jax.Array,
    *,
    num_steps: int,
    dependent_sampler: Optional[DependentNoiseSampler] = None,
    telemetry: bool = False,
) -> Tuple[TrainState, jax.Array]:
    """``num_steps`` tuning steps of the video UNet as one scan:
    :func:`loss_steps` on :func:`diffusion_loss`. Returns (state, losses),
    or (state, losses, grad_norms) with ``telemetry=True``."""
    return loss_steps(
        diffusion_loss(unet_fn, scheduler, latents, text_embeddings,
                       dependent_sampler=dependent_sampler),
        tx, state, key, num_steps=num_steps, telemetry=telemetry)
