"""The plain references against the program at the tiny preset, on the CPU,
float32 on both sides: UNet3D forward, VAE decode, and tuning steps (loss,
Adam moments, parameter change). Each tolerance with its reason."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.harness.weights import flatten_named, make_weights, seed_words
from benchmark.reference import train, tune_check
from benchmark.reference import unet3d as R
from benchmark.reference import vae as RV
from benchmark.reference.numerics import Numerics

TINY_ARCH = {"block_out_channels": (8, 16), "layers_per_block": 2,
             "heads": 2, "groups": 4,
             "down": ("CrossAttnDownBlock3D", "DownBlock3D"),
             "up": ("UpBlock3D", "CrossAttnUpBlock3D")}
HP = {"trainable_modules": ["attn1.to_q", "attn2.to_q", "attn_temp"],
      "learning_rate": 3e-5, "adam_beta1": 0.9, "adam_beta2": 0.999,
      "adam_epsilon": 1e-8, "adam_weight_decay": 0.01, "max_grad_norm": 1.0,
      "num_train_timesteps": 1000, "beta_start": 0.00085, "beta_end": 0.012,
      "beta_schedule": "scaled_linear"}


@pytest.fixture(scope="module")
def tiny():
    from videop2p_tpu.models import UNet3DConditionModel, UNet3DConfig

    cfg = UNet3DConfig.tiny(layers_per_block=2, frame_attention="chunked")
    model = UNet3DConditionModel(config=cfg, dtype=jnp.float32)
    args = (jnp.zeros((1, 3, 8, 8, 4)), jnp.asarray(0),
            jnp.zeros((1, 77, 16)))
    shapes = jax.eval_shape(model.init, jax.random.key(0), *args)
    params = jax.jit(lambda w: make_weights(shapes, w, "unet"))(seed_words(5))
    return model, {"params": params["params"]}


def test_unet3d_forward_matches_program(tiny):
    model, params = tiny
    x = jax.random.normal(jax.random.key(1), (1, 3, 8, 8, 4))
    t = jnp.asarray([417])
    text = jax.random.normal(jax.random.key(2), (1, 77, 16))
    with jax.default_matmul_precision("highest"):
        y = model.apply(params, x, t, text)
    flat = flatten_named(params)
    yr = R.unet3d(flat, TINY_ARCH, x, t, text)
    # float32 on both sides, different op order: a few ulps of O(1) values
    assert float(jnp.abs(y - yr).max()) < 2e-5 * float(jnp.abs(y).max())
    yrm = R.unet3d(flat, TINY_ARCH, x, t, text, remat=True)
    assert float(jnp.abs(yrm - yr).max()) < 2e-5
    # the control's numerics move the answer by far more than that
    yc = R.unet3d(flat, TINY_ARCH, x, t, text, nx=Numerics("float8_e4m3fn"))
    assert float(jnp.abs(yc - yr).max()) > 1e-2 * float(jnp.abs(y).max())


def test_vae_decode_matches_program():
    from videop2p_tpu.models import AutoencoderKL, VAEConfig, decode_video

    vae = AutoencoderKL(config=VAEConfig.tiny(), dtype=jnp.float32)
    shapes = jax.eval_shape(vae.init, jax.random.key(0),
                            jnp.zeros((1, 64, 64, 3)), jax.random.key(0))
    params = jax.jit(lambda w: make_weights(shapes, w, "vae"))(seed_words(5))
    z = jax.random.normal(jax.random.key(3), (1, 3, 8, 8, 4))
    with jax.default_matmul_precision("highest"):
        video = decode_video(vae, params, z, sequential=True)
    ref = RV.decode_frames(
        flatten_named(params),
        {"norm_num_groups": 4, "block_out_channels": (8, 16),
         "layers_per_block": 1}, z[0])
    # float32 both sides: rounding only
    assert float(jnp.abs(video[0] - ref).max()) < 2e-5 * float(
        jnp.abs(ref).max())


def test_tuning_steps_match_program(tiny):
    """Three steps of the program's ``train_steps`` (float32) against the
    reference: losses, Adam moments, parameter change, frozen leaves."""
    from videop2p_tpu.core import DDPMScheduler
    from videop2p_tpu.pipelines import make_unet_fn
    from videop2p_tpu.train import (TrainState, TuneConfig, make_optimizer,
                                    train_steps)

    model, params = tiny
    latents = jax.random.normal(jax.random.key(7), (1, 3, 8, 8, 4))
    text = jax.random.normal(jax.random.key(8), (1, 77, 16))
    run_key = jax.random.key(11)
    tx = make_optimizer(TuneConfig())
    state = TrainState.create(params["params"], tx,
                              tuple(HP["trainable_modules"]))
    init = flatten_named({"params": state.trainable})
    with jax.default_matmul_precision("highest"):
        new, losses = train_steps(
            make_unet_fn(model), tx, state, DDPMScheduler.create_sd(),
            latents, text, run_key, num_steps=3)
    adam = [s for s in jax.tree_util.tree_leaves(
        new.opt_state, is_leaf=lambda x: hasattr(x, "mu"))
        if hasattr(s, "mu")][0]
    prog = {"losses": np.asarray(losses),
            "trainable": flatten_named({"params": new.trainable}),
            "mu": flatten_named({"params": adam.mu}),
            "nu": flatten_named({"params": adam.nu})}
    ref = train.tune(flatten_named(params), TINY_ARCH, HP, latents, text,
                     run_key, 3)
    assert set(ref["trainable"]) == set(prog["trainable"])
    g = tune_check.gaps(prog, ref, init)
    # float32 both sides; Adam's m/sqrt(v) amplifies rounding of tiny
    # gradients, hence 1e-3 and not 1e-5 on the moments and the change
    assert g["loss_gap_worst"] < 1e-5
    assert g["mu_gap_worst"] < 1e-3 and g["nu_gap_worst"] < 1e-3
    assert g["change_gap_worst"] < 1e-3
    # leaf by leaf, not only by norm
    for k, v in ref["trainable"].items():
        d_ref = v - init[k]
        d_prog = prog["trainable"][k] - init[k]
        assert float(jnp.abs(d_prog - d_ref).max()) <= 0.05 * float(
            jnp.abs(d_ref).max()) + 1e-9, k
    # a state left unchanged reads 1 by the change's measure
    same = dict(prog, trainable=init)
    assert abs(tune_check.gaps(same, ref, init)["change_gap_worst"] - 1) < 1e-6
    # half of the clip left out of the loss, the mean taken over the rest
    half = train.tune(flatten_named(params), TINY_ARCH, HP, latents, text,
                      run_key, 3, frame_weight=[1.0, 0.0, 0.0])
    gh = tune_check.gaps(half, ref, init)
    assert gh["loss_gap_first"] > 1e-3 and gh["nu_gap_worst"] > 1e-2


def test_trainable_rule():
    assert train.is_trainable(
        "params/down_blocks_0/attentions_0/blocks_0/attn1/to_q/kernel",
        HP["trainable_modules"])
    assert train.is_trainable(
        "params/mid_block/attentions_0/blocks_0/attn_temp/to_out/bias",
        HP["trainable_modules"])
    assert not train.is_trainable(
        "params/down_blocks_0/attentions_0/blocks_0/attn1/to_k/kernel",
        HP["trainable_modules"])
    assert not train.is_trainable(
        "params/down_blocks_0/attentions_0/blocks_0/attn2/to_out/kernel",
        HP["trainable_modules"])
