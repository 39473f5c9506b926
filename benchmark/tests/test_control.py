"""The controls, at a size a test run can hold (tiny preset, CPU): the
reference put in the program's place and computed in the nearest precision
below the bfloat16 the configurations state — both operands of every product
rounded to ``float8_e4m3fn`` — has to come out as NOT correct under the
limits the cells commit. (The chip readings at the cells' own size, three
seeds each, are in PERF.md.)"""

import json
import os

import jax
import jax.numpy as jnp

from benchmark.harness.weights import flatten_named, make_weights, seed_words
from benchmark.reference import serve_check, train, tune_check
from benchmark.reference.numerics import Numerics
from test_reference import HP, TINY_ARCH

HERE = os.path.dirname(os.path.abspath(__file__))


def _limits(cell):
    with open(os.path.join(HERE, "..", "workloads", cell + ".json")) as f:
        return json.load(f)["limits"]


def test_tune_control_is_not_correct():
    from videop2p_tpu.models import UNet3DConditionModel, UNet3DConfig

    cfg = UNet3DConfig.tiny(layers_per_block=2)
    model = UNet3DConditionModel(config=cfg, dtype=jnp.float32)
    shapes = jax.eval_shape(
        model.init, jax.random.key(0), jnp.zeros((1, 3, 8, 8, 4)),
        jnp.asarray(0), jnp.zeros((1, 77, 16)))
    limits = _limits("sd15-tune-8f.steps")
    failed_by = []
    for seed in (3, 4, 5):
        flat = {k: v for k, v in flatten_named(jax.jit(
            lambda w: make_weights(shapes, w, "unet"))(
                seed_words(seed))).items() if k.startswith("params/")}
        latents = jax.random.normal(jax.random.key(seed), (1, 3, 8, 8, 4))
        text = jax.random.normal(jax.random.key(seed + 100), (1, 77, 16))
        key = jax.random.key(seed + 200)
        ref = train.tune(flat, TINY_ARCH, HP, latents, text, key, 3)
        ctl = train.tune(flat, TINY_ARCH, HP, latents, text, key, 3,
                         nx=Numerics("float8_e4m3fn"))
        init = {k: v for k, v in flat.items()
                if train.is_trainable(k, HP["trainable_modules"])}
        g = tune_check.gaps(ctl, ref, init)
        over = [k for k in limits if k in g and g[k] > limits[k]]
        assert over, (seed, g, limits)
        failed_by.append(over)
    assert all(failed_by)


def test_vae_control_is_not_correct():
    """At the tiny preset the float8 decode reads 0.11, about the served
    cell's limit of 0.1 (eight channels average less rounding away than
    128-512 do; on the chip at the cell's size it read 0.25-0.64, PERF.md).
    What a test run can hold: the control reads three times the bfloat16
    program and more, at the same size."""
    from videop2p_tpu.models import AutoencoderKL, VAEConfig, decode_video

    shapes = jax.eval_shape(
        AutoencoderKL(config=VAEConfig.tiny()).init, jax.random.key(0),
        jnp.zeros((1, 64, 64, 3)), jax.random.key(0))
    program = AutoencoderKL(config=VAEConfig.tiny(), dtype=jnp.bfloat16)
    for seed in (3, 4, 5):
        params = jax.jit(
            lambda w: make_weights(shapes, w, "vae"))(seed_words(seed))
        flat = flatten_named(params)
        z = 0.5 * jax.random.normal(jax.random.key(seed), (2, 8, 8, 4))
        ref = serve_check.decode_clip(flat, serve_check.TINY_VAE, z)
        ctl = serve_check.decode_clip(flat, serve_check.TINY_VAE, z,
                                      nx=Numerics("float8_e4m3fn"))
        served = decode_video(program, params, z[None].astype(jnp.bfloat16),
                              sequential=True)[0].astype(jnp.float32)
        lower = serve_check.rel_l2(served, ref)
        upper = serve_check.rel_l2(ctl, ref)
        assert lower < _limits("sd15-edit-8f.serve-resident")[
            "vae_decode_gap"]
        assert upper > 3 * lower and upper > 0.05, (lower, upper)


def test_chip_readings_under_the_committed_limits():
    """The readings taken on the chip at the cell's own size (PERF.md, PR 25),
    through the harness's own comparison under the limits the cell commits:
    every sound run of the program is correct; the control and the planted
    fault (half of the clip left out of the loss) are not."""
    from benchmark.harness.result import verdict

    with open(os.path.join(HERE, "..", "workloads",
                           "sd15-tune-8f.steps.json")) as f:
        cell = json.load(f)
    with open(os.path.join(HERE, "data", "tune_chip_readings.json")) as f:
        readings = json.load(f)["readings"]
    kinds = {}
    for r in readings:
        cmp = tune_check.compared(cell, r["gaps"], 0, 0)
        over = [k for k, c in cmp.items()
                if c["value"] is not None and c["value"] > c["limit"]]
        kinds.setdefault(r["reading"], []).append((r["seed"], over))
        if r["reading"] == "program":
            assert verdict(cmp) and not over, (r["seed"], over)
        else:
            assert not verdict(cmp) and over, (r["reading"], r["seed"])
    assert len(kinds["program"]) >= 8
    assert len(kinds["fault_half_of_the_clip"]) >= 3
    assert len(kinds["control_float8_e4m3fn"]) >= 3
    assert all(over == ["change_diff_worst"]
               for _, over in kinds["fault_half_of_the_clip"])
