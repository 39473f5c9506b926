"""Steering of the program from the benchmark's process — never through a
new option of the program (the way ``chip_smoke.cut_depth`` does it).

  * ``cut_depth``      the configuration file's ``layers_per_block`` for the
                       random-init branch of ``build_models``;
  * ``seeded_weights`` the benchmark's own weights (harness/weights.py) in
                       place of each model's flax ``init``;
  * ``no_export``      the tuning CLI's final ``save_pipeline`` (3.4 GB of
                       float32 on disk per run) replaced by a recorder.
"""

from __future__ import annotations

import jax


def cut_depth(config: dict) -> dict:
    from videop2p_tpu.models import UNet3DConfig

    published = UNet3DConfig.__dict__["sd15"].__func__
    want = {k: (tuple(v) if isinstance(v, list) else v)
            for k, v in config["unet"].items()}
    UNet3DConfig.sd15 = classmethod(
        lambda cls, **kw: published(cls, **{**want, **kw}))
    cfg = UNet3DConfig.sd15()
    for k, v in want.items():
        assert getattr(cfg, k) == v, (k, getattr(cfg, k), v)
    return {"layers_per_block": cfg.layers_per_block,
            "block_out_channels": list(cfg.block_out_channels)}


REGEN = {}  # tag -> (module, args, kwargs) of the init call, to repeat it


def seeded_weights(fixed=None) -> None:
    """Each model's ``init(key, ...)`` becomes the benchmark's generator,
    seeded by the key the program passes (``jax.random.key(seed)``, traced
    data: one init program serves every seed). ``fixed`` maps a model's tag
    to a seed of its own, which then enters that model's init program as a
    constant: the same model whatever key the program passes."""
    from flax import linen as nn

    from videop2p_tpu.models import (AutoencoderKL, CLIPTextEncoder,
                                     UNet3DConditionModel)

    from benchmark.harness.weights import make_weights, seed_words

    fixed = {tag: seed_words(seed)[-2:]
             for tag, seed in (fixed or {}).items()}
    flax_init = nn.Module.init

    def patched(tag):
        def init(self, key, *args, **kwargs):
            shapes = jax.eval_shape(
                lambda *a: flax_init(self, *a, **kwargs), key, *args)
            REGEN[tag] = (self, jax.tree.map(
                lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype), args),
                kwargs)
            words = fixed.get(tag, jax.random.key_data(key)[-2:])
            return make_weights(shapes, words, tag)
        return init

    UNet3DConditionModel.init = patched("unet")
    AutoencoderKL.init = patched("vae")
    CLIPTextEncoder.init = patched("text")


def no_export() -> dict:
    """Replace ``run_tuning.save_pipeline`` by a recorder: the export is
    outside the window either way, and a run may write little to disk."""
    from videop2p_tpu.cli import run_tuning

    seen = {"calls": 0}

    def recorder(*args, **kwargs):
        seen["calls"] += 1

    run_tuning.save_pipeline = recorder
    return seen


def regenerate(tag: str, seed: int):
    """The same weights again, through the same jitted call the program's
    ``build_models`` made (``jax.jit(module.init)(jax.random.key(seed), ...)``)
    — the same program, so it comes back from the compile cache. (A model
    given a ``fixed`` seed comes back as that model whatever ``seed`` is.)"""
    import jax.numpy as jnp

    module, args, kwargs = REGEN[tag]
    zeros = jax.tree.map(lambda a: jnp.zeros(a.shape, a.dtype), args)
    key = jax.random.key(int(seed) % (2 ** 31 - 1))
    return jax.jit(module.init)(key, *zeros, **kwargs)


def free_program_state() -> None:
    """Drop compiled programs and what only they referenced, before the
    reference runs (``memory_peak_bytes`` has been read by then)."""
    import gc

    gc.collect()
    jax.clear_caches()
    gc.collect()
