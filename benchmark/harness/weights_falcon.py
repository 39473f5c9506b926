"""Seeded weights of Falcon-H1-34B's cell (``models/falcon_h1.py``), made by
the benchmark — not by the program.

The leaves are ``weights_hybrid.make_hybrid_weights``'s as they are — the
token cells' few long threefry draws cut into leaves, the same
distributions by leaf name (kernels N(0, 1/fan_in), scales 1 + N(0,
0.05^2), the embedding and the conv bias N(0, 0.02^2)) and the Mamba-2
mixer's per-head leaves (``A_log``, ``dt_bias``, ``D``) as the Granite cell
draws them. No leaf is fitted to the document: the block has no router.
``steer_init()`` puts the generator in the place of the program's
``init_params``; the key the program passes is traced data, so one init
program serves every seed. The document is ``weights_lm.document``'s."""

from __future__ import annotations

import jax
import jax.numpy as jnp

from benchmark.harness.weights_hybrid import make_hybrid_weights

REGEN = {}  # what the program's build called init with, to repeat it


def steer_init() -> None:
    """``falcon_h1.init_params(key, cfg, dtype)`` becomes the generator,
    seeded by the key the program passes."""
    from videop2p_tpu.models import falcon_h1

    def init(key, cfg, dtype=jnp.bfloat16):
        REGEN["args"] = (cfg, dtype)
        return make_hybrid_weights(falcon_h1.abstract_params(cfg, dtype),
                                   jax.random.key_data(key)[-2:])

    falcon_h1.init_params = init


def regenerate(seed: int, cfg=None, dtype=jnp.bfloat16):
    """The same weights again, through the same jitted call the program's
    ``build_token_model`` made (``cfg``: before the program has made any)."""
    from videop2p_tpu.models import falcon_h1

    if cfg is None:
        cfg, dtype = REGEN["args"]
    return jax.jit(lambda key: falcon_h1.init_params(key, cfg, dtype))(
        jax.random.key(int(seed) % (2 ** 31 - 1)))
