"""Seeded weights of the hybrid token-model cell (``models/granite_hybrid.py``),
made by the benchmark — not by the program.

``make_hybrid_weights(shapes, words)`` is ``weights_lm.make_lm_weights`` —
the same few long threefry draws cut into leaves, the same distributions by
leaf name (kernels N(0, 1/fan_in), scales 1 + N(0, 0.05^2), embedding and
biases N(0, 0.02^2)) — with the three per-head leaves of a Mamba-2 mixer the
other token model does not have, from the same draws (the configuration
file's ``assumed``; u = the normal distribution function of the draw):
  A_log     log(1 + 15 u)                    U[1, 16] decay rates
  dt_bias   softplus^-1(1e-3 + 0.099 u)      step sizes in [1e-3, 1e-1]
  D         1
``steer_init()`` puts it in the place of the program's ``init_params``; the
key the program passes is traced data, so one init program serves every
seed. The document is ``weights_lm.document``'s.

One leaf is not left as drawn: each layer's ROUTER (``router/kernel``; the
configuration file's ``assumed.router_rows``). This router has no bias to
refit, and as drawn the tokens do not spread evenly: every token's residual
stream shares a common component (activations with a positive mean), which
gives each expert's logit an offset, so the busiest held expert sees 1.5
times the mean and the held share — half the step is the expert loop —
swings from seed to seed (PERF.md section 6, PR 32). A checkpoint trained
with the published auxiliary balance loss is level. ``level_router_rows``
rescales each expert's row of W_r (a column of the stored ``kernel``; a
larger row wins the top-k more often) on the cell's document BEFORE the
program is built, with the plain reference's float32 layers
(``reference/granite_moe_hybrid.py``; nothing of the program), layer by
layer, each layer routed with the rows it has just been given, and stops
as soon as the busiest HELD expert sees at most ``LEVEL_AT`` times the held
experts' mean — no further, so that the loads keep an imbalance a trained
router has too. The SHARE of the pairs that lands on the held experts is
then pinned to held / published within ``SHARE_TOL`` by one common factor
on the held rows (the imbalance between experts stays): the expert loop's
work follows that share, and left to the seed (0.2495-0.2505 after the
first pass) it moves ``tune_step_ms`` by 0.33 % from seed to seed (PERF.md
section 6, PR 32). ``with_router_rows`` puts them into a set of weights AFTER
the jitted generator has run (as constants of that program they would
compile it anew for every seed)."""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
from jax.scipy.special import ndtr

from benchmark.harness import weights_lm
from benchmark.harness.weights import _leaf_name

REGEN = {}  # what the program's build called init with, to repeat it
LEVEL_AT = 1.1    # held max over mean at which the rows' refit stops
SHARE_TOL = 5e-4  # of held / published, at which the held share is pinned
LEVEL_STEPS = 40  # at most, of the refit: load ~ scale^1.7 near level loads
_PER_HEAD = {
    "A_log": lambda u: jnp.log(1.0 + 15.0 * u),
    "dt_bias": lambda u: jnp.log(jnp.expm1(1e-3 + (1e-1 - 1e-3) * u)),
    "D": jnp.ones_like,
}


def _last(path) -> str:
    return _leaf_name(path).rsplit("/", 1)[-1]


def make_hybrid_weights(shapes, words):
    # the per-head leaves are drawn in float32 (0.02 z, the generator's
    # "other" leaves) so that the draw z comes back whole
    wide = jax.tree_util.tree_map_with_path(
        lambda p, s: jax.ShapeDtypeStruct(s.shape, jnp.float32)
        if _last(p) in _PER_HEAD else s, shapes)
    drawn = weights_lm.make_lm_weights(wide, words)
    return jax.tree_util.tree_map_with_path(
        lambda p, v, s: _PER_HEAD[_last(p)](ndtr(v / 0.02)).astype(s.dtype)
        if _last(p) in _PER_HEAD else v, drawn, shapes)


def steer_init() -> None:
    """``granite_hybrid.init_params(key, cfg, dtype)`` becomes the generator
    above, seeded by the key the program passes."""
    from videop2p_tpu.models import granite_hybrid

    def init(key, cfg, dtype=jnp.bfloat16):
        REGEN["args"] = (cfg, dtype)
        return make_hybrid_weights(granite_hybrid.abstract_params(cfg, dtype),
                                   jax.random.key_data(key)[-2:])

    granite_hybrid.init_params = init


def regenerate(seed: int, cfg=None, dtype=jnp.bfloat16, rows=None):
    """The same weights again, through the same jitted call the program's
    ``build_token_model`` made (``cfg``: before the program has made any),
    with the router ``rows`` the run was given."""
    from videop2p_tpu.models import granite_hybrid

    if cfg is None:
        cfg, dtype = REGEN["args"]
    tree = jax.jit(lambda key: granite_hybrid.init_params(key, cfg, dtype))(
        jax.random.key(int(seed) % (2 ** 31 - 1)))
    return {"params": with_router_rows(tree["params"], rows or {})}


def _scaled(kernel, scale):
    """``kernel`` (h, experts) with expert e's row of W_r times scale[e],
    rounded back to the leaf's dtype — the ONE place it is computed."""
    return (kernel.astype(jnp.float32)
            * jnp.asarray(scale, jnp.float32)[None, :]).astype(kernel.dtype)


def with_router_rows(params: dict, rows: dict) -> dict:
    """``params`` with each named layer's ``router/kernel`` rescaled."""
    out = dict(params)
    for name, scale in rows.items():
        kernel = params[name]["router"]["kernel"]
        out[name] = {**params[name], "router": {"kernel": _scaled(kernel, scale)}}
    return out


def fit_rows(nx, y, kernel, k: int, held):
    """The scale (experts,) of one router's rows on the normed tokens ``y``
    (T, h), a step at a time: while the busiest of the ``held`` ``(first,
    count)`` experts sees over ``LEVEL_AT`` times the held mean, every
    expert's row is nudged towards the mean load; else, while the held share
    of the (token, expert) pairs is off held / published by over
    ``SHARE_TOL`` of it, the held rows together (and the others the other
    way)."""
    n = kernel.shape[1]
    first, count = held
    level, want = y.shape[0] * k / n, count / n
    is_held = (jnp.arange(n) >= first) & (jnp.arange(n) < first + count)

    def loads(scale):
        experts = jax.lax.top_k(nx.mm(y, _scaled(kernel, scale)), k)[1]
        return jnp.sum(experts[:, :, None] == jnp.arange(n)[None, None, :],
                       axis=(0, 1)).astype(jnp.float32)

    def held_share(load):
        return jnp.sum(jnp.where(is_held, load, 0.0)) / jnp.sum(load)

    def uneven(load):
        on_held = jnp.where(is_held, load, 0.0)
        return jnp.max(on_held) > LEVEL_AT * jnp.sum(on_held) / count

    def off_share(load):
        return jnp.abs(held_share(load) / want - 1.0) > SHARE_TOL

    def unfit(carry):
        i, _, load = carry
        return (i < LEVEL_STEPS) & (uneven(load) | off_share(load))

    def refit(carry):
        i, scale, load = carry
        share = held_share(load)
        scale = scale * jnp.sqrt(jnp.where(
            uneven(load), level / jnp.maximum(load, 1.0),
            jnp.where(is_held, want / share,
                      (1.0 - want) / jnp.maximum(1.0 - share, 1e-9))))
        return i + 1, scale, loads(scale)

    ones = jnp.ones((n,), jnp.float32)
    return jax.lax.while_loop(unfit, refit, (0, ones, loads(ones)))[1]


def level_router_rows(flat: dict, arch: dict, ids, row_block=None) -> dict:
    """``{layer name: scale (num_local_experts,)}`` (on the host) for every
    layer of the weights ``flat`` (by leaf name): one float32 forward pass
    of the plain reference, a layer at a time, each layer routed with the
    rows :func:`fit_rows` has just given it."""
    from benchmark.reference import granite_moe_hybrid as ref

    nx, k = ref._Nx("float32"), arch["num_experts_per_tok"]

    @jax.jit
    def one(fr, x):
        with jax.default_matmul_precision("highest"):
            W = ref.Weights(fr, "")
            x, _ = ref.mixer(W, arch, nx, x, row_block=row_block)
            y = ref._rms_norm(x, W("post_norm/scale"), arch["rms_norm_eps"])
            scale = fit_rows(nx, y, fr["router/kernel"], k,
                             arch["experts_held"])
            routed, shared, _ = ref.moe_parts(
                ref.Weights({**fr, "router/kernel": _scaled(
                    fr["router/kernel"], scale)}, ""), arch, nx, y,
                row_block=row_block)
            return x + arch["residual_multiplier"] * (routed + shared), scale

    x = ref._embed(ref.Weights(flat), arch, ids)
    out = {}
    for i in range(arch["num_hidden_layers"]):
        pre = f"params/layers_{i}/"
        x, scale = one({n[len(pre):]: v for n, v in flat.items()
                        if n.startswith(pre)}, x)
        out[f"layers_{i}"] = np.asarray(scale)
    return out
