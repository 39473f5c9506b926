"""Seeded weights of the third token family's cell (``models/cohere2_moe.py``),
made by the benchmark — not by the program.

The leaves are ``weights_lm.make_lm_weights``'s as they are — the same few
long threefry draws cut into leaves, the same distributions by leaf name
(kernels N(0, 1/fan_in), scales 1 + N(0, 0.05^2), the embedding
N(0, 0.02^2)): this family has no leaf of another kind. ``steer_init()``
puts the generator in the place of the program's ``init_params``; the key
the program passes is traced data, so one init program serves every seed.
The document is ``weights_lm.document``'s.

One leaf is not left as drawn: each layer's ROUTER (``router/kernel``; the
configuration file's ``assumed.router_rows``). This router has no bias to
refit — sigmoid scores, no groups, no correction — and as drawn the tokens
do not spread at all: with random weights attention is a low-pass filter
over the document (near-uniform weights over thousands of keys pass what
the tokens of a stretch SHARE at full strength and what tells them apart at
1 / sqrt(keys)), so from the second layer on the normed input is mostly
what neighbouring tokens share (54 % of its norm at the fourth layer), every
expert's logit carries that offset, the busiest expert sees 5-7 times the
mean and some see no token (PERF.md section 6, PR 34; rescaling a row, the
hybrid cell's rule, cannot lift an expert whose offset is negative, and
diverged). A trained router does not route on what every token of a stretch
shares (its balance loss sees to it). ``level_router_rows`` therefore, on
the cell's document, BEFORE the program is built, with the plain
reference's float32 layers (``reference/cohere2_moe.py``; nothing of the
program), layer by layer, each layer routed with the rows it has just been
given: (1) ``centred``: makes every expert's row of W_r orthogonal to the
means of the normed input over ``CENTRE_BLOCKS`` stretches of the document
— the loads are then those of independent tokens (the repair of the
divergence, and no more than that); (2) ``weights_hybrid.fit_rows`` on the
centred rows, the hybrid cell's rule as it is (the largest sigmoid scores
are the largest logits, so its top-k is this router's): every row rescaled
only until the busiest HELD expert sees at most ``LEVEL_AT`` (1.1) times
the held experts' mean, then the held rows by one factor until the held
share of the pairs is held / published within ``SHARE_TOL``. One-sided: an
expert under the mean stays where the seed put it, so a held expert takes
five or six 384-row blocks by the seed and ``tune_step_ms`` moves with it
(0.57 % over seven seeds, PERF.md section 6, PR 34): that is the traffic's
own spread, and it is reported, not levelled away.
``with_router_kernels`` puts the kernels into a set of weights AFTER the
jitted generator has run (as constants of that program they would compile
it anew for every seed)."""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from benchmark.harness import weights_lm
from benchmark.harness.weights_hybrid import _scaled, fit_rows

REGEN = {}  # what the program's build called init with, to repeat it
CENTRE_BLOCKS = 32   # stretches of the document whose means the rows leave out


def steer_init() -> None:
    """``cohere2_moe.init_params(key, cfg, dtype)`` becomes the benchmark's
    generator, seeded by the key the program passes."""
    from videop2p_tpu.models import cohere2_moe

    def init(key, cfg, dtype=jnp.bfloat16):
        REGEN["args"] = (cfg, dtype)
        return weights_lm.make_lm_weights(
            cohere2_moe.abstract_params(cfg, dtype),
            jax.random.key_data(key)[-2:])

    cohere2_moe.init_params = init


def regenerate(seed: int, cfg=None, dtype=jnp.bfloat16, rows=None):
    """The same weights again, through the same jitted call the program's
    ``build_token_model`` made (``cfg``: before the program has made any),
    with the router ``rows`` the run was given."""
    from videop2p_tpu.models import cohere2_moe

    if cfg is None:
        cfg, dtype = REGEN["args"]
    tree = jax.jit(lambda key: cohere2_moe.init_params(key, cfg, dtype))(
        jax.random.key(int(seed) % (2 ** 31 - 1)))
    return {"params": with_router_kernels(tree["params"], rows or {})}


def with_router_kernels(params: dict, kernels: dict) -> dict:
    """``params`` with each named layer's ``router/kernel`` replaced."""
    out = dict(params)
    for name, kernel in kernels.items():
        old = params[name]["router"]["kernel"]
        out[name] = {**params[name],
                     "router": {"kernel": jnp.asarray(kernel, old.dtype)}}
    return out


def centred(u, kernel):
    """``kernel`` (h, experts) with every expert's row of W_r made
    orthogonal to the means of the normed tokens ``u`` (T, h) over
    ``CENTRE_BLOCKS`` equal stretches of the document (fewer where the
    document is short: eight tokens a stretch at least), rounded back to
    the leaf's dtype."""
    t_len, h = u.shape
    blocks = max(min(CENTRE_BLOCKS, t_len // 8), 1)
    assert t_len % blocks == 0, (t_len, blocks)
    means = jnp.mean(u.reshape(blocks, t_len // blocks, h), axis=1)
    q = jnp.linalg.qr(means.T)[0]                     # (h, blocks)
    k32 = kernel.astype(jnp.float32)
    return (k32 - q @ (q.T @ k32)).astype(kernel.dtype)


def level_router_rows(flat: dict, arch: dict, ids, row_block=None) -> dict:
    """``{layer name: router kernel (h, num_experts)}`` (on the host, the
    leaf's dtype) for every layer of the weights ``flat`` (by leaf name):
    one float32 forward pass of the plain reference, a layer at a time, each
    layer routed with the rows it has just been given on its ONE normed
    input (the parallel block: the router reads what attention reads) —
    :func:`centred`, then ``weights_hybrid.fit_rows``' scale."""
    import functools

    from benchmark.reference import cohere2_moe as ref

    nx, k = ref._Nx("float32"), arch["num_experts_per_tok"]

    @functools.partial(jax.jit, static_argnums=2)
    def one(fr, x, kind):
        with jax.default_matmul_precision("highest"):
            u = ref._layer_norm(x, fr["input_norm/scale"], arch["layer_norm_eps"])
            kernel = centred(u, fr["router/kernel"])
            kernel = _scaled(kernel, fit_rows(nx, u, kernel, k,
                                              arch["experts_held"]))
            return ref.layer(ref.Weights({**fr, "router/kernel": kernel}, ""),
                             arch, nx, x, kind, row_block=row_block)[0], kernel

    x = ref._embed(ref.Weights(flat), ids)
    out = {}
    for i, kind in enumerate(arch["layer_types"]):
        pre = f"params/layers_{i}/"
        x, kernel = one({n[len(pre):]: v for n, v in flat.items()
                         if n.startswith(pre)}, x, kind)
        out[f"layers_{i}"] = np.asarray(kernel)
    return out
