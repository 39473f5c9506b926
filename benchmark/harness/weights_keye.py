"""Seeded weights of Keye-VL-2.0's cell (``models/keye_vl2.py``), made by the
benchmark — not by the program.

The leaves are ``weights_lm.make_lm_weights``'s as they are — the same few
long threefry draws cut into leaves, the same distributions by leaf name
(kernels N(0, 1/fan_in), scales 1 + N(0, 0.05^2), the embedding and the
scorer's key-norm bias N(0, 0.02^2)). ``steer_init()`` puts the generator
in the place of the program's ``init_params``; the key the program passes
is traced data, so one init program serves every seed. The document is
``weights_lm.document``'s.

One leaf is not left as drawn: each layer's ROUTER (``router/kernel``; the
configuration file's ``assumed.router_rows``). This router has no bias to
refit (a softmax over 128 logits, no groups, no correction), and a trained
router's loads are level (its balance loss sees to it). ``level_router_rows``
therefore, on the cell's document, BEFORE the program is built, with the
plain reference's float32 layers (``reference/keye_vl2.py``; nothing of the
program), layer by layer, each layer routed with the rows it has just been
given, fits the rows by the hybrid cell's rule as it is
(``weights_hybrid.fit_rows``: every expert's row rescaled until the busiest
HELD expert sees at most ``LEVEL_AT`` (1.1) times the held mean, and no
further; a softmax's top-k is its logits' top-k). Where the rows alone do
not get there — what the tokens of a stretch of the document share decides
the routing, as in the third token family's cell with random weights — it
makes the rows orthogonal to the stretches' means first
(``weights_command.centred``) and fits those. Every expert is held here, so
the held share is 1 and ``fit_rows`` never moves it. ``with_router_kernels``
(the third cell's) puts the kernels into a set of weights AFTER the jitted
generator has run."""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from benchmark.harness import weights_lm
from benchmark.harness.weights_command import centred, with_router_kernels
from benchmark.harness.weights_hybrid import LEVEL_AT, _scaled, fit_rows

REGEN = {}  # what the program's build called init with, to repeat it


def steer_init() -> None:
    """``keye_vl2.init_params(key, cfg, dtype)`` becomes the benchmark's
    generator, seeded by the key the program passes."""
    from videop2p_tpu.models import keye_vl2

    def init(key, cfg, dtype=jnp.bfloat16):
        REGEN["args"] = (cfg, dtype)
        return weights_lm.make_lm_weights(keye_vl2.abstract_params(cfg, dtype),
                                          jax.random.key_data(key)[-2:])

    keye_vl2.init_params = init


def regenerate(seed: int, cfg=None, dtype=jnp.bfloat16, rows=None):
    """The same weights again, through the same jitted call the program's
    ``build_token_model`` made (``cfg``: before the program has made any),
    with the router kernels ``rows`` the run was given."""
    from videop2p_tpu.models import keye_vl2

    if cfg is None:
        cfg, dtype = REGEN["args"]
    tree = jax.jit(lambda key: keye_vl2.init_params(key, cfg, dtype))(
        jax.random.key(int(seed) % (2 ** 31 - 1)))
    return {"params": with_router_kernels(tree["params"], rows or {})}


def level_kernel(nx, y, kernel, k: int, held):
    """``(kernel, centred?)``: the router ``kernel`` (h, experts) with its
    rows fitted on the normed tokens ``y`` by ``fit_rows``, from the rows as
    drawn where that levels the held experts, else from the rows made
    orthogonal to the stretches' means."""
    first, count = held

    def fitted(kernel):
        kernel = _scaled(kernel, fit_rows(nx, y, kernel, k, held))
        chosen = jax.lax.top_k(nx.mm(y, kernel), k)[1]
        load = jnp.sum(chosen[:, :, None] == jnp.arange(first, first + count),
                       axis=(0, 1))
        return kernel, jnp.max(load) * count <= LEVEL_AT * jnp.sum(load)

    plain, level = fitted(kernel)
    other = fitted(centred(y, kernel))[0]
    return jnp.where(level, plain, other), ~level


def level_router_rows(flat: dict, arch: dict, ids, row_block=None,
                      centred_layers=None) -> dict:
    """``{layer name: router kernel (h, num_experts)}`` (on the host, the
    leaf's dtype) for every layer of the weights ``flat`` (by leaf name):
    one float32 forward pass of the plain reference, a layer at a time,
    each layer routed with the kernel :func:`level_kernel` has just given
    it. The names of the layers whose rows were centred first are appended
    to ``centred_layers`` where one is given."""
    from benchmark.reference import keye_vl2 as ref

    nx, k, eps = ref._Nx("float32"), arch["num_experts_per_tok"], arch[
        "rms_norm_eps"]
    t_len = int(ids.shape[0])

    @functools.partial(jax.jit, static_argnums=2)
    def one(fr, x, t_len):
        with jax.default_matmul_precision("highest"):
            W = ref.Weights(fr, "")
            u = ref._rms_norm(x, W("input_norm/scale"), eps)
            mask = ref.selection(W.at("attn/indexer"), arch, nx, u,
                                 ref.indexer_angles(arch, t_len),
                                 row_block=row_block)
            x = x + ref.attention_part(W.at("attn"), arch, nx, u,
                                       ref.rope_angles(arch, t_len), mask,
                                       row_block=row_block)
            y = ref._rms_norm(x, W("post_norm/scale"), eps)
            kernel, was_centred = level_kernel(nx, y, fr["router/kernel"], k,
                                               arch["experts_held"])
            routed, _ = ref.moe_part(
                ref.Weights({**fr, "router/kernel": kernel}, ""), arch, nx, y,
                row_block=row_block)
            return x + routed, kernel, was_centred

    x = ref._embed(ref.Weights(flat), ids)
    out = {}
    for i in range(arch["num_hidden_layers"]):
        pre = f"params/layers_{i}/"
        x, kernel, was_centred = one({n[len(pre):]: v for n, v in flat.items()
                                      if n.startswith(pre)}, x, t_len)
        out[f"layers_{i}"] = np.asarray(kernel)
        if centred_layers is not None and bool(was_centred):
            centred_layers.append(f"layers_{i}")
    return out
