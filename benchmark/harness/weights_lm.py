"""Seeded weights and the document of a token-model cell, made by the
benchmark — not by the program.

``make_lm_weights(shapes, words)`` fills the pytree of shapes of
``videop2p_tpu.models.deepseek`` (matrices named ``kernel`` with their input
features SECOND TO LAST, expert matrices stacked in front) with values drawn
on the device from the seed, in the dtype each leaf is served in (bfloat16,
as the checkpoint is published). ``harness/weights.py`` takes a kernel's
fan-in as the product of all leading axes, which is wrong for a stacked
leaf; the rest follows it: a few long threefry draws cut into leaves,
every leaf non-zero:
  kernel            N(0, 1/fan_in),   fan_in = shape[-2]
  scale             1 + N(0, 0.05^2)
  embedding, bias   N(0, 0.02^2)
``steer_init()`` puts it in the place of the program's ``init_params``; the
key the program passes (``jax.random.key(seed)``) is traced data, so one
init program serves every seed. The reference is handed these arrays by
name.

One leaf is not left as drawn: each expert layer's SELECTION BIAS
(``router/bias``; the configuration file's ``assumed.selection_bias``). The
published recipe trains it for one thing — to spread the tokens evenly over
the experts (auxiliary-loss-free balancing) — and a checkpoint's routing is
balanced; with a random bias the share of tokens that lands on the 16 experts
held here swings by a tenth from seed to seed (0.056 to 0.070 of the pairs,
my chip runs, PR 28) and the step's time with it. ``balance_routers`` refits
it on the cell's document BEFORE the program is built, with the plain
reference's layer functions in float32 (``reference/deepseek_v32.py``;
nothing of the program): layer by layer it starts from b_e = mean over
experts and tokens of the score, less the mean over tokens of expert e's
score, and then moves each b_e against its expert's excess load, as the
published update does, until the loads are level. ``with_biases`` puts them
into a set of weights AFTER the jitted generator has run: as constants of
that program they would compile it anew for every seed."""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np

from benchmark.harness.weights import GROUP_ELEMENTS, _leaf_name

REGEN = {}  # what the program's build called init with, to repeat it
BALANCE_STEPS, BALANCE_RATE = 60, 0.03  # of the selection bias's refit


def make_lm_weights(shapes, words):
    flat, treedef = jax.tree_util.tree_flatten_with_path(shapes)
    base = jax.random.wrap_key_data(
        jnp.asarray(words, jnp.uint32).reshape(2) ^ jnp.uint32(0x5EED1A5),
        impl="threefry2x32")
    groups, size = [[]], 0          # consecutive leaves, <= GROUP_ELEMENTS
    for item in flat:
        n = math.prod(item[1].shape)
        if groups[-1] and size + n > GROUP_ELEMENTS:
            groups.append([])
            size = 0
        groups[-1].append(item)
        size += n
    leaves = []
    for g, group in enumerate(groups):
        total = sum(math.prod(leaf.shape) for _, leaf in group)
        z_all = jax.random.normal(jax.random.fold_in(base, g),
                                  (-(-total // 1024), 1024),
                                  jnp.float32).reshape(-1)
        at = 0
        for path, leaf in group:
            n = math.prod(leaf.shape)
            z = z_all[at:at + n].reshape(leaf.shape)
            at += n
            last = _leaf_name(path).rsplit("/", 1)[-1]
            if last == "kernel":
                v = z * (1.0 / math.sqrt(leaf.shape[-2]))
            elif last == "scale":
                v = 1.0 + 0.05 * z
            else:
                v = 0.02 * z
            leaves.append(v.astype(leaf.dtype))
    return jax.tree_util.tree_unflatten(treedef, leaves)


def steer_init() -> None:
    """``deepseek.init_params(key, cfg, dtype)`` becomes the generator above,
    seeded by the key the program passes."""
    from videop2p_tpu.models import deepseek

    def init(key, cfg, dtype=jnp.bfloat16):
        REGEN["args"] = (cfg, dtype)
        return make_lm_weights(deepseek.abstract_params(cfg, dtype),
                               jax.random.key_data(key)[-2:])

    deepseek.init_params = init


def regenerate(seed: int, cfg=None, dtype=jnp.bfloat16, biases=None):
    """The same weights again, through the same jitted call the program's
    ``build_token_model`` made (``cfg``: before the program has made any),
    with the selection ``biases`` the run was given."""
    from videop2p_tpu.models import deepseek

    if cfg is None:
        cfg, dtype = REGEN["args"]
    tree = jax.jit(lambda key: deepseek.init_params(key, cfg, dtype))(
        jax.random.key(int(seed) % (2 ** 31 - 1)))
    return {"params": with_biases(tree["params"], biases or {})}


def with_biases(params: dict, biases: dict) -> dict:
    """``params`` with each named layer's ``router/bias`` replaced."""
    out = dict(params)
    for name, bias in biases.items():
        old = params[name]["router"]["bias"]
        out[name] = {**params[name], "router": {
            **params[name]["router"], "bias": jnp.asarray(bias, old.dtype)}}
    return out


def balance_routers(flat: dict, arch: dict, ids, row_block=None) -> dict:
    """``{layer name: bias (n_routed_experts,)}`` (on the host) for every
    expert layer of the weights ``flat`` (by leaf name): one float32 forward
    pass of the plain reference, a layer at a time, each expert layer routed
    with the bias it has just been given."""
    from benchmark.reference import deepseek_v32 as ref

    nx, eps, k = ref._Nx("float32"), arch["rms_norm_eps"], arch[
        "num_experts_per_tok"]
    angles = ref.rope_angles(arch, ids.shape[0])

    def fit(score):
        level = score.shape[0] * k / score.shape[1]

        def nudge(_, b):
            experts = ref.choose_experts(score, b, arch, k)
            load = jnp.sum(experts[:, :, None]
                           == jnp.arange(score.shape[1])[None, None, :],
                           axis=(0, 1))
            return b + BALANCE_RATE * (level - load) / level

        per_expert = jnp.mean(score, axis=0)
        return jax.lax.fori_loop(0, BALANCE_STEPS, nudge,
                                 jnp.mean(per_expert) - per_expert)

    @jax.jit
    def one(fr, x):
        with jax.default_matmul_precision("highest"):
            W = ref.Weights(fr, "")
            if W.has("mlp/gate_proj/kernel"):
                return ref.layer(W, arch, nx, x, angles,
                                 row_block=row_block)[0], None
            a, _ = ref.attention_part(
                W.at("attn"), arch, nx,
                ref._rms_norm(x, W("input_norm/scale"), eps), angles,
                row_block=row_block)
            x = x + a
            y = ref._rms_norm(x, W("post_norm/scale"), eps)
            bias = fit(jax.nn.sigmoid(nx.mm(y, W("router/kernel"))))
            bias = bias.astype(fr["router/bias"].dtype)
            routed, shared, _ = ref.moe_parts(
                ref.Weights({**fr, "router/bias": bias}, ""), arch, nx, y,
                row_block=row_block)
            return x + routed + shared, bias

    x = flat["params/embed/embedding"][ids].astype(jnp.float32)
    out = {}
    for i in range(arch["num_hidden_layers"]):
        pre = f"params/layers_{i}/"
        x, bias = one({n[len(pre):]: v for n, v in flat.items()
                       if n.startswith(pre)}, x)
        if bias is not None:
            out[f"layers_{i}"] = np.asarray(bias)
    return out


def document(seed: int, n_tokens: int, vocab_size: int) -> np.ndarray:
    """The cell's one document: ids uniform over the vocabulary slice, from
    the cell's own seed — the same for every ``--seed``."""
    return np.random.default_rng(int(seed)).integers(
        0, vocab_size, n_tokens, dtype=np.int32)


def fingerprints(named: dict) -> dict:
    """Two wrapping 32-bit sums of each leaf's bits (plain, and weighed by
    position): equal arrays give equal pairs, and a leaf that moved gives
    another pair — so 8 GB of frozen weights can be told unchanged without
    holding a second copy."""

    @jax.jit
    def one(x):
        bits = jax.lax.bitcast_convert_type(
            x, {2: jnp.uint16, 4: jnp.uint32}[x.dtype.itemsize]
        ).astype(jnp.uint32).reshape(-1)
        pos = jnp.arange(bits.shape[0], dtype=jnp.uint32) % 65521 + 1
        return jnp.stack([jnp.sum(bits), jnp.sum(bits * pos)])

    return {k: tuple(int(v) for v in np.asarray(one(x)))
            for k, x in named.items()}
