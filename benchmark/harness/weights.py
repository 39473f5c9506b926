"""Seeded weights, made by the benchmark — not by the program.

``make_weights(shapes, seed_words(seed), tag)`` fills a pytree of shapes with values
drawn on the device from ``seed``, in the dtype each leaf is served in. It is
meant to be traced inside ONE jit (the program's ``build_models`` wraps the
module's ``init`` in ``jax.jit``, and ``steer.seeded_weights`` puts this
function in ``init``'s place), so all leaves of a model are one program.

A few long threefry draws cut into leaves keep the init program small (the program's own flax init is an
88k-instruction program, PERF.md PR 21).

Distribution, by the leaf's name — chosen so that every leaf is non-zero
(a zero leaf hides faults from the comparison with the reference) and
activations keep a sane scale through the depth:
  kernel            N(0, 1/fan_in),   fan_in = prod(shape[:-1])
  embedding         N(0, 0.02^2)
  scale             1 + N(0, 0.05^2)
  bias and others   N(0, 0.02^2)
The reference is handed these same arrays by name; it never sees the
program's modules."""

from __future__ import annotations

import math
import zlib

import jax
import jax.numpy as jnp


def _leaf_name(path) -> str:
    return "/".join(str(getattr(p, "key", getattr(p, "name", p)))
                    for p in path)


def seed_words(seed: int):
    """The two uint32 words a seed enters the init program as — traced data,
    so that every seed runs the SAME compiled program (a seed baked into the
    program as a constant would recompile the init for every seed)."""
    return jax.random.key_data(jax.random.key(int(seed) % (2 ** 31 - 1)))


GROUP_ELEMENTS = 1 << 25  # 32 Mi values (128 MB of float32) per draw


def make_weights(shapes, words, tag: str):
    """``words``: uint32[2] from ``seed_words`` (or the key data of the
    ``jax.random.key(seed)`` the program hands its ``init``).

    The leaves are cut from a few long normal vectors (one RNG op per
    ``GROUP_ELEMENTS`` values), not drawn one by one: 800 RNG ops of their
    own made the init program slower to compile than the model (423 s for
    the serve cell's three models, my chip run, PR 25), and one vector for
    all leaves would set the process's memory peak above the cell's own."""
    flat, treedef = jax.tree_util.tree_flatten_with_path(shapes)
    words = jnp.asarray(words, jnp.uint32).reshape(2)
    salt = jnp.uint32(zlib.crc32(tag.encode()))
    # threefry, not XLA's RngBitGenerator: on this chip the generator's own
    # (n/4, 2, 2) blocks tile to 256 bytes a value (34 GB asked for one
    # group, described-chip compile, PR 25)
    base = jax.random.wrap_key_data(
        words ^ jnp.stack([salt, salt >> 1]), impl="threefry2x32")
    # only the "params" collection holds weights; what a module sows while
    # it is initialised (attention maps) is left at zero
    made = {}
    weights = [(p, leaf) for p, leaf in flat
               if _leaf_name(p).split("/", 1)[0] == "params"]
    groups, size = [[]], 0          # consecutive leaves, <= GROUP_ELEMENTS
    for item in weights:
        n = math.prod(item[1].shape)
        if groups[-1] and size + n > GROUP_ELEMENTS:
            groups.append([])
            size = 0
        groups[-1].append(item)
        size += n
    for g, group in enumerate(groups):
        total = sum(math.prod(leaf.shape) for _, leaf in group)
        rows = -(-total // 1024)
        z_all = jax.random.normal(jax.random.fold_in(base, g), (rows, 1024),
                                  jnp.float32).reshape(-1)
        at = 0
        for path, leaf in group:
            n = math.prod(leaf.shape)
            z = z_all[at:at + n].reshape(leaf.shape)
            at += n
            last = _leaf_name(path).rsplit("/", 1)[-1]
            if last == "kernel":
                fan_in = max(math.prod(leaf.shape[:-1]), 1)
                v = z * (1.0 / math.sqrt(fan_in))
            elif last == "scale":
                v = 1.0 + 0.05 * z
            else:
                v = 0.02 * z
            made[_leaf_name(path)] = v.astype(leaf.dtype)
    leaves = [made[_leaf_name(p)] if _leaf_name(p) in made
              else jnp.zeros(leaf.shape, leaf.dtype) for p, leaf in flat]
    return jax.tree_util.tree_unflatten(treedef, leaves)


def flatten_named(tree) -> dict:
    """``{"a/b/kernel": array}`` — the form the reference takes weights in."""
    flat, _ = jax.tree_util.tree_flatten_with_path(tree)
    return {_leaf_name(p): v for p, v in flat}
