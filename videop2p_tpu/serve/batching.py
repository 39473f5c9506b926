"""Micro-batching for concurrent edit requests.

Requests are *compatible* when their device programs would be identical:
same program-set (checkpoint/geometry/steps), same pytree structure and
leaf shapes/dtypes of the ``(CachedSource, cond, uncond, ControlContext)``
argument tuple — the structure is the jit cache key, so two compatible
requests stacked on a leading batch axis dispatch through ONE warm
program. :func:`compat_key` derives that identity deterministically from
the abstract argument tree (treedef string + shape/dtype list), never from
object ids.

:func:`plan_batches` is the pure grouping/padding rule (deterministic —
submit order in, batch plan out), kept separate from the engine's threads
so it can be pinned by unit tests. Padding repeats the LAST item of a
group up to the next bucket size (1, 2, 4, ... ≤ max_batch): the compiled
batched program is reused across requests arriving in any count, instead
of compiling one program per observed batch size.

Dispatch modes (:func:`stack_items` feeds both):

  * ``"scan"`` (default) — ``lax.map`` over the batch axis: one host
    dispatch, and each element runs the *same per-item subcomputation* as
    a singleton dispatch, so batched results are bit-exact vs singleton
    (tests pin this). The batch amortizes per-dispatch host overhead, not
    FLOP parallelism.
  * ``"vmap"`` — the batch axis is vectorized and (on a ``data``-sharded
    mesh) partitioned across chips: true data-parallel serving. XLA may
    re-associate floating-point math across the batch dimension, so this
    mode is gated by an allclose test, not a bit-exact pin.

Stdlib+numpy+jax only — the import-guard test walks this package.
"""

from __future__ import annotations

import hashlib
from dataclasses import dataclass
from typing import Any, Callable, Dict, List, Optional, Sequence, Tuple

import jax

__all__ = [
    "Batch",
    "compat_key",
    "plan_batches",
    "bucket_size",
    "stack_items",
    "unstack_outputs",
]

DISPATCH_MODES = ("scan", "vmap")


def compat_key(args_tree: Any, extra: Tuple = ()) -> str:
    """Deterministic batching-compatibility key of a request's device
    argument tree: the pytree structure (static fields of ControlContext /
    CachedSource included — they live in the treedef) plus every leaf's
    shape/dtype, plus any ``extra`` statics the caller bakes into the
    program (step count, guidance scale, program-set identity)."""
    leaves, treedef = jax.tree.flatten(args_tree)
    parts = [repr(extra), str(treedef)]
    for leaf in leaves:
        shape = tuple(getattr(leaf, "shape", ()))
        dtype = str(getattr(leaf, "dtype", type(leaf).__name__))
        parts.append(f"{shape}:{dtype}")
    return hashlib.sha256("|".join(parts).encode()).hexdigest()[:16]


def bucket_size(n: int, max_batch: int) -> int:
    """The padded size for a group of ``n``: the smallest power of two
    ≥ n, capped at ``max_batch`` (so at most ``log2(max_batch)+1`` batched
    program variants ever compile)."""
    if n <= 1:
        return 1
    b = 1
    while b < n:
        b *= 2
    return min(b, max(int(max_batch), 1))


@dataclass
class Batch:
    """One planned dispatch: ``items`` in submit order, padded to
    ``padded_size`` by repeating the last item (``pad`` extra copies)."""

    key: str
    items: List[Any]
    padded_size: int

    @property
    def pad(self) -> int:
        return self.padded_size - len(self.items)

    @property
    def occupancy(self) -> float:
        """Real-slot fraction of the dispatch (ISSUE 19): the cost plane
        prices each dispatch over ``padded_size`` slots, so ``1 -
        occupancy`` is exactly the padding share that lands as
        ``padding_seconds`` in the capacity ledger."""
        return len(self.items) / self.padded_size if self.padded_size else 1.0


def plan_batches(
    items: Sequence[Any],
    *,
    max_batch: int = 4,
    key_fn: Callable[[Any], str] = lambda item: item.compat,
    pad: bool = True,
    order: str = "first_seen",
    arrival_fn: Optional[Callable[[Any], Any]] = None,
) -> List[Batch]:
    """Group ``items`` by compatibility key into dispatch batches.

    Deterministic: groups form in first-seen-key order, items keep their
    submit order inside a group, groups split into chunks of at most
    ``max_batch``, and each chunk pads to its bucket size. A pure function
    of (items, max_batch, order).

    ``order`` picks the DISPATCH order of the planned chunks:

      * ``"first_seen"`` (default, pinned bit-exact vs the pre-scheduler
        engine) — chunks dispatch in first-seen-key order, so every chunk
        of an early rare key precedes a later dominant key's batch;
      * ``"oldest"`` — chunks dispatch by the arrival of their OLDEST
        member (``arrival_fn`` per item; defaults to position in
        ``items``), stable-sorted, so a batch full of early requests is
        never stuck behind a singleton that merely arrived first in its
        key group.
    """
    if max_batch < 1:
        raise ValueError(f"max_batch must be >= 1, got {max_batch}")
    if order not in ("first_seen", "oldest"):
        raise ValueError(
            f"order must be 'first_seen' or 'oldest', got {order!r}"
        )
    arrivals = {id(item): (arrival_fn(item) if arrival_fn is not None else i)
                for i, item in enumerate(items)}
    groups: "Dict[str, List[Any]]" = {}
    seen: List[str] = []
    for item in items:
        k = key_fn(item)
        if k not in groups:
            groups[k] = []
            seen.append(k)
        groups[k].append(item)
    batches: List[Batch] = []
    for k in seen:
        group = groups[k]
        for start in range(0, len(group), max_batch):
            chunk = group[start:start + max_batch]
            size = bucket_size(len(chunk), max_batch) if pad else len(chunk)
            batches.append(Batch(key=k, items=chunk, padded_size=size))
    if order == "oldest":
        batches.sort(key=lambda b: min(arrivals[id(i)] for i in b.items))
    return batches


def stack_items(arg_trees: Sequence[Any], padded_size: int):
    """Stack per-request argument trees on a new leading batch axis,
    repeating the final tree to reach ``padded_size``. All trees must share
    one structure (the compat key guarantees it)."""
    import jax.numpy as jnp

    trees = list(arg_trees)
    if not trees:
        raise ValueError("cannot stack an empty batch")
    trees = trees + [trees[-1]] * (padded_size - len(trees))
    return jax.tree.map(lambda *leaves: jnp.stack(leaves), *trees)


def unstack_outputs(outputs: Any, n: int) -> List[Any]:
    """Split a batched output tree back into ``n`` per-request trees
    (padding entries dropped)."""
    return [jax.tree.map(lambda leaf: leaf[i], outputs) for i in range(n)]
