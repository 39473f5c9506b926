"""Cached-source fast editing: replay the source stream from inversion.

The reference's fast mode keeps the source stream in the CFG batch and
re-predicts its ε from the drifting latent every step
(/root/reference/tuneavideo/pipelines/pipeline_tuneavideo.py:412-415) — one
full UNet stream spent on an *approximate* replay of the DDIM inversion.
Here the replay is free and exact: DDIM ``next_step``/``prev_step`` are
linear in (x, ε) with identical coefficients, so the source latent at edit
step *i* IS ``trajectory[N−i]`` — no forward needed. The edit batch drops
from (P−1)+P to (P−1)+(P−1) streams (33 % fewer UNet streams at P=2).

What the dropped stream used to provide, and where it comes from now:

  * its ε — unnecessary: the latent path is read straight off the reversed
    inversion trajectory (exact where the reference drifts);
  * base attention maps for the controllers — captured during inversion
    (``attn_base`` collection, full per-head probs) at the steps that need
    them. The cross gate ``cross_replace_alpha[i]`` is zero past its window
    and the temporal gate is a [lo, hi) step window
    (run_videop2p.py:304-317) — outside the windows the edited output equals
    the unedited edit-stream maps, so capturing ONLY the gated steps is
    semantically exact and is what keeps the cache inside HBM (rabbit-jump:
    ~3 GB vs ~13 GB for all 50 steps);
  * its LocalBlend store contribution — captured per step as the already
    head-meaned, blend-site-stacked tensor (tiny).

One disclosed approximation: the captured maps come from the inversion
forward at ``(trajectory[j], t_j)`` while a live source stream would compute
them at ``(trajectory[j+1], t_j)`` — the same timestep, one trajectory
position earlier. The latent replay itself is exact; only the controllers'
*base maps* carry this one-position offset (they are semantic layout guides,
and the reference's own fast mode feeds the controllers maps from a drifted
latent).
"""

from __future__ import annotations

from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from flax import struct

__all__ = [
    "CachedSource",
    "capture_windows",
    "check_subset_windows",
    "filter_site_tree",
    "merge_site_trees",
    "slice_site_tree",
    "tree_bytes",
    "validate_step_positions",
]


def capture_windows(ctx, num_steps: int) -> Tuple[int, Tuple[int, int]]:
    """The gate rule that decides which inversion steps must capture maps:
    cross base maps are only read while ANY word's ``cross_replace_alpha`` is
    nonzero (a step prefix — conservative for per-word dict schedules), and
    temporal base maps only inside the self-replace window. Returns
    ``(cross_len, (self_lo, self_hi))``. Shared by the CLI, the serving
    programs and the tests so the rule cannot drift between them."""
    import numpy as np

    cra = np.asarray(jax.device_get(ctx.cross_replace_alpha))[:num_steps]
    active = (cra != 0).any(axis=tuple(range(1, cra.ndim)))
    cross_len = int(active.nonzero()[0].max()) + 1 if active.any() else 0
    return cross_len, ctx.self_replace_range


def validate_step_positions(positions, base_steps: int):
    """Normalize/validate a timestep-subset walk's positions into the
    ``base_steps`` edit-order grid (``DDIMScheduler.subset_positions`` is
    the canonical producer). Strictly increasing, starting at 0 (the
    subset walk must begin at the same x_T the capture did), ending inside
    the base grid. Returns an int64 numpy array."""
    import numpy as np

    pos = np.asarray(positions, dtype=np.int64)
    if pos.ndim != 1 or pos.size < 1:
        raise ValueError(f"step_positions must be a 1-D sequence, got {positions!r}")
    if pos[0] != 0:
        raise ValueError(
            f"step_positions must start at 0 (the capture's x_T), got {pos[0]}"
        )
    if pos.size > 1 and (np.diff(pos) <= 0).any():
        raise ValueError(f"step_positions must be strictly increasing: {pos.tolist()}")
    if pos[-1] >= base_steps:
        raise ValueError(
            f"step_positions reach {pos[-1]} but the capture covers "
            f"[0, {base_steps})"
        )
    return pos


def check_subset_windows(ctx, cached, positions, num_steps: int) -> None:
    """Host-side gate-coverage check for a timestep-subset edit over a
    ``cached`` capture: every subset step whose controller gate is OPEN
    must map (via ``positions``) inside the captured base window — a step
    outside it would silently read a clamped/stale base map. Requires a
    CONCRETE controller (call before tracing; the serving layer does)."""
    import numpy as np

    if ctx is None or ctx.kind == "empty":
        return
    cross_len_sub, (lo_s, hi_s) = capture_windows(ctx, num_steps)
    pos = np.asarray(positions)
    if cross_len_sub > 0:
        mapped = pos[:cross_len_sub]
        if cached.cross_len <= 0 or int(mapped.max()) >= cached.cross_len:
            raise ValueError(
                f"subset cross window maps to base steps {mapped.tolist()} "
                f"outside the captured cross window [0, {cached.cross_len})"
            )
    if hi_s > lo_s:
        mapped = pos[lo_s:hi_s]
        lo_b, hi_b = cached.self_window
        if mapped.size and (int(mapped.min()) < lo_b or int(mapped.max()) >= hi_b):
            raise ValueError(
                f"subset self window maps to base steps {mapped.tolist()} "
                f"outside the captured self window [{lo_b}, {hi_b})"
            )


def filter_site_tree(tree: Dict[str, Any], site_name: str) -> Dict[str, Any]:
    """Keep only the subtrees whose path ends at a module named ``site_name``
    (``"attn2"`` for cross sites, ``"attn_temp"`` for temporal sites)."""
    out: Dict[str, Any] = {}
    for k, v in tree.items():
        if k == site_name:
            out[k] = v
        elif isinstance(v, dict):
            sub = filter_site_tree(v, site_name)
            if sub:
                out[k] = sub
    return out


def merge_site_trees(a: Optional[Dict[str, Any]], b: Optional[Dict[str, Any]]) -> Dict[str, Any]:
    """Deep-merge two nested site trees with disjoint leaves."""
    if not a:
        return dict(b or {})
    if not b:
        return dict(a)
    out = dict(a)
    for k, v in b.items():
        if k in out and isinstance(out[k], dict) and isinstance(v, dict):
            out[k] = merge_site_trees(out[k], v)
        else:
            out[k] = v
    return out


def slice_site_tree(tree: Optional[Dict[str, Any]], index: jax.Array) -> Optional[Dict[str, Any]]:
    """Index every leaf's leading (step-window) axis at a traced index."""
    if not tree:
        return None
    return jax.tree.map(
        lambda a: jax.lax.dynamic_index_in_dim(a, index, axis=0, keepdims=False), tree
    )


def tree_bytes(tree) -> int:
    """Total bytes of the array (or ShapeDtypeStruct) leaves of a pytree."""
    import math

    return sum(
        math.prod(leaf.shape) * leaf.dtype.itemsize
        for leaf in jax.tree.leaves(tree)
        if hasattr(leaf, "dtype") and hasattr(leaf, "shape")
    )


class CachedSource(struct.PyTreeNode):
    """Everything the cached-source edit scan reads in place of a live source
    stream. All step-indexed arrays are in EDIT-step order (the reverse of
    the inversion walk that produced them).
    """

    # (num_steps+1, 1, F, h, w, C) — reversed trajectory: [i] is the source
    # latent entering edit step i; [i+1] the latent after it; [-1] is x_0
    src_latents: jax.Array
    # nested {path: {"probs": (cross_len, F, H, Q, W)}} for attn2 sites,
    # covering edit steps [0, cross_len); None/{} when no cross edit
    cross_maps: Optional[Dict[str, Any]] = None
    # nested {path: {"probs": (hi−lo, D, H, F, F)}} for attn_temp sites,
    # covering edit steps [lo, hi); None/{} when no temporal edit
    temporal_maps: Optional[Dict[str, Any]] = None
    # (num_steps, 1, F, S, r, r, L) — the source stream's per-step LocalBlend
    # store contribution; None when no blend is configured
    blend_seq: Optional[jax.Array] = None

    # step windows the maps cover (static)
    cross_len: int = struct.field(pytree_node=False, default=0)
    self_window: Tuple[int, int] = struct.field(pytree_node=False, default=(0, 0))

    def _capture_compute_dtype(self):
        """The dtype the capture's full-precision maps carry — the upcast
        target for float8-stored temporal maps. Sibling cross maps first
        (same capture forward, same probability compute dtype), then the
        blend sequence; float32 when every wide sibling was elided (a
        temporal-only capture declares no other precision)."""
        for tree in (self.cross_maps, self.blend_seq):
            for leaf in jax.tree.leaves(tree):
                if (
                    hasattr(leaf, "dtype")
                    and jnp.dtype(leaf.dtype).itemsize > 1
                ):
                    return leaf.dtype
        return jnp.float32

    def base_tree_at(self, step_index: jax.Array) -> Optional[Dict[str, Any]]:
        """Per-step base-map tree for :class:`AttnControl.cached_base`.

        Outside a window the slice index clamps to the window edge — the
        stale value is provably unused because the corresponding gate
        (cross_replace_alpha / the self-replace window) multiplies it out.
        """
        cross = None
        if self.cross_maps and self.cross_len > 0:
            idx = jnp.clip(step_index, 0, self.cross_len - 1)
            cross = slice_site_tree(self.cross_maps, idx)
        temporal = None
        lo, hi = self.self_window
        if self.temporal_maps and hi > lo:
            idx = jnp.clip(step_index - lo, 0, hi - lo - 1)
            temporal = slice_site_tree(self.temporal_maps, idx)
            # maps may be STORED in a narrow float8 (the long-video budget
            # mode, inversion.py temporal_maps_dtype) — upcast at read to
            # the dtype the sibling captured maps carry (the capture's
            # probability compute dtype), NOT a hardcoded bf16: in an fp32
            # run a bf16 upcast would silently narrow the replaced base
            # maps while the cross maps stay fp32
            target = self._capture_compute_dtype()

            def _widen(a):
                dt = jnp.dtype(a.dtype)
                if dt.itemsize != 1:
                    return a
                if jnp.issubdtype(dt, jnp.integer):
                    # int8 fixed-point storage (inversion.py encodes
                    # round(p·127)) — decode, not just upcast
                    return a.astype(target) / jnp.asarray(127.0, target)
                return a.astype(target)

            temporal = jax.tree.map(_widen, temporal)
        if cross is None and temporal is None:
            return None
        return merge_site_trees(cross, temporal)

    @property
    def num_steps(self) -> int:
        return self.src_latents.shape[0] - 1
