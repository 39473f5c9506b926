"""The fused cached-source fast edit: capture-inversion + controlled edit
as one traceable function.

One device program = one host dispatch, and the multi-GiB capture trees
never surface as program outputs. What the CLI (cli/run_videop2p.py) runs,
so a measurement of this function is a measurement of the users' program.
"""

from __future__ import annotations

from typing import Callable, Optional, Tuple

import jax

from videop2p_tpu.control.controllers import ControlContext
from videop2p_tpu.core.ddim import DDIMScheduler
from videop2p_tpu.core.noise import DependentNoiseSampler
from videop2p_tpu.pipelines.inversion import ddim_inversion_captured
from videop2p_tpu.pipelines.sampling import UNetFn, edit_sample

__all__ = [
    "cached_fast_edit",
    "capture_shapes",
    "maps_budget_decision",
    "choose_cached_maps",
]


def choose_cached_maps(shapes_for, *, sp: int = 1, budget_gb: float = 6.0):
    """Escalating cached-mode decision shared by the CLI and bench: try
    full-precision (bf16) capture first; if the per-chip budget refuses,
    retry with the temporal maps stored at one byte per probability — the
    quadratic-in-frames tree is 8f: 0.6 GiB → 24f: 5.8 GiB at bf16 SD
    scale, 0.3 GiB → 2.9 GiB at 1 byte. Two 1-byte encodings, tried in
    order:

      * ``float8_e4m3fn`` (where this jax exposes it): ~6 % relative step
        on [0,1] probabilities — about one significant decimal digit, with
        sub-~2e-3 values in subnormals;
      * ``int8`` fixed-point (always available): ``round(p·127)`` — a
        UNIFORM 1/254 ≈ 0.004 absolute step, so mid-range probabilities
        quantize FINER than e4m3 while tiny ones coarser; encode/decode at
        the capture/replay seams (pipelines/inversion.py ↔
        ``CachedSource.base_tree_at``).

    Both are acceptable because the empirical edit-output delta test
    (tests/test_cached.py) gates them, and only the edit stream's map
    replacement reads them, never the exact source replay.

    ``shapes_for(temporal_maps_dtype)`` must return the
    :func:`capture_shapes` CachedSource shape tree for that storage dtype.

    Returns ``(use_cached, temporal_maps_dtype, map_gb, per_chip_gb)`` —
    dtype None means full precision.
    """
    import jax.numpy as jnp

    candidates = [None]
    if hasattr(jnp, "float8_e4m3fn"):
        candidates.append(jnp.float8_e4m3fn)
    candidates.append(jnp.int8)
    for dt in candidates:
        fits, map_gb, per_chip_gb = maps_budget_decision(
            shapes_for(dt), sp=sp, budget_gb=budget_gb
        )
        if fits:
            return True, dt, map_gb, per_chip_gb
    return False, None, map_gb, per_chip_gb


def maps_budget_decision(cached_shapes, *, sp: int = 1,
                         budget_gb: float = 6.0):
    """The cached-mode HBM gate, shared by the CLI and tests: given the
    :func:`capture_shapes` result, decide whether the capture trees fit the
    per-chip budget. On a frame-sharded mesh the maps shard over frames /
    spatial positions, so each chip holds 1/sp of the global bytes — which
    is exactly what makes the 24/32-frame long-video configs take the
    cached path on a slice while a single chip falls back to the live
    stream (cli/run_videop2p.py; VERDICT r4 item 5).

    Returns ``(use_cached, map_gb, per_chip_gb)``.
    """
    from videop2p_tpu.pipelines.cached import tree_bytes

    map_gb = tree_bytes(
        (cached_shapes.cross_maps, cached_shapes.temporal_maps)
    ) / 2**30
    per_chip_gb = map_gb / max(int(sp), 1)
    return per_chip_gb <= budget_gb, map_gb, per_chip_gb


def capture_shapes(
    unet_fn: UNetFn,
    params,
    scheduler: DDIMScheduler,
    latents,
    cond_src,
    ctx: Optional[ControlContext],
    *,
    num_inference_steps: int = 50,
    cross_len: int = 0,
    self_window: Tuple[int, int] = (0, 0),
    dependent_weight: float = 0.0,
    dependent_sampler: Optional[DependentNoiseSampler] = None,
    temporal_maps_dtype=None,
):
    """``eval_shape`` of the EXACT capture :func:`cached_fast_edit` will run
    — for HBM budgeting (cli/run_videop2p.py). Sharing the call site means a
    change to the fused program's capture cannot desynchronize the budget
    check that gates it. Returns the (trajectory, CachedSource) shape tree.
    """
    return jax.eval_shape(
        lambda p, x, k: ddim_inversion_captured(
            unet_fn, p, scheduler, x, cond_src,
            num_inference_steps=num_inference_steps,
            cross_len=cross_len,
            self_window=self_window,
            capture_blend=ctx is not None and ctx.blend is not None,
            dependent_weight=dependent_weight,
            dependent_sampler=dependent_sampler,
            key=k,
            temporal_maps_dtype=temporal_maps_dtype,
        ),
        params, latents, jax.random.key(0),
    )


def cached_fast_edit(
    unet_fn: UNetFn,
    params,
    scheduler: DDIMScheduler,
    latents: jax.Array,
    cond_src: jax.Array,
    cond_all: jax.Array,
    uncond: jax.Array,
    ctx: Optional[ControlContext],
    *,
    num_inference_steps: int = 50,
    guidance_scale: float = 7.5,
    cross_len: int = 0,
    self_window: Tuple[int, int] = (0, 0),
    dependent_weight: float = 0.0,
    dependent_sampler: Optional[DependentNoiseSampler] = None,
    key: Optional[jax.Array] = None,
    temporal_maps_dtype=None,
    telemetry: bool = False,
    device_probe: Optional[Callable] = None,
    attn_maps: bool = False,
    reuse_schedule: Optional[str] = None,
    student_head: Optional[dict] = None,
) -> Tuple[jax.Array, jax.Array]:
    """Capture-inversion of ``latents`` under ``cond_src`` followed by the
    cached-source controlled edit under ``cond_all``/``uncond``. Returns
    ``(trajectory, edited_latents)`` — the trajectory for persistence, the
    (P, F, h, w, C) output with stream 0 the exact reconstruction.
    ``telemetry=True`` adds the edit scan's per-step telemetry
    (sampling.edit_sample) riding the same fused program; ``device_probe``
    (obs.comm.make_device_probe) adds per-device stats + cross-replica
    divergence of the edit scan's latents the same way; ``attn_maps=True``
    adds the attention observability capture (obs.attention) as
    ``{"inversion": ..., "edit": ...}`` — the source stream's heatmaps from
    the inversion walk plus the edit streams' heatmaps / entropies / blend
    mask series. Return order ``(trajectory, edited[, tel][, dev][, attn])``;
    all off by default, leaving the program byte-identical.
    ``reuse_schedule`` enables cross-step deep-feature reuse in the edit
    scan (pipelines/reuse.py) — the inversion capture always runs the full
    UNet (its maps feed the controllers); "off"/None is pinned
    byte-identical. ``student_head`` runs the edit scan as the
    consistency-distilled student (train/distill.py) — the inversion
    capture stays the TEACHER's (its maps and trajectory feed the
    controllers and the exact source replay); None is pinned
    byte-identical."""
    inv = ddim_inversion_captured(
        unet_fn, params, scheduler, latents, cond_src,
        num_inference_steps=num_inference_steps,
        cross_len=cross_len,
        self_window=self_window,
        capture_blend=ctx is not None and ctx.blend is not None,
        dependent_weight=dependent_weight,
        dependent_sampler=dependent_sampler,
        key=key,
        temporal_maps_dtype=temporal_maps_dtype,
        attn_maps=attn_maps,
    )
    trajectory, cached = inv[0], inv[1]
    edited = edit_sample(
        unet_fn, params, scheduler, trajectory[-1], cond_all, uncond,
        num_inference_steps=num_inference_steps,
        guidance_scale=guidance_scale,
        ctx=ctx,
        source_uses_cfg=False,
        cached_source=cached,
        telemetry=telemetry,
        device_probe=device_probe,
        attn_maps=attn_maps,
        reuse_schedule=reuse_schedule,
        student_head=student_head,
    )
    if not (telemetry or device_probe is not None or attn_maps):
        return trajectory, edited
    edited, *extras = edited
    out = (trajectory, edited)
    if telemetry:
        out += (extras.pop(0),)
    if device_probe is not None:
        out += (extras.pop(0),)
    if attn_maps:
        out += ({"inversion": inv[2], "edit": extras.pop(0)},)
    return out
