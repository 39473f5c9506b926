"""The attention-controlled denoising loop (Stage-2 editing / validation
sampling).

TPU-native re-design of ``TuneAVideoPipeline.__call__``'s denoise loop
(/root/reference/tuneavideo/pipelines/pipeline_tuneavideo.py:321-441) as one
``lax.scan`` under ``jit``:

  * CFG batch ``[uncond×P, cond×P]`` (pipeline_tuneavideo.py:235);
  * per-step null-embedding injection — the optimized uncond embedding for
    step *i* replaces the static one (pipeline_tuneavideo.py:399-403);
  * fast-mode source branch: the source stream's prediction is its cond-only
    output so DDIM inversion replays exactly (pipeline_tuneavideo.py:412-415);
  * scheduler step with optional η-variance noise from the dependent sampler
    (dependent_ddim.py:320-334), key-threaded;
  * the controller sees every text-cross/temporal attention site via the
    functional control context, and LocalBlend runs as the step callback on a
    running sum of blend-site maps carried through the scan
    (pipeline_tuneavideo.py:423-424, run_videop2p.py:261-291).

The pipeline operates purely in latent space; VAE encode/decode and text
encoding are the caller's (CLI's) concern — that keeps this scan free of
host I/O and lets the whole edit jit to one XLA program.
"""

from __future__ import annotations

from typing import Callable, Optional, Tuple

import jax
import jax.numpy as jnp

from videop2p_tpu.control.controllers import ControlContext
from videop2p_tpu.control.local_blend import blend_mask, local_blend
from videop2p_tpu.core.ddim import DDIMScheduler
from videop2p_tpu.core.noise import DependentNoiseSampler
from videop2p_tpu.models.attention import AttnControl
from videop2p_tpu.obs.attention import ATTN_HEAT_RES, attn_step_record
from videop2p_tpu.obs.telemetry import latent_stats
from videop2p_tpu.pipelines.cached import CachedSource
from videop2p_tpu.pipelines.stores import blend_maps_from_store

__all__ = ["edit_sample", "make_unet_fn", "official_edit"]

# (params, sample, t, text, control) -> (eps, attn_store)
UNetFn = Callable[..., Tuple[jax.Array, dict]]

# jitted official-mode programs, keyed on the statics their closures bake in
# (bounded FIFO — same discipline as inversion.py's program caches)
_OFFICIAL_EDIT_CACHE: dict = {}
_OFFICIAL_EDIT_CACHE_MAX = 4


def _controller_gates(ctx: Optional[ControlContext], i) -> dict:
    """Per-step controller edit activity, as fixed-shape scalars for the
    telemetry stream: the mean cross-replace gate at step ``i`` (the alpha
    that blends source maps into the edit streams) and whether the
    self/temporal replacement window covers the step. ``i`` may be traced."""
    if ctx is None:
        return {"cross_gate_mean": jnp.asarray(0.0, jnp.float32),
                "self_edit_active": jnp.asarray(0, jnp.int32)}
    lo, hi = ctx.self_replace_range
    return {
        "cross_gate_mean": jnp.mean(ctx.cross_replace_alpha[i]).astype(jnp.float32),
        "self_edit_active": jnp.logical_and(i >= lo, i < hi).astype(jnp.int32),
    }


def _mask_series_entry(maps_sum, blend_cfg, step_index, latent_hw):
    """The LocalBlend observability channels for one step: the mask the
    blend used (obs.attention's pooled resolution), its per-stream/frame
    coverage fraction, and whether the blend gate was open."""
    mask = blend_mask(maps_sum, blend_cfg, latent_hw).astype(jnp.float32)
    pooled = jax.image.resize(
        mask, mask.shape[:2] + ATTN_HEAT_RES, method="linear"
    )
    return {
        "mask_cov": mask.mean(axis=(2, 3)),
        "mask_heat": pooled,
        "blend_active": (step_index >= blend_cfg.start_blend).astype(jnp.int32),
    }


def _pack_step_outputs(telemetry, tel, attn_maps, attn, dev=None):
    """Scan ``ys`` for the optional observability channels (None when all
    are off, so the off-path scan is the exact pre-observability scan)."""
    ys = {}
    if telemetry:
        ys["tel"] = tel
    if dev is not None:
        ys["dev"] = dev
    if attn_maps:
        ys["attn"] = attn
    return ys or None


def make_unet_fn(model) -> UNetFn:
    """Adapter from a linen UNet module to the pipeline's callable contract.

    Quantized parameter trees (``models/quant.py`` :class:`QuantizedTensor`
    leaves, produced by ``convert.quantize_unet_params`` at load time) are
    dequantized INSIDE the traced fn to the model's compute dtype — the
    low-precision weights stay the compiled program's inputs (the
    bytes-accessed win) and the upcast happens at the matmul seam, the same
    convention as the float8 temporal-map capture. Unquantized trees pass
    through untouched, so the off path's program is byte-identical.

    ``deep_mode``/``deep_feature`` forward the DeepCache reuse seam to the
    model (see :meth:`UNet3DConditionModel.__call__`); the default
    ``"full"`` call is exactly the pre-reuse adapter.
    """
    from videop2p_tpu.models.quant import QuantizedTensor, dequantize_tree

    def fn(params, sample, t, text, control=None, *, deep_mode="full",
           deep_feature=None):
        # init() also returns sown collections (sow runs during init);
        # passing them back into apply would make sow append a second entry
        # per site — keep only the parameter collections.
        variables = {
            k: v for k, v in params.items() if k not in ("attn_store", "attn_base")
        }
        if any(isinstance(x, QuantizedTensor) for x in jax.tree_util.tree_leaves(
                variables, is_leaf=lambda x: isinstance(x, QuantizedTensor))):
            variables = dequantize_tree(variables, model.dtype)
        kwargs = ({} if deep_mode == "full"
                  else {"deep_mode": deep_mode, "deep_feature": deep_feature})
        out, store = model.apply(
            variables, sample, t, text, control,
            mutable=["attn_store", "attn_base"], **kwargs
        )
        return out, store

    return fn


def edit_sample(
    unet_fn: UNetFn,
    params,
    scheduler: DDIMScheduler,
    latents: jax.Array,
    cond_embeddings: jax.Array,
    uncond_embeddings: jax.Array,
    *,
    num_inference_steps: int = 50,
    guidance_scale: float = 7.5,
    ctx: Optional[ControlContext] = None,
    source_uses_cfg: bool = True,
    eta: float = 0.0,
    key: Optional[jax.Array] = None,
    dependent_sampler: Optional[DependentNoiseSampler] = None,
    blend_res: Optional[Tuple[int, int]] = None,
    null_uncond_embeddings: Optional[jax.Array] = None,
    cached_source: Optional[CachedSource] = None,
    step_positions=None,
    telemetry: bool = False,
    device_probe: Optional[Callable] = None,
    attn_maps: bool = False,
    reuse_schedule: Optional[str] = None,
    student_head: Optional[dict] = None,
) -> jax.Array:
    """Run the controlled denoise loop; returns final latents (P, F, h, w, C).

    ``latents``: x_T, shape (1, F, h, w, C) or (P, F, h, w, C) — a batch-1
    latent is expanded so source & edit share x_T (the reference's
    ``prepare_latents`` expansion, pipeline_tuneavideo.py:312-314).
    ``cond_embeddings``: (P, L, D) text embeddings, source prompt first.
    ``uncond_embeddings``: (L, D) or (1, L, D) — the raw encoder uncond used
    by every stream.
    ``null_uncond_embeddings``: optional per-step null-text optimization
    output, (num_steps, L, D) or (num_steps, 1, L, D) — injected into the
    SOURCE stream's uncond slot only each step; the edit streams keep the raw
    uncond (the reference's ``text_embeddings[0] = uncond_embeddings_pre[i]``,
    pipeline_tuneavideo.py:399-403).
    ``source_uses_cfg=False`` is the --fast mode source branch.
    ``cached_source``: cached-source fast mode — the source stream is dropped
    from the batch entirely; its latents replay the inversion trajectory
    exactly and the controllers read its attention maps from the capture
    (see :mod:`videop2p_tpu.pipelines.cached`). Requires
    ``source_uses_cfg=False``, ``eta=0`` and no null-text embeddings.

    ``step_positions``: the step-reduction seam (cached mode only). A
    strictly increasing sequence of ``num_inference_steps`` positions into
    the capture's base edit-step grid
    (:meth:`~videop2p_tpu.core.ddim.DDIMScheduler.subset_positions` is the
    canonical producer) — the edit then visits only those base timesteps
    from ONE base-steps inversion: the source replay reads the trajectory
    at the visited grid points (still exact — stream 0 stays the capture's
    x_0 bit-for-bit), the captured maps are indexed at the mapped base
    steps, and the scheduler walks the non-uniform grid via explicit
    ``prev_timestep``. The controller must be built for the SUBSET step
    count; gated subset steps must map inside the captured windows
    (``pipelines.cached.check_subset_windows`` — validated here when the
    controller is concrete, and by the serving layer before tracing).

    Per-frame ("multi") conditioning (pipeline_tuneavideo.py:366-367,399-402):
    pass ``cond_embeddings`` as (P, F, L, D); ``uncond_embeddings`` stays
    (L, D) and broadcasts per frame, and ``null_uncond_embeddings`` may be
    per-frame (num_steps, F, L, D).

    ``telemetry=True``: return ``(latents, tel)`` where ``tel`` stacks
    per-DDIM-step scalars riding the scan output (zero extra dispatches —
    obs.telemetry): post-step latent abs-max/mean + NaN/inf counts, the
    controller's cross-edit gate mean at that step, and whether the
    self/temporal replacement window was active. Off by default; the
    telemetry-off program is unchanged (tests/test_obs.py pins the outputs
    bit-exact, cached replay exactness included).

    ``device_probe``: a per-device telemetry probe for sharded runs
    (:func:`videop2p_tpu.obs.comm.make_device_probe`): called on the
    post-step latents inside the scan body, its fixed-shape output dict
    (per-device abs-max/mean/NaN/inf of each device's LOCAL shard plus a
    cross-replica divergence scalar) rides the scan ``ys`` — the same
    zero-extra-dispatch contract as ``telemetry``. Off (None) by default;
    the probe-off program is unchanged.

    ``attn_maps=True``: additionally return a per-step attention capture
    record riding the same scan (obs.attention — zero extra dispatches):
    pooled per-token cross-attention heatmaps over the conditional
    streams, per-site attention entropies, and (when a LocalBlend is
    configured) the blend-mask time series with coverage fractions. The
    return is ``latents`` plus the requested records in fixed order:
    ``(latents[, tel][, dev][, attn])``. Off by default — the capture-off
    program is byte-identical (tests/test_quality.py pins it).

    ``reuse_schedule``: cross-step deep-feature reuse (cached mode only;
    :mod:`videop2p_tpu.pipelines.reuse`). ``"uniform:K"`` /
    ``"custom:<p0,p1,...>"`` mark the steps that run the FULL UNet; on the
    remaining steps the deep down/mid/up stages are skipped and the cached
    deep feature — carried in the scan state — is reused via a
    ``lax.cond`` in the scan body, so the whole edit stays ONE compiled
    program. Incompatible with ``attn_maps`` (shallow steps produce no
    attention store). ``"off"``/None leaves the scan body byte-identical.

    ``student_head``: the consistency-distilled student's time-conditioning
    head (:func:`videop2p_tpu.train.distill.apply_time_head` params; cached
    mode only — the student rides the cached replay at 1–4 subset steps).
    When set, every edit-stream ε prediction is modulated by the head
    before CFG and the scheduler step; the source stream is REPLAYED from
    the capture regardless, so ``src_err == 0.0`` is structurally
    unaffected. ``None`` (the default) leaves the scan body byte-identical
    — the student-off program is the pre-distillation program.
    """
    P = cond_embeddings.shape[0]
    multi = cond_embeddings.ndim == 4
    # latents stay float32 in the scan carry; the UNet casts to its own
    # compute dtype internally (scheduler math is fp32 for step fidelity)
    latents = latents.astype(jnp.float32)
    if latents.shape[0] == 1 and P > 1:
        latents = jnp.broadcast_to(latents, (P,) + latents.shape[1:])
    elif latents.shape[0] != P:
        raise ValueError(f"latents batch {latents.shape[0]} != num prompts {P}")
    video_length = latents.shape[1]
    latent_hw = latents.shape[2:4]
    text_len = cond_embeddings.shape[-2]
    if multi and cond_embeddings.shape[1] != video_length:
        raise ValueError(
            f"per-frame cond_embeddings {cond_embeddings.shape} do not match "
            f"video_length {video_length}"
        )

    timesteps = jnp.asarray(scheduler.timesteps(num_inference_steps))
    if uncond_embeddings.ndim == 3 and uncond_embeddings.shape[0] == 1:
        uncond_embeddings = uncond_embeddings[0]
    if uncond_embeddings.ndim != 2:
        raise ValueError(
            f"uncond_embeddings must be (L, D) or (1, L, D), got "
            f"{uncond_embeddings.shape}; per-step null-text embeddings go in "
            "null_uncond_embeddings"
        )
    if multi:
        # per-frame conditioning: every stream's uncond broadcasts per frame
        # (the reference repeats embeddings '(b f) n c', :366-367)
        uncond_embeddings = jnp.broadcast_to(
            uncond_embeddings[None], (video_length,) + uncond_embeddings.shape
        )

    if step_positions is not None and cached_source is None:
        raise ValueError(
            "step_positions is the cached fast path's step-reduction seam — "
            "it requires cached_source"
        )
    if reuse_schedule not in (None, "off"):
        if cached_source is None:
            raise ValueError(
                "reuse_schedule is the cached fast path's deep-feature reuse "
                "seam — it requires cached_source"
            )
        if attn_maps:
            raise ValueError(
                "attn_maps capture reads every step's attention store and "
                "shallow reuse steps do not produce one — run attention "
                "capture with reuse_schedule='off'"
            )
    if student_head is not None and cached_source is None:
        raise ValueError(
            "student_head is the cached fast path's few-step student seam — "
            "it requires cached_source"
        )
    if cached_source is not None:
        if source_uses_cfg:
            raise ValueError("cached_source requires fast mode (source_uses_cfg=False)")
        if null_uncond_embeddings is not None:
            raise ValueError(
                "cached_source replays the source exactly — null-text "
                "embeddings have nothing left to correct and are not injected"
            )
        if eta > 0:
            raise ValueError(
                "cached_source requires eta=0: η-variance noise would make the "
                "live source stream stochastic while the cached replay is "
                "deterministic"
            )
        if step_positions is not None:
            from videop2p_tpu.pipelines.cached import validate_step_positions

            step_positions = validate_step_positions(
                step_positions, cached_source.num_steps
            )
            if len(step_positions) != num_inference_steps:
                raise ValueError(
                    f"step_positions has {len(step_positions)} entries, edit "
                    f"runs {num_inference_steps}"
                )
        elif cached_source.num_steps != num_inference_steps:
            raise ValueError(
                f"cached trajectory covers {cached_source.num_steps} steps, "
                f"edit runs {num_inference_steps} (pass step_positions for a "
                "timestep-subset fast path from one inversion)"
            )
        return _edit_sample_cached(
            unet_fn, params, scheduler, latents, cond_embeddings,
            uncond_embeddings, cached_source,
            num_inference_steps=num_inference_steps,
            guidance_scale=guidance_scale, ctx=ctx,
            blend_res=blend_res, step_positions=step_positions,
            telemetry=telemetry,
            device_probe=device_probe, attn_maps=attn_maps,
            reuse_schedule=reuse_schedule,
            student_head=student_head,
        )

    # the source stream's per-step uncond: the null-text sequence when given,
    # else the raw uncond every step
    if null_uncond_embeddings is not None:
        if null_uncond_embeddings.ndim == 4 and null_uncond_embeddings.shape[1] == 1:
            # (steps, 1, L, D) — the batch-1 source-stream optimization output
            null_uncond_embeddings = null_uncond_embeddings[:, 0]
        if not multi and null_uncond_embeddings.ndim == 4:
            raise ValueError(
                "null-text embeddings must be optimized on the batch-1 "
                f"source stream, got shape {null_uncond_embeddings.shape}"
            )
        if multi and null_uncond_embeddings.ndim == 3:
            # one (L, D) per step → broadcast over frames (the reference's
            # multi injection fills all F slots, :399-402)
            null_uncond_embeddings = jnp.broadcast_to(
                null_uncond_embeddings[:, None],
                (null_uncond_embeddings.shape[0], video_length)
                + null_uncond_embeddings.shape[1:],
            )
        expected = (num_inference_steps,) + uncond_embeddings.shape
        if null_uncond_embeddings.shape != expected:
            raise ValueError(
                f"null-text embeddings must have shape {expected}, "
                f"got {null_uncond_embeddings.shape}"
            )
        uncond0_seq = null_uncond_embeddings
    else:
        uncond0_seq = jnp.broadcast_to(
            uncond_embeddings[None], (num_inference_steps,) + uncond_embeddings.shape
        )

    if key is None:
        key = jax.random.key(0)
    use_blend = ctx is not None and ctx.blend is not None

    # fast mode (source_uses_cfg=False) discards the source stream's uncond
    # prediction (the reference computes then overwrites it,
    # pipeline_tuneavideo.py:412-415) — skip that forward entirely: the CFG
    # batch shrinks from 2P to (P−1)+P streams, a ~25 % FLOP cut at P=2.
    U = P if source_uses_cfg else P - 1

    def step_text(uncond0):
        # stream 0's uncond is per-step (null-text seam); edit streams keep
        # the raw uncond (pipeline_tuneavideo.py:399-403). In fast mode the
        # source uncond stream does not exist (its output was unused).
        u = jnp.broadcast_to(uncond_embeddings[None], (P,) + uncond_embeddings.shape)
        if source_uses_cfg:
            u = jnp.concatenate([uncond0[None], u[1:]], axis=0)
        else:
            u = u[1:]
        return jnp.concatenate([u, cond_embeddings], axis=0)

    def step_latents(latents):
        return jnp.concatenate([latents[P - U:], latents], axis=0)

    maps_sum = None
    if use_blend:
        # fixed carry shape: count blend sites from an abstract forward
        control0 = AttnControl(ctx=ctx, step_index=jnp.asarray(0), num_uncond=U)
        _, store_shape = jax.eval_shape(
            unet_fn,
            params,
            step_latents(latents),
            timesteps[0],
            step_text(uncond0_seq[0]),
            control0,
        )
        maps_shape = jax.eval_shape(
            lambda s: blend_maps_from_store(
                s,
                latent_hw=latent_hw,
                video_length=video_length,
                num_prompts=P,
                text_len=text_len,
                blend_res=blend_res,
                num_uncond=U,
            ),
            store_shape,
        )
        maps_sum = jnp.zeros(maps_shape.shape, maps_shape.dtype)

    def body(carry, xs):
        latents, maps_sum, key = carry
        t, i, uncond = xs
        latent_in = step_latents(latents)
        text = step_text(uncond)
        control = (
            AttnControl(ctx=ctx, step_index=i, num_uncond=U) if ctx is not None else None
        )
        eps_all, store = unet_fn(params, latent_in, t, text, control)
        eps_uncond, eps_text = eps_all[:U], eps_all[U:]
        if source_uses_cfg:
            eps = eps_uncond + guidance_scale * (eps_text - eps_uncond)
        else:
            # edit streams get CFG against their own uncond; the source
            # stream replays its cond-only prediction exactly
            eps_edit = eps_uncond + guidance_scale * (eps_text[1:] - eps_uncond)
            eps = jnp.concatenate([eps_text[:1], eps_edit], axis=0)

        key, sub = jax.random.split(key)
        variance_noise = None
        if eta > 0:
            if dependent_sampler is not None:
                variance_noise = dependent_sampler.sample_like(sub, eps)
            else:
                variance_noise = jax.random.normal(sub, eps.shape, eps.dtype)

        latents, _ = scheduler.step(
            eps, t, latents, num_inference_steps, eta=eta, variance_noise=variance_noise
        )

        if use_blend:
            maps_sum = maps_sum + blend_maps_from_store(
                store,
                latent_hw=latent_hw,
                video_length=video_length,
                num_prompts=P,
                text_len=text_len,
                blend_res=blend_res,
                num_uncond=U,
            )
            latents = local_blend(latents, maps_sum, ctx.blend, i)
        if ctx is not None and ctx.spatial_replace_until > 0:
            # SpatialReplace step callback (run_videop2p.py:237-241): inject
            # the source latents into every edit stream while active
            active = i < ctx.spatial_replace_until
            latents = jnp.where(
                active, jnp.broadcast_to(latents[:1], latents.shape), latents
            )
        tel = attn = dev = None
        if telemetry:
            tel = dict(latent_stats(latents), **_controller_gates(ctx, i))
        if device_probe is not None:
            dev = device_probe(latents)
        if attn_maps:
            attn = attn_step_record(
                store, num_uncond=U, num_cond=P, video_length=video_length,
                text_len=text_len, latent_hw=latent_hw,
            )
            if use_blend:
                attn.update(_mask_series_entry(maps_sum, ctx.blend, i, latent_hw))
        ys = _pack_step_outputs(telemetry, tel, attn_maps, attn, dev)
        return (latents, maps_sum, key), ys

    xs = (timesteps, jnp.arange(num_inference_steps), uncond0_seq)
    (latents, _, _), ys = jax.lax.scan(body, (latents, maps_sum, key), xs)
    out = (latents,)
    if telemetry:
        out += (ys["tel"],)
    if device_probe is not None:
        out += (ys["dev"],)
    if attn_maps:
        out += (ys["attn"],)
    return out if len(out) > 1 else latents


def _edit_sample_cached(
    unet_fn: UNetFn,
    params,
    scheduler: DDIMScheduler,
    latents: jax.Array,
    cond_embeddings: jax.Array,
    uncond_embeddings: jax.Array,
    cached: CachedSource,
    *,
    num_inference_steps: int,
    guidance_scale: float,
    ctx: Optional[ControlContext],
    blend_res: Optional[Tuple[int, int]],
    step_positions=None,
    telemetry: bool = False,
    device_probe: Optional[Callable] = None,
    attn_maps: bool = False,
    reuse_schedule: Optional[str] = None,
    student_head: Optional[dict] = None,
) -> jax.Array:
    """The cached-source denoise loop: only the P−1 edit streams run the
    UNet; the source stream is read off the reversed inversion trajectory
    (exact replay) and its controller inputs come from the capture
    (:mod:`videop2p_tpu.pipelines.cached`). Fully deterministic — the
    ``eta=0`` requirement means no randomness enters the loop.

    Inputs arrive normalized by :func:`edit_sample` (latents broadcast to
    (P, F, h, w, C), uncond as (L, D) — or per-frame in multi mode);
    ``step_positions`` (already validated) selects a timestep subset of the
    capture's base grid — the few-step fast path from one inversion.
    """
    import numpy as np

    P = cond_embeddings.shape[0]
    E = P - 1  # edit streams
    U = E  # their uncond streams
    if E < 1:
        raise ValueError("cached_source needs at least one edit prompt")
    video_length = latents.shape[1]
    latent_hw = latents.shape[2:4]
    text_len = cond_embeddings.shape[-2]
    subset = step_positions is not None
    if subset:
        base_steps = cached.num_steps
        positions = np.asarray(step_positions, dtype=np.int64)
        base_ts = np.asarray(scheduler.timesteps(base_steps))
        ts_np = base_ts[positions]
        ratio = scheduler.num_train_timesteps // base_steps
        # step j lands on the next subset timestep; the last step lands on
        # the base walk's own terminal target (< 0 → final ᾱ), so every
        # subset walk ends at the same "clean" state as the base walk
        prev_ts_np = np.concatenate([ts_np[1:], [base_ts[-1] - ratio]])
        timesteps = jnp.asarray(ts_np)
        # gate-coverage validation needs a CONCRETE controller; under a
        # trace (the serving programs pass ctx as a jit argument) the
        # caller validates before tracing (serve/programs.py does)
        if ctx is not None and not isinstance(
            ctx.cross_replace_alpha, jax.core.Tracer
        ):
            from videop2p_tpu.pipelines.cached import check_subset_windows

            check_subset_windows(ctx, cached, positions, num_inference_steps)
    else:
        timesteps = jnp.asarray(scheduler.timesteps(num_inference_steps))

    edit_latents = latents[1:]  # (E, F, h, w, C), fp32 from the caller
    cond_edit = cond_embeddings[1:]
    text = jnp.concatenate(
        [jnp.broadcast_to(uncond_embeddings[None], (E,) + uncond_embeddings.shape), cond_edit],
        axis=0,
    )

    if ctx is not None and ctx.kind != "empty":
        # a non-empty gate window with no captured maps would silently skip
        # the edit at every site of that type — fail loudly instead
        lo, hi = cached.self_window
        if cached.cross_len > 0 and not cached.cross_maps:
            raise ValueError(
                f"capture declares a {cached.cross_len}-step cross window but "
                "has no cross maps"
            )
        if hi > lo and not cached.temporal_maps:
            raise ValueError(
                f"capture declares self window {cached.self_window} but has "
                "no temporal maps"
            )

    use_blend = ctx is not None and ctx.blend is not None
    if use_blend and cached.blend_seq is None:
        raise ValueError(
            "LocalBlend is configured but the capture has no blend_seq — run "
            "ddim_inversion_captured(capture_blend=True)"
        )
    # src_seq[i] = source latent AFTER edit step i (= trajectory[N−i−1]);
    # a subset walk's step j lands on the NEXT visited grid point, and its
    # last step lands on x_0 — the replay reads exact trajectory values
    # either way
    if subset:
        positions_next = np.append(positions[1:], base_steps)
        src_seq = cached.src_latents[jnp.asarray(positions_next)]
    else:
        src_seq = cached.src_latents[1:]

    maps_sum = None
    if use_blend:
        control0 = AttnControl(
            ctx=ctx, step_index=jnp.asarray(0), num_uncond=U,
            cached_base=cached.base_tree_at(jnp.asarray(0)),
            cached_source=True,
        )
        _, store_shape = jax.eval_shape(
            unet_fn,
            params,
            jnp.concatenate([edit_latents, edit_latents], axis=0),
            timesteps[0],
            text,
            control0,
        )
        edit_maps_shape = jax.eval_shape(
            lambda s: blend_maps_from_store(
                s,
                latent_hw=latent_hw,
                video_length=video_length,
                num_prompts=E,
                text_len=text_len,
                blend_res=blend_res,
                num_uncond=U,
            ),
            store_shape,
        )
        maps_sum = jnp.zeros(
            (1 + E,) + edit_maps_shape.shape[1:], edit_maps_shape.dtype
        )

    # cross-step deep-feature reuse (pipelines/reuse.py): the schedule is a
    # STATIC per-step boolean riding xs; the deep feature (the final up
    # block's input) and the last full step's blend maps ride the carry, so
    # the edit stays ONE compiled program regardless of K
    reuse_full = None
    if reuse_schedule not in (None, "off"):
        from videop2p_tpu.pipelines.reuse import parse_reuse_schedule

        reuse_full = parse_reuse_schedule(reuse_schedule, num_inference_steps)
        if attn_maps:
            raise ValueError(
                "attn_maps capture is incompatible with reuse_schedule — "
                "shallow steps produce no attention store"
            )
    deep0 = last_maps0 = None
    if reuse_full is not None:
        reuse_control0 = (
            AttnControl(
                ctx=ctx, step_index=jnp.asarray(0), num_uncond=U,
                cached_base=cached.base_tree_at(jnp.asarray(0)),
                cached_source=True,
            )
            if ctx is not None
            else None
        )
        (_, deep_shape), _ = jax.eval_shape(
            lambda p, x: unet_fn(
                p, x, timesteps[0], text, reuse_control0, deep_mode="capture"
            ),
            params,
            jnp.concatenate([edit_latents, edit_latents], axis=0),
        )
        deep0 = jnp.zeros(deep_shape.shape, deep_shape.dtype)
        last_maps0 = (
            jnp.zeros(edit_maps_shape.shape, edit_maps_shape.dtype)
            if use_blend else jnp.zeros((0,), jnp.float32)
        )

    def body(carry, xs):
        if reuse_full is not None:
            edit_latents, maps_sum, deep_feat, last_maps = carry
            *xs, is_full = xs
        else:
            edit_latents, maps_sum = carry
        if subset:
            # base_i indexes the captured maps at the mapped base step; the
            # controller's own gates stay in subset-step space (i)
            t, i, src_after, blend_src, base_i, prev_t = xs
        else:
            t, i, src_after, blend_src = xs
            base_i, prev_t = i, None
        latent_in = jnp.concatenate([edit_latents, edit_latents], axis=0)
        control = (
            AttnControl(
                ctx=ctx, step_index=i, num_uncond=U,
                cached_base=cached.base_tree_at(base_i),
                cached_source=True,
            )
            if ctx is not None
            else None
        )
        if reuse_full is None:
            eps_all, store = unet_fn(params, latent_in, t, text, control)
        else:
            # both branches trace once; one executes per step. The sown
            # attention store must NOT cross the cond boundary (the shallow
            # branch has no deep attention sites, so the pytrees differ) —
            # the blend maps are reduced from it INSIDE the full branch and
            # only the fixed-shape reduction crosses.
            def _cond_maps(store):
                if not use_blend:
                    return jnp.zeros((0,), jnp.float32)
                return blend_maps_from_store(
                    store,
                    latent_hw=latent_hw,
                    video_length=video_length,
                    num_prompts=E,
                    text_len=text_len,
                    blend_res=blend_res,
                    num_uncond=U,
                )

            def _full_step(latent_in, deep_feat, last_maps):
                (eps, deep), store = unet_fn(
                    params, latent_in, t, text, control, deep_mode="capture"
                )
                return (
                    eps,
                    deep.astype(deep_feat.dtype),
                    _cond_maps(store).astype(last_maps.dtype),
                )

            def _shallow_step(latent_in, deep_feat, last_maps):
                eps, _ = unet_fn(
                    params, latent_in, t, text, control,
                    deep_mode="shallow", deep_feature=deep_feat,
                )
                return eps, deep_feat, last_maps

            eps_all, deep_feat, reuse_maps = jax.lax.cond(
                is_full, _full_step, _shallow_step,
                latent_in, deep_feat, last_maps,
            )
            last_maps = reuse_maps
        if student_head is not None:
            # the few-step student: the distilled time-conditioning head
            # modulates ε before CFG (train/distill.py). Only the edit
            # streams run the UNet here — the source stream is replayed
            # from the capture below, so src_err == 0.0 is untouched.
            from videop2p_tpu.train.distill import apply_time_head

            eps_all = apply_time_head(student_head, eps_all, t)
        eps_uncond, eps_text = eps_all[:E], eps_all[E:]
        eps = eps_uncond + guidance_scale * (eps_text - eps_uncond)
        edit_latents, _ = scheduler.step(
            eps, t, edit_latents, num_inference_steps, eta=0.0,
            variance_noise=None, prev_timestep=prev_t,
        )

        if use_blend:
            if reuse_full is None:
                edit_maps = blend_maps_from_store(
                    store,
                    latent_hw=latent_hw,
                    video_length=video_length,
                    num_prompts=E,
                    text_len=text_len,
                    blend_res=blend_res,
                    num_uncond=U,
                )
            else:
                # shallow steps re-add the LAST full step's edit maps — the
                # same "adjacent steps are nearly identical" premise the
                # deep-feature reuse itself rests on
                edit_maps = reuse_maps
            maps_sum = maps_sum + jnp.concatenate([blend_src, edit_maps], axis=0)
            full = jnp.concatenate([src_after, edit_latents], axis=0)
            full = local_blend(full, maps_sum, ctx.blend, i)
            edit_latents = full[1:]
        if ctx is not None and ctx.spatial_replace_until > 0:
            active = i < ctx.spatial_replace_until
            edit_latents = jnp.where(
                active,
                jnp.broadcast_to(src_after, edit_latents.shape),
                edit_latents,
            )
        tel = attn = dev = None
        if telemetry:
            # stats cover the EDIT streams only — the source stream is a
            # replayed constant here, by construction finite and exact
            tel = dict(latent_stats(edit_latents), **_controller_gates(ctx, i))
        if device_probe is not None:
            dev = device_probe(edit_latents)
        if attn_maps:
            # heat covers the E edit streams (the source stream is not in
            # the batch — its maps live in the inversion capture record);
            # the mask series keeps all 1+E streams, source first
            attn = attn_step_record(
                store, num_uncond=U, num_cond=E, video_length=video_length,
                text_len=text_len, latent_hw=latent_hw,
            )
            if use_blend:
                attn.update(_mask_series_entry(maps_sum, ctx.blend, i, latent_hw))
        ys = _pack_step_outputs(telemetry, tel, attn_maps, attn, dev)
        if reuse_full is not None:
            return (edit_latents, maps_sum, deep_feat, last_maps), ys
        return (edit_latents, maps_sum), ys

    if cached.blend_seq is None:
        blend_xs = jnp.zeros((num_inference_steps, 0))
    elif subset:
        # the source's blend contribution captured AT each visited step;
        # the mask's running sum covers fewer steps but is max-normalized
        blend_xs = cached.blend_seq[jnp.asarray(positions)]
    else:
        blend_xs = cached.blend_seq
    xs = (timesteps, jnp.arange(num_inference_steps), src_seq, blend_xs)
    if subset:
        xs += (jnp.asarray(positions, jnp.int32), jnp.asarray(prev_ts_np))
    if reuse_full is not None:
        xs += (jnp.asarray(reuse_full),)
        carry0 = (edit_latents, maps_sum, deep0, last_maps0)
    else:
        carry0 = (edit_latents, maps_sum)
    final_carry, ys = jax.lax.scan(body, carry0, xs)
    edit_latents = final_carry[0]
    # stream 0 = the exact inversion reconstruction (trajectory[0] = x_0)
    out = jnp.concatenate([cached.src_latents[-1], edit_latents], axis=0)
    outs = (out,)
    if telemetry:
        outs += (ys["tel"],)
    if device_probe is not None:
        outs += (ys["dev"],)
    if attn_maps:
        outs += (ys["attn"],)
    return outs if len(outs) > 1 else out


def official_edit(
    unet_fn: UNetFn,
    params,
    scheduler: DDIMScheduler,
    trajectory: jax.Array,
    cond_embeddings: jax.Array,
    uncond_embedding: jax.Array,
    *,
    num_inference_steps: int = 50,
    guidance_scale: float = 7.5,
    ctx: Optional[ControlContext] = None,
    num_inner_steps: int = 10,
    epsilon: float = 1e-5,
    null_text_precision: str = "fp32",
    null_text_mode: str = "optimize",
    hybrid_inner_steps: int = 3,
    early_stop: bool = True,
    dependent_weight: float = 0.0,
    dependent_sampler: Optional[DependentNoiseSampler] = None,
    eta: float = 0.0,
    key: Optional[jax.Array] = None,
    blend_res: Optional[Tuple[int, int]] = None,
    donate: bool = True,
    return_null_stats: bool = False,
):
    """The full official mode — null-text optimization plus the controlled
    full-CFG edit — as ONE jitted device program.

    The split flow surfaces the optimized uncond trajectory
    (num_steps, 1, L, D) on the host between phases: a device→host→device
    round trip plus a second program dispatch. Here
    :func:`edit_sample` consumes the optimized sequence straight out of the
    null-text scan — the embeddings never materialize outside the program,
    and the trajectory buffer is donated to it (``donate=False`` if the
    caller still needs it). HBM note: this holds the null-text grad program
    and the CFG edit program in ONE executable — at fp32 SD scale that can
    exceed a 16 GB chip (the CLI's phase-split + ``jax.clear_caches()``
    exists for that reason); the bf16/``mixed`` working points fit.

    ``trajectory``: (num_steps+1, B=1, F, h, w, C) from
    :func:`~videop2p_tpu.pipelines.inversion.ddim_inversion`;
    ``cond_embeddings``: (P, L, D), source prompt first;
    ``uncond_embedding``: (L, D) or (1, L, D).

    Returns final latents (P, F, h, w, C); with ``return_null_stats=True``
    returns ``(latents, stats)`` — the fused null-text program's
    ``{"final_loss", "inner_steps"}`` record.

    ``null_text_mode``/``hybrid_inner_steps`` select the amortized
    (closed-form negative-prompt) or hybrid (joint K-step) null-text
    substitutes (pipelines/inversion.py) inside the same single program —
    the ≥3× cheaper official path the quality rules gate.
    """
    # lazy import: inversion.py imports this module for the UNetFn contract
    from videop2p_tpu.pipelines.inversion import null_text_optimization

    if uncond_embedding.ndim == 3 and uncond_embedding.shape[0] == 1:
        uncond_embedding = uncond_embedding[0]
    if uncond_embedding.ndim != 2:
        raise ValueError(
            f"uncond_embedding must be (L, D) or (1, L, D), got "
            f"{uncond_embedding.shape}"
        )
    if key is None:
        key = jax.random.key(0)
    # CPU cannot alias donated buffers — avoid the per-call warning
    donate = donate and jax.default_backend() != "cpu"

    cache_key = (
        unet_fn, id(scheduler), id(dependent_sampler), id(ctx),
        float(guidance_scale), int(num_inner_steps), int(num_inference_steps),
        float(dependent_weight), float(epsilon), float(eta),
        bool(early_stop), null_text_precision, null_text_mode,
        int(hybrid_inner_steps), blend_res, bool(donate),
    )
    program = _OFFICIAL_EDIT_CACHE.get(cache_key)
    if program is None:

        def program_fn(p, cond, uncond, traj, k):
            k_null, k_edit = jax.random.split(k)
            null_seq, losses, inner_taken = null_text_optimization(
                unet_fn, p, scheduler, traj, cond[:1], uncond[None],
                num_inference_steps=num_inference_steps,
                guidance_scale=guidance_scale,
                num_inner_steps=num_inner_steps,
                epsilon=epsilon,
                null_text_precision=null_text_precision,
                null_text_mode=null_text_mode,
                hybrid_inner_steps=hybrid_inner_steps,
                dependent_weight=dependent_weight,
                dependent_sampler=dependent_sampler,
                key=k_null,
                early_stop=early_stop,
                return_losses=True,
                return_inner_steps=True,
            )
            out = edit_sample(
                unet_fn, p, scheduler, traj[-1], cond, uncond,
                num_inference_steps=num_inference_steps,
                guidance_scale=guidance_scale,
                ctx=ctx,
                source_uses_cfg=True,
                eta=eta,
                key=k_edit,
                dependent_sampler=dependent_sampler if eta > 0 else None,
                blend_res=blend_res,
                null_uncond_embeddings=null_seq,
            )
            return out, losses, inner_taken

        program = jax.jit(
            program_fn, donate_argnums=(3,) if donate else ()
        )
        while len(_OFFICIAL_EDIT_CACHE) >= _OFFICIAL_EDIT_CACHE_MAX:
            _OFFICIAL_EDIT_CACHE.pop(next(iter(_OFFICIAL_EDIT_CACHE)))
        _OFFICIAL_EDIT_CACHE[cache_key] = program

    out, losses, inner_taken = program(
        params, cond_embeddings, uncond_embedding, trajectory, key
    )
    if return_null_stats:
        return out, {"final_loss": losses, "inner_steps": inner_taken}
    return out
