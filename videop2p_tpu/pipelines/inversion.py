"""DDIM inversion and null-text inversion.

TPU-native re-design of the reference ``NullInversion``
(/root/reference/run_videop2p.py:443-648) and the Stage-1 validation inversion
(/root/reference/tuneavideo/util.py:52-92):

  * ``ddim_inversion`` — 50 forward-DDIM steps, conditional-only (guidance 1),
    as a ``lax.scan`` that keeps the full latent trajectory
    (run_videop2p.py:558-578). The fork's dependent-noise blend
    ``(1-w)·ε̂ + w·ar_noise`` (run_videop2p.py:465-471) is key-threaded.
  * ``null_text_optimization`` — per-step optimization of the unconditional
    embedding (run_videop2p.py:580-612): outer scan over the 50 steps, inner
    ``lax.while_loop`` Adam with the reference's decayed lr
    ``1e-2·(1−i/100)``, ≤``num_inner_steps`` iterations and early stop at
    ``loss < ε + i·2e-5`` — the early stop becomes the while condition, so
    shapes stay static under jit.
  * ``null_text_optimization_fused`` — the same optimization as ONE jitted
    device program with the trajectory buffer donated: scan outer,
    while_loop inner, the convergence predicate carried on-device, and a
    ``null_text_precision`` knob. ``"mixed"`` runs the UNet forwards in
    bf16 (the tensors crossing the UNet boundary are cast down; pair with a
    bf16-compute ``unet_fn`` for the full MXU win) while the scheduler
    coefficients (core/ddim.py fp32 islands), the Adam state, and the
    loss/early-stop accumulation all stay float32 — the precision split
    that keeps the reconstruction inside the fixed-work PSNR band
    (tests/test_null_text_precision.py pins it at tiny scale).

The reference's Python-loop-with-break structure is the hard functionalization
case SURVEY §7 ranks #3; the while_loop preserves its exact update-then-check
semantics (loss is measured pre-update, the update it gated is still applied).
"""

from __future__ import annotations

from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import optax

from videop2p_tpu.core.ddim import DDIMScheduler
from videop2p_tpu.core.noise import DependentNoiseSampler
from videop2p_tpu.models.attention import AttnControl
from videop2p_tpu.obs.attention import attn_step_record
from videop2p_tpu.obs.telemetry import latent_stats
from videop2p_tpu.pipelines.cached import CachedSource, filter_site_tree
from videop2p_tpu.pipelines.sampling import UNetFn
from videop2p_tpu.pipelines.stores import blend_maps_from_store

__all__ = [
    "ddim_inversion",
    "ddim_inversion_captured",
    "null_text_optimization",
    "null_text_optimization_fused",
]

# jitted programs for the outer_chunk and fused paths, keyed by the statics
# their closures bake in (runtime arrays enter as jit inputs); bounded FIFO
_CHUNK_SCAN_CACHE: dict = {}
_CHUNK_SCAN_CACHE_MAX = 4
_FUSED_PROGRAM_CACHE: dict = {}
_FUSED_PROGRAM_CACHE_MAX = 4

_NULL_TEXT_PRECISIONS = ("fp32", "mixed")
# how the per-step unconditional embedding is produced:
#   "optimize"  — the reference's per-step inner Adam loop (Mokady et al.);
#   "amortized" — closed-form negative-prompt-inversion substitute
#                 (Miyake et al., 2023): uncond := cond, under which the CFG
#                 combine collapses to the conditional prediction and the
#                 denoise replays the inversion trajectory with ZERO inner
#                 Adam steps — one forward per outer step, one fused scan;
#   "hybrid"    — amortized seed + K (hybrid_inner_steps) refinement Adam
#                 steps run JOINTLY across all outer steps as one batched
#                 program (vs 50×num_inner_steps sequential inner steps).
_NULL_TEXT_MODES = ("optimize", "amortized", "hybrid")


def _cache_put(cache: dict, cache_max: int, key, value) -> None:
    """Bounded FIFO insert: fresh unet_fn/scheduler objects per pipeline
    would otherwise pin executables forever in a long-lived process."""
    while len(cache) >= cache_max:
        cache.pop(next(iter(cache)))
    cache[key] = value


def ddim_inversion(
    unet_fn: UNetFn,
    params,
    scheduler: DDIMScheduler,
    latents: jax.Array,
    cond_embedding: jax.Array,
    *,
    num_inference_steps: int = 50,
    dependent_weight: float = 0.0,
    dependent_sampler: Optional[DependentNoiseSampler] = None,
    key: Optional[jax.Array] = None,
    return_eps: bool = False,
    attn_maps: bool = False,
):
    """Invert clean latents x_0 to noise x_T.

    ``latents``: (B, F, h, w, C) clean (VAE-encoded, scaled) latents;
    ``cond_embedding``: (B, L, D) source-prompt embedding (no CFG — the
    reference inverts with guidance 1, run_videop2p.py:558-572).

    Returns the full trajectory (num_steps+1, B, F, h, w, C) with
    ``[0] = x_0`` and ``[-1] = x_T`` (the reference's ``all_latent`` list).
    ``dependent_weight > 0`` blends the model output with AR noise:
    ``ε = (1-w)·ε̂ + w·ar_noise`` (run_videop2p.py:467-471).

    ``return_eps``: also return the per-step model outputs
    (num_steps, B, F, h, w, C), ordered along the inversion walk. DDIM's
    ``next_step``/``prev_step`` are linear in (x, ε) with identical
    coefficients, so ``prev_step(eps[i], t[i], trajectory[i+1])`` recovers
    ``trajectory[i]`` EXACTLY — a cached-ε backward replay of the source
    stream is exact where the reference's fast mode re-predicts ε from the
    drifting latent (pipeline_tuneavideo.py:412-415) and only approximately
    reconstructs. This is the seam for replaying the source stream without
    re-running its forwards (tests/test_pipelines.py pins the property).

    ``attn_maps``: also stack the per-step attention observability record
    (obs.attention — pooled per-token cross heatmaps of the source stream
    + per-site entropies, riding the scan's ``ys``) and append it to the
    return. Step axis follows the inversion walk (x_0 → x_T). Return
    order: ``trajectory[, eps_seq][, attn]``.
    """
    # latents stay float32 through the walk regardless of the UNet's compute
    # dtype — scheduler math is fp32 (the reference keeps the Stage-2 UNet and
    # latents fp32 for inversion fidelity, run_videop2p.py:111-113)
    latents = latents.astype(jnp.float32)
    # ascending timesteps: the reference walks timesteps[-(i+1)] for i in 0..N
    # (run_videop2p.py:563-566)
    timesteps = jnp.asarray(scheduler.timesteps(num_inference_steps)[::-1].copy())
    if key is None:
        key = jax.random.key(0)

    video_length = latents.shape[1]
    latent_hw = latents.shape[2:4]
    text_len = cond_embedding.shape[-2]

    def body(carry, t):
        latent, key = carry
        eps, store = unet_fn(params, latent, t, cond_embedding, None)
        if dependent_weight > 0.0:
            if dependent_sampler is None:
                raise ValueError("dependent_weight > 0 requires dependent_sampler")
            key, sub = jax.random.split(key)
            ar_noise = dependent_sampler.sample_like(sub, eps)
            eps = (1.0 - dependent_weight) * eps + dependent_weight * ar_noise
        latent = scheduler.next_step(eps, t, latent, num_inference_steps)
        # return_eps/attn_maps are static: without them the scan must not
        # stack dead buffers (eager callers get no DCE)
        ys = {"latent": latent}
        if return_eps:
            ys["eps"] = eps.astype(jnp.float32)
        if attn_maps:
            ys["attn"] = attn_step_record(
                store, num_uncond=0, num_cond=latent.shape[0],
                video_length=video_length, text_len=text_len,
                latent_hw=latent_hw,
            )
        return (latent, key), ys

    (_, _), ys = jax.lax.scan(body, (latents, key), timesteps)
    full = jnp.concatenate([latents[None], ys["latent"]], axis=0)
    out = (full,)
    if return_eps:
        out += (ys["eps"],)
    if attn_maps:
        out += (ys["attn"],)
    return out if len(out) > 1 else full


def ddim_inversion_captured(
    unet_fn: UNetFn,
    params,
    scheduler: DDIMScheduler,
    latents: jax.Array,
    cond_embedding: jax.Array,
    *,
    num_inference_steps: int = 50,
    cross_len: int = 0,
    self_window: Tuple[int, int] = (0, 0),
    capture_blend: bool = False,
    blend_res: Optional[Tuple[int, int]] = None,
    dependent_weight: float = 0.0,
    dependent_sampler: Optional[DependentNoiseSampler] = None,
    key: Optional[jax.Array] = None,
    temporal_maps_dtype=None,
    attn_maps: bool = False,
) -> Tuple[jax.Array, CachedSource]:
    """DDIM inversion that also captures everything a cached-source edit
    needs (see :mod:`videop2p_tpu.pipelines.cached` for the design).

    ``attn_maps``: additionally stack the per-step attention
    observability record of the SOURCE stream (obs.attention — pooled
    per-token cross heatmaps + per-site entropies; in cached fast mode
    this is the only place source-stream maps are visible, the edit batch
    having dropped the stream) and return it as a third element,
    step-axis in inversion-walk order.

    ``temporal_maps_dtype``: optional narrower STORAGE dtype for the
    captured temporal (attn_temp) probability maps — e.g.
    ``jnp.float8_e4m3fn``. The temporal tree is the long-video memory
    cliff: per spatial position it holds an F×F map, so its bytes grow
    quadratically with frame count (8f: 0.6 GiB → 24f: 5.8 GiB at SD
    scale) while everything else grows linearly. Probabilities live in
    [0, 1] where e4m3's 3 mantissa bits give a ~6 % relative step (about
    one significant decimal digit), and values below ~2e-3 land in
    subnormals or flush to zero — the real acceptance gate is the
    empirical edit-output delta test (tests/test_cached.py), not a digits
    figure; the maps are read back
    upcast to the sibling captured maps' dtype (cached.py ``base_tree_at``), they only
    feed the EDIT stream's map replacement, and the source-stream replay
    is ε-based — its bit-exactness guarantee is unaffected
    (tests/test_cached.py pins both properties).

    Same walk as :func:`ddim_inversion`, but split into segments so that the
    full per-head controlled-site probabilities are stacked ONLY for the
    inversion steps whose maps the edit's gates will actually read:

      * cross maps for edit steps [0, ``cross_len``) — inversion steps
        [N−cross_len, N);
      * temporal maps for edit steps [lo, hi) = ``self_window`` — inversion
        steps [N−hi, N−lo);
      * per-step LocalBlend store contributions for every step when
        ``capture_blend`` (head-meaned and blend-site-stacked first — tiny).

    Edit step *i* reads the maps captured at inversion step ``N−1−i``: the
    same timestep, with the latent one trajectory position earlier than a
    live source stream would use (the disclosed approximation; the latent
    replay itself is exact). Returns ``(trajectory, CachedSource)``.
    """
    if dependent_weight > 0.0 and dependent_sampler is None:
        raise ValueError("dependent_weight > 0 requires dependent_sampler")
    N = num_inference_steps
    lo, hi = self_window
    if not (0 <= lo <= hi <= N):
        raise ValueError(f"self_window {self_window} outside [0, {N}]")
    if not (0 <= cross_len <= N):
        raise ValueError(f"cross_len {cross_len} outside [0, {N}]")
    latents = latents.astype(jnp.float32)
    video_length = latents.shape[1]
    latent_hw = latents.shape[2:4]
    text_len = cond_embedding.shape[-2]
    timesteps = jnp.asarray(scheduler.timesteps(N)[::-1].copy())
    if key is None:
        key = jax.random.key(0)

    def run_segment(latent, key, ts, want_cross, want_temporal):
        capture = want_cross or want_temporal

        def body(carry, t):
            latent, key = carry
            control = (
                AttnControl(ctx=None, step_index=jnp.asarray(0, jnp.int32), capture=True)
                if capture
                else None
            )
            eps, store = unet_fn(params, latent, t, cond_embedding, control)
            if dependent_weight > 0.0:
                key, sub = jax.random.split(key)
                ar_noise = dependent_sampler.sample_like(sub, eps)
                eps = (1.0 - dependent_weight) * eps + dependent_weight * ar_noise
            latent = scheduler.next_step(eps, t, latent, N)
            ys = {"latent": latent}
            if attn_maps:
                ys["attn"] = attn_step_record(
                    store, num_uncond=0, num_cond=latent.shape[0],
                    video_length=video_length, text_len=text_len,
                    latent_hw=latent_hw,
                )
            if capture_blend:
                ys["blend"] = blend_maps_from_store(
                    store,
                    latent_hw=latent_hw,
                    video_length=video_length,
                    num_prompts=1,
                    text_len=text_len,
                    blend_res=blend_res,
                    num_uncond=0,
                )
            if want_cross:
                ys["cross"] = filter_site_tree(store["attn_base"], "attn2")
            if want_temporal:
                t_tree = filter_site_tree(store["attn_base"], "attn_temp")
                if temporal_maps_dtype is not None:
                    if jnp.issubdtype(jnp.dtype(temporal_maps_dtype),
                                      jnp.integer):
                        # int8 fixed-point: probabilities in [0,1] scale to
                        # round(p·127) — a uniform 1/254 absolute step;
                        # CachedSource.base_tree_at divides back by 127
                        t_tree = jax.tree.map(
                            lambda a: jnp.clip(
                                jnp.round(a.astype(jnp.float32) * 127.0),
                                -127.0, 127.0,
                            ).astype(temporal_maps_dtype),
                            t_tree,
                        )
                    else:
                        t_tree = jax.tree.map(
                            lambda a: a.astype(temporal_maps_dtype), t_tree
                        )
                ys["temporal"] = t_tree
            return (latent, key), ys

        return jax.lax.scan(body, (latent, key), ts)

    # segment the walk at the capture-window edges (inversion-step space):
    # cross maps live in [N−cross_len, N), temporal in [N−hi, N−lo)
    bounds = sorted({0, N - hi, N - lo, N - cross_len, N})
    carry = (latents, key)
    lat_pieces, blend_pieces, cross_pieces, temporal_pieces = [], [], [], []
    attn_pieces = []
    for s, e in zip(bounds[:-1], bounds[1:]):
        want_cross = s >= N - cross_len
        want_temporal = s >= N - hi and e <= N - lo
        carry, ys = run_segment(*carry, timesteps[s:e], want_cross, want_temporal)
        lat_pieces.append(ys["latent"])
        if attn_maps:
            attn_pieces.append(ys["attn"])
        if capture_blend:
            blend_pieces.append(ys["blend"])
        if want_cross:
            cross_pieces.append(ys["cross"])
        if want_temporal:
            temporal_pieces.append(ys["temporal"])

    trajectory = jnp.concatenate([latents[None]] + lat_pieces, axis=0)

    def stack_reversed(pieces):
        # inversion order → edit order (edit step i ↔ inversion step N−1−i)
        if not pieces:
            return None
        return jax.tree.map(lambda *xs: jnp.flip(jnp.concatenate(xs, axis=0), axis=0), *pieces)

    cached = CachedSource(
        src_latents=jnp.flip(trajectory, axis=0),
        cross_maps=stack_reversed(cross_pieces),
        temporal_maps=stack_reversed(temporal_pieces),
        blend_seq=stack_reversed(blend_pieces) if capture_blend else None,
        cross_len=cross_len,
        self_window=(lo, hi),
    )
    if attn_maps:
        attn = jax.tree.map(
            lambda *xs: jnp.concatenate(xs, axis=0), *attn_pieces
        )
        return trajectory, cached, attn
    return trajectory, cached


def null_text_optimization(
    unet_fn: UNetFn,
    params,
    scheduler: DDIMScheduler,
    trajectory: jax.Array,
    cond_embedding: jax.Array,
    uncond_embedding: jax.Array,
    *,
    num_inference_steps: int = 50,
    guidance_scale: float = 7.5,
    num_inner_steps: int = 10,
    epsilon: float = 1e-5,
    null_text_precision: str = "fp32",
    null_text_mode: str = "optimize",
    hybrid_inner_steps: int = 3,
    dependent_weight: float = 0.0,
    dependent_sampler: Optional[DependentNoiseSampler] = None,
    key: Optional[jax.Array] = None,
    outer_chunk: Optional[int] = None,
    early_stop: bool = True,
    return_losses: bool = False,
    return_inner_steps: bool = False,
    telemetry: bool = False,
) -> jax.Array:
    """Optimize a per-step unconditional embedding that makes CFG denoising
    replay the recorded inversion trajectory (run_videop2p.py:580-612).

    ``early_stop=False`` runs exactly ``num_inner_steps`` inner iterations
    per outer step (no ``loss < ε + i·2e-5`` break): the work becomes
    weight-independent, giving a stable wall-clock for benchmarking — the
    reference-faithful early-stopped run varies 157–418 s with the random
    early-stop point (run_videop2p.py:603).

    ``trajectory``: (num_steps+1, B, F, h, w, C) from :func:`ddim_inversion`;
    ``cond_embedding`` / ``uncond_embedding``: (B, L, D).
    Returns per-step uncond embeddings (num_steps, B, L, D) to feed
    ``edit_sample``'s injection seam. With ``return_losses=True`` also
    returns the FINAL inner-loop reconstruction loss per outer step
    (num_steps,) — the optimization objective itself
    (``‖x̂_{t-1} − x_{t-1}‖²``, run_videop2p.py:596), which is the direct
    reconstruction-parity metric between the early-stopped and fixed-work
    variants: both minimize the same quantity, so comparable final losses
    mean comparable reconstruction quality.

    In dependent mode every single prediction gets the same AR-noise blend
    the inversion used — ``ε = (1-w)·ε̂ + w·ar_noise`` with a FRESH draw per
    call (the reference's ``get_noise_pred_single``/``get_noise_pred``,
    run_videop2p.py:465-487; gradients flow through the ``(1-w)·ε̂`` term
    only) — so the objective matches the model that produced the trajectory.

    ``null_text_precision``: ``"fp32"`` (default — the reference's Stage-2
    behavior) or ``"mixed"``. Mixed casts the tensors crossing the UNet
    boundary (latents and text embeddings) to bf16 before every forward and
    upcasts the predictions back; the scheduler steps (fp32 islands,
    core/ddim.py), the Adam moments, the CFG combine, and the loss /
    early-stop accumulation all stay float32. With a bf16-compute
    ``unet_fn`` this runs the inner-loop forwards+backward at full MXU
    rate; with an fp32 ``unet_fn`` it still bounds the activation dtype at
    the boundary (the parity test gates both).

    ``return_inner_steps``: also return the number of inner Adam updates
    each outer step actually took (num_steps,) int32 — the early-stop
    observability the fused-vs-host parity test pins.

    ``telemetry``: additionally stack per-outer-step latent statistics
    (obs.telemetry.latent_stats of the advanced ``latent_cur`` — abs-max,
    mean, NaN/inf counts) as a fourth output. The stats ride the outer
    scan's ``ys`` — zero extra dispatches — and are scalars per step, so
    the program output grows by bytes. Off by default: the telemetry-off
    program is the exact pre-telemetry program (tests/test_obs.py pins
    bit-exactness).

    ``outer_chunk``: split the outer scan into host-level jitted chunks of
    this many steps (one compile, several executions). At SD scale the full
    50-step program is a single multi-minute device call, which the TPU
    runtime's execution watchdog kills — chunking keeps each call short.
    Only valid OUTSIDE jit (the function then jits its own chunk scan).
    For the single-dispatch donated-buffer variant see
    :func:`null_text_optimization_fused`.

    ``null_text_mode``: how the embedding sequence is produced.

      * ``"optimize"`` (default) — the reference's per-step inner Adam loop,
        exactly as documented above (every other knob applies unchanged).
      * ``"amortized"`` — the closed-form negative-prompt-inversion
        substitute (Miyake et al., 2023): the unconditional embedding is set
        to the SOURCE conditional embedding at every step, under which the
        CFG combine ``ε_u + g·(ε_c − ε_u)`` collapses to ``ε_c`` and the
        denoise replays the inversion trajectory to NPI accuracy with zero
        inner Adam steps. One forward per outer step (vs ``2 +
        3·num_inner_steps`` forward-equivalents), one fused scan; the
        returned ``final_loss`` per step is the SAME reconstruction
        objective the optimizer would have minimized — the direct parity
        record. ``num_inner_steps``/``epsilon``/``early_stop`` are inert;
        ``inner_steps`` reads 0 everywhere.
      * ``"hybrid"`` — amortized seed + ``hybrid_inner_steps`` (K ≤ 3
        recommended) refinement Adam steps run JOINTLY across all outer
        steps: each step optimizes its embedding against the RECORDED
        trajectory latents (the amortized fixed point), so the 50 outer
        optimizations lose their sequential dependence and batch into one
        K-iteration program — K sequential gradient phases instead of
        ``50 × num_inner_steps``. ``final_loss`` is each step's last
        pre-update loss (the ``"optimize"`` convention); ``inner_steps``
        reads K everywhere (no early stop — the batch is joint).

    Both non-default modes trade a bounded reconstruction-accuracy delta
    (pinned as a PSNR band in tests/test_null_text_precision.py and gated
    by the quality rules, tools/obs_diff.py) for a ≥3× inner-loop flop
    reduction; ``outer_chunk`` composes with every mode (chunked ==
    unchunked, per-step math identical).
    """
    if null_text_precision not in _NULL_TEXT_PRECISIONS:
        raise ValueError(
            f"null_text_precision {null_text_precision!r} not in "
            f"{_NULL_TEXT_PRECISIONS}"
        )
    if null_text_mode not in _NULL_TEXT_MODES:
        raise ValueError(
            f"null_text_mode {null_text_mode!r} not in {_NULL_TEXT_MODES}"
        )
    if dependent_weight > 0.0 and dependent_sampler is None:
        raise ValueError("dependent_weight > 0 requires dependent_sampler")
    if key is None:
        key = jax.random.key(0)
    timesteps = jnp.asarray(scheduler.timesteps(num_inference_steps))
    # the optimized variable and its Adam moments are float32 in EVERY
    # precision mode (a bf16 text encoder hands over a bf16 uncond); the
    # trajectory targets likewise — loss accumulation must be fp32
    uncond_embedding = uncond_embedding.astype(jnp.float32)
    trajectory = trajectory.astype(jnp.float32)
    # latent_prev for outer step i is trajectory[num - i - 1]
    # (the reference's latents[len - i - 2], run_videop2p.py:585)
    prev_seq = trajectory[::-1][1:]
    steps = jnp.arange(num_inference_steps)
    # run_videop2p.py:588 — clamped at 0 so step counts > 100 (the reference
    # hardcodes 50) cannot flip the update into gradient ascent
    lr_seq = jnp.maximum(1e-2 * (1.0 - steps / 100.0), 0.0)
    thresh_seq = epsilon + steps * 2e-5  # run_videop2p.py:603
    # Adam direction with unit lr; the decayed per-step lr scales the update;
    # moments and updates live in the embedding's own float32 — the Adam
    # state is never narrowed in mixed mode
    adam = optax.adam(1.0)
    # mixed precision: only the tensors CROSSING the UNet boundary narrow to
    # bf16; predictions upcast to float32 the moment they come back, so the
    # CFG combine, the scheduler islands, and the loss all accumulate fp32
    mixed = null_text_precision == "mixed"
    cast_in = (lambda a: a.astype(jnp.bfloat16)) if mixed else (lambda a: a)

    def fwd(params, latent, t, text):
        eps, _ = unet_fn(params, cast_in(latent), t, cast_in(text), None)
        return eps.astype(jnp.float32)

    def blend(eps, key):
        if dependent_weight <= 0.0:
            return eps
        ar_noise = dependent_sampler.sample_like(key, eps)
        return (1.0 - dependent_weight) * eps + dependent_weight * ar_noise

    def outer(carry, xs):
        latent_cur, uncond, key, params, cond_embedding = carry
        t, latent_prev, lr, thresh = xs
        key, k_cond, k_fu, k_fc = jax.random.split(key, 4)
        eps_cond_raw = jax.lax.stop_gradient(
            fwd(params, latent_cur, t, cond_embedding)
        )
        eps_cond = blend(eps_cond_raw, k_cond)

        def loss_fn(u, k):
            eps_uncond = blend(fwd(params, latent_cur, t, u), k)
            eps = eps_uncond + guidance_scale * (eps_cond - eps_uncond)
            prev_rec = scheduler.prev_step(eps, t, latent_cur, num_inference_steps)
            return jnp.mean((prev_rec - latent_prev) ** 2)

        def inner_cond(state):
            _, _, last_loss, j, _ = state
            if not early_stop:
                return j < num_inner_steps
            return jnp.logical_and(j < num_inner_steps, last_loss >= thresh)

        def inner_body(state):
            u, opt_state, _, j, k = state
            k, sub = jax.random.split(k)
            loss, grads = jax.value_and_grad(loss_fn)(u, sub)
            updates, opt_state = adam.update(grads, opt_state, u)
            u = optax.apply_updates(u, jax.tree.map(lambda g: lr * g, updates))
            return (u, opt_state, loss, j + 1, k)

        opt_state = adam.init(uncond)
        uncond, _, final_loss, inner_taken, key = jax.lax.while_loop(
            inner_cond,
            inner_body,
            (uncond, opt_state, jnp.asarray(jnp.inf, jnp.float32),
             jnp.asarray(0, jnp.int32), key),
        )

        # advance with the optimized embedding under full CFG; the reference
        # blends the batched (2B) prediction with one batched draw — i.e.
        # independent fresh noise per half (run_videop2p.py:474-487,606-610);
        # the cond prediction is deterministic so its raw value is reused
        eps_uncond = blend(fwd(params, latent_cur, t, uncond), k_fu)
        eps_c = blend(eps_cond_raw, k_fc)
        eps = eps_uncond + guidance_scale * (eps_c - eps_uncond)
        latent_cur = scheduler.prev_step(eps, t, latent_cur, num_inference_steps)
        ys = (uncond, final_loss, inner_taken)
        if telemetry:
            # scalar stats ride the scan output — no extra dispatch, and
            # a fused-scan NaN becomes visible with the step it appeared at
            ys += (latent_stats(latent_cur),)
        return (latent_cur, uncond, key, params, cond_embedding), ys

    def outer_amortized(carry, xs):
        # negative-prompt-inversion closed form: uncond := cond, so the CFG
        # combine collapses to the conditional prediction — ONE forward per
        # outer step, zero inner Adam steps. The per-step loss is the same
        # reconstruction objective the optimizer minimizes (the replay's
        # residual against the recorded trajectory), so the record stays
        # directly comparable to the "optimize" mode's final_loss.
        latent_cur, _uncond, key, params, cond_embedding = carry
        t, latent_prev, _lr, _thresh = xs
        key, k_fu, k_fc = jax.random.split(key, 3)
        eps_cond_raw = fwd(params, latent_cur, t, cond_embedding)
        uncond_out = cond_embedding.astype(jnp.float32)
        # dependent mode: the CFG halves draw independent fresh noise, the
        # same structure as the optimize mode's final advance
        eps_uncond = blend(eps_cond_raw, k_fu)
        eps_c = blend(eps_cond_raw, k_fc)
        eps = eps_uncond + guidance_scale * (eps_c - eps_uncond)
        prev_rec = scheduler.prev_step(eps, t, latent_cur, num_inference_steps)
        final_loss = jnp.mean((prev_rec - latent_prev) ** 2)
        ys = (uncond_out, final_loss, jnp.asarray(0, jnp.int32))
        if telemetry:
            ys += (latent_stats(prev_rec),)
        return (prev_rec, uncond_out, key, params, cond_embedding), ys

    outer_fn = outer if null_text_mode == "optimize" else outer_amortized

    x_t = trajectory[-1]
    xs = (timesteps, prev_seq, lr_seq, thresh_seq)

    def make_body(p, cond):
        # params/cond are scan CONSTANTS (closed over per scan), never carry
        # — a carried tree is held twice inside the executable (carry-in +
        # carry-out), which for SD-scale params tips a 16 GB chip into OOM
        def body(c, x):
            lat, unc, k = c
            (lat, unc, k, _, _), y = outer_fn((lat, unc, k, p, cond), x)
            return (lat, unc, k), y

        return body

    def pack(uncond_seq, losses, inner_taken, tel=None):
        out = (uncond_seq,)
        if return_losses:
            out += (losses,)
        if return_inner_steps:
            out += (inner_taken,)
        if telemetry:
            out += (tel,)
        return out if len(out) > 1 else out[0]

    if null_text_mode == "hybrid":
        K = int(hybrid_inner_steps)
        if K < 1:
            raise ValueError(f"hybrid_inner_steps must be >= 1, got {K}")
        # every step optimizes against the RECORDED trajectory latents (the
        # amortized fixed point, where the CFG replay already tracks the
        # trajectory), so the outer steps lose the sequential dependence the
        # "optimize" mode carries through latent_cur: K gradient phases over
        # a step-batched embedding replace N×num_inner_steps sequential
        # inner steps. Per-step math is chunk-invariant (absolute-index
        # keys, independent steps), so chunked == unchunked exactly.
        lat_cur_seq = trajectory[::-1][:-1]  # latent entering outer step i
        step_keys = jax.vmap(lambda i: jax.random.fold_in(key, i))(steps)

        def hybrid_chunk_fn(p, cond, chunk_xs):
            t_c, lat_c, prev_c, lr_c, k_c = chunk_xs
            ks = jax.vmap(lambda k: jax.random.split(k, 2))(k_c)

            def cond_eps(lat, t, k):
                return blend(jax.lax.stop_gradient(fwd(p, lat, t, cond)), k)

            eps_cond = jax.vmap(cond_eps)(lat_c, t_c, ks[:, 0])
            # amortized seed: uncond := cond at every step
            u0 = jnp.broadcast_to(
                cond.astype(jnp.float32), (t_c.shape[0],) + cond.shape
            )
            opt_state = adam.init(u0)

            def loss_one(u, lat, t, ec, lp, k):
                eps_uncond = blend(fwd(p, lat, t, u), k)
                eps = eps_uncond + guidance_scale * (ec - eps_uncond)
                prev_rec = scheduler.prev_step(
                    eps, t, lat, num_inference_steps
                )
                return jnp.mean((prev_rec - lp) ** 2), prev_rec

            grad_one = jax.value_and_grad(loss_one, has_aux=True)

            def iter_body(carry, _):
                u_seq, opt_state, kseq = carry
                kpair = jax.vmap(lambda k: jax.random.split(k, 2))(kseq)
                (losses, prev_recs), grads = jax.vmap(grad_one)(
                    u_seq, lat_c, t_c, eps_cond, prev_c, kpair[:, 0]
                )
                updates, opt_state = adam.update(grads, opt_state, u_seq)
                u_seq = optax.apply_updates(
                    u_seq,
                    jax.tree.map(
                        lambda g: lr_c[:, None, None, None] * g, updates
                    ),
                )
                ys = (losses,)
                if telemetry:
                    # scalars only in the iteration ys — stacking prev_recs
                    # across K would hold K extra trajectories in HBM
                    ys += (jax.vmap(latent_stats)(prev_recs),)
                return (u_seq, opt_state, kpair[:, 1]), ys

            (u_seq, _, _), it_ys = jax.lax.scan(
                iter_body, (u0, opt_state, ks[:, 1]), None, length=K
            )
            # the "optimize" convention: final_loss is the last executed
            # iteration's pre-update loss
            outs = (
                u_seq,
                it_ys[0][-1],
                jnp.full((t_c.shape[0],), K, jnp.int32),
            )
            if telemetry:
                outs += (jax.tree.map(lambda a: a[-1], it_ys[1]),)
            return outs

        hybrid_xs = (timesteps, lat_cur_seq, prev_seq, lr_seq, step_keys)
        if not outer_chunk or outer_chunk >= num_inference_steps:
            return pack(*hybrid_chunk_fn(params, cond_embedding, hybrid_xs))
        cache_key = (
            "hybrid", unet_fn, id(scheduler), id(dependent_sampler),
            float(guidance_scale), K, int(num_inference_steps),
            float(dependent_weight), null_text_precision, bool(telemetry),
        )
        chunk_prog = _CHUNK_SCAN_CACHE.get(cache_key)
        if chunk_prog is None:
            from videop2p_tpu.obs.ledger import instrumented_jit

            chunk_prog = instrumented_jit(
                hybrid_chunk_fn, program="null_text_chunked"
            )
            _cache_put(_CHUNK_SCAN_CACHE, _CHUNK_SCAN_CACHE_MAX,
                       cache_key, chunk_prog)
        pieces = None
        for start in range(0, num_inference_steps, outer_chunk):
            chunk = jax.tree.map(
                lambda a: a[start : start + outer_chunk], hybrid_xs
            )
            ys = chunk_prog(params, cond_embedding, chunk)
            if pieces is None:
                pieces = [[] for _ in ys]
            for lst, y in zip(pieces, ys):
                lst.append(y)
        return pack(*(
            jax.tree.map(lambda *xs_: jnp.concatenate(xs_, axis=0), *lst)
            for lst in pieces
        ))

    if not outer_chunk or outer_chunk >= num_inference_steps:
        _, ys = jax.lax.scan(
            make_body(params, cond_embedding), (x_t, uncond_embedding, key), xs
        )
        return pack(*ys)

    # chunked path: params/cond enter as plain jit inputs (same no-carry rule
    # as above), and the jitted chunk scan is cached on the statics its
    # closure bakes in so repeat calls reuse the compiled program
    cache_key = (
        unet_fn, id(scheduler), id(dependent_sampler), float(guidance_scale),
        int(num_inner_steps), int(num_inference_steps), float(dependent_weight),
        bool(early_stop), null_text_precision, null_text_mode, bool(telemetry),
    )
    chunk_scan = _CHUNK_SCAN_CACHE.get(cache_key)
    if chunk_scan is None:

        def chunk_fn(p, cond, small_carry, chunk_xs):
            return jax.lax.scan(make_body(p, cond), small_carry, chunk_xs)

        # instrumented: with an active ledger each chunk dispatch records a
        # program_call, and the compile (first chunk) is mined into a
        # program_analysis event (obs/introspect.py); with no ledger this
        # is jax.jit plus one attribute lookup per call
        from videop2p_tpu.obs.ledger import instrumented_jit

        chunk_scan = instrumented_jit(chunk_fn, program="null_text_chunked")
        _cache_put(_CHUNK_SCAN_CACHE, _CHUNK_SCAN_CACHE_MAX, cache_key, chunk_scan)
    small = (x_t, uncond_embedding, key)
    piece_lists = None
    for start in range(0, num_inference_steps, outer_chunk):
        chunk = jax.tree.map(lambda a: a[start : start + outer_chunk], xs)
        small, ys = chunk_scan(params, cond_embedding, small, chunk)
        if piece_lists is None:
            piece_lists = [[] for _ in ys]
        for lst, y in zip(piece_lists, ys):
            lst.append(y)
    return pack(*(
        jax.tree.map(lambda *xs_: jnp.concatenate(xs_, axis=0), *lst)
        for lst in piece_lists
    ))


def null_text_optimization_fused(
    unet_fn: UNetFn,
    params,
    scheduler: DDIMScheduler,
    trajectory: jax.Array,
    cond_embedding: jax.Array,
    uncond_embedding: jax.Array,
    *,
    num_inference_steps: int = 50,
    guidance_scale: float = 7.5,
    num_inner_steps: int = 10,
    epsilon: float = 1e-5,
    null_text_precision: str = "fp32",
    null_text_mode: str = "optimize",
    hybrid_inner_steps: int = 3,
    dependent_weight: float = 0.0,
    dependent_sampler: Optional[DependentNoiseSampler] = None,
    key: Optional[jax.Array] = None,
    early_stop: bool = True,
    donate: bool = True,
    return_stats: bool = False,
    telemetry: bool = False,
):
    """Null-text optimization as ONE jitted, donated-carry device program.

    The host-driven structure (an outer Python/jit-chunk loop re-dispatching
    per segment) pays a host round trip per dispatch and re-uploads the
    scan constants each time; here the whole 50-step outer scan — inner
    bounded ``lax.while_loop`` Adam with the convergence predicate carried
    on-device — compiles to a single XLA program, dispatched once. The
    trajectory buffer (the largest input, ~270 MB at SD scale 8f) is DONATED
    to the program by default: XLA reuses it for scan temporaries instead of
    holding input + workspace side by side. Callers that still need the
    trajectory afterwards must pass ``donate=False`` (the CLI extracts x_T
    before optimizing, so its buffer is free to donate).

    Precision follows ``null_text_precision`` exactly as in
    :func:`null_text_optimization` (which this wraps): bf16 UNet forwards in
    ``"mixed"`` with fp32 scheduler coefficients (core/ddim.py islands),
    fp32 Adam state, and fp32 loss/early-stop accumulation.
    ``null_text_mode``/``hybrid_inner_steps`` select the amortized
    (closed-form negative-prompt) or hybrid (joint K-step refinement)
    substitutes, likewise passed through — every mode compiles to one
    donated-trajectory device program here.

    Watchdog note: at SD scale the fp32 fixed-10 program can be a
    multi-minute single device call — the TPU runtime's execution watchdog
    territory that motivated ``outer_chunk``. The mixed program cuts that
    wall-clock ~3-4×; if a deployment still trips the watchdog, fall back to
    ``null_text_optimization(outer_chunk=...)`` (the CLI exposes
    ``--null_text_chunk`` for exactly this).

    Returns the per-step uncond embeddings (num_steps, B, L, D); with
    ``return_stats=True`` returns ``(uncond_seq, stats)`` where ``stats`` is
    ``{"final_loss": (num_steps,) float32, "inner_steps": (num_steps,)
    int32}`` — the reconstruction objective per outer step and the inner
    Adam updates its early stop actually took. ``telemetry=True``
    (requires ``return_stats``) adds ``stats["latent_stats"]`` — per-outer-
    step latent abs-max/mean/NaN/inf scalars stacked inside the SAME fused
    program (obs.telemetry; zero extra dispatches, off by default so the
    donated fast path is untouched).
    """
    if null_text_precision not in _NULL_TEXT_PRECISIONS:
        raise ValueError(
            f"null_text_precision {null_text_precision!r} not in "
            f"{_NULL_TEXT_PRECISIONS}"
        )
    if null_text_mode not in _NULL_TEXT_MODES:
        raise ValueError(
            f"null_text_mode {null_text_mode!r} not in {_NULL_TEXT_MODES}"
        )
    if dependent_weight > 0.0 and dependent_sampler is None:
        raise ValueError("dependent_weight > 0 requires dependent_sampler")
    if telemetry and not return_stats:
        raise ValueError(
            "telemetry=True surfaces through the stats record — pass "
            "return_stats=True (silently computing-and-dropping telemetry "
            "would still change the compiled program)"
        )
    if key is None:
        key = jax.random.key(0)
    # the CPU backend cannot alias donated buffers — requesting donation
    # there only produces an unusable-donation warning per call
    donate = donate and jax.default_backend() != "cpu"

    cache_key = (
        unet_fn, id(scheduler), id(dependent_sampler), float(guidance_scale),
        int(num_inner_steps), int(num_inference_steps), float(dependent_weight),
        float(epsilon), bool(early_stop), null_text_precision, null_text_mode,
        int(hybrid_inner_steps), bool(donate), bool(telemetry),
    )
    program = _FUSED_PROGRAM_CACHE.get(cache_key)
    if program is None:

        def program_fn(p, cond, traj, uncond, k):
            return null_text_optimization(
                unet_fn, p, scheduler, traj, cond, uncond,
                num_inference_steps=num_inference_steps,
                guidance_scale=guidance_scale,
                num_inner_steps=num_inner_steps,
                epsilon=epsilon,
                null_text_precision=null_text_precision,
                null_text_mode=null_text_mode,
                hybrid_inner_steps=hybrid_inner_steps,
                dependent_weight=dependent_weight,
                dependent_sampler=dependent_sampler,
                key=k,
                early_stop=early_stop,
                return_losses=True,
                return_inner_steps=True,
                telemetry=telemetry,
            )

        # argnum 2 = the trajectory, the only buffer worth donating (the
        # uncond embedding is KB-scale and callers routinely reuse theirs).
        # instrumented_jit: the fused program jits inside this cache where
        # the CLI's wrappers cannot reach it — instrumenting HERE is what
        # lands its program_call / program_analysis ledger events (the
        # analysis abstracts its arguments first, so donation is safe)
        from videop2p_tpu.obs.ledger import instrumented_jit

        program = instrumented_jit(
            program_fn, program="null_text_fused",
            donate_argnums=(2,) if donate else ()
        )
        _cache_put(_FUSED_PROGRAM_CACHE, _FUSED_PROGRAM_CACHE_MAX,
                   cache_key, program)

    outs = program(params, cond_embedding, trajectory, uncond_embedding, key)
    uncond_seq, losses, inner_taken = outs[:3]
    if return_stats:
        stats = {"final_loss": losses, "inner_steps": inner_taken}
        if telemetry:
            stats["latent_stats"] = outs[3]
        return uncond_seq, stats
    return uncond_seq
