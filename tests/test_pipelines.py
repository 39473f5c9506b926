"""Pipeline tests: inversion round-trip, fast-mode source replay, null-text
optimization, and the controlled edit loop end-to-end on a tiny UNet.

SURVEY §4's recommended strategy: exact contract tests on analytic fake
denoisers (where DDIM inversion must invert bit-for-bit), plus a tiny-model
end-to-end edit exercising UNet + scheduler + scan + controllers together.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

pytestmark = pytest.mark.slow

from videop2p_tpu.control import make_controller
from videop2p_tpu.core import DDIMScheduler
from videop2p_tpu.models import UNet3DConditionModel, UNet3DConfig
from videop2p_tpu.pipelines import (
    ddim_inversion,
    edit_sample,
    make_unet_fn,
    null_text_optimization,
)
from videop2p_tpu.utils.tokenizers import WordTokenizer

STEPS = 10
SHAPE = (1, 2, 8, 8, 4)  # (B, F, h, w, C)


@pytest.fixture(scope="module")
def sched():
    return DDIMScheduler.create_sd()


def const_unet(eps0):
    """Denoiser that ignores its input — DDIM inversion is then exactly
    invertible (next_step and prev_step use the identical ε)."""

    def fn(params, sample, t, text, control=None):
        return jnp.broadcast_to(eps0, sample.shape), {}

    return fn


def text_unet():
    """Denoiser whose output depends on the text embedding and latent — gives
    null-text optimization a real objective."""

    def fn(params, sample, t, text, control=None):
        bias = jnp.mean(text, axis=(1, 2))  # (B,)
        return 0.1 * sample + bias[:, None, None, None, None], {}

    return fn


@pytest.fixture(scope="module")
def tiny():
    cfg = UNet3DConfig.tiny()
    model = UNet3DConditionModel(config=cfg)
    sample = jax.random.normal(jax.random.key(0), SHAPE)
    text = jax.random.normal(jax.random.key(1), (1, 77, cfg.cross_attention_dim))
    params = jax.jit(model.init)(jax.random.key(2), sample, jnp.asarray(10), text)
    return make_unet_fn(model), params, cfg


def test_inversion_exact_roundtrip_const_eps(sched):
    """With an x-independent ε the forward DDIM walk must be exactly inverted
    by the reverse walk (scheduler next_step/prev_step are mutual inverses
    given the same ε — run_videop2p.py:445-463 closed forms)."""
    x0 = jax.random.normal(jax.random.key(0), SHAPE)
    eps0 = jax.random.normal(jax.random.key(1), SHAPE[1:])
    fn = const_unet(eps0)
    traj = jax.jit(
        lambda x: ddim_inversion(fn, None, sched, x, jnp.zeros((1, 77, 8)),
                                 num_inference_steps=STEPS)
    )(x0)
    assert traj.shape == (STEPS + 1,) + SHAPE
    # walk back with prev_step
    lat = traj[-1]
    ts = sched.timesteps(STEPS)
    for t in ts:
        lat = sched.prev_step(jnp.broadcast_to(eps0, lat.shape), t, lat, STEPS)
    np.testing.assert_allclose(np.asarray(lat), np.asarray(x0), atol=1e-4)


def test_edit_sample_replays_inversion_const_eps(sched):
    """edit_sample with source_uses_cfg=False (fast mode) must replay the
    inversion for the source stream (pipeline_tuneavideo.py:412-415)."""
    x0 = jax.random.normal(jax.random.key(0), SHAPE)
    eps0 = jax.random.normal(jax.random.key(1), SHAPE[1:])
    fn = const_unet(eps0)
    cond = jnp.zeros((2, 77, 8))
    uncond = jnp.ones((77, 8))
    traj = ddim_inversion(fn, None, sched, x0, cond[:1], num_inference_steps=STEPS)
    out = jax.jit(
        lambda xt: edit_sample(
            fn, None, sched, xt, cond, uncond,
            num_inference_steps=STEPS, guidance_scale=7.5, source_uses_cfg=False,
        )
    )(traj[-1])
    assert out.shape == (2,) + SHAPE[1:]
    np.testing.assert_allclose(np.asarray(out[0]), np.asarray(x0[0]), atol=1e-4)


def test_tiny_unet_fast_source_stream_batch_independent(sched, tiny):
    """On a real (random-init) tiny UNet the fast-mode source stream of the
    CFG batch must equal a standalone single-prompt cond-only denoise from the
    same x_T — i.e. batching other streams alongside cannot perturb the source
    (this is what makes fast-mode inversion replay exact in the reference,
    pipeline_tuneavideo.py:412-415)."""
    fn, params, cfg = tiny
    x_t = jax.random.normal(jax.random.key(3), SHAPE)
    cond1 = jax.random.normal(jax.random.key(4), (1, 77, cfg.cross_attention_dim))
    cond = jnp.concatenate([cond1, cond1 + 0.1], axis=0)
    uncond = jnp.zeros((77, cfg.cross_attention_dim))
    out2 = jax.jit(
        lambda xt: edit_sample(
            fn, params, sched, xt, cond, uncond,
            num_inference_steps=STEPS, source_uses_cfg=False,
        )
    )(x_t)
    out1 = jax.jit(
        lambda xt: edit_sample(
            fn, params, sched, xt, cond1, uncond,
            num_inference_steps=STEPS, source_uses_cfg=False,
        )
    )(x_t)
    np.testing.assert_allclose(np.asarray(out2[0]), np.asarray(out1[0]), atol=1e-4)


def test_null_text_optimization_improves_replay(sched):
    """Optimized per-step uncond embeddings must reconstruct the inversion
    trajectory under CFG better than the raw uncond embedding
    (the whole point of null-text inversion, run_videop2p.py:580-612)."""
    fn = text_unet()
    x0 = jax.random.normal(jax.random.key(0), SHAPE)
    cond = 0.3 * jnp.ones((1, 77, 8))
    uncond = jnp.zeros((1, 77, 8))
    traj = ddim_inversion(fn, None, sched, x0, cond, num_inference_steps=STEPS)
    uncond_seq = jax.jit(
        lambda tr: null_text_optimization(
            fn, None, sched, tr, cond, uncond,
            num_inference_steps=STEPS, guidance_scale=7.5,
        )
    )(traj)
    assert uncond_seq.shape == (STEPS,) + uncond.shape

    def replay(null_seq):
        return edit_sample(
            fn, None, sched, traj[-1], cond, uncond,
            num_inference_steps=STEPS, guidance_scale=7.5, source_uses_cfg=True,
            null_uncond_embeddings=null_seq,
        )

    err_opt = np.mean(np.abs(np.asarray(replay(uncond_seq)[0] - x0[0])))
    err_raw = np.mean(np.abs(np.asarray(replay(None)[0] - x0[0])))
    assert err_opt < err_raw * 0.5, (err_opt, err_raw)


def test_controlled_edit_end_to_end(sched, tiny):
    """Full edit on the tiny UNet: refine controller + equalizer + LocalBlend,
    5 steps. Source stream must match the control-free run; outputs finite."""
    fn, params, cfg = tiny
    tok = WordTokenizer()
    prompts = ["a rabbit is jumping", "a origami rabbit is jumping"]
    ctx = make_controller(
        prompts, tok, num_steps=5,
        is_replace_controller=False,
        cross_replace_steps=0.8, self_replace_steps=0.6,
        blend_words=(["rabbit"], ["rabbit"]),
        equalizer_params={"words": ["origami"], "values": [2.0]},
    )
    # text embeddings must be 77-long to match the control tensors
    cond = jax.random.normal(jax.random.key(7), (2, 77, cfg.cross_attention_dim))
    uncond = jnp.zeros((77, cfg.cross_attention_dim))
    x_t = jax.random.normal(jax.random.key(8), SHAPE)

    run = jax.jit(
        lambda c: edit_sample(
            fn, params, sched, x_t, cond, uncond,
            num_inference_steps=5, ctx=c, source_uses_cfg=False,
            blend_res=(4, 4),
        )
    )
    out_ctrl = run(ctx)
    out_free = jax.jit(
        lambda: edit_sample(
            fn, params, sched, x_t, cond, uncond,
            num_inference_steps=5, source_uses_cfg=False,
        )
    )()
    assert out_ctrl.shape == (2,) + SHAPE[1:]
    assert np.isfinite(np.asarray(out_ctrl)).all()
    # the edit changes the edited stream but not the source stream
    np.testing.assert_allclose(
        np.asarray(out_ctrl[0]), np.asarray(out_free[0]), atol=1e-4
    )
    assert not np.allclose(np.asarray(out_ctrl[1]), np.asarray(out_free[1]), atol=1e-4)


def test_long_video_chunked_controlled_edit(sched):
    """The long-video working point at tiny scale (BASELINE configs 3/5 —
    24 frames): invert + controlled edit with the
    query-chunked frame-attention kernel, which is the only memory-feasible
    kernel at 24 frames on one chip (dense 64²-site scores are ~19 GB).
    Chunked must agree with dense at identical params, and the blend carry /
    temporal control must shape-generalize past the 8-frame default.

    The dispatch rule falls back to dense below 1024 tokens, so at the tiny
    UNet's 64-token sites the kernel is forced in directly with a small
    q_chunk — otherwise this would compare dense against itself."""
    import functools

    from videop2p_tpu.ops.attention import chunked_frame_attention

    F_LONG = 24
    cfg = UNet3DConfig.tiny()
    model = UNet3DConditionModel(
        config=cfg,
        frame_attention_fn=functools.partial(chunked_frame_attention, q_chunk=16),
    )
    shape = (1, F_LONG, 8, 8, 4)
    x0 = jax.random.normal(jax.random.key(0), shape)
    cond = jax.random.normal(jax.random.key(1), (2, 77, cfg.cross_attention_dim))
    uncond = jnp.zeros((77, cfg.cross_attention_dim))
    params = jax.jit(model.init)(jax.random.key(2), x0, jnp.asarray(10), cond[:1])
    fn = make_unet_fn(model)
    ctx = make_controller(
        ["a rabbit is jumping", "a origami rabbit is jumping"],
        WordTokenizer(), num_steps=3,
        is_replace_controller=False,
        cross_replace_steps=0.8, self_replace_steps=0.6,
        blend_words=(["rabbit"], ["rabbit"]),
        equalizer_params={"words": ["origami"], "values": [2.0]},
    )

    def run(fn_):
        traj = ddim_inversion(fn_, params, sched, x0, cond[:1],
                              num_inference_steps=3)
        return edit_sample(
            fn_, params, sched, traj[-1], cond, uncond,
            num_inference_steps=3, ctx=ctx, source_uses_cfg=False,
            blend_res=(4, 4),
        )

    out = jax.jit(lambda: run(fn))()
    assert out.shape == (2,) + shape[1:]
    assert np.isfinite(np.asarray(out)).all()

    # kernel equivalence at the same params: chunked == dense (exact math;
    # the tolerance covers reduce-order fp drift amplified over the scan)
    model_dense = UNet3DConditionModel(config=UNet3DConfig.tiny())
    out_dense = jax.jit(lambda: run(make_unet_fn(model_dense)))()
    np.testing.assert_allclose(
        np.asarray(out), np.asarray(out_dense), atol=2e-3, rtol=1e-3
    )


def test_eta_dependent_noise_path(sched):
    """η>0 with the dependent sampler draws frame-correlated variance noise
    (dependent_ddim.py:320-334) — adjacent-frame noise correlation must be
    visible in the output difference from the η=0 path."""
    from videop2p_tpu.core import DependentNoiseSampler

    fn = const_unet(jnp.zeros(SHAPE[1:]))
    sampler = DependentNoiseSampler.create(num_frames=2, decay_rate=0.9, window_size=2)
    cond = jnp.zeros((1, 77, 8))
    uncond = jnp.zeros((77, 8))
    x_t = jax.random.normal(jax.random.key(0), SHAPE)
    out_eta = edit_sample(
        fn, None, sched, x_t, cond, uncond, num_inference_steps=STEPS,
        eta=0.5, dependent_sampler=sampler, key=jax.random.key(1),
    )
    out_det = edit_sample(
        fn, None, sched, x_t, cond, uncond, num_inference_steps=STEPS,
    )
    assert out_eta.shape == out_det.shape
    assert not np.allclose(np.asarray(out_eta), np.asarray(out_det))


def test_null_text_dependent_mode(sched):
    """Dependent mode threads AR-noise blends through every prediction
    (run_videop2p.py:465-487) and stays finite; lr clamps at 0 for >100 steps."""
    from videop2p_tpu.core import DependentNoiseSampler

    fn = text_unet()
    sampler = DependentNoiseSampler.create(num_frames=2, decay_rate=0.5, window_size=2)
    x0 = jax.random.normal(jax.random.key(0), SHAPE)
    cond = 0.3 * jnp.ones((1, 77, 8))
    uncond = jnp.zeros((1, 77, 8))
    traj = ddim_inversion(
        fn, None, sched, x0, cond, num_inference_steps=STEPS,
        dependent_weight=0.3, dependent_sampler=sampler, key=jax.random.key(1),
    )
    out = jax.jit(
        lambda tr: null_text_optimization(
            fn, None, sched, tr, cond, uncond, num_inference_steps=STEPS,
            dependent_weight=0.3, dependent_sampler=sampler, key=jax.random.key(2),
        )
    )(traj)
    assert out.shape == (STEPS, 1, 77, 8)
    assert np.isfinite(np.asarray(out)).all()
    import pytest
    with pytest.raises(ValueError, match="requires dependent_sampler"):
        null_text_optimization(fn, None, sched, traj, cond, uncond,
                               num_inference_steps=STEPS, dependent_weight=0.3)


def test_spatial_replace_injects_source_latents(sched, tiny):
    """SpatialReplace (run_videop2p.py:235-246): while step < stop bound the
    edit stream's latents are the source stream's; afterwards they evolve
    freely, so with stop_inject=1.0 (never inject) streams differ."""
    from videop2p_tpu.control import make_spatial_replace_controller

    fn, params, cfg = tiny
    cond1 = jax.random.normal(jax.random.key(4), (1, 77, cfg.cross_attention_dim))
    cond = jnp.concatenate([cond1, cond1 + 0.5], axis=0)
    uncond = jnp.zeros((77, cfg.cross_attention_dim))
    x_t = jax.random.normal(jax.random.key(5), SHAPE)

    ctx_full = make_spatial_replace_controller(0.0, STEPS)  # inject every step
    out_full = jax.jit(
        lambda xt: edit_sample(
            fn, params, sched, xt, cond, uncond,
            num_inference_steps=STEPS, ctx=ctx_full,
        )
    )(x_t)
    np.testing.assert_allclose(
        np.asarray(out_full[1]), np.asarray(out_full[0]), atol=1e-5
    )

    ctx_off = make_spatial_replace_controller(1.0, STEPS)  # never inject
    out_off = jax.jit(
        lambda xt: edit_sample(
            fn, params, sched, xt, cond, uncond,
            num_inference_steps=STEPS, ctx=ctx_off,
        )
    )(x_t)
    assert not np.allclose(np.asarray(out_off[1]), np.asarray(out_off[0]), atol=1e-5)


def test_multi_frame_embeddings_match_shared(sched, tiny):
    """Per-frame ("multi") conditioning (pipeline_tuneavideo.py:366-367):
    frame-constant 4-D embeddings must reproduce the 3-D path exactly, and
    per-frame-varying embeddings must change the output."""
    fn, params, cfg = tiny
    F = SHAPE[1]
    cond = jax.random.normal(jax.random.key(6), (2, 77, cfg.cross_attention_dim))
    uncond = jnp.zeros((77, cfg.cross_attention_dim))
    x_t = jax.random.normal(jax.random.key(7), SHAPE)

    out3 = jax.jit(
        lambda xt: edit_sample(
            fn, params, sched, xt, cond, uncond, num_inference_steps=STEPS,
        )
    )(x_t)
    cond4 = jnp.repeat(cond[:, None], F, axis=1)  # (P, F, 77, D)
    out4 = jax.jit(
        lambda xt: edit_sample(
            fn, params, sched, xt, cond4, uncond, num_inference_steps=STEPS,
        )
    )(x_t)
    np.testing.assert_allclose(np.asarray(out4), np.asarray(out3), atol=1e-4)

    cond4v = cond4.at[:, 1:].add(0.5)  # vary later frames
    out4v = jax.jit(
        lambda xt: edit_sample(
            fn, params, sched, xt, cond4v, uncond, num_inference_steps=STEPS,
        )
    )(x_t)
    assert not np.allclose(np.asarray(out4v), np.asarray(out3), atol=1e-4)


def test_null_text_chunked_matches_full(sched):
    """outer_chunk splits the outer scan into host-level jitted chunks — the
    result must be identical to the single-scan path (watchdog workaround
    for the multi-minute SD-scale program)."""
    fn = text_unet()
    x0 = jax.random.normal(jax.random.key(0), SHAPE)
    cond = 0.3 * jnp.ones((1, 77, 8))
    uncond = jnp.zeros((1, 77, 8))
    traj = ddim_inversion(fn, None, sched, x0, cond, num_inference_steps=STEPS)
    full = null_text_optimization(
        fn, None, sched, traj, cond, uncond, num_inference_steps=STEPS,
    )
    chunked = null_text_optimization(
        fn, None, sched, traj, cond, uncond, num_inference_steps=STEPS,
        outer_chunk=4,  # 10 steps → chunks of 4, 4, 2 (uneven tail covered)
    )
    np.testing.assert_allclose(
        np.asarray(chunked), np.asarray(full), rtol=2e-5, atol=2e-6
    )


def test_cached_eps_replay_is_exact(sched, tiny):
    """DDIM next_step/prev_step are linear in (x, ε) with identical
    coefficients, so walking the inversion trajectory BACKWARD with the
    cached per-step ε recovers every latent exactly — the property behind
    the cached-source fast edit (ddim_inversion(return_eps=True)). The
    reference's fast mode re-predicts ε from the drifting latent and only
    approximately reconstructs; the cached replay is bit-tight."""
    fn, params, cfg = tiny
    x0 = 0.3 * jax.random.normal(jax.random.key(11), SHAPE)
    cond = jax.random.normal(jax.random.key(12), (1, 77, cfg.cross_attention_dim))

    traj, eps_seq = jax.jit(
        lambda p, x: ddim_inversion(
            fn, p, sched, x, cond, num_inference_steps=STEPS, return_eps=True
        )
    )(params, x0)
    assert traj.shape[0] == STEPS + 1 and eps_seq.shape[0] == STEPS

    timesteps = np.asarray(sched.timesteps(STEPS))[::-1]  # ascending walk order
    for i in range(STEPS):
        rec = sched.prev_step(eps_seq[i], timesteps[i], traj[i + 1], STEPS)
        np.testing.assert_allclose(
            np.asarray(rec), np.asarray(traj[i]), rtol=1e-5, atol=1e-6
        )
    # default call signature unchanged
    traj_only = jax.jit(
        lambda p, x: ddim_inversion(fn, p, sched, x, cond, num_inference_steps=STEPS)
    )(params, x0)
    # two separately-compiled programs (with/without the ε output) need not
    # be bitwise identical — tight tolerance, not bit equality
    np.testing.assert_allclose(
        np.asarray(traj_only), np.asarray(traj), rtol=1e-6, atol=1e-7
    )
