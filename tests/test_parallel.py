"""Distributed-layer tests on the virtual 8-device CPU mesh (SURVEY §4's
standard fake-pod recipe, set up in conftest.py).

Covers: mesh construction, ring attention exactness vs dense attention,
sequence-parallel UNet forward equivalence, and the sharded train step.

Every test but ``test_megatron_out_dot_unit`` is ``slow`` (multi-device mesh
compiles); that one is seconds and stays in tier-1 so the partial-manual
``jax.shard_map(axis_names={"tensor"})`` seam is guarded on every run.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import NamedSharding, PartitionSpec as P

from videop2p_tpu.parallel import (
    AXIS_FRAMES,
    latent_sharding,
    make_mesh,
    param_shardings,
    replicated,
    ring_attention_sharded,
    text_sharding,
)


@pytest.fixture(scope="module")
def mesh8():
    return make_mesh((1, 8, 1))


@pytest.mark.slow
def test_make_mesh_validates():
    with pytest.raises(ValueError, match="devices"):
        make_mesh((3, 1, 1))
    m = make_mesh((2, 4, 1))
    assert m.shape == {"data": 2, "frames": 4, "tensor": 1}


@pytest.mark.slow
def test_ring_attention_matches_dense(mesh8):
    B, H, S, D = 2, 3, 16, 8  # S=16 over 8 shards → 2 per shard
    kq, kk, kv = jax.random.split(jax.random.key(0), 3)
    q = jax.random.normal(kq, (B, H, S, D))
    k = jax.random.normal(kk, (B, H, S, D))
    v = jax.random.normal(kv, (B, H, S, D))

    out_ring = ring_attention_sharded(q, k, v, mesh8, axis_name=AXIS_FRAMES)
    scale = D**-0.5
    s = jnp.einsum("bhqd,bhkd->bhqk", q, k) * scale
    out_dense = jnp.einsum("bhqk,bhkd->bhqd", jax.nn.softmax(s, axis=-1), v)
    np.testing.assert_allclose(np.asarray(out_ring), np.asarray(out_dense), atol=1e-5)


@pytest.mark.slow
def test_ring_attention_bf16(mesh8):
    B, H, S, D = 1, 2, 8, 4
    q = jax.random.normal(jax.random.key(0), (B, H, S, D), jnp.bfloat16)
    k = jax.random.normal(jax.random.key(1), (B, H, S, D), jnp.bfloat16)
    v = jax.random.normal(jax.random.key(2), (B, H, S, D), jnp.bfloat16)
    out = ring_attention_sharded(q, k, v, mesh8, axis_name=AXIS_FRAMES)
    assert out.dtype == jnp.bfloat16
    assert np.isfinite(np.asarray(out, dtype=np.float32)).all()


@pytest.mark.slow
def test_sequence_parallel_unet_forward(mesh8):
    """The full UNet forward under jit with the frame axis sharded across the
    8-device mesh must equal the single-device result — XLA inserts the
    frame-0 KV broadcast and temporal-attention gathers (SURVEY §5.7)."""
    from videop2p_tpu.models import UNet3DConditionModel, UNet3DConfig

    cfg = UNet3DConfig.tiny()
    model = UNet3DConditionModel(config=cfg)
    B, F = 1, 8
    sample = jax.random.normal(jax.random.key(0), (B, F, 8, 8, 4))
    text = jax.random.normal(jax.random.key(1), (B, 7, cfg.cross_attention_dim))
    params = jax.jit(model.init)(jax.random.key(2), sample, jnp.asarray(5), text)

    out_single = jax.jit(model.apply)(params, sample, jnp.asarray(5), text)

    sharded_sample = jax.device_put(sample, latent_sharding(mesh8))
    sharded_text = jax.device_put(text, text_sharding(mesh8))
    sharded_params = jax.device_put(params, replicated(mesh8))
    out_sharded = jax.jit(
        model.apply, out_shardings=latent_sharding(mesh8)
    )(sharded_params, sharded_sample, jnp.asarray(5), sharded_text)
    np.testing.assert_allclose(
        np.asarray(out_single), np.asarray(out_sharded), atol=2e-4
    )


@pytest.mark.slow
def test_sharded_train_step(mesh8):
    """train_step jitted over the mesh with frame-sharded latents: loss must
    match the unsharded step bit-for-better-than-bf16 tolerance (the psum the
    reference does via accelerator.gather, run_tuning.py:322)."""
    from videop2p_tpu.core import DDPMScheduler
    from videop2p_tpu.models import UNet3DConditionModel, UNet3DConfig
    from videop2p_tpu.pipelines import make_unet_fn
    from videop2p_tpu.train import TrainState, TuneConfig, make_optimizer, train_step

    cfg = UNet3DConfig.tiny()
    model = UNet3DConditionModel(config=cfg)
    latents = 0.3 * jax.random.normal(jax.random.key(0), (1, 8, 8, 8, 4))
    text = jax.random.normal(jax.random.key(1), (1, 7, cfg.cross_attention_dim))
    variables = jax.jit(model.init)(jax.random.key(2), latents, jnp.asarray(0), text)
    fn = make_unet_fn(model)
    params = dict(variables)["params"]
    tx = make_optimizer(TuneConfig())
    state = TrainState.create(params, tx)
    sched = DDPMScheduler.create_sd()

    step = jax.jit(lambda s, lat, txt, k: train_step(fn, tx, s, sched, lat, txt, k))
    _, loss_single = step(state, latents, text, jax.random.key(3))

    s_state = jax.device_put(state, replicated(mesh8))
    s_lat = jax.device_put(latents, latent_sharding(mesh8))
    s_txt = jax.device_put(text, text_sharding(mesh8))
    new_state, loss_sharded = step(s_state, s_lat, s_txt, jax.random.key(3))
    np.testing.assert_allclose(float(loss_single), float(loss_sharded), rtol=1e-4)
    assert int(new_state.step) == 1


@pytest.mark.slow
def test_param_shardings_tensor_parallel(mesh8):
    """Tensor-parallel rules: qkv kernels column-shard, to_out row-shards,
    everything else replicates."""
    mesh = make_mesh((1, 4, 2))
    from videop2p_tpu.models import UNet3DConditionModel, UNet3DConfig

    cfg = UNet3DConfig.tiny()
    model = UNet3DConditionModel(config=cfg)
    sample = jax.random.normal(jax.random.key(0), (1, 2, 8, 8, 4))
    text = jax.random.normal(jax.random.key(1), (1, 7, cfg.cross_attention_dim))
    variables = jax.jit(model.init)(jax.random.key(2), sample, jnp.asarray(0), text)
    params = dict(variables)["params"]
    shardings = param_shardings(mesh, params, tensor_parallel=True)
    flat = jax.tree_util.tree_flatten_with_path(shardings)[0]
    specs = {jax.tree_util.keystr(p): s.spec for p, s in flat}
    qs = [s for k, s in specs.items() if "to_q" in k and "kernel" in k]
    outs = [s for k, s in specs.items() if "attn" in k and "to_out" in k and "kernel" in k]
    convs = [s for k, s in specs.items() if "conv" in k]
    assert all(s == P(None, "tensor") for s in qs) and qs
    assert all(s == P("tensor", None) for s in outs) and outs
    assert all(s == P() for s in convs) and convs
    # all kernels placeable
    jax.device_put(params, shardings)


@pytest.mark.slow
def test_ring_temporal_unet_forward(mesh8):
    """UNet forward with ring attention at the temporal sites over the
    frame-sharded mesh must equal the dense single-device forward (the
    temporal_attention_fn seam, models/attention.py)."""
    from videop2p_tpu.models import UNet3DConditionModel, UNet3DConfig
    from videop2p_tpu.parallel import make_ring_temporal_fn

    cfg = UNet3DConfig.tiny()
    model = UNet3DConditionModel(config=cfg)
    B, F = 1, 8
    sample = jax.random.normal(jax.random.key(0), (B, F, 8, 8, 4))
    text = jax.random.normal(jax.random.key(1), (B, 7, cfg.cross_attention_dim))
    params = jax.jit(model.init)(jax.random.key(2), sample, jnp.asarray(5), text)
    out_dense = jax.jit(model.apply)(params, sample, jnp.asarray(5), text)

    model_ring = model.clone(temporal_attention_fn=make_ring_temporal_fn(mesh8))
    s_sample = jax.device_put(sample, latent_sharding(mesh8))
    s_text = jax.device_put(text, text_sharding(mesh8))
    s_params = jax.device_put(params, replicated(mesh8))
    out_ring = jax.jit(
        model_ring.apply, out_shardings=latent_sharding(mesh8)
    )(s_params, s_sample, jnp.asarray(5), s_text)
    np.testing.assert_allclose(
        np.asarray(out_dense), np.asarray(out_ring), atol=2e-4
    )


@pytest.mark.slow
def test_sharded_frame_attention_matches_dense(mesh8):
    """The shard_map frame-attention wrapper (queries split over frames,
    frame-0 K/V replicated) must equal the single-device kernel — both at the
    raw-kernel level and through the UNet's frame_attention_fn seam. This is
    the path that carries the fused Pallas kernel onto the sharded mesh."""
    from videop2p_tpu.models import UNet3DConditionModel, UNet3DConfig
    from videop2p_tpu.ops import dense_frame_attention
    from videop2p_tpu.parallel import make_sharded_frame_attention_fn

    # raw kernel: realistic token count so the dispatch path is exercised
    B, F, H, N, D = 1, 8, 2, 1024, 8
    kq, kk, kv = jax.random.split(jax.random.key(7), 3)
    q = jax.random.normal(kq, (B, F, H, N, D))
    k = jax.random.normal(kk, (B, H, N, D))
    v = jax.random.normal(kv, (B, H, N, D))
    fn = make_sharded_frame_attention_fn(mesh8)
    out_s = jax.jit(fn)(
        jax.device_put(q, NamedSharding(mesh8, P(None, "frames"))),
        jax.device_put(k, replicated(mesh8)),
        jax.device_put(v, replicated(mesh8)),
    )
    out_d = jax.jit(dense_frame_attention)(q, k, v)
    np.testing.assert_allclose(np.asarray(out_s), np.asarray(out_d), atol=2e-5)

    # through the UNet seam: sharded forward == unsharded forward
    cfg = UNet3DConfig.tiny(frame_attention="dense")
    model = UNet3DConditionModel(config=cfg)
    sample = jax.random.normal(jax.random.key(0), (1, 8, 8, 8, 4))
    text = jax.random.normal(jax.random.key(1), (1, 7, cfg.cross_attention_dim))
    params = jax.jit(model.init)(jax.random.key(2), sample, jnp.asarray(5), text)
    out_dense = jax.jit(model.apply)(params, sample, jnp.asarray(5), text)
    model_sf = model.clone(frame_attention_fn=make_sharded_frame_attention_fn(mesh8))
    out_sharded = jax.jit(
        model_sf.apply, out_shardings=latent_sharding(mesh8)
    )(
        jax.device_put(params, replicated(mesh8)),
        jax.device_put(sample, latent_sharding(mesh8)),
        jnp.asarray(5),
        jax.device_put(text, text_sharding(mesh8)),
    )
    np.testing.assert_allclose(
        np.asarray(out_dense), np.asarray(out_sharded), atol=2e-4
    )


@pytest.mark.slow
def test_sharded_controlled_edit_matches_unsharded(mesh8):
    """The full attention-controlled edit (refine + equalizer + LocalBlend)
    jitted over the frame-sharded mesh must match the single-device edit —
    the Stage-2 --mesh path (cli/run_videop2p.py)."""
    from videop2p_tpu.control import make_controller
    from videop2p_tpu.core import DDIMScheduler
    from videop2p_tpu.models import UNet3DConditionModel, UNet3DConfig
    from videop2p_tpu.pipelines import edit_sample, make_unet_fn
    from videop2p_tpu.utils.tokenizers import WordTokenizer

    mesh = make_mesh((1, 4, 2))
    cfg = UNet3DConfig.tiny()
    model = UNet3DConditionModel(config=cfg)
    F, STEPS = 4, 3
    x_t = jax.random.normal(jax.random.key(0), (1, F, 8, 8, 4))
    cond = jax.random.normal(jax.random.key(1), (2, 77, cfg.cross_attention_dim))
    uncond = jnp.zeros((77, cfg.cross_attention_dim))
    params = jax.jit(model.init)(jax.random.key(2), x_t, jnp.asarray(5), cond[:1])
    fn = make_unet_fn(model)
    sched = DDIMScheduler.create_sd()
    ctx = make_controller(
        ["a rabbit is jumping", "a origami rabbit is jumping"],
        WordTokenizer(), num_steps=STEPS,
        is_replace_controller=False,
        cross_replace_steps=0.8, self_replace_steps=0.6,
        blend_words=(["rabbit"], ["rabbit"]),
        equalizer_params={"words": ["origami"], "values": [2.0]},
    )

    def run(p, xt, c, u):
        return edit_sample(
            fn, p, sched, xt, c, u, num_inference_steps=STEPS, ctx=ctx,
            source_uses_cfg=False, blend_res=(4, 4),
        )

    out_single = jax.jit(run)(params, x_t, cond, uncond)

    s_params = jax.device_put(
        params, param_shardings(mesh, params, tensor_parallel=True)
    )
    s_xt = jax.device_put(x_t, latent_sharding(mesh))
    s_cond = jax.device_put(cond, replicated(mesh))
    s_uncond = jax.device_put(uncond, replicated(mesh))
    out_sharded = jax.jit(run)(s_params, s_xt, s_cond, s_uncond)
    np.testing.assert_allclose(
        np.asarray(out_single), np.asarray(out_sharded), atol=2e-4
    )


@pytest.mark.slow
def test_sharded_cached_source_edit_matches_unsharded(mesh8):
    """The cached-source fast mode (pipelines/cached.py) under a (1,4,2)
    frames×tensor mesh: GSPMD shards the capture trees (cross maps over the
    frame axis, temporal maps over spatial positions) with no shard_map
    changes; sharded must match unsharded, and the source replay must stay
    bit-exact even sharded."""
    from videop2p_tpu.control import make_controller
    from videop2p_tpu.core import DDIMScheduler
    from videop2p_tpu.models import UNet3DConditionModel, UNet3DConfig
    from videop2p_tpu.pipelines import (
        ddim_inversion_captured,
        edit_sample,
        make_unet_fn,
    )
    from videop2p_tpu.pipelines.cached import capture_windows
    from videop2p_tpu.utils.tokenizers import WordTokenizer

    mesh = make_mesh((1, 4, 2))
    cfg = UNet3DConfig.tiny()
    model = UNet3DConditionModel(config=cfg)
    F, STEPS = 4, 3
    x0 = jax.random.normal(jax.random.key(0), (1, F, 8, 8, 4))
    cond = jax.random.normal(jax.random.key(1), (2, 77, cfg.cross_attention_dim))
    uncond = jnp.zeros((77, cfg.cross_attention_dim))
    params = jax.jit(model.init)(jax.random.key(2), x0, jnp.asarray(5), cond[:1])
    fn = make_unet_fn(model)
    sched = DDIMScheduler.create_sd()
    ctx = make_controller(
        ["a rabbit is jumping", "a origami rabbit is jumping"],
        WordTokenizer(), num_steps=STEPS,
        is_replace_controller=False,
        cross_replace_steps=0.8, self_replace_steps=0.6,
        blend_words=(["rabbit"], ["rabbit"]),
        equalizer_params={"words": ["origami"], "values": [2.0]},
    )
    c, sw = capture_windows(ctx, STEPS)

    def invcap(p, x):
        return ddim_inversion_captured(
            fn, p, sched, x, cond[:1], num_inference_steps=STEPS,
            cross_len=c, self_window=sw, capture_blend=True, blend_res=(4, 4),
        )

    def edit(p, xt, cch):
        return edit_sample(
            fn, p, sched, xt, cond, uncond, num_inference_steps=STEPS,
            ctx=ctx, source_uses_cfg=False, blend_res=(4, 4), cached_source=cch,
        )

    traj1, cc1 = jax.jit(invcap)(params, x0)
    out1 = jax.jit(edit)(params, traj1[-1], cc1)

    s_params = jax.device_put(
        params, param_shardings(mesh, params, tensor_parallel=True)
    )
    s_x0 = jax.device_put(x0, latent_sharding(mesh))
    traj2, cc2 = jax.jit(invcap)(s_params, s_x0)
    out2 = jax.jit(edit)(s_params, traj2[-1], cc2)

    # capture maps are STORED in bf16 (models/attention.py): the sharded and
    # unsharded programs' fp drift rounds to different bf16 ULPs in the maps,
    # which the 3-step edit amplifies to ~1e-3 — tolerance covers that, not
    # any semantic divergence
    np.testing.assert_allclose(np.asarray(out1), np.asarray(out2), atol=2e-3)
    # the replay exactness survives sharding
    np.testing.assert_array_equal(np.asarray(out2[0]), np.asarray(s_x0[0]))

    # the long-video budget mode's float8 temporal storage must partition
    # identically (GSPMD treats the narrow dtype like any other): sharded
    # f8 matches unsharded f8, and replay exactness is dtype-independent
    def invcap8(p, x):
        return ddim_inversion_captured(
            fn, p, sched, x, cond[:1], num_inference_steps=STEPS,
            cross_len=c, self_window=sw, capture_blend=True, blend_res=(4, 4),
            temporal_maps_dtype=jnp.float8_e4m3fn,
        )

    traj18, cc18 = jax.jit(invcap8)(params, x0)
    out18 = jax.jit(edit)(params, traj18[-1], cc18)
    traj28, cc28 = jax.jit(invcap8)(s_params, s_x0)
    out28 = jax.jit(edit)(s_params, traj28[-1], cc28)
    np.testing.assert_allclose(np.asarray(out18), np.asarray(out28), atol=2e-3)
    np.testing.assert_array_equal(np.asarray(out28[0]), np.asarray(s_x0[0]))


@pytest.mark.slow
def test_sharded_group_norm_matches_reference(mesh8):
    """The shard_map GroupNorm wrapper (VERDICT r5 next-round #5): the
    fused one-pass kernel runs per-shard on sample-split slabs and must
    match the two-pass reference — directly and through the TpuGroupNorm
    ``group_norm_fn`` seam; uncovered sites return None (→ XLA fallback)."""
    from videop2p_tpu.models.layers import TpuGroupNorm
    from videop2p_tpu.ops.groupnorm import group_norm_reference
    from videop2p_tpu.parallel import make_sharded_group_norm_fn

    fn = make_sharded_group_norm_fn(mesh8, impl="interpret")
    N, rows, C = 8, 256, 32  # 8 samples over 8 shards, VMEM-sized slab
    x2 = jax.random.normal(jax.random.key(0), (N, rows, C))
    scale = jax.random.normal(jax.random.key(1), (C,))
    bias = jax.random.normal(jax.random.key(2), (C,))
    y = fn(x2, scale, bias, num_groups=4, eps=1e-5, act="silu")
    assert y is not None
    ref = group_norm_reference(x2, scale, bias, num_groups=4, eps=1e-5,
                               act="silu")
    np.testing.assert_allclose(np.asarray(y), np.asarray(ref), atol=2e-5)

    # uncovered sites: sample axis not divisible by the shard count (the
    # frame-pooled resnet slabs), or a slab the VMEM gate refuses — None,
    # and the caller falls back to the two-pass math
    assert fn(x2[:3], scale, bias, num_groups=4, eps=1e-5, act="none") is None
    odd = jax.random.normal(jax.random.key(3), (8, 100, 32))
    assert fn(odd, scale, bias, num_groups=4, eps=1e-5, act="none") is None
    # an impl that disables the kernel covers nothing
    off = make_sharded_group_norm_fn(mesh8, impl="xla")
    assert off(x2, scale, bias, num_groups=4, eps=1e-5, act="none") is None

    # through the module seam, jitted with the sample axis sharded:
    # sharded == unsharded with the kernel active in interpret mode
    gn = TpuGroupNorm(num_groups=4, epsilon=1e-5, act="silu",
                      group_norm_fn=fn)
    x = jax.random.normal(jax.random.key(4), (N, 16, 16, C))
    variables = gn.init(jax.random.key(5), x)
    ref_mod = TpuGroupNorm(num_groups=4, epsilon=1e-5, act="silu", impl="xla")
    y_ref = jax.jit(ref_mod.apply)(variables, x)
    s_x = jax.device_put(
        x, NamedSharding(mesh8, P(("data", "frames"), None, None, None))
    )
    y_sharded = jax.jit(gn.apply)(jax.device_put(variables, replicated(mesh8)),
                                  s_x)
    np.testing.assert_allclose(
        np.asarray(y_ref), np.asarray(y_sharded), atol=2e-5
    )


@pytest.mark.slow
def test_setup_mesh_wires_sharded_group_norm():
    """setup_mesh no longer forces group_norm='xla' on sharded meshes — it
    wires the shard_map GroupNorm seam instead, leaving the config knob
    untouched (the kernel decision now lives in the seam)."""
    import jax.numpy as jnp

    from videop2p_tpu.cli.common import build_models, setup_mesh

    bundle = build_models(None, tiny=True, dtype=jnp.float32)
    assert bundle.unet.config.group_norm == "auto"
    assert bundle.unet.group_norm_fn is None
    mesh = setup_mesh(bundle, "1,4,2", 8)
    assert mesh.shape == {"data": 1, "frames": 4, "tensor": 2}
    assert bundle.unet.group_norm_fn is not None
    assert bundle.unet.config.group_norm == "auto"  # knob not clobbered


@pytest.mark.slow
def test_hybrid_mesh_single_slice_and_distributed_noop():
    """make_hybrid_mesh on one slice equals the plain reshape;
    initialize_distributed is a no-op without multi-host config."""
    from videop2p_tpu.parallel import initialize_distributed, make_hybrid_mesh

    assert initialize_distributed() == 0
    m = make_hybrid_mesh(1, 4, 2)
    assert m.shape == {"data": 1, "frames": 4, "tensor": 2}
    with pytest.raises(ValueError, match="needs"):
        make_hybrid_mesh(2, 4, 2)


def _dense_reference(q, k, v):
    scale = q.shape[-1] ** -0.5
    s = jnp.einsum("bhqd,bhkd->bhqk", q.astype(jnp.float32),
                   k.astype(jnp.float32)) * scale
    return jnp.einsum("bhqk,bhkd->bhqd", jax.nn.softmax(s, axis=-1),
                      v.astype(jnp.float32))


@pytest.mark.slow
def test_ring_variants_match_dense(mesh8):
    """ISSUE 10 satellite: every rotation schedule — the serial baseline,
    the double-buffered n−1 default, and the bidirectional split-halves
    variant — must match dense attention at the existing ring tolerance."""
    from videop2p_tpu.parallel import RING_VARIANTS

    B, H, S, D = 2, 3, 16, 8
    kq, kk, kv = jax.random.split(jax.random.key(0), 3)
    q = jax.random.normal(kq, (B, H, S, D))
    k = jax.random.normal(kk, (B, H, S, D))
    v = jax.random.normal(kv, (B, H, S, D))
    dense = _dense_reference(q, k, v)
    for variant in RING_VARIANTS:
        out = ring_attention_sharded(q, k, v, mesh8, axis_name=AXIS_FRAMES,
                                     variant=variant)
        np.testing.assert_allclose(np.asarray(out), np.asarray(dense),
                                   atol=1e-5, err_msg=variant)
    with pytest.raises(ValueError, match="variant"):
        ring_attention_sharded(q, k, v, mesh8, variant="bogus")


@pytest.mark.slow
def test_ring_variants_odd_shards_and_odd_halves():
    """Odd shard counts (a 5-device sub-mesh) and an odd per-shard
    sequence length (unequal bidirectional halves) stay exact."""
    from videop2p_tpu.parallel import RING_VARIANTS

    mesh5 = make_mesh((1, 5, 1), devices=jax.devices()[:5])
    B, H, S, D = 1, 2, 15, 4  # 3 frames per shard: odd halves for bidir
    kq, kk, kv = jax.random.split(jax.random.key(7), 3)
    q = jax.random.normal(kq, (B, H, S, D))
    k = jax.random.normal(kk, (B, H, S, D))
    v = jax.random.normal(kv, (B, H, S, D))
    dense = _dense_reference(q, k, v)
    for variant in RING_VARIANTS:
        out = ring_attention_sharded(q, k, v, mesh5, variant=variant)
        np.testing.assert_allclose(np.asarray(out), np.asarray(dense),
                                   atol=1e-5, err_msg=variant)


@pytest.mark.slow
def test_ring_variants_bf16(mesh8):
    """bf16 inputs: fp32 accumulators inside, bf16 out, finite — and a
    1-frame-per-shard bidir degenerates to overlap instead of failing."""
    from videop2p_tpu.parallel import RING_VARIANTS

    B, H, S, D = 1, 2, 8, 4  # 1 frame per shard on the 8-wide mesh
    for variant in RING_VARIANTS:
        q = jax.random.normal(jax.random.key(0), (B, H, S, D), jnp.bfloat16)
        k = jax.random.normal(jax.random.key(1), (B, H, S, D), jnp.bfloat16)
        v = jax.random.normal(jax.random.key(2), (B, H, S, D), jnp.bfloat16)
        out = ring_attention_sharded(q, k, v, mesh8, variant=variant)
        assert out.dtype == jnp.bfloat16, variant
        assert np.isfinite(np.asarray(out, dtype=np.float32)).all(), variant


def test_megatron_out_dot_unit():
    """make_megatron_out_dot: the explicit psum_scatter row-parallel matmul
    equals the plain dot, and non-matching patterns fall back to it."""
    from videop2p_tpu.parallel import make_megatron_out_dot

    mesh = make_mesh((1, 1, 2), devices=jax.devices()[:2])
    dot = make_megatron_out_dot(mesh)
    dn = (((2,), (0,)), ((), ()))
    lhs = jax.random.normal(jax.random.key(0), (2, 8, 16))
    rhs = jax.random.normal(jax.random.key(1), (16, 6))
    # the scatter path under an outer jit, and called eagerly
    np.testing.assert_allclose(
        np.asarray(jax.jit(lambda l, r: dot(l, r, dn))(lhs, rhs)),
        np.asarray(lhs @ rhs), atol=1e-5,
    )
    np.testing.assert_allclose(
        np.asarray(dot(lhs, rhs, dn)), np.asarray(lhs @ rhs), atol=1e-5
    )
    # fallback: token axis not divisible by tp → plain dot, still exact
    lhs_odd = jax.random.normal(jax.random.key(2), (2, 7, 16))
    np.testing.assert_allclose(
        np.asarray(dot(lhs_odd, rhs, dn)), np.asarray(lhs_odd @ rhs),
        atol=1e-5,
    )
    # batched dims → fallback (no shard_map pattern for them)
    dn_batched = (((2,), (1,)), ((0,), (0,)))
    lhs_b = jax.random.normal(jax.random.key(3), (2, 8, 16))
    rhs_b = jax.random.normal(jax.random.key(4), (2, 16, 6))
    np.testing.assert_allclose(
        np.asarray(dot(lhs_b, rhs_b, dn_batched)),
        np.asarray(jax.lax.dot_general(lhs_b, rhs_b, dn_batched)), atol=1e-5,
    )


@pytest.mark.slow
def test_megatron_unet_forward_matches_gspmd(mesh8):
    """The tensor-parallel UNet forward with the explicit psum_scatter
    output seam must match both the declarative GSPMD forward and the
    unsharded single-device forward."""
    from videop2p_tpu.models import UNet3DConditionModel, UNet3DConfig
    from videop2p_tpu.parallel import make_megatron_out_dot

    mesh = make_mesh((1, 1, 2), devices=jax.devices()[:2])
    cfg = UNet3DConfig.tiny()
    model = UNet3DConditionModel(config=cfg)
    sample = jax.random.normal(jax.random.key(0), (1, 2, 8, 8, 4))
    text = jax.random.normal(jax.random.key(1), (1, 7, cfg.cross_attention_dim))
    params = jax.jit(model.init)(jax.random.key(2), sample, jnp.asarray(5), text)
    out_ref = jax.jit(model.apply)(params, sample, jnp.asarray(5), text)

    s_params = jax.device_put(
        params, param_shardings(mesh, params, tensor_parallel=True)
    )
    s_sample = jax.device_put(sample, latent_sharding(mesh))
    s_text = jax.device_put(text, text_sharding(mesh))
    model_m = model.clone(row_parallel_dot=make_megatron_out_dot(mesh))
    out_m = jax.jit(model_m.apply, out_shardings=latent_sharding(mesh))(
        s_params, s_sample, jnp.asarray(5), s_text
    )
    np.testing.assert_allclose(np.asarray(out_ref), np.asarray(out_m),
                               atol=2e-4)


@pytest.mark.slow
def test_setup_mesh_ring_and_tp_knobs():
    """setup_mesh validates and wires the new schedule knobs: a bad ring
    variant / tp_collectives raises, and psum_scatter on a tp>1 mesh
    threads the row_parallel_dot seam into the UNet."""
    from videop2p_tpu.cli.common import build_models, setup_mesh

    bundle = build_models(None, tiny=True, dtype=jnp.float32)
    with pytest.raises(ValueError, match="ring_variant"):
        setup_mesh(bundle, "1,4,2", 8, ring_variant="bogus")
    with pytest.raises(ValueError, match="tp_collectives"):
        setup_mesh(bundle, "1,4,2", 8, tp_collectives="bogus")
    assert bundle.unet.row_parallel_dot is None
    mesh = setup_mesh(bundle, "1,4,2", 8, ring_variant="bidir",
                      tp_collectives="psum_scatter")
    assert mesh.shape == {"data": 1, "frames": 4, "tensor": 2}
    assert bundle.unet.row_parallel_dot is not None
    assert bundle.unet.temporal_attention_fn is not None
