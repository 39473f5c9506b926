"""UNet3D model tests: shapes, inflation identity, control threading, stores.

Mirrors the test strategy recommended in SURVEY §4 (the reference ships no
tests): shape/equivariance tests for the UNet, the inflation-identity property
(with zero temporal attention the 3-D UNet is a per-frame 2-D UNet), and
controller behavior on live forwards.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from videop2p_tpu.control import make_controller
from videop2p_tpu.models import AttnControl, UNet3DConditionModel, UNet3DConfig
from videop2p_tpu.utils.tokenizers import WordTokenizer


_apply_cache = {}


def apply(model, params, sample, t, text):
    """Jitted apply, cached per model so repeated same-shape calls hit the
    compile cache (eager linen apply dispatches hundreds of tiny kernels)."""
    key = id(model)
    if key not in _apply_cache:
        _apply_cache[key] = jax.jit(model.apply)
    return _apply_cache[key](params, sample, t, text)


@pytest.fixture(scope="module")
def tiny_unet():
    cfg = UNet3DConfig.tiny()
    model = UNet3DConditionModel(config=cfg)
    B, F = 2, 4
    sample = jax.random.normal(jax.random.key(0), (B, F, 8, 8, 4))
    text = jax.random.normal(jax.random.key(1), (B, 7, cfg.cross_attention_dim))
    params = jax.jit(model.init)(jax.random.key(2), sample, jnp.asarray(10), text)
    return model, params, sample, text


def test_forward_shape(tiny_unet):
    model, params, sample, text = tiny_unet
    out = apply(model, params, sample, jnp.asarray(10), text)
    assert out.shape == sample.shape
    assert jnp.isfinite(out).all()


def test_per_sample_timesteps(tiny_unet):
    model, params, sample, text = tiny_unet
    out = apply(model, params, sample, jnp.asarray([10, 20]), text)
    assert out.shape == sample.shape


def test_temporal_attention_zero_init(tiny_unet):
    """The temporal attention output projection must start at zero so
    inflation is the identity (reference attention.py:196-202)."""
    _, params, _, _ = tiny_unet
    flat = jax.tree_util.tree_flatten_with_path(params)[0]
    zero_kernels = [
        jax.tree_util.keystr(path)
        for path, leaf in flat
        if "attn_temp" in jax.tree_util.keystr(path)
        and "to_out" in jax.tree_util.keystr(path)
        and "kernel" in jax.tree_util.keystr(path)
        and not np.any(np.asarray(leaf))
    ]
    assert len(zero_kernels) > 0


def test_inflation_identity(tiny_unet):
    """With identical frames, frame-0-KV spatial attention equals per-frame
    self-attention and zero-init temporal attention contributes nothing — so
    every output frame must equal the single-frame (2-D) result
    (SURVEY §4: 'with zeroed temporal attn, 3-D UNet ≡ per-frame 2-D UNet')."""
    model, params, sample, text = tiny_unet
    one = sample[:, :1]
    rep = jnp.broadcast_to(one, sample.shape)
    out_rep = apply(model, params, rep, jnp.asarray(3), text)
    out_one = apply(model, params, one, jnp.asarray(3), text)
    np.testing.assert_allclose(np.asarray(out_rep[:, 2]), np.asarray(out_one[:, 0]), atol=1e-4)
    np.testing.assert_allclose(
        np.asarray(out_rep[:, 0]), np.asarray(out_rep[:, -1]), atol=1e-4
    )


def test_attention_store_collection(tiny_unet):
    model, params, sample, text = tiny_unet
    out, store = jax.jit(
        lambda p, s, t, e: model.apply(p, s, t, e, mutable=["attn_store"])
    )(params, sample, jnp.asarray(10), text)
    leaves = jax.tree_util.tree_leaves(store)
    assert len(leaves) > 0
    # cross maps: (B·F, Q, L); temporal maps: (B·N, F, F)
    shapes = {leaf.shape for leaf in leaves}
    assert any(s[-1] == text.shape[1] for s in shapes), shapes
    assert any(s[-1] == sample.shape[1] for s in shapes), shapes


def test_control_threading(tiny_unet):
    """A live ControlContext changes the conditional streams' output but not
    the source stream (the conditional-half-only rule, run_videop2p.py:217-218
    — with the base stream's own maps left untouched)."""
    model, params, sample, text = tiny_unet
    tok = WordTokenizer()
    # batch layout: [uncond_src, uncond_edit, cond_src, cond_edit]
    ctx = make_controller(
        ["a cat runs", "a dog runs"],
        tok,
        num_steps=10,
        is_replace_controller=True,
        cross_replace_steps=1.0,
        self_replace_steps=1.0,
    )
    B = 4  # 2 (cfg) * 2 prompts
    F = sample.shape[1]
    smp = jnp.concatenate([sample, sample], axis=0)
    txt = jax.random.normal(jax.random.key(5), (B, 77, 16))
    params2 = jax.jit(model.init)(jax.random.key(2), smp, jnp.asarray(10), txt)
    control = AttnControl(ctx=ctx, step_index=jnp.asarray(0))
    jfwd = jax.jit(lambda p, s, t, e, c: model.apply(p, s, t, e, c))
    out_ctrl = jfwd(params2, smp, jnp.asarray(10), txt, control)
    out_free = jax.jit(lambda p, s, t, e: model.apply(p, s, t, e))(params2, smp, jnp.asarray(10), txt)
    assert out_ctrl.shape == out_free.shape
    # source-conditional stream (index 2) sees unedited attention
    np.testing.assert_allclose(
        np.asarray(out_ctrl[2]), np.asarray(out_free[2]), atol=1e-4
    )
    # edited-conditional stream (index 3) must differ (its attention was
    # replaced by the source stream's)
    assert not np.allclose(np.asarray(out_ctrl[3]), np.asarray(out_free[3]), atol=1e-4)


def test_gradient_checkpointing_matches(tiny_unet):
    model, params, sample, text = tiny_unet
    model_ckpt = UNet3DConditionModel(
        config=UNet3DConfig.tiny(gradient_checkpointing=True)
    )
    out = apply(model, params, sample, jnp.asarray(10), text)
    out_ckpt = jax.jit(model_ckpt.apply)(params, sample, jnp.asarray(10), text)
    np.testing.assert_allclose(np.asarray(out), np.asarray(out_ckpt), atol=1e-5)


def test_gradient_checkpointing_gradient_matches(tiny_unet):
    """The remat'd blocks (``unet_blocks.remat_block``: all but the
    frame-attention kernel's output recomputed) differentiate to the
    gradient of the UNet without remat."""
    model, params, sample, text = tiny_unet
    model_ckpt = UNet3DConditionModel(
        config=UNet3DConfig.tiny(gradient_checkpointing=True)
    )

    def grad(m):
        return jax.jit(jax.grad(lambda p: jnp.mean(
            m.apply(p, sample, jnp.asarray(10), text) ** 2)))(params)

    want, got = grad(model), grad(model_ckpt)
    moved = 0
    for g, w in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        np.testing.assert_allclose(np.asarray(g), np.asarray(w),
                                   rtol=1e-5, atol=1e-7)
        moved += float(jnp.abs(w).max()) > 0
    assert moved > 10


def test_transformer3d_per_frame_norm():
    """The transformer's input GroupNorm must normalize each frame separately
    (the reference folds frames into batch before the norm, attention.py:94-101).
    With per-frame stats, frame 0's output is independent of frame 1's content
    (frame-0 KV + zero-init temporal attention); cross-frame pooling would leak
    frame 1 into frame 0."""
    from videop2p_tpu.models import Transformer3DModel

    model = Transformer3DModel(heads=2, dim_head=4, norm_groups=2)
    x2 = jax.random.normal(jax.random.key(0), (1, 2, 4, 4, 8))
    ctx = jax.random.normal(jax.random.key(1), (1, 5, 8))
    params = jax.jit(model.init)(jax.random.key(2), x2, ctx)
    fwd = jax.jit(lambda p, x, c: model.apply(p, x, c))
    out2 = fwd(params, x2, ctx)
    out1 = fwd(params, x2[:, :1], ctx)
    np.testing.assert_allclose(np.asarray(out2[:, 0]), np.asarray(out1[:, 0]), atol=1e-5)


def test_unknown_block_type_raises(tiny_unet):
    model, params, sample, text = tiny_unet
    bad = UNet3DConditionModel(
        config=UNet3DConfig.tiny(down_block_types=("CrossAttnDownBlock3d", "DownBlock3D"))
    )
    with pytest.raises(ValueError, match="unknown down block type"):
        bad.init(jax.random.key(0), sample, jnp.asarray(1), text)


def test_sdxl_preset_shape_and_depths():
    """SDXL-shaped stress config (BASELINE config 4): 3 levels, per-block
    transformer depths (1, 2, 10), 2048-dim context, 64-wide heads."""
    cfg = UNet3DConfig.sdxl()
    assert cfg.block_out_channels == (320, 640, 1280)
    assert cfg.transformer_depth == (1, 2, 10)
    assert cfg.attention_head_dim == (5, 10, 20)
    assert cfg.cross_attention_dim == 2048
    assert cfg.down_block_types[0] == "DownBlock3D"  # no attention at level 0
    assert cfg.up_block_types[-1] == "UpBlock3D"


def test_sdxl_shaped_forward_and_torch_parity():
    """Width-scaled SDXL topology (same per-block depth/head structure) must
    run, and the converter must map the per-block transformer depths — the
    deep upper blocks have transformer_blocks.0..N keys per site."""
    import torch

    from tests.torch_ref import TorchUNet3D
    from videop2p_tpu.models.convert import unet3d_params_from_torch

    cfg = UNet3DConfig.sdxl(
        sample_size=8,
        block_out_channels=(8, 16, 32),
        attention_head_dim=(1, 2, 4),
        transformer_depth=(1, 2, 3),
        cross_attention_dim=16,
        norm_num_groups=4,
        layers_per_block=1,
    )
    torch.manual_seed(3)
    tmodel = TorchUNet3D(cfg).eval()
    sd = {k: v.detach().numpy() for k, v in tmodel.state_dict().items()}
    # deep block: level-2 down attention carries 3 transformer blocks
    assert any("down_blocks.2.attentions.0.transformer_blocks.2." in k for k in sd)

    model = UNet3DConditionModel(config=cfg)
    B, F, S = 1, 2, 8
    x = np.random.RandomState(0).randn(B, F, S, S, cfg.in_channels).astype(np.float32)
    ctx = np.random.RandomState(1).randn(B, 7, cfg.cross_attention_dim).astype(np.float32)
    t = np.array([11], dtype=np.int32)
    abstract = jax.eval_shape(
        lambda: model.init(jax.random.key(0), jnp.asarray(x), jnp.asarray(t), jnp.asarray(ctx))
    )["params"]
    params, report = unet3d_params_from_torch(sd, abstract)
    assert report["kept_init"] == [] and report["unused"] == []
    # jitted: the eager op-by-op apply of this wider config costs ~35 s of
    # dispatch overhead on the test host, and only jitted programs hit the
    # persistent compilation cache
    out_flax = jax.jit(model.apply)(
        {"params": params}, jnp.asarray(x), jnp.asarray(t), jnp.asarray(ctx)
    )
    with torch.no_grad():
        out_torch = tmodel(
            torch.tensor(np.transpose(x, (0, 4, 1, 2, 3))),
            torch.tensor(t), torch.tensor(ctx),
        )
    np.testing.assert_allclose(
        np.asarray(out_flax),
        np.transpose(out_torch.numpy(), (0, 2, 3, 4, 1)),
        atol=5e-5,
    )
