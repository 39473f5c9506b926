"""Fused one-pass GroupNorm: numerics vs flax nn.GroupNorm (the module the
UNet used through round 4) and the torch-semantics reference math.

The kernel runs in interpret mode on CPU (tests/conftest.py pins cpu);
the real Mosaic compile is covered by tests/test_tpu_compile.py and, on the
chip, by the benchmark's sd15 cell (57 kernels in its ``train_steps``).
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from flax import linen as nn

from videop2p_tpu.ops.groupnorm import (
    fits_fused_group_norm,
    fused_group_norm,
    group_norm_reference,
)


def _flax_gn(x2, scale, bias, groups, eps):
    """nn.GroupNorm on (N, rows, C) with bound params."""
    mod = nn.GroupNorm(num_groups=groups, epsilon=eps, dtype=x2.dtype)
    return mod.apply({"params": {"scale": scale, "bias": bias}}, x2)


@pytest.mark.parametrize(
    "n,rows,c,groups",
    [
        (2, 256, 320, 32),   # 16²-site per-frame shape class
        (1, 512, 640, 32),
        (3, 256, 1280, 32),
        (2, 256, 96, 32),    # tiny-config widths (3 ch/group)
    ],
)
def test_fused_matches_flax_groupnorm(n, rows, c, groups):
    k1, k2, k3 = jax.random.split(jax.random.key(0), 3)
    x = jax.random.normal(k1, (n, rows, c), jnp.float32) * 2.0 + 0.5
    scale = jax.random.normal(k2, (c,)) * 0.2 + 1.0
    bias = jax.random.normal(k3, (c,)) * 0.1
    want = _flax_gn(x, scale, bias, groups, 1e-5)
    got = fused_group_norm(
        x, scale, bias, num_groups=groups, eps=1e-5, interpret=True
    )
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=2e-5)


def test_fused_bf16_matches_reference_math():
    k1, k2, k3 = jax.random.split(jax.random.key(1), 3)
    x = (jax.random.normal(k1, (2, 256, 320)) * 3).astype(jnp.bfloat16)
    scale = jax.random.normal(k2, (320,)).astype(jnp.float32)
    bias = jax.random.normal(k3, (320,)).astype(jnp.float32)
    want = group_norm_reference(x, scale, bias, num_groups=32)
    got = fused_group_norm(x, scale, bias, num_groups=32, interpret=True)
    np.testing.assert_allclose(
        np.asarray(got, np.float32), np.asarray(want, np.float32), atol=0.05
    )


def test_fused_silu_fusion():
    k = jax.random.key(2)
    x = jax.random.normal(k, (1, 256, 128), jnp.float32)
    scale = jnp.ones((128,))
    bias = jnp.zeros((128,))
    plain = fused_group_norm(x, scale, bias, num_groups=32, interpret=True)
    want = plain * jax.nn.sigmoid(plain)
    got = fused_group_norm(
        x, scale, bias, num_groups=32, act="silu", interpret=True
    )
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=2e-6)


def test_reference_math_matches_flax():
    """The XLA fallback itself must be flax/torch GroupNorm (it replaces
    nn.GroupNorm at the un-fusable big-slab sites)."""
    k1, k2, k3 = jax.random.split(jax.random.key(3), 3)
    x = jax.random.normal(k1, (2, 512, 640), jnp.float32)
    scale = jax.random.normal(k2, (640,)) + 1.0
    bias = jax.random.normal(k3, (640,))
    want = _flax_gn(x, scale, bias, 32, 1e-6)
    got = group_norm_reference(x, scale, bias, num_groups=32, eps=1e-6)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=2e-5)


def test_gate_logic():
    assert fits_fused_group_norm(4096, 320)          # 64² per-frame: 2.6 MB
    assert fits_fused_group_norm(1024, 640)          # 32² per-frame: 1.3 MB
    assert fits_fused_group_norm(512, 1280)          # 8² frame-pooled
    assert not fits_fused_group_norm(8 * 4096, 320)  # 64² frame-pooled: 21 MB
    assert not fits_fused_group_norm(8 * 1024, 640)  # 32² frame-pooled: 10 MB
    assert not fits_fused_group_norm(100, 320)       # row-tile misalignment


def test_unfittable_shape_raises():
    x = jnp.zeros((1, 100, 320))
    with pytest.raises(ValueError, match="rows"):
        fused_group_norm(x, jnp.ones(320), jnp.zeros(320), num_groups=32,
                         interpret=True)


@pytest.mark.slow  # ~17 s: two full UNet compiles (interpret-GN vs XLA-GN)
def test_unet_forward_same_with_fused_gn():
    """The whole UNet must produce the same output through the fused-GN
    path (kernel in interpret mode) as through the XLA two-pass path —
    same parameter tree, same math, different schedule."""
    from videop2p_tpu.models import UNet3DConditionModel, UNet3DConfig

    cfg_x = UNet3DConfig.tiny(sample_size=16, group_norm="xla")
    cfg_f = UNet3DConfig.tiny(sample_size=16, group_norm="interpret")
    m_x = UNet3DConditionModel(config=cfg_x)
    m_f = UNet3DConditionModel(config=cfg_f)
    k = jax.random.key(7)
    x = jax.random.normal(k, (1, 2, 16, 16, 4))
    txt = jax.random.normal(jax.random.fold_in(k, 1), (1, 7, cfg_x.cross_attention_dim))
    params = m_x.init(jax.random.fold_in(k, 2), x, jnp.asarray(3), txt)
    out_x = m_x.apply(params, x, jnp.asarray(3), txt)
    out_f = m_f.apply(params, x, jnp.asarray(3), txt)
    np.testing.assert_allclose(
        np.asarray(out_f), np.asarray(out_x), atol=3e-5
    )
    # the tiny-16 shapes actually exercise the kernel (rows 256/512 pass the
    # row-tile gate) — guard against a silently-all-fallback test
    assert fits_fused_group_norm(256, 8) and fits_fused_group_norm(512, 8)


def test_gn_gradients_flow_through_fused_path():
    """Training differentiates through the UNet; the kernel's custom VJP
    recomputes via the reference math and must match its gradients."""
    k = jax.random.key(9)
    x = jax.random.normal(k, (1, 256, 64), jnp.float32)
    scale = jnp.ones((64,))
    bias = jnp.zeros((64,))

    def loss_fused(x, s, b):
        return jnp.sum(fused_group_norm(
            x, s, b, num_groups=32, act="silu", interpret=True) ** 2)

    def loss_ref(x, s, b):
        return jnp.sum(group_norm_reference(
            x, s, b, num_groups=32, act="silu") ** 2)

    g_f = jax.grad(loss_fused, argnums=(0, 1, 2))(x, scale, bias)
    g_r = jax.grad(loss_ref, argnums=(0, 1, 2))(x, scale, bias)
    for a, b_ in zip(g_f, g_r):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b_), atol=1e-4)


def test_tpu_groupnorm_rejects_unknown_impl():
    """A typo'd impl (e.g. 'pallas') must raise, not silently select the
    XLA fallback and change the performance path (ADVICE r5 item 3)."""
    from videop2p_tpu.models.layers import TpuGroupNorm

    x = jnp.ones((1, 8, 32))
    good = TpuGroupNorm(num_groups=4, impl="xla")
    params = good.init(jax.random.key(0), x)
    for impl in ("auto", "xla", "interpret"):
        TpuGroupNorm(num_groups=4, impl=impl).apply(params, x)
    with pytest.raises(ValueError, match="impl"):
        TpuGroupNorm(num_groups=4, impl="pallas").apply(params, x)
