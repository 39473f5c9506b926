"""Frame-attention kernel tests (CPU: chunked vs dense exactness, dispatch).

The Pallas flash path needs a real TPU; it is exercised by bench.py and the
verify drive. Here we pin the chunked kernel's exactness and the dispatch
rules the UNet relies on.
"""

import jax
import jax.numpy as jnp
import numpy as np

from videop2p_tpu.ops import (
    chunked_frame_attention,
    dense_frame_attention,
    make_frame_attention_fn,
)


def _rand_qkv(key, B=1, F=3, H=2, N=1024, D=8):
    kq, kk, kv = jax.random.split(key, 3)
    q = jax.random.normal(kq, (B, F, H, N, D))
    k = jax.random.normal(kk, (B, H, N, D))
    v = jax.random.normal(kv, (B, H, N, D))
    return q, k, v


def test_chunked_matches_dense():
    q, k, v = _rand_qkv(jax.random.key(0))
    out_c = jax.jit(lambda q, k, v: chunked_frame_attention(q, k, v, q_chunk=256))(q, k, v)
    out_d = jax.jit(dense_frame_attention)(q, k, v)
    np.testing.assert_allclose(np.asarray(out_c), np.asarray(out_d), atol=1e-5)


def test_chunked_grad_matches_dense():
    q, k, v = _rand_qkv(jax.random.key(1), N=512, D=4)

    def loss(fn, q):
        return jnp.sum(fn(q, k, v) ** 2)

    g_c = jax.jit(jax.grad(lambda q: loss(
        lambda q, k, v: chunked_frame_attention(q, k, v, q_chunk=128), q)))(q)
    g_d = jax.jit(jax.grad(lambda q: loss(dense_frame_attention, q)))(q)
    np.testing.assert_allclose(np.asarray(g_c), np.asarray(g_d), atol=1e-4)


def test_chunked_falls_back_on_indivisible():
    q, k, v = _rand_qkv(jax.random.key(2), N=96)
    out = chunked_frame_attention(q, k, v, q_chunk=512)
    np.testing.assert_allclose(
        np.asarray(out), np.asarray(dense_frame_attention(q, k, v)), atol=1e-5
    )


def test_fused_matches_dense_interpret():
    """The custom Pallas kernel (frame-0-KV resident in VMEM, full-row
    softmax) must equal dense — run in interpret mode so CPU tests cover the
    kernel math; the real-TPU path is exercised by bench.py."""
    from videop2p_tpu.ops import fused_frame_attention

    q, k, v = _rand_qkv(jax.random.key(5), F=2, N=256, D=8)
    out = jax.jit(
        lambda q, k, v: fused_frame_attention(q, k, v, 128, True)
    )(q, k, v)
    np.testing.assert_allclose(
        np.asarray(out), np.asarray(dense_frame_attention(q, k, v)), atol=1e-5
    )


def test_fused_grad_falls_back_to_chunked():
    """Differentiating through the fused kernel must agree with dense — the
    custom VJP recomputes via the chunked exact backward."""
    from videop2p_tpu.ops import fused_frame_attention

    q, k, v = _rand_qkv(jax.random.key(6), F=2, N=256, D=4)

    g_f = jax.jit(jax.grad(lambda q: jnp.sum(
        fused_frame_attention(q, k, v, 128, True) ** 2)))(q)
    g_d = jax.jit(jax.grad(lambda q: jnp.sum(
        dense_frame_attention(q, k, v) ** 2)))(q)
    np.testing.assert_allclose(np.asarray(g_f), np.asarray(g_d), atol=1e-4)


def test_auto_dispatch_off_tpu_is_dense():
    # "auto" resolves per-backend: dense (None) on CPU, fused on TPU
    assert make_frame_attention_fn("auto") is None
    # "fused" asked for by name off-TPU is an error at the large sites, not
    # a quiet drop to chunked; the small-site dense path needs no kernel
    import pytest

    fn = make_frame_attention_fn("fused", min_large_tokens=1024)
    q, k, v = _rand_qkv(jax.random.key(7), N=2048, D=4)
    with pytest.raises(RuntimeError, match="Pallas TPU kernel"):
        jax.jit(fn)(q, k, v)
    q, k, v = _rand_qkv(jax.random.key(7), N=64, D=4)
    np.testing.assert_allclose(
        np.asarray(fn(q, k, v)),
        np.asarray(dense_frame_attention(q, k, v)), atol=1e-5,
    )


def test_dispatch_rules():
    assert make_frame_attention_fn("dense") is None
    fn = make_frame_attention_fn("chunked", min_large_tokens=1024)
    # small site → dense path
    q, k, v = _rand_qkv(jax.random.key(3), N=64)
    out = fn(q, k, v)
    np.testing.assert_allclose(
        np.asarray(out), np.asarray(dense_frame_attention(q, k, v)), atol=1e-5
    )
    # large site off-TPU → chunked (still exact)
    q, k, v = _rand_qkv(jax.random.key(4), N=2048, D=4)
    out = jax.jit(fn)(q, k, v)
    np.testing.assert_allclose(
        np.asarray(out), np.asarray(dense_frame_attention(q, k, v)), atol=1e-5
    )
    import pytest

    with pytest.raises(ValueError, match="unknown frame attention impl"):
        make_frame_attention_fn("nope")
