"""Frame-attention kernel tests (CPU: chunked vs dense exactness, dispatch).

The Pallas kernels run here in interpret mode (their math, forward and
backward); tests/test_tpu_compile.py compiles them for a described v5e. Here
we also pin the chunked kernel's exactness and the dispatch rules the UNet
and the tuning CLI rely on.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

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
    kernel math; the real-TPU path is the benchmark's sd15 cell."""
    from videop2p_tpu.ops import fused_frame_attention

    q, k, v = _rand_qkv(jax.random.key(5), F=2, N=256, D=8)
    out = jax.jit(
        lambda q, k, v: fused_frame_attention(q, k, v, 128, True)
    )(q, k, v)
    np.testing.assert_allclose(
        np.asarray(out), np.asarray(dense_frame_attention(q, k, v)), atol=1e-5
    )


def _grads(fn, q, k, v, w):
    """dQ, dK, dV of ``sum(fn(q, k, v) * w)``: a cotangent that differs per
    element, so a wrong fold of frames into the query length shows."""
    return jax.jit(jax.grad(
        lambda q, k, v: jnp.sum(fn(q, k, v).astype(jnp.float32) * w),
        argnums=(0, 1, 2)))(q, k, v)


@pytest.mark.parametrize("which", [0, 1, 2], ids=["dQ", "dK", "dV"])
@pytest.mark.parametrize("shape", [(6, 256, 8, 512), (3, 128, 40, 128)],
                         ids=["F6xN256xD8", "F3xN128xD40"])
def test_fused_grad_kernel_matches_dense_interpret(shape, which):
    """The backward kernel (interpret mode) against ``jax.grad`` of dense,
    float32: F > 1 and three query blocks, so dK / dV are summed over blocks
    and frames inside the kernel; the second shape's F·N divides by 128 only
    (the smallest backward block)."""
    from videop2p_tpu.ops import fused_bwd_block, fused_frame_attention

    F, N, D, blk = shape
    q, k, v = _rand_qkv(jax.random.key(6), B=2, F=F, N=N, D=D)
    w = jax.random.normal(jax.random.key(16), q.shape)
    assert fused_bwd_block(F * N, N, D, q.dtype) == blk
    g_f = _grads(lambda q, k, v: fused_frame_attention(q, k, v, 128, True),
                 q, k, v, w)
    g_d = _grads(dense_frame_attention, q, k, v, w)
    np.testing.assert_allclose(
        np.asarray(g_f[which]), np.asarray(g_d[which]), atol=1e-4)


def test_fused_grad_kernel_bf16_against_float32_dense():
    """bf16 operands, float32 scores / softmax / dS inside the kernel: each
    gradient within 2 % of the float32 dense gradient's largest entry (the
    operands' own rounding is 2⁻⁸ = 0.4 %; P, dS and dO enter the products
    rounded once more). The bf16 chunked vjp, which rounds the scores too, is
    held to the same limit, so the kernel is no looser than what it replaces."""
    from videop2p_tpu.ops import fused_frame_attention

    q, k, v = _rand_qkv(jax.random.key(8), B=1, F=2, N=256, D=40)
    w = jax.random.normal(jax.random.key(18), q.shape)
    ref = _grads(dense_frame_attention, q, k, v, w)
    qb, kb, vb = (x.astype(jnp.bfloat16) for x in (q, k, v))
    got = _grads(lambda q, k, v: fused_frame_attention(q, k, v, 128, True),
                 qb, kb, vb, w)
    old = _grads(lambda q, k, v: chunked_frame_attention(q, k, v, q_chunk=128),
                 qb, kb, vb, w)
    for g, o, r in zip(got, old, ref):
        assert g.dtype == jnp.bfloat16
        top = float(jnp.max(jnp.abs(r)))
        assert float(jnp.max(jnp.abs(g.astype(jnp.float32) - r))) <= 0.02 * top
        assert float(jnp.max(jnp.abs(o.astype(jnp.float32) - r))) <= 0.02 * top


def test_fused_grad_falls_back_to_chunked_where_the_fit_test_refuses():
    """A shape the backward's fit test refuses (N off the 128-lane tiling)
    still differentiates exactly — through the chunked vjp."""
    from videop2p_tpu.ops import fused_bwd_block, fused_frame_attention

    q, k, v = _rand_qkv(jax.random.key(9), F=2, N=192, D=4)
    w = jax.random.normal(jax.random.key(19), q.shape)
    assert fused_bwd_block(2 * 192, 192, 4, q.dtype) is None
    g_f = _grads(lambda q, k, v: fused_frame_attention(q, k, v, 128, True),
                 q, k, v, w)
    g_d = _grads(dense_frame_attention, q, k, v, w)
    for a, b in zip(g_f, g_d):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-4)


def test_fused_bwd_block_is_arithmetic_on_the_shape():
    """The tune's two large sites and the 24-frame clip get a block; float32
    operands and longer rows get a smaller one or none (→ chunked)."""
    from videop2p_tpu.ops import fused_bwd_block
    from videop2p_tpu.ops.attention import _BWD_BLOCKS

    bf16, f32 = jnp.bfloat16, jnp.float32
    assert fused_bwd_block(8 * 4096, 4096, 40, bf16) == _BWD_BLOCKS[0]
    assert fused_bwd_block(8 * 1024, 1024, 80, bf16) == _BWD_BLOCKS[0]
    assert fused_bwd_block(24 * 4096, 4096, 40, bf16) == _BWD_BLOCKS[0]
    assert fused_bwd_block(8 * 4096, 4096, 40, f32) in _BWD_BLOCKS
    # 128² latents: even the smallest block is over the budget
    assert fused_bwd_block(8 * 16384, 16384, 40, f32) is None
    assert fused_bwd_block(8 * 4096, 4096, 160, bf16) is None  # d > 128
    assert fused_bwd_block(8 * 4096 + 64, 4096, 40, bf16) is None  # no block divides


def test_training_choice_is_chunked_off_tpu_and_the_kernel_pair_on_it(monkeypatch):
    """``training_frame_attention``: "chunked" on the CPU (today's program,
    dense under ``min_large_tokens``); with the backend reported as TPU the
    same call resolves to ``auto`` and a large site to the kernel pair."""
    from videop2p_tpu.ops import attention, training_frame_attention

    assert training_frame_attention() == "chunked"
    monkeypatch.setattr(attention.jax, "default_backend", lambda: "tpu")
    assert training_frame_attention() == "auto"
    taken = []
    monkeypatch.setattr(
        attention, "fused_frame_attention",
        lambda q, k, v, q_blk: taken.append(q.shape) or q)
    fn = make_frame_attention_fn(training_frame_attention())
    q, k, v = _rand_qkv(jax.random.key(10), N=1024, D=4)
    fn(q, k, v)
    assert taken == [q.shape]
    # a small site stays dense on either backend
    q, k, v = _rand_qkv(jax.random.key(10), N=64, D=4)
    np.testing.assert_allclose(
        np.asarray(fn(q, k, v)),
        np.asarray(dense_frame_attention(q, k, v)), atol=1e-5)
    assert len(taken) == 1


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


def _pallas_calls(jaxpr) -> dict:
    """Pallas calls of a jaxpr by kernel name, sub-jaxprs included."""
    import collections

    counts = collections.Counter(
        eqn.params["name"] for eqn in jaxpr.eqns
        if eqn.primitive.name == "pallas_call")
    for eqn in jaxpr.eqns:
        for sub in jax.core.jaxprs_in_params(eqn.params):
            counts.update(_pallas_calls(sub))
    return dict(counts)


def _named(jaxpr) -> list:
    """``name`` equations of a jaxpr, sub-jaxprs included."""
    found = [eqn for eqn in jaxpr.eqns if eqn.primitive.name == "name"]
    for eqn in jaxpr.eqns:
        for sub in jax.core.jaxprs_in_params(eqn.params):
            found += _named(sub)
    return found


def _site(attend):
    """A frame-attention site as a UNet block holds one: q from the stream,
    frame 0's k / v, the output back onto the stream through a nonlinearity."""
    def site(x):  # (B, F, H, N, D)
        return x + jnp.tanh(attend(x * 1.5, x[:, 0], x[:, 0] * 0.5))
    return site


@pytest.mark.parametrize("frames", [2, 3])
def test_the_policy_keeps_the_forward_kernel_out_of_the_recompute(frames):
    """Two checkpointed sites over the kernel pair, in interpret mode: with
    ``keep_frame_attention_output`` the gradient holds each site's forward
    kernel ONCE (a bare ``jax.checkpoint`` runs it again in the recompute),
    the backward kernel once either way, and the gradient is the bare
    checkpoint's and the uncheckpointed stack's to the bit."""
    from videop2p_tpu.ops.attention import (
        fused_frame_attention,
        keep_frame_attention_output,
    )

    site = _site(lambda q, k, v: fused_frame_attention(q, k, v, 128, True))
    x = jax.random.normal(jax.random.key(12), (1, frames, 2, 256, 8))

    def grad(wrap):
        return jax.grad(lambda x: jnp.sum(wrap(site)(wrap(site)(x)) ** 2))

    kept = grad(lambda f: jax.checkpoint(f, policy=keep_frame_attention_output))
    bare = grad(jax.checkpoint)
    assert _pallas_calls(jax.make_jaxpr(kept)(x).jaxpr) == {
        "fused_frame_attention": 2, "fused_frame_attention_bwd": 2}
    assert _pallas_calls(jax.make_jaxpr(bare)(x).jaxpr) == {
        "fused_frame_attention": 4, "fused_frame_attention_bwd": 2}
    want = jax.jit(grad(lambda f: f))(x)
    assert float(jnp.abs(want).max()) > 0
    assert bool(jnp.array_equal(jax.jit(kept)(x), want))
    assert bool(jnp.array_equal(jax.jit(bare)(x), want))


def test_the_kernels_output_carries_the_name():
    """The forward kernel's output, folded back to (B, F, H, N, D), is the
    one value named ``FRAME_ATTENTION_KEPT``; outside a checkpoint the name is
    an identity."""
    from videop2p_tpu.ops.attention import (
        FRAME_ATTENTION_KEPT,
        fused_frame_attention,
    )

    q, k, v = _rand_qkv(jax.random.key(13), F=2, N=256, D=8)
    fn = lambda q, k, v: fused_frame_attention(q, k, v, 128, True)  # noqa: E731
    names = _named(jax.make_jaxpr(fn)(q, k, v).jaxpr)
    assert [eqn.params["name"] for eqn in names] == [FRAME_ATTENTION_KEPT]
    assert names[0].outvars[0].aval.shape == q.shape
    np.testing.assert_allclose(
        np.asarray(jax.jit(fn)(q, k, v)),
        np.asarray(dense_frame_attention(q, k, v)), atol=1e-5)


@pytest.mark.parametrize("path", ["dense", "chunked", "fused_indivisible"])
def test_off_the_kernel_no_value_carries_the_name(path):
    """The dense and chunked paths, and the kernel's own fallback where the
    query grid does not divide, name nothing: under the policy the recompute
    is the bare checkpoint's, and the gradient equal to it to the bit."""
    from videop2p_tpu.ops.attention import (
        fused_frame_attention,
        keep_frame_attention_output,
    )

    n = 192 if path == "fused_indivisible" else 1024
    attend = {
        "dense": dense_frame_attention,
        "chunked": make_frame_attention_fn("chunked", q_chunk=256),
        "fused_indivisible": lambda q, k, v: fused_frame_attention(
            q, k, v, 256, True),
    }[path]
    site = _site(attend)
    x = jax.random.normal(jax.random.key(14), (1, 1, 2, n, 4))

    def grad(policy):
        return jax.grad(lambda x: jnp.sum(
            jax.checkpoint(site, policy=policy)(x) ** 2))

    kept = grad(keep_frame_attention_output)
    assert _named(jax.make_jaxpr(kept)(x).jaxpr) == []
    assert _pallas_calls(jax.make_jaxpr(kept)(x).jaxpr) == {}
    want = jax.jit(grad(None))(x)
    assert float(jnp.abs(want).max()) > 0
    assert bool(jnp.array_equal(jax.jit(kept)(x), want))
