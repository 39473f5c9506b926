"""The hybrid token models' chunked state-space scan as a Pallas pair
(``ops/ssd_scan.py``), in interpret mode on the CPU, against the XLA scan it
stands in for (``models.granite_hybrid.ssd_scan`` off the TPU) and its
autodiff: ``y``, the last state, the handed-state reading and the cotangents
of x, dt, a, B and C, at both cells' head width, state and chunk (Granite:
P 64, N 128, Q 256, one group; Falcon: P 128, N 256, Q 128, two groups) with
few heads and two or three chunks, and decays slow enough that the state a
chunk is handed matters. In float32 the pair is the XLA scan's mathematics
to rounding; in bfloat16 the forward rounds where the XLA scan rounds, and
the backward also rounds the float32 cotangents entering a product, as the
TPU's default precision does and the CPU's XLA does not. Then the fit test
and the dispatch: a shape off the kernels' tiling stays the XLA scan. What
the chip's compiler says of the kernels is ``tests/test_tpu_compile.py``'s.
"""

import functools
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from videop2p_tpu.models import granite_hybrid as gh
from videop2p_tpu.ops import ssd_scan as ss

# tokens, heads, head width, state, chunk, groups: the cells' widths and
# chunks with few heads and few chunks
SHAPES = {"granite": (512, 4, 64, 128, 256, 1),
          "falcon": (384, 4, 128, 256, 128, 2)}


def operands(name, dtype, seed=0):
    """x, dt, a, B, C as the mixer hands them over, and weights for y and
    the last state. Rates a = −exp(U[−5, 0]) and dt ≈ 0.01–0.1: a chunk's
    decay is as slow as exp(−0.1), so the state it is handed counts."""
    t_len, heads, width, state, _, groups = SHAPES[name]
    ks = jax.random.split(jax.random.key(seed), 7)
    x = jax.nn.silu(jax.random.normal(ks[0], (t_len, heads, width))).astype(dtype)
    dt = jax.nn.softplus(0.5 * jax.random.normal(ks[1], (t_len, heads)) - 3.5)
    a = -jnp.exp(jax.random.uniform(ks[2], (heads,), minval=-5.0, maxval=0.0))
    bc = (t_len, state) if groups == 1 else (t_len, groups, state)
    b, c = ((0.3 * jax.random.normal(k, bc)).astype(dtype) for k in ks[3:5])
    w_y = jax.random.normal(ks[5], (t_len, heads, width))
    w_last = jax.random.normal(ks[6], (heads, width, state))
    return (x, dt, a, b, c), (w_y, w_last)


def run(scan, ops, weights, chunk):
    """The outputs and the cotangents of sum(w_y y) + sum(w_last last) in
    all five operands."""
    w_y, w_last = weights

    def loss(*o):
        y, last, handed_sq = scan(*o, chunk)
        return jnp.sum(w_y * y) + jnp.sum(w_last * last), (y, last, handed_sq)

    (_, outs), grads = jax.value_and_grad(loss, argnums=tuple(range(5)),
                                          has_aux=True)(*ops)
    return outs, grads


def gap(got, want):
    got, want = (np.asarray(v, np.float32) for v in (got, want))
    return float(np.linalg.norm(got - want) / np.linalg.norm(want))


@pytest.fixture(scope="module", params=list(SHAPES))
def cell(request):
    return request.param


@pytest.mark.parametrize("dtype, y_tol, grad_tol", [
    (jnp.float32, 1e-6, 2e-6),
    # the forward's bfloat16 rounding points are the XLA scan's: the outputs
    # differ where float32 summation order flips a rounding of C Bᵀ ∘ L;
    # the cotangents also by the pair's
    # bfloat16 rounding of float32 cotangents entering a product (2⁻⁹)
    (jnp.bfloat16, 1e-4, 1.5e-2),
], ids=["float32", "bfloat16"])
def test_the_pair_is_the_xla_scan_and_its_autodiff(cell, dtype, y_tol,
                                                   grad_tol):
    chunk = SHAPES[cell][4]
    ops, weights = operands(cell, dtype)
    (y, last, handed_sq), grads = run(
        functools.partial(ss.ssd_scan_kernel, interpret=True), ops, weights,
        chunk)
    (y0, last0, handed_sq0), grads0 = run(gh.ssd_scan, ops, weights, chunk)
    assert y.shape == y0.shape and y.dtype == jnp.float32
    assert gap(y, y0) < y_tol and gap(last, last0) < y_tol
    assert abs(float(handed_sq) - float(handed_sq0)) <= 1e-5 * float(handed_sq0)
    for name, g, g0, op in zip(("x", "dt", "a", "b", "c"), grads, grads0, ops):
        assert g.dtype == op.dtype, name
        assert gap(g, g0) < grad_tol, (name, gap(g, g0))


def test_the_handed_state_matters_in_these_cases(cell):
    """The state each chunk is handed adds a visible part of its outputs (so
    the comparisons above see the carried term and its cotangents): its mean
    square over the last chunk is over a tenth of the outputs'."""
    chunk = SHAPES[cell][4]
    ops, _ = operands(cell, jnp.float32)
    y, _, handed_sq = gh.ssd_scan(*ops, chunk)
    assert float(handed_sq) > 0.1 * float(jnp.mean(jnp.square(y[-chunk:])))


@pytest.mark.parametrize("shape, plan", [
    # the cells' own shapes: eight heads a grid step
    ((32768, 32, 64, 128, 256, 1), 8),
    ((32768, 32, 128, 256, 128, 2), 8),
    # two heads of 64 fill a lane tile; one head of 64 alone does not
    ((512, 2, 64, 128, 256, 1), 2),
    ((512, 6, 128, 128, 128, 3), 2),
    # off the tiling: the tiny model's chunks of 8, a length that is not
    # whole chunks, two groups of a 16-wide state, one head of 64 of several
    ((64, 16, 8, 16, 8, 1), None),
    ((300, 4, 64, 128, 128, 1), None),
    ((256, 4, 64, 16, 128, 2), None),
    ((256, 3, 64, 128, 128, 3), None),
], ids=["granite", "falcon", "pair-of-64", "three-groups", "tiny",
        "ragged", "narrow-groups", "lone-64"])
def test_the_fit_test(shape, plan):
    got = ss.ssd_scan_plan(*shape, jnp.bfloat16)
    assert (got and got.heads) == plan
    if got is not None:
        assert got.fwd_vmem < got.bwd_vmem <= 100 * 2 ** 20


def test_a_shape_the_fit_test_refuses_stays_the_xla_scan(monkeypatch):
    """On the TPU too the tiny model's scan (chunks of 8) is the XLA code:
    no pallas_call in its gradient's jaxpr, and the kernel's entry point
    refuses the shape outright."""
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    ops, _ = operands("granite", jnp.float32)
    small = (ops[0][:64, :, :8], ops[1][:64], ops[2], ops[3][:64, :16],
             ops[4][:64, :16])
    assert not gh._scan_kernel_applies(small[0], small[3], 8)
    text = str(jax.make_jaxpr(jax.grad(
        lambda *o: jnp.sum(gh.ssd_scan(*o, 8)[0])))(*small))
    assert "pallas_call" not in text
    with pytest.raises(ValueError, match="ssd_scan_plan"):
        ss.ssd_scan_kernel(*small, 8)


def test_the_scan_takes_the_pair_on_the_tpu_only(cell, monkeypatch):
    """At a shape the fit test takes, ``ssd_scan`` is the pair on the TPU —
    both kernels in its gradient's jaxpr, the groups in one call — and the
    XLA scan elsewhere."""
    chunk = SHAPES[cell][4]
    ops, _ = operands(cell, jnp.bfloat16)

    def kernels():
        text = str(jax.make_jaxpr(jax.grad(
            lambda *o: jnp.sum(gh.ssd_scan(*o, chunk)[0])))(*ops))
        return sorted(re.findall(r"name=(lm_ssd_scan\w*)", text))

    assert kernels() == []
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert kernels() == ["lm_ssd_scan", "lm_ssd_scan_bwd"]
