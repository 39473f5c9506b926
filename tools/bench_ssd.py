"""The chunked state-space scan at the two hybrid cells' shapes: the Pallas
pair of ``ops/ssd_scan.py`` against the XLA scan of
``models/granite_hybrid.py``.

For each shape — ``granite`` (32768 tokens, 32 held heads of 64, a state of
128, chunks of 256, one B / C group: ``granite-4.0-h-small-s4-tune.doc32k-steps``)
and ``falcon`` (32768 tokens, 32 heads of 128, a state of 256, chunks of 128,
two groups: ``falcon-h1-34b-s1-tune.doc32k-steps``) — seeded operands drawn
as the models draw theirs, and the wall time of a call (warm, ``REPS`` calls,
ready at the end) of the forward alone and of the forward with its backward
(``jax.vjp``), on the kernel pair and on the XLA scan (its group-at-a-time
map and checkpoints included), then the pair's results against the XLA
scan's on this device: ``y``, the last state, the handed-state reading and
the five cotangents. Interpret mode cannot show what the chip's compiler
and copies do; read the gaps.

Usage (from the root of the checkout):
       python tools/bench_ssd.py [granite] [falcon]
"""

from __future__ import annotations

import os
import sys
import time

import jax
import jax.numpy as jnp

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if _REPO not in sys.path:
    sys.path.insert(0, _REPO)

from videop2p_tpu.models import granite_hybrid as gh  # noqa: E402
from videop2p_tpu.ops import ssd_scan as ss  # noqa: E402

# tokens, heads, head width, state, chunk, groups
SHAPES = {"granite": (32768, 32, 64, 128, 256, 1),
          "falcon": (32768, 32, 128, 256, 128, 2)}
REPS = 3


def operands(name):
    """x, dt, a, B, C and a cotangent of y, as the mixer hands them over:
    silu of normals in bfloat16, dt = softplus(z + softplus⁻¹(U[1e-3, 0.1])),
    a = −U[1, 16]."""
    t_len, heads, width, state, _, groups = SHAPES[name]
    ks = jax.random.split(jax.random.key(41), 7)
    bf16 = jnp.bfloat16
    x = jax.nn.silu(jax.random.normal(ks[0], (t_len, heads, width))).astype(bf16)
    dt_bias = jnp.log(jnp.expm1(jax.random.uniform(ks[1], (heads,),
                                                   minval=1e-3, maxval=0.1)))
    dt = jax.nn.softplus(0.5 * jax.random.normal(ks[2], (t_len, heads))
                         + dt_bias)
    a = -jax.random.uniform(ks[3], (heads,), minval=1.0, maxval=16.0)
    bc = (t_len, state) if groups == 1 else (t_len, groups, state)
    b, c = (jax.nn.silu(jax.random.normal(k, bc)).astype(bf16)
            for k in ks[4:6])
    dy = jax.random.normal(ks[6], (t_len, heads, width), jnp.float32)
    return (x, dt, a, b, c), dy


def timed(label, fn, *args):
    """Wall ms a call: compiled and warmed, then ``REPS`` calls in a row."""
    try:
        fn = jax.jit(fn)
        out = jax.block_until_ready(fn(*args))
        jax.block_until_ready(fn(*args))
        t0 = time.perf_counter()
        for _ in range(REPS):
            out = fn(*args)
        jax.block_until_ready(out)
        ms = (time.perf_counter() - t0) / REPS * 1e3
        print(f"  {label:44s} {ms:9.3f} ms", flush=True)
        return out
    except Exception as e:  # noqa: BLE001
        print(f"  {label:44s} FAILED: {type(e).__name__}: {str(e)[-400:]}",
              flush=True)
        return None


def gap(name, a, b):
    a, b = a.astype(jnp.float32), b.astype(jnp.float32)
    print(f"    {name:9s} max|pair - XLA| / max|XLA| = "
          f"{float(jnp.max(jnp.abs(a - b)) / jnp.max(jnp.abs(b))):.6f}",
          flush=True)


def xla_scan(*args):
    """The XLA code of ``granite_hybrid.ssd_scan``, whatever the backend."""
    applies = gh._scan_kernel_applies
    gh._scan_kernel_applies = lambda *a: False
    try:
        return gh.ssd_scan(*args)
    finally:
        gh._scan_kernel_applies = applies


def bench(name):
    ops, dy = operands(name)
    t_len, heads, width, state, chunk, groups = SHAPES[name]
    plan = ss.ssd_scan_plan(t_len, heads, width, state, chunk, groups,
                            jnp.bfloat16)
    print(f"{name}: {t_len} tokens, {heads} heads of {width}, state {state}, "
          f"chunks of {chunk}, {groups} group(s), plan {plan}", flush=True)
    if plan is None:
        return

    def pair(*a):
        return ss.ssd_scan_kernel(*a, chunk)

    def xla(*a):
        return xla_scan(*a, chunk)

    def with_grads(scan):
        def run(dy, *a):
            out, vjp = jax.vjp(lambda *o: scan(*o)[:2], *a)
            return out, vjp((dy, jnp.zeros_like(out[1])))
        return run

    got = timed("lm_ssd_scan (forward)", pair, *ops)
    got_g = timed("lm_ssd_scan + lm_ssd_scan_bwd (vjp)", with_grads(pair),
                  dy, *ops)
    want = timed("XLA scan forward", xla, *ops)
    want_g = timed("XLA scan forward + backward (vjp)", with_grads(xla),
                   dy, *ops)
    if got is not None and want is not None:
        for label, u, v in zip(("y", "last"), got, want):
            gap(label, u, v)
        print(f"    handed_sq pair {float(got[2]):.6e} XLA {float(want[2]):.6e}",
              flush=True)
    if got_g is not None and want_g is not None:
        for label, u, v in zip(("dx", "d(dt)", "d(a)", "dB", "dC"),
                               got_g[1], want_g[1]):
            gap(label, u, v)


def main():
    argv = sys.argv[1:]
    if any(a in ("-h", "--help") for a in argv):
        print(__doc__.strip())
        return 0
    print(f"device={jax.devices()[0].device_kind}", flush=True)
    for name in [a for a in argv if a in SHAPES] or list(SHAPES):
        bench(name)
    return 0


if __name__ == "__main__":
    sys.exit(main())
