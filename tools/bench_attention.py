"""Frame-attention kernel shootout at the SD-1.5 hot shape.

Times every ops/attention.py implementation at the 64²-site working point
of the fast edit — B=3 streams, F=8 frames, H=8 heads, N=4096 tokens, d=40.

Measurement per impl: warm on a fresh input, then time a CHAIN of calls
where each input depends on the previous output (no call can start before
the one ahead of it finished), ending with a device→host value fetch.

``grad`` times the BACKWARD of one site (``jax.grad`` with a cotangent that
does not depend on the output, so XLA drops the forward, whose result no
gradient needs) at the two large sites of Stage-1 tuning, batch 1: the
chunked vjp (its own recompute of each chunk included) against the Pallas
backward kernel at each block (PERF.md §6, PR 27, has the chip's readings;
``ops/attention._BWD_BLOCKS`` takes the largest that fits VMEM).

``selected`` times the token model's selected-key attention pair
(``ops/selected_attention.py``) at the cell's shape — 16384 tokens, 8 heads,
128 / 64 / 128, bfloat16, about 2048 keys a query — for each (query, key)
tile and heads a cell given as ``bq,bk[,fwd_heads,bwd_heads]`` (default: the
module's own choice): forward + all five gradients in one program, traced,
and each kernel's device time a call read from the PROFILER's events by the
kernel's name, beside the step's wall time; then the pair against
``models.deepseek._attend`` at 2048 tokens on the same device. ``xla`` among
the arguments also times the chunked XLA path (PERF.md §6, PR 29).

Usage: PYTHONPATH=/root/repo python tools/bench_attention.py
           [reps | grad | selected [xla] [bq,bk[,fh,bh] ...]]
"""

from __future__ import annotations

import functools
import sys
import time

import jax
import jax.numpy as jnp

sys.path.insert(0, "/root/repo")

from videop2p_tpu.ops.attention import (  # noqa: E402
    chunked_frame_attention,
    dense_frame_attention,
    fused_frame_attention,
)

B, F, H, N, D = 3, 8, 8, 4096, 40


def measure(name, fn, reps: int = 8):
    key = jax.random.key(time.time_ns() % (2**31))
    kq, kk, kv, kw = jax.random.split(key, 4)
    q = jax.random.normal(kq, (B, F, H, N, D), jnp.bfloat16)
    k = jax.random.normal(kk, (B, H, N, D), jnp.bfloat16)
    v = jax.random.normal(kv, (B, H, N, D), jnp.bfloat16)

    jfn = jax.jit(fn)
    try:
        out = jfn(jax.random.normal(kw, q.shape, q.dtype), k, v)  # compile+warm
        jax.block_until_ready(out)
        float(out.ravel()[0].astype(jnp.float32))

        t0 = time.time()
        for _ in range(reps):
            out = jfn(q, k, v)
            # chain: next q depends on this output — no two calls share args
            q = q + 0.001 * out
        jax.block_until_ready(out)
        float(out.ravel()[0].astype(jnp.float32))
        dt = (time.time() - t0) / reps
    except Exception as e:  # noqa: BLE001
        print(f"{name:28s} FAILED: {type(e).__name__}: {str(e)[:120]}")
        return None, None

    # FLOPs: QK^T + PV = 2 * 2 * B*F*H*N*N*D
    flops = 4 * B * F * H * N * N * D
    # numerical parity vs dense at a small shape (full-shape dense scores
    # are ~13 GB and OOM the chip outside the fused forward)
    ks = jax.random.split(jax.random.key(7), 3)
    qs = jax.random.normal(ks[0], (1, 2, 2, 1024, D), jnp.bfloat16)
    kk2 = jax.random.normal(ks[1], (1, 2, 1024, D), jnp.bfloat16)
    vs = jax.random.normal(ks[2], (1, 2, 1024, D), jnp.bfloat16)
    small = jax.jit(fn)(qs, kk2, vs)
    ref = jax.jit(dense_frame_attention)(qs, kk2, vs)
    err = float(jnp.max(jnp.abs((small - ref).astype(jnp.float32))))
    print(f"{name:28s} {dt*1e3:8.2f} ms   {flops/dt/1e12:6.1f} TF/s  max|d|={err:.4f}")
    return dt, out


def measure_grad(name, fn, shape, reps: int = 10):
    b, f, h, n, d = shape
    ks = jax.random.split(jax.random.key(n), 4)
    q = jax.random.normal(ks[0], shape, jnp.bfloat16)
    k = jax.random.normal(ks[1], (b, h, n, d), jnp.bfloat16)
    v = jax.random.normal(ks[2], (b, h, n, d), jnp.bfloat16)
    w = jax.random.normal(ks[3], shape, jnp.float32)
    # all three gradients feed the next call's operands INSIDE the program:
    # none can be dropped as unused, and no eager op (whose first use
    # compiles) sits in the timed loop
    def step(q, k, v):
        grads = jax.grad(
            lambda q, k, v: jnp.sum(fn(q, k, v).astype(jnp.float32) * w),
            argnums=(0, 1, 2))(q, k, v)
        return tuple(x + 0.001 * g for x, g in zip((q, k, v), grads))

    step = jax.jit(step)
    try:
        q, k, v = jax.block_until_ready(step(*step(q, k, v)))
        t0 = time.perf_counter()
        for _ in range(reps):
            q, k, v = step(q, k, v)
        jax.block_until_ready(q)
        print(f"{name:28s} {(time.perf_counter() - t0) / reps * 1e3:8.2f} ms")
    except Exception as e:  # noqa: BLE001
        print(f"{name:28s} FAILED: {type(e).__name__}: {str(e)[-200:]}")


def main_grad():
    import videop2p_tpu.ops.attention as attention

    blocks = attention._BWD_BLOCKS
    for shape in [(1, 8, 8, 4096, 40), (1, 8, 8, 1024, 80)]:
        print(f"grad, q={shape}  device={jax.devices()[0].device_kind}")
        measure_grad("chunked(512) vjp", chunked_frame_attention, shape)
        for blk in blocks:
            attention._BWD_BLOCKS = (blk,)
            # a fresh function per block, so that jit traces it again
            measure_grad(f"fused bwd kernel, block {blk}",
                         lambda q, k, v: fused_frame_attention(q, k, v, 256),
                         shape)
        attention._BWD_BLOCKS = blocks


def _selected_operands(t_len, heads=8, nope=128, rope=64, v_dim=128, keys=2048):
    ks = jax.random.split(jax.random.key(t_len), 7)
    shapes = [(t_len, heads, nope), (t_len, heads, rope), (t_len, heads, nope),
              (t_len, rope), (t_len, heads, v_dim)]
    ops = [jax.random.normal(k, s, jnp.bfloat16) for k, s in zip(ks, shapes)]
    pos = jnp.arange(t_len)
    # about ``keys`` keys a query, scattered; every query keeps itself
    mask = jax.jit(lambda k: (pos[None, :] <= pos[:, None]) & (
        (jax.random.uniform(k, (t_len, t_len)) * (pos[:, None] + 1) < keys)
        | (pos[None, :] == pos[:, None])))(ks[5])
    w = jax.random.normal(ks[6], shapes[4], jnp.float32)
    return ops, mask, w


def _kernel_ms(trace_dir, names):
    """Device milliseconds a call of each kernel in ``names``, from the
    trace's ``XLA Ops`` events (an event's name is its HLO instruction)."""
    from videop2p_tpu.obs.trace import iter_line_events, load_xplanes

    total = {n: [0, 0] for n in names}
    # longest first: the backward's name holds the forward's
    by_length = sorted(names, key=len, reverse=True)
    for name, _, dur in iter_line_events(load_xplanes(trace_dir), "XLA Ops"):
        for n in by_length:
            if n in name:
                total[n][0] += dur
                total[n][1] += 1
                break
    return {n: (ps / max(c, 1) / 1e9, c) for n, (ps, c) in total.items()}


def main_selected(argv):
    import shutil
    import tempfile

    import videop2p_tpu.models.deepseek as ds
    import videop2p_tpu.ops.selected_attention as sa

    scale, reps = 192 ** -0.5, 5
    print(f"selected-key attention, device={jax.devices()[0].device_kind}")

    def timed(label, attend, ops, mask, w):
        # the mask and the weights are arguments: closed over they would be
        # 0.3 GB of constants in every executable
        @jax.jit
        def step(mask, w, *ops):
            grads = jax.grad(
                lambda *a: jnp.sum(attend(*a, mask, scale).astype(jnp.float32) * w),
                argnums=range(5))(*ops)
            return tuple(x + 0.001 * g for x, g in zip(ops, grads))

        try:
            ops = jax.block_until_ready(step(mask, w, *step(mask, w, *ops)))
            trace_dir = tempfile.mkdtemp(prefix="bench_selected_")
            jax.profiler.start_trace(trace_dir)
            t0 = time.perf_counter()
            for _ in range(reps):
                ops = step(mask, w, *ops)
            jax.block_until_ready(ops)
            wall = (time.perf_counter() - t0) / reps * 1e3
            jax.profiler.stop_trace()
            ms = _kernel_ms(trace_dir, ("lm_selected_attention_bwd",
                                        "lm_selected_attention"))
            shutil.rmtree(trace_dir, ignore_errors=True)
            print(f"{label:34s} step {wall:8.2f} ms   " + "   ".join(
                f"{n} {v:7.3f} ms x{c}" for n, (v, c) in ms.items()))
        except Exception as e:  # noqa: BLE001
            print(f"{label:34s} FAILED: {type(e).__name__}: {str(e)[-300:]}")

    ops, mask, w = _selected_operands(16384)
    configs = [a for a in argv if "," in a]
    tiles, tiles_of = sa._TILES, sa._tiles_of
    for c in configs or [None]:
        label = "module's choice"
        if c is not None:
            bq, bk, *hb = map(int, c.split(","))
            sa._TILES = ((bq, bk),)
            label = f"tiles {bq} x {bk}"
        got = sa.selected_attention_tiles(16384, 8, 128, 64, 128, jnp.bfloat16)
        if got is None:
            print(f"{label:34s} refused by the fit test")
            continue
        if c is not None and hb:
            # what the fit test returns, with the heads a cell overridden
            got = got._replace(fwd_heads=hb[0], bwd_heads=hb[1])
            sa._tiles_of = lambda *a, got=got: got
        timed(f"{label}, heads {got.fwd_heads} / {got.bwd_heads}",
              sa.selected_key_attention, ops, mask, w)
        sa._tiles_of = tiles_of
    sa._TILES = tiles
    if "xla" in argv:
        timed("chunked XLA (_chunked_attend)", ds._chunked_attend, ops, mask, w)

    # the pair against _attend, on this device, bfloat16
    ops, mask, w = _selected_operands(2048, keys=256)

    def both(attend, mask, w, *ops):
        out, vjp = jax.vjp(lambda *a: attend(*a, mask, scale), *ops)
        return (out,) + vjp(w.astype(out.dtype))

    want = jax.jit(functools.partial(both, ds._attend))(mask, w, *ops)
    got = jax.jit(functools.partial(both, sa.selected_key_attention))(mask, w, *ops)
    for name, a, b in zip(("o", "dq_nope", "dq_rope", "dk_nope", "dk_rope", "dv"),
                          got, want):
        a, b = a.astype(jnp.float32), b.astype(jnp.float32)
        print(f"  {name:8s} max|kernel - _attend| / max|_attend| = "
              f"{float(jnp.max(jnp.abs(a - b)) / jnp.max(jnp.abs(b))):.5f}")


def main():
    if any(a in ("-h", "--help") for a in sys.argv[1:]):
        print(__doc__.strip())
        return 0
    if sys.argv[1:] == ["grad"]:
        return main_grad()
    if sys.argv[1:2] == ["selected"]:
        return main_selected(sys.argv[2:])
    reps = int(sys.argv[1]) if len(sys.argv) > 1 else 8
    print(f"shape: q=({B},{F},{H},{N},{D})  reps={reps}  "
          f"device={jax.devices()[0].device_kind}")
    measure("fused(256)", functools.partial(fused_frame_attention, q_blk=256), reps)
    measure("fused(512)", functools.partial(fused_frame_attention, q_blk=512), reps)
    measure("fused(1024)", functools.partial(fused_frame_attention, q_blk=1024), reps)
    measure("dense", dense_frame_attention, reps)
    measure("chunked(512)", functools.partial(chunked_frame_attention, q_chunk=512), reps)
    measure("chunked(1024)", functools.partial(chunked_frame_attention, q_chunk=1024), reps)


if __name__ == "__main__":
    main()
