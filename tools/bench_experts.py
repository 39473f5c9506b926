"""The grouped expert loop at the token cells' shapes: the Pallas pair of
``ops/grouped_experts.py`` against the XLA loop of ``models/deepseek.py``.

For each shape — ``hybrid`` (32768 tokens x 4096, 18 of 72 experts 768 wide,
top-10: ``granite-4.0-h-small-s4-tune.doc32k-steps``), ``other`` (16384 x
7168, 16 of 256 experts 2048 wide, top-8: ``deepseek-v32-s16-tune.doc16k-steps``),
``keye`` (16384 x 2048, 128 of 128 experts 768 wide, top-8:
``keye-vl2-30b-a3b-s1-tune.doc16k-steps``) and ``command`` (32768 x 4096, 16 of
128 experts 4096 wide, top-8: ``command-a-plus-s8-tune.doc32k-steps``) — a
level random routing, the block table ``expert_blocks`` builds, and per
``EXPERT_BLOCK`` given (default: the model's), at the tiles and row form the
fit test chooses: the bytes the row streams move a call (``x`` / ``dy`` in,
the float32 accumulator in and out, for the rows held), the wall time a call
of the forward and of the backward kernel (warm, ``REPS`` calls, ready at the
end), the same of the XLA loop, and the kernels' results against the XLA
loop's on this device — the check that interpret mode cannot make: on the
chip the copies are really asynchronous (PERF.md §6, PR 33). Where the rows
travel packed (bfloat16 pairs in 32-bit words, ``hidden`` a multiple of
2048) the pair is also run on the float32 rows of the same values (``x`` /
``dy`` widened, the form the kernels take elsewhere), timed, and its results
compared with the packed form's to the bit.

``split <trace dir>`` reads a traced call a benchmark run left behind
(``outputs/bench/<cell>/trace``) and prints the device self time under the
scope ``lm.experts`` by kind of op and result shape, with the harness's own
nesting of events: what the loop's gathers, scatter-adds, products and
kernels each take.

Usage (from the root of the checkout):
       python tools/bench_experts.py [hybrid] [other] [keye] [command] [block ...]
       python tools/bench_experts.py split <trace dir>
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

from videop2p_tpu.models import deepseek as ds  # noqa: E402
from videop2p_tpu.ops import grouped_experts as ge  # noqa: E402

# tokens, hidden, all experts, held, inner, top-k
SHAPES = {"hybrid": (32768, 4096, 72, 18, 768, 10),
          "other": (16384, 7168, 256, 16, 2048, 8),
          "keye": (16384, 2048, 128, 128, 768, 8),
          "command": (32768, 4096, 128, 16, 4096, 8)}
REPS = 3


def operands(name):
    t_len, hidden, n_all, held, inner, k = SHAPES[name]
    ks = jax.random.split(jax.random.key(33), 7)
    bf16 = jnp.bfloat16
    x = jax.random.normal(ks[0], (t_len, hidden), bf16)
    dy = jax.random.normal(ks[1], (t_len, hidden), bf16)
    wg, wu = (jax.random.normal(kk, (held, hidden, inner), bf16) * hidden ** -0.5
              for kk in ks[2:4])
    wd = jax.random.normal(ks[4], (held, inner, hidden), bf16) * inner ** -0.5
    experts = jax.lax.top_k(jax.random.uniform(ks[5], (t_len, n_all)), k)[1]
    gates = jax.random.uniform(ks[6], (t_len, k), jnp.float32, 0.2, 1.5)
    return x, dy, experts.astype(jnp.int32), gates, (wg, wu, wd), (0, held)


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
    print(f"    {name:6s} max|kernel - loop| / max|loop| = "
          f"{float(jnp.max(jnp.abs(a - b)) / jnp.max(jnp.abs(b))):.6f}", flush=True)


def stream_bytes(rows, hidden, packed):
    """Bytes the row streams move for ``rows`` held rows: the forward's
    ``x`` in and accumulator in and out, the backward's ``x``, ``dy`` in and
    accumulator in and out."""
    x = rows * hidden * (2 if packed else 4)
    acc = rows * hidden * 4
    return x + 2 * acc, 2 * x + 2 * acc


def same(name, a, b):
    print(f"    {name:6s} packed == float32 rows, to the bit: "
          f"{bool(jnp.array_equal(a, b.astype(a.dtype)))}", flush=True)


def bench(name, block):
    x, dy, experts, gates, ws, held = operands(name)
    tok, gate, tbl, count = jax.jit(
        lambda e, g: ds.expert_blocks(e, g, held, block))(experts, gates)
    tiles = ge.grouped_expert_tiles(tok.shape[0], x.shape[1], ws[0].shape[2],
                                    block, ws[0].dtype, x.dtype)
    print(f"{name}: block {block}, {int(tbl['n'])} live blocks of "
          f"{tbl['expert'].shape[0]}, rows an expert {int(count.min())}–"
          f"{int(count.max())}, tiles {tiles}", flush=True)
    if tiles is None:
        return
    rows = int(count.sum())
    for form, packed in (("packed", True), ("float32", False)):
        if packed and not tiles.packed:
            continue
        f, b = stream_bytes(rows, x.shape[1], packed)
        print(f"  row streams, {form} rows: forward {f / 1e9:.3f} GB, backward "
              f"{b / 1e9:.3f} GB ({rows} held rows)", flush=True)

    def fwd(x, tok, gate, tbl, *ws):
        return ge.grouped_experts_forward(x, tok, gate, tbl, *ws, block)

    def bwd(x, dy, tok, gate, tbl, *ws):
        return ge.grouped_experts_backward(x, dy, tok, gate, tbl, *ws, block)

    y = timed("lm_grouped_experts", fwd, x, tok, gate, tbl, *ws)
    grads = timed("lm_grouped_experts_bwd", bwd, x, dy, tok, gate, tbl, *ws)
    if tiles.packed:
        x32, dy32 = x.astype(jnp.float32), dy.astype(jnp.float32)
        y32 = timed("lm_grouped_experts, float32 rows", fwd, x32, tok, gate,
                    tbl, *ws)
        grads32 = timed("lm_grouped_experts_bwd, float32 rows", bwd, x32, dy32,
                        tok, gate, tbl, *ws)
        if y is not None and y32 is not None:
            same("y", y, y32)
        if grads is not None and grads32 is not None:
            same("dx", grads[0], grads32[0])
            same("dgate", grads[1], grads32[1])
    want_y = timed("XLA loop forward",
                   lambda *a: ds._grouped_loop_fwd(*a, block),
                   x, tok, gate, tbl, *ws)
    want = timed("XLA loop backward",
                 lambda *a: ds._grouped_loop_bwd(*a, block),
                 x, dy, tok, gate, tbl, *ws)
    if y is not None and want_y is not None:
        gap("y", y, want_y)
    if grads is not None and want is not None:
        gap("dx", grads[0], want[0])
        gap("dgate", grads[1], want[1])


def split(trace_dir, scope="lm.experts"):
    """Device self seconds under ``scope`` by (op family, result) and by
    family alone, most first."""
    import re

    from benchmark.drivers.tune_lm_steps import device_events_with_scope_text
    from benchmark.harness import trace

    by_op, by_family, total = {}, {}, 0.0
    for events in device_events_with_scope_text(trace_dir).values():
        for name, _, _, self_ns, _, stats in trace.self_times(events):
            if scope not in stats["text"]:
                continue
            family = trace.kernel_of(name, stats) or trace.op_family(name)
            m = re.search(r" = (\(?[a-z0-9]+\[[0-9,]*\])", name)
            key = (family, m.group(1) if m else "?")
            seconds = max(self_ns, 0) / 1e9
            row = by_op.setdefault(key, [0, 0.0])
            row[0] += 1
            row[1] += seconds
            by_family[family] = by_family.get(family, 0.0) + seconds
            total += seconds
    print(f"{scope}: {total:.4f} s of device self time in {trace_dir}")
    for family, seconds in sorted(by_family.items(), key=lambda kv: -kv[1])[:12]:
        print(f"  {family:28s} {seconds:9.4f} s")
    print("  by op family and result:")
    for (family, shape), (calls, seconds) in sorted(
            by_op.items(), key=lambda kv: -kv[1][1])[:24]:
        print(f"    {family:24s} {shape:28s} x{calls:<7d} {seconds:9.4f} s")
    return 0


def main():
    argv = sys.argv[1:]
    if any(a in ("-h", "--help") for a in argv):
        print(__doc__.strip())
        return 0
    if argv[:1] == ["split"]:
        return split(argv[1])
    print(f"device={jax.devices()[0].device_kind}", flush=True)
    names = [a for a in argv if a in SHAPES] or list(SHAPES)
    blocks = [int(a) for a in argv if a.isdigit()] or [ds.EXPERT_BLOCK]
    for name in names:
        for block in blocks:
            bench(name, block)
    return 0


if __name__ == "__main__":
    sys.exit(main())
