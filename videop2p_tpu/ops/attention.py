"""Frame-attention kernels: Pallas flash attention on TPU, chunked fallback.

The spatial frame attention (every frame's queries against frame-0 keys,
/root/reference/tuneavideo/models/attention.py:296-302) is the framework's
hw×hw hot op: at 64×64 latents it is a 4096×4096 attention per frame per
head — materialized, that is ~2 GB of probabilities in bf16 and the single
reason the reference needs xformers (SURVEY §2.1 #7). Implementations behind
one dispatch:

  * **fused** — custom Pallas kernel for the frame-0-KV structure: K/V sit
    resident in VMEM (N·D ≈ 320 KB each) while query blocks stream through
    with an exact full-row softmax. The TPU inference default ("auto"):
    measured 19.6 s → 17.0 s fast-edit e2e vs dense (round-3 A/B on v5e).
  * **dense** — plain einsum: the CPU path and the small-site (16²/8²)
    fallback, where the score matrix is tiny and XLA fuses it fine.
  * **chunked** — exact attention scanned over query blocks with
    ``jax.checkpoint``, bounding peak memory on any backend: the TRAINING
    path (bounded backward) and the sharded-mesh path (pjit cannot
    partition a Pallas custom call).
  * **flash / flash_rect** — the stock Pallas flash-attention kernel
    (``jax.experimental.pallas.ops.tpu.flash_attention``); kept for
    comparison — loses to ``fused`` at every measured shape (d=40 grid
    overhead, tools/bench_attention.py).

These kernels are only for the UNCONTROLLED frame attention. The P2P
controlled sites (text-cross, temporal) must materialize probabilities for
editing — they are small (hw×77 and f×f; SURVEY §7 hard-part #2).
"""

from __future__ import annotations

import functools
from typing import Callable, Optional

import jax
import jax.numpy as jnp

__all__ = [
    "dense_frame_attention",
    "chunked_frame_attention",
    "flash_frame_attention",
    "flash_rect_frame_attention",
    "fused_frame_attention",
    "make_frame_attention_fn",
]

# shapes: q (B, F, H, N, D); k, v (B, H, N, D) — frame-0 KV shared across F
FrameAttentionFn = Callable[[jax.Array, jax.Array, jax.Array], jax.Array]

# every implementation below runs under this named scope (metadata on the
# ops, no device work): the profiler's device events carry it, the chunked
# forward's lax.map body and its checkpointed backward included. Where an
# implementation hands over to another, the scope opens after that return.
_SCOPE = "ops.frame_attention"


@jax.named_scope(_SCOPE)
def dense_frame_attention(q: jax.Array, k: jax.Array, v: jax.Array) -> jax.Array:
    scale = q.shape[-1] ** -0.5
    sim = jnp.einsum("bfhqd,bhkd->bfhqk", q, k) * scale
    probs = jax.nn.softmax(sim.astype(jnp.float32), axis=-1).astype(q.dtype)
    return jnp.einsum("bfhqk,bhkd->bfhqd", probs, v)


def chunked_frame_attention(
    q: jax.Array, k: jax.Array, v: jax.Array, *, q_chunk: int = 512
) -> jax.Array:
    """Exact attention, scanned over query chunks (peak score memory
    B·F·H·q_chunk·N instead of B·F·H·N·N); ``jax.checkpoint`` keeps the
    backward pass at the same bound."""
    b, f, h, n, d = q.shape
    if n % q_chunk != 0 or n <= q_chunk:
        return dense_frame_attention(q, k, v)
    nc = n // q_chunk

    @jax.checkpoint
    def one_chunk(q_blk):
        scale = d ** -0.5
        sim = jnp.einsum("bfhqd,bhkd->bfhqk", q_blk, k) * scale
        probs = jax.nn.softmax(sim.astype(jnp.float32), axis=-1).astype(q.dtype)
        return jnp.einsum("bfhqk,bhkd->bfhqd", probs, v)

    with jax.named_scope(_SCOPE):
        qc = jnp.moveaxis(q.reshape(b, f, h, nc, q_chunk, d), 3, 0)  # (nc,B,F,H,C,D)
        out = jax.lax.map(one_chunk, qc)  # (nc, B, F, H, C, D)
        return jnp.moveaxis(out, 0, 3).reshape(b, f, h, n, d)


@jax.named_scope(_SCOPE)
def flash_frame_attention(q: jax.Array, k: jax.Array, v: jax.Array) -> jax.Array:
    """Pallas TPU flash attention with the frame axis folded into batch and
    the shared frame-0 KV broadcast per frame."""
    from jax.experimental.pallas.ops.tpu.flash_attention import flash_attention

    b, f, h, n, d = q.shape
    qf = q.reshape(b * f, h, n, d)
    kf = jnp.broadcast_to(k[:, None], (b, f, h, n, d)).reshape(b * f, h, n, d)
    vf = jnp.broadcast_to(v[:, None], (b, f, h, n, d)).reshape(b * f, h, n, d)
    out = flash_attention(qf, kf, vf, sm_scale=d ** -0.5)
    return out.reshape(b, f, h, n, d)


@jax.named_scope(_SCOPE)
def flash_rect_frame_attention(q: jax.Array, k: jax.Array, v: jax.Array) -> jax.Array:
    """Pallas TPU flash attention with frames folded into the QUERY length.

    The frame-0 KV is shared by every frame, so instead of broadcasting KV
    per frame (``flash_frame_attention`` — the materialized copies eat the
    kernel's win), queries from all frames form one long rectangular
    attention: q (B, H, F·N, D) against kv (B, H, N, D). Softmax is per-row,
    so the fold is exact; no probability tensor or KV copy materializes.
    """
    from jax.experimental.pallas.ops.tpu.flash_attention import flash_attention

    b, f, h, n, d = q.shape
    qf = q.transpose(0, 2, 1, 3, 4).reshape(b, h, f * n, d)
    out = flash_attention(qf, k, v, sm_scale=d ** -0.5)
    return out.reshape(b, h, f, n, d).transpose(0, 2, 1, 3, 4)


def _fused_kernel(q_ref, k_ref, v_ref, o_ref, *, scale: float):
    """One grid cell: full-row attention of a query block against the whole
    (VMEM-resident) frame-0 K/V. No online softmax — the complete score row
    is materialized in VMEM, so max/sum are exact single-pass reductions."""
    import jax.lax as lax

    q = q_ref[0]  # (q_blk, D)
    k = k_ref[0]  # (N, D)
    v = v_ref[0]  # (N, D)
    s = lax.dot_general(
        q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
    ) * scale  # (q_blk, N) f32
    m = jnp.max(s, axis=-1, keepdims=True)
    p = jnp.exp(s - m)
    l = jnp.sum(p, axis=-1, keepdims=True)
    o = lax.dot_general(
        p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    )
    o_ref[0] = (o / l).astype(o_ref.dtype)


def _fused_rect(q3: jax.Array, k: jax.Array, v: jax.Array, q_blk: int,
                interpret: bool = False) -> jax.Array:
    """q3 (BH, M, D) against k/v (BH, N, D) → (BH, M, D)."""
    from jax.experimental import pallas as pl

    bh, m, d = q3.shape
    n = k.shape[1]
    grid = (bh, m // q_blk)
    return pl.pallas_call(
        functools.partial(_fused_kernel, scale=d ** -0.5),
        # explicit name: the compiled program's custom call and the trace
        # events carry it (obs/introspect.tpu_custom_call_counts)
        name="fused_frame_attention",
        out_shape=jax.ShapeDtypeStruct((bh, m, d), q3.dtype),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, q_blk, d), lambda b, i: (b, i, 0)),
            # constant along the inner grid axis → fetched once per (b, h)
            pl.BlockSpec((1, n, d), lambda b, i: (b, 0, 0)),
            pl.BlockSpec((1, n, d), lambda b, i: (b, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, q_blk, d), lambda b, i: (b, i, 0)),
        interpret=interpret,  # CPU-testable (tests/test_ops.py)
    )(q3, k, v)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4))
def fused_frame_attention(
    q: jax.Array, k: jax.Array, v: jax.Array, q_blk: int = 256,
    interpret: bool = False
) -> jax.Array:
    """Pallas TPU frame-attention kernel exploiting the frame-0-KV structure
    (/root/reference/tuneavideo/models/attention.py:296-302: every frame's
    spatial self-attention shares frame 0's keys/values).

    The XLA dense path materializes the (B,F,H,N,N) bf16 score tensor in HBM
    (3.2 GB per 64²-site instance at the edit batch — measured ~18 ms per
    instance per step, ~32 % of the round-2 edit scan; tools/xplane_top_ops).
    Here K/V for one (batch, head) are tiny — N·D ≈ 320 KB each — so they sit
    resident in VMEM while query blocks stream through: one QKᵀ, an exact
    full-row softmax (no online accumulation needed), one PV, nothing but
    q/out ever touching HBM. Frames fold into the query length (softmax is
    per-row, so the fold is exact; same trick as flash_rect), giving long
    M = F·N grids that also cover the 24/32-frame long-video shapes without
    the chunked path's lax.map overhead.

    Differentiation recomputes through :func:`chunked_frame_attention` (the
    memory-bounded exact backward); the kernel itself is inference-path.
    """
    b, f, h, n, d = q.shape
    if (f * n) % q_blk != 0:
        # the grid would silently drop the remainder queries — fall back to
        # the exact chunked kernel (same convention as its own fallback)
        return chunked_frame_attention(q, k, v)
    with jax.named_scope(_SCOPE):
        qr = q.transpose(0, 2, 1, 3, 4).reshape(b * h, f * n, d)
        kr = k.reshape(b * h, n, d)
        vr = v.reshape(b * h, n, d)
        out = _fused_rect(qr, kr, vr, q_blk, interpret)
        return out.reshape(b, h, f, n, d).transpose(0, 2, 1, 3, 4)


def _fused_fwd(q, k, v, q_blk, interpret):
    return fused_frame_attention(q, k, v, q_blk, interpret), (q, k, v)


def _fused_bwd(q_blk, interpret, res, g):
    q, k, v = res
    _, vjp = jax.vjp(chunked_frame_attention, q, k, v)
    return vjp(g)


fused_frame_attention.defvjp(_fused_fwd, _fused_bwd)


def make_frame_attention_fn(
    impl: str = "auto",
    *,
    min_large_tokens: int = 1024,
    q_chunk: int = 512,
) -> Optional[FrameAttentionFn]:
    """Dispatching frame-attention implementation.

    ``impl``:
      * "auto" — ``fused`` on TPU, ``dense`` elsewhere (None → the
        module-inline einsum). Round-3 shootout on v5e at the 64²-site edit
        shape (tools/bench_attention.py): the XLA dense path materializes the
        bf16 score tensor in HBM (~18 ms/instance inside the forward); the
        stock Pallas flash kernel is worse at d=40 regardless of head-dim
        padding (118–124 ms standalone vs chunked 51 ms — its block/grid
        shape, not the 40→128 tile padding, is the loss); the ``fused``
        kernel below keeps everything in VMEM.
      * "fused" — custom Pallas kernel for the frame-0-KV structure: K/V
        resident in VMEM, query blocks stream, exact full-row softmax. The
        memory-optimal AND compute-optimal inference path. Asked for by
        name on a backend with no Pallas TPU lowering it is an error, not
        a quiet drop to ``chunked`` — a run that "works" must not be the
        XLA fallback (only ``auto`` chooses by backend).
      * "dense" — plain einsum; the small-site (16²/8²) and CPU path.
      * "chunked" — the TRAINING path: exact attention scanned over query
        blocks with ``jax.checkpoint``; the backward pass never materializes
        an N×N probability tensor (dense would need ~2 GB per 64²-site and
        OOMs a 16 GB chip when combined with gradients).
      * "flash" / "flash_rect" — the stock Pallas TPU kernel, with per-frame
        broadcast KV or frames folded into the query length respectively
        (head dims pad to ≤128; otherwise falls back to chunked). Kept for
        comparison; loses to ``fused`` at every measured shape.
    """
    if impl == "auto":
        impl = "fused" if jax.default_backend() == "tpu" else "dense"
    if impl == "dense":
        return None
    if impl not in ("flash", "flash_rect", "chunked", "fused"):
        raise ValueError(f"unknown frame attention impl: {impl!r}")

    def fn(q: jax.Array, k: jax.Array, v: jax.Array) -> jax.Array:
        if q.ndim != 5:
            raise ValueError(
                "frame-attention kernels take q of shape (B, F, H, N, D); "
                f"got rank-{q.ndim} {q.shape}"
            )
        b, f, h, n, d = q.shape
        if n < min_large_tokens:
            return dense_frame_attention(q, k, v)
        if impl == "fused":
            if jax.default_backend() != "tpu":
                raise RuntimeError(
                    "frame attention impl 'fused' is the Pallas TPU kernel "
                    f"and the backend is {jax.default_backend()!r} — use "
                    "'auto' to choose by backend, or 'chunked'/'dense'"
                )
            q_blk = 256
            if (f * n) % q_blk == 0 and d <= 128:
                return fused_frame_attention(q, k, v, q_blk)
            return chunked_frame_attention(q, k, v, q_chunk=q_chunk)
        flash_ok = (d <= 128 or d % 128 == 0) and jax.default_backend() == "tpu"
        if impl == "flash_rect" and flash_ok:
            return flash_rect_frame_attention(q, k, v)
        if impl == "flash" and flash_ok:
            return flash_frame_attention(q, k, v)
        return chunked_frame_attention(q, k, v, q_chunk=q_chunk)

    return fn
