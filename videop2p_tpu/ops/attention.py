"""Frame-attention kernels: a Pallas forward / backward pair on TPU, chunked fallback.

The spatial frame attention (every frame's queries against frame-0 keys,
/root/reference/tuneavideo/models/attention.py:296-302) is the framework's
hw×hw hot op: at 64×64 latents it is a 4096×4096 attention per frame per
head — materialized, that is ~2 GB of probabilities in bf16 and the single
reason the reference needs xformers (SURVEY §2.1 #7). Implementations behind
one dispatch (what each costs on the chip is in PERF.md §5–§6, nowhere else):

  * **fused** — custom Pallas kernels for the frame-0-KV structure: K/V sit
    resident in VMEM (N·D ≈ 320 KB each) while query blocks stream through
    with an exact full-row softmax; no score ever reaches HBM. Forward
    ``fused_frame_attention``, backward ``fused_frame_attention_bwd`` (dQ, and
    dK / dV accumulated in float32 in VMEM across query blocks and frames).
    The TPU default ("auto") for inference AND for programs that
    differentiate through the UNet (Stage-1 tuning, null-text inversion).
  * **dense** — plain einsum: the CPU path and the small-site (16²/8²)
    fallback, where the score matrix is tiny and XLA fuses it fine.
  * **chunked** — exact attention scanned over query blocks with
    ``jax.checkpoint``, bounding peak memory on any backend: the training
    path OFF the TPU, the backward of a shape the kernel's VMEM fit test
    refuses, and the sharded-mesh path (pjit cannot partition a Pallas
    custom call). On the chip it writes every score chunk to HBM several
    times over, which is why the tune left it (PERF.md §6, PR 27).

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
    "fused_frame_attention",
    "fused_bwd_block",
    "make_frame_attention_fn",
    "training_frame_attention",
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
    (3.2 GB per 64²-site instance at the edit batch).
    Here K/V for one (batch, head) are tiny — N·D ≈ 320 KB each — so they sit
    resident in VMEM while query blocks stream through: one QKᵀ, an exact
    full-row softmax (no online accumulation needed), one PV, nothing but
    q/out ever touching HBM. Frames fold into the query length (softmax is
    per-row, so the fold is exact), giving long M = F·N grids that also cover
    the 24/32-frame long-video shapes without the chunked path's lax.map
    overhead.

    Differentiation runs the backward kernel of the same structure
    (:func:`_fused_bwd_kernel`; residuals are just ``(q, k, v)``) where
    :func:`fused_bwd_block` finds a block that fits VMEM, and the vjp of
    :func:`chunked_frame_attention` (memory-bounded, exact) otherwise.
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


# The backward kernel's VMEM: one 64²-site block is over the default scoped
# limit (16 MiB), so the call asks for what the arithmetic below says, and a
# shape whose smallest block is over the budget goes to the chunked backward.
# A v5e core has 128 MiB of VMEM; the compiler's own count at the tune's
# shapes is about 0.7 of this one (tests/test_tpu_compile.py compiles them).
# The largest block that fits is taken: at N = 4096 the kernel read 13.99 /
# 8.23 / 7.32 ms at 256 / 512 / 1024 on the v5e (PERF.md §6, PR 27) — the
# dK / dV read-modify-write is paid once per block — and at N = 1024 0.56 /
# 0.48 ms at 512 / 1024.
_BWD_BLOCKS = (1024, 512, 256, 128)
_BWD_VMEM_BUDGET = 96 * 1024 * 1024


def _fused_bwd_vmem_bytes(n: int, d: int, itemsize: int, blk: int) -> int:
    """VMEM the backward kernel holds for one grid cell, from its shapes."""
    lanes = -(-d // 128) * 128  # a (rows, d) block pads its last axis to 128 lanes
    # (N, blk) tiles: Pᵀ, dPᵀ, dSᵀ in float32, Pᵀ and dSᵀ again as operands
    tiles = n * blk * (3 * 4 + 2 * itemsize)
    kv = 2 * (2 * n * lanes + max(d, 16) * n) * itemsize  # K, V, Kᵀ, double-buffered
    acc = 2 * 2 * n * lanes * 4  # dK, dV float32, double-buffered
    streams = 2 * (2 * blk * lanes * itemsize + -(-d // 8) * 8 * blk * 4)  # q, dO, dQᵀ
    return tiles + kv + acc + streams


def fused_bwd_block(m: int, n: int, d: int, dtype) -> Optional[int]:
    """Query block of the backward kernel for q (·, m, d) against k/v (·, n, d):
    the largest that divides ``m`` and whose VMEM fits the budget; None where
    none does (the caller differentiates the chunked code instead). The one
    fit test the dispatch and the kernel call share."""
    if n % 128 != 0 or d > 128:  # the (N, blk) tile's sublanes; one lane tile of D
        return None
    itemsize = jnp.dtype(dtype).itemsize
    for blk in _BWD_BLOCKS:
        if (m % blk == 0
                and _fused_bwd_vmem_bytes(n, d, itemsize, blk) <= _BWD_VMEM_BUDGET):
            return blk
    return None


def _fused_bwd_kernel(q_ref, do_ref, k_ref, kt_ref, v_ref,
                      dqt_ref, dk_ref, dv_ref, *, scale: float):
    """One grid cell: a query block's share of dQ, dK and dV against the whole
    (VMEM-resident) frame-0 K/V. The score tile is held TRANSPOSED, (N, blk):
    every product is then a plain or a transposed-RHS matmul (no transpose of
    a tile), the softmax reduces over sublanes, and dQ comes out as dQᵀ from
    Kᵀ·dSᵀ. The full row is present, so the probabilities are recomputed as
    the forward computes them and neither O nor a log-sum-exp is needed.
    dK / dV accumulate in float32 in their output blocks, which stay resident
    along the inner grid axis — frames are folded into the query length, so
    this also sums them over frames. ``scale`` on dQ and dK is the caller's."""
    import jax.lax as lax
    from jax.experimental import pallas as pl

    @pl.when(pl.program_id(1) == 0)
    def _():
        dk_ref[...] = jnp.zeros_like(dk_ref)
        dv_ref[...] = jnp.zeros_like(dv_ref)

    q = q_ref[0]  # (blk, D)
    do = do_ref[0]  # (blk, D)
    k = k_ref[0]  # (N, D)
    v = v_ref[0]  # (N, D)
    nt = (((1,), (1,)), ((), ()))
    nn = (((1,), (0,)), ((), ()))
    st = lax.dot_general(k, q, nt, preferred_element_type=jnp.float32) * scale
    m = jnp.max(st, axis=0, keepdims=True)  # (1, blk)
    e = jnp.exp(st - m)
    pt = e * (1.0 / jnp.sum(e, axis=0, keepdims=True))  # Pᵀ (N, blk) f32
    dv_ref[0] += lax.dot_general(
        pt.astype(do.dtype), do, nn, preferred_element_type=jnp.float32)
    dpt = lax.dot_general(v, do, nt, preferred_element_type=jnp.float32)
    delta = jnp.sum(pt * dpt, axis=0, keepdims=True)  # rowsum(P ∘ dP), (1, blk)
    dst = (pt * (dpt - delta)).astype(q.dtype)  # dSᵀ, rounded as an operand only
    dk_ref[0] += lax.dot_general(dst, q, nn, preferred_element_type=jnp.float32)
    dqt_ref[0] = lax.dot_general(
        kt_ref[0], dst, nn, preferred_element_type=jnp.float32)  # (D, blk)


def _fused_rect_bwd(q3: jax.Array, do3: jax.Array, k: jax.Array, v: jax.Array,
                    blk: int, interpret: bool = False):
    """q3, do3 (BH, M, D); k, v (BH, N, D) → dQ (BH, M, D), dK, dV (BH, N, D),
    all float32 (the caller rounds them to the operands' dtype)."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    bh, m, d = q3.shape
    n = k.shape[1]
    scale = d ** -0.5
    stream = pl.BlockSpec((1, blk, d), lambda b, i: (b, i, 0))
    resident = pl.BlockSpec((1, n, d), lambda b, i: (b, 0, 0))
    dqt, dk, dv = pl.pallas_call(
        functools.partial(_fused_bwd_kernel, scale=scale),
        name="fused_frame_attention_bwd",
        out_shape=(
            jax.ShapeDtypeStruct((bh, d, m), jnp.float32),
            jax.ShapeDtypeStruct((bh, n, d), jnp.float32),
            jax.ShapeDtypeStruct((bh, n, d), jnp.float32),
        ),
        grid=(bh, m // blk),
        in_specs=[
            stream, stream, resident,
            pl.BlockSpec((1, d, n), lambda b, i: (b, 0, 0)),
            resident,
        ],
        out_specs=(
            pl.BlockSpec((1, d, blk), lambda b, i: (b, 0, i)),
            # constant along the inner axis → resident accumulators, written
            # back once per (b, h); that axis must then run in order
            resident, resident,
        ),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
            vmem_limit_bytes=_fused_bwd_vmem_bytes(
                n, d, q3.dtype.itemsize, blk),
        ),
        interpret=interpret,
    )(q3, do3, k, k.transpose(0, 2, 1), v)
    return dqt.transpose(0, 2, 1) * scale, dk * scale, dv


def _fused_bwd(q_blk, interpret, res, g):
    q, k, v = res
    b, f, h, n, d = q.shape
    blk = fused_bwd_block(f * n, n, d, q.dtype)
    if blk is None:
        _, vjp = jax.vjp(chunked_frame_attention, q, k, v)
        return vjp(g)
    with jax.named_scope(_SCOPE):
        def fold(x):
            return x.transpose(0, 2, 1, 3, 4).reshape(b * h, f * n, d)

        dq, dk, dv = _fused_rect_bwd(
            fold(q), fold(g), k.reshape(b * h, n, d),
            v.reshape(b * h, n, d), blk, interpret,
        )
        dq = dq.reshape(b, h, f, n, d).transpose(0, 2, 1, 3, 4)
        return (dq.astype(q.dtype), dk.reshape(k.shape).astype(k.dtype),
                dv.reshape(v.shape).astype(v.dtype))


fused_frame_attention.defvjp(_fused_fwd, _fused_bwd)


def training_frame_attention() -> str:
    """The ``impl`` of a program that differentiates through the large
    sites, chosen from the backend: ``"auto"`` on the TPU — the kernel pair
    where :func:`make_frame_attention_fn`'s rules and the backward's VMEM fit
    test pass — and ``"chunked"`` elsewhere, whose bounded backward memory is
    the reason it exists (``auto``'s choice off the TPU is ``dense``)."""
    return "auto" if jax.default_backend() == "tpu" else "chunked"


def make_frame_attention_fn(
    impl: str = "auto",
    *,
    min_large_tokens: int = 1024,
    q_chunk: int = 512,
) -> Optional[FrameAttentionFn]:
    """Dispatching frame-attention implementation.

    ``impl``:
      * "auto" — ``fused`` on TPU, ``dense`` elsewhere (None → the
        module-inline einsum): the XLA dense path materializes the bf16
        score tensor in HBM, the ``fused`` kernels keep it in VMEM, forward
        and backward.
      * "fused" — custom Pallas kernels for the frame-0-KV structure: K/V
        resident in VMEM, query blocks stream, exact full-row softmax, and a
        backward of the same structure. Asked for by name on a backend with
        no Pallas TPU lowering it is an error, not a quiet drop to
        ``chunked`` — a run that "works" must not be the XLA fallback (only
        ``auto`` chooses by backend).
      * "dense" — plain einsum; the small-site (16²/8²) and CPU path.
      * "chunked" — exact attention scanned over query blocks with
        ``jax.checkpoint``; the backward pass never materializes an N×N
        probability tensor (dense would need ~2 GB per 64²-site and OOMs a
        16 GB chip when combined with gradients). What training takes off
        the TPU (:func:`training_frame_attention`) and what a mesh takes
        where GSPMD partitions the op.
    """
    if impl == "auto":
        impl = "fused" if jax.default_backend() == "tpu" else "dense"
    if impl == "dense":
        return None
    if impl not in ("chunked", "fused"):
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
        return chunked_frame_attention(q, k, v, q_chunk=q_chunk)

    return fn
