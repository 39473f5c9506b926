"""The chunked state-space scan of the hybrid token models: a Pallas forward /
backward pair.

``models/granite_hybrid.py`` ``ssd_scan`` cuts the recurrence
h_t = exp(dt_t a) h_{t-1} + dt_t x_t (x) b_t, y_t = h_t c_t into chunks of Q
tokens. As XLA every chunk's float32 decay tile (heads, Q, Q) and the float32
chunk states (chunks, H, P, N) — what each chunk leaves, and what it starts
from — make round trips through HBM, in the forward, in the layer's
recompute and in the backward (PERF.md §5). Here the running state of
a block of heads stays in VMEM while the grid walks the chunks in order:

  * **forward** ``lm_ssd_scan`` — per chunk and block of ``hb`` heads: ``C
    Bᵀ`` once, then per head the masked decay tile, the chunk's own outputs
    ``(C Bᵀ ∘ L) (dt x)``, what the state it starts from adds, and the state
    update ``h ← exp(cum_Q) h + (dt x exp(cum_Q − cum))ᵀ B``. Outputs ``y``,
    the last state, and — the one residual — the state each chunk STARTS
    from, float32 (chunks, H, P, N), the ``h_prev`` the XLA path holds too.
  * **backward** ``lm_ssd_scan_bwd`` — the same grid with the chunks in
    reverse, the state's cotangent carried in VMEM; per chunk the decay tile
    is recomputed from ``cum``. Outputs ``dx``, ``d(dt)`` (through ``dt x``),
    ``d(cum)`` and ``dB`` / ``dC``, summed over a group's head blocks in
    their resident output blocks.

**Grid.** ``(groups, chunks, head blocks of a group)``, all ``"arbitrary"``:
a group's chunks in order (in reverse in the backward), inside a chunk its
head blocks, so B / C of a chunk are fetched once and ``dB`` / ``dC`` add up
over the group's heads in VMEM. The forward's running state is its ``last``
output block (a group's heads, resident over the group's walk); the
backward's cotangent a VMEM scratch of the same shape. Head h reads group
h // (H / G) through the index maps.

**Layouts.** ``x`` / ``y`` / ``dx`` as (T, H·P), a block (Q, hb·P): a head's
P channels side by side on the lanes. The per-head ``dt`` and ``cum`` (and
their cotangents) as rows, (H / hb, hb, T): a block is hb dense rows, where
a (Q, hb) column block would be Q copies of hb·4 bytes each; the kernels
transpose the (hb, Q) tile in VMEM for the columns the decay tile
exp(cum_i − cum_j) also takes. XLA makes these views (T·H float32 values,
small) and computes ``cum`` — the within-chunk cumulative sum of dt a —
and, from ``d(cum)``, ``d(dt)`` and ``d(a)`` (the cumulative sum's own
transpose).

**Precision** is the XLA path's, rounding point for rounding point:
log-decays, their sums, states and every accumulation float32; the
operands' dtype only as a matmul operand — ``dt x`` before its products,
``(C Bᵀ ∘ L)``, the state in the ``carried`` product. The backward rounds
where autodiff of the XLA path rounds: the cotangent of an operand in the
operands' dtype; the cotangents of the f32 matmuls enter the MXU in the
operands' dtype, as XLA's default precision on the TPU does. ``dB`` /
``dC`` are summed in float32 and rounded once.
"""

from __future__ import annotations

import functools
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp
from jax import lax

__all__ = ["ssd_scan_kernel", "ssd_scan_plan", "ScanPlan"]

_SUBLANES, _LANES = 8, 128
# heads a grid step: the body is unrolled over them
_HEADS_MAX = 8
# A v5e core has 128 MiB of VMEM; a shape over the budget is refused.
_VMEM_BUDGET = 100 * 1024 * 1024

_NN = (((1,), (0,)), ((), ()))
_NT = (((1,), (1,)), ((), ()))
_TN = (((0,), (0,)), ((), ()))
_F32 = jnp.float32


class ScanPlan(NamedTuple):
    heads: int  # heads a grid step
    fwd_vmem: int  # bytes the forward's grid step holds
    bwd_vmem: int  # bytes the backward's


def _vmem_bytes(heads: int, group_heads: int, width: int, state: int,
                chunk: int, itemsize: int, backward: bool) -> int:
    """VMEM of one grid step, from its shapes: every block twice (the
    pipeline's two buffers), the state of a group's heads, and the values a
    head's body keeps — (Q, Q) float32 tiles, (Q, P) and (P, N) ones."""
    rows = chunk * heads * width
    line = -(-heads // _SUBLANES) * _SUBLANES * chunk * 4  # a (hb, Q) block
    bc = chunk * state * itemsize
    states = heads * width * state * 4
    group = group_heads * width * state * 4
    if backward:
        blocks = (rows * (2 * itemsize + 4) + 4 * line + 2 * bc
                  + 2 * chunk * state * 4 + states + group)
        values = 12 * chunk * chunk * 4 + 12 * chunk * max(width, state) * 4
        return 2 * blocks + group + values + (4 << 20)
    blocks = rows * (itemsize + 4) + 2 * line + 2 * bc + states + group
    values = 8 * chunk * chunk * 4 + 8 * chunk * max(width, state) * 4
    return 2 * blocks + values + (4 << 20)


def ssd_scan_plan(t_len: int, heads: int, width: int, state: int, chunk: int,
                  groups: int, dtype) -> Optional[ScanPlan]:
    """The heads a grid step of the kernel pair takes for ``t_len`` tokens of
    ``heads`` heads ``width`` wide, a state ``state`` wide, chunks of
    ``chunk``, B / C in ``groups`` groups, operands of ``dtype`` — the most
    (up to ``_HEADS_MAX``) of a group's heads whose backward fits the VMEM
    budget with their channels on whole lane tiles — or None where the pair
    does not apply: ``t_len`` not whole chunks, a chunk off the lane tile
    (the rows of ``cum``), a group's state off the lane tile, or no head
    count that fits. The one fit test the model's dispatch and the calls'
    ``vmem_limit_bytes`` share."""
    itemsize = jnp.dtype(dtype).itemsize
    if (itemsize not in (2, 4) or chunk % _LANES or t_len % chunk
            or heads % groups or (groups > 1 and state % _LANES)):
        return None
    group_heads = heads // groups
    for hb in range(min(_HEADS_MAX, group_heads), 0, -1):
        if group_heads % hb or (hb * width % _LANES and hb != heads):
            continue
        need = [_vmem_bytes(hb, group_heads, width, state, chunk, itemsize, bw)
                for bw in (False, True)]
        if max(need) <= _VMEM_BUDGET:
            return ScanPlan(hb, *need)
    return None


# ------------------------------------------------------------ in the kernels


def _lower(q: int):
    return (lax.broadcasted_iota(jnp.int32, (q, q), 0)
            >= lax.broadcasted_iota(jnp.int32, (q, q), 1))


def _head(j: int, x, dt, cum, cumt, lower):
    """Head ``j`` of a block: its columns ``dt x`` (Q, P) float32, its
    ``cum`` as a column (Q, 1), the chunk's end (1, 1), and the masked decay
    tile exp(cum_i − cum_j), j <= i, (Q, Q) float32."""
    width = x.shape[1] // cumt.shape[0]
    q = x.shape[0]
    cum_c, cum_r = cum[:, j:j + 1], cumt[j:j + 1, :]
    xd32 = x[:, j * width:(j + 1) * width] * dt[:, j:j + 1]
    decay = jnp.exp(jnp.where(lower, cum_c - cum_r, -jnp.inf))
    # the last entry of the row, as a lane reduction: a (1, 1) slice off lane
    # 127 is not broadcast to a tile by the chip's compiler
    at_end = lax.broadcasted_iota(jnp.int32, (1, q), 1) == q - 1
    end = jnp.sum(jnp.where(at_end, cum_r, 0.0), axis=1, keepdims=True)
    return xd32, cum_c, end, decay


def _fwd_kernel(x_ref, dtt_ref, cumt_ref, b_ref, c_ref, y_ref, start_ref,
                last_ref, *, heads: int):
    """Group g, chunk i, head block k: the block's outputs of the chunk and
    its state update. ``last_ref`` (a group's heads, resident) is the
    running state; ``start_ref`` gets the state the chunk starts from."""
    from jax.experimental import pallas as pl

    first = pl.multiple_of(pl.program_id(2) * heads, heads)
    at = pl.ds(first, heads)

    @pl.when(pl.program_id(1) == 0)
    def _():
        last_ref[at] = jnp.zeros((heads,) + last_ref.shape[1:], _F32)

    start = last_ref[at]
    start_ref[...] = start
    dtype = x_ref.dtype
    b, c = b_ref[...], c_ref[...]
    cb = lax.dot_general(c, b, _NT, preferred_element_type=_F32)
    x = x_ref[...].astype(_F32)
    cumt = cumt_ref[...]
    dt, cum = dtt_ref[...].T, cumt.T
    width = x.shape[1] // heads
    lower = _lower(cb.shape[0])
    for j in range(heads):
        xd32, cum_c, end, decay = _head(j, x, dt, cum, cumt, lower)
        inside = lax.dot_general((cb * decay).astype(dtype),
                                 xd32.astype(dtype), _NN,
                                 preferred_element_type=_F32)
        h = start[j]
        carried = lax.dot_general(c, h.astype(c.dtype), _NT,
                                  preferred_element_type=_F32)
        y_ref[:, j * width:(j + 1) * width] = inside + carried * jnp.exp(cum_c)
        left = lax.dot_general((xd32 * jnp.exp(end - cum_c)).astype(dtype), b,
                               _TN, preferred_element_type=_F32)
        last_ref[first + j] = jnp.exp(end) * h + left


def _bwd_kernel(x_ref, dtt_ref, cumt_ref, b_ref, c_ref, start_ref, dy_ref,
                dlast_ref, dx_ref, ddt_ref, dcum_ref, db_ref, dc_ref, dh_ref, *,
                heads: int):
    """Group g, chunk i (walked from the last), head block k: the chunk's
    cotangents, autodiff of the XLA path step by step. ``dh_ref`` holds the
    cotangent of the state the chunk LEAVES (the last chunk's: ``dlast``)
    and takes that of the state it starts from; ``db_ref`` / ``dc_ref`` add
    up over the group's head blocks."""
    from jax.experimental import pallas as pl

    k = pl.program_id(2)
    first = pl.multiple_of(k * heads, heads)
    at = pl.ds(first, heads)

    @pl.when(pl.program_id(1) == 0)
    def _():
        dh_ref[at] = dlast_ref[at]

    dtype = x_ref.dtype
    b, c = b_ref[...], c_ref[...]
    cb = lax.dot_general(c, b, _NT, preferred_element_type=_F32)
    q = cb.shape[0]
    x = x_ref[...].astype(_F32)
    cumt = cumt_ref[...]
    dt, cum = dtt_ref[...].T, cumt.T
    start, dy = start_ref[...], dy_ref[...]
    width = x.shape[1] // heads
    lower = _lower(q)
    col = lax.broadcasted_iota(jnp.int32, (q, heads), 1)
    row = lax.broadcasted_iota(jnp.int32, (heads, q), 0)
    at_end = lax.broadcasted_iota(jnp.int32, (1, q), 1) == q - 1
    ds_sum = jnp.zeros((q, q), _F32)
    db = jnp.zeros(b.shape, _F32)
    dc = jnp.zeros(c.shape, _F32)
    ddt = dcum = jnp.zeros((q, heads), _F32)
    dcumt = jnp.zeros((heads, q), _F32)
    for j in range(heads):
        cols = slice(j * width, (j + 1) * width)
        xd32, cum_c, end, decay = _head(j, x, dt, cum, cumt, lower)
        xd = xd32.astype(dtype)
        h, dh_next = start[j], dh_ref[first + j]
        hq = h.astype(c.dtype)
        dyj = dy[:, cols]
        # y = inside + carried * exp(cum)
        e = jnp.exp(cum_c)
        carried = lax.dot_general(c, hq, _NT, preferred_element_type=_F32)
        d_col = jnp.sum(dyj * carried, axis=1, keepdims=True) * e
        dcar = (dyj * e).astype(c.dtype)
        dc = dc + lax.dot_general(dcar, hq, _NN, preferred_element_type=_F32)
        dhq = lax.dot_general(dcar, c, _TN,
                              preferred_element_type=_F32).astype(c.dtype)
        # inside = (C Bᵀ ∘ L) (dt x)
        m = (cb * decay).astype(dtype)
        dyq = dyj.astype(dtype)
        dm = lax.dot_general(dyq, xd, _NT,
                             preferred_element_type=_F32).astype(dtype)
        dxd = lax.dot_general(m, dyq, _TN,
                              preferred_element_type=_F32).astype(dtype)
        dmf = dm.astype(_F32) * decay
        ds_sum = ds_sum + dmf
        dseg = dmf * cb
        d_col = d_col + jnp.sum(dseg, axis=1, keepdims=True)
        d_row = -jnp.sum(dseg, axis=0, keepdims=True)
        # h' = exp(cum_Q) h + ((dt x) exp(cum_Q − cum))ᵀ B
        el, te = jnp.exp(end), jnp.exp(end - cum_c)
        dleft = dh_next.astype(b.dtype)
        u = (xd32 * te).astype(dtype)
        du = lax.dot_general(b, dleft, _NT,
                             preferred_element_type=_F32).astype(dtype)
        db = db + lax.dot_general(u, dleft, _NN, preferred_element_type=_F32)
        duf = du.astype(_F32)
        dte = jnp.sum(duf * xd32, axis=1, keepdims=True) * te
        d_col = d_col - dte
        d_end = (jnp.sum(dte, axis=0, keepdims=True)
                 + jnp.sum(jnp.sum(h * dh_next, axis=1, keepdims=True),
                           axis=0, keepdims=True) * el)
        d_row = d_row + jnp.where(at_end, d_end, 0.0)
        # dt x = x * dt
        dxd32 = duf * te + dxd.astype(_F32)
        dx_ref[:, cols] = (dxd32 * dt[:, j:j + 1]).astype(dx_ref.dtype)
        ddt = jnp.where(col == j, jnp.sum(dxd32 * x[:, cols], axis=1,
                                          keepdims=True), ddt)
        dcum = jnp.where(col == j, d_col, dcum)
        dcumt = jnp.where(row == j, d_row, dcumt)
        dh_ref[first + j] = el * dh_next + dhq.astype(_F32)
    ddt_ref[...] = ddt.T
    dcum_ref[...] = dcum.T + dcumt
    dsq = ds_sum.astype(dtype)
    dc = dc + lax.dot_general(dsq, b, _NN, preferred_element_type=_F32)
    db = db + lax.dot_general(dsq, c, _TN, preferred_element_type=_F32)

    @pl.when(k == 0)
    def _():
        db_ref[...] = db
        dc_ref[...] = dc

    @pl.when(k > 0)
    def _():
        db_ref[...] += db
        dc_ref[...] += dc


# ----------------------------------------------------------------- the calls


def _specs(n_chunks: int, chunk: int, heads: int, width: int, state: int,
           per_group: int, backward: bool):
    """The block specs of the operands both kernels read, in their order
    (``x``, ``dtᵀ``, ``cumᵀ``, B, C), the index maps they are made of, and
    the chunk a grid step works on."""
    from jax.experimental import pallas as pl

    def at(i):
        return n_chunks - 1 - i if backward else i

    def rows(g, i, k):
        return at(i), g * per_group + k

    def lines(g, i, k):
        return g * per_group + k, 0, at(i)

    def group(g, i, k):
        return at(i), g

    line = pl.BlockSpec((None, heads, chunk), lines)
    return ([pl.BlockSpec((chunk, heads * width), rows), line, line,
             pl.BlockSpec((chunk, state), group),
             pl.BlockSpec((chunk, state), group)],
            rows, lines, group, at)


# The kernels' calls are jitted apart: a layer's pallas_call is traced and
# lowered once a program, not once a layer (Granite's cell has nine).
@functools.partial(jax.jit, static_argnames=("groups", "chunk", "plan",
                                             "interpret"))
def _forward_call(x, dt, cum, b, c, *, groups: int, chunk: int,
                  plan: ScanPlan, interpret):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    t_len = x.shape[0]
    n_blocks, hb, _ = dt.shape
    heads = n_blocks * hb
    width, state = x.shape[1] // heads, b.shape[1] // groups
    per_group, n_chunks = n_blocks // groups, t_len // chunk
    in_specs, rows, _, _, _ = _specs(n_chunks, chunk, hb, width, state,
                                     per_group, False)
    return pl.pallas_call(
        functools.partial(_fwd_kernel, heads=hb),
        # explicit name: the compiled program's custom call and the trace
        # events carry it (obs/introspect.tpu_custom_call_counts)
        name="lm_ssd_scan",
        grid=(groups, n_chunks, per_group),
        in_specs=in_specs,
        out_specs=[
            pl.BlockSpec((chunk, hb * width), rows),
            pl.BlockSpec((None, hb, width, state),
                         lambda g, i, k: (i, g * per_group + k, 0, 0)),
            pl.BlockSpec((heads // groups, width, state),
                         lambda g, i, k: (g, 0, 0)),
        ],
        out_shape=[jax.ShapeDtypeStruct((t_len, heads * width), _F32),
                   jax.ShapeDtypeStruct((n_chunks, heads, width, state), _F32),
                   jax.ShapeDtypeStruct((heads, width, state), _F32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary", "arbitrary"),
            vmem_limit_bytes=plan.fwd_vmem,
        ),
        interpret=interpret,  # CPU-testable (tests/test_ssd_scan_kernel.py)
    )(x, dt, cum, b, c)


@functools.partial(jax.jit, static_argnames=("groups", "chunk", "plan",
                                             "interpret"))
def _backward_call(x, dt, cum, b, c, starts, dy, dlast, *, groups: int,
                   chunk: int, plan: ScanPlan, interpret):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    t_len = x.shape[0]
    n_blocks, hb, _ = dt.shape
    heads = n_blocks * hb
    width, state = x.shape[1] // heads, b.shape[1] // groups
    per_group, n_chunks = n_blocks // groups, t_len // chunk
    in_specs, rows, lines, group, at = _specs(
        n_chunks, chunk, hb, width, state, per_group, True)
    whole = pl.BlockSpec((heads // groups, width, state),
                         lambda g, i, k: (g, 0, 0))
    return pl.pallas_call(
        functools.partial(_bwd_kernel, heads=hb),
        name="lm_ssd_scan_bwd",
        grid=(groups, n_chunks, per_group),
        in_specs=in_specs + [
            pl.BlockSpec((None, hb, width, state),
                         lambda g, i, k: (at(i), g * per_group + k, 0, 0)),
            pl.BlockSpec((chunk, hb * width), rows), whole],
        out_specs=[pl.BlockSpec((chunk, hb * width), rows),
                   pl.BlockSpec((None, hb, chunk), lines),
                   pl.BlockSpec((None, hb, chunk), lines),
                   pl.BlockSpec((chunk, state), group),
                   pl.BlockSpec((chunk, state), group)],
        out_shape=[jax.ShapeDtypeStruct(x.shape, x.dtype),
                   jax.ShapeDtypeStruct(dt.shape, _F32),
                   jax.ShapeDtypeStruct(dt.shape, _F32),
                   jax.ShapeDtypeStruct(b.shape, _F32),
                   jax.ShapeDtypeStruct(c.shape, _F32)],
        scratch_shapes=[pltpu.VMEM((heads // groups, width, state), _F32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary", "arbitrary"),
            vmem_limit_bytes=plan.bwd_vmem,
        ),
        interpret=interpret,
    )(x, dt, cum, b, c, starts, dy, dlast)


def _lines(v, hb: int):
    """(T, H) → (H / hb, hb, T): a head block's rows."""
    t_len, heads = v.shape
    return v.T.reshape(heads // hb, hb, t_len)


def _from_lines(v):
    return v.reshape(-1, v.shape[-1]).T


def _operands(x, dt, cum, plan: ScanPlan):
    return x, _lines(dt, plan.heads), _lines(cum, plan.heads)


@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6, 7, 8))
def _scan(x, dt, cum, b, c, groups, chunk, plan, interpret):
    """``(y, last, start)``: the outputs (T, H·P) float32, the last state,
    and the state the last chunk starts from (a reading: its cotangent is
    not taken)."""
    y, starts, last = _forward_call(*_operands(x, dt, cum, plan), b, c,
                                    groups=groups, chunk=chunk, plan=plan,
                                    interpret=interpret)
    return y, last, starts[-1]


def _scan_fwd(x, dt, cum, b, c, groups, chunk, plan, interpret):
    y, starts, last = _forward_call(*_operands(x, dt, cum, plan), b, c,
                                    groups=groups, chunk=chunk, plan=plan,
                                    interpret=interpret)
    return (y, last, starts[-1]), (x, dt, cum, b, c, starts)


def _scan_bwd(groups, chunk, plan, interpret, res, cot):
    x, dt, cum, b, c, starts = res
    dy, dlast, _ = cot
    dx, ddt, dcum, db, dc = _backward_call(
        *_operands(x, dt, cum, plan), b, c, starts, dy, dlast, groups=groups,
        chunk=chunk, plan=plan, interpret=interpret)
    return (dx, _from_lines(ddt), _from_lines(dcum), db.astype(b.dtype),
            dc.astype(c.dtype))


_scan.defvjp(_scan_fwd, _scan_bwd)


def ssd_scan_kernel(x, dt, a, b, c, chunk: int, interpret=False):
    """``models.granite_hybrid.ssd_scan`` on the kernel pair: ``x`` (T, H,
    P), ``dt`` (T, H) float32, ``a`` (H,) float32, ``b``, ``c`` (T, N) or
    (T, G, N) → ``y`` (T, H, P) float32, the last state (H, P, N) float32
    and the mean square, over the last chunk's outputs, of the term the
    state handed to that chunk adds (cut off from the gradient). Raises
    where :func:`ssd_scan_plan` refuses the shape."""
    t_len, heads, width = x.shape
    groups = 1 if b.ndim == 2 else b.shape[1]
    plan = ssd_scan_plan(t_len, heads, width, b.shape[-1], chunk, groups,
                         x.dtype)
    if plan is None:
        raise ValueError(
            f"the scan kernels do not apply to x {x.shape} {x.dtype}, B "
            f"{b.shape}, chunks of {chunk}: ask ssd_scan_plan first and keep "
            "the XLA scan where it returns None")
    n_chunks = t_len // chunk
    cum = jnp.cumsum((dt * a[None, :]).reshape(n_chunks, chunk, heads),
                     axis=1).reshape(t_len, heads)
    y, last, start = _scan(x.reshape(t_len, heads * width), dt, cum,
                           b.reshape(t_len, -1), c.reshape(t_len, -1),
                           groups, chunk, plan, interpret)
    # what the state handed to the last chunk adds to its outputs, as
    # granite_hybrid._chunk_outputs reads it
    c_last = c[-chunk:].reshape(chunk, groups, -1)
    start = lax.stop_gradient(start).reshape(groups, heads // groups, width, -1)
    carried = jnp.einsum("ign,ghpn->ighp", c_last, start.astype(c.dtype),
                         preferred_element_type=_F32).reshape(chunk, heads, width)
    handed = carried * jnp.exp(lax.stop_gradient(cum[-chunk:]))[..., None]
    return (y.reshape(t_len, heads, width), last,
            lax.stop_gradient(jnp.mean(jnp.square(handed))))
