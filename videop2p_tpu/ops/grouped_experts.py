"""The grouped expert loop of the token models: a Pallas forward / backward pair.

``models/deepseek.py`` ``held_expert_ffn`` sorts the (token, expert) rows by
expert and cuts them into blocks of one expert each (``expert_blocks``:
``tbl``, ``tok``, ``gate``); ``_grouped_swiglu`` runs a loop of ``tbl["n"]``
blocks over that table. As XLA every block gathers its rows (``x[t]``) and
scatter-adds its float32 result (``y.at[t].add``) through one custom fusion
each, the scatter-add a serial read-add-write at 435 ns a row (PERF.md §6,
PR 33). Here the rows move by DMA, the accumulator's at 90 ns a row there
and back, and the matrices stay in VMEM while an expert's blocks pass:

  * **forward** ``lm_grouped_experts`` — per block: the rows ``x[tok[r]]``
    HBM → VMEM, one copy a valid row; the three products on the VMEM block;
    ``y[tok[r]] += gate[r] * out[r]`` on the float32 accumulator IN PLACE
    (rows in, add, rows out).
  * **backward** ``lm_grouped_experts_bwd`` — the same with ``x`` and ``dy``
    in and ``dx`` accumulated; the block is recomputed, as the XLA loop
    recomputes it; ``dgate`` rows are contiguous in the sorted order, so a
    block's are merged into their place, not scattered.

**How a row travels.** The chip's DMA moves whole (8, 128) tiles of 32-bit
words, and one row of a ``(T, hidden)`` array is a one-sublane sliver of
``hidden / 128`` tiles (the compiler refuses the slice). So both kernels see
the row arrays as ``(T * R, 128)`` 32-bit words, the plain row-major reshape:
token ``t`` is the ``R`` sublanes from ``R t``, whole tiles in one piece. A
block in VMEM is ``(block * R, 128)``; lane tile ``c`` of all its rows is one
strided read (every ``R``-th sublane from ``c``). Two row forms, ``R`` a
stream:

  * **float32** (``R = hidden / 128``): sublane ``c`` holds columns
    ``128 c ..``; the tiles side by side are the ``(block, hidden)`` operand.
    The accumulators ``y`` / ``dx`` always, and ``x`` / ``dy`` widened
    (exact) where the packed form does not apply.
  * **packed** bfloat16 ``x`` / ``dy`` where ``hidden`` is a multiple of
    2048 (``R = hidden / 256``, a row of whole tiles): a uint32 word holds
    column ``j`` in its low half and ``j + hidden / 2`` in its high half, so
    the low halves of the tiles side by side, then the high halves, are the
    columns in their order. A bfloat16 is the high half of the float32 of
    the same value: each half shifted there is its value, exactly. Half the
    bytes of the float32 form a row.

The callers make the views and undo the accumulators' with the cast the XLA
loop ends on.

**What flies when, and what it costs.** A row copy costs its bytes — 45 ns
a 16 KB row with one stream in flight, 30 ns with three (370–560 GB/s, my
chip runs, PR 33) — not its descriptor, the chip works copies off in the
order they were started, and STARTING one waits while the few that may be
on their way are: a block's worth of starts holds the core for about as
long as the copies take, whether they are started in one go or a few rows
at a time between pieces of the products (measured both ways). So the
copies do not hide behind the products; the kernels only see to it that
nothing is waited for twice: a block starts the next block's ``x`` (and
``dy``) rows into a second buffer before its own products and asks for the
``tok`` / ``gate`` group of the block after that, and every wait is for
something started a block's products ago — but for the accumulator's rows:
they go out at a block's end, are waited for, and only then the next block's
come in (below).

**Why the in-place accumulation is sound.** (a) The grid runs the blocks in
order on the chip's one core (``"arbitrary"`` semantics), and a block's
accumulator rows have landed in HBM before the next block's are read: a
token under two held experts is updated twice in sequence, never at once.
(b) Inside a block the tokens are distinct: the sort is stable by expert and
a token picks an expert at most once, so no two rows of a block read and
write the same slab (``tests/test_grouped_experts.py`` holds this for the
routing ``held_expert_ffn`` builds). Rows past ``end[b]`` are moved nowhere.

**Grid.** ``(len(tbl["expert"]), inner / tile)``: the table's static length
over a dynamic count — steps with ``b >= tbl["n"]`` do nothing and their
index maps are clamped to the last live block, so nothing is fetched for
them. The stacked matrices are indexed by ``tbl["expert"][b]`` in the index
map: consecutive blocks of one expert re-use the resident tile where the
whole expert fits VMEM; where it does not, the inner dimension is tiled on
the second axis and the row buffers accumulate over it.

Precision as the XLA loop: operands in the matrices' dtype, float32
accumulation in every product, ``silu`` in float32, the sum over a token's
experts in float32 in ascending expert order. No row is dropped, no block of
padding computed.
"""

from __future__ import annotations

import functools
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp
from jax import lax

__all__ = ["grouped_experts_forward", "grouped_experts_backward",
           "grouped_expert_tiles", "ExpertTiles"]

_SUBLANES, _LANES = 8, 128
# tok and gate are read, and dgate merged, as aligned groups of 8 x 128
# sorted rows: a block's rows start anywhere inside the first group
_GROUP = _SUBLANES * _LANES
# A v5e core has 128 MiB of VMEM; a shape over the budget at the narrowest
# inner tile is refused.
_VMEM_BUDGET = 100 * 1024 * 1024
# row copies started a trip of the issuing loop
_ISSUE_UNROLL = 8
# bfloat16 rows travel packed two to a word where a row is whole (8, 128)
# tiles of words: hidden a multiple of 2 x 8 x 128
_PACKED_WIDTH = 2 * _SUBLANES * _LANES

_NN = (((1,), (0,)), ((), ()))
_NT = (((1,), (1,)), ((), ()))


class ExpertTiles(NamedTuple):
    inner: int  # columns of the inner dimension a grid step
    fwd_vmem: int  # bytes the forward's grid step holds
    bwd_vmem: int  # bytes the backward's
    packed: bool  # x and dy rows as bfloat16 pairs in uint32 words


def _group_rows(block: int) -> int:
    """Rows of the (rows / 128, 128) views that hold any block's sorted rows:
    whole groups of eight from the group its first row lies in."""
    return _SUBLANES * (-(-(_GROUP - 1 + block) // _GROUP))


def _vmem_bytes(hidden: int, tile: int, block: int, itemsize: int,
                backward: bool, packed: bool = False) -> int:
    """VMEM of one grid step, from its shapes: over what the chip's compiler
    asks for by 3–12 MiB at both cells' shapes and blocks of 256–512 (found
    by halving the limit until it refused, PERF.md §6, PR 33)."""
    weights = 2 * 3 * hidden * tile * itemsize  # three matrices, two buffers
    slab = block * hidden * 4
    # x (and dy) for this block and the next, the accumulator's rows
    rows = (4 if backward else 2) * (slab // 2 if packed else slab) + slab
    # the operand block(s) and the float32 (block, hidden) products as the
    # compiler keeps them: a slab and a half, two in the backward
    values = (4 if backward else 3) * slab // 2 + (2 << 20)
    return weights + rows + values


def grouped_expert_tiles(rows: int, hidden: int, inner: int, block: int,
                         dtype, x_dtype=None) -> Optional[ExpertTiles]:
    """The inner-dimension tile of the kernel pair for ``rows`` sorted
    (token, expert) rows of ``hidden`` columns, experts ``inner`` wide, blocks
    of ``block`` rows, matrices of ``dtype``, ``x`` (and ``dy``) of
    ``x_dtype`` (the matrices' where None) — the widest tile of ``inner``
    (the whole of it first) whose backward fits the VMEM budget, and the rows'
    form: packed where ``x`` is bfloat16 and ``hidden`` a multiple of 2048 —
    or None where the pair does not apply: ``hidden`` not eight lane tiles a
    row, ``inner`` off the lane tiles, ``block`` off the operand's sublane
    tile, ``rows`` not whole groups of 1024, or no tile that fits. The one
    fit test the model's dispatch and the calls' ``vmem_limit_bytes``
    share."""
    itemsize = jnp.dtype(dtype).itemsize
    if (hidden % (_SUBLANES * _LANES) or inner % _LANES or itemsize not in (2, 4)
            or block % (_SUBLANES * 4 // itemsize) or rows % _GROUP
            or rows // _LANES < _group_rows(block)):
        return None
    packed = (jnp.dtype(dtype if x_dtype is None else x_dtype) == jnp.bfloat16
              and hidden % _PACKED_WIDTH == 0)
    for tile in sorted({inner} | {t for t in (1024, 512, 256, 128)
                                  if inner % t == 0}, reverse=True):
        need = [_vmem_bytes(hidden, tile, block, itemsize, bw, packed)
                for bw in (False, True)]
        if max(need) <= _VMEM_BUDGET:
            return ExpertTiles(tile, *need, packed)
    return None


def _tiles_of(x, tok, wg, block) -> ExpertTiles:
    tiles = grouped_expert_tiles(tok.shape[0], x.shape[1], wg.shape[2], block,
                                 wg.dtype, x.dtype)
    if tiles is None:
        raise ValueError(
            f"the grouped expert kernels do not apply to x {x.shape}, "
            f"{tok.shape[0]} rows, matrices {wg.shape} {wg.dtype}, blocks of "
            f"{block}: ask grouped_expert_tiles first and keep the XLA loop "
            "where it returns None")
    return tiles


# ------------------------------------------------------------ in the kernels


def _slab(i, per_row: int):
    """The ``per_row`` sublanes of row ``i`` of a (rows * per_row, 128)
    view."""
    from jax.experimental import pallas as pl

    return pl.ds(pl.multiple_of(i * per_row, _SUBLANES), per_row)


def _start_rows(valid, tok_s, place, block: int, copies):
    """One copy a valid row and ``(hbm, vmem, sem, to_hbm)`` of ``copies``,
    all in flight: HBM slab ``tok[r]`` ↔ VMEM slab ``r``. The issuing loop
    runs ``_ISSUE_UNROLL`` rows a trip, and the rows left over one by one."""
    from jax.experimental.pallas import tpu as pltpu

    def row(r):
        t = tok_s[lax.div(place + r, _LANES), lax.rem(place + r, _LANES)]
        for hbm, vmem, sem, to_hbm in copies:
            per_row = vmem.shape[0] // block  # the stream's own row form
            src, dst = hbm.at[_slab(t, per_row)], vmem.at[_slab(r, per_row)]
            if to_hbm:
                src, dst = dst, src
            pltpu.make_async_copy(src, dst, sem).start()

    def trip(i, c):
        for k in range(_ISSUE_UNROLL):
            row(i * _ISSUE_UNROLL + k)
        return c

    def one(r, c):
        row(r)
        return c

    trips = lax.div(valid, _ISSUE_UNROLL)
    lax.fori_loop(0, trips, trip, 0)
    lax.fori_loop(trips * _ISSUE_UNROLL, valid, one, 0)


def _wait_rows(valid, vmem, sem, block: int):
    """Wait for the ``valid`` row copies that signal ``sem``. A DMA semaphore
    counts what arrived, so the wait is for the rows' sum in a few pieces (one
    a set bit of ``valid``), whatever single copies made it up."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    per_row = vmem.shape[0] // block
    bit = 1 << (block.bit_length() - 1)
    while bit:
        def wait(bit=bit):
            part = vmem.at[pl.ds(0, bit * per_row)]
            pltpu.make_async_copy(part, part, sem).wait()

        pl.when((valid & bit) != 0)(wait)
        bit >>= 1


def _column(group, place, block: int):
    """(block, 1): entry ``r`` is the flat ``group`` (rows, 128) at
    ``place + r`` — a lane-major stretch stood up as a column, exactly (one
    term of each sum is not zero)."""
    at = (place + lax.broadcasted_iota(jnp.int32, (block, _LANES), 0)
          - lax.broadcasted_iota(jnp.int32, (block, _LANES), 1))
    acc = jnp.zeros((block, _LANES), group.dtype)
    for i in range(group.shape[0]):
        acc = acc + jnp.where(at == i * _LANES, group[i:i + 1, :], 0)
    return jnp.sum(acc, axis=1, keepdims=True)


def _block_value(buf, block: int, dtype):
    """The (block, hidden) block a slab buffer holds, in ``dtype``: lane tile
    ``c`` of every row is every ``R``-th sublane from ``c``; a packed
    buffer's words give the low halves' columns, then the high halves'."""
    from jax.experimental import pallas as pl

    per_row = buf.shape[0] // block

    def tile(c):
        return buf[pl.ds(c, block, stride=per_row), :]

    if buf.dtype != jnp.uint32:
        return jnp.concatenate([tile(c).astype(dtype) for c in range(per_row)],
                               axis=1)
    words = [tile(c) for c in range(per_row)]
    halves = ([w << 16 for w in words]
              + [w & jnp.uint32(0xFFFF0000) for w in words])
    return jnp.concatenate(
        [lax.bitcast_convert_type(h, jnp.float32).astype(dtype) for h in halves],
        axis=1)


def _add_to_block(buf, value, block: int):
    """``buf`` (a slab buffer) += ``value`` (block, hidden) float32."""
    from jax.experimental import pallas as pl

    per_row = buf.shape[0] // block
    for c in range(per_row):
        at = pl.ds(c, block, stride=per_row)
        buf[at, :] = buf[at, :] + value[:, c * _LANES:(c + 1) * _LANES]


class _Walk:
    """Where grid step ``(b, j)`` stands in the table, and the copies both
    kernels make the same way: the aligned group of ``tok`` / ``gate`` a
    block's rows lie in, and the row streams. The chip works its copies off
    in the order they were started, so nothing is waited for that was started
    behind a block's worth of rows: the group of block ``b + 2`` is asked for
    at block ``b``'s first tile and waited for at its last, behind copies that
    had the block's products to finish in. ``sems`` rows: 0 the groups, 1..
    the streams, each ``[slot]``."""

    def __init__(self, table, tok_hbm, gate_hbm, tok_s, gate_v, sems,
                 block: int):
        from jax.experimental import pallas as pl

        self.expert, self.first_row, self.end, n_ref = table
        self.tok_hbm, self.gate_hbm = tok_hbm, gate_hbm
        self.tok_s, self.gate_v, self.sems = tok_s, gate_v, sems
        self.block, self.group_rows = block, tok_s.shape[1]
        b, self.j = pl.program_id(0), pl.program_id(1)
        self.b, self.n = b, n_ref[0]
        self.last_j = pl.num_programs(1) - 1
        # x (and dy) alternate between two buffers, the groups between
        # three; the accumulator's rows have one
        self.slot, self.next_slot = lax.rem(b, 2), lax.rem(b + 1, 2)
        self.has_next, self.has_third = b + 1 < self.n, b + 2 < self.n

    def of(self, b):
        """Of block ``b``: its valid rows, the first row of the aligned group
        of the (rows / 128, 128) views copied for it, and its first sorted
        row's place inside that copy."""
        from jax.experimental import pallas as pl

        start = self.first_row[b]
        valid = jnp.clip(self.end[b] - start, 0, self.block)
        first = jnp.minimum(lax.div(start, _GROUP) * _SUBLANES,
                            self.tok_hbm.shape[0] - self.group_rows)
        return valid, pl.multiple_of(first, _SUBLANES), start - first * _LANES

    def group(self, b):
        """The copies of ``tok`` and ``gate`` of block ``b``'s group."""
        from jax.experimental import pallas as pl
        from jax.experimental.pallas import tpu as pltpu

        at, slot = pl.ds(self.of(b)[1], self.group_rows), lax.rem(b, 3)
        return [
            pltpu.make_async_copy(self.tok_hbm.at[at], self.tok_s.at[slot],
                                  self.sems.at[0, 0]),
            pltpu.make_async_copy(self.gate_hbm.at[at], self.gate_v.at[slot],
                                  self.sems.at[0, 1])]

    def ask_for_group(self, b):
        for c in self.group(b):
            c.start()

    def wait_for_group(self, b):
        for c in self.group(b):
            c.wait()

    def start(self, b, streams):
        """Block ``b``'s row copies of ``(stream, hbm, buffer, its slot,
        to_hbm)``."""
        valid, _, place = self.of(b)
        _start_rows(valid, self.tok_s.at[lax.rem(b, 3)], place, self.block,
                    [(hbm, buf.at[s], self.sems.at[stream, s], to_hbm)
                     for stream, hbm, buf, s, to_hbm in streams])

    def wait(self, b, stream, buf, slot):
        _wait_rows(self.of(b)[0], buf.at[slot], self.sems.at[stream, slot],
                   self.block)

    def gate_column(self):
        return _column(self.gate_v[lax.rem(self.b, 3)], self.of(self.b)[2],
                       self.block)

    def first_tile(self, ins, acc):
        """At a block's first inner tile: block 0 starts its own rows (``ins``
        and the accumulator's, ``(stream, hbm, buffer)``); every block starts
        the NEXT block's ``ins`` — they fly behind this block's products —
        asks for the group of the block after that, and waits for its own
        ``ins``."""
        from jax.experimental import pallas as pl

        def mine():
            self.ask_for_group(0)
            self.wait_for_group(0)

            def second():
                self.ask_for_group(1)
                self.wait_for_group(1)

            pl.when(self.has_next)(second)
            self.start(0, [(s, h, buf, 0, False) for s, h, buf in ins + [acc]])

        pl.when(self.b == 0)(mine)
        pl.when(self.has_next)(lambda: self.start(
            self.b + 1, [(s, h, buf, self.next_slot, False) for s, h, buf in ins]))
        pl.when(self.has_third)(lambda: self.ask_for_group(self.b + 2))
        for s, _, buf in ins:
            self.wait(self.b, s, buf, self.slot)

    def last_tile(self, acc_in, acc_out, hbm, buf):
        """At a block's last inner tile: its accumulator rows go back and
        are waited for — the next block may hold the same tokens, and reads
        what this one wrote — then the next block's come in."""
        from jax.experimental import pallas as pl

        pl.when(self.has_third)(lambda: self.wait_for_group(self.b + 2))
        self.start(self.b, [(acc_out, hbm, buf, 0, True)])
        self.wait(self.b, acc_out, buf, 0)
        pl.when(self.has_next)(lambda: self.start(
            self.b + 1, [(acc_in, hbm, buf, 0, False)]))


def _fwd_kernel(expert_ref, start_ref, end_ref, n_ref, tok_hbm, gate_hbm,
                x_hbm, wg_ref, wu_ref, wd_ref, y_in, y_hbm, tok_s, gate_v,
                xbuf, ybuf, sems, *, block: int):
    """Block ``b``, inner tile ``j``: every tile adds
    ``gate * (silu(x Wg) * (x Wu)) Wd`` over its columns of the inner
    dimension to the block's rows of ``y`` in VMEM; around that the rows
    travel as :class:`_Walk` says. ``y_in`` is ``y_hbm``'s own buffer
    (aliased) and is not touched by name."""
    from jax.experimental import pallas as pl

    del y_in
    w = _Walk((expert_ref, start_ref, end_ref, n_ref), tok_hbm, gate_hbm, tok_s,
              gate_v, sems, block)
    x_in, y_rows_in, y_rows_out = 1, 2, 3

    @pl.when(w.b < w.n)
    def _():
        pl.when(w.j == 0)(lambda: w.first_tile(
            [(x_in, x_hbm, xbuf)], (y_rows_in, y_hbm, ybuf)))
        dtype = wg_ref.dtype
        xb = _block_value(xbuf.at[w.slot], block, dtype)
        a = lax.dot_general(xb, wg_ref[...], _NN,
                            preferred_element_type=jnp.float32)
        u = lax.dot_general(xb, wu_ref[...], _NN,
                            preferred_element_type=jnp.float32)
        mid = (jax.nn.silu(a) * u).astype(dtype)
        out = lax.dot_general(mid, wd_ref[...], _NN,
                              preferred_element_type=jnp.float32)
        pl.when(w.j == 0)(lambda: w.wait(w.b, y_rows_in, ybuf, 0))
        _add_to_block(ybuf.at[0], w.gate_column() * out, block)
        pl.when(w.j == w.last_j)(lambda: w.last_tile(
            y_rows_in, y_rows_out, y_hbm, ybuf))


def _grid_spec(blocks: int, hidden: int, inner: int, tile: int, n_any: int,
               n_out_any: int, out_specs, scratch_shapes):
    """Four scalar-prefetched table columns, ``n_any`` operands left in HBM,
    the three stacked matrices by the block's expert and the inner tile."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    steps = inner // tile

    def live(b, j, n_ref):
        """A dead step points at the last live block's last tile."""
        dead = b >= n_ref[0]
        return (jnp.maximum(jnp.minimum(b, n_ref[0] - 1), 0),
                jnp.where(dead, steps - 1, j))

    def up(b, j, expert_ref, start_ref, end_ref, n_ref):
        b, j = live(b, j, n_ref)
        return expert_ref[b], 0, j

    def down(b, j, expert_ref, start_ref, end_ref, n_ref):
        b, j = live(b, j, n_ref)
        return expert_ref[b], j, 0

    any_spec = pl.BlockSpec(memory_space=pl.ANY)
    return pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=4,
        grid=(blocks, steps),
        in_specs=[any_spec] * n_any
        + [pl.BlockSpec((None, hidden, tile), up),
           pl.BlockSpec((None, hidden, tile), up),
           pl.BlockSpec((None, tile, hidden), down)]
        + [any_spec] * n_out_any,
        out_specs=out_specs,
        scratch_shapes=scratch_shapes,
    )


def _table(tbl):
    return (tbl["expert"], tbl["start"], tbl["end"],
            jnp.reshape(tbl["n"], (1,)).astype(jnp.int32))


def _rows_view(a, packed: bool):
    """(T, hidden) → (T * hidden / 128, 128) float32, or where ``packed``
    bfloat16 (T * hidden / 256, 128) uint32, column ``j`` in a word's low
    half and ``j + hidden / 2`` in its high half: a row as one aligned slab
    of whole tiles."""
    if not packed:
        return a.astype(jnp.float32).reshape(-1, _LANES)
    bits = lax.bitcast_convert_type(a, jnp.uint16).astype(jnp.uint32)
    half = a.shape[1] // 2
    return (bits[:, :half] | (bits[:, half:] << 16)).reshape(-1, _LANES)


def _lanes_view(a):
    """(rows,) → (rows / 128, 128)."""
    return a.reshape(-1, _LANES)


def _row_forms(hidden: int, tiles: ExpertTiles):
    """``(sublanes a row, dtype)`` of the ``x`` / ``dy`` streams and of the
    accumulator's."""
    acc = (hidden // _LANES, jnp.float32)
    return ((hidden // (2 * _LANES), jnp.uint32) if tiles.packed else acc), acc


def _scratch(block: int, group_rows: int, row_buffers, streams: int):
    """The groups of ``tok`` (SMEM) and ``gate`` of three blocks in a row,
    the slab buffers (``row_buffers``: ``(how many, (sublanes a row,
    dtype))`` of each), the semaphores."""
    from jax.experimental.pallas import tpu as pltpu

    return ([pltpu.SMEM((3, group_rows, _LANES), jnp.int32),
             pltpu.VMEM((3, group_rows, _LANES), jnp.float32)]
            + [pltpu.VMEM((n, block * per_row, _LANES), dtype)
               for n, (per_row, dtype) in row_buffers]
            + [pltpu.SemaphoreType.DMA((1 + streams, 2))])


# The kernels' calls are jitted apart: an expert layer's pallas_call is traced
# and lowered once a program, not once a layer (the model has ten of them).
# The views and casts around it stay outside, where XLA fuses them with what
# makes and takes them.
@functools.partial(jax.jit, static_argnames=("block", "tiles", "interpret"))
def _forward_call(table, tok, gate, x, wg, wu, wd, y, *, block: int,
                  tiles: ExpertTiles, interpret):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    hidden = wg.shape[1]
    rows, acc = _row_forms(hidden, tiles)
    return pl.pallas_call(
        functools.partial(_fwd_kernel, block=block),
        # explicit name: the compiled program's custom call and the trace
        # events carry it (obs/introspect.tpu_custom_call_counts)
        name="lm_grouped_experts",
        out_shape=jax.ShapeDtypeStruct(y.shape, jnp.float32),
        grid_spec=_grid_spec(
            table[0].shape[0], hidden, wg.shape[2], tiles.inner, 3, 1,
            pl.BlockSpec(memory_space=pl.ANY),
            _scratch(block, _group_rows(block), ((2, rows), (1, acc)), 3)),
        # the float32 accumulator is updated in place (operand 10 of 11,
        # the four table columns counted)
        input_output_aliases={10: 0},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"),
            vmem_limit_bytes=tiles.fwd_vmem,
        ),
        interpret=interpret,  # CPU-testable (tests/test_grouped_experts.py)
    )(*table, tok, gate, x, wg, wu, wd, y)


def grouped_experts_forward(x, tok, gate, tbl, wg, wu, wd, block: int,
                            interpret=False):
    """``y[t] = sum over the rows r of token t of gate[r] * E_{e(r)}(x[t])``
    in ``x``'s dtype, as ``models.deepseek._grouped_swiglu`` defines it over
    ``expert_blocks``' table: ``x`` (T, hidden), ``tok`` / ``gate`` (rows,)
    sorted by expert, ``tbl`` the blocks, ``wg`` / ``wu`` (experts, hidden,
    inner) and ``wd`` (experts, inner, hidden) stacked. Raises where
    :func:`grouped_expert_tiles` refuses the shape."""
    tiles = _tiles_of(x, tok, wg, block)
    xv = _rows_view(x, tiles.packed)
    y = _forward_call(_table(tbl), _lanes_view(tok), _lanes_view(gate), xv,
                      wg, wu, wd, jnp.zeros((x.size // _LANES, _LANES),
                                            jnp.float32),
                      block=block, tiles=tiles, interpret=interpret)
    return y.reshape(x.shape).astype(x.dtype)


def _bwd_kernel(expert_ref, start_ref, end_ref, n_ref, tok_hbm, gate_hbm,
                x_hbm, dy_hbm, wg_ref, wu_ref, wd_ref, dx_in, dgate_in, dx_hbm,
                dgate_hbm, tok_s, gate_v, xbuf, dybuf, dxbuf, dgate_v, dg_acc,
                sems, *, block: int):
    """Block ``b``, inner tile ``j``, as ``models.deepseek``'s XLA loop's
    backward body: every tile recomputes its columns of the block, adds its
    part of ``dgate`` (the row sums of ``dy * out``) and of ``dx`` in VMEM;
    the last tile merges the block's ``dgate`` into the aligned group of
    sorted rows it lies in (read, add, written back and waited for: the next
    block's group may be the same); around that the rows of ``x``, ``dy`` and the
    accumulator ``dx`` travel as :class:`_Walk` says. ``dx_in`` /
    ``dgate_in`` are the outputs' own buffers (aliased)."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    del dx_in, dgate_in
    w = _Walk((expert_ref, start_ref, end_ref, n_ref), tok_hbm, gate_hbm, tok_s,
              gate_v, sems, block)
    x_in, dy_in, dx_rows_in, dx_rows_out, dgate_rows = 1, 2, 3, 4, 5

    @pl.when(w.b < w.n)
    def _():
        @pl.when(w.j == 0)
        def _():
            w.first_tile([(x_in, x_hbm, xbuf), (dy_in, dy_hbm, dybuf)],
                         (dx_rows_in, dx_hbm, dxbuf))
            dg_acc[...] = jnp.zeros_like(dg_acc)

        dtype = wg_ref.dtype
        xb = _block_value(xbuf.at[w.slot], block, dtype)
        a = lax.dot_general(xb, wg_ref[...], _NN,
                            preferred_element_type=jnp.float32)
        u = lax.dot_general(xb, wu_ref[...], _NN,
                            preferred_element_type=jnp.float32)
        sig = jax.nn.sigmoid(a)
        mid = (a * sig * u).astype(dtype)
        out = lax.dot_general(mid, wd_ref[...], _NN,
                              preferred_element_type=jnp.float32)
        dyb = _block_value(dybuf.at[w.slot], block, jnp.float32)
        dg_acc[...] += jnp.sum(dyb * out, axis=1, keepdims=True)
        dout = (w.gate_column() * dyb).astype(dtype)
        dmid = lax.dot_general(dout, wd_ref[...], _NT,
                               preferred_element_type=jnp.float32)
        da = (dmid * u * sig * (1.0 + a * (1.0 - sig))).astype(dtype)
        du = (dmid * a * sig).astype(dtype)
        dxb = (lax.dot_general(da, wg_ref[...], _NT,
                               preferred_element_type=jnp.float32)
               + lax.dot_general(du, wu_ref[...], _NT,
                                 preferred_element_type=jnp.float32))
        pl.when(w.j == 0)(lambda: w.wait(w.b, dx_rows_in, dxbuf, 0))
        _add_to_block(dxbuf.at[0], dxb, block)

        @pl.when(w.j == w.last_j)
        def _():
            # first, while nothing the block started is still on its way:
            # the block's dgate column laid along the lanes of its group
            valid, first, place = w.of(w.b)
            group = pl.ds(first, w.group_rows)
            dgate_io = pltpu.make_async_copy(dgate_hbm.at[group], dgate_v,
                                             sems.at[dgate_rows, 0])
            dgate_io.start()
            r = lax.broadcasted_iota(jnp.int32, (block, _LANES), 0)
            at = place + r - lax.broadcasted_iota(jnp.int32, (block, _LANES), 1)
            dg = jnp.where(r < valid, dg_acc[...], 0.0)
            dgate_io.wait()
            for i in range(w.group_rows):
                dgate_v[i:i + 1, :] += jnp.sum(
                    jnp.where(at == i * _LANES, dg, 0.0), axis=0, keepdims=True)
            dgate_out = pltpu.make_async_copy(dgate_v, dgate_hbm.at[group],
                                              sems.at[dgate_rows, 0])
            dgate_out.start()
            dgate_out.wait()
            w.last_tile(dx_rows_in, dx_rows_out, dx_hbm, dxbuf)


@functools.partial(jax.jit, static_argnames=("block", "tiles", "interpret"))
def _backward_call(table, tok, gate, x, dy, wg, wu, wd, dx, dgate, *,
                   block: int, tiles: ExpertTiles, interpret):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    hidden = wg.shape[1]
    rows, acc = _row_forms(hidden, tiles)
    group_rows = _group_rows(block)
    any_spec = pl.BlockSpec(memory_space=pl.ANY)
    scratch = _scratch(block, group_rows, ((2, rows), (2, rows), (1, acc)), 5)
    return pl.pallas_call(
        functools.partial(_bwd_kernel, block=block),
        name="lm_grouped_experts_bwd",
        out_shape=(jax.ShapeDtypeStruct(dx.shape, jnp.float32),
                   jax.ShapeDtypeStruct(dgate.shape, jnp.float32)),
        grid_spec=_grid_spec(
            table[0].shape[0], hidden, wg.shape[2], tiles.inner, 4, 2,
            (any_spec, any_spec),
            scratch[:-1] + [pltpu.VMEM((group_rows, _LANES), jnp.float32),
                            pltpu.VMEM((block, 1), jnp.float32), scratch[-1]]),
        # both float32 accumulators are updated in place (operands 11 and 12
        # of 13, the four table columns counted)
        input_output_aliases={11: 0, 12: 1},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"),
            vmem_limit_bytes=tiles.bwd_vmem,
        ),
        interpret=interpret,
    )(*table, tok, gate, x, dy, wg, wu, wd, dx, dgate)


def grouped_experts_backward(x, dy, tok, gate, tbl, wg, wu, wd, block: int,
                             interpret=False):
    """``(dx, dgate)`` of :func:`grouped_experts_forward` for the cotangent
    ``dy`` (T, hidden): ``dx`` in ``x``'s dtype (accumulated in float32),
    ``dgate`` (rows,) float32, zero on the rows no held expert has. Equal to
    ``models.deepseek``'s XLA loop's backward; the matrices are frozen."""
    tiles = _tiles_of(x, tok, wg, block)
    xv, tok_v = _rows_view(x, tiles.packed), _lanes_view(tok)
    dx, dgate = _backward_call(
        _table(tbl), tok_v, _lanes_view(gate), xv, _rows_view(dy, tiles.packed),
        wg, wu, wd, jnp.zeros((x.size // _LANES, _LANES), jnp.float32),
        jnp.zeros(tok_v.shape, jnp.float32),
        block=block, tiles=tiles, interpret=interpret)
    return dx.reshape(x.shape).astype(x.dtype), dgate.reshape(-1)
