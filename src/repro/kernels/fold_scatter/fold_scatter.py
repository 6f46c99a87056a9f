"""Pallas TPU kernels: fused fold-side scatters for scatter-bound surveys.

The mesh pipeline overlaps superstep ``t+1``'s wire with superstep ``t``'s
fold (``core.engine``), so the fold must keep up with the faster scheduled
wire. The two scatter-bound folds are :class:`~repro.core.counting_set.
CountingSet` (a count scatter-add plus a packed-record scatter-max per
update — previously two separate ``hist`` kernels re-reading the slot ids
and re-forming the same one-hot) and :class:`~repro.core.surveys.Enumerate`
(a ring-buffer scatter-set XLA lowers to a serial scatter with
backend-defined collision winners).

Both get the ``hist`` family's native TPU idiom — tiled one-hot
compare-and-reduce over a (table tile, batch tile) grid, batch innermost
so each output tile accumulates in VMEM:

``fold_count_max``
    ONE kernel, two outputs: the count table (add-reduce) and the packed
    row table (max-reduce) from a *shared* one-hot match. Integer adds and
    idempotent/commutative max make both reductions bitwise-identical to
    the two-kernel composition and to XLA's ``.at[].add`` / ``.at[].max``.

``ring_set``
    last-writer-wins scatter-set into a carried table: for every table
    lane the winning batch element is the *highest global batch index*
    that targets it — a deterministic tie rule, unlike XLA scatter ties
    (unordered, backend-defined). Batch tiles iterate sequentially, so
    each tile simply overwrites the lanes it hits; within a tile the
    winner is an argmax over unique batch indices. The prior table rides
    in as an input block so untouched lanes pass through unchanged.

Layout (what Mosaic compiles for the TPU): every block is 2-D. Slot ids
arrive as a ``[B, 1]`` column so they broadcast against a ``[1, cap_tile]``
lane row into the ``[bb, cap_tile]`` one-hot; tables are stored
*transposed* — ``[W, capacity]``, one row per record column — so each
column reduces over the batch axis straight into a lane-dense
``[1, cap_tile]`` row, with no 3-D intermediates. Mosaic has no unsigned
reductions, so packed uint32 records travel through the max as int32
under the order-preserving sign-bit flip (``to_ordered_i32``).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

_SIGN = 0x80000000
_I32_MIN = -(2**31)


def to_ordered_i32(x):
    """uint32 → int32 by flipping the sign bit: order-preserving, so a
    max over the result is the max over ``x`` (0 ↦ int32 min)."""
    return jax.lax.bitcast_convert_type(x ^ jnp.uint32(_SIGN), jnp.int32)


def from_ordered_i32(x):
    """Inverse of :func:`to_ordered_i32`."""
    return jax.lax.bitcast_convert_type(x, jnp.uint32) ^ jnp.uint32(_SIGN)


def _one_hot(slot_ref, cap_tile):
    """[bb, cap_tile] match of the batch's slot column against this grid
    step's table lanes."""
    lane = (pl.program_id(0) * cap_tile
            + jax.lax.broadcasted_iota(jnp.int32, (1, cap_tile), 1))
    return slot_ref[...] == lane


def _count_max_kernel(slot_ref, amt_ref, row_ref, count_ref, packed_ref, *,
                      cap_tile, W):
    @pl.when(pl.program_id(1) == 0)
    def _init():
        count_ref[...] = jnp.zeros_like(count_ref)
        # int32 min == the flipped all-zeros uint32 max identity
        packed_ref[...] = jnp.full_like(packed_ref, _I32_MIN)

    hit = _one_hot(slot_ref, cap_tile)                       # [bb, cap_tile]
    count_ref[...] += jnp.where(hit, amt_ref[...], 0).sum(
        axis=0, keepdims=True)
    rows = row_ref[...]                                      # [bb, W]
    for w in range(W):
        col = jnp.where(hit, rows[:, w:w + 1], _I32_MIN).max(
            axis=0, keepdims=True)                           # [1, cap_tile]
        packed_ref[w:w + 1, :] = jnp.maximum(packed_ref[w:w + 1, :], col)


@functools.partial(jax.jit, static_argnames=("capacity", "bb", "cap_tile",
                                             "interpret"))
def fold_count_max_pallas(slots, amounts, rows, capacity: int, bb: int = 256,
                          cap_tile: int = 512, interpret: bool = True):
    """One fused pass: count scatter-add + packed-row scatter-max.

    ``slots``/``amounts`` are ``[B, 1]`` int32 columns, ``rows`` ``[B, W]``
    int32 in the ordered form; returns the count table ``[1, capacity]``
    and the packed table transposed, ``[W, capacity]`` (ordered int32).
    VMEM: a few ``[bb, cap_tile]`` int32 planes — 512 KiB each at the
    default 256×512 tiles."""
    B, W = rows.shape
    assert B % bb == 0 and capacity % cap_tile == 0
    grid = (capacity // cap_tile, B // bb)
    col = pl.BlockSpec((bb, 1), lambda i, j: (j, 0))
    return pl.pallas_call(
        functools.partial(_count_max_kernel, cap_tile=cap_tile, W=W),
        grid=grid,
        in_specs=[col, col, pl.BlockSpec((bb, W), lambda i, j: (j, 0))],
        out_specs=(
            pl.BlockSpec((1, cap_tile), lambda i, j: (0, i)),
            pl.BlockSpec((W, cap_tile), lambda i, j: (0, i)),
        ),
        out_shape=(
            jax.ShapeDtypeStruct((1, capacity), jnp.int32),
            jax.ShapeDtypeStruct((W, capacity), jnp.int32),
        ),
        name="fold_count_max",
        interpret=interpret,
    )(slots, amounts, rows)


def _ring_set_kernel(prior_ref, slot_ref, row_ref, out_ref, *, cap_tile, bb,
                     W):
    j = pl.program_id(1)   # batch tile

    @pl.when(j == 0)
    def _init():
        out_ref[...] = prior_ref[...]

    hit = _one_hot(slot_ref, cap_tile)                       # [bb, cap_tile]
    gidx = j * bb + jax.lax.broadcasted_iota(jnp.int32, (bb, 1), 0)
    cand = jnp.where(hit, gidx, -1)                          # [bb, cap_tile]
    win = cand.max(axis=0, keepdims=True)                    # [1, cap_tile]
    # batch indices are unique, so exactly one element attains the winner
    sel = hit & (cand == win)
    rows = row_ref[...]                                      # [bb, W]
    # later batch tiles run later in the sequential grid and overwrite —
    # the global winner of a lane is the highest batch index that hits it
    for w in range(W):
        col = jnp.where(sel, rows[:, w:w + 1], 0).sum(axis=0, keepdims=True)
        out_ref[w:w + 1, :] = jnp.where(win >= 0, col, out_ref[w:w + 1, :])


@functools.partial(jax.jit, static_argnames=("capacity", "bb", "cap_tile",
                                             "interpret"))
def ring_set_pallas(prior, slots, rows, capacity: int, bb: int = 256,
                    cap_tile: int = 512, interpret: bool = True):
    """Deterministic last-writer-wins scatter-set over a carried table.

    ``prior`` is the table transposed, ``[W, capacity]``; ``slots`` a
    ``[B, 1]`` column; ``rows`` ``[B, W]`` must be non-negative where
    ``slots`` is in range (vertex ids are) — the one-winner select sums
    masked rows."""
    B, W = rows.shape
    assert B % bb == 0 and capacity % cap_tile == 0
    grid = (capacity // cap_tile, B // bb)
    table = pl.BlockSpec((W, cap_tile), lambda i, j: (0, i))
    return pl.pallas_call(
        functools.partial(_ring_set_kernel, cap_tile=cap_tile, bb=bb, W=W),
        grid=grid,
        in_specs=[table, pl.BlockSpec((bb, 1), lambda i, j: (j, 0)),
                  pl.BlockSpec((bb, W), lambda i, j: (j, 0))],
        out_specs=table,
        out_shape=jax.ShapeDtypeStruct((W, capacity), rows.dtype),
        name="ring_set",
        interpret=interpret,
    )(prior, slots, rows)
