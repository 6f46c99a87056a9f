"""Pallas TPU kernel: counting-table update via tiled one-hot reduction.

The distributed counting set (paper Sec. 4.1.4) needs high-throughput
scatter-add of hashed keys. TPUs have no fast random scatter; the native
idiom is a *one-hot compare-and-reduce*: for each (batch tile, table tile)
the kernel compares the slot ids against the tile's slot range and
accumulates matches — O(B·cap/tiles) dense work that vectorizes perfectly
(and becomes an MXU matmul in the f32 variant). Grid iterates batch tiles
innermost so each output tile is revisited and accumulated in VMEM.

Layout is that of :mod:`repro.kernels.fold_scatter` (2-D blocks, slot
column × lane row one-hot, transposed tables, ordered-int32 max).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels.fold_scatter.fold_scatter import _I32_MIN, _one_hot


def _kernel(slot_ref, amt_ref, out_ref, *, cap_tile):
    @pl.when(pl.program_id(1) == 0)
    def _init():
        out_ref[...] = jnp.zeros_like(out_ref)

    hit = _one_hot(slot_ref, cap_tile)
    out_ref[...] += jnp.where(hit, amt_ref[...], 0).sum(axis=0, keepdims=True)


@functools.partial(jax.jit, static_argnames=("capacity", "bb", "cap_tile", "interpret"))
def hist_add_pallas(slots, amounts, capacity: int, bb: int = 1024,
                    cap_tile: int = 512, interpret: bool = True):
    """``slots``/``amounts`` ``[B, 1]`` int32 → count table ``[1, capacity]``."""
    B = slots.shape[0]
    assert B % bb == 0 and capacity % cap_tile == 0
    grid = (capacity // cap_tile, B // bb)
    col = pl.BlockSpec((bb, 1), lambda i, j: (j, 0))
    return pl.pallas_call(
        functools.partial(_kernel, cap_tile=cap_tile),
        grid=grid,
        in_specs=[col, col],
        out_specs=pl.BlockSpec((1, cap_tile), lambda i, j: (0, i)),
        out_shape=jax.ShapeDtypeStruct((1, capacity), jnp.int32),
        name="hist_add",
        interpret=interpret,
    )(slots, amounts)


def _max_kernel(slot_ref, row_ref, out_ref, *, cap_tile, W):
    @pl.when(pl.program_id(1) == 0)
    def _init():
        # int32 min == the flipped all-zeros uint32 max identity
        out_ref[...] = jnp.full_like(out_ref, _I32_MIN)

    hit = _one_hot(slot_ref, cap_tile)
    rows = row_ref[...]
    for w in range(W):
        col = jnp.where(hit, rows[:, w:w + 1], _I32_MIN).max(
            axis=0, keepdims=True)
        out_ref[w:w + 1, :] = jnp.maximum(out_ref[w:w + 1, :], col)


@functools.partial(jax.jit, static_argnames=("capacity", "bb", "cap_tile", "interpret"))
def hist_max_pallas(slots, rows, capacity: int, bb: int = 256,
                    cap_tile: int = 512, interpret: bool = True):
    """Row-wise scatter-max: same one-hot idiom as the add kernel, with
    ``max`` as the reduction — max is idempotent and commutative, so the
    tiled accumulation is bitwise-identical to XLA's ``.at[].max``.
    ``rows`` ``[B, W]`` ordered int32 → ``[W, capacity]`` ordered int32."""
    B, W = rows.shape
    assert B % bb == 0 and capacity % cap_tile == 0
    grid = (capacity // cap_tile, B // bb)
    return pl.pallas_call(
        functools.partial(_max_kernel, cap_tile=cap_tile, W=W),
        grid=grid,
        in_specs=[
            pl.BlockSpec((bb, 1), lambda i, j: (j, 0)),
            pl.BlockSpec((bb, W), lambda i, j: (j, 0)),
        ],
        out_specs=pl.BlockSpec((W, cap_tile), lambda i, j: (0, i)),
        out_shape=jax.ShapeDtypeStruct((W, capacity), jnp.int32),
        name="hist_max",
        interpret=interpret,
    )(slots, rows)
