"""jit'd wrappers for the fused fold scatters (layout, padding, tiling)."""
from __future__ import annotations

import jax.numpy as jnp

from repro.kernels.fold_scatter.fold_scatter import (fold_count_max_pallas,
                                                     from_ordered_i32,
                                                     ring_set_pallas,
                                                     to_ordered_i32)
from repro.utils import ceil_div


def tiles(B: int, capacity: int, bb: int, cap_tile: int):
    """(bb, cap_tile, padded B, padded capacity): tiles shrink to the
    problem, the batch tile stays a sublane multiple (8) and the table
    tile a lane multiple (128) unless the whole table is smaller; the
    batch and table are padded up to whole tiles."""
    bb = min(bb, 8 * ceil_div(max(B, 1), 8))
    cap_tile = min(cap_tile, capacity if capacity < 128
                   else 128 * ceil_div(capacity, 128))
    return (bb, cap_tile, bb * ceil_div(max(B, 1), bb),
            cap_tile * ceil_div(capacity, cap_tile))


def fold_count_max(slots, amounts, rows, capacity: int, bb: int = 256,
                   cap_tile: int = 512, interpret: bool = True):
    """Fused scatter-add + scatter-max at ``slots`` into fresh tables:
    ``count [capacity]`` int32 and ``packed [capacity, W]`` uint32.

    Out-of-range slots (masked entries set to -1) never match a lane and
    are dropped, mirroring ``hist_add``/``hist_max``.
    """
    B = slots.shape[0]
    bb, cap_tile, Bp, cap_p = tiles(B, capacity, bb, cap_tile)
    pad = Bp - B
    slots = jnp.pad(slots, (0, pad), constant_values=-1)[:, None]
    amounts = jnp.pad(amounts, (0, pad))[:, None]
    rows = to_ordered_i32(jnp.pad(rows, ((0, pad), (0, 0))))
    count, packed_t = fold_count_max_pallas(slots, amounts, rows, cap_p,
                                            bb=bb, cap_tile=cap_tile,
                                            interpret=interpret)
    return count[0, :capacity], from_ordered_i32(packed_t.T[:capacity])


def ring_set(prior, slots, rows, capacity: int, bb: int = 256,
             cap_tile: int = 512, interpret: bool = True):
    """Last-writer-wins scatter-set of ``rows`` [B, 3] at ``slots`` into
    the carried ``prior`` [capacity, 3] table (highest batch index wins a
    contested slot — deterministic, unlike XLA scatter ties).

    Out-of-range slots (invalid entries set to ``capacity``) are dropped.
    Padding slots are -1: they never match a lane.
    """
    B = slots.shape[0]
    bb, cap_tile, Bp, cap_p = tiles(B, capacity, bb, cap_tile)
    pad = Bp - B
    # slot ``capacity`` would land in a padding lane: drop it explicitly
    slots = jnp.where((slots < 0) | (slots >= capacity), -1, slots)
    slots = jnp.pad(slots, (0, pad), constant_values=-1)[:, None]
    rows = jnp.pad(rows, ((0, pad), (0, 0)))
    prior_t = jnp.pad(prior, ((0, cap_p - capacity), (0, 0))).T
    out = ring_set_pallas(prior_t, slots, rows, cap_p, bb=bb,
                          cap_tile=cap_tile, interpret=interpret)
    return out.T[:capacity]
