"""jit'd wrappers for the counting-table update."""
from __future__ import annotations

import jax.numpy as jnp

from repro.kernels.fold_scatter.fold_scatter import (from_ordered_i32,
                                                     to_ordered_i32)
from repro.kernels.fold_scatter.ops import tiles
from repro.kernels.hist.hist import hist_add_pallas, hist_max_pallas


def hist_add(slots, amounts, capacity: int, bb: int = 1024,
             cap_tile: int = 512, interpret: bool = True):
    """Scatter-add ``amounts`` at ``slots`` into a fresh [capacity] table.

    Out-of-range slots (e.g. masked-out entries set to -1) are dropped.
    """
    B = slots.shape[0]
    bb, cap_tile, Bp, cap_p = tiles(B, capacity, bb, cap_tile)
    slots = jnp.pad(slots, (0, Bp - B), constant_values=-1)[:, None]
    amounts = jnp.pad(amounts, (0, Bp - B))[:, None]
    out = hist_add_pallas(slots, amounts, cap_p, bb=bb, cap_tile=cap_tile,
                          interpret=interpret)
    return out[0, :capacity]


def hist_max(slots, rows, capacity: int, bb: int = 256,
             cap_tile: int = 512, interpret: bool = True):
    """Scatter-max ``rows`` [B, W] uint32 at ``slots`` into a fresh
    [capacity, W] zero table (zeros = the max identity of the packed
    uint32 layout).

    Out-of-range slots (masked entries set to -1) never match a lane and
    are dropped, mirroring ``hist_add``.
    """
    B = slots.shape[0]
    bb, cap_tile, Bp, cap_p = tiles(B, capacity, bb, cap_tile)
    slots = jnp.pad(slots, (0, Bp - B), constant_values=-1)[:, None]
    rows = to_ordered_i32(jnp.pad(rows, ((0, Bp - B), (0, 0))))
    out = hist_max_pallas(slots, rows, cap_p, bb=bb, cap_tile=cap_tile,
                          interpret=interpret)
    return from_ordered_i32(out.T[:capacity])
