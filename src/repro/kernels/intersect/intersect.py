"""Pallas TPU kernel: batched sorted-suffix × pulled-row intersection.

The Push-Pull pull phase (paper Sec. 4.4) intersects each local pivot
suffix with the pulled ``Adj₊ᵐ(q)`` row. The paper uses a serial
merge-path [24]; on TPU we use per-lane binary search (same O(L log L)
work shape, fully vectorized — DESIGN.md §2).

Blocking: rows and candidate tiles are co-blocked on the batch axis so
each grid step works on a [bB, L] row block + [bB, L] candidate block
resident in VMEM. L = d₊_max is hardware-aligned by the caller (multiples
of 128 recommended for lane efficiency).
"""
from __future__ import annotations

import functools

import numpy as np
import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


def _kernel(rd_ref, rh_ref, ri_ref, ln_ref, qd_ref, qh_ref, qi_ref, out_ref,
            *, n_steps):
    rd = rd_ref[...]
    rh = rh_ref[...]
    ri = ri_ref[...]
    ln = ln_ref[...]
    qd = qd_ref[...]
    qh = qh_ref[...]
    qi = qi_ref[...]

    lo = jnp.zeros_like(qi)
    hi = jnp.broadcast_to(ln, qi.shape)

    def body(_, carry):
        lo, hi = carry
        has = lo < hi
        mid = jnp.where(has, (lo + hi) // 2, 0)
        d = jnp.take_along_axis(rd, mid, axis=1)
        h = jnp.take_along_axis(rh, mid, axis=1)
        i = jnp.take_along_axis(ri, mid, axis=1)
        less = (d < qd) | ((d == qd) & (h < qh)) | ((d == qd) & (h == qh) & (i < qi))
        return jnp.where(has & less, mid + 1, lo), jnp.where(has & ~less, mid, hi)

    lo, _ = jax.lax.fori_loop(0, n_steps, body, (lo, hi))
    out_ref[...] = lo


@functools.partial(jax.jit, static_argnames=("bb", "interpret"))
def intersect_pallas(row_d, row_h, row_i, ln, qd, qh, qi,
                     bb: int = 128, interpret: bool = True):
    B, L = qd.shape
    assert B % bb == 0, (B, bb)
    n_steps = max(1, int(np.ceil(np.log2(max(2, L)))) + 1)
    grid = (B // bb,)
    mat = pl.BlockSpec((bb, L), lambda i: (i, 0))
    vec = pl.BlockSpec((bb, 1), lambda i: (i, 0))
    return pl.pallas_call(
        functools.partial(_kernel, n_steps=n_steps),
        grid=grid,
        in_specs=[mat, mat, mat, vec, mat, mat, mat],
        out_specs=mat,
        out_shape=jax.ShapeDtypeStruct((B, L), jnp.int32),
        name="intersect",
        interpret=interpret,
    )(row_d, row_h, row_i, ln[:, None], qd, qh, qi)
