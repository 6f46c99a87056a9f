"""Pallas TPU kernel: fused wedge addressing + sorted-row intersection.

The pull phase's requester side composes two memory passes (paper
Sec. 4.4): *address* the suffix candidates of each pulled edge — three
``[B, L]`` gathers from the shard's VMEM-resident key arrays — then
*intersect* them against the pulled ``Adj₊ᵐ(q)`` rows (the
``kernels/intersect`` binary search). Run split, the candidate keys make a
round trip through HBM: the gathers materialize ``cd/ch/ci`` staging
arrays that the second kernel immediately re-loads.

This kernel fuses both passes in one VMEM residency: the key arrays are
loaded once as full blocks (E·12 B — the same budget ``wedge_check``
plans against), each batch tile computes its candidate window
``idx = clip(e+1+k, 0, E-1)`` *in-kernel* (bit-for-bit the engine's
``r_pos`` formula), gathers the candidate keys from VMEM, and runs the
identical per-lane lower-bound search against its ``[bb, Lr]`` row tile.
It returns both the positions and the gathered candidate ids, so the
``[B, L]`` staging arrays never exist.

Bitwise contract (asserted in tests/test_kernels.py): for any inputs,
``wedge_intersect(keys, e, rows, ln)`` equals the split composition
``intersect(pad(rows), ln, keys[clip(e+1+k)])`` — the search bodies are
the same code shape and extra fori steps are no-ops once ``lo == hi``.
"""
from __future__ import annotations

import functools

import numpy as np
import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


def _kernel(kd_ref, kh_ref, ki_ref, e_ref, rd_ref, rh_ref, ri_ref, ln_ref,
            pos_ref, ci_ref, *, L, n_steps):
    kd = kd_ref[...]
    kh = kh_ref[...]
    ki = ki_ref[...]
    e = e_ref[...]
    rd = rd_ref[...]
    rh = rh_ref[...]
    ri = ri_ref[...]
    ln = ln_ref[...]

    e_cap = kd.shape[-1]
    # candidate window of edge e: suffix slots e+1 .. e+L, clipped exactly
    # like the engine's r_pos (out-of-row lanes are masked by the caller's
    # cand_ok — the clip only keeps the gather in bounds)
    k = jax.lax.broadcasted_iota(jnp.int32, (e.shape[0], L), 1)
    idx = jnp.clip(e[:, None] + 1 + k, 0, e_cap - 1)
    qd = jnp.take(kd, idx)
    qh = jnp.take(kh, idx)
    qi = jnp.take(ki, idx)

    lo = jnp.zeros_like(qi)
    hi = jnp.broadcast_to(ln[:, None], qi.shape)

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
    pos_ref[...] = lo
    ci_ref[...] = qi


@functools.partial(jax.jit, static_argnames=("L", "bb", "interpret"))
def wedge_intersect_pallas(keys_d, keys_h, keys_i, e, row_d, row_h, row_i,
                           ln, L: int, bb: int = 128,
                           interpret: bool = True):
    """Inputs already padded to ``bb | B``; rows stay at their wire width
    ``Lr`` (≤ L) — the search never probes past ``ln`` so no re-padding."""
    e_cap = keys_d.shape[-1]
    B, Lr = row_d.shape
    assert B % bb == 0, (B, bb)
    # enough steps for either extent; surplus iterations are no-ops, so the
    # result matches the split kernel's L-derived count bit for bit
    n_steps = max(1, int(np.ceil(np.log2(max(2, L, Lr)))) + 1)
    grid = (B // bb,)
    keys_spec = pl.BlockSpec((e_cap,), lambda i: (0,))
    vec = pl.BlockSpec((bb,), lambda i: (i,))
    row = pl.BlockSpec((bb, Lr), lambda i: (i, 0))
    out = pl.BlockSpec((bb, L), lambda i: (i, 0))
    return pl.pallas_call(
        functools.partial(_kernel, L=L, n_steps=n_steps),
        grid=grid,
        in_specs=[keys_spec, keys_spec, keys_spec, vec, row, row, row, vec],
        out_specs=[out, out],
        out_shape=(jax.ShapeDtypeStruct((B, L), jnp.int32),
                   jax.ShapeDtypeStruct((B, L), keys_i.dtype)),
        name="wedge_intersect",
        interpret=interpret,
    )(keys_d, keys_h, keys_i, e, row_d, row_h, row_i, ln)
