"""Pallas TPU kernel: batched keyed lower-bound over VMEM-pinned CSR keys.

The paper's hot loop is adjacency-list intersection; the push phase
resolves it as one wedge-membership check per candidate (Sec. 4.3). On a
TPU the serial merge-path is latency-bound, so we run a *data-parallel
binary search*: all 8×128 VPU lanes probe independent queries against the
shard's key arrays pinned in VMEM (keys: (d, h, id) — the ``<₊`` total
order). log₂(E) gather steps per query tile.

Blocking: the three key arrays are loaded once as full blocks (they are
the working set: E·12 B ≤ VMEM budget by construction — the engine's
e_cap is planned against it); queries stream through in tiles of ``bq``.
"""
from __future__ import annotations

import functools

import numpy as np
import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


def _kernel(kd_ref, kh_ref, ki_ref, lo_ref, hi_ref, qd_ref, qh_ref, qi_ref,
            out_ref, *, n_steps):
    kd = kd_ref[...]
    kh = kh_ref[...]
    ki = ki_ref[...]
    lo = lo_ref[...]
    hi = hi_ref[...]
    qd = qd_ref[...]
    qh = qh_ref[...]
    qi = qi_ref[...]

    def body(_, carry):
        lo, hi = carry
        has = lo < hi
        mid = jnp.where(has, (lo + hi) // 2, 0)
        d = jnp.take(kd, mid)
        h = jnp.take(kh, mid)
        i = jnp.take(ki, mid)
        less = (d < qd) | ((d == qd) & (h < qh)) | ((d == qd) & (h == qh) & (i < qi))
        return jnp.where(has & less, mid + 1, lo), jnp.where(has & ~less, mid, hi)

    lo, _ = jax.lax.fori_loop(0, n_steps, body, (lo, hi))
    out_ref[...] = lo


@functools.partial(jax.jit, static_argnames=("bq", "interpret"))
def wedge_check_pallas(keys_d, keys_h, keys_i, lo, hi, qd, qh, qi,
                       bq: int = 1024, interpret: bool = True):
    """Lower-bound positions for queries; inputs already padded to bq | B."""
    e_cap = keys_d.shape[-1]
    nq = qd.shape[-1]
    assert nq % bq == 0, (nq, bq)
    n_steps = max(1, int(np.ceil(np.log2(max(2, e_cap)))) + 1)
    grid = (nq // bq,)
    keys_spec = pl.BlockSpec((e_cap,), lambda i: (0,))
    q_spec = pl.BlockSpec((bq,), lambda i: (i,))
    return pl.pallas_call(
        functools.partial(_kernel, n_steps=n_steps),
        grid=grid,
        in_specs=[keys_spec, keys_spec, keys_spec,
                  q_spec, q_spec, q_spec, q_spec, q_spec],
        out_specs=q_spec,
        out_shape=jax.ShapeDtypeStruct((nq,), jnp.int32),
        name="wedge_check",
        interpret=interpret,
    )(keys_d, keys_h, keys_i, lo, hi, qd, qh, qi)
