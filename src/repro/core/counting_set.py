"""Distributed counting set (paper Sec. 4.1.4), TPU-native form.

The paper's counting set is a distributed hash map of counters with
per-rank caches that are flushed over the network. On TPU (DESIGN.md §2)
each shard keeps a fixed-capacity open-addressed *counting table*; the
"cache flush" becomes a single ``psum``-style merge of aligned tables
(same hash function ⇒ same slots ⇒ element-wise add merges correctly).

Exactness: with no slot collisions the table is exact. Collisions are
*detected* (per-slot min/max of a check-hash diverge) and reported, never
silently merged into wrong keys — a documented deviation from the paper's
growable map (DESIGN.md §7.3). ``n_keys`` ≪ capacity keeps collisions at
birthday-bound rates.

Hot-path layout: keys, check-hash max, and check-hash min all live in one
``[cap, K+2]`` uint32 table maintained by a *single* scatter-max —
int32 keys are mapped order-preservingly into uint32 by flipping the sign
bit, and the min is recorded as ``max(~chk)`` — so ``increment`` issues
exactly two scatters (one add for counts, one max) instead of four.
``finalize`` unpacks to the same readout as the unfused form, bit for bit.
"""
from __future__ import annotations

from dataclasses import dataclass

import jax
import numpy as np
import jax.numpy as jnp

from repro import kernels
from repro.utils import splitmix32

_CHK_SEED = jnp.uint32(0x9E3779B9)


def _fold_keys(keys: jax.Array, seed: jnp.uint32) -> jax.Array:
    """Mix K int32 key columns [B, K] into one uint32 [B]."""
    acc = jnp.full(keys.shape[:-1], seed, jnp.uint32)
    for k in range(keys.shape[-1]):
        acc = splitmix32(acc ^ keys[..., k].astype(jnp.uint32))
    return acc


_SIGN = 0x80000000  # int32 → uint32 order-preserving sign-bit flip


@dataclass(frozen=True)
class CountingSet:
    """Factory for counting-table state + vectorized increment/merge ops.

    State is ``{count: [cap] i32, packed: [cap, K+2] u32}`` where
    ``packed[:, :K]`` holds sign-flipped keys, ``packed[:, K]`` the
    check-hash max and ``packed[:, K+1]`` the *complemented* check-hash
    min — all three recorded by one scatter-max (the all-zeros init is
    the identity for every column).

    ``backend`` routes *both* table scatters: ``"scatter"`` is the XLA
    ``.at[].add`` / ``.at[].max`` path, ``"pallas"`` the fused
    one-hot-reduction kernel (``kernels/fold_scatter.fold_count_max``:
    counts and the packed key/check-hash rows reduced from ONE shared
    one-hot in one pass — the fold-side twin of the mesh pipeline) — the
    TPU-native scatter idiom, bitwise-identical to the scatter path
    (integer adds; idempotent commutative max). ``"auto"`` (default) picks
    the compiled kernel on a TPU backend and the scatter elsewhere, so CPU
    test runs are unchanged; ``"pallas"`` off a TPU runs the kernel in
    interpret mode (:func:`repro.kernels.compiled` is the gate)."""

    capacity: int
    n_key_cols: int
    backend: str = "auto"           # "auto" | "pallas" | "scatter"

    def __post_init__(self):
        if self.backend not in ("auto", "pallas", "scatter"):
            raise ValueError(f"unknown CountingSet backend {self.backend!r}")

    def uses_pallas(self) -> bool:
        """Whether :meth:`increment` runs the fused Pallas fold."""
        if self.backend == "auto":
            return kernels.compiled()
        return self.backend == "pallas"

    def init(self):
        cap, k = self.capacity, self.n_key_cols
        # zeros == (keys=int32.min, chk_max=0, chk_min=uint32.max) packed
        return dict(
            count=jnp.zeros((cap,), jnp.int32),
            packed=jnp.zeros((cap, k + 2), jnp.uint32),
        )

    def increment(self, state, keys: jax.Array, valid: jax.Array, amount=1):
        """keys [B, K] int32, valid [B] bool — two scatters into the table."""
        cap = self.capacity
        slot = (_fold_keys(keys, jnp.uint32(0)) % jnp.uint32(cap)).astype(jnp.int32)
        chk = _fold_keys(keys, _CHK_SEED)
        amt = jnp.where(valid, jnp.asarray(amount, jnp.int32), 0)
        # keys recorded by max (a no-op when all writers agree; collisions
        # are flagged by the check hash, so an arbitrary winner is fine)
        keys_u = keys.astype(jnp.uint32) ^ jnp.uint32(_SIGN)
        row = jnp.concatenate([keys_u, chk[:, None], (~chk)[:, None]], axis=-1)
        row = jnp.where(valid[:, None], row, jnp.uint32(0))
        if self.uses_pallas():
            from repro.kernels.fold_scatter.ops import fold_count_max

            # OOB slots are dropped by the kernel — mask invalid to -1
            mslot = jnp.where(valid, slot, -1)
            # one fused pass forms the one-hot once and reduces both
            # tables from it (kernels/fold_scatter); merging the fresh
            # scattered tables is bitwise-identical to the in-place
            # .at[].add / .at[].max — integer adds commute, max is
            # idempotent and commutative
            d_count, d_packed = fold_count_max(
                mslot, amt, row, cap, interpret=not kernels.compiled())
            count = state["count"] + d_count
            packed = jnp.maximum(state["packed"], d_packed)
        else:
            count = state["count"].at[slot].add(amt)
            packed = state["packed"].at[slot].max(row)
        return dict(count=count, packed=packed)

    def merge(self, stacked):
        """Merge tables stacked on axis 0 (the cross-shard reduce)."""
        return dict(
            count=stacked["count"].sum(0),
            packed=stacked["packed"].max(0),
        )

    def merge_epochs(self, prev, delta):
        """Combine two merged tables over disjoint triangle sets (the delta
        engine's epoch accumulation): counts add, key/check-hash records
        max-merge exactly like the cross-shard reduce, so accumulation is
        bitwise-identical to one table over the union."""
        return dict(
            count=prev["count"] + delta["count"],
            packed=jnp.maximum(prev["packed"], delta["packed"]),
        )

    def finalize(self, merged) -> dict:
        """Host-side read-out: {key_tuple: count}, plus collision report."""
        count = np.asarray(merged["count"])
        packed = np.asarray(merged["packed"], np.uint32)
        k = self.n_key_cols
        keys = (packed[:, :k] ^ np.uint32(_SIGN)).astype(np.int64)
        keys[keys >= 2**31] -= 2**32  # back to signed int32 values
        chk_max = packed[:, k]
        chk_min = ~packed[:, k + 1]
        used = count > 0
        collided = used & (chk_min != chk_max)
        out = {}
        for i in np.nonzero(used & ~collided)[0]:
            out[tuple(int(x) for x in keys[i])] = int(count[i])
        return dict(
            counts=out,
            n_collided_slots=int(collided.sum()),
            count_in_collided=int(count[collided].sum()),
        )
