"""Fused fold kernels (ISSUE 8): `fold_count_max` (one shared one-hot →
scatter-add counts + scatter-max packed rows) and `ring_set`
(deterministic last-writer-wins scatter-set into a carried ring buffer),
validated against their pure-jnp oracles and against the unfused paths
they replace — plus survey-level parity for the `CountingSet` and
`Enumerate` backends that route through them."""
import numpy as np
import pytest
import jax.numpy as jnp

try:
    from hypothesis import given, settings, strategies as st
    HAVE_HYPOTHESIS = True
except ModuleNotFoundError:
    HAVE_HYPOTHESIS = False

from repro.kernels.fold_scatter.ops import fold_count_max, ring_set
from repro.kernels.fold_scatter.ref import fold_count_max_ref, ring_set_ref
from repro.kernels.hist.ops import hist_add, hist_max


def _count_max_case(rng, B, cap, W):
    slots = rng.integers(-1, cap, B).astype(np.int32)   # -1 == masked out
    amt = rng.integers(0, 7, B).astype(np.int32)
    rows = rng.integers(0, 1 << 32, (B, W), dtype=np.uint64).astype(np.uint32)
    rows[slots < 0] = 0                                  # masked rows zeroed
    return jnp.asarray(slots), jnp.asarray(amt), jnp.asarray(rows)


# ---------------------------------------------------------------------------
# fold_count_max


@pytest.mark.parametrize("B,cap,W,bb,ct", [
    (32, 64, 3, 8, 16), (1000, 512, 5, 256, 512),
    (37, 64, 5, 256, 256), (5, 8, 1, 8, 8), (256, 96, 4, 64, 96)])
def test_fold_count_max_vs_ref(B, cap, W, bb, ct):
    """Fused pass == the .at[].add / .at[].max reference, including
    dropped (negative) slots."""
    rng = np.random.default_rng(B * cap + W)
    slots, amt, rows = _count_max_case(rng, B, cap, W)
    count, packed = fold_count_max(slots, amt, rows, cap, bb=bb, cap_tile=ct,
                                   interpret=True)
    rcount, rpacked = fold_count_max_ref(slots, amt, rows, cap)
    np.testing.assert_array_equal(np.asarray(count), np.asarray(rcount))
    np.testing.assert_array_equal(np.asarray(packed), np.asarray(rpacked))


def test_fold_count_max_equals_two_hist_kernels():
    """The fusion it replaces: one fold_count_max == hist_add + hist_max
    run separately over the same batch, bit for bit."""
    rng = np.random.default_rng(42)
    B, cap, W = 300, 128, 7
    slots, amt, rows = _count_max_case(rng, B, cap, W)
    count, packed = fold_count_max(slots, amt, rows, cap, bb=64, cap_tile=32,
                                   interpret=True)
    np.testing.assert_array_equal(
        np.asarray(count),
        np.asarray(hist_add(slots, amt, cap, bb=64, cap_tile=32,
                            interpret=True)))
    np.testing.assert_array_equal(
        np.asarray(packed),
        np.asarray(hist_max(slots, rows, cap, bb=64, cap_tile=32,
                            interpret=True)))


if HAVE_HYPOTHESIS:
    @settings(max_examples=20, deadline=None)
    @given(st.integers(1, 400), st.sampled_from([8, 64, 256]),
           st.integers(1, 6), st.integers(0, 2**31 - 1))
    def test_fold_count_max_property(B, cap, W, seed):
        """Property: counts conserve total mass of live slots; packed table
        == the scatter-max oracle."""
        rng = np.random.default_rng(seed)
        slots, amt, rows = _count_max_case(rng, B, cap, W)
        count, packed = fold_count_max(slots, amt, rows, cap, bb=64,
                                       cap_tile=8, interpret=True)
        live = np.asarray(slots) >= 0
        assert int(np.asarray(count).sum()) == int(np.asarray(amt)[live].sum())
        rcount, rpacked = fold_count_max_ref(slots, amt, rows, cap)
        np.testing.assert_array_equal(np.asarray(count), np.asarray(rcount))
        np.testing.assert_array_equal(np.asarray(packed), np.asarray(rpacked))
else:
    @pytest.mark.skip(reason="property tests need hypothesis")
    def test_fold_count_max_property():
        pass


# ---------------------------------------------------------------------------
# ring_set


def _ring_case(rng, B, cap, dup=True):
    hi = cap if dup else None
    if dup:
        slots = rng.integers(0, cap, B).astype(np.int32)
    else:
        slots = rng.permutation(cap)[:B].astype(np.int32)
    drop = rng.random(B) < 0.2
    slots = np.where(drop, cap, slots).astype(np.int32)   # OOB == dropped
    rows = rng.integers(0, 1 << 20, (B, 3)).astype(np.int32)
    prior = rng.integers(-1, 1 << 20, (cap, 3)).astype(np.int32)
    return (jnp.asarray(prior), jnp.asarray(slots), jnp.asarray(rows))


@pytest.mark.parametrize("B,cap,bb,ct", [
    (32, 64, 8, 16), (500, 96, 256, 96), (37, 64, 256, 256), (8, 8, 8, 8)])
def test_ring_set_vs_ref(B, cap, bb, ct):
    """Kernel == oracle on contested slots: highest batch index wins,
    untargeted slots keep the carried prior, OOB slots drop."""
    rng = np.random.default_rng(B * cap)
    prior, slots, rows = _ring_case(rng, B, cap)
    got = ring_set(prior, slots, rows, cap, bb=bb, cap_tile=ct,
                   interpret=True)
    want = ring_set_ref(prior, slots, rows, cap)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def test_ring_set_no_collision_equals_xla_scatter():
    """With one writer per slot the deterministic winner is the only
    writer — kernel, oracle, and raw XLA scatter-set all agree bitwise."""
    rng = np.random.default_rng(3)
    cap, B = 128, 64
    prior, slots, rows = _ring_case(rng, B, cap, dup=False)
    got = ring_set(prior, slots, rows, cap, interpret=True)
    xla = prior.at[slots].set(rows, mode="drop")
    np.testing.assert_array_equal(np.asarray(got), np.asarray(xla))
    np.testing.assert_array_equal(
        np.asarray(ring_set_ref(prior, slots, rows, cap)), np.asarray(xla))


def test_ring_set_last_writer_wins():
    """Every writer targets slot 0: the highest batch index must survive
    (XLA scatter would leave this backend-defined)."""
    cap, B = 4, 9
    prior = jnp.full((cap, 3), -7, jnp.int32)
    slots = jnp.zeros((B,), jnp.int32)
    rows = jnp.arange(B * 3, dtype=jnp.int32).reshape(B, 3)
    got = np.asarray(ring_set(prior, slots, rows, cap, interpret=True))
    np.testing.assert_array_equal(got[0], np.asarray(rows[-1]))
    np.testing.assert_array_equal(got[1:], -7)


if HAVE_HYPOTHESIS:
    @settings(max_examples=20, deadline=None)
    @given(st.integers(1, 300), st.sampled_from([8, 64, 128]),
           st.integers(0, 2**31 - 1))
    def test_ring_set_property(B, cap, seed):
        rng = np.random.default_rng(seed)
        prior, slots, rows = _ring_case(rng, B, cap)
        got = ring_set(prior, slots, rows, cap, bb=64, cap_tile=8,
                       interpret=True)
        want = ring_set_ref(prior, slots, rows, cap)
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
else:
    @pytest.mark.skip(reason="property tests need hypothesis")
    def test_ring_set_property():
        pass


# ---------------------------------------------------------------------------
# survey backends routed through the fused kernels


@pytest.mark.parametrize("cap,B,rounds", [(64, 100, 3), (96, 37, 2)])
def test_counting_set_fused_backend_parity(cap, B, rounds):
    """CountingSet backend='pallas' (the fused fold_count_max path) must
    be bitwise-identical to the scatter fallback across carried rounds."""
    from repro.core.counting_set import CountingSet

    rng = np.random.default_rng(cap + B)
    sets = {b: CountingSet(cap, 2, backend=b)
            for b in ("scatter", "pallas")}
    states = {b: cs.init() for b, cs in sets.items()}
    for r in range(rounds):
        keys = jnp.asarray(rng.integers(-50, 50, (B, 2), dtype=np.int64)
                           .astype(np.int32))
        valid = jnp.asarray(rng.random(B) < 0.8)
        for b, cs in sets.items():
            states[b] = cs.increment(states[b], keys, valid)
    np.testing.assert_array_equal(np.asarray(states["scatter"]["count"]),
                                  np.asarray(states["pallas"]["count"]))
    np.testing.assert_array_equal(np.asarray(states["scatter"]["packed"]),
                                  np.asarray(states["pallas"]["packed"]))
    f_s = sets["scatter"].finalize(states["scatter"])
    f_p = sets["pallas"].finalize(states["pallas"])
    assert f_s == f_p


def test_enumerate_fused_backend_parity_no_wrap():
    """Enumerate backend='pallas' (ring_set) == scatter backend whenever
    the ring does not wrap (single writer per slot — the only regime where
    XLA's tie order is defined)."""
    from repro.core.engine import survey_push_pull
    from repro.core.dodgr import shard_dodgr
    from repro.core.pushpull import plan_engine
    from repro.core.surveys import Enumerate

    from test_delta import _labeled_graph, _tree_equal

    g = _labeled_graph(64, 400, seed=9)
    out = []
    for backend in ("scatter", "pallas"):
        sv = Enumerate(4096, backend=backend)
        cfg, _ = plan_engine(g, 4, sv, mode="pushpull", transport="ragged",
                             push_cap=64, pull_q_cap=4)
        gr, _ = shard_dodgr(g, S=4, hub_theta=cfg.hub_theta, orient="degree")
        out.append(survey_push_pull(gr, sv, cfg))   # capacity ≫ triangles
    (fin_s, st_s), (fin_p, st_p) = out
    assert _tree_equal(st_s, st_p)
    np.testing.assert_array_equal(fin_s["triangles"], fin_p["triangles"])
    assert fin_s["total_found"] == fin_p["total_found"]
    assert fin_s["overflowed"] == fin_p["overflowed"] == 0
