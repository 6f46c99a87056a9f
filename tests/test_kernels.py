"""Per-kernel validation: Pallas (interpret=True) vs pure-jnp/NumPy oracles,
with shape sweeps and hypothesis property tests. Only the property tests
need hypothesis — the deterministic oracle/parity tests run without it."""
import numpy as np
import pytest
import jax.numpy as jnp

try:
    from hypothesis import given, settings, strategies as st
    HAVE_HYPOTHESIS = True
except ModuleNotFoundError:
    HAVE_HYPOTHESIS = False

from repro.kernels.hist.ops import hist_add, hist_max
from repro.kernels.hist.ref import hist_add_ref, hist_max_ref
from repro.kernels.intersect.ops import intersect
from repro.kernels.intersect.ref import intersect_numpy, intersect_ref
from repro.kernels.wedge_check.ops import wedge_check
from repro.kernels.wedge_check.ref import lower_bound_numpy, lower_bound_ref
from repro.kernels.wedge_intersect.ops import wedge_intersect
from repro.kernels.wedge_intersect.ref import (wedge_intersect_numpy,
                                               wedge_intersect_ref)


def _sorted_keys(rng, n):
    """Random (d, h, id) keys sorted by the total order."""
    d = rng.integers(0, 8, n).astype(np.int32)
    h = rng.integers(0, 1 << 16, n).astype(np.uint32)
    i = rng.permutation(n).astype(np.int32)
    order = np.lexsort((i, h, d))
    return d[order], h[order], i[order]


# ---------------------------------------------------------------------------
# wedge_check


@pytest.mark.parametrize("e_cap,nq,bq", [(64, 32, 8), (256, 1000, 128),
                                         (1024, 4096, 1024), (8, 3, 8)])
def test_wedge_check_vs_oracles(e_cap, nq, bq):
    rng = np.random.default_rng(e_cap + nq)
    kd, kh, ki = _sorted_keys(rng, e_cap)
    lo = rng.integers(0, e_cap, nq).astype(np.int32)
    hi = (lo + rng.integers(0, e_cap, nq)).clip(0, e_cap).astype(np.int32)
    qd = rng.integers(0, 8, nq).astype(np.int32)
    qh = rng.integers(0, 1 << 16, nq).astype(np.uint32)
    qi = rng.integers(0, e_cap, nq).astype(np.int32)
    want = lower_bound_numpy(kd, kh, ki, lo, hi, qd, qh, qi)
    got_ref = np.asarray(lower_bound_ref(*map(jnp.asarray, (kd, kh, ki, lo, hi, qd, qh, qi))))
    got_pl = np.asarray(wedge_check(*map(jnp.asarray, (kd, kh, ki, lo, hi, qd, qh, qi)),
                                    bq=bq, interpret=True))
    np.testing.assert_array_equal(got_ref, want)
    np.testing.assert_array_equal(got_pl, want)


if HAVE_HYPOTHESIS:
    @settings(max_examples=25, deadline=None)
    @given(st.integers(2, 200), st.integers(1, 300), st.integers(0, 2**31 - 1))
    def test_wedge_check_property(e_cap, nq, seed):
        """Property: result is the true lower bound — all keys below are <,
        key at position (if in range) is ≥."""
        rng = np.random.default_rng(seed)
        kd, kh, ki = _sorted_keys(rng, e_cap)
        lo = np.zeros(nq, np.int32)
        hi = np.full(nq, e_cap, np.int32)
        qd = rng.integers(0, 8, nq).astype(np.int32)
        qh = rng.integers(0, 1 << 16, nq).astype(np.uint32)
        qi = rng.integers(0, e_cap, nq).astype(np.int32)
        pos = np.asarray(wedge_check(*map(jnp.asarray, (kd, kh, ki, lo, hi, qd, qh, qi)),
                                     bq=64, interpret=True))
        keys = list(zip(kd.tolist(), kh.tolist(), ki.tolist()))
        for b in range(nq):
            key = (int(qd[b]), int(qh[b]), int(qi[b]))
            p = int(pos[b])
            assert all(k < key for k in keys[:p])
            if p < e_cap:
                assert keys[p] >= key
else:
    @pytest.mark.skip(reason="property tests need hypothesis")
    def test_wedge_check_property():
        pass


# ---------------------------------------------------------------------------
# intersect


@pytest.mark.parametrize("B,L,bb", [(4, 16, 8), (64, 128, 32), (100, 64, 128),
                                    (128, 256, 128)])
def test_intersect_vs_oracles(B, L, bb):
    rng = np.random.default_rng(B * L)
    rows = [_sorted_keys(rng, L) for _ in range(B)]
    rd = np.stack([r[0] for r in rows])
    rh = np.stack([r[1] for r in rows])
    ri = np.stack([r[2] for r in rows])
    ln = rng.integers(0, L + 1, B).astype(np.int32)
    qd = rng.integers(0, 8, (B, L)).astype(np.int32)
    qh = rng.integers(0, 1 << 16, (B, L)).astype(np.uint32)
    qi = rng.integers(0, L, (B, L)).astype(np.int32)
    want = intersect_numpy(rd, rh, ri, ln, qd, qh, qi)
    got_ref = np.asarray(intersect_ref(*map(jnp.asarray, (rd, rh, ri, ln, qd, qh, qi))))
    got_pl = np.asarray(intersect(*map(jnp.asarray, (rd, rh, ri, ln, qd, qh, qi)),
                                  bb=bb, interpret=True))
    np.testing.assert_array_equal(got_ref, want)
    np.testing.assert_array_equal(got_pl, want)


def test_intersect_finds_common_elements():
    """End-to-end semantic check: hits == set intersection."""
    rng = np.random.default_rng(0)
    L = 32
    # shared key space so intersections are non-trivial
    d = np.zeros(64, np.int32)
    h = np.arange(64, dtype=np.uint32)
    ids = np.arange(64, dtype=np.int32)
    a_idx = np.sort(rng.choice(64, L, replace=False))
    b_idx = np.sort(rng.choice(64, L, replace=False))
    rd, rh, ri = d[a_idx][None], h[a_idx][None], ids[a_idx][None]
    qd, qh, qi = d[b_idx][None], h[b_idx][None], ids[b_idx][None]
    ln = np.array([L], np.int32)
    pos = np.asarray(intersect(*map(jnp.asarray, (rd, rh, ri, ln, qd, qh, qi)),
                               interpret=True))[0]
    hits = {int(qi[0, k]) for k in range(L)
            if pos[k] < L and ri[0, pos[k]] == qi[0, k]}
    assert hits == set(a_idx) & set(b_idx)


# ---------------------------------------------------------------------------
# wedge_intersect (fused candidate addressing + intersection)


def _wedge_intersect_case(rng, e_cap, B, L, Lr):
    kd, kh, ki = _sorted_keys(rng, e_cap)
    e = rng.integers(-1, e_cap, B).astype(np.int32)   # -1: degenerate slot
    rows = [_sorted_keys(rng, Lr) for _ in range(B)]
    rd = np.stack([r[0] for r in rows])
    rh = np.stack([r[1] for r in rows])
    ri = np.stack([r[2] for r in rows])
    ln = rng.integers(0, Lr + 1, B).astype(np.int32)
    return kd, kh, ki, e, rd, rh, ri, ln


@pytest.mark.parametrize("e_cap,B,L,Lr,bb", [
    (64, 16, 8, 8, 8), (256, 100, 16, 32, 32),
    (1024, 128, 32, 16, 128), (8, 3, 4, 4, 8)])
def test_wedge_intersect_vs_oracles(e_cap, B, L, Lr, bb):
    """Fused kernel == jnp ref == host numpy ground truth, including the
    clipped out-of-range candidate addressing at the array edges."""
    rng = np.random.default_rng(e_cap * B + L)
    case = _wedge_intersect_case(rng, e_cap, B, L, Lr)
    want_pos, want_ci = wedge_intersect_numpy(*case, L=L)
    ref_pos, ref_ci = wedge_intersect_ref(*map(jnp.asarray, case), L=L)
    got_pos, got_ci = wedge_intersect(*map(jnp.asarray, case), L=L, bb=bb,
                                      interpret=True)
    np.testing.assert_array_equal(np.asarray(ref_pos), want_pos)
    np.testing.assert_array_equal(np.asarray(ref_ci), want_ci)
    np.testing.assert_array_equal(np.asarray(got_pos), want_pos)
    np.testing.assert_array_equal(np.asarray(got_ci), want_ci)


def test_wedge_intersect_matches_two_kernel_composition():
    """Bitwise parity with the historic split lowering: gather candidate
    keys with jnp, pad rows to L, run kernels/intersect."""
    rng = np.random.default_rng(7)
    e_cap, B, L, Lr = 256, 64, 16, 16
    kd, kh, ki, e, rd, rh, ri, ln = map(
        jnp.asarray, _wedge_intersect_case(rng, e_cap, B, L, Lr))
    k = jnp.arange(L, dtype=jnp.int32)[None, :]
    idx = jnp.clip(e[:, None] + 1 + k, 0, e_cap - 1)
    cd, ch, ci = kd[idx], kh[idx], ki[idx]
    split_pos = intersect(rd, rh, ri, ln, cd, ch, ci, interpret=True)
    fused_pos, fused_ci = wedge_intersect(kd, kh, ki, e, rd, rh, ri, ln,
                                          L=L, interpret=True)
    np.testing.assert_array_equal(np.asarray(fused_pos),
                                  np.asarray(split_pos))
    np.testing.assert_array_equal(np.asarray(fused_ci), np.asarray(ci))


if HAVE_HYPOTHESIS:
    @settings(max_examples=20, deadline=None)
    @given(st.integers(2, 128), st.integers(1, 60), st.integers(1, 16),
           st.integers(1, 16), st.integers(0, 2**31 - 1))
    def test_wedge_intersect_property(e_cap, B, L, Lr, seed):
        """Property twin: the fused kernel returns the true lower bound of
        the addressed candidate key in every valid row prefix."""
        rng = np.random.default_rng(seed)
        case = _wedge_intersect_case(rng, e_cap, B, L, Lr)
        kd, kh, ki, e, rd, rh, ri, ln = case
        pos, ci = wedge_intersect(*map(jnp.asarray, case), L=L, bb=16,
                                  interpret=True)
        pos, ci = np.asarray(pos), np.asarray(ci)
        for b in range(B):
            row = list(zip(rd[b, :ln[b]].tolist(), rh[b, :ln[b]].tolist(),
                           ri[b, :ln[b]].tolist()))
            for kk in range(L):
                j = min(max(int(e[b]) + 1 + kk, 0), e_cap - 1)
                key = (int(kd[j]), int(kh[j]), int(ki[j]))
                assert ci[b, kk] == ki[j]
                p = int(pos[b, kk])
                assert all(r < key for r in row[:p])
                if p < len(row):
                    assert row[p] >= key
else:
    @pytest.mark.skip(reason="property tests need hypothesis")
    def test_wedge_intersect_property():
        pass


@pytest.mark.parametrize("mode", ["pushpull"])
def test_engine_fused_pull_kernel_bitwise(mode, monkeypatch):
    """Engine-level parity: the pull lane's keyed search through the
    wedge_check kernel == the jnp lower bound, result and stats, bit for
    bit. The jnp run is held to the lower bound (the dense row search
    takes rows this narrow)."""
    import dataclasses

    from repro.core import engine
    from repro.core.dodgr import shard_dodgr
    from repro.core.engine import survey_push_pull
    from repro.core.pushpull import plan_engine
    from repro.core.surveys import TriangleCount
    from repro.graphs import generators

    g = generators.rmat(6, 8, seed=11)
    gr, _ = shard_dodgr(g, S=4)
    cfg, _ = plan_engine(g, 4, mode=mode, push_cap=64, pull_q_cap=4,
                         use_pallas=True)
    res_k, st_k = survey_push_pull(gr, TriangleCount(), cfg)
    monkeypatch.setattr(engine, "DENSE_SEARCH_MAX_ROW", 0)
    res_j, st_j = survey_push_pull(
        gr, TriangleCount(), dataclasses.replace(cfg, use_pallas=False))
    assert st_k["wedges_pulled"] > 0
    assert res_k == res_j
    assert st_k == st_j


def test_wedge_intersect_traffic_model_favors_fusion():
    """The interpret-path op-count model: fused candidate-key traffic beats
    the two-kernel composition at the engine's planned shapes (acceptance:
    fusion must win on the model, not just avoid a launch)."""
    bench = pytest.importorskip("benchmarks.bench_kernels")
    from repro.core.dodgr import shard_dodgr
    from repro.core.pushpull import plan_engine
    from repro.graphs import generators

    g = generators.rmat(8, 16, seed=5)
    for S in (2, 4):
        cfg, _ = plan_engine(g, S, mode="pushpull", push_cap=256,
                             pull_q_cap=16)
        gr, _ = shard_dodgr(g, S=S)
        # the engine's fused call: E = shard suffix-key length, B = S·ecap
        # flattened edge slots, L = the suffix window (dodgr.d_plus_max)
        m = bench.wedge_intersect_traffic_model(
            int(gr.e_cap), S * cfg.pull_edge_cap, int(gr.d_plus_max))
        assert m["fused_words"] < m["split_words"], (S, m)


# ---------------------------------------------------------------------------
# hist


@pytest.mark.parametrize("B,cap,bb,ct", [(32, 64, 8, 16), (1000, 512, 256, 512),
                                         (4096, 4096, 1024, 512), (5, 8, 8, 8)])
def test_hist_vs_ref(B, cap, bb, ct):
    rng = np.random.default_rng(B + cap)
    slots = rng.integers(0, cap, B).astype(np.int32)
    amt = rng.integers(0, 5, B).astype(np.int32)
    want = np.asarray(hist_add_ref(jnp.asarray(slots), jnp.asarray(amt), cap))
    got = np.asarray(hist_add(jnp.asarray(slots), jnp.asarray(amt), cap,
                              bb=bb, cap_tile=ct, interpret=True))
    np.testing.assert_array_equal(got, want)
    assert got.sum() == amt.sum()


if HAVE_HYPOTHESIS:
    @settings(max_examples=20, deadline=None)
    @given(st.integers(1, 500), st.sampled_from([8, 64, 256]),
           st.integers(0, 2**31 - 1))
    def test_hist_property_mass_conservation(B, cap, seed):
        rng = np.random.default_rng(seed)
        slots = rng.integers(0, cap, B).astype(np.int32)
        amt = rng.integers(0, 7, B).astype(np.int32)
        got = np.asarray(hist_add(jnp.asarray(slots), jnp.asarray(amt), cap,
                                  bb=64, cap_tile=8, interpret=True))
        assert got.sum() == amt.sum()
        want = np.bincount(slots, weights=amt, minlength=cap).astype(np.int32)
        np.testing.assert_array_equal(got, want)
else:
    @pytest.mark.skip(reason="property tests need hypothesis")
    def test_hist_property_mass_conservation():
        pass


@pytest.mark.parametrize("B,cap,W,bb,ct", [
    (32, 64, 3, 8, 16), (1000, 512, 5, 256, 512),
    (37, 64, 5, 256, 256), (5, 8, 1, 8, 8)])
def test_hist_max_vs_ref(B, cap, W, bb, ct):
    """Tiled scatter-max == the .at[].max reference, including invalid
    (negative) slots, which must be dropped — not wrapped."""
    rng = np.random.default_rng(B * cap + W)
    slots = rng.integers(-1, cap, B).astype(np.int32)
    rows = rng.integers(0, 1 << 32, (B, W)).astype(np.uint32)
    want = np.asarray(hist_max_ref(jnp.asarray(slots), jnp.asarray(rows), cap))
    got = np.asarray(hist_max(jnp.asarray(slots), jnp.asarray(rows), cap,
                              bb=bb, cap_tile=ct, interpret=True))
    np.testing.assert_array_equal(got, want)
    # manual ground truth
    manual = np.zeros((cap, W), np.uint32)
    for b in range(B):
        if slots[b] >= 0:
            manual[slots[b]] = np.maximum(manual[slots[b]], rows[b])
    np.testing.assert_array_equal(got, manual)


if HAVE_HYPOTHESIS:
    @settings(max_examples=20, deadline=None)
    @given(st.integers(1, 300), st.sampled_from([8, 64]), st.integers(1, 4),
           st.integers(0, 2**31 - 1))
    def test_hist_max_property_idempotent(B, cap, W, seed):
        """Scatter-max is idempotent and order-free: applying the batch
        twice (or the kernel vs the reference) changes nothing."""
        rng = np.random.default_rng(seed)
        slots = jnp.asarray(rng.integers(-1, cap, B).astype(np.int32))
        rows = jnp.asarray(rng.integers(0, 1 << 32, (B, W)).astype(np.uint32))
        once = np.asarray(hist_max(slots, rows, cap, bb=64, cap_tile=8,
                                   interpret=True))
        ref = np.asarray(hist_max_ref(slots, rows, cap))
        np.testing.assert_array_equal(once, ref)
        twice = np.maximum(
            once, np.asarray(hist_max(slots, rows, cap, bb=64, cap_tile=8,
                                      interpret=True)))
        np.testing.assert_array_equal(twice, once)
else:
    @pytest.mark.skip(reason="property tests need hypothesis")
    def test_hist_max_property_idempotent():
        pass


# ---------------------------------------------------------------------------
# CountingSet backend wiring: the Pallas hist path must be bitwise-identical
# to the scatter fallback (satellite: "Pallas fold kernels" lever).


@pytest.mark.parametrize("cap,B,rounds", [(64, 100, 3), (4096, 1000, 2),
                                          (96, 37, 2)])
def test_counting_set_pallas_backend_parity(cap, B, rounds):
    from repro.core.counting_set import CountingSet

    rng = np.random.default_rng(cap + B)
    cs_s = CountingSet(cap, 3, backend="scatter")
    cs_p = CountingSet(cap, 3, backend="pallas")
    st_s, st_p = cs_s.init(), cs_p.init()
    for _ in range(rounds):
        keys = jnp.asarray(rng.integers(-50, 50, (B, 3)).astype(np.int32))
        valid = jnp.asarray(rng.random(B) < 0.8)
        st_s = cs_s.increment(st_s, keys, valid)
        st_p = cs_p.increment(st_p, keys, valid)
    np.testing.assert_array_equal(np.asarray(st_s["count"]),
                                  np.asarray(st_p["count"]))
    np.testing.assert_array_equal(np.asarray(st_s["packed"]),
                                  np.asarray(st_p["packed"]))
    fin_s, fin_p = cs_s.finalize(st_s), cs_p.finalize(st_p)
    assert fin_s == fin_p


def test_counting_set_survey_pallas_backend():
    """End-to-end: a CountingSet survey run with the Pallas count path
    matches the scatter path through the full engine."""
    from repro.core.dodgr import shard_dodgr
    from repro.core.engine import survey_push_only
    from repro.core.pushpull import plan_engine
    from repro.core.surveys import LabelTripleSet
    from repro.graphs import generators

    g = generators.temporal_social(100, 800, seed=6)
    gr, _ = shard_dodgr(g, S=2)
    cfg, _ = plan_engine(g, 2, mode="push", push_cap=128)
    res_s, _ = survey_push_only(
        gr, LabelTripleSet(capacity=1 << 10, counting_backend="scatter"), cfg)
    res_p, _ = survey_push_only(
        gr, LabelTripleSet(capacity=1 << 10, counting_backend="pallas"), cfg)
    assert res_s == res_p


def test_counting_set_rejects_unknown_backend():
    from repro.core.counting_set import CountingSet

    with pytest.raises(ValueError, match="backend"):
        CountingSet(64, 3, backend="gpu")


# ---------------------------------------------------------------------------
# engine × kernel integration: the engine produces identical results with
# use_pallas on and off.


@pytest.mark.parametrize("mode", ["push", "pushpull"])
def test_engine_with_pallas_kernels(mode):
    from repro.core.dodgr import shard_dodgr
    from repro.core.engine import survey_push_only, survey_push_pull
    from repro.core.pushpull import plan_engine
    from repro.core.ref import count_triangles_ref
    from repro.core.surveys import TriangleCount
    from repro.graphs import generators

    g = generators.rmat(6, 8, seed=11)
    t_ref = count_triangles_ref(g)
    gr, _ = shard_dodgr(g, S=4)
    cfg, _ = plan_engine(g, 4, mode=mode, push_cap=64, pull_q_cap=4,
                         use_pallas=True)
    run = survey_push_only if mode == "push" else survey_push_pull
    res, st = run(gr, TriangleCount(), cfg)
    assert res == t_ref
