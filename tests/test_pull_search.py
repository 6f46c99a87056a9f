"""The pull lane's key search: a dense compare of the whole pulled row
against the closing vertex, equal to the keyed binary search it replaces
for rows of at most ``engine.DENSE_SEARCH_MAX_ROW`` slots.

The row search is checked against ``_lower_bound`` plus the hit test on
random sorted rows; the engine is checked end to end against itself with
the binary search forced (the bound patched to 0): the same answers and
the same counted stats, but the staged tile slots; and the plan's report
names the search that the engine runs."""
import dataclasses
import hashlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import engine
from repro.core.dodgr import shard_delta, shard_dodgr
from repro.core.engine import (STAT_KEYS, _dense_row_search, _lower_bound,
                               finalize_epochs, survey_delta,
                               survey_push_pull)
from repro.core.pushpull import plan_delta, plan_engine
from repro.core.surveys import LabelTripleSet, SurveyBundle, TriangleCount
from repro.graphs import generators
from repro.graphs.csr import HostGraph

BIG = 2**30
BOUND = engine.DENSE_SEARCH_MAX_ROW
WIDE = BOUND + 76  # a row width above the bound


def _rows(rng, n_rows, Lr):
    """``n_rows`` padded rows of distinct vertex ids sorted by the
    ``(degree, hash, id)`` key, as the owner replies them, plus queries:
    ids inside a row, and ids outside it whose key falls before, between
    and after the row's keys."""
    n_v = 4 * Lr + 64
    deg = rng.integers(1, 40, n_v).astype(np.int32)
    hsh = rng.integers(0, 2**32, n_v, dtype=np.uint64).astype(np.uint32)
    key = lambda v: (int(deg[v]), int(hsh[v]), int(v))
    r_d = np.full((n_rows, Lr), BIG, np.int32)
    r_h = np.full((n_rows, Lr), 0xFFFFFFFF, np.uint32)
    r_i = np.full((n_rows, Lr), BIG, np.int32)
    ln = rng.integers(0, Lr + 1, n_rows).astype(np.int32)
    ln[: n_rows // 8] = 0           # empty rows
    ln[n_rows // 8: n_rows // 4] = Lr  # full rows
    members = []
    for t in range(n_rows):
        vs = sorted(rng.choice(n_v, ln[t], replace=False).tolist(), key=key)
        r_d[t, :ln[t]] = deg[vs]
        r_h[t, :ln[t]] = hsh[vs]
        r_i[t, :ln[t]] = vs
        members.append(vs)
    ridx, ci, where = [], [], []
    for t in range(n_rows):
        vs = members[t]
        outside = sorted(set(range(n_v)) - set(vs), key=key)
        side = {"before": [], "between": [], "after": []}
        for v in outside:
            k = "before" if not vs or key(v) < key(vs[0]) else (
                "after" if key(v) > key(vs[-1]) else "between")
            side[k].append(v)
        picks = [(v, "inside") for v in vs]
        for k, cand in side.items():
            picks += [(int(v), k) for v in
                      rng.choice(cand, min(2, len(cand)), replace=False)]
        ridx += [t] * len(picks)
        ci += [v for v, _ in picks]
        where += [k for _, k in picks]
    ridx = np.asarray(ridx, np.int32)
    ci = np.asarray(ci, np.int32)
    return r_d, r_h, r_i, ln, ridx, ci, deg[ci], hsh[ci], np.asarray(where)


@pytest.mark.parametrize("Lr", [1, 7, 128, 146, WIDE])
@pytest.mark.parametrize("seed", [0, 1])
def test_dense_row_search_matches_binary_search(Lr, seed):
    rng = np.random.default_rng([seed, Lr])
    r_d, r_h, r_i, ln, ridx, ci, qd, qh, where = _rows(rng, 24, Lr)
    lo = ridx * Lr
    hi = lo + ln[ridx]
    n_steps = int(np.ceil(np.log2(max(2, Lr)))) + 1
    pos = np.asarray(_lower_bound(
        jnp.asarray(r_d.ravel()), jnp.asarray(r_h.ravel()),
        jnp.asarray(r_i.ravel()), jnp.asarray(lo), jnp.asarray(hi),
        jnp.asarray(qd), jnp.asarray(qh), jnp.asarray(ci), n_steps))
    pos_c = np.clip(pos, 0, r_i.size - 1)
    hit = (pos < hi) & (r_i.ravel()[pos_c] == ci)

    found, slot = _dense_row_search(jnp.asarray(r_i)[ridx],
                                    jnp.asarray(ln[ridx]), jnp.asarray(ci))
    found, slot = np.asarray(found), np.asarray(slot)
    np.testing.assert_array_equal(found, hit)
    np.testing.assert_array_equal((lo + slot)[hit], pos[hit])
    # a miss points one past the row's last valid slot
    np.testing.assert_array_equal(slot[~hit], ln[ridx][~hit])
    # keys inside a row are hits, all others misses; every case is there
    np.testing.assert_array_equal(hit, where == "inside")
    assert {"inside", "before", "after"} <= set(where)
    assert (ln[ridx] == 0).any()
    if Lr > 7:
        assert "between" in set(where)


# --- the engine: dense search against the binary search it replaces ------

GRAPHS = {
    "clique8": lambda: generators.clique(8),
    "karate": lambda: generators.karate(),
    "rmat7": lambda: generators.rmat(7, 8, seed=1),
    "er": lambda: generators.erdos_renyi(150, 900, seed=2),
    "social": lambda: generators.temporal_social(120, 1200, seed=4),
}

def _labeled(g):
    """``g`` with a vertex label lane in front (id mod 5), which
    LabelTripleSet reads."""
    lab = (np.arange(g.n, dtype=np.int32) % 5)[:, None]
    vi = lab if g.vmeta_i is None else np.concatenate([lab, g.vmeta_i], 1)
    spec = dataclasses.replace(g.spec, v_int=("label",) + g.spec.v_int)
    return dataclasses.replace(g, spec=spec, vmeta_i=vi)


def _survey():
    return SurveyBundle([TriangleCount(), LabelTripleSet(capacity=1 << 12)])


def _digest(res) -> str:
    h = hashlib.sha256()
    for leaf in jax.tree.leaves(jax.device_get(res)):
        a = np.asarray(leaf)
        h.update(str(a.dtype).encode())
        h.update(a.tobytes())
    return h.hexdigest()


def _both(monkeypatch, run):
    """``run()`` with the dense search, then with the binary search forced
    (the bound patched to 0). ``run`` plans and surveys; each result comes
    with the number of times the engine built the dense search."""
    calls = []
    real = engine._dense_row_search
    monkeypatch.setattr(engine, "_dense_row_search",
                        lambda *a: calls.append(1) or real(*a))
    dense = run(), len(calls)
    monkeypatch.setattr(engine, "DENSE_SEARCH_MAX_ROW", 0)
    binary = run(), len(calls) - dense[1]
    return dense, binary


def _check(dense, binary):
    ((res_d, st_d, kind_d), built_d), ((res_b, st_b, kind_b), built_b) = (
        dense, binary)
    assert _digest(res_d) == _digest(res_b)
    # the dense tile also stages the gathered row, so it holds fewer slots
    for k in set(STAT_KEYS) - {"pull_tile_slots"}:
        assert st_d[k] == st_b[k], k
    assert st_d["exact"] is True and st_b["exact"] is True
    assert st_d["wedges_pulled"] > 0
    # the plan says which search runs, and the engine ran that one
    assert (kind_d, kind_b) == ("dense", "binary")
    assert built_d > 0 and built_b == 0


@pytest.mark.parametrize("S", [1, 3])
@pytest.mark.parametrize("name", list(GRAPHS))
def test_engine_dense_search_equals_binary_search(monkeypatch, name, S):
    g = _labeled(GRAPHS[name]())
    sv = _survey()

    def run():
        cfg, rep = plan_engine(g, S, sv, mode="pushpull", push_cap=64,
                               pull_q_cap=8)
        assert 0 < cfg.pull_row_cap <= BOUND
        gr, _ = shard_dodgr(g, S=S, hub_theta=cfg.hub_theta)
        return survey_push_pull(gr, sv, cfg) + (rep.pull_search,)

    _check(*_both(monkeypatch, run))


def test_delta_dense_search_equals_binary_search(monkeypatch):
    """A delta epoch over a base graph (the serve path: stable order,
    per-edge newness on the pulled rows)."""
    g = _labeled(generators.temporal_social(120, 1200, seed=4))
    order = np.argsort(g.emeta_f[:, 0], kind="stable")
    old, new = order[: g.m * 3 // 4], order[g.m * 3 // 4:]
    base = HostGraph(g.n, g.src[old], g.dst[old], g.spec, g.vmeta_i,
                     g.vmeta_f, None, g.emeta_f[old])
    dg = base.append_edges(g.src[new], g.dst[new], emeta_f=g.emeta_f[new])
    sv = _survey()
    gr, _ = shard_delta(dg, 2)

    def run():
        cfg, rep = plan_delta(dg, 2, sv, mode="pushpull", push_cap=64,
                              pull_q_cap=4)
        assert cfg.delta and cfg.n_pull_steps > 0
        state, st = survey_delta(gr, sv, cfg)
        return finalize_epochs(sv, state), st, rep.pull_search

    _check(*_both(monkeypatch, run))


def test_wide_rows_keep_the_binary_search(monkeypatch):
    """A plan whose reply rows are wider than the bound runs the binary
    search unchanged, and its report says so."""
    g = _labeled(generators.rmat(7, 8, seed=1))
    sv = _survey()
    cfg, _ = plan_engine(g, 2, sv, mode="pushpull", push_cap=64,
                         pull_q_cap=8)
    monkeypatch.setattr(engine, "DENSE_SEARCH_MAX_ROW",
                        cfg.pull_row_cap - 1)
    cfg, rep = plan_engine(g, 2, sv, mode="pushpull", push_cap=64,
                           pull_q_cap=8)
    assert rep.pull_search == "binary"
    gr, _ = shard_dodgr(g, S=2, hub_theta=cfg.hub_theta)
    built = []
    real = engine._dense_row_search
    monkeypatch.setattr(engine, "_dense_row_search",
                        lambda *a: built.append(1) or real(*a))
    _, st = survey_push_pull(gr, sv, cfg)
    assert not built
    assert st["pull_tile_slots"] >= st["wedges_pulled"] > 0


def test_plan_reports_the_pull_search():
    """The report names the search of each kind of plan: none without
    pulls, the kernel under ``use_pallas``, else the dense search on these
    narrow rows."""
    g = _labeled(generators.karate())
    sv = _survey()
    kinds = [plan_engine(g, 2, sv, mode=mode, push_cap=64, pull_q_cap=8,
                         use_pallas=pallas)[1].pull_search
             for mode, pallas in (("push", False), ("pushpull", True),
                                  ("pushpull", False))]
    assert kinds == ["", "pallas", "dense"]
