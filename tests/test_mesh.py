"""Real-mesh transport (ISSUE 7): the shard_map lowering over 8 forced
host devices must be bitwise-identical to the stacked dense/ragged paths —
results AND stats — for every built-in survey under push and pushpull,
including a hub (θ) cell and a delta-epoch run; and the compiled HLO's
collective payload must reconcile byte-exactly with the planned physical
wire volume (uniform caps equal the ``VolumeReport`` analytic bytes
exactly; ragged caps exceed them by precisely the rotation-round padding
minus the resident self diagonal). tests/conftest.py forces the device
count before jax initializes.
"""
import dataclasses

import numpy as np
import pytest
import jax

from repro.analysis.contracts import builtin_surveys
from repro.core.dodgr import shard_delta, shard_dodgr
from repro.core.engine import (finalize_epochs, make_survey_fn, survey_delta,
                               survey_push_only, survey_push_pull)
from repro.core.pushpull import plan_delta, plan_engine
from repro.core.surveys import TriangleCount
from repro.launch.mesh import make_shard_mesh
from repro.roofline import reconcile_collectives

from test_delta import (_append, _bundle, _empty_base, _labeled_graph,
                        _tree_equal, _ts_batches)
from test_exchange import _hub_theta_for

S = 8

pytestmark = pytest.mark.skipif(
    jax.device_count() < S,
    reason=f"needs {S} devices (conftest.py forces them unless jax "
           "initialized first)")


@pytest.fixture(scope="module")
def mesh():
    return make_shard_mesh(S)


@pytest.fixture(scope="module")
def graph():
    return _labeled_graph(96, 700, seed=4)


def _run_pair(g, survey, mode, mesh, hub_theta=0, **kw):
    """One stacked-ragged run and one mesh run of the same plan shape;
    returns both (result, stats) pairs."""
    run = survey_push_only if mode == "push" else survey_push_pull
    out = []
    for transport, m in (("ragged", None), ("mesh", mesh)):
        cfg, _ = plan_engine(g, S, survey, mode=mode, transport=transport,
                             hub_theta=hub_theta, push_cap=64, pull_q_cap=4,
                             **kw)
        gr, _ = shard_dodgr(g, S=S, hub_theta=cfg.hub_theta, orient="degree")
        out.append(run(gr, survey, cfg, mesh=m))
    return out


@pytest.mark.usefixtures("window_bound_pull_tiles")
@pytest.mark.parametrize("mode", ["push", "pushpull"])
@pytest.mark.parametrize("name,survey", builtin_surveys(n=96),
                         ids=[n for n, _ in builtin_surveys(n=96)])
def test_mesh_bitwise_identical_per_survey(graph, mesh, name, survey, mode):
    """Every built-in survey: mesh collectives == stacked ragged, result
    and stats, bit for bit."""
    (res_r, st_r), (res_m, st_m) = _run_pair(graph, survey, mode, mesh)
    assert _tree_equal(res_m, res_r), name
    assert _tree_equal(st_m, st_r), name


@pytest.mark.usefixtures("window_bound_pull_tiles")
@pytest.mark.parametrize("mode", ["push", "pushpull"])
def test_mesh_matches_dense_via_uniform_caps(graph, mesh, mode):
    """A uniform-cap mesh run (the literal all_to_all path) reproduces the
    historic dense transport bit for bit."""
    sv = _bundle(graph)
    run = survey_push_only if mode == "push" else survey_push_pull
    cfg_d, _ = plan_engine(graph, S, sv, mode=mode, transport="dense",
                           push_cap=64, pull_q_cap=4)
    gr, _ = shard_dodgr(graph, S=S, orient="degree")
    res_d, st_d = run(gr, sv, cfg_d)
    cfg_m = dataclasses.replace(cfg_d, transport="mesh")
    res_m, st_m = run(gr, sv, cfg_m, mesh=mesh)
    assert _tree_equal(res_m, res_d)
    assert _tree_equal(st_m, st_d)


@pytest.mark.usefixtures("window_bound_pull_tiles")
@pytest.mark.parametrize("mode", ["push", "pushpull"])
def test_mesh_hub_cell_bitwise(graph, mesh, mode):
    """Hub delegation (θ cell): replicated hub tables under shard_map ==
    the stacked ragged+hub run."""
    theta = _hub_theta_for(graph)
    sv = _bundle(graph)
    (res_r, st_r), (res_m, st_m) = _run_pair(graph, sv, mode, mesh,
                                             hub_theta=theta)
    assert st_m["wedges_hub"] > 0      # the θ cell actually delegated
    assert _tree_equal(res_m, res_r)
    assert _tree_equal(st_m, st_r)


def test_mesh_delta_epochs_bitwise(graph, mesh):
    """K=3 appended temporal batches through the delta engine: the mesh
    transport accumulates the same epoch states as stacked ragged."""
    splits = _ts_batches(graph, 3)
    results = []
    for transport, m in (("ragged", None), ("mesh", mesh)):
        sv = _bundle(graph)
        dg, state = None, None
        for idx in splits:
            dg = _append(dg if dg is not None else _empty_base(graph),
                         graph, idx)
            cfg, _ = plan_delta(dg, S, sv, mode="pushpull",
                                transport=transport, push_cap=64,
                                pull_q_cap=4)
            gr, _ = shard_delta(dg, S, hub_theta=cfg.hub_theta)
            state, st = survey_delta(gr, sv, cfg, state, mesh=m)
            assert st["exact"] is True
        results.append(finalize_epochs(sv, state))
    assert _tree_equal(results[0], results[1])


# ---------------------------------------------------------------------------
# HLO reconciliation: measured collective payload == planned wire volume


def _compiled_mesh(g, cfg, mesh, survey):
    gr, _ = shard_dodgr(g, S=S, hub_theta=cfg.hub_theta, orient="degree")
    cfg = dataclasses.replace(cfg, unroll_steps=True)   # cost-analysis mode
    fn = jax.jit(make_survey_fn(survey, cfg, mesh=mesh))
    return fn.lower(gr).compile(), cfg


def test_hlo_reconciles_ragged_mesh(graph, mesh):
    sv = TriangleCount()
    cfg, rep = plan_engine(graph, S, sv, mode="pushpull", transport="mesh",
                           push_cap=64, pull_q_cap=4)
    comp, cfg_u = _compiled_mesh(graph, cfg, mesh, sv)
    rec = reconcile_collectives(comp, cfg_u, S=S, volume=rep)
    assert rec["ok"], rec
    assert rec["extra_bytes"] == 0
    # the padding scalar is the sum of the per-round breakdown: every
    # scheduled wire round carries nonnegative padding, each ragged lane
    # one negative "resident" entry (the self-diagonal words that never
    # hit the wire) — the scalar itself may legitimately go negative once
    # the scheduler shrinks round padding below the resident diagonal
    wire_pad = [e for e in rec["padding_rounds"] if e["round"] >= 0]
    resident = [e for e in rec["padding_rounds"] if e["round"] < 0]
    assert all(e["bytes"] >= 0 for e in wire_pad)
    assert all(e["bytes"] < 0 for e in resident)
    assert rec["padding_bytes"] == sum(e["bytes"]
                                       for e in rec["padding_rounds"])
    # the schedule never exceeds the naive rotation's padded slot total
    for lane in rec["plan"]["schedules"].values():
        assert lane["wire_slots"] <= lane["naive_slots"]
    # the report stamps the same schedule the transport executes
    assert rep.sched_push_slots <= rep.naive_push_slots
    assert rep.sched_req_slots <= rep.naive_req_slots
    # per-op breakdown covers the whole measured payload
    ops_total = sum(o["bytes"] for o in rec["measured"]["ops"])
    assert ops_total >= rec["measured_bytes"]


def test_hlo_reconciles_uniform_mesh_exactly(graph, mesh):
    """Uniform caps: the all-to-all payload equals the dense plan's
    VolumeReport wire bytes word for word (padding == 0)."""
    sv = TriangleCount()
    cfg, rep = plan_engine(graph, S, sv, mode="pushpull", transport="dense",
                           push_cap=64, pull_q_cap=4)
    cfg = dataclasses.replace(cfg, transport="mesh")
    comp, cfg_u = _compiled_mesh(graph, cfg, mesh, sv)
    rec = reconcile_collectives(comp, cfg_u, S=S, volume=rep)
    assert rec["ok"], rec
    assert rec["padding_bytes"] == 0
    # uniform caps lower to literal all-to-all ops, no permute rounds
    assert rec["measured"]["per_kind"]["collective-permute"] == 0
    assert rec["measured"]["counts"]["all-to-all"] > 0


# ---------------------------------------------------------------------------
# guard rails


def test_mesh_plan_requires_mesh(graph):
    cfg, _ = plan_engine(graph, S, TriangleCount(), mode="push",
                         transport="mesh", push_cap=64)
    gr, _ = shard_dodgr(graph, S=S, orient="degree")
    with pytest.raises(ValueError, match="transport='mesh'"):
        survey_push_only(gr, TriangleCount(), cfg)


def test_mesh_device_count_must_match_shards(graph, mesh):
    cfg, _ = plan_engine(graph, 4, TriangleCount(), mode="push",
                         transport="mesh", push_cap=64)
    gr, _ = shard_dodgr(graph, S=4, orient="degree")
    with pytest.raises(ValueError, match="S=4 shards"):
        survey_push_only(gr, TriangleCount(), cfg, mesh=mesh)
