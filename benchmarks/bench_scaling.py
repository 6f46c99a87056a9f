"""Paper Fig. 4/5 analog: strong/weak scaling of the survey engine over
logical shard counts (single CPU device executes all shards, so the
figure of merit is work-rate |W₊|/(S·t) shape, matching Fig. 5's y-axis,
and the aggregation-opportunity trend, not wall-clock speedup).

The ``mesh/S*`` cells run the real-collective transport over S forced
host devices (``XLA_FLAGS=--xla_force_host_platform_device_count=8``
before jax initializes — cells emit a skipped marker otherwise) and
report the compiled HLO's measured collective payload next to the plan's,
via ``roofline.reconcile_collectives`` — plus the round scheduler's
physical structure (scheduled vs naive-rotation round counts, wire slot
totals, and wire padding bytes; ``run.py --compare`` fails on a >10%
``wire_padding_B`` regression). The ``mesh/skew/*`` cells are the
scheduler's acceptance shape: a hub-heavy R-MAT (skewed a/b/c) and its
DOULION-sparsified variant, whose scattered heavy (src, dest) pairs the
naive rotation pads worst — each cell also re-runs the stacked ragged
transport and reports bitwise identity of results and stats."""
from __future__ import annotations

import dataclasses
import time

from repro.core.dodgr import shard_dodgr
from repro.core.engine import survey_push_pull
from repro.core.pushpull import plan_engine
from repro.core.surveys import TriangleCount
from repro.graphs import generators


def run(quick=True):
    rows = []
    # strong scaling: fixed graph, growing shard count
    g = generators.rmat(9 if quick else 11, 16, seed=5)
    for S in (1, 2, 4, 8):
        gr, _ = shard_dodgr(g, S=S)
        cfg, rep = plan_engine(g, S, TriangleCount(), mode="pushpull",
                               push_cap=512, pull_q_cap=16)
        survey_push_pull(gr, TriangleCount(), cfg)  # warm
        t0 = time.time()
        _, st = survey_push_pull(gr, TriangleCount(), cfg)
        dt = time.time() - t0
        w = st["wedges_pushed"] + st["wedges_pulled"]
        rows.append((f"strong/S{S}", dt * 1e6, dict(
            wedges=int(w), comm_MB=round(rep.pushpull_bytes / 1e6, 2))))

    # weak scaling: graph grows with shard count (scale-k R-MAT per shard)
    base_scale = 7 if quick else 9
    for i, S in enumerate((1, 2, 4, 8)):
        g = generators.rmat(base_scale + i, 8, seed=3)
        gr, _ = shard_dodgr(g, S=S)
        cfg, _ = plan_engine(g, S, TriangleCount(), mode="pushpull",
                             push_cap=512, pull_q_cap=16)
        survey_push_pull(gr, TriangleCount(), cfg)  # warm
        t0 = time.time()
        _, st = survey_push_pull(gr, TriangleCount(), cfg)
        dt = time.time() - t0
        w = st["wedges_pushed"] + st["wedges_pulled"]
        rows.append((f"weak/S{S}/scale{base_scale+i}", dt * 1e6, dict(
            work_rate=round(w / S / max(dt, 1e-9)))))

    rows.extend(_mesh_rows(quick))
    return rows


def _schedule_fields(rep, rec):
    """Round-scheduler columns shared by every mesh cell: physical round
    structure vs the naive rotation, and the wire padding it saves."""
    sched = rec["plan"]["schedules"]
    return dict(
        sched_rounds=rep.sched_push_rounds + rep.sched_req_rounds,
        naive_rounds=rep.naive_push_rounds + rep.naive_req_rounds,
        sched_slots=rep.sched_push_slots + rep.sched_req_slots,
        naive_slots=rep.naive_push_slots + rep.naive_req_slots,
        wire_padding_B=sum(l["padding_bytes"] for l in sched.values()),
        naive_padding_B=sum(l["naive_padding_bytes"]
                            for l in sched.values()))


def _mesh_cell(name, g, S, mesh, check_bitwise=False, **plan_kw):
    """One real-collective cell: timed mesh run, HLO reconciliation, and
    the scheduler's padding accounting (optionally proving the mesh run
    bitwise-identical to the stacked ragged transport)."""
    import jax

    from repro.core.engine import make_survey_fn, sum_stats
    from repro.roofline import reconcile_collectives

    cfg, rep = plan_engine(g, S, TriangleCount(), mode="pushpull",
                           transport="mesh", push_cap=512, pull_q_cap=16,
                           **plan_kw)
    gr, _ = shard_dodgr(g, S=S, hub_theta=cfg.hub_theta)
    fn = jax.jit(make_survey_fn(TriangleCount(), cfg, mesh=mesh))
    res, st = jax.block_until_ready(fn(gr))  # warm + compile
    t0 = time.time()
    res, st = jax.block_until_ready(fn(gr))
    dt = time.time() - t0
    st = sum_stats(st)
    # reconcile on the unrolled (cost-analysis mode) compile
    cfg_u = dataclasses.replace(cfg, unroll_steps=True)
    comp = jax.jit(
        make_survey_fn(TriangleCount(), cfg_u, mesh=mesh)).lower(
        gr).compile()
    rec = reconcile_collectives(comp, cfg_u, S=S, volume=rep)
    w = st["wedges_pushed"] + st["wedges_pulled"]
    derived = dict(
        wedges=int(w),
        collective_B_per_dev=rec["measured_bytes"],
        planned_B_per_dev=rec["planned_bytes"],
        reconciled=bool(rec["ok"]),
        padding_B=rec["padding_bytes"],
        wire_MB=round(rep.wire_total_bytes / 1e6, 3),
        **_schedule_fields(rep, rec))
    if check_bitwise:
        # the stacked ragged transport of the same plan shape must produce
        # identical results and stats, bit for bit (but the pull tile
        # slots, which count what each lowering staged)
        cfg_r, _ = plan_engine(g, S, TriangleCount(), mode="pushpull",
                               transport="ragged", push_cap=512,
                               pull_q_cap=16, **plan_kw)
        fr = jax.jit(make_survey_fn(TriangleCount(), cfg_r))
        res_r, st_r = jax.block_until_ready(fr(gr))
        st_r = sum_stats(st_r)
        for s in (st, st_r):
            s.pop("pull_tile_slots")
        same = st == st_r and jax.tree.all(jax.tree.map(
            lambda a, b: bool((a == b).all()), res, res_r))
        derived["bitwise_vs_ragged"] = bool(same)
    return (name, dt * 1e6, derived)


def _mesh_rows(quick=True):
    """Real-collective cells: the same strong-scaling graph lowered through
    shard_map over S forced host devices, with the compiled HLO's collective
    payload reconciled against the plan (byte-exact, or the row is flagged),
    plus the skewed cells the round scheduler exists for.
    """
    import jax

    from repro.launch.mesh import make_shard_mesh

    rows = []
    g = generators.rmat(9 if quick else 11, 16, seed=5)
    for S in (2, 4, 8):
        if jax.device_count() < S:
            rows.append((f"mesh/S{S}", 0.0, dict(
                skipped=f"needs {S} devices; run with XLA_FLAGS="
                        f"--xla_force_host_platform_device_count={S}")))
            continue
        rows.append(_mesh_cell(f"mesh/S{S}", g, S, make_shard_mesh(S)))

    # the scheduler's acceptance cells: hub-heavy R-MAT (skewed a/b/c) and
    # its DOULION sparsification scatter heavy (src, dest) pairs across
    # rotation diagonals — the regime where diagonal rounds pad worst
    S = 8
    if jax.device_count() < S:
        rows.append(("mesh/skew/hub", 0.0, dict(
            skipped=f"needs {S} devices; run with XLA_FLAGS="
                    f"--xla_force_host_platform_device_count={S}")))
        return rows
    mesh = make_shard_mesh(S)
    gh = generators.rmat(9 if quick else 11, 16, seed=5,
                         a=0.75, b=0.055, c=0.055)
    rows.append(_mesh_cell("mesh/skew/hub", gh, S, mesh,
                           check_bitwise=True))
    # DOULION sparsification scatters the surviving heavy pairs across
    # rotation diagonals — the scheduler's biggest win (>= 2x padding
    # reduction at quick scale, asserted in the acceptance criteria)
    rows.append(_mesh_cell("mesh/skew/hub-doulion", gh, S, mesh,
                           check_bitwise=True, sample_p=0.05))
    return rows
