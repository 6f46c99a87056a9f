"""Delta engine vs full recompute on streaming temporal_social batches.

The workload is the streaming shape the delta engine exists for: a large
history ingested once, then small timestamped batches appended one epoch at
a time. Each epoch we measure (a) the warm device wall-clock of
``survey_delta`` over the delta frontier and (b) the planner's exact
exchanged-byte volume, against one full recompute of the final snapshot —
the ISSUE acceptance is both strictly below full recompute at the final
epoch. ``derived`` also reports the wedge restriction (gen_wedges vs the
union's wedge count) and the cumulative delta-vs-recompute advantage a
serving system would see (every epoch answered incrementally vs re-polling
the snapshot each epoch).
"""
from __future__ import annotations

import time

import jax
import numpy as np

from repro.core.dodgr import shard_delta, shard_dodgr
from repro.core.engine import (finalize_epochs, make_survey_fn, sum_stats,
                               survey_delta)
from repro.core.pushpull import plan_delta, plan_engine
from repro.core.surveys import ClosureTime, SurveyBundle, TriangleCount
from repro.graphs import generators
from repro.graphs.csr import HostGraph


def _timed(fn, gr, reps=3):
    jax.block_until_ready(fn(gr))          # compile + warm
    best = float("inf")
    for _ in range(reps):
        t0 = time.perf_counter()
        jax.block_until_ready(fn(gr))
        best = min(best, time.perf_counter() - t0)
    return best


def _survey():
    # a streaming poll: count + closure-time histogram in one pass
    return SurveyBundle([TriangleCount(), ClosureTime(ts_col=0)])


def run(quick=True):
    rows = []
    S = 4
    n, m = (1500, 30000) if quick else (4000, 120000)
    K = 4
    batch_sz = max(50, n // 10)
    g = generators.temporal_social(n, m, seed=1)
    order = np.argsort(g.emeta_f[:, 0], kind="stable")
    n_hist = len(order) - K * batch_sz
    hist = order[:n_hist]
    splits = [order[n_hist + i * batch_sz: n_hist + (i + 1) * batch_sz]
              for i in range(K)]

    base = HostGraph(g.n, np.zeros(0, np.int64), np.zeros(0, np.int64),
                     g.spec, g.vmeta_i, g.vmeta_f)
    dg = base.append_edges(g.src[hist], g.dst[hist],
                           emeta_i=g.emeta_i[hist], emeta_f=g.emeta_f[hist])

    # --- ingest the history once (epoch 1), unmeasured ---
    gr, _ = shard_delta(dg, S)
    cfg, _ = plan_delta(dg, S, _survey(), mode="pushpull", push_cap=1024)
    state, _ = survey_delta(gr, _survey(), cfg)

    # --- full recompute of the FINAL snapshot (the baseline each epoch
    # would pay without the delta engine) ---
    for idx in splits:
        dg = dg.append_edges(g.src[idx], g.dst[idx],
                             emeta_i=g.emeta_i[idx], emeta_f=g.emeta_f[idx])
    u = dg.union()
    gr_u, _ = shard_dodgr(u, S, orient="stable")
    cfg_u, rep_u = plan_engine(u, S, _survey(), mode="pushpull",
                               push_cap=1024, orient="stable")
    t_full = _timed(jax.jit(make_survey_fn(_survey(), cfg_u)), gr_u)

    # --- replay the stream, measuring each epoch ---
    dg = base.append_edges(g.src[hist], g.dst[hist],
                           emeta_i=g.emeta_i[hist], emeta_f=g.emeta_f[hist])
    t_delta_total = 0.0
    bytes_delta_total = 0
    for idx in splits:
        dg = dg.append_edges(g.src[idx], g.dst[idx],
                             emeta_i=g.emeta_i[idx], emeta_f=g.emeta_f[idx])
        gr_d, _ = shard_delta(dg, S)
        cfg_d, rep_d = plan_delta(dg, S, _survey(), mode="pushpull",
                                  push_cap=1024)
        survey = _survey()
        fn = jax.jit(make_survey_fn(survey, cfg_d))
        t_epoch = _timed(fn, gr_d)
        # fold the epoch with the already-compiled fn (what survey_delta
        # would do, minus a redundant re-jit)
        merged, st = jax.device_get(fn(gr_d))
        st = sum_stats(st)
        state = merged if state is None else survey.merge_epochs(state, merged)
        t_delta_total += t_epoch
        bytes_delta_total += rep_d.pushpull_bytes
        rows.append((f"streaming/epoch{dg.epoch}/S{S}", t_epoch * 1e6, dict(
            batch_edges=int(len(idx)),
            new_triangles=int(st["tris_push"] + st["tris_pull"]),
            gen_wedges=rep_d.gen_wedges,
            union_wedges=rep_u.gen_wedges,
            delta_bytes=rep_d.pushpull_bytes,
            full_bytes=rep_u.pushpull_bytes,
            recompute_us=round(t_full * 1e6, 1),
            speedup=round(t_full / t_epoch, 2),
            byte_reduction=round(rep_u.pushpull_bytes
                                 / max(1, rep_d.pushpull_bytes), 2),
        )))

    # sanity: the accumulated stream equals the full snapshot
    res = finalize_epochs(_survey(), state)
    total = int(res["TriangleCount"])
    rows.append((f"streaming/total/S{S}", t_delta_total * 1e6, dict(
        triangles=total,
        epochs=K,
        recompute_total_us=round(K * t_full * 1e6, 1),
        stream_speedup=round(K * t_full / t_delta_total, 2),
        stream_byte_reduction=round(K * rep_u.pushpull_bytes
                                    / max(1, bytes_delta_total), 2),
    )))
    return rows
