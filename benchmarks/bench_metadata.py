"""Paper Fig. 9 analog + lane projection: metadata's price, and the refund.

The paper attaches per-vertex degrees as metadata and counts
(⌈log₂d⌉) triples; throughput drops by a factor just under 2 vs dummy
metadata. We run the same pair of surveys over growing graphs (degree
vertex column + a float edge-weight column, so both metadata classes
exist) and report the throughput ratio per size — and, per survey, the
*projected* vs full-metadata exchanged volumes (MetaSpec lane
projection): exchanged bytes = measured entry counts × the survey-aware
planner's per-entry widths, compared against the same entries at
full-metadata widths, plus wall-clock of the same survey with projection
disabled (``project_meta=False``) at asserted-identical results."""
from __future__ import annotations

import time
from dataclasses import replace

import jax
import numpy as np

from repro.core.dodgr import shard_dodgr
from repro.core.engine import make_survey_fn, sum_stats
from repro.core.pushpull import plan_engine
from repro.core.surveys import DegreeTriples, TriangleCount
from repro.graphs import generators
from repro.graphs.csr import HostGraph, MetaSpec as GraphSpec


def _weighted_rmat(scale, fanout, seed):
    """R-MAT + degree vertex column + float edge-weight column."""
    g = generators.rmat(scale, fanout, seed=seed).with_degree_meta()
    spec = GraphSpec(v_int=g.spec.v_int, v_float=g.spec.v_float,
                     e_int=g.spec.e_int, e_float=g.spec.e_float + ("weight",))
    w = np.random.default_rng(seed).random(g.m, np.float32)[:, None]
    emeta_f = np.concatenate([g.emeta_f, w], axis=1)
    return HostGraph(g.n, g.src, g.dst, spec, g.vmeta_i, g.vmeta_f,
                     g.emeta_i, emeta_f)


def _timed(fn, gr, reps=3):
    jax.block_until_ready(fn(gr))          # compile + warm
    best = float("inf")
    for _ in range(reps):
        t0 = time.perf_counter()
        jax.block_until_ready(fn(gr))
        best = min(best, time.perf_counter() - t0)
    return best


def _bytes_at(rep, st):
    """Exchanged bytes from measured entries at the plan's entry widths."""
    return 4 * (float(st["wedges_pushed"]) * rep.push_entry_width
                + float(st["pull_requests"]) * (rep.request_width
                                                + rep.pull_header_width)
                + rep.pushpull_pull_rows * rep.pull_row_width)


def run(quick=True):
    rows = []
    scales = (7, 8) if quick else (8, 9, 10)
    for sc in scales:
        g = _weighted_rmat(sc, 8, seed=3)
        S = 4
        gr, _ = shard_dodgr(g, S=S)
        for name, survey in (("dummy", TriangleCount()),
                             ("degree_meta", DegreeTriples(deg_col=0))):
            cfg, rep = plan_engine(g, S, survey, mode="pushpull",
                                   push_cap=512, pull_q_cap=16)
            # full-width twin: same entries cost model ⇒ identical traversal,
            # full-metadata widths + projection disabled at runtime
            cfg_full, rep_full = plan_engine(g, S, None, mode="pushpull",
                                             push_cap=512, pull_q_cap=16)
            cfg_full = replace(cfg_full, project_meta=False)

            fn = jax.jit(make_survey_fn(survey, cfg))
            fn_full = jax.jit(make_survey_fn(survey, cfg_full))
            dt = _timed(fn, gr)
            dt_full = _timed(fn_full, gr)
            merged, st = jax.device_get(fn(gr))
            st = sum_stats(st)
            merged_full, _ = jax.device_get(fn_full(gr))
            res = survey.finalize(merged)
            res_full = survey.finalize(merged_full)
            assert str(res) == str(res_full), f"projection changed {name}"
            w = float(st["wedges_pushed"] + st["wedges_pulled"])
            proj_bytes = _bytes_at(rep, st)
            full_bytes = _bytes_at(rep_full, st)
            rows.append((f"metadata/scale{sc}/{name}", dt * 1e6, dict(
                wedges_per_s=round(w / max(dt, 1e-9)),
                push_entry_width=rep.push_entry_width,
                full_push_entry_width=rep.full_push_entry_width,
                exchanged_bytes=round(proj_bytes),
                exchanged_bytes_full=round(full_bytes),
                bytes_reduction=round(full_bytes / max(proj_bytes, 1), 2),
                noproject_us=round(dt_full * 1e6, 1),
                speedup_vs_full=round(dt_full / max(dt, 1e-9), 2),
            )))
    return rows
