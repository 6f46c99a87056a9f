#!/usr/bin/env python3
"""Chip smoke: TriPoll's triangle-survey path end to end on a TPU.

    python chip_smoke.py                # one chip, Graph500 R-MAT scale 14
    python chip_smoke.py --chips 4      # mesh transport at S=4 vs stacked S=4
    python chip_smoke.py --scale 16     # a larger graph (takes longer)

One chip, these phases in one process:

0. kernels — the compiled fold kernels against the XLA scatters they
   stand in for, bitwise;
1. one-shot survey — ``plan_engine(mode="pushpull")`` → ``shard_dodgr`` →
   the jitted survey program (``make_survey_fn``/``survey_with_fn``, what
   ``survey_push_pull`` runs) on a bundle of ``TriangleCount``,
   ``ClosureTime`` and ``LabelTripleSet``; the count is checked against an
   independent host count (``scipy.sparse``) and against ``mode="push"``,
   and on a scale-10 graph the whole bundle against the ``core/ref.py``
   oracle;
2. serving path — a ``SurveyService`` with resident ``TriangleCount`` and
   ``ClosureTime`` ingests three epochs of new edges (~1% of m each) and
   answers ``query()`` after each; resident answers must equal a recompute
   on the union graph, bitwise, and the host count of the union;
3. device report — compile and steady-state wall time of every program,
   compiled temporaries, each device's ``peak_bytes_in_use`` and the
   backend each survey's fold used.

With ``--chips 4`` it runs only the real-collective path
(``transport="mesh"`` under ``shard_map`` over four chips) for
``TriangleCount`` and the bundle, with dense and with ragged caps, and its
comparison, the stacked S=4 run on device 0, bitwise.

Graphs come from ``--seed``: R-MAT (a, b, c) = (0.57, 0.19, 0.19), edge
factor 16, one float timestamp lane on edges, one int label lane on
vertices. The default scale is what fits the run in 1200 s on one TPU
v5e: the engine's searches are chains of scalar gathers (~1e8
elements/s there), so at scale 16 one one-shot traversal took 27 s and
the service phase — four full traversals, three delta epochs and their
compiles — did not end within an 840 s run; at scale 20 one traversal
would take ~1,000 s. The four-chip run is compile-bound (eight
programs), so it uses scale 12.

Every phase line is JSON; the last line is ``{"ok": true, "device":
{...}}`` and is printed only when every check passed on a TPU. Without a TPU backend the script exits non-zero.
"""
from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from repro.core.dodgr import mesh_specs, shard_dodgr  # noqa: E402
from repro.core.engine import make_survey_fn, survey_with_fn  # noqa: E402
from repro.core.pushpull import plan_engine  # noqa: E402
from repro.core.ref import survey_triangles_ref  # noqa: E402
from repro.core.surveys import (ClosureTime, LabelTripleSet,  # noqa: E402
                                SurveyBundle, TriangleCount)
from repro.graphs.csr import MetaSpec  # noqa: E402
from repro.graphs.generators import rmat  # noqa: E402
from repro.serve import SurveyService  # noqa: E402
from repro.utils import enable_compile_cache  # noqa: E402

EDGE_FACTOR = 16
T_MAX = 1.0e6          # edge timestamps span [0, T_MAX) seconds
N_LABELS = 8           # vertex labels: C(8, 3) = 56 distinct label triples
LABEL_CAPACITY = 1024  # LabelTripleSet counting-table slots
ORACLE_SCALE = 10      # the core/ref.py oracle is pure Python
EPOCHS = 3
EPOCH_FRACTION = 0.01  # new edges per epoch, as a share of m
# push-only check: at S=1 every wedge rides one stream, so the default
# 256-slot push window would take m·d₊/256 supersteps (millions at scale 20)
PUSH_CHECK_CAP = 1 << 16


def check(cond, msg: str) -> None:
    """A failed check stops the run (``assert`` vanishes under -O)."""
    if not cond:
        raise RuntimeError(f"check failed: {msg}")


def emit(**rec) -> None:
    print(json.dumps(rec, default=_jsonable), flush=True)


def _jsonable(x):
    if isinstance(x, np.generic):
        return x.item()
    return str(x)


# ---------------------------------------------------------------------------
# workload


def make_graph(scale: int, seed: int):
    """Graph500 R-MAT at ``scale`` with a float timestamp lane on edges and
    an int label lane on vertices, all drawn from ``seed``."""
    spec = MetaSpec(v_int=("label",), e_float=("ts",))
    g = rmat(scale, EDGE_FACTOR, seed=seed, spec=spec)
    rng = np.random.default_rng([seed, 1])
    g.emeta_f = (rng.random((g.m, 1)) * T_MAX).astype(np.float32)
    g.vmeta_i = rng.integers(0, N_LABELS, (g.n, 1)).astype(np.int32)
    return g


def make_bundle():
    return SurveyBundle([TriangleCount(), ClosureTime(),
                         LabelTripleSet(capacity=LABEL_CAPACITY)])


def epoch_batch(g, seed: int, epoch: int):
    """~EPOCH_FRACTION·m new R-MAT edges with timestamps after every
    earlier epoch's."""
    rng = np.random.default_rng([seed, 2, epoch])
    scale = int(g.n).bit_length() - 1
    fresh = rmat(scale, 1, seed=seed + 1000 + epoch)
    k = min(fresh.m, max(1, int(EPOCH_FRACTION * g.m)))
    pick = rng.choice(fresh.m, k, replace=False)
    ts = T_MAX * (1 + epoch) + rng.random(k) * T_MAX
    return fresh.src[pick], fresh.dst[pick], ts.astype(np.float32)[:, None]


# ---------------------------------------------------------------------------
# independent references


def host_triangle_count(g, chunk_rows: int = 1 << 13) -> int:
    """Triangle count by sparse matrix products on the host, independent of
    the engine: orient every edge from lower to higher (degree, id) — an
    acyclic orientation, so each triangle is one directed path p→q→r with
    the chord p→r — then sum (A·A) ∘ A over row chunks, on a thread per
    core (scipy's sparse product releases the interpreter lock)."""
    import os
    from concurrent.futures import ThreadPoolExecutor

    import scipy.sparse as sp

    deg = g.degrees()
    ks = deg[g.src] * g.n + g.src
    kd = deg[g.dst] * g.n + g.dst
    p = np.where(ks < kd, g.src, g.dst)
    q = np.where(ks < kd, g.dst, g.src)
    A = sp.csr_matrix((np.ones(g.m, np.int32), (p, q)), shape=(g.n, g.n))

    def rows(r0):
        Ar = A[r0:r0 + chunk_rows]
        return int((Ar @ A).multiply(Ar).sum())

    with ThreadPoolExecutor(os.cpu_count()) as pool:
        return sum(pool.map(rows, range(0, g.n, chunk_rows)))


def oracle_bundle(g) -> dict:
    """The bundle's answer from the ``core/ref.py`` enumeration oracle."""
    joint = np.zeros((64, 64), np.int64)
    labels: dict = {}

    def bucket(dt):
        return int(np.clip(np.ceil(np.log2(max(dt, 1.0))), 0, 63))

    def cb(p, q, r, meta):
        ts = sorted(float(m[0]) for m in meta["e_f"])
        joint[bucket(ts[1] - ts[0]), bucket(ts[2] - ts[0])] += 1
        lab = tuple(sorted(int(m[0]) for m in meta["v_i"]))
        if lab[0] != lab[1] and lab[1] != lab[2]:
            labels[lab] = labels.get(lab, 0) + 1

    n = survey_triangles_ref(g, cb)
    return dict(count=n, joint=joint, labels=labels)


def check_label_set(res: dict, want: dict, what: str) -> None:
    """Every key outside a collided slot carries exactly the oracle's
    count, and the collided slots hold exactly the remaining mass."""
    counts = res["counts"]
    for key, c in counts.items():
        check(want.get(key) == c, f"{what}: label triple {key} counted {c}, "
              f"oracle {want.get(key)}")
    rest = sum(c for key, c in want.items() if key not in counts)
    check(res["count_in_collided"] == rest,
          f"{what}: collided slots hold {res['count_in_collided']}, "
          f"oracle leaves {rest}")


# ---------------------------------------------------------------------------
# timed programs


def place(gr, mesh=None):
    """Put the sharded graph on the device(s): whole on the default device,
    or one shard per device of ``mesh`` (hub tables replicated)."""
    if mesh is None:
        return jax.device_put(gr)
    from jax.sharding import NamedSharding, PartitionSpec

    shardings = jax.tree.map(lambda s: NamedSharding(mesh, s),
                             mesh_specs(gr, mesh.axis_names[-1]),
                             is_leaf=lambda x: isinstance(x, PartitionSpec))
    return jax.device_put(gr, shardings)


def run_survey(gr, survey, cfg, mesh=None):
    """Compile, run once to completion and finalize one survey program.
    Returns ``(result, stats, timing)``."""
    fn = jax.jit(make_survey_fn(survey, cfg, mesh=mesh))
    t0 = time.perf_counter()
    compiled = fn.lower(gr).compile()
    t1 = time.perf_counter()
    out = jax.block_until_ready(compiled(gr))
    t2 = time.perf_counter()
    result, stats = survey_with_fn(gr, survey, cfg, lambda _: out)
    mem = compiled.memory_analysis()
    timing = dict(compile_s=t1 - t0, run_s=t2 - t1,
                  temp_bytes=getattr(mem, "temp_size_in_bytes", None))
    return result, stats, timing


def fold_backends(survey) -> dict:
    """Which implementation each member's fold runs on this backend."""
    out = {}
    for name, s in zip(survey.names, survey.surveys):
        if hasattr(s, "cs"):
            out[name] = "pallas" if s.cs.uses_pallas() else "xla-scatter"
        elif isinstance(s, TriangleCount):
            out[name] = "xla-reduce"
        else:
            out[name] = "xla-scatter"
    return out


def device_report() -> list:
    """Per-device peak memory where the backend reports it."""
    rows = []
    for d in jax.devices():
        stats = d.memory_stats() or {}
        rows.append(dict(id=d.id, kind=d.device_kind,
                         peak_bytes_in_use=stats.get("peak_bytes_in_use"),
                         bytes_limit=stats.get("bytes_limit")))
    return rows


# ---------------------------------------------------------------------------
# phases


def phase_kernels(seed: int, batch: int = 1 << 16) -> dict:
    """The fold kernels against the XLA scatters they stand in for,
    bitwise, at the widths the surveys use: ``fold_count_max`` as
    ``LabelTripleSet``'s table (3 key columns + 2 check-hash columns), and
    ``ring_set`` as ``Enumerate``'s ring — against the last-writer oracle
    on contested slots and against ``.at[].set`` where every slot has one
    writer (the only case XLA defines)."""
    from functools import partial

    from repro import kernels
    from repro.kernels.fold_scatter.ops import fold_count_max, ring_set
    from repro.kernels.fold_scatter.ref import (fold_count_max_ref,
                                                ring_set_ref)

    interpret = not kernels.compiled()
    rng = np.random.default_rng([seed, 3])
    cap, W = LABEL_CAPACITY, 5
    slots = rng.integers(-1, cap, batch).astype(np.int32)   # -1: masked
    amts = rng.integers(0, 7, batch).astype(np.int32)
    rows = rng.integers(0, 1 << 32, (batch, W), dtype=np.uint64)
    rows = np.where(slots[:, None] >= 0, rows, 0).astype(np.uint32)
    got = jax.jit(partial(fold_count_max, capacity=cap,
                          interpret=interpret))(slots, amts, rows)
    want = jax.jit(partial(fold_count_max_ref, capacity=cap))(slots, amts,
                                                               rows)
    check(all(np.array_equal(a, b) for a, b in zip(got, want)),
          "fold_count_max differs from the scatter-add/scatter-max path")

    ring = 4096
    prior = jnp.asarray(rng.integers(-1, 1 << 20, (ring, 3)), jnp.int32)
    tri = jnp.asarray(rng.integers(0, 1 << 20, (batch, 3)), jnp.int32)
    contested = rng.integers(0, ring + 1, batch).astype(np.int32)  # ring: drop
    k = min(ring // 2, batch)
    unique = rng.permutation(ring)[:k].astype(np.int32)
    run = jax.jit(partial(ring_set, capacity=ring, interpret=interpret))
    check(np.array_equal(run(prior, contested, tri),
                         jax.jit(partial(ring_set_ref, capacity=ring))(
                             prior, contested, tri)),
          "ring_set differs from the last-writer oracle")
    check(np.array_equal(run(prior, unique, tri[:k]),
                         prior.at[unique].set(tri[:k])),
          "ring_set differs from the XLA scatter-set")
    return dict(batch=batch, fold_count_max=dict(capacity=cap, width=W),
                ring_set=dict(capacity=ring),
                mode="interpret" if interpret else "compiled")


def phase_oracle(seed: int, scale: int = ORACLE_SCALE) -> dict:
    """Bundle vs the enumeration oracle on a small graph."""
    g = make_graph(scale, seed)
    bundle = make_bundle()
    cfg, _ = plan_engine(g, 1, bundle, mode="pushpull")
    gr, _ = shard_dodgr(g, 1, hub_theta=cfg.hub_theta, orient="degree")
    res, stats, timing = run_survey(place(gr), bundle, cfg)
    want = oracle_bundle(g)
    check(stats["exact"], "oracle graph: run flagged inexact")
    check(res["TriangleCount"] == want["count"],
          f"oracle graph: count {res['TriangleCount']} != {want['count']}")
    check(np.array_equal(res["ClosureTime"]["joint"], want["joint"]),
          "oracle graph: ClosureTime histogram differs from the oracle")
    check_label_set(res["LabelTripleSet"], want["labels"], "oracle graph")
    return dict(scale=scale, n=g.n, m=g.m, triangles=want["count"],
                **timing)


def phase_survey(g) -> dict:
    """One-shot push-pull bundle at full scale, checked against the host
    count and a push-only run."""
    bundle = make_bundle()
    t0 = time.perf_counter()
    cfg, report = plan_engine(g, 1, bundle, mode="pushpull")
    t1 = time.perf_counter()
    gr, _ = shard_dodgr(g, 1, hub_theta=cfg.hub_theta, orient="degree")
    t2 = time.perf_counter()
    gr = jax.block_until_ready(place(gr))
    t3 = time.perf_counter()
    res, stats, timing = run_survey(gr, bundle, cfg)
    t4 = time.perf_counter()
    host = host_triangle_count(g)
    t5 = time.perf_counter()

    n = res["TriangleCount"]
    check(stats["exact"], "push-pull run flagged inexact")
    check(n == host, f"push-pull count {n} != host count {host}")
    check(int(res["ClosureTime"]["joint"].sum()) == n,
          "ClosureTime histogram does not hold every triangle once")
    lab = res["LabelTripleSet"]
    check(sum(lab["counts"].values()) + lab["count_in_collided"] <= n,
          "LabelTripleSet counted more triangles than exist")

    cfg_p, _ = plan_engine(g, 1, TriangleCount(), mode="push",
                           push_cap=PUSH_CHECK_CAP)
    res_p, stats_p, timing_p = run_survey(gr, TriangleCount(), cfg_p)
    check(stats_p["exact"], "push-only run flagged inexact")
    check(res_p == n, f"push-only count {res_p} != push-pull count {n}")
    return dict(
        triangles=n, plan_s=t1 - t0, shard_s=t2 - t1, place_s=t3 - t2,
        host_count_s=t5 - t4, pushpull=timing, push=timing_p,
        wedges=report.wedges_total, pulled_wedges=report.pulled_wedges,
        d_plus_max=gr.d_plus_max, e_cap=gr.e_cap,
        n_push_steps=cfg.n_push_steps, n_pull_steps=cfg.n_pull_steps,
        pull_edge_cap=cfg.pull_edge_cap, fold_backends=fold_backends(bundle))


def phase_service(g, seed: int, epochs: int = EPOCHS) -> dict:
    """SurveyService with resident surveys over ``epochs`` ingested
    batches; resident answers must equal a recompute on the union."""
    t0 = time.perf_counter()
    svc = SurveyService(g, 1, resident={"TriangleCount": TriangleCount(),
                                        "ClosureTime": ClosureTime()})
    rows = [dict(epoch=0, start_s=time.perf_counter() - t0)]
    try:
        for ep in range(1, epochs + 1):
            src, dst, ts = epoch_batch(g, seed, ep)
            t1 = time.perf_counter()
            svc.append_edges(src, dst, emeta_f=ts, wait=True)
            t2 = time.perf_counter()
            res, stats = svc.query(SurveyBundle([TriangleCount(),
                                                 ClosureTime()]))
            t3 = time.perf_counter()
            resident = svc.resident_answers()
            union = svc.snapshot.union
            host = host_triangle_count(union)
            n = resident["TriangleCount"]
            check(stats["exact"], f"epoch {ep}: query flagged inexact")
            check(n == res["TriangleCount"] == host,
                  f"epoch {ep}: resident count {n}, recompute "
                  f"{res['TriangleCount']}, host {host}")
            check(np.array_equal(resident["ClosureTime"]["joint"],
                                 res["ClosureTime"]["joint"]),
                  f"epoch {ep}: resident ClosureTime != recompute")
            rows.append(dict(epoch=ep, new_edges=len(src), m=union.m,
                             triangles=n, ingest_s=t2 - t1,
                             query_s=t3 - t2))
        rows.append(dict(ingest_stats=svc.ingest_stats()))
    finally:
        svc.close()
    return dict(epochs=rows)


def phase_mesh(g, S: int = 4) -> dict:
    """The mesh transport at ``S`` shards (one per device) vs the stacked
    run of the same plan on the default device, bitwise, for
    ``TriangleCount`` and the bundle with dense and with ragged caps.
    Mesh runs go first so the per-device peaks they leave show the graph
    sharded; the stacked runs then hold it whole on device 0."""
    import dataclasses

    from repro.launch.mesh import make_shard_mesh

    mesh = make_shard_mesh(S)
    cases = []
    for name, mk in (("TriangleCount", TriangleCount),
                     ("bundle", make_bundle)):
        for caps in ("dense", "ragged"):
            survey = mk()
            cfg, _ = plan_engine(g, S, survey, mode="pushpull",
                                 transport=caps)
            cfg_m = (dataclasses.replace(cfg, transport="mesh")
                     if caps == "dense"
                     else plan_engine(g, S, survey, mode="pushpull",
                                      transport="mesh")[0])
            gr, _ = shard_dodgr(g, S, hub_theta=cfg.hub_theta,
                                orient="degree")
            cases.append((name, caps, survey, cfg, cfg_m, gr))
    mesh_out = {}
    for name, caps, survey, _, cfg_m, gr in cases:
        mesh_out[name, caps] = run_survey(place(gr, mesh), survey, cfg_m,
                                          mesh=mesh)
    peaks_mesh = device_report()
    rows = []
    for name, caps, survey, cfg, _, gr in cases:
        res_m, st_m, t_m = mesh_out[name, caps]
        res_s, st_s, t_s = run_survey(place(gr), survey, cfg)
        check(st_m["exact"] and st_s["exact"], f"{name}/{caps}: inexact")
        check(_tree_equal(res_m, res_s),
              f"{name}/{caps}: mesh result != stacked result")
        check(_tree_equal(st_m, st_s),
              f"{name}/{caps}: mesh stats != stacked stats")
        rows.append(dict(survey=name, caps=caps, bitwise=True,
                         triangles=(res_m if name == "TriangleCount"
                                    else res_m["TriangleCount"]),
                         mesh=t_m, stacked=t_s))
    return dict(S=S, cases=rows, peaks_after_mesh=peaks_mesh,
                peaks_after_stacked=device_report())


def _tree_equal(a, b) -> bool:
    """Bitwise equality over nested dict/array/scalar results."""
    if isinstance(a, dict):
        return set(a) == set(b) and all(_tree_equal(a[k], b[k]) for k in a)
    if isinstance(a, np.ndarray) or isinstance(b, np.ndarray):
        a, b = np.asarray(a), np.asarray(b)
        return a.shape == b.shape and bool((a == b).all())
    return a == b


# ---------------------------------------------------------------------------


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--scale", type=int, default=None,
                    help="R-MAT scale (default 14; 12 with --chips 4)")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    args = ap.parse_args(argv)

    if jax.default_backend() != "tpu":
        print(f"chip_smoke: no TPU backend (JAX found "
              f"{jax.default_backend()!r}); nothing was run",
              file=sys.stderr)
        return 2
    devs = jax.devices()
    check(len(devs) >= args.chips,
          f"--chips {args.chips} needs {args.chips} devices, "
          f"JAX found {len(devs)}")
    emit(phase="start", compile_cache=str(enable_compile_cache()),
         jax=jax.__version__, devices=len(devs), kind=devs[0].device_kind)

    scale = args.scale or (12 if args.chips == 4 else 14)
    t0 = time.perf_counter()
    g = make_graph(scale, args.seed)
    emit(phase="graph", scale=scale, seed=args.seed, n=g.n, m=g.m,
         generate_s=time.perf_counter() - t0)

    if args.chips == 4:
        emit(phase="mesh", **phase_mesh(g, S=4))
    else:
        emit(phase="kernels", **phase_kernels(args.seed))
        emit(phase="oracle", **phase_oracle(args.seed))
        emit(phase="survey", **phase_survey(g))
        emit(phase="service", **phase_service(g, args.seed))
    emit(phase="device", devices=device_report(),
         total_s=time.perf_counter() - t0)

    d = jax.devices()[0]
    print(json.dumps({"ok": True, "device": {
        "platform": d.platform, "kind": d.device_kind,
        "count": len(jax.devices())}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
