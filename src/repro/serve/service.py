"""``SurveyService`` — the long-lived, plan-cached survey front door.

One instance owns a graph snapshot and amortizes the whole one-shot
pipeline across requests and epochs:

* **queries** hit the :class:`~repro.serve.plan_cache.PlanCache` first —
  a content-key hit replays the cached (plan, shards, jitted closure)
  triplet and, for an exact repeat, finalizes the memoized warm-up state
  in O(answer); a miss pays plan + shard + compile once and caches it;
* **compiles** are shared one level deeper: jitted ``make_survey_fn``
  closures are keyed by ``(survey fingerprint, cfg with epoch := 0)``
  because ``cfg.epoch`` never enters the traced program, graph epochs are
  normalized the same way at call time, and — under the default
  ``cap_policy="bucket"`` — every planned capacity is rounded up to the
  geometric bucket grid with session high-water hysteresis on the delta
  path, so epochs whose autotuned caps merely *drift* reuse the XLA
  executable outright (hit/recompile counters ride ``Snapshot``, query
  stats, and :meth:`SurveyService.ingest_stats`);
* **restarts** warm-start: :meth:`SurveyService.checkpoint` persists the
  plan cache next to the epoch state (``.plans.npz``) and
  :meth:`SurveyService.restore` preloads it, so the first query after a
  restart answers from the memoized warm-up state without replanning,
  and XLA executables come back from JAX's on-disk compilation cache
  (:func:`repro.utils.enable_compile_cache`);
* **ingestion** rides :class:`~repro.serve.ingest.IngestPipeline`:
  ``append_edges`` batches become delta epochs on a worker thread
  (sharded with :class:`~repro.core.dodgr.HubTableCache` reuse, resident
  surveys advanced incrementally) while queries keep answering from the
  last merged snapshot;
* **tenants** coalesce: :meth:`SurveyService.query_coalesced` folds many
  tenants' surveys into one traversal via :mod:`repro.serve.coalesce`.

Every path is bitwise-identical to the one-shot ``survey_*`` calls with
``orient="stable"`` (the orientation the service fixes so delta epochs
and hub-table reuse stay exact) — tests/test_serve.py asserts
warm == cold == solo == one-shot.
"""
from __future__ import annotations

import threading
import time
from dataclasses import dataclass, replace
from typing import Any, Sequence

import jax
import numpy as np

from repro.core import engine
from repro.core.dodgr import HubTableCache, shard_delta, shard_dodgr
from repro.core.engine import finalize_epochs, make_survey_fn, survey_with_fn
from repro.core.pushpull import (delta_token, graph_token, plan_content_key,
                                 plan_delta, plan_engine, survey_fingerprint)
from repro.core.surveys import Survey, SurveyBundle
from repro.graphs.csr import DeltaGraph, HostGraph
from repro.serve.coalesce import (TenantRequest, coalesce, extract,
                                  warn_if_order_sensitive)
from repro.serve.ingest import IngestPipeline
from repro.serve.plan_cache import (CacheEntry, PlanCache, entry_nbytes,
                                    load_plan_cache, save_plan_cache)
from repro.utils import enable_compile_cache


def _graph_signature(gr) -> tuple:
    """Everything jit keys a call on: the pytree structure (which carries
    every static meta field of the registered dataclass) plus each leaf's
    (shape, dtype). Two graphs with equal signatures reuse one compiled
    executable under the same jitted closure."""
    leaves, treedef = jax.tree_util.tree_flatten(gr)
    return (str(treedef),
            tuple((tuple(leaf.shape), str(leaf.dtype)) for leaf in leaves))


def _plans_path(path) -> str:
    """Sidecar plan-cache file next to an epoch-state checkpoint."""
    p = str(path)
    if p.endswith(".npz"):
        p = p[:-4]
    return p + ".plans.npz"


@dataclass(frozen=True)
class Snapshot:
    """One immutable serving epoch: queries and resident answers read a
    single pointer to this, so an ingest swap is atomic."""

    epoch: int
    token: str               # content token of the union as of this epoch
    union: HostGraph
    dg: DeltaGraph | None    # None before the first appended batch
    resident_state: Any      # resident bundle's merged accumulator (or None)
    jit_hits: int = 0        # cumulative executable reuses as of this swap
    jit_recompiles: int = 0  # cumulative fresh traces as of this swap


class SurveyService:
    """Serve triangle surveys from a cached, epoch-pipelined graph.

    ``resident`` surveys (``{name: Survey}``) are answered *incrementally*:
    their state is advanced by each ingested batch through the delta engine
    and rendered in O(answer) by :meth:`resident_answers`, never paying a
    full re-traversal. Ad-hoc :meth:`query` surveys run against the current
    snapshot through the plan cache.

    The service fixes ``orient="stable"`` — the epoch-stable orientation
    key is what makes delta accumulation and hub-table reuse bitwise-exact
    across ingestion.
    """

    def __init__(self, graph: HostGraph, S: int, *,
                 mode: str = "pushpull",
                 transport: str = "dense",
                 push_cap: int = 256,
                 pull_q_cap: int | None = None,
                 hub_theta: int | str = 0,
                 hub_wedge_cap: int = 256,
                 max_hubs: int = 1024,
                 sample_p: float = 1.0,
                 sample_seed: int = 0,
                 mesh=None,
                 cache_bytes: int | None = None,
                 resident: dict[str, Survey] | None = None,
                 max_pending: int = 64,
                 token: str | None = None,
                 epoch: int = 0,
                 cap_policy: str = "bucket",
                 preload_plans: Sequence[CacheEntry] | None = None):
        if sample_p < 1.0 and resident:
            raise ValueError("resident surveys ride the delta engine, which "
                             "rejects DOULION sampling — serve sampled "
                             "questions as ad-hoc queries instead")
        if cap_policy not in ("exact", "bucket"):
            raise ValueError(f"cap_policy must be 'exact' or 'bucket', "
                             f"got {cap_policy!r}")
        enable_compile_cache()
        self.S = int(S)
        self.mode = mode
        self.transport = transport
        self.push_cap = push_cap
        self.pull_q_cap = pull_q_cap
        self.hub_theta = hub_theta
        self.hub_wedge_cap = hub_wedge_cap
        self.max_hubs = max_hubs
        self.sample_p = float(sample_p)
        self.sample_seed = int(sample_seed)
        # "bucket" (the default) rounds every planned capacity up to the
        # geometric grid (utils.bucket_cap) so epochs whose autotuned caps
        # drift inside one bucket reuse the same compiled executable;
        # results are bitwise-identical to "exact" (the engine masks all
        # padded slots) at ≤ 25% wire padding per capacity
        self.cap_policy = cap_policy
        self._mesh = mesh
        self.cache = PlanCache(cache_bytes)
        self._jit_cache: dict = {}
        self._jit_lock = threading.Lock()
        self._compiled: set = set()    # (jit key, graph signature) seen
        self._jit_hits = 0
        self._jit_recompiles = 0
        self._epochs_applied = 0
        # session shape hysteresis (delta path, cap_policy="bucket" only):
        # the last delta config is fed back to the planner (promote_from)
        # to floor every shape cap, so an epoch whose frontier shrank
        # keeps the previous shapes — the planner re-measures
        # pull_edge_cap under the promoted pull windows, which is what
        # keeps promotion pure padding — and rung-boundary jitter costs
        # at most one recompile per boundary instead of one per
        # oscillation
        self._shape_hw = None          # last delta EngineConfig
        self._ecap_hw = 0
        self._dmax_hw = 0
        if preload_plans:
            for entry in preload_plans:
                self.cache.insert(entry)

        self._resident = (SurveyBundle(list(resident.values()),
                                       names=list(resident.keys()))
                          if resident else None)
        self._hub_cache = (HubTableCache(graph)
                           if self._resident is not None and
                           (hub_theta == "auto" or int(hub_theta) >= 1)
                           else None)

        tok = token if token is not None else graph_token(graph)
        self._snapshot = Snapshot(epoch=int(epoch), token=tok, union=graph,
                                  dg=None, resident_state=None)
        if self._resident is not None:
            entry, _, _ = self._prepare(self._resident)
            if self.cap_policy == "bucket":
                # frontier max d₊ can never exceed the union's (touched
                # vertices carry their full adjacency rows), so seeding the
                # session high-water from the warm-up shard removes one
                # whole recompile source — and costs nothing: d_plus_max
                # is only a fallback window when a plan leaves
                # pull_row_cap=0
                self._dmax_hw = entry.gr.d_plus_max
            self._snapshot = replace(self._snapshot,
                                     resident_state=entry.raw[0])
        self._ingest = IngestPipeline(self._apply_batch,
                                      max_pending=max_pending)

    # -- snapshot queries (plan-cached) -----------------------------------

    @property
    def snapshot(self) -> Snapshot:
        return self._snapshot

    @property
    def epoch(self) -> int:
        return self._snapshot.epoch

    def content_key(self, survey: Survey, snap: Snapshot | None = None) -> str:
        snap = snap or self._snapshot
        return plan_content_key(
            snap.token, self.S, survey, mode=self.mode,
            transport=self.transport, hub_theta=self.hub_theta,
            sample_p=self.sample_p, sample_seed=self.sample_seed,
            orient="stable", epoch=snap.epoch, cap_policy=self.cap_policy)

    def _jit_for(self, survey: Survey, cfg) -> Any:
        """Compile cache keyed by the *bucketed* shape signature.

        ``cfg.epoch`` and ``gr.epoch`` are host-side only (provenance +
        stats — nothing traced reads either), so both are normalized to 0:
        epochs whose planned capacities land in the same buckets share one
        jitted closure AND one XLA executable. The returned closure also
        counts executable reuse: each call's (jit key, graph signature)
        pair is checked against the set already traced — a repeat is a
        ``jit_hits`` tick, a new pair a ``jit_recompiles`` tick (surfaced
        via :meth:`ingest_stats` / query stats / :class:`Snapshot`)."""
        jkey = (survey_fingerprint(survey), replace(cfg, epoch=0))
        with self._jit_lock:
            fn = self._jit_cache.get(jkey)
        if fn is not None:
            return fn
        jitted = jax.jit(make_survey_fn(survey, cfg, mesh=self._mesh))

        def fn(gr, _jkey=jkey, _jitted=jitted):
            gr0 = replace(gr, epoch=0)
            sig = (_jkey, _graph_signature(gr0))
            with self._jit_lock:
                if sig in self._compiled:
                    self._jit_hits += 1
                else:
                    self._compiled.add(sig)
                    self._jit_recompiles += 1
            return _jitted(gr0)

        with self._jit_lock:
            self._jit_cache.setdefault(jkey, fn)
            return self._jit_cache[jkey]

    def _prepare(self, survey: Survey,
                 snap: Snapshot | None = None) -> tuple[CacheEntry, bool, float]:
        """Resolve (plan, shards, compiled closure) for ``survey`` against
        the snapshot — from cache, or built + warmed + cached."""
        snap = snap or self._snapshot
        key = self.content_key(survey, snap)
        t0 = time.perf_counter()
        entry = self.cache.lookup(key)
        if entry is not None:
            if entry.fn is None:
                # restored by load_plan_cache: the plan/shards/memo crossed
                # the process boundary, the Survey instance and jitted
                # closure did not — re-attach both (jit wrapping is lazy,
                # so this costs microseconds; the memoized raw state means
                # an exact repeat never even calls it)
                entry.survey = survey
                entry.fn = self._jit_for(survey, entry.cfg)
            return entry, True, time.perf_counter() - t0
        cfg, report = plan_engine(
            snap.union, self.S, survey, mode=self.mode,
            push_cap=self.push_cap, pull_q_cap=self.pull_q_cap,
            sample_p=self.sample_p, sample_seed=self.sample_seed,
            orient="stable", epoch=snap.epoch, transport=self.transport,
            hub_theta=self.hub_theta, hub_wedge_cap=self.hub_wedge_cap,
            max_hubs=self.max_hubs, cap_policy=self.cap_policy)
        gr, _ = shard_dodgr(
            snap.union, self.S, sample_p=self.sample_p,
            sample_seed=self.sample_seed, orient="stable", epoch=snap.epoch,
            hub_theta=cfg.hub_theta, cap_policy=self.cap_policy)
        fn = self._jit_for(survey, cfg)
        raw = jax.block_until_ready(fn(gr))   # compile + warm-up traversal
        entry = self.cache.insert(CacheEntry(
            key=key, survey=survey, cfg=cfg, report=report, gr=gr, fn=fn,
            raw=raw, nbytes=entry_nbytes(gr),
            survey_fp=survey_fingerprint(survey)))
        return entry, False, time.perf_counter() - t0

    def _annotate(self, stats: dict, *, hit: bool, setup_s: float,
                  snap: Snapshot, served_from: str) -> dict:
        stats["plan_cache_hit"] = float(hit)
        stats["plan_setup_s"] = float(setup_s)
        stats["served_epoch"] = float(snap.epoch)
        stats["served_from"] = served_from
        for k, v in self.cache.stats().items():
            if isinstance(v, (int, float)):
                stats[f"plan_cache_{k}"] = float(v)
        with self._jit_lock:
            stats["jit_cache_hits"] = float(self._jit_hits)
            stats["jit_cache_recompiles"] = float(self._jit_recompiles)
            stats["jit_cache_entries"] = float(len(self._compiled))
        return stats

    def query(self, survey: Survey, *, rerun: bool = False):
        """Answer one survey against the current snapshot.

        A plan-cache hit replays the cached closure; an *exact* repeat
        additionally skips the traversal and just finalizes the memoized
        merged state — O(answer). ``rerun=True`` forces the traversal (the
        QPS benchmarks use it); the result is bitwise-identical either way
        (warm == cold == solo).
        """
        snap = self._snapshot
        entry, hit, setup_s = self._prepare(survey, snap)
        if rerun or entry.raw is None:
            result, stats = survey_with_fn(entry.gr, entry.survey,
                                           entry.cfg, entry.fn)
            served_from = "traversal"
        else:
            merged, dstats = entry.raw
            result, stats = engine._finalize_run(entry.survey, entry.cfg,
                                                 merged, dstats)
            served_from = "memo"
        return result, self._annotate(stats, hit=hit, setup_s=setup_s,
                                      snap=snap, served_from=served_from)

    def query_coalesced(self, requests: Sequence[TenantRequest], *,
                        rerun: bool = False) -> dict:
        """Answer N tenants' surveys with ONE traversal of the snapshot.

        Returns ``{tenant: (result, stats)}``; each tenant's result is
        bitwise-identical to :meth:`query`-ing its survey alone.
        """
        bundle = coalesce(requests)
        snap = self._snapshot
        entry, hit, setup_s = self._prepare(bundle, snap)
        warn_if_order_sensitive(entry.cfg, requests)
        if rerun or entry.raw is None:
            result, stats = survey_with_fn(entry.gr, entry.survey,
                                           entry.cfg, entry.fn)
            served_from = "traversal"
        else:
            merged, dstats = entry.raw
            result, stats = engine._finalize_run(entry.survey, entry.cfg,
                                                 merged, dstats)
            served_from = "memo"
        stats = self._annotate(stats, hit=hit, setup_s=setup_s, snap=snap,
                               served_from=served_from)
        return extract(result, stats, requests)

    # -- resident surveys (epoch-incremental) -----------------------------

    def resident_answers(self) -> dict:
        """Render the resident surveys' accumulated state — O(answer):
        no traversal, the ingest pipeline already folded every epoch."""
        snap = self._snapshot
        if self._resident is None or snap.resident_state is None:
            raise ValueError("no resident surveys were registered")
        return finalize_epochs(self._resident, snap.resident_state)

    # -- ingestion (epoch pipeline) ---------------------------------------

    def append_edges(self, src, dst, emeta_i=None, emeta_f=None, n=None,
                     vmeta_i=None, vmeta_f=None, *, wait: bool = False):
        """Enqueue one edge batch for background epoch merge. Queries keep
        answering from the last merged snapshot until the swap; pass
        ``wait=True`` (or call :meth:`flush`) to block until merged."""
        self._ingest.submit(dict(src=np.asarray(src), dst=np.asarray(dst),
                                 emeta_i=emeta_i, emeta_f=emeta_f, n=n,
                                 vmeta_i=vmeta_i, vmeta_f=vmeta_f))
        if wait:
            self.flush()

    def _apply_batch(self, batch: dict) -> None:
        """Worker-thread epoch merge: advance the delta graph + token
        chain, fold residents through one delta traversal (hub tables
        reused), then atomically swap the snapshot."""
        snap = self._snapshot
        parent = snap.dg if snap.dg is not None else snap.union
        dg = parent.append_edges(**batch)
        token = delta_token(dg, base_token=snap.token)

        new_state = snap.resident_state
        if self._resident is not None:
            # session shape hysteresis happens *inside* the planner
            # (promote_from): the previous delta config's caps floor this
            # epoch's, and the planner re-measures pull_edge_cap under the
            # promoted pull-window partition — promoting a finished plan
            # out here would widen the runtime windows past the measured
            # edge cap and silently drop triangles. on_overflow="raise"
            # because an overflow on this path would corrupt the
            # accumulated resident_state for every later answer.
            cfg_d, _ = plan_delta(
                dg, self.S, self._resident, mode=self.mode,
                push_cap=self.push_cap, pull_q_cap=self.pull_q_cap,
                transport=self.transport, hub_theta=self.hub_theta,
                hub_wedge_cap=self.hub_wedge_cap, max_hubs=self.max_hubs,
                cap_policy=self.cap_policy, on_overflow="raise",
                promote_from=self._shape_hw)
            self._shape_hw = cfg_d
            if self._hub_cache is not None:
                # keep the union-adjacency chain gapless even on epochs
                # whose resolved θ disables hub delegation (idempotent)
                self._hub_cache.advance(dg)
            bucket = self.cap_policy == "bucket"
            gr_d, _ = shard_delta(dg, self.S, hub_theta=cfg_d.hub_theta,
                                  hub_cache=self._hub_cache,
                                  cap_policy=self.cap_policy,
                                  e_cap_floor=self._ecap_hw if bucket else 0,
                                  d_plus_max_floor=(self._dmax_hw
                                                    if bucket else 0))
            if bucket:
                self._ecap_hw = max(self._ecap_hw, gr_d.e_cap)
                self._dmax_hw = max(self._dmax_hw, gr_d.d_plus_max)
            fn = self._jit_for(self._resident, cfg_d)
            engine._check_provenance(gr_d, cfg_d)
            merged, dstats = jax.block_until_ready(fn(gr_d))
            # guard BEFORE merging: a pull-window overflow in the delta
            # fold undercounts triangles, and this state is accumulated —
            # with on_overflow="raise" the epoch fails loudly (surfaced by
            # IngestPipeline on the next flush/submit) instead of
            # persistently corrupting every later resident answer
            engine._exactness_guard(cfg_d, engine.sum_stats(dstats))
            new_state = (self._resident.merge_epochs(snap.resident_state,
                                                     merged)
                         if snap.resident_state is not None else merged)

        with self._jit_lock:
            jh, jr = self._jit_hits, self._jit_recompiles
        self._snapshot = Snapshot(epoch=dg.epoch, token=token,
                                  union=dg.union(), dg=dg,
                                  resident_state=new_state,
                                  jit_hits=jh, jit_recompiles=jr)
        self._epochs_applied += 1

    def flush(self) -> None:
        """Block until every submitted batch is merged into the snapshot."""
        self._ingest.flush()

    def ingest_stats(self) -> dict:
        d = {"epochs_applied": self._epochs_applied,
             "pending": self._ingest.pending,
             "epoch": self._snapshot.epoch}
        with self._jit_lock:
            d["jit_cache_hits"] = self._jit_hits
            d["jit_cache_recompiles"] = self._jit_recompiles
            d["jit_cache_entries"] = len(self._compiled)
        d.update(self._ingest.stats())
        if self._hub_cache is not None:
            d["hub_rows_reused"] = self._hub_cache.rows_reused
            d["hub_rows_refreshed"] = self._hub_cache.rows_refreshed
            d["hub_last_build"] = dict(self._hub_cache.last_build)
        return d

    # -- persistence ------------------------------------------------------

    def checkpoint(self, path, *, plans: bool = True) -> None:
        """Persist the current epoch state (graph + token chain) so a
        restarted service resumes the same content keys — and, unless
        ``plans=False``, every plan-cache entry to a ``.plans.npz``
        sidecar (:func:`repro.serve.plan_cache.save_plan_cache`) so the
        restart also resumes the plans themselves."""
        from repro.graphs import io as gio

        snap = self._snapshot
        dg = snap.dg
        if dg is None:
            g = snap.union
            dei, def_ = g.emeta_i.shape[1], g.emeta_f.shape[1]
            dg = DeltaGraph(base=g,
                            d_src=np.zeros(0, np.int64),
                            d_dst=np.zeros(0, np.int64),
                            d_emeta_i=np.zeros((0, dei), np.int32),
                            d_emeta_f=np.zeros((0, def_), np.float32),
                            epoch=snap.epoch)
        gio.save_epoch_state(path, dg, token=snap.token)
        if plans:
            save_plan_cache(_plans_path(path), self.cache)

    @classmethod
    def restore(cls, path, S: int, **kwargs) -> "SurveyService":
        """Rebuild a service from :meth:`checkpoint` output. The token
        chain — and therefore every content key — continues where it left
        off, and when the ``.plans.npz`` sidecar exists the plan cache is
        preloaded from it: the first query of a persisted question answers
        from the memoized warm-up state without replanning, resharding, or
        retracing."""
        import os

        from repro.graphs import io as gio

        dg, token = gio.load_epoch_state(path)
        if "preload_plans" not in kwargs:
            pp = _plans_path(path)
            if os.path.exists(pp):
                kwargs["preload_plans"] = load_plan_cache(pp)
        return cls(dg.union(), S, token=token, epoch=dg.epoch, **kwargs)

    # -- lifecycle --------------------------------------------------------

    def close(self) -> None:
        self._ingest.close()

    def __enter__(self) -> "SurveyService":
        return self

    def __exit__(self, *exc) -> None:
        self.close()
