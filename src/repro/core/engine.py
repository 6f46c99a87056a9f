"""TriPoll survey engine: Push-Only (Alg. 1) and Push-Pull (Sec. 4.4).

Execution model (DESIGN.md §2): stacked layout — every array carries a
leading shard axis ``S``. Work proceeds in *supersteps* over dest-major
wedge streams with static per-(shard,dest) capacities; the static superstep
counts come from the host planner (:mod:`repro.core.pushpull`) — the BSP
analogue of the paper's "Push vs Pull Dry-Run".

Transport: every cross-shard buffer movement goes through the pluggable
:mod:`repro.comm.exchange` layer. The ``dense`` transport is the historic
``swapaxes(x, 0, 1)`` all-to-all (lowered to a real all-to-all by the GSPMD
partitioner when axis 0 is sharded) with one worst-case per-pair capacity;
the ``ragged`` transport ships sorted-compaction streams with static
*per-(shard, dest)* capacities taken from the planner's exact stream
histograms, so skewed graphs stop paying hub-sized padding on every pair.
Both deliver the same entries — survey results are bitwise-identical.

Push superstep: shard s enumerates wedges (p; q, r) rank-by-rank within
each destination stream, ships (q, r, key(r), meta(p), meta(pq), meta(pr))
to owner(q); the owner closes the wedge with a binary search of r's key in
Adj₊(q) (the paper's merge-path intersection, in its TPU log-time form) and
folds the survey callback with all six metadata items local (Sec. 4.2/4.3).

Pull superstep: shard s requests `Adj₊ᵐ(q)` once per (shard, q) for targets
whose row is cheaper to move than the wedge candidates (the paper's
per-pair decision), receives padded rows, finds each local suffix
wedge's closing vertex in the pulled row (a compare of the whole row where
rows are narrow, else the push lane's keyed lower bound; tiled by wedge
rank within a staging budget) and folds the survey locally.

Hub superstep (two-tier exchange, after Arifuzzaman et al.'s heavy-vertex
split): wedges whose center q has degree ≥ the plan's ``hub_theta`` never
reach either wire lane — q's ``Adj₊`` row is replicated on every shard
(``dodgr.shard_dodgr(hub_theta=θ)``), so the *source* shard closes the
wedge against the hub table and folds locally, at zero exchanged bytes.
The planner chooses θ from the degree histogram + bytes cost model and
removes hub wedges from both the push streams and the pull decision.

Delta mode (epoch-incremental surveys): when ``EngineConfig.delta`` is set
the graph is a *delta frontier* (``dodgr.shard_delta``) and the same lanes
run restricted — wedge generation is masked to the ``delta_gen`` edges
(only wedges that can belong to a triangle with ≥1 new edge), push entries
and pulled rows carry per-edge newness bits, and the fold's ``valid`` mask
additionally requires ≥1 new edge, so exactly the new-old-old /
new-new-old / new-new-new triangle classes are surveyed. ``survey_delta``
accumulates epochs through ``Survey.merge_epochs``; ``finalize_epochs``
renders the running state. Hub delegation composes: a batch that touches a
hub resolves the hub-centered frontier wedges locally instead of blowing
up the exchange.

Lane projection: both wire lanes gather and exchange only the metadata
lanes the survey's :class:`~repro.core.surveys.MetaSpec` declares. Push
queries carry meta(p)/meta(pq)/meta(pr) at declared width; the padded pull
reply — the dominant ``pcap·L`` volume — carries meta(qr)/meta(r) rows and
the meta(q) header at declared width; fully-unread items skip their
gathers entirely and reach the fold as zero-width ``[B, 0]`` fields. Wire
lanes are re-expanded to storage indices (zero-filling undeclared lanes)
before the fold, so survey ``update`` code is projection-agnostic and
bitwise-identical to a full-metadata run. The bytes cost model uses the
same projected widths as the host planner (stamped into
``EngineConfig.meta_widths`` by ``pushpull.plan_engine``), keeping
push-vs-pull decisions in lockstep.

Exactness: the planner sizes every static capacity so nothing is dropped;
if a hand-edited config still overflows a window, the run is flagged
``exact=False`` in its stats with a ``RuntimeWarning`` (or a raise under
``on_overflow='raise'``) instead of silently undercounting.
"""
from __future__ import annotations

import warnings
from dataclasses import dataclass, replace
from functools import partial

import jax
import numpy as np
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro import kernels
from repro.comm.exchange import Exchange, make_exchange
from repro.core.dodgr import PAD_D, ShardedDODGr, meta_widths
from repro.core.surveys import (MetaSpec, Survey, TriangleBatch, expand_lanes,
                                narrow_lanes, project_lanes)
from repro.utils import ceil_div, span

BIG_I32 = jnp.int32(2**30)


# ---------------------------------------------------------------------------
# config


@dataclass(frozen=True)
class EngineConfig:
    """Static engine plan. Produced by ``pushpull.plan_engine`` on host, or
    set directly for dry-run lowering."""

    mode: str = "push"            # "push" | "pushpull"
    push_cap: int = 256           # wedge slots per (shard,dest) per push superstep
    n_push_steps: int = 1
    pull_q_cap: int = 32          # pulled-row slots per (shard,dest) per pull superstep
    pull_edge_cap: int = 64       # edge slots per (shard,dest) pull window
    n_pull_steps: int = 0
    cost_model: str = "entries"   # "entries" (paper-faithful) | "bytes"
    unroll_steps: bool = False    # unroll superstep scans (cost-analysis mode)
    use_pallas: bool = False      # run both lanes' keyed searches through the
    #                               kernels/wedge_check Pallas kernel (compiled
    #                               on a TPU, interpreted elsewhere:
    #                               kernels.compiled)
    shard_axis: str | None = None  # mesh axis name for sharding constraints
    sample_p: float = 1.0         # DOULION edge-keep probability the graph was
    #                               sparsified with (host-side); < 1 debiases
    #                               count-type results by 1/p³ at finalize
    sample_seed: int = 0          # sparsification seed (must match ingestion)
    project_meta: bool = True     # lane-project metadata to the survey's
    #                               MetaSpec; False ships all lanes (debug /
    #                               bitwise-equivalence testing)
    meta_widths: tuple | None = None  # (w_push, w_row, w_hdr, w_req) words,
    #                               stamped by pushpull.plan_engine from the
    #                               survey's resolved spec; None derives them
    #                               from the running survey at compile time
    delta: bool = False           # epoch-incremental mode: restrict wedge
    #                               generation to the delta_gen mask and fold
    #                               only triangles with ≥1 new edge
    epoch: int = 0                # epoch the delta plan was built for (must
    #                               match the frontier's stamp)
    orient: str = "degree"        # orientation key the plan assumed ("degree"
    #                               static default, "stable" for delta epochs)
    transport: str = "dense"      # exchange implementation: "dense" (historic
    #                               swapaxes all-to-all, worst-case per-pair
    #                               caps) | "ragged" (per-(shard,dest) caps
    #                               from the planner's stream histograms)
    push_caps: tuple | None = None  # ragged: S×S nested tuple, wedge slots
    #                               per (src, dest) per push superstep
    pull_caps: tuple | None = None  # ragged: S×S nested tuple, pulled-group
    #                               slots per (src, dest) per pull superstep
    pull_row_cap: int = 0         # reply-row padding length: the planner's
    #                               max d₊ over *pulled* groups (0 = pad to
    #                               the graph-wide d_plus_max, the historic
    #                               worst case). Hub delegation removes the
    #                               heavy rows from the pull set, so this —
    #                               and with it the dominant reply volume —
    #                               shrinks to the next-heaviest survivor
    hub_theta: int = 0            # hub delegation threshold θ (0 = off); must
    #                               match the shard-time stamp — wedges whose
    #                               center has degree ≥ θ resolve on-shard
    #                               against the replicated hub table
    n_hub_steps: int = 0          # hub-lane supersteps (0 = lane off)
    hub_wedge_cap: int = 256      # wedge slots per shard per hub superstep
    on_overflow: str = "warn"     # "warn" | "raise" — what to do when a
    #                               static window overflowed and triangles
    #                               were dropped (stats carry exact=False
    #                               either way)
    cap_policy: str = "exact"     # "exact" | "bucket" — whether the planner
    #                               rounded every shape-determining capacity
    #                               (superstep counts, per-pair caps, reply
    #                               row padding) up to the geometric bucket
    #                               grid (utils.bucket_cap) so drifting
    #                               epochs share jit-compiled executables.
    #                               Host-side bookkeeping only: the engine
    #                               executes whatever caps are stamped, and
    #                               the invalid-slot masks make bucketed
    #                               plans bitwise-identical to exact ones
    determinism: str = "bitwise"  # fold-algebra verdict for the survey the
    #                               plan was built for, stamped by
    #                               pushpull.plan_engine from the static
    #                               verifier (repro.analysis.contracts):
    #                               "bitwise" | "order_sensitive" |
    #                               "unknown". survey_delta warns when an
    #                               order-sensitive survey is accumulated
    #                               through merge_epochs — the incremental
    #                               == recompute identity then holds only
    #                               up to float reduction order


def _constrain(x, cfg: EngineConfig, *trailing):
    if cfg.shard_axis is None:
        return x
    spec = P(cfg.shard_axis, *trailing)
    return jax.lax.with_sharding_constraint(x, spec)


def _push_exchange(cfg: EngineConfig, S: int) -> Exchange:
    return make_exchange(cfg.transport, S, cfg.push_cap, cfg.push_caps)


def _pull_exchange(cfg: EngineConfig, S: int) -> Exchange:
    return make_exchange(cfg.transport, S, cfg.pull_q_cap, cfg.pull_caps)


# ---------------------------------------------------------------------------
# per-shard primitives (vmapped over the shard axis by the engine)


def _lower_bound(nbr_d, nbr_h, nbr_i, lo, hi, qd, qh, qi, n_steps):
    """Vectorized lower_bound of key (qd,qh,qi) in per-row slices [lo,hi)."""

    def body(_, carry):
        lo, hi = carry
        has = lo < hi
        mid = jnp.where(has, (lo + hi) // 2, 0)
        kd = nbr_d[mid]
        kh = nbr_h[mid]
        ki = nbr_i[mid]
        less = (kd < qd) | ((kd == qd) & (kh < qh)) | ((kd == qd) & (kh == qh) & (ki < qi))
        lo = jnp.where(has & less, mid + 1, lo)
        hi = jnp.where(has & ~less, mid, hi)
        return lo, hi

    lo, hi = jax.lax.fori_loop(0, n_steps, body, (lo, hi))
    return lo


def _dense_row_search(rows, ln, key):
    """Slot of ``key[t]`` in the row ``rows[t, :ln[t]]``, by comparing every
    slot: ``(found, slot)``, with ``slot = ln`` where the row lacks it.
    Vertex ids are unique within an adjacency row, so the id alone decides
    the hit that the keyed :func:`_lower_bound` finds by its sort key."""
    j = jnp.arange(rows.shape[-1], dtype=jnp.int32)
    first = jnp.min(jnp.where(rows == key[:, None], j, rows.shape[-1]),
                    axis=-1)
    return first < ln, jnp.minimum(first, ln)


def _stream_setup(gr: ShardedDODGr, weight_mask=None):
    """Dest-major wedge-stream routing tables, per shard (vmapped).

    Returns dict with per-shard [e_cap] / [S+1] arrays:
      perm      dest-sorted edge permutation
      cum       inclusive cumsum of wedge weights in perm order
      base      exclusive stream offset at each dest block  [S+1]
      stream_len wedges per dest [S]
      suffix    per-edge suffix length (wedge fanout)
      dest      owner(q) per edge
      valid     edge-slot validity
    """
    S, e_cap, n_loc = gr.S, gr.e_cap, gr.n_loc

    def per_shard(row_ptr, edge_src, nbr, wmask):
        e = jnp.arange(e_cap, dtype=jnp.int32)
        n_edges = row_ptr[-1]
        valid = e < n_edges
        lp = jnp.clip(edge_src // S, 0, n_loc - 1)
        row_end = row_ptr[lp + 1]
        suffix = jnp.where(valid, jnp.maximum(row_end - e - 1, 0), 0)
        dest = jnp.where(valid, nbr % S, S)
        perm = jnp.argsort(dest, stable=True)
        w = suffix[perm]
        if wmask is not None:
            w = w * wmask[perm].astype(jnp.int32)
        cum = jnp.cumsum(w)
        sorted_dest = dest[perm]
        dest_start = jnp.searchsorted(sorted_dest, jnp.arange(S + 1, dtype=jnp.int32),
                                      side="left").astype(jnp.int32)
        blk_prev = jnp.where(dest_start > 0, cum[jnp.maximum(dest_start - 1, 0)], 0)
        base = blk_prev  # [S+1] exclusive offsets; base[S] == total
        stream_len = base[1:] - base[:-1]
        return dict(perm=perm, cum=cum, base=base[:-1], stream_len=stream_len,
                    suffix=suffix, dest=dest, valid=valid)

    wm = weight_mask if weight_mask is not None else None
    if wm is None:
        return jax.vmap(lambda rp, es, nb: per_shard(rp, es, nb, None))(
            gr.row_ptr, gr.edge_src, gr.nbr)
    return jax.vmap(per_shard)(gr.row_ptr, gr.edge_src, gr.nbr, wm)


def _gen_push_queries(gr: ShardedDODGr, st, t, exch: Exchange, spec: MetaSpec,
                      delta: bool = False):
    """Build the per-shard flat wire buffers of push queries for superstep
    ``t``: slot ``j`` of shard ``s`` is rank ``t·cap(s,d) + lane(j)`` of the
    dest-``d`` wedge stream, where the slot→(dest, lane, cap) maps are the
    transport's static routing tables (dense: one global cap; ragged:
    per-(shard, dest) caps).

    Metadata travels in wire form: only the lanes ``spec`` declares for
    meta(p), meta(pq), meta(pr); unread items ship zero-width. In delta mode
    the entry additionally carries the wedge edges' newness bits — packed
    into the one extra wire word the planner accounts (``w_push + 1``) — so
    the owner can settle the ≥1-new-edge test at closure."""
    S, e_cap, n_loc = gr.S, gr.e_cap, gr.n_loc
    vp_i = project_lanes(gr.vmeta_i, spec.vp_i)
    vp_f = project_lanes(gr.vmeta_f, spec.vp_f)
    epq_i = project_lanes(gr.emeta_i, spec.e_pq_i)
    epq_f = project_lanes(gr.emeta_f, spec.e_pq_f)
    epr_i = project_lanes(gr.emeta_i, spec.e_pr_i)
    epr_f = project_lanes(gr.emeta_f, spec.e_pr_f)
    dest_of = jnp.asarray(exch.dest_of)
    lane_of = jnp.asarray(exch.lane_of)
    cap_of = jnp.asarray(exch.cap_of)

    def per_shard(perm, cum, base, stream_len, row_ptr, edge_src, nbr, nbr_d,
                  nbr_h, nbr_new, epq_i, epq_f, epr_i, epr_f, vp_i, vp_f,
                  dest_of, lane_of, cap_of):
        d = jnp.minimum(dest_of, S - 1)
        offs = t * cap_of + lane_of                       # [out_cap]
        in_stream = (dest_of < S) & (offs < stream_len[d])
        ranks = base[d] + offs                            # [out_cap]
        idx = jnp.searchsorted(cum, ranks, side="right").astype(jnp.int32)
        idx = jnp.clip(idx, 0, e_cap - 1)
        e = perm[idx]
        prev = jnp.where(idx > 0, cum[jnp.maximum(idx - 1, 0)], 0)
        o = jnp.clip(ranks - prev, 0, e_cap - 1)
        r_pos = jnp.clip(e + 1 + o, 0, e_cap - 1)
        p = edge_src[e]
        lp = jnp.clip(p // S, 0, n_loc - 1)
        out = dict(
            q=nbr[e], r=nbr[r_pos], rd=nbr_d[r_pos], rh=nbr_h[r_pos], p=p,
            vp_i=vp_i[lp], vp_f=vp_f[lp],
            epq_i=epq_i[e], epq_f=epq_f[e],
            epr_i=epr_i[r_pos], epr_f=epr_f[r_pos],
            ok=in_stream,
        )
        if delta:
            out["new2"] = (nbr_new[e].astype(jnp.int32)
                           | (nbr_new[r_pos].astype(jnp.int32) << 1))
        return out

    return jax.vmap(per_shard)(
        st["perm"], st["cum"], st["base"], st["stream_len"], gr.row_ptr,
        gr.edge_src, gr.nbr, gr.nbr_d, gr.nbr_h, gr.nbr_new, epq_i, epq_f,
        epr_i, epr_f, vp_i, vp_f, dest_of, lane_of, cap_of)


def _answer_push_queries(gr: ShardedDODGr, qr, cfg: EngineConfig,
                         spec: MetaSpec) -> TriangleBatch:
    """Owner-side wedge closure: search key(r) in Adj₊(q); gather metadata.

    Shipped items (meta(p)/(pq)/(pr)) arrive in wire form and are expanded
    to fold form; owner-local items (meta(q)/(r)/(qr)) are gathered at
    declared width only — unread items skip the gather."""
    S, e_cap, n_loc = gr.S, gr.e_cap, gr.n_loc
    # the search spans one Adj₊(q) row, at most d_plus_max slots — a
    # binary search over the whole [e_cap] array would only add probes
    n_steps = max(1, int(np.ceil(np.log2(max(2, gr.d_plus_max)))) + 1)
    vq_i = narrow_lanes(gr.vmeta_i, spec.vq_i)
    vq_f = narrow_lanes(gr.vmeta_f, spec.vq_f)
    vr_i = narrow_lanes(gr.tmeta_i, spec.vr_i)
    vr_f = narrow_lanes(gr.tmeta_f, spec.vr_f)
    eqr_i = narrow_lanes(gr.emeta_i, spec.e_qr_i)
    eqr_f = narrow_lanes(gr.emeta_f, spec.e_qr_f)

    if cfg.use_pallas:
        from repro.kernels.wedge_check import ops as wc_ops

    def per_shard(row_ptr, nbr, nbr_d, nbr_h, nbr_new, eqr_i, eqr_f, vr_i,
                  vr_f, vq_i, vq_f, q):
        lq = jnp.clip(q["q"] // S, 0, n_loc - 1)
        lo = row_ptr[lq]
        hi = row_ptr[lq + 1]
        if cfg.use_pallas:
            pos = wc_ops.wedge_check(nbr_d, nbr_h, nbr, lo, hi, q["rd"], q["rh"],
                                     q["r"], interpret=not kernels.compiled())
        else:
            pos = _lower_bound(nbr_d, nbr_h, nbr, lo, hi, q["rd"], q["rh"],
                               q["r"], n_steps)
        pos_c = jnp.clip(pos, 0, e_cap - 1)
        # the p >= 0 test is a no-op (every ok slot carries a real vertex
        # id) but keeps the planned p word live on the wire for surveys
        # whose fold never reads it — the planner accounts all six base
        # words, and the mesh HLO reconciliation holds them to it
        found = q["ok"] & (pos < hi) & (nbr[pos_c] == q["r"]) & (q["p"] >= 0)
        if cfg.delta:
            # fold only the three new-triangle classes: ≥1 of pq/pr/qr new
            # (pq_new | pr_new ≡ packed wire word ≠ 0)
            found &= (q["new2"] != 0) | nbr_new[pos_c]
        return TriangleBatch(
            p=q["p"], q=q["q"], r=q["r"],
            vp_i=expand_lanes(q["vp_i"], spec.vp_i),
            vq_i=vq_i[lq], vr_i=vr_i[pos_c],
            vp_f=expand_lanes(q["vp_f"], spec.vp_f),
            vq_f=vq_f[lq], vr_f=vr_f[pos_c],
            e_pq_i=expand_lanes(q["epq_i"], spec.e_pq_i),
            e_pr_i=expand_lanes(q["epr_i"], spec.e_pr_i),
            e_qr_i=eqr_i[pos_c],
            e_pq_f=expand_lanes(q["epq_f"], spec.e_pq_f),
            e_pr_f=expand_lanes(q["epr_f"], spec.e_pr_f),
            e_qr_f=eqr_f[pos_c],
            valid=found,
        )

    return jax.vmap(per_shard)(
        gr.row_ptr, gr.nbr, gr.nbr_d, gr.nbr_h, gr.nbr_new, eqr_i, eqr_f,
        vr_i, vr_f, vq_i, vq_f, qr)


# ---------------------------------------------------------------------------
# hub lane (zero-exchange wedge closure against the replicated hub table)


def _hub_setup(gr: ShardedDODGr, st, hub_mask):
    """Per-shard hub-wedge stream: inclusive cumsum of per-edge hub wedge
    counts in edge order (no dest-major permutation — nothing is routed)."""
    w = st["suffix"] * hub_mask.astype(jnp.int32)
    cum = jnp.cumsum(w, axis=1)
    return dict(cum=cum, total=cum[:, -1])


def _hub_superstep(gr: ShardedDODGr, hst, t, cfg: EngineConfig,
                   spec: MetaSpec):
    """Close one window of hub-centered wedges entirely on-shard.

    For wedge (p; q, r) with hub center q the replicated table holds
    Adj₊ᵐ(q) — key search, meta(q)/meta(r)/meta(qr) gathers and the fold
    all run on owner(p)'s shard; nothing crosses the shard axis."""
    S, e_cap, n_loc = gr.S, gr.e_cap, gr.n_loc
    Hc, Lh = gr.hub_nbr.shape
    cap = cfg.hub_wedge_cap
    n_steps = max(1, int(np.ceil(np.log2(max(2, Lh)))) + 1)

    # replicated hub sources, flattened so per-row slices index like a CSR
    h_nbr = gr.hub_nbr.reshape(-1)
    h_d = gr.hub_nbr_d.reshape(-1)
    h_h = gr.hub_nbr_h.reshape(-1)
    h_new = gr.hub_nbr_new.reshape(-1)
    h_eqr_i = narrow_lanes(gr.hub_eqr_i, spec.e_qr_i).reshape(Hc * Lh, -1)
    h_eqr_f = narrow_lanes(gr.hub_eqr_f, spec.e_qr_f).reshape(Hc * Lh, -1)
    h_vr_i = narrow_lanes(gr.hub_tmeta_i, spec.vr_i).reshape(Hc * Lh, -1)
    h_vr_f = narrow_lanes(gr.hub_tmeta_f, spec.vr_f).reshape(Hc * Lh, -1)
    h_vq_i = narrow_lanes(gr.hub_vmeta_i, spec.vq_i)
    h_vq_f = narrow_lanes(gr.hub_vmeta_f, spec.vq_f)
    h_len = gr.hub_row_len
    # requester-local (fold-form) sources
    vp_i_l = narrow_lanes(gr.vmeta_i, spec.vp_i)
    vp_f_l = narrow_lanes(gr.vmeta_f, spec.vp_f)
    epq_i_l = narrow_lanes(gr.emeta_i, spec.e_pq_i)
    epq_f_l = narrow_lanes(gr.emeta_f, spec.e_pq_f)
    epr_i_l = narrow_lanes(gr.emeta_i, spec.e_pr_i)
    epr_f_l = narrow_lanes(gr.emeta_f, spec.e_pr_f)

    def per_shard(cum, total, edge_src, nbr, nbr_d, nbr_h, nbr_new, nbr_hub,
                  epq_i, epq_f, epr_i, epr_f, vp_i, vp_f):
        c = jnp.arange(cap, dtype=jnp.int32)
        rank = t * cap + c
        ok = rank < total
        idx = jnp.searchsorted(cum, rank, side="right").astype(jnp.int32)
        e = jnp.clip(idx, 0, e_cap - 1)
        prev = jnp.where(e > 0, cum[jnp.maximum(e - 1, 0)], 0)
        o = jnp.clip(rank - prev, 0, e_cap - 1)
        r_pos = jnp.clip(e + 1 + o, 0, e_cap - 1)
        p = edge_src[e]
        lp = jnp.clip(p // S, 0, n_loc - 1)
        hid = jnp.clip(nbr_hub[e], 0, Hc - 1)
        lo = hid * Lh
        hi = lo + h_len[hid]
        pos = _lower_bound(h_d, h_h, h_nbr, lo, hi, nbr_d[r_pos],
                           nbr_h[r_pos], nbr[r_pos], n_steps)
        pos_c = jnp.clip(pos, 0, Hc * Lh - 1)
        found = ok & (pos < hi) & (h_nbr[pos_c] == nbr[r_pos])
        if cfg.delta:
            found &= nbr_new[e] | nbr_new[r_pos] | h_new[pos_c]
        tri = TriangleBatch(
            p=p, q=nbr[e], r=nbr[r_pos],
            vp_i=vp_i[lp], vq_i=h_vq_i[hid], vr_i=h_vr_i[pos_c],
            vp_f=vp_f[lp], vq_f=h_vq_f[hid], vr_f=h_vr_f[pos_c],
            e_pq_i=epq_i[e], e_pr_i=epr_i[r_pos], e_qr_i=h_eqr_i[pos_c],
            e_pq_f=epq_f[e], e_pr_f=epr_f[r_pos], e_qr_f=h_eqr_f[pos_c],
            valid=found,
        )
        return tri, ok.sum(dtype=jnp.int32)

    return jax.vmap(per_shard)(
        hst["cum"], hst["total"], gr.edge_src, gr.nbr, gr.nbr_d, gr.nbr_h,
        gr.nbr_new, gr.nbr_hub, epq_i_l, epq_f_l, epr_i_l, epr_f_l,
        vp_i_l, vp_f_l)


# ---------------------------------------------------------------------------
# pull-phase device planning (Sec. 4.4)


def _pull_setup(gr: ShardedDODGr, st, cfg: EngineConfig, widths,
                hub_mask=None):
    """Per-shard pull decisions + dest-major (dest, pulled, q) edge order.

    ``st['suffix']`` must already be masked to the wedges this plan
    generates (delta mask, hub exclusion) — a masked-out group has zero
    volume and is never pulled, mirroring the host planner exactly.

    Returns per-shard arrays (vmapped):
      pull        [e_cap] bool, per edge slot (original order)
      ord2        [e_cap] edge permutation sorted by (dest, ~pull, q, pos)
      qrank2      [e_cap] global 0-based pulled-group rank per ord2 slot
      qbase       [S]    pulled-group count before each dest block
      qcount      [S]    pulled groups per dest
      pulled_end  [S]    ord2 index one past the pulled edges of each dest
      dest_start2 [S+1]
    """
    S, e_cap = gr.S, gr.e_cap
    w_push, w_row, w_hdr, w_req = widths

    def per_shard(nbr, nbr_dplus, suffix, dest, valid, hub):
        ordq = jnp.argsort(jnp.where(valid, nbr, BIG_I32), stable=True)
        qs = nbr[ordq]
        sfx = suffix[ordq]
        vq = valid[ordq]
        if hub is not None:
            # hub-centered groups resolve on the hub lane — never pulled
            vq_pull = vq & ~hub[ordq]
        else:
            vq_pull = vq
        first = jnp.concatenate([jnp.ones((1,), bool), qs[1:] != qs[:-1]]) & vq
        gid = jnp.cumsum(first.astype(jnp.int32)) - 1
        gid = jnp.where(vq, gid, e_cap - 1)
        vol = jax.ops.segment_sum(sfx, gid, num_segments=e_cap)
        vol_e = vol[gid]
        dq = nbr_dplus[ordq]
        if cfg.cost_model == "entries":
            pull_s = vq_pull & (dq < vol_e)
        else:
            pull_s = vq_pull & (dq * w_row + w_hdr + w_req < vol_e * w_push)
        pull = jnp.zeros((e_cap,), bool).at[ordq].set(pull_s)

        # (dest, ~pull, q, pos) order: stable sort of the q-sorted order by
        # composite bucket key
        dest_q = dest[ordq]
        bucket = jnp.where(vq, dest_q * 2 + (1 - pull_s.astype(jnp.int32)), 2 * S + 1)
        reord = jnp.argsort(bucket, stable=True)
        ord2 = ordq[reord]
        qs2 = qs[reord]
        pull2 = pull_s[reord]
        v2 = vq[reord]
        dest2 = jnp.where(v2, dest_q[reord], S)
        first2 = jnp.concatenate([jnp.ones((1,), bool), qs2[1:] != qs2[:-1]]) & v2
        wq2 = (first2 & pull2).astype(jnp.int32)
        cum_incl = jnp.cumsum(wq2)
        qrank2 = cum_incl - 1                      # group rank for all members
        dest_start2 = jnp.searchsorted(dest2, jnp.arange(S + 1, dtype=jnp.int32),
                                       side="left").astype(jnp.int32)
        qbase = jnp.where(dest_start2[:-1] > 0,
                          cum_incl[jnp.maximum(dest_start2[:-1] - 1, 0)], 0)
        qtop = jnp.where(dest_start2[1:] > 0,
                         cum_incl[jnp.maximum(dest_start2[1:] - 1, 0)], 0)
        qcount = qtop - qbase
        pcum = jnp.cumsum(pull2.astype(jnp.int32))
        p_at = lambda i: jnp.where(i > 0, pcum[jnp.maximum(i - 1, 0)], 0)
        pulled_in_dest = p_at(dest_start2[1:]) - p_at(dest_start2[:-1])
        pulled_end = dest_start2[:-1] + pulled_in_dest
        return dict(pull=pull, ord2=ord2, qrank2=qrank2, qbase=qbase,
                    qcount=qcount, pulled_end=pulled_end,
                    dest_start2=dest_start2[:-1], vol=vol_e, ordq=ordq)

    if hub_mask is None:
        return jax.vmap(lambda nb, dp, sf, de, va: per_shard(nb, dp, sf, de,
                                                             va, None))(
            gr.nbr, gr.nbr_dplus, st["suffix"], st["dest"], st["valid"])
    return jax.vmap(per_shard)(gr.nbr, gr.nbr_dplus, st["suffix"], st["dest"],
                               st["valid"], hub_mask)


def _pull_wire(gr: ShardedDODGr, ps, t, cfg: EngineConfig,
               spec: MetaSpec, exch: Exchange):
    """The wire half of one pull superstep: build q-requests, route them to
    the owners, answer with padded rows, and route the reply back.

    Both wire movements (the request buffer out, the padded reply back)
    route through the transport; the padded reply — ``pcap·L`` row slots,
    the dominant pull-phase volume — carries only the declared
    meta(qr)/meta(r) lanes plus the declared meta(q) header lanes.
    Returns ``(rep, n_req)``: the fold-form reply and the request count —
    everything :func:`_pull_compute` needs, so the engine can issue
    superstep ``t+1``'s collectives while superstep ``t``'s intersection
    and fold still run (the mesh pipeline in :func:`_survey_body`)."""
    S, e_cap, n_loc = gr.S, gr.e_cap, gr.n_loc
    L = gr.d_plus_max
    # reply rows pad to the max *pulled* row length (planner-stamped) — the
    # graph-wide d_plus_max only bounds the local suffix windows
    Lr = cfg.pull_row_cap if cfg.pull_row_cap else L

    # wire-form metadata sources (owner side of the reply)
    eqr_i_w = project_lanes(gr.emeta_i, spec.e_qr_i)
    eqr_f_w = project_lanes(gr.emeta_f, spec.e_qr_f)
    vr_i_w = project_lanes(gr.tmeta_i, spec.vr_i)
    vr_f_w = project_lanes(gr.tmeta_f, spec.vr_f)
    vq_i_w = project_lanes(gr.vmeta_i, spec.vq_i)
    vq_f_w = project_lanes(gr.vmeta_f, spec.vq_f)

    dest_of = jnp.asarray(exch.dest_of)
    lane_of = jnp.asarray(exch.lane_of)
    cap_of = jnp.asarray(exch.cap_of)

    # --- requester: build q-requests, flat [S, out_cap] ---
    def gen_req(qrank2, qbase, qcount, ord2, nbr, dest_of, lane_of, cap_of):
        d = jnp.minimum(dest_of, S - 1)
        offs = t * cap_of + lane_of
        okq = (dest_of < S) & (offs < qcount[d])
        k = qbase[d] + offs                               # global group rank
        posq = jnp.searchsorted(qrank2, k, side="left").astype(jnp.int32)
        posq = jnp.clip(posq, 0, e_cap - 1)
        qid = nbr[ord2[posq]]
        return dict(q=jnp.where(okq, qid, BIG_I32), ok=okq)

    req = jax.vmap(gen_req)(ps["qrank2"], ps["qbase"], ps["qcount"],
                            ps["ord2"], gr.nbr, dest_of, lane_of, cap_of)
    req_x = exch.scatter(req)   # [S_owner, in_cap]
    req_x = dict(req_x, ok=exch.apply_recv_ok(req_x["ok"]))
    req_x = jax.tree.map(lambda x: _constrain(x, cfg), req_x)

    # --- owner: reply with padded rows (declared lanes only on the wire) ---
    def answer(row_ptr, nbr, nbr_d, nbr_h, nbr_new, eqr_i, eqr_f, vr_i, vr_f,
               vq_i, vq_f, dplus, q, ok):
        lq = jnp.clip(q // S, 0, n_loc - 1)
        lo = row_ptr[lq]                                   # [B]
        ln = jnp.where(ok, dplus[lq], 0)
        j = jnp.arange(Lr, dtype=jnp.int32)
        slots = jnp.clip(lo[:, None] + j[None, :], 0, e_cap - 1)   # [B, Lr]
        mask = j[None, :] < ln[:, None]
        out = dict(
            r_nbr=jnp.where(mask, nbr[slots], BIG_I32),
            r_d=jnp.where(mask, nbr_d[slots], BIG_I32),
            r_h=jnp.where(mask, nbr_h[slots], jnp.uint32(0xFFFFFFFF)),
            r_ei=eqr_i[slots] * mask[..., None].astype(jnp.int32),
            r_ef=eqr_f[slots] * mask[..., None],
            r_ti=vr_i[slots] * mask[..., None].astype(jnp.int32),
            r_tf=vr_f[slots] * mask[..., None],
            vq_i=vq_i[lq], vq_f=vq_f[lq],
            ln=ln, ok=ok,
        )
        if cfg.delta:
            out["r_new"] = mask & nbr_new[slots]
        return out

    rep = jax.vmap(answer)(gr.row_ptr, gr.nbr, gr.nbr_d, gr.nbr_h, gr.nbr_new,
                           eqr_i_w, eqr_f_w, vr_i_w, vr_f_w, vq_i_w, vq_f_w,
                           gr.dplus, req_x["q"], req_x["ok"])
    # reply routes back along the inverse path: [S_owner, in_cap, ...] →
    # [S_req, out_cap, ...]
    rep = exch.gather(rep)
    rep = jax.tree.map(lambda x: _constrain(x, cfg), rep)
    # off the wire: re-expand shipped lanes to fold form (storage indices)
    rep = dict(
        rep,
        r_ei=expand_lanes(rep["r_ei"], spec.e_qr_i),
        r_ef=expand_lanes(rep["r_ef"], spec.e_qr_f),
        r_ti=expand_lanes(rep["r_ti"], spec.vr_i),
        r_tf=expand_lanes(rep["r_tf"], spec.vr_f),
        vq_i=expand_lanes(rep["vq_i"], spec.vq_i),
        vq_f=expand_lanes(rep["vq_f"], spec.vq_f),
    )
    return rep, req["ok"].sum(dtype=jnp.int32)


# Requester-side staging budget of one pull superstep, per device. The
# requester closes each pulled edge's suffix wedges against the pulled
# row; built at once as [pull_edge_cap, d_plus_max] candidate blocks per
# (shard, dest), the stacked batch outgrew HBM at R-MAT scale 14 (and
# padded every edge to the longest suffix). The window's wedges are
# instead enumerated by rank and built and folded in tiles whose staged
# entries fit this budget — the device-memory twin of the planner's
# ~4 MiB reply-window bound (pushpull._autotune_pull_q_cap).
PULL_STAGE_BYTES = 64 << 20
# int32-sized words XLA keeps live per staged wedge besides its [T, w]
# lane blocks (ranks, edge and row indices, bounds, hit masks, the p/q/r
# ids), sized against compiled memory_analysis() on TPU v5e
# (tests/test_tpu_compile.py holds two programs to the budget)
_PULL_STAGE_WORDS = 64


# Reply rows of at most this many slots are searched densely: each wedge
# gathers its whole pulled row and compares every slot with the closing
# vertex. Timed alone on a TPU v5e, the compare beats the keyed binary
# search at every width from 146 to 3,654 slots (3.7-6x at a common tile
# of 16,384 wedges). Up to 1,024 slots XLA fuses the row gather into the
# compare; from 2,048 it keeps the [T, Lr] block live, and the search
# alone takes 65 MB at 2,048 and 122 MB at 3,654 slots at the tile that
# pull_tile_wedges gives them. Wider rows (stable-order serve plans pad
# them to d_plus_max) keep the binary search, within the budget
DENSE_SEARCH_MAX_ROW = 1024
# the TPU's lane width: a staged [T, w] block holds w rounded up to it
_LANES = 128


def pull_search(use_pallas: bool, row_cap: int) -> str:
    """How the pull lane finds a wedge's closing vertex in its pulled row of
    ``row_cap`` slots: ``"pallas"`` (the ``wedge_check`` kernel),
    ``"dense"`` (:func:`_dense_row_search`) or ``"binary"``
    (:func:`_lower_bound`). A static property of the plan, which the
    planner's report carries as ``VolumeReport.pull_search``."""
    if use_pallas:
        return "pallas"
    return "dense" if row_cap <= DENSE_SEARCH_MAX_ROW else "binary"


def pull_tile_wedges(S_ax: int, extra_words: int, window_max: int) -> int:
    """Wedge slots per staged pull tile for ``S_ax`` stacked shards whose
    wedges each stage ``extra_words`` words besides the fixed ones (the
    triangle's metadata, the dense search's gathered row) — never more
    than the ``window_max`` wedges one window can hold."""
    return max(1, min(window_max, PULL_STAGE_BYTES
                      // (4 * S_ax * (_PULL_STAGE_WORDS + extra_words))))


def _pull_compute(gr: ShardedDODGr, ps, t, cfg: EngineConfig,
                  spec: MetaSpec, exch: Exchange, rep, survey: Survey,
                  state):
    """The fold half of one pull superstep: close the local suffix wedges
    of this window's pulled edges against the pulled rows ``rep`` (from
    :func:`_pull_wire` at the same ``t``) and fold the triangles into
    ``state``. Purely device-local — no collectives — so the mesh pipeline
    can overlap it with the next superstep's wire.

    The window's requester rows (one per (dest, edge slot), in that order)
    are weighted by their suffix wedge counts; wedge rank ``k`` of the
    inclusive cumsum addresses its edge and suffix position exactly as the
    push lane addresses its streams. Wedges are built and folded in tiles
    of :func:`pull_tile_wedges` ranks — as many tiles as the busiest shard
    needs — so the staged batch stays within :data:`PULL_STAGE_BYTES`
    and no slot is spent on an empty suffix position. Ranks follow the
    (dest, edge, suffix) order, so every survey sees its triangles in one
    fixed sequence. Returns ``(state, tris, checked, overflow, slots)``:
    the counts per shard, and the wedge slots the tiles staged
    (``n_tiles × T × S_ax``, filled or not)."""
    S, e_cap, n_loc = gr.S, gr.e_cap, gr.n_loc
    S_ax = gr.row_ptr.shape[0]
    ecap = cfg.pull_edge_cap
    Lr = cfg.pull_row_cap if cfg.pull_row_cap else gr.d_plus_max
    search = pull_search(cfg.use_pallas, Lr)
    n_steps = max(1, int(np.ceil(np.log2(max(2, Lr)))) + 1)
    out_cap = exch.out_cap

    # fold-form local sources (requester side)
    vp_i_l = narrow_lanes(gr.vmeta_i, spec.vp_i)
    vp_f_l = narrow_lanes(gr.vmeta_f, spec.vp_f)
    epq_i_l = narrow_lanes(gr.emeta_i, spec.e_pq_i)
    epq_f_l = narrow_lanes(gr.emeta_f, spec.e_pq_f)
    epr_i_l = narrow_lanes(gr.emeta_i, spec.e_pr_i)
    epr_f_l = narrow_lanes(gr.emeta_f, spec.e_pr_f)
    # each [T, w] lane block of the staged batch takes whole 128-lane
    # tiles on the TPU: the metadata fields, and the dense search's row
    lanes = lambda w: -(-w // _LANES) * _LANES
    meta_words = sum(lanes(x.shape[-1]) for x in (
        vp_i_l, vp_f_l, epq_i_l, epq_f_l, epr_i_l, epr_f_l, rep["vq_i"],
        rep["vq_f"], rep["r_ti"], rep["r_tf"], rep["r_ei"], rep["r_ef"]))
    row_words = lanes(Lr) if search == "dense" else 0
    # a suffix holds at most d_plus_max - 1 wedges
    T = pull_tile_wedges(S_ax, meta_words + row_words,
                         S * ecap * max(1, gr.d_plus_max - 1))
    # reply rows flattened [out_cap·Lr]: row ``ridx`` spans
    # [ridx·Lr, ridx·Lr + ln)
    flat = lambda x: x.reshape((S_ax, out_cap * Lr) + x.shape[3:])
    rows = {k: flat(rep[k]) for k in ("r_nbr", "r_d", "r_h", "r_ti", "r_tf",
                                      "r_ei", "r_ef")}
    if cfg.delta:
        rows["r_new"] = flat(rep["r_new"])
    if search == "dense":
        # the dense search reads neither sort key, but the planner prices
        # every reply slot with both and the mesh HLO reconciliation holds
        # the wire to the plan: a test true of every row keeps both key
        # lanes on the wire. It rests on every reply degree being >= 0: a
        # real degree, PAD_D or BIG_I32.
        # TODO(PERF.md §7 item 16): price only the lanes the requester
        # reads, let the reconciliation follow that plan, drop this test
        assert PAD_D >= 0 and int(BIG_I32) >= 0
        rep = dict(rep, ok=rep["ok"] & ((rep["r_d"][..., 0] >= 0)
                                        | (rep["r_h"][..., 0] == 0)))

    # jnp (not np) coercion: a mesh local view hands traced map rows
    pcap_d = jnp.asarray(exch.caps, jnp.int32)              # [S, S]
    boff = jnp.asarray(exch.block_off)                      # [S, S]

    if cfg.use_pallas:
        from repro.kernels.wedge_check import ops as wc_ops

    def window(qrank2, qbase, qcount, pulled_end, dest_start2, ord2, pull,
               gen, row_ptr, edge_src, pcap_d, boff):
        """Superstep t's requester rows: the pulled edge, its reply row and
        its suffix wedge count, with the inclusive wedge cumsum."""
        lo_rank = qbase + t * pcap_d
        hi_rank = qbase + jnp.minimum((t + 1) * pcap_d, qcount)
        estart = jnp.searchsorted(qrank2, lo_rank, side="left").astype(jnp.int32)
        eend = jnp.searchsorted(qrank2, hi_rank, side="left").astype(jnp.int32)
        estart = jnp.clip(estart, dest_start2, pulled_end)
        eend = jnp.clip(eend, dest_start2, pulled_end)
        overflow = jnp.maximum(eend - estart - ecap, 0).sum()
        row = jnp.arange(S * ecap, dtype=jnp.int32)
        d = row // ecap                                    # dest of the row
        j = estart[d] + row % ecap                         # ord2 index
        j_c = jnp.clip(j, 0, e_cap - 1)
        e = ord2[j_c]                                      # original edge slot
        ok = (j < eend[d]) & pull[e]
        if cfg.delta:
            # pulled edges outside the delta_gen mask cannot seed a new
            # triangle — skip their suffixes (keeps the wedges_pulled stat
            # equal to the planner's masked pulled_wedges accounting)
            ok = ok & gen[e]
        lp = jnp.clip(edge_src[e] // S, 0, n_loc - 1)
        w = jnp.where(ok, jnp.maximum(row_ptr[lp + 1] - e - 1, 0), 0)
        slot = jnp.clip(qrank2[j_c] - qbase[d] - t * pcap_d[d],
                        0, jnp.maximum(pcap_d[d] - 1, 0))
        ridx = jnp.clip(boff[d] + slot, 0, out_cap - 1)   # flat reply row
        return dict(e=e, ridx=ridx, cum=jnp.cumsum(w)), overflow

    with jax.named_scope("engine/pull/rank"):
        win, overflow = jax.vmap(window)(
            ps["qrank2"], ps["qbase"], ps["qcount"], ps["pulled_end"],
            ps["dest_start2"], ps["ord2"], ps["pull"], gr.delta_gen,
            gr.row_ptr, gr.edge_src, pcap_d, boff)
        total = win["cum"][:, -1]                          # wedges per shard

    def wedges(rank0, win, row_ptr, edge_src, nbr, nbr_d, nbr_h, nbr_new,
               epq_i, epq_f, epr_i, epr_f, vp_i, vp_f, rp, rows):
        """Tile of wedge ranks [rank0, rank0 + T): locate each wedge's
        edge and suffix position, search its key in the pulled row."""
        with jax.named_scope("engine/pull/rank"):
            cum = win["cum"]
            rank = rank0 + jnp.arange(T, dtype=jnp.int32)
            ok = rank < cum[-1]
            r = jnp.clip(jnp.searchsorted(cum, rank, side="right"),
                         0, S * ecap - 1).astype(jnp.int32)
            prev = jnp.where(r > 0, cum[jnp.maximum(r - 1, 0)], 0)
            e = win["e"][r]
            ridx = win["ridx"][r]
            r_pos = jnp.clip(e + 1 + rank - prev, 0, e_cap - 1)
        # the key search, the hit test and the gathers at the found slot
        with jax.named_scope("engine/pull/search"):
            ci = nbr[r_pos]
            ln = rp["ln"][ridx]
            lo = ridx * Lr
            if search == "dense":
                found, slot = _dense_row_search(rp["r_nbr"][ridx], ln, ci)
                pos_c = jnp.clip(lo + slot, 0, out_cap * Lr - 1)
            else:
                hi = lo + ln
                if search == "pallas":
                    pos = wc_ops.wedge_check(
                        rows["r_d"], rows["r_h"], rows["r_nbr"], lo, hi,
                        nbr_d[r_pos], nbr_h[r_pos], ci,
                        interpret=not kernels.compiled())
                else:
                    pos = _lower_bound(rows["r_d"], rows["r_h"],
                                       rows["r_nbr"], lo, hi, nbr_d[r_pos],
                                       nbr_h[r_pos], ci, n_steps)
                pos_c = jnp.clip(pos, 0, out_cap * Lr - 1)
                found = (pos < hi) & (rows["r_nbr"][pos_c] == ci)
            # the reply header's ok word (the owner's view of request
            # validity) rides back with the rows; AND-ing it in is a no-op
            # on every slot the requester's own maps admit, and keeps the
            # planned header word live on the wire
            hit = ok & rp["ok"][ridx] & found
            if cfg.delta:
                hit &= nbr_new[e] | nbr_new[r_pos] | rows["r_new"][pos_c]
            lp = jnp.clip(edge_src[e] // S, 0, n_loc - 1)
            return TriangleBatch(
                p=edge_src[e], q=nbr[e], r=ci,
                vp_i=vp_i[lp], vq_i=rp["vq_i"][ridx],
                vr_i=rows["r_ti"][pos_c],
                vp_f=vp_f[lp], vq_f=rp["vq_f"][ridx],
                vr_f=rows["r_tf"][pos_c],
                e_pq_i=epq_i[e], e_pr_i=epr_i[r_pos],
                e_qr_i=rows["r_ei"][pos_c],
                e_pq_f=epq_f[e], e_pr_f=epr_f[r_pos],
                e_qr_f=rows["r_ef"][pos_c],
                valid=hit,
            )

    def tile(i, carry):
        state, tris = carry
        tri = jax.vmap(partial(wedges, i * T))(
            win, gr.row_ptr, gr.edge_src, gr.nbr, gr.nbr_d, gr.nbr_h,
            gr.nbr_new, epq_i_l, epq_f_l, epr_i_l, epr_f_l, vp_i_l, vp_f_l,
            rep, rows)
        with jax.named_scope("engine/fold"):
            state = jax.vmap(survey.update)(state, tri)
        with jax.named_scope("engine/pull/search"):
            tris = tris + tri.valid.sum(axis=1, dtype=jnp.int32)
        return state, tris

    n_tiles = (total.max() + (T - 1)) // T
    state, tris = jax.lax.fori_loop(
        0, n_tiles, tile, (state, jnp.zeros((S_ax,), jnp.int32)))
    return state, tris, total, overflow, n_tiles * (T * S_ax)


# ---------------------------------------------------------------------------
# top-level survey functions


# static per-step wire-words stats: every device accumulates the identical
# value, so the mesh path keeps one copy instead of summing over devices
_WIRE_STAT_KEYS = ("wire_push_words", "wire_req_words", "wire_reply_words")

# the counted stats. On the device each is an int32 vector of
# per-superstep counts (a lane's scan output, or one setup-time entry); the
# host adds them up as Python ints (:func:`sum_stats`), so a survey's
# counts stay exact however many supersteps it runs
STAT_KEYS = ("wedges_pushed", "tris_push", "wedges_pulled", "tris_pull",
             "wedges_hub", "tris_hub", "pull_requests", "pull_overflow",
             "stream_dropped") + _WIRE_STAT_KEYS + ("pull_tile_slots",)


def sum_stats(stats) -> dict:
    """Host-side totals of a survey program's per-superstep stats: each
    key's int32 parts summed as Python ints (exact at any size)."""
    return {k: int(np.asarray(v, np.int64).sum())
            for k, v in jax.device_get(stats).items()}


def _survey_body(gr: ShardedDODGr, survey: Survey, cfg: EngineConfig,
                 spec: MetaSpec, push_exch: Exchange,
                 pull_exch: Exchange | None):
    """The superstep pipeline, shared verbatim by both lowerings: on the
    stacked path ``gr`` carries all ``S`` shards ([S, ...] leaves, host
    transports); under ``shard_map`` it is one device's shard ([1, ...]
    leaves, a :class:`~repro.comm.mesh_exchange.LocalMeshView` per lane).
    Returns the *unmerged* per-shard state stack and the per-superstep
    stats (``STAT_KEYS``, int32 vectors)."""
    S_ax = gr.row_ptr.shape[0]    # leading shard axis: S stacked, 1 on mesh

    # routing tables live across every superstep: pin them to the shard
    # axis or the partitioner replicates the [S, e_cap] masks per device
    # (measured: 2×36 GB/device on the rmat32 cell; EXPERIMENTS §Perf)
    pin = lambda tree: jax.tree.map(lambda a: _constrain(a, cfg), tree)

    # planner-stamped widths win so host plan and device decisions
    # agree even if the plan was built for a different spec
    mw = cfg.meta_widths
    if mw is None:
        mw = meta_widths(*spec.lane_counts())
        if cfg.delta:   # newness bits on the wire (see plan_engine)
            mw = (mw[0] + 1, mw[1] + 1, mw[2], mw[3])
    w_push, w_row, w_hdr, w_req = mw

    hub_on = cfg.n_hub_steps > 0 and gr.n_hubs > 0
    is_hub = (gr.nbr_hub >= 0) if hub_on else None
    gen = gr.delta_gen if cfg.delta else None

    with jax.named_scope("engine/setup"):
        state = jax.tree.map(lambda x: jnp.repeat(x[None], S_ax, 0),
                             survey.init())
        dropped = jnp.zeros((), jnp.int32)
        push_caps_j = jnp.asarray(push_exch.caps, jnp.int32)
        if cfg.mode == "pushpull":
            st0 = pin(_stream_setup(gr))
            sfx = st0["suffix"]
            if cfg.delta:
                # pull decisions weigh only wedges the delta mask
                # generates, mirroring the planner's masked vol(s, q)
                sfx = sfx * gen
            if hub_on:
                # hub-centered groups carry zero pullable volume
                sfx = sfx * (~is_hub)
            st0 = dict(st0, suffix=sfx)
            ps = pin(_pull_setup(gr, st0, cfg, mw, hub_mask=is_hub))
            push_mask = ~ps["pull"]
            if cfg.delta:
                push_mask = push_mask & gen
            if hub_on:
                push_mask = push_mask & ~is_hub
            st = pin(_stream_setup(gr, weight_mask=push_mask))
            pull_caps_j = jnp.asarray(pull_exch.caps, jnp.int32)
            dropped += jnp.maximum(
                ps["qcount"] - cfg.n_pull_steps * pull_caps_j, 0
            ).sum(dtype=jnp.int32)
        else:
            ps = None
            wm = None
            if cfg.delta and hub_on:
                wm = gen & ~is_hub
            elif cfg.delta:
                wm = gen
            elif hub_on:
                wm = ~is_hub
            st = pin(_stream_setup(gr, weight_mask=wm))
        dropped += jnp.maximum(
            st["stream_len"] - cfg.n_push_steps * push_caps_j, 0
        ).sum(dtype=jnp.int32)

        if hub_on:
            hmask = is_hub if gen is None else (is_hub & gen)
            hst = pin(_hub_setup(gr, st, hmask))
            dropped += jnp.maximum(
                hst["total"] - cfg.n_hub_steps * cfg.hub_wedge_cap, 0
            ).sum(dtype=jnp.int32)

    # each lane's supersteps hand their counts out as scan outputs
    parts: dict[str, list] = {k: [] for k in STAT_KEYS}
    parts["stream_dropped"].append(dropped[None])

    def collect(ys):
        for k, v in ys.items():
            parts[k].append(v.reshape(-1))

    # measured wire volume of one superstep: every slot (including block
    # padding) that crosses the shard axis through the transport
    push_step_words = jnp.int32(push_exch.round_slots() * w_push)

    # On the mesh lowering the superstep loops run as a double-buffered
    # pipeline: superstep t+1's wire (the scatter/gather collectives) is
    # issued before superstep t's fold, so XLA can overlap the next
    # transfer with the current answer/intersect/update. Fold t still
    # consumes exactly wire t's output and emits the same per-superstep
    # counts, so results and stats stay bitwise-identical to the
    # sequential stacked loop (tests/test_mesh.py; docs/mesh.md).
    pipelined = cfg.transport == "mesh"

    def push_wire(t):
        with jax.named_scope("engine/push/wire"):
            qr = _gen_push_queries(gr, st, t, push_exch, spec,
                                   delta=cfg.delta)
            qx = push_exch.scatter(qr)
            qx = dict(qx, ok=push_exch.apply_recv_ok(qx["ok"]))
            qx = jax.tree.map(lambda x: _constrain(x, cfg), qx)
            return qx, qr["ok"].sum(dtype=jnp.int32)

    def push_fold(state, qx, n_gen):
        with jax.named_scope("engine/push/check"):
            tri = _answer_push_queries(gr, qx, cfg, spec)
            n_tri = tri.valid.sum(dtype=jnp.int32)
        with jax.named_scope("engine/fold"):
            state = jax.vmap(survey.update)(state, tri)
        return state, dict(wedges_pushed=n_gen, tris_push=n_tri,
                           wire_push_words=push_step_words)

    if pipelined and cfg.n_push_steps > 0:
        qx, n_gen = push_wire(jnp.int32(0))

        def push_pipe(carry, t):
            state, qx, n_gen = carry
            qx2, n_gen2 = push_wire(t + 1)   # wire t+1 before fold t
            state, ys = push_fold(state, qx, n_gen)
            return (state, qx2, n_gen2), ys

        if cfg.n_push_steps > 1:
            (state, qx, n_gen), ys = jax.lax.scan(
                push_pipe, (state, qx, n_gen),
                jnp.arange(cfg.n_push_steps - 1, dtype=jnp.int32),
                unroll=(cfg.n_push_steps - 1) if cfg.unroll_steps else 1)
            collect(ys)
        state, ys = push_fold(state, qx, n_gen)
        collect(ys)
    else:
        def push_step(state, t):
            qx, n_gen = push_wire(t)
            return push_fold(state, qx, n_gen)

        state, ys = jax.lax.scan(
            push_step, state,
            jnp.arange(cfg.n_push_steps, dtype=jnp.int32),
            unroll=cfg.n_push_steps if cfg.unroll_steps else 1)
        collect(ys)

    if hub_on:
        def hub_step(state, t):
            with jax.named_scope("engine/hub"):
                tri, n_w = _hub_superstep(gr, hst, t, cfg, spec)
                ys = dict(wedges_hub=n_w.sum(),
                          tris_hub=tri.valid.sum(dtype=jnp.int32))
            with jax.named_scope("engine/fold"):
                state = jax.vmap(survey.update)(state, tri)
            return state, ys

        state, ys = jax.lax.scan(
            hub_step, state,
            jnp.arange(cfg.n_hub_steps, dtype=jnp.int32),
            unroll=cfg.n_hub_steps if cfg.unroll_steps else 1)
        collect(ys)

    if cfg.mode == "pushpull" and cfg.n_pull_steps > 0:
        Lr = cfg.pull_row_cap if cfg.pull_row_cap else gr.d_plus_max
        req_step_words = jnp.int32(pull_exch.round_slots() * w_req)
        reply_step_words = jnp.int32(
            pull_exch.round_slots() * (w_hdr + Lr * w_row))

        def pull_wire(t):
            with jax.named_scope("engine/pull/wire"):
                return _pull_wire(gr, ps, t, cfg, spec, pull_exch)

        def pull_fold(state, t, rep, n_req):
            state, tris, checked, overflow, slots = _pull_compute(
                gr, ps, t, cfg, spec, pull_exch, rep, survey, state)
            return state, dict(
                wedges_pulled=checked.sum(dtype=jnp.int32),
                tris_pull=tris.sum(dtype=jnp.int32),
                pull_requests=n_req,
                pull_overflow=overflow.sum(dtype=jnp.int32),
                wire_req_words=req_step_words,
                wire_reply_words=reply_step_words,
                pull_tile_slots=slots)

        if pipelined:
            rep, n_req = pull_wire(jnp.int32(0))

            def pull_pipe(carry, t):
                state, rep, n_req = carry
                rep2, n_req2 = pull_wire(t + 1)            # wire t+1 ...
                state, ys = pull_fold(state, t, rep, n_req)
                return (state, rep2, n_req2), ys           # ... fold t

            if cfg.n_pull_steps > 1:
                (state, rep, n_req), ys = jax.lax.scan(
                    pull_pipe, (state, rep, n_req),
                    jnp.arange(cfg.n_pull_steps - 1, dtype=jnp.int32),
                    unroll=(cfg.n_pull_steps - 1) if cfg.unroll_steps else 1)
                collect(ys)
            state, ys = pull_fold(
                state, jnp.int32(cfg.n_pull_steps - 1), rep, n_req)
            collect(ys)
        else:
            def pull_step(state, t):
                rep, n_req = pull_wire(t)
                return pull_fold(state, t, rep, n_req)

            state, ys = jax.lax.scan(
                pull_step, state,
                jnp.arange(cfg.n_pull_steps, dtype=jnp.int32),
                unroll=cfg.n_pull_steps if cfg.unroll_steps else 1)
            collect(ys)

    stats = {k: (jnp.concatenate(v) if v else jnp.zeros((1,), jnp.int32))
             for k, v in parts.items()}
    return state, stats


def make_survey_fn(survey: Survey, cfg: EngineConfig, mesh=None):
    """Build the jittable global survey function ``gr -> (merged_state,
    stats)``.

    ``mesh=None`` (the default) is the historic stacked lowering: all ``S``
    shards are vmap lanes of one program, transports move bytes with
    reshapes/gathers, results bit-for-bit what every prior PR produced.

    Passing a 1-D device mesh (``launch.make_shard_mesh(S)``) lowers the
    same superstep body through ``shard_map``: one shard per device, hub
    tables replicated, and every transport ``scatter``/``gather`` executing
    *real* collectives (:mod:`repro.comm.mesh_exchange` — a literal
    ``all_to_all`` for uniform caps, ``ppermute`` rotation rounds for
    ragged). Survey results are bitwise-identical to the stacked path:
    the per-device recv buffers are compacted to the exact stacked layout,
    per-shard state is restacked before ``survey.merge``, and every
    counted stat is an int32 count per superstep, summed over devices per
    superstep and over supersteps on the host (:func:`sum_stats`), so the
    split reduction is exact (tests/test_mesh.py asserts all of this;
    docs/mesh.md explains it). ``pull_tile_slots`` counts what each
    lowering staged, so it is the one stat the two lowerings may differ on.
    """
    if mesh is None:
        if cfg.transport == "mesh":
            raise ValueError(
                "a transport='mesh' plan runs real collectives — pass "
                "mesh=launch.make_shard_mesh(S) to make_survey_fn / the "
                "survey entry points, or re-plan with transport='dense' or "
                "'ragged' for the stacked path")

        def run(gr: ShardedDODGr):
            spec = resolve_survey_spec(survey, gr, cfg)
            push_exch = _push_exchange(cfg, gr.S)
            pull_exch = (_pull_exchange(cfg, gr.S)
                         if cfg.mode == "pushpull" else None)
            state, stats = _survey_body(gr, survey, cfg, spec, push_exch,
                                        pull_exch)
            with jax.named_scope("engine/fold"):
                return survey.merge(state), stats

        return run

    from repro.core.dodgr import mesh_specs

    axis = mesh.axis_names[-1]
    n_dev = int(np.prod([mesh.shape[a] for a in mesh.axis_names]))
    # sharding-constraint hints are for the GSPMD path; inside shard_map
    # the placement *is* the program
    cfg_body = replace(cfg, shard_axis=None)

    def run(gr: ShardedDODGr):
        if n_dev != gr.S:
            raise ValueError(
                f"mesh has {n_dev} device(s) along {mesh.axis_names} but "
                f"the graph has S={gr.S} shards; build it with "
                "launch.make_shard_mesh(S)")
        spec = resolve_survey_spec(survey, gr, cfg)
        push_exch = make_exchange("mesh", gr.S, cfg.push_cap, cfg.push_caps,
                                  axis_name=axis)
        pull_exch = (make_exchange("mesh", gr.S, cfg.pull_q_cap,
                                   cfg.pull_caps, axis_name=axis)
                     if cfg.mode == "pushpull" else None)

        def body(grl: ShardedDODGr):
            idx = jax.lax.axis_index(axis)
            pe = push_exch.local_view(idx)
            qe = (pull_exch.local_view(idx)
                  if pull_exch is not None else None)
            state, stats = _survey_body(grl, survey, cfg_body, spec, pe, qe)
            # stats leave the shard_map as [1]-stacks along the mesh axis
            return state, {k: v[None] for k, v in stats.items()}

        sm = jax.shard_map(body, mesh=mesh,
                           in_specs=(mesh_specs(gr, axis),),
                           out_specs=(P(axis), P(axis)), check_vma=False)
        state, stats = sm(gr)
        stats = {k: (v[0] if k in _WIRE_STAT_KEYS else v.sum(0))
                 for k, v in stats.items()}
        with jax.named_scope("engine/fold"):
            return survey.merge(state), stats

    return run


def resolve_survey_spec(survey: Survey, gr: ShardedDODGr,
                        cfg: EngineConfig | None = None) -> MetaSpec:
    """Concretize the survey's declared lanes against the graph's storage
    widths (all static under jit). ``cfg.project_meta=False`` forces the
    full-metadata spec — the historic all-lanes behavior."""
    dvi, dvf = gr.vmeta_i.shape[-1], gr.vmeta_f.shape[-1]
    dei, def_ = gr.emeta_i.shape[-1], gr.emeta_f.shape[-1]
    spec = getattr(survey, "meta_spec", None)
    if spec is None or (cfg is not None and not cfg.project_meta):
        spec = MetaSpec.full()
    return spec.resolve(dvi, dvf, dei, def_)


def _exactness_guard(cfg: EngineConfig, stats: dict) -> dict:
    """Satellite: a static window that overflowed means triangles were
    silently dropped — flag the run inexact, and say so loudly."""
    lost = stats.get("pull_overflow", 0) + stats.get("stream_dropped", 0)
    stats["exact"] = lost == 0
    if lost > 0:
        msg = (
            f"survey result is INEXACT: {stats.get('pull_overflow', 0)} "
            f"pull-window candidate(s) and "
            f"{stats.get('stream_dropped', 0)} stream slot(s) overflowed "
            "their static capacities and were dropped, so triangles are "
            "undercounted. Use the capacities planned by "
            "pushpull.plan_engine/plan_delta (they size every window "
            "exactly), or pass on_overflow='raise' to fail fast.")
        if cfg.on_overflow == "raise":
            raise RuntimeError(msg)
        warnings.warn(msg, RuntimeWarning, stacklevel=3)
    return stats


def _finalize_run(survey: Survey, cfg: EngineConfig, merged, stats):
    """Host-side epilogue shared by the entry points: per-survey stats
    (exact integer totals), exactness guard, DOULION debiasing + its
    variance estimate (Tsourakakis et al.)."""
    with span("finalize"):
        stats = sum_stats(stats)
        members = getattr(survey, "surveys", (survey,))
        stats["n_surveys"] = float(len(members))
        stats = _exactness_guard(cfg, stats)
        result = survey.finalize(merged)
        if cfg.sample_p < 1.0:
            p = cfg.sample_p
            result = survey.scale_sampled(result, p)
            raw = stats["tris_push"] + stats["tris_pull"] + stats["tris_hub"]
            est = raw / p**3
            # Var[T̂] ≈ T(1/p³ − 1) (independent-triangle term; the
            # shared-edge covariance term needs the per-edge triangle
            # multiset — see ref.py)
            var = est * (1.0 / p**3 - 1.0)
            stats["sample_p"] = p
            stats["sample_scale"] = 1.0 / p**3
            stats["sample_variance"] = var
            stats["sample_rel_stderr"] = float(np.sqrt(var) / max(est, 1.0))
        return result, stats


def _check_sampling(gr: ShardedDODGr, cfg: EngineConfig) -> list[str]:
    g_key = (gr.sample_p, gr.sample_seed)
    c_key = (cfg.sample_p, cfg.sample_seed)
    if gr.sample_p == cfg.sample_p == 1.0:
        return []  # unsampled on both sides; seeds are irrelevant
    if g_key != c_key:
        return [
            f"sampling mismatch: graph ingested with (p, seed)={g_key} but "
            f"plan built with {c_key}; pass the same sample_p/sample_seed "
            "to shard_dodgr and plan_engine"]
    return []


def _check_provenance(gr: ShardedDODGr, cfg: EngineConfig):
    """Graph stamps and plan stamps must agree — sampling, orientation key,
    hub threshold, and epoch/delta state — or results are silently wrong.

    Collects *every* diverged field and reports both the graph-side and
    plan-side value for each, so one error names the complete repair
    instead of failing one stamp at a time."""
    diffs = _check_sampling(gr, cfg)
    if gr.is_delta != cfg.delta:
        what = "a delta frontier" if gr.is_delta else "a full snapshot"
        want = "survey_delta with a plan_delta plan" if gr.is_delta \
            else "survey_push_only/survey_push_pull with a plan_engine plan"
        diffs.append(
            f"delta mismatch: graph is {what} (is_delta={gr.is_delta}) but "
            f"the plan stamps delta={cfg.delta}; run it through {want}")
    if gr.orient != cfg.orient:
        diffs.append(
            f"orientation mismatch: graph sharded with orient={gr.orient!r} "
            f"but plan built with orient={cfg.orient!r}")
    if gr.hub_theta != cfg.hub_theta:
        diffs.append(
            f"hub mismatch: graph sharded with hub_theta={gr.hub_theta} but "
            f"plan built with hub_theta={cfg.hub_theta}; pass the planner's "
            "θ (cfg.hub_theta) to shard_dodgr/shard_delta")
    if cfg.delta and gr.is_delta and gr.epoch != cfg.epoch:
        diffs.append(
            f"epoch mismatch: frontier is epoch {gr.epoch} but the plan was "
            f"built for epoch {cfg.epoch}; re-plan each appended batch")
    if diffs:
        raise ValueError(
            "graph/plan provenance diverged on "
            f"{len(diffs)} field(s):\n  - " + "\n  - ".join(diffs))


def survey_push_only(gr: ShardedDODGr, survey: Survey, cfg: EngineConfig,
                     mesh=None):
    _check_provenance(gr, cfg)
    cfg = replace(cfg, mode="push")
    fn = jax.jit(make_survey_fn(survey, cfg, mesh=mesh))
    merged, stats = fn(gr)
    return _finalize_run(survey, cfg, merged, stats)


def survey_push_pull(gr: ShardedDODGr, survey: Survey, cfg: EngineConfig,
                     mesh=None):
    _check_provenance(gr, cfg)
    cfg = replace(cfg, mode="pushpull")
    fn = jax.jit(make_survey_fn(survey, cfg, mesh=mesh))
    merged, stats = fn(gr)
    return _finalize_run(survey, cfg, merged, stats)


def survey_with_fn(gr: ShardedDODGr, survey: Survey, cfg: EngineConfig, fn):
    """Run a *pre-built* jitted survey closure (``jax.jit(make_survey_fn(
    survey, cfg))`` or its raw result) through the same provenance check and
    host epilogue as the one-shot entry points.

    This is the serving fast path: a plan-cache hit replays the cached
    closure against the cached shards and skips ``plan_engine``, re-sharding
    and recompilation entirely — bitwise-identical to a cold
    :func:`survey_push_only`/:func:`survey_push_pull` run because both paths
    execute the identical traced program on the identical arrays (the
    warm == cold == solo entry of docs/determinism.md's identity lattice).
    The caller is responsible for pairing ``fn`` with the ``(survey, cfg)``
    it was built from; provenance between ``gr`` and ``cfg`` is still
    cross-checked here, so a stale graph can never run under a cached plan.
    """
    _check_provenance(gr, cfg)
    merged, stats = fn(gr)
    return _finalize_run(survey, cfg, merged, stats)


# ---------------------------------------------------------------------------
# epoch-incremental entry point (delta engine)


def survey_delta(gr: ShardedDODGr, survey: Survey, cfg: EngineConfig,
                 prev_state=None, mesh=None):
    """One incremental epoch: traverse the delta frontier ``gr``, folding
    ONLY triangles that contain ≥1 edge of the current batch (the
    new-old-old / new-new-old / new-new-new classes), then accumulate into
    ``prev_state`` through the survey's ``merge_epochs`` contract.

    ``cfg`` must come from ``pushpull.plan_delta`` for the same
    :class:`~repro.graphs.csr.DeltaGraph` epoch (provenance is
    cross-checked). Returns ``(state, stats)`` where ``state`` is the
    cross-shard-merged but *not finalized* accumulator — feed it back as
    ``prev_state`` for the next batch and render results at any point with
    :func:`finalize_epochs`. The invariant (asserted in tests): after K
    batches, ``finalize_epochs`` equals one full survey of the unioned
    graph, bitwise, for every built-in survey.
    """
    if not cfg.delta:
        raise ValueError("survey_delta needs a delta plan — build cfg with "
                         "pushpull.plan_delta(dg, S, survey, ...)")
    if cfg.sample_p < 1.0:
        raise ValueError("DOULION sampling is not supported on delta epochs; "
                         "sample the full snapshot instead")
    _check_provenance(gr, cfg)
    if prev_state is not None and cfg.determinism == "order_sensitive":
        warnings.warn(
            "survey_delta: the plan's survey was classified "
            "order_sensitive by the static verifier (repro.analysis) — "
            "accumulating it through merge_epochs holds the incremental == "
            "recompute identity only up to float reduction order, not "
            "bitwise. Run `python -m repro.analysis` for the reasons.",
            RuntimeWarning, stacklevel=2)
    fn = jax.jit(make_survey_fn(survey, cfg, mesh=mesh))
    merged, stats = fn(gr)
    stats = sum_stats(stats)
    stats["epoch"] = float(cfg.epoch)
    stats["n_surveys"] = float(len(getattr(survey, "surveys", (survey,))))
    stats = _exactness_guard(cfg, stats)
    if prev_state is not None:
        merged = survey.merge_epochs(prev_state, merged)
    return merged, stats


def finalize_epochs(survey: Survey, state):
    """Render an epoch accumulator (from :func:`survey_delta`) host-side —
    the delta-engine analogue of the one-shot finalize."""
    return survey.finalize(jax.device_get(state))
