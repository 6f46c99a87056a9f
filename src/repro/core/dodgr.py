"""Degree-ordered directed graph (DODGr), sharded (paper Sec. 3 / 4.2).

Storage layout is *stacked*: every array carries a leading shard axis ``S``.
On one host device this is just an array; under ``jit`` with an
``in_shardings`` that places axis 0 over the device mesh it becomes the
distributed storage, and cross-shard axis-0 reorganizations lower to
all-to-all / all-reduce collectives (DESIGN.md §2). Vertices are cyclic
partitioned: owner ``v % S``, local row ``v // S``.

Per the paper's ``Adj₊ᵐ`` the target vertex's metadata is stored *on the
edge* (``tmeta``) so all six metadata items are local when a wedge closes.
We additionally store the target's full degree/hash (the ``<₊`` sort key)
and its out-degree ``d₊`` (enables the local push-vs-pull decision,
Sec. 4.4: "requires only a small constant amount of additional memory per
edge").
"""
from __future__ import annotations

from dataclasses import dataclass
from functools import partial

import jax
import numpy as np
import jax.numpy as jnp

from repro.graphs.csr import DeltaGraph, HostGraph
from repro.utils import bucket_cap, ceil_div, span, splitmix32_np

PAD_ID = np.int32(2**31 - 1)  # sentinel target id for padded edge slots
PAD_D = np.int32(2**30)       # sentinel degree (sorts after everything real)

ORIENTS = ("degree", "stable")


def meta_widths(n_vp: int, n_vq: int, n_vr: int,
                n_epq: int, n_epr: int, n_eqr: int):
    """Wire-format entry widths in 4-byte words, shared by the device engine
    and the host planner so push-vs-pull decisions agree byte-for-byte.

    Takes the *declared lane count* (int + float) of each of the six
    metadata items — the survey's resolved ``MetaSpec.lane_counts()`` —
    not the raw storage widths, so the cost model (and therefore every
    per-(shard, q) push-vs-pull decision) is survey-aware. A full-metadata
    survey passes ``(dvi+dvf, dvi+dvf, dvi+dvf, dei+def, dei+def,
    dei+def)`` and reproduces the historic full widths.

    (push_entry, row_entry, row_header, request_entry):
      push entry = q,r,key_d,key_h,p,ok + meta(p) + meta(pq) + meta(pr)
      row entry  = nbr,key_d,key_h + meta(q,v) + meta(v)
      row header = row_len + meta(q); request = q + ok
    """
    w_push = 6 + n_vp + n_epq + n_epr
    w_row = 3 + n_eqr + n_vr
    w_hdr = 2 + n_vq
    w_req = 2
    return w_push, w_row, w_hdr, w_req


def hub_widths(dvi: int, dvf: int, dei: int, def_: int,
               delta: bool = False) -> tuple[int, int]:
    """Replicated hub-table widths in 4-byte words: ``(w_elem, w_hdr)``.

    Unlike the wire entries above, the hub table is built at ingestion time
    — before any survey is known — so it stores the *full* metadata widths:

      element = nbr, key_d, key_h + meta(qr) + meta(r)   (+ newness in delta)
      header  = row_len + meta(q)
    """
    w_elem = 3 + dei + def_ + dvi + dvf + (1 if delta else 0)
    w_hdr = 1 + dvi + dvf
    return w_elem, w_hdr


@dataclass(frozen=True)
class ShardedDODGr:
    """Stacked sharded DODGr + metadata. Leading axis of every array = shard."""

    # --- static (aux) ---
    S: int
    n_global: int
    n_loc: int
    e_cap: int
    d_plus_max: int
    # --- per-shard arrays ---
    row_ptr: jax.Array   # [S, n_loc+1] i32
    edge_src: jax.Array  # [S, e_cap] i32 global pivot id per edge slot
    nbr: jax.Array       # [S, e_cap] i32 global target id (row-sorted by key)
    nbr_d: jax.Array     # [S, e_cap] i32 target full degree
    nbr_h: jax.Array     # [S, e_cap] u32 target hash
    nbr_dplus: jax.Array  # [S, e_cap] i32 target out-degree d₊
    emeta_i: jax.Array   # [S, e_cap, dei] i32
    emeta_f: jax.Array   # [S, e_cap, def] f32
    tmeta_i: jax.Array   # [S, e_cap, dvi] i32 (target vertex metadata)
    tmeta_f: jax.Array   # [S, e_cap, dvf] f32
    vmeta_i: jax.Array   # [S, n_loc, dvi] i32
    vmeta_f: jax.Array   # [S, n_loc, dvf] f32
    vdeg: jax.Array      # [S, n_loc] i32 full degree of local vertex
    dplus: jax.Array     # [S, n_loc] i32 out-degree of local vertex
    # --- delta overlay (epoch-aware ingestion) ---
    nbr_new: jax.Array    # [S, e_cap] bool — edge arrived this epoch
    delta_gen: jax.Array  # [S, e_cap] bool — edge may open a new-triangle wedge
    # --- hub delegation (two-tier exchange, Arifuzzaman-style heavy-vertex
    # split): the Adj₊ rows of every vertex with full degree ≥ hub_theta are
    # replicated to all shards as a read-only table, so wedges whose center q
    # is a hub close on the *source* shard with zero exchange. Hub arrays
    # carry no leading shard axis — under GSPMD they are replicated. ---
    nbr_hub: jax.Array     # [S, e_cap] i32 hub-table row of target q, -1 if not hub
    hub_row_len: jax.Array  # [Hc] i32 (Hc = max(1, n_hubs))
    hub_nbr: jax.Array      # [Hc, hub_len] i32 Adj₊ targets (row-sorted by key)
    hub_nbr_d: jax.Array    # [Hc, hub_len] i32
    hub_nbr_h: jax.Array    # [Hc, hub_len] u32
    hub_nbr_new: jax.Array  # [Hc, hub_len] bool
    hub_eqr_i: jax.Array    # [Hc, hub_len, dei] i32  meta(q, r)
    hub_eqr_f: jax.Array    # [Hc, hub_len, def] f32
    hub_tmeta_i: jax.Array  # [Hc, hub_len, dvi] i32  meta(r)
    hub_tmeta_f: jax.Array  # [Hc, hub_len, dvf] f32
    hub_vmeta_i: jax.Array  # [Hc, dvi] i32            meta(q) of the hub itself
    hub_vmeta_f: jax.Array  # [Hc, dvf] f32
    # --- DOULION sampling provenance (static) — the engine entry points
    # cross-check these against EngineConfig so a graph ingested with one
    # (p, seed) can never run under a plan built for another ---
    sample_p: float = 1.0
    sample_seed: int = 0
    # --- epoch provenance (static): orientation key, current epoch, and
    # whether this is a delta frontier (cross-checked like sample_p so a
    # frontier can never run under a full-snapshot plan or vice versa) ---
    orient: str = "degree"
    epoch: int = 0
    is_delta: bool = False
    # --- hub provenance (static): θ the table was built with (0 = no hub
    # delegation), hub count, and padded row length — cross-checked against
    # the plan like sample_p so a graph sharded with one θ can never run
    # under a plan that delegated a different hub set ---
    hub_theta: int = 0
    n_hubs: int = 0
    hub_len: int = 1
    # --- hub-row sourcing (static): "frontier" = rows rebuilt from this
    # view's own edges (the historic inline build); "union" = rows served
    # from a HubTableCache across delta epochs (each row is the hub's full
    # union Adj₊ — a superset of its frontier row). Union rows are only
    # ever stamped on delta frontiers, where the ≥1-new-edge fold mask
    # provably discards every extra (all-old) table hit, so results stay
    # bitwise-identical to a frontier-row build (tests/test_hub_reuse.py) ---
    hub_rows: str = "frontier"

    def __post_init__(self):
        pass

    # number of valid (non-pad) oriented edges per shard
    def edge_valid(self) -> jax.Array:
        e = jnp.arange(self.e_cap, dtype=jnp.int32)[None, :]
        return e < self.row_ptr[:, -1:]


# field split used both by the pytree registration and by the mesh lowering:
# PER_SHARD fields carry the leading [S, ...] shard axis (split one shard per
# device under shard_map); REPLICATED fields are the hub tables (no shard
# axis — every device holds the full read-only copy, see class docstring)
PER_SHARD_FIELDS = (
    "row_ptr", "edge_src", "nbr", "nbr_d", "nbr_h", "nbr_dplus",
    "emeta_i", "emeta_f", "tmeta_i", "tmeta_f", "vmeta_i", "vmeta_f",
    "vdeg", "dplus", "nbr_new", "delta_gen", "nbr_hub",
)
REPLICATED_FIELDS = (
    "hub_row_len", "hub_nbr", "hub_nbr_d", "hub_nbr_h", "hub_nbr_new",
    "hub_eqr_i", "hub_eqr_f", "hub_tmeta_i", "hub_tmeta_f",
    "hub_vmeta_i", "hub_vmeta_f",
)
META_FIELDS = ("S", "n_global", "n_loc", "e_cap", "d_plus_max",
               "sample_p", "sample_seed", "orient", "epoch", "is_delta",
               "hub_theta", "n_hubs", "hub_len", "hub_rows")

jax.tree_util.register_dataclass(
    ShardedDODGr,
    data_fields=list(PER_SHARD_FIELDS) + list(REPLICATED_FIELDS),
    meta_fields=list(META_FIELDS),
)


def mesh_specs(gr: ShardedDODGr, axis_name: str):
    """A ShardedDODGr-shaped pytree of ``PartitionSpec`` for ``shard_map``:
    per-shard arrays split over ``axis_name`` (one shard per device), hub
    tables replicated. The static meta fields ride along unchanged, so the
    result is a valid ``in_specs`` entry for the graph argument."""
    from jax.sharding import PartitionSpec as P

    kw = {f: getattr(gr, f) for f in META_FIELDS}
    for f in PER_SHARD_FIELDS:
        kw[f] = P(axis_name)
    for f in REPLICATED_FIELDS:
        kw[f] = P()
    return ShardedDODGr(**kw)


@dataclass(frozen=True)
class RoutingStats:
    """Host-side facts the engine needs to pick static superstep counts."""

    wedges_total: int          # |W₊|
    max_stream: int            # max over (shard, dest) of wedge-stream length
    max_pairs: int             # max over (shard, dest) of distinct (p,q) edges
    edges_per_shard: np.ndarray  # [S]
    wedge_per_shard: np.ndarray  # [S]


def orient_edges(g: HostGraph, orient: str = "degree"):
    """Host orientation of every undirected edge by the ``<₊`` key.

    ``orient`` picks the first component of the total order:

    * ``"degree"`` — the paper's degree-ordered key ``(deg, hash, id)``;
      best work bound, but the key *changes* as edges are appended.
    * ``"stable"`` — the epoch-stable key ``(0, hash, id)``: a vertex's rank
      never moves when later batches arrive, so every epoch of a delta
      sequence (and the full recompute it is checked against) assigns each
      triangle the same ``(p, q, r)`` roles — the bitwise-identity
      requirement of ``merge_epochs``.

    Returns ``(p, q, okey, h)`` where ``okey`` is the per-vertex first key
    component (the *orientation* key, not necessarily the degree).
    """
    if orient not in ORIENTS:
        raise ValueError(f"orient must be one of {ORIENTS}, got {orient!r}")
    deg = (g.degrees() if orient == "degree"
           else np.zeros(g.n, np.int64))
    h = splitmix32_np(np.arange(g.n, dtype=np.uint32)).astype(np.int64)
    u, v = g.src, g.dst
    ku = np.stack([deg[u], h[u], u], 1)
    kv = np.stack([deg[v], h[v], v], 1)
    u_first = (
        (ku[:, 0] < kv[:, 0])
        | ((ku[:, 0] == kv[:, 0]) & (ku[:, 1] < kv[:, 1]))
        | ((ku[:, 0] == kv[:, 0]) & (ku[:, 1] == kv[:, 1]) & (ku[:, 2] < kv[:, 2]))
    )
    p = np.where(u_first, u, v)
    q = np.where(u_first, v, u)
    return p, q, deg, h


def sparsify_edges(g: HostGraph, p: float, seed: int = 0) -> HostGraph:
    """DOULION sparsification (Tsourakakis et al.): keep each undirected edge
    i.i.d. with probability ``p``. A triangle survives with probability p³,
    so count-type survey results debias by 1/p³
    (:meth:`Survey.scale_sampled`). Deterministic in ``seed`` so ingestion
    (:func:`shard_dodgr`) and planning (``pushpull.plan_engine``) sparsify
    identically and the static plan matches the sampled graph exactly.

    The returned graph is *stamped* with ``(sample_p, sample_seed)``; a
    stamped graph passes through untouched (no second O(m) RNG draw + copy
    when the same view feeds both ingestion and planning), and a stamp
    that disagrees with the requested ``(p, seed)`` raises — the runtime
    provenance cross-check stays intact end to end."""
    if not 0.0 < p <= 1.0:
        raise ValueError(f"sample_p must be in (0, 1], got {p}")
    if g.sample_p != 1.0:
        if p != 1.0 and (g.sample_p, g.sample_seed) != (p, seed):
            raise ValueError(
                f"graph already sparsified with (p, seed)="
                f"({g.sample_p}, {g.sample_seed}); cannot re-sparsify with "
                f"({p}, {seed})")
        return g
    if p >= 1.0:
        return g
    rng = np.random.default_rng(seed)
    keep = rng.random(g.m) < p
    return HostGraph(g.n, g.src[keep], g.dst[keep], g.spec,
                     g.vmeta_i, g.vmeta_f, g.emeta_i[keep], g.emeta_f[keep],
                     sample_p=p, sample_seed=seed)


def delta_gen_mask(q_s: np.ndarray, row_start: np.ndarray, row_len: np.ndarray,
                   new_s: np.ndarray, touched: np.ndarray) -> np.ndarray:
    """Per-edge wedge-generator mask for a delta frontier, in shard-sorted
    edge order. Edge (p→q) at position i may open a wedge of a triangle with
    ≥1 delta edge iff

    * the edge itself is new (new-old-old / new-new-* classes via pq), or
    * a *later* edge in p's row is new (the wedge partner pr is new), or
    * ``q`` is a delta endpoint AND some later edge in the row targets a
      delta endpoint (the closing edge qr may be new — the old-old-new
      class needs *both* endpoints of qr in V(D); the owner-side newness
      check settles it).

    Shared by ``shard_dodgr`` (device mask) and ``pushpull.plan_engine``
    (volume accounting + superstep counts) so the two agree exactly.
    """
    if len(q_s) == 0:
        return np.zeros(0, bool)
    idx = np.arange(len(q_s))
    row_end = np.repeat(row_start + row_len, row_len)
    cum = np.cumsum(new_s.astype(np.int64))
    suffix_new = (cum[row_end - 1] - cum[idx]) > 0
    t_q = touched[q_s]
    cum_t = np.cumsum(t_q.astype(np.int64))
    suffix_touched = (cum_t[row_end - 1] - cum_t[idx]) > 0
    return new_s | suffix_new | (t_q & suffix_touched)


class HubTableCache:
    """Replicate-once / refresh-on-touch hub tables across delta epochs.

    The historic :func:`shard_delta` path rebuilds every ``hub_*`` array
    from the epoch's frontier on every batch — O(frontier) gather + sort
    work per epoch even when the batch never goes near most hubs. This
    cache instead maintains the **oriented union adjacency** host-side
    (seeded once from the base graph, then advanced by each epoch's compact
    overlay in O(batch) inserts) and serves hub rows straight out of it:

    * an **untouched** hub's row is copied verbatim from the cache —
      bitwise-stable across epochs because the epoch-stable orientation key
      ``(0, hash(v), v)`` never moves and metadata is immutable;
    * a **touched** hub's row already holds the freshly inserted overlay
      edges; only its per-entry newness flags are recomputed against the
      current epoch's delta keys.

    Served rows are the hub's full *union* ``Adj₊`` — a superset of the
    frontier row the inline build would produce. That is exact for the
    delta engine: any extra table hit closes a triangle whose three edges
    are all old (a new ``pq``/``pr`` forces ``q``/``r`` into the touched
    set, putting ``qr`` in the frontier row too), and the hub fold's
    ``≥ 1 new edge`` mask discards exactly those, so survey results are
    bitwise-identical to a per-epoch rebuild (tests/test_hub_reuse.py).
    Requires ``orient="stable"`` — under the degree key a vertex's row
    order (and the hub set itself) legally moves between epochs.
    """

    def __init__(self, base: HostGraph, orient: str = "stable"):
        if orient != "stable":
            raise ValueError(
                "HubTableCache requires orient='stable': union rows are "
                "only epoch-stable under the (0, hash, id) key — the "
                f"degree key reorders rows as batches arrive (got "
                f"{orient!r})")
        self.orient = orient
        self.at_epoch = 0   # chain cursor: last overlay folded in
        self.rows_reused = 0      # cumulative: rows served verbatim
        self.rows_refreshed = 0   # cumulative: rows with newness recomputed
        self.last_build: dict = {}
        self._rows: dict[int, dict] = {}   # pivot -> sorted union Adj₊ row
        self._vmeta_i = np.asarray(base.vmeta_i)
        self._vmeta_f = np.asarray(base.vmeta_f)
        self._dei, self._def = base.spec.dei, base.spec.def_
        self._new_keys = np.zeros(0, np.int64)   # this epoch's delta edges
        self._touched_pivots: set = set()
        self._ingest(base.src, base.dst, base.emeta_i, base.emeta_f)

    @staticmethod
    def _orient_stable(src, dst):
        """Per-edge stable orientation — identical to
        :func:`orient_edges` with the zero degree component."""
        src = np.asarray(src, np.int64)
        dst = np.asarray(dst, np.int64)
        h_u = splitmix32_np(src.astype(np.uint32)).astype(np.int64)
        h_v = splitmix32_np(dst.astype(np.uint32)).astype(np.int64)
        u_first = (h_u < h_v) | ((h_u == h_v) & (src < dst))
        p = np.where(u_first, src, dst)
        q = np.where(u_first, dst, src)
        hq = np.where(u_first, h_v, h_u)
        return p, q, hq

    def _ingest(self, src, dst, emeta_i, emeta_f) -> np.ndarray:
        """Insert oriented edges into their pivot rows, keeping each row
        sorted by the (hash, id) key — the shard layer's within-row order.
        Returns the distinct pivot ids whose rows changed."""
        if len(src) == 0:
            return np.zeros(0, np.int64)
        p, q, hq = self._orient_stable(src, dst)
        emeta_i = np.asarray(emeta_i, np.int32).reshape(len(p), self._dei)
        emeta_f = np.asarray(emeta_f, np.float32).reshape(len(p), self._def)
        order = np.lexsort((q, hq, p))
        p, q, hq = p[order], q[order], hq[order]
        emeta_i, emeta_f = emeta_i[order], emeta_f[order]
        piv, starts = np.unique(p, return_index=True)
        bounds = np.append(starts, len(p))
        for i, v in enumerate(piv):
            lo, hi = bounds[i], bounds[i + 1]
            add = dict(nbr=q[lo:hi], h=hq[lo:hi].astype(np.uint32),
                       eqr_i=emeta_i[lo:hi], eqr_f=emeta_f[lo:hi])
            row = self._rows.get(int(v))
            if row is None:
                self._rows[int(v)] = add
                continue
            nbr = np.concatenate([row["nbr"], add["nbr"]])
            h = np.concatenate([row["h"], add["h"]])
            srt = np.lexsort((nbr, h.astype(np.int64)))
            self._rows[int(v)] = dict(
                nbr=nbr[srt], h=h[srt],
                eqr_i=np.concatenate([row["eqr_i"], add["eqr_i"]])[srt],
                eqr_f=np.concatenate([row["eqr_f"], add["eqr_f"]])[srt])
        return piv

    def advance(self, dg: DeltaGraph) -> None:
        """Fold one epoch's overlay into the union rows. Idempotent at the
        current epoch; epochs must arrive in order (no gaps) — the cache is
        a chain over the exact batch history, like the delta engine's
        accumulator."""
        if dg.epoch == self.at_epoch:
            return
        if dg.epoch != self.at_epoch + 1:
            raise ValueError(
                f"HubTableCache is at epoch {self.at_epoch} but the delta "
                f"graph is at epoch {dg.epoch}; advance() must see every "
                "epoch in order")
        piv = self._ingest(dg.d_src, dg.d_dst, dg.d_emeta_i, dg.d_emeta_f)
        p, q, _ = self._orient_stable(dg.d_src, dg.d_dst)
        self._new_keys = (p << np.int64(32)) | q
        self._touched_pivots = set(int(v) for v in piv)
        # base vmeta may have grown with the vertex set; existing rows are
        # immutable (append_edges only extends), so gathers stay bitwise
        self._vmeta_i = np.asarray(dg.base.vmeta_i)
        self._vmeta_f = np.asarray(dg.base.vmeta_f)
        self.at_epoch = dg.epoch

    def build(self, hub_ids: np.ndarray) -> dict:
        """Assemble the replicated ``hub_*`` arrays for this epoch's hub set
        from the cached union rows — the ``hub_tables`` argument of
        :func:`shard_dodgr`. Untouched rows are served verbatim
        (``rows_reused``); touched rows get their newness flags recomputed
        against the epoch's delta keys (``rows_refreshed``)."""
        hub_ids = np.asarray(hub_ids, np.int64)
        n_hubs = len(hub_ids)
        hc = max(1, n_hubs)
        rows = [self._rows.get(int(v)) for v in hub_ids]
        lens = [0 if r is None else len(r["nbr"]) for r in rows]
        hub_len = max(1, max(lens, default=1))
        dvi, dvf = self._vmeta_i.shape[1], self._vmeta_f.shape[1]
        t = dict(
            hub_row_len=np.zeros(hc, np.int32),
            hub_nbr=np.full((hc, hub_len), PAD_ID, np.int32),
            hub_nbr_d=np.full((hc, hub_len), PAD_D, np.int32),
            hub_nbr_h=np.zeros((hc, hub_len), np.uint32),
            hub_nbr_new=np.zeros((hc, hub_len), bool),
            hub_eqr_i=np.zeros((hc, hub_len, self._dei), np.int32),
            hub_eqr_f=np.zeros((hc, hub_len, self._def), np.float32),
            hub_tmeta_i=np.zeros((hc, hub_len, dvi), np.int32),
            hub_tmeta_f=np.zeros((hc, hub_len, dvf), np.float32),
            hub_vmeta_i=np.zeros((hc, dvi), np.int32),
            hub_vmeta_f=np.zeros((hc, dvf), np.float32),
        )
        reused = refreshed = 0
        for i, (v, row) in enumerate(zip(hub_ids, rows)):
            if row is None:
                reused += 1
                continue
            k = lens[i]
            t["hub_row_len"][i] = k
            t["hub_nbr"][i, :k] = row["nbr"]
            t["hub_nbr_d"][i, :k] = 0   # stable key: degree component is 0
            t["hub_nbr_h"][i, :k] = row["h"]
            t["hub_eqr_i"][i, :k] = row["eqr_i"]
            t["hub_eqr_f"][i, :k] = row["eqr_f"]
            t["hub_tmeta_i"][i, :k] = self._vmeta_i[row["nbr"]]
            t["hub_tmeta_f"][i, :k] = self._vmeta_f[row["nbr"]]
            if int(v) in self._touched_pivots:
                key = (np.int64(v) << np.int64(32)) | row["nbr"]
                t["hub_nbr_new"][i, :k] = np.isin(key, self._new_keys)
                refreshed += 1
            else:
                reused += 1
        if n_hubs:
            t["hub_vmeta_i"][:n_hubs] = self._vmeta_i[hub_ids]
            t["hub_vmeta_f"][:n_hubs] = self._vmeta_f[hub_ids]
        self.rows_reused += reused
        self.rows_refreshed += refreshed
        self.last_build = dict(epoch=self.at_epoch, n_hubs=n_hubs,
                               rows_reused=reused, rows_refreshed=refreshed)
        t.update(hub_ids=hub_ids, hub_len=hub_len, hub_rows="union")
        return t

    def nbytes(self) -> int:
        """Host-resident bytes of the cached union rows."""
        return sum(int(a.nbytes) for row in self._rows.values()
                   for a in row.values())


def shard_dodgr(g: HostGraph, S: int, e_cap: int | None = None,
                sample_p: float = 1.0, sample_seed: int = 0,
                edge_new: np.ndarray | None = None, orient: str = "degree",
                epoch: int = 0,
                hub_theta: int = 0,
                hub_tables: dict | None = None,
                cap_policy: str = "exact",
                e_cap_floor: int = 0,
                d_plus_max_floor: int = 0
                ) -> tuple[ShardedDODGr, RoutingStats]:
    """Host-side ingestion: orient, partition cyclically, build padded CSR shards.

    ``sample_p < 1`` ingests a DOULION-sparsified view of ``g`` (see
    :func:`sparsify_edges`); pass the same (p, seed) to ``plan_engine`` —
    or sparsify once up front and pass the stamped graph to both, which
    skips the second O(m) sampling pass. The shard provenance always
    reflects the graph's effective stamp.

    ``edge_new`` ([m] bool, aligned with ``g``'s edge list) ingests ``g`` as
    a *delta frontier*: per-edge newness flags and wedge-generator masks are
    sharded alongside the adjacency and the result is stamped
    ``is_delta=True`` at ``epoch`` — consumed by ``engine.survey_delta``
    under a matching ``pushpull.plan_delta`` plan. Prefer the
    :func:`shard_delta` wrapper, which derives frontier + flags from a
    :class:`~repro.graphs.csr.DeltaGraph`.

    ``hub_theta ≥ 1`` enables hub delegation: the ``Adj₊`` row (plus its
    edge/target metadata) of every vertex whose *full degree in this view*
    is ≥ θ is replicated to all shards, and each edge slot records its
    target's hub-table row in ``nbr_hub`` so the engine can close hub-bound
    wedges locally. θ normally comes from the planner
    (``pushpull.plan_engine(..., hub_theta='auto')``) — pass the same value
    here; provenance is cross-checked at run time.

    ``hub_tables`` (a :meth:`HubTableCache.build` result) substitutes
    cache-served union rows for the inline per-view rebuild — the
    hub-table-reuse path of :func:`shard_delta`. The hub *set* is still
    derived from this view's degrees and must match the prebuilt ids
    exactly; the result is stamped ``hub_rows="union"``.

    ``cap_policy="bucket"`` rounds the derived array shapes — ``e_cap``,
    ``d_plus_max``, and the inline hub-table ``hub_len`` — up to the
    geometric bucket grid (:func:`repro.utils.bucket_cap`), matching the
    planner's ``plan_engine(..., cap_policy="bucket")`` so drifting delta
    epochs produce byte-compatible jit signatures. Extra slots are
    ordinary row padding (``row_ptr`` bounds and pad sentinels already
    mask them), so results are bitwise-identical to ``"exact"``.

    ``e_cap_floor``/``d_plus_max_floor`` raise the derived values to a
    caller-supplied minimum — the serving layer's session hysteresis: a
    delta epoch whose frontier shrank below the session high-water mark
    keeps the larger shapes (pure padding, still bitwise-identical)
    instead of recompiling for the smaller ones.
    """
    with span("shard_dodgr"):
        if cap_policy not in ("exact", "bucket"):
            raise ValueError(f"cap_policy must be 'exact' or 'bucket', "
                             f"got {cap_policy!r}")
        with span("shard_dodgr.orientation"):
            g = sparsify_edges(g, sample_p, sample_seed)
            sample_p, sample_seed = g.sample_p, g.sample_seed
            p, q, deg, h = orient_edges(g, orient)
            d_plus = np.bincount(p, minlength=g.n).astype(np.int64)

            owner = (p % S).astype(np.int64)
            local = (p // S).astype(np.int64)
            n_loc = ceil_div(g.n, S)

            # sort edges by (owner, local row, key(q)) so shard rows are
            # contiguous+sorted
            order = np.lexsort((q, h[q], deg[q], local, owner))
            p_s, q_s = p[order], q[order]
            owner_s, local_s = owner[order], local[order]

            counts = np.bincount(owner_s, minlength=S)
            e_cap_needed = int(counts.max()) if len(counts) else 0
            if e_cap is None:
                e_cap = max(8, int(np.ceil(e_cap_needed / 8.0) * 8))
                if cap_policy == "bucket":
                    e_cap = bucket_cap(e_cap)
                e_cap = max(e_cap, int(e_cap_floor))
            if e_cap < e_cap_needed:
                raise ValueError(f"e_cap {e_cap} < required {e_cap_needed}")

            start = np.zeros(S + 1, np.int64)
            start[1:] = np.cumsum(counts)

        with span("shard_dodgr.rows"):
            def alloc(shape, dtype, fill=0):
                a = np.full(shape, fill, dtype)
                return a

            nbr = alloc((S, e_cap), np.int32, PAD_ID)
            nbr_d = alloc((S, e_cap), np.int32, PAD_D)
            nbr_h = alloc((S, e_cap), np.uint32)
            nbr_dp = alloc((S, e_cap), np.int32)
            edge_src = alloc((S, e_cap), np.int32, PAD_ID)
            dei, def_, dvi, dvf = (g.spec.dei, g.spec.def_, g.spec.dvi, g.spec.dvf)
            emeta_i = alloc((S, e_cap, dei), np.int32)
            emeta_f = alloc((S, e_cap, def_), np.float32)
            tmeta_i = alloc((S, e_cap, dvi), np.int32)
            tmeta_f = alloc((S, e_cap, dvf), np.float32)
            row_ptr = alloc((S, n_loc + 1), np.int32)
            vmeta_i = alloc((S, n_loc, dvi), np.int32)
            vmeta_f = alloc((S, n_loc, dvf), np.float32)
            vdeg = alloc((S, n_loc), np.int32)
            dplus_arr = alloc((S, n_loc), np.int32)
            nbr_new = alloc((S, e_cap), bool, False)
            # all-true for a static snapshot: the engine only consults the mask in
            # delta mode, where it restricts wedge generation to the three
            # new-triangle classes
            delta_gen = alloc((S, e_cap), bool, edge_new is None)

            emeta_i_src = g.emeta_i[order]
            emeta_f_src = g.emeta_f[order]

            # position within row: edges are sorted by (owner, local, key); compute
            # per-edge suffix length = (row_end - pos - 1)
            row_key = owner_s * n_loc + local_s
            _, row_start_idx, row_len = np.unique(
                row_key, return_index=True, return_counts=True)
            pos_in_row = np.arange(len(p_s)) - np.repeat(row_start_idx, row_len)
            suffix = np.repeat(row_len, row_len) - pos_in_row - 1

            if edge_new is not None:
                new_s = np.asarray(edge_new, bool)[order]
                touched = np.zeros(g.n, bool)
                touched[g.src[edge_new]] = True
                touched[g.dst[edge_new]] = True
                gen_s = delta_gen_mask(q_s, row_start_idx, row_len, new_s, touched)
            else:
                new_s = gen_s = None

        with span("shard_dodgr.hub_table"):
            # --- hub table: replicate Adj₊ rows of heavy vertices (deg ≥ θ) ---
            if hub_theta < 0:
                raise ValueError(f"hub_theta must be ≥ 0, got {hub_theta}")
            n_hubs = 0
            hub_ids = np.zeros(0, np.int64)
            if hub_theta >= 1:
                tdeg = deg if orient == "degree" else g.degrees()
                hub_ids = np.nonzero(tdeg >= hub_theta)[0]
                n_hubs = len(hub_ids)
            hc = max(1, n_hubs)
            hub_rows = "frontier"
            hub_len = 1
            hub_of_q = None
            if n_hubs:
                hub_id_of = np.full(g.n, -1, np.int32)
                hub_id_of[hub_ids] = np.arange(n_hubs, dtype=np.int32)
                hub_of_q = hub_id_of[q_s]
            if hub_tables is not None and hub_theta >= 1:
                # cache-served union rows (HubTableCache.build): the hub SET must
                # still be this view's — the planner removed exactly these wedges
                # from the wire lanes, and nbr_hub below marks exactly these edges
                if not np.array_equal(np.asarray(hub_tables["hub_ids"], np.int64),
                                      hub_ids.astype(np.int64)):
                    raise ValueError(
                        "hub_tables was built for a different hub set than "
                        f"deg ≥ {hub_theta} selects in this view; build it from "
                        "this epoch's frontier degrees")
                hub_rows = str(hub_tables["hub_rows"])
                hub_len = int(hub_tables["hub_len"])
                hub_row_len = np.asarray(hub_tables["hub_row_len"], np.int32)
                hub_nbr = np.asarray(hub_tables["hub_nbr"], np.int32)
                hub_nbr_d = np.asarray(hub_tables["hub_nbr_d"], np.int32)
                hub_nbr_h = np.asarray(hub_tables["hub_nbr_h"], np.uint32)
                hub_nbr_new = np.asarray(hub_tables["hub_nbr_new"], bool)
                hub_eqr_i = np.asarray(hub_tables["hub_eqr_i"], np.int32)
                hub_eqr_f = np.asarray(hub_tables["hub_eqr_f"], np.float32)
                hub_tmeta_i = np.asarray(hub_tables["hub_tmeta_i"], np.int32)
                hub_tmeta_f = np.asarray(hub_tables["hub_tmeta_f"], np.float32)
                hub_vmeta_i = np.asarray(hub_tables["hub_vmeta_i"], np.int32)
                hub_vmeta_f = np.asarray(hub_tables["hub_vmeta_f"], np.float32)
            else:
                hub_row_len = np.zeros(hc, np.int32)
                if n_hubs:
                    hub_row_len[:n_hubs] = d_plus[hub_ids]
                    hub_len = max(1, int(d_plus[hub_ids].max()))
                    if cap_policy == "bucket":
                        hub_len = bucket_cap(hub_len)
                hub_nbr = alloc((hc, hub_len), np.int32, PAD_ID)
                hub_nbr_d = alloc((hc, hub_len), np.int32, PAD_D)
                hub_nbr_h = alloc((hc, hub_len), np.uint32)
                hub_nbr_new = alloc((hc, hub_len), bool, False)
                hub_eqr_i = alloc((hc, hub_len, dei), np.int32)
                hub_eqr_f = alloc((hc, hub_len, def_), np.float32)
                hub_tmeta_i = alloc((hc, hub_len, dvi), np.int32)
                hub_tmeta_f = alloc((hc, hub_len, dvf), np.float32)
                hub_vmeta_i = alloc((hc, dvi), np.int32)
                hub_vmeta_f = alloc((hc, dvf), np.float32)
                if n_hubs:
                    # rows of hub pivots are contiguous runs of the sorted edge
                    # list, so the replicated table is a verbatim copy of the owner
                    # shards' rows
                    he = np.nonzero(hub_id_of[p_s] >= 0)[0]
                    hid = hub_id_of[p_s[he]]
                    hpos = pos_in_row[he]
                    hub_nbr[hid, hpos] = q_s[he]
                    hub_nbr_d[hid, hpos] = deg[q_s[he]]
                    hub_nbr_h[hid, hpos] = h[q_s[he]].astype(np.uint32)
                    hub_eqr_i[hid, hpos] = emeta_i_src[he]
                    hub_eqr_f[hid, hpos] = emeta_f_src[he]
                    hub_tmeta_i[hid, hpos] = g.vmeta_i[q_s[he]]
                    hub_tmeta_f[hid, hpos] = g.vmeta_f[q_s[he]]
                    hub_vmeta_i[:n_hubs] = g.vmeta_i[hub_ids]
                    hub_vmeta_f[:n_hubs] = g.vmeta_f[hub_ids]
                    if new_s is not None:
                        hub_nbr_new[hid, hpos] = new_s[he]
            nbr_hub = alloc((S, e_cap), np.int32, -1)

        with span("shard_dodgr.shards"):
            for s in range(S):
                lo, hi = start[s], start[s + 1]
                k = hi - lo
                nbr[s, :k] = q_s[lo:hi]
                nbr_d[s, :k] = deg[q_s[lo:hi]]
                nbr_h[s, :k] = h[q_s[lo:hi]].astype(np.uint32)
                nbr_dp[s, :k] = d_plus[q_s[lo:hi]]
                edge_src[s, :k] = p_s[lo:hi]
                emeta_i[s, :k] = emeta_i_src[lo:hi]
                emeta_f[s, :k] = emeta_f_src[lo:hi]
                tmeta_i[s, :k] = g.vmeta_i[q_s[lo:hi]]
                tmeta_f[s, :k] = g.vmeta_f[q_s[lo:hi]]
                if new_s is not None:
                    nbr_new[s, :k] = new_s[lo:hi]
                    delta_gen[s, :k] = gen_s[lo:hi]
                    delta_gen[s, k:] = False
                if hub_of_q is not None:
                    nbr_hub[s, :k] = hub_of_q[lo:hi]
                rows = np.bincount(local_s[lo:hi], minlength=n_loc)
                row_ptr[s, 1:] = np.cumsum(rows)
                ids = np.arange(s, g.n, S, dtype=np.int64)
                nv = len(ids)
                vmeta_i[s, :nv] = g.vmeta_i[ids]
                vmeta_f[s, :nv] = g.vmeta_f[ids]
                vdeg[s, :nv] = deg[ids]
                dplus_arr[s, :nv] = d_plus[ids]

        with span("shard_dodgr.routing_stats"):
            # --- routing stats for static superstep planning ---
            dest = (q_s % S).astype(np.int64)
            sd = owner_s * S + dest
            stream = np.bincount(sd, weights=suffix, minlength=S * S).astype(np.int64)
            pairs = np.bincount(sd, minlength=S * S)
            stats = RoutingStats(
                wedges_total=int(suffix.sum()),
                max_stream=int(stream.max()) if len(stream) else 0,
                max_pairs=int(pairs.max()) if len(pairs) else 0,
                edges_per_shard=counts,
                wedge_per_shard=np.bincount(
                    owner_s, weights=suffix, minlength=S).astype(np.int64),
            )

        with span("shard_dodgr.device_arrays"):
            d_plus_max = max(1, int(d_plus.max()) if g.n else 0)
            if cap_policy == "bucket":
                # d_plus_max is a static meta field (part of every jit signature)
                # AND the fallback reply-row window when a plan leaves
                # pull_row_cap=0 — every consumer masks by the true row length,
                # so rounding it up is pure padding
                d_plus_max = bucket_cap(d_plus_max)
            d_plus_max = max(d_plus_max, int(d_plus_max_floor))
            gr = ShardedDODGr(
                S=S, n_global=g.n, n_loc=n_loc, e_cap=e_cap,
                d_plus_max=d_plus_max,
                sample_p=sample_p, sample_seed=sample_seed,
                orient=orient, epoch=epoch, is_delta=edge_new is not None,
                hub_theta=hub_theta, n_hubs=n_hubs, hub_len=hub_len,
                hub_rows=hub_rows,
                row_ptr=jnp.asarray(row_ptr), edge_src=jnp.asarray(edge_src),
                nbr=jnp.asarray(nbr), nbr_d=jnp.asarray(nbr_d),
                nbr_h=jnp.asarray(nbr_h), nbr_dplus=jnp.asarray(nbr_dp),
                emeta_i=jnp.asarray(emeta_i), emeta_f=jnp.asarray(emeta_f),
                tmeta_i=jnp.asarray(tmeta_i), tmeta_f=jnp.asarray(tmeta_f),
                vmeta_i=jnp.asarray(vmeta_i), vmeta_f=jnp.asarray(vmeta_f),
                vdeg=jnp.asarray(vdeg), dplus=jnp.asarray(dplus_arr),
                nbr_new=jnp.asarray(nbr_new), delta_gen=jnp.asarray(delta_gen),
                nbr_hub=jnp.asarray(nbr_hub),
                hub_row_len=jnp.asarray(hub_row_len),
                hub_nbr=jnp.asarray(hub_nbr), hub_nbr_d=jnp.asarray(hub_nbr_d),
                hub_nbr_h=jnp.asarray(hub_nbr_h),
                hub_nbr_new=jnp.asarray(hub_nbr_new),
                hub_eqr_i=jnp.asarray(hub_eqr_i), hub_eqr_f=jnp.asarray(hub_eqr_f),
                hub_tmeta_i=jnp.asarray(hub_tmeta_i),
                hub_tmeta_f=jnp.asarray(hub_tmeta_f),
                hub_vmeta_i=jnp.asarray(hub_vmeta_i),
                hub_vmeta_f=jnp.asarray(hub_vmeta_f),
            )
            return gr, stats


def shard_delta(dg: DeltaGraph, S: int, e_cap: int | None = None,
                orient: str = "stable",
                hub_theta: int = 0,
                hub_cache: HubTableCache | None = None,
                cap_policy: str = "exact",
                e_cap_floor: int = 0,
                d_plus_max_floor: int = 0
                ) -> tuple[ShardedDODGr, RoutingStats]:
    """Shard the epoch's delta frontier with the same cyclic owner map as the
    full snapshot (owner ``v % S`` is id-based, so frontier shards align with
    union shards) and stamp epoch provenance.

    Default orientation is ``"stable"`` — the epoch-stable key every epoch
    of a delta sequence must share for ``merge_epochs`` to be bitwise-exact
    against a full recompute (see :func:`orient_edges`).

    ``hub_theta`` replicates heavy *frontier* rows (degree measured in the
    frontier subgraph — a hub the batch touches keeps its full row there),
    the lever against the hub-touching frontier blow-up; pass the θ from
    ``pushpull.plan_delta(..., hub_theta='auto')`` for this epoch.

    ``hub_cache`` (a :class:`HubTableCache` seeded from the stream's base)
    replaces the per-epoch ``hub_*`` rebuild with cache-served union rows:
    the cache is advanced to this epoch's overlay (O(batch) inserts), only
    rows the batch touched get their newness flags refreshed, and survey
    results stay bitwise-identical to the rebuild path (the ≥ 1-new-edge
    fold mask discards the union rows' extra all-old entries — see
    :class:`HubTableCache`). Requires ``orient="stable"``.
    """
    h, edge_new = dg.frontier()
    hub_tables = None
    if hub_cache is not None and hub_theta >= 1:
        if orient != "stable":
            raise ValueError(
                "shard_delta(hub_cache=...) requires orient='stable' — "
                "union hub rows are only epoch-stable under the "
                f"(0, hash, id) key (got {orient!r})")
        hub_cache.advance(dg)
        hub_tables = hub_cache.build(
            np.nonzero(h.degrees() >= hub_theta)[0])
    return shard_dodgr(h, S, e_cap=e_cap, edge_new=edge_new, orient=orient,
                       epoch=dg.epoch, hub_theta=hub_theta,
                       hub_tables=hub_tables, cap_policy=cap_policy,
                       e_cap_floor=e_cap_floor,
                       d_plus_max_floor=d_plus_max_floor)


def dodgr_spec(S: int, n_global: int, n_loc: int, e_cap: int, d_plus_max: int,
               dvi: int, dvf: int, dei: int, def_: int,
               hub_theta: int = 0, n_hubs: int = 0,
               hub_len: int = 1) -> ShardedDODGr:
    """ShapeDtypeStruct stand-in for dry-run lowering (no allocation)."""
    sd = jax.ShapeDtypeStruct
    hc = max(1, n_hubs)
    return ShardedDODGr(
        S=S, n_global=n_global, n_loc=n_loc, e_cap=e_cap, d_plus_max=d_plus_max,
        hub_theta=hub_theta, n_hubs=n_hubs, hub_len=hub_len,
        row_ptr=sd((S, n_loc + 1), jnp.int32),
        edge_src=sd((S, e_cap), jnp.int32),
        nbr=sd((S, e_cap), jnp.int32),
        nbr_d=sd((S, e_cap), jnp.int32),
        nbr_h=sd((S, e_cap), jnp.uint32),
        nbr_dplus=sd((S, e_cap), jnp.int32),
        emeta_i=sd((S, e_cap, dei), jnp.int32),
        emeta_f=sd((S, e_cap, def_), jnp.float32),
        tmeta_i=sd((S, e_cap, dvi), jnp.int32),
        tmeta_f=sd((S, e_cap, dvf), jnp.float32),
        vmeta_i=sd((S, n_loc, dvi), jnp.int32),
        vmeta_f=sd((S, n_loc, dvf), jnp.float32),
        vdeg=sd((S, n_loc), jnp.int32),
        dplus=sd((S, n_loc), jnp.int32),
        nbr_new=sd((S, e_cap), jnp.bool_),
        delta_gen=sd((S, e_cap), jnp.bool_),
        nbr_hub=sd((S, e_cap), jnp.int32),
        hub_row_len=sd((hc,), jnp.int32),
        hub_nbr=sd((hc, hub_len), jnp.int32),
        hub_nbr_d=sd((hc, hub_len), jnp.int32),
        hub_nbr_h=sd((hc, hub_len), jnp.uint32),
        hub_nbr_new=sd((hc, hub_len), jnp.bool_),
        hub_eqr_i=sd((hc, hub_len, dei), jnp.int32),
        hub_eqr_f=sd((hc, hub_len, def_), jnp.float32),
        hub_tmeta_i=sd((hc, hub_len, dvi), jnp.int32),
        hub_tmeta_f=sd((hc, hub_len, dvf), jnp.float32),
        hub_vmeta_i=sd((hc, dvi), jnp.int32),
        hub_vmeta_f=sd((hc, dvf), jnp.float32),
    )
