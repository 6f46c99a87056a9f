"""Survey callbacks as monoid aggregators (paper Sec. 4.5, Algs 2–4).

A :class:`Survey` is the TPU-native form of the paper's user callback:
``init`` builds per-shard state, ``update`` folds a masked batch of
discovered triangles, ``merge`` combines per-shard states (the paper's
"combine in an All-Reduce-type operation"), ``finalize`` renders results
host-side. Every callback in the paper is commutative-associative
aggregation, so this API loses no generality (DESIGN.md §2).

Lane-projection contract: each survey declares a :class:`MetaSpec` naming
the metadata lanes it actually reads from the six items of Δ_pqr (vp, vq,
vr, e_pq, e_pr, e_qr; int and float lanes separately). The engine gathers
and exchanges *only* the declared lanes and hands ``update`` a projected
:class:`TriangleBatch`: items the survey never reads arrive zero-width
(shape ``[B, 0]``), partially-read items are narrowed to
``max(declared lane) + 1`` with undeclared lanes zero-filled so declared
lanes keep their storage indices. ``update`` must therefore only index
lanes its spec declares — under that contract the fold code is unchanged
and its results are bitwise-identical to a full-metadata batch. The
default ``Survey.meta_spec`` is :meth:`MetaSpec.full` (every lane of
every item), so surveys that do not declare anything keep the old
all-metadata behavior. :class:`SurveyBundle` reads the union of its
members' specs.
"""
from __future__ import annotations

from dataclasses import dataclass, fields

import jax
import numpy as np
import jax.numpy as jnp

from repro import kernels
from repro.core.counting_set import CountingSet

# ---------------------------------------------------------------------------
# MetaSpec — survey-declared metadata lanes (communication narrowing)


_V_ITEMS = ("vp", "vq", "vr")
_E_ITEMS = ("e_pq", "e_pr", "e_qr")


@dataclass(frozen=True)
class MetaSpec:
    """Which metadata lanes a survey reads from each of the six items.

    Not to be confused with :class:`repro.graphs.csr.MetaSpec`, the *graph
    schema* naming the storage columns — this spec declares which of those
    columns (by lane index) a survey's ``update`` actually touches, per
    triangle item. Each field is a tuple of lane indices into the storage
    columns (``v_int``/``v_float`` for the vertex items ``vp/vq/vr``,
    ``e_int``/``e_float`` for the edge items ``e_pq/e_pr/e_qr``), or
    ``None`` meaning *all* lanes of that column — resolved against the
    concrete graph widths at plan/compile time. The default is *nothing*.
    """

    vp_i: tuple | None = ()
    vp_f: tuple | None = ()
    vq_i: tuple | None = ()
    vq_f: tuple | None = ()
    vr_i: tuple | None = ()
    vr_f: tuple | None = ()
    e_pq_i: tuple | None = ()
    e_pq_f: tuple | None = ()
    e_pr_i: tuple | None = ()
    e_pr_f: tuple | None = ()
    e_qr_i: tuple | None = ()
    e_qr_f: tuple | None = ()

    @classmethod
    def none(cls) -> "MetaSpec":
        """Reads no metadata at all (e.g. :class:`TriangleCount`)."""
        return cls()

    @classmethod
    def full(cls) -> "MetaSpec":
        """Reads every lane of every item (the conservative default)."""
        return cls(**{f.name: None for f in fields(cls)})

    @classmethod
    def vertices(cls, i=(), f=()) -> "MetaSpec":
        """Same int/float lanes on all three vertex items vp, vq, vr."""
        kw = {}
        for it in _V_ITEMS:
            kw[f"{it}_i"] = None if i is None else tuple(i)
            kw[f"{it}_f"] = None if f is None else tuple(f)
        return cls(**kw)

    @classmethod
    def edges(cls, i=(), f=()) -> "MetaSpec":
        """Same int/float lanes on all three edge items e_pq, e_pr, e_qr."""
        kw = {}
        for it in _E_ITEMS:
            kw[f"{it}_i"] = None if i is None else tuple(i)
            kw[f"{it}_f"] = None if f is None else tuple(f)
        return cls(**kw)

    def union(self, other: "MetaSpec") -> "MetaSpec":
        """Per-item lane union (``None`` = all lanes dominates)."""

        def u(a, b):
            if a is None or b is None:
                return None
            return tuple(sorted(set(a) | set(b)))

        return MetaSpec(**{f.name: u(getattr(self, f.name), getattr(other, f.name))
                           for f in fields(MetaSpec)})

    __or__ = union

    def resolve(self, dvi: int, dvf: int, dei: int, def_: int) -> "MetaSpec":
        """Concretize against a graph's storage widths: ``None`` becomes
        every lane; explicit lanes are deduplicated, sorted, and validated."""

        def r(lanes, width, name):
            if lanes is None:
                return tuple(range(width))
            lanes = tuple(sorted(set(int(l) for l in lanes)))
            if lanes and (lanes[0] < 0 or lanes[-1] >= width):
                raise ValueError(
                    f"MetaSpec.{name} declares lanes {lanes} but the graph "
                    f"stores only {width} lane(s) for that column")
            return lanes

        kw = {}
        for f in fields(MetaSpec):
            width = ((dvi if f.name.endswith("_i") else dvf)
                     if f.name.startswith("v")
                     else (dei if f.name.endswith("_i") else def_))
            kw[f.name] = r(getattr(self, f.name), width, f.name)
        return MetaSpec(**kw)

    def lane_counts(self) -> tuple[int, ...]:
        """Total (int + float) declared lanes per item, in the order
        :func:`repro.core.dodgr.meta_widths` expects:
        ``(n_vp, n_vq, n_vr, n_epq, n_epr, n_eqr)``. Resolved specs only."""
        out = []
        for it in _V_ITEMS + _E_ITEMS:
            li, lf = getattr(self, f"{it}_i"), getattr(self, f"{it}_f")
            if li is None or lf is None:
                raise ValueError("lane_counts() needs a resolved MetaSpec; "
                                 "call .resolve(dvi, dvf, dei, def_) first")
            out.append(len(li) + len(lf))
        return tuple(out)


def eff_width(lanes) -> int:
    """Fold-slot width of a projected item: 0 when unread, else the smallest
    width that keeps every declared lane at its storage index."""
    return 0 if not lanes else max(lanes) + 1


def project_lanes(x: jax.Array, lanes) -> jax.Array:
    """Gather declared lanes from a full-width column: [..., W] → [..., k].

    This is the wire form — only these lanes cross an exchange. An empty
    spec skips the gather entirely (zero-width slice, no data movement)."""
    if not lanes:
        return x[..., :0]
    if lanes == tuple(range(x.shape[-1])):
        return x
    return x[..., list(lanes)]


def expand_lanes(x: jax.Array, lanes) -> jax.Array:
    """Scatter wire lanes back to the fold form: [..., k] → [..., eff_width]
    with undeclared lanes zero-filled, so folds index storage lanes."""
    w = eff_width(lanes)
    if not lanes:
        return x[..., :0]
    if lanes == tuple(range(w)):
        return x
    out = jnp.zeros(x.shape[:-1] + (w,), x.dtype)
    return out.at[..., list(lanes)].set(x)


def narrow_lanes(x: jax.Array, lanes) -> jax.Array:
    """Project then re-expand in place — the owner-local (no-wire) form."""
    return expand_lanes(project_lanes(x, lanes), lanes)


# ---------------------------------------------------------------------------


def _sort3(a, b, c):
    """Exact 3-way sort via a min/max network — elementwise, no XLA sort.

    Survey folds run on every (padded) triangle slot each superstep, so a
    ``jnp.sort`` here is the fold hot path; the network is ~10× cheaper on
    CPU and bitwise-identical (pure min/max, no arithmetic)."""
    lo = jnp.minimum(jnp.minimum(a, b), c)
    hi = jnp.maximum(jnp.maximum(a, b), c)
    mid = jnp.maximum(jnp.minimum(a, b), jnp.minimum(jnp.maximum(a, b), c))
    return lo, mid, hi


@dataclass(frozen=True)
class TriangleBatch:
    """A masked batch of triangles Δ_pqr with their six metadata items.

    Lane-projected: each metadata field carries only the lanes of the
    running survey's :class:`MetaSpec` (unread items are zero-width
    ``[B, 0]``; partially-read items are ``[B, max(lane)+1]`` with declared
    lanes at their storage indices). A full-spec survey sees the classic
    full-width batch."""

    p: jax.Array          # [B] i32 global ids
    q: jax.Array
    r: jax.Array
    vp_i: jax.Array       # [B, ≤dvi] i32   meta(p)
    vq_i: jax.Array
    vr_i: jax.Array
    vp_f: jax.Array       # [B, ≤dvf] f32
    vq_f: jax.Array
    vr_f: jax.Array
    e_pq_i: jax.Array     # [B, ≤dei] i32   meta(p,q)
    e_pr_i: jax.Array
    e_qr_i: jax.Array
    e_pq_f: jax.Array     # [B, ≤def] f32
    e_pr_f: jax.Array
    e_qr_f: jax.Array
    valid: jax.Array      # [B] bool

    @classmethod
    def abstract(cls, spec: "MetaSpec", batch: int = 64) -> "TriangleBatch":
        """Abstract (shape/dtype only) batch at ``spec``'s projected widths.

        Every field is a :class:`jax.ShapeDtypeStruct`, so a survey's
        ``update`` can be traced (``jax.eval_shape`` / ``jax.make_jaxpr``)
        against exactly the batch the engine would hand it — with **zero
        device execution**. ``spec`` must be resolved
        (:meth:`MetaSpec.resolve`). This is the entry point of the static
        fold-contract analysis (:mod:`repro.analysis.contracts`)."""
        sds = jax.ShapeDtypeStruct

        def item(lanes, dtype):
            if lanes is None:
                raise ValueError("TriangleBatch.abstract() needs a resolved "
                                 "MetaSpec; call .resolve(dvi, dvf, dei, "
                                 "def_) first")
            return sds((batch, eff_width(lanes)), dtype)

        i32, f32 = jnp.int32, jnp.float32
        return cls(
            p=sds((batch,), i32), q=sds((batch,), i32), r=sds((batch,), i32),
            vp_i=item(spec.vp_i, i32), vq_i=item(spec.vq_i, i32),
            vr_i=item(spec.vr_i, i32),
            vp_f=item(spec.vp_f, f32), vq_f=item(spec.vq_f, f32),
            vr_f=item(spec.vr_f, f32),
            e_pq_i=item(spec.e_pq_i, i32), e_pr_i=item(spec.e_pr_i, i32),
            e_qr_i=item(spec.e_qr_i, i32),
            e_pq_f=item(spec.e_pq_f, f32), e_pr_f=item(spec.e_pr_f, f32),
            e_qr_f=item(spec.e_qr_f, f32),
            valid=sds((batch,), jnp.bool_),
        )


jax.tree_util.register_dataclass(
    TriangleBatch,
    data_fields=[
        "p", "q", "r", "vp_i", "vq_i", "vr_i", "vp_f", "vq_f", "vr_f",
        "e_pq_i", "e_pr_i", "e_qr_i", "e_pq_f", "e_pr_f", "e_qr_f", "valid",
    ],
    meta_fields=[],
)


class Survey:
    """Base survey. Subclasses override the four hooks and (optionally)
    declare ``meta_spec`` — the metadata lanes their ``update`` reads. The
    default is every lane (safe but pays full-width communication)."""

    meta_spec: MetaSpec = MetaSpec.full()

    def init(self):
        raise NotImplementedError

    def update(self, state, tri: TriangleBatch):
        raise NotImplementedError

    def merge(self, stacked):
        """Default cross-shard merge: elementwise sum over the shard axis."""
        return jax.tree.map(lambda x: x.sum(0), stacked)

    def finalize(self, merged):
        return jax.tree.map(np.asarray, merged)

    def merge_epochs(self, prev, delta):
        """Combine two *merged* states whose triangle sets are disjoint —
        the epoch-accumulation contract of the delta engine
        (:func:`repro.core.engine.survey_delta`). Because each triangle is
        folded in exactly one epoch (the one its last edge arrives in), the
        accumulated state must equal a single full-graph run bitwise; the
        default elementwise sum matches the cross-shard merge of every
        counter-style state."""
        return jax.tree.map(lambda a, b: a + b, prev, delta)

    def scale_sampled(self, result, p: float):
        """Debias a finalized result computed on a DOULION-sparsified graph
        (edges kept i.i.d. with probability ``p``). Count-like surveys scale
        by 1/p³ (each triangle survives w.p. p³); surveys whose output is not
        a count (e.g. enumeration) return it unchanged."""
        return result


# ---------------------------------------------------------------------------
# 64-bit counter from uint32 limbs (x64 stays disabled; global triangle
# counts overflow int32 at paper scale — 9.65T on WDC-2012).

def _scale_counting_set(result: dict, p: float) -> dict:
    """1/p³ debias for a finalized CountingSet readout (counts go float)."""
    return dict(
        counts={k: v / p**3 for k, v in result["counts"].items()},
        n_collided_slots=result["n_collided_slots"],
        count_in_collided=result["count_in_collided"] / p**3,
    )


def counter64_zero():
    return dict(lo=jnp.zeros((), jnp.uint32), hi=jnp.zeros((), jnp.uint32))


def counter64_add(c, amount_u32):
    lo = c["lo"] + amount_u32
    carry = (lo < c["lo"]).astype(jnp.uint32)
    return dict(lo=lo, hi=c["hi"] + carry)


def counter64_value(c) -> int:
    return int(np.asarray(c["hi"], np.uint64)) * 2**32 + int(np.asarray(c["lo"], np.uint64))


class TriangleCount(Survey):
    """Alg. 2 — global triangle count (metadata ignored)."""

    meta_spec = MetaSpec.none()

    def init(self):
        return counter64_zero()

    def update(self, state, tri):
        return counter64_add(state, tri.valid.sum(dtype=jnp.uint32))

    def merge(self, stacked):
        # Vectorized limb reduction (x64 stays off): split lo into 16-bit
        # halves so per-half uint32 sums are exact for S ≤ 2¹⁶ shards, then
        # recombine — mid carries every 2³² wrap into hi.
        lo, hi = stacked["lo"], stacked["hi"]
        s_lo16 = (lo & jnp.uint32(0xFFFF)).sum(dtype=jnp.uint32)
        s_hi16 = (lo >> jnp.uint32(16)).sum(dtype=jnp.uint32)
        mid = s_hi16 + (s_lo16 >> jnp.uint32(16))
        total_lo = (mid << jnp.uint32(16)) | (s_lo16 & jnp.uint32(0xFFFF))
        total_hi = hi.sum(dtype=jnp.uint32) + (mid >> jnp.uint32(16))
        return dict(lo=total_lo, hi=total_hi)

    def finalize(self, merged):
        return counter64_value(merged)

    def merge_epochs(self, prev, delta):
        # 64-bit add over uint32 limbs: lo-sum wrap carries into hi, so the
        # accumulated representation stays canonical (lo = value mod 2³²)
        lo = prev["lo"] + delta["lo"]
        carry = (lo < prev["lo"]).astype(jnp.uint32)
        return dict(lo=lo, hi=prev["hi"] + delta["hi"] + carry)

    def scale_sampled(self, result, p: float):
        return result / p**3


class LocalVertexCount(Survey):
    """Per-vertex triangle participation (truss/clustering building block).

    Dense [n] counters; at production scale use :class:`LabelTripleSet`-style
    hashed counting instead (paper Sec. 5.3 notes these are the same engine).
    """

    meta_spec = MetaSpec.none()

    def __init__(self, n: int):
        self.n = n

    def init(self):
        return jnp.zeros((self.n,), jnp.int32)

    def update(self, state, tri):
        amt = tri.valid.astype(jnp.int32)
        state = state.at[tri.p].add(amt)
        state = state.at[tri.q].add(amt)
        state = state.at[tri.r].add(amt)
        return state

    def scale_sampled(self, result, p: float):
        return np.asarray(result) / p**3


class ClosureTime(Survey):
    """Alg. 4 — joint (⌈log₂ Δt_open⌉, ⌈log₂ Δt_close⌉) histogram.

    Timestamps are edge float column ``ts_col``. Buckets clipped to
    [0, n_buckets); Δt ≤ 1 lands in bucket 0 (matches ceil(log2) for
    sub-unit gaps at the paper's second resolution).
    """

    def __init__(self, ts_col: int = 0, n_buckets: int = 64):
        self.ts_col = ts_col
        self.nb = n_buckets
        self.meta_spec = MetaSpec.edges(f=(ts_col,))

    def _bucket(self, dt):
        dt = jnp.maximum(dt, 1.0)
        b = jnp.ceil(jnp.log2(dt)).astype(jnp.int32)
        return jnp.clip(b, 0, self.nb - 1)

    def init(self):
        return jnp.zeros((self.nb, self.nb), jnp.int32)

    def update(self, state, tri):
        c = self.ts_col
        t1, t2, t3 = _sort3(tri.e_pq_f[:, c], tri.e_pr_f[:, c], tri.e_qr_f[:, c])
        open_b = self._bucket(t2 - t1)
        close_b = self._bucket(t3 - t1)
        return state.at[open_b, close_b].add(tri.valid.astype(jnp.int32))

    def finalize(self, merged):
        joint = np.asarray(merged)
        return dict(joint=joint, close_marginal=joint.sum(0), open_marginal=joint.sum(1))

    def scale_sampled(self, result, p: float):
        return {k: v / p**3 for k, v in result.items()}


class MaxEdgeLabelDist(Survey):
    """Alg. 3 — distribution of max edge label over vertex-distinct triangles."""

    def __init__(self, n_labels: int, e_label_col: int = 0, v_label_col: int = 0):
        self.n_labels = n_labels
        self.ec = e_label_col
        self.vc = v_label_col
        self.meta_spec = (MetaSpec.vertices(i=(v_label_col,))
                          | MetaSpec.edges(i=(e_label_col,)))

    def init(self):
        return jnp.zeros((self.n_labels,), jnp.int32)

    def update(self, state, tri):
        lp, lq, lr = tri.vp_i[:, self.vc], tri.vq_i[:, self.vc], tri.vr_i[:, self.vc]
        distinct = (lp != lq) & (lq != lr) & (lp != lr)
        mx = jnp.maximum(jnp.maximum(tri.e_pq_i[:, self.ec], tri.e_pr_i[:, self.ec]),
                         tri.e_qr_i[:, self.ec])
        mx = jnp.clip(mx, 0, self.n_labels - 1)
        return state.at[mx].add((tri.valid & distinct).astype(jnp.int32))

    def scale_sampled(self, result, p: float):
        return np.asarray(result) / p**3


class DegreeTriples(Survey):
    """Sec. 5.9 — count (⌈log₂ d(p)⌉, ⌈log₂ d(q)⌉, ⌈log₂ d(r)⌉) triples.

    Degrees are a vertex int metadata column (``HostGraph.with_degree_meta``),
    exactly the paper's "degree as a replacement for the dummy metadata".
    Uses the distributed counting set.
    """

    def __init__(self, deg_col: int = 0, capacity: int = 4096,
                 counting_backend: str = "auto"):
        self.deg_col = deg_col
        self.cs = CountingSet(capacity, 3, backend=counting_backend)
        self.meta_spec = MetaSpec.vertices(i=(deg_col,))

    def _lg(self, d):
        return jnp.ceil(jnp.log2(jnp.maximum(d.astype(jnp.float32), 1.0))).astype(jnp.int32)

    def init(self):
        return self.cs.init()

    def scale_sampled(self, result, p: float):
        return _scale_counting_set(result, p)

    def update(self, state, tri):
        c = self.deg_col
        keys = jnp.stack(
            [self._lg(tri.vp_i[:, c]), self._lg(tri.vq_i[:, c]), self._lg(tri.vr_i[:, c])], -1)
        return self.cs.increment(state, keys, tri.valid)

    def merge(self, stacked):
        return self.cs.merge(stacked)

    def merge_epochs(self, prev, delta):
        return self.cs.merge_epochs(prev, delta)

    def finalize(self, merged):
        return self.cs.finalize(merged)


class LabelTripleSet(Survey):
    """Sec. 5.8 — FQDN-style survey: count distinct-label 3-tuples.

    Vertex labels (hashed strings host-side) in int column ``v_label_col``.
    Tuples are canonicalized by sorting so (a,b,c) ≡ (b,a,c).
    """

    def __init__(self, v_label_col: int = 0, capacity: int = 1 << 16,
                 require_distinct: bool = True,
                 counting_backend: str = "auto"):
        self.vc = v_label_col
        self.require_distinct = require_distinct
        self.cs = CountingSet(capacity, 3, backend=counting_backend)
        self.meta_spec = MetaSpec.vertices(i=(v_label_col,))

    def init(self):
        return self.cs.init()

    def update(self, state, tri):
        c = self.vc
        l1, l2, l3 = _sort3(tri.vp_i[:, c], tri.vq_i[:, c], tri.vr_i[:, c])
        valid = tri.valid
        if self.require_distinct:
            valid = valid & (l1 != l2) & (l2 != l3)
        return self.cs.increment(state, jnp.stack([l1, l2, l3], -1), valid)

    def scale_sampled(self, result, p: float):
        return _scale_counting_set(result, p)

    def merge(self, stacked):
        return self.cs.merge(stacked)

    def merge_epochs(self, prev, delta):
        return self.cs.merge_epochs(prev, delta)

    def finalize(self, merged):
        return self.cs.finalize(merged)


class Enumerate(Survey):
    """Triangle enumeration into a fixed-capacity per-shard ring buffer.

    The paper notes enumeration is just another callback. ``triangles`` in
    the finalized result is a *capacity-bounded sample*: once a shard finds
    more than ``capacity`` triangles the ring wraps and earlier entries are
    overwritten (never duplicated — each triangle is written to exactly one
    slot). ``total_found`` stays the exact count and ``overflowed`` reports
    how many triangles are missing from the buffer (Σ per shard of
    max(0, n − capacity)).

    ``backend`` routes the ring scatter: ``"scatter"`` is XLA's
    ``.at[].set`` — which writer survives a *wrapped* slot is
    backend-defined, as JAX scatter ties are unordered; ``"pallas"`` is
    the ``kernels/fold_scatter.ring_set`` one-hot kernel, whose wrap
    winner is *deterministic* (highest batch index — the last writer).
    ``"auto"`` (default) picks the compiled kernel on a TPU backend and
    scatter elsewhere, so CPU runs are unchanged
    (:func:`repro.kernels.compiled` is the gate). The two backends agree
    bitwise whenever the buffer does not wrap (every slot has one
    writer); on wrapped slots only the Pallas winner is reproducible
    across backends.
    """

    meta_spec = MetaSpec.none()

    def __init__(self, capacity: int, backend: str = "auto"):
        if backend not in ("auto", "pallas", "scatter"):
            raise ValueError(f"unknown Enumerate backend {backend!r}")
        self.capacity = capacity
        self.backend = backend

    def uses_pallas(self) -> bool:
        """Whether :meth:`update` runs the ``ring_set`` Pallas kernel."""
        if self.backend == "auto":
            return kernels.compiled()
        return self.backend == "pallas"

    def init(self):
        return dict(
            tris=jnp.full((self.capacity, 3), -1, jnp.int32),
            n=jnp.zeros((), jnp.int32),
        )

    def update(self, state, tri):
        amt = tri.valid.astype(jnp.int32)
        offs = jnp.cumsum(amt) - amt + state["n"]
        idx = jnp.where(tri.valid, offs % self.capacity, self.capacity)  # OOB drop for invalid
        rows = jnp.stack([tri.p, tri.q, tri.r], -1)
        if self.uses_pallas():
            from repro.kernels.fold_scatter.ops import ring_set

            # carried-table scatter-set with a deterministic wrap winner;
            # the one-winner select sums masked rows, so invalid rows must
            # be zeroed (vertex ids are non-negative)
            rows = jnp.where(tri.valid[:, None], rows, 0)
            tris = ring_set(state["tris"], idx, rows, self.capacity,
                            interpret=not kernels.compiled())
        else:
            tris = state["tris"].at[idx].set(rows, mode="drop")
        return dict(tris=tris, n=state["n"] + amt.sum())

    def merge(self, stacked):
        # concatenation semantics: report per-shard buffers stacked
        return stacked

    def merge_epochs(self, prev, delta):
        # concatenate per-epoch buffers along the (shard-)stack axis: totals
        # and overflow stay exact; the *sample* an overflowing buffer keeps
        # is placement-dependent, as in any single run
        return jax.tree.map(lambda a, b: jnp.concatenate([a, b], 0),
                            prev, delta)

    def finalize(self, merged):
        tris = np.asarray(merged["tris"]).reshape(-1, 3)
        tris = tris[tris[:, 0] >= 0]
        n = np.asarray(merged["n"], np.int64)
        return dict(
            triangles=tris,
            total_found=int(n.sum()),
            overflowed=int(np.maximum(n - self.capacity, 0).sum()),
        )


# ---------------------------------------------------------------------------
# SurveyBundle — N surveys folded in one traversal (the "poll" in TriPoll)


class SurveyBundle(Survey):
    """Composite survey: fans one :class:`TriangleBatch` into N members.

    The member states live in a single tuple pytree, so ``make_survey_fn``
    compiles *one* superstep scan whose push queries and pulled rows are
    paid once while every member's fold is fused into the same program —
    polling N questions costs one traversal, not N (paper Sec. 4.5: the
    callback is arbitrary, so a tuple of callbacks is just another
    callback).

    The bundle's ``meta_spec`` is the union of its members' specs, so the
    engine ships exactly the lanes *some* member reads; each member still
    only indexes its own declared lanes. A bundle of one is unwrapped: the
    member's state flows through init/update/merge bare (no tuple-pytree
    wrapper), eliminating the measured ~1.3× singleton overhead; only
    ``finalize`` re-wraps the result under the member's name.
    """

    def __init__(self, surveys, names=None):
        self.surveys = tuple(surveys)
        if not self.surveys:
            raise ValueError("SurveyBundle needs at least one member survey")
        if names is None:
            names, seen = [], {}
            for s in self.surveys:
                base = type(s).__name__
                k = seen.get(base, 0)
                seen[base] = k + 1
                names.append(base if k == 0 else f"{base}_{k}")
        if len(names) != len(self.surveys):
            raise ValueError("names/surveys length mismatch")
        if len(set(names)) != len(names):
            raise ValueError(f"duplicate survey names: {names}")
        self.names = tuple(names)
        self._solo = self.surveys[0] if len(self.surveys) == 1 else None
        spec = MetaSpec.none()
        for s in self.surveys:
            spec = spec | getattr(s, "meta_spec", MetaSpec.full())
        self.meta_spec = spec

    def init(self):
        if self._solo is not None:
            return self._solo.init()
        return tuple(s.init() for s in self.surveys)

    def update(self, state, tri):
        if self._solo is not None:
            return self._solo.update(state, tri)
        return tuple(s.update(st, tri) for s, st in zip(self.surveys, state))

    def merge(self, stacked):
        if self._solo is not None:
            return self._solo.merge(stacked)
        return tuple(s.merge(st) for s, st in zip(self.surveys, stacked))

    def merge_epochs(self, prev, delta):
        if self._solo is not None:
            return self._solo.merge_epochs(prev, delta)
        return tuple(s.merge_epochs(p, d)
                     for s, p, d in zip(self.surveys, prev, delta))

    def finalize(self, merged):
        if self._solo is not None:
            return {self.names[0]: self._solo.finalize(merged)}
        return {n: s.finalize(m)
                for n, s, m in zip(self.names, self.surveys, merged)}

    def scale_sampled(self, result, p: float):
        return {n: s.scale_sampled(result[n], p)
                for n, s in zip(self.names, self.surveys)}


class TopKWeightedTriangles(Survey):
    """Top-k heaviest triangles, weight = Σ of an edge float column
    (after Kumar et al., *Retrieving Top Weighted Triangles in Graphs*).

    Per-shard state is a k-slot weight heap re-selected against each
    incoming batch; the cross-shard ``merge`` is the paper's merge-by-sort
    over the S·k stacked candidates. Exact because the engine discovers
    every triangle exactly once (push, pull or hub lane — never two).

    Every selection orders candidates by (weight desc, triangle key
    (p, q, r) lex asc), so when more than k triangles tie at the k-th
    weight the survivors are a *deterministic* function of the triangle
    set — independent of discovery order, shard count, transport, and
    epoch split. That makes the finalized result bitwise-identical across
    {dense, ragged, ragged+hub} runs and epoch-accumulated vs one-shot
    runs (asserted in tests), closing the tie caveat documented in PR 3.
    """

    def __init__(self, k: int, weight_col: int = 0):
        self.k = k
        self.wc = weight_col
        self.meta_spec = MetaSpec.edges(f=(weight_col,))

    def init(self):
        return dict(
            w=jnp.full((self.k,), -jnp.inf, jnp.float32),
            tri=jnp.full((self.k, 3), -1, jnp.int32),
        )

    def _select(self, w, tri):
        # -w ascending == weight descending; -(-inf) pads sort last. The
        # remaining keys never decide between distinct weights, only ties.
        order = jnp.lexsort((tri[:, 2], tri[:, 1], tri[:, 0], -w))
        idx = order[: self.k]
        return dict(w=w[idx], tri=tri[idx])

    def update(self, state, tri):
        c = self.wc
        w = tri.e_pq_f[:, c] + tri.e_pr_f[:, c] + tri.e_qr_f[:, c]
        w = jnp.where(tri.valid, w, -jnp.inf)
        rows = jnp.stack([tri.p, tri.q, tri.r], -1)
        return self._select(jnp.concatenate([state["w"], w]),
                            jnp.concatenate([state["tri"], rows]))

    def merge(self, stacked):
        S = stacked["w"].shape[0]
        return self._select(stacked["w"].reshape(S * self.k),
                            stacked["tri"].reshape(S * self.k, 3))

    def merge_epochs(self, prev, delta):
        # merge-by-sort of the two k-heaps — top-k is decomposable over a
        # disjoint partition of the triangle set, and the lexicographic
        # tie-break in _select makes the k survivors a pure function of the
        # candidate multiset, so epoch accumulation is bitwise-identical to
        # a one-shot run even at a tied boundary weight.
        return self._select(jnp.concatenate([prev["w"], delta["w"]]),
                            jnp.concatenate([prev["tri"], delta["tri"]]))

    def finalize(self, merged):
        w = np.asarray(merged["w"])
        tri = np.asarray(merged["tri"])
        keep = np.isfinite(w)
        return dict(weights=w[keep], triangles=tri[keep])
