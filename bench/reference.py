"""Plain reference for the configurations' surveys, independent of the
program: every triangle of the graph is listed on the host with numpy and
the surveys are folded from that list.

Triangles are found by wedge closure under the (degree, id) order: each
edge points from its lower to its higher end, so each triangle is exactly
one wedge p→q, p→r (q < r by id) closed by the pair {q, r}."""
from __future__ import annotations

from dataclasses import dataclass

import numpy as np

from bench.graphs import Graph


@dataclass(frozen=True)
class Triangles:
    """Every triangle once: its vertices and the indices of its edges."""

    v: np.ndarray   # [T, 3] int64 vertex ids
    e: np.ndarray   # [T, 3] int64 edge indices into the graph's edge list


def triangles(g: Graph) -> Triangles:
    n, m = g.n, g.m
    deg = np.bincount(g.src, minlength=n) + np.bincount(g.dst, minlength=n)
    rank = np.empty(n, np.int64)
    rank[np.lexsort((np.arange(n), deg))] = np.arange(n)
    fwd = rank[g.src] < rank[g.dst]
    p = np.where(fwd, g.src, g.dst)
    q = np.where(fwd, g.dst, g.src)
    order = np.lexsort((q, p))           # out-edges grouped by p, by q id
    p, q, eid = p[order], q[order], order.astype(np.int64)
    start = np.searchsorted(p, np.arange(n + 1))
    # wedge (i, j), i < j, for each pair of out-edges of one vertex
    pos = np.arange(m) - start[p]
    later = start[p + 1] - start[p] - pos - 1
    i = np.repeat(np.arange(m), later)
    j = i + 1 + np.arange(len(i)) - np.repeat(np.cumsum(later) - later, later)
    a, b = q[i], q[j]
    key = np.minimum(a, b) * n + np.maximum(a, b)
    ekey = g.src * n + g.dst
    sort = np.argsort(ekey)
    at = np.minimum(np.searchsorted(ekey, key, sorter=sort), m - 1)
    hit = ekey[sort[at]] == key
    i, j = i[hit], j[hit]
    v = np.stack([p[i], q[i], q[j]], 1)
    e = np.stack([eid[i], eid[j], sort[at[hit]]], 1)
    return Triangles(v, e)


def label_triples(g: Graph, tri: Triangles) -> dict:
    """Count of each sorted label triple whose three labels differ."""
    lab = np.sort(g.label[tri.v], axis=1).astype(np.int64)
    lab = lab[(lab[:, 0] != lab[:, 1]) & (lab[:, 1] != lab[:, 2])]
    lo = lab.min(initial=0)
    span = lab.max(initial=0) - lo + 1
    code = ((lab[:, 0] - lo) * span + lab[:, 1] - lo) * span + lab[:, 2] - lo
    codes, counts = np.unique(code, return_counts=True)
    keys = np.stack([codes // span ** 2, codes // span % span,
                     codes % span], 1) + lo
    return {tuple(int(x) for x in k): int(c) for k, c in zip(keys, counts)}


def answers(g: Graph, surveys) -> dict:
    """The reference answer of every survey named in ``surveys`` on
    ``g``."""
    tri = triangles(g)
    out = {}
    for name in surveys:
        if name == "TriangleCount":
            out[name] = len(tri.v)
        elif name == "LabelTripleSet":
            out[name] = label_triples(g, tri)
        else:
            raise ValueError(f"no reference for survey {name!r}")
    return out
