"""Workload data drawn from a seed: Graph500 R-MAT graphs with metadata.

Kept here, apart from the program's own generators, so that a change to
the program cannot move what the benchmark feeds it. Everything is
numpy on the host; the arrays go to the program through its public
``HostGraph`` container (``to_host_graph``)."""
from __future__ import annotations

from dataclasses import dataclass

import numpy as np

T_MAX = 1.0e6  # edge timestamps span [0, T_MAX) seconds


@dataclass(frozen=True)
class Graph:
    """An undirected simple graph as the reference sees it: edges once per
    pair with ``src < dst`` in arrival order, one float32 timestamp per
    edge, one int32 label per vertex."""

    n: int
    src: np.ndarray   # [m] int64
    dst: np.ndarray   # [m] int64
    ts: np.ndarray    # [m] float32
    label: np.ndarray  # [n] int32

    @property
    def m(self) -> int:
        return len(self.src)


def rmat_edges(scale: int, edge_factor: int, seed, a: float, b: float,
               c: float) -> tuple[np.ndarray, np.ndarray]:
    """R-MAT recursive quadrant sampling (Chakrabarti et al. 2004), the
    Graph500 Kronecker generator's edge draw: ``edge_factor · 2**scale``
    directed pairs, loops and duplicates included."""
    rng = np.random.default_rng(seed)
    m = (1 << scale) * edge_factor
    src = np.zeros(m, np.int64)
    dst = np.zeros(m, np.int64)
    d = 1.0 - a - b - c
    for bit in range(scale):
        u = rng.random(m)
        v = rng.random(m)
        src_bit = u > (a + b)
        dst_bit = np.where(src_bit, v > c / (c + d), v > a / (a + b))
        src |= src_bit.astype(np.int64) << bit
        dst |= dst_bit.astype(np.int64) << bit
    return src, dst


def canonical(n: int, src, dst, ts) -> tuple:
    """Drop loops, store each pair as ``src < dst`` and keep the first
    arrival of a repeated pair (the earliest-edge semantics of the
    paper's Reddit stream). Survivors stay in arrival order."""
    src = np.asarray(src, np.int64)
    dst = np.asarray(dst, np.int64)
    ts = np.asarray(ts, np.float32)
    keep = src != dst
    lo = np.minimum(src, dst)[keep]
    hi = np.maximum(src, dst)[keep]
    ts = ts[keep]
    _, first = np.unique(lo * n + hi, return_index=True)
    first.sort()
    return lo[first], hi[first], ts[first]


def make_graph(graph: dict, structure: int, seed: int) -> Graph:
    """One of the configuration's graphs: R-MAT structure drawn from the
    structure seed ``structure``, and metadata from ``seed``: one
    timestamp per edge uniform in [0, T_MAX) and one label per vertex
    uniform over ``graph["labels"]`` values."""
    scale = graph["scale"]
    n = 1 << scale
    src, dst = rmat_edges(scale, graph["edge_factor"], structure,
                          *graph["abc"])
    rng = np.random.default_rng([seed, 1, structure])
    ts = (rng.random(len(src)) * T_MAX).astype(np.float32)
    src, dst, ts = canonical(n, src, dst, ts)
    label = rng.integers(0, graph["labels"], n).astype(np.int32)
    return Graph(n, src, dst, ts, label)


def to_host_graph(g: Graph):
    """The program's ``HostGraph`` for ``g`` (the only program type this
    module touches, and only here)."""
    from repro.graphs.csr import HostGraph, MetaSpec

    spec = MetaSpec(v_int=("label",), e_float=("ts",))
    return HostGraph.from_edges(g.n, g.src, g.dst, spec=spec,
                                emeta_f=g.ts[:, None],
                                vmeta_i=g.label[:, None])
