"""The benchmark's plain reference against the engine at R-MAT scale 8 on
the CPU, and its control: the program with DOULION sampling switched on
has to fail the comparison the benchmark makes."""
import numpy as np
import pytest

from bench import control, graphs, reference, surveys
from bench.tests.cells import tiny

GRAPH = {"generator": "rmat", "scale": 8, "edge_factor": 16,
         "abc": [0.57, 0.19, 0.19], "labels": 8}


NAMES = ["TriangleCount", "LabelTripleSet"]
PARAMS = {"LabelTripleSet": {"capacity": 1024}}


def graph_of(structure: int, seed: int):
    return graphs.make_graph(GRAPH, structure, seed)


@pytest.fixture(scope="module", params=[(3, 5), (2**31 + 17, 2**32 + 1)])
def graph(request):
    return graph_of(*request.param)


def engine_answer(g, orient="degree", mode="pushpull"):
    from repro.core.dodgr import shard_dodgr
    from repro.core.engine import survey_push_pull
    from repro.core.pushpull import plan_engine

    hg = graphs.to_host_graph(g)
    bundle = surveys.make(NAMES, PARAMS)
    cfg, _ = plan_engine(hg, 1, bundle, mode=mode, orient=orient)
    gr, _ = shard_dodgr(hg, 1, hub_theta=cfg.hub_theta, orient=orient)
    return survey_push_pull(gr, bundle, cfg)


def test_triangles_are_listed_once_with_their_edges(graph):
    tri = reference.triangles(graph)
    key = np.sort(tri.v, axis=1)
    assert len(np.unique(key, axis=0)) == len(key)
    for k in range(3):
        ends = np.sort(np.stack([graph.src[tri.e[:, k]],
                                 graph.dst[tri.e[:, k]]], 1), axis=1)
        pairs = [np.sort(tri.v[:, [a, b]], axis=1)
                 for a, b in ((0, 1), (0, 2), (1, 2))]
        assert all(any((ends[i] == p[i]).all() for p in pairs)
                   for i in range(len(ends)))


@pytest.mark.parametrize("structure,seed,orient", [
    (3, 5, "degree"), (2**31 + 17, 2**32 + 1, "stable")])
def test_reference_equals_the_engine(structure, seed, orient):
    graph = graph_of(structure, seed)
    res, stats = engine_answer(graph, orient)
    ref = reference.answers(graph, NAMES)
    assert ref["TriangleCount"] > 1000
    got = surveys.compare(res, stats, ref)
    assert got == {"inexact": 0, "count_gap": 0, "label_gap": 0}


@pytest.mark.parametrize("cell", ["rmat14.count_labels", "rmat14.count"])
def test_control_with_sampling_fails(cell):
    spec = tiny(cell)
    spec["config"]["graph"]["scale"] = 8
    got = control.readings(spec, 2**31 + 9)
    assert got["count_gap"] > 0


def test_the_seed_draws_metadata_on_the_stated_structures():
    a, b = graph_of(3, 5), graph_of(3, 6)
    assert (a.src == b.src).all() and (a.dst == b.dst).all()
    assert not (a.ts == b.ts).all() and not (a.label == b.label).all()
    c = graph_of(4, 5)
    assert a.m != c.m or not (a.src == c.src).all()
    # each graph of a run gets metadata of its own
    assert not (a.label == c.label).all()
