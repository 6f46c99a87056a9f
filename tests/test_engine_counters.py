"""The engine's counted stats: int32 per superstep on the device, summed
exactly on the host (``engine.sum_stats``).

The golden values were recorded from the engine as it stood when its
stats were float32 accumulators: results and every stat must stay what
they were, bit for bit, on the graphs tests/test_engine.py uses."""
import hashlib

import jax
import numpy as np
import pytest

from repro.core.dodgr import shard_dodgr
from repro.core.engine import STAT_KEYS, sum_stats, survey_push_only, \
    survey_push_pull
from repro.core.pushpull import plan_engine
from repro.core.surveys import (ClosureTime, LabelTripleSet, SurveyBundle,
                                TopKWeightedTriangles, TriangleCount)
from repro.graphs import generators

GRAPHS = {
    "clique8": lambda: generators.clique(8),
    "karate": lambda: generators.karate(),
    "rmat7": lambda: generators.rmat(7, 8, seed=1),
    "er": lambda: generators.erdos_renyi(150, 900, seed=2),
    "social": lambda: generators.temporal_social(120, 1200, seed=4),
}

OLD_KEYS = ("wedges_pushed", "tris_push", "wedges_pulled", "tris_pull",
            "wedges_hub", "tris_hub", "pull_requests", "pull_overflow",
            "stream_dropped", "wire_push_words", "wire_req_words",
            "wire_reply_words")

# (graph, mode, S, hub_theta) -> (digest of the finalized result, stats in
# OLD_KEYS order)
GOLDEN = {
    ("clique8", "push", 2, 0): (
        "5cdc18df21e97952", (56, 56, 0, 0, 0, 0, 0, 0, 0, 1536, 0, 0)),
    ("clique8", "pushpull", 3, 0): (
        "5cdc18df21e97952", (19, 19, 37, 37, 0, 0, 7, 0, 0, 3456, 144, 1224)),
    ("karate", "push", 2, 0): (
        "86fd8a4236d6acb6", (69, 45, 0, 0, 0, 0, 0, 0, 0, 1536, 0, 0)),
    ("karate", "pushpull", 3, 0): (
        "86fd8a4236d6acb6", (22, 15, 47, 30, 0, 0, 11, 0, 0, 3456, 144, 792)),
    ("rmat7", "push", 2, 0): (
        "dba534be55ff3189", (1961, 1466, 0, 0, 0, 0, 0, 0, 0, 23040, 0, 0)),
    ("rmat7", "pushpull", 3, 0): (
        "dba534be55ff3189",
        (225, 124, 1736, 1342, 0, 0, 71, 0, 0, 3456, 288, 6336)),
    ("er", "push", 2, 0): (
        "2d15a7edf5bdd922", (2528, 251, 0, 0, 0, 0, 0, 0, 0, 16896, 0, 0)),
    ("er", "pushpull", 3, 0): (
        "2d15a7edf5bdd922",
        (480, 42, 2048, 209, 0, 0, 195, 0, 0, 6912, 576, 7488)),
    ("social", "push", 2, 0): (
        "b5d8d31c80c176d1",
        (17751, 7436, 0, 0, 0, 0, 0, 0, 0, 184320, 0, 0)),
    ("social", "pushpull", 3, 0): (
        "b5d8d31c80c176d1",
        (712, 189, 17039, 7247, 0, 0, 264, 0, 0, 10368, 720, 44280)),
    ("rmat7", "pushpull", 2, "auto"): (
        "dba534be55ff3189",
        (63, 16, 84, 25, 1814, 1425, 8, 0, 0, 1536, 64, 736)),
}


def _digest(res) -> str:
    h = hashlib.sha256()
    for leaf in jax.tree.leaves(jax.device_get(res)):
        a = np.asarray(leaf)
        h.update(str(a.dtype).encode())
        h.update(a.tobytes())
    return h.hexdigest()[:16]


def _survey(name):
    if name == "social":   # float, label and top-k folds besides the count
        return SurveyBundle([TriangleCount(), ClosureTime(),
                             LabelTripleSet(capacity=512),
                             TopKWeightedTriangles(k=8)])
    return TriangleCount()


def _run(name, mode, S, theta):
    g = GRAPHS[name]()
    sv = _survey(name)
    cfg, _ = plan_engine(g, S, sv, mode=mode, push_cap=64, pull_q_cap=8,
                         hub_theta=theta)
    gr, _ = shard_dodgr(g, S=S, hub_theta=cfg.hub_theta)
    run = survey_push_only if mode == "push" else survey_push_pull
    return run(gr, sv, cfg)


@pytest.mark.parametrize("case", list(GOLDEN),
                         ids=["-".join(map(str, c)) for c in GOLDEN])
def test_results_and_stats_bitwise_unchanged(case):
    res, st = _run(*case)
    digest, old = GOLDEN[case]
    assert _digest(res) == digest
    for k, v in zip(OLD_KEYS, old):
        assert type(st[k]) is int and st[k] == v, k
    assert st["exact"] is True
    # every counted stat is reported, and the staged pull slots cover the
    # wedges the pull lane checked
    assert set(STAT_KEYS) <= set(st)
    assert st["pull_tile_slots"] >= st["wedges_pulled"]
    if st["wedges_pulled"]:
        assert st["pull_tile_slots"] > 0
    else:
        assert st["pull_tile_slots"] == 0


def test_host_sum_exact_past_int32():
    """Per-superstep int32 parts whose total passes 2^31 (and float32's
    2^24 by far) sum exactly on the host."""
    step = np.int32(2**31 - 1)
    parts = {"wedges_pulled": np.full(5, step, np.int32),
             "tris_pull": np.array([2**24 + 1, 2**24 + 1, 1], np.int32),
             "stream_dropped": np.zeros(1, np.int32)}
    st = sum_stats(parts)
    assert st["wedges_pulled"] == 5 * (2**31 - 1)
    assert st["tris_pull"] == 2 * (2**24 + 1) + 1
    assert st["stream_dropped"] == 0
    assert all(type(v) is int for v in st.values())
    # float32 accumulation, as the stats were, rounds the same parts
    f = np.float32(0)
    for x in parts["tris_pull"]:
        f = np.float32(f + np.float32(x))
    assert int(f) != st["tris_pull"]


def test_device_stats_are_int32_per_superstep():
    """The compiled program hands out one int32 count per superstep of
    each lane; the host sums them."""
    from repro.core.engine import make_survey_fn

    g = generators.rmat(7, 8, seed=1)
    cfg, _ = plan_engine(g, 2, TriangleCount(), mode="pushpull",
                         push_cap=64, pull_q_cap=8)
    gr, _ = shard_dodgr(g, S=2, hub_theta=cfg.hub_theta)
    _, raw = jax.jit(make_survey_fn(TriangleCount(), cfg))(gr)
    assert set(raw) == set(STAT_KEYS)
    assert all(v.dtype == np.int32 and v.ndim == 1 for v in raw.values())
    assert raw["wedges_pushed"].shape == (cfg.n_push_steps,)
    assert raw["wedges_pulled"].shape == (cfg.n_pull_steps,)
    assert raw["pull_tile_slots"].shape == (cfg.n_pull_steps,)
    assert raw["stream_dropped"].shape == (1,)
