"""The planner's and the sharder's host phases are spans of the program
(``repro.utils.span``): a parent span per call, one level of children
inside it, and ``repro.finalize`` around the engine's host epilogue."""
import contextlib
import glob
import os

import jax
import pytest

from repro import utils
from repro.core import dodgr, engine, pushpull
from repro.core.surveys import TriangleCount
from repro.graphs import generators

PLAN_CHILDREN = ("orientation", "pull_decision", "hub_theta", "caps",
                 "report")
SHARD_CHILDREN = ("orientation", "rows", "hub_table", "shards",
                  "routing_stats", "device_arrays")


@pytest.fixture
def recorded(monkeypatch):
    """Replace the span helper where the program calls it; record each
    span with the names of the spans open around it."""
    log, open_ = [], []

    @contextlib.contextmanager
    def span(name):
        log.append((name, tuple(open_)))
        open_.append(name)
        try:
            yield
        finally:
            open_.pop()

    for mod in (pushpull, dodgr, engine):
        monkeypatch.setattr(mod, "span", span)
    return log


def _graph():
    return generators.rmat(7, 8, seed=1)


def test_plan_engine_spans(recorded):
    pushpull.plan_engine(_graph(), 2, TriangleCount(), mode="pushpull",
                         hub_theta="auto")
    assert recorded[0] == ("plan_engine", ())
    kids = [n for n, up in recorded if up == ("plan_engine",)]
    assert kids == [f"plan_engine.{c}" for c in PLAN_CHILDREN]
    assert len(recorded) == 1 + len(PLAN_CHILDREN)


def test_shard_dodgr_spans(recorded):
    dodgr.shard_dodgr(_graph(), 2, hub_theta=8)
    assert recorded[0] == ("shard_dodgr", ())
    kids = [n for n, up in recorded if up == ("shard_dodgr",)]
    assert kids == [f"shard_dodgr.{c}" for c in SHARD_CHILDREN]
    assert len(recorded) == 1 + len(SHARD_CHILDREN)


def test_finalize_span(recorded):
    g = _graph()
    cfg, _ = pushpull.plan_engine(g, 2, TriangleCount(), mode="pushpull")
    gr, _ = dodgr.shard_dodgr(g, 2, hub_theta=cfg.hub_theta)
    recorded.clear()
    engine.survey_push_pull(gr, TriangleCount(), cfg)
    assert recorded == [("finalize", ())]


def test_spans_reach_the_profiler_trace(tmp_path):
    """The real helper writes ``repro.<name>`` host events into the
    profiler's trace, children inside their parent's interval."""
    from jax.profiler import ProfileData

    assert isinstance(utils.span("x"), jax.profiler.TraceAnnotation)
    g = _graph()
    jax.profiler.start_trace(str(tmp_path))
    try:
        pushpull.plan_engine(g, 2, TriangleCount(), mode="pushpull")
    finally:
        jax.profiler.stop_trace()
    path, = glob.glob(os.path.join(str(tmp_path), "plugins", "profile", "*",
                                   "*.xplane.pb"))
    evs = [(e.name, e.start_ns, e.start_ns + e.duration_ns)
           for p in ProfileData.from_file(path).planes
           if p.name.startswith("/host:") for line in p.lines
           for e in line.events if e.name.startswith("repro.")]
    parent = [e for e in evs if e[0] == "repro.plan_engine"]
    assert len(parent) == 1
    _, lo, hi = parent[0]
    kids = sorted((s, n) for n, s, _ in evs
                  if n.startswith("repro.plan_engine."))
    assert [n for _, n in kids] == [f"repro.plan_engine.{c}"
                                    for c in PLAN_CHILDREN]
    assert all(lo <= s and e <= hi for n, s, e in evs
               if n.startswith("repro.plan_engine."))
