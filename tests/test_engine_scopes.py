"""The engine names its device work: every lane's ops carry an
``engine/…`` name scope in the compiled program's op metadata, on the
stacked lowering and on the mesh lowering alike. Scopes are metadata
only — what they compute is checked bitwise in test_engine_counters.py."""
import re

import jax
import pytest

from repro.core.dodgr import shard_dodgr
from repro.core.engine import make_survey_fn
from repro.core.pushpull import plan_engine
from repro.core.surveys import TriangleCount
from repro.graphs import generators
from repro.launch.mesh import make_shard_mesh

SCOPES = ("engine/setup", "engine/push/wire", "engine/push/check",
          "engine/hub", "engine/pull/wire", "engine/pull/rank",
          "engine/pull/search", "engine/fold")


def _op_names(text: str) -> set[str]:
    return set(re.findall(r'op_name="([^"]*)"', text))


def _compiled(S, transport, mesh=None):
    g = generators.rmat(7, 8, seed=1)
    sv = TriangleCount()
    # θ chosen by the planner: this graph then runs all three lanes
    cfg, _ = plan_engine(g, S, sv, mode="pushpull", push_cap=64,
                         pull_q_cap=8, hub_theta="auto", transport=transport)
    assert cfg.n_push_steps and cfg.n_pull_steps and cfg.n_hub_steps
    gr, _ = shard_dodgr(g, S=S, hub_theta=cfg.hub_theta)
    fn = jax.jit(make_survey_fn(sv, cfg, mesh=mesh))
    return fn.lower(gr).compile().as_text()


def _check(text):
    names = _op_names(text)
    for scope in SCOPES:
        assert any(scope in n for n in names), scope
    # the pull lane's rank addressing and key search are told apart
    rank = [n for n in names if "engine/pull/rank" in n]
    search = [n for n in names if "engine/pull/search" in n]
    assert any("searchsorted" in n for n in rank)
    assert any("gather" in n for n in search)


def test_stacked_program_carries_every_scope():
    _check(_compiled(2, "dense"))


@pytest.mark.skipif(jax.device_count() < 2,
                    reason="needs 2 devices (tests/conftest.py forces 8)")
def test_mesh_program_carries_every_scope():
    _check(_compiled(2, "mesh", mesh=make_shard_mesh(2)))
