"""Pull tiles at the real staging budget on the two lowerings: the stacked
lowering stages all S shards on one device, so its tile is the budget over
S shards where the mesh's is the budget over one. Where that makes the
stacked tile smaller than the window, the two stage different slots, and
every other result and stat is still bitwise the same (tests/test_mesh.py
holds the tiles to the window and compares every stat)."""
import jax
import pytest

from repro.core import engine

from test_delta import _bundle, _labeled_graph, _tree_equal
from test_mesh import S, _run_pair

pytestmark = pytest.mark.skipif(
    jax.device_count() < S,
    reason=f"needs {S} devices (conftest.py forces them unless jax "
           "initialized first)")


def test_budget_bound_stacked_tiles_differ_only_in_staged_slots(monkeypatch):
    from repro.launch.mesh import make_shard_mesh

    tiles = []
    real = engine.pull_tile_wedges
    monkeypatch.setattr(engine, "pull_tile_wedges",
                        lambda *a: tiles.append((a[0], real(*a)))
                        or tiles[-1][1])
    g = _labeled_graph(96, 700, seed=4)
    (res_r, st_r), (res_m, st_m) = _run_pair(g, _bundle(g), "pushpull",
                                             make_shard_mesh(S))
    # the stacked tile (S_ax = S) is budget-bound, the mesh's (S_ax = 1) not
    T = dict(tiles)
    assert T[S] < T[1]
    assert _tree_equal(res_m, res_r)
    drop = lambda st: {k: v for k, v in st.items() if k != "pull_tile_slots"}
    assert _tree_equal(drop(st_m), drop(st_r))
    assert st_r["pull_tile_slots"] != st_m["pull_tile_slots"]
    for st in (st_r, st_m):
        assert st["pull_tile_slots"] >= st["wedges_pulled"] > 0
