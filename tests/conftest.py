"""Force 8 host CPU devices before jax initializes, so the mesh-transport
tests (tests/test_mesh.py) exercise real shard_map collectives on this
single-host container. A no-op if jax is somehow already imported (the
flag cannot take effect then — test_mesh skips itself on device count) or
if the environment already forces a device count (the CI matrix does).
"""
import os
import sys

if "jax" not in sys.modules:
    _flags = os.environ.get("XLA_FLAGS", "")
    if "xla_force_host_platform_device_count" not in _flags:
        os.environ["XLA_FLAGS"] = (
            _flags + " --xla_force_host_platform_device_count=8").strip()


import pytest  # noqa: E402


@pytest.fixture
def window_bound_pull_tiles(monkeypatch):
    """A pull staging budget under which each pull window, not the budget,
    bounds every pull tile. The stacked lowering stages all S shards on one
    device, so at the real budget its tile may be S times smaller than the
    mesh's and `pull_tile_slots` differ (docs/mesh.md); with window-bound
    tiles both lowerings stage the same slots and agree on every stat."""
    from repro.core import engine
    monkeypatch.setattr(engine, "PULL_STAGE_BYTES", 1 << 40)
