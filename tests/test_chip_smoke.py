"""chip_smoke.py's phases on the CPU at a tiny scale, its refusal to run
without a TPU, and the compile-cache helper it shares with the service.

The phases check themselves (a failed check raises); these tests run them
end to end on R-MAT graphs of scale 7–8 and never print the ``ok`` line.
The four-device mesh phase runs on the host devices ``conftest.py``
forces.
"""
import importlib.util
import os
import shutil
import subprocess
import sys
from pathlib import Path

import jax
import pytest

from repro import utils

ROOT = Path(__file__).resolve().parents[1]


@pytest.fixture(scope="module")
def smoke():
    spec = importlib.util.spec_from_file_location("chip_smoke",
                                                  ROOT / "chip_smoke.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.fixture(scope="module")
def graph(smoke):
    return smoke.make_graph(8, seed=3)


def test_kernels_phase(smoke):
    assert smoke.phase_kernels(seed=2, batch=1024)["mode"] == "interpret"


def test_oracle_phase(smoke):
    out = smoke.phase_oracle(seed=1, scale=7)
    assert out["triangles"] > 0


def test_survey_phase(smoke, graph):
    out = smoke.phase_survey(graph)
    assert out["triangles"] == smoke.host_triangle_count(graph) > 0
    assert out["n_pull_steps"] > 0          # the pull phase really ran
    # off a TPU every fold stays on XLA
    assert set(out["fold_backends"].values()) <= {"xla-reduce",
                                                  "xla-scatter"}


def test_service_phase(smoke, graph):
    out = smoke.phase_service(graph, seed=3, epochs=2)
    rows = [r for r in out["epochs"] if "triangles" in r]
    assert [r["epoch"] for r in rows] == [1, 2]
    assert rows[0]["new_edges"] > 0
    assert rows[1]["triangles"] >= rows[0]["triangles"]


@pytest.mark.skipif(jax.device_count() < 4,
                    reason="needs 4 devices (conftest.py forces them "
                           "unless jax initialized first)")
@pytest.mark.usefixtures("window_bound_pull_tiles")
def test_mesh_phase(smoke, graph):
    out = smoke.phase_mesh(graph, S=4)
    assert [(c["survey"], c["caps"]) for c in out["cases"]] == [
        ("TriangleCount", "dense"), ("TriangleCount", "ragged"),
        ("bundle", "dense"), ("bundle", "ragged")]
    assert all(c["bitwise"] for c in out["cases"])


def test_host_count_matches_oracle(smoke):
    from repro.core.ref import count_triangles_ref

    g = smoke.make_graph(7, seed=5)
    assert smoke.host_triangle_count(g) == count_triangles_ref(g)


def test_main_refuses_without_tpu(smoke, capsys):
    assert jax.default_backend() != "tpu"
    assert smoke.main([]) != 0
    assert '"ok"' not in capsys.readouterr().out


def test_script_alone_fails(tmp_path):
    """Copied away from the repo, the script cannot import the program:
    it exits non-zero and prints no result."""
    shutil.copy(ROOT / "chip_smoke.py", tmp_path)
    env = dict(os.environ, PYTHONPATH="", JAX_PLATFORMS="cpu")
    out = subprocess.run([sys.executable, "chip_smoke.py"], cwd=tmp_path,
                         env=env, capture_output=True, text=True,
                         timeout=120)
    assert out.returncode != 0
    assert '"ok"' not in out.stdout


@pytest.fixture
def cache_dir_config():
    """Restore JAX's compilation-cache directory after the test."""
    was = jax.config.jax_compilation_cache_dir
    yield
    jax.config.update("jax_compilation_cache_dir", was)


def test_compile_cache_honours_env(monkeypatch, tmp_path, cache_dir_config):
    jax.config.update("jax_compilation_cache_dir", None)
    monkeypatch.setenv(utils.COMPILE_CACHE_ENV, str(tmp_path))
    assert utils.enable_compile_cache() == tmp_path
    # JAX reads the variable itself; the helper sets no directory
    assert jax.config.jax_compilation_cache_dir is None


def test_compile_cache_defaults_to_checkout(monkeypatch, cache_dir_config):
    monkeypatch.delenv(utils.COMPILE_CACHE_ENV, raising=False)
    path = utils.enable_compile_cache()
    assert path == ROOT / ".jax_cache" == utils.CHECKOUT_COMPILE_CACHE
    assert jax.config.jax_compilation_cache_dir == str(path)
