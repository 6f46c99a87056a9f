"""Compile the main path for a described TPU v5e — no chip needed.

The TPU compiler is installed here and compiles for a topology that is
described, not attached, so what Mosaic or XLA would refuse on the chip
(kernel layouts, unsupported reductions, programs that outgrow HBM) fails
here first. Nothing runs: these tests say nothing about results or times.

The topology is described inside a module-scoped fixture — never at
import time — so only the worker that runs this file loads the TPU
library. ``jax.default_backend()`` still reports the CPU, so the tests
steer the one Pallas backend gate (``repro.kernels.compiled``) to the
chip's answer themselves.
"""
import functools

import jax
import jax.numpy as jnp
import pytest

from repro import kernels
from repro.core.dodgr import shard_dodgr
from repro.core.engine import (DENSE_SEARCH_MAX_ROW, PULL_STAGE_BYTES,
                               make_survey_fn)
from repro.core.pushpull import plan_engine
from repro.core.surveys import (DegreeTriples, Enumerate, LabelTripleSet,
                                TriangleBatch, TriangleCount)
from repro.graphs.csr import MetaSpec
from repro.graphs.generators import rmat
from repro.kernels.fold_scatter.ops import fold_count_max, ring_set
from repro.kernels.hist.ops import hist_add, hist_max
from repro.kernels.intersect.ops import intersect

i32, u32 = jnp.int32, jnp.uint32


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    from jax.sharding import SingleDeviceSharding

    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def chip(one_chip):
    """Shape builder on the described chip, with the Pallas gate answering
    as it does on a TPU and JAX's persistent compilation cache off (a
    described-chip compile is written to it but cannot be read back)."""
    from jax.experimental.compilation_cache import compilation_cache

    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(kernels, "compiled", lambda: True)
        yield lambda shape, dtype: jax.ShapeDtypeStruct(shape, dtype,
                                                        sharding=one_chip)
    jax.config.update("jax_enable_compilation_cache", was)


def _compile(fn, *args):
    return jax.jit(fn).lower(*args).compile()


B = 4096   # one push superstep's fold batch at S=4, push_cap=256


@pytest.mark.parametrize("name,capacity,W", [
    ("LabelTripleSet", 1 << 16, 5), ("DegreeTriples", 4096, 5),
    ("smoke_labels", 1024, 5)])
def test_fold_count_max_compiles(chip, name, capacity, W):
    """CountingSet's fused fold at the widths the surveys use: K=3 key
    columns + 2 check-hash columns."""
    c = _compile(functools.partial(fold_count_max, capacity=capacity,
                                   interpret=False),
                 chip((B,), i32), chip((B,), i32), chip((B, W), u32))
    assert "tpu_custom_call" in c.as_text(), name


@pytest.mark.parametrize("capacity", [4096, 1000])
def test_ring_set_compiles(chip, capacity):
    """Enumerate's ring scatter, including a capacity off the lane grid."""
    c = _compile(functools.partial(ring_set, capacity=capacity,
                                   interpret=False),
                 chip((capacity, 3), i32), chip((B,), i32),
                 chip((B, 3), i32))
    assert "tpu_custom_call" in c.as_text()


@pytest.mark.parametrize("kernel", ["hist_add", "hist_max", "intersect"])
def test_repaired_kernels_compile(chip, kernel):
    """The other kernels this layout repaired; ``intersect`` gathers along
    one vreg, so it compiles for rows of at most 128 lanes."""
    if kernel == "hist_add":
        c = _compile(functools.partial(hist_add, capacity=4096,
                                       interpret=False),
                     chip((B,), i32), chip((B,), i32))
    elif kernel == "hist_max":
        c = _compile(functools.partial(hist_max, capacity=4096,
                                       interpret=False),
                     chip((B,), i32), chip((B, 5), u32))
    else:
        row = [chip((B, 128), t) for t in (i32, u32, i32)]
        c = _compile(functools.partial(intersect, interpret=False),
                     *row, chip((B,), i32), *row)
    assert "tpu_custom_call" in c.as_text()


@pytest.mark.parametrize("survey", [
    LabelTripleSet(v_label_col=0), DegreeTriples(deg_col=0),
    Enumerate(4096)], ids=["LabelTripleSet", "DegreeTriples", "Enumerate"])
def test_survey_fold_takes_compiled_kernel(chip, survey):
    """With the gate on the chip's answer, the surveys' ``auto`` backends
    fold through the Pallas kernels, which compile."""
    assert getattr(survey, "cs", survey).uses_pallas()
    spec = survey.meta_spec.resolve(1, 0, 0, 0)
    tri = jax.tree.map(lambda s: chip(s.shape, s.dtype),
                       TriangleBatch.abstract(spec, batch=B))
    state = jax.tree.map(lambda s: chip(s.shape, s.dtype),
                         jax.eval_shape(survey.init))
    c = _compile(survey.update, state, tri)
    assert "tpu_custom_call" in c.as_text()


def test_pushpull_temporaries_within_stage_budget(chip):
    """Push-pull TriangleCount at R-MAT scale 12, S=4: the pull phase's
    requester batch is staged in tiles, so the compiled program's
    temporaries stay within the staging budget (1.8 GB untiled)."""
    g = rmat(12, 16, seed=0, spec=MetaSpec())
    cfg, _ = plan_engine(g, 4, TriangleCount(), mode="pushpull")
    assert cfg.n_pull_steps > 0
    gr, _ = shard_dodgr(g, 4, hub_theta=cfg.hub_theta, orient="degree")
    ab = jax.tree.map(lambda x: chip(x.shape, x.dtype), gr)
    c = _compile(make_survey_fn(TriangleCount(), cfg), ab)
    assert c.memory_analysis().temp_size_in_bytes < PULL_STAGE_BYTES


def test_pushpull_dense_search_with_labels_within_stage_budget(chip):
    """The same graph with a vertex label lane, surveyed by LabelTripleSet:
    the dense pull search gathers whole reply rows, and the staged tile
    still keeps the compiled program's temporaries within the budget."""
    g = rmat(12, 16, seed=0, spec=MetaSpec(v_int=("label",)))
    sv = LabelTripleSet(v_label_col=0)
    cfg, _ = plan_engine(g, 4, sv, mode="pushpull")
    assert cfg.n_pull_steps > 0
    assert 0 < cfg.pull_row_cap <= DENSE_SEARCH_MAX_ROW
    gr, _ = shard_dodgr(g, 4, hub_theta=cfg.hub_theta, orient="degree")
    ab = jax.tree.map(lambda x: chip(x.shape, x.dtype), gr)
    c = _compile(make_survey_fn(sv, cfg), ab)
    assert c.memory_analysis().temp_size_in_bytes < PULL_STAGE_BYTES
