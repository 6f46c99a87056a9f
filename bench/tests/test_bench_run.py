"""The harness end to end on the CPU at a tiny size: it refuses to
measure without an accelerator or without the program, and with the look
for a chip skipped it drives whole one-shot runs whose timed path is
sound, or broken underneath, and ``correct`` says which."""
import dataclasses
import json
import os
import shutil
import subprocess
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import pytest

from bench import run
from bench.tests.cells import SEED, alter, run_tiny, tiny

ROOT = Path(__file__).resolve().parents[2]


def bench_cli(cwd, env) -> subprocess.CompletedProcess:
    return subprocess.run(
        [sys.executable, "bench/run.py", "--workload", "rmat14.count_labels",
         "--seed", str(SEED), "--seconds", "1", "--trace", "0"],
        cwd=cwd, env=dict(env, JAX_PLATFORMS="cpu"), capture_output=True,
        text=True, timeout=120)


def test_without_an_accelerator_it_exits_nonzero_and_prints_nothing():
    p = bench_cli(ROOT, os.environ)
    assert p.returncode != 0
    assert p.stdout == ""
    assert "accelerator" in p.stderr


def test_without_the_program_it_exits_nonzero_and_prints_nothing(tmp_path):
    shutil.copytree(ROOT / "bench", tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    p = bench_cli(tmp_path, env)
    assert p.returncode != 0
    assert p.stdout == ""
    assert "No module named 'repro'" in p.stderr


@pytest.mark.parametrize("cell,checks", [
    ("rmat14.count_labels", {"count_gap", "label_gap", "inexact"}),
    ("rmat14.count", {"count_gap", "inexact"})])
def test_a_sound_run_is_correct(cell, checks):
    out = run_tiny(cell)
    assert out["correct"], out["checks"]
    assert out["failed"] == 0 and out["attempted"] >= 1
    assert set(out["metrics"]) == {"setup_s", "survey_s"}
    # only what was compared is reported, each at its limit of 0
    assert out["checks"] == {k: {"value": 0, "limit": 0} for k in checks}
    assert list(out)[-1] == "checks"
    json.dumps(out)


def test_the_surveys_go_round_the_graphs_in_turn():
    from bench import generator, spans

    spec = tiny("rmat14.count", graphs=2)
    drv = generator.OneShot(spec["config"], spec["traffic"], SEED,
                            spans.Spans())
    drv.warm()
    for _ in range(3):
        drv.step()
    assert [k for k, _, _ in drv.answers] == [0, 1, 0]
    counts = [r["TriangleCount"] for _, r, _ in drv.answers]
    assert counts[0] == counts[2] != counts[1]


def test_a_traced_run_without_a_device_plane_reads_host_spans_only():
    out = run.run_cell(tiny("rmat14.count_labels"), SEED, 0.01, True,
                       jax.devices())
    assert out["correct"]
    # the CPU trace has no device plane: no device number is reported
    assert set(out["metrics"]) == {"plan_ms.survey"}
    assert out["device"]["busy_s"] == 0.0
    assert out["breakdown"] == {"device_ops": [], "idle_gaps": []}


@pytest.mark.parametrize("cell,member,check", [
    ("rmat14.count_labels", "TriangleCount", "count_gap"),
    ("rmat14.count_labels", "LabelTripleSet", "label_gap"),
    ("rmat14.count", "TriangleCount", "count_gap")])
def test_an_answer_altered_where_it_is_produced_fails(monkeypatch, cell,
                                                      member, check):
    from repro.core import engine

    real = engine.survey_with_fn

    def altered(*a, **k):
        res, st = real(*a, **k)
        return alter(res, member), st

    monkeypatch.setattr(engine, "survey_with_fn", altered)
    out = run_tiny(cell)
    assert not out["correct"]
    assert out["checks"][check]["value"] == 1


def test_half_of_the_batch_left_out_fails(monkeypatch):
    from repro.core.surveys import SurveyBundle

    real = SurveyBundle.update

    def half(self, state, tri):
        keep = jnp.arange(tri.valid.shape[0]) % 2 == 0  # every other slot
        return real(self, state,
                    dataclasses.replace(tri, valid=tri.valid & keep))

    monkeypatch.setattr(SurveyBundle, "update", half)
    out = run_tiny("rmat14.count_labels")
    assert not out["correct"]
    assert out["checks"]["count_gap"]["value"] > 0
