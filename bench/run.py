#!/usr/bin/env python3
"""Benchmark of the triangle-survey system on the chip, driven by data.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

One run is one fresh process: it builds the cell named in
``BENCHMARK.json`` from its configuration file (``bench/configs/``) and
traffic file (``bench/traffic/``), draws its inputs from ``--seed`` (the
graphs' metadata; their structure is the configuration's), warms up
every shape the cell uses (set-up), runs the traffic for ``--seconds``
(the window), frees the program's state and compares every answer the
window produced that the traffic checks against a plain reference
(``bench/reference.py``).

The last line on standard output is one JSON object: ``correct``,
``attempted``, ``failed``, ``metrics`` (the cell's end-to-end metrics, or
with ``--trace 1`` its per-layer metrics, read by ``bench/metrics/<name>.py``
from a profiler trace of the window), ``device`` and, last, ``checks``:
each compared number beside its limit. The same checks end standard
error. Without an accelerator, or with fewer chips than the cell asks
for, it exits non-zero and prints no result."""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import contextlib  # noqa: E402
import gc  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

HERE = Path(__file__).resolve().parent
ROOT = HERE.parent
for p in (ROOT, ROOT / "src"):
    if str(p) not in sys.path:
        sys.path.insert(0, str(p))

# every compared number must stay at or under its limit: the surveys are
# exact, so an answer off by anything is wrong
LIMITS = {"count_gap": 0, "label_gap": 0, "inexact": 0}


def load_cell(name: str, root: Path = ROOT) -> dict:
    """The workload entry ``name`` of ``BENCHMARK.json`` with its
    configuration, traffic and metric entries resolved."""
    bench = json.loads((root / "BENCHMARK.json").read_text())
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise SystemExit(f"no workload {name!r} in BENCHMARK.json")
    cell = cells[name]
    conf = {c["name"]: c for c in bench["configs"]}[cell["config"]]
    config = json.loads((root / conf["file"]).read_text())
    traffic = json.loads((HERE / "traffic" / f"{cell['traffic']}.json")
                         .read_text())

    def mine(metrics):
        return [m for m in metrics if name in m.get("workloads", [name])]

    return dict(cell=cell, config=config, traffic=traffic,
                end_to_end=mine(bench["end_to_end"]),
                per_layer=mine(bench["per_layer"]))


def reader(metric: str):
    """``read(run)`` of ``bench/metrics/<metric>.py``."""
    path = HERE / "metrics" / f"{metric}.py"
    spec = importlib.util.spec_from_file_location(
        f"bench_metric_{metric.replace('.', '_')}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def accelerator(chips: int):
    """The devices, or None where JAX found no accelerator or too few."""
    import jax

    devs = jax.devices()
    if devs[0].platform == "cpu" or len(devs) < chips:
        print(f"bench: needs {chips} accelerator chip(s), JAX found "
              f"{len(devs)} {devs[0].platform} device(s)", file=sys.stderr)
        return None
    return devs


class Run:
    """What a run hands the per-layer metric readers: the traffic (its
    answers and the program's counters), the host spans, the reduced
    trace and the device kind."""

    def __init__(self, traffic, spans, trace, device_kind):
        self.traffic, self.spans = traffic, spans
        self.trace, self.device_kind = trace, device_kind


def run_cell(spec: dict, seed: int, seconds: float, traced: bool,
             devices, t_start: float = T_START) -> dict:
    """Set up, measure and check the cell ``spec`` (from ``load_cell``);
    returns the result line."""
    import jax

    from bench import generator, trace
    from bench.spans import Spans

    spans = Spans()
    seed = int(seed) % 2 ** 63
    drv = generator.KINDS[spec["traffic"]["kind"]](
        spec["config"], spec["traffic"], seed, spans)
    drv.warm()
    # what set-up built lives for the whole run: move it out of the
    # collector's reach, so that a full collection in the window scans
    # only what the window allocates
    gc.collect()
    gc.freeze()
    t0 = spans.mark = time.perf_counter()
    setup_s = t0 - t_start
    ends: list[float] = []   # window seconds at the end of each step
    with (trace.Capture() if traced else contextlib.nullcontext()) as cap:
        with jax.profiler.TraceAnnotation(trace.WINDOW_SPAN):
            while not ends or ends[-1] < seconds:
                drv.step()
                ends.append(time.perf_counter() - t0)
    gc.unfreeze()
    window_s = ends[-1]
    used = devices[:spec["cell"]["chips"]]
    peak = max((d.memory_stats() or {}).get("peak_bytes_in_use", 0)
               for d in used)
    e2e = drv.result(window_s)
    e2e["setup_s"] = setup_s
    run = Run(drv, spans, cap.trace if cap else None, used[0].device_kind)
    per_layer = {}
    if traced:
        for m in spec["per_layer"]:
            v = reader(m["name"])(run)
            if v is not None:
                per_layer[m["name"]] = {"value": v, "unit": m["unit"]}
    drv.close()
    rows = drv.compare()
    # only what was compared: a survey the traffic does not ask reports
    # no number
    checks = {k: max(r[k] for r in rows if k in r) for k in LIMITS
              if any(k in r for r in rows)}
    failed = sum(any(v > LIMITS[k] for k, v in r.items()) for r in rows)
    correct = failed == 0
    metrics = per_layer if traced else {
        m["name"]: {"value": e2e[m["name"]], "unit": m["unit"]}
        for m in spec["end_to_end"]}
    device = {"platform": used[0].platform, "kind": used[0].device_kind,
              "count": len(devices), "memory_peak_bytes": peak}
    out = {"correct": correct, "attempted": len(ends), "failed": failed,
           "metrics": metrics, "device": device}
    if traced:
        tr = run.trace
        device["busy_s"] = trace.busy_s(tr)
        device["window_s"] = tr.window_s
        out["breakdown"] = {"device_ops": trace.top_ops(tr),
                            "idle_gaps": trace.idle_gaps(tr)}
    steps = [b - a for a, b in zip([0.0] + ends, ends)]
    slow = max(range(len(steps)), key=steps.__getitem__)
    print(f"slowest step {slow}: {steps[slow]:.4f} s; spans "
          f"{spans.within(t0 + ends[slow] - steps[slow], t0 + ends[slow])}",
          file=sys.stderr)
    out["steps_s"] = steps
    out["checks"] = {k: {"value": v, "limit": LIMITS[k]}
                     for k, v in checks.items()}
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    spec = load_cell(args.workload)
    import repro  # noqa: F401  (the system under test must be present)

    devices = accelerator(spec["cell"]["chips"])
    if devices is None:
        return 2
    import jax

    from repro.utils import enable_compile_cache

    # cache every program, however quickly it compiled, so that a second
    # run of a cell finds all of them
    enable_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    out = run_cell(spec, args.seed, args.seconds, bool(args.trace),
                   devices)
    print(f"steps_s {out.pop('steps_s')}", file=sys.stderr)
    for k, c in out["checks"].items():
        print(f"check {k} = {c['value']} (limit {c['limit']})",
              file=sys.stderr)
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
