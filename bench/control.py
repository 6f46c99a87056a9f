#!/usr/bin/env python3
"""The control of the comparison that decides ``correct``: the program
with its own approximate path switched on, DOULION edge sampling at
p = 1/2 (``sample_p``), which breaks the exactness every configuration
states. Its answers, compared with the reference exactly as a run
compares the program's, have to fail.

    python3 bench/control.py --workload <cell> --seed <n> [--seed <n> ...]

prints one JSON line per seed with the numbers compared: the one-shot
path, sampled, on each of the cell's graphs at the cell's size. The
benchmark's own runs never run it."""
from __future__ import annotations

import argparse
import json
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
for p in (ROOT, ROOT / "src"):
    if str(p) not in sys.path:
        sys.path.insert(0, str(p))

from bench import graphs, reference, run, surveys  # noqa: E402

SAMPLE_P = 0.5


def sampled(spec: dict, g: graphs.Graph, names, seed: int):
    """The program's answer on ``g`` with DOULION sampling on."""
    from repro.core.dodgr import shard_dodgr
    from repro.core.engine import survey_push_pull
    from repro.core.pushpull import plan_engine

    config = spec["config"]
    lay = config["layout"]
    hg = graphs.to_host_graph(g)
    bundle = surveys.make(names, config["surveys"])
    sample = dict(sample_p=SAMPLE_P, sample_seed=seed % 2 ** 31)
    cfg, _ = plan_engine(hg, lay["S"], bundle, mode=lay["mode"],
                         orient=lay["orient"], **sample)
    gr, _ = shard_dodgr(hg, lay["S"], hub_theta=cfg.hub_theta,
                        orient=lay["orient"], **sample)
    return survey_push_pull(gr, bundle, cfg)


def readings(spec: dict, seed: int) -> dict:
    """The numbers a run compares, for the control on each of the
    configuration's graphs: the largest of each over the graphs."""
    names = spec["traffic"]["surveys"]
    graph = spec["config"]["graph"]
    out: dict = {}
    for structure in graph["structure_seeds"]:
        g = graphs.make_graph(graph, structure, seed)
        res, stats = sampled(spec, g, names, seed)
        got = surveys.compare(res, stats, reference.answers(g, names))
        out = {k: max(v, out.get(k, v)) for k, v in got.items()}
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, action="append", required=True)
    args = ap.parse_args(argv)
    spec = run.load_cell(args.workload)
    for seed in args.seed:
        out = readings(spec, seed % 2 ** 63)
        print(json.dumps({"workload": args.workload, "seed": seed,
                          "control": out,
                          "fails": any(v > run.LIMITS[k]
                                       for k, v in out.items())}),
              flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
