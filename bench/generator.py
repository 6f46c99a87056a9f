"""The general traffic generator: one class per kind of traffic, each
configured by a traffic file's parameters and a configuration file.

A traffic object builds its state from the seed in ``__init__`` and ``warm``
(set-up), runs one closed-loop step per ``step`` call (the window), and
after the window ``compare``s what the timed path produced with the plain
reference: one row of numbers per answer checked (``surveys.compare``).
``close`` frees the program's device state before the reference runs.

The program is imported where it is called, so that a test can replace
an entry point of it underneath a whole run."""
from __future__ import annotations

import jax

from bench import graphs, reference, surveys


class OneShot:
    """One analyst in a closed loop: back-to-back one-shot surveys of the
    traffic's surveys, each through the whole path plan → shard → place →
    compiled program → finalize. The surveys go round the configuration's
    graphs (one per structure seed) in the order listed, so every seed
    does the same work."""

    def __init__(self, config: dict, traffic: dict, seed: int, spans):
        self.spans = spans
        self.layout = config["layout"]
        self.graphs = [graphs.make_graph(config["graph"], s, seed)
                       for s in config["graph"]["structure_seeds"]]
        self.hgs = [graphs.to_host_graph(g) for g in self.graphs]
        self.names = traffic["surveys"]
        self.params = config["surveys"]
        self.fns: dict = {}   # compiled program per plan
        self.answers: list = []   # (graph index, result, stats)
        self.wedges = 0.0

    def _survey(self, k: int):
        from repro.core.dodgr import shard_dodgr
        from repro.core.engine import make_survey_fn, survey_with_fn
        from repro.core.pushpull import plan_engine

        lay, sp, hg = self.layout, self.spans, self.hgs[k]
        bundle = surveys.make(self.names, self.params)
        with sp("plan"):
            cfg, _ = plan_engine(hg, lay["S"], bundle, mode=lay["mode"],
                                 orient=lay["orient"])
        with sp("shard"):
            gr, _ = shard_dodgr(hg, lay["S"], hub_theta=cfg.hub_theta,
                                orient=lay["orient"])
        with sp("place"):
            gr = jax.block_until_ready(jax.device_put(gr))
        fn = self.fns.get(cfg)
        if fn is None:
            fn = self.fns[cfg] = jax.jit(make_survey_fn(bundle, cfg))
        with sp("traverse"):
            out = jax.block_until_ready(fn(gr))
        with sp("finalize"):
            res, st = survey_with_fn(gr, bundle, cfg, lambda _: out)
        return res, st

    def warm(self) -> None:
        for k in range(len(self.graphs)):
            self._survey(k)

    def step(self) -> None:
        k = len(self.answers) % len(self.graphs)
        res, st = self._survey(k)
        self.answers.append((k, res, st))
        self.wedges += (st["wedges_pushed"] + st["wedges_pulled"]
                        + st["wedges_hub"])

    def result(self, window_s: float) -> dict:
        return {"survey_s": window_s / len(self.answers)}

    def close(self) -> None:
        self.fns.clear()

    def compare(self) -> list[dict]:
        refs = [reference.answers(g, self.names) for g in self.graphs]
        return [surveys.compare(r, s, refs[k]) for k, r, s in self.answers]


KINDS = {"oneshot": OneShot}
