"""Percent of the wedge slots the engine's pull lane staged that held a
wedge: the window's answers' ``wedges_pulled`` over their
``pull_tile_slots`` (n_tiles × tile × shards per pull superstep, the
program's own counters). The tiles are device work, so like the other
engine metrics it reads nothing from a trace that saw no device; and
nothing where the program counts no staged slots."""


def read(run):
    if not run.trace.devices:
        return None
    answers = run.traffic.answers
    slots = sum(st.get("pull_tile_slots", 0) for _, _, st in answers)
    if not slots:
        return None
    return 100.0 * sum(st["wedges_pulled"] for _, _, st in answers) / slots
