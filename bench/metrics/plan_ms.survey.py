"""Host milliseconds per survey spent planning, sharding and placing the
graph (``plan_engine`` + ``shard_dodgr`` + ``device_put``), from the
benchmark's own spans."""


def read(run):
    n = len(run.traffic.answers)
    tot = sum(sum(run.spans.in_window(k)) for k in ("plan", "shard", "place"))
    return 1e3 * tot / n if n else None
