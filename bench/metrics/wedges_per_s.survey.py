"""Wedges the engine checked (pushed + pulled + hub, the engine's own
counters) per second of device busy time in the traced window."""
from bench import trace


def read(run):
    busy = trace.busy_s(run.trace)
    return run.traffic.wedges / busy if busy > 0 else None
