"""Percent of the traced window in which no operation ran on the device
(averaged over the chips used)."""
from bench import trace


def read(run):
    share = trace.idle_share(run.trace)
    return None if share is None else 100.0 * share
