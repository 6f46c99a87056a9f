"""Device milliseconds per survey under the engine scope
``engine/pull/search``: the key's binary search in the pulled row, the
hit test and the gathers at the found slot. The self time of its device
operations, summed over chips (``bench/scopes.py``)."""
from bench import scopes


def read(run):
    return scopes.ms_per_survey(run, "pull/search")
