"""Device milliseconds per survey under the engine scope
``engine/pull/rank``: the window of pulled edges and the rank addressing
of each staged wedge (``searchsorted`` over the wedge cumsum, the
gathers of its edge, reply row and suffix position). The self time of
its device operations, summed over chips (``bench/scopes.py``)."""
from bench import scopes


def read(run):
    return scopes.ms_per_survey(run, "pull/rank")
