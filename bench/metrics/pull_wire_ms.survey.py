"""Device milliseconds per survey under the engine scope
``engine/pull/wire``: the pull requests, the owners' padded reply rows
and both exchanges. The self time of its device operations, summed over
chips (``bench/scopes.py``)."""
from bench import scopes


def read(run):
    return scopes.ms_per_survey(run, "pull/wire")
