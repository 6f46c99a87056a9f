"""Device milliseconds per survey in the engine's push lane: the self
time of the device operations under ``engine/push/`` (the wire half and
the owner's check), summed over chips (``bench/scopes.py``)."""
from bench import scopes


def read(run):
    return scopes.ms_per_survey(run, "push/wire", "push/check")
