"""Device milliseconds per survey in the counting-set fold kernel
(``fold_count_max``'s Pallas call, matched by the name the trace gives
it), summed over chips."""
from bench import trace

FOLD = r"fold_count_max"


def read(run):
    n = len(run.traffic.answers)
    if not n or not trace.op_events(run.trace, FOLD):
        return None
    return 1e3 * trace.op_seconds(run.trace, FOLD) / n
