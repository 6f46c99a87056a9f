"""Percent of the chip's roofline that the fold kernel reaches: the work
a counting-set fold requires (``peaks.fold_count_max_work``, read from
each call's operand shapes in the trace) done at peak, over the kernel's
device time. The required work counts each entry and the table once, not
the one-hot batch x slots the kernel computes, so it reads the same
whatever implements the fold."""
import re

from bench import peaks, trace

FOLD = r"fold_count_max"


def shapes(op: str) -> dict:
    """Batch, table slots and key words of one kernel call: its operands
    are the slot ids [B, 1], amounts [B, 1] and key rows [B, W]; its first
    result is the count table [1, capacity]."""
    result, _, args = op.partition("custom-call(")
    ops = [tuple(map(int, d.split(","))) for d in
           re.findall(r"[su]32\[([0-9,]+)\]", args)[:3]]
    cap = re.search(r"[su]32\[1,([0-9]+)\]", result)
    if len(ops) != 3 or cap is None:
        raise ValueError(f"unexpected fold kernel operands: {op[:300]}")
    return dict(batch=ops[0][0], capacity=int(cap.group(1)),
                width=ops[2][1])


def read(run):
    evs = trace.op_events(run.trace, FOLD)
    if not evs:
        return None
    ops = bytes_ = 0
    for name, _, _ in evs:
        o, b = peaks.fold_count_max_work(**shapes(name))
        ops, bytes_ = ops + o, bytes_ + b
    seconds = sum(d for _, _, d in evs) * 1e-9
    return peaks.roofline_share(ops, bytes_, seconds, run.device_kind)
