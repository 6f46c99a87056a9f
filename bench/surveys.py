"""The program's survey objects for the names a configuration lists, and
the program's answers read back into the reference's form."""
from __future__ import annotations


def make(names, params: dict):
    """A ``SurveyBundle`` of the named surveys, each built with the
    configuration's parameters for it, members named as listed."""
    from repro.core import surveys as sv

    kinds = {"TriangleCount": sv.TriangleCount,
             "LabelTripleSet": sv.LabelTripleSet}
    names = list(names)
    return sv.SurveyBundle([kinds[n](**params.get(n, {})) for n in names],
                           names=names)


def answer(name: str, got, ref) -> dict:
    """Numbers comparing one survey's answer ``got`` with the reference's
    ``ref``; each must be 0 for an exact answer."""
    if name == "TriangleCount":
        return {"count_gap": abs(int(got) - int(ref))}
    if name == "LabelTripleSet":
        # a key read back outside a collided slot carries its exact count;
        # the collided slots hold exactly the rest of the mass
        counts = got["counts"]
        gap = sum(abs(c - ref.get(k, 0)) for k, c in counts.items())
        rest = sum(c for k, c in ref.items() if k not in counts)
        gap += abs(int(got["count_in_collided"]) - rest)
        return {"label_gap": gap}
    raise ValueError(f"no comparison for survey {name!r}")


def compare(result: dict, stats: dict, ref: dict) -> dict:
    """All numbers for one bundle answer: one per member plus whether the
    program flagged its run inexact."""
    out = {"inexact": 0 if stats.get("exact", True) else 1}
    for name, got in result.items():
        out.update(answer(name, got, ref[name]))
    return out
