"""Whole runs of a cell on the CPU at a tiny size, for the tests."""
import jax

from bench import run

SEED = 2**31 + 3


def tiny(cell: str, graphs: int = 1) -> dict:
    """The cell at R-MAT scale 7 on the first ``graphs`` of its graphs."""
    spec = run.load_cell(cell)
    graph = spec["config"]["graph"]
    graph.update(scale=7, structure_seeds=graph["structure_seeds"][:graphs])
    return spec


def run_tiny(cell: str) -> dict:
    """The result line of a run that skips the look for a chip."""
    return run.run_cell(tiny(cell), SEED, 0.01, False, jax.devices())


def alter(result: dict, name: str) -> dict:
    """The answer with member ``name``'s count, or its label table's
    collided total, one off."""
    if name == "TriangleCount":
        result[name] += 1
    else:
        lab = result[name]
        result[name] = dict(lab,
                            count_in_collided=lab["count_in_collided"] + 1)
    return result
