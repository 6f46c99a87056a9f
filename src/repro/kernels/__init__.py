"""Pallas TPU kernels for the engine's compute hot spots — the adjacency
intersection the paper identifies as "the most expensive operation in a
triangle counting kernel" (Sec. 2), in its TPU-native binary-search form
(DESIGN.md §2), plus the counting-set and ring-buffer fold scatters.

Each kernel package: <name>.py (pl.pallas_call + BlockSpec), ops.py
(jit'd wrapper with padding + interpret flag), ref.py (pure-jnp oracle).
"""
import jax


def compiled() -> bool:
    """The one Pallas backend gate. On a TPU backend kernels compile with
    Mosaic (a kernel the compiler refuses raises its error — there is no
    fallback to interpret mode) and ``backend="auto"`` folds pick them;
    elsewhere kernels run in interpret mode and ``"auto"`` folds keep the
    XLA scatter."""
    return jax.default_backend() == "tpu"
