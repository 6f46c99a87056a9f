"""Small shared utilities: hashing, padding, integer helpers.

Device-side code uses int32 ids and uint32 hashes throughout (x64 stays
disabled). The splitmix-style mixer below is the deterministic tie-break
``hash(u)`` from the paper (Sec. 3), identical on host (numpy) and device
(jnp) so DODGr orientation agrees everywhere.
"""
from __future__ import annotations

import os
from pathlib import Path

import numpy as np
import jax.numpy as jnp

__all__ = [
    "splitmix32",
    "splitmix32_np",
    "key_less",
    "key_less_eq",
    "ceil_div",
    "pad_to",
    "pad_axis_to",
    "bucket_cap",
    "bucket_caps",
    "enable_compile_cache",
    "span",
]


# geometric shape-bucket grid: within each power-of-two octave [2^k, 2^(k+1))
# the rungs approximate ceil(2^k · 2^(j/4)), j = 0..3, as exact integer
# fractions so the grid is identical on every host. Every power of two is
# an anchor and successive rungs are an even ~19% apart — deliberately NOT
# ×1.25 steps, whose fourth rung (1.25³ ≈ 1.953) sits 2.4% under the next
# anchor and turns tiny epoch-to-epoch jitter into rung flips. Bucketed
# capacities drift through four values per octave instead of one per
# integer, with worst-case round-up < 20%.
_BUCKET_RUNGS = ((1, 1), (19, 16), (45, 32), (27, 16))


def bucket_cap(x: int) -> int:
    """Round a shape-determining capacity up to the bucket grid.

    The smallest grid value ≥ ``x``, where the grid is
    ``ceil(2^k · 2^(j/4))`` for ``k ≥ 0, j ∈ {0..3}`` (integer-fraction
    rungs, see ``_BUCKET_RUNGS``). 0 and 1 are their own buckets; the
    function is idempotent (grid values map to themselves) and monotone —
    the two properties the bucketing conservation pass
    (:mod:`repro.analysis.conservation`) re-verifies on every stamped
    ``cap_policy="bucket"`` plan."""
    x = int(x)
    if x <= 1:
        return max(x, 0)
    k = x.bit_length() - 1
    if (1 << k) == x:
        return x
    for kk in (k, k + 1):
        base = 1 << kk
        for num, den in _BUCKET_RUNGS:
            v = -(-base * num // den)
            if v >= x:
                return v
    raise AssertionError(f"bucket grid has no rung >= {x}")  # unreachable


def bucket_floor(x: int) -> int:
    """Largest bucket-grid value ≤ ``x`` — the round-*down* twin of
    :func:`bucket_cap`, for quantizing an upper *bound* (e.g. the pull
    autotuner's reply-window byte budget) so that clipping a cap against
    it yields an on-grid value that still respects the bound. Idempotent
    and monotone like :func:`bucket_cap`; 0 and 1 map to themselves."""
    x = int(x)
    if x <= 1:
        return max(x, 0)
    k = x.bit_length() - 1
    best = 1 << k                     # the anchor below x is always on-grid
    for num, den in _BUCKET_RUNGS:
        v = -(-(1 << k) * num // den)
        if v <= x:
            best = max(best, v)
    return best


def bucket_caps(a: "np.ndarray") -> "np.ndarray":
    """Elementwise :func:`bucket_cap` over an integer array (host-side)."""
    flat = np.asarray(a, np.int64).ravel()
    return np.array([bucket_cap(int(x)) for x in flat],
                    np.int64).reshape(np.shape(a))


def _mix(x, xp):
    # xor-shift / multiply mixer (finalizer of MurmurHash3 / splitmix).
    x = x.astype(xp.uint32)
    x = (x ^ (x >> xp.uint32(16))) * xp.uint32(0x7FEB352D)
    x = (x ^ (x >> xp.uint32(15))) * xp.uint32(0x846CA68B)
    x = x ^ (x >> xp.uint32(16))
    return x


def splitmix32(x: jnp.ndarray) -> jnp.ndarray:
    """Deterministic 32-bit mixer (device)."""
    return _mix(x, jnp)


def splitmix32_np(x: np.ndarray) -> np.ndarray:
    """Deterministic 32-bit mixer (host); bit-identical to :func:`splitmix32`."""
    with np.errstate(over="ignore"):
        return _mix(np.asarray(x), np)


def key_less(d1, h1, i1, d2, h2, i2):
    """Lexicographic `(degree, hash, id) <` — the paper's ``<₊`` total order.

    The id component makes the order total even under hash collisions.
    Works on numpy or jnp arrays (broadcasting).
    """
    return (
        (d1 < d2)
        | ((d1 == d2) & (h1 < h2))
        | ((d1 == d2) & (h1 == h2) & (i1 < i2))
    )


def key_less_eq(d1, h1, i1, d2, h2, i2):
    return key_less(d1, h1, i1, d2, h2, i2) | ((d1 == d2) & (h1 == h2) & (i1 == i2))


def ceil_div(a: int, b: int) -> int:
    return -(-a // b)


def pad_to(x: np.ndarray, n: int, fill=0) -> np.ndarray:
    """Pad 1-D array to length ``n`` with ``fill``."""
    if x.shape[0] > n:
        raise ValueError(f"cannot pad length {x.shape[0]} down to {n}")
    out = np.full((n,) + x.shape[1:], fill, dtype=x.dtype)
    out[: x.shape[0]] = x
    return out


def pad_axis_to(x: np.ndarray, axis: int, n: int, fill=0) -> np.ndarray:
    if x.shape[axis] > n:
        raise ValueError(f"cannot pad axis {axis} of {x.shape} to {n}")
    pad = [(0, 0)] * x.ndim
    pad[axis] = (0, n - x.shape[axis])
    return np.pad(x, pad, constant_values=fill)


# JAX's persistent compilation cache lives at a fixed path inside the
# checkout unless JAX_COMPILATION_CACHE_DIR names one: the path is part of
# every entry's key, so a directory that moved (a temporary name, a pid, a
# time) would never hit.
COMPILE_CACHE_ENV = "JAX_COMPILATION_CACHE_DIR"
CHECKOUT_COMPILE_CACHE = (Path(__file__).resolve().parents[2]
                          / ".jax_cache")


def enable_compile_cache() -> Path:
    """Turn on JAX's on-disk compilation cache and return its directory.

    With ``JAX_COMPILATION_CACHE_DIR`` set, JAX already uses that
    directory and nothing is changed; otherwise the cache goes to the
    fixed ``<checkout>/.jax_cache``. Entry points call this once at start
    (``chip_smoke.py``, :class:`~repro.serve.SurveyService`), so a
    restarted process re-reads what an earlier one compiled."""
    import jax

    env = os.environ.get(COMPILE_CACHE_ENV)
    if env:
        return Path(env)
    if jax.config.jax_compilation_cache_dir != str(CHECKOUT_COMPILE_CACHE):
        jax.config.update("jax_compilation_cache_dir",
                          str(CHECKOUT_COMPILE_CACHE))
    return CHECKOUT_COMPILE_CACHE


def span(name: str):
    """``with span(name):`` marks a host phase of the program as the span
    ``repro.<name>`` in the profiler's trace (a
    ``jax.profiler.TraceAnnotation``, on the device trace's clock). Spans
    opened inside it nest under it on the thread. With no profiler
    running it costs one inactive check."""
    import jax

    return jax.profiler.TraceAnnotation(f"repro.{name}")
