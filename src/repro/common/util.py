"""Small shared utilities used across the framework.

Nothing in here touches jax device state at import time — important because
launch/dryrun.py must be able to set XLA_FLAGS before any jax initialization.
"""
from __future__ import annotations

import logging
import sys
from typing import Any, Iterable

import jax
import numpy as np

from repro.runtime.telemetry import clock


def ceil_div(a: int, b: int) -> int:
    return -(-a // b)


def next_multiple(x: int, m: int) -> int:
    """Smallest multiple of ``m`` that is >= ``x`` (and >= m)."""
    return max(m, ceil_div(x, m) * m)


def pad_to_multiple(arr: np.ndarray, multiple: int, axis: int = 0, fill: Any = 0) -> np.ndarray:
    """Pad ``arr`` along ``axis`` so its length is a multiple of ``multiple``."""
    n = arr.shape[axis]
    target = next_multiple(n, multiple)
    return pad_axis_to(arr, target, axis=axis, fill=fill)


def pad_axis_to(arr: np.ndarray, target: int, axis: int = 0, fill: Any = 0) -> np.ndarray:
    """Pad ``arr`` along ``axis`` with ``fill`` up to length ``target``."""
    n = arr.shape[axis]
    if n == target:
        return arr
    if n > target:
        raise ValueError(f"cannot pad axis {axis} of length {n} down to {target}")
    widths = [(0, 0)] * arr.ndim
    widths[axis] = (0, target - n)
    return np.pad(arr, widths, mode="constant", constant_values=fill)


def tree_size_bytes(tree: Any) -> int:
    """Total bytes of all arrays in a pytree (works on ShapeDtypeStruct too)."""
    leaves = jax.tree_util.tree_leaves(tree)
    total = 0
    for leaf in leaves:
        shape = getattr(leaf, "shape", ())
        dtype = np.dtype(getattr(leaf, "dtype", np.float32))
        total += int(np.prod(shape, dtype=np.int64)) * dtype.itemsize
    return total


def tree_num_params(tree: Any) -> int:
    leaves = jax.tree_util.tree_leaves(tree)
    return sum(int(np.prod(getattr(l, "shape", ()), dtype=np.int64)) for l in leaves)


def checkout_root() -> str:
    """Root of the checkout this package runs from (``src/..``)."""
    import os

    return os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.dirname(os.path.abspath(__file__)))))


def bench_engine_path() -> str:
    """Repo-root ``BENCH_engine.json`` — the ONE location the engine bench
    writes and the serve sync-budget check reads (both must agree)."""
    import os

    return os.path.join(checkout_root(), "BENCH_engine.json")


def enable_compile_cache() -> str:
    """Turn on JAX's persistent compilation cache; entry points call this
    once, before their first compile (never at import). Returns the cache
    directory.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it itself and
    nothing else is set here. Otherwise the cache goes to ``.jax_cache`` in
    the checkout: a fixed path, because the directory is part of the cache
    key and a path that moves never hits.
    """
    import os

    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    path = os.path.join(checkout_root(), ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path


class Timer:
    """Context-manager wall timer. ``with Timer() as t: ...; t.seconds``.

    Reads time through ``telemetry.clock()`` — the one determinism-lint
    sanctioned clock seam — so every Timer site is covered without a
    per-site ``# det:`` pragma."""

    def __enter__(self) -> "Timer":
        self._t0 = clock()
        self.seconds = 0.0
        return self

    def __exit__(self, *exc) -> None:
        self.seconds = clock() - self._t0


_LOGGERS: dict[str, logging.Logger] = {}


def get_logger(name: str = "repro") -> logging.Logger:
    if name in _LOGGERS:
        return _LOGGERS[name]
    logger = logging.getLogger(name)
    if not logger.handlers:
        handler = logging.StreamHandler(sys.stderr)
        handler.setFormatter(logging.Formatter("[%(asctime)s %(name)s] %(message)s", "%H:%M:%S"))
        logger.addHandler(handler)
        logger.setLevel(logging.INFO)
        logger.propagate = False
    _LOGGERS[name] = logger
    return logger


def batched(iterable: Iterable, n: int):
    """Yield lists of up to ``n`` items."""
    buf = []
    for item in iterable:
        buf.append(item)
        if len(buf) == n:
            yield buf
            buf = []
    if buf:
        yield buf
