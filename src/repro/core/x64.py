"""64-bit JAX, scoped to the simulator engines.

The engines pack int64 words, and their frozen references accumulate in
float64, so each entry point traces, compiles and runs under
``jax.enable_x64(True)``.  Nothing switches the process to 64-bit: the
model stack and the Pallas kernels compile as 32-bit programs beside them.
"""

from __future__ import annotations

import functools

import jax


def x64_scoped(fn):
    """Run ``fn`` under ``jax.enable_x64(True)``.

    A fresh context manager per call: ``jax.enable_x64(True)`` used as a
    decorator is one shared object, and a re-entrant call (a bisecting
    batch recursing into its own entry point) would overwrite its saved
    state and leave the thread in 64-bit mode."""
    @functools.wraps(fn)
    def run(*args, **kwargs):
        with jax.enable_x64(True):
            return fn(*args, **kwargs)
    return run
