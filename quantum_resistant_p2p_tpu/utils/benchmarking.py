"""Device timing helpers.

Every timed region ends in ``jax.block_until_ready`` on its outputs: JAX
returns before the device finishes, so a timing without the fence measures
the enqueue.  Benchmarks time ``reps`` back-to-back dispatches followed by
one fence, so per-dispatch overhead pipelines the way it does in production
(the batching queue also issues back-to-back batches).
"""

from __future__ import annotations

import time
from typing import Any, Callable

import jax


def sync(tree: Any) -> None:
    """Block until every array in ``tree`` is computed on its device."""
    jax.block_until_ready(tree)


def timeit(fn: Callable, *args, min_time_s: float = 1.5, trials: int = 2) -> float:
    """Best-of-``trials`` mean seconds per call of ``fn(*args)``.

    The first call (compile + warm-up) is excluded.  Each trial times ``reps``
    back-to-back dispatches ending in one fence; ``reps`` is grown until a
    trial takes at least ``min_time_s``, so the fixed cost of the last
    dispatch's round trip is amortised over many calls.
    """
    sync(fn(*args))  # compile + warm caches

    def trial(reps: int) -> float:
        t0 = time.perf_counter()
        out = None
        for _ in range(reps):
            out = fn(*args)
        sync(out)
        return time.perf_counter() - t0

    reps = 1
    total = trial(reps)
    while total < min_time_s:
        reps = max(reps * 2, int(reps * min_time_s / max(total, 1e-6)) + 1)
        total = trial(reps)
    best = total
    for _ in range(trials - 1):
        best = min(best, trial(reps))
    return best / reps
