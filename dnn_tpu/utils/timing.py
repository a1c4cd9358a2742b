"""Validated device timing.

The reference has no timers at all (SURVEY §5 — print logging only).
Measuring a device honestly is nontrivial: JAX returns before the device
finishes, a run's first measurements carry one-time dispatch overheads,
and the cost of one host sync dwarfs small kernels. `device_time` is the
framework's one blessed answer — every bench (bench.py, benchmarks/) uses
it so numbers are comparable.
"""

from __future__ import annotations

import time

import jax
import numpy as np


def device_time(fn, *args, n1: int = 4, n2: int = 12, trials: int = 3) -> float:
    """Per-call device wall time of `fn(*args)` via the two-point slope
    method.

    Queue N calls back-to-back, force the dependency chain with a
    1-element host read of the last output (device execution is in-order,
    so the read completes only after all N), and take
    (t(n2) - t(n1)) / (n2 - n1) so the constant sync round-trip cancels.

    Validity guards (first-measurement effects were observed to skew a
    single slope by up to 2x in either direction): warm up past compile
    AND past the first few post-compile dispatches, evaluate t(n1) before
    t(n2) in a fixed order, and report the median slope of `trials`
    repeats.
    """

    def run(n):
        out = None
        t0 = time.perf_counter()
        for _ in range(n):
            out = fn(*args)
        leaf = jax.tree.leaves(out)[0]
        np.asarray(leaf.ravel()[0])  # scalar pull -> full sync
        return time.perf_counter() - t0

    run(2)  # compile
    run(n1)  # absorb post-compile first-dispatch overhead
    slopes = []
    for _ in range(trials):
        t1 = run(n1)
        t2 = run(n2)
        slopes.append((t2 - t1) / (n2 - n1))
    slopes.sort()
    return slopes[len(slopes) // 2]
