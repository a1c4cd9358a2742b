"""What the mixers that keep a STATE a slot share (models/kda.py, models/
retention.py, models/mamba2.py): the zeros of a kind's `slot_leaves`, and
the short causal depthwise convolution that carries its last rows — the
TAIL — from one call to the next.

A kind's `slot_leaves` (runtime/paged_kvcache.py's module docstring) is
name -> (the shape a slot a layer, dtype or None for the cache's): the ONE
place a state leaf's shape is said; the pool (`init_paged_cache(kinds=)`),
a family's transient row and a dense forward's empty state are all made
from it.
"""

from __future__ import annotations

import jax.numpy as jnp
from jax import lax


def fresh(slot_leaves, batch, tail_dtype=None, layers=None):
    """Zeros of `slot_leaves` for `batch` slots — a leaf whose dtype is
    None in `tail_dtype` — with a leading layer axis where `layers` is
    given."""
    lead = (batch,) if layers is None else (layers, batch)
    return {name: jnp.zeros((*lead, *shape), dtype or tail_dtype)
            for name, (shape, dtype) in slot_leaves.items()}


def _rows(tail, pre):
    return jnp.concatenate([tail.astype(pre.dtype), pre], axis=1)


def conv_chunk(tail, pre, taps, n_real):
    """The convolution over a chunk: `pre` (B, T, W) the un-convolved rows
    whose first `n_real` are real, `tail` (B, n - 1, W) the rows before
    them, `taps` (n, W) float32 or a function that makes them (y_t = sum_j
    taps_j x_{t - n + 1 + j}) -> (y (B, T, W) float32, the last n - 1 REAL
    rows: the next call's tail, in `pre`'s dtype)."""
    t = pre.shape[1]
    rows = _rows(tail, pre)
    if callable(taps):
        taps = taps()
    n = taps.shape[0]
    y = sum(taps[j] * rows[:, j:j + t].astype(jnp.float32) for j in range(n))
    # the last n - 1 real rows: rows [n_real, n_real + n - 1)
    return y, lax.dynamic_slice_in_dim(rows, n_real, n - 1, axis=1)


def conv_step(tail, pre, taps):
    """The convolution for one position a slot: `pre` (B, 1, W), `tail` (B,
    n - 1, W), `taps` as `conv_chunk`'s -> (y (B, 1, W) float32, the n rows
    it read: all but the first are the next call's tail)."""
    rows = _rows(tail, pre)
    if callable(taps):
        taps = taps()
    return (taps * rows.astype(jnp.float32)).sum(1, keepdims=True), rows
