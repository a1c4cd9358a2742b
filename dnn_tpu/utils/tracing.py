"""Profiling / tracing spans — DEPRECATED shim over dnn_tpu.obs.profile.

This module predates the obs layer (dnn_tpu/obs); its profiler-span API
grew a duplicate in PR 3 and is now unified: `span` / `step_span` are
re-exports of `obs.profile.annotation` / `step_annotation`, which means
they RESPECT THE DNN_TPU_OBS GATE (the orphaned originals annotated even
with observability off). Existing callers keep working unchanged; new
code should import from `dnn_tpu.obs.profile`, and full captures should
go through `obs.profile.capture` / POST /profilez rather than the bare
`trace_to` kept here for compatibility.

`device_sync` / `timed_blocked` are NOT spans — they are the honest
device-completion barrier `PipelineEngine.benchmark` is built on — and live on
here as this module's real content.
"""

from __future__ import annotations

import contextlib
import time
from typing import Iterator

import jax

from dnn_tpu.obs.profile import (  # noqa: F401 — deprecated re-exports
    annotation as span,
    step_annotation as step_span,
)


@contextlib.contextmanager
def trace_to(log_dir: str) -> Iterator[None]:
    """Capture a full profile (host + device) into `log_dir` for
    TensorBoard / Perfetto. Deprecated: prefer obs.profile.capture
    (bounded spool, busy-locking, flight-logged) for server use."""
    from dnn_tpu.obs import profile as _profile

    jax.profiler.start_trace(log_dir)
    try:
        # the deprecated `span` shim only annotates while a capture is
        # marked recording (annotation_ctx's hot-path gate) — mark this
        # legacy capture too, or trace_to + span silently loses spans
        with _profile.mark_recording():
            yield
    finally:
        jax.profiler.stop_trace()


def device_sync(out) -> None:
    """Force completion of all device work `out` depends on.

    JAX returns before the device finishes, so timing without a barrier
    measures dispatch only. The barrier here is a 1-element host read per
    device: execution is in-order, so the read completes only after
    everything queued before it — the same guarantee as
    `jax.block_until_ready`, at a constant cost.
    """
    import numpy as np

    # Per-device queues are independent, so the barrier must touch every
    # device `out` lives on — one 1-element read per device (any array on
    # that device works: the read completes only after all work enqueued
    # before it on that device's in-order queue).
    per_device = {}
    for leaf in jax.tree.leaves(out):
        shards = getattr(leaf, "addressable_shards", None)
        if shards:
            for s in shards:
                per_device[s.device] = s.data
    if per_device:
        for data in per_device.values():
            np.asarray(data.ravel()[0] if data.size else data)
    else:  # no jax array leaves
        jax.block_until_ready(out)


def timed_blocked(fn, *args) -> tuple:
    """Run `fn(*args)`, force device completion (`device_sync`), return
    (result, seconds). The honest way to time jit'd code — timing dispatch
    alone measures nothing (SURVEY §7 hard part 4)."""
    t0 = time.perf_counter()
    out = fn(*args)
    device_sync(out)
    return out, time.perf_counter() - t0
