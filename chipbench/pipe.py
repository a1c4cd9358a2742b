"""The `batch_forward` traffic kind: token-id batches through the engine's
pipeline forward, back to back, each call ending in `block_until_ready`.
Everything runs in this one process, which holds all the chips."""

from __future__ import annotations

import json
import os
import time

import numpy as np

from chipbench import check, traffic as tg


def run(cell, *, seed, seconds, trace, rehearse, workdir, emit):
    import jax

    from dnn_tpu.config import TopologyConfig
    from dnn_tpu.runtime.engine import PipelineEngine
    from dnn_tpu.utils.compile_cache import enable_compile_cache

    config, traffic = cell["config"], cell["traffic"]
    if traffic["kind"] != "batch_forward":
        raise SystemExit(f"traffic kind {traffic['kind']!r} is not one the "
                         "pipeline driver runs")
    runcfg = config["run"]
    stages = runcfg["stages"]
    t_start = time.perf_counter()
    enable_compile_cache()
    device = check.device_info(cell["cell"]["chips"], rehearse=rehearse)
    if device["count"] < stages:
        raise RuntimeError(f"{stages} stages need {stages} devices; JAX "
                           f"found {device['count']}")
    topo = {"nodes": [{"id": f"node{i + 1}", "part_index": i,
                       "address": "127.0.0.1:0"} for i in range(stages)],
            "num_parts": stages, "model": runcfg["model"],
            "dtype": runcfg["dtype"], "runtime": runcfg["runtime"],
            "microbatches": traffic["microbatches"]}
    if runcfg.get("device_type") is not None:
        topo["device_type"] = runcfg["device_type"]
    path = os.path.join(workdir, "engine_config.json")
    with open(path, "w") as f:
        json.dump(topo, f)
    engine = PipelineEngine(TopologyConfig.from_json(path), role="full",
                            rng_seed=seed)
    if engine.runtime != runcfg["runtime"]:
        raise RuntimeError(f"engine runtime is {engine.runtime}")
    t_engine = time.perf_counter()
    batches = tg.make_batches(traffic, seed, config["vocab_size"])
    batch, seq = batches[0].shape
    mb = engine._effective_microbatches(batch)
    if mb != traffic["microbatches"]:
        raise RuntimeError(f"engine runs {mb} microbatches, the traffic "
                           f"file says {traffic['microbatches']}")
    # warm-up: the one shape of this cell, twice (compile, then steady)
    first = engine.run(batches[0]).block_until_ready()
    engine.run(batches[-1]).block_until_ready()
    kept = np.asarray(first[: traffic["check_rows"]])
    del first

    compiles = []  # backend compilations from here on, by JAX's own events
    jax.monitoring.register_event_duration_secs_listener(
        lambda event, duration, **kw: compiles.append(event)
        if event == "/jax/core/compile/backend_compile_duration" else None)
    trace_dir = os.path.join(workdir, "trace")
    trace_after = int(traffic["trace_after_batches"]) if trace else None
    trace_len = int(traffic["trace_batches"])
    n, traced, tracing, untraced = 0, None, False, None
    t0 = time.perf_counter()
    while True:
        if trace and n == trace_after:
            untraced = (n, time.perf_counter() - t0)
            jax.profiler.start_trace(trace_dir)
            tracing = True
        engine.run(batches[n % len(batches)]).block_until_ready()
        n += 1
        if tracing and n == trace_after + trace_len:
            jax.profiler.stop_trace()
            tracing, traced = False, trace_dir
        elapsed = time.perf_counter() - t0
        if elapsed >= seconds and not tracing:
            break
    # a traced run's rate is taken before the profiler starts: starting it
    # and writing the trace out stall the loop for seconds
    rate_n, rate_s = untraced if untraced else (n, elapsed)
    if compiles:
        raise RuntimeError(f"{len(compiles)} compilations inside the "
                           "measured window; want 0")
    peak = None
    stats = [d.memory_stats() for d in jax.local_devices()]
    if all(s and "peak_bytes_in_use" in s for s in stats):
        peak = max(s["peak_bytes_in_use"] for s in stats)
    emit(phase="window", kind="batch_forward", batches=n, seconds=elapsed,
         engine_ready_s=t_engine - t_start, warm_up_s=t0 - t_engine,
         batch=[batch, seq], microbatches=mb, compilations_in_window=0)

    # correct: rows of the first batch against the plain reference
    # (on the engine's own seeded weights, which it keeps on the host)
    params = jax.device_put(engine.params, jax.local_devices()[0])
    res = check.logits_diff(config["reference"], engine.spec.config, params,
                            batches[0][: traffic["check_rows"]], kept)
    tol = config["check"]["logits_tol"]
    emit(phase="check", **res, logits_tol=tol)
    return {
        "client": {"tok_s": rate_n * batch * seq / rate_s,
                   "ms_per_batch": 1e3 * rate_s / rate_n},
        "config": config, "traffic": traffic, "memory_peak_bytes": peak,
        "trace_capture": traced, "attempted": n, "failed": 0,
        "device": device, "correct": res["max_abs_diff"] <= tol, "t0": t0,
        "compared": {"max_abs_diff": {"value": res["max_abs_diff"],
                                      "limit": tol, "passes": "at most"}},
    }
