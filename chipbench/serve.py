"""Serving cells: the LM daemon as a child process on the chip, driven over
gRPC by this process, which stays off JAX until the child has exited.

Order of a run: spawn the daemon -> warm-up (the configuration's check
requests, sent together: they compile the daemon's programs and their
tokens are kept for `correct`) -> the traffic, with its window -> scrape,
stop the daemon -> take the chip here for the reference check, of the
warm-up requests and of a sample of the window's own streams (the longest
context among them), so that `correct` covers the cell's lengths and its
occupancy and not only the quiet daemon before the traffic.
"""

from __future__ import annotations

import os
import threading
import time

from chipbench import cells, stats, traffic as tg
from chipbench.daemon import Daemon

READY_DEADLINE_S = 1100.0  # a checkout's first run compiles


def _warm_and_collect(client, prompts, max_new):
    """The check requests, all in flight together; their tokens."""
    out, errors = [None] * len(prompts), []

    def one(i):
        try:
            out[i] = list(client.generate_stream(
                prompts[i], max_new_tokens=max_new, timeout=READY_DEADLINE_S))
        except Exception as e:  # noqa: BLE001 — re-raised below
            errors.append(repr(e))

    threads = [threading.Thread(target=one, args=(i,))
               for i in range(len(prompts))]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    if errors or any(len(t or ()) != max_new for t in out):
        raise RuntimeError(f"warm-up requests failed: {errors or out}")
    return out


def pick_streams(sent, t0, t1, n):
    """Up to `n` of the window's streams for the reference check: the one
    holding the most positions (prompt + streamed tokens), then the rest
    spread evenly over the order of submission. A stream qualifies with
    two or more tokens, one of them arrived in [t0, t1); it need not be
    complete, since every served token is checked on its own."""
    live = [s for s in sent if not s.error and len(s.tokens) >= 2
            and any(t0 <= t < t1 for t in s.times)]
    if not live or n < 1:
        return []
    first = max(live, key=lambda s: (s.req.prompt_len + len(s.tokens),
                                     -s.req.index))
    rest = [s for s in live if s is not first]
    k = min(n - 1, len(rest))
    return [first] + [rest[(2 * i + 1) * len(rest) // (2 * k)]
                      for i in range(k)]


def _profile(daemon, delay_s, ms, box):
    """POST /profilez after `delay_s`; the capture directory lands in box."""
    time.sleep(delay_s)
    try:
        box["capture"] = daemon.get_json(
            f"/profilez?ms={int(ms)}", method="POST",
            timeout=ms / 1e3 + 300)["capture"]
    except Exception as e:  # noqa: BLE001 — reported, the run goes on
        box["error"] = repr(e)


def run(cell, *, seed, seconds, trace, rehearse, workdir, emit):
    """One run of a serving cell; returns the facts run.py turns into
    metrics (client statistics, scraped counters, the check requests)."""
    from dnn_tpu.comm.client import NodeClient

    config, traffic = cell["config"], cell["traffic"]
    runcfg = config["run"]
    vocab = config["vocab_size"]
    daemon = Daemon(
        repo=os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
        workdir=workdir, model=runcfg["model"], dtype=runcfg["dtype"],
        device_type=runcfg.get("device_type"), seed=seed,
        serve_flags=runcfg["serve_flags"],
        env_extra={"DNN_TPU_OBS_DIR": os.path.join(workdir, "obs")})
    chk = config["check"]
    check_prompts = [tg.prompt_ids(seed, 10_000_000 + i, n, vocab)
                     for i, n in enumerate(chk["prompt_lens"])]
    client = None
    prof_box, prof_thread = {}, None
    t_spawn = time.perf_counter()
    daemon.spawn()
    try:
        client = NodeClient(daemon.addr, breaker=False)
        daemon.wait_ready(client, READY_DEADLINE_S)
        t_ready = time.perf_counter()
        check_tokens = _warm_and_collect(client, check_prompts, chk["max_new"])
        t_warm = time.perf_counter()

        gen = cells.named(traffic["generator"], "loadgen").from_traffic(
            client, traffic, seed=seed, vocab=vocab, seconds=seconds)
        gen.start()
        t0 = gen.window_start()
        t1 = t0 + seconds
        metrics0 = daemon.metrics()
        if trace:
            prof_thread = threading.Thread(
                target=_profile, daemon=True,
                args=(daemon, 0.35 * seconds, runcfg["trace_ms"], prof_box))
            prof_thread.start()
        time.sleep(max(0.0, t1 - time.perf_counter()))
        metrics1 = daemon.metrics()

        # open loop: requests due inside the window are owed a first token
        unanswered = gen.unanswered(t0, t1)
        if prof_thread is not None:
            prof_thread.join(runcfg["trace_ms"] / 1e3 + 320)
        gen.stop.set()
        client.close()  # ends every stream still open
        client = None
        gen.finish()
        rc = daemon.stop()
    finally:
        if client is not None:
            client.close()
        if daemon.proc is not None and daemon.proc.poll() is None:
            daemon.proc.kill()
            daemon.proc.wait(timeout=30)
    if rc != 0:
        raise RuntimeError(f"daemon drained with rc={rc}, want 0:\n"
                           f"{daemon.log_tail()}")

    compiles = (metrics1.get("jax_compilations_total", 0.0)
                - metrics0.get("jax_compilations_total", 0.0))
    if "jax_compilations_total" not in metrics1 or compiles != 0:
        raise RuntimeError(
            f"{compiles:.0f} compilations inside the measured window "
            "(jax_compilations_total on /metrics); want 0 and the counter")

    sent = gen.snapshot()
    errors = [s for s in sent if s.error]
    times = [s.times for s in sent]
    gaps = stats.gaps_in_window(times, t0, t1)
    ttfts = stats.ttfts_in_window(((s.t0, s.times) for s in sent), t0, t1)
    n_tok = stats.tokens_in_window(times, t0, t1)
    live = stats.mean_live_positions(
        ((s.req.prompt_len, s.times) for s in sent), t0, t1)
    client_stats = {
        "tok_s": n_tok / seconds,
        "ttft_p50_ms": _ms(stats.percentile(ttfts, 50)),
        "ttft_p95_ms": _ms(stats.percentile(ttfts, 95)),
        "itl_p50_ms": _ms(stats.percentile(gaps, 50)),
        "itl_p95_ms": _ms(stats.percentile(gaps, 95)),
        "mean_live_positions": live,
        "mean_live_requests": sum(
            max(0.0, min(s.times[-1], t1) - max(s.times[0], t0))
            for s in sent if len(s.times) > 1) / seconds,
    }
    window = {
        "phase": "window", "kind": traffic["kind"], "seconds": seconds,
        "spawn_to_ready_s": t_ready - t_spawn, "warm_up_s": t_warm - t_ready,
        "warm_to_window_s": t0 - t_warm,
        "requests_sent": len(sent),
        "requests_first_token_in_window": len(ttfts),
        "requests_completed": sum(s.done for s in sent),
        "tokens_in_window": n_tok, "gaps_in_window": len(gaps),
        "ttft_samples": len(ttfts), "unanswered": unanswered,
        "errors": [s.error for s in errors][:3],
        "compilations_in_window": compiles,
        "mean_live_positions": live,
    }
    window["longest_silence_s"], window["longest_silence_at_s"] = \
        stats.longest_silence(times, t0, t1)
    if not rehearse:
        # every client statistic, whichever of them the cell reports
        window["client"] = client_stats
    window.update(gen.report())
    picked = pick_streams(sent, t0, t1, int(chk["window_streams"]))
    peak = max((v for k, v in metrics1.items()
                if k.startswith("dnn_tpu_device_peak_bytes_in_use")),
               default=None)
    facts = {
        "client": client_stats, "metrics0": metrics0,
        "metrics1": metrics1, "config": config, "traffic": traffic,
        "memory_peak_bytes": peak, "trace_capture": prof_box.get("capture"),
        "trace_error": prof_box.get("error"),
        "attempted": len(sent), "failed": len(errors) + unanswered,
        "t0": t0,
        "check": {"prompts": check_prompts + [s.req.prompt for s in picked],
                  "tokens": check_tokens + [list(s.tokens) for s in picked],
                  "window_streams": len(picked)},
    }
    emit(**window)
    return facts


def _ms(x):
    return None if x is None else 1e3 * x


def check_served(facts, *, seed, emit, margins=None) -> bool:
    """After the daemon has exited: the served check tokens against the
    plain reference on the same weights. `margins` is
    `check.served_margins` or a driver's own form of it (`serve_rows`).
    Leaves each number compared, beside its limit, in `facts["compared"]`."""
    from chipbench import check

    config = facts["config"]
    t = time.perf_counter()
    cfg, params = check.init_params(config["run"]["model"], seed)
    t_init = time.perf_counter() - t
    res = (margins or check.served_margins)(
        config["reference"], cfg, params, facts["check"]["prompts"],
        facts["check"]["tokens"])
    bound = config["check"]["margin_bound"]
    floor = config["check"]["argmax_floor"]
    emit(phase="check", **res,
         window_streams=facts["check"]["window_streams"], margin_bound=bound,
         argmax_floor=floor, init_s=t_init,
         reference_s=time.perf_counter() - t - t_init)
    facts["compared"] = {
        "worst_margin": {"value": res["worst_margin"], "limit": bound,
                         "passes": "at most"},
        "argmax_share": {"value": res["argmax_share"], "limit": floor,
                         "passes": "at least"}}
    return res["worst_margin"] <= bound and res["argmax_share"] >= floor
