"""Benchmark harness — prints ONE JSON line.

Headline metric: GPT-2 small forward throughput (tokens/sec) on one chip,
bf16 compute, stacked-block layout (the same compiled program the pipeline
runtime shards across chips).

Baseline: the reference runs its models as torch nn.Modules on
cuda-if-available-else-cpu (/root/reference/node.py:25); on this machine
that means torch CPU. We time the same GPT-2 architecture as a torch CPU
forward (HF GPT2LMHeadModel instantiated from config — no download) and
report vs_baseline = ours / torch_cpu. If torch is unavailable, the
baseline falls back to this framework's own forward pinned to the host CPU
backend (noted in the metric name).
"""

import json
import time

import jax
import jax.numpy as jnp

BATCH, SEQ = 8, 512


from dnn_tpu.utils.timing import device_time as _time_fn  # shared harness


def bench_ours(light: bool = False):
    from dnn_tpu.models import gpt

    cfg = gpt.PRESETS["gpt2"]
    params = gpt.init(jax.random.PRNGKey(0), cfg)
    prepared = gpt.prepare_stacked(params, cfg)
    # serving configuration: bf16 operands AND bf16 logit store (f32
    # accumulation) — the f32 logit write is the forward's largest HBM
    # store; rounding it to bf16 measures +11% end-to-end (see gpt.head)
    fn = jax.jit(gpt.make_apply_stacked(
        cfg, compute_dtype=jnp.bfloat16, logits_dtype=jnp.bfloat16
    ))
    ids = jax.random.randint(
        jax.random.PRNGKey(1), (BATCH, SEQ), 0, cfg.vocab_size, dtype=jnp.int32
    )
    # light: the CPU-fallback path (emulated bf16 is ~seconds per forward;
    # the slope method's usual rep counts would blow the bench budget)
    dt = _time_fn(fn, prepared, ids, n1=1, n2=2) if light \
        else _time_fn(fn, prepared, ids)
    return BATCH * SEQ / dt


def bench_torch_cpu():
    import torch
    from transformers import GPT2Config, GPT2LMHeadModel

    torch.manual_seed(0)
    model = GPT2LMHeadModel(GPT2Config())  # gpt2-small shape, random init
    model.eval()
    ids = torch.randint(0, 50257, (BATCH, SEQ))
    with torch.no_grad():
        model(ids)  # warmup
        t0 = time.perf_counter()
        for _ in range(2):
            model(ids)
        dt = (time.perf_counter() - t0) / 2
    return BATCH * SEQ / dt


def bench_jax_cpu():
    from dnn_tpu.models import gpt

    cfg = gpt.PRESETS["gpt2"]
    cpu = jax.devices("cpu")[0]
    with jax.default_device(cpu):
        params = gpt.init(jax.random.PRNGKey(0), cfg)
        prepared = gpt.prepare_stacked(params, cfg)
        fn = jax.jit(gpt.make_apply_stacked(cfg))
        ids = jax.random.randint(
            jax.random.PRNGKey(1), (BATCH, SEQ), 0, cfg.vocab_size, dtype=jnp.int32
        )
        dt = _time_fn(fn, prepared, ids, n1=1, n2=3)
    return BATCH * SEQ / dt


def _backend_alive(deadlines_s=(90.0, 180.0, 300.0),
                   backoff_s: float = 30.0) -> bool:
    """Bounded retry-with-backoff around the subprocess device probe —
    the probe itself is the serving watchdog's
    (dnn_tpu/obs/watchdog.subprocess_device_probe): one definition of
    "the chip answered" shared by the bench and the LM daemon's
    /statusz. Round-2 lesson (BENCH_r02.json, rc=1): a wedged TPU plugin
    hangs at backend init inside the first device op — in-process there
    is nothing to catch; the subprocess turns "hangs forever" into a
    detectable timeout. Round-3 lesson (BENCH_r03.json): a single
    attempt means one TRANSIENT wedge (e.g. a driver restart)
    costs the round's TPU headline. Deadlines ESCALATE so a
    slow-but-healthy cold init (plugin bringup + first-op compile can
    take minutes) is never mistaken for a wedge: the last attempt allows
    300 s, beyond the longest healthy init observed, while a genuinely
    dead chip still falls back to the honest CPU row in ~11 min worst
    case. Every failed attempt lands in the flight ring and the
    bench.probe_failures_total counter (machine-readable outcomes, not
    free-text notes — the round driver reads them off the JSON row)."""
    import sys

    from dnn_tpu import obs
    from dnn_tpu.obs.watchdog import subprocess_device_probe

    n = len(deadlines_s)
    for i, deadline in enumerate(deadlines_s):
        ok, detail, timed_out = subprocess_device_probe(deadline)
        if ok:
            if i:  # recovered after failures: record the flap too
                obs.flight.record("probe_recovered", attempt=i + 1)
            return True
        m = obs.metrics()
        if m is not None:
            m.inc("bench.probe_failures_total")
        obs.flight.record("probe_fail", attempt=i + 1, attempts=n,
                          deadline_s=deadline, detail=detail,
                          timed_out=timed_out)
        print(f"[bench] backend probe attempt {i + 1}/{n} failed "
              f"({detail})", file=sys.stderr)
        if timed_out and i + 1 < n:
            # WEDGED (hung probe), not merely unhealthy: invoke the
            # supervisor's device-restart path — a fresh subprocess
            # re-initializing the plugin from nothing with the longest
            # healthy-cold-init deadline — and count its success as the
            # round's recovery instead of burning the remaining ladder
            # (the failure shape that cost BENCH_r03–r05 their on-chip
            # rows). recover_backend records supervisor_device_restart
            # flight events either way.
            from dnn_tpu.chaos.supervisor import recover_backend

            r_ok, r_detail = recover_backend(
                deadline_s=max(deadlines_s))
            if r_ok:
                obs.flight.record("probe_recovered", attempt=i + 1,
                                  via="supervisor_device_restart")
                print("[bench] backend recovered via supervisor "
                      "restart path", file=sys.stderr)
                return True
            print(f"[bench] supervisor restart path failed "
                  f"({r_detail})", file=sys.stderr)
        if i + 1 < n:
            time.sleep(backoff_s * (i + 1))
    obs.flight.record("probe_exhausted", attempts=n)
    return False


def _last_good_tpu_reference(path=None):
    """The most recent COMMITTED on-chip headline from benchmarks/
    RESULTS.md, or None. Round-4 lesson: the chip answered the builder's
    session and wedged before the driver's, so BENCH_r04.json carried
    only the CPU fallback even though an on-chip table existed from hours
    earlier. When the probe ladder exhausts, this echo rides along on the
    fallback row (labeled, provenance-stamped — never mixed into the
    fresh measurement) so a wedged-chip round still surfaces a
    TPU-credible number."""
    import os
    import re

    if path is None:
        path = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                            "benchmarks", "RESULTS.md")
    try:
        with open(path) as f:
            text = f.read()
    except OSError:
        return None
    head = re.search(r"Generated at commit `([^`]+)` on ([^;]+); "
                     r"device-section platform: ([^.\n]+)", text)
    if not head or "tpu" not in head.group(3):
        return None  # no on-chip table to echo
    row = re.search(r"\| gpt2_fwd \| tokens_per_sec \| ([0-9.]+) \| "
                    r"([0-9.]+%|—) \| tpu \| ([^|\n]*)", text)
    if not row:
        return None
    # a CARRIED row (off-chip refresh cycles re-stamp the table header
    # with the refresh commit) names its own measurement vintage in a
    # provenance= detail — that, not the header, is when this number
    # was actually measured on chip
    commit, date = head.group(1), head.group(2).strip()
    carried = re.match(r"provenance=(\S+) ([^,]+)",
                       row.group(3).strip())
    if carried:
        commit, date = carried.group(1), carried.group(2).strip()
    ref = {
        "metric": "gpt2_fwd_tokens_per_sec_per_chip",
        "value": float(row.group(1)),
        "commit": commit,
        "date": date,
        "note": "last committed on-chip measurement (benchmarks/"
                "RESULTS.md), NOT measured this run",
    }
    if row.group(2) != "—":
        ref["mfu"] = round(float(row.group(2).rstrip("%")) / 100, 4)
    return ref


def _previous_round_ratio(repo_dir=None):
    """The latest committed round's vs_baseline (BENCH_r*.json), for
    drift detection: the r4->r5 ratio swing (0.97 -> 0.84) went two
    rounds uninterrogated because nothing echoed the history next to the
    fresh number. Returns {"round", "vs_baseline"} or None."""
    import os
    import re

    repo_dir = repo_dir or os.path.dirname(os.path.abspath(__file__))
    best = None
    for name in os.listdir(repo_dir):
        m = re.fullmatch(r"BENCH_r(\d+)\.json", name)
        if not m:
            continue
        rnd = int(m.group(1))
        if best is None or rnd > best[0]:
            best = (rnd, name)
    if best is None:
        return None
    try:
        with open(os.path.join(repo_dir, best[1])) as f:
            obj = json.load(f)
    except (OSError, json.JSONDecodeError):
        return None
    if not isinstance(obj, dict):
        return None
    row = obj
    if "vs_baseline" not in row and isinstance(obj.get("tail"), str):
        # driver format: the bench's printed JSON line rides inside the
        # captured "tail" text — take the last parseable line
        row = {}
        for line in obj["tail"].splitlines():
            if line.startswith("{"):
                try:
                    row = json.loads(line)
                except json.JSONDecodeError:
                    pass
    ratio = row.get("vs_baseline")
    if ratio is None:
        return None
    return {"round": best[0], "vs_baseline": ratio,
            "metric": row.get("metric")}


def main(argv=None):
    import sys

    args = list(sys.argv[1:] if argv is None else argv)
    require = None
    if "--require-substrate" in args:
        # contract flag (ROADMAP item 5a prep): the round driver states
        # the substrate this round's trajectory needs; a probe fallback
        # then marks the row ok=false and exits nonzero instead of
        # silently polluting the TPU trend with a CPU number
        idx = args.index("--require-substrate")
        try:
            require = args[idx + 1]
        except IndexError:
            print("--require-substrate needs a value (tpu|cpu)",
                  file=sys.stderr)
            return 2
        if require not in ("tpu", "cpu"):
            print(f"--require-substrate must be tpu|cpu, got "
                  f"{require!r}", file=sys.stderr)
            return 2
    from dnn_tpu.utils.compile_cache import enable_compile_cache

    enable_compile_cache()  # before the first compile
    # the probe children run BEFORE this process initializes a backend:
    # a chip belongs to one process, so nothing that needs the device is
    # spawned once this process has measured on it
    fell_back = not _backend_alive()
    if fell_back:
        # default (TPU) backend is wedged: force CPU before first use so
        # this process can still measure and report (one JSON line either
        # way; the row carries platform + a note)
        jax.config.update("jax_platforms", "cpu")
    # substrate, not history: a TPU-less host passes the probe on a
    # healthy CPU backend yet must still take the light timing path AND
    # the cpu-marked metric key below
    on_cpu = jax.default_backend() == "cpu"
    baseline_fn, metric = None, None
    try:
        import torch  # noqa: F401 — probe only; bench_torch_cpu imports
        import transformers  # noqa: F401

        baseline_fn = bench_torch_cpu
        metric = "gpt2_fwd_tokens_per_sec_per_chip_vs_torch_cpu"
    except Exception:
        baseline_fn = bench_jax_cpu
        metric = "gpt2_fwd_tokens_per_sec_per_chip_vs_jax_cpu"
    # A-B-A-B interleave, median of >= 3 pairs (VERDICT r5 weak #3): the
    # ratio previously paired ONE repo measurement with ONE baseline
    # measurement taken after it, so host-load drift between the two
    # swung the headline ~15% round-over-round. Interleaving puts both
    # legs under the same load regime and the per-pair ratios expose the
    # remaining noise as an explicit spread instead of silent drift.
    pairs = []
    while len(pairs) < 3:
        a = bench_ours(light=on_cpu)
        try:
            b = baseline_fn()
        except Exception:
            if baseline_fn is bench_jax_cpu:
                raise  # no further fallback
            # torch present but broke mid-run: switch baselines AND
            # discard earlier pairs — a median over mixed torch/jax
            # denominators under one metric key is exactly the
            # cross-substrate comparison the key exists to prevent
            baseline_fn = bench_jax_cpu
            metric = "gpt2_fwd_tokens_per_sec_per_chip_vs_jax_cpu"
            pairs = []
            continue
        pairs.append((a, b))
    ratios = sorted(a / b for a, b in pairs)
    ours = sorted(a for a, _ in pairs)[len(pairs) // 2]
    vs_baseline = ratios[len(ratios) // 2]
    if on_cpu:
        # distinct key: a CPU-substrate number must never be compared
        # against TPU rounds under the headline metric name — whether we
        # landed here via the wedge fallback or a TPU-less host
        metric = metric.replace("per_chip", "cpu_fallback")
    row = {
        "metric": metric,
        "value": round(ours, 1),
        "unit": "tokens/s",
        "vs_baseline": round(vs_baseline, 2),
        # spread of the interleaved per-pair ratios (max - min): the
        # uncertainty the single-shot ratio used to hide
        "vs_baseline_spread": round(ratios[-1] - ratios[0], 3),
        "vs_baseline_pairs": [round(r, 3) for r in ratios],
    }
    prev = _previous_round_ratio()
    if prev is not None:
        row["vs_baseline_prev_round"] = prev
    # MFU: the round-over-round "fast on TPU" number (vs_baseline only says
    # "faster than the reference's CPU substrate"). Omitted off-TPU.
    from dnn_tpu.models import gpt
    from dnn_tpu.utils.flops import gpt_forward_flops, mfu

    cfg = gpt.PRESETS["gpt2"]
    m = mfu(gpt_forward_flops(cfg, BATCH, SEQ) / (BATCH * SEQ), ours)
    if m is not None:
        row["mfu"] = round(m, 4)
    row["platform"] = jax.default_backend()
    # provenance (ISSUE 8): round_substrate is the contract-named alias
    # of platform (the substrate the round ACTUALLY ran on), plus
    # whether the chip recovered via the supervisor restart path — a
    # recovered chip yields an on-chip row, never a silent CPU row
    row["round_substrate"] = row["platform"]
    from dnn_tpu import obs as _obs_prov

    if _obs_prov.flight.recorder().events(kind="probe_recovered"):
        row["probe_recovered"] = True
    if fell_back:
        row["note"] = "default backend unresponsive; CPU fallback"
    # live decode goodput (ISSUE 6): every round's row carries the
    # serving hot path's dnn_tpu_mfu / dnn_tpu_mbu gauges, measured
    # fresh on this round's substrate (benchmarks/decode_mbu_probe.py,
    # light leg) — the MBU-gap trend rides BENCH_r*.json automatically,
    # like stale_tpu_reference already does. Never allowed to cost the
    # round its headline: any failure lands as a labeled error field.
    try:
        from benchmarks.decode_mbu_probe import measure as _mbu_measure

        g = _mbu_measure(light=True)
        row["decode_goodput"] = {
            k: g[k] for k in ("mfu", "mbu", "tokens_per_sec",
                              "rooflines", "platform", "asserted_leg",
                              "vs_studies_s10")}
    except Exception as e:  # noqa: BLE001 — headline must survive
        row["decode_goodput"] = {"error": str(e)[:200]}
    # inter-stage transport contract (ISSUE 7): every round's row carries
    # the relay_transport A-B numbers — negotiated-auto vs nested-grpc
    # per-hop p50 and the fleet-stitched bubble fraction, measured fresh
    # on real stage subprocesses (benchmarks/relay_transport_probe.py,
    # light leg). Error-isolated like decode_goodput: never allowed to
    # cost the round its headline.
    try:
        from benchmarks.relay_transport_probe import measure as _rt_measure

        r = _rt_measure(light=True)
        row["relay_transport"] = {
            "hop_p50_ratio": r["hop_p50_ratio"],
            "bubble_drop": r["bubble_drop"],
            "vs_studies_s10": r["vs_studies_s10"],
            "negotiated": r["auto"]["negotiated"],
            "hop_nested_grpc_p50_ms": r["grpc"]["hop_nested_p50_ms"],
            "hop_streamed_auto_p50_ms": r["auto"]["hop_streamed_p50_ms"],
            "ok": r["ok"],
        }
    except Exception as e:  # noqa: BLE001 — headline must survive
        row["relay_transport"] = {"error": str(e)[:200]}
    from dnn_tpu import obs

    if on_cpu:
        # a CPU-substrate round still surfaces the last committed on-chip
        # headline (distinctly labeled) so no round ships perf-blind
        ref = _last_good_tpu_reference()
        if ref is not None:
            row["stale_tpu_reference"] = ref
            m = obs.metrics()
            if m is not None:
                m.inc("bench.stale_tpu_reference_used_total")
            obs.flight.record("stale_tpu_reference", commit=ref["commit"],
                              date=ref["date"], value=ref["value"])
    # the probe/echo outcomes as EVENTS on the row whatever substrate the
    # round landed on — a TPU round that recovered after a transient
    # probe failure must still ship the flap machine-readably (the
    # free-text `note` stays for humans): the round driver can count
    # probe_fail/probe_recovered/stale_tpu_reference without parsing prose
    events = obs.flight.recorder().events()
    outcomes = [e for e in events
                if e["kind"] in ("probe_fail", "probe_exhausted",
                                 "probe_recovered",
                                 "stale_tpu_reference")]
    if outcomes:
        row["flight_events"] = outcomes
    rc = 0
    if require is not None:
        # the substrate contract decides the row's ok — a CPU-fallback
        # round against --require-substrate tpu is a FAILED row (and a
        # nonzero exit), never a silently-mislabeled data point
        row["required_substrate"] = require
        row["ok"] = row["round_substrate"] == require
        if not row["ok"]:
            rc = 1
            row["note"] = (row.get("note", "") + "; " if row.get("note")
                           else "") + (
                f"required substrate '{require}' but the round ran on "
                f"'{row['round_substrate']}'")
    print(json.dumps(row), flush=True)
    return rc


if __name__ == "__main__":
    import sys

    sys.exit(main())
