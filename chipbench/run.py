#!/usr/bin/env python3
"""The chip benchmark's one command.

    python3 chipbench/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

One run of one cell of BENCHMARK.json, in a new process: set-up (weights
from the seed, compile or load from the compile cache, warm-up of this
cell's shapes), a measured window of `--seconds`, the correctness check,
and as the LAST line of stdout one JSON object: `correct`, `attempted`,
`failed`, `metrics`, `device` (and `breakdown` with `--trace 1`), then
`compared`: each number `correct` rests on beside its limit, which are also
the last lines of stderr. Earlier lines are JSON too: sample counts,
generator lateness, margins.

`--trace 0` reports the cell's end-to-end metrics; `--trace 1` profiles a
few seconds of the window and reports its per-layer metrics.

Without `--rehearse` the run fails unless JAX finds the TPU chips the cell
asks for. `--rehearse` (CPU, the files' `rehearsal` sizes) exercises the
harness end to end; it prints every device metric as "not measured" and
its last line carries `"rehearsal": true` and no metrics.
"""

from __future__ import annotations

import time

_T0_EPOCH, _T0_PERF = time.time(), time.perf_counter()

import argparse  # noqa: E402
import importlib  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
REPO = os.path.dirname(HERE)
sys.path.insert(0, REPO)


def _process_start_epoch() -> float:
    """When the kernel started this process (its set-up clock starts
    there, not where Python got to its first line)."""
    try:
        with open("/proc/self/stat") as f:
            ticks = int(f.read().rsplit(")", 1)[1].split()[19])
        with open("/proc/stat") as f:
            btime = next(int(l.split()[1]) for l in f if l.startswith("btime"))
        return min(_T0_EPOCH, btime + ticks / os.sysconf("SC_CLK_TCK"))
    except (OSError, ValueError, IndexError, StopIteration):
        return _T0_EPOCH


def emit(**row):
    print(json.dumps(row), flush=True)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearse", action="store_true",
                    help="CPU rehearsal at the files' rehearsal sizes; "
                         "reports no device metric")
    args = ap.parse_args(argv)

    if args.rehearse:
        os.environ["JAX_PLATFORMS"] = "cpu"
    from chipbench import cells, tracered
    from chipbench import peaks as pk

    cell = cells.resolve(args.workload, rehearse=args.rehearse)
    if args.rehearse and cell["cell"]["chips"] > 1:
        os.environ["XLA_FLAGS"] = (
            os.environ.get("XLA_FLAGS", "")
            + f" --xla_force_host_platform_device_count="
              f"{cell['cell']['chips']}").strip()
    workdir = os.path.join(HERE, ".work", args.workload)
    shutil.rmtree(workdir, ignore_errors=True)
    os.makedirs(workdir)

    # the configuration names the module that runs it (serve.py: the
    # daemon behind gRPC; pipe.py: the engine in this process)
    driver = importlib.import_module(
        f"chipbench.{cell['config']['run']['driver']}")
    facts = driver.run(cell, seed=args.seed, seconds=args.seconds,
                       trace=bool(args.trace), rehearse=args.rehearse,
                       workdir=workdir, emit=emit)
    setup_s = (facts["t0"] - _T0_PERF) + (_T0_EPOCH - _process_start_epoch())

    # serving cells: the daemon has exited, this process may take the chip
    if "device" not in facts:
        from dnn_tpu.utils.compile_cache import enable_compile_cache

        from chipbench import check

        enable_compile_cache()
        t = time.perf_counter()
        facts["device"] = check.device_info(cell["cell"]["chips"],
                                            rehearse=args.rehearse)
        emit(phase="device", take_chip_s=time.perf_counter() - t)
        facts["correct"] = driver.check_served(facts, seed=args.seed,
                                               emit=emit)
    device = dict(facts["device"])  # a TPU, unless this is a rehearsal
    facts["peaks"] = None if args.rehearse else pk.peaks_for(device["kind"])

    facts["trace"] = None
    if args.trace and facts.get("trace_capture"):
        xplane = tracered.find_xplane(facts["trace_capture"])
        events = tracered.load_xplane(xplane) if xplane else None
        if events is not None:
            facts["trace"] = tracered.reduce_trace(events)
    if args.trace and facts.get("trace_error"):
        emit(phase="trace", error=facts["trace_error"])

    units = cell["units"]
    if args.trace:
        values = {name: fn(facts, **fargs)
                  for name, (fn, fargs) in cell["per_layer"].items()}
    else:
        # the traffic file says which client statistic is which metric
        reports = dict(cell["traffic"]["reports"], setup_s="setup_s")
        stats = dict(facts["client"], setup_s=setup_s)
        values = {name: stats.get(reports.get(name))
                  for name in cell["end_to_end"]}
        missing = [k for k, v in values.items() if v is None]
        if missing:
            raise RuntimeError(f"no value for end-to-end metrics {missing}")
    values = {k: v for k, v in values.items() if v is not None}
    for note in facts.get("notes", ()):
        emit(phase="note", **note)

    if args.rehearse:
        # counts are real, device numbers are not measured
        emit(phase="rehearsal",
             metrics={k: "not measured" for k in values},
             note="CPU rehearsal: counts above are real, every time, rate "
                  "and share is not measured")
        print(json.dumps({"rehearsal": True, "correct": bool(facts["correct"]),
                          "attempted": facts["attempted"],
                          "failed": facts["failed"], "device": device,
                          "metrics": "not measured"}), flush=True)
        return 0 if facts["correct"] and not facts["failed"] else 1

    if facts.get("memory_peak_bytes") is None:
        raise RuntimeError("no peak device memory was read")
    device["memory_peak_bytes"] = int(facts["memory_peak_bytes"])
    line = {"correct": bool(facts["correct"]), "attempted": facts["attempted"],
            "failed": facts["failed"],
            "metrics": {k: {"value": v, "unit": units[k]}
                        for k, v in values.items()},
            "device": device}
    if args.trace:
        t = facts["trace"]
        if t is None:
            raise RuntimeError("the traced run read no device operation: "
                               f"{facts.get('trace_error')}")
        device["busy_s"], device["window_s"] = t["busy_s"], t["window_s"]
        line["breakdown"] = {"device_ops": t["device_ops"],
                             "idle_gaps": t["idle_gaps"]}
    # each number `correct` compared, beside its limit: the result's last
    # key and the last lines of stderr
    line["compared"] = facts["compared"]
    for name, c in facts["compared"].items():
        print(f"compared {name} {c['value']!r} {c['passes']} {c['limit']!r}",
              file=sys.stderr, flush=True)
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
