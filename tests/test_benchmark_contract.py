"""The names the chip benchmark reads from the program, held on the CPU.

`BENCHMARK.json`'s per-layer metrics are computed by `chipbench/` from what
the program writes: `/metrics` series and `/stepz` fields, the names of the
jitted programs on the device trace, `jax.named_scope` prefixes in the
operations' `op_name`, and the worker thread's span names. A rename passes
every other test here and surfaces on the chip as a per-layer metric that
reads `null`. One case per distinct name, so each fails alone.

No name passes by being named in this file: the names come from `BENCHMARK.json`, the
files under `chipbench/layers/` and the benchmark's own readers (called on
a mapping that remembers what was asked of it), and each is looked for in
what the program really wrote — the benchmark's own spawner
(`chipbench.daemon.Daemon`) running `python -m dnn_tpu.node --serve_lm` at
the cell's rehearsal size, the step programs lowered from the arguments of
their first real calls, the engine's traced forward, one real capture.
"""

import json
import os
import re
import threading
import time

import pytest

from chipbench import cells, hosttime, rpctime, spans, tracered
from chipbench.daemon import Daemon

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

with open(os.path.join(REPO, "BENCHMARK.json")) as _f:
    _BENCH = json.load(_f)


class _Asked(dict):
    """A mapping that holds whatever is asked of it and remembers the keys:
    handed to the benchmark's readers in place of a scrape, it collects
    the series they read."""

    def __init__(self):
        super().__init__()
        self.keys_asked = set()

    def __bool__(self):
        return True

    def __contains__(self, key):
        self.keys_asked.add(key)
        return True

    def __getitem__(self, key):
        self.keys_asked.add(key)
        return 1.0

    def get(self, key, default=None):
        return self[key]


def _as_list(value):
    return [] if value is None else \
        list(value) if isinstance(value, (list, tuple)) else [value]


def _read_by(config_name):
    """What the per-layer metrics of one configuration's cells read from
    the program: {"series", "stepz", "programs", "scopes", "under"}, each
    a sorted list of names."""
    series, stepz = _Asked(), _Asked()
    programs, scopes, under = set(), set(), set()
    for w in _BENCH["workloads"]:
        if w["config"] != config_name:
            continue
        cell = cells.resolve(w["name"], rehearse=True)
        facts = {"metrics0": series, "metrics1": series, "stepz": stepz,
                 "config": cell["config"], "client": {}}
        # the prefixes the configuration itself declares (what its
        # `scopes:share_pct` readers divide the step among), beside those
        # a metric's file names
        scopes.update(cell["config"].get("trace", {}).get("known_scopes")
                      or ())
        for reader, args in cell["per_layer"].values():
            for key in ("series", "num", "den"):
                series.keys_asked.update(_as_list(args.get(key)))
            for key in ("program", "programs", "per"):
                programs.update(_as_list(args.get(key)))
            for key in ("scopes", "known", "scope"):
                scopes.update(_as_list(args.get(key)))
            under.update(_as_list(args.get("under")))
            try:
                reader(facts, **args)
            except Exception:  # noqa: BLE001 — a reader of the trace or of
                pass           # the client's clock: it has no scrape to ask
    under.discard("outside")  # the absence of both spans
    return {"series": sorted(series.keys_asked),
            "stepz": sorted(stepz.keys_asked),
            "programs": sorted(programs), "scopes": sorted(scopes),
            "under": sorted(under)}


def _driver(config_entry):
    with open(os.path.join(REPO, config_entry["file"])) as f:
        return json.load(f)["run"]["driver"]


# the engine runs in the benchmark's own process; every other driver
# spawns the daemon
_PIPED = {c["name"]: _read_by(c["name"]) for c in _BENCH["configs"]
          if _driver(c) == "pipe"}
_SERVED = {c["name"]: _read_by(c["name"]) for c in _BENCH["configs"]
           if c["name"] not in _PIPED}


def _cases(read_by, kind, once=True):
    """(configuration, name) for every name of `kind`. With `once`, a name
    that several configurations read through the same code of the program
    is held by the first that reads it; without, by each (a scope prefix
    names operations of each model family's own step programs)."""
    seen, out = set(), []
    for config in sorted(read_by):
        for name in read_by[config][kind]:
            if not (once and name in seen):
                seen.add(name)
                out.append(pytest.param(config, name,
                                        id=f"{config}:{name}"))
    return out


def _first_cell(config_name):
    return next(w["name"] for w in _BENCH["workloads"]
                if w["config"] == config_name)


# ----------------------------------------------------------------------
# /metrics, /stepz and the worker's spans: one real daemon a configuration
# ----------------------------------------------------------------------

@pytest.fixture(scope="module")
def served(tmp_path_factory):
    """{configuration: {"metrics", "stepz", "span_names"}} of a daemon
    spawned as the benchmark spawns it, at the cell's rehearsal size, after
    a few streamed requests with a capture taken over some of them. Each
    configuration's daemon is started on first use and stopped with the
    module."""
    from dnn_tpu.comm.client import NodeClient

    cache = {}

    def of(config_name):
        if config_name in cache:
            return cache[config_name]
        cell = cells.resolve(_first_cell(config_name), rehearse=True)
        run = cell["config"]["run"]
        workdir = str(tmp_path_factory.mktemp(config_name))
        daemon = Daemon(
            repo=REPO, workdir=workdir, model=run["model"],
            dtype=run["dtype"], device_type=run.get("device_type"), seed=0,
            serve_flags=run["serve_flags"],
            env_extra={"DNN_TPU_OBS_DIR": os.path.join(workdir, "obs")})
        daemon.spawn()
        client = None
        try:
            client = NodeClient(daemon.addr, breaker=False)
            daemon.wait_ready(client, 300)
            vocab = cell["config"]["vocab_size"]
            prompt = [1 + i % (vocab - 1) for i in range(24)]

            def ask(n_new):
                return list(client.generate_stream(
                    prompt, max_new_tokens=n_new, timeout=300.0))

            assert len(ask(4)) == 4  # every program compiled
            box = {}

            def capture():
                box["capture"] = daemon.get_json(
                    "/profilez?ms=1500", method="POST",
                    timeout=300)["capture"]

            taker = threading.Thread(target=capture, daemon=True)
            taker.start()
            while taker.is_alive():  # requests all through the capture,
                ask(8)               # with a gap for the loop to WAIT in:
                time.sleep(0.02)     # back to back, a loaded host may
                #                      queue the next before the worker looks
            taker.join()
            found = {"metrics": daemon.metrics(),
                     "stepz": daemon.get_json("/stepz"),
                     "statusz": daemon.get_json("/statusz"),
                     "capture": box["capture"]}
            xplane = tracered.find_xplane(box["capture"])
            # the worker's line as each of the benchmark's two loaders
            # keeps it (`step*` and `admit*`; those and `loop*`)
            found["span_names"] = {
                s[0] for s in spans.load_capture(xplane)["spans"]
                + hosttime.load_capture(xplane)}
            # the host plane's two lines as `rpctime` keeps them
            found["rpc_lines"] = rpctime.load_lines(xplane)
        except BaseException:
            if daemon.proc.poll() is None:
                daemon.proc.kill()
            raise
        finally:
            if client is not None:
                client.close()
        cache[config_name] = found
        assert daemon.stop() == 0, daemon.log_tail()
        return found

    return of


@pytest.mark.parametrize("config,series", _cases(_SERVED, "series"))
def test_metrics_series_is_written(served, config, series):
    assert series in served(config)["metrics"], (
        f"{series} is read by a per-layer metric of {config}'s cells and "
        "is not on the daemon's /metrics page")


@pytest.mark.parametrize("config,field", _cases(_SERVED, "stepz"))
def test_stepz_field_is_written(served, config, field):
    assert field in served(config)["stepz"]


@pytest.mark.parametrize("config,root", _cases(_SERVED, "under"))
def test_span_is_written_during_a_capture(served, config, root):
    names = served(config)["span_names"]
    assert any(n == root or n.startswith(root + ".") for n in names), (
        root, sorted(names))


# the loop's own span and the retirement's span are read by no metric
# alone (`hosttime`'s `unnamed` holds what `loop` keeps of itself, and
# `step.commit`'s share holds `step.commit.retire`): README's capture
# walkthrough names them for whoever opens a capture
@pytest.mark.parametrize("name", list(hosttime.ROOTS) + [
    "loop." + p for p in hosttime.SPAN_PARTS] + ["step.commit.retire"])
def test_worker_span_is_written_by_the_daemon(served, name):
    config = sorted(_SERVED)[0]
    assert name in served(config)["span_names"], sorted(
        served(config)["span_names"])


def test_capture_meta_says_where_the_capture_lies_in_the_window(served):
    """`hosttime.capture_rates` reads the capture's `meta.json` on the
    daemon's own clock, which `/metrics` shows at each scrape."""
    found = served(sorted(_SERVED)[0])
    with open(os.path.join(found["capture"], "meta.json")) as f:
        meta = json.load(f)
    assert meta["step_begin"] <= meta["step_end"] <= meta["step_stopped"]
    assert meta["perf_begin"] < meta["perf_end"]
    assert meta["stop_s"] > 0 and meta["python_tracer"] in (True, False)
    assert found["metrics"]["process_perf_counter_seconds"] > \
        meta["perf_end"] + meta["stop_s"]
    facts = {"trace_capture": found["capture"],
             "metrics0": {"process_perf_counter_seconds":
                          meta["perf_begin"] - 2.0,
                          "step_steps_total": meta["step_begin"] - 40},
             "metrics1": dict(found["metrics"])}
    rates = hosttime.capture_rates(facts)
    assert rates["quiet_steps_per_s"] > 0 and rates["inside_steps_per_s"] > 0
    assert rates["slowdown_pct"] == pytest.approx(100 * (
        1 - rates["inside_steps_per_s"] / rates["quiet_steps_per_s"]))


def test_worker_time_is_partitioned_on_the_daemons_page(served):
    """The six `step_phase_seconds_total` and four `step_loop_seconds_total`
    sum to the worker thread's life so far (the page's own clock less the
    moment the loop began: under the daemon's uptime, over the time since
    its first request); the threads' CPU clocks are read at the scrape."""
    m = served(sorted(_SERVED)[0])["metrics"]

    def total(family, label, values):
        return sum(m[f'{family}{{{label}="{v}"}}'] for v in values)

    wall_loop = total("step_loop_seconds_total", "part", hosttime.LOOP_PARTS)
    wall = wall_loop + total("step_phase_seconds_total", "phase",
                             spans.PHASES)
    assert 0 < wall < m["process_perf_counter_seconds"]
    assert m['step_loop_seconds_total{part="wait"}'] > 0
    # the two threads' own clocks, and the process's
    worker = m['process_thread_cpu_seconds_total{thread="worker"}']
    rpc = m['process_thread_cpu_seconds_total{thread="rpc_loop"}']
    assert 0 < worker and 0 < rpc
    assert worker + rpc <= m["process_cpu_seconds_total"] + 0.05
    assert m["serving_emit_lag_seconds_count"] > 0
    assert 0 < m["serving_emit_lag_seconds_sum"] / \
        m["serving_emit_lag_seconds_count"] <= \
        m["serving_emit_lag_seconds_max"]
    # every streamed token crossed to the event loop in some hand-off, and
    # the handlers have taken all of them off their queues by now
    assert 0 < m["serving_emit_handoffs_total"] <= \
        m["serving_emit_tokens_total"]
    assert m["serving_emit_tokens_total"] == \
        m["serving_emit_lag_seconds_count"]
    assert m["jax_traces_total"] >= m["jax_compilations_total"] > 0


_RPC_ENTRIES = {"srv_rpc_loop_busy_pct": rpctime.loop_busy_pct,
                "srv_rpc_us_per_token": rpctime.us_per_token,
                "srv_rpc_token_build_us": rpctime.token_build_us,
                "srv_fan_out_lag_ms": rpctime.fan_out_lag_ms,
                "srv_host_overlap_rpc_ms_per_step":
                    rpctime.host_overlap_rpc_ms_per_step,
                "steady_rpc_us_per_token": rpctime.us_per_token}


@pytest.mark.parametrize("name", sorted(_RPC_ENTRIES))
def test_rpc_entry_lists_emit_lags_cells_and_names_its_reader(name):
    """Each entry that reads the event-loop thread's time is reported by
    the cells that report `srv_emit_lag_ms` (steady's by steady), under
    `Daemon`, and resolves there to its reader in `chipbench/rpctime.py`
    with no arguments; on a page and a capture that predate the series and
    the spans (the commit before) the reader returns nothing."""
    entry = {m["name"]: m for m in _BENCH["per_layer"]}
    twin = "steady_emit_lag_ms" if name.startswith("steady_") \
        else "srv_emit_lag_ms"
    assert entry[name]["workloads"] == entry[twin]["workloads"]
    assert entry[name]["moves"] == entry[twin]["moves"]
    assert (entry[name]["layer"], entry[name]["better"]) == ("Daemon",
                                                             "lower")
    assert entry[name]["source"] == (
        "program_span" if "overlap" in name else "program_counter")
    for cell_name in entry[name]["workloads"]:
        reader, args = cells.resolve(cell_name,
                                     rehearse=True)["per_layer"][name]
        assert reader is _RPC_ENTRIES[name] and args == {}
    old = {"step_steps_total": 9.0, "serving_emit_tokens_total": 80.0,
           "process_perf_counter_seconds": 50.0}
    facts = {"metrics0": dict.fromkeys(old, 0.0), "metrics1": old,
             "trace_capture": None}
    assert _RPC_ENTRIES[name](facts) is None
    row = next(n["rpctime"] for n in facts["notes"] if "rpctime" in n)
    assert "error" not in row and row["overlap"] is None
    assert row["loop_busy_pct"] is None and row["us_per_token"] is None


@pytest.mark.parametrize("name,stats", [
    ("rpc.run", ("iter",)), ("rpc.fan_out", ("tokens", "handoff")),
    ("rpc.tokens", ("tokens",))])
def test_rpc_span_is_written_by_the_daemons_loop_thread(served, name, stats):
    """The event-loop thread's spans in a real capture of a daemon started
    as the benchmark starts it: on ONE line of the host plane, which is not
    the worker's, with the stats `rpctime`'s note pairs them by."""
    lines = served(sorted(_SERVED)[0])["rpc_lines"]
    found = [s for s in lines["rpc"] if s[0] == name]
    assert found and all(k in s[3] for s in found for k in stats)
    assert not any(s[0].startswith("rpc") for s in lines["worker"])
    assert not any(s[0].split(".")[0] in hosttime.ROOTS
                   for s in lines["rpc"])
    if name != "rpc.run":  # nested in a run: a synchronous section of it
        runs = [s for s in lines["rpc"] if s[0] == "rpc.run"]
        inside = sum(any(r[1] <= s[1] and s[1] + s[2] <= r[1] + r[2]
                         for r in runs) for s in found)
        assert inside >= len(found) - 1  # the capture's edge may cut one


def test_rpc_loop_time_is_partitioned_on_the_daemons_page(served):
    """The four `serving_rpc_loop_seconds_total` parts sum to the loop
    thread's life so far (under the daemon's uptime; the block in progress
    counts), most of it `select` on a daemon that served a few requests;
    every hand-off left one lag, and the readers turn the page into their
    numbers."""
    found = served(sorted(_SERVED)[0])
    m = found["metrics"]
    parts = {p: m[f'serving_rpc_loop_seconds_total{{part="{p}"}}']
             for p in rpctime.PARTS}
    assert all(v > 0 for v in parts.values())
    assert parts["select"] > sum(parts.values()) / 2
    assert sum(parts.values()) < m["process_perf_counter_seconds"]
    assert m["serving_rpc_loop_iterations_total"] > \
        m["serving_emit_handoffs_total"]
    assert m["serving_fan_out_lag_seconds_count"] == \
        m["serving_emit_handoffs_total"]
    zero = dict.fromkeys(m, 0.0)
    facts = {"metrics0": zero, "metrics1": m,
             "trace_capture": found["capture"]}
    busy = rpctime.loop_busy_pct(facts)
    assert busy == pytest.approx(
        100 * (1 - parts["select"] / sum(parts.values())))
    per_token = rpctime.us_per_token(facts)
    assert per_token == pytest.approx(
        1e6 * (sum(parts.values()) - parts["select"])
        / m["serving_emit_tokens_total"])
    assert 0 < rpctime.token_build_us(facts) < per_token
    assert 0 < rpctime.fan_out_lag_ms(facts) < 1e3
    # the capture's own reading, and the whole split as ONE note row
    assert rpctime.host_overlap_rpc_ms_per_step(facts) >= 0
    (row,) = [n["rpctime"] for n in facts["notes"] if "rpctime" in n]
    assert "error" not in row
    assert row["overlap"]["steps"] > 0
    assert sum(row["overlap"]["by"].values()) == pytest.approx(
        row["overlap"]["ms_per_step"])
    assert not set(row["overlap"]["by"]) & set(rpctime.WAITING)


def test_tokens_per_handoff_reads_the_daemons_two_counters(served):
    """`srv_tokens_per_handoff` is the benchmark's own `counter_ratio` over
    the two series the worker's hand-off counts, in each of the cells that
    report `srv_emit_lag_ms`; on a page without them (the commit before)
    the reader returns nothing."""
    from chipbench import scopes

    entry = {m["name"]: m for m in _BENCH["per_layer"]}
    assert entry["srv_tokens_per_handoff"]["workloads"] == \
        entry["srv_emit_lag_ms"]["workloads"]
    cell = cells.resolve("kexaone-reasoning-saturated", rehearse=True)
    reader, args = cell["per_layer"]["srv_tokens_per_handoff"]
    assert reader is scopes.counter_ratio
    assert args == {"num": "serving_emit_tokens_total",
                    "den": "serving_emit_handoffs_total"}
    m = served(sorted(_SERVED)[0])["metrics"]
    before = dict(m, **{args["num"]: 0.0, args["den"]: 0.0})
    ratio = reader({"metrics0": before, "metrics1": m}, **args)
    assert ratio == m[args["num"]] / m[args["den"]] >= 1.0
    old = {k: v for k, v in m.items() if k not in args.values()}
    assert reader({"metrics0": old, "metrics1": old}, **args) is None


def test_steps_pipelined_share_reads_the_batchers_two_counters(served):
    """`srv_steps_pipelined_share` is the benchmark's own `counter_ratio`
    over `step_pipelined_total` (the batcher's: steps dispatched while the
    step before them was uncommitted) and the clock's `step_steps_total`, in
    each `out_tok_s` cell; a daemon started as the benchmark starts it, with
    no flag, pipelines and says so on `/statusz`; on a page without the
    counter (the commit before) the reader returns nothing."""
    from chipbench import scopes

    entry = {m["name"]: m for m in _BENCH["per_layer"]}
    e2e = {m["name"]: m for m in _BENCH["end_to_end"]}
    assert entry["srv_steps_pipelined_share"]["workloads"] == \
        e2e["out_tok_s"]["workloads"]
    cell = cells.resolve("olmoe-chat-saturated", rehearse=True)
    reader, args = cell["per_layer"]["srv_steps_pipelined_share"]
    assert reader is scopes.counter_ratio
    assert args == {"num": "step_pipelined_total", "den": "step_steps_total"}
    for name in sorted(_SERVED):
        found = served(name)
        m = found["metrics"]
        loop = found["statusz"]["components"]["batcher"]
        assert loop["loop"] == "pipelined" and loop["depth"] == 1
        assert 0 < m["step_pipelined_total"] < m["step_steps_total"]
        assert 0 <= m["step_stale_rows_total"] <= m["step_pipelined_total"]
        before = dict(m, **{args["num"]: 0.0, args["den"]: 0.0})
        share = reader({"metrics0": before, "metrics1": m}, **args)
        assert share == m[args["num"]] / m[args["den"]] > 0.5
    old = {k: v for k, v in m.items() if k != args["num"]}
    assert reader({"metrics0": old, "metrics1": old}, **args) is None


# ----------------------------------------------------------------------
# hosttime's arithmetic, on a stretch of a real capture
# ----------------------------------------------------------------------

@pytest.fixture(scope="module")
def recorded_loop():
    """120 ms of `olmoe-chat-saturated`'s capture on the chip (PR 37): the
    device's operations and the worker's `step*`, `admit*` and `loop*`
    spans, in the plain lists `chipbench/spans.py` describes."""
    import gzip

    path = os.path.join(REPO, "tests", "recorded_loop_spans.json.gz")
    with gzip.open(path, "rt") as f:
        return json.load(f)


def _facts_of(capture, roots):
    keep = [s for s in capture["spans"] if s[0].split(".")[0] in roots]
    return {"spans_capture": {"devices": capture["devices"], "spans": keep},
            "hosttime_capture": capture, "trace_capture": None}


def test_loop_shares_add_up_to_what_spans_calls_outside(recorded_loop):
    """`hosttime.idle_pct` under each `loop.<part>` plus `unnamed` equals
    `spans.idle_pct(under="outside")` on the same capture; the shares under
    `step*` and `admit*` are what `spans` reads without the `loop` spans."""
    facts = _facts_of(recorded_loop, spans.SPAN_ROOTS)
    parts = {p: hosttime.idle_pct(facts, under="loop." + p)
             for p in hosttime.SPAN_PARTS}
    unnamed = hosttime.idle_pct(facts, under="unnamed")
    outside = spans.idle_pct(facts, under="outside")
    assert all(v is not None and v >= 0 for v in parts.values())
    assert sum(parts.values()) + unnamed == pytest.approx(outside, abs=1e-9)
    assert 0 <= unnamed < 0.5
    assert outside > 1.0 and max(parts.values()) > 1.0
    by = facts["hosttime_idle"]["by"]
    for root in ("step", "admit"):
        own = 100 * sum(v for k, v in by.items()
                        if k == root or k.startswith(root + ".")) \
            / facts["hosttime_idle"]["window_s"]
        assert own == pytest.approx(spans.idle_pct(facts, under=root),
                                    abs=1e-9)
    phases = sum(spans.idle_pct(facts, under="step." + p)
                 for p in spans.PHASES[1:])
    assert phases <= spans.idle_pct(facts, under="step") <= phases + 0.5
    # the whole split, as the run's `note` row carries it
    row = next(n["hosttime"] for n in facts["notes"] if "hosttime" in n)
    assert row["idle_loop_parts_sum_pct"] == pytest.approx(
        row["idle_outside_pct"], abs=1e-9)
    assert row["idle_unnamed_pct"] == pytest.approx(unnamed)
    assert row["emit_lag_ms"] is None  # no scrape here


def test_recorded_loop_spans_nest_as_the_program_writes_them(recorded_loop):
    """Every iteration is a `loop` span whose children are its parts and
    the step; `admit*` nests in `loop.admit`, `step.commit.retire` in
    `step.commit`; all carry `iter` or `step` / `rid`."""
    sp = recorded_loop["spans"]
    loops = [s for s in sp if s[0] == "loop"]
    assert len(loops) >= 5

    def inside(s, parent):
        return parent[1] <= s[1] and s[1] + s[2] <= parent[1] + parent[2]

    for lp in loops:
        kids = [s for s in sp if s is not lp and inside(s, lp)]
        names = [s[0] for s in kids if s[0].startswith("loop.")]
        assert names in (["loop.pre", "loop.admit", "loop.step",
                          "loop.emit"],
                         ["loop.pre", "loop.wait"],
                         ["loop.pre", "loop.wait", "loop.admit",
                          "loop.step", "loop.emit"]), names
        assert all(s[3]["iter"] == lp[3]["iter"] for s in kids
                   if s[0].startswith("loop."))
    for s in sp:
        root = s[0].split(".")[0]
        if root == "admit":
            assert any(inside(s, p) for p in sp if p[0] == "loop.admit")
        if s[0] == "step":
            assert any(inside(s, p) for p in sp if p[0] == "loop.step")
        if s[0] == "step.commit.retire":
            assert any(inside(s, p) for p in sp if p[0] == "step.commit")
            assert "rid" in s[3] and "slot" in s[3]


def _scripted_capture(lead_ms, n=12):
    """A worker that dispatches for 0.5 ms (the program starts 0.3 ms in),
    waits for a 4 ms program and 0.3 ms more, commits 0.2, observes 0.2,
    emits for 1 ms and comes round in 6.2 ms; the device plane's clock
    runs `lead_ms` ahead of the host's."""
    ms = 1_000_000
    sp, ops = [], []
    for i in range(n):
        t = i * 62 * ms // 10
        it = {"iter": i}
        st = {"step": i}
        sp += [["loop", t, 62 * ms // 10, it],
               ["loop.pre", t, ms // 10, it],
               ["loop.admit", t + ms // 10, ms // 10, it],
               ["loop.step", t + 2 * ms // 10, 5 * ms, it],
               ["step", t + 2 * ms // 10, 5 * ms, st],
               ["step.host", t + 2 * ms // 10, 0, st],
               ["step.dispatch", t + 2 * ms // 10, ms // 2, st],
               ["step.wait", t + 7 * ms // 10, 41 * ms // 10, st],
               ["step.commit", t + 48 * ms // 10, 2 * ms // 10, st],
               ["step.obs", t + 5 * ms, 2 * ms // 10, st],
               ["loop.emit", t + 52 * ms // 10, ms, it]]
        start = t + 5 * ms // 10 - int(lead_ms * ms)
        ops += [[start + k * ms, ms, None] for k in range(4)]
    return {"devices": [{"name": "/device:TPU:0", "ops": ops}], "spans": sp}


@pytest.mark.parametrize("lead_ms", [0.0, 0.7, 1.5, 2.4])
def test_step_cycle_needs_no_agreement_of_the_two_clocks(lead_ms):
    """The device plane's clock leads the host plane's by another 1-2 ms
    each capture on the chip, which moves idle time between neighbouring
    spans by a tenth of the extent. `clock_lead_ms` brackets the lead from
    what cannot happen (a program before its dispatch, a token before its
    program's end); `step_cycle` splits a step's idle time into serial host
    work, measured on the host's clock alone, and the rest, and reads the
    same whatever the lead."""
    cap = _scripted_capture(lead_ms)
    lead = hosttime.clock_lead_ms(cap)
    assert lead["lo"] == pytest.approx(lead_ms - 0.3, abs=1e-6)
    assert lead["hi"] == pytest.approx(lead_ms + 0.3, abs=1e-6)
    facts = {"spans_capture": cap, "hosttime_capture": cap,
             "trace_capture": None}
    cycle = hosttime.step_cycle(facts)
    # 2.2 ms idle a step: 1.6 of serial host work (pre 0.1, admit 0.1,
    # commit 0.2, obs 0.2, emit 1.0), 0.3 launch + 0.3 wake-up
    assert cycle["host_serial"] == pytest.approx(1.6, rel=0.1)
    assert cycle["launch_wake"] == pytest.approx(0.6, abs=0.2)
    assert cycle["idle"] == pytest.approx(
        cycle["under_admit"] + cycle["under_wait"] + cycle["host_serial"]
        + cycle["launch_wake"], abs=1e-9)
    # the shares by span, by contrast, follow the lead ...
    raw = hosttime.split(dict(facts))
    true = hosttime.split({"spans_capture": _scripted_capture(0.0),
                           "hosttime_capture": _scripted_capture(0.0),
                           "trace_capture": None})
    assert true["idle_step_wait_pct"] == pytest.approx(100 * 0.3 / 6.2,
                                                       abs=1.0)
    if lead_ms >= 0.7:
        assert raw["idle_step_wait_pct"] > true["idle_step_wait_pct"] + 8
        assert raw["idle_loop_emit_pct"] < true["idle_loop_emit_pct"] - (
            8 if lead_ms >= 1.5 else 2)
    # ... and come back with the device plane moved to the bounds' middle
    fixed = raw["lead_corrected"]
    assert fixed["step.wait"] == pytest.approx(
        true["idle_step_wait_pct"], abs=1.0)
    assert fixed["loop.emit"] == pytest.approx(
        true["idle_loop_emit_pct"], abs=1.0)


def _scripted_two_threads(lead_ms=0.0, n=12):
    """`_scripted_capture`'s worker with the host plane's other two lines:
    the runtime's enqueue 0.05 to 0.15 ms into each `step.dispatch` (the
    program handed to the device at 0.1), and an event-loop thread that
    runs from 0.2 ms into the dispatch for 2 ms (a hand-off's fan-out, then
    four tokens) and again over the last 0.3 ms of `loop.emit` and the
    0.1 ms of the next `loop.pre`."""
    ms = 1_000_000
    cap = _scripted_capture(lead_ms, n)
    worker, rpc, runtime = list(cap["spans"]), [], []
    for i in range(n):
        t = i * 62 * ms // 10
        d0 = t + 2 * ms // 10
        runtime += [[rpctime.ENQUEUE, d0 + ms // 20, ms // 10, {}],
                    [rpctime.ISSUE, d0 + ms // 10, ms // 50, {}]]
        rpc += [["rpc.run", t + 4 * ms // 10, 2 * ms, {"iter": 2 * i}],
                ["rpc.fan_out", t + 4 * ms // 10, ms // 10,
                 {"tokens": 4, "handoff": i + 1}],
                ["rpc.run", t + 59 * ms // 10, 4 * ms // 10,
                 {"iter": 2 * i + 1}]]
        rpc += [["rpc.tokens", t + 24 * ms // 10, 0, {"tokens": 4}]]
    return {"worker": worker, "rpc": rpc, "runtime": runtime,
            "devices": cap["devices"]}


@pytest.mark.parametrize("shift_ms", [0.0, 2.0, -2.0])
def test_overlap_and_dispatch_tail_read_the_host_plane_alone(shift_ms):
    """Hand-computed on a scripted capture: a step's `step.dispatch`
    (0.2-0.7 ms) lies under `rpc.run` (0.4-2.4 ms) for 0.3 ms, its
    `loop.emit` (5.2-6.2) under the second run (5.9-6.3) for 0.3 and the
    next `loop.pre` for 0.1; `step.wait`, where the worker waits, counts
    nothing. The enqueue returns 0.15 ms into the dispatch: 0.35 ms of tail,
    0.3 of it under `rpc.run`. Moving the device plane by 2 ms either way
    changes neither reading."""
    cap = _scripted_two_threads(lead_ms=shift_ms)
    over = rpctime.overlap(cap)
    # both threads' spans cover 0.4 ms .. 74.4 ms: the 11 steps that begin
    # in there, the first iteration's dispatch and emit, and every later
    # iteration's pre, dispatch and emit
    assert over["steps"] == 11
    assert over["by"] == pytest.approx(
        {"loop.pre": 11 * 0.1 / 11, "step.dispatch": 12 * 0.3 / 11,
         "loop.emit": 12 * 0.3 / 11})
    assert over["ms_per_step"] == pytest.approx((0.6 + 11 * 0.7) / 11)
    tail = rpctime.dispatch_tail(cap)
    assert tail == pytest.approx({"steps": 12, "launch": 0.15, "tail": 0.35,
                                  "tail_under_rpc": 0.3})
    facts = {"rpctime_capture": cap, "trace_capture": None}
    assert rpctime.host_overlap_rpc_ms_per_step(facts) == over["ms_per_step"]
    row = next(n["rpctime"] for n in facts["notes"] if "rpctime" in n)
    assert row["dispatch_tail"] == tail and row["overlap"] == over


@pytest.mark.parametrize("lead_ms", [0.0, 0.7, 1.5, 2.4])
def test_device_lead_is_bounded_below_by_the_runtimes_events(lead_ms):
    """The scripted program starts 0.2 ms after the runtime hands it to the
    device: the bound from the runtime's events is 0.1 ms closer to the
    lead than the dispatch span's begin can be, whatever the lead."""
    cap = _scripted_two_threads(lead_ms=lead_ms)
    lead = rpctime.device_lead_ms(cap)
    assert lead == {"lo": pytest.approx(lead_ms - 0.2, abs=1e-6),
                    "launches": 12}
    from_spans = hosttime.clock_lead_ms(
        {"devices": cap["devices"], "spans": cap["worker"]})
    assert from_spans["lo"] == pytest.approx(lead["lo"] - 0.1, abs=1e-6)
    assert rpctime.device_lead_ms(dict(cap, runtime=[])) is None


@pytest.fixture(scope="module")
def recorded_rpc():
    """A stretch of whole loop iterations of `olmoe-chat-saturated`'s
    capture on the chip (PR 52), as `rpctime.load_lines` keeps it: the
    worker's line (its spans), the event-loop thread's line (`rpc*`), the
    runtime's enqueue events, and the device's busy intervals."""
    import gzip

    path = os.path.join(REPO, "tests", "recorded_rpc_lines.json.gz")
    with gzip.open(path, "rt") as f:
        return json.load(f)


def test_recorded_overlap_is_what_a_microsecond_grid_counts(recorded_rpc):
    """`srv_host_overlap_rpc_ms_per_step` on a real capture against a count
    that shares none of its code: on a grid of microseconds, those at which
    some span of the worker is open, none of the three it waits in, and an
    `rpc.run` is open on the other line."""
    import numpy as np

    cap = recorded_rpc
    prog = [s for s in cap["worker"] if s[0].split(".")[0] in hosttime.ROOTS]
    runs = [s for s in cap["rpc"] if s[0] == "rpc.run"]
    assert len(runs) > 50 and sum(s[0] == "step" for s in prog) >= 8
    t0 = max(min(s[1] for s in runs), min(s[1] for s in prog))
    t1 = min(max(s[1] + s[2] for s in runs),
             max(s[1] + s[2] for s in prog))
    n = (t1 - t0) // 1000

    def grid(events):
        on = np.zeros(n + 1, np.int32)
        for _, start, dur, _ in events:
            a = min(max((start - t0 + 500) // 1000, 0), n)
            b = min(max((start + dur - t0 + 500) // 1000, 0), n)
            on[a] += 1
            on[b] -= 1
        return np.cumsum(on)[:n] > 0

    working = grid(prog) & ~grid([s for s in prog
                                  if s[0] in rpctime.WAITING])
    both = int((working & grid(runs)).sum())  # microseconds
    steps = sum(1 for s in prog if s[0] == "step" and t0 <= s[1] < t1)
    over = rpctime.overlap(cap)
    assert over["steps"] == steps
    assert over["ms_per_step"] == pytest.approx(both / 1e3 / steps, rel=0.01)
    assert over["ms_per_step"] > 1.0  # the chip's reading: section 5
    assert max(over["by"], key=over["by"].get) == "step.dispatch"
    # the jit call returns long after the runtime's enqueue has, and nearly
    # all of that tail lies under the other thread's run
    tail = rpctime.dispatch_tail(cap)
    assert tail["steps"] >= steps - 2
    assert 0.05 < tail["launch"] < 1.0 < tail["tail"]
    assert tail["tail_under_rpc"] > 0.8 * tail["tail"]
    # ... on the host plane alone: the device plane may lie anywhere
    for ns in (2_000_000, -2_000_000):
        moved = dict(cap, devices=hosttime._shifted(
            {"devices": cap["devices"], "spans": []}, ns)["devices"])
        assert rpctime.overlap(moved) == over
        assert rpctime.dispatch_tail(moved) == tail
    # the one reading that does follow the device plane: its clock's lead
    lead = rpctime.device_lead_ms(cap)
    assert 0.1 < lead["lo"] < 3.0 and lead["launches"] >= 8


def test_recorded_capture_has_a_clock_lead_and_a_cycle(recorded_loop):
    lead = hosttime.clock_lead_ms(recorded_loop)
    assert 0.5 < lead["lo"] < lead["hi"] < 4.0  # 1.27 .. 2.56 ms
    facts = _facts_of(recorded_loop, spans.SPAN_ROOTS)
    cycle = hosttime.step_cycle(facts)
    assert cycle["steps"] >= 8
    parts = sum(v for k, v in cycle.items() if k.startswith("own."))
    assert parts == pytest.approx(cycle["host_serial"])
    assert cycle["launch_wake"] > 0.5 and cycle["own.loop.emit"] > 0.5
    assert hosttime.launch_wake_ms_per_step(facts) == cycle["launch_wake"]


def test_hosttime_reads_nothing_from_a_program_without_the_spans(
        recorded_loop):
    """On the parent commit's capture (no `loop*` span, no new series) each
    reader returns None and raises nothing."""
    old = {"devices": recorded_loop["devices"],
           "spans": [s for s in recorded_loop["spans"]
                     if s[0].split(".")[0] in spans.SPAN_ROOTS
                     and s[0] != "step.commit.retire"]}
    facts = {"spans_capture": old, "hosttime_capture": old,
             "trace_capture": None, "metrics0": {"step_steps_total": 1.0},
             "metrics1": {"step_steps_total": 9.0}}
    assert hosttime.idle_pct(facts, under="loop.emit") is None
    assert hosttime.idle_pct(facts, under="unnamed") is None
    assert hosttime.emit_lag_ms(facts) is None
    assert hosttime.loop_ms_per_step(facts) is None
    assert hosttime.launch_wake_ms_per_step(facts) is None
    assert hosttime.capture_rates(facts) is None
    assert spans.idle_pct(facts, under="step.wait") is not None


@pytest.mark.parametrize("config", sorted(_SERVED))
def test_statusz_says_how_the_expert_layers_meet_their_matrices(served,
                                                                config):
    """`/statusz` `components.weights.moe_experts`: the form the expert
    matmuls of the built programs took, said while they were traced — on
    the CPU the plain one (on the chip "stack_kernel", or a daemon that
    fell back shows it without a capture); a dense model has no such
    line."""
    weights = served(config)["statusz"]["components"]["weights"]
    moe = any(s.startswith("moe_") for s in _SERVED[config]["series"])
    assert weights.get("moe_experts") == ("ragged_dot" if moe else None)


@pytest.mark.parametrize("config", sorted(_SERVED))
def test_the_permutation_counts_the_rows_it_moves(served, config):
    """ISSUE 65: beside the rows the experts ran, a model with experts
    counts the rows its permutation moved and the rounds it took beyond a
    layer call's first (`moe_rows_permuted_total`, `moe_extra_rounds_total`
    on `/metrics`; no per-layer entry reads them yet). On the CPU the stacks
    are cut and the extent is every pick: S*k rows a call, in one pass —
    the picks of ALL the experts, so a share's moved rows are its routed
    rows times experts over held."""
    m = served(config)["metrics"]
    if not any(s.startswith("moe_") for s in _SERVED[config]["series"]):
        assert not [k for k in m if k.startswith("moe_")]
        return
    for program in ("decode", "prefill"):
        moved = m[f'moe_rows_permuted_total{{program="{program}"}}']
        routed = m[f'moe_assignments_total{{program="{program}"}}']
        assert moved >= routed > 0
        assert m[f'moe_extra_rounds_total{{program="{program}"}}'] == 0


def _latent(config):
    return any(s.startswith("mla_") for s in _SERVED[config]["series"])


@pytest.mark.parametrize("config", sorted(_SERVED))
def test_statusz_says_what_a_prefill_kernel_step_covers(served, config):
    """`/statusz` `components.attention.mla_prefill`: by layer kind, for
    every count of columns the built chunk programs hand the latent
    prefill kernel, the heads, rows and columns one grid step covers —
    said while they were traced. On the CPU the plain form runs and the
    three are None (on the chip `block_s` 512 and `heads_per_step` above
    1, or a daemon that fell to a narrow tile shows it without a
    capture); a model without latent attention has no such component."""
    comps = served(config)["statusz"]["components"]
    if not _latent(config):
        assert "mla_prefill" not in comps.get("attention", {})
        return
    kinds = comps["attention"]["mla_prefill"]
    assert "full" in kinds and all(kinds.values())
    for calls in kinds.values():
        for call in calls:
            assert sorted(call) == ["block_q", "block_s", "columns",
                                    "heads_per_step"]
            assert call["columns"] > 0
            assert call["heads_per_step"] is call["block_s"] is None


def test_the_benchmark_counts():
    """Thirteen configurations, sixteen cells (one on four chips), 127 of
    the 128 per-layer entries the list holds (ISSUE 66; ROADMAP R0: the
    next configuration's entries do not fit)."""
    assert len(_BENCH["configs"]) == 13
    assert len(_BENCH["workloads"]) == 16
    assert sum(w["chips"] == 4 for w in _BENCH["workloads"]) == 1
    assert len(_BENCH["per_layer"]) == 127 <= 128
    assert len({w["config"] for w in _BENCH["workloads"]}) == 13
    assert "blocks" not in {m["layer"] for m in _BENCH["per_layer"]}


@pytest.mark.parametrize("config", sorted(_SERVED))
def test_statusz_says_which_form_each_layer_kinds_reads_took(served, config):
    """`/statusz` `components.attention.kinds`: for a model whose K and V
    leaves are by layer kind (it writes `attn_cached_positions_read_total`),
    the form each kind's chunk and decode reads took in the built programs
    — on the CPU the plain ones (on the chip "kernel" / "banded_kernel"
    and "paged_kernel", and Falcon-H1's `ssm_decode` reads "step_kernel"
    there as Brumby's `decode` does, or a daemon that fell back shows it
    without a capture) — and the pool's bytes by the kinds' leaves; a model with a
    STATE kind beside K and V (it writes `state_pool_*`) names that kind's
    forms too, and its leaves without a position axis are counted among
    the pool's bytes — whether they are a kind of their own or the slot
    leaves of the kind that pages K and V (`kv_cache.kinds` says which
    leaves a kind pages and which a slot holds whole); no other model has
    the line."""
    comps = served(config)["statusz"]["components"]
    series = _SERVED[config]["series"]
    # a K/V kind's window and rotation ride beside its forms (held below)
    tables = {kind: {n: f.pop(n) for n in ("window", "rotation") if n in f}
              for kind, f in comps.get("attention", {}).get(
                  "kinds", {}).items()}
    if any(s.startswith("state_pool_") for s in series):
        state = {"prefill": "chunked_jnp", "decode": "step_jnp"}
        kinds, leaves = comps["attention"]["kinds"], sorted(
            comps["kv_cache"]["bytes_by_leaf"])
        if "linear" in kinds and "kc" in leaves:
            # a state kind of ONE leaf beside a kind of K and V that
            # selects by blocks: a strided leaf of pooled keys, a read of a
            # list of blocks (on the chip "masked_kernel" / "list_kernel"
            # and "step_kernel")
            assert kinds == {"full": {"prefill": "plain",
                                      "decode": "list_gather"},
                             "linear": state}
            assert leaves == ["k", "kc", "state", "v"]
            assert comps["kv_cache"]["kinds"] == {
                "full": {"leaves": ["k", "v"], "strided_leaves": {"kc": 2},
                         "slot_leaves": [], "tables": "tables"},
                "linear": {"leaves": [], "slot_leaves": ["state"],
                           "tables": None}}
        elif "linear" in kinds:  # a state kind BESIDE a kind of K and V
            assert kinds == {"full": {"prefill": "plain",
                                      "decode": "gather_einsum"},
                             "linear": state}
            assert leaves == ["conv_tail", "k", "state", "v"]
        elif "ssm" in kinds:
            # blocks of ONE mixer: a state kind of slot leaves alone IN
            # PLACE of attention, a K/V kind for the attention blocks,
            # nothing for the expert blocks — which `components.blocks`
            # counts, with what the experts are handed
            assert kinds == {"full": {"prefill": "plain",
                                      "decode": "gather_einsum"},
                             "ssm": state}
            assert tables["full"] == {"window": None, "rotation": None}
            assert leaves == ["conv_tail", "k", "ssm_state", "v"]
            assert comps["kv_cache"]["kinds"] == {
                "full": {"leaves": ["k", "v"], "slot_leaves": [],
                         "tables": "tables"},
                "ssm": {"leaves": [], "tables": None,
                        "slot_leaves": ["conv_tail", "ssm_state"]}}
            blocks = comps["blocks"]
            assert blocks["kinds"] == {"ssm": 3, "experts": 3, "full": 1}
            assert blocks["moe"] == {"latent_size": 32, "picks": 6,
                                     "held": 4, "of": 16}
            m = served(config)["metrics"]
            assert m["state_pool_installs_total"] > 0
            # rows through W_down: a chunk's 16 positions, a step's 4
            # slots, three expert blocks each
            for program, rows in (("prefill", 16), ("decode", 4)):
                calls = m[f'moe_layer_calls_total{{program="{program}"}}']
                assert m[f'moe_latent_rows_total{{program="{program}"}}'] \
                    == calls * rows > 0
        elif "full" in kinds:  # ONE kind: paged K and V AND slot leaves
            assert kinds == {"full": {
                "prefill": "plain", "decode": "gather_einsum",
                "ssm_prefill": "chunked_jnp", "ssm_decode": "step_jnp"}}
            assert leaves == ["conv_tail", "k", "ssm_state", "v"]
            assert comps["kv_cache"]["kinds"] == {"full": {
                "leaves": ["k", "v"], "tables": "tables",
                "slot_leaves": ["conv_tail", "ssm_state"]}}
            m = served(config)["metrics"]
            assert m['kv_pool_blocks_in_use{kind="full"}'] >= 0
            assert m["state_pool_installs_total"] > 0
        else:  # no K/V layer at all: ONE kind, nothing paged
            assert kinds == {"retention": state}
            assert leaves == ["norm", "state"]
            m = served(config)["metrics"]
            assert not [k for k in m if "blocks" in k
                        and k.startswith(("serving_paged", "kv_pool"))]
        return
    by_kind = any(s.startswith("attn_cached_positions_read_total")
                  for s in series)
    if not by_kind:
        assert "kinds" not in comps.get("attention", {})
        return
    assert comps["attention"]["kinds"] == {
        kind: {"prefill": "plain", "decode": "gather_einsum"}
        for kind in ("full", "window")}
    assert sorted(comps["kv_cache"]["bytes_by_leaf"]) == [
        "k", "k_w", "v", "v_w"]
    # the window kind has a window and plain RoPE; the full kind none, and
    # is unrotated (K-EXAONE) or under a table of its OWN (Mellum2: YaRN)
    assert tables["window"]["window"] > 0 and tables["full"]["window"] is None
    assert tables["window"]["rotation"]["type"] == "default"
    full = tables["full"]["rotation"]
    assert full is None or (
        full["type"] == "yarn" and full["factor"] > 1
        and full["attention_factor"] != 1.0
        and full["theta"] == tables["window"]["rotation"]["theta"])


@pytest.mark.parametrize("config", sorted(_SERVED))
def test_statusz_says_the_pools_block_and_the_kernels_span(served, config):
    """`/statusz` `components.kv_cache`: a paged pool's `block_len`, and
    under `decode_group_span` the positions a group of the paged decode
    kernel covers in the built decode program, by the first leaf it reads
    — empty on the CPU, where no program calls the kernel (on the chip
    {"latent": 1024} for JoyAI and dots3, {"k": 512} Keye, 256 K-EXAONE and
    Solar, 128 OLMoE and GPT-2 Large: ISSUE 51); a model with nothing to
    page has neither line."""
    kv = served(config)["statusz"]["components"]["kv_cache"]
    paged = any(k.startswith(("serving_paged", "kv_pool"))
                and "blocks" in k for k in served(config)["metrics"])
    if not paged:
        assert "block_len" not in kv and "decode_group_span" not in kv
        return
    assert kv["block_len"] in (8, 16) and kv["decode_group_span"] == {}


# ----------------------------------------------------------------------
# program names and scope prefixes: the lowered step programs
# ----------------------------------------------------------------------

@pytest.fixture(scope="module")
def lowered():
    """{configuration: {program name: lowered text with locations}} of the
    step programs the batcher dispatches while two requests run through
    it, built as the daemon builds it at the cell's rehearsal size."""
    import jax
    import jax.numpy as jnp
    from test_chip_compile import first_calls

    from dnn_tpu.models.llama import LlamaConfig, family_rows
    from dnn_tpu.node import _stack_and_release
    from dnn_tpu.registry import get_model
    from dnn_tpu.runtime.serving import ContinuousBatcher

    cache = {}

    def of(config_name):
        if config_name in cache:
            return cache[config_name]
        run = cells.resolve(_first_cell(config_name),
                            rehearse=True)["config"]["run"]
        spec = get_model(run["model"])
        cfg = spec.config
        held_in = None if run["dtype"] == "float32" else jnp.dtype(
            run["dtype"])

        prepared = _stack_and_release(
            spec.init(jax.random.PRNGKey(0)), cfg, held_in)
        family = None
        if isinstance(cfg, LlamaConfig):  # as node._serve_lm picks it
            family = family_rows(cfg, compute_dtype=held_in)
        batcher = ContinuousBatcher(
            cfg, prepared, compute_dtype=held_in, family=family,
            kv="auto",  # the daemon's default (LMServer's)
            **run["serve_flags"])
        calls = first_calls([(batcher, (
            "_prefill_chunk", "_prefill_finish", "_decode"))], prompt_len=24)
        found = {}
        for fn, args in calls.values():
            t = fn.lower(*args).as_text(debug_info=True)
            found[re.search(r"module @(\S+)", t).group(1)] = t
        cache[config_name] = found
        return found

    return of


def _op_name_components(text):
    return {part for loc in re.findall(r'loc\("([^"]+)"', text)
            for part in loc.split("/")}


@pytest.mark.parametrize("config,program", _cases(_SERVED, "programs"))
def test_program_is_one_the_batcher_dispatches(lowered, config, program):
    assert program in lowered(config), sorted(lowered(config))


@pytest.mark.parametrize("config,prefix",
                         _cases(_SERVED, "scopes", once=False))
def test_scope_names_operations_of_the_step_programs(lowered, config,
                                                     prefix):
    parts = set().union(*(_op_name_components(t)
                          for t in lowered(config).values()))
    assert any(p.startswith(prefix) for p in parts), prefix


@pytest.mark.parametrize("config,program", _cases(_PIPED, "programs"))
def test_program_is_the_one_the_engine_dispatches(tmp_path, config, program):
    """The staged forward, built as `chipbench/pipe.py` builds it at the
    cell's rehearsal size: the jitted function `engine.run` calls."""
    import jax
    import numpy as np

    from dnn_tpu.config import TopologyConfig
    from dnn_tpu.runtime.engine import PipelineEngine

    cell = cells.resolve(_first_cell(config), rehearse=True)
    run, traffic = cell["config"]["run"], cell["traffic"]
    topo = {"nodes": [{"id": f"node{i + 1}", "part_index": i,
                       "address": "127.0.0.1:0"}
                      for i in range(run["stages"])],
            "num_parts": run["stages"], "model": run["model"],
            "dtype": run["dtype"], "runtime": run["runtime"],
            "microbatches": traffic["microbatches"], "device_type": "cpu"}
    path = tmp_path / "engine_config.json"
    path.write_text(json.dumps(topo))
    engine = PipelineEngine(TopologyConfig.from_json(str(path)),
                            role="full", rng_seed=0)
    ids = np.zeros((traffic["batch"], traffic["seq"]), np.int32)
    jaxpr = jax.make_jaxpr(engine.run)(ids)
    dispatched = {"jit_" + e.params["name"] for e in jaxpr.eqns
                  if "jaxpr" in e.params and "name" in e.params}
    assert program in dispatched, dispatched


# ----------------------------------------------------------------------
# the paged kernel's counters: what they count, a step at a time
# ----------------------------------------------------------------------

@pytest.mark.parametrize("kv", ["paged", "dense"])
def test_attn_block_counters_follow_the_live_blocks(monkeypatch, kv):
    """`step_attn_live_blocks_total` advances by the blocks that hold a
    live position (sum over the slots of ceil(positions / block_len)) and
    `step_attn_table_blocks_total` by slots x blocks a slot, each decode
    step of a paged pool; a dense cache writes neither."""
    import jax
    import numpy as np

    from dnn_tpu import obs
    from dnn_tpu.models import gpt
    from dnn_tpu.obs.timeline import StepClock
    from dnn_tpu.runtime.serving import ContinuousBatcher
    from dnn_tpu.utils.metrics import Metrics, render_prometheus

    monkeypatch.setenv("DNN_TPU_OBS", "1")
    if not obs.enabled():
        pytest.skip("observability gate is off in this process")
    cfg = gpt.GPTConfig(vocab_size=89, block_size=128, n_layer=2, n_head=2,
                        n_embd=32)
    slots, max_len, bp = 3, 64, 8
    srv = ContinuousBatcher(
        cfg, gpt.prepare_stacked(gpt.init(jax.random.PRNGKey(0), cfg), cfg),
        slots=slots, max_len=max_len, prompt_pad=16, kv=kv,
        **({"block_len": bp} if kv == "paged" else {}))
    reg = Metrics()
    srv.step_clock = clk = StepClock(registry=reg)

    def series():
        return dict(line.rsplit(" ", 1)
                    for line in render_prometheus(reg).splitlines()
                    if line and not line.startswith("#"))

    srv.submit(np.arange(1, 6), max_new_tokens=20)    # 5 positions
    srv.submit(np.arange(1, 18), max_new_tokens=20)   # 17: three blocks
    want_live = want_table = 0
    for _ in range(6):
        srv.step()
        held = [r["prompt_len"] + len(r["emitted"])
                for r in srv._slot_req if r is not None]
        assert len(held) == 2
        want_live += sum(-(-n // bp) for n in held)
        want_table += slots * (max_len // bp)
        if kv == "paged":
            assert clk.attn_blocks_total == [want_live, want_table]
    got = series()
    if kv == "paged":
        assert float(got["step_attn_live_blocks_total"]) == want_live
        assert float(got["step_attn_table_blocks_total"]) == want_table
        assert 0 < want_live < want_table
    else:
        assert clk.attn_blocks_total == [0, 0]
        assert not [k for k in got if k.startswith("step_attn_")]
        assert "step_steps_total" in got


@pytest.mark.parametrize("case", ["kernel", "einsum", "obs-off"])
def test_attn_group_counters_follow_the_kernels_groups(monkeypatch, case):
    """ISSUE 51: `step_attn_groups_total` advances by the groups the paged
    decode kernel walks — sum over its layers and the slots of ceil(blocks
    / blocks a group) — and `step_attn_full_groups_total` by the whole
    ones among them (floor), whose copies are straight-line code, each
    decode step, from the slots' positions; `/statusz` says the span. A
    pool read by gather and einsums writes neither, and nothing is noted
    with observability off."""
    import jax
    import numpy as np

    from dnn_tpu import obs
    from dnn_tpu.models import gpt
    from dnn_tpu.obs.timeline import StepClock
    from dnn_tpu.runtime.serving import ContinuousBatcher
    from dnn_tpu.utils.metrics import Metrics, render_prometheus
    from tests.test_decode_hotpath import _pinned_span

    monkeypatch.setattr(obs, "_enabled", case != "obs-off")
    cfg = gpt.GPTConfig(vocab_size=89, block_size=512, n_layer=2, n_head=2,
                        n_embd=32)
    slots, max_len, bp, span = 3, 256, 8, 128
    srv = ContinuousBatcher(
        cfg, gpt.prepare_stacked(gpt.init(jax.random.PRNGKey(0), cfg), cfg),
        slots=slots, max_len=max_len, prompt_pad=16, kv="paged",
        block_len=bp,
        attn_kernel=False if case == "einsum" else "interpret")
    reg = Metrics()
    srv.step_clock = clk = StepClock(registry=reg)
    assert srv.attn_kernel_span() == 0  # no decode program traced yet
    # 16 blocks a group: 5 positions are one partial group; 135 to 140
    # are 17 or 18 blocks, one whole group and one partial; 128 + 6 steps
    # stay at 17 blocks
    srv.submit(np.arange(1, 6), max_new_tokens=20)
    srv.submit(1 + np.arange(134) % 80, max_new_tokens=20)
    want = [0, 0]
    with _pinned_span(span):
        for _ in range(6):
            srv.step()
            held = [r["prompt_len"] + len(r["emitted"])
                    for r in srv._slot_req if r is not None]
            assert len(held) == 2
            blocks = [-(-n // bp) for n in held]
            want[0] += cfg.n_layer * sum(-(-b // 16) for b in blocks)
            want[1] += cfg.n_layer * sum(b // 16 for b in blocks)
    got = dict(line.rsplit(" ", 1)
               for line in render_prometheus(reg).splitlines()
               if line and not line.startswith("#"))
    if case == "kernel":
        assert srv.attn_kernel_span() == span
        assert srv._paged_codec.kernel_spans == {"k": span}
        assert clk.attn_groups_total == want == [cfg.n_layer * 6 * 3,
                                                 cfg.n_layer * 6]
        assert float(got["step_attn_groups_total"]) == want[0]
        assert float(got["step_attn_full_groups_total"]) == want[1]
    else:
        assert clk.attn_groups_total == [0, 0]
        assert not [k for k in got if "attn_groups" in k
                    or "attn_full_groups" in k]
        if case == "obs-off":
            assert clk.attn_blocks_total == [0, 0]
        else:
            assert srv.attn_kernel_span() == 0
            assert float(got["step_attn_live_blocks_total"]) > 0
