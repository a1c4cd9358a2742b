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

import pytest

from chipbench import cells, spans, tracered
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
            while taker.is_alive():  # requests all through the capture
                ask(8)
            taker.join()
            found = {"metrics": daemon.metrics(),
                     "stepz": daemon.get_json("/stepz"),
                     "statusz": daemon.get_json("/statusz"),
                     "span_names": {s[0] for s in spans.load_capture(
                         tracered.find_xplane(box["capture"]))["spans"]}}
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
