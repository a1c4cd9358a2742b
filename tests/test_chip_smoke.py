"""chip_smoke.py's own logic, at tiny size on the CPU.

The smoke itself only passes on a chip. What can be held to account here:
who owns the device (the parent initializes no backend while the daemon
child lives; a chip-holding daemon probes in-process), that no chip means
a non-zero exit and no `"ok": true` line, where the compile cache lands,
and that a config naming `tpu` never runs on the CPU.
"""

import json
import os
import shutil
import subprocess
import sys

import jax
import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

import chip_smoke  # noqa: E402

TINY = dict(model="gpt2-test", device_type="cpu", dtype="float32", seed=3,
            prompt_lens=(3, 9, 20, 30, 40), max_new=8, vocab=256,
            watchdog_s=1.0)


def _python(code, *, env=None, cwd=REPO, timeout=300):
    return subprocess.run([sys.executable, "-c", code], env=env, cwd=cwd,
                          capture_output=True, text=True, timeout=timeout)


def _env(**extra):
    """The suite's environment without its compile-cache directory
    (conftest's, or an operator's): the children here are held to the rule
    for a process that was given none, which places the cache in the
    checkout — so the cache itself is off for them, or a test run would
    write its programs into the tree."""
    env = {k: v for k, v in os.environ.items()
           if k != "JAX_COMPILATION_CACHE_DIR"}
    env.update(PYTHONPATH=REPO, JAX_ENABLE_COMPILATION_CACHE="false")
    env.update(extra)
    return env


# ----------------------------------------------------------------------
# the serve phase, end to end, in a process of its own (this one has long
# since initialized its backend)
# ----------------------------------------------------------------------

_DRIVE = """
import json, tempfile
import chip_smoke as cs
from jax._src import xla_bridge

with tempfile.TemporaryDirectory() as wd:
    r = cs.serve_phase(wd, **{tiny!r})
    off_device = not xla_bridge.backends_are_initialized()
    import jax
    from dnn_tpu.registry import get_model
    spec = get_model("gpt2-test")
    params = spec.init(jax.random.PRNGKey({seed}))
    worst, sigma = cs.teacher_forced_margins(
        spec.config, params, r["prompts"], r["tokens"])
    kinds, shape = cs.serving_program_kinds(
        cs.serve_argv(r["config_path"], seed={seed}))
print("RESULT " + json.dumps({{
    "off_device": off_device, "drain_rc": r["drain_rc"],
    "statusz": r["statusz"], "tokens": [t.tolist() for t in r["tokens"]],
    "worst": worst, "kinds": kinds, "shape": shape}}))
"""


@pytest.fixture(scope="module")
def tiny_run():
    proc = _python(_DRIVE.format(tiny=TINY, seed=TINY["seed"]), env=_env())
    assert proc.returncode == 0, proc.stdout[-2000:] + proc.stderr[-4000:]
    line = [l for l in proc.stdout.splitlines() if l.startswith("RESULT ")]
    return json.loads(line[-1][len("RESULT "):])


def test_parent_holds_no_backend_while_the_daemon_lives(tiny_run):
    # serve_phase asserts it before the spawn and before the SIGTERM; this
    # is the same reading once the phase has returned
    assert tiny_run["off_device"] is True
    # and the assertion has teeth: this process HAS a backend
    with pytest.raises(RuntimeError, match="initialized a JAX backend"):
        chip_smoke._assert_off_device("test")


def test_daemon_answers_concurrent_requests_and_drains_rc0(tiny_run):
    assert tiny_run["drain_rc"] == 0
    assert len(tiny_run["tokens"]) == len(TINY["prompt_lens"]) >= 4
    assert all(len(t) == TINY["max_new"] for t in tiny_run["tokens"])


def test_chip_holding_daemon_probes_in_process(tiny_run):
    # a real daemon under --watchdog_s, read over /statusz after three
    # probe periods: ok, and by the in-process probe
    assert tiny_run["statusz"]["state"] == "ok"
    assert "in-process" in tiny_run["statusz"]["device"]


def test_in_process_probe_answers_for_the_backend_this_process_holds():
    from dnn_tpu.obs.watchdog import Watchdog, in_process_device_probe

    ok, detail = in_process_device_probe(5.0)
    assert ok and jax.default_backend() in detail and "in-process" in detail
    wd = Watchdog(period_s=60.0, probe_deadline_s=5.0,
                  device_probe=in_process_device_probe)
    wd._run_probe()
    assert wd.status()["components"]["device"]["state"] == "ok"


def test_served_tokens_pass_the_teacher_forced_check(tiny_run):
    assert tiny_run["worst"] <= chip_smoke.MARGIN_BOUND


def test_program_kinds_agree_with_the_policy_off_tpu(tiny_run):
    assert tiny_run["shape"]["paged"] is True
    assert set(tiny_run["kinds"]) == {"prefill_chunk", "prefill_finish",
                                      "decode"}
    for kind in tiny_run["kinds"].values():
        assert kind == {"pallas": False, "policy_says_pallas": False}


def test_teacher_forced_check_catches_wrong_tokens():
    from dnn_tpu.registry import get_model

    spec = get_model("gpt2-test")
    params = spec.init(jax.random.PRNGKey(0))
    prompts = chip_smoke.make_prompts(0, 256, (5, 17))
    wrong = [np.random.default_rng(1).integers(0, 256, 8).astype(np.int32)
             for _ in prompts]
    worst, sigma = chip_smoke.teacher_forced_margins(
        spec.config, params, prompts, wrong)
    assert worst > chip_smoke.MARGIN_BOUND and sigma > 0


# ----------------------------------------------------------------------
# no chip: non-zero exit, no "ok" line
# ----------------------------------------------------------------------

@pytest.mark.parametrize("args", [[], ["--chips", "4"]],
                         ids=["one_chip", "four_chips"])
def test_no_chip_exits_nonzero_and_prints_no_ok_line(args):
    proc = subprocess.run([sys.executable, "chip_smoke.py"] + args,
                          cwd=REPO, env=_env(JAX_PLATFORMS="cpu"),
                          capture_output=True, text=True, timeout=300)
    assert proc.returncode != 0
    assert '"ok"' not in proc.stdout
    assert "tpu" in proc.stderr.lower()


def test_alone_in_a_directory_it_fails_without_a_result(tmp_path):
    shutil.copy(os.path.join(REPO, "chip_smoke.py"), tmp_path)
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    proc = subprocess.run([sys.executable, "chip_smoke.py"], cwd=tmp_path,
                          env=env, capture_output=True, text=True,
                          timeout=120)
    assert proc.returncode != 0
    assert '"ok"' not in proc.stdout


# ----------------------------------------------------------------------
# the compile cache can be placed from outside
# ----------------------------------------------------------------------

_WHERE = ("import json, jax, dnn_tpu\n"
          "from dnn_tpu.utils.compile_cache import enable_compile_cache\n"
          "at_import = jax.config.jax_compilation_cache_dir\n"
          "print(json.dumps([at_import, enable_compile_cache(), "
          "enable_compile_cache(), jax.config.jax_compilation_cache_dir]))")


def test_cache_dir_from_the_environment_is_the_only_one(monkeypatch,
                                                        tmp_path):
    from dnn_tpu.utils import compile_cache

    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))

    def no_update(name, value):
        raise AssertionError(f"code set {name}={value!r}")

    monkeypatch.setattr(jax.config, "update", no_update)
    assert compile_cache.enable_compile_cache() == str(tmp_path)
    monkeypatch.undo()
    # and JAX itself reads the variable: nothing else is configured
    proc = _python(_WHERE, env=_env(JAX_COMPILATION_CACHE_DIR=str(tmp_path)))
    assert json.loads(proc.stdout.splitlines()[-1]) == [str(tmp_path)] * 4


def test_cache_dir_unset_is_fixed_in_the_checkout(tmp_path):
    want = os.path.join(REPO, ".jax_cache")
    # two processes started from different directories, two calls each;
    # and `import dnn_tpu` alone switches nothing on
    for cwd in (REPO, str(tmp_path)):
        proc = _python(_WHERE, env=_env(), cwd=cwd)
        assert proc.returncode == 0, proc.stderr[-2000:]
        assert json.loads(proc.stdout.splitlines()[-1]) == [None] + [want] * 3


def test_node_main_enables_the_cache_before_the_engine(monkeypatch, tmp_path):
    """The daemon child and the smoke's own process land in the same
    directory because both go through the helper: node.main calls it
    before it builds an engine."""
    from dnn_tpu import node
    from dnn_tpu.runtime import engine as engine_mod
    from dnn_tpu.utils import compile_cache

    order = []
    monkeypatch.setattr(compile_cache, "enable_compile_cache",
                        lambda: order.append("cache"))

    class Stop(Exception):
        pass

    def no_engine(*a, **k):
        order.append("engine")
        raise Stop("stop here")

    monkeypatch.setattr(node, "PipelineEngine", no_engine)
    cfg = chip_smoke._write_config(str(tmp_path), model="mlp",
                                   device_type="cpu", dtype="float32")
    assert node.main(["--node_id", "node1", "--config", cfg]) == 1
    assert order == ["cache", "engine"]
    assert engine_mod.PipelineEngine is not no_engine


# ----------------------------------------------------------------------
# a config that names the TPU never runs on the CPU
# ----------------------------------------------------------------------

def test_explicit_tpu_without_a_tpu_is_an_error(tmp_path, caplog):
    from dnn_tpu import node
    from dnn_tpu.config import TopologyConfig
    from dnn_tpu.runtime.engine import PipelineEngine, _pick_devices

    cfg = chip_smoke._write_config(str(tmp_path), model="mlp",
                                   device_type="tpu", dtype="float32")
    with pytest.raises(RuntimeError, match="device_type='tpu'"):
        PipelineEngine(TopologyConfig.from_json(cfg))
    # the CLI turns it into one error line and rc=1; nothing is served
    with caplog.at_level("ERROR"):
        assert node.main(["--node_id", "node1", "--config", cfg]) == 1
    # absent key = JAX's default backend; cpu is always there
    assert _pick_devices(None) == jax.devices()
    assert _pick_devices("cpu") == jax.devices("cpu")
    with pytest.raises(ValueError, match="device_type"):
        TopologyConfig.from_dict({"nodes": [], "device_type": "gpu"})


def test_supervising_parent_initializes_no_backend():
    code = """
import sys
import dnn_tpu.chaos.supervisor as sup
from dnn_tpu import node
from jax._src import xla_bridge

class Spawned:
    state = "crashloop"   # main() gives up at once
    def __init__(self, spawn, **kw):
        self.clean = not xla_bridge.backends_are_initialized()
    def start(self):
        print("CLEAN_AT_SPAWN", self.clean)

sup.Supervisor = Spawned
rc = node.main(["--node_id", "n", "--config", "none.json", "--serve_lm",
                "--supervise"])
print("CLEAN_AT_EXIT", not xla_bridge.backends_are_initialized(), rc)
"""
    proc = _python(code, env=_env())
    assert "CLEAN_AT_SPAWN True" in proc.stdout, proc.stderr[-2000:]
    assert "CLEAN_AT_EXIT True 1" in proc.stdout


# ----------------------------------------------------------------------
# built from what git commits
# ----------------------------------------------------------------------

def test_native_binary_is_keyed_on_the_source_text(tmp_path):
    from dnn_tpu import native

    if shutil.which("g++") is None:
        pytest.skip("no toolchain")
    src = tmp_path / "thing.cpp"
    src.write_text('extern "C" int answer() { return 1; }\n')
    stale = tmp_path / "_thing_1790424195.so"  # an mtime-era name
    stale.write_bytes(b"not a library")
    first = native._build_src(str(src), "thing")
    assert first and first != str(stale) and not stale.exists()
    # same text, newer mtime: the same binary
    os.utime(src, (1, 1))
    assert native._build_src(str(src), "thing") == first
    # other text: another name, and the old binary goes
    src.write_text('extern "C" int answer() { return 2; }\n')
    second = native._build_src(str(src), "thing")
    assert second != first and not os.path.exists(first)
