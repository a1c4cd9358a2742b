"""Test harness: run every test on a virtual 8-device CPU mesh.

The reference has no tests at all (SURVEY.md §4); its only multi-node story
is "N localhost processes". The TPU-native analog is N virtual host devices:
we force the CPU platform with 8 devices *before* JAX initializes, so the
pipeline/mesh tests (tests/test_pipeline*.py) exercise real
shard_map/ppermute collectives without TPU hardware.

The run also shares one persistent compile cache (below): a program is
compiled once a run, not once a case, a worker and a spawned daemon.
"""

import importlib.metadata
import os
import tempfile

# The suite always runs on the CPU backend, whatever the host has: the
# mesh tests need 8 devices, the parity tolerances assume f32 matmuls (a
# TPU's default matmul precision is bf16), and a test run must never take
# the chip from a process that is using it. Both variables are read when
# the backend first initializes, so setting them before `import jax` is
# enough.
os.environ["JAX_PLATFORMS"] = "cpu"
# One compile a program a run: the persistent compile cache is ON for the
# suite. Every batcher a test builds makes fresh `jax.jit` closures, so the
# same step programs reach XLA again in the next case, in each xdist
# worker and in every daemon a test spawns; with the cache the second
# meeting is a read. ONE directory for all of those processes (the
# directory is part of the key; children inherit the variable, JAX reads
# it itself and `utils.compile_cache` sets no other): fixed, under the
# system's temp directory — never under the checkout, which the driver
# copies — and named by the installed jax, so an upgrade starts clean. It
# is set whatever the caller's environment held: a test run must not
# depend on it. The key is JAX's (HLO, compile options, backend,
# version), so entries an earlier run left are safe to read; `rm -rf` the
# directory for a cold-start timing. Every program is stored, the
# thousands of sub-second ones too: on the four heaviest serving files a
# one-second floor measured no gain at all over the cache off, no floor a
# quarter of their case time (PR 46). A hit still fires the
# backend-compile event the compile counters read (`obs/compile_watch`;
# held by tests/test_harness.py). Opt-outs, each in its own module:
# `test_chip_compile`'s `chip` fixture (an entry compiled for a
# described, not attached, TPU cannot be read back without a chip), and
# the children `test_chip_smoke` starts without the variable, which would
# write into the checkout.
_cache_dir = os.path.join(
    tempfile.gettempdir(), "dnn_tpu_test_jax_cache-{}-{}".format(
        *map(importlib.metadata.version, ("jax", "jaxlib"))))
os.makedirs(_cache_dir, exist_ok=True)
os.environ["JAX_COMPILATION_CACHE_DIR"] = _cache_dir
os.environ["JAX_ENABLE_COMPILATION_CACHE"] = "true"
os.environ["JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS"] = "0"
os.environ["JAX_PERSISTENT_CACHE_MIN_ENTRY_SIZE_BYTES"] = "0"
# A hit logs two error-level `cpu_aot_loader.cc` lines of 4 kB each about
# tuning hints (`+prefer-no-scatter`, `+prefer-no-gather`: written and read
# on the same machine, harmless), thousands a run. They go, with XLA's
# other error-level chatter, so that what a failing test prints can be
# found; a failure itself reaches pytest as an exception, and fatal lines
# still print. Export the variable to see them.
os.environ.setdefault("TF_CPP_MIN_LOG_LEVEL", "3")
_flags = os.environ.get("XLA_FLAGS", "")
if "--xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (_flags + " --xla_force_host_platform_device_count=8").strip()

import jax  # noqa: E402

import pytest  # noqa: E402


@pytest.fixture(scope="session", autouse=True)
def _cpu_mesh_guard():
    """Fail loudly if the suite ever lands on an accelerator backend."""
    assert jax.default_backend() == "cpu", f"suite must run on CPU, got {jax.default_backend()}"
    assert len(jax.devices()) >= 8, f"expected >=8 virtual devices, got {jax.devices()}"


def _rss_gb() -> float:
    """Current resident set of this process, GB. Non-Linux hosts fall
    back to getrusage peak RSS; an unreadable RSS returns inf so the
    gate FAILS CLOSED (clears every module — the old, safe behavior)."""
    try:
        with open("/proc/self/statm") as f:
            pages = int(f.read().split()[1])
        return pages * os.sysconf("SC_PAGE_SIZE") / 1e9
    except (OSError, ValueError, IndexError):
        pass
    try:
        import resource
        import sys

        ru = resource.getrusage(resource.RUSAGE_SELF).ru_maxrss
        # ru_maxrss is KB on Linux, bytes on macOS
        return ru / 1e9 if sys.platform == "darwin" else ru / 1e6
    except Exception:  # noqa: BLE001 — no RSS signal at all
        return float("inf")


@pytest.fixture(scope="module", autouse=True)
def _drop_compile_caches_between_modules():
    """Free compiled executables between modules WHEN MEMORY IS HIGH.

    A single pytest process otherwise accumulates every jitted program
    of its share of the suite's 1860 tests (plus the device buffers their
    closures pin); late in
    the run an XLA CPU compile can then die with a hard SIGSEGV inside
    backend_compile_and_load — observed reproducibly at ~85% of the
    suite, while the same test passes in isolation. Clearing BETWEEN
    modules (never within) keeps intra-module contracts intact — e.g.
    the serving tests' jit-cache-size regression checks.

    Gated on actual resident memory (default 3 GB, override with
    DNN_TEST_CLEAR_RSS_GB; 0 = clear every module, the old behavior):
    an unconditional clear forced every module to recompile the shared
    helpers, costing the tier-1 run a large slice of its
    window for protection that is only needed near the memory ceiling.
    This is the IN-MEMORY pathology (`utils/xla_cache.py`), not the
    persistent cache's: after a clear a program is traced and lowered
    again and its executable read back from disk."""
    yield
    threshold = float(os.environ.get("DNN_TEST_CLEAR_RSS_GB", "3"))
    if _rss_gb() >= threshold:
        import gc

        jax.clear_caches()
        gc.collect()


@pytest.fixture(scope="session")
def devices():
    return jax.devices()
