"""Test harness: run every test on a virtual 8-device CPU mesh.

The reference has no tests at all (SURVEY.md §4); its only multi-node story
is "N localhost processes". The TPU-native analog is N virtual host devices:
we force the CPU platform with 8 devices *before* JAX initializes, so the
pipeline/mesh tests (tests/test_pipeline*.py) exercise real
shard_map/ppermute collectives without TPU hardware.
"""

import os

# The suite always runs on the CPU backend, whatever the host has: the
# mesh tests need 8 devices, the parity tolerances assume f32 matmuls (a
# TPU's default matmul precision is bf16), and a test run must never take
# the chip from a process that is using it. Both variables are read when
# the backend first initializes, so setting them before `import jax` is
# enough. The persistent compile cache is off for the suite and every
# child it spawns: tests count compilations, and entries compiled for a
# described (not attached) TPU cannot be read back without a chip.
os.environ["JAX_PLATFORMS"] = "cpu"
os.environ["JAX_ENABLE_COMPILATION_CACHE"] = "false"
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
    of ~500 tests (plus the device buffers their closures pin); late in
    the run an XLA CPU compile can then die with a hard SIGSEGV inside
    backend_compile_and_load — observed reproducibly at ~85% of the
    suite, while the same test passes in isolation. Clearing BETWEEN
    modules (never within) keeps intra-module contracts intact — e.g.
    the serving tests' jit-cache-size regression checks.

    Gated on actual resident memory (default 3 GB, override with
    DNN_TEST_CLEAR_RSS_GB; 0 = clear every module, the old behavior):
    an unconditional clear forced every module to recompile the shared
    helpers, costing the tier-1 run a large slice of its
    window for protection that is only needed near the memory ceiling."""
    yield
    threshold = float(os.environ.get("DNN_TEST_CLEAR_RSS_GB", "3"))
    if _rss_gb() >= threshold:
        import gc

        jax.clear_caches()
        gc.collect()


@pytest.fixture(scope="session")
def devices():
    return jax.devices()
