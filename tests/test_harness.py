"""The test harness's own contract (`tests/conftest.py`).

The suite pays for a compiled program once a run: the persistent compile
cache is on, in one directory every process of the run shares. What that
rests on is held here — a hit still fires the event the compile counters
read, the modules that compile for a described TPU are outside it, and a
child a test spawns lands in the same directory.
"""

import os
import subprocess
import sys
import tempfile

import jax
import jax.numpy as jnp
import pytest
from jax import monitoring

from dnn_tpu import obs

# the opt-out under test, by the name pytest resolves it
from test_chip_compile import chip  # noqa: F401

_HIT = "/jax/compilation_cache/cache_hits"
_MISS = "/jax/compilation_cache/cache_misses"
_ASKED = "/jax/compilation_cache/compile_requests_use_cache"

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture
def events():
    """The names of the `jax.monitoring` events fired while a test runs."""
    seen = []

    def listen(name, **kw):
        seen.append(name)

    monitoring.register_event_listener(listen)
    yield seen
    monitoring.unregister_event_listener(listen)


def _closure(salt):
    """A fresh `jax.jit` closure of one function, as each batcher a test
    builds makes of its step programs; `salt` makes the program this
    run's own, so an earlier run's directory does not hold it."""
    return jax.jit(lambda x: jnp.tanh(x) * salt + 1.0)


def test_second_closure_hits_the_cache_and_is_still_counted(events):
    path = os.environ["JAX_COMPILATION_CACHE_DIR"]
    assert jax.config.jax_compilation_cache_dir == path
    assert jax.config.jax_enable_compilation_cache
    assert os.path.isdir(path) and os.access(path, os.W_OK)
    assert os.path.dirname(path) == tempfile.gettempdir()
    assert jax.__version__ in os.path.basename(path)
    assert not path.startswith(REPO + os.sep)

    assert obs.install_compile_telemetry()
    m = obs.metrics()
    x = jnp.ones((5,))
    salt = float(int.from_bytes(os.urandom(3), "little"))
    _closure(salt)(x).block_until_ready()  # the program enters the cache
    del events[:]
    before = m.counters["jax_compilations_total"]
    _closure(salt)(x).block_until_ready()
    assert events.count(_HIT) == 1 and _MISS not in events
    assert m.counters["jax_compilations_total"] == before + 1


def test_child_of_the_suite_lands_in_the_same_directory():
    code = ("import jax\n"
            "from dnn_tpu.utils.compile_cache import enable_compile_cache\n"
            "print(enable_compile_cache())\n"
            "print(jax.config.jax_compilation_cache_dir)\n"
            "print(jax.config.jax_enable_compilation_cache)")
    proc = subprocess.run([sys.executable, "-c", code], cwd=REPO,
                          capture_output=True, text=True, timeout=120)
    assert proc.returncode == 0, proc.stderr[-2000:]
    path = os.environ["JAX_COMPILATION_CACHE_DIR"]
    assert proc.stdout.split() == [path, path, "True"]


# last in the file: the fixture is module-scoped, so the cache stays off
# from here to the end of this module
def test_cache_is_off_inside_the_described_chip_fixture(chip,  # noqa: F811
                                                        events):
    assert not jax.config.jax_enable_compilation_cache
    _closure(3.0)(jnp.ones((5,))).block_until_ready()
    assert not {_HIT, _MISS, _ASKED} & set(events)
