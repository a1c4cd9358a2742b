"""The main path's kernels, compiled for the chip without the chip.

The TPU's compiler is installed beside the CPU backend and compiles for a
DESCRIBED v5e (on-chip-measurement guide, section 2): it refuses what the
real chip's compiler would refuse — a slice off the tiling, too much fast
memory, a kernel that cannot be partitioned — which interpret mode never
shows. Nothing runs, so nothing here says a result is right or fast; a
compile that passes is not a chip run.

Shapes only (a described device holds no array), `interpret=False` passed
explicitly (`jax.default_backend()` still says cpu here), every test
skipped — not failed — where the topology cannot be described.
"""

import os

os.environ.setdefault("TPU_LOG_DIR", "disabled")  # or the compiler logs to /tmp
# compiling for a described chip opens no device, so several test processes
# (pytest-xdist workers) may load the TPU compiler at once; without this
# all but the first fail to describe the topology and skip
os.environ.setdefault("ALLOW_MULTIPLE_LIBTPU_LOAD", "1")

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import SingleDeviceSharding

from dnn_tpu.ops.pallas import cached_attention as ca
from dnn_tpu.ops.pallas.flash_attention import flash_attention

F32, BF16, I8 = jnp.float32, jnp.bfloat16, jnp.int8


@pytest.fixture(scope="module")
def chip():
    """One described v5e chip, with the persistent compile cache off: an
    entry compiled for a described device cannot be read back without a
    chip, and the next compile would warn about it."""
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — no TPU compiler on this host
        pytest.skip(f"cannot describe a v5e topology here: {e}")
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _compile(chip, fn, *shapes):
    args = [jax.ShapeDtypeStruct(s, d, sharding=chip) for s, d in shapes]
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()
    return compiled


# (slots, kv heads, query rows per kv head, head dim): GPT-2 as the smoke
# serves it, and a GQA shape with 128-wide heads
GPT2 = (4, 12, 1, 64)
GQA = (4, 8, 4, 128)
BLOCK_LEN, CTX = 16, 1024


@pytest.mark.parametrize("shape,dtype", [
    (GPT2, F32), (GPT2, BF16), (GPT2, I8), (GQA, BF16)])
def test_paged_decode_kernel_compiles(chip, shape, dtype):
    b, hk, r, d = shape
    nb = CTX // BLOCK_LEN
    pool = ((b * nb + 1, hk, BLOCK_LEN, d), dtype)
    scales = ((b * nb + 1, hk, BLOCK_LEN), F32)
    quant = dtype == I8
    qdt = BF16 if quant else dtype

    def fn(q, kp, vp, tables, pos, *ksvs):
        ks, vs = ksvs if quant else (None, None)
        return ca.paged_decode_attention(q, kp, vp, tables, pos, ks=ks,
                                         vs=vs, interpret=False)

    _compile(chip, fn, ((b, hk, r, d), qdt), pool, pool,
             ((b, nb), jnp.int32), ((b,), jnp.int32),
             *([scales, scales] if quant else []))


@pytest.mark.parametrize("shape", [GPT2, GQA])
def test_dense_decode_kernel_compiles(chip, shape):
    b, hk, r, d = shape
    cache = ((b, hk, CTX, d), BF16)
    _compile(chip,
             lambda q, k, v, pos: ca.decode_attention(q, k, v, pos,
                                                      interpret=False),
             ((b, hk, r, d), BF16), cache, cache, ((b,), jnp.int32))


@pytest.mark.parametrize("heads,d", [(12, 64), (8, 128)])
def test_chunked_prefill_kernel_compiles(chip, heads, d):
    """One 64-token prompt chunk against the 1024-position row cache at a
    runtime start position — the daemon's prefill_chunk attention."""
    cache = ((1, heads, CTX, d), BF16)
    _compile(chip,
             lambda q, k, v, pos: ca.cached_attention(q, k, v, pos,
                                                      interpret=False),
             ((1, heads, 64, d), BF16), cache, cache, ((1,), jnp.int32))


@pytest.mark.parametrize("shape", [(8, 12, 1024, 64), (2, 8, 1024, 128)])
@pytest.mark.parametrize("grad", [False, True], ids=["fwd", "bwd"])
def test_flash_attention_compiles(chip, shape, grad):
    def fwd(q, k, v):
        return flash_attention(q, k, v, interpret=False)

    def loss(q, k, v):
        return fwd(q, k, v).astype(F32).sum()

    fn = jax.grad(loss, argnums=(0, 1, 2)) if grad else fwd
    _compile(chip, fn, *[(shape, BF16)] * 3)


def test_serving_step_programs_compile_with_the_kernels(chip, monkeypatch):
    """The whole step programs, not the kernels alone: the paged kernel
    inside the layer scan with donation and the paged scatter, and the
    chunked-prefill kernel inside forward_with_cache — GPT-2's widths and
    the daemon's default pool geometry, depth cut to 2 layers. Each
    program is lowered from its real call's arguments; `_kernel_on` is
    steered from here (the backend answers "tpu" while lowering), not
    through an option of the program."""
    from dnn_tpu.models import gpt
    from dnn_tpu.runtime.serving import ContinuousBatcher

    cfg = gpt.GPTConfig(n_layer=2)
    prepared = gpt.prepare_stacked(gpt.init(jax.random.PRNGKey(0), cfg), cfg)
    b = ContinuousBatcher(cfg, prepared, slots=4, compute_dtype=BF16,
                          kv="auto")
    assert b._paged and b.max_len == CTX
    compiled = {}

    def described(x):
        if isinstance(x, (jax.Array, np.ndarray)):
            return jax.ShapeDtypeStruct(
                x.shape, x.dtype, sharding=chip,
                weak_type=getattr(x, "weak_type", False))
        return x

    def lower_first(name):
        fn = getattr(b, name)

        def call(*args):
            if name not in compiled:
                with monkeypatch.context() as m:
                    m.setattr(jax, "default_backend", lambda: "tpu")
                    compiled[name] = fn.lower(
                        *jax.tree.map(described, args)).compile()
                jax.clear_caches()  # drop the trace made under the patch
            return fn(*args)

        setattr(b, name, call)

    for name in ("_prefill_chunk", "_prefill_finish", "_decode"):
        lower_first(name)
    b.submit(np.arange(1, 70, dtype=np.int32), max_new_tokens=2)
    b.drain()
    has_kernel = {n: "tpu_custom_call" in c.as_text()
                  for n, c in compiled.items()}
    assert has_kernel == {"_prefill_chunk": True, "_prefill_finish": False,
                          "_decode": True}
    # the decode step's donated pool and per-slot state alias its outputs
    mem = compiled["_decode"].memory_analysis()
    assert mem.alias_size_in_bytes >= sum(
        x.nbytes for x in jax.tree.leaves(b.cache) if x.ndim > 3)
