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

import contextlib
import functools
import re

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
# serves it, a GQA shape with 128-wide heads, the two configurations the
# benchmark's cells serve at 16 slots (GPT-2 Large; OLMoE), and the cells'
# grouped shapes: Keye's, which reads a set, and K-EXAONE's and Solar's
GPT2 = (4, 12, 1, 64)
GQA = (4, 8, 4, 128)
LARGE = (16, 20, 1, 64)
OLMOE = (16, 16, 1, 128)
KEYE = (16, 4, 8, 128)
GROUPED = (64, 8, 8, 128)
BLOCK_LEN, CTX = 16, 1024
RIDGE = 256  # GPT-2 Large's prefill chunk on a v5e: its ridge (ISSUE 67)
MASK_ROWS = 3600  # the daemon's constraint pools (lm_server.py)

# (shape, pool dtype, block_len, positions a slot): the pool holds its rows
# 128 lanes wide (paged_kvcache.lane_padded), as the daemon's does. An
# int8 pool's (Hk, block_len) scale blocks go through the kernel only where
# block_len fills the lanes (`test_paged_kernel_leaves_what_it_cannot_copy`)
PAGED = [(GPT2, F32, 16, CTX), (GPT2, BF16, 16, CTX), (GPT2, I8, 128, CTX),
         (GQA, BF16, 16, CTX), (LARGE, BF16, 16, 1024),
         (OLMOE, BF16, 16, 4096), (KEYE, BF16, 16, 16384),
         (GROUPED, BF16, 16, 6144), (GROUPED, BF16, 16, 9216)]


def _paged_call(shape, dtype, bp, ctx, *, whole, n_layer=3, width=None):
    """(fn, argument shapes) of one paged_decode_attention call: per
    layer and read-only, or (`whole`) the decode loop's form — the whole
    (L, n_blocks, ...) pool entered at a layer that rides scalar prefetch,
    the step's rows placed by the kernel and the pools handed back
    through aliased outputs. `width`: the pool's row width, where it is
    not the daemon's. Keye's call reads a set (`sel=`), as its step's."""
    from dnn_tpu.runtime.paged_kvcache import lane_padded

    b, hk, r, d = shape
    nb = ctx // bp
    width = width or lane_padded(d)
    lead = (n_layer, b * nb + 1) if whole else (b * nb + 1,)
    pool = (lead + (hk, bp, width), dtype)
    scales = (pool[0][:-1], F32)
    quant = dtype == I8
    leaves = [pool, pool] + ([scales, scales] if quant else [])
    q = ((b, hk, r, d), BF16 if quant else dtype)
    tables, pos = ((b, nb), jnp.int32), ((b,), jnp.int32)
    sel = [((b, ctx), jnp.bool_)] if shape == KEYE else []

    if not whole:
        def fn(q, tables, pos, kp, vp, *rest):
            ks, vs = rest[:2] if quant else (None, None)
            return ca.paged_decode_attention(
                q, kp, vp, tables, pos, ks=ks, vs=vs,
                sel=rest[-1] if sel else None, interpret=False)

        return fn, [q, tables, pos, *leaves, *sel]

    rows = [((b, hk, 1, width), dtype)] * 2 + (
        [((b, hk, 1), F32)] * 2 if quant else [])

    def fn(q, tables, pos, layer, gate, *rest):
        kp, vp, *ksvs = rest[:len(leaves)]
        ks, vs = ksvs if quant else (None, None)
        return ca.paged_decode_attention(
            q, kp, vp, tables, pos, ks=ks, vs=vs, layer=layer,
            new=(*rest[len(leaves):len(leaves) + len(rows)], gate),
            sel=rest[-1] if sel else None, interpret=False)

    return fn, [q, tables, pos, ((), jnp.int32), ((b,), jnp.bool_),
                *leaves, *rows, *sel]


def _kernel_calls(fn, shapes):
    """Every pallas_call equation of fn's jaxpr."""
    jaxpr = jax.make_jaxpr(fn)(*(jax.ShapeDtypeStruct(s, d)
                                 for s, d in shapes))
    return [e for e in jaxpr.eqns if e.primitive.name == "pallas_call"]


def _kernel_grids(fn, shapes):
    """The grid of every pallas_call of fn's jaxpr."""
    return [tuple(e.params["grid_mapping"].grid)
            for e in _kernel_calls(fn, shapes)]


def _eqns(jaxpr):
    """Every equation of a jaxpr, those of its sub-jaxprs (the bodies of
    loops and branches) included."""
    for e in jaxpr.eqns:
        yield e
        for sub in jax.core.jaxprs_in_params(e.params):
            yield from _eqns(sub)


@pytest.mark.parametrize("shape,dtype,bp,ctx", PAGED)
def test_paged_decode_kernel_compiles(chip, shape, dtype, bp, ctx):
    fn, shapes = _paged_call(shape, dtype, bp, ctx, whole=False)
    _compile(chip, fn, *shapes)


@pytest.mark.parametrize("shape,dtype,bp,ctx", PAGED[1:])
def test_paged_decode_kernel_compiles_on_the_whole_pool(chip, shape, dtype,
                                                        bp, ctx):
    fn, shapes = _paged_call(shape, dtype, bp, ctx, whole=True)
    compiled = _compile(chip, fn, *shapes)
    # the pools are updated where they lie: nothing of a pool's size is
    # allocated beside the arguments
    assert compiled.memory_analysis().temp_size_in_bytes < 2 ** 20


@pytest.mark.parametrize("shape,dtype,bp,ctx", PAGED[4:6],
                         ids=["gpt2-large", "olmoe"])
@pytest.mark.parametrize("whole", [False, True], ids=["read", "write"])
def test_paged_kernel_grid_is_over_slots_alone(shape, dtype, bp, ctx, whole):
    """ISSUE 29: one grid step a SLOT, whatever its table could hold (64
    entries for GPT-2 Large, 256 for OLMoE): the live blocks are walked
    inside the step."""
    fn, shapes = _paged_call(shape, dtype, bp, ctx, whole=whole)
    assert _kernel_grids(fn, shapes) == [(shape[0],)]


@pytest.mark.parametrize("shape,dtype,bp,ctx", PAGED[1:])
def test_paged_kernel_scores_a_group_as_one_tile_a_head(shape, dtype, bp,
                                                        ctx):
    """ISSUE 49: a group's G blocks are ONE matrix of G * bp positions a
    KV head, so neither product of the kernel is `bp` columns (or `bp`
    rows of values) at a time, and a query row has ONE running softmax
    state: the three scratch arrays behind the block buffers are Hk * R
    rows, not G times that. ISSUE 51: the group is as wide as the call's
    leaves allow (`_paged_group`) — 512 positions for Keye's 4 KV heads,
    256 for K-EXAONE's and Solar's 8, 128 from OLMoE's 16 on and for an
    int8 pool, whose scale blocks the compiler lays side by side only at
    128."""
    _, hk, r, _ = shape
    fn, shapes = _paged_call(shape, dtype, bp, ctx, whole=True)
    call, = _kernel_calls(fn, shapes)
    dots = [e for e in _eqns(call.params["jaxpr"])
            if e.primitive.name == "dot_general"]
    leaves = [jax.ShapeDtypeStruct(s, dt) for s, dt in shapes
              if len(s) >= 4 and s[0] == 3]  # the (3, n_blocks, ...) pools
    span = ca.paged_group(leaves, ctx // bp, whole=True) * bp
    assert span == {KEYE: 512, GROUPED: 256, GQA: 256}.get(shape, 128)
    assert len(dots) == 2
    scores, values = dots
    assert scores.outvars[0].aval.shape == (hk, r, span)
    assert values.invars[0].aval.shape == (hk, r, span)
    n_scratch = call.params["grid_mapping"].num_scratch_operands
    states = call.params["jaxpr"].invars[-n_scratch:][-3:]
    assert [v.aval.shape[0] for v in states] == [hk * r] * 3


@pytest.mark.parametrize("dtype,width", [(I8, 128), (BF16, 64)],
                         ids=["int8-scales", "narrow-rows"])
def test_paged_kernel_leaves_what_it_cannot_copy(chip, dtype, width):
    """The chip's compiler copies a block out of a leaf only in whole
    128-lane rows ("Slice shape along dimension 3 must be aligned to tiling
    (128), but is 16"): an int8 pool's (Hk, 16) scale blocks, and rows
    stored narrower than a tile, take the gather-and-einsum form on the
    chip — no kernel, and a program that compiles."""
    fn, shapes = _paged_call(GPT2, dtype, BLOCK_LEN, CTX, whole=False,
                             width=width)
    assert _kernel_grids(fn, shapes) == []
    args = [jax.ShapeDtypeStruct(s, dt, sharding=chip) for s, dt in shapes]
    assert "tpu_custom_call" not in jax.jit(fn).lower(
        *args).compile().as_text()


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


# K-EXAONE's cut as its cell serves it (64 slots, 8 KV heads of 128 under
# 8 query heads each, rows of 6144, chunks of 1024, a window of 128)
KX = dict(slots=64, kv=8, group=8, d=128, row=6144, chunk=1024, window=128)


@pytest.mark.parametrize("window", [None, KX["window"]],
                         ids=["full", "banded"])
@pytest.mark.parametrize("tile", [(128, 128), (512, 512)],
                         ids=lambda t: "x".join(map(str, t)))
def test_folded_prefill_kernel_compiles(chip, window, tile):
    """A 1024-token chunk of K-EXAONE against its 6144-position row: the
    query group folded into the kernel's rows (8 x 1024 a KV head), the
    column tiles clamped to the live ones — and, banded, to the window's."""
    kx = KX
    cache = ((1, kx["kv"], kx["row"], kx["d"]), BF16)
    _compile(chip,
             lambda q, k, v, pos: ca.cached_attention(
                 q, k, v, pos, rows_mod=kx["chunk"], window=window,
                 block_q=tile[0], block_s=tile[1], interpret=False),
             ((1, kx["kv"], kx["group"] * kx["chunk"], kx["d"]), BF16),
             cache, cache, ((1,), jnp.int32))


def test_delta_rule_kernel_compiles(chip):
    """Solar-Open2's chunked delta rule as its cell's chunk program runs
    it: 64 heads, sixteen closed-form chunks of 64 positions of a
    1024-token prefill chunk, heads of 128 — q, k, v, the log-decay and
    beta in, the (128, 128) float32 state a head resident across the chunk
    axis; the cumulative log-decay, the decay products, the substitution
    and the state's products made in the grid step, eight heads in
    lockstep."""
    from dnn_tpu.ops.pallas.delta_rule import delta_rule

    g, n, c, d = 64, 16, 64, 128
    tile = ((g, n, c, d), F32)
    _compile(chip, lambda *a: delta_rule(*a, interpret=False),
             tile, tile, tile, tile, ((g, n, c), F32), ((g, d, d), F32))


@pytest.mark.parametrize("shape", [(8, 12, 1024, 64), (2, 8, 1024, 128)])
@pytest.mark.parametrize("grad", [False, True], ids=["fwd", "bwd"])
def test_flash_attention_compiles(chip, shape, grad):
    def fwd(q, k, v):
        return flash_attention(q, k, v, interpret=False)

    def loss(q, k, v):
        return fwd(q, k, v).astype(F32).sum()

    fn = jax.grad(loss, argnums=(0, 1, 2)) if grad else fwd
    _compile(chip, fn, *[(shape, BF16)] * 3)


def _large_convoy(chip, **kw):
    """(cfg, prepared, batcher): GPT-2 Large's widths at 2 layers behind
    the daemon's defaults, built while the default device states the
    described chip's peaks: asked for no `prompt_pad`, the batcher takes a
    v5e's ridge (241 tokens for bfloat16, `serving.ridge_pad`)."""
    from dnn_tpu.models import gpt
    from dnn_tpu.node import _stack_and_release
    from dnn_tpu.runtime.serving import ContinuousBatcher

    cfg = gpt.GPTConfig(n_layer=2, n_embd=1280, n_head=20)
    # as the daemon holds it: matmul kernels and the head in bfloat16
    prepared = _stack_and_release(gpt.init(jax.random.PRNGKey(0), cfg), cfg,
                                  BF16)
    with pytest.MonkeyPatch.context() as m:
        _described_peaks(m, chip)
        convoy = ContinuousBatcher(cfg, prepared, slots=16,
                                   compute_dtype=BF16, kv="auto", **kw)
    return cfg, prepared, convoy


@pytest.fixture(scope="module")
def step_programs(chip):
    """The daemon's step programs compiled for the chip — the whole
    programs, not the kernels alone: the paged kernel inside the layer
    loop with donation, and the chunked-prefill kernel inside
    forward_with_cache — GPT-2 Large's widths and 16 slots of the
    daemon's default pool geometry (what the benchmark serves: at a toy
    pool the compiler prefetches whole layer slices into VMEM, which the
    real one never fits), depth cut to 2 layers. Each program is lowered
    from its real call's arguments; `_kernel_on` is steered from here
    (the backend answers "tpu" while lowering), not through an option of
    the program. -> {name: compiled}, the pool's (n_blocks, H, block_len,
    Dp) and its bytes."""
    from dnn_tpu.analysis.program import chunk_args
    from dnn_tpu.runtime.serving import ContinuousBatcher

    cfg, prepared, convoy = _large_convoy(chip)
    assert (convoy.prompt_pad, convoy._row_len) == (RIDGE, CTX)
    # and the chunk a `--prompt_pad 64` deployment launches
    narrow = _large_convoy(chip, prompt_pad=64)[2]
    # the interleaved batcher with the per-request capabilities the daemon
    # compiles in (node.py: bias and constraints on; lm_server.py: a mask
    # pool of MASK_ROWS rows), so that its programs carry the pools
    mixed = ContinuousBatcher(cfg, prepared, slots=16, compute_dtype=BF16,
                              kv="auto", prefill_chunk_tokens=64,
                              allow_logit_bias=True, allow_constraints=True,
                              constraint_rows=MASK_ROWS)
    assert convoy._paged and convoy.max_len == CTX
    compiled = _lower_programs(chip, [
        (convoy, ("_prefill_chunk", "_prefill_finish", "_decode")),
        (mixed, ("_mixed", "_ilv_finish=_prefill_finish",
                 "_decode_constrained=_decode"))],
        more={"_prefill_chunk_64": (
            narrow._prefill_chunk, _shapes(chunk_args(narrow)))})
    pool_bytes = sum(x.nbytes for x in jax.tree.leaves(convoy.cache)
                     if x.ndim > 3)
    return (compiled, convoy.cache["k"].shape[1:], pool_bytes,
            _held_weight_shapes(prepared))


def _shapes(args):
    """`args` with every array as its shape and dtype."""
    def shape(x):
        if isinstance(x, (jax.Array, np.ndarray)):
            return jax.ShapeDtypeStruct(
                x.shape, x.dtype, weak_type=getattr(x, "weak_type", False))
        return x

    return jax.tree.map(shape, args)


def first_calls(batchers, prompt_len=69):
    """{name: (jitted program, the arguments of its first real call as
    shapes)} of the named step programs of each (batcher, names), while
    two requests of `prompt_len` tokens go through the batcher's own host
    loop here: the programs the batcher really dispatches, with the
    arguments it really passes (tests/test_benchmark_contract.py lowers
    the same calls for their names and scopes). No program of the batcher
    RUNS: each call is answered with zeros of the shapes its results have
    (`jax.eval_shape`), which is all the next call's arguments take from
    it — at the published widths the CPU spent most of a fixture's time
    compiling and running programs whose results nothing here reads.
    `_ilv_finish` names the one finish-and-install program,
    `_prefill_finish`, as an INTERLEAVED admission calls it."""
    calls = {}

    def zeros(s):
        assert not s.weak_type, s  # a result fed back would change type
        return jnp.zeros(s.shape, s.dtype)

    def answered_in_shapes(b, attr, name=None):
        fn = getattr(b, attr)

        def call(*args):
            if name is not None:
                calls.setdefault(name, (fn, _shapes(args)))
            return jax.tree.map(zeros, jax.eval_shape(fn, *args))

        setattr(b, attr, call)

    for b, names in batchers:
        # "key=attribute": the program under a name of the caller's (two
        # batchers' `_prefill_finish` in one table)
        named = {attr or key: key for key, _, attr in
                 (n.partition("=") for n in names)}
        programs = {id(fn) for fn in b.jit_programs()}
        for attr, fn in list(vars(b).items()):
            if id(fn) in programs or attr in named:
                answered_in_shapes(b, attr, named.get(attr))
        prompt = np.arange(1, prompt_len + 1, dtype=np.int32)
        b.submit(prompt, max_new_tokens=2)
        b.step()
        b.submit(prompt, max_new_tokens=2)
        b.drain()
    return calls


def _described_peaks(m, chip):
    """While `m` lasts the default device's peaks are the described
    chip's (`utils/flops`'s own tables): a batcher built meanwhile reckons
    its `prompt_pad` (`serving.ridge_pad`) as it does on the chip."""
    from dnn_tpu.utils import flops

    device = next(iter(chip.device_set))
    for name in ("device_peak_flops", "device_peak_hbm_bw"):
        m.setattr(flops, name, functools.partial(getattr(flops, name),
                                                 device))


def _lower_programs(chip, batchers, more=None):
    """{name: compiled for the chip} of the named step programs of each
    (batcher, names), each lowered from its first real call's arguments
    (`first_calls`) with the backend answering "tpu"; `more`: further
    {name: (jitted program, arguments)} compiled the same way."""
    def described(x):
        if isinstance(x, jax.ShapeDtypeStruct):
            return jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=chip,
                                        weak_type=x.weak_type)
        return x

    calls = {**first_calls(batchers), **(more or {})}
    compiled = {}
    with _answering_as(chip):
        for name, (fn, args) in calls.items():
            compiled[name] = fn.lower(*jax.tree.map(described, args)).compile()
    return compiled


@contextlib.contextmanager
def _answering_as(chip):
    """While it lasts the backend answers "tpu" (`_kernel_on`) and its
    devices are the described chip's: a chunk program carries its row in
    the layout the DEVICE holds it in (`scan_rows`)."""
    jax.clear_caches()  # the traces made so far, with the kernels off
    with pytest.MonkeyPatch.context() as m:
        m.setattr(jax, "default_backend", lambda: "tpu")
        m.setattr(jax, "devices", lambda *a: list(chip._device_assignment))
        yield
    jax.clear_caches()  # drop the traces made under the patch


@pytest.fixture(scope="module")
def olmoe_programs(chip):
    """The same for the first LLaMA-family model the chip serves: OLMoE at
    its published widths (64 experts of 1024, 8 per token, 16 heads of
    128, RoPE, q/k norm), 16 slots of the 4096-position pool the
    benchmark's daemon holds, 256-token chunks, depth cut to TWO layers
    (1.7 GB of weights, held as the daemon holds them; two, so that the
    layer loop has a slice to take).
    Interleaved admission, so that one batcher gives the decode step, the
    mixed step and the fused finish."""
    import dataclasses

    from dnn_tpu.models import llama_moe
    from dnn_tpu.node import _stack_and_release
    from dnn_tpu.runtime.serving import ContinuousBatcher

    cfg = dataclasses.replace(llama_moe.PRESETS["olmoe-1b-7b"], n_layer=2)
    prepared = _stack_and_release(
        llama_moe.init(jax.random.PRNGKey(0), cfg), cfg, BF16)
    b = ContinuousBatcher(
        cfg, prepared, slots=16, kv="auto", prompt_pad=256,
        prefill_chunk_tokens=256,
        family=llama_moe.family_rows(cfg, compute_dtype=BF16))
    assert b._paged and b.max_len == 4096 and b._moe_stats
    compiled = _lower_programs(
        chip, [(b, ("_mixed", "_ilv_finish=_prefill_finish", "_decode"))])
    return (compiled, b.cache["k"].shape[1:], cfg,
            _held_weight_shapes(prepared))


def _held_weight_shapes(prepared):
    """The shapes of the leaves the daemon holds in bfloat16: the stacked
    block kernels, the expert stacks and the head."""
    shapes = {x.shape for x in jax.tree.leaves(prepared) if x.dtype == BF16}
    assert len(shapes) >= 3 and all(np.prod(s) >= 2 ** 20 for s in shapes)
    return sorted(shapes)


def _pool_extent_ops(compiled, pool):
    """(opcode, name) of every instruction of a compiled program whose
    result has the extent of one layer's pool slice or of the whole pool
    — arguments, tuples and the loop itself apart."""
    return _extent_ops(
        compiled, re.compile(r"\[(?:\d+,)?%d,%d,%d,%d\]" % tuple(pool)))


def _extent_ops(compiled, extent):
    found = []
    for line in compiled.as_text().splitlines():
        m = re.match(r"\s*(?:ROOT )?%?([\w.\-]+) = (.+?) ([\w\-]+)\(", line)
        if m and extent.search(m.group(2)) and m.group(3) not in (
                "parameter", "get-tuple-element", "tuple", "bitcast",
                "while"):
            found.append((m.group(3), m.group(1)))
    return found


@pytest.mark.parametrize("name,kernel", [
    ("_prefill_chunk", True), ("_prefill_chunk_64", True),
    ("_prefill_finish", False), ("_decode", True),
    ("_mixed", True), ("_ilv_finish", False)])
def test_serving_step_programs_compile_with_the_kernels(step_programs, name,
                                                        kernel):
    compiled, _, pool_bytes, _ = step_programs
    assert ("tpu_custom_call" in compiled[name].as_text()) == kernel
    if not name.startswith("_prefill_chunk"):  # on the transient row
        # the donated pool aliases the program's result
        assert compiled[name].memory_analysis().alias_size_in_bytes \
            >= pool_bytes


@pytest.mark.parametrize("name", ["_prefill_chunk", "_prefill_chunk_64"])
def test_chunk_program_writes_its_row_in_place(step_programs, name):
    """ISSUE 63: the chunk program carries its donated transient row through
    the layer loop (`paged_kvcache.scan_rows`) in the layout the device
    holds it in — positions minor-most for GPT-2's heads of 64 — and writes
    a chunk's positions into it: nothing of the row cache's extent but the
    two in-place writes (riding the loop as xs / ys the row was copied whole
    once a chunk, `copy.55` on the chip; carried in the layout the kernel
    reads, the compiler transposed it whole into the loop and out again),
    the row aliased to the result and no temporary of a leaf's size."""
    chunk = step_programs[0][name]
    row = re.compile(r"\[2,1,20,%d,64\]" % CTX)
    assert sorted(op for op, _ in _extent_ops(chunk, row)) == [
        "dynamic-update-slice"] * 2
    leaf = 2 * 20 * CTX * 64 * 2
    mem = chunk.memory_analysis()
    assert mem.alias_size_in_bytes >= 2 * leaf
    assert mem.temp_size_in_bytes < leaf


def test_ridge_chunk_program_moves_a_chunks_positions(chip):
    """ISSUE 67: `analysis/program.check_chunk_program` on GPT-2 Large's
    256-token launch as it is lowered for the chip (the prefill kernel at
    64-wide heads and 256 queries, the row in the layout the device holds):
    both leaves of the donated row alias a result and nothing but a
    chunk's positions is moved — no whole-row copy or transpose at the
    loop's edges."""
    from dnn_tpu.analysis.program import check_chunk_program, chunk_args

    _, _, convoy = _large_convoy(chip)
    args = jax.tree.map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=chip),
        _shapes(chunk_args(convoy)))
    assert args[2].shape == (1, RIDGE)
    with _answering_as(chip):
        report, findings = check_chunk_program(
            "gpt2-large@256", convoy._prefill_chunk, args)
    assert findings == []
    assert report["aliased"] == report["expected"] == 2
    assert report["cache_sized_ops"] == {}


@pytest.mark.parametrize("name", ["_decode_constrained", "_mixed",
                                  "_ilv_finish"])
def test_step_programs_read_rows_of_the_constraint_pools(step_programs, name):
    """ISSUE 48: the step reads `slots` rows of the mask pool and `slots`
    words of the transition pool, whatever they hold. The compiler answered
    the parent's `bool[3600, vocab]` gather by bringing the whole table into
    fast memory in column pieces every step (`slice-done pred[3600,25041]` +
    `pred[3600,25216]` here, 5 % of GPT-2 Large's busy time on the chip and
    1.5 ms of JoyAI's 11 ms step), and a bit-packed table read by a gather
    the same at an eighth: no operation of a step program may have a
    pool's row count as an extent."""
    compiled = step_programs[0]
    assert _extent_ops(compiled[name],
                       re.compile(r"\[%d," % MASK_ROWS)) == []


@pytest.fixture
def programs(request):
    """(compiled, pool extent, held weight shapes) of the model a case names: GPT-2 Large or
    OLMoE — resolved here, so that one model's fixture is built only for
    its own cases."""
    got = request.getfixturevalue(
        {"gpt2": "step_programs", "olmoe": "olmoe_programs"}[request.param])
    return got[0], got[1], got[-1]


@pytest.mark.parametrize("programs,name", [
    ("gpt2", "_decode"), ("gpt2", "_mixed"),
    ("olmoe", "_decode"), ("olmoe", "_mixed")], indirect=["programs"])
def test_decode_programs_leave_the_pool_in_place(programs, name):
    """ISSUE 25 point 4, on the chip's compiled text: no operation of the
    decode step (nor of the mixed step, which shares its core) has a
    result of the extent of a layer's pool slice or of the whole pool —
    no copy, no dynamic-slice / dynamic-update-slice fusion, no
    copy-start / copy-done, no scatter — but the kernel's own aliased
    pool results."""
    compiled, pool, _ = programs
    ops = _pool_extent_ops(compiled[name], pool)
    assert [o for o in ops if o[0] != "custom-call"] == []
    assert ops, "the kernel hands the pool back through its results"


@pytest.mark.parametrize("programs,name", [
    ("gpt2", "_prefill_finish"), ("gpt2", "_ilv_finish"),
    ("olmoe", "_ilv_finish")], indirect=["programs"])
def test_finish_programs_install_without_a_pool_copy(programs, name):
    """The finish installs the row's blocks with one in-place scatter a
    leaf; stored lane-padded the pool is block-contiguous by its shape and
    needs no relayout around it (OLMoE's 128-wide heads need no padding
    at all)."""
    compiled, pool, _ = programs
    ops = _pool_extent_ops(compiled[name], pool)
    assert {o[0] for o in ops} <= {"scatter", "fusion"}, ops
    assert compiled[name].memory_analysis().temp_size_in_bytes < 2 ** 24


def _converts_of_extent(compiled, shapes):
    """(opcode, name, result type) of every `convert` of a compiled
    program — alone, or as the root of a fusion — whose result has one of
    `shapes`."""
    text = compiled.as_text()
    roots, current = {}, None  # computation -> its root's opcode
    for line in text.splitlines():
        m = re.match(r"%?([\w.\-]+) \(.*\) -> .* \{$", line)
        if m:
            current = m.group(1)
        m = re.match(r"\s*ROOT %?[\w.\-]+ = .+? ([\w\-]+)\(", line)
        if m and current:
            roots[current] = m.group(1)
    extent = re.compile("|".join(
        r"\[%s\]" % ",".join(map(str, shape)) for shape in shapes))
    found = []
    for line in text.splitlines():
        m = re.match(r"\s*(?:ROOT )?%?([\w.\-]+) = (.+?) ([\w\-]+)\(", line)
        if not (m and extent.search(m.group(2))):
            continue
        op = m.group(3)
        if op == "fusion":
            called = re.search(r"calls=%?([\w.\-]+)", line)
            op = roots.get(called.group(1)) if called else op
        if op == "convert":
            found.append((m.group(3), m.group(1), m.group(2)))
    return found


def test_converts_of_extent_reads_the_compiled_text(chip):
    """The reader itself, on a program that does convert a stack: both
    forms are found, and a convert of another extent is not."""
    def fn(w, x, v):
        return x.astype(BF16) @ w.astype(BF16)[0], v.astype(BF16)

    compiled = jax.jit(fn).lower(*(
        jax.ShapeDtypeStruct(s, F32, sharding=chip)
        for s in ((2, 1280, 5120), (16, 1280), (2, 5120, 1280)))).compile()
    found = _converts_of_extent(compiled, [(2, 1280, 5120), (2, 5120, 1280)])
    assert len(found) >= 1 and all("bf16[2," in f[2] for f in found), found
    assert _converts_of_extent(compiled, [(36, 1280, 5120)]) == []


@pytest.mark.parametrize("programs,name", [
    ("gpt2", "_decode"), ("gpt2", "_prefill_chunk"), ("gpt2", "_mixed"),
    ("gpt2", "_prefill_finish"),
    ("olmoe", "_decode"), ("olmoe", "_mixed")], indirect=["programs"])
def test_step_programs_convert_no_weight_stack(programs, name):
    """ISSUE 27, on the chip's compiled text: the daemon holds its matmul
    weights in the compute dtype, so the decode step, the prefill chunk
    and the mixed step hold no `convert` (alone or as a fusion's root)
    whose result has the extent of a stacked weight leaf (`[L,1280,5120]`,
    `[L,5120,1280]`, `[L,1280,3840]`, `[L,1280,1280]`; `[L,64,2048,1024]`,
    `[L,64,1024,2048]`) or of the head — what the compiler hoisted out of
    the layer loop and ran once a program (6.6 ms of a 25 ms GPT-2 Large
    step) — and the stacks arrive as bfloat16 arguments. (At this depth
    the compiler prefetches the small stacks into fast memory; those
    copies are not converts, and 36 layers never fit there.)"""
    compiled, _, weights = programs
    text = compiled[name].as_text()
    # ISSUE 38: the chunk stops at the last block and the finish is the
    # head on one row, so the chunk holds the stacks and NOT the head (the
    # vocabulary's extent is the input table's alone: no operation's result
    # has it), the finish the head alone
    if name == "_prefill_chunk":
        weights = [s for s in weights if len(s) > 2]
        made = [l for l in text.splitlines()
                if re.search(r" = [^=(]*\b50257\b[^=(]* [\w\-]+\(", l)
                and " parameter(" not in l]
        assert made == [], made[:3]
    elif name == "_prefill_finish":
        # its ONE-row product is a multiply-and-reduce fusion that converts
        # the head's tiles on their way through: float32 of the head's
        # extent INSIDE that fusion, and no operation of the program itself
        # has such a result (the program's temporaries:
        # test_finish_programs_install_without_a_pool_copy)
        weights = [s for s in weights if len(s) == 2]
        text, fused = text[text.index("\nENTRY "):], text
        for shape in weights:
            assert "bf16[%s]" % ",".join(map(str, shape)) in fused, shape
            assert "f32[%s]" % ",".join(map(str, shape)) not in text, shape
        assert weights
        return
    for shape in weights:
        assert "bf16[%s]" % ",".join(map(str, shape)) in text, shape
        assert "f32[%s]" % ",".join(map(str, shape)) not in text, shape
    assert _converts_of_extent(compiled[name], weights) == []


def _grouped_matmul_calls(compiled):
    """The lines of a compiled program that call the grouped-matmul
    kernel (ops/pallas/grouped_matmul.py)."""
    return [l for l in compiled.as_text().splitlines()
            if "tpu_custom_call" in l and "grouped_matmul" in l]


@pytest.mark.parametrize("name", ["_decode", "_mixed"])
def test_olmoe_expert_matrices_are_read_from_the_stack_in_place(
        olmoe_programs, name):
    """ISSUE 36, on the chip's compiled text: the layer loop keeps the
    expert stacks whole and the grouped-matmul kernel reads the active
    experts' tiles out of them, so NO operation of the decode step or the
    mixed step has a result the size of one layer's expert matrices —
    `bf16[64,2048,1024]` / `bf16[64,1024,2048]`, the 268 MB copy a matrix
    that fed `ragged_dot`'s custom call in every layer of every step —
    and the kernel's calls carry `/moe.experts/` in their `op_name`: the
    benchmark's `moe_experts_roofline_pct` divides by the device time
    under that scope, which is now the matmul's."""
    compiled, _, cfg, _ = olmoe_programs
    e, d, f = cfg.n_expert, cfg.n_embd, cfg.d_ff
    matrix = re.compile(r" = bf16\[%d,(?:%d,%d|%d,%d)\]" % (e, d, f, f, d))
    made = [l for l in compiled[name].as_text().splitlines()
            if matrix.search(l)]
    assert made == [], made[:3]
    calls = _grouped_matmul_calls(compiled[name])
    # gate, up and down: one loop body for the decode step, the chunk's
    # and the rows' for the mixed step
    assert len(calls) >= 3, len(calls)
    for line in calls:
        op_name = re.search(r'op_name="([^"]*)"', line)
        assert op_name and "/moe.experts/" in op_name.group(1), line[:300]


def test_olmoe_decode_step_gathers_no_expert_weights_by_token(
        olmoe_programs):
    """The grouped experts meet their weights through the grouped matmul
    alone: no operation of the decode step has a result with one expert
    matrix per routed row — (S*k, D, F) or (S*k, F, D), 128 rows x 2 M
    weights here — which is what gathering `wg[expert_of_row]` would
    build; and the grouped matmul is the kernel that reads the stack, not
    `ragged_dot`'s custom call nor a dense product over all 64 experts."""
    compiled, _, cfg, _ = olmoe_programs
    text = compiled["_decode"].as_text()
    rows = 16 * cfg.router_top_k
    d, f = cfg.n_embd, cfg.d_ff
    per_row = re.compile(r"\[%d,(?:%d,%d|%d,%d)\]" % (rows, d, f, f, d))
    assert not [l for l in text.splitlines() if per_row.search(l)]
    assert _grouped_matmul_calls(compiled["_decode"])
    assert "ragged-dot" not in text


# ----------------------------------------------------------------------
# learned sparse attention (models/dsa.py) at the Keye cut's shapes: a
# 1024-token chunk against a 16 384-position row, 16 slots of 16 384
# positions in blocks of 16, 4 KV heads of 128 with 8 query heads each
# ----------------------------------------------------------------------

def _sparse_cases():
    from dnn_tpu.models import dsa
    from dnn_tpu.ops.pallas import sparse_attention as sa

    t, s, kv, g, d, hi, di = 1024, 16384, 4, 8, 128, 16, 64
    b, nb, bp, n_layer = 16, 1024, 16, 2

    def prefill_attention(dt):
        return (lambda q, k, v, sel, st: sa.sparse_prefill_attention(
            q, k, v, sel, st, interpret=False),
            [((kv, g, t, d), dt), ((kv, s, d), dt), ((kv, s, d), dt),
             ((t, s), jnp.bool_), ((), jnp.int32)])

    # MiniCPM-SALA's call (models/block_select.py): a set a KV head, 16
    # query heads each, a 25 600-position row (50 column tiles)
    def prefill_attention_by_kv_head(dt):
        kv, g, s = 2, 16, 25600
        return (lambda q, k, v, sel, st: sa.sparse_prefill_attention(
            q, k, v, sel, st, interpret=False),
            [((kv, g, t, d), dt), ((kv, s, d), dt), ((kv, s, d), dt),
             ((kv, t, s), jnp.bool_), ((), jnp.int32)])

    def index_scores(dt):
        return (lambda qi, w, ki, st: sa.chunk_index_scores(
            qi, w, ki, st, interpret=False),
            [((t, hi, di), dt), ((t, hi), F32), ((s, di), dt),
             ((), jnp.int32)])

    def paged_decode_under_a_set(dt):
        pool = ((n_layer, b * nb + 1, kv, bp, d), dt)
        row = ((b, kv, 1, d), dt)

        def fn(q, tables, pos, layer, gate, kp, vp, nk, nv, sel):
            return ca.paged_decode_attention(
                q, kp, vp, tables, pos, layer=layer, new=(nk, nv, gate),
                sel=sel, interpret=False)

        return fn, [((b, kv, g, d), dt), ((b, nb), jnp.int32),
                    ((b,), jnp.int32), ((), jnp.int32), ((b,), jnp.bool_),
                    pool, pool, row, row, ((b, nb * bp), jnp.bool_)]

    return {"prefill_attention": prefill_attention,
            "prefill_attention_by_kv_head": prefill_attention_by_kv_head,
            "index_scores": index_scores,
            "paged_decode_under_a_set": paged_decode_under_a_set}


@pytest.mark.parametrize("dtype", [BF16, F32], ids=["bf16", "f32"])
@pytest.mark.parametrize("kernel", ["prefill_attention",
                                    "prefill_attention_by_kv_head",
                                    "index_scores",
                                    "paged_decode_under_a_set"])
def test_sparse_attention_kernels_compile(chip, kernel, dtype):
    fn, shapes = _sparse_cases()[kernel](dtype)
    _compile(chip, fn, *shapes)


@pytest.fixture(scope="module")
def keye_chunk(chip):
    """(compiled, batcher) of the Keye cut's chunk program at its published
    widths and the cell's row (16 slots of 16 384 positions in blocks of
    16, 1024-token chunks), depth cut to TWO layers and the vocabulary to
    4096 rows (not the 8192 of a chunk's picks: an extent the tests below
    look for)."""
    import dataclasses

    from dnn_tpu.models import llama_moe
    from dnn_tpu.node import _stack_and_release
    from dnn_tpu.runtime.serving import ContinuousBatcher

    cfg = dataclasses.replace(
        llama_moe.PRESETS["keye-vl-2.0-30b-a3b-ep8-1chip"], n_layer=2,
        vocab_size=4096)
    prepared = _stack_and_release(
        llama_moe.init(jax.random.PRNGKey(0), cfg), cfg, BF16)
    b = ContinuousBatcher(
        cfg, prepared, slots=16, max_len=16384, prompt_pad=1024, kv="auto",
        block_len=16, family=llama_moe.family_rows(cfg, compute_dtype=BF16))
    assert b._paged and b._row_len == 16384
    assert b.cache["ik"].shape == (2, 16 * 1024 + 1, 1, 16, 128)
    return _lower_programs(chip, [(b, ("_prefill_chunk",))])[
        "_prefill_chunk"], b


def test_keye_chunk_program_attends_under_the_set_in_the_kernel(keye_chunk):
    """Every layer of the Keye cut's chunk program scores the row's index
    keys and attends under the set in the two
    kernels of ops/pallas/sparse_attention.py, on the transient row alone;
    its temporaries are the (T, S) scores, the selection's passes over them
    and the set (64 MB + 16 MB at 1024 x 16 384), not a (T, Hi, S) product
    nor a gathered K/V."""
    chunk, b = keye_chunk
    text = chunk.as_text()
    assert "sparse_prefill_attention" in text
    assert "chunk_index_scores" in text
    assert _pool_extent_ops(chunk, b.cache["k"].shape[1:]) == []
    assert chunk.memory_analysis().temp_size_in_bytes < 2 ** 29


def _scatters_of_the_permutation(compiled):
    """The lines of a compiled program that hold a scatter under
    `moe.route` or `moe.combine`."""
    return [l[:200] for l in compiled.as_text().splitlines()
            if "scatter" in l.split("(")[0]
            and re.search(r'op_name="[^"]*/moe\.(?:route|combine)/', l)]


def test_keye_chunk_program_permutes_the_rows_held(keye_chunk):
    """ISSUE 64: 16 of 128 experts are held here, so the chunk's 8192 picks
    are sorted (integers) and the ROWS that move, in and out, are one
    round's `permutation_extent` of 1536: no operation has a result of all
    the picks' rows — `[8192, 2048]`, in either dtype: the dispatch's
    gather, the mask over the experts' result, the combine's gather —
    nothing under `moe.route` / `moe.combine` is a scatter, and the rows
    come back through `row_accumulate` under `moe.combine`."""
    from dnn_tpu.parallel.moe import permutation_extent

    chunk, b = keye_chunk
    cfg = b.cfg
    picks = 1024 * cfg.router_top_k
    assert permutation_extent(picks, cfg.n_expert, cfg.held[1]) == 1536
    assert _extent_ops(chunk, re.compile(
        r"\[%d,%d\]" % (picks, cfg.n_embd))) == []
    assert _scatters_of_the_permutation(chunk) == []
    calls = [l for l in chunk.as_text().splitlines()
             if "tpu_custom_call" in l and "row_accumulate" in l]
    assert calls and all("/moe.combine/" in l for l in calls)


# (tokens S, width D, rows a round): a 1024-token chunk of Keye's and JoyAI's
# (16 of 128 and of 256 experts held), of Solar's, dots3's and K-EXAONE's
# widths (y in column tiles there), and Keye's 64-slot step
ACCUMULATE = {"keye_chunk": (1024, 2048, 1536),
              "joyai_chunk": (1024, 2048, 768),
              "solar_chunk": (1024, 4096, 1536),
              "dots3_chunk": (1024, 5120, 1536),
              "kexaone_chunk": (1024, 6144, 1536),
              "keye_step": (64, 2048, 128)}


@pytest.mark.parametrize("case", sorted(ACCUMULATE))
def test_row_accumulate_compiles(chip, case):
    """ISSUE 64: the kernel that adds a round's live rows into their tokens
    (ops/pallas/row_accumulate.py: a dynamic row of y a trip, y resident)
    at the held cells' shapes, y aliased to the result."""
    from dnn_tpu.ops.pallas.row_accumulate import row_accumulate

    s, d, r = ACCUMULATE[case]
    compiled = _compile(
        chip, lambda y, rows, tok, w, live: row_accumulate(
            y, rows, tok, w, live, interpret=False),
        ((s, d), jnp.float32), ((r, d), jnp.float32), ((r,), jnp.int32),
        ((r,), jnp.float32), ((), jnp.int32))
    assert compiled.memory_analysis().temp_size_in_bytes < s * d * 4


def test_olmoe_decode_step_permutes_with_no_scatter(olmoe_programs):
    """ISSUE 64, where every expert is held: the group sizes are a
    compare-and-sum over the picks and the inverse permutation an argsort
    — no scatter under `moe.route` / `moe.combine` (on the chip a scatter
    is an update at a time: 72 + 39 us of a chunk's layer call)."""
    assert _scatters_of_the_permutation(olmoe_programs[0]["_decode"]) == []


# ----------------------------------------------------------------------
# latent attention (models/mla.py) at the JoyAI cut's shapes: a
# 1024-token chunk of 32 heads (key 128 | 64, value 128) against a prefix
# of a 16 384-position row, 32 slots of 16 384 positions in blocks of 16,
# ONE pool leaf of one head, 576 values stored 640 lanes wide
# ----------------------------------------------------------------------

def _mla_cases():
    from dnn_tpu.ops.pallas import mla_attention as ma

    h, t, dn, dr, dv, r = 32, 1024, 128, 64, 128, 512
    b, nb, bp, n_layer, width = 32, 1024, 16, 2, 640

    def prefill_attention(dt, s=16384):
        return (lambda qn, qr, kn, kr, v, st: ma.mla_prefill_attention(
            qn, qr, kn, kr, v, st, scale=(dn + dr) ** -0.5,
            interpret=False),
            [((h, t, dn), dt), ((h, t, dr), dt), ((h, s, dn), dt),
             ((s, dr), dt), ((h, s, dv), dt), ((), jnp.int32)])

    def prefill_attention_short(dt):
        return prefill_attention(dt, s=2048)

    # dots3's two kinds (PR 42: a group of heads a grid step, sized from
    # these shapes): a full layer's 128 heads under the indexer's set over
    # the whole 13 312-position row, a sliding layer's 64 heads of a
    # 192-wide key under a band of 513 over window + chunk
    def prefill_attention_selected(dt, h=128, s=13312):
        return (lambda qn, qr, kn, kr, v, st, sel: ma.mla_prefill_attention(
            qn, qr, kn, kr, v, st, scale=(dn + dr) ** -0.5, sel=sel,
            interpret=False),
            [((h, t, dn), dt), ((h, t, dr), dt), ((h, s, dn), dt),
             ((s, dr), dt), ((h, s, dv), dt), ((), jnp.int32),
             ((t, s), jnp.bool_)])

    def prefill_attention_banded(dt, h=64, s=2048, dn=192):
        return (lambda qn, qr, kn, kr, v, st: ma.mla_prefill_attention(
            qn, qr, kn, kr, v, st, scale=(dn + dr) ** -0.5, window=513,
            interpret=False),
            [((h, t, dn), dt), ((h, t, dr), dt), ((h, s, dn), dt),
             ((s, dr), dt), ((h, s, dv), dt), ((), jnp.int32)])

    def paged_decode_latent(dt):
        pool = ((n_layer, b * nb + 1, 1, bp, width), dt)

        def fn(q, tables, pos, layer, gate, cp, row):
            return ca.paged_decode_attention(
                q, cp, None, tables, pos, layer=layer, new=(row, gate),
                latent=r, scale=(dn + dr) ** -0.5, interpret=False)

        return fn, [((b, 1, h, r + dr), dt), ((b, nb), jnp.int32),
                    ((b,), jnp.int32), ((), jnp.int32), ((b,), jnp.bool_),
                    pool, ((b, 1, 1, width), dt)]

    return {"prefill_attention": prefill_attention,
            "prefill_attention_short": prefill_attention_short,
            "prefill_attention_selected": prefill_attention_selected,
            "prefill_attention_banded": prefill_attention_banded,
            "paged_decode_latent": paged_decode_latent}


def test_latent_decode_kernel_walks_groups_of_1024(chip):
    """ISSUE 51: JoyAI's absorbed decode call — 32 slots, the 32 heads as
    rows, ONE bfloat16 leaf of 640 lanes in blocks of 16 — at the span the
    rule picks for it: 64 blocks, one (1024, 640) matrix, a group; two
    products and one softmax state a query row; lowered for the TPU within
    the kernel's default VMEM."""
    fn, shapes = _mla_cases()["paged_decode_latent"](BF16)
    pool = jax.ShapeDtypeStruct(*shapes[5])
    assert ca.paged_group([pool], 1024, whole=True) * 16 == 1024
    call, = _kernel_calls(fn, shapes)
    dots = [e for e in _eqns(call.params["jaxpr"])
            if e.primitive.name == "dot_general"]
    scores, values = dots
    assert scores.outvars[0].aval.shape == (32, 1024)
    assert values.invars[1].aval.shape == (1024, 512)
    n_scratch = call.params["grid_mapping"].num_scratch_operands
    buf, *_, m, l, acc = call.params["jaxpr"].invars[-n_scratch:]
    assert buf.aval.shape == (2, 1, 1024, 640)
    assert [v.aval.shape[0] for v in (m, l, acc)] == [32] * 3
    _compile(chip, fn, *shapes)


@pytest.mark.parametrize("dtype", [BF16, F32], ids=["bf16", "f32"])
@pytest.mark.parametrize("kernel", ["prefill_attention",
                                    "prefill_attention_short",
                                    "prefill_attention_selected",
                                    "prefill_attention_banded",
                                    "paged_decode_latent"])
def test_latent_attention_kernels_compile(chip, kernel, dtype):
    fn, shapes = _mla_cases()[kernel](dtype)
    _compile(chip, fn, *shapes)


# ----------------------------------------------------------------------
# ISSUE 50 — a model with no K/V layer: the state's one pass, in place
# ----------------------------------------------------------------------

# the Brumby cell's pool: 16 slots, 8 KV heads in groups of 5 query heads,
# heads of 128, a state 8 704 wide
RET = dict(slots=16, kv=8, group=5, d=128, wide=8704)


@pytest.mark.parametrize("mm", [BF16, F32], ids=["bf16", "f32"])
def test_retention_step_kernel_compiles(chip, mm):
    """ops/pallas/retention_step.py on the cell's whole pool of five layers
    (2.85 GB), entered at a layer that rides scalar prefetch: the pool is
    the call's aliased result, and nothing else of its size exists."""
    from dnn_tpu.ops.pallas.retention_step import retention_step

    b, kv, g, d, wide = (RET[k] for k in ("slots", "kv", "group", "d",
                                          "wide"))
    pool = (5, b, kv, d, wide)
    args = [jax.ShapeDtypeStruct(s, t, sharding=chip) for s, t in (
        (pool, F32), ((), jnp.int32), ((b, kv), F32), ((b, kv, d), F32),
        ((b, kv, wide), F32), ((b, kv, g, wide), F32))]
    compiled = jax.jit(
        lambda *a: retention_step(*a, mm_dtype=mm, interpret=False),
        donate_argnums=(0,)).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()
    mem = compiled.memory_analysis()
    assert mem.alias_size_in_bytes >= int(np.prod(pool)) * 4
    assert mem.temp_size_in_bytes < 2 ** 26  # the spread value, the answers


@pytest.fixture(scope="module")
def brumby_programs(chip):
    """Brumby's step programs at its published widths and the cell's pool
    (16 slots of state, 1024-token chunks), depth cut to TWO layers and
    the vocabulary to 8192 rows (the head is not what is held here).
    -> ({name: compiled}, the state leaf's extent a layer, its bytes)."""
    import dataclasses

    from dnn_tpu.models import llama
    from dnn_tpu.node import _stack_and_release
    from dnn_tpu.runtime.serving import ContinuousBatcher

    cfg = dataclasses.replace(llama.PRESETS["brumby-14b"], n_layer=2,
                              vocab_size=8192)
    prepared = _stack_and_release(llama.init(jax.random.PRNGKey(0), cfg),
                                  cfg, BF16)
    b = ContinuousBatcher(
        cfg, prepared, slots=RET["slots"], max_len=4096, prompt_pad=1024,
        kv="auto", family=llama.family_rows(cfg, compute_dtype=BF16))
    assert not b._paged and b._allocator is None
    assert b.cache["state"].shape == (
        2, RET["slots"], RET["kv"], RET["d"], RET["wide"])
    compiled = _lower_programs(
        chip, [(b, ("_prefill_chunk", "_prefill_finish", "_decode"))])
    return compiled, b.cache["state"].shape[1:], sum(
        x.nbytes for x in b.cache.values())


def _state_extent_ops(compiled, extent):
    return _extent_ops(compiled, re.compile(
        r"\[(?:\d+,)?%d,%d,%d,%d\]" % tuple(extent)))


def test_brumby_decode_step_updates_the_state_in_place(brumby_programs):
    """No operation of the decode step has a result of the extent of a
    layer's states (0.57 GB) or of the leaf but the kernel's own aliased
    result: no slice cut out, no copy written back; the donated leaves are
    the program's results and its temporaries stay far under a layer's
    states."""
    compiled, extent, pool_bytes = brumby_programs
    step = compiled["_decode"]
    ops = _state_extent_ops(step, extent)
    assert ops and {o[0] for o in ops} == {"custom-call"}, ops
    mem = step.memory_analysis()
    assert mem.alias_size_in_bytes >= pool_bytes
    assert mem.temp_size_in_bytes < int(np.prod(extent)) * 4 // 2


def test_brumby_finish_installs_a_state_without_a_pool_copy(brumby_programs):
    compiled, extent, pool_bytes = brumby_programs
    finish = compiled["_prefill_finish"]
    ops = _state_extent_ops(finish, extent)
    assert {o[0] for o in ops} <= {"dynamic-update-slice", "fusion"}, ops
    mem = finish.memory_analysis()
    assert mem.alias_size_in_bytes >= pool_bytes
    assert mem.temp_size_in_bytes < 2 ** 24


def test_brumby_chunk_program_fits_beside_the_pool(brumby_programs):
    """The chunk program works on the transient row alone (two leaves, no
    position axis) and its temporaries — a KV head's expanded queries at a
    time, own factors and partners — stay under a GB."""
    compiled, extent, _ = brumby_programs
    chunk = compiled["_prefill_chunk"]
    assert _state_extent_ops(chunk, extent) == []
    assert chunk.memory_analysis().temp_size_in_bytes < 2 ** 30


# ----------------------------------------------------------------------
# ISSUE 54 — ONE kind with paged K and V AND a state a slot (Falcon-H1)
# ----------------------------------------------------------------------

@pytest.fixture(scope="module")
def falcon_h1_programs(chip):
    """Falcon-H1's step programs at its published widths and the cell's
    pool (64 slots of state and of 1536 positions, 256-token chunks), depth
    cut to TWO layers and the vocabulary to 8192 rows (the head is not what
    is held here). -> ({name: compiled}, the state leaf's extent a layer,
    the K leaf's, the pool's bytes)."""
    import dataclasses

    from dnn_tpu.models import llama
    from dnn_tpu.node import _stack_and_release
    from dnn_tpu.runtime.serving import ContinuousBatcher

    cfg = dataclasses.replace(llama.PRESETS["falcon-h1-34b"], n_layer=2,
                              vocab_size=8192)
    prepared = _stack_and_release(llama.init(jax.random.PRNGKey(0), cfg),
                                  cfg, BF16)
    b = ContinuousBatcher(
        cfg, prepared, slots=64, max_len=1536, prompt_pad=256, kv="auto",
        family=llama.family_rows(cfg, compute_dtype=BF16))
    assert b._paged and b._allocator is not None
    assert b.cache["ssm_state"].shape == (2, 64, 32, 128, 256)
    assert b.cache["ssm_state"].dtype == jnp.float32
    assert b.cache["k"].shape == (2, 64 * 96 + 1, 4, 16, 128)
    compiled = _lower_programs(
        chip, [(b, ("_prefill_chunk", "_prefill_finish", "_decode"))])
    return (compiled, b.cache["ssm_state"].shape[1:], b.cache["k"].shape[1:],
            sum(x.nbytes for x in b.cache.values()))


def test_falcon_h1_decode_step_reaches_blocks_and_state_in_place(
        falcon_h1_programs):
    """The decode step runs the paged kernel and the one-token rule's kernel
    (ops/pallas/ssm_step.py, ISSUE 55) in one layer body: nothing of the
    extent of the K/V leaves but the paged kernel's aliased results, nothing
    of a layer's states' (0.27 GB) or of the state leaf's but the step
    kernel's aliased result — no `fusion`, `dynamic-update-slice` or `copy`
    of a layer's states, which is what the plain form's two reads and a
    write were (PERF.md section 6, PR 54 and PR 55); the donated leaves are
    the program's results and its temporaries stay under half a layer's
    states."""
    compiled, state, pool, pool_bytes = falcon_h1_programs
    step = compiled["_decode"]
    assert "tpu_custom_call" in step.as_text()
    assert {o[0] for o in _pool_extent_ops(step, pool)} <= {"custom-call"}
    ops = _state_extent_ops(step, state)
    assert ops and {o[0] for o in ops} == {"custom-call"}, ops
    assert "ssm_step" in step.as_text()
    mem = step.memory_analysis()
    assert mem.alias_size_in_bytes >= pool_bytes
    assert mem.temp_size_in_bytes < int(np.prod(state)) * 4 // 2


def test_ssm_step_kernel_compiles(chip):
    """ops/pallas/ssm_step.py on the cell's whole state leaf of nine layers
    (2.4 GB), entered at a layer that rides scalar prefetch: the leaf is the
    call's aliased result, nothing else of its size exists, and the heads of
    a grid step are a loop, not 16 written-out bodies (one transpose for the
    column, one for the answers)."""
    from dnn_tpu.ops.pallas.ssm_step import ssm_step

    b, h, g, p, n = 64, 32, 2, 128, 256
    pool = (9, b, h, p, n)
    shapes = ((pool, F32), ((), jnp.int32), ((b, h), F32), ((b, h), F32),
              ((h,), F32), ((b, h, p), F32), ((b, g, n), F32),
              ((b, g, n), F32))
    fn = functools.partial(ssm_step, interpret=False)
    call, = _kernel_calls(fn, shapes)
    names = [e.primitive.name for e in _eqns(call.params["jaxpr"])]
    assert names.count("transpose") == 2 and "dot_general" not in names
    args = [jax.ShapeDtypeStruct(s, t, sharding=chip) for s, t in shapes]
    compiled = jax.jit(fn, donate_argnums=(0,)).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()
    mem = compiled.memory_analysis()
    assert mem.alias_size_in_bytes >= int(np.prod(pool)) * 4
    assert mem.temp_size_in_bytes < 2 ** 22  # the rows beside the state


def test_falcon_h1_finish_installs_blocks_and_state_without_a_pool_copy(
        falcon_h1_programs):
    compiled, state, pool, pool_bytes = falcon_h1_programs
    finish = compiled["_prefill_finish"]
    for ops in (_state_extent_ops(finish, state),
                _pool_extent_ops(finish, pool)):
        assert {o[0] for o in ops} <= {"dynamic-update-slice", "fusion",
                                       "scatter"}, ops
    mem = finish.memory_analysis()
    assert mem.alias_size_in_bytes >= pool_bytes
    assert mem.temp_size_in_bytes < 2 ** 26


def test_falcon_h1_chunk_program_fits_beside_the_pool(falcon_h1_programs):
    """The chunk program works on the transient row alone (K and V of 1536
    positions, one slot's state and tail) and its temporaries stay under a
    quarter of a GB."""
    compiled, state, pool, _ = falcon_h1_programs
    chunk = compiled["_prefill_chunk"]
    assert _state_extent_ops(chunk, state) == []
    assert _pool_extent_ops(chunk, pool) == []
    assert "tpu_custom_call" in chunk.as_text()  # the prefill kernel
    assert chunk.memory_analysis().temp_size_in_bytes < 2 ** 28


# ----------------------------------------------------------------------
# ISSUE 58 — a strided leaf and a read of a LIST of blocks beside a state
# kind of one leaf (MiniCPM-SALA)
# ----------------------------------------------------------------------

@pytest.fixture(scope="module")
def sala_programs(chip):
    """MiniCPM-SALA's step programs at its published widths and the cell's
    pool (32 slots of 25 600 positions in blocks of 64, 1024-token chunks),
    depth cut to TWO layers (one of each kind) and the vocabulary to 8192
    rows. -> ({name: compiled}, the state leaf's extent a layer, the K
    leaf's, the pool's bytes)."""
    import dataclasses

    from dnn_tpu.models import llama
    from dnn_tpu.node import _stack_and_release
    from dnn_tpu.runtime.serving import ContinuousBatcher

    cfg = dataclasses.replace(
        llama.PRESETS["minicpm-sala"], n_layer=2, vocab_size=8192,
        layer_types=("full", "linear"))
    prepared = _stack_and_release(llama.init(jax.random.PRNGKey(0), cfg),
                                  cfg, BF16)
    b = ContinuousBatcher(
        cfg, prepared, slots=32, max_len=25600, prompt_pad=1024, kv="auto",
        block_len=64, family=llama.family_rows(cfg, compute_dtype=BF16))
    assert b._paged and b._allocator is not None
    assert b.cache["state"].shape == (1, 32, 32, 128, 128)
    assert b.cache["state"].dtype == jnp.float32
    assert b.cache["k"].shape == (1, 32 * 400 + 1, 2, 64, 128)
    assert b.cache["kc"].shape == (1, 32 * 400 + 1, 2, 4, 128)
    compiled = _lower_programs(
        chip, [(b, ("_prefill_chunk", "_prefill_finish", "_decode"))])
    return (compiled, b.cache["state"].shape[1:], b.cache["k"].shape[1:],
            sum(x.nbytes for x in b.cache.values()))


def test_sala_decode_step_reads_a_list_of_blocks_in_place(sala_programs):
    """The decode step scores the slot's pooled rows and hands the chosen
    blocks' LIST and the step's K and V rows to ops/pallas/
    block_list_attention.py, which places the rows itself: nothing of the K/V
    leaves' extent but the kernel's aliased results (an XLA scatter into the
    pool beside the kernel made the compiler copy each leaf twice a step to
    reconcile their layouts: 4 x 0.42 GB), and the donated leaves are the
    program's results."""
    compiled, state, pool, pool_bytes = sala_programs
    step = compiled["_decode"]
    assert "block_list_attention" in step.as_text()
    assert {o[0] for o in _pool_extent_ops(step, pool)} <= {"custom-call"}
    # the one-token rule's kernel (ops/pallas/lin_step.py): nothing of a
    # layer's states' extent but its aliased result
    ops = _state_extent_ops(step, state)
    assert ops and {o[0] for o in ops} == {"custom-call"}, ops
    assert "lin_step" in step.as_text()
    mem = step.memory_analysis()
    assert mem.alias_size_in_bytes >= pool_bytes
    # the gathered pooled rows and the scores, not a copy of a leaf
    assert mem.temp_size_in_bytes < 2 ** 29


def test_sala_finish_installs_blocks_and_state_without_a_pool_copy(
        sala_programs):
    compiled, state, pool, pool_bytes = sala_programs
    finish = compiled["_prefill_finish"]
    for ops in (_state_extent_ops(finish, state),
                _pool_extent_ops(finish, pool)):
        assert {o[0] for o in ops} <= {"dynamic-update-slice", "fusion",
                                       "scatter"}, ops
    mem = finish.memory_analysis()
    assert mem.alias_size_in_bytes >= pool_bytes
    assert mem.temp_size_in_bytes < 2 ** 27


def test_sala_chunk_program_fits_beside_the_pool(sala_programs):
    """The chunk program works on the transient row alone (K and V of 25 600
    positions, their pooled keys, one slot's state): the prefill kernel
    under a mask a KV group, temporaries under 1.5 GB (the pooled scores of
    32 heads and the mask)."""
    compiled, state, pool, _ = sala_programs
    chunk = compiled["_prefill_chunk"]
    assert _pool_extent_ops(chunk, pool) == []
    assert "sparse_prefill_attention" in chunk.as_text()
    assert chunk.memory_analysis().temp_size_in_bytes < 3 * 2 ** 29


def test_lin_step_kernel_compiles(chip):
    """ops/pallas/lin_step.py on the cell's whole state leaf of three layers
    (0.6 GB), entered at a layer that rides scalar prefetch: the leaf is the
    call's aliased result and nothing else of its size exists."""
    from dnn_tpu.ops.pallas.lin_step import lin_step

    b, h, d = 32, 32, 128
    pool = (3, b, h, d, d)
    shapes = ((pool, F32), ((), jnp.int32), ((h,), F32), ((b, h, d), F32),
              ((b, h, d), F32), ((b, h, d), F32))
    fn = functools.partial(lin_step, interpret=False)
    args = [jax.ShapeDtypeStruct(s, t, sharding=chip) for s, t in shapes]
    compiled = jax.jit(fn, donate_argnums=(0,)).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()
    mem = compiled.memory_analysis()
    assert mem.alias_size_in_bytes >= int(np.prod(pool)) * 4
    assert mem.temp_size_in_bytes < 2 ** 22  # the rows beside the state


def test_a_kernels_text_compares_without_its_callers_lines(chip):
    """`tests/step_program_texts.without_kernel_locations`: one kernel
    lowered from two call sites differs in its serialized body — the MLIR
    bytecode carries the callers' lines — and compares equal once the body
    is printed without debug locations (how PR 60 held the state families'
    real-width programs to their parent's)."""
    from dnn_tpu.ops.pallas.lin_step import lin_step
    from tests.step_program_texts import without_kernel_locations

    b, h, d = 4, 8, 128
    shapes = (((2, b, h, d, d), F32), ((), jnp.int32), ((h,), F32),
              ((b, h, d), F32), ((b, h, d), F32), ((b, h, d), F32))
    args = [jax.ShapeDtypeStruct(s, t, sharding=chip) for s, t in shapes]

    def here():
        def call(*a):
            return lin_step(*a, interpret=False)
        return call

    def there():
        def call(*a):
            return lin_step(*a, interpret=False)
        return call

    one, other = (jax.jit(f()).lower(*args).as_text() for f in (here, there))
    assert "tpu_custom_call" in one and one != other
    assert without_kernel_locations(one) == without_kernel_locations(other)
    assert without_kernel_locations("no kernel here") == "no kernel here"


def test_block_list_kernel_compiles(chip):
    """ops/pallas/block_list_attention.py at the cell's call: 32 slots x 2
    KV heads of 16 query rows, lists of 96 blocks of 64 positions out of a
    pool of 12 801."""
    from dnn_tpu.ops.pallas.block_list_attention import block_list_attention

    pool = ((1, 12801, 2, 64, 128), BF16)
    shapes = (((32, 2, 16, 128), BF16), pool, pool, ((32, 2, 96), jnp.int32),
              ((32, 2), jnp.int32), ((32,), jnp.int32), ((), jnp.int32))

    def fn(q, kp, vp, ids, count, pos, layer):
        return block_list_attention(q, kp, vp, ids, count, pos, layer=layer,
                                    interpret=False)

    compiled = _compile(chip, fn, *shapes)
    assert compiled.memory_analysis().temp_size_in_bytes < 2 ** 22


# ----------------------------------------------------------------------
# ISSUE 62 — a window kind of 65 blocks a slot beside a full kind of 1088,
# each rotated by its own table (Mellum2)
# ----------------------------------------------------------------------

@pytest.fixture(scope="module")
def mellum_programs(chip):
    """Mellum2's step programs at its published widths and the cell's pool
    (16 slots of 17 408 positions in blocks of 16, 1024-token chunks), depth
    cut to ONE period (S S S F: the window kind's run is a loop over a leaf
    of three layers, as the cell's) and the vocabulary to 8192 rows.
    -> ({name: compiled}, the full kind's K leaf's extent a layer, the
    window kind's, the pool's bytes)."""
    import dataclasses

    from dnn_tpu.models import llama_moe
    from dnn_tpu.node import _stack_and_release
    from dnn_tpu.registry import ParamParts
    from dnn_tpu.runtime.serving import ContinuousBatcher

    cfg = dataclasses.replace(
        llama_moe.PRESETS["mellum2-12b-a2.5b"], n_layer=4, vocab_size=8192,
        layer_types=("window", "window", "window", "full"))
    prepared = _stack_and_release(
        ParamParts(llama_moe.init_parts(jax.random.PRNGKey(0), cfg)), cfg,
        BF16)  # a layer at a time, as the daemon boots
    b = ContinuousBatcher(
        cfg, prepared, slots=16, max_len=17408, prompt_pad=1024, kv="auto",
        family=llama_moe.family_rows(cfg, compute_dtype=BF16))
    assert b._paged and b._allocator is not None
    assert b.cache["k"].shape == (1, 16 * 1088 + 1, 4, 16, 128)
    assert b.cache["k_w"].shape == (3, 16 * 65 + 1, 4, 16, 128)
    compiled = _lower_programs(
        chip, [(b, ("_prefill_chunk", "_prefill_finish", "_decode"))])
    return (compiled, b.cache["k"].shape[1:], b.cache["k_w"].shape[1:],
            sum(x.nbytes for x in b.cache.values()))


#: the kinds of operation that have a window leaf's extent in the compiled
#: programs: the step's rows scattered in, and the compiler's own moves of a
#: leaf this small (51 MB at three layers) — another layout for the gather,
#: a layer's slice or the whole leaf fetched into fast memory in pieces
#: (`slice-start` ... `ConcatBitcast`)
_WINDOW_LEAF_OPS = {"scatter", "fusion", "dynamic-update-slice",
                    "dynamic-slice", "copy", "copy-start", "copy-done",
                    "slice-start", "slice-done", "custom-call"}


def test_mellum_decode_step_reaches_the_full_kind_in_place(mellum_programs):
    """The full kind's read is the paged kernel over the pool left in HBM:
    nothing of the leaf's extent but its aliased results, and the donated
    leaves are the program's results. The window kind's 65 blocks a slot are
    GATHERED, and the compiled text shows what that costs at this width
    (PERF.md section 7, PR 62): the compiler gives the window leaves another
    layout for the gather — a `copy` of each whole leaf on the way in and
    on the way out — and brings a layer's whole slice (17 MB) into fast
    memory for each gather (`dynamic-slice`, `copy` under
    `kv_pool.gather`), as it does for every gather's operand. This case
    holds the kinds of operation that have the leaf's extent, so that a
    change to the read shows here before it shows on the chip."""
    compiled, pool, pool_w, pool_bytes = mellum_programs
    step = compiled["_decode"]
    assert "tpu_custom_call" in step.as_text()
    assert {o[0] for o in _pool_extent_ops(step, pool)} <= {"custom-call"}
    ops_w = _pool_extent_ops(step, pool_w)
    assert {o[0] for o in ops_w} <= _WINDOW_LEAF_OPS, ops_w
    mem = step.memory_analysis()
    assert mem.alias_size_in_bytes >= pool_bytes
    assert mem.temp_size_in_bytes < 2 ** 29


def test_mellum_finish_installs_blocks_without_a_pool_copy(mellum_programs):
    compiled, pool, pool_w, pool_bytes = mellum_programs
    finish = compiled["_prefill_finish"]
    ops = _pool_extent_ops(finish, pool)
    assert {o[0] for o in ops} <= {"dynamic-update-slice", "fusion",
                                   "scatter"}, ops
    ops_w = _pool_extent_ops(finish, pool_w)
    assert {o[0] for o in ops_w} <= _WINDOW_LEAF_OPS, ops_w
    mem = finish.memory_analysis()
    assert mem.alias_size_in_bytes >= pool_bytes
    assert mem.temp_size_in_bytes < 2 ** 27


def test_mellum_chunk_program_fits_beside_the_pool(mellum_programs):
    """The chunk program works on the transient rows alone (K and V of
    17 408 positions a layer, for the window kind too: ROADMAP R2 (d)):
    both kinds' reads are the folded prefill kernel, the window kind's
    banded; temporaries under 1 GB."""
    compiled, pool, pool_w, _ = mellum_programs
    chunk = compiled["_prefill_chunk"]
    assert _pool_extent_ops(chunk, pool) == []
    assert _pool_extent_ops(chunk, pool_w) == []
    assert chunk.as_text().count("tpu_custom_call") >= 2
    assert chunk.memory_analysis().temp_size_in_bytes < 2 ** 30


# ----------------------------------------------------------------------
# ISSUE 66 — blocks of ONE mixer: a state kind of slot leaves alone, a full
# kind of one layer, expert blocks that keep nothing, experts in a latent
# (Nemotron-3-Super)
# ----------------------------------------------------------------------

@pytest.fixture(scope="module")
def nemotron_programs(chip):
    """The Nemotron-3-Super cut's step programs at its published widths and
    the cell's pool (64 slots of state and of 10 240 positions, 1024-token
    chunks), depth cut to M E M * E (every kind, two runs of the state and
    of the expert stack), 16 of the 512 experts held (each 11 MB: the
    stack's extent is not what is held here) and the vocabulary to 8192
    rows. -> ({name: compiled}, the state leaf's extent a layer, the K
    leaf's, the pool's bytes, the held expert stacks' shapes)."""
    import dataclasses

    from dnn_tpu.models import llama_moe
    from dnn_tpu.node import _stack_and_release
    from dnn_tpu.registry import ParamParts
    from dnn_tpu.runtime.serving import ContinuousBatcher

    cfg = dataclasses.replace(
        llama_moe.PRESETS["nemotron-3-super-120b-a12b-ep4-1chip"], n_layer=5,
        layer_types=llama_moe.pattern_types("MEM*E"), vocab_size=8192,
        experts_held=16)
    prepared = _stack_and_release(
        ParamParts(llama_moe.init_parts(jax.random.PRNGKey(0), cfg)), cfg,
        BF16)  # a block at a time, as the daemon boots
    b = ContinuousBatcher(
        cfg, prepared, slots=64, max_len=10240, prompt_pad=1024, kv="auto",
        family=llama_moe.family_rows(cfg, compute_dtype=BF16))
    assert b._paged and b._allocator is not None and b._moe_stats
    assert b.cache["ssm_state"].shape == (2, 64, 128, 64, 128)
    assert b.cache["ssm_state"].dtype == jnp.float32
    assert b.cache["conv_tail"].shape == (2, 64, 3, 10240)
    assert b.cache["k"].shape == (1, 64 * 640 + 1, 2, 16, 128)
    stacks = {n: prepared["expert_blocks"]["moe"][n].shape
              for n in ("wi", "wo")}
    assert stacks == {"wi": (2, 16, 1024, 2688), "wo": (2, 16, 2688, 1024)}
    compiled = _lower_programs(
        chip, [(b, ("_prefill_chunk", "_prefill_finish", "_decode"))])
    return (compiled, b.cache["ssm_state"].shape[1:], b.cache["k"].shape[1:],
            sum(x.nbytes for x in b.cache.values()), stacks)


def _stack_extent_ops(compiled, shape):
    return _extent_ops(compiled, re.compile(
        r"\[(?:\d+,)?%d,%d,%d\]" % tuple(shape[1:])))


def test_nemotron_decode_step_reaches_state_blocks_and_experts_in_place(
        nemotron_programs):
    """One decode step runs the one-token rule's kernel (ops/pallas/
    ssm_step.py at P = 64, N = 128), the paged kernel over the ONE attention
    block's pool and the grouped matmuls over the held stacks, each in
    place: nothing of a layer's states' extent but the step kernel's aliased
    result, nothing of the K/V leaves' but the paged kernel's, no operation
    whose result is an expert stack's or a layer's slice of one; the donated
    leaves are the program's results."""
    compiled, state, pool, pool_bytes, stacks = nemotron_programs
    step = compiled["_decode"]
    text = step.as_text()
    assert "ssm_step" in text and "grouped_matmul" in text
    assert {o[0] for o in _pool_extent_ops(step, pool)} <= {"custom-call"}
    # the kernel reads the leaf FOLDED, two heads of 64 a (128, 128) tile:
    # the same bytes (a bitcast either way), so the leaf's extent as the
    # pool holds it appears in no operation and the folded one in the
    # kernel's aliased result alone
    slots, h, p, n = state
    assert _state_extent_ops(step, state) == []
    ops = _state_extent_ops(step, (slots, h // 2, 2 * p, n))
    assert ops and {o[0] for o in ops} == {"custom-call"}, ops
    for shape in stacks.values():
        assert _stack_extent_ops(step, shape) == []
    mem = step.memory_analysis()
    assert mem.alias_size_in_bytes >= pool_bytes
    assert mem.temp_size_in_bytes < int(np.prod(state)) * 4 // 2


def test_nemotron_finish_installs_blocks_and_state_without_a_pool_copy(
        nemotron_programs):
    compiled, state, pool, pool_bytes, _ = nemotron_programs
    finish = compiled["_prefill_finish"]
    for ops in (_state_extent_ops(finish, state),
                _pool_extent_ops(finish, pool)):
        assert {o[0] for o in ops} <= {"dynamic-update-slice", "fusion",
                                       "scatter"}, ops
    mem = finish.memory_analysis()
    assert mem.alias_size_in_bytes >= pool_bytes
    assert mem.temp_size_in_bytes < 2 ** 26


def test_nemotron_chunk_program_fits_beside_the_pool(nemotron_programs):
    """The chunk program works on the transient row alone (K and V of 10 240
    positions of one layer, one slot's state and tail a state layer): the
    prefill kernel, the grouped matmuls at K = 1024 / N = 2688 and back and
    the rounds' row_accumulate; no expert stack is cut or copied and its
    temporaries stay under 1 GB."""
    compiled, state, pool, _, stacks = nemotron_programs
    chunk = compiled["_prefill_chunk"]
    text = chunk.as_text()
    assert _state_extent_ops(chunk, state) == []
    assert _pool_extent_ops(chunk, pool) == []
    assert "grouped_matmul" in text and "row_accumulate" in text
    for shape in stacks.values():
        assert _stack_extent_ops(chunk, shape) == []
    assert chunk.memory_analysis().temp_size_in_bytes < 2 ** 30


def test_ssm_step_kernel_compiles_at_the_nemotron_heads(chip):
    """ops/pallas/ssm_step.py on the cell's whole state leaf of five layers
    (1.34 GB) at P = 64, N = 128, 16 heads a group, folded two heads a
    tile: the leaf is the call's aliased result, nothing else of its size
    exists (the fold's reshapes are bitcasts) and both transposes are 128 x
    128."""
    from dnn_tpu.ops.pallas.ssm_step import ssm_step

    b, h, g, p, n = 64, 128, 8, 64, 128
    pool = (5, b, h, p, n)
    shapes = ((pool, F32), ((), jnp.int32), ((b, h), F32), ((b, h), F32),
              ((h,), F32), ((b, h, p), F32), ((b, g, n), F32),
              ((b, g, n), F32))
    fn = functools.partial(ssm_step, interpret=False)
    call, = _kernel_calls(fn, shapes)
    eqns = _eqns(call.params["jaxpr"])
    turned = [e.invars[0].aval.shape for e in eqns
              if e.primitive.name == "transpose"]
    assert turned == [(128, 128), (128, 128)]
    args = [jax.ShapeDtypeStruct(s, t, sharding=chip) for s, t in shapes]
    compiled = jax.jit(fn, donate_argnums=(0,)).lower(*args).compile()
    text = compiled.as_text()
    assert "tpu_custom_call" in text
    assert not re.search(r"f32\[5,64,(128,64|64,128),128\]\S* copy\(", text)
    mem = compiled.memory_analysis()
    assert mem.alias_size_in_bytes >= int(np.prod(pool)) * 4
    assert mem.temp_size_in_bytes < 2 ** 23
