"""Pipeline-parallel runtimes.

Two executors for the reference's core capability — "split a model into
sequential parts, run each part on a different device, relay activations"
(readme.md:1-3, node.py:35-105) — redesigned for TPU:

1. `RelayExecutor` — device-per-stage sequential relay. Execution semantics
   identical to the reference (one request traverses the chain, stage i+1
   starts after stage i finishes — SURVEY §3.3), but each hop is a
   device-to-device transfer of a jit output instead of a gRPC unary RPC
   with numpy-bytes payloads. Handles arbitrarily heterogeneous stages.

2. `spmd_pipeline` — the TPU-native fast path. One SPMD program over a
   Mesh "stage" axis: every device runs the same compiled step; activations
   move stage->stage with `lax.ppermute` (XLA CollectivePermute over ICI);
   microbatches flow in a GPipe schedule (M microbatches through S stages in
   M+S-1 steps, all stages busy in steady state). The reference cannot
   overlap stages at all — its nested-RPC design holds every hop open for
   the full downstream latency (node.py:84, SURVEY §3.3).

Heterogeneous stages are uniformized for SPMD by flattening + zero-padding
activations to one (microbatch, F) buffer and `lax.switch`-ing on the
stage coordinate. The SPMD contract this relies on — every switch branch
(= every stage program) issues the IDENTICAL collective sequence, else
ranks deadlock — is enforced statically: the analyzer's program pass
walks the traced pipeline's jaxpr and compares branch collective
signatures (dnn_tpu/analysis/program.check_branch_collectives, PRG001;
pinned by tests/test_analysis.py::test_pipeline_audit_collectives_consistent),
so a stage fn that grows a psum the others lack fails CI before it can
hang a mesh. The buffer dtype follows the payloads (see
_buffer_dtype): single-dtype pipelines ride natively (bf16 hops cost bf16
bytes over ICI), mixed pipelines use an f32 carrier with integer payloads
bitcast in (exact for all of int32, not just ints < 2^24). Homogeneous
stacks (transformer blocks) should use `spmd_pipeline_stacked` instead,
which shards one block's params per stage and skips the switch entirely.
"""

from __future__ import annotations

import time
from typing import Any, Callable, List, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from dnn_tpu.analysis.shardcheck import contract as _shardcheck_contract
from dnn_tpu.obs.profile import annotation_ctx as _prof_annotation
from dnn_tpu.parallel.mesh import STAGE_AXIS


# ----------------------------------------------------------------------
# microbatch helpers
# ----------------------------------------------------------------------

def split_microbatches(x, num_microbatches: int):
    """(B, ...) -> (M, B//M, ...). The reference has no microbatching (batch
    size 1 end to end, node.py:147,151); this is the upgrade that makes the
    pipeline actually parallel."""
    b = x.shape[0]
    if b % num_microbatches != 0:
        raise ValueError(f"batch {b} not divisible by microbatches {num_microbatches}")
    return x.reshape(num_microbatches, b // num_microbatches, *x.shape[1:])


def merge_microbatches(y):
    return y.reshape(y.shape[0] * y.shape[1], *y.shape[2:])


# ----------------------------------------------------------------------
# 1. relay executor (reference semantics, TPU devices)
# ----------------------------------------------------------------------

class RelayExecutor:
    """Sequential stage relay across explicit devices.

    Mirrors the reference pipeline one-to-one: stage i's jitted program runs
    on device i, the output is handed to device i+1 (XLA device-to-device
    copy — the rebuilt SendTensor hop), and the final output returns to the
    host (the rebuilt result_tensor response chain, node.py:88-105).

    This IS the `device` rung of the pluggable transport ladder
    (comm/transport.py), in its same-process form: the hop is a direct
    `jax.device_put` of the jit output with zero host serialization —
    what the gRPC edge negotiates per hop when both stages share a
    process (the mailbox ticket path), this executor does inline. Hop
    metrics/spans carry the `transport="device"` label so the fleet
    view compares rungs directly.
    """

    #: negotiated-transport label for this executor's hops
    transport = "device"

    def __init__(self, stage_fns: Sequence[Callable], stage_params: Sequence[Any], devices=None):
        if len(stage_fns) != len(stage_params):
            raise ValueError("one params pytree per stage required")
        devices = list(devices if devices is not None else jax.devices())
        self.devices = [devices[i % len(devices)] for i in range(len(stage_fns))]
        # Params are committed to their stage's device once, at load time —
        # the HBM-resident analog of each node loading its slice at startup
        # (node.py:294-317).
        self.stage_params = [
            jax.device_put(p, d) for p, d in zip(stage_params, self.devices)
        ]
        self.stage_fns = [jax.jit(fn) for fn in stage_fns]
        # populated by record_timings runs (per-stage compute only; hop
        # latency needs the slope method — see measure_hop_latency)
        self.last_stage_times: Optional[List[float]] = None

    def __call__(self, x, *, record_timings: bool = False):
        if not record_timings:
            for i, (fn, params, dev) in enumerate(
                    zip(self.stage_fns, self.stage_params, self.devices)):
                # host annotation per stage hop: a profiler capture
                # (POST /profilez, obs/profile.py) names each relay stage
                # on the host track. annotation_ctx, not the generator
                # `annotation` form — this runs once per hop per decode
                # step, and the generator shape pays a frame per call
                # even with nothing recording
                with _prof_annotation(f"relay.stage{i}"):
                    x = fn(params, jax.device_put(x, dev))
            self.last_stage_times = None
            return x

        from dnn_tpu import obs
        from dnn_tpu.utils.metrics import labeled
        from dnn_tpu.utils.tracing import device_sync

        stages = []
        m = obs.metrics()
        for i, (fn, params, dev) in enumerate(
                zip(self.stage_fns, self.stage_params, self.devices)):
            xd = jax.device_put(x, dev)
            device_sync(xd)
            t1 = time.perf_counter()
            x = fn(params, xd)
            device_sync(x)
            dt = time.perf_counter() - t1
            stages.append(dt)
            if m is not None:
                # per-stage compute in the shared registry — the relay
                # runtime's contribution to the /metrics breakdown
                m.observe(labeled("relay.stage_compute_seconds", stage=i,
                                  transport=self.transport),
                          dt)
        self.last_stage_times = stages
        return x

    def measure_hop_latency(self, x, *, n1: int = 2, n2: int = 8) -> List[float]:
        """One-way device-to-device transfer time per inter-stage hop,
        measured honestly (SURVEY §7 hard part 4).

        A naive `device_put + sync` sample would be dominated by the
        host sync round trip, not the transfer. Instead,
        ping-pong the *actual activation entering stage i* between the two
        stage devices n times back-to-back (an async dependency chain), sync
        once, and take the two-point slope (t(n2) - t(n1)) / (n2 - n1) so
        the constant sync RTT cancels; halve the per-pair slope for the
        one-way time. Returns one entry per hop (stage i-1 -> stage i;
        stage 0 has no incoming hop)."""
        from dnn_tpu.utils.tracing import device_sync

        acts = []  # activation entering each stage, as produced upstream
        for fn, params, dev in zip(self.stage_fns, self.stage_params, self.devices):
            acts.append(x)
            x = fn(params, jax.device_put(x, dev))
        device_sync(x)

        hops = []
        for i in range(1, len(self.devices)):
            a, b = self.devices[i - 1], self.devices[i]
            act = jax.device_put(acts[i], a)
            device_sync(act)

            def run(n):
                y = act
                t0 = time.perf_counter()
                for _ in range(n):
                    y = jax.device_put(jax.device_put(y, b), a)
                device_sync(y)
                return time.perf_counter() - t0

            run(1)  # warmup
            # clamp: on fast transports the slope can jitter below zero,
            # which is pure measurement noise, not a latency
            hops.append(max(0.0, (run(n2) - run(n1)) / (n2 - n1) / 2.0))
        from dnn_tpu import obs
        from dnn_tpu.utils.metrics import labeled

        m = obs.metrics()
        if m is not None:
            for i, h in enumerate(hops, start=1):
                m.observe(labeled("relay.hop_seconds", hop=i,
                                  transport=self.transport), h)
        return hops


# ----------------------------------------------------------------------
# 2. SPMD microbatched pipeline (shard_map + ppermute)
# ----------------------------------------------------------------------

def _flat_size(shape) -> int:
    return int(np.prod(shape)) if shape else 1


def _buffer_dtype(dtypes):
    """Carrier dtype for a ring buffer holding payloads of `dtypes`.

    One payload dtype -> carry it natively (a bf16 pipeline pays bf16 ICI
    bytes per hop, half of f32; an all-int pipeline rides exactly). Mixed
    dtypes -> an f32 buffer; float payloads upcast losslessly and integer
    payloads are BITCAST in (exact for the full int32 range — no "ints fit
    in f32 below 2^24" assumption). Bitcasting is safe here because the
    hop path is pure data movement (ppermute / select / pad / slice):
    nothing arithmetic ever touches the carrier bits.
    """
    dtypes = {jnp.dtype(d) for d in dtypes}
    if len(dtypes) == 1:
        return next(iter(dtypes))
    for d in dtypes:
        if d.itemsize > 4:
            raise ValueError(
                f"cannot carry {d} on a mixed-dtype pipeline ring (the "
                "carrier is 32-bit); cast integer ids to int32 / floats "
                "to float32"
            )
    return jnp.dtype(jnp.float32)


def _pad_flat(y, width, buf_dtype=jnp.float32):
    flat = y.reshape(y.shape[0], -1)
    if flat.dtype != buf_dtype:
        if jnp.issubdtype(flat.dtype, jnp.integer):
            # mixed-dtype buffer: ints bitcast into the f32 carrier
            flat = lax.bitcast_convert_type(flat.astype(jnp.int32), jnp.float32)
            flat = flat.astype(buf_dtype)  # no-op (carrier is f32)
        else:
            flat = flat.astype(buf_dtype)
    return jnp.pad(flat, ((0, 0), (0, width - flat.shape[1])))


def _unpad(buf, shape, dtype, buf_dtype=jnp.float32):
    mb = buf.shape[0]
    flat = buf[:, : _flat_size(shape[1:])]
    # mirror of _pad_flat: integer payloads on the mixed (f32-carrier) ring
    # were bitcast in, so bitcast them back out; everything else astypes
    if jnp.dtype(buf_dtype) != jnp.dtype(dtype) and jnp.issubdtype(dtype, jnp.integer):
        flat = lax.bitcast_convert_type(flat, jnp.int32).astype(dtype)
    return flat.reshape(mb, *shape[1:]).astype(dtype)


def pack_stage_params(stage_params):
    """Heterogeneous per-stage param pytrees -> one (S, W) f32 HOST (numpy)
    array (each stage's leaves flattened, concatenated, zero-padded to the
    widest stage) + per-stage unpack metadata. Sharded P(stage), this is
    what lets `spmd_pipeline` place each stage's weights on its own device:
    lax.switch executes only the selected branch (XLA Case), but branch
    OPERANDS must exist on every device — packing turns "operand = all
    stages' params, replicated" into "operand = my (1, W) shard".

    Packing runs in numpy on the host on purpose: the whole (S, W) array
    must never materialize in one device's HBM (that would cap model size
    at single-device memory — the opposite of per-stage placement).
    Callers `jax.device_put` the result with a P(stage) NamedSharding,
    which sends each row directly to its stage's device.

    Carrier dtype: when every leaf shares one float dtype, the packed
    array keeps it — a bf16 model's per-device row is bf16, not a 2x-HBM
    f32 upcast. Mixed float dtypes ride an f32 carrier (lossless for
    bf16/f16/f32; a mix including f64 is rejected rather than silently
    truncated). Integer leaves are rejected outright (params are float in
    every shipped family, and silent bitcast here would be invisible to
    readers of the packed array) — keep integer-param models on
    `param_placement="replicated"`."""
    per_stage, dtypes = [], set()
    for p in stage_params:
        leaves, treedef = jax.tree.flatten(p)
        arrs = []
        for leaf in leaves:
            arr = np.asarray(leaf)
            if not jnp.issubdtype(arr.dtype, jnp.floating):
                raise ValueError(
                    f"pack_stage_params supports float leaves only, got "
                    f"{arr.dtype}; use spmd_pipeline(..., "
                    f"param_placement='replicated') for non-float params"
                )
            arrs.append(arr)
            dtypes.add(jnp.dtype(arr.dtype))
        per_stage.append((treedef, arrs))

    if len(dtypes) == 1:
        carrier = dtypes.pop()
    else:
        carrier = jnp.dtype(np.float32)
        wide = [d for d in dtypes if d.itemsize > 4]
        if wide:
            raise ValueError(
                f"pack_stage_params: mixed param dtypes {sorted(map(str, dtypes))} "
                f"would silently truncate {sorted(map(str, wide))} through the "
                f"f32 carrier; cast params to one dtype or use "
                f"spmd_pipeline(..., param_placement='replicated')"
            )

    flats, metas = [], []
    for treedef, arrs in per_stage:
        vecs = [a.astype(carrier).reshape(-1) for a in arrs]
        leafmeta = [(a.shape, jnp.dtype(a.dtype)) for a in arrs]
        flats.append(np.concatenate(vecs) if vecs else np.zeros((0,), carrier))
        metas.append((treedef, leafmeta))
    width = max((f.shape[0] for f in flats), default=1) or 1
    packed = np.stack([np.pad(f, (0, width - f.shape[0])) for f in flats])
    return packed, metas


def _unpack_stage(vec, meta):
    """(W,) packed vector -> the stage's param pytree (inverse of one row
    of pack_stage_params)."""
    treedef, leafmeta = meta
    leaves, off = [], 0
    for shape, dtype in leafmeta:
        n = _flat_size(shape)
        leaves.append(lax.slice(vec, (off,), (off + n,)).reshape(shape).astype(dtype))
        off += n
    return jax.tree_util.tree_unflatten(treedef, leaves)


def _stage_shapes(stage_fns, stage_params, x_shape_dtype):
    """Trace per-stage input/output shapes (static — the reference discovers
    them at runtime from the wire header, node_service.proto:28-29)."""
    shapes = [x_shape_dtype]
    for fn, p in zip(stage_fns, stage_params):
        out = jax.eval_shape(fn, p, shapes[-1])
        shapes.append(jax.ShapeDtypeStruct(out.shape, out.dtype))
    return shapes


def _gpipe_loop(
    stage_step, inputs_buf, num_stages, num_microbatches, mb, width_hop, width_out, axis_name,
    out_dtype=jnp.float32,
):
    """The schedule, run per-device inside shard_map: at step t, stage d
    works on microbatch t-d; outputs hop to d+1 via ppermute.

    `stage_step(buf) -> (hop, out)`: `hop` (mb, width_hop) feeds the next
    stage; `out` (mb, width_out) is the pipeline product, only meaningful on
    the last stage. Hop and output widths are separate on purpose — for LM
    pipelines the final logits are ~vocab/hidden times wider than the
    inter-stage activations, and must never ride the ppermute ring. The hop
    buffer dtype is whatever `inputs_buf` carries (see _buffer_dtype); the
    out buffer is always the final stage's OWN dtype — unlike the hop ring
    it passes through an arithmetic psum, so bitcast carriage would be
    unsafe there (FTZ can flush denormal bit patterns), and it never mixes
    dtypes anyway.
    """
    m_count = num_microbatches
    steps = m_count + num_stages - 1
    d = lax.axis_index(axis_name)
    is_last = d == num_stages - 1

    out_buf = jnp.zeros((m_count + 1, mb, width_out), out_dtype)  # slot M = scratch
    buf0 = inputs_buf[0]

    def step(carry, t):
        buf, out = carry
        hop_y, out_y = stage_step(buf)

        # collect on the last stage: microbatch m = t - (S-1)
        m = t - (num_stages - 1)
        valid = jnp.logical_and(is_last, jnp.logical_and(m >= 0, m < m_count))
        write_idx = jnp.where(valid, jnp.clip(m, 0, m_count - 1), m_count)
        out = lax.dynamic_update_index_in_dim(out, out_y, write_idx, 0)

        # hop: my output becomes stage d+1's next input
        recv = lax.ppermute(hop_y, axis_name, [(i, i + 1) for i in range(num_stages - 1)])
        nxt = jnp.clip(t + 1, 0, m_count - 1)
        fresh = lax.dynamic_index_in_dim(inputs_buf, nxt, 0, keepdims=False)
        buf = jnp.where(d == 0, fresh, recv)
        return (buf, out), None

    (_, out_buf), _ = lax.scan(step, (buf0, out_buf), jnp.arange(steps))
    out = out_buf[:m_count]
    # only the last stage holds real data; replicate it to everyone
    return lax.psum(jnp.where(is_last, out, jnp.zeros_like(out)), axis_name)


def spmd_pipeline(
    stage_fns: Sequence[Callable],
    stage_params: Sequence[Any],
    x,
    *,
    mesh: Mesh,
    num_microbatches: int = 1,
    axis_name: str = STAGE_AXIS,
    param_placement: str = "auto",
    packed=None,
):
    """Heterogeneous-stage SPMD pipeline.

    All ranks run one program; each applies its own stage via `lax.switch`
    on the stage coordinate. Activations ride a uniform padded buffer
    (ppermute needs one shape on every rank — the SPMD answer to the
    reference's per-hop dynamic wire shapes) whose dtype follows the
    payloads (_buffer_dtype): native when uniform, f32 carrier with
    integer payloads bitcast in — exact over the whole int32 range — when
    mixed.

    `param_placement`:
      * "auto" (default): per-stage packed placement when the params are
        concrete values (or `packed=` is given); replicated when they are
        tracers (caller jits/grads with params as arguments — packing is
        impossible mid-trace, and output is placement-independent).
      * "stage": stage params are packed into one (S, W) array sharded
        over the stage axis (pack_stage_params), so each device's HBM
        holds only its own stage's weights (padded to the widest stage) —
        the per-stage-HBM north star, now for heterogeneous models too.
        Long-lived callers (the engine) should pack ONCE at load time and
        pass `packed=(packed_array, metas)`; otherwise the pack runs
        inside this call. Raises if the params are tracers and no
        `packed=` was supplied (an explicit placement request must not be
        silently downgraded).
      * "replicated": all weights on all devices, no pack/unpack work in
        the branches — right for models whose params are smaller than
        their activations.

    Returns the final stage's output with microbatches re-merged.
    """
    num_stages = len(stage_fns)
    if mesh.shape[axis_name] != num_stages:
        raise ValueError(
            f"mesh axis '{axis_name}' has size {mesh.shape[axis_name]}, "
            f"need {num_stages} (one device per stage)"
        )
    if param_placement not in ("auto", "stage", "replicated"):
        raise ValueError(
            f"param_placement must be auto|stage|replicated, got {param_placement!r}"
        )

    x_mb = split_microbatches(x, num_microbatches)
    mb = x_mb.shape[1]
    shapes = _stage_shapes(
        stage_fns, stage_params, jax.ShapeDtypeStruct(x_mb.shape[1:], x_mb.dtype)
    )
    # Hop buffer carries stage INPUTS (shapes[0..S-1]); the final output
    # (often vocab-wide logits) gets its own width and never rides the ring.
    width_hop = max(_flat_size(s.shape[1:]) for s in shapes[:-1])
    width_out = _flat_size(shapes[-1].shape[1:])
    out_shape, out_dtype = shapes[-1].shape, shapes[-1].dtype
    buf_dtype = _buffer_dtype([s.dtype for s in shapes[:-1]])

    inputs_buf = _pad_flat(
        x_mb.reshape(num_microbatches * mb, -1), width_hop, buf_dtype
    ).reshape(num_microbatches, mb, width_hop)

    sharded = param_placement in ("auto", "stage")
    if sharded and packed is None:
        if any(isinstance(l, jax.core.Tracer) for l in jax.tree.leaves(stage_params)):
            if param_placement == "stage":
                raise ValueError(
                    "param_placement='stage' with traced stage_params: "
                    "packing is impossible mid-trace. Pack once outside the "
                    "jit and pass packed=(array, metas) (what the engine "
                    "does), or use param_placement='replicated'/'auto'."
                )
            sharded = False  # auto: replicated semantics, identical output
    if sharded:
        if packed is None:
            packed_arr, metas = pack_stage_params(stage_params)
            packed_arr = jax.device_put(
                packed_arr, NamedSharding(mesh, P(axis_name))
            )
        else:
            packed_arr, metas = packed

    def make_branch(i):
        fn, in_s, in_dt = stage_fns[i], shapes[i].shape, shapes[i].dtype
        is_last = i == num_stages - 1

        def branch(params_vec, buf):
            # trace-time scope: device timelines (obs/profile.py) name
            # each pipeline stage's ops instead of one fused switch blob
            with jax.named_scope(f"pipeline.stage{i}"):
                sp = _unpack_stage(params_vec, metas[i]) if sharded else stage_params[i]
                xin = _unpad(buf, (mb, *in_s[1:]) if len(in_s) > 0 else (mb,), in_dt, buf_dtype)
                y = fn(sp, xin)
                if is_last:
                    return (jnp.zeros((mb, width_hop), buf_dtype),
                            _pad_flat(y, width_out, out_dtype))
                return _pad_flat(y, width_hop, buf_dtype), jnp.zeros((mb, width_out), out_dtype)

        return branch

    branches = [make_branch(i) for i in range(num_stages)]

    def per_device(params_local, inputs):
        d = lax.axis_index(axis_name)
        vec = params_local[0] if sharded else params_local

        def stage_step(buf):
            return lax.switch(d, branches, vec, buf)

        return _gpipe_loop(
            stage_step, inputs, num_stages, num_microbatches, mb,
            width_hop, width_out, axis_name, out_dtype=out_dtype,
        )

    result = jax.shard_map(
        per_device, mesh=mesh,
        in_specs=(P(axis_name) if sharded else P(), P()),
        out_specs=P(), check_vma=False,
    )(packed_arr if sharded else jnp.zeros(()), inputs_buf)

    y = _unpad(
        result.reshape(num_microbatches * mb, width_out),
        (num_microbatches * mb, *out_shape[1:]),
        out_dtype, out_dtype,
    )
    return y


def spmd_pipeline_train_1f1b(
    block_fn: Callable,
    embed_fn: Callable,
    head_loss_fn: Callable,
    stacked_params,
    aux_params,
    ids_mb,
    tgt_mb,
    *,
    mesh: Mesh,
    axis_name: str = STAGE_AXIS,
):
    """Fused 1F1B pipeline-parallel loss+grad (one fwd + one bwd per
    microbatch, interleaved).

    GPipe + `jax.grad` (make_pipeline_train_step) keeps every microbatch's
    stage activations alive between the forward and backward sweeps — peak
    live activations grow O(M). 1F1B starts each microbatch's backward as
    soon as the last stage finishes its forward, so a stage frees its
    stashed activation after at most one ring traversal: the stash here is
    a static ring of K = min(M, 2S-1) slots per device, independent of M.

    Schedule (step t, device d, S stages, M microbatches):
      forward of microbatch m runs at t = m + d;
      backward of microbatch m runs at t = 2(S-1) - d + m + 1
    so the last stage's backward trails its forward by one step, gradients
    ride a reverse ppermute ring one hop per step, and the whole loop is
    M + 2S - 1 lockstep scan iterations.

    Memory-for-compute trade vs GPipe, made explicit: embed is folded into
    stage 0 and head+loss into the last stage (nothing M-sized outlives
    the loop — embed grads come from re-linearizing embed_fn at stage 0's
    backward, head grads from the last stage's), but SPMD lockstep means
    every device evaluates both the mid-stage and the last-stage vjp forms
    each step and selects — the head+loss vjp runs S times oftener than
    mathematically needed. Right when activations dominate (long sequence,
    many microbatches, big models); wrong when the head dominates (tiny
    model, huge vocab, short sequences).

    Args: `stacked_params` (S, per_stage, ...) sharded P(stage); `aux_params`
    replicated (embed + head weights); `ids_mb`/`tgt_mb` (M, mb, T) int.
    `embed_fn(aux, ids) -> x`; `block_fn(local, x) -> y` shape-preserving;
    `head_loss_fn(aux, h, tgt) -> scalar` (mean over the microbatch's
    tokens). Returns (loss, d_stacked, d_aux) — loss/grads averaged over
    microbatches; d_stacked sharded P(stage) like its params.
    """
    num_stages = mesh.shape[axis_name]
    m_count = ids_mb.shape[0]
    if m_count < 1:
        raise ValueError("need at least one microbatch")
    k_slots = min(m_count, 2 * num_stages - 1)
    steps = m_count + 2 * num_stages - 1
    fwd_perm = [(i, i + 1) for i in range(num_stages - 1)]
    bwd_perm = [(i, i - 1) for i in range(1, num_stages)]

    param_specs = jax.tree.map(lambda _: P(axis_name), stacked_params)
    stacked_params = jax.device_put(
        stacked_params, NamedSharding(mesh, P(axis_name))
    )
    x_shape = jax.eval_shape(embed_fn, aux_params, ids_mb[0])

    def per_device(params, aux, ids, tgt):
        local = jax.tree.map(lambda p: p[0], params)
        d = lax.axis_index(axis_name)
        is_first = d == 0
        is_last = d == num_stages - 1

        stash = jnp.zeros((k_slots, *x_shape.shape), x_shape.dtype)
        g_stacked = jax.tree.map(jnp.zeros_like, local)
        g_aux = jax.tree.map(jnp.zeros_like, aux)
        loss_acc = jnp.zeros((), jnp.float32)
        fwd_buf = jnp.zeros(x_shape.shape, x_shape.dtype)
        bwd_buf = jnp.zeros(x_shape.shape, x_shape.dtype)

        def step(carry, t):
            stash, g_stacked, g_aux, loss_acc, fwd_buf, bwd_buf = carry

            # ---- backward stash READ first: with K = 2S-1 slots, stage 0's
            # forward write of microbatch m+K lands in the same slot, same
            # step, as its backward read of microbatch m — the read must
            # see the old value (mb m's stash is dead right after) ----
            m_b = t - (2 * (num_stages - 1) - d + 1)
            active_b = jnp.logical_and(m_b >= 0, m_b < m_count)
            mi_b = jnp.clip(m_b, 0, m_count - 1)
            x_st = lax.dynamic_index_in_dim(stash, mi_b % k_slots, 0, False)
            ids_b = lax.dynamic_index_in_dim(ids, mi_b, 0, False)
            tgt_b = lax.dynamic_index_in_dim(tgt, mi_b, 0, False)

            # ---- forward wave: microbatch m_f = t - d ----
            m_f = t - d
            active_f = jnp.logical_and(m_f >= 0, m_f < m_count)
            mi_f = jnp.clip(m_f, 0, m_count - 1)
            x0 = embed_fn(aux, lax.dynamic_index_in_dim(ids, mi_f, 0, False))
            x_in = jnp.where(is_first, x0.astype(fwd_buf.dtype), fwd_buf)
            slot_f = mi_f % k_slots
            stash = jnp.where(
                active_f,
                lax.dynamic_update_index_in_dim(stash, x_in, slot_f, 0),
                stash,
            )
            y = block_fn(local, x_in)
            fwd_next = lax.ppermute(y.astype(fwd_buf.dtype), axis_name, fwd_perm)

            # ---- backward wave: microbatch m_b (read above) ----

            # last stage: d(loss_mb)/d(local, aux, x) seeded by the loss
            lval, vjp_last = jax.vjp(
                lambda lp, ax, xx: head_loss_fn(ax, block_fn(lp, xx), tgt_b),
                local, aux, x_st,
            )
            dp_l, daux_l, dx_l = vjp_last(jnp.ones((), lval.dtype))
            # mid/first stage: d(block)/d(local, x) seeded by the grad hop
            _, vjp_mid = jax.vjp(lambda lp, xx: block_fn(lp, xx), local, x_st)
            dp_m, dx_m = vjp_mid(bwd_buf.astype(x_shape.dtype))

            dp = jax.tree.map(lambda a, b: jnp.where(is_last, a, b), dp_l, dp_m)
            dx = jnp.where(is_last, dx_l, dx_m)
            # stage 0 additionally backprops its dx through embed
            _, vjp_emb = jax.vjp(lambda ax: embed_fn(ax, ids_b), aux)
            (daux_e,) = vjp_emb(dx.astype(x_shape.dtype))

            g_stacked = jax.tree.map(
                lambda g, u: g + jnp.where(active_b, u, jnp.zeros_like(u)),
                g_stacked, dp,
            )
            g_aux = jax.tree.map(
                lambda g, ul, ue: g
                + jnp.where(jnp.logical_and(active_b, is_last), ul, jnp.zeros_like(ul))
                + jnp.where(jnp.logical_and(active_b, is_first), ue, jnp.zeros_like(ue)),
                g_aux, daux_l, daux_e,
            )
            loss_acc = loss_acc + jnp.where(
                jnp.logical_and(active_b, is_last), lval.astype(jnp.float32), 0.0
            )
            bwd_next = lax.ppermute(dx.astype(bwd_buf.dtype), axis_name, bwd_perm)

            return (stash, g_stacked, g_aux, loss_acc, fwd_next, bwd_next), None

        (_, g_stacked, g_aux, loss_acc, _, _), _ = lax.scan(
            step,
            (stash, g_stacked, g_aux, loss_acc, fwd_buf, bwd_buf),
            jnp.arange(steps),
        )
        inv_m = 1.0 / m_count
        # aux grads and loss live on single stages; psum replicates them.
        # stacked grads stay per-stage (sharded like their params).
        g_aux = jax.tree.map(lambda g: lax.psum(g * inv_m, axis_name), g_aux)
        loss = lax.psum(loss_acc * inv_m, axis_name)
        g_stacked = jax.tree.map(lambda g: (g * inv_m)[None], g_stacked)
        return loss, g_stacked, g_aux

    return jax.shard_map(
        per_device,
        mesh=mesh,
        in_specs=(param_specs, P(), P(), P()),
        out_specs=(P(), param_specs, P()),
        check_vma=False,
    )(stacked_params, aux_params, ids_mb, tgt_mb)


def interleaved_schedule_steps(num_stages: int, virtual_stages: int,
                               num_microbatches: int) -> int:
    """Sub-step count of the interleaved schedule: V*M + S - 1. Each
    sub-step costs 1/V of a device's layers, so relative to GPipe's
    V*(M + S - 1) sub-step-equivalents the bubble shrinks from
    (S-1)/(M+S-1) to (S-1)/(VM+S-1)."""
    return virtual_stages * num_microbatches + num_stages - 1


def spmd_pipeline_interleaved(
    block_fn: Callable,
    stacked_params,
    x,
    *,
    mesh: Mesh,
    num_microbatches: int,
    virtual_stages: int,
    axis_name: str = STAGE_AXIS,
):
    """Interleaved (virtual-stage) pipeline over stacked homogeneous chunks
    — the Megatron-style schedule that cuts the pipeline bubble.

    Layer-chunk j of V*S chunks lives on device j % S, so each device owns
    V non-adjacent chunks and a microbatch makes V circuits of the ring.
    Sub-step t on device d serves (chunk c, microbatch m) by the standard
    interleaved order (groups of S microbatches sweep all V chunks before
    the next group enters):

        k = t - d;  g = k // (V*S);  c = (k % (V*S)) // S
        m = g*S + k % S

    Every consecutive global sub-stage (c*S + d -> c*S + d + 1) is one
    wrapping ppermute hop one sub-step later, so the whole schedule is one
    lockstep `lax.scan` of V*M + S - 1 sub-steps, each applying 1/V of a
    device's layers — against GPipe's (M + S - 1) full-stage steps that's
    the bubble dropping from (S-1)/(M+S-1) to (S-1)/(VM+S-1)
    (interleaved_schedule_steps pins the arithmetic; the wrap hops are the
    price, V-1 extra ring circuits of ICI traffic per microbatch).

    `stacked_params` carries a leading (V*S,) chunk axis in LAYER order
    (chunk j = layers [j*Lc, (j+1)*Lc)); `block_fn(chunk_params, x) -> y`
    shape-preserving. `num_microbatches` must divide by the stage count
    (the interleaved ordering is defined on full groups). virtual_stages=1
    degrades to exactly the GPipe dataflow (wrap hops never observed).

    Training composes via autodiff like the stacked GPipe path: reverse-AD
    re-runs the scan backwards with reversed ppermutes, so
    train.make_pipeline_train_step(schedule="interleaved") gets the same
    loss/grads as gpipe/1f1b (parity-tested) with the shorter schedule.
    """
    num_stages = mesh.shape[axis_name]
    v = virtual_stages
    if v < 1:
        raise ValueError(f"virtual_stages must be >= 1, got {v}")
    leading = {p.shape[0] for p in jax.tree.leaves(stacked_params)}
    if leading != {v * num_stages}:
        raise ValueError(
            f"stacked_params leading axis {leading} != virtual_stages * "
            f"num_stages = {v * num_stages}"
        )
    if num_microbatches % num_stages:
        raise ValueError(
            f"num_microbatches {num_microbatches} must divide by the stage "
            f"count {num_stages} for the interleaved ordering"
        )
    m_count = num_microbatches
    x_mb = split_microbatches(x, m_count)
    mb = x_mb.shape[1]

    # chunk-major -> (S, V) so P(stage) gives device d chunks {c*S + d}
    def reorder(p):
        return p.reshape(v, num_stages, *p.shape[1:]).swapaxes(0, 1)

    params_sv = jax.tree.map(reorder, stacked_params)
    param_specs = jax.tree.map(lambda _: P(axis_name), params_sv)
    params_sv = jax.device_put(params_sv, NamedSharding(mesh, P(axis_name)))

    trail = x_mb.shape[2:]
    buf_dtype = x_mb.dtype
    flat = x_mb.reshape(m_count, mb, -1)
    width = flat.shape[-1]
    steps = interleaved_schedule_steps(num_stages, v, m_count)
    perm = [(i, (i + 1) % num_stages) for i in range(num_stages)]  # wrapping

    def per_device(params, inputs):
        local = jax.tree.map(lambda p: p[0], params)  # (V, Lc, ...)
        d = lax.axis_index(axis_name)
        is_last = d == num_stages - 1
        out_buf = jnp.zeros((m_count + 1, mb, width), buf_dtype)  # slot M = scratch
        buf = jnp.zeros((mb, width), buf_dtype)

        def step(carry, t):
            buf, out = carry
            k = t - d
            valid = jnp.logical_and(k >= 0, k < v * m_count)
            kc = jnp.clip(k, 0, v * m_count - 1)
            g = kc // (v * num_stages)
            j = kc % (v * num_stages)
            c = j // num_stages
            m = g * num_stages + j % num_stages

            fresh = lax.dynamic_index_in_dim(inputs, m, 0, keepdims=False)
            start = jnp.logical_and(d == 0, c == 0)
            xin = jnp.where(start, fresh, buf)
            chunk = jax.tree.map(
                lambda p: lax.dynamic_index_in_dim(p, c, 0, keepdims=False),
                local,
            )
            y = block_fn(chunk, xin.reshape(mb, *trail)) \
                .reshape(mb, -1).astype(buf_dtype)

            done = jnp.logical_and(
                valid, jnp.logical_and(is_last, c == v - 1))
            widx = jnp.where(done, m, m_count)
            out = lax.dynamic_update_index_in_dim(out, y, widx, 0)
            buf = lax.ppermute(y, axis_name, perm)
            return (buf, out), None

        (_, out_buf), _ = lax.scan(step, (buf, out_buf), jnp.arange(steps))
        out = out_buf[:m_count]
        return lax.psum(
            jnp.where(is_last, out, jnp.zeros_like(out)), axis_name)

    result = jax.shard_map(
        per_device, mesh=mesh,
        in_specs=(param_specs, P()),
        out_specs=P(), check_vma=False,
    )(params_sv, flat)

    return result.reshape(m_count * mb, *trail)


def stacked_param_placement(stacked_params, *, axis_name: str = STAGE_AXIS):
    """The declared placement contract of the stacked pipeline: every
    leaf of the (S, ...)-stacked param tree shards its leading stage
    axis — each device holds exactly its own stage's 1/S slice,
    resident in its HBM.
    Registered as the `pipeline.stacked_param_placement` sharding
    contract: the analysis gate lowers spmd_pipeline_stacked and fails
    if any leaf's compiled placement drifts from this declaration."""
    return jax.tree.map(lambda _: P(axis_name), stacked_params)


_shardcheck_contract("pipeline.stacked_param_placement")(
    stacked_param_placement)


def spmd_pipeline_stacked(
    block_fn: Callable,
    stacked_params,
    x,
    *,
    mesh: Mesh,
    num_microbatches: int = 1,
    axis_name: str = STAGE_AXIS,
    data_axis: Optional[str] = None,
    param_specs=None,
):
    """Homogeneous-stage SPMD pipeline over stacked params.

    `stacked_params` has a leading stage axis (S, ...) that lives sharded
    P('stage', ...) — each device holds only its own stage's slice,
    resident in its HBM. No
    switch, no padding: this is the fast path for transformer block stacks.
    `block_fn(params_slice, x) -> y` must map (mb, ...) -> (mb, ...) with an
    unchanged shape.

    `data_axis` composes data parallelism with the pipeline (a 2D
    {data, stage} mesh): each microbatch's BATCH dim shards over the data
    axis, so every data column runs the same pipeline on its batch slice —
    stage params replicate across data columns (their spec doesn't mention
    the axis), ppermute hops stay within a column, and under `jax.grad`
    the shard_map transpose psums the param cotangents over data columns
    automatically — dp×pp with no extra code at the call site.

    `param_specs` composes TENSOR parallelism with the pipeline (TP x PP,
    the Megatron 3D recipe with `data_axis`): a PartitionSpec pytree for
    `stacked_params` whose leading dim is the stage axis and whose trailing
    dims may shard over a `model` axis (e.g. train.gpt_tp_pp_specs). The
    supplied `block_fn` must then be TP-aware — compute on its local weight
    shard and combine partial sums over the model axis itself
    (gpt.make_tp_block_fn). Activations stay replicated over the model
    axis: hops ppermute within each model column, and the ring pays one
    activation per hop regardless of tp. Default None keeps the 1D
    P(stage) placement."""
    num_stages = mesh.shape[axis_name]
    x_mb = split_microbatches(x, num_microbatches)
    mb = x_mb.shape[1]
    d_size = mesh.shape[data_axis] if data_axis else 1
    if mb % d_size:
        raise ValueError(
            f"microbatch size {mb} not divisible by data axis size {d_size}"
        )
    mb_local = mb // d_size

    if param_specs is None:
        param_specs = stacked_param_placement(stacked_params,
                                              axis_name=axis_name)
    # map over the PARAMS tree: flatten_up_to stops at its array leaves, so
    # the P specs (themselves tuples) come through whole
    stacked_params = jax.tree.map(
        lambda p, s: jax.device_put(p, NamedSharding(mesh, s)),
        stacked_params, param_specs,
    )

    # flatten trailing dims into the buffer width for the generic loop; the
    # ring carries the activation's OWN dtype (bf16 pipelines pay bf16 ICI
    # bytes per ppermute hop, not 2x in f32)
    trail = x_mb.shape[2:]
    buf_dtype = x_mb.dtype
    flat = x_mb.reshape(num_microbatches, mb, -1)

    def per_device_wrapped(params, inputs):
        local = jax.tree.map(lambda p: p[0], params)

        def stage_step(buf):
            xin = buf.reshape(mb_local, *trail)
            y = block_fn(local, xin).reshape(mb_local, -1).astype(buf_dtype)
            return y, y  # uniform shapes: hop and output coincide

        return _gpipe_loop(
            stage_step, inputs, num_stages, num_microbatches, mb_local,
            flat.shape[-1], flat.shape[-1], axis_name, out_dtype=buf_dtype,
        )

    result = jax.shard_map(
        per_device_wrapped,
        mesh=mesh,
        in_specs=(param_specs, P(None, data_axis)),
        out_specs=P(None, data_axis),
        check_vma=False,
    )(stacked_params, flat)

    return result.reshape(num_microbatches * mb, *trail)
