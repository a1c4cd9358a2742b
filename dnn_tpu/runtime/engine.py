"""The inference engine: config -> mesh -> staged model -> results.

Rebuilds the reference's per-node runtime (node.py:210-364) as a single
SPMD controller: where the reference starts N OS processes that each parse
the config, load the full checkpoint, keep their slice, and relay tensors
over gRPC (SURVEY §3.1-3.3), this engine parses the same config once, maps
`part_index` onto the mesh "stage" axis, loads + slices the checkpoint per
stage, and runs the whole pipeline as compiled programs with ppermute hops.

Everything is compiled once: per-stage jits and the pipeline callable are
built in __init__ and reused (jit itself handles new input shapes), unlike
the reference which pays torch dispatch per request.

Roles:
  role="full"  — this process drives the whole pipeline (default).
  role="stage" — this process serves exactly one stage behind the gRPC
                 edge (the reference's per-node deployment); no mesh or
                 full-pipeline runtime is built, so an 8-stage config can
                 be served from 1-device hosts.

Runtime selection for role="full" (config key `runtime`, SURVEY §7.4):
  "relay" — device-per-stage sequential relay (reference semantics;
            heterogeneous-friendly; also the 1-device fallback)
  "spmd"  — shard_map + ppermute GPipe pipeline (the TPU-native fast
            path; GPT-family block stacks additionally get per-stage
            HBM-resident weights via the stacked pipeline)
  "auto"  — spmd when the devices exist, else relay
"""

from __future__ import annotations

import logging
from typing import Any, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from dnn_tpu.config import TopologyConfig
from dnn_tpu.parallel.mesh import STAGE_AXIS, mesh_from_config
from dnn_tpu.parallel.pipeline import (
    RelayExecutor,
    spmd_pipeline,
    spmd_pipeline_stacked,
)
from dnn_tpu.registry import get_model

log = logging.getLogger("dnn_tpu.engine")

_DTYPES = {"float32": None, "bfloat16": jnp.bfloat16}


def _pick_devices(device_type: Optional[str]):
    """Consume config.device_type. Absent (None) means JAX's default
    backend. A named platform is a requirement, not a preference: a
    config that says "tpu" on a host where JAX found none is an error —
    it never runs on the CPU under the TPU's name."""
    if device_type is None:
        return jax.devices()
    devs = [d for d in jax.devices() if d.platform == device_type]
    if devs:
        return devs
    if device_type == "cpu":
        # the CPU backend exists beside an accelerator default
        return jax.devices("cpu")
    raise RuntimeError(
        f"config asks for device_type={device_type!r} but JAX found no "
        f"such device (default backend: {jax.default_backend()})")


class PipelineEngine:
    """Load once, run many — the object behind both the CLI (`dnn_tpu.node`)
    and the gRPC edge service."""

    def __init__(
        self,
        config: TopologyConfig,
        *,
        params: Optional[Any] = None,
        devices=None,
        rng_seed: int = 0,
        role: str = "full",
        lora_path: Optional[str] = None,
    ):
        if role not in ("full", "stage", "lm"):
            raise ValueError(f"role must be full|stage|lm, got {role}")
        # runtime compile telemetry (dnn_tpu/obs): every XLA compile this
        # engine triggers — construction-time stage jits and any later
        # shape churn — lands in jax_compilations_total, the live
        # cross-check of the static recompile census (analysis PRG004)
        from dnn_tpu import obs

        obs.install_compile_telemetry()
        self.config = config
        self.role = role
        # downstream hop preference for the gRPC edge deployment
        # (role="stage" / --serve): the stage server negotiates
        # device | shm | grpc per hop at handshake (comm/transport.py);
        # serve_stage defaults to this resolved value
        self.transport = config.transport
        self.spec = get_model(config.model)
        if config.num_parts not in self.spec.supported_parts:
            raise ValueError(
                f"model '{config.model}' supports num_parts in "
                f"{self.spec.supported_parts}, config asks for {config.num_parts}"
            )
        if config.dtype not in _DTYPES:
            raise ValueError(f"dtype must be one of {sorted(_DTYPES)}, got {config.dtype}")
        self.compute_dtype = _DTYPES[config.dtype]

        # dtype plumbing: families exposing factories get real bf16 compute;
        # others warn rather than silently ignoring the config key.
        extras = self.spec.extras
        if self.compute_dtype is not None and "make_partition" not in extras:
            log.warning(
                "model '%s' has no dtype-aware factories; dtype=%s ignored",
                config.model, config.dtype,
            )
        if "make_partition" in extras:
            self.stages = list(
                extras["make_partition"](compute_dtype=self.compute_dtype)(config.num_parts)
            )
        else:
            self.stages = list(self.spec.partition(config.num_parts))

        # before any weight is loaded: a platform the config names and JAX
        # cannot find fails here, not after a model has been built elsewhere
        self.devices = list(devices) if devices is not None else _pick_devices(config.device_type)
        self.params = params if params is not None else self._load_params(rng_seed)
        if lora_path:
            # merge-once LoRA deployment: base checkpoint + adapter npz ->
            # adapted weights, then every runtime below (stage slices,
            # stacked decode, gRPC edge) serves the tuned model at zero
            # inference-time overhead (dnn_tpu/lora.py)
            from dnn_tpu import lora as _lora

            adapters, alpha = _lora.load_lora(lora_path)
            # (a tree whose entries are made on reading is made whole here)
            self.params = _lora.merge_lora(dict(self.params), adapters,
                                           alpha=alpha)
            log.info("merged LoRA adapters from %s (%d sites%s)",
                     lora_path, len(adapters),
                     f", alpha={alpha}" if alpha is not None else "")

        if role == "lm":
            # the LM daemon serves from its own held, stacked copy
            # (node._stack_and_release), which it builds an entry at a
            # time from `self.params`: no stage program, no runtime, and
            # nothing here reads a weight
            self.runtime, self.mesh = "lm", None
            self._relay = self._pipeline_fn = None
            self._stage_params, self._stage_jits = [], []
            self.param_placement = None
            log.info("engine ready: model=%s role=lm devices=%d dtype=%s",
                     config.model, len(self.devices), config.dtype)
            return
        # compiled-once per-stage programs (the unit the gRPC edge serves)
        self._stage_params = [s.slice_params(self.params) for s in self.stages]
        # resolved spmd weight placement ("stage"|"replicated"); None until
        # (unless) the generic spmd runtime is built
        self.param_placement = None
        self._stage_jits = [jax.jit(s.apply) for s in self.stages]

        # Per-part device-resident param cache for run_stage: committed to
        # device on first use (HBM-resident thereafter, the analog of each
        # node loading its slice at startup — node.py:294-317). Lazy so a
        # 1-device stage host only ever uploads the one part it serves.
        self._stage_params_on_device: dict = {}

        if role == "stage":
            self.runtime = "stage"
            self.mesh = None
            self._relay = None
            self._pipeline_fn = None
        else:
            self.runtime = self._pick_runtime()
            if self.runtime == "spmd":
                self.mesh = mesh_from_config(config, self.devices)
                self._relay = None
                self._pipeline_fn = self._build_spmd_fn()
            else:
                self.mesh = None
                self._pipeline_fn = None
                self._relay = RelayExecutor(
                    [s.apply for s in self.stages], self._stage_params, devices=self.devices
                )
        log.info(
            "engine ready: model=%s parts=%d runtime=%s devices=%d dtype=%s",
            config.model, config.num_parts, self.runtime, len(self.devices), config.dtype,
        )

    # ------------------------------------------------------------------

    def _load_params(self, rng_seed: int):
        """Checkpoint path from config (config.json:15, node.py:241,296) or
        fresh init when absent (the reference hard-exits; we degrade to
        random weights so dry runs work without a blob — its weights file
        was stripped from the mirror too, .MISSING_LARGE_BLOBS)."""
        path = self.config.model_weights
        if not path:
            log.warning("no model_weights in config; using random init")
            if self.role == "lm":
                # an entry (a layer) at a time, as the daemon reads them
                return self.spec.init_parts(jax.random.PRNGKey(rng_seed))
            return self.spec.init(jax.random.PRNGKey(rng_seed))
        from dnn_tpu.io import checkpoint as ckpt

        sd = ckpt.load_checkpoint(path)
        if ckpt.is_native_flat(sd):
            return ckpt.flat_to_params(sd)
        if self.spec.convert_state_dict is None:
            raise ValueError(
                f"checkpoint {path} is in a foreign layout and model "
                f"'{self.spec.name}' has no converter"
            )
        return self.spec.convert_state_dict(sd)

    def _pick_runtime(self) -> str:
        rt = self.config.runtime
        if jax.process_count() > 1:
            # Multi-host: every process must run one SPMD program over the
            # global mesh. The relay runtime device_puts onto explicit
            # devices, which are non-addressable from other hosts — it is
            # host-local by design.
            if rt == "relay":
                raise ValueError(
                    "runtime=relay is host-local; multi-host (distributed) "
                    "runs require runtime=spmd"
                )
            rt = "spmd"
        if rt == "auto":
            if self.config.num_parts == 1:
                return "relay"
            rt = "spmd" if len(self.devices) >= self.config.num_parts else "relay"
        if rt == "spmd" and len(self.devices) < self.config.num_parts:
            raise ValueError(
                f"runtime=spmd needs >= {self.config.num_parts} devices, "
                f"have {len(self.devices)} (use --serve / role='stage' to host "
                "a single stage on a small host)"
            )
        return rt

    # ------------------------------------------------------------------
    # compiled pipeline callables
    # ------------------------------------------------------------------

    def _effective_microbatches(self, batch: int) -> int:
        """Resolve the config's microbatch setting for a concrete batch.
        Explicit values pass through; 0 (auto) picks the largest divisor of
        the batch up to 2*num_parts — enough microbatches that the GPipe
        bubble fraction (S-1)/(M+S-1) drops to ~1/3, without a remainder
        microbatch. A batch of 1 degenerates to 1 (the reference's whole
        operating regime, node.py:147)."""
        m = self.config.microbatches
        if m != 0:
            return m
        desired = max(2 * self.config.num_parts, 1)
        for cand in range(min(desired, batch), 0, -1):
            if batch % cand == 0:
                return cand
        return 1

    def _gpt_stacked_ready(self) -> bool:
        """Dense-GPT fast path: uniform block stacks sharded one-stage-per-
        device, embed/head outside the ring. Needs equal blocks per stage.
        EXACT type match on purpose: subclassed configs (GPTMoEConfig) have
        different block params (no 'mlp'), so they take the generic
        partitioned path instead."""
        from dnn_tpu.models.gpt import GPTConfig

        cfg = self.spec.config
        return (
            type(cfg) is GPTConfig
            and cfg.n_layer % self.config.num_parts == 0
            and self.config.num_parts > 1
        )

    # Auto param-placement threshold: below this total param size the
    # per-device HBM savings of packed placement can't matter (every shipped
    # small model's weights fit everywhere many times over) while its
    # per-scan-step unpack work is paid every step. Above it, per-stage
    # HBM residency wins. Not measured on the chip.
    PLACEMENT_AUTO_BYTES = 32 * 1024 * 1024

    def _resolve_param_placement(self) -> str:
        pp = self.config.param_placement
        if pp != "auto":
            return pp
        total = sum(
            l.size * jnp.dtype(l.dtype).itemsize
            for l in jax.tree.leaves(self._stage_params)
        )
        return "stage" if total > self.PLACEMENT_AUTO_BYTES else "replicated"

    def _demote_params_to_host(self):
        """Per-stage placement only spreads the model over the devices if
        the un-partitioned copy the loader left on the default device
        dies. The relay helpers (run_stage), the single-program decoders
        and parity tests still work off the host arrays — they just
        transfer on use."""
        self.params = jax.tree.map(np.asarray, self.params)
        self._stage_params = [
            jax.tree.map(np.asarray, p) for p in self._stage_params
        ]

    def _build_spmd_fn(self):
        if self._gpt_stacked_ready():
            return self._build_gpt_stacked_fn()

        from dnn_tpu.parallel.pipeline import pack_stage_params

        stage_applies = [s.apply for s in self.stages]
        mesh = self.mesh
        self.param_placement = self._resolve_param_placement()

        if self.param_placement == "replicated":
            def run_pipeline(sp, x, microbatches):
                return spmd_pipeline(
                    stage_applies, sp, x,
                    mesh=mesh, num_microbatches=microbatches,
                    axis_name=STAGE_AXIS, param_placement="replicated",
                )

            fn = jax.jit(run_pipeline, static_argnums=2)
            # replicate the params onto the mesh once — plain host arrays as
            # args would re-transfer host->devices on every call
            sp_placed = jax.device_put(
                tuple(self._stage_params), NamedSharding(mesh, P())
            )
            return lambda x: fn(
                sp_placed, x, self._effective_microbatches(x.shape[0])
            )

        # pack ONCE at load (on the host — the full (S, W) array never
        # touches a single device's HBM): each device holds only its own
        # stage's packed weight vector (P(stage)) — the per-stage placement
        # the relay runtime gets for free from explicit devices, now on the
        # SPMD path too
        packed_arr, metas = pack_stage_params(self._stage_params)
        packed_arr = jax.device_put(
            packed_arr, NamedSharding(mesh, P(STAGE_AXIS))
        )
        self._spmd_packed = packed_arr
        self._demote_params_to_host()
        stage_shapes = [
            # .dtype/.shape read straight off the (now-host) leaves — no
            # jnp.asarray, which would round-trip the whole model through
            # the default device right after demoting it
            jax.tree.map(lambda l: jax.ShapeDtypeStruct(jnp.shape(l), l.dtype), p)
            for p in self._stage_params
        ]

        def run_pipeline(packed_in, x, microbatches):
            return spmd_pipeline(
                stage_applies, stage_shapes, x,
                mesh=mesh, num_microbatches=microbatches, axis_name=STAGE_AXIS,
                packed=(packed_in, metas),
            )

        fn = jax.jit(run_pipeline, static_argnums=2)
        return lambda x: fn(packed_arr, x, self._effective_microbatches(x.shape[0]))

    def _build_gpt_stacked_fn(self):
        from dnn_tpu.models import gpt

        from dnn_tpu.runtime.generate import prepare_pipeline_stacked

        cfg = self.spec.config
        mesh = self.mesh
        compute_dtype = self.compute_dtype

        # The stacked layout IS per-stage placement (block params sharded
        # P(stage) below); record that so the resolved placement is
        # observable on this path too. An explicit "replicated" request
        # can't apply here — the stacked runtime exists to avoid it.
        if self.config.param_placement == "replicated":
            log.warning(
                "param_placement='replicated' ignored: the stacked GPT "
                "runtime always places block weights per-stage"
            )
        self.param_placement = "stage"

        # One-time, load-side: stack blocks stage-major (S, per_stage, ...)
        # and place each stage's slice on its device (HBM-resident per-stage
        # weights, no host round trip between stages). prepare_pipeline_stacked is
        # the single owner of this layout; generation consumes the same
        # placement (self._gen_parts).
        stage_major, aux = prepare_pipeline_stacked(
            gpt.prepare_stacked(self.params, cfg), cfg, mesh
        )
        # embed/head weights run outside the ring on every device:
        # replicate them onto the mesh ONCE (left on the default device
        # they would be re-broadcast on every call)
        aux = jax.device_put(aux, NamedSharding(mesh, P()))
        self._gen_parts = (stage_major, aux)
        self._demote_params_to_host()

        def block_fn(stage_blocks, h):
            # stage_blocks: (per_stage, ...) — scan this stage's blocks
            return gpt.blocks_scan(
                stage_blocks, h, cfg=cfg, compute_dtype=compute_dtype
            )

        def run_pipeline(stacked, aux_params, ids, microbatches):
            x = gpt.embed(aux_params, ids, cfg=cfg)
            if compute_dtype is not None:
                x = x.astype(compute_dtype)
            h = spmd_pipeline_stacked(
                block_fn, stacked, x,
                mesh=mesh, num_microbatches=microbatches, axis_name=STAGE_AXIS,
            )
            return gpt.head(aux_params, h.astype(jnp.float32), cfg=cfg)

        fn = jax.jit(run_pipeline, static_argnums=3)
        return lambda ids: fn(
            stage_major, aux, ids, self._effective_microbatches(ids.shape[0])
        )

    # ------------------------------------------------------------------

    def run(self, x) -> jax.Array:
        """Full pipeline forward (all stages)."""
        if self.role == "stage":
            raise RuntimeError(
                "engine was built with role='stage' (serves one part); "
                "use run_stage, or build with role='full'"
            )
        if self.runtime == "spmd":
            return self._pipeline_fn(x)
        return self._relay(x)

    def run_stage(self, part_index: int, x) -> jax.Array:
        """One stage only — the unit of work a reference node performs per
        SendTensor (node.py:52-54); used by the gRPC edge service."""
        params = self._stage_params_on_device.get(part_index)
        if params is None:
            if self._relay is not None:
                # the relay executor already committed this stage's params to
                # its stage device — reuse, don't duplicate HBM on device 0
                params = self._relay.stage_params[part_index]
            else:
                params = jax.device_put(
                    self._stage_params[part_index], self.devices[0]
                )
            self._stage_params_on_device[part_index] = params
        return self._stage_jits[part_index](params, x)

    def predict(self, x) -> int:
        """Client-path final step: argmax over the last stage's output
        (node.py:61, 190-192). Spanned end-to-end (the np.asarray pull
        forces device completion, so the span is honest wall time)."""
        from dnn_tpu import obs

        with obs.span("engine.predict", runtime=self.runtime):
            pred = int(np.argmax(np.asarray(self.run(x))))
        m = obs.metrics()
        if m is not None:
            m.inc("engine.predicts_total")
        return pred

    # ------------------------------------------------------------------
    # autoregressive generation (GPT family)
    # ------------------------------------------------------------------

    def make_generator(self, *, max_new_tokens: int, temperature: float = 0.0,
                       top_k: Optional[int] = None,
                       top_p: Optional[float] = None,
                       attn_kernel="auto", kv_dtype=None):
        """Build `generate(ids, rng=None) -> (B, max_new_tokens)` on this
        engine's weights. On the spmd runtime with the GPT stacked layout,
        decode runs PIPELINE-PARALLEL: each stage keeps its KV-cache shard
        with its blocks and the hidden state rides the ppermute ring per
        token (runtime/generate.make_pipeline_generate) — the serving
        capability the reference's partitions stop short of (they emit one
        stateless forward's logits, gpt_model_parts.py:36-50, and cannot
        decode). Other runtimes fall back to the single-program KV-cache
        decoder; both are token-for-token identical. `attn_kernel` is the
        cache-attention routing policy for the single-program decoders
        (kvcache._KernelDispatch): the default "auto" streams
        long-context decode through the Pallas position-clamped kernel
        on TPU and stays on the einsum path everywhere else. `kv_dtype`
        picks the cache storage for the single-program decoders (None
        follows the engine's compute dtype; "int8"/"int4" quantize the
        cache with per-(position, head) scales — runtime/kvcache.py;
        the pipeline-parallel ring decoder keeps its stage shards at
        compute dtype and rejects the override rather than silently
        ignoring it)."""
        from dnn_tpu.models.gpt import GPTConfig
        from dnn_tpu.models.gpt_moe import GPTMoEConfig
        from dnn_tpu.runtime.generate import make_generate, make_pipeline_generate

        cfg = self.spec.config
        self._require_full_role()
        default_rng = jax.random.PRNGKey(0)

        def single_program(gen):
            """Shared tail for every single-program family decoder: cache
            the prepared layout once, default the rng."""
            prepared = self._prepared()
            return lambda ids, rng=None: gen(
                prepared, ids, default_rng if rng is None else rng
            )

        from dnn_tpu.models.llama import LlamaConfig

        if isinstance(cfg, GPTMoEConfig):
            # MoE family decodes through the single-program routed decoder
            # (runtime/generate_moe.py); pipeline-parallel MoE decode is not
            # built, so spmd engines fall back to the local program too.
            from dnn_tpu.runtime.generate_moe import make_generate_moe

            if kv_dtype is not None:
                raise ValueError(
                    "kv_dtype is not plumbed through the MoE decoder")
            return single_program(make_generate_moe(
                cfg, max_new_tokens=max_new_tokens, temperature=temperature,
                sample_top_k=top_k, sample_top_p=top_p,
                compute_dtype=self.compute_dtype,
            ))
        if isinstance(cfg, LlamaConfig):
            from dnn_tpu.models import llama

            return single_program(llama.make_generate(
                cfg, max_new_tokens=max_new_tokens, temperature=temperature,
                top_k=top_k, top_p=top_p, compute_dtype=self.compute_dtype,
                attn_kernel=attn_kernel, kv_dtype=kv_dtype,
            ))
        if type(cfg) is not GPTConfig:
            # exact match: the KV-cache decoder assumes dense-GPT block
            # params ('mlp'); unknown subclasses are not decodable through it
            raise ValueError(
                f"generation requires a GPT-family model; "
                f"'{self.config.model}' has config {type(cfg).__name__}"
            )
        if self.runtime == "spmd" and self._gpt_stacked_ready():
            if kv_dtype is not None:
                raise ValueError(
                    "kv_dtype applies to the single-program decoders; "
                    "pass kv_dtype on a family adapter for the "
                    "pipeline-parallel ring (generate.GPTPipelineFamily)")
            gen = make_pipeline_generate(
                cfg, self.mesh, max_new_tokens=max_new_tokens,
                temperature=temperature, top_k=top_k, top_p=top_p,
                compute_dtype=self.compute_dtype,
            )
            stage_major, aux = self._gen_parts
            return lambda ids, rng=None: gen(
                stage_major, aux, ids, default_rng if rng is None else rng
            )
        return single_program(make_generate(
            cfg, max_new_tokens=max_new_tokens, temperature=temperature,
            top_k=top_k, top_p=top_p, compute_dtype=self.compute_dtype,
            attn_kernel=attn_kernel, kv_dtype=kv_dtype,
        ))

    def _require_full_role(self):
        if self.role == "stage":
            raise RuntimeError(
                "generation needs the full pipeline; this engine was built "
                "with role='stage' (serves one part)"
            )

    def _prepared(self):
        """The stacked decode layout, built once per engine."""
        if not hasattr(self, "_prepared_single"):
            from dnn_tpu.models.gpt import prepare_stacked

            self._prepared_single = prepare_stacked(self.params,
                                                    self.spec.config)
        return self._prepared_single

    def _gen_cache(self) -> dict:
        cache = getattr(self, "_generators", None)
        if cache is None:
            cache = self._generators = {}
        return cache

    def generate(self, ids, *, max_new_tokens: int, temperature: float = 0.0,
                 top_k: Optional[int] = None, top_p: Optional[float] = None,
                 rng=None) -> jax.Array:
        """One-call generation; caches the compiled generator per
        (max_new_tokens, temperature, top_k) so repeated serving calls reuse
        the jitted program."""
        key = (max_new_tokens, temperature, top_k, top_p)
        cache = self._gen_cache()
        if key not in cache:
            cache[key] = self.make_generator(
                max_new_tokens=max_new_tokens, temperature=temperature,
                top_k=top_k, top_p=top_p,
            )
        return cache[key](jnp.asarray(ids, jnp.int32), rng)

    def generate_beam(self, ids, *, max_new_tokens: int, beam_size: int,
                      eos_id: Optional[int] = None,
                      length_penalty: float = 0.0) -> jax.Array:
        """Deterministic beam-search decode on this engine's weights
        (runtime/beam.py; dense GPT family only — the beams run as batch
        rows through the single-program KV-cache decoder). Compiled
        programs cache per parameter tuple like `generate`."""
        from dnn_tpu.models.gpt import GPTConfig
        from dnn_tpu.runtime.beam import make_beam_generate

        cfg = self.spec.config
        self._require_full_role()
        if type(cfg) is not GPTConfig:
            raise ValueError(
                f"beam search requires a dense GPT-family model; "
                f"'{self.config.model}' has config {type(cfg).__name__}")
        key = ("beam", max_new_tokens, beam_size, eos_id, length_penalty)
        cache = self._gen_cache()
        if key not in cache:
            prepared = self._prepared()
            gen = make_beam_generate(
                cfg, max_new_tokens=max_new_tokens, beam_size=beam_size,
                eos_id=eos_id, length_penalty=length_penalty,
                compute_dtype=self.compute_dtype)
            cache[key] = lambda i: gen(prepared, i)
        return cache[key](jnp.asarray(ids, jnp.int32))

    # ------------------------------------------------------------------
    # observability (SURVEY §5: the reference has none — prints only)
    # ------------------------------------------------------------------

    def benchmark(self, x, *, iters: int = 20, warmup: int = 3) -> dict:
        """Time this engine's pipeline on whatever backend it runs on:
        items/sec (images or tokens), p50/p90 end-to-end step latency, and —
        in relay mode, where hops are individually observable — p50
        inter-stage hop latency (device->device transfer, stage 0's host
        ingress excluded) and per-stage compute. Timings force device
        completion via `tracing.device_sync` (JAX returns before the
        device finishes; timing dispatch alone measures nothing)."""
        from dnn_tpu.utils import tracing
        from dnn_tpu.utils.metrics import Metrics

        if self.role == "stage":
            raise RuntimeError(
                "benchmark() needs the full pipeline; this engine was built "
                "with role='stage' (serves one part)"
            )
        m = Metrics()
        xs = np.asarray(x).shape
        # items: tokens (B*T) for integer id inputs, else examples (B)
        if np.issubdtype(np.asarray(x).dtype, np.integer) and len(xs) == 2:
            batch_items = int(xs[0] * xs[1])
        else:
            batch_items = int(xs[0])
        for _ in range(warmup):
            tracing.device_sync(self.run(x))
        # step latency: un-instrumented runs (one sync per step), so relay
        # numbers are comparable to spmd and to production behavior
        run_once = (lambda: self._relay(x)) if self.runtime == "relay" \
            else (lambda: self._pipeline_fn(x))
        for i in range(iters):
            with tracing.step_span(i, "bench_step"):
                with m.timer("step"):
                    tracing.device_sync(run_once())
        # hop/stage breakdown: separate instrumented relay runs (per-stage
        # syncs perturb the step timing, so they don't share iterations).
        # Hop latency uses the slope-based ping-pong measurement — a naive
        # per-hop device_put+sync sample is dominated by the host sync.
        if self.runtime == "relay":
            for _ in range(min(iters, 5)):
                self._relay(x, record_timings=True)
                for st_t in self._relay.last_stage_times or []:
                    m.observe("stage_compute", st_t)
            if len(self.stages) > 1:
                for hop_t in self._relay.measure_hop_latency(x):
                    m.observe("inter_stage_hop", hop_t)
        snap = m.snapshot()
        step = snap["latency"]["step"]
        result = {
            "items_per_sec": batch_items / step["p50"],
            "step_latency_p50_s": step["p50"],
            "step_latency_p90_s": step["p90"],
            "runtime": self.runtime,
            "iters": iters,
        }
        if "inter_stage_hop" in snap["latency"]:
            result["inter_stage_hop_p50_s"] = snap["latency"]["inter_stage_hop"]["p50"]
        if "stage_compute" in snap["latency"]:
            result["stage_compute_p50_s"] = snap["latency"]["stage_compute"]["p50"]
        # mirror the headline gauges into the shared obs registry so a
        # /metrics scrape of a long-lived server reflects the last
        # measured pipeline numbers too
        from dnn_tpu import obs

        m_obs = obs.metrics()
        if m_obs is not None:
            m_obs.set("engine.items_per_sec", result["items_per_sec"])
            m_obs.set("engine.step_latency_p50_seconds", step["p50"])
        return result
