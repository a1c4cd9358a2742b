"""Node CLI — drop-in replacement for the reference's entrypoint.

Same flags as /root/reference/node.py:212-216:

    python -m dnn_tpu.node --node_id node1 --config ./config.json \
        [--input_image img.png] [--serve] [--log_level INFO]

Behavior by mode:

  * Default (TPU single-controller): the whole pipeline runs on the local
    mesh — `part_index` maps to stage coordinates, hops are ppermute, and
    if `--input_image` is given the client path runs end to end and prints
    `FINAL PREDICTION (Index): N` exactly like node.py:192. The reference
    needed N machines + N terminals for this; here one process does it
    with zero gRPC hops.

  * `--serve` (distributed edge mode): behave like one reference node —
    host this node's stage behind the gRPC NodeService and relay to
    `next_node` by address. Wire-compatible with reference nodes. In this
    mode a node with part_index 0 and `--input_image` also initiates
    inference after a short delay (node.py:203-207,332-337).

  * `--serve_lm` (LM daemon): long-lived generation server on this node's
    port — SendTensor carries prompt token ids, the response carries the
    generated tokens, and all in-flight requests decode together through
    the continuous-batching pool (runtime/lm_server.py). The LM analog of
    the reference's serving-process shape (node.py:114-133).
"""

from __future__ import annotations

import argparse
import asyncio
import logging
import sys

import numpy as np

from dnn_tpu.config import TopologyConfig
from dnn_tpu.io.preprocess import load_image_or_dummy
from dnn_tpu.runtime.engine import PipelineEngine
from dnn_tpu.utils.logging import setup_logging

log = logging.getLogger("dnn_tpu.node")

# cold-start ledger feed (obs/caplens): the spawn->first-token wall is
# attributed from gauges the CHILD measures about itself — the parent
# only scrapes. Stamped in main() (process age = exec + interpreter +
# imports) and _serve_lm() (weight-load / pre-ready compile spans).
_BOOT: dict = {}


def _proc_age_s() -> float:
    """Seconds since this process exec'd (Linux /proc; 0.0 elsewhere
    — the imports bucket degrades, the ledger's coverage says so)."""
    try:
        import os

        with open("/proc/self/stat") as f:
            stat = f.read()
        # starttime is field 22; comm (field 2) may hold spaces, so
        # split after its closing paren
        start_ticks = float(stat.rsplit(")", 1)[1].split()[19])
        with open("/proc/uptime") as f:
            uptime = float(f.read().split()[0])
        return max(0.0, uptime - start_ticks / os.sysconf("SC_CLK_TCK"))
    except Exception:  # noqa: BLE001 — non-Linux / hardened /proc
        return 0.0


def _compile_total_s() -> float:
    """Current jax_compile_seconds_total (obs/compile_watch) — lets
    boot spans subtract the compile time that landed inside them."""
    from dnn_tpu import obs

    m = obs.metrics()
    if m is None:
        return 0.0
    try:
        return float(m.snapshot()["counters"].get(
            "jax_compile_seconds_total", 0.0))
    except Exception:  # noqa: BLE001 — scrape must not break boot
        return 0.0


def build_parser() -> argparse.ArgumentParser:
    p = argparse.ArgumentParser(
        prog="dnn_tpu.node",
        description="Run a pipeline node / the whole pipeline (reference-compatible CLI)",
    )
    p.add_argument("--node_id", required=True, help="Unique ID for this node (e.g. node1)")
    p.add_argument("--config", required=True, help="Path to the JSON configuration file")
    p.add_argument("--input_image", help="Input image path (part_index 0 initiates inference)")
    p.add_argument("--generate", type=int, metavar="N", default=None,
                   help="GPT families: decode N new tokens through the "
                        "pipeline (pipeline-parallel KV cache on the spmd "
                        "runtime) and print them")
    p.add_argument("--prompt_ids", default=None,
                   help="Comma-separated prompt token ids for --generate "
                        "(default: a single BOS-like token 0)")
    p.add_argument("--temperature", type=float, default=0.0,
                   help="Sampling temperature for --generate (0 = greedy)")
    p.add_argument("--top_k", type=int, default=None,
                   help="Top-k sampling cutoff for --generate")
    p.add_argument("--top_p", type=float, default=None,
                   help="Nucleus (top-p) sampling cutoff for --generate / "
                        "--serve_lm")
    p.add_argument("--min_p", type=float, default=None,
                   help="--serve_lm: drop tokens below min_p x the top "
                        "token's probability (per-request m= overrides)")
    p.add_argument("--repetition_penalty", type=float, default=None,
                   help="--serve_lm: HF-style repetition penalty over each "
                        "request's tokens (per-request r= overrides)")
    p.add_argument("--seed", type=int, default=0,
                   help="rng seed: sampling (--generate / --serve_lm), "
                        "and the random weights when the config names "
                        "no checkpoint")
    p.add_argument("--beam", type=int, default=None, metavar="K",
                   help="--generate: deterministic beam search with K beams "
                        "instead of sampling (dense GPT family; "
                        "runtime/beam.py)")
    p.add_argument("--eos_id", type=int, default=None,
                   help="--beam: end-of-sequence token id (finished beams "
                        "freeze; output pads with it)")
    p.add_argument("--length_penalty", type=float, default=0.0,
                   help="--beam: GNMT length-penalty alpha (0 = off)")
    p.add_argument("--lora", default=None, metavar="NPZ",
                   help="LoRA adapter artifact (dnn_tpu.lora.save_lora) "
                        "merged into the model weights at load — every "
                        "mode then serves the fine-tuned model")
    p.add_argument("--serve_adapter", action="append", default=None,
                   metavar="NPZ",
                   help="--serve_lm: serve this LoRA adapter PER REQUEST "
                        "alongside the base model (repeatable; requests "
                        "pick one with the a=IDX request-id option, "
                        "0-based in flag order). Unlike --lora, the base "
                        "weights stay unmerged — one pool serves every "
                        "adapter mix")
    p.add_argument("--serve", action="store_true",
                   help="Host this node's stage behind gRPC (reference-interop mode)")
    p.add_argument("--transport", choices=["auto", "grpc", "shm", "device"],
                   default=None,
                   help="--serve: inter-stage hop transport "
                        "(comm/transport.py). 'auto' (default, or the "
                        "config's `transport` key) negotiates "
                        "device -> shm -> grpc per hop at a "
                        "wire-compatible handshake — reference peers "
                        "land on grpc; 'grpc' pins the reference wire "
                        "path; explicit 'device'/'shm' FAIL LOUD when "
                        "the hop cannot prove them (same process / "
                        "same host)")
    p.add_argument("--serve_lm", action="store_true",
                   help="GPT families: run the continuous-batching LM daemon "
                        "on this node's port — SendTensor(prompt ids) answers "
                        "with generated tokens (runtime/lm_server.py)")
    p.add_argument("--role", choices=["prefill", "decode", "both"],
                   default="both",
                   help="--serve_lm: this replica's fleet role "
                        "(dnn_tpu/control): a front door routes prompt "
                        "prefill exports to 'prefill' replicas and "
                        "generation to 'decode'/'both' — the "
                        "disaggregated split. Advisory (every endpoint "
                        "still serves); advertised on /statusz and the "
                        "dnn_tpu_replica_role gauge")
    p.add_argument("--route", action="store_true",
                   help="run the FLEET FRONT DOOR on this node's port "
                        "instead of a model: route Generate/"
                        "GenerateStream across --route_targets replicas "
                        "with SLO-driven admission, session affinity "
                        "and sibling retry (dnn_tpu/control/router.py; "
                        "NodeClient — or a reference-built client — "
                        "points at it unchanged). To also SPAWN the "
                        "replicas, use `python -m dnn_tpu.control`")
    p.add_argument("--route_targets", default=None,
                   help="--route: comma-separated replica gRPC "
                        "addresses (host:port)")
    p.add_argument("--route_signals", default=None,
                   help="--route: comma-separated replica obs base "
                        "URLs (http://host:port), one per target in "
                        "order — enables signal-fed policies "
                        "(least_queue/slo_burn read queue depth, "
                        "KV-slot utilization, latency percentiles and "
                        "SLO burn from each replica's /metrics) and "
                        "HTTP health probing; omitted, health falls "
                        "back to gRPC HealthCheck and policies to the "
                        "router's own in-flight counts")
    p.add_argument("--policy",
                   choices=["round_robin", "least_queue", "slo_burn"],
                   default="least_queue",
                   help="--route: routing policy (dnn_tpu/control/"
                        "policy.py)")
    p.add_argument("--kvtier", choices=["auto", "pull", "off"],
                   default="auto",
                   help="--route: prefix-aware placement over the "
                        "fleet KV tier (dnn_tpu/kvtier) — 'auto' "
                        "routes to the replica holding a prompt's "
                        "prefix blocks (else instructs a pull), "
                        "'pull' always places by policy and migrates "
                        "the blocks, 'off' restores dedup-key "
                        "affinity only")
    p.add_argument("--slots", type=int, default=4,
                   help="--serve_lm: concurrent decode slots in the pool")
    p.add_argument("--max_len", type=int, default=None,
                   help="--serve_lm: max sequence length per slot "
                        "(default: model block_size)")
    p.add_argument("--draft_model", default=None,
                   help="--serve_lm: model-zoo name of a DRAFT model — "
                        "enables speculative continuous batching (each "
                        "step commits up to spec_k+1 tokens per slot; "
                        "runtime/serving_spec.py)")
    p.add_argument("--draft_weights", default=None,
                   help="--serve_lm: checkpoint for the draft model "
                        "(.pth/npz/safetensors; random init if omitted)")
    p.add_argument("--spec_k", type=int, default=4,
                   help="--serve_lm: draft proposals per speculative step")
    p.add_argument("--kv", choices=["paged", "dense", "auto"],
                   default="auto",
                   help="--serve_lm: KV cache layout. 'auto' (default) "
                        "serves the PAGED block pool whenever this "
                        "configuration can page — block-granular "
                        "admission by actual request length — and falls "
                        "back to the dense per-slot pool otherwise "
                        "(recorded as a kv_fallback_dense flight event); "
                        "'dense' opts out; 'paged' fails loud when "
                        "paging is impossible")
    p.add_argument("--kv_dtype", choices=["f32", "bf16", "int8", "int4"],
                   default=None,
                   help="--serve_lm: KV cache storage dtype (default: "
                        "the model's compute dtype). int8/int4 quantize "
                        "the cache with per-(position, head) scales — "
                        "4x/8x less cache bandwidth per decode step than "
                        "f32 (runtime/kvcache.Int8KV/Int4KV; int4 costs "
                        "more rounding error — see README 'Decode hot "
                        "path')")
    p.add_argument("--paged_blocks", type=int, default=0,
                   help="--serve_lm: paged KV cache — shared pool of this "
                        "many blocks (0 with --kv=paged/auto auto-sizes "
                        "to the dense pool's capacity; see "
                        "runtime/paged_kvcache.py)")
    p.add_argument("--block_len", type=int, default=16,
                   help="--serve_lm: positions per paged-cache block")
    p.add_argument("--kv_lease_ttl_s", type=float, default=30.0,
                   help="--serve_lm: KV-tier migration lease TTL "
                        "(dnn_tpu/kvtier): a staged block export an "
                        "adopter never pulls/acks is reclaimed after "
                        "this many seconds (lease_expire/lease_reclaim "
                        "flight events)")
    p.add_argument("--kv_handoff_ttl_s", type=float, default=120.0,
                   help="--serve_lm: kvput inbox TTL — a staged "
                        "prefill handoff nobody consumes is swept "
                        "after this many seconds (kvput_expired "
                        "flight event; <= 0 disables)")
    p.add_argument("--prefix_cache", type=int, default=0,
                   help="--serve_lm: prefix-cache capacity (LRU entries); "
                        "requests sharing a prompt prefix skip re-prefilling "
                        "identical chunks. 0 disables (default). Each entry "
                        "holds one transient row cache in HBM")
    p.add_argument("--decode_buckets", action="store_true",
                   help="--serve_lm: length-aware bucketed decode — the "
                        "dense slot-pool cache grows bucket-by-bucket so "
                        "decode bytes/step track the LIVE context "
                        "instead of max_len (runtime/decode_buckets.py; "
                        "dense pools only)")
    p.add_argument("--prompt_pad", type=int, default=None,
                   help="--serve_lm: prompt padding bucket and the width "
                        "of the prefill chunk (one prefill compilation). "
                        "Default: the chip's ridge — peak FLOP/s x the "
                        "compute dtype's itemsize / (2 x peak HBM bytes/s) "
                        "tokens rounded up to a power of two, 256 for "
                        "bfloat16 on a v5e (runtime/serving.ridge_pad) — "
                        "and min(64, max_len) off the TPU; replicas that "
                        "hand K/V to each other need the same one")
    p.add_argument("--weights", choices=["f32", "int8"], default="f32",
                   help="--serve_lm: served weight precision. 'int8' "
                        "quantizes the model ONCE at startup (symmetric "
                        "per-output-channel, quant.py) — ~4x fewer "
                        "weight bytes streamed per decode step; the "
                        "goodput MBU gauges price the quantized stream "
                        "exactly (utils/flops.tree_weight_bytes)")
    p.add_argument("--prefill_chunk_tokens", type=int, default=0,
                   metavar="N",
                   help="--serve_lm: interleaved chunked prefill — fold "
                        "one N-token prompt chunk of an admitting "
                        "request into each decode step (the mixed "
                        "program) instead of convoying the whole "
                        "prefill through submit. 0 (default) keeps the "
                        "convoy path. JSON-mode constraints ride the "
                        "interleave (the grammar DFA advances on "
                        "device)")
    p.add_argument("--tokenizer", default=None,
                   help="--serve_lm: text endpoint tokenizer — 'bytes' "
                        "(UTF-8 bytes as ids; any vocab >= 256) or a LOCAL "
                        "HF tokenizer directory. SendMessage then serves "
                        "prompt text -> generated text")
    p.add_argument("--process_id", type=int, default=None,
                   help="This host's process id for multi-host (config 'distributed') runs")
    p.add_argument("--metrics_port", type=int, default=None,
                   help="--serve/--serve_lm: also serve the observability "
                        "endpoint on this port over plain HTTP — GET "
                        "/metrics (Prometheus text format), /trace "
                        "(Chrome-trace JSON of recent request spans), "
                        "/debugz (flight-recorder ring), /statusz "
                        "(watchdog per-component state), /healthz, POST "
                        "/profilez?ms=N (on-demand jax.profiler capture) "
                        "(dnn_tpu/obs; 0 = ephemeral port)")
    p.add_argument("--fleet_port", type=int, default=None, metavar="PORT",
                   help="--serve/--serve_lm: ALSO run the fleet "
                        "collector in this process and serve the merged "
                        "/fleetz view on this port (dnn_tpu/obs/"
                        "fleet.py; 0 = ephemeral). Stage endpoints come "
                        "from --fleet_targets, or from the config's "
                        "node hosts + --metrics_port when omitted — the "
                        "convention where every node passes the same "
                        "--metrics_port")
    p.add_argument("--fleet_targets", default=None,
                   help="comma-separated obs endpoint base URLs "
                        "(http://host:port) for --fleet_port, one per "
                        "stage")
    p.add_argument("--fleet_interval", type=float, default=None,
                   help="--fleet_port: poll period in seconds "
                        "(default 5)")
    p.add_argument("--slo_ttft_ms", type=float, default=None,
                   help="--serve_lm: TTFT objective in ms — 99%% of "
                        "requests (see --slo_target) must see their "
                        "first token within it; exported as the "
                        "dnn_tpu_slo_burn_rate{slo=\"ttft\"} "
                        "error-budget gauge with a flight event on "
                        "breach (dnn_tpu/obs/goodput.py)")
    p.add_argument("--slo_itl_ms", type=float, default=None,
                   help="--serve_lm: inter-token latency objective in "
                        "ms (slo=\"inter_token\" burn-rate gauge)")
    p.add_argument("--slo_avail", type=float, default=None,
                   help="--serve_lm: availability objective as a "
                        "success fraction, e.g. 0.999 "
                        "(slo=\"availability\" burn-rate gauge)")
    p.add_argument("--slo_target", type=float, default=None,
                   help="--serve_lm: fraction of requests that must "
                        "meet each latency objective (default 0.99; "
                        "needs at least one --slo_* objective)")
    p.add_argument("--chaos", default=None, metavar="PLAN",
                   help="--serve/--serve_lm: install a fault-injection "
                        "plan in THIS process (dnn_tpu/chaos; a JSON "
                        "file path or inline JSON). Deterministic "
                        "seeded injections — RPC drop/delay/corrupt, "
                        "relay-frame faults, KV-pool exhaustion, "
                        "device-step faults, watchdog wedge windows — "
                        "each recorded as a chaos_inject flight event "
                        "so the induced incident reconstructs from "
                        "/debugz")
    p.add_argument("--on_wedged", choices=["503", "restart", "drain"],
                   default="503",
                   help="--serve_lm: policy when the watchdog declares "
                        "wedged (warm-up grace preserved). '503' "
                        "(default): passive — /healthz 503s until a "
                        "human acts. 'restart': exit with code 43 so a "
                        "supervisor (--supervise, or any process "
                        "manager) relaunches from the latest "
                        "checkpoint. 'drain': finish in-flight decodes "
                        "within the drain grace, hand queued work back "
                        "retriable, then exit 43. Needs --watchdog_s")
    p.add_argument("--supervise", action="store_true",
                   help="run the serving mode as a SUPERVISED CHILD "
                        "process: this process respawns it on death "
                        "with exponential backoff + crash-loop cap, "
                        "and (with --metrics_port) polls its /healthz "
                        "to catch wedged-but-alive children — the "
                        "--on_wedged policy then applies from outside "
                        "too (dnn_tpu/chaos/supervisor.py)")
    p.add_argument("--watchdog_s", type=float, default=None, metavar="S",
                   help="--serve_lm: run the hung-device watchdog with "
                        "this probe period in seconds (deadline-bounded "
                        "in-process device probe + decode heartbeat; /healthz "
                        "degrades ok|degraded|wedged and /statusz carries "
                        "detail — dnn_tpu/obs/watchdog.py). Off unless "
                        "given")
    p.add_argument("--log_level", default="INFO")
    return p


def _initiate_local(engine: PipelineEngine, image_path: str, *, announce: bool = True) -> int:
    """Single-controller client path: preprocess -> full pipeline -> argmax
    (rebuilds initiate_inference, node.py:137-200, minus the RPCs).
    `announce=False` computes without printing (multi-host: every process
    runs the same program, only process 0 speaks)."""
    x, used_dummy = load_image_or_dummy(image_path)
    if used_dummy and image_path:
        log.warning("input image unavailable; using dummy data (node.py:149-154 behavior)")
    pred = engine.predict(x)
    if announce:
        print(f"***** FINAL PREDICTION (Index): {pred} *****")
    return pred


async def _initiate_edge(engine: PipelineEngine, node_id: str, image_path: str,
                         health_deadline: float = 30.0):
    """Edge-mode initiator: run stage 0 locally, relay downstream over gRPC
    (start_inference_after_delay + initiate_inference, node.py:137-207).
    Instead of the reference's blind 2-second sleep before initiating
    (node.py:203-207), poll the next node's HealthCheck until it comes up
    (bounded by `health_deadline`) — late-starting downstream nodes are
    normal during rollout, not errors.

    The sync gRPC client calls run in a thread executor so this node's own
    server handlers stay responsive while the pipeline round-trip is in
    flight (the reference simply blocks inside one event loop, node.py:181).
    """
    from dnn_tpu.comm.client import NodeClient, pipeline_budget

    loop = asyncio.get_running_loop()
    cfg = engine.config
    me = cfg.node_by_id(node_id)
    nxt = cfg.next_node(me)
    x, used_dummy = load_image_or_dummy(image_path)
    if used_dummy:
        log.warning("input image unavailable; using dummy data")
    y = np.asarray(engine.run_stage(me.part_index, x))
    if nxt is None:
        print(f"***** FINAL PREDICTION (Index): {int(np.argmax(y))} *****")
        return
    client = NodeClient(nxt.address)
    if not await loop.run_in_executor(
        None, lambda: client.wait_healthy(deadline=health_deadline)
    ):
        log.error("next node %s not healthy after %.0fs", nxt.address, health_deadline)
        return
    status, result = await loop.run_in_executor(
        None, lambda: client.send_tensor(
            y, request_id="dnn_tpu_pipe_001",
            timeout=pipeline_budget(cfg.num_parts),
        )
    )
    log.info("pipeline status: %s", status)
    if result is not None:
        print(f"***** FINAL PREDICTION (Index): {int(np.argmax(result))} *****")
    else:
        log.error("no result tensor in response chain")


def main(argv=None) -> int:
    import time as _time

    _BOOT["imports_s"] = _proc_age_s()
    _BOOT["t_main"] = _time.monotonic()
    raw_argv = list(sys.argv[1:] if argv is None else argv)
    args = build_parser().parse_args(argv)
    setup_logging(args.log_level, node_id=args.node_id)

    if args.supervise:
        # the supervising parent stays off the device: it initializes no
        # JAX backend, so the child it spawns can own the chip
        if not (args.serve or args.serve_lm):
            log.error("--supervise applies to the serving modes "
                      "(--serve / --serve_lm)")
            return 1
        return _supervise(args, raw_argv)

    try:
        config = TopologyConfig.from_json(args.config)
    except FileNotFoundError:
        log.error("Config file not found at '%s'", args.config)
        return 1
    except (ValueError, KeyError) as e:
        log.error("Invalid config '%s': %s", args.config, e)
        return 1

    try:
        me = config.node_by_id(args.node_id)
    except KeyError as e:
        log.error("%s", e)
        return 1

    if args.role != "both" and not args.serve_lm:
        log.error("--role applies to --serve_lm (the replica's fleet "
                  "role; the router's own role is implicit)")
        return 1
    if (args.route_targets or args.route_signals) and not args.route:
        log.error("--route_targets/--route_signals apply only with "
                  "--route")
        return 1
    if args.route:
        # front-door mode: no model, no engine — the router is pure
        # control plane over the listed replicas
        if args.serve or args.serve_lm or args.generate is not None:
            log.error("--route is a standalone mode (no --serve/"
                      "--serve_lm/--generate)")
            return 1
        if not args.route_targets:
            log.error("--route needs --route_targets (comma-separated "
                      "replica host:port addresses); to spawn replicas "
                      "too, use `python -m dnn_tpu.control`")
            return 1
        return _route(args, config, me)

    if config.device_type == "cpu":
        # the config names the platform for the whole process (default
        # device included, not just the engine's device list). By now
        # `import dnn_tpu` has imported jax, so putting JAX_PLATFORMS
        # into os.environ here would come too late; the config option
        # is read when the backend first initializes, which no code on
        # this path has done yet. (JAX_PLATFORMS set by the caller's
        # shell is honoured without any of this.)
        import jax

        jax.config.update("jax_platforms", "cpu")

    from dnn_tpu.utils.compile_cache import enable_compile_cache

    enable_compile_cache()  # before the first compile

    if config.distributed is not None:
        # multi-host: join the jax.distributed job before any backend use so
        # jax.devices() spans all hosts (dnn_tpu/parallel/multihost.py)
        from dnn_tpu.parallel.multihost import initialize_from_config

        try:
            initialize_from_config(config.distributed, process_id=args.process_id)
        except Exception as e:  # noqa: BLE001 — CLI boundary
            log.error("distributed initialization failed: %s", e)
            return 1

    # --serve hosts ONE stage (the reference's per-node role): build the
    # engine in stage role so an 8-part config serves fine from a 1-device
    # host; full role only when this process drives the whole pipeline.
    import time as _time

    _BOOT["t_engine0"] = _time.monotonic()
    _BOOT["compile_at_engine0"] = _compile_total_s()
    try:
        engine = PipelineEngine(
            config, role="stage" if args.serve else
            "lm" if args.serve_lm else "full",
            lora_path=args.lora, rng_seed=args.seed)
    except Exception as e:  # noqa: BLE001 — CLI boundary: checkpoint loads
        # raise FileNotFoundError/unpickling errors etc.; exit with a clean
        # one-liner like the reference does for every config problem
        # (node.py:296, 226-258) instead of a traceback.
        log.error("engine construction failed: %s", e)
        return 1
    _BOOT["engine_wall_s"] = _time.monotonic() - _BOOT["t_engine0"]
    _BOOT["compile_in_engine_s"] = max(
        0.0, _compile_total_s() - _BOOT["compile_at_engine0"])
    log.info(
        "node=%s part=%d/%d runtime=%s model=%s",
        me.id, me.part_index, config.num_parts - 1, engine.runtime, config.model,
    )
    if args.generate is None and (args.beam is not None
                                  or args.eos_id is not None
                                  or args.length_penalty != 0.0):
        log.error("--beam/--eos_id/--length_penalty only apply to "
                  "--generate; pass --generate N")
        return 1
    if args.generate is not None and args.beam is None and (
            args.eos_id is not None or args.length_penalty != 0.0):
        # the sampling decode path has no EOS/length-penalty support —
        # error rather than silently dropping the flags
        log.error("--eos_id/--length_penalty apply to beam search only; "
                  "pass --beam K alongside --generate")
        return 1
    if args.watchdog_s is not None and not args.serve_lm:
        log.error("--watchdog_s applies to --serve_lm only (the watchdog "
                  "monitors the LM daemon's decode loop)")
        return 1
    slo_objectives = any(v is not None for v in (
        args.slo_ttft_ms, args.slo_itl_ms, args.slo_avail))
    if (slo_objectives or args.slo_target is not None) \
            and not args.serve_lm:
        log.error("--slo_* flags apply to --serve_lm only (SLO tracking "
                  "lives on the LM daemon's request stream)")
        return 1
    if args.slo_target is not None and not slo_objectives:
        # a target without an objective would silently track nothing
        log.error("--slo_target needs at least one objective "
                  "(--slo_ttft_ms / --slo_itl_ms / --slo_avail)")
        return 1
    if args.fleet_port is not None and not (args.serve or args.serve_lm):
        log.error("--fleet_port applies to the serving modes; for a "
                  "standalone collector use `python -m dnn_tpu.obs "
                  "fleet --serve PORT`")
        return 1
    if (args.fleet_targets or args.fleet_interval is not None) \
            and args.fleet_port is None:
        # silent no-op would read as "the fleet view is live"
        log.error("--fleet_targets/--fleet_interval apply only with "
                  "--fleet_port")
        return 1
    fleet_srv = fleet_col = None
    if args.fleet_port is not None:
        # fleet collector riding this serving process (obs/fleet.py):
        # polls every stage's obs endpoint, serves the merged /fleetz
        from dnn_tpu import obs
        from dnn_tpu.obs.fleet import FleetCollector, targets_from_config

        try:
            if args.fleet_targets:
                targets = [u.strip() for u in args.fleet_targets.split(",")
                           if u.strip()]
            elif args.metrics_port:
                targets = targets_from_config(config, args.metrics_port)
            else:
                raise ValueError(
                    "--fleet_port needs --fleet_targets, or a nonzero "
                    "--metrics_port to derive them from the config")
            fleet_col = FleetCollector(
                targets,
                interval_s=args.fleet_interval
                if args.fleet_interval is not None else 5.0).start()
            fleet_srv = obs.serve_metrics(args.fleet_port,
                                          fleet=fleet_col)
            log.info("fleet collector on http://127.0.0.1:%d/fleetz "
                     "(%d stages)", fleet_srv.port,
                     len(fleet_col.targets))
        except Exception as e:  # noqa: BLE001 — CLI boundary
            log.error("fleet collector setup failed: %s", e)
            return 1
    if args.serve_adapter and not args.serve_lm:
        # per-request adapters exist only in the LM daemon's slot pool —
        # error rather than silently serving the base model
        log.error("--serve_adapter applies to --serve_lm only; to serve a "
                  "single merged fine-tune in other modes use --lora")
        return 1
    if (args.min_p is not None or args.repetition_penalty is not None) \
            and not args.serve_lm:
        log.error("--min_p/--repetition_penalty apply to --serve_lm only")
        return 1

    if args.on_wedged != "503" and not args.serve_lm:
        log.error("--on_wedged applies to --serve_lm (the watchdog's "
                  "escalation policy) or alongside --supervise")
        return 1
    if args.on_wedged != "503" and args.watchdog_s is None:
        log.error("--on_wedged %s needs --watchdog_s (the watchdog is "
                  "what declares wedged)", args.on_wedged)
        return 1
    if args.chaos is not None:
        if not (args.serve or args.serve_lm):
            log.error("--chaos applies to the serving modes (--serve / "
                      "--serve_lm)")
            return 1
        from dnn_tpu import chaos

        try:
            chaos.install(chaos.FaultPlan.from_cli(args.chaos))
            log.warning("chaos fault plan INSTALLED (%s) — injected "
                        "faults will be recorded as chaos_inject "
                        "flight events", args.chaos[:120])
        except (ValueError, OSError) as e:
            log.error("--chaos plan invalid: %s", e)
            return 1

    if args.transport is not None and not args.serve:
        # BEFORE the serve_lm dispatch: `--serve_lm --transport shm`
        # must fail loud here, not silently serve grpc (the LM daemon
        # declines negotiation — prompt payloads are bytes-tiny)
        log.error("--transport applies to --serve (the gRPC edge "
                  "deployment's inter-stage hops); the LM daemon and "
                  "single-controller runs do not negotiate hops")
        return 1

    if args.serve or args.serve_lm:
        # black box for the long-lived serving modes: an unhandled crash
        # dumps the flight-recorder ring to $DNN_TPU_OBS_DIR before the
        # process dies (dnn_tpu/obs/flight.py; idempotent with the
        # LMServer's own install)
        from dnn_tpu import obs

        if obs.enabled():
            obs.flight.install_crash_dump()

    if args.serve_lm:
        return _serve_lm(engine, args)

    if args.serve:
        from dnn_tpu.comm.service import serve_stage

        async def _run():
            tasks = [asyncio.create_task(serve_stage(
                engine, args.node_id, metrics_port=args.metrics_port,
                transport=args.transport))]
            if me.part_index == 0 and args.input_image:
                tasks.append(asyncio.create_task(
                    _initiate_edge(engine, args.node_id, args.input_image)
                ))
            await asyncio.gather(*tasks)

        try:
            asyncio.run(_run())
        except KeyboardInterrupt:
            log.info("shutting down")
        except Exception as e:  # noqa: BLE001 — CLI boundary: bind/address
            # failures exit with a clean one-liner (node.py:124-126), not a
            # traceback
            log.error("serve failed: %s", e)
            return 1
        return 0

    # single-controller mode
    if args.generate is not None:
        if config.distributed is not None and config.distributed.num_processes > 1:
            # the decode loop is a single-controller program for now; a
            # silently different behavior (image forward) would be worse
            # than an honest error
            log.error("--generate is not supported on multi-host runs yet")
            return 1
        return _generate_local(engine, args)

    if config.distributed is not None and config.distributed.num_processes > 1:
        # Multi-host SPMD: EVERY process must execute the same program — a
        # host that exits here would strand the others' collectives over
        # the global mesh. All hosts run the full pipeline on the same
        # input (the standard run-the-same-script-everywhere JAX pattern);
        # only process 0 announces the result.
        import jax

        # NOTE: every host must feed identical input (replicated SPMD
        # operand) — run this CLI with the same --input_image path on
        # shared storage, or no image at all (deterministic dummy).
        _initiate_local(engine, args.input_image,
                        announce=jax.process_index() == 0)
        return 0

    if args.input_image or me.part_index == 0:
        _initiate_local(engine, args.input_image)
    else:
        log.info("nothing to do for non-initiator node in single-controller mode "
                 "(use --serve for distributed edge mode)")
    return 0


def _route(args, config, me) -> int:
    """Front-door mode (dnn_tpu/control): serve the router on this
    node's port across already-running replicas (attach mode — nothing
    is spawned; `python -m dnn_tpu.control` owns the spawn-everything
    shape). SIGTERM drains and exits 0."""
    from dnn_tpu.control.replicaset import ReplicaHandle, ReplicaSet
    from dnn_tpu.control.router import serve_router

    if me.port is None:
        log.error("node '%s' has no IP:Port address in the config; the "
                  "router needs one to bind", args.node_id)
        return 1
    targets = [t.strip() for t in args.route_targets.split(",")
               if t.strip()]
    signals = [u.strip() for u in (args.route_signals or "").split(",")
               if u.strip()]
    if signals and len(signals) != len(targets):
        log.error("--route_signals must list one obs URL per "
                  "--route_targets entry (%d vs %d)", len(signals),
                  len(targets))
        return 1
    try:
        handles = [
            ReplicaHandle(f"r{i}", addr,
                          obs_url=signals[i] if signals else None)
            for i, addr in enumerate(targets)]
        rset = ReplicaSet(handles).start()
    except Exception as e:  # noqa: BLE001 — CLI boundary
        log.error("router setup failed: %s", e)
        return 1
    log.info("routing %d replicas (policy=%s, signals=%s)",
             len(targets), args.policy, "scraped" if signals else "local")
    try:
        return asyncio.run(serve_router(
            rset, port=me.port, metrics_port=args.metrics_port,
            policy=args.policy, kvtier=args.kvtier,
            # the directory must index at the REPLICAS' block
            # granularity or locate/pull truncate at the wrong depth
            kv_block_len=args.block_len))
    except KeyboardInterrupt:
        log.info("router shutting down")
        return 0
    except Exception as e:  # noqa: BLE001 — CLI boundary (bind etc.)
        log.error("router failed: %s", e)
        return 1
    finally:
        rset.stop()


def _supervise(args, raw_argv) -> int:
    """Supervisor-parent mode: spawn the SAME node command (minus
    --supervise) as a child and keep it alive — restart-with-backoff on
    death (including the deliberate EXIT_RESTART=43 a wedged-policy
    escalation uses), crash-loop cap, and — with --metrics_port — a
    fresh-connection /healthz poll that catches wedged-but-alive
    children and applies the --on_wedged policy from OUTSIDE the
    process (a hung process cannot run its own policy). Blocks until
    Ctrl-C; returns 1 when the child crash-loops."""
    import subprocess
    import time as _time

    from dnn_tpu.chaos.supervisor import Supervisor

    child_argv, skip = [], False
    for a in raw_argv:
        if skip:
            skip = False
            continue
        if a == "--supervise":
            continue
        if not args.serve_lm and (a == "--on_wedged"
                                  or a.startswith("--on_wedged=")):
            # the stage server has no in-process wedged policy; the
            # flag configures THIS supervisor only (both argparse
            # spellings: '--on_wedged restart' and '--on_wedged=restart')
            skip = a == "--on_wedged"
            continue
        child_argv.append(a)
    cmd = [sys.executable, "-m", "dnn_tpu.node"] + child_argv
    health = None
    if args.metrics_port:
        health = f"http://127.0.0.1:{args.metrics_port}"
    elif args.metrics_port == 0:
        log.warning("--supervise with --metrics_port 0 (ephemeral): "
                    "the supervisor cannot poll an unknown port — "
                    "wedged-but-alive children will not be detected")
    policy = {"503": "none", "restart": "restart",
              "drain": "drain"}[args.on_wedged]
    log.info("supervising: %s (health=%s, on_wedged=%s)",
             " ".join(cmd), health or "process-exit only", policy)
    sup = Supervisor(lambda: subprocess.Popen(cmd),
                     name=args.node_id, health_url=health,
                     on_wedged=policy,
                     health_interval_s=2.0, health_timeout_s=3.0,
                     ready_deadline_s=180.0)
    sup.start()
    try:
        while True:
            if sup.state == "crashloop":
                log.error("child crash-looped; giving up (see "
                          "crash_loop flight event)")
                return 1
            _time.sleep(1.0)
    except KeyboardInterrupt:
        log.info("supervisor shutting down")
        sup.stop()
        return 0


def _kv_dtype_arg(name):
    """--kv_dtype CLI spelling -> the batcher's kv_dtype spec: dtypes for
    the float widths, the codec strings for the quantized caches
    (runtime/generate.init_cache dispatches on exactly these)."""
    if name is None or name in ("int8", "int4"):
        return name
    import jax.numpy as jnp

    return {"f32": jnp.float32, "bf16": jnp.bfloat16}[name]


def _stack_and_release(params, cfg, compute_dtype=None):
    """`gpt.prepare_stacked` for the daemon, which serves from the stacked
    copy alone, trains nothing and does not run the engine again.

    Held in `compute_dtype` (None: nothing is cast) is every leaf that
    its readers cast to it before use anyway — the block matmul kernels,
    the expert stacks, a separate `lm_head` (`ops.nn.matmul_operand` is
    the rule) — so that no program converts a weight stack on every
    call; routers, norms, biases and embeddings stay as loaded. The cast
    rounds as the in-program convert did: the matmuls see the same
    operands.

    The per-layer `h_<i>` originals leave `params` ONE LAYER AT A TIME
    (`params.pop`), each layer's leaves are cast and its originals freed
    before the next layer is taken, and a stack is built a leaf at a time
    from the held leaves, which then go. Where `params` makes an entry
    when it is read (`registry.ParamParts`: the daemon's random init, a
    layer from that layer's keys), no two layers ever exist in float32:
    the boot peak is the held tree plus ONE layer as drawn — or, while
    stacking, plus the largest leaf's stack in the dtype it is held in —
    never the float32 tree (16.8 GB for one chip's share of five layers
    of hidden 5120, which no 16 GB chip holds), nor a float32 stack beside
    its cast copy (2.8 GB of a 16 GB chip for GPT-2 Large, 5 GB for three
    OLMoE layers). A plain dict of a tree that is already whole is
    handled alike, and holds the same values."""
    import time

    import jax
    import jax.numpy as jnp

    from dnn_tpu.ops.nn import held_leaf

    def free(leaf):
        if isinstance(leaf, jax.Array):
            leaf.delete()

    def held(path, leaf):
        out = held_leaf(path, leaf, compute_dtype)
        if out is not leaf:
            jax.block_until_ready(out)
            free(leaf)
        return out

    def held_layer(i):
        """Layer i, taken out of `params`, as it is held: (leaves, tree
        structure)."""
        t0 = time.monotonic()
        leaves, structure = jax.tree_util.tree_flatten_with_path(
            params.pop(f"h_{i}"))
        out = [held(path, leaf) for path, leaf in leaves]
        # the boot span of a layer's draw-and-cast: part of the wall time
        # `dnn_tpu_boot_weight_load_seconds` reports, on its own as
        # `dnn_tpu_boot_layer_init_seconds`
        _BOOT["layer_init_s"] = _BOOT.get("layer_init_s", 0.0) + (
            time.monotonic() - t0)
        return out, structure

    def stack_of(column):
        # eager `jnp.stack` first makes an expanded COPY of every member
        # (each `expand_dims` is a program of its own), so a column stands
        # in memory three times while it is stacked; a column of GBs (a
        # layer kind's expert stacks of 128 experts: 3.5 GB, and the boot's
        # high-water mark 15.8 GB of a 16 GB chip: PERF.md section 6, PR
        # 66) is stacked by ONE program instead: twice. Smaller columns
        # keep the eager form, which compiles nothing
        if sum(leaf.nbytes for leaf in column) >= 2 ** 31:
            return jax.jit(lambda *xs: jnp.stack(xs))(*column)
        return jnp.stack(column)

    def stack(layers):
        flat = [held_layer(i) for i in layers]
        stacked = []
        for column in zip(*(leaves for leaves, _ in flat)):
            # done before the leaves go, so that the next leaf's stack
            # is allocated after this one's are free
            stacked.append(jax.block_until_ready(stack_of(column)))
            for leaf in column:
                free(leaf)
        return jax.tree_util.tree_unflatten(flat[0][1], stacked)

    # layers of another kind (a dense prefix before expert layers;
    # layers whose attention differs) are stacks of their own
    # (`gpt.stack_layers`)
    from dnn_tpu.models.gpt import stack_layers

    stacks = {name: stack(layers)
              for name, layers in stack_layers(cfg).items()}
    return {**jax.tree_util.tree_map_with_path(held, dict(params)), **stacks}


def _serve_lm(engine: PipelineEngine, args) -> int:
    """Long-lived LM daemon: the reference's defining serving-process shape
    (node.py:114-133) with the continuous batcher as the workload. Every
    GPT family serves; MoE plugs its routed FFN into the same pool."""
    from dnn_tpu.models.gpt import GPTConfig, prepare_stacked
    from dnn_tpu.models.gpt_moe import GPTMoEConfig
    from dnn_tpu.models.llama import LlamaConfig, family_rows
    from dnn_tpu.obs.timeline import rpc_event_loop
    from dnn_tpu.runtime.lm_server import serve_lm

    cfg = engine.spec.config
    ffn, family = None, None
    if isinstance(cfg, GPTMoEConfig):
        from dnn_tpu.runtime.generate_moe import moe_cache_ffn

        ffn = moe_cache_ffn(cfg, compute_dtype=engine.compute_dtype)
    elif isinstance(cfg, LlamaConfig):
        family = family_rows(cfg, compute_dtype=engine.compute_dtype)
    elif type(cfg) is not GPTConfig:
        log.error("--serve_lm requires a GPT-family model; '%s' (config %s) "
                  "is not one", engine.config.model, type(cfg).__name__)
        return 1
    me = engine.config.node_by_id(args.node_id)
    if me.port is None:
        log.error("node '%s' has no IP:Port address in the config; the LM "
                  "daemon needs one to bind", args.node_id)
        return 1
    tokenizer = None
    if args.tokenizer:
        # CLI boundary: a bad --tokenizer (vocab too small, missing HF
        # dir, vocab mismatch) exits with a clean one-liner, not a
        # traceback — same contract as every other config failure here
        try:
            if args.tokenizer == "bytes":
                from dnn_tpu.io.tokenizer import ByteTokenizer

                tokenizer = ByteTokenizer(cfg.vocab_size)
            else:
                from dnn_tpu.io.tokenizer import load_hf_tokenizer

                tokenizer = load_hf_tokenizer(args.tokenizer)
            tok_vocab = getattr(tokenizer, "vocab_size", None)
            if tok_vocab is not None and tok_vocab > cfg.vocab_size:
                raise ValueError(
                    f"tokenizer vocab {tok_vocab} exceeds the model's "
                    f"vocab_size {cfg.vocab_size} — out-of-range ids would "
                    f"gather garbage embeddings silently")
        except Exception as e:  # noqa: BLE001 — CLI boundary
            log.error("tokenizer setup failed: %s", e)
            return 1
    import time as _time

    _t_prep = _time.monotonic()
    _compile_at_prep = _compile_total_s()
    # --weights int8 quantizes from the float32 values (LMServer), and
    # casts what stays float itself
    prepared = _stack_and_release(
        engine.params, cfg,
        None if args.weights == "int8" else engine.compute_dtype)
    _BOOT["prepare_wall_s"] = _time.monotonic() - _t_prep
    _BOOT["compile_in_prepare_s"] = max(
        0.0, _compile_total_s() - _compile_at_prep)
    lora_kwargs = {}
    if args.serve_adapter:
        from dnn_tpu.lora import adapters_to_stacked, load_lora

        try:
            ads, alphas = [], []
            for path in args.serve_adapter:
                ad, alpha = load_lora(path)
                if any(p.split("/")[0].startswith("h_") for p in ad):
                    # training layout -> the prepared serving layout
                    ad = adapters_to_stacked(ad, cfg.n_layer)
                ads.append(ad)
                alphas.append(alpha)
            lora_kwargs = {"lora_adapters": ads, "lora_alphas": alphas}
        except Exception as e:  # noqa: BLE001 — CLI boundary
            log.error("--serve_adapter setup failed: %s", e)
            return 1
    spec_kwargs = {}
    if args.draft_model:
        # speculative serving: load/init the draft family from the zoo
        import jax as _jax

        from dnn_tpu.registry import get_model

        try:
            d_spec = get_model(args.draft_model)
            d_cfg = d_spec.config
            if d_cfg is None or not isinstance(d_cfg, GPTConfig) or \
                    isinstance(d_cfg, GPTMoEConfig):
                raise ValueError(
                    f"--draft_model must name a dense GPT-family zoo "
                    f"entry, got '{args.draft_model}'")
            if d_cfg.vocab_size != cfg.vocab_size:
                raise ValueError(
                    f"draft vocab {d_cfg.vocab_size} != target vocab "
                    f"{cfg.vocab_size}")
            if args.draft_weights:
                from dnn_tpu.io import checkpoint as ckpt

                sd = ckpt.load_checkpoint(args.draft_weights)
                if ckpt.is_native_flat(sd):
                    d_params = ckpt.flat_to_params(sd)
                elif d_spec.convert_state_dict is not None:
                    d_params = d_spec.convert_state_dict(sd)
                else:
                    raise ValueError(
                        f"draft checkpoint {args.draft_weights} is in a "
                        f"foreign layout and '{args.draft_model}' has no "
                        "converter")
            else:
                log.warning("no --draft_weights; draft uses random init "
                            "(wiring/testing only — a random draft "
                            "accepts ~nothing)")
                d_params = d_spec.init(_jax.random.PRNGKey(0))
            spec_kwargs = {
                "draft_cfg": d_cfg,
                "draft_prepared": prepare_stacked(d_params, d_cfg),
                "spec_k": args.spec_k,
            }
        except Exception as e:  # noqa: BLE001 — CLI boundary
            log.error("draft model setup failed: %s", e)
            return 1
    slo = None
    if any(v is not None for v in (args.slo_ttft_ms, args.slo_itl_ms,
                                   args.slo_avail)):
        from dnn_tpu.obs.goodput import SLOConfig

        slo = SLOConfig(
            ttft_s=args.slo_ttft_ms / 1e3
            if args.slo_ttft_ms is not None else None,
            inter_token_s=args.slo_itl_ms / 1e3
            if args.slo_itl_ms is not None else None,
            availability=args.slo_avail,
            target=args.slo_target
            if args.slo_target is not None else 0.99)
    if args.prefill_chunk_tokens:
        log.info("interleaved admission enabled "
                 "(prefill_chunk_tokens=%d): JSON-mode constraints ride "
                 "this hot path too (the grammar DFA walks on device)",
                 args.prefill_chunk_tokens)
    # publish the boot gauges the caplens cold-start ledger scrapes:
    # each bucket is an independent child-side measurement (weight
    # spans subtract the compile seconds that landed inside them, so
    # compile stays its own bucket); the serve-bind span after this
    # point is deliberately UNattributed — coverage reports it
    from dnn_tpu import obs as _obs

    _m = _obs.metrics()
    if _m is not None:
        _imports = float(_BOOT.get("imports_s", 0.0))
        _weight = max(0.0, _BOOT.get("engine_wall_s", 0.0)
                      - _BOOT.get("compile_in_engine_s", 0.0)) \
            + max(0.0, _BOOT.get("prepare_wall_s", 0.0)
                  - _BOOT.get("compile_in_prepare_s", 0.0))
        _ready = _imports + (_time.monotonic()
                             - _BOOT.get("t_main", _time.monotonic()))
        _m.bulk(gauges={
            "dnn_tpu_boot_imports_seconds": round(_imports, 4),
            "dnn_tpu_boot_weight_load_seconds": round(_weight, 4),
            "dnn_tpu_boot_layer_init_seconds":
                round(float(_BOOT.get("layer_init_s", 0.0)), 4),
            "dnn_tpu_boot_compile_preready_seconds":
                round(_compile_total_s(), 4),
            "dnn_tpu_boot_ready_total_seconds": round(_ready, 4),
        })
    try:
        rc = asyncio.run(serve_lm(
            cfg, prepared, port=me.port, slots=args.slots, slo=slo,
            on_wedged=args.on_wedged, role=args.role,
            **spec_kwargs,
            max_len=args.max_len, prompt_pad=args.prompt_pad,
            temperature=args.temperature, top_k=args.top_k,
            top_p=args.top_p, min_p=args.min_p,
            repetition_penalty=args.repetition_penalty,
            compute_dtype=engine.compute_dtype, seed=args.seed, ffn=ffn,
            family=family, default_max_new=args.generate or 32,
            metrics_port=args.metrics_port,
            watchdog=args.watchdog_s,
            tokenizer=tokenizer, prefix_cache=args.prefix_cache,
            kv=args.kv, kv_dtype=_kv_dtype_arg(args.kv_dtype),
            paged_blocks=args.paged_blocks, block_len=args.block_len,
            decode_buckets=args.decode_buckets,
            weights=args.weights,
            kv_lease_ttl_s=args.kv_lease_ttl_s,
            kv_handoff_ttl_s=args.kv_handoff_ttl_s,
            prefill_chunk_tokens=args.prefill_chunk_tokens,
            # the daemon's clients choose options per request, so the
            # per-slot bias capability is on at this edge — except for
            # speculative serving, whose batcher rejects per-request
            # bias anyway (the buffer would be dead weight). Constraints
            # (JSON mode, j=) share the gate: on for every dense
            # configuration INCLUDING overlap/interleave (the grammar
            # DFA walks on device — serving.py), off only for
            # speculative serving, whose k-token verify the per-token
            # masks cannot gate (the batcher rejects constraint= loud).
            allow_logit_bias=not spec_kwargs,
            allow_constraints=not spec_kwargs,
            **lora_kwargs,
        ), loop_factory=rpc_event_loop)
    except KeyboardInterrupt:
        log.info("shutting down")
        return 0
    except Exception as e:  # noqa: BLE001 — CLI boundary (bind failures etc.)
        log.error("LM serve failed: %s", e)
        return 1
    # EXIT_RESTART (43) from a wedged-policy escalation rides through to
    # the supervisor; 0 is a clean (drained) shutdown
    return rc or 0


def _generate_local(engine: PipelineEngine, args) -> int:
    """CLI decode mode: prompt ids -> N generated tokens, pipeline-parallel
    when the engine runs spmd (the serving capability the reference's GPT
    partitions lack — one stateless forward is all they can do,
    gpt_model_parts.py:36-50)."""
    import jax

    if args.prompt_ids:
        try:
            ids = [int(s) for s in args.prompt_ids.split(",") if s.strip()]
        except ValueError:
            log.error("--prompt_ids must be comma-separated integers, got %r",
                      args.prompt_ids)
            return 1
        if not ids:
            log.error("--prompt_ids contained no token ids: %r", args.prompt_ids)
            return 1
    else:
        ids = [0]
    try:
        if args.beam is not None:
            # any explicit --beam takes the deterministic path (beam 1 ==
            # greedy; invalid K surfaces beam.py's own validation)
            toks = engine.generate_beam(
                np.asarray([ids], np.int32),
                max_new_tokens=args.generate,
                beam_size=args.beam,
                eos_id=args.eos_id,
                length_penalty=args.length_penalty,
            )
        else:
            toks = engine.generate(
                np.asarray([ids], np.int32),
                max_new_tokens=args.generate,
                temperature=args.temperature,
                top_k=args.top_k,
                top_p=args.top_p,
                rng=jax.random.PRNGKey(args.seed),
            )
    except (ValueError, RuntimeError) as e:
        log.error("generation failed: %s", e)
        return 1
    out = ",".join(str(int(t)) for t in np.asarray(toks)[0])
    print(f"***** GENERATED TOKENS: {out} *****")
    return 0


if __name__ == "__main__":
    sys.exit(main())
