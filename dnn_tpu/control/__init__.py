"""dnn_tpu.control — the fleet front door (ROADMAP item 1).

Everything before this package serves ONE replica per model: the
hardened single-host stack (continuous batching, paged/quantized KV,
negotiated transport, chaos-supervised restart, SLO gauges) ends at a
single `node --serve_lm` process. This package is the first
control-plane subsystem — the stage that composes those primitives
into a *fleet*:

  * `replicaset.py` — replica lifecycle: spawn N `node --serve_lm`
    children through the existing `chaos.supervisor.Supervisor`
    (health/drain/respawn), each replica a declared state machine
    (idle/warming/serving/draining/dead — model-checked in
    `analysis/protocol.REPLICA` like breaker/drain/supervisor), plus
    signal scraping through the existing `obs.fleet.FleetCollector`.
  * `policy.py` — pluggable routing policy, the way `attn_kernel` and
    `transport` already are: `round_robin | least_queue | slo_burn`,
    fed by scrape-time signals the replicas already export (queue
    depth, KV-slot utilization, TTFT/ITL percentiles, error-budget
    burn rate), plus the `dnn_tpu_wanted_replicas` autoscaling signal.
  * `router.py` — a stdlib-asyncio gRPC front door speaking the
    EXISTING Generate/GenerateStream wire format, so `NodeClient`
    points at it unchanged: SLO-driven admission (sheds via the
    breaker/UNAVAILABLE ladder), per-hop `dl=` deadline re-tagging,
    dedup-key-aware session affinity, retry-on-sibling for draining
    replicas, and disaggregated prefill/decode routing.
  * `handoff.py` — the prefill->decode KV handoff wire format: a
    prefill replica computes the prompt's row cache
    (`ContinuousBatcher.export_prefill`), the payload rides the
    negotiated transport's grpc rung, and the decode replica adopts it
    (`submit(prefilled=...)`) — zero prompt FLOPs on the decode side.

CLI: `python -m dnn_tpu.control` spawns a whole fleet (router + N
supervised replicas); `node --route` runs the router alone against
explicit targets. Not measured on the chip (PERF.md section 7).
"""

from dnn_tpu.control.policy import (  # noqa: F401
    POLICIES,
    ReplicaView,
    get_policy,
    shed_reason,
    wanted_replicas,
)

__all__ = ["POLICIES", "get_policy", "ReplicaView", "shed_reason",
           "wanted_replicas"]
