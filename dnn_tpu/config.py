"""Topology / runtime configuration.

Parses the reference's JSON schema (/root/reference/config.json:1-18,
parsed at node.py:222-277) — `nodes[].{id,address,part_index}`,
`model_weights`, `num_parts`, `return_to_node_id` — and extends it with
TPU-native keys. Unlike the reference, which hard-exits unless
`num_parts == 2` (node.py:246-248), any num_parts supported by the model
family is accepted.

Extended keys (all optional, with reference-equivalent defaults):
  model:           model-zoo name (default "cifar_cnn", the reference's only
                   wired family — node.py:11,29-32)
  device_type:     "tpu" | "cpu" — the platform the engine must run on;
                   a named platform JAX cannot find is an error. Absent =
                   JAX's default backend
  runtime:         "spmd" (shard_map+ppermute pipeline) | "relay"
                   (device-per-stage sequential relay, the reference's
                   semantics) | "auto"
  microbatches:    GPipe-style microbatching factor for the spmd runtime;
                   0 (the default) = auto — the engine picks the largest
                   divisor of the batch up to 2*num_parts, so out of the
                   box the pipeline actually overlaps stages instead of
                   degenerating to a serial relay with a (S-1)/(S) bubble
  dtype:           compute dtype ("float32" | "bfloat16")
  mesh:            {axis_name: size} overrides for multi-axis runs
  distributed:     {coordinator_address, num_processes, process_id?} — join
                   a multi-host jax.distributed job (DCN); see
                   dnn_tpu/parallel/multihost.py
"""

from __future__ import annotations

import dataclasses
import json
from typing import Dict, List, Optional, Tuple


def _parse_distributed(d: Optional[dict]):
    if d is None:
        return None
    from dnn_tpu.parallel.multihost import DistributedConfig

    return DistributedConfig.from_dict(d)


@dataclasses.dataclass(frozen=True)
class NodeEntry:
    """One entry of config `nodes[]` (config.json:3-14). In the TPU runtime
    a "node" maps to a pipeline-stage coordinate on the mesh rather than a
    separate gRPC process; `address` is kept for the gRPC edge/serve mode."""

    id: str
    part_index: int
    address: Optional[str] = None

    @property
    def port(self) -> Optional[int]:
        # node.py:254-258 parses the port off "ip:port".
        if not self.address:
            return None
        try:
            return int(self.address.rsplit(":", 1)[-1])
        except ValueError:
            raise ValueError(
                f"Invalid address '{self.address}' for node '{self.id}'; expected IP:Port"
            ) from None


@dataclasses.dataclass(frozen=True)
class TopologyConfig:
    nodes: Tuple[NodeEntry, ...]
    num_parts: int
    model_weights: Optional[str] = None
    return_to_node_id: Optional[str] = None
    model: str = "cifar_cnn"
    device_type: Optional[str] = None  # None = JAX's default backend
    runtime: str = "auto"
    microbatches: int = 0  # 0 = auto (see engine._effective_microbatches)
    # spmd-runtime weight placement: "stage" (packed, each device holds only
    # its own stage's weights), "replicated" (all weights everywhere, no
    # pack/unpack work), or "auto" (stage iff the model is big enough for
    # per-device HBM savings to outweigh the unpack overhead — see
    # engine._resolve_param_placement)
    param_placement: str = "auto"
    dtype: str = "float32"
    mesh: Dict[str, int] = dataclasses.field(default_factory=dict)
    distributed: Optional["DistributedConfig"] = None  # multihost job spec
    # inter-stage hop transport for the gRPC edge deployment (--serve):
    # "auto" negotiates device -> shm -> grpc per hop at handshake
    # (comm/transport.py); "grpc" pins the reference wire path; explicit
    # "device"/"shm" fail loud when the hop cannot satisfy them
    transport: str = "auto"

    # ---- construction ----------------------------------------------------

    @classmethod
    def from_dict(cls, d: dict) -> "TopologyConfig":
        raw_nodes: List[dict] = d.get("nodes", [])
        nodes = tuple(
            NodeEntry(id=n["id"], part_index=int(n["part_index"]), address=n.get("address"))
            for n in raw_nodes
        )
        num_parts = d.get("num_parts")
        if num_parts is None:
            num_parts = len(nodes) if nodes else 1
        cfg = cls(
            nodes=nodes,
            num_parts=int(num_parts),
            model_weights=d.get("model_weights"),
            return_to_node_id=d.get("return_to_node_id"),
            model=d.get("model", "cifar_cnn"),
            device_type=d.get("device_type"),
            runtime=d.get("runtime", "auto"),
            microbatches=int(d.get("microbatches", 0)),
            param_placement=d.get("param_placement", "auto"),
            dtype=d.get("dtype", "float32"),
            mesh=dict(d.get("mesh", {})),
            distributed=_parse_distributed(d.get("distributed")),
            transport=d.get("transport", "auto"),
        )
        cfg.validate()
        return cfg

    @classmethod
    def from_json(cls, path: str) -> "TopologyConfig":
        with open(path) as f:
            return cls.from_dict(json.load(f))

    def validate(self):
        if self.num_parts < 1:
            raise ValueError(f"num_parts must be >= 1, got {self.num_parts}")
        if self.device_type not in (None, "tpu", "cpu"):
            raise ValueError(
                f"device_type must be 'tpu' or 'cpu', got {self.device_type!r}")
        if self.nodes:
            part_indices = sorted(n.part_index for n in self.nodes)
            if part_indices != list(range(self.num_parts)):
                raise ValueError(
                    "nodes[].part_index must cover exactly 0..num_parts-1; got "
                    f"{part_indices} for num_parts={self.num_parts}"
                )
            ids = [n.id for n in self.nodes]
            if len(set(ids)) != len(ids):
                raise ValueError(f"duplicate node ids in config: {ids}")
        if self.return_to_node_id and self.nodes:
            if all(n.id != self.return_to_node_id for n in self.nodes):
                raise ValueError(
                    f"return_to_node_id '{self.return_to_node_id}' not among node ids"
                )
        if self.runtime not in ("auto", "spmd", "relay"):
            raise ValueError(f"runtime must be auto|spmd|relay, got '{self.runtime}'")
        if self.microbatches < 0:
            raise ValueError("microbatches must be >= 0 (0 = auto)")
        if self.param_placement not in ("auto", "stage", "replicated"):
            raise ValueError(
                "param_placement must be auto|stage|replicated, got "
                f"'{self.param_placement}'"
            )
        if self.transport not in ("auto", "grpc", "shm", "device"):
            raise ValueError(
                "transport must be auto|grpc|shm|device, got "
                f"'{self.transport}'"
            )

    # ---- lookups (reference: node.py:234-277) ----------------------------

    def node_by_id(self, node_id: str) -> NodeEntry:
        for n in self.nodes:
            if n.id == node_id:
                return n
        raise KeyError(f"Node ID '{node_id}' not found in config")

    def node_by_part(self, part_index: int) -> NodeEntry:
        for n in self.nodes:
            if n.part_index == part_index:
                return n
        raise KeyError(f"No node with part_index {part_index} in config")

    def next_node(self, node: NodeEntry) -> Optional[NodeEntry]:
        """Next-hop resolution (node.py:262-271): the node owning
        part_index+1, or None for the last stage."""
        if node.part_index == self.num_parts - 1:
            return None
        return self.node_by_part(node.part_index + 1)

    def return_node(self) -> Optional[NodeEntry]:
        """The reference resolves `return_to_node_id` but never dials it
        (dead code, node.py:272-277 / SURVEY §3.3); here it names the stage
        coordinate that receives the final result ring-shifted back."""
        if not self.return_to_node_id:
            return None
        return self.node_by_id(self.return_to_node_id)
