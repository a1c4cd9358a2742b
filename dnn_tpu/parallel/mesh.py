"""Device-mesh construction.

The reference's topology is a list of (node, address, part_index) entries
(config.json:3-14) with next-hop resolution by part_index+1
(node.py:262-271). The TPU-native equivalent: `part_index` becomes a
coordinate on the "stage" axis of a `jax.sharding.Mesh`, and the "hop" is
`lax.ppermute` over ICI instead of a gRPC call.

Axis conventions used across the framework:
  "data"   — data parallelism (batch sharding, gradient psum)
  "stage"  — pipeline parallelism (the reference's only axis)
  "model"  — tensor parallelism (Megatron-style head/mlp sharding)
  "seq"    — sequence/context parallelism (ring attention)
  "expert" — expert parallelism (MoE expert sharding, all_to_all dispatch)
"""

from __future__ import annotations

from typing import Dict, Optional, Sequence

import jax
import numpy as np
from jax.sharding import Mesh

STAGE_AXIS = "stage"
DATA_AXIS = "data"
MODEL_AXIS = "model"
SEQ_AXIS = "seq"
EXPERT_AXIS = "expert"


def make_mesh(axes: Dict[str, int], devices: Optional[Sequence] = None) -> Mesh:
    """Build a Mesh with named axes, e.g. {"data": 2, "stage": 2, "model": 2}.

    Axis order follows dict order; put the fastest-varying (most
    bandwidth-hungry, usually "model") axis last so it lands on the
    innermost/closest ICI neighbors.
    """
    devices = list(devices if devices is not None else jax.devices())
    sizes = list(axes.values())
    need = int(np.prod(sizes)) if sizes else 1
    if len(devices) < need:
        raise ValueError(
            f"mesh {axes} needs {need} devices, have {len(devices)} "
            f"({[str(d) for d in devices[:4]]}...)"
        )
    grid = np.array(devices[:need], dtype=object).reshape(sizes)
    return Mesh(grid, tuple(axes.keys()))


def mesh_from_config(config, devices: Optional[Sequence] = None) -> Mesh:
    """TopologyConfig -> Mesh. `num_parts` (the reference's stage count,
    config.json:16) sizes the "stage" axis; any extra axes come from the
    extended `mesh` config key."""
    axes = dict(config.mesh) if config.mesh else {}
    axes.setdefault(STAGE_AXIS, config.num_parts)
    if axes[STAGE_AXIS] != config.num_parts:
        raise ValueError(
            f"config.mesh['stage']={axes[STAGE_AXIS]} conflicts with "
            f"num_parts={config.num_parts}"
        )
    return make_mesh(axes, devices)
