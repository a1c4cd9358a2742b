"""dnn_tpu — a TPU-native distributed neural-network framework.

Re-implements (from scratch, TPU-first) the capabilities of the reference
framework 123-code/Distributed-neural-networks: a model is split into
sequential stages placed on separate devices from a JSON topology config
(reference: config.json, node.py:222-277), activations flow stage-to-stage
through a pipeline (reference: gRPC SendTensor relay, node.py:35-105), a
single shared checkpoint is sliced per stage (node.py:294-317), and a
client path preprocesses an input and returns the final prediction
(node.py:137-200).

Where the reference hosts each stage as a PyTorch nn.Module in a separate
gRPC process and relays raw numpy bytes over TCP, this framework hosts
stages as jit-compiled JAX programs on TPU chips, maps the config's
`part_index` onto a `jax.sharding.Mesh` pipeline axis, and moves
activations with `jax.lax.ppermute` (XLA CollectivePermute) over ICI.
"""

from dnn_tpu.version import __version__
from dnn_tpu.registry import get_model, register_model, available_models
from dnn_tpu.config import TopologyConfig

__all__ = [
    "__version__",
    "get_model",
    "register_model",
    "available_models",
    "TopologyConfig",
]
