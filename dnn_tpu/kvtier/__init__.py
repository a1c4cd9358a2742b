"""dnn_tpu.kvtier — the fleet-wide radix prefix/KV tier (ROADMAP item 2).

Four connected pieces:

  * `radix.py`    — a trie over block_len-aligned token-id chunks; pure
                    host data structure (no jax), one node per KV pool
                    block, leaf-LRU eviction under refcount protection;
  * `store.py`    — PrefixStore: binds the radix index to the paged
                    BlockAllocator (dnn_tpu/runtime/paged_kvcache.py),
                    owning one reference per resident block; the serving
                    pool (`ContinuousBatcher(kv="paged", prefix_cache=N)`)
                    consults it at admission — longest-prefix-match
                    returns a run of refcounted physical blocks,
                    divergence copy-on-writes only the boundary block;
  * `migrate.py`  — per-block migration between replicas: the packed
                    block wire format (int8/int4 quantized blocks
                    migrate as-is), the model-checked lease state
                    machine (offered/pulling/adopted/released/expired —
                    analysis/protocol.KVLEASE), and the shm/grpc rungs;
  * `directory.py`— the router's bounded which-replica-holds-which-prefix
                    map feeding prefix-aware placement
                    (dnn_tpu/control/router.py).

The serving integration lives in runtime/serving.py (admission +
stage/export/adopt) and runtime/lm_server.py (the kvstage/kvlease/
kvfetch/kvack/kvpull endpoints); tests/test_kvtier.py holds the contract.
"""

from dnn_tpu.kvtier.radix import RadixIndex, RadixNode  # noqa: F401
from dnn_tpu.kvtier.store import PrefixStore, PrefixHit  # noqa: F401

__all__ = ["RadixIndex", "RadixNode", "PrefixStore", "PrefixHit"]
