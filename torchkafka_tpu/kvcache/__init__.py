"""Paged KV-cache pool with radix-tree prefix reuse for the serving path.

Host-side machinery (static device shapes live in ops/kvattn.py and the
servers): ``BlockAllocator`` — refcounted free-list blocks over a
``[num_blocks, block_size, K, Dh]`` pool, block 0 reserved as the sink
for idle-slot writes; ``RadixCache`` — prompt-prefix tree mapping whole
block runs, LRU-evicting unreferenced leaves (eviction is advisory: a
miss just re-prefills, token-exactness never depends on the cache);
``PagedKVConfig`` — the ``StreamingGenerator(kv_pages=...)`` knob;
``resolve_kv_backend`` — the single capability probe deciding how the
four cache axes (dense/paged × compute/int8 × gather/kernel ×
single-device/mesh) compose for one server (kvcache/backend.py); what the
layout it names means for the dense server is kvcache/slot_pool.py's.
"""

from torchkafka_tpu.kvcache.backend import (
    KV_KERNEL_AUTO_MIN_POOL,
    KVBackend,
    resolve_kv_backend,
)
from torchkafka_tpu.kvcache.blocks import (
    SINK_BLOCK,
    BlockAllocator,
    PagedKVConfig,
)
from torchkafka_tpu.kvcache.radix import RadixCache
from torchkafka_tpu.kvcache.tier import HostTier, TierConfig

__all__ = [
    "BlockAllocator",
    "HostTier",
    "KVBackend",
    "KV_KERNEL_AUTO_MIN_POOL",
    "PagedKVConfig",
    "RadixCache",
    "SINK_BLOCK",
    "TierConfig",
    "resolve_kv_backend",
]
