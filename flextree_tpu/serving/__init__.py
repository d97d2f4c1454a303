"""Serving front-end: continuous-batching generation over a paged KV cache.

The traffic-facing layer of the framework — requests in, tokens out,
measured in throughput and latency percentiles instead of step time:

- :mod:`.kv_cache` — the paged/blocked KV cache: fixed-size blocks, a
  host-side free-list allocator, per-sequence block tables; ragged
  sequences share one static-shaped pool and the decode step stays one
  compiled program (gather pages → batched ragged decode → scatter
  appended K/V), bitwise-identical to the contiguous-cache ``generate``.
- :mod:`.batcher` — the continuous batcher: FIFO request queue, admission
  by token budget (all cache blocks reserved up front, so admitted
  requests never hit mid-decode exhaustion), join-at-step prefill, and
  per-sequence retirement that frees blocks immediately.
- :mod:`.engine` — one serving replica: paged pool + batcher + the two
  jitted programs, with per-request greedy/temperature/top-k sampling and
  TTFT / per-token timestamps on an injectable clock.
- :mod:`.prefix_index` — the cross-request prefix cache: a radix trie
  over prompt token ids at block granularity, refcounted copy-on-write
  sharing of full prompt blocks, LRU eviction under pool pressure, and
  suffix-only prefill on a hit — bitwise-identical to a cold engine
  (``tests/test_prefix_cache.py``).  The front door
  routes by prefix affinity so shared prompts land where their blocks
  already are.
- :mod:`.pool` — the elastic replica pool: ``runtime.Supervisor``
  heartbeat/lease membership over replicas, a ``StepWatchdog`` deadline
  around each scheduling round, and drain/re-route off dead replicas so
  the pool degrades instead of failing.
- :mod:`.rpc` / :mod:`.replica_main` / :mod:`.frontdoor` — the
  real-process tier: a CRC-trailered frame protocol over TCP, a replica
  server process per engine (heartbeat-registered, SIGTERM-drainable),
  and a front-door router with deadlines, bounded retries, windowed-p99
  hedging, circuit breakers, and load shedding — exactly-once results
  via replica-side idempotency, proven under kill chaos by
  ``tools/rpc_chaos.py`` → ``RPC_CHAOS.json``.
- :mod:`.migration` / :mod:`.costs` — prefill/decode disaggregation:
  replicas run as ``--role prefill`` (prompt forward only, KV shipped
  out) or ``--role decode`` (admit migrated blocks mid-stream), the KV
  payload rides the frame protocol as int8/f32 block-scaled tensors
  with per-tensor CRCs, and the cost planner's migration-vs-recompute
  crossover decides per request whether the hop pays — exactly-once
  and bitwise in ``tests/test_disagg.py``.

Measured by the benchmark's serving cells (``BENCHMARK.json``,
``benchmarks/run.py``; ``PERF.md`` has the numbers).  Design notes and
the honest limits: ``docs/SERVING.md``.
"""

from .batcher import (
    BatcherConfig,
    ContinuousBatcher,
    PreemptedSeq,
    Request,
    SeqState,
)
from .engine import CompletedRequest, ServingEngine
from .frontdoor import (
    FrontDoor,
    FrontDoorConfig,
    FrontDoorResult,
    ReplicaClient,
)
from .kv_cache import (
    NULL_BLOCK,
    BlockAllocator,
    CacheExhausted,
    PagedCacheConfig,
    gather_seq,
    init_pools,
    init_state,
    make_paged_decode_fn,
    paged_decode_step,
    write_prefill,
    write_prefill_at,
    write_swapped,
)
from .migration import (
    MigrationError,
    migration_error_bound,
    pack_kv,
    unpack_kv,
    unpack_state,
)
from .pool import PoolConfig, ReplicaFailed, ReplicaPool
from .prefix_index import PrefixIndex, PrefixIndexError
from .replica_main import ReplicaConfig, ReplicaServer
from .rpc import (
    RpcConnection,
    RpcConnRefused,
    RpcError,
    RpcShed,
    RpcTimeout,
    RpcTornFrame,
)

__all__ = [
    "NULL_BLOCK",
    "BlockAllocator",
    "CacheExhausted",
    "PagedCacheConfig",
    "init_pools",
    "init_state",
    "write_prefill",
    "write_prefill_at",
    "write_swapped",
    "paged_decode_step",
    "make_paged_decode_fn",
    "gather_seq",
    "Request",
    "SeqState",
    "PreemptedSeq",
    "BatcherConfig",
    "ContinuousBatcher",
    "PrefixIndex",
    "PrefixIndexError",
    "ServingEngine",
    "CompletedRequest",
    "PoolConfig",
    "ReplicaFailed",
    "ReplicaPool",
    "RpcError",
    "RpcTimeout",
    "RpcConnRefused",
    "RpcTornFrame",
    "RpcShed",
    "RpcConnection",
    "ReplicaConfig",
    "ReplicaServer",
    "FrontDoor",
    "FrontDoorConfig",
    "FrontDoorResult",
    "ReplicaClient",
    "MigrationError",
    "pack_kv",
    "unpack_kv",
    "unpack_state",
    "migration_error_bound",
]
