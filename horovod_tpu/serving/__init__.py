"""Continuous-batching inference engine with a paged KV cache.

ABSENT in the reference (Horovod is a training collective layer); this is
the serving counterpart the ROADMAP's "heavy traffic from millions of
users" north star needs.  The batch-synchronous
:func:`horovod_tpu.models.llama.generate` decodes one fixed batch at one
shared sequence length — a single long request stalls the whole batch and
every short request pays worst-case KV memory.  This package replaces that
with request-level scheduling:

- :mod:`~horovod_tpu.serving.kv_pager` — block-paged KV cache over the
  grouped ``[B, S, KV, D]`` layout: a free-list allocator, per-request
  block tables, and paged-attention dispatch (gather-by-block-table under
  XLA, the Pallas kernel's own copies of each stream's live pages).
- :mod:`~horovod_tpu.serving.scheduler` — continuous batching: admission
  queue, prefill/decode phase split, per-step join/evict, and a prefill
  token budget that bounds decode latency.
- :mod:`~horovod_tpu.serving.engine` — the serving loop owning compiled
  prefill/decode step functions (bucketed shapes bound recompiles) on
  dp/tp meshes.
- :mod:`~horovod_tpu.serving.api` — ``serve()`` front door: ``submit()``
  futures, streaming token callbacks, per-request TTFT / queue-wait /
  tok/s metrics.
- :mod:`~horovod_tpu.serving.frontdoor` — the production front door on
  top of one-replica sessions: a multi-replica router over the obs
  plane's KV-store signals, a radix prefix cache that lets shared prompt
  prefixes skip prefill, and draft-model speculative decoding.
- :mod:`~horovod_tpu.serving.disagg` — disaggregated prefill/decode:
  pool-tagged replicas, cross-replica KV-block migration over the job
  KV store (versioned manifest + chunked payloads, one shared retry
  deadline), and a pool-aware router whose migration handoff is
  first-class state with durable-point failover.

The split follows HiCCL's policy/transport separation (arXiv:2408.05962):
the scheduler decides *what* runs each step, the engine owns *how* it runs
on the mesh.
"""

from .api import RequestResult, ServingSession, serve  # noqa: F401
from .engine import EngineConfig, ServingEngine  # noqa: F401
from .kv_pager import KVPager, PagedKVCache  # noqa: F401
from .scheduler import Request, Scheduler  # noqa: F401
