"""The serving loop: compiled prefill/decode steps over the page pool.

Transport half of the policy/transport split (the scheduler decides what
runs; this owns how it runs on devices):

- **Page pools** — ``[L, num_blocks, block_size, *row]`` arrays, as many
  and of such rows as the model's cache has (K and V ``[KV, Dh]`` for
  the Llama family, one pool of latent rows for GLM-4.7-Flash; ``L`` the
  model's ``cache_layers``: its layers times its passes), allocated
  once, donated through every jitted step so writes land in place.  On
  a mesh a K/V pool is constrained ``kv_heads`` over tp (the
  round-5 never-replicate-the-cache rule) and activations ``batch`` over
  dp·fsdp, via :mod:`horovod_tpu.parallel.sharding` logical rules.
- **Bucketed shapes** — prompts are right-padded to a bucket length and
  decode block tables to a power-of-two column count, so the number of
  distinct compiled shapes is logarithmic in the workload spread rather
  than linear (each novel shape is a fresh XLA compile).
- **Fixed decode batch** — the decode step always runs ``max_active``
  slots; inactive slots carry token 0 at position 0 against an
  all-scratch block table (block 0 is reserved), so their masked writes
  are harmless and their logits are ignored.
- **Greedy decode** — token-identical to batch
  :func:`~horovod_tpu.models.llama.generate` on the same prompts (the
  model-side steps reuse its math op for op); asserted in
  ``tests/test_serving.py``.
"""

from __future__ import annotations

import dataclasses
import time
from typing import Any, Optional

import numpy as np

from ..models import llama
from .. import chaos
from ..obs import REGISTRY as _obs
from ..obs import trace as _trace
from ..parallel.moe import combine_form, record_held_pairs
from ..utils import logging as hvd_logging
from .kv_pager import KVPager, OutOfBlocks, PagedKVCache
from .scheduler import Request, RequestState, Scheduler

log = hvd_logging.get_logger()

# Serving-plane health (horovod_tpu.obs), sampled once per step():
_m_queue_depth = _obs.gauge(
    "hvd_serving_queue_depth", "requests waiting for admission")
_m_occupancy = _obs.gauge(
    "hvd_serving_batch_occupancy",
    "active decode slots / max_active (1.0 = the compiled batch is full)")
_m_kv_util = _obs.gauge(
    "hvd_serving_kv_utilization",
    "allocated pool blocks / usable blocks (block 0 is scratch)")
_m_steps = _obs.counter(
    "hvd_serving_steps_total", "serving rounds executed")
_m_prefill_tokens = _obs.counter(
    "hvd_serving_prefill_tokens_total", "prompt tokens prefilled")
_m_decode_tokens = _obs.counter(
    "hvd_serving_decode_tokens_total", "tokens emitted by decode ticks")
_m_prefill_skipped = _obs.counter(
    "hvd_serving_prefill_skipped_tokens_total",
    "prompt tokens NOT prefilled because a cached prefix covered them")
# What the decode step's block table holds, summed over ticks: the host
# builds and ships max_active x n_cols table slots whatever they hold, so
# blocks_total / slots_total is the share of them that names a page (the
# gather path reads them all; the Pallas kernel visits live pages only).
_m_table_slots = _obs.counter(
    "hvd_serving_decode_table_slots_total",
    "block-table entries handed to decode steps (max_active x n_cols)")
_m_table_blocks = _obs.counter(
    "hvd_serving_decode_table_blocks_total",
    "of those, entries that name a real (non-scratch) block")
_m_idle_rows = _obs.counter(
    "hvd_serving_decode_idle_rows_total",
    "rows of decode steps' tables that held no stream (all scratch): "
    "the Pallas kernel skips them")
_m_reprefill_tokens = _obs.counter(
    "hvd_serving_reprefill_tokens_total",
    "of the prompt tokens prefilled, those of a preempted request's "
    "prefill after it came back (prompt and what it had generated)")
# What a token costs the pool, set once an engine is built.
_m_cache_layers = _obs.gauge(
    "hvd_serving_cache_layers",
    "K/V layers of the page pool: the model's layers x its passes")
_m_kv_bytes_per_token = _obs.gauge(
    "hvd_serving_kv_bytes_per_token",
    "bytes one token holds in the pools (K and V, or its latent rows as "
    "the pool pads them), all cache layers")

_span = _trace.profiler_span


def _named(name: str, fn):
    """``fn`` under the name its jitted program carries in a profile
    (``jit_<name>`` on the trace's XLA Modules line); a bound method or a
    partial would trace as ``jit__unknown`` or under a private name."""
    def call(*args):
        return fn(*args)
    call.__name__ = call.__qualname__ = name
    return call


def _bucket_pow2(n: int, floor: int = 1) -> int:
    b = floor
    while b < n:
        b *= 2
    return b


@dataclasses.dataclass(frozen=True)
class EngineConfig:
    """Serving-engine knobs (model geometry comes from the model's
    configuration)."""

    #: tokens per KV block (pool page size)
    block_size: int = 16
    #: total pool blocks (block 0 is scratch; HBM budget knob)
    num_blocks: int = 128
    #: decode slots — the fixed compiled decode batch
    max_active: int = 8
    #: max prompt tokens admitted to prefill per step (bounds the latency
    #: a decode tick can see; an over-budget prompt still runs, alone)
    prefill_token_budget: int = 512
    #: round prompt lengths up to one of these before compiling; empty =
    #: exact lengths (one compile per distinct prompt length)
    prefill_buckets: tuple = ()
    #: "auto" (Pallas paged kernel on TPU), "never" (XLA gather), or
    #: "interpret" (kernel through the Pallas interpreter — CPU testing)
    use_flash: str = "auto"
    #: radix prefix cache (frontdoor): admissions sharing a cached
    #: prompt prefix attach its blocks and skip prefilling them
    prefix_cache: bool = False
    #: cap on blocks the cache may pin (None = pool-pressure bounded)
    prefix_cache_max_blocks: Optional[int] = None
    #: speculative decoding: draft tokens per round (0 = off; > 0 needs
    #: ``draft_params``/``draft_cfg`` at engine construction)
    spec_k: int = 0


class ServingEngine:
    """Continuous-batching engine over one model + page pool.

    Drive it with :meth:`submit` + :meth:`step` (one scheduler round:
    retire, admit+prefill, decode tick); :meth:`run` loops until idle.
    Emitted tokens reach the caller through ``Request.generated`` and the
    per-token callbacks the API layer wires in.
    """

    def __init__(self, params: Any, cfg: Any, *,
                 engine_cfg: EngineConfig = EngineConfig(),
                 mesh=None, timeline=None,
                 draft_params: Any = None,
                 draft_cfg: Optional[llama.LlamaConfig] = None) -> None:
        #: Timeline-v2 sink request traces render on (one lane per
        #: request with QUEUE->PREFILL->DECODE flow arrows); None keeps
        #: traces JSON/flight-recorder-only.
        self.timeline = timeline
        #: the module of ``cfg``'s class: what the serving steps ask for
        #: the layer runs, the cache's geometry and the attention paths
        self.model = llama.model_of(cfg)
        self.model.check_servable(cfg, mesh)
        self.params = params
        self.cfg = cfg
        self.ecfg = engine_cfg
        self.mesh = mesh
        if mesh is not None:
            dpf = mesh.shape.get("dp", 1) * mesh.shape.get("fsdp", 1)
            if engine_cfg.max_active % dpf:
                raise ValueError(
                    f"max_active={engine_cfg.max_active} must divide over "
                    f"dp*fsdp={dpf}")
            for a in ("sp", "ep", "pp"):
                if mesh.shape.get(a, 1) > 1:
                    raise NotImplementedError(
                        "serving supports dp/fsdp/tp meshes; "
                        f"{a} is a training-path axis here")
        import jax
        import jax.numpy as jnp
        self._jax, self._jnp = jax, jnp

        self.cache = PagedKVCache(
            n_layers=cfg.cache_layers, num_blocks=engine_cfg.num_blocks,
            block_size=engine_cfg.block_size,
            rows=self.model.cache_rows(cfg))
        self.pager = KVPager(self.cache)
        _m_cache_layers.set(self.cache.n_layers)
        _m_kv_bytes_per_token.set(
            self.cache.bytes_per_block(jnp.dtype(cfg.dtype).itemsize)
            // self.cache.block_size)
        self.prefix_cache = None
        if engine_cfg.prefix_cache:
            from .frontdoor.prefix_cache import PrefixCache
            self.prefix_cache = PrefixCache(
                self.pager,
                max_blocks=engine_cfg.prefix_cache_max_blocks)
        self.scheduler = Scheduler(
            self.pager, max_active=engine_cfg.max_active,
            prefill_token_budget=engine_cfg.prefill_token_budget,
            prefix_cache=self.prefix_cache)

        def fresh_pool(shape):
            pool = jnp.zeros(shape, cfg.dtype)
            if mesh is not None:
                from ..parallel import sharding as shd
                pool = jax.device_put(pool, shd.logical_sharding(
                    mesh, self.model.pool_dims(cfg),
                    self.model.shard_rules(cfg, mesh)))
            return pool

        #: the page pools, one array for each of ``cache.rows``
        self.pools = tuple(fresh_pool(shape) for shape in self.cache.shapes)

        #: what the prefill and decode spans say of the model's depth
        self._depth = dict(loops=cfg.loops, cache_layers=cfg.cache_layers)
        self._slots: list[Optional[Request]] = \
            [None] * engine_cfg.max_active
        self._next_id = 0
        self._steps = 0

        flash = engine_cfg.use_flash
        self._interpret = flash == "interpret"
        wanted = flash == "interpret" or (
            flash == "auto" and jax.default_backend() == "tpu")
        self._use_flash = wanted and self.model.paged_kernel_ok(
            cfg, mesh, engine_cfg.block_size, self._interpret)
        log.info("serving decode attention path: %s (use_flash=%s, "
                 "backend %s, block_size %d, cache rows %s, mesh %s)",
                 self.attention_path, flash, jax.default_backend(),
                 engine_cfg.block_size, self.cache.rows,
                 dict(mesh.shape) if mesh is not None else None)

        # One jit per step kind; bucketing keeps the traced shape set
        # small and jax's cache does the rest.  A profile shows each as
        # the program jit_<name>.
        self._prefill = jax.jit(_named(
            "hvd_serve_prefill", self._prefill_impl))
        self._scatter = jax.jit(_named(
            "hvd_serve_scatter", self._scatter_impl), donate_argnums=(0,))
        self._decode = jax.jit(_named(
            "hvd_serve_decode", self._decode_impl), donate_argnums=(1,))
        self._extend = jax.jit(_named(
            "hvd_serve_extend", self._extend_impl), donate_argnums=(1,))

        self.spec = None
        if engine_cfg.spec_k:
            if draft_params is None or draft_cfg is None:
                raise ValueError(
                    "spec_k > 0 needs draft_params and draft_cfg")
            from .frontdoor.spec_decode import SpecDecoder
            self.spec = SpecDecoder(self, draft_params, draft_cfg,
                                    k=engine_cfg.spec_k)

    # -- jitted step bodies ---------------------------------------------
    # Each returns ``(tokens, stats)`` first: what the host fetches, in
    # one transfer (``stats`` the expert layers' counts, or nothing).
    def _pick(self, logits):
        """The token pick, the last of the program's ``hvd.head``."""
        with _trace.region("head"):
            return self._jnp.argmax(logits, axis=-1).astype(self._jnp.int32)

    def _prefill_impl(self, params, tokens, last_pos):
        logits, kept, stats = llama.prefill_step(
            params, tokens, self.cfg, mesh=self.mesh, last_pos=last_pos)
        return (self._pick(logits), stats), kept

    def _scatter_impl(self, pools, kept, blocks):
        """Write one request's prefill entries (each ``[L, 1, P, *row]``)
        into its ``nb`` blocks of each pool.  P is the prefill bucket: it
        is cut to the blocks' positions where it is longer (here and not
        by the caller, whose eager slices would be more programs, each a
        copy) and padded up to them where it is shorter; the tail slots
        hold pad-token entries, masked by position until decode
        overwrites them one at a time."""
        jnp = self._jnp
        BS = self.cache.block_size
        nb = blocks.shape[0]

        def put(pool, new):
            L = new.shape[0]
            P = min(new.shape[2], nb * BS)
            pad = [(0, 0), (0, nb * BS - P)] + [(0, 0)] * (new.ndim - 3)
            new = jnp.pad(new[:, 0, :P], pad)
            return pool.at[:, blocks].set(
                new.reshape(L, nb, BS, *new.shape[2:]))

        return tuple(put(pool, new) for pool, new in zip(pools, kept))

    def _decode_impl(self, params, pools, tok, pos, tables):
        logits, pools, stats = llama.decode_step_paged(
            params, tok, pos, pools, tables, self.cfg, mesh=self.mesh,
            use_flash=self._use_flash, interpret=self._interpret)
        return (self._pick(logits), stats), pools

    def _extend_impl(self, params, pools, tok, pos, valid, tables):
        """Multi-token paged forward ([B, S] at arbitrary positions):
        the prefix-hit tail prefill and the speculative verify step."""
        logits, pools, stats = llama.extend_step_paged(
            params, tok, pos, valid, pools, tables, self.cfg,
            mesh=self.mesh)
        return (self._pick(logits), stats), pools

    # -- public surface --------------------------------------------------
    @property
    def attention_path(self) -> str:
        """What the decode tick reads the pool through: the model's paged
        kernel, compiled (``"pallas"`` for the Llama family,
        ``"pallas-mla"`` for the latent pool), the same with
        ``"-interpret"``, or ``"gather"`` (XLA)."""
        if not self._use_flash:
            return "gather"
        return self.model.PAGED_KERNEL + (
            "-interpret" if self._interpret else "")

    def lower_decode(self, n_cols: int):
        """The decode tick, lowered (``jax.stages.Lowered``) at a block
        table ``n_cols`` wide — what :meth:`step` runs at that width,
        for inspection of the compiled module (``chip_smoke.py`` looks
        for the paged kernel's custom call in it)."""
        jax, jnp = self._jax, self._jnp
        R = self.ecfg.max_active
        i32 = lambda *shape: jax.ShapeDtypeStruct(shape, jnp.int32)
        return self._decode.lower(self.params, self.pools,
                                  i32(R), i32(R), i32(R, n_cols))

    def submit(self, prompt, max_new_tokens: int, *, eos_token=None,
               stream_cb=None, migrate_cb=None, trace_ctx=None) -> Request:
        # Chaos site: admission.  err rejects the request before it
        # queues (the caller sees the raise, nothing leaks into the
        # scheduler); delay throttles intake.
        chaos.fire("serving_admit")
        prompt = np.asarray(prompt, np.int32).reshape(-1)
        if prompt.size < 1:
            raise ValueError("empty prompt")
        if max_new_tokens < 1:
            raise ValueError(
                f"max_new_tokens must be >= 1, got {max_new_tokens}")
        need = self.cache.blocks_for(int(prompt.size) + 1)
        usable = self.cache.num_blocks - 1
        if need > usable:
            # Reject up front: an unfillable prompt at the head of the
            # strictly-FIFO queue would otherwise livelock admission.
            raise ValueError(
                f"prompt of {prompt.size} tokens needs {need} blocks; the "
                f"pool only has {usable} (raise num_blocks/block_size)")
        req = Request(req_id=self._next_id, prompt=prompt,
                      max_new_tokens=max_new_tokens, eos_token=eos_token,
                      stream_cb=stream_cb, migrate_cb=migrate_cb)
        self._next_id += 1
        # Admission is the root of the request's causal chain: one trace
        # id covers every phase span from here to the terminal state
        # (obs/trace decides sampling; unsampled requests ride NULL_SPAN).
        # trace_ctx joins a trace started upstream (the frontdoor router's
        # ingress span, carried through the request transport) instead of
        # opening a fresh one.
        req.trace = _trace.TRACER.start_trace(
            "serving.request", lane=f"req{req.req_id}",
            timeline=self.timeline, parent=trace_ctx, req_id=req.req_id,
            prompt_len=int(prompt.size), max_new_tokens=max_new_tokens)
        self.scheduler.submit(req)
        return req

    def has_work(self) -> bool:
        return self.scheduler.has_work()

    def pop_failed(self) -> list:
        """Requests the scheduler declared unrunnable (e.g. a preempted
        request whose folded-in progress no longer fits the pool), as
        ``(request, exception)`` pairs — callers fail their futures."""
        failed = self.scheduler.failed
        self.scheduler.failed = []
        return failed

    def step(self) -> list[tuple[Request, int]]:
        """One serving round; returns the (request, token) emissions.

        A raise out of here (device failure, collective abort, injected
        fault) leaves the scheduler/pager bookkeeping consistent enough
        for :meth:`abort_inflight` — the session layer catches, aborts
        the in-flight set, flips ``/healthz``, and either
        drains-and-rejoins (collective/transport failures) or re-raises
        (everything else)."""
        # Chaos site: one traversal per serving round (decode step).
        chaos.fire("serving_step")
        emitted: list[tuple[Request, int]] = []
        self._steps += 1
        _m_steps.inc()
        with _span("hvd.serve.step", step=self._steps):
            with _span("hvd.serve.admit"):
                admitted = self.scheduler.admit()
            for req in admitted:
                self._assign_slot(req)
                n = int(req.prefill_tokens.shape[0]) - req.cached_tokens
                _m_prefill_tokens.inc(n)
                if req.preemptions:
                    _m_reprefill_tokens.inc(n)
                emitted.append((req, self._prefill_one(req)))
                if req.migrate_cb is not None \
                        and req.state == RequestState.RUNNING:
                    # Disaggregated handoff: this replica's job ends at
                    # the prefill emission — export the KV blocks while
                    # the pager table is still held and let a decode
                    # replica continue the request (serving/disagg).
                    self._migrate_out(req)
            if self.scheduler.running:
                with _span("hvd.serve.decode", **self._depth) as tick:
                    ticked = (self.spec.tick(tick) if self.spec is not None
                              else self._decode_tick(tick))
                _m_decode_tokens.inc(len(ticked))
                emitted.extend(ticked)
            self._sample_gauges()
        return emitted

    def _sample_gauges(self) -> None:
        """Pool/queue health after a step: queue depth, compiled-batch
        occupancy, KV-pool utilization."""
        _m_queue_depth.set(len(self.scheduler.waiting))
        _m_occupancy.set(
            len(self.scheduler.running) / self.ecfg.max_active)
        usable = self.cache.num_blocks - 1
        _m_kv_util.set((usable - self.pager.free_blocks) / usable)

    def run(self, max_steps: Optional[int] = None
            ) -> list[tuple[Request, int]]:
        """Steps until the queue drains; returns all emissions in order."""
        out: list[tuple[Request, int]] = []
        n = 0
        while self.has_work():
            out.extend(self.step())
            n += 1
            if max_steps is not None and n >= max_steps:
                break
        return out

    # -- internals -------------------------------------------------------
    def _assign_slot(self, req: Request) -> None:
        i = self._slots.index(None)
        self._slots[i] = req

    def _drop_slot(self, req: Request) -> None:
        self._slots[self._slots.index(req)] = None

    def _sync_slots(self) -> None:
        """Preemption inside scheduler.grow() removes requests from the
        running set behind the engine's back; drop their slots."""
        running = set(id(r) for r in self.scheduler.running)
        for i, r in enumerate(self._slots):
            if r is not None and id(r) not in running:
                self._slots[i] = None

    def _bucket_len(self, n: int) -> int:
        for b in self.ecfg.prefill_buckets:
            if n <= b:
                return b
        return n

    def _prefill_one(self, req: Request) -> int:
        if req.cached_tokens > 0:
            return self._prefill_cached(req)
        jnp = self._jnp
        toks = req.prefill_tokens
        P = int(toks.shape[0])
        Pb = self._bucket_len(P)
        sp = req.open_phase("prefill", tokens=P, bucket=Pb)
        with _span("hvd.serve.prefill", req=req.req_id, tokens=P, cached=0,
                   resumed=P if req.preemptions else 0,
                   **self._depth) as span:
            # The span is the context's current span while the prefill
            # dispatches, so nested layers (collectives the model
            # enqueues) attach their events to this request's chain.
            with sp.use(), _span("hvd.serve.prefill.dispatch"):
                padded = np.zeros((1, Pb), np.int32)
                padded[0, :P] = toks
                # Small integer inputs go in as numpy arrays: jnp.asarray
                # of a list, like an index into a device array, is an
                # eager program of its own (jit_convert_element_type).
                out, kept = self._prefill(
                    self.params, jnp.asarray(padded),
                    np.asarray([P - 1], np.int32))
                blocks = self.pager.table(req.req_id)
                # Only the blocks the P real positions span are written;
                # the +1 slot block (for the emitted token) is untouched.
                nb = self.cache.blocks_for(P)
                self.pools = self._scatter(
                    self.pools, kept, np.asarray(blocks[:nb], np.int32))
                if self.spec is not None:
                    self.spec.mirror_prefill(req, padded, P)
            if self.prefix_cache is not None:
                self.prefix_cache.insert(toks,
                                         self.pager.table(req.req_id))
            req.close_phase("prefill")
            with _span("hvd.serve.prefill.fetch"):
                first = int(self._fetch(out, span)[0])
            token = self._emit(req, first)
            if req.state == RequestState.RUNNING:
                # The decode phase opens once and spans every tick until
                # the terminal state (scheduler.finish/preempt closes it).
                req.open_phase("decode")
        return token

    def _prefill_cached(self, req: Request) -> int:
        """Prefix-hit prefill: the cached head's K/V is already in the
        pool under the shared table head, so only the ``P - C`` tail
        tokens run — through the multi-token extend step, attending over
        the cached blocks via the request's table."""
        jnp = self._jnp
        toks = req.prefill_tokens
        P = int(toks.shape[0])
        C = req.cached_tokens
        S = P - C
        Sb = _bucket_pow2(S)
        sp = req.open_phase("prefill", tokens=P, cached=C, bucket=Sb)
        with _span("hvd.serve.prefill", req=req.req_id, tokens=P, cached=C,
                   resumed=S if req.preemptions else 0,
                   **self._depth) as span:
            with sp.use(), _span("hvd.serve.prefill.dispatch"):
                req.trace.event("prefill_skip", cached_tokens=C)
                tok2 = np.zeros((1, Sb), np.int32)
                tok2[0, :S] = toks[C:]
                # Padded slots repeat a valid position but carry
                # valid=False, so their writes land in scratch block 0
                # and their logits are never read.
                pos2 = np.full((1, Sb), P - 1, np.int32)
                pos2[0, :S] = np.arange(C, P, dtype=np.int32)
                val2 = np.zeros((1, Sb), bool)
                val2[0, :S] = True
                n_cols = min(_bucket_pow2(self.cache.blocks_for(P)),
                             self.cache.num_blocks)
                tables = self.pager.table_matrix([req.req_id], n_cols)
                out, self.pools = self._extend(
                    self.params, self.pools, jnp.asarray(tok2),
                    jnp.asarray(pos2), jnp.asarray(val2),
                    jnp.asarray(tables))
                if self.spec is not None:
                    self.spec.mirror_extend(tok2, pos2, val2, tables)
            if self.prefix_cache is not None:
                # The tail may complete further full blocks; share them
                # too.
                self.prefix_cache.insert(toks,
                                         self.pager.table(req.req_id))
            _m_prefill_skipped.inc(C)
            req.close_phase("prefill")
            with _span("hvd.serve.prefill.fetch"):
                first = int(self._fetch(out, span)[0, S - 1])
            token = self._emit(req, first)
            if req.state == RequestState.RUNNING:
                req.open_phase("decode")
        return token

    def _fetch(self, out, span, touched: bool = False):
        """A step's tokens and stats in the one transfer the host makes.
        Where the model has expert layers, their pairs by expert go onto
        ``span`` (``moe_pairs``, and ``moe_combine``, the form in which
        the program's grouped products came back to the rows, by the rule
        the program itself goes by; with ``touched`` also how many of the
        ``experts_held`` (layer, expert) pairs the step sent any row to)
        and into the per-layer routing metrics."""
        tok, stats = self._jax.device_get(out)
        counts = stats.get("expert_counts")
        if counts is not None:
            scored = self.model.moe_experts_scored(self.cfg)
            attrs = dict(moe_pairs=int(counts.sum()),
                         moe_combine=combine_form(counts.shape[-1], scored))
            if touched:
                attrs.update(experts_touched=int(np.count_nonzero(counts)),
                             experts_held=counts.size)
            span.set_metadata(**attrs)
            for layer, c in zip(self.model.moe_layer_names(self.cfg),
                                counts):
                record_held_pairs(c, layer=layer, scored=scored)
        return tok

    def _count_table(self, tick, tables: np.ndarray, **more) -> None:
        """What one decode step's block table holds and how full the
        pool stands (and ``more``), onto the step's profiler span and the
        cumulative counters.  Block 0 is scratch and never in a request's table, so
        the non-zero entries are the real pages and a row whose first
        entry is non-zero has a stream (the decode program tells by the
        same)."""
        blocks = int(np.count_nonzero(tables))
        rows = int(np.count_nonzero(tables[:, 0]))
        _m_table_slots.inc(tables.size)
        _m_table_blocks.inc(blocks)
        _m_idle_rows.inc(tables.shape[0] - rows)
        usable = self.cache.num_blocks - 1
        tick.set_metadata(n_cols=tables.shape[1], blocks=blocks, rows=rows,
                          blocks_held=usable - self.pager.free_blocks,
                          blocks_usable=usable, **more)

    def _decode_tick(self, tick) -> list[tuple[Request, int]]:
        """One decode step for the running set, under ``tick``, the
        round's ``hvd.serve.decode`` profiler span."""
        jnp = self._jnp
        with _span("hvd.serve.decode.grow"):
            # Reserve the write position for every running request
            # first — growth can preempt, shrinking the running set.
            for req in list(self.scheduler.running):
                if req in self.scheduler.running:
                    try:
                        self.scheduler.grow(req)
                    except OutOfBlocks as e:
                        # Only reachable when req cannot fit even alone
                        # (grow preempts every other victim first): fail
                        # THIS request and keep the batch serving — a
                        # per-request capacity problem must not abort
                        # the engine.
                        self.scheduler.fail_running(req, e)
            self._sync_slots()
        active = [r for r in self._slots if r is not None]
        if not active:
            return []
        with _span("hvd.serve.decode.tables"):
            R = self.ecfg.max_active
            need_cols = max(
                self.cache.blocks_for(r.context_len + 1) for r in active)
            n_cols = min(_bucket_pow2(need_cols), self.cache.num_blocks)
            tok = np.zeros((R,), np.int32)
            pos = np.zeros((R,), np.int32)
            ids = [-1] * R
            for i, r in enumerate(self._slots):
                if r is None:
                    continue
                tok[i] = r.generated[-1]
                pos[i] = r.context_len
                ids[i] = r.req_id
            tables = self.pager.table_matrix(ids, n_cols)
            # kv_tokens: the cached tokens this tick's queries read, the
            # one being written included
            self._count_table(tick, tables,
                              kv_tokens=int(pos.sum()) + len(active))
            args = (jnp.asarray(tok), jnp.asarray(pos), jnp.asarray(tables))
        with _span("hvd.serve.decode.dispatch"):
            out, self.pools = self._decode(self.params, self.pools, *args)
        with _span("hvd.serve.decode.fetch"):
            nxt = self._fetch(out, tick, touched=True)
        emitted = []
        with _span("hvd.serve.decode.emit"):
            for i, r in enumerate(list(self._slots)):
                if r is None:
                    continue
                r.context_len += 1          # this tick wrote pos[i]
                emitted.append((r, self._emit(r, int(nxt[i]))))
        return emitted

    def _emit(self, req: Request, token: int) -> int:
        req.generated.append(token)
        eos = req.eos_token is not None and token == req.eos_token
        done = eos or len(req.generated) >= req.max_new_tokens
        if done:
            req.finish_reason = "stop" if eos else "length"
            self.scheduler.finish(req)
            self._drop_slot(req)
        return token

    def _migrate_out(self, req: Request) -> None:
        """Export ``req``'s KV blocks and retire it locally with
        ``finish_reason="migrated"``.  Runs right after the prefill
        emission, BEFORE ``scheduler.finish`` releases the blocks; the
        export is a host-side copy, so by the time the callback gets the
        payload the pool blocks are free to recycle.  A callback failure
        (KV store down, injected fault) fails THIS request only — the
        batch keeps serving."""
        from .disagg import migration
        sp = req.open_phase("migrate", context_len=req.context_len)
        try:
            with sp.use():
                manifest, k_bytes, v_bytes = migration.export_request(
                    self, req)
            req.close_phase("migrate",
                            bytes=len(k_bytes) + len(v_bytes))
            req.finish_reason = "migrated"
            self.scheduler.finish(req)
            self._drop_slot(req)
            req.migrate_cb(manifest, k_bytes, v_bytes)
        except Exception as e:
            req.close_phase("migrate", error=str(e))
            if req in self.scheduler.running:
                self.scheduler.fail_running(req, e)
                self._drop_slot(req)
            else:
                # Export succeeded but the publish callback failed after
                # finish(): surface through the failed list so the
                # session fails the future instead of hanging it.
                req.state = RequestState.CANCELLED
                req.finish_reason = "error"
                self.scheduler.failed.append((req, e))

    def import_migrated(self, manifest: dict, k_bytes: bytes,
                        v_bytes: bytes, *, stream_cb=None) -> Request:
        """Attach a migrated request's exported KV blocks to this
        engine's pool and resume decoding it — zero re-prefill, token
        identical to a local prefill (greedy decode).  See
        :mod:`horovod_tpu.serving.disagg.migration`."""
        from .disagg import migration
        return migration.import_request(self, manifest, k_bytes, v_bytes,
                                        stream_cb=stream_cb)

    def abort_inflight(self, exc: BaseException) -> list[Request]:
        """Graceful-degradation half of a step failure: finish every
        queued and running request NOW with ``finish_reason="error"``
        (partial tokens preserved — streamed clients already hold
        them), release their pool blocks, and leave the engine empty
        and reusable.  Returns the aborted requests; the session layer
        resolves their futures and owns the /healthz + rejoin story."""
        aborted: list[Request] = []
        for req in list(self.scheduler.running):
            self.scheduler.running.remove(req)
            self.pager.release(req.req_id)
            aborted.append(req)
        while self.scheduler.waiting:
            aborted.append(self.scheduler.waiting.popleft())
        for req in aborted:
            req.state = RequestState.CANCELLED
            req.finish_reason = "error"
            req.t_finished = time.monotonic()
            req.close_trace("aborted", error=str(exc))
        self._slots = [None] * self.ecfg.max_active
        self._sample_gauges()
        return aborted
