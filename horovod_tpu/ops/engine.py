"""Asynchronous collective engine: tensor queue + background fusion cycle.

Reference architecture († ``horovod/common/operations.cc``): framework ops
enqueue a ``TensorTableEntry`` and return immediately; a background thread
(``BackgroundThreadLoop`` → ``RunLoopOnce`` every ``HOROVOD_CYCLE_TIME`` ms)
negotiates readiness across ranks, fuses ready tensors up to
``HOROVOD_FUSION_THRESHOLD`` bytes, executes one collective per fused batch,
and fires completion callbacks.  ``synchronize(handle)`` blocks the caller
(† ``horovod/torch/mpi_ops_v2.cc HandleManager``).

TPU-native redesign:

- *Negotiation* is a pluggable ``Negotiator``.  Single-controller mode (one
  process drives all devices) needs none — the enqueueing thread is the only
  source of requests, so everything is trivially "ready on all ranks".
  Multi-process mode plugs in the native controller
  (``horovod_tpu/_native``) which runs the reference's rank-0 coordinator
  protocol over TCP.
- *Fusion* batches queue entries with matching (verb, reduce-op, dtype,
  process-set) signatures into one compiled grouped program per cycle
  († fusion buffer, minus the explicit memcpys — XLA owns HBM layout).
- *Overlap* comes from JAX async dispatch: the cycle thread enqueues device
  work and returns without blocking; ``synchronize`` only blocks the caller.

Urgent wakeup: ``synchronize(handle)`` nudges the engine for an immediate
cycle instead of letting the blocked caller wait out the cycle time, so
blocking latency ≈ dispatch cost while concurrent async traffic still fuses.

"""

from __future__ import annotations

import json
import threading
import time
from dataclasses import dataclass, field
from typing import Any, Callable, Optional, Sequence

from . import collectives as C
from . import reduction as _R
from .. import chaos
from ..obs import REGISTRY as _obs
from ..obs import flightrec as _frec
from ..obs import perfmodel as _perf
from ..obs import trace as _trace
from ..utils import logging as hvd_logging

log = hvd_logging.get_logger()

# Engine telemetry (horovod_tpu.obs): per-collective count/byte accounting
# is the substrate for comms optimization (Awan et al., arXiv:1810.11112)
# the reference only exposed as a Chrome trace.
_m_collectives = _obs.counter(
    "hvd_collectives_total", "collectives dispatched by the engine",
    ("verb",))
_m_bytes = _obs.counter(
    "hvd_collective_bytes_total",
    "payload bytes through engine-dispatched collectives", ("verb",))
_m_errors = _obs.counter(
    "hvd_collective_errors_total",
    "collectives that completed with an error", ("verb",))
_m_fusion_batch = _obs.histogram(
    "hvd_fusion_batch_tensors", "tensors per fused allreduce dispatch",
    buckets=(1, 2, 4, 8, 16, 32, 64, 128, 256))
_m_cycle = _obs.histogram(
    "hvd_cycle_seconds",
    "engine cycle wall time (drain -> negotiate -> fuse -> dispatch)")
_m_queue_depth = _obs.gauge(
    "hvd_engine_queue_depth",
    "entries left pending in the tensor queue after a cycle")

# Pre-resolved per-verb children: the completion loop runs once per tensor
# per cycle (the gradient-hook hot path), so keep it at one locked float
# add per series — no labels() lookup per event.
_VERBS = ("allreduce", "allgather", "broadcast", "alltoall", "reducescatter")
_m_coll_v = {v: _m_collectives.labels(verb=v) for v in _VERBS}
_m_bytes_v = {v: _m_bytes.labels(verb=v) for v in _VERBS}
_m_errors_v = {v: _m_errors.labels(verb=v) for v in _VERBS}


class HorovodInternalError(RuntimeError):
    """A collective failed after being accepted († ``common.h`` status →
    ``HorovodInternalError`` raised by every framework binding).  Elastic
    mode catches this to trigger restore/re-rendezvous."""


@dataclass
class TensorTableEntry:
    """† ``horovod/common/common.h TensorTableEntry`` (name, tensor, context,
    callback) — payloads here are per-rank jax Arrays."""
    name: str
    verb: str                      # allreduce | allgather | broadcast | alltoall | reducescatter
    payload: Any
    op: C.ReduceOp = C.ReduceOp.AVERAGE
    root_rank: int = 0
    splits: Optional[Sequence[int]] = None
    prescale: float = 1.0
    postscale: float = 1.0
    process_set: Any = None
    # Wire precision mode (ops/reduction.py): resolved at enqueue time so
    # every rank derives it from the same (op, dtype, size, config) and
    # fused groups / negotiation signatures agree.  "" = fp32 default.
    precision: str = ""
    # Collective schedule descriptor (ops/sched): "" = monolithic, else
    # a concrete "rs_ag:<chunks>".  Resolved at enqueue time under the
    # same determinism contract as ``precision``.
    schedule: str = ""
    enqueue_time: float = field(default_factory=time.monotonic)
    # Timeline phase currently open for this entry ("" | QUEUE | NEGOTIATE);
    # † timeline.cc tracks the same per-tensor lifecycle state.
    tl_phase: str = field(default="", compare=False)
    # Timeline-v2 flow id linking this entry's QUEUE span to its DISPATCH
    # span (0 = no flow open).
    tl_flow: int = field(default=0, compare=False)

    def meta(self) -> str:
        """Serialized descriptor carried through negotiation so a joined
        rank can construct zero-payload participation († the Response's
        tensor metadata that backs ``RequestType::JOIN``).  Empty for
        entries a joined rank cannot rebuild (process-set sub-meshes,
        ragged list payloads)."""
        if self.process_set is not None:
            return ""
        p = self.payload
        try:
            shape, dtype = tuple(p.shape), str(p.dtype)
        except AttributeError:
            return ""
        m: dict = {"v": self.verb, "d": dtype, "s": list(shape),
                   "o": self.op.value}
        if self.root_rank:
            m["r"] = self.root_rank
        if self.splits is not None:
            m["sp"] = list(self.splits)
        if self.prescale != 1.0:
            m["ps"] = self.prescale
        if self.postscale != 1.0:
            m["po"] = self.postscale
        if self.precision and self.precision != "fp32":
            # The negotiator signature carries the wire mode: a joined
            # rank must fabricate its zero participation at the SAME
            # precision or the fused XLA programs diverge across ranks.
            # fp32 (the implicit default) is omitted so default-mode
            # metas stay byte-identical with pre-wire-precision peers.
            m["wp"] = self.precision
        if self.schedule:
            # Same contract for the schedule: a joined rank must rebuild
            # the identical decomposed program (chunk count included) or
            # the per-chunk XLA dispatches diverge across ranks.
            # Monolithic ("") is omitted, keeping default-mode metas
            # byte-identical with pre-schedule-IR peers.
            m["sc"] = self.schedule
        return json.dumps(m, separators=(",", ":"))


def _joinable_entry(e: TensorTableEntry) -> bool:
    """Can a joined rank stand in for this entry with zeros?

    † Reference join semantics: allreduce (and its grouped/fused form)
    only.  Process-set entries and entries whose descriptor cannot be
    serialized (ragged payloads) are excluded — the joined rank could not
    rebuild them.  Must agree with :func:`_parse_joinable_meta`: live
    ranks decide from their own entry, joined ranks from the echoed meta,
    and both must reach the same verdict for the mesh to stay consistent.
    """
    return (e.verb == "allreduce" and e.process_set is None
            and e.meta() != "")


def _parse_joinable_meta(meta: str) -> Optional[dict]:
    """Parse an echoed descriptor; None unless it fully describes a
    joinable (allreduce) entry — verb, shape, dtype, and reduce op must
    all be present and well-formed, so :meth:`CollectiveEngine._zero_entry`
    is total on accepted metas (a half-valid descriptor from a
    version-skewed peer must be skipped, not crash the cycle thread).
    The joined-rank half of :func:`_joinable_entry`."""
    if not meta:
        return None
    try:
        m = json.loads(meta)
        if m.get("v") != "allreduce":
            return None
        m["s"] = [int(d) for d in m["s"]]
        C.ReduceOp(m["o"])
        if not isinstance(m["d"], str):
            return None
        if m.get("wp", "") not in ("",) + _R.MODES:
            # Unknown wire mode from a version-skewed peer: we could not
            # build a matching program — skip, don't crash the cycle.
            return None
        if m.get("sc", ""):
            from .sched import known_descriptor
            if not known_descriptor(m["sc"]):
                # Unknown schedule lowering from a version-skewed peer
                # (not rs_ag:<k>, hier:<n_local>:<k> or
                # compiled:rs_ag:<k>): same rule — skip, don't crash.
                return None
    except (ValueError, TypeError, KeyError):
        return None
    return m


class Handle:
    """Async completion handle († ``handle_manager.cc``: int handle +
    ``synchronize``)."""

    __slots__ = ("_event", "_result", "_error", "name")

    def __init__(self, name: str) -> None:
        self.name = name
        self._event = threading.Event()
        self._result: Any = None
        self._error: Optional[BaseException] = None

    def _complete(self, result: Any = None,
                  error: Optional[BaseException] = None) -> None:
        self._result = result
        self._error = error
        self._event.set()

    def poll(self) -> bool:
        """Non-blocking completion check († ``hvd.poll``)."""
        return self._event.is_set()

    def wait(self, timeout: Optional[float] = None) -> Any:
        """Block until complete and return the output († ``hvd.synchronize``)."""
        if not self._event.wait(timeout):
            raise TimeoutError(f"collective {self.name!r} still pending")
        if self._error is not None:
            raise HorovodInternalError(
                f"collective {self.name!r} failed: {self._error}"
            ) from self._error
        return self._result


@dataclass
class NegotiationOutcome:
    """One round's agreed result († ``Response`` list).

    ``ready``: globally-ready names in the agreed dispatch order.
    ``metas``: name → serialized entry descriptor for ready tensors this
    process may not hold locally (join zero-participation).
    ``join_covered``: ready names whose readiness depended on a joined
    rank's fabricated zero participation — only allreduce dispatches for
    these; other verbs error identically on every rank († the reference
    returns an error Response for non-allreduce ops while a rank is
    joined).
    ``all_joined`` / ``last_join_rank``: † ``hvd.join()`` completion.
    ``stall_info``: name → attribution record (which ranks never
    submitted a stalled tensor, and its age) from the coordinator's
    stall inspector; empty in single-controller mode.
    """
    ready: list[str]
    stalled: list[str] = field(default_factory=list)
    metas: dict = field(default_factory=dict)
    all_joined: bool = False
    last_join_rank: int = 0
    join_covered: set = field(default_factory=set)
    stall_info: dict = field(default_factory=dict)


class Negotiator:
    """Readiness protocol interface († ``Controller::ComputeResponseList``)."""

    # Distributed protocols are round-barriers: every process must check in
    # every cycle even with an empty queue († every rank sends its Request
    # list each cycle, possibly empty).
    always_check_in = False

    def negotiate(self, entries: list[TensorTableEntry], *,
                  joined: bool = False) -> NegotiationOutcome:
        """Return the agreed ready set (ordered) for this cycle."""
        raise NotImplementedError

    def stall_attribution(self, name: str) -> Optional[str]:
        """Straggler attribution for a stalled tensor ("awaiting rank(s)
        3, 12s"), when this protocol can know it; None otherwise.  The
        engine folds it into stall warnings and shutdown errors."""
        return None

    def close(self) -> None:
        pass


class SingleControllerNegotiator(Negotiator):
    """One process sees every request — everything is ready immediately."""

    def negotiate(self, entries: list[TensorTableEntry], *,
                  joined: bool = False) -> NegotiationOutcome:
        if entries:
            # Chaos site (single-controller half; the distributed
            # negotiator fires it at its barrier entry) — lets
            # single-process chaos tests exercise the round-abort path.
            chaos.fire("negotiate")
        return NegotiationOutcome(ready=[e.name for e in entries])


class CollectiveEngine:
    """Background cycle thread owning the tensor queue.

    † ``BackgroundThreadLoop`` + ``TensorQueue`` + fusion, restructured so the
    queue drain → negotiate → fuse → dispatch path is synchronous within one
    cycle and device execution is left async to JAX.
    """

    def __init__(self, state, negotiator: Optional[Negotiator] = None) -> None:
        self._state = state
        self._negotiator = negotiator or SingleControllerNegotiator()
        self._queue: list[tuple[TensorTableEntry, Handle]] = []
        self._names_pending: set[str] = set()
        self._lock = threading.Lock()
        self._wake = threading.Condition(self._lock)
        self._urgent = False
        self._paused = False
        self._running = False
        self._thread: Optional[threading.Thread] = None
        self._cycle_count = 0
        self._last_cycle_ts = time.monotonic()
        self._last_stall_warn = 0.0
        self._autotuner = None  # attached lazily when autotune is enabled
        self._join_requested = False
        self._join_result = -1
        self._join_event = threading.Event()
        # Latched completion: set by the engine when a join finishes with
        # no caller waiting (the caller timed out); consumed by the next
        # join() call so it returns the delivered result instead of
        # re-raising the JOIN flag into a new phase.
        self._join_pending_consume = False

    # -- lifecycle ----------------------------------------------------------
    def start(self) -> None:
        self._running = True
        self._thread = threading.Thread(
            target=self._loop, name="hvdtpu-engine", daemon=True)
        self._thread.start()
        if self._state.config.autotune:
            from ..utils.autotune import Autotuner
            self._autotuner = Autotuner(self._state)

    def stop(self) -> None:
        with self._wake:
            self._running = False
            self._wake.notify_all()
        if self._thread is not None:
            self._thread.join(timeout=5)
            self._thread = None
        self._negotiator.close()
        # Fail any stragglers so synchronize() callers don't hang.
        with self._lock:
            for entry, handle in self._queue:
                self._tl_close(entry)
                handle._complete(error=RuntimeError("engine shut down"))
            self._queue.clear()
            self._names_pending.clear()

    def _tl_close(self, e: TensorTableEntry) -> None:
        """End any open timeline span for an entry leaving the engine on an
        error path, keeping Chrome-trace B/E events balanced."""
        if e.tl_phase:
            tl = self._state.timeline
            if tl is not None and tl.enabled:
                tl.end_activity(e.name)
            e.tl_phase = ""

    def nudge(self) -> None:
        """Request an immediate cycle (used by ``synchronize`` so a blocking
        caller doesn't wait out the cycle time)."""
        with self._wake:
            self._urgent = True
            self._wake.notify_all()

    def pause(self) -> None:
        """Hold queue processing (elastic re-rendezvous; deterministic tests)."""
        with self._wake:
            self._paused = True

    def resume(self) -> None:
        with self._wake:
            self._paused = False
            self._urgent = True
            self._wake.notify_all()

    # -- enqueue († EnqueueTensorAllreduce et al.) --------------------------
    def enqueue(self, entry: TensorTableEntry, *, urgent: bool = False
                ) -> Handle:
        handle = Handle(entry.name)
        with self._wake:
            if not self._running:
                handle._complete(error=RuntimeError("engine not running"))
                return handle
            if entry.name in self._names_pending:
                # † TensorQueue rejects duplicate in-flight names.
                handle._complete(error=ValueError(
                    f"a collective named {entry.name!r} is already pending"))
                return handle
            self._names_pending.add(entry.name)
            self._queue.append((entry, handle))
            # Request-scoped tracing: when the enqueueing context works
            # a traced request (serving prefill under span.use()), the
            # collective joins that request's causal chain.
            sp = _trace.current_span()
            if sp is not None:
                sp.event("collective.enqueue", tensor=entry.name,
                         verb=entry.verb)
            tl = self._state.timeline
            if tl is not None and tl.enabled:
                # † NEGOTIATING/QUEUE phases: QUEUE = enqueue -> cycle
                # pickup; NEGOTIATE = pickup -> globally ready.
                tl.start_activity(entry.name, "QUEUE")
                entry.tl_phase = "QUEUE"
                entry.tl_flow = tl.new_flow()
                tl.flow_start(entry.name, entry.tl_flow)
            if urgent:
                self._urgent = True
                self._wake.notify_all()
        return handle

    # -- background loop († RunLoopOnce) ------------------------------------
    def _loop(self) -> None:
        while True:
            with self._wake:
                if not self._running:
                    return
                if not self._urgent:
                    self._wake.wait(
                        timeout=self._state.config.cycle_time_ms / 1000.0)
                if not self._running:
                    return
                self._urgent = False
                if self._paused:
                    continue
                batch = self._queue
                self._queue = []
            try:
                self._run_cycle(batch)
            except BaseException:  # pragma: no cover - defensive
                log.exception("engine cycle crashed")
            try:
                self._check_stalls()
            except HorovodInternalError as err:
                # Stall shutdown: fail every pending handle so all callers
                # raise († error Response to all ranks), then stop the loop.
                with self._lock:
                    pending = self._queue
                    self._queue = []
                    self._names_pending.clear()
                    self._running = False
                for entry, handle in pending:
                    self._tl_close(entry)
                    handle._complete(error=err)
                log.error("engine stopped by stall shutdown: %s", err)
                # Postmortem bundle: the ring + registry + the
                # coordinator's straggler attribution (missing-rank
                # bitmap per stalled tensor) — the scrape you can no
                # longer take, written to disk instead.
                _frec.RECORDER.record("stall_shutdown", error=str(err))
                _frec.RECORDER.maybe_dump(
                    "stall_shutdown",
                    stall=getattr(self._negotiator,
                                  "last_stall_info", None),
                    extra={"error": str(err),
                           "pending": [e.name for e, _ in pending]})
                return

    @property
    def distributed(self) -> bool:
        return self._negotiator.always_check_in

    # -- health (the /healthz readiness probe reads these) ------------------
    @property
    def alive(self) -> bool:
        """Cycle thread running — the readiness half of ``/healthz``."""
        return bool(self._running and self._thread is not None
                    and self._thread.is_alive())

    @property
    def last_negotiation_age_s(self) -> float:
        """Seconds since the last completed negotiation (multi-process)
        or engine cycle (single-controller) — a growing age on a rank
        whose peers are advancing is the wedged-rank probe signal."""
        ts = getattr(self._negotiator, "last_negotiate_ts", None)
        return time.monotonic() - (ts if ts is not None
                                   else self._last_cycle_ts)

    def _run_cycle(self, batch: list[tuple[TensorTableEntry, Handle]]) -> None:
        self._cycle_count += 1
        self._last_cycle_ts = time.monotonic()
        tl = self._state.timeline
        if tl is not None:
            tl.mark_cycle()
        if not batch and not self._negotiator.always_check_in:
            return
        t0 = time.monotonic()
        entries = [e for e, _ in batch]
        handles = {id(e): h for e, h in batch}
        tl = self._state.timeline
        if tl is not None and tl.enabled:
            for e in entries:
                if e.tl_phase == "QUEUE":
                    tl.end_activity(e.name)
                    tl.start_activity(e.name, "NEGOTIATE")
                    e.tl_phase = "NEGOTIATE"
        join_req = self._join_requested
        try:
            outcome = self._negotiator.negotiate(entries, joined=join_req)
        except Exception as err:
            # Negotiation transport failure (controller died, TCP error):
            # fail every handle in the batch so waiters raise instead of
            # hanging († error Response to all ranks; elastic catches the
            # resulting HorovodInternalError and re-rendezvouses).
            for e, h in batch:
                with self._lock:
                    self._names_pending.discard(e.name)
                self._tl_close(e)
                # A round abort usually means a peer stall-shut-down
                # first; fold the last known straggler attribution into
                # THIS entry's error so victim ranks also learn which
                # rank was withholding what, not just that a peer died.
                e_err = err
                attr = self._negotiator.stall_attribution(e.name)
                if attr is not None:
                    try:
                        e_err = type(err)(
                            f"{err} [stalled tensor {e.name!r}: {attr}]")
                    except Exception:   # exotic ctor: keep the original
                        e_err = err
                h._complete(error=e_err)
            if join_req:
                with self._lock:
                    self._join_requested = False
                    self._join_result = -1
                    self._join_pending_consume = True
                self._join_event.set()
            log.error("negotiation failed; %d collectives errored: %s",
                      len(batch), err)
            # Round abort (controller died / peer stall-shut-down first):
            # same postmortem contract as a local stall shutdown, so the
            # victim ranks leave bundles naming the withheld tensors too.
            _frec.RECORDER.record("round_abort", error=str(err))
            _frec.RECORDER.maybe_dump(
                "round_abort",
                stall=getattr(self._negotiator, "last_stall_info", None),
                extra={"error": str(err),
                       "entries": [e.name for e, _ in batch]})
            return
        by_name = {e.name: e for e in entries}
        ready: list[TensorTableEntry] = []
        errored: set[int] = set()
        for name in outcome.ready:
            e = by_name.get(name)
            if e is not None:
                if name in outcome.join_covered and not _joinable_entry(e):
                    # † Join supports allreduce only: a joined rank cannot
                    # fabricate meaningful participation in an allgather /
                    # broadcast / alltoall (zero rows would silently corrupt
                    # the result), so every rank errors this entry instead
                    # of dispatching.  The joined rank skips it by the same
                    # rule (below), keeping the mesh consistent — no hang.
                    errored.add(id(e))
                    with self._lock:
                        self._names_pending.discard(e.name)
                    self._tl_close(e)
                    handles[id(e)]._complete(error=HorovodInternalError(
                        f"collective {name!r} ({e.verb}"
                        + (", process-set" if e.process_set is not None
                           else "")
                        + ") became ready through a joined rank, but only "
                        "allreduce supports join zero-participation "
                        "(† reference join semantics)"))
                    continue
                ready.append(e)
            elif join_req:
                # Not ours: another rank's tensor became ready because we
                # joined — participate with zeros († JoinOp) when the verb
                # allows it.  Non-joinable entries are skipped here and
                # error on the ranks that own them (same rule, so nobody
                # dispatches and nobody hangs).
                meta = _parse_joinable_meta(outcome.metas.get(name, ""))
                if meta is None:
                    log.warning(
                        "join: skipping non-joinable ready tensor %r "
                        "(it errors on the ranks that submitted it)", name)
                    continue
                try:
                    e = self._zero_entry(name, meta)
                except Exception as err:  # defensive: never kill the cycle
                    log.error(
                        "join: failed to build zero participation for %r "
                        "(%s); skipping — peers may stall (stall inspector "
                        "will report)", name, err)
                    continue
                handles[id(e)] = Handle(e.name)  # result dropped
                ready.append(e)
        # Errored entries are consumed too — re-queueing them would
        # renegotiate a dead tensor every cycle (livelock) and re-complete
        # an already-errored handle.
        consumed_ids = {id(e) for e in ready} | errored
        deferred = [(e, h) for e, h in batch if id(e) not in consumed_ids]
        if deferred:
            with self._lock:
                self._queue = deferred + self._queue
        self._reconcile_metas(ready, by_name, outcome.metas)
        for group in self._fuse(ready):
            self._execute_group(group, handles)
        _m_cycle.observe(time.monotonic() - t0)
        with self._lock:
            depth = len(self._queue)
        _m_queue_depth.set(depth)
        if tl is not None and tl.enabled:
            # Timeline v2: registry-fed counter tracks alongside the spans.
            tl.counter("hvd.engine", {
                "queue_depth": depth,
                "collectives_total": _m_collectives.total(),
                "collective_bytes_total": _m_bytes.total(),
            })
        if join_req and outcome.all_joined:
            with self._lock:
                self._join_requested = False
                self._join_result = outcome.last_join_rank
                self._join_pending_consume = True
            self._join_event.set()
        if self._autotuner is not None:
            payload = sum(self._entry_bytes(e) for e in ready)
            self._autotuner.record_cycle(payload, time.monotonic() - t0)

    def _reconcile_metas(self, ready: list[TensorTableEntry],
                         by_name: dict, metas: dict) -> None:
        """Adopt the coordinator's echoed schedule/wire-mode for locally
        held ready entries whose own resolution differs.

        Both fields are normally deterministic in synchronized config, so
        every rank resolves the same values and this is a no-op.  But a
        deliberately skewed fleet — one rank pinned
        ``HOROVOD_TPU_SCHED_MODE=compiled``, a peer ``decomposed`` —
        would otherwise dispatch *different executables* for the same
        collective, which cannot work at all: under ``jax.distributed``
        the collective channel IDs are assigned per-executable, so a
        compiled rank and a dispatched rank would rendezvous on nothing
        and hang.  The coordinator stores ONE meta per tensor (lowest
        submitting rank wins — see native ``RecordName``) and echoes it
        identically to every rank, so adopting the echoed value here —
        before fusion, which keys on the descriptor — is the only sound
        reconciliation: any rule must be independent of the local value,
        because the rank whose meta was stored sees no mismatch.  An
        unparseable echoed meta keeps the local resolution (that peer
        skips the entry by the :func:`_parse_joinable_meta` rule, so
        nothing dispatches against us).
        """
        if not metas:
            return
        for e in ready:
            if (e.verb != "allreduce" or e.process_set is not None
                    or by_name.get(e.name) is not e):
                continue
            raw = metas.get(e.name)
            if raw is None or raw == e.meta():
                continue
            m = _parse_joinable_meta(raw)
            if m is None:
                continue
            sc = m.get("sc", "")
            wp = m.get("wp", "")
            if sc != e.schedule or wp != (
                    e.precision if e.precision != "fp32" else ""):
                log.info(
                    "adopting negotiated meta for %r: schedule %r -> %r, "
                    "wire %r -> %r (peer resolutions differed; one "
                    "executable per collective is mandatory)", e.name,
                    e.schedule or "monolithic", sc or "monolithic",
                    e.precision or "fp32", wp or "fp32")
                e.schedule = sc
                e.precision = wp

    # -- join († RequestType::JOIN, hvd.join()) ------------------------------
    def join(self, timeout: Optional[float] = None) -> int:
        """Signal this rank has no more input; participate as zeros in
        other ranks' collectives until every rank joins.  Returns the last
        rank to join († ``horovod/torch/__init__.py join()``)."""
        if not self.distributed:
            raise RuntimeError(
                "engine.join() requires distributed (multi-process) mode; "
                "single-controller callers use the barrier fallback")
        deadline = None if timeout is None else time.monotonic() + timeout
        with self._lock:
            if self._join_pending_consume:
                # A previous join() timed out but the join completed while
                # no caller was waiting; hand over the latched result
                # instead of enrolling this rank in a brand-new join phase.
                return self._consume_join_locked()
            resuming = self._join_requested
        if not resuming:
            # Drain our own pending collectives first: a joining rank has
            # no more inputs, so everything already enqueued must dispatch
            # before the JOIN flag is raised (matching the reference,
            # where JOIN is itself a queued request ordered after prior
            # submissions).
            while True:
                with self._lock:
                    if not self._queue and not self._names_pending:
                        break
                if deadline is not None and time.monotonic() > deadline:
                    raise TimeoutError(
                        "join(): pending collectives never drained")
                self.nudge()
                time.sleep(0.005)
            self._join_event.clear()
            with self._wake:
                self._join_requested = True
                self._urgent = True
                self._wake.notify_all()
        remaining = None if deadline is None else \
            max(0.0, deadline - time.monotonic())
        if not self._join_event.wait(remaining):
            # The JOIN flag already sent to the controller is irrevocable
            # (other ranks' tensors may have become ready through our
            # implicit coverage), so the engine MUST stay in joined mode
            # and keep zero-participating; clearing the flag here would
            # strand the other ranks mid-collective.  The caller may
            # re-invoke join() to resume waiting — it resumes this join
            # phase (or consumes the result if it completed meanwhile)
            # rather than starting a new one.
            raise TimeoutError(
                "join(): not all ranks joined in time (this rank remains "
                "joined; call join() again to keep waiting)")
        with self._lock:
            return self._consume_join_locked()

    def _consume_join_locked(self) -> int:
        """Hand the completed join result to the caller (lock held)."""
        self._join_pending_consume = False
        result = self._join_result
        self._join_result = -1
        self._join_event.clear()
        if result < 0:
            raise HorovodInternalError("join(): failed mid-join (see log)")
        return result

    def _zero_entry(self, name: str, m: dict) -> TensorTableEntry:
        """Build the zero-payload stand-in a joined rank contributes.

        † JoinOp semantics: the joined rank supplies zeros of the same
        shape/dtype; AVERAGE divides by the full world size including
        joined ranks (reference behavior).  ``m`` is a descriptor already
        validated by :func:`_parse_joinable_meta` (verb, shape, dtype and
        op all checked); dtype resolution goes through jnp so extended
        types (bfloat16, fp8) work.  The caller still guards the call —
        an unresolvable dtype string must skip the tensor, not crash the
        cycle thread.
        """
        import jax.numpy as jnp
        import numpy as np
        shape = tuple(m["s"])
        local_rows = len(self._state.local_devices)
        zeros = np.zeros((local_rows,) + shape[1:],
                         dtype=jnp.dtype(m["d"]))
        payload = C.from_local(zeros)
        return TensorTableEntry(
            name=name, verb=m["v"], payload=payload,
            op=C.ReduceOp(m["o"]), root_rank=m.get("r", 0),
            splits=m.get("sp"), prescale=m.get("ps", 1.0),
            postscale=m.get("po", 1.0), precision=m.get("wp", ""),
            schedule=m.get("sc", ""))

    @staticmethod
    def _entry_bytes(e: TensorTableEntry) -> int:
        p = e.payload
        try:
            return int(p.size * p.dtype.itemsize)
        except AttributeError:
            return 0

    def _fuse(self, entries: list[TensorTableEntry]
              ) -> list[list[TensorTableEntry]]:
        """Group fusable entries; split at the fusion threshold.

        † fusion_buffer_manager.cc: same dtype+op tensors share a fused
        dispatch up to ``fusion_threshold`` bytes.  Only allreduce fuses
        (matching the reference — other verbs execute per-tensor).
        """
        threshold = self._state.config.fusion_threshold
        # HOROVOD_TPU_BUCKET_BYTES: the sched bucket layer's size target
        # also caps fused groups, so a bucketed backward's per-bucket
        # dispatches are not re-coalesced into one giant buffer that
        # would serialize the overlap the buckets exist to create.
        bucket = int(getattr(self._state.config, "bucket_bytes", 0) or 0)
        if bucket > 0:
            threshold = min(threshold, bucket)
        groups: dict[tuple, list[TensorTableEntry]] = {}
        order: list[tuple] = []
        singles: list[list[TensorTableEntry]] = []
        for e in entries:
            if e.verb == "allreduce" and e.op is not C.ReduceOp.ADASUM:
                # Same wire precision fuses together; mixing modes in one
                # buffer would force the whole group to the widest wire.
                # "" (entries built without API resolution, e.g. join
                # zero-participation for default-mode tensors) IS fp32 —
                # normalized here so both fuse identically on all ranks.
                # Same rule for the schedule: decomposed entries fuse
                # only with same-descriptor entries (one chunked program
                # per fused buffer; "" IS monolithic).
                key = ("allreduce", e.op, str(e.payload.dtype),
                       id(e.process_set), e.prescale, e.postscale,
                       e.precision or "fp32", e.schedule)
                if key not in groups:
                    groups[key] = []
                    order.append(key)
                groups[key].append(e)
            else:
                singles.append([e])
        fused: list[list[TensorTableEntry]] = []
        for key in order:
            current: list[TensorTableEntry] = []
            current_bytes = 0
            for e in groups[key]:
                nbytes = self._entry_bytes(e)
                if current and current_bytes + nbytes > threshold:
                    fused.append(current)
                    current, current_bytes = [], 0
                current.append(e)
                current_bytes += nbytes
            if current:
                fused.append(current)
        return fused + singles

    def _execute_group(self, group: list[TensorTableEntry],
                       handles: dict[int, Handle]) -> None:
        tl = self._state.timeline
        try:
            if tl is not None and tl.enabled:
                for e in group:
                    if e.tl_phase == "NEGOTIATE":
                        tl.end_activity(e.name)
                    tl.start_activity(e.name, "DISPATCH")
                    e.tl_phase = "DISPATCH"
                    if e.tl_flow:
                        # v2 flow arrow: QUEUE span -> this DISPATCH span.
                        tl.flow_end(e.name, e.tl_flow)
                        e.tl_flow = 0
            # Named span in device profiles too: `jax.profiler.trace()`
            # captures show which collective a compiled program belongs
            # to, complementing the host-side Chrome timeline
            # († SURVEY aux: timeline + per-collective profiler spans).
            label = (group[0].name if len(group) == 1
                     else f"hvd.fused[{len(group)}].{group[0].name}")
            # Chaos site: one traversal per fused dispatch.  err lands
            # in this handler's error path (HorovodInternalError to
            # every waiter — the elastic recovery trigger); die is the
            # injected rank death the chaos CI scenario rides.
            chaos.fire("dispatch")
            t_disp = time.monotonic()
            with _trace.profiler_span(f"hvd.{group[0].verb}:{label}"):
                results = self._dispatch(group)
            t_disp = time.monotonic() - t_disp
            if tl is not None and tl.enabled:
                for e in group:
                    tl.end_activity(e.name)
                    e.tl_phase = ""
            if group[0].verb == "allreduce":
                _m_fusion_batch.observe(len(group))
            e0 = group[0]
            if not e0.schedule:
                # Expected-vs-achieved feed for monolithic dispatches
                # (decomposed allreduces are observed by the sched
                # executor itself, from its per-step windows).  The host
                # dispatch window is the achieved timing — async
                # dispatch makes it a lower bound, consistent within
                # each (verb, mode, schedule) series.
                try:
                    itemsize = int(e0.payload.dtype.itemsize)
                except AttributeError:
                    itemsize = 4
                # _entry_bytes counts the device-stacked array; the ring
                # model wants the per-rank logical payload (what the
                # sched executor also accounts: shape[1:]).
                nranks = max(1, self._state.size)
                _perf.MODEL.observe(
                    e0.verb,
                    sum(self._entry_bytes(e) for e in group) // nranks,
                    nranks, t_disp,
                    mode=e0.precision or "fp32", itemsize=itemsize)
            _frec.RECORDER.record(
                "dispatch", name=label, verb=group[0].verb,
                tensors=len(group),
                bytes=sum(self._entry_bytes(e) for e in group))
            for e, r in zip(group, results):
                _m_coll_v[e.verb].inc()
                _m_bytes_v[e.verb].inc(self._entry_bytes(e))
                with self._lock:
                    self._names_pending.discard(e.name)
                handles[id(e)]._complete(result=r)
        except BaseException as err:
            # † error Response delivered to every participating rank so all
            # raise rather than some hanging.
            _frec.RECORDER.record(
                "collective_error", name=group[0].name,
                verb=group[0].verb, error=repr(err))
            for e in group:
                # .get fallback: an unknown verb reaches this loop via the
                # _dispatch ValueError, and the error path must never throw.
                (_m_errors_v.get(e.verb)
                 or _m_errors.labels(verb=e.verb)).inc()
                with self._lock:
                    self._names_pending.discard(e.name)
                self._tl_close(e)
                handles[id(e)]._complete(error=err)

    def _dispatch(self, group: list[TensorTableEntry]) -> list[Any]:
        e0 = group[0]
        if e0.verb == "allreduce":
            if e0.schedule and e0.op is not C.ReduceOp.ADASUM:
                # Decomposed schedule (ops/sched): walk the chunked
                # reduce-scatter/allgather pipeline, overlapping later
                # chunks' communication with earlier chunks' compute.
                # The whole fused group rides one schedule (fusion key
                # includes the descriptor, so the group is homogeneous).
                from .sched import executor as SE
                label = (e0.name if len(group) == 1
                         else f"hvd.fused[{len(group)}].{e0.name}")
                return SE.execute_allreduce(
                    [e.payload for e in group], e0.op,
                    descriptor=e0.schedule,
                    precision=e0.precision or "fp32",
                    prescale=e0.prescale, postscale=e0.postscale,
                    process_set=e0.process_set, name=label)
            # schedule="monolithic" pins the dispatch to the enqueue-time
            # resolution — C.allreduce must not re-resolve from config
            # (the entry's schedule was agreed across ranks at enqueue).
            if len(group) == 1:
                return [C.allreduce(e0.payload, e0.op,
                                    prescale_factor=e0.prescale,
                                    postscale_factor=e0.postscale,
                                    precision=e0.precision or "fp32",
                                    schedule="monolithic",
                                    process_set=e0.process_set)]
            return C.grouped_allreduce(
                [e.payload for e in group], e0.op,
                prescale_factor=e0.prescale, postscale_factor=e0.postscale,
                precision=e0.precision or "fp32", schedule="monolithic",
                process_set=e0.process_set)
        assert len(group) == 1
        if e0.verb == "allgather":
            return [C.allgather(e0.payload, process_set=e0.process_set)]
        if e0.verb == "broadcast":
            return [C.broadcast(e0.payload, e0.root_rank,
                                process_set=e0.process_set)]
        if e0.verb == "alltoall":
            return [C.alltoall(e0.payload, e0.splits,
                               process_set=e0.process_set)]
        if e0.verb == "reducescatter":
            return [C.reducescatter(e0.payload, e0.op,
                                    process_set=e0.process_set)]
        raise ValueError(f"unknown verb {e0.verb!r}")

    # -- stall inspector († stall_inspector.cc) ----------------------------
    def _check_stalls(self) -> None:
        cfg = self._state.config
        if not cfg.stall_check:
            return
        now = time.monotonic()
        if now - self._last_stall_warn < cfg.stall_warning_time_s:
            return
        with self._lock:
            stalled = [(e.name, now - e.enqueue_time)
                       for e, _ in self._queue
                       if now - e.enqueue_time > cfg.stall_warning_time_s]
        if stalled:
            self._last_stall_warn = now
            # Fold in the coordinator's straggler attribution when the
            # protocol knows it (multi-process mode): the shutdown error
            # then names the exact withholding rank(s), not just the
            # tensor († the reference's stall log stopped at the name).
            def _desc(n: str, age: float) -> str:
                attr = self._negotiator.stall_attribution(n)
                return (f"{n} ({age:.0f}s; {attr})" if attr
                        else f"{n} ({age:.0f}s)")
            desc = ", ".join(_desc(n, age) for n, age in stalled)
            _frec.RECORDER.record("stall_warning", desc=desc)
            log.warning(
                "Stall detected: collectives pending > %.0fs without "
                "completing negotiation: %s. One or more ranks may have "
                "diverged (e.g. rank-dependent conditionals).",
                cfg.stall_warning_time_s, desc)
            if cfg.stall_shutdown_time_s > 0:
                worst = max(age for _, age in stalled)
                if worst > cfg.stall_shutdown_time_s:
                    raise HorovodInternalError(
                        f"stalled collectives exceeded shutdown time "
                        f"({cfg.stall_shutdown_time_s}s): {desc}")

    # -- stats --------------------------------------------------------------
    @property
    def cycle_count(self) -> int:
        return self._cycle_count
