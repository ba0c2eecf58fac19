"""horovod_tpu — a TPU-native distributed training framework with Horovod's
capabilities, rebuilt from scratch on JAX/XLA.

Public API parity map (reference: ``jayhpark530/horovod``, a snapshot of
upstream Horovod; see SURVEY.md):

=====================================  =====================================
Reference († upstream path)            Here
=====================================  =====================================
``hvd.init()``                         :func:`init`
``hvd.rank()/size()/local_*``          :func:`rank` / :func:`size` / ...
``hvd.allreduce`` (+``_async_``)       :func:`allreduce` / :func:`allreduce_async`
``hvd.grouped_allreduce``              :func:`grouped_allreduce`
``hvd.allgather`` / ``alltoall``       :func:`allgather` / :func:`alltoall`
``hvd.broadcast``                      :func:`broadcast`
``hvd.synchronize/poll`` (torch)       :func:`synchronize` / :func:`poll`
``hvd.DistributedOptimizer``           :class:`optim.DistributedOptimizer`
``hvd.broadcast_parameters``           :func:`broadcast_parameters`
``hvd.elastic.run`` / ``State``        :mod:`horovod_tpu.elastic`
``horovodrun``                         ``hvdrun`` (:mod:`horovod_tpu.runner`)
``hvd.add_process_set``                :func:`add_process_set`
``hvd.join()``                         :func:`join`
=====================================  =====================================

Usage::

    import horovod_tpu as hvd
    hvd.init()
    g = hvd.per_rank_from_fn(lambda r: np.full((4,), r, np.float32))
    avg = hvd.allreduce(g)              # replicated mean across ranks
"""

from __future__ import annotations

import itertools
from typing import Any, Optional, Sequence

from . import config  # noqa: F401
from . import obs  # noqa: F401  (also arms the env-gated metrics endpoint)
from .context import (  # noqa: F401
    init,
    shutdown,
    is_initialized,
    rank,
    size,
    local_rank,
    local_size,
    cross_rank,
    cross_size,
    mesh,
    global_state,
    NotInitializedError,
)
from .ops import (  # noqa: F401
    ReduceOp,
    Average,
    Sum,
    Min,
    Max,
    Product,
    Adasum,
    per_rank,
    per_rank_from_fn,
    to_numpy,
)
from .ops.collectives import (  # noqa: F401
    from_local,
    replicate_local,
    to_local,
)
from .ops.engine import Handle, HorovodInternalError, TensorTableEntry
from .ops import collectives as _C
from .ops import reduction as _R
from .ops.compression import Compression  # noqa: F401  (hvd.Compression.*)

__version__ = "0.1.0"

_name_counter = itertools.count()


def _auto_name(prefix: str, name: Optional[str]) -> str:
    # † reference auto-names tensors per framework op when name is omitted.
    return name if name is not None else f"{prefix}.noname.{next(_name_counter)}"


def _engine():
    state = global_state()
    if not state.initialized or state.engine is None:
        raise NotInitializedError()
    return state.engine


# ---------------------------------------------------------------------------
# Synchronous verbs.
#
# Single-process: direct compiled dispatch (lowest latency).  Multi-process:
# routed through the engine so the coordinator orders them against
# concurrent async traffic — mixing un-negotiated dispatches with negotiated
# ones could interleave differently across processes and deadlock the
# device queues (the exact failure Horovod's coordinator exists to prevent).
# ---------------------------------------------------------------------------

def _sync_via_engine_or_direct(direct_fn, verb: str, payload: Any,
                               **entry_kw) -> Any:
    state = global_state()
    if state.initialized and state.engine is not None \
            and state.engine.distributed:
        entry = TensorTableEntry(
            name=_auto_name(verb, None), verb=verb, payload=payload,
            **entry_kw)
        handle = state.engine.enqueue(entry, urgent=True)
        return handle.wait()
    return direct_fn()


def _resolve_entry_precision(compression, payload, op, process_set) -> str:
    """Wire mode for an engine entry, resolved at enqueue time.

    Deterministic in (compression, op, dtype, per-rank bytes, config) so
    every rank building the same entry at the same program point derives
    the same mode — the property fusion groups and negotiation
    signatures rely on (the same reason DistributedOptimizer latches
    the fusion threshold).  Delegates to the canonical convention in
    ops/collectives so enqueue-time and dispatch-time resolution can
    never drift apart.
    """
    state = global_state()
    if not state.initialized:
        return _R.as_wire_mode(compression) or "fp32"
    mesh, axis = _C._mesh_axis(process_set)
    return _C._resolve_precision(_R.as_wire_mode(compression), op, payload,
                                 mesh.shape[axis])


def _resolve_entry_schedule(payload, op, process_set, mode: str) -> str:
    """Collective schedule for an engine entry, resolved at enqueue time
    under the same determinism contract as ``_resolve_entry_precision``
    (the descriptor rides the negotiation meta's ``sc`` field, so every
    rank — joined ranks included — must derive the same one)."""
    state = global_state()
    if not state.initialized:
        return ""
    mesh, axis = _C._mesh_axis(process_set)
    return _C._resolve_schedule("", op, payload, mesh.shape[axis], mode)


def allreduce(x: Any, op: ReduceOp = Average, *,
              prescale_factor: float = 1.0, postscale_factor: float = 1.0,
              compression=None, process_set=None) -> Any:
    """Reduce a per-rank tensor across ranks; result replicated
    († ``hvd.allreduce``).

    ``compression`` selects the wire precision: a ``hvd.Compression.*``
    entry or a mode string (``"fp32"``/``"bf16"``/``"fp16"``/``"int8"``/
    ``"fp8"``); None defers to ``HOROVOD_TPU_WIRE_PRECISION``.
    """
    payload = _C.as_per_rank(x, process_set)
    mode = _resolve_entry_precision(compression, payload, op, process_set)
    sched = _resolve_entry_schedule(payload, op, process_set, mode)
    return _sync_via_engine_or_direct(
        lambda: _C.allreduce(payload, op, prescale_factor=prescale_factor,
                             postscale_factor=postscale_factor,
                             precision=mode, schedule=sched or "monolithic",
                             process_set=process_set),
        "allreduce", payload, op=op, prescale=prescale_factor,
        postscale=postscale_factor, precision=mode, schedule=sched,
        process_set=process_set)


def grouped_allreduce(xs: Sequence[Any], op: ReduceOp = Average, *,
                      prescale_factor: float = 1.0,
                      postscale_factor: float = 1.0,
                      compression=None, process_set=None) -> list:
    """Fused allreduce of several tensors in one program/collective
    († ``hvd.grouped_allreduce``).  ``compression`` as in
    :func:`allreduce`; the wire mode resolves against the group's total
    bytes (one quantized program covers the whole explicit group)."""
    return _C.grouped_allreduce(
        xs, op, prescale_factor=prescale_factor,
        postscale_factor=postscale_factor,
        precision=_R.as_wire_mode(compression), process_set=process_set)


def allgather(x: Any, process_set=None) -> Any:
    """Concatenate per-rank tensors along dim 0 († ``hvd.allgather``).

    A list/tuple input is the ragged (``MPI_Allgatherv``) form: one piece
    per rank this process drives (single-controller: all ranks;
    multi-process: this process's local ranks), with per-rank row counts
    free to differ.  See :func:`_allgather_v`.
    """
    if isinstance(x, (list, tuple)):
        return _allgather_v(list(x), process_set)
    payload = _C.as_per_rank(x, process_set)
    return _sync_via_engine_or_direct(
        lambda: _C.allgather(payload, process_set=process_set),
        "allgather", payload, process_set=process_set)


def _allgather_v(pieces: list, process_set=None) -> Any:
    """Ragged allgather († ``MPI_Allgatherv``), multi-process correct.

    Built from two negotiated uniform collectives — no host-side
    reassembly of other ranks' data, so the same path runs in
    single-controller and multi-process modes:

    1. allgather each rank's row count (tiny int32 collective);
    2. pad every piece to the max count, allgather the padded block
       (one compiled program), and index out the valid rows.
    """
    import numpy as _np
    import jax.numpy as _jnp
    arrs = [_np.asarray(p) for p in pieces]
    if not arrs:
        raise ValueError("allgather needs at least one local piece")
    trailing = {a.shape[1:] for a in arrs}
    dtypes = {a.dtype for a in arrs}
    if len(trailing) != 1 or len(dtypes) != 1:
        raise ValueError(
            "allgather pieces must agree on trailing dims/dtype "
            "(† coordinator shape-consistency check)")
    counts = _np.array([[a.shape[0]] for a in arrs], _np.int32)
    sizes = _C.to_numpy(allgather(
        _C.from_local(counts, process_set), process_set=process_set))
    sizes = sizes.reshape(-1).astype(int)
    maxr = max(1, int(sizes.max()))
    padded = _np.zeros((len(arrs), maxr) + arrs[0].shape[1:], arrs[0].dtype)
    for i, a in enumerate(arrs):
        padded[i, :a.shape[0]] = a
    g = allgather(_C.from_local(padded, process_set),
                  process_set=process_set)           # [n*maxr, *rest]
    idx = _np.concatenate([
        _np.arange(i * maxr, i * maxr + s) for i, s in enumerate(sizes)
    ]) if sizes.sum() else _np.zeros((0,), _np.int64)
    return g[_jnp.asarray(idx)]


def broadcast(x: Any, root_rank: int, process_set=None) -> Any:
    """Every rank receives root's tensor († ``hvd.broadcast``)."""
    payload = _C.as_per_rank(x, process_set)
    return _sync_via_engine_or_direct(
        lambda: _C.broadcast(payload, root_rank, process_set=process_set),
        "broadcast", payload, root_rank=root_rank, process_set=process_set)


def alltoall(x: Any, splits: Optional[Sequence[int]] = None,
             process_set=None) -> Any:
    """Scatter dim-0 slices of each rank's tensor to all ranks
    († ``hvd.alltoall``).

    With ``splits`` (the ``MPI_Alltoallv`` form): ``splits[j]`` rows of
    this rank's tensor go to rank *j*.  Input may be a per-rank array
    (same splits everywhere) or a list of pieces — one per rank this
    process drives — whose row totals may differ.  Returns a list of
    received tensors for this process's ranks.
    """
    if splits is not None or isinstance(x, (list, tuple)):
        return _alltoall_v(x, splits, process_set)
    payload = _C.as_per_rank(x, process_set)
    return _sync_via_engine_or_direct(
        lambda: _C.alltoall(payload, splits, process_set=process_set),
        "alltoall", payload, splits=splits, process_set=process_set)


def _alltoall_v(x: Any, splits: Optional[Sequence[int]], process_set=None
                ) -> list:
    """Non-uniform alltoall († ``MPI_Alltoallv``), multi-process correct.

    Three negotiated uniform collectives — no host reassembly of remote
    data: (1) allgather every rank's splits vector; (2) pad each
    destination block to the global max split and run one compiled
    uniform alltoall; (3) index out each local rank's valid rows.
    """
    import numpy as _np
    mesh, axis = _C._mesh_axis(process_set)
    n = mesh.shape[axis]
    if isinstance(x, (list, tuple)):
        arrs = [_np.asarray(p) for p in x]
    else:
        arrs = list(_C.to_local(_C.as_per_rank(x, process_set)))
    local = len(arrs)
    if splits is None:
        raise ValueError("list-form alltoall requires splits")
    splits = _np.asarray(splits, _np.int32)
    if splits.ndim == 1:
        sp_local = _np.broadcast_to(splits, (local, n)).copy()
    else:
        sp_local = splits.reshape(local, n).copy()
    for a, sp in zip(arrs, sp_local):
        if a.shape[0] != int(sp.sum()):
            raise ValueError(
                f"splits {sp.tolist()} must sum to rows ({a.shape[0]})")
    # (1) everyone learns the full [n, n] send matrix.
    S = _C.to_numpy(allgather(_C.from_local(sp_local, process_set),
                              process_set=process_set))
    S = S.reshape(n, n).astype(int)
    maxs = max(1, int(S.max()))
    # (2) pad each destination block to maxs rows; one uniform alltoall.
    rest = arrs[0].shape[1:]
    padded = _np.zeros((local, n * maxs) + rest, arrs[0].dtype)
    for i, (a, sp) in enumerate(zip(arrs, sp_local)):
        off = 0
        for j, s in enumerate(sp):
            padded[i, j * maxs:j * maxs + s] = a[off:off + s]
            off += s
    out = alltoall(_C.from_local(padded, process_set),
                   process_set=process_set)          # per-rank [n*maxs,*rest]
    recv = _C.to_local(out).reshape((local, n * maxs) + rest)
    # (3) slice valid rows per local rank: rank r receives S[i][r] rows
    # from source i, stored at block offset i*maxs.
    first = _rank_offset(mesh, axis, process_set)
    results = []
    for k in range(local):
        r = first + k
        idx = _np.concatenate([
            _np.arange(i * maxs, i * maxs + S[i][r]) for i in range(n)
        ]) if S[:, r].sum() else _np.zeros((0,), _np.int64)
        results.append(recv[k][idx])
    return results


def _rank_offset(mesh, axis: str, process_set=None) -> int:
    """Global index of this process's first rank in the group."""
    import jax as _jax
    if _jax.process_count() == 1:
        return 0
    me = _jax.process_index()
    for i, d in enumerate(mesh.devices.flat):
        if d.process_index == me:
            return i
    return 0


def reducescatter(x: Any, op: ReduceOp = Sum, process_set=None) -> Any:
    """Reduce then scatter dim-0 slices across ranks."""
    payload = _C.as_per_rank(x, process_set)
    return _sync_via_engine_or_direct(
        lambda: _C.reducescatter(payload, op, process_set=process_set),
        "reducescatter", payload, op=op, process_set=process_set)


def barrier(process_set=None) -> None:
    """Block until all ranks arrive († ``hvd.barrier``)."""
    import numpy as _np
    import jax as _jax
    state = global_state()
    if state.initialized and state.engine is not None \
            and state.engine.distributed:
        n = process_set.size() if process_set is not None else size()
        if process_set is not None:
            me = _jax.process_index()
            my_rows = sum(1 for d in process_set.mesh.devices.flat
                          if d.process_index == me)
            if my_rows == 0:
                return  # this process owns no ranks in the set
        else:
            my_rows = local_size()
        ones = _C.from_local(
            _np.ones((my_rows, ), _np.int32)[:, None], process_set)
        entry = TensorTableEntry(
            name=_auto_name("barrier", None), verb="allreduce",
            payload=ones, op=Sum, process_set=process_set)
        result = state.engine.enqueue(entry, urgent=True).wait()
        total = int(_C.to_numpy(result)[0])
        if total != n:
            raise RuntimeError(f"barrier allreduce returned {total} != {n}")
        return
    _C.barrier(process_set)


# ---------------------------------------------------------------------------
# Async verbs († horovod/torch *_async_ + synchronize/poll)
# ---------------------------------------------------------------------------

def allreduce_async(x: Any, op: ReduceOp = Average, *,
                    name: Optional[str] = None,
                    prescale_factor: float = 1.0,
                    postscale_factor: float = 1.0,
                    compression=None,
                    process_set=None) -> Handle:
    """Enqueue an allreduce; returns a :class:`Handle` immediately.

    Entries enqueued within one engine cycle fuse into a single compiled
    collective (the fusion-buffer path) — this is the hot call
    ``DistributedOptimizer`` gradient hooks use.  Same-``compression``
    entries fuse together; the wire mode applies to the whole fused
    buffer (see :mod:`horovod_tpu.ops.reduction`).
    """
    payload = _C.as_per_rank(x, process_set)
    mode = _resolve_entry_precision(compression, payload, op, process_set)
    entry = TensorTableEntry(
        name=_auto_name("allreduce", name), verb="allreduce",
        payload=payload, op=op,
        prescale=prescale_factor, postscale=postscale_factor,
        precision=mode,
        schedule=_resolve_entry_schedule(payload, op, process_set, mode),
        process_set=process_set)
    return _engine().enqueue(entry)


def allgather_async(x: Any, *, name: Optional[str] = None,
                    process_set=None) -> Handle:
    if isinstance(x, (list, tuple)):
        raise TypeError(
            "ragged (Allgatherv) input is synchronous-only — it sequences "
            "multiple negotiated collectives; call hvd.allgather(pieces)")
    entry = TensorTableEntry(
        name=_auto_name("allgather", name), verb="allgather",
        payload=_C.as_per_rank(x, process_set), process_set=process_set)
    return _engine().enqueue(entry)


def broadcast_async(x: Any, root_rank: int, *, name: Optional[str] = None,
                    process_set=None) -> Handle:
    entry = TensorTableEntry(
        name=_auto_name("broadcast", name), verb="broadcast",
        payload=_C.as_per_rank(x, process_set), root_rank=root_rank,
        process_set=process_set)
    return _engine().enqueue(entry)


def alltoall_async(x: Any, splits: Optional[Sequence[int]] = None, *,
                   name: Optional[str] = None, process_set=None) -> Handle:
    entry = TensorTableEntry(
        name=_auto_name("alltoall", name), verb="alltoall",
        payload=_C.as_per_rank(x, process_set), splits=splits,
        process_set=process_set)
    return _engine().enqueue(entry)


def grouped_allreduce_async(xs: Sequence[Any], op: ReduceOp = Average, *,
                            name: Optional[str] = None,
                            prescale_factor: float = 1.0,
                            postscale_factor: float = 1.0,
                            compression=None,
                            process_set=None) -> list[Handle]:
    """Enqueue several allreduces at once († ``hvd.grouped_allreduce_async``,
    v0.21).  The entries share one engine cycle, so they fuse into a single
    compiled collective (subject to the fusion threshold)."""
    base = _auto_name("grouped", name)
    handles = []
    eng = _engine()
    for i, x in enumerate(xs):
        payload = _C.as_per_rank(x, process_set)
        mode = _resolve_entry_precision(compression, payload, op,
                                        process_set)
        entry = TensorTableEntry(
            name=f"{base}.{i}", verb="allreduce",
            payload=payload, op=op,
            prescale=prescale_factor, postscale=postscale_factor,
            precision=mode,
            schedule=_resolve_entry_schedule(payload, op, process_set,
                                             mode),
            process_set=process_set)
        handles.append(eng.enqueue(entry))
    return handles


def grouped_allreduce_sync(xs: Sequence[Any], op: ReduceOp = Average,
                           **kw) -> list:
    """† ``hvd.grouped_allreduce``: fused sync variant."""
    handles = grouped_allreduce_async(xs, op, **kw)
    if handles:
        _engine().nudge()
    return [h.wait() for h in handles]


def reducescatter_async(x: Any, op: ReduceOp = Sum, *,
                        name: Optional[str] = None, process_set=None) -> Handle:
    entry = TensorTableEntry(
        name=_auto_name("reducescatter", name), verb="reducescatter",
        payload=_C.as_per_rank(x, process_set), op=op, process_set=process_set)
    return _engine().enqueue(entry)


def synchronize(handle: Handle) -> Any:
    """Block until an async collective completes; return its output
    († ``hvd.synchronize`` / ``HandleManager::ReleaseHandle``).

    Nudges the engine for an immediate cycle so the blocking caller does not
    wait out the cycle time.
    """
    if not handle.poll():
        _engine().nudge()
    return handle.wait()


def poll(handle: Handle) -> bool:
    """True once the async collective has completed († ``hvd.poll``)."""
    return handle.poll()


# ---------------------------------------------------------------------------
# Pytree conveniences († broadcast_parameters / broadcast_object)
# ---------------------------------------------------------------------------

def _root_process_of_rank(root_rank: int) -> int:
    state = global_state()
    return state.devices[root_rank].process_index


def broadcast_parameters(params: Any, root_rank: int = 0) -> Any:
    """Broadcast a pytree of host/device arrays from root; result replicated.

    † ``horovod/torch/__init__.py broadcast_parameters`` — the step-0 weight
    sync.  Single-process: one copy of the values exists, so this re-places
    them replicated on the mesh.  Multi-process: the process owning
    ``root_rank``'s device is the source and every host receives its values
    (via the coordination-service broadcast), so diverged initializations
    cannot leak in.
    """
    import jax
    import numpy as np
    from jax.sharding import NamedSharding, PartitionSpec as P
    state = global_state()
    if not state.initialized:
        raise NotInitializedError()
    if jax.process_count() > 1:
        # Per-leaf negotiated broadcast verb, not
        # multihost_utils.broadcast_one_to_all: that issues its own
        # cross-process computation from the calling thread, unordered
        # against the engine's negotiated collectives.
        params = jax.tree.map(
            lambda a: _C.to_numpy(broadcast(
                _C.replicate_local(np.asarray(a)), root_rank)),
            params)
    sharding = NamedSharding(state.mesh, P())
    return jax.tree.map(
        lambda a: jax.device_put(np.asarray(a), sharding), params)


def broadcast_object(obj: Any, root_rank: int = 0) -> Any:
    """Pickle-broadcast an arbitrary object from root
    († ``hvd.broadcast_object``).

    Multi-process: two-phase broadcast (length, then padded pickle buffer)
    riding the negotiated broadcast verb, since buffer shapes must agree on
    every host; non-source hosts contribute zero-filled placeholders.
    (``multihost_utils.broadcast_one_to_all`` is deliberately not used: it
    issues its own cross-process computation from the calling thread,
    unordered against the engine's negotiated collectives.)
    """
    import jax
    if jax.process_count() > 1:
        import pickle
        import numpy as np
        src = _root_process_of_rank(root_rank) == jax.process_index()
        payload = (np.frombuffer(pickle.dumps(obj), dtype=np.uint8)
                   if src else np.zeros((0,), np.uint8))
        length = int(np.asarray(_C.to_numpy(broadcast(
            _C.replicate_local(np.zeros((1,), np.int64) + payload.size),
            root_rank)))[0])
        buf = np.zeros((length,), np.uint8)
        if src:
            buf[:] = payload
        buf = np.asarray(_C.to_numpy(broadcast(
            _C.replicate_local(buf), root_rank)))
        return pickle.loads(bytes(buf))
    return obj


def allgather_object(objs: Sequence[Any], process_set=None) -> list:
    """Gather one picklable object per rank († ``hvd.allgather_object``).

    Single-controller semantics: the caller *is* every rank, so it must pass
    the per-rank sequence explicitly (length == set size); the gathered
    result is that list.  Anything else is rejected rather than guessed at.
    """
    n = process_set.size() if process_set is not None else size()
    if not isinstance(objs, (list, tuple)) or len(objs) != n:
        raise ValueError(
            f"allgather_object expects one object per rank "
            f"(a sequence of length {n}); got {type(objs).__name__}"
            + (f" of length {len(objs)}" if isinstance(objs, (list, tuple))
               else ""))
    return list(objs)


# ---------------------------------------------------------------------------
# Process sets
# ---------------------------------------------------------------------------

def add_process_set(ranks: Sequence[int]):
    """Create a subgroup usable as ``process_set=`` on any verb
    († ``hvd.add_process_set``, v0.23)."""
    state = global_state()
    if not state.initialized:
        raise NotInitializedError()
    return state.process_set_table.add(ranks)


def remove_process_set(ps) -> None:
    state = global_state()
    if not state.initialized:
        raise NotInitializedError()
    state.process_set_table.remove(ps)


def global_process_set():
    state = global_state()
    if not state.initialized:
        raise NotInitializedError()
    return state.process_set_table.global_set


# ---------------------------------------------------------------------------
# join() — uneven-input termination
# ---------------------------------------------------------------------------

def join(timeout: Optional[float] = None) -> int:
    """Signal this rank has no more input († ``hvd.join()``,
    ``RequestType::JOIN``).  Returns the last rank to join.

    Multi-process mode: the joined rank keeps participating in other ranks'
    negotiated collectives as zero tensors until every rank joins — uneven
    per-rank input sizes terminate cleanly instead of deadlocking.  As in
    the reference, ``Average`` divides by the full world size including
    joined (zero-contributing) ranks.

    Single-controller mode drains outstanding work (one process holds every
    rank's data, so inputs cannot be uneven across ranks) and returns
    ``size()-1``.
    """
    state = global_state()
    if not state.initialized or state.engine is None:
        raise NotInitializedError()
    if state.engine.distributed:
        return state.engine.join(timeout=timeout)
    barrier()
    return size() - 1


# ---------------------------------------------------------------------------
# Telemetry (horovod_tpu.obs; beyond the reference, whose surface stops at
# the timeline).
# ---------------------------------------------------------------------------

def metrics(fmt: str = "dict"):
    """Snapshot of the process-wide metrics registry.

    Every runtime layer (collective engine, serving, elastic, autotune)
    reports counters/gauges/histograms into :data:`horovod_tpu.obs.REGISTRY`;
    this returns them as

    - ``fmt="dict"`` — plain-data snapshot (list of metric families);
    - ``fmt="json"`` — the ``/metrics.json`` endpoint's JSON string;
    - ``fmt="prometheus"`` — Prometheus text exposition, byte-identical
      to ``GET :$HVDTPU_METRICS_PORT/metrics``.

    Works before/without ``init()`` — the registry is process-wide, not
    part of engine state.
    """
    snap = obs.REGISTRY.snapshot()
    return _format_snapshot(snap, fmt)


def cluster_metrics(fmt: str = "dict"):
    """Job-level merged view of every rank's metrics registry.

    Each rank periodically publishes its registry snapshot to the job's
    KV control plane (armed by ``hvd.init()`` in multi-process mode);
    this fetches and merges them: counters keep per-rank ``rank``-labeled
    series plus a cluster-summed series, gauges stay per-rank, histogram
    buckets merge when the edges agree.  Formats as :func:`metrics`.
    The same view is served over HTTP at ``/cluster`` (Prometheus) and
    ``/cluster.json`` next to the per-process ``/metrics``.

    Works on any rank with KV access (rank 0 is the canonical scrape
    target); single-process jobs return the local registry labeled
    ``rank="0"`` — the world-size-1 cluster, no special case needed.
    """
    from .obs import aggregate
    return _format_snapshot(aggregate.cluster_snapshot(), fmt)


def flight_record(path: Optional[str] = None) -> Optional[str]:
    """Write a flight-recorder postmortem bundle NOW and return its path
    (:mod:`horovod_tpu.obs.flightrec`).

    The bundle holds the per-rank ring of recent events (trace spans,
    collective dispatches, stall warnings, elastic interrupts), an
    atomic metrics-registry snapshot, the process identity, and — in
    multi-process mode — the coordinator's current straggler attribution
    (missing-rank list + bitmap per stalled tensor).  The same bundle is
    auto-dumped on stall-shutdown / round-abort / elastic failure /
    crash when ``HOROVOD_TPU_FLIGHT_RECORDER_DIR`` (or
    ``Config.flight_recorder_dir``) is set; this is the on-demand form
    ("grab me a black box of the last N events") and works before/without
    ``init()``.  ``path=None`` names a file under the armed directory
    (or the CWD).  Returns None only if the dump itself failed (logged,
    never raised)."""
    state = global_state()
    stall = None
    if state.engine is not None:
        stall = getattr(state.engine._negotiator, "last_stall_info", None)
    return obs.flightrec.RECORDER.dump(path, reason="manual", stall=stall)


def _format_snapshot(snap, fmt: str):
    if fmt == "dict":
        return snap
    if fmt == "json":
        return obs.export.to_json(snap)
    if fmt == "prometheus":
        return obs.export.to_prometheus(snap)
    raise ValueError(
        f"fmt must be 'dict', 'json' or 'prometheus', got {fmt!r}")


# ---------------------------------------------------------------------------
# Runtime timeline control († hvd.start_timeline / stop_timeline, v0.21)
# ---------------------------------------------------------------------------

def start_timeline(file_path: str, mark_cycles: bool = False) -> None:
    """Begin writing the Chrome-trace timeline at runtime
    († ``hvd.start_timeline``).  Replaces any active timeline."""
    import jax
    from .utils.timeline import Timeline
    state = global_state()
    if not state.initialized:
        raise NotInitializedError()
    old = state.timeline
    # rank stamps the clock_sync merge anchor, same as init()'s timeline,
    # so runtime-started per-rank files merge onto correct lanes too.
    state.timeline = Timeline(file_path, mark_cycles=mark_cycles,
                              rank=jax.process_index())
    if old is not None:
        old.close()


def stop_timeline() -> None:
    """Stop and flush the active timeline († ``hvd.stop_timeline``)."""
    state = global_state()
    if not state.initialized:
        raise NotInitializedError()
    old, state.timeline = state.timeline, None
    if old is not None:
        old.close()


# ---------------------------------------------------------------------------
# Capability queries († basics.py mpi_built/nccl_built/gloo_built/...).
# The reference answers "which backends were compiled in"; the TPU-native
# equivalents answer the questions users actually asked of them: is there a
# compiled data plane, a native control plane, a multi-host launcher.
# ---------------------------------------------------------------------------

def xla_built() -> bool:
    """Always True: XLA is the data plane (≙ † ``nccl_built``)."""
    return True


def native_built() -> bool:
    """True when the C++ control-plane extension loaded
    (≙ † ``gloo_built``: the rendezvous/controller transport)."""
    try:
        from . import _native
        _native.load()
        return True
    except Exception:
        return False


def mpi_built() -> bool:
    """False: MPI has no role on TPU — the coordination service + XLA
    collectives replace it († ``mpi_built``)."""
    return False


def mpi_enabled() -> bool:
    return False


def gloo_built() -> bool:
    """The native KV/controller transport fills Gloo's role."""
    return native_built()


def gloo_enabled() -> bool:
    """† ``gloo_enabled``: the native transport is the only (and therefore
    always-enabled) control plane when built."""
    return gloo_built()


def is_homogeneous() -> bool:
    """True when every process drives the same number of devices
    († ``horovod_is_homogeneous``: equal local sizes on all hosts —
    heterogeneous jobs disable some fusion fast paths upstream).

    Single-controller approximation: derived as ``size == local_size *
    cross_size`` from THIS process's view rather than comparing every
    rank's local size over the control plane (the reference gathers all
    local sizes).  A heterogeneous job whose local sizes happen to
    multiply out (e.g. 1,2,3 seen from a 2-slot host) reports True; the
    launcher's slot assignment produces equal slots per host, so this
    arises only with hand-built rank maps."""
    from .context import cross_size, local_size, size
    return size() == local_size() * cross_size()


def nccl_built() -> int:
    """XLA's ICI/DCN collectives fill NCCL's role (int like the reference,
    which returns the NCCL version or 0)."""
    return 1 if xla_built() else 0


def ddl_built() -> bool:
    return False


def ccl_built() -> bool:
    return False


def cuda_built() -> bool:
    return False


def rocm_built() -> bool:
    return False


def mpi_threads_supported() -> bool:
    """The engine's background thread model never requires
    MPI_THREAD_MULTIPLE; collective submission is thread-safe
    (≙ † ``mpi_threads_supported``)."""
    return True


# Optimizer/elastic API re-export (imported lazily so collective-only users
# don't pay the optax import at package load).
def __getattr__(name: str):
    if name in ("DistributedOptimizer", "DistributedGradientTransformation",
                "distributed_gradients"):
        from .optim import distributed
        return getattr(distributed, name)
    if name == "ZeroDistributedOptimizer":
        # ZeRO-1 sharded optimizer: rs chain stops at the shard, inner
        # optax state lives on the 1/n slice, one param allgather/step.
        from .optim import zero
        return zero.ZeroDistributedOptimizer
    if name == "bucketed_distributed_gradients":
        from .ops.sched import buckets
        return buckets.bucketed_distributed_gradients
    if name == "elastic":
        import importlib
        return importlib.import_module("horovod_tpu.elastic")
    if name == "sched":
        # ops/sched: the collective schedule IR (hvd.sched.overlap_allreduce
        # / matmul_reducescatter are the in-jit entry points).
        import importlib
        return importlib.import_module("horovod_tpu.ops.sched")
    if name == "run_func":
        # † ``horovod.run`` — programmatic function launcher.
        from .runner.api import run_func
        return run_func
    raise AttributeError(f"module 'horovod_tpu' has no attribute {name!r}")
