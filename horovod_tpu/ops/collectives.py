"""Synchronous collective verbs lowered to XLA collectives on a persistent mesh.

Reference parity: the five verbs of † ``horovod/common/ops/collective_operations.cc``
(``AllreduceOp/AllgatherOp/BroadcastOp/AlltoallOp/JoinOp``) plus
reduce-scatter.  Reduction kinds mirror † ``horovod/common/common.h``
``ReduceOp {AVERAGE, SUM, ADASUM, MIN, MAX, PRODUCT}``.

Data model (single-controller SPMD)
-----------------------------------
A *per-rank tensor* — what a Horovod process would pass from its own memory —
is represented as one global ``jax.Array`` of shape ``[num_ranks, *shape]``
sharded over the mesh's data-parallel axis on dim 0, so rank *i*'s tensor
lives on device *i*.  Collectives consume per-rank tensors and produce either
a replicated result (allreduce/allgather/broadcast) or a new per-rank tensor
(alltoall/reducescatter).  Helpers :func:`per_rank` / :func:`per_rank_from_fn`
build these from host data; :func:`to_numpy` reads results back.

Dispatch cache
--------------
Each (verb, reduce-op, dtype, shape, static-params) signature compiles once
via ``jax.jit`` and is memoized here.  This table is the moral equivalent of
the reference's response cache († ``response_cache.cc``): in steady-state
training every step re-issues identical signatures and skips all setup.
"""

from __future__ import annotations

import enum
import functools
import threading
from collections import OrderedDict
from typing import Any, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax import shard_map
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from .. import context as ctx_mod


class ReduceOp(enum.Enum):
    """† ``horovod/common/common.h`` ReduceOp enum."""
    AVERAGE = "average"
    SUM = "sum"
    ADASUM = "adasum"
    MIN = "min"
    MAX = "max"
    PRODUCT = "product"


# Module-level aliases matching ``hvd.Average`` etc.
Average = ReduceOp.AVERAGE
Sum = ReduceOp.SUM
Adasum = ReduceOp.ADASUM
Min = ReduceOp.MIN
Max = ReduceOp.MAX
Product = ReduceOp.PRODUCT


from ..obs import REGISTRY as _obs

_m_cache_hits = _obs.counter(
    "hvd_dispatch_cache_hits_total",
    "compiled-collective dispatch cache hits (response-cache analogue)")
_m_cache_misses = _obs.counter(
    "hvd_dispatch_cache_misses_total",
    "compiled-collective dispatch cache misses (each one is an XLA build)")


class _DispatchCache:
    """LRU table of compiled collective programs (response-cache analogue)."""

    def __init__(self) -> None:
        self._lock = threading.Lock()
        self._table: OrderedDict[tuple, Any] = OrderedDict()
        self.hits = 0
        self.misses = 0

    def get_or_build(self, key: tuple, builder) -> Any:
        with self._lock:
            fn = self._table.get(key)
            if fn is not None:
                self._table.move_to_end(key)
                self.hits += 1
                _m_cache_hits.inc()
                return fn
            self.misses += 1
            _m_cache_misses.inc()
        fn = builder()
        with self._lock:
            self._table[key] = fn
            cap = ctx_mod.global_state().config.cache_capacity
            while len(self._table) > cap:
                self._table.popitem(last=False)
        return fn


_cache = _DispatchCache()


def dispatch_cache_stats() -> dict:
    return {"hits": _cache.hits, "misses": _cache.misses}


# ---------------------------------------------------------------------------
# Mesh / sharding helpers
# ---------------------------------------------------------------------------

def _mesh_axis(process_set=None) -> tuple[Mesh, str]:
    if process_set is not None:
        return process_set.mesh, process_set.axis_name
    state = ctx_mod.global_state()
    if not state.initialized:
        raise ctx_mod.NotInitializedError()
    cfg = state.config
    assert state.mesh is not None
    return state.mesh, cfg.dp_axis_name


def _rank_sharding(mesh: Mesh, axis: str) -> NamedSharding:
    return NamedSharding(mesh, P(axis))


def _replicated(mesh: Mesh) -> NamedSharding:
    return NamedSharding(mesh, P())


def per_rank(values: Sequence[Any], process_set=None) -> jax.Array:
    """Build a per-rank tensor from one host array per rank.

    Equivalent to each Horovod process holding its own tensor before a
    collective.  All values must share shape and dtype (the reference's
    controller enforces the same †``Controller::ComputeResponseList`` shape
    checks and errors otherwise).
    """
    mesh, axis = _mesh_axis(process_set)
    n = mesh.shape[axis]
    if len(values) != n:
        raise ValueError(f"expected {n} per-rank values, got {len(values)}")
    arrs = [np.asarray(v) for v in values]
    shapes = {a.shape for a in arrs}
    dtypes = {a.dtype for a in arrs}
    if len(shapes) != 1 or len(dtypes) != 1:
        raise ValueError(
            "mismatched shapes/dtypes across ranks: "
            f"{sorted(map(str, shapes))} / {sorted(map(str, dtypes))} "
            "(reference parity: coordinator shape-consistency check)")
    stacked = np.stack(arrs)
    return jax.device_put(stacked, _rank_sharding(mesh, axis))


def per_rank_from_fn(fn, process_set=None) -> jax.Array:
    """``per_rank([fn(0), fn(1), ...])`` — the common test-fixture shape."""
    mesh, axis = _mesh_axis(process_set)
    return per_rank([fn(i) for i in range(mesh.shape[axis])],
                    process_set=process_set)


def as_per_rank(x: Any, process_set=None) -> jax.Array:
    """Coerce ``x`` to a per-rank tensor.

    Already-sharded arrays pass through; a host array of shape
    ``[num_ranks, ...]`` is scattered rank-major (Horovod semantics: row *i*
    is rank *i*'s local tensor).
    """
    mesh, axis = _mesh_axis(process_set)
    n = mesh.shape[axis]
    if isinstance(x, jax.Array) and x.ndim >= 1 and x.shape[0] == n:
        if x.sharding == _rank_sharding(mesh, axis):
            return x
    x = jnp.asarray(x)
    if x.ndim < 1 or x.shape[0] != n:
        raise ValueError(
            f"per-rank tensor must have leading dim {n}, got shape {x.shape}")
    return jax.device_put(x, _rank_sharding(mesh, axis))


def to_numpy(x: jax.Array) -> np.ndarray:
    """Fetch a (replicated or per-rank) result to host memory."""
    return np.asarray(jax.device_get(x))


def from_local(x: Any, process_set=None) -> jax.Array:
    """Build a per-rank tensor from this process's local shards (multi-host).

    ``x``: host array of shape ``[local_ranks, *shape]`` — one row per device
    this process drives, in mesh order.  Every process calls this with its
    own rows and receives the same global ``[size, *shape]`` per-rank array
    (the Horovod process-local-tensor model mapped onto a global array).
    Single-process: equivalent to :func:`per_rank`.
    """
    mesh, axis = _mesh_axis(process_set)
    x = np.asarray(x)
    sharding = _rank_sharding(mesh, axis)
    if jax.process_count() == 1:
        return per_rank(list(x), process_set)
    me = jax.process_index()
    local_devs = [d for d in mesh.devices.flat if d.process_index == me]
    if x.shape[0] != len(local_devs):
        raise ValueError(
            f"expected {len(local_devs)} local rows, got {x.shape[0]}")
    n = mesh.shape[axis]
    shards = [jax.device_put(x[i:i + 1], d)
              for i, d in enumerate(local_devs)]
    return jax.make_array_from_single_device_arrays(
        (n,) + x.shape[1:], sharding, shards)


def replicate_local(value: Any, process_set=None) -> jax.Array:
    """Per-rank tensor where every rank this process drives holds the same
    value (the single-process torch-bridge model: one process's tensor
    stands for each of its devices).

    One host→device transfer regardless of ``local_size``: the value is
    staged to the first local device, then replicated device-to-device —
    never ``local_size`` host-side copies of the payload.
    """
    mesh, axis = _mesh_axis(process_set)
    arr = np.asarray(value)
    n = mesh.shape[axis]
    me = jax.process_index()
    local_devs = [d for d in mesh.devices.flat if d.process_index == me]
    first = jax.device_put(arr[None], local_devs[0])
    shards = [first] + [jax.device_put(first, d) for d in local_devs[1:]]
    return jax.make_array_from_single_device_arrays(
        (n,) + arr.shape, _rank_sharding(mesh, axis), shards)


def to_local(x: jax.Array) -> np.ndarray:
    """Rows of a per-rank result owned by this process's devices; replicated
    results return the single full copy (every local shard is identical)."""
    if jax.process_count() == 1 or x.sharding.is_fully_replicated:
        # Replicated: each addressable shard holds the full array — return
        # one copy, not one per local device.
        return to_numpy(x)
    shards = list(x.addressable_shards)
    shards.sort(key=lambda s: s.index)
    return np.concatenate([np.asarray(s.data) for s in shards], axis=0)


# ---------------------------------------------------------------------------
# Compiled program builders
# ---------------------------------------------------------------------------

def _build_allreduce(mesh: Mesh, axis: str, op: ReduceOp,
                     prescale: float, postscale: float):
    n = mesh.shape[axis]

    def kernel(v):  # v: per-device shard [1, *shape]
        if prescale != 1.0:
            v = v * jnp.asarray(prescale, v.dtype)
        if op in (ReduceOp.SUM, ReduceOp.AVERAGE):
            out = lax.psum(v, axis)
            if op is ReduceOp.AVERAGE:
                if jnp.issubdtype(out.dtype, jnp.integer):
                    out = out // n
                else:
                    out = out / n
        elif op is ReduceOp.MIN:
            out = lax.pmin(v, axis)
        elif op is ReduceOp.MAX:
            out = lax.pmax(v, axis)
        elif op is ReduceOp.PRODUCT:
            gathered = lax.all_gather(v, axis, axis=0, tiled=True)
            out = jnp.prod(gathered, axis=0, keepdims=True)
        else:  # ADASUM handled at a higher layer (ops/adasum.py)
            raise NotImplementedError(f"reduce op {op}")
        if postscale != 1.0:
            out = out * jnp.asarray(postscale, out.dtype)
        return out

    fn = shard_map(kernel, mesh=mesh, in_specs=P(axis), out_specs=P(),
                   check_vma=False)
    return jax.jit(lambda x: fn(x)[0])


def _build_grouped_allreduce(mesh: Mesh, axis: str, op: ReduceOp,
                             numels: tuple[int, ...],
                             shapes: tuple[tuple[int, ...], ...],
                             prescale: float, postscale: float,
                             hier: Optional[tuple[int, int]] = None,
                             mode: str = "fp32", block: int = 512,
                             dtype=None):
    """One fused program for many tensors: flatten → concat → reduce → split.

    This *is* the fusion buffer († ``fusion_buffer_manager.cc``): instead of
    memcpying into a 64 MB scratch allocation, the flatten/concat lives inside
    the compiled program where XLA fuses it with the collective, and HBM
    layout is the compiler's problem.  With ``hier`` set, the fused buffer
    rides the two-level path; with ``mode`` != fp32 it rides the
    wire-precision path (quantization applies to the whole fused buffer,
    so per-block scale overhead amortizes across the group's tensors).
    """
    if mode != "fp32":
        from . import reduction as R
        total = int(sum(numels))
        reduce_one = R.build_allreduce(
            mesh, axis, op, mode, (total,), dtype, prescale, postscale,
            block)
    elif hier is not None:
        reduce_one = _build_hier_allreduce(
            ctx_mod.global_state(), op, hier[0], hier[1], prescale, postscale)
    else:
        reduce_one = _build_allreduce(mesh, axis, op, prescale, postscale)

    def fused(xs):
        n = xs[0].shape[0]
        flat = jnp.concatenate([x.reshape(n, -1) for x in xs], axis=1)
        out = reduce_one(flat)
        outs = []
        offset = 0
        for numel, shape in zip(numels, shapes):
            outs.append(lax.dynamic_slice_in_dim(
                out, offset, numel, axis=0).reshape(shape))
            offset += numel
        return outs

    return jax.jit(fused)


def _build_allgather(mesh: Mesh, axis: str):
    fn = shard_map(
        lambda v: lax.all_gather(v[0], axis, axis=0, tiled=True),
        mesh=mesh, in_specs=P(axis), out_specs=P(), check_vma=False)
    return jax.jit(fn)


def _build_broadcast(mesh: Mesh, axis: str, root: int):
    def kernel(v):
        idx = lax.axis_index(axis)
        masked = jnp.where(idx == root, v, jnp.zeros_like(v))
        # psum of the root-masked value is a real broadcast collective and
        # works for every dtype incl. bool/int.
        if v.dtype == jnp.bool_:
            return lax.psum(masked.astype(jnp.int8), axis).astype(jnp.bool_)
        return lax.psum(masked, axis)
    fn = shard_map(kernel, mesh=mesh, in_specs=P(axis), out_specs=P(),
                   check_vma=False)
    return jax.jit(lambda x: fn(x)[0])


def _build_alltoall(mesh: Mesh, axis: str, rows_per_dest: int):
    n = mesh.shape[axis]

    def kernel(v):  # [1, n*rows_per_dest, *s]
        x = v[0].reshape((n, rows_per_dest) + v.shape[2:])
        out = lax.all_to_all(x, axis, split_axis=0, concat_axis=0)
        return out.reshape((n * rows_per_dest,) + v.shape[2:])[None]

    fn = shard_map(kernel, mesh=mesh, in_specs=P(axis), out_specs=P(axis),
                   check_vma=False)
    return jax.jit(fn)


def _build_reducescatter(mesh: Mesh, axis: str, op: ReduceOp):
    n = mesh.shape[axis]

    def kernel(v):  # [1, n*k, *s]
        if op in (ReduceOp.SUM, ReduceOp.AVERAGE):
            out = lax.psum_scatter(v[0], axis, scatter_dimension=0, tiled=True)
            if op is ReduceOp.AVERAGE:
                if jnp.issubdtype(out.dtype, jnp.integer):
                    out = out // n
                else:
                    out = out / n
        else:
            raise NotImplementedError(
                f"reducescatter supports SUM/AVERAGE, got {op}")
        return out[None]

    fn = shard_map(kernel, mesh=mesh, in_specs=P(axis), out_specs=P(axis),
                   check_vma=False)
    return jax.jit(fn)


def _detect_local_size(state) -> Optional[int]:
    """Fast-tier (ICI) group size from topology, not from a knob.

    Preference order:

    1. **Slice boundaries** — on a multislice TPU pod every jax device
       carries a ``slice_index``; uniform per-slice device counts over
       more than one slice ARE the ICI/DCN split (intra-slice links are
       ICI, inter-slice is DCN).
    2. **Per-host rank layout** — the runner exports
       ``HVDTPU_LOCAL_SIZE`` per worker; ranks on one host share a host
       interconnect that beats the network between hosts.
    3. **This process's device count** — the single-controller analogue
       of "local ranks per node" (the historical default).
    """
    devices = list(getattr(state, "devices", ()) or ())
    slices: dict = {}
    for d in devices:
        si = getattr(d, "slice_index", None)
        if si is None:
            slices = {}
            break
        slices[si] = slices.get(si, 0) + 1
    if len(slices) > 1:
        counts = set(slices.values())
        if len(counts) == 1:
            return counts.pop()
    cfg = state.config
    if cfg.local_size_env:
        return int(cfg.local_size_env)
    return getattr(state, "local_size", None)


def _hier_split(process_set) -> Optional[tuple[int, int]]:
    """(n_cross, n_local) when two-level allreduce is enabled and valid
    († HOROVOD_HIERARCHICAL_ALLREDUCE gate in nccl_operations.cc).

    ``hierarchical_local_size`` is the explicit override; otherwise the
    split comes from :func:`_detect_local_size` (slice boundaries, then
    the runner's per-host layout).  Invalid splits (indivisible world,
    one-rank or whole-world "tier") fall back to the flat path — same on
    every rank, since the inputs are synchronized config + topology."""
    if process_set is not None:
        return None  # subgroup topology unknown; flat path
    state = ctx_mod.global_state()
    cfg = state.config
    if not cfg.hierarchical_allreduce:
        return None
    n = state.size
    n_local = cfg.hierarchical_local_size or _detect_local_size(state)
    if not n_local or n_local <= 1 or n_local >= n or n % n_local:
        return None
    return (n // n_local, n_local)


def _build_hier_allreduce(state, op: ReduceOp, n_cross: int, n_local: int,
                          prescale: float, postscale: float):
    from . import hierarchical as H
    devices = np.array(list(state.devices)).reshape(n_cross, n_local)
    mesh2 = Mesh(devices, ("hvd_cross", "hvd_local"))

    def kernel(v):  # [1, *shape] per device
        x = v[0]
        if prescale != 1.0:
            x = x * jnp.asarray(prescale, x.dtype)
        out = H.hierarchical_allreduce_local(
            x, local_axis="hvd_local", cross_axis="hvd_cross",
            average=(op is ReduceOp.AVERAGE))
        if postscale != 1.0:
            out = out * jnp.asarray(postscale, out.dtype)
        return out

    fn = shard_map(kernel, mesh=mesh2,
                   in_specs=P(("hvd_cross", "hvd_local")),
                   out_specs=P(), check_vma=False)
    return jax.jit(fn)


# ---------------------------------------------------------------------------
# Public verbs
# ---------------------------------------------------------------------------

def _sig(mesh: Mesh, axis: str, *extras) -> tuple:
    return (id(mesh), axis) + extras


def _resolve_precision(precision: str, op: ReduceOp, x: jax.Array,
                       n: int) -> str:
    """Engine-default + per-call wire mode -> the mode actually built.

    ``x`` is the per-rank tensor ([n, *shape]); the size floor applies
    to ONE rank's payload, matching the engine's per-entry accounting.
    This is THE canonical resolution convention: the API layer's
    enqueue-time resolution (horovod_tpu._resolve_entry_precision) calls
    here, and dispatch re-resolves through the same function — the two
    must agree byte-for-byte or negotiated metas and compiled programs
    diverge across ranks.
    """
    from . import reduction as R
    cfg = ctx_mod.global_state().config
    nbytes = int(x.size * x.dtype.itemsize) // max(1, n)
    return R.resolve_precision(precision, op, x.dtype, nbytes, cfg, n)


def _resolve_schedule(schedule: str, op: ReduceOp, x: jax.Array, n: int,
                      mode: str) -> str:
    """Engine-default + per-call schedule -> the concrete descriptor
    actually executed ("" = monolithic).  Same canonical-convention rule
    as :func:`_resolve_precision`: enqueue-time and dispatch-time
    resolution share this function so they can never drift apart."""
    from . import sched as S
    cfg = ctx_mod.global_state().config
    nbytes = int(x.size * x.dtype.itemsize) // max(1, n)
    return S.resolve_schedule(schedule, "allreduce", op, x.dtype, nbytes,
                              cfg, n, mode)


def allreduce(x: Any, op: ReduceOp = ReduceOp.AVERAGE, *,
              prescale_factor: float = 1.0, postscale_factor: float = 1.0,
              precision: str = "", schedule: str = "",
              process_set=None) -> jax.Array:
    """Reduce a per-rank tensor across ranks; result replicated.

    † ``EnqueueTensorAllreduce`` / ``MPI_Allreduce`` / ``ncclAllReduce``;
    prescale/postscale as in the reference's allreduce signature.
    ``precision`` selects the wire mode (see :mod:`ops.reduction`);
    empty defers to ``config.wire_precision`` and falls back to fp32
    whenever the mode cannot apply (non-float, non-sum, too small).
    ``schedule`` selects the collective schedule (see :mod:`ops.sched`):
    empty defers to ``config.sched_mode``; the decomposed schedule runs
    the chunked reduce-scatter/allgather pipeline with identical results.
    """
    if op is ReduceOp.ADASUM:
        from . import adasum
        return adasum.adasum_allreduce(x, process_set=process_set)
    mesh, axis = _mesh_axis(process_set)
    x = as_per_rank(x, process_set)
    n = mesh.shape[axis]
    mode = _resolve_precision(precision, op, x, n)
    sched_desc = _resolve_schedule(schedule, op, x, n, mode)
    if sched_desc:
        from .sched import executor as SE
        return SE.execute_allreduce(
            [x], op, descriptor=sched_desc, precision=mode,
            prescale=float(prescale_factor),
            postscale=float(postscale_factor), process_set=process_set)[0]
    if mode != "fp32":
        from . import reduction as R
        cfg = ctx_mod.global_state().config
        block = cfg.quant_block_size
        key = _sig(mesh, axis, "allreduce", op, x.dtype.name, x.shape,
                   mode, block,
                   float(prescale_factor), float(postscale_factor))
        fn = _cache.get_or_build(
            key, lambda: R.build_allreduce(
                mesh, axis, op, mode, x.shape[1:], x.dtype,
                float(prescale_factor), float(postscale_factor), block))
        R.account_wire(mode, int(x.size * x.dtype.itemsize) // n, n, block,
                       itemsize=x.dtype.itemsize)
        return fn(x)
    split = _hier_split(process_set)
    if split is not None and (
            op is ReduceOp.SUM
            or (op is ReduceOp.AVERAGE
                and jnp.issubdtype(x.dtype, jnp.floating))):
        n_cross, n_local = split
        state = ctx_mod.global_state()
        key = _sig(mesh, axis, "hier_allreduce", op, x.dtype.name, x.shape,
                   n_cross, n_local,
                   float(prescale_factor), float(postscale_factor))
        fn = _cache.get_or_build(
            key, lambda: _build_hier_allreduce(
                state, op, n_cross, n_local,
                float(prescale_factor), float(postscale_factor)))
        return fn(x)
    key = _sig(mesh, axis, "allreduce", op, x.dtype.name, x.shape,
               float(prescale_factor), float(postscale_factor))
    fn = _cache.get_or_build(
        key, lambda: _build_allreduce(mesh, axis, op,
                                      float(prescale_factor),
                                      float(postscale_factor)))
    return fn(x)


def grouped_allreduce(xs: Sequence[Any], op: ReduceOp = ReduceOp.AVERAGE, *,
                      prescale_factor: float = 1.0,
                      postscale_factor: float = 1.0,
                      precision: str = "", schedule: str = "",
                      process_set=None) -> list[jax.Array]:
    """Fused allreduce of several tensors in one program/collective.

    † grouped allreduce (v0.21) and the implicit fusion of
    † ``fusion_buffer_manager.cc``.  ``precision`` applies the wire mode
    to the whole fused buffer (the engine fuses same-precision entries
    together, so one quantized program covers the group); ``schedule``
    likewise applies to the fused buffer — the decomposed pipeline chunks
    the concatenated payload, so per-chunk overlap spans tensor
    boundaries.
    """
    if not xs:
        return []
    mesh, axis = _mesh_axis(process_set)
    arrs = [as_per_rank(x, process_set) for x in xs]
    dtypes = {a.dtype for a in arrs}
    if len(dtypes) != 1:
        # Mixed dtypes cannot share one fused buffer; split by dtype.
        out: list[Optional[jax.Array]] = [None] * len(arrs)
        for dt in dtypes:
            idxs = [i for i, a in enumerate(arrs) if a.dtype == dt]
            sub = grouped_allreduce([arrs[i] for i in idxs], op,
                                    prescale_factor=prescale_factor,
                                    postscale_factor=postscale_factor,
                                    precision=precision, schedule=schedule,
                                    process_set=process_set)
            for i, r in zip(idxs, sub):
                out[i] = r
        return out  # type: ignore[return-value]
    shapes = tuple(a.shape[1:] for a in arrs)
    numels = tuple(int(np.prod(s, dtype=np.int64)) if s else 1 for s in shapes)
    n = mesh.shape[axis]
    # The fused buffer is quantized as one payload.  DIRECT callers of
    # this function resolve against the group's total bytes (small
    # tensors sharing a big explicit group can quantize together); the
    # ENGINE path instead resolves per-entry at enqueue — deterministic
    # across ranks — and passes a concrete mode through, so the size
    # floor there gates each tensor individually.
    from . import reduction as R
    cfg = ctx_mod.global_state().config
    total_bytes = int(sum(numels)) * arrs[0].dtype.itemsize
    mode = R.resolve_precision(precision, op, arrs[0].dtype, total_bytes,
                               cfg, n)
    if op in (ReduceOp.SUM, ReduceOp.AVERAGE):
        from . import sched as S
        sched_desc = S.resolve_schedule(schedule, "allreduce", op,
                                        arrs[0].dtype, total_bytes, cfg, n,
                                        mode)
        if sched_desc:
            # Wire accounting happens inside the executor.
            from .sched import executor as SE
            return SE.execute_allreduce(
                arrs, op, descriptor=sched_desc, precision=mode,
                prescale=float(prescale_factor),
                postscale=float(postscale_factor), process_set=process_set)
    block = cfg.quant_block_size
    hier = _hier_split(process_set)
    if hier is not None and (mode != "fp32" or not (
            op is ReduceOp.SUM
            or (op is ReduceOp.AVERAGE
                and jnp.issubdtype(arrs[0].dtype, jnp.floating)))):
        hier = None
    key = _sig(mesh, axis, "grouped_allreduce", op, arrs[0].dtype.name,
               numels, shapes, hier, mode, block,
               float(prescale_factor), float(postscale_factor))
    fn = _cache.get_or_build(
        key, lambda: _build_grouped_allreduce(
            mesh, axis, op, numels, shapes,
            float(prescale_factor), float(postscale_factor), hier=hier,
            mode=mode, block=block, dtype=arrs[0].dtype))
    if mode != "fp32":
        R.account_wire(mode, total_bytes, n, block,
                       itemsize=arrs[0].dtype.itemsize)
    return list(fn(arrs))


def allgather(x: Any, process_set=None) -> jax.Array:
    """Concatenate per-rank tensors along dim 0; result replicated.

    † ``EnqueueTensorAllgather`` / ``MPI_Allgatherv``.  Equal per-rank shapes
    take the compiled all-gather path; ragged first dimensions (the
    ``Allgatherv`` case) are accepted as a list of per-rank host arrays.
    """
    mesh, axis = _mesh_axis(process_set)
    if isinstance(x, (list, tuple)):
        raise TypeError(
            "ragged (Allgatherv) input is handled by horovod_tpu.allgather"
            " — it composes negotiated uniform collectives (pad-to-max + "
            "slice) so it stays correct in multi-process mode")
    x = as_per_rank(x, process_set)
    if x.ndim < 2:
        # scalar-per-rank gather == the per-rank vector itself, replicated
        return jax.device_put(x, _replicated(mesh))
    key = _sig(mesh, axis, "allgather", x.dtype.name, x.shape)
    fn = _cache.get_or_build(key, lambda: _build_allgather(mesh, axis))
    return fn(x)


def broadcast(x: Any, root_rank: int, process_set=None) -> jax.Array:
    """Every rank receives rank ``root_rank``'s tensor; result replicated.

    † ``EnqueueTensorBroadcast`` / ``MPI_Bcast`` / ``ncclBcast``.
    """
    mesh, axis = _mesh_axis(process_set)
    n = mesh.shape[axis]
    if not 0 <= root_rank < n:
        raise ValueError(f"root_rank {root_rank} out of range [0,{n})")
    x = as_per_rank(x, process_set)
    key = _sig(mesh, axis, "broadcast", x.dtype.name, x.shape, root_rank)
    fn = _cache.get_or_build(key,
                             lambda: _build_broadcast(mesh, axis, root_rank))
    return fn(x)


def alltoall(x: Any, splits: Optional[Sequence[int]] = None,
             process_set=None) -> jax.Array:
    """Each rank scatters dim-0 slices of its tensor to all ranks.

    † ``EnqueueTensorAlltoall`` (v0.20+) / ``MPI_Alltoallv``.  With ``splits``
    omitted, rank *i*'s rows are split evenly across ranks.  Non-uniform
    splits follow Horovod's semantics (``splits[j]`` rows from every rank go
    to rank *j*) and return a ragged result as a per-rank list.
    """
    mesh, axis = _mesh_axis(process_set)
    n = mesh.shape[axis]
    x = as_per_rank(x, process_set)
    rows = x.shape[1]
    if splits is None:
        if rows % n:
            raise ValueError(
                f"alltoall rows ({rows}) not divisible by ranks ({n}); "
                "pass explicit splits")
        key = _sig(mesh, axis, "alltoall", x.dtype.name, x.shape)
        fn = _cache.get_or_build(
            key, lambda: _build_alltoall(mesh, axis, rows // n))
        return fn(x)
    raise TypeError(
        "non-uniform (Alltoallv) splits are handled by "
        "horovod_tpu.alltoall — it composes negotiated uniform "
        "collectives (splits exchange + pad-to-max) so it stays correct "
        "in multi-process mode")


def reducescatter(x: Any, op: ReduceOp = ReduceOp.SUM,
                  process_set=None) -> jax.Array:
    """Reduce across ranks, then scatter dim-0 slices: rank *i* keeps slice *i*.

    Beyond the reference's public API of its era (reduce-scatter landed
    upstream later); first-class here because it is the building block of
    ZeRO/FSDP-style sharded optimizers.
    """
    mesh, axis = _mesh_axis(process_set)
    n = mesh.shape[axis]
    x = as_per_rank(x, process_set)
    if x.ndim < 2 or x.shape[1] % n:
        raise ValueError(
            f"reducescatter dim 1 ({x.shape}) must exist and divide {n}")
    key = _sig(mesh, axis, "reducescatter", op, x.dtype.name, x.shape)
    fn = _cache.get_or_build(key,
                             lambda: _build_reducescatter(mesh, axis, op))
    return fn(x)


def barrier(process_set=None) -> None:
    """Block until all ranks reach the barrier († ``hvd.barrier``, v0.23).

    Implemented as a tiny allreduce, same as the reference's fallback; in
    single-controller mode it also drains JAX's async dispatch queue.
    """
    mesh, axis = _mesh_axis(process_set)
    n = mesh.shape[axis]
    ones = per_rank([np.ones((), np.int32)] * n, process_set)
    out = allreduce(ones, ReduceOp.SUM, process_set=process_set)
    result = int(to_numpy(out))
    if result != n:
        raise RuntimeError(f"barrier allreduce returned {result} != {n}")
