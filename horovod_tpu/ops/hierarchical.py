"""Hierarchical (two-level) collectives: ICI within a slice, DCN across.

† ``nccl_operations.cc`` ``HOROVOD_HIERARCHICAL_ALLREDUCE``: the reference
splits an allreduce into NCCL reduce-scatter within the node, MPI allreduce
across nodes on the scattered shards, and NCCL all-gather back — because
intra-node NVLink is an order of magnitude faster than the inter-node
fabric.  The TPU analogue is identical in shape: ICI within a slice is
~10× DCN across slices, so the cross-slice hop should carry only 1/n_local
of the bytes:

    reduce_scatter over 'local' (ICI)          # bytes/chip: B
    allreduce     over 'cross' (DCN)           # bytes/chip: B / n_local
    all_gather    over 'local' (ICI)           # bytes/chip: B

On a single slice XLA already picks bandwidth-optimal ICI algorithms, so
hierarchical mode matters for multislice meshes; the mesh builder puts the
slice boundary on the outer axes (see parallel/mesh.py) and this module
provides the explicit two-level lowering plus a flat fallback.

Enabled via ``HVDTPU_HIERARCHICAL_ALLREDUCE`` (+ optional
``HVDTPU_HIERARCHICAL_LOCAL_SIZE`` for the ICI-group size, defaulting to
this process's device count): ``ops/collectives.allreduce`` and the fused
``grouped_allreduce`` route SUM/AVERAGE reductions through the two-level
kernel when the split is valid, including batches fused by the engine.
The standalone entries below also work directly on explicit 2-D meshes.

Schedule IR (ops/sched): the two-level pipeline is expressed as an IR
schedule — ``reduce_scatter@local -> all_reduce@cross -> combine ->
all_gather@local`` (:func:`horovod_tpu.ops.sched.lower_hierarchical`) —
and interpreted in-graph, so the hierarchical path and the engine's
chunked decomposition share one step vocabulary.  The topology-aware
lowering that chunks *and* tiers lives alongside it:
:func:`horovod_tpu.ops.sched.lower_hierarchical_chunked` emits
``hier:<n_local>:<k>`` schedules that the sched executor runs on a 2-D
(cross × local) device mesh with per-chunk DCN/ICI overlap and an
optional quantized cross-tier hop (``HVDTPU_HIERARCHICAL_CROSS_PRECISION``);
``resolve_schedule`` routes decomposed traffic there when the split is
valid.  This module keeps the unchunked kernel path used by the
monolithic ``allreduce``/``grouped_allreduce`` route and the standalone
2-D-mesh entries below.
"""

from __future__ import annotations

import time
from functools import lru_cache

import jax
from jax import lax
from jax import shard_map
from jax.sharding import Mesh, PartitionSpec as P


@lru_cache(maxsize=None)
def hierarchical_schedule(local_axis: str, cross_axis: str):
    """The two-tier IR schedule for an axis pair (cached: lowering is a
    pure function of the axis names)."""
    from .sched import lower_hierarchical
    return lower_hierarchical(local_axis, cross_axis)


def hierarchical_allreduce_local(v: jax.Array, *, local_axis: str,
                                 cross_axis: str,
                                 average: bool = False) -> jax.Array:
    """Two-level allreduce inside a mapped context over both axes.

    v: this device's full tensor [*shape] (replic-intent).  Returns the
    global sum (or mean) with the cross-axis hop carrying 1/n_local
    bytes.  Lowered through the schedule IR (module docstring): the
    interpreter executes reduce-scatter over ICI, allreduce over DCN on
    the 1/n_local shard, and all-gather back over ICI.
    """
    from .sched import run_in_context
    return run_in_context(hierarchical_schedule(local_axis, cross_axis),
                          v, average=average)


# AOT-compiled two-tier programs, keyed by everything the lowering
# specializes on.  Compilation must happen OUTSIDE the observe_tiers
# timing window: a first-call ``jax.jit(fn)(x)`` runs trace+compile
# synchronously inside the dispatch window, so the first observation fed
# the perf model hundreds of ms of compiler time as if it were wire time.
_COMPILE_CACHE: dict = {}


def _compiled_hierarchical(x: jax.Array, mesh: Mesh, local_axis: str,
                           cross_axis: str, average: bool):
    key = (tuple(d.id for d in mesh.devices.flat),
           mesh.axis_names, local_axis, cross_axis, average,
           x.shape, x.dtype.name, getattr(x, "sharding", None))
    prog = _COMPILE_CACHE.get(key)
    if prog is None:
        fn = shard_map(
            lambda v: hierarchical_allreduce_local(
                v[0, 0], local_axis=local_axis, cross_axis=cross_axis,
                average=average)[None, None],
            mesh=mesh,
            in_specs=P(cross_axis, local_axis),
            out_specs=P(cross_axis, local_axis),
            check_vma=False)
        prog = jax.jit(fn).lower(x).compile()
        _COMPILE_CACHE[key] = prog
    return prog


def hierarchical_allreduce(x: jax.Array, mesh: Mesh, *,
                           local_axis: str = "tp",
                           cross_axis: str = "dp",
                           average: bool = False) -> jax.Array:
    """Standalone entry: x is a per-device-stacked array
    ``[n_cross, n_local, *shape]`` sharded over (cross, local); every
    device contributes its slice and receives the full reduction."""
    prog = _compiled_hierarchical(x, mesh, local_axis, cross_axis, average)
    t0 = time.monotonic()
    out = prog(x)
    # Per-tier expected-cost attribution (ROADMAP item 3's straggler
    # feed): the host dispatch window against the two-tier wire model.
    # The program is compiled above, before t0, so the window never
    # includes compile time (regression-tested).
    from ..obs import perfmodel as _perf
    n_local = mesh.shape[local_axis]
    n_cross = mesh.shape[cross_axis]
    per_chip = int(x.size // max(1, n_local * n_cross) * x.dtype.itemsize)
    _perf.MODEL.observe_tiers(per_chip, n_local, n_cross,
                              time.monotonic() - t0)
    return out


def hierarchical_allgather_local(v: jax.Array, *, local_axis: str,
                                 cross_axis: str) -> jax.Array:
    """† ``HOROVOD_HIERARCHICAL_ALLGATHER``: gather locally over ICI first,
    then exchange the (bigger, but fewer) blocks across DCN."""
    local = lax.all_gather(v, local_axis, axis=0, tiled=True)
    return lax.all_gather(local, cross_axis, axis=0, tiled=True)
