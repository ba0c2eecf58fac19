"""Engine-side schedule executor: walk a lowered schedule, dispatch steps
asynchronously so later chunks' communication overlaps earlier chunks'
compute.

Execution model
---------------
The schedule's dispatch units — per chunk: a *reduce-scatter* unit (wire
encode folded in), a *combine* unit (the fp32 dequant-accumulate /
average / requant arithmetic), an *allgather* unit (decode folded in) —
each compile to one jitted program (cached in the collectives dispatch
table by schedule signature).  The walk follows
:meth:`~horovod_tpu.ops.sched.ir.Schedule.interleaved_order`: every
chunk's reduce-scatter is dispatched before any chunk's combine, so with
JAX's async dispatch the device is free to run chunk *c+1*'s collective
while chunk *c*'s arithmetic executes.  Nothing blocks until the caller
synchronizes the returned arrays.

Timeline spans (Timeline v2)
----------------------------
Each dispatched unit opens a span on its own lane
(``<tensor>/rs.c0``, ``/combine.c0``, ``/ag.c0``) at dispatch time and
closes it when the step's consumer unit is dispatched — i.e. the span is
the step's **in-flight window**: the host has issued it and no later
dispatch has demanded its result yet.  That window is exactly where the
device may overlap it with other in-flight work, so a communication span
overlapping a compute span in the trace is the *schedule's* overlap
opportunity made visible (on a bandwidth-bound interconnect the device
realizes it; the CPU rig serializes — see docs/performance.md).  Flow
arrows link RS -> COMBINE -> AG per chunk, and
``hvd_sched_overlap_fraction`` integrates the same windows into a gauge:
the fraction of communication in-flight time overlapped by compute
in-flight time.
"""

from __future__ import annotations

import os
import time
from typing import Any, Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from jax import shard_map
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ...obs import REGISTRY as _obs
from ...obs import perfmodel as _perf
from .. import reduction as R
from .lower import (chunk_layout, parse_compiled_descriptor,
                    parse_descriptor, parse_hier_descriptor)

_m_overlap = _obs.gauge(
    "hvd_sched_overlap_fraction",
    "fraction of communication-step in-flight time overlapped by "
    "compute-step in-flight time in the last decomposed collective "
    "(host dispatch windows; 0 = fully serialized schedule)")
_m_sched = _obs.counter(
    "hvd_sched_dispatches_total",
    "decomposed-schedule collective dispatches", ("schedule",))
# Pre-resolved per-descriptor children (engine.py keeps its per-verb
# counters allocation-free the same way): one locked float add per
# dispatch, no labels() lookup on the cycle-thread hot path.
_m_sched_d: dict = {}


def _m_sched_child(descriptor: str):
    child = _m_sched_d.get(descriptor)
    if child is None:
        child = _m_sched_d.setdefault(
            descriptor, _m_sched.labels(schedule=descriptor))
    return child


#: HVDTPU_SCHED_FENCE_DISPATCH=1 blocks on every dispatched unit instead
#: of pipelining them.  Escape hatch for the in-process XLA:CPU rig: its
#: cross_module rendezvous runs device executions on a shared pool sized
#: by host cores, and two *independent* in-flight programs (chunk c's
#: cross hop under chunk c+1's scatter — the overlap this executor
#: exists to create) can each hold threads the other's rendezvous needs;
#: on few-core hosts that intermittently deadlocks ("This thread has
#: been waiting..." spew).  Fencing forfeits overlap (gauge reads 0), so
#: only benchmarks/collective_bench --hierarchy sets it by default —
#: real multi-process transports (gloo/TPU) never need it.
_FENCE_DISPATCH = os.environ.get(
    "HVDTPU_SCHED_FENCE_DISPATCH", "") not in ("", "0")


def _fence_unit(v):
    if _FENCE_DISPATCH and v is not None:
        jax.block_until_ready(v)
    return v


# ---------------------------------------------------------------------------
# Phase program builders (one jitted program per dispatch unit, cached by
# the collectives dispatch table under the schedule signature)
# ---------------------------------------------------------------------------

def _build_prepare(mesh: Mesh, axis: str, layout: tuple, total: int,
                   plen: int):
    """Flatten + concat + zero-pad the group payloads, split into chunk
    buffers (the IR's leading ``chunk`` step)."""
    shard = NamedSharding(mesh, P(axis))

    def fn(xs):
        n = xs[0].shape[0]
        flat = (xs[0].reshape(n, -1) if len(xs) == 1 else
                jnp.concatenate([x.reshape(n, -1) for x in xs], axis=1))
        if plen != total:
            flat = jnp.concatenate(
                [flat, jnp.zeros((n, plen - total), flat.dtype)], axis=1)
        outs = []
        off = 0
        for clen in layout:
            outs.append(lax.dynamic_slice_in_dim(flat, off, clen, axis=1))
            off += clen
        return outs

    return jax.jit(fn, out_shardings=[shard] * len(layout))


def _build_finish(mesh: Mesh, numels: tuple, shapes: tuple, dtype,
                  total: int):
    """Concat chunk results, drop padding, split back per group entry
    (the IR's trailing ``concat`` step)."""
    repl = NamedSharding(mesh, P())

    def fn(chunks):
        flat = (chunks[0] if len(chunks) == 1
                else jnp.concatenate(chunks))[:total]
        outs = []
        off = 0
        for numel, shape in zip(numels, shapes):
            outs.append(lax.dynamic_slice_in_dim(flat, off, numel)
                        .reshape(shape).astype(dtype))
            off += numel
        return outs

    return jax.jit(fn, out_shardings=[repl] * len(numels))


def _build_rs_fp32(mesh: Mesh, axis: str, prescale: float):
    def kernel(v):  # [1, clen] per device
        x = v[0]
        if prescale != 1.0:
            x = x * jnp.asarray(prescale, x.dtype)
        return lax.psum_scatter(x, axis, scatter_dimension=0, tiled=True)

    return jax.jit(shard_map(kernel, mesh=mesh, in_specs=P(axis),
                             out_specs=P(axis), check_vma=False))


def _build_combine_fp32(mesh: Mesh, axis: str, n: int):
    # The AVERAGE divide on the owning shard.  Elementwise, so dividing
    # the shard then gathering is bit-identical to the monolithic
    # psum-then-divide (same per-element float ops in the same order).
    def kernel(s):  # [clen // n] per device
        return s / n

    return jax.jit(shard_map(kernel, mesh=mesh, in_specs=P(axis),
                             out_specs=P(axis), check_vma=False))


def _build_ag_fp32(mesh: Mesh, axis: str, postscale: float):
    def kernel(s):  # [clen // n] per device
        g = lax.all_gather(s, axis, axis=0, tiled=True)
        if postscale != 1.0:
            g = g * jnp.asarray(postscale, g.dtype)
        return g

    return jax.jit(shard_map(kernel, mesh=mesh, in_specs=P(axis),
                             out_specs=P(), check_vma=False))


def _build_rs_quant(mesh: Mesh, axis: str, mode: str, clen: int,
                    block: int, prescale: float):
    """Encode + reduce-scatter unit: shared-scale block quantization
    (pmax of raw absmax, then the zero-block sentinel — the same order
    :func:`reduction._build_quant_allreduce` uses, for the same poisoned-
    sentinel reason) and a ``psum_scatter`` of the narrow accumulator in
    which sums are exact (int8/int16) or fp16-rounded (fp8)."""
    n = mesh.shape[axis]
    alg = R.algebra_for(mode)
    cblocks = clen // block
    sblocks = cblocks // n

    def kernel(v):  # [1, clen] per device
        x = v[0].astype(jnp.float32)
        if prescale != 1.0:
            x = x * prescale
        blocks = x.reshape(cblocks, block)
        shared = alg.scale_from_absmax(
            lax.pmax(alg.block_absmax(blocks), axis))
        q, _ = alg.wire_encode(blocks, shared_scale=shared)
        acc = lax.psum_scatter(
            q.astype(alg.acc_dtype).reshape(-1), axis,
            scatter_dimension=0, tiled=True)              # [clen // n]
        me = lax.axis_index(axis)
        my_scale = lax.dynamic_slice_in_dim(shared, me * sblocks, sblocks)
        return acc, my_scale

    return jax.jit(shard_map(kernel, mesh=mesh, in_specs=P(axis),
                             out_specs=(P(axis), P(axis)),
                             check_vma=False))


def _build_combine_quant(mesh: Mesh, axis: str, mode: str, block: int,
                         n: int, average: bool):
    """Compute unit: fp32 dequant-accumulate (+average) on the owning
    shard, then requantize with LOCAL per-block scales.  Per-block and
    order-independent (exact narrow sums), so the result is bit-identical
    to the monolithic quantized kernel regardless of chunking."""
    alg = R.algebra_for(mode)

    def kernel(acc_sh, scale_sh):  # [clen//n], [cblocks//n] per device
        accf = alg.wire_decode(
            acc_sh.reshape(scale_sh.shape[0], block), scale_sh)
        if average:
            accf = accf / n
        w2, s2 = alg.wire_encode(accf)
        return w2.reshape(-1), s2

    return jax.jit(shard_map(kernel, mesh=mesh, in_specs=(P(axis), P(axis)),
                             out_specs=(P(axis), P(axis)),
                             check_vma=False))


def _build_ag_quant(mesh: Mesh, axis: str, mode: str, block: int,
                    postscale: float):
    """Allgather + decode unit: 1-byte payload + 4B/block scales on the
    wire, fp32 decode on arrival."""
    alg = R.algebra_for(mode)

    def kernel(w_sh, s_sh):
        gw = lax.all_gather(w_sh, axis, axis=0, tiled=True)
        gs = lax.all_gather(s_sh, axis, axis=0, tiled=True)
        out = alg.wire_decode(gw.reshape(gs.shape[0], block), gs).reshape(-1)
        if postscale != 1.0:
            out = out * postscale
        return out

    return jax.jit(shard_map(kernel, mesh=mesh, in_specs=(P(axis), P(axis)),
                             out_specs=P(), check_vma=False))


def _build_programs(mesh, axis, average, mode, numels, shapes, dtype,
                    prescale, postscale, block, layout):
    """All dispatch-unit programs for one schedule signature."""
    n = mesh.shape[axis]
    total = int(sum(numels))
    plen = int(sum(layout))
    quant = mode in R.QUANT_MODES
    progs: dict = {
        "prepare": _build_prepare(mesh, axis, tuple(layout), total, plen),
        "finish": _build_finish(mesh, tuple(numels), tuple(shapes), dtype,
                                total),
        "rs": {}, "combine": {}, "ag": {},
    }
    for clen in sorted(set(layout)):
        if quant:
            progs["rs"][clen] = _build_rs_quant(mesh, axis, mode, clen,
                                                block, prescale)
            progs["combine"][clen] = _build_combine_quant(
                mesh, axis, mode, block, n, average)
            progs["ag"][clen] = _build_ag_quant(mesh, axis, mode, block,
                                                postscale)
        else:
            progs["rs"][clen] = _build_rs_fp32(mesh, axis, prescale)
            if average:
                progs["combine"][clen] = _build_combine_fp32(mesh, axis, n)
            progs["ag"][clen] = _build_ag_fp32(mesh, axis, postscale)
    return progs


# ---------------------------------------------------------------------------
# Tiered phase builders (hier:<n_local>:<k> — chunked + two-tier).  Three
# dispatch units per chunk on the 2-D (hvd_cross, hvd_local) mesh:
#
#   rs     — fast-tier (ICI) reduce-scatter of the chunk over n_local;
#   cross  — slow-tier (DCN) allreduce of the 1/n_local shard over
#            n_cross, with its own wire mode (the EQuARX placement: the
#            bandwidth-starved hop is where quantization pays), combine
#            (average / dequant-requant) folded in;
#   ag     — fast-tier allgather back to the full chunk.
#
# Quantized base mode stays bit-identical to the flat quantized kernel:
# the shared scale is a pmax over BOTH axes (associative max == the flat
# axis pmax), the narrow accumulator sums exactly under either grouping,
# and the cross-then-local gathers reassemble the identical element
# order.  fp32 changes the n-way sum's association (local ring then
# cross) — the <=2 ulp contract, same as flat rs_ag at np>=4.
# ---------------------------------------------------------------------------

_HIER_AXES = ("hvd_cross", "hvd_local")
_HIER_SPEC = P(_HIER_AXES)
_HIER_MESHES: dict = {}


def _hier_mesh(state, n_cross: int, n_local: int) -> Mesh:
    devs = tuple(state.devices)
    ent = _HIER_MESHES.get((n_cross, n_local))
    if ent is not None and ent[0] == devs:
        return ent[1]
    mesh = Mesh(np.array(devs).reshape(n_cross, n_local), _HIER_AXES)
    _HIER_MESHES[(n_cross, n_local)] = (devs, mesh)
    return mesh


def _build_hier_rs_fp32(mesh: Mesh, prescale: float):
    def kernel(v):  # [1, clen] per device
        x = v[0]
        if prescale != 1.0:
            x = x * jnp.asarray(prescale, x.dtype)
        return lax.psum_scatter(x, "hvd_local", scatter_dimension=0,
                                tiled=True)                # [clen/n_local]

    return jax.jit(shard_map(kernel, mesh=mesh, in_specs=_HIER_SPEC,
                             out_specs=_HIER_SPEC, check_vma=False))


def _build_hier_cross_fp32(mesh: Mesh, average: bool, n_total: int):
    def kernel(s):  # [clen/n_local] per device
        r = lax.psum(s, "hvd_cross")
        if average:
            r = r / n_total
        return r

    return jax.jit(shard_map(kernel, mesh=mesh, in_specs=_HIER_SPEC,
                             out_specs=_HIER_SPEC, check_vma=False))


def _build_hier_cross_quant(mesh: Mesh, cross_mode: str, clen: int,
                            block: int, average: bool, n_total: int):
    """Slow-tier hop under an fp32 fast tier: quantize the 1/n_local
    shard with cross-group shared scales, exchange the narrow
    accumulator (psum_scatter + requantized allgather), decode back to
    fp32 — the only hop whose bytes cross DCN carries ~1/4 the width."""
    alg = R.algebra_for(cross_mode)
    n_local = mesh.shape["hvd_local"]
    n_cross = mesh.shape["hvd_cross"]
    sb = clen // (n_local * block)      # blocks per local shard
    sbc = sb // n_cross

    def kernel(s):  # [clen/n_local] fp32 per device
        blocks = s.reshape(sb, block)
        shared = alg.scale_from_absmax(
            lax.pmax(alg.block_absmax(blocks), "hvd_cross"))
        q, _ = alg.wire_encode(blocks, shared_scale=shared)
        acc = lax.psum_scatter(
            q.astype(alg.acc_dtype).reshape(-1), "hvd_cross",
            scatter_dimension=0, tiled=True)               # [clen/n]
        me = lax.axis_index("hvd_cross")
        my_scale = lax.dynamic_slice_in_dim(shared, me * sbc, sbc)
        accf = alg.wire_decode(acc.reshape(sbc, block), my_scale)
        if average:
            accf = accf / n_total
        w2, s2 = alg.wire_encode(accf)
        gw = lax.all_gather(w2.reshape(-1), "hvd_cross", axis=0, tiled=True)
        gs = lax.all_gather(s2, "hvd_cross", axis=0, tiled=True)
        return alg.wire_decode(gw.reshape(sb, block), gs).reshape(-1)

    return jax.jit(shard_map(kernel, mesh=mesh, in_specs=_HIER_SPEC,
                             out_specs=_HIER_SPEC, check_vma=False))


def _build_hier_ag_fp32(mesh: Mesh, postscale: float):
    def kernel(s):  # [clen/n_local] per device, cross-replicated
        g = lax.all_gather(s, "hvd_local", axis=0, tiled=True)
        if postscale != 1.0:
            g = g * jnp.asarray(postscale, g.dtype)
        return g

    return jax.jit(shard_map(kernel, mesh=mesh, in_specs=_HIER_SPEC,
                             out_specs=P(), check_vma=False))


def _build_hier_rs_quant(mesh: Mesh, mode: str, clen: int, block: int,
                         prescale: float):
    """Quantized base mode, fast-tier half: shared-scale encode with the
    GLOBAL pmax (both axes — identical to the flat kernel's flat-axis
    pmax, max being associative) and an exact narrow psum_scatter over
    the local tier only."""
    alg = R.algebra_for(mode)
    n_local = mesh.shape["hvd_local"]
    cblocks = clen // block
    sbl = cblocks // n_local

    def kernel(v):  # [1, clen] per device
        x = v[0].astype(jnp.float32)
        if prescale != 1.0:
            x = x * prescale
        blocks = x.reshape(cblocks, block)
        shared = alg.scale_from_absmax(
            lax.pmax(alg.block_absmax(blocks), _HIER_AXES))
        q, _ = alg.wire_encode(blocks, shared_scale=shared)
        acc = lax.psum_scatter(
            q.astype(alg.acc_dtype).reshape(-1), "hvd_local",
            scatter_dimension=0, tiled=True)           # [clen/n_local]
        me = lax.axis_index("hvd_local")
        my_scale = lax.dynamic_slice_in_dim(shared, me * sbl, sbl)
        return acc, my_scale

    return jax.jit(shard_map(kernel, mesh=mesh, in_specs=_HIER_SPEC,
                             out_specs=(_HIER_SPEC, _HIER_SPEC),
                             check_vma=False))


def _build_hier_cross_quant_acc(mesh: Mesh, mode: str, block: int,
                                average: bool, n_total: int):
    """Quantized base mode, slow-tier hop: finish the exact narrow sum
    over the cross tier (total == the flat kernel's n-way sum, integer
    addition under either grouping), dequant/average/requant with LOCAL
    per-block scales — bit-identical to the flat combine — then gather
    the re-encoded wire back across the cross tier, still 1 byte/elem."""
    alg = R.algebra_for(mode)
    n_cross = mesh.shape["hvd_cross"]

    def kernel(acc, scale):  # [clen/n_local] acc_dtype, [sbl] fp32
        sbl = scale.shape[0]
        sbc = sbl // n_cross
        acc2 = lax.psum_scatter(acc, "hvd_cross", scatter_dimension=0,
                                tiled=True)                # [clen/n]
        me = lax.axis_index("hvd_cross")
        my_scale = lax.dynamic_slice_in_dim(scale, me * sbc, sbc)
        accf = alg.wire_decode(acc2.reshape(sbc, block), my_scale)
        if average:
            accf = accf / n_total
        w2, s2 = alg.wire_encode(accf)
        gw = lax.all_gather(w2.reshape(-1), "hvd_cross", axis=0, tiled=True)
        gs = lax.all_gather(s2, "hvd_cross", axis=0, tiled=True)
        return gw, gs                    # [clen/n_local] wire, [sbl] scales

    return jax.jit(shard_map(kernel, mesh=mesh,
                             in_specs=(_HIER_SPEC, _HIER_SPEC),
                             out_specs=(_HIER_SPEC, _HIER_SPEC),
                             check_vma=False))


def _build_hier_ag_quant(mesh: Mesh, mode: str, block: int,
                         postscale: float):
    alg = R.algebra_for(mode)

    def kernel(w, s):  # [clen/n_local] wire, [sbl] scales per device
        gw = lax.all_gather(w, "hvd_local", axis=0, tiled=True)
        gs = lax.all_gather(s, "hvd_local", axis=0, tiled=True)
        out = alg.wire_decode(gw.reshape(gs.shape[0], block),
                              gs).reshape(-1)
        if postscale != 1.0:
            out = out * postscale
        return out

    return jax.jit(shard_map(kernel, mesh=mesh,
                             in_specs=(_HIER_SPEC, _HIER_SPEC),
                             out_specs=P(), check_vma=False))


def _build_hier_programs(mesh, average, mode, cross_mode, numels, shapes,
                         dtype, prescale, postscale, block, layout,
                         n_total):
    """All dispatch-unit programs for one hier schedule signature."""
    total = int(sum(numels))
    plen = int(sum(layout))
    quant = mode in R.QUANT_MODES
    progs: dict = {
        "prepare": _build_prepare(mesh, _HIER_AXES, tuple(layout), total,
                                  plen),
        "finish": _build_finish(mesh, tuple(numels), tuple(shapes), dtype,
                                total),
        "rs": {}, "cross": {}, "ag": {},
    }
    for clen in sorted(set(layout)):
        if quant:
            progs["rs"][clen] = _build_hier_rs_quant(
                mesh, mode, clen, block, prescale)
            progs["cross"][clen] = _build_hier_cross_quant_acc(
                mesh, mode, block, average, n_total)
            progs["ag"][clen] = _build_hier_ag_quant(
                mesh, mode, block, postscale)
        else:
            progs["rs"][clen] = _build_hier_rs_fp32(mesh, prescale)
            if cross_mode in R.QUANT_MODES:
                progs["cross"][clen] = _build_hier_cross_quant(
                    mesh, cross_mode, clen, block, average, n_total)
            else:
                progs["cross"][clen] = _build_hier_cross_fp32(
                    mesh, average, n_total)
            progs["ag"][clen] = _build_hier_ag_fp32(mesh, postscale)
    return progs


# ---------------------------------------------------------------------------
# The walk
# ---------------------------------------------------------------------------

_UNIT_ACTIVITY = {"rs": "SCHED_RS", "combine": "SCHED_COMBINE",
                  "ag": "SCHED_AG", "cross": "SCHED_CROSS"}


def _overlap_fraction(comm: list, compute: list) -> float:
    """Fraction of total comm in-flight time covered by the union of
    compute in-flight windows (both lists of (t0, t1) host timestamps)."""
    total = sum(t1 - t0 for t0, t1 in comm)
    if total <= 0.0 or not compute:
        return 0.0
    # Merge compute windows first: the engine walk's windows are disjoint
    # today, but summing pairwise intersections would double-count any
    # future walk with concurrently-open compute spans.
    merged: list = []
    for k0, k1 in sorted(compute):
        if merged and k0 <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], k1)
        else:
            merged.append([k0, k1])
    covered = 0.0
    for c0, c1 in comm:
        for k0, k1 in merged:
            lo, hi = max(c0, k0), min(c1, k1)
            if hi > lo:
                covered += hi - lo
    return min(1.0, covered / total)


def execute_allreduce(xs: Sequence[Any], op, *, descriptor: str,
                      precision: str = "fp32", prescale: float = 1.0,
                      postscale: float = 1.0, process_set=None,
                      name: str = "allreduce") -> list:
    """Run a (possibly fused) allreduce group through the decomposed
    reduce-scatter/allgather schedule named by ``descriptor``.

    ``xs`` are per-rank tensors ([n, *shape] sharded over the collective
    axis); results are replicated, one per input, in input order —
    bit-identical to the monolithic path (fp32: identical per-element
    float ops; quantized: identical block layout + exact narrow sums; see
    the phase builders).
    """
    from .. import collectives as C
    from ... import context as ctx_mod
    chunks = parse_descriptor(descriptor)
    if chunks is None:
        if parse_compiled_descriptor(descriptor) is not None:
            # Single-program GSPMD backend: same schedule, no dispatch
            # walk — _m_sched stays untouched on this path (the CI
            # zero-dispatch guard rests on that).
            from . import compiled as CP
            return CP.execute_allreduce(
                xs, op, descriptor=descriptor, precision=precision,
                prescale=prescale, postscale=postscale,
                process_set=process_set, name=name)
        if parse_hier_descriptor(descriptor) is not None:
            return _execute_hier_allreduce(
                xs, op, descriptor=descriptor, precision=precision,
                prescale=prescale, postscale=postscale,
                process_set=process_set, name=name)
        raise ValueError(f"unknown schedule descriptor {descriptor!r}")
    if precision in ("bf16", "fp16"):
        # resolve_schedule never admits cast modes (they keep the
        # single-psum shape — see its docstring); running them here
        # would silently execute fp32 programs while accounting cast
        # savings.  Fail loudly instead.
        raise ValueError(
            f"decomposed schedule does not support cast wire mode "
            f"{precision!r}; resolve_schedule should have fallen back")
    mesh, axis = C._mesh_axis(process_set)
    n = mesh.shape[axis]
    state = ctx_mod.global_state()
    cfg = state.config
    block = cfg.quant_block_size
    mode = precision or "fp32"
    arrs = [C.as_per_rank(x, process_set) for x in xs]
    dtype = arrs[0].dtype
    shapes = tuple(a.shape[1:] for a in arrs)
    numels = tuple(int(np.prod(s, dtype=np.int64)) if s else 1
                   for s in shapes)
    total = int(sum(numels))
    layout = tuple(chunk_layout(total, n, chunks, mode, block))
    # Cache key: the raw lowering inputs.  Lowering is deterministic in
    # exactly these (plus mesh/axis), so the cheap tuple IS the schedule
    # signature — no per-dispatch Schedule rebuild or string formatting
    # on the cycle-thread hot path (lower_allreduce stays the source of
    # truth for IR consumers and tests/test_sched.py asserts the
    # executor's walk matches its interleaved_order).
    key = C._sig(mesh, axis, "sched", descriptor, op, dtype.name,
                 numels, shapes, mode, block,
                 float(prescale), float(postscale))
    average = op is C.ReduceOp.AVERAGE
    progs = C._cache.get_or_build(
        key, lambda: _build_programs(mesh, axis, average, mode, numels,
                                     shapes, dtype, float(prescale),
                                     float(postscale), block, layout))
    if mode != "fp32":
        R.account_wire(mode, total * dtype.itemsize, n, block,
                       itemsize=dtype.itemsize)
    _m_sched_child(descriptor).inc()

    # -- dispatch walk ------------------------------------------------------
    tl = state.timeline
    tl_on = tl is not None and tl.enabled
    chunk_bufs = progs["prepare"](list(arrs))
    quant = mode in R.QUANT_MODES
    k = len(layout)
    vals: list = [None] * k           # per-chunk in-flight value(s)
    outs: list = [None] * k           # per-chunk gathered result
    opened: dict = {}                 # (unit, c) -> (lane, t_open)
    windows: dict = {"comm": [], "compute": []}
    flows: dict = {}

    def _open(unit: str, c: int) -> None:
        t = time.monotonic()
        lane = f"{name}/{unit}.c{c}"
        opened[(unit, c)] = (lane, t)
        if tl_on:
            tl.start_activity(lane, _UNIT_ACTIVITY[unit])
            if unit == "rs":
                fid = tl.new_flow()
                flows[c] = fid
                tl.flow_start(lane, fid)
            elif c in flows:
                # Land the chunk's arrow on this span, then re-open it so
                # the chain RS -> COMBINE -> AG stays connected.
                tl.flow_end(lane, flows[c])
                if unit != "ag":
                    fid = tl.new_flow()
                    flows[c] = fid
                    tl.flow_start(lane, fid)

    def _close(unit: str, c: int) -> None:
        ent = opened.pop((unit, c), None)
        if ent is None:
            return
        lane, t0 = ent
        windows["comm" if unit in ("rs", "ag") else "compute"].append(
            (t0, time.monotonic()))
        if tl_on:
            tl.end_activity(lane)

    has_combine = quant or average
    order = [(u, c) for c in range(k) for u in ("rs", "combine", "ag")
             if u != "combine" or has_combine]
    # Interleave exactly as Schedule.interleaved_order does for rs_ag:
    # all reduce-scatters first, then combine/allgather per chunk —
    # asserted equivalent in tests/test_sched.py.
    order.sort(key=lambda uc: (0 if uc[0] == "rs" else 1, uc[1],
                               0 if uc[0] == "combine" else 1))
    for unit, c in order:
        clen = layout[c]
        if unit == "rs":
            _open("rs", c)
            vals[c] = _fence_unit(progs["rs"][clen](chunk_bufs[c]))
        elif unit == "combine":
            _close("rs", c)          # its consumer is now dispatched
            _open("combine", c)
            v = vals[c]
            vals[c] = _fence_unit(progs["combine"][clen](*v) if quant
                                  else progs["combine"][clen](v))
        else:  # ag
            _close("combine" if has_combine else "rs", c)
            _open("ag", c)
            v = vals[c]
            outs[c] = _fence_unit(progs["ag"][clen](*v) if quant
                                  else progs["ag"][clen](v))
    results = progs["finish"](outs)
    for c in range(k):
        _close("ag", c)
    _m_overlap.set(_overlap_fraction(windows["comm"], windows["compute"]))
    # Feed the same dispatch windows into the expected-vs-achieved model:
    # the union span is the host-observed in-flight time of the whole
    # pipeline, the per-chunk comm windows give straggler attribution.
    _perf.MODEL.observe_schedule(
        descriptor=descriptor, mode=mode,
        payload_bytes=total * dtype.itemsize, n=n, chunks=k,
        comm_windows=windows["comm"], compute_windows=windows["compute"],
        block=block, itemsize=dtype.itemsize)
    return list(results)


def _union_seconds(windows: list) -> float:
    """Total covered time of a set of (t0, t1) host windows (union, not
    sum — concurrently-open spans count once)."""
    merged: list = []
    for t0, t1 in sorted(windows):
        if merged and t0 <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], t1)
        else:
            merged.append([t0, t1])
    return sum(t1 - t0 for t0, t1 in merged)


def resolve_cross_mode(mode: str, cfg) -> str:
    """Wire mode on the cross-tier hop, from synchronized config.

    A quantized base mode keeps its own algebra end to end (the exact
    narrow accumulator must survive both tiers for the bit-exactness
    contract); an fp32 base mode takes ``hierarchical_cross_precision``
    on the slow hop only.  Deterministic in (mode, config) — every rank
    derives the same answer, so the descriptor need not carry it.
    """
    if mode in R.QUANT_MODES:
        return mode
    cross = getattr(cfg, "hierarchical_cross_precision", "") or ""
    if cross in R.QUANT_MODES:
        return cross
    return "fp32"


def _execute_hier_allreduce(xs: Sequence[Any], op, *, descriptor: str,
                            precision: str = "fp32", prescale: float = 1.0,
                            postscale: float = 1.0, process_set=None,
                            name: str = "allreduce") -> list:
    """Run a fused allreduce group through the chunked+tiered
    ``hier:<n_local>:<k>`` schedule: per chunk, an ICI reduce-scatter
    over the local tier, a DCN allreduce of the 1/n_local shard over the
    cross tier (with its own wire mode), and an ICI allgather back.  All
    local scatters are dispatched before any cross hop, so chunk *c*'s
    slow-tier exchange is in flight while chunk *c+1*'s fast-tier
    scatter runs — the overlap the ``hvd_sched_overlap_fraction`` gauge
    measures here as (cross windows covered by local windows).
    """
    from .. import collectives as C
    from ... import context as ctx_mod
    n_local, chunks = parse_hier_descriptor(descriptor)
    if precision in ("bf16", "fp16"):
        raise ValueError(
            f"tiered schedule does not support cast wire mode "
            f"{precision!r}; resolve_schedule should have fallen back")
    if process_set is not None:
        raise ValueError("tiered schedule requires the global process set "
                         "(subgroup topology unknown)")
    state = ctx_mod.global_state()
    cfg = state.config
    n = state.size
    if n % n_local or not (1 < n_local < n):
        raise ValueError(
            f"descriptor {descriptor!r} does not divide world size {n}")
    n_cross = n // n_local
    mesh = _hier_mesh(state, n_cross, n_local)
    block = cfg.quant_block_size
    mode = precision or "fp32"
    cross_mode = resolve_cross_mode(mode, cfg)
    arrs = [C.as_per_rank(x, process_set) for x in xs]
    dtype = arrs[0].dtype
    shapes = tuple(a.shape[1:] for a in arrs)
    numels = tuple(int(np.prod(s, dtype=np.int64)) if s else 1
                   for s in shapes)
    total = int(sum(numels))
    # Chunk boundaries use the TOTAL rank count and the quantized unit
    # when EITHER tier is quantized: clen % (n * block) == 0 makes the
    # 1/n_local local shard a whole number of n_cross * block units, so
    # the cross hop can scatter on block boundaries — and lands on the
    # same boundaries the flat lowering uses (bit-exactness per chunk).
    mode_eff = mode if mode in R.QUANT_MODES else cross_mode
    layout = tuple(chunk_layout(total, n, chunks, mode_eff, block))
    key = C._sig(mesh, "hier", "sched", descriptor, op, dtype.name,
                 numels, shapes, mode, cross_mode, block,
                 float(prescale), float(postscale))
    average = op is C.ReduceOp.AVERAGE
    progs = C._cache.get_or_build(
        key, lambda: _build_hier_programs(
            mesh, average, mode, cross_mode, numels, shapes, dtype,
            float(prescale), float(postscale), block, layout, n))
    # Per-tier wire accounting: the local tier rings the full payload
    # over n_local, the cross tier rings the 1/n_local shard over
    # n_cross — each at its own wire mode.
    if mode in R.QUANT_MODES:
        R.account_wire(mode, total * dtype.itemsize, n_local, block,
                       itemsize=dtype.itemsize)
    if cross_mode in R.QUANT_MODES:
        R.account_wire(cross_mode, total * dtype.itemsize // n_local,
                       n_cross, block, itemsize=dtype.itemsize)
    _m_sched_child(descriptor).inc()

    # -- dispatch walk ------------------------------------------------------
    tl = state.timeline
    tl_on = tl is not None and tl.enabled
    chunk_bufs = progs["prepare"](list(arrs))
    quant = mode in R.QUANT_MODES
    k = len(layout)
    vals: list = [None] * k
    outs: list = [None] * k
    opened: dict = {}                 # (unit, c) -> (lane, t_open)
    windows: dict = {"local": [], "cross": []}
    flows: dict = {}

    def _open(unit: str, c: int) -> None:
        t = time.monotonic()
        lane = f"{name}/{'local_' if unit != 'cross' else ''}{unit}.c{c}"
        opened[(unit, c)] = (lane, t)
        if tl_on:
            tl.start_activity(lane, _UNIT_ACTIVITY[unit])
            if unit == "rs":
                fid = tl.new_flow()
                flows[c] = fid
                tl.flow_start(lane, fid)
            elif c in flows:
                tl.flow_end(lane, flows[c])
                if unit != "ag":
                    fid = tl.new_flow()
                    flows[c] = fid
                    tl.flow_start(lane, fid)

    def _close(unit: str, c: int) -> None:
        ent = opened.pop((unit, c), None)
        if ent is None:
            return
        lane, t0 = ent
        windows["cross" if unit == "cross" else "local"].append(
            (t0, time.monotonic()))
        if tl_on:
            tl.end_activity(lane)

    order = [(u, c) for c in range(k) for u in ("rs", "cross", "ag")]
    # Same interleave contract as the flat walk vs interleaved_order:
    # every chunk's local scatter first, then (cross, ag) per chunk —
    # chunk c's DCN hop in flight under chunk c+1's ICI scatter.
    order.sort(key=lambda uc: (0 if uc[0] == "rs" else 1, uc[1],
                               0 if uc[0] == "cross" else 1))
    for unit, c in order:
        clen = layout[c]
        if unit == "rs":
            _open("rs", c)
            vals[c] = _fence_unit(progs["rs"][clen](chunk_bufs[c]))
        elif unit == "cross":
            _close("rs", c)
            _open("cross", c)
            v = vals[c]
            vals[c] = _fence_unit(progs["cross"][clen](*v) if quant
                                  else progs["cross"][clen](v))
        else:  # ag
            _close("cross", c)
            _open("ag", c)
            v = vals[c]
            outs[c] = _fence_unit(progs["ag"][clen](*v) if quant
                                  else progs["ag"][clen](v))
    results = progs["finish"](outs)
    for c in range(k):
        _close("ag", c)
    # Overlap here means: how much of the slow tier's in-flight time was
    # hidden under fast-tier work.
    _m_overlap.set(_overlap_fraction(windows["cross"], windows["local"]))
    all_windows = windows["local"] + windows["cross"]
    _perf.MODEL.observe_tiers(
        total * dtype.itemsize, n_local, n_cross,
        _union_seconds(all_windows),
        tier_seconds={"local": _union_seconds(windows["local"]),
                      "cross": _union_seconds(windows["cross"])},
        mode=mode, cross_mode=cross_mode, chunks=k, schedule=descriptor,
        block=block, itemsize=dtype.itemsize)
    return list(results)
