"""Compiled GSPMD backend: lower a sched-IR schedule into ONE jitted
NamedSharding program.

The dispatched executor (:mod:`.executor`) walks a lowered schedule unit
by unit — per chunk a reduce-scatter program, a combine program, an
allgather program — and relies on JAX's async dispatch to overlap them.
That buys host-visible overlap windows but pays one host dispatch (and
one XLA executable launch) per unit: on dispatch-bound payloads the walk
itself is the bottleneck (round 7's 0.06–1.1× decomposed ratios on the
CPU rig).  This module lowers the SAME schedule — same
:func:`~.lower.chunk_layout` boundaries, same per-chunk arithmetic, same
encode/decode algebra — into one ``jax.jit`` program over the
NamedSharding mesh, so the XLA compiler owns collective placement,
fusion and overlap (GC3's compile-don't-interpret thesis; see
PAPERS.md).  One launch, zero per-unit dispatches.

Numerics contract — identical to the dispatched path's, because the
per-chunk chains are the executor's phase-builder bodies inlined:

- fp32: ``prescale -> psum_scatter -> /n (AVERAGE) -> all_gather ->
  postscale`` per chunk, the same per-element float ops in the same
  order as both the monolithic psum and the dispatched walk (bit-exact
  on same-association backends; <=2 ulp normwise across associations);
- int8/fp8: shared-scale block quantization (global pmax), exact
  narrow-accumulator ``psum_scatter``, per-block dequant/average/requant
  with LOCAL scales, wire+scale allgathers, decode — block boundaries
  land on the SAME ``n * block`` units as the monolithic kernel, so the
  result is bit-identical to it (and to the dispatched schedule).

Every process in the mesh MUST execute this same program for a given
collective: under ``jax.distributed`` the collective channel IDs are
assigned per-executable, so the backend choice rides the negotiation
meta (``sc = "compiled:rs_ag:<k>"``) exactly like the wire mode, and the
engine reconciles mixed-mode peers to one common descriptor before
dispatch (see ``engine._run_cycle``).

The cached program is keyed by schedule signature (the same raw lowering
inputs the dispatched path keys on, under a distinct ``"sched_compiled"``
tag) in the shared collectives dispatch cache, so re-dispatching the
same fused group is a table hit — no re-trace, no re-compile.
"""

from __future__ import annotations

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
from .lower import chunk_layout, parse_compiled_descriptor

_m_compiled = _obs.counter(
    "hvd_sched_compiled_dispatches_total",
    "single-program compiled-schedule collective dispatches",
    ("schedule",))
_m_compiled_d: dict = {}


def _m_compiled_child(descriptor: str):
    child = _m_compiled_d.get(descriptor)
    if child is None:
        child = _m_compiled_d.setdefault(
            descriptor, _m_compiled.labels(schedule=descriptor))
    return child


def _chunk_fp32(x, axis: str, n: int, average: bool, prescale: float,
                postscale: float):
    """One chunk's fp32 chain — the executor's rs/combine/ag fp32
    builders inlined (same ops, same order, so same bits)."""
    if prescale != 1.0:
        x = x * jnp.asarray(prescale, x.dtype)
    s = lax.psum_scatter(x, axis, scatter_dimension=0, tiled=True)
    if average:
        s = s / n
    g = lax.all_gather(s, axis, axis=0, tiled=True)
    if postscale != 1.0:
        g = g * jnp.asarray(postscale, g.dtype)
    return g


def _chunk_quant(x, axis: str, n: int, average: bool, mode: str,
                 block: int, prescale: float, postscale: float):
    """One chunk's quantized chain — rs_quant + combine_quant + ag_quant
    inlined: global-pmax shared scales, exact narrow psum_scatter,
    local-scale requant, wire+scale gathers, decode."""
    alg = R.algebra_for(mode)
    clen = x.shape[0]
    cblocks = clen // block
    sblocks = cblocks // n
    xf = x.astype(jnp.float32)
    if prescale != 1.0:
        xf = xf * prescale
    blocks = xf.reshape(cblocks, block)
    shared = alg.scale_from_absmax(
        lax.pmax(alg.block_absmax(blocks), axis))
    q, _ = alg.wire_encode(blocks, shared_scale=shared)
    acc = lax.psum_scatter(
        q.astype(alg.acc_dtype).reshape(-1), axis,
        scatter_dimension=0, tiled=True)                  # [clen // n]
    me = lax.axis_index(axis)
    my_scale = lax.dynamic_slice_in_dim(shared, me * sblocks, sblocks)
    accf = alg.wire_decode(acc.reshape(sblocks, block), my_scale)
    if average:
        accf = accf / n
    w2, s2 = alg.wire_encode(accf)
    gw = lax.all_gather(w2.reshape(-1), axis, axis=0, tiled=True)
    gs = lax.all_gather(s2, axis, axis=0, tiled=True)
    out = alg.wire_decode(gw.reshape(cblocks, block), gs).reshape(-1)
    if postscale != 1.0:
        out = out * postscale
    return out


def _build_compiled(mesh: Mesh, axis: str, average: bool, mode: str,
                    numels: tuple, shapes: tuple, dtype, prescale: float,
                    postscale: float, block: int, layout: tuple):
    """The whole schedule as ONE jitted program: prepare (flatten /
    concat / zero-pad), every chunk's chain inside a single shard_map
    (XLA sees all k chunks at once and pipelines their collectives
    itself), finish (truncate / split / reshape), replicated outputs."""
    n = mesh.shape[axis]
    total = int(sum(numels))
    plen = int(sum(layout))
    quant = mode in R.QUANT_MODES
    repl = NamedSharding(mesh, P())

    def kernel(v):  # [1, plen] per device — this rank's padded row
        x = v[0]
        outs = []
        off = 0
        for clen in layout:
            xc = lax.dynamic_slice_in_dim(x, off, clen)
            off += clen
            if quant:
                outs.append(_chunk_quant(xc, axis, n, average, mode,
                                         block, prescale, postscale))
            else:
                outs.append(_chunk_fp32(xc, axis, n, average, prescale,
                                        postscale))
        return outs[0] if len(outs) == 1 else jnp.concatenate(outs)

    kern = shard_map(kernel, mesh=mesh, in_specs=P(axis), out_specs=P(),
                     check_vma=False)

    def fn(xs):
        rows = xs[0].shape[0]
        flat = (xs[0].reshape(rows, -1) if len(xs) == 1 else
                jnp.concatenate([x.reshape(rows, -1) for x in xs],
                                axis=1))
        if plen != total:
            flat = jnp.concatenate(
                [flat, jnp.zeros((rows, plen - total), flat.dtype)],
                axis=1)
        full = kern(flat)[:total]
        outs = []
        off = 0
        for numel, shape in zip(numels, shapes):
            outs.append(lax.dynamic_slice_in_dim(full, off, numel)
                        .reshape(shape).astype(dtype))
            off += numel
        return outs

    return jax.jit(fn, out_shardings=[repl] * len(numels))


def execute_allreduce(xs: Sequence[Any], op, *, descriptor: str,
                      precision: str = "fp32", prescale: float = 1.0,
                      postscale: float = 1.0, process_set=None,
                      name: str = "allreduce") -> list:
    """Run a (possibly fused) allreduce group through the compiled
    single-program backend named by ``descriptor``
    (``"compiled:rs_ag:<k>"``).

    Same call contract as :func:`.executor.execute_allreduce`; the
    difference is purely backend — one cached jitted program, zero
    per-unit dispatches (``hvd_sched_dispatches_total`` never moves on
    this path; ``hvd_sched_compiled_dispatches_total`` counts instead).
    """
    from .. import collectives as C
    from ... import context as ctx_mod
    chunks = parse_compiled_descriptor(descriptor)
    if chunks is None:
        raise ValueError(
            f"unknown compiled schedule descriptor {descriptor!r}")
    if precision in ("bf16", "fp16"):
        # Same backstop as the dispatched executor: resolve_schedule
        # never admits cast modes into any decomposed family.
        raise ValueError(
            f"compiled schedule does not support cast wire mode "
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
    key = C._sig(mesh, axis, "sched_compiled", descriptor, op, dtype.name,
                 numels, shapes, mode, block,
                 float(prescale), float(postscale))
    average = op is C.ReduceOp.AVERAGE
    prog = C._cache.get_or_build(
        key, lambda: _build_compiled(mesh, axis, average, mode, numels,
                                     shapes, dtype, float(prescale),
                                     float(postscale), block, layout))
    if mode != "fp32":
        R.account_wire(mode, total * dtype.itemsize, n, block,
                       itemsize=dtype.itemsize)
    _m_compiled_child(descriptor).inc()

    tl = state.timeline
    tl_on = tl is not None and tl.enabled
    lane = f"{name}/compiled"
    if tl_on:
        tl.start_activity(lane, "SCHED_COMPILED")
    t0 = time.monotonic()
    results = prog(list(arrs))
    t1 = time.monotonic()
    if tl_on:
        tl.end_activity(lane)
    # One program, one window: the whole pipeline's host dispatch time.
    # Overlap is invisible from the host here — it happens inside the
    # executable — so the comm window carries everything and the perf
    # model's compiled arm (steps = one ring, not k rings) supplies the
    # matching expectation.
    _perf.MODEL.observe_schedule(
        descriptor=descriptor, mode=mode,
        payload_bytes=total * dtype.itemsize, n=n, chunks=len(layout),
        comm_windows=[(t0, t1)], compute_windows=[],
        block=block, itemsize=dtype.itemsize)
    return list(results)
