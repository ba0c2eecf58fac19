"""In-jit schedule entry points: decomposed collectives inside an
already-mapped region (shard_map/pmap body).

The engine-side executor (:mod:`.executor`) owns host-dispatched
collectives; these helpers serve callers that are *already inside* a
compiled program — jitted train steps, the llama decode projections —
where the schedule must be expressed as graph structure and the overlap
is realized by XLA's latency-hiding scheduler (on TPU, async collective
start/done pairs; the CPU rig serializes, same caveat as everywhere).

``matmul_reducescatter`` is the fused computation-collective form (per
"Optimizing Distributed ML Communication with Fused Computation-
Collective Operations", PAPERS.md): a row-parallel projection
``psum(x @ w)`` chunked along the output dim so chunk *c*'s
reduce-scatter can run under chunk *c+1*'s partial matmul.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax

from jax.lax import axis_size
from .. import reduction as R
from .ir import Schedule
from .lower import chunk_layout


def overlap_allreduce(x: jax.Array, axis_name: str, *, average: bool = True,
                      mode: str = "fp32", chunks: int = 2,
                      block: int = 512) -> jax.Array:
    """Chunked reduce-scatter/allgather allreduce of one already-mapped
    tensor — the in-graph analogue of the engine executor, composing with
    the wire-precision algebras the same way.

    Each chunk is an independent ``[encode] -> psum_scatter -> combine ->
    all_gather [-> decode]`` chain; XLA is free to overlap chain *c+1*'s
    collective with chain *c*'s arithmetic.  Falls back to the monolithic
    form when the payload is too small to chunk or the mesh axis is
    trivial.  Results are bit-identical to ``lax.psum`` (fp32) /
    :func:`reduction.in_context_allreduce` numerics (quantized modes use
    the identical shared-scale pipeline, per chunk).
    """
    n = axis_size(axis_name)
    if n <= 1:
        return x
    alg = R.algebra_for(mode)
    quant = mode in R.QUANT_MODES
    cast = mode in ("bf16", "fp16")
    out_dtype = x.dtype
    flat = (x.astype(jnp.float32) if quant else x).reshape(-1)
    numel = flat.shape[0]
    layout = chunk_layout(numel, n, max(1, chunks), mode, block)
    plen = sum(layout)
    if plen != numel:
        flat = jnp.concatenate(
            [flat, jnp.zeros((plen - numel,), flat.dtype)])
    outs = []
    off = 0
    for clen in layout:
        ch = lax.dynamic_slice_in_dim(flat, off, clen)
        off += clen
        if quant:
            blocks = ch.reshape(clen // block, block)
            shared = alg.scale_from_absmax(
                lax.pmax(alg.block_absmax(blocks), axis_name))
            q, _ = alg.wire_encode(blocks, shared_scale=shared)
            acc = lax.psum_scatter(
                q.astype(alg.acc_dtype).reshape(-1), axis_name,
                scatter_dimension=0, tiled=True)
            sblocks = (clen // block) // n
            me = lax.axis_index(axis_name)
            my_scale = lax.dynamic_slice_in_dim(
                shared, me * sblocks, sblocks)
            accf = alg.wire_decode(acc.reshape(sblocks, block), my_scale)
            if average:
                accf = accf / n
            w2, s2 = alg.wire_encode(accf)
            gw = lax.all_gather(w2.reshape(-1), axis_name, axis=0,
                                tiled=True)
            gs = lax.all_gather(s2, axis_name, axis=0, tiled=True)
            outs.append(alg.wire_decode(
                gw.reshape(clen // block, block), gs).reshape(-1))
        elif cast:
            sh = lax.psum_scatter(alg.wire_encode(ch)[0], axis_name,
                                  scatter_dimension=0, tiled=True)
            g = alg.wire_decode(
                lax.all_gather(sh, axis_name, axis=0, tiled=True), None)
            outs.append(g / n if average else g)
        else:
            sh = lax.psum_scatter(ch, axis_name, scatter_dimension=0,
                                  tiled=True)
            if average:
                sh = sh / n
            outs.append(lax.all_gather(sh, axis_name, axis=0, tiled=True))
    out = (outs[0] if len(outs) == 1 else jnp.concatenate(outs))[:numel]
    return out.reshape(x.shape).astype(out_dtype)


def overlap_reducescatter(flat: jax.Array, axis_name: str, *,
                          layout, average: bool = True,
                          mode: str = "fp32",
                          block: int = 512) -> jax.Array:
    """The :func:`overlap_allreduce` chain STOPPED at the shard — the
    ZeRO-1 half: per chunk ``[encode] -> psum_scatter -> combine`` with
    **no** gradient allgather; the caller closes the step with one
    *parameter* allgather instead (:mod:`optim.zero`).

    ``flat`` must already be padded to ``sum(layout)`` (fp32 for the
    quant modes, matching ``overlap_allreduce``'s internal cast); each
    ``layout`` entry must divide by the axis size (and by ``n * block``
    for quant modes) — :func:`~.lower.chunk_layout` guarantees both.
    Returns the rank's ``sum(layout)/n`` shard in chunk-major order.

    Numerics are bit-identical to the corresponding elements of
    ``overlap_allreduce``'s output: the quant path re-applies the same
    post-combine requantization roundtrip the dense chain wires through
    its allgather, so a ZeRO step and a dense step see the exact same
    reduced-gradient bits for every element of the shard.
    """
    n = axis_size(axis_name)
    if n <= 1:
        return flat
    alg = R.algebra_for(mode)
    quant = mode in R.QUANT_MODES
    outs = []
    off = 0
    for clen in layout:
        ch = lax.dynamic_slice_in_dim(flat, off, clen)
        off += clen
        if quant:
            blocks = ch.reshape(clen // block, block)
            shared = alg.scale_from_absmax(
                lax.pmax(alg.block_absmax(blocks), axis_name))
            q, _ = alg.wire_encode(blocks, shared_scale=shared)
            acc = lax.psum_scatter(
                q.astype(alg.acc_dtype).reshape(-1), axis_name,
                scatter_dimension=0, tiled=True)
            sblocks = (clen // block) // n
            me = lax.axis_index(axis_name)
            my_scale = lax.dynamic_slice_in_dim(
                shared, me * sblocks, sblocks)
            accf = alg.wire_decode(acc.reshape(sblocks, block), my_scale)
            if average:
                accf = accf / n
            # Dense parity: the dense chain requantizes the combined
            # shard onto the wire for its allgather; replay the same
            # encode/decode roundtrip so shard bits match exactly.
            w2, s2 = alg.wire_encode(accf)
            outs.append(alg.wire_decode(w2, s2).reshape(-1))
        else:
            sh = lax.psum_scatter(ch, axis_name, scatter_dimension=0,
                                  tiled=True)
            if average:
                sh = sh / n
            outs.append(sh)
    return outs[0] if len(outs) == 1 else jnp.concatenate(outs)


def matmul_reducescatter(x: jax.Array, w: jax.Array, axis_name: str, *,
                         chunks: int = 2) -> jax.Array:
    """Row-parallel projection ``psum(x @ w, axis)`` as a chunked
    partial-matmul + reduce-scatter fusion, allgathered back.

    ``x``: [..., K_local] (contraction dim sharded over ``axis_name``);
    ``w``: [K_local, D].  The output dim D is split into ``chunks``
    column slices; per slice the partial product reduce-scatters over the
    axis (each rank owns D/(n·chunks) columns of the sum) and an
    allgather rebuilds the replicated slice — elementwise the same sums
    as ``lax.psum``, so results are bit-identical on backends whose
    psum/psum_scatter share the accumulation order (asserted on the CPU
    rig in tests/test_sched.py).  Falls back to the plain ``psum`` when D
    does not split evenly or the axis/chunking is trivial.
    """
    n = axis_size(axis_name)
    d = w.shape[-1]
    if n <= 1 or chunks <= 1 or d % (n * chunks):
        return lax.psum(jnp.matmul(x, w), axis_name)
    csz = d // chunks
    outs = []
    for c in range(chunks):
        wc = lax.slice_in_dim(w, c * csz, (c + 1) * csz, axis=-1)
        pc = jnp.matmul(x, wc)                        # [..., csz]
        sh = lax.psum_scatter(pc, axis_name,
                              scatter_dimension=pc.ndim - 1, tiled=True)
        outs.append(lax.all_gather(sh, axis_name, axis=pc.ndim - 1,
                                   tiled=True))
    return jnp.concatenate(outs, axis=-1)


def run_in_context(schedule: Schedule, x: jax.Array, *,
                   average: bool = False) -> jax.Array:
    """Interpret a (single-chunk) schedule in-graph on a mapped tensor.

    The interpreter for schedules whose steps operate on the whole
    buffer — today the two-tier hierarchical family
    (:func:`~.lower.lower_hierarchical`): reduce-scatter and allgather
    steps pad/scatter over their tier's axis, ``all_reduce`` runs on the
    scattered shard, ``combine`` applies the AVERAGE divide over every
    axis reduced so far.  ``ops/hierarchical.py`` routes through here, so
    the two-level path and the engine's chunked path share one IR.
    """
    shape = x.shape
    flat = x.reshape(-1)
    pad_total = 0
    denom = 1
    for s in schedule.interleaved_order():
        if s.kind == "reduce_scatter":
            n = axis_size(s.axis)
            denom *= n
            pad = (-flat.size) % n
            if pad:
                flat = jnp.concatenate(
                    [flat, jnp.zeros((pad,), flat.dtype)])
                pad_total += pad
            flat = lax.psum_scatter(flat, s.axis, scatter_dimension=0,
                                    tiled=True)
        elif s.kind == "all_reduce":
            denom *= axis_size(s.axis)
            flat = lax.psum(flat, s.axis)
        elif s.kind == "combine":
            if average and denom > 1:
                flat = flat / denom
        elif s.kind == "all_gather":
            flat = lax.all_gather(flat, s.axis, axis=0, tiled=True)
        # chunk/concat/barrier/encode/decode: no-ops for this family.
    if pad_total:
        flat = flat[:-pad_total]
    return flat.reshape(shape)
