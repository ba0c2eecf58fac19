"""DistributedOptimizer: synchronous data-parallel gradient averaging.

Reference behavior († ``horovod/torch/optimizer.py`` ``_DistributedOptimizer``,
† ``horovod/tensorflow/__init__.py`` ``DistributedOptimizer`` /
``DistributedGradientTape``, † ``gradient_aggregation.py``):

- per-parameter gradient hooks enqueue async allreduces during backward;
  ``step()`` synchronizes and applies averaged gradients;
- ``backward_passes_per_step=N`` accumulates N micro-batch gradients locally
  before one allreduce (local gradient aggregation);
- optional fp16 compression on the wire; optional Adasum reduction.

TPU-native redesign.  On TPU the training step is one compiled program, so
"hook + background negotiation" would fight the compiler.  Instead the
averaging *is part of the jitted step*, expressed with a collective the
compiler schedules (and fuses/overlaps with backward compute — XLA's latency
hiding replaces Horovod's comm/compute-overlap machinery):

- :func:`DistributedOptimizer` wraps any optax ``GradientTransformation`` so
  its ``update()`` cross-replica-averages gradients first.  Use it inside a
  ``shard_map``/``pmap`` step over the data-parallel axis — the Horovod-style
  explicit-SPMD form.
- For plain-``jit``-with-shardings training (compiler-inserted collectives),
  no wrapper is needed; this module still adds value via
  ``backward_passes_per_step`` accumulation and compression.
- :func:`distributed_gradients` is the eager escape hatch: per-rank gradient
  pytrees reduced through the async engine (fusion, handles) — the direct
  analogue of the reference's hook path, for host-driven loops.
"""

from __future__ import annotations

from typing import Any, NamedTuple, Optional

import jax
import jax.numpy as jnp
import optax
from jax import lax

from ..obs.trace import region
from ..ops import collectives as C
from ..ops.compression import Compression, Compressor, routes_engine_side


def _in_axis_context(axis_name: str) -> bool:
    """True when tracing inside shard_map/pmap over ``axis_name``."""
    try:
        lax.axis_index(axis_name)
        return True
    except NameError:
        return False


def _reduce_in_context(g, axis_name: str, op: C.ReduceOp,
                       compression: type[Compressor]):
    """Average/sum/adasum one gradient leaf across the mapped axis.

    Quantized compressors (``Compression.int8`` / ``fp8``) lower to the
    reduction-algebra's in-context form: shared block scales via
    ``pmax``, then one ``psum`` of the narrow accumulator — 2B/elem on
    the wire instead of 4 (see :mod:`ops.reduction`).  Adasum never
    quantizes (dot-product projections amplify the error).  Under
    ``sched_mode="decomposed"`` or ``"compiled"`` (``HVDTPU_SCHED_MODE``
    / ``HOROVOD_TPU_SCHED_MODE``) the fp32 and quant paths route through
    :func:`ops.sched.overlap_allreduce` instead — the allreduce becomes
    chunked reduce-scatter/allgather chains inside the step's one jitted
    program (for ``compiled`` this IS the single-program contract; for
    ``decomposed`` XLA may still overlap them with the surrounding
    arithmetic); bf16/fp16 cast modes stay monolithic, same rule as the
    engine resolver.
    """
    g_arr = jnp.asarray(g)
    quant = routes_engine_side(compression)
    if op in (C.ReduceOp.AVERAGE, C.ReduceOp.SUM) \
            and jnp.issubdtype(g_arr.dtype, jnp.floating) \
            and (quant or not compression.wire_mode):
        from ..context import global_state
        from .. import config as config_mod
        state = global_state()
        # Trace-time constants; dataclass defaults before init().
        cfg = state.config if state.initialized else config_mod.Config()
        big = int(g_arr.size) * g_arr.dtype.itemsize >= cfg.quant_min_bytes
        # Sub-floor leaves ride fp32, same as the engine path's resolver.
        mode = compression.wire_mode if (quant and big) else "fp32"
        if cfg.sched_mode in ("decomposed", "compiled"):
            # Same eligibility rules as the engine's resolve_schedule:
            # only fp32 and the quant wire modes decompose (bf16/fp16
            # cast stays monolithic — see its docstring), so the
            # gradient allreduce inside a jitted train step chunks into
            # reduce-scatter/allgather chains XLA can overlap.  The
            # compiled mode takes the same in-graph chains: inside a
            # jitted train step the whole step ALREADY IS one program —
            # this branch is the compiled path end to end, with zero
            # engine dispatches (the CI compiled-parity job asserts the
            # per-chunk dispatch counter stays at 0), and only the eager
            # engine route differs between the two modes.
            from ..ops.sched import overlap_allreduce
            return overlap_allreduce(
                g_arr, axis_name, average=op is C.ReduceOp.AVERAGE,
                mode=mode, chunks=cfg.sched_chunks,
                block=cfg.quant_block_size)
        if quant and big:
            from ..ops.reduction import in_context_allreduce
            return in_context_allreduce(
                g_arr, axis_name, mode,
                average=op is C.ReduceOp.AVERAGE,
                block=cfg.quant_block_size)
    wire, ctx = compression.compress(g)
    if op is C.ReduceOp.AVERAGE:
        red = lax.pmean(wire, axis_name)
    elif op is C.ReduceOp.SUM:
        red = lax.psum(wire, axis_name)
    elif op is C.ReduceOp.ADASUM:
        red = _adasum_in_context(wire, axis_name)
    else:
        raise ValueError(f"unsupported gradient reduce op {op}")
    return compression.decompress(red, ctx)


def _adasum_in_context(g, axis_name: str):
    """Adasum combination inside a mapped context († ``adasum/adasum.h``):
    gather per-rank copies, combine pairwise (per-tensor dot/norm rule)."""
    from ..ops.adasum import _pair_combine
    stacked = lax.all_gather(g, axis_name, axis=0)  # [n, *shape]
    vecs = [stacked[i].reshape(-1) for i in range(stacked.shape[0])]
    while len(vecs) > 1:
        nxt = [_pair_combine(vecs[i], vecs[i + 1])
               for i in range(0, len(vecs) - 1, 2)]
        if len(vecs) % 2:
            nxt.append(vecs[-1])
        vecs = nxt
    return vecs[0].reshape(g.shape)


class _AggState(NamedTuple):
    """State for local gradient aggregation († ``LocalGradientAggregationHelper``)."""
    inner: Any
    acc: Any
    counter: jnp.ndarray  # int32 scalar


def DistributedGradientTransformation(
    inner: optax.GradientTransformation,
    *,
    op: C.ReduceOp = C.ReduceOp.AVERAGE,
    axis_name: str = "hvd",
    backward_passes_per_step: int = 1,
    compression: type[Compressor] = Compression.none,
    average_aggregated_gradients: bool = True,
) -> optax.GradientTransformation:
    """Wrap an optax transformation with cross-replica gradient reduction.

    Use inside a ``shard_map``/``pmap``-mapped train step whose data axis is
    ``axis_name``.  With ``backward_passes_per_step > 1``, gradients
    accumulate locally and the (one) collective fires every N-th update;
    off-cycle updates are zero (parameters unchanged), matching the
    reference's aggregation helper semantics.
    """
    if backward_passes_per_step < 1:
        raise ValueError("backward_passes_per_step must be >= 1")

    def reduce_grads(grads):
        with region("exchange"):
            return jax.tree.map(
                lambda g: _reduce_in_context(g, axis_name, op, compression),
                grads)

    def inner_update(grads, state, params):
        with region("optim"):
            return inner.update(grads, state, params)

    if backward_passes_per_step == 1:
        def init(params):
            return inner.init(params)

        def update(grads, state, params=None):
            return inner_update(reduce_grads(grads), state, params)

        return optax.GradientTransformation(init, update)

    n = backward_passes_per_step

    def init(params):
        return _AggState(
            inner=inner.init(params),
            acc=jax.tree.map(jnp.zeros_like, params),
            counter=jnp.zeros((), jnp.int32))

    def update(grads, state, params=None):
        # Accumulate in the GRADIENT dtype: ``init`` seeds the
        # accumulator as zeros_like(params), and with bf16 params +
        # fp32 grads a param-dtype accumulator would round every
        # micro-batch's contribution onto the bf16 grid before the sum.
        # The explicit widen keeps the accumulator in the grad dtype
        # from the first pass on (zeros cast losslessly).
        acc = jax.tree.map(lambda a, g: a.astype(g.dtype) + g,
                           state.acc, grads)
        counter = state.counter + 1
        is_step = counter >= n

        def do_step(operand):
            acc_, inner_state = operand
            if average_aggregated_gradients:
                scaled = jax.tree.map(lambda a: a / n, acc_)
            else:
                scaled = acc_
            reduced = reduce_grads(scaled)
            updates, new_inner = inner_update(reduced, inner_state, params)
            return updates, new_inner, jax.tree.map(jnp.zeros_like, acc_), \
                jnp.zeros((), jnp.int32)

        def skip_step(operand):
            acc_, inner_state = operand
            zeros = jax.tree.map(jnp.zeros_like, acc_)
            return zeros, inner_state, acc_, counter

        updates, new_inner, new_acc, new_counter = lax.cond(
            is_step, do_step, skip_step, (acc, state.inner))
        return updates, _AggState(new_inner, new_acc, new_counter)

    return optax.GradientTransformation(init, update)


# Horovod-familiar alias: ``hvd.DistributedOptimizer(opt)``.
DistributedOptimizer = DistributedGradientTransformation


def distributed_gradients(per_rank_grads: Any,
                          op: C.ReduceOp = C.ReduceOp.AVERAGE,
                          *, compression: type[Compressor] = Compression.none,
                          process_set=None) -> Any:
    """Eager reduction of a pytree of per-rank gradients via the async engine.

    The host-loop analogue of the reference's hook path: every leaf (shape
    ``[num_ranks, ...]``) is enqueued async — so the engine fuses them into
    as few compiled collectives as possible — then synchronized, returning
    the reduced pytree.  † ``allreduce_async_`` + ``synchronize()``.
    """
    import horovod_tpu as hvd
    leaves, treedef = jax.tree.flatten(per_rank_grads)
    # Quantized compressors route as wire modes: the engine quantizes
    # inside the fused collective (host-side int8 values with per-rank
    # scales could not be summed by a plain allreduce).
    kw = {"compression": compression} if routes_engine_side(compression) \
        else {}
    compressed, ctxs = [], []
    for leaf in leaves:
        if kw:
            wire, ctx = jnp.asarray(leaf), None
        else:
            wire, ctx = compression.compress(jnp.asarray(leaf))
        compressed.append(wire)
        ctxs.append(ctx)
    handles = [hvd.allreduce_async(leaf, op, process_set=process_set, **kw)
               for leaf in compressed]
    # Engine-side (quantized) compressors dequantize inside the fused
    # collective — the engine output is already fp32, so the host-side
    # decompress must NOT run again (a lossy Compressor whose decompress
    # is not the identity would corrupt the result).
    reduced = [h.wait() if kw else compression.decompress(h.wait(), ctx)
               for h, ctx in zip(handles, ctxs)]
    return jax.tree.unflatten(treedef, reduced)


def broadcast_optimizer_state(opt_state: Any, root_rank: int = 0) -> Any:
    """† ``hvd.broadcast_optimizer_state`` — sync optimizer state from root."""
    import horovod_tpu as hvd
    return hvd.broadcast_parameters(opt_state, root_rank=root_rank)
