"""ZeRO-1 sharded optimizer on the reduce-scatter/allgather decomposition.

The schedule IR (PR 7/16) already lowers every gradient allreduce into
chunked reduce-scatter/allgather chains — but the dense
:func:`~.distributed.DistributedOptimizer` immediately allgathers the
gradient back and keeps FULL Adam state on every rank, throwing away the
1/n shard the reduce-scatter just produced.
:func:`ZeroDistributedOptimizer` keeps it:

1. gradients lower through the same rs chain but STOP at the shard
   (:func:`~..ops.sched.in_context.overlap_reducescatter` — no gradient
   allgather);
2. the inner optax transformation's ``init``/``update`` run on the 1/n
   parameter shard, so m/v (any inner state) is sharded n ways;
3. ONE parameter-delta allgather per bucket closes the step.

Total wire bytes are identical to the dense path (rs + param-ag == rs +
grad-ag) while optimizer-state memory drops to ``1/n`` of dense plus the
shard-divisible padding (:mod:`.partition`); the ``hvd_zero_state_bytes``
gauge publishes the per-rank state footprint.

Parity contract (asserted in tests/test_optimizer.py and the
``zero1-parity`` CI job): updated parameters are bit-exact vs the dense
``DistributedOptimizer`` at np=2 for fp32 and the int8 wire, and within
2 ulp at np>=4, across all three ``HOROVOD_TPU_SCHED_MODE``s.  The quant
modes stay exact because bucket flattening pads every leaf to the same
``n * block`` unit the dense chunk layout uses, so quant *block*
boundaries — and therefore every shared scale — land identically, and
the shard chain replays the dense path's post-combine requantization
roundtrip.  In ``compiled`` mode the whole ZeRO step stays one jitted
program (``hvd_sched_dispatches_total == 0``, same guard as the dense
compiled path).

Restrictions: elementwise inner transformations (Adam/SGD/AdamW-style —
each element's update depends only on that element's grad/param/state);
``op`` must be AVERAGE or SUM (Adasum's dot-product projections need the
full gradient); ``update`` must run inside the mapped context
(shard_map/pmap over ``axis_name``), same as the dense wrapper.
"""

from __future__ import annotations

from typing import Any, Optional

import jax
import jax.numpy as jnp
import optax
from jax import lax

from jax.lax import axis_size
from ..obs import REGISTRY as _obs
from ..ops import collectives as C
from ..ops.compression import Compression, Compressor, routes_engine_side
from .distributed import _in_axis_context, _reduce_in_context
from . import partition as P

_g_state_bytes = _obs.gauge(
    "hvd_zero_state_bytes",
    "per-rank optimizer-state bytes under the ZeRO-1 sharded optimizer "
    "(sharded inner state; ~1/n of the dense footprint plus padding)")


def _resolved_config():
    from ..context import global_state
    from .. import config as config_mod
    state = global_state()
    return state.config if state.initialized else config_mod.Config()


def _resolve_n(axis_name: str, num_shards: Optional[int]) -> int:
    if num_shards is not None:
        return int(num_shards)
    if _in_axis_context(axis_name):
        return axis_size(axis_name)
    from ..context import global_state
    state = global_state()
    if state.initialized:
        return state.size
    raise ValueError(
        "ZeroDistributedOptimizer.init called outside the mapped context "
        "before hvd.init(); pass num_shards= explicitly")


def _leaf_modes(leaves, compression, cfg) -> list:
    """Resolved wire mode per leaf — the same eligibility rule the dense
    ``_reduce_in_context`` applies (sub-floor leaves ride fp32)."""
    quant = routes_engine_side(compression)
    modes = []
    for leaf in leaves:
        arr = jnp.asarray(leaf)
        big = int(arr.size) * arr.dtype.itemsize >= cfg.quant_min_bytes
        eligible = quant and big and jnp.issubdtype(arr.dtype,
                                                    jnp.floating)
        modes.append(compression.wire_mode if eligible else "fp32")
    return modes


def ZeroDistributedOptimizer(
    inner: optax.GradientTransformation,
    partition: int = 1,
    *,
    op: C.ReduceOp = C.ReduceOp.AVERAGE,
    axis_name: str = "hvd",
    compression: type[Compressor] = Compression.none,
    bucket_bytes: Optional[int] = None,
    num_shards: Optional[int] = None,
) -> optax.GradientTransformation:
    """Wrap ``inner`` as a ZeRO-1 sharded optimizer (see module docs).

    ``partition=1`` is the supported stage (optimizer-state sharding);
    stages 2/3 (gradient/parameter sharding) are out of scope here.
    ``bucket_bytes`` overrides ``HOROVOD_TPU_BUCKET_BYTES`` (<=0 means
    one bucket per dtype/wire-mode group).  ``num_shards`` pins the
    shard count when ``init`` runs outside the mapped context on a mesh
    smaller than the world (e.g. an np-subset bench mesh).
    """
    if partition != 1:
        raise NotImplementedError(
            f"ZeRO stage {partition} is not supported; only stage 1 "
            "(optimizer-state sharding) is implemented")
    if op not in (C.ReduceOp.AVERAGE, C.ReduceOp.SUM):
        raise ValueError(
            f"ZeroDistributedOptimizer supports AVERAGE/SUM, got {op}")

    # The plan is static (shapes + config), so it is latched once and
    # every rank recomputes the identical object; ``update`` rebuilds it
    # from the gradients when ``init`` never ran (restored state).
    holder: dict = {}

    def _build(tree, n, cfg):
        leaves = jax.tree.flatten(tree)[0]
        bb = cfg.bucket_bytes if bucket_bytes is None else bucket_bytes
        plan = P.build_plan(
            tree, n, modes=_leaf_modes(leaves, compression, cfg),
            block=cfg.quant_block_size,
            chunks=max(1, cfg.sched_chunks), bucket_bytes=int(bb or 0))
        holder["plan"] = plan
        return plan

    def _shard_params(plan, leaves, me):
        shards = []
        for bucket in plan.buckets:
            layout = P.bucket_layout(plan, bucket)
            flat = P.flatten_bucket(bucket, leaves)
            shards.append(P.extract_shard(flat, me, layout, plan.n))
        return tuple(shards)

    def init(params):
        cfg = _resolved_config()
        n = _resolve_n(axis_name, num_shards)
        plan = _build(params, n, cfg)
        leaves = jax.tree.flatten(params)[0]
        if _in_axis_context(axis_name):
            shard = _shard_params(plan, leaves, lax.axis_index(axis_name))
        else:
            # Outside the mapped context the rank is unknown; standard
            # scale_by_* inits are value-independent (zeros_like), so a
            # zero-valued shard template of the right shape/dtype is
            # exact for them.  Value-dependent inits need in-context
            # init (call ``tx.init`` inside the shard_map body).
            shard = tuple(
                jnp.zeros((b.shard,), b.dtype) for b in plan.buckets)
        state = inner.init(shard)
        try:
            _g_state_bytes.set(float(P.shard_bytes(state)))
        except Exception:  # telemetry must never break a step
            pass
        return state

    def update(grads, state, params=None):
        if not _in_axis_context(axis_name):
            raise ValueError(
                "ZeroDistributedOptimizer.update must run inside the "
                f"mapped context (shard_map/pmap over {axis_name!r})")
        cfg = _resolved_config()
        n = axis_size(axis_name)
        plan = holder.get("plan")
        if plan is None or plan.n != n:
            plan = _build(grads, n, cfg)
        me = lax.axis_index(axis_name)
        gleaves, gdef = jax.tree.flatten(grads)
        pleaves = jax.tree.flatten(params)[0] if params is not None \
            else None
        average = op is C.ReduceOp.AVERAGE
        decompose = cfg.sched_mode in ("decomposed", "compiled") and \
            (routes_engine_side(compression) or not compression.wire_mode)
        shard_grads, shard_params, layouts = [], [], []
        for bucket in plan.buckets:
            layout = P.bucket_layout(plan, bucket)
            layouts.append(layout)
            quant = bucket.mode != "fp32"
            flat = P.flatten_bucket(bucket, gleaves)
            gdtype = flat.dtype
            if decompose and jnp.issubdtype(gdtype, jnp.floating):
                # The rs chain stopped at the shard: the ZeRO half of
                # the dense overlap_allreduce, chunk boundaries and
                # quant blocks identical by construction.
                from ..ops.sched import overlap_reducescatter
                if quant:
                    flat = flat.astype(jnp.float32)
                shard = overlap_reducescatter(
                    flat, axis_name, layout=layout, average=average,
                    mode=bucket.mode, block=plan.block)
                shard = shard.astype(gdtype)
            else:
                # Monolithic / cast-wire fallback: the exact dense
                # reduce per leaf, then slice this rank's shard — parity
                # is trivially bit-exact, memory still shards.
                reduced = list(gleaves)
                for spec in bucket.leaves:
                    reduced[spec.index] = _reduce_in_context(
                        gleaves[spec.index], axis_name, op, compression)
                rflat = P.flatten_bucket(bucket, reduced)
                shard = P.extract_shard(rflat, me, layout, plan.n)
            shard_grads.append(shard)
            if pleaves is not None:
                pflat = P.flatten_bucket(bucket, pleaves)
                shard_params.append(
                    P.extract_shard(pflat, me, layout, plan.n))
        sp = tuple(shard_params) if pleaves is not None else None
        shard_updates, new_state = inner.update(
            tuple(shard_grads), state, sp)
        out = [None] * len(gleaves)
        for bucket, layout, ush in zip(plan.buckets, layouts,
                                       shard_updates):
            # The ONE parameter allgather that closes the ZeRO step
            # (per bucket; buckets never mix dtypes or wire modes).
            gathered = lax.all_gather(ush, axis_name, axis=0, tiled=True)
            full = P.assemble_from_shards(gathered, layout, plan.n)
            for idx, arr in P.unflatten_bucket(bucket, full):
                out[idx] = arr
        return jax.tree.unflatten(gdef, out), new_state

    return optax.GradientTransformation(init, update)


def from_config(
    inner: optax.GradientTransformation,
    **kwargs: Any,
) -> optax.GradientTransformation:
    """``HOROVOD_TPU_ZERO`` dispatcher: the ZeRO-1 wrapper when
    ``cfg.zero`` is set, the dense :func:`DistributedOptimizer`
    otherwise — so train-step builders and benches flip between the two
    with one env knob."""
    if _resolved_config().zero:
        return ZeroDistributedOptimizer(inner, **kwargs)
    from .distributed import DistributedGradientTransformation
    kwargs.pop("bucket_bytes", None)
    kwargs.pop("num_shards", None)
    return DistributedGradientTransformation(inner, **kwargs)
