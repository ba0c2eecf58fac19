"""Pipeline parallelism: GPipe-style microbatch streaming over the pp axis.

ABSENT in the reference (SURVEY §2.6).  TPU-native design: stage parameters
are stacked along a leading ``[pp, ...]`` dimension sharded over the ``pp``
mesh axis; inside ``shard_map`` every device runs the *same* program (SPMD)
and hands activations to its successor with ``ppermute`` — the point-to-point
collective that tolerates DCN, which is why pp is the outermost mesh axis
(see :mod:`horovod_tpu.parallel.mesh`).

Schedule: GPipe fill-drain with M microbatches over S stages: T = M + S - 1
ticks.  At tick t, the device at stage s processes microbatch ``t - s`` when
``0 <= t - s < M`` and garbage otherwise (masked out).  Bubble fraction
(S-1)/(M+S-1) — callers pick M >= 4·S to keep it small.  The tick loop is a
``lax.scan`` (compiler-friendly control flow; one compiled body regardless
of M).
"""

from __future__ import annotations

from functools import partial
from typing import Any, Callable

import jax
import jax.numpy as jnp
from jax import lax
from jax import shard_map
from jax.lax import axis_size
from jax.sharding import Mesh, PartitionSpec as P


def pipeline_apply_local(stage_fn: Callable[[Any, jax.Array], jax.Array],
                         stage_params: Any,
                         microbatches: jax.Array, *,
                         axis_name: str = "pp",
                         with_aux: bool = False):
    """Run the pipeline inside a mapped context.

    ``stage_params``: this device's stage parameters (leading pp dim already
    stripped to local, i.e. leaves are one stage's params with a leading
    singleton removed by the caller's in_specs).
    ``microbatches``: [M, mb, ...] — the full microbatch set, replicated
    across pp (each stage only *uses* its inputs when scheduled).
    Returns [M, mb, ...] outputs, valid on the last stage.

    With ``with_aux`` the stage returns ``(y, aux_scalar)``; aux from valid
    ticks is accumulated per stage, psummed over pp (each stage owns
    disjoint layers) and averaged over microbatches; the return becomes
    ``(outputs, aux)``.
    """
    n = axis_size(axis_name)
    idx = lax.axis_index(axis_name)
    M = microbatches.shape[0]
    T = M + n - 1
    perm = [(i, (i + 1) % n) for i in range(n)]

    def tick(carry, t):
        buf, outputs, aux_acc = carry
        # Stage 0 injects microbatch t (when in range); others take the
        # activation handed over from the previous stage.
        mb_idx = jnp.clip(t, 0, M - 1)
        injected = microbatches[mb_idx]
        x = jnp.where(idx == 0, injected, buf)
        res = stage_fn(stage_params, x)
        y, aux = res if with_aux else (res, None)
        if with_aux:
            # This stage processes real data at tick t iff 0 <= t-idx < M.
            live = (t - idx >= 0) & (t - idx < M)
            aux_acc = aux_acc + jnp.where(live, aux, 0.0)
        # The last stage records its result for microbatch t - (n-1).
        out_idx = jnp.clip(t - (n - 1), 0, M - 1)
        is_valid = (t - (n - 1) >= 0) & (t - (n - 1) < M)
        record = jnp.where((idx == n - 1) & is_valid, 1.0, 0.0)
        outputs = outputs.at[out_idx].set(
            jnp.where(record > 0, y, outputs[out_idx]))
        # Hand activations downstream (ring; stage n-1 → 0 is ignored).
        buf = lax.ppermute(y, axis_name, perm)
        return (buf, outputs, aux_acc), None

    buf0 = jnp.zeros_like(microbatches[0])
    out0 = jnp.zeros(microbatches.shape[:1] + _out_shape(
        stage_fn, stage_params, microbatches[0], with_aux),
        microbatches.dtype)
    carry0 = (buf0, out0, jnp.zeros((), jnp.float32))
    (_, outputs, aux_acc), _ = lax.scan(tick, carry0, jnp.arange(T))
    # Broadcast final outputs from the last stage to all pp ranks so the
    # caller sees replicated results (one psum, masked).
    outputs = lax.psum(
        jnp.where(idx == n - 1, outputs, jnp.zeros_like(outputs)), axis_name)
    if with_aux:
        return outputs, lax.psum(aux_acc, axis_name) / M
    return outputs


def _out_shape(stage_fn, params, x, with_aux: bool = False) -> tuple[int, ...]:
    shape = jax.eval_shape(stage_fn, params, x)
    return (shape[0] if with_aux else shape).shape


def pipeline_train_local(stage_fn: Callable[[Any, jax.Array], tuple],
                         stage_params: Any,
                         microbatches: jax.Array,
                         loss_head: Callable[[Any, jax.Array, jax.Array],
                                             jax.Array],
                         head_params: Any, *,
                         axis_name: str = "pp",
                         aux_weight: float = 0.0,
                         seed_scale: float = 1.0):
    """1F1B training schedule inside a mapped context.

    The GPipe path (:func:`pipeline_apply_local` under ``jax.grad``) keeps
    every microbatch's forward state live until the whole backward starts —
    activation memory grows with M.  This schedule interleaves: at tick
    ``t`` stage ``s`` runs the FORWARD of microbatch ``t - s`` and the
    BACKWARD of microbatch ``t - 2(n-1) + s`` (the tick its cotangent
    physically arrives from downstream), so in steady state every tick does
    one forward and one backward and at most ``2(n-1)`` microbatch inputs
    are in flight per stage — a ring buffer of ``2(n-1)`` slots replaces
    GPipe's M-deep saved state.  The backward recomputes the stage forward
    from the saved INPUT (``jax.vjp`` per tick, remat-style), the standard
    memory/compute trade of 1F1B pipelines.

    ``stage_fn(params, x) -> (y, aux_scalar)``.
    ``loss_head(head_params, y, m) -> scalar`` — per-microbatch loss,
    evaluated (and differentiated) on the LAST stage; ``m`` indexes any
    per-microbatch data (targets) the closure carries.  Its gradient seed
    is ``seed_scale`` (callers pass 1/n_data_shards so per-shard local
    means add up to the global mean).  ``aux_weight`` seeds each stage's
    aux output cotangent (microbatch-mean semantics after the final /M).

    Returns ``(loss, aux, d_microbatches, d_stage_params, d_head_params)``:
    loss/aux psummed over the pipeline and microbatch-averaged;
    d_microbatches the cotangent w.r.t. the stage-0 inputs (replicated
    over pp), d_stage_params THIS stage's parameter gradients (fp32),
    d_head_params the loss-head gradients (fp32, psummed over pp).  All
    gradients are for the microbatch-MEAN loss, matching the returned
    ``loss`` (i.e. already divided by M).
    """
    n = axis_size(axis_name)
    if n < 2:
        raise ValueError("pipeline_train_local needs a pp axis of size >= 2")
    s = lax.axis_index(axis_name)
    M = microbatches.shape[0]
    K = 2 * (n - 1)
    T = M + K
    perm_down = [(i, (i + 1) % n) for i in range(n)]
    perm_up = [(i, (i - 1) % n) for i in range(n)]
    f32 = jnp.float32

    zeros_f32 = lambda tree: jax.tree.map(
        lambda l: jnp.zeros(l.shape, f32), tree)

    def mask_add(acc, grads, live):
        return jax.tree.map(
            lambda a, g: a + jnp.where(live, g.astype(f32), 0.0), acc, grads)

    y_aval = jax.eval_shape(stage_fn, stage_params, microbatches[0])[0]

    def tick(carry, t):
        fwd_buf, bwd_buf, ring, gacc, hacc, loss_acc, aux_acc, dmbs = carry
        is_last = s == n - 1
        # ---- backward bookkeeping reads BEFORE the forward write: at
        # stage 0 the bwd slot and this tick's fwd slot coincide (mod K).
        m_b = t - K + s
        live_b = (m_b >= 0) & (m_b < M)
        slot_b = jnp.clip(m_b, 0, M - 1) % K
        x_saved_pre = ring[slot_b]
        # ---- forward ----
        m_f = t - s
        live_f = (m_f >= 0) & (m_f < M)
        mclip_f = jnp.clip(m_f, 0, M - 1)
        x_in = jnp.where(s == 0, microbatches[mclip_f], fwd_buf)
        y, aux_f = stage_fn(stage_params, x_in)
        aux_acc = aux_acc + jnp.where(live_f, aux_f, 0.0)
        slot_f = mclip_f % K
        ring = ring.at[slot_f].set(jnp.where(live_f, x_in, ring[slot_f]))
        # ---- loss head (last stage; its bwd microbatch == m_f this tick)
        lval, head_vjp = jax.vjp(
            lambda hp, yy: loss_head(hp, yy, mclip_f), head_params, y)
        live_loss = live_f & is_last
        loss_acc = loss_acc + jnp.where(live_loss, lval, 0.0)
        dhead_t, dy_seed = head_vjp(jnp.asarray(seed_scale, lval.dtype))
        hacc = mask_add(hacc, dhead_t, live_loss)
        # ---- backward (recompute-from-saved-input vjp) ----
        # Last stage: the saved input for m_b IS this tick's x_in.
        x_bwd = jnp.where(is_last, x_in, x_saved_pre)
        cot_in = jnp.where(is_last, dy_seed, bwd_buf)
        _, stage_vjp = jax.vjp(stage_fn, stage_params, x_bwd)
        # Seeded per tick with weight * seed_scale (the final /M turns the
        # accumulated sum into the same microbatch mean as ``aux``).  The
        # seed_scale factor matters: like the CE seed, the aux cotangent is
        # per-data-shard, and the caller's blanket psum of replicated-param
        # grads over the data axes would otherwise count it n_data times
        # (caught by a round-4 review finite-difference probe: router grad
        # 4x the oracle on a pp*ep*dp mesh).
        aux_seed = jnp.where(
            live_b, jnp.asarray(aux_weight * seed_scale, f32), 0.0)
        dparams, dx = stage_vjp((cot_in, aux_seed))
        gacc = mask_add(gacc, dparams, live_b)
        out_slot = jnp.clip(m_b, 0, M - 1)
        rec = live_b & (s == 0)
        dmbs = dmbs.at[out_slot].set(
            jnp.where(rec, dx, dmbs[out_slot]))
        # ---- handoffs ----
        fwd_buf = lax.ppermute(y, axis_name, perm_down)
        bwd_buf = lax.ppermute(dx, axis_name, perm_up)
        return (fwd_buf, bwd_buf, ring, gacc, hacc, loss_acc, aux_acc,
                dmbs), None

    mb0 = microbatches[0]
    carry0 = (
        jnp.zeros(y_aval.shape, y_aval.dtype),            # fwd handoff
        jnp.zeros(mb0.shape, mb0.dtype),                  # bwd handoff
        jnp.zeros((K,) + mb0.shape, mb0.dtype),           # input ring
        zeros_f32(stage_params),                          # stage grads
        zeros_f32(head_params),                           # head grads
        jnp.zeros((), f32),                               # loss
        jnp.zeros((), f32),                               # aux
        jnp.zeros(microbatches.shape, mb0.dtype),         # d_microbatches
    )
    (_, _, _, gacc, hacc, loss_acc, aux_acc, dmbs), _ = lax.scan(
        tick, carry0, jnp.arange(T))
    loss = lax.psum(jnp.where(s == n - 1, loss_acc, 0.0), axis_name) / M
    aux = lax.psum(aux_acc, axis_name) / M
    inv_m = 1.0 / M
    gacc = jax.tree.map(lambda g: g * inv_m, gacc)
    hacc = jax.tree.map(lambda g: lax.psum(g, axis_name) * inv_m, hacc)
    dmbs = lax.psum(
        jnp.where(s == 0, dmbs, jnp.zeros_like(dmbs)), axis_name) * inv_m
    return loss, aux, dmbs, gacc, hacc


def pipeline_apply(stage_fn: Callable[[Any, jax.Array], jax.Array],
                   stacked_params: Any,
                   microbatches: jax.Array,
                   mesh: Mesh, *,
                   axis_name: str = "pp") -> jax.Array:
    """Standalone entry: ``stacked_params`` leaves have leading dim = pp size
    (stage-major), sharded over ``axis_name``; ``microbatches`` is [M, mb,...]
    replicated.  Returns [M, mb, ...] outputs replicated."""

    def local(params, mb):
        local_params = jax.tree.map(lambda a: a[0], params)
        return pipeline_apply_local(stage_fn, local_params, mb,
                                    axis_name=axis_name)

    fn = shard_map(
        local, mesh=mesh,
        in_specs=(P(axis_name), P()),
        out_specs=P(),
        check_vma=False)
    return jax.jit(fn)(stacked_params, microbatches)
