"""DistributedOptimizer semantics.

Mirrors † ``test/parallel/test_torch.py`` ``test_gradient_aggregation`` /
``test_horovod_allreduce_grad`` and † TF ``gradient_aggregation`` tests:
averaged gradients equal the mean of per-rank gradients; aggregation fires
the collective every N-th call; compression round-trips.
"""

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest
from jax import lax
from jax import shard_map
from jax.sharding import NamedSharding, PartitionSpec as P

import horovod_tpu as hvd
from horovod_tpu.ops.compression import Compression

N = 8


def _mapped_update(tx, grads_per_rank, params):
    """Run tx.update inside shard_map over the hvd axis, one grad per rank."""
    mesh = hvd.mesh()
    opt_state = tx.init(params)

    def step(g, p):
        local = jax.tree.map(lambda a: a[0], g)   # strip rank dim
        updates, _ = tx.update(local, opt_state, p)
        return jax.tree.map(lambda u: u[None], updates)

    fn = shard_map(step, mesh=mesh, in_specs=(P("hvd"), P()),
                   out_specs=P("hvd"), check_vma=False)
    out = jax.jit(fn)(grads_per_rank, params)
    return out


def test_update_averages_across_ranks():
    params = {"w": jnp.zeros((4,), jnp.float32)}
    tx = hvd.DistributedOptimizer(optax.sgd(1.0))
    grads = hvd.per_rank([np.full((4,), float(r), np.float32)
                          for r in range(N)])
    updates = _mapped_update(tx, {"w": grads}, params)["w"]
    # SGD lr=1: update = -mean(grads) = -3.5, identical on every rank.
    got = hvd.to_numpy(updates)
    np.testing.assert_allclose(got, np.full((N, 4), -3.5), rtol=1e-6)


def test_update_sum_op():
    params = {"w": jnp.zeros((2,), jnp.float32)}
    tx = hvd.DistributedOptimizer(optax.sgd(1.0), op=hvd.Sum)
    grads = hvd.per_rank([np.full((2,), 1.0, np.float32)] * N)
    updates = _mapped_update(tx, {"w": grads}, params)["w"]
    np.testing.assert_allclose(hvd.to_numpy(updates), np.full((N, 2), -8.0),
                               rtol=1e-6)


def test_fp16_compression_roundtrip():
    params = {"w": jnp.zeros((3,), jnp.float32)}
    tx = hvd.DistributedOptimizer(optax.sgd(1.0),
                                  compression=Compression.fp16)
    grads = hvd.per_rank([np.full((3,), float(r), np.float32)
                          for r in range(N)])
    updates = _mapped_update(tx, {"w": grads}, params)["w"]
    got = hvd.to_numpy(updates)
    assert got.dtype == np.float32          # decompressed back
    np.testing.assert_allclose(got, np.full((N, 3), -3.5), rtol=1e-2)


def test_update_decomposed_schedule_parity():
    """sched_mode=decomposed routes the in-step gradient allreduce
    through ops.sched.overlap_allreduce; fp32 updates must be
    bit-identical to the monolithic psum path."""
    state = hvd.global_state()
    cfg = state.config
    params = {"w": jnp.zeros((3000,), jnp.float32)}
    grads = hvd.per_rank(
        [np.random.RandomState(r).randn(3000).astype(np.float32)
         for r in range(N)])
    tx = hvd.DistributedOptimizer(optax.sgd(1.0))
    base = hvd.to_numpy(_mapped_update(tx, {"w": grads}, params)["w"])
    old = (cfg.sched_mode, cfg.sched_chunks)
    cfg.sched_mode, cfg.sched_chunks = "decomposed", 3
    try:
        got = hvd.to_numpy(_mapped_update(tx, {"w": grads}, params)["w"])
    finally:
        cfg.sched_mode, cfg.sched_chunks = old
    assert np.array_equal(got, base)


def test_update_compiled_schedule_single_program():
    """sched_mode=compiled: the optax train step is ONE jitted program —
    updates bit-identical to the monolithic psum path AND the engine's
    per-chunk schedule dispatch counter never moves (inside jit the
    whole step already is one executable; this is the invariant the CI
    compiled-parity job's zero-dispatch guard pins at np=2/4)."""
    from horovod_tpu.ops.sched.executor import _m_sched
    cfg = hvd.global_state().config
    params = {"w": jnp.zeros((3000,), jnp.float32)}
    grads = hvd.per_rank(
        [np.random.RandomState(40 + r).randn(3000).astype(np.float32)
         for r in range(N)])
    tx = hvd.DistributedOptimizer(optax.sgd(1.0))
    base = hvd.to_numpy(_mapped_update(tx, {"w": grads}, params)["w"])
    old = (cfg.sched_mode, cfg.sched_chunks)
    before = _m_sched.total()
    cfg.sched_mode, cfg.sched_chunks = "compiled", 3
    try:
        got = hvd.to_numpy(_mapped_update(tx, {"w": grads}, params)["w"])
    finally:
        cfg.sched_mode, cfg.sched_chunks = old
    assert np.array_equal(got, base)
    assert _m_sched.total() == before


def test_update_decomposed_quant_within_bound():
    """Decomposed + int8 wire: the update stays inside the documented
    shared-scale quantization bound of the exact mean (the decomposed
    form re-quantizes the combined shard before the allgather, so it is
    close to — not bit-equal to — the monolithic quant path)."""
    state = hvd.global_state()
    cfg = state.config
    old = (cfg.sched_mode, cfg.sched_chunks, cfg.quant_min_bytes)
    g_np = np.stack([np.random.RandomState(100 + r).randn(4096)
                     .astype(np.float32) for r in range(N)])
    params = {"w": jnp.zeros((4096,), jnp.float32)}
    tx = hvd.DistributedOptimizer(optax.sgd(1.0),
                                  compression=Compression.int8)
    cfg.sched_mode, cfg.sched_chunks = "decomposed", 2
    cfg.quant_min_bytes = 1024
    try:
        got = hvd.to_numpy(
            _mapped_update(tx, {"w": hvd.per_rank(list(g_np))},
                           params)["w"])
    finally:
        (cfg.sched_mode, cfg.sched_chunks,
         cfg.quant_min_bytes) = old
    exact = -g_np.mean(0)                       # sgd lr=1 update
    gmax = np.abs(g_np).max()
    assert np.abs(got - exact).max() <= 1.5 * (N + 1) * gmax / 254.0


def test_backward_passes_per_step_accumulates():
    # With N_agg=3: two zero-update calls, then one averaged step.
    n_agg = 3
    params = {"w": jnp.zeros((2,), jnp.float32)}
    inner = optax.sgd(1.0)
    tx = hvd.DistributedOptimizer(inner, backward_passes_per_step=n_agg)
    mesh = hvd.mesh()

    def roll(g_seq, p):
        state = tx.init(p)
        outs = []
        for g in g_seq:
            updates, state = tx.update(g, state, p)
            outs.append(updates["w"])
        return jnp.stack(outs)

    def step(gs, p):
        g_seq = [{"w": gs[0, i]} for i in range(gs.shape[1])]
        return roll(g_seq, p)[None]

    grads = hvd.per_rank([
        np.stack([np.full((2,), float(r + 1 + 10 * i), np.float32)
                  for i in range(n_agg)]) for r in range(N)])
    fn = shard_map(step, mesh=mesh, in_specs=(P("hvd"), P()),
                   out_specs=P("hvd"), check_vma=False)
    outs = hvd.to_numpy(jax.jit(fn)(grads, params))  # [N, n_agg, 2]
    # First two updates are zero (accumulating).
    np.testing.assert_allclose(outs[:, 0], 0.0)
    np.testing.assert_allclose(outs[:, 1], 0.0)
    # Third: -mean over ranks of mean over micro-batches.
    per_rank_mean = np.stack([
        np.full((2,), np.mean([r + 1 + 10 * i for i in range(n_agg)]))
        for r in range(N)])
    expected = -per_rank_mean.mean(0)
    np.testing.assert_allclose(outs[:, 2], np.tile(expected, (N, 1)),
                               rtol=1e-5)


def test_distributed_gradients_eager():
    grads = {
        "a": hvd.per_rank([np.full((3,), float(r), np.float32)
                           for r in range(N)]),
        "b": hvd.per_rank([np.full((2, 2), float(2 * r), np.float32)
                           for r in range(N)]),
    }
    out = hvd.distributed_gradients(grads)
    np.testing.assert_allclose(hvd.to_numpy(out["a"]), np.full((3,), 3.5))
    np.testing.assert_allclose(hvd.to_numpy(out["b"]), np.full((2, 2), 7.0))


def test_bad_backward_passes():
    with pytest.raises(ValueError):
        hvd.DistributedOptimizer(optax.sgd(1.0), backward_passes_per_step=0)
