"""ZeRO-1 sharded optimizer (optim/zero + optim/partition) and the
bucketed backward overlap layer (ops/sched/buckets).

The load-bearing contract: ``ZeroDistributedOptimizer`` produces
BIT-identical updated parameters to the dense ``DistributedOptimizer``
on this backend — fp32 across all three ``HOROVOD_TPU_SCHED_MODE``s, and
the int8 wire too (bucket flattening pads every leaf to the dense chunk
layout's ``n * block`` unit, so quant block boundaries and shared scales
land identically, and the shard chain replays the dense post-combine
requantization).  Parity over the real negotiated transport lives in
tests/mp_sched_worker.py ``main_zero`` / test_runner.py (the CI
``zero1-parity`` job).
"""

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest
from jax import lax
from jax.sharding import PartitionSpec as P

import horovod_tpu as hvd
from jax import shard_map
from horovod_tpu.ops.compression import Compression
from horovod_tpu.optim import partition as PP
from horovod_tpu.optim import zero as zero_mod

N = 8


@pytest.fixture
def sched_cfg():
    cfg = hvd.global_state().config
    old = (cfg.sched_mode, cfg.sched_chunks, cfg.quant_min_bytes,
           cfg.bucket_bytes, cfg.zero)
    yield cfg
    (cfg.sched_mode, cfg.sched_chunks, cfg.quant_min_bytes,
     cfg.bucket_bytes, cfg.zero) = old


def _mapped_update(tx, grads_per_rank, params):
    """tx.init outside the mapped context, tx.update inside — the
    train-step shape ZeRO documents (init's zero-valued shard template
    is exact for scale_by_* style inits)."""
    mesh = hvd.mesh()
    opt_state = tx.init(params)

    def step(g, p):
        local = jax.tree.map(lambda a: a[0], g)
        updates, _ = tx.update(local, opt_state, p)
        return jax.tree.map(lambda u: u[None], updates)

    fn = shard_map(step, mesh=mesh, in_specs=(P("hvd"), P()),
                   out_specs=P("hvd"), check_vma=False)
    return jax.jit(fn)(grads_per_rank, params)


def _params_and_grads(seed=0):
    params = {"w": jnp.zeros((3000,), jnp.float32),
              "b": jnp.ones((37,), jnp.float32)}
    grads = {
        "w": hvd.per_rank(
            [np.random.RandomState(seed + r).randn(3000).astype(np.float32)
             for r in range(N)]),
        "b": hvd.per_rank(
            [np.random.RandomState(seed + 50 + r).randn(37)
             .astype(np.float32) for r in range(N)]),
    }
    return params, grads


# ---------------------------------------------------------------------------
# parity vs the dense DistributedOptimizer
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("mode", ["monolithic", "decomposed", "compiled"])
def test_zero_parity_all_sched_modes(sched_cfg, mode):
    """Updated parameters bit-identical to the dense wrapper in every
    sched mode: psum_scatter performs the same per-element float ops as
    psum on this backend (decomposed/compiled), and the monolithic
    fallback reuses the dense ``_reduce_in_context`` verbatim before
    slicing the shard."""
    params, grads = _params_and_grads(seed=0)
    dense = hvd.DistributedOptimizer(optax.adam(1e-2))
    zero = hvd.ZeroDistributedOptimizer(optax.adam(1e-2))
    sched_cfg.sched_mode, sched_cfg.sched_chunks = mode, 3
    base = jax.tree.map(hvd.to_numpy, _mapped_update(dense, grads, params))
    got = jax.tree.map(hvd.to_numpy, _mapped_update(zero, grads, params))
    for k in base:
        assert np.array_equal(base[k], got[k]), k


def test_zero_compiled_stays_single_program(sched_cfg):
    """Compiled mode: the whole ZeRO step (rs -> sharded update ->
    param allgather) is ONE jitted program — the engine's per-unit
    schedule dispatch counter never moves (the invariant the CI
    zero1-parity job's zero-dispatch guard pins over real transport)."""
    from horovod_tpu.ops.sched.executor import _m_sched
    params, grads = _params_and_grads(seed=7)
    zero = hvd.ZeroDistributedOptimizer(optax.adam(1e-2))
    sched_cfg.sched_mode, sched_cfg.sched_chunks = "compiled", 3
    before = _m_sched.total()
    _mapped_update(zero, grads, params)
    assert _m_sched.total() == before


def test_zero_int8_parity_decomposed(sched_cfg):
    """int8 wire, decomposed: bit-identical to the DENSE int8 decomposed
    path — the bucket pads every leaf to the n*block unit, so quant
    block boundaries/shared scales match, and the shard chain replays
    the dense post-combine requantization roundtrip."""
    params, grads = _params_and_grads(seed=20)
    sched_cfg.sched_mode, sched_cfg.sched_chunks = "decomposed", 2
    sched_cfg.quant_min_bytes = 1024
    dense = hvd.DistributedOptimizer(optax.adam(1e-2),
                                     compression=Compression.int8)
    zero = hvd.ZeroDistributedOptimizer(optax.adam(1e-2),
                                        compression=Compression.int8)
    base = jax.tree.map(hvd.to_numpy, _mapped_update(dense, grads, params))
    got = jax.tree.map(hvd.to_numpy, _mapped_update(zero, grads, params))
    for k in base:
        assert np.array_equal(base[k], got[k]), k


def test_zero_bucket_split_keeps_parity(sched_cfg):
    """A small HOROVOD_TPU_BUCKET_BYTES splits the fp32 group into
    several buckets (each its own rs chain + param allgather); the math
    per bucket is unchanged, so parity stays bit-exact."""
    params, grads = _params_and_grads(seed=33)
    dense = hvd.DistributedOptimizer(optax.adam(1e-2))
    zero = hvd.ZeroDistributedOptimizer(optax.adam(1e-2),
                                        bucket_bytes=4096)
    plan = PP.build_plan(params, N, modes=["fp32", "fp32"],
                         block=512, chunks=2, bucket_bytes=4096)
    assert len(plan.buckets) > 1
    sched_cfg.sched_mode, sched_cfg.sched_chunks = "decomposed", 2
    base = jax.tree.map(hvd.to_numpy, _mapped_update(dense, grads, params))
    got = jax.tree.map(hvd.to_numpy, _mapped_update(zero, grads, params))
    for k in base:
        assert np.array_equal(base[k], got[k]), k


def test_zero_sum_op_parity(sched_cfg):
    params, grads = _params_and_grads(seed=41)
    dense = hvd.DistributedOptimizer(optax.sgd(1.0), op=hvd.Sum)
    zero = hvd.ZeroDistributedOptimizer(optax.sgd(1.0), op=hvd.Sum)
    sched_cfg.sched_mode = "decomposed"
    base = jax.tree.map(hvd.to_numpy, _mapped_update(dense, grads, params))
    got = jax.tree.map(hvd.to_numpy, _mapped_update(zero, grads, params))
    for k in base:
        assert np.array_equal(base[k], got[k]), k


# ---------------------------------------------------------------------------
# state sharding + gauge
# ---------------------------------------------------------------------------

def test_zero_state_bytes_gauge_shards_state():
    """The acceptance gauge: per-rank optimizer-state bytes under ZeRO
    stay at <= 1/n of the dense footprint plus shard-divisible padding
    (scalar leaves like Adam's step count don't shard)."""
    params = {"w": jnp.zeros((3000,), jnp.float32),
              "b": jnp.ones((37,), jnp.float32)}
    zero = hvd.ZeroDistributedOptimizer(optax.adam(1e-2))
    state = zero.init(params)
    zb = zero_mod._g_state_bytes.value
    assert zb == PP.shard_bytes(state)
    db = PP.shard_bytes(optax.adam(1e-2).init(params))
    # Padding bound: every leaf pads by < n elements, counted twice for
    # Adam's mu+nu, plus the unsharded count scalar.
    pad_allowance = 2 * len(params) * N * 4 + 64
    assert zb <= db / N + pad_allowance
    assert zb / db < 0.2    # way below dense; ~1/8 for these shapes


def test_zero_init_in_context_uses_true_shard(sched_cfg):
    """init INSIDE the mapped context slices the real parameter shard
    (value-dependent inner inits see true values, not the zero
    template) — and the end-to-end update still matches dense."""
    params, grads = _params_and_grads(seed=55)
    sched_cfg.sched_mode = "decomposed"
    mesh = hvd.mesh()
    dense = hvd.DistributedOptimizer(optax.adam(1e-2))
    zero = hvd.ZeroDistributedOptimizer(optax.adam(1e-2))

    def step(tx):
        def body(g, p):
            local = jax.tree.map(lambda a: a[0], g)
            st = tx.init(p)
            updates, _ = tx.update(local, st, p)
            return jax.tree.map(lambda u: u[None], updates)
        fn = shard_map(body, mesh=mesh, in_specs=(P("hvd"), P()),
                       out_specs=P("hvd"), check_vma=False)
        return jax.tree.map(hvd.to_numpy, jax.jit(fn)(grads, params))

    base, got = step(dense), step(zero)
    for k in base:
        assert np.array_equal(base[k], got[k]), k


# ---------------------------------------------------------------------------
# restrictions / config dispatch
# ---------------------------------------------------------------------------

def test_zero_rejects_unsupported():
    with pytest.raises(NotImplementedError):
        hvd.ZeroDistributedOptimizer(optax.sgd(1.0), partition=2)
    with pytest.raises(ValueError):
        hvd.ZeroDistributedOptimizer(optax.sgd(1.0), op=hvd.Adasum)


def test_zero_update_requires_mapped_context():
    zero = hvd.ZeroDistributedOptimizer(optax.sgd(1.0))
    params = {"w": jnp.zeros((16,), jnp.float32)}
    state = zero.init(params)
    with pytest.raises(ValueError, match="mapped context"):
        zero.update(params, state, params)


def test_zero_from_config_dispatch(sched_cfg):
    """HOROVOD_TPU_ZERO flips train-step builders between the dense and
    the ZeRO wrapper through one entry point."""
    from horovod_tpu.optim.zero import from_config
    params = {"w": jnp.zeros((16,), jnp.float32)}
    sched_cfg.zero = True
    tx = from_config(optax.sgd(1.0))
    st = tx.init(params)
    with pytest.raises(ValueError, match="mapped context"):
        tx.update(params, st, params)   # the ZeRO signature
    sched_cfg.zero = False
    tx = from_config(optax.sgd(1.0), bucket_bytes=4096, num_shards=N)
    st = tx.init(params)                # dense: extra kwargs dropped


# ---------------------------------------------------------------------------
# partition plan unit behavior
# ---------------------------------------------------------------------------

def test_partition_plan_pads_to_chunk_units():
    params = {"w": jnp.zeros((3000,), jnp.float32),
              "b": jnp.ones((37,), jnp.float32)}
    plan = PP.build_plan(params, N, modes=["fp32", "fp32"], block=512,
                         chunks=2)
    assert plan.n == N
    for b in plan.buckets:
        assert b.numel % N == 0
        assert b.shard == b.numel // N
        layout = PP.bucket_layout(plan, b)
        # Unit-multiple bucket: chunk_layout never re-pads.
        assert sum(layout) == b.numel
    # Quant buckets pad to n*block so block boundaries match dense.
    plan_q = PP.build_plan(params, N, modes=["int8", "fp32"], block=512,
                           chunks=2)
    wq = next(b for b in plan_q.buckets if b.mode == "int8")
    assert wq.numel % (N * 512) == 0


def test_partition_shard_roundtrip():
    """extract_shard per rank -> assemble_from_shards reconstructs the
    flat bucket exactly (the allgather-side identity the update relies
    on)."""
    params = {"w": jnp.arange(3000, dtype=jnp.float32),
              "b": jnp.arange(37, dtype=jnp.float32)}
    plan = PP.build_plan(params, N, modes=["fp32", "fp32"], block=512,
                         chunks=3)
    leaves = jax.tree.flatten(params)[0]
    for bucket in plan.buckets:
        layout = PP.bucket_layout(plan, bucket)
        flat = PP.flatten_bucket(bucket, leaves)
        shards = [PP.extract_shard(flat, r, layout, N) for r in range(N)]
        gathered = jnp.stack(shards).reshape(-1)
        back = PP.assemble_from_shards(gathered, layout, N)
        assert np.array_equal(np.asarray(back), np.asarray(flat))
        # And the leaves unflatten to their original values.
        for idx, arr in PP.unflatten_bucket(bucket, back):
            assert np.array_equal(np.asarray(arr),
                                  np.asarray(leaves[idx]))


# ---------------------------------------------------------------------------
# bucketed backward overlap (ops/sched/buckets)
# ---------------------------------------------------------------------------

def test_plan_buckets_groups_by_dtype_and_size():
    from horovod_tpu.ops.sched.buckets import plan_buckets
    leaves = [jnp.zeros((1024,), jnp.float32),     # 4096 B
              jnp.zeros((1024,), jnp.float32),
              jnp.zeros((8,), jnp.int32),          # different dtype
              jnp.zeros((1024,), jnp.float32)]
    # Uncapped: one bucket per dtype, pytree order preserved.
    assert plan_buckets(leaves, 0) == [[0, 1, 3], [2]]
    # 8 KB cap: two fp32 leaves fit, the third spills.
    assert plan_buckets(leaves, 8192) == [[0, 1], [2], [3]]
    # One oversized leaf still gets its own bucket.
    assert plan_buckets([jnp.zeros((65536,), jnp.float32)], 8192) == [[0]]


def test_bucketed_distributed_gradients_matches_dense(sched_cfg):
    """Eager bucketed reduction: identical results to the unbucketed
    engine path, and the per-bucket ASAP dispatch realizes comm/compute
    overlap the executor's gauge reports (>0) — the acceptance assert
    for the eager path."""
    from horovod_tpu.ops.sched.executor import _m_overlap
    sched_cfg.sched_mode, sched_cfg.sched_chunks = "decomposed", 4
    grads = {
        f"p{i}": hvd.per_rank(
            [np.random.RandomState(100 * i + r).randn(8192)
             .astype(np.float32) for r in range(N)])
        for i in range(3)
    }
    _m_overlap.set(0.0)
    out = hvd.bucketed_distributed_gradients(grads, bucket_bytes=40000)
    for i in range(3):
        want = np.mean(np.stack(
            [np.random.RandomState(100 * i + r).randn(8192)
             .astype(np.float32) for r in range(N)]), axis=0)
        np.testing.assert_allclose(hvd.to_numpy(out[f"p{i}"]), want,
                                   rtol=1e-6, atol=1e-6)
    assert _m_overlap.value > 0.0


def test_attach_gradient_reduction_reduces_per_bucket(sched_cfg):
    """In-jit bucket boundaries: jax.grad through the attached params
    yields already-averaged gradients, bit-equal to the explicit pmean
    (fp32 chains are bit-exact vs monolithic by the sched contract)."""
    sched_cfg.sched_mode = "decomposed"
    from horovod_tpu.ops.sched.buckets import attach_gradient_reduction
    mesh = hvd.mesh()
    params = {"w": jnp.ones((2048,), jnp.float32),
              "v": jnp.ones((512,), jnp.float32)}
    xs = hvd.per_rank([np.random.RandomState(r).randn(2048)
                       .astype(np.float32) for r in range(N)])

    def step(x, p):
        xl = x[0]

        def loss(p_):
            wp = attach_gradient_reduction(p_, "hvd", chunks=2,
                                           bucket_bytes=4096)
            return jnp.sum(wp["w"] * xl) + 3.0 * jnp.sum(wp["v"])

        g = jax.grad(loss)(p)
        return jax.tree.map(lambda u: u[None], g)

    fn = shard_map(step, mesh=mesh, in_specs=(P("hvd"), P()),
                   out_specs=P("hvd"), check_vma=False)
    got = jax.tree.map(hvd.to_numpy, jax.jit(fn)(xs, params))
    want_w = np.mean(np.asarray(hvd.to_numpy(xs)), axis=0)
    for r in range(N):
        assert np.array_equal(got["w"][r], want_w)
        np.testing.assert_allclose(got["v"][r], np.full((512,), 3.0))


def test_engine_fusion_respects_bucket_cap(sched_cfg):
    """cfg.bucket_bytes caps the engine's fusion grouping: two 4 KB
    entries that would fuse under the 64 MB threshold stay separate
    collectives under a 4 KB bucket cap."""
    engine = hvd.global_state().engine
    sched_cfg.bucket_bytes = 4096
    a = hvd.per_rank([np.full((1024,), float(r), np.float32)
                      for r in range(N)])
    b = hvd.per_rank([np.full((1024,), 2.0 * r, np.float32)
                      for r in range(N)])
    h1 = hvd.allreduce_async(a, hvd.Average)
    h2 = hvd.allreduce_async(b, hvd.Average)
    out1, out2 = h1.wait(), h2.wait()
    np.testing.assert_allclose(hvd.to_numpy(out1), np.full((1024,), 3.5))
    np.testing.assert_allclose(hvd.to_numpy(out2), np.full((1024,), 7.0))
    assert engine is not None


# ---------------------------------------------------------------------------
# satellite regressions (optim/distributed)
# ---------------------------------------------------------------------------

def test_distributed_gradients_engine_side_decompress_runs_once():
    """Regression: engine-side (quantized) compressors dequantize inside
    the fused collective — the host-side decompress must NOT run again
    on the engine output (a lossy decompress would corrupt it)."""
    calls = {"n": 0}

    class SpyInt8(Compression.int8):
        @staticmethod
        def decompress(tensor, ctx):
            calls["n"] += 1
            return tensor

    grads = {"g": hvd.per_rank([np.full((512,), float(r), np.float32)
                                for r in range(N)])}
    out = hvd.distributed_gradients(grads, compression=SpyInt8)
    assert calls["n"] == 0
    np.testing.assert_allclose(hvd.to_numpy(out["g"]),
                               np.full((512,), 3.5), rtol=0.05)
    # Bucketed twin shares the routing rule.
    out2 = hvd.bucketed_distributed_gradients(grads, compression=SpyInt8)
    assert calls["n"] == 0
    np.testing.assert_allclose(hvd.to_numpy(out2["g"]),
                               np.full((512,), 3.5), rtol=0.05)


def test_aggregation_accumulator_keeps_grad_dtype():
    """Regression: bf16 params + fp32 grads — the local-aggregation
    accumulator must carry the GRADIENT dtype, not round every
    micro-batch onto the bf16 grid (zeros_like(params) seeds it bf16)."""
    mesh = hvd.mesh()
    params = {"w": jnp.zeros((4,), jnp.bfloat16)}
    tx = hvd.DistributedOptimizer(optax.sgd(1.0),
                                  backward_passes_per_step=2)
    # 1.0 then 2**-10: a bf16 accumulator would round the sum to 1.0.
    g1 = np.full((4,), 1.0, np.float32)
    g2 = np.full((4,), 2.0 ** -10, np.float32)

    def step(gs, p):
        state = tx.init(p)
        outs = []
        for i in range(2):
            updates, state = tx.update({"w": gs[0, i]}, state, p)
            outs.append(updates["w"])
        return jnp.stack(outs)[None]

    grads = hvd.per_rank([np.stack([g1, g2])] * N)
    fn = shard_map(step, mesh=mesh, in_specs=(P("hvd"), P()),
                   out_specs=P("hvd"), check_vma=False)
    outs = hvd.to_numpy(jax.jit(fn)(grads, params))  # [N, 2, 4]
    np.testing.assert_allclose(outs[:, 0], 0.0)
    exact = -(1.0 + 2.0 ** -10) / 2.0
    assert outs.dtype == np.float32
    np.testing.assert_allclose(outs[:, 1], exact, rtol=0, atol=0)


@pytest.mark.parametrize("mode", ["decomposed", "compiled"])
def test_backward_passes_with_sched_modes(sched_cfg, mode):
    """Satellite: backward_passes_per_step > 1 composed with the
    decomposed/compiled schedules — off-cycle updates zero, the firing
    step bit-equal to the monolithic aggregation path."""
    params = {"w": jnp.zeros((2048,), jnp.float32)}
    tx = hvd.DistributedOptimizer(optax.sgd(1.0),
                                  backward_passes_per_step=2)
    mesh = hvd.mesh()

    def step(gs, p):
        state = tx.init(p)
        outs = []
        for i in range(2):
            updates, state = tx.update({"w": gs[0, i]}, state, p)
            outs.append(updates["w"])
        return jnp.stack(outs)[None]

    grads = hvd.per_rank([
        np.stack([np.random.RandomState(1000 + 2 * r + i).randn(2048)
                  .astype(np.float32) for i in range(2)])
        for r in range(N)])
    fn = shard_map(step, mesh=mesh, in_specs=(P("hvd"), P()),
                   out_specs=P("hvd"), check_vma=False)
    base = hvd.to_numpy(jax.jit(fn)(grads, params))
    sched_cfg.sched_mode, sched_cfg.sched_chunks = mode, 2
    got = hvd.to_numpy(jax.jit(fn)(grads, params))
    np.testing.assert_allclose(got[:, 0], 0.0)
    assert np.array_equal(got, base)
