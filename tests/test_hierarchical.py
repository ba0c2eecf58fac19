"""Hierarchical two-level collectives († HOROVOD_HIERARCHICAL_ALLREDUCE /
ALLGATHER semantics): correctness on a 2-slice × 4-local mesh, including
padding for non-divisible payloads.
"""

import jax
import numpy as np
import pytest
from jax import shard_map
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from horovod_tpu.ops.hierarchical import (
    hierarchical_allgather_local,
    hierarchical_allreduce,
)


@pytest.fixture
def mesh2x4():
    return Mesh(np.array(jax.devices()).reshape(2, 4), ("dp", "tp"))


@pytest.mark.parametrize("numel", [32, 33, 7])   # incl. non-divisible
def test_hierarchical_allreduce_sum(mesh2x4, numel):
    rng = np.random.RandomState(0)
    x = rng.randn(2, 4, numel).astype(np.float32)
    xs = jax.device_put(x, NamedSharding(mesh2x4, P("dp", "tp")))
    out = np.asarray(hierarchical_allreduce(
        xs, mesh2x4, local_axis="tp", cross_axis="dp"))
    expected = x.sum(axis=(0, 1))
    for i in range(2):
        for j in range(4):
            np.testing.assert_allclose(out[i, j], expected,
                                       rtol=1e-4, atol=1e-5)


def test_hierarchical_allreduce_average(mesh2x4):
    x = np.ones((2, 4, 16), np.float32)
    xs = jax.device_put(x, NamedSharding(mesh2x4, P("dp", "tp")))
    out = np.asarray(hierarchical_allreduce(
        xs, mesh2x4, local_axis="tp", cross_axis="dp", average=True))
    np.testing.assert_allclose(out, 1.0, rtol=1e-6)


def test_hierarchical_allgather(mesh2x4):
    y = np.arange(2 * 4 * 3, dtype=np.float32).reshape(2, 4, 3)

    def ag(v):
        return hierarchical_allgather_local(
            v[0, 0], local_axis="tp", cross_axis="dp")[None, None]

    f = jax.jit(shard_map(ag, mesh=mesh2x4, in_specs=P("dp", "tp"),
                          out_specs=P("dp", "tp"), check_vma=False))
    got = np.asarray(f(jax.device_put(
        y, NamedSharding(mesh2x4, P("dp", "tp")))))
    expected = np.concatenate(
        [np.concatenate([y[i, j] for j in range(4)]) for i in range(2)])
    np.testing.assert_allclose(got[0, 0], expected)


def test_collective_bench_harness_runs():
    from benchmarks.collective_bench import allreduce_busbw
    row = allreduce_busbw(1 << 14, iters=3, warmup=1)
    assert row["ranks"] == 8
    assert row["busbw_GBs"] > 0
    assert row["bytes"] == 1 << 14


def test_hierarchical_flag_routes_allreduce():
    """HVDTPU_HIERARCHICAL_ALLREDUCE wiring: flag + local-size split routes
    the public allreduce through the two-level kernel with equal results."""
    import horovod_tpu as hvd
    from horovod_tpu.ops import collectives as C
    state = hvd.global_state()
    old_flag = state.config.hierarchical_allreduce
    old_ls = state.config.hierarchical_local_size
    state.config.hierarchical_allreduce = True
    state.config.hierarchical_local_size = 4   # 2 slices x 4
    try:
        assert C._hier_split(None) == (2, 4)
        parts = [np.random.RandomState(r).randn(33).astype(np.float32)
                 for r in range(8)]
        x = hvd.per_rank(parts)
        got = np.asarray(C.allreduce(x, hvd.Sum))
        np.testing.assert_allclose(got, np.stack(parts).sum(0),
                                   rtol=1e-4, atol=1e-5)
        got_avg = np.asarray(C.allreduce(x, hvd.Average))
        np.testing.assert_allclose(got_avg, np.stack(parts).mean(0),
                                   rtol=1e-4, atol=1e-6)
        # grouped path too
        outs = C.grouped_allreduce([x, x], hvd.Sum)
        np.testing.assert_allclose(np.asarray(outs[1]),
                                   np.stack(parts).sum(0),
                                   rtol=1e-4, atol=1e-5)
        # int AVERAGE must stay on the flat path (floor semantics)
        xi = hvd.per_rank([np.full((3,), r, np.int32) for r in range(8)])
        gi = np.asarray(C.allreduce(xi, hvd.Average))
        np.testing.assert_array_equal(gi, np.full((3,), 28 // 8))
    finally:
        state.config.hierarchical_allreduce = old_flag
        state.config.hierarchical_local_size = old_ls


def test_hierarchical_split_invalid_cases():
    import horovod_tpu as hvd
    from horovod_tpu.ops import collectives as C
    state = hvd.global_state()
    old = (state.config.hierarchical_allreduce,
           state.config.hierarchical_local_size)
    try:
        state.config.hierarchical_allreduce = False
        assert C._hier_split(None) is None
        state.config.hierarchical_allreduce = True
        state.config.hierarchical_local_size = 3   # 8 % 3 != 0
        assert C._hier_split(None) is None
        state.config.hierarchical_local_size = 8   # == size
        assert C._hier_split(None) is None
    finally:
        (state.config.hierarchical_allreduce,
         state.config.hierarchical_local_size) = old


def test_hierarchical_rides_the_schedule_ir():
    """The two-level path lowers through ops/sched (ROADMAP item 3 seed):
    the IR schedule carries the tier structure, and the in-graph
    interpreter reproduces the hand-written pipeline's numbers exactly
    (default behavior unchanged)."""
    from horovod_tpu.ops import hierarchical as H
    from horovod_tpu.ops.sched import lower_hierarchical

    s = H.hierarchical_schedule("hvd_local", "hvd_cross")
    kinds = [(st.kind, st.axis) for st in s.steps if st.axis]
    assert kinds == [("reduce_scatter", "hvd_local"),
                     ("all_reduce", "hvd_cross"),
                     ("all_gather", "hvd_local")]
    # Cached + deterministic: same axes -> the same schedule object and
    # an identical signature to a fresh lowering.
    assert H.hierarchical_schedule("hvd_local", "hvd_cross") is s
    assert s.signature() == lower_hierarchical(
        "hvd_local", "hvd_cross").signature()
