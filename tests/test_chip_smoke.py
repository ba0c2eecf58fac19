"""The chip path's guards that a CPU can check: ``chip_smoke.py`` in its
tiny mode, refusal without a TPU, the compile-cache rule, serving errors
that must not read as results, ``bench.py``'s peaks table, and the four
Pallas kernels compiled ahead of time for a v5e."""

import json
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _run(script_args, **env_overrides):
    env = dict(os.environ, PYTHONPATH=REPO, JAX_PLATFORMS="cpu")
    for k, v in env_overrides.items():
        if v is None:
            env.pop(k, None)
        else:
            env[k] = v
    return subprocess.run([sys.executable] + script_args, cwd=REPO, env=env,
                          capture_output=True, text=True, timeout=300)


def test_chip_smoke_tiny_cpu_mode(tmp_path):
    res = _run([os.path.join(REPO, "chip_smoke.py"), "--cpu-tiny", "1"],
               JAX_COMPILATION_CACHE_DIR=str(tmp_path / "cache"),
               # cache every compile, however fast this machine is
               JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS="0")
    assert res.returncode == 0, res.stdout + res.stderr
    report, verdict = map(json.loads, res.stdout.strip().splitlines()[-2:])
    # the last line carries exactly the keys the driver's contract names
    assert verdict == {"ok": True, "device": {
        "platform": "cpu", "kind": "cpu", "count": 1}}
    out = report
    assert out["mode"] == "cpu-tiny" and out["device"] == verdict["device"]
    assert out["compile_cache"]["dir"] == str(tmp_path / "cache")
    assert set(out["phases"]) == {"collective", "train", "serve"}
    assert out["phases"]["train"]["attention_path"] == "flash"
    assert out["phases"]["serve"]["attention_path"] == "pallas-interpret"
    assert os.listdir(tmp_path / "cache"), "nothing was cached there"


@pytest.mark.parametrize("script", ["chip_smoke.py", "bench.py"])
def test_no_tpu_no_result(script):
    """Without a TPU both root scripts exit non-zero and print no metric."""
    res = _run([os.path.join(REPO, script)])
    assert res.returncode != 0
    assert res.stdout.strip() == "", res.stdout
    assert "not a TPU" in res.stderr


def test_bench_unknown_device_kind_raises():
    sys.path.insert(0, REPO)
    try:
        import bench
    finally:
        sys.path.remove(REPO)
    assert bench._peak_flops("TPU v5 lite") == 197e12
    with pytest.raises(KeyError, match="TPU v99"):
        bench._peak_flops("TPU v99")


def test_compile_cache_env_wins(monkeypatch, tmp_path):
    from horovod_tpu.utils.compile_cache import ensure_compile_cache
    before = jax.config.jax_compilation_cache_dir
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    assert ensure_compile_cache() == str(tmp_path)
    assert jax.config.jax_compilation_cache_dir == before
    # either way a program is keyed by its names too (obs.trace.region)
    assert jax.config.jax_compilation_cache_include_metadata_in_key


def test_compile_cache_default_is_the_checkout():
    """Unset, every process of a checkout gets the same directory."""
    from horovod_tpu.utils import compile_cache
    code = ("from horovod_tpu.utils.compile_cache import "
            "ensure_compile_cache as e; import jax; "
            "print(e()); print(jax.config.jax_compilation_cache_dir)")
    res = _run(["-c", code], JAX_COMPILATION_CACHE_DIR=None)
    assert res.returncode == 0, res.stderr
    want = os.path.join(REPO, ".jax_cache")
    assert res.stdout.split() == [want, want]
    assert compile_cache.DEFAULT_DIR == want


def test_failed_engine_step_is_not_a_clean_result():
    """A step failure that is not collective/transport fails the futures
    and propagates; it never resolves them with tokens."""
    from horovod_tpu import serving
    from horovod_tpu.context import set_component_health
    from horovod_tpu.models import llama
    cfg = llama.LlamaConfig.tiny()
    params = llama.init_params(cfg, jax.random.PRNGKey(0))

    def refuse():
        raise RuntimeError("Mosaic failed to compile TPU kernel")

    try:
        with serving.serve(params, cfg, num_blocks=8, block_size=8,
                           max_active=2) as sess:
            sess.engine.step = refuse
            fut = sess.submit(np.arange(4, dtype=np.int32), max_tokens=4)
            with pytest.raises(RuntimeError, match="Mosaic"):
                sess.drain()
            with pytest.raises(RuntimeError, match="Mosaic"):
                fut.result(timeout=0)
            assert sess.recoveries == 0
            assert not sess.engine.has_work()
    finally:
        set_component_health("serving", None)


def test_kernels_compile_for_v5e(monkeypatch):
    """All four pallas_calls through Mosaic at chip_smoke's shapes, on
    compile-only v5e devices (libtpu is installed; no chip is needed)."""
    from jax.experimental import topologies
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
    from horovod_tpu.ops import flash_attention as FA
    monkeypatch.setenv("TPU_ACCELERATOR_TYPE", "v5litepod-4")
    monkeypatch.setenv("TPU_WORKER_HOSTNAMES", "localhost")
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no libtpu on this machine
        pytest.skip(f"no compile-only TPU topology: {e}")
    sharding = NamedSharding(Mesh(np.array(topo.devices[:1]), ("x",)), P())

    def spec(shape, dtype=jnp.bfloat16):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)

    def custom_calls(fn, *args):
        text = jax.jit(fn).lower(*args).compile().as_text()
        return text.count('custom_call_target="tpu_custom_call"')

    B, S, H, D = 4, 2048, 32, 128
    assert FA.supported((B, S, H, D), 2)
    qkv = spec((B, S, H, D))
    grads = jax.grad(
        lambda q, k, v: FA.flash_attention(q, k, v).astype(
            jnp.float32).sum(), argnums=(0, 1, 2))
    assert custom_calls(grads, qkv, qkv, qkv) == 3      # fwd, dq, dkv

    R, L, NB, BS, n_cols = 4, 4, 2048, 16, 64
    assert FA.paged_supported(BS, D, H, H, 2)
    pool = spec((L, NB, BS, H, D))
    assert custom_calls(
        FA.paged_attention, spec((R, H, D)), pool, pool,
        spec((), jnp.int32), spec((R, n_cols), jnp.int32),
        spec((R,), jnp.int32)) == 1


def test_kimi_kernels_compile_for_v5e(monkeypatch):
    """The KDA kernels through Mosaic at the Kimi-Linear cell's shape (B4
    x S8192, 32 heads of 128): the stateless forward, and the gradient,
    which is the state-writing forward and the reverse kernel, within
    the VMEM they ask for."""
    from jax.experimental import topologies
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
    from horovod_tpu.ops import kda
    monkeypatch.setenv("TPU_ACCELERATOR_TYPE", "v5litepod-4")
    monkeypatch.setenv("TPU_WORKER_HOSTNAMES", "localhost")
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no libtpu on this machine
        pytest.skip(f"no compile-only TPU topology: {e}")
    sharding = NamedSharding(Mesh(np.array(topo.devices[:1]), ("x",)), P())

    def spec(shape, dtype=jnp.bfloat16):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)

    def compiled_text(fn, *args):
        return jax.jit(fn).lower(*args).compile().as_text()

    B, S, H, K = 4, 8192, 32, 128
    args = (spec((B, S, H, K)), spec((B, S, H, K)), spec((B, S, H, K)),
            spec((B, S, H, K), jnp.float32), spec((B, S, H), jnp.float32))
    core = lambda *a: kda.chunk_kda(*a, kda.CHUNK, False)
    text = compiled_text(core, *args)
    assert text.count('custom_call_target="tpu_custom_call"') == 1
    assert "hvd_kda_fwd" in text

    text = compiled_text(jax.grad(
        lambda *a: core(*a).astype(jnp.float32).sum(),
        argnums=(0, 1, 2, 3, 4)), *args)
    assert text.count('custom_call_target="tpu_custom_call"') == 2
    assert "hvd_kda_fwd" in text and "hvd_kda_bwd" in text
    assert f"f32[{B},{H},{S // kda.CHUNK},{K},{K}]" in text     # the states
    assert " while(" not in text
    # Neither kernel asks for more than the default scoped VMEM: the
    # forward for nothing, the reverse kernel for the default itself,
    # under which it carries the heads that fit by its own estimate (the
    # compile above held it to that).
    heads = kda._head_block(H, kda.CHUNK, K, K, 2)
    assert 1 < heads < H
    assert kda._bwd_resident(heads, kda.CHUNK, K, K, 2) * 3 // 2 \
        <= kda._SCOPED_VMEM == 16 << 20


# (S, heads, kv heads, key width, value width, asks for VMEM).  Latent
# attention at the Kimi-Linear cell's shape; a Llama block at 8k, which
# stays under the default scoped VMEM and hands the compiler no limit;
# and at 16k, which needs more than the default.
@pytest.mark.parametrize("S,H,KV,D,Dv,asks", [
    (8192, 32, 32, 192, 128, True),
    (8192, 32, 8, 128, 128, False),
    (16384, 32, 8, 128, 128, True),
], ids=["mla-8k", "llama-8k", "llama-16k"])
def test_flash_kernels_compile_for_v5e_at_long_sequences(
        monkeypatch, S, H, KV, D, Dv, asks):
    """The three flash kernels through Mosaic where their resident set
    nears or passes the default scoped VMEM: :func:`FA.supported` and the
    limit the kernels ask for go by one estimate."""
    from jax.experimental import topologies
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
    from horovod_tpu.ops import flash_attention as FA
    monkeypatch.setenv("TPU_ACCELERATOR_TYPE", "v5litepod-4")
    monkeypatch.setenv("TPU_WORKER_HOSTNAMES", "localhost")
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no libtpu on this machine
        pytest.skip(f"no compile-only TPU topology: {e}")
    sharding = NamedSharding(Mesh(np.array(topo.devices[:1]), ("x",)), P())

    def spec(shape):
        return jax.ShapeDtypeStruct(shape, jnp.bfloat16, sharding=sharding)

    B = 1
    assert FA.supported((B, S, H, D), 2, Dv)
    assert bool(FA._compiler_params(S, D, Dv, 2, 512)) == asks
    assert not FA._compiler_params(2048, 128, 128, 2, 512)
    grads = jax.grad(
        lambda q, k, v: FA.flash_attention(q, k, v).astype(
            jnp.float32).sum(), argnums=(0, 1, 2))
    text = jax.jit(grads).lower(
        spec((B, S, H, D)), spec((B, S, KV, D)), spec((B, S, KV, Dv))
    ).compile().as_text()
    assert text.count('custom_call_target="tpu_custom_call"') == 3
