"""Proof that the system starts and computes correctly on the TPU.

    python chip_smoke.py

One process, no children.  Drives the three subsystems once through the
entry points a user calls, on every device JAX finds, at the widths of
``LlamaConfig.llama2_7b`` cut by depth only (random seeded weights):

- ``collective``: ``hvd.init`` and eager/async allreduce of a 16 MB
  payload per rank, against the numpy answer;
- ``train``: ``build_mesh`` -> ``init_params`` -> ``make_train_step``
  (the path of ``examples/llama_finetune.py``), the Pallas flash kernels
  found in the compiled step, loss falling on a repeated batch, one
  checkpoint save and restore;
- ``serve``: ``serving.serve`` (the path of ``examples/llama_serve.py``),
  eight mixed requests through continuous batching over the paged pool,
  the Pallas paged-decode kernel found in the compiled decode tick, and
  its logits against the XLA gather path.

It fails (non-zero exit, no result line) on the first phase that fails
and at once when JAX's first device is not a TPU.  The last two lines of
standard output are one JSON object each.  The last is the verdict the
driver reads, with exactly these keys:

    {"ok": true, "device": {"platform": "tpu", "kind": "...", "count": 1}}

The line before it is the report: device, versions, compile cache, and
per phase wall seconds, compile seconds and the attention path that ran.
Neither reports a throughput, a utilization or a latency: those are the
benchmark's.

``--cpu-tiny N`` (with ``JAX_PLATFORMS=cpu``) runs the same phases and
assertions at toy widths on N virtual CPU devices, kernels through the
Pallas interpreter, stamped ``platform: cpu``.  It exists to debug this
script without a chip and is what the tests run; the script never
chooses it.
"""

from __future__ import annotations

import argparse
import contextlib
import gc
import importlib.metadata
import json
import os
import shutil
import sys
import tempfile
import time

import numpy as np

# Kernel-against-gather bound on the decode logits, in units of the
# largest |logit|.  The kernel keeps scores and softmax weights in fp32;
# the gather path's bf16 einsum rounds each score to bf16 (2^-8 relative
# on scores of magnitude a few, so about a percent on each softmax
# weight) before the softmax.  That percent carries through the depth of
# the cut model to the logits: 1.2e-2 measured on a v5e at 4 layers.
# fp32 in the tiny mode leaves only summation order.
LOGIT_BOUND_REL = {"bfloat16": 3e-2, "float32": 1e-4}

# Layers the 7B-width model is cut to.  About 202 M parameters a layer
# plus 262 M in embedding and head, at 8 bytes a parameter with bf16 Adam
# state: 9.0 GB peak of a v5e chip's 16 (measured).
DEPTH = 4

# First-step loss on a dp x tp mesh against one device, same weights and
# global batch.  tp splits the wo / w_down / lm_head contractions into
# per-chip partial sums, so bf16 activations differ by rounding; the loss
# is a mean over B*S tokens of fp32 log-probabilities near ln(vocab), and
# the rounding noise averages out well below this.
MESH_LOSS_TOL = 5e-2


def _parse(argv):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--cpu-tiny", type=int, metavar="N", default=0,
                    help="debug mode: toy widths on N virtual CPU devices "
                         "(requires JAX_PLATFORMS=cpu)")
    return ap.parse_args(argv)


class _CompileClock:
    """Seconds JAX spent tracing, lowering and compiling (or fetching
    from the persistent cache), from jax.monitoring's duration events."""

    def __init__(self) -> None:
        import jax
        self.total = 0.0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event: str, duration: float, **_) -> None:
        if event.startswith("/jax/core/compile/"):
            self.total += duration


@contextlib.contextmanager
def _phase(name: str, clock: _CompileClock, report: dict):
    print(f"[chip_smoke] phase {name} ...", flush=True)
    t0, c0 = time.perf_counter(), clock.total
    out = report.setdefault(name, {})
    yield out
    out["wall_s"] = round(time.perf_counter() - t0, 2)
    out["compile_s"] = round(clock.total - c0, 2)
    print(f"[chip_smoke] phase {name} ok: {json.dumps(out)}", flush=True)
    # Device arrays caught in reference cycles (the checkpoint manager's)
    # are freed only by the collector; without this the next phase's peak
    # varied between 9.1 and 13.8 GB from run to run on a 16 GB chip.
    gc.collect()


def _cache_entries(path: str) -> int:
    return len(os.listdir(path)) if os.path.isdir(path) else 0


def _pallas_custom_calls(compiled) -> dict:
    """Mosaic custom calls in a compiled module, split by whether the
    op sits under autodiff's transpose (a backward kernel)."""
    lines = [ln for ln in compiled.as_text().splitlines()
             if 'custom_call_target="tpu_custom_call"' in ln]
    backward = sum("transpose(" in ln for ln in lines)
    return {"forward": len(lines) - backward, "backward": backward}


def _check_spread(tree, devices, what: str) -> None:
    """Every array of ``tree`` has a shard on every device, and (where
    the backend reports it) no device holds less than half of another's
    bytes: code that has only seen one chip may put all on the first."""
    import jax
    want = set(devices)
    for leaf in jax.tree.leaves(tree):
        got = {s.device for s in leaf.addressable_shards}
        assert got == want, f"{what}: shards on {got}, expected {want}"
    stats = [d.memory_stats() for d in devices]
    if all(s and "bytes_in_use" in s for s in stats):
        used = [s["bytes_in_use"] for s in stats]
        assert min(used) > 0 and max(used) <= 2 * min(used), \
            f"{what}: uneven device memory {used}"


def _peak_bytes(devices):
    stats = devices[0].memory_stats()
    return stats.get("peak_bytes_in_use") if stats else None


# ---------------------------------------------------------------------------
# phases
# ---------------------------------------------------------------------------

def phase_collective(out: dict, devices, payload_bytes: int) -> None:
    import horovod_tpu as hvd

    hvd.init()
    n = hvd.size()
    assert n == len(devices), f"hvd.size()={n}, {len(devices)} devices"
    elems = payload_bytes // 4                    # fp32, per rank
    rows = [np.random.RandomState(r).standard_normal(elems)
            .astype(np.float32) for r in range(n)]
    x = hvd.per_rank_from_fn(lambda r: rows[r])
    _check_spread(x, devices, "per-rank payload")
    want_sum = np.sum(np.stack(rows).astype(np.float64), axis=0)
    tol = dict(rtol=1e-5, atol=1e-5 * n)

    got = hvd.to_numpy(hvd.allreduce(x, op=hvd.Sum))
    np.testing.assert_allclose(got, want_sum, **tol)
    got = hvd.to_numpy(hvd.allreduce(x, op=hvd.Average))
    np.testing.assert_allclose(got, want_sum / n, **tol)
    handle = hvd.allreduce_async(x, op=hvd.Sum, name="chip_smoke")
    got = hvd.to_numpy(hvd.synchronize(handle))
    np.testing.assert_allclose(got, want_sum, **tol)
    out.update(size=n, payload_bytes=elems * 4)


def phase_train(out: dict, devices, cfg, batch_shape, interpret: bool
                ) -> None:
    import jax
    import jax.numpy as jnp
    import optax
    from jax.sharding import NamedSharding, PartitionSpec as P

    from horovod_tpu.models import llama, steps
    from horovod_tpu.parallel import MeshConfig, build_mesh
    from horovod_tpu.utils.checkpoint import Checkpointer

    n = len(devices)
    B, S = batch_shape
    mesh_cfg = MeshConfig.auto(n)                 # 4 devices: dp=2, tp=2
    mesh = build_mesh(mesh_cfg)
    tokens = np.random.RandomState(0).randint(
        0, cfg.vocab_size, size=(B, S + 1)).astype(np.int32)
    key = jax.random.PRNGKey(0)

    path = llama.attention_path((B, S, cfg.n_heads, cfg.head_dim),
                                jnp.dtype(cfg.dtype).itemsize, mesh)
    assert path == "flash", f"training attention path is {path!r}"

    loss_one = None
    if n > 1:
        # The same weights and batch on one device, forward only.
        one = build_mesh(MeshConfig(), devices=devices[:1])
        params_one = llama.init_params(cfg, key, one)
        loss_one = float(jax.jit(
            lambda p, b: llama.loss_fn(p, b, cfg, mesh=one))(
                params_one, {"tokens": jnp.asarray(tokens)}))
        del params_one

    params = llama.init_params(cfg, key, mesh)
    tx = optax.adamw(3e-4, weight_decay=0.01)
    opt_state = jax.jit(tx.init)(params)
    batch = jax.device_put({"tokens": jnp.asarray(tokens)},
                           NamedSharding(mesh, P(("dp", "fsdp"))))
    step = steps.make_train_step(cfg, mesh, tx).lower(
        params, opt_state, batch).compile()
    if not interpret:
        calls = _pallas_custom_calls(step)
        assert calls["forward"] >= 1 and calls["backward"] >= 2, \
            f"flash kernels missing from the compiled step: {calls}"
        out["pallas_custom_calls"] = calls

    losses = []
    for _ in range(6):
        params, opt_state, loss = step(params, opt_state, batch)
        losses.append(float(loss))
    assert all(np.isfinite(losses)), losses
    assert losses[-1] < losses[0], f"loss did not fall: {losses}"
    if loss_one is not None:
        assert abs(losses[0] - loss_one) <= MESH_LOSS_TOL, \
            f"first-step loss {losses[0]} on {mesh_cfg.axis_sizes()} vs " \
            f"{loss_one} on one device"
    _check_spread({"params": params, "opt_state": opt_state}, devices,
                  "train state")

    ckpt_dir = tempfile.mkdtemp(prefix="chip_smoke_ckpt_")
    try:
        ckpt = Checkpointer(ckpt_dir)
        ckpt.save(len(losses), {"params": params})
        restored = ckpt.restore(target={"params": params})["params"]
        ckpt.close()
        same = jax.tree.map(lambda a, b: bool(jnp.array_equal(a, b)),
                            params, restored)
        assert all(jax.tree.leaves(same)), "restored params differ"
    finally:
        shutil.rmtree(ckpt_dir, ignore_errors=True)

    out.update(mesh={k: v for k, v in mesh_cfg.axis_sizes().items()
                     if v > 1},
               batch=[B, S], steps=len(losses), attention_path=path,
               loss_first=round(losses[0], 4),
               loss_last=round(losses[-1], 4),
               loss_one_device=(None if loss_one is None
                                else round(loss_one, 4)),
               peak_bytes_in_use=_peak_bytes(devices))


def phase_serve(out: dict, devices, cfg, serve_kw: dict, lens, budgets,
                interpret: bool) -> None:
    import jax
    import jax.numpy as jnp

    from horovod_tpu import serving
    from horovod_tpu.models import llama, steps
    from horovod_tpu.parallel import MeshConfig, build_mesh

    n = len(devices)
    mesh = build_mesh(MeshConfig(tp=n)) if n > 1 else None
    params = llama.init_params(cfg, jax.random.PRNGKey(1), mesh)
    rng = np.random.RandomState(1)

    with serving.serve(params, cfg, mesh=mesh, **serve_kw) as session:
        engine = session.engine
        want_path = "pallas-interpret" if interpret else "pallas"
        assert engine.attention_path == want_path, engine.attention_path
        futs = [session.submit(
            rng.randint(0, cfg.vocab_size, size=(p,)).astype(np.int32), m)
            for p, m in zip(lens, budgets)]
        session.drain()
        for fut, budget in zip(futs, budgets):
            m = fut.result(timeout=0).metrics
            assert "error" not in m, m
            assert m["new_tokens"] == budget, m
        assert session.recoveries == 0, session.recoveries
        _check_spread({"params": params, "pools": engine.pools}, devices,
                      "serve state")

        n_cols = 64
        if not interpret:
            tick = engine.lower_decode(n_cols).compile()
            calls = _pallas_custom_calls(tick)
            assert calls["forward"] >= 1, \
                "paged kernel missing from the compiled decode tick"
            assert any("hvd_paged_decode" in ln
                       for ln in tick.as_text().splitlines()
                       if 'custom_call_target="tpu_custom_call"' in ln), \
                "the decode tick's Mosaic call does not carry the name " \
                "hvd_paged_decode that the benchmark finds it by"
            out["pallas_custom_calls"] = calls

        # Kernel check outside the engine: one decode tick on the pools
        # the requests just wrote, through the kernel and the gather.
        R, BS = serve_kw["max_active"], serve_kw["block_size"]
        n_cols = min(n_cols, (serve_kw["num_blocks"] - 1) // R)
        tables = 1 + np.arange(R * n_cols, dtype=np.int32).reshape(R, -1)
        pos = np.linspace(BS, n_cols * BS - 1, R).astype(np.int32)
        tok = rng.randint(0, cfg.vocab_size, size=(R,)).astype(np.int32)

        def tick(use_flash):
            return jax.jit(
                lambda p, pools: steps.decode_step_paged(
                    p, jnp.asarray(tok), jnp.asarray(pos), pools,
                    jnp.asarray(tables), cfg, mesh=mesh,
                    use_flash=use_flash, interpret=interpret)[:2],
                donate_argnums=(1,))

        # The pools are donated through both ticks; each writes the same
        # K/V rows at ``pos``, so both attend over identical pools.
        logits_k, pools = tick(True)(params, engine.pools)
        logits_g, engine.pools = tick(False)(params, pools)
        logits_k, logits_g = np.asarray(logits_k), np.asarray(logits_g)
        assert np.isfinite(logits_k).all()
        diff = float(np.max(np.abs(logits_k - logits_g)))
        scale = float(np.max(np.abs(logits_g)))
        bound = LOGIT_BOUND_REL[jnp.dtype(cfg.dtype).name] * scale
        assert diff <= bound, \
            f"kernel vs gather logits differ by {diff} > {bound}"

    out.update(mesh={"tp": n} if n > 1 else {}, requests=len(lens),
               attention_path=engine.attention_path,
               kernel_vs_gather_max_abs_diff=diff,
               bound=bound, max_abs_logit=scale,
               peak_bytes_in_use=_peak_bytes(devices))


# ---------------------------------------------------------------------------

def main(argv=None) -> int:
    args = _parse(argv)
    tiny = args.cpu_tiny > 0
    if tiny:
        if os.environ.get("JAX_PLATFORMS") != "cpu":
            sys.exit("chip_smoke: --cpu-tiny needs JAX_PLATFORMS=cpu")
        from horovod_tpu.utils.cpurig import force_cpu_platform
        force_cpu_platform(args.cpu_tiny)
    import jax
    import jaxlib

    devices = jax.devices()
    platform = devices[0].platform
    if platform != ("cpu" if tiny else "tpu"):
        sys.exit(f"chip_smoke: JAX's first device is {platform!r}, not a "
                 "TPU; nothing was run")

    from horovod_tpu.models import layers, llama
    from horovod_tpu.utils.compile_cache import ensure_compile_cache

    cache_dir = ensure_compile_cache()
    cache_before = _cache_entries(cache_dir)
    clock = _CompileClock()

    if tiny:
        layers._FORCE_FLASH_INTERPRET = True
        cfg = llama.LlamaConfig.tiny(n_kv_heads=4, n_layers=1)
        payload_bytes = 1 << 20
        batch_shape = (2, 128)
        serve_kw = dict(block_size=8, num_blocks=64, max_active=2,
                        prefill_buckets=(8, 32), use_flash="interpret")
        lens, budgets = [4, 27, 9, 30, 6, 18, 12, 3], \
            [4, 2, 6, 3, 5, 2, 3, 8]
    else:
        cfg = llama.LlamaConfig.llama2_7b(n_layers=DEPTH)
        payload_bytes = 16 << 20
        batch_shape = (4, 2048)
        # 1 MiB of K+V a block at 4 layers: a 2 GiB pool.
        serve_kw = dict(block_size=16, num_blocks=2048, max_active=4,
                        prefill_buckets=(128, 512, 1024))
        lens, budgets = [32, 1024, 100, 512, 48, 700, 256, 900], \
            [16, 8, 64, 12, 32, 8, 24, 48]
    print(f"[chip_smoke] {len(devices)} x {devices[0].device_kind}, "
          f"d_model {cfg.d_model}, layers {cfg.n_layers}, "
          f"cache {cache_dir} ({cache_before} entries)", flush=True)

    phases: dict = {}
    with _phase("collective", clock, phases) as out:
        phase_collective(out, devices, payload_bytes)
    with _phase("train", clock, phases) as out:
        phase_train(out, devices, cfg, batch_shape, interpret=tiny)
    with _phase("serve", clock, phases) as out:
        phase_serve(out, devices, cfg, serve_kw, lens, budgets,
                    interpret=tiny)
    import horovod_tpu as hvd
    hvd.shutdown()

    def version(dist):
        try:
            return importlib.metadata.version(dist)
        except importlib.metadata.PackageNotFoundError:
            return None

    device = {"platform": platform, "kind": devices[0].device_kind,
              "count": len(devices)}
    print(json.dumps({
        "device": device,
        "mode": "cpu-tiny" if tiny else "full",
        "versions": {"jax": jax.__version__, "jaxlib": jaxlib.__version__,
                     "libtpu": version("libtpu")},
        "model": {"d_model": cfg.d_model, "n_heads": cfg.n_heads,
                  "head_dim": cfg.head_dim, "d_ff": cfg.d_ff,
                  "vocab_size": cfg.vocab_size, "n_layers": cfg.n_layers,
                  "dtype": np.dtype(cfg.dtype).name},
        "compile_cache": {"dir": cache_dir,
                          "entries_before": cache_before,
                          "entries_after": _cache_entries(cache_dir)},
        "phases": phases,
    }))
    # The verdict: these keys and no others, last on standard output.
    print(json.dumps({"ok": True, "device": device}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
