"""Benchmark harness: prints ONE JSON line for the driver.

Measures flagship (Llama-family) training-step throughput in tokens/sec on
the TPU, plus MFU against the chip's peak bf16 FLOPs and an allreduce
bus-bandwidth point from ``benchmarks.collective_bench``.

One process, no children: it measures on the chip or it fails.  Without a
TPU, or on a ``device_kind`` missing from the peaks table, it exits
non-zero and prints no metric.

``vs_baseline`` compares against ``BENCH_BASELINE`` below.  The reference's
published numbers are GPU-cluster scaling efficiencies (BASELINE.md) with
no single-chip figure, so the anchor is this repo's own round-4 median.
"""

from __future__ import annotations

import json
import sys
import time

# tokens/sec/chip anchor.  The MEDIAN of the round-4 variance study: six
# back-to-back runs of the round-3 code on a TPU v5 lite chip measured
# 81246/81295/81484/81491/81495/82957 tok/s/chip (median 81487, spread
# ±1%; the ``variance_study`` record in benchmarks/measured.jsonl).
BENCH_BASELINE = 81487.0

# Peak bf16 matmul FLOPs/s per chip by device-kind substring (public specs).
PEAK_FLOPS = [
    ("v6", 918e12), ("trillium", 918e12),
    ("v5p", 459e12),
    ("v5 lite", 197e12), ("v5e", 197e12), ("v5lite", 197e12),
    ("v4", 275e12),
    ("v3", 123e12),
    ("v2", 45e12),
]


def _peak_flops(device_kind: str) -> float:
    kind = device_kind.lower()
    for sub, peak in PEAK_FLOPS:
        if sub in kind:
            return peak
    raise KeyError(f"no peak FLOP/s on record for device_kind "
                   f"{device_kind!r}; add it to PEAK_FLOPS with its source")


def main() -> None:
    """Measure on this process's TPU and print one JSON line."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    import optax
    from jax.sharding import NamedSharding, PartitionSpec as P

    from horovod_tpu.models import llama, steps
    from horovod_tpu.parallel import MeshConfig, build_mesh
    from horovod_tpu.utils.compile_cache import ensure_compile_cache

    devices = jax.devices()
    if devices[0].platform != "tpu":
        sys.exit(f"bench: JAX's first device is {devices[0].platform!r}, "
                 "not a TPU; nothing was measured")
    ensure_compile_cache()
    n_dev = len(devices)
    device_kind = devices[0].device_kind
    peak_flops = _peak_flops(device_kind)

    cfg = llama.LlamaConfig(
        vocab_size=32000, d_model=1024, n_layers=8, n_heads=16,
        n_kv_heads=16, d_ff=4096, remat=False, scan_unroll=8)
    # scan_unroll=8 (full unroll at L=8): round-5 trace showed the
    # rolled layer scan paying 5.8 ms/step of stacked-residual
    # dynamic-update-slice copy traffic; full unroll removes it and
    # lets XLA fuse across layers (+10% step time, ~50 s compile).
    # PARTIAL unroll is a trap — 2/4 measured ~35% WORSE than
    # rolled (layout thrash inside the remaining while loop); the
    # knob is binary: 1 or n_layers.
    B, S = 8, 1024
    steps, warmup = 20, 3  # 20 steps: the ANCHOR's protocol — the
    # round-4 40-step runs mixed protocols with the 20-step anchor
    # (verdict weak #2); vs_baseline is only meaningful like-for-like

    mesh = build_mesh(MeshConfig(dp=n_dev))
    params = llama.init_params(cfg, jax.random.PRNGKey(0), mesh)
    n_params = sum(int(x.size) for x in jax.tree.leaves(params))
    tx = optax.adam(1e-4)
    opt_state = jax.jit(tx.init)(params)
    step = steps.make_train_step(cfg, mesh, tx)

    tokens = np.random.RandomState(0).randint(
        0, cfg.vocab_size, size=(B * n_dev, S + 1))
    batch = jax.device_put({"tokens": jnp.asarray(tokens, jnp.int32)},
                           NamedSharding(mesh, P(("dp", "fsdp"))))

    for _ in range(warmup):
        params, opt_state, loss = step(params, opt_state, batch)
    float(loss)  # host fetch fences the warm-up steps

    t0 = time.perf_counter()
    for _ in range(steps):
        params, opt_state, loss = step(params, opt_state, batch)
    final_loss = float(loss)
    elapsed = time.perf_counter() - t0
    assert np.isfinite(final_loss)

    tokens_per_sec = B * n_dev * S * steps / elapsed
    per_chip = tokens_per_sec / n_dev

    # Training FLOPs/token: 6*N for the dense params (+backward), plus the
    # attention score/value matmuls 12*L*d_model*S (PaLM-appendix counting).
    flops_per_token = 6.0 * n_params + 12.0 * cfg.n_layers * cfg.d_model * S
    mfu = (per_chip * flops_per_token) / peak_flops

    # Allreduce point on the same mesh (16 MB payload).  With n>1 ranks
    # this is bus bandwidth; at n=1 there is no wire, so it is labeled as
    # dispatch throughput (round-3 verdict: no number may claim to be bus
    # bandwidth without N>1).
    import horovod_tpu as hvd
    from benchmarks.collective_bench import allreduce_busbw
    hvd.init()
    pt = allreduce_busbw(1 << 24, iters=10, warmup=2)
    key = "busbw_GBs" if "busbw_GBs" in pt else "dispatch_GBs"
    busbw = {key: round(pt[key], 2),
             "at_bytes": pt["bytes"], "ranks": pt["ranks"]}

    print(json.dumps({
        "metric": "llama_train_tokens_per_sec_per_chip_tpu",
        "value": round(per_chip, 1),
        "unit": "tokens/s/chip",
        "vs_baseline": round(per_chip / BENCH_BASELINE, 3),
        "mfu": round(mfu, 4),
        "platform": devices[0].platform,
        "device_kind": device_kind,
        "n_devices": n_dev,
        "allreduce": busbw,
    }))


if __name__ == "__main__":
    main()
