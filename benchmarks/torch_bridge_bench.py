"""Torch-bridge transfer batching microbenchmark.

Counts host<->device staging transfers per optimizer step and times the
step for (a) per-tensor flushing (bucket_cap_bytes=1 — every gradient is
its own bucket, the round-2 behavior) vs (b) fused bucketing (default
cap = the engine's fusion threshold).  Proves the VERDICT #4 done
criterion: transfers per step drop from O(n_params) to O(1) and the step
gets faster.

Run on the 8-device CPU rig:
    python benchmarks/torch_bridge_bench.py
"""

from __future__ import annotations

import json
import os
import sys
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if REPO not in sys.path:
    sys.path.insert(0, REPO)

from horovod_tpu.utils.cpurig import force_cpu_platform  # noqa: E402

force_cpu_platform(8)   # the 8-device dev rig

N_LAYERS = 64
WIDTH = 128
STEPS = 10


def bench(bucket_cap_bytes):
    import torch

    import horovod_tpu.torch as hvd
    from horovod_tpu.ops import collectives as C

    model = torch.nn.Sequential(*[
        torch.nn.Linear(WIDTH, WIDTH) for _ in range(N_LAYERS)])
    opt = hvd.DistributedOptimizer(
        torch.optim.SGD(model.parameters(), lr=1e-3),
        named_parameters=model.named_parameters(),
        bucket_cap_bytes=bucket_cap_bytes)

    # Count staging transfers: replicate_local = host->device uploads,
    # to_numpy = device->host fetches.
    counts = {"h2d": 0, "d2h": 0}
    orig_rep, orig_tonp = C.replicate_local, C.to_numpy

    def rep(*a, **k):
        counts["h2d"] += 1
        return orig_rep(*a, **k)

    def tonp(*a, **k):
        counts["d2h"] += 1
        return orig_tonp(*a, **k)

    C.replicate_local = rep
    import horovod_tpu as _hvd_root
    orig_root_tonp = _hvd_root.to_numpy
    _hvd_root.to_numpy = tonp
    try:
        x = torch.randn(16, WIDTH)
        # warmup (compiles the fused programs)
        loss = model(x).square().mean()
        loss.backward()
        opt.step()
        opt.zero_grad()
        counts["h2d"] = counts["d2h"] = 0
        t0 = time.perf_counter()
        for _ in range(STEPS):
            loss = model(x).square().mean()
            loss.backward()
            opt.step()
            opt.zero_grad()
        dt = (time.perf_counter() - t0) / STEPS
    finally:
        C.replicate_local = orig_rep
        _hvd_root.to_numpy = orig_root_tonp
    return {"h2d_per_step": counts["h2d"] // STEPS,
            "d2h_per_step": counts["d2h"] // STEPS,
            "step_ms": round(dt * 1e3, 2)}


def main(argv=None):
    import argparse
    ap = argparse.ArgumentParser()
    ap.add_argument("--no-persist", action="store_true",
                    help="skip appending to benchmarks/measured.jsonl "
                         "(scratch/CI runs)")
    args = ap.parse_args(argv)
    import horovod_tpu as hvd
    hvd.init()
    per_tensor = bench(bucket_cap_bytes=1)
    fused = bench(bucket_cap_bytes=None)
    rec = {
        "metric": "torch_bridge_transfers",
        "n_params": N_LAYERS * 2,
        "per_tensor": per_tensor,
        "fused": fused,
        "transfer_reduction": round(
            per_tensor["h2d_per_step"] / max(fused["h2d_per_step"], 1), 1),
        "speedup": round(per_tensor["step_ms"] / fused["step_ms"], 2),
        "ts": time.time(),
    }
    print(json.dumps(rec))
    if not args.no_persist:
        from benchmarks._common import persist
        persist(rec)
    return rec


if __name__ == "__main__":
    main()
