"""Autotuner proof: the GP+EI loop must find knobs that beat a bad start.

† ``parameter_manager.cc`` purpose — the reference shipped
``HOROVOD_AUTOTUNE_LOG`` traces showing fusion-threshold moves; this is
the equivalent committed evidence for the TPU rebuild (round-2 verdict
item 7).

Workload: many small async allreduces per round (a gradient-stream
shape).  Both runs start from deliberately bad knobs (64 KB fusion
threshold — nothing fuses — and a 20 ms cycle).  The autotuned run must
converge to a bigger threshold / shorter cycle and beat the untuned
steady-state throughput.

    python benchmarks/autotune_bench.py        # 8-device CPU rig
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

# The PJRT CPU client runs every device's share of a program on one pool
# of max(cores, devices) threads, and a collective holds its thread until
# all 8 shares have arrived.  With several programs in flight on a loaded
# box the pool fills with shares of later programs, one of the earliest
# never starts, and XLA aborts the process after 40 s (what tier-1 saw of
# this bench under six test workers).  A pool with room for the waiters
# cannot starve; the variable is read when the client is made.
os.environ.setdefault("PJRT_NPROC", "64")
force_cpu_platform(8)

import numpy as np  # noqa: E402

BAD_THRESHOLD = 4 * 1024           # nothing fuses
BAD_CYCLE_MS = 20.0                # sluggish batching window
N_TENSORS = 96                     # grads per "step": many small tensors,
TENSOR_ELEMS = 1024                # 4 KB fp32 each -> dispatch-bound
ROUNDS_MEASURE = 30
ROUNDS_TUNE = 260                  # enough cycles for warmup+converge


def _one_round(hvd, i: int) -> int:
    # Waves of 24 bound the number of concurrently-executing XLA CPU
    # programs: each 8-device collective needs all 8 device threads to
    # rendezvous, and unbounded async dispatch of ~100 tiny programs can
    # starve one participant past the 40 s rendezvous abort.
    for base in range(0, N_TENSORS, 24):
        hs = [hvd.allreduce_async(
            hvd.per_rank([np.full((TENSOR_ELEMS,), float(r + j), np.float32)
                          for r in range(8)]),
            hvd.Average, name=f"g.{j}")
            for j in range(base, min(base + 24, N_TENSORS))]
        for h in hs:
            hvd.synchronize(h)
    return N_TENSORS * TENSOR_ELEMS * 4


def run(autotune: bool, log_path: str | None = None) -> dict:
    os.environ["HVDTPU_FUSION_THRESHOLD"] = str(BAD_THRESHOLD)
    os.environ["HVDTPU_CYCLE_TIME"] = str(BAD_CYCLE_MS)
    os.environ["HVDTPU_AUTOTUNE"] = "1" if autotune else "0"
    os.environ["HVDTPU_AUTOTUNE_STEPS_PER_SAMPLE"] = "8"
    if log_path:
        os.environ["HVDTPU_AUTOTUNE_LOG"] = log_path
    import horovod_tpu as hvd
    hvd.shutdown()
    hvd.init()
    try:
        # Warm the dispatch cache / let the tuner explore.
        tune_rounds = ROUNDS_TUNE if autotune else 10
        for i in range(tune_rounds):
            _one_round(hvd, i)
        cfg = hvd.global_state().config
        knobs = {"fusion_threshold": cfg.fusion_threshold,
                 "cycle_time_ms": cfg.cycle_time_ms}
        t0 = time.perf_counter()
        total = 0
        for i in range(ROUNDS_MEASURE):
            total += _one_round(hvd, i)
        dt = time.perf_counter() - t0
    finally:
        hvd.shutdown()
    return {"autotune": autotune, "knobs": knobs,
            "throughput_MBs": round(total / dt / 1e6, 2),
            "rounds_per_s": round(ROUNDS_MEASURE / dt, 2)}


def main(argv=None) -> dict:
    import argparse
    ap = argparse.ArgumentParser()
    ap.add_argument("--log", default=None,
                    help="autotune log path (default: the committed "
                         "benchmarks/autotune_log.txt; tests pass a "
                         "scratch path so CI never dirties the artifact)")
    ap.add_argument("--no-persist", action="store_true",
                    help="skip appending to benchmarks/measured.jsonl")
    args = ap.parse_args(argv)
    evidence_mode = not args.no_persist
    log_path = args.log or os.path.join(REPO, "benchmarks",
                                        "autotune_log.txt")
    if os.path.exists(log_path):
        os.remove(log_path)
    untuned = run(False)
    tuned = run(True, log_path)
    rec = {
        "metric": "autotune_throughput",
        "untuned": untuned, "tuned": tuned,
        "speedup": round(tuned["throughput_MBs"]
                         / untuned["throughput_MBs"], 2),
        "ts": time.time(),
    }
    print(json.dumps(rec))
    if evidence_mode:
        from benchmarks._common import persist
        persist(rec)
    return rec


if __name__ == "__main__":
    main()
