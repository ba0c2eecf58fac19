"""Llama-tiny train-step bench: dense optimizer vs ZeRO-1 sharded.

Runs the same data-parallel train step (shard_map over the ``hvd`` axis,
decomposed rs_ag schedule) twice per world size — once with the dense
``DistributedOptimizer`` (full Adam state on every rank) and once with
``ZeroDistributedOptimizer`` (state sharded 1/n, one parameter allgather
closing the step) — and records per variant

- ``trainstep_{dense|zero1}_step_ms@np{N}``       wall-clock per step
- ``trainstep_{dense|zero1}_opt_state_bytes@np{N}`` per-rank Adam state

Honest CPU-rig caveat (same as collective_bench): the rig serializes
device work through shared memory, so ZeRO's wall-clock is dispatch-
bound here and lands at ~parity with dense (its wire bytes are identical
by construction: rs + param-ag == rs + grad-ag).  The number that
transfers to a real pod is the ``opt_state_bytes`` series — ~1/n of
dense plus shard padding — which is why the byte rows are gated
lower-is-better in benchmarks/regress.py.

    python -m benchmarks.train_bench --cpu-devices 8 --np 2,4 \
        --out BENCH_r12.json

Appends one measured.jsonl record per metric (``--no-persist`` to skip)
and, with ``--out``, writes the round record whose ``trainstep`` section
benchmarks/regress.py normalizes into the committed trajectory.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if REPO not in sys.path:
    sys.path.insert(0, REPO)

from benchmarks._common import fence, persist  # noqa: E402


def bench_np(np_: int, *, steps: int, reps: int, B: int, S: int,
             do_persist: bool) -> list:
    import jax
    import numpy as np
    import optax
    from jax import lax
    from jax.sharding import Mesh, PartitionSpec as P

    import horovod_tpu as hvd
    from jax import shard_map
    from horovod_tpu.models import llama
    from horovod_tpu.optim import partition as PP

    mesh = Mesh(np.array(jax.devices()[:np_]), ("hvd",))
    mcfg = llama.LlamaConfig.tiny()
    params = llama.init_params(mcfg, jax.random.PRNGKey(0))
    rng = np.random.RandomState(1234)
    tokens = rng.randint(0, mcfg.vocab_size, size=(np_, steps, B, S + 1)
                         ).astype(np.int32)

    def make_tx(label):
        if label == "zero1":
            # num_shards pins the shard count: this subset mesh is
            # smaller than the world hvd.init() saw.
            return hvd.ZeroDistributedOptimizer(
                optax.adam(1e-3), num_shards=np_)
        return hvd.DistributedOptimizer(optax.adam(1e-3))

    rows, losses_by = [], {}
    for label in ("dense", "zero1"):
        tx = make_tx(label)

        def run(tok, p):
            # init INSIDE the mapped context: ZeRO slices the true
            # parameter shard; every timed call reinitializes state on
            # both variants, so the measured work is identical in kind.
            st0 = tx.init(p)

            def body(carry, t):
                p_, st_ = carry
                loss, grads = jax.value_and_grad(
                    lambda q: llama.loss_fn(q, {"tokens": t}, mcfg))(p_)
                upd, st_ = tx.update(grads, st_, p_)
                return (optax.apply_updates(p_, upd), st_), loss

            (_, _), ls = lax.scan(body, (p, st0), tok[0])
            return ls[None]

        fn = jax.jit(shard_map(run, mesh=mesh, in_specs=(P("hvd"), P()),
                               out_specs=P("hvd"), check_vma=False))
        out = fn(tokens, params)        # compile + warmup
        fence(out)
        t0 = time.perf_counter()
        for _ in range(reps):
            out = fn(tokens, params)
        fence(out)
        dt = time.perf_counter() - t0
        losses_by[label] = np.asarray(hvd.to_numpy(out))
        step_ms = dt * 1e3 / (reps * steps)
        state_bytes = PP.shard_bytes(tx.init(params))
        note = (f"llama-tiny B={B} S={S} adam, decomposed rs_ag, "
                f"{'1/n-sharded' if label == 'zero1' else 'replicated'} "
                "state")
        for metric, value, unit in (
                (f"trainstep_{label}_step_ms@np{np_}",
                 round(step_ms, 3), "ms"),
                (f"trainstep_{label}_opt_state_bytes@np{np_}",
                 int(state_bytes), "bytes")):
            rec = {"metric": metric, "value": value, "unit": unit,
                   "device_kind": f"cpu-rig-np{np_}", "ranks": np_,
                   "ts": time.time(), "note": note}
            print(json.dumps(rec))
            rows.append(rec)
            if do_persist:
                persist(rec)

    # Parity sanity on the bench config itself: the two loss trajectories
    # may differ only by reduce-scatter association order (<= a few ulp).
    d_, z_ = losses_by["dense"], losses_by["zero1"]
    rel = float(np.max(np.abs(d_ - z_) / np.maximum(np.abs(d_), 1e-12)))
    assert rel < 1e-5, f"dense/zero1 loss divergence at np={np_}: {rel}"
    print(json.dumps({"parity_check": f"np{np_}",
                      "max_rel_loss_diff": rel}))
    return rows


def main() -> int:
    ap = argparse.ArgumentParser(prog="python -m benchmarks.train_bench")
    ap.add_argument("--cpu-devices", type=int, default=0, metavar="N",
                    help="force an N-device virtual CPU rig (the np list "
                    "runs on subset meshes of it)")
    ap.add_argument("--np", default="2,4", metavar="LIST",
                    help="comma-separated world sizes (default 2,4)")
    ap.add_argument("--steps", type=int, default=6,
                    help="train steps per timed program (lax.scan length)")
    ap.add_argument("--reps", type=int, default=3,
                    help="timed repetitions of the scanned program")
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--out", default=None, metavar="FILE",
                    help="write a BENCH_rXX.json round record (trainstep "
                    "section) for benchmarks/regress.py")
    ap.add_argument("--no-persist", action="store_true",
                    help="skip appending to benchmarks/measured.jsonl")
    args = ap.parse_args()
    if args.cpu_devices:
        from horovod_tpu.utils.cpurig import force_cpu_platform
        force_cpu_platform(args.cpu_devices)
    import horovod_tpu as hvd
    hvd.init()
    cfg = hvd.global_state().config
    # The schedule under test: the decomposed rs_ag chain ZeRO rides
    # (monolithic would fall back to the dense reduce + slice path).
    cfg.sched_mode, cfg.sched_chunks = "decomposed", 2

    sizes = [int(s) for s in args.np.split(",") if s.strip()]
    rows = []
    for np_ in sizes:
        if np_ > hvd.size():
            print(f"skip np={np_}: rig has {hvd.size()} devices",
                  file=sys.stderr)
            continue
        rows += bench_np(np_, steps=args.steps, reps=args.reps,
                         B=args.batch, S=args.seq,
                         do_persist=not args.no_persist)
    if args.out:
        record = {
            "cmd": "python -m benchmarks.train_bench --cpu-devices "
                   f"{args.cpu_devices or 0} --np {args.np} "
                   f"--out {os.path.basename(args.out)}",
            "notes": (
                "Llama-tiny dense vs ZeRO-1 train step (decomposed "
                "rs_ag, adam). CPU-rig caveat: step_ms is dispatch-"
                "bound shared-memory wall-clock, expected ~parity "
                "(identical wire bytes by construction); the "
                "transferable series is opt_state_bytes (~1/n of dense "
                "+ shard padding), gated lower-is-better."),
            "trainstep": rows,
        }
        with open(args.out, "w") as fh:
            json.dump(record, fh, indent=1, sort_keys=True)
            fh.write("\n")
        print(f"wrote {args.out}: {len(rows)} trainstep rows")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
