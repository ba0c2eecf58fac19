"""Multi-host step-time overhead microbench (round-4 verdict ask #2).

The multi-HOST data plane was correctness-proven in round 4
(``tests/test_run_api.py``: flagship step over a 2-process
``jax.distributed`` global mesh, bitwise rank-identical).  This measures
its COST on the same rig: per parallelism axis (dp/tp/pp), flagship step
time with the two mesh devices split across two PROCESSES (collectives
ride the jax.distributed cross-process transport) vs the single-process
oracle on the same 2-device CPU mesh (collectives stay in-process).

The absolute times are host-CPU numbers — the record is the RATIO shape
(which axes pay how much for crossing a process boundary), the TPU
analogue of † ``docs/benchmarks.rst`` scaling evidence within a
1-chip-rig's limits.

Usage: python benchmarks/multihost_bench.py [--steps 8] [--no-persist]
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

from benchmarks._common import persist as _persist  # noqa: E402

# Big enough that a step is milliseconds (not noise), small enough that
# the 2-process jobs stay seconds on a CPU rig.
MODEL_KW = dict(vocab_size=512, d_model=256, n_layers=4, n_heads=8,
                n_kv_heads=8, d_ff=1024, remat=False)
B, S = 8, 128
DTYPE = "float32"


def _step_loop(mesh, batch, steps, warmup):
    import jax
    import jax.numpy as jnp
    import optax

    from horovod_tpu.models import llama, steps

    cfg = llama.LlamaConfig(**MODEL_KW, dtype=jnp.dtype(DTYPE))
    params = llama.init_params(cfg, jax.random.PRNGKey(0), mesh)
    tx = optax.adam(1e-3)
    opt = jax.jit(tx.init)(params)
    step = steps.make_train_step(cfg, mesh, tx)
    for _ in range(warmup):
        params, opt, loss = step(params, opt, batch)
    float(loss)
    t0 = time.perf_counter()
    for _ in range(steps):
        params, opt, loss = step(params, opt, batch)
    float(loss)
    return (time.perf_counter() - t0) / steps * 1e3     # ms/step


def _tokens():
    import numpy as np
    return np.random.RandomState(0).randint(
        0, MODEL_KW["vocab_size"], (B, S + 1))


def _multiproc_work(axis, steps, warmup):
    """One rank of the 2-process job: global 2-device mesh, timed loop."""
    from horovod_tpu.utils.cpurig import force_cpu_platform
    force_cpu_platform(1)
    import jax
    import jax.numpy as jnp

    import horovod_tpu as hvd
    hvd.init()
    from jax.sharding import NamedSharding, PartitionSpec as P

    from horovod_tpu.parallel import MeshConfig, build_mesh
    mesh = build_mesh(MeshConfig(**{axis: 2}))
    tokens = _tokens()
    me = hvd.rank()
    local = tokens[B // 2 * me:B // 2 * (me + 1)] if axis == "dp" else tokens
    batch = {"tokens": jax.make_array_from_process_local_data(
        NamedSharding(mesh, P(("dp", "fsdp"))),
        jnp.asarray(local, jnp.int32), (B, S + 1))}
    ms = _step_loop(mesh, batch, steps, warmup)
    hvd.shutdown()
    return ms


def run(steps: int = 8, warmup: int = 2, persist: bool = True):
    from horovod_tpu.runner.api import run_func

    axes = {}
    for axis in ("dp", "tp", "pp"):
        mp_ms = max(run_func(_multiproc_work, args=(axis, steps, warmup),
                             np=2))

        # Single-process oracle on the same mesh shape/data, measured in a
        # fresh subprocess so backend/platform state never leaks between
        # the flavors.
        code = (
            "import sys; sys.path.insert(0, %r)\n"
            "from horovod_tpu.utils.cpurig import force_cpu_platform\n"
            "force_cpu_platform(2)\n"
            "import jax, jax.numpy as jnp\n"
            "from jax.sharding import NamedSharding, PartitionSpec as P\n"
            "from horovod_tpu.parallel import MeshConfig, build_mesh\n"
            "import benchmarks.multihost_bench as MB\n"
            "mesh = build_mesh(MeshConfig(%s=2))\n"
            "batch = {'tokens': jax.device_put(\n"
            "    jnp.asarray(MB._tokens(), jnp.int32),\n"
            "    NamedSharding(mesh, P(('dp', 'fsdp'))))}\n"
            "print('MS', MB._step_loop(mesh, batch, %d, %d))\n"
        ) % (REPO, axis, steps, warmup)
        import subprocess
        r = subprocess.run([sys.executable, "-c", code],
                           capture_output=True, text=True, timeout=600,
                           cwd=REPO)
        if r.returncode != 0:
            raise RuntimeError(f"oracle failed for {axis}: "
                               f"{r.stdout}\n{r.stderr}")
        sp_ms = float([ln for ln in r.stdout.splitlines()
                       if ln.startswith("MS")][-1].split()[1])
        axes[axis] = {
            "multiproc_ms_per_step": round(mp_ms, 2),
            "singleproc_ms_per_step": round(sp_ms, 2),
            "overhead_pct": round((mp_ms / sp_ms - 1.0) * 100, 1),
        }
        print(f"{axis}: mp={mp_ms:.2f} ms  sp={sp_ms:.2f} ms  "
              f"overhead={axes[axis]['overhead_pct']}%")

    rec = {
        "metric": "multihost_step_overhead_cpu2proc",
        "model": MODEL_KW, "batch": B, "seq": S, "dtype": DTYPE,
        "steps": steps, "axes": axes,
        "note": ("flagship train-step time, 2-device mesh as 2 PROCESSES "
                 "(jax.distributed cross-process collectives) vs one "
                 "process (in-process collectives), same CPU rig; "
                 "absolute ms are host-CPU — the overhead shape per axis "
                 "is the datum (round-4 verdict ask #2)"),
        "platform": "cpu-2dev", "ts": time.time(),
    }
    print(json.dumps(rec))
    if persist:
        _persist(rec)
    return rec


if __name__ == "__main__":
    ap = argparse.ArgumentParser()
    ap.add_argument("--steps", type=int, default=8)
    ap.add_argument("--no-persist", action="store_true")
    args = ap.parse_args()
    run(steps=args.steps, persist=not args.no_persist)
