"""The training driver: back-to-back steps of the program's train step.

One driver for both training cells.  The traffic file's ``step`` says
which of the program's two step shapes runs:

- ``mesh``: ``llama.make_train_step`` on a one-device mesh (the path of
  ``examples/llama_finetune.py``);
- ``hvd``: ``hvd.init()`` and the step of ``optim/distributed.py``'s
  docstring, a jitted ``shard_map`` over the ``hvd`` axis whose
  ``hvd.DistributedOptimizer(optax.adamw).update`` averages the gradients
  (the step shape of ``benchmarks/train_bench.py``), every chip holding a
  whole replica and its own rows.

Set-up builds one object (the compiled step with its state), drives it
through its first ``check_steps`` steps by the window's own feed and call,
reads what the reference will be compared with, and hands the same object
to the window.
"""

from __future__ import annotations

import sys
import time

import numpy as np

from .. import loadgen, reference, weights
from . import llama_config


def _adam_mu(state):
    """The first-moment tree inside an optax state, wherever it nests."""
    if hasattr(state, "mu"):
        return state.mu
    if isinstance(state, (tuple, list)):
        for s in state:
            mu = _adam_mu(s)
            if mu is not None:
                return mu
    return None


def run(run) -> dict:
    import jax
    import jax.numpy as jnp
    import optax
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    from horovod_tpu.models import llama

    cfg, tr = run.config, run.traffic
    dims = reference.dims_of(cfg)
    dtype_name = cfg["torch_dtype"]
    dtype = jnp.dtype(dtype_name)
    opt = cfg["optimizer"]
    B, S = tr["rows_per_chip"], tr["sequence_length"]
    n = len(run.devices)
    lcfg = llama_config(cfg, dims, dtype)
    inner = optax.adamw(opt["learning_rate"], b1=opt["b1"], b2=opt["b2"],
                        eps=opt["eps"], weight_decay=opt["weight_decay"])
    make = lambda key: weights.stacked(key, dims, dtype)
    key = weights.root_key(run.seed)

    if tr["step"] == "mesh":
        from horovod_tpu.parallel import MeshConfig, build_mesh
        mesh = build_mesh(MeshConfig(), devices=run.devices)
        want = cfg.get("attention_path")
        got = llama.attention_path(
            (B, S, lcfg.n_heads, lcfg.head_dim), dtype.itemsize, mesh)
        assert want in (None, got), f"attention path {got!r}, not {want!r}"
        tx = inner
        params = jax.jit(
            make, out_shardings=llama.param_shardings(lcfg, mesh))(key)
        batch_sharding = NamedSharding(mesh, P(("dp", "fsdp")))
        step = llama.make_train_step(lcfg, mesh, tx)
        call = lambda p, st, tok: step(p, st, {"tokens": tok})
    elif tr["step"] == "hvd":
        import horovod_tpu as hvd
        from jax import shard_map
        hvd.init()
        assert hvd.size() >= n, (hvd.size(), n)
        mesh = Mesh(np.array(run.devices), ("hvd",))
        tx = hvd.DistributedOptimizer(inner)
        repl = NamedSharding(mesh, P())
        params = jax.jit(make, out_shardings=repl)(key)
        batch_sharding = NamedSharding(mesh, P("hvd"))

        def local(p, st, tok):
            loss, grads = jax.value_and_grad(
                lambda q: llama.loss_fn(q, {"tokens": tok}, lcfg))(p)
            upd, st = tx.update(grads, st, p)
            return (optax.apply_updates(p, upd), st,
                    jax.lax.pmean(loss, "hvd"))

        call = jax.jit(
            shard_map(local, mesh=mesh, in_specs=(P(), P(), P("hvd")),
                      out_specs=(P(), P(), P()), check_vma=False),
            donate_argnums=(0, 1))
    else:
        raise SystemExit(f"chipbench: unknown train step {tr['step']!r}")
    opt_state = jax.jit(tx.init)(params)
    jax.block_until_ready(opt_state)
    run.mark("weights")

    feed = loadgen.TokenBatches(run.seed, n * B, S + 1, dims["vocab_size"])

    def one_step(i, p, st):
        """The window's own feed and call."""
        with run.span("feed"):
            tok = jax.device_put(feed.batch(i), batch_sharding)
        with run.span("train_step"):
            return call(p, st, tok)

    # Per-leaf norms, one entry per layer for the stacked leaves.
    def leaf_norms(tree):
        sq = lambda a, axes: jnp.sqrt(jnp.sum(
            jnp.square(a.astype(jnp.float32)), axis=axes))
        out = {k: sq(v, None) for k, v in tree.items() if k != "layers"}
        out["layers"] = {k: sq(v, tuple(range(1, v.ndim)))
                         for k, v in tree["layers"].items()}
        return out

    first_grad = jax.jit(lambda mu: leaf_norms(
        jax.tree.map(lambda m: m.astype(jnp.float32) / (1 - opt["b1"]), mu)))

    @jax.jit
    def delta(p, key):
        """Norms of the change from the seed's weights, a layer at a time."""
        def one(i):
            w0 = weights.layer(key, i, dims, dtype)
            return {k: jnp.sqrt(jnp.sum(jnp.square(
                p["layers"][k][i].astype(jnp.float32)
                - w0[k].astype(jnp.float32)))) for k in w0}
        w0 = weights.outer(key, dims, dtype)
        out = {k: jnp.sqrt(jnp.sum(jnp.square(
            p[k].astype(jnp.float32) - w0[k].astype(jnp.float32))))
            for k in w0}
        out["layers"] = jax.lax.map(one, jnp.arange(dims["n_layers"]))
        return out

    def flat(norms):
        norms = jax.device_get(norms)
        out = {k: float(v) for k, v in norms.items() if k != "layers"}
        for k, v in norms["layers"].items():
            out.update({f"L{i}.{k}": float(x) for i, x in enumerate(v)})
        return out

    n_check = int(tr["check_steps"])
    program = {"loss": []}
    for i in range(n_check):
        params, opt_state, loss = one_step(i, params, opt_state)
        program["loss"].append(float(loss))
        run.mark(f"step{i + 1}")
        if i == 0:
            program["grad_norm"] = flat(first_grad(_adam_mu(opt_state)))
    program["delta_norm"] = flat(delta(params, key))
    steps_done = n_check

    # -- the window ------------------------------------------------------
    t_open = run.open_window()
    in_window = 0
    ahead = int(tr.get("steps_in_flight", 1))
    queued: list = []
    while True:
        params, opt_state, loss = one_step(steps_done, params, opt_state)
        steps_done += 1
        in_window += 1
        queued.append(loss)
        if len(queued) > ahead:
            with run.span("wait_step"):
                queued.pop(0).block_until_ready()
        if run.poll() - t_open >= run.seconds:
            break
    with run.span("wait_step"):
        loss.block_until_ready()
    wall = run.close_window()
    last_loss = float(loss)
    tokens = in_window * n * B * S
    rate = tokens / wall / n

    state = {"params": params, "opt_state": opt_state}

    def release():
        state.clear()

    def check(control: bool):
        batches = [feed.batch(i) for i in range(n_check)]
        args = (run.seed, dims, dtype_name, batches, opt, run.devices)
        kw0 = dict(history=tr.get("reference_history", "host"))
        t0 = time.perf_counter()
        ref = reference.train_readings(*args, **kw0)
        run.counters["reference_s"] = time.perf_counter() - t0
        got = reference.compare_training(program, ref)
        lim = tr["limits"]
        rows = [(k, got[k], lim[k]) for k in lim]
        run.counters["check_others"] = {
            k: got[k] for k in got if k not in lim}
        rows.append(("last_loss_finite", 0.0 if np.isfinite(last_loss)
                     else 1.0, 0.0))
        if control:
            R = n * B
            plants = {"fp8": dict(quant="fp8"),
                      "half_batch": dict(rows=slice(0, R // 2))}
            if n > 1:
                plants["no_exchange"] = dict(rows=slice(0, B))
            for name, kw in plants.items():
                t0 = time.perf_counter()
                bad = reference.compare_training(
                    reference.train_readings(*args, **kw0, **kw), ref)
                run.controls[name] = bad
                print(f"[chipbench] control {name} "
                      f"({time.perf_counter() - t0:.1f} s): {bad}",
                      file=sys.stderr, flush=True)
        return rows

    return {
        "attempted": in_window, "failed": 0,
        "end_to_end": {"train_tokens_per_s_per_chip": rate},
        "counters": {"steps_in_window": in_window, "window_wall_s": wall,
                     "tokens_per_step_per_chip": B * S,
                     "train_tokens_per_s_per_chip": rate,
                     "program_losses": program["loss"]},
        "notes": [f"{in_window} steps of {n}x{B}x{S} tokens in "
                  f"{wall:.3f} s; losses of the checked steps "
                  f"{program['loss']}, last {last_loss:.4f}",
                  lambda: f"the reference took "
                  f"{run.counters.get('reference_s', 0):.1f} s; read but "
                  f"not held to a limit: {run.counters.get('check_others')}"],
        "release": release, "check": check,
    }
