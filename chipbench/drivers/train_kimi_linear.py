"""The training driver of the Kimi-Linear configuration: back-to-back
steps of the program's train step with the new model.

The loop of :mod:`chipbench.drivers.train` (the seeded feed, ``check_steps``
checked steps in set-up, ``steps_in_flight`` steps queued, the last one
fenced) around ``llama.make_train_step(cfg, mesh, tx, model=kimi_linear)``
on a one-device mesh.  Token ids are drawn over the rows of the vocabulary
held.  The step returns, beside the loss, how many (token, expert) pairs
each expert layer held and how they fell on its experts; the driver keeps
them per step (``counters``) and hands them to the program's own metrics
(``kimi_linear.record_routing``), as a training loop would.
"""

from __future__ import annotations

import sys
import time

import numpy as np

from .. import loadgen, reference_kimi_linear as reference
from .. import weights_kimi_linear as weights
from .train import _adam_mu


def kimi_config(cfg: dict, dims: dict, dtype):
    """The program's ``KimiLinearConfig`` for a configuration file."""
    from horovod_tpu.models import kimi_linear
    return kimi_linear.KimiLinearConfig.from_published(
        cfg, n_experts=dims["n_experts"], experts_held=dims["experts_held"],
        held_first=dims["held_first"], gate_rank=dims["gate_rank"],
        l2_eps=dims["l2_eps"], dtype=dtype, **cfg.get("kimi_config", {}))


def run(run) -> dict:
    import jax
    import jax.numpy as jnp
    import optax
    from jax.sharding import NamedSharding, PartitionSpec as P

    from horovod_tpu.models import kimi_linear, llama
    from horovod_tpu.parallel import MeshConfig, build_mesh

    cfg, tr = run.config, run.traffic
    dims = weights.dims_of(cfg)
    dtype_name = cfg["torch_dtype"]
    dtype = jnp.dtype(dtype_name)
    opt = cfg["optimizer"]
    B, S = tr["rows_per_chip"], tr["sequence_length"]
    n = len(run.devices)
    kcfg = kimi_config(cfg, dims, dtype)
    runs = weights.runs_of(dims)
    assert [(k, f + 1, c) for k, f, c in runs] == \
        kimi_linear.layer_runs(kcfg), "layer pattern"

    mesh = build_mesh(MeshConfig(), devices=run.devices)
    want = cfg.get("attention_path")
    got = llama.attention_path(
        (B, S, kcfg.n_heads, kcfg.qk_nope_dim + kcfg.qk_rope_dim),
        dtype.itemsize, mesh, v_dim=kcfg.v_head_dim)
    assert want in (None, got), f"attention path {got!r}, not {want!r}"
    tx = kimi_linear.optimizer(optax.adamw(
        opt["learning_rate"], b1=opt["b1"], b2=opt["b2"], eps=opt["eps"],
        weight_decay=opt["weight_decay"]))
    key = weights.root_key(run.seed)
    params = jax.jit(lambda k: weights.stacked(k, dims, dtype),
                     out_shardings=kimi_linear.param_shardings(kcfg, mesh)
                     )(key)
    feed = loadgen.TokenBatches(run.seed, n * B, S + 1, dims["vocab_size"])
    batch_sharding = NamedSharding(mesh, P(("dp", "fsdp")))
    step = llama.make_train_step(kcfg, mesh, tx, model=kimi_linear)
    # The state is made on the mesh, as the step hands it back: made
    # off it, the second step would be traced and lowered once more.
    on_mesh = NamedSharding(mesh, P())
    opt_state = jax.jit(tx.init, out_shardings=jax.tree.map(
        lambda _: on_mesh, jax.eval_shape(tx.init, params)))(params)
    jax.block_until_ready(opt_state)
    run.mark("weights")

    def one_step(i, p, st):
        """The window's own feed and call."""
        with run.span("feed"):
            tok = jax.device_put(feed.batch(i), batch_sharding)
        with run.span("train_step"):
            return step(p, st, {"tokens": tok})

    # Per-leaf norms, "L<i>.<leaf>" with 0-based layers, as the reference.
    def by_layer(per_run: list) -> dict:
        """[{leaf: [count]} a run] -> {"L<i>.<leaf>": float}."""
        out = {}
        for (_, first, count), leaves in zip(runs, per_run):
            for k, v in leaves.items():
                if k not in reference.FROZEN:
                    out.update({f"L{first + j}.{k}": float(v[j])
                                for j in range(count)})
        return out

    def flat(norms) -> dict:
        norms = jax.device_get(norms)
        out = {k: float(v) for k, v in norms.items() if k != "runs"}
        out.update(by_layer(norms["runs"]))
        return out

    sq = lambda a, axes: jnp.sqrt(jnp.sum(
        jnp.square(a.astype(jnp.float32)), axis=axes))

    @jax.jit
    def first_grad(mu):
        scale = 1.0 / (1 - opt["b1"])
        out = {k: scale * sq(v, None) for k, v in mu.items() if k != "runs"}
        out["runs"] = [{k: scale * sq(v, tuple(range(1, v.ndim)))
                        for k, v in r.items() if hasattr(v, "ndim")}
                       for r in mu["runs"]]
        return out

    @jax.jit
    def delta(p, key):
        """Norms of the change from the seed's weights."""
        w0 = weights.outer(key, dims, dtype)
        out = {k: sq(p[k].astype(jnp.float32) - w0[k].astype(jnp.float32),
                     None) for k in w0}
        out["runs"] = []
        for (_, first, count), stack in zip(runs, p["runs"]):
            per = [weights.layer(key, first + j, dims, dtype)
                   for j in range(count)]
            out["runs"].append({k: jnp.stack([sq(
                stack[k][j].astype(jnp.float32) - w[k].astype(jnp.float32),
                None) for j, w in enumerate(per)]) for k in stack})
        return out

    n_check = int(tr["check_steps"])
    program = {"loss": []}
    for i in range(n_check):
        params, opt_state, (loss, _) = one_step(i, params, opt_state)
        program["loss"].append(float(loss))
        run.mark(f"step{i + 1}")
        if i == 0:
            program["grad_norm"] = flat(first_grad(_adam_mu(opt_state)))
    program["delta_norm"] = flat(delta(params, key))
    steps_done = n_check

    # -- the window ------------------------------------------------------
    routing: list = []        # per finished step: counts [n_moe, E_held]

    def settle(done):
        loss, stats = done
        with run.span("wait_step"):
            loss.block_until_ready()
        stats = jax.device_get(stats)
        kimi_linear.record_routing(kcfg, stats)
        routing.append(np.asarray(stats["expert_counts"]))

    t_open = run.open_window()
    in_window = 0
    ahead = int(tr.get("steps_in_flight", 1))
    queued: list = []
    while True:
        params, opt_state, out = one_step(steps_done, params, opt_state)
        steps_done += 1
        in_window += 1
        queued.append(out)
        if len(queued) > ahead:
            settle(queued.pop(0))
        if run.poll() - t_open >= run.seconds:
            break
    while queued:
        settle(queued.pop(0))
    wall = run.close_window()
    last_loss = float(out[0])
    tokens = in_window * n * B * S
    rate = tokens / wall / n
    counts = np.stack(routing).astype(np.float64)       # [steps, n_moe, Eh]
    pairs_per_step = float(counts.sum(axis=(1, 2)).mean())
    # fullest held expert over the mean, of every layer and step that
    # held a pair at all
    held = counts.mean(-1) > 0
    load = float((counts.max(-1)[held] / counts.mean(-1)[held]).mean()) \
        if held.any() else None

    state = {"params": params, "opt_state": opt_state}

    def release():
        state.clear()

    def check(control: bool):
        batches = [feed.batch(i) for i in range(n_check)]
        args = (run.seed, dims, dtype_name, batches, opt, run.devices)
        t0 = time.perf_counter()
        ref = reference.train_readings(*args)
        run.counters["reference_s"] = time.perf_counter() - t0
        got = reference.compare_training(program, ref)
        lim = tr["limits"]
        rows = [(k, got[k], lim[k]) for k in lim]
        run.counters["check_others"] = {
            k: got[k] for k in got if k not in lim}
        rows.append(("last_loss_finite", 0.0 if np.isfinite(last_loss)
                     else 1.0, 0.0))
        if control:
            plants = {"fp8": dict(quant="fp8"),
                      "half_batch": dict(rows=slice(0, n * B // 2)),
                      "no_decay": dict(fault="no_decay"),
                      "no_shared": dict(fault="no_shared"),
                      "absent_added": dict(fault="absent_added")}
            for name, kw in plants.items():
                t0 = time.perf_counter()
                bad = reference.compare_training(
                    reference.train_readings(*args, **kw), ref)
                run.controls[name] = bad
                over = [k for k in lim if bad[k] > lim[k]]
                print(f"[chipbench] control {name} "
                      f"({time.perf_counter() - t0:.1f} s): {bad}; over "
                      f"its limit: {over or 'NOTHING'}",
                      file=sys.stderr, flush=True)
        return rows

    return {
        "attempted": in_window, "failed": 0,
        "end_to_end": {"train_tokens_per_s_per_chip": rate},
        "counters": {"steps_in_window": in_window, "window_wall_s": wall,
                     "tokens_per_step_per_chip": B * S,
                     "train_tokens_per_s_per_chip": rate,
                     "pairs_held_per_step": pairs_per_step,
                     "moe_load_max_over_mean": load,
                     "program_losses": program["loss"]},
        "notes": [f"{in_window} steps of {n}x{B}x{S} tokens in "
                  f"{wall:.3f} s; losses of the checked steps "
                  f"{program['loss']}, last {last_loss:.4f}; pairs held a "
                  f"step {pairs_per_step:.0f} of "
                  f"{B * S * dims['experts_per_token'] * counts.shape[1]}, "
                  f"fullest expert over mean {load}",
                  lambda: f"the reference took "
                  f"{run.counters.get('reference_s', 0):.1f} s; read but "
                  f"not held to a limit: {run.counters.get('check_others')}"],
        "release": release, "check": check,
    }
