"""Mixture-of-experts Llama training under the elastic launcher.

The MoE variant rides one config flag: ``LlamaConfig(use_moe=True)``
replaces every MLP with a Switch layer (top-1 routing, static capacity,
aux load-balancing loss), and ``MeshConfig(ep=...)`` shards the experts
— dispatch/combine run over the ep axis inside the compiled step.
Dropped-token counts surface as ``hvd_moe_dropped_tokens_total{layer}``,
the capacity-factor tuning signal.

Demo shapes run anywhere:

    JAX_PLATFORMS=cpu XLA_FLAGS=--xla_force_host_platform_device_count=8 \
        python examples/llama_moe.py

or under the launcher (the autoscale chaos scenario drives the same
layer through ``hvd.alltoall`` at job scale):

    hvdrun -np 2 --platform cpu -- python examples/llama_moe.py
"""

import jax
import jax.numpy as jnp
import numpy as np
import optax
from jax.sharding import NamedSharding, PartitionSpec as P

import horovod_tpu as hvd
from horovod_tpu.models import llama, steps
from horovod_tpu.parallel import MeshConfig, build_mesh


def main():
    hvd.init()
    n = len(jax.devices())   # global device count = mesh size
    # Experts want an ep axis when there is room; n_experts must divide
    # across it.
    ep = 2 if n % 2 == 0 and n >= 2 else 1
    mesh_cfg = MeshConfig(dp=n // ep, ep=ep)
    mesh = build_mesh(mesh_cfg)
    print("mesh:", mesh_cfg.axis_sizes())

    cfg = llama.LlamaConfig.tiny(d_model=64, n_layers=2, n_heads=4,
                                 n_kv_heads=4, d_ff=128,
                                 use_moe=True, n_experts=4,
                                 capacity_factor=1.25)
    params = llama.init_params(cfg, jax.random.PRNGKey(0), mesh)
    tx = optax.adamw(3e-4, weight_decay=0.01)
    opt_state = jax.jit(tx.init)(params)
    step = steps.make_train_step(cfg, mesh, tx)

    B, S = 2 * (n // ep), 32   # 2 sequences per dp shard
    tokens = np.random.RandomState(0).randint(0, cfg.vocab_size,
                                              size=(B, S + 1))
    batch = jax.device_put({"tokens": jnp.asarray(tokens, jnp.int32)},
                           NamedSharding(mesh, P(("dp", "fsdp"))))

    losses = []
    for i in range(5):
        params, opt_state, loss = step(params, opt_state, batch)
        losses.append(float(loss))
        print(f"step {i}: loss {losses[-1]:.4f}")
    assert all(np.isfinite(losses)), losses
    assert losses[-1] < losses[0], (
        "MoE loss did not improve", losses)
    print(f"DONE moe rank={hvd.rank()}/{hvd.size()} ep={ep} "
          f"loss {losses[0]:.4f} -> {losses[-1]:.4f}")
    hvd.shutdown()


if __name__ == "__main__":
    main()
