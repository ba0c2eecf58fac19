"""Llama fine-tune with full multi-axis parallelism — BASELINE config 4.

Pick the mesh for your hardware: dp for batch, tp for per-layer sharding,
sp for long context (ring attention by default; Ulysses via
``LlamaConfig(sp_attention="ulysses")``), pp for depth (1F1B schedule by
default; tune the bubble with ``pp_microbatches``), ep for MoE.  On a
v5p-64 (64 chips): e.g. MeshConfig(dp=4, tp=8, sp=2) for 7B long-context,
or MeshConfig(pp=4, dp=4, tp=4) for depth-heavy models.

Demo shapes run anywhere:

    JAX_PLATFORMS=cpu XLA_FLAGS=--xla_force_host_platform_device_count=8 \
        python examples/llama_finetune.py
"""

import jax
import jax.numpy as jnp
import numpy as np
import optax
from jax.sharding import NamedSharding, PartitionSpec as P

import horovod_tpu as hvd
from horovod_tpu.models import llama, steps
from horovod_tpu.parallel import MeshConfig, build_mesh
from horovod_tpu.utils.checkpoint import Checkpointer


def main():
    hvd.init()
    n = hvd.size()
    # Demo mesh: dp × sp × tp (swap for your topology).
    if n == 8:
        mesh_cfg = MeshConfig(dp=2, sp=2, tp=2)
    else:
        mesh_cfg = MeshConfig.auto(n)
    mesh = build_mesh(mesh_cfg)
    print("mesh:", mesh_cfg.axis_sizes())

    cfg = llama.LlamaConfig.tiny(d_model=128, n_layers=4, n_heads=8,
                                 n_kv_heads=4, d_ff=256)
    # Real runs: llama.LlamaConfig.llama2_7b()
    params = llama.init_params(cfg, jax.random.PRNGKey(0), mesh)
    tx = optax.adamw(3e-4, weight_decay=0.01)
    opt_state = jax.jit(tx.init)(params)
    step = steps.make_train_step(cfg, mesh, tx)

    B, S = 8, 64
    tokens = np.random.RandomState(0).randint(0, cfg.vocab_size,
                                              size=(B, S + 1))
    batch = jax.device_put({"tokens": jnp.asarray(tokens, jnp.int32)},
                           NamedSharding(mesh, P(("dp", "fsdp"))))

    ckpt = Checkpointer("/tmp/llama_ckpt")
    for i in range(10):
        params, opt_state, loss = step(params, opt_state, batch)
        print(f"step {i}: loss {float(loss):.4f}")
    ckpt.save(10, {"params": params})
    print("checkpoint saved at step", ckpt.latest_step())


if __name__ == "__main__":
    main()
