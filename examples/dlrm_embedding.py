"""DLRM with sharded embeddings + alltoall exchange — BASELINE config 5.

The reference's reason for ``hvd.alltoall`` († v0.20): DLRM-style
model-parallel embedding tables.  Tables shard across devices; every step,
one alltoall each way re-shards lookups between table-major and
batch-major.

    JAX_PLATFORMS=cpu XLA_FLAGS=--xla_force_host_platform_device_count=8 \
        python examples/dlrm_embedding.py
"""

import jax
import jax.numpy as jnp
import numpy as np
import optax
from functools import partial
from jax import shard_map
from jax.sharding import NamedSharding, PartitionSpec as P

import horovod_tpu as hvd
from horovod_tpu.models import dlrm


def main():
    hvd.init()
    mesh = hvd.mesh()
    cfg = dlrm.DlrmConfig.tiny()
    model = dlrm.DlrmDense(cfg)
    tables = dlrm.init_embedding_tables(cfg, jax.random.PRNGKey(0))
    batch = dlrm.synthetic_batch(cfg, batch=64)
    params = model.init(
        jax.random.PRNGKey(1), jnp.zeros((1, cfg.n_dense)),
        jnp.zeros((1, cfg.n_sparse, cfg.embed_dim)))
    tx = optax.adam(1e-2)
    opt_state = tx.init((params, tables))

    b_sh = NamedSharding(mesh, P("hvd"))
    repl = NamedSharding(mesh, P())

    def step(params, tables, opt_state, dense, sparse, label):
        def loss_fn(pt):
            p, tb = pt
            emb = shard_map(
                partial(dlrm.sharded_embedding_lookup_local,
                        axis_name="hvd"),
                mesh=mesh, in_specs=(P("hvd"), P("hvd")),
                out_specs=P("hvd"), check_vma=False)(tb, sparse)
            logit = model.apply(p, dense, emb)
            return optax.sigmoid_binary_cross_entropy(logit, label).mean()
        loss, grads = jax.value_and_grad(loss_fn)((params, tables))
        updates, opt_state = tx.update(grads, opt_state, (params, tables))
        params, tables = optax.apply_updates((params, tables), updates)
        return params, tables, opt_state, loss

    jstep = jax.jit(step, in_shardings=(repl, b_sh, None, b_sh, b_sh, b_sh),
                    out_shardings=(repl, b_sh, None, repl))
    args = [jax.device_put(batch[k], b_sh)
            for k in ("dense", "sparse", "label")]
    tables = jax.device_put(tables, b_sh)
    for i in range(10):
        params, tables, opt_state, loss = jstep(params, tables, opt_state,
                                                *args)
        print(f"step {i}: loss {float(loss):.4f}")


if __name__ == "__main__":
    main()
