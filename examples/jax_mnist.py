"""MNIST ConvNet, data-parallel — BASELINE config 1.

Reference example: † ``examples/pytorch/pytorch_mnist.py`` (run as
``horovodrun -np 8 python pytorch_mnist.py``).  Here the 8 ranks are the
devices of one host (or a pod): run directly on TPU, or on CPU with

    JAX_PLATFORMS=cpu XLA_FLAGS=--xla_force_host_platform_device_count=8 \
        python examples/jax_mnist.py
"""

import jax
import jax.numpy as jnp
import numpy as np
import optax
from jax import shard_map
from jax.sharding import NamedSharding, PartitionSpec as P

import horovod_tpu as hvd
from horovod_tpu.models.mnist import ConvNet


def synthetic_mnist(n, seed=0):
    rng = np.random.RandomState(seed)
    x = rng.rand(n, 28, 28, 1).astype(np.float32)
    y = (x.mean(axis=(1, 2, 3)) * 10).astype(np.int32) % 10  # learnable rule
    return x, y


def main():
    hvd.init()
    print(f"ranks: {hvd.size()} (local {hvd.local_size()})")
    model = ConvNet()
    params = model.init(jax.random.PRNGKey(0), jnp.zeros((1, 28, 28, 1)))
    params = hvd.broadcast_parameters(params, root_rank=0)  # step-0 sync
    tx = hvd.DistributedOptimizer(optax.adam(1e-3))
    opt_state = tx.init(params)
    mesh = hvd.mesh()

    def step(params, opt_state, x, y):
        def loss_fn(p):
            logits = model.apply(p, x)
            return optax.softmax_cross_entropy_with_integer_labels(
                logits, y).mean()
        loss, grads = jax.value_and_grad(loss_fn)(params)
        updates, opt_state = tx.update(grads, opt_state, params)
        return (optax.apply_updates(params, updates), opt_state,
                jax.lax.pmean(loss, "hvd"))

    train_step = jax.jit(shard_map(
        step, mesh=mesh, in_specs=(P(), P(), P("hvd"), P("hvd")),
        out_specs=(P(), P(), P()), check_vma=False))

    x, y = synthetic_mnist(64 * hvd.size())
    xs = jax.device_put(x, NamedSharding(mesh, P("hvd")))
    ys = jax.device_put(y, NamedSharding(mesh, P("hvd")))
    for epoch in range(5):
        params, opt_state, loss = train_step(params, opt_state, xs, ys)
        print(f"epoch {epoch}: loss {float(loss):.4f}")


if __name__ == "__main__":
    main()
