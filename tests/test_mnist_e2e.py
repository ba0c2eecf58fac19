"""End-to-end: MNIST ConvNet trained data-parallel on 8 fake devices.

The SURVEY §7 phase-1 milestone (reference config 1, †
``examples/pytorch/pytorch_mnist.py`` run under ``horovodrun``): model
replicated, batch sharded across the hvd axis, gradients averaged by
``DistributedOptimizer``, loss must decrease and parameters must stay
identical across ranks.
"""

import jax
import jax.numpy as jnp
import numpy as np
import optax
from jax import shard_map
from jax.sharding import NamedSharding, PartitionSpec as P

import horovod_tpu as hvd
from horovod_tpu.models.mnist import ConvNet

N = 8
BATCH = 32  # global; 4 per rank


def _synthetic_mnist(n, seed=0):
    rng = np.random.RandomState(seed)
    x = rng.rand(n, 28, 28, 1).astype(np.float32)
    y = rng.randint(0, 10, size=(n,)).astype(np.int32)
    return x, y


def test_mnist_convnet_trains():
    model = ConvNet()
    x_host, y_host = _synthetic_mnist(BATCH)
    params = model.init(jax.random.PRNGKey(0), jnp.zeros((1, 28, 28, 1)))
    params = hvd.broadcast_parameters(params, root_rank=0)

    tx = hvd.DistributedOptimizer(optax.adam(1e-3))
    opt_state = tx.init(params)
    mesh = hvd.mesh()

    def step(params, opt_state, x, y):
        def loss_fn(p):
            logits = model.apply(p, x)
            return optax.softmax_cross_entropy_with_integer_labels(
                logits, y).mean()
        loss, grads = jax.value_and_grad(loss_fn)(params)
        updates, opt_state2 = tx.update(grads, opt_state, params)
        new_params = optax.apply_updates(params, updates)
        return new_params, opt_state2, jax.lax.pmean(loss, "hvd")

    sharded_step = jax.jit(shard_map(
        step, mesh=mesh,
        in_specs=(P(), P(), P("hvd"), P("hvd")),
        out_specs=(P(), P(), P()),
        check_vma=False))

    x = jax.device_put(x_host, NamedSharding(mesh, P("hvd")))
    y = jax.device_put(y_host, NamedSharding(mesh, P("hvd")))

    losses = []
    for _ in range(30):
        params, opt_state, loss = sharded_step(params, opt_state, x, y)
        losses.append(float(loss))

    # Overfits the fixed batch: loss must drop substantially.
    assert losses[-1] < losses[0] * 0.5, f"loss did not decrease: {losses}"

    # Parameters must be replicated (identical on every device).
    leaf = jax.tree.leaves(params)[0]
    assert leaf.sharding.is_fully_replicated

    # Inference path produces a valid distribution.
    logits = model.apply(params, jnp.asarray(x_host[:4]))
    assert logits.shape == (4, 10)
    assert np.isfinite(np.asarray(logits)).all()
