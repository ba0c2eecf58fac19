"""Blockwise cross-entropy (ops/losses.py) vs the dense log_softmax
oracle: values and gradients must agree to fp32 tolerance."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from horovod_tpu.ops.losses import blockwise_cross_entropy


def _dense_nll(x, w, targets):
    logits = (x @ w).astype(jnp.float32)
    logp = jax.nn.log_softmax(logits, axis=-1)
    return -jnp.take_along_axis(logp, targets[:, None], axis=1)[:, 0]


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("block", [128, 256, None])
def test_blockwise_matches_dense(dtype, block):
    T, D, V = 48, 32, 512
    k = jax.random.PRNGKey(0)
    kx, kw, kt = jax.random.split(k, 3)
    x = jax.random.normal(kx, (T, D), dtype)
    w = jax.random.normal(kw, (D, V), dtype) * 0.1
    targets = jax.random.randint(kt, (T,), 0, V, jnp.int32)

    got = blockwise_cross_entropy(x, w, targets, block)
    want = _dense_nll(x, w, targets)
    tol = 1e-5 if dtype == jnp.float32 else 2e-2
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=tol, atol=tol)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_blockwise_grads_match_dense(dtype):
    T, D, V = 32, 16, 256
    k = jax.random.PRNGKey(1)
    kx, kw, kt = jax.random.split(k, 3)
    x = jax.random.normal(kx, (T, D), dtype)
    w = jax.random.normal(kw, (D, V), dtype) * 0.1
    targets = jax.random.randint(kt, (T,), 0, V, jnp.int32)

    def loss_b(x, w):
        return blockwise_cross_entropy(x, w, targets, 64).mean()

    def loss_d(x, w):
        return _dense_nll(x, w, targets).mean()

    gb = jax.grad(loss_b, argnums=(0, 1))(x, w)
    gd = jax.grad(loss_d, argnums=(0, 1))(x, w)
    tol = 1e-5 if dtype == jnp.float32 else 3e-2
    for a, b in zip(gb, gd):
        np.testing.assert_allclose(
            np.asarray(a, np.float32), np.asarray(b, np.float32),
            rtol=tol, atol=tol)


def test_blockwise_under_jit_and_vocab_not_power_of_two():
    T, D, V = 16, 8, 320   # V = 320 -> block picks 128? 320 % 128 != 0
    k = jax.random.PRNGKey(2)
    x = jax.random.normal(k, (T, D), jnp.float32)
    w = jax.random.normal(k, (D, V), jnp.float32) * 0.1
    targets = jnp.zeros((T,), jnp.int32)
    got = jax.jit(lambda x, w, t: blockwise_cross_entropy(x, w, t))(
        x, w, targets)
    want = _dense_nll(x, w, targets)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-5)


def test_blockwise_pads_awkward_vocab():
    """A vocab with no usable divisor (e.g. GPT-2's prime 50257) must be
    padded to big blocks and masked — never hundreds of 1-column scan
    iterations (code-review finding)."""
    from horovod_tpu.ops.losses import _pick_block
    assert _pick_block(32000, None) == 8000      # clean divisor
    assert _pick_block(50257, None) == 1733      # largest usable divisor
    assert _pick_block(1031, None) == 1031       # small vocab: one block
    assert _pick_block(50026, None) == 4096      # 2 x prime -> pad path
    T, D, V = 16, 8, 50026                       # exercises the padding
    k = jax.random.PRNGKey(3)
    x = jax.random.normal(k, (T, D), jnp.float32)
    w = jax.random.normal(k, (D, V), jnp.float32) * 0.1
    targets = jax.random.randint(k, (T,), 0, V, jnp.int32)
    got = blockwise_cross_entropy(x, w, targets)
    want = _dense_nll(x, w, targets)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-5)
    gb = jax.grad(lambda x, w: blockwise_cross_entropy(
        x, w, targets).mean(), argnums=(0, 1))(x, w)
    gd = jax.grad(lambda x, w: _dense_nll(x, w, targets).mean(),
                  argnums=(0, 1))(x, w)
    for a, b in zip(gb, gd):
        assert a.shape == b.shape
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-5, atol=1e-5)


def test_llama_blockwise_ce_trains_on_fsdp_mesh():
    """blockwise_ce composes with dp/fsdp sharding (the documented
    support surface): training losses must match the dense path."""
    import optax
    from jax.sharding import NamedSharding, PartitionSpec as P

    from horovod_tpu.models import llama, steps
    from horovod_tpu.parallel import MeshConfig, build_mesh

    def losses(blockwise):
        mesh = build_mesh(MeshConfig(dp=4, fsdp=2))
        cfg = llama.LlamaConfig.tiny(vocab_size=512,
                                     blockwise_ce=blockwise)
        params = llama.init_params(cfg, jax.random.PRNGKey(0), mesh)
        tx = optax.adam(1e-2)
        opt_state = jax.jit(tx.init)(params)
        step = steps.make_train_step(cfg, mesh, tx)
        tokens = np.random.RandomState(0).randint(0, 512, size=(8, 17))
        batch = jax.device_put(
            {"tokens": jnp.asarray(tokens, jnp.int32)},
            NamedSharding(mesh, P(("dp", "fsdp"))))
        out = []
        for _ in range(3):
            params, opt_state, loss = step(params, opt_state, batch)
            out.append(float(loss))
        return out

    np.testing.assert_allclose(losses(True), losses(False),
                               rtol=1e-4, atol=1e-5)


def test_llama_loss_paths_agree():
    """The flagship loss with blockwise_ce forced on must match the dense
    path (same params/batch)."""
    from horovod_tpu.models import llama
    cfg_d = llama.LlamaConfig.tiny(vocab_size=512, blockwise_ce=False)
    cfg_b = llama.LlamaConfig.tiny(vocab_size=512, blockwise_ce=True)
    params = llama.init_params(cfg_d, jax.random.PRNGKey(0))
    tokens = jnp.asarray(
        np.random.RandomState(0).randint(0, 512, size=(2, 17)), jnp.int32)
    batch = {"tokens": tokens}
    ld = float(llama.loss_fn(params, batch, cfg_d))
    lb = float(llama.loss_fn(params, batch, cfg_b))
    np.testing.assert_allclose(lb, ld, rtol=1e-5, atol=1e-5)
