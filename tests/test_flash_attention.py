"""Pallas flash-attention kernel vs dense oracle (interpret mode on the CPU
rig; the same kernel runs compiled on TPU — see ops/flash_attention.py
docstring for measured speedups)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from horovod_tpu.ops.flash_attention import (
    _dense_attention,
    default_blocks,
    flash_attention,
    supported,
)

INTERP = jax.default_backend() != "tpu"


@pytest.mark.parametrize("causal", [True, False])
def test_flash_matches_dense(causal):
    B, S, H, D = 2, 256, 4, 64
    rng = np.random.RandomState(0)
    q = jnp.asarray(rng.randn(B, S, H, D), jnp.float32)
    k = jnp.asarray(rng.randn(B, S, H, D), jnp.float32)
    v = jnp.asarray(rng.randn(B, S, H, D), jnp.float32)
    out = flash_attention(q, k, v, None, causal, 128, 128, INTERP)
    ref = _dense_attention(q, k, v, 1.0 / np.sqrt(D), causal)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=1e-5, atol=2e-5)


def test_flash_gradients_match_dense():
    B, S, H, D = 1, 128, 2, 32
    rng = np.random.RandomState(1)
    q = jnp.asarray(rng.randn(B, S, H, D), jnp.float32)
    k = jnp.asarray(rng.randn(B, S, H, D), jnp.float32)
    v = jnp.asarray(rng.randn(B, S, H, D), jnp.float32)

    def loss_flash(q, k, v):
        return jnp.sum(flash_attention(q, k, v, None, True, 128, 128,
                                       INTERP) ** 2)

    def loss_dense(q, k, v):
        return jnp.sum(_dense_attention(q, k, v, 1.0 / np.sqrt(D),
                                        True) ** 2)

    gf = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
    gd = jax.grad(loss_dense, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(gf, gd):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=2e-4, atol=2e-4)


@pytest.mark.parametrize("rep,causal", [(2, True), (4, True), (2, False)])
def test_flash_gqa_matches_dense(rep, causal):
    # GQA-native path: k/v carry H/rep heads; the kernel indexes kv
    # groups directly (no jnp.repeat expansion anywhere on the path).
    B, S, H, D = 2, 256, 4, 64
    KV = H // rep
    rng = np.random.RandomState(2)
    q = jnp.asarray(rng.randn(B, S, H, D), jnp.float32)
    k = jnp.asarray(rng.randn(B, S, KV, D), jnp.float32)
    v = jnp.asarray(rng.randn(B, S, KV, D), jnp.float32)
    out = flash_attention(q, k, v, None, causal, 128, 128, INTERP)
    ref = _dense_attention(q, k, v, 1.0 / np.sqrt(D), causal)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=1e-5, atol=2e-5)


def test_flash_gqa_gradients_match_dense():
    # dk/dv come back at kv_heads width: the dkv grid's innermost rep
    # dimension accumulates the group's q heads in fp32 scratch, which
    # must equal the repeat-expand oracle's sum over the group.
    B, S, H, D, KV = 1, 128, 4, 32, 2
    rng = np.random.RandomState(3)
    q = jnp.asarray(rng.randn(B, S, H, D), jnp.float32)
    k = jnp.asarray(rng.randn(B, S, KV, D), jnp.float32)
    v = jnp.asarray(rng.randn(B, S, KV, D), jnp.float32)

    def loss_flash(q, k, v):
        return jnp.sum(flash_attention(q, k, v, None, True, 128, 128,
                                       INTERP) ** 2)

    def loss_dense(q, k, v):
        return jnp.sum(_dense_attention(q, k, v, 1.0 / np.sqrt(D),
                                        True) ** 2)

    gf = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
    gd = jax.grad(loss_dense, argnums=(0, 1, 2))(q, k, v)
    assert gf[1].shape == (B, S, KV, D) and gf[2].shape == (B, S, KV, D)
    for a, b in zip(gf, gd):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=2e-4, atol=2e-4)


def test_flash_rejects_bad_kv_heads():
    q = jnp.zeros((1, 128, 4, 32))
    k = jnp.zeros((1, 128, 3, 32))
    with pytest.raises(ValueError, match="kv heads"):
        flash_attention(q, k, k, None, True, 128, 128, INTERP)


def test_default_blocks_divisibility():
    # Per-length tuning from the round-4 fwd+bwd sweep (see module doc).
    # S=512 follows the committed sweep's fastest point, 256x256 (parity
    # with dense; the parity-is-the-decision rationale is in BASELINE.md).
    assert default_blocks(512) == (256, 256)
    assert default_blocks(1024) == (512, 512)
    assert default_blocks(2048) == (512, 512)
    assert default_blocks(256) == (256, 256)
    assert default_blocks(384) == (128, 128)


def test_supported_gating():
    assert supported((1, 1024, 8, 64))
    assert not supported((1, 100, 8, 64))     # not block-divisible
    assert not supported((1, 4096, 8, 512))   # resident set over budget
    # a 13,312-token prefill at keys and values 256 wide in bf16 (33 MiB
    # resident: the latent-attention cell's longest bucket) is inside it
    assert supported((1, 13312, 20, 256), 2, 256)
