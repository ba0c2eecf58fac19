"""Seeded weights, made on the device, one layer at a time.

The benchmark makes the weights, not the program: the timed path is
handed the stacked tree, and the reference calls :func:`layer` and
:func:`outer` again from the seed after the program's state is freed, so
nothing the program produced reaches the reference.  Values are uniform
with the variance of the usual 1/sqrt(fan_in) normal init (uniform bits
cost a third of the normal's erf_inv on the chip), rounded to the
served type; norm gains are ones in float32.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np


def root_key(seed: int) -> jax.Array:
    """Seeds run past 2**31; fold the high bits in instead of overflowing."""
    seed = int(seed)
    return jax.random.fold_in(jax.random.PRNGKey(seed & 0x7FFFFFFF),
                              seed >> 31)


def _rnd(key, shape, fan_in, dtype):
    a = float(np.sqrt(3.0 / fan_in))
    return jax.random.uniform(key, shape, jnp.float32, -a, a).astype(dtype)


def layer(key: jax.Array, i, dims: dict, dtype) -> dict:
    """One transformer layer's weights (``i`` may be traced)."""
    D, H, KV, Dh, F = (dims[k] for k in
                       ("d_model", "n_heads", "n_kv_heads", "head_dim",
                        "d_ff"))
    ks = jax.random.split(jax.random.fold_in(key, i), 7)
    return {
        "attn_norm": jnp.ones((D,), jnp.float32),
        "wq": _rnd(ks[0], (D, H, Dh), D, dtype),
        "wk": _rnd(ks[1], (D, KV, Dh), D, dtype),
        "wv": _rnd(ks[2], (D, KV, Dh), D, dtype),
        "wo": _rnd(ks[3], (H, Dh, D), H * Dh, dtype),
        "mlp_norm": jnp.ones((D,), jnp.float32),
        "w_gate": _rnd(ks[4], (D, F), D, dtype),
        "w_up": _rnd(ks[5], (D, F), D, dtype),
        "w_down": _rnd(ks[6], (F, D), F, dtype),
    }


def outer(key: jax.Array, dims: dict, dtype) -> dict:
    """Embedding, final norm and the untied head."""
    D, V = dims["d_model"], dims["vocab_size"]
    ke, kh = jax.random.split(jax.random.fold_in(key, 1 << 20))
    return {
        "embed": _rnd(ke, (V, D), D, dtype),
        "final_norm": jnp.ones((D,), jnp.float32),
        "lm_head": _rnd(kh, (D, V), D, dtype),
    }


def stacked(key: jax.Array, dims: dict, dtype) -> dict:
    """The whole tree in the program's layout: layer leaves stacked on a
    leading depth axis.  ``lax.map`` keeps one layer's float32 draw live
    at a time.  Call under ``jax.jit``."""
    layers = jax.lax.map(lambda i: layer(key, i, dims, dtype),
                         jnp.arange(dims["n_layers"]))
    return {**outer(key, dims, dtype), "layers": layers}
