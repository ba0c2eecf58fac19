"""Seeded weights of Falcon-H1 (``falcon_h1``), made on the device.

As :mod:`chipbench.weights`: the benchmark makes the weights, the timed
path is handed the tree in the program's layout (:func:`stacked`) and the
reference calls :func:`layer` and :func:`outer` again from the seed, one
layer at a time.  Nothing is imported from the program, so the leaf
shapes are worked out here from the configuration file's own keys.

Matrices are uniform with the variance of the usual 1/sqrt(fan_in)
normal init, rounded to the served type; the convolution's kernel and
bias are drawn the same way with the kernel's width as their fan-in.
Mamba-2's own initialisation for the rest, in float32: ``exp(A_log)``
uniform in [1, 16], ``dt_bias`` the softplus inverse of a ``dt`` whose log
is uniform in [log 1e-3, log 1e-1], ``D`` 1; every gain 1.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from .weights import _rnd, root_key  # noqa: F401  (root_key re-exported)

F32 = jnp.float32


def dims_of(config: dict) -> dict:
    """Sizes and multipliers from a configuration file's published keys."""
    m = config
    return {
        "d_model": m["hidden_size"], "n_layers": m["num_hidden_layers"],
        "n_heads": m["num_attention_heads"],
        "n_kv_heads": m["num_key_value_heads"], "head_dim": m["head_dim"],
        "d_ff": m["intermediate_size"], "vocab_size": m["vocab_size"],
        "ssm_heads": m["mamba_n_heads"], "ssm_head_dim": m["mamba_d_head"],
        "d_ssm": m["mamba_d_ssm"], "ssm_groups": m["mamba_n_groups"],
        "ssm_state": m["mamba_d_state"], "conv_kernel": m["mamba_d_conv"],
        "rope_theta": float(m["rope_theta"]),
        "rms_norm_eps": float(m["rms_norm_eps"]),
        "embedding_multiplier": float(m["embedding_multiplier"]),
        "lm_head_multiplier": float(m["lm_head_multiplier"]),
        "key_multiplier": float(m["key_multiplier"]),
        "attention_in_multiplier": float(m["attention_in_multiplier"]),
        "attention_out_multiplier": float(m["attention_out_multiplier"]),
        "ssm_in_multiplier": float(m["ssm_in_multiplier"]),
        "ssm_out_multiplier": float(m["ssm_out_multiplier"]),
        "ssm_multipliers": tuple(float(x) for x in m["ssm_multipliers"]),
        "mlp_multipliers": tuple(float(x) for x in m["mlp_multipliers"]),
    }


def conv_dim(d: dict) -> int:
    """Channels of the convolution: x, B and C."""
    return d["d_ssm"] + 2 * d["ssm_groups"] * d["ssm_state"]


def in_dim(d: dict) -> int:
    """Width of W_in's output: z, x, B, C, dt."""
    return d["d_ssm"] + conv_dim(d) + d["ssm_heads"]


def layer_shapes(d: dict) -> dict:
    """One layer's leaves: ``name -> (shape, fan_in)``; fan_in None marks
    a float32 leaf with an initialisation of its own."""
    D, H, KV, Dh, F = (d[k] for k in ("d_model", "n_heads", "n_kv_heads",
                                      "head_dim", "d_ff"))
    Hs, k = d["ssm_heads"], d["conv_kernel"]
    return {
        "attn_norm": ((D,), None), "mlp_norm": ((D,), None),
        "wq": ((D, H, Dh), D), "wk": ((D, KV, Dh), D),
        "wv": ((D, KV, Dh), D), "wo": ((H, Dh, D), H * Dh),
        "ssm_in": ((D, in_dim(d)), D),
        "conv_w": ((k, conv_dim(d)), k), "conv_b": ((conv_dim(d),), k),
        "A_log": ((Hs,), None), "dt_bias": ((Hs,), None),
        "ssm_D": ((Hs,), None), "ssm_norm": ((d["d_ssm"],), None),
        "ssm_out": ((d["d_ssm"], D), d["d_ssm"]),
        "w_gate": ((D, F), D), "w_up": ((D, F), D), "w_down": ((F, D), F)}


def _own(name: str, shape: tuple, key):
    if name == "A_log":
        return jnp.log(jax.random.uniform(key, shape, F32, 1.0, 16.0))
    if name == "dt_bias":
        dt = jnp.exp(jax.random.uniform(key, shape, F32, np.log(1e-3),
                                        np.log(1e-1)))
        return dt + jnp.log(-jnp.expm1(-dt))      # softplus(dt_bias) = dt
    return jnp.ones(shape, F32)                   # D and the gains


def layer(key: jax.Array, i, dims: dict, dtype) -> dict:
    """Layer ``i``'s weights (``i`` may be traced)."""
    shapes = layer_shapes(dims)
    ks = jax.random.split(jax.random.fold_in(key, i), len(shapes))
    return {name: (_own(name, shape, k) if fan is None
                   else _rnd(k, shape, fan, dtype))
            for k, (name, (shape, fan)) in zip(ks, sorted(shapes.items()))}


def outer(key: jax.Array, dims: dict, dtype) -> dict:
    """Embedding, final norm and the untied head."""
    D, V = dims["d_model"], dims["vocab_size"]
    ke, kh = jax.random.split(jax.random.fold_in(key, 1 << 20))
    return {"embed": _rnd(ke, (V, D), D, dtype),
            "final_norm": jnp.ones((D,), F32),
            "lm_head": _rnd(kh, (D, V), D, dtype)}


def stacked(key: jax.Array, dims: dict, dtype) -> dict:
    """The tree the program serves: the layers' leaves stacked on a
    leading depth axis.  ``lax.map`` keeps one layer's float32 draw live
    at a time.  Call under ``jax.jit``."""
    layers = jax.lax.map(lambda i: layer(key, i, dims, dtype),
                         jnp.arange(dims["n_layers"]))
    return {**outer(key, dims, dtype), "layers": layers}


def parameter_count(dims: dict) -> dict:
    """Parameters by part, gains too: one layer's ``attention``, ``ssm``
    (the Mamba-2 mixer), ``mlp`` and ``norms``, the whole ``layer``,
    ``embed_and_head``, and ``held``, all of the configuration as cut."""
    shapes = layer_shapes(dims)
    size = lambda names: sum(int(np.prod(shapes[n][0])) for n in names)
    attn = ("wq", "wk", "wv", "wo")
    mlp = ("w_gate", "w_up", "w_down")
    norms = ("attn_norm", "mlp_norm")
    ssm = tuple(n for n in shapes if n not in attn + mlp + norms)
    head = 2 * dims["d_model"] * dims["vocab_size"]
    one = size(shapes)
    return {"attention": size(attn), "ssm": size(ssm), "mlp": size(mlp),
            "norms": size(norms), "layer": one, "embed_and_head": head,
            "held": dims["n_layers"] * one + head + dims["d_model"]}
