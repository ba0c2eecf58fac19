"""Seeded weights of Xing4.0-29B-A4B (``xing4_0``), made on the device.

GLM-4.7-Flash's leaves (:mod:`chipbench.weights_glm`, drawn the same way
from the same keys) and, in every layer and before the head, the maps of
the four-stream residual in float32: ``phi`` uniform with variance
1/fan_in (fan_in ``n * hidden_size``, so that ``x~ phi`` has unit
spread), ``alpha`` uniform in [0.5, 1.5] and the bias uniform in [-1, 1].
The published model initialises ``alpha`` near zero, which would make
every map a constant and let a wrong ``phi`` through any comparison; a
seeded ``alpha`` of order 1 makes each map depend on the token.  Nothing
is imported from the program.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from . import weights_glm as glm
from .weights import _rnd, root_key  # noqa: F401  (root_key re-exported)
from .weights_glm import is_dense  # noqa: F401  (re-exported)

F32 = jnp.float32
#: where the maps' keys are folded in, apart from the layers' own
_HC_FOLD = 1 << 21


def dims_of(config: dict) -> dict:
    """Sizes from a configuration file's published keys: GLM-4.7-Flash's,
    the streams and YaRN's."""
    m, rs = config, config["rope_scaling"]
    return {**glm.dims_of(config),
            "hc_mult": m["hc_mult"], "hc_iters": m["hc_sinkhorn_iters"],
            "hc_eps": float(m["hc_eps"]),
            "hc_clamp": (float(m["mhc_h_res_clamp_min"]),
                         float(m["mhc_h_res_clamp_max"])),
            "yarn_factor": float(rs["factor"]),
            "yarn_original": int(rs["original_max_position_embeddings"]),
            "yarn_beta_fast": float(rs["beta_fast"]),
            "yarn_beta_slow": float(rs["beta_slow"]),
            "yarn_mscale": float(rs["mscale"]),
            "yarn_mscale_all_dim": float(rs["mscale_all_dim"])}


def hc_shapes(dims: dict) -> dict:
    """A layer's map leaves: ``name -> shape``, for the attention's and
    the MLP's sub-layer."""
    n, D = dims["hc_mult"], dims["d_model"]
    m = n * n + 2 * n
    return {f"hc_{f}_{leaf}": shape for f in ("attn", "mlp")
            for leaf, shape in (("phi", (n * D, m)), ("bias", (m,)),
                                ("alpha", (3,)))}


def head_shapes(dims: dict) -> dict:
    n = dims["hc_mult"]
    return {"hc_head_phi": (n * dims["d_model"], n), "hc_head_bias": (n,),
            "hc_head_alpha": (1,)}


def _maps(key, shapes: dict) -> dict:
    ks = jax.random.split(key, len(shapes))
    out = {}
    for k, (name, shape) in zip(ks, sorted(shapes.items())):
        if name.endswith("_phi"):
            out[name] = _rnd(k, shape, shape[0], F32)
        elif name.endswith("_alpha"):
            out[name] = jax.random.uniform(k, shape, F32, 0.5, 1.5)
        else:
            out[name] = jax.random.uniform(k, shape, F32, -1.0, 1.0)
    return out


def layer_shapes(dims: dict, dense: bool) -> dict:
    """One layer's leaves: ``name -> (shape, fan_in)``, fan_in None for
    the float32 ones."""
    return {**glm.layer_shapes(dims, dense),
            **{k: (s, None) for k, s in hc_shapes(dims).items()}}


def layer(key: jax.Array, i, dims: dict, dtype, dense: bool) -> dict:
    """Layer ``i``'s weights (``i`` may be traced)."""
    return {**glm.layer(key, i, dims, dtype, dense),
            **_maps(jax.random.fold_in(key, _HC_FOLD + i), hc_shapes(dims))}


def outer(key: jax.Array, dims: dict, dtype) -> dict:
    """Embedding, final norm, the untied head and the read-out's maps."""
    return {**glm.outer(key, dims, dtype),
            **_maps(jax.random.fold_in(key, _HC_FOLD - 1), head_shapes(dims))}


def stacked(key: jax.Array, dims: dict, dtype) -> dict:
    """The tree the program serves (as :func:`chipbench.weights_glm
    .stacked`'s, the maps beside each layer's leaves and the head's).
    Call under ``jax.jit``."""
    k, L = dims["first_k_dense"], dims["n_layers"]
    runs = [jax.lax.map(lambda i: layer(key, i, dims, dtype, dense),
                        jnp.arange(lo, hi))
            for lo, hi, dense in ((0, min(k, L), True), (min(k, L), L, False))
            if hi > lo]
    return {**outer(key, dims, dtype), "runs": runs}


def parameter_count(dims: dict) -> dict:
    """Parameters by part, as :func:`chipbench.weights_glm
    .parameter_count`'s with the maps in: ``hc_layer`` (one layer's maps),
    ``hc_head`` (the read-out's), ``dense_layer`` and ``expert_layer``
    with their maps, ``embed_and_head``, ``held``."""
    size = lambda shapes: sum(int(jnp.prod(jnp.asarray(s)))
                              for s in shapes.values())
    base = glm.parameter_count(dims)
    hc, head = size(hc_shapes(dims)), size(head_shapes(dims))
    k, L = min(dims["first_k_dense"], dims["n_layers"]), dims["n_layers"]
    return {**base, "hc_layer": hc, "hc_head": head,
            "dense_layer": base["dense_layer"] + hc,
            "expert_layer": base["expert_layer"] + hc,
            "held": base["held"] + L * hc + head}
