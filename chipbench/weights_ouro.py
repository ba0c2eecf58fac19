"""Seeded weights of a looped decoder with sandwich norms (Ouro).

The leaves of :mod:`chipbench.weights` plus what this family adds: a
layer's two post-norm gains, and beside the embedding, the final norm and
the head the exit gate, a ``hidden -> 1`` linear layer with bias.  As
there, matrices are uniform with variance 1/fan_in and rounded to the
served type, gains are ones, the gate's bias is 0; the reference calls
:func:`layer` and :func:`outer` again from the seed.  The program is
handed :func:`stacked`, which leaves the gate out: at the published exit
threshold no token leaves early and the served program does not read it.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from . import weights
from .reference import dims_of as reference_dims_of
from .weights import root_key  # noqa: F401  (re-exported)


def dims_of(config: dict) -> dict:
    """Sizes from a configuration file's published keys."""
    return {**reference_dims_of(config), "loops": config["total_ut_steps"],
            "exit_threshold": float(config["early_exit_threshold"])}


def layer(key: jax.Array, i, dims: dict, dtype) -> dict:
    """One layer's weights (``i`` may be traced): the plain decoder's,
    and the gains of the norms after attention and after the MLP."""
    ones = jnp.ones((dims["d_model"],), jnp.float32)
    return {**weights.layer(key, i, dims, dtype),
            "attn_post_norm": ones, "mlp_post_norm": ones}


def outer(key: jax.Array, dims: dict, dtype) -> dict:
    """Embedding, final norm, the untied head, and the exit gate."""
    D = dims["d_model"]
    kg = jax.random.fold_in(key, (1 << 20) + 1)
    return {**weights.outer(key, dims, dtype),
            "exit_gate_w": weights._rnd(kg, (D,), D, dtype),
            "exit_gate_b": jnp.zeros((), jnp.float32)}


def stacked(key: jax.Array, dims: dict, dtype) -> dict:
    """The tree the program serves: layer leaves stacked on a leading
    depth axis (used ``loops`` times, held once), no exit gate.  Call
    under ``jax.jit``."""
    layers = jax.lax.map(lambda i: layer(key, i, dims, dtype),
                         jnp.arange(dims["n_layers"]))
    out = outer(key, dims, dtype)
    return {"embed": out["embed"], "final_norm": out["final_norm"],
            "lm_head": out["lm_head"], "layers": layers}


def parameter_count(dims: dict) -> int:
    """Every parameter of the published model, gains and gate too."""
    shapes = jax.eval_shape(
        lambda k: (layer(k, 0, dims, jnp.bfloat16),
                   outer(k, dims, jnp.bfloat16)), jax.random.PRNGKey(0))
    per_layer, out = (sum(x.size for x in jax.tree.leaves(t))
                      for t in shapes)
    return dims["n_layers"] * per_layer + out
