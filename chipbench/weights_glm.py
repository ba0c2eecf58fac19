"""Seeded weights of GLM-4.7-Flash (``glm4_moe_lite``), made on the device.

As :mod:`chipbench.weights`: the benchmark makes the weights, the timed
path is handed the tree in the program's layout (:func:`stacked`) and the
reference calls :func:`layer` and :func:`outer` again from the seed, one
layer at a time.  Nothing is imported from the program, so the layer
pattern and the leaf shapes are worked out here from the configuration
file's own keys.

Matrices (the router too) are uniform with the variance of the usual
1/sqrt(fan_in) normal init, rounded to the served type (the router stays
float32); every gain is 1; ``e_score_correction_bias`` is 0.  The
multi-token prediction block is not made: the served configuration does
not load it.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from .weights import _rnd, root_key  # noqa: F401  (root_key re-exported)

F32 = jnp.float32


def dims_of(config: dict) -> dict:
    """Sizes from a configuration file's published keys."""
    m = config
    return {
        "d_model": m["hidden_size"], "n_layers": m["num_hidden_layers"],
        "first_k_dense": m["first_k_dense_replace"],
        "n_heads": m["num_attention_heads"],
        "q_lora_rank": m["q_lora_rank"], "kv_lora_rank": m["kv_lora_rank"],
        "qk_nope": m["qk_nope_head_dim"], "qk_rope": m["qk_rope_head_dim"],
        "v_dim": m["v_head_dim"], "d_ff": m["intermediate_size"],
        "moe_d_ff": m["moe_intermediate_size"],
        "n_experts": m["n_routed_experts"],
        "n_shared": m["n_shared_experts"],
        "experts_per_token": m["num_experts_per_tok"],
        "routed_scale": float(m["routed_scaling_factor"]),
        "renormalize": bool(m["norm_topk_prob"]),
        "vocab_size": m["vocab_size"],
        "rope_theta": float(m["rope_theta"]),
        "rms_norm_eps": float(m["rms_norm_eps"]),
    }


def is_dense(dims: dict, i: int) -> bool:
    """Whether the 0-based layer ``i`` has the dense MLP."""
    return i < dims["first_k_dense"]


def layer_shapes(dims: dict, dense: bool) -> dict:
    """One layer's leaves: ``name -> (shape, fan_in)``; fan_in None marks
    a float32 leaf that is not drawn (gains 1, the selection bias 0);
    the router is drawn and stays float32."""
    D, H, C, Q = (dims[k] for k in ("d_model", "n_heads", "kv_lora_rank",
                                    "q_lora_rank"))
    qk = dims["qk_nope"] + dims["qk_rope"]
    out = {
        "attn_norm": ((D,), None), "mlp_norm": ((D,), None),
        "w_qa": ((D, Q), D), "q_norm": ((Q,), None),
        "w_qb": ((Q, H, qk), Q),
        "w_kva": ((D, C + dims["qk_rope"]), D), "kv_norm": ((C,), None),
        "w_kvb": ((C, H, dims["qk_nope"] + dims["v_dim"]), C),
        "wo": ((H, dims["v_dim"], D), H * dims["v_dim"])}
    if dense:
        F = dims["d_ff"]
        out.update({"w_gate": ((D, F), D), "w_up": ((D, F), D),
                    "w_down": ((F, D), F)})
    else:
        E, F = dims["n_experts"], dims["moe_d_ff"]
        Fs = F * dims["n_shared"]
        out.update({
            "router": ((D, E), D), "router_bias": ((E,), None),
            "e_gate": ((E, D, F), D), "e_up": ((E, D, F), D),
            "e_down": ((E, F, D), F),
            "s_gate": ((D, Fs), D), "s_up": ((D, Fs), D),
            "s_down": ((Fs, D), Fs)})
    return out


def layer(key: jax.Array, i, dims: dict, dtype, dense: bool) -> dict:
    """Layer ``i``'s weights (``i`` may be traced; ``dense`` is its kind,
    :func:`is_dense` of it)."""
    shapes = layer_shapes(dims, dense)
    ks = jax.random.split(jax.random.fold_in(key, i), len(shapes))
    out = {}
    for k, (name, (shape, fan)) in zip(ks, sorted(shapes.items())):
        if fan is None:
            out[name] = jnp.zeros(shape, F32) if name == "router_bias" \
                else jnp.ones(shape, F32)
        else:
            out[name] = _rnd(k, shape, fan,
                             F32 if name == "router" else dtype)
    return out


def outer(key: jax.Array, dims: dict, dtype) -> dict:
    """Embedding, final norm and the untied head."""
    D, V = dims["d_model"], dims["vocab_size"]
    ke, kh = jax.random.split(jax.random.fold_in(key, 1 << 20))
    return {"embed": _rnd(ke, (V, D), D, dtype),
            "final_norm": jnp.ones((D,), F32),
            "lm_head": _rnd(kh, (D, V), D, dtype)}


def stacked(key: jax.Array, dims: dict, dtype) -> dict:
    """The tree the program serves: ``runs`` holds the dense layers and
    then the expert layers, each run's leaves stacked on a leading depth
    axis.  ``lax.map`` keeps one layer's float32 draw live at a time.
    Call under ``jax.jit``."""
    k, L = dims["first_k_dense"], dims["n_layers"]
    runs = [jax.lax.map(lambda i: layer(key, i, dims, dtype, dense),
                        jnp.arange(lo, hi))
            for lo, hi, dense in ((0, min(k, L), True), (min(k, L), L, False))
            if hi > lo]
    return {**outer(key, dims, dtype), "runs": runs}


def parameter_count(dims: dict) -> dict:
    """Parameters by part, gains and the selection bias too:
    ``attention`` (one layer's), ``dense_layer``, ``expert_layer``,
    ``experts`` (the routed experts of one layer), ``embed_and_head``,
    and ``held``, all of the configuration as cut."""
    size = lambda shapes, names=None: sum(
        int(jnp.prod(jnp.asarray(s))) for n, (s, _) in shapes.items()
        if names is None or n in names)
    dense, moe = layer_shapes(dims, True), layer_shapes(dims, False)
    attn = ("w_qa", "q_norm", "w_qb", "w_kva", "kv_norm", "w_kvb", "wo")
    k, L = min(dims["first_k_dense"], dims["n_layers"]), dims["n_layers"]
    head = 2 * dims["d_model"] * dims["vocab_size"]
    return {
        "attention": size(dense, attn), "dense_layer": size(dense),
        "expert_layer": size(moe),
        "experts": size(moe, ("e_gate", "e_up", "e_down")),
        "embed_and_head": head,
        "held": k * size(dense) + (L - k) * size(moe) + head
        + dims["d_model"]}
