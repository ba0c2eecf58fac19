"""Seeded weights of the Kimi-Linear configuration, made on the device.

As :mod:`chipbench.weights`: the benchmark makes the weights, the timed
path is handed the tree in the program's layout (:func:`stacked`) and the
reference calls :func:`layer` and :func:`outer` again from the seed.
Nothing is imported from the program, so the layer pattern and the leaf
shapes are worked out here from the configuration file's own keys.

Matrices are uniform with the variance of the usual 1/sqrt(fan_in) normal
init, rounded to the trained type; a layer is one draw, cut into its
leaves.  What the published ``config.json``
does not pin (the configuration file's ``assumed``): ``A_log`` is the log
of uniform(1, 16), ``dt_bias`` the inverse softplus of a log-uniform step
in [1e-3, 1e-1], both as the family's code draws them; the router is
float32; its selection bias is uniform in +-0.03 (the program and the
reference add the per-sequence balancing shift to it); norm gains are
ones.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from .weights import root_key  # noqa: F401  (re-exported)

F32 = jnp.float32


def dims_of(config: dict) -> dict:
    """Sizes from the configuration file: the published keys as cut
    (``num_experts`` counts the experts held, ``vocab_size`` the rows
    held), the router's width from ``published``."""
    m, la = config, config["linear_attn_config"]
    return {
        "d_model": m["hidden_size"], "n_layers": m["num_hidden_layers"],
        "kda_layers": tuple(la["kda_layers"]),
        "full_attn_layers": tuple(la["full_attn_layers"]),
        "first_k_dense": m["first_k_dense_replace"],
        "kda_heads": la["num_heads"], "kda_head_dim": la["head_dim"],
        "conv_kernel": la["short_conv_kernel_size"],
        "gate_rank": m["assumed_values"]["gate_low_rank"],
        "l2_eps": m["assumed_values"]["l2_norm_eps"],
        "n_heads": m["num_attention_heads"],
        "kv_lora_rank": m["kv_lora_rank"], "qk_nope": m["qk_nope_head_dim"],
        "qk_rope": m["qk_rope_head_dim"], "v_dim": m["v_head_dim"],
        "d_ff": m["intermediate_size"], "moe_d_ff": m["moe_intermediate_size"],
        "n_experts": m["published"]["num_experts"],
        "experts_held": m["num_experts"],
        "held_first": m["first_expert_held"],
        "experts_per_token": m["num_experts_per_token"],
        "routed_scale": m["routed_scaling_factor"],
        "renormalize": m["moe_renormalize"],
        "vocab_size": m["vocab_size"], "rms_norm_eps": float(m["rms_norm_eps"]),
    }


def kind_of(dims: dict, i: int) -> str:
    """Kind of the 0-based layer ``i``: mixer ``kda`` or ``mla``, MLP
    ``dense`` or ``moe``."""
    mixer = "kda" if i + 1 in dims["kda_layers"] else "mla"
    assert (i + 1 in dims["full_attn_layers"]) == (mixer == "mla"), i
    return mixer + ("_dense" if i < dims["first_k_dense"] else "_moe")


def runs_of(dims: dict) -> list:
    """[(kind, first 0-based layer, count)] of consecutive layers of one
    kind: the program's ``params["runs"]``."""
    out: list = []
    for i in range(dims["n_layers"]):
        kind = kind_of(dims, i)
        if out and out[-1][0] == kind:
            out[-1][2] += 1
        else:
            out.append([kind, i, 1])
    return [tuple(r) for r in out]


def _leaves(dims: dict, kind: str) -> list:
    """[(name, shape, what)] of one layer of ``kind``: ``what`` is the
    fan-in of a matrix (drawn uniform with variance 1 / fan_in, in the
    trained type), or ``"one"``, ``"A_log"``, ``"dt_bias"``, ``"router"``,
    ``"bias"`` for the float32 leaves with a draw of their own."""
    d = dims
    D, H, K, R = d["d_model"], d["kda_heads"], d["kda_head_dim"], d["gate_rank"]
    mixer, mlp = kind.split("_")
    out = [("attn_norm", (D,), "one"), ("mlp_norm", (D,), "one")]
    if mixer == "kda":
        cv = d["conv_kernel"]
        out += [("wq", (D, H, K), D), ("wk", (D, H, K), D),
                ("wv", (D, H, K), D), ("conv_q", (cv, H, K), cv),
                ("conv_k", (cv, H, K), cv), ("conv_v", (cv, H, K), cv),
                ("w_fa", (D, R), D), ("w_fb", (R, H, K), R),
                ("w_ga", (D, R), D), ("w_gb", (R, H, K), R),
                ("w_beta", (D, H), D), ("wo", (H, K, D), H * K),
                ("o_norm", (K,), "one"), ("A_log", (H,), "A_log"),
                ("dt_bias", (H, K), "dt_bias")]
    else:
        Hm, C = d["n_heads"], d["kv_lora_rank"]
        out += [("wq", (D, Hm, d["qk_nope"] + d["qk_rope"]), D),
                ("w_kva", (D, C + d["qk_rope"]), D),
                ("kv_norm", (C,), "one"),
                ("w_kvb", (C, Hm, d["qk_nope"] + d["v_dim"]), C),
                ("wo", (Hm, d["v_dim"], D), Hm * d["v_dim"])]
    if mlp == "dense":
        F = d["d_ff"]
        out += [("w_gate", (D, F), D), ("w_up", (D, F), D),
                ("w_down", (F, D), F)]
    else:
        E, Eh, F = d["n_experts"], d["experts_held"], d["moe_d_ff"]
        out += [("router", (D, E), "router"), ("router_bias", (E,), "bias"),
                ("e_gate", (Eh, D, F), D), ("e_up", (Eh, D, F), D),
                ("e_down", (Eh, F, D), F), ("s_gate", (D, F), D),
                ("s_up", (D, F), D), ("s_down", (F, D), F)]
    return out


def _carve(u, leaves: list, dtype) -> dict:
    """Cut one draw ``u`` (uniform in [-1, 1)) into the leaves, in order.
    One draw a layer, not one a leaf: the generator's program is most of
    what a CPU test of the cell compiles."""
    w, at = {}, 0
    for name, shape, what in leaves:
        if what == "one":
            w[name] = jnp.ones(shape, F32)
            continue
        n = int(np.prod(shape))
        x = u[at:at + n].reshape(shape)
        at += n
        if what == "A_log":            # log of uniform(1, 16)
            w[name] = jnp.log(8.5 + 7.5 * x)
        elif what == "dt_bias":        # inverse softplus of a log-uniform step
            dt = jnp.exp(np.log(1e-2) + np.log(10.0) * x)
            w[name] = dt + jnp.log(-jnp.expm1(-dt))
        elif what == "bias":
            w[name] = 0.03 * x
        elif what == "router":
            w[name] = float(np.sqrt(3.0 / shape[0])) * x
        else:
            w[name] = (float(np.sqrt(3.0 / what)) * x).astype(dtype)
    return w


def _drawn(leaves: list) -> int:
    return sum(int(np.prod(shape)) for _, shape, what in leaves
               if what != "one")


def layer(key: jax.Array, i: int, dims: dict, dtype) -> dict:
    """The weights of the 0-based layer ``i`` (a Python int: the kinds
    have different leaves)."""
    leaves = _leaves(dims, kind_of(dims, i))
    u = jax.random.uniform(jax.random.fold_in(key, i), (_drawn(leaves),),
                           F32, -1.0, 1.0)
    return _carve(u, leaves, dtype)


def outer(key: jax.Array, dims: dict, dtype) -> dict:
    """Embedding, final norm and the untied head, over the rows held."""
    D, V = dims["d_model"], dims["vocab_size"]
    leaves = [("embed", (V, D), D), ("final_norm", (D,), "one"),
              ("lm_head", (D, V), D)]
    u = jax.random.uniform(jax.random.fold_in(key, 1 << 20),
                           (_drawn(leaves),), F32, -1.0, 1.0)
    return _carve(u, leaves, dtype)


def stacked(key: jax.Array, dims: dict, dtype) -> dict:
    """The whole tree in the program's layout: one stacked tree per run
    of layers of one kind.  Call under ``jax.jit``."""
    runs = []
    for _, first, n in runs_of(dims):
        ws = [layer(key, first + j, dims, dtype) for j in range(n)]
        runs.append({k: jnp.stack([w[k] for w in ws]) for k in ws[0]})
    return {**outer(key, dims, dtype), "runs": runs}
