"""The work GLM-4.7-Flash needs, from shapes and lengths alone.

A token multiplies the attention matrices, and in the dense layer the
SwiGLU, in an expert layer the router, its ``experts_per_token`` routed
experts and the shared one: 4 of 64 experts' FLOPs, whatever a tick's
tiles pad to.  Attention is counted in the form each step can run at
least: a prompt in the expanded form (keys ``nope + rope`` wide, values
``v_dim``), a decode tick in the absorbed form (the query against a
token's cached row: scores over ``kv_lora_rank + rope`` columns, values
over ``kv_lora_rank``), which makes more FLOPs a pair but expands no
cached key.

Bytes are the least a step has to move.  The cache is counted at the
**published** ``kv_lora_rank + qk_rope_head_dim`` = 576 values a token
and layer whatever the pool pads a row to, so a roofline share cannot
pass 100% and the padding shows as lost share.  A decode tick reads the
attention matrices, the dense layer, each expert layer's router and
shared expert, the head, the embedding rows of its tokens, and of the
routed experts those it touched.
"""

from __future__ import annotations

from . import weights_glm as weights
from .costs import least_seconds  # noqa: F401  (re-exported)


def counts(d: dict) -> dict:
    return weights.parameter_count(d)


def n_moe(d: dict) -> int:
    return d["n_layers"] - min(d["first_k_dense"], d["n_layers"])


def cache_values(d: dict) -> int:
    """Values one token leaves in one cache layer, as published."""
    return d["kv_lora_rank"] + d["qk_rope"]


def cache_bytes_per_token(d: dict, itemsize: int = 2) -> int:
    """Bytes one token holds over all layers, as published (8,064 for
    the seven-layer cut in bf16)."""
    return d["n_layers"] * cache_values(d) * itemsize


def attention_weights(d: dict) -> int:
    """Matmul weights of one layer's attention (gains left out)."""
    D, H, C, Q = (d[k] for k in ("d_model", "n_heads", "kv_lora_rank",
                                 "q_lora_rank"))
    qk = d["qk_nope"] + d["qk_rope"]
    return (D * Q + Q * H * qk + D * (C + d["qk_rope"])
            + C * H * (d["qk_nope"] + d["v_dim"]) + H * d["v_dim"] * D)


def expert_weights(d: dict) -> int:
    """One routed expert's three matrices (18.87 MB in bf16)."""
    return 3 * d["d_model"] * d["moe_d_ff"]


def token_weights(d: dict) -> int:
    """Matmul weights one token multiplies in the layers: attention
    everywhere, the dense SwiGLU, and in each expert layer the router,
    its picked experts and the shared ones."""
    dense = d["n_layers"] - n_moe(d)
    moe = d["d_model"] * d["n_experts"] + expert_weights(d) * (
        d["experts_per_token"] + d["n_shared"])
    return (d["n_layers"] * attention_weights(d)
            + dense * 3 * d["d_model"] * d["d_ff"] + n_moe(d) * moe)


def head_weights(d: dict) -> int:
    return d["d_model"] * d["vocab_size"]


def pair_flops(d: dict, absorbed: bool) -> int:
    """FLOPs of one (query, key) pair in one layer, all heads: scores and
    values, in the expanded or in the absorbed form."""
    if absorbed:
        return 2 * d["n_heads"] * (cache_values(d) + d["kv_lora_rank"])
    return 2 * d["n_heads"] * (d["qk_nope"] + d["qk_rope"] + d["v_dim"])


def prefill_cost(d: dict, prompt: int, itemsize: int = 2) -> tuple:
    """(FLOPs, bytes) of one prompt's prefill: causal attention in the
    expanded form, the head on the last position; every matrix of the
    configuration read once (a prompt of thousands touches every expert),
    the embedding rows of the prompt, its cache rows written."""
    flops = (2.0 * token_weights(d) * prompt
             + d["n_layers"] * pair_flops(d, False)
             * (prompt * (prompt + 1) // 2)
             + 2.0 * head_weights(d))
    held = counts(d)["held"] - head_weights(d)        # all but the embedding
    bytes_ = itemsize * (held + prompt * d["d_model"]) \
        + prompt * cache_bytes_per_token(d, itemsize)
    return flops, float(bytes_)


def mla_decode_cost(d: dict, lengths, itemsize: int = 2) -> tuple:
    """(FLOPs, bytes) of ONE layer's latent decode call over streams at
    ``lengths``: every live token's published row read once, scored and
    summed by every head."""
    live = sum(int(n) for n in lengths)
    return (float(pair_flops(d, True)) * live,
            float(itemsize) * cache_values(d) * live)


def decode_tick_cost(d: dict, lengths, experts_touched=None,
                     itemsize: int = 2) -> tuple:
    """(FLOPs, bytes) of one decode tick over streams at ``lengths``.
    ``experts_touched``: (layer, expert) pairs the tick sent any token
    to, summed over the expert layers (None: the most it can touch,
    ``min(streams * k, experts)`` a layer)."""
    n = len(lengths)
    if experts_touched is None:
        experts_touched = n_moe(d) * min(n * d["experts_per_token"],
                                         d["n_experts"])
    kf, kb = mla_decode_cost(d, lengths, itemsize)
    flops = 2.0 * (token_weights(d) + head_weights(d)) * n \
        + d["n_layers"] * kf
    fixed = counts(d)["held"] - head_weights(d) \
        - n_moe(d) * counts(d)["experts"]
    bytes_ = itemsize * (fixed + n * d["d_model"]
                         + experts_touched * expert_weights(d)) \
        + d["n_layers"] * kb
    return flops, float(bytes_)
