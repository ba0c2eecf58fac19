"""The work a looped decoder needs, from shapes and lengths alone.

Every pass counts: the stack's weights are held once and used ``loops``
times a token, and a token leaves keys and values in ``loops x n_layers``
cache layers, a pass's queries reading that pass's own.  Bytes are the
least a step has to move: a weight read once for every pass that uses it
(a pass is 4.9 GB of them, which no on-chip memory holds from one pass to
the next), the head once, the live pages of every cache layer once.
"""

from __future__ import annotations

from . import costs
from .costs import (  # noqa: F401  (re-exported: one cache layer's call)
    least_seconds,
    paged_decode_bytes,
)


def cache_layers(d: dict) -> int:
    return d["loops"] * d["n_layers"]


def stack_weight_count(d: dict) -> int:
    """Matmul weights of the layers, held once (gains left out)."""
    return d["n_layers"] * costs.layer_weight_count(d)


def head_weight_count(d: dict) -> int:
    return d["d_model"] * d["vocab_size"]


def kv_bytes_per_token(d: dict, itemsize: int = 2) -> int:
    """Bytes of K and V one token holds, all cache layers."""
    return 2 * cache_layers(d) * d["n_kv_heads"] * d["head_dim"] * itemsize


def forward_flops(d: dict, tokens: int, attended: int, head_tokens: int
                  ) -> float:
    """A forward pass over ``tokens`` positions that between them attend
    to ``attended`` (query, key) pairs in each cache layer, the head on
    ``head_tokens``: every matrix of the stack ``loops`` times, QK^T and
    PV in every cache layer.  The embedding is a lookup; norms and the
    exit gate (2 x hidden FLOPs a pass) are not matrix work."""
    per_pair = 4 * d["n_heads"] * d["head_dim"]
    return (2.0 * d["loops"] * stack_weight_count(d) * tokens
            + cache_layers(d) * per_pair * attended
            + 2.0 * head_weight_count(d) * head_tokens)


def matmul_flops_per_token(d: dict) -> float:
    """A token's FLOPs in the matrices alone (no attention)."""
    return forward_flops(d, 1, 0, 1)


def prefill_cost(d: dict, prompt: int, itemsize: int = 2) -> tuple:
    """(FLOPs, bytes) of one prompt's prefill: causal attention in every
    cache layer, the head on the last position; the layers read once a
    pass, the head once, K and V of every position written."""
    flops = forward_flops(d, prompt, prompt * (prompt + 1) // 2, 1)
    bytes_ = itemsize * (d["loops"] * stack_weight_count(d)
                         + head_weight_count(d)) \
        + prompt * kv_bytes_per_token(d, itemsize)
    return flops, float(bytes_)


def decode_tick_cost(d: dict, lengths, block_size: int, itemsize: int = 2
                     ) -> tuple:
    """(FLOPs, bytes) of one decode tick over streams at ``lengths``: the
    layers read once a pass, the head once, every stream's pages read in
    every cache layer."""
    n = len(lengths)
    flops = forward_flops(d, n, sum(int(x) for x in lengths), n)
    bytes_ = itemsize * (d["loops"] * stack_weight_count(d)
                         + head_weight_count(d)) \
        + cache_layers(d) * paged_decode_bytes(d, lengths, block_size,
                                               itemsize)
    return flops, float(bytes_)
