"""The work Xing4.0-29B-A4B needs, from shapes and lengths alone.

GLM-4.7-Flash's count (:mod:`chipbench.costs_glm`: attention by the form
each step runs, 4 routed experts and the shared one a token, the cache at
the published 576 values a token and layer) with the four-stream
residual added.  A token's sub-layer multiplies ``x~ [n D]`` by its maps
``phi [n D, n^2 + 2n]``, and the head's read-out by ``phi_head [n D,
n]``: those products' FLOPs; the streams' mixing is ``O(n^2 D)`` vector
work and counts nothing.  Bytes: the maps in float32, and a token's
streams, ``n D`` values, read and written by each sub-layer and read once
by the read-out.
"""

from __future__ import annotations

from . import costs_glm as glm
from . import weights_xing4 as weights
from .costs import least_seconds  # noqa: F401  (re-exported)
from .costs_glm import (  # noqa: F401  (mla_decode_cost re-exported)
    cache_bytes_per_token,
    expert_weights,
    head_weights,
    mla_decode_cost,
    n_moe,
)


def counts(d: dict) -> dict:
    return weights.parameter_count(d)


def hc_weights(d: dict) -> int:
    """The maps' parameters of the whole cut, every layer's and the
    read-out's (float32)."""
    c = counts(d)
    return d["n_layers"] * c["hc_layer"] + c["hc_head"]


def hc_token_flops(d: dict) -> int:
    """FLOPs of one token's map products: two sub-layers a layer, and the
    read-out."""
    n, D = d["hc_mult"], d["d_model"]
    return 2 * n * D * (2 * d["n_layers"] * (n * n + 2 * n) + n)


def stream_bytes(d: dict, tokens: int, itemsize: int = 2) -> int:
    """The streams' bytes for ``tokens``: each sub-layer reads and writes
    a token's ``n D`` values, the read-out reads them once."""
    return tokens * itemsize * d["hc_mult"] * d["d_model"] * (
        4 * d["n_layers"] + 1)


def _weight_bytes(params: int, d: dict, itemsize: int) -> int:
    """``params`` of which the maps are all, at their float32."""
    return itemsize * (params - hc_weights(d)) + 4 * hc_weights(d)


def prefill_cost(d: dict, prompt: int, itemsize: int = 2) -> tuple:
    """(FLOPs, bytes) of one prompt's prefill: GLM's with the maps'
    products, the maps in float32 and the prompt's streams."""
    flops, _ = glm.prefill_cost(d, prompt, itemsize)
    flops += hc_token_flops(d) * prompt
    held = counts(d)["held"] - head_weights(d)        # all but the embedding
    bytes_ = _weight_bytes(held, d, itemsize) \
        + itemsize * prompt * d["d_model"] \
        + prompt * cache_bytes_per_token(d, itemsize) \
        + stream_bytes(d, prompt, itemsize)
    return flops + 0.0, float(bytes_)


def decode_tick_cost(d: dict, lengths, experts_touched=None,
                     itemsize: int = 2) -> tuple:
    """(FLOPs, bytes) of one decode tick over streams at ``lengths``:
    GLM's with the maps' products, the maps in float32 and the rows'
    streams.  ``experts_touched`` as :func:`chipbench.costs_glm
    .decode_tick_cost`'s."""
    n = len(lengths)
    if experts_touched is None:
        experts_touched = n_moe(d) * min(n * d["experts_per_token"],
                                         d["n_experts"])
    kf, kb = mla_decode_cost(d, lengths, itemsize)
    flops = 2.0 * (glm.token_weights(d) + head_weights(d)) * n \
        + d["n_layers"] * kf + hc_token_flops(d) * n
    fixed = counts(d)["held"] - head_weights(d) \
        - n_moe(d) * counts(d)["experts"]
    bytes_ = _weight_bytes(fixed, d, itemsize) \
        + itemsize * (n * d["d_model"]
                      + experts_touched * expert_weights(d)) \
        + d["n_layers"] * kb + stream_bytes(d, n, itemsize)
    return flops, float(bytes_)
