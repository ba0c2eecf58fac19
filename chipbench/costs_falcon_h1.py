"""The work Falcon-H1 needs, from shapes and lengths alone.

A token multiplies every layer's attention matrices, ``W_in`` and
``W_out`` of the SSM and the MLP, and runs the SSM's convolution and one
step of its recurrence: ``5 H P N`` FLOPs a layer (the state's decay, the
outer product added, the product with ``C``; 5.2 MFLOP at the published
widths, against 860 MFLOP of its matrices).  A prompt's chunked scan
does more than that; its least is the recurrence's, and that is what is
counted.  Attention is counted by its (query, key) pairs.

Bytes are the least a step has to move: every matrix but the embedding
(of which a step reads its tokens' rows), the K and V a tick reads and a
prefill writes (``2 layers KV head_dim`` values a token), and the state
slots: a tick reads and writes each live row's, a prefill writes its
row's.  A row's slot is the SSM state ``H N P`` and the convolution's last
``k - 1`` inputs, a layer each, in the served type.
"""

from __future__ import annotations

from . import weights_falcon_h1 as weights
from .costs import least_seconds  # noqa: F401  (re-exported)

#: bytes of a float32 value: the decode kernel's inputs and outputs
F32_BYTES = 4


def counts(d: dict) -> dict:
    return weights.parameter_count(d)


def layer_weights(d: dict) -> int:
    """Matmul weights one token multiplies in one layer."""
    D = d["d_model"]
    return (D * d["head_dim"] * (2 * d["n_heads"] + 2 * d["n_kv_heads"])
            + D * weights.in_dim(d) + d["d_ssm"] * D + 3 * D * d["d_ff"])


def head_weights(d: dict) -> int:
    return d["d_model"] * d["vocab_size"]


def ssm_token_flops(d: dict) -> int:
    """One token's SSM in one layer beside its matrices: the convolution
    and one step of the recurrence."""
    HPN = d["ssm_heads"] * d["ssm_head_dim"] * d["ssm_state"]
    return 5 * HPN + 2 * d["conv_kernel"] * weights.conv_dim(d)


def pair_flops(d: dict) -> int:
    """FLOPs of one (query, key) pair in one layer, all heads."""
    return 4 * d["n_heads"] * d["head_dim"]


def kv_bytes_per_token(d: dict, itemsize: int = 2) -> int:
    """Bytes one token holds in the K and V pools, all layers (12,288 for
    the six-layer cut in bf16)."""
    return d["n_layers"] * 2 * d["n_kv_heads"] * d["head_dim"] * itemsize


def ssm_state_bytes(d: dict, itemsize: int = 2) -> int:
    """One row's SSM state in one layer (2,097,152 B in bf16)."""
    return d["ssm_heads"] * d["ssm_head_dim"] * d["ssm_state"] * itemsize


def state_bytes_per_row(d: dict, itemsize: int = 2) -> int:
    """One row's state slot, all layers: the SSM state and the
    convolution's tail ((2,097,152 + 30,720) x 6 in bf16)."""
    tail = (d["conv_kernel"] - 1) * weights.conv_dim(d) * itemsize
    return d["n_layers"] * (ssm_state_bytes(d, itemsize) + tail)


def _fixed_bytes(d: dict, itemsize: int) -> int:
    """Every parameter but the embedding, in the served type."""
    return itemsize * (counts(d)["held"] - head_weights(d))


def prefill_cost(d: dict, prompt: int, itemsize: int = 2) -> tuple:
    """(FLOPs, bytes) of one prompt's prefill: its tokens through every
    layer, causal attention, the head on the last position; every matrix
    read once, the prompt's embedding rows, its K and V and its row's
    state slot written."""
    L = d["n_layers"]
    flops = (2.0 * layer_weights(d) * L * prompt
             + L * pair_flops(d) * (prompt * (prompt + 1) // 2)
             + L * ssm_token_flops(d) * prompt + 2.0 * head_weights(d))
    bytes_ = (_fixed_bytes(d, itemsize) + itemsize * prompt * d["d_model"]
              + prompt * kv_bytes_per_token(d, itemsize)
              + state_bytes_per_row(d, itemsize))
    return flops, float(bytes_)


def tick_cost(d: dict, rows: int, kv_tokens: int, state_bytes: int,
              itemsize: int = 2) -> tuple:
    """(FLOPs, bytes) of one decode tick of ``rows`` streams that read
    ``kv_tokens`` cached tokens and ``state_bytes`` of state slots (read
    and written), as the tick's ``hvd.serve.decode`` span says."""
    L = d["n_layers"]
    flops = (2.0 * (L * layer_weights(d) + head_weights(d)) * rows
             + L * pair_flops(d) * kv_tokens
             + L * ssm_token_flops(d) * rows)
    bytes_ = (_fixed_bytes(d, itemsize) + itemsize * rows * d["d_model"]
              + kv_tokens * kv_bytes_per_token(d, itemsize) + state_bytes)
    return flops, float(bytes_)


def decode_tick_cost(d: dict, lengths, itemsize: int = 2) -> tuple:
    """(FLOPs, bytes) of one decode tick over streams whose contexts are
    ``lengths`` (the token being written included)."""
    n = len(lengths)
    return tick_cost(d, n, sum(int(t) for t in lengths),
                     2 * n * state_bytes_per_row(d, itemsize), itemsize)


def ssd_decode_cost(d: dict, rows: int, itemsize: int = 2) -> tuple:
    """(FLOPs, bytes) of ``hvd_ssd_decode`` over every layer of a tick
    with ``rows`` live rows: each row's state read and written, and its
    float32 inputs (``dt x``, the decay, ``B``, ``C``) and output."""
    H, P, G, N = (d["ssm_heads"], d["ssm_head_dim"], d["ssm_groups"],
                  d["ssm_state"])
    io = F32_BYTES * (2 * H * P + H + 2 * G * N)
    per = 2 * ssm_state_bytes(d, itemsize) + io
    return (float(d["n_layers"] * rows * 5 * H * P * N),
            float(d["n_layers"] * rows * per))
