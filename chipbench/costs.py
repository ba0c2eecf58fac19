"""The work the algorithm needs, from shapes and lengths alone.

FLOPs and bytes a model step or a kernel call cannot do without, whatever
implements it: a later kernel is read against the same work.  Recomputed
operations (remat) do not count for a step's share of the peak; for a
kernel's own roofline every executed call counts what that call needs.
"""

from __future__ import annotations


def layer_weight_count(d: dict) -> int:
    D, H, KV, Dh, F = (d[k] for k in ("d_model", "n_heads", "n_kv_heads",
                                       "head_dim", "d_ff"))
    return D * H * Dh + 2 * D * KV * Dh + H * Dh * D + 3 * D * F


def weight_count(d: dict) -> int:
    """Matmul weights of the whole model (norm gains left out)."""
    return d["n_layers"] * layer_weight_count(d) + 2 * d["d_model"] * \
        d["vocab_size"]


def forward_flops(d: dict, tokens: int, attended: int, head_tokens: int
                  ) -> float:
    """A forward pass over ``tokens`` positions that between them attend
    to ``attended`` (query, key) pairs, with the head on ``head_tokens``.
    The embedding is a lookup: no FLOPs."""
    per_pair = 4 * d["n_heads"] * d["head_dim"]        # QK^T and PV
    return (2.0 * d["n_layers"] * layer_weight_count(d) * tokens
            + d["n_layers"] * per_pair * attended
            + 2.0 * d["d_model"] * d["vocab_size"] * head_tokens)


def train_flops_per_token(d: dict, seq: int) -> float:
    """Forward and backward of causal LM training, per token: the backward
    is twice the forward; nothing recomputed is counted."""
    pairs = seq * (seq + 1) // 2
    return 3.0 * forward_flops(d, seq, pairs, seq) / seq


def prefill_cost(d: dict, prompt: int, itemsize: int = 2) -> tuple:
    """(FLOPs, bytes) of one prompt's prefill: causal attention, the head
    on the last position only; the weights are read once and the K/V of
    every position written."""
    flops = forward_flops(d, prompt, prompt * (prompt + 1) // 2, 1)
    kv = 2 * d["n_layers"] * prompt * d["n_kv_heads"] * d["head_dim"]
    return flops, float(itemsize) * (weight_count(d) - d["d_model"] *
                                     d["vocab_size"] + kv)


def paged_decode_bytes(d: dict, lengths, block_size: int,
                       itemsize: int = 2) -> float:
    """Bytes one layer's paged-decode call has to read: the K and V pages
    up to each stream's length (not the table's width)."""
    pages = sum(-(-int(n) // block_size) for n in lengths)
    return float(itemsize) * 2 * pages * block_size * d["n_kv_heads"] * \
        d["head_dim"]


def decode_tick_cost(d: dict, lengths, block_size: int, itemsize: int = 2
                     ) -> tuple:
    """(FLOPs, bytes) of one decode tick over streams at ``lengths``:
    every weight read once, every stream's pages read in every layer."""
    n = len(lengths)
    flops = forward_flops(d, n, sum(int(x) for x in lengths), n)
    bytes_ = itemsize * weight_count(d) - itemsize * d["d_model"] * \
        d["vocab_size"] + d["n_layers"] * paged_decode_bytes(
            d, lengths, block_size, itemsize)
    return flops, float(bytes_)


def least_seconds(flops: float, bytes_: float, peaks: dict) -> float:
    """The roofline: the larger of FLOPs over peak and bytes over peak."""
    return max(flops / peaks["flops_per_s"],
               bytes_ / peaks["hbm_bytes_per_s"])


def flash_flops(d: dict, rows: int, seq: int) -> dict:
    """Causal FLOPs of one call of each flash kernel on [rows, seq]: the
    forward makes 2 matmuls per (query, key) block, dq 3, dk/dv 4."""
    pair = 2.0 * rows * d["n_heads"] * d["head_dim"] * seq * (seq + 1) / 2
    return {"fwd": 2 * pair, "dq": 3 * pair, "dkv": 4 * pair}
