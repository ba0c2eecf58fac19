"""The work the Kimi-Linear configuration needs, from shapes and from the
counted routing alone (``dims`` as :func:`weights_kimi_linear.dims_of`).

As :mod:`chipbench.costs`: FLOPs and bytes that the algorithm cannot do
without, whatever implements it; nothing recomputed (remat) and nothing
padded counts.  What the program executes beyond this and why: the
embedding as a one-hot product (2 x rows held x hidden a token, shared
with the Llama path), the chunked KDA form's triangular solve and rebased
products (several times the recurrence's 7 K V a token and head, on the
MXU), and the routed experts' tiles padded to whole tiles of 512 pairs.

Routed experts are counted from the pairs the step reported as held, not
from tokens x 8: a chip's share of the routed traffic is what it got.
"""

from __future__ import annotations


def kda_mixer_weights(d: dict) -> int:
    D, HK, R = d["d_model"], d["kda_heads"] * d["kda_head_dim"], d["gate_rank"]
    return (4 * D * HK                      # q, k, v, o
            + 2 * (D * R + R * HK)          # decay gate, output gate
            + D * d["kda_heads"])           # beta


def mla_mixer_weights(d: dict) -> int:
    D, H, C = d["d_model"], d["n_heads"], d["kv_lora_rank"]
    return (D * H * (d["qk_nope"] + d["qk_rope"]) + D * (C + d["qk_rope"])
            + C * H * (d["qk_nope"] + d["v_dim"]) + H * d["v_dim"] * D)


def expert_weights(d: dict) -> int:
    return 3 * d["d_model"] * d["moe_d_ff"]


def kda_core_flops_per_token(d: dict) -> float:
    """The recurrence of one layer, all heads: the decay of the state (K
    V), ``k^T S``, the rank-one update and ``S^T q`` (2 K V each)."""
    return 7.0 * d["kda_heads"] * d["kda_head_dim"] ** 2


def kda_core_cost(d: dict, rows: int, seq: int, itemsize: int = 2) -> tuple:
    """(FLOPs, bytes) of one forward call of the KDA core on ``[rows,
    seq]``: the recurrence's FLOPs; q, k, v read and o written in the
    trained type, the log decay read in float32, beta in float32."""
    tokens = rows * seq
    per_head = d["kda_head_dim"] * (4 * itemsize + 4) + 4
    return (tokens * kda_core_flops_per_token(d),
            float(tokens * d["kda_heads"] * per_head))


def flash_flops(d: dict, rows: int, seq: int) -> dict:
    """Causal FLOPs of one call of each flash kernel in the MLA layer on
    ``[rows, seq]``, keys ``nope + rope`` wide and values ``v_dim``: per
    (query, key) pair and head the forward makes QK^T and PV, dq makes
    QK^T, dO V^T and dS K, dk/dv those two and P^T dO and dS^T Q."""
    pairs = 2.0 * d["n_heads"] * rows * seq * (seq + 1) / 2
    K, V = d["qk_nope"] + d["qk_rope"], d["v_dim"]
    return {"fwd": pairs * (K + V), "dq": pairs * (2 * K + V),
            "dkv": pairs * (2 * K + 2 * V)}


def forward_flops(d: dict, rows: int, seq: int, pairs_held: float) -> dict:
    """Required forward FLOPs of one step on ``[rows, seq]`` by part;
    ``pairs_held``: (token, expert) pairs this chip's experts got, summed
    over the expert layers."""
    tokens = rows * seq
    kinds = [("kda" if i + 1 in d["kda_layers"] else "mla",
              i < d["first_k_dense"]) for i in range(d["n_layers"])]
    n_kda = sum(m == "kda" for m, _ in kinds)
    n_mla = len(kinds) - n_kda
    n_dense = sum(dense for _, dense in kinds)
    n_moe = len(kinds) - n_dense
    attended = rows * seq * (seq + 1) // 2
    per_pair = 2 * d["n_heads"] * (d["qk_nope"] + d["qk_rope"] + d["v_dim"])
    conv = 2 * 3 * d["conv_kernel"] * d["kda_heads"] * d["kda_head_dim"]
    return {
        "kda_projections": n_kda * tokens * (2.0 * kda_mixer_weights(d) + conv),
        "kda_core": n_kda * tokens * kda_core_flops_per_token(d),
        "mla_projections": n_mla * tokens * 2.0 * mla_mixer_weights(d),
        "mla_attention": n_mla * float(per_pair) * attended,
        "dense_mlp": n_dense * tokens * 2.0 * 3 * d["d_model"] * d["d_ff"],
        "shared_experts": n_moe * tokens * 2.0 * expert_weights(d),
        "routers": n_moe * tokens * 2.0 * d["d_model"] * d["n_experts"],
        "routed_experts": 2.0 * expert_weights(d) * pairs_held,
        "head": tokens * 2.0 * d["d_model"] * d["vocab_size"],
    }


def train_flops_per_step(d: dict, rows: int, seq: int, pairs_held: float
                         ) -> float:
    """Forward and backward of one step: the backward is twice the
    forward; nothing recomputed is counted."""
    return 3.0 * sum(forward_flops(d, rows, seq, pairs_held).values())
