"""Operations and bytes the ``deepseek_v3`` training step *requires*, from
shapes alone. Recomputed work is not counted: rematerialised layers, the
flash backward's second score tile; nor are lanes a kernel pads.

``sizes`` is the runner's (``chipbench/runners/train_dsv3.model_sizes``):
n_layer, n_head (H), kv_lora_rank (r), qk_nope_head_dim (Dn),
qk_rope_head_dim (Dr), v_head_dim (Dv), n_embd (d), vocab_size, block_size
(T), num_dense_layers, intermediate_size, moe_intermediate_size (F),
n_shared_experts, num_experts (E, the router's width), num_experts_per_tok
(k), experts_held (first, count).

The grouped matmuls' cost is a shape function that names no family (d, F,
count): the accepted one of ``chipbench/flops_afmoe.py``, under its name.
"""

from __future__ import annotations

from chipbench.flops import least_seconds, load_peaks  # noqa: F401
from chipbench.flops_afmoe import (attention_pairs, expected_rows_held,  # noqa: F401
                                   gmm_cost)


def _attention_weights(sizes: dict) -> int:
    """The four matrices of latent attention that multiply a token."""
    d, H, r = sizes["n_embd"], sizes["n_head"], sizes["kv_lora_rank"]
    Dn, Dr, Dv = (sizes["qk_nope_head_dim"], sizes["qk_rope_head_dim"],
                  sizes["v_head_dim"])
    return (d * H * (Dn + Dr) + d * (r + Dr) + r * H * (Dn + Dv)
            + H * Dv * d)


def n_params(sizes: dict) -> int:
    """Every parameter held here."""
    d, r = sizes["n_embd"], sizes["kv_lora_rank"]
    F, count = sizes["moe_intermediate_size"], sizes["experts_held"][1]
    dense = sizes["num_dense_layers"]
    total = 2 * sizes["vocab_size"] * d + d
    total += sizes["n_layer"] * (_attention_weights(sizes) + r + 2 * d)
    total += dense * 3 * d * sizes["intermediate_size"]
    total += (sizes["n_layer"] - dense) * (
        3 * d * F * (sizes["n_shared_experts"] + count)
        + (d + 1) * sizes["num_experts"])
    return total


def train_flops_per_token(sizes: dict, rows_held_per_token=None) -> float:
    """Forward + backward operations one trained token requires: 6 per
    parameter that multiplies it (the attention matrices, the dense MLP, the
    shared expert, the router, the head; one routed expert for each held
    slot the token has: k * count / E on average, or the counted mean handed
    in), plus, for every (query, key) pair causal attention leaves it and
    head, 2 * (Dn + Dr) for the score and 2 * Dv for the value forward and
    twice that backward."""
    d, H = sizes["n_embd"], sizes["n_head"]
    Dn, Dr, Dv = (sizes["qk_nope_head_dim"], sizes["qk_rope_head_dim"],
                  sizes["v_head_dim"])
    F, T = sizes["moe_intermediate_size"], sizes["block_size"]
    if rows_held_per_token is None:
        rows_held_per_token = expected_rows_held(sizes, 1)
    dense = sizes["num_dense_layers"]
    per_token = sizes["vocab_size"] * d
    per_token += sizes["n_layer"] * _attention_weights(sizes)
    per_token += dense * 3 * d * sizes["intermediate_size"]
    per_token += (sizes["n_layer"] - dense) * (
        3 * d * F * (sizes["n_shared_experts"] + rows_held_per_token)
        + d * sizes["num_experts"])
    pairs = sizes["n_layer"] * attention_pairs(T, None) / T
    return 6.0 * per_token + 6.0 * H * (Dn + Dr + Dv) * pairs


def mla_attention_cost(sizes: dict, batch: int, itemsize: int = 2) -> dict:
    """Operations and bytes of ONE layer's latent-attention kernels, forward
    and backward, on ``batch`` rows: REQUIRED work only.

    Operations, a causal (query, key) pair and head: forward 2 * (Dn + Dr)
    for the score and 2 * Dv for P V; backward 2 * Dv each for dP and dV and
    2 * (Dn + Dr) each for dQ and dK: 2 * (Dn + Dr + Dv) forward and
    2 * (2 * (Dn + Dr) + 2 * Dv) backward. The backward's recomputed score
    and any lanes a kernel pads (a 64-lane part on a 128-lane unit) are not
    counted: a kernel that pads reads lower.

    Bytes: the forward reads q_nope, q_pe, k_nope, v and the ONE rotary key
    and writes o; the backward reads those and o, dO and writes dq_nope,
    dq_pe, dk_nope, dv and dk_pe, in the compute type; the per-row softmax
    statistic (float32, B*H*T) written once and read once."""
    H, T = sizes["n_head"], sizes["block_size"]
    Dn, Dr, Dv = (sizes["qk_nope_head_dim"], sizes["qk_rope_head_dim"],
                  sizes["v_head_dim"])
    ops = 6.0 * (Dn + Dr + Dv) * H * batch * attention_pairs(T, None)
    per_head = 3 * (Dn + Dr) + 3 * Dn + 6 * Dv   # q, dq; k_nope, dk; v, o, dO
    nbytes = (batch * T * (H * per_head + 3 * Dr) * itemsize
              + 2.0 * batch * H * T * 4)
    return {"ops": ops, "bytes": nbytes}
