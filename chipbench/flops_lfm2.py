"""Operations and bytes the ``lfm2`` training step *requires*, from shapes
alone. Recomputed work is not counted: rematerialised layers, the flash
backward's second QK^T.

``sizes`` is the runner's (``chipbench/runners/train_lfm2.model_sizes``):
n_layer, n_head (H), n_kv_head (G), head_dim (D), n_embd (d), vocab_size,
block_size (T), layer_types ('conv' | 'full' a layer), conv_L_cache (L),
num_dense_layers, intermediate_size, moe_intermediate_size (F), num_experts
(E, the router's width), num_experts_per_tok (k), experts_held (first,
count).

The attention kernels' and the grouped matmuls' costs are shape functions
that name no family (H, G, D, T, no window; d, F, count): the accepted ones
of ``chipbench/flops_afmoe.py``, under their names.
"""

from __future__ import annotations

from chipbench.flops import least_seconds, load_peaks  # noqa: F401
from chipbench.flops_afmoe import (attention_cost, attention_pairs,  # noqa: F401
                                   expected_rows_held, gmm_cost)


def _mixer_weights(sizes: dict) -> dict:
    """{kind of layer: the weights of its token mixer that multiply a
    token}: in/out projections and the filter's taps; q, k, v, o."""
    d, H, G, D = (sizes["n_embd"], sizes["n_head"], sizes["n_kv_head"],
                  sizes["head_dim"])
    return {"conv": 4 * d * d + d * sizes["conv_L_cache"],
            "full": d * (2 * H * D + 2 * G * D)}


def n_params(sizes: dict) -> int:
    """Every parameter held here (the tied head counts once)."""
    d, D = sizes["n_embd"], sizes["head_dim"]
    F, count = sizes["moe_intermediate_size"], sizes["experts_held"][1]
    mixer = _mixer_weights(sizes)
    mixer["full"] += 2 * D                  # the q and k norms' scales
    dense = sizes["num_dense_layers"]
    total = sizes["vocab_size"] * d + d
    total += sum(mixer[kind] + 2 * d for kind in sizes["layer_types"])
    total += dense * 3 * d * sizes["intermediate_size"]
    total += (sizes["n_layer"] - dense) * (
        3 * d * F * count + (d + 1) * sizes["num_experts"])
    return total


def train_flops_per_token(sizes: dict, rows_held_per_token=None) -> float:
    """Forward + backward operations one trained token requires: 6 per
    parameter that multiplies it (the mixers' projections, the filter's L
    taps a channel, the dense MLP, the router, the tied head; one routed
    expert for each held slot the token has: k * count / E on average, or
    the counted mean handed in), plus 12 * H * D for every (query, key) pair
    a full-attention layer leaves it."""
    d, H, D = sizes["n_embd"], sizes["n_head"], sizes["head_dim"]
    F, T = sizes["moe_intermediate_size"], sizes["block_size"]
    if rows_held_per_token is None:
        rows_held_per_token = expected_rows_held(sizes, 1)
    mixer = _mixer_weights(sizes)
    dense = sizes["num_dense_layers"]
    per_token = sizes["vocab_size"] * d
    per_token += sum(mixer[kind] for kind in sizes["layer_types"])
    per_token += dense * 3 * d * sizes["intermediate_size"]
    per_token += (sizes["n_layer"] - dense) * (
        3 * d * F * rows_held_per_token + d * sizes["num_experts"])
    pairs = sizes["layer_types"].count("full") * attention_pairs(T, None) / T
    return 6.0 * per_token + 12.0 * H * D * pairs


def conv_mix_cost(sizes: dict, batch: int, itemsize: int = 2) -> dict:
    """Operations and bytes of ONE conv layer's gates and taps on ``batch``
    rows, forward and backward with no recomputation, whatever implements
    them. Bytes: the forward reads the projection's (B, T, 3d) output and
    writes (B, T, d); the backward reads both inputs again ((B, T, 3d) and
    the (B, T, d) cotangent) and writes the (B, T, 3d) gradient: eleven
    tensors of B*T*d in the compute type (the (d, L) filter and its gradient
    are nothing beside them). Operations: per channel and position two gate
    products and L multiply-adds forward, about three times that backward:
    far under the bytes' time on any chip, counted all the same."""
    d, T, L = sizes["n_embd"], sizes["block_size"], sizes["conv_L_cache"]
    positions = float(batch * T * d)
    return {"ops": 3.0 * (2 + 2 * L) * positions,
            "bytes": 11.0 * positions * itemsize}
