"""Operations and bytes the ``afmoe`` training step *requires*, from shapes
alone (``chipbench/flops.py`` is GPT-2's). Recomputed work is not counted:
rematerialised layers, the flash backward's second QK^T, the split
backward's second score tile.

``sizes`` is the new runner's: n_layer, n_head (H), n_kv_head (G), head_dim
(D), n_embd (d), vocab_size, block_size (T), layer_types ('sliding' |
'full' a layer), sliding_window (W), num_dense_layers, intermediate_size,
moe_intermediate_size (F), num_experts (E, the router's width),
num_experts_per_tok (k), experts_held (first, count).
"""

from __future__ import annotations

from chipbench.flops import least_seconds, load_peaks  # noqa: F401


def attention_pairs(T: int, window) -> int:
    """(query, key) pairs the masks leave in one row of T tokens: sum over
    queries i of min(i + 1, window)."""
    if window is None or window >= T:
        return T * (T + 1) // 2
    return window * (window + 1) // 2 + (T - window) * window


def layer_windows(sizes: dict) -> list:
    return [sizes["sliding_window"] if kind == "sliding" else None
            for kind in sizes["layer_types"]]


def expected_rows_held(sizes: dict, tokens: int) -> float:
    """tokens * k * count / E: what a router that spreads evenly sends to
    the experts held here, a layer."""
    return (tokens * sizes["num_experts_per_tok"] * sizes["experts_held"][1]
            / sizes["num_experts"])


def n_params(sizes: dict) -> int:
    """Every parameter held here."""
    d, H, G, D = (sizes["n_embd"], sizes["n_head"], sizes["n_kv_head"],
                  sizes["head_dim"])
    F, count = sizes["moe_intermediate_size"], sizes["experts_held"][1]
    attn = d * (2 * H * D + 2 * G * D) + H * D * d + 2 * D
    dense = sizes["num_dense_layers"]
    total = 2 * sizes["vocab_size"] * d + d
    total += sizes["n_layer"] * (attn + 4 * d)
    total += dense * 3 * d * sizes["intermediate_size"]
    total += (sizes["n_layer"] - dense) * (
        3 * d * F * (1 + count) + (d + 1) * sizes["num_experts"])
    return total


def train_flops_per_token(sizes: dict, rows_held_per_token=None) -> float:
    """Forward + backward operations one trained token requires: 6 per
    parameter that multiplies it (projections, the dense MLP, the shared
    expert, the router, the head; one routed expert for each held slot the
    token has: k * count / E on average, or the counted mean handed in),
    plus 12 * H * D for every (query, key) pair attention leaves it."""
    d, H, G, D = (sizes["n_embd"], sizes["n_head"], sizes["n_kv_head"],
                  sizes["head_dim"])
    F, T = sizes["moe_intermediate_size"], sizes["block_size"]
    if rows_held_per_token is None:
        rows_held_per_token = expected_rows_held(sizes, 1)
    dense = sizes["num_dense_layers"]
    per_token = sizes["vocab_size"] * d
    per_token += sizes["n_layer"] * (d * (2 * H * D + 2 * G * D) + H * D * d)
    per_token += dense * 3 * d * sizes["intermediate_size"]
    per_token += (sizes["n_layer"] - dense) * (
        3 * d * F * (1 + rows_held_per_token) + d * sizes["num_experts"])
    pairs = sum(attention_pairs(T, w) for w in layer_windows(sizes)) / T
    return 6.0 * per_token + 12.0 * H * D * pairs


def attention_cost(sizes: dict, batch: int, window, itemsize: int = 2) -> dict:
    """Operations and bytes of ONE layer's grouped-query flash forward and
    backward on ``batch`` rows, ``window`` None for a full layer.

    Operations: six matmuls of 2 * D a (query, key) pair and query head
    (QK^T, PV; dV, dP, dQ, dK). Bytes: the forward reads q, k, v and writes
    o; the backward reads q, k, v, o, do and writes dq, dk, dv: six tensors
    of B*T*H*D and six of B*T*G*D in the compute type; the per-row softmax
    statistic (float32, B*H*T) written once and read once."""
    H, G, D, T = (sizes["n_head"], sizes["n_kv_head"], sizes["head_dim"],
                  sizes["block_size"])
    ops = 12.0 * D * H * batch * attention_pairs(T, window)
    nbytes = 6.0 * batch * T * (H + G) * D * itemsize + 2.0 * batch * H * T * 4
    return {"ops": ops, "bytes": nbytes}


def attention_step_cost(sizes: dict, batch: int) -> dict:
    """attention_cost summed over the step's layers."""
    costs = [attention_cost(sizes, batch, w) for w in layer_windows(sizes)]
    return {"ops": sum(c["ops"] for c in costs),
            "bytes": sum(c["bytes"] for c in costs)}


def gmm_cost(sizes: dict, rows: float, itemsize: int = 2) -> dict:
    """Operations and bytes of ONE expert layer's grouped matmuls on
    ``rows`` sorted rows, forward and backward: three products forward
    (gate, up: rows x d x F; down: rows x F x d), and for each a dgrad (the
    same shape against the transposed weights) and a wgrad (per expert,
    lhs^T @ dout): nine products of 2 * rows * d * F. Bytes: each product
    reads its two operands and writes its result once, in the compute
    type."""
    d, F = sizes["n_embd"], sizes["moe_intermediate_size"]
    count = sizes["experts_held"][1]
    weights = count * d * F
    per_product = rows * d + rows * F + weights    # in, out / grads, weights
    return {"ops": 9 * 2.0 * rows * d * F,
            "bytes": 9.0 * per_product * itemsize}
