"""Operations and bytes the ``ouro`` training step *requires*, from shapes
alone: every parameter is counted as often as a step APPLIES it, so L times
(``total_ut_steps``) for the stack, the head and the exit gate. Recomputed
work is not counted: rematerialised layers, the chunked heads' replay in the
backward, the flash backward's second score tile.

``sizes`` is the runner's (``chipbench/runners/train_ouro.model_sizes``):
n_layer (layers held, one pass), n_head (H), n_kv_head (G), head_dim (D),
n_embd (d), intermediate_size (F), vocab_size (V), block_size (T),
total_ut_steps (L).

The flash kernels' cost is a shape function that names no family: the
accepted one of ``chipbench/flops_afmoe.py``, under its name.
"""

from __future__ import annotations

from chipbench.flops import least_seconds, load_peaks  # noqa: F401
from chipbench.flops_afmoe import attention_cost, attention_pairs  # noqa: F401


def _layer_weights(sizes: dict) -> int:
    """The matrices of one layer that multiply a token: q, k, v, o and the
    SwiGLU's three."""
    d, H, G, D = (sizes["n_embd"], sizes["n_head"], sizes["n_kv_head"],
                  sizes["head_dim"])
    return d * (2 * H * D + 2 * G * D) + 3 * d * sizes["intermediate_size"]


def n_params(sizes: dict) -> int:
    """Every parameter held here (each once, however often it is applied):
    the layers with their four norms, the embedding and the untied head, the
    final norm, the exit gate with its bias."""
    d = sizes["n_embd"]
    return (sizes["n_layer"] * (_layer_weights(sizes) + 4 * d)
            + 2 * sizes["vocab_size"] * d + d + d + 1)


def exits_cost(sizes: dict, tokens: int) -> dict:
    """Operations of the L exits' head products and exit gate, forward and
    backward (6 a parameter and token: the product, its two gradients), on
    ``tokens`` tokens; bytes: each exit reads the head in the compute type
    three times (product, the hidden's gradient, the head's) and writes its
    float32 gradient once, and reads its state twice and writes its
    gradient (float32). The chunked heads' replay is not counted."""
    d, V, L = sizes["n_embd"], sizes["vocab_size"], sizes["total_ut_steps"]
    ops = 6.0 * L * (V * d + d) * tokens
    nbytes = L * (V * d * (3 * 2 + 4) + 3.0 * tokens * d * 4)
    return {"ops": ops, "bytes": nbytes}


def train_flops_per_token(sizes: dict) -> float:
    """Forward + backward operations one trained token requires: 6 per
    parameter applied (L passes of the layers' matrices, L heads, L gates),
    plus 12 * H * D for every (query, key) pair causal attention leaves it,
    in every layer of every pass."""
    d, H, D, T = (sizes["n_embd"], sizes["n_head"], sizes["head_dim"],
                  sizes["block_size"])
    L, n = sizes["total_ut_steps"], sizes["n_layer"]
    per_pass = n * _layer_weights(sizes) + sizes["vocab_size"] * d + d
    pairs = n * attention_pairs(T, None) / T
    return L * (6.0 * per_pass + 12.0 * H * D * pairs)
