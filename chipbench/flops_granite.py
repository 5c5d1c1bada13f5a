"""Operations and bytes the ``granite`` training step *requires*, from shapes
alone. Recomputed work is not counted: rematerialised layers, the flash
backward's second score tile.

``sizes`` is the runner's (``chipbench/runners/train_granite.model_sizes``):
n_head (H), n_kv_head (G), head_dim (D), n_embd (d), vocab_size,
block_size (T), layer_types ('mamba' | 'attention' a layer),
intermediate_size (F, the shared MLP), mamba_n_heads (Hm), mamba_d_head (P),
mamba_d_state (N), mamba_n_groups (Gm), mamba_d_conv (K),
mamba_chunk_size (L).

The scan's required forward products a token are counted here, from the
shapes, and the model's MFU and the scan's roofline read that one number
(``tests/test_granite.py`` checks the program's own count against it). The
flash kernels' cost is the accepted shape function of
``chipbench/flops_afmoe.py``.
"""

from __future__ import annotations

from chipbench.flops import least_seconds, load_peaks  # noqa: F401
from chipbench.flops_afmoe import attention_cost, attention_pairs  # noqa: F401


def _scan_width(sizes: dict) -> tuple[int, int]:
    """(inner = Hm P, what the taps mix = inner + 2 Gm N)."""
    inner = sizes["mamba_n_heads"] * sizes["mamba_d_head"]
    return inner, inner + 2 * sizes["mamba_n_groups"] * sizes["mamba_d_state"]


def _mixer_weights(sizes: dict) -> dict:
    """{kind of layer: the weights of its token mixer that multiply a
    token}: a Mamba layer's in / out projections and the conv's taps; q, k,
    v, o."""
    d, H, G, D = (sizes["n_embd"], sizes["n_head"], sizes["n_kv_head"],
                  sizes["head_dim"])
    inner, width = _scan_width(sizes)
    Hm = sizes["mamba_n_heads"]
    return {"mamba": d * (inner + width + Hm) + inner * d
            + width * sizes["mamba_d_conv"],
            "attention": d * (2 * H * D + 2 * G * D)}


def n_params(sizes: dict) -> int:
    """Every parameter held here (the tied table counts once)."""
    d, Hm = sizes["n_embd"], sizes["mamba_n_heads"]
    inner, width = _scan_width(sizes)
    mixer = _mixer_weights(sizes)
    # the conv's bias; dt_bias, A_log, D; the gated norm's scale
    mixer["mamba"] += width + 3 * Hm + inner
    total = sizes["vocab_size"] * d + d        # the table, the final norm
    return total + sum(mixer[kind] + 3 * d * sizes["intermediate_size"]
                       + 2 * d for kind in sizes["layer_types"])


def scan_flops_per_token(sizes: dict) -> float:
    """The scan's required forward products a token of one Mamba layer:
    inside its chunk of L the (L + 1) / 2 positions it sees on average, each
    2 Gm N for C B^T and 2 Hm P for the masked product with x; the chunk
    state, 2 Hm P N; the carried state's output, as much again."""
    L, Hm, P = (sizes["mamba_chunk_size"], sizes["mamba_n_heads"],
                sizes["mamba_d_head"])
    N, Gm = sizes["mamba_d_state"], sizes["mamba_n_groups"]
    return (L + 1) / 2 * (2 * Gm * N + 2 * Hm * P) + 4 * Hm * P * N


def ssd_cost(sizes: dict, tokens: int, itemsize: int = 4) -> dict:
    """Operations and bytes of ONE Mamba layer's scan on ``tokens`` tokens,
    forward and backward with no recomputation, whatever implements it.
    Operations: three times the required forward products
    (``scan_flops_per_token``). Bytes: what the
    op's interface moves at the type the family hands it (float32): forward
    reads x (inner), dt (Hm), B and C (Gm N each) and writes y (inner);
    backward reads them and dy again and writes their gradients. The (Hm,)
    parameters are nothing beside them."""
    inner, _ = _scan_width(sizes)
    Hm = sizes["mamba_n_heads"]
    bc = 2 * sizes["mamba_n_groups"] * sizes["mamba_d_state"]
    ins = inner + Hm + bc
    per_token = (ins + inner) + (ins + inner) + ins   # fwd; bwd reads; writes
    return {"ops": 3.0 * scan_flops_per_token(sizes) * tokens,
            "bytes": float(per_token * itemsize * tokens)}


def train_flops_per_token(sizes: dict) -> float:
    """Forward + backward operations one trained token requires: 6 per
    parameter that multiplies it (the mixers' projections and taps, the
    shared MLPs, the tied head), 12 * H * D for every (query, key) pair an
    attention layer leaves it, and three times the scan's required forward
    products in every Mamba layer."""
    d, H, D, T = (sizes["n_embd"], sizes["n_head"], sizes["head_dim"],
                  sizes["block_size"])
    kinds = sizes["layer_types"]
    mixer = _mixer_weights(sizes)
    per_token = sizes["vocab_size"] * d + sum(
        mixer[kind] + 3 * d * sizes["intermediate_size"] for kind in kinds)
    pairs = kinds.count("attention") * attention_pairs(T, None) / T
    return (6.0 * per_token + 12.0 * H * D * pairs
            + 3.0 * kinds.count("mamba") * scan_flops_per_token(sizes))
