"""Operations and bytes the training step *requires*, from shapes alone.

The yardstick for every share of a peak the benchmark reports. Kept here,
not taken from the program (``Trainer.flops_per_iter`` counts attention as
full, nanoGPT's convention; the model is causal, so the passes require half
of that). Recomputed work (rematerialisation, the flash backward's second
QK^T) is not counted.
"""

from __future__ import annotations

import json
import os

_HERE = os.path.dirname(os.path.abspath(__file__))


def load_peaks(device_kind: str) -> dict:
    """The published peaks of ``device_kind``; an unknown kind is an error."""
    with open(os.path.join(_HERE, "peaks.json"), encoding="utf-8") as f:
        table = json.load(f)["device_kinds"]
    if device_kind not in table:
        raise KeyError(
            f"device_kind {device_kind!r} is not in chipbench/peaks.json "
            f"(known: {sorted(table)}); add its published peaks with a source")
    return table[device_kind]


def n_params(sizes: dict) -> int:
    """All parameters of the GPT-2 the sizes describe (tied head once)."""
    L, C = sizes["n_layer"], sizes["n_embd"]
    V, T = sizes["vocab_size"], sizes["block_size"]
    per_layer = 12 * C * C + 2 * C          # qkv, proj, fc, proj + 2 LN scales
    if sizes.get("bias", False):
        per_layer += 9 * C + 2 * C          # dense biases + 2 LN biases
    final_ln = C * (2 if sizes.get("bias", False) else 1)
    return V * C + T * C + L * per_layer + final_ln


def train_flops_per_token(sizes: dict) -> float:
    """Forward + backward operations one trained token requires:
    6 per parameter that multiplies it (all but the position table), plus
    causal attention, 6 * L * H * Q * T (half of the full 12 * L * H * Q * T)."""
    L, H = sizes["n_layer"], sizes["n_head"]
    Q, T = sizes["n_embd"] // sizes["n_head"], sizes["block_size"]
    n = n_params(sizes) - sizes["block_size"] * sizes["n_embd"]
    return 6.0 * n + 6.0 * L * H * Q * T


def flash_attention_cost(sizes: dict, batch: int, itemsize: int = 2) -> dict:
    """Operations and bytes of the causal flash forward and backward over
    all layers of one step on ``batch`` rows.

    Operations: six T x T x D matmuls a head (QK^T and PV forward; dV, dP,
    dQ, dK backward), each 2 * T * T * D, halved for the causal triangle.
    Bytes: forward reads q, k, v and writes o; backward reads q, k, v, o, do
    and writes dq, dk, dv (12 tensors of B*H*T*D in the compute type), and
    the per-row softmax statistic (float32) is written once and read once.
    """
    L, H = sizes["n_layer"], sizes["n_head"]
    D, T = sizes["n_embd"] // sizes["n_head"], sizes["block_size"]
    per_head = 6 * (2 * T * T * D) / 2
    ops = float(L * batch * H * per_head)
    tensor = batch * H * T * D * itemsize
    nbytes = float(L * (12 * tensor + 2 * batch * H * T * 4))
    return {"ops": ops, "bytes": nbytes}


def least_seconds(cost: dict, peaks: dict, chips: int = 1) -> dict:
    """The least time ``chips`` chips could take for ``cost``, and which
    bound (compute or memory) sets it."""
    t_ops = cost["ops"] / (chips * peaks["bf16_flops_per_s"])
    t_mem = cost["bytes"] / (chips * peaks["hbm_bytes_per_s"])
    return {"seconds": max(t_ops, t_mem),
            "bound": "compute" if t_ops >= t_mem else "memory"}
