"""The benchmark's weights for the ``lfm2`` family, made on the device from
``--seed``: the tree of the program's ``models/lfm2.py`` (``wte``; ``h_<i>``
with ``operator_norm``, ``conv`` (``in_proj``, ``filter`` (d, L),
``out_proj``) or ``attn_full`` (``q_proj``, ``k_proj``, ``v_proj``,
``o_proj``, ``q_norm``, ``k_norm``), ``ffn_norm``, ``mlp`` or ``moe``;
``embedding_norm``; no head of its own: the head is the embedding table),
kernels stored (in, out), expert matrices (experts held, in, out). The runner
checks the layout against the program's own abstract state and fails loudly
where they differ.

Initialisation (``assumed`` in the configuration's file: the published
config.json carries no initializer): normal(0, 0.02) for every matrix, the
embedding, the router and the filter's taps (what HF ``transformers`` does
with a Linear, an Embedding and a Conv1d at ``initializer_range`` 0.02); ones
for every norm's scale; for the experts' selection bias zeros, or the rows of
``expert_bias``: what ``chipbench/reference/lfm2.balanced_bias`` makes of the
zero-bias weights and rows of the corpus (``chipbench/runners/train_lfm2.py``).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from chipbench.weights import _nest, flatten, seed_key  # noqa: F401


def param_shapes(sizes: dict) -> dict:
    """{path tuple: (shape, kind)}; kind is 'normal', 'ones' or 'zeros'."""
    d, V = sizes["n_embd"], sizes["vocab_size"]
    H, G, D = sizes["n_head"], sizes["n_kv_head"], sizes["head_dim"]
    F, count = sizes["moe_intermediate_size"], sizes["experts_held"][1]
    out = {("wte", "embedding"): ((V, d), "normal"),
           ("embedding_norm", "scale"): ((d,), "ones")}
    for i, kind in enumerate(sizes["layer_types"]):
        h = (f"h_{i}",)
        for norm in ("operator_norm", "ffn_norm"):
            out[h + (norm, "scale")] = ((d,), "ones")
        if kind == "conv":
            c = h + ("conv",)
            out[c + ("in_proj", "kernel")] = ((d, 3 * d), "normal")
            out[c + ("filter",)] = ((d, sizes["conv_L_cache"]), "normal")
            out[c + ("out_proj", "kernel")] = ((d, d), "normal")
        else:
            a = h + ("attn_full",)
            out[a + ("q_proj", "kernel")] = ((d, H * D), "normal")
            out[a + ("k_proj", "kernel")] = ((d, G * D), "normal")
            out[a + ("v_proj", "kernel")] = ((d, G * D), "normal")
            out[a + ("o_proj", "kernel")] = ((H * D, d), "normal")
            out[a + ("q_norm", "scale")] = ((D,), "ones")
            out[a + ("k_norm", "scale")] = ((D,), "ones")
        if i < sizes["num_dense_layers"]:
            width = sizes["intermediate_size"]
            m = h + ("mlp",)
            out[m + ("gate_proj", "kernel")] = ((d, width), "normal")
            out[m + ("up_proj", "kernel")] = ((d, width), "normal")
            out[m + ("down_proj", "kernel")] = ((width, d), "normal")
        else:
            m = h + ("moe",)
            out[m + ("router",)] = ((d, sizes["num_experts"]), "normal")
            out[m + ("expert_bias",)] = ((sizes["num_experts"],), "zeros")
            out[m + ("w_gate",)] = ((count, d, F), "normal")
            out[m + ("w_up",)] = ((count, d, F), "normal")
            out[m + ("w_down",)] = ((count, F, d), "normal")
    return out


def make_params(sizes: dict, key, expert_bias=None,
                dtype=jnp.float32) -> dict:
    """The parameter tree drawn from ``key`` (``seed_key(seed)``); traceable,
    as ``chipbench.weights.make_params``. ``expert_bias`` (expert layers, E)
    or None for zeros."""
    flat = {}
    for n, (path, (shape, kind)) in enumerate(
            sorted(param_shapes(sizes).items())):
        if kind in ("ones", "zeros"):
            flat[path] = jnp.full(shape, float(kind == "ones"), dtype)
        else:
            flat[path] = (0.02 * jax.random.normal(
                jax.random.fold_in(key, n), shape, jnp.float32)).astype(dtype)
    if expert_bias is not None:
        for n, row in enumerate(expert_bias):
            layer = f"h_{sizes['num_dense_layers'] + n}"
            flat[(layer, "moe", "expert_bias")] = jnp.asarray(row, dtype)
    return _nest(flat)
