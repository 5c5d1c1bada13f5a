"""The benchmark's weights for the ``deepseek_v3`` family, made on the device
from ``--seed``: the tree of the program's ``models/deepseek_v3.py`` (``wte``;
``h_<i>`` with ``input_layernorm``, ``attn_mla`` (``q_proj`` (d, H*(Dn+Dr)),
``kv_a_proj_with_mqa`` (d, r+Dr), ``kv_a_layernorm``, ``kv_b_proj``
(r, H*(Dn+Dv)), ``o_proj`` (H*Dv, d): leaves of the module, stored (in, out)
in the published column order), ``post_attention_layernorm``, ``mlp`` or
``moe`` (``router``, ``expert_bias``, ``w_gate`` / ``w_up`` / ``w_down`` of
the experts held, ``moe_shared``); ``final_norm``; ``lm_head``). The runner
checks the layout against the program's own abstract state and fails loudly
where they differ.

Initialisation (``assumed`` in the configuration's file: the catalog's row of
config.json carries no initializer): normal(0, 0.02) for every matrix, the
embedding, the head and the router; ones for every norm's scale; for the
experts' selection bias zeros, or the rows of ``expert_bias``: what
``chipbench/reference/dsv3.balanced_bias`` makes of the zero-bias weights and
rows of the corpus (``chipbench/runners/train_dsv3.py``).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from chipbench.weights import _nest, flatten, seed_key  # noqa: F401


def param_shapes(sizes: dict) -> dict:
    """{path tuple: (shape, kind)}; kind is 'normal', 'ones' or 'zeros'."""
    d, V, H = sizes["n_embd"], sizes["vocab_size"], sizes["n_head"]
    r, Dn, Dr, Dv = (sizes["kv_lora_rank"], sizes["qk_nope_head_dim"],
                     sizes["qk_rope_head_dim"], sizes["v_head_dim"])
    F, count = sizes["moe_intermediate_size"], sizes["experts_held"][1]
    out = {("wte", "embedding"): ((V, d), "normal"),
           ("lm_head",): ((V, d), "normal"),
           ("final_norm", "scale"): ((d,), "ones")}

    def swiglu(at, width):
        out[at + ("gate_proj", "kernel")] = ((d, width), "normal")
        out[at + ("up_proj", "kernel")] = ((d, width), "normal")
        out[at + ("down_proj", "kernel")] = ((width, d), "normal")

    for i in range(sizes["n_layer"]):
        h = (f"h_{i}",)
        for norm in ("input_layernorm", "post_attention_layernorm"):
            out[h + (norm, "scale")] = ((d,), "ones")
        a = h + ("attn_mla",)
        out[a + ("q_proj",)] = ((d, H * (Dn + Dr)), "normal")
        out[a + ("kv_a_proj_with_mqa",)] = ((d, r + Dr), "normal")
        out[a + ("kv_a_layernorm", "scale")] = ((r,), "ones")
        out[a + ("kv_b_proj",)] = ((r, H * (Dn + Dv)), "normal")
        out[a + ("o_proj",)] = ((H * Dv, d), "normal")
        if i < sizes["num_dense_layers"]:
            swiglu(h + ("mlp",), sizes["intermediate_size"])
        else:
            m = h + ("moe",)
            out[m + ("router",)] = ((d, sizes["num_experts"]), "normal")
            out[m + ("expert_bias",)] = ((sizes["num_experts"],), "zeros")
            out[m + ("w_gate",)] = ((count, d, F), "normal")
            out[m + ("w_up",)] = ((count, d, F), "normal")
            out[m + ("w_down",)] = ((count, F, d), "normal")
            swiglu(m + ("moe_shared",), sizes["n_shared_experts"] * F)
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
