"""The benchmark's weights for the ``ouro`` family, made on the device from
``--seed``: the tree of the program's ``models/ouro.py`` (``wte``;
``lm_head``; ``loop``, the ONE stack every pass runs: ``h_<i>`` with
``ln_in``, ``attn_full`` (``q_proj``, ``k_proj``, ``v_proj``, ``o_proj``,
kernels stored (in, out)), ``ln_post_attn``, ``ln_pre_mlp``, ``mlp``
(``gate_proj``, ``up_proj``, ``down_proj``), ``ln_post_mlp``; ``ln_f``, the
final norm; ``exit_gate`` (kernel (d, 1), bias (1,))). The runner checks the
layout against the program's own abstract state and fails loudly where they
differ.

Initialisation (``assumed`` in the configuration's file: the catalog's row of
config.json carries no initializer): normal(0, 0.02) for every matrix, the
embedding, the head and the exit gate's kernel; ones for every norm's scale;
zeros for the gate's bias.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from chipbench.weights import _nest, flatten, seed_key  # noqa: F401


def param_shapes(sizes: dict) -> dict:
    """{path tuple: (shape, kind)}; kind is 'normal', 'ones' or 'zeros'."""
    d, V, F = sizes["n_embd"], sizes["vocab_size"], sizes["intermediate_size"]
    H, G, D = sizes["n_head"], sizes["n_kv_head"], sizes["head_dim"]
    out = {("wte", "embedding"): ((V, d), "normal"),
           ("lm_head",): ((V, d), "normal"),
           ("loop", "ln_f", "scale"): ((d,), "ones"),
           ("loop", "exit_gate", "kernel"): ((d, 1), "normal"),
           ("loop", "exit_gate", "bias"): ((1,), "zeros")}
    for i in range(sizes["n_layer"]):
        h = ("loop", f"h_{i}")
        for norm in ("ln_in", "ln_post_attn", "ln_pre_mlp", "ln_post_mlp"):
            out[h + (norm, "scale")] = ((d,), "ones")
        for name, shape in (("q_proj", (d, H * D)), ("k_proj", (d, G * D)),
                            ("v_proj", (d, G * D)), ("o_proj", (H * D, d))):
            out[h + ("attn_full", name, "kernel")] = (shape, "normal")
        for name, shape in (("gate_proj", (d, F)), ("up_proj", (d, F)),
                            ("down_proj", (F, d))):
            out[h + ("mlp", name, "kernel")] = (shape, "normal")
    return out


def make_params(sizes: dict, key, dtype=jnp.float32) -> dict:
    """The parameter tree drawn from ``key`` (``seed_key(seed)``); traceable,
    as ``chipbench.weights.make_params``."""
    flat = {}
    for n, (path, (shape, kind)) in enumerate(
            sorted(param_shapes(sizes).items())):
        if kind in ("ones", "zeros"):
            flat[path] = jnp.full(shape, float(kind == "ones"), dtype)
        else:
            flat[path] = (0.02 * jax.random.normal(
                jax.random.fold_in(key, n), shape, jnp.float32)).astype(dtype)
    return _nest(flat)
