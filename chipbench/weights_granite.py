"""The benchmark's weights for the ``granite`` family, made on the device from
``--seed``: the tree of the program's ``models/granite.py`` (``wte``, tied
to the head; ``final_norm``; ``h_<i>`` with ``input_layernorm``,
``post_attention_layernorm``, ``mlp`` (``gate_proj``, ``up_proj``,
``down_proj``) and, by ``layer_types``, ``mamba`` (``in_proj``,
``conv_weight`` (width, taps), ``conv_bias``, ``dt_bias``, ``A_log``, ``D``,
``norm``, ``out_proj``) or ``attn_full`` (``q_proj``, ``k_proj``,
``v_proj``, ``o_proj``); kernels stored (in, out)). The runner checks the
layout against the program's own abstract state and fails loudly where they
differ.

Initialisation (``assumed`` in the configuration's file: the catalog's row
of config.json carries none): normal(0, 0.02) for every projection and the
table; ones for every norm's scale and for D; Mamba-2's own defaults
elsewhere: the conv's taps uniform on +-1/sqrt(taps) (PyTorch's Conv1d, which
Mamba-2 keeps) with a zero bias, A_log = log U[1, 16], dt_bias =
softplus^-1(dt0) with dt0 log-uniform on [1e-3, 1e-1] (floored at 1e-4).
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from chipbench.weights import _nest, flatten, seed_key  # noqa: F401

DT_RANGE, DT_FLOOR = (1e-3, 1e-1), 1e-4
A_RANGE = (1.0, 16.0)


def param_shapes(sizes: dict) -> dict:
    """{path tuple: (shape, kind)}; kind is 'normal', 'ones', 'zeros',
    'taps', 'a_log' or 'dt_bias'."""
    d, V, F = sizes["n_embd"], sizes["vocab_size"], sizes["intermediate_size"]
    H, G, D = sizes["n_head"], sizes["n_kv_head"], sizes["head_dim"]
    Hm, P = sizes["mamba_n_heads"], sizes["mamba_d_head"]
    inner = Hm * P
    width = inner + 2 * sizes["mamba_n_groups"] * sizes["mamba_d_state"]
    out = {("wte", "embedding"): ((V, d), "normal"),
           ("final_norm", "scale"): ((d,), "ones")}
    for i, kind in enumerate(sizes["layer_types"]):
        h = (f"h_{i}",)
        for norm in ("input_layernorm", "post_attention_layernorm"):
            out[h + (norm, "scale")] = ((d,), "ones")
        for name, shape in (("gate_proj", (d, F)), ("up_proj", (d, F)),
                            ("down_proj", (F, d))):
            out[h + ("mlp", name, "kernel")] = (shape, "normal")
        if kind == "mamba":
            m = h + ("mamba",)
            out[m + ("in_proj", "kernel")] = ((d, inner + width + Hm),
                                              "normal")
            out[m + ("conv_weight",)] = ((width, sizes["mamba_d_conv"]),
                                         "taps")
            out[m + ("conv_bias",)] = ((width,), "zeros")
            out[m + ("dt_bias",)] = ((Hm,), "dt_bias")
            out[m + ("A_log",)] = ((Hm,), "a_log")
            out[m + ("D",)] = ((Hm,), "ones")
            out[m + ("norm", "scale")] = ((inner,), "ones")
            out[m + ("out_proj", "kernel")] = ((inner, d), "normal")
        else:
            for name, shape in (("q_proj", (d, H * D)), ("k_proj", (d, G * D)),
                                ("v_proj", (d, G * D)),
                                ("o_proj", (H * D, d))):
                out[h + ("attn_full", name, "kernel")] = (shape, "normal")
    return out


def _draw(kind: str, shape: tuple, key) -> jax.Array:
    """One leaf of ``kind`` (not 'ones' / 'zeros'), float32."""
    if kind == "normal":
        return 0.02 * jax.random.normal(key, shape, jnp.float32)
    u = jax.random.uniform(key, shape, jnp.float32)
    if kind == "taps":
        bound = 1.0 / math.sqrt(shape[1])
        return (2.0 * u - 1.0) * bound
    if kind == "a_log":
        return jnp.log(A_RANGE[0] + u * (A_RANGE[1] - A_RANGE[0]))
    lo, hi = (math.log(v) for v in DT_RANGE)            # 'dt_bias'
    dt = jnp.maximum(jnp.exp(lo + u * (hi - lo)), DT_FLOOR)
    return dt + jnp.log(-jnp.expm1(-dt))                 # softplus^-1(dt)


def make_params(sizes: dict, key, dtype=jnp.float32) -> dict:
    """The parameter tree drawn from ``key`` (``seed_key(seed)``); traceable,
    as ``chipbench.weights.make_params``."""
    flat = {}
    for n, (path, (shape, kind)) in enumerate(
            sorted(param_shapes(sizes).items())):
        if kind in ("ones", "zeros"):
            flat[path] = jnp.full(shape, float(kind == "ones"), dtype)
        else:
            flat[path] = _draw(kind, shape, jax.random.fold_in(key, n)
                               ).astype(dtype)
    return _nest(flat)
