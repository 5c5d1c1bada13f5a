"""The benchmark's own weights: made on the device from ``--seed``.

The trainer under test and the plain reference both start from what
``make_params`` returns, so the reference takes nothing the program made.
The tree has the layout of the program's GPT-2 (``wte``/``wpe``/``h_<i>``/
``ln_f``, kernels stored (in, out)); the runner checks that against the
program's own abstract state and fails loudly where they differ.

Initialisation is GPT-2's: normal(0, 0.02) for embeddings and kernels,
normal(0, 0.02 / sqrt(2 L)) for the two residual projections, ones for
the layer-norm scales, zeros for biases (present only with ``bias``).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp


def param_shapes(sizes: dict) -> dict:
    """{path tuple: (shape, kind)}; kind is 'normal', 'proj', 'ones' or
    'zeros'. ``sizes`` holds n_layer, n_embd, block_size, vocab_size, bias."""
    L, C = sizes["n_layer"], sizes["n_embd"]
    V, T, bias = sizes["vocab_size"], sizes["block_size"], sizes.get("bias", False)
    out = {("wte", "embedding"): ((V, C), "normal"),
           ("wpe", "embedding"): ((T, C), "normal")}

    def norm(prefix):
        out[prefix + ("scale",)] = ((C,), "ones")
        if bias:
            out[prefix + ("bias",)] = ((C,), "zeros")

    def dense(prefix, n_in, n_out, kind):
        out[prefix + ("kernel",)] = ((n_in, n_out), kind)
        if bias:
            out[prefix + ("bias",)] = ((n_out,), "zeros")

    for i in range(L):
        h = (f"h_{i}",)
        norm(h + ("ln_1",))
        dense(h + ("attn", "c_attn"), C, 3 * C, "normal")
        dense(h + ("attn", "c_proj"), C, C, "proj")
        norm(h + ("ln_2",))
        dense(h + ("mlp", "c_fc"), C, 4 * C, "normal")
        dense(h + ("mlp", "c_proj"), 4 * C, C, "proj")
    norm(("ln_f",))
    return out


def _nest(flat: dict) -> dict:
    tree: dict = {}
    for path, leaf in flat.items():
        node = tree
        for k in path[:-1]:
            node = node.setdefault(k, {})
        node[path[-1]] = leaf
    return tree


def seed_key(seed: int):
    """The key all weights of ``seed`` are drawn from. A seed over 32 bits is
    folded in two halves: ``jax.random.key`` takes 32."""
    return jax.random.fold_in(jax.random.key(seed & 0xFFFFFFFF), seed >> 32)


def make_params(sizes: dict, key, dtype=jnp.float32) -> dict:
    """The parameter tree drawn from ``key`` (``seed_key(seed)``). Traceable:
    call it under jit, the key an argument so that every seed runs the same
    compiled program, with ``out_shardings`` to make each chip's shard where
    it lives."""
    proj_std = 0.02 / (2 * sizes["n_layer"]) ** 0.5
    flat = {}
    for n, (path, (shape, kind)) in enumerate(sorted(param_shapes(sizes).items())):
        if kind == "ones":
            flat[path] = jnp.ones(shape, dtype)
        elif kind == "zeros":
            flat[path] = jnp.zeros(shape, dtype)
        else:
            std = proj_std if kind == "proj" else 0.02
            flat[path] = (std * jax.random.normal(
                jax.random.fold_in(key, n), shape, jnp.float32)).astype(dtype)
    return _nest(flat)


def flatten(tree: dict, prefix: tuple = ()) -> dict:
    """{'h_0/attn/c_attn/kernel': leaf, ...} for a nested dict of arrays."""
    out = {}
    for k in sorted(tree):
        v = tree[k]
        if isinstance(v, dict):
            out.update(flatten(v, prefix + (k,)))
        else:
            out["/".join(prefix + (k,))] = v
    return out
