"""What every model family is written with and none owns: the dense
initializer, the activation anchor, a block under jax.checkpoint, the
parameter count."""

from __future__ import annotations

from typing import Any

import flax.linen as nn
import jax


def _dense_init(std: float = 0.02):
    return nn.initializers.normal(stddev=std)


def constrain_acts(mesh: Any, x: jax.Array) -> jax.Array:
    """Pin (B, T, C) activations to batch-over-(data, fsdp) /
    seq-over-seq / C-replicated at the embedding lookup and between
    blocks. Without the anchor at the wte gather, SPMD has to invert a
    sharding transition through a gather whose table is fsdp-sharded —
    a move it only solves by involuntary full rematerialization
    (replicate, then re-partition — the SPMD partitioner warns).
    Free when the sharding already matches, which it does everywhere
    else, so this is an anchor, not a resharding. Shared by every model
    family (models/afmoe.py)."""
    if mesh is None or mesh.size == 1:
        return x
    from jax.sharding import NamedSharding, PartitionSpec as P

    return jax.lax.with_sharding_constraint(
        x, NamedSharding(mesh, P(("data", "fsdp"), "seq", None)))


def remat_block(block_cls, remat_policy: str, saved_names: tuple,
                static_argnums=(2,)):
    """``block_cls`` under jax.checkpoint, by ``remat_policy``.

    'save_attention': save each block's attention output + the flash
    kernel's logsumexp residual (tagged with checkpoint_name inside
    ops/attention.py) so the backward never re-runs the O(T^2) forward
    kernel — a remat region discards custom_vjp residuals, so without
    the tags the flash forward would execute twice in the backward. The
    saved bytes are O(B*T*C) per block; everything else (qkv dense, MLP)
    recomputes cheaply. ``saved_names`` are the family's: those two tags,
    and whatever else of its block it tags as dearer to recompute than to
    keep. 'full' is the classic save-nothing trade."""
    if remat_policy == "save_attention":
        policy = jax.checkpoint_policies.save_only_these_names(*saved_names)
    elif remat_policy == "full":
        policy = None
    else:
        raise ValueError(
            f"unknown remat_policy: {remat_policy!r} "
            "(expected 'save_attention' or 'full')")
    return nn.remat(block_cls, static_argnums=static_argnums, policy=policy)


def count_params(params: Any, include_embeddings: bool = True) -> int:
    total = sum(x.size for x in jax.tree.leaves(params))
    if not include_embeddings:
        emb = params.get("params", params)
        for name in ("wpe",):
            node = emb.get(name)
            if node is not None:
                total -= sum(x.size for x in jax.tree.leaves(node))
    return total
