"""Parameter sharding rules: path-pattern -> PartitionSpec.

DP replicates parameters; FSDP (cfg.shard_params, BASELINE config 5) shards
each parameter's largest eligible dim over the ``fsdp`` axis (ZeRO-3 under
jit: XLA all-gathers params for compute and reduce-scatters grads); TP
shards attention/MLP kernels over ``model`` (column-parallel c_attn/c_fc,
row-parallel c_proj — the classic Megatron layout, expressed purely as
sharding annotations for XLA's SPMD partitioner rather than explicit
collectives).

A dim is only sharded when divisible by the axis size, so tiny test models
fall back to replication rather than erroring.

The ``afmoe`` family's leaves (models/afmoe.py: ``q_proj`` / ``k_proj`` /
``v_proj`` / ``gate_proj`` / ``o_proj``, ``router``, the ``(experts, in,
out)`` matrices ``w_gate`` / ``w_up`` / ``w_down``, ``lm_head``) take the
fsdp rule like any other leaf (largest divisible dim; ``wte`` its rows).
They have NO ``model`` rule and there is no expert axis: ``_tp_dim`` knows
GPT-2's names only, and the family's own ``check`` (models/afmoe.py) refuses
``mesh_tp`` > 1 and ``mesh_sp`` > 1 instead of replicating in silence.
"""

from __future__ import annotations

from typing import Any

import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P


def _path_str(path) -> str:
    parts = []
    for p in path:
        key = getattr(p, "key", None)
        if key is None:
            key = getattr(p, "idx", str(p))
        parts.append(str(key))
    return "/".join(parts)


def _tp_dim(path: str, ndim: int) -> int | None:
    """Megatron placement: column-parallel then row-parallel per block."""
    if ndim != 2:
        return None
    if path.endswith("c_attn/kernel") or path.endswith("c_fc/kernel"):
        return 1  # output dim
    if path.endswith("c_proj/kernel"):
        return 0  # input dim
    if path.endswith("wte/embedding"):
        return None  # keep vocab replicated over model (weight-tied head)
    return None


def spec_for_param(path: str, shape: tuple[int, ...], *, axis_sizes: dict,
                   shard_params: bool, tp: bool) -> P:
    from nanosandbox_tpu.parallel.mesh import REGISTERED_AXES

    unknown = set(axis_sizes) - REGISTERED_AXES
    if unknown:
        # The rule table below only places registered axes, but the
        # mesh handed in must speak the same axis vocabulary or the
        # P() fallbacks would silently replicate what the caller
        # thought was sharded (jaxlint's axis-mismatch rule is the
        # static twin of this check).
        raise ValueError(
            f"mesh axis names {sorted(unknown)} are not in the "
            f"registered set {sorted(REGISTERED_AXES)}")
    ndim = len(shape)
    placement: list[Any] = [None] * ndim

    if tp and axis_sizes["model"] > 1:
        d = _tp_dim(path, ndim)
        if d is not None and shape[d] % axis_sizes["model"] == 0:
            placement[d] = "model"

    if shard_params and axis_sizes["fsdp"] > 1:
        # Shard the largest still-free, divisible dim over fsdp — except
        # embedding tables, which may only shard their ROW (vocab/position)
        # dim: a feature-dim-sharded table turns every lookup into a gather
        # whose output is C-sharded, and SPMD can only move that back to
        # the C-replicated activation layout via involuntary full
        # rematerialization (replicate-then-repartition, which the SPMD
        # partitioner warns about). Row-sharded
        # gathers lower to the clean masked-gather + psum pattern.
        allowed = ((0,) if path.endswith("wte/embedding")
                   or path.endswith("wpe/embedding") else range(ndim))
        candidates = sorted(
            (i for i in allowed
             if placement[i] is None and shape[i] % axis_sizes["fsdp"] == 0
             and shape[i] >= axis_sizes["fsdp"]),
            key=lambda i: shape[i], reverse=True)
        if candidates:
            placement[candidates[0]] = "fsdp"

    return P(*placement) if any(p is not None for p in placement) else P()


def param_shardings(mesh: Mesh, abstract_params: Any, *,
                    shard_params: bool = False, tp: bool = True) -> Any:
    """Tree of NamedSharding matching an abstract param tree."""
    axis_sizes = {name: int(size)
                  for name, size in zip(mesh.axis_names, mesh.devices.shape)}

    def one(path, leaf):
        spec = spec_for_param(_path_str(path), tuple(leaf.shape),
                              axis_sizes=axis_sizes,
                              shard_params=shard_params, tp=tp)
        return NamedSharding(mesh, spec)

    return jax.tree_util.tree_map_with_path(one, abstract_params)
