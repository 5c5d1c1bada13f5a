"""Device mesh construction and batch sharding.

Axes: ``data`` (pure data parallel), ``fsdp`` (data parallel + parameter
sharding — ZeRO-3 style), ``seq`` (sequence/context parallel — ring
attention over long sequences, ops/ring_attention.py), ``model`` (tensor
parallel). The batch dim is sharded over (data, fsdp) jointly and the
sequence dim over ``seq``; params are replicated over ``data``/``seq``,
sharded over ``fsdp`` when cfg.shard_params, and sharded over ``model``
per the TP rules in sharding.py.

Replaces the reference's torchrun process-group topology (SURVEY.md §2.5):
workflow A (1 pod × 3 GPU) maps to a single-host mesh over local devices;
workflow B (3 pods × 1 GPU) maps to the same mesh spanning hosts after
jax.distributed.initialize. The ``seq`` and ``model`` axes go beyond the
reference's DDP-only envelope.
"""

from __future__ import annotations

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

AXES = ("data", "fsdp", "seq", "model")

# The axis-name registry: every PartitionSpec in the stack may only name
# these axes. jaxlint's `axis-mismatch` rule enforces the same set
# statically (analysis/rules_sharding.py mirrors it — jax-free — and a
# test pins the two in sync), and sharding.spec_for_param validates it
# at runtime.
REGISTERED_AXES = frozenset(AXES)

_CURRENT_MESH: Mesh | None = None


def axis_sizes(mesh: Mesh) -> dict[str, int]:
    """{axis name: size} in mesh order — the shape dict shardcheck's
    replica-group attribution and the budget files key on."""
    return {name: int(size)
            for name, size in zip(mesh.axis_names, mesh.devices.shape)}


def replicated_abstract(mesh: Mesh, tree):
    """Abstract twin of a pytree with every leaf REPLICATED over the
    mesh — the lowering helper for AOT-analyzing today's single-chip
    serve programs under a declared mesh (shardcheck): lowering with
    these shardings makes the SPMD partitioner run for real, so any
    collective it inserts is by definition accidental."""
    import jax

    rep = NamedSharding(mesh, P())
    return jax.tree.map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=rep),
        tree)


def make_mesh(mesh_dp: int = -1, mesh_fsdp: int = 1, mesh_tp: int = 1,
              mesh_sp: int = 1, devices: list | None = None) -> Mesh:
    """Build a (data, fsdp, seq, model) mesh over all devices.

    mesh_dp = -1 means "all devices not claimed by fsdp/seq/model". Axis
    order puts ``model`` innermost so TP collectives ride the fastest ICI
    links, then ``seq`` (ring neighbor exchanges), then ``fsdp``, then
    ``data`` outermost (its allreduce tolerates DCN).
    """
    devices = devices if devices is not None else jax.devices()
    n = len(devices)
    if mesh_fsdp <= 0 or mesh_tp <= 0 or mesh_sp <= 0:
        raise ValueError("mesh_fsdp, mesh_tp, and mesh_sp must be positive")
    claimed = mesh_fsdp * mesh_tp * mesh_sp
    if mesh_dp == -1:
        if n % claimed:
            raise ValueError(
                f"{n} devices not divisible by fsdp*sp*tp={claimed}")
        mesh_dp = n // claimed
    if mesh_dp * claimed != n:
        raise ValueError(
            f"mesh {mesh_dp}x{mesh_fsdp}x{mesh_sp}x{mesh_tp} != {n} devices")
    dev_array = np.asarray(devices).reshape(mesh_dp, mesh_fsdp, mesh_sp,
                                            mesh_tp)
    return Mesh(dev_array, AXES)


def make_hybrid_mesh(mesh_dp: int = -1, mesh_fsdp: int = 1,
                     mesh_tp: int = 1, mesh_sp: int = 1, *,
                     num_slices: int = -1,
                     devices: list | None = None) -> Mesh:
    """(data, fsdp, seq, model) mesh over a MULTI-SLICE topology: the
    ``data`` axis spans slices (its allreduce rides DCN, the only
    cross-slice fabric), while fsdp/seq/model are constrained to live
    INSIDE one slice so their chattier collectives (reduce-scatter /
    all-gather per step, ring ppermute per layer) stay on ICI — the
    placement rule docs/collectives.md teaches, now enforced by
    construction (round-4 VERDICT missing #4: the doc existed, the
    constructor didn't).

    num_slices = -1 groups devices by their ``slice_index`` attribute
    (real multi-slice TPU); an explicit count splits the device list into
    that many contiguous groups (the no-hardware test path — virtual CPU
    devices carry no slice ids). Slice grouping is VALIDATED: every
    (fsdp, seq, model) block must fall entirely within one slice, and
    the dp axis is laid out slice-major so adjacent dp indices within a
    slice stay on ICI.
    """
    if mesh_fsdp <= 0 or mesh_tp <= 0 or mesh_sp <= 0:
        raise ValueError("mesh_fsdp, mesh_tp, and mesh_sp must be positive")
    devices = list(devices if devices is not None else jax.devices())
    n = len(devices)
    if num_slices == -1:
        ids = {getattr(d, "slice_index", 0) for d in devices}
        num_slices = len(ids)
        groups = [[d for d in devices if getattr(d, "slice_index", 0) == i]
                  for i in sorted(ids)]
    else:
        if num_slices <= 0 or n % num_slices:
            raise ValueError(
                f"{n} devices cannot split into {num_slices} slices")
        per = n // num_slices
        groups = [devices[i * per:(i + 1) * per] for i in range(num_slices)]
    per_slice = len(groups[0])
    if any(len(g) != per_slice for g in groups):
        raise ValueError(
            f"unequal slice sizes {[len(g) for g in groups]}: a mesh "
            "needs homogeneous slices")
    claimed = mesh_fsdp * mesh_tp * mesh_sp
    if per_slice % claimed:
        raise ValueError(
            f"fsdp*sp*tp={claimed} must divide the per-slice device count "
            f"{per_slice}: those axes' collectives must stay on ICI — "
            "only the data axis may span slices (DCN)")
    dp_per_slice = per_slice // claimed
    dp = num_slices * dp_per_slice
    if mesh_dp not in (-1, dp):
        raise ValueError(
            f"mesh_dp={mesh_dp} inconsistent with {num_slices} slices x "
            f"{dp_per_slice} in-slice dp (= {dp})")
    # Slice-major dp: dev_array[s * dp_per_slice + i] is slice s's i-th
    # (fsdp, seq, model) block, so dp neighbors within a slice are on ICI
    # and only the slice-crossing hop pays DCN.
    dev_array = np.stack([
        np.asarray(g).reshape(dp_per_slice, mesh_fsdp, mesh_sp, mesh_tp)
        for g in groups]).reshape(dp, mesh_fsdp, mesh_sp, mesh_tp)
    return Mesh(dev_array, AXES)


def shard_map(f, *, mesh: Mesh, in_specs, out_specs, check_vma: bool = True):
    """``jax.shard_map``. Every shard_map in the package goes through
    here, so the package has one place that names the binding."""
    return jax.shard_map(f, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, check_vma=check_vma)


def batch_sharding(mesh: Mesh) -> NamedSharding:
    """Batch dim over data+fsdp jointly; sequence dim over seq."""
    return NamedSharding(mesh, P(("data", "fsdp"), "seq"))


def replicated(mesh: Mesh) -> NamedSharding:
    return NamedSharding(mesh, P())


def set_current_mesh(mesh: Mesh | None) -> None:
    """Record the active training mesh so mesh-aware ops (ring attention)
    can be reached from inside model code without threading the mesh
    through every module signature.

    Switching to a DIFFERENT mesh drops ring attention's cached shard_map
    closures: jax interns Mesh objects forever, so this hook is the
    deterministic release point for retired-mesh closures in long-lived
    processes (ADVICE.md round-1 item 5)."""
    global _CURRENT_MESH
    if mesh is not _CURRENT_MESH and _CURRENT_MESH is not None:
        from nanosandbox_tpu.ops.ring_attention import clear_sharded_cache

        clear_sharded_cache()
    _CURRENT_MESH = mesh


def current_mesh() -> Mesh | None:
    return _CURRENT_MESH
