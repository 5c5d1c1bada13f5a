"""Ring attention: causal attention over a sequence-parallel mesh axis.

Long-context support beyond the reference's capability envelope (the
reference caps at block_size=1024 and has no sequence parallelism,
SURVEY.md §5 "Long-context"): the sequence dimension is sharded over the
mesh's ``seq`` axis, each device holds a T/cp chunk of Q/K/V, and K/V
chunks rotate around the ring via ``lax.ppermute`` while an online-softmax
accumulator builds the exact attention output — full attention over the
global sequence without ever materializing global K/V (or the (T, T)
score matrix) on any chip.

TPU-first shape: the per-step block matmuls are MXU-sized, the rotation is
a neighbor exchange that XLA schedules on ICI and overlaps with the block
compute, and the whole loop is unrolled at trace time (cp is a static mesh
property) so autodiff works straight through — the backward pass rotates
in the opposite direction automatically via the transpose of ppermute.

Dropout (round-5): attention-probability dropout composes with the ring
because the keep-mask is a counter-based hash of GLOBAL (q_pos, k_pos)
coordinates (ops/attention.py) — every ring step reconstructs the same
mask for the same global score element no matter which device computes
it, and the per-shard offsets ride in the (5,) seed vector. The xla and
pallas block impls derive bit-identical masks (hash_dropout_keep_mask is
the same function the kernels inline).

Composition: designed to run inside jit via jax.shard_map; everything
outside attention (MLP, layernorm, embeddings) is position-wise, so the
GSPMD partitioner handles the sharded T dimension there with no
collectives at all.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import PartitionSpec as P

NEG_INF = -1e30

__all__ = ["ring_attention", "ring_attention_sharded"]


# ---------------------------------------------------------------------------
# Per-block math: XLA einsum or the Pallas flash kernel
# ---------------------------------------------------------------------------
#
# Both ring bodies are expressed over ONE block primitive returning a
# normalized partial result + its logsumexp:
#
#   (out_j, lse_j) = attention(q_blk, k_chunk, v_chunk)   [diag or full]
#
# merged exactly across chunks via
#
#   lse   = logaddexp(lse_a, lse_b)
#   out   = out_a * exp(lse_a - lse) + out_b * exp(lse_b - lse)
#
# The 'xla' impl materializes one (B, H, Tq, Tk) f32 score block per call
# (fine at test scale); 'pallas' runs the Mosaic flash kernel per call —
# scores never leave VMEM, residuals stay O(T) per chunk — making the
# long-context configs this feature exists for actually fit in HBM
# (round-2 VERDICT weak #1). Autodiff flows through the flash custom_vjps
# (the lse cotangent folds into their backward row stat).
#
# Dropout merging note: each block's lse is the UNMASKED normalizer, and
# each block's out is (masked p) @ v / l_block. The merge rescales by
# exp(lse_j - lse_total), which telescopes to (masked p) @ v / l_total —
# exactly dropout(softmax(s_global)) @ v, because masking commutes with
# the global normalization.


def _xla_block(q, k, v, mask, sm_scale, keep=None, rate: float = 0.0):
    """(out f32, lse f32) for one block; mask True = attend; keep is an
    optional (B, H, Tq, Tk) dropout keep-mask applied to the normalized
    probabilities (with the 1/(1-rate) inverted-dropout rescale)."""
    scores = jnp.einsum("bhqd,bhkd->bhqk", q.astype(jnp.float32) * sm_scale,
                        k.astype(jnp.float32))
    if mask is not None:
        scores = jnp.where(mask, scores, NEG_INF)
    m = scores.max(axis=-1)
    p = jnp.exp(scores - m[..., None])
    l = p.sum(axis=-1)
    if keep is not None:
        p = jnp.where(keep, p * (1.0 / (1.0 - rate)), 0.0)
    out = jnp.einsum("bhqk,bhkd->bhqd", p, v.astype(jnp.float32))
    return out / jnp.maximum(l, 1e-30)[..., None], m + jnp.log(l)


def _make_block_fn(block_impl: str, sm_scale: float,
                   stat_layout: str = "replicated",
                   dropout_rate: float = 0.0,
                   hash_heads: int | None = None,
                   hash_seq_len: int | None = None):
    """Returns block(q, k, v, diag, seed) -> (out f32, lse (B, H, Tq) f32).

    diag=True applies the in-chunk causal mask (q and k share a position
    base); diag=False attends fully (the chunk is entirely in the past).
    seed: (SEED_WORDS,) uint32 with global offsets (ignored when
    dropout_rate == 0).
    """
    if block_impl == "xla":
        from nanosandbox_tpu.ops.attention import hash_dropout_keep_mask

        def block(q, k, v, diag, seed):
            mask = None
            Tq, Tk = q.shape[2], k.shape[2]
            if diag:
                mask = (lax.broadcasted_iota(jnp.int32, (Tq, Tk), 0)
                        >= lax.broadcasted_iota(jnp.int32, (Tq, Tk), 1))
            keep = None
            if dropout_rate > 0.0:
                keep = hash_dropout_keep_mask(
                    seed, q.shape[0], q.shape[1], Tq, Tk,
                    hash_heads=hash_heads, hash_seq_len=hash_seq_len,
                    rate=dropout_rate)
            return _xla_block(q, k, v, mask, sm_scale, keep, dropout_rate)
        return block
    if block_impl in ("pallas", "pallas_interpret"):
        from nanosandbox_tpu.ops.attention import (flash_attention_lse,
                                                   flash_attention_lse_dropout)

        interpret = block_impl == "pallas_interpret"

        def block(q, k, v, diag, seed):
            if dropout_rate > 0.0:
                out, lse = flash_attention_lse_dropout(
                    q, k, v, seed, diag, sm_scale, dropout_rate,
                    interpret, stat_layout, hash_heads, hash_seq_len)
            else:
                out, lse = flash_attention_lse(q, k, v, diag, sm_scale,
                                               interpret, stat_layout)
            return out.astype(jnp.float32), lse
        return block
    raise ValueError(f"unknown ring block impl: {block_impl!r}")


def _merge(carry, blk):
    out_a, lse_a = carry
    out_b, lse_b = blk
    lse = jnp.logaddexp(lse_a, lse_b)
    out = (out_a * jnp.exp(lse_a - lse)[..., None]
           + out_b * jnp.exp(lse_b - lse)[..., None])
    return out, lse


def _shard_offsets(q, dropout_rate: float, data_size: int, fsdp_size: int,
                   model_size: int = 1):
    """(b_off, h_off) — global index of this shard's first batch row and
    head, from the mesh axis indices. Only consulted when dropout is
    active (the axis names only exist under the full training mesh;
    direct shard_map harnesses without them keep working dropout-free)."""
    if dropout_rate <= 0.0:
        return jnp.uint32(0), jnp.uint32(0)
    B_loc, H_loc = q.shape[0], q.shape[1]
    b_idx = 0
    if data_size > 1 or fsdp_size > 1:
        b_idx = (lax.axis_index("data") * fsdp_size
                 + lax.axis_index("fsdp"))
    h_idx = lax.axis_index("model") if model_size > 1 else 0
    return (jnp.uint32(b_idx) * jnp.uint32(B_loc),
            jnp.uint32(h_idx) * jnp.uint32(H_loc))


def _block_seed(seed, b_off, h_off, q_off, k_off):
    """Assemble the (5,) seed vector for one block call."""
    s0 = (jnp.zeros((), jnp.uint32) if seed is None
          else jnp.asarray(seed, jnp.uint32).reshape(-1)[0])
    return jnp.stack([s0, b_off, h_off,
                      jnp.uint32(q_off), jnp.uint32(k_off)])


def ring_attention(q: jax.Array, k: jax.Array, v: jax.Array,
                   seed: Optional[jax.Array] = None, *,
                   axis_name: str, axis_size: int, causal: bool = True,
                   sm_scale: Optional[float] = None,
                   block_impl: str = "xla",
                   stat_layout: str = "replicated",
                   dropout_rate: float = 0.0,
                   hash_heads: int | None = None,
                   hash_seq_len: int | None = None,
                   data_size: int = 1, fsdp_size: int = 1,
                   model_size: int = 1) -> jax.Array:
    """Per-shard ring attention body (call under shard_map).

    q, k, v: (B, H, Tc, D) local sequence chunks; global T = Tc * axis_size,
    chunked contiguously (device i holds positions [i*Tc, (i+1)*Tc)).
    seed: (1,) uint32 per-step dropout seed (replicated; required when
    dropout_rate > 0).
    """
    if sm_scale is None:
        sm_scale = q.shape[-1] ** -0.5
    my = lax.axis_index(axis_name)
    Tc = q.shape[2]
    block = _make_block_fn(block_impl, sm_scale, stat_layout,
                           dropout_rate, hash_heads, hash_seq_len)
    b_off, h_off = _shard_offsets(q, dropout_rate, data_size, fsdp_size,
                                  model_size)
    q_off = my.astype(jnp.uint32) * jnp.uint32(Tc)

    perm = [(i, (i + 1) % axis_size) for i in range(axis_size)]

    # Step 0: the local chunk — diagonal (in-chunk causal) when causal.
    carry = block(q, k, v, causal,
                  _block_seed(seed, b_off, h_off, q_off, q_off))
    for s in range(1, axis_size):
        k = lax.ppermute(k, axis_name, perm)
        v = lax.ppermute(v, axis_name, perm)
        # After s rotations device `my` holds the chunk originating at
        # ring position (my - s) mod cp.
        src = (my - s) % axis_size
        k_off = src.astype(jnp.uint32) * jnp.uint32(Tc)
        blk_seed = _block_seed(seed, b_off, h_off, q_off, k_off)
        if causal:
            # Chunks strictly in this query's future are fully masked:
            # skip their matmuls entirely (they'd contribute exactly 0).
            # With contiguous chunking that's blocks where src > my, i.e.
            # s > my — devices still step the ring together, but a skipping
            # device does no attention FLOPs this step. (The zigzag layout
            # below equalizes per-device work; contiguous-but-skipping is
            # exact already.)
            carry = lax.cond(s <= my,
                             lambda c, kk, vv, sd: _merge(
                                 c, block(q, kk, vv, False, sd)),
                             lambda c, kk, vv, sd: c,
                             carry, k, v, blk_seed)
        else:
            carry = _merge(carry, block(q, k, v, False, blk_seed))
    out, _ = carry
    return out.astype(q.dtype)


# ---------------------------------------------------------------------------
# Zigzag (load-balanced) layout
# ---------------------------------------------------------------------------
#
# Contiguous chunking skips future blocks exactly, but unevenly: device 0
# computes 1 block while device cp-1 computes cp, so the ring's wall-clock
# is the worst device and causal skipping saves nothing. Zigzag ownership
# fixes the balance: split T into 2*cp half-chunks c_0..c_{2cp-1} and give
# device i the PAIR (c_i, c_{2cp-1-i}) — one early, one late. Then at
# every ring step each device computes exactly 2 half-blocks:
#
#   step 0 (local):  diag(q_early, k_early) + full(q_late, k_early)
#                    + diag(q_late, k_late)              [2 blocks total]
#   step s>0, src j: full(q_late, k_early_j) always, plus EITHER
#                    full(q_early, k_early_j)  when j < i
#                    OR full(q_late, k_late_j) when j > i [2 blocks total]
#
# (q_early never attends any late chunk: its global index i < cp <= every
# late index. q_late attends every early chunk: 2cp-1-i >= cp > j.)
# Same math, same comms (one k/v pair rotation per step), equal work —
# wall-clock drops from cp blocks to (cp+1) half-blocks ~= a 2x win at
# large cp.
#
# Dropout positions under zigzag are the ORIGINAL global row/col indices
# (the take() permutation is undone in the hash by per-half offsets), so
# zigzag, contiguous, and the non-ring path all agree on which global
# score elements drop for a given seed.


def zigzag_ring_attention(q: jax.Array, k: jax.Array, v: jax.Array,
                          seed: Optional[jax.Array] = None, *,
                          axis_name: str, axis_size: int,
                          sm_scale: Optional[float] = None,
                          block_impl: str = "xla",
                          stat_layout: str = "replicated",
                          dropout_rate: float = 0.0,
                          hash_heads: int | None = None,
                          hash_seq_len: int | None = None,
                          data_size: int = 1, fsdp_size: int = 1,
                          model_size: int = 1) -> jax.Array:
    """Per-shard zigzag ring body (call under shard_map; causal only).

    q, k, v: (B, H, 2h, D) where rows [:h] are this device's EARLY
    half-chunk c_i and rows [h:] its LATE half-chunk c_{2cp-1-i}
    (the layout zigzag_permutation() produces).
    """
    if sm_scale is None:
        sm_scale = q.shape[-1] ** -0.5
    B, H, T2, D = q.shape
    h = T2 // 2
    cp = axis_size
    my = lax.axis_index(axis_name)
    block = _make_block_fn(block_impl, sm_scale, stat_layout,
                           dropout_rate, hash_heads, hash_seq_len)
    b_off, h_off = _shard_offsets(q, dropout_rate, data_size, fsdp_size,
                                  model_size)
    hh = jnp.uint32(h)
    qe_off = my.astype(jnp.uint32) * hh                      # c_my
    ql_off = (jnp.uint32(2 * cp - 1) - my.astype(jnp.uint32)) * hh

    def sd(q_off, k_off):
        return _block_seed(seed, b_off, h_off, q_off, k_off)

    qe, ql = q[:, :, :h, :], q[:, :, h:, :]
    ke, kl = k[:, :, :h, :], k[:, :, h:, :]
    ve, vl = v[:, :, :h, :], v[:, :, h:, :]
    carry_e = block(qe, ke, ve, True, sd(qe_off, qe_off))
    carry_l = _merge(block(ql, ke, ve, False, sd(ql_off, qe_off)),
                     block(ql, kl, vl, True, sd(ql_off, ql_off)))

    perm = [(i, (i + 1) % cp) for i in range(cp)]
    for s in range(1, cp):
        k = lax.ppermute(k, axis_name, perm)
        v = lax.ppermute(v, axis_name, perm)
        src = (my - s) % cp
        ke_off = src.astype(jnp.uint32) * hh
        kl_off = (jnp.uint32(2 * cp - 1) - src.astype(jnp.uint32)) * hh
        ke, kl = k[:, :, :h, :], k[:, :, h:, :]
        ve, vl = v[:, :, :h, :], v[:, :, h:, :]
        carry_l = _merge(carry_l, block(ql, ke, ve, False,
                                        sd(ql_off, ke_off)))
        carry_e, carry_l = lax.cond(
            src < my,
            lambda ce, cl, ke=ke, ve=ve, ke_off=ke_off: (
                _merge(ce, block(qe, ke, ve, False, sd(qe_off, ke_off))),
                cl),
            lambda ce, cl, kl=kl, vl=vl, kl_off=kl_off: (
                ce,
                _merge(cl, block(ql, kl, vl, False, sd(ql_off, kl_off)))),
            carry_e, carry_l)

    out = jnp.concatenate([carry_e[0], carry_l[0]], axis=2)
    return out.astype(q.dtype)


def zigzag_permutation(T: int, cp: int):
    """(idx, inv): x.take(idx, axis) puts global rows into zigzag order
    (device i's contiguous shard = [c_i, c_{2cp-1-i}]); take(inv) undoes
    it. Requires T % (2*cp) == 0."""
    import numpy as np

    h = T // (2 * cp)
    idx = np.concatenate([
        np.concatenate([np.arange(i * h, (i + 1) * h),
                        np.arange((2 * cp - 1 - i) * h, (2 * cp - i) * h)])
        for i in range(cp)])
    inv = np.argsort(idx)
    return idx, inv


# Cache the shard_map closure per (mesh, params), bounded at 8 entries.
# Note a weakref cache would buy nothing here: jax interns Mesh objects
# with strong references (jax._src.mesh._mesh_object_dict), so a mesh
# key never dies. Instead the cache is small and explicitly clearable —
# parallel.mesh.set_current_mesh() calls clear_sharded_cache() whenever
# the active mesh actually changes, releasing retired closures
# deterministically in long-lived processes (Trainer re-creation, tests).


@functools.lru_cache(maxsize=8)
def _sharded_fn(mesh, causal: bool, sm_scale: float, seq_axis: str,
                zigzag: bool = False, block_impl: str = "xla",
                stat_layout: str = "replicated", dropout_rate: float = 0.0,
                hash_heads: int | None = None,
                hash_seq_len: int | None = None):
    spec = P(("data", "fsdp"), "model", seq_axis, None)
    common = dict(axis_name=seq_axis, axis_size=mesh.shape[seq_axis],
                  sm_scale=sm_scale, block_impl=block_impl,
                  stat_layout=stat_layout, dropout_rate=dropout_rate,
                  hash_heads=hash_heads, hash_seq_len=hash_seq_len,
                  data_size=mesh.shape["data"],
                  fsdp_size=mesh.shape["fsdp"],
                  model_size=mesh.shape["model"])
    if zigzag:
        body = functools.partial(zigzag_ring_attention, **common)
    else:
        body = functools.partial(ring_attention, causal=causal, **common)
    from nanosandbox_tpu.parallel.mesh import shard_map

    return shard_map(body, mesh=mesh,
                     in_specs=(spec, spec, spec, P(None)),
                     out_specs=spec, check_vma=False)


def clear_sharded_cache() -> None:
    """Drop cached shard_map closures (call when the active mesh changes)."""
    _sharded_fn.cache_clear()


def _resolve_block_impl(block_impl: str, chunk_len: int,
                        has_full_blocks: bool = True) -> str:
    """'auto' -> 'pallas' on a tpu backend (ops.attention's rule: a
    compile error propagates) when the per-call chunk is 128-lane
    aligned (the flash path's full [non-causal] blocks forbid T
    padding); 'xla' otherwise. A PINNED
    pallas impl with an unaligned chunk fails here with a ring-level
    error — previously it surfaced as a block-divisibility ValueError
    deep inside _pad_qkv that never mentioned ring_block_impl (ADVICE r3).

    has_full_blocks=False (cp == 1, the degenerate ring that wraps plain
    flash attention in its SPMD shell): the only block is the CAUSAL
    local one, which pads T freely — alignment is not required."""
    unaligned = chunk_len % 128 and has_full_blocks
    if block_impl in ("pallas", "pallas_interpret") and unaligned:
        raise ValueError(
            f"ring_block_impl={block_impl!r} requires the per-device "
            f"sequence chunk to be a multiple of 128 (got {chunk_len}): "
            "non-causal ring blocks cannot pad T. Use a block_size "
            "divisible by 128*mesh_sp, or ring_block_impl='xla'/'auto'")
    if block_impl != "auto":
        return block_impl
    if unaligned:
        return "xla"
    from nanosandbox_tpu.ops.attention import resolve_attention_impl

    return resolve_attention_impl("auto")


def ring_attention_sharded(q: jax.Array, k: jax.Array, v: jax.Array, *,
                           mesh, causal: bool = True,
                           sm_scale: Optional[float] = None,
                           seq_axis: str = "seq",
                           layout: str = "zigzag",
                           block_impl: str = "auto",
                           stat_layout: str = "replicated",
                           dropout_rate: float = 0.0,
                           dropout_seed: Optional[jax.Array] = None
                           ) -> jax.Array:
    """Ring attention over (B, H, T, D) global arrays on ``mesh``.

    Batch is sharded over (data, fsdp), heads over model, sequence over
    ``seq_axis``. With a size-1 seq axis this degenerates to one local
    flash/XLA-equivalent block — still correct, so callers don't need a
    special case.

    layout='zigzag' (default) redistributes rows so each device owns one
    early + one late half-chunk, equalizing per-device causal work (see
    zigzag_ring_attention); the redistribution is a static take() the
    partitioner lowers to an all-to-all once on entry and once on exit.
    Falls back to the contiguous layout when zigzag does not apply
    (non-causal, cp == 1, or T not divisible by 2*cp).

    block_impl selects the per-chunk math: 'auto' runs the Pallas flash
    kernel inside the ring when available (scores stay in VMEM — the
    long-context configs need this), degrading to the XLA einsum block.
    stat_layout is forwarded to the flash backward (round-4 ADVICE #3).

    dropout_rate/dropout_seed: attention-probability dropout via the
    global-position hash mask; seed is a (1,) uint32 per-step value
    (required when dropout_rate > 0). The mask is keyed on global
    coordinates, so all layouts and block impls — and the sp=1 non-ring
    kernel at the same padded length — drop the same elements.
    """
    if sm_scale is None:
        sm_scale = q.shape[-1] ** -0.5
    T = q.shape[2]
    cp = mesh.shape[seq_axis]
    if T % cp:
        raise ValueError(f"sequence length {T} not divisible by seq axis {cp}")
    if layout not in ("zigzag", "contiguous"):
        raise ValueError(f"unknown ring layout: {layout!r}")
    if dropout_rate > 0.0 and dropout_seed is None:
        raise ValueError("ring attention dropout needs a per-step "
                         "dropout_seed ((1,) uint32) when dropout_rate > 0")
    use_zigzag = (layout == "zigzag" and causal and cp > 1
                  and T % (2 * cp) == 0)
    chunk = T // (2 * cp) if use_zigzag else T // cp
    impl = _resolve_block_impl(block_impl, chunk,
                               has_full_blocks=cp > 1 or not causal)
    seed = (jnp.zeros((1,), jnp.uint32) if dropout_seed is None
            else jnp.asarray(dropout_seed, jnp.uint32).reshape((1,)))
    hash_heads = q.shape[1]  # global head count (sharded over 'model')
    fn_args = dict(stat_layout=stat_layout, dropout_rate=float(dropout_rate),
                   hash_heads=hash_heads, hash_seq_len=T)
    if not use_zigzag:
        return _sharded_fn(mesh, causal, float(sm_scale), seq_axis,
                           block_impl=impl, **fn_args)(q, k, v, seed)
    idx, inv = zigzag_permutation(T, cp)
    qz, kz, vz = (jnp.take(x, idx, axis=2) for x in (q, k, v))
    out = _sharded_fn(mesh, causal, float(sm_scale), seq_axis,
                      zigzag=True, block_impl=impl, **fn_args)(qz, kz, vz,
                                                               seed)
    return jnp.take(out, inv, axis=2)
