"""Causal self-attention: Pallas flash-attention (fwd + bwd) for TPU + XLA fallback.

The reference's training core (karpathy/nanoGPT, exercised via
/root/reference/notebooks/colab_nanoGPT_companion.ipynb:71-78) relies on
torch scaled_dot_product_attention/CUDA flash kernels. The TPU-native
equivalent is a Pallas kernel compiled by Mosaic: the forward pass is an
online-softmax (flash) kernel that never materializes the (T, T) score
matrix in HBM, tiled to the MXU (128-lane blocks, f32 accumulation).

The backward pass is two Pallas kernels under jax.custom_vjp sharing the
forward's per-row logsumexp L and the precomputed row term
Drow = rowsum(dO * O): one computes dQ (parallel over query blocks), the
other dK/dV (parallel over key blocks); both recompute P = exp(S - L)
block-by-block instead of saving the (T, T) probability matrix, and both
skip fully-masked blocks at the causal frontier.

Two HBM interfaces, one set of tile functions (_fwd_tile, _bwd_tile,
_expand_stat_tile):

  * flash_attention_qkv: the MODEL'S OWN layout. In qkv (B, T, 3C) as
    c_attn emits it, out o (B, T, C) as c_proj consumes it, gradient one
    dqkv (B, T, 3C); the logsumexp goes from forward to backward compact,
    (B, H, 1, T) float32. A 128-lane block of the last dimension is a
    group of heads (two at D = 64), so no transpose, pad, slice or cast
    surrounds the kernels. Taken by training / eval on one device when
    the heads tile the lanes (attention_layout decides, from shapes and
    mesh); see the section heading further down.
  * flash_attention / flash_attention_dropout / flash_attention_lse*:
    q, k, v are (B, H, T, D), flattened to (B*H, T, D). D (head_dim) is
    padded to a multiple of 128 lanes (64 runs unpadded) and T to a
    multiple of the 128-row block inside the Pallas path. The entry ring
    attention composes, and the one for every shape the first cannot take
    (GPT-2 XL's 25 heads, D = 32, T off the 128 grid, a mesh).

Beside them, on the projections' own (B, T, heads*D) layout too:
flash_attention_gqa (grouped KV heads, an optional window: the afmoe family;
forward and ONE backward kernel) and flash_attention_mla (latent attention:
a query / key head of 128 content + 64 rotary lanes whose rotary key is ONE
head that all query heads read, a value head of 128; the deepseek_v3 family;
forward and ONE backward kernel built from the same tile functions with a
second (q, k) pair). Each section's heading says what its kernels hold.

Mosaic layout note, (B, H, T, D) entry: per-row softmax stats (L, Drow)
leave its forward lane-REPLICATED as (..., T, 128) arrays — Mosaic
requires the last two block dims of every operand to tile onto (8, 128)
sublane x lane registers, so a (block_q, 1) column block cannot lower;
broadcasting each row stat across the 128-lane minor dim (the layout
jax's own pallas.ops.tpu.flash_attention uses) makes every BlockSpec
legal at the cost of a 128x blowup in HBM. The (B, T, 3C) entry turns the
column into a lane-dense (1, block_q) row in-register instead
(_stat_column_to_row) and never writes the replicated form.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _tpu_params(*semantics: str):
    """Mosaic grid-dimension semantics: 'parallel' dims may be executed in
    any order / across cores, letting the pipeline prefetch blocks across
    grid steps instead of serializing them.

    vmem_limit_bytes raises Mosaic's default (~16 MB) VMEM budget check to
    100 MB of the chip's 128: the backward kernels stream q/do/o as
    full-T blocks, whose footprint scales with sequence length — at the
    default budget the backward stops COMPILING between T=8192 and 16384
    (and the 'replicated' stat layout already fails at 8192 with 12
    heads). The limit is a constraint check, not an allocation: small
    kernels are unaffected (124M bench measured identical), and with it
    the single-shard envelope extends through T=32768 (r5, v5e)."""
    return pltpu.CompilerParams(dimension_semantics=semantics,
                                vmem_limit_bytes=VMEM_LIMIT_BYTES)


VMEM_LIMIT_BYTES = 100 * 1024 * 1024

NEG_INF = -1e30
LANES = 128  # minor-dim register width; row stats are replicated across it

__all__ = ["causal_attention", "causal_attention_qkv", "attention_layout",
           "causal_attention_gqa", "xla_attention", "flash_attention",
           "flash_attention_dropout", "flash_attention_lse",
           "flash_attention_lse_dropout", "flash_attention_qkv",
           "flash_attention_gqa", "gqa_layout_supported", "gqa_route",
           "causal_attention_mla", "flash_attention_mla",
           "mla_layout_supported", "mla_route",
           "hash_dropout_keep_mask", "qk_prep", "qk_rotary",
           "qkv_layout_supported", "resolve_attention_impl",
           "resolve_gqa_bwd", "resolve_gqa_impl", "rotary_table"]


# ---------------------------------------------------------------------------
# In-kernel dropout mask
# ---------------------------------------------------------------------------
#
# Attention-probability dropout needs the SAME keep-mask in the forward and
# both backward kernels (they recompute P block-by-block instead of saving
# it). pltpu.prng_* can't provide that — reseeding per tile would work on
# hardware but the interpreter returns zero bits, so the CPU test tier
# could never exercise the masked math. Instead the mask is a pure
# counter-based hash (murmur3's fmix32 finalizer) over the GLOBAL
# (q_pos, k_pos) element index, keyed by a per-call seed mixed with the
# batch*head grid index: any (fwd, bwd-dq, bwd-dkv) kernel visiting the
# same score element derives the same bit from plain uint32 VPU ops, in
# compiled and interpret mode alike. ~6 integer ops per element, noise
# against the two MXU matmuls that touch the same tile.

_GOLDEN = 0x9E3779B9  # 2^32 / golden ratio; decorrelates the bh stream


def _fmix32(h: jax.Array) -> jax.Array:
    """murmur3 32-bit finalizer — a cheap bijective avalanche on uint32."""
    h = h ^ (h >> 16)
    h = h * jnp.uint32(0x85EBCA6B)
    h = h ^ (h >> 13)
    h = h * jnp.uint32(0xC2B2AE35)
    h = h ^ (h >> 16)
    return h


# The seed operand is a (5,) uint32 vector so the mask can be keyed on
# GLOBAL coordinates under sequence/tensor parallelism (ring attention —
# each ring step sees a different slice of the global score matrix, and
# sharded batches/heads must draw distinct streams):
#   [0] per-call seed   [1] global batch offset of row 0
#   [2] global head offset of head 0   [3] global q position of row 0
#   [4] global k position of col 0
# All zeros for the plain (non-ring) path, which makes the stream id
# reduce to the local bh index — bit-identical to the pre-ring masks.
SEED_WORDS = 5


def _dropout_tile_seed(seed_ref, bh, local_heads: int,
                       hash_heads: int) -> jax.Array:
    """Per-(call, GLOBAL batch*head) uint32 stream key. local_heads is the
    head count of this kernel call's arrays; hash_heads the global head
    count the stream id is linearized over (equal when not head-sharded)."""
    bh = bh.astype(jnp.uint32)
    b = bh // jnp.uint32(local_heads) + seed_ref[1]
    h = bh % jnp.uint32(local_heads) + seed_ref[2]
    gbh = b * jnp.uint32(hash_heads) + h
    return _fmix32(seed_ref[0] ^ (gbh * jnp.uint32(_GOLDEN)))


def _dropout_keep(mix: jax.Array, q_start, k_start, shape: tuple[int, int],
                  seq_len: int, rate: float) -> jax.Array:
    """Boolean keep-mask for the (block_q, block_k) tile whose top-left
    element is (q_start, k_start) in the padded (seq_len, seq_len) score
    matrix. Element identity is positional, so every kernel agrees no
    matter which grid axis it iterates."""
    q_pos = q_start + lax.broadcasted_iota(jnp.int32, shape, 0)
    k_pos = k_start + lax.broadcasted_iota(jnp.int32, shape, 1)
    idx = (q_pos.astype(jnp.uint32) * jnp.uint32(seq_len)
           + k_pos.astype(jnp.uint32))
    threshold = jnp.uint32(min(int(round(rate * 2**32)), 2**32 - 1))
    return _fmix32(idx ^ mix) >= threshold


def _apply_dropout(x: jax.Array, keep: jax.Array, rate: float) -> jax.Array:
    """Inverted dropout: zero masked elements, rescale kept ones by
    1/(1-rate). Single-sourced so the fwd and both bwd kernels can never
    drift in how kept elements are scaled."""
    return jnp.where(keep, x * (1.0 / (1.0 - rate)), 0.0)


# ---------------------------------------------------------------------------
# XLA reference implementation (also the backward recompute path)
# ---------------------------------------------------------------------------

def xla_attention(q: jax.Array, k: jax.Array, v: jax.Array,
                  *, causal: bool = True, sm_scale: float | None = None,
                  dropout_rate: float = 0.0,
                  dropout_rng: jax.Array | None = None,
                  window: int | None = None) -> jax.Array:
    """Plain attention; XLA fuses this adequately for short-T and CPU tests.

    window (causal only): key j is visible to query i iff j <= i and
    i - j < window; None is full causal attention.

    dropout_rate/dropout_rng apply inverted dropout to the softmax weights
    (nanoGPT's attn_dropout; the reference model regularizes attention
    probabilities as well as residuals).
    """
    if sm_scale is None:
        sm_scale = q.shape[-1] ** -0.5
    q32 = q.astype(jnp.float32) * sm_scale
    s = jnp.einsum("bhqd,bhkd->bhqk", q32, k.astype(jnp.float32))
    if causal:
        T = q.shape[2]
        mask = jnp.tril(jnp.ones((T, T), dtype=bool))
        if window is not None:
            mask = mask & ~jnp.tril(jnp.ones((T, T), dtype=bool), -window)
        s = jnp.where(mask[None, None, :, :], s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    if dropout_rate > 0.0 and dropout_rng is not None:
        keep = jax.random.bernoulli(dropout_rng, 1.0 - dropout_rate, p.shape)
        p = jnp.where(keep, p / (1.0 - dropout_rate), 0.0)
    o = jnp.einsum("bhqk,bhkd->bhqd", p, v.astype(jnp.float32))
    # Saveable under remat_policy='save_attention' (the AD backward of
    # this einsum needs p and v, not o, so saving o prunes the p@v
    # forward recompute — the one piece of XLA-path attention a
    # save-the-output policy can elide).
    from jax.ad_checkpoint import checkpoint_name

    return checkpoint_name(o.astype(q.dtype), "attn_out")


# ---------------------------------------------------------------------------
# Pallas flash forward
# ---------------------------------------------------------------------------

def _scores(q, k, qk2=None):
    """q k^T (bq, bk) in float32, unscaled; with ``qk2`` = (q2, k2), a second
    pair of another width, plus q2 k2^T: ONE score from two contractions
    summed in float32 (the latent kernels' 128 content lanes and 64 rotary
    lanes; the rotary key is one head that every query head reads)."""
    s = lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                        preferred_element_type=jnp.float32)
    if qk2 is not None:
        s = s + lax.dot_general(qk2[0], qk2[1], (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32)
    return s


def _fwd_tile(q, k, v, carry, *, sm_scale: float, mask=None, keep=None,
              dropout_rate: float = 0.0, qk2=None):
    """One (block_q, block_k) step of the online softmax for ONE head: the
    tile mathematics both forward kernels share (the (B*H, T, D) kernel
    and the (B, T, heads*D) head-group kernel).

    q (bq, W), k / v (bk, W) in their storage dtype; carry = (acc (bq, W)
    f32, m (bq, 1), l (bq, 1)); mask / keep are (bq, bk) booleans (causal
    frontier, dropout keep-mask) or None. W is the head size for the
    per-head kernel; the head-group kernel passes 128-lane tiles with the
    other head's q lanes zeroed, which contracts to the same scores.

    MXU inputs stay in their storage dtype (bf16 on TPU) with float32
    ACCUMULATION: pre-casting to f32 would run the matmuls at the MXU's
    f32 rate, ~8x slower. Scores are scaled in f32 after the dot instead
    of scaling q (same math, better bf16 numerics)."""
    acc, m, l = carry
    s = _scores(q, k, qk2) * sm_scale                        # (bq, bk)
    if mask is not None:
        s = jnp.where(mask, s, NEG_INF)
    m_new = jnp.maximum(m, jnp.max(s, axis=1, keepdims=True))  # (bq, 1)
    p = jnp.exp(s - m_new)
    alpha = jnp.exp(m - m_new)
    # The softmax normalizer l accumulates UNMASKED p: dropout applies
    # to the normalized probabilities (o = dropout(softmax(s)) @ v), and
    # masking commutes with the final per-row division by l, so masking
    # only the p@v accumulation implements exactly that.
    l_new = alpha * l + jnp.sum(p, axis=1, keepdims=True)
    p_v = p if keep is None else _apply_dropout(p, keep, dropout_rate)
    acc_new = acc * alpha + lax.dot_general(
        p_v.astype(v.dtype), v, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)
    return acc_new, m_new, l_new


def _causal_kb_range(qi, block_q: int, block_k: int):
    """(fully-unmasked, total) key-block counts for causal q block qi.
    Only k blocks at or before the q block's frontier are walked, and the
    walk is split at the diagonal: blocks strictly below it need no
    causal mask, so the iota/compare/select VPU work (a real cost: the
    per-tile matmuls are tiny at head_dim 64, leaving the kernel
    VPU-bound) only runs on the block(s) the frontier crosses."""
    return (lax.div(qi * block_q, block_k),
            lax.div((qi + 1) * block_q + block_k - 1, block_k))


def _flash_fwd_kernel(seed_ref, q_ref, k_ref, v_ref, o_ref, lse_ref, *,
                      block_q: int, block_k: int, sm_scale: float,
                      causal: bool, dropout_rate: float = 0.0,
                      local_heads: int = 1, hash_heads: int = 1,
                      hash_seq_len: int = 0):
    qi = pl.program_id(1)
    if dropout_rate > 0.0:
        mix = _dropout_tile_seed(seed_ref, pl.program_id(0),
                                 local_heads, hash_heads)
        q_off = seed_ref[3].astype(jnp.int32)
        k_off = seed_ref[4].astype(jnp.int32)
    q = q_ref[0]                                           # (block_q, D)
    seq_len = k_ref.shape[1]
    head_dim = q_ref.shape[2]

    if causal:
        num_kb_inner, num_kb = _causal_kb_range(qi, block_q, block_k)
    else:
        num_kb = seq_len // block_k
        num_kb_inner = num_kb

    q_pos = qi * block_q + lax.broadcasted_iota(jnp.int32, (block_q, block_k), 0)

    def body(j, carry, *, masked: bool):
        k = k_ref[0, pl.ds(j * block_k, block_k), :]
        v = v_ref[0, pl.ds(j * block_k, block_k), :]
        mask = keep = None
        if masked:
            k_pos = j * block_k + lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 1)
            mask = q_pos >= k_pos
        if dropout_rate > 0.0:
            keep = _dropout_keep(mix, q_off + qi * block_q,
                                 k_off + j * block_k,
                                 (block_q, block_k), hash_seq_len,
                                 dropout_rate)
        return _fwd_tile(q, k, v, carry, sm_scale=sm_scale, mask=mask,
                         keep=keep, dropout_rate=dropout_rate)

    init = (
        jnp.zeros((block_q, head_dim), jnp.float32),
        jnp.full((block_q, 1), NEG_INF, jnp.float32),
        jnp.zeros((block_q, 1), jnp.float32),
    )
    carry = lax.fori_loop(0, num_kb_inner,
                          functools.partial(body, masked=False), init)
    # For non-causal calls num_kb_inner == num_kb and this loop is empty.
    acc, m, l = lax.fori_loop(num_kb_inner, num_kb,
                              functools.partial(body, masked=True), carry)
    o_ref[0] = (acc / l).astype(o_ref.dtype)
    # Per-row logsumexp, the softmax residual the flash backward needs
    # (recomputing p = exp(s - L) block-by-block instead of saving (T, T)),
    # written lane-replicated: (block_q, 1) broadcast across the 128-lane
    # minor dim so the output block tiles legally onto Mosaic registers.
    lse_ref[0] = jnp.broadcast_to(m + jnp.log(l), (block_q, LANES))


DEFAULT_BLOCK = 512  # measured on v5e: 512x512 runs ~2.3-3x faster than
# 128x128 (fewer grid programs; the MXU pipeline amortizes over bigger
# score tiles) while a 512x512 f32 score tile is only 1 MiB of VMEM.

# The dKV kernel's own best blocking differs from dQ's: it is parallel
# over KEY blocks with an inner loop over q blocks, so a WIDE block_k
# (fewer grid programs, each amortizing the q/do/o streams) wins — r5
# microbench on v5e at (16, 12, 1024, 64): dkv 512x1024 = 1.26 ms vs
# 512x512 = 1.37 ms, and the combined fwd+bwd layer drops ~25% once the
# two backward kernels stop sharing one compromise blocking.
DKV_BLOCK_K = 1024


def _clamp_blocks(T: int, block_q: int, block_k: int) -> tuple[int, int]:
    """Pick per-call block sizes: the largest value <= the requested block
    that DIVIDES the 128-padded sequence length. Dividing (not just
    clamping) matters for T between block multiples — e.g. T=640 must use
    128-row blocks, not pad up to 1024 and burn +60% attention FLOPs on
    pad rows (and it keeps non-causal calls, which forbid T padding,
    working for every 128-multiple T)."""
    Tp128 = -(-T // LANES) * LANES

    def pick(b: int) -> int:
        # Round a caller-supplied block down to the LANES grid first: the
        # divisor search below steps by LANES and only terminates from a
        # LANES multiple (e.g. b=200 would step 200,72,... past 128 and
        # never divide Tp128).
        b = max(LANES, b // LANES * LANES)
        b = min(b, Tp128)
        while Tp128 % b:
            b -= LANES  # terminates at 128, which always divides Tp128
        return b

    return pick(block_q), pick(block_k)


def _pad_qkv(q, k, v, block_q, block_k, causal):
    """Pad head_dim to the 128-lane tile and T to the block size; returns
    padded (B*H, Tp, Dp)-flattened tensors plus the pad bookkeeping."""
    if block_q % 8 or block_k % LANES:
        raise ValueError(
            f"block_q must be a multiple of 8 and block_k of {LANES} "
            f"(got {block_q}, {block_k}): Mosaic tiles blocks onto "
            f"(8, 128) sublane*lane registers")
    B, H, T, D = q.shape
    # Head-dim padding: Mosaic's (8, 128) register tiling accepts a
    # 64-lane minor dim directly (verified compiled + correct on v5e),
    # so GPT-2's D=64 runs UNPADDED — the old unconditional pad-to-128
    # doubled every q/k/v/o/do stream and grad write in HBM. Only the
    # VERIFIED cases skip padding (64 exactly, or full 128-lane
    # multiples); other dims — including 128k+64 shapes like 192, a
    # partial-trailing-tile case never exercised — keep the proven
    # pad-to-128-multiple path.
    pad_D = 0 if (D == 64 or D % 128 == 0) else (-D) % 128
    if pad_D:
        pads = [(0, 0), (0, 0), (0, 0), (0, pad_D)]
        q, k, v = (jnp.pad(x, pads) for x in (q, k, v))
    pad_T = (-T) % max(block_q, block_k)
    if pad_T:
        # Padded key rows would attract softmax mass for padded query rows
        # only; padded queries are sliced off after the kernel, and causal
        # masking keeps real queries from seeing padded (future) keys.
        pads = [(0, 0), (0, 0), (0, pad_T), (0, 0)]
        q, k, v = (jnp.pad(x, pads) for x in (q, k, v))
        if not causal:
            raise ValueError("non-causal pallas path requires T % block == 0")
    Tp, Dp = q.shape[2], q.shape[3]
    flat = lambda x: x.reshape(B * H, Tp, Dp)
    return flat(q), flat(k), flat(v), (B, H, T, D, Tp, Dp, pad_T, pad_D)


def _dropout_seed_arg(seed, dropout_rate: float = 0.0) -> jax.Array:
    """Normalize the optional dropout seed to the (SEED_WORDS,) uint32
    SMEM operand every kernel takes (ignored when dropout_rate == 0).
    Accepts a scalar/(1,) seed (offsets zero — the non-ring path) or a
    full (SEED_WORDS,) vector (ring callers supply global offsets)."""
    if seed is None:
        if dropout_rate > 0.0:
            # A silent constant seed would drop the SAME attention entries
            # every step — a fixed sparsity pattern, not regularization.
            raise ValueError(
                "flash attention dropout needs a per-step seed ((1,) "
                "uint32) when dropout_rate > 0")
        return jnp.zeros((SEED_WORDS,), jnp.uint32)
    seed = jnp.asarray(seed, jnp.uint32).reshape(-1)
    if seed.shape[0] == SEED_WORDS:
        return seed
    return jnp.concatenate(
        [seed[:1], jnp.zeros((SEED_WORDS - 1,), jnp.uint32)])


def _check_dropout_seq_len(dropout_rate: float, padded_len: int) -> None:
    """The keep-mask hashes q_pos * seq_len + k_pos in uint32, which is
    collision-free only while seq_len**2 <= 2**32; beyond that, rows
    would silently share masks (correlated dropout)."""
    if dropout_rate > 0.0 and padded_len > 65536:
        raise ValueError(
            f"flash attention dropout supports sequence lengths up to "
            f"65536 (padded {padded_len}): the positional mask hash "
            "would wrap uint32 and correlate rows")


def _pallas_flash_fwd(q: jax.Array, k: jax.Array, v: jax.Array, *,
                      causal: bool, sm_scale: float,
                      block_q: int = DEFAULT_BLOCK,
                      block_k: int = DEFAULT_BLOCK,
                      interpret: bool = False,
                      dropout_rate: float = 0.0, seed=None,
                      hash_heads: int | None = None,
                      hash_seq_len: int | None = None):
    """Returns (out, lse) — lse is the lane-replicated per-row logsumexp
    with PADDED shape (B*H, Tp, 128); the bwd kernels consume it as-is.

    hash_heads / hash_seq_len: GLOBAL head count and sequence length the
    dropout mask hash is keyed over (ring callers pass the global values
    with per-shard offsets in the seed vector); default local/padded."""
    block_q, block_k = _clamp_blocks(q.shape[2], block_q, block_k)
    qf, kf, vf, (B, H, T, D, Tp, Dp, pad_T, pad_D) = _pad_qkv(
        q, k, v, block_q, block_k, causal)

    hash_heads = hash_heads if hash_heads is not None else H
    hash_seq_len = hash_seq_len if hash_seq_len is not None else Tp
    _check_dropout_seq_len(dropout_rate, hash_seq_len)
    grid = (B * H, Tp // block_q)
    kernel = functools.partial(
        _flash_fwd_kernel, block_q=block_q, block_k=block_k,
        sm_scale=sm_scale, causal=causal, dropout_rate=dropout_rate,
        local_heads=H, hash_heads=hash_heads, hash_seq_len=hash_seq_len)
    out, lse = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.SMEM),
            pl.BlockSpec((1, block_q, Dp), lambda b, i: (b, i, 0)),
            pl.BlockSpec((1, Tp, Dp), lambda b, i: (b, 0, 0)),
            pl.BlockSpec((1, Tp, Dp), lambda b, i: (b, 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, block_q, Dp), lambda b, i: (b, i, 0)),
            pl.BlockSpec((1, block_q, LANES), lambda b, i: (b, i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((B * H, Tp, Dp), q.dtype),
            jax.ShapeDtypeStruct((B * H, Tp, LANES), jnp.float32),
        ],
        compiler_params=None if interpret else _tpu_params(
            "parallel", "parallel"),
        interpret=interpret,
    )(_dropout_seed_arg(seed, dropout_rate), qf, kf, vf)
    out = out.reshape(B, H, Tp, Dp)[:, :, :T, :D]
    return out, lse


# ---------------------------------------------------------------------------
# Pallas flash backward
# ---------------------------------------------------------------------------
#
# Stat-operand layouts (--attention_stat_layout):
#   'replicated' (default): per-row stats broadcast across the 128-lane
#     minor dim, (B*H, Tp, LANES) f32 — every BlockSpec trivially legal,
#     but the backward streams ~128x more stat bytes from HBM than the
#     information content (~400 MB/layer/step at the 124M bench shape).
#   'compact': the (Tp,) stat vector reshaped to (Tp//LANES, LANES) rows —
#     dense in HBM (the minor dim carries REAL data, so XLA's (8, 128)
#     tiling pads nothing). The catch: inside the kernel the (rows, LANES)
#     tile must become a (block_q, 1) column, a cross-lane -> sublane
#     relayout Mosaic cannot express as a plain reshape. _expand_stat_tile
#     does it with a tiny selection matmul + masked rowsum — ops that
#     always lower (MXU + VPU), no relayout primitive needed.
#
# Standard flash-attention backward split into two kernels sharing the
# forward's per-row logsumexp L and the precomputed row term
# Drow = rowsum(dO * O):
#   dQ_i  = sm_scale * sum_j dS_ij @ K_j
#   dK_j  = sm_scale * sum_i dS_ij^T @ Q_i
#   dV_j  = sum_i P_ij^T @ dO_i
# with P = exp(S*scale - L) recomputed per block (never materialized at
# (T, T)), dP = dO @ V^T, dS = P * (dP - Drow). The causal frontier skips
# fully-masked blocks, halving the work the XLA-recompute backward did.

def _expand_stat_tile(tile: jax.Array, row_offset, block_q: int) -> jax.Array:
    """FULL compact stat tile (R, LANES) -> the (block_q, 1) column for
    global rows [row_offset*LANES, row_offset*LANES + block_q), where
    tile[r, c] holds the stat for global row r*LANES + c.

    The needed cross-lane -> sublane relayout is built from ops Mosaic
    always lowers: a (block_q, R) 0/1 selection matmul (which also absorbs
    the q-block's row offset — Mosaic forbids sub-8-sublane stat blocks
    AND dynamic sublane slicing at unaligned offsets, so selecting rows
    via the contraction sidesteps both) replicates each stat row across
    the 128 q-rows it covers, then a masked rowsum picks each q-row's own
    lane. ~block_q*R MACs + block_q*LANES VPU ops — noise against the
    (bq, bk) @ (bk, D) main matmuls. row_offset may be a traced scalar
    (it is grid-position-dependent)."""
    R, lanes = tile.shape
    sel = (lax.broadcasted_iota(jnp.int32, (block_q, R), 0) // lanes
           + row_offset
           == lax.broadcasted_iota(jnp.int32, (block_q, R), 1))
    # HIGHEST precision: each output element sums exactly ONE tile value,
    # so full-f32 passes make the expansion bit-exact (default MXU f32
    # precision would round lse to ~bf16 and visibly perturb p = exp(s-L));
    # the matmul is tiny, the extra passes are free.
    spread = lax.dot_general(sel.astype(jnp.float32), tile,
                             (((1,), (0,)), ((), ())),
                             preferred_element_type=jnp.float32,
                             precision=lax.Precision.HIGHEST)  # (bq, lanes)
    own_lane = (lax.broadcasted_iota(jnp.int32, (block_q, lanes), 1)
                == lax.broadcasted_iota(jnp.int32, (block_q, lanes), 0)
                % lanes)
    return jnp.sum(jnp.where(own_lane, spread, 0.0), axis=1, keepdims=True)


def _bwd_tile(q, k, v, do, lse, drow, *, sm_scale: float, mask=None,
              keep=None, dropout_rate: float = 0.0, qk2=None):
    """One (block_q, block_k) tile of the flash backward for ONE head, up
    to the three gradient matmuls: returns (p~, ds), both (bq, bk) f32,
    with p = exp(s - L) recomputed, dp = dO V^T, ds = p (dp - Drow) and
    p~ the probabilities that multiplied v in the forward. Shared by the
    dQ kernel, the key-parallel walk, the (B, T, heads*D) head-group walk
    and the latent kernel's one-pass walk (``qk2``: its second (q, k) pair,
    _scores), so they cannot drift.

    With dropout, p~ = keep * p / (1-r) is what multiplied v, so the mask
    (and its 1/(1-r) rescale) lands on dp too; the row term drow =
    rowsum(do*o) already equals rowsum(dp_masked * p) and needs no
    correction. The head-group walk passes 128-lane tiles with the other
    head's k and v lanes zeroed: the contractions give the same s, dp."""
    s = _scores(q, k, qk2) * sm_scale
    if mask is not None:
        s = jnp.where(mask, s, NEG_INF)
    p = jnp.exp(s - lse)                              # (bq, bk) f32
    dp = lax.dot_general(do, v, (((1,), (1,)), ((), ())),
                         preferred_element_type=jnp.float32)
    p_v = p
    if keep is not None:
        p_v = _apply_dropout(p, keep, dropout_rate)
        dp = _apply_dropout(dp, keep, dropout_rate)
    return p_v, p * (dp - drow)


def _flash_bwd_dq_kernel(seed_ref, q_ref, k_ref, v_ref, o_ref, do_ref,
                         lse_ref, dq_ref, *, block_q: int, block_k: int,
                         sm_scale: float, causal: bool, has_dlse: bool,
                         dropout_rate: float = 0.0,
                         stat_layout: str = "replicated",
                         local_heads: int = 1, hash_heads: int = 1,
                         hash_seq_len: int = 0):
    qi = pl.program_id(1)
    if dropout_rate > 0.0:
        mix = _dropout_tile_seed(seed_ref, pl.program_id(0),
                                 local_heads, hash_heads)
        q_off = seed_ref[3].astype(jnp.int32)
        k_off = seed_ref[4].astype(jnp.int32)
    q = q_ref[0]                                     # (bq, D) storage dtype
    do = do_ref[0]
    # The row term Drow = rowsum(dO * O) is computed HERE from the o
    # block instead of arriving as a precomputed lane-replicated f32
    # operand: that operand cost an XLA prepass plus ~350 MB/layer/step
    # of HBM traffic at the 124M bench shape, vs a few VPU ops on data
    # the kernel touches anyway. (bq, 1) column vectors are fine
    # in-register; only memory-ref blocks must tile to (8, 128).
    drow = jnp.sum(do.astype(jnp.float32) * o_ref[0].astype(jnp.float32),
                   axis=1, keepdims=True)            # (bq, 1) f32
    if stat_layout == "compact":
        # lse_ref block: (1, S, Tp//LANES, LANES) full dense rows; the
        # expansion matmul selects this q block's slice.
        row0 = qi * (block_q // LANES)
        lse = _expand_stat_tile(lse_ref[0, 0], row0, block_q)
        if has_dlse:
            # Fold the lse cotangent into the row term
            # (ds = p * (dp - (drow - dlse))).
            drow = drow - _expand_stat_tile(lse_ref[0, 1], row0, block_q)
    else:
        if has_dlse:
            # lse_ref carries [lse | dlse] stacked on the minor dim.
            drow = drow - lse_ref[0][:, LANES:LANES + 1]
        lse = lse_ref[0][:, :1]                      # (bq, 1) f32
    seq_len = k_ref.shape[1]
    if causal:
        num_kb_inner, num_kb = _causal_kb_range(qi, block_q, block_k)
    else:
        num_kb = seq_len // block_k
        num_kb_inner = num_kb
    q_pos = qi * block_q + lax.broadcasted_iota(jnp.int32,
                                                (block_q, block_k), 0)

    def body(j, dq_acc, *, masked: bool):
        k = k_ref[0, pl.ds(j * block_k, block_k), :]
        v = v_ref[0, pl.ds(j * block_k, block_k), :]
        mask = keep = None
        if masked:
            k_pos = j * block_k + lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 1)
            mask = q_pos >= k_pos
        if dropout_rate > 0.0:
            keep = _dropout_keep(mix, q_off + qi * block_q,
                                 k_off + j * block_k,
                                 (block_q, block_k), hash_seq_len,
                                 dropout_rate)
        _, ds = _bwd_tile(q, k, v, do, lse, drow, sm_scale=sm_scale,
                          mask=mask, keep=keep, dropout_rate=dropout_rate)
        return dq_acc + lax.dot_general(
            ds.astype(k.dtype), k, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    dq = lax.fori_loop(0, num_kb_inner, functools.partial(body, masked=False),
                       jnp.zeros((block_q, q.shape[1]), jnp.float32))
    dq = lax.fori_loop(num_kb_inner, num_kb,
                       functools.partial(body, masked=True), dq)
    dq_ref[0] = (dq * sm_scale).astype(dq_ref.dtype)


def _flash_bwd_tiles_kernel(seed_ref, q_ref, k_ref, v_ref, o_ref, do_ref,
                            lse_ref, *out_refs, block_q: int, block_k: int,
                            sm_scale: float, causal: bool, has_dlse: bool,
                            with_dq: bool, dropout_rate: float = 0.0,
                            stat_layout: str = "replicated",
                            local_heads: int = 1, hash_heads: int = 1,
                            hash_seq_len: int = 0):
    """The key-parallel backward walk, shared by BOTH backward strategies.

    Grid (batch*head, key blocks); inner loop over the causal q-block
    range, computing per tile: p = exp(s - L), dv += p~^T dO,
    dp = dO V^T, ds = p (dp - Drow), dk += ds^T Q.

    with_dq=False: out_refs = (dk_ref, dv_ref) — the split strategy's
    dKV kernel (a separate q-parallel kernel computes dQ).
    with_dq=True: out_refs = (dq_ref, dk_ref, dv_ref) — the FUSED
    one-pass strategy: the same ds additionally accumulates dq += ds K
    into an f32 output block that stays RESIDENT in VMEM across the
    (sequential, 'arbitrary'-semantics) key grid dimension and flushes
    once per batch*head. The split backward recomputes s/exp/dp twice
    (once per kernel); fused computes each causal tile once and feeds
    all three gradients — r5 measured 124M bench 147 -> 141.6 ms. Cost:
    a (Tp, D) f32 VMEM accumulator (256 KB at the 124M shape); dq is
    scaled by sm_scale and cast OUTSIDE the kernel (XLA fuses both into
    the unpad copy).
    """
    if with_dq:
        dq_ref, dk_ref, dv_ref = out_refs
    else:
        dk_ref, dv_ref = out_refs
    ki = pl.program_id(1)
    if dropout_rate > 0.0:
        mix = _dropout_tile_seed(seed_ref, pl.program_id(0),
                                 local_heads, hash_heads)
        q_off = seed_ref[3].astype(jnp.int32)
        k_off = seed_ref[4].astype(jnp.int32)
    k = k_ref[0]                                      # (bk, D)
    v = v_ref[0]
    seq_len = q_ref.shape[1]
    num_qb = seq_len // block_q
    if causal:
        start_qb = lax.div(ki * block_k, block_q)
        # q blocks at/after this index sit fully above the diagonal for
        # every key in this block — no mask needed (see the fwd kernel's
        # split-loop note; masking is pure VPU cost).
        diag_end = lax.div((ki + 1) * block_k + block_q - 1, block_q)
    else:
        start_qb = 0
        diag_end = 0
    k_pos = ki * block_k + lax.broadcasted_iota(jnp.int32,
                                                (block_q, block_k), 1)

    if with_dq:
        # The dq accumulator is revisited across ki: zero on first visit.
        @pl.when(ki == 0)
        def _zero_dq():
            dq_ref[0] = jnp.zeros_like(dq_ref[0])

    def body(i, carry, *, masked: bool):
        dk_acc, dv_acc = carry
        q = q_ref[0, pl.ds(i * block_q, block_q), :]
        do = do_ref[0, pl.ds(i * block_q, block_q), :]
        # Drow recomputed in-kernel from o (see _flash_bwd_dq_kernel).
        drow = jnp.sum(
            do.astype(jnp.float32)
            * o_ref[0, pl.ds(i * block_q, block_q), :].astype(jnp.float32),
            axis=1, keepdims=True)                    # (bq, 1) f32
        if stat_layout == "compact":
            row0 = i * (block_q // LANES)
            lse = _expand_stat_tile(lse_ref[0, 0], row0, block_q)
            if has_dlse:
                drow = drow - _expand_stat_tile(lse_ref[0, 1], row0, block_q)
        else:
            stats = lse_ref[0, pl.ds(i * block_q, block_q), :]
            if has_dlse:
                drow = drow - stats[:, LANES:LANES + 1]
            lse = stats[:, :1]                        # (bq, 1) f32
        mask = keep = None
        if masked:
            q_pos = i * block_q + lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 0)
            mask = q_pos >= k_pos
        if dropout_rate > 0.0:
            keep = _dropout_keep(mix, q_off + i * block_q,
                                 k_off + ki * block_k,
                                 (block_q, block_k), hash_seq_len,
                                 dropout_rate)
        p_v, ds = _bwd_tile(q, k, v, do, lse, drow, sm_scale=sm_scale,
                            mask=mask, keep=keep, dropout_rate=dropout_rate)
        dv_acc = dv_acc + lax.dot_general(
            p_v.astype(do.dtype), do, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)       # (bk, D)
        ds = ds.astype(q.dtype)
        dk_acc = dk_acc + lax.dot_general(
            ds, q, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)       # (bk, D)
        if with_dq:
            dq_blk = dq_ref[0, pl.ds(i * block_q, block_q), :]
            dq_ref[0, pl.ds(i * block_q, block_q), :] = (
                dq_blk + lax.dot_general(
                    ds, k, (((1,), (0,)), ((), ())),
                    preferred_element_type=jnp.float32))  # f32 accum
        return dk_acc, dv_acc

    D = k.shape[1]
    init = (jnp.zeros((block_k, D), jnp.float32),
            jnp.zeros((block_k, D), jnp.float32))
    if causal:
        carry = lax.fori_loop(start_qb, diag_end,
                              functools.partial(body, masked=True), init)
        dk, dv = lax.fori_loop(diag_end, num_qb,
                               functools.partial(body, masked=False), carry)
    else:
        dk, dv = lax.fori_loop(0, num_qb,
                               functools.partial(body, masked=False), init)
    dk_ref[0] = (dk * sm_scale).astype(dk_ref.dtype)
    dv_ref[0] = dv.astype(dv_ref.dtype)


# Backward strategy: 'fused' (one pass, dq resident — the r5 default) or
# 'split' (q-parallel dQ kernel + key-parallel dKV walk). Both strategies
# share _flash_bwd_tiles_kernel for the dk/dv math, so they cannot drift
# there; tests/test_attention.py pins fused-vs-split gradient parity so
# the split path stays exercised. NOT an automatic fallback: nothing
# degrades fused -> split (or pallas -> XLA) on a compile error.
BWD_IMPL = "fused"


def _pallas_flash_bwd(q, k, v, o, lse, do, *, causal: bool, sm_scale: float,
                      block_q: int = DEFAULT_BLOCK,
                      block_k: int = DEFAULT_BLOCK,
                      interpret: bool = False, dlse=None,
                      dropout_rate: float = 0.0, seed=None,
                      stat_layout: str = "replicated",
                      hash_heads: int | None = None,
                      hash_seq_len: int | None = None):
    """lse arrives compact and T-padded from the forward: (B*H, Tp, 1) f32.

    stat_layout picks the HBM operand the kernels read it through:
    'replicated' broadcasts both row stats across the 128-lane minor dim
    (transiently, here); 'compact' reshapes the dense vector to
    (Tp//LANES, LANES) rows and the kernels expand tiles in-register
    (_expand_stat_tile) — ~128x less stat traffic.

    dlse (optional, (B, H, T) f32): cotangent of the logsumexp output for
    callers of flash_attention_lse. Since d lse / d s = p, the extra term
    folds into the existing row stat: ds = p * (dp - (drow - dlse)).
    dV has no lse dependence (dv = p^T do only)."""
    if stat_layout not in ("replicated", "compact"):
        raise ValueError(f"unknown attention stat_layout: {stat_layout!r} "
                         "(expected 'replicated' or 'compact')")
    block_q, block_k = _clamp_blocks(q.shape[2], block_q, block_k)
    # The dKV kernel gets its own (wider) key blocking — see DKV_BLOCK_K.
    dkv_block_k = _clamp_blocks(q.shape[2], block_q,
                                max(block_k, DKV_BLOCK_K))[1]
    qf, kf, vf, (B, H, T, D, Tp, Dp, pad_T, pad_D) = _pad_qkv(
        q, k, v, block_q, max(block_k, dkv_block_k), causal)
    dof = _pad_qkv(do, do, do, block_q, block_k, causal)[0]
    of = _pad_qkv(o, o, o, block_q, block_k, causal)[0]
    # Drow is NOT built here — both kernels recompute it in-register from
    # (do, o), which they read anyway. When the caller supplies a dlse
    # cotangent (flash_attention_lse), it rides along in the same stats
    # operand so the kernels keep a single stats ref.
    has_dlse = dlse is not None
    dlsef = None
    if has_dlse:
        d = dlse.astype(jnp.float32)
        if pad_T:
            d = jnp.pad(d, [(0, 0), (0, 0), (0, pad_T)])
        dlsef = d.reshape(B * H, Tp, 1)
    if stat_layout == "compact":
        # (B*H, S, Tp//LANES, LANES): dense rows, S in {1, 2} stacks
        # [lse, dlse?] on a dedicated dim so one contiguous block serves
        # each q-block's slice of both stats.
        parts = [lse[..., 0].reshape(B * H, Tp // LANES, LANES)]
        if has_dlse:
            parts.append(dlsef[..., 0].reshape(B * H, Tp // LANES, LANES))
        statsf = jnp.stack(parts, axis=1)
        S = len(parts)
        # Both kernels take the FULL (tiny: Tp*4 bytes/bh) stats block —
        # Mosaic requires the last two block dims be 8/128-divisible OR
        # equal to the array dims, and block_q//LANES rows is neither.
        full_stats = pl.BlockSpec((1, S, Tp // LANES, LANES),
                                  lambda b, i: (b, 0, 0, 0))
        dq_stats_spec = dkv_stats_spec = full_stats
    else:
        statsf = jnp.broadcast_to(lse, (B * H, Tp, LANES))
        if has_dlse:
            statsf = jnp.concatenate(
                [statsf, jnp.broadcast_to(dlsef, (B * H, Tp, LANES))],
                axis=-1)
        W = statsf.shape[-1]  # LANES or 2*LANES
        dq_stats_spec = pl.BlockSpec((1, block_q, W), lambda b, i: (b, i, 0))
        dkv_stats_spec = pl.BlockSpec((1, Tp, W), lambda b, j: (b, 0, 0))

    hash_heads = hash_heads if hash_heads is not None else H
    hash_seq_len = hash_seq_len if hash_seq_len is not None else Tp
    _check_dropout_seq_len(dropout_rate, hash_seq_len)
    seed_arg = _dropout_seed_arg(seed, dropout_rate)

    unpad = lambda g: g.reshape(B, H, Tp, Dp)[:, :, :T, :D]
    if BWD_IMPL == "fused":
        # One pass over the causal tiles computing all three grads; dq is
        # an f32 accumulator block resident across the (sequential) key
        # grid dimension, scaled+cast outside (XLA fuses both into the
        # unpad copy). dkv_stats_spec already serves the per-q-block
        # stats reads this kernel does.
        grid_f = (B * H, Tp // block_k)
        dq, dk, dv = pl.pallas_call(
            functools.partial(_flash_bwd_tiles_kernel, block_q=block_q,
                              block_k=block_k, sm_scale=sm_scale,
                              causal=causal, has_dlse=has_dlse,
                              with_dq=True,
                              dropout_rate=dropout_rate,
                              stat_layout=stat_layout, local_heads=H,
                              hash_heads=hash_heads,
                              hash_seq_len=hash_seq_len),
            grid=grid_f,
            in_specs=[
                pl.BlockSpec(memory_space=pltpu.SMEM),
                pl.BlockSpec((1, Tp, Dp), lambda b, j: (b, 0, 0)),
                pl.BlockSpec((1, block_k, Dp), lambda b, j: (b, j, 0)),
                pl.BlockSpec((1, block_k, Dp), lambda b, j: (b, j, 0)),
                pl.BlockSpec((1, Tp, Dp), lambda b, j: (b, 0, 0)),
                pl.BlockSpec((1, Tp, Dp), lambda b, j: (b, 0, 0)),
                dkv_stats_spec,
            ],
            out_specs=[
                pl.BlockSpec((1, Tp, Dp), lambda b, j: (b, 0, 0)),
                pl.BlockSpec((1, block_k, Dp), lambda b, j: (b, j, 0)),
                pl.BlockSpec((1, block_k, Dp), lambda b, j: (b, j, 0)),
            ],
            out_shape=[
                jax.ShapeDtypeStruct((B * H, Tp, Dp), jnp.float32),
                jax.ShapeDtypeStruct((B * H, Tp, Dp), k.dtype),
                jax.ShapeDtypeStruct((B * H, Tp, Dp), v.dtype),
            ],
            # The key grid dim is 'arbitrary' (sequential): the resident
            # dq block's read-modify-write across ki requires it.
            compiler_params=None if interpret else _tpu_params(
                "parallel", "arbitrary"),
            interpret=interpret,
        )(seed_arg, qf, kf, vf, of, dof, statsf)
        return (unpad(dq * sm_scale).astype(q.dtype),
                unpad(dk).astype(k.dtype), unpad(dv).astype(v.dtype))

    grid_q = (B * H, Tp // block_q)
    dq = pl.pallas_call(
        functools.partial(_flash_bwd_dq_kernel, block_q=block_q,
                          block_k=block_k, sm_scale=sm_scale, causal=causal,
                          has_dlse=has_dlse, dropout_rate=dropout_rate,
                          stat_layout=stat_layout, local_heads=H,
                          hash_heads=hash_heads, hash_seq_len=hash_seq_len),
        grid=grid_q,
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.SMEM),
            pl.BlockSpec((1, block_q, Dp), lambda b, i: (b, i, 0)),
            pl.BlockSpec((1, Tp, Dp), lambda b, i: (b, 0, 0)),
            pl.BlockSpec((1, Tp, Dp), lambda b, i: (b, 0, 0)),
            pl.BlockSpec((1, block_q, Dp), lambda b, i: (b, i, 0)),
            pl.BlockSpec((1, block_q, Dp), lambda b, i: (b, i, 0)),
            dq_stats_spec,
        ],
        out_specs=pl.BlockSpec((1, block_q, Dp), lambda b, i: (b, i, 0)),
        # Grads leave the kernel already in the input dtype: the f32
        # accumulators are rounded on the register->VMEM write, which
        # halves the grad HBM writes AND deletes the XLA cast pass that a
        # f32 out_shape forced afterwards (r5 microbench: the three
        # (B*H, Tp, 128-padded) f32 grad tensors cost ~1 ms/layer in
        # write+cast traffic at the 124M bench shape).
        out_shape=jax.ShapeDtypeStruct((B * H, Tp, Dp), q.dtype),
        compiler_params=None if interpret else _tpu_params(
            "parallel", "parallel"),
        interpret=interpret,
    )(seed_arg, qf, kf, vf, of, dof, statsf)

    grid_k = (B * H, Tp // dkv_block_k)
    dk, dv = pl.pallas_call(
        functools.partial(_flash_bwd_tiles_kernel, block_q=block_q,
                          block_k=dkv_block_k, sm_scale=sm_scale,
                          causal=causal, has_dlse=has_dlse,
                          with_dq=False,
                          dropout_rate=dropout_rate,
                          stat_layout=stat_layout, local_heads=H,
                          hash_heads=hash_heads, hash_seq_len=hash_seq_len),
        grid=grid_k,
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.SMEM),
            pl.BlockSpec((1, Tp, Dp), lambda b, j: (b, 0, 0)),
            pl.BlockSpec((1, dkv_block_k, Dp), lambda b, j: (b, j, 0)),
            pl.BlockSpec((1, dkv_block_k, Dp), lambda b, j: (b, j, 0)),
            pl.BlockSpec((1, Tp, Dp), lambda b, j: (b, 0, 0)),
            pl.BlockSpec((1, Tp, Dp), lambda b, j: (b, 0, 0)),
            dkv_stats_spec,
        ],
        out_specs=[
            pl.BlockSpec((1, dkv_block_k, Dp), lambda b, j: (b, j, 0)),
            pl.BlockSpec((1, dkv_block_k, Dp), lambda b, j: (b, j, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((B * H, Tp, Dp), k.dtype),
            jax.ShapeDtypeStruct((B * H, Tp, Dp), v.dtype),
        ],
        compiler_params=None if interpret else _tpu_params(
            "parallel", "parallel"),
        interpret=interpret,
    )(seed_arg, qf, kf, vf, of, dof, statsf)

    return (unpad(dq).astype(q.dtype), unpad(dk).astype(k.dtype),
            unpad(dv).astype(v.dtype))


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6))
def flash_attention(q, k, v, causal: bool = True, sm_scale: float | None = None,
                    interpret: bool = False, stat_layout: str = "replicated"):
    """Flash attention: Pallas forward AND backward (both causal-aware).

    stat_layout ('replicated' | 'compact') picks the backward's softmax-
    stat operand layout; forward math is identical either way."""
    if sm_scale is None:
        sm_scale = q.shape[-1] ** -0.5
    out, _ = _pallas_flash_fwd(q, k, v, causal=causal, sm_scale=sm_scale,
                               interpret=interpret)
    return out


def _flash_fwd_rule(q, k, v, causal, sm_scale, interpret,
                    stat_layout="replicated"):
    from jax.ad_checkpoint import checkpoint_name

    if sm_scale is None:
        sm_scale = q.shape[-1] ** -0.5
    o, lse = _pallas_flash_fwd(q, k, v, causal=causal, sm_scale=sm_scale,
                               interpret=interpret)
    # Store the residual COMPACT (B*H, Tp, 1): the lane-replicated
    # (..., 128) form would be the largest per-layer activation held
    # across the whole backward (128x a (B, H, T) vector); the backward
    # re-broadcasts it transiently right before its pallas_call.
    #
    # checkpoint_name tags make these residuals SAVEABLE under
    # remat_policy='save_attention' (models/gpt.py): a jax.checkpoint
    # region discards custom_vjp residuals by default, which would
    # re-run this whole forward kernel during the backward — tagging
    # o and lse (q/k/v recompute from the block input via one cheap
    # dense matmul) is what actually elides the O(T^2) recompute.
    o = checkpoint_name(o, "attn_out")
    return o, (q, k, v, o, checkpoint_name(lse[..., :1], "attn_lse"))


def _flash_bwd_rule(causal, sm_scale, interpret, stat_layout, res, do):
    q, k, v, o, lse = res
    if sm_scale is None:
        sm_scale = q.shape[-1] ** -0.5
    return _pallas_flash_bwd(q, k, v, o, lse, do, causal=causal,
                             sm_scale=sm_scale, interpret=interpret,
                             stat_layout=stat_layout)


flash_attention.defvjp(_flash_fwd_rule, _flash_bwd_rule)


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5, 6, 7, 8))
def flash_attention_dropout(q, k, v, seed, causal: bool = True,
                            sm_scale: float | None = None,
                            dropout_rate: float = 0.0,
                            interpret: bool = False,
                            stat_layout: str = "replicated"):
    """Flash attention with attention-probability dropout IN the kernels.

    Semantically o = dropout(softmax(s)) @ v — identical regularization to
    xla_attention's dropout path (nanoGPT's attn_dropout, the reference's
    exercised ``--dropout`` key, ipynb:74-77) but at flash-kernel speed:
    round 3's convergence runs fell to the ~10%-MFU XLA fallback solely
    because dropout wasn't expressible here (r3 VERDICT weak #1).

    seed: (1,) uint32 array. The keep-mask is a counter-based hash of the
    global element position keyed by (seed, batch*head), so the forward
    and both backward kernels reconstruct the same mask without ever
    materializing it; the same (seed, shapes) pair always yields the same
    mask, making the op a pure function of its inputs (remat-safe).
    """
    if sm_scale is None:
        sm_scale = q.shape[-1] ** -0.5
    out, _ = _pallas_flash_fwd(q, k, v, causal=causal, sm_scale=sm_scale,
                               interpret=interpret,
                               dropout_rate=dropout_rate, seed=seed)
    return out


def _flash_dropout_fwd_rule(q, k, v, seed, causal, sm_scale, dropout_rate,
                            interpret, stat_layout="replicated"):
    from jax.ad_checkpoint import checkpoint_name

    if sm_scale is None:
        sm_scale = q.shape[-1] ** -0.5
    o, lse = _pallas_flash_fwd(q, k, v, causal=causal, sm_scale=sm_scale,
                               interpret=interpret,
                               dropout_rate=dropout_rate, seed=seed)
    o = checkpoint_name(o, "attn_out")  # see _flash_fwd_rule
    return o, (q, k, v, o, checkpoint_name(lse[..., :1], "attn_lse"), seed)


def _flash_dropout_bwd_rule(causal, sm_scale, dropout_rate, interpret,
                            stat_layout, res, do):
    q, k, v, o, lse, seed = res
    if sm_scale is None:
        sm_scale = q.shape[-1] ** -0.5
    dq, dk, dv = _pallas_flash_bwd(q, k, v, o, lse, do, causal=causal,
                                   sm_scale=sm_scale, interpret=interpret,
                                   dropout_rate=dropout_rate, seed=seed,
                                   stat_layout=stat_layout)
    return dq, dk, dv, None


flash_attention_dropout.defvjp(_flash_dropout_fwd_rule,
                               _flash_dropout_bwd_rule)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6))
def flash_attention_lse(q, k, v, causal: bool = True,
                        sm_scale: float | None = None,
                        interpret: bool = False,
                        stat_layout: str = "replicated"):
    """Flash attention that ALSO returns the per-row logsumexp.

    Returns (out (B, H, T, D), lse (B, H, T) f32) where
    lse = log sum_k exp(s_k * sm_scale). This is the block primitive ring
    attention composes: per-chunk (out_j, lse_j) pairs merge exactly via
    out = sum_j exp(lse_j - logsumexp_j lse_j) * out_j, so the ring can
    run the real Mosaic kernel per block instead of materializing
    (Tc, Tc) score tensors (round-2 VERDICT weak #1). Differentiable in
    both outputs: the lse cotangent folds into the backward's row stat
    (see _pallas_flash_bwd).
    """
    if sm_scale is None:
        sm_scale = q.shape[-1] ** -0.5
    out, lse = _pallas_flash_fwd(q, k, v, causal=causal, sm_scale=sm_scale,
                                 interpret=interpret)
    return out, _compact_lse(lse, q.shape)


def _compact_lse(lse, qshape):
    """(B*H, Tp, LANES) lane-replicated -> (B, H, T) compact."""
    B, H, T, _ = qshape
    return lse[:, :T, 0].reshape(B, H, T)


def _flash_lse_fwd_rule(q, k, v, causal, sm_scale, interpret,
                        stat_layout="replicated"):
    from jax.ad_checkpoint import checkpoint_name

    if sm_scale is None:
        sm_scale = q.shape[-1] ** -0.5
    o, lse = _pallas_flash_fwd(q, k, v, causal=causal, sm_scale=sm_scale,
                               interpret=interpret)
    o = checkpoint_name(o, "attn_out")  # see _flash_fwd_rule
    return ((o, _compact_lse(lse, q.shape)),
            (q, k, v, o, checkpoint_name(lse[..., :1], "attn_lse")))


def _flash_lse_bwd_rule(causal, sm_scale, interpret, stat_layout, res, cts):
    q, k, v, o, lse = res
    do, dlse = cts
    if sm_scale is None:
        sm_scale = q.shape[-1] ** -0.5
    return _pallas_flash_bwd(q, k, v, o, lse, do, causal=causal,
                             sm_scale=sm_scale, interpret=interpret,
                             dlse=dlse, stat_layout=stat_layout)


flash_attention_lse.defvjp(_flash_lse_fwd_rule, _flash_lse_bwd_rule)


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5, 6, 7, 8, 9, 10))
def flash_attention_lse_dropout(q, k, v, seed, causal: bool = True,
                                sm_scale: float | None = None,
                                dropout_rate: float = 0.0,
                                interpret: bool = False,
                                stat_layout: str = "replicated",
                                hash_heads: int | None = None,
                                hash_seq_len: int | None = None):
    """flash_attention_lse + in-kernel dropout keyed on GLOBAL coordinates
    — the block primitive regularized ring attention composes.

    seed: (SEED_WORDS,) uint32 [seed, b_off, h_off, q_off, k_off] (or a
    (1,) seed for the degenerate unsharded case). hash_heads /
    hash_seq_len are the GLOBAL head count and sequence length the mask
    hash is keyed over, so every ring step (and the dq/dkv backward
    kernels recomputing P) reconstructs the same mask for the same global
    score element regardless of which shard computes it.

    The returned lse is the logsumexp of the UNMASKED scores (dropout
    applies to normalized probabilities; the normalizer is mask-free), so
    ring merging of (out_j, lse_j) pairs over dropout blocks is exact:
    the masked probabilities are rescaled by the same global normalizer
    the unmasked merge computes.
    """
    if sm_scale is None:
        sm_scale = q.shape[-1] ** -0.5
    out, lse = _pallas_flash_fwd(q, k, v, causal=causal, sm_scale=sm_scale,
                                 interpret=interpret,
                                 dropout_rate=dropout_rate, seed=seed,
                                 hash_heads=hash_heads,
                                 hash_seq_len=hash_seq_len)
    return out, _compact_lse(lse, q.shape)


def _flash_lse_dropout_fwd_rule(q, k, v, seed, causal, sm_scale,
                                dropout_rate, interpret,
                                stat_layout="replicated",
                                hash_heads=None, hash_seq_len=None):
    from jax.ad_checkpoint import checkpoint_name

    if sm_scale is None:
        sm_scale = q.shape[-1] ** -0.5
    o, lse = _pallas_flash_fwd(q, k, v, causal=causal, sm_scale=sm_scale,
                               interpret=interpret,
                               dropout_rate=dropout_rate, seed=seed,
                               hash_heads=hash_heads,
                               hash_seq_len=hash_seq_len)
    o = checkpoint_name(o, "attn_out")  # see _flash_fwd_rule
    return ((o, _compact_lse(lse, q.shape)),
            (q, k, v, o, checkpoint_name(lse[..., :1], "attn_lse"), seed))


def _flash_lse_dropout_bwd_rule(causal, sm_scale, dropout_rate, interpret,
                                stat_layout, hash_heads, hash_seq_len,
                                res, cts):
    q, k, v, o, lse, seed = res
    do, dlse = cts
    if sm_scale is None:
        sm_scale = q.shape[-1] ** -0.5
    dq, dk, dv = _pallas_flash_bwd(q, k, v, o, lse, do, causal=causal,
                                   sm_scale=sm_scale, interpret=interpret,
                                   dlse=dlse, dropout_rate=dropout_rate,
                                   seed=seed, stat_layout=stat_layout,
                                   hash_heads=hash_heads,
                                   hash_seq_len=hash_seq_len)
    return dq, dk, dv, None


flash_attention_lse_dropout.defvjp(_flash_lse_dropout_fwd_rule,
                                   _flash_lse_dropout_bwd_rule)


# ---------------------------------------------------------------------------
# The (B, T, heads*D) entry: the kernels read c_attn's output and write
# c_proj's input
# ---------------------------------------------------------------------------
#
# The model's own activation layout is (B, T, heads*D): c_attn emits
# qkv (B, T, 3C), c_proj consumes o (B, T, C). The (B*H, T, D) kernels
# above need every operand transposed to heads-major first and every
# result transposed back, forward and backward: at the 124M training shape
# 168 activation-sized copies a step, plus a slice of the lane-replicated
# logsumexp and a scale+cast pass over dq. The kernels below take the
# SAME tile functions (_fwd_tile, _bwd_tile, _expand_stat_tile) to the
# data where it lies:
#
#   * a 128-lane block of the last dimension is a GROUP of 128 // D heads
#     (two at GPT-2's D = 64; one head when D % 128 == 0), so the grid
#     walks (batch, head groups, blocks) and the BlockSpecs index the q, k
#     and v thirds of qkv directly;
#   * inside a program the heads of a group are separated without moving
#     a lane: a 64-deep contraction half-fills the 128-deep MXU anyway, so
#     q (forward) or k and v (backward) are zeroed outside the head's
#     lanes and contracted at full width — the same MXU passes, the same
#     scores. Products that come out 128 lanes wide (p @ v, p^T @ dO,
#     ds^T @ q) are right in the head's own lanes and are picked by one
#     select when the program ends; ds @ k against the zeroed k is exactly
#     zero in the other head's lanes, so dq needs no select at all;
#   * the logsumexp leaves the forward compact, (B, H, 1, T) float32, one
#     row per head, and the backward reads it through the compact stat
#     layout's (T // 128, 128) tiles (_expand_stat_tile);
#   * the backward writes dq (scaled, in the compute type), dk and dv
#     into the thirds of ONE dqkv (B, T, 3C) array, which c_attn's two
#     backward matmuls read as it is. One pallas output has one BlockSpec,
#     so the three thirds are written by the kernel's own DMAs from VMEM
#     staging buffers; a program waits for its predecessor's DMAs only
#     when it is about to refill the buffers, so they overlap the next
#     program's compute.

# The two calls below are jitted: a model's layers then share ONE trace and
# ONE lowering of each kernel (24 / 48 kernel bodies a step otherwise, each
# twice the (B*H, T, D) kernel's size: +2.4 s of tracing and +1.5 s of
# lowering in the 124M set-up, measured on the chip's host). XLA names a
# custom call after the innermost scope, which would be the jit's; the
# scope below keeps the name the model's own 'attn' scope gave it, which
# the trace readers look for (%attn.N).
KERNEL_SCOPE = "attn"


def _head_lane_masks(width: int, head_dim: int):
    """(1, width) boolean lane masks, one per head of a lane group; [None]
    when the group is a single head (nothing to separate)."""
    group = width // head_dim
    if group == 1:
        return [None]
    lane_head = lax.broadcasted_iota(jnp.int32, (1, width), 1) // head_dim
    return [lane_head == h for h in range(group)]


def _only_lanes(x: jax.Array, lanes) -> jax.Array:
    """x with every lane outside the head's zeroed (x itself for a
    single-head group). Through float32: the v5e's vector unit has no
    bfloat16 select, and the round trip is exact."""
    if lanes is None:
        return x
    return jnp.where(lanes, x.astype(jnp.float32), 0.0).astype(x.dtype)


def _pick_lanes(per_head: list, lane_masks: list) -> jax.Array:
    """Assemble one 128-lane array from per-head arrays that are each
    right in their own head's lanes."""
    out = per_head[-1]
    for x, lanes in zip(per_head[-2::-1], lane_masks[-2::-1]):
        out = jnp.where(lanes, x, out)
    return out


def _stat_column_to_row(col: jax.Array) -> jax.Array:
    """(block_q, 1) per-row statistic -> the (1, block_q) lane-dense row
    the compact logsumexp output stores: the sublane -> lane relayout,
    built 128 rows at a time from a select against the identity and a
    sublane sum (ops Mosaic always lowers; 128 x 128 per chunk, noise
    against a (block_q, block_k) score tile)."""
    n = col.shape[0]
    eye = (lax.broadcasted_iota(jnp.int32, (LANES, LANES), 0)
           == lax.broadcasted_iota(jnp.int32, (LANES, LANES), 1))
    rows = [jnp.sum(jnp.where(eye, col[c:c + LANES], 0.0), axis=0,
                    keepdims=True) for c in range(0, n, LANES)]
    return rows[0] if len(rows) == 1 else jnp.concatenate(rows, axis=1)


def _flash_fwd_qkv_kernel(seed_ref, q_ref, k_ref, v_ref, o_ref, lse_ref, *,
                          block_q: int, block_k: int, sm_scale: float,
                          n_head: int, head_dim: int,
                          dropout_rate: float = 0.0):
    """Causal flash forward for one (batch row, head group, q block).

    q_ref (1, block_q, W), k_ref / v_ref (1, T, W): 128-lane column blocks
    of qkv's thirds; o_ref (1, block_q, W) the same columns of o;
    lse_ref (1, group, 1, block_q). Walks the same causal k-block range as
    _flash_fwd_kernel, loading each k / v tile once for the group."""
    b, g, qi = pl.program_id(0), pl.program_id(1), pl.program_id(2)
    width = q_ref.shape[2]
    seq_len = k_ref.shape[1]
    lane_masks = _head_lane_masks(width, head_dim)
    group = len(lane_masks)
    q = q_ref[0]
    q_heads = [_only_lanes(q, lanes) for lanes in lane_masks]
    if dropout_rate > 0.0:
        mixes = [_dropout_tile_seed(seed_ref, b * n_head + g * group + h,
                                    n_head, n_head) for h in range(group)]
        q_off = seed_ref[3].astype(jnp.int32)
        k_off = seed_ref[4].astype(jnp.int32)
    num_kb_inner, num_kb = _causal_kb_range(qi, block_q, block_k)
    q_pos = qi * block_q + lax.broadcasted_iota(
        jnp.int32, (block_q, block_k), 0)

    def body(j, carries, *, masked: bool):
        k = k_ref[0, pl.ds(j * block_k, block_k), :]
        v = v_ref[0, pl.ds(j * block_k, block_k), :]
        mask = None
        if masked:
            k_pos = j * block_k + lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 1)
            mask = q_pos >= k_pos
        out = []
        for h in range(group):
            keep = None
            if dropout_rate > 0.0:
                keep = _dropout_keep(mixes[h], q_off + qi * block_q,
                                     k_off + j * block_k,
                                     (block_q, block_k), seq_len,
                                     dropout_rate)
            out.append(_fwd_tile(q_heads[h], k, v, carries[h],
                                 sm_scale=sm_scale, mask=mask, keep=keep,
                                 dropout_rate=dropout_rate))
        return tuple(out)

    init = tuple((jnp.zeros((block_q, width), jnp.float32),
                  jnp.full((block_q, 1), NEG_INF, jnp.float32),
                  jnp.zeros((block_q, 1), jnp.float32))
                 for _ in range(group))
    carries = lax.fori_loop(0, num_kb_inner,
                            functools.partial(body, masked=False), init)
    carries = lax.fori_loop(num_kb_inner, num_kb,
                            functools.partial(body, masked=True), carries)
    o_ref[0] = _pick_lanes([acc / l for acc, _, l in carries],
                           lane_masks).astype(o_ref.dtype)
    for h, (_, m, l) in enumerate(carries):
        lse_ref[0, h] = _stat_column_to_row(m + jnp.log(l))


def _qkv_geometry(qkv_shape, n_head: int):
    """(B, T, C, head_dim, lane-block width, lane blocks per third)."""
    B, T, C3 = qkv_shape
    C = C3 // 3
    head_dim = C // n_head
    width = max(head_dim, LANES)
    if not qkv_layout_supported(n_head, head_dim, T):
        raise ValueError(
            f"flash_attention_qkv needs T % {LANES} == 0 and heads that "
            f"tile the 128-lane blocks of (B, T, heads*D) (D == 64 with an "
            f"even head count, or D % 128 == 0); got T={T}, "
            f"n_head={n_head}, head_dim={head_dim}: use the (B, H, T, D) "
            "entry (causal_attention)")
    return B, T, C, head_dim, width, C // width


def qkv_layout_supported(n_head: int, head_dim: int, T: int) -> bool:
    """Whether (B, T, n_head * head_dim) splits into whole 128-lane head
    groups the kernels above can walk with no padding and no copy."""
    return (T % LANES == 0 and (n_head * head_dim) % LANES == 0
            and ((head_dim == 64 and n_head % 2 == 0)
                 or head_dim % LANES == 0))


@functools.partial(jax.jit, static_argnames=(
    "n_head", "interpret", "dropout_rate"))
def _pallas_flash_fwd_qkv(qkv: jax.Array, n_head: int, *,
                          interpret: bool = False,
                          dropout_rate: float = 0.0, seed=None):
    """qkv (B, T, 3C) -> (o (B, T, C), lse (B, H, 1, T) f32). qkv is
    handed to the kernel three times, each BlockSpec indexing its third."""
    B, T, C, head_dim, width, nw = _qkv_geometry(qkv.shape, n_head)
    group = width // head_dim
    block_q, block_k = _clamp_blocks(T, DEFAULT_BLOCK, DEFAULT_BLOCK)
    _check_dropout_seq_len(dropout_rate, T)
    kernel = functools.partial(
        _flash_fwd_qkv_kernel, block_q=block_q, block_k=block_k,
        sm_scale=head_dim ** -0.5, n_head=n_head, head_dim=head_dim,
        dropout_rate=dropout_rate)
    call = pl.pallas_call(
        kernel,
        grid=(B, n_head // group, T // block_q),
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.SMEM),
            pl.BlockSpec((1, block_q, width), lambda b, g, i: (b, i, g)),
            pl.BlockSpec((1, T, width), lambda b, g, i: (b, 0, nw + g)),
            pl.BlockSpec((1, T, width), lambda b, g, i: (b, 0, 2 * nw + g)),
        ],
        out_specs=[
            pl.BlockSpec((1, block_q, width), lambda b, g, i: (b, i, g)),
            pl.BlockSpec((1, group, 1, block_q),
                         lambda b, g, i: (b, g, 0, i)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((B, T, C), qkv.dtype),
            jax.ShapeDtypeStruct((B, n_head, 1, T), jnp.float32),
        ],
        compiler_params=None if interpret else _tpu_params(
            "parallel", "parallel", "parallel"),
        interpret=interpret,
    )
    with jax.named_scope(KERNEL_SCOPE):
        return call(_dropout_seed_arg(seed, dropout_rate), qkv, qkv, qkv)


def _flash_bwd_qkv_kernel(seed_ref, q_ref, k_ref, v_ref, o_ref, do_ref,
                          lse_ref, dqkv_ref, dq_acc, dq_buf, dk_buf, dv_buf,
                          sems, *, block_q: int, block_k: int,
                          sm_scale: float, n_head: int, head_dim: int,
                          dropout_rate: float = 0.0):
    """The fused one-pass backward (see _flash_bwd_tiles_kernel,
    with_dq=True) for one (batch row, head group, key block).

    q_ref / o_ref / do_ref (1, T, W), k_ref / v_ref (1, block_k, W),
    lse_ref (1, group, T // 128, 128); dqkv_ref is the whole (B, T, 3C)
    output in HBM. dq_acc (T, W) f32 accumulates dq across the key blocks
    of the group; dq_buf / dk_buf / dv_buf stage the finished blocks, in
    the compute type, for the DMAs into dqkv's thirds (sems: dq, dk, dv).
    The grid runs in order ('arbitrary'), so "the previous program" is
    well defined: its DMAs are waited for right before the buffers are
    written again, and the last program waits for its own."""
    b, g, ki = pl.program_id(0), pl.program_id(1), pl.program_id(2)
    nb, ng, nk = pl.num_programs(0), pl.num_programs(1), pl.num_programs(2)
    width = k_ref.shape[2]
    seq_len = q_ref.shape[1]
    num_qb = seq_len // block_q
    C = dqkv_ref.shape[2] // 3
    lane_masks = _head_lane_masks(width, head_dim)
    group = len(lane_masks)
    k = k_ref[0]
    v = v_ref[0]
    k_heads = [_only_lanes(k, lanes) for lanes in lane_masks]
    v_heads = [_only_lanes(v, lanes) for lanes in lane_masks]
    if dropout_rate > 0.0:
        mixes = [_dropout_tile_seed(seed_ref, b * n_head + g * group + h,
                                    n_head, n_head) for h in range(group)]
        q_off = seed_ref[3].astype(jnp.int32)
        k_off = seed_ref[4].astype(jnp.int32)
    start_qb = lax.div(ki * block_k, block_q)
    diag_end = lax.div((ki + 1) * block_k + block_q - 1, block_q)
    k_pos = ki * block_k + lax.broadcasted_iota(
        jnp.int32, (block_q, block_k), 1)

    @pl.when(ki == 0)
    def _zero_dq():
        dq_acc[...] = jnp.zeros_like(dq_acc)

    def body(i, carry, *, masked: bool):
        rows = pl.ds(i * block_q, block_q)
        q = q_ref[0, rows, :]
        do = do_ref[0, rows, :]
        # Drow = rowsum(dO * O) per head, from the group's one product.
        do_o = do.astype(jnp.float32) * o_ref[0, rows, :].astype(jnp.float32)
        mask = None
        if masked:
            q_pos = i * block_q + lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 0)
            mask = q_pos >= k_pos
        dq_blk = dq_acc[rows, :]
        out = []
        for h, (dk_acc, dv_acc) in enumerate(carry):
            lanes = lane_masks[h]
            drow = jnp.sum(do_o if lanes is None
                           else jnp.where(lanes, do_o, 0.0),
                           axis=1, keepdims=True)     # (bq, 1) f32
            lse = _expand_stat_tile(lse_ref[0, h], i * (block_q // LANES),
                                    block_q)
            keep = None
            if dropout_rate > 0.0:
                keep = _dropout_keep(mixes[h], q_off + i * block_q,
                                     k_off + ki * block_k,
                                     (block_q, block_k), seq_len,
                                     dropout_rate)
            p_v, ds = _bwd_tile(q, k_heads[h], v_heads[h], do, lse, drow,
                                sm_scale=sm_scale, mask=mask, keep=keep,
                                dropout_rate=dropout_rate)
            # Right in head h's lanes, picked when the program ends.
            dv_acc = dv_acc + lax.dot_general(
                p_v.astype(do.dtype), do, (((0,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)   # (bk, W)
            ds = ds.astype(q.dtype)
            dk_acc = dk_acc + lax.dot_general(
                ds, q, (((0,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)   # (bk, W)
            # Exactly zero outside head h's lanes (k is zeroed there).
            dq_blk = dq_blk + lax.dot_general(
                ds, k_heads[h], (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)   # (bq, W)
            out.append((dk_acc, dv_acc))
        dq_acc[rows, :] = dq_blk
        return tuple(out)

    init = tuple((jnp.zeros((block_k, width), jnp.float32),
                  jnp.zeros((block_k, width), jnp.float32))
                 for _ in range(group))
    carry = lax.fori_loop(start_qb, diag_end,
                          functools.partial(body, masked=True), init)
    carry = lax.fori_loop(diag_end, num_qb,
                          functools.partial(body, masked=False), carry)
    dk = _pick_lanes([dk_acc for dk_acc, _ in carry], lane_masks)
    dv = _pick_lanes([dv_acc for _, dv_acc in carry], lane_masks)

    col = pl.multiple_of(g * width, LANES)
    row = pl.multiple_of(ki * block_k, LANES)

    def copy_out(buf, sem, rows, third):
        return pltpu.make_async_copy(
            buf, dqkv_ref.at[b, rows, pl.ds(third * C + col, width)],
            sems.at[sem])

    dq_copy = copy_out(dq_buf, 0, pl.ds(0, seq_len), 0)
    dk_copy = copy_out(dk_buf, 1, pl.ds(row, block_k), 1)
    dv_copy = copy_out(dv_buf, 2, pl.ds(row, block_k), 2)
    first_group = jnp.logical_and(b == 0, g == 0)
    last_group = jnp.logical_and(b == nb - 1, g == ng - 1)

    # A wait needs only the semaphore and the byte count, which are those
    # of the predecessor's copy of the same buffer.
    @pl.when(jnp.logical_not(jnp.logical_and(first_group, ki == 0)))
    def _wait_previous_dk_dv():
        dk_copy.wait()
        dv_copy.wait()

    dk_buf[...] = (dk * sm_scale).astype(dk_buf.dtype)
    dv_buf[...] = dv.astype(dv_buf.dtype)
    dk_copy.start()
    dv_copy.start()

    @pl.when(ki == nk - 1)
    def _flush_dq():
        @pl.when(jnp.logical_not(first_group))
        def _wait_previous_dq():
            dq_copy.wait()

        dq_buf[...] = (dq_acc[...] * sm_scale).astype(dq_buf.dtype)
        dq_copy.start()

    @pl.when(jnp.logical_and(last_group, ki == nk - 1))
    def _drain():
        dq_copy.wait()
        dk_copy.wait()
        dv_copy.wait()


@functools.partial(jax.jit, static_argnames=(
    "n_head", "interpret", "dropout_rate"))
def _pallas_flash_bwd_qkv(qkv, o, lse, do, n_head: int, *,
                          interpret: bool = False,
                          dropout_rate: float = 0.0, seed=None):
    """(qkv (B, T, 3C), o, lse (B, H, 1, T), do (B, T, C)) -> dqkv
    (B, T, 3C) in qkv's dtype, dq already scaled by head_dim ** -0.5."""
    B, T, C, head_dim, width, nw = _qkv_geometry(qkv.shape, n_head)
    group = width // head_dim
    block_q, block_k = _clamp_blocks(T, DEFAULT_BLOCK, DEFAULT_BLOCK)
    _check_dropout_seq_len(dropout_rate, T)
    kernel = functools.partial(
        _flash_bwd_qkv_kernel, block_q=block_q, block_k=block_k,
        sm_scale=head_dim ** -0.5, n_head=n_head, head_dim=head_dim,
        dropout_rate=dropout_rate)
    full = lambda b, g, j: (b, 0, g)
    call = pl.pallas_call(
        kernel,
        grid=(B, n_head // group, T // block_k),
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.SMEM),
            pl.BlockSpec((1, T, width), full),
            pl.BlockSpec((1, block_k, width),
                         lambda b, g, j: (b, j, nw + g)),
            pl.BlockSpec((1, block_k, width),
                         lambda b, g, j: (b, j, 2 * nw + g)),
            pl.BlockSpec((1, T, width), full),
            pl.BlockSpec((1, T, width), full),
            pl.BlockSpec((1, group, T // LANES, LANES),
                         lambda b, g, j: (b, g, 0, 0)),
        ],
        out_specs=pl.BlockSpec(memory_space=pl.ANY),
        out_shape=jax.ShapeDtypeStruct(qkv.shape, qkv.dtype),
        scratch_shapes=[
            pltpu.VMEM((T, width), jnp.float32),
            pltpu.VMEM((T, width), qkv.dtype),
            pltpu.VMEM((block_k, width), qkv.dtype),
            pltpu.VMEM((block_k, width), qkv.dtype),
            pltpu.SemaphoreType.DMA((3,)),
        ],
        # In grid order throughout: dq_acc is revisited across the key
        # blocks, and the staging buffers' DMAs are waited for by the
        # program that runs next.
        compiler_params=None if interpret else _tpu_params(
            "arbitrary", "arbitrary", "arbitrary"),
        interpret=interpret,
    )
    with jax.named_scope(KERNEL_SCOPE):
        return call(_dropout_seed_arg(seed, dropout_rate), qkv, qkv, qkv, o,
                    do, lse.reshape(B, n_head, T // LANES, LANES))


@functools.partial(jax.custom_vjp, nondiff_argnums=(2, 3, 4))
def flash_attention_qkv(qkv, seed, n_head: int, dropout_rate: float = 0.0,
                        interpret: bool = False):
    """Causal flash attention on the model's own layout: qkv (B, T, 3C) as
    c_attn emits it -> o (B, T, C) as c_proj consumes it, heads of size
    C // n_head side by side in the last dimension, scores scaled by
    head_dim ** -0.5. The gradient is one dqkv (B, T, 3C). No transpose,
    pad, slice or cast around the kernels.

    seed: (1,) uint32 for in-kernel attention dropout (None when
    dropout_rate == 0); the keep-mask is hash_dropout_keep_mask's, bit for
    bit, as in flash_attention_dropout. Shapes must satisfy
    qkv_layout_supported; everything else goes through causal_attention.
    """
    return _pallas_flash_fwd_qkv(qkv, n_head, interpret=interpret,
                                 dropout_rate=dropout_rate, seed=seed)[0]


def _flash_qkv_fwd_rule(qkv, seed, n_head, dropout_rate, interpret):
    from jax.ad_checkpoint import checkpoint_name

    o, lse = _pallas_flash_fwd_qkv(qkv, n_head, interpret=interpret,
                                   dropout_rate=dropout_rate, seed=seed)
    o = checkpoint_name(o, "attn_out")  # see _flash_fwd_rule
    return o, (qkv, o, checkpoint_name(lse, "attn_lse"), seed)


def _flash_qkv_bwd_rule(n_head, dropout_rate, interpret, res, do):
    qkv, o, lse, seed = res
    dqkv = _pallas_flash_bwd_qkv(qkv, o, lse, do, n_head,
                                 interpret=interpret,
                                 dropout_rate=dropout_rate, seed=seed)
    return dqkv, None


flash_attention_qkv.defvjp(_flash_qkv_fwd_rule, _flash_qkv_bwd_rule)


# ---------------------------------------------------------------------------
# The grouped-query, windowed entry: q (B, T, H*D), k / v (B, T, G*D)
# ---------------------------------------------------------------------------
#
# A model whose query heads share KV heads (H // G query heads read one KV
# head) and whose layers bound how far back a query looks (key j visible to
# query i iff j <= i and i - j < window). Head size D % 128 == 0, so one
# 128-lane-aligned column block of the last dimension IS one head: no lane
# pairing, no zeroed lanes. The same tile functions (_fwd_tile, _bwd_tile,
# _expand_stat_tile, _stat_column_to_row) and the compact statistic layout:
#
#   * forward: grid (B, H, q blocks); k and v arrive as whole-T blocks of
#     KV head h // (H // G), so the 8 query heads of a KV head and all their
#     q blocks reuse one fetch. The key-block walk has a LOWER bound beside
#     _causal_kb_range's upper one (_window_kb_range); blocks wholly inside
#     the window and below the diagonal run unmasked.
#   * backward, ONE pass (_flash_bwd_gqa_kernel): grid (B, G, query heads
#     of the KV head, q blocks), the last two in order. A program is the
#     forward's walk with _bwd_tile: every visible score tile is computed
#     once and feeds all three gradients (five matmuls a tile). QUERY-major,
#     so dq of the q block stays in registers and is written once, and what
#     is resident is dk and dv of ONE KV head's whole sequence, float32 in
#     VMEM scratch (2 * T * D * 4 bytes: 8 MB at T = 8192, D = 128), zeroed
#     at a (b, g)'s first program and written out, scaled and cast, at its
#     last; beside them the whole-T k / v blocks (fetched once a (b, g)) and
#     the single-buffered dk / dv output blocks: 20 * T * D bytes in
#     bfloat16. The key-major order would keep a (T, (H // G) * D) float32
#     dq instead, 32 MB at T = 8192. Alone on a v5e at (2, 8192, 32 x 128)
#     on 4 KV heads (PERF.md, PR 34): 10.65 ms against the split pair's
#     18.66 with window 2048, 18.30 against 33.71 with none.
#   * backward, split (_flash_bwd_gqa_dq_kernel, then
#     _flash_bwd_gqa_dkv_kernel on a grid (B, G, key blocks, query heads of
#     the KV head, q blocks walked)): the score tile recomputed in each,
#     seven matmuls a tile, VMEM independent of T but for the whole-T k / v
#     of the dQ kernel. It runs where the one-pass kernel's whole-T blocks
#     do not fit VMEM: gqa_bwd_fused_fits, ONE predicate on (D, T, itemsize)
#     decided at trace time (T > 36,864 at D = 128 in bfloat16), recorded by
#     the trainer as trainer_init's ``gqa_bwd`` (resolve_gqa_bwd). At equal
#     blocks the two give the same three arrays bit for bit: a key block's
#     sums arrive query head outer, q block ascending, in both.

def gqa_layout_supported(head_dim: int, T: int) -> bool:
    """Whether the grouped-query kernels can walk these shapes: whole
    128-lane heads and whole 128-row blocks."""
    return head_dim % LANES == 0 and T % LANES == 0


def _window_kb_range(qi, block_q: int, block_k: int, window):
    """(first, first-unmasked) key blocks of q block qi under ``window``:
    the first block holding a key the block's first query still sees, and
    the first block every query of the block sees whole. (0, 0) without a
    window."""
    if window is None:
        return 0, 0
    first = lax.div(jnp.maximum(qi * block_q - window + 1, 0), block_k)
    whole = lax.div(jnp.maximum((qi + 1) * block_q - window, 0)
                    + block_k - 1, block_k)
    return first, whole


def _visible(q_pos, k_pos, window):
    mask = q_pos >= k_pos
    if window is not None:
        mask = jnp.logical_and(mask, q_pos - k_pos < window)
    return mask


def _walk_key_blocks(body, init, qi, block_q: int, block_k: int, window):
    """fori over the key blocks q block qi sees: masked at the window's
    edge, unmasked inside, masked at the diagonal."""
    first, whole = _window_kb_range(qi, block_q, block_k, window)
    inner_end, end = _causal_kb_range(qi, block_q, block_k)
    carry = init
    if window is not None:
        whole = jnp.minimum(jnp.maximum(whole, first), inner_end)
        carry = lax.fori_loop(first, whole,
                              functools.partial(body, masked=True), carry)
    carry = lax.fori_loop(whole, inner_end,
                          functools.partial(body, masked=False), carry)
    return lax.fori_loop(inner_end, end,
                         functools.partial(body, masked=True), carry)


def _flash_fwd_gqa_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref, *,
                          block_q: int, block_k: int, sm_scale: float,
                          window):
    """q_ref / o_ref (1, block_q, D) of query head h; k_ref / v_ref
    (1, T, D) of its KV head; lse_ref (1, 1, 1, block_q)."""
    qi = pl.program_id(2)
    q = q_ref[0]
    q_pos = qi * block_q + lax.broadcasted_iota(
        jnp.int32, (block_q, block_k), 0)

    def body(j, carry, *, masked: bool):
        k = k_ref[0, pl.ds(j * block_k, block_k), :]
        v = v_ref[0, pl.ds(j * block_k, block_k), :]
        mask = None
        if masked:
            mask = _visible(q_pos, j * block_k + lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 1), window)
        return _fwd_tile(q, k, v, carry, sm_scale=sm_scale, mask=mask)

    init = (jnp.zeros(q.shape, jnp.float32),
            jnp.full((block_q, 1), NEG_INF, jnp.float32),
            jnp.zeros((block_q, 1), jnp.float32))
    acc, m, l = _walk_key_blocks(body, init, qi, block_q, block_k, window)
    o_ref[0] = (acc / l).astype(o_ref.dtype)
    lse_ref[0, 0] = _stat_column_to_row(m + jnp.log(l))


def _flash_bwd_gqa_dq_kernel(q_ref, k_ref, v_ref, o_ref, do_ref, lse_ref,
                             dq_ref, *, block_q: int, block_k: int,
                             sm_scale: float, window):
    """dQ of one (query head, q block): the forward's walk with _bwd_tile.
    lse_ref (1, 1, T // 128, 128), the head's compact statistic."""
    qi = pl.program_id(2)
    q = q_ref[0]
    do = do_ref[0]
    drow = jnp.sum(do.astype(jnp.float32) * o_ref[0].astype(jnp.float32),
                   axis=1, keepdims=True)
    lse = _expand_stat_tile(lse_ref[0, 0], qi * (block_q // LANES), block_q)
    q_pos = qi * block_q + lax.broadcasted_iota(
        jnp.int32, (block_q, block_k), 0)

    def body(j, dq_acc, *, masked: bool):
        k = k_ref[0, pl.ds(j * block_k, block_k), :]
        v = v_ref[0, pl.ds(j * block_k, block_k), :]
        mask = None
        if masked:
            mask = _visible(q_pos, j * block_k + lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 1), window)
        _, ds = _bwd_tile(q, k, v, do, lse, drow, sm_scale=sm_scale,
                          mask=mask)
        return dq_acc + lax.dot_general(
            ds.astype(k.dtype), k, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    dq = _walk_key_blocks(body, jnp.zeros(q.shape, jnp.float32), qi,
                          block_q, block_k, window)
    dq_ref[0] = (dq * sm_scale).astype(dq_ref.dtype)


def _gqa_q_steps(T: int, block_q: int, block_k: int, window) -> int:
    """How many q blocks a key block's walk may have to visit."""
    num_qb = T // block_q
    if window is None:
        return num_qb
    return min(num_qb, (block_k + window - 2) // block_q + 2)


def _flash_bwd_gqa_dkv_kernel(q_ref, k_ref, v_ref, o_ref, do_ref, lse_ref,
                              dk_ref, dv_ref, dk_acc, dv_acc, *,
                              block_q: int, block_k: int, sm_scale: float,
                              window, num_qb: int):
    """dK / dV of one (KV head, key block), summed over the query heads
    that read the KV head (grid axis 3) and the q blocks that see the key
    block (grid axis 4: q block start + step; steps past the sequence's
    end, or past the window's reach, do nothing)."""
    ki, r, step = pl.program_id(2), pl.program_id(3), pl.program_id(4)
    last = jnp.logical_and(r == pl.num_programs(3) - 1,
                           step == pl.num_programs(4) - 1)
    i = lax.div(ki * block_k, block_q) + step

    @pl.when(jnp.logical_and(r == 0, step == 0))
    def _zero():
        dk_acc[...] = jnp.zeros_like(dk_acc)
        dv_acc[...] = jnp.zeros_like(dv_acc)

    reaches = i < num_qb
    whole = i * block_q >= (ki + 1) * block_k - 1
    if window is not None:
        reaches = jnp.logical_and(
            reaches, i * block_q - ((ki + 1) * block_k - 1) < window)
        whole = jnp.logical_and(
            whole, (i + 1) * block_q - 1 - ki * block_k < window)

    def tile(masked: bool):
        q = q_ref[0]
        do = do_ref[0]
        k = k_ref[0]
        drow = jnp.sum(do.astype(jnp.float32) * o_ref[0].astype(jnp.float32),
                       axis=1, keepdims=True)
        lse = _expand_stat_tile(lse_ref[0, 0], i * (block_q // LANES),
                                block_q)
        mask = None
        if masked:
            mask = _visible(
                i * block_q + lax.broadcasted_iota(
                    jnp.int32, (block_q, block_k), 0),
                ki * block_k + lax.broadcasted_iota(
                    jnp.int32, (block_q, block_k), 1), window)
        p, ds = _bwd_tile(q, k, v_ref[0], do, lse, drow, sm_scale=sm_scale,
                          mask=mask)
        dv_acc[...] += lax.dot_general(
            p.astype(do.dtype), do, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        dk_acc[...] += lax.dot_general(
            ds.astype(q.dtype), q, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    pl.when(jnp.logical_and(reaches, whole))(
        functools.partial(tile, False))
    pl.when(jnp.logical_and(reaches, jnp.logical_not(whole)))(
        functools.partial(tile, True))

    @pl.when(last)
    def _write():
        dk_ref[0] = (dk_acc[...] * sm_scale).astype(dk_ref.dtype)
        dv_ref[0] = dv_acc[...].astype(dv_ref.dtype)


def _flash_bwd_gqa_kernel(q_ref, k_ref, v_ref, o_ref, do_ref, lse_ref,
                          dq_ref, dk_ref, dv_ref, dk_acc, dv_acc, *,
                          block_q: int, block_k: int, sm_scale: float,
                          window):
    """dQ of one (query head, q block) and its share of dK / dV of the KV
    head: the forward's walk with _bwd_tile, every visible score tile
    computed ONCE. Grid (B, G, H // G, q blocks), the last two in order:
    q / o / do / dq_ref (1, block_q, D) of query head g * rep + r;
    k / v / dk / dv_ref (1, T, D) of KV head g, resident over the (r, q
    block) programs of a (b, g); lse_ref (1, 1, T // 128, 128), the query
    head's compact statistic; dk_acc / dv_acc (T, D) float32, zeroed at
    the first program of a (b, g) and written out at the last. A key
    block's sums arrive query head outer, q block ascending."""
    r, qi = pl.program_id(2), pl.program_id(3)
    num_kb = dk_acc.shape[0] // block_k

    def key_rows(j):
        return pl.ds(pl.multiple_of(j * block_k, block_k), block_k)

    @pl.when(jnp.logical_and(r == 0, qi == 0))
    def _zero():
        def zero(j, _):
            zeros = jnp.zeros((block_k, dk_acc.shape[1]), jnp.float32)
            dk_acc[key_rows(j), :] = zeros
            dv_acc[key_rows(j), :] = zeros
        lax.fori_loop(0, num_kb, zero, None)

    q = q_ref[0]
    do = do_ref[0]
    drow = jnp.sum(do.astype(jnp.float32) * o_ref[0].astype(jnp.float32),
                   axis=1, keepdims=True)
    lse = _expand_stat_tile(lse_ref[0, 0], qi * (block_q // LANES), block_q)
    q_pos = qi * block_q + lax.broadcasted_iota(
        jnp.int32, (block_q, block_k), 0)

    def body(j, dq_acc, *, masked: bool):
        rows = key_rows(j)
        k = k_ref[0, rows, :]
        v = v_ref[0, rows, :]
        mask = None
        if masked:
            mask = _visible(q_pos, j * block_k + lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 1), window)
        p, ds = _bwd_tile(q, k, v, do, lse, drow, sm_scale=sm_scale,
                          mask=mask)
        ds = ds.astype(q.dtype)
        dv_acc[rows, :] += lax.dot_general(
            p.astype(do.dtype), do, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        dk_acc[rows, :] += lax.dot_general(
            ds, q, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        return dq_acc + lax.dot_general(
            ds, k, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    dq = _walk_key_blocks(body, jnp.zeros(q.shape, jnp.float32), qi,
                          block_q, block_k, window)
    dq_ref[0] = (dq * sm_scale).astype(dq_ref.dtype)

    @pl.when(jnp.logical_and(r == pl.num_programs(2) - 1,
                             qi == pl.num_programs(3) - 1))
    def _write():
        def write(j, _):
            rows = key_rows(j)
            dk_ref[0, rows, :] = (dk_acc[rows, :] * sm_scale).astype(
                dk_ref.dtype)
            dv_ref[0, rows, :] = dv_acc[rows, :].astype(dv_ref.dtype)
        lax.fori_loop(0, num_kb, write, None)


def _gqa_geometry(q_shape, k_shape, n_head: int, n_kv_head: int):
    B, T, HD = q_shape
    D = HD // n_head
    if (n_head % n_kv_head or k_shape != (B, T, n_kv_head * D)
            or not gqa_layout_supported(D, T)):
        raise ValueError(
            f"flash_attention_gqa needs q (B, T, H*D), k / v (B, T, G*D) "
            f"with G dividing H, D % {LANES} == 0 and T % {LANES} == 0; got "
            f"q {q_shape}, k {k_shape}, H={n_head}, G={n_kv_head}")
    return B, T, D, n_head // n_kv_head


@functools.partial(jax.jit, static_argnames=(
    "n_head", "n_kv_head", "window", "interpret", "scope"))
def _pallas_flash_fwd_gqa(q, k, v, *, n_head: int, n_kv_head: int, window,
                          interpret: bool = False, scope: str = KERNEL_SCOPE):
    """-> (o (B, T, H*D), lse (B, H, 1, T) f32). Jitted like the (B, T, 3C)
    calls: layers of one kind share one trace and one lowering; ``scope``
    names the custom call (%<scope>.N) and its part in obs.opscopes."""
    B, T, D, rep = _gqa_geometry(q.shape, k.shape, n_head, n_kv_head)
    block_q, block_k = _clamp_blocks(T, DEFAULT_BLOCK, DEFAULT_BLOCK)
    kernel = functools.partial(
        _flash_fwd_gqa_kernel, block_q=block_q, block_k=block_k,
        sm_scale=D ** -0.5, window=window)
    kv_spec = pl.BlockSpec((1, T, D), lambda b, h, i: (b, 0, h // rep))
    call = pl.pallas_call(
        kernel,
        grid=(B, n_head, T // block_q),
        in_specs=[pl.BlockSpec((1, block_q, D), lambda b, h, i: (b, i, h)),
                  kv_spec, kv_spec],
        out_specs=[
            pl.BlockSpec((1, block_q, D), lambda b, h, i: (b, i, h)),
            pl.BlockSpec((1, 1, 1, block_q), lambda b, h, i: (b, h, 0, i)),
        ],
        out_shape=[jax.ShapeDtypeStruct(q.shape, q.dtype),
                   jax.ShapeDtypeStruct((B, n_head, 1, T), jnp.float32)],
        compiler_params=None if interpret else _tpu_params(
            "parallel", "parallel", "parallel"),
        interpret=interpret,
    )
    with jax.named_scope(scope):
        return call(q, k, v)


# The one-pass backward's own blocks (the forward keeps DEFAULT_BLOCK), from
# a probe of ten blockings on a v5e at the shapes above (PERF.md, PR 34):
# 512 x 512 10.65 / 18.30 ms (window 2048 / none); 1024 x 1024 11.98 / 19.05;
# 512 x 256 12.24 / 21.70; 256 x 512 12.54 / 21.88; 256 x 256 13.73 / 25.90:
# a narrower block visits fewer part-masked tiles and loses more to the
# shorter matmuls.
GQA_BWD_BLOCK_Q = 512
GQA_BWD_BLOCK_K = 512
# What the one-pass backward may keep resident under _tpu_params' limit; the
# 10 MiB left hold the q / o / dO / dQ blocks in flight and the score tiles.
GQA_BWD_RESIDENT_BYTES = VMEM_LIMIT_BYTES - 10 * 1024 * 1024


def gqa_bwd_fused_fits(head_dim: int, T: int, itemsize: int = 2) -> bool:
    """Whether the one-pass backward's whole-T blocks of ONE KV head fit
    VMEM: k and v double-buffered, dk and dv single (written once a KV
    head), and the two float32 accumulators. From the shapes alone, at
    trace time; itemsize is the operands' (2: bfloat16)."""
    return T * head_dim * (6 * itemsize + 8) <= GQA_BWD_RESIDENT_BYTES


def _gqa_bwd_fused(q, k, v, o, stats, do, *, n_head, n_kv_head, window,
                   interpret):
    """(dq, dk, dv) by the one-pass kernel; stats is the compact
    (B, H, T // 128, 128) statistic."""
    B, T, D, rep = _gqa_geometry(q.shape, k.shape, n_head, n_kv_head)
    block_q, block_k = _clamp_blocks(T, GQA_BWD_BLOCK_Q, GQA_BWD_BLOCK_K)
    q_blk = pl.BlockSpec((1, block_q, D),
                         lambda b, g, r, i: (b, i, g * rep + r))
    kv_all = pl.BlockSpec((1, T, D), lambda b, g, r, i: (b, 0, g))
    kv_out = pl.BlockSpec((1, T, D), lambda b, g, r, i: (b, 0, g),
                          pipeline_mode=pl.Buffered(1))
    return pl.pallas_call(
        functools.partial(_flash_bwd_gqa_kernel, block_q=block_q,
                          block_k=block_k, sm_scale=D ** -0.5,
                          window=window),
        grid=(B, n_kv_head, rep, T // block_q),
        in_specs=[q_blk, kv_all, kv_all, q_blk, q_blk,
                  pl.BlockSpec((1, 1, T // LANES, LANES),
                               lambda b, g, r, i: (b, g * rep + r, 0, 0))],
        out_specs=[q_blk, kv_out, kv_out],
        out_shape=[jax.ShapeDtypeStruct(q.shape, q.dtype),
                   jax.ShapeDtypeStruct(k.shape, k.dtype),
                   jax.ShapeDtypeStruct(v.shape, v.dtype)],
        scratch_shapes=[pltpu.VMEM((T, D), jnp.float32),
                        pltpu.VMEM((T, D), jnp.float32)],
        compiler_params=None if interpret else _tpu_params(
            "parallel", "parallel", "arbitrary", "arbitrary"),
        interpret=interpret,
    )(q, k, v, o, do, stats)


def _gqa_bwd_split(q, k, v, o, stats, do, *, n_head, n_kv_head, window,
                   interpret):
    """(dq, dk, dv) by the dQ kernel, then the dK/dV kernel."""
    B, T, D, rep = _gqa_geometry(q.shape, k.shape, n_head, n_kv_head)
    block_q, block_k = _clamp_blocks(T, DEFAULT_BLOCK, DEFAULT_BLOCK)
    num_qb = T // block_q
    common = dict(block_q=block_q, block_k=block_k, sm_scale=D ** -0.5,
                  window=window)
    params = lambda *sem: None if interpret else _tpu_params(*sem)

    q_blk = pl.BlockSpec((1, block_q, D), lambda b, h, i: (b, i, h))
    kv_all = pl.BlockSpec((1, T, D), lambda b, h, i: (b, 0, h // rep))
    dq_call = pl.pallas_call(
        functools.partial(_flash_bwd_gqa_dq_kernel, **common),
        grid=(B, n_head, num_qb),
        in_specs=[q_blk, kv_all, kv_all, q_blk, q_blk,
                  pl.BlockSpec((1, 1, T // LANES, LANES),
                               lambda b, h, i: (b, h, 0, 0))],
        out_specs=q_blk,
        out_shape=jax.ShapeDtypeStruct(q.shape, q.dtype),
        compiler_params=params("parallel", "parallel", "parallel"),
        interpret=interpret,
    )

    def q_rows(b, g, j, r, s):
        i = jnp.minimum(lax.div(j * block_k, block_q) + s, num_qb - 1)
        return (b, i, g * rep + r)

    q_step = pl.BlockSpec((1, block_q, D), q_rows)
    kv_blk = pl.BlockSpec((1, block_k, D), lambda b, g, j, r, s: (b, j, g))
    dkv_call = pl.pallas_call(
        functools.partial(_flash_bwd_gqa_dkv_kernel, num_qb=num_qb,
                          **common),
        grid=(B, n_kv_head, T // block_k, rep,
              _gqa_q_steps(T, block_q, block_k, window)),
        in_specs=[q_step, kv_blk, kv_blk, q_step, q_step,
                  pl.BlockSpec((1, 1, T // LANES, LANES),
                               lambda b, g, j, r, s: (b, g * rep + r, 0, 0))],
        out_specs=[kv_blk, kv_blk],
        out_shape=[jax.ShapeDtypeStruct(k.shape, k.dtype),
                   jax.ShapeDtypeStruct(v.shape, v.dtype)],
        scratch_shapes=[pltpu.VMEM((block_k, D), jnp.float32),
                        pltpu.VMEM((block_k, D), jnp.float32)],
        compiler_params=params("parallel", "parallel", "parallel",
                               "arbitrary", "arbitrary"),
        interpret=interpret,
    )
    dq = dq_call(q, k, v, o, do, stats)
    dk, dv = dkv_call(q, k, v, o, do, stats)
    return dq, dk, dv


@functools.partial(jax.jit, static_argnames=(
    "n_head", "n_kv_head", "window", "interpret", "scope"))
def _pallas_flash_bwd_gqa(q, k, v, o, lse, do, *, n_head: int,
                          n_kv_head: int, window, interpret: bool = False,
                          scope: str = KERNEL_SCOPE):
    """-> (dq, dk, dv) in the operands' shapes and dtypes: the one-pass
    kernel where its whole-T blocks fit VMEM, the split pair elsewhere."""
    B, T, D, _ = _gqa_geometry(q.shape, k.shape, n_head, n_kv_head)
    stats = lse.reshape(B, n_head, T // LANES, LANES)
    bwd = (_gqa_bwd_fused if gqa_bwd_fused_fits(D, T, k.dtype.itemsize)
           else _gqa_bwd_split)
    with jax.named_scope(scope):
        return bwd(q, k, v, o, stats, do, n_head=n_head,
                   n_kv_head=n_kv_head, window=window, interpret=interpret)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7))
def flash_attention_gqa(q, k, v, n_head: int, n_kv_head: int,
                        window: int | None = None, interpret: bool = False,
                        scope: str = KERNEL_SCOPE):
    """Causal flash attention with grouped KV heads and an optional window,
    on the projections' own layout: q (B, T, H*D), k / v (B, T, G*D) ->
    o (B, T, H*D); query head h reads KV head h // (H // G); key j is
    visible to query i iff j <= i and (window is None or i - j < window).
    Scores are scaled by D ** -0.5. Shapes must satisfy
    gqa_layout_supported."""
    return _pallas_flash_fwd_gqa(q, k, v, n_head=n_head, n_kv_head=n_kv_head,
                                 window=window, interpret=interpret,
                                 scope=scope)[0]


def _flash_gqa_fwd_rule(q, k, v, n_head, n_kv_head, window, interpret, scope):
    from jax.ad_checkpoint import checkpoint_name

    o, lse = _pallas_flash_fwd_gqa(q, k, v, n_head=n_head,
                                   n_kv_head=n_kv_head, window=window,
                                   interpret=interpret, scope=scope)
    o = checkpoint_name(o, "attn_out")  # see _flash_fwd_rule
    return o, (q, k, v, o, checkpoint_name(lse, "attn_lse"))


def _flash_gqa_bwd_rule(n_head, n_kv_head, window, interpret, scope, res, do):
    q, k, v, o, lse = res
    return _pallas_flash_bwd_gqa(q, k, v, o, lse, do, n_head=n_head,
                                 n_kv_head=n_kv_head, window=window,
                                 interpret=interpret, scope=scope)


flash_attention_gqa.defvjp(_flash_gqa_fwd_rule, _flash_gqa_bwd_rule)


# ---------------------------------------------------------------------------
# Latent attention (MLA): a split query/key head, one rotary key for all heads
# ---------------------------------------------------------------------------
#
# DeepSeek-V3's attention (models/deepseek_v3.py) scores a query against a key
# over TWO parts: D content lanes a head ("nope": 128) and R rotary lanes
# ("pe": 64) whose key is ONE head that all H query heads read; the value head
# has D lanes. s = (q_nope k_nope^T + q_pe k_pe^T) / sqrt(D + R). No entry
# above takes it: they contract q and k over one width and use it for v.
#
#   q_nope, k_nope, v, o   (B, T, H*D)   the projections' own layout
#   q_pe                   (B, H, T, R)  a 64-lane block is legal only as an
#                                        array's WHOLE minor dimension; the
#                                        rotary pass writes q_pe anew anyway
#   k_pe                   (B, T, R)     read where it lies, never repeated
#
# The kernels are the grouped-query ones at one KV head a query head, with
# _fwd_tile / _bwd_tile's second (q, k) pair: the score tile is two
# contractions (D lanes, R lanes) summed in float32 before the scale, then
# ONE softmax pass; P V and dP run at D. No (B, H, T, T) array and no
# concatenated (B, T, H*(D+R)) key exists anywhere.
#
#   * forward: grid (B, H, q blocks); k_nope / v arrive as whole-T blocks of
#     head h, k_pe as the one whole-T block of the batch row.
#   * backward, ONE pass (the design of _flash_bwd_gqa_kernel): grid (B, H,
#     q blocks), the last in order. Every visible score tile is computed once
#     and feeds all five gradients (eight matmuls a tile: two for the score,
#     dP, dV, dQ_nope, dQ_pe, dK_nope, dK_pe). Resident over a (b, h): whole-T
#     k_nope / v / dk_nope / dv of ONE head and their float32 accumulators
#     (20 * T * D bytes in bfloat16), and the rope's k_pe and dk_pe
#     accumulator. dk_pe is a PER-HEAD PARTIAL, (B, H, T, R) float32: head
#     h's ds^T q_pe; XLA sums the H partials and casts once (the head axis
#     stays 'parallel', and the sum is exact to one rounding).
#   mla_layout_supported is the one predicate on the shapes (whole 128-lane
#   content and value heads, whole 128-row blocks, the backward's resident
#   blocks inside VMEM: T <= 23,040 at D = 128, R = 64 in bfloat16); what it
#   turns away (a trainer's 8-token init batch, the CPU) runs xla_attention
#   on the concatenated heads (mla_route).

MLA_SCOPE = "attn_mla"   # names the custom calls: %attn_mla.N


def mla_layout_supported(head_dim: int, rope_dim: int, v_dim: int, T: int,
                         itemsize: int = 2) -> bool:
    """Whether the latent kernels can walk these shapes: content and value
    heads of the same whole 128-lane width, a rotary part that fits one
    lane tile, whole 128-row blocks, and the one-pass backward's whole-T
    blocks of ONE head inside VMEM: k_nope and v double-buffered, dk_nope and
    dv single, their two float32 accumulators, and the rope's k_pe (double),
    dk_pe block and accumulator (float32), each padded to 128 lanes."""
    resident = T * (head_dim * (6 * itemsize + 8)
                    + LANES * (2 * itemsize + 8))
    return (head_dim % LANES == 0 and v_dim == head_dim
            and 0 < rope_dim <= LANES and rope_dim % 8 == 0
            and T % LANES == 0 and resident <= GQA_BWD_RESIDENT_BYTES)


def _mla_geometry(q_nope, q_pe, k_nope, k_pe, v, n_head: int):
    B, T, HD = q_nope.shape
    D, R = HD // n_head, k_pe.shape[-1]
    if (HD % n_head or q_pe.shape != (B, n_head, T, R)
            or k_nope.shape != (B, T, HD) or k_pe.shape != (B, T, R)
            or v.shape != (B, T, HD)
            or not mla_layout_supported(D, R, D, T, k_nope.dtype.itemsize)):
        raise ValueError(
            f"flash_attention_mla needs q_nope / k_nope / v (B, T, H*D), "
            f"q_pe (B, H, T, R), k_pe (B, T, R) with D % {LANES} == 0, "
            f"R <= {LANES}, T % {LANES} == 0 and a T whose one-pass backward "
            f"fits VMEM (mla_layout_supported); got q_nope {q_nope.shape}, "
            f"q_pe {q_pe.shape}, k_nope {k_nope.shape}, k_pe {k_pe.shape}, "
            f"v {v.shape}, H={n_head}")
    return B, T, D, R


def _flash_fwd_mla_kernel(q_ref, qpe_ref, k_ref, kpe_ref, v_ref, o_ref,
                          lse_ref, *, block_q: int, block_k: int,
                          sm_scale: float):
    """q_ref / o_ref (1, block_q, D) and qpe_ref (1, 1, block_q, R) of head
    h; k_ref / v_ref (1, T, D) of head h; kpe_ref (1, T, R), the batch row's
    one rotary key; lse_ref (1, 1, 1, block_q)."""
    qi = pl.program_id(2)
    q, qpe = q_ref[0], qpe_ref[0, 0]
    q_pos = qi * block_q + lax.broadcasted_iota(
        jnp.int32, (block_q, block_k), 0)

    def body(j, carry, *, masked: bool):
        rows = pl.ds(j * block_k, block_k)
        mask = None
        if masked:
            mask = _visible(q_pos, j * block_k + lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 1), None)
        return _fwd_tile(q, k_ref[0, rows, :], v_ref[0, rows, :], carry,
                         sm_scale=sm_scale, mask=mask,
                         qk2=(qpe, kpe_ref[0, rows, :]))

    init = (jnp.zeros(q.shape, jnp.float32),
            jnp.full((block_q, 1), NEG_INF, jnp.float32),
            jnp.zeros((block_q, 1), jnp.float32))
    acc, m, l = _walk_key_blocks(body, init, qi, block_q, block_k, None)
    o_ref[0] = (acc / l).astype(o_ref.dtype)
    lse_ref[0, 0] = _stat_column_to_row(m + jnp.log(l))


def _flash_bwd_mla_kernel(q_ref, qpe_ref, k_ref, kpe_ref, v_ref, o_ref,
                          do_ref, lse_ref, dq_ref, dqpe_ref, dk_ref,
                          dkpe_ref, dv_ref, dk_acc, dkpe_acc, dv_acc, *,
                          block_q: int, block_k: int, sm_scale: float):
    """dQ_nope and dQ_pe of one (head, q block) and its share of the head's
    dK_nope, dV and dK_pe partial: the forward's walk with _bwd_tile, every
    visible score tile computed ONCE. Grid (B, H, q blocks), the last in
    order. q / o / do / dq_ref (1, block_q, D); qpe / dqpe_ref
    (1, 1, block_q, R); k / v / dk / dv_ref (1, T, D) of head h and kpe_ref
    (1, T, R), resident over a (b, h)'s programs; dkpe_ref (1, 1, T, R)
    float32, the head's partial; lse_ref (1, 1, T // 128, 128); the three
    accumulators float32, zeroed at a (b, h)'s first program and written out
    at its last."""
    qi = pl.program_id(2)
    num_kb = dk_acc.shape[0] // block_k
    accs = (dk_acc, dkpe_acc, dv_acc)

    def key_rows(j):
        return pl.ds(pl.multiple_of(j * block_k, block_k), block_k)

    @pl.when(qi == 0)
    def _zero():
        def zero(j, _):
            for acc in accs:
                acc[key_rows(j), :] = jnp.zeros((block_k, acc.shape[1]),
                                                jnp.float32)
        lax.fori_loop(0, num_kb, zero, None)

    q, qpe, do = q_ref[0], qpe_ref[0, 0], do_ref[0]
    drow = jnp.sum(do.astype(jnp.float32) * o_ref[0].astype(jnp.float32),
                   axis=1, keepdims=True)
    lse = _expand_stat_tile(lse_ref[0, 0], qi * (block_q // LANES), block_q)
    q_pos = qi * block_q + lax.broadcasted_iota(
        jnp.int32, (block_q, block_k), 0)
    by_key = lambda a, b: lax.dot_general(        # a^T b: (bk, width)
        a, b, (((0,), (0,)), ((), ())), preferred_element_type=jnp.float32)
    by_query = lambda a, b: lax.dot_general(      # a b: (bq, width)
        a, b, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)

    def body(j, carry, *, masked: bool):
        dq_acc, dqpe_acc = carry
        rows = key_rows(j)
        k, kpe, v = k_ref[0, rows, :], kpe_ref[0, rows, :], v_ref[0, rows, :]
        mask = None
        if masked:
            mask = _visible(q_pos, j * block_k + lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 1), None)
        p, ds = _bwd_tile(q, k, v, do, lse, drow, sm_scale=sm_scale,
                          mask=mask, qk2=(qpe, kpe))
        ds = ds.astype(q.dtype)
        dv_acc[rows, :] += by_key(p.astype(do.dtype), do)
        dk_acc[rows, :] += by_key(ds, q)
        dkpe_acc[rows, :] += by_key(ds, qpe)
        return dq_acc + by_query(ds, k), dqpe_acc + by_query(ds, kpe)

    dq, dqpe = _walk_key_blocks(
        body, (jnp.zeros(q.shape, jnp.float32),
               jnp.zeros(qpe.shape, jnp.float32)), qi, block_q, block_k, None)
    dq_ref[0] = (dq * sm_scale).astype(dq_ref.dtype)
    dqpe_ref[0, 0] = (dqpe * sm_scale).astype(dqpe_ref.dtype)

    @pl.when(qi == pl.num_programs(2) - 1)
    def _write():
        def write(j, _):
            rows = key_rows(j)
            dk_ref[0, rows, :] = (dk_acc[rows, :] * sm_scale).astype(
                dk_ref.dtype)
            dkpe_ref[0, 0, rows, :] = dkpe_acc[rows, :] * sm_scale
            dv_ref[0, rows, :] = dv_acc[rows, :].astype(dv_ref.dtype)
        lax.fori_loop(0, num_kb, write, None)


@functools.partial(jax.jit, static_argnames=("n_head", "interpret", "scope"))
def _pallas_flash_fwd_mla(q_nope, q_pe, k_nope, k_pe, v, *, n_head: int,
                          interpret: bool = False, scope: str = MLA_SCOPE):
    """-> (o (B, T, H*D), lse (B, H, 1, T) f32). Jitted like the grouped-
    query calls: a model's layers share one trace and one lowering; ``scope``
    names the custom call (%<scope>.N) and its part in obs.opscopes."""
    B, T, D, R = _mla_geometry(q_nope, q_pe, k_nope, k_pe, v, n_head)
    block_q, block_k = _clamp_blocks(T, DEFAULT_BLOCK, DEFAULT_BLOCK)
    q_blk = pl.BlockSpec((1, block_q, D), lambda b, h, i: (b, i, h))
    kv_all = pl.BlockSpec((1, T, D), lambda b, h, i: (b, 0, h))
    call = pl.pallas_call(
        functools.partial(_flash_fwd_mla_kernel, block_q=block_q,
                          block_k=block_k, sm_scale=(D + R) ** -0.5),
        grid=(B, n_head, T // block_q),
        in_specs=[q_blk,
                  pl.BlockSpec((1, 1, block_q, R),
                               lambda b, h, i: (b, h, i, 0)),
                  kv_all,
                  pl.BlockSpec((1, T, R), lambda b, h, i: (b, 0, 0)),
                  kv_all],
        out_specs=[
            q_blk,
            pl.BlockSpec((1, 1, 1, block_q), lambda b, h, i: (b, h, 0, i)),
        ],
        out_shape=[jax.ShapeDtypeStruct(v.shape, v.dtype),
                   jax.ShapeDtypeStruct((B, n_head, 1, T), jnp.float32)],
        compiler_params=None if interpret else _tpu_params(
            "parallel", "parallel", "parallel"),
        interpret=interpret,
    )
    with jax.named_scope(scope):
        return call(q_nope, q_pe, k_nope, k_pe, v)


@functools.partial(jax.jit, static_argnames=("n_head", "interpret", "scope"))
def _pallas_flash_bwd_mla(q_nope, q_pe, k_nope, k_pe, v, o, lse, do, *,
                          n_head: int, interpret: bool = False,
                          scope: str = MLA_SCOPE):
    """-> (dq_nope, dq_pe, dk_nope, dk_pe, dv) in the operands' shapes and
    dtypes, by the one-pass kernel; dk_pe is the sum of its H float32
    partials."""
    B, T, D, R = _mla_geometry(q_nope, q_pe, k_nope, k_pe, v, n_head)
    block_q, block_k = _clamp_blocks(T, GQA_BWD_BLOCK_Q, GQA_BWD_BLOCK_K)
    once = dict(pipeline_mode=pl.Buffered(1))
    q_blk = pl.BlockSpec((1, block_q, D), lambda b, h, i: (b, i, h))
    qpe_blk = pl.BlockSpec((1, 1, block_q, R), lambda b, h, i: (b, h, i, 0))
    kv_all = pl.BlockSpec((1, T, D), lambda b, h, i: (b, 0, h))
    kv_out = pl.BlockSpec((1, T, D), lambda b, h, i: (b, 0, h), **once)
    call = pl.pallas_call(
        functools.partial(_flash_bwd_mla_kernel, block_q=block_q,
                          block_k=block_k, sm_scale=(D + R) ** -0.5),
        grid=(B, n_head, T // block_q),
        in_specs=[q_blk, qpe_blk, kv_all,
                  pl.BlockSpec((1, T, R), lambda b, h, i: (b, 0, 0)),
                  kv_all, q_blk, q_blk,
                  pl.BlockSpec((1, 1, T // LANES, LANES),
                               lambda b, h, i: (b, h, 0, 0))],
        out_specs=[q_blk, qpe_blk, kv_out,
                   pl.BlockSpec((1, 1, T, R), lambda b, h, i: (b, h, 0, 0),
                                **once),
                   kv_out],
        out_shape=[jax.ShapeDtypeStruct(q_nope.shape, q_nope.dtype),
                   jax.ShapeDtypeStruct(q_pe.shape, q_pe.dtype),
                   jax.ShapeDtypeStruct(k_nope.shape, k_nope.dtype),
                   jax.ShapeDtypeStruct((B, n_head, T, R), jnp.float32),
                   jax.ShapeDtypeStruct(v.shape, v.dtype)],
        scratch_shapes=[pltpu.VMEM((T, D), jnp.float32),
                        pltpu.VMEM((T, R), jnp.float32),
                        pltpu.VMEM((T, D), jnp.float32)],
        compiler_params=None if interpret else _tpu_params(
            "parallel", "parallel", "arbitrary"),
        interpret=interpret,
    )
    stats = lse.reshape(B, n_head, T // LANES, LANES)
    with jax.named_scope(scope):
        dq, dqpe, dk, dkpe, dv = call(q_nope, q_pe, k_nope, k_pe, v, o, do,
                                      stats)
    return dq, dqpe, dk, jnp.sum(dkpe, axis=1).astype(k_pe.dtype), dv


@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6, 7))
def flash_attention_mla(q_nope, q_pe, k_nope, k_pe, v, n_head: int,
                        interpret: bool = False, scope: str = MLA_SCOPE):
    """Causal flash attention with a split query/key head and ONE rotary key
    for all heads: q_nope / k_nope / v (B, T, H*D), q_pe (B, H, T, R),
    k_pe (B, T, R) -> o (B, T, H*D); head h scores query i against key
    j <= i as (q_nope_h[i] . k_nope_h[j] + q_pe_h[i] . k_pe[j]) *
    (D + R) ** -0.5. Shapes must satisfy mla_layout_supported."""
    return _pallas_flash_fwd_mla(q_nope, q_pe, k_nope, k_pe, v,
                                 n_head=n_head, interpret=interpret,
                                 scope=scope)[0]


def _flash_mla_fwd_rule(q_nope, q_pe, k_nope, k_pe, v, n_head, interpret,
                        scope):
    from jax.ad_checkpoint import checkpoint_name

    o, lse = _pallas_flash_fwd_mla(q_nope, q_pe, k_nope, k_pe, v,
                                   n_head=n_head, interpret=interpret,
                                   scope=scope)
    o = checkpoint_name(o, "attn_out")  # see _flash_fwd_rule
    return o, (q_nope, q_pe, k_nope, k_pe, v, o,
               checkpoint_name(lse, "attn_lse"))


def _flash_mla_bwd_rule(n_head, interpret, scope, res, do):
    return _pallas_flash_bwd_mla(*res, do, n_head=n_head,
                                 interpret=interpret, scope=scope)


flash_attention_mla.defvjp(_flash_mla_fwd_rule, _flash_mla_bwd_rule)


# ---------------------------------------------------------------------------
# The prologue of that entry: head RMSNorm, then rotary positions, in place
# ---------------------------------------------------------------------------
#
# q and k leave their projections as (B, T, heads*D) in the compute dtype and
# enter the kernels above in the same layout and dtype; between the two every
# head's D lanes are normalised (one scale of D shared by the heads) and, in
# layers that carry positions, rotated (rotate-half over all of D). Both
# cross the lanes of a head, which XLA does as products with constant
# (D, D) matrices at full float32 precision over float32 (B, T, heads, D)
# arrays in HBM (models/afmoe.py's XLA path). Here a (rows, heads * D) block
# is read once, in the dtype it has, and written once: float32 in registers,
# the mean of squares a lane reduce and rotate-half a lane roll by D / 2.
# The backward is one pass as well: it recomputes the norm from the saved
# input, and leaves the scale's gradient as one (1, D) partial sum a program.
# A model with no q/k norm (models/ouro.py) takes the rotation alone
# (qk_rotary): the forward kernel with no scale, and as its backward the same
# kernel turning the other way, since a rotation's transpose is its inverse.

QK_PREP_SCOPE = "qk_prep"   # names the custom calls: %qk_prep.N
# A program's block, the largest of each that divides T and the heads. On a
# v5e at (2, 8192, 32 * 128) the forward / backward take 0.44 / 0.68 ms at
# 1024 rows x 4 heads against 0.33 / 0.49 ms of traffic at the memory's
# peak; 512 x 4 is 0.49 / 0.73, 256 x 4 0.59 / 0.80, 512 x 2 0.60 / 0.84,
# 2048 x 4 0.44 / 0.66 (PERF.md §6, PR 30).
PREP_BLOCK_ROWS = (1024, 512, 256, LANES)
PREP_BLOCK_HEADS = (4, 2, 1)


def rotary_table(T: int, D: int, theta: float):
    """(cos, sin), each (T, D) float32, of positions 0..T-1 for rotate-half
    rotary over all D dimensions: both halves carry the same D / 2 angles."""
    inv_freq = theta ** (-jnp.arange(0, D, 2, dtype=jnp.float32) / D)
    angle = jnp.arange(T, dtype=jnp.float32)[:, None] * inv_freq[None]
    return (jnp.concatenate([jnp.cos(angle)] * 2, -1),
            jnp.concatenate([jnp.sin(angle)] * 2, -1))


def _rotate_half_sin(T: int, D: int, theta: float, inverse: bool = False):
    """(cos, sin with the first half negated): rotate_half(y) * sin is
    roll(y, D / 2) * that, the sign moved onto the table. ``inverse``: the
    table of the opposite angles (sin negated as well)."""
    cos, sin = rotary_table(T, D, theta)
    sign = jnp.where(jnp.arange(D) < D // 2, -1.0, 1.0)
    return cos, sin * (-sign if inverse else sign)


def _qk_prep_fwd_kernel(x_ref, *refs, heads: int, D: int,
                        eps: float | None):
    """x_ref / o_ref (1, rows, heads * D); where eps is given (the norm),
    scale_ref (1, D) float32 next; with positions, cos_ref / sin_ref
    (rows, D) before o_ref."""
    if eps is not None:
        scale_ref, *refs = refs
        scale = scale_ref[...]
    *table, o_ref = refs
    for h in range(heads):
        lanes = slice(h * D, (h + 1) * D)
        y = x = x_ref[0, :, lanes].astype(jnp.float32)
        if eps is not None:
            mean_sq = jnp.sum(x * x, axis=1, keepdims=True) * (1.0 / D)
            y = x * lax.rsqrt(mean_sq + eps) * scale
        if table:
            cos_ref, sin_ref = table
            y = y * cos_ref[...] + pltpu.roll(y, D // 2, 1) * sin_ref[...]
        o_ref[0, :, lanes] = y.astype(o_ref.dtype)


def _qk_prep_bwd_kernel(x_ref, scale_ref, dz_ref, *refs, heads: int, D: int,
                        eps: float):
    """dx_ref like x_ref; dscale_ref (1, 1, 1, 1, D): this program's sum
    over its rows and heads of dy * normalised x."""
    *table, dx_ref, dscale_ref = refs
    scale = scale_ref[...]
    dscale = jnp.zeros((1, D), jnp.float32)
    for h in range(heads):
        lanes = slice(h * D, (h + 1) * D)
        x = x_ref[0, :, lanes].astype(jnp.float32)
        mean_sq = jnp.sum(x * x, axis=1, keepdims=True) * (1.0 / D)
        r = lax.rsqrt(mean_sq + eps)
        n = x * r
        dy = dz_ref[0, :, lanes].astype(jnp.float32)
        if table:  # the transpose of rotate-half is its negative
            cos_ref, sin_ref = table
            dy = dy * cos_ref[...] - pltpu.roll(dy, D // 2, 1) * sin_ref[...]
        dscale = dscale + jnp.sum(dy * n, axis=0, keepdims=True)
        dn = dy * scale
        proj = jnp.sum(dn * n, axis=1, keepdims=True) * (1.0 / D)
        dx_ref[0, :, lanes] = (r * (dn - n * proj)).astype(dx_ref.dtype)
    dscale_ref[0, 0, 0] = dscale


@functools.partial(jax.jit, static_argnames=(
    "n_head", "eps", "theta", "interpret", "inverse"))
def _pallas_qk_prep(x, scale, dz=None, *, n_head: int, eps: float | None,
                    theta, interpret: bool = False, inverse: bool = False):
    """The forward (-> z like x) or, given the cotangent dz of z, the
    backward (-> dx like x, dscale like scale). With no scale (and no eps)
    the forward alone, the rotation without the norm; ``inverse`` turns it
    by the opposite angles, which is its backward. Grid (B, row blocks, head
    groups), the head groups innermost so that a row block's table is
    fetched once. Jitted like the kernel calls round it: one trace and one
    lowering a (shape, pass, norm or not, positions or not) variant,
    whatever the number of layers."""
    B, T, HD = x.shape
    D = HD // n_head
    if HD != n_head * D or (scale is not None and scale.shape != (D,)) or (
            not gqa_layout_supported(D, T)):
        raise ValueError(
            f"qk_prep needs x (B, T, heads*D), and scale (D,) where it "
            f"normalises, with D % {LANES} == 0 and T % {LANES} == 0; got x "
            f"{x.shape}, scale {None if scale is None else scale.shape}, "
            f"heads={n_head}")
    backward = dz is not None
    rows = next(r for r in PREP_BLOCK_ROWS if T % r == 0)
    heads = next(h for h in PREP_BLOCK_HEADS if n_head % h == 0)
    grid = (B, T // rows, n_head // heads)
    blk = pl.BlockSpec((1, rows, heads * D), lambda b, i, g: (b, i, g))
    operands, in_specs = [x], [blk]
    if scale is not None:
        operands.append(scale.astype(jnp.float32).reshape(1, D))
        in_specs.append(pl.BlockSpec((1, D), lambda b, i, g: (0, 0)))
    if backward:
        operands.append(dz)
        in_specs.append(blk)
    if theta is not None:
        operands += _rotate_half_sin(T, D, theta, inverse)
        in_specs += [pl.BlockSpec((rows, D), lambda b, i, g: (i, 0))] * 2
    out_specs, out_shape = [blk], [jax.ShapeDtypeStruct(x.shape, x.dtype)]
    if backward:
        out_specs.append(pl.BlockSpec((1, 1, 1, 1, D),
                                      lambda b, i, g: (b, i, g, 0, 0)))
        out_shape.append(jax.ShapeDtypeStruct((*grid, 1, D), jnp.float32))
    call = pl.pallas_call(
        functools.partial(
            _qk_prep_bwd_kernel if backward else _qk_prep_fwd_kernel,
            heads=heads, D=D, eps=None if scale is None else eps),
        grid=grid, in_specs=in_specs, out_specs=out_specs,
        out_shape=out_shape,
        compiler_params=None if interpret else _tpu_params(
            "parallel", "parallel", "parallel"),
        interpret=interpret,
    )
    with jax.named_scope(QK_PREP_SCOPE):
        out = call(*operands)
    if not backward:
        return out[0]
    return out[0], out[1].sum(axis=(0, 1, 2, 3)).astype(scale.dtype)


@functools.partial(jax.custom_vjp, nondiff_argnums=(2, 3, 4, 5))
def qk_prep(x, scale, n_head: int, eps: float, theta: float | None = None,
            interpret: bool = False):
    """What stands between a projection and flash_attention_gqa, as one
    pass: x (B, T, heads*D) -> the same shape and dtype, every head's D
    lanes RMS-normalised (``x * rsqrt(mean(x^2) + eps) * scale``, scale (D,)
    shared by the heads) and then, where ``theta`` is given, turned by
    rotate-half rotary positions 0..T-1 (``y * cos + [-y2, y1] * sin``).
    Computed in float32 from x as it lies; nothing of activation size is
    kept for the backward but x. Shapes must satisfy gqa_layout_supported."""
    return _pallas_qk_prep(x, scale, n_head=n_head, eps=eps, theta=theta,
                           interpret=interpret)


def _qk_prep_fwd_rule(x, scale, n_head, eps, theta, interpret):
    z = _pallas_qk_prep(x, scale, n_head=n_head, eps=eps, theta=theta,
                        interpret=interpret)
    return z, (x, scale)


def _qk_prep_bwd_rule(n_head, eps, theta, interpret, res, dz):
    return _pallas_qk_prep(*res, dz, n_head=n_head, eps=eps, theta=theta,
                           interpret=interpret)


qk_prep.defvjp(_qk_prep_fwd_rule, _qk_prep_bwd_rule)


@functools.partial(jax.custom_vjp, nondiff_argnums=(1, 2, 3))
def qk_rotary(x, n_head: int, theta: float, interpret: bool = False):
    """qk_prep without the norm, for a model whose q and k have none: x
    (B, T, heads*D) -> the same shape and dtype, every head's D lanes turned
    by rotate-half rotary positions 0..T-1 (``x * cos + [-x2, x1] * sin``),
    in float32 from x as it lies. The rotation is linear and orthogonal, so
    the backward is the same one-pass kernel by the opposite angles and
    keeps nothing. Shapes must satisfy gqa_layout_supported."""
    return _pallas_qk_prep(x, None, n_head=n_head, eps=None, theta=theta,
                           interpret=interpret)


def _qk_rotary_fwd_rule(x, n_head, theta, interpret):
    return qk_rotary(x, n_head, theta, interpret), None


def _qk_rotary_bwd_rule(n_head, theta, interpret, _, dz):
    return (_pallas_qk_prep(dz, None, n_head=n_head, eps=None, theta=theta,
                            interpret=interpret, inverse=True),)


qk_rotary.defvjp(_qk_rotary_fwd_rule, _qk_rotary_bwd_rule)


def hash_dropout_keep_mask(seed, B: int, H: int, Tq: int, Tk: int, *,
                           q_off=0, k_off=0, b_off=0, h_off=0,
                           hash_heads: int | None = None,
                           hash_seq_len: int | None = None,
                           rate: float = 0.1) -> jax.Array:
    """The EXACT (B, H, Tq, Tk) keep-mask the Pallas kernels derive, as
    plain jnp ops — shared by the XLA ring block (so pallas and xla ring
    impls drop identical elements for the same seed) and by tests
    verifying the in-kernel mask against a dense reference."""
    seed = _dropout_seed_arg(seed, rate)
    hash_heads = hash_heads if hash_heads is not None else H
    if hash_seq_len is None:
        # Match the kernels' default: they hash over the BLOCK-PADDED
        # length, which (clamped blocks always divide the 128-padded T)
        # is T rounded up to a multiple of 128 — not the raw Tq.
        hash_seq_len = -(-Tq // LANES) * LANES
    bh = jnp.arange(B * H, dtype=jnp.uint32)
    b = bh // jnp.uint32(H) + seed[1] + jnp.uint32(b_off)
    h = bh % jnp.uint32(H) + seed[2] + jnp.uint32(h_off)
    mix = _fmix32(seed[0] ^ ((b * jnp.uint32(hash_heads) + h)
                             * jnp.uint32(_GOLDEN)))        # (B*H,)
    q_pos = (seed[3].astype(jnp.int32) + q_off
             + jnp.arange(Tq))[:, None]
    k_pos = (seed[4].astype(jnp.int32) + k_off
             + jnp.arange(Tk))[None, :]
    idx = (q_pos.astype(jnp.uint32) * jnp.uint32(hash_seq_len)
           + k_pos.astype(jnp.uint32))                       # (Tq, Tk)
    threshold = jnp.uint32(min(int(round(rate * 2**32)), 2**32 - 1))
    keep = _fmix32(idx[None] ^ mix[:, None, None]) >= threshold
    return keep.reshape(B, H, Tq, Tk)


# ---------------------------------------------------------------------------
# Dispatch
# ---------------------------------------------------------------------------

def resolve_attention_impl(impl: str) -> str:
    """'auto' means ONE thing per backend: this file's compiled Pallas
    kernel on tpu, XLA attention everywhere else. Nothing is probed and
    nothing is caught: a kernel the chip's compiler refuses fails the
    train step's compile instead of silently training on the XLA path.
    Explicit impls pass through."""
    if impl != "auto":
        return impl
    return "pallas" if jax.default_backend() == "tpu" else "xla"


def attention_layout(n_head: int, head_dim: int, T: int, *,
                     impl: str = "auto", stat_layout: str = "compact",
                     mesh=None) -> str:
    """Which HBM interface the training / eval attention of these shapes
    takes: 'btc' (flash_attention_qkv: the kernels read qkv (B, T, 3C) and
    write o (B, T, C), no layout copy) or 'bhtd' (causal_attention over
    (B, H, T, D) after the transposes). Decided once, at trace time, from
    what the code can observe: the resolved impl, the shapes and the bound
    mesh. 'btc' needs this file's Pallas kernels, the compact statistic
    layout (the only one the new kernels have), head groups that tile the
    128 lanes (qkv_layout_supported: not GPT-2 XL's 25 heads) and one
    device: on a mesh the kernels run inside ring_attention_sharded's
    shard_map shell, which composes the (B, H, T, D) entry."""
    if (resolve_attention_impl(impl) in ("pallas", "pallas_interpret")
            and stat_layout == "compact"
            and (mesh is None or mesh.size == 1)
            and qkv_layout_supported(n_head, head_dim, T)):
        return "btc"
    return "bhtd"


def causal_attention_qkv(qkv: jax.Array, n_head: int, *, impl: str = "auto",
                         dropout_rate: float = 0.0,
                         dropout_rng: jax.Array | None = None) -> jax.Array:
    """Causal attention from qkv (B, T, 3C) to o (B, T, C) through
    flash_attention_qkv: the 'btc' side of attention_layout, which the
    caller has asked first. Dropout draws its seed from dropout_rng as
    causal_attention does."""
    impl = resolve_attention_impl(impl)
    if impl not in ("pallas", "pallas_interpret"):
        raise ValueError(
            f"causal_attention_qkv is the Pallas (B, T, heads*D) entry; "
            f"impl {impl!r} goes through causal_attention")
    seed = None
    if dropout_rate > 0.0 and dropout_rng is not None:
        seed = jax.random.bits(dropout_rng, (1,), jnp.uint32)
    return flash_attention_qkv(
        qkv, seed, n_head, float(dropout_rate) if seed is not None else 0.0,
        impl == "pallas_interpret")


def causal_attention(q: jax.Array, k: jax.Array, v: jax.Array, *,
                     impl: str = "auto", sm_scale: float | None = None,
                     dropout_rate: float = 0.0,
                     dropout_rng: jax.Array | None = None,
                     stat_layout: str = "replicated") -> jax.Array:
    """Causal attention over (B, H, T, D) tensors.

    impl: 'auto' (Pallas on a tpu backend — a compile error propagates —
    and XLA on any other; see resolve_attention_impl), 'pallas',
    'pallas_interpret' (for CPU tests), or 'xla'.

    stat_layout ('replicated' | 'compact'): the flash backward's softmax-
    stat operand layout (--attention_stat_layout); ignored by the xla
    path.

    Attention-probability dropout runs INSIDE the flash kernels
    (flash_attention_dropout) for the pallas impls.
    The pallas and XLA paths draw different (equally valid) masks from the
    same rng — identical regularization statistics, different bits.
    """
    impl = resolve_attention_impl(impl)
    if dropout_rate > 0.0 and dropout_rng is not None:
        if impl in ("pallas", "pallas_interpret"):
            seed = jax.random.bits(dropout_rng, (1,), jnp.uint32)
            return flash_attention_dropout(q, k, v, seed, True, sm_scale,
                                           float(dropout_rate),
                                           impl == "pallas_interpret",
                                           stat_layout)
        return xla_attention(q, k, v, causal=True, sm_scale=sm_scale,
                             dropout_rate=dropout_rate,
                             dropout_rng=dropout_rng)
    if impl == "xla":
        return xla_attention(q, k, v, causal=True, sm_scale=sm_scale)
    if impl == "pallas":
        return flash_attention(q, k, v, True, sm_scale, False, stat_layout)
    if impl == "pallas_interpret":
        return flash_attention(q, k, v, True, sm_scale, True, stat_layout)
    raise ValueError(f"unknown attention impl: {impl!r}")


def resolve_gqa_impl(impl: str, head_dim: int, T: int) -> str:
    """What the grouped-query kernels and their prologue (qk_prep) run at
    these shapes: 'pallas' / 'pallas_interpret' where the resolved impl is
    one and the kernels can walk them (gqa_layout_supported: decided from
    shapes at trace time, like attention_layout; a trainer's 8-token init
    batch is what does not), 'xla' everywhere else."""
    impl = resolve_attention_impl(impl)
    if impl not in ("pallas", "pallas_interpret", "xla"):
        raise ValueError(
            f"grouped-query attention has impls 'pallas', "
            f"'pallas_interpret' and 'xla'; got {impl!r}")
    return impl if gqa_layout_supported(head_dim, T) else "xla"


def resolve_gqa_bwd(impl: str, head_dim: int, T: int,
                    itemsize: int = 2) -> str:
    """What flash_attention_gqa's backward runs at these shapes: 'fused'
    (dQ, dK and dV from one walk of the score tiles, gqa_bwd_fused_fits),
    'split' (the dQ kernel, then the dK/dV kernel: a T whose whole-T
    accumulators do not fit VMEM) or 'xla' (no kernel: resolve_gqa_impl)."""
    if resolve_gqa_impl(impl, head_dim, T) == "xla":
        return "xla"
    return "fused" if gqa_bwd_fused_fits(head_dim, T, itemsize) else "split"


def gqa_route(impl: str, head_dim: int, T: int,
              window: int | None = None) -> str:
    """Which entry causal_attention_gqa takes at these shapes, from the
    resolved impl and the shapes alone (no option): 'btc-gqa'
    (flash_attention_gqa on the projections' (B, T, heads*D) layout: whole
    128-lane heads, resolve_gqa_impl), 'bhtd-rep' (a Pallas impl at head
    size 64 over whole 128-row blocks, no window: the (B, H, T, D) entry
    flash_attention, which runs D = 64 unpadded and knows no window, fed
    the KV heads repeated H // G times, dK / dV summed back over the group
    by the repeat's transpose; no (B, H, T, T) array either) or 'xla'
    (xla_attention with the KV heads repeated: the CPU, an init batch,
    every other shape)."""
    if resolve_gqa_impl(impl, head_dim, T) != "xla":
        return "btc-gqa"
    if (resolve_attention_impl(impl) in ("pallas", "pallas_interpret")
            and head_dim == 64 and T % LANES == 0 and window is None):
        return "bhtd-rep"
    return "xla"


def causal_attention_gqa(q: jax.Array, k: jax.Array, v: jax.Array,
                         n_head: int, n_kv_head: int, *,
                         window: int | None = None, impl: str = "auto",
                         scope: str = KERNEL_SCOPE) -> jax.Array:
    """Causal attention with grouped KV heads and an optional window from
    q (B, T, H*D), k / v (B, T, G*D) to o (B, T, H*D), by gqa_route: the
    grouped-query kernels where heads are whole 128 lanes, the (B, H, T, D)
    kernels on repeated KV heads at head size 64 without a window,
    xla_attention everywhere else. ``scope`` names the Pallas routes'
    custom calls (%<scope>.N)."""
    B, T, HD = q.shape
    D = HD // n_head
    route = gqa_route(impl, D, T, window)
    interpret = resolve_attention_impl(impl) == "pallas_interpret"
    if route == "btc-gqa":
        return flash_attention_gqa(q, k, v, n_head, n_kv_head, window,
                                   interpret, scope)
    heads = lambda x, n: x.reshape(B, T, n, D).transpose(0, 2, 1, 3)
    rep = n_head // n_kv_head
    qh = heads(q, n_head)
    kh = jnp.repeat(heads(k, n_kv_head), rep, axis=1)
    vh = jnp.repeat(heads(v, n_kv_head), rep, axis=1)
    if route == "bhtd-rep":
        with jax.named_scope(scope):
            o = flash_attention(qh, kh, vh, True, None, interpret, "compact")
    else:
        o = xla_attention(qh, kh, vh, window=window)
    return o.transpose(0, 2, 1, 3).reshape(B, T, HD)


def mla_route(impl: str, head_dim: int, rope_dim: int, v_dim: int, T: int,
              itemsize: int = 2) -> str:
    """Which entry causal_attention_mla takes at these shapes, from the
    resolved impl and the shapes alone (no option): 'mla' (the latent
    kernels, forward and one-pass backward: a Pallas impl and
    mla_layout_supported) or 'xla' (xla_attention on the concatenated heads:
    the CPU, a trainer's 8-token init batch, every other shape)."""
    impl = resolve_attention_impl(impl)
    if impl not in ("pallas", "pallas_interpret", "xla"):
        raise ValueError(
            f"latent attention has impls 'pallas', 'pallas_interpret' and "
            f"'xla'; got {impl!r}")
    if impl != "xla" and mla_layout_supported(head_dim, rope_dim, v_dim, T,
                                              itemsize):
        return "mla"
    return "xla"


def causal_attention_mla(q_nope: jax.Array, q_pe: jax.Array,
                         k_nope: jax.Array, k_pe: jax.Array, v: jax.Array,
                         n_head: int, *, impl: str = "auto",
                         scope: str = MLA_SCOPE) -> jax.Array:
    """Causal latent attention from q_nope / k_nope (B, T, H*D), q_pe
    (B, T, H, R), k_pe (B, T, R) (both already rotated) and v (B, T, H*Dv)
    to o (B, T, H*Dv), scores scaled by (D + R) ** -0.5, by mla_route: the
    latent kernels, or xla_attention on heads concatenated to D + R with
    the rotary key repeated. ``scope`` names the kernels' custom calls."""
    B, T, H, R = q_pe.shape
    D, Dv = q_nope.shape[-1] // H, v.shape[-1] // H
    if mla_route(impl, D, R, Dv, T, k_nope.dtype.itemsize) == "mla":
        return flash_attention_mla(
            q_nope, q_pe.transpose(0, 2, 1, 3), k_nope, k_pe, v, H,
            resolve_attention_impl(impl) == "pallas_interpret", scope)
    heads = lambda x, n: x.reshape(B, T, H, n).transpose(0, 2, 1, 3)
    q = jnp.concatenate([heads(q_nope, D), q_pe.transpose(0, 2, 1, 3)], -1)
    k = jnp.concatenate([heads(k_nope, D), jnp.broadcast_to(
        k_pe[:, None], (B, H, T, R))], -1)
    o = xla_attention(q, k, heads(v, Dv), sm_scale=(D + R) ** -0.5)
    return o.transpose(0, 2, 1, 3).reshape(B, T, H * Dv)
