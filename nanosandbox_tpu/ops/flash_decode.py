"""Fused single-query flash-decode over the serve engine's slot KV pool.

The decode hot loop's attention is HBM-bandwidth-bound: every token of
every slot streams that slot's whole K/V history from HBM once. The
generic cached path in models/gpt.py pays that stream twice over —
scores materialize against the full ``max_len`` buffer in fp32, the
probability tensor round-trips through XLA fusions — and, with an int8
pool, would need a dequantized fp copy of the cache before the first
dot. This kernel is the decode twin of ops/attention.py's training
kernel: ONE pass over each row's K/V blocks with an online softmax, the
frontier mask read from the device-resident per-row ``pos`` state (never
attend past a row's own frontier), and int8→fp dequantization FUSED into
the score/probability math so quantized K/V is the only cache
representation that ever touches HBM.

Dequant-by-folding (why no fp K/V copy exists even transiently):
the per-position scales are constant across the head_dim contraction, so

    q · (k_int * k_scale) == (q · k_int) * k_scale      (fold into scores)
    p · (v_int * v_scale) == (p * v_scale) · v_int      (fold into probs)

Both folds are lane-dim (1, block_k) elementwise multiplies — no
cross-lane relayout, no (block_k, 1) scale column Mosaic can't express.
Scales are per (slot, head, position): one fp32 scalar per ≤128-lane
K/V row, i.e. per block-of-128-lanes of pool data (head_dim ≤ 128
everywhere this repo runs), ~6% byte overhead at D=64 against the 2-4x
the int8 values save.

Layouts: q (B, H, D) — the single query per row; k/v (B, H, L, D) in
fp32/bf16, or int8 with (B, H, L) f32 scales; lengths (B,) int32 = the
number of valid positions (the engine passes pos + 1: attend kpos <=
pos). Heads fold into the grid's row dim exactly like the training
kernel's (B*H, ...) flattening; each grid step owns one (slot, head)
row and walks only ceil(length / block_k) K/V blocks — blocks past the
frontier are skipped at the compute level (the fori_loop bound is the
row's own frontier), and the diagonal-split idiom from the training
kernel keeps the mask VPU work off the fully-valid blocks. DMA-level
block skipping (not fetching past-frontier blocks at all) belongs to
the ROADMAP-2 paged pool, whose block table this kernel is built to
page over.

Impls (the training kernel's idiom, --decode_impl):
  'auto'             — the compiled Pallas kernel on a tpu backend (a
                       compile error propagates), XLA on any other;
  'pallas'           — pin the compiled Mosaic kernel;
  'pallas_interpret' — the same kernel through the Pallas interpreter,
                       so CPU CI exercises this file's exact math;
  'xla'              — the masked-score reference (also what
                       models/gpt.py keeps inline for dense-pool T > 1
                       verify blocks and scalar-index prefill).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30
LANES = 128
# Sublane quantum that tiles legally for every KV dtype this kernel
# accepts (f32 needs 8, bf16 16, int8 32 — see the Pallas tiling table).
SUBLANE_QUANTUM = 32
DEFAULT_BLOCK_K = 256

__all__ = ["flash_decode", "flash_decode_paged", "flash_prefill_paged",
           "xla_decode_attention", "xla_decode_attention_paged",
           "resolve_decode_impl", "compile_check", "quantize_kv_rows",
           "quantize_kv_rows_int4", "unpack_int4", "DECODE_IMPLS"]

DECODE_IMPLS = ("auto", "pallas", "pallas_interpret", "xla")


# ---------------------------------------------------------------------------
# Quantization (shared with models/gpt.py's cache writes)
# ---------------------------------------------------------------------------

def quantize_kv_rows(x: jax.Array, valid=None):
    """Per-row symmetric int8 quantization over the trailing (head_dim)
    axis: returns (values int8 same shape, scales f32 x.shape[:-1]).

    One scale per K/V row — for head_dim <= 128 a row is one <=128-lane
    register block, so this is the per-block-of-128 granularity the
    kernel folds into scores/probs. Symmetric round-to-nearest; the
    round-trip error per element is bounded by scale/2 =
    max|row| / 254 (pinned by tests/test_flash_decode.py). All-zero
    rows (parked slots, unwritten tail) quantize to zeros exactly.

    ``valid`` (optional bool, x.shape[:-1]-broadcastable): False rows
    skip the scale chain — scale pinned to 1 for the divide, values and
    the returned scale zeroed. Sentinel-drop rows in a prefill wave
    (ladder padding, parked block-table rows) feed writes that drop at
    the scatter, so their amax/divide/round work was pure waste."""
    xf = x.astype(jnp.float32)
    amax = jnp.max(jnp.abs(xf), axis=-1)
    scale = jnp.maximum(amax, 1e-30) / 127.0
    if valid is not None:
        scale = jnp.where(valid, scale, 1.0)
        xf = jnp.where(valid[..., None], xf, 0.0)
    q = jnp.clip(jnp.round(xf / scale[..., None]), -127, 127).astype(jnp.int8)
    if valid is not None:
        scale = jnp.where(valid, scale, 0.0)
    return q, scale


def quantize_kv_rows_int4(x: jax.Array, valid=None):
    """Per-row symmetric int4 quantization, two nibbles per byte packed
    along head_dim: returns (packed uint8 x.shape[:-1] + (D//2,),
    scales f32 x.shape[:-1]).

    Same per-block-of-lanes scale granularity as the int8 path — one
    f32 residual scale per K/V row (= per (slot|block, head, position))
    — so the kernels fold it into scores/probs identically; only the
    value bytes halve again. Nibbles are biased (+8) so a packed byte
    holds positions 2d (low) and 2d+1 (high) of the row. scale =
    max|row| / 7: levels [-7, 7], round-trip error per element bounded
    by scale/2 = max|row| / 14 (the tests pin <= max|row| / 7.5 per
    block of lanes). All-zero rows quantize to zeros exactly (packed
    byte 0x88 decodes to 0 after the bias).

    ``valid`` (optional bool, shape x.shape[:-1] broadcastable): rows
    that are False skip the scale chain entirely — their scale is
    pinned to 1 and their values to the zero nibble, so sentinel-drop
    rows (ladder padding, parked block-table rows) never spend the
    amax/divide/round lane work feeding a write that drops anyway."""
    if x.shape[-1] % 2:
        raise ValueError(f"int4 packing needs an even head_dim, "
                         f"got {x.shape[-1]}")
    xf = x.astype(jnp.float32)
    amax = jnp.max(jnp.abs(xf), axis=-1)
    scale = jnp.maximum(amax, 1e-30) / 7.0
    if valid is not None:
        scale = jnp.where(valid, scale, 1.0)
        xf = jnp.where(valid[..., None], xf, 0.0)
    q = (jnp.clip(jnp.round(xf / scale[..., None]), -7, 7)
         .astype(jnp.int32) + 8)                      # nibbles in [1, 15]
    packed = (q[..., 0::2] | (q[..., 1::2] << 4)).astype(jnp.uint8)
    if valid is not None:
        scale = jnp.where(valid, scale, 0.0)
    return packed, scale


def unpack_int4(packed: jax.Array) -> jax.Array:
    """Packed uint8 (..., D//2) -> int8 (..., D): the inverse of
    quantize_kv_rows_int4's nibble layout (low nibble first). Shared by
    the XLA path and the test oracles; the Pallas kernels unpack the
    same bytes per K/V tile into two contiguous halves instead
    (_unpack_int4_halves) — the chip's compiler refuses this lane
    interleave."""
    lo = jnp.bitwise_and(packed, 15).astype(jnp.int8) - 8
    hi = jnp.right_shift(packed, 4).astype(jnp.int8) - 8
    return jnp.stack([lo, hi], axis=-1).reshape(
        *packed.shape[:-1], packed.shape[-1] * 2)


def _unpack_int4_halves(packed: jax.Array) -> jax.Array:
    """In-kernel int4 unpack: (rows, D//2) packed bytes -> (rows, D) with
    the EVEN head dims in lanes [0, D//2) and the ODD ones in [D//2, D)
    — two contiguous halves, because Mosaic will not interleave along
    lanes (unpack_int4's stack+reshape). The kernels' wrappers permute
    the query into the same order (so q.k is unchanged, a dot being
    permutation-invariant) and un-permute the small output. The nibble
    arithmetic runs in int32: the v5e vector unit has no int8 subtract
    (Mosaic: "failed to legalize arith.subi")."""
    p32 = packed.astype(jnp.int32)
    lo = jnp.bitwise_and(p32, 15) - 8
    hi = jnp.right_shift(p32, 4) - 8
    return jnp.concatenate([lo, hi], axis=-1)


def _even_odd_halves(x: jax.Array) -> jax.Array:
    """(..., D) -> (..., D) ordered [even dims | odd dims]."""
    return jnp.concatenate([x[..., 0::2], x[..., 1::2]], axis=-1)


def _interleave_halves(x: jax.Array) -> jax.Array:
    """Inverse of _even_odd_halves."""
    h = x.shape[-1] // 2
    return jnp.stack([x[..., :h], x[..., h:]], axis=-1).reshape(x.shape)


# ---------------------------------------------------------------------------
# XLA reference (the fallback + the test oracle)
# ---------------------------------------------------------------------------

def xla_decode_attention(q: jax.Array, k: jax.Array, v: jax.Array,
                         lengths: jax.Array, *, k_scale=None, v_scale=None,
                         sm_scale: float | None = None) -> jax.Array:
    """Masked single-query attention in plain jnp: q (B, H, D) against
    k/v (B, H, L, D) with per-row valid ``lengths`` (B,). int8 k/v take
    per-position scales (B, H, L), folded into scores/probs exactly as
    the kernel folds them — the two impls share one numeric contract.
    Packed-int4 k/v (uint8, trailing dim D//2) unpack first and then
    follow the identical scale-fold math."""
    if sm_scale is None:
        sm_scale = q.shape[-1] ** -0.5
    if k.dtype == jnp.uint8:
        k, v = unpack_int4(k), unpack_int4(v)
    dtype = q.dtype
    s = jnp.einsum("bhd,bhsd->bhs", q.astype(jnp.float32),
                   k.astype(jnp.float32),
                   preferred_element_type=jnp.float32)
    if k_scale is not None:
        s = s * k_scale
    s = s * sm_scale
    mask = jnp.arange(k.shape[2])[None, None, :] < lengths[:, None, None]
    s = jnp.where(mask, s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    if v_scale is not None:
        p = p * v_scale
    return jnp.einsum("bhs,bhsd->bhd", p,
                      v.astype(jnp.float32),
                      preferred_element_type=jnp.float32).astype(dtype)


def xla_decode_attention_paged(q: jax.Array, k: jax.Array, v: jax.Array,
                               block_table: jax.Array,
                               lengths: jax.Array, *, k_scale=None,
                               v_scale=None,
                               sm_scale: float | None = None) -> jax.Array:
    """Single-query masked attention DIRECTLY over a block-paged pool —
    the XLA fallback's paged fast path. q (B, H, D); k/v (num_blocks,
    H, page, D) (int8, or packed-int4 uint8, with (num_blocks, H,
    page) scales); block_table (B, nb); lengths (B,). Returns (B, H, D).

    The old fallback gathered each row's chain into contiguous
    (B, H, nb*page, D) rows — a gather PLUS a transpose/reshape copy of
    the whole working set, per layer, per decode step (the measured
    paged-vs-dense CPU decode gap). Here the einsums contract straight
    against the gathered (B, nb, H, page, D) layout, so the relayout
    copy never happens; only the score tensor (tiny) reshapes for the
    softmax. Sentinel table entries (>= num_blocks) clamp to a real
    block and their positions sit past ``lengths``, masked like any
    stale tail."""
    if sm_scale is None:
        sm_scale = q.shape[-1] ** -0.5
    N, H, page, _ = k.shape
    B, nb = block_table.shape
    tbl = jnp.minimum(block_table, N - 1)
    gk, gv = k[tbl], v[tbl]                  # (B, nb, H, page, D')
    if k.dtype == jnp.uint8:
        gk, gv = unpack_int4(gk), unpack_int4(gv)
    dtype = q.dtype
    s = jnp.einsum("bhd,bjhpd->bhjp", q.astype(jnp.float32),
                   gk.astype(jnp.float32),
                   preferred_element_type=jnp.float32)
    if k_scale is not None:
        s = s * k_scale[tbl].transpose(0, 2, 1, 3)
    s = s * sm_scale
    kpos = (jnp.arange(nb)[:, None] * page
            + jnp.arange(page)[None, :])     # (nb, page)
    mask = kpos[None, None] < lengths[:, None, None, None]
    s = jnp.where(mask, s, NEG_INF)
    p = jax.nn.softmax(s.reshape(B, H, nb * page),
                       axis=-1).reshape(B, H, nb, page)
    if v_scale is not None:
        p = p * v_scale[tbl].transpose(0, 2, 1, 3)
    return jnp.einsum("bhjp,bjhpd->bhd", p, gv.astype(jnp.float32),
                      preferred_element_type=jnp.float32).astype(dtype)


# ---------------------------------------------------------------------------
# Pallas kernel
# ---------------------------------------------------------------------------

def _flash_decode_kernel(len_ref, q_ref, k_ref, v_ref, ks_ref, vs_ref,
                         o_ref, *, block_k: int, sm_scale: float,
                         heads: int, quantized: bool,
                         four_bit: bool = False):
    """One grid step == one (slot, head) row: walk the row's K/V blocks
    up to its OWN frontier with an online softmax. Same split-loop idiom
    as the training kernel: blocks fully inside the frontier skip the
    iota/compare mask (pure VPU cost), only the partial frontier block
    masks. ``four_bit`` K/V tiles arrive packed (two nibbles per byte
    along the lane dim) and unpack in-register — half the int8 HBM
    bytes stream in, and the fp representation still never exists."""
    b = pl.program_id(0)
    length = len_ref[b // heads]          # this row's valid positions
    # Dot dtype: int8 K/V feed the MXU in the QUERY's dtype (integers up
    # to 127 are exact in bf16) with f32 accumulation; full-precision
    # pools use the WIDER of (query, pool) — an fp32 pool under a bf16
    # query must not silently lose its precision on the flash path (the
    # XLA reference keeps fp32 operands there too).
    dot_dt = (q_ref.dtype if quantized
              else jnp.promote_types(q_ref.dtype, k_ref.dtype))
    q = q_ref[0].astype(dot_dt)           # (1, D)
    num_kb = lax.div(length + block_k - 1, block_k)
    num_kb_inner = lax.div(length, block_k)   # fully-valid blocks

    def body(j, carry, *, masked: bool):
        acc, m, l = carry
        k = k_ref[0, pl.ds(j * block_k, block_k), :]
        if four_bit:
            k = _unpack_int4_halves(k)
        # int8 K enters the dot WITHOUT its scale; the scale folds into
        # the (1, block_k) score row below — a lane-dim multiply, never
        # a dequantized K tile.
        s = lax.dot_general(q, k.astype(dot_dt), (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32)  # (1, bk)
        if quantized:
            s = s * ks_ref[0, :, pl.ds(j * block_k, block_k)]
        s = s * sm_scale
        if masked:
            k_pos = j * block_k + lax.broadcasted_iota(
                jnp.int32, (1, block_k), 1)
            s = jnp.where(k_pos < length, s, NEG_INF)
        m_new = jnp.maximum(m, jnp.max(s, axis=1, keepdims=True))  # (1, 1)
        p = jnp.exp(s - m_new)
        alpha = jnp.exp(m - m_new)
        l_new = alpha * l + jnp.sum(p, axis=1, keepdims=True)
        if quantized:
            # v's scale folds into the probability row (p * s) @ v_int —
            # the normalizer l above sums the UNSCALED p, so the final
            # acc / l division is exactly softmax(s) @ (v_int * scale).
            p = p * vs_ref[0, :, pl.ds(j * block_k, block_k)]
        v = v_ref[0, pl.ds(j * block_k, block_k), :]
        if four_bit:
            v = _unpack_int4_halves(v)
        acc_new = acc * alpha + lax.dot_general(
            p.astype(dot_dt), v.astype(dot_dt), (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        return acc_new, m_new, l_new

    init = (
        jnp.zeros((1, q_ref.shape[2]), jnp.float32),
        jnp.full((1, 1), NEG_INF, jnp.float32),
        jnp.zeros((1, 1), jnp.float32),
    )
    carry = lax.fori_loop(0, num_kb_inner,
                          functools.partial(body, masked=False), init)
    acc, m, l = lax.fori_loop(num_kb_inner, num_kb,
                              functools.partial(body, masked=True), carry)
    o_ref[0] = (acc / l).astype(o_ref.dtype)


def _clamp_block_k(L: int, block_k: int) -> tuple[int, int]:
    """(block_k, Lp): the largest SUBLANE_QUANTUM multiple <= the request
    that the padded pool length divides into — same divide-don't-pad
    policy as the training kernel's _clamp_blocks, on the 32-row quantum
    every KV dtype tiles at."""
    Lq = -(-L // SUBLANE_QUANTUM) * SUBLANE_QUANTUM
    b = max(SUBLANE_QUANTUM,
            block_k // SUBLANE_QUANTUM * SUBLANE_QUANTUM)
    b = min(b, Lq)
    while Lq % b:
        b -= SUBLANE_QUANTUM  # terminates at SUBLANE_QUANTUM
    return b, Lq


def decode_pad_copies(max_len: int, head_dim: int) -> bool:
    """True when flash_decode must PAD — i.e. copy — the pool on every
    call: max_len off the 32-row sublane quantum, or a head_dim outside
    the verified-unpadded set (64 / 128-multiples). On the HBM-bound
    decode hot path that copy roughly doubles per-step traffic, so the
    engine warns at construction instead of paying it silently."""
    return (max_len % SUBLANE_QUANTUM != 0
            or not (head_dim == 64 or head_dim % 128 == 0))


def flash_decode(q: jax.Array, k: jax.Array, v: jax.Array,
                 lengths: jax.Array, *, k_scale=None, v_scale=None,
                 sm_scale: float | None = None,
                 block_k: int = DEFAULT_BLOCK_K,
                 interpret: bool = False) -> jax.Array:
    """Single-query flash attention over per-row frontiers (see module
    docstring for layouts). Returns (B, H, D) in q's dtype."""
    if sm_scale is None:
        sm_scale = q.shape[-1] ** -0.5
    if (k_scale is None) != (v_scale is None):
        raise ValueError("k_scale and v_scale must be supplied together")
    if k_scale is not None and k.dtype not in (jnp.int8, jnp.uint8):
        raise ValueError(
            f"scales supplied for non-quantized k/v ({k.dtype}/{v.dtype})")
    quantized = k_scale is not None
    four_bit = quantized and k.dtype == jnp.uint8
    B, H, L, Dk = k.shape
    # Packed int4 stores two lanes per byte: the LOGICAL head_dim is
    # twice the stored trailing dim, and the pads below halve on the
    # packed operands.
    D = Dk * 2 if four_bit else Dk  # jaxlint: disable=tracer-leak -- four_bit is a static Python bool (dtype metadata, not data)
    if q.shape != (B, H, D):
        raise ValueError(f"q shape {q.shape} != {(B, H, D)}")
    block_k, Lp = _clamp_block_k(L, block_k)
    # head_dim padding: same verified rule as the training kernel
    # (ops/attention.py _pad_qkv) — 64 lanes and 128-multiples run
    # unpadded, anything else pads to the 128-lane tile. Packed int4
    # pads pad_D // 2 bytes (a zero byte unpacks to the -8 bias pair,
    # harmless: the matching q lanes are zero-padded so the score
    # contribution is exactly 0, and padded OUTPUT lanes are sliced).
    pad_D = 0 if (D == 64 or D % 128 == 0) else (-D) % 128
    pad_L = Lp - L
    pad_Dk = pad_D // 2 if four_bit else pad_D  # jaxlint: disable=tracer-leak -- four_bit is a static Python bool (dtype metadata, not data)
    if pad_D:
        q = jnp.pad(q, [(0, 0), (0, 0), (0, pad_D)])
    if pad_Dk or pad_L:
        pads = [(0, 0), (0, 0), (0, pad_L), (0, pad_Dk)]
        k, v = jnp.pad(k, pads), jnp.pad(v, pads)
    Dp = D + pad_D
    Dkp = Dk + pad_Dk
    if four_bit:  # jaxlint: disable=tracer-leak -- four_bit is a static Python bool (dtype metadata, not data)
        q = _even_odd_halves(q)
    qf = q.reshape(B * H, 1, Dp)
    kf = k.reshape(B * H, Lp, Dkp)
    vf = v.reshape(B * H, Lp, Dkp)
    if k_scale is not None:
        spad = [(0, 0), (0, 0), (0, pad_L)]
        ksf = jnp.pad(k_scale.astype(jnp.float32), spad).reshape(
            B * H, 1, Lp)
        vsf = jnp.pad(v_scale.astype(jnp.float32), spad).reshape(
            B * H, 1, Lp)
    else:
        # Zero-size dummy operands would need their own BlockSpec rules;
        # a (B*H, 1, SUBLANE_QUANTUM-free) tiny array keeps the operand
        # list fixed across modes at negligible cost.
        ksf = vsf = jnp.ones((B * H, 1, LANES), jnp.float32)
    Ls = ksf.shape[2]

    kernel = functools.partial(
        _flash_decode_kernel, block_k=block_k, sm_scale=sm_scale,
        heads=H, quantized=quantized, four_bit=four_bit)
    out = pl.pallas_call(
        kernel,
        grid=(B * H,),
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.SMEM),
            pl.BlockSpec((1, 1, Dp), lambda b: (b, 0, 0)),
            pl.BlockSpec((1, Lp, Dkp), lambda b: (b, 0, 0)),
            pl.BlockSpec((1, Lp, Dkp), lambda b: (b, 0, 0)),
            pl.BlockSpec((1, 1, Ls), lambda b: (b, 0, 0)),
            pl.BlockSpec((1, 1, Ls), lambda b: (b, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, Dp), lambda b: (b, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((B * H, 1, Dp), q.dtype),
        compiler_params=None if interpret else pltpu.CompilerParams(
            dimension_semantics=("parallel",)),
        interpret=interpret,
    )(jnp.asarray(lengths, jnp.int32), qf, kf, vf, ksf, vsf)
    if four_bit:  # jaxlint: disable=tracer-leak -- four_bit is a static Python bool (dtype metadata, not data)
        out = _interleave_halves(out)
    return out.reshape(B, H, Dp)[:, :, :D]


# ---------------------------------------------------------------------------
# Paged variant: the block-table indirection (ROADMAP-2 / ISSUE 9)
# ---------------------------------------------------------------------------

def _paged_scale_operands(k_scale, v_scale, page: int):
    """The paged kernels' scale operands: the pool's (num_blocks, H,
    page) f32 planes viewed as (num_blocks, H, 1, page), so the
    (1, 1, 1, page) block's last two dims are WHOLE array dims — the TPU
    lowering refuses a (1, page) block cut out of an (H, page) plane.
    fp pools get a 1-block dummy the index_map pins to block 0, keeping
    the operand list fixed across modes (flash_decode's idiom)."""
    if k_scale is None:
        ones = jnp.ones((1, 1, 1, page), jnp.float32)
        return ones, ones
    N, H, _ = k_scale.shape
    return (k_scale.astype(jnp.float32).reshape(N, H, 1, page),
            v_scale.astype(jnp.float32).reshape(N, H, 1, page))


def _paged_decode_kernel(len_ref, tbl_ref, q_ref, k_ref, v_ref, ks_ref,
                         vs_ref, o_ref, acc_ref, m_ref, l_ref, *,
                         page: int, heads: int, sm_scale: float,
                         num_kb: int, quantized: bool,
                         four_bit: bool = False):
    """One grid step == one (row, block-slot) pair of the flattened
    (B*H, max_blocks) grid. The CHUNK ADDRESS is the indirection: the
    BlockSpec index_map reads the scalar-prefetched block table, so the
    DMA for grid step (r, i) fetches pool block table[r // H, i] — the
    paged twin of flash_decode's contiguous pl.ds(i * block_k) walk.
    The online-softmax carry lives in VMEM scratch across the
    sequential block dim (dimension_semantics: the row dim is parallel,
    the block dim arbitrary); blocks at or past the row's frontier are
    skipped at the compute level via pl.when, and the frontier block
    masks by position exactly like the contiguous kernel. int8 dequant
    is the same fold: scales multiply the (1, page) score/probability
    rows, never a dequantized K/V tile."""
    r = pl.program_id(0)
    i = pl.program_id(1)
    length = len_ref[r // heads]

    @pl.when(i == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    @pl.when(i * page < length)
    def _block():
        dot_dt = (q_ref.dtype if quantized
                  else jnp.promote_types(q_ref.dtype, k_ref.dtype))
        q = q_ref[0].astype(dot_dt)                      # (1, D)
        k = k_ref[0, 0]                                  # (page, D)
        if four_bit:
            k = _unpack_int4_halves(k)
        s = lax.dot_general(q, k.astype(dot_dt), (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32)  # (1, page)
        if quantized:
            s = s * ks_ref[0, 0]
        s = s * sm_scale
        kpos = i * page + lax.broadcasted_iota(jnp.int32, (1, page), 1)
        s = jnp.where(kpos < length, s, NEG_INF)
        m_prev, l_prev = m_ref[...], l_ref[...]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        p = jnp.exp(s - m_new)
        alpha = jnp.exp(m_prev - m_new)
        l_ref[...] = alpha * l_prev + jnp.sum(p, axis=1, keepdims=True)
        if quantized:
            p = p * vs_ref[0, 0]
        v = v_ref[0, 0]
        if four_bit:
            v = _unpack_int4_halves(v)
        acc_ref[...] = acc_ref[...] * alpha + lax.dot_general(
            p.astype(dot_dt), v.astype(dot_dt), (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        m_ref[...] = m_new

    @pl.when(i == num_kb - 1)
    def _out():
        o_ref[0] = (acc_ref[...] / l_ref[...]).astype(o_ref.dtype)


def flash_decode_paged(q: jax.Array, k: jax.Array, v: jax.Array,
                       block_table: jax.Array, lengths: jax.Array, *,
                       k_scale=None, v_scale=None,
                       sm_scale: float | None = None,
                       interpret: bool = False) -> jax.Array:
    """Single-query flash attention over a BLOCK-PAGED pool.

    q (B, H, D); k/v (num_blocks, H, page, D) — the global block pool,
    fp32/bf16 or int8 with (num_blocks, H, page) f32 scales;
    block_table (B, max_blocks) int32 mapping each row's i-th logical
    chunk to a pool block (entries >= num_blocks are the engine's
    unallocated sentinel — clamped in the index_map, masked/skipped by
    length); lengths (B,) valid positions per row. Returns (B, H, D).

    Unlike flash_decode there is no pool-wide pad path for the block
    dim: ``page`` IS the DMA chunk (every kv mode compiles for v5e at
    pages 4..256; tests/test_chip_compile.py holds 16 and 32). head_dim
    follows the same verified rule as
    flash_decode (64 or 128-multiples unpadded; anything else pads
    q AND the pool — a per-call copy the engine warns about)."""
    if sm_scale is None:
        sm_scale = q.shape[-1] ** -0.5
    if (k_scale is None) != (v_scale is None):
        raise ValueError("k_scale and v_scale must be supplied together")
    if k_scale is not None and k.dtype not in (jnp.int8, jnp.uint8):
        raise ValueError(
            f"scales supplied for non-quantized k/v ({k.dtype}/{v.dtype})")
    quantized = k_scale is not None
    four_bit = quantized and k.dtype == jnp.uint8
    N, H, page, Dk = k.shape
    D = Dk * 2 if four_bit else Dk
    B = q.shape[0]
    if q.shape != (B, H, D):
        raise ValueError(f"q shape {q.shape} != {(B, H, D)}")
    if block_table.ndim != 2 or block_table.shape[0] != B:
        raise ValueError(
            f"block_table shape {block_table.shape} != ({B}, max_blocks)")
    nb = block_table.shape[1]
    pad_D = 0 if (D == 64 or D % 128 == 0) else (-D) % 128
    pad_Dk = pad_D // 2 if four_bit else pad_D
    if pad_D:
        q = jnp.pad(q, [(0, 0), (0, 0), (0, pad_D)])
        pads = [(0, 0), (0, 0), (0, 0), (0, pad_Dk)]
        k, v = jnp.pad(k, pads), jnp.pad(v, pads)
    Dp = D + pad_D
    Dkp = Dk + pad_Dk
    if four_bit:
        q = _even_odd_halves(q)
    qf = q.reshape(B * H, 1, Dp)
    ksf, vsf = _paged_scale_operands(k_scale, v_scale, page)

    def q_map(r, i, lens, tbl):
        return (r, 0, 0)

    def kv_map(r, i, lens, tbl):
        # THE indirection: chunk i of row r DMAs pool block tbl[row, i].
        # Sentinel entries (>= N, the engine's unallocated marker) clamp
        # to a real block — their contents are never read (pl.when skips
        # whole blocks past the frontier, the iota mask the rest).
        return (jnp.minimum(tbl[r // H, i], N - 1), r % H, 0, 0)

    def scale_map(r, i, lens, tbl):
        if not quantized:
            return (0, 0, 0, 0)
        return (jnp.minimum(tbl[r // H, i], N - 1), r % H, 0, 0)

    kernel = functools.partial(
        _paged_decode_kernel, page=page, heads=H, sm_scale=sm_scale,
        num_kb=nb, quantized=quantized, four_bit=four_bit)
    out = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(B * H, nb),
            in_specs=[
                pl.BlockSpec((1, 1, Dp), q_map),
                pl.BlockSpec((1, 1, page, Dkp), kv_map),
                pl.BlockSpec((1, 1, page, Dkp), kv_map),
                pl.BlockSpec((1, 1, 1, page), scale_map),
                pl.BlockSpec((1, 1, 1, page), scale_map),
            ],
            out_specs=pl.BlockSpec((1, 1, Dp), q_map),
            scratch_shapes=[
                pltpu.VMEM((1, Dp), jnp.float32),
                pltpu.VMEM((1, 1), jnp.float32),
                pltpu.VMEM((1, 1), jnp.float32),
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((B * H, 1, Dp), q.dtype),
        compiler_params=None if interpret else pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
    )(jnp.asarray(lengths, jnp.int32), jnp.asarray(block_table, jnp.int32),
      qf, k, v, ksf, vsf)
    if four_bit:
        out = _interleave_halves(out)
    return out.reshape(B, H, Dp)[:, :, :D]


def _paged_prefill_kernel(start_ref, tbl_ref, q_ref, k_ref, v_ref,
                          ks_ref, vs_ref, o_ref, acc_ref, m_ref, l_ref, *,
                          page: int, heads: int, sm_scale: float,
                          num_kb: int, T: int, quantized: bool,
                          four_bit: bool):
    """One grid step == one (row, block-slot) pair, exactly like the
    paged decode kernel — but the query is the row's whole (T, D)
    suffix block at positions start .. start+T-1, so one pass over the
    row's block chain computes the full prefill attention the XLA
    fallback had to GATHER the chain for. The split masked/unmasked
    idiom from the training kernel carries over with a traced split:
    a K/V block wholly at-or-before the first query position is valid
    for every (q, k) pair and skips the iota/compare entirely; only
    blocks overlapping the causal frontier pay the (T, page) mask.
    Block 0 is valid for every query row (kpos 0 <= any qpos), so the
    online-softmax carry is finite from the first executed block and
    later fully-masked rows renormalize cleanly (p underflows to 0
    against a finite m)."""
    r = pl.program_id(0)
    i = pl.program_id(1)
    base = start_ref[r // heads]          # first query position
    end = base + T                        # one past the last query

    @pl.when(i == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    @pl.when(i * page < end)
    def _block():
        dot_dt = (q_ref.dtype if quantized
                  else jnp.promote_types(q_ref.dtype, k_ref.dtype))
        q = q_ref[0].astype(dot_dt)                      # (T, D)
        k = k_ref[0, 0]                                  # (page, D)
        if four_bit:
            k = _unpack_int4_halves(k)
        s = lax.dot_general(q, k.astype(dot_dt), (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32)  # (T, page)
        if quantized:
            s = s * ks_ref[0, 0]
        s = s * sm_scale

        def _accumulate(s):
            m_prev, l_prev = m_ref[...], l_ref[...]
            m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
            p = jnp.exp(s - m_new)
            alpha = jnp.exp(m_prev - m_new)
            l_ref[...] = alpha * l_prev + jnp.sum(p, axis=1, keepdims=True)
            if quantized:
                p = p * vs_ref[0, 0]
            v = v_ref[0, 0]
            if four_bit:
                v = _unpack_int4_halves(v)
            acc_ref[...] = acc_ref[...] * alpha + lax.dot_general(
                p.astype(dot_dt), v.astype(dot_dt),
                (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)
            m_ref[...] = m_new

        # The split: max kpos of this block is (i+1)*page - 1; when it
        # sits at or before the FIRST query position ``base`` the whole
        # (T, page) tile is causally valid — no iota, no compare, no
        # select. Only frontier-overlapping blocks mask.
        inner = (i + 1) * page <= base + 1

        @pl.when(inner)
        def _unmasked():
            _accumulate(s)

        @pl.when(jnp.logical_not(inner))
        def _frontier():
            kpos = i * page + lax.broadcasted_iota(jnp.int32, (T, page), 1)
            qpos = base + lax.broadcasted_iota(jnp.int32, (T, page), 0)
            _accumulate(jnp.where(kpos <= qpos, s, NEG_INF))

    @pl.when(i == num_kb - 1)
    def _out():
        o_ref[0] = (acc_ref[...] / l_ref[...]).astype(o_ref.dtype)


def flash_prefill_paged(q: jax.Array, k: jax.Array, v: jax.Array,
                        block_table: jax.Array, start: jax.Array, *,
                        k_scale=None, v_scale=None,
                        sm_scale: float | None = None,
                        interpret: bool = False) -> jax.Array:
    """Multi-query (T > 1) flash attention over a BLOCK-PAGED pool —
    the prefill/verify twin of flash_decode_paged, replacing the
    gathered-masked XLA fallback that was the last non-kernel hot path.

    q (B, H, T, D) — row b's suffix queries at positions start[b] ..
    start[b]+T-1 (the serve engine's per-row prefix-hit frontier; 0 for
    a cold prefill). k/v (num_blocks, H, page, D) — the global pool,
    fp32/bf16, int8 with (num_blocks, H, page) f32 scales, or packed
    int4 (uint8, trailing dim D//2) with the same scale shape;
    block_table (B, max_blocks) int32 with the engine's >= num_blocks
    sentinel for unallocated entries (clamped in the index_map, their
    contents never attended: positions past start+T are skipped at the
    grid level and the causal mask covers the frontier block). The pool
    must already contain the suffix K/V (the caller scatters before it
    attends, the same order the XLA path uses). Returns (B, H, T, D).

    Each (row, head) walks only ceil((start+T) / page) blocks — the
    resident-prefix blocks included, which is exactly the read a prefix
    hit pays instead of recomputing the prefix forward — and the chunk
    address is the scalar-prefetched table indirection, so the chain is
    never gathered into a contiguous copy (the per-wave byte cost the
    XLA fallback pays and this kernel exists to kill)."""
    if sm_scale is None:
        sm_scale = q.shape[-1] ** -0.5
    if (k_scale is None) != (v_scale is None):
        raise ValueError("k_scale and v_scale must be supplied together")
    if k_scale is not None and k.dtype not in (jnp.int8, jnp.uint8):
        raise ValueError(
            f"scales supplied for non-quantized k/v ({k.dtype}/{v.dtype})")
    quantized = k_scale is not None
    four_bit = quantized and k.dtype == jnp.uint8
    N, H, page, Dk = k.shape
    D = Dk * 2 if four_bit else Dk
    B, _, T, _ = q.shape
    if q.shape != (B, H, T, D):
        raise ValueError(f"q shape {q.shape} != {(B, H, T, D)}")
    if block_table.ndim != 2 or block_table.shape[0] != B:
        raise ValueError(
            f"block_table shape {block_table.shape} != ({B}, max_blocks)")
    nb = block_table.shape[1]
    pad_D = 0 if (D == 64 or D % 128 == 0) else (-D) % 128
    pad_Dk = pad_D // 2 if four_bit else pad_D
    if pad_D:
        q = jnp.pad(q, [(0, 0), (0, 0), (0, 0), (0, pad_D)])
        pads = [(0, 0), (0, 0), (0, 0), (0, pad_Dk)]
        k, v = jnp.pad(k, pads), jnp.pad(v, pads)
    Dp = D + pad_D
    Dkp = Dk + pad_Dk
    # (B, H, T, Dp) -> (B*H, T, Dp): heads fold into the row dim, the
    # same flattening as the decode kernels.
    if four_bit:
        q = _even_odd_halves(q)
    qf = q.reshape(B * H, T, Dp)
    ksf, vsf = _paged_scale_operands(k_scale, v_scale, page)

    def q_map(r, i, start, tbl):
        return (r, 0, 0)

    def kv_map(r, i, start, tbl):
        return (jnp.minimum(tbl[r // H, i], N - 1), r % H, 0, 0)

    def scale_map(r, i, start, tbl):
        if not quantized:
            return (0, 0, 0, 0)
        return (jnp.minimum(tbl[r // H, i], N - 1), r % H, 0, 0)

    kernel = functools.partial(
        _paged_prefill_kernel, page=page, heads=H, sm_scale=sm_scale,
        num_kb=nb, T=T, quantized=quantized, four_bit=four_bit)
    out = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(B * H, nb),
            in_specs=[
                pl.BlockSpec((1, T, Dp), q_map),
                pl.BlockSpec((1, 1, page, Dkp), kv_map),
                pl.BlockSpec((1, 1, page, Dkp), kv_map),
                pl.BlockSpec((1, 1, 1, page), scale_map),
                pl.BlockSpec((1, 1, 1, page), scale_map),
            ],
            out_specs=pl.BlockSpec((1, T, Dp), q_map),
            scratch_shapes=[
                pltpu.VMEM((T, Dp), jnp.float32),
                pltpu.VMEM((T, 1), jnp.float32),
                pltpu.VMEM((T, 1), jnp.float32),
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((B * H, T, Dp), q.dtype),
        compiler_params=None if interpret else pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
    )(jnp.asarray(start, jnp.int32), jnp.asarray(block_table, jnp.int32),
      qf, k, v, ksf, vsf)
    if four_bit:
        out = _interleave_halves(out)
    return out.reshape(B, H, T, Dp)[:, :, :, :D]


def paged_pad_copies(page: int, head_dim: int) -> bool:
    """True when flash_decode_paged must pad — copy — the POOL on every
    call: head_dim outside the verified-unpadded set. (A page the chip's
    compiler refuses is a compile error, not a pad: the page is the DMA
    chunk and cannot be padded in place.)"""
    return not (head_dim == 64 or head_dim % 128 == 0)


# ---------------------------------------------------------------------------
# Dispatch
# ---------------------------------------------------------------------------

def _backend() -> str:
    return jax.default_backend()


def compile_check(*, interpret: bool = False) -> None:
    """AOT lower+compile the kernels on small shapes in EVERY kv mode
    (fp, int8-with-scales, packed int4), BOTH pool layouts (contiguous
    slot rows and the block-paged table) and BOTH query shapes (the T=1
    decode walk and the T>1 paged prefill), raising on failure. A
    DIAGNOSTIC for bench.py's per-impl preflight report — it decides
    nothing: 'auto' never consults it, and tests/test_chip_compile.py
    is what holds the kernels to the chip's compiler at real widths."""
    dt = jnp.float32 if interpret else jnp.bfloat16
    q = jax.ShapeDtypeStruct((2, 2, 64), dt)
    kv = jax.ShapeDtypeStruct((2, 2, 256, 64), dt)
    kv8 = jax.ShapeDtypeStruct((2, 2, 256, 64), jnp.int8)
    kv4 = jax.ShapeDtypeStruct((2, 2, 256, 32), jnp.uint8)
    sc = jax.ShapeDtypeStruct((2, 2, 256), jnp.float32)
    ln = jax.ShapeDtypeStruct((2,), jnp.int32)
    # Paged shapes: an 8-block pool at the int8-legal page (32 rows).
    pkv = jax.ShapeDtypeStruct((8, 2, 32, 64), dt)
    pkv8 = jax.ShapeDtypeStruct((8, 2, 32, 64), jnp.int8)
    pkv4 = jax.ShapeDtypeStruct((8, 2, 32, 32), jnp.uint8)
    psc = jax.ShapeDtypeStruct((8, 2, 32), jnp.float32)
    tbl = jax.ShapeDtypeStruct((2, 4), jnp.int32)
    qT = jax.ShapeDtypeStruct((2, 2, 32, 64), dt)

    def fp(q, k, v, n):
        return flash_decode(q, k, v, n, interpret=interpret)

    def q8(q, k, v, n, ks, vs):
        return flash_decode(q, k, v, n, k_scale=ks, v_scale=vs,
                            interpret=interpret)

    def pfp(q, k, v, t, n):
        return flash_decode_paged(q, k, v, t, n, interpret=interpret)

    def pq8(q, k, v, t, n, ks, vs):
        return flash_decode_paged(q, k, v, t, n, k_scale=ks, v_scale=vs,
                                  interpret=interpret)

    def prefp(q, k, v, t, s):
        return flash_prefill_paged(q, k, v, t, s, interpret=interpret)

    def preq8(q, k, v, t, s, ks, vs):
        return flash_prefill_paged(q, k, v, t, s, k_scale=ks, v_scale=vs,
                                   interpret=interpret)

    jax.jit(fp).lower(q, kv, kv, ln).compile()
    jax.jit(q8).lower(q, kv8, kv8, ln, sc, sc).compile()
    jax.jit(q8).lower(q, kv4, kv4, ln, sc, sc).compile()
    jax.jit(pfp).lower(q, pkv, pkv, tbl, ln).compile()
    jax.jit(pq8).lower(q, pkv8, pkv8, tbl, ln, psc, psc).compile()
    jax.jit(pq8).lower(q, pkv4, pkv4, tbl, ln, psc, psc).compile()
    jax.jit(prefp).lower(qT, pkv, pkv, tbl, ln).compile()
    jax.jit(preq8).lower(qT, pkv8, pkv8, tbl, ln, psc, psc).compile()
    jax.jit(preq8).lower(qT, pkv4, pkv4, tbl, ln, psc, psc).compile()


def resolve_decode_impl(impl: str) -> str:
    """'auto' means ONE thing per backend: the compiled Pallas kernel on
    tpu, the XLA path everywhere else. Nothing is probed and nothing is
    caught — a kernel the chip's compiler refuses fails the program that
    uses it, at its first compile, instead of being served (and
    benchmarked) as the fallback. Explicit impls pass through."""
    if impl not in DECODE_IMPLS:
        raise ValueError(f"unknown decode impl: {impl!r} "
                         f"(expected one of {DECODE_IMPLS})")
    if impl != "auto":
        return impl
    return "pallas" if _backend() == "tpu" else "xla"
