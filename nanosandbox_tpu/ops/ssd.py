"""The selective state-space scan of a Mamba-2 layer (models/granite.py), in
its chunked ("SSD", state-space dual) form.

Per head h of H (P lanes a head), each head reading the group g = h // (H/G)
of B and C (N lanes a group), from S_{-1} = 0:

    S_t = exp(dt_t A) S_{t-1} + dt_t x_t (x) B_t      S in R^{P x N}, float32
    y_t = S_t C_t + D x_t

The same map computed in blocks of ``chunk`` tokens (a chunk's positions
t, s; cs = the running sum of dt A inside the chunk, last = its last entry):

    within a chunk   y_t += sum_{s <= t} exp(cs_t - cs_s) (C_t . B_s) dt_s x_s
    chunk states     st_c = sum_s exp(last - cs_s) dt_s x_s (x) B_s
    across chunks    S_c = exp(last of chunk c-1) S_{c-1} + st_{c-1}
    the carried state's output   y_t += exp(cs_t) S_c C_t

The decay exp(cs_t - cs_s) is taken of the difference masked to s <= t
BEFORE the exp: above the diagonal the difference is positive and would
overflow. The segment sums, their ``exp``s, the decays, the state and every
gradient are float32. The four products (C B^T, the masked product with
dt x, the chunk states and the state's output) take ``dtype`` inputs with
float32 accumulation.

``impl`` (``resolve_ssd_impl``, from the model's attention impl and the
shapes, as ``ops.short_conv.resolve_conv_impl`` follows it):

  * 'xla': all chunks at once (batched products over (batch, chunk, head));
    the decay block of every chunk, (H, chunk, chunk), is one array, and the
    chunks' recurrence one float32 product at full precision with the
    chunks' decay matrix (the exps of their segment sums). What the CPU, an
    init batch shorter than a chunk and shapes that do not tile run, and
    the tests' reference for the kernels.
  * 'pallas' / 'pallas_interpret': one kernel each way, under a custom VJP.
    The grid is (batch, chunk, head block), the chunk and head-block axes
    sequential. A program takes one chunk of a block of heads of one group
    (``head_block``: 8 heads of 64 in the granite cell, whole 128-lane tiles
    of x), reads x, dt, B and C of that chunk once and writes y once. C B^T,
    (chunk, chunk), is made once a chunk and group and kept in VMEM for the
    group's head blocks. The chunk's map is taken in (128, 128) blocks: a
    block on the diagonal gets its masked decay made and used in VMEM, a
    head at a time, exactly as above; below the diagonal the decay of rows
    t of block k and columns s before it is the product exp(cs_t - cs_r)
    exp(cs_r - cs_s) at r = the last position before block k, both factors
    at most 1, so those blocks are products of C B^T with factor-scaled
    rows, for the heads of a lane group at once (``lane_group``: two heads
    of 64 share a 128-lane tile, so no head's lanes are shifted). No (chunk,
    chunk) block leaves VMEM. The state of every head, (P, N) float32, lives
    in VMEM scratch across the chunk axis (zeroed at chunk 0) and moves on
    by a float32 multiply-add a chunk: the same recurrence as the XLA form's
    product, at no loss of precision. The forward writes each chunk's
    starting states (b, c, H, P, N) float32 as the residual of the
    backward, which walks the chunks in reverse carrying dS in VMEM,
    rebuilds each chunk's blocks from x, dt, B and C, and writes dx, dB and
    dC (summed over a group's heads in VMEM) and, a position and head, the
    gradient of the running sums and x . dU, and x . dy summed a chunk; XLA
    takes the reverse running sum of the first into ddt and dA, and the
    others into ddt and dD. The gradient accumulators are float32; the sums
    of dM * M that make the running sums' gradient take M (and C B^T) in
    float32 as two ``dtype`` parts. The running sums cs, their transposes
    and the counters are XLA beside the kernels, under the same scope.

Everything here runs under the named scope ``ssd``, forward and backward: a
device trace finds the scan's ops by that name, whatever implements it (the
kernels' custom calls are ``%ssd.N``).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from nanosandbox_tpu.ops.attention import resolve_attention_impl

SSD_SCOPE = "ssd"           # names the kernels' custom calls: %ssd.N
SSD_IMPLS = ("pallas", "pallas_interpret", "xla")
LANES = 128
HEAD_LANES = 512            # the most lanes of x a kernel program takes

_NT = (((1,), (1,)), ((), ()))      # a b^T
_TN = (((0,), (0,)), ((), ()))      # a^T b


def forward_flops_per_token(chunk: int, heads: int, head_dim: int,
                            d_state: int, groups: int) -> float:
    """The scan's REQUIRED forward products a token of one layer: inside
    its chunk the (chunk + 1) / 2 positions it sees on average, each costing
    2 * groups * d_state for C B^T and 2 * heads * head_dim for the masked
    product with x; the chunk state, 2 * heads * head_dim * d_state; the
    carried state's output, as much again. Backward is twice the forward."""
    return ((chunk + 1) / 2 * (2 * groups * d_state + 2 * heads * head_dim)
            + 4 * heads * head_dim * d_state)


def head_block(heads: int, groups: int, head_dim: int) -> int:
    """Heads a kernel program takes: the most heads of one group whose
    lanes of x fill whole 128-lane tiles and stay within HEAD_LANES; where
    none do, every head of a group (what the interpreter runs at the tests'
    small shapes)."""
    per_group = heads // groups
    fits = [n for n in range(1, per_group + 1)
            if per_group % n == 0 and n * head_dim % LANES == 0
            and n * head_dim <= HEAD_LANES]
    return fits[-1] if fits else per_group


def resolve_ssd_impl(attention_impl: str, T: int, chunk: int, head_dim: int,
                     d_state: int, *, heads: int, groups: int) -> str:
    """What computes the scan at these shapes. It follows the model's
    ``attention_impl`` as ``ops.short_conv.resolve_conv_impl`` does: the
    kernels where that resolves to a Pallas impl ('auto' on a tpu backend),
    the sequence holds at least one whole chunk, ``chunk`` and ``d_state``
    are whole 128-lane tiles and a head block fills whole tiles of x
    (``head_block``); 'xla' everywhere else."""
    impl = resolve_attention_impl(attention_impl)
    hb = head_block(heads, groups, head_dim)
    if (impl not in ("pallas", "pallas_interpret") or T < chunk
            or chunk % LANES or d_state % LANES or hb * head_dim % LANES):
        return "xla"
    return impl


def _segsum(a: jax.Array) -> jax.Array:
    """(..., n, n) from a (..., n): [t, s] = sum_{s < r <= t} a_r where
    s <= t, -inf above the diagonal. Summed as a running sum of a masked
    copy, so that no segment is a difference of two long sums."""
    n = a.shape[-1]
    rows = jnp.broadcast_to(a[..., :, None], a.shape + (n,))
    below = jnp.tril(jnp.ones((n, n), bool), -1)
    sums = jnp.cumsum(jnp.where(below, rows, 0.0), axis=-2)
    return jnp.where(jnp.tril(jnp.ones((n, n), bool)), sums, -jnp.inf)


def _pad_to_chunks(chunk, *arrays):
    pad = -arrays[0].shape[1] % chunk
    if not pad:
        return arrays
    return tuple(jnp.pad(a, ((0, 0), (0, pad), (0, 0))) for a in arrays)


def _xla_ssd(x, dt, A, B, C, D, *, chunk: int, groups: int, dtype):
    """The 'xla' form: ``ssd``'s result, every chunk at once."""
    b, T, HP = x.shape
    H = dt.shape[-1]
    P, N, G = HP // H, B.shape[-1] // groups, groups
    r = H // G
    f32 = jnp.float32
    x, dt, B, C = _pad_to_chunks(chunk, x, dt, B, C)
    c = x.shape[1] // chunk
    x = x.astype(f32).reshape(b, c, chunk, G, r, P)
    dt = dt.astype(f32).reshape(b, c, chunk, G, r)
    B = B.astype(dtype).reshape(b, c, chunk, G, N)
    C = C.astype(dtype).reshape(b, c, chunk, G, N)
    cs = jnp.cumsum(dt * A.astype(f32).reshape(G, r), axis=2)
    last = cs[:, :, -1:]                                # (b, c, 1, G, r)
    xdt = x * dt[..., None]

    # within each chunk: (C_t . B_s) exp(cs_t - cs_s) on s <= t
    cb = jnp.einsum("bclgn,bcsgn->bcgls", C, B, preferred_element_type=f32)
    by_head = jnp.moveaxis(cs, 2, -1)                   # (b, c, G, r, L)
    causal = jnp.tril(jnp.ones((chunk, chunk), bool))
    decay = jnp.exp(jnp.where(
        causal, by_head[..., :, None] - by_head[..., None, :], -jnp.inf))
    mixed = (cb[:, :, :, None] * decay).astype(dtype)   # (b,c,G,r,L,L)
    y = jnp.einsum("bcgrls,bcsgrp->bclgrp", mixed, xdt.astype(dtype),
                   preferred_element_type=f32)

    # each chunk's own state, and the states carried into every chunk
    kept = (xdt * jnp.exp(last - cs)[..., None]).astype(dtype)
    own = jnp.einsum("bclgn,bclgrp->bcgrpn", B, kept,
                     preferred_element_type=f32)
    chunks = _segsum(jnp.pad(jnp.moveaxis(last[:, :, 0], 1, -1),
                             ((0, 0), (0, 0), (0, 0), (1, 0))))
    carried = jnp.einsum("bgrzk,bkgrpn->bzgrpn", jnp.exp(chunks)[..., 1:],
                         own, precision=lax.Precision.HIGHEST)
    y = y + jnp.exp(cs)[..., None] * jnp.einsum(
        "bclgn,bcgrpn->bclgrp", C, carried[:, :c].astype(dtype),
        preferred_element_type=f32)

    y = (y + D.astype(f32).reshape(G, r, 1) * x).reshape(b, c * chunk, HP)
    stats = {"ssd_decay": jnp.mean(jnp.exp(last)),
             "ssd_state_max": jnp.max(jnp.abs(carried[:, 1:]))}
    return y[:, :T], stats


# -- the kernels ----------------------------------------------------------------

def _tile(chunk: int) -> int:
    """Rows and columns of a block of a chunk's (chunk, chunk) map: a whole
    128-lane tile where the chunk holds whole ones, else half the chunk (the
    interpreter's small shapes, so that the blocks off the diagonal run
    there too)."""
    if chunk % LANES == 0:
        return LANES
    return chunk // 2 if chunk % 2 == 0 else chunk


def lane_group(hb: int, P: int, chunk: int) -> int:
    """Heads whose P lanes share one 128-lane tile (two of 64): a program
    takes them together, so that x, y and every (L, P) array fill whole
    vregs and no head's lanes are shifted. At most a chunk's width of
    lanes (the backward writes a group's sums in a chunk's row)."""
    if LANES % P:
        return 1
    return max(n for n in range(1, min(hb, LANES // P) + 1)
               if hb % n == 0 and n * P <= chunk)


def _per_head(dt_ref, cs_ref, W: int, N: int, tile: int):
    """What the block's hb heads need of dt and cs: cs as (hb, L) rows; dt
    and cs as (L, 2 hb) columns (dt's in lanes 0..hb-1); as (hb, W) lanes,
    ``last`` and, for each block boundary b = tile, 2 tile, ..., cs_{b-1};
    exp(last) in (hb, N) lanes. Those are taken by masked sums along lanes,
    not sliced from a lane: Mosaic broadcasts along sublanes and lanes at
    once only from lane 0."""
    dt_rows, cs_rows = dt_ref[0, 0], cs_ref[0, 0]
    hb, L = cs_rows.shape
    lane = lax.broadcasted_iota(jnp.int32, (hb, L), 1)
    at = lambda pos: jnp.sum(jnp.where(lane == pos, cs_rows, 0.0), axis=1,
                             keepdims=True)                      # (hb, 1)
    last = at(L - 1)
    refs = [jnp.broadcast_to(at(b - 1), (hb, W)) for b in range(tile, L, tile)]
    cols = jnp.transpose(jnp.concatenate([dt_rows, cs_rows], axis=0))
    return (cs_rows, cols, jnp.broadcast_to(last, (hb, W)), refs,
            jnp.broadcast_to(jnp.exp(last), (hb, N)))


class _Group:
    """One lane group's heads h0 .. h0 + gl - 1 (P lanes each, W = gl P in
    all): per head and block k of rows, cs broadcast along max(tile, W)
    lanes (one lane permute a vreg: the only broadcast of a column); and, in
    the group's (rows, W) lanes, each head's values in its own P lanes: dt,
    cs, exp(cs), exp(last - cs) and, below the first block, the decay's
    factors exp(cs_t - cs_{b-1}) from b = k tile on and exp(cs_{b-1} - cs_s)
    before it."""

    def __init__(self, cols, cs_rows, last, refs, h0, gl, P, tile):
        L, hb = cols.shape[0], cols.shape[1] // 2
        W = gl * P
        self.W, self.heads = W, range(gl)
        self.own = [lax.broadcasted_iota(jnp.int32, (1, W), 1) // P == m
                    for m in self.heads]
        pick = lambda parts: functools.reduce(
            lambda acc, m: jnp.where(self.own[m], parts[m], acc),
            self.heads[1:], parts[0])
        self.pick = pick
        width = max(tile, W)
        self.dt = pick([jnp.broadcast_to(cols[:, h0 + m:h0 + m + 1], (L, W))
                        for m in self.heads])
        self.cs, self.cs_row = [], []           # [k][m]: (tile, tile), (1, tile)
        self.cs_w = []                          # [k]: (tile, W)
        for k in range(L // tile):
            t = slice(k * tile, (k + 1) * tile)
            each = [jnp.broadcast_to(cols[t, hb + h0 + m:hb + h0 + m + 1],
                                     (tile, width)) for m in self.heads]
            self.cs.append([e[:, :tile] for e in each])
            self.cs_row.append([cs_rows[h0 + m:h0 + m + 1, t]
                                for m in self.heads])
            self.cs_w.append(pick([e[:, :W] for e in each]))
        self.last = pick([last[h0 + m:h0 + m + 1] for m in self.heads])
        self.refs = [pick([r[h0 + m:h0 + m + 1] for m in self.heads])
                     for r in refs]
        self.grown = [jnp.exp(c) for c in self.cs_w]
        self.kept = [jnp.exp(self.last - c) for c in self.cs_w]

    def from_ref(self, k):
        """exp(cs_t - cs_{b-1}) on block k's rows, b = k tile."""
        return jnp.exp(self.cs_w[k] - self.refs[k - 1])

    def to_ref(self, k):
        """exp(cs_{b-1} - cs_s) on the rows before block k, b = k tile."""
        return jnp.concatenate([jnp.exp(self.refs[k - 1] - c)
                                for c in self.cs_w[:k]], axis=0)

    def rows(self, v):
        """(W, N) rows: head m's (1, N) row of v (hb, N) on its P rows."""
        P = self.W // len(self.heads)
        row = lax.broadcasted_iota(jnp.int32, (self.W, 1), 0) // P
        return functools.reduce(
            lambda acc, m: jnp.where(row == m, v[m:m + 1], acc),
            self.heads[1:], jnp.broadcast_to(v[0:1], (self.W, v.shape[1])))


def _diagonal_block(G, cs, cs_row, causal):
    """(decay, C B^T * decay) of a block on the diagonal, float32: the
    difference masked to s <= t before the exp."""
    decay = jnp.exp(jnp.where(causal, cs - cs_row, -jnp.inf))
    return decay, G * decay


def _fwd_kernel(x_ref, dt_ref, cs_ref, b_ref, c_ref, d_ref, y_ref, smax_ref,
                *rest, hb: int, P: int, gl: int, per_group: int, tile: int,
                dtype):
    """One chunk of one head block. x_ref / y_ref (1, L, hb P) float32;
    dt_ref / cs_ref (1, 1, hb, L) float32, the block's dt and running sums of
    dt A; b_ref / c_ref (1, L, N) of the block's group; d_ref (1, hb P), D a
    lane; smax_ref (1, 8, 128), the largest |S| at a chunk's end so far of
    this batch row; rest: [s0_ref (1, 1, hb / gl, W, N), the chunk's
    starting states], the states (head blocks, hb / gl, W, N), C B^T (L, L)
    float32 and in ``dtype``, in VMEM. A lane group's gl heads are taken
    together (``lane_group``; W = gl P): their states stacked as (W, N).

    The chunk's (L, L) map is taken in (tile, tile) blocks. A block on the
    diagonal is made and used as the XLA form makes it, a head at a time.
    Below the diagonal, the rows t of block k and every column s before it,
    the decay is the product exp(cs_t - cs_r) exp(cs_r - cs_s) at r = k tile
    - 1, both factors at most 1: those blocks are C B^T in ``dtype`` times
    the columns' factors times dt x, the rows' factors applied to the
    product's float32 result, for the group's heads at once. No exp is
    taken there."""
    s0_ref = rest[0] if len(rest) == 4 else None
    state, gram, gram_lo = rest[-3:]
    chunk, block = pl.program_id(1), pl.program_id(2)
    L = x_ref.shape[1]
    W = gl * P
    f32 = jnp.float32

    @pl.when((chunk == 0) & (block == 0))
    def _():
        smax_ref[...] = jnp.zeros(smax_ref.shape, f32)

    @pl.when(chunk == 0)
    def _():
        state[block] = jnp.zeros(state.shape[1:], f32)

    Cb, Bb = c_ref[0].astype(dtype), b_ref[0].astype(dtype)

    @pl.when(block % per_group == 0)
    def _():
        gram[...] = lax.dot_general(Cb, Bb, _NT, preferred_element_type=f32)
        gram_lo[...] = gram[...].astype(dtype)

    cs_rows, cols, last, refs, carry = _per_head(dt_ref, cs_ref, W,
                                                 Bb.shape[1], tile)
    causal = (lax.broadcasted_iota(jnp.int32, (tile, tile), 0)
              >= lax.broadcasted_iota(jnp.int32, (tile, tile), 1))
    biggest = jnp.zeros(state.shape[2:], f32)
    for j in range(hb // gl):
        lanes = slice(j * W, (j + 1) * W)
        g = _Group(cols, cs_rows, last, refs, j * gl, gl, P, tile)
        x = x_ref[0, :, lanes]
        u = x * g.dt
        ub = u.astype(dtype)
        S = state[block, j]
        if s0_ref is not None:
            s0_ref[0, 0, j] = S
        out = lax.dot_general(Cb, S.astype(dtype), _NT,
                              preferred_element_type=f32)          # (L, W)
        for k in range(L // tile):
            t = slice(k * tile, (k + 1) * tile)
            y = g.pick([jnp.dot(_diagonal_block(
                gram[t, t], g.cs[k][m], g.cs_row[k][m], causal)[1].astype(
                    dtype), ub[t], preferred_element_type=f32)
                for m in g.heads])
            if k:
                scaled = (u[:k * tile] * g.to_ref(k)).astype(dtype)
                y = y + g.from_ref(k) * jnp.dot(
                    gram_lo[t, :k * tile], scaled, preferred_element_type=f32)
            y = y + g.grown[k] * out[t]
            y_ref[0, t, lanes] = y + d_ref[:, lanes] * x[t]
        kept = jnp.concatenate(g.kept, axis=0)
        S = g.rows(carry[j * gl:(j + 1) * gl]) * S + lax.dot_general(
            (u * kept).astype(dtype), Bb, _TN, preferred_element_type=f32)
        state[block, j] = S
        biggest = jnp.maximum(biggest, jnp.abs(S))
    smax_ref[0] = jnp.maximum(smax_ref[0], jnp.max(biggest, keepdims=True))


def _bwd_kernel(x_ref, dt_ref, cs_ref, b_ref, c_ref, d_ref, s0_ref, dy_ref,
                dx_ref, db_ref, dc_ref, rows_ref, dstate, gram, gram_lo,
                gram_rest, dgram, *, hb: int, P: int, gl: int, per_group: int,
                tile: int, dtype):
    """One chunk of one head block, the chunks walked in reverse. As the
    forward, and: s0_ref (1, 1, hb / gl, W, N), the chunk's starting
    states; dy_ref / dx_ref (1, L, hb P) float32; db_ref / dc_ref (1, L, N)
    float32, the group's gradients, summed over its head blocks in place;
    rows_ref (1, 1, 3 hb, L) float32: a head's gradient of cs and x . dU at
    each position, and (rows 2hb.., first W lanes) a lane group's sums over
    the chunk's positions of x * dy; dstate (head blocks, hb / gl, W, N), the
    gradient of each chunk's final states; dgram (L, L), the gradient of
    C B^T.

    With M = C B^T * decay, dM = dy u^T and Q = dM * M, Q's row sums
    (dcs_t += ...) and column sums (dcs_s -= ...) are taken as dy . (M u)
    and u . (M^T dy) a position, of the same rounded factors, so that each
    Q_ts enters both as one number; M there (and C B^T below the diagonal)
    is the float32 one as two ``dtype`` parts, M^T dy with its first part
    is dU's. A block on the
    diagonal is rebuilt as the forward builds it; below the diagonal the
    decay's two factors f make every term a product: dU_s += f_s (G^T (f_t
    dy))_s and dG += (f_t dy) (f_s u)^T. Every term of dcs at a position is
    summed in the group's lanes first and reduced along lanes once a head."""
    chunk, block = pl.program_id(1), pl.program_id(2)
    L = x_ref.shape[1]
    W = gl * P
    f32 = jnp.float32

    @pl.when(chunk == 0)
    def _():
        dstate[block] = jnp.zeros(dstate.shape[1:], f32)

    Cb, Bb = c_ref[0].astype(dtype), b_ref[0].astype(dtype)

    @pl.when(block % per_group == 0)
    def _():
        gram[...] = lax.dot_general(Cb, Bb, _NT, preferred_element_type=f32)
        gram_lo[...] = gram[...].astype(dtype)
        gram_rest[...] = (gram[...] - gram_lo[...].astype(f32)).astype(dtype)
        dgram[...] = jnp.zeros(dgram.shape, f32)
        db_ref[...] = jnp.zeros(db_ref.shape, f32)
        dc_ref[...] = jnp.zeros(dc_ref.shape, f32)

    cs_rows, cols, last, refs, carry = _per_head(dt_ref, cs_ref, W,
                                                 Bb.shape[1], tile)
    causal = (lax.broadcasted_iota(jnp.int32, (tile, tile), 0)
              >= lax.broadcasted_iota(jnp.int32, (tile, tile), 1))
    at_last = lax.broadcasted_iota(jnp.int32, (L, 1), 0) == L - 1
    rowsum = lambda a: jnp.sum(a, axis=1, keepdims=True)
    db = jnp.zeros(db_ref.shape[1:], f32)
    dc = jnp.zeros(dc_ref.shape[1:], f32)
    dcs_cols, xdu_cols, xdy_rows = [], [], []
    for j in range(hb // gl):
        lanes = slice(j * W, (j + 1) * W)
        g = _Group(cols, cs_rows, last, refs, j * gl, gl, P, tile)
        x, dy = x_ref[0, :, lanes], dy_ref[0, :, lanes]
        u = x * g.dt
        ub, dyb = u.astype(dtype), dy.astype(dtype)
        uf, dyf = ub.astype(f32), dyb.astype(f32)
        S0, dS1 = s0_ref[0, 0, j], dstate[block, j]
        S0b, dS1b = S0.astype(dtype), dS1.astype(dtype)
        V = lax.dot_general(Bb, dS1b, _NT, preferred_element_type=f32)
        Z = lax.dot_general(Cb, S0b, _NT, preferred_element_type=f32)
        grown = jnp.concatenate(g.grown, axis=0)
        kept = jnp.concatenate(g.kept, axis=0)
        through = kept * u * V
        # every term of dcs a position, in the group's lanes
        acc = grown * dy * Z - through
        acc_t, du_t = [], []
        for k in range(L // tile):
            t = slice(k * tile, (k + 1) * tile)
            fwd, back, back_q = [], [], []
            for m in g.heads:
                decay, mixed = _diagonal_block(gram[t, t], g.cs[k][m],
                                               g.cs_row[k][m], causal)
                hi = mixed.astype(dtype)
                lo = (mixed - hi.astype(f32)).astype(dtype)
                mine = jnp.where(g.own[m], dyb[t], 0)
                # dM = dy u^T: masked by the decay, 0 above the diagonal
                dmixed = lax.dot_general(mine, ub[t], _NT,
                                         preferred_element_type=f32)
                dgram[t, t] += dmixed * decay
                fwd.append(jnp.dot(hi, ub[t], preferred_element_type=f32)
                           + jnp.dot(lo, ub[t], preferred_element_type=f32))
                back.append(lax.dot_general(hi, dyb[t], _TN,
                                            preferred_element_type=f32))
                back_q.append(back[-1] + lax.dot_general(
                    lo, dyb[t], _TN, preferred_element_type=f32))
            acc_t.append(acc[t] + dyf[t] * g.pick(fwd)
                         - uf[t] * g.pick(back_q))
            du_t.append(kept[t] * V[t] + g.pick(back))
            if k:
                hib = (g.from_ref(k) * dy[t]).astype(dtype)
                f_s = g.to_ref(k)
                lob = (f_s * u[:k * tile]).astype(dtype)
                G = gram_lo[t, :k * tile]
                rest = gram_rest[t, :k * tile]
                dgram[t, :k * tile] += lax.dot_general(
                    hib, lob, _NT, preferred_element_type=f32)
                acc_t[k] = acc_t[k] + hib.astype(f32) * (
                    jnp.dot(G, lob, preferred_element_type=f32)
                    + jnp.dot(rest, lob, preferred_element_type=f32))
                back_o = lax.dot_general(G, hib, _TN,
                                         preferred_element_type=f32)
                back_oq = back_o + lax.dot_general(
                    rest, hib, _TN, preferred_element_type=f32)
                for i in range(k):
                    s = slice(i * tile, (i + 1) * tile)
                    du_t[i] = du_t[i] + f_s[s] * back_o[s]
                    acc_t[i] = acc_t[i] - lob[s].astype(f32) * back_oq[s]
        du = jnp.concatenate(du_t, axis=0)
        acc = jnp.concatenate(acc_t, axis=0)
        dy_grown = (grown * dy).astype(dtype)
        dc = dc + jnp.dot(dy_grown, S0b, preferred_element_type=f32)
        db = db + jnp.dot((kept * u).astype(dtype), dS1b,
                          preferred_element_type=f32)
        carried = g.rows(carry[j * gl:(j + 1) * gl])
        dstate[block, j] = carried * dS1 + lax.dot_general(
            dy_grown, Cb, _TN, preferred_element_type=f32)
        ends = carried * dS1 * S0                                  # (W, N)
        state_row = lax.broadcasted_iota(jnp.int32, (W, 1), 0) // P
        xdu = x * du
        for m in g.heads:
            end = (jnp.sum(jnp.where(state_row == m, ends, 0.0),
                           keepdims=True)
                   + jnp.sum(jnp.where(g.own[m], through, 0.0),
                             keepdims=True))
            dcs_cols.append(rowsum(jnp.where(g.own[m], acc, 0.0))
                            + jnp.where(at_last, end, 0.0))
            xdu_cols.append(rowsum(jnp.where(g.own[m], xdu, 0.0)))
        dx_ref[0, :, lanes] = g.dt * du + d_ref[:, lanes] * dy
        xdy_rows.append(jnp.sum(x * dy, axis=0, keepdims=True))   # (1, W)
    rows = jnp.transpose(jnp.concatenate(dcs_cols + xdu_cols, axis=1))
    xdy = jnp.concatenate(xdy_rows, axis=0)                  # (hb / gl, W)
    if L > W:
        xdy = jnp.concatenate([xdy, jnp.zeros((hb // gl, L - W), f32)], axis=1)
    if gl > 1:
        xdy = jnp.concatenate([xdy, jnp.zeros((hb - hb // gl, L), f32)], axis=0)
    rows_ref[0, 0] = jnp.concatenate([rows, xdy], axis=0)
    db_ref[0] += db
    dc_ref[0] += dc

    @pl.when(block % per_group == per_group - 1)
    def _():
        dG = dgram[...].astype(dtype)
        dc_ref[0] += jnp.dot(dG, Bb, preferred_element_type=f32)
        db_ref[0] += lax.dot_general(dG, Cb, _TN, preferred_element_type=f32)


def _prepare(x, dt, A, B, C, D, chunk: int, hb: int):
    """Whole chunks; dt and its running sums of dt A inside each chunk as
    rows (b, head blocks, hb, T) of each head block; D a lane (1, H P)."""
    f32 = jnp.float32
    x, dt, B, C = _pad_to_chunks(chunk, x.astype(f32), dt.astype(f32),
                                 B.astype(f32), C.astype(f32))
    b, T, H = dt.shape
    cs = jnp.cumsum((dt * A.astype(f32)).reshape(b, T // chunk, chunk, H),
                    axis=2)
    rows = lambda a: jnp.swapaxes(a.reshape(b, T, H), 1, 2).reshape(
        b, H // hb, hb, T)
    d_lanes = jnp.repeat(D.astype(f32), x.shape[-1] // H)[None]
    return x, rows(dt), rows(cs), B, C, d_lanes, cs[:, :, -1]


def _specs(b, T, H, P, N, hb, gl, chunk, per_group, reverse):
    """The kernels' BlockSpecs over the grid (batch, chunk, head block): a
    block of x's lanes, a block's rows of dt / cs, the block's group of B /
    C, D's lanes, the block's states (a lane group's stacked). Walked in
    ``reverse``, program c sees chunk ``chunks - 1 - c``."""
    chunks = T // chunk
    at = (lambda c: chunks - 1 - c) if reverse else (lambda c: c)
    lanes = pl.BlockSpec((1, chunk, hb * P), lambda i, c, j: (i, at(c), j))
    rows = pl.BlockSpec((1, 1, hb, chunk), lambda i, c, j: (i, j, 0, at(c)))
    group = pl.BlockSpec((1, chunk, N),
                         lambda i, c, j: (i, at(c), j // per_group))
    d_lanes = pl.BlockSpec((1, hb * P), lambda i, c, j: (0, j))
    states = pl.BlockSpec((1, 1, hb // gl, gl * P, N),
                          lambda i, c, j: (i, at(c), j, 0, 0))
    return lanes, rows, group, d_lanes, states


@functools.partial(jax.jit, static_argnames=(
    "chunk", "groups", "dtype", "interpret", "residual"))
def _pallas_ssd_fwd(x, dt, A, B, C, D, *, chunk: int, groups: int, dtype,
                    interpret: bool, residual: bool):
    """(y (b, T, H P) float32, ssd_decay, ssd_state_max) and, with
    ``residual``, what the backward reads: the kernel's inputs and each
    chunk's starting states (b, c, H / gl, gl P, N) float32, a lane group's
    heads stacked."""
    b, T0, HP = x.shape
    H = dt.shape[-1]
    P, N = HP // H, B.shape[-1] // groups
    hb = head_block(H, groups, P)
    gl = lane_group(hb, P, chunk)
    per_group = H // groups // hb
    xs, dt_r, cs_r, Bs, Cs, d_lanes, last = _prepare(x, dt, A, B, C, D,
                                                     chunk, hb)
    T = xs.shape[1]
    lanes, rows, group, d_spec, states = _specs(b, T, H, P, N, hb, gl, chunk,
                                                per_group, reverse=False)
    out_specs = [lanes, pl.BlockSpec((1, 8, LANES), lambda i, c, j: (i, 0, 0))]
    out_shape = [jax.ShapeDtypeStruct((b, T, HP), jnp.float32),
                 jax.ShapeDtypeStruct((b, 8, LANES), jnp.float32)]
    if residual:
        out_specs.append(states)
        out_shape.append(jax.ShapeDtypeStruct(
            (b, T // chunk, H // gl, gl * P, N), jnp.float32))
    call = pl.pallas_call(
        functools.partial(_fwd_kernel, hb=hb, P=P, gl=gl, per_group=per_group,
                          tile=_tile(chunk), dtype=dtype),
        grid=(b, T // chunk, H // hb),
        in_specs=[lanes, rows, rows, group, group, d_spec],
        out_specs=out_specs, out_shape=out_shape,
        scratch_shapes=[pltpu.VMEM((H // hb, hb // gl, gl * P, N),
                                   jnp.float32),
                        pltpu.VMEM((chunk, chunk), jnp.float32),
                        pltpu.VMEM((chunk, chunk), dtype)],
        compiler_params=None if interpret else pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary", "arbitrary"),
            vmem_limit_bytes=64 * 1024 * 1024),
        interpret=interpret)
    with jax.named_scope(SSD_SCOPE):
        y, smax, *s0 = call(xs, dt_r, cs_r, Bs, Cs, d_lanes)
        out = (y[:, :T0], jnp.mean(jnp.exp(last)), jnp.max(smax))
    if residual:
        return out, (xs, dt_r, cs_r, Bs, Cs, d_lanes, s0[0])
    return out


@functools.partial(jax.jit, static_argnames=(
    "chunk", "groups", "dtype", "interpret"))
def _pallas_ssd_bwd(A, res, dy, *, chunk: int, groups: int, dtype,
                    interpret: bool):
    """The gradients (dx, ddt, dA, dB, dC, dD), float32 at the kernel's
    padded length, from the forward's residuals and the cotangent of y."""
    xs, dt_r, cs_r, Bs, Cs, d_lanes, s0 = res
    b, T, HP = xs.shape
    _, blocks, hb, _ = dt_r.shape
    H = blocks * hb
    P, N = HP // H, Bs.shape[-1] // groups
    per_group = H // groups // hb
    gl = lane_group(hb, P, chunk)
    dy = _pad_to_chunks(chunk, dy.astype(jnp.float32))[0]
    lanes, rows, group, d_spec, states = _specs(b, T, H, P, N, hb, gl, chunk,
                                                per_group, reverse=True)
    call = pl.pallas_call(
        functools.partial(_bwd_kernel, hb=hb, P=P, gl=gl, per_group=per_group,
                          tile=_tile(chunk), dtype=dtype),
        grid=(b, T // chunk, blocks),
        in_specs=[lanes, rows, rows, group, group, d_spec, states, lanes],
        out_specs=[lanes, group, group,
                   pl.BlockSpec((1, 1, 3 * hb, chunk),
                                lambda i, c, j: (i, j, 0, T // chunk - 1 - c))],
        out_shape=[jax.ShapeDtypeStruct((b, T, HP), jnp.float32),
                   jax.ShapeDtypeStruct(Bs.shape, jnp.float32),
                   jax.ShapeDtypeStruct(Cs.shape, jnp.float32),
                   jax.ShapeDtypeStruct((b, blocks, 3 * hb, T), jnp.float32)],
        scratch_shapes=[pltpu.VMEM((blocks, hb // gl, gl * P, N),
                                   jnp.float32),
                        pltpu.VMEM((chunk, chunk), jnp.float32),
                        pltpu.VMEM((chunk, chunk), dtype),
                        pltpu.VMEM((chunk, chunk), dtype),
                        pltpu.VMEM((chunk, chunk), jnp.float32)],
        compiler_params=None if interpret else pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary", "arbitrary"),
            vmem_limit_bytes=64 * 1024 * 1024),
        interpret=interpret)
    with jax.named_scope(SSD_SCOPE):
        dx, dB, dC, per_pos = call(xs, dt_r, cs_r, Bs, Cs, d_lanes, s0, dy)
        per_pos = per_pos.reshape(b, blocks, 3, hb, T)
        # (b, T, H), positions along sublanes as the forward's running sums
        by_token = lambda a: jnp.swapaxes(a.reshape(b, H, T), 1, 2)
        # cs_t = sum_{s <= t} dt_s A inside a chunk: dcs's reverse running
        # sum inside each chunk is the gradient of dt_s A
        g = lax.cumsum(by_token(per_pos[:, :, 0]).reshape(
            b, T // chunk, chunk, H), axis=2, reverse=True).reshape(b, T, H)
        ddt = A.astype(jnp.float32) * g + by_token(per_pos[:, :, 1])
        dA = jnp.sum(by_token(dt_r) * g, axis=(0, 1))
        # x . dy summed a chunk, a lane group's in the first gl P lanes
        xdy = per_pos[:, :, 2, :hb // gl].reshape(b, blocks, hb // gl,
                                                  T // chunk, chunk)
        dD = jnp.sum(xdy[..., :gl * P].reshape(
            b, blocks, hb // gl, T // chunk, gl, P), axis=(0, 3, 5)).reshape(H)
    return dx, ddt, dA, dB, dC, dD


@functools.partial(jax.custom_vjp, nondiff_argnums=(6, 7, 8, 9))
def _kernel_ssd(x, dt, A, B, C, D, chunk, groups, dtype, interpret):
    return _pallas_ssd_fwd(x, dt, A, B, C, D, chunk=chunk, groups=groups,
                           dtype=dtype, interpret=interpret, residual=False)


def _ssd_fwd_rule(x, dt, A, B, C, D, chunk, groups, dtype, interpret):
    with jax.named_scope(SSD_SCOPE):
        out, res = _pallas_ssd_fwd(x, dt, A, B, C, D, chunk=chunk,
                                   groups=groups, dtype=dtype,
                                   interpret=interpret, residual=True)
    # the inputs' dtypes, carried by empty arrays
    dtypes = tuple(jnp.zeros((0,), a.dtype) for a in (x, dt, A, B, C, D))
    return out, (A, res, dtypes)


def _ssd_bwd_rule(chunk, groups, dtype, interpret, saved, cts):
    A, res, dtypes = saved
    dy = cts[0]
    with jax.named_scope(SSD_SCOPE):
        grads = _pallas_ssd_bwd(A, res, dy, chunk=chunk, groups=groups,
                                dtype=dtype, interpret=interpret)
        T = dy.shape[1]
        return tuple((g[:, :T] if g.ndim == 3 else g).astype(a.dtype)
                     for g, a in zip(grads, dtypes))


_kernel_ssd.defvjp(_ssd_fwd_rule, _ssd_bwd_rule)


def ssd(x: jax.Array, dt: jax.Array, A: jax.Array, B: jax.Array,
        C: jax.Array, D: jax.Array, *, chunk: int, groups: int = 1,
        dtype=jnp.bfloat16, impl: str = "xla"):
    """y (b, T, H*P) float32 and the scan's counters from x (b, T, H*P) as
    the input projection leaves it, dt (b, T, H) (positive: after the
    softplus), A (H,) (negative), B / C (b, T, G*N), D (H,). T is padded to
    a whole number of chunks (dt = 0, x = 0 there: nothing moves). ``impl``
    is a resolved one (``resolve_ssd_impl``).

    The counters, float32 scalars no gradient reaches: ``ssd_decay``, the
    mean over batch rows, chunks and heads of exp(sum over a chunk of
    dt A), what the state keeps across one chunk; ``ssd_state_max``, the
    largest |S| at a chunk boundary (every chunk's end)."""
    if impl not in SSD_IMPLS:
        raise ValueError(f"unknown scan impl {impl!r} "
                         f"(expected one of {SSD_IMPLS})")
    with jax.named_scope(SSD_SCOPE):
        if impl == "xla":
            y, stats = _xla_ssd(x, dt, A, B, C, D, chunk=chunk,
                                groups=groups, dtype=dtype)
        else:
            y, decay, smax = _kernel_ssd(x, dt, A, B, C, D, chunk, groups,
                                         jnp.dtype(dtype),
                                         impl == "pallas_interpret")
            stats = {"ssd_decay": decay, "ssd_state_max": smax}
        return y, jax.tree.map(lax.stop_gradient, stats)
