"""The routed-expert layer's device work: rows sorted by expert into a
bounded buffer, a grouped matmul over the experts HELD here, and the
weighted sum back to tokens.

A chip of an expert-parallel job holds ``count`` of a layer's ``E`` experts
(``first .. first + count - 1``). The router scores and selects over all E;
of a token's k (token, slot) choices only those naming a held expert become
rows here. On one chip there is no exchange, and nothing in this file stands
in for one: what absent experts would have added is simply not in the sum.

**The buffer and its bound.** The held (token, slot) pairs are sorted by
expert. No router can select more than ``never = N * min(k, count)`` held
pairs from N tokens: that is the static bound, and nothing is ever dropped.
It is k * E / count / min(k, count) times the EXPECTED load N * k * count / E
(8x at 8 of 128 experts a token, 16 held), and a random router on real text
does reach 2.3x of the expected load in a layer (every occurrence of a
frequent token goes the same way; PERF.md §6, PR 29), so neither the
expected load nor a small multiple of it is a bound. The sorted pairs are
therefore walked in CHUNKS of ``rows`` rows (``chunk_rows``: ROWS_FACTOR
times the expected load, in whole tiles), ``ceil(never / rows)`` of them.
THE FIRST CHUNK always runs, under no loop and no ``lax.cond``, and what it
gives is used as it is: its combine's float32 (N, d) IS the layer's output,
its ``jax.vjp``'s gradients, in the operands' own types, ARE the layer's
gradients. A first chunk that holds no pair gives zeros by itself
(``row_valid`` is all false and every group empty), so a layer that holds
nothing needs no case of its own. The chunks after it run under a scan
whose body is a ``lax.cond``: a chunk that no held pair reaches is not run,
and only where a second chunk holds pairs (``total > rows``) do sums over
chunks exist: float32, started from the first chunk's results (cast to
float32 in the backward), added to in chunk order and cast back at the end.
Memory is one chunk's; work is the chunks that hold pairs: a layer at the
expected load runs one, and pays for no sum, no zero-fill and no cast.
``dropped`` (pairs held less pairs covered by the chunks run) is 0 by
construction and is counted all the same: the trainer's counter and the
benchmark's ``fault`` read it.

Both directions of dispatch and combine are GATHERS (custom VJPs below): a
row knows its token (``row_token``), a (token, slot) pair knows its row
(``dest``), so neither pass needs a scatter-add with repeated indices. What
runs where:

  * rows from tokens (dispatch, its recompute, combine's backward:
    ``x[row_token]``, ``dout[row_token]``, ``w[row_pair]``): XLA gathers of
    ``rows`` indices on every backend (1.1 / 1.3 / 0.2 ms each in the
    Trinity-Mini cell's step; a kernel copying only the rows held was no
    faster: PERF.md §6, PR 32).
  * tokens from rows (combine, dispatch's backward): sum_j of a token's k
    slots, of which k * count / E hold a pair; and ``dw_row[dest]``, one
    number a pair. The 'xla' mover is k gathers of (N, d), 0.6 ms each
    whether a slot is held or not (_rows_of_pairs), and a gather of N * k
    numbers (0.94 ms). The 'pallas' mover (_pallas_rows_to_tokens,
    _pallas_row_scalars_to_pairs; custom calls %moe_rows.N) reads the plan
    from scalar memory and touches ONLY the pairs held: rows come by
    asynchronous copies, a set of tokens' in flight while the set before is
    summed, 0.9 ms a pass in the step against 5.9, the same float32 sums bit
    for bit; dw in 0.43 ms. ``resolve_row_mover`` picks from the grouped
    matmul's resolved ``impl`` and the shapes; there is no option.

Grouped matmul, ``impl``: 'ragged_dot' (``jax.lax.ragged_dot``: XLA's own,
every backend), 'megablox' (the Pallas kernels shipped with JAX,
``jax.experimental.pallas.ops.tpu.megablox``, forward + both backward
products under their custom VJP), 'megablox_interpret' (the same in the
Pallas interpreter, CPU tests). 'auto' is ONE thing a backend, as
``ops.attention.resolve_attention_impl``: the choice measured on the chip
(PERF.md §6, PR 29: 3.94 against 8.00 ms) on tpu, 'ragged_dot' elsewhere.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

__all__ = ["chunk_rows", "plan_pairs", "chunk_plan", "dispatch", "combine",
           "grouped_matmul", "expert_ffn", "routed_experts", "gmm_tiling",
           "resolve_gmm_impl", "resolve_row_mover", "GMM_IMPLS", "MOVERS"]

GMM_IMPLS = ("auto", "ragged_dot", "megablox", "megablox_interpret")
MOVERS = ("pallas", "pallas_interpret", "xla")
# What 'auto' means on a tpu backend: measured, one expert layer's three
# products at (16,384 rows, 16 groups, 2048 x 1024) on a v5e (PERF.md §6).
TPU_GMM_IMPL = "megablox"
# Rows of the buffer come in whole tiles of the grouped matmul's m tiling.
ROW_TILE = 512
# Rows of ONE chunk of the sorted pairs, as a multiple of the expected load.
ROWS_FACTOR = 2.0
# megablox's (m, k, n) tiles; gmm_tiling picks between the two by the shapes.
MEGABLOX_TILING = (512, 1024, 1024)
MEGABLOX_TILING_NARROW = (512, 2048, 512)
LANES = 128
MOVE_SCOPE = "moe_rows"   # names the row mover's custom calls: %moe_rows.N
# The row mover's walk: a program sums MOVE_STEP tokens, MOVE_TOKENS at a
# time with the next MOVE_TOKENS' rows already in flight; the plan reaches
# scalar memory in blocks of SMEM_BLOCK tokens (XLA tiles a 1-D 32-bit array
# by 1024, and a block is whole tiles).
MOVE_STEP = 256
MOVE_TOKENS = 64
SMEM_BLOCK = 1024
SCALAR_BYTES = 512 * 1024   # of a chip's 1 MiB of scalar memory: one array


def resolve_gmm_impl(impl: str) -> str:
    if impl not in GMM_IMPLS:
        raise ValueError(f"unknown grouped-matmul impl {impl!r} "
                         f"(expected one of {GMM_IMPLS})")
    if impl != "auto":
        return impl
    return TPU_GMM_IMPL if jax.default_backend() == "tpu" else "ragged_dot"


def chunk_rows(n_tokens: int, k: int, n_experts: int, count: int,
               factor: float = ROWS_FACTOR) -> tuple[int, int]:
    """(rows of a chunk, chunks): ``factor`` x the expected load
    N * k * count / E in whole ROW_TILEs, and as many chunks as cover the
    bound no router can exceed, N * min(k, count)."""
    never = n_tokens * min(k, count)
    want = min(never, math.ceil(factor * n_tokens * k * count / n_experts))
    rows = -(-want // ROW_TILE) * ROW_TILE
    return rows, -(-never // rows)


def plan_pairs(sel: jax.Array, first: int, count: int, padded: int) -> dict:
    """Every held (token, slot) pair's place among the held pairs sorted by
    expert ((token, slot) order kept inside an expert's group).

    sel (N, k) int32: the experts each token selected, over all E. Returns
      pos     (N * k,)  the pair's sorted position; ``padded`` if not held
      order   (padded,) the pair at each sorted position (held ones first)
      sizes, offsets (count,) each held expert's pairs and where they start
      total, max_rows: int32 scalars."""
    local = sel.reshape(-1) - first
    held = jnp.logical_and(local >= 0, local < count)
    bucket = jnp.where(held, local, count)                 # count: not held
    onehot = bucket[:, None] == jnp.arange(count, dtype=bucket.dtype)[None]
    rank = jnp.cumsum(onehot.astype(jnp.int32), axis=0) - 1
    sizes = jnp.sum(onehot.astype(jnp.int32), axis=0)
    offsets = jnp.cumsum(sizes) - sizes
    pos = jnp.sum(jnp.where(onehot, rank + offsets[None], 0), axis=1)
    # A stable sort by bucket lists the held pairs by expert, in the order
    # the ranks above count them: position r holds pair order[r].
    order = jnp.argsort(bucket, stable=True)[:padded].astype(jnp.int32)
    return {"pos": jnp.where(held, pos, padded).astype(jnp.int32),
            "order": jnp.pad(order, (0, padded - order.shape[0])),
            "sizes": sizes, "offsets": offsets,
            "total": jnp.sum(sizes), "max_rows": jnp.max(sizes)}


def chunk_plan(pairs: dict, c, rows: int, k: int) -> dict:
    """The sorted positions [c * rows, (c + 1) * rows) as a buffer of
    ``rows`` rows:
      group_sizes (count,) rows of each held expert in the chunk
      row_pair    (rows,)  the (token, slot) pair of each row, flattened
      row_token   (rows,)  its token (whatever past the rows held)
      row_valid   (rows,)  whether the row holds a pair
      dest        (N, k)   the row of each pair; ``rows`` (out of bounds)
                           for a pair that is not in the chunk."""
    lo = c * rows
    start, sizes = pairs["offsets"], pairs["sizes"]
    row_pair = lax.dynamic_slice_in_dim(pairs["order"], lo, rows)
    local = pairs["pos"] - lo
    return {
        "group_sizes": jnp.clip(jnp.minimum(start + sizes, lo + rows)
                                - jnp.maximum(start, lo), 0, None),
        "row_pair": row_pair, "row_token": row_pair // k,
        "row_valid": lo + jnp.arange(rows) < pairs["total"],
        "dest": jnp.where(jnp.logical_and(local >= 0, local < rows), local,
                          rows).reshape(-1, k),
    }


def resolve_row_mover(impl: str, n_tokens: int, d: int) -> str:
    """What moves rows back to their tokens (combine, and dispatch's
    backward) at these shapes. It follows the grouped matmul's resolved
    ``impl`` and what the kernel can walk, as
    ``ops.attention.resolve_gqa_impl`` does: 'pallas' under 'megablox',
    'pallas_interpret' under 'megablox_interpret', where a row is whole
    128-lane tiles and the tokens whole programs; 'xla' (k gathers a token)
    under 'ragged_dot' and everywhere else: a trainer's 8-token init batch
    is what does not."""
    impl = resolve_gmm_impl(impl)
    if impl == "ragged_dot" or d % LANES or n_tokens % MOVE_STEP:
        return "xla"
    return "pallas" if impl == "megablox" else "pallas_interpret"


def _rows_of_pairs(y: jax.Array, dest: jax.Array, w=None) -> jax.Array:
    """sum_j w[:, j] * y[dest[:, j]] in float32 (w None: ones), the 'xla'
    mover: one gather of (N, d) a slot, rows out of bounds reading 0: XLA
    visits all N * k indices, held or not (0.6 ms a slot at N = 16,384,
    d = 2048 on a v5e). Unrolled, not scanned: XLA then sums the k gathers
    without a float32 (N, d) round trip between them (PERF.md §6, PR 29)."""
    acc = 0.0
    for j in range(dest.shape[1]):
        got = y.at[dest[:, j]].get(mode="fill", fill_value=0)
        got = got.astype(jnp.float32)
        acc = acc + (got if w is None else got * w[:, j, None])
    return acc


def _held_ranks(dest: jax.Array, rows: int):
    """Per slot j, over the tokens padded to whole SMEM_BLOCKs: the slot's
    row, whether it holds a pair (``dest < rows``), and how many held slots
    the token has before it; then the tokens' counts of held slots."""
    N, k = dest.shape
    cols = [jnp.pad(dest[:, j], (0, -N % SMEM_BLOCK), constant_values=rows)
            for j in range(k)]
    held = [c < rows for c in cols]
    rank, cnt = [], jnp.zeros(cols[0].shape, jnp.int32)
    for h in held:
        rank.append(cnt)
        cnt = cnt + h.astype(jnp.int32)
    return cols, held, rank, cnt


def _by_rank(cols, held, rank):
    """k columns by slot -> by rank among the token's held slots, as the
    kernels read them from scalar memory: (k * Np,), a block of SMEM_BLOCK
    tokens laid out rank-major, rank r of token t at r * SMEM_BLOCK + t."""
    k = len(cols)
    out = []
    for r in range(k):              # slot j can only be a rank <= j
        v = jnp.zeros_like(cols[0])
        for j in range(r, k):
            v = jnp.where(jnp.logical_and(held[j], rank[j] == r), cols[j], v)
        out.append(v)
    return jnp.stack(out).reshape(k, -1, SMEM_BLOCK).transpose(
        1, 0, 2).reshape(-1)


def _by_slot(got, held, rank, n_tokens: int):
    """_by_rank's way back: (k * Np,) by rank -> (N, k) by slot, 0 in the
    slots that hold no pair (whatever the kernel left there)."""
    k = len(held)
    got = got.reshape(-1, k, SMEM_BLOCK)            # (block, rank, token)
    out = []
    for j in range(k):
        v = jnp.zeros(held[0].shape, got.dtype)
        for r in range(j + 1):
            v = jnp.where(jnp.logical_and(held[j], rank[j] == r),
                          got[:, r].reshape(-1), v)
        out.append(v[:n_tokens])
    return jnp.stack(out, axis=1)


def _rows_to_tokens_kernel(cnt_ref, src_ref, *refs, k: int, weighted: bool):
    """One program: MOVE_STEP tokens of out_ref (step, c, 128), MOVE_TOKENS
    at a time. Of a set of tokens, first every held slot's row is asked for
    (one asynchronous copy of a whole (c, 128) tile from y_ref in HBM into
    ``buf``, in (token, slot) order, with the token and the weight noted in
    scalar memory); the set before it is then waited for and summed: float32
    in ``acc``, the rows of a token in slot order, cast on the way out."""
    if weighted:
        w_ref, y_ref, out_ref, buf, acc, token_of, weight_of, sem = refs
    else:
        y_ref, out_ref, buf, acc, token_of, sem = refs
    step, tokens = out_ref.shape[0], acc.shape[0]
    room = tokens * k                       # a set's rows, at the bound
    first = (pl.program_id(0) % (SMEM_BLOCK // step)) * step

    def ask(s, half):
        def token(i, n):
            t = first + s * tokens + i

            def slot(r, n):
                at = half * room + n
                pltpu.make_async_copy(y_ref.at[src_ref[r * SMEM_BLOCK + t]],
                                      buf.at[at], sem.at[half]).start()
                token_of[at] = i
                if weighted:
                    weight_of[at] = w_ref[r * SMEM_BLOCK + t]
                return n + 1

            return lax.fori_loop(0, cnt_ref[t], slot, n)

        return lax.fori_loop(0, tokens, token, 0)

    def add(s, half, n):
        def wait(times):      # every copy moves one row: n waits of that size
            def body(_, carry):
                for _ in range(times):
                    pltpu.make_async_copy(y_ref.at[0], buf.at[0],
                                          sem.at[half]).wait()
                return carry
            return body

        lax.fori_loop(0, n // 8, wait(8), 0)
        lax.fori_loop(0, n % 8, wait(1), 0)
        acc[...] = jnp.zeros(acc.shape, jnp.float32)

        def row(p, carry):
            at = half * room + p
            got = buf[at].astype(jnp.float32)
            i = token_of[at]
            acc[i] = acc[i] + (got * weight_of[at] if weighted else got)
            return carry

        lax.fori_loop(0, n, row, 0)
        out_ref[pl.ds(s * tokens, tokens)] = acc[...].astype(out_ref.dtype)

    n = ask(0, 0)
    for s in range(step // tokens):
        ahead = ask(s + 1, (s + 1) % 2) if s + 1 < step // tokens else None
        add(s, s % 2, n)
        n = ahead


@functools.partial(jax.jit, static_argnames=("out_dtype", "interpret"))
def _pallas_rows_to_tokens(y, dest, w=None, *, out_dtype,
                           interpret: bool = False):
    """sum_j w[:, j] * y[dest[:, j]] (w None: ones) as ``out_dtype`` (N, d),
    float32 inside: _rows_of_pairs's sum with only the rows that hold a pair
    moved. An unheld slot adds nothing where _rows_of_pairs adds an exact 0,
    and a token's held slots are added in slot order: the same float32 sum,
    bit for bit. Jitted like the kernel calls of ops/attention.py: one trace
    and one lowering a (shape, weighted or not) variant, whatever the number
    of layers."""
    rows, d = y.shape
    N, k = dest.shape
    if d % LANES or N % MOVE_STEP:
        raise ValueError(
            f"the row mover needs y (rows, d) with d % {LANES} == 0 and "
            f"dest (N, k) with N % {MOVE_STEP} == 0; got y {y.shape}, "
            f"dest {dest.shape}")
    cols, held, rank, cnt = _held_ranks(dest, rows)
    plan = [cnt, _by_rank(cols, held, rank)]
    if w is not None:
        plan.append(_by_rank([jnp.pad(w[:, j].astype(jnp.float32),
                                      (0, cnt.shape[0] - N))
                              for j in range(k)], held, rank))
    share = SMEM_BLOCK // MOVE_STEP         # programs to a block of the plan
    scalars = [pl.BlockSpec((SMEM_BLOCK * per,), lambda i: (i // share,),
                            memory_space=pltpu.SMEM)
               for per in (1,) + (k,) * (len(plan) - 1)]
    c, room = d // LANES, 2 * MOVE_TOKENS * k
    call = pl.pallas_call(
        functools.partial(_rows_to_tokens_kernel, k=k, weighted=w is not None),
        grid=(N // MOVE_STEP,),
        in_specs=scalars + [pl.BlockSpec(memory_space=pl.ANY)],
        out_specs=pl.BlockSpec((MOVE_STEP, c, LANES), lambda i: (i, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((N, c, LANES), out_dtype),
        scratch_shapes=(
            [pltpu.VMEM((room, c, LANES), y.dtype),
             pltpu.VMEM((MOVE_TOKENS, c, LANES), jnp.float32),
             pltpu.SMEM((room,), jnp.int32)]
            + ([] if w is None else [pltpu.SMEM((room,), jnp.float32)])
            + [pltpu.SemaphoreType.DMA((2,))]),
        compiler_params=None if interpret else pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=64 * 1024 * 1024),
        interpret=interpret,
    )
    # A row as (d / 128, 128): whole tiles, so a row is one contiguous copy.
    with jax.named_scope(MOVE_SCOPE):
        out = call(*plan, y.reshape(rows, c, LANES))
    return out.reshape(N, d)


def _pairs_to_tokens(y, dest, w, out_dtype, mover: str) -> jax.Array:
    """sum_j w[:, j] * y[dest[:, j]] (w None: ones), summed in float32 and
    returned as ``out_dtype`` (N, d), by the resolved ``mover``."""
    if mover == "xla":
        return _rows_of_pairs(y, dest, w).astype(out_dtype)
    return _pallas_rows_to_tokens(y, dest, w, out_dtype=out_dtype,
                                  interpret=mover == "pallas_interpret")


def _slot_scalars_kernel(val_ref, cnt_ref, src_ref, out_ref):
    """out[r, t] = val[src[r, t]] for the held ranks r of a block's tokens;
    what no held slot writes is left as it was."""
    def token(t, carry):
        def slot(r, carry):
            out_ref[r * SMEM_BLOCK + t] = val_ref[src_ref[r * SMEM_BLOCK + t]]
            return carry

        return lax.fori_loop(0, cnt_ref[t], slot, carry)

    lax.fori_loop(0, SMEM_BLOCK, token, 0)


@functools.partial(jax.jit, static_argnames=("interpret",))
def _pallas_row_scalars_to_pairs(val, dest, *, interpret: bool = False):
    """val[dest] (N, k), 0 where ``dest`` is out of bounds: one number a row
    back to its (token, slot) pair, ``val.at[dest].get(mode='fill')`` with
    only the held pairs read. All in scalar memory: ``val`` (rows,) whole,
    the plan and the result a block of tokens at a time."""
    N, k = dest.shape
    cols, held, rank, cnt = _held_ranks(dest, val.shape[0])
    blocks = [pl.BlockSpec((SMEM_BLOCK * per,), lambda i, val: (i,),
                           memory_space=pltpu.SMEM) for per in (1, k, k)]
    call = pl.pallas_call(
        _slot_scalars_kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1, grid=(cnt.shape[0] // SMEM_BLOCK,),
            in_specs=blocks[:2], out_specs=blocks[2]),
        out_shape=jax.ShapeDtypeStruct((k * cnt.shape[0],), val.dtype),
        compiler_params=None if interpret else pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=interpret,
    )
    with jax.named_scope(MOVE_SCOPE):
        got = call(val, cnt, _by_rank(cols, held, rank))
    return _by_slot(got, held, rank, N)


@functools.partial(jax.custom_vjp, nondiff_argnums=(2,))
def dispatch(x: jax.Array, plan: dict, mover: str = "xla") -> jax.Array:
    """x (N, d) -> the sorted buffer (rows, d): row r is x[row_token[r]],
    0 past the rows held. One XLA gather of ``rows`` rows, whatever the
    mover (0.65 ms; a kernel copying the ~half that hold a pair took 0.69:
    PERF.md §6, PR 32); ``mover`` is the backward's."""
    with jax.named_scope("route_dispatch"):
        return jnp.where(plan["row_valid"][:, None], x[plan["row_token"]], 0)


def _dispatch_fwd(x, plan, mover):
    return dispatch(x, plan, mover), plan


def _dispatch_bwd(mover, plan, dxs):  # the buffer has x's dtype
    with jax.named_scope("route_dispatch"):
        return (_pairs_to_tokens(dxs, plan["dest"], None, dxs.dtype, mover),
                None)


dispatch.defvjp(_dispatch_fwd, _dispatch_bwd)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3,))
def combine(y: jax.Array, w: jax.Array, plan: dict,
            mover: str = "xla") -> jax.Array:
    """out[n] = sum_j w[n, j] * y[dest[n, j]], float32 (N, d): the weighted
    sum of what a token's HELD experts gave. y (rows, d), w (N, k) f32."""
    with jax.named_scope("route_combine"):
        return _pairs_to_tokens(y, plan["dest"], w, jnp.float32, mover)


def _combine_fwd(y, w, plan, mover):
    return combine(y, w, plan, mover), (y, w, plan)


def _combine_bwd(mover, res, dout):
    # The rows from tokens are XLA gathers of ``rows`` indices under every
    # mover; dw's way back to (N, k), N * k indices, is the mover's.
    y, w, plan = res
    with jax.named_scope("route_combine"):
        rows = jnp.where(plan["row_valid"][:, None],
                         dout[plan["row_token"]], 0)      # (rows, d) f32
        w_row = jnp.where(plan["row_valid"],
                          w.reshape(-1)[plan["row_pair"]], 0)
        dy = (rows * w_row[:, None]).astype(y.dtype)
        dw_row = jnp.sum(rows * y.astype(jnp.float32), axis=1)
        if mover == "xla" or 4 * dw_row.shape[0] > SCALAR_BYTES:
            dw = dw_row.at[plan["dest"]].get(mode="fill", fill_value=0)
        else:
            dw = _pallas_row_scalars_to_pairs(
                dw_row, plan["dest"], interpret=mover == "pallas_interpret")
        return dy, dw.astype(w.dtype), None


combine.defvjp(_combine_fwd, _combine_bwd)


def _padded(size: int, tile: int) -> int:
    """``size`` in whole tiles of ``tile`` (no tile larger than ``size``)."""
    tile = min(tile, size)
    return -(-size // tile) * tile


def gmm_tiling(rows: int, K: int, N: int) -> tuple[int, int, int]:
    """megablox's (m, k, n) tiles for ``rows`` x (K, N) products: ONE tuple
    serves a product's forward, its dgrad (the same kernel with K and N
    exchanged) and its wgrad. ONE rule on the shapes, for every family: a
    tile of 1024 across an expert's widths unless a tile of 512 pads one of
    them less; then 512 across the output with the whole contraction in one
    step; no tile larger than its dimension. Measured on a v5e, three
    products forward and nine with the backward (PERF.md §6, PRs 29, 33, 35):

      * 2048 x 1024 (16,384 rows, 16 groups): 1024 fits whole, MEGABLOX_TILING
        the best of those tried;
      * 2048 x 1792 (16,384 rows, 8 groups): 1792 pads to 2048 under either
        tile, and the larger wins: 7.29 ms against 8.78 for tiles of 512 and
        12.77 for 256, the only size dividing both widths;
      * 2048 x 1408 (12,288 rows, 8 groups): a tile of 1024 leaves a ragged
        384 (2048 computed for 1408) where 512 pads to 1536:
        MEGABLOX_TILING_NARROW 5.41 ms against 6.27 (rows spread evenly) and
        6.61 against 7.78 (skewed), (512, 512, 512) 5.86 / 6.92; a
        contraction of 2048 in one step with 1024 across the output does not
        fit VMEM."""
    narrow = any(_padded(s, 512) < _padded(s, 1024) for s in (K, N))
    tiling = MEGABLOX_TILING_NARROW if narrow else MEGABLOX_TILING
    return tuple(min(t, s) for t, s in zip(tiling, (rows, K, N)))


def grouped_matmul(lhs: jax.Array, rhs: jax.Array, group_sizes: jax.Array,
                   *, impl: str = "auto") -> jax.Array:
    """lhs (rows, K) sorted by group, rhs (groups, K, N): row r of group g
    times rhs[g], in lhs's dtype with float32 accumulation. Rows past
    sum(group_sizes) come back 0. Differentiable in lhs and rhs (dgrad: the
    same product against rhs transposed; wgrad: per group, lhs^T @ dout)."""
    impl = resolve_gmm_impl(impl)
    covered = (jnp.arange(lhs.shape[0]) < jnp.sum(group_sizes))[:, None]
    if impl == "ragged_dot":
        out = lax.ragged_dot(lhs, rhs, group_sizes.astype(jnp.int32),
                             preferred_element_type=jnp.float32
                             ).astype(lhs.dtype)
    else:
        # Its custom calls are named after its own jitted functions:
        # %gmm.N (forward, dgrad) and %tgmm.N (wgrad) in a device trace.
        from jax.experimental.pallas.ops.tpu.megablox import ops

        tiling = gmm_tiling(lhs.shape[0], lhs.shape[1], rhs.shape[2])
        out = ops.gmm(lhs, rhs, group_sizes.astype(jnp.int32),
                      lhs.dtype, tiling, None, None, False,
                      impl == "megablox_interpret")
    # The kernels leave rows no group covers unwritten; their cotangent
    # is masked by the same select.
    return jnp.where(covered, out, 0)


def expert_ffn(xs: jax.Array, w_gate: jax.Array, w_up: jax.Array,
               w_down: jax.Array, group_sizes: jax.Array, *,
               impl: str = "auto") -> jax.Array:
    """SwiGLU of each row by its group's expert: (silu(xs Wg) * (xs Wu)) Wd.
    xs (rows, d) and the weights (count, d, F) / (count, F, d) in the
    compute type."""
    gmm = functools.partial(grouped_matmul, group_sizes=group_sizes,
                            impl=impl)
    with jax.named_scope("moe_experts"):  # a part of its own in obs.opscopes
        g = gmm(xs, w_gate).astype(jnp.float32)
        u = gmm(xs, w_up).astype(jnp.float32)
        return gmm((jax.nn.silu(g) * u).astype(xs.dtype), w_down)


def _walk(x, sel, first, count, n_experts, factor):
    N, k = sel.shape
    rows, chunks = chunk_rows(N, k, n_experts, count, factor)
    with jax.named_scope("route_plan"):
        return rows, chunks, plan_pairs(sel, first, count, rows * chunks)


def _chunk_out(x, w, w_gate, w_up, w_down, plan, impl):
    mover = resolve_row_mover(impl, *x.shape)
    ys = expert_ffn(dispatch(x, plan, mover), w_gate, w_up, w_down,
                    plan["group_sizes"], impl=impl)
    return combine(ys, w, plan, mover)


@functools.partial(jax.custom_vjp, nondiff_argnums=(6, 7, 8, 9, 10))
def routed_experts(x: jax.Array, sel: jax.Array, w: jax.Array, w_gate, w_up,
                   w_down, first: int, count: int, n_experts: int,
                   factor: float = ROWS_FACTOR, impl: str = "auto"):
    """sum_j w[n, j] * Expert_{sel[n, j]}(x[n]) over the experts held,
    float32 (N, d), and the layer's counters.

    x (N, d) in the compute type, sel (N, k) int32 over all ``n_experts``,
    w (N, k) float32, the held experts' matrices (count, ...) in the
    compute type. Walks the sorted pairs a chunk at a time (see the
    module's docstring); returns (out, stats) with stats int32 (3,): pairs
    held, the fullest held expert's, pairs no chunk covered (0).

    Chunk 0 runs outside the scan and under no ``cond``: with no held pair
    at all (``total == 0``) its rows are all invalid and its groups empty,
    so it gives zeros and covers 0 pairs, which is what makes it safe to run
    unasked. Its output and its count of covered pairs are the carry the
    scan over chunks 1 .. starts from: no float32 zeros, and no ``acc + out``
    unless a second chunk holds pairs. Where one chunk covers the bound
    (``chunks == 1``: half of the experts held, or more) there is no scan.

    Its own VJP: the backward walks the chunks again, recomputing each
    chunk's forward before its gradients, so that one chunk's activations
    are alive at a time however many chunks run (a scan's own transpose
    keeps every iteration's: 19 GB at the Trinity-Mini cell). Chunk 0's
    gradients (``jax.vjp`` of its forward: dx and the expert matrices' in
    the compute type, dw float32) are the layer's where no second chunk
    holds pairs; otherwise ONE ``lax.cond`` runs the rest of the walk:
    float32 sums that start from chunk 0's gradients, over chunks 1 .. in
    order, cast back to the operands' types. The residuals are the inputs
    alone, so under a rematerialised block whose policy saves this
    function's output the replayed forward is dead code: the experts run
    twice a step (forward, backward's recompute), as any rematerialised
    layer does."""
    k = sel.shape[1]
    rows, chunks, pairs = _walk(x, sel, first, count, n_experts, factor)

    def run(c):
        with jax.named_scope("route_plan"):
            plan = chunk_plan(pairs, c, rows, k)
        return (_chunk_out(x, w, w_gate, w_up, w_down, plan, impl),
                jnp.sum(plan["group_sizes"]))

    def chunk(carry, c):
        def add(carry):
            out, covered = run(c)
            return carry[0] + out, carry[1] + covered

        return lax.cond(pairs["total"] > c * rows, add, lambda held: held,
                        carry), None

    # The walk's own work (the sum over chunks, the counters) is the stage
    # round it; what a chunk's plan, rows and experts cost names its own.
    with jax.named_scope("route_accumulate"):
        carry = run(0)
        if chunks > 1:
            carry, _ = lax.scan(chunk, carry, jnp.arange(1, chunks))
        out, covered = carry
        stats = jnp.stack([pairs["total"], pairs["max_rows"],
                           pairs["total"] - covered]).astype(jnp.int32)
    return out, stats


def _routed_fwd(x, sel, w, w_gate, w_up, w_down, first, count, n_experts,
                factor, impl):
    out = routed_experts(x, sel, w, w_gate, w_up, w_down, first, count,
                         n_experts, factor, impl)
    return out, (x, sel, w, w_gate, w_up, w_down)


def _routed_bwd(first, count, n_experts, factor, impl, res, cts):
    x, sel, w, w_gate, w_up, w_down = res
    d_out, _ = cts                          # the counters carry no gradient
    k = sel.shape[1]
    rows, chunks, pairs = _walk(x, sel, first, count, n_experts, factor)
    operands = (x, w, w_gate, w_up, w_down)
    # The float32 sums over chunks (where a second chunk holds pairs), by
    # whose they are (obs.opscopes' stages): the tokens' and the weights' are
    # the walk's own, the expert matrices' their casts' way back.
    stages = ("route_accumulate",) * 2 + ("route_weights",) * 3

    def staged(fn, *trees):
        out = []
        for stage, *leaves in zip(stages, *trees):
            with jax.named_scope(stage):
                out.append(fn(*leaves))
        return tuple(out)

    def grads_of(c):
        with jax.named_scope("route_plan"):
            plan = chunk_plan(pairs, c, rows, k)
        _, vjp = jax.vjp(lambda *a: _chunk_out(*a, plan, impl), *operands)
        return vjp(d_out)

    def rest(grads):
        def chunk(sums, c):
            def add(sums):
                return staged(lambda g, d: g + d.astype(jnp.float32),
                              sums, grads_of(c))

            return lax.cond(pairs["total"] > c * rows, add, lambda g: g,
                            sums), None

        sums = staged(lambda d: d.astype(jnp.float32), grads)
        sums, _ = lax.scan(chunk, sums, jnp.arange(1, chunks))
        return staged(lambda g, d: g.astype(d.dtype), sums, grads)

    with jax.named_scope("route_accumulate"):   # the walk is no expert's
        grads = grads_of(0)
        if chunks > 1:
            # The barrier keeps what reads the gradients (their casts to
            # float32, the optimizer's norm) out of the cond: XLA otherwise
            # sinks it into both branches, and the branch that hands chunk
            # 0's gradients through writes every one out again as float32.
            grads = lax.optimization_barrier(lax.cond(
                pairs["total"] > rows, rest, lambda g: g, grads))
    dx, dw, dg, du, dd = grads
    return dx, None, dw, dg, du, dd


routed_experts.defvjp(_routed_fwd, _routed_bwd)
