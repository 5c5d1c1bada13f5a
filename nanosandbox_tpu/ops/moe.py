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
times the expected load, in whole tiles), ``ceil(never / rows)`` of them, under a scan whose body is a ``lax.cond``: a chunk that no held pair
reaches is not run. Memory is one chunk's; work is the chunks that hold
pairs: a layer at the expected load runs one. ``dropped`` (pairs held less
pairs covered by the chunks run) is 0 by construction and is counted all
the same: the trainer's counter and the benchmark's ``fault`` read it.

Both directions of dispatch and combine are GATHERS (custom VJPs below): a
row knows its token (``row_token``), a (token, slot) pair knows its row
(``dest``), so neither pass needs a scatter-add with repeated indices.

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

__all__ = ["chunk_rows", "plan_pairs", "chunk_plan", "dispatch", "combine",
           "grouped_matmul", "expert_ffn", "routed_experts",
           "resolve_gmm_impl", "GMM_IMPLS"]

GMM_IMPLS = ("auto", "ragged_dot", "megablox", "megablox_interpret")
# What 'auto' means on a tpu backend: measured, one expert layer's three
# products at (16,384 rows, 16 groups, 2048 x 1024) on a v5e (PERF.md §6).
TPU_GMM_IMPL = "megablox"
# Rows of the buffer come in whole tiles of the grouped matmul's m tiling.
ROW_TILE = 512
# Rows of ONE chunk of the sorted pairs, as a multiple of the expected load.
ROWS_FACTOR = 2.0
MEGABLOX_TILING = (512, 1024, 1024)


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


def _rows_of_pairs(y: jax.Array, dest: jax.Array, w=None) -> jax.Array:
    """sum_j w[:, j] * y[dest[:, j]] in float32 (w None: ones): one gather
    of (N, d) a slot, rows out of bounds reading 0. Unrolled, not scanned:
    XLA then sums the k gathers without a float32 (N, d) round trip between
    them (5.8 against 7.4 ms at N = 16,384, k = 8, d = 2048, PERF.md §6)."""
    acc = 0.0
    for j in range(dest.shape[1]):
        got = y.at[dest[:, j]].get(mode="fill", fill_value=0)
        got = got.astype(jnp.float32)
        acc = acc + (got if w is None else got * w[:, j, None])
    return acc


@jax.custom_vjp
def dispatch(x: jax.Array, plan: dict) -> jax.Array:
    """x (N, d) -> the sorted buffer (rows, d): row r is x[row_token[r]],
    0 past the rows held."""
    return jnp.where(plan["row_valid"][:, None], x[plan["row_token"]], 0)


def _dispatch_fwd(x, plan):
    return dispatch(x, plan), plan


def _dispatch_bwd(plan, dxs):  # the buffer has x's dtype
    return _rows_of_pairs(dxs, plan["dest"]).astype(dxs.dtype), None


dispatch.defvjp(_dispatch_fwd, _dispatch_bwd)


@jax.custom_vjp
def combine(y: jax.Array, w: jax.Array, plan: dict) -> jax.Array:
    """out[n] = sum_j w[n, j] * y[dest[n, j]], float32 (N, d): the weighted
    sum of what a token's HELD experts gave. y (rows, d), w (N, k) f32."""
    return _rows_of_pairs(y, plan["dest"], w)


def _combine_fwd(y, w, plan):
    return combine(y, w, plan), (y, w, plan)


def _combine_bwd(res, dout):
    y, w, plan = res
    rows = jnp.where(plan["row_valid"][:, None],
                     dout[plan["row_token"]], 0)          # (rows, d) f32
    w_row = jnp.where(plan["row_valid"],
                      w.reshape(-1)[plan["row_pair"]], 0)
    dy = (rows * w_row[:, None]).astype(y.dtype)
    dw_row = jnp.sum(rows * y.astype(jnp.float32), axis=1)
    dw = dw_row.at[plan["dest"]].get(mode="fill", fill_value=0)
    return dy, dw.astype(w.dtype), None


combine.defvjp(_combine_fwd, _combine_bwd)


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

        tiling = tuple(min(t, s) for t, s in zip(
            MEGABLOX_TILING, (lhs.shape[0], lhs.shape[1], rhs.shape[2])))
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
    return rows, chunks, plan_pairs(sel, first, count, rows * chunks)


def _chunk_out(x, w, w_gate, w_up, w_down, plan, impl):
    ys = expert_ffn(dispatch(x, plan), w_gate, w_up, w_down,
                    plan["group_sizes"], impl=impl)
    return combine(ys, w, plan)


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

    Its own VJP: the backward walks the chunks again, recomputing each
    chunk's forward before its gradients, so that one chunk's activations
    are alive at a time however many chunks run (a scan's own transpose
    keeps every iteration's: 19 GB at the Trinity-Mini cell). The
    residuals are the inputs alone, so under a rematerialised block whose
    policy saves this function's output the replayed forward is dead code:
    the experts run twice a step (forward, backward's recompute), as any
    rematerialised layer does."""
    k = sel.shape[1]
    rows, chunks, pairs = _walk(x, sel, first, count, n_experts, factor)

    def chunk(carry, c):
        def run(carry):
            acc, covered = carry
            plan = chunk_plan(pairs, c, rows, k)
            return (acc + _chunk_out(x, w, w_gate, w_up, w_down, plan, impl),
                    covered + jnp.sum(plan["group_sizes"]))

        return lax.cond(pairs["total"] > c * rows, run, lambda c: c,
                        carry), None

    init = (jnp.zeros((x.shape[0], x.shape[1]), jnp.float32),
            jnp.zeros((), jnp.int32))
    (out, covered), _ = lax.scan(chunk, init, jnp.arange(chunks))
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

    def chunk(grads, c):
        def run(grads):
            plan = chunk_plan(pairs, c, rows, k)
            _, vjp = jax.vjp(
                lambda *a: _chunk_out(*a, plan, impl), *operands)
            return jax.tree.map(lambda g, d: g + d.astype(jnp.float32),
                                grads, vjp(d_out))

        return lax.cond(pairs["total"] > c * rows, run, lambda g: g,
                        grads), None

    zeros = tuple(jnp.zeros(a.shape, jnp.float32) for a in operands)
    with jax.named_scope("moe_route"):   # the accumulation is no expert's
        grads, _ = lax.scan(chunk, zeros, jnp.arange(chunks))
    dx, dw, dg, du, dd = (g.astype(a.dtype) for g, a in zip(grads, operands))
    return dx, None, dw, dg, du, dd


routed_experts.defvjp(_routed_fwd, _routed_bwd)
