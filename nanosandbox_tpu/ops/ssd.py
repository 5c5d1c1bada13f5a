"""The selective state-space scan of a Mamba-2 layer (models/granite.py), in
its chunked ("SSD", state-space dual) form.

Per head h of H (P lanes a head), each head reading the group g = h // (H/G)
of B and C (N lanes a group), from S_{-1} = 0:

    S_t = exp(dt_t A) S_{t-1} + dt_t x_t (x) B_t      S in R^{P x N}, float32
    y_t = S_t C_t + D x_t

The same map computed in blocks of ``chunk`` tokens (a chunk's positions
t, s; cs = the running sum of dt A inside the chunk):

    within a chunk   y_t += sum_{s <= t} exp(cs_t - cs_s) (C_t . B_s) dt_s x_s
    chunk states     st_c = sum_s exp(cs_last - cs_s) dt_s x_s (x) B_s
    across chunks    S_c = exp(cs_last of chunk c-1) S_{c-1} + st_{c-1},
                     written as one product with the chunks' decay matrix
                     exp(segment sums of the chunks' decays), float32
    the carried state's output   y_t += exp(cs_t) S_c C_t

All chunks are computed at once (batched products over (batch, chunk,
head)); the decay block of every chunk, (H, chunk, chunk), is one array.
The segment sums, their ``exp``s, the decays and the state are float32. The
four products (C B^T, the masked product with x, the chunk states and the
state's output) take ``dtype`` inputs with float32 accumulation; the
chunks' recurrence is a float32 product at full precision.

Everything here runs under the named scope ``ssd``, forward and backward: a
device trace finds the scan's ops by that name, whatever implements it.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax

SSD_SCOPE = "ssd"


def forward_flops_per_token(chunk: int, heads: int, head_dim: int,
                            d_state: int, groups: int) -> float:
    """The scan's REQUIRED forward products a token of one layer: inside
    its chunk the (chunk + 1) / 2 positions it sees on average, each costing
    2 * groups * d_state for C B^T and 2 * heads * head_dim for the masked
    product with x; the chunk state, 2 * heads * head_dim * d_state; the
    carried state's output, as much again. Backward is twice the forward."""
    return ((chunk + 1) / 2 * (2 * groups * d_state + 2 * heads * head_dim)
            + 4 * heads * head_dim * d_state)


def _segsum(a: jax.Array) -> jax.Array:
    """(..., n, n) from a (..., n): [t, s] = sum_{s < r <= t} a_r where
    s <= t, -inf above the diagonal. Summed as a running sum of a masked
    copy, so that no segment is a difference of two long sums."""
    n = a.shape[-1]
    rows = jnp.broadcast_to(a[..., :, None], a.shape + (n,))
    below = jnp.tril(jnp.ones((n, n), bool), -1)
    sums = jnp.cumsum(jnp.where(below, rows, 0.0), axis=-2)
    return jnp.where(jnp.tril(jnp.ones((n, n), bool)), sums, -jnp.inf)


def ssd(x: jax.Array, dt: jax.Array, A: jax.Array, B: jax.Array,
        C: jax.Array, D: jax.Array, *, chunk: int, groups: int = 1,
        dtype=jnp.bfloat16):
    """y (b, T, H*P) float32 and the scan's counters from x (b, T, H*P) as
    the input projection leaves it, dt (b, T, H) (positive: after the
    softplus), A (H,) (negative), B / C (b, T, G*N), D (H,). T is padded to
    a whole number of chunks (dt = 0, x = 0 there: nothing moves).

    The counters, float32 scalars no gradient reaches: ``ssd_decay``, the
    mean over batch rows, chunks and heads of exp(sum over a chunk of
    dt A), what the state keeps across one chunk; ``ssd_state_max``, the
    largest |S| at a chunk boundary (every chunk's end)."""
    with jax.named_scope(SSD_SCOPE):
        b, T, HP = x.shape
        H = dt.shape[-1]
        P, N, G = HP // H, B.shape[-1] // groups, groups
        r = H // G
        pad = -T % chunk
        f32 = jnp.float32
        if pad:
            grow = lambda a: jnp.pad(a, ((0, 0), (0, pad), (0, 0)))
            x, dt, B, C = grow(x), grow(dt), grow(B), grow(C)
        c = (T + pad) // chunk
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
        return y[:, :T], jax.tree.map(lax.stop_gradient, stats)
