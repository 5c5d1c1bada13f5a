"""The gated short convolution: the token mixer of a ``conv`` layer
(models/lfm2.py) between its two projections.

    [Bg | Cg | x] = bcx                      thirds of the last dimension
    u = Bg * x
    c[t] = sum_j w[:, j] * u[t - (L-1) + j]  one filter of L taps a channel,
                                             u = 0 before position 0
    y = Cg * c

bcx (B, T, 3d) as the input projection leaves it (the compute type), w (d, L)
float32, y (B, T, d) in bcx's type; the gates and the taps in float32.
Channels lie in lanes and the taps shift along T (rows), so nothing crosses a
lane. Everything here runs under the named scope ``conv_mix``: whatever
implements it, a device trace finds its ops, forward and backward, by that
name (the kernels' custom calls are ``%conv_mix.N``).

``impl`` (``resolve_conv_impl``, from the model's attention impl and the
shapes, as ``ops.moe.resolve_row_mover`` follows the grouped matmul's):

  * 'xla': the plain form, L shifted multiply-adds. At (2, 8192, 3 * 2048)
    XLA takes 4.6 ms a layer for forward, replayed forward and backward
    against 0.90 ms of required traffic at the memory's peak (it keeps
    float32 copies of the thirds and the padded shifts in HBM; PERF.md §6,
    PR 33): what the CPU, an init batch and odd shapes run.
  * 'pallas' / 'pallas_interpret': ONE pass each way. A program takes a
    block of whole rows of bcx where it lies (a contiguous copy) with the
    L - 1 rows before it as a halo (a 16-row block that ends where the block
    starts; zeros before position 0), computes in float32 a chunk of
    channels at a time, and writes (rows, d). The backward reads bcx, the
    cotangent and both halos (the rows before for u, the rows after for the
    cotangent's anti-causal taps), recomputes c, and writes the whole
    (rows, 3d) gradient and one (L, d) partial sum of the filter's gradient
    a program, which XLA adds up. Residuals: bcx and w only.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from nanosandbox_tpu.ops.attention import resolve_attention_impl

__all__ = ["gated_short_conv", "causal_taps", "resolve_conv_impl",
           "CONV_SCOPE", "CONV_IMPLS"]

CONV_SCOPE = "conv_mix"     # names the custom calls: %conv_mix.N
CONV_IMPLS = ("pallas", "pallas_interpret", "xla")
LANES = 128
HALO = 16                   # rows of a halo block: a whole bfloat16 tile
BLOCK_ROWS = (512, 256)     # a program's rows: the largest that divides T
CHUNK = 512                 # channels computed at a time inside a program


def resolve_conv_impl(attention_impl: str, T: int, d: int) -> str:
    """What computes the gates and taps at these shapes. It follows the
    model's ``attention_impl`` as ``ops.moe.resolve_row_mover`` follows the
    grouped matmul's: the kernels where that resolves to a Pallas impl
    ('auto' on a tpu backend), channels are whole 128-lane tiles and T whole
    blocks; 'xla' everywhere else."""
    impl = resolve_attention_impl(attention_impl)
    if (impl not in ("pallas", "pallas_interpret")
            or d % LANES or T % BLOCK_ROWS[-1]):
        return "xla"
    return impl


def causal_taps(u: jax.Array, w: jax.Array) -> jax.Array:
    """c[:, t] = sum_j w[:, j] * u[:, t - (L-1) + j] for u (B, T, d) and
    w (d, L): a depth-wise causal convolution along T, tap L-1 on the
    current position, zeros before position 0."""
    T, L = u.shape[1], w.shape[1]
    c = u * w[:, L - 1]
    for back in range(1, min(L, T)):
        shifted = jnp.pad(u[:, :T - back], ((0, 0), (back, 0), (0, 0)))
        c = c + shifted * w[:, L - 1 - back]
    return c


def _xla_short_conv(bcx: jax.Array, w: jax.Array) -> jax.Array:
    with jax.named_scope(CONV_SCOPE):
        gate_in, gate_out, x = jnp.split(bcx.astype(jnp.float32), 3, axis=-1)
        c = causal_taps(gate_in * x, w.astype(jnp.float32))
        return (gate_out * c).astype(bcx.dtype)


# -- the kernels ----------------------------------------------------------------

def _thirds(ref, lanes, d: int):
    """(Bg, Cg, x) of ref (1, rows, 3d) at the channel chunk ``lanes``,
    float32."""
    return tuple(ref[0, :, third * d + lanes.start:third * d + lanes.stop
                     ].astype(jnp.float32) for third in range(3))


def _earlier(u, u_before, back: int):
    """u moved ``back`` rows on: row t holds u[t - back], the first rows
    the last of ``u_before`` (HALO rows)."""
    rows = u.shape[0]
    ext = jnp.concatenate([u_before, u], axis=0)
    return pltpu.roll(ext, back, 0)[HALO:HALO + rows]


def _later(g, g_after, ahead: int):
    """g moved ``ahead`` rows back: row t holds g[t + ahead], the last rows
    the first of ``g_after`` (HALO rows)."""
    rows = g.shape[0]
    ext = jnp.concatenate([g, g_after], axis=0)
    return pltpu.roll(ext, rows + HALO - ahead, 0)[:rows]


def _fwd_kernel(bcx_ref, before_ref, w_ref, y_ref, *, d: int, L: int):
    """bcx_ref (1, rows, 3d); before_ref (1, HALO, 3d): the rows that end
    where the block starts (the block itself for the first: masked);
    w_ref (L, d) float32; y_ref (1, rows, d)."""
    first = pl.program_id(1) == 0
    for lo in range(0, d, CHUNK):
        lanes = slice(lo, min(lo + CHUNK, d))
        gate_in, gate_out, x = _thirds(bcx_ref, lanes, d)
        b_in, _, b_x = _thirds(before_ref, lanes, d)
        u = gate_in * x
        u_before = jnp.where(first, 0.0, b_in * b_x)
        c = u * w_ref[L - 1:L, lanes]
        for back in range(1, L):
            c = c + _earlier(u, u_before, back) * w_ref[L - 1 - back:L - back,
                                                         lanes]
        y_ref[0, :, lanes] = (gate_out * c).astype(y_ref.dtype)


def _bwd_kernel(bcx_ref, before_ref, after_ref, dy_ref, dy_after_ref, w_ref,
                dbcx_ref, dw_ref, *, d: int, L: int):
    """As the forward, and: after_ref (1, HALO, 3d) / dy_after_ref
    (1, HALO, d), the rows that start where the block ends (masked for the
    last block); dy_ref (1, rows, d); dbcx_ref (1, rows, 3d);
    dw_ref (1, 1, 8, d) float32, rows 0..L-1 this program's sums of
    dc[t] * u[t - (L-1) + j]."""
    first = pl.program_id(1) == 0
    last = pl.program_id(1) == pl.num_programs(1) - 1
    for lo in range(0, d, CHUNK):
        lanes = slice(lo, min(lo + CHUNK, d))
        gate_in, gate_out, x = _thirds(bcx_ref, lanes, d)
        b_in, _, b_x = _thirds(before_ref, lanes, d)
        _, a_out, _ = _thirds(after_ref, lanes, d)
        dy = dy_ref[0, :, lanes].astype(jnp.float32)
        u = gate_in * x
        u_before = jnp.where(first, 0.0, b_in * b_x)
        dc = dy * gate_out
        dc_after = jnp.where(
            last, 0.0, dy_after_ref[0, :, lanes].astype(jnp.float32) * a_out)
        tap = w_ref[L - 1:L, lanes]
        c, du = u * tap, dc * tap
        dw_ref[0, 0, L - 1:L, lanes] = jnp.sum(dc * u, axis=0, keepdims=True)
        for back in range(1, L):
            tap = w_ref[L - 1 - back:L - back, lanes]
            moved = _earlier(u, u_before, back)
            c = c + moved * tap
            du = du + _later(dc, dc_after, back) * tap
            dw_ref[0, 0, L - 1 - back:L - back, lanes] = jnp.sum(
                dc * moved, axis=0, keepdims=True)
        for third, grad in enumerate((du * x, dy * c, du * gate_in)):
            dbcx_ref[0, :, third * d + lanes.start:third * d + lanes.stop] = (
                grad.astype(dbcx_ref.dtype))


@functools.partial(jax.jit, static_argnames=("interpret",))
def _pallas_short_conv(bcx, w, dy=None, *, interpret: bool = False):
    """The forward (-> y (B, T, d)) or, given the cotangent dy of y, the
    backward (-> dbcx like bcx, dw like w). Jitted like the kernel calls of
    ops/attention.py: one trace and one lowering a (shape, pass) variant,
    whatever the number of layers."""
    B, T, d3 = bcx.shape
    d, L = w.shape
    rows = next((r for r in BLOCK_ROWS if T % r == 0), None)
    if d3 != 3 * d or d % LANES or rows is None or not 1 <= L <= 8:
        raise ValueError(
            f"the short-convolution kernels need bcx (B, T, 3d), w (d, L) "
            f"with d % {LANES} == 0, T % {BLOCK_ROWS[-1]} == 0 and L <= 8; "
            f"got bcx {bcx.shape}, w {w.shape}")
    per = rows // HALO                      # halo blocks to a block of rows
    block = lambda width: pl.BlockSpec((1, rows, width), lambda b, i: (b, i, 0))
    before = lambda width: pl.BlockSpec(
        (1, HALO, width), lambda b, i: (b, jnp.maximum(i * per - 1, 0), 0))
    after = lambda width: pl.BlockSpec(
        (1, HALO, width),
        lambda b, i: (b, jnp.minimum((i + 1) * per, T // HALO - 1), 0))
    taps = pl.BlockSpec((L, d), lambda b, i: (0, 0))
    w_t = jnp.transpose(w.astype(jnp.float32))          # (L, d)
    params = None if interpret else pltpu.CompilerParams(
        dimension_semantics=("parallel", "parallel"),
        vmem_limit_bytes=96 * 1024 * 1024)
    if dy is None:
        call = pl.pallas_call(
            functools.partial(_fwd_kernel, d=d, L=L), grid=(B, T // rows),
            in_specs=[block(d3), before(d3), taps], out_specs=block(d),
            out_shape=jax.ShapeDtypeStruct((B, T, d), bcx.dtype),
            compiler_params=params, interpret=interpret)
        with jax.named_scope(CONV_SCOPE):
            return call(bcx, bcx, w_t)
    call = pl.pallas_call(
        functools.partial(_bwd_kernel, d=d, L=L), grid=(B, T // rows),
        in_specs=[block(d3), before(d3), after(d3), block(d), after(d), taps],
        out_specs=[block(d3),
                   pl.BlockSpec((1, 1, 8, d), lambda b, i: (b, i, 0, 0))],
        out_shape=[jax.ShapeDtypeStruct(bcx.shape, bcx.dtype),
                   jax.ShapeDtypeStruct((B, T // rows, 8, d), jnp.float32)],
        compiler_params=params, interpret=interpret)
    with jax.named_scope(CONV_SCOPE):
        dbcx, dw = call(bcx, bcx, bcx, dy, dy, w_t)
        dw = jnp.transpose(dw.sum(axis=(0, 1))[:L]).astype(w.dtype)
    return dbcx, dw


@functools.partial(jax.custom_vjp, nondiff_argnums=(2,))
def _kernel_short_conv(bcx, w, interpret: bool):
    return _pallas_short_conv(bcx, w, interpret=interpret)


def _conv_fwd_rule(bcx, w, interpret):
    return _pallas_short_conv(bcx, w, interpret=interpret), (bcx, w)


def _conv_bwd_rule(interpret, res, dy):
    return _pallas_short_conv(*res, dy, interpret=interpret)


_kernel_short_conv.defvjp(_conv_fwd_rule, _conv_bwd_rule)


def gated_short_conv(bcx: jax.Array, w: jax.Array,
                     impl: str = "xla") -> jax.Array:
    """bcx (B, T, 3d) = [Bg | Cg | x], w (d, L) -> Cg * taps(Bg * x),
    (B, T, d) in bcx's dtype; float32 inside. ``impl`` is a resolved one
    (resolve_conv_impl)."""
    if impl not in CONV_IMPLS:
        raise ValueError(f"unknown short-convolution impl {impl!r} "
                         f"(expected one of {CONV_IMPLS})")
    if impl == "xla":
        return _xla_short_conv(bcx, w)
    return _kernel_short_conv(bcx, w, impl == "pallas_interpret")
