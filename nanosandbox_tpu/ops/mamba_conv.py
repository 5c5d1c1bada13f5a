"""The causal convolution of a Mamba-2 layer (models/granite.py) between its
input projection and the scan: depth-wise taps over the xBC columns of the
projection's output, a bias and silu, split into the scan's x, B and C.

    xBC = proj[..., start:start + width]          width = inner + 2 GN
    pre[t] = bias + sum_j w[:, j] * xBC[t - (L-1) + j]   xBC = 0 before t = 0
    x | B | C = silu(pre)                         inner | GN | GN lanes

proj (b, T, ·) in the compute type as ``in_proj`` leaves it (z, xBC and dt
side by side), w (width, L), bias (width,); x, B and C float32, the taps,
bias and silu in float32. Everything here runs under the named scope
``mamba_conv``, whatever implements it: a device trace finds its ops by that
name (the kernels' custom calls are ``%mamba_conv.N``). The scope names no
part of its own (obs/opscopes.py), so its ops are filed under the module
``mamba`` around it.

``impl`` (``resolve_mamba_conv_impl``, from the model's attention impl and
the shapes, as ``ops.short_conv.resolve_conv_impl`` follows it):

  * 'xla': xBC sliced out, ``short_conv.causal_taps`` in float32, bias,
    silu, and the split: what the CPU, an init batch and odd shapes run,
    and the tests' reference for the kernels. At the granite cell's shape
    XLA writes about 5.9 GB a layer-step of xBC-wide float32 copies (the
    cast, the padded shifts, silu's, the split) against 0.71 GB required.
  * 'pallas' / 'pallas_interpret': ONE pass each way, under a custom VJP.
    The grid is (batch, row block, channel block) with channel blocks of
    2 GN lanes (one holds B and C): a program reads its block of xBC where
    ``in_proj`` left it, with the 16 rows before it as a halo, and writes
    x's block or B's and C's; no xBC-wide float32 array and no split copy.
    The backward reads xBC with both halos and the cotangent of x or of B
    and C with the 16 rows after, recomputes the pre-activation in VMEM,
    and writes dxBC in proj's type and one (8, 2 GN) float32 partial a
    program (rows 0..L-1 the taps' gradient, row L the bias's), which XLA
    adds up. Residuals: proj, w and bias.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from nanosandbox_tpu.ops.attention import resolve_attention_impl
from nanosandbox_tpu.ops.short_conv import HALO, LANES, causal_taps

__all__ = ["mamba_conv", "resolve_mamba_conv_impl", "MAMBA_CONV_SCOPE",
           "MAMBA_CONV_IMPLS"]

MAMBA_CONV_SCOPE = "mamba_conv"     # names the custom calls: %mamba_conv.N
MAMBA_CONV_IMPLS = ("pallas", "pallas_interpret", "xla")
BLOCK_ROWS = (1024, 512, 256)       # a program's rows: the largest dividing T
MAX_BLOCK = 512                     # the widest channel block (2 GN lanes)


def _tiles(T: int, start: int, inner: int, width: int, taps: int) -> bool:
    """T is whole row blocks, a channel block of B's and C's 2 GN lanes is
    whole 128-lane tiles (at most MAX_BLOCK) that also tile x and the
    columns before xBC, and the taps' gradients and the bias's fit one
    8-row tile."""
    block = width - inner
    return not (T % BLOCK_ROWS[-1] or block % LANES or block > MAX_BLOCK
                or inner % block or start % block or not 1 <= taps < 8)


def resolve_mamba_conv_impl(attention_impl: str, T: int, start: int,
                            inner: int, width: int, taps: int) -> str:
    """What computes the conv at these shapes. It follows the model's
    ``attention_impl`` as ``ops.short_conv.resolve_conv_impl`` does: the
    kernels where that resolves to a Pallas impl ('auto' on a tpu backend)
    and the shapes tile (``_tiles``); 'xla' everywhere else."""
    impl = resolve_attention_impl(attention_impl)
    if (impl not in ("pallas", "pallas_interpret")
            or not _tiles(T, start, inner, width, taps)):
        return "xla"
    return impl


def _xla_mamba_conv(proj, w, bias, start: int, inner: int):
    width = w.shape[0]
    xbc = proj[..., start:start + width].astype(jnp.float32)
    xbc = jax.nn.silu(causal_taps(xbc, w.astype(jnp.float32))
                      + bias.astype(jnp.float32))
    return tuple(jnp.split(xbc, [inner, inner + (width - inner) // 2],
                           axis=-1))


# -- the kernels ----------------------------------------------------------------

def _moved(ext, back: int):
    """Rows HALO.. of ext moved ``back`` rows on: row t holds
    ext[HALO + t - back], the first rows from the halo before them. As
    ``short_conv._earlier``, but on the halo and block joined once: the
    backward takes the taps' gradients from the same join."""
    return pltpu.roll(ext, back, 0)[HALO:]


def _pre(u, u_before, w_ref, b_ref, L: int):
    """bias + the taps over u (rows, c) float32, u_before the HALO rows
    before it: tap L-1 on the current row first, as ``causal_taps``."""
    ext = jnp.concatenate([u_before, u], axis=0)
    pre = u * w_ref[L - 1:L]
    for back in range(1, L):
        pre = pre + _moved(ext, back) * w_ref[L - 1 - back:L - back]
    return pre + b_ref[...], ext


def _fwd_kernel(xbc_ref, before_ref, w_ref, b_ref, x_ref, bo_ref, co_ref, *,
                L: int, nx: int):
    """xbc_ref (1, rows, c) the block's xBC columns; before_ref (1, HALO, c)
    the rows that end where the block starts (the block's own for the
    first: masked); w_ref (L, c), b_ref (1, c) float32; x_ref (1, rows, c);
    bo_ref / co_ref (1, rows, c / 2), written by the last channel block.
    An output block a program does not write keeps what the one before it
    wrote: the channel axis is sequential."""
    first, j = pl.program_id(1) == 0, pl.program_id(2)
    u = xbc_ref[0].astype(jnp.float32)
    u_before = jnp.where(first, 0.0, before_ref[0].astype(jnp.float32))
    out = jax.nn.silu(_pre(u, u_before, w_ref, b_ref, L)[0])

    @pl.when(j < nx)
    def _():
        x_ref[0] = out

    @pl.when(j == nx)
    def _():
        half = out.shape[1] // 2
        bo_ref[0] = out[:, :half]
        co_ref[0] = out[:, half:]


def _bwd_kernel(xbc_ref, before_ref, after_ref, dx_ref, dx_after_ref, db_ref,
                db_after_ref, dc_ref, dc_after_ref, w_ref, b_ref, dxbc_ref,
                dwb_ref, *, L: int, nx: int):
    """As the forward, and: after_ref (1, HALO, c) the rows of xBC that
    start where the block ends (masked for the last block); dx_ref
    (1, rows, c) / dx_after_ref (1, HALO, c) the cotangent of x, db_* /
    dc_* (.., c / 2) those of B and C, read by the last channel block;
    dxbc_ref (1, rows, c); dwb_ref (1, 1, 8, c) float32: row j < L this
    program's sum of g[t] * xBC[t - (L-1) + j], row L its sum of g, where
    g = d silu(pre); the rows after L are not written."""
    first = pl.program_id(1) == 0
    last = pl.program_id(1) == pl.num_programs(1) - 1
    j = pl.program_id(2)
    u = xbc_ref[0].astype(jnp.float32)
    u_before = jnp.where(first, 0.0, before_ref[0].astype(jnp.float32))
    u_after = after_ref[0].astype(jnp.float32)
    pre, ext = _pre(u, u_before, w_ref, b_ref, L)
    pre_after = _pre(u_after, u[-HALO:], w_ref, b_ref, L)[0]

    def d_silu(dy, p):
        # as autodiff takes x * sigmoid(x) apart, to the XLA form's rounding
        s = jax.nn.sigmoid(p)
        return dy * s + (dy * p) * (s * (1.0 - s))

    def grads(dy, dy_after):
        g = d_silu(dy, pre)
        g_after = jnp.where(last, 0.0, d_silu(dy_after, pre_after))
        rows = g.shape[0]
        g_ext = jnp.concatenate([g, g_after], axis=0)
        # the taps' terms summed in the XLA form's backward order: the
        # farthest tap first, the current row's last
        du = None
        for back in range(L - 1, 0, -1):
            tap = w_ref[L - 1 - back:L - back]
            # row t: g[t + back], the last rows from the halo after
            term = pltpu.roll(g_ext, rows + HALO - back, 0)[:rows] * tap
            du = term if du is None else du + term
            dwb_ref[0, 0, L - 1 - back:L - back] = jnp.sum(
                g * _moved(ext, back), axis=0, keepdims=True)
        term = g * w_ref[L - 1:L]
        du = term if du is None else du + term
        dwb_ref[0, 0, L - 1:L] = jnp.sum(g * u, axis=0, keepdims=True)
        dxbc_ref[0] = du.astype(dxbc_ref.dtype)
        dwb_ref[0, 0, L:L + 1] = jnp.sum(g, axis=0, keepdims=True)

    @pl.when(j < nx)
    def _():
        grads(dx_ref[0], dx_after_ref[0])

    @pl.when(j == nx)
    def _():
        grads(jnp.concatenate([db_ref[0], dc_ref[0]], axis=1),
              jnp.concatenate([db_after_ref[0], dc_after_ref[0]], axis=1))


@functools.partial(jax.jit, static_argnames=("start", "inner", "interpret"))
def _pallas_mamba_conv(proj, w, bias, cts=None, *, start: int, inner: int,
                       interpret: bool = False):
    """The forward (-> x, B, C float32) or, given the cotangents (dx, dB,
    dC), the backward (-> dxBC in proj's type, dw, dbias). Jitted like the
    other kernel calls: one trace and one lowering a (shape, pass)
    variant, whatever the number of layers."""
    b, T, _ = proj.shape
    width, L = w.shape
    block = width - inner
    if not _tiles(T, start, inner, width, L):
        raise ValueError(
            f"the Mamba conv kernels need T % {BLOCK_ROWS[-1]} == 0, B and "
            f"C together whole {LANES}-lane tiles (at most {MAX_BLOCK}) that "
            f"tile x and the start column, and L < 8; got proj {proj.shape}, "
            f"w {w.shape}, inner {inner}, start {start}")
    rows = next(r for r in BLOCK_ROWS if T % r == 0)
    nx, half, off = inner // block, block // 2, start // block
    per, halos = rows // HALO, T // HALO
    grid = (b, T // rows, nx + 1)
    before_at = lambda i: jnp.maximum(i * per - 1, 0)
    after_at = lambda i: jnp.minimum((i + 1) * per, halos - 1)
    x_at = lambda j: jnp.minimum(j, nx - 1)
    xbc = pl.BlockSpec((1, rows, block), lambda n, i, j: (n, i, off + j))
    xbc_before = pl.BlockSpec((1, HALO, block),
                              lambda n, i, j: (n, before_at(i), off + j))
    x_spec = pl.BlockSpec((1, rows, block), lambda n, i, j: (n, i, x_at(j)))
    bc_spec = pl.BlockSpec((1, rows, half), lambda n, i, j: (n, i, 0))
    per_channel = pl.BlockSpec((L, block), lambda n, i, j: (0, j))
    w_t = jnp.transpose(w.astype(jnp.float32))              # (L, width)
    b_row = bias.astype(jnp.float32)[None]                  # (1, width)
    bias_spec = pl.BlockSpec((1, block), lambda n, i, j: (0, j))
    params = lambda semantics: None if interpret else pltpu.CompilerParams(
        dimension_semantics=semantics, vmem_limit_bytes=64 * 1024 * 1024)
    if cts is None:
        call = pl.pallas_call(
            functools.partial(_fwd_kernel, L=L, nx=nx), grid=grid,
            in_specs=[xbc, xbc_before, per_channel, bias_spec],
            out_specs=[x_spec, bc_spec, bc_spec],
            out_shape=[jax.ShapeDtypeStruct((b, T, inner), jnp.float32),
                       jax.ShapeDtypeStruct((b, T, half), jnp.float32),
                       jax.ShapeDtypeStruct((b, T, half), jnp.float32)],
            compiler_params=params(("parallel", "parallel", "arbitrary")),
            interpret=interpret)
        with jax.named_scope(MAMBA_CONV_SCOPE):
            return tuple(call(proj, proj, w_t, b_row))
    xbc_after = pl.BlockSpec((1, HALO, block),
                             lambda n, i, j: (n, after_at(i), off + j))
    x_after = pl.BlockSpec((1, HALO, block),
                           lambda n, i, j: (n, after_at(i), x_at(j)))
    bc_after = pl.BlockSpec((1, HALO, half),
                            lambda n, i, j: (n, after_at(i), 0))
    call = pl.pallas_call(
        functools.partial(_bwd_kernel, L=L, nx=nx), grid=grid,
        in_specs=[xbc, xbc_before, xbc_after, x_spec, x_after, bc_spec,
                  bc_after, bc_spec, bc_after, per_channel, bias_spec],
        out_specs=[pl.BlockSpec((1, rows, block), lambda n, i, j: (n, i, j)),
                   pl.BlockSpec((1, 1, 8, block),
                                lambda n, i, j: (n, i, 0, j))],
        out_shape=[jax.ShapeDtypeStruct((b, T, width), proj.dtype),
                   jax.ShapeDtypeStruct((b, T // rows, 8, width),
                                        jnp.float32)],
        compiler_params=params(("parallel", "parallel", "parallel")),
        interpret=interpret)
    dx, dB, dC = cts
    with jax.named_scope(MAMBA_CONV_SCOPE):
        dxbc, dwb = call(proj, proj, proj, dx, dx, dB, dB, dC, dC, w_t,
                         b_row)
        dwb = dwb[:, :, :L + 1].sum(axis=(0, 1))
        return (dxbc, jnp.transpose(dwb[:L]).astype(w.dtype),
                dwb[L].astype(bias.dtype))


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5))
def _kernel_mamba_conv(proj, w, bias, start, inner, interpret):
    return _pallas_mamba_conv(proj, w, bias, start=start, inner=inner,
                              interpret=interpret)


def _fwd_rule(proj, w, bias, start, inner, interpret):
    out = _pallas_mamba_conv(proj, w, bias, start=start, inner=inner,
                             interpret=interpret)
    return out, (proj, w, bias)


def _bwd_rule(start, inner, interpret, res, cts):
    proj, w, bias = res
    dxbc, dw, dbias = _pallas_mamba_conv(*res, cts, start=start,
                                         inner=inner, interpret=interpret)
    after = proj.shape[-1] - start - dxbc.shape[-1]
    return jnp.pad(dxbc, ((0, 0), (0, 0), (start, after))), dw, dbias


_kernel_mamba_conv.defvjp(_fwd_rule, _bwd_rule)


def mamba_conv(proj: jax.Array, w: jax.Array, bias: jax.Array, *,
               start: int, inner: int, impl: str = "xla"):
    """proj (b, T, ·) with xBC at columns ``start`` .. ``start + width``,
    w (width, L), bias (width,) -> (x (b, T, inner), B, C (b, T, GN)),
    float32: silu(bias + the causal taps over xBC), split. ``impl`` is a
    resolved one (``resolve_mamba_conv_impl``)."""
    if impl not in MAMBA_CONV_IMPLS:
        raise ValueError(f"unknown Mamba conv impl {impl!r} "
                         f"(expected one of {MAMBA_CONV_IMPLS})")
    with jax.named_scope(MAMBA_CONV_SCOPE):
        if impl == "xla":
            return _xla_mamba_conv(proj, w, bias, start, inner)
        return _kernel_mamba_conv(proj, w, bias, start, inner,
                                  impl == "pallas_interpret")
