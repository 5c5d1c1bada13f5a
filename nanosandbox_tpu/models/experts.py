"""What the expert families (models/afmoe.py, models/lfm2.py,
models/deepseek_v3.py) are written with and none owns: the bias-free
projection and the RMSNorm of a float32 residual stream, the q/k head norm
with its rotary positions, SwiGLU, the sigmoid router, the routed half of an
expert layer, the shared expert beside it (``shared_expert``: one SwiGLU of
a width the family gives, every token through it) and the loop over a
decoder's blocks that gathers the expert layers' counters. Beside
models/common.py because it imports ops/moe.py, which a GPT-2 run never does.

``cfg`` is the family's own model config; read here are ``compute_dtype``,
``param_dtype``, ``n_embd``, ``rms_norm_eps`` and, by ``routed_experts``,
``num_experts``, ``num_experts_per_tok``, ``experts_held``,
``moe_intermediate_size``, ``route_norm`` and ``route_scale``.
"""

from __future__ import annotations

from functools import partial
from typing import Any

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.ad_checkpoint import checkpoint_name

from nanosandbox_tpu.models.common import (_dense_init, constrain_acts,
                                           remat_block)
from nanosandbox_tpu.ops import moe
from nanosandbox_tpu.ops.attention import qk_prep, rotary_table

# What a step reports of its expert layers, one entry a layer.
STAT_NAMES = ("moe_held", "moe_max_rows", "moe_dropped")
# What a block under remat keeps: the attention kernels' output and
# logsumexp (ops/attention.py) and the routed experts' weighted sum
# (routed_experts below).
SAVED_NAMES = ("attn_out", "attn_lse", "moe_routed")


def dense(cfg: Any, features: int, name: str) -> nn.Dense:
    return nn.Dense(features, use_bias=False,
                    dtype=jnp.dtype(cfg.compute_dtype),
                    param_dtype=cfg.param_dtype, kernel_init=_dense_init(),
                    name=name)


def rms_norm(cfg: Any, name: str) -> nn.RMSNorm:
    return nn.RMSNorm(epsilon=cfg.rms_norm_eps, dtype=jnp.float32,
                      param_dtype=cfg.param_dtype, name=name)


def head_rms_norm(x: jax.Array, scale: jax.Array, eps: float) -> jax.Array:
    """RMSNorm over the last (head) dimension of x (B, T, heads, D),
    float32, one scale of D shared by the heads: the XLA path. The mean of
    squares over a head's lanes is taken as a product with the constant 1/D
    matrix at full float32 precision, which leaves it in every lane with no
    cross-lane reduce and broadcast: 3.8 against 7.6 ms for a layer's q,
    forward and backward, at (2, 8192, 32, 128) (PERF.md §6, PR 29)."""
    D = x.shape[-1]
    x = x.astype(jnp.float32)
    mean_sq = jnp.einsum("bthd,de->bthe", x * x,
                         jnp.full((D, D), 1.0 / D, jnp.float32),
                         precision=lax.Precision.HIGHEST)
    return x * lax.rsqrt(mean_sq + eps) * scale


def rotary(x: jax.Array, theta: float) -> jax.Array:
    """Rotate-half rotary positions 0..T-1 over all of the last dimension
    of x (B, T, heads, D), float32: the XLA path.

    rotate_half(x) = [-x2, x1] is taken as a product with the fixed signed
    permutation matrix that says so, at full float32 precision: the same
    numbers to float32 rounding, on the MXU. Written as a split and a
    concatenate of the 128 lanes it cost 20.0 ms a layer's q (forward and
    backward, (2, 8192, 32, 128)) against 4.6 ms (PERF.md §6, PR 29)."""
    T, D = x.shape[1], x.shape[-1]
    cos, sin = (t[None, :, None, :] for t in rotary_table(T, D, theta))
    lane = np.arange(D)
    half_turn = np.zeros((D, D), np.float32)
    half_turn[(lane + D // 2) % D, lane] = np.where(lane < D // 2, -1.0, 1.0)
    rotated = jnp.einsum("bthd,de->bthe", x, jnp.asarray(half_turn),
                         precision=lax.Precision.HIGHEST)
    return x * cos + rotated * sin


class HeadRMSNorm(nn.Module):
    """The prologue of attention for q or k as its projection leaves it,
    x (B, T, heads*D) -> the same shape and dtype for the kernels: RMSNorm
    over each head's D lanes (one leaf, ``scale`` (D,), shared by the heads),
    then rotary positions where ``theta`` is given; float32 inside.

    ``impl`` (ops.attention.resolve_gqa_impl, the predicate that picks the
    grouped-query kernels) picks the form. 'pallas' / 'pallas_interpret':
    ops.attention.qk_prep, ONE kernel over x where it lies, forward and
    backward (custom call ``%qk_prep.N``; PERF.md §6, PR 30). 'xla':
    head_rms_norm and rotary above, float32 (B, T, heads, D) arrays in HBM
    between them: what the CPU, the trainer's 8-token init batch, the
    kernel's tests and heads of 64 lanes run."""
    heads: int
    eps: float
    param_dtype: str

    @nn.compact
    def __call__(self, x: jax.Array, theta: float | None,
                 impl: str) -> jax.Array:
        B, T, HD = x.shape
        D = HD // self.heads
        scale = self.param("scale", nn.initializers.ones, (D,),
                           jnp.dtype(self.param_dtype))
        if impl != "xla":
            return qk_prep(x, scale, self.heads, self.eps, theta,
                           impl == "pallas_interpret")
        y = head_rms_norm(x.reshape(B, T, self.heads, D), scale, self.eps)
        if theta is not None:
            y = rotary(y, theta)
        return y.reshape(B, T, HD).astype(x.dtype)


class SwiGLU(nn.Module):
    cfg: Any
    width: int

    @nn.compact
    def __call__(self, m: jax.Array) -> jax.Array:
        cfg = self.cfg
        g = dense(cfg, self.width, "gate_proj")(m).astype(jnp.float32)
        u = dense(cfg, self.width, "up_proj")(m).astype(jnp.float32)
        return dense(cfg, cfg.n_embd, "down_proj")(
            (jax.nn.silu(g) * u).astype(cfg.compute_dtype))


def _hits(sel: jax.Array, num_experts: int) -> jax.Array:
    """(N, k, E) bool: slot j of token n chose expert e. One (8, 128)
    register a token at E 128 / k 8, made inside the fusion that reads it,
    never an array in HBM."""
    return sel[:, :, None] == lax.iota(sel.dtype, num_experts)


@partial(jax.custom_vjp, nondiff_argnums=(2,))
def selected_scores(s: jax.Array, sel: jax.Array,
                    num_experts: int) -> jax.Array:
    """s[n, sel[n, j]] (N, k) for scores s (N, E) and ids sel (N, k) that
    are DISTINCT in a row (``lax.top_k``'s), with neither a gather nor, in
    the backward pass, a scatter-add: those cost by their N*k INDICES, 1.04
    and 1.14 ms a call at N*k = 131,072, forward, in remat's replay and
    backward (12.9 ms of Trinity-Mini's step; PERF.md §6, PR 36).

    Forward: each (token, slot) has one hit, so the max over the experts of
    ``where(hit, s, -inf)`` is that score bit for bit. A max and not a sum:
    XLA merges a sum with the caller's sum over k into one reduce over
    (k, E), which rounds the denominator in another order (an ulp of w,
    read on the chip and on the CPU); no reduce merges with a max.
    Backward: ds[n, e] = sum_j where(hit[n, j, e], dw[n, j], 0), at most
    one term each: the scatter-add's bits. The residual is ``sel`` alone."""
    return jnp.max(jnp.where(_hits(sel, num_experts), s[:, None, :],
                             -jnp.inf), axis=-1)


def _selected_scores_fwd(s, sel, num_experts):
    return selected_scores(s, sel, num_experts), sel


def _selected_scores_bwd(num_experts, sel, dw):
    ds = jnp.sum(jnp.where(_hits(sel, num_experts), dw[:, :, None], 0.0),
                 axis=1)
    return ds, None


selected_scores.defvjp(_selected_scores_fwd, _selected_scores_bwd)


def route(x: jax.Array, w_router: jax.Array, bias: jax.Array, k: int, *,
          norm: bool, scale: float, eps: float):
    """(sel (N, k) int32, w (N, k) float32) for tokens x (N, d) float32:
    the k experts of the highest sigmoid score + bias, weighted by their
    scores alone (``lax.top_k`` ranks s + bias, so its values are not the
    weights: ``selected_scores`` reads s[sel] by a compare against the
    expert ids); ``norm``: divided by their sum + ``eps``; times
    ``scale``."""
    with jax.named_scope("route_router"):
        s = jax.nn.sigmoid(jnp.dot(x, w_router.astype(jnp.float32),
                                   precision=lax.Precision.HIGHEST))
        _, sel = lax.top_k(s + lax.stop_gradient(bias.astype(jnp.float32)),
                           k)
        w = selected_scores(s, sel, s.shape[1])
        if norm:
            w = w / (jnp.sum(w, axis=-1, keepdims=True) + eps)
        return sel.astype(jnp.int32), w * scale


def routed_experts(module: nn.Module, m: jax.Array, cfg: Any, *,
                   route_eps: float):
    """The routed half of an expert layer, its leaves (``router``,
    ``expert_bias``, ``w_gate``, ``w_up``, ``w_down``) declared on
    ``module``, the family's expert-layer module, from inside its compact
    call. m (B, T, d) float32 -> (sum_j w_j Expert_{sel_j}(m) over the
    experts held (B, T, d) float32, stats (3,) int32: STAT_NAMES), all
    under the scope ``moe_route``. ``expert_bias`` is a leaf no gradient
    reaches."""
    B, T, d = m.shape
    first, count = cfg.experts_held
    F = cfg.moe_intermediate_size
    dtype = jnp.dtype(cfg.compute_dtype)
    init, pd = _dense_init(), jnp.dtype(cfg.param_dtype)
    w_router = module.param("router", init, (d, cfg.num_experts), pd)
    bias = module.param("expert_bias", nn.initializers.zeros,
                        (cfg.num_experts,), pd)
    w_gate = module.param("w_gate", init, (count, d, F), pd)
    w_up = module.param("w_up", init, (count, d, F), pd)
    w_down = module.param("w_down", init, (count, F, d), pd)
    x = m.reshape(B * T, d)
    with jax.named_scope("moe_route"):
        # Every op of the part lies in one of six stages (obs.opscopes:
        # _STAGE): the router's here, the plan's, the rows' two ways and
        # the walk's sums in ops/moe.py, forward and backward.
        sel, w = route(x, w_router, bias, cfg.num_experts_per_tok,
                       norm=cfg.route_norm, scale=cfg.route_scale,
                       eps=route_eps)
        with jax.named_scope("route_accumulate"):
            xs = x.astype(dtype)
        with jax.named_scope("route_weights"):
            held = [a.astype(dtype) for a in (w_gate, w_up, w_down)]
        routed, stats = moe.routed_experts(xs, sel, w, *held, first, count,
                                           cfg.num_experts)
        # Saved under remat (the families' SAVED_NAMES): as large as the
        # block's output; what follows the layer needs it, and recomputing
        # it is k row gathers a token.
        with jax.named_scope("route_accumulate"):
            routed = checkpoint_name(routed, "moe_routed").reshape(B, T, d)
    return routed, stats


def walk_rows(cfg: Any, n_tokens: int) -> tuple[int, int]:
    """(rows of a chunk, chunks) of the walk over the sorted pairs that
    ``routed_experts`` makes of ``n_tokens`` tokens under ``cfg``
    (``ops.moe.chunk_rows`` of the numbers it hands the walk): what
    ``moe_held`` is read against by whoever counts the chunks run."""
    return moe.chunk_rows(n_tokens, cfg.num_experts_per_tok, cfg.num_experts,
                          cfg.experts_held[1])


def shared_expert(cfg: Any, width: int, m: jax.Array) -> jax.Array:
    """The expert every token passes through, beside the routed ones: one
    SwiGLU of ``width`` (afmoe: moe_intermediate_size; deepseek_v3:
    n_shared_experts times it), the module ``moe_shared`` of the expert-layer
    module whose compact call this is made from. m (B, T, d) float32 ->
    (B, T, d) float32. Every chip of an expert-parallel job computes it
    whole: the ranks' shares of a layer count it once."""
    return SwiGLU(cfg, width, name="moe_shared")(
        m.astype(jnp.dtype(cfg.compute_dtype))).astype(jnp.float32)


def decoder_layers(block_cls, cfg: Any, mesh: Any, h: jax.Array):
    """h (B, T, d) float32 through the family's ``cfg.n_layer`` blocks
    (``block_cls(cfg, layer, name=f"h_{layer}")(h) -> (h, stats)``, created
    under the calling module; rematerialised by ``cfg.remat`` /
    ``cfg.remat_policy`` keeping SAVED_NAMES) -> (h, {name: (expert layers,)
    int32} for STAT_NAMES: the layers past ``cfg.num_dense_layers``)."""
    if cfg.remat:
        block_cls = remat_block(block_cls, cfg.remat_policy, SAVED_NAMES,
                                static_argnums=())
    stats = []
    for i in range(cfg.n_layer):
        h, st = block_cls(cfg, i, name=f"h_{i}")(h)
        h = constrain_acts(mesh, h)
        if i >= cfg.num_dense_layers:
            stats.append(st)
    stats = (jnp.stack(stats) if stats else jnp.zeros(
        (0, len(STAT_NAMES)), jnp.int32))
    return h, {name: stats[:, n] for n, name in enumerate(STAT_NAMES)}
