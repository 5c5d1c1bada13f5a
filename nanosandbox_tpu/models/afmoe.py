"""The ``afmoe`` decoder (Arcee Trinity; HF ``transformers`` models/afmoe)
in flax.linen, on the trainer's normal path beside models/gpt.py.

A layer, with the published config's names (d = n_embd, H = n_head query
heads, G = n_kv_head, D = head_dim, E = num_experts, k = num_experts_per_tok):

    a = RMSNorm_in(h)
    q, k, v, g = a Wq, a Wk, a Wv, a Wg          (no biases; g is (T, H D))
    q, k = RMSNorm_q(q), RMSNorm_k(k)            (over D, one scale each)
    sliding layers only: rotary positions on q, k (rotate-half, all D dims)
    o = softmax(q k^T / sqrt(D) + mask) v        (query head i reads KV head
                                                  i // (H // G); key j visible
                                                  iff j <= i, and in sliding
                                                  layers i - j < window)
    h = h + RMSNorm_post_attn((o * sigmoid(g)) Wo)
    m = RMSNorm_pre_mlp(h)
    dense layers (the first num_dense_layers): f = SwiGLU(m), intermediate_size
    expert layers: s = sigmoid(m Wr); sel = top_k(s); w = s[sel];
        w = route_scale * w / (sum w + 1e-20)
        f = Shared(m) + sum_j w_j Expert_{sel_j}(m), SwiGLUs of
        moe_intermediate_size
    h = h + RMSNorm_post_mlp(f)

Embedding scaled by sqrt(d) (mup_enabled), final RMSNorm, untied head.

The expert layer is told which experts it holds (``experts_held`` = (first,
count)): it scores and selects over all E and computes only the slots naming
a held expert, plus the shared expert (ops/moe.py). On one chip that partial
sum goes on to the next layer; nothing stands in for absent chips.

The selection bias of the published model (``expert_bias``, one number an
expert: added to the scores for the SELECTION only, never to the weights,
and moved by a load-balancing update outside the forward pass that the
config does not specify) is a leaf of the expert layer that no gradient
reaches: zeros unless a checkpoint, or the benchmark's weights, bring
values.

Precision: parameters ``param_dtype``; matmul inputs ``compute_dtype`` with
float32 accumulation; the residual stream, the norms, rotary positions,
the output gate, the router (matmul at full float32 precision, sigmoid,
top-k, weights) and the weighted sum of expert outputs in float32.

Between the projections and the attention kernels, q and k stay
(B, T, heads * D) in ``compute_dtype``. Where the grouped-query kernels run
(ops.attention.resolve_gqa_impl: a Pallas impl and whole 128-lane heads over
whole 128-row blocks), RMSNorm_q / RMSNorm_k and the rotary positions are ONE
kernel a tensor, forward and backward (ops.attention.qk_prep: float32 in
registers from the projection's output as it lies, no float32 copy of q or k
in HBM); everywhere else head_rms_norm and rotary below, in XLA.

Scopes (obs/opscopes.py): modules ``attn_sliding`` / ``attn_full``, ``mlp``,
``moe_shared``, the norms ``ln_*``, ``wte``; named scopes ``moe_route``
(router, top-k, sort, gather, combine) and, inside it, ``moe_experts`` (the
grouped matmuls and the activation between them, ops/moe.expert_ffn);
``qk_prep`` inside the attention modules (its custom calls are
``%qk_prep.N``, apart from the flash kernels' ``%attn_sliding.N`` /
``%attn_full.N``; it names no part, so its time is the module's).
The family's custom calls in a device trace: ``%attn_sliding.N`` /
``%attn_full.N``, ``%qk_prep.N``, ``%gmm.N`` / ``%tgmm.N`` (megablox) and
``%moe_rows.N`` (ops/moe.py's row mover: the routed experts' rows summed
back to their tokens, inside ``moe_route``, so its time is that part's).
"""

from __future__ import annotations

import functools
import math
from typing import Any

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.ad_checkpoint import checkpoint_name

from nanosandbox_tpu.config import AfmoeConfig
from nanosandbox_tpu.models.common import (_dense_init, constrain_acts,
                                           remat_block)
from nanosandbox_tpu.ops import moe
from nanosandbox_tpu.ops.attention import (causal_attention_gqa, qk_prep,
                                           resolve_gqa_impl, rotary_table)

# What a step reports of its expert layers, one entry a layer.
STAT_NAMES = ("moe_held", "moe_max_rows", "moe_dropped")
# What a block under remat keeps: the attention kernels' output and
# logsumexp (ops/attention.py) and the routed experts' weighted sum (Moe).
SAVED_NAMES = ("attn_out", "attn_lse", "moe_routed")


def _dense(cfg: AfmoeConfig, features: int, name: str) -> nn.Dense:
    return nn.Dense(features, use_bias=False,
                    dtype=jnp.dtype(cfg.compute_dtype),
                    param_dtype=cfg.param_dtype, kernel_init=_dense_init(),
                    name=name)


def _rms_norm(cfg: AfmoeConfig, name: str) -> nn.RMSNorm:
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
    attention kernels) picks the form. 'pallas' / 'pallas_interpret':
    ops.attention.qk_prep, ONE kernel over x where it lies, forward and
    backward (custom call ``%qk_prep.N``; PERF.md §6, PR 30). 'xla':
    head_rms_norm and rotary above, float32 (B, T, heads, D) arrays in HBM
    between them: what the CPU, the trainer's 8-token init batch and the
    kernel's tests run."""
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


class Attention(nn.Module):
    """Named ``attn_sliding`` or ``attn_full`` by its block: the name is
    the kernel's scope and the part the device trace files it under."""
    cfg: AfmoeConfig
    window: int | None

    @nn.compact
    def __call__(self, a: jax.Array) -> jax.Array:
        cfg = self.cfg
        B, T, _ = a.shape
        H, G, D = cfg.n_head, cfg.n_kv_head, cfg.head_dim
        dtype = jnp.dtype(cfg.compute_dtype)
        q = _dense(cfg, H * D, "q_proj")(a)
        k = _dense(cfg, G * D, "k_proj")(a)
        v = _dense(cfg, G * D, "v_proj")(a)
        gate = _dense(cfg, H * D, "gate_proj")(a)
        # full layers carry no positions
        theta = cfg.rope_theta if self.window is not None else None
        impl = resolve_gqa_impl(cfg.attention_impl, D, T)
        norm = functools.partial(HeadRMSNorm, eps=cfg.rms_norm_eps,
                                 param_dtype=cfg.param_dtype)
        q = norm(H, name="q_norm")(q, theta, impl)
        k = norm(G, name="k_norm")(k, theta, impl)
        o = causal_attention_gqa(q, k, v, H, G, window=self.window,
                                 impl=impl, scope=self.name)
        gated = o.astype(jnp.float32) * jax.nn.sigmoid(
            gate.astype(jnp.float32))
        return _dense(cfg, cfg.n_embd, "o_proj")(gated.astype(dtype))


class SwiGLU(nn.Module):
    cfg: AfmoeConfig
    width: int

    @nn.compact
    def __call__(self, m: jax.Array) -> jax.Array:
        cfg = self.cfg
        g = _dense(cfg, self.width, "gate_proj")(m).astype(jnp.float32)
        u = _dense(cfg, self.width, "up_proj")(m).astype(jnp.float32)
        return _dense(cfg, cfg.n_embd, "down_proj")(
            (jax.nn.silu(g) * u).astype(cfg.compute_dtype))


def route(x: jax.Array, w_router: jax.Array, bias: jax.Array,
          cfg: AfmoeConfig):
    """(sel (N, k) int32, w (N, k) float32) for tokens x (N, d) float32:
    the k experts of the highest score + bias, weighted by their scores."""
    s = jax.nn.sigmoid(jnp.dot(x, w_router.astype(jnp.float32),
                               precision=lax.Precision.HIGHEST))
    _, sel = lax.top_k(s + lax.stop_gradient(bias.astype(jnp.float32)),
                       cfg.num_experts_per_tok)
    w = jnp.take_along_axis(s, sel, axis=1)
    if cfg.route_norm:
        w = w / (jnp.sum(w, axis=-1, keepdims=True) + 1e-20)
    return sel.astype(jnp.int32), w * cfg.route_scale


class Moe(nn.Module):
    cfg: AfmoeConfig

    @nn.compact
    def __call__(self, m: jax.Array):
        """m (B, T, d) float32 -> (f (B, T, d) float32, stats (3,) int32:
        STAT_NAMES)."""
        cfg = self.cfg
        B, T, d = m.shape
        first, count = cfg.experts_held
        F = cfg.moe_intermediate_size
        dtype = jnp.dtype(cfg.compute_dtype)
        init, pd = _dense_init(), jnp.dtype(cfg.param_dtype)
        w_router = self.param("router", init, (d, cfg.num_experts), pd)
        bias = self.param("expert_bias", nn.initializers.zeros,
                          (cfg.num_experts,), pd)
        w_gate = self.param("w_gate", init, (count, d, F), pd)
        w_up = self.param("w_up", init, (count, d, F), pd)
        w_down = self.param("w_down", init, (count, F, d), pd)
        x = m.reshape(B * T, d)
        with jax.named_scope("moe_route"):
            sel, w = route(x, w_router, bias, cfg)
            routed, stats = moe.routed_experts(
                x.astype(dtype), sel, w, w_gate.astype(dtype),
                w_up.astype(dtype), w_down.astype(dtype), first, count,
                cfg.num_experts)
            # Saved under remat (SAVED_NAMES): as large as the block's
            # output; the norm after the layer needs it, and recomputing it
            # is k row gathers a token.
            routed = checkpoint_name(routed, "moe_routed").reshape(B, T, d)
        shared = SwiGLU(cfg, F, name="moe_shared")(m.astype(dtype))
        return shared.astype(jnp.float32) + routed, stats


class Block(nn.Module):
    cfg: AfmoeConfig
    layer: int

    @nn.compact
    def __call__(self, h: jax.Array):
        cfg = self.cfg
        dtype = jnp.dtype(cfg.compute_dtype)
        sliding = cfg.layer_types[self.layer] == "sliding"
        attn = Attention(cfg, cfg.sliding_window if sliding else None,
                         name="attn_sliding" if sliding else "attn_full")
        y = attn(_rms_norm(cfg, "ln_in")(h).astype(dtype))
        h = h + _rms_norm(cfg, "ln_post_attn")(y)
        m = _rms_norm(cfg, "ln_pre_mlp")(h)
        if self.layer < cfg.num_dense_layers:
            f = SwiGLU(cfg, cfg.intermediate_size, name="mlp")(
                m.astype(dtype))
            stats = jnp.zeros((len(STAT_NAMES),), jnp.int32)
        else:
            f, stats = Moe(cfg, name="moe")(m)
        return h + _rms_norm(cfg, "ln_post_mlp")(f), stats


class Afmoe(nn.Module):
    cfg: AfmoeConfig
    mesh: Any = None

    @nn.compact
    def __call__(self, idx: jax.Array, *, deterministic: bool = True,
                 return_hidden: bool = False):
        """(logits (B, T, vocab), stats) or, with return_hidden, (the final
        norm's output (B, T, d) float32, stats) for the chunked head + loss.
        stats: {name: (expert layers,) int32} for STAT_NAMES. The model has
        no dropout; ``deterministic`` is the trainer's call convention."""
        cfg = self.cfg
        B, T = idx.shape
        if T > cfg.block_size:
            raise ValueError(
                f"sequence length {T} > block_size {cfg.block_size}")
        wte = nn.Embed(cfg.vocab_size, cfg.n_embd,
                       embedding_init=_dense_init(),
                       param_dtype=cfg.param_dtype, name="wte")
        head = self.param("lm_head", _dense_init(),
                          (cfg.vocab_size, cfg.n_embd),
                          jnp.dtype(cfg.param_dtype))
        h = wte(idx).astype(jnp.float32)
        if cfg.mup_enabled:
            h = h * math.sqrt(cfg.n_embd)
        h = constrain_acts(self.mesh, h)
        block_cls = (remat_block(Block, cfg.remat_policy, SAVED_NAMES,
                                 static_argnums=())
                     if cfg.remat else Block)
        stats = []
        for i in range(cfg.n_layer):
            h, st = block_cls(cfg, i, name=f"h_{i}")(h)
            h = constrain_acts(self.mesh, h)
            if i >= cfg.num_dense_layers:
                stats.append(st)
        stats = (jnp.stack(stats) if stats else jnp.zeros(
            (0, len(STAT_NAMES)), jnp.int32))
        aux = {name: stats[:, n] for n, name in enumerate(STAT_NAMES)}
        h = _rms_norm(cfg, "ln_f")(h)
        if return_hidden:
            return h, aux
        return jnp.einsum("btd,vd->btv", h.astype(cfg.param_dtype),
                          head), aux


# -- the family's answers to Trainer (models/__init__.py: FAMILIES) ----------

model_config = AfmoeConfig.from_train_config

# What restore_for_inference misses for this family.
inference = (
    "a cache branch in its attention (grouped KV heads, rotary positions at "
    "the cached offset, a window bound on the keys read), paged pools for "
    "two kinds of layer, and a decode path through the routed experts")


def check(cfg, pretrained: bool) -> None:
    """What of a TrainConfig this family cannot run yet, refused by name
    instead of replicating or attending wrongly in silence."""
    if pretrained:
        raise ValueError("init_from loads GPT-2 weights; "
                         "model_family='afmoe' starts from scratch")
    if cfg.mesh_sp > 1 or cfg.mesh_tp > 1:
        raise NotImplementedError(
            "model_family='afmoe' runs on the data and fsdp axes "
            f"only (got seq={cfg.mesh_sp}, model={cfg.mesh_tp}). "
            "Missing for seq: ring attention with grouped KV heads "
            "and a window (ops/ring_attention.py walks one KV head "
            "a query head, all keys). Missing for model: a rule in "
            "parallel/sharding.py for q/k/v/gate/o projections and "
            "expert matrices, and an expert axis with its exchange "
            "in parallel/mesh.py")


def build(cfg: AfmoeConfig, mesh: Any):
    """(the model, what ``trainer_init`` records of it)."""
    # What a full batch's attention resolves to, as the model will at trace
    # time. 'pallas': q/k head norm + rotary as one kernel (qk_prep), then
    # the grouped-query kernels, all on the projections' own
    # (B, T, heads*D) layout ('btc-gqa'); 'xla': head_rms_norm, rotary and
    # xla_attention ('bhtd').
    prep = resolve_gqa_impl(cfg.attention_impl, cfg.head_dim, cfg.block_size)
    # What brings the routed experts' rows back to their tokens, resolved as
    # ops.moe.routed_experts will for a batch of whole sequences (a batch is
    # a multiple of block_size tokens): 'pallas' (%moe_rows.N, only the rows
    # that hold a pair are moved) or 'xla' (k gathers a token).
    mover = moe.resolve_row_mover("auto", cfg.block_size, cfg.n_embd)
    return Afmoe(cfg, mesh=mesh), {
        "attn_layout": "bhtd" if prep == "xla" else "btc-gqa",
        "qk_prep": prep, "moe_row_mover": mover,
        "layer_types": ",".join(cfg.layer_types),
        "experts_held": list(cfg.experts_held)}


def apply(model: Afmoe, params, x: jax.Array, *, deterministic: bool,
          return_hidden: bool, rngs=None):
    """(logits or hidden, the expert layers' counters), as the model
    returns them."""
    return model.apply({"params": params}, x, deterministic=deterministic,
                       return_hidden=return_hidden, rngs=rngs)


def head(params) -> jax.Array:
    """The head's (vocab, d) table: the model's own, untied."""
    return params["lm_head"]


def flops_per_token(cfg: AfmoeConfig, T: int, n_params: int) -> float:
    """Forward + backward operations a trained token requires here: 6 per
    parameter that multiplies it (one routed expert for each of the
    k * count / E held slots a token has on average) plus attention over
    the (query, key) pairs the masks leave, 12 * H * D a pair. Counted
    from the config: ``n_params`` is the hook's and not read."""
    d, H, G, D = cfg.n_embd, cfg.n_head, cfg.n_kv_head, cfg.head_dim
    attn_params = d * (2 * H * D + 2 * G * D) + H * D * d
    expert = 3 * d * cfg.moe_intermediate_size
    first, count = cfg.experts_held
    held = cfg.num_experts_per_tok * count / max(cfg.num_experts, 1)
    total = cfg.vocab_size * d  # the head; the embedding is a lookup
    for kind in cfg.layer_types:
        w = min(cfg.sliding_window, T) if kind == "sliding" else T
        pairs = (w * (w + 1) // 2 + (T - w) * w) / T   # sum_i min(i + 1, w)
        total += attn_params + 2 * H * D * pairs
    n_dense = cfg.num_dense_layers
    total += n_dense * 3 * d * cfg.intermediate_size
    total += (cfg.n_layer - n_dense) * (expert * (1 + held)
                                        + d * cfg.num_experts)
    return 6.0 * total
