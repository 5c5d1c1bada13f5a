"""The ``granite`` decoder (IBM's Granite 4.0-H hybrid; HF ``transformers``
models/granitemoehybrid without experts) in flax.linen, on the trainer's
normal path beside the other families.

Layers are Mamba-2 state-space layers or grouped-query attention layers with
no positions ("NoPE"), by ``layer_types``. With the published config's names
(d = n_embd; H, G, D the attention's heads; Hm = mamba_n_heads of
P = mamba_d_head lanes, inner = Hm * P; N = mamba_d_state, Gm =
mamba_n_groups; K = mamba_d_conv):

    h = embedding_multiplier * Emb[x]
    a layer:
        h = h + residual_multiplier * Mixer(RMSNorm_in(h))
        m = RMSNorm_post(h)
        h = h + residual_multiplier * (silu(m Wg) * (m Wu)) Wd   the shared MLP
    logits = (RMSNorm_f(h) / logits_scaling) Emb^T               (tied)

    Mixer, 'attention' layers: q, k, v = a Wq, a Wk, a Wv (no bias, no
        rotary); o = softmax(attention_multiplier q k^T + causal) v Wo,
        query head i reading KV head i // (H / G)
    Mixer, 'mamba' layers:
        [z | xBC | dt] = a W_in                 inner | inner + 2 Gm N | Hm
        xBC = silu(causal_conv_K(xBC) + b_conv)  depth-wise, taps t-K+1..t
        x, B, C = xBC split inner | Gm N | Gm N
        dt = softplus(dt + dt_bias)             no clamp;  A = -exp(A_log)
        y = SSD(x, dt, A, B, C, D)              ops/ssd.py, in chunks: one
                                                Pallas kernel each way where
                                                ssd.resolve_ssd_impl says so,
                                                the XLA form elsewhere
        y = RMSNorm_inner(y * silu(z)) * w_norm  the gate before the norm,
                                                over inner / Gm lanes a group
        out = y W_out

Initialisation: normal(0, 0.02) for every projection and the table (the
other families' ``_dense_init``); ones for the norms' scales and D; Mamba-2's
own defaults elsewhere: the conv's taps U(+-1/sqrt(K)) (PyTorch's Conv1d) with
a zero bias, A_log = log U[1, 16], dt_bias = softplus^-1(dt0) with dt0
log-uniform on [1e-3, 1e-1] and floored at 1e-4.

Precision: parameters ``param_dtype``; matmul inputs ``compute_dtype`` with
float32 accumulation (the projections, the shared MLP, the head, attention's
and the scan's products); the residual stream, the norms, the taps, dt, the
decays, the state and the gate in float32. Attention's score scale is
``attention_multiplier``, not D^-0.5: the family scales q by
attention_multiplier * sqrt(D) before ops.attention.causal_attention_gqa
(1/8 at the published 1/64 and D 64: exact in bfloat16).

Under remat each layer is one ``remat_block`` (``save_attention`` keeps the
attention kernels' output and logsumexp; a Mamba layer keeps nothing).

A step reports, for each Mamba layer, the scan's health (ops/ssd.py): the
mean decay of the state over a chunk (``ssd_decay``) and the largest |S| at
a chunk boundary (``ssd_state_max``); ``Trainer`` leaves them as the
instant ``granite_ssd`` at log steps (``INSTANTS``).

Scopes (obs/opscopes.py): modules ``mamba`` (the projections, taps, dt and
the gated norm) and, inside it, the named scope ``ssd`` (the scan, forward
and backward; the kernels' custom calls ``%ssd.N``); ``attn_full``
(projections and the flash kernels, ``%attn_full.N``); ``mlp``; the norms
``input_layernorm``,
``post_attention_layernorm``, ``final_norm``; ``wte``.
"""

from __future__ import annotations

import math
from typing import Any

import flax.linen as nn
import jax
import jax.numpy as jnp

from nanosandbox_tpu.config import GraniteConfig
from nanosandbox_tpu.models.common import (_dense_init, constrain_acts,
                                           remat_block)
from nanosandbox_tpu.models.experts import SwiGLU, dense, rms_norm
from nanosandbox_tpu.ops import ssd
from nanosandbox_tpu.ops.attention import causal_attention_gqa, gqa_route
from nanosandbox_tpu.ops.short_conv import causal_taps

# What a block under remat keeps: the attention kernels' output and
# logsumexp (ops/attention.py).
SAVED_NAMES = ("attn_out", "attn_lse")
# What a step reports of its Mamba layers, one entry a layer.
SSD_STATS = ("ssd_decay", "ssd_state_max")
# The counters Trainer leaves as an instant at log steps: {name: aux keys}.
INSTANTS = {"granite_ssd": SSD_STATS}
# Mamba-2's ranges for A = -exp(A_log) and for the initial dt.
A_RANGE, DT_RANGE, DT_FLOOR = (1.0, 16.0), (1e-3, 1e-1), 1e-4


def _taps_init(key, shape, dtype):
    """(width, K) taps from U(+-1/sqrt(K)), PyTorch's Conv1d default."""
    bound = 1.0 / math.sqrt(shape[-1])
    return jax.random.uniform(key, shape, dtype, -bound, bound)


def _a_log_init(key, shape, dtype):
    """log U[A_RANGE]: each head's decay rate |A|."""
    a = jax.random.uniform(key, shape, jnp.float32, *A_RANGE)
    return jnp.log(a).astype(dtype)


def _dt_bias_init(key, shape, dtype):
    """softplus^-1(dt0), dt0 log-uniform on DT_RANGE and floored at
    DT_FLOOR, so that softplus(0 + dt_bias) starts at dt0."""
    lo, hi = (math.log(v) for v in DT_RANGE)
    dt = jnp.maximum(jnp.exp(jax.random.uniform(key, shape, jnp.float32,
                                                lo, hi)), DT_FLOOR)
    return (dt + jnp.log(-jnp.expm1(-dt))).astype(dtype)


class GatedRMSNorm(nn.Module):
    """RMSNorm of y * silu(z) over each of ``groups`` equal slices of the
    last dimension, float32, one scale of the whole width."""
    eps: float
    groups: int
    param_dtype: str

    @nn.compact
    def __call__(self, y: jax.Array, z: jax.Array) -> jax.Array:
        *lead, width = y.shape
        scale = self.param("scale", nn.initializers.ones, (width,),
                           jnp.dtype(self.param_dtype))
        g = (y * jax.nn.silu(z.astype(jnp.float32))).reshape(
            *lead, self.groups, width // self.groups)
        g = g * jax.lax.rsqrt(jnp.mean(g * g, axis=-1, keepdims=True)
                              + self.eps)
        return g.reshape(*lead, width) * scale


class Mamba(nn.Module):
    """Named ``mamba`` by its block. a (B, T, d) in the compute type ->
    (out (B, T, d) in the compute type, the scan's counters (2,) float32:
    SSD_STATS)."""
    cfg: GraniteConfig

    @nn.compact
    def __call__(self, a: jax.Array):
        cfg = self.cfg
        H, P = cfg.mamba_n_heads, cfg.mamba_d_head
        G, N = cfg.mamba_n_groups, cfg.mamba_d_state
        inner, pd = H * P, jnp.dtype(cfg.param_dtype)
        width = inner + 2 * G * N                 # what the taps mix
        z, xbc, dt = jnp.split(dense(cfg, inner + width + H, "in_proj")(a),
                               [inner, inner + width], axis=-1)
        taps = self.param("conv_weight", _taps_init,
                          (width, cfg.mamba_d_conv), pd)
        bias = self.param("conv_bias", nn.initializers.zeros, (width,), pd)
        xbc = jax.nn.silu(causal_taps(xbc.astype(jnp.float32),
                                      taps.astype(jnp.float32)) + bias)
        x, B, C = jnp.split(xbc, [inner, inner + G * N], axis=-1)
        dt_bias = self.param("dt_bias", _dt_bias_init, (H,), pd)
        A_log = self.param("A_log", _a_log_init, (H,), pd)
        D = self.param("D", nn.initializers.ones, (H,), pd)
        impl = ssd.resolve_ssd_impl(cfg.attention_impl, a.shape[1],
                                    cfg.mamba_chunk_size, P, N, heads=H,
                                    groups=G)
        y, stats = ssd.ssd(x, jax.nn.softplus(dt.astype(jnp.float32)
                                              + dt_bias),
                           -jnp.exp(A_log.astype(jnp.float32)), B, C, D,
                           chunk=cfg.mamba_chunk_size, groups=G,
                           dtype=jnp.dtype(cfg.compute_dtype), impl=impl)
        y = GatedRMSNorm(cfg.rms_norm_eps, G, cfg.param_dtype, name="norm")(
            y, z)
        out = dense(cfg, cfg.n_embd, "out_proj")(y.astype(cfg.compute_dtype))
        return out, jnp.stack([stats[k] for k in SSD_STATS])


class Attention(nn.Module):
    """Named ``attn_full`` by its block: the kernels' scope and the part the
    device trace files it under. No positions; the score's scale is
    ``attention_multiplier``."""
    cfg: GraniteConfig

    @nn.compact
    def __call__(self, a: jax.Array) -> jax.Array:
        cfg = self.cfg
        H, G, D = cfg.n_head, cfg.n_kv_head, cfg.head_dim
        # the kernels scale the score by D^-0.5: q carries the rest
        q = dense(cfg, H * D, "q_proj")(a) * (cfg.attention_multiplier
                                              * D ** 0.5)
        k = dense(cfg, G * D, "k_proj")(a)
        v = dense(cfg, G * D, "v_proj")(a)
        o = causal_attention_gqa(q, k, v, H, G, impl=cfg.attention_impl,
                                 scope=self.name)
        return dense(cfg, cfg.n_embd, "o_proj")(o)


class Block(nn.Module):
    cfg: GraniteConfig
    layer: int

    @nn.compact
    def __call__(self, h: jax.Array):
        cfg = self.cfg
        dtype = jnp.dtype(cfg.compute_dtype)
        a = rms_norm(cfg, "input_layernorm")(h).astype(dtype)
        if cfg.layer_types[self.layer] == "mamba":
            y, stats = Mamba(cfg, name="mamba")(a)
        else:
            y = Attention(cfg, name="attn_full")(a)
            stats = jnp.zeros((len(SSD_STATS),), jnp.float32)
        h = h + cfg.residual_multiplier * y.astype(jnp.float32)
        f = SwiGLU(cfg, cfg.intermediate_size, name="mlp")(
            rms_norm(cfg, "post_attention_layernorm")(h).astype(dtype))
        return h + cfg.residual_multiplier * f.astype(jnp.float32), stats


class Granite(nn.Module):
    cfg: GraniteConfig
    mesh: Any = None

    @nn.compact
    def __call__(self, idx: jax.Array, *, deterministic: bool = True,
                 return_hidden: bool = False):
        """(logits (B, T, vocab), aux) or, with return_hidden, (the final
        norm's output (B, T, d) float32, aux) for the chunked head + loss.
        aux: {name: (Mamba layers,) float32} for SSD_STATS. The model has no
        dropout; ``deterministic`` is the trainer's call convention."""
        cfg = self.cfg
        B, T = idx.shape
        if T > cfg.block_size:
            raise ValueError(
                f"sequence length {T} > block_size {cfg.block_size}")
        wte = nn.Embed(cfg.vocab_size, cfg.n_embd,
                       embedding_init=_dense_init(),
                       param_dtype=cfg.param_dtype, name="wte")
        h = constrain_acts(self.mesh, wte(idx).astype(jnp.float32)
                           * cfg.embedding_multiplier)
        block = Block
        if cfg.remat:
            block = remat_block(Block, cfg.remat_policy, SAVED_NAMES,
                                static_argnums=())
        stats = []
        for i, kind in enumerate(cfg.layer_types):
            h, st = block(cfg, i, name=f"h_{i}")(h)
            h = constrain_acts(self.mesh, h)
            if kind == "mamba":
                stats.append(st)
        stats = (jnp.stack(stats) if stats
                 else jnp.zeros((0, len(SSD_STATS)), jnp.float32))
        aux = {name: stats[:, n] for n, name in enumerate(SSD_STATS)}
        # logits = (h E^T) / logits_scaling, with the divisor taken on h so
        # that the head is the table itself (``head``): at a power of two
        # (8 as published) the same numbers to the bit
        h = rms_norm(cfg, "final_norm")(h) / cfg.logits_scaling
        if return_hidden:
            return h, aux
        return jnp.einsum("btd,vd->btv", h.astype(cfg.param_dtype),
                          wte.embedding), aux


# -- the family's answers to Trainer (models/__init__.py: FAMILIES) ----------

model_config = GraniteConfig.from_train_config

# What restore_for_inference misses for this family.
inference = (
    "a recurrent state for its Mamba layers (the scan's (heads, d_head, "
    "d_state) state and the conv's last d_conv - 1 inputs a sequence) beside "
    "the KV cache of its attention layers in serve's BlockPool, and a decode "
    "path that advances the state one token at a time")


def check(cfg, pretrained: bool) -> None:
    """What of a TrainConfig this family cannot run yet, refused by name
    instead of replicating or scanning wrongly in silence."""
    if pretrained:
        raise ValueError("init_from loads GPT-2 weights; "
                         "model_family='granite' starts from scratch")
    if cfg.mesh_sp > 1 or cfg.mesh_tp > 1:
        raise NotImplementedError(
            "model_family='granite' runs on the data and fsdp axes "
            f"only (got seq={cfg.mesh_sp}, model={cfg.mesh_tp}). "
            "Missing for seq: the scan's state handed from one sequence "
            "shard to the next (and the conv's d_conv - 1 rows of halo), and "
            "ring attention with grouped KV heads. Missing for model: a rule "
            "in parallel/sharding.py for the Mamba layers' in_proj / "
            "out_proj and the gated norm's statistics over the whole inner "
            "width, and for the attention and MLP projections")


def build(cfg: GraniteConfig, mesh: Any):
    """(the model, what ``trainer_init`` records of it)."""
    # What a full batch's attention and scan resolve to, as the model will
    # at trace time (ops.attention.gqa_route, ops.ssd.resolve_ssd_impl).
    route = gqa_route(cfg.attention_impl, cfg.head_dim, cfg.block_size)
    scan = ssd.resolve_ssd_impl(
        cfg.attention_impl, cfg.block_size, cfg.mamba_chunk_size,
        cfg.mamba_d_head, cfg.mamba_d_state, heads=cfg.mamba_n_heads,
        groups=cfg.mamba_n_groups)
    return Granite(cfg, mesh=mesh), {
        "attn_layout": "bhtd" if route == "xla" else route,
        "attn_route": route,
        "ssd_impl": scan, "ssd_chunk": cfg.mamba_chunk_size,
        "layer_types": ",".join(cfg.layer_types),
        "remat_policy": cfg.remat_policy if cfg.remat else "none"}


def apply(model: Granite, params, x: jax.Array, *, deterministic: bool,
          return_hidden: bool, rngs=None):
    """(logits or hidden, the Mamba layers' counters), as the model returns
    them."""
    return model.apply({"params": params}, x, deterministic=deterministic,
                       return_hidden=return_hidden, rngs=rngs)


def head(params) -> jax.Array:
    """The head's (vocab, d) table: the embedding's, tied (the model hands
    out its final state already divided by ``logits_scaling``)."""
    return params["wte"]["embedding"]


def flops_per_token(cfg: GraniteConfig, T: int, n_params: int) -> float:
    """Forward + backward operations a trained token requires here: 6 per
    parameter that multiplies it (the Mamba layers' in / out projections and
    the conv's taps, attention's q / k / v / o, the shared MLPs, the tied
    head), full causal attention's (query, key) pairs at 12 * H * D a pair,
    and three times the scan's required forward products
    (``ops.ssd.forward_flops_per_token``). Counted from the config:
    ``n_params`` is the hook's and not read."""
    d, H, G, D = cfg.n_embd, cfg.n_head, cfg.n_kv_head, cfg.head_dim
    Hm, P = cfg.mamba_n_heads, cfg.mamba_d_head
    width = Hm * P + 2 * cfg.mamba_n_groups * cfg.mamba_d_state
    mixer = {"mamba": d * (Hm * P + width + Hm) + Hm * P * d
             + width * cfg.mamba_d_conv,
             "attention": d * (2 * H * D + 2 * G * D)}
    per_token = cfg.vocab_size * d + sum(
        mixer[kind] + 3 * d * cfg.intermediate_size
        for kind in cfg.layer_types)
    scan = ssd.forward_flops_per_token(cfg.mamba_chunk_size, Hm, P,
                                       cfg.mamba_d_state, cfg.mamba_n_groups)
    n_mamba = cfg.layer_types.count("mamba")
    n_attention = cfg.layer_types.count("attention")
    return (6.0 * per_token + 12.0 * H * D * n_attention * (T + 1) / 2
            + 3.0 * n_mamba * scan)
