"""The ``lfm2`` decoder (LiquidAI LFM2 MoE; HF ``transformers``
models/lfm2_moe) in flax.linen, on the trainer's normal path beside
models/gpt.py and models/afmoe.py.

A layer, with the published config's names (d = n_embd, H = n_head query
heads, G = n_kv_head, D = head_dim = 64, E = num_experts,
k = num_experts_per_tok, L = conv_L_cache taps):

    a = RMSNorm_operator(h)
    conv layers (layer_types[i] == 'conv'):
        [Bg | Cg | x] = a W_in           W_in (d, 3d), no bias
        u = Bg * x
        c[t] = sum_j w[:, j] * u[t - (L-1) + j]   w (d, L), one filter a
                                         channel; u = 0 before position 0
        o = (Cg * c) W_out               W_out (d, d), no bias
    attention layers ('full'):
        q, k, v = a Wq, a Wk, a Wv       (no biases)
        q, k = RMSNorm_q(q), RMSNorm_k(k)  over D, one scale each
        q, k = rotary(q), rotary(k)      rotate-half, all D dims, EVERY layer
        o = softmax(q k^T / sqrt(D) + causal) v Wo   query head i reads KV
                                         head i // (H // G); no gate, no window
    h = h + o
    m = RMSNorm_ffn(h)
    dense layers (the first num_dense_layers): f = SwiGLU(m), intermediate_size
    expert layers: s = sigmoid(m Wr); sel = top_k(s + expert_bias); w = s[sel];
        w = route_scale * w / (sum w + 1e-6)
        f = sum_j w_j Expert_{sel_j}(m), SwiGLUs of moe_intermediate_size;
        NO shared expert
    h = h + f

No scale on the embedding, final RMSNorm (``embedding_norm``), and the head
is the embedding table (tied).

The expert layer is told which experts it holds (``experts_held`` = (first,
count)): it scores and selects over all E and computes only the slots naming
a held expert (models/experts.routed_experts, ops/moe.py). On one chip that
partial sum goes on to the next layer; nothing stands in for absent chips.
``expert_bias`` moves the selection and never the weights, and is a leaf no
gradient reaches: zeros unless a checkpoint, or the benchmark's weights,
bring values (the published config gives no update rule, and none is
applied).

Precision: parameters ``param_dtype``; matmul inputs ``compute_dtype`` with
float32 accumulation; the residual stream, the norms, rotary positions, the
gates and taps of the convolution, the router (matmul at full float32
precision, sigmoid, top-k, weights) and the weighted sum of expert outputs
in float32.

Attention at head size 64 (ops.attention.gqa_route): two heads share a
128-lane tile, which the grouped-query kernels do not walk; on a Pallas impl
the layer runs the (B, H, T, D) flash kernels on KV heads repeated H // G
times ('bhtd-rep'), and the q/k prologue is models/experts.py's XLA form.

Under remat (``save_attention``) a block keeps the attention kernels' output
and logsumexp and the routed experts' weighted sum; a conv layer keeps
NOTHING of its mixer: its backward needs the (B, T, 3d) projection output,
three times the block's input, and recomputing it is one matmul.

Scopes (obs/opscopes.py): modules ``conv`` (``in_proj``, ``out_proj`` and,
inside it, the named scope ``conv_mix``: ops/short_conv.py's gates and
taps, one kernel a pass where ``resolve_conv_impl`` says so) and
``attn_full``; ``mlp``; named scopes ``moe_route`` and, inside
it, ``moe_experts``; the norms ``operator_norm``, ``ffn_norm``,
``embedding_norm``; ``wte``. Custom calls in a device trace:
``%attn_full.N`` (the flash kernels), ``%conv_mix.N`` (the gated short
convolution), ``%gmm.N`` / ``%tgmm.N`` (megablox) and ``%moe_rows.N``
(ops/moe.py's row mover).
"""

from __future__ import annotations

import functools
from typing import Any

import flax.linen as nn
import jax
import jax.numpy as jnp

from nanosandbox_tpu.config import Lfm2Config
from nanosandbox_tpu.models import experts
from nanosandbox_tpu.models.common import _dense_init, constrain_acts
from nanosandbox_tpu.models.experts import (STAT_NAMES, HeadRMSNorm, SwiGLU,
                                            dense, rms_norm)
from nanosandbox_tpu.ops import moe, short_conv
from nanosandbox_tpu.ops.attention import (causal_attention_gqa, gqa_route,
                                           resolve_gqa_impl)

ROUTE_EPS = 1e-6   # in the sum the selected scores are divided by


class ShortConv(nn.Module):
    """Named ``conv`` by its block. a (B, T, d) in the compute type."""
    cfg: Lfm2Config

    @nn.compact
    def __call__(self, a: jax.Array) -> jax.Array:
        cfg = self.cfg
        d = cfg.n_embd
        bcx = dense(cfg, 3 * d, "in_proj")(a)
        taps = self.param("filter", _dense_init(), (d, cfg.conv_L_cache),
                          jnp.dtype(cfg.param_dtype))
        impl = short_conv.resolve_conv_impl(cfg.attention_impl, a.shape[1],
                                            d)
        return dense(cfg, d, "out_proj")(
            short_conv.gated_short_conv(bcx, taps, impl))


class Attention(nn.Module):
    """Named ``attn_full`` by its block: the kernels' scope and the part the
    device trace files it under."""
    cfg: Lfm2Config

    @nn.compact
    def __call__(self, a: jax.Array) -> jax.Array:
        cfg = self.cfg
        T = a.shape[1]
        H, G, D = cfg.n_head, cfg.n_kv_head, cfg.head_dim
        q = dense(cfg, H * D, "q_proj")(a)
        k = dense(cfg, G * D, "k_proj")(a)
        v = dense(cfg, G * D, "v_proj")(a)
        prep = resolve_gqa_impl(cfg.attention_impl, D, T)
        norm = functools.partial(HeadRMSNorm, eps=cfg.rms_norm_eps,
                                 param_dtype=cfg.param_dtype)
        q = norm(H, name="q_norm")(q, cfg.rope_theta, prep)
        k = norm(G, name="k_norm")(k, cfg.rope_theta, prep)
        o = causal_attention_gqa(q, k, v, H, G, impl=cfg.attention_impl,
                                 scope=self.name)
        return dense(cfg, cfg.n_embd, "o_proj")(o)


class Moe(nn.Module):
    cfg: Lfm2Config

    @nn.compact
    def __call__(self, m: jax.Array):
        """m (B, T, d) float32 -> (f (B, T, d) float32, stats (3,) int32:
        STAT_NAMES): the routed experts held, and nothing beside them."""
        return experts.routed_experts(self, m, self.cfg,
                                      route_eps=ROUTE_EPS)


class Block(nn.Module):
    cfg: Lfm2Config
    layer: int

    @nn.compact
    def __call__(self, h: jax.Array):
        cfg = self.cfg
        dtype = jnp.dtype(cfg.compute_dtype)
        mixer = (ShortConv(cfg, name="conv")
                 if cfg.layer_types[self.layer] == "conv"
                 else Attention(cfg, name="attn_full"))
        h = h + mixer(rms_norm(cfg, "operator_norm")(h).astype(dtype))
        m = rms_norm(cfg, "ffn_norm")(h)
        if self.layer < cfg.num_dense_layers:
            f = SwiGLU(cfg, cfg.intermediate_size, name="mlp")(
                m.astype(dtype))
            stats = jnp.zeros((len(STAT_NAMES),), jnp.int32)
        else:
            f, stats = Moe(cfg, name="moe")(m)
        return h + f, stats


class Lfm2(nn.Module):
    cfg: Lfm2Config
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
        h = constrain_acts(self.mesh, wte(idx).astype(jnp.float32))
        h, aux = experts.decoder_layers(Block, cfg, self.mesh, h)
        h = rms_norm(cfg, "embedding_norm")(h)
        if return_hidden:
            return h, aux
        return jnp.einsum("btd,vd->btv", h.astype(cfg.param_dtype),
                          wte.embedding), aux


# -- the family's answers to Trainer (models/__init__.py: FAMILIES) ----------

model_config = Lfm2Config.from_train_config

# What restore_for_inference misses for this family.
inference = (
    "a state for its conv layers (the last conv_L_cache - 1 gated inputs a "
    "sequence) beside a cache branch in its attention (grouped KV heads at "
    "head size 64, rotary positions at the cached offset), paged pools for "
    "two kinds of layer, and a decode path through the routed experts")


def check(cfg, pretrained: bool) -> None:
    """What of a TrainConfig this family cannot run yet, refused by name
    instead of replicating or mixing wrongly in silence."""
    if pretrained:
        raise ValueError("init_from loads GPT-2 weights; "
                         "model_family='lfm2' starts from scratch")
    if cfg.mesh_sp > 1 or cfg.mesh_tp > 1:
        raise NotImplementedError(
            "model_family='lfm2' runs on the data and fsdp axes "
            f"only (got seq={cfg.mesh_sp}, model={cfg.mesh_tp}). "
            "Missing for seq: the convolution's conv_L_cache - 1 rows of "
            "halo from the shard before, and ring attention with grouped "
            "KV heads (ops/ring_attention.py walks one KV head a query "
            "head). Missing for model: a rule in parallel/sharding.py for "
            "the in/out and q/k/v/o projections, the filter and the expert "
            "matrices, and an expert axis with its exchange in "
            "parallel/mesh.py")


def build(cfg: Lfm2Config, mesh: Any):
    """(the model, what ``trainer_init`` records of it)."""
    # What a full batch resolves to, as the model will at trace time: the
    # attention entry (ops.attention.gqa_route), the q/k prologue, what
    # brings the routed experts' rows back to their tokens, and the grouped
    # matmul's tiling at the experts' shapes.
    d, F = cfg.n_embd, cfg.moe_intermediate_size
    route = gqa_route(cfg.attention_impl, cfg.head_dim, cfg.block_size)
    return Lfm2(cfg, mesh=mesh), {
        "attn_layout": "bhtd" if route == "xla" else route,
        "attn_route": route,
        "qk_prep": resolve_gqa_impl(cfg.attention_impl, cfg.head_dim,
                                    cfg.block_size),
        "conv_mix": short_conv.resolve_conv_impl(cfg.attention_impl,
                                                 cfg.block_size, d),
        "moe_row_mover": moe.resolve_row_mover("auto", cfg.block_size, d),
        "gmm_tiling": list(moe.gmm_tiling(moe.ROW_TILE, d, F)),
        "layer_types": ",".join(cfg.layer_types),
        "experts_held": list(cfg.experts_held)}


def apply(model: Lfm2, params, x: jax.Array, *, deterministic: bool,
          return_hidden: bool, rngs=None):
    """(logits or hidden, the expert layers' counters), as the model
    returns them."""
    return model.apply({"params": params}, x, deterministic=deterministic,
                       return_hidden=return_hidden, rngs=rngs)


def head(params) -> jax.Array:
    """The head's (vocab, d) table: the embedding's, tied."""
    return params["wte"]["embedding"]


def flops_per_token(cfg: Lfm2Config, T: int, n_params: int) -> float:
    """Forward + backward operations a trained token requires here: 6 per
    parameter that multiplies it (one routed expert for each of the
    k * count / E held slots a token has on average; the filter's L taps a
    channel) plus full causal attention's (query, key) pairs, 12 * H * D a
    pair. Counted from the config: ``n_params`` is the hook's and not
    read."""
    d, H, G, D = cfg.n_embd, cfg.n_head, cfg.n_kv_head, cfg.head_dim
    mixer = {"conv": 4 * d * d + d * cfg.conv_L_cache,
             "full": d * (2 * H * D + 2 * G * D) + 2 * H * D * (T + 1) / 2}
    first, count = cfg.experts_held
    held = cfg.num_experts_per_tok * count / max(cfg.num_experts, 1)
    total = cfg.vocab_size * d  # the tied head; the embedding is a lookup
    total += sum(mixer[kind] for kind in cfg.layer_types)
    n_dense = cfg.num_dense_layers
    total += n_dense * 3 * d * cfg.intermediate_size
    total += (cfg.n_layer - n_dense) * (
        3 * d * cfg.moe_intermediate_size * held + d * cfg.num_experts)
    return 6.0 * total
