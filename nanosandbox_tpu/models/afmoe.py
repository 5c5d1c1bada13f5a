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
in HBM); everywhere else models/experts.py's head_rms_norm and rotary, in
XLA. The router, SwiGLU, the head norm, the routed half of the expert layer
and the shared expert are models/experts.py's, shared with the other expert
families.

Scopes (obs/opscopes.py): modules ``attn_sliding`` / ``attn_full``, ``mlp``,
``moe_shared``, the norms ``ln_*``, ``wte``; named scopes ``moe_route``
(router, top-k, sort, gather, combine: six stage scopes inside it, opened
by models/experts.py and ops/moe.py, ``opscopes._STAGE``) and, inside it,
``moe_experts`` (the grouped matmuls and the activation between them,
ops/moe.expert_ffn);
``qk_prep`` inside the attention modules (its custom calls are
``%qk_prep.N``, apart from the flash kernels' ``%attn_sliding.N`` /
``%attn_full.N``; it names no part, so its time is the module's).
The family's custom calls in a device trace: ``%attn_sliding.N`` /
``%attn_full.N`` (two a layer: the forward and the one-pass backward that
gives dQ, dK and dV; three where ``trainer_init``'s ``gqa_bwd`` reads
'split'), ``%qk_prep.N``, ``%gmm.N`` / ``%tgmm.N`` (megablox) and
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

from nanosandbox_tpu.config import AfmoeConfig
from nanosandbox_tpu.models import experts
from nanosandbox_tpu.models.common import _dense_init, constrain_acts
from nanosandbox_tpu.models.experts import (STAT_NAMES, HeadRMSNorm, SwiGLU,
                                            dense as _dense,
                                            rms_norm as _rms_norm)
from nanosandbox_tpu.ops import moe
from nanosandbox_tpu.ops.attention import (causal_attention_gqa,
                                           resolve_gqa_bwd, resolve_gqa_impl)

ROUTE_EPS = 1e-20   # in the sum the selected scores are divided by


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


class Moe(nn.Module):
    cfg: AfmoeConfig

    @nn.compact
    def __call__(self, m: jax.Array):
        """m (B, T, d) float32 -> (f (B, T, d) float32, stats (3,) int32:
        STAT_NAMES): the routed experts held (models/experts.py) plus this
        family's addition, the shared expert."""
        cfg = self.cfg
        routed, stats = experts.routed_experts(self, m, cfg,
                                               route_eps=ROUTE_EPS)
        return experts.shared_expert(cfg, cfg.moe_intermediate_size,
                                     m) + routed, stats


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
        h, aux = experts.decoder_layers(Block, cfg, self.mesh, h)
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
    # The grouped-query kernels' backward: 'fused' (dQ, dK and dV from one
    # walk of the score tiles, dK / dV of a KV head's whole sequence in
    # VMEM), 'split' (two kernels: a sequence too long for that) or 'xla'.
    bwd = resolve_gqa_bwd(cfg.attention_impl, cfg.head_dim, cfg.block_size,
                          jnp.dtype(cfg.compute_dtype).itemsize)
    # What brings the routed experts' rows back to their tokens, resolved as
    # ops.moe.routed_experts will for a batch of whole sequences (a batch is
    # a multiple of block_size tokens): 'pallas' (%moe_rows.N, only the rows
    # that hold a pair are moved) or 'xla' (k gathers a token).
    mover = moe.resolve_row_mover("auto", cfg.block_size, cfg.n_embd)
    return Afmoe(cfg, mesh=mesh), {
        "attn_layout": "bhtd" if prep == "xla" else "btc-gqa",
        "qk_prep": prep, "gqa_bwd": bwd, "moe_row_mover": mover,
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
