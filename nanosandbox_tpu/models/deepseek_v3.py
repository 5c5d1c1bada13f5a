"""The ``deepseek_v3`` decoder (DeepSeek-V3's block; HF ``transformers``
models/deepseek_v3, whose published instances include Moonshot's Moonlight)
in flax.linen, on the trainer's normal path beside models/gpt.py,
models/afmoe.py and models/lfm2.py.

A layer, with the published config's names (d = n_embd, H = n_head,
r = kv_lora_rank, Dn = qk_nope_head_dim, Dr = qk_rope_head_dim,
Dv = v_head_dim, E = num_experts, k = num_experts_per_tok; no biases):

    a = RMSNorm_input(h)
    q = a Wq                       (T, H, Dn + Dr) = [q_nope | q_pe] a head
    [c | k_pe] = a Wkva            (T, r + Dr); k_pe is ONE head
    [k_nope | v] = RMSNorm_kv(c) Wkvb    (T, H, Dn + Dv) a head
    q_pe, k_pe = rotary(q_pe), rotary(k_pe)   rotate-half over the Dr dims
    s_h = (q_nope_h k_nope_h^T + q_pe_h k_pe^T) / sqrt(Dn + Dr) + causal
    h = h + concat_h(softmax(s_h) v_h) Wo
    m = RMSNorm_post_attention(h)
    dense layers (the first num_dense_layers): f = SwiGLU(m), intermediate_size
    expert layers: s = sigmoid(m Wr); sel = top_k(s + expert_bias);
        w = route_scale * s[sel] / (sum s[sel] + 1e-20)
        f = Shared(m) + sum_j w_j Expert_{sel_j}(m): experts SwiGLUs of
        moe_intermediate_size, the shared one n_shared_experts times as wide
    h = h + f

No scale on the embedding, final RMSNorm, untied head. This is ``noaux_tc``
selection at ONE group (n_group = topk_group = 1), a query with no latent of
its own (q_lora_rank 0) and no rope scaling: what else the family publishes
is refused by name (``check``).

The rotary pairing: HF's modelling code de-interleaves the Dr dims (pairs
(2i, 2i+1) into halves) and then rotates halves; that is one fixed
permutation of Wq's and Wkva's rotary columns, the same for q_pe and k_pe, so
the scores are those of rotate-half on the columns as they lie, which is what
runs here (and in the benchmark's reference). Weights converted from a
published checkpoint would need the permutation applied once.

The expert layer is told which experts it holds (``experts_held`` = (first,
count)): it scores and selects over all E and computes only the slots naming
a held expert, plus the shared expert (models/experts.py, ops/moe.py). On one
chip that partial sum goes on to the next layer; nothing stands in for absent
chips. ``expert_bias`` (HF: e_score_correction_bias) moves the selection and
never the weights, and is a leaf no gradient reaches: zeros unless a
checkpoint, or the benchmark's weights, bring values.

Precision: parameters ``param_dtype``; matmul inputs ``compute_dtype`` with
float32 accumulation; the residual stream, the norms (the latent's too),
rotary positions, the router (matmul at full float32 precision, sigmoid,
top-k, weights) and the weighted sum of expert outputs in float32.

Attention's layout: Wq's and Wkvb's columns are taken apart BEFORE the
products (q_nope and q_pe are two products of ``a``, k_nope and v two of the
latent), so every operand leaves its projection in the layout the kernels
read, (B, T, H * 128), with no slice or copy of an activation; only the
64-wide q_pe is moved, by the rotary pass that writes it anew. Where
ops.attention.mla_route says so (a Pallas impl, whole 128-lane heads, whole
128-row blocks) attention is ops.attention.flash_attention_mla, forward and
ONE backward kernel; elsewhere xla_attention on the concatenated heads. The
latent's RMSNorm and the rotary positions are XLA (models/experts.rotary on
the 64-wide slice), under the named scope ``mla_prep``.

Under remat (``save_attention``) a block keeps the kernels' output and
logsumexp and the routed experts' weighted sum.

Scopes (obs/opscopes.py): module ``attn_mla`` and, inside it, the named scope
``mla_prep``; ``mlp``; ``moe_shared``; named scopes ``moe_route`` and,
inside it, ``moe_experts``; the norms ``input_layernorm``,
``post_attention_layernorm``, ``final_norm``; ``wte``. Custom calls in a
device trace: ``%attn_mla.N`` (two a layer), ``%gmm.N`` / ``%tgmm.N``
(megablox) and ``%moe_rows.N`` (ops/moe.py's row mover).
"""

from __future__ import annotations

from typing import Any

import flax.linen as nn
import jax
import jax.numpy as jnp

from nanosandbox_tpu.config import DeepseekV3Config
from nanosandbox_tpu.models import experts
from nanosandbox_tpu.models.common import _dense_init, constrain_acts
from nanosandbox_tpu.models.experts import STAT_NAMES, SwiGLU, rms_norm
from nanosandbox_tpu.ops import moe
from nanosandbox_tpu.ops.attention import causal_attention_mla, mla_route

ROUTE_EPS = 1e-20   # in the sum the selected scores are divided by


class Attention(nn.Module):
    """Named ``attn_mla`` by its block: the kernels' scope and the part the
    device trace files it under. a (B, T, d) in the compute type. The four
    matrices are leaves of this module, stored (in, out) with the published
    column order (a head's [nope | rope], [k_nope | v])."""
    cfg: DeepseekV3Config

    @nn.compact
    def __call__(self, a: jax.Array) -> jax.Array:
        cfg = self.cfg
        B, T, d = a.shape
        H, r = cfg.n_head, cfg.kv_lora_rank
        Dn, Dr, Dv = (cfg.qk_nope_head_dim, cfg.qk_rope_head_dim,
                      cfg.v_head_dim)
        dtype, pd = jnp.dtype(cfg.compute_dtype), jnp.dtype(cfg.param_dtype)
        leaf = lambda name, *shape: self.param(
            name, _dense_init(), shape, pd).astype(dtype)
        wq = leaf("q_proj", d, H * (Dn + Dr)).reshape(d, H, Dn + Dr)
        wkva = leaf("kv_a_proj_with_mqa", d, r + Dr)
        wkvb = leaf("kv_b_proj", r, H * (Dn + Dv)).reshape(r, H, Dn + Dv)
        wo = leaf("o_proj", H * Dv, d)

        q_nope = a @ wq[..., :Dn].reshape(d, H * Dn)
        q_pe = (a @ wq[..., Dn:].reshape(d, H * Dr)).reshape(B, T, H, Dr)
        ckv = a @ wkva
        with jax.named_scope("mla_prep"):
            c = rms_norm(cfg, "kv_a_layernorm")(ckv[..., :r]).astype(dtype)
            q_pe = experts.rotary(q_pe.astype(jnp.float32),
                                  cfg.rope_theta).astype(dtype)
            k_pe = experts.rotary(
                ckv[..., None, r:].astype(jnp.float32),
                cfg.rope_theta)[:, :, 0].astype(dtype)
        k_nope = c @ wkvb[..., :Dn].reshape(r, H * Dn)
        v = c @ wkvb[..., Dn:].reshape(r, H * Dv)
        o = causal_attention_mla(q_nope, q_pe, k_nope, k_pe, v, H,
                                 impl=cfg.attention_impl, scope=self.name)
        return o @ wo


class Moe(nn.Module):
    cfg: DeepseekV3Config

    @nn.compact
    def __call__(self, m: jax.Array):
        """m (B, T, d) float32 -> (f (B, T, d) float32, stats (3,) int32:
        STAT_NAMES): the routed experts held plus the shared expert, both
        models/experts.py's."""
        cfg = self.cfg
        routed, stats = experts.routed_experts(self, m, cfg,
                                               route_eps=ROUTE_EPS)
        return experts.shared_expert(
            cfg, cfg.n_shared_experts * cfg.moe_intermediate_size,
            m) + routed, stats


class Block(nn.Module):
    cfg: DeepseekV3Config
    layer: int

    @nn.compact
    def __call__(self, h: jax.Array):
        cfg = self.cfg
        dtype = jnp.dtype(cfg.compute_dtype)
        a = rms_norm(cfg, "input_layernorm")(h).astype(dtype)
        h = h + Attention(cfg, name="attn_mla")(a)
        m = rms_norm(cfg, "post_attention_layernorm")(h)
        if self.layer < cfg.num_dense_layers:
            f = SwiGLU(cfg, cfg.intermediate_size, name="mlp")(
                m.astype(dtype))
            stats = jnp.zeros((len(STAT_NAMES),), jnp.int32)
        else:
            f, stats = Moe(cfg, name="moe")(m)
        return h + f, stats


class DeepseekV3(nn.Module):
    cfg: DeepseekV3Config
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
        h = constrain_acts(self.mesh, wte(idx).astype(jnp.float32))
        h, aux = experts.decoder_layers(Block, cfg, self.mesh, h)
        h = rms_norm(cfg, "final_norm")(h)
        if return_hidden:
            return h, aux
        return jnp.einsum("btd,vd->btv", h.astype(cfg.param_dtype),
                          head), aux


# -- the family's answers to Trainer (models/__init__.py: FAMILIES) ----------

model_config = DeepseekV3Config.from_train_config

# What restore_for_inference misses for this family.
inference = (
    "a cache of the latent (kv_lora_rank + qk_rope_head_dim numbers a token "
    "and layer, not per-head keys and values) with its paged pool, a decode "
    "path that absorbs the key / value up-projection into the query and the "
    "output (attention over the latent itself), rotary positions at the "
    "cached offset, and a decode path through the routed experts")


def check(cfg, pretrained: bool) -> None:
    """What of a TrainConfig this family cannot run yet, refused by name
    instead of replicating or attending wrongly in silence."""
    if pretrained:
        raise ValueError("init_from loads GPT-2 weights; "
                         "model_family='deepseek_v3' starts from scratch")
    if cfg.q_lora_rank:
        raise NotImplementedError(
            f"model_family='deepseek_v3' with q_lora_rank={cfg.q_lora_rank}: "
            "the query latent (q_a_proj, q_a_layernorm, q_b_proj) is not "
            "built; models/deepseek_v3.py projects q in one product "
            "(q_lora_rank null, as Moonlight publishes)")
    if cfg.n_group > 1 or cfg.topk_group > 1:
        raise NotImplementedError(
            f"model_family='deepseek_v3' with n_group={cfg.n_group}, "
            f"topk_group={cfg.topk_group}: group-limited expert selection "
            "(the best topk_group of n_group groups by their two best "
            "scores, then top-k inside them) is not built; "
            "models/experts.route selects over all experts as one group")
    if cfg.mesh_sp > 1 or cfg.mesh_tp > 1:
        raise NotImplementedError(
            "model_family='deepseek_v3' runs on the data and fsdp axes "
            f"only (got seq={cfg.mesh_sp}, model={cfg.mesh_tp}). "
            "Missing for seq: ring attention with a split query/key head "
            "and one rotary key (ops/ring_attention.py walks one head size). "
            "Missing for model: a rule in parallel/sharding.py for the "
            "q / kv_a / kv_b / o projections and the expert matrices, and "
            "an expert axis with its exchange in parallel/mesh.py")


def build(cfg: DeepseekV3Config, mesh: Any):
    """(the model, what ``trainer_init`` records of it)."""
    # What a full batch resolves to, as the model will at trace time: the
    # attention entry and its backward (ops.attention.mla_route: the latent
    # kernels have ONE backward, the one-pass kernel), what brings the routed
    # experts' rows back to their tokens, and the grouped matmul's tiling at
    # the experts' shapes.
    d, F = cfg.n_embd, cfg.moe_intermediate_size
    route = mla_route(cfg.attention_impl, cfg.qk_nope_head_dim,
                      cfg.qk_rope_head_dim, cfg.v_head_dim, cfg.block_size,
                      jnp.dtype(cfg.compute_dtype).itemsize)
    return DeepseekV3(cfg, mesh=mesh), {
        "attn_layout": "bhtd" if route == "xla" else "btc-mla",
        "attn_route": route,
        "mla_bwd": "xla" if route == "xla" else "fused",
        "moe_row_mover": moe.resolve_row_mover("auto", cfg.block_size, d),
        "gmm_tiling": list(moe.gmm_tiling(moe.ROW_TILE, d, F)),
        "layer_types": ",".join(cfg.layer_types),
        "experts_held": list(cfg.experts_held)}


def apply(model: DeepseekV3, params, x: jax.Array, *, deterministic: bool,
          return_hidden: bool, rngs=None):
    """(logits or hidden, the expert layers' counters), as the model
    returns them."""
    return model.apply({"params": params}, x, deterministic=deterministic,
                       return_hidden=return_hidden, rngs=rngs)


def head(params) -> jax.Array:
    """The head's (vocab, d) table: the model's own, untied."""
    return params["lm_head"]


def flops_per_token(cfg: DeepseekV3Config, T: int, n_params: int) -> float:
    """Forward + backward operations a trained token requires here: 6 per
    parameter that multiplies it (the four attention matrices, the dense
    MLP, the router, the shared expert, the head; one routed expert for each
    of the k * count / E held slots a token has on average) plus full causal
    attention's (query, key) pairs over the split head: 2 * H * (Dn + Dr)
    for the score and 2 * H * Dv for the value a pair forward, twice that
    backward. Counted from the config: ``n_params`` is the hook's and not
    read."""
    d, H, r = cfg.n_embd, cfg.n_head, cfg.kv_lora_rank
    Dn, Dr, Dv = cfg.qk_nope_head_dim, cfg.qk_rope_head_dim, cfg.v_head_dim
    attn = (d * H * (Dn + Dr) + d * (r + Dr) + r * H * (Dn + Dv)
            + H * Dv * d)
    expert = 3 * d * cfg.moe_intermediate_size
    first, count = cfg.experts_held
    held = cfg.num_experts_per_tok * count / max(cfg.num_experts, 1)
    n_dense = cfg.num_dense_layers
    total = cfg.vocab_size * d  # the head; the embedding is a lookup
    total += cfg.n_layer * attn
    total += n_dense * 3 * d * cfg.intermediate_size
    total += (cfg.n_layer - n_dense) * (
        expert * (cfg.n_shared_experts + held) + d * cfg.num_experts)
    pairs = cfg.n_layer * (T + 1) / 2
    return 6.0 * total + 6.0 * H * (Dn + Dr + Dv) * pairs
