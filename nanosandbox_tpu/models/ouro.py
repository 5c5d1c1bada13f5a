"""The ``ouro`` decoder (ByteDance's Ouro LoopLM; HF model_type ``ouro``) in
flax.linen, on the trainer's normal path beside models/gpt.py and the expert
families.

ONE stack of ``n_layer`` layers and its final norm run ``total_ut_steps``
times a step (L passes), the state each pass leaves going on to the next,
with an exit after every pass. A layer, with the published config's names
(d = n_embd, H = n_head, G = n_kv_head, D = head_dim, F = intermediate_size;
no biases, "sandwich" norms: four RMSNorms a layer):

    a = RMSNorm_in(h)
    q, k, v = a Wq, a Wk, a Wv;  q, k = rotary(q), rotary(k)  (rotate-half,
                                                  all D dims, rope_theta)
    h = h + RMSNorm_post_attn(softmax(q k^T / sqrt(D) + causal) v Wo)
    h = h + RMSNorm_post_mlp(SwiGLU_F(RMSNorm_pre_mlp(h)))

The loop and its exits:

    h^0 = Emb[x]                                 (no scale)
    h^t = RMSNorm_f(L_{n-1}(... L_0(h^{t-1})))   t = 1..L, the same layers
                                                 and norm on every pass; the
                                                 NORMED state goes on
    z^t = Head h^t                               (untied head)
    lambda^t = sigmoid(w_g . h^t + b_g)          (the exit gate, d -> 1)
    p_t = lambda^t prod_{j<t} (1 - lambda^j),  p_L = prod_{j<L} (1 - lambda^j)
    loss = mean over tokens of  sum_t p_t CE(z^t, y) - beta H(p)

Training always runs all L passes: ``early_exit_threshold`` governs inference
only. The loss over the exits is this module's ``exit_loss``: the one family
whose hidden states become a loss its own way (``Trainer._loss_fn`` asks the
module for it and runs the four heads' cross entropies under the scope
``exits``).

The loop is ONE ``nn.scan`` over the passes with the layers' parameters
broadcast (each weight's gradient is the sum of its L uses): the compiled
program holds one pass, its layers unrolled inside, each under
``remat_block`` where ``remat`` is on. A pass returns its normed state and
the gate's logit; the model stacks them, (L, B, T, d) and (L, B, T).

Precision: parameters ``param_dtype``; matmul inputs ``compute_dtype`` with
float32 accumulation; the residual stream, the norms, rotary positions and
the exit gate (a float32 matmul at full precision) in float32.

Attention is ops.attention.causal_attention_gqa on the projections'
(B, T, heads * D) layout (G = H here: a group of one). Its rotary positions
take the form the grouped-query kernels take (ops.attention.resolve_gqa_impl):
beside the kernels, ops.attention.qk_rotary, the attention prologue's kernel
without its norm (there is no q/k norm), one pass over the projection's
output each way (``%qk_prep.N``); on the XLA path models/experts.rotary, in
float32. Under remat (``save_attention``) a block keeps the kernels' output
and logsumexp.

Scopes (obs/opscopes.py): module ``attn_full`` (projections, rotary and the
flash kernels, ``%attn_full.N``), ``mlp``, the norms ``ln_*`` (``ln_f`` is
the loop's final norm), ``wte``; ``exit_gate`` and the trainer's named scope
``exits`` (the L heads with their cross entropies, the expected loss and the
entropy) are the part ``exits``.
"""

from __future__ import annotations

from typing import Any

import flax.linen as nn
import jax
import jax.numpy as jnp
from jax import lax

from nanosandbox_tpu.config import OuroConfig
from nanosandbox_tpu.models.common import (_dense_init, constrain_acts,
                                           remat_block)
from nanosandbox_tpu.models.experts import SwiGLU, dense, rms_norm, rotary
from nanosandbox_tpu.ops.attention import (causal_attention_gqa,
                                           gqa_route, qk_rotary,
                                           resolve_gqa_bwd, resolve_gqa_impl)

# The weight of the exit distribution's entropy in the loss (beta): the
# LoopLM paper's stage-one value; config.json does not state it (assumed).
EXIT_ENTROPY_WEIGHT = 0.1
# What a block under remat keeps: the attention kernels' output and
# logsumexp (ops/attention.py).
SAVED_NAMES = ("attn_out", "attn_lse")
# The counters Trainer leaves as an instant at log steps: {name: aux keys}.
INSTANTS = {"ouro_exits": ("exit_p", "exit_nll")}


class Attention(nn.Module):
    """Named ``attn_full`` by its block: the kernels' scope and the part the
    device trace files it under. a (B, T, d) in the compute type."""
    cfg: OuroConfig

    @nn.compact
    def __call__(self, a: jax.Array) -> jax.Array:
        cfg = self.cfg
        B, T, _ = a.shape
        H, G, D = cfg.n_head, cfg.n_kv_head, cfg.head_dim
        dtype = jnp.dtype(cfg.compute_dtype)
        impl = resolve_gqa_impl(cfg.attention_impl, D, T)

        def rotated(x, heads):
            if impl != "xla":
                return qk_rotary(x, heads, cfg.rope_theta,
                                 impl == "pallas_interpret")
            x = x.reshape(B, T, heads, D).astype(jnp.float32)
            return rotary(x, cfg.rope_theta).reshape(B, T, heads * D).astype(
                dtype)

        q = rotated(dense(cfg, H * D, "q_proj")(a), H)
        k = rotated(dense(cfg, G * D, "k_proj")(a), G)
        v = dense(cfg, G * D, "v_proj")(a)
        o = causal_attention_gqa(q, k, v, H, G, impl=cfg.attention_impl,
                                 scope=self.name)
        return dense(cfg, cfg.n_embd, "o_proj")(o)


class Block(nn.Module):
    cfg: OuroConfig

    @nn.compact
    def __call__(self, h: jax.Array) -> jax.Array:
        cfg = self.cfg
        dtype = jnp.dtype(cfg.compute_dtype)
        y = Attention(cfg, name="attn_full")(
            rms_norm(cfg, "ln_in")(h).astype(dtype))
        h = h + rms_norm(cfg, "ln_post_attn")(y)
        f = SwiGLU(cfg, cfg.intermediate_size, name="mlp")(
            rms_norm(cfg, "ln_pre_mlp")(h).astype(dtype))
        return h + rms_norm(cfg, "ln_post_mlp")(f)


class Pass(nn.Module):
    """One pass of the stack: the layers, the final norm and the exit gate.
    (h, _) -> (the normed state, (it, the gate's logit (B, T)))."""
    cfg: OuroConfig
    mesh: Any = None

    @nn.compact
    def __call__(self, h: jax.Array, _):
        cfg = self.cfg
        block = Block
        if cfg.remat:
            block = remat_block(Block, cfg.remat_policy, SAVED_NAMES,
                                static_argnums=())
        for i in range(cfg.n_layer):
            h = constrain_acts(self.mesh, block(cfg, name=f"h_{i}")(h))
        h = rms_norm(cfg, "ln_f")(h)
        z = nn.Dense(1, dtype=jnp.float32, param_dtype=cfg.param_dtype,
                     kernel_init=_dense_init(), precision=lax.Precision.HIGHEST,
                     name="exit_gate")(h)[..., 0]
        return h, (h, z)


class Ouro(nn.Module):
    cfg: OuroConfig
    mesh: Any = None

    @nn.compact
    def __call__(self, idx: jax.Array, *, deterministic: bool = True,
                 return_hidden: bool = False):
        """With return_hidden, (every pass's normed state (L, B, T, d)
        float32, {"exit_logits": the gate's logits (L, B, T)}) for the
        trainer's heads and ``exit_loss``; else (the last exit's logits
        (B, T, vocab), the same aux). The model has no dropout;
        ``deterministic`` is the trainer's call convention."""
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
        loop = nn.scan(Pass, variable_broadcast="params",
                       split_rngs={"params": False},
                       length=cfg.total_ut_steps)
        _, (hs, z) = loop(cfg, self.mesh, name="loop")(h, None)
        aux = {"exit_logits": z}
        if return_hidden:
            return hs, aux
        return jnp.einsum("btd,vd->btv", hs[-1].astype(cfg.param_dtype),
                          head), aux


def exit_log_probs(z: jax.Array) -> jax.Array:
    """log p_t (L, ...) of the exit distribution from the gate's logits z
    (L, ...): log lambda^t + sum_{j<t} log(1 - lambda^j) for t < L, and the
    remainder sum_{j<L} log(1 - lambda^j) on the last exit (whose own gate
    is not read). Log-sigmoids, so that no probability underflows."""
    log_exit = jax.nn.log_sigmoid(z)
    stayed = jax.nn.log_sigmoid(-z[:-1])
    before = jnp.cumsum(jnp.concatenate([jnp.zeros_like(z[:1]), stayed]),
                        axis=0)              # sum_{j<t} log(1 - lambda^j)
    return jnp.concatenate([log_exit[:-1] + before[:-1], before[-1:]])


def exit_loss(nll: jax.Array, aux: dict):
    """(loss, aux) from the exits' per-token cross entropies nll (L, B, T)
    float32 and the model's aux: the mean over tokens of the expected cross
    entropy under the exit distribution less EXIT_ENTROPY_WEIGHT times its
    entropy. The aux handed back has, in place of the gate's logits,
    ``exit_p`` (L,) (the mean exit probability of each pass) and
    ``exit_nll`` (L,) (each exit's mean cross entropy)."""
    log_p = exit_log_probs(aux["exit_logits"])
    p = jnp.exp(log_p)
    expected = jnp.mean(jnp.sum(p * nll, axis=0))
    entropy = jnp.mean(-jnp.sum(p * log_p, axis=0))
    rest = {k: v for k, v in aux.items() if k != "exit_logits"}
    return expected - EXIT_ENTROPY_WEIGHT * entropy, {
        **rest, "exit_p": p.mean(axis=(1, 2)),
        "exit_nll": nll.mean(axis=(1, 2))}


# -- the family's answers to Trainer (models/__init__.py: FAMILIES) ----------

model_config = OuroConfig.from_train_config

# What restore_for_inference misses for this family.
inference = (
    "one KV cache a pass of the stack (the same layers attend over what each "
    "pass cached) with its paged pool, the exit gate read at every pass and "
    "the early_exit_threshold that stops the loop, and a decode path that "
    "runs the passes a token needs")


def check(cfg, pretrained: bool) -> None:
    """What of a TrainConfig this family cannot run yet, refused by name
    instead of replicating or attending wrongly in silence."""
    if pretrained:
        raise ValueError("init_from loads GPT-2 weights; "
                         "model_family='ouro' starts from scratch")
    if cfg.mesh_sp > 1 or cfg.mesh_tp > 1:
        raise NotImplementedError(
            "model_family='ouro' runs on the data and fsdp axes "
            f"only (got seq={cfg.mesh_sp}, model={cfg.mesh_tp}). "
            "Missing for seq: ring attention inside the loop over passes "
            "and the exits' heads over a sequence shard. Missing for model: "
            "a rule in parallel/sharding.py for the q / k / v / o "
            "projections, the SwiGLU and the exit gate of the looped stack")


def build(cfg: OuroConfig, mesh: Any):
    """(the model, what ``trainer_init`` records of it)."""
    # What a full batch's attention resolves to, as the model will at trace
    # time: 'btc-gqa' (the grouped-query kernels on the projections' own
    # layout, a group of one) or 'xla', and their backward; the rotary
    # positions' form beside them ('pallas': qk_rotary).
    route = gqa_route(cfg.attention_impl, cfg.head_dim, cfg.block_size)
    return Ouro(cfg, mesh=mesh), {
        "attn_layout": "btc-gqa" if route == "btc-gqa" else "bhtd",
        "attn_route": route,
        "qk_prep": resolve_gqa_impl(cfg.attention_impl, cfg.head_dim,
                                    cfg.block_size),
        "gqa_bwd": resolve_gqa_bwd(cfg.attention_impl, cfg.head_dim,
                                   cfg.block_size,
                                   jnp.dtype(cfg.compute_dtype).itemsize),
        "loops": cfg.total_ut_steps, "layers_held": cfg.n_layer,
        "remat_policy": cfg.remat_policy if cfg.remat else "none"}


def apply(model: Ouro, params, x: jax.Array, *, deterministic: bool,
          return_hidden: bool, rngs=None):
    """(every pass's state or the last exit's logits, the gate's logits),
    as the model returns them."""
    return model.apply({"params": params}, x, deterministic=deterministic,
                       return_hidden=return_hidden, rngs=rngs)


def head(params) -> jax.Array:
    """The head's (vocab, d) table: the model's own, untied, read by every
    exit."""
    return params["lm_head"]


def flops_per_token(cfg: OuroConfig, T: int, n_params: int) -> float:
    """Forward + backward operations a trained token requires here: 6 per
    parameter APPLIED, so L times per pass (the layers' four projections and
    SwiGLU, the head, the exit gate), plus full causal attention's
    (query, key) pairs at 12 * H * D a pair, in every layer of every pass.
    Counted from the config: ``n_params`` is the hook's and not read."""
    d, H, G, D = cfg.n_embd, cfg.n_head, cfg.n_kv_head, cfg.head_dim
    layer = d * (2 * H * D + 2 * G * D) + 3 * d * cfg.intermediate_size
    per_pass = cfg.n_layer * layer + cfg.vocab_size * d + d
    pairs = cfg.n_layer * (T + 1) / 2
    return cfg.total_ut_steps * (6.0 * per_pass + 12.0 * H * D * pairs)
