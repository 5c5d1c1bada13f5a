"""Plain reference for the ``deepseek_v3`` training cells (Moonshot's
Moonlight-16B-A3B).

float32 ``jax.numpy`` under ``default_matmul_precision("highest")``, no
kernels, no sort, no gather of rows, nothing imported from the program. It
follows HF ``transformers`` ``models/deepseek_v3`` with the published
config's keys (d = hidden_size, H = num_attention_heads, r = kv_lora_rank,
Dn = qk_nope_head_dim, Dr = qk_rope_head_dim, Dv = v_head_dim,
E = n_routed_experts, k = num_experts_per_tok, eps = rms_norm_eps):

    h = Emb[ids]                                   (no scale)
    layer l, dense where l < first_k_dense_replace:
      a = RMSNorm_input(h)
      q = a Wq                      (T, H, Dn + Dr) = [q_nope | q_pe] a head
      [c | k_pe] = a Wkva           (T, r + Dr); k_pe is ONE head
      [k_nope | v] = RMSNorm_kv(c) Wkvb          (T, H, Dn + Dv) a head
      q_pe, k_pe = rotary(q_pe), rotary(k_pe)    theta, over the Dr dims
      s_h = (q_nope_h k_nope_h^T + q_pe_h k_pe^T) / sqrt(Dn + Dr) + causal
      h = h + concat_h(softmax(s_h) v_h) Wo
      m = RMSNorm_post_attention(h)
      dense:  f = (silu(m Wgate) * (m Wup)) Wdown
      expert: s = sigmoid(m Wr); sel = top_k(s + b); w = s[sel];
              w = routed_scaling_factor * w / (sum w + 1e-20)
              f = Shared(m) + sum_j w_j Expert_{sel_j}(m)
      h = h + f
    logits = RMSNorm_final(h) Head^T (untied); loss = mean cross entropy.

Departures from the HF modelling code, each stated by the configuration:

* ``experts_held`` = (first, count): the sum over a token's selected experts
  runs over those THIS chip holds; what the others would add is left out and
  the partial sum goes on (one chip's share of an 8-way expert-parallel job).
  The router still scores and selects over all E; the shared expert is whole.
* The vocabulary is the chip's slice: embedding and head have ``vocab_size``
  rows as the configuration's file gives it, and the loss is over the slice.
* The selection bias b (``e_score_correction_bias``) is a leaf no gradient
  reaches and no update moves: it holds what the weights bring, zeros or
  ``balanced_bias``'s values. There is no auxiliary loss (``seq_aux``).
* Rotary positions are rotate-half on the Dr columns AS THEY LIE. HF first
  de-interleaves them (pairs (2i, 2i+1) into halves): one fixed permutation
  of Wq's and Wkva's rotary columns, the same for q_pe and k_pe, which leaves
  every score as it is. With weights drawn from a seed the two are one
  computation; the program does the same.
* ``n_group = topk_group = 1`` as published: ``noaux_tc``'s group-limited
  selection is a plain top-k. ``q_lora_rank`` is null: q is one projection.

Every held expert is evaluated on EVERY token and multiplied by the token's
weight for it (zero where the token did not select it): no sorting to get
wrong. The expert layer (shared expert, router, held experts) is the
accepted ``reference/afmoe._moe``: it names no family, the shared expert's
width is its weights'.

So that three AdamW steps of 669 M parameters (8.0 GB of parameters and
moments, 2.7 GB of gradient) fit one chip once the program's state is freed:
layers are rematerialised, attention runs one head at a time (its columns of
Wq and Wkvb, its rows of Wo; the latent and the rotary key once a layer) and
in blocks of query rows, position-wise SwiGLUs in chunks of tokens, the held
experts one at a time, the head and loss in chunks of positions, and the
batch is one block (no second gradient tree).

``quant`` is the hook of the control, as in ``reference/gpt2.py``: a round
trip through a lower precision on every operand of the matmuls the
configuration runs in bfloat16 (the projections, experts, head, attention's
products). The router's matmul is float32 in the configuration and stays so
in the control. ``leave_out`` plants the faults
``chipbench/read_limits_dsv3.py`` reads: 'routed' (the routed experts' sum
left out), 'rope' (the rotary term of the score left out: s = q_nope
k_nope^T alone), 'scale' (the score scaled by Dn ** -0.5, not
(Dn + Dr) ** -0.5).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax

# What knows no family is the accepted references': the norm, the matmul
# with the control's hook, rotate-half rotary, the chunked SwiGLU, the expert
# layer (shared + held routed experts, 1e-20 in the weights' sum), the fit of
# one layer's selection bias, the AdamW step, and the block sizes.
from chipbench.reference.afmoe import (LOSS_CHUNK, Q_BLOCK,  # noqa: F401
                                       _balance, _ident, _mm, _moe,
                                       _rms_norm, _rotary, _swiglu,
                                       adamw_step, fp8_round_trip,
                                       global_norm)


def _attention(p, a, sizes, quant, leave_out):
    """One head at a time (its columns of Wq and Wkvb, its rows of Wo),
    summed over heads; the latent and the one rotary key once."""
    B, T, d = a.shape
    H, r = sizes["n_head"], sizes["kv_lora_rank"]
    Dn, Dr, Dv = (sizes["qk_nope_head_dim"], sizes["qk_rope_head_dim"],
                  sizes["v_head_dim"])
    theta = sizes["rope_theta"]
    scale = (Dn if "scale" in leave_out else Dn + Dr) ** -0.5
    qb = min(Q_BLOCK, T)
    assert T % qb == 0, (T, qb)
    k_pos = jnp.arange(T)[None, :]
    ckv = _mm(a, p["kv_a_proj_with_mqa"], quant)
    c = _rms_norm(ckv[..., :r], p["kv_a_layernorm"]["scale"],
                  sizes["rms_norm_eps"])
    k_pe = _rotary(ckv[..., None, r:], theta)[:, :, 0]      # (B, T, Dr)
    by_head = lambda w, n: jnp.moveaxis(w.reshape(w.shape[0], H, n), 1, 0)

    @jax.checkpoint
    def head(y, w):
        wq, wkvb, wo = w
        q = _mm(a, wq, quant)                               # (B, T, Dn + Dr)
        q_nope = q[..., :Dn]
        q_pe = _rotary(q[..., None, Dn:], theta)[:, :, 0]
        kv = _mm(c, wkvb, quant)
        k_nope, v = kv[..., :Dn], kv[..., Dn:]

        @jax.checkpoint
        def block(start):
            rows = lambda x: lax.dynamic_slice_in_dim(x, start, qb, axis=1)
            s = jnp.einsum("bqd,bkd->bqk", quant(rows(q_nope)), quant(k_nope))
            if "rope" not in leave_out:
                s = s + jnp.einsum("bqd,bkd->bqk", quant(rows(q_pe)),
                                   quant(k_pe))
            visible = k_pos <= start + jnp.arange(qb)[:, None]
            prob = jax.nn.softmax(jnp.where(visible, s * scale, -jnp.inf),
                                  axis=-1)
            return jnp.einsum("bqk,bkd->bqd", quant(prob), quant(v))

        o = lax.map(block, jnp.arange(0, T, qb))            # (T/qb, B, qb, Dv)
        o = jnp.moveaxis(o, 0, 1).reshape(B, T, Dv)
        return y + _mm(o, wo, quant), None

    y, _ = lax.scan(head, jnp.zeros_like(a), (
        by_head(p["q_proj"], Dn + Dr), by_head(p["kv_b_proj"], Dn + Dv),
        p["o_proj"].reshape(H, Dv, d)))
    return y


def _attend(h, p, layer, sizes, quant, leave_out):
    """The layer's first half: h + attention."""
    a = _rms_norm(h, p["input_layernorm"]["scale"], sizes["rms_norm_eps"])
    return h + _attention(p["attn_mla"], a, sizes, quant, leave_out)


def _feed_forward(h, p, layer, sizes, quant, leave_out):
    """The layer's second half: h + the MLP or the experts."""
    m = _rms_norm(h, p["post_attention_layernorm"]["scale"],
                  sizes["rms_norm_eps"])
    if layer < sizes["num_dense_layers"]:
        mlp = p["mlp"]
        return h + _swiglu(m, mlp["gate_proj"]["kernel"],
                           mlp["up_proj"]["kernel"],
                           mlp["down_proj"]["kernel"], quant)
    return h + _moe(p["moe"], m, sizes, quant, leave_out)


def _layer(h, p, layer, sizes, quant, leave_out):
    return _feed_forward(_attend(h, p, layer, sizes, quant, leave_out),
                         p, layer, sizes, quant, leave_out)


def hidden(params, x, sizes, quant=_ident, leave_out=frozenset()):
    """The final norm's output (B, T, d) for ids x (B, T)."""
    h = params["wte"]["embedding"][x]
    for i in range(sizes["n_layer"]):
        layer = jax.checkpoint(
            lambda h, p, i=i: _layer(h, p, i, sizes, quant, leave_out))
        h = layer(h, params[f"h_{i}"])
    return _rms_norm(h, params["final_norm"]["scale"], sizes["rms_norm_eps"])


def balanced_bias(params, x, sizes, rows_per_block=2):
    """(bias, load), each (expert layers, E): the selection bias under which
    rows x (R, T) of ids route evenly over all E experts of every expert
    layer, whatever ``params`` hold as bias, and the loads it leaves there
    in even shares. One forward pass, layer by layer, as
    ``reference/afmoe.balanced_bias``: an expert layer's bias is fitted to
    its scores of all R * T tokens (``_balance``) before the layer is applied
    with it. Each half of a layer is a program of ``rows_per_block`` rows, and
    a block's activations are replaced as soon as its program has run: what
    is held is ONE copy of all rows' activations (134 MB a block of 2 x 8192
    at d 2048), not the layer's inputs and outputs side by side."""
    R, T = x.shape
    d, k, eps = sizes["n_embd"], sizes["num_experts_per_tok"], sizes["rms_norm_eps"]
    dense, last = sizes["num_dense_layers"], sizes["n_layer"] - 1
    programs = {}

    def program(half, i):  # one trace a kind of layer, not one a layer
        kind = (half, i < dense)
        if kind not in programs:
            programs[kind] = jax.jit(
                lambda h, p: half(h, p, i, sizes, _ident, frozenset()))
        return programs[kind]

    embed = jax.jit(lambda table, ids: table[ids])
    scores = jax.jit(lambda h, p: jax.nn.sigmoid(
        _rms_norm(h, p["post_attention_layernorm"]["scale"],
                  eps).reshape(-1, d) @ p["moe"]["router"]))
    balance = jax.jit(lambda s: _balance(s, k))
    hs = [embed(params["wte"]["embedding"], x[r:r + rows_per_block])
          for r in range(0, R, rows_per_block)]
    out = []

    def advance(half, p):  # every block through one half of a layer, in place
        for j, h in enumerate(hs):
            hs[j] = jax.block_until_ready(half(h, p))

    for i in range(sizes["n_layer"]):
        p = params[f"h_{i}"]
        advance(program(_attend, i), p)
        if i >= dense:
            out.append(balance(jnp.concatenate([scores(h, p) for h in hs])))
            p = {**p, "moe": {**p["moe"], "expert_bias": out[-1][0]}}
        if i < last:  # nothing reads the last layer's second half
            advance(program(_feed_forward, i), p)
    return tuple(jnp.stack(v) for v in zip(*out))


def logits_fn(params, x, sizes, quant=_ident, leave_out=frozenset()):
    return _mm(hidden(params, x, sizes, quant, leave_out),
               params["lm_head"].T, quant)


def loss_fn(params, x, y, sizes, quant=_ident, leave_out=frozenset()):
    """Mean next-token cross entropy of rows x (B, T) against y (B, T),
    the head and the loss computed LOSS_CHUNK positions at a time."""
    h = hidden(params, x, sizes, quant, leave_out)
    B, T, d = h.shape
    cs = min(LOSS_CHUNK, T)
    assert T % cs == 0, (T, cs)
    head = params["lm_head"]

    @jax.checkpoint
    def chunk(total, hy):
        h_c, y_c = hy
        logits = _mm(h_c, head.T, quant)
        lse = jax.nn.logsumexp(logits, axis=-1)
        tgt = jnp.take_along_axis(logits, y_c[..., None], axis=-1)[..., 0]
        return total + jnp.sum(lse - tgt), None

    total, _ = lax.scan(chunk, jnp.zeros((), jnp.float32), (
        jnp.moveaxis(h.reshape(B, T // cs, cs, d), 1, 0),
        jnp.moveaxis(y.reshape(B, T // cs, cs), 1, 0)))
    return total / (B * T)


def loss_and_grad(params, x, y, sizes, quant=_ident, leave_out=frozenset()):
    """Loss and gradient of the whole batch, in one block."""
    return jax.value_and_grad(loss_fn)(params, x, y, sizes, quant, leave_out)
