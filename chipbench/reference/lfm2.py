"""Plain reference for the ``lfm2`` training cells (LiquidAI LFM2-8B-A1B).

float32 ``jax.numpy`` under ``default_matmul_precision("highest")``, no
kernels, no sort, no gather of rows, nothing imported from the program. It
follows HF ``transformers`` ``models/lfm2_moe`` with the published config's
keys (d = hidden_size, H = num_attention_heads, G = num_key_value_heads,
D = d / H, E = num_experts, k = num_experts_per_tok, L = conv_L_cache,
eps = norm_eps):

    h = Emb[ids]                                   (no scale)
    layer l, 'conv' or 'full' by layer_types[l], dense where
    l < num_dense_layers:
      a = RMSNorm_operator(h)
      conv:  [Bg | Cg | x] = a W_in;  u = Bg * x
             c[t] = sum_j w[:, j] * u[t - (L-1) + j]   (u = 0 before 0)
             o = (Cg * c) W_out
      full:  q, k, v = a Wq, a Wk, a Wv
             q, k = RMSNorm_q(q), RMSNorm_k(k)   over D, one scale each
             q, k = rotary(q), rotary(k)         theta, all D dims, rotate-half
             o = softmax(q k^T / sqrt(D) + causal) v Wo   query head i reads
                                                 KV head i // (H // G)
      h = h + o
      m = RMSNorm_ffn(h)
      dense:  f = (silu(m Wgate) * (m Wup)) Wdown
      expert: s = sigmoid(m Wr); sel = top_k(s + b); w = s[sel];
              w = routed_scaling_factor * w / (sum w + 1e-6)  (norm_topk_prob)
              f = sum_j w_j Expert_{sel_j}(m)
      h = h + f
    logits = RMSNorm_embedding(h) Emb^T (tied head); loss = mean cross entropy.

Departures from the HF modelling code, each stated by the configuration:

* ``experts_held`` = (first, count): the sum over a token's selected experts
  runs over those THIS chip holds; what the others would add is left out and
  the partial sum goes on (one chip's share of a 4-way expert-parallel job).
  The router still scores and selects over all E.
* The vocabulary is the chip's slice: the embedding (and so the head) has
  ``vocab_size`` rows as the configuration's file gives it, and the loss is
  over the slice.
* The selection bias b is a leaf no gradient reaches and no update moves
  (HF keeps it as a buffer that training code outside the model moves; the
  config gives no rule): it holds what the weights bring, zeros or
  ``balanced_bias``'s values. There is no auxiliary loss.
* HF computes the router in the model's dtype; here, as the configuration's
  ``precision`` says, the router is float32 whatever the matmuls run in.

Every held expert is evaluated on EVERY token and multiplied by the token's
weight for it (zero where the token did not select it): no sorting to get
wrong.

So that three AdamW steps of 613 M parameters (9.8 GB of parameters, moments
and gradient) fit one chip once the program's state is freed: layers are
rematerialised, attention runs one KV head's group of query heads at a time
and in blocks of query rows, position-wise SwiGLUs in chunks of tokens, the
held experts one at a time, the head and loss in chunks of positions, and the
batch is one block (no second gradient tree).

``balanced_bias`` makes the benchmark's selection bias from the seed's
weights and rows of the corpus, by this file's own float32 forward pass: the
values a job whose balancing rule has run for a while would hold.

``quant`` is the hook of the control, as in ``reference/gpt2.py``: a round
trip through a lower precision on every operand of the matmuls the
configuration runs in bfloat16 (projections, experts, head, attention's two
products). The router's matmul and the convolution's gates and taps are
float32 in the configuration and stay so in the control. ``leave_out`` plants
the faults ``chipbench/read_limits_lfm2.py`` reads: 'routed' (the routed
experts' sum left out), 'taps' (the convolution's earlier taps left out: L
read as 1), 'kv_mod' (query head i reads KV head i % G).
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp
from jax import lax

# What knows no family is the accepted references': the norm, the matmul
# with the control's hook, rotate-half rotary, the chunked SwiGLU, the fit of
# one layer's selection bias, the AdamW step, and the block sizes.
from chipbench.reference.afmoe import (LOSS_CHUNK, Q_BLOCK,  # noqa: F401
                                       _balance, _ident, _mm, _rms_norm,
                                       _rotary, _swiglu, adamw_step,
                                       fp8_round_trip, global_norm)

ROUTE_EPS = 1e-6     # in the sum the selected scores are divided by


def _short_conv(p, a, quant, leave_out):
    """The gated short convolution of one conv layer, a (B, T, d)."""
    bcx = _mm(a, p["in_proj"]["kernel"], quant)
    gate_in, gate_out, x = jnp.split(bcx, 3, axis=-1)   # Bg, Cg, x
    u = gate_in * x
    taps = p["filter"]                                  # (d, L)
    L = taps.shape[1]
    c = u * taps[:, L - 1]                              # the current position
    if "taps" not in leave_out:
        for back in range(1, L):
            before = jnp.concatenate(
                [jnp.zeros_like(u[:, :back]), u[:, :-back]], axis=1)
            c = c + before * taps[:, L - 1 - back]
    return _mm(gate_out * c, p["out_proj"]["kernel"], quant)


def _attention(p, a, sizes, quant, leave_out):
    """One KV head with the H // G query heads that read it at a time
    (their columns of Wq, Wk, Wv and rows of Wo), summed over KV heads."""
    B, T, d = a.shape
    H, G, D = sizes["n_head"], sizes["n_kv_head"], sizes["head_dim"]
    rep, eps, theta = H // G, sizes["rms_norm_eps"], sizes["rope_theta"]
    qb = min(Q_BLOCK, T)
    assert T % qb == 0, (T, qb)
    k_pos = jnp.arange(T)[None, :]
    if "kv_mod" in leave_out:   # the fault: head i reads KV head i % G
        q_heads = lambda w: jnp.moveaxis(
            w.reshape(d, rep, G, D), 2, 0).reshape(G, d, rep * D)
        o_heads = lambda w: jnp.moveaxis(
            w.reshape(rep, G, D, d), 1, 0).reshape(G, rep * D, d)
    else:                       # head i reads KV head i // rep
        q_heads = lambda w: jnp.moveaxis(w.reshape(d, G, rep * D), 1, 0)
        o_heads = lambda w: w.reshape(G, rep * D, d)
    kv_heads = lambda w: jnp.moveaxis(w.reshape(d, G, D), 1, 0)

    @jax.checkpoint
    def group(y, w):
        wq, wk, wv, wo = w
        q = _rms_norm(_mm(a, wq, quant).reshape(B, T, rep, D),
                      p["q_norm"]["scale"], eps)
        k = _rms_norm(_mm(a, wk, quant).reshape(B, T, 1, D),
                      p["k_norm"]["scale"], eps)
        v = _mm(a, wv, quant)                              # (B, T, D)
        q, k = _rotary(q, theta), _rotary(k, theta)[:, :, 0]

        @jax.checkpoint
        def block(start):
            qs = lax.dynamic_slice_in_dim(q, start, qb, axis=1)
            s = jnp.einsum("bqrd,bkd->brqk", quant(qs),
                           quant(k)) / math.sqrt(D)
            visible = k_pos <= start + jnp.arange(qb)[:, None]
            prob = jax.nn.softmax(jnp.where(visible, s, -jnp.inf), axis=-1)
            return jnp.einsum("brqk,bkd->bqrd", quant(prob), quant(v))

        o = lax.map(block, jnp.arange(0, T, qb))           # (T/qb, B, qb, ..)
        o = jnp.moveaxis(o, 0, 1).reshape(B, T, rep * D)
        return y + _mm(o, wo, quant), None

    y, _ = lax.scan(group, jnp.zeros_like(a), (
        q_heads(p["q_proj"]["kernel"]), kv_heads(p["k_proj"]["kernel"]),
        kv_heads(p["v_proj"]["kernel"]), o_heads(p["o_proj"]["kernel"])))
    return y


def route(x, w_router, bias, sizes):
    """(sel (N, k), w (N, k)) over all E experts; float32 in the control
    too. The bias b moves the selection and not the weights."""
    s = jax.nn.sigmoid(x @ w_router)
    _, sel = lax.top_k(s + lax.stop_gradient(bias),
                       sizes["num_experts_per_tok"])
    w = jnp.take_along_axis(s, sel, axis=1)
    if sizes["route_norm"]:
        w = w / (jnp.sum(w, axis=-1, keepdims=True) + ROUTE_EPS)
    return sel, w * sizes["route_scale"]


def _moe(p, m, sizes, quant, leave_out):
    B, T, d = m.shape
    x = m.reshape(B * T, d)
    if "routed" in leave_out:
        return jnp.zeros_like(m)
    first, count = sizes["experts_held"]
    sel, w = route(x, p["router"], p["expert_bias"], sizes)

    @jax.checkpoint
    def one(acc, ew):
        e, w_gate, w_up, w_down = ew
        mine = jnp.sum(jnp.where(sel == first + e, w, 0.0), axis=1)
        return acc + mine[:, None] * _swiglu(x, w_gate, w_up, w_down,
                                             quant), None

    routed, _ = lax.scan(one, jnp.zeros_like(x), (
        jnp.arange(count), p["w_gate"], p["w_up"], p["w_down"]))
    return routed.reshape(B, T, d)


def _mix(h, p, layer, sizes, quant, leave_out):
    """The layer's first half: h + the token mixer."""
    a = _rms_norm(h, p["operator_norm"]["scale"], sizes["rms_norm_eps"])
    if sizes["layer_types"][layer] == "conv":
        return h + _short_conv(p["conv"], a, quant, leave_out)
    return h + _attention(p["attn_full"], a, sizes, quant, leave_out)


def _feed_forward(h, p, layer, sizes, quant, leave_out):
    """The layer's second half: h + the MLP or the experts."""
    m = _rms_norm(h, p["ffn_norm"]["scale"], sizes["rms_norm_eps"])
    if layer < sizes["num_dense_layers"]:
        mlp = p["mlp"]
        return h + _swiglu(m, mlp["gate_proj"]["kernel"],
                           mlp["up_proj"]["kernel"],
                           mlp["down_proj"]["kernel"], quant)
    return h + _moe(p["moe"], m, sizes, quant, leave_out)


def _layer(h, p, layer, sizes, quant, leave_out):
    return _feed_forward(_mix(h, p, layer, sizes, quant, leave_out),
                         p, layer, sizes, quant, leave_out)


def hidden(params, x, sizes, quant=_ident, leave_out=frozenset()):
    """The final norm's output (B, T, d) for ids x (B, T)."""
    h = params["wte"]["embedding"][x]
    for i in range(sizes["n_layer"]):
        layer = jax.checkpoint(
            lambda h, p, i=i: _layer(h, p, i, sizes, quant, leave_out))
        h = layer(h, params[f"h_{i}"])
    return _rms_norm(h, params["embedding_norm"]["scale"],
                     sizes["rms_norm_eps"])


def balanced_bias(params, x, sizes, rows_per_block=2):
    """(bias, load), each (expert layers, E): the selection bias under which
    rows x (R, T) of ids route evenly over all E experts of every expert
    layer, whatever ``params`` hold as bias, and the loads it leaves there
    in even shares. One forward pass, layer by layer: an expert layer's bias
    is fitted to its scores of all R * T tokens (``_balance``) before the
    layer is applied with it, so that the next layer sees what it will see
    in a step. Each half of a layer is a program of ``rows_per_block`` rows,
    and all rows' activations are arrays between the programs: no program's
    temporaries grow with R."""
    R, T = x.shape
    d, k, eps = sizes["n_embd"], sizes["num_experts_per_tok"], sizes["rms_norm_eps"]
    dense, last = sizes["num_dense_layers"], sizes["n_layer"] - 1
    programs = {}

    def program(half, i):  # one trace a kind of layer, not one a layer
        kind = (half, sizes["layer_types"][i], i < dense)
        if kind not in programs:
            programs[kind] = jax.jit(
                lambda h, p: half(h, p, i, sizes, _ident, frozenset()))
        return programs[kind]

    embed = jax.jit(lambda table, ids: table[ids])
    scores = jax.jit(lambda h, p: jax.nn.sigmoid(
        _rms_norm(h, p["ffn_norm"]["scale"], eps).reshape(-1, d)
        @ p["moe"]["router"]))
    balance = jax.jit(lambda s: _balance(s, k))
    hs = [embed(params["wte"]["embedding"], x[r:r + rows_per_block])
          for r in range(0, R, rows_per_block)]
    out = []
    for i in range(sizes["n_layer"]):
        p = params[f"h_{i}"]
        hs = [program(_mix, i)(h, p) for h in hs]
        if i >= dense:
            out.append(balance(jnp.concatenate([scores(h, p) for h in hs])))
            p = {**p, "moe": {**p["moe"], "expert_bias": out[-1][0]}}
        if i < last:  # nothing reads the last layer's second half
            hs = [program(_feed_forward, i)(h, p) for h in hs]
    return tuple(jnp.stack(v) for v in zip(*out))


def logits_fn(params, x, sizes, quant=_ident, leave_out=frozenset()):
    return _mm(hidden(params, x, sizes, quant, leave_out),
               params["wte"]["embedding"].T, quant)


def loss_fn(params, x, y, sizes, quant=_ident, leave_out=frozenset()):
    """Mean next-token cross entropy of rows x (B, T) against y (B, T),
    the tied head and the loss computed LOSS_CHUNK positions at a time."""
    h = hidden(params, x, sizes, quant, leave_out)
    B, T, d = h.shape
    cs = min(LOSS_CHUNK, T)
    assert T % cs == 0, (T, cs)
    head = params["wte"]["embedding"]

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
