"""Plain reference for the ``granite`` training cell (IBM's Granite 4.0-H
Micro: Mamba-2 state-space layers beside grouped-query attention layers with
no positions).

float32 ``jax.numpy`` under ``default_matmul_precision("highest")``, no
kernels, nothing imported from the program. It writes the equations of HF
``transformers`` ``models/granitemoehybrid`` (no experts) with the published
config's keys (d = hidden_size; H, G, D = num_attention_heads,
num_key_value_heads, d / H; Hm = mamba_n_heads, P = mamba_d_head,
inner = Hm P, N = mamba_d_state, Gm = mamba_n_groups, K = mamba_d_conv;
eps = rms_norm_eps):

    h = embedding_multiplier * Emb[ids]
    layer l, 'mamba' or 'attention' by layer_types[l]:
      a = RMSNorm_in(h)
      attention: q, k, v = a Wq, a Wk, a Wv (no bias, no positions);
                 o = softmax(attention_multiplier q k^T + causal) v Wo,
                 query head i reading KV head i // (H // G)
      mamba:     [z | xBC | dt] = a W_in;  xBC = silu(conv_K(xBC) + b),
                 taps t-K+1..t, zeros before 0;  x, B, C = split(xBC);
                 dt = softplus(dt + dt_bias);  A = -exp(A_log)
                 S_t = exp(dt_t A) S_{t-1} + dt_t x_t (x) B_t  per head,
                 S_{-1} = 0;  y_t = S_t C_t + D x_t          TOKEN BY TOKEN
                 y = RMSNorm_{inner / Gm}(y * silu(z)) * w;  o = y W_out
      h = h + residual_multiplier * o
      h = h + residual_multiplier * (silu(m Wgate) * (m Wup)) Wdown,
          m = RMSNorm_post(h)
    logits = RMSNorm_f(h) Emb^T / logits_scaling;  loss = mean cross entropy

The scan is the recurrence itself, one token at a time, not the chunked
form the program computes: the two share nothing but the equations. A Mamba
layer runs over segments of SEGMENT tokens, carrying its state and its
conv's last K - 1 inputs from one to the next, each segment under
``jax.checkpoint``: a step at 8192 tokens keeps one segment's states and
activations at a time.

Departures from the published model, each stated by the configuration:
``num_hidden_layers`` (one period of the layer pattern stands for the stack)
and ``vocab_size`` (the chip's quarter of the tied table; the loss over it).

So that three AdamW steps of 797.9 M parameters fit one chip once the
program's state is freed: every layer is rematerialised, attention runs one
KV head's group of query heads at a time in blocks of query rows, the shared
MLPs in chunks of tokens, the head and loss in chunks of positions, and the
batch is one block.

``quant`` is the hook of the control, as in ``reference/gpt2.py``: a round
trip through a lower precision on every operand of the products the
configuration runs in bfloat16 (the projections, the shared MLPs, the head,
attention's products and the scan's: dt x, B, C and the state that meets
C). ``leave_out`` plants the faults ``chipbench/read_limits_granite.py``
reads: 'reset' (the state reset to zero at every chunk boundary), 'decay'
(A = 0: the state never decays), 'gate' (the gate left out of the gated
norm), 'scale' (attention's score scaled by D^-0.5 in place of
attention_multiplier).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax

# What knows no family is the accepted references': the norm, the matmul
# with the control's hook, the chunked SwiGLU, the AdamW step, the blocks.
from chipbench.reference.afmoe import (LOSS_CHUNK, Q_BLOCK,  # noqa: F401
                                       _ident, _mm, _rms_norm, _swiglu,
                                       adamw_step, fp8_round_trip,
                                       global_norm)

SEGMENT = 128        # tokens a checkpointed segment of the recurrence


def _attention(p, a, sizes, quant, leave_out):
    """One KV head with the H // G query heads that read it at a time
    (their columns of Wq, Wk, Wv and rows of Wo), summed over KV heads."""
    B, T, d = a.shape
    H, G, D = sizes["n_head"], sizes["n_kv_head"], sizes["head_dim"]
    rep = H // G
    scale = (D ** -0.5 if "scale" in leave_out
             else sizes["attention_multiplier"])
    qb = min(Q_BLOCK, T)
    assert T % qb == 0, (T, qb)
    k_pos = jnp.arange(T)[None, :]
    by_group = lambda w, n: jnp.moveaxis(w.reshape(d, G, n), 1, 0)

    @jax.checkpoint
    def group(y, w):
        wq, wk, wv, wo = w
        q = _mm(a, wq, quant).reshape(B, T, rep, D)
        k = _mm(a, wk, quant)                              # (B, T, D)
        v = _mm(a, wv, quant)

        @jax.checkpoint
        def block(start):
            qs = lax.dynamic_slice_in_dim(q, start, qb, axis=1)
            s = jnp.einsum("bqrd,bkd->brqk", quant(qs), quant(k)) * scale
            visible = k_pos <= start + jnp.arange(qb)[:, None]
            prob = jax.nn.softmax(jnp.where(visible, s, -jnp.inf), axis=-1)
            return jnp.einsum("brqk,bkd->bqrd", quant(prob), quant(v))

        o = lax.map(block, jnp.arange(0, T, qb))           # (T/qb, B, qb, ..)
        o = jnp.moveaxis(o, 0, 1).reshape(B, T, rep * D)
        return y + _mm(o, wo, quant), None

    y, _ = lax.scan(group, jnp.zeros_like(a), (
        by_group(p["q_proj"]["kernel"], rep * D),
        by_group(p["k_proj"]["kernel"], D), by_group(p["v_proj"]["kernel"], D),
        p["o_proj"]["kernel"].reshape(G, rep * D, d)))
    return y


def _tokens(S, x, dt, A, B, C, D, t, sizes, quant, leave_out):
    """(S, y (n, b, Hm, P)) after tokens t (n,) from state S (b, Hm, P, N):
    x (n, b, Hm, P), dt (n, b, Hm), B / C (n, b, Gm, N); one token a step."""
    Hm, Gm = sizes["mamba_n_heads"], sizes["mamba_n_groups"]
    heads = lambda v: jnp.repeat(v, Hm // Gm, axis=1)  # a token's B / C
    if "decay" in leave_out:
        A = jnp.zeros_like(A)

    def token(S, inp):
        xt, dtt, bt, ct, tt = inp
        if "reset" in leave_out:
            S = jnp.where(tt % sizes["mamba_chunk_size"] == 0, 0.0, S)
        S = (jnp.exp(dtt * A)[..., None, None] * S
             + quant(dtt[..., None] * xt)[..., None]
             * quant(heads(bt))[:, :, None])
        y = jnp.einsum("bhpn,bhn->bhp", quant(S), quant(heads(ct)))
        return S, y + D[:, None] * xt

    return lax.scan(token, S, (x, dt, B, C, t))


def _by_segment(v, seg):
    """(b, T, ...) -> (T / seg, seg, b, ...): segments, tokens first."""
    b, T = v.shape[:2]
    return jnp.moveaxis(v.reshape(b, T // seg, seg, *v.shape[2:]), 0, 2)


def recurrence(x, dt, A, B, C, D, sizes, quant=_ident, leave_out=frozenset()):
    """y (b, T, Hm P) from x (b, T, Hm P), dt (b, T, Hm), A / D (Hm,),
    B / C (b, T, Gm N): the state updated token by token, float32, in
    checkpointed segments of SEGMENT tokens."""
    b, T, _ = x.shape
    Hm, P = sizes["mamba_n_heads"], sizes["mamba_d_head"]
    Gm, N = sizes["mamba_n_groups"], sizes["mamba_d_state"]
    seg = min(SEGMENT, T)
    assert T % seg == 0, (T, seg)

    @jax.checkpoint
    def segment(S, inp):
        return _tokens(S, *inp[:2], A, *inp[2:4], D, inp[4], sizes, quant,
                       leave_out)

    xs = (_by_segment(x.reshape(b, T, Hm, P), seg), _by_segment(dt, seg),
          _by_segment(B.reshape(b, T, Gm, N), seg),
          _by_segment(C.reshape(b, T, Gm, N), seg),
          jnp.arange(T).reshape(T // seg, seg))
    _, y = lax.scan(segment, jnp.zeros((b, Hm, P, N), jnp.float32), xs)
    return jnp.moveaxis(y, 2, 0).reshape(b, T, Hm * P)


def _mamba(p, a, sizes, quant, leave_out):
    """The Mamba mixer over a (b, T, d), SEGMENT tokens at a time: the
    recurrent layer carries its state and the conv's last K - 1 inputs from
    one segment to the next, each segment checkpointed, so that no program
    holds a (T, inner) array."""
    b, T, _ = a.shape
    Hm, P = sizes["mamba_n_heads"], sizes["mamba_d_head"]
    Gm, N = sizes["mamba_n_groups"], sizes["mamba_d_state"]
    inner, K = Hm * P, sizes["mamba_d_conv"]
    width = inner + 2 * Gm * N
    seg = min(SEGMENT, T)
    assert T % seg == 0, (T, seg)
    A = -jnp.exp(p["A_log"])

    @jax.checkpoint
    def segment(carry, inp):
        S, tail = carry                       # tail: (b, K - 1, width)
        a_s, t = inp                          # (b, seg, d), (seg,)
        zxbcdt = _mm(a_s, p["in_proj"]["kernel"], quant)
        z, xbc, dt = (zxbcdt[..., :inner], zxbcdt[..., inner:inner + width],
                      zxbcdt[..., inner + width:])
        both = jnp.concatenate([tail, xbc], axis=1)
        conv = sum(both[:, j:j + seg] * p["conv_weight"][:, j]
                   for j in range(K)) + p["conv_bias"]
        xbc_act = jax.nn.silu(conv)
        x, B, C = (xbc_act[..., :inner], xbc_act[..., inner:inner + Gm * N],
                   xbc_act[..., inner + Gm * N:])
        tokens = lambda v: jnp.moveaxis(v, 1, 0)
        S, y = _tokens(S, tokens(x.reshape(b, seg, Hm, P)),
                       tokens(jax.nn.softplus(dt + p["dt_bias"])), A,
                       tokens(B.reshape(b, seg, Gm, N)),
                       tokens(C.reshape(b, seg, Gm, N)), p["D"], t, sizes,
                       quant, leave_out)
        y = jnp.moveaxis(y, 0, 1).reshape(b, seg, inner)
        g = y if "gate" in leave_out else y * jax.nn.silu(z)
        g = g.reshape(b, seg, Gm, inner // Gm)
        g = g * lax.rsqrt(jnp.mean(g * g, axis=-1, keepdims=True)
                          + sizes["rms_norm_eps"])
        g = g.reshape(b, seg, inner) * p["norm"]["scale"]
        return (S, both[:, seg:]), _mm(g, p["out_proj"]["kernel"], quant)

    init = (jnp.zeros((b, Hm, P, N), jnp.float32),
            jnp.zeros((b, K - 1, width), jnp.float32))
    _, out = lax.scan(segment, init, (
        jnp.moveaxis(a.reshape(b, T // seg, seg, -1), 1, 0),
        jnp.arange(T).reshape(T // seg, seg)))
    return jnp.moveaxis(out, 0, 1).reshape(b, T, -1)


def _layer(h, p, kind, sizes, quant, leave_out):
    eps, rm = sizes["rms_norm_eps"], sizes["residual_multiplier"]
    a = _rms_norm(h, p["input_layernorm"]["scale"], eps)
    if kind == "mamba":
        o = _mamba(p["mamba"], a, sizes, quant, leave_out)
    else:
        o = _attention(p["attn_full"], a, sizes, quant, leave_out)
    h = h + rm * o
    m = _rms_norm(h, p["post_attention_layernorm"]["scale"], eps)
    mlp = p["mlp"]
    return h + rm * _swiglu(m, mlp["gate_proj"]["kernel"],
                            mlp["up_proj"]["kernel"],
                            mlp["down_proj"]["kernel"], quant)


def hidden(params, x, sizes, quant=_ident, leave_out=frozenset()):
    """The final norm's output (B, T, d) for ids x (B, T)."""
    h = params["wte"]["embedding"][x] * sizes["embedding_multiplier"]
    for i, kind in enumerate(sizes["layer_types"]):
        layer = jax.checkpoint(
            lambda h, p, kind=kind: _layer(h, p, kind, sizes, quant,
                                           leave_out))
        h = layer(h, params[f"h_{i}"])
    return _rms_norm(h, params["final_norm"]["scale"], sizes["rms_norm_eps"])


def loss_fn(params, x, y, sizes, quant=_ident, leave_out=frozenset()):
    """Mean next-token cross entropy of rows x (B, T) against y (B, T), the
    tied head divided by logits_scaling, LOSS_CHUNK positions at a time."""
    h = hidden(params, x, sizes, quant, leave_out)
    B, T, d = h.shape
    cs = min(LOSS_CHUNK, T)
    assert T % cs == 0, (T, cs)
    table = params["wte"]["embedding"]

    @jax.checkpoint
    def chunk(total, hy):
        h_c, y_c = hy
        logits = _mm(h_c, table.T, quant) / sizes["logits_scaling"]
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
