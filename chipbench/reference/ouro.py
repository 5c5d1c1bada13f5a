"""Plain reference for the ``ouro`` training cell (ByteDance's Ouro-2.6B
LoopLM).

float32 ``jax.numpy`` under ``default_matmul_precision("highest")``, no
kernels, no scan over the passes, nothing imported from the program. It
writes the equations of ISSUE 39 (the LoopLM paper, arXiv:2510.25741, as its
published modelling code reads) with the published config's keys
(d = hidden_size, H = num_attention_heads, G = num_key_value_heads,
D = head_dim, F = intermediate_size, L = total_ut_steps,
eps = rms_norm_eps):

    h^0 = Emb[ids]                                    (no scale)
    pass t = 1..L, the SAME layers and final norm every time:
      layer l:
        a = RMSNorm_in(h); q, k, v = a Wq, a Wk, a Wv
        q, k = rotary(q), rotary(k)           rotate-half, all D, rope_theta
        o = softmax(q k^T / sqrt(D) + causal) v    query head i reads KV head
                                                   i // (H // G)
        h = h + RMSNorm_post_attn(o Wo)
        h = h + RMSNorm_post_mlp((silu(m Wgate) * (m Wup)) Wdown),
            m = RMSNorm_pre_mlp(h)
      h^t = RMSNorm_f(h); h = h^t                 (the normed state goes on)
      z^t = h^t Head^T (untied);  lambda^t = sigmoid(h^t w_g + b_g)
    p_1 = lambda^1, p_t = lambda^t prod_{j<t} (1 - lambda^j) (1 < t < L),
    p_L = prod_{j<L} (1 - lambda^j)
    loss = mean over tokens of  sum_t p_t CE(z^t, y) + beta sum_t p_t log p_t

with beta the configuration's ``exit_entropy_weight`` (0.1, assumed). The
exit gate's product is float32 in the configuration and stays so in the
control.

Departures from the published model, each stated by the configuration:
``num_hidden_layers`` is the cut's (one pipeline stage's layers stand for the
stack; every pass runs them); nothing else.

So that three AdamW steps of 509.7 M parameters fit one chip once the
program's state is freed: every layer application is rematerialised,
attention runs one KV head's group of query heads at a time in blocks of
query rows, SwiGLUs in chunks of tokens, each exit's head and cross entropy
in chunks of positions, and the batch is one block. Its loss + gradient
program (described-chip compile, PR 39): 6.8 GB of temporaries beside 2.0 GB
of parameters, 2.0 GB of gradient and 4.1 GB of moments. A scan over the
layers (stacked weights) or over the passes would make it a tenth of its
1.1 GB of code, and take 14.4 / 11.8 GB of temporaries: it would not fit.

``quant`` is the hook of the control, as in ``reference/gpt2.py``: a round
trip through a lower precision on every operand of the matmuls the
configuration runs in bfloat16 (the projections, the SwiGLUs, the head,
attention's products). ``leave_out`` plants the faults
``chipbench/read_limits_ouro.py`` reads: 'pass' (one pass fewer: L - 1
exits), 'gate' (the exit gate ignored: p uniform over the exits), 'last'
(the loss of the last exit only), 'unnormed' (the state passed on to the
next pass before the final norm; the exits still read the normed one).
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp
from jax import lax

# What knows no family is the accepted references': the norm, the matmul
# with the control's hook, rotate-half rotary, the chunked SwiGLU, the AdamW
# step, and the block sizes.
from chipbench.reference.afmoe import (LOSS_CHUNK, Q_BLOCK,  # noqa: F401
                                       _ident, _mm, _rms_norm, _rotary,
                                       _swiglu, adamw_step, fp8_round_trip,
                                       global_norm)


def _attention(p, a, sizes, quant):
    """One KV head with the H // G query heads that read it at a time
    (their columns of Wq, Wk, Wv and rows of Wo), summed over KV heads."""
    B, T, d = a.shape
    H, G, D = sizes["n_head"], sizes["n_kv_head"], sizes["head_dim"]
    rep, theta = H // G, sizes["rope_theta"]
    qb = min(Q_BLOCK, T)
    assert T % qb == 0, (T, qb)
    k_pos = jnp.arange(T)[None, :]
    by_group = lambda w, n: jnp.moveaxis(w.reshape(d, G, n), 1, 0)

    @jax.checkpoint
    def group(y, w):
        wq, wk, wv, wo = w
        q = _rotary(_mm(a, wq, quant).reshape(B, T, rep, D), theta)
        k = _rotary(_mm(a, wk, quant).reshape(B, T, 1, D), theta)[:, :, 0]
        v = _mm(a, wv, quant)                              # (B, T, D)

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
        by_group(p["q_proj"]["kernel"], rep * D),
        by_group(p["k_proj"]["kernel"], D), by_group(p["v_proj"]["kernel"], D),
        p["o_proj"]["kernel"].reshape(G, rep * D, d)))
    return y


def _layer(h, p, sizes, quant):
    eps = sizes["rms_norm_eps"]
    a = _rms_norm(h, p["ln_in"]["scale"], eps)
    h = h + _rms_norm(_attention(p["attn_full"], a, sizes, quant),
                      p["ln_post_attn"]["scale"], eps)
    m = _rms_norm(h, p["ln_pre_mlp"]["scale"], eps)
    mlp = p["mlp"]
    f = _swiglu(m, mlp["gate_proj"]["kernel"], mlp["up_proj"]["kernel"],
                mlp["down_proj"]["kernel"], quant)
    return h + _rms_norm(f, p["ln_post_mlp"]["scale"], eps)


def passes(params, x, sizes, quant=_ident, leave_out=frozenset()):
    """([h^t (B, T, d)], [gate logit (B, T)]), one of each a pass, for ids
    x (B, T): the stack run L times (L - 1 under the fault 'pass')."""
    loop, eps = params["loop"], sizes["rms_norm_eps"]
    n_pass = sizes["total_ut_steps"] - ("pass" in leave_out)
    layer = jax.checkpoint(lambda h, p: _layer(h, p, sizes, quant))
    gate = loop["exit_gate"]
    h = params["wte"]["embedding"][x]
    states, logits = [], []
    for _ in range(n_pass):
        for i in range(sizes["n_layer"]):
            h = layer(h, loop[f"h_{i}"])
        normed = _rms_norm(h, loop["ln_f"]["scale"], eps)
        states.append(normed)
        logits.append((normed @ gate["kernel"])[..., 0] + gate["bias"][0])
        h = h if "unnormed" in leave_out else normed
    return states, logits


def token_nll(h, head, y, quant=_ident):
    """Cross entropy of each position, (B, T), of the head over h (B, T, d)
    against y (B, T), LOSS_CHUNK positions at a time."""
    B, T, d = h.shape
    cs = min(LOSS_CHUNK, T)
    assert T % cs == 0, (T, cs)

    @jax.checkpoint
    def chunk(_, hy):
        h_c, y_c = hy
        logits = _mm(h_c, head.T, quant)
        lse = jax.nn.logsumexp(logits, axis=-1)
        tgt = jnp.take_along_axis(logits, y_c[..., None], axis=-1)[..., 0]
        return None, lse - tgt

    _, nll = lax.scan(chunk, None, (
        jnp.moveaxis(h.reshape(B, T // cs, cs, d), 1, 0),
        jnp.moveaxis(y.reshape(B, T // cs, cs), 1, 0)))
    return jnp.moveaxis(nll, 0, 1).reshape(B, T)


def exit_probs(logits):
    """[p_t (B, T)] from the gates' logits, the products written out."""
    lam = [jax.nn.sigmoid(z) for z in logits]
    stay = jnp.ones_like(lam[0])
    p = []
    for t in range(len(lam) - 1):
        p.append(lam[t] * stay)
        stay = stay * (1.0 - lam[t])
    return p + [stay]


def loss_fn(params, x, y, sizes, quant=_ident, leave_out=frozenset()):
    """The mean over tokens of the expected cross entropy under the exit
    distribution less beta times its entropy."""
    states, logits = passes(params, x, sizes, quant, leave_out)
    nll = [token_nll(h, params["lm_head"], y, quant) for h in states]
    if "last" in leave_out:
        return jnp.mean(nll[-1])
    p = exit_probs(logits)
    if "gate" in leave_out:
        p = [jnp.full_like(q, 1.0 / len(p)) for q in p]
    expected = sum(q * n for q, n in zip(p, nll))
    entropy = -sum(q * jnp.log(q) for q in p)
    return jnp.mean(expected - sizes["exit_entropy_weight"] * entropy)


def loss_and_grad(params, x, y, sizes, quant=_ident, leave_out=frozenset()):
    """Loss and gradient of the whole batch, in one block."""
    return jax.value_and_grad(loss_fn)(params, x, y, sizes, quant, leave_out)
