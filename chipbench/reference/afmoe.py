"""Plain reference for the ``afmoe`` training cells (Arcee Trinity-Mini).

float32 ``jax.numpy`` under ``default_matmul_precision("highest")``, no
kernels, no sort, no gather of rows, nothing imported from the program. It
follows HF ``transformers`` ``models/afmoe`` with the published config's keys
(d = hidden_size, H = num_attention_heads, G = num_key_value_heads,
D = head_dim, W = sliding_window, E = num_experts, k = num_experts_per_tok):

    h = Emb[ids] * sqrt(d)                                   (mup_enabled)
    layer l, 'sliding' or 'full' by layer_types[l], dense where
    l < num_dense_layers:
      a = RMSNorm_in(h); q, k, v, g = a Wq, a Wk, a Wv, a Wg
      q, k = RMSNorm_q(q), RMSNorm_k(k)      over D, one scale each
      sliding only: rotary positions on q, k (theta, all D dims, rotate-half)
      o = softmax(q k^T / sqrt(D) + mask) v  query head i reads KV head
                                             i // (H // G); key j visible iff
                                             j <= i (sliding: and i - j < W)
      h = h + RMSNorm_post_attn((o * sigmoid(g)) Wo)
      m = RMSNorm_pre_mlp(h)
      dense:  f = (silu(m Wgate) * (m Wup)) Wdown
      expert: s = sigmoid(m Wr); sel = top_k(s + b); w = s[sel];
              w = route_scale * w / (sum w + 1e-20)          (route_norm)
              f = Shared(m) + sum_j w_j Expert_{sel_j}(m)
      h = h + RMSNorm_post_mlp(f)
    logits = RMSNorm_f(h) W_head^T; loss = mean cross entropy.

Departures from the published description, each stated by the configuration:

* ``experts_held`` = (first, count): the sum over a token's selected experts
  runs over those THIS chip holds; what the others would add is left out and
  the partial sum goes on (one chip's share of an 8-way expert-parallel job).
  The router still scores and selects over all E.
* The vocabulary is the chip's slice: embedding and head have
  ``vocab_size`` rows as the configuration's file gives it, and the loss is
  over the slice.
* The selection bias b is a leaf no gradient reaches and no update moves
  (the published one is moved by a load-balancing rule outside the forward
  pass that the config does not specify): it holds what the weights bring,
  zeros or ``balanced_bias``'s values. There is no auxiliary loss.

Every held expert is evaluated on EVERY token and multiplied by the token's
weight for it (zero where the token did not select it): count / (k * count /
E) times the work, and no sorting to get wrong.

So that three AdamW steps of 705.5 M parameters (11.3 GB of parameters,
moments and gradient) fit one chip once the program's state is freed:
layers are rematerialised, attention runs one KV head's group of query heads
at a time and in blocks of query rows, position-wise SwiGLUs in chunks of
tokens, the held
experts one at a time, the head and loss in chunks of positions, and the
batch is one block (no second gradient tree).

``balanced_bias`` makes the benchmark's selection bias from the seed's
weights and rows of the corpus, by this file's own float32 forward pass: the
values a job whose balancing rule has run for a while would hold.

``quant`` is the hook of the control, as in ``reference/gpt2.py``: a round
trip through a lower precision on every operand of the matmuls the
configuration runs in bfloat16. The router's matmul is float32 in the
configuration and stays so in the control. ``leave_out`` plants the faults
``chipbench/read_limits_afmoe.py`` reads: 'routed' (the routed experts' sum
left out), 'window' (the window ignored in sliding layers).
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp
from jax import lax

from chipbench.reference.gpt2 import (_ident, fp8_round_trip,  # noqa: F401
                                      global_norm, learning_rate)

Q_BLOCK = 128        # query rows a block of attention
MLP_CHUNK = 4096     # tokens a chunk of a position-wise SwiGLU
LOSS_CHUNK = 1024    # positions a chunk of head + loss
# balanced_bias: updates of a layer's bias, and their first and last step
# (in units of score, which lies in (0, 1)), shrinking geometrically.
BALANCE_UPDATES, BALANCE_STEP = 32, (0.1, 0.003)


def _rms_norm(x, scale, eps):
    return x * lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * scale


def _mm(x, w, quant):
    return quant(x) @ quant(w)


def _rotary(x, theta):
    """x (B, T, heads, D): positions 0..T-1, rotate-half over all D."""
    T, D = x.shape[1], x.shape[-1]
    inv_freq = theta ** (-jnp.arange(0, D, 2, dtype=jnp.float32) / D)
    angle = jnp.arange(T, dtype=jnp.float32)[:, None] * inv_freq[None]
    cos = jnp.concatenate([jnp.cos(angle), jnp.cos(angle)], -1)[None, :, None]
    sin = jnp.concatenate([jnp.sin(angle), jnp.sin(angle)], -1)[None, :, None]
    half = D // 2
    rotated = jnp.concatenate([-x[..., half:], x[..., :half]], -1)
    return x * cos + rotated * sin


def _attention(p, a, sizes, sliding, window, quant):
    """One KV head with the H // G query heads that read it at a time
    (their columns of Wq, Wk, Wv, Wg and rows of Wo), summed over KV heads."""
    B, T, d = a.shape
    H, G, D = sizes["n_head"], sizes["n_kv_head"], sizes["head_dim"]
    rep, eps, theta = H // G, sizes["rms_norm_eps"], sizes["rope_theta"]
    qb = min(Q_BLOCK, T)
    assert T % qb == 0, (T, qb)
    k_pos = jnp.arange(T)[None, :]
    by_group = lambda w, n: jnp.moveaxis(w.reshape(d, G, n), 1, 0)

    @jax.checkpoint
    def group(y, w):
        wq, wk, wv, wg, wo = w
        q = _rms_norm(_mm(a, wq, quant).reshape(B, T, rep, D),
                      p["q_norm"]["scale"], eps)
        k = _rms_norm(_mm(a, wk, quant).reshape(B, T, 1, D),
                      p["k_norm"]["scale"], eps)
        v = _mm(a, wv, quant)                              # (B, T, D)
        if sliding:  # full layers carry no positions
            q, k = _rotary(q, theta), _rotary(k, theta)
        k = k[:, :, 0]

        @jax.checkpoint
        def block(start):
            qs = lax.dynamic_slice_in_dim(q, start, qb, axis=1)
            s = jnp.einsum("bqrd,bkd->brqk", quant(qs),
                           quant(k)) / math.sqrt(D)
            q_pos = start + jnp.arange(qb)[:, None]
            visible = k_pos <= q_pos
            if window is not None:
                visible = visible & (q_pos - k_pos < window)
            prob = jax.nn.softmax(jnp.where(visible, s, -jnp.inf), axis=-1)
            return jnp.einsum("brqk,bkd->bqrd", quant(prob), quant(v))

        o = lax.map(block, jnp.arange(0, T, qb))           # (T/qb, B, qb, ..)
        o = jnp.moveaxis(o, 0, 1).reshape(B, T, rep * D)
        return y + _mm(o * jax.nn.sigmoid(_mm(a, wg, quant)), wo, quant), None

    y, _ = lax.scan(group, jnp.zeros_like(a), (
        by_group(p["q_proj"]["kernel"], rep * D),
        by_group(p["k_proj"]["kernel"], D), by_group(p["v_proj"]["kernel"], D),
        by_group(p["gate_proj"]["kernel"], rep * D),
        p["o_proj"]["kernel"].reshape(G, rep * D, d)))
    return y


def _swiglu(x, w_gate, w_up, w_down, quant):
    """(silu(x Wgate) * (x Wup)) Wdown for tokens x (..., d), MLP_CHUNK
    tokens at a time (position-wise, so the chunks are independent)."""
    shape = x.shape
    x = x.reshape(-1, shape[-1])
    cs = min(MLP_CHUNK, x.shape[0])
    assert x.shape[0] % cs == 0, (x.shape, cs)

    @jax.checkpoint
    def chunk(x_c):
        return _mm(jax.nn.silu(_mm(x_c, w_gate, quant))
                   * _mm(x_c, w_up, quant), w_down, quant)

    out = lax.map(chunk, x.reshape(-1, cs, shape[-1]))
    return out.reshape(shape[:-1] + (w_down.shape[-1],))


def route(x, w_router, bias, sizes):
    """(sel (N, k), w (N, k)) over all E experts; float32 in the control
    too. The bias b moves the selection and not the weights."""
    s = jax.nn.sigmoid(x @ w_router)
    _, sel = lax.top_k(s + lax.stop_gradient(bias),
                       sizes["num_experts_per_tok"])
    w = jnp.take_along_axis(s, sel, axis=1)
    if sizes["route_norm"]:
        w = w / (jnp.sum(w, axis=-1, keepdims=True) + 1e-20)
    return sel, w * sizes["route_scale"]


def _moe(p, m, sizes, quant, leave_out):
    B, T, d = m.shape
    x = m.reshape(B * T, d)
    sh = p["moe_shared"]
    f = _swiglu(x, sh["gate_proj"]["kernel"], sh["up_proj"]["kernel"],
                sh["down_proj"]["kernel"], quant)
    if "routed" not in leave_out:
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
        f = f + routed
    return f.reshape(B, T, d)


def _attend(h, p, layer, sizes, quant, leave_out):
    """The layer's first half: h + RMSNorm_post_attn(attention)."""
    eps = sizes["rms_norm_eps"]
    sliding = sizes["layer_types"][layer] == "sliding"
    window = (sizes["sliding_window"]
              if sliding and "window" not in leave_out else None)
    name = "attn_sliding" if sliding else "attn_full"
    a = _rms_norm(h, p["ln_in"]["scale"], eps)
    y = _attention(p[name], a, sizes, sliding, window, quant)
    return h + _rms_norm(y, p["ln_post_attn"]["scale"], eps)


def _feed_forward(h, p, layer, sizes, quant, leave_out):
    """The layer's second half: h + RMSNorm_post_mlp(MLP or experts)."""
    eps = sizes["rms_norm_eps"]
    m = _rms_norm(h, p["ln_pre_mlp"]["scale"], eps)
    if layer < sizes["num_dense_layers"]:
        mlp = p["mlp"]
        f = _swiglu(m, mlp["gate_proj"]["kernel"], mlp["up_proj"]["kernel"],
                    mlp["down_proj"]["kernel"], quant)
    else:
        f = _moe(p["moe"], m, sizes, quant, leave_out)
    return h + _rms_norm(f, p["ln_post_mlp"]["scale"], eps)


def _layer(h, p, layer, sizes, quant, leave_out):
    return _feed_forward(_attend(h, p, layer, sizes, quant, leave_out),
                         p, layer, sizes, quant, leave_out)


def hidden(params, x, sizes, quant=_ident, leave_out=frozenset()):
    """The final norm's output (B, T, d) for ids x (B, T)."""
    h = params["wte"]["embedding"][x] * math.sqrt(sizes["n_embd"])
    for i in range(sizes["n_layer"]):
        layer = jax.checkpoint(
            lambda h, p, i=i: _layer(h, p, i, sizes, quant, leave_out))
        h = layer(h, params[f"h_{i}"])
    return _rms_norm(h, params["ln_f"]["scale"], sizes["rms_norm_eps"])


def _balance(s, k):
    """(b, load): the bias (E,) under which top_k(s + b) draws evenly from
    scores s (N, E), after BALANCE_UPDATES moves of every expert's bias
    against its load's excess over the even share N * k / E (clipped to +-1
    of it), and each expert's load under b, in even shares."""
    N, E = s.shape
    first, last = BALANCE_STEP

    def load(b):
        kth = lax.top_k(s + b, k)[0][:, -1:]
        return jnp.sum(s + b >= kth, axis=0) * (E / (N * k))

    def update(t, b):
        step = first * (last / first) ** (t / (BALANCE_UPDATES - 1))
        return b - step * jnp.clip(load(b) - 1.0, -1.0, 1.0)

    b = lax.fori_loop(0, BALANCE_UPDATES, update, jnp.zeros((E,), jnp.float32))
    return b, load(b)


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

    embed = jax.jit(lambda table, ids: table[ids] * math.sqrt(d))
    scores = jax.jit(lambda h, p: jax.nn.sigmoid(
        _rms_norm(h, p["ln_pre_mlp"]["scale"], eps).reshape(-1, d)
        @ p["moe"]["router"]))
    balance = jax.jit(lambda s: _balance(s, k))
    hs = [embed(params["wte"]["embedding"], x[r:r + rows_per_block])
          for r in range(0, R, rows_per_block)]
    out = []
    for i in range(sizes["n_layer"]):
        p = params[f"h_{i}"]
        hs = [program(_attend, i)(h, p) for h in hs]
        if i >= dense:
            out.append(balance(jnp.concatenate([scores(h, p) for h in hs])))
            p = {**p, "moe": {**p["moe"], "expert_bias": out[-1][0]}}
        if i < last:  # nothing reads the last layer's second half
            hs = [program(_feed_forward, i)(h, p) for h in hs]
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


def adamw_step(params, m, v, grads, count, opt: dict):
    """One update, as ``reference/gpt2.adamw_step`` makes it (global-norm
    clip, AdamW with decoupled decay on the matrices), returning (params, m,
    v, clip scale) and no second gradient tree: the clipped gradient is
    ``grads * scale``, and 2.8 GB at this size."""
    gnorm = global_norm(grads)
    clip = opt["grad_clip"]
    scale = (jnp.minimum(1.0, clip / jnp.maximum(gnorm, 1e-30))
             if clip > 0 else jnp.ones((), jnp.float32))
    b1, b2, eps = opt["beta1"], opt["beta2"], 1e-8
    t = jnp.asarray(count, jnp.float32) + 1.0
    lr = learning_rate(count, opt)

    def upd(p, m, v, g):
        g = g * scale
        m = b1 * m + (1 - b1) * g
        v = b2 * v + (1 - b2) * g * g
        step = (m / (1 - b1 ** t)) / (jnp.sqrt(v / (1 - b2 ** t)) + eps)
        if p.ndim >= 2:
            step = step + opt["weight_decay"] * p
        return p - lr * step, m, v

    out = jax.tree.map(upd, params, m, v, grads)
    pick = lambda n: jax.tree.map(lambda o: o[n], out,
                                  is_leaf=lambda o: isinstance(o, tuple))
    return pick(0), pick(1), pick(2), scale
