"""Plain reference for the GPT-2 training cells.

float32 ``jax.numpy`` under ``default_matmul_precision("highest")``, no
kernels, no cache, nothing imported from the program. It follows
Radford et al. 2019 (GPT-2) as nanoGPT trains it: pre-LN blocks, fused
qkv, causal softmax attention, tanh-GELU MLP, tied LM head, mean
next-token cross entropy; AdamW with decoupled weight decay on the
matrices only, global-norm clipping, linear warm-up into cosine decay.

Departures from the paper, both stated by the configurations: no biases
where ``bias`` is false, and a vocabulary padded to a multiple of 64
(padded rows take part in the softmax and are never targets).

So that it fits beside nothing but itself on one chip it runs the layers
under ``lax.scan`` with each layer rematerialised, and the batch in blocks
of rows whose gradients are summed.

``quant`` is the hook of the control: the identity for the reference, a
round trip through a lower precision on every matmul operand for the
control (see ``chipbench/runners/train.py``).
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp
from jax import lax


def _ident(x):
    return x


def _rounded_forward(x, rounded):
    """``rounded`` going forward, the identity going back: the gradient is
    not itself cast to the lower precision (whose smallest step would flush
    most of a gradient to nought, and the control would read as a crash)."""
    return x + lax.stop_gradient(rounded - x)


def fp8_round_trip(x):
    """The control's precision: float8 e4m3, the step below bfloat16, as a
    float8 training recipe uses it: each matmul operand scaled so that its
    largest magnitude sits at e4m3's largest (448), rounded, scaled back."""
    scale = lax.stop_gradient(jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / 448.0)
    return _rounded_forward(
        x, (x / scale).astype(jnp.float8_e4m3fn).astype(x.dtype) * scale)


def bf16_round_trip(x):
    return _rounded_forward(x, x.astype(jnp.bfloat16).astype(x.dtype))


def _layer_norm(x, scale, bias, eps=1e-5):
    mu = x.mean(-1, keepdims=True)
    var = ((x - mu) ** 2).mean(-1, keepdims=True)
    y = (x - mu) * lax.rsqrt(var + eps) * scale
    return y if bias is None else y + bias


def _dense(x, p, quant):
    y = quant(x) @ quant(p["kernel"])
    return y + p["bias"] if "bias" in p else y


def _gelu_tanh(x):
    return 0.5 * x * (1.0 + jnp.tanh(
        math.sqrt(2.0 / math.pi) * (x + 0.044715 * x ** 3)))


def _block(x, p, n_head, quant):
    B, T, C = x.shape
    D = C // n_head
    h = _layer_norm(x, p["ln_1"]["scale"], p["ln_1"].get("bias"))
    qkv = _dense(h, p["attn"]["c_attn"], quant)
    q, k, v = jnp.split(qkv, 3, axis=-1)
    q, k, v = (t.reshape(B, T, n_head, D).transpose(0, 2, 1, 3)
               for t in (q, k, v))
    s = jnp.einsum("bhtd,bhsd->bhts", quant(q), quant(k)) / math.sqrt(D)
    causal = jnp.tril(jnp.ones((T, T), bool))
    s = jnp.where(causal, s, -jnp.inf)
    a = jax.nn.softmax(s, axis=-1)
    y = jnp.einsum("bhts,bhsd->bhtd", quant(a), quant(v))
    y = y.transpose(0, 2, 1, 3).reshape(B, T, C)
    x = x + _dense(y, p["attn"]["c_proj"], quant)
    h = _layer_norm(x, p["ln_2"]["scale"], p["ln_2"].get("bias"))
    h = _gelu_tanh(_dense(h, p["mlp"]["c_fc"], quant))
    return x + _dense(h, p["mlp"]["c_proj"], quant)


def _stack_layers(params, n_layer):
    layers = [params[f"h_{i}"] for i in range(n_layer)]
    return jax.tree.map(lambda *xs: jnp.stack(xs), *layers)


def loss_fn(params, x, y, *, n_layer, n_head, quant=_ident):
    """Mean next-token cross entropy of rows x (B, T) against y (B, T)."""
    T = x.shape[1]
    wte, wpe = params["wte"]["embedding"], params["wpe"]["embedding"]
    h = wte[x] + wpe[jnp.arange(T)][None]
    block = jax.checkpoint(lambda h, p: _block(h, p, n_head, quant))
    h, _ = lax.scan(lambda h, p: (block(h, p), None), h,
                    _stack_layers(params, n_layer))
    h = _layer_norm(h, params["ln_f"]["scale"], params["ln_f"].get("bias"))
    logits = quant(h) @ quant(wte).T
    lse = jax.nn.logsumexp(logits, axis=-1)
    tgt = jnp.take_along_axis(logits, y[..., None], axis=-1)[..., 0]
    return (lse - tgt).mean()


def loss_and_grad(params, x, y, *, n_layer, n_head, rows_per_block,
                  quant=_ident):
    """Loss and gradient of the whole batch, computed ``rows_per_block``
    rows at a time (equal blocks, so the mean of means is the mean)."""
    B = x.shape[0]
    assert B % rows_per_block == 0, (B, rows_per_block)
    n = B // rows_per_block
    xs = x.reshape(n, rows_per_block, -1)
    ys = y.reshape(n, rows_per_block, -1)
    vg = jax.value_and_grad(
        lambda p, xb, yb: loss_fn(p, xb, yb, n_layer=n_layer, n_head=n_head,
                                  quant=quant))

    def body(carry, xy):
        loss, grads = carry
        l, g = vg(params, *xy)
        return (loss + l, jax.tree.map(jnp.add, grads, g)), None

    zero = jax.tree.map(jnp.zeros_like, params)
    (loss, grads), _ = lax.scan(body, (jnp.zeros((), jnp.float32), zero),
                                (xs, ys))
    return loss / n, jax.tree.map(lambda g: g / n, grads)


def learning_rate(count, opt: dict):
    """Linear warm-up from 0 over ``warmup_iters`` steps, then cosine decay
    to ``min_lr`` at ``lr_decay_iters``; ``count`` is the number of updates
    made so far (0 for the first)."""
    lr, warm = opt["learning_rate"], max(opt["warmup_iters"], 1)
    if not opt.get("decay_lr", True):
        return jnp.asarray(lr, jnp.float32)
    count = jnp.asarray(count, jnp.float32)
    decay = max(opt["lr_decay_iters"] - opt["warmup_iters"], 1)
    frac = jnp.clip((count - opt["warmup_iters"]) / decay, 0.0, 1.0)
    alpha = opt["min_lr"] / lr
    cosine = lr * ((1 - alpha) * 0.5 * (1 + jnp.cos(jnp.pi * frac)) + alpha)
    return jnp.where(count < opt["warmup_iters"], lr * count / warm, cosine)


def global_norm(tree):
    return jnp.sqrt(sum(jnp.sum(jnp.square(l)) for l in jax.tree.leaves(tree)))


def adamw_step(params, m, v, grads, count, opt: dict):
    """One update. Returns (params, m, v, clipped gradient)."""
    gnorm = global_norm(grads)
    clip = opt["grad_clip"]
    if clip > 0:
        scale = jnp.minimum(1.0, clip / jnp.maximum(gnorm, 1e-30))
        grads = jax.tree.map(lambda g: g * scale, grads)
    b1, b2, eps = opt["beta1"], opt["beta2"], 1e-8
    t = jnp.asarray(count, jnp.float32) + 1.0
    lr = learning_rate(count, opt)
    m = jax.tree.map(lambda m, g: b1 * m + (1 - b1) * g, m, grads)
    v = jax.tree.map(lambda v, g: b2 * v + (1 - b2) * g * g, v, grads)

    def upd(p, m, v):
        step = (m / (1 - b1 ** t)) / (jnp.sqrt(v / (1 - b2 ** t)) + eps)
        if p.ndim >= 2:
            step = step + opt["weight_decay"] * p
        return p - lr * step

    return jax.tree.map(upd, params, m, v), m, v, grads
