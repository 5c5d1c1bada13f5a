"""The plain reference against the program's model at a tiny size, and the
control put in the reference's place."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from chipbench import weights
from chipbench.reference import gpt2
from chipbench.runners import train

SIZES = {"n_layer": 2, "n_head": 2, "n_embd": 32, "vocab_size": 128,
         "block_size": 16, "bias": False}


def _batch(seed, rows=4):
    rng = np.random.default_rng(seed)
    xy = rng.integers(0, SIZES["vocab_size"], (rows, SIZES["block_size"] + 1))
    return jnp.asarray(xy[:, :-1], jnp.int32), jnp.asarray(xy[:, 1:], jnp.int32)


@pytest.mark.parametrize("bias", [False, True])
def test_reference_matches_the_programs_model(bias):
    from nanosandbox_tpu.config import GPTConfig
    from nanosandbox_tpu.models.gpt import GPT, cross_entropy_loss

    sizes = dict(SIZES, bias=bias)
    params = weights.make_params(sizes, weights.seed_key(7))
    if bias:  # biases start at nought; move them so that they matter
        params = jax.tree.map(lambda p: p + 0.01 if p.ndim == 1 else p, params)
    x, y = _batch(1)
    model = GPT(GPTConfig(n_layer=2, n_head=2, n_embd=32, block_size=16,
                          vocab_size=128, bias=bias, compute_dtype="float32",
                          attention_impl="xla"))

    def program_loss(p):
        return cross_entropy_loss(model.apply({"params": p}, x), y)

    want, want_g = jax.value_and_grad(program_loss)(params)
    got, got_g = gpt2.loss_and_grad(params, x, y, n_layer=2, n_head=2,
                                    rows_per_block=2)
    assert float(got) == pytest.approx(float(want), abs=2e-6)
    for k, g in weights.flatten(got_g).items():
        w = weights.flatten(want_g)[k]
        assert jnp.allclose(g, w, atol=1e-6, rtol=1e-4), k


def test_adamw_follows_optax():
    from nanosandbox_tpu.config import TrainConfig
    from nanosandbox_tpu.train import make_optimizer
    import optax

    opt = {"learning_rate": 6e-4, "min_lr": 6e-5, "warmup_iters": 2,
           "lr_decay_iters": 10, "decay_lr": True, "weight_decay": 0.1,
           "beta1": 0.9, "beta2": 0.95, "grad_clip": 1.0}
    tx, _ = make_optimizer(TrainConfig(max_iters=10, **opt))
    params = weights.make_params(SIZES, weights.seed_key(3))
    p_ref, m, v = params, jax.tree.map(jnp.zeros_like, params), \
        jax.tree.map(jnp.zeros_like, params)
    p_prog, state = params, tx.init(params)
    for i in range(5):
        grads = jax.tree.map(
            lambda p: jax.random.normal(jax.random.key(i), p.shape) * 0.3, params)
        upd, state = tx.update(grads, state, p_prog)
        p_prog = optax.apply_updates(p_prog, upd)
        p_ref, m, v, _ = gpt2.adamw_step(p_ref, m, v, grads, i, opt)
    for k, a in weights.flatten(p_ref).items():
        assert jnp.allclose(a, weights.flatten(p_prog)[k], atol=1e-7, rtol=1e-5), k


def test_weights_are_the_seeds_and_large_seeds_differ():
    a = weights.flatten(weights.make_params(SIZES, weights.seed_key(2**31 + 5)))
    b = weights.flatten(weights.make_params(SIZES, weights.seed_key(2**31 + 5)))
    c = weights.flatten(weights.make_params(SIZES, weights.seed_key(2**31 + 6)))
    d = weights.flatten(weights.make_params(SIZES, weights.seed_key(2**32 + 2**31 + 5)))
    k = "h_1/mlp/c_proj/kernel"
    assert jnp.array_equal(a[k], b[k])
    assert not jnp.array_equal(a[k], c[k]) and not jnp.array_equal(a[k], d[k])
    assert float(jnp.std(a["h_0/attn/c_attn/kernel"])) == pytest.approx(0.02, rel=0.1)
    assert float(jnp.std(a[k])) == pytest.approx(0.02 / 2.0, rel=0.1)


class _Ctx:
    seed = 11
    config = {"optimizer": {"learning_rate": 6e-4, "min_lr": 6e-5,
                            "warmup_iters": 100, "lr_decay_iters": 3000,
                            "decay_lr": True, "weight_decay": 0.1, "beta1": 0.9,
                            "beta2": 0.95, "grad_clip": 1.0}}
    cell = {"check": {"ref_rows_per_block": 2}}


def test_the_control_and_the_half_batch_fault_read_far_from_the_reference():
    """The control (float8 operands) and the fault 'half of the batch left
    out', each put in the program's place at a size a test can hold, read
    at least ten times what the reference in bfloat16 (what the program
    is meant to compute) reads, on three seeds."""
    from chipbench.read_limits import half_left_out

    for seed in (11, 12, 13):
        ctx = _Ctx()
        ctx.seed = seed
        batches = [tuple(np.asarray(a) for a in _batch(seed * 10 + i, rows=8))
                   for i in range(3)]
        ref = train.reference_numbers(ctx, SIZES, batches)
        sound = train.gaps(train.reference_numbers(
            ctx, SIZES, batches, quant=gpt2.bf16_round_trip), ref)
        control = train.gaps(train.reference_numbers(
            ctx, SIZES, batches, quant=gpt2.fp8_round_trip), ref)
        half = train.gaps(train.reference_numbers(
            ctx, SIZES, half_left_out(batches)), ref)
        assert control["g1_leaf_gap"] > 10 * sound["g1_leaf_gap"], (seed, control, sound)
        assert half["g1_leaf_gap"] > 10 * sound["g1_leaf_gap"], (seed, half, sound)
        assert half["grad_norm_gap"] > 10 * sound["grad_norm_gap"], (seed, half, sound)
