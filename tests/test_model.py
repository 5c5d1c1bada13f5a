"""GPT model tests: shapes, tying, causality, init scale, param count."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from nanosandbox_tpu.config import GPTConfig
from nanosandbox_tpu.models.gpt import GPT, count_params, cross_entropy_loss


def tiny(**kw):
    base = dict(n_layer=2, n_head=2, n_embd=32, block_size=16, vocab_size=65,
                dropout=0.0, compute_dtype="float32", attention_impl="xla")
    base.update(kw)
    return GPTConfig(**base)


@pytest.fixture(scope="module")
def model_and_params():
    cfg = tiny()
    model = GPT(cfg)
    params = model.init(jax.random.key(0), jnp.zeros((1, 8), jnp.int32))[
        "params"]
    return model, params, cfg


def test_forward_shape(model_and_params):
    model, params, cfg = model_and_params
    x = jnp.zeros((3, 16), jnp.int32)
    logits = model.apply({"params": params}, x)
    assert logits.shape == (3, 16, cfg.vocab_size)
    assert bool(jnp.isfinite(logits).all())


def test_weight_tying(model_and_params):
    _, params, _ = model_and_params
    assert "lm_head" not in params  # head reuses wte.attend


def test_causality(model_and_params):
    model, params, _ = model_and_params
    rng = np.random.default_rng(0)
    x = rng.integers(0, 65, (1, 16))
    x2 = x.copy()
    x2[0, 10:] = rng.integers(0, 65, 6)  # perturb the future
    l1 = model.apply({"params": params}, jnp.asarray(x, jnp.int32))
    l2 = model.apply({"params": params}, jnp.asarray(x2, jnp.int32))
    np.testing.assert_allclose(np.asarray(l1[0, :10]), np.asarray(l2[0, :10]),
                               atol=1e-5)
    assert not np.allclose(np.asarray(l1[0, 10:]), np.asarray(l2[0, 10:]))


def test_gpt2_124m_param_count():
    cfg = GPTConfig(n_layer=12, n_head=12, n_embd=768, block_size=1024,
                    vocab_size=50304, bias=False)
    model = GPT(cfg)
    abstract = jax.eval_shape(
        lambda: model.init(jax.random.key(0), jnp.zeros((1, 8), jnp.int32)))
    n = count_params(abstract["params"])
    # nanoGPT reports 124.34M for GPT-2 with wpe included at vocab 50304.
    assert 120e6 < n < 130e6


def test_cross_entropy_matches_manual():
    logits = jnp.asarray(np.random.default_rng(0).normal(size=(2, 4, 7)),
                         jnp.float32)
    targets = jnp.asarray([[1, 2, 3, -1], [0, 6, -1, -1]])
    loss = cross_entropy_loss(logits, targets)
    logp = jax.nn.log_softmax(logits, -1)
    manual = []
    for b in range(2):
        for t in range(4):
            if int(targets[b, t]) != -1:
                manual.append(-float(logp[b, t, int(targets[b, t])]))
    assert float(loss) == pytest.approx(np.mean(manual), rel=1e-5)


def test_dropout_requires_rng_and_varies():
    cfg = tiny(dropout=0.5)
    model = GPT(cfg)
    x = jnp.zeros((1, 8), jnp.int32)
    params = model.init({"params": jax.random.key(0),
                         "dropout": jax.random.key(1)}, x,
                        deterministic=False)["params"]
    a = model.apply({"params": params}, x, deterministic=False,
                    rngs={"dropout": jax.random.key(2)})
    b = model.apply({"params": params}, x, deterministic=False,
                    rngs={"dropout": jax.random.key(3)})
    assert not np.allclose(np.asarray(a), np.asarray(b))
    c = model.apply({"params": params}, x, deterministic=True)
    d = model.apply({"params": params}, x, deterministic=True)
    np.testing.assert_allclose(np.asarray(c), np.asarray(d))


def test_remat_matches(model_and_params):
    model, params, cfg = model_and_params
    rcfg = tiny(remat=True)
    rmodel = GPT(rcfg)
    x = jnp.zeros((2, 16), jnp.int32)
    a = model.apply({"params": params}, x)
    b = rmodel.apply({"params": params}, x)
    np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-5)


def test_block_size_overflow_raises(model_and_params):
    model, params, _ = model_and_params
    with pytest.raises(ValueError, match="block_size"):
        model.apply({"params": params}, jnp.zeros((1, 17), jnp.int32))


# -- chunked_cross_entropy_loss parity (ADVICE.md round-1 items 2+3) ------

def _chunk_case(B=2, T=12, C=32, V=65, seed=0):
    rng = np.random.default_rng(seed)
    hidden = jnp.asarray(rng.normal(size=(B, T, C)), jnp.float32)
    emb = jnp.asarray(rng.normal(size=(V, C)) * 0.1, jnp.float32)
    targets = rng.integers(0, V, (B, T))
    targets[0, :3] = -1  # ignore_index rows
    targets[1, -1] = -1
    return hidden, emb, jnp.asarray(targets, jnp.int32)


@pytest.mark.parametrize("chunk_size", [5, 4, 128])  # 5 does not divide 12
def test_chunked_loss_matches_full_f32(chunk_size):
    from nanosandbox_tpu.models.loss import chunked_cross_entropy_loss

    hidden, emb, targets = _chunk_case()
    logits = hidden @ emb.T
    full = cross_entropy_loss(logits, targets)
    chunked = chunked_cross_entropy_loss(
        hidden, emb, targets, chunk_size=chunk_size,
        compute_dtype="float32")
    np.testing.assert_allclose(np.asarray(chunked), np.asarray(full),
                               rtol=1e-6, atol=1e-6)


def test_chunked_loss_grads_match_full_f32():
    from nanosandbox_tpu.models.loss import chunked_cross_entropy_loss

    hidden, emb, targets = _chunk_case(seed=1)

    def full_fn(h, e):
        return cross_entropy_loss(h @ e.T, targets)

    def chunk_fn(h, e):
        return chunked_cross_entropy_loss(h, e, targets, chunk_size=4,
                                          compute_dtype="float32")

    gh_f, ge_f = jax.grad(full_fn, argnums=(0, 1))(hidden, emb)
    gh_c, ge_c = jax.grad(chunk_fn, argnums=(0, 1))(hidden, emb)
    np.testing.assert_allclose(np.asarray(gh_c), np.asarray(gh_f),
                               rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(np.asarray(ge_c), np.asarray(ge_f),
                               rtol=1e-5, atol=1e-6)


def test_chunked_loss_bf16_within_rounding_of_full():
    """Documented tradeoff: chunked feeds the MXU bf16 inputs while the
    full path casts to f32 — under bf16 they agree to bf16 rounding."""
    from nanosandbox_tpu.models.loss import chunked_cross_entropy_loss

    hidden, emb, targets = _chunk_case(seed=2)
    full = cross_entropy_loss(hidden @ emb.T, targets)
    chunked = chunked_cross_entropy_loss(
        hidden, emb, targets, chunk_size=4, compute_dtype="bfloat16")
    np.testing.assert_allclose(np.asarray(chunked), np.asarray(full),
                               rtol=2e-2, atol=2e-2)


def test_chunked_loss_all_ignored_is_zero():
    from nanosandbox_tpu.models.loss import chunked_cross_entropy_loss

    hidden, emb, _ = _chunk_case()
    targets = jnp.full((2, 12), -1, jnp.int32)
    out = chunked_cross_entropy_loss(hidden, emb, targets, chunk_size=4,
                                     compute_dtype="float32")
    assert float(out) == 0.0


@pytest.mark.parametrize("policy", ["save_attention", "full"])
def test_remat_policies_match(model_and_params, policy):
    """Selective remat changes what's saved, never the math: outputs and
    gradients agree with the non-remat model."""
    model, params, cfg = model_and_params
    rmodel = GPT(tiny(remat=True, remat_policy=policy))
    x = jnp.zeros((2, 16), jnp.int32) + jnp.arange(16)[None, :] % 5
    np.testing.assert_allclose(
        np.asarray(model.apply({"params": params}, x)),
        np.asarray(rmodel.apply({"params": params}, x)), atol=1e-5)

    def loss(m, p):
        return (m.apply({"params": p}, x).astype(jnp.float32) ** 2).mean()

    g1 = jax.grad(lambda p: loss(model, p))(params)
    g2 = jax.grad(lambda p: loss(rmodel, p))(params)
    for a, b in zip(jax.tree.leaves(g1), jax.tree.leaves(g2)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   atol=2e-5, rtol=2e-5)


def test_remat_policy_unknown_raises(model_and_params):
    _, params, _ = model_and_params
    bad = GPT(tiny(remat=True, remat_policy="nope"))
    with pytest.raises(ValueError, match="remat_policy"):
        bad.apply({"params": params}, jnp.zeros((1, 16), jnp.int32))


def test_save_attention_policy_elides_kernel_recompute():
    """The policy's reason to exist, pinned by counting pallas_calls in
    the grad jaxpr: a remat region discards custom_vjp residuals, so
    without the checkpoint_name tags on (o, lse) the flash forward runs
    TWICE in the backward (3 calls/layer); with them it runs once
    (2 = fwd + fused one-pass bwd), same as no remat."""

    def count_calls(remat, policy):
        cfg = tiny(block_size=128, attention_impl="pallas_interpret",
                   remat=remat, remat_policy=policy)
        model = GPT(cfg)
        x = jnp.zeros((1, 128), jnp.int32)
        params = model.init(jax.random.key(0), x)["params"]

        def loss(p):
            return (model.apply({"params": p}, x)
                    .astype(jnp.float32) ** 2).mean()

        return str(jax.make_jaxpr(jax.grad(loss))(params)).count(
            "pallas_call")

    assert count_calls(False, "full") == 2 * tiny().n_layer
    assert count_calls(True, "full") == 3 * tiny().n_layer
    assert count_calls(True, "save_attention") == 2 * tiny().n_layer


def test_cached_positions_past_block_size_stay_finite(model_and_params):
    """Lanes that overshoot a row's end on purpose (speculative verify
    lanes, a scan rung's trailing steps, padded prefill buckets) look up
    positions >= block_size. nn.Embed FILLS an out-of-range row with
    NaN; the wpe lookup is bounded to the last position instead, so the
    logits the poison guard reads stay finite — and in-range lanes are
    untouched."""
    from nanosandbox_tpu.models.gpt import init_cache

    model, params, cfg = model_and_params
    T = 4
    tok = jnp.ones((2, T), jnp.int32)
    # Row 0 sits well inside the buffer; row 1 starts at the last
    # position, so its lanes 1..3 are positions block_size..block_size+2.
    index = jnp.asarray([3, cfg.block_size - 1], jnp.int32)

    @jax.jit
    def step(params, tok, index):
        logits, _ = model.apply(
            {"params": params}, tok, deterministic=True,
            cache=init_cache(cfg, 2, cfg.block_size), cache_index=index)
        return logits

    logits = np.asarray(step(params, tok, index))
    assert np.isfinite(logits).all()
    # Bounding changes nothing for in-range positions: row 0 equals the
    # same row run alone, far from the end.
    alone = np.asarray(step(params, tok, jnp.asarray([3, 3], jnp.int32)))
    np.testing.assert_array_equal(logits[0], alone[0])
