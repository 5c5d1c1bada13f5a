"""Generation tests: KV-cached decode + windowed fallback parity."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from nanosandbox_tpu.config import GPTConfig
from nanosandbox_tpu.models.gpt import GPT, init_cache
from nanosandbox_tpu.sample import _generate_windowed, generate


def test_generate_shapes_and_range():
    cfg = GPTConfig(n_layer=2, n_head=2, n_embd=32, block_size=16,
                    vocab_size=50, dropout=0.0, compute_dtype="float32",
                    attention_impl="xla")
    model = GPT(cfg)
    params = model.init(jax.random.key(0),
                        jnp.zeros((1, 8), jnp.int32))["params"]
    idx = jnp.asarray([[1, 2, 3]], jnp.int32)
    out = generate(model, params, idx, 40, temperature=1.0, top_k=10,
                   rng=jax.random.key(1), block_size=cfg.block_size)
    assert out.shape == (1, 43)
    assert int(out.max()) < 50 and int(out.min()) >= 0
    # prompt preserved
    assert out[0, :3].tolist() == [1, 2, 3]


def test_generate_deterministic_given_rng():
    cfg = GPTConfig(n_layer=1, n_head=1, n_embd=16, block_size=8,
                    vocab_size=20, compute_dtype="float32",
                    attention_impl="xla")
    model = GPT(cfg)
    params = model.init(jax.random.key(0),
                        jnp.zeros((1, 4), jnp.int32))["params"]
    idx = jnp.asarray([[5]], jnp.int32)
    a = generate(model, params, idx, 12, temperature=0.8, top_k=5,
                 rng=jax.random.key(7), block_size=cfg.block_size)
    b = generate(model, params, idx, 12, temperature=0.8, top_k=5,
                 rng=jax.random.key(7), block_size=cfg.block_size)
    assert a.tolist() == b.tolist()


def _tiny_model(block_size=32, vocab=50):
    cfg = GPTConfig(n_layer=2, n_head=2, n_embd=32, block_size=block_size,
                    vocab_size=vocab, dropout=0.0, compute_dtype="float32",
                    attention_impl="xla")
    model = GPT(cfg)
    params = model.init(jax.random.key(0),
                        jnp.zeros((1, 8), jnp.int32))["params"]
    return cfg, model, params


def test_cached_logits_match_full_forward():
    """Prefill + per-token cached steps reproduce the full forward's logits
    at every position — the correctness contract of the KV-cache path."""
    cfg, model, params = _tiny_model()
    idx = jax.random.randint(jax.random.key(3), (2, 12), 0, 50, jnp.int32)

    ref = model.apply({"params": params}, idx, deterministic=True)

    T0 = 5
    cache = init_cache(cfg, 2, 12)
    logits, cache = model.apply({"params": params}, idx[:, :T0],
                                deterministic=True, cache=cache,
                                cache_index=0)
    got = [logits]  # (2, T0, V)
    for i in range(T0, 12):
        logits, cache = model.apply({"params": params}, idx[:, i:i + 1],
                                    deterministic=True, cache=cache,
                                    cache_index=i)
        got.append(logits)
    got = jnp.concatenate(got, axis=1)
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                               rtol=1e-4, atol=1e-4)


def test_cached_greedy_matches_windowed():
    """temperature=0 decode is identical between the KV-cache path and the
    sliding-window full-forward fallback (VERDICT r3 next #3 done-bar)."""
    cfg, model, params = _tiny_model()
    idx = jnp.asarray([[1, 2, 3, 4]], jnp.int32)
    a = generate(model, params, idx, 20, temperature=0.0, top_k=0,
                 rng=jax.random.key(1), block_size=cfg.block_size)
    b = _generate_windowed(model, params, idx, 20, temperature=0.0, top_k=0,
                           rng=jax.random.key(1), block_size=cfg.block_size)
    assert a.shape == (1, 24)
    assert a.tolist() == b.tolist()


def test_cached_path_shapes_and_edges():
    cfg, model, params = _tiny_model()
    idx = jnp.asarray([[7, 8]], jnp.int32)
    # Single new token (scan length 0).
    out = generate(model, params, idx, 1, temperature=0.0, top_k=0,
                   rng=jax.random.key(0), block_size=cfg.block_size)
    assert out.shape == (1, 3)
    assert out[0, :2].tolist() == [7, 8]
    # Zero new tokens returns the prompt.
    out = generate(model, params, idx, 0, temperature=0.0, top_k=0,
                   rng=jax.random.key(0), block_size=cfg.block_size)
    assert out.tolist() == idx.tolist()
    # Exactly filling block_size stays on the cached path.
    out = generate(model, params, idx, cfg.block_size - 2, temperature=0.0,
                   top_k=0, rng=jax.random.key(0), block_size=cfg.block_size)
    assert out.shape == (1, cfg.block_size)


def test_init_cache_rejects_beyond_block_size():
    cfg, _, _ = _tiny_model(block_size=16)
    import pytest
    with pytest.raises(ValueError, match="block_size"):
        init_cache(cfg, 1, 17)


def test_cache_and_return_hidden_conflict_raises():
    cfg, model, params = _tiny_model()
    cache = init_cache(cfg, 1, 8)
    import pytest
    with pytest.raises(ValueError, match="return_hidden"):
        model.apply({"params": params}, jnp.zeros((1, 4), jnp.int32),
                    deterministic=True, return_hidden=True,
                    cache=cache, cache_index=0)


def test_top_p_nucleus_filter():
    """top_p keeps exactly the smallest prefix of the sorted distribution
    whose mass reaches p: probs (.5, .3, .15, .05) @ p=0.6 -> tokens
    {0, 1} only (mass before token 2 is already 0.8)."""
    from nanosandbox_tpu.sample import _sample_token

    probs = jnp.asarray([[0.5, 0.3, 0.15, 0.05]])
    logits = jnp.log(probs)
    seen = set()
    rng = jax.random.key(0)
    for _ in range(200):
        tok, rng = _sample_token(logits, rng, temperature=1.0, top_k=0,
                                 top_p=0.6)
        seen.add(int(tok[0]))
    assert seen == {0, 1}, seen
    # p=1.0 disables the filter: the tail tokens reappear.
    seen = set()
    for _ in range(400):
        tok, rng = _sample_token(logits, rng, temperature=1.0, top_k=0,
                                 top_p=1.0)
        seen.add(int(tok[0]))
    assert seen == {0, 1, 2, 3}, seen


def test_top_p_composes_with_cached_generate():
    cfg, model, params = _tiny_model()
    idx = jnp.asarray([[1, 2]], jnp.int32)
    out = generate(model, params, idx, 10, temperature=0.9, top_k=0,
                   rng=jax.random.key(2), block_size=cfg.block_size,
                   top_p=0.9)
    assert out.shape == (1, 12)


def test_top_p_zero_keeps_top1():
    """top_p<=0 must degrade to near-greedy (top-1 survives), never to the
    all-masked uniform-categorical failure mode."""
    from nanosandbox_tpu.sample import _sample_token

    logits = jnp.log(jnp.asarray([[0.5, 0.3, 0.15, 0.05]]))
    rng = jax.random.key(1)
    for _ in range(50):
        tok, rng = _sample_token(logits, rng, temperature=1.0, top_k=0,
                                 top_p=0.0)
        assert int(tok[0]) == 0


# ------------------------------------------------- per-row sampling (serve)

def test_sample_token_per_row_greedy_and_topk1():
    """Vector params: a temperature=0 row takes argmax of the RAW logits;
    a top_k=1 row is argmax via filtering — both deterministic, each row
    governed only by its own settings."""
    from nanosandbox_tpu.sample import _sample_token

    logits = jnp.log(jnp.asarray([[0.5, 0.3, 0.15, 0.05],
                                  [0.05, 0.15, 0.3, 0.5]]))
    for _ in range(20):
        tok, _ = _sample_token(logits, jax.random.key(0),
                               temperature=jnp.asarray([0.0, 1.0]),
                               top_k=jnp.asarray([0, 1]),
                               top_p=jnp.asarray([1.0, 1.0]))
        assert int(tok[0]) == 0   # greedy row
        assert int(tok[1]) == 3   # top-1-filtered row


def test_sample_token_per_row_top_p_masks_per_row():
    """Row 0 (p=0.6) may only emit tokens {0, 1}; row 1 (p=1.0) of the
    same distribution eventually emits the tail too."""
    from nanosandbox_tpu.sample import _sample_token

    row = [0.5, 0.3, 0.15, 0.05]
    logits = jnp.log(jnp.asarray([row, row]))
    seen0, seen1 = set(), set()
    rng = jax.random.key(0)
    # One program for the 300 draws: op by op they were 148 s of tier-1.
    draw = jax.jit(lambda key, t, k, p: _sample_token(
        logits, key, temperature=t, top_k=k, top_p=p))
    for _ in range(300):
        rng, sub = jax.random.split(rng)
        tok, _ = draw(sub, jnp.asarray([1.0, 1.0]), jnp.asarray([0, 0]),
                      jnp.asarray([0.6, 1.0]))
        seen0.add(int(tok[0]))
        seen1.add(int(tok[1]))
    assert seen0 == {0, 1}, seen0
    assert seen1 == {0, 1, 2, 3}, seen1


def test_sample_token_per_row_key_batch_isolates_rows():
    """With a (B,) key batch, each row samples from its own stream: the
    same key must yield the same token no matter what other rows ride
    along — the engine's batch-composition-independence anchor."""
    from nanosandbox_tpu.sample import _sample_token

    row = [0.25, 0.25, 0.25, 0.25]
    keys1 = jnp.stack([jax.random.key(5)])
    keys3 = jnp.stack([jax.random.key(5), jax.random.key(6),
                       jax.random.key(7)])
    t1, _ = _sample_token(jnp.log(jnp.asarray([row])), keys1,
                          temperature=jnp.asarray([1.0]),
                          top_k=jnp.asarray([0]), top_p=jnp.asarray([1.0]))
    t3, _ = _sample_token(jnp.log(jnp.asarray([row] * 3)), keys3,
                          temperature=jnp.ones(3), top_k=jnp.zeros(3, jnp.int32),
                          top_p=jnp.ones(3))
    assert int(t1[0]) == int(t3[0])


def test_sample_token_scalar_path_unchanged_by_vector_dispatch():
    """A (B,)-broadcast of identical scalar params filters identically to
    the scalar path: with top_k=2 both paths can only emit {0, 1}."""
    from nanosandbox_tpu.sample import _sample_token

    logits = jnp.log(jnp.asarray([[0.5, 0.3, 0.15, 0.05]]))
    seen = set()
    rng = jax.random.key(3)
    draw = jax.jit(lambda key, t, k, p: _sample_token(
        logits, key, temperature=t, top_k=k, top_p=p))
    for _ in range(100):
        rng, sub = jax.random.split(rng)
        tok, _ = draw(sub, jnp.asarray([1.0]), jnp.asarray([2]),
                      jnp.asarray([1.0]))
        seen.add(int(tok[0]))
    assert seen == {0, 1}, seen


# ------------------------------------------------------ CLI parity (main)

def test_main_rejects_num_samples_below_one(tmp_path):
    """--num_samples=0 must fail fast (argparse error), BEFORE any
    checkpoint restore is attempted — the bogus out_dir would raise a
    different error if validation ran late."""
    from nanosandbox_tpu.sample import main

    with pytest.raises(SystemExit) as ei:
        main(["--num_samples=0", f"--out_dir={tmp_path}/definitely-missing"])
    assert ei.value.code == 2  # argparse error exit, not FileNotFoundError


def test_resolve_start_file_convention(tmp_path):
    """nanoGPT's --start=FILE:<path> reads the prompt from a file."""
    from nanosandbox_tpu.sample import resolve_start

    p = tmp_path / "prompt.txt"
    p.write_text("To be, or not to be\n")
    assert resolve_start(f"FILE:{p}") == "To be, or not to be\n"
    assert resolve_start("plain text") == "plain text"
    with pytest.raises(FileNotFoundError):
        resolve_start(f"FILE:{tmp_path}/nope.txt")
