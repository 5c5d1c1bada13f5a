"""The three serving kernels, compiled and RUN on the chip, against the
XLA path: logits-level parity for every kv mode (bf16 / int8 / int4),
both pool layouts (contiguous slot rows and the block-paged heap) and
both query shapes (T=1 decode, T>1 paged prefill), at GPT-2 124M head
geometry (H=12, D=64).

The kernel-level tests compare attention outputs on random K/V; the
model-level test compares next-token LOGITS of a small GPT through the
cached per-row path under decode_impl='pallas' vs 'xla' — the two
programs the serve engine chooses between.

Tolerances: bf16 operands, f32 accumulation, different summation order
(online softmax over blocks vs one masked softmax) — agreement is at the
1e-2 level on O(1) outputs.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from nanosandbox_tpu.config import GPTConfig
from nanosandbox_tpu.models.gpt import GPT, init_cache, init_paged_cache
from nanosandbox_tpu.ops import flash_decode as fd

B, H, D, L = 4, 12, 64, 256
MODES = ("fp", "int8", "int4")


def make_kv(rng, shape, mode):
    """K or V of ``shape`` (..., D) in ``mode``: (stored values, scales)."""
    x = jnp.asarray(rng.normal(size=shape), jnp.bfloat16)
    if mode == "fp":
        return x, None
    quantize = fd.quantize_kv_rows if mode == "int8" \
        else fd.quantize_kv_rows_int4
    return quantize(x)


def scales_kw(ks, vs):
    return {} if ks is None else {"k_scale": ks, "v_scale": vs}


def assert_close(out, ref):
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref, np.float32),
                               atol=2e-2, rtol=2e-2)


@pytest.mark.parametrize("mode", MODES)
def test_flash_decode_contiguous_matches_xla(mode):
    rng = np.random.default_rng(0)
    q = jnp.asarray(rng.normal(size=(B, H, D)), jnp.bfloat16)
    k, ks = make_kv(rng, (B, H, L, D), mode)
    v, vs = make_kv(rng, (B, H, L, D), mode)
    lengths = jnp.asarray(rng.integers(1, L + 1, size=B), jnp.int32)
    out = jax.jit(lambda *a: fd.flash_decode(*a, **scales_kw(ks, vs)))(
        q, k, v, lengths)
    ref = fd.xla_decode_attention(q, k, v, lengths, **scales_kw(ks, vs))
    assert_close(out, ref)


@pytest.mark.parametrize("page", [16, 32])
@pytest.mark.parametrize("mode", MODES)
def test_flash_decode_paged_matches_xla(mode, page):
    rng = np.random.default_rng(1)
    nb = L // page
    n_blocks = B * nb + 3
    q = jnp.asarray(rng.normal(size=(B, H, D)), jnp.bfloat16)
    k, ks = make_kv(rng, (n_blocks, H, page, D), mode)
    v, vs = make_kv(rng, (n_blocks, H, page, D), mode)
    table = jnp.asarray(rng.permutation(n_blocks)[:B * nb].reshape(B, nb),
                        jnp.int32)
    lengths = jnp.asarray(rng.integers(1, L + 1, size=B), jnp.int32)
    out = jax.jit(lambda *a: fd.flash_decode_paged(
        *a, **scales_kw(ks, vs)))(q, k, v, table, lengths)
    ref = fd.xla_decode_attention_paged(q, k, v, table, lengths,
                                        **scales_kw(ks, vs))
    assert_close(out, ref)


@pytest.mark.parametrize("T", [16, 128])
@pytest.mark.parametrize("page", [16, 32])
@pytest.mark.parametrize("mode", MODES)
def test_flash_prefill_paged_matches_xla(mode, page, T):
    """T>1 queries at positions start..start+T-1 over the paged pool vs
    per-position single-query XLA attention (query t attends start+t+1
    keys)."""
    rng = np.random.default_rng(2)
    nb = L // page
    n_blocks = B * nb
    q = jnp.asarray(rng.normal(size=(B, H, T, D)), jnp.bfloat16)
    k, ks = make_kv(rng, (n_blocks, H, page, D), mode)
    v, vs = make_kv(rng, (n_blocks, H, page, D), mode)
    table = jnp.asarray(rng.permutation(n_blocks).reshape(B, nb), jnp.int32)
    start = jnp.asarray(rng.integers(0, L - T + 1, size=B), jnp.int32)
    out = jax.jit(lambda *a: fd.flash_prefill_paged(
        *a, **scales_kw(ks, vs)))(q, k, v, table, start)
    ref = jnp.stack([
        fd.xla_decode_attention_paged(q[:, :, t], k, v, table, start + t + 1,
                                      **scales_kw(ks, vs))
        for t in range(T)], axis=2)
    assert_close(out, ref)


@pytest.mark.parametrize("paged", [True, False], ids=["paged", "contiguous"])
@pytest.mark.parametrize("kv_dtype", ["bf16", "int8", "int4"])
def test_model_logits_pallas_vs_xla(kv_dtype, paged):
    """Prefill (T>1) then one decode step (T=1) through the model's
    cached per-row path: logits under decode_impl='pallas' vs 'xla'."""
    cfg = GPTConfig(n_layer=2, n_head=12, n_embd=768, block_size=256,
                    vocab_size=512, dropout=0.0, compute_dtype="bfloat16",
                    attention_impl="xla")
    params = GPT(cfg).init(jax.random.key(0),
                           jnp.zeros((1, 8), jnp.int32))["params"]
    rng = np.random.default_rng(3)
    S, T, page = 2, 32, 16
    prompt = jnp.asarray(rng.integers(0, cfg.vocab_size, size=(S, T)),
                         jnp.int32)
    nxt = jnp.asarray(rng.integers(0, cfg.vocab_size, size=(S, 1)), jnp.int32)
    nb = cfg.block_size // page
    table = (jnp.arange(S * nb, dtype=jnp.int32).reshape(S, nb)
             if paged else None)

    def run(impl):
        model = GPT(cfg.replace(decode_impl=impl))
        cache = (init_paged_cache(cfg, S * nb, page, kv_dtype=kv_dtype)
                 if paged else init_cache(cfg, S, cfg.block_size,
                                          kv_dtype=kv_dtype))

        @jax.jit
        def both(params, cache):
            pre, cache = model.apply(
                {"params": params}, prompt, deterministic=True, cache=cache,
                cache_index=jnp.zeros((S,), jnp.int32), block_table=table)
            dec, _ = model.apply(
                {"params": params}, nxt, deterministic=True, cache=cache,
                cache_index=jnp.full((S,), T, jnp.int32), block_table=table)
            return pre, dec

        return both(params, cache)

    (pre_p, dec_p), (pre_x, dec_x) = run("pallas"), run("xla")
    for got, want in ((pre_p, pre_x), (dec_p, dec_x)):
        assert np.isfinite(np.asarray(got, np.float32)).all()
        np.testing.assert_allclose(np.asarray(got, np.float32),
                                   np.asarray(want, np.float32),
                                   atol=5e-2, rtol=5e-2)
