"""Attention kernel tests: Pallas (interpret mode on CPU) vs XLA reference."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from nanosandbox_tpu.ops.attention import (causal_attention, flash_attention,
                                           xla_attention)


def rand_qkv(rng, B=2, H=2, T=128, D=64, dtype=jnp.float32):
    q, k, v = (jnp.asarray(rng.normal(size=(B, H, T, D)), dtype)
               for _ in range(3))
    return q, k, v


@pytest.mark.parametrize("T,D", [(128, 64), (128, 128), (256, 64), (96, 32)])
def test_flash_matches_xla(T, D):
    rng = np.random.default_rng(0)
    q, k, v = rand_qkv(rng, T=T, D=D)
    ref = xla_attention(q, k, v, causal=True)
    out = flash_attention(q, k, v, interpret=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=2e-5, rtol=2e-5)


def test_flash_gradients_match():
    rng = np.random.default_rng(1)
    q, k, v = rand_qkv(rng, T=64, D=32)

    def loss_flash(q, k, v):
        return flash_attention(q, k, v, interpret=True).sum()

    def loss_ref(q, k, v):
        return xla_attention(q, k, v, causal=True).sum()

    gf = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
    gr = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(gf, gr):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   atol=2e-4, rtol=2e-4)


def test_dispatch_auto_on_cpu_uses_xla():
    rng = np.random.default_rng(2)
    q, k, v = rand_qkv(rng, T=32, D=16)
    out = causal_attention(q, k, v, impl="auto")
    ref = xla_attention(q, k, v, causal=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=1e-6)


def test_causal_masking():
    rng = np.random.default_rng(3)
    q, k, v = rand_qkv(rng, B=1, H=1, T=64, D=32)
    out1 = flash_attention(q, k, v, interpret=True)
    k2 = k.at[:, :, 40:, :].set(0.0)
    v2 = v.at[:, :, 40:, :].set(0.0)
    out2 = flash_attention(q, k2, v2, interpret=True)
    # Positions < 40 never see keys >= 40, so they are identical.
    np.testing.assert_allclose(np.asarray(out1[:, :, :40]),
                               np.asarray(out2[:, :, :40]), atol=1e-5)


def test_bf16_inputs():
    rng = np.random.default_rng(4)
    q, k, v = rand_qkv(rng, T=128, D=64, dtype=jnp.bfloat16)
    ref = xla_attention(q, k, v, causal=True)
    out = flash_attention(q, k, v, interpret=True)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref, np.float32), atol=3e-2)


@pytest.mark.parametrize("T,expect", [
    (64, (128, 128)),     # tiny T -> single 128 block
    (640, (128, 128)),    # 640 = 5*128: only 128 divides -> no pad waste
    (768, (384, 384)),    # largest divisor <= 512
    (1024, (512, 512)),
    (8192, (512, 512)),
])
def test_clamp_blocks_divides_padded_T(T, expect):
    from nanosandbox_tpu.ops.attention import _clamp_blocks, DEFAULT_BLOCK

    got = _clamp_blocks(T, DEFAULT_BLOCK, DEFAULT_BLOCK)
    assert got == expect
    Tp128 = -(-T // 128) * 128
    assert Tp128 % got[0] == 0 and Tp128 % got[1] == 0


@pytest.mark.parametrize("block", [200, 8, 1, 129, 511])
def test_clamp_blocks_off_grid_request_terminates(block):
    """Caller-supplied blocks off the 128-lane grid (e.g. 200, which
    passes _pad_qkv's %8 check) used to make the divisor search loop
    forever / go negative (ADVICE r2); they now round down to the grid."""
    from nanosandbox_tpu.ops.attention import _clamp_blocks

    bq, bk = _clamp_blocks(1024, block, block)
    assert bq % 128 == 0 and bk % 128 == 0
    assert bq >= 128 and bk >= 128
    assert 1024 % bq == 0 and 1024 % bk == 0


@pytest.mark.parametrize("T", [640, 320])
def test_flash_matches_xla_non_divisor_T(T):
    """T between block multiples must not pad past the 128 boundary
    (would waste FLOPs on pad query rows and change nothing numerically —
    this pins the parity either way)."""
    rng = np.random.default_rng(7)
    q, k, v = rand_qkv(rng, B=1, H=2, T=T, D=32)
    ref = xla_attention(q, k, v, causal=True)
    out = flash_attention(q, k, v, interpret=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=2e-5, rtol=2e-5)


# -- with_lse: the ring's block primitive ---------------------------------

def _lse_reference(q, k, v, causal=True, seed=None, rate=0.0):
    """(out, lse) via plain XLA ops; with a seed, the probabilities are
    dropped by hash_dropout_keep_mask, the kernels' own mask, and lse
    stays that of the unmasked scores."""
    from nanosandbox_tpu.ops.attention import hash_dropout_keep_mask

    B, H, T, _ = q.shape
    sm_scale = q.shape[-1] ** -0.5
    s = jnp.einsum("bhqd,bhkd->bhqk", q.astype(jnp.float32) * sm_scale,
                   k.astype(jnp.float32))
    if causal:
        mask = jnp.tril(jnp.ones((T, T), dtype=bool))
        s = jnp.where(mask[None, None], s, -1e30)
    lse = jax.scipy.special.logsumexp(s, axis=-1)
    p = jax.nn.softmax(s, -1)
    if seed is not None:
        keep = hash_dropout_keep_mask(seed, B, H, T, T, rate=rate)
        p = jnp.where(keep, p / (1.0 - rate), 0.0)
    out = jnp.einsum("bhqk,bhkd->bhqd", p, v.astype(jnp.float32))
    return out, lse


@pytest.mark.parametrize("causal", [True, False])
def test_flash_lse_matches_reference(causal):
    rng = np.random.default_rng(11)
    mk = lambda: jnp.asarray(rng.normal(size=(1, 2, 256, 32)), jnp.float32)
    q, k, v = mk(), mk(), mk()
    out, lse = flash_attention(q, k, v, None, causal, with_lse=True,
                               interpret=True)
    ref_out, ref_lse = _lse_reference(q, k, v, causal)
    assert lse.shape == (1, 2, 256)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref_out),
                               atol=2e-5, rtol=2e-5)
    np.testing.assert_allclose(np.asarray(lse), np.asarray(ref_lse),
                               atol=2e-5, rtol=2e-5)


# -- dropout_rate > 0: in-kernel attention-prob dropout --------------------

def _reference_keep_mask(seed: int, bh: int, T: int, rate: float) -> np.ndarray:
    """Rebuild the kernel's counter-hash mask with the SAME shared helpers
    on full (T, T) indices — position-keyed, so block layout is irrelevant."""
    from nanosandbox_tpu.ops.attention import _GOLDEN, _fmix32

    mix = np.asarray(_fmix32(jnp.uint32(seed)
                             ^ (jnp.uint32(bh) * jnp.uint32(_GOLDEN))))
    idx = (np.arange(T, dtype=np.uint32)[:, None] * np.uint32(T)
           + np.arange(T, dtype=np.uint32)[None, :])
    h = np.asarray(_fmix32(jnp.asarray(idx ^ mix)))
    thr = np.uint32(min(int(round(rate * 2**32)), 2**32 - 1))
    return h >= thr


def _reference_dropout_attention(q, k, v, seed: int, rate: float):
    """dropout(softmax(s)) @ v with the kernel's exact mask, in plain jnp."""
    B, H, T, D = q.shape
    sm = D ** -0.5
    s = jnp.einsum("bhqd,bhkd->bhqk", q.astype(jnp.float32) * sm,
                   k.astype(jnp.float32))
    s = jnp.where(jnp.tril(jnp.ones((T, T), bool))[None, None], s, -1e30)
    p = jax.nn.softmax(s, axis=-1)
    keep = jnp.stack([
        jnp.stack([jnp.asarray(_reference_keep_mask(seed, b * H + h_, T, rate))
                   for h_ in range(H)]) for b in range(B)])
    p = jnp.where(keep, p / (1 - rate), 0.0)
    return jnp.einsum("bhqk,bhkd->bhqd", p, v.astype(jnp.float32))


def test_flash_dropout_rate0_is_exact_flash():
    rng = np.random.default_rng(20)
    q, k, v = rand_qkv(rng, T=128, D=64)
    seed = jnp.array([77], jnp.uint32)
    base = flash_attention(q, k, v, interpret=True)
    out = flash_attention(q, k, v, seed, interpret=True)
    np.testing.assert_array_equal(np.asarray(out), np.asarray(base))
    gb = jax.grad(lambda q, k, v: flash_attention(
        q, k, v, interpret=True).sum(), argnums=(0, 1, 2))(q, k, v)
    gd = jax.grad(lambda q, k, v: flash_attention(
        q, k, v, seed, interpret=True).sum(), argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(gb, gd):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


@pytest.mark.parametrize("rate", [0.1, 0.5])
def test_flash_dropout_matches_masked_reference(rate):
    """Forward AND all three grads against a plain-jnp reference using the
    identical positional mask — proves fwd and both bwd kernels agree on
    every mask bit (the whole correctness risk of recomputed-mask dropout)."""
    rng = np.random.default_rng(21)
    q, k, v = rand_qkv(rng, B=2, H=2, T=256, D=64)
    seed_val = 12345
    seed = jnp.array([seed_val], jnp.uint32)
    w = jnp.asarray(rng.normal(size=(64,)), jnp.float32)

    ref = _reference_dropout_attention(q, k, v, seed_val, rate)
    out = flash_attention(q, k, v, seed, dropout_rate=rate, interpret=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=2e-5, rtol=2e-5)

    def loss_got(q, k, v):
        return (flash_attention(q, k, v, seed, dropout_rate=rate,
                                interpret=True) * w).sum()

    def loss_ref(q, k, v):
        return (_reference_dropout_attention(q, k, v, seed_val, rate) * w).sum()

    g = jax.grad(loss_got, argnums=(0, 1, 2))(q, k, v)
    g_ref = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(g, g_ref):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   atol=5e-5, rtol=5e-5)


def test_flash_dropout_keep_rate_and_determinism():
    """Statistical contract: drop fraction ~ Binomial(rate), masks differ
    across seeds, identical across calls with the same seed."""
    rate = 0.2
    B, H, T = 1, 2, 128
    # v = identity: each output row IS that query's dropped-prob row, so
    # the mask is directly observable from the forward output.
    q = jnp.zeros((B, H, T, T), jnp.float32)  # uniform scores
    k = jnp.zeros((B, H, T, T), jnp.float32)
    v = jnp.broadcast_to(jnp.eye(T, dtype=jnp.float32), (B, H, T, T))
    attend = lambda seed: flash_attention(
        q, k, v, jnp.array([seed], jnp.uint32), dropout_rate=rate,
        interpret=True)
    out1, out2, out3 = attend(5), attend(5), attend(6)
    np.testing.assert_array_equal(np.asarray(out1), np.asarray(out2))
    assert not np.array_equal(np.asarray(out1), np.asarray(out3))
    tril = np.tril(np.ones((T, T), bool))
    dropped = (np.asarray(out1)[:, :, tril] == 0.0)
    frac = dropped.mean()
    sd = (rate * (1 - rate) / dropped.size) ** 0.5
    assert abs(frac - rate) < 6 * sd, (frac, rate, sd)
    # Kept cells carry the 1/(1-rate) inverted-dropout scale: row i holds
    # i+1 uniform probs 1/(i+1), so kept cells of the last row must all be
    # exactly 1/(T*(1-rate)).
    last_row = np.asarray(out1)[0, 0, T - 1]
    nz = last_row[last_row > 0]
    np.testing.assert_allclose(nz, 1.0 / (T * (1 - rate)), rtol=1e-5)


def test_causal_attention_dropout_dispatches_to_pallas_kernel():
    """impl='pallas_interpret' + dropout must run the in-kernel path (not
    silently fall back to XLA as rounds 1-3 did): kernel masks are a pure
    function of (seed, positions), so two calls with the SAME rng must
    agree — the XLA path consumes the rng differently."""
    rng = np.random.default_rng(22)
    q, k, v = rand_qkv(rng, T=128, D=64)
    key = jax.random.PRNGKey(3)
    out = causal_attention(q, k, v, impl="pallas_interpret",
                           dropout_rate=0.25, dropout_rng=key)
    seed = jax.random.bits(key, (1,), jnp.uint32)
    direct = flash_attention(q, k, v, seed, dropout_rate=0.25,
                             interpret=True)
    np.testing.assert_array_equal(np.asarray(out), np.asarray(direct))
    # and the mean over many cells is ~ the no-dropout output (unbiased)
    base = flash_attention(q, k, v, interpret=True)
    assert float(jnp.abs(out.mean() - base.mean())) < 0.05


def test_flash_lse_gradients_including_dlse():
    """A loss that consumes BOTH outputs exercises the dlse fold-in
    (ds = p * (dp - (drow - dlse))) — exactly what the ring's
    logsumexp-weighted merge does in its backward."""
    rng = np.random.default_rng(12)
    mk = lambda: jnp.asarray(rng.normal(size=(1, 2, 256, 32)), jnp.float32)
    q, k, v = mk(), mk(), mk()
    w = jnp.asarray(rng.normal(size=(1, 2, 256)), jnp.float32)

    def loss_flash(q, k, v):
        out, lse = flash_attention(q, k, v, with_lse=True, interpret=True)
        return (out ** 2).sum() + (lse * w).sum()

    def loss_ref(q, k, v):
        out, lse = _lse_reference(q, k, v, True)
        return (out ** 2).sum() + (lse * w).sum()

    g = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
    g_ref = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(g, g_ref):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   atol=5e-4, rtol=5e-4)


# ---------------------------------------------------------------------------
# The one backward: compact statistics, one pass, against XLA
# ---------------------------------------------------------------------------

def _assert_grads_close(got, want, tol):
    for a, b, name in zip(got, want, "qkv"):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=tol,
                                   rtol=tol, err_msg=f"d{name} diverged")


@pytest.mark.parametrize("T,D", [(128, 64), (256, 32), (640, 64), (256, 64),
                                 (200, 64), (96, 32)])
def test_one_pass_backward_matches_xla(T, D):
    """dQ, dK and dV from the one key-parallel pass, reading the logsumexp
    as (T // 128, 128) rows, against XLA's: one stat row, several, blocks
    clamped to 128 (T = 640), and T off the 128 grid (200 and 96 pad to
    256 and 128; D = 32 pads to 128 lanes)."""
    rng = np.random.default_rng(21)
    q, k, v = rand_qkv(rng, T=T, D=D)

    def grads(attend):
        return jax.grad(lambda q, k, v: (attend(q, k, v) ** 2).sum(),
                        argnums=(0, 1, 2))(q, k, v)

    _assert_grads_close(
        grads(lambda q, k, v: flash_attention(q, k, v, interpret=True)),
        grads(lambda q, k, v: xla_attention(q, k, v, causal=True)), 2e-4)


@pytest.mark.parametrize("T,D,dropout,with_lse", [
    (256, 32, True, False), (640, 32, False, True), (256, 64, True, True),
    (200, 64, True, True)])
def test_one_pass_backward_matches_reference_dropout_dlse(T, D, dropout,
                                                          with_lse):
    """The other branches of the one backward against the dense reference:
    the positional keep-mask (hash_dropout_keep_mask's), a dlse cotangent
    riding beside the logsumexp in the stats operand (two stacked rows a
    head), and both at once (the ring block's surface), on and off the
    block grid."""
    rng = np.random.default_rng(23)
    q, k, v = rand_qkv(rng, B=1, H=2, T=T, D=D)
    seed = jnp.asarray([1234], jnp.uint32) if dropout else None
    w = jnp.asarray(rng.normal(size=(1, 2, T)), jnp.float32)

    def grads(attend):
        def loss(q, k, v):
            out, lse = attend(q, k, v)
            return (out ** 2).sum() + ((lse * w).sum() if with_lse else 0.0)
        return jax.grad(loss, argnums=(0, 1, 2))(q, k, v)

    def flash(q, k, v):
        got = flash_attention(q, k, v, seed,
                              dropout_rate=0.2 if dropout else 0.0,
                              with_lse=with_lse, interpret=True)
        return got if with_lse else (got, None)

    _assert_grads_close(
        grads(flash),
        grads(lambda q, k, v: _lse_reference(q, k, v, seed=seed, rate=0.2)),
        5e-4 if with_lse else 2e-4)


def test_train_config_has_one_statistic_layout():
    """attention_stat_layout stays a TrainConfig key only because the
    benchmark's files name it: 'compact' is its one value, from a
    constructor, a --key=value flag or a checkpoint's saved config."""
    from nanosandbox_tpu.config import TrainConfig, load_config

    assert TrainConfig().attention_stat_layout == "compact"
    with pytest.raises(ValueError, match="only statistic layout"):
        TrainConfig(attention_stat_layout="replicated")
    with pytest.raises(ValueError, match="only statistic layout"):
        load_config(["--attention_stat_layout=replicated"])
    assert load_config(["--attention_stat_layout=compact"]
                       ).attention_stat_layout == "compact"


# -- the (B, T, heads*D) entry: kernels on the model's own layout ----------

def _heads_major(x, H):
    B, T, C = x.shape
    return x.reshape(B, T, H, C // H).transpose(0, 2, 1, 3)


def _xla_on_qkv(qkv, H):
    """xla_attention on the same (B, T, 3C) input, back to (B, T, C)."""
    B, T, C3 = qkv.shape
    q, k, v = (_heads_major(x, H) for x in jnp.split(qkv, 3, axis=-1))
    o = xla_attention(q, k, v, causal=True)
    return o.transpose(0, 2, 1, 3).reshape(B, T, C3 // 3)


@pytest.mark.parametrize("T", [256, 640, 1024])
@pytest.mark.parametrize("H,D", [(12, 64), (16, 64), (2, 128)])
def test_qkv_entry_matches_xla(H, D, T):
    """Forward and the gradient with respect to qkv, head pairs (D = 64)
    and whole-head blocks (D = 128), at T that clamp the blocks to 256,
    128 and 512."""
    from nanosandbox_tpu.ops.attention import flash_attention_qkv

    rng = np.random.default_rng(30)
    qkv = jnp.asarray(rng.normal(size=(1, T, 3 * H * D)), jnp.float32)
    w = jnp.asarray(rng.normal(size=(1, T, H * D)), jnp.float32)
    out = flash_attention_qkv(qkv, None, H, 0.0, True)
    np.testing.assert_allclose(np.asarray(out),
                               np.asarray(_xla_on_qkv(qkv, H)),
                               atol=2e-5, rtol=2e-5)
    g = jax.grad(lambda x: (flash_attention_qkv(
        x, None, H, 0.0, True) * w).sum())(qkv)
    g_ref = jax.grad(lambda x: (_xla_on_qkv(x, H) * w).sum())(qkv)
    np.testing.assert_allclose(np.asarray(g), np.asarray(g_ref),
                               atol=2e-4, rtol=2e-4)


def test_qkv_entry_dropout_mask_and_gradients():
    """In the head-pair programs the keep-mask is hash_dropout_keep_mask's
    bit for bit (read off the forward: uniform scores, v one-hot over one
    64-key block at a time), and output and gradients equal the
    (B, H, T, D) entry's for the same seed."""
    from nanosandbox_tpu.ops.attention import (flash_attention_qkv,
                                               hash_dropout_keep_mask)

    B, H, D, T, rate = 2, 2, 64, 256, 0.1
    seed = jnp.array([4242], jnp.uint32)
    blocks = []
    for c in range(T // D):
        v = jnp.zeros((T, D), jnp.float32).at[c * D:(c + 1) * D].set(
            jnp.eye(D))
        qkv = jnp.concatenate(
            [jnp.zeros((B, T, 2 * H * D), jnp.float32),
             jnp.broadcast_to(jnp.tile(v, (1, H)), (B, T, H * D))], axis=-1)
        o = flash_attention_qkv(qkv, seed, H, rate, True)
        blocks.append(_heads_major(o, H))              # (B, H, T, 64 keys)
    seen = np.asarray(jnp.concatenate(blocks, axis=-1)) != 0.0
    want = np.asarray(hash_dropout_keep_mask(seed, B, H, T, T, rate=rate))
    tril = np.tril(np.ones((T, T), bool))
    np.testing.assert_array_equal(seen[:, :, tril], want[:, :, tril])
    assert 0.05 < 1.0 - want[:, :, tril].mean() < 0.15

    rng = np.random.default_rng(31)
    qkv = jnp.asarray(rng.normal(size=(B, T, 3 * H * D)), jnp.float32)
    w = jnp.asarray(rng.normal(size=(B, T, H * D)), jnp.float32)

    def old(x):
        q, k, v = (_heads_major(t, H) for t in jnp.split(x, 3, axis=-1))
        o = flash_attention(q, k, v, seed, dropout_rate=rate,
                            interpret=True)
        return o.transpose(0, 2, 1, 3).reshape(B, T, H * D)

    new = lambda x: flash_attention_qkv(x, seed, H, rate, True)
    np.testing.assert_allclose(np.asarray(new(qkv)), np.asarray(old(qkv)),
                               atol=1e-6, rtol=1e-6)
    g_new = jax.grad(lambda x: (new(x) * w).sum())(qkv)
    g_old = jax.grad(lambda x: (old(x) * w).sum())(qkv)
    np.testing.assert_allclose(np.asarray(g_new), np.asarray(g_old),
                               atol=1e-5, rtol=1e-5)


def _attn_module_jaxprs(n_head, n_embd, T, *, mesh=None, cached=False,
                        grad=False, **cfg_kw):
    """The jaxpr of CausalSelfAttention.apply (or of its gradient with
    respect to the input) on abstract operands: nothing runs."""
    from nanosandbox_tpu.config import GPTConfig
    from nanosandbox_tpu.models.gpt import CausalSelfAttention

    cfg = GPTConfig(n_layer=1, n_head=n_head, n_embd=n_embd, block_size=T,
                    vocab_size=64, dropout=0.0, bias=False,
                    attention_impl="pallas_interpret",
                    compute_dtype="float32", **cfg_kw)
    mod = CausalSelfAttention(cfg, mesh=mesh)
    B = 2
    x = jnp.zeros((B, T, n_embd), jnp.float32)
    params = jax.eval_shape(
        lambda: mod.init(jax.random.PRNGKey(0), x[:, :8], True))
    if cached:
        kv = jnp.zeros((B, n_head, 2 * T, n_embd // n_head), jnp.float32)

        def fn(p, x):
            return mod.apply(p, x, True, (kv, kv), 0)[0]
    else:
        def fn(p, x):
            return mod.apply(p, x, True)
    if grad:
        return jax.make_jaxpr(jax.grad(
            lambda p, x: fn(p, x).sum(), argnums=(0, 1)))(params, x), B
    return jax.make_jaxpr(fn)(params, x), B


def _walk_eqns(jaxpr, *, into_kernels=False):
    """Every equation of a jaxpr and of the jaxprs its equations carry
    (custom_vjp bodies, pjit, shard_map); Pallas kernel bodies only on
    request: they work on tiles in VMEM, not on arrays in HBM."""
    for eqn in jaxpr.eqns:
        yield eqn
        if eqn.primitive.name == "pallas_call" and not into_kernels:
            continue
        for val in eqn.params.values():
            for sub in (val if isinstance(val, (list, tuple)) else [val]):
                inner = getattr(sub, "jaxpr", sub)
                if hasattr(inner, "eqns"):
                    yield from _walk_eqns(inner, into_kernels=into_kernels)


def _activation_transposes(closed, floor):
    return [e for e in _walk_eqns(closed.jaxpr)
            if e.primitive.name == "transpose"
            and e.outvars[0].aval.size >= floor]


def _kernel_operand_ranks(closed):
    return sorted({len(v.aval.shape) for e in _walk_eqns(closed.jaxpr)
                   if e.primitive.name == "pallas_call"
                   for v in e.invars if v.aval.size > 8})


def _keep_bhtd(monkeypatch):
    """Send every shape to the (B, H, T, D) entry: the control that the
    (B, T, heads*D) entry is what changes a program."""
    from nanosandbox_tpu.ops import attention

    monkeypatch.setattr(attention, "qkv_layout_supported", lambda *a: False)


@pytest.mark.parametrize("grad", [False, True], ids=["forward", "grad"])
def test_new_path_has_no_activation_sized_transpose(grad, monkeypatch):
    """CausalSelfAttention on the (B, T, heads*D) entry, forward and
    jax.grad: no transpose of an activation-sized array in the jaxpr (a
    weight gradient's (3C, C) -> (C, 3C) is smaller and allowed). The same
    module kept on the (B, H, T, D) entry is the control that the detector
    sees the old path's transposes."""
    T, C = 256, 128
    new, B = _attn_module_jaxprs(2, C, T, grad=grad)
    assert _kernel_operand_ranks(new)[:1] == [3]           # (B, T, 3C)
    assert _activation_transposes(new, B * T * C) == []
    _keep_bhtd(monkeypatch)
    old, _ = _attn_module_jaxprs(2, C, T, grad=grad)
    assert len(_activation_transposes(old, B * T * C)) >= 4


@pytest.mark.parametrize("case", ["25x64", "12x32", "model2", "cache",
                                  "data2", "12x64"])
def test_layout_dispatch(case):
    """What lands on the old (B, H, T, D) entry: head counts that do not
    tile 128 lanes (GPT-2 XL's 25 x 64; 12 x 32), a bound mesh with
    model = 2 or data = 2, a cache. 12 x 64 on no mesh is the control that
    takes the new entry."""
    from nanosandbox_tpu.config import GPTConfig
    from nanosandbox_tpu.models.gpt import attn_layout
    from nanosandbox_tpu.ops.attention import attention_layout

    T = 256
    H, D = {"25x64": (25, 64), "12x32": (12, 32)}.get(case, (12, 64))
    mesh = None
    if case in ("model2", "data2"):
        from nanosandbox_tpu.parallel.mesh import make_mesh

        tp = 2 if case == "model2" else 1
        mesh = make_mesh(mesh_dp=2 // tp, mesh_tp=tp,
                         devices=jax.devices()[:2])
    closed, B = _attn_module_jaxprs(H, H * D, T, mesh=mesh,
                                    cached=case == "cache")
    cfg = GPTConfig(n_layer=1, n_head=H, n_embd=H * D, block_size=T,
                    vocab_size=64, attention_impl="pallas_interpret")
    want = "btc" if case == "12x64" else "bhtd"
    if case != "cache":      # a cache is the module's own branch
        assert attn_layout(cfg, mesh, T) == want
    kernels = [e for e in _walk_eqns(closed.jaxpr)
               if e.primitive.name == "pallas_call"]
    ranks = _kernel_operand_ranks(closed)
    moved = _activation_transposes(closed, B * T * H * D)
    if want == "btc":
        assert ranks[:1] == [3] and not moved, (ranks, moved)
    else:
        # heads-major operands ((B*H, T, D) kernels, or the cache
        # branch's XLA scores) behind activation-sized transposes
        assert moved and 3 * H * D not in {
            v.aval.shape[-1] for e in kernels for v in e.invars}
    # shapes alone: T off the 128 grid and CPU 'auto' keep the old entry
    assert attention_layout(12, 64, 200, impl="pallas_interpret") == "bhtd"
    assert attention_layout(12, 64, 256, impl="auto") == "bhtd"
    assert attention_layout(12, 64, 256, impl="xla") == "bhtd"
    assert attention_layout(12, 64, 256, impl="ring") == "bhtd"


@pytest.mark.parametrize("dropout", [0.0, 0.1])
def test_gpt_loss_and_gradients_equal_across_layouts(dropout, monkeypatch):
    """The whole model, both HBM interfaces: same parameters, same batch,
    same dropout rng -> the same loss and the same gradient for every
    parameter (the control keeps the (B, H, T, D) entry; the keep-masks
    agree because both key them on the global (b, h, q, k))."""
    from nanosandbox_tpu.config import GPTConfig
    from nanosandbox_tpu.models.gpt import GPT, attn_layout

    T = 128
    rng = np.random.default_rng(40)
    idx = jnp.asarray(rng.integers(0, 64, size=(2, T)), jnp.int32)
    tgt = jnp.asarray(rng.integers(0, 64, size=(2, T)), jnp.int32)

    def loss_and_grads(want):
        cfg = GPTConfig(n_layer=2, n_head=2, n_embd=128, block_size=T,
                        vocab_size=64, dropout=dropout, bias=False,
                        attention_impl="pallas_interpret",
                        compute_dtype="float32")
        assert attn_layout(cfg, None, T) == want
        model = GPT(cfg)
        params = model.init(jax.random.PRNGKey(0), idx, deterministic=True)

        def loss(p):
            logits = model.apply(p, idx, deterministic=dropout == 0.0,
                                 rngs={"dropout": jax.random.PRNGKey(7)})
            logits = logits[0] if isinstance(logits, tuple) else logits
            logp = jax.nn.log_softmax(logits.astype(jnp.float32))
            return -jnp.take_along_axis(logp, tgt[..., None], -1).mean()

        return jax.value_and_grad(loss)(params)

    l_new, g_new = loss_and_grads("btc")
    _keep_bhtd(monkeypatch)
    l_old, g_old = loss_and_grads("bhtd")
    np.testing.assert_allclose(float(l_new), float(l_old), rtol=1e-6)
    for a, b in zip(jax.tree.leaves(g_new), jax.tree.leaves(g_old)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   atol=1e-6, rtol=1e-4)
