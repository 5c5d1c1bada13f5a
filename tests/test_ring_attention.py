"""Ring attention (sequence/context parallelism) on 8 virtual CPU devices.

Correctness bar: ring attention over a sharded sequence must match plain
XLA attention over the full sequence — forward AND gradients — because it
computes the exact same math, just blockwise around the ring."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from nanosandbox_tpu.ops.attention import xla_attention
from nanosandbox_tpu.ops.ring_attention import ring_attention_sharded
from nanosandbox_tpu.parallel.mesh import (batch_sharding, make_mesh,
                                           set_current_mesh)


def _qkv(B=2, H=4, T=64, D=16, seed=0):
    rng = np.random.default_rng(seed)
    mk = lambda: jnp.asarray(rng.normal(size=(B, H, T, D)), jnp.float32)
    return mk(), mk(), mk()


@pytest.mark.parametrize("sp", [2, 4, 8])
def test_ring_matches_xla_forward(sp):
    mesh = make_mesh(mesh_dp=1, mesh_sp=sp, devices=jax.devices()[:sp])
    q, k, v = _qkv()
    ref = xla_attention(q, k, v, causal=True)
    out = jax.jit(lambda q, k, v: ring_attention_sharded(
        q, k, v, mesh=mesh))(q, k, v)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=2e-5, rtol=2e-5)


def test_ring_matches_xla_gradients():
    mesh = make_mesh(mesh_dp=2, mesh_sp=4)  # B=2 over dp=2, T over sp=4
    q, k, v = _qkv()

    def loss_ring(q, k, v):
        return (ring_attention_sharded(q, k, v, mesh=mesh) ** 2).sum()

    def loss_ref(q, k, v):
        return (xla_attention(q, k, v, causal=True) ** 2).sum()

    g_ring = jax.jit(jax.grad(loss_ring, argnums=(0, 1, 2)))(q, k, v)
    g_ref = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(g_ring, g_ref):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   atol=5e-4, rtol=5e-4)


def test_ring_seq_axis_one_degenerates():
    mesh = make_mesh(mesh_dp=1, devices=jax.devices()[:1])  # seq axis size 1
    q, k, v = _qkv(T=32)
    ref = xla_attention(q, k, v, causal=True)
    out = ring_attention_sharded(q, k, v, mesh=mesh)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=2e-5, rtol=2e-5)


def test_ring_rejects_indivisible_seq():
    mesh = make_mesh(mesh_dp=2, mesh_sp=4)
    q, k, v = _qkv(T=30)
    with pytest.raises(ValueError, match="not divisible"):
        ring_attention_sharded(q, k, v, mesh=mesh)


def test_ring_end_to_end_training(tiny_cfg):
    """Tiny GPT trains under mesh_sp=4 with ring attention; loss falls and
    the first-step loss matches the non-sequence-parallel run (same data)."""
    from nanosandbox_tpu.train import Trainer

    cfg = tiny_cfg.replace(batch_size=8, mesh_dp=2, mesh_sp=4,
                           attention_impl="ring")
    trainer = Trainer(cfg)
    state = trainer.init_state()
    train_step, _ = trainer.compiled_steps()
    loader = trainer.make_loader("train", prefetch=False)
    losses = []
    rng = jax.random.key(0)
    for _ in range(8):
        xb, yb = next(loader)
        state, m = train_step(state, trainer.to_global(xb),
                              trainer.to_global(yb), rng)
        losses.append(float(m["loss"]))
    assert np.isfinite(losses).all()
    assert losses[-1] < losses[0]

    # parity with a plain-DP run on identical data
    cfg2 = tiny_cfg.replace(batch_size=8, mesh_dp=8)
    t2 = Trainer(cfg2)
    s2 = t2.init_state()
    step2, _ = t2.compiled_steps()
    loader2 = t2.make_loader("train", prefetch=False)
    xb, yb = next(loader2)
    _, m2 = step2(s2, t2.to_global(xb), t2.to_global(yb), jax.random.key(0))
    assert float(m2["loss"]) == pytest.approx(losses[0], rel=1e-4)


def test_ring_trainer_with_dp_and_coexisting_trainer(tiny_cfg):
    """Regressions: (a) Trainer init must work for ring configs whose
    data*fsdp shards exceed the old fixed dummy batch of 2; (b) a second
    Trainer must not silently steal the ring Trainer's mesh (the model
    binds its mesh explicitly)."""
    import jax

    from nanosandbox_tpu.train import Trainer

    cfg = tiny_cfg.replace(batch_size=8, mesh_dp=4, mesh_sp=2,
                           attention_impl="ring")
    trainer = Trainer(cfg)
    state = trainer.init_state()  # dummy init batch respects the shardings

    # Constructing another trainer overwrites the *global* mesh...
    other = Trainer(tiny_cfg.replace(batch_size=8, mesh_dp=8))
    assert other.mesh is not trainer.mesh

    # ...but the ring trainer still traces with ITS OWN mesh afterwards.
    train_step, _ = trainer.compiled_steps()
    loader = trainer.make_loader("train", prefetch=False)
    xb, yb = next(loader)
    _, m = train_step(state, trainer.to_global(xb), trainer.to_global(yb),
                      jax.random.key(0))
    assert np.isfinite(float(m["loss"]))


def test_trainer_validates_ring_config(tiny_cfg):
    from nanosandbox_tpu.train import Trainer

    with pytest.raises(ValueError, match="requires attention_impl='ring'"):
        Trainer(tiny_cfg.replace(mesh_dp=4, mesh_sp=2))
    with pytest.raises(ValueError, match="block_size"):
        Trainer(tiny_cfg.replace(mesh_dp=1, mesh_sp=8, block_size=60,
                                 attention_impl="ring"))
    # dropout + ring is SUPPORTED as of round 5 (global-position hash
    # masks); construction must succeed.
    Trainer(tiny_cfg.replace(mesh_dp=4, mesh_sp=2, dropout=0.1,
                             attention_impl="ring"))


def teardown_module():
    set_current_mesh(None)


# -- zigzag layout (VERDICT.md round-1 stretch #10) -----------------------

def test_zigzag_permutation_inverse():
    from nanosandbox_tpu.ops.ring_attention import zigzag_permutation

    idx, inv = zigzag_permutation(64, 4)
    x = np.arange(64)
    assert (x[idx][inv] == x).all()
    # device 0's shard = first early + last late half-chunk
    h = 64 // 8
    assert (idx[:h] == np.arange(0, h)).all()
    assert (idx[h:2 * h] == np.arange(64 - h, 64)).all()


@pytest.mark.parametrize("sp", [2, 4, 8])
@pytest.mark.parametrize("layout", ["zigzag", "contiguous"])
def test_ring_layouts_match_xla_forward(sp, layout):
    mesh = make_mesh(mesh_dp=1, mesh_sp=sp, devices=jax.devices()[:sp])
    q, k, v = _qkv(seed=3)
    ref = xla_attention(q, k, v, causal=True)
    out = jax.jit(lambda q, k, v: ring_attention_sharded(
        q, k, v, mesh=mesh, layout=layout))(q, k, v)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=2e-5, rtol=2e-5)


def test_zigzag_matches_xla_gradients():
    mesh = make_mesh(mesh_dp=2, mesh_sp=4)
    q, k, v = _qkv(seed=4)

    def loss_zig(q, k, v):
        return (ring_attention_sharded(q, k, v, mesh=mesh,
                                       layout="zigzag") ** 2).sum()

    def loss_ref(q, k, v):
        return (xla_attention(q, k, v, causal=True) ** 2).sum()

    g_zig = jax.jit(jax.grad(loss_zig, argnums=(0, 1, 2)))(q, k, v)
    g_ref = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(g_zig, g_ref):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   atol=5e-4, rtol=5e-4)


def test_zigzag_falls_back_when_T_not_2cp_divisible():
    """T=40 with sp=4: divisible by cp but not 2*cp — zigzag silently
    uses the (exact) contiguous path."""
    mesh = make_mesh(mesh_dp=2, mesh_sp=4)
    q, k, v = _qkv(T=40, seed=5)
    ref = xla_attention(q, k, v, causal=True)
    out = jax.jit(lambda q, k, v: ring_attention_sharded(
        q, k, v, mesh=mesh, layout="zigzag"))(q, k, v)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=2e-5, rtol=2e-5)


def test_zigzag_end_to_end_training_matches_dp(tiny_cfg):
    """Tiny GPT under mesh_sp=4 + zigzag ring: first-step loss matches a
    plain-DP run on identical data (layout is invisible to the math)."""
    from nanosandbox_tpu.train import Trainer

    cfg = tiny_cfg.replace(batch_size=8, mesh_dp=2, mesh_sp=4,
                           attention_impl="ring", ring_layout="zigzag")
    trainer = Trainer(cfg)
    state = trainer.init_state()
    train_step, _ = trainer.compiled_steps()
    loader = trainer.make_loader("train", prefetch=False)
    xb, yb = next(loader)
    _, m = train_step(state, trainer.to_global(xb), trainer.to_global(yb),
                      jax.random.key(0))

    cfg2 = tiny_cfg.replace(batch_size=8, mesh_dp=8)
    t2 = Trainer(cfg2)
    s2 = t2.init_state()
    step2, _ = t2.compiled_steps()
    loader2 = t2.make_loader("train", prefetch=False)
    xb2, yb2 = next(loader2)
    _, m2 = step2(s2, t2.to_global(xb2), t2.to_global(yb2), jax.random.key(0))
    assert float(m2["loss"]) == pytest.approx(float(m["loss"]), rel=1e-4)


# -- Pallas flash blocks inside the ring (round-2 VERDICT weak #1) --------

@pytest.mark.parametrize("sp,T,layout", [
    (2, 512, "zigzag"),      # half-chunk h = 128
    (4, 1024, "zigzag"),     # h = 128 across 4 devices
    (2, 256, "contiguous"),  # full chunk Tc = 128
])
def test_ring_flash_blocks_match_xla(sp, T, layout):
    """Ring with the real flash kernel per block (interpret mode on CPU)
    must equal plain full-sequence attention, like the einsum body does."""
    mesh = make_mesh(mesh_dp=1, mesh_sp=sp, devices=jax.devices()[:sp])
    q, k, v = _qkv(B=1, H=2, T=T, D=16, seed=7)
    ref = xla_attention(q, k, v, causal=True)
    out = jax.jit(lambda q, k, v: ring_attention_sharded(
        q, k, v, mesh=mesh, layout=layout,
        block_impl="pallas_interpret"))(q, k, v)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=2e-5, rtol=2e-5)


def test_ring_flash_blocks_gradients():
    mesh = make_mesh(mesh_dp=1, mesh_sp=2, devices=jax.devices()[:2])
    q, k, v = _qkv(B=1, H=2, T=512, D=16, seed=8)

    def loss_ring(q, k, v):
        return (ring_attention_sharded(
            q, k, v, mesh=mesh, layout="zigzag",
            block_impl="pallas_interpret") ** 2).sum()

    def loss_ref(q, k, v):
        return (xla_attention(q, k, v, causal=True) ** 2).sum()

    g = jax.jit(jax.grad(loss_ring, argnums=(0, 1, 2)))(q, k, v)
    g_ref = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(g, g_ref):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   atol=5e-4, rtol=5e-4)


def test_ring_block_impl_auto_resolution(monkeypatch):
    """'auto' resolves per backend, with no probe in between: the einsum
    body on cpu, the flash body where the backend says tpu; unaligned
    chunks force einsum regardless of backend."""
    from nanosandbox_tpu.ops import attention
    from nanosandbox_tpu.ops.ring_attention import _resolve_block_impl

    assert _resolve_block_impl("xla", 128) == "xla"
    with pytest.raises(ValueError, match="ring_block_impl"):
        _resolve_block_impl("pallas", 77)  # pinned + unaligned: loud error
    assert _resolve_block_impl("auto", 64) == "xla"       # unaligned
    assert _resolve_block_impl("auto", 128) == "xla"      # cpu
    monkeypatch.setattr(attention.jax, "default_backend", lambda: "tpu")
    assert _resolve_block_impl("auto", 128) == "pallas"
    assert _resolve_block_impl("auto", 64) == "xla"       # still unaligned
    assert not hasattr(attention, "pallas_compile_probe")


def test_model_ring_attention_dropout_trains_directly():
    """Round 5: ring attention + dropout is supported (global-position
    hash masks). The direct model path must trace AND regularize — the
    non-deterministic forward must differ from the deterministic one."""
    import jax.numpy as jnp

    from nanosandbox_tpu.config import GPTConfig
    from nanosandbox_tpu.models.gpt import GPT

    mesh = make_mesh(mesh_dp=1, mesh_sp=2, devices=jax.devices()[:2])
    cfg = GPTConfig(n_layer=1, n_head=2, n_embd=16, block_size=16,
                    vocab_size=32, dropout=0.1, attention_impl="ring",
                    compute_dtype="float32")
    model = GPT(cfg, mesh=mesh)
    x = jnp.zeros((2, 16), jnp.int32)
    variables = model.init(jax.random.key(0), x, deterministic=True)
    det = model.apply(variables, x, deterministic=True)
    reg = model.apply(variables, x, deterministic=False,
                      rngs={"dropout": jax.random.key(1)})
    assert np.isfinite(np.asarray(reg)).all()
    assert not np.allclose(np.asarray(det), np.asarray(reg))


def test_pinned_pallas_unaligned_chunk_raises_ring_level_error():
    """A pinned ring_block_impl='pallas' with a non-128-multiple per-device
    chunk must fail with an error naming ring_block_impl and the chunk
    (ADVICE r3) — not a block-divisibility ValueError deep in _pad_qkv."""
    mesh = make_mesh(mesh_dp=1, mesh_sp=2, devices=jax.devices()[:2])
    q, k, v = _qkv(T=64)  # 32 per device: unaligned
    with pytest.raises(ValueError, match="ring_block_impl.*multiple of 128"):
        jax.jit(lambda q, k, v: ring_attention_sharded(
            q, k, v, mesh=mesh, block_impl="pallas"))(q, k, v)


# -- dropout in the ring (round-5 VERDICT next #5) -------------------------
#
# The keep-mask is a hash of GLOBAL (q_pos, k_pos), so a masked-XLA dense
# reference built from the same hash must match the ring output exactly —
# per layout (contiguous + zigzag) and per block impl (xla +
# pallas_interpret), at sp=2.


def _masked_dense_reference(q, k, v, seed, rate, hash_seq_len):
    """Full attention with the hash keep-mask applied to normalized
    probabilities — the ground truth every ring variant must reproduce."""
    from nanosandbox_tpu.ops.attention import hash_dropout_keep_mask

    B, H, T, D = q.shape
    sm_scale = D ** -0.5
    s = jnp.einsum("bhqd,bhkd->bhqk", q.astype(jnp.float32) * sm_scale,
                   k.astype(jnp.float32))
    mask = jnp.tril(jnp.ones((T, T), dtype=bool))
    s = jnp.where(mask[None, None], s, -1e30)
    p = jax.nn.softmax(s, axis=-1)
    keep = hash_dropout_keep_mask(seed, B, H, T, T,
                                  hash_seq_len=hash_seq_len, rate=rate)
    p = jnp.where(keep, p / (1.0 - rate), 0.0)
    return jnp.einsum("bhqk,bhkd->bhqd", p, v.astype(jnp.float32))


@pytest.mark.parametrize("layout", ["contiguous", "zigzag"])
@pytest.mark.parametrize("block_impl", ["xla", "pallas_interpret"])
def test_ring_dropout_matches_masked_reference(layout, block_impl):
    sp = 2
    mesh = make_mesh(mesh_dp=1, mesh_sp=sp, devices=jax.devices()[:sp])
    # pallas blocks need 128-aligned per-call chunks; zigzag halves the
    # chunk (T / (2*sp)), so T=512 keeps both layouts aligned at sp=2.
    T = 512 if block_impl == "pallas_interpret" else 64
    q, k, v = _qkv(T=T)
    seed = jnp.asarray([1234], jnp.uint32)
    rate = 0.2
    ref = _masked_dense_reference(q, k, v, seed, rate, hash_seq_len=T)
    out = jax.jit(lambda q, k, v: ring_attention_sharded(
        q, k, v, mesh=mesh, layout=layout, block_impl=block_impl,
        dropout_rate=rate, dropout_seed=seed))(q, k, v)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=5e-5, rtol=5e-5)


def test_ring_dropout_gradients_flow_and_seed_matters():
    mesh = make_mesh(mesh_dp=2, mesh_sp=4)
    q, k, v = _qkv()
    s1 = jnp.asarray([7], jnp.uint32)
    s2 = jnp.asarray([8], jnp.uint32)

    def loss(q, k, v, seed):
        return (ring_attention_sharded(
            q, k, v, mesh=mesh, dropout_rate=0.2, dropout_seed=seed,
        ) ** 2).sum()

    val1, grads = jax.jit(jax.value_and_grad(loss, argnums=(0, 1, 2)))(
        q, k, v, s1)
    val1b = jax.jit(loss)(q, k, v, s1)
    val2 = jax.jit(loss)(q, k, v, s2)
    assert np.isfinite(float(val1))
    assert float(val1) == pytest.approx(float(val1b))  # deterministic
    assert float(val1) != pytest.approx(float(val2))   # seed matters
    for g in grads:
        assert np.isfinite(np.asarray(g)).all()
        assert float(jnp.abs(g).max()) > 0


def test_ring_dropout_batch_shards_draw_distinct_masks():
    """With batch sharded over dp, each global row must draw its own
    dropout stream — two identical batch rows on different devices must
    NOT produce identical outputs."""
    mesh = make_mesh(mesh_dp=2, mesh_sp=2, devices=jax.devices()[:4])
    q, k, v = _qkv(B=2)
    # Duplicate row 0 into row 1: without per-shard b_off the two rows
    # (placed on different dp shards) would get identical masks.
    q = q.at[1].set(q[0]); k = k.at[1].set(k[0]); v = v.at[1].set(v[0])
    seed = jnp.asarray([42], jnp.uint32)
    out = jax.jit(lambda q, k, v: ring_attention_sharded(
        q, k, v, mesh=mesh, dropout_rate=0.3, dropout_seed=seed))(q, k, v)
    assert not np.allclose(np.asarray(out[0]), np.asarray(out[1]))
