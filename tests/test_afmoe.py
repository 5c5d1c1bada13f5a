"""The ``afmoe`` family (models/afmoe.py, ops/moe.py, the grouped-query
windowed kernels of ops/attention.py) against the plain reference
``chipbench/reference/afmoe.py``, which imports nothing of the program.
Small sizes, CPU."""

import dataclasses
import functools
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from chipbench import weights_afmoe
from chipbench.reference import afmoe as ref
from nanosandbox_tpu.config import AfmoeConfig, TrainConfig
from nanosandbox_tpu.models import afmoe, experts
from nanosandbox_tpu.ops import attention as A
from nanosandbox_tpu.ops import moe

SIZES = {
    "n_layer": 3, "n_head": 4, "n_kv_head": 2, "head_dim": 16, "n_embd": 32,
    "vocab_size": 96, "block_size": 64,
    "layer_types": ("sliding", "full", "sliding"), "sliding_window": 16,
    "num_dense_layers": 1, "intermediate_size": 48,
    "moe_intermediate_size": 24, "num_experts": 8, "num_experts_per_tok": 2,
    "experts_held": (2, 4), "route_scale": 2.0, "route_norm": True,
    "mup_enabled": True, "rope_theta": 10000.0, "rms_norm_eps": 1e-5,
}


def train_cfg(**kw) -> TrainConfig:
    s = SIZES
    base = dict(
        model_family="afmoe", layer_types=",".join(s["layer_types"]),
        compute_dtype="float32",
        **{k: s[k] for k in s if k not in ("layer_types", "mup_enabled")})
    return TrainConfig(**{**base, **kw})


def model_cfg(**kw) -> AfmoeConfig:
    return AfmoeConfig.from_train_config(train_cfg(**kw), SIZES["vocab_size"])


@pytest.fixture(scope="module")
def seeded():
    params = weights_afmoe.make_params(SIZES, weights_afmoe.seed_key(3))
    x = jax.random.randint(jax.random.key(1), (2, 65), 0, SIZES["vocab_size"])
    return params, x[:, :-1], x[:, 1:]


@pytest.fixture(scope="module")
def ref_loss_and_grad(seeded):
    params, x, y = seeded
    with jax.default_matmul_precision("highest"):
        return jax.jit(lambda p: ref.loss_and_grad(p, x, y, SIZES))(params)


def program_loss_and_grad(cfg, params, x, y):
    return jax.jit(jax.value_and_grad(
        lambda p: program_loss(cfg, p, x, y), has_aux=True))(params)


def program_loss(cfg, params, x, y):
    from nanosandbox_tpu.models.loss import chunked_cross_entropy_loss

    hidden, aux = afmoe.Afmoe(cfg).apply({"params": params}, x,
                                         return_hidden=True)
    return chunked_cross_entropy_loss(
        hidden, params["lm_head"], y, chunk_size=32,
        compute_dtype=cfg.compute_dtype), aux


def flat(tree):
    return weights_afmoe.flatten(tree)


def route(cfg, x, w_router, bias):
    """The shared router (models/experts.py) with this family's arguments,
    as models/afmoe.Moe hands them over."""
    return experts.route(x, w_router, bias, cfg.num_experts_per_tok,
                         norm=cfg.route_norm, scale=cfg.route_scale,
                         eps=afmoe.ROUTE_EPS)


# -- the program against the plain reference ----------------------------------

def test_weights_file_has_the_programs_layout(seeded):
    params, x, _ = seeded
    own = jax.eval_shape(afmoe.Afmoe(model_cfg()).init, jax.random.key(0),
                         x)["params"]
    assert (jax.tree.map(lambda a: (a.shape, a.dtype), own)
            == jax.tree.map(lambda a: (a.shape, a.dtype), params))


def test_logits_equal_the_reference_in_float32(seeded):
    params, x, _ = seeded
    with jax.default_matmul_precision("highest"):
        got, aux = jax.jit(afmoe.Afmoe(model_cfg()).apply)(
            {"params": params}, x)
        want = jax.jit(lambda p: ref.logits_fn(p, x, SIZES))(params)
    np.testing.assert_allclose(got, want, atol=2e-5, rtol=2e-5)
    assert aux["moe_dropped"].tolist() == [0, 0]


# Heads of 128 over 128 positions: the smallest shapes the grouped-query
# kernels and their prologue (ops.attention.qk_prep) take.
KERNEL_SIZES = {**SIZES, "head_dim": 128, "block_size": 128}


@pytest.mark.parametrize("variant", ["plain", "remat", "megablox_interpret",
                                     "pallas_interpret"])
def test_loss_and_every_gradient_leaf_equal_the_reference(
        seeded, ref_loss_and_grad, variant, monkeypatch):
    params, x, y = seeded
    cfg = model_cfg(remat=variant == "remat")
    if variant == "megablox_interpret":  # what 'auto' is on a tpu backend
        monkeypatch.setattr(moe, "resolve_gmm_impl", lambda impl: variant)
    if variant == "pallas_interpret":    # ... and the attention kernels with
        # their one-pass norm + rotary, under remat as the cell runs them
        cfg = model_cfg(attention_impl=variant, remat=True, head_dim=128,
                        block_size=128)
        params = weights_afmoe.make_params(KERNEL_SIZES,
                                           weights_afmoe.seed_key(3))
        x = jax.random.randint(jax.random.key(1), (2, 129), 0,
                               SIZES["vocab_size"])
        x, y = x[:, :-1], x[:, 1:]
        assert A.resolve_gqa_impl(cfg.attention_impl, 128, 128) == variant
        with jax.default_matmul_precision("highest"):
            ref_loss_and_grad = jax.jit(
                lambda p: ref.loss_and_grad(p, x, y, KERNEL_SIZES))(params)
    with jax.default_matmul_precision("highest"):
        (loss, _), grads = program_loss_and_grad(cfg, params, x, y)
    want_loss, want = ref_loss_and_grad
    assert abs(float(loss) - float(want_loss)) < 2e-6
    got, want = flat(grads), flat(want)
    assert got.keys() == want.keys()
    for name in want:
        scale = float(jnp.abs(want[name]).max()) + 1e-8
        assert float(jnp.abs(got[name] - want[name]).max()) < 2e-4 * scale + 1e-7, name


def test_bfloat16_compute_stays_near_the_reference(seeded, ref_loss_and_grad):
    """bfloat16 matmul inputs: the loss within 2e-2 and every gradient
    leaf's norm within 5 % of the float32 reference's (or of the median
    leaf's where the leaf's own is smaller): rounding of 8-bit mantissas
    through three layers, not another computation. A routing flip moves one
    token's weight between experts; at this size none does."""
    params, x, y = seeded
    cfg = model_cfg(compute_dtype="bfloat16")
    (loss, _), grads = program_loss_and_grad(cfg, params, x, y)
    want_loss, want = ref_loss_and_grad
    assert abs(float(loss) - float(want_loss)) < 2e-2
    norm = lambda t: {k: float(jnp.linalg.norm(v)) for k, v in flat(t).items()}
    got, want = norm(grads), norm(want)
    floor = float(np.median(list(want.values())))
    for name in want:
        assert abs(got[name] - want[name]) < 0.05 * max(want[name], floor), name


def test_the_shares_add_up_to_the_uncut_layer():
    """Four shares of two experts each (eight of sixteen in the cell): every share's routed
    part, plus the shared expert ONCE, is the reference's uncut layer."""
    E, count = SIZES["num_experts"], 2
    d, F = SIZES["n_embd"], SIZES["moe_intermediate_size"]
    keys = jax.random.split(jax.random.key(5), 8)
    normal = lambda k, *s: 0.2 * jax.random.normal(k, s, jnp.float32)
    full = {"router": normal(keys[0], d, E),
            "expert_bias": normal(keys[0], E),     # moves the selection too
            "w_gate": normal(keys[1], E, d, F), "w_up": normal(keys[2], E, d, F),
            "w_down": normal(keys[3], E, F, d),
            "moe_shared": {
                "gate_proj": {"kernel": normal(keys[4], d, F)},
                "up_proj": {"kernel": normal(keys[5], d, F)},
                "down_proj": {"kernel": normal(keys[6], F, d)}}}
    m = jax.random.normal(keys[7], (2, 32, d), jnp.float32)
    with jax.default_matmul_precision("highest"):
        whole = ref._moe(full, m, {**SIZES, "experts_held": (0, E)},
                         ref._ident, frozenset())
        sh = full["moe_shared"]
        shared = ref._swiglu(m, sh["gate_proj"]["kernel"],
                             sh["up_proj"]["kernel"],
                             sh["down_proj"]["kernel"], ref._ident)
        total = shared
        held = 0
        for first in range(0, E, count):
            cfg = model_cfg(experts_held=(first, count))
            share = {**full, **{k: full[k][first:first + count]
                                for k in ("w_gate", "w_up", "w_down")}}
            out, stats = jax.jit(afmoe.Moe(cfg).apply)({"params": share}, m)
            assert int(stats[2]) == 0
            held += int(stats[0])
            total = total + (out - shared)
    assert held == m.shape[0] * m.shape[1] * SIZES["num_experts_per_tok"]
    np.testing.assert_allclose(total, whole, atol=2e-5, rtol=2e-5)


def test_selection_bias_moves_the_selection_and_not_the_weights(seeded):
    params, x, y = seeded
    cfg = model_cfg()
    xs = jax.random.normal(jax.random.key(4), (64, SIZES["n_embd"]))
    router = params["h_1"]["moe"]["router"]
    sel0, w0 = route(cfg, xs, router, jnp.zeros(8))
    bias = jnp.zeros(8).at[5].set(10.0)         # expert 5 wins every token
    sel1, w1 = route(cfg, xs, router, bias)
    assert bool((sel1 == 5).any(axis=1).all()) and not bool(
        (sel0 == 5).any(axis=1).all())
    # a pair both selections hold weighs by its own score, not score + bias
    s = jax.nn.sigmoid(xs @ router)
    got = jnp.take_along_axis(s, sel1, axis=1)
    np.testing.assert_allclose(
        w1, 2.0 * got / got.sum(-1, keepdims=True), rtol=1e-6)
    # ... and no gradient reaches it
    biased = weights_afmoe.make_params(
        SIZES, weights_afmoe.seed_key(3),
        0.3 * jax.random.normal(jax.random.key(6), (2, 8)))
    _, grads = program_loss_and_grad(cfg, biased, x, y)
    assert float(jnp.abs(grads["h_1"]["moe"]["expert_bias"]).max()) == 0.0
    with jax.default_matmul_precision("highest"):
        want, _ = jax.jit(lambda p: ref.loss_and_grad(p, x, y, SIZES))(biased)
        got, _ = program_loss(cfg, biased, x, y)
    assert abs(float(got) - float(want)) < 2e-6


def test_the_references_balanced_bias_evens_the_load(seeded):
    """The benchmark's selection bias, fitted by the reference alone: on
    its own rows every expert of every expert layer draws within a tenth of
    the even share where zeros leave the fullest at twice it or more, the
    held experts' share is the even one, the values do not depend on the
    bias the weights came with, and the program routes the same rows as
    evenly with them."""
    params, _, _ = seeded
    E, k = SIZES["num_experts"], SIZES["num_experts_per_tok"]
    first, count = SIZES["experts_held"]
    rows = jax.random.randint(jax.random.key(8), (16, 64), 0,
                              SIZES["vocab_size"])
    with jax.default_matmul_precision("highest"):
        fit = jax.jit(lambda p: ref.balanced_bias(p, rows, SIZES, 4))
        bias, load = fit(params)
        again, _ = fit(weights_afmoe.make_params(
            SIZES, weights_afmoe.seed_key(3), jnp.ones((2, E))))
    assert bias.shape == load.shape == (2, E)
    np.testing.assert_array_equal(bias, again)
    assert float(load.max()) < 1.1 and float(load.min()) > 0.9
    np.testing.assert_allclose(load[:, first:first + count].mean(axis=1), 1.0,
                               atol=0.03)

    def held_rows(p):
        _, aux = jax.jit(afmoe.Afmoe(model_cfg()).apply)({"params": p}, rows)
        return np.asarray(aux["moe_held"]) / (rows.size * k * count / E)

    plain = held_rows(params)
    fitted = held_rows(weights_afmoe.make_params(
        SIZES, weights_afmoe.seed_key(3), bias))
    assert np.abs(fitted - 1).max() < 0.03 < np.abs(plain - 1).max()


# -- attention -----------------------------------------------------------------

def _dense_mask_attention(q, k, v, window):
    T, D = q.shape[2], q.shape[3]
    i, j = np.arange(T)[:, None], np.arange(T)[None, :]
    visible = (j <= i) if window is None else (j <= i) & (i - j < window)
    s = jnp.einsum("bhqd,bhkd->bhqk", q, k) / np.sqrt(D)
    p = jax.nn.softmax(jnp.where(visible, s, -jnp.inf), axis=-1)
    return jnp.einsum("bhqk,bhkd->bhqd", p, v)


@pytest.mark.parametrize("window", [None, 16, 24, 100])
def test_window_mask_against_a_dense_mask(window):
    """T = 64 > W: xla_attention's window is the dense mask's."""
    q, k, v = (jax.random.normal(kk, (2, 3, 64, 8), jnp.float32)
               for kk in jax.random.split(jax.random.key(2), 3))
    with jax.default_matmul_precision("highest"):
        got = A.xla_attention(q, k, v, window=window)
        want = _dense_mask_attention(q, k, v, window)
    np.testing.assert_allclose(got, want, atol=2e-6, rtol=2e-6)


def _gqa_operands(B, T, H, G, D, dtype=jnp.float32, seed=0):
    """q, k, v and a fourth array of q's shape (weights of a loss, or dO)."""
    ks = jax.random.split(jax.random.key(seed), 4)
    return (jax.random.normal(ks[0], (B, T, H * D), dtype),
            jax.random.normal(ks[1], (B, T, G * D), dtype),
            jax.random.normal(ks[2], (B, T, G * D), dtype),
            jax.random.normal(ks[3], (B, T, H * D), dtype))


# (B, T, H, G, window, (block_q, block_k) of the one-pass backward; the
# forward's blocks are 128). Five windows at T = 512: aligned to the blocks,
# across them, inside one, wider than T. Then what the backward's walk keeps
# between programs: two batch rows x two KV heads x three query heads a
# group (the accumulators zeroed and written out at the right programs),
# windows that are no multiple of a block, blocks that differ either way.
GQA_CASES = {
    **{f"window-{w}": (1, 512, 4, 2, w, (128, 128))
       for w in (None, 256, 200, 64, 1000, 72, 333)},
    "b2-g2-rep3-window-200": (2, 256, 6, 2, 200, (128, 128)),
    "b2-g2-rep3-full": (2, 256, 6, 2, None, (128, 128)),
    "bq256-bk128-window-200": (1, 512, 4, 2, 200, (256, 128)),
    "bq128-bk256-window-200": (2, 512, 2, 1, 200, (128, 256)),
    "bq256-bk128-full": (1, 512, 2, 2, None, (256, 128)),
}


@pytest.mark.parametrize("case", list(GQA_CASES))
def test_gqa_window_kernels_equal_xla_attention(monkeypatch, case):
    """The Pallas kernels in interpret mode (the forward, and the one-pass
    backward: dQ, dK and dV from one walk of the score tiles) against
    xla_attention with the same mask: output and all three gradients."""
    B, T, H, G, window, blocks = GQA_CASES[case]
    monkeypatch.setattr(A, "DEFAULT_BLOCK", 128)
    monkeypatch.setattr(A, "GQA_BWD_BLOCK_Q", blocks[0])
    monkeypatch.setattr(A, "GQA_BWD_BLOCK_K", blocks[1])
    assert A.resolve_gqa_bwd("pallas_interpret", 128, T, 4) == "fused"
    q, k, v, w = _gqa_operands(B, T, H, G, 128)

    def run(impl):
        def loss(q, k, v):
            # a scope a case: the jitted kernel calls are cached by their
            # static arguments, which the patched blocks are not among
            o = A.causal_attention_gqa(q, k, v, H, G, window=window,
                                       impl=impl, scope=case)
            return jnp.sum(o * w), o
        return jax.value_and_grad(loss, argnums=(0, 1, 2), has_aux=True)(
            q, k, v)

    with jax.default_matmul_precision("highest"):
        (_, o_x), g_x = run("xla")
        (_, o_p), g_p = run("pallas_interpret")
    np.testing.assert_allclose(o_p, o_x, atol=1e-5, rtol=1e-5)
    for got, want in zip(g_p, g_x):
        np.testing.assert_allclose(got, want, atol=2e-5, rtol=2e-5)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["float32", "bfloat16"])
@pytest.mark.parametrize("window", [None, 200, 256],
                         ids=lambda w: f"window-{w}")
def test_gqa_fused_backward_equals_the_split_pair_bit_for_bit(
        monkeypatch, window, dtype):
    """At equal blocks a key block's sums arrive in the split dK/dV
    kernel's order (query head outer, q block ascending) and a q block's
    in the dQ kernel's: the same float32 sums, the same three arrays."""
    for name in ("DEFAULT_BLOCK", "GQA_BWD_BLOCK_Q", "GQA_BWD_BLOCK_K"):
        monkeypatch.setattr(A, name, 128)
    B, T, H, G, D = 2, 384, 6, 2, 128
    q, k, v, do = _gqa_operands(B, T, H, G, D, dtype, seed=1)
    o, lse = A._pallas_flash_fwd_gqa(q, k, v, n_head=H, n_kv_head=G,
                                     window=window, interpret=True)
    stats = lse.reshape(B, H, T // 128, 128)
    kw = dict(n_head=H, n_kv_head=G, window=window, interpret=True)
    fused = A._gqa_bwd_fused(q, k, v, o, stats, do, **kw)
    split = A._gqa_bwd_split(q, k, v, o, stats, do, **kw)
    for got, want in zip(fused, split):
        assert got.dtype == want.dtype == dtype
        np.testing.assert_array_equal(np.asarray(got, np.float32),
                                      np.asarray(want, np.float32))


def test_gqa_backward_is_chosen_from_the_shapes_alone(monkeypatch):
    """One predicate: the one-pass kernel while a KV head's whole-T k, v,
    dk, dv and float32 accumulators fit VMEM, the split pair beyond, XLA
    where no kernel walks the shapes; and the entry runs what it says."""
    assert A.resolve_gqa_bwd("pallas", 128, 8192) == "fused"
    assert A.resolve_gqa_bwd("pallas", 128, 36864) == "fused"
    assert A.resolve_gqa_bwd("pallas", 128, 40960) == "split"
    assert A.resolve_gqa_bwd("pallas", 256, 16384) == "fused"
    assert A.resolve_gqa_bwd("pallas", 256, 20480) == "split"
    assert A.resolve_gqa_bwd("pallas_interpret", 128, 16384, 4) == "fused"
    assert A.resolve_gqa_bwd("pallas_interpret", 128, 32768, 4) == "split"
    assert A.resolve_gqa_bwd("pallas", 64, 8192) == "xla"
    assert A.resolve_gqa_bwd("xla", 128, 8192) == "xla"

    ran = []

    def spy(name):
        real = getattr(A, name)

        def wrapped(*args, **kw):
            ran.append(name)
            return real(*args, **kw)
        monkeypatch.setattr(A, name, wrapped)

    spy("_gqa_bwd_fused")
    spy("_gqa_bwd_split")
    monkeypatch.setattr(A, "DEFAULT_BLOCK", 128)
    q, k, v, w = _gqa_operands(1, 256, 2, 1, 128)
    grad = lambda scope: jax.grad(lambda q: jnp.sum(w * A.flash_attention_gqa(
        q, k, v, 2, 1, 64, True, scope)))(q)
    here = grad("fits")
    monkeypatch.setattr(A, "GQA_BWD_RESIDENT_BYTES", 0)
    np.testing.assert_allclose(grad("does-not-fit"), here, atol=1e-6)
    assert ran == ["_gqa_bwd_fused", "_gqa_bwd_split"]


def test_gqa_entry_refuses_shapes_it_cannot_walk():
    x = jnp.zeros((1, 128, 4 * 64))
    with pytest.raises(ValueError, match="D % 128"):
        A.flash_attention_gqa(x, x[..., :128], x[..., :128], 4, 2, None, True)
    with pytest.raises(ValueError, match="impls"):
        A.causal_attention_gqa(x, x, x, 4, 4, impl="ring")


def _prologue(impl, x, scale, heads, theta):
    return experts.HeadRMSNorm(heads, 1e-5, "float32").apply(
        {"params": {"scale": scale}}, x, theta, impl)


@pytest.mark.parametrize("T", [128, 384])
@pytest.mark.parametrize("theta", [10000.0, None], ids=["rotary", "none"])
@pytest.mark.parametrize("heads", [32, 4])
@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
def test_qk_prep_kernel_equals_head_norm_then_rotary(dtype, heads, theta, T):
    """The one-pass kernel in interpret mode against head_rms_norm + rotary
    (the XLA path, products at full precision): output, input gradient and
    the scale's gradient; float32 to rounding, bfloat16 to one bfloat16
    step of the largest value (both round the same float32 numbers)."""
    D = 128
    ks = jax.random.split(jax.random.key(heads + T), 3)
    x = (2.0 * jax.random.normal(ks[0], (2, T, heads * D))).astype(dtype)
    w = jax.random.normal(ks[1], (2, T, heads * D)).astype(dtype)
    scale = 1.0 + 0.2 * jax.random.normal(ks[2], (D,))

    def run(impl):
        def loss(x, scale):
            z = _prologue(impl, x, scale, heads, theta)
            return jnp.sum((z * w).astype(jnp.float32)), z
        return jax.value_and_grad(loss, argnums=(0, 1), has_aux=True)(
            x, scale)

    with jax.default_matmul_precision("highest"):
        (_, z_x), (dx_x, ds_x) = run("xla")
    (_, z_p), (dx_p, ds_p) = run("pallas_interpret")
    assert z_p.dtype == dx_p.dtype == x.dtype and ds_p.shape == (D,)
    f32 = lambda a: np.asarray(a, np.float32)
    step = 2.0 ** -8 if dtype == "bfloat16" else 2e-6
    for got, want in ((z_p, z_x), (dx_p, dx_x)):
        assert np.abs(f32(got) - f32(want)).max() <= step * np.abs(
            f32(want)).max()
    np.testing.assert_allclose(ds_p, ds_x, rtol=2e-5,
                               atol=2e-6 * float(jnp.abs(ds_x).max()))


def test_qk_prep_refuses_shapes_it_cannot_walk():
    with pytest.raises(ValueError, match="D % 128"):
        A.qk_prep(jnp.zeros((1, 128, 4 * 64)), jnp.ones((64,)), 4, 1e-5,
                  None, True)
    with pytest.raises(ValueError, match="T % 128"):
        A.qk_prep(jnp.zeros((1, 8, 4 * 128)), jnp.ones((128,)), 4, 1e-5,
                  None, True)
    assert A.resolve_gqa_impl("pallas_interpret", 128, 8) == "xla"
    assert A.resolve_gqa_impl("pallas", 128, 8192) == "pallas"
    assert A.resolve_gqa_impl("xla", 128, 8192) == "xla"


def test_rotary_positions_only_on_window_layers(monkeypatch, seeded):
    params, x, _ = seeded
    calls = []
    real = experts.rotary
    monkeypatch.setattr(experts, "rotary",
                        lambda t, theta: calls.append(t.shape) or real(t, theta))
    afmoe.Afmoe(model_cfg()).apply({"params": params}, x)
    # q and k of the two sliding layers; the full layer none
    assert len(calls) == 2 * SIZES["layer_types"].count("sliding")
    # and positions matter there: a rotated head is not the head
    t = jax.random.normal(jax.random.key(0), (1, 8, 2, 16))
    assert float(jnp.abs(real(t, 1e4) - t)[:, 1:].max()) > 1e-2
    np.testing.assert_allclose(real(t, 1e4)[:, 0], t[:, 0], atol=1e-6)


# -- the sorted buffer ----------------------------------------------------------

def test_chunks_walk_the_held_pairs_sorted_by_expert():
    rng = np.random.default_rng(0)
    N, k, E, first, count, rows, chunks = 40, 3, 10, 4, 3, 16, 8
    sel = np.stack([rng.permutation(E)[:k] for _ in range(N)]).astype(np.int32)
    pairs = moe.plan_pairs(jnp.asarray(sel), first, count, rows * chunks)
    held = [(e - first, n, j) for n in range(N) for j in range(k)
            for e in [sel[n, j]] if first <= e < first + count]
    want = sorted(held)                     # by expert, then (token, slot)
    assert int(pairs["total"]) == len(want) > rows
    assert int(pairs["max_rows"]) == max(
        sum(1 for h in want if h[0] == e) for e in range(count))
    seen = 0
    for c in range(chunks):
        plan = jax.tree.map(np.asarray, moe.chunk_plan(pairs, c, rows, k))
        mine = want[c * rows:(c + 1) * rows]
        assert plan["row_valid"].sum() == len(mine)
        assert plan["group_sizes"].tolist() == [
            sum(1 for h in mine if h[0] == e) for e in range(count)]
        assert plan["row_pair"][:len(mine)].tolist() == [
            n * k + j for _, n, j in mine]
        for r, (_, n, j) in enumerate(mine):
            assert plan["dest"][n, j] == r and plan["row_token"][r] == n
        assert (plan["dest"] < rows).sum() == len(mine)
        seen += len(mine)
    assert seen == len(want)


@pytest.mark.parametrize("factor", [100.0, 0.5, moe.ROWS_FACTOR])
def test_a_router_biased_to_one_expert_drops_nothing(factor):
    """Every token sends a slot to held expert 2, four times the expected
    load: one chunk of the whole bound (factor 100), chunks of half the
    expected load (0.5: four of them run) and of twice it all give the
    same sum as the model's layer, and none drops a pair."""
    cfg = model_cfg(block_size=2048)
    d, E = SIZES["n_embd"], SIZES["num_experts"]
    first, count = SIZES["experts_held"]
    init = afmoe.Moe(cfg).init(jax.random.key(0), jnp.zeros((1, 8, d)))
    router = np.zeros((d, E), np.float32)
    router[:, 2] = 1.0                       # the first held expert (2..5)
    params = {**init["params"], "router": jnp.asarray(router)}
    m = jnp.abs(jax.random.normal(jax.random.key(1), (2, 2048, d))) + 0.1
    x = m.reshape(-1, d)
    sel, w = route(cfg, x, params["router"], params["expert_bias"])
    out, stats = jax.jit(moe.routed_experts, static_argnums=(6, 7, 8, 9))(
        x, sel, w, params["w_gate"], params["w_up"], params["w_down"],
        first, count, E, factor)
    held, fullest, dropped = (int(v) for v in stats)
    assert fullest == 2 * 2048 and held == fullest and dropped == 0
    layer, _ = jax.jit(afmoe.Moe(cfg).apply)({"params": params}, m)
    sh = params["moe_shared"]
    shared = ref._swiglu(x, sh["gate_proj"]["kernel"], sh["up_proj"]["kernel"],
                         sh["down_proj"]["kernel"], ref._ident)
    np.testing.assert_allclose(out + shared, layer.reshape(-1, d),
                               atol=1e-5, rtol=1e-5)
    assert float(jnp.abs(out).max()) > 0


# -- the row mover (ops.moe: %moe_rows.N) ----------------------------------------

def _plans(case):
    """(N, k, d, rows, every chunk's plan) of a tiny layer whose held pairs
    are the named case's."""
    N, k, E, d = 256, 4, 16, 128
    rng = np.random.default_rng(7)
    sel = np.stack([rng.permutation(E)[:k] for _ in range(N)]).astype(np.int32)
    first, count, factor = 2, 4, moe.ROWS_FACTOR
    if case == "none_held":
        first, count = E, 4              # every choice names an absent expert
    elif case == "all_held":             # the bound: N * k rows, one chunk
        first, count, factor = 0, E, 1.0
    elif case == "two_chunks":           # 12 of 16 held: ~768 pairs, in
        first, count, factor = 2, 12, 0.5     # chunks of ROW_TILE = 512 rows
    elif case == "whole_tokens":         # a token holds all its k or none
        sel = np.where(np.arange(N)[:, None] % 3 == 0, np.arange(2, 2 + k),
                       np.arange(8, 8 + k)).astype(np.int32)
    rows, chunks = moe.chunk_rows(N, k, E, count, factor)
    pairs = moe.plan_pairs(jnp.asarray(sel), first, count, rows * chunks)
    total = int(pairs["total"])
    ran = [c for c in range(chunks) if total > c * rows] or [0]
    return N, k, d, rows, total, [moe.chunk_plan(pairs, c, rows, k)
                                  for c in ran]


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("case", ["expected", "none_held", "all_held",
                                  "two_chunks", "whole_tokens"])
def test_row_mover_equals_the_xla_gathers_bit_for_bit(case, dtype):
    """combine and dispatch under the kernel ('pallas_interpret') against
    the k gathers a token ('xla'): the outputs and, through jax.vjp, dx, dy
    and dw, every bit. The inputs lie on a grid (eighths, no negative zero;
    weights of eight bits, positive as the router's) so that every PRODUCT
    is exact in float32: whether a backend contracts a multiply and an add
    into one rounding, or drops the sum's leading 0.0, is then not seen; the
    ORDER of a token's float32 sum is (the sums are not exact)."""
    N, k, d, rows, total, plans = _plans(case)
    assert {"expected": 200 < total < 330 and len(plans) == 1,
            "none_held": total == 0, "all_held": total == N * k == rows,
            "two_chunks": len(plans) == 2 and rows < total < 2 * rows,
            "whole_tokens": total == k * len(range(0, N, 3))}[case]
    assert moe.resolve_row_mover("megablox_interpret", N, d) == "pallas_interpret"
    keys = jax.random.split(jax.random.key(5), 5)

    def grid(key, shape):
        g = (jnp.round(8 * jax.random.normal(key, shape)) / 8).clip(-4, 4)
        return jnp.where(g == 0, 0.0, g).astype(dtype)

    x, y, dxs = grid(keys[0], (N, d)), grid(keys[1], (rows, d)), grid(
        keys[2], (rows, d))
    dout = grid(keys[3], (N, d)).astype(jnp.float32)
    w = (jax.random.uniform(keys[4], (N, k)) + 0.1).astype(
        jnp.bfloat16).astype(jnp.float32)

    @functools.partial(jax.jit, static_argnums=0)
    def both_ways(mover):
        got = []
        for plan in plans:
            xs, dx_of = jax.vjp(lambda x: moe.dispatch(x, plan, mover), x)
            out, dyw_of = jax.vjp(
                lambda y, w: moe.combine(y, w, plan, mover), y, w)
            got.append((xs, out, dx_of(dxs)[0], *dyw_of(dout)))
        return got

    want, got = both_ways("xla"), both_ways("pallas_interpret")
    for c, (a, b) in enumerate(zip(want, got)):
        for name, u, v in zip(("xs", "out", "dx", "dy", "dw"), a, b):
            assert u.dtype == v.dtype and u.shape == v.shape, (c, name)
            assert np.asarray(u).tobytes() == np.asarray(v).tobytes(), (
                c, name, float(jnp.abs(u.astype(jnp.float32)
                                       - v.astype(jnp.float32)).max()))
    if total:
        assert any(float(jnp.abs(a[1]).max()) > 0 for a in want)
        assert any(float(jnp.abs(a[2].astype(jnp.float32)).max()) > 0
                   for a in want)


def test_row_mover_refuses_shapes_it_cannot_walk(monkeypatch):
    """The mover follows the grouped matmul's impl and the shapes: rows that
    are no whole 128-lane tiles, the trainer's 8-token init batch and
    'ragged_dot' keep XLA's gathers; the kernel's entry says what it needs."""
    resolve = moe.resolve_row_mover
    assert resolve("megablox", 16384, 2048) == "pallas"
    assert resolve("megablox_interpret", 256, 128) == "pallas_interpret"
    assert resolve("megablox", 16384, 2048 + 64) == "xla"
    assert resolve("megablox", 8, 2048) == "xla"
    assert resolve("ragged_dot", 16384, 2048) == "xla"
    assert resolve("auto", 16384, 2048) == "xla"          # off the chip
    with pytest.raises(ValueError, match="row mover needs"):
        moe._pallas_rows_to_tokens(
            jnp.zeros((512, 96)), jnp.zeros((256, 2), jnp.int32),
            out_dtype=jnp.float32, interpret=True)
    with pytest.raises(ValueError, match="row mover needs"):
        moe._pallas_rows_to_tokens(
            jnp.zeros((512, 128)), jnp.zeros((8, 2), jnp.int32),
            out_dtype=jnp.float32, interpret=True)
    # ... and the family says which it will be, beside qk_prep
    monkeypatch.setattr(moe, "resolve_gmm_impl", lambda impl: "megablox")
    wide = model_cfg(n_embd=128, block_size=256)
    assert afmoe.build(wide, None)[1]["moe_row_mover"] == "pallas"
    assert afmoe.build(model_cfg(), None)[1]["moe_row_mover"] == "xla"


def test_routed_experts_with_the_row_mover_equal_the_xla_walk():
    """The whole layer through its own VJP, two chunks, the kernels inside
    the walk's cond: 'megablox_interpret' (grouped matmul AND row mover in
    the interpreter) against 'ragged_dot' (XLA for both)."""
    N, k, E, d, F, first, count = 256, 4, 16, 128, 64, 2, 12
    keys = jax.random.split(jax.random.key(11), 6)
    x = jax.random.normal(keys[0], (N, d))
    _, sel = jax.lax.top_k(jax.random.uniform(keys[1], (N, E)), k)
    w = jax.random.uniform(keys[2], (N, k)) + 0.1
    mats = [0.1 * jax.random.normal(kk, shape) for kk, shape in zip(
        keys[3:], [(count, d, F), (count, d, F), (count, F, d)])]

    def loss(impl, x, w, *mats):
        out, stats = moe.routed_experts(x, sel.astype(jnp.int32), w, *mats,
                                        first, count, E, 0.5, impl)
        return jnp.sum(out * jnp.cos(out)), stats

    with jax.default_matmul_precision("highest"):
        run = lambda impl: jax.jit(jax.value_and_grad(
            functools.partial(loss, impl), argnums=(0, 1, 2, 3, 4),
            has_aux=True))(x, w, *mats)
        (want, stats), dwant = run("ragged_dot")
        (got, stats2), dgot = run("megablox_interpret")
    assert stats.tolist() == stats2.tolist() and int(stats[2]) == 0
    assert int(stats[0]) > moe.chunk_rows(N, k, E, count, 0.5)[0]   # 2 chunks
    np.testing.assert_allclose(got, want, rtol=1e-5)
    for a, b in zip(dgot, dwant):
        np.testing.assert_allclose(a, b, rtol=1e-4,
                                   atol=1e-5 * float(jnp.abs(b).max()))


# -- the walk's first chunk, used as it is (ISSUE 38) ----------------------------

WALK = {  # name: (held experts, factor, tokens that favour held expert 3)
    "one_chunk_of_several": ((2, 4), moe.ROWS_FACTOR, 0),
    "two_chunks": ((2, 4), 0.5, 366),
    "three_chunks": ((2, 4), 0.5, 1024),
    "one_chunk_statically": ((2, 12), moe.ROWS_FACTOR, 0),
    "no_pair_held": ((16, 4), moe.ROWS_FACTOR, 0),
}


def _walk_case(case, dtype):
    """A tiny layer's operands in the cell's types (tokens and matrices in
    ``dtype``, weights float32), the chunks its walk runs, and a cotangent."""
    N, k, E, d, F = 1024, 2, 16, 32, 24
    (first, count), factor, skewed = WALK[case]
    keys = jax.random.split(jax.random.key(38), 7)
    score = jax.random.uniform(keys[1], (N, E))
    _, sel = jax.lax.top_k(score.at[:skewed, 3].add(1.0), k)
    x = jax.random.normal(keys[0], (N, d)).astype(dtype)
    w = jax.random.uniform(keys[2], (N, k)) + 0.1
    mats = tuple((0.3 * jax.random.normal(kk, shape)).astype(dtype)
                 for kk, shape in zip(keys[3:6], [(count, d, F), (count, d, F),
                                                  (count, F, d)]))
    d_out = jax.random.normal(keys[6], (N, d))
    rows, chunks = moe.chunk_rows(N, k, E, count, factor)
    pairs = moe.plan_pairs(sel.astype(jnp.int32), first, count, rows * chunks)
    total = int(pairs["total"])
    ran = tuple(c for c in range(chunks) if total > c * rows)
    return (x, sel.astype(jnp.int32), w, *mats), (first, count, E, factor), \
        d_out, rows, chunks, ran


def _layer_both_ways(operands, static, d_out, impl="ragged_dot"):
    """(the jitted layer through its own VJP: (x, w, *matrices) -> (out,
    stats, the five gradients), its arguments)."""
    x, sel, w, *mats = operands

    @jax.jit
    def run(x, w, *mats):
        (out, stats), vjp = jax.vjp(
            lambda *a: moe.routed_experts(a[0], sel, a[1], *a[2:], *static,
                                          impl), x, w, *mats)
        return out, stats, vjp((d_out, np.zeros(3, jax.dtypes.float0)))
    return run, (x, w, *mats)


def _program(operands, static, d_out):
    """(out, stats, the five gradients) of the layer through its own VJP."""
    run, args = _layer_both_ways(operands, static, d_out)
    return run(*args)


def _sums_from_zeros(operands, static, d_out, rows, chunks):
    """The walk as a sum that starts at zero, as ops/moe.py ran it before
    ISSUE 38: float32 zeros for the output and the five gradients, a scan
    over ALL the chunks whose body is a ``lax.cond`` that adds a chunk
    holding pairs, every gradient cast back to its operand's type at the
    end. (Under the same loop the CPU backend compiles a chunk's own ops as
    it does the program's, so the comparison sees the sums alone.)"""
    x, sel, w, *mats = operands
    first, count, E, _ = static
    k = sel.shape[1]

    @jax.jit
    def run(*held):
        pairs = moe.plan_pairs(sel, first, count, rows * chunks)

        def chunk(carry, c):
            def add(carry):
                out, covered, grads = carry
                plan = moe.chunk_plan(pairs, c, rows, k)
                got, vjp = jax.vjp(
                    lambda *a: moe._chunk_out(*a, plan, "ragged_dot"), *held)
                return (out + got, covered + jnp.sum(plan["group_sizes"]),
                        tuple(g + d.astype(jnp.float32)
                              for g, d in zip(grads, vjp(d_out))))

            return jax.lax.cond(pairs["total"] > c * rows, add,
                                lambda carry: carry, carry), None

        zeros = (jnp.zeros(x.shape, jnp.float32), jnp.zeros((), jnp.int32),
                 tuple(jnp.zeros(a.shape, jnp.float32) for a in held))
        (out, covered, grads), _ = jax.lax.scan(chunk, zeros,
                                                jnp.arange(chunks))
        stats = jnp.stack([pairs["total"], pairs["max_rows"],
                           pairs["total"] - covered]).astype(jnp.int32)
        return out, stats, tuple(g.astype(a.dtype)
                                 for g, a in zip(grads, held))
    return run(x, w, *mats)


def _same(got, want, bits):
    """Equal arrays of equal types; ``bits``: every bit (a zero's sign too)."""
    for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(want), strict=True):
        assert a.dtype == b.dtype and a.shape == b.shape
        if bits:
            assert np.asarray(a).tobytes() == np.asarray(b).tobytes()
        else:
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
def test_one_chunk_run_of_several_is_used_as_it_is(dtype):
    """A balanced layer under factor 2: chunk 0 of two holds every pair.
    The output, the counters and all five gradients are ``jax.vjp`` of
    ``_chunk_out`` on chunk 0's plan alone, in the operands' own types, and
    equal the sum that starts at zero (equal, not bit for bit: ``0.0 +
    (-0.0)`` is ``+0.0``, so a zero's sign survives now where the sum lost
    it)."""
    operands, static, d_out, rows, chunks, ran = _walk_case(
        "one_chunk_of_several", dtype)
    assert chunks == 2 and ran == (0,)
    out, stats, grads = _program(operands, static, d_out)
    x, sel, w, *mats = operands
    first, count, E, _ = static
    assert 0 < int(stats[0]) <= rows and int(stats[2]) == 0

    @jax.jit
    def chunk0(x, w, *mats):
        pairs = moe.plan_pairs(sel, first, count, rows * chunks)
        plan = moe.chunk_plan(pairs, 0, rows, sel.shape[1])
        got, vjp = jax.vjp(
            lambda *a: moe._chunk_out(*a, plan, "ragged_dot"), x, w, *mats)
        return got, vjp(d_out)

    _same((out, grads), chunk0(x, w, *mats), bits=False)
    assert [g.dtype for g in grads] == [a.dtype for a in (x, w, *mats)]
    _same((out, stats, grads),
          _sums_from_zeros(operands, static, d_out, rows, chunks),
          bits=False)
    assert float(jnp.abs(out).max()) > 0
    assert all(float(jnp.abs(g.astype(jnp.float32)).max()) > 0 for g in grads)


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("case", ["two_chunks", "three_chunks"])
def test_chunks_past_the_first_are_summed_in_float32_in_chunk_order(case,
                                                                    dtype):
    """Chunks of half the expected load, and a router that sends most
    tokens to one held expert: two and three chunks hold pairs. The float32
    sums are those of the sum that starts at zero, chunk 0 first, every bit
    (``0.0 + a`` is ``a`` to the bit unless ``a`` is ``-0.0``, and the sums
    here meet none)."""
    operands, static, d_out, rows, chunks, ran = _walk_case(case, dtype)
    assert chunks == 4 and len(ran) == {"two_chunks": 2,
                                        "three_chunks": 3}[case]
    got = _program(operands, static, d_out)
    assert int(got[1][0]) > (len(ran) - 1) * rows and int(got[1][2]) == 0
    _same(got, _sums_from_zeros(operands, static, d_out, rows, chunks),
          bits=True)


def test_a_walk_of_one_chunk_is_no_loop():
    """Where one chunk covers the bound no router can pass (``never <=
    rows``: half of the experts held, or more), the layer is chunk 0 and
    nothing else: its lowered text, forward and backward, holds no loop and
    no branch."""
    operands, static, d_out, rows, chunks, ran = _walk_case(
        "one_chunk_statically", "float32")
    assert chunks == 1 and ran == (0,)
    run, args = _layer_both_ways(operands, static, d_out)
    text = run.lower(*args).as_text()
    assert "stablehlo.dot_general" in text or "ragged_dot" in text
    for word in ("stablehlo.while", "stablehlo.case", "stablehlo.if"):
        assert word not in text, word
    _same(run(*args),
          _sums_from_zeros(operands, static, d_out, rows, chunks),
          bits=False)


@pytest.mark.parametrize("factor", [moe.ROWS_FACTOR, 100.0],
                         ids=["of_several", "statically_one"])
def test_a_layer_that_holds_no_pair_gives_zeros(factor):
    """Every selected expert is another chip's: chunk 0 runs all the same
    (it is under no ``cond``), its rows are all invalid and its groups
    empty, so the output and every gradient are zeros and the counters
    (0, 0, 0)."""
    operands, static, d_out, rows, chunks, ran = _walk_case(
        "no_pair_held", "bfloat16")
    static = (*static[:3], factor)
    assert ran == () and (moe.chunk_rows(1024, 2, 16, 4, factor)[1] == 1) == (
        factor == 100.0)
    out, stats, grads = _program(operands, static, d_out)
    assert stats.tolist() == [0, 0, 0]
    for a in (out, *grads):
        assert not np.asarray(a.astype(jnp.float32)).any()


@pytest.mark.parametrize("factor", [0.5, moe.ROWS_FACTOR],
                         ids=["two_chunks", "one_chunk"])
def test_no_sum_over_chunks_starts_from_zeros(factor):
    """The lowered text of a tiny layer's step, forward and VJP, under the
    cells' kernels in the interpreter (the 'xla' mover's own ``0.0 + ...``
    would fill (N, d) zeros of its own): no float32 array of the tokens',
    the weights' or an expert matrix's shape is filled with zeros anywhere,
    the branch that sums over chunks included: its sums start from the
    first chunk's results. (The walk that started from zeros filled six.)"""
    N, k, E, d, F, first, count = 256, 4, 16, 128, 64, 2, 12
    keys = jax.random.split(jax.random.key(11), 6)
    x = jax.random.normal(keys[0], (N, d)).astype(jnp.bfloat16)
    _, sel = jax.lax.top_k(jax.random.uniform(keys[1], (N, E)), k)
    w = jax.random.uniform(keys[2], (N, k)) + 0.1
    mats = [(0.1 * jax.random.normal(kk, shape)).astype(jnp.bfloat16)
            for kk, shape in zip(keys[3:], [(count, d, F), (count, d, F),
                                            (count, F, d)])]
    assert moe.chunk_rows(N, k, E, count, factor)[1] == (2 if factor < 1
                                                         else 1)

    run, args = _layer_both_ways(
        (x, sel.astype(jnp.int32), w, *mats), (first, count, E, factor),
        jnp.ones((N, d)), "megablox_interpret")
    text = run.lower(*args).as_text()
    shapes = "|".join("x".join(map(str, a.shape)) for a in (x, w, *mats))
    seen = 0
    for func in text.split("func.func")[1:]:    # a name is a function's own
        zeros = re.findall(r"(%\S+) = stablehlo.constant dense<0\.0+e\+00> "
                           r": tensor<f32>", func)
        seen += len(zeros)
        filled = re.findall(
            rf"broadcast_in_dim (%\S+), dims = \[\] : \(tensor<f32>\) -> "
            rf"tensor<(?:{shapes})xf32>", func)
        assert not [c for c in filled if c in zeros]
    assert seen                 # the pattern reads this text's constants


def test_chunk_rows():
    assert moe.chunk_rows(16384, 8, 128, 16) == (32768, 4)
    assert moe.chunk_rows(16384, 8, 128, 16, 3.0) == (49152, 3)
    assert moe.chunk_rows(16384, 8, 128, 16, 100.0) == (16384 * 8, 1)
    assert moe.chunk_rows(16, 2, 8, 4, 1.0) == (moe.ROW_TILE, 1)


# -- the trainer's normal path ---------------------------------------------------

@pytest.fixture()
def afmoe_train_cfg(char_dataset, tmp_path):
    return train_cfg(
        out_dir=str(tmp_path / "out"), data_dir=char_dataset,
        dataset="shakespeare_char", vocab_size=0, batch_size=8,
        max_iters=2, lr_decay_iters=2, eval_interval=0, eval_iters=1,
        log_interval=1, warmup_iters=1, learning_rate=1e-3, min_lr=1e-4,
        tensorboard=False, seed=0, loss_chunk_size=32, remat=True)


def test_trainer_two_steps_save_restore_same_loss(afmoe_train_cfg):
    from nanosandbox_tpu.checkpoint import Checkpointer
    from nanosandbox_tpu.obs import opscopes, process_tracer
    from nanosandbox_tpu.train import Trainer, restore_for_inference

    cfg = afmoe_train_cfg
    trainer = Trainer(cfg)
    out = trainer.run()
    assert out["iter_num"] == 2 and out["model_family"] == "afmoe"
    assert np.isfinite(out["final_loss"])
    init = [s for s in process_tracer().spans() if s.name == "trainer_init"][-1]
    assert init.args["model_family"] == "afmoe"
    assert init.args["experts_held"] == [2, 4]
    assert init.args["layer_types"] == "sliding,full,sliding"
    assert init.args["qk_prep"] == "xla"    # heads of 16: no kernel takes them
    assert init.args["gqa_bwd"] == "xla"
    rows = [s for s in process_tracer().spans() if s.name == "moe_rows"][-1]
    assert rows.args["moe_dropped"] == [0, 0] and len(rows.args["moe_held"]) == 2
    assert rows.args["chunks_run"] == [1, 1]    # one chunk covers the bound
    parts = set(opscopes.step_parts().values())
    assert {"attn_sliding", "attn_full", "moe_route", "moe_experts",
            "moe_shared"} <= parts and "attn" not in parts
    # the stages of moe_route, out of the same lowering as the parts (under
    # remat here: the replayed forward and the backward's second walk); in
    # float32 the held matrices need no cast and this walk is one chunk, with
    # no sum over chunks: ``route_weights`` has nothing to own (in bfloat16
    # it has: tests/test_train_tracing.py)
    stages = opscopes.step_stages()
    # exactly the other five: a stage that loses its scope, ``route_weights``
    # gaining work in float32 or an unstaged instruction is noticed here
    assert set(stages.values()) == set(opscopes.STAGES) - {"route_weights"}
    assert set(stages) == {n for n, p in opscopes.step_parts().items()
                           if p == "moe_route"}

    ckpt = Checkpointer(cfg.out_dir)
    state, extra = ckpt.restore(trainer.abstract_state)
    ckpt.close()
    assert extra["config"]["model_family"] == "afmoe"
    again = Trainer(dataclasses.replace(cfg, init_from="resume"))
    state2, _ = Checkpointer(cfg.out_dir).restore(again.abstract_state)
    loss = trainer.estimate_loss(state, eval_iters=1)
    loss2 = again.estimate_loss(state2, eval_iters=1)
    assert loss == loss2

    with pytest.raises(NotImplementedError, match="cache branch"):
        restore_for_inference(cfg.out_dir)


@pytest.mark.parametrize("axis", ["mesh_sp", "mesh_tp"])
def test_seq_and_model_axes_are_refused_by_name(afmoe_train_cfg, axis):
    from nanosandbox_tpu.train import Trainer

    extra = {"attention_impl": "ring"} if axis == "mesh_sp" else {}
    with pytest.raises(NotImplementedError, match="data and fsdp axes"):
        Trainer(dataclasses.replace(afmoe_train_cfg, **{axis: 2}, **extra))


def test_config_says_what_is_missing():
    with pytest.raises(ValueError, match="layer_types needs 3"):
        AfmoeConfig.from_train_config(train_cfg(layer_types="sliding"), 96)
    with pytest.raises(ValueError, match="experts_held inside"):
        AfmoeConfig.from_train_config(train_cfg(experts_held=(6, 4)), 96)
    assert model_cfg(experts_held=(0, 0)).experts_held == (0, 8)
    with pytest.raises(ValueError, match="unknown model_family"):
        from nanosandbox_tpu.train import Trainer
        Trainer(TrainConfig(model_family="llama"))
