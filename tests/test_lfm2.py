"""The ``lfm2`` family (models/lfm2.py, ops/short_conv.py, the head-size-64
route of ops/attention.py, the expert pieces of models/experts.py) against
the plain reference ``chipbench/reference/lfm2.py``, which imports nothing of
the program. Small sizes, CPU."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax import lax

from chipbench import weights_lfm2
from chipbench.reference import lfm2 as ref
from nanosandbox_tpu.config import Lfm2Config, TrainConfig
from nanosandbox_tpu.models import experts, lfm2
from nanosandbox_tpu.ops import attention as A
from nanosandbox_tpu.ops import moe, short_conv

SIZES = {
    "n_layer": 4, "n_head": 4, "n_kv_head": 2, "head_dim": 16, "n_embd": 32,
    "vocab_size": 96, "block_size": 64,
    "layer_types": ("conv", "conv", "full", "conv"), "conv_L_cache": 3,
    "num_dense_layers": 1, "intermediate_size": 48,
    "moe_intermediate_size": 24, "num_experts": 8, "num_experts_per_tok": 2,
    "experts_held": (2, 4), "route_scale": 1.0, "route_norm": True,
    "rope_theta": 1e6, "rms_norm_eps": 1e-5,
}


def train_cfg(**kw) -> TrainConfig:
    s = SIZES
    base = dict(
        model_family="lfm2", layer_types=",".join(s["layer_types"]),
        compute_dtype="float32",
        **{k: s[k] for k in s if k != "layer_types"})
    return TrainConfig(**{**base, **kw})


def model_cfg(**kw) -> Lfm2Config:
    return Lfm2Config.from_train_config(train_cfg(**kw), SIZES["vocab_size"])


@pytest.fixture(scope="module")
def seeded():
    params = weights_lfm2.make_params(SIZES, weights_lfm2.seed_key(3))
    x = jax.random.randint(jax.random.key(1), (2, 65), 0, SIZES["vocab_size"])
    return params, x[:, :-1], x[:, 1:]


@pytest.fixture(scope="module")
def ref_loss_and_grad(seeded):
    params, x, y = seeded
    with jax.default_matmul_precision("highest"):
        return jax.jit(lambda p: ref.loss_and_grad(p, x, y, SIZES))(params)


def program_loss(cfg, params, x, y):
    from nanosandbox_tpu.models.loss import chunked_cross_entropy_loss

    hidden, aux = lfm2.Lfm2(cfg).apply({"params": params}, x,
                                       return_hidden=True)
    return chunked_cross_entropy_loss(
        hidden, lfm2.head(params), y, chunk_size=32,
        compute_dtype=cfg.compute_dtype), aux


def program_loss_and_grad(cfg, params, x, y):
    return jax.jit(jax.value_and_grad(
        lambda p: program_loss(cfg, p, x, y), has_aux=True))(params)


flat = weights_lfm2.flatten


# -- the program against the plain reference ----------------------------------

def test_weights_file_has_the_programs_layout(seeded):
    params, x, _ = seeded
    own = jax.eval_shape(lfm2.Lfm2(model_cfg()).init, jax.random.key(0),
                         x)["params"]
    assert (jax.tree.map(lambda a: (a.shape, a.dtype), own)
            == jax.tree.map(lambda a: (a.shape, a.dtype), params))
    assert "lm_head" not in params          # the head is the embedding
    assert lfm2.head(params) is params["wte"]["embedding"]


def test_logits_equal_the_reference_in_float32(seeded):
    params, x, _ = seeded
    with jax.default_matmul_precision("highest"):
        got, aux = jax.jit(lfm2.Lfm2(model_cfg()).apply)({"params": params}, x)
        want = jax.jit(lambda p: ref.logits_fn(p, x, SIZES))(params)
    np.testing.assert_allclose(got, want, atol=2e-5, rtol=2e-5)
    assert aux["moe_dropped"].tolist() == [0, 0, 0]


# Heads of 64 on 128 channels over 256 positions: the smallest shapes the
# head-size-64 attention route and the convolution's kernels take.
KERNEL_SIZES = {**SIZES, "n_head": 2, "n_kv_head": 1, "head_dim": 64,
                "n_embd": 128, "block_size": 256}


@pytest.mark.parametrize("variant", ["plain", "remat", "megablox_interpret",
                                     "pallas_interpret"])
def test_loss_and_every_gradient_leaf_equal_the_reference(
        seeded, ref_loss_and_grad, variant, monkeypatch):
    params, x, y = seeded
    cfg = model_cfg(remat=variant == "remat")
    if variant == "megablox_interpret":  # what 'auto' is on a tpu backend
        monkeypatch.setattr(moe, "resolve_gmm_impl", lambda impl: variant)
    if variant == "pallas_interpret":    # ... and the attention route at head
        # size 64 with the convolution's kernels, under remat as the cell
        # runs them
        sizes = KERNEL_SIZES
        cfg = model_cfg(attention_impl=variant, remat=True, **{
            k: sizes[k] for k in ("n_head", "n_kv_head", "head_dim", "n_embd",
                                  "block_size")})
        params = weights_lfm2.make_params(sizes, weights_lfm2.seed_key(3))
        x = jax.random.randint(jax.random.key(1), (2, 257), 0,
                               SIZES["vocab_size"])
        x, y = x[:, :-1], x[:, 1:]
        assert A.gqa_route(variant, 64, 256) == "bhtd-rep"
        assert short_conv.resolve_conv_impl(variant, 256, 128) == variant
        with jax.default_matmul_precision("highest"):
            ref_loss_and_grad = jax.jit(
                lambda p: ref.loss_and_grad(p, x, y, sizes))(params)
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
    leaf's where the leaf's own is smaller)."""
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


@pytest.mark.parametrize("fault, leaf", [
    ("routed", "h_1/moe/w_up"), ("taps", "h_1/conv/filter"),
    ("kv_mod", "h_2/attn_full/k_proj/kernel")])
def test_the_references_planted_faults_are_another_computation(
        seeded, ref_loss_and_grad, fault, leaf):
    """Each fault the limits are read against moves a leaf's gradient by
    far more than rounding does (the loss of random weights hardly moves;
    exchanging heads moves a leaf's direction, hardly its norm)."""
    params, x, y = seeded
    with jax.default_matmul_precision("highest"):
        _, broken = jax.jit(lambda p: ref.loss_and_grad(
            p, x, y, SIZES, leave_out=frozenset([fault])))(params)
    want, got = flat(ref_loss_and_grad[1])[leaf], flat(broken)[leaf]
    assert float(jnp.linalg.norm(got - want)) > 0.05 * float(
        jnp.linalg.norm(want))


def test_the_four_ranks_shares_add_up_to_the_uncut_layer():
    """Four shares of two experts each (eight of thirty-two in the cell):
    the shares' routed sums are the reference's uncut layer; no shared
    expert is added anywhere."""
    E, count = SIZES["num_experts"], 2
    d, F = SIZES["n_embd"], SIZES["moe_intermediate_size"]
    keys = jax.random.split(jax.random.key(5), 6)
    normal = lambda k, *s: 0.2 * jax.random.normal(k, s, jnp.float32)
    full = {"router": normal(keys[0], d, E),
            "expert_bias": normal(keys[1], E),     # moves the selection too
            "w_gate": normal(keys[2], E, d, F), "w_up": normal(keys[3], E, d, F),
            "w_down": normal(keys[4], E, F, d)}
    m = jax.random.normal(keys[5], (2, 32, d), jnp.float32)
    with jax.default_matmul_precision("highest"):
        whole = ref._moe(full, m, {**SIZES, "experts_held": (0, E)},
                         ref._ident, frozenset())
        total, held = 0.0, 0
        for first in range(0, E, count):
            cfg = model_cfg(experts_held=(first, count))
            share = {**full, **{k: full[k][first:first + count]
                                for k in ("w_gate", "w_up", "w_down")}}
            out, stats = jax.jit(lfm2.Moe(cfg).apply)({"params": share}, m)
            assert int(stats[2]) == 0
            held += int(stats[0])
            total = total + out
    assert held == m.shape[0] * m.shape[1] * SIZES["num_experts_per_tok"]
    np.testing.assert_allclose(total, whole, atol=2e-5, rtol=2e-5)


def test_selection_bias_moves_the_selection_and_not_the_weights(seeded):
    params, x, y = seeded
    cfg = model_cfg()
    xs = jax.random.normal(jax.random.key(4), (64, SIZES["n_embd"]))
    router = params["h_1"]["moe"]["router"]
    route = lambda bias: experts.route(
        xs, router, bias, cfg.num_experts_per_tok, norm=cfg.route_norm,
        scale=cfg.route_scale, eps=lfm2.ROUTE_EPS)
    sel0, _ = route(jnp.zeros(8))
    sel1, w1 = route(jnp.zeros(8).at[5].set(10.0))   # expert 5 wins everywhere
    assert bool((sel1 == 5).any(axis=1).all()) and not bool(
        (sel0 == 5).any(axis=1).all())
    # a pair both selections hold weighs by its own score, not score + bias
    got = jnp.take_along_axis(jax.nn.sigmoid(xs @ router), sel1, axis=1)
    np.testing.assert_allclose(
        w1, got / (got.sum(-1, keepdims=True) + 1e-6), rtol=1e-6)
    # ... and no gradient reaches it
    biased = weights_lfm2.make_params(
        SIZES, weights_lfm2.seed_key(3),
        0.3 * jax.random.normal(jax.random.key(6), (3, 8)))
    _, grads = program_loss_and_grad(cfg, biased, x, y)
    assert float(jnp.abs(grads["h_1"]["moe"]["expert_bias"]).max()) == 0.0
    with jax.default_matmul_precision("highest"):
        want, _ = jax.jit(lambda p: ref.loss_and_grad(p, x, y, SIZES))(biased)
        got, _ = program_loss(cfg, biased, x, y)
    assert abs(float(got) - float(want)) < 2e-6


def test_the_references_balanced_bias_evens_the_load(seeded):
    params, _, _ = seeded
    rows = jax.random.randint(jax.random.key(9), (32, 64), 0,
                              SIZES["vocab_size"])
    with jax.default_matmul_precision("highest"):
        bias, load = ref.balanced_bias(params, rows, SIZES)
    assert bias.shape == load.shape == (3, 8)
    assert float(load.max()) < 1.15 and float(load.min()) > 0.85


# -- the gated short convolution -----------------------------------------------

def _lax_conv(u, w):
    """The depth-wise causal convolution as lax's own, u (B, T, d), w (d, L):
    Conv1d(groups = d, padding L - 1) cut to the sequence."""
    d, L = w.shape
    return lax.conv_general_dilated(
        u, jnp.transpose(w)[:, None, :], window_strides=(1,),
        padding=[(L - 1, 0)], dimension_numbers=("NWC", "WIO", "NWC"),
        feature_group_count=d, precision=lax.Precision.HIGHEST)


@pytest.mark.parametrize("L", [1, 3, 4])
def test_short_conv_equals_lax_conv_forward_and_gradient(L):
    B, T, d = 2, 40, 24
    keys = jax.random.split(jax.random.key(L), 3)
    bcx = jax.random.normal(keys[0], (B, T, 3 * d), jnp.float32)
    w = jax.random.normal(keys[1], (d, L), jnp.float32)
    dy = jax.random.normal(keys[2], (B, T, d), jnp.float32)

    def plain(bcx, w):
        gate_in, gate_out, x = jnp.split(bcx, 3, axis=-1)
        return gate_out * _lax_conv(gate_in * x, w)

    got, vjp = jax.vjp(short_conv.gated_short_conv, bcx, w)
    want, vjp_want = jax.vjp(plain, bcx, w)
    np.testing.assert_allclose(got, want, atol=1e-5, rtol=1e-5)
    for g, g_want in zip(vjp(dy), vjp_want(dy)):
        np.testing.assert_allclose(g, g_want, atol=1e-4, rtol=1e-5)


@pytest.mark.parametrize("shape", [(2, 512, 256, 3), (1, 768, 384, 3),
                                   (1, 256, 128, 4), (1, 256, 128, 1)],
                         ids=lambda s: "x".join(map(str, s)))
@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
def test_short_conv_kernels_equal_the_plain_form(shape, dtype):
    """One pass each way (interpret mode) against the XLA form: the halo
    across row blocks, the first and last block's zeros, the filter's
    gradient summed over programs."""
    B, T, d, L = shape
    keys = jax.random.split(jax.random.key(T + d + L), 3)
    cast = lambda a: a.astype(dtype)
    bcx = cast(jax.random.normal(keys[0], (B, T, 3 * d), jnp.float32))
    w = jax.random.normal(keys[1], (d, L), jnp.float32)
    dy = cast(jax.random.normal(keys[2], (B, T, d), jnp.float32))
    assert short_conv.resolve_conv_impl("pallas_interpret", T, d) == (
        "pallas_interpret")
    run = lambda impl: jax.vjp(
        lambda a, b: short_conv.gated_short_conv(a, b, impl), bcx, w)
    (got, vjp), (want, vjp_want) = run("pallas_interpret"), run("xla")
    tol = dict(atol=1e-5, rtol=1e-5) if dtype == "float32" else dict(
        atol=0.13, rtol=2e-2)       # one bfloat16 rounding of sums up to ~16
    f32 = lambda a: np.asarray(a.astype(jnp.float32))
    np.testing.assert_allclose(f32(got), f32(want), **tol)
    (dbcx, dw), (dbcx_want, dw_want) = vjp(dy), vjp_want(dy)
    assert dbcx.dtype == bcx.dtype and dw.dtype == w.dtype
    np.testing.assert_allclose(f32(dbcx), f32(dbcx_want), **tol)
    np.testing.assert_allclose(dw, dw_want, rtol=1e-4, atol=1e-3)


def test_short_conv_impl_from_the_shapes():
    resolve = short_conv.resolve_conv_impl
    assert resolve("pallas", 8192, 2048) == "pallas"
    assert resolve("pallas_interpret", 256, 128) == "pallas_interpret"
    assert resolve("xla", 8192, 2048) == "xla"
    assert resolve("pallas", 8, 2048) == "xla"          # an init batch
    assert resolve("pallas", 8192, 2000) == "xla"       # lanes not whole
    with pytest.raises(ValueError, match="T % 256"):
        short_conv.gated_short_conv(jnp.zeros((1, 8, 3 * 128)),
                                    jnp.zeros((128, 3)), "pallas_interpret")
    with pytest.raises(ValueError, match="unknown short-convolution impl"):
        short_conv.gated_short_conv(jnp.zeros((1, 8, 3 * 128)),
                                    jnp.zeros((128, 3)), "auto")


def test_short_conv_is_causal_and_keeps_the_inputs_type():
    B, T, d = 1, 16, 8
    bcx = jax.random.normal(jax.random.key(0), (B, T, 3 * d), jnp.bfloat16)
    w = jax.random.normal(jax.random.key(1), (d, 3), jnp.float32)
    y = short_conv.gated_short_conv(bcx, w)
    assert y.dtype == jnp.bfloat16 and y.shape == (B, T, d)
    moved = short_conv.gated_short_conv(bcx.at[:, 9].add(1.0), w)
    same = np.asarray(y == moved).all(axis=(0, 2))
    # position t is unmoved by a change at t + 1; two taps reach back
    assert same[:9].all() and not same[9:12].any() and same[12:].all()


# -- attention at head size 64 --------------------------------------------------

def test_gqa_route_from_the_shapes():
    assert A.gqa_route("pallas", 128, 8192) == "btc-gqa"
    assert A.gqa_route("pallas", 64, 8192) == "bhtd-rep"
    assert A.gqa_route("pallas_interpret", 64, 128) == "bhtd-rep"
    assert A.gqa_route("pallas", 64, 8) == "xla"       # an init batch
    assert A.gqa_route("pallas", 32, 8192) == "xla"
    assert A.gqa_route("xla", 64, 8192) == "xla"
    assert A.resolve_gqa_impl("pallas", 64, 8192) == "xla"   # the prologue


def test_head_size_64_grouped_route_equals_xla_attention():
    """The (B, H, T, D) kernels on repeated KV heads, interpret mode, against
    xla_attention: forward and the three gradients (dK / dV summed back over
    the group)."""
    B, T, H, G, D = 1, 256, 4, 2, 64
    keys = jax.random.split(jax.random.key(0), 4)
    q = jax.random.normal(keys[0], (B, T, H * D), jnp.float32)
    k = jax.random.normal(keys[1], (B, T, G * D), jnp.float32)
    v = jax.random.normal(keys[2], (B, T, G * D), jnp.float32)
    do = jax.random.normal(keys[3], (B, T, H * D), jnp.float32)
    run = lambda impl: jax.vjp(
        lambda q, k, v: A.causal_attention_gqa(q, k, v, H, G, impl=impl,
                                               scope="attn_full"), q, k, v)
    got, vjp = run("pallas_interpret")
    want, vjp_want = run("xla")
    np.testing.assert_allclose(got, want, atol=2e-5, rtol=2e-5)
    for g, g_want in zip(vjp(do), vjp_want(do)):
        np.testing.assert_allclose(g, g_want, atol=2e-4, rtol=2e-4)


def test_gmm_tiling_from_the_shapes():
    """The measured tiling, no tile larger than its dimension: the same at
    both cells' expert shapes (the chip's probe, PERF.md §6 PR 33)."""
    assert moe.gmm_tiling(32768, 2048, 1024) == (512, 1024, 1024)
    assert moe.gmm_tiling(32768, 2048, 1792) == (512, 1024, 1024)
    assert moe.gmm_tiling(32768, 1792, 2048) == (512, 1024, 1024)
    assert moe.gmm_tiling(512, 32, 24) == (512, 32, 24)
    assert moe.gmm_tiling(128, 2048, 512) == (128, 1024, 512)


# -- the trainer -----------------------------------------------------------------

@pytest.fixture()
def lfm2_train_cfg(char_dataset, tmp_path):
    return train_cfg(
        out_dir=str(tmp_path / "out"), data_dir=char_dataset,
        dataset="shakespeare_char", vocab_size=0, batch_size=8,
        max_iters=2, lr_decay_iters=2, eval_interval=0, eval_iters=1,
        log_interval=1, warmup_iters=1, learning_rate=1e-3, min_lr=1e-4,
        tensorboard=False, seed=0, loss_chunk_size=32, remat=True)


def test_trainer_two_steps_save_restore_same_loss(lfm2_train_cfg):
    from nanosandbox_tpu.checkpoint import Checkpointer
    from nanosandbox_tpu.obs import opscopes, process_tracer
    from nanosandbox_tpu.train import Trainer, restore_for_inference

    cfg = lfm2_train_cfg
    trainer = Trainer(cfg)
    out = trainer.run()
    assert out["iter_num"] == 2 and out["model_family"] == "lfm2"
    assert np.isfinite(out["final_loss"])
    init = [s for s in process_tracer().spans() if s.name == "trainer_init"][-1]
    assert init.args["model_family"] == "lfm2"
    assert init.args["experts_held"] == [2, 4]
    assert init.args["layer_types"] == "conv,conv,full,conv"
    assert init.args["attn_route"] == "xla" and init.args["qk_prep"] == "xla"
    assert init.args["conv_mix"] == "xla"
    assert init.args["moe_row_mover"] == "xla"
    assert init.args["gmm_tiling"] == [512, 32, 24]
    rows = [s for s in process_tracer().spans() if s.name == "moe_rows"][-1]
    assert rows.args["moe_dropped"] == [0, 0, 0]
    assert len(rows.args["moe_held"]) == 3
    assert rows.args["chunks_run"] == [1, 1, 1]    # one chunk covers the bound
    parts = set(opscopes.step_parts().values())
    assert {"conv", "conv_mix", "attn_full", "moe_route",
            "moe_experts"} <= parts
    assert not {"attn", "attn_sliding", "moe_shared"} & parts
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
    assert extra["config"]["model_family"] == "lfm2"
    again = Trainer(dataclasses.replace(cfg, init_from="resume"))
    state2, _ = Checkpointer(cfg.out_dir).restore(again.abstract_state)
    loss = trainer.estimate_loss(state, eval_iters=1)
    loss2 = again.estimate_loss(state2, eval_iters=1)
    assert loss == loss2

    with pytest.raises(NotImplementedError, match="state for its conv"):
        restore_for_inference(cfg.out_dir)


@pytest.mark.parametrize("axis", ["mesh_sp", "mesh_tp"])
def test_seq_and_model_axes_are_refused_by_name(lfm2_train_cfg, axis):
    from nanosandbox_tpu.train import Trainer

    extra = {"attention_impl": "ring"} if axis == "mesh_sp" else {}
    with pytest.raises(NotImplementedError, match="data and fsdp axes"):
        Trainer(dataclasses.replace(lfm2_train_cfg, **{axis: 2}, **extra))


def test_config_says_what_is_missing():
    with pytest.raises(ValueError, match="layer_types needs 4"):
        Lfm2Config.from_train_config(train_cfg(layer_types="conv"), 96)
    with pytest.raises(ValueError, match="'conv' | 'full'"):
        Lfm2Config.from_train_config(
            train_cfg(layer_types="conv,sliding,full,conv"), 96)
    with pytest.raises(ValueError, match="experts_held inside"):
        Lfm2Config.from_train_config(train_cfg(experts_held=(6, 4)), 96)
    with pytest.raises(ValueError, match="conv_L_cache >= 1"):
        Lfm2Config.from_train_config(train_cfg(conv_L_cache=0), 96)
    assert model_cfg(experts_held=(0, 0)).experts_held == (0, 8)
