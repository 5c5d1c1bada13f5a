"""The ``deepseek_v3`` family (models/deepseek_v3.py, the latent kernels of
ops/attention.py, the shared expert of models/experts.py) against the plain
reference ``chipbench/reference/dsv3.py``, which imports nothing of the
program. Small sizes, CPU."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from chipbench import flops_dsv3, weights_dsv3
from chipbench.reference import dsv3 as ref
from nanosandbox_tpu.config import DeepseekV3Config, TrainConfig
from nanosandbox_tpu.models import deepseek_v3, experts
from nanosandbox_tpu.ops import attention as A
from nanosandbox_tpu.ops import moe

SIZES = {
    "n_layer": 4, "n_head": 4, "n_embd": 32, "vocab_size": 96,
    "block_size": 64, "kv_lora_rank": 24, "qk_nope_head_dim": 16,
    "qk_rope_head_dim": 8, "v_head_dim": 16, "num_dense_layers": 1,
    "intermediate_size": 48, "moe_intermediate_size": 24,
    "n_shared_experts": 2, "num_experts": 8, "num_experts_per_tok": 2,
    "experts_held": (2, 4), "route_scale": 2.446, "route_norm": True,
    "rope_theta": 50000.0, "rms_norm_eps": 1e-5,
}
# Heads of 128 + 64 with values of 128 over 256 positions: the kernels' real
# lanes at the smallest shapes they take.
KERNEL_SIZES = {**SIZES, "n_head": 2, "qk_nope_head_dim": 128,
                "qk_rope_head_dim": 64, "v_head_dim": 128, "n_embd": 128,
                "block_size": 256}


def train_cfg(**kw) -> TrainConfig:
    base = dict(model_family="deepseek_v3", compute_dtype="float32", **SIZES)
    return TrainConfig(**{**base, **kw})


def model_cfg(**kw) -> DeepseekV3Config:
    return DeepseekV3Config.from_train_config(train_cfg(**kw),
                                              SIZES["vocab_size"])


def _batch(T):
    x = jax.random.randint(jax.random.key(1), (2, T + 1), 0,
                           SIZES["vocab_size"])
    return x[:, :-1], x[:, 1:]


@pytest.fixture(scope="module")
def seeded():
    params = weights_dsv3.make_params(SIZES, weights_dsv3.seed_key(3))
    return (params, *_batch(64))


@pytest.fixture(scope="module")
def ref_loss_and_grad(seeded):
    params, x, y = seeded
    with jax.default_matmul_precision("highest"):
        return jax.jit(lambda p: ref.loss_and_grad(p, x, y, SIZES))(params)


def program_loss(cfg, params, x, y):
    from nanosandbox_tpu.models.loss import chunked_cross_entropy_loss

    hidden, aux = deepseek_v3.DeepseekV3(cfg).apply(
        {"params": params}, x, return_hidden=True)
    return chunked_cross_entropy_loss(
        hidden, deepseek_v3.head(params), y, chunk_size=32,
        compute_dtype=cfg.compute_dtype), aux


def program_loss_and_grad(cfg, params, x, y):
    return jax.jit(jax.value_and_grad(
        lambda p: program_loss(cfg, p, x, y), has_aux=True))(params)


flat = weights_dsv3.flatten


# -- the program against the plain reference ----------------------------------

def test_weights_file_has_the_programs_layout(seeded):
    params, x, _ = seeded
    own = jax.eval_shape(deepseek_v3.DeepseekV3(model_cfg()).init,
                         jax.random.key(0), x)["params"]
    assert (jax.tree.map(lambda a: (a.shape, a.dtype), own)
            == jax.tree.map(lambda a: (a.shape, a.dtype), params))
    assert deepseek_v3.head(params) is params["lm_head"]      # untied
    assert params["h_1"]["moe"]["moe_shared"]["up_proj"]["kernel"].shape == (
        32, 2 * 24)                     # n_shared_experts experts wide
    held = sum(a.size for a in jax.tree.leaves(params))
    assert held == flops_dsv3.n_params(SIZES)


def test_the_cells_sizes_count_what_the_issue_counted():
    """Moonlight's widths at the cell's cut: 668.9 M parameters, 2.64 GFLOP
    a token."""
    cell = {**SIZES, "n_layer": 6, "n_head": 16, "n_embd": 2048,
            "vocab_size": 20480, "block_size": 8192, "kv_lora_rank": 512,
            "qk_nope_head_dim": 128, "qk_rope_head_dim": 64,
            "v_head_dim": 128, "intermediate_size": 11264,
            "moe_intermediate_size": 1408, "num_experts": 64,
            "num_experts_per_tok": 6, "experts_held": (0, 8)}
    assert round(flops_dsv3.n_params(cell) / 1e6, 1) == 668.9
    per_token = flops_dsv3.train_flops_per_token(cell)
    assert round(per_token / 1e9, 2) == 2.64
    cfg = DeepseekV3Config.from_train_config(train_cfg(**cell), 20480)
    assert deepseek_v3.flops_per_token(cfg, 8192, 0) == per_token
    kernels = 6 * flops_dsv3.mla_attention_cost(cell, 1)["ops"] / 8192
    assert 0.28 < kernels / per_token < 0.30


def test_logits_equal_the_reference_in_float32(seeded):
    params, x, _ = seeded
    with jax.default_matmul_precision("highest"):
        got, aux = jax.jit(deepseek_v3.DeepseekV3(model_cfg()).apply)(
            {"params": params}, x)
        want = jax.jit(lambda p: ref.logits_fn(p, x, SIZES))(params)
    np.testing.assert_allclose(got, want, atol=2e-5, rtol=2e-5)
    assert aux["moe_dropped"].tolist() == [0, 0, 0]


@pytest.mark.parametrize("variant", ["plain", "remat", "megablox_interpret",
                                     "pallas_interpret"])
def test_loss_and_every_gradient_leaf_equal_the_reference(
        seeded, ref_loss_and_grad, variant, monkeypatch):
    params, x, y = seeded
    cfg = model_cfg(remat=variant == "remat")
    if variant == "megablox_interpret":  # what 'auto' is on a tpu backend
        monkeypatch.setattr(moe, "resolve_gmm_impl", lambda impl: variant)
    if variant == "pallas_interpret":    # ... and the latent kernels, under
        # remat as the cell runs them
        sizes = KERNEL_SIZES
        cfg = model_cfg(attention_impl=variant, remat=True, **{
            k: sizes[k] for k in ("n_head", "qk_nope_head_dim",
                                  "qk_rope_head_dim", "v_head_dim", "n_embd",
                                  "block_size")})
        params = weights_dsv3.make_params(sizes, weights_dsv3.seed_key(3))
        x, y = _batch(256)
        assert deepseek_v3.build(cfg, None)[1]["attn_route"] == "mla"
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
    ("routed", "h_1/moe/w_up"), ("rope", "h_2/attn_mla/q_proj"),
    ("scale", "h_2/attn_mla/q_proj")])
def test_the_references_planted_faults_are_another_computation(
        seeded, ref_loss_and_grad, fault, leaf):
    """Each fault the limits are read against moves a leaf's gradient by
    far more than rounding does (the loss of random weights hardly
    moves)."""
    params, x, y = seeded
    with jax.default_matmul_precision("highest"):
        _, broken = jax.jit(lambda p: ref.loss_and_grad(
            p, x, y, SIZES, leave_out=frozenset([fault])))(params)
    want, got = flat(ref_loss_and_grad[1])[leaf], flat(broken)[leaf]
    assert float(jnp.linalg.norm(got - want)) > 0.05 * float(
        jnp.linalg.norm(want))


def test_the_eight_ranks_shares_add_up_to_the_uncut_layer():
    """Eight shares of one expert each (eight of sixty-four in the cell):
    the shares' routed sums, with the shared expert that every rank computes
    alike counted ONCE, are the reference's uncut layer."""
    E, count = SIZES["num_experts"], 1
    d, F = SIZES["n_embd"], SIZES["moe_intermediate_size"]
    keys = jax.random.split(jax.random.key(5), 9)
    normal = lambda k, *s: 0.2 * jax.random.normal(k, s, jnp.float32)
    shared = {name: {"kernel": normal(keys[n], *shape)} for n, (name, shape)
              in enumerate((("gate_proj", (d, 2 * F)), ("up_proj", (d, 2 * F)),
                            ("down_proj", (2 * F, d))))}
    full = {"router": normal(keys[3], d, E),
            "expert_bias": normal(keys[4], E),     # moves the selection too
            "w_gate": normal(keys[5], E, d, F), "w_up": normal(keys[6], E, d, F),
            "w_down": normal(keys[7], E, F, d), "moe_shared": shared}
    m = jax.random.normal(keys[8], (2, 32, d), jnp.float32)
    with jax.default_matmul_precision("highest"):
        whole = ref._moe(full, m, {**SIZES, "experts_held": (0, E)},
                         ref._ident, frozenset())
        alone = ref._moe(full, m, SIZES, ref._ident, frozenset(["routed"]))
        total, held = 0.0, 0
        for first in range(0, E, count):
            cfg = model_cfg(experts_held=(first, count))
            share = {**full, **{k: full[k][first:first + count]
                                for k in ("w_gate", "w_up", "w_down")}}
            out, stats = jax.jit(deepseek_v3.Moe(cfg).apply)(
                {"params": share}, m)
            assert int(stats[2]) == 0
            held += int(stats[0])
            total = total + (out - alone)        # this rank's routed part
    assert held == m.shape[0] * m.shape[1] * SIZES["num_experts_per_tok"]
    np.testing.assert_allclose(total + alone, whole, atol=2e-5, rtol=2e-5)
    assert float(jnp.abs(alone).max()) > 0.01    # the shared expert is there


def test_selection_bias_moves_the_selection_and_not_the_weights(seeded):
    params, x, y = seeded
    cfg = model_cfg()
    xs = jax.random.normal(jax.random.key(4), (64, SIZES["n_embd"]))
    router = params["h_1"]["moe"]["router"]
    route = lambda bias: experts.route(
        xs, router, bias, cfg.num_experts_per_tok, norm=cfg.route_norm,
        scale=cfg.route_scale, eps=deepseek_v3.ROUTE_EPS)
    sel0, _ = route(jnp.zeros(8))
    sel1, w1 = route(jnp.zeros(8).at[5].set(10.0))   # expert 5 wins everywhere
    assert bool((sel1 == 5).any(axis=1).all()) and not bool(
        (sel0 == 5).any(axis=1).all())
    # a pair both selections hold weighs by its own score, not score + bias
    got = jnp.take_along_axis(jax.nn.sigmoid(xs @ router), sel1, axis=1)
    np.testing.assert_allclose(
        w1, 2.446 * got / got.sum(-1, keepdims=True), rtol=1e-6)
    # ... and no gradient reaches it
    biased = weights_dsv3.make_params(
        SIZES, weights_dsv3.seed_key(3),
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


# -- the latent kernels ----------------------------------------------------------

def _mla_operands(B, T, H, dtype=jnp.float32, seed=0, D=128, R=64):
    keys = jax.random.split(jax.random.key(seed), 6)
    draw = lambda k, *s: jax.random.normal(k, s, jnp.float32).astype(dtype)
    return (draw(keys[0], B, T, H * D), draw(keys[1], B, T, H, R),
            draw(keys[2], B, T, H * D), draw(keys[3], B, T, R),
            draw(keys[4], B, T, H * D)), draw(keys[5], B, T, H * D)


MLA_CASES = {
    "b2-t128-h2": (2, 128, 2, (128, 128)),
    "b2-t384-h3": (2, 384, 3, (128, 128)),
    "bq256-bk128": (1, 512, 2, (256, 128)),
    "bq128-bk256": (2, 512, 1, (128, 256)),
    "bq384-whole": (1, 384, 2, (512, 512)),
}


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["float32", "bfloat16"])
@pytest.mark.parametrize("case", list(MLA_CASES))
def test_mla_kernels_equal_xla_attention_on_concatenated_heads(
        monkeypatch, case, dtype):
    """The latent kernels in interpret mode (the forward, and the one-pass
    backward) against xla_attention on heads concatenated to 192 with the
    rotary key repeated: output and all five gradients."""
    B, T, H, blocks = MLA_CASES[case]
    monkeypatch.setattr(A, "DEFAULT_BLOCK", min(blocks))
    monkeypatch.setattr(A, "GQA_BWD_BLOCK_Q", blocks[0])
    monkeypatch.setattr(A, "GQA_BWD_BLOCK_K", blocks[1])
    operands, w = _mla_operands(B, T, H, dtype)
    assert A.mla_route("pallas_interpret", 128, 64, 128, T) == "mla"

    def run(impl):
        def loss(*xs):
            # a scope a case: the jitted kernel calls are cached by their
            # static arguments, which the patched blocks are not among
            o = A.causal_attention_mla(*xs, H, impl=impl,
                                       scope=f"{case}-{dtype.__name__}")
            return jnp.sum(o.astype(jnp.float32) * w.astype(jnp.float32)), o
        return jax.value_and_grad(loss, argnums=(0, 1, 2, 3, 4),
                                  has_aux=True)(*operands)

    with jax.default_matmul_precision("highest"):
        (_, o_x), g_x = run("xla")
        (_, o_p), g_p = run("pallas_interpret")
    f32 = lambda a: np.asarray(a.astype(jnp.float32))
    if dtype == jnp.float32:
        tol, g_tol = dict(atol=1e-5, rtol=1e-5), dict(atol=3e-5, rtol=3e-5)
    else:   # one rounding of an output of size ~1, of a gradient of size ~4
        tol, g_tol = dict(atol=2e-2, rtol=2e-2), dict(atol=6e-2, rtol=3e-2)
    np.testing.assert_allclose(f32(o_p), f32(o_x), **tol)
    for got, want in zip(g_p, g_x):
        assert got.shape == want.shape and got.dtype == want.dtype == dtype
        np.testing.assert_allclose(f32(got), f32(want), **g_tol)


def test_mla_rotary_keys_gradient_is_the_sum_over_heads():
    """dk_pe of H heads is the sum of the H one-head problems' dk_pe (the
    kernel leaves a float32 partial a head, summed once)."""
    B, T, H = 1, 256, 3
    (qn, qp, kn, kp, v), do = _mla_operands(B, T, H, seed=2)

    def grad(qn, qp, kn, v, heads):
        return jax.vjp(lambda kp: A.causal_attention_mla(
            qn, qp, kn, kp, v, heads, impl="pallas_interpret"), kp)[1]

    whole = grad(qn, qp, kn, v, heads=H)(do)[0]
    one = lambda x, h: x[..., h * 128:(h + 1) * 128]
    parts = [grad(one(qn, h), qp[:, :, h:h + 1], one(kn, h), one(v, h),
                  heads=1)(one(do, h))[0] for h in range(H)]
    np.testing.assert_allclose(whole, sum(parts), atol=2e-5, rtol=2e-5)


def test_mla_route_from_the_shapes_alone():
    route = A.mla_route
    assert route("pallas", 128, 64, 128, 8192) == "mla"
    assert route("pallas_interpret", 128, 64, 128, 128) == "mla"
    assert route("xla", 128, 64, 128, 8192) == "xla"
    assert route("pallas", 128, 64, 128, 8) == "xla"        # an init batch
    assert route("pallas", 16, 8, 16, 8192) == "xla"        # lanes not whole
    assert route("pallas", 128, 64, 256, 8192) == "xla"     # another v size
    # the one-pass backward's whole-T blocks of one head, inside VMEM
    assert route("pallas", 128, 64, 128, 23040) == "mla"
    assert route("pallas", 128, 64, 128, 23168) == "xla"
    with pytest.raises(ValueError, match="latent attention has impls"):
        route("ring", 128, 64, 128, 8192)


def test_mla_kernel_refuses_shapes_by_name():
    (qn, qp, kn, kp, v), _ = _mla_operands(1, 128, 2)
    call = lambda *xs: A.flash_attention_mla(*xs, 2, True)
    qp = qp.transpose(0, 2, 1, 3)
    assert call(qn, qp, kn, kp, v).shape == (1, 128, 256)
    for bad in ((qn, qp.transpose(0, 2, 1, 3), kn, kp, v),     # q_pe (B,T,H,R)
                (qn, qp, kn, jnp.repeat(kp, 2, axis=-1), v),   # a key a head
                (qn[:, :96], qp[:, :, :96], kn[:, :96], kp[:, :96],
                 v[:, :96])):                                  # T off the grid
        with pytest.raises(ValueError, match="flash_attention_mla needs"):
            call(*bad)


def test_gmm_tiling_at_the_expert_width_1408():
    """ONE rule on the shapes (ops.moe.gmm_tiling): 1024 across an expert's
    widths unless 512 pads one of them less: 1408 is 2048 in tiles of 1024
    and 1536 in tiles of 512. The other cells' widths keep their tiles."""
    assert moe.gmm_tiling(24576, 2048, 1408) == (512, 2048, 512)
    assert moe.gmm_tiling(24576, 1408, 2048) == (512, 1408, 512)
    assert moe.gmm_tiling(32768, 2048, 1024) == (512, 1024, 1024)
    assert moe.gmm_tiling(32768, 2048, 1792) == (512, 1024, 1024)
    assert moe.gmm_tiling(32768, 1792, 2048) == (512, 1024, 1024)
    assert moe.gmm_tiling(512, 32, 24) == (512, 32, 24)


# -- the trainer -----------------------------------------------------------------

@pytest.fixture()
def dsv3_train_cfg(char_dataset, tmp_path):
    return train_cfg(
        out_dir=str(tmp_path / "out"), data_dir=char_dataset,
        dataset="shakespeare_char", vocab_size=0, batch_size=8,
        max_iters=2, lr_decay_iters=2, eval_interval=0, eval_iters=1,
        log_interval=1, warmup_iters=1, learning_rate=1e-3, min_lr=1e-4,
        tensorboard=False, seed=0, loss_chunk_size=32, remat=True)


def test_trainer_two_steps_save_restore_same_loss(dsv3_train_cfg):
    from nanosandbox_tpu.checkpoint import Checkpointer
    from nanosandbox_tpu.obs import opscopes, process_tracer
    from nanosandbox_tpu.train import Trainer, restore_for_inference

    cfg = dsv3_train_cfg
    trainer = Trainer(cfg)
    out = trainer.run()
    assert out["iter_num"] == 2 and out["model_family"] == "deepseek_v3"
    assert np.isfinite(out["final_loss"])
    init = [s for s in process_tracer().spans() if s.name == "trainer_init"][-1]
    assert init.args["model_family"] == "deepseek_v3"
    assert init.args["experts_held"] == [2, 4]
    assert init.args["layer_types"] == "mla,mla,mla,mla"
    assert init.args["attn_route"] == "xla" and init.args["mla_bwd"] == "xla"
    assert init.args["attn_layout"] == "bhtd"
    assert init.args["moe_row_mover"] == "xla"
    assert init.args["gmm_tiling"] == [512, 32, 24]
    rows = [s for s in process_tracer().spans() if s.name == "moe_rows"][-1]
    assert rows.args["moe_dropped"] == [0, 0, 0]
    assert len(rows.args["moe_held"]) == 3
    assert rows.args["chunks_run"] == [1, 1, 1]    # one chunk covers the bound
    parts = set(opscopes.step_parts().values())
    assert {"attn_mla", "mla_prep", "mlp", "moe_route", "moe_experts",
            "moe_shared", "ln"} <= parts
    assert not {"attn", "attn_full", "attn_sliding", "conv"} & parts
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
    assert extra["config"]["model_family"] == "deepseek_v3"
    again = Trainer(dataclasses.replace(cfg, init_from="resume"))
    state2, _ = Checkpointer(cfg.out_dir).restore(again.abstract_state)
    loss = trainer.estimate_loss(state, eval_iters=1)
    loss2 = again.estimate_loss(state2, eval_iters=1)
    assert loss == loss2

    with pytest.raises(NotImplementedError, match="cache of the latent"):
        restore_for_inference(cfg.out_dir)


@pytest.mark.parametrize("keys, said", [
    (dict(mesh_sp=2, attention_impl="ring"), "data and fsdp axes"),
    (dict(mesh_tp=2), "data and fsdp axes"),
    (dict(q_lora_rank=1536), "query latent"),
    (dict(n_group=8, topk_group=4), "group-limited expert selection"),
])
def test_what_is_not_built_is_refused_by_name(dsv3_train_cfg, keys, said):
    from nanosandbox_tpu.train import Trainer

    with pytest.raises(NotImplementedError, match=said):
        Trainer(dataclasses.replace(dsv3_train_cfg, **keys))


def test_init_from_weights_are_refused(dsv3_train_cfg):
    with pytest.raises(ValueError, match="starts from scratch"):
        deepseek_v3.check(dsv3_train_cfg, pretrained=True)


def test_config_says_what_is_missing():
    make = lambda **kw: DeepseekV3Config.from_train_config(train_cfg(**kw), 96)
    with pytest.raises(ValueError, match="layer_types needs 4"):
        make(layer_types="mla")
    with pytest.raises(ValueError, match="kv_lora_rank, qk_nope_head_dim"):
        make(kv_lora_rank=0)
    with pytest.raises(ValueError, match="even qk_rope_head_dim"):
        make(qk_rope_head_dim=7)
    with pytest.raises(ValueError, match="n_shared_experts > 0"):
        make(n_shared_experts=0)
    with pytest.raises(ValueError, match="experts_held inside"):
        make(experts_held=(6, 4))
    assert make(experts_held=(0, 0)).experts_held == (0, 8)
    assert make().layer_types == ("mla",) * 4


def test_afmoes_tree_is_unchanged_by_the_shared_experts_move():
    """``afmoe.Moe`` calls models/experts.shared_expert at its own width: the
    leaf stays ``moe/moe_shared/...`` and chipbench/weights_afmoe.py's layout
    is still the program's."""
    from chipbench import weights_afmoe
    from nanosandbox_tpu.config import AfmoeConfig
    from nanosandbox_tpu.models import afmoe

    sizes = {"n_layer": 3, "n_head": 4, "n_kv_head": 2, "head_dim": 16,
             "n_embd": 32, "vocab_size": 96, "block_size": 64,
             "layer_types": ("sliding", "full", "sliding"),
             "sliding_window": 16, "num_dense_layers": 1,
             "intermediate_size": 48, "moe_intermediate_size": 24,
             "num_experts": 8, "num_experts_per_tok": 2,
             "experts_held": (2, 4)}
    cfg = AfmoeConfig.from_train_config(TrainConfig(
        model_family="afmoe", compute_dtype="float32",
        **{**sizes, "layer_types": ",".join(sizes["layer_types"])}), 96)
    own = jax.eval_shape(afmoe.Afmoe(cfg).init, jax.random.key(0),
                         jnp.zeros((2, 64), jnp.int32))["params"]
    want = weights_afmoe.make_params(sizes, weights_afmoe.seed_key(0))
    assert (jax.tree.map(lambda a: (a.shape, a.dtype), own)
            == jax.tree.map(lambda a: (a.shape, a.dtype), want))
    assert set(own["h_1"]["moe"]["moe_shared"]) == {"gate_proj", "up_proj",
                                                    "down_proj"}
