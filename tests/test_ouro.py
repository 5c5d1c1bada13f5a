"""The ``ouro`` family (models/ouro.py: one stack of layers run
``total_ut_steps`` times, an exit after every pass, the loss over the exits)
through ``Trainer``'s own loss and optimizer, against the plain reference
``chipbench/reference/ouro.py``, which imports nothing of the program. Small
sizes, CPU."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

from chipbench import flops_ouro, weights_ouro
from chipbench.reference import ouro as ref
from nanosandbox_tpu.config import OuroConfig, TrainConfig
from nanosandbox_tpu.models import experts, ouro
from nanosandbox_tpu.ops import attention as A

SIZES = {
    "n_layer": 2, "n_head": 4, "n_kv_head": 4, "head_dim": 16, "n_embd": 64,
    "intermediate_size": 96, "vocab_size": 96, "block_size": 64,
    "total_ut_steps": 4, "rope_theta": 1e6, "rms_norm_eps": 1e-6,
    "exit_entropy_weight": ouro.EXIT_ENTROPY_WEIGHT,
}
OPT = {"learning_rate": 1e-3, "min_lr": 1e-4, "warmup_iters": 0,
       "lr_decay_iters": 20, "max_iters": 20, "decay_lr": True,
       "weight_decay": 0.1, "beta1": 0.9, "beta2": 0.95, "grad_clip": 1.0}


def train_cfg(**kw) -> TrainConfig:
    base = dict(model_family="ouro", compute_dtype="float32",
                **{k: SIZES[k] for k in (
                    "n_layer", "n_head", "n_kv_head", "head_dim", "n_embd",
                    "intermediate_size", "vocab_size", "block_size",
                    "total_ut_steps", "rope_theta", "rms_norm_eps")})
    return TrainConfig(**{**base, **kw})


@pytest.fixture(scope="module")
def trainer(char_dataset, tmp_path_factory):
    from nanosandbox_tpu.train import Trainer

    return Trainer(train_cfg(
        out_dir=str(tmp_path_factory.mktemp("ouro") / "out"),
        data_dir=char_dataset, dataset="shakespeare_char", batch_size=8,
        loss_chunk_size=32, tensorboard=False, seed=0, **OPT))


@pytest.fixture(scope="module")
def seeded():
    params = weights_ouro.make_params(SIZES, weights_ouro.seed_key(5))
    x = jax.random.randint(jax.random.key(1), (2, 65), 0, SIZES["vocab_size"])
    return params, x[:, :-1], x[:, 1:]


def program_loss_and_grad(trainer, params, x, y):
    """The trainer's own loss: the model's passes, four chunked heads under
    the scope ``exits``, the family's ``exit_loss``."""
    return jax.jit(jax.value_and_grad(
        lambda p: trainer._loss_fn(p, x, y, None), has_aux=True))(params)


def _both(trainer, params, x, y, sizes):
    with jax.default_matmul_precision("highest"):
        mine = program_loss_and_grad(trainer, params, x, y)
        theirs = jax.jit(lambda p: ref.loss_and_grad(p, x, y, sizes))(params)
    return mine, theirs


@pytest.fixture(scope="module")
def both(trainer, seeded):
    return _both(trainer, *seeded, SIZES)


# Heads of 128 lanes and T 128: what the grouped-query kernels and the rotary
# kernel (ops.attention.qk_rotary) walk, here in the interpreter.
KERNEL_SIZES = {**SIZES, "n_head": 2, "n_kv_head": 2, "head_dim": 128,
                "block_size": 128}


@pytest.fixture(scope="module")
def both_kernels(char_dataset, tmp_path_factory):
    """``both`` with attention_impl 'pallas_interpret' at KERNEL_SIZES: the
    looped stack's attention through the grouped-query kernels, its rotary
    positions through qk_rotary, against the same float32 reference."""
    from nanosandbox_tpu.train import Trainer

    trainer = Trainer(train_cfg(
        out_dir=str(tmp_path_factory.mktemp("ouro_kernels") / "out"),
        data_dir=char_dataset, dataset="shakespeare_char", batch_size=8,
        loss_chunk_size=32, tensorboard=False, seed=0,
        attention_impl="pallas_interpret",
        **{k: KERNEL_SIZES[k] for k in ("n_head", "n_kv_head", "head_dim",
                                        "block_size")}, **OPT))
    assert trainer.qk_prep == "pallas_interpret"
    params = weights_ouro.make_params(KERNEL_SIZES, weights_ouro.seed_key(5))
    x = jax.random.randint(jax.random.key(1), (2, 129), 0,
                           KERNEL_SIZES["vocab_size"])
    return _both(trainer, params, x[:, :-1], x[:, 1:], KERNEL_SIZES)


flat = weights_ouro.flatten


def test_weights_file_has_the_programs_layout(trainer, seeded):
    params, _, _ = seeded
    own = trainer.abstract_state["params"]
    assert (jax.tree.map(lambda a: (a.shape, a.dtype), own)
            == jax.tree.map(lambda a: (a.shape, a.dtype), params))
    assert ouro.head(params) is params["lm_head"]                 # untied
    assert sum(a.size for a in jax.tree.leaves(params)) == flops_ouro.n_params(
        SIZES)


@pytest.mark.parametrize("fixture", ["both", "both_kernels"],
                         ids=["xla", "kernels"])
def test_loss_and_every_gradient_leaf_equal_the_reference(fixture, request):
    """The XLA path at heads of 16, and the kernels' path (the grouped-query
    kernels and qk_rotary in the interpreter) at heads of 128."""
    mine, theirs = request.getfixturevalue(fixture)
    (loss, aux), grads = mine
    ref_loss, ref_grads = theirs
    np.testing.assert_allclose(loss, ref_loss, rtol=1e-5)
    got, want = flat(grads), flat(ref_grads)
    assert set(got) == set(want)
    for k in want:
        np.testing.assert_allclose(got[k], want[k], atol=1e-6, rtol=2e-4,
                                   err_msg=k)
    # the gate's and the last pass's leaves are live: all four passes and
    # the exit weights reach the gradient
    assert float(jnp.abs(got["loop/exit_gate/kernel"]).max()) > 0
    assert aux["exit_p"].shape == aux["exit_nll"].shape == (4,)
    assert float(aux["exit_p"].sum()) == pytest.approx(1.0, abs=1e-5)


def test_one_adamw_update_equals_the_reference(trainer, seeded, both):
    """The trainer's optimizer (global-norm clip, AdamW with decay on the
    matrices, the GPT-2 cells' schedule) against the reference's, both on
    the reference's gradient: a first Adam step is nearly lr * sign(g), so
    an element whose gradient lies within rounding of zero would flip on
    the program's (held to the reference above)."""
    params, _, _ = seeded
    _, ref_grads = both[1]
    mine = jax.jit(lambda p, g: optax.apply_updates(p, trainer.tx.update(
        g, trainer.tx.init(p), p)[0]))(params, ref_grads)
    zeros = jax.tree.map(jnp.zeros_like, params)
    theirs, _, _, _ = ref.adamw_step(params, zeros, zeros, ref_grads, 0, OPT)
    got, want = flat(mine), flat(theirs)
    for k in want:
        np.testing.assert_allclose(got[k], want[k], atol=1e-7, rtol=1e-5,
                                   err_msg=k)
    moved = [k for k in want if not np.allclose(want[k], flat(params)[k])]
    assert len(moved) == len(want)


def test_one_pass_is_one_exits_plain_cross_entropy(trainer, seeded):
    """total_ut_steps 1: p = 1 on the one exit, entropy 0, and the loss is
    that exit's mean cross entropy (models/loss.py's chunked mean)."""
    from nanosandbox_tpu.models.loss import chunked_cross_entropy_loss

    params, x, y = seeded
    cfg = dataclasses.replace(trainer.model_cfg, total_ut_steps=1)
    model = ouro.Ouro(cfg)
    hidden, aux = model.apply({"params": params}, x, return_hidden=True)
    assert hidden.shape == (1, 2, 64, 64)
    nll = jnp.stack([ref.token_nll(hidden[0], params["lm_head"], y)])
    loss, aux = ouro.exit_loss(nll, aux)
    plain = chunked_cross_entropy_loss(hidden[0], params["lm_head"], y,
                                       chunk_size=32, compute_dtype="float32")
    np.testing.assert_allclose(loss, plain, rtol=1e-6)
    assert aux["exit_p"].tolist() == [1.0]


def _untied_loss(copies, shared, x, y):
    """The reference's loss with pass t reading copies[t] of the stack (the
    layers, the final norm and the gate), shared the embedding and head."""
    eps = SIZES["rms_norm_eps"]
    h = shared["wte"]["embedding"][x]
    states, logits = [], []
    for loop in copies:
        for i in range(SIZES["n_layer"]):
            h = ref._layer(h, loop[f"h_{i}"], SIZES, ref._ident)
        h = ref._rms_norm(h, loop["ln_f"]["scale"], eps)
        gate = loop["exit_gate"]
        states.append(h)
        logits.append((h @ gate["kernel"])[..., 0] + gate["bias"][0])
    nll = [ref.token_nll(s, shared["lm_head"], y) for s in states]
    p = ref.exit_probs(logits)
    return jnp.mean(sum(q * n for q, n in zip(p, nll))
                    - SIZES["exit_entropy_weight"]
                    * -sum(q * jnp.log(q) for q in p))


def test_a_weights_gradient_is_the_sum_of_its_four_uses(both, seeded):
    params, x, y = seeded
    (_, _), grads = both[0]
    shared = {k: params[k] for k in ("wte", "lm_head")}
    with jax.default_matmul_precision("highest"):
        per_pass = jax.jit(jax.grad(_untied_loss))([params["loop"]] * 4,
                                                   shared, x, y)
    summed = flat(jax.tree.map(lambda *g: sum(g), *per_pass))
    got = flat(grads["loop"])
    for k in summed:
        np.testing.assert_allclose(got[k], summed[k], atol=1e-6, rtol=2e-4,
                                   err_msg=k)
    # and no pass's share is zero: each use contributes
    assert all(float(jnp.abs(flat(g)["h_0/mlp/up_proj/kernel"]).max()) > 0
               for g in per_pass)


def test_the_exit_distribution_sums_to_one_with_the_rest_on_the_last():
    z = jax.random.normal(jax.random.key(0), (4, 3, 5)) * 3.0
    log_p = ouro.exit_log_probs(z)
    p = jnp.exp(log_p)
    np.testing.assert_allclose(p.sum(axis=0), 1.0, rtol=1e-6)
    lam = jax.nn.sigmoid(z)
    np.testing.assert_allclose(p[-1], jnp.prod(1 - lam[:-1], axis=0),
                               rtol=1e-4)
    np.testing.assert_allclose(p[0], lam[0], rtol=1e-6)
    np.testing.assert_allclose(p, jnp.stack(ref.exit_probs(list(z))),
                               rtol=1e-4, atol=1e-7)
    # the last pass's own gate is not read
    z2 = z.at[-1].set(-z[-1])
    np.testing.assert_allclose(ouro.exit_log_probs(z2), log_p)


def test_flops_per_token_counts_four_applications_and_four_heads():
    cell = {**SIZES, "n_layer": 6, "n_head": 16, "n_kv_head": 16,
            "head_dim": 128, "n_embd": 2048, "intermediate_size": 5632,
            "vocab_size": 49152, "block_size": 8192}
    cfg = OuroConfig.from_train_config(train_cfg(**{
        k: cell[k] for k in ("n_layer", "n_head", "n_kv_head", "head_dim",
                             "n_embd", "intermediate_size", "block_size")}),
        49152)
    per_token = ouro.flops_per_token(cfg, 8192, 0)
    assert per_token == pytest.approx(flops_ouro.train_flops_per_token(cell))
    one = ouro.flops_per_token(dataclasses.replace(cfg, total_ut_steps=1),
                               8192, 0)
    assert per_token == pytest.approx(4 * one)
    assert round(per_token / 1e9, 1) == 12.2            # the issue's count
    heads = 6.0 * 4 * 49152 * 2048
    assert 0.19 < heads / per_token < 0.21


def test_grouped_query_kernels_at_one_kv_head_a_query_head():
    """causal_attention_gqa at H = G on the (B, T, heads * D) layout, the
    route the cell takes ('btc-gqa'), in interpret mode: forward and the
    gradients against xla_attention."""
    B, T, H, D = 1, 256, 2, 128
    k0, k1, k2, k3 = jax.random.split(jax.random.key(2), 4)
    q, k, v = (jax.random.normal(kk, (B, T, H * D)) for kk in (k0, k1, k2))
    do = jax.random.normal(k3, (B, T, H * D))
    assert A.gqa_route("pallas_interpret", D, T) == "btc-gqa"

    def run(impl):
        f = lambda q, k, v: jnp.sum(A.causal_attention_gqa(
            q, k, v, H, H, impl=impl) * do)
        return jax.value_and_grad(f, argnums=(0, 1, 2))(q, k, v)

    with jax.default_matmul_precision("highest"):
        (got, g_got), (want, g_want) = run("pallas_interpret"), run("xla")
    np.testing.assert_allclose(got, want, rtol=1e-4)
    for a, b in zip(g_got, g_want):
        np.testing.assert_allclose(a, b, atol=2e-4, rtol=1e-3)


def _xla_rotary(x, heads, theta):
    B, T, HD = x.shape
    y = experts.rotary(x.reshape(B, T, heads, HD // heads).astype(
        jnp.float32), theta)
    return y.reshape(B, T, HD).astype(x.dtype)


@pytest.mark.parametrize("T", [128, 384])
@pytest.mark.parametrize("heads", [16, 4])
@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
def test_rotary_kernel_equals_the_xla_rotary(dtype, heads, T):
    """ops.attention.qk_rotary in interpret mode against models/experts.rotary
    (the XLA path: the permutation product at full precision): output and
    input gradient, float32 to rounding, bfloat16 to one bfloat16 step of
    the largest value (both round the same float32 numbers)."""
    D, theta = 128, SIZES["rope_theta"]
    ks = jax.random.split(jax.random.key(heads + T), 2)
    x = (2.0 * jax.random.normal(ks[0], (2, T, heads * D))).astype(dtype)
    w = jax.random.normal(ks[1], (2, T, heads * D)).astype(dtype)

    def run(rotate):
        def loss(x):
            z = rotate(x)
            return jnp.sum((z * w).astype(jnp.float32)), z
        return jax.value_and_grad(loss, has_aux=True)(x)

    with jax.default_matmul_precision("highest"):
        (_, z_x), dx_x = run(lambda x: _xla_rotary(x, heads, theta))
    (_, z_p), dx_p = run(lambda x: A.qk_rotary(x, heads, theta, True))
    assert z_p.dtype == dx_p.dtype == x.dtype
    f32 = lambda a: np.asarray(a, np.float32)
    step = 2.0 ** -8 if dtype == "bfloat16" else 2e-6
    for got, want in ((z_p, z_x), (dx_p, dx_x)):
        assert np.abs(f32(got) - f32(want)).max() <= step * np.abs(
            f32(want)).max()


def test_the_rotary_kernel_keeps_nothing_for_its_backward(capsys):
    """A rotation's transpose is its inverse: the custom VJP's forward hands
    its backward no residual, and differentiating it saves no array (the
    normed prologue, qk_prep, keeps its input and scale)."""
    x = jnp.ones((1, 128, 2 * 128))
    _, res = A._qk_rotary_fwd_rule(x, 2, 1e6, True)
    assert jax.tree.leaves(res) == []
    from jax.ad_checkpoint import print_saved_residuals

    print_saved_residuals(lambda x: A.qk_rotary(x, 2, 1e6, True), x)
    assert capsys.readouterr().out == ""
    print_saved_residuals(
        lambda x, s: A.qk_prep(x, s, 2, 1e-5, 1e6, True), x, jnp.ones(128))
    assert "f32[1,128,256] from the argument x" in capsys.readouterr().out


def test_the_rotary_kernel_refuses_shapes_it_cannot_walk():
    with pytest.raises(ValueError, match="D % 128"):
        A.qk_rotary(jnp.zeros((1, 128, 4 * 64)), 4, 1e6, True)
    with pytest.raises(ValueError, match="T % 128"):
        A.qk_rotary(jnp.zeros((1, 8, 4 * 128)), 4, 1e6, True)
    with pytest.raises(ValueError, match="heads=3"):
        A.qk_rotary(jnp.zeros((1, 128, 4 * 128)), 3, 1e6, True)
    # and the model takes the XLA rotary wherever the kernels cannot go
    assert A.resolve_gqa_impl("pallas_interpret", 16, 128) == "xla"


def test_the_cli_trains_two_steps_and_leaves_the_exits_instants(
        char_dataset, tmp_path):
    """``python -m nanosandbox_tpu.train configs/train_ouro_2_6b_pp8.py``,
    its widths cut to a toy by flags: the family's normal path."""
    import os

    from nanosandbox_tpu import train
    from nanosandbox_tpu.obs import opscopes, process_tracer
    from nanosandbox_tpu.train import restore_for_inference

    flags = dict(out_dir=tmp_path / "out", data_dir=char_dataset,
                 dataset="shakespeare_char", vocab_size=0, batch_size=8,
                 max_iters=2, lr_decay_iters=2, eval_interval=0,
                 eval_iters=1, log_interval=1, warmup_iters=1, seed=0,
                 tensorboard=False, loss_chunk_size=32, device="cpu",
                 compute_dtype="float32",
                 **{k: SIZES[k] for k in ("n_layer", "n_head", "n_kv_head",
                                          "head_dim", "n_embd",
                                          "intermediate_size",
                                          "block_size")})
    config = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "configs", "train_ouro_2_6b_pp8.py")
    process_tracer().clear()
    out = train.main([config, *(f"--{k}={v}" for k, v in flags.items())])
    assert out["iter_num"] == 2 and out["model_family"] == "ouro"
    assert np.isfinite(out["final_loss"])
    spans = process_tracer().spans()
    init = [s for s in spans if s.name == "trainer_init"][-1]
    assert {"loops": 4, "layers_held": 2, "remat_policy": "full",
            "attn_route": "xla", "attn_layout": "bhtd",
            "qk_prep": "xla"}.items() <= \
        init.args.items()
    exits = [s for s in spans if s.name == "ouro_exits"]
    assert [s.args["iter"] for s in exits] == [0, 1]
    assert sum(exits[-1].args["exit_p"]) == pytest.approx(1.0, abs=1e-5)
    assert len(exits[-1].args["exit_nll"]) == 4
    parts = set(opscopes.step_parts().values())
    assert {"exits", "attn_full", "mlp", "ln", "embed", "optimizer"} <= parts
    with pytest.raises(NotImplementedError, match="one KV cache a pass"):
        restore_for_inference(str(flags["out_dir"]))


@pytest.mark.parametrize("keys, said", [
    (dict(mesh_sp=2, attention_impl="ring"), "data and fsdp axes"),
    (dict(mesh_tp=2), "data and fsdp axes"),
])
def test_what_is_not_built_is_refused_by_name(keys, said):
    with pytest.raises(NotImplementedError, match=said):
        ouro.check(train_cfg(**keys), pretrained=False)


def test_init_from_weights_are_refused_and_the_config_says_what_is_missing():
    with pytest.raises(ValueError, match="starts from scratch"):
        ouro.check(train_cfg(), pretrained=True)
    make = lambda **kw: OuroConfig.from_train_config(train_cfg(**kw), 96)
    with pytest.raises(ValueError, match="intermediate_size must be set"):
        make(intermediate_size=0)
    with pytest.raises(ValueError, match="total_ut_steps"):
        make(total_ut_steps=0)
    with pytest.raises(ValueError, match="n_kv_head must divide"):
        make(n_kv_head=3)
    assert make(n_kv_head=0, head_dim=0).head_dim == 16
