"""The ``granite`` family (models/granite.py: Mamba-2 state-space layers
beside grouped-query attention with no positions, the muP multipliers) and
its scan (ops/ssd.py) through ``Trainer``'s own loss, against the plain
reference ``chipbench/reference/granite.py``, whose scan is the token
recurrence and which imports nothing of the program. Small sizes, CPU.

Tolerances. Everything here is float32 at ``highest`` on both sides, so the
two differ by the order in which float32 sums run (the chunked form sums a
chunk's terms as products, the recurrence one token at a time) and by one
thing more: the chunked form takes a decay inside a chunk as a difference
of two running sums of dt A (cs_t - cs_s, as Mamba-2's own kernels do),
which loses |cs| * 2^-24 of the exponent. The scan's values are held to
rtol 2e-4 and 1e-5 of their largest magnitude, its gradients to 1e-4 of each
one's largest magnitude, 5e-4 where dt is large (at dt A of -45 a token, cs
reaches -720 inside a chunk of 16, and A's gradient, a sum over every
token, reads up to 2e-4 of its largest off there); the model's loss to rtol 1e-5 and each gradient leaf to
rtol 2e-4 / atol 1e-6, as the other families' tests hold theirs. The scan's
kernels run in the interpreter under the same tolerances.
"""

import dataclasses
import functools
import re

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

from chipbench import flops_granite, weights_granite
from chipbench.reference import granite as ref
from nanosandbox_tpu.config import GraniteConfig, TrainConfig
from nanosandbox_tpu.models import granite
from nanosandbox_tpu.ops import mamba_conv as conv_op
from nanosandbox_tpu.ops import ssd as ssd_op

SIZES = {
    "n_layer": 3, "n_head": 4, "n_kv_head": 2, "head_dim": 16, "n_embd": 64,
    "intermediate_size": 96, "vocab_size": 96, "block_size": 64,
    "layer_types": ["mamba", "attention", "mamba"],
    "mamba_n_heads": 4, "mamba_d_head": 16, "mamba_d_state": 16,
    "mamba_n_groups": 1, "mamba_d_conv": 4, "mamba_chunk_size": 16,
    "embedding_multiplier": 12.0, "residual_multiplier": 0.22,
    "attention_multiplier": 0.015625, "logits_scaling": 8.0,
    "rms_norm_eps": 1e-5,
}
OPT = {"learning_rate": 1e-3, "min_lr": 1e-4, "warmup_iters": 0,
       "lr_decay_iters": 20, "max_iters": 20, "decay_lr": True,
       "weight_decay": 0.1, "beta1": 0.9, "beta2": 0.95, "grad_clip": 1.0}
KEYS = ("n_layer", "n_head", "n_kv_head", "head_dim", "n_embd",
        "intermediate_size", "vocab_size", "block_size", "mamba_n_heads",
        "mamba_d_head", "mamba_d_state", "mamba_n_groups", "mamba_d_conv",
        "mamba_chunk_size", "embedding_multiplier", "residual_multiplier",
        "attention_multiplier", "logits_scaling", "rms_norm_eps")
flat = weights_granite.flatten


def train_cfg(**kw) -> TrainConfig:
    base = dict(model_family="granite", compute_dtype="float32",
                layer_types=",".join(SIZES["layer_types"]),
                **{k: SIZES[k] for k in KEYS})
    return TrainConfig(**{**base, **kw})


# -- the scan against the token recurrence -------------------------------------

IMPLS = ["xla", "pallas_interpret"]
SCAN = {"mamba_n_heads": 4, "mamba_d_head": 8, "mamba_d_state": 16,
        "mamba_n_groups": 2, "mamba_chunk_size": 16}


def _scan_inputs(T, dt_shift, seed=0):
    k = jax.random.split(jax.random.key(seed), 6)
    H, P = SCAN["mamba_n_heads"], SCAN["mamba_d_head"]
    GN = SCAN["mamba_n_groups"] * SCAN["mamba_d_state"]
    x = jax.random.normal(k[0], (2, T, H * P))
    dt = jax.nn.softplus(jax.random.normal(k[1], (2, T, H)) + dt_shift)
    A = -jnp.exp(jax.random.uniform(k[2], (H,), maxval=2.7))
    B = 0.5 * jax.random.normal(k[3], (2, T, GN))
    C = 0.5 * jax.random.normal(k[4], (2, T, GN))
    D = jax.random.normal(k[5], (H,))
    return x, dt, A, B, C, D


def _chunked(*a, impl="xla", groups=SCAN["mamba_n_groups"],
             dtype=jnp.float32):
    return ssd_op.ssd(*a, chunk=SCAN["mamba_chunk_size"], groups=groups,
                      dtype=dtype, impl=impl)[0]


def _token_by_token(x, dt, A, B, C, D):
    """The reference's recurrence (one segment: T is at most its SEGMENT)."""
    return ref.recurrence(x, dt, A, B, C, D, SCAN)


@pytest.mark.parametrize("impl", IMPLS)
@pytest.mark.parametrize("dt_shift", [-8.0, 0.0, 3.0],
                         ids=["dt_near_0", "dt_mid", "dt_large"])
@pytest.mark.parametrize("T", [16, 40, 48], ids=["1_chunk", "2.5_chunks",
                                                 "3_chunks"])
def test_the_chunked_scan_is_the_token_recurrence(T, dt_shift, impl):
    """Values and the gradient of every input, at one whole chunk, two and a
    half (padded inside the op) and three, with dt near 0 (the state barely
    moves: exp(dt A) ~ 1), in the middle, and large (it forgets within a few
    tokens); the XLA form and the kernels in the interpreter (blocks of 8
    positions in the chunk of 16, so that the blocks below the diagonal
    run)."""
    args = _scan_inputs(T, dt_shift)
    w = jax.random.normal(jax.random.key(9), (2, T, 32))
    scan = functools.partial(_chunked, impl=impl)
    both = lambda f: jax.jit(jax.value_and_grad(
        lambda *a: jnp.sum(f(*a) * w), argnums=range(6)))
    with jax.default_matmul_precision("highest"):
        got, want = jax.jit(scan)(*args), jax.jit(_token_by_token)(*args)
        np.testing.assert_allclose(got, want, rtol=2e-4,
                                   atol=1e-5 * float(jnp.max(jnp.abs(want))))
        (_, g), (_, r) = both(scan)(*args), both(_token_by_token)(*args)
    for name, a, b in zip("x dt A B C D".split(), g, r):
        scale = float(jnp.max(jnp.abs(b)))
        off = 5e-4 if dt_shift > 0 else 1e-4
        np.testing.assert_allclose(a, b, rtol=2e-4, atol=off * scale,
                                   err_msg=name)


@pytest.mark.parametrize("impl", IMPLS)
def test_the_scans_counters_and_its_scope(impl):
    """``ssd_decay`` is the mean of exp(sum of dt A over a chunk), which the
    state keeps of itself across one chunk; ``ssd_state_max`` the largest
    |S| at a chunk's end, here read off the recurrence; no gradient reaches
    them; every op lies under the scope ``ssd``, forward and through
    ``jax.grad``."""
    x, dt, A, B, C, D = _scan_inputs(48, -2.0)
    L = SCAN["mamba_chunk_size"]
    _, stats = ssd_op.ssd(x, dt, A, B, C, D, chunk=L, groups=2,
                          dtype=jnp.float32, impl=impl)
    decay = jnp.exp((dt * A).reshape(2, 3, L, 4).sum(axis=2))
    np.testing.assert_allclose(stats["ssd_decay"], decay.mean(), rtol=1e-5)
    # the state at each chunk's end, by the recurrence
    H, P, N, G = 4, 8, 16, 2
    x, dt, A, B = (np.asarray(v, np.float64) for v in (x, dt, A, B))
    Bh = np.repeat(B.reshape(2, 48, G, N), H // G, axis=2)
    S, ends = np.zeros((2, H, P, N)), []
    for t in range(48):
        S = (np.exp(dt[:, t] * A)[..., None, None] * S
             + (dt[:, t, :, None] * x[:, t].reshape(2, H, P))[..., None]
             * Bh[:, t, :, None])
        if t % L == L - 1:
            ends.append(np.abs(S).max())
    np.testing.assert_allclose(stats["ssd_state_max"], max(ends), rtol=1e-4)
    counted = jax.grad(lambda x: sum(ssd_op.ssd(
        x, *_scan_inputs(48, -2.0)[1:], chunk=L, groups=2,
        impl=impl)[1].values()))
    assert not np.any(counted(_scan_inputs(48, -2.0)[0]))
    # forward and backward under the scope (obs.opscopes reads the paths)
    from nanosandbox_tpu.obs import opscopes

    scan = lambda *a: ssd_op.ssd(*a, chunk=L, groups=2, impl=impl)[0]
    grad = jax.jit(jax.grad(lambda *a: jnp.sum(scan(*a)), argnums=range(6)))
    text = grad.lower(*_scan_inputs(48, -2.0)).compile().as_text()
    paths = opscopes._OP_NAME.findall(text)
    assert any(p.startswith("jit(<lambda>)/transpose(")
               and opscopes.part_of(p) == "ssd" for p in paths)
    assert "ssd" in opscopes.op_parts(text).values()
    # every op the scan writes, forward and backward, names the scope: the
    # lowered program's op names (not its file and function locations; the
    # interpreter's loop over a kernel's grid names its body afresh: on the
    # chip that body is one custom call, tests/test_chip_compile.py)
    args = _scan_inputs(48, -2.0)
    pullback = jax.jit(lambda a, ct: jax.vjp(scan, *a)[1](ct))
    for lowered in (jax.jit(scan).lower(*args),
                    pullback.lower(args, jnp.ones((2, 48, 32)))):
        names = [n for n in re.findall(r'loc\("([^"]*)"',
                                       lowered.as_text(debug_info=True))
                 if "/" in n and not n.startswith(("/", "while/"))]
        assert names and all(opscopes.part_of(n) == "ssd" for n in names), {
            n for n in names if opscopes.part_of(n) != "ssd"}


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("groups", [1, 2])
def test_the_kernels_are_the_xla_form(groups, dtype):
    """The kernels (in the interpreter) against the XLA form at 2.5 chunks:
    y, both counters and the gradient of every input. In float32 they are
    one map summed in another order. With bfloat16 products both are held
    to the float32 form: the kernels' largest error within 2.5 times the
    XLA form's own (on the CPU the XLA form keeps its cotangents in float32
    where the kernels round them for the products, as XLA does on the
    chip)."""
    args = _scan_inputs(40, 0.0)
    w = jax.random.normal(jax.random.key(9), (2, 40, 32))

    def run(impl, dt):
        def loss(*a):
            y, stats = ssd_op.ssd(*a, chunk=SCAN["mamba_chunk_size"],
                                  groups=groups, dtype=jnp.dtype(dt),
                                  impl=impl)
            return jnp.sum(y * w), (y, stats)
        (_, (y, stats)), grads = jax.jit(jax.value_and_grad(
            loss, argnums=range(6), has_aux=True))(*args)
        return [y, *grads], stats

    with jax.default_matmul_precision("highest"):
        want, want_stats = run("xla", "float32")
        got, stats = run("pallas_interpret", dtype)
        xla, xla_stats = run("xla", dtype)
    for k in want_stats:
        np.testing.assert_allclose(stats[k], xla_stats[k], rtol=1e-5,
                                   err_msg=k)
    for name, a, b, c in zip("y x dt A B C D".split(), got, want, xla):
        scale = float(jnp.max(jnp.abs(b)))
        if dtype == "float32":
            np.testing.assert_allclose(a, b, rtol=2e-4, atol=1e-5 * scale,
                                       err_msg=name)
        else:
            own = float(jnp.max(jnp.abs(c - b)))
            assert float(jnp.max(jnp.abs(a - b))) <= max(
                2.5 * own, 1e-6 * scale), name


@pytest.mark.parametrize("case, want", [
    (dict(), "pallas"),
    (dict(attention_impl="xla"), "xla"),
    (dict(attention_impl="auto"), "xla"),          # the CPU backend
    (dict(T=128), "xla"),                          # shorter than a chunk
    (dict(chunk=192), "xla"),
    (dict(d_state=64), "xla"),
    (dict(head_dim=48, heads=4), "xla"),           # no whole 128-lane tile
    (dict(attention_impl="pallas_interpret"), "pallas_interpret"),
])
def test_resolve_ssd_impl_gives_the_kernels_where_the_blocks_tile(case,
                                                                  want):
    shape = dict(attention_impl="pallas", T=8192, chunk=256, head_dim=64,
                 d_state=128, heads=64, groups=1)
    shape.update(case)
    impl = shape.pop("attention_impl")
    assert ssd_op.resolve_ssd_impl(impl, shape.pop("T"), shape.pop("chunk"),
                                   shape.pop("head_dim"), shape.pop("d_state"),
                                   **shape) == want
    # the cell's: a block of 8 heads of 64 lanes, taken two at a time
    assert ssd_op.head_block(64, 1, 64) == 8
    assert ssd_op.lane_group(8, 64, 256) == 2


# -- the conv between in_proj and the scan ------------------------------------

@pytest.mark.parametrize("dtype, inner", [("float32", 256), ("bfloat16", 128)])
def test_the_conv_kernels_are_the_xla_form(dtype, inner):
    """The conv's kernels (ops/mamba_conv.py, in the interpreter) against its
    XLA form: x, B and C, and the gradients of in_proj's output, the taps
    and the bias under fixed cotangents. Two rows of 768 tokens in three
    row blocks of 256, so the taps reach across block edges and are masked
    at position 0; GN 64, so a channel block of B and C is one 128-lane
    tile, and x one or two more. xBC lies between z (``inner`` lanes) and
    dt (4 lanes), where in_proj leaves it. Both sides are float32 inside:
    the outputs within a few float32 units of rounding, the taps' and
    bias's gradients (sums over 1,536 rows in another order) within 1e-5 of
    their largest, dxBC within one unit of rounding of the input's dtype."""
    T, GN, L = 768, 64, 4
    width = inner + 2 * GN
    k = jax.random.split(jax.random.key(7), 4)
    proj = jax.random.normal(k[0], (2, T, inner + width + 4)).astype(dtype)
    w = jax.random.uniform(k[1], (width, L), minval=-0.5, maxval=0.5)
    bias = 0.1 * jax.random.normal(k[2], (width,))
    cts = [jax.random.normal(c, (2, T, n)) for c, n in zip(
        jax.random.split(k[3], 3), (inner, GN, GN))]
    assert conv_op.resolve_mamba_conv_impl(
        "pallas_interpret", T, inner, inner, width, L) == "pallas_interpret"

    def run(impl):
        def loss(*args):
            out = conv_op.mamba_conv(*args, start=inner, inner=inner,
                                     impl=impl)
            return sum(jnp.sum(o * c) for o, c in zip(out, cts)), out
        (_, out), grads = jax.jit(jax.value_and_grad(
            loss, argnums=(0, 1, 2), has_aux=True))(proj, w, bias)
        return [*out, *grads]

    got, want = run("pallas_interpret"), run("xla")
    for name, a, b in zip("x B C dproj dw dbias".split(), got, want):
        assert a.shape == b.shape and a.dtype == b.dtype, name
        a, b = np.asarray(a, np.float32), np.asarray(b, np.float32)
        scale = float(np.abs(b).max())
        if name == "dproj":
            atol = float(jnp.finfo(dtype).eps) * scale
        elif name in ("dw", "dbias"):
            atol = 1e-5 * scale
        else:
            atol = 4 * float(jnp.finfo(jnp.float32).eps) * scale
        np.testing.assert_allclose(a, b, rtol=0, atol=atol, err_msg=name)
    # z's and dt's columns get no gradient from the conv
    assert not got[3][..., :inner].any() and not got[3][..., -4:].any()


@pytest.mark.parametrize("case, want", [
    (dict(), "pallas"),
    (dict(attention_impl="xla"), "xla"),
    (dict(attention_impl="auto"), "xla"),          # the CPU backend
    (dict(T=128), "xla"),                          # under a row block
    (dict(T=8000), "xla"),                         # no whole row blocks
    (dict(inner=4000, width=4256), "xla"),         # x in no whole blocks
    (dict(width=4096 + 192), "xla"),               # B | C no whole tile
    (dict(start=4160), "xla"),                     # xBC off a block edge
    (dict(taps=8), "xla"),                         # gradients past a tile
    (dict(attention_impl="pallas_interpret"), "pallas_interpret"),
])
def test_resolve_mamba_conv_impl_gives_the_kernels_where_the_blocks_tile(
        case, want):
    shape = dict(attention_impl="pallas", T=8192, start=4096, inner=4096,
                 width=4352, taps=4)               # the granite cell's
    shape.update(case)
    assert conv_op.resolve_mamba_conv_impl(**shape) == want


def test_forward_flops_per_token_is_the_hand_count():
    # chunk 256, 64 heads of 64, d_state 128, one group
    got = ssd_op.forward_flops_per_token(256, 64, 64, 128, 1)
    assert got == 257 / 2 * (2 * 128 + 2 * 64 * 64) + 4 * 64 * 64 * 128


# -- the family against the reference ------------------------------------------

@pytest.fixture(scope="module")
def trainer(char_dataset, tmp_path_factory):
    from nanosandbox_tpu.train import Trainer

    return Trainer(train_cfg(
        out_dir=str(tmp_path_factory.mktemp("granite") / "out"),
        data_dir=char_dataset, dataset="shakespeare_char", batch_size=8,
        loss_chunk_size=32, tensorboard=False, seed=0, **OPT))


@pytest.fixture(scope="module")
def seeded():
    params = weights_granite.make_params(SIZES, weights_granite.seed_key(5))
    x = jax.random.randint(jax.random.key(1), (2, 65), 0, SIZES["vocab_size"])
    return params, x[:, :-1], x[:, 1:]


def _program(trainer, params, x, y):
    """The trainer's own loss: the model, the chunked head and loss."""
    with jax.default_matmul_precision("highest"):
        return jax.jit(jax.value_and_grad(
            lambda p: trainer._loss_fn(p, x, y, None), has_aux=True))(params)


def _reference(params, x, y, sizes):
    with jax.default_matmul_precision("highest"):
        return jax.jit(lambda p: ref.loss_and_grad(p, x, y, sizes))(params)


@pytest.fixture(scope="module")
def both(trainer, seeded):
    return _program(trainer, *seeded), _reference(*seeded, SIZES)


def test_weights_file_has_the_programs_layout(trainer, seeded):
    params, _, _ = seeded
    own = trainer.abstract_state["params"]
    assert (jax.tree.map(lambda a: (a.shape, a.dtype), own)
            == jax.tree.map(lambda a: (a.shape, a.dtype), params))
    assert granite.head(params) is params["wte"]["embedding"]        # tied
    assert sum(a.size for a in jax.tree.leaves(params)) == \
        flops_granite.n_params(SIZES)


def test_the_programs_own_initial_parameters_are_mamba2s():
    """The model's own init (what ``python -m nanosandbox_tpu.train`` starts
    from) draws the taps, A_log and dt_bias in Mamba-2's ranges, every head
    its own, as the benchmark's weights do."""
    cfg = GraniteConfig.from_train_config(train_cfg(mamba_n_heads=16), 96)
    params = granite.Granite(cfg).init(
        jax.random.key(3), jnp.zeros((1, 32), jnp.int32))["params"]
    for i in (0, 2):
        m = jax.device_get(params[f"h_{i}"]["mamba"])
        taps, a, dt = (m["conv_weight"], np.exp(m["A_log"]),
                       np.log1p(np.exp(m["dt_bias"])))   # softplus
        assert np.abs(taps).max() <= 0.5 < 4 * np.abs(taps).std()  # 1/sqrt 4
        assert 1.0 <= a.min() and a.max() <= 16.0 and a.std() > 1.0
        assert 1e-3 * (1 - 1e-5) <= dt.min() and dt.max() <= 1e-1 * (1 + 1e-5)
        assert np.log(dt).std() > 0.5                   # spread over decades
        assert not m["conv_bias"].any() and (m["D"] == 1).all()
    assert params["h_1"]["attn_full"]["q_proj"]["kernel"].std() == \
        pytest.approx(0.02, rel=0.1)


def test_loss_and_every_gradient_leaf_equal_the_reference(both):
    ((loss, aux), grads), (ref_loss, ref_grads) = both
    np.testing.assert_allclose(loss, ref_loss, rtol=1e-5)
    got, want = flat(grads), flat(ref_grads)
    assert set(got) == set(want)
    for k in want:
        np.testing.assert_allclose(got[k], want[k], atol=1e-6, rtol=2e-4,
                                   err_msg=k)
    # the scan's own leaves are live
    for leaf in ("A_log", "dt_bias", "D", "conv_weight"):
        assert float(jnp.abs(got[f"h_0/mamba/{leaf}"]).max()) > 0, leaf
    assert aux["ssd_decay"].shape == aux["ssd_state_max"].shape == (2,)
    assert all(0 < float(v) < 1 for v in aux["ssd_decay"])


def test_one_adamw_update_equals_the_reference(trainer, seeded, both):
    params, _, _ = seeded
    ref_grads = both[1][1]
    mine = jax.jit(lambda p, g: optax.apply_updates(p, trainer.tx.update(
        g, trainer.tx.init(p), p)[0]))(params, ref_grads)
    zeros = jax.tree.map(jnp.zeros_like, params)
    theirs, _, _, _ = ref.adamw_step(params, zeros, zeros, ref_grads, 0, OPT)
    got, want = flat(mine), flat(theirs)
    for k in want:
        np.testing.assert_allclose(got[k], want[k], atol=1e-7, rtol=1e-5,
                                   err_msg=k)


@pytest.mark.parametrize("key, planted", [
    ("residual_multiplier", 1.0), ("embedding_multiplier", 1.0),
    ("attention_multiplier", 0.25), ("logits_scaling", 1.0)])
def test_a_multiplier_planted_wrong_fails_the_comparison(both, seeded, key,
                                                         planted):
    """The reference with one muP multiplier at another value (1/sqrt(16) for
    attention's 1/64) is told apart from the program: the loss or a leaf
    leaves the tolerances the sound comparison keeps."""
    ((loss, _), grads), _ = both
    params, x, y = seeded
    ref_loss, ref_grads = _reference(params, x, y, {**SIZES, key: planted})
    got, want = flat(grads), flat(ref_grads)
    worst = max(float(jnp.max(jnp.abs(got[k] - want[k])
                              / (1e-6 + 2e-4 * jnp.abs(want[k]))))
                for k in want)
    assert abs(float(loss - ref_loss)) > 1e-5 * abs(float(ref_loss)) or \
        worst > 1.0, key


def test_flops_per_token_is_the_benchmarks_count():
    cell = {**SIZES, "n_layer": 10, "n_head": 32, "n_kv_head": 8,
            "head_dim": 64, "n_embd": 2048, "intermediate_size": 8192,
            "vocab_size": 25088, "block_size": 8192,
            "layer_types": ["mamba"] * 5 + ["attention"] + ["mamba"] * 4,
            "mamba_n_heads": 64, "mamba_d_head": 64, "mamba_d_state": 128,
            "mamba_chunk_size": 256}
    cfg = GraniteConfig.from_train_config(train_cfg(
        layer_types=",".join(cell["layer_types"]),
        **{k: cell[k] for k in KEYS if k != "vocab_size"}), 25088)
    per_token = granite.flops_per_token(cfg, 8192, 0)
    assert per_token == pytest.approx(
        flops_granite.train_flops_per_token(cell))
    # the benchmark counts the scan's products itself: the program's count
    # agrees at the cell's shape and at the tests' (two groups, chunk 16)
    for sizes in (cell, {**SIZES, **SCAN}):
        assert flops_granite.scan_flops_per_token(sizes) == \
            ssd_op.forward_flops_per_token(
                sizes["mamba_chunk_size"], sizes["mamba_n_heads"],
                sizes["mamba_d_head"], sizes["mamba_d_state"],
                sizes["mamba_n_groups"])
    # the cut's parameter count, and the scan's share
    assert flops_granite.n_params(cell) == 797_850_560
    scan = 3 * 9 * ssd_op.forward_flops_per_token(256, 64, 64, 128, 1)
    assert 0.015 < scan / per_token < 0.02


def test_the_cli_trains_two_steps_and_leaves_the_scans_instants(
        char_dataset, tmp_path):
    """``python -m nanosandbox_tpu.train configs/train_granite_...py``, its
    widths cut to a toy by flags: the family's normal path."""
    import os

    from nanosandbox_tpu import train
    from nanosandbox_tpu.obs import opscopes, process_tracer
    from nanosandbox_tpu.train import restore_for_inference

    flags = dict(out_dir=tmp_path / "out", data_dir=char_dataset,
                 dataset="shakespeare_char", vocab_size=0, batch_size=8,
                 max_iters=2, lr_decay_iters=2, eval_interval=0,
                 eval_iters=1, log_interval=1, warmup_iters=1, seed=0,
                 tensorboard=False, loss_chunk_size=32, device="cpu",
                 compute_dtype="float32", n_layer=3,
                 layer_types=",".join(SIZES["layer_types"]),
                 **{k: SIZES[k] for k in KEYS if k not in (
                     "vocab_size", "n_layer")})
    config = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "configs",
        "train_granite_4_0_h_micro_pp4.py")
    process_tracer().clear()
    out = train.main([config, *(f"--{k}={v}" for k, v in flags.items())])
    assert out["iter_num"] == 2 and out["model_family"] == "granite"
    assert np.isfinite(out["final_loss"])
    spans = process_tracer().spans()
    init = [s for s in spans if s.name == "trainer_init"][-1]
    resolved = ssd_op.resolve_ssd_impl(
        "auto", SIZES["block_size"], SIZES["mamba_chunk_size"],
        SIZES["mamba_d_head"], SIZES["mamba_d_state"],
        heads=SIZES["mamba_n_heads"], groups=SIZES["mamba_n_groups"])
    assert resolved == "xla"                         # the CPU backend
    assert conv_op.resolve_mamba_conv_impl(
        "auto", SIZES["block_size"], 64, 64, 96, 4) == "xla"
    assert {"ssd_impl": resolved, "ssd_chunk": 16, "conv_impl": "xla",
            "remat_policy": "save_attention",
            "attn_route": "xla", "layer_types": "mamba,attention,mamba"
            }.items() <= init.args.items()
    noted = [s for s in spans if s.name == "granite_ssd"]
    assert [s.args["iter"] for s in noted] == [0, 1]
    assert len(noted[-1].args["ssd_decay"]) == 2
    parts = set(opscopes.step_parts().values())
    assert {"mamba", "ssd", "attn_full", "mlp", "ln", "embed"} <= parts
    with pytest.raises(NotImplementedError, match="recurrent state"):
        restore_for_inference(str(flags["out_dir"]))


@pytest.mark.parametrize("keys, said", [
    (dict(mesh_sp=2, attention_impl="ring"), "state handed from one"),
    (dict(mesh_tp=2), "in_proj / out_proj"),
])
def test_what_is_not_built_is_refused_by_name(keys, said):
    with pytest.raises(NotImplementedError, match=said):
        granite.check(train_cfg(**keys), pretrained=False)


def test_init_from_weights_are_refused_and_the_config_says_what_is_missing():
    with pytest.raises(ValueError, match="starts from scratch"):
        granite.check(train_cfg(), pretrained=True)
    make = lambda **kw: GraniteConfig.from_train_config(train_cfg(**kw), 96)
    with pytest.raises(ValueError, match="layer_types needs 3 entries"):
        make(layer_types="mamba,conv,mamba")
    with pytest.raises(ValueError, match="mamba_d_state"):
        make(mamba_d_state=0)
    with pytest.raises(ValueError, match="must divide mamba_n_heads"):
        make(mamba_n_groups=3)
    with pytest.raises(ValueError, match="attention_multiplier"):
        make(attention_multiplier=0.0)
    assert dataclasses.replace(make(), remat=True).remat
