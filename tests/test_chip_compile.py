"""The main path's kernels, compiled for a DESCRIBED v5e at GPT-2 124M widths.

Interpret mode (every other kernel test in this suite) checks the math
and none of the chip compiler's rules: block shapes, vector layouts,
what the vector unit can do to an int8. The TPU compiler is installed
here and compiles for a chip that is described, not attached — so these
tests hold every later PR to what the chip accepts, at no chip time:

  * the training kernel ``flash_attention``, forward and backward, with
    and without in-kernel dropout, at (16, 12, 1024, 64) bf16, its
    kernels' text pinned, and its 'bhtd-rep' route at the lfm2 and
    granite cells' shapes pinned whole;
  * ``flash_attention_qkv``, the same kernels on the model's own
    (B, T, 3C) layout, forward and backward, with and without dropout,
    at both benchmark cells' shapes: (16, 1024, 2304) (12 heads) and
    (8, 1024, 3072) (16 heads);
  * ``flash_attention_gqa``, the grouped-query windowed kernels of the
    ``afmoe`` family, forward and backward, at the Trinity-Mini cell's
    shapes (2 x 8192 tokens, 32 query heads on 4 KV heads of 128, window
    2048 and full: the forward and the one-pass backward), at the longest
    sequence that backward takes (T = 36,864) and the first it leaves to
    the split pair (40,960), and the routed experts' grouped matmul (megablox,
    forward, dgrad and wgrad) at the cell's buffer (32,768 rows, 16
    experts, 2048 x 1024), with the row mover that brings that buffer's
    rows back to their 16,384 tokens (ops.moe: combine, and dispatch's
    backward);
  * ``qk_prep``, the one-pass head RMSNorm + rotary positions in front of
    those kernels, forward and backward, at the cell's q (2, 8192, 4096) /
    32 heads and k (2, 8192, 512) / 4 heads, with and without positions,
    its kernels' text pinned; and its rotary-only form ``qk_rotary`` at the
    Ouro cell's q and k, (2, 8192, 2048) / 16 heads;
  * the ``lfm2`` family's shapes through the same entries: grouped-query
    attention at head size 64 (2 x 8192 tokens, 32 query heads on 8 KV
    heads of 64: ``causal_attention_gqa``'s 'bhtd-rep' route, the
    (B, H, T, D) kernels on repeated KV heads), the grouped matmul at its
    buffer (32,768 rows, 8 experts, 2048 x 1792 and back) with the tiling
    ``ops.moe.gmm_tiling`` chooses there, the row mover at 16,384 tokens
    of 4 slots;
  * the ``deepseek_v3`` family's latent attention at the Moonlight cell's
    shape (2 x 8192 tokens, 16 heads of 128 + 64 lanes with one shared
    rotary key, values of 128): ``flash_attention_mla``'s forward and ONE
    backward kernel, at the cell's T and at the longest its VMEM predicate
    lets through, and the grouped matmul at the expert width 1408;
  * the ``granite`` family's Mamba-2 scan (``ops/ssd.py``) at the cell's
    (1, 8192) tokens, 64 heads of 64, d_state 128, forward and backward:
    the XLA form, its temporaries under 1 GB, and the kernels (``%ssd.N``,
    one call a pass, no (..., 256, 256) array beside them), also inside the
    family's loss and gradient at the cell's widths, where the conv's
    kernels (``ops/mamba_conv.py``, ``%mamba_conv.N``) hand it x, B and C;
  * all nine serving variants — ``flash_decode`` / ``flash_decode_paged``
    / ``flash_prefill_paged`` x fp / int8 / int4 — at B=8, H=12, D=64,
    the engine's default page 16 and page 32, prefill T = a page and
    T = 128.

A compile that passes is not a chip run: nothing executes here.

ONE file, by design. Only one process may load the TPU library; the
topology is described inside a module-scoped fixture (never at import,
in a skipif, in parametrize arguments or in conftest.py), so under
pytest-xdist every worker collects the same tests and only the worker
that is handed this file loads the library. Compiles run in the test's
own process, with the persistent compilation cache off (an entry written
for a described chip cannot be read back without one).
"""

import os
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from nanosandbox_tpu.ops import flash_decode as fd
from nanosandbox_tpu.ops.attention import (flash_attention,
                                           flash_attention_gqa,
                                           flash_attention_qkv, qk_prep,
                                           qk_rotary, resolve_gqa_bwd)

B, H, D, L = 8, 12, 64, 1024          # serving widths (GPT-2 124M heads)
TRAIN_SHAPE = (16, 12, 1024, 64)       # the 124M train step's q/k/v
# c_attn's output in the two benchmark cells: (qkv shape, heads)
QKV_SHAPES = {"124m": ((16, 1024, 2304), 12), "medium": ((8, 1024, 3072), 16)}


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    try:
        desc = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler here, or its lock is held
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield desc
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def sds(topo):
    """ShapeDtypeStruct factory placing every operand on one described chip."""
    one_chip = SingleDeviceSharding(topo.devices[0])

    def make(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    return make


def compiled_text(fn, *args) -> str:
    return jax.jit(fn).lower(*args).compile().as_text()


# ------------------------------------------------------------- training

def test_flash_attention_forward(sds):
    x = sds(TRAIN_SHAPE, jnp.bfloat16)
    txt = compiled_text(
        lambda q, k, v: flash_attention(q, k, v), x, x, x)
    assert "tpu_custom_call" in txt


@pytest.mark.parametrize("dropout", [False, True], ids=["plain", "dropout"])
def test_flash_attention_backward(sds, dropout):
    x = sds(TRAIN_SHAPE, jnp.bfloat16)

    def loss(q, k, v, seed):
        return flash_attention(
            q, k, v, seed if dropout else None,
            dropout_rate=0.1 if dropout else 0.0).astype(jnp.float32).sum()

    txt = compiled_text(jax.grad(loss, argnums=(0, 1, 2)), x, x, x,
                        sds((1,), jnp.uint32))
    assert "tpu_custom_call" in txt


# an HLO copy or transpose of a floating-point array (the dropout seed's
# u32[1] move into scalar memory is not one)
MOVES_AN_ACTIVATION = re.compile(r"= (?:bf16|f32)\[[^ ]* (?:copy|transpose)\(")


# Trinity-Mini's cell: (B, T, H, G, D)
GQA_SHAPE = (2, 8192, 32, 4, 128)


def _gqa_custom_calls(sds, shape, window, scope):
    """The custom calls of flash_attention_gqa's forward + backward at
    ``shape``, by name; no copy or transpose of an activation beside
    them."""
    B, T, H, G, D = shape

    def loss(q, k, v):
        return flash_attention_gqa(q, k, v, H, G, window, False,
                                   scope).astype(jnp.float32).sum()

    q = sds((B, T, H * D), jnp.bfloat16)
    kv = sds((B, T, G * D), jnp.bfloat16)
    txt = compiled_text(jax.grad(loss, argnums=(0, 1, 2)), q, kv, kv)
    assert not MOVES_AN_ACTIVATION.search(txt)
    return set(re.findall(rf"%({scope}[.0-9]*) = [^\n]*custom-call\(", txt))


@pytest.mark.parametrize("window", [2048, None], ids=["window", "full"])
def test_flash_attention_gqa_forward_and_backward(sds, window):
    """The forward and ONE backward kernel (dQ, dK and dV from one walk of
    the score tiles), each named after its scope."""
    scope = "attn_sliding" if window else "attn_full"
    assert resolve_gqa_bwd("pallas", GQA_SHAPE[4], GQA_SHAPE[1]) == "fused"
    assert len(_gqa_custom_calls(sds, GQA_SHAPE, window, scope)) == 2


@pytest.mark.parametrize("T,bwd", [(36864, "fused"), (40960, "split")])
@pytest.mark.parametrize("window", [2048, None], ids=["window", "full"])
def test_flash_attention_gqa_backward_at_the_predicates_edge(sds, window, T,
                                                             bwd):
    """The longest sequence ops.attention.gqa_bwd_fused_fits lets through
    (a KV head's whole-T k, v, dk, dv and float32 accumulators: 90 MiB of
    VMEM) compiles as the one-pass kernel, and the first it turns away as
    the split pair (forward, dQ, dK/dV)."""
    _, _, H, G, D = GQA_SHAPE
    assert resolve_gqa_bwd("pallas", D, T) == bwd
    calls = _gqa_custom_calls(sds, (1, T, H, G, D), window, "attn_long")
    assert len(calls) == {"fused": 2, "split": 3}[bwd]


def test_gqa_at_head_size_64_forward_and_backward(sds):
    """32 query heads on 8 KV heads of 64 lanes, the lfm2 cell's one
    attention layer: the (B, H, T, D) flash kernels on repeated KV heads
    (ops.attention.gqa_route: 'bhtd-rep'), named after the layer's scope,
    and no (B, H, T, T) array anywhere."""
    from nanosandbox_tpu.ops.attention import causal_attention_gqa, gqa_route

    B, T, H, G, D = 2, 8192, 32, 8, 64
    assert gqa_route("pallas", D, T) == "bhtd-rep"

    def loss(q, k, v):
        with jax.named_scope("Model"):   # takes the jvp( ) wrappers, as the
            # model's own outermost scope does in a step
            return causal_attention_gqa(q, k, v, H, G, impl="pallas",
                                        scope="attn_full"
                                        ).astype(jnp.float32).sum()

    q = sds((B, T, H * D), jnp.bfloat16)
    kv = sds((B, T, G * D), jnp.bfloat16)
    txt = compiled_text(jax.grad(loss, argnums=(0, 1, 2)), q, kv, kv)
    calls = re.findall(r"%(attn_full[.0-9]*) = [^\n]*custom-call\(", txt)
    assert len(set(calls)) >= 2, calls        # forward, fused backward
    assert not re.search(rf"\[{B},{H},{T},{T}\]|\[{B * H},{T},{T}\]", txt)


# Moonlight's cell: (B, T, H, content lanes, rotary lanes)
MLA_SHAPE = (2, 8192, 16, 128, 64)


@pytest.mark.parametrize("B, T", [MLA_SHAPE[:2], (1, 23040)],
                         ids=["cell", "predicates-edge"])
def test_flash_attention_mla_forward_and_backward(sds, B, T):
    """The latent kernels: one call forward, one backward (dQ_nope, dQ_pe,
    dK_nope, dK_pe's partials and dV from one walk of the score tiles), named
    after their scope; no (B, H, T, T) array, no key concatenated to
    (B, T, H * 192), at the cell's shape and at the longest sequence
    ops.attention.mla_layout_supported lets through (whole-T blocks of one
    head and their accumulators inside VMEM); one row more is turned away."""
    from nanosandbox_tpu.ops.attention import (causal_attention_mla,
                                               mla_route)

    _, _, H, D, R = MLA_SHAPE
    assert mla_route("pallas", D, R, D, T) == "mla"
    assert mla_route("pallas", D, R, D, 23040 + 128) == "xla"

    def loss(qn, qp, kn, kp, v):
        with jax.named_scope("Model"):   # takes the jvp( ) wrappers
            return causal_attention_mla(qn, qp, kn, kp, v, H, impl="pallas",
                                        scope="attn_mla"
                                        ).astype(jnp.float32).sum()

    wide = sds((B, T, H * D), jnp.bfloat16)
    txt = compiled_text(
        jax.grad(loss, argnums=(0, 1, 2, 3, 4)), wide,
        sds((B, T, H, R), jnp.bfloat16), wide, sds((B, T, R), jnp.bfloat16),
        wide)
    calls = set(re.findall(r"%(attn_mla[.0-9]*) = [^\n]*custom-call\(", txt))
    assert len(calls) == 2, calls
    assert txt.count("custom-call(") == 2
    assert not re.search(
        rf"\[{B},{H},{T},{T}\]|\[{B * H},{T},{T}\]|\[{B},{T},{H * (D + R)}\]"
        rf"|\[{B},{T},{H},{D + R}\]|\[{B},{H},{T},{D + R}\]", txt)


def test_gated_short_conv_forward_and_backward(sds):
    """The lfm2 cell's conv mixer between its projections, (2, 8192, 3 x
    2048) bfloat16 with a (2048, 3) filter: ONE custom call a pass
    (%conv_mix.N), and no float32 array of activation size beside it."""
    from nanosandbox_tpu.ops.short_conv import (gated_short_conv,
                                                resolve_conv_impl)

    B, T, d, L = 2, 8192, 2048, 3
    assert resolve_conv_impl("pallas", T, d) == "pallas"

    def loss(bcx, w):
        return gated_short_conv(bcx, w, "pallas").astype(jnp.float32).sum()

    args = (sds((B, T, 3 * d), jnp.bfloat16), sds((d, L), jnp.float32))
    for fn in (jax.grad(loss, argnums=(0, 1)),
               lambda bcx, w: gated_short_conv(bcx, w, "pallas")):
        txt = compiled_text(fn, *args)
        assert len(re.findall(r"%conv_mix[.0-9]* = [^\n]*custom-call\(",
                              txt)) == 1
        assert not re.search(rf"f32\[{B},{T},", txt)


# the granite cell's scan: (1, T) tokens, H heads of P, d_state N, chunk L
SSD_SHAPE = (8192, 64, 64, 128, 256)
# an instruction's results, `%name = (f32[...]..., f32[...]...) custom-call(`
SSD_CALL = re.compile(r"%(ssd[.0-9]*) = \(?([^\n]*?)\)? custom-call\(")


@pytest.mark.parametrize("impl", ["xla", "pallas"])
@pytest.mark.parametrize("pass_", ["forward", "backward"])
def test_ssd_scan_forward_and_backward(sds, pass_, impl):
    """The granite cell's Mamba-2 scan (ops/ssd.py) at (1, 8192) tokens, 64
    heads of 64, d_state 128, one group, chunk 256, forward and forward +
    backward. The XLA form (all chunks at once) compiles for the chip with
    its temporaries under 1 GB (0.47 GB forward, 0.42 GB forward + backward
    when written), where the decay blocks of all 32 chunks, (32, 64, 256,
    256), would be 537 MB each in float32 if the compiler wrote them out.
    The kernels: one custom call forward, one more backward, each filed
    under the part ``ssd``, no (..., 256, 256) array outside them, and
    temporaries under the XLA form's."""
    from nanosandbox_tpu.obs import opscopes
    from nanosandbox_tpu.ops.ssd import resolve_ssd_impl, ssd

    T, H, P, N, L = SSD_SHAPE
    if impl == "pallas":
        assert resolve_ssd_impl("pallas", T, L, P, N, heads=H,
                                groups=1) == "pallas"
    args = (sds((1, T, H * P), jnp.float32), sds((1, T, H), jnp.float32),
            sds((H,), jnp.float32), sds((1, T, N), jnp.float32),
            sds((1, T, N), jnp.float32), sds((H,), jnp.float32))
    forward = lambda *a: ssd(*a, chunk=L, impl=impl)[0]
    fn = (forward if pass_ == "forward" else jax.grad(
        lambda *a: jnp.sum(forward(*a) * a[0]), argnums=range(6)))
    compiled = jax.jit(fn).lower(*args).compile()
    temp = compiled.memory_analysis().temp_size_in_bytes
    assert temp < 1e9
    if impl == "xla":
        return
    assert temp < 0.42e9
    txt = compiled.as_text()
    calls = [name for name, _ in SSD_CALL.findall(txt)]
    assert len(calls) == {"forward": 1, "backward": 2}[pass_], calls
    parts = opscopes.op_parts(txt)
    assert all(parts[name] == "ssd" for name in calls)
    assert not re.search(rf"\[[0-9,]*{L},{L}\]", txt)


def _producer(txt: str, name: str) -> str:
    """The instruction that makes ``name``'s value, through the
    get-tuple-elements, bitcasts and memory-space copies on its way."""
    ops = dict(re.findall(
        r"^\s*(?:ROOT )?%(\S+) = [^\n]*?\b(?:get-tuple-element|bitcast|"
        r"copy-done|copy-start)\(%([^,)\s]+)", txt, re.M))
    while name in ops:
        name = ops[name]
    return name


def test_the_granite_step_runs_the_scan_kernels(sds):
    """The family's loss and gradient at the cell's widths and T (three
    layers: mamba, attention, mamba; ``save_attention`` remat; a toy
    vocabulary): per Mamba layer one forward kernel call of the scan,
    remat's replay of it and one backward call, every one filed under
    ``ssd``; and as many of the conv's kernels (ops/mamba_conv.py), filed
    under ``mamba``, whose outputs are the scan calls' x, B and C as they
    are, with no xBC-wide float32 array left in the step."""
    from nanosandbox_tpu.config import GraniteConfig, TrainConfig
    from nanosandbox_tpu.models import granite
    from nanosandbox_tpu.obs import opscopes

    T, H, P, N, L = SSD_SHAPE
    tc = TrainConfig(
        model_family="granite", n_layer=3, n_head=32, n_kv_head=8,
        head_dim=64, n_embd=2048, intermediate_size=8192, block_size=T,
        layer_types="mamba,attention,mamba", mamba_n_heads=H,
        mamba_d_head=P, mamba_d_state=N, mamba_n_groups=1, mamba_d_conv=4,
        mamba_chunk_size=L, embedding_multiplier=12.0,
        residual_multiplier=0.22, attention_multiplier=0.015625,
        logits_scaling=8.0, rms_norm_eps=1e-5, compute_dtype="bfloat16",
        attention_impl="pallas", remat=True, remat_policy="save_attention")
    model, recorded = granite.build(GraniteConfig.from_train_config(tc, 512),
                                    None)
    assert recorded["ssd_impl"] == recorded["conv_impl"] == "pallas"
    params = jax.eval_shape(lambda: model.init(
        jax.random.key(0), jnp.zeros((1, T), jnp.int32))["params"])
    params = jax.tree.map(lambda a: sds(a.shape, a.dtype), params)

    def loss(p, idx):
        h, _ = model.apply({"params": p}, idx, return_hidden=True)
        return jnp.mean(jnp.square(h))

    txt = compiled_text(jax.grad(loss), params, sds((1, T), jnp.int32))
    calls = SSD_CALL.findall(txt)
    # results: the forward's y, the largest |S| and the chunks' states; the
    # backward's dx, dB, dC and the per-position rows
    outputs = sorted(results.count("f32[") for _, results in calls)
    assert outputs == [3] * 4 + [4] * 2, calls
    parts = opscopes.op_parts(txt)
    assert all(parts[name] == "ssd" for name, _ in calls)
    # the conv: a forward, its replay and a backward a Mamba layer
    convs = dict(re.findall(r"%(mamba_conv[.0-9]*) = \(([^\n]*?)\) "
                            r"custom-call\(", txt))
    # results: the forward's x, B and C; the backward's dxBC (bfloat16)
    # and the per-program sums of the taps' and bias's gradients
    assert sorted(results.count("bf16[") for results in convs.values()
                  ) == [0] * 4 + [1] * 2, convs
    assert all(parts[name] == "mamba" for name in convs)
    assert f"f32[1,{T},{H * P + 2 * N}]" not in txt
    for name, _ in calls:
        operands = re.search(rf"%{re.escape(name)} = [^\n]*?custom-call\("
                             r"([^)]*)\)", txt).group(1)
        operands = re.findall(r"%([^,\s]+)", operands)
        # x, B and C: the scan's operands 0, 3 and 4, both ways
        assert {_producer(txt, operands[i]) for i in (0, 3, 4)} <= set(
            convs), (name, operands)


@pytest.mark.parametrize("theta", [10000.0, None], ids=["rotary", "none"])
@pytest.mark.parametrize("heads", [32, 4], ids=["q-32-heads", "k-4-heads"])
def test_qk_prep_forward_and_backward(sds, heads, theta):
    """One custom call a pass, named apart from the flash kernels'
    (%qk_prep.N), and no other pass over the activation: the float32
    (B, T, heads, D) arrays of the XLA path are gone."""
    B, T, _, _, D = GQA_SHAPE

    def loss(x, scale):
        return qk_prep(x, scale, heads, 1e-5, theta).astype(jnp.float32).sum()

    args = (sds((B, T, heads * D), jnp.bfloat16), sds((D,), jnp.float32))
    # the backward alone (this loss's gradient does not need the output),
    # then the forward
    for fn in (jax.grad(loss, argnums=(0, 1)),
               lambda x, scale: qk_prep(x, scale, heads, 1e-5, theta)):
        txt = compiled_text(fn, *args)
        assert len(re.findall(r"%qk_prep[.0-9]* = [^\n]*custom-call\(",
                              txt)) == 1
        assert txt.count("custom-call(") == 1
        assert not MOVES_AN_ACTIVATION.search(txt)
        assert not re.search(rf"f32\[{B},{T},", txt)


def _kernel_texts(lowered_text: str) -> list:
    """The Mosaic modules of a lowering's tpu_custom_calls, as text without
    source locations: what the chip's compiler receives of each kernel."""
    import base64
    import json

    from jax._src.interpreters import mlir
    from jaxlib.mlir import ir

    out = []
    for m in re.finditer(r'@tpu_custom_call\([^)]*\) \{backend_config = '
                         r'"((?:[^"\\]|\\.)*)"', lowered_text):
        config = json.loads(m.group(1).replace("\\22", '"'))
        ctx = mlir.make_ir_context()
        ctx.allow_unregistered_dialects = True
        module = ir.Module.parse(
            base64.b64decode(config["custom_call_config"]["body"]),
            context=ctx)
        out.append(module.operation.get_asm(enable_debug_info=False))
    return out


# sha256 of the normed prologue's kernel text (_kernel_texts), forward and
# backward, at the Trinity-Mini cell's q / k with and without positions, as
# the tree before the rotary-only form (0e57a95) lowered them.
QK_PREP_KERNEL_SHA256 = {
    (32, 10000.0): (
        "1ad580d9bed090b60604c44dac779ece7c0fc8bc11254dff6dad5d6f7a97500a",
        "8e7ab3b12a0da79250ee2b057bb15954739adf9b808248c170fced0f6ff64936"),
    (32, None): (
        "007d4dadf557655886f1207d5adadf37ff3786909bdaa3c60713a83b70c6989e",
        "65bc3e70b9ddcf7cd2feb858b785fd04ddabe3d38d3f5716c14c4ec5f3479314"),
    (4, 10000.0): (
        "b71a85e866e12dd82f3747a96cf22e0eacb54efd3a6175cf0f346b1613dfedc6",
        "59d99d79682a4b2ee1b832cb93cebb5771694b2d40dd6ae3c023539beb34fd78"),
    (4, None): (
        "45b6315252fccc083b3540bcdb6df1978a5b2dab66315bf46eb12f805ef4e885",
        "26cb60d4636e84d165ae42f73d457f03d3d7763b3c2260168d9a998d5129ac65"),
}


@pytest.mark.parametrize("theta", [10000.0, None], ids=["rotary", "none"])
@pytest.mark.parametrize("heads", [32, 4], ids=["q-32-heads", "k-4-heads"])
def test_qk_prep_normed_kernels_are_what_they_were(sds, heads, theta):
    """The normed prologue (Trinity-Mini's) lowers to the same kernel text,
    forward and backward, since the rotary-only form shares its forward
    kernel."""
    import hashlib

    B, T, _, _, D = GQA_SHAPE
    fwd = lambda x, scale: qk_prep(x, scale, heads, 1e-5, theta)
    bwd = jax.grad(lambda x, scale: fwd(x, scale).astype(jnp.float32).sum(),
                   argnums=(0, 1))
    args = (sds((B, T, heads * D), jnp.bfloat16), sds((D,), jnp.float32))
    got = []
    for fn in (fwd, bwd):
        (text,) = _kernel_texts(jax.jit(fn).lower(*args).as_text())
        got.append(hashlib.sha256(text.encode()).hexdigest())
    assert tuple(got) == QK_PREP_KERNEL_SHA256[(heads, theta)]


def _sha256(text: str) -> str:
    import hashlib

    return hashlib.sha256(text.encode()).hexdigest()


def _wrapper_text(lowered) -> str:
    """A lowering's HLO without what names a source line: every op's
    metadata, and the custom calls' backend_config (the kernels' bodies,
    which _kernel_texts reads without their locations)."""
    hlo = re.sub(r", metadata=\{[^}]*\}", "", lowered.as_text(dialect="hlo"))
    return re.sub(r", backend_config=.*$", "", hlo, flags=re.M)


def _kernel_hashes(lowered) -> tuple:
    return tuple(_sha256(t) for t in _kernel_texts(lowered.as_text()))


# The (B, H, T, D) entry's programs as b2443c7 lowered them, before its four
# custom_vjps became one, its backward lost the replicated statistic layout
# and the split pair. sha256 of causal_attention_gqa's 'bhtd-rep' route,
# jax.grad at 32 query heads on 8 KV heads of 64 and T = 8192, the lfm2
# cell's B = 2 and the granite cell's B = 1: the wrapper (_wrapper_text),
# then the forward's and the backward's kernel text.
BHTD_REP_SHA256 = {
    2: (
        "8732f2d2517e50ef40496e06702ad8e66923d92db5a94f956ad65ffd70e79dc8",
        "01217c456d0b8c28458799275d83f42c81ba89579a3c200eedf0b8534ddef3cb",
        "c439ebddfe05ba99854949f8db88e04a724a6597397dffcc19e566112972de47",
    ),
    1: (
        "2f14b41d20270d142588131912ccee222958505f2ad28ceec62784402f1d38cf",
        "26eb32a99a5e0cf688488f810fb2342c13bd2dc9ae69dedef17c635ee40f24e4",
        "8af00eeffbf8476d2232a47f2ad466f41bfd9e79683e8482182141d2d9ab7a24",
    ),
}


@pytest.mark.parametrize("B", [2, 1], ids=["lfm2", "granite"])
def test_bhtd_rep_route_is_what_it_was(sds, B):
    from nanosandbox_tpu.ops.attention import causal_attention_gqa

    T, H, G, D = 8192, 32, 8, 64

    def loss(q, k, v):
        with jax.named_scope("Model"):
            return causal_attention_gqa(q, k, v, H, G, impl="pallas",
                                        scope="attn_full"
                                        ).astype(jnp.float32).sum()

    kv = sds((B, T, G * D), jnp.bfloat16)
    lowered = jax.jit(jax.grad(loss, argnums=(0, 1, 2))).lower(
        sds((B, T, H * D), jnp.bfloat16), kv, kv)
    assert (_sha256(_wrapper_text(lowered)),
            *_kernel_hashes(lowered)) == BHTD_REP_SHA256[B]


# flash_attention's kernel text at TRAIN_SHAPE, as b2443c7 lowered it: the
# forward's kernel, and the forward's and the backward's under jax.grad,
# without and with dropout (rate 0.1).
FLASH_KERNEL_SHA256 = {
    ("forward", False): (
        "bb6ad4371c2f3f1c0b92f13b5dcdf3ab658a40179d74fa242f07e0229ac067dd",
    ),
    ("forward", True): (
        "a8389a4e25834c7864b00dbf6f4500a3829b23b89103bd402fcaca2103c9f685",
    ),
    ("backward", False): (
        "bb6ad4371c2f3f1c0b92f13b5dcdf3ab658a40179d74fa242f07e0229ac067dd",
        "3fcb90a2aa9b8f8898c5b027a97ff8a01a596efb07c0f027a2cc8ac351efed4f",
    ),
    ("backward", True): (
        "a8389a4e25834c7864b00dbf6f4500a3829b23b89103bd402fcaca2103c9f685",
        "c023e08fe49c655d4d2b911a7dedc5b1cef842099b035481332bceccb91967ac",
    ),
}


@pytest.mark.parametrize("dropout", [False, True], ids=["plain", "dropout"])
@pytest.mark.parametrize("pass_", ["forward", "backward"])
def test_flash_attention_kernels_are_what_they_were(sds, pass_, dropout):
    def attend(q, k, v, seed):
        return flash_attention(q, k, v, seed if dropout else None,
                               dropout_rate=0.1 if dropout else 0.0)

    fn = attend if pass_ == "forward" else jax.grad(
        lambda *a: attend(*a).astype(jnp.float32).sum(), argnums=(0, 1, 2))
    x = sds(TRAIN_SHAPE, jnp.bfloat16)
    assert _kernel_hashes(jax.jit(fn).lower(
        x, x, x, sds((1,), jnp.uint32))) == FLASH_KERNEL_SHA256[
            (pass_, dropout)]


# flash_attention_qkv's kernel text at the 124M cell's (16, 1024, 2304), as
# b2443c7 lowered it: its tiles are the (B, H, T, D) entry's.
QKV_KERNEL_SHA256 = {
    "forward": (
        "1308f2c50cf430c47e4968223e01f7328b958ed51e1112a7ada3e3fea690ea2b",
    ),
    "backward": (
        "1308f2c50cf430c47e4968223e01f7328b958ed51e1112a7ada3e3fea690ea2b",
        "d900c196aa3dba53308ab3ea7a870682f7a611087c0b5837f13ae5358d2b4268",
    ),
}


@pytest.mark.parametrize("pass_", ["forward", "backward"])
def test_flash_attention_qkv_kernels_are_what_they_were(sds, pass_):
    attend, args = _qkv_call("124m", False, sds)
    fn = attend if pass_ == "forward" else jax.grad(
        lambda qkv, seed: attend(qkv, seed).astype(jnp.float32).sum())
    assert _kernel_hashes(jax.jit(fn).lower(*args)) == QKV_KERNEL_SHA256[
        pass_]


def test_qk_rotary_forward_and_backward(sds):
    """The rotary-only form at the Ouro cell's q and k, (2, 8192, 2048) / 16
    heads: one custom call a pass (%qk_prep.N; the backward is the forward
    kernel by the opposite angles), no other pass over the activation and
    no float32 array of activation size."""
    B, T, heads, D = 2, 8192, 16, 128
    rotate = lambda x: qk_rotary(x, heads, 1e6)
    x = sds((B, T, heads * D), jnp.bfloat16)
    # the backward from a cotangent (it reads nothing of x), then the forward
    for fn, args in ((lambda x, dz: jax.vjp(rotate, x)[1](dz), (x, x)),
                     (rotate, (x,))):
        txt = compiled_text(fn, *args)
        assert len(re.findall(r"%qk_prep[.0-9]* = [^\n]*custom-call\(",
                              txt)) == 1
        assert txt.count("custom-call(") == 1
        assert not MOVES_AN_ACTIVATION.search(txt)
        assert not re.search(rf"f32\[{B},{T},", txt)


@pytest.mark.parametrize("experts, K, N", [
    (16, 2048, 1024), (8, 2048, 1792), (8, 1792, 2048), (8, 2048, 1408),
    (8, 1408, 2048)],
    ids=["trinity-mini", "lfm2-gate-up", "lfm2-down", "moonlight-gate-up",
         "moonlight-down"])
def test_megablox_grouped_matmul_backward(sds, experts, K, N):
    """At each cell's buffer and expert shapes, with the tiling
    ops.moe.gmm_tiling gives there: the one measured at each (1024 across
    the widths; at 1408, which 512 pads less, 512 across the output and the
    whole contraction in one step)."""
    from nanosandbox_tpu.ops.moe import gmm_tiling, grouped_matmul

    assert gmm_tiling(32768, K, N) == (
        (512, K, 512) if 1408 in (K, N) else (512, 1024, 1024))

    def loss(xs, w, sizes):
        return grouped_matmul(xs, w, sizes,
                              impl="megablox").astype(jnp.float32).sum()

    txt = compiled_text(
        jax.grad(loss, argnums=(0, 1)), sds((32768, K), jnp.bfloat16),
        sds((experts, K, N), jnp.bfloat16), sds((experts,), jnp.int32))
    # dgrad (the forward product against the transposed weights) and wgrad;
    # the forward's own output is not needed for this loss's gradient
    calls = re.findall(r"%([\w.]*gmm[\w.]*) = [^\n]*custom-call\(", txt)
    assert len(calls) == 2 and sum("tgmm" in c for c in calls) == 1, calls


@pytest.mark.parametrize("k", [8, 4], ids=["k8", "k4"])
@pytest.mark.parametrize("pass_", ["combine", "dispatch_backward",
                                   "combine_backward"])
def test_moe_row_mover(sds, pass_, k):
    """The routed experts' rows back to their tokens at the cells' shapes
    (16,384 tokens of 8 slots, or of 4, a 32,768-row buffer of 2048): ONE kernel a
    pass (%moe_rows.N), weighted into float32 (combine), plain into the
    compute type (dispatch's backward), or one number a row (dw in
    combine's backward, whose row gathers stay XLA's)."""
    from nanosandbox_tpu.ops import moe

    plan = {"dest": sds((16384, k), jnp.int32),
            "row_valid": sds((32768,), jnp.bool_),
            "row_token": sds((32768,), jnp.int32),
            "row_pair": sds((32768,), jnp.int32)}
    y, w = sds((32768, 2048), jnp.bfloat16), sds((16384, k), jnp.float32)
    if pass_ == "combine":
        txt = compiled_text(
            lambda y, w, plan: moe.combine(y, w, plan, "pallas"), y, w, plan)
    elif pass_ == "dispatch_backward":
        txt = compiled_text(
            lambda dxs, plan: moe._dispatch_bwd("pallas", plan, dxs)[0], y,
            plan)
    else:
        txt = compiled_text(
            lambda y, w, plan, dout: moe._combine_bwd(
                "pallas", (y, w, plan), dout)[:2], y, w, plan,
            sds((16384, 2048), jnp.float32))
    assert len(re.findall(r"%moe_rows[.0-9]* = [^\n]*custom-call\(",
                          txt)) == 1


def _qkv_call(shape, dropout, sds):
    qkv_shape, n_head = QKV_SHAPES[shape]
    rate = 0.1 if dropout else 0.0

    def attend(qkv, seed):
        return flash_attention_qkv(qkv, seed if dropout else None, n_head,
                                   rate, False)

    return attend, (sds(qkv_shape, jnp.bfloat16), sds((1,), jnp.uint32))


@pytest.mark.parametrize("dropout", [False, True], ids=["plain", "dropout"])
@pytest.mark.parametrize("shape", list(QKV_SHAPES))
def test_flash_attention_qkv_forward(sds, shape, dropout):
    attend, args = _qkv_call(shape, dropout, sds)
    txt = compiled_text(attend, *args)
    assert "tpu_custom_call" in txt
    assert not MOVES_AN_ACTIVATION.search(txt)


@pytest.mark.parametrize("dropout", [False, True], ids=["plain", "dropout"])
@pytest.mark.parametrize("shape", list(QKV_SHAPES))
def test_flash_attention_qkv_backward(sds, shape, dropout):
    """Forward + backward kernels and nothing else: the gradient program
    holds no transpose and no copy of any array, and the logsumexp goes
    from one kernel to the other through a bitcast."""
    attend, args = _qkv_call(shape, dropout, sds)
    txt = compiled_text(
        jax.grad(lambda qkv, seed: attend(qkv, seed).astype(
            jnp.float32).sum()), *args)
    assert txt.count("custom-call(") == 2
    assert not MOVES_AN_ACTIVATION.search(txt)


# -------------------------------------------------------------- serving

KV_MODES = {"fp": (jnp.bfloat16, 1), "int8": (jnp.int8, 1),
            "int4": (jnp.uint8, 2)}     # (stored dtype, head dims per byte)


def _scaled(kernel, mode):
    """The kernel with its scale planes as trailing positional operands
    for the quantised modes (fp takes none)."""
    if mode == "fp":
        return kernel
    return lambda *a: kernel(*a[:-2], k_scale=a[-2], v_scale=a[-1])


@pytest.mark.parametrize("mode", list(KV_MODES))
def test_flash_decode_contiguous(sds, mode):
    dt, pack = KV_MODES[mode]
    q = sds((B, H, D), jnp.bfloat16)
    kv = sds((B, H, L, D // pack), dt)
    args = [q, kv, kv, sds((B,), jnp.int32)]
    if mode != "fp":
        args += [sds((B, H, L), jnp.float32)] * 2
    assert "tpu_custom_call" in compiled_text(
        _scaled(fd.flash_decode, mode), *args)


@pytest.mark.parametrize("page", [16, 32])
@pytest.mark.parametrize("mode", list(KV_MODES))
def test_flash_decode_paged(sds, mode, page):
    dt, pack = KV_MODES[mode]
    n_blocks = B * L // page
    q = sds((B, H, D), jnp.bfloat16)
    kv = sds((n_blocks, H, page, D // pack), dt)
    args = [q, kv, kv, sds((B, L // page), jnp.int32), sds((B,), jnp.int32)]
    if mode != "fp":
        args += [sds((n_blocks, H, page), jnp.float32)] * 2
    assert "tpu_custom_call" in compiled_text(
        _scaled(fd.flash_decode_paged, mode), *args)


@pytest.mark.parametrize("T", ["page", 128])
@pytest.mark.parametrize("page", [16, 32])
@pytest.mark.parametrize("mode", list(KV_MODES))
def test_flash_prefill_paged(sds, mode, page, T):
    dt, pack = KV_MODES[mode]
    T = page if T == "page" else T
    n_blocks = B * L // page
    q = sds((B, H, T, D), jnp.bfloat16)
    kv = sds((n_blocks, H, page, D // pack), dt)
    args = [q, kv, kv, sds((B, L // page), jnp.int32), sds((B,), jnp.int32)]
    if mode != "fp":
        args += [sds((n_blocks, H, page), jnp.float32)] * 2
    assert "tpu_custom_call" in compiled_text(
        _scaled(fd.flash_prefill_paged, mode), *args)
