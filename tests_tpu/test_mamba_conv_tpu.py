"""The Mamba conv's kernels on the chip (ops/mamba_conv.py, 'pallas') against
its XLA form, at the granite cell's shape: in_proj's output (1, 8192, 8512)
bfloat16 with xBC at columns 4096..8447 (x 4096 lanes, B and C 128 each),
4 taps.

Both are held to the same float32 reference (the XLA form on in_proj's
output cast to float32, at ``highest`` precision): x, B, C and the
gradients of in_proj's output (dxBC), the taps and the bias under fixed
cotangents, each as its largest error over the reference's largest
magnitude. The kernels have to come within ``SLACK`` times the XLA form's
own error, or ``FLOOR`` where the XLA form reads lower still. Both paths'
ms forward and forward + backward (the min of 10 calls) are printed and
written as a line to ``chiprun_out/mamba_conv_tpu.jsonl``.

    python -m pytest tests_tpu/test_mamba_conv_tpu.py -q -s   # one TPU chip
"""

import json
import os
import time

import jax
import jax.numpy as jnp

from nanosandbox_tpu.ops import mamba_conv as conv_op

T, INNER, GN, L, HEADS = 8192, 4096, 128, 4, 64
WIDTH = INNER + 2 * GN
SLACK, FLOOR = 2.0, 1e-5
OUT = "chiprun_out/mamba_conv_tpu.jsonl"
NAMES = ("x", "B", "C", "dxBC", "dw", "dbias")


def _inputs():
    k = jax.random.split(jax.random.key(44), 4)
    proj = jax.random.normal(k[0], (1, T, INNER + WIDTH + HEADS),
                             jnp.bfloat16)
    w = jax.random.uniform(k[1], (WIDTH, L), minval=-0.5, maxval=0.5)
    bias = 0.1 * jax.random.normal(k[2], (WIDTH,))
    cts = tuple(jax.random.normal(c, (1, T, n)) for c, n in zip(
        jax.random.split(k[3], 3), (INNER, GN, GN)))
    return (proj, w, bias), cts


def _programs(impl):
    def both(args, cts):
        out, vjp = jax.vjp(lambda *a: conv_op.mamba_conv(
            *a, start=INNER, inner=INNER, impl=impl), *args)
        dproj, dw, dbias = vjp(cts)
        return (*out, dproj[..., INNER:INNER + WIDTH], dw, dbias)

    forward = jax.jit(lambda args: conv_op.mamba_conv(
        *args, start=INNER, inner=INNER, impl=impl))
    return jax.jit(both), forward


def _timed(fn, *args, n=10):
    out = jax.block_until_ready(fn(*args))
    times = []
    for _ in range(n):
        t0 = time.perf_counter()
        jax.block_until_ready(fn(*args))
        times.append((time.perf_counter() - t0) * 1e3)
    return out, min(times)


def test_kernels_against_the_xla_form_on_the_chip():
    assert conv_op.resolve_mamba_conv_impl("auto", T, INNER, INNER, WIDTH,
                                           L) == "pallas"
    args, cts = _inputs()
    with jax.default_matmul_precision("highest"):
        want = _programs("xla")[0]((args[0].astype(jnp.float32), *args[1:]),
                                   cts)
    got, ms = {}, {}
    for impl in ("xla", "pallas"):
        both, forward = _programs(impl)
        got[impl], ms[f"{impl}_fwd_bwd"] = _timed(both, args, cts)
        _, ms[f"{impl}_fwd"] = _timed(forward, args)
    errors = {impl: {name: float(jnp.max(jnp.abs(a.astype(jnp.float32) - b))
                                 / jnp.max(jnp.abs(b)))
                     for name, a, b in zip(NAMES, got[impl], want)}
              for impl in got}
    line = dict(ms={k: round(v, 3) for k, v in ms.items()}, errors=errors,
                device=jax.devices()[0].device_kind)
    print(json.dumps(line))
    os.makedirs(os.path.dirname(OUT), exist_ok=True)
    with open(OUT, "a") as f:
        f.write(json.dumps(line) + "\n")
    for name in NAMES:
        bound = max(SLACK * errors["xla"][name], FLOOR)
        assert errors["pallas"][name] <= bound, (name, errors)
