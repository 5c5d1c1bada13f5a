"""The Mamba-2 scan's kernels on the chip (ops/ssd.py, 'pallas') against its
XLA form, at the granite cell's shape: (1, 8192) tokens, 64 heads of 64,
d_state 128, one group, chunk 256, bfloat16 products.

Both are held to the same float32 reference (the XLA form with float32
products at ``highest`` precision): y and the gradient of every input
(x, dt, A, B, C, D) under a fixed cotangent, each as its largest error over
the reference's largest magnitude. The kernels have to come within
``SLACK`` times the XLA form's own error (or ``FLOOR``, where the XLA form
reads lower still), at dt as the model starts (log-uniform on [1e-3, 0.1])
and at large dt (the state forgets within a few tokens); their counters
within 1e-4 of the XLA form's at the same bfloat16 products. Both paths' ms
forward and forward + backward (the min of 10 calls) are printed and
written a line a case to ``chiprun_out/ssd_tpu.jsonl``.

    python -m pytest tests_tpu/test_ssd_tpu.py -q -s   # on one TPU chip
"""

import json
import os
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from nanosandbox_tpu.ops import ssd

T, H, P, N, CHUNK = 8192, 64, 64, 128, 256
SLACK, FLOOR = 2.5, 1e-3
OUT = "chiprun_out/ssd_tpu.jsonl"
NAMES = ("y", "dx", "ddt", "dA", "dB", "dC", "dD")


def _inputs(dt_case: str):
    k = jax.random.split(jax.random.key(42), 7)
    x = jax.nn.silu(jax.random.normal(k[0], (1, T, H * P)))
    if dt_case == "init":
        lo, hi = np.log(1e-3), np.log(1e-1)
        dt = jnp.exp(jax.random.uniform(k[1], (1, T, H), minval=lo,
                                        maxval=hi))
    else:
        dt = jax.nn.softplus(jax.random.normal(k[1], (1, T, H)) + 1.0)
    A = -jax.random.uniform(k[2], (H,), minval=1.0, maxval=16.0)
    B = jax.nn.silu(jax.random.normal(k[3], (1, T, N)))
    C = jax.nn.silu(jax.random.normal(k[4], (1, T, N)))
    D = jnp.ones((H,))
    dy = jax.random.normal(k[5], (1, T, H * P))
    return (x, dt, A, B, C, D), dy


def _program(impl, dtype):
    def run(args, dy):
        (y, stats), vjp = jax.vjp(
            lambda *a: ssd.ssd(*a, chunk=CHUNK, dtype=dtype, impl=impl),
            *args)
        zero = jax.tree.map(jnp.zeros_like, stats)
        return (y, *vjp((dy, zero))), stats

    forward = jax.jit(lambda args: ssd.ssd(*args, chunk=CHUNK, dtype=dtype,
                                           impl=impl)[0])
    return jax.jit(run), forward


def _timed(fn, *args, n=10):
    out = jax.block_until_ready(fn(*args))
    times = []
    for _ in range(n):
        t0 = time.perf_counter()
        jax.block_until_ready(fn(*args))
        times.append((time.perf_counter() - t0) * 1e3)
    return out, min(times)


@pytest.mark.parametrize("dt_case", ["init", "large"])
def test_kernels_against_the_xla_form_on_the_chip(dt_case):
    assert ssd.resolve_ssd_impl("auto", T, CHUNK, P, N, heads=H,
                                groups=1) == "pallas"
    args, dy = _inputs(dt_case)
    with jax.default_matmul_precision("highest"):
        (want, want_stats), _ = _timed(_program("xla", jnp.float32)[0],
                                       args, dy, n=1)
    got, ms = {}, {}
    for impl in ("xla", "pallas"):
        both, forward = _program(impl, jnp.bfloat16)
        got[impl], ms[f"{impl}_fwd_bwd"] = _timed(both, args, dy)
        _, ms[f"{impl}_fwd"] = _timed(forward, args)
    errors = {impl: {name: float(jnp.max(jnp.abs(a - b))
                                 / jnp.max(jnp.abs(b)))
                     for name, a, b in zip(NAMES, got[impl][0], want)}
              for impl in got}
    stats = {impl: {k: float(v) for k, v in got[impl][1].items()}
             for impl in got}
    line = dict(case=dt_case, ms={k: round(v, 3) for k, v in ms.items()},
                errors=errors, stats=stats,
                reference_stats={k: float(v) for k, v in want_stats.items()},
                device=jax.devices()[0].device_kind)
    print(json.dumps(line))
    os.makedirs(os.path.dirname(OUT), exist_ok=True)
    with open(OUT, "a") as f:
        f.write(json.dumps(line) + "\n")
    for name in NAMES:
        bound = max(SLACK * errors["xla"][name], FLOOR)
        assert errors["pallas"][name] <= bound, (name, errors)
    # the counters read states made of bfloat16 products: the XLA form's
    for k, v in stats["xla"].items():
        np.testing.assert_allclose(stats["pallas"][k], v, rtol=1e-4,
                                   err_msg=k)
