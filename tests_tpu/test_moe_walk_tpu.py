"""The expert layer's walk on the chip (ISSUE 38): ``ops.moe.routed_experts``
through its own VJP at the three expert cells' shapes, with megablox and the
row mover, against the walk written as a sum that starts at zero (float32
zeros, a scan over ALL the chunks, every gradient cast back at the end: what
ops/moe.py ran before that issue). One chunk run (a balanced selection), a
second chunk barely reached (the fullest layer-step of the Moonlight cell
holds 95 % of a chunk) and a second chunk well filled. Every output, counter
and gradient has to be the sum's (bit for bit from a second chunk on), and
both programs are timed: a line a case in ``chiprun_out/moe_walk_tpu.jsonl``
(the min of 10 calls). ``program_ms`` is where PERF.md's price of a
layer-step that takes the second chunk comes from. ``from_zeros_ms`` is NOT
what the walk cost before the issue: the sum here takes a chunk's output and
its gradients from one ``jax.vjp``, where the layer's own VJP walks the
chunks a second time and recomputes each chunk's forward.

    chiprun --chips 1 -- python -m pytest tests_tpu/test_moe_walk_tpu.py -q
"""

import json
import os
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from nanosandbox_tpu.ops import moe

CELLS = {  # N, k, E, held, d, F: a chip's share of the cell's expert layer
    "lfm2": (16384, 4, 32, 8, 2048, 1792),
    "trinity": (16384, 8, 128, 16, 2048, 1024),
    "moonlight": (16384, 6, 64, 8, 2048, 1408),
}
# tokens (of N) whose two first choices are held experts 0 and 1
LOADS = {"balanced": 0.0, "just_over": None, "skewed": 1.0}
OUT = "chiprun_out/moe_walk_tpu.jsonl"


def _selection(score, k, count, rows, load):
    """sel (N, k) and the pairs it holds. ``just_over``: as many tokens sent
    to experts 0 and 1 as bring the held pairs a little over one chunk."""
    N, E = score.shape

    def pick(skewed):
        favour = (jnp.arange(N) < skewed)[:, None] & (jnp.arange(E) < 2)[None]
        _, sel = jax.lax.top_k(score + favour, k)
        return sel.astype(jnp.int32), int(jnp.sum(sel < count))

    if LOADS[load] is not None:
        return pick(int(LOADS[load] * N))
    lo, hi = 0, N                          # held pairs rise with the skew
    while hi - lo > 1:
        mid = (lo + hi) // 2
        lo, hi = (lo, mid) if pick(mid)[1] > rows else (mid, hi)
    return pick(hi + N // 256)


def _timed(fn, args, n=10):
    for _ in range(2):
        out = jax.block_until_ready(fn(*args))
    times = []
    for _ in range(n):
        t0 = time.perf_counter()
        jax.block_until_ready(fn(*args))
        times.append((time.perf_counter() - t0) * 1e3)
    return out, min(times)


@pytest.mark.parametrize("load", list(LOADS))
@pytest.mark.parametrize("cell", list(CELLS))
def test_walk_equals_the_sum_from_zeros_on_the_chip(cell, load):
    N, k, E, count, d, F = CELLS[cell]
    rows, chunks = moe.chunk_rows(N, k, E, count)
    keys = jax.random.split(jax.random.key(38), 7)
    sel, held = _selection(jax.random.uniform(keys[1], (N, E)), k, count,
                           rows, load)
    x = jax.random.normal(keys[0], (N, d)).astype(jnp.bfloat16)
    w = jax.random.uniform(keys[2], (N, k)) + 0.1
    mats = [(0.05 * jax.random.normal(kk, shape)).astype(jnp.bfloat16)
            for kk, shape in zip(keys[3:6], [(count, d, F), (count, d, F),
                                             (count, F, d)])]
    d_out = jax.random.normal(keys[6], (N, d))
    ran = max(1, -(-held // rows))
    assert ran == (1 if load == "balanced" else 2) and chunks >= 2
    if load == "just_over":
        assert rows < held < 1.02 * rows

    @jax.jit
    def program(x, w, *mats):
        (out, stats), vjp = jax.vjp(
            lambda *a: moe.routed_experts(a[0], sel, a[1], *a[2:], 0, count,
                                          E), x, w, *mats)
        return out, stats, vjp((d_out, np.zeros(3, jax.dtypes.float0)))

    @jax.jit
    def from_zeros(*ops):
        pairs = moe.plan_pairs(sel, 0, count, rows * chunks)

        def chunk(carry, c):
            def add(carry):
                out, covered, grads = carry
                plan = moe.chunk_plan(pairs, c, rows, k)
                got, vjp = jax.vjp(
                    lambda *a: moe._chunk_out(*a, plan, "auto"), *ops)
                return (out + got, covered + jnp.sum(plan["group_sizes"]),
                        tuple(g + dg.astype(jnp.float32)
                              for g, dg in zip(grads, vjp(d_out))))

            return jax.lax.cond(pairs["total"] > c * rows, add,
                                lambda carry: carry, carry), None

        zeros = (jnp.zeros((N, d), jnp.float32), jnp.zeros((), jnp.int32),
                 tuple(jnp.zeros(a.shape, jnp.float32) for a in ops))
        (out, covered, grads), _ = jax.lax.scan(chunk, zeros,
                                                jnp.arange(chunks))
        stats = jnp.stack([pairs["total"], pairs["max_rows"],
                           pairs["total"] - covered]).astype(jnp.int32)
        return out, stats, tuple(g.astype(a.dtype)
                                 for g, a in zip(grads, ops))

    got, program_ms = _timed(program, (x, w, *mats))
    want, zeros_ms = _timed(from_zeros, (x, w, *mats))
    names = ["out", "stats", "dx", "dw", "dg", "du", "dd"]
    got, want = jax.tree.leaves(got), jax.tree.leaves(want)
    bits_differ = [n for n, a, b in zip(names, got, want, strict=True)
                   if np.asarray(a).tobytes() != np.asarray(b).tobytes()]
    os.makedirs(os.path.dirname(OUT), exist_ok=True)
    with open(OUT, "a") as f:
        f.write(json.dumps(dict(
            cell=cell, load=load, held=held, rows=rows, chunks=chunks,
            chunks_run=ran, program_ms=round(program_ms, 3),
            from_zeros_ms=round(zeros_ms, 3), bits_differ=bits_differ,
            device=jax.devices()[0].device_kind)) + "\n")
    assert got[1].tolist()[::2] == [held, 0]           # held, dropped
    for name, a, b in zip(names, got, want):
        assert a.dtype == b.dtype and a.shape == b.shape, name
        # a zero's sign may survive where the sum from +0.0 lost it
        np.testing.assert_array_equal(
            np.asarray(a.astype(jnp.float32)),
            np.asarray(b.astype(jnp.float32)), err_msg=name)
    if ran > 1:                    # the float32 sums, in the sum's order
        assert not bits_differ
