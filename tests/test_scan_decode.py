"""Multi-token decode scan + int4 KV + paged-prefill kernel (ISSUE 12).

The lag-k contract under test (its greedy token PARITY across pools and KV
modes is tests/test_scan_parity.py's):
  * a mid-chunk eos truncates exactly where the single-step loop would
    have stopped, with no leaked slots or KV blocks;
  * a poisoned MID-SCAN chunk recovers through the supervisor and the
    resumed stream restitches token-identically to a no-fault run
    (clean pre-poison prefix kept, downstream-of-garbage tokens
    discarded);
  * the compile set widens ONLY by the declared scan-rung ladder:
    max_programs()['decode'] == len(scan_rungs), trace counts within
    budget, everything else identical to a scan_k=1 engine;
  * the dispatch ledger: decode dispatches drop by the chunking factor
    (tokens_per_dispatch > 1) and the serve_host_dispatches_total /
    serve_tokens_per_dispatch families land on /metrics;
  * int4 quantization round-trips within max|row|/7.5 per block of
    lanes (the per-(row, head, position) residual-scale format).
"""

import jax.numpy as jnp
import numpy as np
import pytest

from _scan_common import _mixed_reqs, _run, served_model  # noqa: F401
from nanosandbox_tpu.serve import Engine, EngineSupervisor
from nanosandbox_tpu.serve.faults import FaultPlan


def test_scan_parity_survives_sync_loop(served_model):
    """scan composes with pipeline=False too (chunked sync loop)."""
    _, model, params = served_model
    reqs = _mixed_reqs(seed=5)
    _, base = _run(model, params, reqs)
    _, out = _run(model, params, reqs, pipeline=False, scan_k=4)
    assert out == base


def test_mid_chunk_eos_truncates_exactly_no_leaks(served_model):
    """An eos landing mid-chunk cuts the stream exactly where the
    single-step loop would; afterwards no slot or block is leaked."""
    from collections import Counter

    _, model, params = served_model
    # Self-calibrating eos: run once eos-free, pick the most common
    # MID-stream token — per-row keyed sampling means re-running with
    # that token as eos truncates those rows exactly there, so the
    # workload is guaranteed to exercise the mid-chunk eos path.
    reqs0 = _mixed_reqs(n=12, seed=11)
    _, free = _run(model, params, reqs0, paged=True)
    cnt = Counter(t for toks, _ in free.values() for t in toks[:-1])
    eos = cnt.most_common(1)[0][0]
    reqs = [(p, m, s, eos) for (p, m, s, _) in reqs0]
    _, base = _run(model, params, reqs, paged=True)
    eng, out = _run(model, params, reqs, paged=True, scan_k=8)
    assert out == base
    assert any(r[1] == "eos" for r in out.values()), \
        "workload never hit eos — the test lost its subject"
    assert not eng._active and eng.sched.free_slots == eng.num_slots
    ps = eng.block_pool.stats()
    assert ps["live"] == 0, ps


def test_mid_scan_poison_recovery_restitches(served_model):
    """A nan_logits fault poisoning a whole scan chunk recovers via the
    supervisor and the final outputs equal a no-fault run's — the
    clean pre-poison tokens are kept, downstream garbage discarded,
    victims requeued with prompt' = prompt + tokens-so-far."""
    _, model, params = served_model
    reqs = _mixed_reqs(n=8, seed=7)
    _, clean = _run(model, params, reqs, scan_k=4)
    plan = FaultPlan.parse("nan_logits@3")
    eng = Engine(model, params, num_slots=4, max_len=64, scan_k=4,
                 faults=plan)
    sup = EngineSupervisor(eng, backoff_base_s=0)
    for prompt, mnt, seed, eos in reqs:
        eng.submit(prompt, mnt, seed=seed, eos_id=eos)
    out = []
    while eng.has_work() and sup.state != "failed":
        out.extend(sup.step())
    assert sup.state == "ok"
    assert eng.recoveries >= 1
    assert {r.rid: (r.tokens, r.finish_reason) for r in out} == clean


def test_scan_budget_pinned_not_widened(served_model):
    """The compile set grows by EXACTLY the scan-rung ladder (decode
    programs), nothing else; trace counts stay within the published
    budget."""
    _, model, params = served_model
    reqs = _mixed_reqs(seed=13)
    e1, _ = _run(model, params, reqs)
    e8, _ = _run(model, params, reqs, scan_k=8)
    p1, p8 = e1.max_programs(), e8.max_programs()
    assert e8.scan_rungs == [1, 2, 4, 8]
    assert p8["decode"] == len(e8.scan_rungs)
    assert {k: v for k, v in p8.items() if k != "decode"} == \
        {k: v for k, v in p1.items() if k != "decode"}
    for name, n in e8.trace_counts.items():
        assert n <= p8[name], (name, n, p8)


def test_scan_dispatch_ledger_and_metrics(served_model):
    """Chunked decode amortizes dispatches: tokens_per_dispatch well
    above 1, and the ledger lands on /metrics as
    serve_host_dispatches_total{kind=} + serve_tokens_per_dispatch."""
    _, model, params = served_model
    reqs = [(list(range(2, 10)), 16, s, None) for s in range(6)]
    eng, _ = _run(model, params, reqs, scan_k=8)
    st = eng.stats()
    assert st["scan_k"] == 8
    assert st["tokens_per_dispatch"] is not None
    assert st["tokens_per_dispatch"] > 2.0
    assert eng.host_dispatches["decode"] * 2 < eng.tokens_generated
    from nanosandbox_tpu.obs import render_prometheus

    text = render_prometheus(eng.metrics)
    assert 'serve_host_dispatches_total{kind="decode"}' in text
    assert "serve_tokens_per_dispatch" in text
    # The single-step twin must retire ~one token per row per dispatch.
    eng1, _ = _run(model, params, reqs)
    assert eng1.host_dispatches["decode"] >= eng.host_dispatches["decode"]


def test_flight_retire_events_carry_chunk_index(served_model):
    """Under lag-k every retire event records n tokens + its scan-chunk
    index, so per-token TPOT stays derivable from the flight JSONL."""
    _, model, params = served_model
    eng = Engine(model, params, num_slots=2, max_len=64, scan_k=4)
    eng.submit(list(range(2, 8)), 10, seed=1)
    eng.drain()
    retires = [e for e in eng.flight.events() if e["ev"] == "retire"]
    assert retires
    chunked = [e for e in retires if e.get("n", 0) > 1]
    assert chunked, "scan_k=4 never retired a multi-token chunk"
    assert all("chunk" in e for e in chunked)
    total = sum(e["n"] for e in retires)
    finishes = [e for e in eng.flight.events() if e["ev"] == "finish"]
    # Each request's FIRST token comes from its prefill wave, not a
    # decode retire — the ledger splits them by design.
    assert sum(f["tokens"] for f in finishes) == total + len(finishes)


def test_scan_forced_to_one_under_spec(served_model):
    """spec keeps the synchronous loop: scan_k silently collapses to 1
    (the verify readback gates the next frontier)."""
    from nanosandbox_tpu.serve import NGramDrafter

    _, model, params = served_model
    eng = Engine(model, params, num_slots=4, max_len=64, scan_k=8,
                 spec=NGramDrafter(k=3))
    assert eng.scan_k == 1 and eng.scan_rungs == [1]


def test_scan_k_validation(served_model):
    _, model, params = served_model
    with pytest.raises(ValueError, match="scan_k"):
        Engine(model, params, num_slots=2, max_len=64, scan_k=0)


def test_int4_round_trip_error_bound():
    """Per-block-of-lanes int4 residual scales: round-trip error is
    bounded by max|row| / 7.5 (the nibble grid's worst case), and
    all-zero rows survive exactly."""
    from nanosandbox_tpu.ops.flash_decode import (quantize_kv_rows_int4,
                                                  unpack_int4)

    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.normal(size=(3, 2, 17, 32)) * 9.0, jnp.float32)
    x = x.at[1, 0, 4].set(0.0)                      # an all-zero row
    packed, scale = quantize_kv_rows_int4(x)
    assert packed.dtype == jnp.uint8
    assert packed.shape == (3, 2, 17, 16)
    back = unpack_int4(packed).astype(jnp.float32) * scale[..., None]
    amax = jnp.max(jnp.abs(x), axis=-1, keepdims=True)
    err = jnp.abs(back - x)
    assert bool(jnp.all(err <= amax / 7.5 + 1e-7))
    assert bool(jnp.all(back[1, 0, 4] == 0.0))


def test_int4_sentinel_rows_skip_scale_chain():
    """The valid-mask fast path: sentinel rows quantize to zero scale
    and zero values without feeding the amax/divide chain."""
    from nanosandbox_tpu.ops.flash_decode import (quantize_kv_rows,
                                                  quantize_kv_rows_int4,
                                                  unpack_int4)

    rng = np.random.default_rng(1)
    x = jnp.asarray(rng.normal(size=(4, 2, 8)), jnp.float32)
    valid = jnp.asarray([True, False, True, False])[:, None]
    p4, s4 = quantize_kv_rows_int4(x, valid=valid)
    assert bool(jnp.all(s4[1] == 0)) and bool(jnp.all(s4[3] == 0))
    assert bool(jnp.all(unpack_int4(p4)[1] == 0))
    q8, s8 = quantize_kv_rows(x, valid=valid)
    assert bool(jnp.all(s8[1] == 0)) and bool(jnp.all(q8[1] == 0))
    # valid rows match the unmasked quantization exactly
    p4u, s4u = quantize_kv_rows_int4(x)
    assert bool(jnp.all(p4[0] == p4u[0])) and bool(jnp.all(s4[0] == s4u[0]))


@pytest.mark.parametrize("paged", [True, False])
def test_int4_vs_fp32_greedy_agreement(served_model, paged):
    """int4 is a lossy mode: require >= 90% greedy token agreement with
    the fp32 pool on the mixed workload (the ISSUE-12 parity floor),
    and identical agreement paged vs dense (same quantizer, same
    positions)."""
    _, model, params = served_model
    reqs = _mixed_reqs(n=10, seed=17)
    _, fp = _run(model, params, reqs, paged=paged)
    _, q4 = _run(model, params, reqs, paged=paged, kv_dtype="int4")
    total = matched = 0
    for rid, (toks, _) in fp.items():
        qtoks = q4[rid][0]
        total += max(len(toks), len(qtoks))
        matched += sum(a == b for a, b in zip(toks, qtoks))
    assert matched / total >= 0.9, f"int4 greedy agreement {matched/total}"


def test_int4_paged_equals_dense_token_exact(served_model):
    """Paged int4 reads/writes the same quantized values at the same
    positions as dense int4 — token-identical outputs."""
    _, model, params = served_model
    reqs = _mixed_reqs(n=10, seed=19)
    _, dense = _run(model, params, reqs, paged=False, kv_dtype="int4")
    _, paged = _run(model, params, reqs, paged=True, kv_dtype="int4")
    assert paged == dense


def test_int4_doubles_pool_capacity_at_equal_value_bytes(served_model):
    """The capacity story: an int4 pool holds 2x the blocks of an int8
    pool at equal value bytes, and admission need per request is
    dtype-independent — so effective capacity doubles."""
    cfg, model, params = served_model
    e8 = Engine(model, params, num_slots=4, max_len=64, kv_dtype="int8")
    e4 = Engine(model, params, num_slots=4, max_len=64, kv_dtype="int4",
                kv_pool_blocks=2 * e8.kv_pool_blocks)
    # per-block value bytes: int4 stores head_dim // 2 uint8 lanes
    k8 = e8._pool[0][0]
    k4 = e4._pool[0][0]
    assert k4.shape[-1] * 2 == k8.shape[-1]
    assert k4.dtype == jnp.uint8 and k8.dtype == jnp.int8
    assert (k4.size * k4.dtype.itemsize
            == k8.size * k8.dtype.itemsize)      # equal value bytes
    need8 = e8.block_pool.blocks_needed(20, 10)
    need4 = e4.block_pool.blocks_needed(20, 10)
    assert need8 == need4
    assert e4.kv_pool_blocks == 2 * e8.kv_pool_blocks


def test_scan_bench_smoke():
    """bench.py --mode=decode --scan_k wiring: scan twin fields land in
    the JSON with parity 1.0 and a sane dispatch ledger."""
    import bench

    out = bench.main(["--quick", "--mode=decode", "--mixed",
                      "--scan_k=4", "--repeat=2", "--requests=8"])
    extra = out["extra"]
    assert extra["scan_k"] == 4
    assert extra["scan_rungs"] == [1, 2, 4]
    assert extra["scan_greedy_parity"] == 1.0
    assert extra["scan_vs_single_toks"] > 0
    assert extra["dispatches_per_token"] <= 0.5
    assert extra["tokens_per_dispatch"] > 1.0


@pytest.mark.parametrize("max_len", [64, 10])
def test_scan_rung_warmup_is_freeze_safe(served_model, max_len):
    """Engine.warm_scan_rungs() (the serve __main__ / bench warmup)
    compiles the ENTIRE ladder — including rungs only reachable through
    tie-breaks or mixed-row budget profiles — so a frozen registry
    survives arbitrary post-warmup traffic. max_len=10 pins the
    short-context case where a budget-capped warmup heuristic used to
    skip the top rung and the first max-budget request retraced
    post-freeze."""
    _, model, params = served_model
    e = Engine(model, params, num_slots=4, max_len=max_len, scan_k=8)
    lo = 1
    for bucket in e.sched.buckets:
        length = min(bucket, e.max_len - 2)
        lo, prev_lo = bucket + 1, lo
        if length < prev_lo:
            continue
        for k in e.admit_buckets:
            for _ in range(k):
                e.submit([0] * length, 2)
            e.drain()
            e.reset_prefix_cache()
    e.warm_scan_rungs()
    e.reset_prefix_cache()
    assert e.trace_counts["decode"] == len(e.scan_rungs)
    with e.tracecheck.frozen():
        rng = np.random.default_rng(0)
        for i in range(40):
            L = int(rng.integers(1, min(50, max_len - 1)))
            mnt = int(rng.integers(1, max_len - L + 1))
            e.submit(rng.integers(0, 50, L).tolist(), mnt, seed=i)
        e.drain()
