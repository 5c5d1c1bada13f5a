"""Serve-engine tests: continuous batching, slot KV pool, fixed shapes.

The contract under test (ISSUE 1 acceptance bar, extended by ISSUE 2's
pipelined hot loop):
  * >= 8 concurrent mixed-length requests on CPU, each token-for-token
    identical to single-request sample.generate under greedy decoding —
    under the PIPELINED engine (one decode step in flight, finish
    decisions lagging one step);
  * a bounded compile set — prefill programs capped by the
    (admit-ladder x bucket) grid, ONE decode shape, admit programs
    capped by the ladder, ONE release shape — asserted via the engine's
    trace counters;
  * mid-flight backfill: more requests than slots all complete, and a
    just-finished row's ride-along token never leaks into results or a
    backfilled occupant;
  * batched-prefill admission preserves FIFO order;
  * per-request determinism independent of batch composition (per-row
    keyed sampling).
"""

import threading

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from nanosandbox_tpu.config import GPTConfig
from nanosandbox_tpu.models.gpt import GPT
from nanosandbox_tpu.sample import generate
from nanosandbox_tpu.serve import (Engine, SlotScheduler, admit_ladder,
                                   default_buckets)


def _assert_compile_budget(eng):
    """The closed-compile-set contract, enforced two ways: the runtime
    guard's own postcondition (utils.tracecheck — a retrace past budget
    would already have raised), and the published per-kind numbers."""
    eng.tracecheck.assert_within_budget()
    assert eng.tracecheck.budgets() == eng.max_programs()
    budget = eng.max_programs()
    for kind, count in eng.trace_counts.items():
        assert count <= budget[kind], (kind, count, budget)
    assert eng.trace_counts["decode"] <= 1
    assert eng.trace_counts["release"] <= 1


@pytest.fixture(scope="module")
def served_model():
    cfg = GPTConfig(n_layer=2, n_head=2, n_embd=32, block_size=64,
                    vocab_size=50, dropout=0.0, compute_dtype="float32",
                    attention_impl="xla")
    model = GPT(cfg)
    params = model.init(jax.random.key(0),
                        jnp.zeros((1, 8), jnp.int32))["params"]
    return cfg, model, params


def _ref_greedy(model, params, prompt, max_new, block_size):
    out = generate(model, params, jnp.asarray([prompt], jnp.int32), max_new,
                   temperature=0.0, top_k=0, rng=jax.random.key(0),
                   block_size=block_size)
    return [int(t) for t in out[0, len(prompt):]]


# ---------------------------------------------------------------- scheduler

def test_default_buckets_ladder():
    assert default_buckets(64) == [16, 32, 64]
    assert default_buckets(100) == [16, 32, 64, 100]
    assert default_buckets(8) == [8]
    with pytest.raises(ValueError, match="max_len"):
        default_buckets(0)


def test_admit_ladder():
    assert admit_ladder(8) == [1, 2, 4, 8]
    assert admit_ladder(3) == [1, 2, 3]
    assert admit_ladder(1) == [1]
    with pytest.raises(ValueError, match="num_slots"):
        admit_ladder(0)


def test_scheduler_wave_fifo_prefix():
    """next_admission_wave pops the maximal FIFO *prefix* sharing the
    head's bucket — a different-bucket request ends the wave instead of
    being jumped over (FIFO preserved), and waves cap at free slots."""
    class Item:
        def __init__(self, n):
            self.prompt = [0] * n

    s = SlotScheduler(5, [8, 16])
    for n in (5, 3, 9, 4, 2):   # buckets: 8, 8, 16, 8, 8
        s.enqueue(Item(n))
    items, slots, bucket = s.next_admission_wave()
    # Only the two leading bucket-8 prompts: Item(9) fences the wave even
    # though Item(4)/Item(2) behind it would fit.
    assert bucket == 8 and [len(i.prompt) for i in items] == [5, 3]
    assert len(slots) == len(set(slots)) == 2
    items, slots, bucket = s.next_admission_wave()
    assert bucket == 16 and [len(i.prompt) for i in items] == [9]
    items, slots, bucket = s.next_admission_wave()
    assert bucket == 8 and [len(i.prompt) for i in items] == [4, 2]
    assert s.next_admission_wave() is None  # queue empty
    # Free-slot cap: 4 same-bucket requests, 1 free slot -> wave of 1.
    s2 = SlotScheduler(1, [8])
    for _ in range(4):
        s2.enqueue(Item(3))
    items, slots, _ = s2.next_admission_wave()
    assert len(items) == 1 and s2.queued == 3
    assert s2.next_admission_wave() is None  # no free slot left


def test_scheduler_admission_and_release():
    class Item:
        def __init__(self, n):
            self.prompt = [0] * n

    s = SlotScheduler(2, [8, 16])
    assert s.next_admission() is None  # nothing queued
    s.enqueue(Item(5))
    s.enqueue(Item(9))
    s.enqueue(Item(3))
    a = s.next_admission()
    b = s.next_admission()
    assert a[2] == 8 and b[2] == 16  # FIFO order, smallest fitting bucket
    assert a[1] != b[1]
    assert s.next_admission() is None  # both slots busy
    s.release(a[1])
    c = s.next_admission()
    assert c[1] == a[1] and c[2] == 8
    s.release(b[1])
    with pytest.raises(ValueError, match="twice"):
        s.release(b[1])


def test_scheduler_rejects_oversized_prompt():
    s = SlotScheduler(1, [8])
    with pytest.raises(ValueError, match="exceeds"):
        s.bucket_for(9)


# ------------------------------------------------------------------- engine

def test_single_request_greedy_matches_sample_generate(served_model):
    """The ISSUE's parity anchor: engine output for one request ==
    sample.generate token-for-token under greedy decoding."""
    cfg, model, params = served_model
    eng = Engine(model, params, num_slots=2, max_len=64)
    prompt = [1, 2, 3, 4, 5]
    rid = eng.submit(prompt, 15)
    res = {r.rid: r for r in eng.drain()}
    assert res[rid].tokens == _ref_greedy(model, params, prompt, 15,
                                          cfg.block_size)
    assert res[rid].finish_reason == "length"


def test_eight_concurrent_mixed_lengths_parity_and_compile_budget(
        served_model):
    """Acceptance: >= 8 concurrent mixed-length requests, per-request
    greedy parity with sample.generate, and a compile set bounded by
    #prefill-buckets + 1 decode shape."""
    cfg, model, params = served_model
    eng = Engine(model, params, num_slots=8, max_len=64)
    rng = np.random.default_rng(7)
    reqs = []
    for _ in range(8):
        L = int(rng.integers(1, 30))
        prompt = [int(x) for x in rng.integers(0, cfg.vocab_size, L)]
        mnt = int(rng.integers(1, 16))
        reqs.append((eng.submit(prompt, mnt), prompt, mnt))
    assert eng.stats()["queued"] == 8

    res = {r.rid: r for r in eng.drain()}
    assert len(res) == 8
    for rid, prompt, mnt in reqs:
        assert res[rid].tokens == _ref_greedy(model, params, prompt, mnt,
                                              cfg.block_size), rid

    assert eng.trace_counts["decode"] == 1
    _assert_compile_budget(eng)


def test_backfill_more_requests_than_slots(served_model):
    """Continuous batching proper: 10 requests through 3 slots, evicted
    rows backfilled mid-flight, every output still exact."""
    cfg, model, params = served_model
    eng = Engine(model, params, num_slots=3, max_len=64)
    rng = np.random.default_rng(11)
    reqs = []
    for _ in range(10):
        L = int(rng.integers(1, 20))
        prompt = [int(x) for x in rng.integers(0, cfg.vocab_size, L)]
        mnt = int(rng.integers(1, 10))
        reqs.append((eng.submit(prompt, mnt), prompt, mnt))
    res = {r.rid: r for r in eng.drain()}
    assert len(res) == 10
    assert eng.stats()["admitted"] == 10
    assert eng.stats()["free_slots"] == 3
    for rid, prompt, mnt in reqs:
        assert res[rid].tokens == _ref_greedy(model, params, prompt, mnt,
                                              cfg.block_size), rid


def test_eos_evicts_early(served_model):
    """A request whose eos_id is the first greedy token stops after one
    token with finish_reason='eos' and frees its slot."""
    cfg, model, params = served_model
    prompt = [3, 1, 4]
    first = _ref_greedy(model, params, prompt, 1, cfg.block_size)[0]
    eng = Engine(model, params, num_slots=1, max_len=64)
    rid = eng.submit(prompt, 20, eos_id=first)
    res = {r.rid: r for r in eng.drain()}
    assert res[rid].tokens == [first]
    assert res[rid].finish_reason == "eos"
    assert eng.stats()["free_slots"] == 1


def test_eos_mid_stream_one_step_lag_no_ride_along_leak(served_model):
    """The pipelined finish lag: an eos hit at step k is discovered after
    step k+1 was dispatched, so the engine decodes one ride-along token —
    which must NOT appear in the result, and the backfilled next occupant
    of the slot must not inherit it either."""
    cfg, model, params = served_model
    # Find a prompt whose greedy stream produces a NOVEL token somewhere
    # mid-generation (first occurrence at index >= 2) — that token is a
    # valid mid-stream eos for this randomly-initialized model.
    prompt = ref = idx = None
    for cand in ([5, 3], [6, 6, 2], [42, 13, 27, 33], [49, 48, 47]):
        r = _ref_greedy(model, params, cand, 12, cfg.block_size)
        novel = [i for i in range(2, len(r) - 1) if r[i] not in r[:i]]
        if novel:
            prompt, ref, idx = cand, r, novel[0]
            break
    assert prompt is not None, "no candidate prompt with a mid-stream " \
        "novel greedy token; extend the candidate list"
    eos = ref[idx]
    eng = Engine(model, params, num_slots=1, max_len=64)
    rid_a = eng.submit(prompt, 12, eos_id=eos)
    rid_b = eng.submit([9, 9], 6)   # backfills the SAME slot afterwards
    res = {r.rid: r for r in eng.drain()}
    assert res[rid_a].tokens == ref[:idx + 1]  # truncated AT the eos hit
    assert res[rid_a].finish_reason == "eos"
    assert res[rid_b].tokens == _ref_greedy(model, params, [9, 9], 6,
                                            cfg.block_size)
    assert eng.stats()["free_slots"] == 1


def test_pipelined_matches_synchronous_engine(served_model):
    """pipeline=True and pipeline=False produce identical results for an
    identical mixed workload — the overlap is a scheduling change, not a
    semantics change."""
    cfg, model, params = served_model
    rng = np.random.default_rng(3)
    work = []
    for i in range(7):
        L = int(rng.integers(1, 25))
        work.append(([int(x) for x in rng.integers(0, cfg.vocab_size, L)],
                     int(rng.integers(1, 12)), i))

    def run(pipeline):
        eng = Engine(model, params, num_slots=3, max_len=64,
                     pipeline=pipeline)
        rids = [eng.submit(p, mnt, temperature=0.8, top_k=7, seed=100 + s)
                for p, mnt, s in work]
        res = {r.rid: r for r in eng.drain()}
        return [(res[r].tokens, res[r].finish_reason) for r in rids]

    assert run(True) == run(False)


def test_batched_prefill_preserves_fifo_admission(served_model):
    """With 2 slots and a same-bucket pair queued BEHIND a bucket fence,
    the fenced request is admitted before later same-bucket ones (no
    reorder for wave-packing); every output still exact."""
    cfg, model, params = served_model
    eng = Engine(model, params, num_slots=2, max_len=64)
    prompts = [[1] * 4, [2] * 20, [3] * 5, [4] * 6]  # buckets 16,32,16,16
    rids = [eng.submit(p, 6) for p in prompts]
    eng.step()  # first admission wave only has room for... slots=2
    first_wave_rids = {st.req.rid for st in eng._active.values()}
    # FIFO: the wave is [prompt0] alone (bucket fence at prompt1), then
    # prompt1 takes the second slot in its own wave — prompts 2/3 (same
    # bucket as 0) must NOT jump it.
    assert first_wave_rids == {rids[0], rids[1]}
    res = {r.rid: r for r in eng.drain()}
    for rid, p in zip(rids, prompts):
        assert res[rid].tokens == _ref_greedy(model, params, p, 6,
                                              cfg.block_size)
    _assert_compile_budget(eng)


def test_stats_latency_fields(served_model):
    """The observability satellite: /stats-visible latency signal —
    tokens/sec, queue-wait, TTFT/TPOT percentiles from bounded rings."""
    cfg, model, params = served_model
    eng = Engine(model, params, num_slots=2, max_len=64)
    for i in range(5):
        eng.submit([1 + i, 2, 3], 8, seed=i)
    eng.drain()
    s = eng.stats()
    assert s["tokens_generated"] == 5 * 8
    assert s["decode_tokens_per_sec"] is None or s["decode_tokens_per_sec"] > 0
    assert s["queue_wait_steps_mean"] >= 0
    for key in ("ttft_s", "tpot_s"):
        pct = s[key]
        assert set(pct) == {"p50", "p90", "p99"}
        assert 0 <= pct["p50"] <= pct["p99"]
    assert s["pipeline"] is True
    assert s["admit_buckets"] == [1, 2]


def test_deliberate_extra_retrace_raises(served_model):
    """ISSUE 3 acceptance: the compile budget is ENFORCED, not just
    counted — feeding the compiled decode step operands of a new shape
    (the classic leak: a pool/state sized off a runtime value instead
    of num_slots) retraces past the budget of 1 and raises, instead of
    silently compiling a second program per distinct shape."""
    from nanosandbox_tpu.models.gpt import init_cache
    from nanosandbox_tpu.utils.tracecheck import CompileBudgetExceeded

    cfg, model, params = served_model
    eng = Engine(model, params, num_slots=2, max_len=64)
    rid = eng.submit([1, 2, 3], 4)
    res = {r.rid: r for r in eng.drain()}
    assert len(res[rid].tokens) == 4
    assert eng.trace_counts["decode"] == 1

    shrunken_pool = init_cache(cfg, 1, eng.max_len)
    shrunken_state = {k: v[:1] for k, v in eng._state.items()}
    with pytest.raises(CompileBudgetExceeded, match="'decode'"):
        eng._decode(eng.params, shrunken_pool, shrunken_state)
    # The rejected trace compiled nothing and consumed no counter —
    # trace_counts keeps describing the REAL compile set.
    assert eng.trace_counts["decode"] == 1
    eng.tracecheck.assert_within_budget()
    # The healthy programs keep serving: the budget names the leaky
    # program instead of poisoning the engine.
    rid2 = eng.submit([4, 5], 3)
    res = {r.rid: r for r in eng.drain()}
    assert len(res[rid2].tokens) == 3


def test_frozen_registry_turns_lazy_compiles_into_errors(served_model):
    """The serve __main__ post-warmup contract: after --warmup=full the
    registry freezes, so a request shape that somehow escaped warmup
    fails loudly instead of eating a cold compile mid-traffic."""
    from nanosandbox_tpu.utils.tracecheck import CompileBudgetExceeded

    cfg, model, params = served_model
    eng = Engine(model, params, num_slots=1, max_len=64)
    eng.submit([1, 2, 3], 2)
    eng.drain()                      # bucket-16 single-wave set compiled
    with eng.tracecheck.frozen():
        eng.submit([1, 2], 2)        # same (1, 16) programs: cached, fine
        eng.drain()
        eng.submit([9] * 20, 2)      # bucket 32: would need a NEW compile
        with pytest.raises(CompileBudgetExceeded, match="frozen"):
            eng.drain()


def test_sampled_output_independent_of_batch_composition(served_model):
    """Per-row keyed sampling: a request's tokens are a function of its
    own (prompt, settings, seed), not of its batch neighbours — the
    invariant that makes continuous batching deterministic per request."""
    cfg, model, params = served_model

    def run(prompts):
        eng = Engine(model, params, num_slots=4, max_len=64)
        rids = [eng.submit(p, 8, temperature=0.9, top_k=5, top_p=0.95,
                           seed=100 + i) for i, p in enumerate(prompts)]
        res = {r.rid: r.tokens for r in eng.drain()}
        return [res[r] for r in rids]

    solo = run([[1, 2, 3]])[0]
    crowded = run([[1, 2, 3], [9] * 12, [7, 8], [5, 4, 3, 2, 1]])[0]
    assert solo == crowded


def test_submit_validation(served_model):
    cfg, model, params = served_model
    eng = Engine(model, params, num_slots=2, max_len=32)
    with pytest.raises(ValueError, match="empty prompt"):
        eng.submit([], 4)
    with pytest.raises(ValueError, match="max_new_tokens"):
        eng.submit([1], -1)
    with pytest.raises(ValueError, match="prefill bucket"):
        eng.submit([1] * 33, 1)
    with pytest.raises(ValueError, match="per-slot KV length"):
        eng.submit([1] * 30, 10)


def test_max_new_tokens_zero_completes_without_slot(served_model):
    cfg, model, params = served_model
    eng = Engine(model, params, num_slots=1, max_len=32)
    rid = eng.submit([1, 2], 0)
    res = {r.rid: r for r in eng.drain()}
    assert res[rid].tokens == [] and res[rid].finish_reason == "length"
    assert eng.stats()["admitted"] == 0  # never took a slot


def test_idle_slots_do_not_perturb_active_rows(served_model):
    """A decode step always runs all num_slots rows; idle/padding rows
    must not change an active row's tokens (masked frontiers)."""
    cfg, model, params = served_model
    prompt = [2, 7, 1, 8]
    ref = _ref_greedy(model, params, prompt, 12, cfg.block_size)
    for slots in (1, 4, 8):
        eng = Engine(model, params, num_slots=slots, max_len=64)
        rid = eng.submit(prompt, 12)
        res = {r.rid: r for r in eng.drain()}
        assert res[rid].tokens == ref, slots


# --------------------------------------------------------------------- http

def test_http_frontend_concurrent_roundtrip(served_model):
    """N concurrent HTTP clients multiplex into one engine batch and get
    their own results back; bad requests surface as 400s."""
    import json
    import urllib.error
    import urllib.request

    from nanosandbox_tpu.serve.http import EngineLoop, make_server

    cfg, model, params = served_model
    eng = Engine(model, params, num_slots=4, max_len=64)
    loop = EngineLoop(eng)
    loop.start()
    encode = lambda s: [min(ord(c), cfg.vocab_size - 1) for c in s]  # noqa: E731
    decode = lambda ids: " ".join(str(i) for i in ids)  # noqa: E731
    srv = make_server("127.0.0.1", 0, loop, encode, decode)
    port = srv.server_address[1]
    t = threading.Thread(target=srv.serve_forever, daemon=True)
    t.start()
    try:
        def post(payload):
            req = urllib.request.Request(
                f"http://127.0.0.1:{port}/generate",
                data=json.dumps(payload).encode())
            with urllib.request.urlopen(req, timeout=60) as r:
                return json.loads(r.read())

        out = {}

        def client(i):
            out[i] = post({"prompt": "ab" * (i + 1), "max_new_tokens": 4,
                           "temperature": 0.0})

        threads = [threading.Thread(target=client, args=(i,))
                   for i in range(6)]
        for th in threads:
            th.start()
        for th in threads:
            th.join()
        assert all(len(out[i]["tokens"]) == 4 for i in range(6))
        assert all(out[i]["finish_reason"] == "length" for i in range(6))

        with urllib.request.urlopen(
                f"http://127.0.0.1:{port}/healthz", timeout=10) as r:
            assert json.loads(r.read()) == {"ok": True}
        with urllib.request.urlopen(
                f"http://127.0.0.1:{port}/stats", timeout=10) as r:
            assert json.loads(r.read())["admitted"] >= 6

        with pytest.raises(urllib.error.HTTPError) as ei:
            post({"prompt": "x" * 100, "max_new_tokens": 4})
        assert ei.value.code == 400
    finally:
        srv.shutdown()
        srv.server_close()
        loop.stop()


def test_engine_loop_failure_fails_waiters_fast():
    """If the engine dies mid-step, every waiter (queued AND in-flight)
    is failed immediately — not left to block until timeout — and later
    submissions fail fast with the death reason."""
    from nanosandbox_tpu.serve.http import EngineLoop

    class BoomEngine:
        def submit(self, **kw):
            return 0

        def has_work(self):
            return True

        def step(self):
            raise RuntimeError("boom")

    loop = EngineLoop(BoomEngine())
    loop.start()
    p = loop.submit(prompt=[1], max_new_tokens=1)
    assert p.done.wait(30)
    assert isinstance(p.error, RuntimeError) and "boom" in str(p.error)
    loop.join(30)
    assert loop.dead is not None
    p2 = loop.submit(prompt=[1], max_new_tokens=1)
    assert p2.done.is_set() and "boom" in str(p2.error)


# -------------------------------------------------------------------- bench

def test_bench_decode_mode_emits_json():
    import bench

    result = bench.bench_decode({"num_slots": "2", "max_new_tokens": "3",
                                 "requests": "3"}, quick=True, on_tpu=False)
    assert result["unit"] == "tokens/sec"
    assert result["value"] > 0
    extra = result["extra"]
    assert extra["tokens_generated"] == 9
    # Pipelined-vs-synchronous comparison fields (trend-tracking, no
    # threshold) + the latency signal.
    assert extra["pipelined_tokens_per_sec"] > 0
    assert extra["sync_tokens_per_sec"] > 0
    assert extra["pipeline_speedup"] == pytest.approx(
        extra["pipelined_tokens_per_sec"] / extra["sync_tokens_per_sec"])
    assert set(extra["ttft_s"]) == {"p50", "p90", "p99"}
    # Compile budget: the closed (admit-rung x bucket) grid.
    budget = (len(extra["prefill_buckets"]) * len(extra["admit_buckets"])
              + len(extra["admit_buckets"]) + 2)
    assert sum(extra["trace_counts"].values()) <= budget
    assert extra["trace_counts"]["decode"] == 1


def test_bench_decode_mixed_mode():
    import bench

    result = bench.bench_decode({"num_slots": "2", "max_new_tokens": "4",
                                 "requests": "4", "mixed": "1"},
                                quick=True, on_tpu=False)
    assert result["extra"]["mixed"] is True
    assert 0 < result["extra"]["tokens_generated"] <= 16


def test_engine_from_restored_checkpoint_keeps_compile_budget(tiny_cfg):
    """`python -m nanosandbox_tpu.serve`'s own path: params restored from
    a checkpoint arrive on the Trainer's mesh, and that mesh is part of
    their TYPE. A single-chip engine must shed it, or every program's
    outputs come back mesh-typed, retrace against the mesh-free pool
    and slot state it was first traced with, and the warm-up dies with
    CompileBudgetExceeded (it did, on every restored checkpoint)."""
    from jax.sharding import SingleDeviceSharding

    from nanosandbox_tpu.sample import cast_params_for_serving
    from nanosandbox_tpu.train import Trainer, restore_for_inference

    cfg = tiny_cfg.replace(max_iters=2, eval_interval=0)
    Trainer(cfg).run()
    trainer, state, _ = restore_for_inference(
        cfg.out_dir, data_dir=cfg.data_dir, device="cpu")
    params = cast_params_for_serving(state["params"],
                                     trainer.cfg.compute_dtype)
    eng = Engine(trainer.model, params, num_slots=2, max_len=32)
    assert all(isinstance(leaf.sharding, SingleDeviceSharding)
               for leaf in jax.tree.leaves(eng.params))
    # The same shapes three times over: from the second round on, every
    # program is fed its own (or another program's) outputs.
    for _ in range(3):
        eng.submit([1, 2, 3], 3)
        eng.submit([4, 5], 3)
        assert all(len(r.tokens) == 3 for r in eng.drain())
    _assert_compile_budget(eng)
