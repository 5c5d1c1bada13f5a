"""Continuous-batching decode engine over a slot-based KV cache pool.

Design (the TPU fixed-shape discipline, end to end):

  * One per-layer KV pool of shape (num_slots, H, max_len, D)
    (models/gpt.py init_cache with batch = num_slots). Each in-flight
    request OWNS one slot row for its lifetime; eviction is just
    returning the row to the free list — no copies, the next occupant's
    prefill overwrites it and the per-row causal mask hides any stale
    tail.

  * Batched prefill: an admission WAVE — the FIFO prefix of the queue
    sharing one prompt bucket, up to the free slots — runs the model
    once over a (k, L_bucket) prompt block, scatters the K/V rows into
    the wave's slot rows, and samples each request's first token from
    its TRUE last prompt position. k is padded up a power-of-two ladder
    (scheduler.admit_ladder) so the compile set stays bounded at
    len(admit_ladder) * len(buckets) programs.

  * Device-resident slot state: the per-slot decode operands
    (pos/tok/temp/top_k/top_p/seed/active) live in a donated on-device
    struct threaded through the decode step alongside the pool — the
    decode hot loop uploads NOTHING from the host. Admission and
    eviction mutate the struct through two small compiled programs
    (_admit_fn / _release_fn) instead of re-staging six host arrays
    every token.

  * Pipelined decode: step k+1 is dispatched from the device-resident
    token array of step k BEFORE step k's tokens are read back, so the
    per-token host round trip overlaps device compute instead of
    serializing with it (the same async-dispatch discipline
    train.estimate_loss applies to eval). Finish/eviction decisions
    therefore lag ONE step: a row that finished at step k still rides
    along in step k+1, and its ride-along token is dropped at readback
    via the dispatch-time (slot -> rid) snapshot — a backfilled slot's
    new occupant can never inherit it. On device the active mask parks
    finished/idle rows (pos frozen, token pinned) so their garbage
    stays inside their own slot row.

  * Sampling is per-row (_sample_token with (S,) parameter vectors) and
    per-row keyed: the token at position q of request r is sampled with
    fold_in(key(r.seed), q), so a request's output stream is a pure
    function of (params, prompt, settings, seed) — independent of which
    other requests happen to share its batch. That invariant is what
    makes continuous batching testable against single-request
    sample.generate token-for-token, and it survives pipelining because
    the device state the next step consumes is exactly the sampled
    token the host would have re-uploaded.

  * Speculative decoding (spec=...): a drafter (serve/drafters.py)
    guesses k tokens per slot and ONE fixed-shape verify program
    (serve/spec.py) scores all k+1 positions per row against the slot
    pool, accepting the longest target-agreed prefix plus one fresh
    token — up to k+1 tokens per forward instead of 1, outputs
    distributed exactly as non-spec decode (greedy: token-identical).
    Spec steps replace the decode dispatch and run SYNCHRONOUSLY: a
    host drafter needs the latest tokens to propose from, and the
    verify readback (accepted lengths) gates the next frontier, so
    the one-step pipeline lag has nothing to overlap.

The engine is single-threaded by design (one step() == at most one
decode dispatch + one lagged readback); http.py wraps it in a
background thread for concurrent clients.
"""

from __future__ import annotations

import itertools
import os
import tempfile
import threading
import time
from collections import deque
from dataclasses import dataclass, field, replace
from typing import Dict, List, Optional, Sequence, Tuple

import numpy as np

from nanosandbox_tpu.obs import (FlightRecorder, MetricRegistry, SLOLedger,
                                 SpanTracer, WatchdogPanel,
                                 validate_slo_class)
from nanosandbox_tpu.serve.brownout import BrownoutController
from nanosandbox_tpu.serve.faults import FaultInjected, FaultPlan
from nanosandbox_tpu.serve.scheduler import SlotScheduler, default_buckets
from nanosandbox_tpu.utils import tracecheck as _tracecheck
from nanosandbox_tpu.utils.tracecheck import TraceBudgetRegistry

# Scheduling priority by SLO class (ISSUE 13): higher admits first.
# Interactive traffic outranks the default class, which outranks batch;
# an explicit Request.priority overrides the class mapping, and unknown
# classes land on the default. The brownout ladder's shed floors
# (serve/brownout.py) are expressed against these same numbers.
PRIORITY_BY_CLASS = {"batch": 0, "default": 1, "interactive": 2}
DEFAULT_PRIORITY = 1


# Consecutive poisoned readbacks a row survives before it terminates
# 'failed' — the UNSUPERVISED backstop: with an EngineSupervisor the
# first poison triggers a recovery (fresh row state, counter gone), so
# the limit is only ever reached when nobody is recovering and the
# poison is persistent (bad checkpoint, broken device). Pre-PR-11 such
# a row terminated with garbage tokens; wedging the slot forever would
# be strictly worse.
POISON_STRIKE_LIMIT = 3


def single_device_params(params):
    """Weights for a single-chip (tp == 1) engine: committed to ONE
    device, with no mesh. Params restored from a checkpoint arrive on
    the Trainer's (data, fsdp, seq, model) mesh, and under the installed
    JAX that mesh is part of every array's TYPE: each program's outputs
    inherit it, so the mesh-free pool and slot state the engine builds
    would come back from their first dispatch re-typed and retrace every
    program once more — `python -m nanosandbox_tpu.serve` then died in
    warm-up with CompileBudgetExceeded. Mesh-free params (model.init)
    pass through untouched."""
    import jax
    from jax.sharding import NamedSharding, SingleDeviceSharding

    leaves = jax.tree.leaves(params)
    if not leaves or not isinstance(getattr(leaves[0], "sharding", None),
                                    NamedSharding):
        return params
    dev = min(leaves[0].sharding.device_set, key=lambda d: d.id)
    return jax.device_put(params, SingleDeviceSharding(dev))


class EngineFailedError(RuntimeError):
    """The engine escalated to permanent failure (recovery exhausted its
    attempts) and drained; submissions are refused until a restart. The
    HTTP layer maps this to 503 — clients should hit another replica."""


@dataclass(frozen=True)
class Request:
    """One generation request, in token-id space (the HTTP layer owns
    text <-> tokens). ``deadline_s`` is the submit-to-finish SLO budget
    (None = best-effort: never SLO-tracked, never shed); ``slo_class``
    labels the request's SLO accounting on /metrics; ``priority``
    orders the scheduler queue (higher first; defaulted from the class
    via PRIORITY_BY_CLASS) and decides who preempts whom."""
    rid: int
    prompt: tuple
    max_new_tokens: int
    temperature: float = 0.0
    top_k: int = 0
    top_p: float = 1.0
    seed: int = 0
    eos_id: Optional[int] = None
    deadline_s: Optional[float] = None
    slo_class: str = "default"
    priority: int = DEFAULT_PRIORITY


@dataclass
class Result:
    rid: int
    prompt: tuple
    tokens: List[int]          # generated ids (includes the eos hit, if any)
    finish_reason: str         # 'length' | 'eos' | 'shed' | 'failed'
    # Chained fingerprints of the prompt's full KV blocks DONATED to
    # this engine's radix cache at finish (paged.prefix_digests; empty
    # when the prefix cache is off or nothing was donated). The fleet
    # router's per-replica index ingests these — "replica r now holds
    # this chain" — which is what turns the radix cache into a
    # fleet-wide routing signal (ISSUE 15).
    prefix_digest: tuple = ()


@dataclass
class _Active:
    req: Request
    slot: int
    tokens: List[int] = field(default_factory=list)
    first_token_t: float = 0.0   # wall clock of the prefill-token readback
    submit_t: float = 0.0        # wall clock at submit (SLO end-to-end)
    last_t: float = 0.0          # wall clock of the last retired token
    spec_accepted: int = 0       # draft tokens this request accepted
    span: int = 0                # open "generate" span id (obs tracer)
    alloc: object = None         # paged.Allocation (block-paged engines)
    poison_strikes: int = 0      # consecutive poisoned readbacks (row
    #                              terminates 'failed' at the cap when
    #                              no supervisor recovers in between)


@dataclass
class _Resume:
    """Host-side stitch record for a request re-admitted after an
    engine recovery OR a priority preemption: the ORIGINAL prompt and
    the tokens generated before the interruption, so the terminal
    Result (and its flight/SLO accounting) reads as one uninterrupted
    request."""
    prompt: tuple
    tokens: List[int]
    submit_t: float


@dataclass
class _Chunking:
    """One request mid-chunked-prefill (ISSUE 13): popped from the
    queue with a slot claimed and ALL blocks reserved, its (suffix)
    prompt lands in the KV pool across several bucket-shaped prefill
    dispatches interleaved with decode steps. ``hit`` is the prefix-
    cache hit (the first chunk's cache_index); ``done`` counts suffix
    tokens already written. Intermediate chunks carry the sentinel slot
    id — no admit scatter, no readback — so only the FINAL chunk
    samples a first token and activates the row."""
    req: Request
    slot: int
    alloc: object
    hit: int
    done: int = 0


@dataclass
class _Export:
    """One request parked in MIGRATION LIMBO (ISSUE 16): its prefill
    completed on this engine — the whole prompt's K/V sits in
    ``alloc``'s block chain and ``first_tok`` was sampled with the
    fold_in(seed, true_len) key — but its decode belongs to another
    tier. The slot was released at export (the row must never decode
    here), so the record owns exactly {blocks, first token, request}:
    the migration wire format. Parked in the scheduler's limbo queue,
    where the deadline sweep sees it like any queued request; shed or
    aborted from limbo, its blocks free WITHOUT donation (the handoff
    never completed — the terminal says so, the cache must not claim
    otherwise... the chain IS fully written, but a shed request's
    blocks are freed not donated by the ISSUE 16 contract: nothing
    should warm a cache on traffic the engine refused to serve)."""
    req: Request
    alloc: object
    first_tok: int
    export_t: float              # wall clock at export (migration p50/p99)
    submit_t: float              # wall clock at submit (deadline budget)
    submit_step: int

    # drain_expired applies one predicate to queue items and limbo
    # records alike — forward the fields it reads.
    @property
    def rid(self) -> int:
        return self.req.rid

    @property
    def deadline_s(self) -> Optional[float]:
        return self.req.deadline_s

    @property
    def priority(self) -> int:
        return self.req.priority


@dataclass
class _Adoption:
    """The adopt-side handle between begin_adopt (slot + blocks
    reserved, nothing written) and commit_adopt (row activated) /
    abort_adopt (unwound). ``copy`` lists the chain positions whose
    blocks the caller must fill from the source pool before commit —
    ``dst_blocks`` are their block ids here."""
    req: Request
    slot: int
    alloc: object
    copy: List[int]

    @property
    def dst_blocks(self) -> List[int]:
        return [self.alloc.table[i] for i in self.copy]


class Engine:
    """submit() / step() / drain() continuous-batching engine.

    Parameters
    ----------
    model, params : the flax GPT and its (cast) params — exactly what
        sample.generate takes, so one checkpoint serves both paths.
    num_slots : concurrent request capacity (the decode batch).
    max_len : per-slot KV length; prompt + new tokens must fit. Capped
        at block_size (wpe defines no positions past it).
    prefill_buckets : padded prompt lengths to compile; default is the
        power-of-two ladder up to max_len.
    pipeline : keep one decode step in flight ahead of the host
        (default). False restores the synchronous PR-1 loop — dispatch,
        read back, repeat — which bench.py uses as the comparison
        baseline; results are identical either way, only the
        dispatch/readback overlap differs.
    spec : a drafter (serve/drafters.py NGramDrafter / ModelDrafter, or
        anything matching the host protocol) enabling speculative
        decoding: each "decode" step verifies k drafted tokens per slot
        in one fixed-shape forward instead of computing one. Forces the
        synchronous loop (see module docstring); greedy outputs are
        token-identical to spec=None, sampled outputs identically
        distributed.
    scan_k : decode steps fused into ONE compiled dispatch via lax.scan
        (default 1, the classic per-token loop). With scan_k = k the
        host dispatches once per k tokens — sample -> (paged) KV
        quantize-and-write through the block table -> frontier advance
        all stay in-program — so the per-dispatch host floor (~180 us
        per staging upload measured in PR 9) amortizes over k tokens.
        Finish detection lags up to k steps: a row hitting eos or its
        budget mid-chunk keeps riding the chunk on device, its overrun
        tokens truncate at readback, and its overrun KV writes land in
        its own private frontier positions (dense) or drop on the
        sentinel block-table entries past its reservation (paged) —
        the PR 2 lagged-retire argument stretched from lag-1 to lag-k.
        Composes with ``pipeline`` (one k-chunk in flight ahead of the
        host); forced to 1 under ``spec`` (the verify readback gates
        the next frontier — there is no chunk to fuse). Tradeoff:
        larger k = fewer dispatches, but more wasted lane work when
        rows finish mid-chunk and chunk-granular TTFT for backfilled
        requests (docs/playbook.md has the k-vs-lag table). Greedy
        outputs are token-identical to scan_k=1 (pinned by test).
    metrics : obs.MetricRegistry to publish on (default: a fresh
        per-engine registry — tests spin up many engines). Counters and
        gauges are mirrored from the engine's plain ints by a
        collection-time callback, so the hot loop never touches them;
        only the latency histograms observe per event.
    tracer : obs.SpanTracer recording the span timeline (prefill waves,
        decode steps with the pipelined one-step-lag retire, spec verify
        rounds, per-request queued/generate). Default: a fresh bounded
        tracer; records only already-host-resident ints/floats, so it
        adds no host sync.
    kv_dtype : KV-pool storage mode ('fp32' | 'bf16' | 'int8'; default
        None = the model's compute dtype, the pre-int8 behavior).
        'int8' stores per-(slot, head, position) scales alongside the
        values (models/gpt.py init_cache): ~2x less HBM per cached
        token than bf16 — 2x the slots at constant HBM — and
        proportionally less decode read traffic. Applies to the
        drafter's pool too (spec verify and drafts read the same mode).
    decode_impl : cached-decode attention impl for the T=1 hot path
        ('auto' | 'pallas' | 'pallas_interpret' | 'xla',
        ops/flash_decode.py ladder). Default None keeps the model
        config's own setting. The RESOLVED impl (auto settles on
        pallas or xla at construction, with a warn_once when a TPU
        lands on the fallback) is exported as the
        serve_decode_attention_impl gauge and in stats().
    paged : block-paged KV pool (default True, the ROADMAP-2 layout):
        the pool is a global heap of kv_pool_blocks fixed-size blocks
        of kv_page_size positions, a device-resident (num_slots,
        max_blocks) block table maps each slot's positions onto blocks,
        and admission reserves each request's ACTUAL need
        (ceil((prompt + max_new) / page) blocks) instead of a dense
        worst-case (max_len) row — elastic memory at constant pool
        bytes, plus prefix reuse (below). False restores the dense
        per-slot rows (the PR 8 layout), kept as the bench comparison
        baseline. Same compile set either way: the block table is
        DATA, not shape, so max_programs() is identical.
    kv_page_size : positions per KV block (paged only; must divide
        max_len). Small pages waste less memory on final-block
        fragmentation and shorten shareable-prefix granularity; large
        pages cut table overhead and DMA count. Every kv mode's
        kernels compile for v5e at pages 4..256 (head_dim 64 and 128;
        tests/test_chip_compile.py holds 16 and 32).
    kv_pool_blocks : pool size in blocks (paged only; default
        num_slots * max_len / page — byte-identical to the dense
        pool, so paged-vs-dense comparisons hold pool HBM constant
        while capacity becomes elastic).
    prefix_cache : radix/trie prefix reuse over finished requests'
        prompt blocks (paged only, default True): a request whose
        prompt prefix is resident skips those prefill chunks entirely
        — admission prefills only the (bucketed) suffix — with
        refcounted copy-on-write block sharing and LRU eviction of
        refcount-zero blocks (serve/paged.py).
    flight : obs.FlightRecorder for the per-request lifecycle ledger
        (default: a fresh bounded recorder). Records submit -> queue ->
        block-reserve/stall -> admit -> prefill[hit|miss] -> retire* ->
        evict -> finish|reject|shed, from already-host-resident
        dispatch-time state only — no host sync, < 50 us/event (pinned).
        Serves GET /debug/requests and the watchdog dumps.
    watchdogs / watchdog_dir : anomaly watchdogs (obs.WatchdogPanel:
        TTFT spike, admission stall, pool thrash, post-steady retrace,
        stuck slot). A trip counts on watchdog_trips_total{kind=} and
        snapshots flight + span ring + stats() into watchdog_dir
        (default: a tempdir created on the first trip).
    default_deadline_s : deadline applied to requests that submit none
        (None = best-effort). A queued request whose deadline expires
        before admission is SHED — a terminal 'shed' Result instead of
        burning a slot on an answer its client stopped waiting for —
        and every deadline-carrying request lands in the SLO ledger
        (attainment, goodput tokens, deadline margin) on /metrics.
    faults : a serve.faults.FaultPlan injecting deterministic failures
        at named hot-path sites (nan_logits, slow_step, alloc_fail,
        drafter_fault, scatter_corrupt, prefill_exc) — chaos testing
        and the recovery subsystem's test bench. None (the default)
        reduces every site to one `is None` branch: production pays
        nothing, and the compile set / host-sync ledger are identical
        with and without the hook (pinned by test).
    spec_fault_tolerance : consecutive drafter faults absorbed (each
        degrades that step to plain decode) before speculative decoding
        auto-DISABLES for the engine's lifetime — degrade, don't die:
        a dead drafter costs throughput, never correctness or uptime.
    prefill_chunk : per-STEP prefill token budget (ISSUE 13; None = the
        classic admit-everything-now behavior). Must be one of the
        prefill buckets. Each engine step spends at most ~this many
        prefill tokens before dispatching its decode step, so a
        prefill storm interleaves with decode instead of stalling every
        active row's TPOT for the whole wave. Paged engines
        additionally SPLIT a single long (suffix) prompt into
        chunk-sized pieces across steps — each chunk is an ordinary
        (1, bucket) prefill dispatch writing at cache_index = tokens-
        already-prefilled, exactly the prefix-hit machinery, so the
        compile set does not widen (max_programs() identical, pinned).
        Dense engines cannot split one prompt (their prefill has no
        write offset) and fall back to pacing whole waves.
    preemption : allow deadline-driven preemption-by-eviction (default
        True): when the highest-priority queued request would miss its
        deadline waiting on slots or KV blocks, the lowest-priority
        active victim is evicted — its blocks (prompt AND generated)
        are donated to the radix cache and it requeues with prompt' =
        prompt + tokens-so-far through the recovery _Resume path, so
        its resume is a prefix hit and greedy output is token-identical
        to an unpreempted run (pinned). Equal-priority traffic never
        preempts, so single-class deployments behave exactly as before.
    brownout : attach a BrownoutController (serve/brownout.py): an
        SLO-ledger-driven ladder of named degradation levels (shrink
        scan chunk -> suspend spec -> shed batch class -> interactive
        only) with hysteresis, each transition a flight/metrics event.
        Default False; `python -m nanosandbox_tpu.serve` turns it on.
    tp : tensor-parallel degree (default 1 = today's single-chip
        engine, bit-for-bit unchanged). tp > 1 shards ONE engine over
        a (1, 1, 1, tp) mesh on the first tp devices: weights via the
        Megatron placements in parallel/sharding.py (column-parallel
        c_attn/c_fc, row-parallel c_proj), the KV pool — paged block
        heap or dense slot rows — and its per-position scale planes
        row-sharded along the HEADS dim over the ``model`` axis, and
        the per-slot frontier/slot state replicated (it is O(slots)
        ints; the bytes live in the pool). Decode/prefill/scan/verify
        all ride with_sharding_constraint anchors (models/gpt.py) so
        the only collectives are the bounded per-block activation
        exchanges — one model-axis all-reduce per block plus the qkv
        head resharding — never a full-pool all-gather; the committed
        budgets/serve_tp_cpu8.json pins exactly that contract in CI.
        Greedy outputs are token-identical to tp=1 (same keys, same
        per-row math; collectives are deterministic — pinned by test),
        the compile set does not widen, and recovery/preemption
        rebuild the SHARDED placements. Requires n_head % tp == 0.
        Flash kernels run per-shard over local heads via shard_map;
        the gather-free XLA paths partition under the same anchors.
    tp_mesh : an explicit mesh to shard over instead of the default
        (1, 1, 1, tp) slice — shardcheck's fleet lowers the tp=2
        engine under the full cpu8 mesh this way. Its ``model`` axis
        size must equal ``tp``.
    """

    def __init__(self, model, params, *, num_slots: int = 8,
                 max_len: Optional[int] = None,
                 prefill_buckets: Optional[Sequence[int]] = None,
                 pipeline: bool = True, spec=None, scan_k: int = 1,
                 metrics: Optional[MetricRegistry] = None,
                 tracer: Optional[SpanTracer] = None,
                 kv_dtype: Optional[str] = None,
                 decode_impl: Optional[str] = None,
                 paged: bool = True, kv_page_size: int = 16,
                 kv_pool_blocks: Optional[int] = None,
                 prefix_cache: bool = True,
                 flight: Optional[FlightRecorder] = None,
                 watchdogs: bool = True,
                 watchdog_dir: Optional[str] = None,
                 default_deadline_s: Optional[float] = None,
                 faults: Optional[FaultPlan] = None,
                 spec_fault_tolerance: int = 3,
                 prefill_chunk: Optional[int] = None,
                 preemption: bool = True,
                 brownout: bool = False,
                 tp: int = 1, tp_mesh=None,
                 role: str = "both"):
        import jax
        import jax.numpy as jnp

        from nanosandbox_tpu.models.gpt import (init_cache,
                                                init_paged_cache,
                                                normalize_kv_dtype)
        from nanosandbox_tpu.ops.flash_decode import resolve_decode_impl
        from nanosandbox_tpu.serve.paged import BlockPool

        if decode_impl is not None and decode_impl != model.cfg.decode_impl:
            # Rebind the module with the requested decode impl; params
            # are impl-independent, so the same tree serves the rebuilt
            # module (the same move sample.py relies on for dtype casts).
            model = type(model)(
                cfg=model.cfg.replace(decode_impl=decode_impl),
                mesh=getattr(model, "mesh", None))
        cfg = model.cfg
        # Tensor-parallel setup (tp > 1): build/validate the mesh, bind
        # it onto the model (the with_sharding_constraint anchors in
        # models/gpt.py key off it), and commit the weights to their
        # Megatron placements. Pool/state placement happens below where
        # those arrays are built; tp == 1 takes none of these branches.
        self.tp = int(tp)
        self._mesh = None
        self._rep = None
        if self.tp < 1:
            raise ValueError(f"tp must be >= 1, got {tp}")
        if self.tp > 1:
            from jax.sharding import NamedSharding, PartitionSpec
            from nanosandbox_tpu.parallel.mesh import (axis_sizes,
                                                       make_mesh)
            from nanosandbox_tpu.parallel.sharding import param_shardings

            if cfg.n_head % self.tp:
                raise ValueError(
                    f"tp={self.tp} must divide n_head={cfg.n_head}: the "
                    "KV pool shards along the heads dim")
            if tp_mesh is not None:
                mesh = tp_mesh
                if axis_sizes(mesh).get("model", 1) != self.tp:
                    raise ValueError(
                        f"tp_mesh model axis is {axis_sizes(mesh)} but "
                        f"tp={self.tp}")
            else:
                devs = jax.devices()
                if len(devs) < self.tp:
                    raise ValueError(
                        f"tp={self.tp} needs {self.tp} devices, have "
                        f"{len(devs)}")
                mesh = make_mesh(1, 1, self.tp, 1,
                                 devices=devs[:self.tp])
            self._mesh = mesh
            self._rep = NamedSharding(mesh, PartitionSpec())
            model = type(model)(cfg=cfg, mesh=mesh)
            params = jax.device_put(
                params,
                param_shardings(mesh, jax.eval_shape(lambda: params),
                                shard_params=False, tp=True))
        else:
            # The single-chip engine owns no mesh: not in its weights'
            # type, and not bound onto the model either (a Trainer's
            # model carries the training mesh, whose activation anchors
            # would pin a multi-device mesh into a one-device program).
            params = single_device_params(params)
            if getattr(model, "mesh", None) is not None:
                model = type(model)(cfg=cfg)
        self.kv_dtype = normalize_kv_dtype(kv_dtype) or (
            "bf16" if cfg.compute_dtype == "bfloat16" else "fp32")
        # Resolve ONCE at construction: 'auto' is the Pallas kernel on a
        # tpu backend and XLA elsewhere — never a probed fallback; a
        # kernel the compiler refuses fails the warm-up's first compile.
        self.decode_impl = resolve_decode_impl(cfg.decode_impl)
        self.model = model
        self.params = params
        self.cfg = cfg
        self.num_slots = num_slots
        # Spec steps must read accepted lengths back before the next
        # dispatch (and host drafters propose from the latest tokens),
        # so speculative mode runs the synchronous loop.
        self.pipeline = bool(pipeline) and spec is None
        if scan_k < 1:
            raise ValueError(f"scan_k must be >= 1, got {scan_k}")
        # scan_k composes with the pipeline, not with verify: a spec
        # step's readback gates the next frontier, so under spec the
        # chunk length collapses to 1 (the sync loop).
        self.scan_k = 1 if spec is not None else int(scan_k)
        # The scan-chunk rung ladder: power-of-two chunk lengths up to
        # scan_k (plus scan_k itself when off the ladder), one compiled
        # megaprogram per rung. Each dispatch picks the largest rung no
        # live row's remaining budget overruns, so a row one token from
        # its budget pulls the chunk down to what everyone can use
        # instead of riding 7 wasted lane-steps — budget overrun waste
        # is structurally zero (only eos still overruns, and eos is
        # host knowledge by design). The ladder is the compile-set
        # growth the budgets pin: len(scan_rungs) decode programs.
        self.scan_rungs = [1]
        r = 2
        while r < self.scan_k:
            self.scan_rungs.append(r)
            r *= 2
        if self.scan_k > 1:
            self.scan_rungs.append(self.scan_k)
        self.max_len = min(max_len or cfg.block_size, cfg.block_size)
        buckets = (sorted(b for b in prefill_buckets if b <= self.max_len)
                   if prefill_buckets else default_buckets(self.max_len))
        if not buckets:
            raise ValueError("no prefill bucket fits within max_len "
                             f"{self.max_len}: {prefill_buckets!r}")
        self.sched = SlotScheduler(num_slots, buckets)
        self.admit_buckets = self.sched.admit_buckets
        # Chunked prefill (ISSUE 13): the per-step prefill token budget.
        # The chunk must be a BUCKET so chunk dispatches reuse the
        # existing (rung, bucket) prefill grid — any other size would
        # either widen the compile set or pad every chunk.
        if prefill_chunk is not None:
            prefill_chunk = int(prefill_chunk)
            if prefill_chunk not in self.sched.buckets:
                raise ValueError(
                    f"prefill_chunk {prefill_chunk} must be one of the "
                    f"prefill buckets {self.sched.buckets} (chunk "
                    "dispatches reuse the bucket-shaped programs)")
        self.prefill_chunk = prefill_chunk
        self.preemption = bool(preemption)
        self.preemptions = 0
        self._prefill_spent = 0              # tokens this step (budgeted)
        self._chunking: List[_Chunking] = []  # mid-chunked-prefill lane
        # Brownout knobs (set by the controller; consulted on the hot
        # path as one attribute read each — see serve/brownout.py).
        self.scan_cap: Optional[int] = None
        self.spec_suspended = False
        self.brownout_min_priority: Optional[int] = None

        if self.decode_impl != "xla":
            from nanosandbox_tpu.ops.flash_decode import (decode_pad_copies,
                                                          paged_pad_copies)
            from nanosandbox_tpu.utils.metrics import warn_once

            pad = (paged_pad_copies(kv_page_size, cfg.n_embd // cfg.n_head)
                   if paged else
                   decode_pad_copies(self.max_len, cfg.n_embd // cfg.n_head))
            if pad:
                # The kernel would jnp.pad — copy — the whole pool
                # inside EVERY decode step, erasing the bytes the
                # kernel/int8 exist to save. Loud beats silent.
                warn_once(
                    f"flash-decode-pad-copy-{self.max_len}",
                    f"[serve] max_len={self.max_len} (head_dim "
                    f"{cfg.n_embd // cfg.n_head}) forces the flash-decode "
                    "kernel to pad-copy the KV pool on every step — use a "
                    "multiple of 32 (and head_dim 64 or a 128-multiple) "
                    "to keep the decode read zero-copy.")
        self.paged = bool(paged)
        self.kv_page_size = int(kv_page_size) if self.paged else 0
        self.block_pool = None
        if self.paged:
            if kv_page_size < 1:
                raise ValueError(
                    f"kv_page_size must be >= 1, got {kv_page_size}")
            # ceil: a max_len off the page quantum just leaves the last
            # block of a full-length request partially used.
            self.slot_blocks = -(-self.max_len // kv_page_size)
            self.kv_pool_blocks = int(kv_pool_blocks
                                      or num_slots * self.slot_blocks)
            self._pool = self._place_pool(
                init_paged_cache(cfg, self.kv_pool_blocks, kv_page_size,
                                 kv_dtype=kv_dtype))
            self.block_pool = BlockPool(self.kv_pool_blocks, kv_page_size,
                                        prefix_cache=prefix_cache)
        else:
            self.slot_blocks = 0
            self.kv_pool_blocks = 0
            self._pool = self._place_pool(
                init_cache(cfg, num_slots, self.max_len,
                           kv_dtype=kv_dtype))
        # The kv_dtype ARGUMENT (not the resolved mode): recover() must
        # rebuild the pool with exactly the constructor's layout.
        self._kv_dtype_arg = kv_dtype
        # Device-resident per-slot decode operands. Idle rows keep
        # harmless parked values (pos 0, temperature 0, active False):
        # their garbage decode writes stay inside their own slot row —
        # paged engines park the block-table row on the out-of-range
        # sentinel (kv_pool_blocks) instead, so an idle row's garbage
        # writes DROP rather than touch a block it no longer owns.
        self._state = self._fresh_slot_state()

        self._active: Dict[int, _Active] = {}        # slot -> state
        self._pending_results: List[Result] = []     # max_new_tokens == 0
        # The one decode step/chunk in flight ahead of the host:
        # (device token array — (S,) single-step or (k, S) chunk,
        # {slot: rid} snapshot at dispatch, open decode_step span id,
        # the dispatch's step number = the scan-chunk index the flight
        # retire events carry, and the chunk length the next rung
        # choice subtracts). The snapshot is the host half of the
        # eviction lag — a slot whose occupant changed between dispatch
        # and readback drops its ride-along tokens. The span closes at
        # RETIRE, so the exported timeline shows chunk k overlapping
        # chunk k+1's dispatch — the pipeline's true shape.
        self._inflight: Optional[
            Tuple[object, Dict[int, int], int, int, int]] = None
        self._rid = itertools.count()
        # rid -> (submit step, submit wall clock, open "queued" span id)
        self._submit_meta: Dict[int, Tuple[int, float, int]] = {}
        self.steps = 0
        self.admitted = 0
        self.completed = 0
        self.tokens_generated = 0
        # Host-dispatch ledger (ISSUE 12): every compiled-program launch
        # the engine performs, by program kind — the denominator of the
        # dispatch-floor story scan_k attacks. Plain ints on the hot
        # path, mirrored into labeled counters at collection time.
        self.host_dispatches: Dict[str, int] = {
            "decode": 0, "prefill": 0, "admit": 0, "release": 0,
            "verify": 0}
        self.shed = 0                                # deadline-expired drops
        # Deadline-carrying sheds CAUSED BY the brownout floor (subset
        # of the SLO ledger's shed count): the controller subtracts
        # these from its window signal — its own shedding must read as
        # load REMOVED, not as ongoing burn, or level 3 would sustain
        # itself on the traffic it sheds and never clear.
        self.brownout_sheds = 0
        self.rejected: Dict[str, int] = {}           # submit rejects, by kind
        # Fault-injection + crash-safe recovery state (ISSUE 11). The
        # hooks cost one `is None` branch each when no plan is attached;
        # recovery bookkeeping is cold-path only.
        self.faults = faults
        if faults is not None:
            faults.arm(0)
        self.spec_fault_tolerance = int(spec_fault_tolerance)
        self.quarantined = False
        self.quarantine_cause: Optional[str] = None
        self.failed = False
        self.recoveries = 0
        self.poisoned_steps = 0
        self.requeued = 0
        self.drafter_faults = 0
        self.spec_disabled_reason: Optional[str] = None
        self._drafter_fault_streak = 0
        self._poison: Optional[dict] = None
        # The wave currently mid-prefill: (req, slot, alloc) triples,
        # populated between the queue pop and the admission commit so a
        # prefill-dispatch crash leaves recover() enough to requeue.
        # _admitting_span is the wave's open tracer span, ended by
        # recover()/abort_all() when a crash skips the normal close.
        self._admitting: List[Tuple] = []
        self._admitting_span: Optional[int] = None
        self._resumed: Dict[int, _Resume] = {}
        # Disaggregated serving (ISSUE 16). ``role`` labels the tier
        # this engine plays ("prefill" runs chunked waves and exports,
        # "decode" adopts migrated chains, "both" is the classic
        # colocated engine — the role is telemetry + fleet routing
        # metadata, never a capability gate: a prefill engine that must
        # fall back to colocated decode, e.g. when its decode tier
        # died, still can). ``_migrate_rids`` marks requests submitted
        # with migrate=True: they allocate prompt-only block footprints
        # and EXPORT at the first-token readback instead of going
        # active. ``migrated``/``adopted`` count handoffs out of / into
        # this engine.
        if role not in ("both", "prefill", "decode"):
            raise ValueError(
                f"role must be 'both', 'prefill' or 'decode', got "
                f"{role!r}")
        self.role = role
        self._migrate_rids: set = set()
        self.migrated = 0
        self.adopted = 0
        if default_deadline_s is not None and default_deadline_s <= 0:
            raise ValueError(f"default_deadline_s must be > 0, got "
                             f"{default_deadline_s}")
        self.default_deadline_s = default_deadline_s
        # Telemetry spine (nanosandbox_tpu/obs): the latency signal
        # lives in registry histograms (RingStat window + Prometheus
        # buckets — /stats and /metrics read the SAME series), counters
        # and gauges mirror the engine's plain ints at collection time
        # (zero hot-loop cost), and the tracer records the span
        # timeline /trace exports.
        self.metrics = metrics if metrics is not None else MetricRegistry()
        self.tracer = tracer if tracer is not None else SpanTracer()
        m = self.metrics
        # One engine per registry: re-registration would hand BOTH
        # engines the same unlabeled families, and their collectors
        # would silently overwrite each other's mirrored counters at
        # every scrape. Loud beats last-writer-wins.
        if any(f.name == "serve_ttft_seconds" for f in m.families()):
            raise ValueError(
                "metrics registry already hosts an Engine's families; "
                "give each Engine its own MetricRegistry")
        self._ttft = m.histogram(
            "serve_ttft_seconds", "Submit -> first-token seconds.",
            unit="seconds")
        self._tpot = m.histogram(
            "serve_tpot_seconds", "Per-token seconds after the first.",
            unit="seconds")
        self._queue_wait = m.histogram(
            "serve_queue_wait_steps",
            "Decode steps a request spent queued before admission.",
            unit="steps", buckets=(0, 1, 2, 4, 8, 16, 32, 64, 128))
        self._c_submitted = m.counter(
            "serve_requests_submitted_total", "Requests accepted by submit().")
        self._c_completed = m.counter(
            "serve_requests_completed_total",
            "Requests finished, by finish reason.", labelnames=("reason",))
        self._c_waves = m.counter(
            "serve_prefill_waves_total", "Batched prefill admission waves.")
        self._c_tokens = m.counter(
            "serve_tokens_generated_total", "Generated tokens read back.")
        self._c_steps = m.counter(
            "serve_decode_steps_total",
            "Batched decode/verify step dispatches.")
        # Dispatch-floor observability (ISSUE 12): how many compiled-
        # program launches the host performs per kind, and how many
        # tokens each decode dispatch amortizes (scan_k's win, live).
        self._c_dispatches = m.counter(
            "serve_host_dispatches_total",
            "Compiled-program dispatches from the engine loop, by "
            "program kind.", labelnames=("kind",))
        self._g_toks_per_dispatch = m.gauge(
            "serve_tokens_per_dispatch",
            "Generated tokens per decode dispatch over the engine "
            "lifetime (== scan_k when every chunk retires fully).")
        self._c_admitted = m.counter(
            "serve_requests_admitted_total", "Requests admitted to slots.")
        self._c_traces = m.counter(
            "serve_compile_traces_total",
            "Observed jit traces of this engine's programs, by kind.",
            labelnames=("program",))
        self._g_active = m.gauge("serve_slots_active",
                                 "Slots owned by in-flight requests.")
        self._g_free = m.gauge("serve_slots_free", "Free KV-pool slots.")
        self._g_queued = m.gauge("serve_queue_depth",
                                 "Requests queued awaiting admission.")
        self._g_rate = m.gauge(
            "serve_decode_tokens_per_sec",
            "Generated tokens/sec over the recent readback window.")
        # The RESOLVED decode-attention impl and KV storage mode, as
        # 1-hot labeled gauges: a scrape can tell whether this engine is
        # on the flash kernel or silently landed on the xla fallback
        # (the warn_once above fires once; the gauge persists).
        self._g_impl = m.gauge(
            "serve_decode_attention_impl",
            "Resolved cached-decode attention impl (1 = active).",
            labelnames=("impl",))
        self._g_kv = m.gauge(
            "serve_kv_dtype", "KV-pool storage mode (1 = active).",
            labelnames=("kv_dtype",))
        # Tensor-parallel posture (ISSUE 14): the model-axis shard
        # count this engine decodes across (1 = single chip).
        self._g_tp = m.gauge(
            "serve_tp_degree",
            "Tensor-parallel degree of the decode engine (model-axis "
            "shards; 1 = single chip).")
        # Disaggregated-serving posture (ISSUE 16): which tier this
        # engine serves (1-hot), plus its sides of the migration flow.
        self._g_role = m.gauge(
            "serve_engine_role",
            "Serving tier of this engine (1 = active role).",
            labelnames=("role",))
        self._g_limbo = m.gauge(
            "serve_migration_limbo_depth",
            "Exports parked awaiting adoption by the decode tier.")
        self._c_migrated = m.counter(
            "serve_migrated_out_total",
            "Requests this engine prefilled and handed to another "
            "tier (terminal accounting moves with them).")
        self._c_adopted = m.counter(
            "serve_adopted_in_total",
            "Migrated requests this engine re-admitted as pure prefix "
            "hits (zero prefill dispatches).")
        # Paged-pool + prefix-cache signal (ISSUE 9): block states
        # partition the pool, the hit/miss token counters are the
        # prefix_hit_rate numerator/denominator, and TTFT re-observes
        # into a by-prefix-outcome labeled histogram so the hit-vs-miss
        # latency cut is a first-class /metrics series, not a bench-only
        # artifact. All mirrored/observed host-side — zero hot-loop cost.
        self._g_pool_blocks = m.gauge(
            "serve_kv_pool_blocks",
            "Paged KV pool blocks by state (free | live | cached).",
            labelnames=("state",))
        self._c_prefix_hit = m.counter(
            "serve_prefix_hit_tokens_total",
            "Prompt tokens skipped via radix prefix-cache hits.")
        self._c_prefix_miss = m.counter(
            "serve_prefix_miss_tokens_total",
            "Prompt tokens prefilled from scratch.")
        self._c_block_stalls = m.counter(
            "serve_admission_block_stall_steps_total",
            "Admission attempts deferred on KV-block availability "
            "(the no-deadlock backpressure: the request stays queued).")
        self._ttft_prefix = m.histogram(
            "serve_prefix_ttft_seconds",
            "Submit -> first-token seconds by prefix-cache outcome.",
            unit="seconds", labelnames=("prefix",))
        # Overload/SLO observability (ISSUE 10): submit-time rejects and
        # deadline sheds as mirrored counters, the SLO ledger (per-class
        # attainment / goodput / deadline margins) on the same registry,
        # the per-request flight recorder, and the anomaly watchdogs.
        # Label children appear only when the events actually happen —
        # a deadline-less deployment scrapes no placeholder SLO series.
        self._c_rejected = m.counter(
            "serve_requests_rejected_total",
            "Requests rejected at submit, by reason.",
            labelnames=("reason",))
        self._c_shed = m.counter(
            "serve_requests_shed_total",
            "Queued requests shed after their deadline expired.")
        # Scheduling-endgame signal (ISSUE 13): preemption-by-eviction
        # events, mirrored from a plain int at collection time.
        self._c_preempted = m.counter(
            "serve_preemptions_total",
            "Active requests preempted (evicted + requeued) so a "
            "higher-priority deadline could admit.")
        # Crash-safe recovery signal (ISSUE 11): recovery cycles by
        # cause, rebuild latency, poisoned steps caught by the in-
        # program isfinite guard, re-admissions, drafter faults, and a
        # quarantine gauge readiness probes can alert on. Counters with
        # labels mint children only when the event happens (hygiene);
        # all are cold-path — a recovery is already an outage moment.
        self._c_recoveries = m.counter(
            "serve_engine_recoveries_total",
            "Engine quarantine -> rebuild -> re-admit cycles, by cause.",
            labelnames=("cause",))
        self._h_recovery = m.histogram(
            "serve_engine_recovery_seconds",
            "Quarantine -> device state rebuilt and victims requeued.",
            unit="seconds",
            buckets=(0.001, 0.005, 0.02, 0.1, 0.5, 2.0, 10.0))
        self._c_poisoned = m.counter(
            "serve_poisoned_steps_total",
            "Steps whose readback carried poisoned (non-finite-logit "
            "or out-of-vocab) tokens.")
        self._c_requeued = m.counter(
            "serve_requests_requeued_total",
            "In-flight requests re-admitted after an engine recovery.")
        self._c_drafter_faults = m.counter(
            "serve_spec_drafter_faults_total",
            "Drafter faults absorbed (the step degraded to plain "
            "decode).")
        self._g_quarantined = m.gauge(
            "serve_engine_quarantined",
            "1 while the engine is quarantined for recovery, else 0.")
        self.slo = SLOLedger(m)
        self.flight = flight if flight is not None else FlightRecorder()
        self.watchdog = WatchdogPanel(self, dump_dir=watchdog_dir,
                                      enabled=watchdogs)
        m.add_collector(self._collect_metrics)
        self._rate_ring: deque = deque(maxlen=256)   # (t, tokens read back)
        # On-demand jax.profiler window (POST /profile): requested from
        # an HTTP handler thread, opened/advanced/closed by the one
        # engine-stepping thread inside step().
        self._profile_lock = threading.Lock()
        self._profile: Optional[dict] = None
        self.last_profile: Optional[dict] = None
        # Retrace budgets (utils.tracecheck): jax calls each guarded
        # body once per TRACE, so a shape leak (e.g. a Python scalar
        # specializing a trace) raises CompileBudgetExceeded at the
        # retrace instead of becoming a silent 10x serving slowdown.
        # Per-engine registry — tests spin up many engines.
        self.tracecheck = TraceBudgetRegistry()

        # CPU jit ignores donation (and warns); only donate pool/state on
        # accelerators, where reusing the buffers in place matters.
        on_accel = jax.default_backend() != "cpu"

        # Speculative layer: built before max_programs() so the verify
        # (and any ModelDrafter draft/draft_prefill) budgets join the
        # published compile set the guards enforce.
        self._spec = None
        if spec is not None:
            from nanosandbox_tpu.serve.spec import SpecRunner

            if self.tp > 1 and getattr(spec, "kind", "host") == "device":
                # A device drafter owns its OWN model + KV pool; running
                # it under TP means sharding that second model too —
                # future work. Host drafters (NGram prompt lookup) ride
                # TP today: the verify program is the target model's and
                # shards like every other cached path.
                raise ValueError(
                    "tp > 1 supports host drafters only (e.g. "
                    "NGramDrafter); a tensor-parallel ModelDrafter "
                    "needs its own sharded pool")

            self._spec = SpecRunner(
                spec, model=model, num_slots=num_slots,
                max_len=self.max_len,
                n_prefill_programs=(len(self.sched.buckets)
                                    * len(self.admit_buckets)),
                registry=self.tracecheck, on_accel=on_accel,
                kv_dtype=kv_dtype, decode_impl=cfg.decode_impl,
                paged=self.paged, kv_page_size=kv_page_size,
                kv_pool_blocks=self.kv_pool_blocks)
        # Acceptance observability (windowed histograms, like the
        # latency signal): per-verify-row accepted lengths and
        # per-request accepted-token totals.
        self._spec_accept_len = m.histogram(
            "serve_spec_accept_len",
            "Accepted draft length per drafting verify row.",
            unit="tokens", buckets=(0, 1, 2, 3, 4, 6, 8, 12, 16),
            window=4096)
        self._spec_req_accepted = m.histogram(
            "serve_spec_req_accepted_tokens",
            "Draft tokens accepted over one request's lifetime.",
            unit="tokens", buckets=(0, 1, 2, 4, 8, 16, 32, 64, 128, 256))
        if self._spec is not None:
            self._spec.register_metrics(m)

        budget = self.max_programs()
        guard = self.tracecheck.guard
        # One prefill body per pool layout, published under ONE program
        # name and budget: the paged variant swaps the temp-cache
        # scatter for gather-prefix / suffix-forward / scatter-back, but
        # its shape key is the same (rung, bucket) grid — the bucketed
        # SUFFIX length, which without prefix hits IS the prompt bucket.
        prefill_body = (self._prefill_paged_fn if self.paged
                        else self._prefill_fn)
        self._prefill = jax.jit(
            guard("prefill", budget["prefill"])(prefill_body),
            donate_argnums=(1,) if on_accel else ())
        # The chunk length k is STATIC (the scan_rungs ladder): each
        # rung traces once under the one guarded name, so the decode
        # budget is exactly len(scan_rungs) and a rung outside the
        # ladder raises at the retrace, not as a silent program leak.
        self._decode = jax.jit(
            guard("decode", budget["decode"])(self._decode_fn),
            donate_argnums=(1, 2) if on_accel else (),
            static_argnums=(3,))
        self._admit = jax.jit(
            guard("admit", budget["admit"])(self._admit_fn),
            donate_argnums=(0,) if on_accel else ())
        self._release = jax.jit(
            guard("release", budget["release"])(self._release_fn),
            donate_argnums=(0,) if on_accel else ())

        # The brownout ladder (ISSUE 13): constructed last so the
        # controller sees the finished engine (slo ledger, metrics,
        # scan rungs all in place).
        self.brownout = BrownoutController(self) if brownout else None

    # ------------------------------------------------------------------
    # compiled step functions
    # ------------------------------------------------------------------
    # Wave-staging layout: the host packs a wave's per-row operands into
    # THREE uploads instead of nine — on the dispatch-bound CPU serving
    # floor each host->device staging array costs ~180us, so the packing
    # is a measurable slice of every admission wave (and it keeps the
    # paged and dense upload counts identical, which the paged-vs-dense
    # bench comparison relies on):
    #   prompts (k, L_bucket) int32 — the (suffix-)token block;
    #   meta    (k, meta_width) int32 — paged: [table row (slot_blocks)
    #           | slot | true_len | top_k | seed | hit_len]; dense:
    #           [slot | true_len | top_k | seed];
    #   fmeta   (k, 2) float32 — [temperature, top_p].
    # meta_width is a per-RUNG constant, so the admit program (which
    # consumes meta/fmeta plus the device-resident first tokens) keeps
    # its one-program-per-rung budget.
    @property
    def _meta_width(self) -> int:
        return (self.slot_blocks + 5) if self.paged else 4

    def _place_pool(self, pool: list) -> list:
        """Commit a freshly-built KV pool to its tensor-parallel
        placement — values AND scale planes row-sharded along the heads
        dim over the ``model`` axis (paged (N, H, page, D) and dense
        (S, H, L, D) both carry heads at dim 1). Identity at tp == 1.
        Construction and the recovery rebuild both come through here,
        so a recovered engine's placements match a fresh one's."""
        if self._mesh is None:
            return pool
        import jax
        from jax.sharding import NamedSharding, PartitionSpec as P

        val = NamedSharding(self._mesh, P(None, "model", None, None))
        sc = NamedSharding(self._mesh, P(None, "model", None))
        out = []
        for layer in pool:
            placed = (jax.device_put(layer[0], val),
                      jax.device_put(layer[1], val))
            if len(layer) == 4:
                placed += (jax.device_put(layer[2], sc),
                           jax.device_put(layer[3], sc))
            out.append(placed)
        return out

    def _stage(self, x):
        """Host->device staging for wave operands. Under TP the upload
        is an explicit replicated device_put (one copy per mesh device
        — these are O(wave) int32 rows, not pool bytes); tp == 1 keeps
        the plain single-device transfer."""
        import jax
        import jax.numpy as jnp

        if self._mesh is None:
            return jnp.asarray(x)
        return jax.device_put(x, self._rep)

    def _fresh_slot_state(self) -> dict:
        """A fully-parked device slot-state dict — construction AND the
        recovery rebuild use the same one, so a recovered engine starts
        from exactly the state a fresh one would. Under TP the struct
        is REPLICATED over the mesh (O(slots) ints — the sharded bytes
        are the pool's, and a replicated frontier is what lets every
        shard mask its local heads without an exchange)."""
        import jax.numpy as jnp

        state = {
            "pos": jnp.zeros(self.num_slots, jnp.int32),
            "tok": jnp.zeros(self.num_slots, jnp.int32),
            "temp": jnp.zeros(self.num_slots, jnp.float32),
            "topk": jnp.zeros(self.num_slots, jnp.int32),
            "topp": jnp.ones(self.num_slots, jnp.float32),
            "seed": jnp.zeros(self.num_slots, jnp.int32),
            "active": jnp.zeros(self.num_slots, jnp.bool_),
        }
        if self.paged:
            state["table"] = jnp.full(
                (self.num_slots, self.slot_blocks), self.kv_pool_blocks,
                jnp.int32)
        if self._mesh is not None:
            import jax

            state = jax.device_put(state, self._rep)
        return state

    def _split_meta(self, meta, fmeta):
        nb = self.slot_blocks if self.paged else 0
        tables = meta[:, :nb] if self.paged else None
        slots, true_lens, top_ks, seeds = (meta[:, nb], meta[:, nb + 1],
                                           meta[:, nb + 2], meta[:, nb + 3])
        hits = meta[:, nb + 4] if self.paged else None
        return tables, slots, hits, true_lens, top_ks, seeds, \
            fmeta[:, 0], fmeta[:, 1]

    def _prefill_fn(self, params, pool, prompts, meta, fmeta):
        """Admission wave (k, L_bucket) -> (new pool, first tokens (k,)).

        Runs the ordinary scalar-cache prefill on a batch-k temp cache of
        the bucket length, then scatters those rows into the wave's slot
        rows. Positions >= true_lens[i] hold garbage K/V — decode
        overwrites each position before attending to it and the per-row
        mask hides the rest, so padding never leaks into any output (the
        greedy parity test pins this). Ladder-padding rows carry slot id
        num_slots, which the scatter drops on the floor."""
        import jax.numpy as jnp

        from nanosandbox_tpu.models.gpt import init_cache, scatter_cache_rows
        from nanosandbox_tpu.sample import _sample_token, row_keys

        _, slots, _, true_lens, top_ks, seeds, temps, top_ps = \
            self._split_meta(meta, fmeta)
        k, L = prompts.shape
        cache = init_cache(self.cfg, k, L)
        logits, cache = self.model.apply({"params": params}, prompts,
                                         deterministic=True, cache=cache,
                                         cache_index=0)
        new_pool = scatter_cache_rows(pool, cache, slots)
        last = logits[jnp.arange(k), true_lens - 1, :]
        # Token destined for position true_len: fold_in(seed, true_len) —
        # the same stream the decode step continues at true_len + 1.
        keys = row_keys(seeds, true_lens)
        toks, _ = _sample_token(last, keys, temperature=temps,
                                top_k=top_ks, top_p=top_ps)
        return new_pool, self._poison_guard(toks, last)

    def _prefill_paged_fn(self, params, pool, suffix, meta, fmeta):
        """Paged admission wave: (k, L_suffix_bucket) SUFFIX tokens ->
        (new pool, first tokens (k,)).

        ONE model call straight against the pool — no temp cache, no
        scatter-back: the model's paged write path lands each row's
        suffix K/V at positions [hit, hit + Ls) through its block-table
        row (the same per-row vector-index scatter the spec verify
        uses), and its paged read path gathers the row's chain — the
        resident prefix INCLUDED — for the suffix's attention. The hit
        skips the prefix's forward FLOPs, which is where TTFT goes;
        shared hit blocks are never written (the write range starts at
        the block-aligned hit boundary, always a private block) and
        ladder-padding rows carry all-sentinel tables, so every one of
        their writes drops.

        The first token samples from position true_len - 1 with the
        SAME fold_in(seed, true_len) key a from-scratch prefill would
        use — prefix-hit outputs are token-identical to cold ones by
        construction (pinned by test)."""
        import jax.numpy as jnp

        from nanosandbox_tpu.sample import _sample_token, row_keys

        tables, _, hit_lens, true_lens, top_ks, seeds, temps, top_ps = \
            self._split_meta(meta, fmeta)
        k, _ = suffix.shape
        logits, pool = self.model.apply({"params": params}, suffix,
                                        deterministic=True, cache=pool,
                                        cache_index=hit_lens,
                                        block_table=tables)
        suf_lens = true_lens - hit_lens
        last = logits[jnp.arange(k), suf_lens - 1, :]
        keys = row_keys(seeds, true_lens)
        toks, _ = _sample_token(last, keys, temperature=temps,
                                top_k=top_ks, top_p=top_ps)
        return pool, self._poison_guard(toks, last)

    def _decode_step_fn(self, params, pool, state):
        """One batched token step over ALL slots at per-row frontiers —
        the scan body. pos advances and the sampled token becomes the
        next step's input ON DEVICE, so neither the host loop (scan_k
        == 1) nor the in-program scan (scan_k > 1) ever reads a token
        back before continuing. Inactive rows are parked by the mask —
        frozen pos, pinned token — so a released slot's garbage can't
        random-walk its own state. Paged pools ride the same program:
        the block table is one more state leaf, and the model's cached
        path pages reads/writes through it (with sentinel entries
        dropping any overrun row's writes)."""
        import jax.numpy as jnp

        from nanosandbox_tpu.sample import _sample_token, row_keys

        logits, pool = self.model.apply({"params": params},
                                        state["tok"][:, None],
                                        deterministic=True, cache=pool,
                                        cache_index=state["pos"],
                                        block_table=state.get("table"))
        keys = row_keys(state["seed"], state["pos"] + 1)
        nxt, _ = _sample_token(logits[:, 0, :], keys,
                               temperature=state["temp"],
                               top_k=state["topk"], top_p=state["topp"])
        nxt = self._poison_guard(nxt, logits[:, 0, :])
        active = state["active"]
        new_state = dict(state,
                         pos=state["pos"] + active.astype(jnp.int32),
                         tok=jnp.where(active, nxt, state["tok"]))
        return pool, new_state, nxt

    def _decode_fn(self, params, pool, state, k: int = 1):
        """The decode dispatch: one token step (k == 1, tokens (S,)) or
        the fused multi-step MEGAPROGRAM — a lax.scan of k token steps
        inside one compiled program, tokens (k, S). The scan carries
        (pool, state) through the same body the single-step path
        compiles, so the modes cannot drift: row r's token at position
        q is sampled from fold_in(key(seed_r), q) either way, and
        greedy outputs are token-identical across every k (pinned).
        ``k`` is a static jit arg drawn from the scan_rungs ladder —
        one compiled program per rung, the budget max_programs()
        publishes as {'decode': len(scan_rungs)}."""
        if k == 1:
            return self._decode_step_fn(params, pool, state)
        from jax import lax

        def body(carry, _):
            pool, state = carry
            pool, state, tok = self._decode_step_fn(params, pool, state)
            return (pool, state), tok

        (pool, state), toks = lax.scan(body, (pool, state), None,
                                       length=k)
        return pool, state, toks

    def _poison_guard(self, toks, logits):
        """In-program NaN/inf sentinel: a row whose logits went non-
        finite would otherwise sample an arbitrary-but-valid token
        (argmax over NaN is 0) and poison its KV history silently —
        instead the sampled token is replaced with the out-of-vocab
        sentinel, which the host retire loop detects for free from the
        readback it already performs (no extra sync, no extra program;
        the recovery supervisor turns the detection into a rebuild)."""
        import jax.numpy as jnp

        ok = jnp.isfinite(logits).all(axis=-1)
        return jnp.where(ok, toks, jnp.int32(self.cfg.vocab_size))

    def _admit_fn(self, state, toks, meta, fmeta):
        """Scatter an admission wave's operands into the slot-state rows.

        One per-rung program keyed by the packed (k, meta_width) staging
        shape; padding rows carry the out-of-range slot id num_slots,
        dropped by the scatter. Paged engines additionally scatter the
        wave's (k, max_blocks) block-table rows. ``toks`` is the prefill
        program's device-resident output — first tokens flow device-to-
        device into the slot state, never through the host."""
        tables, slots, _, pos0, top_ks, seeds, temps, top_ps = \
            self._split_meta(meta, fmeta)
        out = {
            "pos": state["pos"].at[slots].set(pos0, mode="drop"),
            "tok": state["tok"].at[slots].set(toks, mode="drop"),
            "temp": state["temp"].at[slots].set(temps, mode="drop"),
            "topk": state["topk"].at[slots].set(top_ks, mode="drop"),
            "topp": state["topp"].at[slots].set(top_ps, mode="drop"),
            "seed": state["seed"].at[slots].set(seeds, mode="drop"),
            "active": state["active"].at[slots].set(True, mode="drop"),
        }
        if tables is not None:
            out["table"] = state["table"].at[slots].set(tables, mode="drop")
        return out

    def _release_fn(self, state, slot):
        """Park one slot row back at the harmless idle values — for a
        paged engine that includes pointing the whole block-table row at
        the unallocated sentinel, so the parked row's garbage decode
        writes DROP instead of landing in a block the host may have
        already freed or donated to the prefix cache."""
        out = {
            "pos": state["pos"].at[slot].set(0),
            "tok": state["tok"].at[slot].set(0),
            "temp": state["temp"].at[slot].set(0.0),
            "topk": state["topk"].at[slot].set(0),
            "topp": state["topp"].at[slot].set(1.0),
            "seed": state["seed"].at[slot].set(0),
            "active": state["active"].at[slot].set(False),
        }
        if "table" in state:
            out["table"] = state["table"].at[slot].set(self.kv_pool_blocks)
        return out

    # ------------------------------------------------------------------
    # public API
    # ------------------------------------------------------------------
    def _collect_metrics(self) -> None:
        """Collection-time mirror of the engine's plain-int state into
        the registry — runs per snapshot/scrape, NEVER in the decode
        loop, which is how telemetry stays off the hot path."""
        self._c_tokens._set_total(self.tokens_generated)
        self._c_steps._set_total(self.steps)
        for kind, n in list(self.host_dispatches.items()):
            if n:
                self._c_dispatches.labels(kind=kind)._set_total(n)
        dec = self.host_dispatches["decode"] + self.host_dispatches["verify"]
        self._g_toks_per_dispatch.set(
            self.tokens_generated / dec if dec else 0.0)
        self._c_admitted._set_total(self.admitted)
        self._c_shed._set_total(self.shed)
        self._c_preempted._set_total(self.preemptions)
        for reason, n in list(self.rejected.items()):
            self._c_rejected.labels(reason=reason)._set_total(n)
        self._c_poisoned._set_total(self.poisoned_steps)
        self._c_requeued._set_total(self.requeued)
        self._c_drafter_faults._set_total(self.drafter_faults)
        self._g_quarantined.set(1.0 if self.quarantined else 0.0)
        self._g_active.set(len(self._active))
        self._g_free.set(self.sched.free_slots)
        self._g_queued.set(self.sched.queued)
        rate = self._recent_rate()
        self._g_rate.set(0.0 if rate is None else rate)
        self._g_impl.labels(impl=self.decode_impl).set(1.0)
        self._g_kv.labels(kv_dtype=self.kv_dtype).set(1.0)
        self._g_tp.set(float(self.tp))
        self._g_role.labels(role=self.role).set(1.0)
        self._g_limbo.set(self.sched.limbo)
        self._c_migrated._set_total(self.migrated)
        self._c_adopted._set_total(self.adopted)
        if self.block_pool is not None:
            ps = self.block_pool.stats()
            for state in ("free", "live", "cached"):
                self._g_pool_blocks.labels(state=state).set(ps[state])
            self._c_prefix_hit._set_total(ps["prefix_hit_tokens"])
            self._c_prefix_miss._set_total(ps["prefix_miss_tokens"])
            self._c_block_stalls._set_total(ps["block_stall_steps"])
        for name, n in self.tracecheck.counts().items():
            self._c_traces.labels(program=name)._set_total(n)

    def _reject(self, reason: str, msg: str, **fields) -> None:
        """Reject a submission: count it, leave the terminal ``reject``
        event in the flight ledger (rid None — no id was ever assigned,
        matching the error the caller gets), raise the client error."""
        self.rejected[reason] = self.rejected.get(reason, 0) + 1
        self.flight.record("reject", step=self.steps, reason=reason,
                           **fields)
        raise ValueError(msg)

    def submit(self, prompt: Sequence[int], max_new_tokens: int, *,
               temperature: float = 0.0, top_k: int = 0, top_p: float = 1.0,
               seed: int = 0, eos_id: Optional[int] = None,
               deadline_s: Optional[float] = None,
               slo_class: str = "default",
               priority: Optional[int] = None,
               migrate: bool = False) -> int:
        """Queue one request; returns its id. Fixed-shape admission rules
        are enforced here so a bad request fails at submit, not as a
        mid-flight surprise — every reject leaves a terminal ``reject``
        event in the flight ledger. ``deadline_s`` (default: the
        engine's default_deadline_s) arms SLO accounting and queue-time
        shedding; ``slo_class`` labels it on /metrics; ``priority``
        (default: PRIORITY_BY_CLASS[slo_class]) orders the queue and
        the preemption policy. Under an active brownout shed floor a
        below-floor submission is accepted but immediately SHED (a
        terminal 'shed' Result — 429 + Retry-After upstream, never a
        silent queue-rot).

        ``migrate=True`` (ISSUE 16, paged engines only) marks the
        request for DISAGGREGATED handoff: this engine runs only its
        prefill (allocating the prompt's blocks, no generation
        budget), then parks the block chain + sampled first token in
        migration limbo for a decode tier to adopt — see pop_export()
        / Engine.begin_adopt(). The request's terminal Result comes
        from the ADOPTING engine (or from here, if the first token
        already finishes it or the export is shed/aborted)."""
        prompt = tuple(int(t) for t in prompt)
        plen = len(prompt)
        if self.failed:
            # Permanent failure drains, it does not crash-loop: refuse
            # loudly (503 upstream) instead of queueing into a void.
            self.rejected["engine_failed"] = \
                self.rejected.get("engine_failed", 0) + 1
            self.flight.record("reject", step=self.steps,
                               reason="engine_failed", prompt_len=plen)
            raise EngineFailedError(
                "engine permanently failed "
                f"({self.quarantine_cause or 'unknown cause'}); "
                "restart the process or route to another replica")
        if not prompt:
            self._reject("empty_prompt",
                         "empty prompt (encode at least one token)")
        bad = next((t for t in prompt
                    if not 0 <= t < self.cfg.vocab_size), None)
        if bad is not None:
            # An out-of-range id is not just garbage-in-garbage-out:
            # the embedding gather FILLS out-of-bounds rows (NaN under
            # jit), the poison sentinel fires on the non-finite logits,
            # and the recovery supervisor burns every attempt re-
            # admitting the same request until the engine PERMANENTLY
            # fails — one malformed request kills the replica (and a
            # failover-happy fleet would hand the same poison pill to
            # the next replica). Client errors reject at the boundary.
            self._reject(
                "token_out_of_range",
                f"prompt token {bad} outside [0, vocab_size="
                f"{self.cfg.vocab_size})", prompt_len=plen)
        if max_new_tokens < 0:
            self._reject(
                "bad_max_new",
                f"max_new_tokens must be >= 0, got {max_new_tokens}",
                prompt_len=plen)
        if deadline_s is None:
            deadline_s = self.default_deadline_s
        else:
            deadline_s = float(deadline_s)
            if deadline_s <= 0:
                self._reject("bad_deadline",
                             f"deadline_s must be > 0, got {deadline_s}",
                             prompt_len=plen)
        try:
            slo_class = validate_slo_class(str(slo_class))
        except ValueError as e:
            self._reject("bad_slo_class", str(e), prompt_len=plen)
        if plen > self.sched.buckets[-1]:
            self._reject(
                "prompt_exceeds_bucket",
                f"prompt length {plen} exceeds the largest prefill "
                f"bucket {self.sched.buckets[-1]}", prompt_len=plen)
        total = plen + max_new_tokens
        if total > self.max_len:
            self._reject(
                "exceeds_max_len",
                f"prompt ({plen}) + max_new_tokens "
                f"({max_new_tokens}) = {total} exceeds the per-slot KV "
                f"length {self.max_len}; long-context decode belongs to "
                "sample.py's windowed path", prompt_len=plen)
        if self.paged:
            # The no-deadlock split: a request the POOL could never hold
            # (even with every block free) is rejected HERE, loudly; one
            # that merely cannot fit RIGHT NOW queues and admission
            # defers it until running requests release blocks — full
            # reservation at admit means nothing mid-decode ever waits.
            need = self.block_pool.blocks_needed(plen, max_new_tokens)
            if need > self.kv_pool_blocks:
                self._reject(
                    "pool_too_small",
                    f"request needs {need} KV blocks but the pool holds "
                    f"{self.kv_pool_blocks}; raise kv_pool_blocks or "
                    "shorten the request", prompt_len=plen)
        if migrate and not self.paged:
            self._reject(
                "migrate_unpaged",
                "migrate=True needs a paged engine: the block chain IS "
                "the migration wire format (dense per-slot caches have "
                "nothing portable to hand off)", prompt_len=plen)
        if priority is None:
            priority = PRIORITY_BY_CLASS.get(slo_class, DEFAULT_PRIORITY)
        else:
            priority = int(priority)
        rid = next(self._rid)
        req = Request(rid=rid, prompt=prompt, max_new_tokens=max_new_tokens,
                      temperature=float(temperature), top_k=int(top_k),
                      top_p=float(top_p), seed=int(seed), eos_id=eos_id,
                      deadline_s=deadline_s, slo_class=slo_class,
                      priority=priority)
        self._c_submitted.inc()
        sub_fields = {"prompt_len": plen, "max_new": max_new_tokens,
                      "slo_class": slo_class, "priority": priority}
        if deadline_s is not None:
            sub_fields["deadline_s"] = deadline_s
        self.flight.record("submit", rid=rid, step=self.steps,
                           **sub_fields)
        floor = self.brownout_min_priority
        if floor is not None and priority < floor:
            # Brownout shed-at-submit: the request is valid but the
            # engine is deliberately refusing its class right now —
            # terminal 'shed' (429 + Retry-After upstream), counted
            # against its class's attainment, zero resources spent.
            self.shed += 1
            self.flight.record("shed", rid=rid, step=self.steps,
                               reason="brownout", slo_class=slo_class,
                               priority=priority, floor=floor)
            if deadline_s is not None:
                self.slo.record_shed(slo_class)
                self.brownout_sheds += 1
            self._pending_results.append(
                Result(rid=rid, prompt=prompt, tokens=[],
                       finish_reason="shed"))
            return rid
        if max_new_tokens == 0:
            # Counts as completed too (never reaches _finish): the
            # natural submitted-minus-completed in-flight alert must
            # not drift on zero-token requests.
            self._c_completed.labels(reason="length").inc()
            self.flight.record("finish", rid=rid, step=self.steps,
                               reason="length", tokens=0, e2e_s=0.0)
            self.slo.record_finish(slo_class, tokens=0, elapsed_s=0.0,
                                   deadline_s=deadline_s)
            self._pending_results.append(
                Result(rid=rid, prompt=prompt, tokens=[],
                       finish_reason="length"))
            return rid
        if migrate and max_new_tokens > 1:
            # max_new <= 1 finishes at the prefill readback — nothing
            # left to migrate; those ride the colocated path untouched.
            self._migrate_rids.add(rid)
        sid = self.tracer.begin("queued", cat="request", rid=rid,
                                args={"prompt_len": plen,
                                      "max_new": max_new_tokens})
        self._submit_meta[rid] = (self.steps, time.monotonic(), sid)
        self.sched.enqueue(req)
        self.flight.record("queue", rid=rid, step=self.steps,
                           depth=self.sched.queued)
        return rid

    def has_work(self) -> bool:
        # Limbo counts: a parked export owes its client a terminal and
        # holds blocks — idle-with-limbo is not idle. Callers that
        # drain() a migrate-submitting engine must pump its exports
        # (DisaggPair.drain does) or carry deadlines that shed them.
        return bool(self._active or self.sched.queued or self._chunking
                    or self.sched.limbo
                    or self._pending_results or self._inflight is not None)

    def step(self) -> List[Result]:
        """Admit as many queued requests as slots allow (one batched
        prefill per wave), dispatch one batched decode step, then retire
        the PREVIOUS step's readback (pipelined; with pipeline=False the
        readback is the step just dispatched). Returns the requests that
        finished during this call."""
        if self.failed:
            # A permanently-failed engine only flushes already-terminal
            # results; abort_all() has drained everything else.
            finished, self._pending_results = self._pending_results, []
            return finished
        t0 = time.monotonic()
        self._prefill_spent = 0        # the per-step chunked-prefill budget
        traces0 = sum(self.tracecheck.counts().values())
        self._profile_window_start()
        finished = self._step_impl()
        self._profile_window_advance()
        # A single step stalling for tens of seconds is a wedged device,
        # not load — feed the stalled_step watchdog from the wall time
        # the step just took (one float compare when healthy). A step
        # that COMPILED something (--warmup=buckets lazy waves, tests)
        # is legitimately slow and must not read as a wedge: tearing
        # down a healthy replica for compiling would be recovery-
        # induced outage.
        if sum(self.tracecheck.counts().values()) == traces0:
            self.watchdog.on_step_time(time.monotonic() - t0)
        self.watchdog.check()
        if self.brownout is not None:
            self.brownout.on_step()
        return finished

    def _step_impl(self) -> List[Result]:
        # ``finished`` IS self._pending_results until the successful
        # detach at each return: an exception mid-step (device failure,
        # injected fault) must not strand already-terminal Results —
        # the supervisor's next step delivers them after recovery.
        finished = self._pending_results

        # Shed queued requests whose deadline already passed — BEFORE
        # admission, so an expired request never eats a slot, a prefill
        # program, or KV blocks on its way to a missed SLO.
        self._shed_expired(finished)
        # Preemption-by-eviction (ISSUE 13): if the highest-priority
        # queued deadline would expire waiting on slots/blocks, evict
        # the lowest-priority victim now so the admission below can
        # take its place. (Also hosts the preempt_storm fault site.)
        self._maybe_preempt()
        # Backfill free slots mid-flight; a wave finishing on its prefill
        # tokens immediately frees slots for the next wave in line.
        self._admit_waves(finished)

        if self._spec is not None and not self.spec_suspended:
            # Speculative step: draft -> one fixed-shape verify ->
            # retire, synchronously (any live row needs >= 1 more token
            # by construction — rows finish the moment they hit budget).
            if self._active:
                self._spec_step(finished)
                # Slots the retire just freed backfill NOW, same as the
                # pipelined loop's post-retire admission.
                self._admit_waves(finished)
            self._pending_results = []
            return finished

        retired = False
        chunk_len = self._next_chunk() if self._active else 0
        if chunk_len:
            if self.faults is not None:
                f = self.faults.fire("slow_step", self.steps)
                if f is not None:
                    self.flight.record("fault", step=self.steps,
                                       site="slow_step", stall_s=f.stall_s)
                    time.sleep(f.stall_s)
            self._pool, self._state, toks = self._decode(
                self.params, self._pool, self._state, chunk_len)
            self.steps += 1
            self.host_dispatches["decode"] += 1
            if (self.faults is not None
                    and self.faults.fire("nan_logits", self.steps)
                    is not None):
                # Injection happens at the host boundary: the readback
                # the retire will perform sees exactly what a real
                # non-finite step produces (the in-program sentinel),
                # so detection + recovery exercise the production path.
                # Under scan_k the whole chunk poisons — the worst
                # real case, a non-finite step mid-scan feeding every
                # later step garbage.
                self.flight.record("fault", step=self.steps,
                                   site="nan_logits")
                toks = np.full(np.shape(toks), self.cfg.vocab_size,
                               np.int32)
            snapshot = {slot: st.req.rid
                        for slot, st in self._active.items()}
            # decode_step span: opened at DISPATCH, closed at RETIRE —
            # under pipelining that close happens after the NEXT step's
            # open, so the exported timeline shows the true one-step
            # (one-CHUNK, under scan_k) overlap instead of a
            # synchronous fiction.
            sid = self.tracer.begin("decode_step", cat="decode",
                                    args={"step": self.steps,
                                          "rows": len(snapshot),
                                          "chunk_len": chunk_len})
            prev, self._inflight = self._inflight, (
                toks, snapshot, sid, self.steps, chunk_len)
            if not self.pipeline:
                inflight, self._inflight = self._inflight, None
                self._retire(inflight, finished)
                retired = True
            elif prev is not None:
                self._retire(prev, finished)
                retired = True
        elif self._inflight is not None:
            # Nothing left to dispatch (all rows' budgets covered by
            # computed tokens) — drain the lagging readback.
            inflight, self._inflight = self._inflight, None
            self._retire(inflight, finished)
            retired = True
        if retired:
            # Slots the retire just freed backfill NOW — their prefill
            # queues behind the in-flight step and the next dispatch
            # picks the new rows up, so eviction->readmission costs the
            # same one-step lag as the synchronous loop instead of two.
            self._admit_waves(finished)
        self._pending_results = []
        return finished

    def _shed_expired(self, finished: List[Result]) -> None:
        """Drop queued requests whose deadline expired while waiting —
        or whose priority sits below the active brownout shed floor:
        terminal ``shed`` Result (empty tokens), counted against SLO
        attainment. Requests without deadlines never deadline-shed
        (brownout can still shed them). Cheap when the queue carries no
        deadlines and no brownout is active — one attribute scan, no
        allocation (scheduler.drain_expired).

        The sweep also covers MIGRATION LIMBO (the ISSUE 16 fix):
        a request parked awaiting decode-tier adoption carries the same
        unserved deadline as a queued one — a stalled decode tier must
        shed it with a terminal ``shed``, its blocks released WITHOUT
        donation, not leak it forever. Limbo records shed on deadline
        only (never the brownout floor: their prefill is already paid —
        shedding it saves nothing)."""
        if not (self.sched.queued or self.sched.limbo):
            return
        now = time.monotonic()
        meta = self._submit_meta
        floor = self.brownout_min_priority

        def expired(item) -> bool:
            if isinstance(item, _Export):
                return (item.deadline_s is not None
                        and now - item.submit_t > item.deadline_s)
            return ((item.deadline_s is not None
                     and now - meta[item.rid][1] > item.deadline_s)
                    or (floor is not None and item.priority < floor))

        for item in self.sched.drain_expired(expired):
            if isinstance(item, _Export):
                self.shed += 1
                # Blocks freed, never donated (the ISSUE 16 contract):
                # a shed must not warm the cache on refused traffic.
                self.block_pool.release(item.alloc, donate=False)
                waited = now - item.submit_t
                self.flight.record(
                    "shed", rid=item.rid, step=self.steps,
                    reason="deadline", limbo=True,
                    waited_s=round(waited, 6),
                    deadline_s=item.deadline_s,
                    slo_class=item.req.slo_class)
                self.slo.record_shed(item.req.slo_class)
                finished.append(Result(rid=item.rid,
                                       prompt=item.req.prompt,
                                       tokens=[],
                                       finish_reason="shed"))
                continue
            req = item
            sub_step, sub_t, sid = meta.pop(req.rid)
            self.shed += 1
            self.tracer.end(sid, {"shed": True,
                                  "wait_steps": self.steps - sub_step})
            # A recovery-requeued victim can expire while waiting for
            # re-admission: unstitch it like every other terminal — the
            # Result carries the ORIGINAL prompt and the salvaged
            # pre-fault tokens, and the _Resume record must not leak.
            prompt_out, tokens_out, resumed = self._unstitch(
                req.rid, req, [])
            deadline_hit = (req.deadline_s is not None
                            and now - sub_t > req.deadline_s)
            shed_fields = {"waited_s": round(now - sub_t, 6),
                           "deadline_s": req.deadline_s,
                           "slo_class": req.slo_class,
                           "reason": ("deadline" if deadline_hit
                                      else "brownout")}
            if resumed:
                shed_fields["resumed"] = True
                shed_fields["tokens"] = len(tokens_out)
            self.flight.record("shed", rid=req.rid, step=self.steps,
                               **shed_fields)
            if req.deadline_s is not None:
                self.slo.record_shed(req.slo_class)
                if not deadline_hit:
                    self.brownout_sheds += 1
            finished.append(Result(rid=req.rid, prompt=prompt_out,
                                   tokens=tokens_out,
                                   finish_reason="shed"))

    def drain(self) -> List[Result]:
        """Run step() until queue, slots and pipeline are empty."""
        out: List[Result] = []
        while self.has_work():
            out.extend(self.step())
        return out

    # ------------------------------------------------------------------
    # on-demand profiling (POST /profile)
    # ------------------------------------------------------------------
    def request_profile(self, steps: int, out_dir: Optional[str] = None,
                        ) -> dict:
        """Arm a jax.profiler window over the next ``steps`` engine
        steps (train.py's --profile_steps machinery, serving-side).
        Thread-safe: HTTP handlers arm it, the one stepping thread
        opens/advances/closes it inside step(). Freeze-safe by
        construction — the window only wraps already-compiled programs,
        so a frozen tracecheck registry stays silent (pinned by test)."""
        steps = int(steps)
        if steps < 1:
            raise ValueError(f"profile steps must be >= 1, got {steps}")
        # Create/validate the dir BEFORE taking _profile_lock: the one
        # stepping thread takes this lock inside step() once a window is
        # armed, so filesystem I/O under it would let a slow /tmp stall
        # serving (lockcheck: blocking-under-lock). Validation stays on
        # the arming thread, where failure is a clean 400 — a bad path
        # surfacing later inside start_trace on the stepping thread
        # would kill the whole serving loop for one bad request.
        auto = out_dir is None
        d = out_dir or tempfile.mkdtemp(prefix="serve-profile-")
        try:
            os.makedirs(d, exist_ok=True)
        except OSError as e:
            raise ValueError(f"unusable profile dir {d!r}: {e}") from e
        with self._profile_lock:
            if self._profile is not None and self._profile["started"]:
                # Roll back the tempdir this losing arm just created.
                if auto:
                    try:
                        os.rmdir(d)
                    except OSError:
                        pass
                raise RuntimeError("a profile window is already in progress")
            # An armed-but-unstarted window (no traffic arrived yet) is
            # simply replaced — 409ing on it would wedge /profile
            # behind a window nothing is profiling, with no way out
            # until unrelated traffic drains it.
            self._reap_unstarted_dir()
            self._profile = {"dir": d, "auto_dir": auto, "steps": steps,
                             "remaining": steps, "started": False,
                             "span": 0, "sync_mark": None}
        return {"dir": d, "steps": steps}

    def _reap_unstarted_dir(self) -> None:
        """Remove the empty auto-created tempdir of a replaced/cancelled
        un-started window (call with _profile_lock held) — repeated arms
        from a flapping prober must not leak one /tmp dir per call.
        rmdir only: a dir a trace ever wrote into is never touched."""
        prof = self._profile
        if prof is not None and prof["auto_dir"] and not prof["started"]:
            try:
                os.rmdir(prof["dir"])
            except OSError:
                pass

    def cancel_profile(self) -> bool:
        """Disarm an armed-but-unstarted window (a started one belongs
        to the stepping thread and runs to its close). Returns whether
        anything was cancelled."""
        with self._profile_lock:
            if self._profile is not None and not self._profile["started"]:
                self._reap_unstarted_dir()
                self._profile = None
                return True
            return False

    def _profile_window_start(self) -> None:
        # Unlocked None fast path: this runs EVERY step, and the zero-
        # hot-loop-cost contract means no mutex traffic unless a window
        # is actually armed (arming publishes a non-None dict under the
        # lock; worst case the window starts one step late).
        if self._profile is None:
            return
        # The started flag flips under the lock so cancel/re-arm from
        # an HTTP thread can never swap the window out between this
        # check and the trace actually opening.
        with self._profile_lock:
            prof = self._profile
            if prof is None or prof["started"] or not self.has_work():
                return
            prof["started"] = True
        import jax

        try:
            jax.profiler.start_trace(prof["dir"])
        except Exception as e:  # dir went bad since arming, profiler busy
            # Fail the PROFILE, never the serving loop it rides in —
            # and reap the never-written auto dir, same as cancel.
            with self._profile_lock:
                if prof["auto_dir"]:
                    try:
                        os.rmdir(prof["dir"])
                    except OSError:
                        pass
                self._profile = None
            self.last_profile = {"dir": prof["dir"], "steps": prof["steps"],
                                 "error": f"{type(e).__name__}: {e}"}
            return
        prof["sync_mark"] = _tracecheck.sync_counts()
        prof["span"] = self.tracer.begin(
            "profile_window", cat="profile",
            args={"steps": prof["steps"], "dir": prof["dir"]})

    def _profile_window_advance(self) -> None:
        prof = self._profile
        if prof is None or not prof["started"]:
            return
        prof["remaining"] -= 1
        # Close early when the engine runs dry: the loop stops stepping
        # an idle engine, so an N-step window armed during a burst that
        # drains after k<N steps would otherwise stay open (trace
        # buffering, /profile 409ing) until traffic returns hours later.
        if prof["remaining"] > 0 and self.has_work():
            return
        import jax

        self.last_profile = {"dir": prof["dir"], "steps": prof["steps"],
                             "steps_profiled": prof["steps"]
                             - prof["remaining"]}
        try:
            jax.profiler.stop_trace()
        except Exception as e:  # trace dir reaped, disk full
            # Same contract as the start side: a stop failure loses the
            # PROFILE, never the serving loop — and must still clear
            # the window or /profile would 409 forever.
            self.last_profile["error"] = f"{type(e).__name__}: {e}"
            self.tracer.end(prof["span"], {"error": self.last_profile["error"]})
        else:
            by_kind = _tracecheck.sync_delta(prof["sync_mark"])
            self.tracer.end(prof["span"],
                            {"host_syncs": sum(by_kind.values())})
            self.last_profile["host_syncs_in_window"] = by_kind
        with self._profile_lock:
            self._profile = None

    def stats(self) -> dict:
        spec_stats = ({"enabled": False} if self._spec is None
                      else self._spec.stats())
        paged_stats: dict = {"enabled": self.paged}
        if self.block_pool is not None:
            paged_stats.update(self.block_pool.stats())
            # peek, never labels(): reading stats must not mint empty
            # {prefix=} series for the exposition to render (hygiene).
            hit = self._ttft_prefix.peek(prefix="hit")
            miss = self._ttft_prefix.peek(prefix="miss")
            paged_stats["ttft_hit_s"] = (
                hit.percentiles((50, 90, 99)) if hit is not None else None)
            paged_stats["ttft_miss_s"] = (
                miss.percentiles((50, 90, 99)) if miss is not None
                else None)
        return {
            "num_slots": self.num_slots,
            "max_len": self.max_len,
            "kv_dtype": self.kv_dtype,
            "tp": self.tp,
            "paged": self.paged,
            "kv_page_size": self.kv_page_size,
            "kv_pool_blocks": self.kv_pool_blocks,
            "kv_pool": paged_stats,
            "decode_attention_impl": self.decode_impl,
            "prefill_buckets": list(self.sched.buckets),
            "admit_buckets": list(self.admit_buckets),
            "pipeline": self.pipeline,
            "scan_k": self.scan_k,
            "host_dispatches": dict(self.host_dispatches),
            "tokens_per_dispatch": (
                self.tokens_generated
                / (self.host_dispatches["decode"]
                   + self.host_dispatches["verify"])
                if (self.host_dispatches["decode"]
                    + self.host_dispatches["verify"]) else None),
            "active": len(self._active),
            "queued": self.sched.queued,
            "free_slots": self.sched.free_slots,
            # The classless client-backoff estimate, scrapeable: the
            # fleet router's HTTP tier aggregates these across replicas
            # (min over ready) instead of forwarding whichever replica
            # happened to shed.
            "retry_after_s": self.retry_after_s(),
            "admitted": self.admitted,
            "completed": self.completed,
            "shed": self.shed,
            # Disaggregated posture (ISSUE 16): tier role plus both
            # sides of the migration flow this engine has seen.
            "role": self.role,
            "limbo": self.sched.limbo,
            "migrated": self.migrated,
            "adopted": self.adopted,
            "rejected": dict(self.rejected),
            "default_deadline_s": self.default_deadline_s,
            # Scheduling endgame (ISSUE 13): preemption/chunk/brownout
            # posture — what /debug/scheduler explains in detail.
            "preemptions": self.preemptions,
            "prefill_chunk": self.prefill_chunk,
            "chunking": len(self._chunking),
            "spec_suspended": self.spec_suspended,
            "brownout": (None if self.brownout is None
                         else self.brownout.stats()),
            # Fault/recovery posture (ISSUE 11): what readiness probes
            # and the /debug views key off, plus the armed fault plan
            # when chaos testing.
            "recovery": {
                "quarantined": self.quarantined,
                "failed": self.failed,
                "cause": self.quarantine_cause,
                "recoveries": self.recoveries,
                "recovery_s": self._h_recovery.percentiles((50, 90, 99)),
                "poisoned_steps": self.poisoned_steps,
                "requeued": self.requeued,
                "resumed_in_flight": len(self._resumed),
                "drafter_faults": self.drafter_faults,
                "spec_disabled": self.spec_disabled_reason,
            },
            "faults": (None if self.faults is None
                       else self.faults.stats()),
            "slo": self.slo.stats(),
            "flight": self.flight.stats(),
            "watchdog": self.watchdog.stats(),
            "decode_steps": self.steps,
            "tokens_generated": self.tokens_generated,
            "decode_tokens_per_sec": self._recent_rate(),
            "queue_wait_steps_mean": self._queue_wait.mean(),
            "ttft_s": self._ttft.percentiles((50, 90, 99)),
            "tpot_s": self._tpot.percentiles((50, 90, 99)),
            "trace_counts": dict(self.trace_counts),
            # Speculative signal: token-level acceptance rate, the mean
            # accepted draft length per verify row (ring window), and
            # per-request accepted-token totals (recorded at finish).
            "spec": spec_stats,
            "spec_acceptance_rate": spec_stats.get("acceptance_rate"),
            "spec_accepted_len_mean": self._spec_accept_len.mean(),
            "spec_req_accepted_tokens": self._spec_req_accepted.percentiles(
                (50, 90, 99)),
            "profile": {"active": self._profile is not None,
                        "last": self.last_profile},
        }

    def max_programs(self) -> dict:
        """The closed compile set by program kind — the budgets the
        tracecheck guards enforce at runtime (a retrace past these
        raises CompileBudgetExceeded) and tests/CI assert against.
        scan_k widens ONLY the decode entry, and exactly by its rung
        ladder: one megaprogram per scan_rungs chunk length (scan_k=1
        keeps the classic single program), pinned by test."""
        progs = {
            "prefill": len(self.sched.buckets) * len(self.admit_buckets),
            "decode": len(self.scan_rungs),
            "admit": len(self.admit_buckets),
            "release": 1,
        }
        if self._spec is not None:
            # ONE verify shape (fixed num_slots x (k+1); per-row draft
            # lengths are a mask, not a shape) — plus, for a
            # ModelDrafter, one draft scan and the drafter's own
            # (ladder x buckets) prefill grid.
            progs.update(self._spec.programs)
        return progs

    @property
    def mesh(self):
        """The tensor-parallel mesh this engine shards over (None at
        tp == 1 — the single-chip engine owns no mesh)."""
        return self._mesh

    def shardcheck_programs(self, mesh) -> list:
        """ProgramSpecs for the comms analyzer (analysis/shardcheck):
        the engine's full compiled set — decode, the prefill
        ladder x bucket grid, and (with spec=...) the verify/drafter
        programs — AOT-lowered under ``mesh``.

        tp == 1 lowers with every operand REPLICATED: the single-chip
        contract stated on the mesh, so the committed serve budget pins
        ZERO collectives. tp > 1 lowers under the engine's OWN mesh
        with the LIVE placements (Megatron weights, heads-sharded pool,
        replicated slot state): the partitioner runs for real and the
        committed TP budget (budgets/serve_tp_cpu8.json) pins the
        bounded model-axis collectives — while the accidental-all-gather
        rule stays armed (gather_ok_axes empty), so a dropped
        with_sharding_constraint that rebuilds the full pool on every
        chip is a CI finding with exact bytes, not a silent 2x HBM
        regression. Fresh jits: an analysis lower must not consume the
        live tracecheck budgets."""
        import jax
        import jax.numpy as jnp
        from jax.sharding import NamedSharding, PartitionSpec

        from nanosandbox_tpu.analysis.shardcheck import (Expectations,
                                                         ProgramSpec)
        from nanosandbox_tpu.parallel.mesh import replicated_abstract

        rep = NamedSharding(mesh, PartitionSpec())
        if self.tp > 1:
            if mesh is not self._mesh:
                raise ValueError(
                    "a tensor-parallel engine lowers under its own mesh "
                    "— pass engine.mesh (or build the engine with "
                    "tp_mesh=<the fleet mesh>)")

            def live_abstract(tree):
                return jax.tree.map(
                    lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype,
                                                   sharding=x.sharding),
                    tree)

            aparams = live_abstract(self.params)
            apool = live_abstract(self._pool)
            astate = live_abstract(self._state)
            # Comms expected — the budget pins how much and where; the
            # empty gather_ok_axes keeps accidental-all-gather armed
            # against any full materialization of the sharded pool.
            expect = Expectations(comms_free=False)
            jit_kwargs = {}
        else:
            aparams = replicated_abstract(mesh, self.params)
            apool = replicated_abstract(mesh, self._pool)
            astate = replicated_abstract(mesh, self._state)
            expect = Expectations(comms_free=True)
            jit_kwargs = {"in_shardings": rep, "out_shardings": rep}

        def jit_fleet(fn):
            return jax.jit(fn, **jit_kwargs)

        def sds(shape, dtype):
            return jax.ShapeDtypeStruct(shape, dtype, sharding=rep)

        # Quantized-KV engines publish under distinct names so one
        # budget file can pin every pool mode's comms (the fleet
        # commits int8 and int4 twins); likewise the dense (pre-paged)
        # layout keeps a _dense suffix — the unsuffixed names ARE the
        # paged programs, the default engine contract the budgets pin.
        # A scan_k > 1 engine's decode is the fused megaprogram LADDER,
        # a materially different compile surface per rung, so each rung
        # above 1 owns a decode_scan<r> name the budget must list
        # explicitly (rung 1 is the classic single-step program).
        # Tensor-parallel engines append _tp<N>: a different comms
        # contract is a different program identity.
        sfx = {"int8": "_kv8", "int4": "_kv4"}.get(self.kv_dtype, "")
        if not self.paged:
            sfx += "_dense"
        if self.tp > 1:
            sfx += f"_tp{self.tp}"

        def decode_spec(r):
            name = f"decode_scan{r}{sfx}" if r > 1 else f"decode{sfx}"

            def lower(r=r):
                return jax.jit(self._decode_fn, static_argnums=(3,),
                               **jit_kwargs).lower(
                                   aparams, apool, astate, r)

            return ProgramSpec(name=name, lower=lower,
                               abstract_args=(aparams, apool, astate),
                               expect=expect, tags=("serve",))

        specs = [decode_spec(r) for r in self.scan_rungs]
        prefill_body = (self._prefill_paged_fn if self.paged
                        else self._prefill_fn)
        for bucket in self.sched.buckets:
            for k in self.admit_buckets:
                args = (aparams, apool, sds((k, bucket), jnp.int32),
                        sds((k, self._meta_width), jnp.int32),
                        sds((k, 2), jnp.float32))
                specs.append(ProgramSpec(
                    name=f"prefill{sfx}_k{k}_L{bucket}",
                    lower=(lambda args=args:
                           jit_fleet(prefill_body).lower(*args)),
                    abstract_args=args, expect=expect, tags=("serve",)))
        if self._spec is not None:
            specs.extend(self._spec.shardcheck_programs(
                mesh, aparams=aparams, apool=apool, astate=astate,
                buckets=self.sched.buckets, rungs=self.admit_buckets,
                suffix=sfx, expect=expect,
                replicated_io=self.tp == 1))
        return specs

    @property
    def trace_counts(self) -> Dict[str, int]:
        """Observed traces per program kind, read from the tracecheck
        registry (the engine no longer hand-counts; /stats, warmup
        logging and the bench report all read this view)."""
        return self.tracecheck.counts()

    # ------------------------------------------------------------------
    # live introspection (GET /debug/slots | /debug/kvpool |
    # /debug/scheduler). Best-effort reads from an HTTP handler thread
    # while the loop thread mutates — same discipline as /stats: every
    # shared structure is snapshotted (list()/get()) before iteration,
    # and a torn read across two fields yields a stale view, never a
    # crash. No device state is touched (host dicts and plain ints).
    # ------------------------------------------------------------------
    def debug_slots(self) -> dict:
        """Per-slot occupancy: who owns each row, how far along it is,
        and how stale its last token is (the stuck-slot watchdog's view,
        on demand)."""
        now = time.monotonic()
        inflight = dict(self._inflight[1]) if self._inflight is not None \
            else {}
        active = dict(self._active)
        chunking = {e.slot: e for e in list(self._chunking)}
        slots = []
        for slot in range(self.num_slots):
            st = active.get(slot)
            if st is None:
                e = chunking.get(slot)
                if e is not None:
                    slots.append({"slot": slot, "state": "prefilling",
                                  "rid": e.req.rid,
                                  "prompt_len": len(e.req.prompt),
                                  "prefilled": e.hit + e.done})
                else:
                    slots.append({"slot": slot, "state": "free"})
                continue
            req = st.req
            slots.append({
                "slot": slot, "state": "active", "rid": req.rid,
                "slo_class": req.slo_class, "deadline_s": req.deadline_s,
                "prompt_len": len(req.prompt),
                "max_new": req.max_new_tokens,
                "tokens": len(st.tokens),
                "age_s": round(now - st.submit_t, 6),
                "since_last_token_s": round(now - st.last_t, 6),
                "prefix_hit": bool(st.alloc.n_hit)
                if st.alloc is not None else False,
                "in_flight_step": inflight.get(slot) == req.rid,
                "spec_accepted": st.spec_accepted,
            })
        return {"num_slots": self.num_slots, "active": len(active),
                "free_slots": self.sched.free_slots, "slots": slots}

    def debug_kvpool(self) -> dict:
        """Paged-pool block states, fragmentation and radix-trie
        occupancy (serve/paged.py debug view); {"paged": False} on a
        dense engine."""
        if self.block_pool is None:
            return {"paged": False}
        live = [(st.req.rid, len(st.req.prompt) + len(st.tokens), st.alloc)
                for st in list(self._active.values())
                if st.alloc is not None]
        return {"paged": True, "kv_page_size": self.kv_page_size,
                **self.block_pool.debug(live)}

    def prefix_summary(self) -> dict:
        """The authoritative radix-cache residency summary a fleet
        router refreshes its approximate per-replica index from
        (GET /debug/prefix_summary): one chained fingerprint per
        resident trie node (paged.prefix_digests' chain, so membership
        answers "would block i of this prompt hit here"). Pure host
        bookkeeping over block ids — no device read, no sync. Routers
        should treat the digest SET as a full replacement: anything
        absent was LRU-evicted since the last refresh."""
        if self.block_pool is None or self.block_pool.cache is None:
            return {"enabled": False, "page": 0, "blocks": 0,
                    "digests": []}
        digests = self.block_pool.cache.digests()
        return {"enabled": True, "page": self.kv_page_size,
                "blocks": len(digests), "digests": digests}

    def debug_scheduler(self) -> dict:
        """Queue composition head-first — per-request wait, deadline
        state (the shed forecast), bucket, priority — plus per-class
        queue depths, the brownout posture, the chunked-prefill lane,
        the admission ladders and, under spec, the drafter's live
        acceptance."""
        now = time.monotonic()
        queued = []
        by_class: Dict[str, dict] = {}
        for item in self.sched.queued_items():
            meta = self._submit_meta.get(item.rid)
            waited = None if meta is None else round(now - meta[1], 6)
            queued.append({
                "rid": item.rid, "prompt_len": len(item.prompt),
                "max_new": item.max_new_tokens,
                # The no-hit bucket (bucket_for, not _suffix_bucket): a
                # debug read must not walk the radix trie the loop
                # thread owns, nor touch its LRU clocks.
                "bucket": self.sched.bucket_for(len(item.prompt)),
                "slo_class": item.slo_class,
                "priority": item.priority,
                "deadline_s": item.deadline_s,
                "waited_s": waited,
                "expired": bool(item.deadline_s is not None
                                and waited is not None
                                and waited > item.deadline_s),
            })
            # Per-priority counts, not one representative priority: a
            # class can mix explicit overrides with its default, and an
            # operator judging a brownout floor needs to see how much
            # of the class sits on each side of it.
            cls = by_class.setdefault(
                item.slo_class, {"queued": 0, "priorities": {}})
            cls["queued"] += 1
            pr = cls["priorities"]
            pr[item.priority] = pr.get(item.priority, 0) + 1
        # The migration limbo queue (ISSUE 16): exports prefilled here,
        # awaiting adoption by the decode tier. Same deadline fields as
        # the admission queue — limbo is swept by the same shed pass.
        limbo = []
        for exp in self.sched.limbo_items():
            waited = round(now - exp.submit_t, 6)
            limbo.append({
                "rid": exp.rid, "prompt_len": len(exp.req.prompt),
                "chain_blocks": len(exp.alloc.table),
                "hit_blocks": exp.alloc.n_hit,
                "slo_class": exp.req.slo_class,
                "priority": exp.priority,
                "deadline_s": exp.deadline_s,
                "waited_s": waited,
                "limbo_s": round(now - exp.export_t, 6),
                "expired": bool(exp.deadline_s is not None
                                and waited > exp.deadline_s),
            })
        out = {"queued": len(queued), "queue": queued,
               "queue_by_class": by_class,
               "role": self.role,
               "limbo": len(limbo), "limbo_queue": limbo,
               "migrated": self.migrated, "adopted": self.adopted,
               "free_slots": self.sched.free_slots,
               "active": len(self._active),
               "prefill_buckets": list(self.sched.buckets),
               "admit_buckets": list(self.admit_buckets),
               "pipeline": self.pipeline,
               "inflight_step": self._inflight is not None,
               "steps": self.steps, "shed": self.shed,
               "default_deadline_s": self.default_deadline_s,
               "preemptions": self.preemptions,
               "prefill_chunk": self.prefill_chunk,
               "chunking": [{"rid": e.req.rid, "slot": e.slot,
                             "prefilled": e.hit + e.done,
                             "prompt_len": len(e.req.prompt)}
                            for e in list(self._chunking)],
               "brownout": (None if self.brownout is None
                            else self.brownout.stats())}
        if self._spec is not None:
            out["spec"] = self._spec.debug()
        return out

    # ------------------------------------------------------------------
    # internals
    # ------------------------------------------------------------------
    def _suffix_bucket(self, req) -> int:
        """The paged wave key: the prefill bucket of the prompt MINUS
        its resident prefix (a pure probe — blocks commit in the admit
        callback). Requests sharing a hot system prompt therefore land
        together in small-suffix waves; with a cold cache this is
        exactly bucket_for(len(prompt))."""
        hit = self.block_pool.match_len(req.prompt)
        return self.sched.bucket_for(len(req.prompt) - hit)

    def _try_alloc(self, req):
        """Reserve one request's KV blocks (paged engines): the
        alloc_fail fault hook, the block_stall/block_reserve flight
        events and the no-deadlock backpressure in one place — shared
        by wave admission and the chunked-prefill lane. Returns the
        Allocation, or None (request stays queued)."""
        if (self.faults is not None
                and self.faults.fire("alloc_fail", self.steps)
                is not None):
            # Forced exhaustion: the request stays queued (the normal
            # no-deadlock backpressure), the stall is counted so the
            # admission_stall watchdog sees the same signal a real one
            # produces.
            self.block_pool.stall_steps += 1
            self.flight.record("fault", rid=req.rid, step=self.steps,
                               site="alloc_fail")
            return None
        # A migrate-flagged request reserves its PROMPT chain only: the
        # generation budget belongs to the adopting decode tier, and
        # double-reserving it here is exactly the pool pressure
        # disaggregation exists to remove from the prefill tier.
        max_new = (0 if req.rid in self._migrate_rids
                   else req.max_new_tokens)
        a = self.block_pool.admit(req.prompt, max_new)
        if a is None:
            self.flight.record(
                "block_stall", rid=req.rid, step=self.steps,
                need=self.block_pool.blocks_needed(
                    len(req.prompt), max_new),
                free=self.block_pool.free_blocks)
            return None
        self.flight.record("block_reserve", rid=req.rid,
                           step=self.steps, blocks=len(a.table),
                           hit_blocks=a.n_hit)
        return a

    def _admit_waves(self, finished: List[Result]) -> None:
        import jax.numpy as jnp

        budget = self.prefill_chunk
        # Chunked-prefill lane first (ISSUE 13): requests mid-chunking
        # are AHEAD of the queue in admission order — they were popped
        # from its head — so they get first claim on this step's budget.
        if budget is not None and self._chunking:
            self._advance_chunked(finished)
        while True:
            max_items: Optional[int] = None
            if budget is not None:
                if self._prefill_spent >= budget:
                    break
                head = self.sched.peek_head()
                if head is None:
                    break
                if self.sched.free_slots:
                    hb = (self._suffix_bucket(head) if self.paged
                          else self.sched.bucket_for(len(head.prompt)))
                    if self.paged and hb > budget:
                        # Too long for one budgeted wave: route into the
                        # chunked lane (paged prefill writes at
                        # cache_index offsets, so the split costs no new
                        # program). A block-starved head fences — FIFO.
                        if not self._start_chunked(head):
                            break
                        self._advance_chunked(finished)
                        continue
                    # Cap the wave so rung * bucket fits the remaining
                    # budget. A dense over-budget bucket (no way to
                    # split) admits alone on a fresh step.
                    remaining = budget - self._prefill_spent
                    max_items = 0
                    for r in self.admit_buckets:
                        if r * hb <= remaining:
                            max_items = r
                    if max_items == 0:
                        if self._prefill_spent:
                            break       # resume on the next step
                        max_items = 1
            allocs: List = []
            if self.paged:

                def try_alloc(req):
                    a = self._try_alloc(req)
                    if a is None:
                        return False
                    allocs.append(a)
                    return True

                wave = self.sched.next_admission_wave(
                    max_items=max_items,
                    bucket_of=self._suffix_bucket, admit=try_alloc)
            else:
                wave = self.sched.next_admission_wave(
                    max_items=max_items)
            if wave is None:
                break
            reqs, slots, bucket = wave
            if budget is not None:
                self._prefill_spent += (
                    self.sched.rung_for(len(reqs)) * bucket)
            # From here until the admission commits, the wave is in
            # limbo: popped from the queue, blocks reserved, slots
            # claimed, but not yet active. Track it so a prefill crash
            # leaves recover() enough to unwind and requeue.
            self._admitting = [
                (req, slot, allocs[i] if self.paged else None)
                for i, (req, slot) in enumerate(zip(reqs, slots))]
            k = self.sched.rung_for(len(reqs))
            self._c_waves.inc()
            wave_sid = self.tracer.begin(
                "prefill_wave", cat="prefill",
                args={"bucket": bucket, "rung": k, "wave": len(reqs),
                      "rids": [r.rid for r in reqs]})
            self._admitting_span = wave_sid
            # Host staging for the wave — the ONLY host->device uploads
            # the engine performs (three arrays, the packed layout above
            # _meta_width); the per-token loop stages nothing.
            nb = self.slot_blocks if self.paged else 0
            prompts = np.zeros((k, bucket), np.int32)
            meta = np.zeros((k, self._meta_width), np.int32)
            # Padding rows point at slot id num_slots (and, paged, an
            # all-sentinel table row): out of range, so the pool writes
            # and the state scatter all drop them.
            meta[:, nb] = self.num_slots
            meta[:, nb + 1] = 1                     # true_len floor
            if self.paged:
                meta[:, :nb] = self.kv_pool_blocks
            fmeta = np.zeros((k, 2), np.float32)
            fmeta[:, 1] = 1.0                       # top_p
            for i, (req, slot) in enumerate(zip(reqs, slots)):
                meta[i, nb] = slot
                meta[i, nb + 1] = len(req.prompt)
                meta[i, nb + 2] = req.top_k
                meta[i, nb + 3] = req.seed
                fmeta[i] = (req.temperature, req.top_p)
                if self.paged:
                    a = allocs[i]
                    hit = a.n_hit * self.kv_page_size
                    sfx = req.prompt[hit:]
                    prompts[i, :len(sfx)] = sfx
                    meta[i, :len(a.table)] = a.table
                    meta[i, nb + 4] = hit
                else:
                    prompts[i, :len(req.prompt)] = req.prompt
            prompts_dev = self._stage(prompts)
            meta_dev = self._stage(meta)
            fmeta_dev = self._stage(fmeta)
            if (self.faults is not None
                    and self.faults.fire("prefill_exc", self.steps)
                    is not None):
                self.flight.record("fault", step=self.steps,
                                   site="prefill_exc",
                                   rids=[r.rid for r in reqs])
                raise FaultInjected("prefill_exc", self.steps)
            self._pool, toks = self._prefill(self.params, self._pool,
                                             prompts_dev, meta_dev,
                                             fmeta_dev)
            self.host_dispatches["prefill"] += 1
            # First tokens flow device-to-device into the slot state;
            # the host copy below is for result lists and finish checks
            # only.
            self._state = self._admit(self._state, toks, meta_dev,
                                      fmeta_dev)
            self.host_dispatches["admit"] += 1
            if self._spec is not None and self._spec.drafter.kind == "device":
                # The drafter ingests the SAME staged wave into its own
                # pool (its frontier state is the engine's pos/tok, so
                # prompt K/V is all it needs). Paged drafters share the
                # engine's block ids: one table, two parallel pools.
                self._spec.drafter.prefill_wave(prompts_dev, meta_dev)
            # jaxlint: disable=host-sync -- first-token readback feeds results/eos checks
            toks_host = np.asarray(toks)
            if (self.faults is not None
                    and self.faults.fire("scatter_corrupt", self.steps)
                    is not None):
                # A corrupted slot scatter surfaces as garbage first
                # tokens at the wave readback — same detection boundary
                # as a poisoned decode step.
                self.flight.record("fault", step=self.steps,
                                   site="scatter_corrupt")
                toks_host = np.full(k, self.cfg.vocab_size, np.int32)
            now = time.monotonic()
            self._rate_ring.append((now, len(reqs)))
            poisoned_wave = False
            for i, (req, slot) in enumerate(zip(reqs, slots)):
                poisoned_wave |= self._commit_admission(
                    req, slot, allocs[i] if self.paged else None,
                    int(toks_host[i]), bucket=bucket, rung=k, now=now,
                    finished=finished)
            # Wave committed: nothing is in limbo anymore.
            self._admitting = []
            self._admitting_span = None
            if poisoned_wave:
                self._mark_poison("poisoned_prefill",
                                  rids=[r.rid for r in reqs])
            self.tracer.end(wave_sid)

    def _commit_admission(self, req: Request, slot: int, alloc,
                          first_tok: int, *, bucket: int, rung: int,
                          now: float, finished: List[Result]) -> bool:
        """Per-request admission commit — the bookkeeping between the
        first-token readback and the row going active, shared by wave
        admission and the chunked-prefill lane's final chunk. Returns
        whether the first token was poisoned (the caller aggregates
        into one _mark_poison)."""
        self.admitted += 1
        poisoned = not 0 <= first_tok < self.cfg.vocab_size
        resumed = req.rid in self._resumed
        if not poisoned:
            self.tokens_generated += 1
        sub_step, sub_t, queued_sid = self._submit_meta.pop(req.rid)
        self._queue_wait.observe(self.steps - sub_step)
        if not resumed and not poisoned:
            # A resumed request's first token predates the
            # recovery/preemption — re-observing submit->now as "TTFT"
            # would poison the spike watchdog's baseline; the recovery
            # histograms carry that latency instead. A POISONED first
            # token was discarded: its latency describes nothing the
            # client ever received.
            self._ttft.observe(now - sub_t)
            self.watchdog.on_ttft(now - sub_t)
        hit_toks = (alloc.n_hit * self.kv_page_size
                    if alloc is not None else 0)
        if (self.paged and self.block_pool.cache is not None
                and not resumed and not poisoned):
            # The by-prefix-outcome TTFT split exists only when the
            # prefix cache does — a cache-less engine must not mint
            # placeholder {prefix=} series (the /metrics label-hygiene
            # rule).
            self._ttft_prefix.labels(
                prefix="hit" if hit_toks else "miss").observe(
                    now - sub_t)
        self.tracer.end(queued_sid,
                        {"wait_steps": self.steps - sub_step})
        self.flight.record("admit", rid=req.rid, step=self.steps,
                           slot=slot, bucket=bucket, rung=rung,
                           wait_steps=self.steps - sub_step)
        self.flight.record(
            "prefill", rid=req.rid, step=self.steps,
            prefix="hit" if hit_toks else "miss",
            hit_tokens=hit_toks,
            suffix_tokens=len(req.prompt) - hit_toks)
        if req.rid in self._migrate_rids:
            self._migrate_rids.discard(req.rid)
            finishes_here = (
                poisoned
                or (req.eos_id is not None and first_tok == req.eos_id)
                or req.max_new_tokens <= 1)
            if not finishes_here and alloc is not None:
                import jax.numpy as jnp

                # EXPORT (ISSUE 16): the prompt's K/V is fully written —
                # including the partial tail block — and the first token
                # is in hand; everything a decode tier needs. Release
                # the slot NOW, host and device (commit runs before this
                # step's decode dispatch, so the row never decodes here
                # and the chain is never written again — the bit-
                # identity the adoption copy depends on), and park the
                # chain + token in migration limbo. The request's
                # terminal belongs to whoever adopts (or to the deadline
                # sweep / abort path if nobody does). A poisoned,
                # instantly-finished, or alloc-less first token falls
                # through to the colocated path below instead: migration
                # is an optimization, never a correctness fork.
                self.sched.release(slot)
                self._state = self._release(self._state,
                                            jnp.asarray(slot, jnp.int32))
                self.host_dispatches["release"] += 1
                exp = _Export(req=req, alloc=alloc, first_tok=first_tok,
                              export_t=now, submit_t=sub_t,
                              submit_step=sub_step)
                self.sched.park_limbo(exp)
                self.flight.record(
                    "export", rid=req.rid, step=self.steps,
                    chain_blocks=len(alloc.table),
                    hit_blocks=alloc.n_hit,
                    prompt_len=len(req.prompt))
                return False
        gen_sid = self.tracer.begin(
            "generate", cat="request", rid=req.rid,
            args={"slot": slot, "bucket": bucket})
        st = _Active(req=req, slot=slot,
                     tokens=[] if poisoned else [first_tok],
                     first_token_t=now,
                     submit_t=sub_t, last_t=now,
                     span=gen_sid, alloc=alloc)
        self._active[slot] = st
        if not poisoned:
            done = self._maybe_finish(st)
            if done is not None:
                finished.append(done)
        return poisoned

    # ------------------------------------------------------------------
    # chunked prefill (ISSUE 13): a long (suffix) prompt lands in the
    # pool across several bucket-shaped dispatches, interleaved with
    # decode steps — the admission pacing that keeps a prefill storm
    # from stalling every active row's TPOT.
    # ------------------------------------------------------------------
    def _start_chunked(self, head: Request) -> bool:
        """Claim the queue head into the chunked-prefill lane: blocks
        reserved (full reservation — the no-deadlock contract), a slot
        claimed (so traffic behind it cannot starve it out of one), no
        device work yet. False when block-starved: the head stays
        queued and fences admission, exactly like a starved wave."""
        alloc = self._try_alloc(head)
        if alloc is None:
            return False
        popped = self.sched.pop_head()
        assert popped is head
        slot = self.sched.take_slot()
        self._chunking.append(_Chunking(
            req=head, slot=slot, alloc=alloc,
            hit=alloc.n_hit * self.kv_page_size))
        return True

    def _advance_chunked(self, finished: List[Result]) -> None:
        """Dispatch prefill chunks for the in-progress chunked
        admissions, oldest first, until this step's budget is spent.
        Every chunk is an ordinary (1, bucket) prefill program — the
        suffix-bucket grid already compiled — writing at cache_index =
        hit + done (the prefix-hit machinery with a moving hit), so
        intermediate chunks need no admit scatter and no readback: the
        sampled token of an incomplete prompt is discarded unread. The
        FINAL chunk passes the real slot and true_len, samples the
        first token from fold_in(seed, true_len) — token-identical to a
        monolithic prefill (pinned) — and commits the admission."""
        import jax.numpy as jnp

        budget = self.prefill_chunk
        for entry in list(self._chunking):
            while self._prefill_spent < budget:
                req = entry.req
                remaining = len(req.prompt) - entry.hit - entry.done
                # Size the chunk to the REMAINING step budget, not the
                # full one — waves admitted earlier this step already
                # spent part of it, and a full-size chunk on top would
                # run the step up to ~2x the TPOT-protection budget.
                c = min(remaining, budget - self._prefill_spent)
                bucket = self.sched.bucket_for(c)
                if bucket > budget - self._prefill_spent:
                    # Bucket padding would overshoot: resume on the
                    # next step's fresh budget (which always fits one
                    # full chunk — prefill_chunk is itself a bucket).
                    # Same rule as the wave path's over-budget fence.
                    return
                final = c == remaining
                start = entry.hit + entry.done
                nb = self.slot_blocks
                prompts = np.zeros((1, bucket), np.int32)
                prompts[0, :c] = req.prompt[start:start + c]
                meta = np.zeros((1, self._meta_width), np.int32)
                meta[0, :nb] = self.kv_pool_blocks
                meta[0, :len(entry.alloc.table)] = entry.alloc.table
                # Intermediate chunks carry the sentinel slot id: only
                # the final chunk's sampled token may reach the slot
                # state (and only the final chunk is ever read back).
                meta[0, nb] = entry.slot if final else self.num_slots
                meta[0, nb + 1] = len(req.prompt) if final else start + c
                meta[0, nb + 2] = req.top_k
                meta[0, nb + 3] = req.seed
                meta[0, nb + 4] = start
                fmeta = np.zeros((1, 2), np.float32)
                fmeta[0] = (req.temperature, req.top_p)
                prompts_dev = self._stage(prompts)
                meta_dev = self._stage(meta)
                fmeta_dev = self._stage(fmeta)
                if (self.faults is not None
                        and self.faults.fire("prefill_exc", self.steps)
                        is not None):
                    self.flight.record("fault", rid=req.rid,
                                       step=self.steps,
                                       site="prefill_exc")
                    raise FaultInjected("prefill_exc", self.steps)
                self._pool, toks = self._prefill(
                    self.params, self._pool, prompts_dev, meta_dev,
                    fmeta_dev)
                self.host_dispatches["prefill"] += 1
                self._prefill_spent += bucket
                if (self._spec is not None
                        and self._spec.drafter.kind == "device"):
                    # The drafter's pool tracks the engine's chunk for
                    # chunk (same staged arrays, same table) so a later
                    # draft reads complete prompt K/V.
                    self._spec.drafter.prefill_wave(prompts_dev, meta_dev)
                entry.done += c
                self.flight.record(
                    "prefill_chunk", rid=req.rid, step=self.steps,
                    n=c, prefilled=entry.hit + entry.done,
                    of=len(req.prompt))
                if not final:
                    continue
                # Final chunk: admit into the slot and commit.
                self._state = self._admit(self._state, toks, meta_dev,
                                          fmeta_dev)
                self.host_dispatches["admit"] += 1
                # jaxlint: disable=host-sync -- the admission first-token readback (same contract as the wave path)
                first = int(np.asarray(toks)[0])
                if (self.faults is not None
                        and self.faults.fire("scatter_corrupt",
                                             self.steps) is not None):
                    self.flight.record("fault", step=self.steps,
                                       site="scatter_corrupt")
                    first = self.cfg.vocab_size
                now = time.monotonic()
                self._rate_ring.append((now, 1))
                self._chunking.remove(entry)
                if self._commit_admission(req, entry.slot, entry.alloc,
                                          first, bucket=bucket, rung=1,
                                          now=now, finished=finished):
                    self._mark_poison("poisoned_prefill",
                                      rids=[req.rid])
                break
            else:
                return    # budget spent; later entries wait their turn

    # ------------------------------------------------------------------
    # priority preemption (ISSUE 13): when the head of the queue would
    # miss its deadline waiting on slots or blocks, evict the lowest-
    # priority active victim. The victim's blocks — prompt AND
    # generated — are donated to the radix cache and it requeues with
    # prompt' = prompt + tokens-so-far through the recovery _Resume
    # path, so its resume is a prefix hit and greedy output is
    # token-identical to an unpreempted run (pinned by test).
    # ------------------------------------------------------------------
    def _head_blocks_available(self, head: Request) -> bool:
        """Could the head's reservation be covered right now (free +
        evictable blocks, minus its prefix hit)? A pure probe — nothing
        commits."""
        need = self.block_pool.blocks_needed(len(head.prompt),
                                             head.max_new_tokens)
        hit_blocks = self.block_pool.match_len(head.prompt) \
            // self.kv_page_size
        avail = self.block_pool.free_blocks
        if self.block_pool.cache is not None:
            avail += self.block_pool.cache.evictable()
        return need - hit_blocks <= avail

    def _steps_per_s(self) -> Optional[float]:
        """Recent dispatch rate in rate-ring entries/sec — the unit the
        queue-wait histogram counts in, shared by the preemption policy
        and the Retry-After hint so the two can never drift. None while
        the signal is cold. list(deque): single C-level copy — callers
        may run on an HTTP handler thread while the loop appends."""
        ring = list(self._rate_ring)
        if len(ring) < 2:
            return None
        dt = ring[-1][0] - ring[0][0]
        if dt <= 0:
            return None
        return (len(ring) - 1) / dt

    def _projected_slot_free_s(self) -> Optional[float]:
        """Seconds until a slot frees NATURALLY: the smallest remaining
        budget over active rows, converted through the recent PER-ROW
        token rate. Token rate, not dispatch rate: under scan_k (or
        spec) one retire lands several tokens per row, so dividing
        remaining TOKENS by the dispatch rate would overestimate the
        wait by the chunk length and preempt over-eagerly on exactly
        the engines PR 12 sped up. None while the signal is cold."""
        rate = self._recent_rate()
        if rate is None or rate <= 0 or not self._active:
            return None
        per_row = rate / len(self._active)
        min_rem = min(st.req.max_new_tokens - len(st.tokens)
                      for st in self._active.values())
        return max(0, min_rem) / per_row

    def _maybe_preempt(self) -> None:
        """One preemption per step, at most: evict the lowest-priority
        active victim when the queue head (highest priority queued) is
        blocked on slots/blocks AND its deadline slack no longer covers
        the projected natural wait. Deadline-less heads never preempt
        (there is no miss to prevent); equal-or-higher-priority victims
        never exist by definition. Also hosts the ``preempt_storm``
        fault site, which skips the policy and forces an eviction."""
        if self.faults is not None and self._active:
            f = self.faults.fire("preempt_storm", self.steps)
            if f is not None:
                victim = min(self._active.values(),
                             key=lambda s: (s.req.priority, s.req.rid))
                self.flight.record("fault", rid=victim.req.rid,
                                   step=self.steps, site="preempt_storm")
                self._preempt(victim, cause="preempt_storm")
        if not self.preemption or not self._active:
            return
        head = self.sched.peek_head()
        if head is None or head.deadline_s is None:
            return
        if self.sched.free_slots and (
                self.block_pool is None
                or self._head_blocks_available(head)):
            return          # admissible this step without violence
        meta = self._submit_meta.get(head.rid)
        if meta is None:
            return
        now = time.monotonic()
        waited = now - meta[1]
        slack = head.deadline_s - waited
        if slack <= 0:
            return          # already doomed: it sheds — never waste a
        #                     victim's work on a request past saving
        proj = self._projected_slot_free_s()
        if proj is not None:
            if slack > proj:
                return      # the natural wait still makes the deadline
        elif waited < 0.5 * head.deadline_s:
            # Cold rate signal: only preempt once the head has burned
            # half its budget waiting — conservative, but deterministic.
            return
        victims = [st for st in self._active.values()
                   if st.req.priority < head.priority]
        if not victims:
            return
        # Lowest priority first; among equals the LATEST admission (the
        # least sunk work — and its resume re-prefills the least).
        victim = min(victims, key=lambda st: (st.req.priority,
                                              -st.req.rid))
        self._preempt(victim, cause="deadline")

    def _preempt(self, st: _Active, cause: str) -> None:
        """Evict one active request in favor of the queue: park its
        slot, donate its prompt+generated blocks to the radix cache,
        and requeue it at the HEAD of its priority class (requeue_front
        — seniority preserved, same as a crash-recovery victim; it can
        never bounce back and evict its evictor, whose priority is
        strictly higher by the victim-selection rule) with prompt' =
        prompt + tokens-so-far via the _Resume stitch, so the terminal
        Result reads as one uninterrupted request. Not a terminal: no
        ``evict``/``finish`` event — the fuzz pin holds."""
        import jax.numpy as jnp

        req = st.req
        del self._active[st.slot]
        self.sched.release(st.slot)
        if self._inflight is not None:
            # Drop the in-flight snapshot's claim on this slot: the
            # victim's rid is about to re-enter the queue, and if it
            # re-admits into the SAME slot before the lagged readback,
            # the ride-along tokens would double-count. They are
            # recomputed identically on resume (position-keyed
            # sampling), so dropping them costs only the lane work.
            self._inflight[1].pop(st.slot, None)
        self._state = self._release(self._state,
                                    jnp.asarray(st.slot, jnp.int32))
        self.host_dispatches["release"] += 1
        donated = 0
        if st.alloc is not None:
            donated = self.block_pool.release(st.alloc,
                                              generated=st.tokens)
        base = self._resumed.get(req.rid)
        orig_prompt = base.prompt if base is not None else req.prompt
        pre = (base.tokens if base is not None else []) + st.tokens
        new_req = replace(req, prompt=req.prompt + tuple(st.tokens),
                          max_new_tokens=req.max_new_tokens
                          - len(st.tokens))
        self._resumed[req.rid] = _Resume(prompt=orig_prompt, tokens=pre,
                                         submit_t=st.submit_t)
        self.tracer.end(st.span, {"preempted": True, "cause": cause})
        sid = self.tracer.begin("queued", cat="request", rid=req.rid,
                                args={"preempted": True})
        self._submit_meta[req.rid] = (self.steps, st.submit_t, sid)
        self.preemptions += 1
        self.flight.record("preempt", rid=req.rid, step=self.steps,
                           cause=cause, slot=st.slot,
                           salvaged_tokens=len(pre),
                           donated_blocks=donated,
                           priority=req.priority)
        self.sched.requeue_front([new_req])

    def _spec_step(self, finished: List[Result]) -> None:
        """One speculative round: collect per-row drafts (host prompt
        lookup, or the compiled ModelDrafter scan), run the fixed-shape
        verify, and retire the accepted prefix + one fresh token per
        row — with per-token eos/length checks so a mid-chunk eos
        truncates exactly where the non-spec loop would have stopped.

        Per-row draft lengths are capped at remaining_budget - 1: the
        verify always emits accepted+1 tokens, so the cap guarantees a
        row can never overshoot max_new_tokens (greedy parity then
        needs no trimming) nor write an accepted token past max_len
        (submit already bounds prompt + max_new there)."""
        import jax

        # Local handle: _disable_spec (drafter-fault streak) nulls
        # self._spec mid-call; the already-dispatched verify still
        # retires through this runner.
        runner = self._spec
        k = runner.k
        drafter = runner.drafter
        verify_sid = self.tracer.begin(
            "spec_verify", cat="spec",
            args={"k": k, "rows": len(self._active)})
        caps = {slot: min(k, st.req.max_new_tokens - len(st.tokens) - 1)
                for slot, st in self._active.items()}
        dl = np.zeros(self.num_slots, np.int32)
        drafts = np.zeros((self.num_slots, k), np.int32)
        try:
            if (self.faults is not None
                    and self.faults.fire("drafter_fault", self.steps)
                    is not None):
                raise FaultInjected("drafter_fault", self.steps)
            if drafter.kind == "host":
                # The ONLY per-step host->device transfer spec mode adds:
                # the (num_slots, k) + (num_slots,) int32 blocks ride the
                # verify dispatch itself (numpy args into jit measure
                # ~25% cheaper per CPU verify than a separate device_put
                # round).
                for slot, st in self._active.items():
                    if caps[slot] <= 0:
                        continue
                    prop = drafter.propose(list(st.req.prompt) + st.tokens,
                                           caps[slot])
                    dl[slot] = len(prop)
                    drafts[slot, :len(prop)] = prop
            else:
                drafts = drafter.draft(self._state["tok"],
                                       self._state["pos"],
                                       self._state["active"],
                                       table=self._state.get("table"))
                for slot, cap in caps.items():
                    dl[slot] = max(cap, 0)
        except Exception as e:
            # Degrade, don't die: a drafter failure turns THIS step into
            # plain decode (zero drafts -> the verify's always-emitted
            # fresh token is the only output), and a streak of them
            # disables speculation for good — correctness and uptime
            # never depend on the drafter.
            self.drafter_faults += 1
            self._drafter_fault_streak += 1
            dl[:] = 0
            drafts = np.zeros((self.num_slots, k), np.int32)
            self.flight.record("drafter_fault", step=self.steps,
                               error=f"{type(e).__name__}: {e}",
                               streak=self._drafter_fault_streak)
            if self._drafter_fault_streak >= self.spec_fault_tolerance:
                self._disable_spec(
                    f"{self._drafter_fault_streak} consecutive drafter "
                    f"faults (last: {type(e).__name__}: {e})")
        else:
            self._drafter_fault_streak = 0
        # Under TP the draft block replicates over the mesh explicitly;
        # tp == 1 keeps the bare-numpy dispatch (measurably cheaper on
        # the CPU floor, PR 4). dl/drafts stay host-resident numpy for
        # the per-slot accounting below either way.
        drafts_in = drafts if self._mesh is None else self._stage(drafts)
        dl_in = dl if self._mesh is None else self._stage(dl)
        self._pool, self._state, emitted, counts, accepted = \
            runner.verify(self.params, self._pool, self._state,
                          drafts_in, dl_in)
        self.steps += 1
        self.host_dispatches["verify"] += 1
        runner.steps += 1
        # ONE batched readback for the whole retire (synchronous by
        # design — docstring; three separate np.asarray blocks cost a
        # measurable slice of the verify step on CPU).
        # jaxlint: disable=host-sync -- the spec retire: synchronous by design (docstring)
        emit_host, counts_host, acc_host = jax.device_get(
            (emitted, counts, accepted))
        if (self.faults is not None
                and self.faults.fire("nan_logits", self.steps) is not None):
            # The spec twin of the decode-branch injection: the verify's
            # emitted tokens are what the retire reads back (emit_host
            # is already host-resident — the device_get above).
            self.flight.record("fault", step=self.steps, site="nan_logits")
            emit_host = np.full(np.shape(emit_host), self.cfg.vocab_size,
                                np.int32)
        now = time.monotonic()
        n_kept = 0
        poisoned_slots: List[int] = []
        for slot, st in list(self._active.items()):
            c = int(counts_host[slot])
            if c <= 0:
                continue
            acc = int(acc_host[slot])
            toks = emit_host[slot, :c].tolist()
            if any(not 0 <= t < self.cfg.vocab_size for t in toks):
                # Poisoned verify output: keep the row's clean tokens,
                # let the supervisor rebuild (same contract as _retire,
                # including the unsupervised strike backstop).
                poisoned_slots.append(slot)
                st.poison_strikes += 1
                if st.poison_strikes >= POISON_STRIKE_LIMIT:
                    self._fail_row(st, "persistent_poison", finished)
                continue
            if dl[slot] > 0:
                runner.drafted += int(dl[slot])
                runner.accepted += acc
                self._spec_accept_len.observe(acc)
                st.spec_accepted += acc
            if st.req.eos_id is not None and st.req.eos_id in toks:
                # eos mid-chunk: the verify's tokens after it belong past
                # the finish and are dropped — the spec twin of the
                # pipelined ride-along drop.
                toks = toks[:toks.index(st.req.eos_id) + 1]
            st.tokens.extend(toks)
            st.poison_strikes = 0      # consecutive means consecutive
            st.last_t = now
            self.flight.record("retire", rid=st.req.rid, step=self.steps,
                               n=len(toks), accepted=acc)
            n_kept += len(toks)
            done = self._maybe_finish(st)
            if done is not None:
                finished.append(done)
        if poisoned_slots:
            self._mark_poison("poisoned_step", slots=poisoned_slots)
        self.tokens_generated += n_kept
        self._rate_ring.append((now, n_kept))
        self.tracer.end(verify_sid,
                        {"emitted": n_kept,
                         "drafted": int(dl.sum()),
                         "accepted": int(acc_host.sum())})

    # A host dispatch's fixed overhead, in units of one fused scan
    # step's device time — the rung policy's exchange rate between
    # "fewer dispatches" and "wasted lane-steps past a row's budget".
    # PR 9 measured ~180us per staging upload against sub-100us fused
    # steps on the CPU floor; 2.0 is a deliberately conservative
    # middle that also behaves on TPUs (where the fixed cost dominates
    # tiny-step compute even harder). Exposed as an attribute so
    # operators can re-pin it from a measured profile.
    scan_dispatch_cost_steps = 2.0

    def _next_chunk(self) -> int:
        """The next dispatch's scan-chunk length, from the scan_rungs
        ladder — 0 when every live row's budget is already covered by
        computed tokens (read back + the chunk in flight), meaning a
        dispatch could only produce ride-along garbage.

        The rung maximizes USEFUL lane-steps per unit wall time:
        sum_rows min(remaining, r) / (dispatch_cost + r). When every
        row has budget to burn this saturates at the top rung (the
        fewest dispatches); when most rows are a token or two from
        done it shrinks toward 1 instead of spending k lane-steps to
        harvest one token per row. A row the chunk overruns just
        truncates at readback (the same machinery eos overruns use —
        eos is host knowledge by design and the one overrun no policy
        here can see). The choice never changes the token stream:
        chunks are dispatch boundaries, not sampling state, so greedy
        outputs are identical across every scan_k (pinned by test).
        eos can finish a row EARLIER than its budget, never later, so
        the length-only remaining test never skips a needed step."""
        inflight = self._inflight
        inflight_slots = inflight[1] if inflight is not None else {}
        inflight_len = inflight[4] if inflight is not None else 0
        rems = []
        for slot, st in self._active.items():
            rem = st.req.max_new_tokens - len(st.tokens)
            if inflight_slots.get(slot) == st.req.rid:
                rem -= inflight_len
            if rem > 0:
                rems.append(rem)
        if not rems:
            return 0
        # Brownout level >= 1 caps the rung (serve/brownout.py): shorter
        # chunks, finer admission interleaving, less finish-lag waste —
        # a policy input only, never a new shape (the cap selects from
        # the compiled ladder).
        rungs = self.scan_rungs
        if self.scan_cap is not None:
            rungs = [r for r in rungs if r <= self.scan_cap] or rungs[:1]
        cost = self.scan_dispatch_cost_steps
        best, best_score = 1, -1.0
        for r in rungs:
            score = sum(min(rem, r) for rem in rems) / (cost + r)
            if score >= best_score:      # ties go to the larger rung
                best, best_score = r, score
        return best

    def _retire(self, inflight: Tuple[object, Dict[int, int], int, int,
                                      int],
                finished: List[Result]) -> None:
        """Read one dispatched step's (or scan chunk's) tokens back and
        apply the lagged finish/eviction decisions. A slot whose
        occupant is no longer the snapshot's rid was evicted after
        dispatch — its ride-along tokens belong to nobody and are
        dropped (the host half of the lag-k finish machinery; the
        device active mask is the other half). Within a live row's
        chunk, tokens walk in order and truncate at the first of:
        budget reached (the row overran mid-chunk — surplus dropped),
        eos (everything after belongs past the finish), or the poison
        sentinel (everything after was computed FROM garbage — the
        clean prefix is kept, the strike/recovery machinery takes the
        rest, and the supervisor unwinds the mid-scan chunk through
        the ordinary requeue path)."""
        toks, snapshot, sid, chunk, _ = inflight
        # jaxlint: disable=host-sync -- the pipelined readback: one step/chunk behind dispatch
        nxt = np.asarray(toks)
        if nxt.ndim == 1:
            nxt = nxt[None, :]           # (1, S): the scan_k == 1 shape
        now = time.monotonic()
        n_live = 0
        poisoned_slots: List[int] = []
        for slot, rid in snapshot.items():
            st = self._active.get(slot)
            if st is None or st.req.rid != rid:
                continue
            kept = 0
            poisoned = False
            for j in range(nxt.shape[0]):
                if len(st.tokens) >= st.req.max_new_tokens:
                    break                # mid-chunk budget overrun
                tok = int(nxt[j, slot])
                if not 0 <= tok < self.cfg.vocab_size:
                    # The in-program isfinite sentinel (or an injected
                    # poison): this token — and every later one in the
                    # chunk, each sampled from state downstream of the
                    # garbage — must never reach the request's output.
                    poisoned = True
                    break
                st.tokens.append(tok)
                kept += 1
                if (st.req.eos_id is not None
                        and tok == st.req.eos_id):
                    break                # mid-chunk eos: exact truncate
            if kept:
                st.last_t = now
                n_live += kept
                # One flight event per retired (row, chunk) — n tokens
                # at once under scan_k, with the chunk index, so
                # per-token TPOT stays derivable from the JSONL.
                ev = {"rid": rid, "step": self.steps, "n": kept}
                if self.scan_k > 1:
                    ev["chunk"] = chunk
                self.flight.record("retire", **ev)
            if poisoned:
                poisoned_slots.append(slot)
                st.poison_strikes += 1
                if st.poison_strikes >= POISON_STRIKE_LIMIT:
                    self._fail_row(st, "persistent_poison", finished)
                continue
            if kept:
                st.poison_strikes = 0    # consecutive means consecutive
                done = self._maybe_finish(st)
                if done is not None:
                    finished.append(done)
        if poisoned_slots:
            self._mark_poison("poisoned_step", slots=poisoned_slots)
        self.tokens_generated += n_live
        self._rate_ring.append((now, n_live))
        self.tracer.end(sid, {"live_tokens": n_live})

    def _recent_rate(self) -> Optional[float]:
        # list(deque): single C-level copy — stats() may run on an HTTP
        # handler thread while the engine loop appends, and Python-level
        # deque iteration would raise "mutated during iteration".
        ring = list(self._rate_ring)
        if len(ring) < 2:
            return None
        t0, t1 = ring[0][0], ring[-1][0]
        if t1 <= t0:
            return None
        # Tokens attributed to the window AFTER its first timestamp.
        toks = sum(n for _, n in ring[1:])
        return toks / (t1 - t0)

    def reset_latency_stats(self) -> None:
        """Clear the TTFT/TPOT/queue-wait/rate windows (and the span
        ring) — benchmarks call this between warmup and the timed
        workload so the reported percentiles describe the measured
        traffic, not compile-time."""
        self._ttft.reset()
        self._ttft_prefix.reset()
        self._tpot.reset()
        self._queue_wait.reset()
        self._rate_ring.clear()
        self._spec_accept_len.reset()
        self._spec_req_accepted.reset()
        self.tracer.clear()
        # The SLO ledger, flight ring and the watchdog's TTFT baseline
        # describe the measured traffic too — warmup requests are
        # synthetic, deadline-less, and compile-time slow.
        self.slo.reset()
        self.flight.clear()
        self.watchdog.reset()
        if self.block_pool is not None:
            # Hit rates and capacity means should describe the measured
            # workload too — warmup prompts are synthetic and all-miss.
            self.block_pool.reset_ledger()
        if self._spec is not None:
            # Acceptance rate should describe the measured workload too —
            # warmup prompts are degenerate (all-zero) and would skew it.
            self._spec.steps = 0
            self._spec.drafted = 0
            self._spec.accepted = 0

    def warm_scan_rungs(self) -> None:
        """Compile EVERY scan-rung megaprogram by dispatching each rung
        once over the parked slot state — no synthetic requests, no
        reasoning about which remaining-budget mixes the chunk policy
        can reach (ties and mixed-row scores make that set subtle).
        Parked rows are harmless to dispatch: their writes land at
        their own row's position 0 (dense — overwritten by the next
        occupant's prefill before any read, the stale-tail argument) or
        drop on the sentinel block-table entries (paged), pos stays
        frozen, and the garbage tokens are never read back. serve
        __main__ --warmup=full and the bench warmups call this; a rung
        left uncompiled would be a post-freeze retrace outage the first
        time live traffic's budget mix makes the policy pick it.
        Idle-only (enforced): on a busy engine the rung dispatches
        would advance live rows' device frontiers with no readback,
        silently dropping tokens from their outputs."""
        if self._active or self._inflight is not None:
            raise RuntimeError(
                "warm_scan_rungs on a busy engine: active rows' "
                "frontiers would advance without a readback")
        for r in self.scan_rungs:
            self._pool, self._state, _ = self._decode(
                self.params, self._pool, self._state, r)

    def reset_prefix_cache(self) -> None:
        """Drop every cached prefix block back to the free list. Only
        legal on an idle engine (no active requests hold cache refs) —
        warmup calls this so its synthetic prompts can never serve a
        hit to live traffic, and tests use it to force cold-cache
        baselines. The hit/miss token ledger resets with it (the rate
        should describe the traffic after the reset)."""
        if not self.paged:
            return
        if self._active:
            raise RuntimeError(
                "reset_prefix_cache on a busy engine: active requests "
                "hold references into the radix cache")
        self.block_pool.reset_cache()

    def _maybe_finish(self, state: _Active) -> Optional[Result]:
        import jax.numpy as jnp

        req = state.req
        reason = None
        if (req.eos_id is not None and state.tokens
                and state.tokens[-1] == req.eos_id):
            reason = "eos"
        elif len(state.tokens) >= req.max_new_tokens:
            reason = "length"
        if reason is None:
            return None
        now = time.monotonic()
        del self._active[state.slot]
        self.sched.release(state.slot)
        self.flight.record("evict", rid=req.rid, step=self.steps,
                           slot=state.slot)
        # Park the idle row on device; queued after any in-flight step,
        # so the ride-along step (if one is in flight) still reads the
        # pre-release state it was dispatched with.
        self._state = self._release(self._state,
                                    jnp.asarray(state.slot, jnp.int32))
        self.host_dispatches["release"] += 1
        prefix_digest: tuple = ()
        if state.alloc is not None:
            # Host block release: deref the hit chain, DONATE the full
            # prompt blocks to the radix cache, free the rest. Safe even
            # with a ride-along decode step in flight: that step was
            # dispatched with the old table and only ever writes the
            # row's generated-region frontier block — never a donated
            # (prompt-only) block — and any reallocation's prefill
            # queues behind it, overwriting its garbage block-for-block.
            self.block_pool.release(state.alloc)
            if self.block_pool.cache is not None:
                # What this replica now caches, as chained block
                # fingerprints (paged.prefix_digests — host-side hashing
                # of the already-host-resident prompt tuple, no sync):
                # the fleet router's affinity signal, reported on the
                # Result, the flight terminal, and the /generate body.
                from nanosandbox_tpu.serve.paged import prefix_digests
                prefix_digest = tuple(
                    prefix_digests(req.prompt, self.kv_page_size))
        self.completed += 1
        self._c_completed.labels(reason=reason).inc()
        # Stitch a recovered request back together: the Result (and its
        # SLO/flight accounting) must read as ONE uninterrupted request
        # — original prompt, pre-fault tokens + post-recovery tokens,
        # end-to-end latency from the original submit.
        prompt_out, tokens_out, resumed = self._unstitch(
            req.rid, req, state.tokens)
        self.tracer.end(state.span, {"tokens": len(tokens_out),
                                     "finish_reason": reason})
        # SLO + flight terminal: end-to-end latency vs deadline, tokens
        # into the goodput ledger, the exactly-once `finish` event.
        elapsed = now - state.submit_t
        prefix = ("hit" if state.alloc is not None and state.alloc.n_hit
                  else "miss")
        met = self.slo.record_finish(req.slo_class,
                                     tokens=len(tokens_out),
                                     elapsed_s=elapsed,
                                     deadline_s=req.deadline_s,
                                     prefix=prefix)
        fin = {"reason": reason, "tokens": len(tokens_out),
               "e2e_s": round(elapsed, 6)}
        if resumed:
            fin["resumed"] = True
        if met is not None:
            fin["deadline_met"] = met
        if prefix_digest:
            fin["prefix_digest"] = list(prefix_digest)
        self.flight.record("finish", rid=req.rid, step=self.steps, **fin)
        if self._spec is not None:
            self._spec_req_accepted.observe(state.spec_accepted)
        if len(state.tokens) > 1:
            self._tpot.observe((now - state.first_token_t)
                               / (len(state.tokens) - 1))
        return Result(rid=req.rid, prompt=prompt_out, tokens=tokens_out,
                      finish_reason=reason, prefix_digest=prefix_digest)

    # ------------------------------------------------------------------
    # fault detection, quarantine & crash-safe recovery (ISSUE 11).
    # The engine owns the MECHANISM (detect poison, rebuild device
    # state, re-admit victims); serve/recovery.py's EngineSupervisor
    # owns the POLICY (when to recover, backoff, permanent-failure
    # escalation).
    # ------------------------------------------------------------------
    def _mark_poison(self, kind: str, **info) -> None:
        """Record a detected poisoned step (latched until take_poison):
        the step's outputs were discarded, the device state is suspect,
        and the supervisor should rebuild before the next dispatch."""
        self.poisoned_steps += 1
        if self._poison is None:
            self._poison = {"kind": kind, "step": self.steps, **info}
        self.flight.record("poison", step=self.steps, kind=kind, **info)

    def take_poison(self) -> Optional[dict]:
        """The supervisor's post-step check: returns and clears the
        latched poison detection, if any."""
        poison, self._poison = self._poison, None
        return poison

    def _unstitch(self, rid: int, req: Request,
                  tokens: Sequence[int]) -> Tuple[tuple, List[int], bool]:
        """Resolve a terminal's (prompt, tokens, was_resumed) through
        the _Resume record: EVERY terminal path (finish, shed, failed,
        abort) must report the ORIGINAL prompt and the pre-fault tokens
        ahead of whatever this incarnation generated — and must pop the
        record, or a long-lived server leaks one per recovered rid."""
        res = self._resumed.pop(rid, None)
        if res is None:
            return req.prompt, list(tokens), False
        return res.prompt, res.tokens + list(tokens), True

    def _fail_row(self, st: _Active, cause: str,
                  finished: List[Result]) -> None:
        """Terminate ONE wedged row with a 'failed' Result — the
        unsupervised-poison backstop (POISON_STRIKE_LIMIT). A
        supervisor-driven engine recovers after the first poison, so
        this path means nobody is recovering and the poison is
        persistent: free the slot, salvage the clean tokens, leave
        exactly one terminal. No ``evict`` event — like abort_all, the
        row never finished (evict is reserved for the finish path)."""
        import jax.numpy as jnp

        req = st.req
        del self._active[st.slot]
        self.sched.release(st.slot)
        self._state = self._release(self._state,
                                    jnp.asarray(st.slot, jnp.int32))
        self.host_dispatches["release"] += 1
        if st.alloc is not None:
            # Prompt blocks are prefill-written (clean) — donation is
            # safe under the same argument recover() relies on.
            self.block_pool.release(st.alloc)
        prompt_out, tokens_out, _ = self._unstitch(req.rid, req,
                                                   st.tokens)
        if req.deadline_s is not None:
            self.slo.record_shed(req.slo_class)
        self._c_completed.labels(reason="failed").inc()
        self.tracer.end(st.span, {"failed": True, "cause": cause})
        self.flight.record("failed", rid=req.rid, step=self.steps,
                           cause=cause, tokens=len(tokens_out))
        finished.append(Result(rid=req.rid, prompt=prompt_out,
                               tokens=tokens_out, finish_reason="failed"))

    def _disable_spec(self, reason: str) -> None:
        """Graceful spec degradation: drop to plain synchronous decode
        for the engine's lifetime. Outputs stay correct (greedy spec ==
        greedy non-spec by construction); only throughput is lost."""
        from nanosandbox_tpu.utils.metrics import warn_once

        self.spec_disabled_reason = reason
        self._spec = None
        self.flight.record("spec_disabled", step=self.steps, reason=reason)
        warn_once("serve-spec-disabled",
                  f"[serve] speculative decoding DISABLED: {reason}; "
                  "continuing with plain decode")

    def quarantine(self, cause: str) -> None:
        """Flip the engine into quarantine: readiness probes go red and
        the supervisor rebuilds before anything else is dispatched."""
        self.quarantined = True
        self.quarantine_cause = cause
        self.flight.record("quarantine", step=self.steps, cause=cause)

    def _close_dangling_spans(self) -> None:
        """End the spans a crash left open — the in-flight decode_step
        (never retired) and a mid-prefill wave — so the tracer's open
        table cannot grow across repeated recoveries (open_count()'s
        zero-after-drain contract survives faults)."""
        if self._inflight is not None:
            self.tracer.end(self._inflight[2], {"aborted": True})
        if self._admitting_span is not None:
            self.tracer.end(self._admitting_span, {"aborted": True})
            self._admitting_span = None

    def recover(self, cause: str = "unknown", *,
                flush_cache: bool = False) -> dict:
        """Rebuild device slot state + block table from scratch and
        re-admit every in-flight request through the normal admission
        path.

        The flight recorder and the host request journal (_active /
        _admitting / scheduler queue) are the source of truth: each
        victim is re-queued AT THE HEAD with prompt' = prompt +
        tokens-generated-so-far and the remaining token budget. Row
        keys derive from fold_in(seed, absolute_position), so the
        resumed stream continues EXACTLY where the fault cut it —
        greedy outputs are token-identical to a no-fault run (pinned by
        test) and sampled outputs are identically distributed. With the
        prefix cache on, a victim's full prompt blocks are donated at
        release and its re-prefill is a prefix HIT: resume costs one
        suffix prefill, not a full re-prefill.

        ``flush_cache=True`` (the exception path: a dispatch crashed
        with donated buffers possibly invalidated) additionally drops
        the radix cache and re-materializes the KV pool arrays; the
        poison path keeps both — a poisoned step only ever wrote its
        rows' private frontier blocks, which are freed here and fully
        overwritten by re-prefill before any read (the PR 9 argument).
        """
        t0 = time.monotonic()
        self._close_dangling_spans()
        self._inflight = None
        self._poison = None
        actives = sorted(self._active.values(), key=lambda s: s.req.rid)
        # A crash INSIDE the wave-commit loop leaves the committed part
        # of the wave in BOTH _active and _admitting — releasing such a
        # slot/alloc twice would crash the recovery itself, so _active
        # wins and the overlap is dropped from the limbo list.
        active_rids = {st.req.rid for st in actives}
        admitting = [entry for entry in self._admitting
                     if entry[0].rid not in active_rids]
        self._active = {}
        self._admitting = []
        requeue: List[Tuple[Request, int, Optional[float]]] = []
        for st in actives:
            self.sched.release(st.slot)
            if st.alloc is not None:
                self.block_pool.release(st.alloc)
            self.tracer.end(st.span, {"recovered": True})
            base = self._resumed.get(st.req.rid)
            orig_prompt = base.prompt if base is not None else st.req.prompt
            pre = (base.tokens if base is not None else []) + st.tokens
            remaining = st.req.max_new_tokens - len(st.tokens)
            req = replace(st.req,
                          prompt=st.req.prompt + tuple(st.tokens),
                          max_new_tokens=remaining)
            self._resumed[req.rid] = _Resume(prompt=orig_prompt,
                                             tokens=pre,
                                             submit_t=st.submit_t)
            requeue.append((req, len(pre), st.submit_t))
        for req, slot, alloc in admitting:
            # A wave caught mid-prefill: blocks committed, slots
            # claimed, nothing active yet. Its submit meta (and queued
            # span) are still open — requeue as-is.
            self.sched.release(slot)
            if alloc is not None:
                self.block_pool.release(alloc)
            base = self._resumed.get(req.rid)
            requeue.append((req, len(base.tokens) if base else 0, None))
        for entry in self._chunking:
            # A chunked prefill caught mid-pipeline: its blocks hold a
            # PARTIALLY-written prompt, so they free without donation
            # (a half-written chain must never serve a prefix hit);
            # the request requeues as-is and re-chunks from scratch.
            self.sched.release(entry.slot)
            if entry.alloc is not None:
                self.block_pool.release(entry.alloc, donate=False)
            base = self._resumed.get(entry.req.rid)
            requeue.append((entry.req,
                            len(base.tokens) if base else 0, None))
        self._chunking = []
        while True:
            # Migration limbo: the export's chain is fully written and
            # its row already released — donate it back (clean by the
            # same copy-on-write argument as actives; under flush_cache
            # the reset below evicts it anyway), restore the migrate
            # intent, and requeue. Re-prefill is a prefix hit over the
            # just-donated chain and resamples the SAME first token
            # (fold_in(seed, true_len)), so the re-export is token-
            # identical to the one this recovery discarded.
            exp = self.sched.pop_limbo()
            if exp is None:
                break
            self.block_pool.release(exp.alloc)
            self._migrate_rids.add(exp.req.rid)
            base = self._resumed.get(exp.req.rid)
            requeue.append((exp.req,
                            len(base.tokens) if base else 0,
                            exp.submit_t))
        if flush_cache:
            from nanosandbox_tpu.models.gpt import (init_cache,
                                                    init_paged_cache)
            if self.paged:
                self.block_pool.reset_cache()
                # _place_pool: a TP engine's rebuilt pool must land on
                # the SAME heads-sharded placement the anchors expect —
                # a replicated rebuild would reshard (or worse, gather)
                # on the first post-recovery dispatch.
                self._pool = self._place_pool(
                    init_paged_cache(self.cfg, self.kv_pool_blocks,
                                     self.kv_page_size,
                                     kv_dtype=self._kv_dtype_arg))
            else:
                self._pool = self._place_pool(
                    init_cache(self.cfg, self.num_slots, self.max_len,
                               kv_dtype=self._kv_dtype_arg))
        self._state = self._fresh_slot_state()
        # FIFO restoration: victims re-enter at the head of their
        # PRIORITY CLASS in rid (= original admission) order, ahead of
        # same-class traffic that arrived after them but never jumping
        # higher-priority queued requests.
        requeue.sort(key=lambda item: item[0].rid)
        now = time.monotonic()
        for req, done, sub_t in requeue:
            if req.rid not in self._submit_meta:
                sid = self.tracer.begin("queued", cat="request",
                                        rid=req.rid,
                                        args={"resumed": True})
                self._submit_meta[req.rid] = (
                    self.steps, sub_t if sub_t is not None else now, sid)
            self.requeued += 1
            self.flight.record("requeue", rid=req.rid, step=self.steps,
                               cause=cause, tokens_done=done)
        self.sched.requeue_front([item[0] for item in requeue])
        self.recoveries += 1
        self._c_recoveries.labels(cause=cause).inc()
        dt = time.monotonic() - t0
        self._h_recovery.observe(dt)
        self.quarantined = False
        self.quarantine_cause = None
        self.flight.record("recover", step=self.steps, cause=cause,
                           requeued=len(requeue), flushed=flush_cache,
                           rebuild_s=round(dt, 6))
        return {"cause": cause, "requeued": len(requeue),
                "flush_cache": flush_cache, "rebuild_s": dt}

    def abort_all(self, cause: str) -> List[Result]:
        """Permanent-failure drain: terminal-fail every in-flight and
        queued request (partial tokens are salvaged into the Result),
        park the device state, and refuse future submissions — the
        clean alternative to a crash loop. Each victim gets exactly one
        terminal ``failed`` flight event."""
        self.failed = True
        self.quarantined = False
        self.quarantine_cause = cause
        self._close_dangling_spans()
        self._inflight = None
        self._poison = None
        results, self._pending_results = self._pending_results, []
        victims: List[Tuple[Request, Optional[int], object, List[int],
                            bool]] = []
        active_rids = set()
        for st in sorted(self._active.values(), key=lambda s: s.req.rid):
            self.sched.release(st.slot)
            if st.alloc is not None:
                self.block_pool.release(st.alloc)
            self.tracer.end(st.span, {"failed": True})
            active_rids.add(st.req.rid)
            victims.append((st.req, st.slot, st.alloc, st.tokens, False))
        for req, slot, alloc in self._admitting:
            if req.rid in active_rids:
                continue    # committed mid-wave: _active already owns it
            self.sched.release(slot)
            if alloc is not None:
                self.block_pool.release(alloc)
            victims.append((req, slot, alloc, [], True))
        for entry in self._chunking:
            self.sched.release(entry.slot)
            if entry.alloc is not None:
                # Partially-written chain: free, never donate.
                self.block_pool.release(entry.alloc, donate=False)
            victims.append((entry.req, entry.slot, entry.alloc, [], True))
        self._active = {}
        self._admitting = []
        self._chunking = []
        self._migrate_rids.clear()
        for item in self.sched.drain_expired(lambda item: True):
            if isinstance(item, _Export):
                # Migration limbo: blocks held, no slot. The handoff
                # never completed — free without donation and salvage
                # the sampled first token into the terminal, like any
                # in-flight victim's partial tokens.
                self.block_pool.release(item.alloc, donate=False)
                victims.append((item.req, None, item.alloc,
                                [item.first_tok], True))
                continue
            victims.append((item, None, None, [], True))
        self._state = self._fresh_slot_state()
        for req, slot, alloc, toks, queued in victims:
            meta = self._submit_meta.pop(req.rid, None)
            if meta is not None:
                self.tracer.end(meta[2], {"failed": True})
            prompt_out, tokens_out, _ = self._unstitch(req.rid, req, toks)
            if req.deadline_s is not None:
                self.slo.record_shed(req.slo_class)
            self._c_completed.labels(reason="failed").inc()
            self.flight.record("failed", rid=req.rid, step=self.steps,
                               cause=cause, tokens=len(tokens_out))
            results.append(Result(rid=req.rid, prompt=prompt_out,
                                  tokens=tokens_out,
                                  finish_reason="failed"))
        self.flight.record("engine_failed", step=self.steps, cause=cause,
                           aborted=len(victims))
        return results

    # ------------------------------------------------------------------
    # disaggregated prefill/decode (ISSUE 16). Export side: a migrate-
    # flagged request parks (block chain + first token) in limbo at its
    # first-token readback; the pump pops it, moves the blocks, and
    # either completes the export (adopted elsewhere) or requeues it
    # (colocated fallback — the exactly-once failure path). Adopt side:
    # begin/commit/abort adopt re-admits a migrated chain as a pure
    # prefix hit through the rung-1 admit program — ZERO prefill
    # dispatches, which is the whole point: the decode tier's compile
    # set stays {decode rungs, admit, release}, a strict subset of the
    # colocated engine's (jits are lazy; a program never dispatched is
    # never compiled), and its TPOT never pays for anyone's prompt.
    # ------------------------------------------------------------------
    def pop_export(self) -> Optional[_Export]:
        """Claim the oldest limbo-parked export for transfer (None when
        empty). The caller now owns the record: it must end in exactly
        one of complete_export (handoff succeeded), requeue_export
        (fallback to colocated here), or repark_export (transient
        backpressure — try again next pump)."""
        return self.sched.pop_limbo()

    def repark_export(self, exp: _Export) -> None:
        """Return an un-transferred export to the HEAD of limbo (the
        adopting tier had no slot/blocks this pump); the deadline sweep
        keeps watching it."""
        self.sched.park_limbo_front(exp)

    def complete_export(self, exp: _Export, *, dst: str = "",
                        blocks_copied: int = 0, bytes_moved: int = 0,
                        migrate_s: float = 0.0) -> None:
        """The handoff landed: the adopting engine committed the row.
        Release the chain WITH donation — it is fully written and
        clean, and keeping it warm in this tier's radix trie is what
        makes a later failover restitch (prompt + salvaged tokens) a
        prefix HIT here instead of a full re-prefill — and leave the
        terminal accounting to the adopter. Records the exactly-once
        ``migrate`` flight event (chain length, transferred bytes,
        src/dst) on THIS engine: the source owns the handoff story."""
        self.migrated += 1
        self.completed += 1
        self._c_completed.labels(reason="migrated").inc()
        self.block_pool.release(exp.alloc)
        self.flight.record(
            "migrate", rid=exp.req.rid, step=self.steps,
            dst=dst, chain_blocks=len(exp.alloc.table),
            hit_blocks=exp.alloc.n_hit, copied_blocks=blocks_copied,
            bytes=bytes_moved, migrate_s=round(migrate_s, 6),
            limbo_s=round(time.monotonic() - exp.export_t, 6))

    def requeue_export(self, exp: _Export, *, migrate: bool = False) -> None:
        """Fallback: no decode tier can adopt (tier death, permanent
        backpressure) — put the request back through THIS engine's
        admission, colocated by default. Blocks release WITH donation
        (the chain is clean and fully written), so the re-prefill is a
        pure prefix hit that resamples the SAME first token
        (fold_in(seed, true_len)) — the terminal Result is token-
        identical to the migration that never happened, under the
        request's ORIGINAL rid and deadline budget: exactly-once by
        construction, no pair-level dedup needed."""
        self.block_pool.release(exp.alloc)
        if migrate:
            self._migrate_rids.add(exp.req.rid)
        sid = self.tracer.begin("queued", cat="request", rid=exp.req.rid,
                                args={"requeued_export": True})
        self._submit_meta[exp.req.rid] = (exp.submit_step,
                                          exp.submit_t, sid)
        self.sched.requeue_front([exp.req])
        self.flight.record("requeue", rid=exp.req.rid, step=self.steps,
                           cause="export_fallback", tokens_done=0)

    def begin_adopt(self, req: Request, *,
                    max_new_tokens: Optional[int] = None
                    ) -> Optional[_Adoption]:
        """Phase 1 of adopting a migrated request: claim a slot and the
        FULL block footprint (prompt chain + generation budget —
        paged.adopt_chain). Returns None when this engine cannot take
        it right now (no free slot, pool shortfall, quarantine) — the
        adoption-backpressure signal; the caller re-parks the export
        and retries next pump. On success the handle's ``copy``/
        ``dst_blocks`` name the blocks to fill via write_pool_blocks
        before commit_adopt; abort_adopt unwinds a transfer that died
        mid-flight. The request is re-keyed into THIS engine's rid
        space (the pair/frontend owns the cross-engine mapping)."""
        if self.failed:
            raise EngineFailedError(
                "engine permanently failed; cannot adopt")
        if not self.paged:
            raise ValueError("adoption needs a paged engine: the block "
                             "chain is the migration wire format")
        max_new = (req.max_new_tokens if max_new_tokens is None
                   else int(max_new_tokens))
        if len(req.prompt) + max_new > self.max_len:
            raise ValueError(
                f"adopted prompt ({len(req.prompt)}) + max_new "
                f"({max_new}) exceeds max_len {self.max_len}")
        if self.quarantined or not self.sched.free_slots:
            return None
        got = self.block_pool.adopt_chain(req.prompt, max_new)
        if got is None:
            self.flight.record(
                "block_stall", rid=-1, step=self.steps,
                need=self.block_pool.blocks_needed(len(req.prompt),
                                                   max_new),
                free=self.block_pool.free_blocks, adopt=True)
            return None
        alloc, copy = got
        req = replace(req, rid=next(self._rid), max_new_tokens=max_new)
        return _Adoption(req=req, slot=self.sched.take_slot(),
                         alloc=alloc, copy=copy)

    def abort_adopt(self, ad: _Adoption) -> None:
        """Unwind a begun adoption whose transfer failed (source died
        mid-copy, injected fault): slot back, blocks freed WITHOUT
        donation — the chain may be partially copied and a half-written
        chain must never serve a prefix hit. No terminal here: the
        request still lives on the SOURCE side (its export record),
        which resolves it exactly once via requeue/complete/shed."""
        self.sched.release(ad.slot)
        self.block_pool.release(ad.alloc, donate=False)

    def commit_adopt(self, ad: _Adoption, first_tok: int, *,
                     submit_t: Optional[float] = None,
                     src: str = "") -> Tuple[int, Optional[Result]]:
        """Phase 2: activate the adopted row. One rung-1 ``admit``
        scatter stages the block table, pos = true_len = len(prompt),
        the migrated first token, and the ORIGINAL seed — decode
        continues with fold_in(seed, pos + 1) keys, exactly the stream
        the source's colocated decode would have produced, so greedy
        outputs are token-identical to never having migrated (pinned by
        test). NO prefill dispatch, no readback: admission here is a
        pure prefix hit by construction. Returns (rid-on-this-engine,
        immediately-finished Result or None)."""
        req = ad.req
        if not 0 <= int(first_tok) < self.cfg.vocab_size:
            # A poisoned/corrupt wire token must not be scattered: the
            # caller unwinds (abort_adopt) and the source falls back.
            raise ValueError(
                f"migrated first token {first_tok} outside [0, "
                f"vocab_size={self.cfg.vocab_size})")
        now = time.monotonic()
        nb = self.slot_blocks
        meta = np.zeros((1, self._meta_width), np.int32)
        meta[0, :nb] = self.kv_pool_blocks
        meta[0, :len(ad.alloc.table)] = ad.alloc.table
        meta[0, nb] = ad.slot
        meta[0, nb + 1] = len(req.prompt)
        meta[0, nb + 2] = req.top_k
        meta[0, nb + 3] = req.seed
        meta[0, nb + 4] = ad.alloc.n_hit * self.kv_page_size
        fmeta = np.array([[req.temperature, req.top_p]], np.float32)
        toks = np.array([int(first_tok)], np.int32)
        self._state = self._admit(self._state, self._stage(toks),
                                  self._stage(meta), self._stage(fmeta))
        self.host_dispatches["admit"] += 1
        self.admitted += 1
        self.adopted += 1
        self._c_submitted.inc()
        gen_sid = self.tracer.begin(
            "generate", cat="request", rid=req.rid,
            args={"slot": ad.slot, "adopted": True})
        st = _Active(req=req, slot=ad.slot, tokens=[int(first_tok)],
                     first_token_t=now,
                     submit_t=submit_t if submit_t is not None else now,
                     last_t=now, span=gen_sid, alloc=ad.alloc)
        self._active[ad.slot] = st
        self.flight.record(
            "adopt", rid=req.rid, step=self.steps, slot=ad.slot,
            src=src, chain_blocks=len(ad.alloc.table),
            hit_blocks=ad.alloc.n_hit, copied_blocks=len(ad.copy),
            prompt_len=len(req.prompt))
        return req.rid, self._maybe_finish(st)

    def read_pool_blocks(self, block_ids: Sequence[int]) -> List:
        """Gather whole KV-pool blocks by id, one host array per pool
        leaf in jax.tree flatten order — the migration wire payload
        (quantized pools ride as-is: int8/int4 codes + their scales are
        just more leaves, so a migration never dequantizes). A host
        sync by design: migration is a cold-path transfer the pump runs
        BETWEEN steps, never a per-token cost — it lives outside the
        engine's guarded compile set and its host-sync ledger."""
        import jax
        idx = np.asarray(list(block_ids), np.int32)
        return [np.asarray(leaf)[idx]
                for leaf in jax.tree_util.tree_leaves(self._pool)]

    def write_pool_blocks(self, block_ids: Sequence[int],
                          values: Sequence) -> int:
        """Scatter whole blocks into this pool by id — the adopt-side
        twin of read_pool_blocks. Updates are padded to the fixed
        slot_blocks rung with the out-of-range drop sentinel, so every
        chain length rides ONE implicit program per leaf instead of
        minting a shape per migration (the fixed-shape discipline,
        applied to the cold path too). Returns payload bytes written
        (real rows only — padding is free)."""
        import jax
        n = len(block_ids)
        if n == 0:
            return 0
        if n > self.slot_blocks:
            raise ValueError(
                f"{n} blocks exceed the per-request maximum "
                f"{self.slot_blocks}")
        idx = np.full((self.slot_blocks,), self.kv_pool_blocks, np.int32)
        idx[:n] = np.asarray(list(block_ids), np.int32)
        idx_dev = self._stage(idx)
        leaves, treedef = jax.tree_util.tree_flatten(self._pool)
        if len(values) != len(leaves):
            raise ValueError(
                f"payload has {len(values)} leaves, pool has "
                f"{len(leaves)}")
        out = []
        nbytes = 0
        for leaf, vals in zip(leaves, values):
            v = np.asarray(vals)
            if v.shape[0] < n or v.shape[1:] != leaf.shape[1:] \
                    or v.dtype != leaf.dtype:
                raise ValueError(
                    f"payload leaf {v.shape}/{v.dtype} does not match "
                    f"pool leaf {leaf.shape}/{leaf.dtype}")
            nbytes += v[:n].nbytes
            padded = np.zeros((self.slot_blocks,) + tuple(leaf.shape[1:]),
                              v.dtype)
            padded[:n] = v[:n]
            out.append(leaf.at[idx_dev].set(self._stage(padded),
                                            mode="drop"))
        self._pool = jax.tree_util.tree_unflatten(treedef, out)
        return nbytes

    def retry_after_s(self, slo_class: Optional[str] = None,
                      priority: Optional[int] = None) -> float:
        """Client backoff hint for 429/503 responses: the scheduler's
        queue-wait p50 converted to wall seconds through the recent
        step rate (fallback 1s when either signal is cold) — a shed
        client that waits this long lands where today's admitted
        traffic is actually clearing the queue.

        Priority-aware (ISSUE 13): under the priority queue a batch
        request waits behind EVERYTHING at or above its class, so its
        hint scales with the queue mass ahead of it — a batch client
        behind a deep interactive queue no longer gets an interactive
        client's optimistic number. ``slo_class`` maps through
        PRIORITY_BY_CLASS when ``priority`` is not given; with neither,
        the classless base estimate is returned (the pre-priority
        behavior)."""
        base = 1.0
        p = self._queue_wait.percentiles((50,))
        steps_per_s = self._steps_per_s()
        if p and p.get("p50") is not None and steps_per_s is not None:
            base = max(0.5, p["p50"] / steps_per_s)
        if priority is None:
            if slo_class is None:
                return base
            priority = PRIORITY_BY_CLASS.get(slo_class, DEFAULT_PRIORITY)
        # Everything at-or-above the class waits ahead of it; STRICTLY
        # higher backlog counts double — its depth is the best available
        # proxy for the arrival pressure that will keep jumping this
        # class after it requeues (and, with deadlines, preempting it).
        ahead, jumps = self.sched.queue_mass(priority)
        return base * (1.0 + (ahead + jumps) / max(1, self.num_slots))
