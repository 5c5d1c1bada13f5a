"""`python -m nanosandbox_tpu.serve` — serve a trained checkpoint.

Restores the latest checkpoint under --out_dir (the same
restore_for_inference dance sample.py uses), casts params to the
serving dtype, and exposes the continuous-batching engine over HTTP:

    python -m nanosandbox_tpu.serve --out_dir=out --port=8000 &
    curl -s localhost:8000/generate -d '{"prompt": "ROMEO:", \
        "max_new_tokens": 64, "temperature": 0.8, "top_k": 40}'
    curl -s localhost:8000/metrics            # Prometheus exposition
    curl -s 'localhost:8000/trace?rid=0'      # Perfetto-loadable trace
    curl -s localhost:8000/profile -d '{"steps": 50}'   # profiler window
"""

from __future__ import annotations

import argparse
import contextlib
import sys


def parse_args(argv: list[str] | None = None) -> argparse.Namespace:
    ap = argparse.ArgumentParser(prog="python -m nanosandbox_tpu.serve")
    ap.add_argument("--router", action="store_true",
                    help="run the FLEET ROUTER front tier instead of an "
                         "engine replica (ISSUE 15): an asyncio proxy "
                         "routing POST /generate across --replicas by "
                         "radix-prefix affinity with health/load "
                         "fallback and failover re-routing. Loads no "
                         "checkpoint and touches no accelerator — the "
                         "k8s router Deployment runs exactly this")
    ap.add_argument("--replicas", default="",
                    help="router mode: comma-separated replica base "
                         "URLs (http://host:port), or a "
                         "dns+http://name:port spec resolved every "
                         "health interval — point it at the headless "
                         "Service (serve-replicas.disttrain) and the "
                         "rotation tracks pod scale-up/down and "
                         "readiness automatically")
    ap.add_argument("--health_interval_s", type=float, default=2.0,
                    help="router mode: seconds between per-replica "
                         "health + load + prefix-summary polls; a "
                         "draining/dead replica leaves rotation within "
                         "one interval")
    ap.add_argument("--router_page", type=int, default=16,
                    help="router mode: KV page size the replicas run "
                         "(must match their --kv_page_size, or prefix "
                         "fingerprints will never match)")
    ap.add_argument("--no_affinity", action="store_true",
                    help="router mode: disable prefix-affinity scoring "
                         "(pure least-loaded routing — the comparison "
                         "baseline, and the right mode for dense or "
                         "cache-less replicas)")
    ap.add_argument("--out_dir", default="out")
    ap.add_argument("--data_dir", default="data")
    ap.add_argument("--dataset", default="shakespeare_char")
    ap.add_argument("--host", default="0.0.0.0")
    ap.add_argument("--port", type=int, default=8000)
    ap.add_argument("--num_slots", type=int, default=8,
                    help="concurrent request capacity (decode batch rows)")
    ap.add_argument("--tp", type=int, default=1,
                    help="tensor-parallel degree: shard ONE engine over "
                         "the first N devices — Megatron weight "
                         "placements, the KV pool (and its scale "
                         "planes) row-sharded along heads over the "
                         "``model`` mesh axis, slot state replicated. "
                         "Greedy outputs are token-identical to tp=1; "
                         "the comms contract is CI-pinned in "
                         "budgets/serve_tp_cpu8.json and exported on "
                         "/metrics at startup (serve_tp_degree + "
                         "serve_collective_bytes_per_token). Requires "
                         "n_head %% tp == 0 and N local devices; 1 = "
                         "the single-chip engine, unchanged")
    ap.add_argument("--max_len", type=int, default=0,
                    help="per-slot KV length; 0 = block_size")
    ap.add_argument("--device", default="auto")
    ap.add_argument("--no_pipeline", action="store_true",
                    help="synchronous decode loop (debugging baseline); "
                         "default keeps one decode step in flight")
    ap.add_argument("--prefill_chunk", type=int, default=0,
                    help="per-step prefill token budget (must be one of "
                         "the prefill buckets; 0 = off): admission "
                         "waves are paced and long prompts split into "
                         "chunk-sized prefills interleaved with decode "
                         "steps, so a prefill storm cannot spike active "
                         "requests' TPOT. Paged engines only for the "
                         "splitting half; the compile set does not grow")
    ap.add_argument("--no_preemption", action="store_true",
                    help="disable deadline-driven preemption-by-"
                         "eviction (default on: when the highest-"
                         "priority queued request would miss its "
                         "deadline waiting on slots/KV blocks, the "
                         "lowest-priority active request is evicted — "
                         "its blocks donate to the prefix cache and it "
                         "resumes token-identically as a prefix hit)")
    ap.add_argument("--brownout", default="on", choices=("on", "off"),
                    help="SLO-driven brownout degradation ladder "
                         "(default on): under sustained deadline burn "
                         "the engine steps through shrink-scan -> "
                         "suspend-spec -> shed-batch -> interactive-"
                         "only, with hysteresis; each transition is a "
                         "flight/metrics event. Costs nothing without "
                         "deadlines")
    ap.add_argument("--scan_k", type=int, default=1,
                    help="decode steps fused into one compiled dispatch "
                         "(lax.scan megaprogram ladder): the host "
                         "dispatches once per up-to-k tokens instead of "
                         "once per token, finish detection lags the "
                         "chunk. 1 = the classic per-token loop; "
                         "ignored under --spec (the verify readback "
                         "gates the next frontier)")
    ap.add_argument("--paged", default="on", choices=("on", "off"),
                    help="block-paged KV pool + radix prefix cache "
                         "(default on): admission reserves each "
                         "request's actual block need instead of a "
                         "worst-case max_len row, and prompts sharing "
                         "a resident prefix skip its prefill chunks. "
                         "'off' restores the dense per-slot rows")
    ap.add_argument("--role", default="both",
                    choices=("both", "prefill", "decode"),
                    help="disaggregated serving tier (ISSUE 16): "
                         "'prefill' pods run chunked prefill waves and "
                         "export {block chain, first token, seed} as a "
                         "202 on migrate-flagged /generate; 'decode' "
                         "pods adopt them via /internal/adopt with zero "
                         "prefill dispatches (and warm only the admit/"
                         "decode programs — the strict-subset compile "
                         "set). 'both' (default) is classic colocated "
                         "serving. The router frontend discovers the "
                         "role from /stats and phase-tiers routing "
                         "when both tiers are ready")
    ap.add_argument("--kv_page_size", type=int, default=16,
                    help="positions per KV block (paged pool); int8 "
                         "pools on real TPUs want >= 32 (sublane "
                         "tiling quantum)")
    ap.add_argument("--kv_pool_blocks", type=int, default=0,
                    help="paged pool size in blocks; 0 = num_slots * "
                         "max_len / page (byte-identical to the dense "
                         "pool)")
    ap.add_argument("--no_prefix_cache", action="store_true",
                    help="disable radix prefix reuse (paged pool only)")
    ap.add_argument("--kv_dtype", default=None,
                    choices=("fp32", "bf16", "int8", "int4"),
                    help="KV-pool storage mode (default: the serving "
                         "compute dtype). int8 stores per-position "
                         "scales alongside the values: ~2x less HBM per "
                         "cached token than bf16, so 2x the slots at "
                         "constant HBM and ~2x less decode read traffic. "
                         "int4 packs two nibbles per byte (same scale "
                         "format): ~2x int8's slot capacity again, at "
                         "a coarser 4-bit quantization grid")
    ap.add_argument("--decode_impl", default=None,
                    choices=("auto", "pallas", "pallas_interpret", "xla"),
                    help="cached-decode attention impl "
                         "(ops/flash_decode.py); 'auto' is the Pallas "
                         "kernel on a tpu backend (a compile error "
                         "fails the warm-up) and xla on any other. The "
                         "resolved impl is exported on /metrics")
    ap.add_argument("--spec", default="off",
                    help="speculative decoding: 'ngram' (prompt-lookup "
                         "drafting) or 'model:<out_dir>' (smaller "
                         "same-tokenizer draft checkpoint); up to "
                         "spec_k+1 tokens per target forward, greedy "
                         "outputs unchanged (forces the synchronous "
                         "loop)")
    ap.add_argument("--spec_k", type=int, default=4,
                    help="draft tokens per verify step (--spec only)")
    ap.add_argument("--shardcheck_budget", default=None,
                    help="shardcheck comms budget to export as "
                         "shardcheck_collectives_total{program=,kind=} "
                         "gauges on /metrics at startup (the pinned "
                         "comms contract this engine runs under); "
                         "default budgets/serve_cpu8.json, skipped "
                         "silently when absent — an EXPLICIT path must "
                         "exist; '' disables")
    ap.add_argument("--deadline_s", type=float, default=0.0,
                    help="default per-request SLO deadline in seconds "
                         "(submit -> finish), applied to requests that "
                         "send none; 0 = best-effort. Deadline-carrying "
                         "requests land in the SLO ledger "
                         "(serve_slo_* + serve_goodput_tokens_total on "
                         "/metrics) and are SHED from the queue once "
                         "expired (finish_reason 'shed')")
    ap.add_argument("--watchdog_dir", default=None,
                    help="directory for anomaly-watchdog dumps (flight "
                         "ledger + span ring + stats snapshot per "
                         "trip); default: a tempdir created on the "
                         "first trip")
    ap.add_argument("--no_watchdogs", action="store_true",
                    help="disable the anomaly watchdogs (TTFT spike, "
                         "admission stall, pool thrash, post-warmup "
                         "retrace, stuck slot)")
    ap.add_argument("--faults", default=None,
                    help="arm a deterministic fault-injection plan "
                         "(serve/faults.py) for chaos drills: "
                         "'site@step[xN][:param]' entries comma-"
                         "separated, or a canned plan name "
                         "('chaos-smoke', 'chaos-full'). Steps are "
                         "relative to the END of warmup. NEVER default "
                         "on: production pays zero cost without it")
    ap.add_argument("--no_recovery", action="store_true",
                    help="disable the crash-safe engine supervisor "
                         "(quarantine + device-state rebuild + "
                         "re-admission on poisoned steps/watchdog "
                         "trips/dispatch crashes); without it a "
                         "dispatch crash kills the serving loop and a "
                         "persistently poisoned row terminates "
                         "'failed' after 3 strikes instead of "
                         "recovering")
    ap.add_argument("--warmup", choices=("full", "buckets"), default="full",
                    help="'full' compiles every (wave-size, bucket) "
                         "prefill pair before binding the port (the "
                         "/healthz readiness contract); 'buckets' "
                         "compiles one single-request prefill per bucket "
                         "and leaves larger waves to compile lazily")
    return ap.parse_args(argv if argv is not None else sys.argv[1:])


class Served:
    """What ``python -m nanosandbox_tpu.serve`` builds before it listens:
    the warmed engine behind its loop and HTTP server. ``main`` calls
    serve_forever() on it; chip_smoke.py drives the same object from a
    thread, so the smoke serves through exactly the stack users get."""

    def __init__(self, server, loop, engine, freeze, tokenizer):
        self.server, self.loop, self.engine = server, loop, engine
        self.freeze, self.tokenizer = freeze, tokenizer

    @property
    def port(self) -> int:
        return self.server.server_address[1]

    def serve_forever(self) -> None:
        """Blocks until KeyboardInterrupt or server.shutdown()."""
        try:
            with self.freeze:
                self.server.serve_forever()
        except KeyboardInterrupt:
            pass
        finally:
            self.loop.stop()
            self.server.server_close()


def main(argv: list[str] | None = None) -> None:
    args = parse_args(argv)
    if args.router:
        # Front-tier mode: no checkpoint, no jax — just the router
        # proxy over the replica fleet.
        from nanosandbox_tpu.serve.http import RouterFrontend

        replicas = [u for u in args.replicas.split(",") if u.strip()]
        if not replicas:
            raise SystemExit("--router needs --replicas=<url,url,...> "
                             "or --replicas=dns+http://name:port")
        fe = RouterFrontend(
            replicas, host=args.host, port=args.port,
            page=args.router_page,
            health_interval_s=args.health_interval_s,
            affinity=not args.no_affinity).start()
        print(f"[serve-router] routing {replicas} "
              f"(affinity={'off' if args.no_affinity else 'on'}, "
              f"page={args.router_page}, health every "
              f"{args.health_interval_s}s); listening on "
              f"{args.host}:{fe.port} (POST /generate, GET /healthz "
              "/debug/router /metrics)", file=sys.stderr, flush=True)
        try:
            while True:
                import time

                time.sleep(3600)
        except KeyboardInterrupt:
            pass
        finally:
            fe.stop()
        return
    build_server(args).serve_forever()


def build_server(args: argparse.Namespace) -> Served:
    """Restore the checkpoint, build the Engine with the flags' values,
    warm its whole compile set and bind the port (``--port=0`` binds a
    free one) — everything short of serving."""
    from nanosandbox_tpu.data.loader import BinDataset
    from nanosandbox_tpu.data.tokenizer import get_tokenizer
    from nanosandbox_tpu.sample import cast_params_for_serving
    from nanosandbox_tpu.serve.engine import Engine
    from nanosandbox_tpu.serve.http import EngineLoop, make_server
    from nanosandbox_tpu.train import restore_for_inference
    from nanosandbox_tpu.utils.compile_cache import enable_compile_cache

    # Load the shardcheck budget BEFORE the restore + warmup compiles:
    # a typo'd path or corrupt file must fail in milliseconds, not
    # after minutes of prefill-grid compilation. (The export itself
    # happens post-warmup, next to the other /metrics publishing.)
    # None (flag not given) falls back to the committed default and is
    # skipped when absent; an EXPLICIT path — even one spelling out the
    # default — must exist (argparse cannot tell a typed-out default
    # from the fallback, so the sentinel is None, not the path).
    shardcheck_budget = None
    implicit_budget = args.shardcheck_budget is None
    # A tensor-parallel engine runs under the TP comms contract — the
    # implicit default follows the --tp flag so the exported gauges
    # describe the engine actually serving. The committed contract is
    # pinned at tp=2; any OTHER degree gets no implicit budget (its
    # program names and bytes would describe a different engine —
    # misleading gauges are worse than none) and must pass an explicit
    # --shardcheck_budget regenerated at that degree.
    if args.tp > 1:
        default_budget = ("budgets/serve_tp_cpu8.json" if args.tp == 2
                          else None)
        if default_budget is None and implicit_budget:
            print(f"[serve] no committed shardcheck budget for tp="
                  f"{args.tp} (the pinned contract is tp=2) — skipping "
                  "the /metrics budget export; pass --shardcheck_budget="
                  "<path> regenerated at this degree to restore it",
                  file=sys.stderr, flush=True)
    else:
        default_budget = "budgets/serve_cpu8.json"
    budget_path = (default_budget if implicit_budget
                   else args.shardcheck_budget)
    if budget_path:
        import os

        if os.path.exists(budget_path):
            from nanosandbox_tpu.analysis.shardcheck import load_budget

            try:
                shardcheck_budget = load_budget(budget_path)
            except ValueError as e:
                raise SystemExit(f"--shardcheck_budget: {e}")
        elif not implicit_budget:
            raise SystemExit(
                f"--shardcheck_budget={budget_path}: no such file (only "
                "the implicit default is skipped when absent)")

    # Fault plan (chaos drills): parsed BEFORE the expensive restore so
    # a typo fails in milliseconds; armed only after warmup — the
    # plan's relative steps aim at live traffic, never at compile time.
    fault_plan = None
    if args.faults:
        from nanosandbox_tpu.serve.faults import FaultPlan

        try:
            fault_plan = FaultPlan.parse(args.faults)
        except ValueError as e:
            raise SystemExit(f"--faults: {e}")
        fault_plan.enabled = False

    enable_compile_cache()
    trainer, state, step = restore_for_inference(
        args.out_dir, data_dir=args.data_dir, device=args.device)
    params = cast_params_for_serving(state["params"],
                                     trainer.cfg.compute_dtype)

    ds = BinDataset(args.data_dir, args.dataset)
    tok = get_tokenizer(ds.meta.get("kind", "char"), ds.meta)

    from nanosandbox_tpu.serve.drafters import drafter_from_flag

    drafter = drafter_from_flag(args.spec, k=args.spec_k,
                                data_dir=args.data_dir)
    engine = Engine(trainer.model, params, num_slots=args.num_slots,
                    max_len=args.max_len or None,
                    pipeline=not args.no_pipeline, spec=drafter,
                    scan_k=args.scan_k, tp=args.tp,
                    kv_dtype=args.kv_dtype, decode_impl=args.decode_impl,
                    paged=args.paged == "on",
                    kv_page_size=args.kv_page_size,
                    kv_pool_blocks=args.kv_pool_blocks or None,
                    prefix_cache=not args.no_prefix_cache,
                    watchdogs=not args.no_watchdogs,
                    watchdog_dir=args.watchdog_dir,
                    default_deadline_s=args.deadline_s or None,
                    faults=fault_plan,
                    prefill_chunk=args.prefill_chunk or None,
                    preemption=not args.no_preemption,
                    brownout=args.brownout == "on",
                    role=args.role)
    # Warm the compile set BEFORE binding the port: /healthz going green
    # is the readiness contract the k8s manifest and docs promise
    # ("restore + first compile done"), so no live request may ever eat
    # a cold XLA compile. The set is bounded by design —
    # len(admit_ladder) * len(buckets) prefills + admit/release/decode —
    # so this is a fixed startup cost; --warmup=buckets trades lazy
    # wave-size compiles for a faster start.
    rungs = (engine.admit_buckets if args.warmup == "full" else [1])
    lo = 1
    # A decode-tier pod (ISSUE 16) never dispatches a prefill: warming
    # the prefill grid would WIDEN its compile set and break the
    # strict-subset contract the disagg shardcheck re-pin asserts, so
    # the bucket loop is skipped entirely for --role=decode.
    warm_buckets = ([] if args.role == "decode"
                    else engine.sched.buckets)
    for bucket in warm_buckets:
        # Warmup prompt length must actually MAP to this bucket (in
        # (previous rung, bucket]). Prefer leaving room for 2 new
        # tokens — a 1-token request finishes on its prefill-sampled
        # token and would never touch (= compile) the batched decode
        # step. But a bucket reachable ONLY by max_new_tokens=1
        # requests (max_len within 2 of the previous rung) still gets
        # its prefill/admit programs compiled via a 1-token warmup:
        # the post-warmup freeze below makes EVERY admissible request
        # shape's absence an outage, not a lazy compile. Only a bucket
        # no admissible request can map to at all (no length in range
        # even with one new token) is skipped — submit() can never
        # send traffic there, so skipping keeps the readiness contract
        # honest AND freeze-safe.
        length = min(bucket, engine.max_len - 2)
        new_tokens = 2
        lo, prev_lo = bucket + 1, lo
        if length < prev_lo:
            length, new_tokens = min(bucket, engine.max_len - 1), 1
            if length < prev_lo:
                continue
        for k in rungs:
            # k same-bucket submissions land as ONE admission wave,
            # compiling the (k, bucket) prefill.
            for _ in range(k):
                engine.submit([0] * length, new_tokens)
            engine.drain()
            # A warmup prompt's blocks must never serve a prefix hit to
            # the NEXT warmup wave: a hit shrinks the suffix bucket, and
            # the (k, bucket) program this wave exists to compile would
            # silently not compile — a post-freeze outage on the first
            # real prompt that maps there. Same-wave submissions are
            # safe (admission happens before any donation), so flushing
            # between drains closes the hole completely.
            engine.reset_prefix_cache()
    # The scan-chunk rung ladder (--scan_k > 1): one megaprogram per
    # rung, compiled by dispatching each rung once over the parked slot
    # state — the freeze below would otherwise turn the first request
    # mix whose budgets make the chunk policy pick an uncompiled rung
    # into a post-warmup retrace outage.
    if args.role == "decode":
        if args.paged != "on":
            raise SystemExit("--role=decode needs --paged=on: adoption "
                             "is a paged block-chain operation")
        # Warm exactly what the decode tier runs — the rung-1 admit
        # scatter and one decode dispatch — via a throwaway adoption.
        # The adopted blocks are never written (zero-initialized KV is
        # fine for a compile) and the chain is flushed so no real
        # request can prefix-hit it.
        from nanosandbox_tpu.serve.engine import Request as _Request
        ad = engine.begin_adopt(
            _Request(rid=-1, prompt=(0, 0, 0), max_new_tokens=2))
        if ad is not None:
            engine.commit_adopt(ad, 0)
            engine.drain()
            engine.reset_prefix_cache()
    if args.warmup == "full":
        engine.warm_scan_rungs()
    print(f"[serve] warmup: compiled {engine.trace_counts['prefill']} "
          f"prefill program(s) ({args.warmup}), "
          f"{engine.trace_counts['admit']} admit, "
          f"{engine.trace_counts['decode']} decode"
          + (f", {engine.trace_counts.get('verify', 0)} verify "
             f"(spec={args.spec}, k={args.spec_k})"
             if args.spec != "off" else "")
          + f" (pipeline={'on' if engine.pipeline else 'off'}"
          + (f", role={args.role}" if args.role != "both" else "")
          + ")",
          file=sys.stderr, flush=True)
    engine.reset_latency_stats()  # /stats should describe live traffic
    # Post-warmup, ANY compile eats a live request's latency, so the
    # watchdog marks steady in BOTH warmup modes: under --warmup=buckets
    # the deliberate lazy wave compiles are exactly what an operator
    # wants counted and dumped (the freeze doesn't cover that mode);
    # under --warmup=full the tracecheck freeze makes a retrace fatal
    # first, and the mark is a belt-and-braces backstop.
    engine.watchdog.mark_steady()
    # Host health on the same scrape as the engine counters: RSS, open
    # fds, uptime, live jax buffer bytes — sampled per scrape.
    from nanosandbox_tpu.obs import register_process_vitals

    register_process_vitals()
    # Publish the pinned comms contract (shardcheck budget) as gauges on
    # the process-global registry so every /metrics scrape carries the
    # collective counts this deployment is budgeted for — a TP-serving
    # rollout that rewrites the budget becomes visible in the same
    # dashboard that watches its latency.
    if shardcheck_budget is not None:
        from nanosandbox_tpu.analysis.shardcheck import (
            export_collective_bytes_per_token, export_manifest_metrics)
        from nanosandbox_tpu.obs import global_registry

        export_manifest_metrics(shardcheck_budget, global_registry())
        if args.tp > 1:
            # The TP wire cost per token, per program — the startup
            # shardcheck pass normalized onto the scrape next to the
            # serve_tp_degree gauge the engine itself exports.
            export_collective_bytes_per_token(shardcheck_budget,
                                              global_registry())
        print(f"[serve] shardcheck budget {budget_path} exported to "
              "/metrics", file=sys.stderr, flush=True)
    if fault_plan is not None:
        # Arm at the post-warmup step: the plan's relative schedule
        # targets live traffic.
        fault_plan.rearm(engine.steps)
        fault_plan.enabled = True
        print(f"[serve] CHAOS: fault plan armed — "
              f"{fault_plan.describe()}", file=sys.stderr, flush=True)
    supervisor = None
    if not args.no_recovery:
        from nanosandbox_tpu.serve.recovery import EngineSupervisor

        supervisor = EngineSupervisor(engine)
    loop = EngineLoop(engine, supervisor=supervisor)
    loop.start()
    server = make_server(args.host, args.port, loop, tok.encode,
                         lambda ids: tok.decode([int(t) for t in ids]))
    pool_desc = (f"paged pool {engine.kv_pool_blocks} blocks x "
                 f"{engine.kv_page_size} positions"
                 + ("" if args.no_prefix_cache else " + prefix cache")
                 if engine.paged else "dense per-slot rows")
    print(f"[serve] checkpoint step {step}; {args.num_slots} slots x "
          f"{engine.max_len} ctx, tp={engine.tp} "
          f"({pool_desc}, kv_dtype={engine.kv_dtype}, "
          f"decode_impl={engine.decode_impl}, recovery="
          f"{'off' if supervisor is None else 'on'}, "
          f"prefill_chunk={engine.prefill_chunk or 'off'}, preemption="
          f"{'on' if engine.preemption else 'off'}, brownout="
          f"{'on' if engine.brownout is not None else 'off'}); "
          f"prefill buckets "
          f"{engine.sched.buckets}; listening on "
          f"{args.host}:{server.server_address[1]} (POST /generate /drain /profile, "
          "GET /healthz[?ready=1] /stats /metrics /trace "
          "/debug/requests /debug/slots /debug/kvpool "
          "/debug/scheduler)",
          file=sys.stderr, flush=True)
    # After a FULL warmup the compile set is complete by contract, so
    # freeze the retrace budgets: a compile after /healthz went green
    # is a shape leak eating a live request's latency, and the engine
    # loop dying with CompileBudgetExceeded (failing queued requests
    # with the reason) beats serving it silently. --warmup=buckets
    # deliberately leaves lazy wave compiles, so no freeze there.
    freeze = (engine.tracecheck.frozen() if args.warmup == "full"
              else contextlib.nullcontext())
    return Served(server, loop, engine, freeze, tok)


if __name__ == "__main__":
    main()
