#!/usr/bin/env python3
"""chip_smoke.py — the quickest proof that this tree still starts on the chip.

One process, one TPU chip, the entry points a user would call, at the
full width of GPT-2 124M (12L / 12H / 768d, T=1024, V=50304 —
configs/train_gpt2_124m_englishprose_bpe.py; weights random from the
seed, corpus and BPE vocab committed under data/fixtures/):

  prepare   python -m nanosandbox_tpu.data.prepare english_prose_bpe
  train     nanosandbox_tpu.train.main: a handful of 16x1024 bf16 steps,
            one eval, one Orbax checkpoint; loss finite and falling,
            attention resolved to the Pallas kernel
  serve     what `python -m nanosandbox_tpu.serve` builds from that
            checkpoint (paged pool, decode_impl=auto, bf16 KV), full
            warm-up, behind HTTP on a loopback port in a thread:
            /healthz, a few /generate, /stats; the same prompt through
            an Engine(decode_impl="xla") beside it
  quantised one request each through kv_dtype=int8 and int4 engines

Each phase prints one JSON line; a phase that fails raises, and nothing
is caught and carried past. Everything the run needs it builds under
<checkout>/out/chip_smoke (gitignored) from committed files. The LAST
line of stdout is the contract line:

  {"ok": true, "device": {"platform": "tpu", "kind": "...", "count": 1}}

It refuses to start (exit 2, no result line) unless
jax.devices()[0].platform == "tpu".

  python chip_smoke.py            # one chip, as the driver runs it
  python chip_smoke.py --chips=4  # ONLY the sharded train step on a
                                  # (data=1, fsdp=2, seq=1, model=2) mesh
                                  # vs the same seed and batch on one of
                                  # the four chips; last line count: 4

Timings printed here are smoke timings, not a benchmark.
"""

from __future__ import annotations

import argparse
import gc
import glob
import json
import os
import shutil
import sys
import threading
import time
import urllib.request

REPO = os.path.dirname(os.path.abspath(__file__))
WORK = os.path.join(REPO, "out", "chip_smoke")
CONFIG = os.path.join(REPO, "configs", "train_gpt2_124m_englishprose_bpe.py")
DATASET = "english_prose_bpe"
# What every phase must resolve to on the chip. (A CPU rehearsal of this
# file's control flow patches these two names from outside; the script
# itself has no way to run off the chip.)
DEVICE = "tpu"
KERNEL_IMPL = "pallas"

# Greedy tokens of the Pallas and the XLA engine may part at a bf16 tie.
# Where they do, the next-token logits of the two impls at that position
# must agree within this (the logits are bf16: 2 ulps at magnitude 8-16),
# and the two tokens' logits must be that close to each other.
LOGIT_TOL = 0.125
# One-chip vs four-chip per-step training loss (bf16 compute, different
# reduction order across the mesh): absolute, at a loss of 9-11. The
# first chip run agreed to 4e-5 over four steps.
LOSS_TOL = 0.005


def emit(phase: str, **kw) -> None:
    print(json.dumps({"phase": phase, **kw}), flush=True)


class CompileClock:
    """Seconds JAX spent in backend compilation (a persistent-cache hit
    counts with the little time it takes) and the cache's hit count,
    from jax.monitoring — the split each phase reports."""

    def __init__(self):
        from jax import monitoring

        self.compile_s = 0.0
        self.cache_hits = 0
        monitoring.register_event_duration_secs_listener(self._duration)
        monitoring.register_event_listener(self._event)

    def _duration(self, event: str, secs: float, **kw) -> None:
        if event == "/jax/core/compile/backend_compile_duration":
            self.compile_s += secs

    def _event(self, event: str, **kw) -> None:
        if event == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1

    def mark(self) -> tuple:
        return time.time(), self.compile_s, self.cache_hits

    def since(self, mark: tuple) -> dict:
        t0, c0, h0 = mark
        return {"seconds": round(time.time() - t0, 2),
                "compile_seconds": round(self.compile_s - c0, 2),
                "cache_hits": self.cache_hits - h0}


# ---------------------------------------------------------------------------
# phases (one chip)
# ---------------------------------------------------------------------------

def phase_prepare(clock: CompileClock) -> str:
    from nanosandbox_tpu.data import prepare

    mark = clock.mark()
    data_dir = os.path.join(WORK, "data")
    prepare.main([DATASET, f"--data_dir={data_dir}"])
    emit("prepare", dataset=DATASET, data_dir=os.path.relpath(data_dir, REPO),
         **clock.since(mark))
    return data_dir


def train_argv(data_dir: str, out_dir: str, steps: int) -> list[str]:
    """The 124M config as committed, cut to a handful of steps: batch
    16 x 1024, bf16, attention_impl=auto all come from the file."""
    return [CONFIG, f"--data_dir={data_dir}", f"--out_dir={out_dir}",
            f"--max_iters={steps}", f"--lr_decay_iters={steps}",
            "--warmup_iters=2", "--eval_interval=0", "--eval_iters=2",
            "--log_interval=1", "--profile_steps=", "--tensorboard=False",
            "--init_from=scratch", f"--device={DEVICE}"]


def phase_train(clock: CompileClock, data_dir: str, steps: int) -> str:
    from nanosandbox_tpu import train
    from nanosandbox_tpu.config import load_config

    mark = clock.mark()
    out_dir = os.path.join(WORK, "train")
    argv = train_argv(data_dir, out_dir, steps)
    cfg = load_config(argv)
    result = train.main(argv)
    gc.collect()  # the trainer and its state died with main()'s frame
    runs = sorted(glob.glob(os.path.join(out_dir, "runs", "*",
                                         "metrics.jsonl")))
    with open(runs[-1]) as f:
        rows = [json.loads(line) for line in f]
    losses = [r["train/loss"] for r in rows if "train/loss" in r]
    final = [result["final_train_loss"], result["final_val_loss"]]
    if not all(x == x and abs(x) < 1e4 for x in losses + final):
        raise AssertionError(f"non-finite loss: {losses} / {final}")
    if len(losses) != steps or not losses[-1] < losses[0]:
        raise AssertionError(f"loss did not fall over {steps} steps: {losses}")
    if result["attention_impl"] != KERNEL_IMPL:
        raise AssertionError(
            f"attention_impl resolved to {result['attention_impl']!r}")
    ckpts = sorted(os.listdir(os.path.join(out_dir, "ckpt")))
    if str(steps) not in ckpts:
        raise AssertionError(f"no checkpoint for step {steps}: {ckpts}")
    emit("train", config=os.path.relpath(CONFIG, REPO), steps=steps,
         model=f"{cfg.n_layer}L/{cfg.n_head}H/{cfg.n_embd}d/V{cfg.vocab_size}",
         batch=f"{cfg.batch_size}x{cfg.block_size}",
         compute_dtype=cfg.compute_dtype, losses=[round(x, 4) for x in losses],
         eval_train_loss=round(final[0], 4), eval_val_loss=round(final[1], 4),
         attention_impl=result["attention_impl"],
         loader_native=result["loader_native"], checkpoint=f"ckpt/{steps}",
         **clock.since(mark))
    return out_dir


def http_json(port: int, path: str, payload: dict | None = None) -> dict:
    data = None if payload is None else json.dumps(payload).encode()
    with urllib.request.urlopen(
            urllib.request.Request(f"http://127.0.0.1:{port}{path}",
                                   data=data), timeout=300) as r:
        return json.loads(r.read())


def next_token_logits(model, params, tokens: list[int], impl: str,
                      page: int = 16):
    """Next-token logits after ``tokens`` through the cached, paged,
    per-row path of ``impl`` — the programs the engine runs, without
    the engine (which does not hand out logits)."""
    import jax
    import jax.numpy as jnp
    from nanosandbox_tpu.models.gpt import init_paged_cache

    m = type(model)(cfg=model.cfg.replace(decode_impl=impl))
    n = len(tokens)
    T = -(-n // page) * page       # pad to whole pages; causal masking
    idx = jnp.asarray([tokens + [0] * (T - n)], jnp.int32)   # hides the pad
    nb = T // page
    pool = init_paged_cache(m.cfg, nb, page)
    logits, _ = jax.jit(lambda p, i, c: m.apply(
        {"params": p}, i, deterministic=True, cache=c,
        cache_index=jnp.zeros((1,), jnp.int32),
        block_table=jnp.arange(nb, dtype=jnp.int32)[None]))(params, idx, pool)
    return logits[0, n - 1].astype(jnp.float32)


def compare_greedy(model, params, prompt: list[int], a: list[int],
                   b: list[int]) -> dict:
    """Pallas tokens ``a`` vs XLA tokens ``b``: equal, or parted at a
    bf16 near-tie (see LOGIT_TOL)."""
    import jax.numpy as jnp

    if a == b:
        return {"tokens_equal": True, "n_tokens": len(a)}
    i = next(j for j, (x, y) in enumerate(zip(a, b)) if x != y)
    ctx = prompt + a[:i]
    la = next_token_logits(model, params, ctx, KERNEL_IMPL)
    lb = next_token_logits(model, params, ctx, "xla")
    impl_gap = float(jnp.max(jnp.abs(la - lb)))
    tie_gap = float(jnp.abs(la[a[i]] - la[b[i]]))
    out = {"tokens_equal": False, "first_divergence": i,
           "pallas_token": a[i], "xla_token": b[i],
           "logits_max_abs_diff": round(impl_gap, 5),
           "tie_gap": round(tie_gap, 5), "tolerance": LOGIT_TOL}
    if not (impl_gap <= LOGIT_TOL and tie_gap <= LOGIT_TOL):
        raise AssertionError(f"pallas and xla engines disagree: {out}")
    return out


def check_tokens(tokens: list[int], finish_reason: str, vocab: int,
                 what: str) -> None:
    if (len(tokens) != 16 or finish_reason != "length"
            or not all(0 <= t < vocab for t in tokens)):
        raise AssertionError(f"{what}: bad result {finish_reason} {tokens}")


def check_engine_stats(stats: dict, budget: dict, kv_dtype: str) -> None:
    rec = stats["recovery"]
    bad = {k: rec[k] for k in ("poisoned_steps", "recoveries", "failed",
                               "quarantined") if rec[k]}
    if bad:
        raise AssertionError(f"engine recovery posture not clean: {bad}")
    if stats["decode_attention_impl"] != KERNEL_IMPL:
        raise AssertionError(
            f"decode_attention_impl is {stats['decode_attention_impl']!r}")
    if stats["kv_dtype"] != kv_dtype:
        raise AssertionError(f"kv_dtype {stats['kv_dtype']!r} != {kv_dtype!r}")
    over = {k: (n, budget.get(k)) for k, n in stats["trace_counts"].items()
            if n > budget.get(k, 0)}
    if over:
        raise AssertionError(f"trace counts over the published budget: {over}")


def phase_serve(clock: CompileClock, data_dir: str, out_dir: str):
    from nanosandbox_tpu.serve.__main__ import build_server, parse_args
    from nanosandbox_tpu.serve.engine import Engine

    mark = clock.mark()
    # The defaults users get — paged pool, decode_impl=auto, bf16 KV,
    # --warmup=full — sized (2 slots x 128 positions) so the whole
    # (wave x bucket) prefill grid compiles inside the smoke's limit.
    served = build_server(parse_args([
        f"--out_dir={out_dir}", f"--data_dir={data_dir}",
        f"--dataset={DATASET}", "--host=127.0.0.1", "--port=0",
        "--num_slots=2", "--max_len=128", f"--device={DEVICE}"]))
    warm = clock.since(mark)
    thread = threading.Thread(target=served.serve_forever, daemon=True)
    thread.start()
    try:
        port, engine, tok = served.port, served.engine, served.tokenizer
        health = http_json(port, "/healthz?ready=1")
        if not health.get("ok"):
            raise AssertionError(f"/healthz not green: {health}")
        with open(os.path.join(REPO, "data", "fixtures",
                               "english_prose.txt")) as f:
            ids = tok.encode(f.read(4000))
        # Different prompt lengths; the last shares its first 40 tokens
        # (two whole 16-position pages) with the first, which has
        # finished and donated its blocks by then.
        prompts = [ids[:40], ids[100:117], ids[200:290], ids[:40] + ids[300:330]]
        replies = []
        for p in prompts:
            r = http_json(port, "/generate", {
                "prompt_tokens": p, "max_new_tokens": 16, "temperature": 0})
            check_tokens(r["tokens"], r["finish_reason"],
                         engine.cfg.vocab_size, "/generate")
            replies.append(r["tokens"])
        stats = http_json(port, "/stats")
        check_engine_stats(stats, engine.max_programs(), "bf16")
        if stats["kv_pool"]["prefix_hit_tokens"] < 32:
            raise AssertionError(
                f"the shared prefix was not served from the cache: "
                f"{stats['kv_pool']}")
        if stats["loop"]["dead"]:
            raise AssertionError(f"engine loop died: {stats['loop']}")
    finally:
        served.server.shutdown()
        thread.join(timeout=60)
    emit("serve", warmup=warm, decode_attention_impl=stats["decode_attention_impl"],
         kv_dtype=stats["kv_dtype"], paged=stats["paged"],
         kv_page_size=stats["kv_page_size"],
         prompt_lens=[len(p) for p in prompts],
         generated=[len(t) for t in replies],
         prefix_hit_tokens=stats["kv_pool"]["prefix_hit_tokens"],
         trace_counts=stats["trace_counts"], budget=engine.max_programs(),
         poisoned_steps=stats["recovery"]["poisoned_steps"],
         recoveries=stats["recovery"]["recoveries"],
         ttft_s=stats["ttft_s"], **clock.since(mark))

    # The same prompt through an XLA engine built beside it on the chip.
    mark = clock.mark()
    xla = Engine(engine.model, engine.params, num_slots=2, max_len=128,
                 decode_impl="xla")
    xla.submit(prompts[0], 16)
    (res,) = xla.drain()
    cmp = compare_greedy(engine.model, engine.params, prompts[0],
                         replies[0], list(res.tokens))
    emit("serve_vs_xla", xla_decode_attention_impl=xla.decode_impl, **cmp,
         **clock.since(mark))
    return engine.model, engine.params, prompts[1], replies[1]


def phase_quantised(clock: CompileClock, model, params, prompt: list[int],
                    bf16_tokens: list[int]) -> None:
    from nanosandbox_tpu.serve.engine import Engine

    for kv_dtype in ("int8", "int4"):
        mark = clock.mark()
        eng = Engine(model, params, num_slots=2, max_len=128,
                     kv_dtype=kv_dtype)
        eng.submit(prompt, 16)
        (res,) = eng.drain()
        toks = list(res.tokens)
        check_tokens(toks, res.finish_reason, eng.cfg.vocab_size, kv_dtype)
        check_engine_stats(eng.stats(), eng.max_programs(), kv_dtype)
        agree = sum(x == y for x, y in zip(toks, bf16_tokens))
        emit(f"serve_{kv_dtype}", decode_attention_impl=eng.decode_impl,
             kv_dtype=eng.kv_dtype, generated=len(toks),
             tokens_equal_to_bf16=f"{agree}/16",
             poisoned_steps=eng.poisoned_steps, **clock.since(mark))
        del eng
        gc.collect()


# ---------------------------------------------------------------------------
# --chips=4: the sharded train step against one chip
# ---------------------------------------------------------------------------

def tree_bytes_per_device(tree, devices) -> list[int]:
    import jax

    per = {d.id: 0 for d in devices}
    for leaf in jax.tree.leaves(tree):
        for s in leaf.addressable_shards:
            per[s.device.id] += s.data.nbytes
    return [per[d.id] for d in devices]


def run_steps(trainer, steps: int):
    """``steps`` train steps from the config's seed; per-step losses and
    the final state. The loader draws the same batches for the same seed
    whatever the mesh."""
    import jax

    state = trainer.init_state()
    placed = tree_bytes_per_device(state, list(trainer.mesh.devices.flat))
    train_step, _ = trainer.compiled_steps()
    loader = trainer.make_loader("train", prefetch=False)
    rng = trainer.train_rng(trainer.cfg.seed + 7)
    losses = []
    try:
        for i in range(steps):
            xb, yb = next(loader)
            state, m = train_step(state, trainer.to_global(xb),
                                  trainer.to_global(yb),
                                  jax.random.fold_in(rng, i))
            losses.append(float(m["loss"]))
    finally:
        loader.close()
    return losses, placed


def phase_four_chips(clock: CompileClock, data_dir: str, steps: int) -> None:
    import jax
    from nanosandbox_tpu.config import load_config
    from nanosandbox_tpu.train import Trainer

    devices = jax.devices()
    base = load_config(train_argv(data_dir, os.path.join(WORK, "train4"),
                                  steps))
    mark = clock.mark()
    sharded = Trainer(base.replace(mesh_dp=1, mesh_fsdp=2, mesh_sp=1,
                                   mesh_tp=2, shard_params=True),
                      mesh_devices=devices[:4])
    losses4, bytes4 = run_steps(sharded, steps)
    mesh = {k: int(v) for k, v in sharded.mesh.shape.items()}
    del sharded
    gc.collect()
    if not (all(b > 0 for b in bytes4) and max(bytes4) < 1.2 * min(bytes4)):
        raise AssertionError(f"state is not spread over the mesh: {bytes4}")
    emit("train_4chip", mesh=mesh, shard_params=True, steps=steps,
         losses=[round(x, 4) for x in losses4],
         state_bytes_per_device=bytes4, **clock.since(mark))

    mark = clock.mark()
    single = Trainer(base.replace(mesh_dp=1), mesh_devices=[devices[0]])
    losses1, bytes1 = run_steps(single, steps)
    del single
    gc.collect()
    emit("train_1chip", steps=steps, losses=[round(x, 4) for x in losses1],
         state_bytes_per_device=bytes1, **clock.since(mark))

    diffs = [abs(a - b) for a, b in zip(losses4, losses1)]
    ok = (all(x == x for x in losses4 + losses1) and max(diffs) <= LOSS_TOL
          and losses4[-1] < losses4[0])
    emit("compare_4chip_vs_1chip", max_abs_loss_diff=round(max(diffs), 5),
         per_step_abs_diff=[round(d, 5) for d in diffs], tolerance=LOSS_TOL,
         state_bytes_1chip_over_4chip=round(bytes1[0] / max(bytes4), 2),
         ok=ok)
    if not ok:
        raise AssertionError(
            f"sharded and single-chip losses disagree: {losses4} vs {losses1}")


# ---------------------------------------------------------------------------

def main(argv: list[str] | None = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    args = ap.parse_args(argv)

    import jax

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: JAX found no TPU (devices: {jax.devices()}); "
              "this script proves the chip path and runs nowhere else.",
              file=sys.stderr)
        return 2
    if len(jax.devices()) < args.chips:
        print(f"chip_smoke: --chips={args.chips} but JAX sees "
              f"{len(jax.devices())} device(s).", file=sys.stderr)
        return 2

    from nanosandbox_tpu.utils.compile_cache import enable_compile_cache

    cache_dir = enable_compile_cache()
    # Everything under WORK is this script's own and is built anew by
    # every run from committed files (the compile cache lives elsewhere).
    shutil.rmtree(WORK, ignore_errors=True)
    clock = CompileClock()
    t0 = time.time()
    emit("start", chips=args.chips, device_kind=dev.device_kind,
         n_devices=len(jax.devices()), jax=jax.__version__,
         compile_cache=cache_dir,
         cache_entries_at_start=len(os.listdir(cache_dir))
         if os.path.isdir(cache_dir) else 0)
    data_dir = phase_prepare(clock)
    if args.chips == 4:
        phase_four_chips(clock, data_dir, steps=4)
    else:
        out_dir = phase_train(clock, data_dir, steps=8)
        model, params, prompt, bf16_tokens = phase_serve(clock, data_dir,
                                                         out_dir)
        phase_quantised(clock, model, params, prompt, bf16_tokens)
    emit("done", seconds=round(time.time() - t0, 2),
         compile_seconds=round(clock.compile_s, 2),
         cache_hits=clock.cache_hits)
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
