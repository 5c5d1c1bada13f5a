"""Throughput sweep on the current backend: impl x batch x remat x chunk.

Promotes round-1's perf_probe.py scratch script into a proper JSON-emitting
tool (VERDICT.md next-step #4). Each point trains GPT-2 124M (or a tiny
model on CPU) for a few timed steps and records tokens/sec/chip + MFU;
results stream to stdout as JSON lines and are summarized at the end.

Usage:
    python scripts/perf_sweep.py [--out=sweep.json] [--iters=10]
        [--impls=pallas,xla] [--batch_sizes=8,16,32,64] [--full]
        [--mode=remat|longcontext|scale]

Default sweeps impl x batch at remat=False/chunk=128, then re-measures the
winner with remat on/off and chunked vs full loss. --full crosses
everything (slow). --mode presets replace the grid (and take precedence
over --full): 'remat' compares no-remat vs remat_policy
save_attention/full per batch size; 'longcontext' measures block 8192
with chunked loss; 'scale' measures 350M/760M single-chip points;
'decode' measures KV-cached vs windowed generation tok/s; 'autoconfig'
measures the UNPINNED flag surface of a real config file
(--config=configs/train_gpt2_124m_....py) so the headline number is
proven for the command a user actually types, not just bench.py's
hand-pinned flags.
"""

from __future__ import annotations

import itertools
import json
import os
import sys
import tempfile

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


from nanosandbox_tpu.utils.benchmarking import measure_train_throughput


def main(argv: list[str]) -> list[dict]:
    kv = dict(a.lstrip("-").split("=", 1) for a in argv if "=" in a)
    full = "--full" in argv
    import jax

    from nanosandbox_tpu.config import TrainConfig
    from nanosandbox_tpu.data.prepare import prepare_char_dataset

    on_tpu = jax.default_backend() == "tpu"
    n_chips = len(jax.devices())
    tmp = tempfile.mkdtemp(prefix="sweep_")
    data_dir = os.path.join(tmp, "data")
    prepare_char_dataset(os.path.join(data_dir, "shakespeare_char"),
                         allow_synthetic=True,
                         url="http://invalid.localhost/offline")

    if on_tpu:
        base = TrainConfig(
            out_dir=os.path.join(tmp, "out"), data_dir=data_dir,
            dataset="shakespeare_char", vocab_size=50304,
            n_layer=12, n_head=12, n_embd=768, block_size=1024,
            max_iters=0, eval_interval=0, dropout=0.0,
            compute_dtype="bfloat16", tensorboard=False)
        impls = kv.get("impls", "pallas,xla,pallas_jax").split(",")
        batches = [int(b) for b in kv.get("batch_sizes", "8,16,32,64").split(",")]
        warmup, iters = 2, int(kv.get("iters", 10))
    else:
        base = TrainConfig(
            out_dir=os.path.join(tmp, "out"), data_dir=data_dir,
            dataset="shakespeare_char",
            n_layer=2, n_head=2, n_embd=64, block_size=128,
            max_iters=0, eval_interval=0, dropout=0.0,
            compute_dtype="float32", tensorboard=False)
        impls = kv.get("impls", "xla").split(",")
        batches = [int(b) for b in kv.get("batch_sizes", "8").split(",")]
        warmup, iters = 1, int(kv.get("iters", 3))

    results = []

    def record(point, cfg):
        """Measure cfg, merge into the point dict, stream + collect it —
        errors (a config that does not fit, a kernel the compiler
        refuses) become recorded rows, never crashes."""
        try:
            point.update(measure_train_throughput(cfg, warmup, iters))
        except Exception as e:
            point["error"] = f"{type(e).__name__}: {str(e)[:200]}"
        print(json.dumps(point), flush=True)
        results.append(point)
        return point

    def run_point(**overrides):
        # batch_size values are PER-CHIP (same semantics as bench.py, so
        # sweep points stay comparable to bench output on any host size);
        # the global batch scales with the chip count. The recorded point
        # keeps the per-chip value so re-feeding a winner doesn't rescale.
        point = {k: overrides[k] for k in sorted(overrides)}
        if "batch_size" in overrides:
            overrides = dict(overrides,
                             batch_size=overrides["batch_size"] * n_chips)
        cfg = base.replace(**overrides)
        point["global_batch_size"] = cfg.batch_size
        return record(point, cfg)

    mode = kv.get("mode", "")
    if mode and full:
        print(json.dumps({"warning": "--full is ignored when --mode is "
                                     "given"}), flush=True)
    if mode and mode not in ("remat", "longcontext", "scale", "decode",
                             "autoconfig", "statlayout"):
        raise SystemExit(f"unknown --mode={mode} (expected 'remat', "
                         "'longcontext', 'scale', 'decode', 'autoconfig', "
                         "or 'statlayout')")
    if mode == "decode":
        results.extend(_decode_mode(kv, on_tpu))
    elif mode == "autoconfig":
        # VERDICT r3 next #8: bench.py hand-pins the fast flags; this
        # measures the config FILE's own flag surface (attention_impl
        # auto, loss_chunk_size auto, remat as written) so the recorded
        # headline holds for `python -m nanosandbox_tpu.train <config>`.
        cfg_path = kv.get("config")
        if not cfg_path:
            raise SystemExit("--mode=autoconfig requires --config=<file.py>")
        from nanosandbox_tpu.config import load_config

        user = load_config([cfg_path])
        # resolved_loss_chunk_size is reported by measure_train_throughput
        # from the Trainer that actually runs — never recomputed here,
        # which would silently desync from train.py's resolution.
        point = {"mode": "autoconfig", "config": os.path.basename(cfg_path),
                 "attention_impl": user.attention_impl,
                 "loss_chunk_size": user.loss_chunk_size,
                 "remat": user.remat, "batch_size": user.batch_size}
        cfg = user.replace(
            out_dir=os.path.join(tmp, "out"), data_dir=data_dir,
            dataset="shakespeare_char", vocab_size=user.vocab_size or 50304,
            max_iters=0, eval_interval=0, tensorboard=False,
            profile_steps="", init_from="scratch")
        record(point, cfg)
    elif mode == "statlayout":
        # A/B the flash-backward stat-operand layout (r3 VERDICT next #6):
        # 'compact' cuts ~128x of lane-replicated stat HBM traffic at the
        # cost of an in-kernel expansion matmul; gradients are bitwise
        # identical (tests/test_attention.py + on-chip parity check).
        # run_point's try/except keeps a Mosaic regression as a
        # recorded error row, not a crash. Also A/B'd at 8k context where stat bytes scale with T.
        for bs in batches:
            for layout in ("replicated", "compact"):
                run_point(attention_impl="pallas", batch_size=bs,
                          loss_chunk_size=0, attention_stat_layout=layout)
        if on_tpu:
            for layout in ("replicated", "compact"):
                run_point(attention_impl="pallas", batch_size=1,
                          block_size=8192, loss_chunk_size=512,
                          attention_stat_layout=layout)
    elif mode == "remat":
        # Round-2 VERDICT weak #2: remat was 35.5% MFU vs 43% without.
        # Compare the selective policy (saves flash residuals, backward
        # never re-runs the forward kernel) against classic full remat
        # and the no-remat ceiling, at the remat configs' batch size.
        # loss_chunk_size pinned to 0 (full logits): the TrainConfig
        # default of 128 would silently put these points on the chunked
        # path, ~10% off the full-logits numbers bench.py reports.
        for bs in batches:
            run_point(attention_impl="pallas", batch_size=bs, remat=False,
                      loss_chunk_size=0)
            for policy in ("save_attention", "full"):
                run_point(attention_impl="pallas", batch_size=bs,
                          remat=True, remat_policy=policy,
                          loss_chunk_size=0)
    elif mode == "scale":
        # Model-size scaling on ONE chip: bigger matmuls feed the MXU
        # better (124M ~39-43% MFU by chip conditions; 350M ~47%; 760M
        # fits in 16 GB HBM only with remat). batch_size here is pinned
        # per point — the known-good HBM fit, not the CLI list.
        # 350M batch 8: full logits for the MFU-ceiling number; the
        # batch-16 remat point pins the chunked loss at 512 (full logits
        # there are 3.3 GB and the lingering allocation makes the NEXT
        # point spill — memory economy is the whole reason to remat).
        run_point(n_layer=24, n_head=16, n_embd=1024, batch_size=8,
                  attention_impl="pallas", remat=False,
                  loss_chunk_size=0)                             # 350M
        run_point(n_layer=24, n_head=16, n_embd=1024, batch_size=16,
                  attention_impl="pallas", remat=True,
                  loss_chunk_size=512)
        run_point(n_layer=36, n_head=20, n_embd=1280, batch_size=8,
                  attention_impl="pallas", remat=True,
                  loss_chunk_size=512)                           # 760M
    elif mode == "longcontext":
        # Round-2 VERDICT weak #1 follow-through: a measured long-context
        # number on this hardware (single chip -> plain flash at T=8192;
        # the ring carries the same kernel across chips). The block-1024
        # default batch list would mostly OOM at 8192 tokens/sequence, so
        # this mode has its own default; --batch_sizes still overrides.
        if "batch_sizes" not in kv:
            batches = [1, 2]
        for bs in batches:
            for remat, policy in [(False, "save_attention"),
                                  (True, "save_attention"), (True, "full")]:
                run_point(attention_impl="pallas", batch_size=bs,
                          block_size=8192, remat=remat, remat_policy=policy,
                          loss_chunk_size=512)
    elif full:
        grid = itertools.product(impls, batches, [False, True], [0, 128])
        for impl, bs, remat, chunk in grid:
            run_point(attention_impl=impl, batch_size=bs, remat=remat,
                      loss_chunk_size=chunk)
    else:
        for impl, bs in itertools.product(impls, batches):
            run_point(attention_impl=impl, batch_size=bs)
        good = [r for r in results if "error" not in r]
        if good:
            best = max(good, key=lambda r: r["tokens_per_sec_per_chip"])
            for remat, chunk in [(True, 128), (False, 0), (True, 0)]:
                run_point(attention_impl=best["attention_impl"],
                          batch_size=best["batch_size"], remat=remat,
                          loss_chunk_size=chunk)

    good = [r for r in results
            if "error" not in r and "tokens_per_sec_per_chip" in r]
    if good:
        best = max(good, key=lambda r: r["tokens_per_sec_per_chip"])
        print(json.dumps({"best": best}), flush=True)
    if "out" in kv:
        with open(kv["out"], "w") as f:
            json.dump(results, f, indent=1)
    return results


def _decode_mode(kv, on_tpu) -> list[dict]:
    """KV-cached vs sliding-window decode throughput (VERDICT r3 next #3).

    Both paths run as ONE jit-compiled program (prefill + lax.scan), so the
    comparison isolates the algorithmic difference — cached O(1) model work
    per token vs the windowed path's full block_size re-forward — from
    dispatch overhead. Sync is a token readback, which waits for the
    whole program.
    """
    import time
    from functools import partial

    import jax
    import jax.numpy as jnp

    from nanosandbox_tpu.config import GPTConfig
    from nanosandbox_tpu.models.gpt import GPT
    from nanosandbox_tpu.sample import (_generate_windowed,
                                        cast_params_for_serving, generate)

    if on_tpu:
        gcfg = GPTConfig(n_layer=12, n_head=12, n_embd=768, block_size=1024,
                         vocab_size=50304, compute_dtype="bfloat16",
                         attention_impl="auto")
        prompt_len = int(kv.get("prompt_len", 64))
        new_tokens = int(kv.get("new_tokens", 448))
        batches = [int(b) for b in kv.get("batch_sizes", "1,8").split(",")]
        reps = int(kv.get("reps", 3))
    else:
        gcfg = GPTConfig(n_layer=2, n_head=2, n_embd=64, block_size=128,
                         vocab_size=256, compute_dtype="float32",
                         attention_impl="xla")
        prompt_len, new_tokens, batches, reps = 8, 24, [1], 1

    model = GPT(gcfg)
    params = model.init(jax.random.key(0),
                        jnp.zeros((1, 8), jnp.int32))["params"]
    # Serve in compute_dtype exactly as sample.py main does: batch-1 decode
    # is weight-read-bound, so f32 params would halve BOTH paths' rates.
    params = cast_params_for_serving(params, gcfg.compute_dtype)
    results = []
    for bs in batches:
        idx = jax.random.randint(jax.random.key(1), (bs, prompt_len), 0,
                                 gcfg.vocab_size, jnp.int32)
        for path, fn in (("cached", generate),
                         ("windowed", _generate_windowed)):
            point = {"mode": "decode", "path": path, "batch_size": bs,
                     "prompt_len": prompt_len, "new_tokens": new_tokens}
            try:
                g = jax.jit(partial(fn, model, max_new_tokens=new_tokens,
                                    temperature=0.8, top_k=40,
                                    block_size=gcfg.block_size))
                out = g(params, idx, rng=jax.random.key(2))
                int(out[0, -1])  # hard sync past compile + warmup
                t0 = time.perf_counter()
                for r in range(reps):
                    out = g(params, idx, rng=jax.random.key(3 + r))
                int(out[0, -1])
                dt = (time.perf_counter() - t0) / reps
                point.update(gen_s=round(dt, 4),
                             decode_tok_per_sec=round(
                                 bs * new_tokens / dt, 1))
            except Exception as e:
                point["error"] = f"{type(e).__name__}: {str(e)[:200]}"
            print(json.dumps(point), flush=True)
            results.append(point)
    return results


if __name__ == "__main__":
    main(sys.argv[1:])
