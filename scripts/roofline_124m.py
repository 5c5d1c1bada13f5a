"""Per-component time breakdown of the 124M train step on one chip.

Round-4 VERDICT weak #1: the single-chip 124M headline sat at 99-100k
tok/s / 43% MFU for three rounds while 350M reached 48.4% on the same
chip, and no committed artifact showed WHERE the ~164 ms step goes. This
script answers that by timing the pieces separately, plus candidate
replacements for the suspected bottleneck (the weight-tied LM head +
cross entropy, whose full-logits f32 tensor is B*T*V*4 = 3.3 GB of HBM
traffic per pass at the bench shape):

  full_step        the real jitted train step (anchor; = bench.py timing)
  body_fwd_bwd     transformer body only (return_hidden, loss=mean(hidden))
  head_*           LM head + CE fwd+bwd on a FIXED hidden buffer:
                     full_f32    current default (f32 attend + CE)
                     full_bf16   bf16-materialized logits, f32 softmax math
                     lse_f32     logsumexp-form CE (fusion-friendly)
                     chunk_N     existing chunked path at several sizes
  optimizer        tx.update + apply_updates on fixed grads
  attention_12x    12 layers of just the flash kernel fwd+bwd

Timing matches utils/benchmarking.py: enqueue all iters, one scalar
readback at the end (per-iter syncs would swamp ms-scale components).
Host-clock timing of a queue, not a profiler trace: ROADMAP A3 replaces
it with per-kernel device times.

Usage: python scripts/roofline_124m.py [--iters=20] [--batch_size=16]
       [--out=benchmarks/r5/roofline_124m.json]
"""

from __future__ import annotations

import json
import os
import sys
import tempfile
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def time_fn(fn, args, iters: int, readback) -> float:
    """Enqueue `iters` calls of jitted `fn`, sync once; ms per call."""
    out = fn(*args)
    float(readback(out))  # warmup + hard sync (compile outside the clock)
    t0 = time.perf_counter()
    for _ in range(iters):
        out = fn(*args)
    float(readback(out))
    return (time.perf_counter() - t0) / iters * 1000


def main(argv: list[str]) -> dict:
    kv = dict(a.lstrip("-").split("=", 1) for a in argv if "=" in a)
    iters = int(kv.get("iters", 20))
    B = int(kv.get("batch_size", 16))

    import jax
    import jax.numpy as jnp
    from jax import lax

    from nanosandbox_tpu.config import TrainConfig
    from nanosandbox_tpu.data.prepare import prepare_char_dataset
    from nanosandbox_tpu.models.gpt import (chunked_cross_entropy_loss,
                                            cross_entropy_loss)
    from nanosandbox_tpu.train import Trainer

    tmp = tempfile.mkdtemp(prefix="roofline_")
    data_dir = os.path.join(tmp, "data")
    prepare_char_dataset(os.path.join(data_dir, "shakespeare_char"),
                         allow_synthetic=True,
                         url="http://invalid.localhost/offline")
    cfg = TrainConfig(
        out_dir=os.path.join(tmp, "out"), data_dir=data_dir,
        dataset="shakespeare_char", vocab_size=50304,
        n_layer=12, n_head=12, n_embd=768, block_size=1024,
        batch_size=B, max_iters=0, eval_interval=0, log_interval=1,
        dropout=0.0, compute_dtype="bfloat16", loss_chunk_size=0,
        attention_impl="auto", tensorboard=False)
    trainer = Trainer(cfg)
    state = trainer.init_state()
    train_step, _ = trainer.compiled_steps()
    loader = trainer.make_loader("train", prefetch=False)
    xb, yb = next(loader)
    loader.close()
    x, y = trainer.to_global(xb), trainer.to_global(yb)
    rng = trainer.train_rng(0)

    T, C, V = cfg.block_size, cfg.n_embd, 50304
    results: dict[str, float] = {}

    # -- anchor: the real train step (no donation here; state reused) -----
    step_nodonate = jax.jit(trainer._train_step_fn)
    results["full_step"] = time_fn(
        step_nodonate, (state, x, y, rng), iters, lambda o: o[1]["loss"])

    # -- body only: fwd+bwd through the 12 blocks, no head ----------------
    def body_loss(params, x):
        h = trainer.model.apply({"params": params}, x, deterministic=True,
                                return_hidden=True)
        return h.astype(jnp.float32).mean()

    body_g = jax.jit(jax.value_and_grad(body_loss))
    results["body_fwd_bwd"] = time_fn(
        body_g, (state["params"], x), iters, lambda o: o[0])

    # -- head variants on a fixed hidden buffer ---------------------------
    hidden = trainer.model.apply({"params": state["params"]}, x,
                                 deterministic=True, return_hidden=True)
    hidden = jax.block_until_ready(hidden)
    emb = state["params"]["wte"]["embedding"]  # (V, C) f32

    def head_full_f32(h, w, y):  # current default: f32 attend + CE
        logits = lax.dot_general(h.astype(jnp.float32), w,
                                 (((2,), (1,)), ((), ())))
        return cross_entropy_loss(logits, y)

    def head_full_bf16(h, w, y):  # bf16-materialized logits
        logits = lax.dot_general(h.astype(jnp.bfloat16),
                                 w.astype(jnp.bfloat16),
                                 (((2,), (1,)), ((), ())),
                                 preferred_element_type=jnp.bfloat16)
        return cross_entropy_loss(logits, y)

    def head_lse_f32(h, w, y):  # logsumexp-form CE (no logp tensor)
        logits = lax.dot_general(h.astype(jnp.float32), w,
                                 (((2,), (1,)), ((), ())))
        lse = jax.nn.logsumexp(logits, axis=-1)
        tgt = jnp.take_along_axis(logits, y[..., None], axis=-1)[..., 0]
        return (lse - tgt).mean()

    def head_lse_bf16(h, w, y):
        logits = lax.dot_general(h.astype(jnp.bfloat16),
                                 w.astype(jnp.bfloat16),
                                 (((2,), (1,)), ((), ())),
                                 preferred_element_type=jnp.bfloat16)
        logits32 = logits.astype(jnp.float32)
        lse = jax.nn.logsumexp(logits32, axis=-1)
        tgt = jnp.take_along_axis(logits32, y[..., None], axis=-1)[..., 0]
        return (lse - tgt).mean()

    for name, fn in [("head_full_f32", head_full_f32),
                     ("head_full_bf16", head_full_bf16),
                     ("head_lse_f32", head_lse_f32),
                     ("head_lse_bf16", head_lse_bf16)]:
        g = jax.jit(jax.value_and_grad(fn, argnums=(0, 1)))
        results[name] = time_fn(g, (hidden, emb, y), iters, lambda o: o[0])

    for cs in (256, 512, 1024):
        def head_chunk(h, w, y, cs=cs):
            return chunked_cross_entropy_loss(h, w, y, chunk_size=cs,
                                              compute_dtype="bfloat16")
        g = jax.jit(jax.value_and_grad(head_chunk, argnums=(0, 1)))
        results[f"head_chunk_{cs}"] = time_fn(
            g, (hidden, emb, y), iters, lambda o: o[0])

    # -- optimizer ---------------------------------------------------------
    grads = jax.tree.map(jnp.zeros_like, state["params"])

    def opt_only(grads, opt_state, params):
        import optax
        updates, opt_state = trainer.tx.update(grads, opt_state, params)
        params = optax.apply_updates(params, updates)
        return params["wte"]["embedding"][0, 0], opt_state

    opt_j = jax.jit(opt_only)
    results["optimizer"] = time_fn(
        opt_j, (grads, state["opt_state"], state["params"]), iters,
        lambda o: o[0])

    # -- attention kernel, 12 layers worth --------------------------------
    from nanosandbox_tpu.ops.attention import causal_attention
    q = jax.random.normal(jax.random.key(0),
                          (B, cfg.n_head, T, C // cfg.n_head), jnp.bfloat16)

    def attn12(q):
        def body(x, _):
            # stat_layout matches the production (TrainConfig) default so
            # the component number decomposes the same step full_step runs.
            o = causal_attention(x, x, x, impl="auto",
                                 stat_layout=cfg.attention_stat_layout)
            return o, None
        o, _ = lax.scan(body, q, None, length=cfg.n_layer)
        return o.astype(jnp.float32).mean()

    attn_g = jax.jit(jax.value_and_grad(attn12))
    results["attention_12x"] = time_fn(attn_g, (q,), iters, lambda o: o[0])

    report = {
        "shape": {"B": B, "T": T, "C": C, "V": V, "n_layer": cfg.n_layer},
        "iters": iters,
        "ms": {k: round(v, 2) for k, v in results.items()},
        "derived": {
            "head_current_ms": round(results["head_full_f32"], 2),
            "body_plus_head_plus_opt_ms": round(
                results["body_fwd_bwd"] + results["head_full_f32"]
                + results["optimizer"], 2),
            "full_step_ms": round(results["full_step"], 2),
        },
    }
    print(json.dumps(report, indent=1))
    out = kv.get("out")
    if out:
        os.makedirs(os.path.dirname(out), exist_ok=True)
        with open(out, "w") as f:
            json.dump(report, f, indent=1)
    return report


if __name__ == "__main__":
    main(sys.argv[1:])
