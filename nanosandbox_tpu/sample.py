"""Autoregressive sampling from a trained checkpoint.

nanoGPT ships sample.py alongside train.py (the reference exercises the
trainer only, SURVEY.md §2.3, but generation is part of the nanoGPT
capability surface a user expects). TPU-native decode: one prefill pass
then a KV-cached lax.scan — one token per step against per-layer cache
buffers, fully jit-compiled, no Python control flow per token. Requests
longer than block_size fall back to the sliding-window full-forward scan.

    python -m nanosandbox_tpu.sample --out_dir=out --start="\\n" \
        --num_samples=3 --max_new_tokens=200 --temperature=0.8 --top_k=40
"""

from __future__ import annotations

import os
import sys
from functools import partial


def _sample_token(logits_i, rng, *, temperature: float, top_k: int,
                  top_p: float = 1.0):
    """One sampling decision from (B, V) logits. temperature=0 is greedy
    (argmax, no RNG consumed) — torch's convention and the determinism
    anchor for the cached-vs-windowed parity tests. top_k and top_p
    (nucleus) compose: k-truncation first, then the smallest probability
    mass >= top_p survives.

    temperature/top_k/top_p may also be (B,) vectors — each row then
    samples under its OWN parameters (the serve engine's continuous
    batch mixes requests with different settings in one step). The
    vector path also accepts ``rng`` as a (B,) batch of typed keys
    (one independent stream per row, so a request's tokens don't
    depend on which other requests share its batch); with a single
    key it splits once and samples all rows from the same stream."""
    import jax
    import jax.numpy as jnp

    logits_i = logits_i.astype(jnp.float32)
    if any(getattr(x, "ndim", 0) >= 1 for x in (temperature, top_k, top_p)):
        return _sample_token_rows(logits_i, rng, temperature=temperature,
                                  top_k=top_k, top_p=top_p)
    if temperature == 0.0:
        return jnp.argmax(logits_i, axis=-1).astype(jnp.int32), rng
    logits_i = logits_i / temperature
    if top_k > 0:
        k = min(top_k, logits_i.shape[-1])  # nanoGPT clamps to vocab
        # lax.top_k, not a full vocab sort: the decode loop runs this every
        # token and a 50k-entry sort costs more than the whole 124M
        # per-token matmul work.
        kth = jax.lax.top_k(logits_i, k)[0][:, -1][:, None]
        logits_i = jnp.where(logits_i < kth, -1e30, logits_i)
    if top_p < 1.0:
        # Nucleus filter: drop tokens outside the smallest set whose
        # probability mass reaches top_p. Sorted once (descending); a
        # token survives if the mass BEFORE it is still < top_p (keeps
        # at least the top-1 token by construction).
        sort_idx = jnp.argsort(-logits_i, axis=-1)
        sorted_logits = jnp.take_along_axis(logits_i, sort_idx, axis=-1)
        probs = jax.nn.softmax(sorted_logits, axis=-1)
        mass_before = jnp.cumsum(probs, axis=-1) - probs
        # .at[0].set(True): mass_before[0] == 0 is not < top_p when
        # top_p <= 0, which would mask EVERY token and turn categorical
        # into uniform-over-vocab garbage; the top-1 token always survives.
        keep_sorted = (mass_before < top_p).at[:, 0].set(True)
        keep = jnp.zeros_like(keep_sorted).at[
            jnp.arange(keep_sorted.shape[0])[:, None], sort_idx
        ].set(keep_sorted)
        logits_i = jnp.where(keep, logits_i, -1e30)
    rng, sub = jax.random.split(rng)
    return jax.random.categorical(sub, logits_i).astype(jnp.int32), rng


def _filter_logits_rows(logits_i, *, temperature, top_k, top_p):
    """Per-row temperature/top-k/nucleus filtering of (B, V) float32
    logits: returns categorical-ready logits (filtered entries -1e30).
    Shared by _sample_token_rows and the speculative-verify path
    (serve/spec.py) — the verify step must score draft tokens against
    EXACTLY the distribution the decode step samples from, or rejection
    sampling stops preserving the output distribution, so the filter
    lives in one function both compile.

    Rows with temperature <= 0 are scaled by 1 (the caller takes argmax
    of the RAW logits for those, the scalar greedy contract).

    Costs one full-vocab argsort per call — the descending permutation
    is shared by the per-row kth threshold (lax.top_k needs a static k;
    per-row k does not have one) and the nucleus cumsum. The sort only
    RUNS when some row actually filters (lax.cond below): greedy rows
    never consume the filtered logits (their callers take raw argmax),
    and t>0 rows with top-k/top-p disabled get identity filtering, so
    an all-greedy/unfiltered batch — the serving common case, and every
    speculative-verify step of a greedy workload — skips the whole sort
    at runtime while staying ONE compiled program."""
    import jax
    import jax.numpy as jnp
    from jax import lax

    B, V = logits_i.shape
    t = jnp.broadcast_to(jnp.asarray(temperature, jnp.float32), (B,))
    k = jnp.broadcast_to(jnp.asarray(top_k, jnp.int32), (B,))
    p = jnp.broadcast_to(jnp.asarray(top_p, jnp.float32), (B,))

    x = logits_i / jnp.where(t > 0, t, 1.0)[:, None]

    def _full(x):
        # ONE shared descending permutation serves both filters (the
        # full-vocab sort is this path's hot cost — see docstring).
        # Top-k only demotes entries already below the kth threshold to
        # -1e30, so the pre-filter order still sorts the post-filter
        # array for the nucleus cumsum.
        sort_idx = jnp.argsort(-x, axis=-1)

        # Per-row top-k: the kth-largest value is the keep threshold;
        # rows with k <= 0 (disabled) skip the filter via the mask.
        srt = jnp.take_along_axis(x, sort_idx, axis=-1)
        kth = jnp.take_along_axis(srt, (jnp.clip(k, 1, V) - 1)[:, None],
                                  axis=-1)
        x = jnp.where((k[:, None] > 0) & (x < kth), -1e30, x)

        # Per-row nucleus: same construction as the scalar path with p
        # broadcast per row; p >= 1 rows keep everything exactly (no
        # reliance on cumsum rounding), p <= 0 rows degrade to top-1.
        sorted_logits = jnp.take_along_axis(x, sort_idx, axis=-1)
        probs = jax.nn.softmax(sorted_logits, axis=-1)
        mass_before = jnp.cumsum(probs, axis=-1) - probs
        keep_sorted = ((mass_before < p[:, None]) |
                       (p[:, None] >= 1.0)).at[:, 0].set(True)
        keep = jnp.zeros_like(keep_sorted).at[
            jnp.arange(B)[:, None], sort_idx].set(keep_sorted)
        return jnp.where(keep, x, -1e30)

    # A row filters only when it both samples (t > 0; greedy rows take
    # raw argmax and never read this output) and truncates (k > 0 or
    # p < 1; otherwise the filter is identity on the scaled logits).
    need = jnp.any((t > 0.0) & ((k > 0) | (p < 1.0)))
    return lax.cond(need, _full, lambda x: x, x)


def _sample_token_rows(logits_i, rng, *, temperature, top_k, top_p):
    """Vectorized per-row variant of _sample_token: every parameter is
    broadcast to (B,) and each row is filtered/sampled under its own
    settings (via _filter_logits_rows above). Rows with temperature == 0
    take argmax of the RAW logits (identical to the scalar greedy
    contract, and independent of the other rows' parameters). Branches
    become masks — one compiled shape serves every parameter mix, which
    is what bounds the serve engine's compile count."""
    import jax
    import jax.numpy as jnp

    B = logits_i.shape[0]
    t = jnp.broadcast_to(jnp.asarray(temperature, jnp.float32), (B,))
    greedy = jnp.argmax(logits_i, axis=-1).astype(jnp.int32)
    x = _filter_logits_rows(logits_i, temperature=temperature,
                            top_k=top_k, top_p=top_p)

    # jaxlint: disable=tracer-leak -- _is_key_batch reads dtype/ndim only (static)
    if _is_key_batch(rng):
        sampled = jax.vmap(jax.random.categorical)(rng, x).astype(jnp.int32)
    else:
        rng, sub = jax.random.split(rng)
        sampled = jax.random.categorical(sub, x).astype(jnp.int32)
    return jnp.where(t == 0.0, greedy, sampled), rng


def row_keys(seeds, positions):
    """(B,) typed PRNG keys, one per row: fold_in(key(seeds[b]),
    positions[b]). The serve engine's sampling-stream contract — the
    token destined for position q of a request seeded s is always drawn
    from fold_in(key(s), q), whether it comes from a prefill wave or a
    batched decode step — lives here so the two compiled paths can never
    drift apart."""
    import jax

    return jax.vmap(
        lambda s, q: jax.random.fold_in(jax.random.key(s), q)
    )(seeds, positions)


def _is_key_batch(rng) -> bool:
    """True when rng is a (B,) batch of typed PRNG keys (vs one key)."""
    import jax

    try:
        return (jax.dtypes.issubdtype(rng.dtype, jax.dtypes.prng_key)
                and rng.ndim == 1)
    except (AttributeError, TypeError):
        return False


def resolve_start(start: str) -> str:
    """nanoGPT's --start convention: 'FILE:<path>' reads the prompt from a
    file (verbatim, trailing newline included); anything else is the
    prompt text itself."""
    if start.startswith("FILE:"):
        with open(start[len("FILE:"):], "r", encoding="utf-8") as f:
            return f.read()
    return start


def generate(model, params, idx, max_new_tokens: int, *, temperature: float,
             top_k: int, rng, block_size: int, top_p: float = 1.0):
    """KV-cached decode: one prefill over the prompt, then a lax.scan whose
    step runs the model on a SINGLE token against per-layer (B, H, total, D)
    cache buffers (models/gpt.py cache path). Attention reads grow with the
    frontier instead of re-running block_size positions per token — the
    windowed fallback below re-forwards the full context every step, O(T)
    model FLOPs per token vs the cache's O(1).

    Falls back to the windowed path only when the requested total exceeds
    block_size (the learned wpe table defines no positions past it, so a
    sliding window is the only meaning 'longer than block_size' can have)."""
    import jax.numpy as jnp
    from jax import lax

    from nanosandbox_tpu.models.gpt import init_cache

    B, T0 = idx.shape
    total = T0 + max_new_tokens
    if max_new_tokens == 0:
        return idx
    if total > block_size:
        return _generate_windowed(model, params, idx, max_new_tokens,
                                  temperature=temperature, top_k=top_k,
                                  rng=rng, block_size=block_size, top_p=top_p)

    cache = init_cache(model.cfg, B, total)
    logits, cache = model.apply({"params": params}, idx, deterministic=True,
                                cache=cache, cache_index=0)
    nxt, rng = _sample_token(logits[:, -1, :], rng,
                             temperature=temperature, top_k=top_k, top_p=top_p)

    def step(carry, i):
        tok, cache, rng = carry
        logits, cache = model.apply({"params": params}, tok[:, None],
                                    deterministic=True,
                                    cache=cache, cache_index=i)
        nxt, rng = _sample_token(logits[:, 0, :], rng,
                                 temperature=temperature, top_k=top_k,
                                 top_p=top_p)
        return (nxt, cache, rng), tok

    (last, _, _), ys = lax.scan(step, (nxt, cache, rng),
                                jnp.arange(T0, total - 1))
    new_tokens = jnp.concatenate([ys.T, last[:, None]], axis=1) \
        if max_new_tokens > 1 else last[:, None]
    return jnp.concatenate([idx, new_tokens], axis=1)


def cast_params_for_serving(params, compute_dtype):
    """Inference-standard cast of float32 params to compute_dtype (bf16 on
    TPU): batch-~1 decode is weight-READ-bound — the whole parameter set
    streams from HBM per token — so halving the bytes halves per-token
    latency. No-op when compute_dtype is float32 (CPU configs)."""
    import jax
    import jax.numpy as jnp

    cdt = jnp.dtype(compute_dtype)
    return jax.tree.map(
        lambda a: a.astype(cdt) if a.dtype == jnp.float32 else a, params)


def _generate_windowed(model, params, idx, max_new_tokens: int, *,
                       temperature: float, top_k: int, rng, block_size: int,
                       top_p: float = 1.0):
    """Full-forward sliding-window decode (nanoGPT's crop-and-reforward
    semantics) — the only correct option once positions pass block_size."""
    import jax.numpy as jnp
    from jax import lax

    B, T0 = idx.shape
    total = max(T0 + max_new_tokens, block_size + 1)
    # Fixed-shape buffer so the whole decode is one compiled scan; causal
    # attention makes the zero-padding beyond the frontier harmless.
    buf = jnp.zeros((B, total), jnp.int32).at[:, :T0].set(idx)

    def step(carry, i):
        # i = position of the last known token; we sample position i+1.
        buf, rng = carry
        start = jnp.clip(i + 1 - block_size, 0, total - block_size)
        ctx = lax.dynamic_slice(buf, (0, start), (B, block_size))
        logits = model.apply({"params": params}, ctx, deterministic=True)
        pos_in_ctx = i - start
        logits_i = logits[jnp.arange(B), pos_in_ctx, :]
        nxt, rng = _sample_token(logits_i, rng,
                                 temperature=temperature, top_k=top_k,
                                 top_p=top_p)
        buf = buf.at[:, i + 1].set(nxt)
        return (buf, rng), None

    (buf, _), _ = lax.scan(step, (buf, rng),
                           jnp.arange(T0 - 1, T0 - 1 + max_new_tokens))
    return buf[:, :T0 + max_new_tokens]


def main(argv: list[str] | None = None) -> list[str]:
    import argparse

    ap = argparse.ArgumentParser()
    ap.add_argument("--out_dir", default="out")
    ap.add_argument("--data_dir", default="data")
    ap.add_argument("--dataset", default="shakespeare_char")
    ap.add_argument("--start", default="\n",
                    help="prompt text, or FILE:<path> to read it from a "
                         "file (nanoGPT convention)")
    ap.add_argument("--num_samples", type=int, default=1)
    ap.add_argument("--max_new_tokens", type=int, default=200)
    ap.add_argument("--temperature", type=float, default=0.8)
    ap.add_argument("--top_k", type=int, default=40)
    ap.add_argument("--top_p", type=float, default=1.0,
                    help="nucleus sampling mass (1.0 disables)")
    ap.add_argument("--seed", type=int, default=1337)
    ap.add_argument("--spec", default="off",
                    help="speculative decoding: 'ngram' (prompt-lookup "
                         "drafting, zero extra weights) or "
                         "'model:<out_dir>' (a smaller same-tokenizer "
                         "draft checkpoint); routes generation through "
                         "the serve engine's batched verify step — "
                         "greedy outputs identical, sampled outputs "
                         "identically distributed (per-sample seeds "
                         "seed+i instead of one shared stream)")
    ap.add_argument("--spec_k", type=int, default=4,
                    help="draft tokens per verify step (--spec only)")
    args = ap.parse_args(argv if argv is not None else sys.argv[1:])
    if args.num_samples < 1:
        # Validate BEFORE the checkpoint restore below: a bad flag should
        # fail in milliseconds, not after loading a model.
        ap.error(f"--num_samples must be >= 1, got {args.num_samples}")
    # Same fail-fast rule for --start=FILE:<path>: a typo'd path must not
    # cost the user a full model restore before erroring.
    start_text = resolve_start(args.start)

    import jax
    import jax.numpy as jnp
    import numpy as np

    from nanosandbox_tpu.data.loader import BinDataset
    from nanosandbox_tpu.data.tokenizer import get_tokenizer
    from nanosandbox_tpu.train import restore_for_inference
    from nanosandbox_tpu.utils.compile_cache import enable_compile_cache

    enable_compile_cache()
    trainer, state, _ = restore_for_inference(args.out_dir,
                                              data_dir=args.data_dir)
    cfg = trainer.cfg
    params = cast_params_for_serving(state["params"], cfg.compute_dtype)

    ds = BinDataset(args.data_dir, args.dataset)
    meta = ds.meta
    tok = get_tokenizer(meta.get("kind", "char"), meta)
    start_ids = tok.encode(start_text) or [0]

    if args.spec != "off":
        # Speculative path: generation runs through the serve engine's
        # batched verify step (serve/spec.py) — the drafter guesses k
        # tokens and one target forward scores them all. Bounded to the
        # cached-decode regime: the windowed fallback has no KV frontier
        # to verify against.
        from nanosandbox_tpu.serve import Engine
        from nanosandbox_tpu.serve.drafters import drafter_from_flag

        total = len(start_ids) + args.max_new_tokens
        if total > cfg.block_size:
            ap.error(f"--spec needs prompt + max_new_tokens <= block_size "
                     f"({total} > {cfg.block_size}); drop --spec to use "
                     "the windowed fallback")
        drafter = drafter_from_flag(args.spec, k=args.spec_k,
                                    data_dir=args.data_dir)
        engine = Engine(trainer.model, params,
                        num_slots=min(args.num_samples, 8),
                        max_len=cfg.block_size, spec=drafter)
        rids = [engine.submit(start_ids, args.max_new_tokens,
                              temperature=args.temperature,
                              top_k=args.top_k, top_p=args.top_p,
                              seed=args.seed + i)
                for i in range(args.num_samples)]
        res = {r.rid: r for r in engine.drain()}
        texts = []
        for rid in rids:
            text = tok.decode(list(res[rid].prompt) + res[rid].tokens)
            texts.append(text)
            print(text)
            print("---------------")
        s = engine.stats()
        print(f"[spec] drafter={s['spec']['drafter']} k={s['spec']['k']} "
              f"acceptance_rate={s['spec_acceptance_rate']} "
              f"accepted_len_mean={s['spec_accepted_len_mean']}",
              file=sys.stderr)
        return texts

    idx = jnp.asarray([start_ids] * args.num_samples, jnp.int32)
    rng = jax.random.key(args.seed)
    gen = jax.jit(partial(generate, trainer.model,
                          max_new_tokens=args.max_new_tokens,
                          temperature=args.temperature, top_k=args.top_k,
                          top_p=args.top_p, block_size=cfg.block_size))
    out = gen(params, idx, rng=rng)
    # ONE batched readback, then host-side decode: int() per element of
    # a live device array costs a device->host round trip PER TOKEN
    # (jaxlint host-sync caught this one).
    # jaxlint: disable=host-sync -- the single final readback of the samples
    out_host = np.asarray(out)
    texts = []
    for row in out_host:
        text = tok.decode([int(t) for t in row])
        texts.append(text)
        print(text)
        print("---------------")
    return texts


if __name__ == "__main__":
    main()
