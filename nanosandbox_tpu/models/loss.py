"""The head's losses, for every model family: mean next-token cross entropy
over full logits, and the head matmul fused into it chunk by chunk (alone, or
per shard under sequence parallelism). ``Trainer._loss_fn`` picks by
``loss_chunk_size`` and the mesh."""

from __future__ import annotations

import jax
import jax.numpy as jnp


def cross_entropy_loss(logits: jax.Array, targets: jax.Array,
                       ignore_index: int = -1) -> jax.Array:
    """Mean next-token cross entropy; positions == ignore_index are masked.

    Written in logsumexp form — nll = logsumexp(logits) - logits[target] —
    rather than log_softmax + gather: identical math (log_softmax is
    logits - logsumexp, the gather distributes), but the (B, T, vocab)
    log-probability tensor never materializes. At the 124M bench shape
    that tensor is 3.3 GB of f32 HBM writes+reads per step; the lse form
    reduces the head+CE fwd+bwd from ~38.6 to ~25.8 ms on v5e
    (benchmarks/r5/roofline_124m.json; measured July 2026 on an earlier
    tree, not re-measured)."""
    logits = logits.astype(jnp.float32)
    valid = targets != ignore_index
    safe_targets = jnp.where(valid, targets, 0)
    lse = jax.nn.logsumexp(logits, axis=-1)
    tgt = jnp.take_along_axis(logits, safe_targets[..., None], axis=-1)[..., 0]
    nll = jnp.where(valid, lse - tgt, 0.0)
    return nll.sum() / jnp.maximum(valid.sum(), 1)


def chunked_cross_entropy_loss(hidden: jax.Array, embedding: jax.Array,
                               targets: jax.Array, *, chunk_size: int = 128,
                               compute_dtype: str = "bfloat16",
                               ignore_index: int = -1) -> jax.Array:
    """Fused LM-head + cross entropy, scanned over sequence chunks.

    The full-logits path materializes a (B, T, vocab) float32 tensor —
    13 GB at batch 64 / 1024 ctx / 50304 vocab, the single largest HBM
    consumer of the whole train step and the reason batch size caps early.
    Here the weight-tied head matmul runs chunk-by-chunk inside a
    lax.scan whose body is jax.checkpoint'd: only (B, chunk, vocab) logits
    are ever alive, forward or backward (the backward recomputes the chunk
    matmul instead of saving it). The matmul feeds the MXU in
    ``compute_dtype`` with float32 accumulation, softmax math is float32.

    hidden: (B, T, C) from GPT(..., return_hidden=True); embedding: (V, C)
    (the tied wte table).

    Numerics note: the full-logits path (GPT.__call__ -> wte.attend) casts
    hidden to param_dtype (float32) before the head matmul; this path
    deliberately feeds the MXU in compute_dtype instead (bf16 inputs,
    f32 accumulation — the reference trains its head under torch autocast
    bf16 too). With compute_dtype=float32 the two paths agree to float
    rounding (tests/test_model.py pins this); under bf16 training they
    differ by bf16 input rounding, a worthwhile trade for the ~2x MXU rate
    and the 128x logits-memory saving.
    """
    tot, cnt = _chunked_nll_sums(hidden, embedding, targets,
                                 chunk_size=chunk_size,
                                 compute_dtype=compute_dtype,
                                 ignore_index=ignore_index)
    return tot / jnp.maximum(cnt, 1)


def _chunked_nll_sums(hidden, embedding, targets, *, chunk_size: int,
                      compute_dtype: str, ignore_index: int = -1):
    """(sum of nll, count of valid targets) via the chunked scan — the
    reduction core shared by the single-device mean above and the
    sequence-parallel psum variant below."""
    from jax import lax

    B, T, C = hidden.shape
    cs = min(chunk_size, T)
    while T % cs:
        cs -= 1  # largest divisor <= chunk_size; worst case 1
    n = T // cs
    dtype = jnp.dtype(compute_dtype)
    h = hidden.reshape(B, n, cs, C).transpose(1, 0, 2, 3)
    y = targets.reshape(B, n, cs).transpose(1, 0, 2)
    emb = embedding.astype(dtype)

    @jax.checkpoint
    def body(carry, xy):
        h_c, y_c = xy
        logits = lax.dot_general(
            h_c.astype(dtype), emb,
            (((2,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)  # (B, cs, V)
        valid = y_c != ignore_index
        safe = jnp.where(valid, y_c, 0)
        # logsumexp form, same as cross_entropy_loss: the (B, cs, V)
        # log-prob tensor never materializes (here it would also be
        # recomputed by the checkpoint during backward, doubling the
        # waste).
        lse = jax.nn.logsumexp(logits, axis=-1)
        tgt = jnp.take_along_axis(logits, safe[..., None], axis=-1)[..., 0]
        nll = lse - tgt
        tot, cnt = carry
        return (tot + jnp.where(valid, nll, 0.0).sum()[None],
                cnt + valid.sum()[None]), None

    # Shape-(1,) carries, not scalars: under the sequence-parallel
    # shard_map wrapper below, jax 0.4.x cannot transpose a scan whose
    # residuals are rank-0 (the scalar-residual promotion that fixes
    # this landed after 0.4.37, _SpecError from grad-of-shard_map), and
    # a trailing squeeze is free either way.
    (tot, cnt), _ = lax.scan(
        body, (jnp.zeros((1,), jnp.float32), jnp.zeros((1,), jnp.int32)),
        (h, y))
    return tot[0], cnt[0]


def sharded_chunked_cross_entropy_loss(hidden: jax.Array,
                                       embedding: jax.Array,
                                       targets: jax.Array, *, mesh,
                                       chunk_size: int = 128,
                                       compute_dtype: str = "bfloat16",
                                       ignore_index: int = -1) -> jax.Array:
    """Chunked loss under sequence parallelism (attention_impl='ring').

    A plain lax.scan over a T-sharded hidden would make the partitioner
    gather the full sequence onto every device; and the full-logits
    fallback materializes (B, T, vocab) f32 — 1.6 GB per sequence at
    8k/50304, defeating ring attention's whole memory story. Instead
    each device runs the chunked scan over its LOCAL T shard inside
    shard_map (only (B, T_local/chunks, vocab) logits alive anywhere)
    and the scalar (nll_sum, count) pairs psum across the batch- and
    sequence-sharding axes.
    """
    from jax import lax
    from jax.sharding import PartitionSpec as P

    hspec = P(("data", "fsdp"), "seq", None)
    yspec = P(("data", "fsdp"), "seq")

    def body(h, emb, y):
        tot, cnt = _chunked_nll_sums(h, emb, y, chunk_size=chunk_size,
                                     compute_dtype=compute_dtype,
                                     ignore_index=ignore_index)
        tot = lax.psum(tot, ("data", "fsdp", "seq"))
        cnt = lax.psum(cnt, ("data", "fsdp", "seq"))
        return tot / jnp.maximum(cnt, 1)

    from nanosandbox_tpu.parallel.mesh import shard_map

    fn = shard_map(body, mesh=mesh,
                   in_specs=(hspec, P(None, None), yspec),
                   out_specs=P(), check_vma=False)
    return fn(hidden, embedding, targets)
