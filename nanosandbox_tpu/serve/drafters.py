"""Draft-token proposers for speculative decoding (serve/spec.py).

A drafter's only job is to guess the next ``k`` tokens of a request
cheaply; the verify step (one batched target-model forward over the
k+1-token block) then accepts the longest correct prefix and samples
one more token, so a WRONG draft costs nothing but the wasted draft
work — outputs are provably distributed exactly as non-speculative
decoding (greedy drafts are point-mass proposals, for which the
Leviathan et al. rejection rule reduces to: accept token d with
probability p(d), else resample from p with d's mass removed).

Two backends, one protocol:

  * ``NGramDrafter`` (kind='host') — prompt-lookup drafting (the
    tokenizer-free scheme HF assisted generation popularized): the
    request's own context (prompt + generated tokens) is scanned for
    the most recent earlier occurrence of its trailing n-gram and the
    tokens that followed it are proposed. Zero extra weights, zero
    device programs, CPU-testable; shines on repetitive/extractive
    workloads (code, structured text, summarization-with-quoting).

  * ``ModelDrafter`` (kind='device') — a smaller GPT sharing the
    target's tokenizer, run greedily for k steps against its OWN
    slot-pool KV cache (same fixed-shape discipline as the engine:
    one compiled draft program, drafter prefills bounded by the same
    admit-ladder x bucket grid). The drafter's frontier needs no
    separate bookkeeping: it consumes the engine's device-resident
    (pos, tok, active) state, so verification rollback is simply the
    engine not advancing pos past the accepted prefix.

The host-side protocol is deliberately tiny (``kind``, ``k``, and
``propose(context, max_tokens)`` for host drafters) so tests can plug
in adversarial drafters (e.g. always-wrong proposals pin the
full-reject rollback path).
"""

from __future__ import annotations

from typing import List, Optional, Sequence


class NGramDrafter:
    """Prompt-lookup drafting: propose the continuation of the most
    recent earlier occurrence of the context's trailing n-gram.

    ``max_ngram`` is the longest suffix tried (longest first — a longer
    match is stronger evidence the continuation repeats); matching
    prefers the MOST RECENT earlier occurrence (locality: loops and
    boilerplate repeat at short range). A match at distance d from the
    context end supplies only d literal continuation tokens; the
    proposal is extended to the full budget by CYCLING those d tokens
    (exact for text of period d, e.g. a degenerate greedy loop — and a
    wrong guess costs nothing: the verify block is the same fixed shape
    whether a draft slot holds a hot guess or filler, acceptance just
    stops at the first miss). Always returns the full budget when any
    match exists; returns [] on no match — the engine then verifies
    that row with draft length 0, which degrades to exactly one
    ordinary decode step, so mixed hit/miss batches never stall.
    """

    kind = "host"

    def __init__(self, k: int = 4, max_ngram: int = 3):
        if k < 1:
            raise ValueError(f"k must be >= 1, got {k}")
        if max_ngram < 1:
            raise ValueError(f"max_ngram must be >= 1, got {max_ngram}")
        self.k = int(k)
        self.max_ngram = int(max_ngram)

    def propose(self, context: Sequence[int],
                max_tokens: Optional[int] = None) -> List[int]:
        cap = self.k if max_tokens is None else min(self.k, max_tokens)
        n_ctx = len(context)
        if cap <= 0 or n_ctx < 2:
            return []
        context = list(context)
        for n in range(min(self.max_ngram, n_ctx - 1), 0, -1):
            suffix = context[n_ctx - n:]
            # Most recent earlier occurrence: scan right-to-left.
            for start in range(n_ctx - n - 1, -1, -1):
                if context[start:start + n] == suffix:
                    m = start + n          # continuation begins here
                    d = n_ctx - m          # literal tokens before the end
                    return [context[m + i % d] for i in range(cap)]
        return []


class ModelDrafter:
    """A small GPT (same vocabulary) drafting k tokens greedily against
    its own slot-pool KV cache.

    Construction takes only (model, params, k); the engine calls
    ``build(...)`` with its slot geometry and trace registry, which
    allocates the drafter pool and compiles the two drafter programs:

      * ``draft``         — ONE program: a lax.scan of k+1 single-token
                            greedy steps over all slots at the engine's
                            per-row frontiers, proposing the first k
                            (the extra step only writes the k-th
                            draft's K/V — see _draft_fn; consumes the
                            engine's pos/tok/active state — see module
                            docstring).
      * ``draft_prefill`` — one program per (admit rung, bucket) pair,
                            the same closed grid as the engine's own
                            prefill: the drafter must ingest every
                            admitted prompt into its pool.
    """

    kind = "device"

    def __init__(self, model, params, k: int = 4):
        if k < 1:
            raise ValueError(f"k must be >= 1, got {k}")
        self.model = model
        self.params = params
        self.k = int(k)
        self._pool = None
        self._draft = None
        self._prefill = None
        self.num_slots = None
        self.max_len = None
        self.paged = False

    # -- engine-driven lifecycle ------------------------------------------

    def build(self, *, target_cfg, num_slots: int, max_len: int,
              n_prefill_programs: int, registry, on_accel: bool,
              kv_dtype=None, decode_impl=None, paged: bool = False,
              kv_page_size: int = 0, kv_pool_blocks: int = 0) -> dict:
        """Allocate the drafter pool + compile draft/prefill under the
        engine's trace registry; returns the program budget entries to
        merge into Engine.max_programs(). kv_dtype mirrors the engine's
        pool mode onto the drafter's own pool ('int8' halves it too);
        decode_impl (the ENGINE's setting) overrides the drafter
        model's own ladder rung, so an operator pinning the engine off
        a broken kernel pins the drafter's draft steps with it.

        ``paged`` mirrors the engine's block-paged layout: the drafter
        pool becomes a parallel (kv_pool_blocks, H, page, D) heap
        indexed by the ENGINE's block table — block lifecycle (alloc,
        prefix sharing, eviction) is decided once, by the engine's
        BlockPool, and both pools follow the same ids, which is also
        why a prefix-cache hit skips the DRAFTER's prefill chunks for
        free (its blocks for those ids still hold that prefix's K/V)."""
        import jax

        from nanosandbox_tpu.serve.engine import single_device_params

        # Like the tp == 1 engine it drafts for, the drafter owns no
        # mesh: neither in its weights' type nor bound onto its model.
        self.params = single_device_params(self.params)
        self.model = type(self.model)(cfg=self.model.cfg.replace(
            decode_impl=decode_impl or self.model.cfg.decode_impl))

        from nanosandbox_tpu.models.gpt import init_cache, init_paged_cache

        dcfg = self.model.cfg
        if dcfg.vocab_size != target_cfg.vocab_size:
            raise ValueError(
                f"drafter vocab_size {dcfg.vocab_size} != target "
                f"vocab_size {target_cfg.vocab_size}: speculative drafts "
                "are token ids, so the models must share one tokenizer")
        if dcfg.block_size < max_len:
            raise ValueError(
                f"drafter block_size {dcfg.block_size} < engine max_len "
                f"{max_len}: the drafter must hold every slot frontier "
                "the target can reach")
        self.num_slots = num_slots
        self.max_len = max_len
        self.paged = bool(paged)
        if self.paged:
            self._pool = init_paged_cache(dcfg, kv_pool_blocks,
                                          kv_page_size, kv_dtype=kv_dtype)
        else:
            self._pool = init_cache(dcfg, num_slots, max_len,
                                    kv_dtype=kv_dtype)
        budget = {"draft": 1, "draft_prefill": n_prefill_programs}
        draft_body = self._draft_paged_fn if self.paged else self._draft_fn
        prefill_body = (self._prefill_paged_fn if self.paged
                        else self._prefill_fn)
        self._draft = jax.jit(
            registry.guard("draft", budget["draft"])(draft_body),
            donate_argnums=(1,) if on_accel else ())
        self._prefill = jax.jit(
            registry.guard("draft_prefill",
                           budget["draft_prefill"])(prefill_body),
            donate_argnums=(1,) if on_accel else ())
        return budget

    def prefill_wave(self, prompts, meta) -> None:
        """Ingest an admission wave's (k_wave, L_bucket) prompts into the
        drafter pool at the wave's slot rows — called by the engine right
        after its own wave prefill, with the SAME staged device arrays
        (the engine's packed ``meta`` layout; ladder-padding rows carry
        the out-of-range slot id / sentinel table row and drop). Under
        the paged engine ``prompts`` is the SUFFIX block, written
        straight into the drafter pool through the shared block table —
        a prefix-cache hit skips the drafter's prefill chunks too."""
        self._pool = self._prefill(self.params, self._pool, prompts, meta)

    def draft(self, tok, pos, active, table=None):
        """(S, k) greedy draft tokens for every slot at the engine's
        frontiers; rewrites the drafter cache rows pos..pos+k-1 (via the
        engine's block table when paged)."""
        if self.paged:
            self._pool, drafts = self._draft(self.params, self._pool, tok,
                                             pos, active, table)
        else:
            self._pool, drafts = self._draft(self.params, self._pool, tok,
                                             pos, active)
        return drafts

    def shardcheck_programs(self, mesh, *, buckets=(), rungs=(),
                            suffix: str = "") -> list:
        """ProgramSpecs for the drafter's compiled set (draft scan +
        the draft_prefill grid) under the engine's replicated-on-mesh
        contract — see Engine.shardcheck_programs. Requires build()."""
        import jax
        import jax.numpy as jnp
        from jax.sharding import NamedSharding, PartitionSpec

        from nanosandbox_tpu.analysis.shardcheck import (Expectations,
                                                         ProgramSpec)
        from nanosandbox_tpu.parallel.mesh import replicated_abstract

        if self._pool is None:
            raise RuntimeError("shardcheck_programs requires build() — "
                               "construct the Engine with this drafter "
                               "first")
        rep = NamedSharding(mesh, PartitionSpec())
        aparams = replicated_abstract(mesh, self.params)
        apool = replicated_abstract(mesh, self._pool)
        expect = Expectations(comms_free=True)

        def sds(shape, dtype):
            return jax.ShapeDtypeStruct(shape, dtype, sharding=rep)

        def jit_rep(fn):
            return jax.jit(fn, in_shardings=rep, out_shardings=rep)

        S = self.num_slots
        nb = (-(-self.max_len // self._pool[0][0].shape[2])
              if self.paged else 0)
        if self.paged:
            args = (aparams, apool, sds((S,), jnp.int32),
                    sds((S,), jnp.int32), sds((S,), jnp.bool_),
                    sds((S, nb), jnp.int32))
            draft_body = self._draft_paged_fn
        else:
            args = (aparams, apool, sds((S,), jnp.int32),
                    sds((S,), jnp.int32), sds((S,), jnp.bool_))
            draft_body = self._draft_fn
        specs = [ProgramSpec(
            name=f"drafter_draft{suffix}",
            lower=lambda: jit_rep(draft_body).lower(*args),
            abstract_args=args, expect=expect, tags=("serve", "drafter"))]
        meta_w = (nb + 5) if self.paged else 4
        for bucket in buckets:
            for k in rungs:
                prefill_body = (self._prefill_paged_fn if self.paged
                                else self._prefill_fn)
                pargs = (aparams, apool, sds((k, bucket), jnp.int32),
                         sds((k, meta_w), jnp.int32))
                specs.append(ProgramSpec(
                    name=f"drafter_prefill{suffix}_k{k}_L{bucket}",
                    lower=(lambda pargs=pargs, prefill_body=prefill_body:
                           jit_rep(prefill_body).lower(*pargs)),
                    abstract_args=pargs, expect=expect,
                    tags=("serve", "drafter")))
        return specs

    # -- compiled bodies ---------------------------------------------------

    def _prefill_fn(self, dparams, dpool, prompts, meta):
        """Same shape discipline as Engine._prefill_fn, minus sampling:
        the drafter only needs the prompt K/V in its pool (the first
        generated token reaches it through the engine's tok state).
        ``meta`` is the engine's packed dense staging row ([slot |
        true_len | top_k | seed]); only the slot column matters here."""
        from nanosandbox_tpu.models.gpt import init_cache, scatter_cache_rows

        kk, L = prompts.shape
        cache = init_cache(self.model.cfg, kk, L)
        _, cache = self.model.apply({"params": dparams}, prompts,
                                    deterministic=True, cache=cache,
                                    cache_index=0)
        return scatter_cache_rows(dpool, cache, meta[:, 0])

    def _prefill_paged_fn(self, dparams, dpool, suffix, meta):
        """Engine._prefill_paged_fn minus the sampling: forward the
        SUFFIX at per-row cache_index = hit length, its K/V written
        straight into the drafter pool through the shared block table
        (the resident prefix's drafter K/V rides the same refcounted
        blocks, so a hit skips the DRAFTER's prefill chunks too).
        Shared hit blocks stay read-only in the drafter pool as well —
        the write range starts at the block-aligned hit boundary. meta
        is the engine's packed paged staging row ([table (nb) | slot |
        true_len | top_k | seed | hit_len])."""
        nb = -(-self.max_len // self._pool[0][0].shape[2])
        _, dpool = self.model.apply({"params": dparams}, suffix,
                                    deterministic=True, cache=dpool,
                                    cache_index=meta[:, nb + 4],
                                    block_table=meta[:, :nb])
        return dpool

    def _draft_paged_fn(self, dparams, dpool, tok, pos, active, table):
        """The k+1-step draft scan over the block-paged drafter pool:
        identical control flow to _draft_fn, with every cached read and
        write paged through the engine's block table."""
        import jax.numpy as jnp
        from jax import lax

        def step(carry, _):
            tok, pos, pool = carry
            logits, pool = self.model.apply({"params": dparams},
                                            tok[:, None],
                                            deterministic=True, cache=pool,
                                            cache_index=pos,
                                            block_table=table)
            nxt = jnp.argmax(logits[:, 0, :], axis=-1).astype(jnp.int32)
            nxt = jnp.where(active, nxt, tok)
            pos = pos + active.astype(jnp.int32)
            return (nxt, pos, pool), nxt

        (_, _, dpool), drafts = lax.scan(step, (tok, pos, dpool), None,
                                         length=self.k + 1)
        return dpool, drafts[:self.k].T  # (k+1, S) -> (S, k)

    def _draft_fn(self, dparams, dpool, tok, pos, active):
        """k+1 greedy single-token steps over all slots, proposing the
        first k predictions. Inactive rows are parked (pos frozen, token
        pinned) exactly like the engine's decode step, so a released
        slot's garbage stays in its own row. The extra step exists for
        the CACHE, not the proposal: it feeds the k-th draft so its K/V
        lands at column pos+k. When the verify accepts all k drafts the
        engine's frontier jumps to pos+k+1 and the next draft call
        queries across that column — without this write it would stay
        stale garbage for the rest of the request (never overwritten:
        later writes all land past it), silently degrading every
        subsequent draft for the slot. Partial accepts don't need it
        (the next call's writes cover the rejected tail before any
        query attends there), but the full accept is the drafter's
        TARGET regime."""
        import jax.numpy as jnp
        from jax import lax

        def step(carry, _):
            tok, pos, pool = carry
            logits, pool = self.model.apply({"params": dparams},
                                            tok[:, None],
                                            deterministic=True, cache=pool,
                                            cache_index=pos)
            nxt = jnp.argmax(logits[:, 0, :], axis=-1).astype(jnp.int32)
            nxt = jnp.where(active, nxt, tok)
            pos = pos + active.astype(jnp.int32)
            return (nxt, pos, pool), nxt

        (_, _, dpool), drafts = lax.scan(step, (tok, pos, dpool), None,
                                         length=self.k + 1)
        return dpool, drafts[:self.k].T  # (k+1, S) -> (S, k)


def drafter_from_flag(spec: str, *, k: int = 4, data_dir: str = "data"):
    """CLI plumbing shared by sample.py / serve __main__ / bench.py:
    'ngram' -> NGramDrafter, 'model:<out_dir>' -> ModelDrafter restored
    from that checkpoint directory (params cast to its serving dtype).
    'off'/'' -> None."""
    if spec in ("", "off", "none"):
        return None
    if spec == "ngram":
        return NGramDrafter(k=k)
    if spec.startswith("model:"):
        from nanosandbox_tpu.sample import cast_params_for_serving
        from nanosandbox_tpu.train import restore_for_inference

        out_dir = spec[len("model:"):]
        if not out_dir:
            raise ValueError("--spec=model:<out_dir> needs a checkpoint dir")
        trainer, state, _ = restore_for_inference(out_dir, data_dir=data_dir)
        dparams = cast_params_for_serving(state["params"],
                                          trainer.cfg.compute_dtype)
        return ModelDrafter(trainer.model, dparams, k=k)
    raise ValueError(
        f"unknown --spec value {spec!r} (expected off, ngram, or "
        "model:<out_dir>)")
