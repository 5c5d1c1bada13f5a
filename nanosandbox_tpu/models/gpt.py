"""Decoder-only GPT in flax.linen, bf16-MXU-first.

Reimplements the model contract the reference exercises from karpathy/nanoGPT
(/root/reference/notebooks/colab_nanoGPT_companion.ipynb:71-78, 108-115 and
SURVEY.md §2.3 #25): a decoder-only transformer configurable by
``n_layer / n_head / n_embd / block_size / dropout`` with learned positional
embeddings, pre-LayerNorm blocks, GELU MLP (4x), optional biases, weight
tying between the token embedding and the LM head, and GPT-2 initialization
(normal 0.02, residual projections scaled by 1/sqrt(2*n_layer)).

TPU-first choices: parameters kept in float32, matmuls run in bfloat16
(MXU-native) with float32 softmax/layernorm numerics; attention dispatches to
the Pallas flash kernel on TPU (ops/attention.py); optional per-block
jax.checkpoint (rematerialization) to trade FLOPs for HBM.
"""

from __future__ import annotations

from typing import Any, Optional

import flax.linen as nn
import jax
import jax.numpy as jnp

from nanosandbox_tpu.config import GPTConfig
# count_params and cross_entropy_loss stay importable from here: bench.py
# and chipbench/ name them so.
from nanosandbox_tpu.models.common import (  # noqa: F401
    _dense_init, constrain_acts, count_params, remat_block)
from nanosandbox_tpu.models.loss import cross_entropy_loss  # noqa: F401
from nanosandbox_tpu.ops.attention import (attention_layout,
                                           causal_attention,
                                           causal_attention_qkv)


def _layer_norm(cfg: GPTConfig, name: str) -> nn.LayerNorm:
    """LayerNorm in f32 with epsilon=1e-5 — torch.nn.LayerNorm's default
    (nanoGPT/HF GPT-2), not flax's 1e-6; pretrained-weight import
    (models/convert.py) relies on the match."""
    return nn.LayerNorm(use_bias=cfg.bias, dtype=jnp.float32, epsilon=1e-5,
                        param_dtype=cfg.param_dtype, name=name)


def attn_layout(cfg: GPTConfig, mesh: Any, T: int) -> str:
    """'btc' or 'bhtd': the HBM interface the no-cache attention of this
    model takes at sequence length T (ops/attention.attention_layout).
    CausalSelfAttention asks it while tracing; the Trainer asks it for
    its block_size and records the answer on ``trainer_init``. 'ring'
    resolves to no Pallas impl of its own, so it keeps (B, H, T, D)."""
    return attention_layout(
        cfg.n_head, cfg.n_embd // cfg.n_head, T, impl=cfg.attention_impl,
        mesh=mesh)


class CausalSelfAttention(nn.Module):
    cfg: GPTConfig
    mesh: Any = None  # required for attention_impl='ring' (sequence parallel)

    @nn.compact
    def __call__(self, x: jax.Array, deterministic: bool,
                 cache: Optional[tuple] = None, cache_index=None,
                 block_table=None):
        cfg = self.cfg
        B, T, C = x.shape
        assert C % cfg.n_head == 0
        head_dim = C // cfg.n_head
        dtype = jnp.dtype(cfg.compute_dtype)

        qkv = nn.Dense(3 * C, use_bias=cfg.bias, dtype=dtype,
                       param_dtype=cfg.param_dtype,
                       kernel_init=_dense_init(), name="c_attn")(x)
        # Training / eval on one device with the Pallas kernels: they read
        # qkv and write c_proj's input where they lie (attn_layout). Every
        # other path — the cache branches, ring, a mesh, XLA, head counts
        # that do not tile 128 lanes — works on (B, H, T, D) and pays the
        # transposes there.
        layout = "bhtd" if cache is not None else attn_layout(
            cfg, self.mesh, T)
        if layout == "bhtd":
            q, k, v = jnp.split(qkv, 3, axis=-1)
            # (B, T, C) -> (B, H, T, D)
            q = q.reshape(B, T, cfg.n_head, head_dim).transpose(0, 2, 1, 3)
            k = k.reshape(B, T, cfg.n_head, head_dim).transpose(0, 2, 1, 3)
            v = v.reshape(B, T, cfg.n_head, head_dim).transpose(0, 2, 1, 3)

        new_cache = None
        if layout == "btc":
            attn_rng = None
            if cfg.dropout > 0.0 and not deterministic:
                attn_rng = self.make_rng("dropout")
            y = causal_attention_qkv(
                qkv, cfg.n_head, impl=cfg.attention_impl,
                dropout_rate=0.0 if deterministic else cfg.dropout,
                dropout_rng=attn_rng)
        elif cache is not None:
            # Incremental decode: write this call's K/V into the cache
            # buffer at cache_index and attend q against the buffer.
            # The T=1 per-row hot path dispatches to the fused flash-
            # decode Pallas kernel (ops/flash_decode.py) when the config
            # selects it; everything else (T = k+1 verify blocks, scalar-
            # index prefill, the XLA fallback) runs the masked-score
            # path below. Unwritten buffer tail is masked off by
            # position (kpos > qpos), so the zeros never contribute.
            # Falls through to the SHARED c_proj below — the projection
            # must be declared exactly once so decode can never desync
            # from the trained parameter's definition.
            if not deterministic and cfg.dropout > 0.0:
                raise ValueError("cached decode is inference-only; "
                                 "call with deterministic=True")
            from jax import lax

            from nanosandbox_tpu.ops.flash_decode import (
                flash_decode, flash_decode_paged, flash_prefill_paged,
                quantize_kv_rows, quantize_kv_rows_int4,
                resolve_decode_impl, unpack_int4,
                xla_decode_attention_paged)

            # int8/int4 KV mode (init_cache kv_dtype=): the layer cache
            # is (K, V, k_scale f32, v_scale f32) with one scale per
            # (row, head, position) — quantize-on-write, so quantized
            # K/V is the only representation the pool holds. int4 packs
            # two nibbles per byte along head_dim (uint8 storage, the
            # dtype that distinguishes the two modes).
            # Tensor-parallel serving (mesh with model > 1): heads are
            # sharded over the ``model`` axis — column-parallel c_attn
            # lands q/k/v pre-sharded by head, the KV pool (and its
            # per-position scales) lives row-sharded along its heads
            # dim, and attention is embarrassingly parallel across
            # heads. The constraints below are ANCHORS threaded through
            # every cached path (decode, prefill, scan body, spec
            # verify): each one is free when the sharding already
            # matches, and dropping any of them is exactly how GSPMD
            # quietly rebuilds the whole pool on every chip — the
            # full-pool all-gather the shardcheck ``frontier_slice``
            # fixture pins against the bounded exchange. The TP serve
            # budget (budgets/serve_tp_cpu8.json) CI-fails if that ever
            # happens.
            tp_mesh = (self.mesh if self.mesh is not None
                       and self.mesh.shape.get("model", 1) > 1 else None)

            def _tp(x, *spec):
                if tp_mesh is None or x is None:
                    return x
                from jax.sharding import NamedSharding, PartitionSpec

                return jax.lax.with_sharding_constraint(
                    x, NamedSharding(tp_mesh, PartitionSpec(*spec)))

            q = _tp(q, None, "model", None, None)
            k = _tp(k, None, "model", None, None)
            v = _tp(v, None, "model", None, None)

            quantized = len(cache) == 4
            four_bit = quantized and cache[0].dtype == jnp.uint8
            _quantize = quantize_kv_rows_int4 if four_bit \
                else quantize_kv_rows
            if quantized:
                ck, cv, cks, cvs = cache
            else:
                ck, cv = cache
                cks = cvs = None
            if quantized and block_table is None:
                k_w, ks_w = _quantize(k)         # (B, H, T, D')->(B,H,T)
                v_w, vs_w = _quantize(v)
            elif not quantized:
                k_w, v_w = k.astype(ck.dtype), v.astype(cv.dtype)
            Tc = ck.shape[2]
            per_row = getattr(cache_index, "ndim", 0) == 1
            if block_table is not None:
                # Block-paged pool (init_paged_cache): the layer holds
                # GLOBAL (num_blocks, H, page, D) blocks and block_table
                # maps each row's i-th logical chunk to a pool block.
                # Write: position p of row b lands in pool block
                # table[b, p // page] at offset p % page — one flat
                # scatter over the (B*T) written positions, with the
                # engine's unallocated sentinel (>= num_blocks) dropped
                # so a parked/overrun row can never corrupt a block it
                # does not own. Read: the T=1 hot path pages the flash
                # kernel through the table (flash_decode_paged, same
                # fused int8 dequant); everything else gathers the
                # row's chain into contiguous (B, H, max_len, D) rows
                # and falls through to the shared masked-score path —
                # bit-identical math, the gather is the byte cost the
                # kernel exists to avoid.
                if not per_row:
                    raise ValueError(
                        "a paged cache is per-row by construction: "
                        "cache_index must be a (B,) frontier vector")
                n_blk, _, page, _ = ck.shape
                nb = block_table.shape[1]
                qpos = cache_index[:, None] + jnp.arange(T)[None, :]
                jblk = qpos // page
                blk = jnp.take_along_axis(block_table,
                                          jnp.minimum(jblk, nb - 1), axis=1)
                blk = jnp.where(jblk < nb, blk, n_blk)       # drop overruns
                bf, of = blk.reshape(-1), (qpos % page).reshape(-1)
                if quantized:
                    # Quantize AFTER the drop mask is known: positions
                    # destined for the sentinel block (ladder-padding
                    # rows, parked tables, frontier overruns) skip the
                    # amax/divide/round scale chain outright — that
                    # work fed a write the scatter drops on the floor
                    # anyway, a measurable lane-waste on every prefill
                    # wave.
                    w_valid = (blk < n_blk)[:, None, :]       # (B, 1, T)
                    k_w, ks_w = _quantize(k, valid=w_valid)
                    v_w, vs_w = _quantize(v, valid=w_valid)

                def _scatter_vals(buf, x):
                    vals = x.transpose(0, 2, 1, 3).reshape(
                        B * T, cfg.n_head, x.shape[-1])
                    return buf.at[bf, :, of, :].set(vals, mode="drop")

                ck = _scatter_vals(ck, k_w)
                cv = _scatter_vals(cv, v_w)
                if quantized:

                    def _scatter_scale(buf, s):
                        vals = s.transpose(0, 2, 1).reshape(B * T,
                                                            cfg.n_head)
                        return buf.at[bf, :, of].set(vals, mode="drop")

                    cks = _scatter_scale(cks, ks_w)
                    cvs = _scatter_scale(cvs, vs_w)
                Tc = nb * page
            elif per_row:
                # Per-row frontiers (serve engine's slot pool): each batch
                # row b writes its K/V at its OWN position cache_index[b]
                # and attends up to it. vmap over the batch dim turns the
                # single write into one write per row — the shapes stay
                # fixed, so one compiled decode step serves every mix of
                # in-flight request lengths.
                if T == 1:
                    # Decode hot path: a 1-column dynamic_update_slice per
                    # row, unchanged from the pre-speculative engine.
                    def _row_write(buf, x, i):
                        return lax.dynamic_update_slice(buf, x, (0, i, 0))

                    def _row_write_scale(buf, x, i):
                        return lax.dynamic_update_slice(buf, x, (0, i))
                else:
                    # Speculative-verify path: a fixed (T = k+1)-column
                    # block per row. Scatter with mode='drop', NOT
                    # dynamic_update_slice — for a row whose frontier sits
                    # within T of the buffer end, the slice CLAMP would
                    # shift the whole write backwards and overwrite valid
                    # history; drop discards only the out-of-range
                    # columns (masked off by position anyway).
                    def _row_write(buf, x, i):
                        cols = i + jnp.arange(T)
                        return buf.at[:, cols, :].set(x, mode="drop")

                    def _row_write_scale(buf, x, i):
                        cols = i + jnp.arange(T)
                        return buf.at[:, cols].set(x, mode="drop")
                ck = jax.vmap(_row_write)(ck, k_w, cache_index)
                cv = jax.vmap(_row_write)(cv, v_w, cache_index)
                if quantized:
                    cks = jax.vmap(_row_write_scale)(cks, ks_w, cache_index)
                    cvs = jax.vmap(_row_write_scale)(cvs, vs_w, cache_index)
                qpos = cache_index[:, None] + jnp.arange(T)[None, :]  # (B, T)
            else:
                ck = lax.dynamic_update_slice(ck, k_w, (0, 0, cache_index, 0))
                cv = lax.dynamic_update_slice(cv, v_w, (0, 0, cache_index, 0))
                if quantized:
                    cks = lax.dynamic_update_slice(cks, ks_w,
                                                   (0, 0, cache_index))
                    cvs = lax.dynamic_update_slice(cvs, vs_w,
                                                   (0, 0, cache_index))
                qpos = (cache_index + jnp.arange(T))[None, :]  # (1, T) global
            # Re-anchor the UPDATED pool layers: paged (N, H, page, D)
            # and dense (B, H, L, D) both carry heads at dim 1 (scales
            # drop the trailing D). Without this the jit's output
            # sharding is whatever the partitioner inferred — one
            # inference change away from returning the pool replicated,
            # i.e. all-gathering it every step.
            ck = _tp(ck, None, "model", None, None)
            cv = _tp(cv, None, "model", None, None)
            if quantized:
                cks = _tp(cks, None, "model", None)
                cvs = _tp(cvs, None, "model", None)
            decode_impl = resolve_decode_impl(
                getattr(cfg, "decode_impl", "auto"))

            sm_scale = 1.0 / head_dim ** 0.5
            interpret = decode_impl == "pallas_interpret"
            from jax.sharding import PartitionSpec as _P
            HP = _P(None, "model", None)         # q (B,H,D) / (.,H,.) scales
            PL = _P(None, "model", None, None)   # pool layers / (B,H,T,D) q

            def _heads_shard(fn, out_spec, args, in_specs):
                """Run a flash kernel per-shard over LOCAL heads under
                tensor parallelism: GSPMD cannot partition Mosaic custom
                calls, so under a model > 1 mesh the kernel body runs
                inside shard_map with the heads dim split over ``model``
                — the grid already iterates (B*H) rows, so each shard
                simply sees H_local rows and the kernel body is
                unchanged. Single-chip engines call the kernel direct."""
                if tp_mesh is None:
                    return fn(*args)
                from nanosandbox_tpu.parallel.mesh import shard_map

                return shard_map(fn, mesh=tp_mesh, in_specs=in_specs,
                                 out_specs=out_spec, check_vma=False)(*args)

            def _kernel(fn_kw, base, base_specs, out_spec):
                """One flash-kernel dispatch, TP-aware. Quantized pools
                append the scale planes as positional shard_map operands
                (a spec cannot describe a None leaf); fp pools call with
                the kernels' default None scales."""
                if quantized:
                    return _heads_shard(
                        lambda *a: fn_kw(*a[:-2], k_scale=a[-2],
                                         v_scale=a[-1]),
                        out_spec, base + (cks, cvs),
                        base_specs + (HP, HP))
                return _heads_shard(fn_kw, out_spec, base, base_specs)

            if per_row and T == 1 and decode_impl != "xla":
                # Fused single-query flash decode: one pass over each
                # row's K/V blocks up to its own frontier, int8 dequant
                # folded into scores/probs so quantized K/V never
                # materializes in fp (ops/flash_decode.py). A paged pool
                # routes the block-table variant: the same walk, with
                # each chunk's address an indirection through the table.
                if block_table is not None:
                    y = _kernel(
                        lambda *a, **kw: flash_decode_paged(
                            *a, sm_scale=sm_scale, interpret=interpret,
                            **kw),
                        (q[:, :, 0, :], ck, cv, block_table,
                         cache_index + 1),
                        (HP, PL, PL, _P(None, None), _P(None)),
                        HP)[:, :, None, :]
                else:
                    y = _kernel(
                        lambda *a, **kw: flash_decode(
                            *a, sm_scale=sm_scale, interpret=interpret,
                            **kw),
                        (q[:, :, 0, :], ck, cv, cache_index + 1),
                        (HP, PL, PL, _P(None)),
                        HP)[:, :, None, :]
            elif per_row and T == 1 and block_table is not None:
                # XLA fallback's paged DECODE fast path: masked
                # attention contracted straight against the block-
                # indexed (B, nb, H, page, D) gather — no chain
                # relayout into contiguous rows, which was a full
                # working-set transpose copy per layer per decode step
                # (the measured paged-vs-dense CPU decode gap, and
                # under scan_k it recurred every fused step).
                y = xla_decode_attention_paged(
                    q[:, :, 0, :], ck, cv, block_table, cache_index + 1,
                    k_scale=cks, v_scale=cvs,
                    sm_scale=1.0 / head_dim ** 0.5)[:, :, None, :]
            elif (per_row and block_table is not None
                  and decode_impl != "xla"):
                # Paged prefill / verify (T > 1) flash kernel: each
                # row's (T, D) suffix queries walk its block chain
                # through the scalar-prefetched table — the resident
                # prefix included — instead of the gathered-masked XLA
                # fallback below, which copies every row's whole chain
                # into contiguous rows per wave (the last non-kernel
                # hot path, and the known paged-vs-dense CPU TTFT gap).
                y = _kernel(
                    lambda *a, **kw: flash_prefill_paged(
                        *a, sm_scale=sm_scale, interpret=interpret, **kw),
                    (q, ck, cv, block_table, cache_index),
                    (PL, PL, PL, _P(None, None), _P(None)),
                    PL)
            else:
                # Masked-score XLA path. When cache_index is a STATIC int
                # (prefill / sample.generate's first pass) the attended
                # range is bounded to the known frontier instead of the
                # full buffer: positions past cache_index + T can only
                # ever be masked, so slicing them off saves their score
                # FLOPs and K/V bytes outright (bit-identical output —
                # the masked columns' softmax mass is exactly 0). Traced
                # indices (the per-row decode/verify paths) keep the full
                # buffer: their frontier is data, not shape.
                span = Tc
                if isinstance(cache_index, int):
                    span = min(cache_index + T, Tc)
                if block_table is not None:
                    # XLA fallback / T > 1 verify blocks over a paged
                    # pool: gather each row's block chain into the
                    # contiguous rows the shared masked path expects.
                    # Same values at the same positions as a dense row
                    # (garbage beyond the frontier is masked either
                    # way), so the math below is bit-identical.
                    gathered, = _gather_paged_layers(
                        [(ck, cv, cks, cvs) if quantized else (ck, cv)],
                        block_table)
                    ck_a, cv_a = gathered[0], gathered[1]
                    cks_a = gathered[2] if quantized else None
                    cvs_a = gathered[3] if quantized else None
                else:
                    ck_a, cv_a = ck[:, :, :span], cv[:, :, :span]
                    cks_a = cks[:, :, :span] if quantized else None
                    cvs_a = cvs[:, :, :span] if quantized else None
                if four_bit:
                    # Packed int4 unpacks to int8 for the reference
                    # math; scales then fold identically to int8 (the
                    # kernels unpack per-tile in-register instead).
                    ck_a, cv_a = unpack_int4(ck_a), unpack_int4(cv_a)
                # (B|1, 1, T, span): kpos <= qpos. The unwritten/stale
                # buffer tail beyond each row's frontier is masked off,
                # so garbage K/V from a previous slot occupant never
                # contributes.
                mask = (jnp.arange(span)[None, None, None, :]
                        <= qpos[:, None, :, None])
                scores = jnp.einsum(
                    "bhtd,bhsd->bhts", q,
                    ck_a.astype(q.dtype) if quantized else ck_a,
                    preferred_element_type=jnp.float32)
                scores = scores * (1.0 / head_dim ** 0.5)
                if quantized:
                    # Per-position scales fold into the score/probability
                    # tensors (scale is constant across the head_dim
                    # contraction) — the same dequant-by-folding contract
                    # as the flash kernel, so the two paths agree.
                    scores = scores * cks_a[:, :, None, :]
                scores = jnp.where(mask, scores, -1e30)
                probs = jax.nn.softmax(scores, axis=-1)
                if quantized:
                    probs_v = (probs * cvs_a[:, :, None, :]).astype(q.dtype)
                    y = jnp.einsum("bhts,bhsd->bhtd", probs_v,
                                   cv_a.astype(q.dtype))
                else:
                    y = jnp.einsum("bhts,bhsd->bhtd", probs.astype(cv.dtype),
                                   cv_a)
            # Per-head attention output stays head-sharded into the
            # row-parallel c_proj below: its (B, T, C) reshape carries
            # the split on C, so the projection contracts locally and
            # XLA inserts exactly ONE model-axis all-reduce per block.
            y = _tp(y, None, "model", None, None)
            new_cache = (ck, cv, cks, cvs) if quantized else (ck, cv)
        elif cfg.attention_impl == "ring":
            # Sequence-parallel ring attention: T is sharded over the mesh's
            # seq axis; K/V chunks rotate over ICI (ops/ring_attention.py).
            from nanosandbox_tpu.ops.ring_attention import ring_attention_sharded
            from nanosandbox_tpu.parallel.mesh import current_mesh

            mesh = self.mesh if self.mesh is not None else current_mesh()
            if mesh is None:
                raise ValueError(
                    "attention_impl='ring' needs an active mesh — construct "
                    "the model via Trainer, or call "
                    "parallel.mesh.set_current_mesh(make_mesh(...)) first")
            dropout_seed = None
            ring_rate = 0.0
            if cfg.dropout > 0.0 and not deterministic:
                # Attention-prob dropout composes with the ring because
                # the keep-mask is keyed on GLOBAL (q_pos, k_pos)
                # coordinates (ops/ring_attention.py round-5) — same
                # regularization as the non-ring flash path.
                ring_rate = cfg.dropout
                dropout_seed = jax.random.bits(self.make_rng("dropout"),
                                               (1,), jnp.uint32)
            y = ring_attention_sharded(
                q, k, v, mesh=mesh, layout=cfg.ring_layout,
                block_impl=cfg.ring_block_impl,
                dropout_rate=ring_rate, dropout_seed=dropout_seed)
        else:
            attn_rng = None
            if cfg.dropout > 0.0 and not deterministic:
                attn_rng = self.make_rng("dropout")
            # Only the EXPLICITLY bound mesh routes through the shard_map
            # wrapper — the current_mesh() global (a ring-path fallback)
            # must not leak into standalone-model use, where the caller's
            # arrays have no relation to whatever mesh a previous Trainer
            # registered.
            mesh = self.mesh
            if (mesh is not None and mesh.size > 1
                    and mesh.shape.get("seq", 1) == 1
                    and cfg.attention_impl in ("auto", "pallas",
                                               "pallas_interpret")):
                # seq-axis gate: with mesh_sp > 1 the ring branch above is
                # the only correct path (Trainer validates that); a
                # direct-model user with a seq-sharded mesh but a
                # non-ring impl falls through and gets GSPMD's own
                # error rather than a silently-contiguous ring that
                # ignores cfg.ring_layout/ring_block_impl.
                # GSPMD cannot auto-partition Mosaic custom calls ("Mosaic
                # kernels cannot be automatically partitioned") — on a
                # >1-device mesh the flash kernel must sit inside a
                # shard_map. The sp=1-degenerate ring wrapper IS that
                # shell: one local flash block per shard, batch over
                # (data, fsdp), heads over model, with the global-position
                # dropout offsets keeping per-shard masks decorrelated.
                from nanosandbox_tpu.ops.ring_attention import (
                    ring_attention_sharded)

                rate = 0.0 if deterministic else cfg.dropout
                seed = None
                if rate > 0.0:
                    seed = jax.random.bits(attn_rng, (1,), jnp.uint32)
                y = ring_attention_sharded(
                    q, k, v, mesh=mesh, layout="contiguous",
                    block_impl=cfg.attention_impl,
                    dropout_rate=rate, dropout_seed=seed)
            else:
                y = causal_attention(
                    q, k, v, impl=cfg.attention_impl,
                    dropout_rate=0.0 if deterministic else cfg.dropout,
                    dropout_rng=attn_rng)
        if layout == "bhtd":
            y = y.transpose(0, 2, 1, 3).reshape(B, T, C)
        if cache is not None and tp_mesh is not None:
            # The merged (H, D) -> C dim keeps the head split: this is
            # the Megatron row-parallel input layout for c_proj (kernel
            # sharded on its contraction dim by spec_for_param).
            y = _tp(y, None, None, "model")

        proj_std = 0.02 / (2 * cfg.n_layer) ** 0.5
        y = nn.Dense(C, use_bias=cfg.bias, dtype=dtype,
                     param_dtype=cfg.param_dtype,
                     kernel_init=_dense_init(proj_std), name="c_proj")(y)
        if cfg.dropout > 0.0:
            y = nn.Dropout(cfg.dropout)(y, deterministic=deterministic)
        return (y, new_cache) if cache is not None else y


class MLP(nn.Module):
    cfg: GPTConfig

    @nn.compact
    def __call__(self, x: jax.Array, deterministic: bool) -> jax.Array:
        cfg = self.cfg
        C = x.shape[-1]
        dtype = jnp.dtype(cfg.compute_dtype)
        proj_std = 0.02 / (2 * cfg.n_layer) ** 0.5
        h = nn.Dense(4 * C, use_bias=cfg.bias, dtype=dtype,
                     param_dtype=cfg.param_dtype,
                     kernel_init=_dense_init(), name="c_fc")(x)
        h = nn.gelu(h, approximate=True)
        h = nn.Dense(C, use_bias=cfg.bias, dtype=dtype,
                     param_dtype=cfg.param_dtype,
                     kernel_init=_dense_init(proj_std), name="c_proj")(h)
        if cfg.dropout > 0.0:
            h = nn.Dropout(cfg.dropout)(h, deterministic=deterministic)
        return h


class Block(nn.Module):
    cfg: GPTConfig
    mesh: Any = None

    @nn.compact
    def __call__(self, x: jax.Array, deterministic: bool,
                 cache: Optional[tuple] = None, cache_index=None,
                 block_table=None):
        cfg = self.cfg
        attn = CausalSelfAttention(cfg, mesh=self.mesh, name="attn")
        a_in = _layer_norm(cfg, "ln_1")(x).astype(cfg.compute_dtype)
        if cache is not None:
            y, new_cache = attn(a_in, deterministic, cache, cache_index,
                                block_table)
            x = x + y
        else:
            x = x + attn(a_in, deterministic)
            new_cache = None
        x = x + MLP(cfg, name="mlp")(
            _layer_norm(cfg, "ln_2")(x).astype(cfg.compute_dtype),
            deterministic)
        return (x, new_cache) if cache is not None else x


class GPT(nn.Module):
    cfg: GPTConfig
    mesh: Any = None  # bound by Trainer; needed for attention_impl='ring'

    def _constrain_acts(self, x: jax.Array) -> jax.Array:
        return constrain_acts(self.mesh, x)

    @nn.compact
    def __call__(self, idx: jax.Array, *, deterministic: bool = True,
                 return_hidden: bool = False,
                 cache: Optional[list] = None, cache_index=None,
                 block_table=None):
        """Returns logits (B, T, vocab) — or, with return_hidden=True, the
        final-layernorm hidden states (B, T, C) so the caller can fuse the
        LM head into a chunked loss (chunked_cross_entropy_loss) without
        ever materializing full logits in HBM.

        Incremental decode: pass ``cache`` (per-layer (K, V) buffers from
        init_cache) and ``cache_index`` (global position of idx[:, 0] —
        a scalar, or a (B,) int32 vector giving each row its OWN position,
        the serve engine's slot-pool contract where every row is an
        independent request at its own frontier); returns
        (logits, new_cache). Each call attends against everything
        written so far, so a prefill call (T = prompt length) followed by
        T=1 calls decodes in O(T) total attention reads instead of the
        windowed full-forward's O(T * block_size) recompute per token."""
        cfg = self.cfg
        B, T = idx.shape
        if T > cfg.block_size:
            raise ValueError(f"sequence length {T} > block_size {cfg.block_size}")

        wte = nn.Embed(cfg.vocab_size, cfg.n_embd,
                       embedding_init=_dense_init(),
                       param_dtype=cfg.param_dtype, name="wte")
        wpe = nn.Embed(cfg.block_size, cfg.n_embd,
                       embedding_init=_dense_init(),
                       param_dtype=cfg.param_dtype, name="wpe")

        if cache is not None:
            if getattr(cache_index, "ndim", 0) == 1:
                # Per-row decode positions (serve slot pool): row b's
                # tokens sit at cache_index[b] + [0, T).
                pos = cache_index[:, None] + jnp.arange(T)[None, :]
            else:
                pos = cache_index + jnp.arange(T)[None, :]
            # Lanes that overshoot a row's end ON PURPOSE (speculative
            # verify lanes, a scan rung's trailing lane-steps, padded
            # prefill buckets) carry positions >= block_size. nn.Embed's
            # gather FILLS an out-of-range row with NaN, it does not
            # clamp — and one NaN row poisons the logits the poison
            # guard reads. Bound the lookup here: such a lane reads the
            # last (finite) row, and the masks already discard it. (wte
            # is deliberately NOT bounded: token ids are validated at
            # submit, and the one out-of-vocab id in the system is the
            # poison sentinel, whose row must stay poisoned.)
            pos = jnp.clip(pos, 0, cfg.block_size - 1)
        else:
            pos = jnp.arange(T)[None, :]
        x = self._constrain_acts(wte(idx) + wpe(pos))
        if cfg.dropout > 0.0:
            x = nn.Dropout(cfg.dropout)(x, deterministic=deterministic)
        x = x.astype(cfg.compute_dtype)

        if cache is not None:
            if return_hidden:
                raise ValueError(
                    "return_hidden is a training-loss hook (chunked CE); "
                    "the cached decode path always returns (logits, cache)")
            # Contract: cache_index + T must stay within the cache buffer.
            # An overrun would not error — dynamic_update_slice clamps the
            # write offset and the wpe lookup above is bounded to the last
            # position — it would silently produce wrong logits for the
            # overrunning lanes. Checkable only when the index
            # is a Python int (jit callers pass a traced scalar and must
            # enforce the bound themselves, as sample.generate does by
            # falling back to the windowed path when total > block_size).
            if isinstance(cache_index, int) and cache:
                cache_len = cache[0][0].shape[2]
                if cache_index + T > cache_len:
                    raise ValueError(
                        f"cached decode overrun: cache_index {cache_index} "
                        f"+ T {T} exceeds the cache length {cache_len}")
            # Decode path: no remat (inference has no backward to feed).
            new_cache = []
            for i in range(cfg.n_layer):
                x, layer_cache = Block(cfg, mesh=self.mesh, name=f"h_{i}")(
                    x, deterministic, cache[i], cache_index, block_table)
                new_cache.append(layer_cache)
            x = _layer_norm(cfg, "ln_f")(x)
            logits = wte.attend(x.astype(cfg.param_dtype))
            return logits, new_cache

        block_cls = Block
        if cfg.remat:
            block_cls = remat_block(Block, cfg.remat_policy,
                                    ("attn_out", "attn_lse"))
        for i in range(cfg.n_layer):
            x = self._constrain_acts(
                block_cls(cfg, mesh=self.mesh, name=f"h_{i}")(x, deterministic))

        x = _layer_norm(cfg, "ln_f")(x)
        if return_hidden:
            return x
        # Weight-tied LM head (nanoGPT ties lm_head.weight = wte.weight).
        # Note on dtype: JAX's default matmul precision on TPU already
        # runs f32-input matmuls at the MXU's bf16 rate (measured: an
        # explicit bf16 cast of the embedding table changes nothing but
        # adds ~230 MB/step of cast traffic), so the f32 attend is
        # already the fast path.
        logits = wte.attend(x.astype(cfg.param_dtype))
        return logits


KV_DTYPES = ("fp32", "bf16", "int8", "int4")


def normalize_kv_dtype(kv_dtype) -> str | None:
    """Canonicalize a --kv_dtype flag value: None/''/'auto' -> None (use
    the compute dtype, the pre-int8 default), else one of KV_DTYPES."""
    if kv_dtype in (None, "", "auto"):
        return None
    alias = {"fp32": "fp32", "float32": "fp32",
             "bf16": "bf16", "bfloat16": "bf16", "int8": "int8",
             "int4": "int4"}
    norm = alias.get(str(kv_dtype))
    if norm is None:
        raise ValueError(f"unknown kv_dtype {kv_dtype!r} "
                         f"(expected one of {KV_DTYPES})")
    return norm


def _quantized_layer_shapes(kvd: str, lead: tuple, n_head: int,
                            length: int, head_dim: int):
    """(value shape+dtype, scale shape) for an int8/int4 cache layer.
    int4 packs two nibbles per byte along head_dim (uint8 storage —
    the dtype is how every consumer tells the two modes apart); both
    keep one f32 scale per (row, head, position) block of lanes."""
    if kvd == "int4":
        if head_dim % 2:
            raise ValueError(
                f"int4 KV packs two lanes per byte; head_dim "
                f"{head_dim} must be even")
        vshape = lead + (n_head, length, head_dim // 2)
        vdtype = jnp.uint8
    else:
        vshape = lead + (n_head, length, head_dim)
        vdtype = jnp.int8
    return vshape, vdtype, lead + (n_head, length)


def init_cache(cfg: GPTConfig, batch_size: int, max_len: int,
               dtype: Any = None, kv_dtype=None) -> list:
    """Per-layer (K, V) decode buffers, shape (B, H, max_len, head_dim).

    max_len caps at block_size — the learned positional table (wpe) defines
    positions no further, matching nanoGPT's context-cropping contract.
    Stored in compute_dtype by default (bf16 on TPU): halves cache HBM and
    matches the dtype K/V are produced in, so writes are cast-free.

    kv_dtype ('fp32' | 'bf16' | 'int8' | 'int4', see normalize_kv_dtype)
    overrides the storage mode. 'int8' switches each layer to a 4-tuple
    (K int8, V int8, k_scale f32 (B, H, max_len), v_scale f32 likewise):
    per-(row, head, position) symmetric scales, quantize-on-write in the
    attention cache path (models above) and in scatter_cache_rows, so
    fp K/V never reaches the pool — 2x (vs bf16) / 4x (vs fp32) less HBM
    per cached token, i.e. 2x the concurrent slots at constant HBM and
    proportionally less decode read traffic. 'int4' halves the value
    bytes again: two nibbles per byte packed along head_dim (uint8
    storage), the SAME per-(row, head, position) f32 residual scales,
    round-trip error <= max|row|/7.5 per block of lanes."""
    if max_len > cfg.block_size:
        raise ValueError(
            f"cache length {max_len} > block_size {cfg.block_size}")
    kvd = normalize_kv_dtype(kv_dtype)
    head_dim = cfg.n_embd // cfg.n_head
    shape = (batch_size, cfg.n_head, max_len, head_dim)
    if kvd in ("int8", "int4"):
        vshape, vdtype, sshape = _quantized_layer_shapes(
            kvd, (batch_size,), cfg.n_head, max_len, head_dim)
        return [(jnp.zeros(vshape, vdtype), jnp.zeros(vshape, vdtype),
                 jnp.zeros(sshape, jnp.float32),
                 jnp.zeros(sshape, jnp.float32))
                for _ in range(cfg.n_layer)]
    if kvd == "fp32":
        dtype = jnp.float32
    elif kvd == "bf16":
        dtype = jnp.bfloat16
    else:
        dtype = jnp.dtype(dtype or cfg.compute_dtype)
    return [(jnp.zeros(shape, dtype), jnp.zeros(shape, dtype))
            for _ in range(cfg.n_layer)]


def scatter_cache_rows(pool: list, rows: list, slots: jax.Array) -> list:
    """Write a prefill wave's per-layer (k, H, L, D) K/V rows into the
    slot rows of a (num_slots, H, max_len, D) pool at columns [0, L).

    The scatter uses mode='drop': a slot id >= num_slots (the serve
    engine's ladder-padding rows) writes nowhere, unlike
    dynamic_update_slice whose index CLAMP would silently overwrite the
    last real slot row. Stale columns past L are hidden by the per-row
    causal mask until the new occupant's decode overwrites them.

    An int8/int4 pool (4-tuple layers) accepts fp rows — they are
    quantized HERE, inside the compiled prefill program, so a prefill
    wave's K/V lands already-quantized (the prefill forward itself
    keeps full precision; only the pool representation narrows). Rows
    that are already quantized 4-tuples (a quantized temp cache)
    scatter as-is. Ladder-padding rows (slot id >= num_slots) skip the
    quantizer's scale chain entirely — their scatter drops anyway, so
    computing per-position amax/divide/round for them was wasted lane
    work on every prefill wave."""
    from nanosandbox_tpu.ops.flash_decode import (quantize_kv_rows,
                                                  quantize_kv_rows_int4)

    out = []
    num_slots = pool[0][0].shape[0]
    # (k, 1, 1) over the wave's (k, H, L) quantize rows.
    row_valid = (slots < num_slots)[:, None, None]
    for pool_layer, row_layer in zip(pool, rows):
        if len(pool_layer) == 4:
            pk, pv, pks, pvs = pool_layer
            qfn = (quantize_kv_rows_int4 if pk.dtype == jnp.uint8
                   else quantize_kv_rows)
            if len(row_layer) == 4:
                ck, cv, cks, cvs = row_layer
            else:
                ck, cv = row_layer
                ck, cks = qfn(ck, valid=row_valid)
                cv, cvs = qfn(cv, valid=row_valid)
            L = ck.shape[2]
            pk = pk.at[slots, :, :L, :].set(ck, mode="drop")
            pv = pv.at[slots, :, :L, :].set(cv, mode="drop")
            pks = pks.at[slots, :, :L].set(cks, mode="drop")
            pvs = pvs.at[slots, :, :L].set(cvs, mode="drop")
            out.append((pk, pv, pks, pvs))
            continue
        ck, cv = row_layer[0], row_layer[1]
        if len(row_layer) == 4:
            raise ValueError(
                "cannot scatter quantized rows into a full-precision "
                "pool; build the pool with init_cache(kv_dtype=...)")
        pk, pv = pool_layer
        L = ck.shape[2]
        pk = pk.at[slots, :, :L, :].set(ck.astype(pk.dtype), mode="drop")
        pv = pv.at[slots, :, :L, :].set(cv.astype(pv.dtype), mode="drop")
        out.append((pk, pv))
    return out


def init_paged_cache(cfg: GPTConfig, num_blocks: int, page: int,
                     kv_dtype=None) -> list:
    """Per-layer K/V BLOCK pools, shape (num_blocks, H, page, head_dim).

    The paged twin of init_cache: instead of one (B, H, max_len, D) row
    per slot, the pool is a global heap of fixed-size blocks of ``page``
    positions each, and a (num_slots, max_blocks) block table (serve
    engine slot state) maps each row's logical positions onto blocks —
    allocate-on-demand memory, refcount-shared prefixes
    (serve/paged.py). Same kv_dtype modes as init_cache; 'int8'/'int4'
    layers are 4-tuples with (num_blocks, H, page) f32 per-position
    scales (int4 values pack two nibbles per byte along head_dim)."""
    kvd = normalize_kv_dtype(kv_dtype)
    head_dim = cfg.n_embd // cfg.n_head
    shape = (num_blocks, cfg.n_head, page, head_dim)
    if kvd in ("int8", "int4"):
        vshape, vdtype, sshape = _quantized_layer_shapes(
            kvd, (num_blocks,), cfg.n_head, page, head_dim)
        return [(jnp.zeros(vshape, vdtype), jnp.zeros(vshape, vdtype),
                 jnp.zeros(sshape, jnp.float32),
                 jnp.zeros(sshape, jnp.float32))
                for _ in range(cfg.n_layer)]
    if kvd == "fp32":
        dtype = jnp.float32
    elif kvd == "bf16":
        dtype = jnp.bfloat16
    else:
        dtype = jnp.dtype(cfg.compute_dtype)
    return [(jnp.zeros(shape, dtype), jnp.zeros(shape, dtype))
            for _ in range(cfg.n_layer)]


def _gather_paged_layers(pool: list, block_table: jax.Array) -> list:
    """Gather each row's block chain into contiguous per-layer rows:
    (num_blocks, H, page, D) pool + (B, nb) table -> (B, H, nb*page, D)
    rows (scales likewise). Sentinel table entries clamp to a real
    block — their positions sit beyond the row's frontier and every
    consumer masks them. This is the XLA fallback's per-step byte cost
    (a full row-copy) that flash_decode_paged's in-kernel indirection
    exists to avoid."""
    B, nb = block_table.shape
    out = []
    for layer in pool:
        pk, pv = layer[0], layer[1]
        _, H, page, D = pk.shape
        L = nb * page

        def _vals(p):
            return p[block_table].transpose(0, 2, 1, 3, 4).reshape(
                B, H, L, D)

        if len(layer) == 4:
            pks, pvs = layer[2], layer[3]

            def _scales(s):
                return s[block_table].transpose(0, 2, 1, 3).reshape(B, H, L)

            out.append((_vals(pk), _vals(pv), _scales(pks), _scales(pvs)))
        else:
            out.append((_vals(pk), _vals(pv)))
    return out


def gather_paged_rows(pool: list, block_table: jax.Array) -> list:
    """Public alias of the per-layer paged gather (tests use it to
    build the contiguous reference view of a paged pool)."""
    return _gather_paged_layers(pool, block_table)




# -- the family's answers to Trainer (models/__init__.py: FAMILIES) ----------

model_config = GPTConfig.from_train_config

# What restore_for_inference misses for this family: nothing.
inference = None


def check(cfg, pretrained: bool) -> None:
    """Nothing a TrainConfig can ask is refused."""


def pretrained(cfg, dataset_meta: dict):
    """(cfg with the architecture of the weights ``cfg.init_from`` names,
    their converted params): the reference's ``--init_from=gpt2*``. The HF
    config dictates the architecture, exactly as nanoGPT forces its model
    args from the loaded checkpoint. block_size may be CROPPED below the
    pretrained context (wpe rows sliced); growing it has no trained
    positions to use and errors."""
    from nanosandbox_tpu.models.convert import (HF_GPT2_NAMES, load_hf_gpt2,
                                                resolve_init_from)

    meta_kind = dataset_meta.get("kind")
    if cfg.init_from in HF_GPT2_NAMES and meta_kind not in ("gpt2", None):
        # Real OpenAI GPT-2 weights expect the canonical tiktoken-gpt2
        # id space; a dataset prepared with the char/byte/local-BPE
        # tokenizers has the same SHAPE but different token ids, so
        # fine-tuning would silently train on garbage mappings
        # (round-4 VERDICT missing #1). kind=None (no meta.pkl) is the
        # nanoGPT OWT convention, which means gpt2 BPE — allowed.
        # Checked BEFORE the weight download so the mismatch fails
        # fast (and offline) rather than after pulling ~0.5-6 GB.
        raise ValueError(
            f"init_from={cfg.init_from!r} loads real GPT-2 weights, "
            f"but dataset {cfg.dataset!r} was tokenized with the "
            f"{meta_kind!r} tokenizer, not GPT-2 BPE. Re-prepare the "
            "dataset with the gpt2 tokenizer (python -m "
            "nanosandbox_tpu.data.prepare openwebtext ...) or drop "
            "init_from.")
    hf_cfg, hf_params = load_hf_gpt2(resolve_init_from(cfg.init_from))
    if cfg.block_size > hf_cfg.block_size:
        raise ValueError(
            f"block_size {cfg.block_size} exceeds the pretrained "
            f"context {hf_cfg.block_size} ({cfg.init_from})")
    if cfg.block_size < hf_cfg.block_size:
        hf_params["wpe"]["embedding"] = \
            hf_params["wpe"]["embedding"][:cfg.block_size]
    return cfg.replace(
        n_layer=hf_cfg.n_layer, n_head=hf_cfg.n_head,
        n_embd=hf_cfg.n_embd, vocab_size=hf_cfg.vocab_size,
        bias=True), hf_params


def build(cfg: GPTConfig, mesh: Any):
    """(the model, what ``trainer_init`` records of it)."""
    return GPT(cfg, mesh=mesh), {
        "attn_layout": attn_layout(cfg, mesh, cfg.block_size)}


def apply(model: GPT, params, x: jax.Array, *, deterministic: bool,
          return_hidden: bool, rngs=None):
    """(logits or hidden, {}): the model reports nothing beside them."""
    return model.apply({"params": params}, x, deterministic=deterministic,
                       return_hidden=return_hidden, rngs=rngs), {}


def head(params) -> jax.Array:
    """The head's (vocab, C) table: the tied token embedding."""
    return params["wte"]["embedding"]


def flops_per_token(cfg: GPTConfig, T: int, n_params: int) -> int:
    """Forward + backward operations a trained token requires (nanoGPT's
    count: 6 a parameter bar the positions' table, plus attention)."""
    N = n_params - cfg.block_size * cfg.n_embd  # exclude wpe (nanoGPT)
    L, H, Q = cfg.n_layer, cfg.n_head, cfg.n_embd // cfg.n_head
    return 6 * N + 12 * L * H * Q * T
