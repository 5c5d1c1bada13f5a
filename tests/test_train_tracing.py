"""The trainer's own spans and scopes (ISSUE 27): what a run
records into the process tracer, ``Trainer.train_iter`` as a call anyone can
drive, the map from the compiled step's instructions to parts of the model,
and the compile-cache listeners. CPU, tiny model."""

import glob
import json
import os
import re
import time

import jax
import numpy as np
import pytest

from nanosandbox_tpu.obs import opscopes, process_tracer
from nanosandbox_tpu.obs.tracer import SpanTracer
from nanosandbox_tpu.train import Trainer
from nanosandbox_tpu.utils import compile_cache


@pytest.fixture()
def traced_run(tiny_cfg):
    """A 3-step run() with a log read-back every step; returns (result,
    spans it recorded, sids that were open before it, cfg)."""
    tracer = process_tracer()
    tracer.clear()
    open_before = set(tracer._open)
    cfg = tiny_cfg.replace(max_iters=3, lr_decay_iters=3, log_interval=1)
    result = Trainer(cfg).run()
    return result, tracer.spans(), open_before, cfg


def _named(spans, name):
    return [s for s in spans if s.name == name]


def test_run_spans_have_parents_and_steps(traced_run):
    result, spans, open_before, _ = traced_run
    assert result["iter_num"] == 3

    (init,) = _named(spans, "trainer_init")
    assert init.parent == 0 and init.step is None
    for child in ("dataset_open", "make_mesh", "abstract_state",
                  "make_optimizer"):
        (s,) = _named(spans, child)
        assert s.parent == init.sid, child
        assert init.t0_ns <= s.t0_ns and s.t1_ns <= init.t1_ns

    iters = _named(spans, "train_iter")
    assert [s.step for s in iters] == [0, 1, 2]
    for it in iters:
        kids = [s for s in spans if s.parent == it.sid]
        assert sorted(s.name for s in kids) == [
            "dispatch", "loader_wait", "to_global", "to_global"]
        assert all(s.step == it.step for s in kids)
        assert all(s.args["bytes"] > 0 for s in kids if s.name == "to_global")
    # the loop's own read-backs pass through host_sync: one span each
    assert len(_named(spans, "train-log-readback")) == 3
    # the prefetch thread fills ahead, on a track of its own, by step
    fills = _named(spans, "loader_fill")
    assert {0, 1, 2} <= {s.step for s in fills}
    assert all(s.track == "loader_prefetch" for s in fills)
    # the final evaluation and save: as before, now with their children
    (ev,) = _named(spans, "eval")
    assert any(s.parent == ev.sid and s.name == "to_global" for s in spans)
    assert any(s.parent == ev.sid and s.name == "eval-readback"
               for s in spans)
    assert _named(spans, "checkpoint_save")[-1].args["final"] is True


def test_run_leaves_no_span_open(traced_run):
    _, _, open_before, _ = traced_run
    left = {sid: s.name for sid, s in process_tracer()._open.items()
            if sid not in open_before}
    assert left == {}


def test_loader_wait_says_how_many_batches_waited(tiny_cfg):
    """`args.depth` is the one record of starvation (0: the loop waited for
    the worker); a producer that finds the queue full records its slack."""
    tracer = process_tracer()
    trainer = Trainer(tiny_cfg)
    loader = trainer.make_loader("train", prefetch=True)
    mark = time.perf_counter_ns()
    try:
        deadline = time.time() + 10
        while loader._queue.qsize() < 2 and time.time() < deadline:
            time.sleep(0.01)        # the worker fills its queue of two
        time.sleep(0.05)            # ... and blocks on the third put
        for _ in range(4):
            next(loader)
    finally:
        loader.close()
    spans = [s for s in tracer.spans() if s.t0_ns >= mark]
    waits = _named(spans, "loader_wait")
    assert len(waits) == 4 and waits[0].args["depth"] == 2
    assert all(0 <= s.args["depth"] <= 2 for s in waits)
    full = [s for s in tracer.spans() if s.name == "loader_full"
            and s.t1_ns >= mark]
    assert full and all(s.track == "loader_prefetch" for s in full)


def test_train_iter_by_hand_matches_run(traced_run):
    """The five calls run() makes per iteration are train_iter: driven by
    hand from the same seed it gives the losses run() logged, step for
    step."""
    _, _, _, cfg = traced_run
    (path,) = glob.glob(os.path.join(cfg.resolved_log_dir, "*",
                                     "metrics.jsonl"))
    logged = {}
    with open(path, encoding="utf-8") as f:
        for line in f:
            row = json.loads(line)
            if "train/loss" in row:
                logged[row["step"]] = row["train/loss"]
    assert sorted(logged) == [0, 1, 2]

    trainer = Trainer(cfg)
    state = trainer.init_state()
    loader = trainer.make_loader("train")
    rng = trainer.train_rng(cfg.seed + 7)
    try:
        for i in range(3):
            state, m = trainer.train_iter(state, loader, rng, i)
            assert float(m["loss"]) == logged[i], i
    finally:
        loader.close()
    assert int(state["step"]) == 3


# -- device time by part of the model: the map ---------------------------------

_PLUMBING = re.compile(
    r"\s(parameter|constant|tuple|get-tuple-element|bitcast|copy|broadcast|"
    r"iota)\(")


def _working_instructions(text):
    """(name, line) of the instructions that compute something."""
    out = []
    for line in text.splitlines():
        m = opscopes._INSTRUCTION.match(line)
        if (m and not opscopes._COMPUTATION.match(line)
                and not _PLUMBING.search(line)):
            out.append((m.group(1), line))
    return out


@pytest.mark.parametrize("loss_chunk_size", [0, 16],
                         ids=["full-logits", "chunked"])
def test_op_parts_of_the_tiny_step(tiny_cfg, loss_chunk_size):
    trainer = Trainer(tiny_cfg.replace(loss_chunk_size=loss_chunk_size))
    train_step, _ = trainer.compiled_steps()
    text = train_step.lower(*trainer._step_operands()).compile().as_text()
    parts = opscopes.op_parts(text)
    work = _working_instructions(text)
    assert len(work) > 200
    labels = set(opscopes.PARTS) | {opscopes.UNSCOPED}
    for name, line in work:
        if re.search(r"\s(dot|fusion)\(", line):
            assert parts[name] in labels, name
    by_part = {p: [n for n, _ in work if parts[n] == p] for p in labels}
    for part in ("attn", "mlp", "ln", "embed", "lm_head_loss", "optimizer"):
        assert by_part[part], part
    # every matmul of the model and of the head is somebody's
    dots = [n for n, line in work if re.search(r"\sdot\(", line)]
    assert dots and all(parts[n] != opscopes.UNSCOPED for n in dots)
    assert len(by_part[opscopes.UNSCOPED]) < 0.05 * len(work)
    # the same map through the trainer's own door, and through the
    # provider compiled_steps() left with obs.opscopes
    assert trainer.step_op_parts() == parts
    assert opscopes.step_parts() == parts
    opscopes.set_provider(None)
    assert opscopes.step_parts() is None


# The tiny expert steps walk 1024 tokens with top-2 of ``num_experts``, three
# held: of 4 experts one chunk covers the bound (ops.moe.chunk_rows: no scan
# at all), of 8 the walk has a second chunk, under its scan and conds. They
# compute in bfloat16, as the cells do: in float32 the held matrices need no
# cast, and a walk of one chunk leaves ``route_weights`` nothing to own.
WALKS = pytest.mark.parametrize("num_experts", [4, 8],
                                ids=["one-chunk-walk", "two-chunk-walk"])


def _check_stages(text, parts, work, cfg):
    """The stage map of a lowered expert step, out of the same text as
    ``parts``: one walk gives both, the part map as ``op_parts`` gives it;
    the six stages of ``moe_route`` are all there, nothing outside the part
    has one, and NO instruction of the part is left unstaged: the walk's
    first chunk lies outside the scan, under the stages that name its code,
    like the chunks inside it."""
    from nanosandbox_tpu.ops import moe

    again, stages = opscopes.op_maps(text)
    assert again == parts and stages == opscopes.op_stages(text)
    assert set(stages) == {n for n, p in parts.items() if p == "moe_route"}
    labels = set(opscopes.STAGES) | {opscopes.UNSTAGED}
    by_stage = {s: [n for n, _ in work if stages.get(n) == s] for s in labels}
    for stage in opscopes.STAGES:
        assert by_stage[stage], stage
    assert not by_stage[opscopes.UNSTAGED]
    assert opscopes.UNSTAGED not in stages.values()
    # a path's own words decide where there are any: the plan's sort, the
    # rows' gathers and the router's top-k are never another stage's
    for name, line in work:
        op = opscopes._OP_NAME.search(line)
        if name in stages and op and opscopes.stage_of(op.group(1)):
            assert stages[name] == opscopes.stage_of(op.group(1)), name
    _, chunks = moe.chunk_rows(cfg.batch_size * cfg.block_size,
                               cfg.num_experts_per_tok, cfg.num_experts,
                               cfg.experts_held[1])
    assert chunks == {4: 1, 8: 2}[cfg.num_experts]
    walked = [n for n, line in work if n in stages and re.search(
        r"route_accumulate/(while|cond)", line)]
    assert bool(walked) == (chunks > 1)
    # the provider hands both maps over from ONE lowering
    calls = []
    opscopes.set_provider(lambda: calls.append(1) or opscopes.op_maps(text))
    assert opscopes.step_stages() == stages
    assert opscopes.step_parts() == parts and len(calls) == 1


@WALKS
def test_op_stages_of_a_two_layer_afmoe_step(tiny_cfg, num_experts):
    """The ``afmoe`` family's step, a window layer and a full one, both with
    experts: what ``_check_stages`` holds the stage map of ``moe_route`` to,
    with a walk of one chunk and of two."""
    cfg = tiny_cfg.replace(
        model_family="afmoe", n_layer=2, n_head=2, n_kv_head=1, head_dim=16,
        n_embd=32, layer_types="sliding,full", sliding_window=16,
        num_dense_layers=0, intermediate_size=48, moe_intermediate_size=24,
        num_experts=num_experts, num_experts_per_tok=2, experts_held=(0, 3),
        batch_size=16, loss_chunk_size=16, compute_dtype="bfloat16")
    trainer = Trainer(cfg)
    train_step, _ = trainer.compiled_steps()
    text = train_step.lower(*trainer._step_operands()).compile().as_text()
    parts = opscopes.op_parts(text)
    work = _working_instructions(text)
    by_part = {p for n, _ in work for p in [parts[n]]}
    assert {"attn_sliding", "attn_full", "moe_route", "moe_experts",
            "moe_shared"} <= by_part
    _check_stages(text, parts, work, cfg)
    opscopes.set_provider(None)


def test_moe_rows_instant_counts_the_chunks_run(tiny_cfg):
    """``Trainer._count_expert_rows`` adds ``chunks_run`` by layer to the
    ``moe_rows`` instant: the rows held against a chunk's rows, at least 1
    (the first chunk runs whatever it holds). Host arithmetic on numbers
    the step's metrics carry already."""
    from nanosandbox_tpu.ops import moe

    cfg = tiny_cfg.replace(
        model_family="lfm2", n_layer=2, n_head=2, n_kv_head=1, head_dim=16,
        n_embd=32, layer_types="conv,full", num_dense_layers=0,
        moe_intermediate_size=24, num_experts=8, num_experts_per_tok=2,
        experts_held=(0, 2), loss_chunk_size=16)
    trainer = Trainer(cfg)
    rows, chunks = moe.chunk_rows(cfg.batch_size * cfg.block_size, 2, 8, 2)
    assert (rows, chunks) == (512, 2)
    tracer = process_tracer()
    tracer.clear()
    held = [0, 1, rows, rows + 1, 2 * rows]
    trainer._count_expert_rows(
        {"moe_held": np.array(held), "moe_max_rows": np.array(held),
         "moe_dropped": np.zeros(5, int)}, 7)
    (instant,) = _named(tracer.spans(), "moe_rows")
    assert instant.args["iter"] == 7 and instant.args["moe_held"] == held
    assert instant.args["chunks_run"] == [1, 1, 1, 2, 2] == [
        max(1, -(-h // rows)) for h in held]
    # a step without expert layers leaves no such instant
    trainer._count_expert_rows({"loss": 1.0}, 8)
    assert len(_named(tracer.spans(), "moe_rows")) == 1


FAMILY_KEYS = {
    "afmoe": dict(n_head=2, n_kv_head=1, head_dim=16, intermediate_size=48,
                  layer_types="sliding,full", sliding_window=16),
    "lfm2": dict(n_head=2, n_kv_head=1, head_dim=16,
                 layer_types="conv,full"),
    "deepseek_v3": dict(n_head=2, kv_lora_rank=24, qk_nope_head_dim=16,
                        qk_rope_head_dim=8, v_head_dim=16,
                        n_shared_experts=2),
}


@pytest.mark.parametrize("family", sorted(FAMILY_KEYS))
def test_run_counts_the_chunks_with_experts_held_left_unset(tiny_cfg, family):
    """``experts_held`` left at its default (0, 0) means ALL experts are
    held: the family's model config resolves it, and ``chunks_run`` is read
    against the chunk of the walk THAT config gives (``experts.walk_rows``),
    not against the unresolved pair (a chunk of 0 rows). A two-step run()
    with a log read-back every step goes through ``_count_expert_rows``."""
    from nanosandbox_tpu.models import experts

    cfg = tiny_cfg.replace(
        model_family=family, n_layer=2, n_embd=32, num_dense_layers=0,
        moe_intermediate_size=24, num_experts=4, num_experts_per_tok=2,
        loss_chunk_size=16, max_iters=2, lr_decay_iters=2, log_interval=1,
        **FAMILY_KEYS[family])
    assert cfg.experts_held == (0, 0)
    tracer = process_tracer()
    tracer.clear()
    trainer = Trainer(cfg)
    assert trainer.model_cfg.experts_held == (0, 4)
    assert trainer.run()["iter_num"] == 2
    rows, chunks = experts.walk_rows(trainer.model_cfg,
                                     cfg.batch_size * cfg.block_size)
    assert rows > 0 and chunks == 1
    instants = _named(tracer.spans(), "moe_rows")
    assert len(instants) >= 2
    for instant in instants:
        held = instant.args["moe_held"]
        # every token's k pairs are held, and one chunk covers them
        assert held == [cfg.batch_size * cfg.block_size * 2] * 2
        assert instant.args["chunks_run"] == [1, 1] == [
            max(1, -(-h // rows)) for h in held]
        assert instant.args["moe_dropped"] == [0, 0]


@WALKS
def test_op_parts_of_a_two_layer_lfm2_step(tiny_cfg, num_experts):
    """The ``lfm2`` family's step: a conv layer and an attention layer with
    experts; its ops fall under conv (the projections) and conv_mix (the
    gates and taps), attn_full, moe_route and moe_experts, every matmul is
    somebody's, and the map names no part the table lacks."""
    cfg = tiny_cfg.replace(
        model_family="lfm2", n_layer=2, n_head=2, n_kv_head=1, head_dim=16,
        n_embd=32, layer_types="conv,full", num_dense_layers=0,
        moe_intermediate_size=24, num_experts=num_experts,
        num_experts_per_tok=2, experts_held=(0, 3), batch_size=16,
        loss_chunk_size=16, compute_dtype="bfloat16")
    trainer = Trainer(cfg)
    train_step, _ = trainer.compiled_steps()
    text = train_step.lower(*trainer._step_operands()).compile().as_text()
    parts = opscopes.op_parts(text)
    work = _working_instructions(text)
    labels = set(opscopes.PARTS) | {opscopes.UNSCOPED}
    assert {parts[n] for n, _ in work} <= labels      # none unmapped
    by_part = {p: [n for n, _ in work if parts[n] == p] for p in labels}
    for part in ("conv", "conv_mix", "attn_full", "moe_route", "moe_experts",
                 "ln", "embed", "lm_head_loss", "optimizer"):
        assert by_part[part], part
    for part in ("attn", "mlp", "attn_sliding", "moe_shared"):
        assert not by_part[part], part
    dots = [n for n, line in work if re.search(r"\sdot\(", line)]
    assert dots and all(parts[n] != opscopes.UNSCOPED for n in dots)
    _check_stages(text, parts, work, cfg)
    opscopes.set_provider(None)


@WALKS
def test_op_parts_of_a_two_layer_deepseek_v3_step(tiny_cfg, num_experts):
    """The ``deepseek_v3`` family's step: two latent-attention layers with
    experts; its ops fall under attn_mla (the projections and attention),
    mla_prep (the latent's norm, the rotary positions), moe_route,
    moe_experts and moe_shared, every matmul is somebody's, and the map
    names no part the table lacks."""
    cfg = tiny_cfg.replace(
        model_family="deepseek_v3", n_layer=2, n_head=2, n_embd=32,
        kv_lora_rank=24, qk_nope_head_dim=16, qk_rope_head_dim=8,
        v_head_dim=16, num_dense_layers=0, moe_intermediate_size=24,
        n_shared_experts=2, num_experts=num_experts, num_experts_per_tok=2,
        experts_held=(0, 3), batch_size=16, loss_chunk_size=16,
        compute_dtype="bfloat16")
    trainer = Trainer(cfg)
    train_step, _ = trainer.compiled_steps()
    text = train_step.lower(*trainer._step_operands()).compile().as_text()
    parts = opscopes.op_parts(text)
    work = _working_instructions(text)
    labels = set(opscopes.PARTS) | {opscopes.UNSCOPED}
    assert {parts[n] for n, _ in work} <= labels      # none unmapped
    by_part = {p: [n for n, _ in work if parts[n] == p] for p in labels}
    for part in ("attn_mla", "mla_prep", "moe_route", "moe_experts",
                 "moe_shared", "ln", "embed", "lm_head_loss", "optimizer"):
        assert by_part[part], part
    for part in ("attn", "mlp", "attn_full", "attn_sliding", "conv"):
        assert not by_part[part], part
    dots = [n for n, line in work if re.search(r"\sdot\(", line)]
    assert dots and all(parts[n] != opscopes.UNSCOPED for n in dots)
    _check_stages(text, parts, work, cfg)
    opscopes.set_provider(None)


def test_lowering_for_the_map_keeps_the_live_budget_of_one(tiny_cfg):
    """step_op_parts() may trace the step once more, which is allowed for by
    name; the live loop's own budget stays one trace."""
    trainer = Trainer(tiny_cfg)
    state = trainer.init_state()
    loader = trainer.make_loader("train", prefetch=False)
    rng = trainer.train_rng(0)
    state, _ = trainer.train_iter(state, loader, rng, 0)
    assert trainer.tracecheck.counts()["train_step"] == 1
    trainer.step_op_parts()
    counts, budgets = trainer.tracecheck.counts(), trainer.tracecheck.budgets()
    assert counts["train_step"] == budgets["train_step"] <= 2
    state, _ = trainer.train_iter(state, loader, rng, 1)   # no retrace
    assert trainer.tracecheck.counts() == counts
    trainer.tracecheck.assert_within_budget()


@pytest.mark.parametrize("op_name,part", [
    ("jit(traced)/jvp(GPT)/h_3/attn/c_attn/dot_general", "attn"),
    ("jit(traced)/transpose(jvp(GPT))/h_0/mlp/c_fc/dot_general", "mlp"),
    ("jit(traced)/transpose(jvp(GPT))/h_11/ln_2/mul", "ln"),
    ("jit(traced)/jvp(GPT)/ln_f/reduce_sum", "ln"),
    ("jit(traced)/jvp(GPT)/wpe/jit(_take)/gather", "embed"),
    ("jit(traced)/transpose(jvp(GPT))/wte/jit(_take)/scatter-add", "embed"),
    ("jit(traced)/jvp(GPT)/wte.attend/dot_general", "lm_head_loss"),
    ("jit(traced)/transpose(jvp(lm_head_loss))/while/body/closed_call/"
     "checkpoint/rematted_computation/dot_general", "lm_head_loss"),
    ("jit(traced)/optimizer/jit(clip)/max", "optimizer"),
    ("jit(traced)/grad_norm/reduce_sum", "grad_norm"),
    # the innermost component that names a part decides
    ("jit(traced)/jvp(GPT)/h_0/attn/mlp/x", "mlp"),
    # a wrapper never decides: what it wraps does, as a component
    ("jit(traced)/jvp(GPT)/reshape", "unscoped"),
    ("jit(traced)/jvp(attn)/mul", "attn"),
    ("jit(traced)/jvp(jit(attn_like))/mul", "unscoped"),
    ("jit(traced)/transpose(jvp())/add_any", "unscoped"),
    ("jit(traced)/accum/while/body/add", "unscoped"),
    ("state['params']['h_0']['attn']['c_attn']['kernel']", "unscoped"),
    # ops the compiler merged carry several paths: the first with a part
    ("jit(traced)/mul;jit(traced)/jvp(GPT)/h_1/mlp/add", "mlp"),
    # afmoe's one-pass q/k norm + rotary: a scope of its own that names no
    # part, so the attention module round it decides, forward and backward
    ("jit(traced)/jvp(Afmoe)/h_0/attn_sliding/q_norm/jit(_pallas_qk_prep)"
     "/qk_prep/pallas_call", "attn_sliding"),
    ("jit(traced)/transpose(jvp(Afmoe))/h_3/attn_full/k_norm/"
     "jit(_pallas_qk_prep)/qk_prep/pallas_call", "attn_full"),
    # ... and its row mover, inside the routing scope in both directions
    # (the backward under ops.moe's own VJP, which opens the scope again)
    ("jit(traced)/jvp(Afmoe)/h_1/moe/moe_route/while/body/closed_call/cond/"
     "branch_1_fun/jit(_pallas_rows_to_tokens)/moe_rows/pallas_call",
     "moe_route"),
    ("jit(traced)/transpose(jvp(Afmoe))/jvp(Afmoe)/checkpoint/h_4/moe/"
     "moe_route/moe_route/while/body/closed_call/cond/branch_1_fun/"
     "transpose(jvp(jit(_pallas_rows_to_tokens)))/moe_rows/pallas_call",
     "moe_route"),
    # lfm2's conv module: its projections are the module's, the gates and
    # taps a part of their own inside it; its three norms are norms
    ("jit(traced)/jvp(Lfm2)/h_0/conv/in_proj/dot_general", "conv"),
    ("jit(traced)/transpose(jvp(Lfm2))/h_3/conv/conv_mix/mul", "conv_mix"),
    ("jit(traced)/jvp(Lfm2)/h_2/attn_full/attn_full/pallas_call",
     "attn_full"),
    ("jit(traced)/jvp(Lfm2)/h_1/operator_norm/mul", "ln"),
    ("jit(traced)/transpose(jvp(Lfm2))/h_1/ffn_norm/mul", "ln"),
    ("jit(traced)/jvp(Lfm2)/embedding_norm/reduce_sum", "ln"),
    # deepseek_v3's latent attention: the projections and the kernels are
    # the module's; the latent's norm and the rotary positions a part of
    # their own inside it (the norm's own name decides nothing); its three
    # block norms are norms; the shared expert is models/experts.py's module
    ("jit(traced)/jvp(DeepseekV3)/h_0/attn_mla/dot_general", "attn_mla"),
    ("jit(traced)/jvp(DeepseekV3)/h_2/attn_mla/jit(_pallas_flash_fwd_mla)/"
     "attn_mla/pallas_call", "attn_mla"),
    ("jit(traced)/transpose(jvp(DeepseekV3))/h_2/attn_mla/"
     "jit(_pallas_flash_bwd_mla)/attn_mla/pallas_call", "attn_mla"),
    ("jit(traced)/jvp(DeepseekV3)/h_1/attn_mla/mla_prep/kv_a_layernorm/mul",
     "mla_prep"),
    ("jit(traced)/transpose(jvp(DeepseekV3))/h_1/attn_mla/mla_prep/"
     "dot_general", "mla_prep"),
    ("jit(traced)/jvp(DeepseekV3)/h_1/input_layernorm/mul", "ln"),
    ("jit(traced)/transpose(jvp(DeepseekV3))/h_1/post_attention_layernorm/"
     "mul", "ln"),
    ("jit(traced)/jvp(DeepseekV3)/final_norm/reduce_sum", "ln"),
    ("jit(traced)/jvp(DeepseekV3)/h_3/moe/moe_shared/up_proj/dot_general",
     "moe_shared"),
    # the stages of moe_route (ISSUE 37) name no part: the routing scope
    # round them decides, and the experts' own scope inside the walk
    ("jit(traced)/jvp(Lfm2)/h_2/moe/moe_route/route_router/top_k",
     "moe_route"),
    ("jit(traced)/jvp(Afmoe)/h_1/moe/moe_route/route_plan/jit(argsort)/sort",
     "moe_route"),
    ("jit(traced)/jvp(Afmoe)/h_1/moe/moe_route/route_accumulate/while/body/"
     "closed_call/cond/branch_1_fun/route_dispatch/gather", "moe_route"),
    ("jit(traced)/transpose(jvp(DeepseekV3))/h_1/moe/moe_route/"
     "route_accumulate/while/body/closed_call/cond/branch_1_fun/"
     "transpose(jvp(route_combine))/mul", "moe_route"),
    ("jit(traced)/transpose(jvp(Lfm2))/h_2/moe/moe_route/route_accumulate/"
     "while/body/closed_call/cond/branch_1_fun/route_weights/add",
     "moe_route"),
    ("jit(traced)/jvp(Lfm2)/h_2/moe/moe_route/route_accumulate/while/body/"
     "closed_call/cond/branch_1_fun/add", "moe_route"),
    ("jit(traced)/jvp(Lfm2)/h_2/moe/moe_route/route_accumulate/while/body/"
     "closed_call/cond/branch_1_fun/moe_experts/dot_general", "moe_experts"),
    ("jit(traced)/route_router/mul", "unscoped"),
])
def test_part_of_a_scope_path(op_name, part):
    assert opscopes.part_of(op_name) == part


def test_no_stage_names_a_part():
    assert not set(opscopes._STAGE) & set(opscopes._COMPONENT)
    assert set(opscopes._STAGE.values()) <= set(opscopes.PARTS)


@pytest.mark.parametrize("op_name,stage", [
    # forward, and the backward where plain autodiff transposes it
    ("jit(traced)/jvp(Lfm2)/h_2/moe/moe_route/route_router/dot_general",
     "route_router"),
    ("jit(traced)/transpose(jvp(Lfm2))/h_2/moe/moe_route/route_router/"
     "jit(_where)/select_n", "route_router"),
    # under remat: the replayed router, the backward's second walk
    ("jit(traced)/transpose(jvp(DeepseekV3))/jvp(DeepseekV3)/checkpoint/"
     "rematted_computation/h_1/moe/moe_route/route_router/top_k",
     "route_router"),
    ("jit(traced)/transpose(jvp(DeepseekV3))/jvp(DeepseekV3)/checkpoint/h_1/"
     "moe/moe_route/route_plan/jit(argsort)/sort", "route_plan"),
    # inside the walk the innermost stage decides, not the walk's own
    ("jit(traced)/jvp(Afmoe)/h_1/moe/moe_route/route_accumulate/while/body/"
     "closed_call/cond/branch_1_fun/route_plan/jit(clip)/max", "route_plan"),
    ("jit(traced)/jvp(Afmoe)/h_1/moe/moe_route/route_accumulate/while/body/"
     "closed_call/cond/branch_1_fun/add", "route_accumulate"),
    ("jit(traced)/jvp(Afmoe)/h_1/moe/moe_route/route_accumulate/while/body/"
     "closed_call/cond", "route_accumulate"),
    ("jit(traced)/transpose(jvp(Afmoe))/jvp(Afmoe)/checkpoint/h_4/moe/"
     "moe_route/route_accumulate/while/body/closed_call/cond/branch_1_fun/"
     "jvp(route_dispatch)/gather", "route_dispatch"),
    ("jit(traced)/transpose(jvp(Afmoe))/jvp(Afmoe)/checkpoint/h_4/moe/"
     "moe_route/route_accumulate/while/body/closed_call/cond/branch_1_fun/"
     "route_weights/add", "route_weights"),
    # the row mover's kernels: dispatch's backward, combine's forward
    ("jit(traced)/transpose(jvp(Afmoe))/jvp(Afmoe)/checkpoint/h_4/moe/"
     "moe_route/route_accumulate/while/body/closed_call/cond/branch_1_fun/"
     "transpose(jvp(route_dispatch))/jit(_pallas_rows_to_tokens)/moe_rows/"
     "pallas_call", "route_dispatch"),
    ("jit(traced)/jvp(Afmoe)/h_1/moe/moe_route/route_accumulate/while/body/"
     "closed_call/cond/branch_1_fun/route_combine/"
     "jit(_pallas_rows_to_tokens)/moe_rows/pallas_call", "route_combine"),
    ("jit(traced)/transpose(jvp(Afmoe))/jvp(Afmoe)/checkpoint/h_4/moe/"
     "moe_route/route_accumulate/while/body/closed_call/cond/branch_1_fun/"
     "transpose(jvp(route_combine))/jit(_pallas_row_scalars_to_pairs)/"
     "moe_rows/pallas_call", "route_combine"),
    # the experts are a part of their own inside the walk: no stage
    ("jit(traced)/jvp(Afmoe)/h_1/moe/moe_route/route_accumulate/while/body/"
     "closed_call/cond/branch_1_fun/moe_experts/dot_general", None),
    # outside moe_route a stage's name is nobody's; the part without a
    # stage; ops the compiler merged: the first path that names each
    ("jit(traced)/jvp(Afmoe)/h_1/moe/moe_shared/up_proj/dot_general", None),
    ("jit(traced)/jvp(GPT)/h_0/mlp/route_router/mul", None),
    ("jit(traced)/route_plan/sort", None),
    ("jit(traced)/jvp(Afmoe)/h_1/moe/moe_route/mul", None),
    ("jit(traced)/mul;jit(traced)/jvp(Lfm2)/h_2/moe/moe_route/route_plan/add",
     "route_plan"),
])
def test_stage_of_a_scope_path(op_name, stage):
    assert opscopes.stage_of(op_name) == stage
    if stage is not None:
        assert opscopes.part_of(op_name) == opscopes._STAGE[stage]


def test_op_parts_votes_and_inherits():
    text = """
HloModule jit_traced, is_scheduled=true

%fused_computation.1 (p0: f32[4]) -> f32[4] {
  %p0 = f32[4]{0} parameter(0)
  %a = f32[4]{0} add(%p0, %p0), metadata={op_name="jit(traced)/jvp(GPT)/h_0/mlp/add"}
  ROOT %b = f32[4]{0} multiply(%a, %a), metadata={op_name="jit(traced)/jvp(GPT)/h_0/mlp/mul"}
}

ENTRY %main.9 (x: f32[4]) -> f32[4] {
  %x = f32[4]{0} parameter(0), metadata={op_name="x"}
  %fusion.7 = f32[4]{0} fusion(%x), kind=kLoop, calls=%fused_computation.1, metadata={op_name="jit(traced)/mul"}
  %copy.3 = f32[4]{0} copy(%fusion.7)
  ROOT %fusion.8 = f32[4]{0} fusion(%copy.3), kind=kLoop, calls=%fused_computation.1, metadata={op_name="jit(traced)/optimizer/add"}
}
"""
    parts = opscopes.op_parts(text)
    assert parts["fusion.7"] == "mlp"        # voted by its computation
    assert parts["copy.3"] == "mlp"          # no path: its operand's
    assert parts["fusion.8"] == "optimizer"  # its own path wins
    assert parts["x"] == "unscoped"
    assert parts["a"] == parts["b"] == "mlp"


def test_op_stages_vote_and_inherit_inside_their_part():
    route = "jit(traced)/jvp(Lfm2)/h_2/moe/moe_route"
    walk = route + "/route_accumulate/while/body"
    text = f"""
HloModule jit_traced, is_scheduled=true

%fused_computation.1 (p0: f32[4]) -> f32[4] {{
  %p0 = f32[4]{{0}} parameter(0)
  %a = f32[4]{{0}} add(%p0, %p0), metadata={{op_name="{walk}/route_dispatch/add"}}
  %b = f32[4]{{0}} add(%a, %a), metadata={{op_name="{walk}/route_dispatch/mul"}}
  ROOT %c = f32[4]{{0}} multiply(%b, %b), metadata={{op_name="{walk}/add"}}
}}

%fused_computation.2 (p1: f32[4]) -> f32[4] {{
  %p1 = f32[4]{{0}} parameter(0)
  ROOT %d = f32[4]{{0}} add(%p1, %p1), metadata={{op_name="{walk}/moe_experts/add"}}
}}

ENTRY %main.9 (x: f32[4]) -> f32[4] {{
  %x = f32[4]{{0}} parameter(0), metadata={{op_name="x"}}
  %fusion.1 = f32[4]{{0}} fusion(%x), kind=kLoop, calls=%fused_computation.1, metadata={{op_name="jit(traced)/mul"}}
  %copy.1 = f32[4]{{0}} copy(%fusion.1)
  %fusion.2 = f32[4]{{0}} fusion(%copy.1), kind=kLoop, calls=%fused_computation.1, metadata={{op_name="{route}/mul"}}
  %fusion.3 = f32[4]{{0}} fusion(%fusion.2), kind=kLoop, calls=%fused_computation.1, metadata={{op_name="{route}/route_plan/add"}}
  %fusion.4 = f32[4]{{0}} fusion(%fusion.3), kind=kLoop, calls=%fused_computation.2, metadata={{op_name="{route}/mul"}}
  %fusion.5 = f32[4]{{0}} fusion(%fusion.4), kind=kLoop, calls=%fused_computation.2, metadata={{op_name="jit(traced)/jvp(Lfm2)/h_2/ffn_norm/route_plan/mul"}}
  ROOT %fusion.6 = f32[4]{{0}} fusion(%fusion.5), kind=kLoop, calls=%fused_computation.2, metadata={{op_name="jit(traced)/mul"}}
}}
"""
    parts, stages = opscopes.op_maps(text)
    assert parts == opscopes.op_parts(text)
    assert stages == opscopes.op_stages(text)
    # no path of its own: part and stage voted by its computation
    assert (parts["fusion.1"], stages["fusion.1"]) == (
        "moe_route", "route_dispatch")
    assert stages["copy.1"] == "route_dispatch"     # its operand's
    # the part by its own path, the stage (it names none) by the vote
    assert stages["fusion.2"] == "route_dispatch"
    assert stages["fusion.3"] == "route_plan"        # its own path wins
    assert stages["a"] == stages["b"] == "route_dispatch"
    assert stages["c"] == "route_accumulate"
    # the part's, and nothing in it names a stage of the part: unstaged
    assert (parts["fusion.4"], stages["fusion.4"]) == ("moe_route", "unstaged")
    # a stage's name under another part, a vote for the experts' part:
    # neither instruction is in the stage map, nor is anything unscoped
    assert parts["fusion.5"] == "ln" and parts["fusion.6"] == "moe_experts"
    assert set(stages) == {"a", "b", "c", "fusion.1", "copy.1", "fusion.2",
                           "fusion.3", "fusion.4"}


def test_both_step_maps_come_from_one_call_of_the_provider():
    calls = []
    maps = ({"fusion.1": "moe_route"}, {"fusion.1": "route_plan"})
    opscopes.set_provider(lambda: calls.append(1) or maps)
    try:
        assert opscopes.step_stages() == maps[1]
        assert opscopes.step_parts() == maps[0]
        assert opscopes.step_stages() is maps[1] and len(calls) == 1
        opscopes.set_provider(None)
        assert opscopes.step_parts() is None
        assert opscopes.step_stages() is None
    finally:
        opscopes.set_provider(None)


# -- compile-cache listeners ---------------------------------------------------

def test_monitoring_listeners_register_once():
    from jax._src import monitoring

    def ours():
        return [f for f in monitoring.get_event_duration_listeners()
                + monitoring.get_event_listeners()
                if getattr(f, "__module__", "") == compile_cache.__name__]

    compile_cache._listen()
    first = ours()
    assert len(first) == 2
    for _ in range(3):
        compile_cache._listen()
    assert ours() == first


def test_compile_instants_follow_a_compile():
    """The instants say how much and when: one per INSTANT_EVERY_S accrued
    by a phase (a set-up reports thousands of small traces), so what a
    phase's instants sum to is what it took, to within that much."""
    compile_cache._listen()
    tracer = process_tracer()

    def instants(phase, since=0):
        return [s for s in tracer.spans() if s.name == "jax_compile"
                and s.args["phase"] == phase and s.t0_ns >= since]

    # what JAX reports for a long compile, and for many short traces
    from jax._src import monitoring
    every = compile_cache.INSTANT_EVERY_S
    mark = time.perf_counter_ns()
    monitoring.record_event_duration_secs(
        "/jax/core/compile/backend_compile_duration", 10 * every)
    (long_one,) = instants("backend", mark)
    assert long_one.dur_ns == 0 and long_one.args["seconds"] >= 10 * every
    before = sum(s.args["seconds"] for s in instants("trace"))
    for _ in range(200):
        monitoring.record_event_duration_secs(
            "/jax/core/compile/jaxpr_trace_duration", every / 20)
    short = instants("trace", mark)
    assert 9 <= len(short) <= 11
    took = sum(s.args["seconds"] for s in instants("trace")) - before
    assert 10 * every - every < took < 10 * every + every
    # a real compile reports through the same door
    mark = time.perf_counter_ns()
    monitoring.record_event_duration_secs(
        "/jax/core/compile/jaxpr_trace_duration", every)   # flush the rest
    n = len(instants("trace", mark))
    jax.jit(lambda x: x * 3 + 1).lower(np.arange(7.0))
    monitoring.record_event_duration_secs(
        "/jax/core/compile/jaxpr_trace_duration", every)
    assert len(instants("trace", mark)) > n
    # a cache lookup is rare and always an instant
    monitoring.record_event("/jax/compilation_cache/cache_hits")
    assert len(instants("hit", mark)) == 1


# -- what the spans cost -------------------------------------------------------

class _Annotation:
    """Stands in for jax.profiler.TraceAnnotation: counts enter and exit."""
    entered = exited = 0

    def __init__(self, name):
        self.name = name

    def __enter__(self):
        _Annotation.entered += 1

    def __exit__(self, *exc):
        _Annotation.exited += 1


def test_annotate_hook_wraps_every_span():
    tr = SpanTracer(annotate=_Annotation, nest=True)
    _Annotation.entered = _Annotation.exited = 0
    with tr.span("outer", step=4) as outer:
        inner = tr.begin("inner")
        assert _Annotation.entered == 2 and _Annotation.exited == 0
        tr.end(inner)
    assert _Annotation.entered == _Annotation.exited == 2
    a, b = tr.spans()
    assert (a.name, a.parent, a.step) == ("inner", outer, 4)
    assert (b.name, b.parent, b.step) == ("outer", 0, 4)
    ev = {e["name"]: e for e in tr.export_chrome()["traceEvents"]
          if e["ph"] == "X"}
    assert ev["inner"]["args"] == {"parent": outer, "step": 4}
    assert ev["outer"]["args"] == {"step": 4}


def test_spans_of_another_thread_get_their_own_track_and_no_parent():
    import threading

    tr = SpanTracer(nest=True)
    with tr.span("main_work"):
        t = threading.Thread(target=lambda: tr.end(
            tr.begin("helper", track="loader_prefetch", step=9)))
        t.start()
        t.join()
    helper, main = tr.spans()
    assert helper.parent == 0 and helper.step == 9
    evs = tr.export_chrome()["traceEvents"]
    tids = {e["name"]: e["tid"] for e in evs if e["ph"] == "X"}
    assert tids["main_work"] == 0 and tids["helper"] != 0
    assert {"tid": tids["helper"], "name": "loader_prefetch"} in [
        {"tid": e["tid"], "name": e["args"]["name"]} for e in evs
        if e["ph"] == "M"]


def test_a_span_ended_by_another_thread_is_not_a_parent_for_ever():
    """A request queued by one thread and admitted by another: once ended it
    must neither stay on the opening thread's stack nor parent later spans."""
    import threading

    tr = SpanTracer(nest=True)
    sid = tr.begin("queued")
    t = threading.Thread(target=tr.end, args=(sid,))
    t.start()
    t.join()
    later = tr.begin("later")
    tr.end(later)
    assert [s.parent for s in tr.spans()] == [0, 0]
    assert tr._local.stack == []


def test_spans_ended_out_of_order_leave_no_stale_parent():
    tr = SpanTracer(nest=True)
    with tr.span("long_open") as outer:
        a, b = tr.begin("a"), tr.begin("b")
        tr.end(a)                  # not the innermost: gone from the middle
        assert tr._local.stack == [outer, b]
        tr.end(b)
        c = tr.begin("c")
        tr.end(c)
    assert tr._local.stack == []
    assert {s.name: s.parent for s in tr.spans()} == {
        "a": outer, "b": a, "c": outer, "long_open": 0}


def test_a_tracer_without_nest_tracks_nothing():
    """The serve engine's tracer: request spans open and close out of order
    and across threads, so it keeps no stack and gives no parent."""
    tr = SpanTracer()
    with tr.span("wave", step=3):
        inner = tr.begin("decode_step")
        tr.end(inner)
    assert not hasattr(tr._local, "stack")
    assert [(s.parent, s.step) for s in tr.spans()] == [(0, None), (0, 3)]


def test_per_step_span_overhead_pinned():
    """What an iteration records (train_iter with loader_wait, two to_global
    and dispatch: five spans, nested, each with the annotation hook) must
    stay far below a step: under 250 us an iteration here, against steps of
    134 ms and 183 ms on the chip. Median of 5, like
    test_tracer_overhead_pinned."""
    tr = SpanTracer(capacity=16384, annotate=_Annotation, nest=True)
    n = 400
    runs = []
    for _ in range(5):
        t0 = time.perf_counter()
        for i in range(n):
            with tr.span("train_iter", cat="train", step=i):
                with tr.span("loader_wait", cat="loader", step=i,
                             args={"depth": 2}):
                    pass
                with tr.span("to_global", cat="train", args={"bytes": 1}):
                    pass
                with tr.span("to_global", cat="train", args={"bytes": 1}):
                    pass
                with tr.span("dispatch", cat="train"):
                    pass
        runs.append((time.perf_counter() - t0) / n)
    runs.sort()
    assert runs[2] < 250e-6, f"five spans an iteration {runs[2] * 1e6:.1f}us"
    assert tr.open_count() == 0


@pytest.mark.parametrize("case,want", [
    ("cpu-auto", "bhtd"),            # 'auto' off the chip is the XLA path
    ("kernels-one-device", "btc"),   # what a one-chip run resolves to
    ("kernels-on-a-mesh", "bhtd"),   # shard_map shell: (B, H, T, D) entry
])
def test_trainer_init_records_attn_layout(tiny_cfg, case, want):
    """Which HBM interface the step's attention takes is a set-up fact:
    an argument of the ``trainer_init`` span, decided from impl, shapes
    and mesh before anything is traced."""
    tracer = process_tracer()
    tracer.clear()
    cfg = tiny_cfg.replace(n_embd=128, block_size=128)
    if case != "cpu-auto":
        cfg = cfg.replace(attention_impl="pallas_interpret")
    one = case == "kernels-one-device"
    trainer = Trainer(cfg, mesh_devices=jax.devices()[:1] if one else None)
    assert trainer.mesh.size == (1 if one else len(jax.devices()))
    (init,) = [s for s in tracer.spans() if s.name == "trainer_init"]
    assert init.args["attn_layout"] == trainer.attn_layout == want


@pytest.mark.parametrize("case,want", [
    ("cpu-auto", ("xla", "bhtd", "xla")),        # 'auto' off the chip
    ("kernels", ("pallas_interpret", "btc-gqa", "fused")),  # on the chip
    ("kernels-heads-of-64", ("xla", "bhtd", "xla")),   # no whole-lane heads
])
def test_trainer_init_records_qk_prep(tiny_cfg, case, want):
    """The ``afmoe`` family's q/k head norm + rotary runs as one kernel
    exactly where its attention runs the grouped-query kernels; which, is
    an argument of ``trainer_init`` beside ``attn_layout``, and beside
    them ``gqa_bwd``, what those kernels' backward runs at the model's
    sequence length (ops.attention.resolve_gqa_bwd)."""
    tracer = process_tracer()
    tracer.clear()
    cfg = tiny_cfg.replace(
        model_family="afmoe", n_layer=2, n_head=2, n_kv_head=1,
        head_dim=64 if case == "kernels-heads-of-64" else 128,
        block_size=128, layer_types="sliding,full", sliding_window=32,
        num_dense_layers=1, intermediate_size=48, moe_intermediate_size=24,
        num_experts=4, num_experts_per_tok=2, experts_held=(0, 2),
        attention_impl="auto" if case == "cpu-auto" else "pallas_interpret")
    trainer = Trainer(cfg, mesh_devices=jax.devices()[:1])
    (init,) = [s for s in tracer.spans() if s.name == "trainer_init"]
    assert (init.args["qk_prep"], init.args["attn_layout"],
            init.args["gqa_bwd"]) == want
    assert trainer.qk_prep == want[0]
