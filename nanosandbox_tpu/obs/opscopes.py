"""Which part of the model an instruction of a compiled step belongs to.

XLA keeps, on every instruction of a compiled program, the scope path JAX
traced it under (``metadata={op_name="jit(traced)/transpose(jvp(GPT))/h_3/
mlp/c_fc/dot_general"}``), backward pass included. Flax names the modules
(``h_3/attn/c_attn``, ``mlp``, ``ln_1``, ``ln_f``, ``wte``, ``wpe``; for
models/afmoe.py ``attn_sliding`` / ``attn_full``, ``moe_shared``, ``ln_in``
...; for models/lfm2.py ``conv``, ``attn_full``, ``operator_norm`` ...; for
models/deepseek_v3.py ``attn_mla``, ``input_layernorm`` ...; for
models/ouro.py ``exit_gate``; for models/granite.py ``mamba``) and the trainer adds ``jax.named_scope``
where no module names the work (``lm_head_loss``, ``exits``, ``ssd``,
``optimizer``, ``grad_norm``, ``accum``). A device trace
names its events by instruction (``%fusion.24 = ...``), a name the compiler
hands out anew at every change to the step; this file turns that name into
one of a few stable labels, so that "what does the head cost a step" has an
answer before and after a change.

One part is split one level down, into *stages* (``_STAGE``, ``stage_of``,
``op_stages``): ``moe_route``, by the scopes models/experts.py and ops/moe.py
open inside it where each stage's work is written. A stage names no part:
``part_of`` reads a path as it did before there were any.

Stdlib only. The trainer leaves a *provider* here (``set_provider``): a
callable that lowers its train step again, on demand, and returns
``op_maps`` of the executable's text. It holds what a lowering needs: the
jitted step, whose function is the trainer's own method (so the trainer
object with its model, optimizer and mesh, none of which holds an array),
and the step's abstract operands; no state, no loader and no batch. So the
map can be had after the trainer's owner has let go of all three (a
benchmark reads it after its window, never in it). There is one provider a
process, as there is one ``process_tracer()``: the train step built last.
"""

from __future__ import annotations

import re
from typing import Callable, Dict, Optional, Tuple

# The parts, in the order a report lists them. Ops whose path names none
# (parameters, copies the compiler added, the accumulation's own adds) are
# ``UNSCOPED``.
PARTS = ("attn", "mlp", "ln", "embed", "lm_head_loss", "optimizer",
         "grad_norm",
         # models/afmoe.py: its attention modules are named by their kind
         # (nothing of it falls under "attn"), its expert layer by stage.
         "attn_sliding", "attn_full", "moe_route", "moe_experts",
         "moe_shared",
         # models/lfm2.py: the gated short convolution's two projections,
         # and its gates and taps (ops/short_conv.py, whatever implements
         # them); its attention is an "attn_full", its experts as above.
         "conv", "conv_mix",
         # models/deepseek_v3.py: latent attention (projections and the
         # %attn_mla kernels), and inside it the latent's norm with the
         # rotary positions of the 64-lane parts; its experts as above.
         "attn_mla", "mla_prep",
         # models/ouro.py: the exits of its looped stack (the heads with
         # their cross entropies, the exit gate, the expected loss and the
         # entropy); its attention is an "attn_full".
         "exits",
         # models/granite.py: a Mamba-2 layer's projections, taps, dt and
         # gated norm, and inside it the scan (ops/ssd.py, whatever
         # implements it); its attention is an "attn_full".
         "mamba", "ssd")
UNSCOPED = "unscoped"

# Path component -> part. ``wte.attend`` is the tied head's matmul where the
# model computes full logits itself; the chunked losses compute it under the
# trainer's ``lm_head_loss`` scope.
_COMPONENT = {
    "attn": "attn", "mlp": "mlp",
    "ln_1": "ln", "ln_2": "ln", "ln_f": "ln",
    "wte": "embed", "wpe": "embed",
    "wte.attend": "lm_head_loss", "lm_head_loss": "lm_head_loss",
    "optimizer": "optimizer", "grad_norm": "grad_norm",
    # models/afmoe.py. Its q_norm / k_norm with the rotary positions (the
    # ``qk_prep`` kernel's scope, or the XLA path's ops) and its output gate
    # name no part of their own and ride with the attention module's.
    "attn_sliding": "attn_sliding", "attn_full": "attn_full",
    "moe_route": "moe_route", "moe_experts": "moe_experts",
    "moe_shared": "moe_shared",
    "ln_in": "ln", "ln_post_attn": "ln", "ln_pre_mlp": "ln",
    "ln_post_mlp": "ln", "lm_head": "lm_head_loss",
    # models/lfm2.py. The named scope ``conv_mix`` lies inside the module
    # ``conv``, as ``moe_experts`` inside ``moe_route``.
    "conv": "conv", "conv_mix": "conv_mix",
    "operator_norm": "ln", "ffn_norm": "ln", "embedding_norm": "ln",
    # models/deepseek_v3.py. The named scope ``mla_prep`` lies inside the
    # module ``attn_mla`` and holds its ``kv_a_layernorm``, which names no
    # part of its own.
    "attn_mla": "attn_mla", "mla_prep": "mla_prep",
    "input_layernorm": "ln", "post_attention_layernorm": "ln",
    "final_norm": "ln",
    # models/ouro.py. The trainer's named scope ``exits`` and the model's
    # gate module; the norms are afmoe's names.
    "exits": "exits", "exit_gate": "exits",
    # models/granite.py. The named scope ``ssd`` lies inside the module
    # ``mamba``, whose gated norm (``norm``) names no part of its own; the
    # norms are deepseek_v3's names.
    "mamba": "mamba", "ssd": "ssd",
}

# Scope name -> the part it splits: the stages of ``moe_route``, each a
# ``jax.named_scope`` inside the part's own, forward and backward. The
# stage's label is the scope's name. None of them is a key of _COMPONENT.
#   route_router      models/experts.route: the float32 matmul, sigmoid,
#                     top-k, selected_scores both ways, norm and scale
#   route_plan        ops/moe.plan_pairs and every chunk_plan, in the forward
#                     walk and in the backward's
#   route_dispatch    ops/moe.dispatch (x[row_token] + select, and its
#                     recompute), _dispatch_bwd (rows back to tokens)
#   route_combine     ops/moe.combine (weighted rows to tokens), _combine_bwd
#   route_weights     the held expert matrices' casts to the compute type and
#                     back; their gradients' float32 sums over chunks
#   route_accumulate  the walk's own: the sum over chunks, the counters, the
#                     tokens' cast in and out, dx's and dw's float32 sums
STAGES = ("route_router", "route_plan", "route_dispatch", "route_combine",
          "route_weights", "route_accumulate")
_STAGE = dict.fromkeys(STAGES, "moe_route")
# An instruction of a staged part that no stage claims (the compiler's own:
# a fusion merged across stages votes, a copy follows its operand).
UNSTAGED = "unstaged"

# `%fusion.24 = f32[...] fusion(...), ..., metadata={... op_name="..." ...}`;
# the text of a compiled module prints the `%`, a lowered one may not.
_INSTRUCTION = re.compile(r"^\s*(?:ROOT\s+)?%?([\w.\-]+)\s*=\s")
_OP_NAME = re.compile(r'op_name="((?:[^"\\]|\\.)*)"')
_CALLS = re.compile(r"(?:calls|to_apply|body)=%?([\w.\-]+)")
_OPERAND = re.compile(r"%([\w.\-]+)")
_COMPUTATION = re.compile(r"^\s*(?:ENTRY\s+)?%?([\w.\-]+)\s*\(.*\)\s*->.*\{\s*$")
# jvp(GPT) -> GPT, transpose(jvp(lm_head_loss)) -> lm_head_loss, jit(f) -> f.
_WRAPPER = re.compile(r"^[A-Za-z_][\w.]*\((.*)\)$")


def _unwrap(component: str) -> str:
    while True:
        m = _WRAPPER.match(component)
        if m is None:
            return component
        component = m.group(1)


def part_of(op_name: str) -> str:
    """The innermost component of a scope path that names a part. Wrappers
    (``jvp(``, ``transpose(``, ``jit(``) are stripped first and never decide:
    ``transpose(jvp(GPT))/h_0/mlp/c_fc/dot_general`` is ``mlp``."""
    # Several paths joined by ';' (ops the compiler merged): the first
    # that names a part.
    for path in op_name.split(";"):
        for component in reversed(path.split("/")):
            part = _COMPONENT.get(_unwrap(component))
            if part is not None:
                return part
    return UNSCOPED


def stage_of(op_name: str) -> Optional[str]:
    """The innermost component of a scope path that names a stage, where
    ``part_of`` gives the part that stage splits; else None: under
    ``moe_route/route_accumulate/while/body/moe_experts`` the part is
    ``moe_experts``, which has no stages."""
    for path in op_name.split(";"):
        for component in reversed(path.split("/")):
            stage = _unwrap(component)
            if stage in _STAGE:
                return stage if part_of(op_name) == _STAGE[stage] else None
    return None


def _walk(hlo_text: str):
    """One pass over a module's text: every instruction's scope path (None
    where it has none), nested computations included; which computation a
    fusion, call or loop runs; each computation's instructions; the operands
    of the instructions without a path."""
    paths: Dict[str, Optional[str]] = {}
    calls: Dict[str, str] = {}            # instruction -> computation called
    members: Dict[str, list] = {}         # computation -> its instructions
    pathless: Dict[str, list] = {}        # instruction -> its operands
    computation = None
    for line in hlo_text.splitlines():
        head = _COMPUTATION.match(line)
        if head is not None:
            computation = head.group(1)
            members[computation] = []
            continue
        m = _INSTRUCTION.match(line)
        if m is None:
            continue
        name = m.group(1)
        op = _OP_NAME.search(line)
        paths[name] = op.group(1) if op else None
        if op is None:
            pathless[name] = _OPERAND.findall(line[m.end():])
        if computation is not None:
            members[computation].append(name)
        called = _CALLS.search(line)
        if called is not None:
            calls[name] = called.group(1)
    return paths, calls, members, pathless


def _labels(walk, label_of: Callable[[str], Optional[str]],
            none: Optional[str]) -> Dict[str, Optional[str]]:
    """{instruction: label_of(its path)}, ``none`` where the path names no
    label. A fusion or call left with ``none`` (its root is a cast or an add
    in a block's own scope, say) takes the label most of its computation's
    instructions have; an instruction the compiler made, with no path at all
    (the CPU backend's rewritten dots), that of its first operand that has
    one."""
    paths, calls, members, pathless = walk
    # a step's instructions share their paths ten to one: read each once
    of_path = {path: label_of(path) for path in set(paths.values())
               if path is not None}
    labels = {name: of_path.get(path, none) for name, path in paths.items()}
    for name, called in calls.items():
        if labels[name] != none:
            continue
        votes: Dict[str, int] = {}
        for inner in members.get(called, ()):
            if labels[inner] != none:
                votes[labels[inner]] = votes.get(labels[inner], 0) + 1
        if votes:
            labels[name] = max(votes, key=votes.get)
    # Text order defines an operand before its user, so one pass carries a
    # label along a chain of such instructions.
    for name, operands in pathless.items():
        if labels[name] == none:
            labels[name] = next((labels[o] for o in operands
                                 if labels.get(o, none) != none), none)
    return labels


def op_maps(hlo_text: str) -> Tuple[Dict[str, str], Dict[str, str]]:
    """(``op_parts``, ``op_stages``) of a module's text, from one walk of
    it."""
    walk = _walk(hlo_text)
    parts = _labels(walk, part_of, UNSCOPED)
    found = _labels(walk, stage_of, None)
    staged = set(_STAGE.values())
    stages = {}
    for name, part in parts.items():
        if part in staged:       # another part's stage is none of this one's
            stage = found[name]
            stages[name] = stage if _STAGE.get(stage) == part else UNSTAGED
    return parts, stages


def op_parts(hlo_text: str) -> Dict[str, str]:
    """{instruction name: part} for every instruction of a module's text,
    nested computations included (a trace shows the ops of a loop's body by
    their own names), by ``_labels``' rules for a fusion whose own path
    names no part and for an instruction with no path."""
    return _labels(_walk(hlo_text), part_of, UNSCOPED)


def op_stages(hlo_text: str) -> Dict[str, str]:
    """{instruction name: stage} for the instructions whose part has stages
    (the keys are exactly those ``op_parts`` gives ``moe_route``), by the
    same rules; ``UNSTAGED`` where they find none, or one of another part."""
    return op_maps(hlo_text)[1]


# -- the train step's map, on demand ------------------------------------------

Maps = Tuple[Dict[str, str], Dict[str, str]]
_provider: Optional[Callable[[], Maps]] = None
_cached: Optional[Maps] = None


def set_provider(provider: Optional[Callable[[], Maps]]) -> None:
    """Replaces the one before it, and its maps; None leaves none."""
    global _provider, _cached
    _provider, _cached = provider, None


def _step_maps() -> Optional[Maps]:
    global _cached
    if _cached is None and _provider is not None:
        _cached = _provider()
    return _cached


def step_parts() -> Optional[Dict[str, str]]:
    """``op_parts`` of the last train step a trainer built in this process
    (made at the first call of this or of ``step_stages``, from one
    lowering, then kept); None where no trainer left a provider."""
    maps = _step_maps()
    return None if maps is None else maps[0]


def step_stages() -> Optional[Dict[str, str]]:
    """``op_stages`` of the same step, out of the same lowering."""
    maps = _step_maps()
    return None if maps is None else maps[1]
