"""Which part of the model an instruction of a compiled step belongs to.

XLA keeps, on every instruction of a compiled program, the scope path JAX
traced it under (``metadata={op_name="jit(traced)/transpose(jvp(GPT))/h_3/
mlp/c_fc/dot_general"}``), backward pass included. Flax names the modules
(``h_3/attn/c_attn``, ``mlp``, ``ln_1``, ``ln_f``, ``wte``, ``wpe``; for
models/afmoe.py ``attn_sliding`` / ``attn_full``, ``moe_shared``, ``ln_in``
...; for models/lfm2.py ``conv``, ``attn_full``, ``operator_norm`` ...; for
models/deepseek_v3.py ``attn_mla``, ``input_layernorm`` ...) and
the trainer adds ``jax.named_scope`` where no module names the work
(``lm_head_loss``, ``optimizer``, ``grad_norm``, ``accum``). A device trace
names its events by instruction (``%fusion.24 = ...``), a name the compiler
hands out anew at every change to the step; this file turns that name into
one of a few stable labels, so that "what does the head cost a step" has an
answer before and after a change.

Stdlib only. The trainer leaves a *provider* here (``set_provider``): a
callable that lowers its train step again, on demand, and returns
``op_parts`` of the executable's text. It holds what a lowering needs: the
jitted step, whose function is the trainer's own method (so the trainer
object with its model, optimizer and mesh, none of which holds an array),
and the step's abstract operands; no state, no loader and no batch. So the
map can be had after the trainer's owner has let go of all three (a
benchmark reads it after its window, never in it). There is one provider a
process, as there is one ``process_tracer()``: the train step built last.
"""

from __future__ import annotations

import re
from typing import Callable, Dict, Optional

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
         "attn_mla", "mla_prep")
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
}

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


def op_parts(hlo_text: str) -> Dict[str, str]:
    """{instruction name: part} for every instruction of a module's text,
    nested computations included (a trace shows the ops of a loop's body by
    their own names). A fusion or call whose own path names no part (its
    root is a cast or an add in a block's own scope, say) takes the part
    most of its computation's instructions have; an instruction the
    compiler made, with no path at all (the CPU backend's rewritten dots),
    the part of its first operand that has one."""
    parts: Dict[str, str] = {}
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
        parts[name] = part_of(op.group(1)) if op else UNSCOPED
        if op is None:
            pathless[name] = _OPERAND.findall(line[m.end():])
        if computation is not None:
            members[computation].append(name)
        called = _CALLS.search(line)
        if called is not None:
            calls[name] = called.group(1)
    for name, called in calls.items():
        if parts[name] != UNSCOPED:
            continue
        votes: Dict[str, int] = {}
        for inner in members.get(called, ()):
            if parts[inner] != UNSCOPED:
                votes[parts[inner]] = votes.get(parts[inner], 0) + 1
        if votes:
            parts[name] = max(votes, key=votes.get)
    # Text order defines an operand before its user, so one pass carries a
    # part along a chain of such instructions.
    for name, operands in pathless.items():
        if parts[name] == UNSCOPED:
            parts[name] = next((parts[o] for o in operands
                                if parts.get(o, UNSCOPED) != UNSCOPED),
                               UNSCOPED)
    return parts


# -- the train step's map, on demand ------------------------------------------

_provider: Optional[Callable[[], Dict[str, str]]] = None
_cached: Optional[Dict[str, str]] = None


def set_provider(provider: Optional[Callable[[], Dict[str, str]]]) -> None:
    """Replaces the one before it, and its map; None leaves none."""
    global _provider, _cached
    _provider, _cached = provider, None


def step_parts() -> Optional[Dict[str, str]]:
    """The map of the last train step a trainer built in this process
    (made at the first call, then kept); None where no trainer left a
    provider."""
    global _cached
    if _cached is None and _provider is not None:
        _cached = _provider()
    return _cached
