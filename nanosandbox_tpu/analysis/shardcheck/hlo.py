"""Post-partitioning HLO text parser: collectives, bytes, groups.

shardcheck reads the OPTIMIZED HLO of a compiled program
(``jax.jit(f).lower(...).compile().as_text()``) because that is the only
layer where XLA's SPMD partitioner has already made its communication
decisions — the StableHLO a ``lower()`` emits still carries abstract
``sharding`` annotations, not the all-gathers GSPMD will insert for a
missing or inconsistent one. Parsing is line-oriented and deliberately
jax-free (plain ``re``/stdlib): the unit tests pin the grammar against
literal instruction lines, so an XLA text-format drift breaks a fast
pure-Python test instead of a compile-heavy integration run.

Grammar covered (the forms XLA:CPU/TPU emit today):

  %ag = f32[8,64]{1,0} all-gather(f32[8,32]{1,0} %p), channel_id=1,
        replica_groups={{0,2},{1,3}}, dimensions={2}, ...
  %ar = f32[] all-reduce(f32[] %x), replica_groups=[4,2]<=[8], ...
  %rs = f32[4,8]{1,0} reduce-scatter(...), replica_groups=[2,4]<=[4,2]T(1,0)
  %cp = f32[8]{0} collective-permute(...), source_target_pairs={{0,1},{1,0}}
  %aa = (f32[...], f32[...]) all-to-all(f32[...] %a, f32[...] %b), ...

``replica_groups`` comes in two spellings: explicit nested braces, and
the iota form ``[G,S]<=[d0,d1,...]`` with an optional transpose
``T(p...)`` — reshape iota(prod(d)) to ``d``, transpose by ``p``,
flatten, then reshape to (G, S) rows. Async pairs (``all-gather-start``
/ ``-done``) count once, on the start.
"""

from __future__ import annotations

import math
import re
from dataclasses import dataclass, field
from typing import Dict, FrozenSet, List, Optional, Tuple

COLLECTIVE_KINDS = ("all-gather", "all-reduce", "reduce-scatter",
                    "collective-permute", "all-to-all")

# dtype -> itemsize in bytes (sub-byte types round up to 1).
_ITEMSIZE = {
    "pred": 1, "s2": 1, "u2": 1, "s4": 1, "u4": 1,
    "s8": 1, "u8": 1, "f8e4m3fn": 1, "f8e5m2": 1, "f8e4m3": 1,
    "f8e3m4": 1, "f8e4m3b11fnuz": 1, "f8e5m2fnuz": 1, "f8e4m3fnuz": 1,
    "s16": 2, "u16": 2, "f16": 2, "bf16": 2,
    "s32": 4, "u32": 4, "f32": 4,
    "s64": 8, "u64": 8, "f64": 8, "c64": 8,
    "c128": 16,
}

_SHAPE_RE = re.compile(r"\b([a-z][a-z0-9]*)\[([0-9,]*)\]")
_INSTR_RE = re.compile(
    r"^\s*(?:ROOT\s+)?%(?P<name>[\w.\-]+)\s*=\s*(?P<rest>.*)$")
_KIND_RE = re.compile(
    r"\b(?P<kind>" + "|".join(COLLECTIVE_KINDS) + r")(?P<async>-start)?\(")
_GROUPS_EXPLICIT_RE = re.compile(r"replica_groups=\{(\{[0-9,{} ]*\})\}")
_GROUPS_IOTA_RE = re.compile(
    r"replica_groups=\[(\d+),(\d+)\]<=\[([0-9,]+)\](?:T\(([0-9,]+)\))?")
_PAIRS_RE = re.compile(r"source_target_pairs=\{(\{[0-9,{} ]*\})\}")
# A parameter definition has no parens before "parameter(N)" — this
# cannot match a collective line or a metadata op_name string (both put
# parens/quotes first).
_PARAM_RE = re.compile(r"^[^()\"]*\bparameter\((\d+)\)")
_OPERAND_NAME_RE = re.compile(r"%([\w.\-]+)")
# The opcode's opening paren: everything before it is the result shape.
_OPCODE_RE = re.compile(r"\s[a-z][\w\-]*\(")


@dataclass
class Collective:
    """One collective instruction in the optimized HLO."""
    kind: str
    name: str
    bytes_in: int                 # summed operand tensor bytes
    bytes_out: int                # summed result tensor bytes
    groups: Optional[FrozenSet[FrozenSet[int]]] = None   # replica groups
    pairs: Tuple[Tuple[int, int], ...] = ()              # permute pairs
    operand_params: Tuple[int, ...] = ()   # parameter numbers fed directly
    line: str = ""

    @property
    def bytes_moved(self) -> int:
        """The materialized-tensor convention the budgets pin: a gather
        is charged its (larger) result, everything else its operand —
        a stable ratchet quantity, not a link-level byte count."""
        if self.kind in ("all-gather", "all-to-all"):
            return max(self.bytes_out, self.bytes_in)
        return self.bytes_in


@dataclass
class HloCollectives:
    collectives: List[Collective] = field(default_factory=list)
    # parameter-instruction name -> parameter(N) index, for the
    # donation-boundary rule.
    params: Dict[str, int] = field(default_factory=dict)


def _shape_bytes(text: str) -> int:
    """Summed byte size of every ``dtype[dims]`` shape token in text."""
    total = 0
    for dtype, dims in _SHAPE_RE.findall(text):
        if dtype not in _ITEMSIZE:
            continue           # token/tuple/opaque
        n = 1
        if dims:
            n = math.prod(int(d) for d in dims.split(","))
        total += n * _ITEMSIZE[dtype]
    return total


def parse_replica_groups(attrs: str) -> Optional[FrozenSet[FrozenSet[int]]]:
    m = _GROUPS_EXPLICIT_RE.search(attrs)
    if m:
        groups = []
        for grp in re.findall(r"\{([0-9, ]*)\}", m.group(1)):
            ids = [int(x) for x in grp.replace(" ", "").split(",") if x]
            if ids:
                groups.append(frozenset(ids))
        return frozenset(groups) if groups else None
    m = _GROUPS_IOTA_RE.search(attrs)
    if m:
        n_groups, group_size = int(m.group(1)), int(m.group(2))
        dims = [int(d) for d in m.group(3).split(",")]
        total = math.prod(dims)
        ids = list(range(total))
        if m.group(4):
            perm = [int(p) for p in m.group(4).split(",")]
            # reshape to dims, transpose by perm, flatten — index math
            # without numpy (this module stays stdlib-pure).
            strides = [0] * len(dims)
            acc = 1
            for i in range(len(dims) - 1, -1, -1):
                strides[i] = acc
                acc *= dims[i]
            tdims = [dims[p] for p in perm]
            tstrides = [strides[p] for p in perm]
            out = []
            idx = [0] * len(tdims)
            for _ in range(total):
                out.append(sum(i * s for i, s in zip(idx, tstrides)))
                for ax in range(len(tdims) - 1, -1, -1):
                    idx[ax] += 1
                    if idx[ax] < tdims[ax]:
                        break
                    idx[ax] = 0
            ids = out
        if n_groups * group_size != total:
            return None
        return frozenset(
            frozenset(ids[g * group_size:(g + 1) * group_size])
            for g in range(n_groups))
    return None


def parse_permute_pairs(attrs: str) -> Tuple[Tuple[int, int], ...]:
    m = _PAIRS_RE.search(attrs)
    if not m:
        return ()
    pairs = []
    for grp in re.findall(r"\{([0-9, ]*)\}", m.group(1)):
        ids = [int(x) for x in grp.replace(" ", "").split(",") if x]
        if len(ids) == 2:
            pairs.append((ids[0], ids[1]))
    return tuple(pairs)


def _split_operands(rest: str, open_idx: int) -> Tuple[str, str]:
    """(operand text, trailing attrs) by paren balance from open_idx."""
    depth = 0
    for i in range(open_idx, len(rest)):
        if rest[i] == "(":
            depth += 1
        elif rest[i] == ")":
            depth -= 1
            if depth == 0:
                return rest[open_idx + 1:i], rest[i + 1:]
    return rest[open_idx + 1:], ""


def parse_hlo_collectives(text: str) -> HloCollectives:
    out = HloCollectives()
    # name -> bytes of the value that instruction defines. Newer XLA
    # prints operands by NAME only (``all-reduce(%wrapped_reduce)``, no
    # inline ``f32[128]{0}``), so operand bytes come from the defining
    # instruction's result shape; older text carries the shapes inline
    # and never consults this map.
    defined: Dict[str, int] = {}
    for line in text.splitlines():
        im = _INSTR_RE.match(line)
        if im is None:
            continue
        rest = im.group("rest")
        op_open = _OPCODE_RE.search(rest)
        defined[im.group("name")] = _shape_bytes(
            rest[:op_open.start()] if op_open else rest)
        pm = _PARAM_RE.match(rest)
        if pm is not None:
            out.params[im.group("name")] = int(pm.group(1))
            continue
        km = _KIND_RE.search(rest)
        if km is None or rest[:km.start()].count('"') % 2:
            continue           # kind name inside a metadata string
        if f"{km.group('kind')}-done(" in rest:
            continue           # async completion: counted at -start
        result_text = rest[:km.start()]
        operands, attrs = _split_operands(rest, km.end() - 1)
        bytes_out = _shape_bytes(result_text)
        if km.group("async"):
            # An async start returns a tuple whose FIRST element echoes
            # the operand buffer (all-gather-start: (input, output);
            # permute-start adds u32 context scalars) — summing the
            # tuple would charge the operand twice and break the
            # full-input-gather byte match. The true result is the
            # second tuple element.
            shapes = _SHAPE_RE.findall(result_text)
            if len(shapes) >= 2:
                dtype, dims = shapes[1]
                if dtype in _ITEMSIZE:
                    n = (math.prod(int(d) for d in dims.split(","))
                         if dims else 1)
                    bytes_out = n * _ITEMSIZE[dtype]
        out.collectives.append(Collective(
            kind=km.group("kind"),
            name=im.group("name"),
            bytes_in=(_shape_bytes(operands) if _SHAPE_RE.search(operands)
                      else sum(defined.get(n, 0) for n in
                               _OPERAND_NAME_RE.findall(operands))),
            bytes_out=bytes_out,
            groups=parse_replica_groups(attrs),
            pairs=parse_permute_pairs(attrs),
            operand_params=tuple(
                out.params[n] for n in _OPERAND_NAME_RE.findall(operands)
                if n in out.params),
            line=line.strip()))
    return out
