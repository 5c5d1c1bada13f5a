"""host-sync and impure-trace: the hot loop must not talk to the host.

host-sync — the ROADMAP's "as fast as the hardware allows" dies the
first time a ``.item()`` / ``float()`` / ``np.asarray`` sneaks into the
decode or train hot loop: under JAX async dispatch each readback is a
host<->device sync that drains the queue and serializes the host with
device compute. The rule fires inside jit-traced code
AND inside the host functions that drive compiled programs (the
jitscope dispatcher set). Deliberate syncs go through the blessed
``utils.tracecheck.host_sync`` wrapper (which this rule recognizes and
counts at runtime) or carry a reasoned
``# jaxlint: disable=host-sync -- <why>``.

impure-trace — a jit-traced function's body replays once per compile,
not once per call: ``np.random``/``time`` reads bake one trace-time
value into the program forever, and mutation of ``self``/globals counts
retraces, not steps (the exact bug class the engine's old hand-rolled
``self.trace_counts[...] += 1`` counters exploited deliberately — now
owned by ``utils.tracecheck.compile_budget`` OUTSIDE the traced body).
"""

from __future__ import annotations

import ast
from typing import Iterable, List

from nanosandbox_tpu.analysis.core import (Finding, ModuleContext, Rule,
                                           register)
from nanosandbox_tpu.analysis.jitscope import (DeviceTracker, dotted_name,
                                               walk_body)

_HOST_SYNC_CALLS = {"jax.device_get", "np.asarray", "numpy.asarray"}
_IMPURE_PREFIXES = ("np.random.", "numpy.random.", "random.")
_IMPURE_EXACT = {
    "time.time", "time.monotonic", "time.perf_counter", "time.sleep",
    "time.time_ns", "time.process_time", "datetime.datetime.now",
}
_MUTATORS = {"append", "add", "extend", "update", "pop", "setdefault",
             "remove", "insert", "clear", "appendleft", "popleft", "write"}


def _is_blessed(name: str) -> bool:
    """utils.tracecheck APIs are the sanctioned way to sync/count."""
    return "tracecheck" in name or name.split(".")[-1] == "host_sync"


@register
class HostSyncRule(Rule):
    id = "host-sync"
    doc = (".item()/float()/int()/np.asarray/jax.device_get/print on "
           "device values in jit-traced code or in the host loops that "
           "drive compiled programs")

    def check(self, ctx: ModuleContext) -> Iterable[Finding]:
        idx = ctx.index
        out: List[Finding] = []
        for qual in sorted(idx.hot_scope() & set(idx.functions)):
            info = idx.functions[qual]
            tracker = DeviceTracker(info, idx)
            traced = qual in idx.traced
            where = ("jit-traced code" if traced
                     else "a hot path driving compiled programs")
            for node in walk_body(info.node):
                if not isinstance(node, ast.Call):
                    continue
                name = dotted_name(node.func) or ""
                if name and _is_blessed(name):
                    continue
                msg = None
                if (isinstance(node.func, ast.Attribute)
                        and node.func.attr == "item" and not node.args):
                    msg = (f".item() in {where} ({qual}) forces a "
                           "device->host readback")
                elif name in _HOST_SYNC_CALLS:
                    msg = (f"{name}() in {where} ({qual}) forces a "
                           "device->host readback (route deliberate "
                           "syncs through utils.tracecheck.host_sync)")
                elif (isinstance(node.func, ast.Name)
                      and node.func.id in ("float", "int") and node.args
                      and tracker.is_device(node.args[0])):
                    msg = (f"{node.func.id}() on a device value in "
                           f"{where} ({qual}) blocks on the async "
                           "dispatch queue (route deliberate syncs "
                           "through utils.tracecheck.host_sync)")
                elif (isinstance(node.func, ast.Name)
                      and node.func.id == "bool" and not traced
                      and node.args and tracker.is_device(node.args[0])):
                    msg = (f"bool() on a device value in {where} "
                           f"({qual}) forces a device->host readback")
                elif (isinstance(node.func, ast.Name)
                      and node.func.id == "print"
                      and any(tracker.is_device(a) for a in node.args)):
                    msg = (f"print() of a device value in {where} "
                           f"({qual}) forces a device->host readback")
                if msg:
                    out.append(Finding(ctx.path, node.lineno,
                                       node.col_offset, self.id, msg))
        return out


@register
class ImpureTraceRule(Rule):
    id = "impure-trace"
    doc = ("np.random/time reads and self/global mutation inside "
           "jit-traced functions (side effects replay per trace, "
           "values freeze at trace time)")

    def check(self, ctx: ModuleContext) -> Iterable[Finding]:
        idx = ctx.index
        out: List[Finding] = []
        module_globals = {
            t.id for stmt in ctx.tree.body if isinstance(stmt, ast.Assign)
            for t in stmt.targets if isinstance(t, ast.Name)
        }
        for qual in sorted(idx.traced & set(idx.functions)):
            info = idx.functions[qual]
            for node in walk_body(info.node):
                if isinstance(node, (ast.Global, ast.Nonlocal)):
                    out.append(Finding(
                        ctx.path, node.lineno, node.col_offset, self.id,
                        f"{type(node).__name__.lower()} statement in "
                        f"jit-traced {qual}: the rebind happens once per "
                        "trace, not once per call"))
                elif isinstance(node, ast.Call):
                    name = dotted_name(node.func) or ""
                    if (name.startswith(_IMPURE_PREFIXES)
                            or name in _IMPURE_EXACT):
                        out.append(Finding(
                            ctx.path, node.lineno, node.col_offset,
                            self.id,
                            f"{name}() inside jit-traced {qual}: the "
                            "value is baked in at trace time (use "
                            "jax.random / pass times in as operands)"))
                elif isinstance(node, ast.Expr) \
                        and isinstance(node.value, ast.Call):
                    # Mutator heuristic fires only on BARE statement
                    # calls: `self.seen.append(x)` is a side effect,
                    # while `a, b = self.tx.update(...)` is functional
                    # (optax) and must not match.
                    call = node.value
                    if (isinstance(call.func, ast.Attribute)
                            and call.func.attr in _MUTATORS):
                        recv = dotted_name(call.func.value) or ""
                        if (recv.startswith("self.")
                                or recv.split(".")[0] in module_globals):
                            out.append(Finding(
                                ctx.path, call.lineno, call.col_offset,
                                self.id,
                                f"mutation of {recv} inside jit-traced "
                                f"{qual} runs once per RETRACE, not per "
                                "call (use utils.tracecheck for trace "
                                "counting; thread state functionally)"))
                elif isinstance(node, (ast.Assign, ast.AugAssign)):
                    targets = (node.targets
                               if isinstance(node, ast.Assign)
                               else [node.target])
                    for t in targets:
                        root = t
                        while isinstance(root, (ast.Subscript,
                                                ast.Attribute)):
                            if (isinstance(root, ast.Attribute)
                                    and dotted_name(root) is not None
                                    and dotted_name(root)
                                    .startswith("self.")):
                                out.append(Finding(
                                    ctx.path, node.lineno,
                                    node.col_offset, self.id,
                                    f"assignment to {dotted_name(root)} "
                                    f"inside jit-traced {qual} mutates "
                                    "host state once per RETRACE (use "
                                    "utils.tracecheck.compile_budget "
                                    "for trace counting)"))
                                break
                            root = root.value
        return out
