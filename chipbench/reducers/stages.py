"""Readers of the stages inside a part of the train step: the device time of
``moe_route`` split by the scopes the program opens inside it
(``nanosandbox_tpu.obs.opscopes``: ``_STAGE``, ``step_stages``; router, plan,
dispatch, combine, the expert matrices' casts, the walk's own sums).

The stage map comes out of the same lowering as the part map the accepted
reader asks for (``program:device_ms_of_parts``), and the events are the same
ones, clipped and reduced to self times the same way: in every traced run the
stages of a part, ``unstaged`` included, sum to what that reader gives the
part. A program without ``step_stages`` (the parent of the PR that added it)
gives the reader nothing to read: it returns None and the metric is left out
of the line. Tests hand the map in as ``run["program"]["stages"]``.
"""

from __future__ import annotations

import time

from chipbench import trace
from chipbench.reducers import program

KERNEL = "%moe_rows"   # ops/moe.py's row mover: custom calls %moe_rows.N


def _stages_or_none():
    try:
        from nanosandbox_tpu.obs import opscopes
    except ImportError:
        return None
    get = getattr(opscopes, "step_stages", None)
    return get() if get is not None else None


def step_stages(run: dict):
    """The train step's stage map, and in ``side["stages_s"]`` what making it
    cost: nothing to speak of once a reader of parts has paid for the
    lowering (both maps are made from it), a lowering otherwise."""
    side = program.program_side(run)
    if "stages" not in side:
        t0 = time.perf_counter()
        side["stages"] = _stages_or_none()
        side["stages_s"] = time.perf_counter() - t0
    return side["stages"]


def _device_ms_by_stage(run: dict):
    """{stage: [ms a step, ops a step, ms a step inside KERNEL events]} of
    chip 0 in the window, kept in ``run`` with the seconds the reduction
    took; an op the map lacks (another part's, or unmapped) is in none."""
    side = program.program_side(run)
    if "stage_ms" in side:
        return side["stage_ms"]
    tr, steps = run["trace"], run["record"].get("steps")
    stages = step_stages(run)
    side["stage_ms"] = None
    if not tr.ops or not tr.ops[0] or not steps or not stages:
        return None
    t0 = time.perf_counter()
    order, own = program.self_times(trace.clip(tr.ops[0], tr.window))
    out: dict[str, list[float]] = {}
    for e, t in zip(order, own):
        stage = stages.get(e.name.split(" = ", 1)[0].strip().lstrip("%"))
        if stage is None:
            continue
        cell = out.setdefault(stage, [0.0, 0.0, 0.0])
        cell[0] += t / 1e6 / steps
        cell[1] += 1.0 / steps
        if e.name.startswith(KERNEL):
            cell[2] += t / 1e6 / steps
    side["stage_ms"], side["stage_read_s"] = out, time.perf_counter() - t0
    return out


def device_ms_of_stages(run: dict, metric: dict):
    """Device milliseconds a step of the ops whose stage (``opscopes.STAGES``
    or 'unstaged') is in ``params.stages``: self times, as
    ``program:device_ms_of_parts`` counts them. With the share of the staged
    part's device time, the ops a step, the milliseconds of it inside the
    row mover's kernels, and what the map and this reduction cost."""
    by_stage = _device_ms_by_stage(run)
    if by_stage is None:
        return None
    side = program.program_side(run)
    whole = sum(ms for ms, _, _ in by_stage.values())
    mine = [by_stage[s] for s in metric["params"]["stages"] if s in by_stage]
    value = sum(ms for ms, _, _ in mine)
    return value, {
        "share_of_part_pct": 100.0 * value / whole if whole else 0.0,
        "ops_per_step": sum(n for _, n, _ in mine),
        "kernel_ms": sum(k for _, _, k in mine),
        "map_s": side["stages_s"], "read_s": side["stage_read_s"]}
