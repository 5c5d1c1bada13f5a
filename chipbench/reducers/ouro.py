"""Readers of the ``ouro`` cell's per-layer metrics.

Its four ``dev_ms_ouro_*`` read through the accepted ``device_ms_of_parts``
under this module's name (``tests/test_program_readers.py`` pins the count of
files that say ``program:``); the exits' one adds what the program's
``ouro_exits`` instants say of the exits. The kernels' share of their
roofline takes the accepted ``flops_afmoe.attention_cost`` (a group of one
here) times the layers applied, L passes of the stack; the exits' share
takes ``flops_ouro.exits_cost``. A record without the family's sizes (a
parent's), a trace without the kernels or a program without the part gives
nothing to read: None.
"""

from __future__ import annotations

from chipbench import flops_ouro
from chipbench.reducers.afmoe import _kernel_seconds, _share
from chipbench.reducers.program import (_device_ms_by_part,  # noqa: F401
                                        device_ms_of_parts, program_side)

INSTANT = "ouro_exits"


def _sizes(run: dict):
    sizes = run["record"].get("sizes") or {}
    return sizes if "total_ut_steps" in sizes else None


def exits_ms(run: dict, metric: dict):
    """``device_ms_of_parts`` of the part ``exits``, with the mean over the
    program's ``ouro_exits`` instants of each pass's exit probability and
    each exit's cross entropy, and how many instants there were."""
    got = device_ms_of_parts(run, metric)
    if got is None:
        return None
    value, extra = got
    seen = [s.args for s in program_side(run)["spans"] if s.name == INSTANT]
    if seen:
        n = len(seen)
        for key in ("exit_p", "exit_nll"):
            extra[key] = [sum(a[key][t] for a in seen) / n
                          for t in range(len(seen[0][key]))]
        extra["instants"] = n
    return value, extra


def attention_roofline_pct(run: dict, metric: dict):
    """Least time for the required operations and bytes of every layer
    application's flash kernels (forward and backward; n_layer * L of them)
    over their summed device time a step (``params.pattern``)."""
    sizes = _sizes(run)
    if sizes is None:
        return None
    got = _kernel_seconds(run, metric, metric["params"]["pattern"])
    if got is None:
        return None
    rec = run["record"]
    one = flops_ouro.attention_cost(sizes, rec["batch_rows"] // rec["chips"],
                                    None)
    applied = sizes["n_layer"] * sizes["total_ut_steps"]
    whole = _share({k: applied * v for k, v in one.items()}, got[0],
                   run["peaks"])
    return whole["pct"], {"bound": whole["bound"],
                          "kernel_ms_per_step": whole["kernel_ms_per_step"],
                          "events_per_step": got[1],
                          "layer_applications": applied}


def exits_roofline_pct(run: dict, metric: dict):
    """Least time for the required operations of the L exits' head products
    and exit gate, forward and backward (``flops_ouro.exits_cost``: the
    chunked heads' replay not counted), over the device time a step of the
    part ``params.part``."""
    sizes = _sizes(run)
    by_part = _device_ms_by_part(run)
    if sizes is None or by_part is None or run["peaks"] is None:
        return None
    ms, ops = by_part.get(metric["params"]["part"], (0.0, 0.0))
    if not ms:
        return None
    rec = run["record"]
    tokens = rec["batch_rows"] // rec["chips"] * sizes["block_size"]
    least = flops_ouro.least_seconds(flops_ouro.exits_cost(sizes, tokens),
                                     run["peaks"])
    return 100.0 * least["seconds"] / (ms / 1e3), {
        "bound": least["bound"], "device_ms_per_step": ms,
        "least_ms_per_step": least["seconds"] * 1e3, "ops_per_step": ops}
