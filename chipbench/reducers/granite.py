"""Readers of the ``granite`` cell's per-layer metrics.

Its five ``dev_ms_granite_*`` read through the accepted ``device_ms_of_parts``
under this module's name (``tests/test_program_readers.py`` pins the count of
files that say ``program:``); the scan's one adds what the program's
``granite_ssd`` instants say of the scan. The scan's share of its roofline
takes ``flops_granite.ssd_cost`` (the same work whatever implements it) over
the device time of the part ``ssd``. The one attention layer's flash calls
(``%attn_full.N``) are read by the accepted ``afmoe`` reader at the family's
H, G, D, as LFM2's are. A record without the family's sizes (a parent's), a
trace without the kernels or a program without the part gives nothing to
read: None.
"""

from __future__ import annotations

from chipbench import flops_granite
from chipbench.reducers.afmoe import attention_roofline_pct  # noqa: F401
from chipbench.reducers.program import (_device_ms_by_part,  # noqa: F401
                                        device_ms_of_parts, program_side)

INSTANT = "granite_ssd"
SSD_STATS = ("ssd_decay", "ssd_state_max")


def _sizes(run: dict):
    sizes = run["record"].get("sizes") or {}
    return sizes if "mamba_d_state" in sizes else None


def ssd_ms(run: dict, metric: dict):
    """``device_ms_of_parts`` of the part ``ssd``, with the mean over the
    program's ``granite_ssd`` instants of each Mamba layer's ``ssd_decay``
    and ``ssd_state_max``, and how many instants there were."""
    got = device_ms_of_parts(run, metric)
    if got is None:
        return None
    value, extra = got
    seen = [s.args for s in program_side(run)["spans"] if s.name == INSTANT]
    if seen:
        n = len(seen)
        for key in SSD_STATS:
            extra[key] = [sum(a[key][i] for a in seen) / n
                          for i in range(len(seen[0][key]))]
        extra["instants"] = n
    return value, extra


def ssd_roofline_pct(run: dict, metric: dict):
    """Least time for the required operations and bytes of every Mamba
    layer's scan, forward and backward (``flops_granite.ssd_cost``), over
    the device time a step of the part ``params.part``."""
    sizes = _sizes(run)
    by_part = _device_ms_by_part(run)
    if sizes is None or by_part is None or run["peaks"] is None:
        return None
    ms, ops = by_part.get(metric["params"]["part"], (0.0, 0.0))
    layers = sizes["layer_types"].count("mamba")
    if not ms or not layers:
        return None
    rec = run["record"]
    tokens = rec["batch_rows"] // rec["chips"] * sizes["block_size"]
    one = flops_granite.ssd_cost(sizes, tokens)
    least = flops_granite.least_seconds(
        {k: layers * v for k, v in one.items()}, run["peaks"])
    return 100.0 * least["seconds"] / (ms / 1e3), {
        "bound": least["bound"], "device_ms_per_step": ms,
        "least_ms_per_step": least["seconds"] * 1e3, "ops_per_step": ops,
        "mamba_layers": layers}
