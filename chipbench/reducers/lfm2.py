"""Readers of the ``lfm2`` cell's per-layer metrics.

Six of the ten are accepted readers under this module's name: the files say
``lfm2:`` and read through them unchanged (``tests/test_program_readers.py``
pins the count of files that say ``program:``). ``reducers/afmoe.py``'s
kernel readers know shapes and patterns and no family: the flash calls'
roofline takes ``flops_afmoe.attention_cost`` at (H, G, D, T, no window) and
the kinds its file's ``patterns`` name; the grouped matmuls' takes the
runner's counted rows. The one reader this cell brings is the gated short
convolution's share of its memory roofline.
"""

from __future__ import annotations

from chipbench import flops_lfm2
from chipbench.reducers.afmoe import (attention_roofline_pct,  # noqa: F401
                                      balance_bias_s, gmm_roofline_pct,
                                      moe_load_max_over_mean)
from chipbench.reducers.program import (_device_ms_by_part,  # noqa: F401
                                        device_ms_of_parts)


def conv_mix_roofline_pct(run: dict, metric: dict):
    """Least time for the REQUIRED bytes (and operations) of every conv
    layer's gates and taps, forward + backward with no recomputation
    (``flops_lfm2.conv_mix_cost``), over the device time a step of the ops
    under the program's scope ``conv_mix`` (``params.part`` in the step's
    map of parts): the same work whatever implements it. A program without
    the scope, or a record without the family's sizes, gives nothing to
    read."""
    rec = run["record"]
    sizes = rec.get("sizes") or {}
    by_part = _device_ms_by_part(run)
    if by_part is None or run["peaks"] is None or "conv_L_cache" not in sizes:
        return None
    ms, ops = by_part.get(metric["params"]["part"], (0.0, 0.0))
    layers = sizes["layer_types"].count("conv")
    if not ms or not layers:
        return None
    one = flops_lfm2.conv_mix_cost(sizes, rec["batch_rows"] // rec["chips"])
    least = flops_lfm2.least_seconds(
        {k: layers * v for k, v in one.items()}, run["peaks"])
    return 100.0 * least["seconds"] / (ms / 1e3), {
        "bound": least["bound"], "device_ms_per_step": ms,
        "least_ms_per_step": least["seconds"] * 1e3, "ops_per_step": ops}
