"""Readers of the ``deepseek_v3`` cell's per-layer metrics.

Seven of the nine are accepted readers under this module's name: the files
say ``dsv3:`` and read through them unchanged (``tests/test_program_readers.py``
pins the count of files that say ``program:``). ``reducers/afmoe.py``'s
grouped-matmul reader knows shapes and no family and takes the runner's
counted rows. The two this cell brings read the latent kernels: their share
of their roofline, and their milliseconds beside the rest of the attention
part's.
"""

from __future__ import annotations

from chipbench import flops_dsv3
from chipbench.reducers.afmoe import (_kernel_seconds, _share,  # noqa: F401
                                      balance_bias_s, gmm_roofline_pct,
                                      moe_load_max_over_mean)
from chipbench.reducers.program import device_ms_of_parts  # noqa: F401


def _mla_kernels(run: dict, metric: dict):
    """(one layer's required cost times the layers, (seconds, events) a step
    of the calls matching ``params.pattern``), or None: a record without the
    family's sizes, a trace without the kernels."""
    rec = run["record"]
    sizes = rec.get("sizes") or {}
    if "kv_lora_rank" not in sizes:
        return None
    got = _kernel_seconds(run, metric, metric["params"]["pattern"])
    if got is None:
        return None
    one = flops_dsv3.mla_attention_cost(sizes,
                                        rec["batch_rows"] // rec["chips"])
    return {k: sizes["n_layer"] * v for k, v in one.items()}, got


def mla_roofline_pct(run: dict, metric: dict):
    """Least time for the REQUIRED operations and bytes of every layer's
    latent-attention kernels, forward + backward
    (``flops_dsv3.mla_attention_cost``: causal pairs, 2 * (192 + 128) forward
    and 2 * (2 * 192 + 2 * 128) backward a pair and head; neither the
    recomputed score nor padded lanes), over the summed device time a step of
    the ``%attn_mla.N`` calls."""
    found = _mla_kernels(run, metric)
    if found is None:
        return None
    cost, (seconds, events) = found
    whole = _share(cost, seconds, run["peaks"])
    return whole["pct"], {"bound": whole["bound"],
                          "kernel_ms_per_step": whole["kernel_ms_per_step"],
                          "events_per_step": events}


def attention_ms(run: dict, metric: dict):
    """``device_ms_of_parts`` of the attention parts (``attn_mla``: the
    projections and the kernels; ``mla_prep``: the latent's norm and the
    rotary positions), with the kernels' milliseconds a step and what is left
    of the parts beside them."""
    got = device_ms_of_parts(run, metric)
    if got is None:
        return None
    value, extra = got
    found = _mla_kernels(run, metric)
    if found is not None:
        kernels = found[1][0] * 1e3
        extra = {**extra, "kernels_ms": kernels,
                 "beside_kernels_ms": value - kernels}
    return value, extra
