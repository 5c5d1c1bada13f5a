"""Model families: ``gpt2`` (models/gpt.py), ``afmoe`` (Arcee Trinity's
block), ``lfm2`` (LiquidAI's LFM2 MoE block) and ``deepseek_v3`` (latent
attention beside routed and shared experts; Moonlight is a published
instance). ``FAMILIES`` is the one place that knows which exist:
``TrainConfig.model_family`` -> the family's module, imported on first use (a
GPT-2 run imports no other family's kernels). The MODULE is the interface:
``Trainer`` and ``restore_for_inference`` ask it, by these module-level names,
what they would otherwise ask by the family's name:

    model_config(cfg, vocab)   the model's own config from a TrainConfig
    check(cfg, pretrained)     raise for what of cfg the family cannot run
    build(model_cfg, mesh)     (model, what ``trainer_init`` records of it:
                               'attn_layout' and the family's own keys)
    apply(model, params, x, *, deterministic, return_hidden, rngs)
                               (logits or hidden, what a step reports beside
                               its loss: a dict of arrays)
    head(params)               the head's (vocab, C) table
    flops_per_token(model_cfg, T, n_params)   forward + backward, a token
    inference                  None, or what inference misses for the family
    pretrained(cfg, dataset_meta)   (cfg, params) where ``init_from`` can
                               name weights; a family without it refuses them

A new family is its module (and kernels), one line here, its ``TrainConfig``
keys and model-config class (config.py), its rows in ``opscopes._COMPONENT``.
What two families share and neither owns lies beside them: models/common.py
(every family), models/experts.py (the expert families).
"""

from __future__ import annotations

import importlib

FAMILIES = {
    "gpt2": "nanosandbox_tpu.models.gpt",
    "afmoe": "nanosandbox_tpu.models.afmoe",
    "lfm2": "nanosandbox_tpu.models.lfm2",
    "deepseek_v3": "nanosandbox_tpu.models.deepseek_v3",
}


def family_of(cfg):
    """The module of ``cfg.model_family``."""
    if cfg.model_family not in FAMILIES:
        raise ValueError(f"unknown model_family {cfg.model_family!r} "
                         f"(expected one of {tuple(FAMILIES)})")
    return importlib.import_module(FAMILIES[cfg.model_family])
