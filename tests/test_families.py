"""The seam between ``Trainer`` and the model families (models/__init__.py:
``FAMILIES``): every family answers every question ``Trainer`` and
``restore_for_inference`` ask, and neither names a family. Tiny sizes, CPU."""

import ast
import importlib
import os
import re
import subprocess
import sys

import jax
import jax.numpy as jnp
import pytest

from nanosandbox_tpu import config
from nanosandbox_tpu.models import FAMILIES, family_of
from nanosandbox_tpu.obs import process_tracer

HOOKS = ("model_config", "check", "build", "apply", "head",
         "flops_per_token", "inference")

# One tiny TrainConfig a family, the keys its `trainer_init` span carries
# beside `model_family`, and `Trainer.flops_per_iter()` of that config at
# PR 31's parent (d2d0539): the counts moved, they did not change (a family
# that came later is counted by hand in its own test file: None here).
TINY = {
    "gpt2": (dict(n_layer=2, n_head=2, n_embd=64),
             {"attn_layout"}, 372178944),
    "afmoe": (dict(n_layer=3, n_head=4, n_kv_head=2, head_dim=16, n_embd=32,
                   layer_types="sliding,full,sliding", sliding_window=16,
                   num_dense_layers=1, intermediate_size=48,
                   moe_intermediate_size=24, num_experts=8,
                   num_experts_per_tok=2, experts_held=(2, 4),
                   route_scale=2.0),
              {"attn_layout", "qk_prep", "gqa_bwd", "moe_row_mover",
               "layer_types", "experts_held"},
              152862720.0),
    "lfm2": (dict(n_layer=3, n_head=4, n_kv_head=2, head_dim=8, n_embd=32,
                  layer_types="conv,full,conv", num_dense_layers=1,
                  intermediate_size=48, moe_intermediate_size=24,
                  num_experts=8, num_experts_per_tok=2, experts_held=(2, 4)),
             {"attn_layout", "attn_route", "qk_prep", "conv_mix",
              "moe_row_mover", "gmm_tiling", "layer_types", "experts_held"},
             None),
    "deepseek_v3": (dict(n_layer=3, n_head=4, n_embd=32, kv_lora_rank=24,
                         qk_nope_head_dim=16, qk_rope_head_dim=8,
                         v_head_dim=16, num_dense_layers=1,
                         intermediate_size=48, moe_intermediate_size=24,
                         n_shared_experts=2, num_experts=8,
                         num_experts_per_tok=2, experts_held=(2, 4),
                         route_scale=2.446),
                    {"attn_layout", "attn_route", "mla_bwd", "moe_row_mover",
                     "gmm_tiling", "layer_types", "experts_held"},
                    None),
    "ouro": (dict(n_layer=2, n_head=4, n_kv_head=4, head_dim=16, n_embd=64,
                  intermediate_size=96, total_ut_steps=4),
             {"attn_layout", "attn_route", "qk_prep", "gqa_bwd", "loops",
              "layers_held", "remat_policy"},
             None),
    "granite": (dict(n_layer=3, n_head=4, n_kv_head=2, head_dim=16,
                     n_embd=64, layer_types="mamba,attention,mamba",
                     intermediate_size=96, mamba_n_heads=4, mamba_d_head=16,
                     mamba_d_state=16, mamba_chunk_size=16,
                     embedding_multiplier=12.0, residual_multiplier=0.22,
                     attention_multiplier=0.015625, logits_scaling=8.0),
                {"attn_layout", "attn_route", "ssd_impl", "ssd_chunk",
                 "conv_impl", "layer_types", "remat_policy"},
                None),
}
# sha256 of each family's train step as `built` lowers it for the compiler
# (its HLO text without metadata: scope paths and source lines are not the
# program), at PR 38's tree (1614ae0). PR 39 put `ouro` beside them, gave
# Trainer._loss_fn a branch for a family with `exit_loss` and models/loss.py
# a per-token head: the four steps are what they were, op for op. `ouro`'s
# is pinned as of 8cab71d, and `granite` came beside the five, which a new
# family leaves as they are. What the compiler receives,
# not what it returns: the same text compiles to the same program on any
# host, where the CPU's own passes may differ by machine.
STEP_SHA256 = {
    "gpt2": "d09f607a08ceb2dc34899bbeb2bb6c714fdc3eb50457033b81736b29c4986067",
    "afmoe": "cc6dede9a0095121916826a52063dd92cddaa618c90c1692ba3b8a648c609b3f",
    "lfm2": "50ae1f1f769ff459850a277cfd21443fb6ee62a1860e5552d8b6fcf23ee38053",
    "deepseek_v3":
        "8f1dab78531ca3c83a43fbe39908ece0b8f7c9b5e7cefd0002d04bb2878a9942",
    "ouro": "7d82eb737a79366e7adc7eae8ca216c5cad1319d28881e09279113f2d9193dfc",
}
# The parts a family's step has beside the ones every family has, where its
# step is not pinned above.
OWN_PARTS = {"granite": {"mamba", "ssd", "attn_full", "mlp"}}


def test_the_table_and_the_config_name_the_same_families():
    assert tuple(FAMILIES) == config.MODEL_FAMILIES
    assert set(TINY) == set(FAMILIES)


def test_an_unknown_family_is_refused_with_the_tables_keys():
    with pytest.raises(ValueError, match="unknown model_family 'llama'") as e:
        family_of(config.TrainConfig(model_family="llama"))
    assert all(repr(name) in str(e.value) for name in FAMILIES)


@pytest.mark.parametrize("name", list(FAMILIES))
def test_every_family_answers_every_question(name):
    module = family_of(config.TrainConfig(model_family=name))
    assert module is importlib.import_module(FAMILIES[name])
    missing = [h for h in HOOKS if not hasattr(module, h)]
    assert not missing, f"{FAMILIES[name]} lacks {missing}"
    assert all(callable(getattr(module, h)) for h in HOOKS[:-1])
    assert module.inference is None or isinstance(module.inference, str)


@pytest.fixture(scope="module", params=list(FAMILIES))
def built(request, char_dataset, tmp_path_factory):
    """(family's name, its tiny Trainer, the `trainer_init` span)."""
    from nanosandbox_tpu.train import Trainer

    sizes, _, _ = TINY[request.param]
    cfg = config.TrainConfig(
        model_family=request.param, data_dir=char_dataset,
        dataset="shakespeare_char", vocab_size=96, block_size=64,
        batch_size=8,
        out_dir=str(tmp_path_factory.mktemp(request.param) / "out"),
        compute_dtype="float32", dropout=0.0, tensorboard=False, seed=0,
        **sizes)
    tracer = process_tracer()
    tracer.clear()
    trainer = Trainer(cfg)
    (init,) = [s for s in tracer.spans() if s.name == "trainer_init"]
    return request.param, trainer, init


def test_trainer_init_carries_what_the_family_says_of_its_model(built):
    name, trainer, init = built
    assert init.args["model_family"] == name
    assert set(init.args) - {"model_family"} == TINY[name][1]
    assert init.args["attn_layout"] == trainer.attn_layout
    assert init.args.get("qk_prep") == trainer.qk_prep


def test_flops_per_iter_is_the_parents(built):
    name, trainer, _ = built
    if TINY[name][2] is None:
        assert trainer.flops_per_iter() > 0
    else:
        assert trainer.flops_per_iter() == TINY[name][2]


def test_head_and_apply_have_the_shapes_the_loss_takes(built):
    """One hidden state (B, T, C) for the head, or, where the family makes
    its loss of several exits (``exit_loss``), one an exit (exits, B, T, C)
    with the exits' float32 logits in the aux; the counters are int32, but
    for those the family leaves as instants (``INSTANTS``), which may be
    float32."""
    _, trainer, _ = built
    family, m = trainer.family, trainer.model_cfg
    params = jax.eval_shape(trainer._init_state, jax.random.key(0))["params"]
    assert family.head(params).shape == (m.vocab_size, m.n_embd)
    x = jax.ShapeDtypeStruct((8, 16), jnp.int32)  # a row a device
    exits = hasattr(family, "exit_loss")
    for hidden, last in ((True, m.n_embd), (False, m.vocab_size)):
        out, aux = jax.eval_shape(
            lambda p, x: family.apply(trainer.model, p, x, deterministic=True,
                                      return_hidden=hidden), params, x)
        lead = (m.total_ut_steps,) if exits and hidden else ()
        assert out.shape == (*lead, 8, 16, last)
        assert isinstance(aux, dict)
        measured = {k for keys in getattr(family, "INSTANTS", {}).values()
                    for k in keys}
        assert all(a.dtype == jnp.int32 for k, a in aux.items()
                   if k != "exit_logits" and k not in measured)
        assert all(aux[k].dtype == jnp.float32 for k in measured
                   if k in aux)
        if exits:
            assert aux["exit_logits"].shape == (m.total_ut_steps, 8, 16)


def _step_hlo(trainer) -> str:
    """The train step's HLO as lowered for the compiler, without metadata."""
    from nanosandbox_tpu.train import _lower_step

    step, _ = trainer.compiled_steps()
    lowered = _lower_step(step, trainer._step_operands(), trainer.tracecheck)
    return re.sub(r", metadata=\{[^}]*\}", "", lowered.as_text(dialect="hlo"))


def test_the_other_families_steps_are_what_they_were(built):
    """The five families that came before ``granite`` lower to the steps
    pinned above; ``granite``'s step has its own parts, ``mamba`` and
    ``ssd``, beside its attention and MLP (obs.opscopes reads them)."""
    import hashlib

    from nanosandbox_tpu.obs import opscopes

    name, trainer, _ = built
    if name in STEP_SHA256:
        got = hashlib.sha256(_step_hlo(trainer).encode()).hexdigest()
        assert got == STEP_SHA256[name]
        return
    parts = set(trainer.step_op_parts().values())
    assert OWN_PARTS[name] | {"ln", "embed", "lm_head_loss"} <= parts
    assert OWN_PARTS[name] <= set(opscopes.PARTS)


def test_inference_is_refused_with_the_familys_own_sentence(tmp_path):
    """Where a family says what inference misses, a checkpoint of it is
    refused with that sentence (one that says None restores: tests/
    test_sample.py and every serving test come through there)."""
    from nanosandbox_tpu.checkpoint import Checkpointer
    from nanosandbox_tpu.train import restore_for_inference

    for name, (sizes, _, _) in TINY.items():
        cfg = config.TrainConfig(model_family=name, **sizes,
                                 out_dir=str(tmp_path / name))
        missing = family_of(cfg).inference
        if missing is None:
            continue
        # The refusal reads the saved config and nothing of the state.
        ckpt = Checkpointer(cfg.out_dir)
        ckpt.save(1, {"step": jnp.zeros(())}, {"config": cfg.to_dict()},
                  wait=True)
        ckpt.close()
        with pytest.raises(NotImplementedError) as e:
            restore_for_inference(cfg.out_dir)
        assert str(e.value).endswith(f"Missing for this family: {missing}")
        assert repr(name) in str(e.value)


def _code_of(path: str) -> str:
    """The module's code as the interpreter sees it: no comments, and no
    docstrings."""
    tree = ast.parse(open(path, encoding="utf-8").read())
    for node in ast.walk(tree):
        body = getattr(node, "body", None)
        if (isinstance(body, list) and body
                and isinstance(body[0], ast.Expr)
                and isinstance(body[0].value, ast.Constant)
                and isinstance(body[0].value.value, str)):
            body[0] = ast.Pass()
    return ast.unparse(tree)


def test_train_py_names_no_family():
    import nanosandbox_tpu.train as train

    names = "|".join(FAMILIES) + "|GPT2"
    code = _code_of(train.__file__).splitlines()
    assert len(code) > 500
    named = [line for line in code if re.search(names, line)]
    assert not named, named
    reads = [line.strip() for line in code if "model_family" in line]
    # `family_of` reads it; the span and run()'s result record it; the
    # inference refusal quotes it.
    assert len(reads) == 3 and all(
        "'model_family': cfg.model_family" in r
        or "{cfg.model_family!r}" in r for r in reads), reads


@pytest.mark.parametrize("name", list(FAMILIES))
def test_no_family_imports_anothers_module(name):
    """What two families share lies in models/common.py or
    models/experts.py, which neither owns."""
    code = _code_of(importlib.import_module(FAMILIES[name]).__file__)
    others = [path.rsplit(".", 2)[-2] + "." + path.rsplit(".", 1)[-1]
              for other, path in FAMILIES.items() if other != name]
    assert not [o for o in others if o in code], others


def test_the_expert_families_share_one_router_swiglu_layer_and_head_norm():
    from nanosandbox_tpu.models import afmoe, deepseek_v3, experts, lfm2

    for family in (afmoe, lfm2, deepseek_v3):
        assert family.SwiGLU is experts.SwiGLU
        if family is not deepseek_v3:    # its heads carry no norm
            assert family.HeadRMSNorm is experts.HeadRMSNorm
        assert family.experts is experts
        code = _code_of(family.__file__)
        assert "experts.routed_experts(self, " in code
        assert "def route" not in code and "class SwiGLU" not in code
    # the shared expert is one module with a width, at both families' widths
    for family in (afmoe, deepseek_v3):
        code = _code_of(family.__file__)
        assert "experts.shared_expert(" in code and "moe_shared" not in code


def test_a_gpt2_trainer_imports_no_other_familys_kernels():
    """Imports are seconds of set-up (PERF.md §7): the registry names
    modules and imports the one asked for."""
    probe = (
        "import sys\n"
        "import nanosandbox_tpu.train\n"
        "from nanosandbox_tpu.config import TrainConfig\n"
        "from nanosandbox_tpu.models import family_of\n"
        "family_of(TrainConfig())\n"
        "mine = sorted(m for m in sys.modules\n"
        "              if m.startswith('nanosandbox_tpu.'))\n"
        "assert 'nanosandbox_tpu.models.gpt' in mine, mine\n"
        "other = [m for m in mine if m.endswith(('.afmoe', '.lfm2',\n"
        "         '.deepseek_v3', '.ouro', '.granite', '.experts',\n"
        "         '.ops.moe', '.short_conv', '.ssd'))]\n"
        "assert not other, other\n")
    env = {**os.environ, "JAX_PLATFORMS": "cpu"}
    subprocess.run([sys.executable, "-c", probe], check=True, env=env,
                   timeout=120,
                   cwd=os.path.dirname(os.path.dirname(
                       os.path.abspath(config.__file__))))
