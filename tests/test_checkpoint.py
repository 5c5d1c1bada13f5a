"""Checkpoint round-trip tests (Orbax, /data layout contract)."""

import jax
import numpy as np

from nanosandbox_tpu.checkpoint import Checkpointer, abstract_like
from nanosandbox_tpu.train import Trainer


def test_roundtrip(tiny_cfg):
    trainer = Trainer(tiny_cfg)
    state = trainer.init_state()
    ckpt = Checkpointer(tiny_cfg.out_dir, keep=2)
    ckpt.save(3, state, {"iter_num": 3, "best_val_loss": 1.5}, wait=True)
    assert ckpt.latest_step() == 3

    restored, extra = ckpt.restore(trainer.abstract_state)
    assert extra["iter_num"] == 3
    assert extra["best_val_loss"] == 1.5
    for a, b in zip(jax.tree.leaves(state["params"]),
                    jax.tree.leaves(restored["params"])):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    ckpt.close()


def test_restores_a_config_that_names_the_statistic_layout(tiny_cfg):
    """A checkpoint whose saved config holds attention_stat_layout
    'compact', as every saved config does, restores for inference: the key
    is still a TrainConfig field and 'compact' its one value."""
    from nanosandbox_tpu.train import restore_for_inference

    trainer = Trainer(tiny_cfg)
    state = trainer.init_state()
    config = tiny_cfg.to_dict()
    assert config["attention_stat_layout"] == "compact"
    ckpt = Checkpointer(tiny_cfg.out_dir, keep=2)
    ckpt.save(3, state, {"iter_num": 3, "config": config}, wait=True)
    ckpt.close()
    trainer2, restored, step = restore_for_inference(tiny_cfg.out_dir,
                                                     device="cpu")
    assert step == 3
    assert trainer2.cfg.attention_stat_layout == "compact"
    for a, b in zip(jax.tree.leaves(state["params"]),
                    jax.tree.leaves(restored["params"])):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_keep_limit(tiny_cfg):
    trainer = Trainer(tiny_cfg)
    state = trainer.init_state()
    ckpt = Checkpointer(tiny_cfg.out_dir, keep=2)
    for s in (1, 2, 3):
        ckpt.save(s, state, wait=True)
    steps = ckpt.mgr.all_steps()
    assert 3 in steps and len(steps) <= 2
    ckpt.close()


def test_duplicate_step_is_noop(tiny_cfg):
    trainer = Trainer(tiny_cfg)
    state = trainer.init_state()
    ckpt = Checkpointer(tiny_cfg.out_dir, keep=2)
    ckpt.save(1, state, {"iter_num": 1}, wait=True)
    ckpt.save(1, state, {"iter_num": 1}, wait=True)  # must not raise
    ckpt.close()


def test_duplicate_step_save_logs_skip_once(tmp_path, capsys):
    """A skipped re-save (resume re-evals at the restored step) must say
    so ONCE on stderr — a resumed run that never logs a save otherwise
    looks like checkpointing silently stopped — and must not repeat on
    every subsequent eval_interval hit."""
    state = {"w": np.zeros((2, 2), np.float32)}
    ckpt = Checkpointer(str(tmp_path / "out"), keep=2)
    ckpt.save(1, state, wait=True)
    capsys.readouterr()  # drop orbax's own chatter from the first save
    ckpt.save(1, state, wait=True)
    ckpt.save(1, state, wait=True)
    err = capsys.readouterr().err
    assert err.count("already exists") == 1, err
    assert "skipping save" in err
    ckpt.close()


def test_abstract_like(tiny_cfg):
    trainer = Trainer(tiny_cfg)
    state = trainer.init_state()
    ab = abstract_like(state)
    leaf = jax.tree.leaves(ab)[0]
    assert isinstance(leaf, jax.ShapeDtypeStruct)


def test_restore_into_sharded(tiny_cfg):
    """Save replicated, restore into an FSDP-sharded abstract state."""
    t1 = Trainer(tiny_cfg)
    state = t1.init_state()
    ckpt = Checkpointer(tiny_cfg.out_dir, keep=2)
    ckpt.save(5, state, wait=True)

    cfg2 = tiny_cfg.replace(mesh_dp=1, mesh_fsdp=8, shard_params=True)
    t2 = Trainer(cfg2)
    restored, _ = ckpt.restore(t2.abstract_state, 5)
    k = restored["params"]["h_0"]["attn"]["c_attn"]["kernel"]
    assert k.sharding.is_fully_replicated is False
    np.testing.assert_allclose(
        np.asarray(jax.device_get(k)),
        np.asarray(jax.device_get(
            state["params"]["h_0"]["attn"]["c_attn"]["kernel"])))
    ckpt.close()
