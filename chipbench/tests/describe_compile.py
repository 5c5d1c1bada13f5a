"""Compile a cell's step program for a DESCRIBED v5e (no chip attached), for
the builder only: seconds to compile and bytes per device, never a chip run.

    JAX_PLATFORMS=cpu python -m chipbench.tests.describe_compile <cell> [...]
    JAX_PLATFORMS=cpu python -m chipbench.tests.describe_compile --xl

``--xl`` compiles the planned four-chip cell (PERF.md, Open questions),
whose files are not in the benchmark yet.
"""

from __future__ import annotations

import json
import os
import sys
import time
from types import SimpleNamespace

os.environ.setdefault("TPU_LOG_DIR", "disabled")

XL = {
    "config": {"name": "gpt2-xl", "n_layer": 48, "n_head": 25, "n_embd": 1600,
               "vocab_size": 50304, "bias": False, "resid_pdrop": 0.0,
               "trainer": {"param_dtype": "float32", "compute_dtype": "bfloat16",
                           "attention_stat_layout": "compact", "loss_chunk_size": -1},
               "optimizer": {"learning_rate": 2e-4, "min_lr": 2e-5,
                             "warmup_iters": 2000, "lr_decay_iters": 100000,
                             "max_iters": 100000}},
    "traffic": {"batch_size": 16, "block_size": 1024,
                "gradient_accumulation_steps": 1, "log_interval": 10},
    "cell": {"chips": 4, "mesh": {"data": 1, "fsdp": 4, "seq": 1, "model": 1},
             "shard_params": True, "remat": True},
}


def compile_cell(config, traffic, cell, data_dir) -> dict:
    import jax
    from jax.experimental import topologies

    from chipbench.runners import train
    from nanosandbox_tpu.train import Trainer

    jax.config.update("jax_enable_compilation_cache", False)
    topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    devices = list(topo.devices)[:cell["chips"]]
    ctx = SimpleNamespace(config=config, traffic=traffic, cell=cell, seed=1,
                          work_dir="/tmp/chipbench-describe", data_dir=data_dir)
    # 'auto' asks the CPU backend here; the chip's program has the kernel.
    cfg = train.train_config(ctx).replace(attention_impl="pallas")
    trainer = Trainer(cfg, mesh_devices=devices)
    t = time.time()
    mem = trainer.memory_report()
    return {"compile_s": round(time.time() - t, 1), "devices": len(devices),
            "device_kind": devices[0].device_kind,
            "mesh": dict(trainer.mesh.shape),
            "loss_chunk_size": trainer.loss_chunk_size, **mem}


def main() -> None:
    from chipbench import run as harness
    from chipbench.tests import helpers

    data_dir = os.path.join(helpers.CHIPBENCH, "_data")
    for name in sys.argv[1:]:
        if name == "--xl":
            out = compile_cell(XL["config"], XL["traffic"], XL["cell"], data_dir)
        else:
            f = harness.find_cell(name)
            out = compile_cell(f.config, f.traffic, f.cell, data_dir)
        print(json.dumps({"cell": name, **out}), flush=True)


if __name__ == "__main__":
    main()
