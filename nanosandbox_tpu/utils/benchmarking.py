"""Train-step throughput measurement for bench.py.

Pipelined timing: enqueue all timed iters, sync once at the end. This is
what the real train loop achieves under JAX async dispatch (it only reads
a scalar back every log_interval); a per-step readback would drain the
queue every step and understate sustained throughput.
"""

from __future__ import annotations

import time


def measure_train_throughput(cfg, warmup: int, iters: int) -> dict:
    """Train `warmup + iters` steps of cfg's model; returns step_ms,
    tokens_per_sec_per_chip, mfu, and the last loss."""
    import jax

    from nanosandbox_tpu.train import Trainer

    if warmup < 1:
        # The hard-sync below reads the last warmup step's metrics; with
        # no warmup there is nothing to sync on and t0 would include
        # compilation.
        raise ValueError("measure_train_throughput requires warmup >= 1")

    trainer = Trainer(cfg)
    state = trainer.init_state()
    train_step, _ = trainer.compiled_steps()
    loader = trainer.make_loader("train", prefetch=True)
    rng = trainer.train_rng(0)
    try:
        for _ in range(warmup):
            xb, yb = next(loader)
            state, m = train_step(state, trainer.to_global(xb),
                                  trainer.to_global(yb), rng)
        # jaxlint: disable=host-sync -- the warmup fence the timing needs
        float(m["loss"])  # hard sync: a scalar readback waits for the
        # whole queue behind it.

        t0 = time.perf_counter()
        for _ in range(iters):
            xb, yb = next(loader)
            state, m = train_step(state, trainer.to_global(xb),
                                  trainer.to_global(yb), rng)
        # jaxlint: disable=host-sync -- the stop-the-clock drain being measured
        loss = float(m["loss"])
        step_s = (time.perf_counter() - t0) / iters
    finally:
        loader.close()

    n_chips = len(jax.devices())
    mfu = trainer.mfu(step_s)  # None on a cpu backend: it has no peak
    return {
        "step_ms": round(step_s * 1000, 2),
        "tokens_per_sec_per_chip": round(
            cfg.tokens_per_iter / step_s / n_chips, 1),
        "mfu": None if mfu is None else round(mfu, 4),
        "loss": round(loss, 4),
        # Provenance: the value the measured Trainer ACTUALLY resolved
        # (auto chunk depends on per-device batch/mesh — reporting it from
        # the source keeps the record honest).
        "resolved_loss_chunk_size": trainer.loss_chunk_size,
    }
