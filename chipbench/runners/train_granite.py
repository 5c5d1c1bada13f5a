"""The training runner for the ``granite`` family (IBM's Granite 4.0-H Micro:
Mamba-2 state-space layers beside grouped-query attention layers with no
positions, muP multipliers).

As ``chipbench/runners/train_ouro.py`` for its family: everything that knows
no model is ``train.py``'s (``Job``'s ``feed`` / ``window`` / ``close``, the
spans, the feed check, the peak-memory reading, the leaf measures and the
comparison's four numbers) and the folded corpus is ``train_afmoe``'s (ids
folded ``mod vocab_size``: here the chip's quarter of the tied table, 25,088
rows). This runner supplies what is the family's: the program's
``TrainConfig``, the weights (``chipbench/weights_granite.py``), the sizes,
the reference (``chipbench/reference/granite.py``, whose scan is the token
recurrence) and the required operations (``chipbench/flops_granite.py``).
There is no routing state to freeze: the schedule is the GPT-2 and Ouro
cells' kind.

Beside what ``train.py`` decides ``correct`` on, a step of this family
reports the scan's health in each Mamba layer (``ssd_decay``: the state's
mean decay over a chunk; ``ssd_state_max``: the largest |S| at a chunk
boundary). The runner keeps every step's (two tiny device arrays, never read
in the window) and, once the window has closed, hands those of the logged
steps to the trainer's own ``note_instants``: the ``granite_ssd`` instants
``Trainer.run()`` leaves at every log step, which
``chipbench/reducers/granite.py`` reads.

**A program without the family is refused at once**: ``Job`` asks the
program's ``FAMILIES`` before it folds the corpus, builds a trainer or
compiles, and exits non-zero where ``granite`` is missing (an older commit
under these files fails in seconds).
"""

from __future__ import annotations

import math
import os
import sys
import time

import numpy as np

from chipbench import flops_granite, weights_granite
from chipbench.runners import train
from chipbench.runners.train import (CHECK_STEPS, PIPELINED_WARM_STEPS,
                                     _adam_mu, _check_feed, _leaf_norms,
                                     _Spans, gaps, memory_peak_bytes)
from chipbench.runners.train_afmoe import dataset_name, prepare_folded

FAMILY = "granite"
SSD_STATS = ("ssd_decay", "ssd_state_max")


def log(msg: str) -> None:
    print(f"[chipbench.train_granite] {msg}", file=sys.stderr, flush=True)


# -- the program under test ---------------------------------------------------

def model_sizes(ctx) -> dict:
    """The family's sizes as this cell runs them, under the program's
    names; the reference, the weights and the cost functions read these.
    The layers are the first ``num_hidden_layers`` of ``layer_types``."""
    c = ctx.config
    n = c["num_hidden_layers"]
    return {
        "n_layer": n, "n_head": c["num_attention_heads"],
        "n_kv_head": c["num_key_value_heads"],
        "head_dim": c["hidden_size"] // c["num_attention_heads"],
        "n_embd": c["hidden_size"], "vocab_size": c["vocab_size"],
        "block_size": ctx.traffic["block_size"],
        "intermediate_size": c["shared_intermediate_size"],
        "layer_types": list(c["layer_types"][:n]),
        "mamba_n_heads": c["mamba_n_heads"], "mamba_d_head": c["mamba_d_head"],
        "mamba_d_state": c["mamba_d_state"],
        "mamba_n_groups": c["mamba_n_groups"],
        "mamba_d_conv": c["mamba_d_conv"],
        "mamba_chunk_size": c["mamba_chunk_size"],
        "embedding_multiplier": float(c["embedding_multiplier"]),
        "residual_multiplier": float(c["residual_multiplier"]),
        "attention_multiplier": float(c["attention_multiplier"]),
        "logits_scaling": float(c["logits_scaling"]),
        "rms_norm_eps": c["rms_norm_eps"],
    }


def train_config(ctx):
    from nanosandbox_tpu.config import TrainConfig

    c, job, cell = ctx.config, ctx.traffic, ctx.cell
    s = model_sizes(ctx)
    mesh = cell.get("mesh", {})
    return TrainConfig(
        out_dir=os.path.join(ctx.work_dir, "out"), data_dir=ctx.data_dir,
        dataset=dataset_name(s["vocab_size"]), seed=ctx.seed, device="auto",
        tensorboard=False, eval_interval=0, init_from="scratch",
        model_family=FAMILY, dropout=0.0, bias=False,
        layer_types=",".join(s["layer_types"]),
        **{k: s[k] for k in (
            "n_layer", "n_head", "n_kv_head", "head_dim", "n_embd",
            "vocab_size", "block_size", "intermediate_size", "mamba_n_heads",
            "mamba_d_head", "mamba_d_state", "mamba_n_groups",
            "mamba_d_conv", "mamba_chunk_size", "embedding_multiplier",
            "residual_multiplier", "attention_multiplier", "logits_scaling",
            "rms_norm_eps")},
        batch_size=job["batch_size"],
        gradient_accumulation_steps=job["gradient_accumulation_steps"],
        log_interval=job["log_interval"],
        mesh_dp=mesh.get("data", 1), mesh_fsdp=mesh.get("fsdp", 1),
        mesh_sp=mesh.get("seq", 1), mesh_tp=mesh.get("model", 1),
        shard_params=cell.get("shard_params", False),
        remat=cell.get("remat", False),
        remat_policy=cell.get("remat_policy", "save_attention"),
        **c["trainer"], **c["optimizer"])


def _change_norms(sizes: dict):
    """f(params, key) -> norms of each leaf's change from the benchmark's
    weights for ``key``, drawn again, not kept."""
    import jax

    def change(params, key):
        p0 = weights_granite.make_params(sizes, key)
        return _leaf_norms(jax.tree.map(lambda a, b: a - b, params, p0))
    return jax.jit(change)


def _by_leaf(sizes: dict, vector, scale: float = 1.0) -> dict:
    names = sorted("/".join(p) for p in weights_granite.param_shapes(sizes))
    return {k: float(v) * scale for k, v in zip(names, np.asarray(vector))}


class Job(train.Job):
    """``train.Job`` with this family's config, weights and sizes: ``feed``,
    ``window`` and ``close`` are the parent's, ``call`` the parent's plus a
    note of the step's scan counters."""

    def __init__(self, ctx):  # not the parent's: it builds GPT-2
        import jax
        import jax.numpy as jnp

        from nanosandbox_tpu.models import FAMILIES
        from nanosandbox_tpu.ops.attention import resolve_attention_impl
        from nanosandbox_tpu.train import Trainer
        from nanosandbox_tpu.utils.compile_cache import enable_compile_cache

        if FAMILY not in FAMILIES:   # an older program under these files:
            # said before the corpus is folded or anything compiles
            raise SystemExit("chipbench.train_granite: this program has no "
                             f"model_family {FAMILY!r} (it has "
                             f"{tuple(FAMILIES)})")
        cache_dir = enable_compile_cache()
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
        jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
        log(f"compile cache: {cache_dir}")
        self.ctx = ctx
        self.sizes = sizes = model_sizes(ctx)
        prep_s = prepare_folded(ctx.data_dir, sizes["vocab_size"])
        log(f"data: {ctx.data_dir} (folded in {prep_s:.1f} s; 0 = reused)")
        self._since_start("imports, compile cache and data ready")
        self.cfg = train_config(ctx)
        self.trainer = trainer = Trainer(self.cfg)
        self.chips = len(jax.devices())
        self.key = weights_granite.seed_key(ctx.seed)
        self.spans = _Spans(ctx.trace)
        self._since_start("Trainer built")

        def make_state(key):
            params = weights_granite.make_params(sizes, key)
            return {"params": params, "opt_state": trainer.tx.init(params),
                    "step": jnp.zeros((), jnp.int32)}

        want = jax.tree.map(lambda a: (a.shape, a.dtype),
                            trainer.abstract_state)
        got = jax.tree.map(lambda a: (a.shape, a.dtype),
                           jax.eval_shape(make_state, self.key))
        if want != got:
            raise RuntimeError(
                "chipbench/weights_granite.py no longer has the program's "
                f"parameter layout:\n program {want}\n benchmark {got}")
        self.state = jax.jit(
            make_state, out_shardings=trainer.state_shardings)(self.key)
        jax.block_until_ready(self.state)
        self._since_start("weights and optimizer state made on the device")
        self.step, _ = trainer.compiled_steps()
        if ctx.break_step is not None:  # chipbench/tests plant a fault here
            self.step = ctx.break_step(self.step)
        self.loader = trainer.make_loader("train", prefetch=True)
        self.rng = trainer.train_rng(ctx.seed + 7)
        self.i = 0
        self.ssd: list[dict] = []     # every step's scan counters
        log(f"attention_impl resolved: "
            f"{resolve_attention_impl(trainer.model_cfg.attention_impl)}; "
            f"what the family says of its model: {trainer._describe}; "
            f"loader native: {self.loader.native}; loss_chunk_size: "
            f"{trainer.loss_chunk_size}; remat: {self.cfg.remat} "
            f"({self.cfg.remat_policy}); mesh: {dict(trainer.mesh.shape)}; "
            f"chips: {self.chips}")

    def call(self, xb, yb):
        m = super().call(xb, yb)
        self.ssd.append({k: m[k] for k in SSD_STATS})
        return m

    def first_steps(self) -> dict:
        """As ``train.Job.first_steps``, with this family's leaves."""
        import jax

        sizes = self.sizes
        b1 = self.ctx.config["optimizer"]["beta1"]
        seen = {"loss": [], "grad_norm": [], "batches": []}
        for i in range(CHECK_STEPS):
            xb, yb = self.feed()
            seen["batches"].append((np.array(xb), np.array(yb)))
            m = self.call(xb, yb)
            seen["loss"].append(float(m["loss"]))
            seen["grad_norm"].append(float(m["grad_norm"]))
            if i == 0:
                self._since_start("first step made (the step program "
                                  "compiled or out of the cache)")
                mu = jax.jit(_leaf_norms)(_adam_mu(self.state["opt_state"]))
                seen["g1_leaf"] = _by_leaf(sizes, mu, 1.0 / (1.0 - b1))
        seen["dp_leaf"] = _by_leaf(sizes, _change_norms(sizes)(
            self.state["params"], self.key))
        self._since_start("three steps made and read")
        log(f"first steps: loss {seen['loss']}, "
            f"grad norm {seen['grad_norm']}; scan "
            f"{jax.device_get(self.ssd[:CHECK_STEPS])}")
        for _ in range(PIPELINED_WARM_STEPS):
            m = self.call(*self.feed())
        float(m["loss"])
        self._since_start(f"{PIPELINED_WARM_STEPS} pipelined steps drained")
        return seen

    def note_ssd(self, first_window_step: int) -> int:
        """The scan counters of the window's logged steps (those whose loss
        the window read, and the last), read once after the window and left
        by the trainer as its instants; how many."""
        import jax

        got = jax.device_get(self.ssd)
        self.ssd = []
        every = max(self.cfg.log_interval, 1)
        logged = [k for k in range(first_window_step, len(got))
                  if k % every == 0 or k == len(got) - 1]
        for k in logged:
            self.trainer.note_instants(got[k], k)
        return len(logged)


def run(ctx) -> dict:
    import jax

    job = Job(ctx)
    try:
        seen = job.first_steps()
        first_window_step = job.i
        win = job.window()
        final_step = int(job.state["step"])
        noted = job.note_ssd(first_window_step)
    finally:
        job.close()
    steps, window_s = win["steps"], win["t1"] - win["t0"]
    mem_peak = memory_peak_bytes()
    log(f"memory: {jax.devices()[0].memory_stats()}")
    log(f"window: {steps} steps in {window_s:.3f} s; last loss "
        f"{win['logged'][-1]:.4f}; peak memory {mem_peak} B; scan noted "
        f"for {noted} logged steps")

    sizes, cfg, chips = job.sizes, job.cfg, job.chips
    t = time.time()
    check = compare(ctx, sizes, seen)
    log(f"reference and comparison took {time.time() - t:.1f} s")
    faults = _check_feed(seen["batches"])
    if win["compiled_in_window"]:
        faults.append(f"{win['compiled_in_window']} program(s) compiled in "
                      "the window")
    if final_step != job.i:
        faults.append(f"state counts {final_step} steps, {job.i} were made")
    failed = sum(1 for v in win["logged"] if not math.isfinite(v))
    tokens = steps * cfg.tokens_per_iter
    return {
        "attempted": steps, "failed": failed, "check": check,
        "faults": faults, "memory_peak_bytes": mem_peak,
        "setup_s": win["setup_s"], "window_s": window_s,
        "compile_in_setup": win["compile_in_setup"],
        "window_t0": win["t0"], "window_t1": win["t1"], "steps": steps,
        "tokens": tokens, "chips": chips,
        "values": {"train_tok_s_chip": tokens / window_s / chips},
        "spans": job.spans.items, "sizes": sizes,
        "batch_rows": cfg.sequences_per_iter,
        "flops_per_token": flops_granite.train_flops_per_token(sizes),
    }


# -- the comparison that decides `correct` ------------------------------------

def reference_numbers(ctx, sizes: dict, batches, quant=None,
                      leave_out=frozenset()) -> dict:
    """The plain reference's three steps from the benchmark's weights, as
    host numbers. ``quant`` puts the control in the reference's place,
    ``leave_out`` a planted fault."""
    import jax
    import jax.numpy as jnp

    from chipbench.reference import granite

    opt = ctx.config["optimizer"]
    kw = {} if quant is None else {"quant": quant}
    key = weights_granite.seed_key(ctx.seed)

    @jax.jit
    def first(key):
        p = weights_granite.make_params(sizes, key)
        zeros = jax.tree.map(jnp.zeros_like, p)
        return p, zeros, jax.tree.map(jnp.zeros_like, p)

    # Two programs a step, so that the backward's temporaries and the
    # update's never share the chip: loss and gradient (parameters and
    # moments resident, 9.6 GB, + gradient 3.2 GB + activations), then the
    # update in place.
    def grad(params, x, y):
        loss, grads = granite.loss_and_grad(params, x, y, sizes,
                                         leave_out=leave_out, **kw)
        return loss, granite.global_norm(grads), _leaf_norms(grads), grads

    def update(params, m, v, grads, count):
        return granite.adamw_step(params, m, v, grads, count, opt)

    on_chip = jax.default_backend() != "cpu"
    grad = jax.jit(grad)
    update = jax.jit(update, donate_argnums=(0, 1, 2, 3) if on_chip else ())

    def one(params, m, v, x, y, count):
        loss, gnorm, leaf, grads = grad(params, x, y)
        params, m, v, scale = update(params, m, v, grads, count)
        return params, m, v, loss, gnorm, leaf * scale  # the clipped one's

    with jax.default_matmul_precision("highest"):
        params, m, v = first(key)
        out = {"loss": [], "grad_norm": []}
        for i, (x, y) in enumerate(batches):
            params, m, v, loss, gnorm, g_leaf = one(
                params, m, v, jnp.asarray(x), jnp.asarray(y), i)
            out["loss"].append(float(loss))
            out["grad_norm"].append(float(gnorm))
            if i == 0:
                out["g1_leaf"] = _by_leaf(sizes, g_leaf)
        out["dp_leaf"] = _by_leaf(sizes, _change_norms(sizes)(params, key))
    del params, m, v
    return out


def compare(ctx, sizes: dict, seen: dict) -> dict:
    ref = reference_numbers(ctx, sizes, seen["batches"])
    log(f"reference: loss {ref['loss']}, grad norm {ref['grad_norm']}")
    g = gaps(seen, ref)
    log(f"worst leaves: {g['_where']}")
    limits = ctx.cell["check"]["limits"]
    return {name: {"value": g[name], "limit": limits[name]} for name in limits}
