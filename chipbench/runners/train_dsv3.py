"""The training runner for the ``deepseek_v3`` family (Moonshot's
Moonlight-16B-A3B is its published instance here).

As ``chipbench/runners/train_lfm2.py`` for its family, and through
``train_afmoe.py`` ``train.py``: everything that knows no model is theirs
(``train.Job``'s ``feed`` / ``window`` / ``close``, the spans, the feed check,
the peak-memory reading, the leaf measures and the comparison's four numbers;
``train_afmoe``'s folded corpus, its ``Job.call`` and ``Job.read_stats``:
every step's expert counters kept and read once after the window, a dropped
pair in ANY step a ``fault``). This runner supplies what is the family's: the
program's ``TrainConfig``, the weights (``chipbench/weights_dsv3.py``), the
sizes, the reference (``chipbench/reference/dsv3.py``) and the required
operations (``chipbench/flops_dsv3.py``).

**A program without the family is refused at once**: ``Job`` asks the
program's ``FAMILIES`` before it folds the corpus, builds a trainer or
compiles, and exits non-zero where ``deepseek_v3`` is missing (an older
commit under these files fails in seconds).

**The experts' selection bias is the benchmark's, made in set-up**, as in the
``afmoe`` and ``lfm2`` cells and for their reason: the rate follows the
seed's routing (the rows this chip's 8 experts draw swing with the seed under
random weights), and a job that has run for a while is balanced by the rule
the published model moves its ``e_score_correction_bias`` with. Before the
program's state exists the plain reference's own float32 forward pass
(``reference/dsv3.balanced_bias``; nothing of the program runs) fits each
expert layer's bias to BALANCE_BATCHES batches of the training split that no
step will see, and those values are part of the benchmark's weights, the
program's and the reference's alike. ``dsv3_moe_load_max_over_mean`` says how
even the timed steps were.
"""

from __future__ import annotations

import math
import os
import sys
import time

import numpy as np

from chipbench import flops_dsv3, weights_dsv3
from chipbench.runners import train_afmoe
from chipbench.runners.train import (CHECK_STEPS, PIPELINED_WARM_STEPS,
                                     _adam_mu, _check_feed, _leaf_norms,
                                     _Spans, gaps, memory_peak_bytes)
from chipbench.runners.train_afmoe import (BALANCE_FIRST_BATCH, _rows_bound,
                                           dataset_name, prepare_folded)

FAMILY = "deepseek_v3"
# The batches of the training split the selection bias is fitted to (the
# accepted expert cells fit 16). With 6 of 64 experts a token and 8 held, the
# rows this chip's experts draw in a window followed the fit's sample: over
# six seeds 12,046-13,054 a layer (expected 12,288) on a bias fitted to 16
# batches, and the rate with them, 31,067-31,231 tokens/s, spread 0.45 % for
# a bound of 1 % (my chip runs, PR 35). A window is itself ~98 rows of the
# corpus's 667; three times the rows bring the fit's error under the
# window's own.
BALANCE_BATCHES = 48


def log(msg: str) -> None:
    print(f"[chipbench.train_dsv3] {msg}", file=sys.stderr, flush=True)


# -- the program under test ---------------------------------------------------

def model_sizes(ctx) -> dict:
    """The family's sizes as this cell runs them, under the program's
    names; the reference, the weights and the cost functions read these."""
    c = ctx.config
    layers = c["num_hidden_layers"]
    return {
        "n_layer": layers, "n_head": c["num_attention_heads"],
        "n_embd": c["hidden_size"], "vocab_size": c["vocab_size"],
        "block_size": ctx.traffic["block_size"],
        "layer_types": ["mla"] * layers,       # moe_layer_freq 1: one kind
        # what the program's `check` refuses by name where the file sets it
        "q_lora_rank": c["q_lora_rank"] or 0, "n_group": c["n_group"],
        "topk_group": c["topk_group"],
        "kv_lora_rank": c["kv_lora_rank"],
        "qk_nope_head_dim": c["qk_nope_head_dim"],
        "qk_rope_head_dim": c["qk_rope_head_dim"],
        "v_head_dim": c["v_head_dim"],
        "num_dense_layers": c["first_k_dense_replace"],
        "intermediate_size": c["intermediate_size"],
        "moe_intermediate_size": c["moe_intermediate_size"],
        "n_shared_experts": c["n_shared_experts"],
        "num_experts": c["router_num_experts"],
        "num_experts_per_tok": c["num_experts_per_tok"],
        "experts_held": (c["experts_first"], c["n_routed_experts"]),
        "route_scale": float(c["routed_scaling_factor"]),
        "route_norm": c["norm_topk_prob"],
        "rope_theta": float(c["rope_theta"]),
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
        **{**s, "layer_types": ",".join(s["layer_types"])},
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
    """f(params, key, bias) -> norms of each leaf's change from the
    benchmark's weights for ``key`` and ``bias``, drawn again, not kept."""
    import jax

    def change(params, key, bias):
        p0 = weights_dsv3.make_params(sizes, key, bias)
        return _leaf_norms(jax.tree.map(lambda a, b: a - b, params, p0))
    return jax.jit(change)


def balanced_bias(ctx, sizes: dict, rows) -> tuple:
    """(bias, load), each (expert layers, E) on the host: the selection bias
    the plain reference fits to ``rows`` (R, T) of ids on the seed's
    weights (bias zero), in float32 at ``highest`` precision, and the loads
    it leaves there; the weights are freed again."""
    import jax

    from chipbench.reference import dsv3

    with jax.default_matmul_precision("highest"):
        params = jax.jit(lambda key: weights_dsv3.make_params(sizes, key))(
            weights_dsv3.seed_key(ctx.seed))
        return jax.device_get(dsv3.balanced_bias(
            params, rows, sizes,
            ctx.cell["check"]["ref_rows_per_block"]))


def _by_leaf(sizes: dict, vector, scale: float = 1.0) -> dict:
    names = sorted("/".join(p) for p in weights_dsv3.param_shapes(sizes))
    return {k: float(v) * scale for k, v in zip(names, np.asarray(vector))}


class Job(train_afmoe.Job):
    """``train_afmoe.Job`` with this family's config, weights and sizes:
    ``feed``, ``window`` and ``close`` are ``train.Job``'s, ``call`` and
    ``read_stats`` ``train_afmoe.Job``'s (the step's expert counters)."""

    def __init__(self, ctx):  # not the parents': theirs build their family
        import jax
        import jax.numpy as jnp

        from nanosandbox_tpu.models import FAMILIES
        from nanosandbox_tpu.ops.attention import resolve_attention_impl
        from nanosandbox_tpu.ops.moe import resolve_gmm_impl
        from nanosandbox_tpu.train import Trainer
        from nanosandbox_tpu.utils.compile_cache import enable_compile_cache

        if FAMILY not in FAMILIES:   # an older program under these files:
            # said before the corpus is folded or anything compiles
            raise SystemExit("chipbench.train_dsv3: this program has no "
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
        self.key = weights_dsv3.seed_key(ctx.seed)
        self.spans = _Spans(ctx.trace)
        self._since_start("Trainer built")
        # Rows of the split the steps draw from, at offsets no step of a run
        # reaches (train_afmoe.py says why not the validation split).
        rows = np.concatenate([
            trainer.dataset.sample_batch(
                "train", BALANCE_FIRST_BATCH + n, self.cfg.batch_size,
                self.cfg.block_size, seed=ctx.seed)[0]
            for n in range(BALANCE_BATCHES)])
        with self.spans("balance_bias"):
            self.expert_bias, load = balanced_bias(ctx, sizes, rows)
        first, count = sizes["experts_held"]
        self.balance = {
            "rows": int(rows.shape[0]),
            "fullest_over_even": float(load.max()),
            "held_share_by_layer": load[:, first:first + count].mean(
                axis=1).tolist()}
        self._since_start(f"selection bias fitted by the reference: "
                          f"{self.balance}")

        def make_state(key, bias):
            params = weights_dsv3.make_params(sizes, key, bias)
            return {"params": params, "opt_state": trainer.tx.init(params),
                    "step": jnp.zeros((), jnp.int32)}

        want = jax.tree.map(lambda a: (a.shape, a.dtype),
                            trainer.abstract_state)
        got = jax.tree.map(lambda a: (a.shape, a.dtype), jax.eval_shape(
            make_state, self.key, self.expert_bias))
        if want != got:
            raise RuntimeError(
                "chipbench/weights_dsv3.py no longer has the program's "
                f"parameter layout:\n program {want}\n benchmark {got}")
        self.state = jax.jit(
            make_state, out_shardings=trainer.state_shardings)(
                self.key, self.expert_bias)
        jax.block_until_ready(self.state)
        self._since_start("weights and optimizer state made on the device")
        self.step, _ = trainer.compiled_steps()
        if ctx.break_step is not None:  # chipbench/tests plant a fault here
            self.step = ctx.break_step(self.step)
        self.loader = trainer.make_loader("train", prefetch=True)
        self.rng = trainer.train_rng(ctx.seed + 7)
        self.i = 0
        self.stats: list[dict] = []   # every step's expert counters
        log(f"attention_impl resolved: "
            f"{resolve_attention_impl(trainer.model_cfg.attention_impl)}; "
            f"attn_layout: {trainer.attn_layout}; gmm_impl resolved: "
            f"{resolve_gmm_impl('auto')}; what the family says of its "
            f"model: {trainer._describe}; loader native: "
            f"{self.loader.native}; loss_chunk_size: "
            f"{trainer.loss_chunk_size}; remat: {self.cfg.remat} "
            f"({self.cfg.remat_policy}); mesh: {dict(trainer.mesh.shape)}; "
            f"chips: {self.chips}")

    def first_steps(self) -> dict:
        """As ``train_afmoe.Job.first_steps``, with this family's leaves."""
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
            self.state["params"], self.key, self.expert_bias))
        seen["expert_bias"] = self.expert_bias
        self._since_start("three steps made and read")
        log(f"first steps: loss {seen['loss']}, "
            f"grad norm {seen['grad_norm']}; rows held by expert layer "
            f"{[np.asarray(s['moe_held']).tolist() for s in self.stats]}, "
            f"fullest expert's rows "
            f"{[np.asarray(s['moe_max_rows']).tolist() for s in self.stats]}")
        for _ in range(PIPELINED_WARM_STEPS):
            m = self.call(*self.feed())
        float(m["loss"])
        self._since_start(f"{PIPELINED_WARM_STEPS} pipelined steps drained")
        return seen


def run(ctx) -> dict:
    import jax

    job = Job(ctx)
    try:
        seen = job.first_steps()
        first_window_step = job.i
        win = job.window()
        final_step = int(job.state["step"])
        moe = job.read_stats(first_window_step, job.cfg.log_interval)
        rows_bound = _rows_bound(job)
    finally:
        job.close()
    steps, window_s = win["steps"], win["t1"] - win["t0"]
    mem_peak = memory_peak_bytes()
    log(f"memory: {jax.devices()[0].memory_stats()}")
    log(f"window: {steps} steps in {window_s:.3f} s; last loss "
        f"{win['logged'][-1]:.4f}; peak memory {mem_peak} B")
    log(f"expert layers: {moe}; buffer rows {rows_bound}")

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
    if moe["dropped"]:
        faults.append(f"{moe['dropped']} routed (token, slot) pair(s) "
                      f"dropped in {moe['steps_counted']} steps: the sorted "
                      f"walk covers {rows_bound} rows")
    failed = sum(1 for v in win["logged"] if not math.isfinite(v))
    tokens = steps * cfg.tokens_per_iter
    moe["rows_bound"] = rows_bound
    moe["rows_expected"] = flops_dsv3.expected_rows_held(
        sizes, cfg.tokens_per_iter)
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
        "flops_per_token": flops_dsv3.train_flops_per_token(sizes),
        "moe": moe, "balance": job.balance,
    }


# -- the comparison that decides `correct` ------------------------------------

def reference_numbers(ctx, sizes: dict, batches, expert_bias, quant=None,
                      leave_out=frozenset()) -> dict:
    """The plain reference's three steps from the benchmark's weights (the
    seed's, with ``expert_bias``), as host numbers. ``quant`` puts the
    control in the reference's place, ``leave_out`` a planted fault."""
    import jax
    import jax.numpy as jnp

    from chipbench.reference import dsv3

    opt = ctx.config["optimizer"]
    kw = {} if quant is None else {"quant": quant}
    key = weights_dsv3.seed_key(ctx.seed)

    @jax.jit
    def first(key):
        p = weights_dsv3.make_params(sizes, key, expert_bias)
        zeros = jax.tree.map(jnp.zeros_like, p)
        return p, zeros, jax.tree.map(jnp.zeros_like, p)

    # Two programs a step, so that the backward's temporaries and the
    # update's never share the chip: loss and gradient (parameters and
    # moments resident, 8.0 GB, + gradient 2.7 GB + activations), then the
    # update in place.
    def grad(params, x, y):
        loss, grads = dsv3.loss_and_grad(params, x, y, sizes,
                                         leave_out=leave_out, **kw)
        return loss, dsv3.global_norm(grads), _leaf_norms(grads), grads

    def update(params, m, v, grads, count):
        return dsv3.adamw_step(params, m, v, grads, count, opt)

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
        out["dp_leaf"] = _by_leaf(
            sizes, _change_norms(sizes)(params, key, expert_bias))
    del params, m, v
    return out


def compare(ctx, sizes: dict, seen: dict) -> dict:
    ref = reference_numbers(ctx, sizes, seen["batches"], seen["expert_bias"])
    log(f"reference: loss {ref['loss']}, grad norm {ref['grad_norm']}")
    g = gaps(seen, ref)
    log(f"worst leaves: {g['_where']}")
    limits = ctx.cell["check"]["limits"]
    return {name: {"value": g[name], "limit": limits[name]} for name in limits}
