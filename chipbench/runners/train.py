"""The training runner: drives the trainer's own step path for a window.

Call for call what ``Trainer.run()`` does per iteration (which cannot be
used itself: it is bounded by iterations, evaluates at iteration 0 and owns
its profiler window): ``next(loader)`` -> ``to_global`` ->
``train_step(state, x, y, fold_in(train_rng, i))``, a scalar loss read-back
every ``log_interval`` steps, and one drain at the end, inside the window.

Set-up builds ONE compiled step with its state, from the benchmark's own
weights for ``--seed``, drives it through its first three steps by the
window's own call and feed, and hands that same object to the window.
Those three steps are what ``correct`` is decided on: once the window has
closed, the peak memory has been read and the program's state is freed,
the plain reference (``chipbench/reference/gpt2.py``) follows the same
three batches from the same weights, and each number below is held to the
limit in the cell's file:

  loss_gap       widest |loss - reference loss| over the three steps
  grad_norm_gap  widest relative gap of the step's reported global
                 gradient norm (before clipping) over the three steps
  g1_leaf_gap    first gradient as the optimizer gets it (clipped; read
                 from Adam's first moment after one step, mu / (1 - b1)):
                 worst leaf's |norm - reference norm| over the larger of
                 the reference's norm of that leaf and of the median leaf
  dp_leaf_gap    the parameters' change over the three steps, same measure,
                 leaves whose reference gradient is under a thousandth of
                 the median leaf's left out

Tests break the timed path underneath through ``ctx.break_step``.
"""

from __future__ import annotations

import contextlib
import math
import os
import statistics
import sys
import time

import numpy as np

from chipbench import flops, weights

CHECK_STEPS = 3
# After the checked steps (each read back, with other programs run between
# them) a few more, enqueued as the window enqueues them and drained once:
# the first step after another program has run starts some tens of
# milliseconds late (30-100 ms in the traces, my chip runs, PR 26), and that
# belongs to set-up, not to the window.
PIPELINED_WARM_STEPS = 3
_DATASET = "english_prose_bpe"


def log(msg: str) -> None:
    print(f"[chipbench.train] {msg}", file=sys.stderr, flush=True)


# -- data ---------------------------------------------------------------------

def prepare_data(data_dir: str) -> float:
    """Tokenise the committed corpus once per checkout; later runs reuse it.
    Built beside its final place and renamed, so a killed run leaves no
    half-written set behind. Returns the seconds it took (0 when reused)."""
    final = os.path.join(data_dir, _DATASET)
    if os.path.exists(os.path.join(final, "train.bin")):
        return 0.0
    from nanosandbox_tpu.data import prepare

    t = time.time()
    tmp = os.path.join(data_dir, f".building-{os.getpid()}")
    with contextlib.redirect_stdout(sys.stderr):
        prepare.main([_DATASET, f"--data_dir={tmp}"])
    os.makedirs(data_dir, exist_ok=True)
    try:
        os.rename(os.path.join(tmp, _DATASET), final)
    except OSError:
        if not os.path.exists(os.path.join(final, "train.bin")):
            raise
    import shutil

    shutil.rmtree(tmp, ignore_errors=True)
    return time.time() - t


# -- the program under test ---------------------------------------------------

def train_config(ctx):
    """The program's TrainConfig for this cell: the configuration's sizes
    and optimizer, the traffic's batch, the cell's mesh."""
    from nanosandbox_tpu.config import TrainConfig

    c, job, cell = ctx.config, ctx.traffic, ctx.cell
    mesh = cell.get("mesh", {})
    return TrainConfig(
        out_dir=os.path.join(ctx.work_dir, "out"), data_dir=ctx.data_dir,
        dataset=_DATASET, seed=ctx.seed, device="auto", tensorboard=False,
        eval_interval=0, init_from="scratch",
        n_layer=c["n_layer"], n_head=c["n_head"], n_embd=c["n_embd"],
        block_size=job["block_size"], vocab_size=c["vocab_size"],
        bias=c["bias"], dropout=c["resid_pdrop"],
        batch_size=job["batch_size"],
        gradient_accumulation_steps=job["gradient_accumulation_steps"],
        log_interval=job["log_interval"],
        mesh_dp=mesh.get("data", 1), mesh_fsdp=mesh.get("fsdp", 1),
        mesh_sp=mesh.get("seq", 1), mesh_tp=mesh.get("model", 1),
        shard_params=cell.get("shard_params", False),
        remat=cell.get("remat", False),
        **c["trainer"], **c["optimizer"])


def model_sizes(ctx) -> dict:
    c = ctx.config
    return {"n_layer": c["n_layer"], "n_head": c["n_head"],
            "n_embd": c["n_embd"], "vocab_size": c["vocab_size"],
            "block_size": ctx.traffic["block_size"], "bias": c["bias"]}


def _adam_mu(opt_state):
    """Adam's first moment inside the optimizer's state, wherever the chain
    keeps it."""
    import jax

    found = [n for n in jax.tree.leaves(
        opt_state, is_leaf=lambda n: hasattr(n, "mu") and hasattr(n, "nu"))
        if hasattr(n, "mu")]
    if len(found) != 1:
        raise RuntimeError(f"expected one Adam state, found {len(found)}")
    return found[0].mu


def _leaf_norms(tree):
    """Each leaf's norm, as ONE vector in ``weights.flatten``'s order (one
    transfer to the host, not one a leaf)."""
    import jax.numpy as jnp

    return jnp.stack([jnp.sqrt(jnp.sum(jnp.square(v.astype(jnp.float32))))
                      for v in weights.flatten(tree).values()])


def _change_norms(sizes: dict):
    """f(params, key) -> norms of each leaf's change from the weights that
    ``key`` gives, which are drawn again rather than kept."""
    import jax

    def change(params, key):
        p0 = weights.make_params(sizes, key)
        return _leaf_norms(jax.tree.map(lambda a, b: a - b, params, p0))
    return jax.jit(change)


def _by_leaf(sizes: dict, vector, scale: float = 1.0) -> dict:
    names = sorted("/".join(path) for path in weights.param_shapes(sizes))
    return {k: float(v) * scale for k, v in zip(names, np.asarray(vector))}


def _check_feed(batches) -> list[str]:
    """The fed rows are what a trainer must be fed: y is x moved on by one
    token, and no two rows of the three steps are alike."""
    faults = []
    rows = set()
    n = 0
    for x, y in batches:
        if not np.array_equal(x[:, 1:], y[:, :-1]):
            faults.append("targets are not the inputs moved on by one token")
        for r in x:
            rows.add(r.tobytes())
            n += 1
    if len(rows) != n:
        faults.append(f"only {len(rows)} of {n} fed rows differ")
    return faults


class _Spans:
    """Host spans of the runner's own calls, kept in memory; with the
    profiler on they are written into its trace too, on the trace's clock."""

    def __init__(self, traced: bool):
        self.traced = traced
        self.items: list[tuple[str, float, float]] = []

    @contextlib.contextmanager
    def __call__(self, name: str):
        if not self.traced:
            yield
            return
        import jax

        t = time.perf_counter()
        with jax.profiler.TraceAnnotation(f"cb:{name}"):
            yield
        self.items.append((name, t, time.perf_counter()))


class Job:
    """The one compiled step with its state, fed as the window feeds it."""

    def __init__(self, ctx):
        import jax
        import jax.numpy as jnp

        from nanosandbox_tpu.ops.attention import resolve_attention_impl
        from nanosandbox_tpu.train import Trainer
        from nanosandbox_tpu.utils.compile_cache import enable_compile_cache

        cache_dir = enable_compile_cache()
        # Cache the small programs too (init, norms, fold_in): after the
        # first run of a cell in a checkout nothing compiles again.
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
        jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
        log(f"compile cache: {cache_dir}")
        prep_s = prepare_data(ctx.data_dir)
        log(f"data: {ctx.data_dir} (prepared in {prep_s:.1f} s; 0 = reused)")

        self.ctx = ctx
        self._since_start("imports, compile cache and data ready")
        self.cfg = train_config(ctx)
        self.sizes = sizes = model_sizes(ctx)
        self.trainer = trainer = Trainer(self.cfg)
        self.chips = len(jax.devices())
        self.key = weights.seed_key(ctx.seed)
        self._since_start("Trainer built")

        # The benchmark's weights, each chip's shard made where it lives,
        # and the optimizer's fresh state, in one jitted call from the seed.
        def make_state(key):
            params = weights.make_params(sizes, key)
            return {"params": params, "opt_state": trainer.tx.init(params),
                    "step": jnp.zeros((), jnp.int32)}

        want = jax.tree.map(lambda a: (a.shape, a.dtype), trainer.abstract_state)
        got = jax.tree.map(lambda a: (a.shape, a.dtype),
                           jax.eval_shape(make_state, self.key))
        if want != got:
            raise RuntimeError(
                "chipbench/weights.py no longer has the program's parameter "
                f"layout:\n program {want}\n benchmark {got}")
        self.state = jax.jit(
            make_state, out_shardings=trainer.state_shardings)(self.key)
        jax.block_until_ready(self.state)
        self._since_start("weights and optimizer state made on the device")
        self.step, _ = trainer.compiled_steps()
        if ctx.break_step is not None:  # chipbench/tests plant a fault here
            self.step = ctx.break_step(self.step)
        self.loader = trainer.make_loader("train", prefetch=True)
        self.rng = trainer.train_rng(ctx.seed + 7)
        self.spans = _Spans(ctx.trace)
        self.i = 0
        impl = resolve_attention_impl(trainer.model_cfg.attention_impl)
        log(f"attention_impl resolved: {impl}; loader native: "
            f"{self.loader.native}; loss_chunk_size resolved: "
            f"{trainer.loss_chunk_size}; mesh: {dict(trainer.mesh.shape)}; "
            f"chips: {self.chips}")

    def _since_start(self, what: str) -> None:
        log(f"set-up, {time.time() - self.ctx.t_start:6.1f} s in: {what}")

    def feed(self):
        with self.spans("loader_next"):
            xb, yb = next(self.loader)
        return xb, yb

    def call(self, xb, yb):
        """One step, as ``Trainer.run()`` makes it."""
        import jax

        with self.spans("to_global"):
            xg, yg = self.trainer.to_global(xb), self.trainer.to_global(yb)
        with self.spans("dispatch"):
            self.state, m = self.step(self.state, xg, yg,
                                      jax.random.fold_in(self.rng, self.i))
        self.i += 1
        return m

    def first_steps(self) -> dict:
        """The first three steps: warm-up, and what ``correct`` is decided
        on. Returns what the program showed of them, as host numbers."""
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
        seen["dp_leaf"] = _by_leaf(
            sizes, _change_norms(sizes)(self.state["params"], self.key))
        self._since_start("three steps made and read")
        log(f"first steps: loss {seen['loss']}, "
            f"grad norm {seen['grad_norm']}")
        for _ in range(PIPELINED_WARM_STEPS):
            m = self.call(*self.feed())
        float(m["loss"])
        self._since_start(f"{PIPELINED_WARM_STEPS} pipelined steps drained")
        return seen

    def window(self) -> dict:
        """Steps for ``--seconds`` seconds, then the drain, inside the
        window: all the work over all the time."""
        ctx, spans = self.ctx, self.spans
        log_interval = self.cfg.log_interval
        max_steps = (ctx.cell.get("trace", {}).get("max_steps")
                     if ctx.trace else None)
        logged, marks = [], []   # marks: (steps done, seconds) at each read-back
        if ctx.trace:
            ctx.start_trace()
        before = ctx.clock.snapshot()
        setup_s = time.time() - ctx.t_start
        first = self.i
        with spans("window"):
            t0 = time.perf_counter()
            deadline = t0 + ctx.seconds
            while time.perf_counter() < deadline and not (
                    max_steps and self.i - first >= max_steps):
                m = self.call(*self.feed())
                if log_interval > 0 and (self.i - 1) % log_interval == 0:
                    with spans("log_readback"):
                        logged.append(float(m["loss"]))
                    marks.append((self.i - first, time.perf_counter() - t0))
            with spans("drain"):
                logged.append(float(m["loss"]))  # waits for all enqueued
            t1 = time.perf_counter()
            marks.append((self.i - first, t1 - t0))
        compiled = ctx.clock.snapshot()["count"] - before["count"]
        if ctx.trace:
            ctx.stop_trace()
        log("read-backs (steps done, seconds into the window): "
            + ", ".join(f"({n}, {t:.4f})" for n, t in marks))
        return {"setup_s": setup_s, "t0": t0, "t1": t1,
                "steps": self.i - first, "logged": logged,
                "compile_in_setup": before, "compiled_in_window": compiled}

    def close(self) -> None:
        self.loader.close()
        self.state = self.step = self.trainer = None


def memory_peak_bytes() -> int:
    """Peak bytes held on the fullest chip. This runtime counts live arrays
    under ``bytes_in_use`` and compiled programs' temporaries under
    ``bytes_reserved`` (its ``largest_free_block_bytes`` is the limit less
    both), so the peak is the sum of the two peaks."""
    import jax

    peak = 0
    for d in jax.devices():
        st = d.memory_stats() or {}
        peak = max(peak, st.get("peak_bytes_in_use", 0)
                   + st.get("peak_bytes_reserved", 0))
    return peak


def run(ctx) -> dict:
    import jax

    job = Job(ctx)
    try:
        seen = job.first_steps()
        win = job.window()
        final_step = int(job.state["step"])
    finally:
        job.close()
    steps, window_s = win["steps"], win["t1"] - win["t0"]
    mem_peak = memory_peak_bytes()
    log(f"memory: {jax.devices()[0].memory_stats()}")
    log(f"window: {steps} steps in {window_s:.3f} s; last loss "
        f"{win['logged'][-1]:.4f}; peak memory {mem_peak} B")

    # -- the program's state is freed: now the reference -----------------------
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
        "flops_per_token": flops.train_flops_per_token(sizes),
    }


# -- the comparison that decides `correct` ------------------------------------

def reference_numbers(ctx, sizes: dict, batches, quant=None) -> dict:
    """The plain reference's three steps from the benchmark's weights, as
    host numbers. ``quant`` puts the control in the reference's place."""
    import jax
    import jax.numpy as jnp

    from chipbench.reference import gpt2

    opt = ctx.config["optimizer"]
    rows = ctx.cell["check"]["ref_rows_per_block"]
    kw = {} if quant is None else {"quant": quant}

    key = weights.seed_key(ctx.seed)

    @jax.jit
    def first(key):
        p = weights.make_params(sizes, key)
        zeros = jax.tree.map(jnp.zeros_like, p)
        return p, zeros, jax.tree.map(jnp.zeros_like, p)

    def one(params, m, v, x, y, count):
        loss, grads = gpt2.loss_and_grad(
            params, x, y, n_layer=sizes["n_layer"], n_head=sizes["n_head"],
            rows_per_block=rows, **kw)
        gnorm = gpt2.global_norm(grads)
        params, m, v, clipped = gpt2.adamw_step(params, m, v, grads, count, opt)
        return params, m, v, loss, gnorm, _leaf_norms(clipped)

    one = jax.jit(one, donate_argnums=(0, 1, 2)
                  if jax.default_backend() != "cpu" else ())

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


def leaf_gap(got: dict, ref: dict, keep=None) -> tuple[float, str]:
    """Worst leaf's |norm - reference norm| over the larger of the
    reference's norm of that leaf and of the median leaf."""
    keys = [k for k in ref if keep is None or k in keep]
    floor = statistics.median(ref[k] for k in keys)
    worst, where = 0.0, ""
    for k in keys:
        gap = abs(got[k] - ref[k]) / max(ref[k], floor, 1e-30)
        if not gap <= worst:        # a NaN is the worst there is
            worst, where = gap, k
    return worst, where


def gaps(got: dict, ref: dict) -> dict:
    """The four numbers compared, with the leaf each worst gap sits in."""
    g_floor = 1e-3 * statistics.median(ref["g1_leaf"].values())
    moved = {k for k, v in ref["g1_leaf"].items() if v >= g_floor}
    g1, g1_at = leaf_gap(got["g1_leaf"], ref["g1_leaf"])
    dp, dp_at = leaf_gap(got["dp_leaf"], ref["dp_leaf"], keep=moved)
    return {
        "loss_gap": max(abs(a - b) for a, b in zip(got["loss"], ref["loss"])),
        "grad_norm_gap": max(abs(a - b) / b for a, b in
                             zip(got["grad_norm"], ref["grad_norm"])),
        "g1_leaf_gap": g1, "dp_leaf_gap": dp,
        "_where": {"g1_leaf_gap": g1_at, "dp_leaf_gap": dp_at,
                   "left_out_of_dp": sorted(set(ref["g1_leaf"]) - moved)},
    }


def compare(ctx, sizes: dict, seen: dict) -> dict:
    ref = reference_numbers(ctx, sizes, seen["batches"])
    log(f"reference: loss {ref['loss']}, grad norm {ref['grad_norm']}")
    g = gaps(seen, ref)
    log(f"worst leaves: {g['_where']}")
    limits = ctx.cell["check"]["limits"]
    return {name: {"value": g[name], "limit": limits[name]} for name in limits}
