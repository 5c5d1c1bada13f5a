"""Iter-driven training loop: the nanoGPT train.py contract, TPU-native.

CLI contract (reference ipynb:71-78, 108-115):

    python -m nanosandbox_tpu.train [config/foo.py] --key=value ...

Loop semantics reimplemented from the reference's exercised surface
(SURVEY.md §2.3 #26): iter-driven (max_iters), periodic eval
(eval_interval, eval_iters) and logging (log_interval), cosine LR decay
with warmup (lr_decay_iters, min_lr), AdamW with weight decay on >=2D
params only, global-norm grad clip, checkpoints to out_dir, resume via
--init_from=resume, TensorBoard scalars.

TPU-native structure: ONE jit-compiled train step over a
(data, fsdp, model) mesh — the gradient allreduce that DDP/NCCL did
per-step (SURVEY.md §3.1 hot loop) is an XLA collective inserted by the
SPMD partitioner, riding ICI. Gradient accumulation is a lax.scan inside
the same compiled step. Batches are built per-host and assembled into
global arrays with jax.make_array_from_process_local_data.
"""

from __future__ import annotations

import os
import sys
import time
from functools import partial
from typing import Any

import numpy as np

from nanosandbox_tpu.config import TrainConfig, load_config
from nanosandbox_tpu.models import family_of
from nanosandbox_tpu.obs import opscopes, process_tracer
from nanosandbox_tpu.utils import tracecheck

# Peak bf16 FLOP/s per chip for MFU reporting (public spec-sheet numbers),
# keyed by device_kind. A device that is not here is an error, not a
# default; the CPU has no row and its runs print no MFU.
_PEAK_FLOPS = {
    "TPU v4": 275e12,
    "TPU v5 lite": 197e12,  # v5e
    "TPU v5": 459e12,  # v5p
    "TPU v5p": 459e12,
    "TPU v6 lite": 918e12,  # v6e / Trillium
}

_DEVICES = ("auto", "cpu", "tpu")


def _select_platform(device: str) -> None:
    """Map the reference's --device={cpu,cuda} switch (ipynb:77) to JAX.

    'auto' takes what JAX finds. 'cpu' forces the host platform
    (jax.config wins over the environment as long as no backend is
    initialized yet). 'tpu' is a DEMAND, not a hint: a run that asks
    for the chip and finds none fails here instead of training on the
    CPU and exiting 0.
    """
    if device not in _DEVICES:
        raise ValueError(f"--device={device!r}: expected one of {_DEVICES}")
    if device == "auto":
        return
    import jax

    if device == "tpu":
        backend = jax.default_backend()
        if backend != "tpu":
            raise RuntimeError(
                f"--device=tpu, but JAX's default backend is {backend!r} "
                f"(devices: {jax.devices()}). Refusing to run on it; use "
                "--device=auto or --device=cpu to run off the chip.")
        return
    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    try:
        jax.config.update("jax_platforms", "cpu")
    except RuntimeError:
        pass  # backend already initialized; caller chose the platform


def make_lr_schedule(cfg: TrainConfig):
    import optax

    if not cfg.decay_lr:
        return cfg.learning_rate
    warmup = optax.linear_schedule(0.0, cfg.learning_rate,
                                   max(cfg.warmup_iters, 1))
    decay_steps = max(cfg.lr_decay_iters - cfg.warmup_iters, 1)
    cosine = optax.cosine_decay_schedule(
        cfg.learning_rate, decay_steps,
        alpha=cfg.min_lr / cfg.learning_rate)
    return optax.join_schedules([warmup, cosine], [cfg.warmup_iters])


def make_optimizer(cfg: TrainConfig):
    import jax
    import optax

    schedule = make_lr_schedule(cfg)
    decay_mask = lambda params: jax.tree.map(lambda p: p.ndim >= 2, params)
    tx = optax.chain(
        optax.clip_by_global_norm(cfg.grad_clip) if cfg.grad_clip > 0
        else optax.identity(),
        optax.adamw(schedule, b1=cfg.beta1, b2=cfg.beta2,
                    weight_decay=cfg.weight_decay, mask=decay_mask),
    )
    return tx, schedule


def restore_for_inference(out_dir: str, *, step: int | None = None,
                          device: str = "auto", **overrides):
    """(trainer, state, step): rebuild a Trainer from a checkpoint's SAVED
    config for single-host inference/conversion — the shared restore dance
    of sample.py and models/convert.py.

    Normalizations every inference consumer needs: training-time
    model/sequence parallelism is dropped (Orbax restores any checkpoint
    onto a pure-DP mesh, and short-sequence decode runs on whatever host
    invokes it), and batch_size is replaced by a mesh-divisible dummy
    (inference builds its own batches; the saved value may not divide
    this host's device count). Caller ``overrides`` are applied last.
    """
    # Force the platform BEFORE jax initializes below: len(jax.devices())
    # would otherwise be the call that grabs an accelerator a training job
    # may already hold (the device='cpu' conversion path).
    _select_platform(device)
    import jax
    import orbax.checkpoint as ocp

    from nanosandbox_tpu.checkpoint import Checkpointer
    from nanosandbox_tpu.config import TrainConfig

    ckpt = Checkpointer(out_dir)
    step = step if step is not None else ckpt.latest_step()
    if step is None:
        raise FileNotFoundError(f"no checkpoint under {out_dir}/ckpt")
    restored = ckpt.mgr.restore(
        step, args=ocp.args.Composite(extra=ocp.args.JsonRestore()))
    cfg = TrainConfig(**{**restored["extra"]["config"], "device": device,
                         "init_from": "resume", "out_dir": out_dir})
    missing = family_of(cfg).inference
    if missing is not None:
        # sample.py, serve/ and models/convert.py all come through here.
        raise NotImplementedError(
            f"checkpoint {out_dir} holds a model of family "
            f"{cfg.model_family!r}: inference (sample.py, serve/, convert) "
            "runs models/gpt.py's cached decode only. Missing for this "
            f"family: {missing}")
    # Unconditional pure-DP normalization (idempotent for already-pure-DP
    # configs): a saved EXPLICIT mesh_dp (e.g. 8 from a v4-8 run) must not
    # survive onto a host with a different device count any more than
    # fsdp/sp/tp may.
    defaults = dict(
        attention_impl="auto" if cfg.attention_impl == "ring"
        else cfg.attention_impl,
        mesh_sp=1, mesh_fsdp=1, mesh_tp=1, mesh_dp=-1, mesh_slices=0,
        shard_params=False,
        batch_size=len(jax.devices()), gradient_accumulation_steps=1)
    cfg = cfg.replace(**{**defaults, **overrides})
    trainer = Trainer(cfg)
    state, _ = ckpt.restore(trainer.abstract_state, step)
    ckpt.close()
    return trainer, state, step


def _lower_step(train_step, operands: tuple, budgets):
    """The jitted train step lowered on abstract operands, off the hot path
    (memory_report, step_op_parts). The extra trace this may cost is allowed
    for by name: the budget of the live loop's one trace is raised by one
    for the lowering and put back if it traced nothing."""
    name = "train_step"
    budget, traced = budgets.budgets()[name], budgets.counts()[name]
    budgets.register(name, budget + 1)
    try:
        return train_step.lower(*operands)
    finally:
        if budgets.counts()[name] == traced:
            budgets.register(name, budget)


def _step_op_maps(train_step, operands: tuple, budgets) -> tuple:
    """Trainer.step_op_parts with the stages beside it (opscopes.op_maps),
    over what a lowering needs and no more."""
    lowered = _lower_step(train_step, operands, budgets)
    return opscopes.op_maps(lowered.compile().as_text())


class Trainer:
    """Owns model/optimizer/state/mesh and the compiled step functions.

    mesh_devices: optional explicit device list for the mesh — the
    AOT-validation path (__graft_entry__.dryrun_multichip_full) passes
    abstract topology devices here to compile real-shape programs for a
    TPU target the host doesn't have. Normal training leaves it None
    (mesh over jax.devices()). Not a config field: device objects are
    process-local and must never serialize into checkpoints.
    """

    def __init__(self, cfg: TrainConfig, mesh_devices: list | None = None):
        _select_platform(cfg.device)
        import jax

        # The training path's one tracer (obs.process_tracer): this
        # object, the loader, tracecheck.host_sync and the compile-cache
        # listeners record into it. With the profiler's annotation as its
        # hook every span is also a TraceMe: in any xplane recorded over
        # this process (--profile_steps, a benchmark's window) the spans
        # lie on the host's `python` line under their own names, on the
        # device's clock. Recording is a dict build and a deque append;
        # nothing is read back from the device.
        self.tracer = process_tracer()
        self.tracer.annotate = jax.profiler.TraceAnnotation
        sid = self.tracer.begin("trainer_init", cat="train")
        try:
            self._init(cfg, mesh_devices)
        finally:
            # What the family's build says of its model. 'attn_layout':
            # which HBM interface the step's attention takes ('btc': the
            # kernels read qkv (B, T, 3C) where it lies; 'bhtd': after the
            # transposes), decided from shapes and mesh at trace time.
            self.tracer.end(sid, args={"model_family": cfg.model_family,
                                       **getattr(self, "_describe", {})})

    def _init(self, cfg: TrainConfig, mesh_devices: list | None) -> None:
        import jax

        from nanosandbox_tpu.data.loader import BinDataset
        from nanosandbox_tpu.parallel.distributed import (
            maybe_initialize_distributed)
        from nanosandbox_tpu.parallel.mesh import (batch_sharding, make_mesh,
                                                   set_current_mesh)
        from nanosandbox_tpu.parallel.sharding import param_shardings

        self.cfg = cfg
        # Everything below that depends on which model this is asks the
        # family's module (models/__init__.py lists the questions).
        self.family = family = family_of(cfg)
        self.multi_host = maybe_initialize_distributed(
            cfg.coordinator_address, cfg.num_processes, cfg.process_id)
        self.process_index = jax.process_index()
        self.process_count = jax.process_count()
        self.is_main = self.process_index == 0

        span = partial(self.tracer.span, cat="train")
        with span("dataset_open"):
            self.dataset = BinDataset(cfg.data_dir, cfg.dataset)
        # Pretrained import (reference `--init_from=gpt2*`): the weights'
        # own config dictates the architecture, so cfg is the family's from
        # here on.
        from nanosandbox_tpu.models.convert import resolve_init_from
        self._hf_params = None
        # 'hf:' (empty path) is not one
        self._pretrained = bool(resolve_init_from(cfg.init_from))
        family.check(cfg, self._pretrained)
        if self._pretrained:
            cfg, self._hf_params = family.pretrained(cfg, self.dataset.meta)
            self.cfg = cfg
            if self.is_main:
                print(f"initializing from pretrained {cfg.init_from}: "
                      f"{cfg.n_layer}L/{cfg.n_head}H/{cfg.n_embd}d, "
                      f"vocab {cfg.vocab_size}")

        vocab = cfg.vocab_size or self.dataset.vocab_size
        self.model_cfg = family.model_config(cfg, vocab)

        with span("make_mesh"):
            if cfg.mesh_slices:
                from nanosandbox_tpu.parallel.mesh import make_hybrid_mesh
                self.mesh = make_hybrid_mesh(cfg.mesh_dp, cfg.mesh_fsdp,
                                             cfg.mesh_tp, cfg.mesh_sp,
                                             num_slices=cfg.mesh_slices,
                                             devices=mesh_devices)
            else:
                self.mesh = make_mesh(cfg.mesh_dp, cfg.mesh_fsdp, cfg.mesh_tp,
                                      cfg.mesh_sp, devices=mesh_devices)
        set_current_mesh(self.mesh)
        # The mesh is bound to the model explicitly (ring attention needs
        # it); the global above is only a fallback for standalone model use.
        self.model, self._describe = family.build(self.model_cfg, self.mesh)
        self.attn_layout = self._describe["attn_layout"]
        # Only a family whose q/k pass exists as a kernel says which runs.
        self.qk_prep = self._describe.get("qk_prep")
        self.batch_sharding = batch_sharding(self.mesh)
        # Fail fast on batch/mesh mismatches instead of surfacing them later
        # as opaque pjit sharding errors (docs/playbook.md pitfalls).
        dp_shards = self.mesh.shape["data"] * self.mesh.shape["fsdp"]
        if cfg.batch_size % dp_shards:
            raise ValueError(
                f"batch_size {cfg.batch_size} must be divisible by "
                f"data*fsdp mesh shards ({dp_shards})")
        from nanosandbox_tpu.config import resolve_loss_chunk_size

        self.loss_chunk_size = resolve_loss_chunk_size(
            cfg.loss_chunk_size, cfg.batch_size // dp_shards,
            cfg.block_size, self.model_cfg.vocab_size,
            seq_shards=self.mesh.shape["seq"])
        if cfg.sequences_per_iter % self.process_count:
            raise ValueError(
                f"batch_size*accum {cfg.sequences_per_iter} must be "
                f"divisible by num_processes ({self.process_count})")
        if cfg.batch_size % self.process_count:
            # estimate_loss builds per-process eval batches of
            # batch_size // process_count rows; accumulation does NOT
            # carry the divisibility there, so a config like batch 2 /
            # accum 4 / 8 processes would crash mid-run at the first
            # eval with a 0-row batch. Fail at construction instead.
            raise ValueError(
                f"batch_size {cfg.batch_size} must be divisible by "
                f"num_processes ({self.process_count}) for evaluation")
        if cfg.block_size % self.mesh.shape["seq"]:
            raise ValueError(
                f"block_size {cfg.block_size} must be divisible by the "
                f"seq mesh axis ({self.mesh.shape['seq']})")
        if cfg.mesh_sp > 1 and cfg.attention_impl != "ring":
            raise ValueError(
                "mesh_sp > 1 requires attention_impl='ring' (other impls "
                "compute attention over the local sequence shard only)")
        if (cfg.attention_impl == "ring" and cfg.mesh_tp > 1
                and cfg.n_head % cfg.mesh_tp):
            raise ValueError(
                f"attention_impl='ring' shards heads over model: n_head "
                f"{cfg.n_head} must be divisible by mesh_tp {cfg.mesh_tp}")
        with span("make_optimizer"):
            self.tx, self.lr_schedule = make_optimizer(cfg)

        # Abstract state -> shardings -> sharded init.
        with span("abstract_state"):
            abstract = jax.eval_shape(self._init_state,
                                      jax.random.key(cfg.seed))
            self.state_shardings = {
                "params": param_shardings(
                    self.mesh, abstract["params"],
                    shard_params=cfg.shard_params, tp=cfg.mesh_tp > 1),
                "opt_state": param_shardings(
                    self.mesh, abstract["opt_state"],
                    shard_params=cfg.shard_params, tp=cfg.mesh_tp > 1),
                "step": jax.sharding.NamedSharding(
                    self.mesh, jax.sharding.PartitionSpec()),
            }
            self.abstract_state = jax.tree.map(
                lambda a, s: jax.ShapeDtypeStruct(a.shape, a.dtype,
                                                  sharding=s),
                abstract, self.state_shardings,
                is_leaf=lambda x: isinstance(x, jax.ShapeDtypeStruct))

        self._train_step = None
        self._eval_step = None
        # Retrace budgets for the compiled steps (utils.tracecheck):
        # each is ONE program — batch/sequence shapes are fixed by the
        # config — so a second trace means something specialized the
        # step (the failure mode jaxlint's nonstatic-shape rule hunts
        # statically) and raises instead of silently recompiling.
        self.tracecheck = tracecheck.TraceBudgetRegistry()

    # -- state ---------------------------------------------------------------

    def _init_state(self, rng) -> dict[str, Any]:
        import jax.numpy as jnp

        # The dummy init batch must satisfy the same sharding divisibility
        # as real batches (ring attention's shard_map validates shapes at
        # trace time): B divisible by data*fsdp, T by the seq axis.
        dp_shards = self.mesh.shape["data"] * self.mesh.shape["fsdp"]
        sp = self.mesh.shape["seq"]
        B = max(2, dp_shards)
        T = min(self.cfg.block_size, max(8, sp))
        T = max(sp, (T // sp) * sp)
        dummy = jnp.zeros((B, T), jnp.int32)
        variables = self.model.init(rng, dummy, deterministic=True)
        params = variables["params"]
        opt_state = self.tx.init(params)
        return {"params": params, "opt_state": opt_state,
                "step": jnp.zeros((), jnp.int32)}

    def init_state(self) -> dict[str, Any]:
        import jax

        init = jax.jit(self._init_state,
                       out_shardings=self.state_shardings)
        return init(jax.random.key(self.cfg.seed))

    def pretrained_state(self) -> dict[str, Any]:
        """Training state from the imported HF weights: each converted
        leaf is placed with its mesh sharding (so FSDP fine-tuning of a
        pretrained model shards on arrival), fresh optimizer state.

        Single-shot: the host-side float32 copy is released once placed
        (gpt2-xl is ~6 GB of numpy that must not stay pinned for the whole
        run), so a second call raises instead of silently re-initializing.
        """
        import jax
        import jax.numpy as jnp

        if self._hf_params is None:
            raise RuntimeError(
                "pretrained weights already consumed (pretrained_state is "
                "single-shot) or init_from is not a pretrained source")
        dtype = jnp.dtype(self.cfg.param_dtype)
        # Cast on host, then ONE placement directly onto the sharding:
        # jnp.asarray would first copy to the default device and the
        # device_put would then reshard device-to-device — double
        # transfer plus a transient full replica for a gpt2-xl import.
        params = jax.tree.map(
            lambda x, s: jax.device_put(np.asarray(x, dtype), s),
            self._hf_params, self.state_shardings["params"])
        opt_state = jax.jit(
            self.tx.init,
            out_shardings=self.state_shardings["opt_state"])(params)
        step = jax.device_put(jnp.zeros((), jnp.int32),
                              self.state_shardings["step"])
        self._hf_params = None
        return {"params": params, "opt_state": opt_state, "step": step}

    # -- compiled steps ------------------------------------------------------

    def _loss_fn(self, params, x, y, rng):
        """(loss, aux): aux is what the family's ``apply`` reports of a
        step beside its loss ({} for a dense model; expert layers' rows
        held, fullest expert and dropped slots where there are any)."""
        import jax

        from nanosandbox_tpu.models.loss import (
            chunked_cross_entropy_loss, cross_entropy_loss,
            sharded_chunked_cross_entropy_loss)

        deterministic = self.cfg.dropout == 0.0 or rng is None
        apply = partial(
            self.family.apply, self.model, params, x,
            deterministic=deterministic,
            rngs=None if deterministic else {"dropout": rng})
        # The head matmul and the cross entropy are no flax module's, so
        # they get a scope of their own (obs.opscopes reads it back from
        # the compiled step): round them alone, not round this whole
        # function, whose scope would sit inside the jvp(...) wrapper of
        # every op of the model.
        head_scope = jax.named_scope("lm_head_loss")
        # Chunked head+loss keeps (B, T, vocab) logits out of HBM. Under
        # sequence parallelism the scan runs per-shard inside shard_map
        # (a scan over the T-sharded dim would otherwise force gathers,
        # and full logits at long context defeat the ring's memory story).
        if self.loss_chunk_size > 0:
            hidden, aux = apply(return_hidden=True)
            head = self.family.head(params)  # (vocab, C), tied or its own
            with head_scope:
                if self.mesh.shape["seq"] == 1:
                    return chunked_cross_entropy_loss(
                        hidden, head, y,
                        chunk_size=self.loss_chunk_size,
                        compute_dtype=self.cfg.compute_dtype), aux
                return sharded_chunked_cross_entropy_loss(
                    hidden, head, y, mesh=self.mesh,
                    chunk_size=self.loss_chunk_size,
                    compute_dtype=self.cfg.compute_dtype), aux
        # Full logits: the head's matmul is the model's own (GPT-2's
        # `wte.attend`, which opscopes also counts as the head).
        logits, aux = apply(return_hidden=False)
        with head_scope:
            return cross_entropy_loss(logits, y), aux

    def train_rng(self, seed: int):
        """Root key of the TRAINING rng stream (dropout masks), honoring
        cfg.rng_impl. Init/eval keys stay on the default impl — they are
        not per-step costs and their determinism contract predates the
        knob."""
        import jax

        return jax.random.key(seed, impl=self.cfg.rng_impl)

    def _train_step_fn(self, state, x, y, rng):
        import jax
        import jax.numpy as jnp
        from jax import lax

        accum = self.cfg.gradient_accumulation_steps
        params = state["params"]

        loss_and_grad = jax.value_and_grad(self._loss_fn, has_aux=True)
        if accum == 1:
            (loss, aux), grads = loss_and_grad(params, x, y, rng)
        else:
            # x is (accum * batch_size, T): nanoGPT semantics — accumulation
            # multiplies the data per optimizer step, micro-batch stays
            # batch_size.
            micro = x.shape[0] // accum
            xs = x.reshape(accum, micro, -1)
            ys = y.reshape(accum, micro, -1)

            def body(carry, xy):
                loss_acc, grad_acc = carry
                xm, ym, i = xy
                r = None if rng is None else jax.random.fold_in(rng, i)
                (l, aux), g = loss_and_grad(params, xm, ym, r)
                return (loss_acc + l,
                        jax.tree.map(jnp.add, grad_acc, g)), aux

            with jax.named_scope("accum"):
                zero = jax.tree.map(jnp.zeros_like, params)
                (loss, grads), aux = lax.scan(
                    body, (jnp.zeros(()), zero),
                    (xs, ys, jnp.arange(accum)))
                # Counters of the micro-steps: the fullest one of each.
                aux = jax.tree.map(lambda a: a.max(axis=0), aux)
                loss = loss / accum
                grads = jax.tree.map(lambda g: g / accum, grads)

        import optax
        with jax.named_scope("optimizer"):
            updates, opt_state = self.tx.update(grads, state["opt_state"],
                                                params)
            params = optax.apply_updates(params, updates)
        new_state = {"params": params, "opt_state": opt_state,
                     "step": state["step"] + 1}
        with jax.named_scope("grad_norm"):
            grad_norm = optax.global_norm(grads)
        return new_state, {"loss": loss, "grad_norm": grad_norm, **aux}

    def _eval_step_fn(self, state, x, y):
        return self._loss_fn(state["params"], x, y, None)[0]

    def compiled_steps(self):
        if self._train_step is None:
            self._build_steps()
            if self.cfg.compile:
                # The map from the step's instructions to parts of the
                # model is made only when somebody asks
                # (obs.opscopes.step_parts). What the provider holds is
                # what a lowering needs, so it still answers after the
                # trainer's owner has dropped its state and loader.
                self._op_maps = partial(
                    _step_op_maps, self._train_step, self._step_operands(),
                    self.tracecheck)
                opscopes.set_provider(self._op_maps)
        return self._train_step, self._eval_step

    def _build_steps(self) -> None:
        import jax

        step = partial(self._train_step_fn)
        if not self.cfg.compile:
            # Uncompiled steps run the body EVERY call — a call counter
            # would not be a trace counter, so no guard.
            self._train_step = step
            self._eval_step = self._eval_step_fn
            return
        # CPU jit ignores donation (and warns every compile); donate the
        # train state only on accelerators, the same gate the serve
        # engine applies to its pool/state.
        on_accel = jax.default_backend() != "cpu"
        step = self.tracecheck.guard("train_step", 1)(step)
        eval_fn = self.tracecheck.guard("eval_step", 1)(self._eval_step_fn)
        self._train_step = jax.jit(
            step,
            in_shardings=(self.state_shardings, self.batch_sharding,
                          self.batch_sharding, None),
            out_shardings=(self.state_shardings, None),
            donate_argnums=(0,) if on_accel else ())
        # jaxlint: disable=unconstrained-output -- scalar loss output: nothing mesh-sized to constrain
        self._eval_step = jax.jit(
            eval_fn,
            in_shardings=(self.state_shardings, self.batch_sharding,
                          self.batch_sharding))

    def _step_operands(self) -> tuple:
        """The train step's operands in the abstract: no array but a key."""
        import jax
        import jax.numpy as jnp

        batch_sds = jax.ShapeDtypeStruct(
            (self.cfg.sequences_per_iter, self.cfg.block_size), jnp.int32,
            sharding=self.batch_sharding)
        return (self.abstract_state, batch_sds, batch_sds, self.train_rng(0))

    def step_op_parts(self) -> dict:
        """{instruction name: part of the model} for the train step's
        executable (obs.opscopes), by lowering the same jitted step on the
        abstract state and compiling. Where the step has already run in
        this process that finds the executable in memory (0.3-0.7 s at
        124M / 350M on a v5e, PERF.md); before its first run it is a
        load out of the persistent cache or a compile. So on demand only:
        never in the loop, and in set-up only if asked. The names are
        those of the code that compiled the executable: see
        utils/compile_cache.py on a cache that older code filled."""
        if not self.cfg.compile:
            raise ValueError("step_op_parts requires compile=True")
        self.compiled_steps()
        return self._op_maps()[0]

    def memory_report(self) -> dict:
        """XLA's compile-time memory analysis of the train step — the
        'will this config fit HBM?' answer without burning a step (the
        760M/1.5B configs live or die by this, BASELINE.md scaling notes).

        AOT-lowers on abstract inputs; costs one extra compile, which is
        why it sits behind --memory_report instead of running always.
        Keys are bytes, per device."""
        import jax.numpy as jnp

        if not self.cfg.compile:
            raise ValueError("memory_report requires compile=True")
        train_step, _ = self.compiled_steps()
        ma = _lower_step(train_step, self._step_operands(),
                         self.tracecheck).compile().memory_analysis()
        if ma is None:  # backend without memory analysis
            return {}
        self.flops_per_iter()  # populates self._n_params
        itemsize = jnp.dtype(self.cfg.param_dtype).itemsize
        return {
            "params_bytes": itemsize * self._n_params,
            "state_bytes": ma.argument_size_in_bytes,   # params+opt+batch
            "temp_bytes": ma.temp_size_in_bytes,        # activations/workspace
            "output_bytes": ma.output_size_in_bytes,
            "code_bytes": ma.generated_code_size_in_bytes,
            # alias_size: the donated train state appears in BOTH argument
            # and output sizes (donate_argnums=(0,)); the aliased bytes
            # occupy HBM once, so subtract them or the preflight would
            # overstate by the whole params+opt footprint.
            "total_bytes": (ma.argument_size_in_bytes
                            + ma.temp_size_in_bytes
                            + ma.output_size_in_bytes
                            + ma.generated_code_size_in_bytes
                            - ma.alias_size_in_bytes),
        }

    # -- sharding analysis (shardcheck program enumeration) ------------------

    def shardcheck_programs(self) -> list:
        """ProgramSpecs for the comms analyzer (analysis/shardcheck):
        the train and eval steps AOT-lowered under this trainer's mesh
        with the REAL in/out shardings. Fresh ``jax.jit`` objects, not
        the guarded ``compiled_steps`` ones — an analysis lower must not
        consume the tracecheck retrace budgets the live loop enforces.

        Expectations encode the mesh contract: full param gathers are
        the point of ZeRO-3 (fsdp) and ring attention's transposes
        (seq), TP activations gather over model — but the data axis
        carries gradient all-reduces ONLY, and nothing may materialize
        a sharded tensor on any other axis."""
        import jax
        import jax.numpy as jnp

        from nanosandbox_tpu.analysis.shardcheck import (Expectations,
                                                         ProgramSpec)

        rows = self.cfg.sequences_per_iter
        batch = jax.ShapeDtypeStruct((rows, self.cfg.block_size), jnp.int32,
                                     sharding=self.batch_sharding)
        key = self.train_rng(0)
        expect = Expectations(gather_ok_axes=("fsdp", "seq", "model"),
                              allreduce_only_axes=("data",))

        def lower_train():
            return jax.jit(
                self._train_step_fn,
                in_shardings=(self.state_shardings, self.batch_sharding,
                              self.batch_sharding, None),
                out_shardings=(self.state_shardings, None),
            ).lower(self.abstract_state, batch, batch, key)

        def lower_eval():
            # jaxlint: disable=unconstrained-output -- scalar loss output: nothing mesh-sized to constrain
            return jax.jit(
                self._eval_step_fn,
                in_shardings=(self.state_shardings, self.batch_sharding,
                              self.batch_sharding),
            ).lower(self.abstract_state, batch, batch)

        return [
            ProgramSpec(name="train_step", lower=lower_train,
                        abstract_args=(self.abstract_state, batch, batch),
                        expect=expect, tags=("train",)),
            ProgramSpec(name="eval_step", lower=lower_eval,
                        abstract_args=(self.abstract_state, batch, batch),
                        expect=expect, tags=("train",)),
        ]

    # -- data ----------------------------------------------------------------

    def make_loader(self, split: str, start_step: int = 0, prefetch=True):
        from nanosandbox_tpu.data.loader import BatchLoader

        return BatchLoader(
            self.dataset, split, self.cfg.sequences_per_iter,
            self.cfg.block_size,
            seed=self.cfg.seed, process_index=self.process_index,
            num_processes=self.process_count, start_step=start_step,
            prefetch=prefetch)

    def to_global(self, local: np.ndarray):
        import jax

        global_batch = local.shape[0] * self.process_count
        global_shape = (global_batch,) + local.shape[1:]
        with self.tracer.span("to_global", cat="train",
                              args={"bytes": local.nbytes}):
            return jax.make_array_from_process_local_data(
                self.batch_sharding, local, global_shape)

    def train_iter(self, state, loader, rng, iter_num: int):
        """One iteration of the step path, as run() makes it and as any
        other caller may drive it: the next batch, its two host-to-device
        assemblies, the step's key and the step. Returns (state, metrics)
        as the compiled step does; nothing is read back."""
        import jax

        train_step, _ = self.compiled_steps()
        with self.tracer.span("train_iter", cat="train", step=iter_num):
            xb, yb = next(loader)
            xg, yg = self.to_global(xb), self.to_global(yb)
            with self.tracer.span("dispatch", cat="train"):
                return train_step(state, xg, yg,
                                  jax.random.fold_in(rng, iter_num))

    def _count_expert_rows(self, metrics: dict, iter_num: int) -> None:
        """The expert layers' counters of a step whose loss has been read
        (so the step is done and this waits for nothing): an instant in the
        process tracer, rows held by layer, the fullest expert's rows and
        the held (token, slot) pairs no chunk of the sorted walk covered
        (ops/moe.py: 0 by construction), and ``chunks_run`` by layer: the
        chunks of that walk that held pairs, from the rows held against a
        chunk's rows (1 at the expected load: the first chunk's results
        used as they are, no sum over chunks). A dropped pair is a step
        computed wrong: said loudly, once a log step."""
        if "moe_dropped" not in metrics:
            return
        # the family whose step counts these has imported models/experts.py
        from nanosandbox_tpu.models.experts import walk_rows

        got = {k: np.asarray(metrics[k]).tolist()
               for k in ("moe_held", "moe_max_rows", "moe_dropped")}
        # the model's own config: ``experts_held`` resolved ((0, 0) = all)
        rows, _ = walk_rows(self.model_cfg,
                            self.cfg.batch_size * self.cfg.block_size)
        got["chunks_run"] = [max(1, -(-held // rows))
                             for held in got["moe_held"]]
        self.tracer.instant("moe_rows", cat="train",
                            args={"iter": iter_num, **got})
        if sum(got["moe_dropped"]) and self.is_main:
            print(f"iter {iter_num}: {sum(got['moe_dropped'])} routed "
                  f"(token, slot) pairs DROPPED (rows held by layer "
                  f"{got['moe_held']}): ops/moe.py's chunks no longer "
                  "cover its own bound", file=sys.stderr)

    # -- evaluation (nanoGPT estimate_loss) ----------------------------------

    def estimate_loss(self, state, eval_iters: int | None = None) -> dict:
        import jax.numpy as jnp

        eval_iters = eval_iters or self.cfg.eval_iters
        _, eval_step = self.compiled_steps()
        sid = self.tracer.begin("eval", cat="train",
                                args={"eval_iters": eval_iters})
        out = {}
        for split in ("train", "val"):
            # Build ALL host batches up front, THEN enqueue every eval
            # step, THEN read ONE scalar. The host-side gather (memmap
            # window copies, ~ms each) used to sit inside the enqueue
            # loop, serializing with eval dispatch; hoisted, the device
            # chews through back-to-back steps while the host is already
            # done gathering. And under async dispatch each float() is a
            # host<->device sync that drains the queue, so a per-step
            # readback would serialize eval_iters steps per split with
            # the host instead of letting them run back to back.
            batches = [
                self.dataset.sample_batch(
                    split, 1_000_000 + i,
                    self.cfg.batch_size // self.process_count,
                    self.cfg.block_size, seed=self.cfg.seed + 1,
                    process_index=self.process_index)
                for i in range(eval_iters)
            ]
            losses = [eval_step(state, self.to_global(xb), self.to_global(yb))
                      for xb, yb in batches]
            # tracecheck.host_sync is THE deliberate readback: the one
            # scalar sync per split the comment above promises, logged
            # so profiler windows can report their sync count.
            out[split] = tracecheck.host_sync("eval-readback",
                                              jnp.stack(losses).mean())
        self.tracer.end(sid, {f"{k}_loss": round(v, 6)
                              for k, v in out.items()})
        return out

    # -- MFU -----------------------------------------------------------------

    def flops_per_iter(self) -> float:
        from nanosandbox_tpu.models.common import count_params
        import jax

        if not hasattr(self, "_n_params"):
            abstract = jax.eval_shape(self._init_state,
                                      jax.random.key(0))
            self._n_params = count_params(abstract["params"])
        return self.family.flops_per_token(
            self.model_cfg, self.cfg.block_size,
            self._n_params) * self.cfg.tokens_per_iter

    def peak_flops(self) -> float:
        import jax

        kind = jax.devices()[0].device_kind
        # Longest key first: "TPU v5 lite" must not match the "TPU v5" row.
        for k in sorted(_PEAK_FLOPS, key=len, reverse=True):
            if kind.lower().startswith(k.lower()):
                return _PEAK_FLOPS[k] * len(jax.devices())
        raise ValueError(
            f"no peak FLOP/s for device_kind {kind!r}: add its published "
            f"peak to train._PEAK_FLOPS (known: {sorted(_PEAK_FLOPS)})")

    def mfu(self, step_s: float) -> float | None:
        """Model FLOP/s utilization of one step of ``step_s`` seconds —
        None on a cpu backend, which has no peak and reports no MFU."""
        import jax

        if jax.default_backend() == "cpu":
            return None
        return self.flops_per_iter() / max(step_s, 1e-9) / self.peak_flops()

    # -- main loop -----------------------------------------------------------

    def _write_window_spans(self) -> None:
        """The profiler window's spans, kept in memory until now: as
        Chrome JSON beside the xplane, and in seconds by name."""
        import json

        last_s = (time.perf_counter_ns() - self._profile_t0_ns) / 1e9
        path = os.path.join(self.profile_dir, "spans.json")
        with open(path, "w", encoding="utf-8") as f:
            json.dump(self.tracer.export_chrome(last_s=last_s), f)
        by_name: dict[str, float] = {}
        for s in self.tracer.spans(last_s=last_s):
            by_name[s.name] = by_name.get(s.name, 0.0) + s.dur
        print(f"spans of the window -> {path}; seconds by span: "
              + ", ".join(f"{k} {v:.4f}" for k, v in
                          sorted(by_name.items(), key=lambda kv: -kv[1])))

    def run(self) -> dict:
        import jax

        from nanosandbox_tpu.checkpoint import Checkpointer
        from nanosandbox_tpu.utils.metrics import MetricsWriter

        cfg = self.cfg
        ckpt = Checkpointer(cfg.out_dir, keep=cfg.keep_checkpoints)

        iter_num = 0
        best_val_loss = 1e9
        # 'auto' = resume when a checkpoint exists, else scratch — the mode
        # k8s restarts use: a crashed pod comes back with the same identity
        # (SURVEY.md §5 restart-with-stable-identity) and must continue, but
        # the very first boot has nothing to restore.
        init_from = cfg.init_from
        if init_from == "auto":
            init_from = ("resume" if ckpt.latest_step() is not None
                         else "scratch")
        if init_from == "resume":
            state, extra = ckpt.restore(self.abstract_state)
            # One-time resume readback.
            iter_num = int(extra.get("iter_num", int(state["step"])))
            best_val_loss = float(extra.get("best_val_loss", 1e9))
            if self.is_main:
                print(f"resumed from iter {iter_num} "
                      f"(best val loss {best_val_loss:.4f})")
        elif self._pretrained:
            state = self.pretrained_state()  # raises if already consumed
        else:
            state = self.init_state()

        self.compiled_steps()
        writer = MetricsWriter(cfg.resolved_log_dir, cfg.run_name,
                               enabled=self.is_main,
                               tensorboard=cfg.tensorboard)
        if cfg.memory_report and not cfg.compile and self.is_main:
            print("memory_report skipped: requires compile=True")
        if cfg.memory_report and cfg.compile:
            mem = self.memory_report()
            if mem and self.is_main:
                gb = 1 << 30
                print(f"memory report (per device): params "
                      f"{mem['params_bytes'] / gb:.2f} GB, state+batch "
                      f"{mem['state_bytes'] / gb:.2f} GB, activations/temp "
                      f"{mem['temp_bytes'] / gb:.2f} GB, total "
                      f"{mem['total_bytes'] / gb:.2f} GB")
            if mem:
                writer.log(0, {f"mem/{k}": float(v)
                               for k, v in mem.items()})
        loader = self.make_loader("train", start_step=iter_num)
        rng = self.train_rng(cfg.seed + 7)
        writer.write_header({
            # estimate_loss draws the SAME batches every eval (step index
            # 1_000_000+i, seed seed+1): deliberate low-variance gating,
            # but "best val loss" is therefore ranked on one frozen
            # eval_iters-batch sample.
            "eval_batch_policy": "fixed", "eval_seed": cfg.seed + 1,
            "eval_iters": cfg.eval_iters,
            # Which offset sampler the loader actually resolved — the
            # native (csrc) xorshift128+ path and the numpy Philox
            # fallback draw DIFFERENT batch streams from the same seed,
            # so cross-machine reproduction needs this recorded.
            "offset_sampler": ("native-xorshift128+" if loader.native
                               else "numpy-philox"),
            "rng_impl": cfg.rng_impl,
        })

        tokens_per_iter = cfg.tokens_per_iter
        last_loss = float("nan")
        last_eval: tuple[int, dict] | None = None
        # --profile_steps=a:b — jax.profiler trace of iters [a, b), written
        # next to the TB events (the README runbook's profiling workflow;
        # SURVEY.md §5 tracing hook point). Validated in TrainConfig.
        self._profiling = False
        prof_range = cfg.profile_range() if self.is_main else None
        if prof_range:
            self.profile_dir = os.path.join(cfg.resolved_log_dir, "profile")
        t0 = time.time()
        window_start_iter = iter_num - 1  # sync precedes step iter_num
        try:
            while iter_num < cfg.max_iters:
                # iter 0 included: every curve gets a scratch-loss anchor
                # (round-4 VERDICT weak #6 — the "resumes at 2.22 vs
                # scratch 11.0" style argument needs the scratch point in
                # the metrics stream). Checkpoint saving below still
                # requires iter_num > 0.
                if (cfg.eval_interval > 0
                        and iter_num % cfg.eval_interval == 0):
                    losses = self.estimate_loss(state)
                    last_eval = (iter_num, losses)
                    if self.is_main:
                        print(f"step {iter_num}: train loss "
                              f"{losses['train']:.4f}, val loss "
                              f"{losses['val']:.4f}")
                    writer.log(iter_num, {"eval/train_loss": losses["train"],
                                          "eval/val_loss": losses["val"]})
                    # The iter-0 anchor is metrics-only: it must not seed
                    # best_val_loss, or a run that never beats its
                    # random-init val loss (too-high LR, tiny corpus)
                    # would end with ZERO checkpoints — the save below is
                    # gated on iter_num > 0 but the bar would already be
                    # set at the scratch loss.
                    if iter_num > 0 and (losses["val"] < best_val_loss
                                         or cfg.always_save_checkpoint):
                        best_val_loss = min(best_val_loss, losses["val"])
                        sid = self.tracer.begin("checkpoint_save",
                                                cat="train",
                                                args={"iter": iter_num})
                        ckpt.save(iter_num, state,
                                  {"iter_num": iter_num,
                                   "best_val_loss": best_val_loss,
                                   "config": cfg.to_dict()})
                        self.tracer.end(sid)
                    if cfg.eval_only:
                        break
                    # Eval + checkpoint time is reported on its own lines;
                    # restart the throughput window so the next logged
                    # tok/s reflects training steps only. iter_num - 1,
                    # not iter_num: this sync point is BEFORE step
                    # iter_num runs, while the log-step sync is after its
                    # step completes — the next window spans steps
                    # [iter_num, next_log] inclusive.
                    t0, window_start_iter = time.time(), iter_num - 1

                if prof_range and iter_num == prof_range[0]:
                    jax.profiler.start_trace(self.profile_dir)
                    self._profiling = True
                    self._profile_span = self.tracer.begin(
                        "profiler_window", cat="train", step=iter_num,
                        args={"start": prof_range[0],
                              "stop": prof_range[1]})
                    self._profile_t0_ns = time.perf_counter_ns()
                    # Snapshot the sync ledger so the window report
                    # below describes the TRACED REGION's syncs, not the
                    # process-lifetime totals.
                    self._profile_sync_mark = tracecheck.sync_counts()

                state, metrics = self.train_iter(state, loader, rng,
                                                 iter_num)

                if self._profiling and iter_num == prof_range[1] - 1:
                    # Drain the async queue so the traced window contains
                    # the device work, then stop. A scalar readback
                    # through host_sync (not a bare float() or
                    # block_until_ready) so the drain lands in the sync
                    # ledger with the rest of the window.
                    tracecheck.host_sync("profile-window-drain",
                                         metrics["loss"])
                    jax.profiler.stop_trace()
                    self._profiling = False
                    self.tracer.end(self._profile_span)
                    if self.is_main:
                        by_kind = tracecheck.sync_delta(
                            self._profile_sync_mark)
                        print(f"profiler trace for iters "
                              f"[{prof_range[0]}:{prof_range[1]}) -> "
                              f"{self.profile_dir} "
                              f"({sum(by_kind.values())} logged host "
                              f"sync(s) in the window; by kind: "
                              f"{by_kind})")
                        self._write_window_spans()

                if cfg.log_interval > 0 and iter_num % cfg.log_interval == 0:
                    # The log-step sync point, through the audited
                    # readback wrapper (profiler windows count it).
                    loss = tracecheck.host_sync("train-log-readback",
                                                metrics["loss"])
                    last_loss = loss
                    # Window-averaged timing: under async dispatch the
                    # host enqueues steps far faster than the device runs
                    # them, and the scalar readback above drains the whole
                    # backlog — so per-iteration wall time is meaningless
                    # at the log step (it would charge ~log_interval
                    # steps of device work to one iteration and understate
                    # tok/s by that factor). Average over the iterations
                    # since the last sync point instead.
                    now = time.time()
                    n_iters = iter_num - window_start_iter
                    dt = (now - t0) / max(n_iters, 1)
                    t0, window_start_iter = now, iter_num
                    toks = tokens_per_iter / max(dt, 1e-9)
                    mfu = self.mfu(dt)
                    if self.is_main:
                        print(f"iter {iter_num}: loss {loss:.4f}, "
                              f"time {dt * 1000:.2f}ms, tok/s {toks:,.0f}"
                              + ("" if mfu is None
                                 else f", mfu {mfu * 100:.2f}%"))
                    # Free after the loss sync above: the step that made
                    # both has finished. (jaxlint does not follow
                    # train_iter's return value, so no suppression here.)
                    grad_norm = float(metrics["grad_norm"])
                    self._count_expert_rows(metrics, iter_num)
                    lr = (float(self.lr_schedule(iter_num))
                          if callable(self.lr_schedule)
                          else self.lr_schedule)
                    writer.log(iter_num, {
                        "train/loss": loss,
                        "train/grad_norm": grad_norm,
                        "train/lr": lr,
                        "perf/tokens_per_sec": toks,
                        **({} if mfu is None else {"perf/mfu": mfu}),
                    })
                iter_num += 1
        finally:
            if self._profiling:
                jax.profiler.stop_trace()
                self._profiling = False
                self.tracer.end(self._profile_span)
            loader.close()
            writer.close()

        if last_eval is not None and last_eval[0] == iter_num:
            losses = last_eval[1]  # already evaluated at this exact step
        else:
            losses = self.estimate_loss(state) if cfg.max_iters > 0 else {}
        if cfg.max_iters > 0 and not cfg.eval_only:
            sid = self.tracer.begin("checkpoint_save", cat="train",
                                    args={"iter": iter_num, "final": True})
            ckpt.save(iter_num, state,
                      {"iter_num": iter_num,
                       "best_val_loss": min(best_val_loss,
                                            losses.get("val", 1e9)),
                       "config": cfg.to_dict()}, wait=True)
            self.tracer.end(sid)
        ckpt.close()
        from nanosandbox_tpu.ops.attention import resolve_attention_impl

        return {"iter_num": iter_num, "final_loss": last_loss,
                # What the run actually resolved, for callers that must
                # not take a fallback for the real thing (chip_smoke.py).
                "attention_impl": resolve_attention_impl(
                    self.model_cfg.attention_impl),
                "model_family": cfg.model_family,
                "loader_native": loader.native,
                **{f"final_{k}_loss": v for k, v in losses.items()}}


def main(argv: list[str] | None = None) -> dict:
    from nanosandbox_tpu.utils.compile_cache import enable_compile_cache

    cfg = load_config(argv if argv is not None else sys.argv[1:])
    _select_platform(cfg.device)
    enable_compile_cache()
    trainer = Trainer(cfg)
    if trainer.is_main:
        print(f"tokens per iteration: {cfg.tokens_per_iter:,}; "
              f"attn_layout: {trainer.attn_layout}")
        print(f"mesh: {trainer.mesh}")
    return trainer.run()


if __name__ == "__main__":
    main()
