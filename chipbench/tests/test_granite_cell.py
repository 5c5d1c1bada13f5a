"""The ``granite`` runner's CPU rehearsal on a tiny cell added as files and
entries (``tests/tiny/*tiny-granite*``), the five planted faults turning
``correct`` false there, its cost functions against hand counts, and its
readers on a record."""

import json
import os
import types

import jax.numpy as jnp
import pytest

from chipbench import flops_granite
from chipbench import run as harness
from chipbench.tests import helpers
from chipbench.tests.test_harness import _half_left_out

CELL = "tiny-granite.train-b2-t128"
REAL = "granite-4.0-h-micro-pp4.train-b1-t8192"
PARTS = ("dev_ms_granite_mamba", "dev_ms_granite_ssd", "dev_ms_granite_attn",
         "dev_ms_granite_mlp", "dev_ms_granite_rest")
OWN = set(PARTS) | {"granite_ssd_roofline", "granite_attn_roofline"}


def _args(seed=2**31 + 41, seconds=1.0, trace=0):
    return types.SimpleNamespace(workload=CELL, seed=seed, seconds=seconds,
                                 trace=trace)


@pytest.fixture(scope="module")
def tiny_root(tmp_path_factory):
    root = helpers.copy_root(str(tmp_path_factory.mktemp("root")))
    helpers.add_tiny(root)          # copies every file under tests/tiny
    path = os.path.join(root, "BENCHMARK.json")
    with open(path) as f:
        bench = json.load(f)
    bench["configs"].append({
        "name": "tiny-granite", "source": "none", "reduced": [], "why": "tests",
        "file": "chipbench/configs/tiny-granite.json"})
    bench["workloads"].append({
        "name": CELL, "config": "tiny-granite", "traffic": "train-b2-t128",
        "chips": 1, "why": "tests"})
    for m in bench["per_layer"]:    # the real cell's own metrics
        if REAL in m.get("workloads", ()):
            m["workloads"].append(CELL)
    with open(path, "w") as f:
        json.dump(bench, f)
    return root


# -- the faults, each in the program's place -----------------------------------

def _scan(monkeypatch, wrap):
    from nanosandbox_tpu.ops import ssd

    real = ssd.ssd
    monkeypatch.setattr(ssd, "ssd", lambda *a, **kw: wrap(real, *a, **kw))


def _state_reset(monkeypatch):
    """Every chunk scanned as a sequence of its own: the state starts at
    zero at every chunk boundary."""
    def by_chunk(real, x, dt, A, B, C, D, *, chunk, **kw):
        b, T = x.shape[:2]
        pad = -T % chunk            # the trainer's 8-token init batch
        rows = lambda v: jnp.pad(v, ((0, 0), (0, pad), (0, 0))).reshape(
            -1, chunk, v.shape[-1])
        y, stats = real(rows(x), rows(dt), A, rows(B), rows(C), D,
                        chunk=chunk, **kw)
        return y.reshape(b, T + pad, -1)[:, :T], stats
    _scan(monkeypatch, by_chunk)


def _no_decay(monkeypatch):
    """A = 0: the state never decays."""
    _scan(monkeypatch, lambda real, x, dt, A, *a, **kw: real(
        x, dt, jnp.zeros_like(A), *a, **kw))


def _no_gate(monkeypatch):
    """The gated norm without its gate: RMSNorm(y) * w."""
    from nanosandbox_tpu.models import granite

    class Ungated(granite.GatedRMSNorm):
        def __call__(self, y, z):
            return super().__call__(y, jnp.full_like(z, 1e4))  # silu -> 1e4
    monkeypatch.setattr(granite, "GatedRMSNorm", Ungated)


def _score_scale(monkeypatch):
    """Attention's score scaled by D^-0.5 in place of the multiplier."""
    from chipbench.runners import train_granite

    real = train_granite.train_config
    monkeypatch.setattr(train_granite, "train_config", lambda ctx: real(
        ctx).replace(attention_multiplier=real(ctx).head_dim ** -0.5))


FAULTS = {"state_reset_at_chunks": _state_reset, "decay_left_out": _no_decay,
          "gate_left_out": _no_gate, "score_scaled_by_d": _score_scale}


@pytest.mark.parametrize("fault", ["none", "half_batch_left_out", *FAULTS])
def test_a_whole_run_and_the_faults_it_must_catch(tiny_root, data_dir, fault,
                                                  monkeypatch):
    broken = _half_left_out if fault == "half_batch_left_out" else None
    if fault in FAULTS:
        FAULTS[fault](monkeypatch)
    out = harness.drive(_args(), require_chip=False, root=tiny_root,
                        data_dir=data_dir, break_step=broken)
    assert set(out["check"]) == {"loss_gap", "grad_norm_gap", "g1_leaf_gap",
                                 "dp_leaf_gap"}
    assert out["attempted"] > 0 and out["failed"] == 0 and not out["faults"]
    assert out["metrics"]["train_tok_s_chip"]["value"] > 0
    assert out["correct"] is (fault == "none"), out["check"]


def test_the_record_feeds_the_unlisted_readers_and_its_own(tiny_root, data_dir):
    """A traced run on the CPU has no device plane: the device metrics find
    nothing and are left out, nothing raises; the scan's instants are left
    by the trainer for the logged steps."""
    from nanosandbox_tpu.obs import process_tracer

    found = harness.find_cell(CELL, tiny_root)
    names = {m["name"] for m in harness.metrics_of(found, "per_layer")}
    assert OWN | {"compile_s", "host_input_ms", "train_step_mfu_pct",
                  "step_gap_p95_ms", "device_idle_pct"} <= names
    assert not {"dev_ms_attn", "dev_ms_ouro_attn", "lfm2_attn_roofline",
                "dev_ms_route_router"} & names
    process_tracer().clear()
    out = harness.drive(_args(trace=1), require_chip=False, root=tiny_root,
                        data_dir=data_dir)
    assert out["correct"]
    assert not OWN & set(out["metrics"])
    noted = [s for s in process_tracer().spans() if s.name == "granite_ssd"]
    assert noted and all(len(s.args["ssd_decay"]) == 2 for s in noted)
    init = [s for s in process_tracer().spans() if s.name == "trainer_init"]
    assert {"ssd_impl": "xla", "ssd_chunk": 32,
            "layer_types": "mamba,attention,mamba",
            "remat_policy": "save_attention"}.items() <= init[-1].args.items()


def test_a_program_without_the_family_is_refused_at_once(tiny_root, data_dir,
                                                         monkeypatch):
    """An older commit under these files: non-zero before the corpus is
    folded, a trainer built or anything compiled."""
    from chipbench.runners import train_granite
    from nanosandbox_tpu import models

    monkeypatch.setattr(models, "FAMILIES", {
        k: v for k, v in models.FAMILIES.items() if k != "granite"})
    monkeypatch.setattr(train_granite, "prepare_folded",
                        lambda *a: pytest.fail("the corpus was folded"))
    with pytest.raises(SystemExit, match="no model_family 'granite'"):
        harness.drive(_args(), require_chip=False, root=tiny_root,
                      data_dir=data_dir)


def test_the_real_cells_files_hold_the_published_shape_and_the_cut():
    found = harness.find_cell(REAL)
    assert found.cell["runner"] == "train_granite" and found.entry["chips"] == 1
    assert found.entry["traffic"] == "train-b1-t8192"
    assert (found.traffic["batch_size"], found.traffic["block_size"]) == (
        1, 8192)
    c = found.config
    assert (c["hidden_size"], c["num_attention_heads"],
            c["num_key_value_heads"], c["shared_intermediate_size"],
            c["mamba_n_heads"], c["mamba_d_head"], c["mamba_d_state"],
            c["mamba_n_groups"], c["mamba_d_conv"], c["mamba_chunk_size"],
            c["embedding_multiplier"], c["residual_multiplier"],
            c["attention_multiplier"], c["logits_scaling"],
            c["rms_norm_eps"]) == (2048, 32, 8, 8192, 64, 64, 128, 1, 4, 256,
                                   12, 0.22, 0.015625, 8, 1e-5)
    changed = {k for k, v in c["published"].items() if c[k] != v}
    assert changed == set(c["reduced"]) == set(c["reduced_why"]) == {
        "num_hidden_layers", "vocab_size"}
    assert (c["num_hidden_layers"], c["vocab_size"]) == (10, 25088)
    kinds = c["layer_types"][:c["num_hidden_layers"]]
    assert kinds == ["mamba"] * 5 + ["attention"] + ["mamba"] * 4
    own = {m["name"] for m in found.bench["per_layer"]
           if m.get("workloads") == [REAL]}
    assert own == OWN


SIZES = dict(n_layer=10, n_head=32, n_kv_head=8, head_dim=64, n_embd=2048,
             intermediate_size=8192, vocab_size=25088, block_size=8192,
             layer_types=["mamba"] * 5 + ["attention"] + ["mamba"] * 4,
             mamba_n_heads=64, mamba_d_head=64, mamba_d_state=128,
             mamba_n_groups=1, mamba_d_conv=4, mamba_chunk_size=256)


def test_cost_functions_against_hand_counts():
    # the parameter table: a Mamba layer 76,182,976, the attention layer
    # 60,821,504, with the quarter table and the final norm 797,850,560
    mamba = 17_432_576 + 21_760 + 192 + 4_096 + 8_388_608 + 50_331_648 + 4_096
    attention = 10_485_760 + 50_331_648 + 4_096
    assert (mamba, attention) == (76_182_976, 60_821_504)
    assert flops_granite.n_params(SIZES) == (
        9 * mamba + attention + 25088 * 2048 + 2048) == 797_850_560
    # a token: 6 per parameter that multiplies it, the attention layer's
    # causal pairs, three times the scan's forward products in nine layers
    applied = (9 * (17_432_576 + 8_388_608 + 17_408 + 50_331_648)
               + 4 * 2048 * 2048 - 2 * 2048 * 1536 + 50_331_648
               + 25088 * 2048)
    scan = 257 / 2 * (2 * 128 + 2 * 4096) + 4 * 64 * 64 * 128
    want = 6.0 * applied + 12.0 * 32 * 64 * 8193 / 2 + 3 * 9 * scan
    assert flops_granite.train_flops_per_token(SIZES) == pytest.approx(want)
    one = flops_granite.ssd_cost(SIZES, 8192)
    assert one["ops"] == 3 * scan * 8192
    assert one["bytes"] == 4 * 8192 * (3 * (4096 + 64 + 256) + 2 * 4096)
    least = flops_granite.least_seconds(one, {"bf16_flops_per_s": 197e12,
                                              "hbm_bytes_per_s": 819e9})
    assert least["bound"] == "memory"


def test_the_readers_on_a_record():
    """Nothing where the record lacks the family's sizes (a parent's); the
    scan's instants in the extras where the program left them; the five
    parts sum to the step's device time."""
    from chipbench.reducers import granite as readers
    from chipbench.reducers import program

    no_ops = types.SimpleNamespace(ops=[])
    peaks = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
    theirs = {"record": {"sizes": {"n_layer": 6}, "batch_rows": 1,
                         "chips": 1}, "trace": no_ops, "peaks": peaks,
              "program": {"spans": [], "parts_s": 0.0, "device_ms": None}}
    assert readers.ssd_roofline_pct(theirs, {"params": {"part": "ssd"}}) is None
    assert readers.ssd_ms(theirs, {"params": {"parts": ["ssd"]}}) is None
    inst = [types.SimpleNamespace(name="granite_ssd", args={
        "ssd_decay": [0.5, 0.25], "ssd_state_max": [2.0, 4.0]}),
        types.SimpleNamespace(name="granite_ssd", args={
            "ssd_decay": [0.3, 0.15], "ssd_state_max": [6.0, 8.0]})]
    parts = {"mamba": [300.0, 90.0], "ssd": [40.0, 70.0],
             "attn_full": [30.0, 10.0], "mlp": [200.0, 50.0],
             "ln": [20.0, 40.0], "optimizer": [30.0, 20.0],
             "unscoped": [5.0, 5.0]}
    mine = {"record": {"sizes": SIZES, "batch_rows": 1, "chips": 1},
            "trace": no_ops, "peaks": peaks,
            "program": {"spans": inst, "parts_s": 0.0, "device_ms": parts}}
    value, extra = readers.ssd_ms(mine, {"params": {"parts": ["ssd"]}})
    assert value == 40.0 and extra["instants"] == 2
    assert extra["ssd_decay"] == pytest.approx([0.4, 0.2])
    assert extra["ssd_state_max"] == [4.0, 6.0]
    pct, extra = readers.ssd_roofline_pct(mine, {"params": {"part": "ssd"}})
    least = 9 * flops_granite.ssd_cost(SIZES, 8192)["bytes"] / 819e9
    assert pct == pytest.approx(100 * least / 0.040)
    assert extra["bound"] == "memory" and extra["mamba_layers"] == 9
    files = {}
    for name in OWN:
        with open(os.path.join(helpers.CHIPBENCH, "layer_metrics",
                               f"{name}.json")) as f:
            files[name] = json.load(f)
    total = 0.0
    for name in PARTS:
        module, fn = files[name]["reducer"].split(":")
        assert module == "granite"
        total += getattr(readers, fn)(mine, files[name])[0]
    assert total == sum(ms for ms, _ in parts.values())
    assert program.device_ms_of_parts is readers.device_ms_of_parts


def test_the_attention_layers_kernels_against_their_roofline():
    """The one attention layer's ``%attn_full`` calls, read by the accepted
    reader at H 32, G 8, D 64: the least time of one layer's required work
    over the calls' device time a step; nothing without the family's sizes
    or without the calls."""
    from chipbench import flops_afmoe
    from chipbench.reducers import granite as readers
    from chipbench.trace import Event

    with open(os.path.join(helpers.CHIPBENCH, "layer_metrics",
                           "granite_attn_roofline.json")) as f:
        metric = json.load(f)
    assert metric["reducer"] == "granite:attention_roofline_pct"
    steps = [Event("jit_traced(step)", 0.0, 500e6),
             Event("jit_traced(step)", 500e6, 1000e6)]
    calls = [Event("%attn_full.7 = custom-call", 100e6, 104e6),
             Event("%attn_full.8 = custom-call", 200e6, 210e6),
             Event("%attn_full.7 = custom-call", 600e6, 604e6),
             Event("%attn_full.8 = custom-call", 700e6, 710e6),
             Event("%fusion.3 = fusion", 300e6, 400e6)]
    tr = types.SimpleNamespace(modules=[steps], ops=[calls],
                               window=(0.0, 1000e6))
    peaks = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
    rec = {"sizes": SIZES, "batch_rows": 1, "chips": 1}
    pct, extra = readers.attention_roofline_pct(
        {"record": rec, "trace": tr, "peaks": peaks}, metric)
    least = flops_afmoe.least_seconds(
        flops_afmoe.attention_cost(SIZES, 1, None), peaks)
    assert pct == pytest.approx(100 * least["seconds"] / 0.014)
    assert extra["bound"] == "compute" and extra["events_per_step"] == 2
    assert 0 < pct < 100
    theirs = {"record": {**rec, "sizes": {"n_layer": 6}}, "trace": tr,
              "peaks": peaks}
    assert readers.attention_roofline_pct(theirs, metric) is None
    empty = types.SimpleNamespace(modules=[steps], ops=[calls[-1:]],
                                  window=tr.window)
    assert readers.attention_roofline_pct(
        {"record": rec, "trace": empty, "peaks": peaks}, metric) is None
