"""Readings from which a training cell's limits are set (on the chip):

    python -m chipbench.read_limits --workload <cell> --seeds 1,2,3 [--controls 3]

For every seed, in one process: the program's first three steps (the same
``Job`` the benchmark times) against the plain reference. For the first
``--controls`` seeds also, each put in the program's place: the control (the
reference with every matmul operand rounded to float8 e4m3, the step below
the configuration's bfloat16), the reference with bfloat16 operands (what the
program is meant to compute), and the fault "half of the batch left out, the
mean taken over the rest". One JSON line per seed, on standard output and in
``chiprun_out/limits.<cell>.jsonl``. The benchmark's own runs never run this.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

import numpy as np

from chipbench import run as harness
from chipbench.reference import gpt2
from chipbench.runners import train


def half_left_out(batches):
    """Rows of the second half replaced by the first half's: the mean over
    the batch is then the mean over the first half alone."""
    out = []
    for x, y in batches:
        h = x.shape[0] // 2
        out.append((np.concatenate([x[:h], x[:h]]), np.concatenate([y[:h], y[:h]])))
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--controls", type=int, default=3)
    args = ap.parse_args(argv)
    found = harness.find_cell(args.workload)
    harness.look_for_the_chip(found.entry["chips"])
    os.makedirs("chiprun_out", exist_ok=True)
    out_path = os.path.join("chiprun_out", f"limits.{args.workload}.jsonl")
    for n, seed in enumerate(int(s) for s in args.seeds.split(",")):
        t = time.time()
        ctx, _ = harness.make_ctx(found, seed, 0.0, 0)
        job = train.Job(ctx)
        try:
            seen = job.first_steps()
        finally:
            job.close()
        sizes = job.sizes
        del job
        ref = train.reference_numbers(ctx, sizes, seen["batches"])
        line = {"seed": seed, "program": strip(train.gaps(seen, ref)),
                "loss": seen["loss"], "ref_loss": ref["loss"],
                "grad_norm": seen["grad_norm"], "ref_grad_norm": ref["grad_norm"]}
        if n < args.controls:
            for name, quant, batches in (
                    ("control_fp8", gpt2.fp8_round_trip, seen["batches"]),
                    ("reference_bf16", gpt2.bf16_round_trip, seen["batches"]),
                    ("fault_half_batch", None, half_left_out(seen["batches"]))):
                got = train.reference_numbers(ctx, sizes, batches, quant=quant)
                line[name] = strip(train.gaps(got, ref))
        line["seconds"] = time.time() - t
        text = json.dumps(line)
        print(text, flush=True)
        with open(out_path, "a", encoding="utf-8") as f:
            f.write(text + "\n")
    return 0


def strip(g: dict) -> dict:
    return {k: v for k, v in g.items() if not k.startswith("_")} | {
        "where": {k: g["_where"][k] for k in ("g1_leaf_gap", "dp_leaf_gap")}}


if __name__ == "__main__":
    sys.exit(main())
