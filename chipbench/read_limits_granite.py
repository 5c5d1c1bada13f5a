"""Readings from which the ``granite`` training cell's limits are set (on the
chip), as ``chipbench/read_limits_ouro.py`` takes its family's:

    python -m chipbench.read_limits_granite --workload <cell> --seeds 1,2,3 [--controls 1]

For every seed, in one process: the program's first three steps (the same
``Job`` the benchmark times) against the plain reference. For the first
``--controls`` seeds also, each put in the program's place: the control (the
reference with every bfloat16 product's operands rounded to float8 e4m3) and
five planted faults: half of the step's tokens left out (the cell's one row:
its second half replaced by its first); the scan's state reset at
every chunk boundary; its decay left out (A = 0); the gate left out of the
gated norm; attention's score scaled by D^-0.5 in place of
attention_multiplier. One JSON line per
seed on standard output. The benchmark's own runs never run this.
"""

from __future__ import annotations

import argparse
import json
import sys
import time

import numpy as np

from chipbench import run as harness
from chipbench.read_limits import strip
from chipbench.reference import granite
from chipbench.runners import train_granite
from chipbench.runners.train import gaps


def half_left_out(batches):
    """Each row's second half of tokens replaced by its first half's: with
    one row a step, half of the step's tokens left out."""
    out = []
    for x, y in batches:
        h = x.shape[1] // 2
        out.append((np.concatenate([x[:, :h], x[:, :h]], axis=1),
                    np.concatenate([y[:, :h], y[:, :h]], axis=1)))
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--controls", type=int, default=1)
    args = ap.parse_args(argv)
    found = harness.find_cell(args.workload)
    harness.look_for_the_chip(found.entry["chips"])
    for n, seed in enumerate(int(s) for s in args.seeds.split(",")):
        t = time.time()
        ctx, _ = harness.make_ctx(found, seed, 0.0, 0)
        job = train_granite.Job(ctx)
        try:
            seen = job.first_steps()
        finally:
            job.close()
        sizes = job.sizes
        del job
        ref = train_granite.reference_numbers(ctx, sizes, seen["batches"])
        line = {"seed": seed, "program": strip(gaps(seen, ref)),
                "loss": seen["loss"], "ref_loss": ref["loss"],
                "grad_norm": seen["grad_norm"],
                "ref_grad_norm": ref["grad_norm"]}
        if n < args.controls:
            same = seen["batches"]
            for name, quant, batches, leave_out in (
                    ("control_fp8", granite.fp8_round_trip, same, ()),
                    ("fault_half_tokens", None, half_left_out(same), ()),
                    ("fault_state_reset", None, same, ("reset",)),
                    ("fault_no_decay", None, same, ("decay",)),
                    ("fault_no_gate", None, same, ("gate",)),
                    ("fault_attention_scale", None, same, ("scale",))):
                got = train_granite.reference_numbers(
                    ctx, sizes, batches, quant=quant,
                    leave_out=frozenset(leave_out))
                line[name] = strip(gaps(got, ref))
                print(json.dumps({name: line[name]}), file=sys.stderr,
                      flush=True)
        line["seconds"] = time.time() - t
        print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
