"""Readings from which the ``ouro`` training cell's limits are set (on the
chip), as ``chipbench/read_limits_dsv3.py`` takes its family's:

    python -m chipbench.read_limits_ouro --workload <cell> --seeds 1,2,3 [--controls 1]

For every seed, in one process: the program's first three steps (the same
``Job`` the benchmark times) against the plain reference. For the first
``--controls`` seeds also, each put in the program's place: the control (the
reference with every bfloat16 matmul's operands rounded to float8 e4m3) and
five planted faults: half of the batch left out; one pass fewer (3 for 4);
the exit gate ignored (p uniform over the exits); the loss of the last exit
only; the state passed on to the next pass un-normed. One JSON line per
seed, on standard output and in ``chiprun_out/limits.<cell>.jsonl``. The
benchmark's own runs never run this.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

from chipbench import run as harness
from chipbench.read_limits import half_left_out, strip
from chipbench.reference import ouro
from chipbench.runners import train_ouro
from chipbench.runners.train import gaps


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--controls", type=int, default=1)
    args = ap.parse_args(argv)
    found = harness.find_cell(args.workload)
    harness.look_for_the_chip(found.entry["chips"])
    os.makedirs("chiprun_out", exist_ok=True)
    out_path = os.path.join("chiprun_out", f"limits.{args.workload}.jsonl")
    for n, seed in enumerate(int(s) for s in args.seeds.split(",")):
        t = time.time()
        ctx, _ = harness.make_ctx(found, seed, 0.0, 0)
        job = train_ouro.Job(ctx)
        try:
            seen = job.first_steps()
        finally:
            job.close()
        sizes = job.sizes
        del job
        ref = train_ouro.reference_numbers(ctx, sizes, seen["batches"])
        line = {"seed": seed, "program": strip(gaps(seen, ref)),
                "loss": seen["loss"], "ref_loss": ref["loss"],
                "grad_norm": seen["grad_norm"],
                "ref_grad_norm": ref["grad_norm"]}
        if n < args.controls:
            same = seen["batches"]
            for name, quant, batches, leave_out in (
                    ("control_fp8", ouro.fp8_round_trip, same, ()),
                    ("fault_half_batch", None, half_left_out(same), ()),
                    ("fault_one_pass_fewer", None, same, ("pass",)),
                    ("fault_gate_ignored", None, same, ("gate",)),
                    ("fault_last_exit_only", None, same, ("last",)),
                    ("fault_unnormed_state", None, same, ("unnormed",))):
                got = train_ouro.reference_numbers(
                    ctx, sizes, batches, quant=quant,
                    leave_out=frozenset(leave_out))
                line[name] = strip(gaps(got, ref))
                print(json.dumps({name: line[name]}), file=sys.stderr,
                      flush=True)
        line["seconds"] = time.time() - t
        text = json.dumps(line)
        print(text, flush=True)
        with open(out_path, "a", encoding="utf-8") as f:
            f.write(text + "\n")
    return 0


if __name__ == "__main__":
    sys.exit(main())
