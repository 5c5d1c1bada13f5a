"""CPU rehearsal of a whole run at a toy size, for the builder only:

    JAX_PLATFORMS=cpu python -m chipbench.tests.rehearse [--trace 1] [--seed N]

Never a fallback of ``chipbench.run``, which fails without the chip. What
this prints are the harness's plumbing and counts; its times are the CPU's
and are never a device metric.
"""

from __future__ import annotations

import argparse
import json
import os
import tempfile

from chipbench import run
from chipbench.tests import helpers


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--seed", type=int, default=2**31 + 11)
    ap.add_argument("--seconds", type=float, default=2.0)
    ap.add_argument("--trace", type=int, default=0)
    ap.add_argument("--data", default=os.path.join(tempfile.gettempdir(),
                                                   "chipbench-rehearsal-data"))
    args = ap.parse_args()
    with tempfile.TemporaryDirectory() as tmp:
        root = helpers.copy_root(tmp)
        args.workload = helpers.add_tiny(root)
        out = run.drive(args, require_chip=False, root=root, data_dir=args.data)
    print(json.dumps(out))


if __name__ == "__main__":
    main()
