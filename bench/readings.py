"""The readings a cell's limits are set from, in one process on the chip.

    python bench/readings.py --workload serve-flat.churn --seeds 12 --controls 3

For each seed, a short window at the cell's own load, compared as a run
compares it: first the program (its largest reading over the seeds is the
lower reading), then each control, the reference put in the program's
place one precision step below what the configuration states (the smallest
reading of a control that fails is the upper one).  One JSON line per run
on standard output.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT / "bench"), str(ROOT / "src")]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, default=12)
    ap.add_argument("--controls", type=int, default=3, help="seeds per control")
    ap.add_argument("--control", action="append", default=None,
                    help="a control by name (repeatable; default: every control)")
    ap.add_argument("--seconds", type=float, default=3.0)
    ap.add_argument("--first-seed", type=int, default=5_000_000_000)
    args = ap.parse_args(argv)
    os.environ.setdefault("TPU_LOG_DIR", "disabled")

    from run import _compile_cache
    from yardstick import reference
    from yardstick.device import NoChip, require_tpu
    from yardstick.registry import Registry
    from yardstick.runner import Options, log_stderr, run

    reg = Registry(ROOT)
    try:
        devices = require_tpu(reg.workload(args.workload)["chips"])
    except NoChip as e:
        print(f"readings: {e}", file=sys.stderr)
        return 2
    _compile_cache()
    runs = [("program", None, args.first_seed + i) for i in range(args.seeds)]
    for name in args.control or list(reference.CONTROLS):
        runs += [(f"control:{name}", reference.CONTROLS[name], args.first_seed + 1000 + i)
                 for i in range(args.controls)]
    for kind, arith, seed in runs:
        opts = Options(args.workload, seed, args.seconds, arithmetic=arith)
        r = run(reg, opts, devices, time.perf_counter(), log=log_stderr)
        line = {"kind": kind, "seed": seed, "attempted": r["attempted"],
                "failed": r["failed"], **{k: c["value"] for k, c in r["checks"].items()}}
        print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
