"""On-chip benchmark of the executed scheduler: one cell, one run.

    python bench/run.py --workload serve-flat.churn --seed 7 --seconds 30 --trace 0

The cell (configuration x traffic mix) is looked up by name in
``BENCHMARK.json`` at the checkout's root.  The run finds the chips the cell
asks for (with no TPU, or too few chips, it exits non-zero and prints no
result), builds the cell, warms up, drives a closed loop of DAG submissions
for ``--seconds``, compares a seeded sample of the window's DAGs with the
plain reference, and prints one JSON line last on standard output.
``--trace 1`` reports the per-layer metrics from a profiler trace of the
window instead of the end-to-end ones.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT / "bench"), str(ROOT / "src")]


def _compile_cache() -> str:
    """JAX's persistent cache: ``$JAX_COMPILATION_CACHE_DIR``, else a fixed
    directory in the checkout (the path is part of every entry's key)."""
    import jax

    path = os.environ.get("JAX_COMPILATION_CACHE_DIR") or str(ROOT / ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    # libtpu writes no log files (by default it writes them under /tmp)
    os.environ.setdefault("TPU_LOG_DIR", "disabled")

    from yardstick.device import NoChip, require_tpu
    from yardstick.registry import Registry
    from yardstick.runner import Options, log_stderr, run

    reg = Registry(ROOT)
    chips = reg.workload(args.workload)["chips"]
    t_imported = time.perf_counter()
    try:
        devices = require_tpu(chips)
    except NoChip as e:
        print(f"bench: {e}", file=sys.stderr)
        return 2
    t_chips = time.perf_counter()
    log_stderr(
        f"[bench] cache_dir={_compile_cache()} devices={devices} "
        f"imports_s={t_imported - T_START:.3f} tpu_init_s={t_chips - t_imported:.3f}"
    )
    opts = Options(args.workload, args.seed, args.seconds, trace=bool(args.trace))
    result = run(reg, opts, devices, T_START, log=log_stderr)
    for name, c in result["checks"].items():
        log_stderr(f"check {name} {c['value']!r} limit {c['limit']!r}")
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
