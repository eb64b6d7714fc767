"""Shared by the benchmark's tests: one run of a cell on the CPU at a small
block side, with the harness's look for a chip skipped."""

from __future__ import annotations

import time

from yardstick.registry import ROOT, Registry
from yardstick.runner import Options, run


def run_cell(workload: str, *, seed: int = 2**31 + 7, seconds: float = 0.6, side: int = 64,
             root=ROOT, **kw) -> dict:
    import jax

    reg = Registry(root)
    chips = reg.workload(workload)["chips"]
    devices = jax.devices()[:chips]
    return run(reg, Options(workload, seed, seconds, side=side, **kw), devices,
               time.perf_counter(), log=lambda m: None)
