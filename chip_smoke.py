"""Chip smoke run: the executed scheduling path end to end on a TPU.

    python chip_smoke.py              # one chip
    python chip_smoke.py --chips 4    # the rack/pod stream, one class per chip

One chip: the churning request stream of ``serve --arena --execute``
(``repro.launch.serve.run_arena_executed``) at 2048x2048 f32 blocks (16 MiB
each), under ``incremental-gp`` (the paper's policy) and ``dmda`` (the queue
baseline), first with the default kernel-at-a-time dispatch, then with fused
super-steps in dependency waves.  Every interval's exit outputs are compared,
as the interval finishes, with a plain reference evaluation of the same DAG,
and the compiled kernels must be Pallas (``tpu_custom_call``), not the jnp
oracle.

``--chips 4``: only the rack/pod stream (``hier=True``), whose four classes
go one per chip; the outputs must match the reference, each output must sit
on the chip of the group that produced it, and blocks must cross chips.

Everything runs in this one process (a chip belongs to one process).  The
last line of standard output is the JSON result; with no TPU the script
exits non-zero and prints no result.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

# the stream: requests x (1 prefill + DECODE_CHUNKS decodes) per interval,
# STEPS intervals, the default worker drop at DROP_STEP, SIDE^2 f32 blocks
REQUESTS = 32
DECODE_CHUNKS = 8
STEPS = 4
DROP_STEP = 2
SIDE = 2048
SEED = 0
POLICIES = ("incremental-gp", "dmda")
MODES = {
    "unfused": {},
    "fused+waves": {"fused": True, "async_groups": True},
}
# the f32 blocks are f32 in memory, but kernels/matmul.py's jnp.dot runs at
# default precision: one bf16 pass on the MXU, which rounds each operand to
# 8 mantissa bits (5.1e-4 of the largest output measured at side 2048)
TOLERANCE = 2e-3


def log(msg: str) -> None:
    print(msg, flush=True)


class CompileCounter:
    """XLA backend compiles and persistent-cache hits/misses, from JAX's
    monitoring events (process-wide running totals)."""

    def __init__(self):
        import jax

        self.compiles = self.hits = self.misses = 0
        jax.monitoring.register_event_listener(self._event)
        jax.monitoring.register_event_duration_secs_listener(self._duration)

    def _event(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.hits += 1
        elif event == "/jax/compilation_cache/cache_misses":
            self.misses += 1

    def _duration(self, event, duration, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.compiles += 1

    def snapshot(self) -> tuple[int, int, int]:
        return self.compiles, self.hits, self.misses


def peak_bytes() -> int:
    import jax

    return max(d.memory_stats()["peak_bytes_in_use"] for d in jax.local_devices())


def check_pallas(side: int) -> None:
    """The executed path's kernels compile to Mosaic custom calls here."""
    import jax
    import jax.numpy as jnp

    from repro.kernels import ops

    spec = jax.ShapeDtypeStruct((side, side), jnp.float32)
    for name, fn in (("matmul", ops.matmul), ("matadd", ops.matadd)):
        text = jax.jit(fn).lower(spec, spec).compile().as_text()
        if "tpu_custom_call" not in text:
            raise SystemExit(f"ops.{name} compiled without a Pallas kernel")
        log(f"[kernels] ops.{name} at {side}: tpu_custom_call present")


class IntervalCheck:
    """The executor's per-interval ``check`` hook: compares each interval's
    exit outputs with the reference and, given ``groups``, asserts each
    output sits on the chip of the group that produced it.  Errors are kept
    per step report; the outputs themselves are not kept."""

    def __init__(self, groups=None):
        self.groups = groups
        self.err: dict[int, float] = {}  # id(StepReport) -> max rel error
        self.chips: set = set()
        self.n_outputs = 0
        self.ms = 0.0

    def __call__(self, step, report, outputs):
        from repro.core.reference import interval_error

        t0 = time.perf_counter()
        if self.groups is not None:
            for name, arr in outputs.items():
                want = self.groups[report.ran_on[name]]
                if arr.devices() != {want}:
                    raise SystemExit(
                        f"{step.tag}: {name} ran on {report.ran_on[name]} "
                        f"({want}) but lives on {arr.devices()}")
                self.chips.add(want)
        self.n_outputs += len(outputs)
        self.err[id(report)] = interval_error(step, outputs, SIDE)
        self.ms += (time.perf_counter() - t0) * 1e3


def run_phase(label, mode, counter, *, hier=False, groups=None) -> float:
    """One executed stream under both policies; returns the largest error."""
    from repro.launch.serve import run_arena_executed

    check = IntervalCheck(groups)
    c0 = counter.snapshot()
    t0 = time.perf_counter()
    _, arena = run_arena_executed(
        REQUESTS, DECODE_CHUNKS, steps=STEPS, drop_step=DROP_STEP, seed=SEED,
        side=SIDE, policies=POLICIES, hier=hier, check=check, **mode,
    )
    phase_ms = (time.perf_counter() - t0) * 1e3
    c1 = counter.snapshot()
    worst = 0.0
    for policy, rep in arena.reports.items():
        d = rep.to_dict()
        err = max(check.err[id(s)] for s in rep.steps)
        worst = max(worst, err)
        log(
            f"[{label}] {policy}: kernels={d['kernels']} "
            f"window_ms={d['wall_ms']:.1f} transfers={d['transfers']} "
            f"moved_MiB={d['bytes_moved'] / 2**20:.0f} "
            f"superstep_compiles={d['cache_misses']} "
            f"superstep_hits={d['cache_hits']} waves={d['waves']} "
            f"donated={int(rep.total('n_donated'))} "
            f"redispatched={d['redispatched']} reexecuted={d['reexecuted']} "
            f"max_rel_err={err:.3e}"
        )
        if err > TOLERANCE:
            raise SystemExit(f"{label} {policy}: error {err:.3e} > {TOLERANCE}")
        if groups is not None and d["transfers"] <= 0:
            raise SystemExit(f"{label} {policy}: no cross-chip traffic")
    if groups is not None:
        log(f"[{label}] {check.n_outputs} outputs on their groups' chips "
            f"({len(check.chips)} chips)")
        if len(check.chips) < 2:
            raise SystemExit(f"{label}: outputs on {len(check.chips)} chip")
    window_ms = sum(r.total("wall_ms") for r in arena.reports.values())
    log(
        f"[{label}] phase_ms={phase_ms:.1f} window_ms={window_ms:.1f} "
        f"check_ms={check.ms:.1f} "
        f"setup_ms={phase_ms - window_ms - check.ms:.1f} "
        f"xla_compiles={c1[0] - c0[0]} cache_hits={c1[1] - c0[1]} "
        f"cache_misses={c1[2] - c0[2]} peak_bytes={peak_bytes()}"
    )
    return worst


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    args = ap.parse_args(argv)

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    import jax

    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: needs a TPU; JAX found {dev.platform}", file=sys.stderr)
        return 2
    if len(devices) < args.chips:
        print(f"chip_smoke: --chips {args.chips} but JAX found {len(devices)}",
              file=sys.stderr)
        return 2

    from repro.launch.compile_cache import enable_compile_cache

    cache_dir = enable_compile_cache()
    counter = CompileCounter()
    log(f"[device] {dev.device_kind} x{len(devices)} jax={jax.__version__} "
        f"cache_dir={cache_dir}")
    log(f"[stream] requests={REQUESTS} decode_chunks={DECODE_CHUNKS} "
        f"steps={STEPS} drop_step={DROP_STEP} side={SIDE} f32 "
        f"block_MiB={SIDE**2 * 4 / 2**20:.0f}")
    check_pallas(SIDE)

    worst = 0.0
    if args.chips == 1:
        for label, mode in MODES.items():
            worst = max(worst, run_phase(label, mode, counter))
    else:
        from repro.core.serving import groups_for_platform
        from repro.launch.serve import hierarchical_platform

        groups = groups_for_platform(hierarchical_platform())
        if len(set(groups.values())) != 4:
            raise SystemExit(f"classes do not map one per chip: {groups}")
        log("[hier] " + " ".join(f"{c}->{d.id}" for c, d in groups.items()))
        for label, mode in MODES.items():
            worst = max(worst, run_phase(f"hier {label}", mode, counter,
                                         hier=True, groups=groups))

    compiles, hits, misses = counter.snapshot()
    log(f"[done] max_rel_err={worst:.3e} tolerance={TOLERANCE} "
        f"xla_compiles={compiles} persistent_cache_hits={hits} "
        f"persistent_cache_misses={misses} "
        f"cache={'hit' if hits else 'cold'} peak_bytes={peak_bytes()}")
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
