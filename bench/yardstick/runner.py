"""One run of one cell: build it from its files, warm up, drive the
program's ``ServingExecutor.run_step`` in a closed loop for the window,
then compare a seeded sample of the window's DAGs with the reference.

The program is built as its serving launcher builds it: the platform from
the configuration's builder, one device group per class
(``groups_for_platform``), the policy from ``make_policy`` wrapped in
``as_executed``, and the executor at its default dispatch mode.  The
harness wraps the program's ``attach`` in one way only: the ``<kernel>/in``
blocks the program drew are replaced by blocks drawn from the seed and the
DAG's index.  Its ``check`` hook, which runs inside ``run_step``, waits for
every DAG's exit outputs and keeps the sampled DAGs'.
"""

from __future__ import annotations

import dataclasses
import math
import random
import shutil
import sys
import time

from yardstick import reference, trace
from yardstick.compiles import CompileCounter
from yardstick.dag import resolve
from yardstick.device import describe, peak_bytes
from yardstick.registry import Registry
from yardstick.seeding import BlockDrawer
from yardstick.window import closed_loop


@dataclasses.dataclass
class Options:
    workload: str
    seed: int
    seconds: float
    trace: bool = False
    side: int | None = None  # a smaller block side than the configuration's (tests)
    arithmetic: reference.Arithmetic | None = None  # run this in the program's place


@dataclasses.dataclass
class RunView:
    """What a per-layer metric reader may read."""

    reports: list  # the program's StepReport of every DAG of the window
    graphs: int
    compiles_in_window: int
    trace: trace.Summary | None
    side: int
    device_kind: str
    devices: int  # distinct devices behind the platform's classes
    kernels: dict  # DAG op -> roofline kernel



class SeededAttach:
    """The program's ``attach``, then its ``<kernel>/in`` blocks replaced
    by blocks drawn from (seed, DAG index, block name).  With an arithmetic
    set, every kernel runs the reference op in it instead (the control)."""

    def __init__(self, attach, drawer: BlockDrawer, seed: int, ops=None, arith=None):
        self.attach, self.drawer, self.seed = attach, drawer, seed
        self.ops, self.arith = ops, arith
        self.index = 0

    def __call__(self, g, side: int) -> dict:
        import jax

        with jax.profiler.TraceAnnotation("bench.attach"):
            program_inputs = self.attach(g, side)
            if self.arith is not None:
                for k in g.nodes.values():
                    if k.op in self.ops:
                        k.fn = lambda *xs, op=self.ops[k.op]: op(list(xs), self.arith)
            return self.drawer(self.seed, self.index, list(program_inputs))


class Sample:
    """The executor's ``check`` hook: waits until every DAG's exit outputs
    are ready on their devices, so that ``run_step`` returns only then, and
    keeps the exit outputs of a sample of ``k`` window DAGs drawn from the
    seed (reservoir sampling), and nothing else once a DAG is out of it."""

    def __init__(self, k: int, seed: int):
        self.k, self.rng = k, random.Random(f"sample/{seed}")
        self.kept: dict[int, dict] = {}
        self.index = 0
        self.active = False
        self.seen = 0

    def __call__(self, step, report, outputs) -> None:
        import jax

        jax.block_until_ready(list(outputs.values()))
        if not self.active:
            return
        self.seen += 1
        if len(self.kept) < self.k:
            self.kept[self.index] = dict(outputs)
            return
        j = self.rng.randrange(self.seen)
        if j < self.k:
            del self.kept[sorted(self.kept)[j]]
            self.kept[self.index] = dict(outputs)


def run(reg: Registry, opts: Options, devices: list, t_start: float, log=print) -> dict:
    """-> the result line (``correct``, ``attempted``, ``failed``,
    ``metrics``, ``device``, and ``checks`` last)."""
    import jax

    from repro.core.schedulers import as_executed, make_policy
    from repro.core.serving import ServingExecutor, groups_for_platform

    t_init = time.perf_counter()
    wl = reg.workload(opts.workload)
    cfg = dict(reg.config(wl["config"]))
    traffic = reg.traffic(wl["traffic"])
    side = opts.side or cfg["side"]
    cfg["side"] = side

    platform = resolve(cfg["platform"]["builder"])(**cfg["platform"].get("kwargs", {}))
    groups = groups_for_platform(platform, devices)
    policy = as_executed(make_policy(cfg["policy"]["name"], **cfg["policy"].get("kwargs", {})))
    family = reg.family(traffic["dag"]).Family(cfg, traffic, platform, opts.seed)
    drawer = BlockDrawer(side, cfg["dtype"], family.scale)
    attach = SeededAttach(family.attach, drawer, opts.seed, family.ops, opts.arithmetic)
    sample = Sample(traffic["check_sample"], opts.seed)
    executor = ServingExecutor(groups, platform, side=side, attach=attach, check=sample)
    counter = CompileCounter()
    reports: list = []

    def prepare(i: int) -> None:
        with jax.profiler.TraceAnnotation("bench.prepare"):
            family[i]

    def submit(i: int) -> None:
        _, step = family[i]
        attach.index = sample.index = i
        with jax.profiler.TraceAnnotation("bench.run_step"):
            reports.append(executor.run_step(step, policy, i))

    # every DAG is built lazily, just before its submission, so that building
    # costs the same at every speed of the program
    warm = traffic["warmup_graphs"]
    t_built = time.perf_counter()
    for i in range(warm):
        prepare(i)
        submit(i)
    t_warm = time.perf_counter()
    c_warm = counter.snapshot()
    reports.clear()
    sample.active = True
    trace_dir = None
    if opts.trace:
        trace_dir = reg.root / ".bench_out" / "trace" / opts.workload
        shutil.rmtree(trace_dir, ignore_errors=True)
        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0
        jax.profiler.start_trace(str(trace_dir), profiler_options=options)
    c0 = counter.snapshot()
    setup_s = time.perf_counter() - t_start
    log(
        f"[setup] setup_s={setup_s:.3f} start_to_build_s={t_init - t_start:.3f} "
        f"build_s={t_built - t_init:.3f} warmup_s={t_warm - t_built:.3f} "
        f"warmup_compiles={c_warm[0]} cache_hits={c_warm[1]} cache_misses={c_warm[2]}"
    )
    with jax.profiler.TraceAnnotation(trace.WINDOW_SPAN):
        window = closed_loop(submit, opts.seconds, first=warm, prepare=prepare)
    c1 = counter.snapshot()
    if opts.trace:
        jax.profiler.stop_trace()
    memory_peak = peak_bytes(devices)
    lat = sorted(window.latencies_s)
    log(
        f"[window] graphs={window.attempted} failed={len(window.failed)} "
        f"seconds={window.seconds:.3f} p50_ms={1e3 * lat[len(lat) // 2] if lat else 0:.1f} "
        f"p90_ms={window.percentile_ms(90) if lat else 0:.1f} samples={len(lat)} "
        f"compiles_in_window={c1[0] - c0[0]} cache_hits={c1[1] - c0[1]} "
        f"memory_peak_bytes={memory_peak}"
    )
    for err in window.errors:
        log(err)

    # the program's state goes before the reference runs
    del executor, policy
    checks = _check(family, drawer, sample, opts.seed, window, cfg["limits"], log)
    failed = len(window.failed) + checks.pop("_bad_graphs")

    dev = describe(devices)
    dev["memory_peak_bytes"] = memory_peak
    view = RunView(
        reports=reports,
        graphs=window.completed,
        compiles_in_window=c1[0] - c0[0],
        trace=None,
        side=side,
        device_kind=dev["kind"],
        devices=len(set(groups.values())),
        kernels=family.kernels,
    )
    result: dict = {
        "correct": all(c["value"] <= c["limit"] for c in checks.values()),
        "attempted": window.attempted,
        "failed": failed,
    }
    if opts.trace:
        view.trace = trace.summarize(trace.load(trace_dir))
        dev["busy_s"] = view.trace.busy_s
        dev["window_s"] = view.trace.window_s
        result["metrics"] = _per_layer(reg, opts.workload, view)
        result["breakdown"] = view.trace.breakdown()
    else:
        result["metrics"] = _end_to_end(reg, opts.workload, window, setup_s)
    result["device"] = dev
    result["checks"] = checks
    return result


def _check(family, drawer, sample, seed, window, limits, log) -> dict:
    """Compare every sampled DAG's exit outputs with the reference."""
    import jax

    errs: list[float] = []
    missing = bad = 0
    t0 = time.perf_counter()
    with jax.profiler.TraceAnnotation("bench.check"):
        for index in sorted(sample.kept):
            outputs = sample.kept.pop(index)
            spec, _ = family[index]
            inputs = drawer(seed, index, spec.inputs)
            got = reference.compare(outputs, reference.evaluate(spec, inputs, family.ops))
            del outputs, inputs
            errs.append(got["max_rel_err"])
            missing += got["missing_blocks"]
            bad += not (got["max_rel_err"] <= limits["max_rel_err"] and not got["missing_blocks"])
    # NaN (a non-finite output, or nothing compared) wins over any number
    worst = max(errs, key=lambda e: math.inf if e != e else e) if errs else math.nan
    log(f"[check] window_graphs={sample.seen} compared={len(errs)} "
        f"check_s={time.perf_counter() - t0:.3f}")
    return {
        "max_rel_err": {"value": worst, "limit": limits["max_rel_err"]},
        "missing_blocks": {"value": missing, "limit": 0},
        "raised": {"value": len(window.failed), "limit": 0},
        "_bad_graphs": bad,
    }


def _end_to_end(reg, workload, window, setup_s) -> dict:
    values = {
        "graphs_per_s": window.rate(),
        "graph_p90_ms": window.percentile_ms(90) if window.attempted else float("nan"),
        "setup_s": setup_s,
    }
    return {
        m["name"]: {"value": values[m["name"]], "unit": m["unit"]}
        for m in reg.metrics(workload, "end_to_end")
    }


def _per_layer(reg, workload, view) -> dict:
    out = {}
    for m in reg.metrics(workload, "per_layer"):
        value = reg.reader(m["name"]).read(view)
        if value is not None:
            out[m["name"]] = {"value": value, "unit": m["unit"]}
    return out


def log_stderr(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)
