"""Serving driver: batched request decode with scheduler-driven placement.

Two layers, mirroring the paper's stack:

1. **Model serving** — prefill + decode loop of a (reduced) arch on this
   host's devices, with continuous slot management.
2. **Request-DAG scheduling** — a batch of requests forms a task graph
   (prefill -> N decode chunks per request, sharing weights); the
   ``--scheduler`` flag picks eager / dmda / gp / incremental-gp to place
   request chains on heterogeneous device groups (e.g. a big pod + a small
   pod).  The placement is evaluated in the discrete-event simulator and
   (for smoke sizes) executed for real through ``core.executor``.  The
   default is ``incremental-gp``: across serving intervals the request DAG
   churns, and the online partitioner carries placements over instead of
   re-partitioning from scratch (``repro.core.online``).

  PYTHONPATH=src python -m repro.launch.serve --arch granite_3_2b --smoke \
      --requests 8 --decode-len 16 --scheduler incremental-gp

  # policy-vs-policy on a churning request stream (SchedulerArena):
  PYTHONPATH=src python -m repro.launch.serve --arena --requests 16 --steps 6

  # the same stream EXECUTED on real device groups (gp vs incremental-gp),
  # measured per-kernel times feeding back into the online targets; metrics
  # land in BENCH_serve.json (the CI bench-smoke gate consumes it).  --fused
  # dispatches each partition group's kernel chain as ONE compiled
  # super-step (async dispatch, one barrier per group-step, in-process
  # super-step cache) instead of the kernel-at-a-time loop.  XLA's on-disk
  # compilation cache lives where JAX_COMPILATION_CACHE_DIR says, else in
  # <repo>/.jax_cache (repro.launch.compile_cache):
  PYTHONPATH=src python -m repro.launch.serve --arena --execute --fused
"""

from __future__ import annotations

import argparse
import dataclasses
import json
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.registry import get_config, canon, make_batch
from repro.core.arena import (
    SCENARIOS,
    SchedulerArena,
    format_table,
    make_request_stream,
    DEFAULT_POLICIES,
)
from repro.core.comm import HierTopology, Topology
from repro.core.cost import LEAF_NIC, POD_UPLINK, RACK_UPLINK, Link
from repro.core.graph import TaskGraph
from repro.core.router import MODES, ReplicaRouter, RouterReport, SimReplica
from repro.core.schedulers import as_executed, make_policy
from repro.core.serving import ServingExecutor, groups_for_platform
from repro.core.simulate import Platform, Processor, WorkerDrop, simulate
from repro.launch.compile_cache import enable_compile_cache
from repro.launch.steps import DistConfig
from repro.models import transformer as T
from repro.models.params import init_params
from repro.launch.steps import make_ctx

# every policy runs in executed mode: gp/incremental-gp produce class
# assignments natively; eager/dmda/heft go through the worker-pull dispatch
# shim (repro.core.schedulers.as_executed)
EXECUTED_POLICIES = ("eager", "dmda", "heft", "gp", "incremental-gp")


# ---------------------------------------------------------------------------
# 1) real decode loop
# ---------------------------------------------------------------------------


def serve_smoke(
    cfg, *, n_requests: int, prompt_len: int, decode_len: int, seed: int = 0
):
    """Prefill a batch of prompts, decode greedily; returns tokens/s."""
    ctx = make_ctx(cfg, None, "decode", DistConfig(decode_seqpar=False))
    params = init_params(T.model_param_specs(cfg, tp=1), jax.random.PRNGKey(seed))
    batch = make_batch(cfg, prompt_len, n_requests, train=False)
    cache_len = prompt_len + decode_len + (cfg.n_patches if cfg.vlm else 0)

    pctx = make_ctx(cfg, None, "prefill", DistConfig())
    cache, logits = T.prefill(params, batch, cfg, pctx, cache_len=cache_len)

    decode = jax.jit(lambda p, c, t, pos: T.decode_step(p, c, t, pos, cfg, ctx))
    tok = jnp.argmax(logits, -1).astype(jnp.int32)
    pos0 = prompt_len + (cfg.n_patches if cfg.vlm else 0)
    t0 = time.perf_counter()
    out_tokens = [tok]
    for i in range(decode_len):
        logits, cache = decode(params, cache, tok, jnp.int32(pos0 + i))
        tok = jnp.argmax(logits, -1).astype(jnp.int32)
        out_tokens.append(tok)
    jax.block_until_ready(tok)
    dt = time.perf_counter() - t0
    tps = n_requests * decode_len / dt
    return np.stack([np.asarray(t) for t in out_tokens], 1), tps


# ---------------------------------------------------------------------------
# 2) request-DAG scheduling across heterogeneous groups
# ---------------------------------------------------------------------------


def request_dag(
    n_requests: int,
    decode_chunks: int,
    *,
    prefill_ms_big: float,
    prefill_ms_small: float,
    decode_ms_big: float,
    decode_ms_small: float,
    kv_bytes: int,
) -> TaskGraph:
    """One prefill kernel + a chain of decode-chunk kernels per request.
    Edge bytes = the KV cache handed from chunk to chunk (moving a request
    between groups pays a cache migration over the slow link — the paper's
    data-transfer cost in serving form)."""
    g = TaskGraph()
    for r in range(n_requests):
        g.add(
            f"r{r}.prefill",
            op="prefill",
            costs={"big": prefill_ms_big, "small": prefill_ms_small},
            out_bytes=kv_bytes,
        )
        prev = f"r{r}.prefill"
        for c in range(decode_chunks):
            name = f"r{r}.dec{c}"
            g.add(
                name,
                op="decode",
                costs={"big": decode_ms_big, "small": decode_ms_small},
                out_bytes=kv_bytes,
            )
            g.add_edge(prev, name, nbytes=kv_bytes)
            prev = name
    g.validate()
    return g


def heterogeneous_platform(
    link_gbps: float = 6.25,
    mem_capacity_bytes: dict | None = None,
    lanes: int = 2,
) -> Platform:
    """A big pod (fast class) + a small pod (slow class) over DCN.
    ``mem_capacity_bytes`` optionally budgets each pod's KV capacity
    (class -> bytes), turning memory pressure on in the simulator.
    The cross-pod DCN link carries ``lanes`` concurrent copy engines
    (per-link transfer lanes; KV migrations overlap with compute)."""
    procs = [
        Processor("big0", "big", 0),
        Processor("small0", "small", 1),
        Processor("small1", "small", 1),
    ]
    dcn = Link("dcn", bw=link_gbps * 1e9, latency_ms=0.05)
    return Platform(
        procs,
        link=dcn,
        host_node=0,
        mem_capacity_bytes=dict(mem_capacity_bytes or {}),
        topology=Topology.dedicated(dcn, lanes=lanes),
    )


def hierarchical_platform(
    n_pods: int = 2,
    *,
    pod_lanes: int = 1,
    rack_lanes: int = 1,
    leaf_lanes: int = 2,
    leaf: Link = LEAF_NIC,
    rack: Link = RACK_UPLINK,
    pod: Link = POD_UPLINK,
    mem_capacity_bytes: dict | None = None,
) -> Platform:
    """The rack/pod preset: each pod holds a big-class rack (1 worker) and a
    small-class rack (2 workers); classes are named ``pod<i>.big`` /
    ``pod<i>.small``.  Cross-rack traffic books both rack uplinks, cross-pod
    traffic additionally the two *shared* pod uplinks (``pod_lanes`` copy
    engines each) — the contention regime the hierarchy bench sweeps."""
    procs: list[Processor] = []
    node_rack: dict[int, str] = {}
    rack_pod: dict[str, str] = {}
    node = 0
    for p in range(n_pods):
        for cls_kind, n_workers in (("big", 1), ("small", 2)):
            cls = f"pod{p}.{cls_kind}"
            for j in range(n_workers):
                procs.append(Processor(f"{cls}.w{j}", cls, node))
            rack_name = f"r{node}"
            node_rack[node] = rack_name
            rack_pod[rack_name] = f"p{p}"
            node += 1
    topo = HierTopology(
        leaf=leaf,
        rack=rack,
        pod=pod,
        node_rack=node_rack,
        rack_pod=rack_pod,
        leaf_lanes=leaf_lanes,
        rack_lanes=rack_lanes,
        pod_lanes=pod_lanes,
    )
    return Platform(
        procs,
        link=pod,
        host_node=0,
        mem_capacity_bytes=dict(mem_capacity_bytes or {}),
        topology=topo,
    )


def hier_request_costs(
    platform: Platform,
    *,
    prefill_big: float = 20.0,
    prefill_small: float = 60.0,
    decode_big: float = 8.0,
    decode_small: float = 24.0,
) -> tuple[dict, dict]:
    """Per-class cost tables for request streams on a rack/pod platform
    (every pod's big class prices like ``big``, small like ``small``)."""
    prefill = {
        c: prefill_big if c.endswith("big") else prefill_small
        for c in platform.classes
    }
    decode = {
        c: decode_big if c.endswith("big") else decode_small for c in platform.classes
    }
    return prefill, decode


def _arena_setup(
    hier: bool, drop_proc: str
) -> tuple[Platform, str, dict | None, dict | None]:
    """Shared arena plumbing for the simulated and executed runners:
    (platform, drop_proc, costs_prefill, costs_decode).  On the rack/pod
    platform the default flat drop target remaps to its small-rack
    equivalent and the cost tables grow per-pod classes."""
    if not hier:
        return heterogeneous_platform(), drop_proc, None, None
    plat = hierarchical_platform()
    if drop_proc == "small1":
        drop_proc = "pod0.small.w1"
    costs_prefill, costs_decode = hier_request_costs(plat)
    return plat, drop_proc, costs_prefill, costs_decode


def _policy_kwargs(scheduler: str) -> dict:
    """Both GP flavours scale Formula (1)/(2) by per-class worker counts here
    (1 big worker vs 2 small ones — without it the big pod serializes)."""
    if scheduler in ("gp", "incremental-gp"):
        return {"scale_by_workers": True}
    return {}


def schedule_requests(
    n_requests: int, decode_chunks: int, scheduler: str, *, kv_mb: float = 64.0
) -> dict:
    g = request_dag(
        n_requests,
        decode_chunks,
        prefill_ms_big=20.0,
        prefill_ms_small=60.0,
        decode_ms_big=8.0,
        decode_ms_small=24.0,
        kv_bytes=int(kv_mb * 2**20),
    )
    plat = heterogeneous_platform()
    pol = make_policy(scheduler, **_policy_kwargs(scheduler))
    res = simulate(g, pol, plat)
    return {
        "scheduler": scheduler,
        "makespan_ms": res.makespan_ms,
        "transfers": res.n_transfers,
        "bytes_moved_mb": res.bytes_transferred / 2**20,
        "per_class": res.kernels_per_class,
    }


def run_arena(
    n_requests: int,
    decode_chunks: int,
    *,
    steps: int = 6,
    kv_mb: float = 16.0,
    churn: float = 0.3,
    seed: int = 0,
    drop_step: int | None = None,
    drop_proc: str = "small1",
    policies=DEFAULT_POLICIES,
    hier: bool = False,
    scenario: str = "serve",
) -> tuple[list, SchedulerArena]:
    """Replay a churning request stream through every policy (the online
    serving experiment).  ``drop_step`` optionally kills ``drop_proc``
    mid-run at that step — the elastic path.  ``hier=True`` swaps in the
    rack/pod platform (shared-uplink contention + prefetch throttling).
    ``scenario`` selects a zoo generator (:data:`repro.core.arena.SCENARIOS`
    — MoE routing, speculative decoding, train/serve colocation) instead of
    the default prefill/decode stream; the non-serve scenarios cost their
    kernels for the flat big/small platform only."""
    if scenario not in SCENARIOS:
        raise ValueError(f"unknown scenario {scenario!r}")
    if hier and scenario != "serve":
        raise ValueError("--hier only supports the 'serve' scenario")
    plat, drop_proc, costs_prefill, costs_decode = _arena_setup(hier, drop_proc)
    events_at = {}
    if drop_step is not None:
        # each step simulates on a fresh platform copy, so the death must be
        # re-injected: mid-run at the drop step, then at t=0 ever after
        events_at[drop_step] = (WorkerDrop(30.0, drop_proc),)
        for later in range(drop_step + 1, steps):
            events_at[later] = (WorkerDrop(0.0, drop_proc),)
    kw: dict = dict(
        base_requests=n_requests,
        churn=churn,
        kv_bytes=int(kv_mb * 2**20),
        seed=seed,
        arrival_spread_ms=10.0,
        events_at=events_at,
    )
    if scenario in ("serve", "colocate"):
        kw.update(
            decode_chunks=decode_chunks,
            costs_prefill=costs_prefill,
            costs_decode=costs_decode,
        )
    stream = SCENARIOS[scenario](steps, **kw)
    arena = SchedulerArena(
        plat, policies, policy_kwargs={p: _policy_kwargs(p) for p in policies}
    )
    rows = arena.run(stream)
    return rows, arena


def run_arena_executed(
    n_requests: int,
    decode_chunks: int,
    *,
    steps: int = 6,
    kv_mb: float = 16.0,
    churn: float = 0.3,
    seed: int = 0,
    drop_step: int | None = None,
    drop_proc: str = "small1",
    policies=EXECUTED_POLICIES,
    side: int = 48,
    drop_t_ms: float = 1.0,
    hier: bool = False,
    fused: bool = False,
    async_groups: bool = False,
    check=None,
) -> tuple[list, SchedulerArena]:
    """The arena stream EXECUTED on real device groups.

    Same stream construction as :func:`run_arena`, but each interval is
    dispatched through :class:`~repro.core.serving.ServingExecutor`:
    kernels run for real, per-kernel wall times feed the measured-cost /
    heartbeat loop, and drop events fire on the virtual stream clock
    (``drop_t_ms`` — virtual milliseconds, so a mid-interval drop actually
    lands mid-interval regardless of host speed).  ``hier=True`` executes on
    the rack/pod platform: every ``device_put`` pull books the tiered lanes
    (shared-uplink contention + prefetch throttling), matching the
    simulated ``run_arena(hier=True)`` stream.  ``fused=True`` dispatches
    each group's runnable kernel chain as one compiled super-step (async
    dispatch + in-process super-step cache) instead of kernel-at-a-time;
    ``async_groups=True`` additionally dispatches every group whose
    cross-group inputs are satisfied in the same dependency wave — one
    barrier per wave instead of per group (requires ``fused``).
    ``check(step, report, outputs)`` sees every interval's exit outputs
    (:class:`~repro.core.serving.ServingExecutor`)."""
    plat, drop_proc, costs_prefill, costs_decode = _arena_setup(hier, drop_proc)
    events_at = {}
    if drop_step is not None:
        events_at[drop_step] = (WorkerDrop(drop_t_ms, drop_proc),)
        for later in range(drop_step + 1, steps):
            events_at[later] = (WorkerDrop(0.0, drop_proc),)
    stream = make_request_stream(
        steps,
        base_requests=n_requests,
        decode_chunks=decode_chunks,
        churn=churn,
        kv_bytes=int(kv_mb * 2**20),
        seed=seed,
        costs_prefill=costs_prefill,
        costs_decode=costs_decode,
        arrival_spread_ms=0.5,
        events_at=events_at,
    )
    executor = ServingExecutor(groups_for_platform(plat), plat, side=side,
                               fused=fused, async_groups=async_groups,
                               check=check)
    factories = {
        p: (lambda n=p: as_executed(make_policy(n, **_policy_kwargs(n))))
        for p in policies
    }
    arena = SchedulerArena(plat, factories)
    rows = arena.run_executed(stream, executor)
    return rows, arena


def run_router(
    n_requests: int,
    decode_chunks: int,
    *,
    replicas: int = 3,
    mode: str = "affinity",
    steps: int = 6,
    kv_mb: float = 16.0,
    churn: float = 0.3,
    seed: int = 0,
    hier: bool = False,
    arrival_spread_ms: float = 40.0,
    burst_factor: float = 6.0,
    drain_step: int | None = None,
    drain_replica: str | None = None,
) -> RouterReport:
    """Fleet mode: ``replicas`` platform replicas behind a
    :class:`~repro.core.router.ReplicaRouter`, fed one shared bursty
    (Markov ON/OFF) request stream.  Every replica runs a persistent
    ``incremental-gp`` policy, so the router's affinity score reads real
    partitioner residency.  ``drain_step`` gracefully drains a replica
    (default: the last one) before that step — proactive KV migration."""
    plat0 = hierarchical_platform() if hier else heterogeneous_platform()
    costs_prefill, costs_decode = (
        hier_request_costs(plat0) if hier else (None, None)
    )
    stream = make_request_stream(
        steps,
        base_requests=n_requests,
        decode_chunks=decode_chunks,
        churn=churn,
        kv_bytes=int(kv_mb * 2**20),
        seed=seed,
        costs_prefill=costs_prefill,
        costs_decode=costs_decode,
        arrival_spread_ms=arrival_spread_ms,
        arrival_mode="onoff",
        burst_factor=burst_factor,
    )
    reps = [
        SimReplica(
            f"r{i}",
            hierarchical_platform() if hier else heterogeneous_platform(),
            "incremental-gp",
            policy_kwargs=_policy_kwargs("incremental-gp"),
        )
        for i in range(replicas)
    ]
    router = ReplicaRouter(reps, mode=mode)
    drain_at = None
    if drain_step is not None:
        drain_at = {drain_step: drain_replica or f"r{replicas - 1}"}
    return router.run(stream, drain_at=drain_at)


def write_bench(path: str, *, meta: dict, sim_rows=(), arena=None) -> dict:
    """Dump the serving benchmark to JSON (the CI ``bench-smoke`` artifact).

    ``simulated`` rows are fully deterministic (the regression gate compares
    them against a checked-in baseline); ``executed`` rows carry measured
    wall quantities (the gate only sanity-checks their counters)."""
    doc = {
        "meta": dict(meta, jax=jax.__version__, python=sys.version.split()[0]),
        "simulated": {r.policy: dataclasses.asdict(r) for r in sim_rows},
        "executed": {
            name: rep.to_dict()
            for name, rep in (arena.reports if arena else {}).items()
        },
    }
    with open(path, "w") as f:
        json.dump(doc, f, indent=2, sort_keys=True)
    return doc


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", type=str, default="granite_3_2b")
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--requests", type=int, default=8)
    ap.add_argument("--prompt-len", type=int, default=32)
    ap.add_argument("--decode-len", type=int, default=16)
    ap.add_argument(
        "--scheduler",
        type=str,
        default="incremental-gp",
        choices=[
            "incremental-gp",
            "gp",
            "dmda",
            "eager",
            "heft",
            "random",
            "affinity-steal",
        ],
    )
    ap.add_argument("--decode-chunks", type=int, default=8)
    ap.add_argument(
        "--arena",
        action="store_true",
        help="replay a churning request stream through every "
        "policy and print the comparison table",
    )
    ap.add_argument(
        "--scenario",
        type=str,
        default="serve",
        choices=list(SCENARIOS),
        help="with --arena: zoo stream generator — the default "
        "prefill/decode serving stream, MoE conditional routing, "
        "speculative-decoding verify-or-discard, or train/serve "
        "colocation (simulated comparison incl. affinity-steal)",
    )
    ap.add_argument(
        "--hier",
        action="store_true",
        help="with --arena (and --execute): run the stream on "
        "the rack/pod platform — shared-uplink contention "
        "+ prefetch throttling, simulated and executed",
    )
    ap.add_argument(
        "--steps",
        type=int,
        default=6,
        help="stream length (scheduling intervals) for --arena",
    )
    ap.add_argument(
        "--replicas",
        type=int,
        default=1,
        help="with --arena: >1 runs the fleet tier — N platform "
        "replicas behind the partition-affine router on a "
        "bursty ON/OFF stream",
    )
    ap.add_argument(
        "--router",
        type=str,
        default="affinity",
        choices=list(MODES) + ["all"],
        help="fleet routing mode for --replicas > 1 "
        "('all' compares every mode on the same stream)",
    )
    ap.add_argument(
        "--drain-step",
        type=int,
        default=None,
        help="with --replicas: gracefully drain the last replica "
        "before this step (proactive KV migration)",
    )
    ap.add_argument(
        "--drop-step",
        type=int,
        default=None,
        help="kill a small-pod worker at this arena step",
    )
    ap.add_argument(
        "--execute",
        action="store_true",
        help="with --arena: also run the stream on real device "
        "groups (gp vs incremental-gp) through the serving "
        "executor and dump metrics to --bench-out",
    )
    ap.add_argument(
        "--fused",
        action=argparse.BooleanOptionalAction,
        default=False,
        help="with --execute: dispatch each partition group's kernel "
        "chain as ONE jitted, buffer-donating super-step (one barrier "
        "per group-step + in-process super-step cache) instead of the "
        "kernel-at-a-time loop; --no-fused is the bit-identical "
        "fallback the CI baseline pins",
    )
    ap.add_argument(
        "--async-groups",
        action=argparse.BooleanOptionalAction,
        default=False,
        help="with --execute --fused: dispatch every group whose "
        "cross-group inputs are satisfied in the same dependency wave "
        "(one barrier per wave, non-blocking comm pulls) instead of "
        "serializing group-steps; --no-async-groups keeps the "
        "serialized fused dispatch bit-identical",
    )
    ap.add_argument(
        "--bench-out",
        type=str,
        default="BENCH_serve.json",
        help="JSON metrics path for --execute",
    )
    ap.add_argument(
        "--kernel-side",
        type=int,
        default=48,
        help="square matrix side for executed kernels",
    )
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)
    enable_compile_cache()

    if args.arena and args.replicas > 1:
        modes = list(MODES) if args.router == "all" else [args.router]
        for mode in modes:
            rep = run_router(
                args.requests,
                args.decode_chunks,
                replicas=args.replicas,
                mode=mode,
                steps=args.steps,
                seed=args.seed,
                hier=args.hier,
                drain_step=args.drain_step,
            )
            d = rep.to_dict()
            print(
                f"[router] mode={mode} replicas={args.replicas} "
                f"steps={d['steps']}: mean_lat={d['mean_latency_ms']:.1f}ms "
                f"p95={d['p95_latency_ms']:.1f}ms "
                f"fleet_mk={d['total_makespan_ms']:.1f}ms "
                f"warm_hit={d['warm_hit_rate']:.0%} "
                f"migrated={d['kv_migrated_bytes'] / 2**20:.0f}MiB"
            )
        return

    if args.arena:
        policies = DEFAULT_POLICIES
        if args.scenario != "serve":
            # zoo scenarios exist to compare the partitioners against the
            # strongest queue baseline; the serve default stays pinned to
            # the CI baseline's exact policy set
            policies = DEFAULT_POLICIES + ("affinity-steal",)
        rows, _ = run_arena(
            args.requests,
            args.decode_chunks,
            steps=args.steps,
            drop_step=args.drop_step,
            seed=args.seed,
            hier=args.hier,
            scenario=args.scenario,
            policies=policies,
        )
        print(format_table(rows))
        if args.execute:
            if args.scenario != "serve":
                raise SystemExit("--execute only supports --scenario serve")
            xrows, xarena = run_arena_executed(
                args.requests,
                args.decode_chunks,
                steps=args.steps,
                drop_step=args.drop_step,
                seed=args.seed,
                side=args.kernel_side,
                hier=args.hier,
                fused=args.fused,
                async_groups=args.async_groups,
            )
            print(
                "\n[serve] executed on device groups "
                f"({', '.join(r.policy for r in xrows)}"
                f"{', fused super-steps' if args.fused else ''}"
                f"{', async waves' if args.async_groups else ''}):"
            )
            print(format_table(xrows))
            meta = {
                "requests": args.requests,
                "decode_chunks": args.decode_chunks,
                "steps": args.steps,
                "drop_step": args.drop_step,
                "seed": args.seed,
                "kernel_side": args.kernel_side,
                "hier": args.hier,
                "fused": args.fused,
                "async_groups": args.async_groups,
            }
            write_bench(args.bench_out, meta=meta, sim_rows=rows, arena=xarena)
            print(f"[serve] wrote {args.bench_out}")
        return

    cfg = get_config(canon(args.arch))
    if args.smoke:
        cfg = dataclasses.replace(cfg.smoke(), activation_dtype="float32")
        toks, tps = serve_smoke(
            cfg,
            n_requests=args.requests,
            prompt_len=args.prompt_len,
            decode_len=args.decode_len,
        )
        print(
            f"[serve] {cfg.name}: {args.requests} requests x "
            f"{args.decode_len} tokens -> {tps:.1f} tok/s "
            f"({jax.devices()[0].platform})"
        )
    for pol in [args.scheduler] if args.scheduler else []:
        r = schedule_requests(args.requests, args.decode_chunks, pol)
        print(
            f"[serve] scheduler={pol}: makespan={r['makespan_ms']:.1f}ms "
            f"transfers={r['transfers']} moved={r['bytes_moved_mb']:.0f}MiB "
            f"placement={r['per_class']}"
        )


if __name__ == "__main__":
    main()
