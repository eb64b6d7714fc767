"""Real JAX executor for task graphs — the StarPU-runtime role.

Executes a :class:`TaskGraph` whose kernels carry real JAX callables
(``Kernel.fn``) over named *device groups*, honoring a placement
(kernel -> group) from any scheduling policy.  What StarPU does with worker
threads + MSI, this does with JAX async dispatch + explicit ``device_put``:

* data consistency: each data block tracks which groups hold a valid copy
  (write-invalidate, like the paper's StarPU runtime);
* a kernel launched on group g first pulls missing inputs with
  ``jax.device_put`` (the PCIe/ICI transfer — counted, like Fig 5's
  transfer metric);
* JAX's async dispatch gives the overlap StarPU gets from worker threads;
  the final ``block_until_ready`` is the makespan barrier.

With a :class:`~repro.core.comm.CommEngine` attached, the session *also*
charges every transfer to the same per-link lane model the simulator uses —
one communication model, two backends.  Each executed kernel gets a virtual
start/finish on a two-resource timeline (per-group compute streams + comm
lanes): compute starts when the group is free AND the inputs' modeled copies
have landed, instead of serializing measured kernel time plus modeled
transfer time on one clock.  Inputs of the next ready kernels are
*prefetched* (real ``device_put`` + a ``kind="prefetch"`` lane booking), so
cut-edge transfers hide under the previous kernel's compute.  On a
hierarchical topology every pull books each tier its path crosses (shared
pod uplinks contend) and prefetches are contention-throttled: a deferred
prefetch moves nothing and simply retries at the next step.

Two entry points:

* :meth:`JaxExecutor.run` — one-shot batch execution (unchanged API);
* :class:`ExecSession` — the *online* form: kernels execute one
  :meth:`~ExecSession.step` at a time, the assignment can be rewritten
  between steps (:meth:`~ExecSession.reassign`), a sample of the kernels'
  wall times is measured (``time_kernels=True``, see *Sampled timing*
  below), and a group that leaves the platform is evicted
  (:meth:`~ExecSession.evict_group`): its block copies are lost and
  any producer whose output a pending consumer still needs is transparently
  re-queued for re-execution — the executor-land analogue of the simulator's
  in-flight abort + re-dispatch on :class:`~repro.core.simulate.WorkerDrop`.
  Prefetched-but-unconsumed copies targeting the dead group are discarded
  from the consistency *and* the comm model, so the consumer's re-pull is
  charged again (the transfer really does happen twice).

**Sampled timing** (``time_kernels=True``, unfused): a host sync per kernel
would keep the device idle through every round trip, so only a fixed sample
is timed — each group's first executed kernel and every
``max(1, n_g // TIMED_PER_GROUP)``-th after it, ``n_g`` the kernels the
group was given at session start (about five a group; a group of 9 or fewer
is timed in full).  A timed kernel first waits on its inputs and on the last
output launched to its device (the device runs in order, so its queue is
drained), then its launch and run are timed up to its output's
``block_until_ready``.  Every other kernel launches asynchronously and is
never waited on; its ``KernelRun.ms`` is an estimate (the last timed ms of
its (op, group), else :attr:`ExecSession.op_ms` as the caller seeded it,
else 0) that keeps the virtual timeline moving, and it stays out of
``ExecResult.kernel_ms``.

**Fused super-steps** (``fused=True``): instead of the Python-driven
kernel-at-a-time loop — one async dispatch per kernel, plus (with
``time_kernels``) the sampled syncs — the session assembles each partition
group's currently-runnable intra-group kernel chain into a single jitted,
buffer-donating callable (:func:`repro.kernels.ops.build_chain` composed per
the graph's topological order) and dispatches it as ONE XLA computation with
one ready-barrier per group-step.  Per-kernel wall times are *apportioned*
from the fused wall time by the kernels' cost-table weights, so the
measured-cost / EWMA feedback loop keeps working, and the per-kernel input
sync of the unfused path never pollutes them (the one sync per group-step
happens outside the timed region).  Compiled group-steps live in a
persistent :class:`SuperStepCache` keyed by (graph revision, group
signature, input shapes/dtypes): an online re-partition only recompiles the
groups whose membership actually changed, and a full-repartition escalation
(a new revision tag) invalidates everything.  The unfused path is preserved
bit-identical — it is the fallback when exact per-kernel event interleaving
matters (platform churn lands *between* kernels, not between group-steps)
and the A/B baseline for the parity suite.

**Streaming pulls** (``streaming=True``, comm attached): demand pulls open
:class:`~repro.core.comm.StreamChannel` s instead of bulk fetches — the
consumer's virtual start gates on the FIRST chunk's arrival and the residual
chunks drain against its compute window (bounded ``stream_depth`` in-flight
chunks = backpressure), while the real ``device_put`` happens chunk-wise too:
the donor's leading axis is split and copied as depth-bounded async
dispatches that reassemble bit-identically on the destination.  Bulk
speculative prefetch is disabled under streaming (channels already overlap
chunk-wise); ``streaming=False`` keeps the bulk path bit-identical.

On one device all groups alias it (transfers are no-op-counted but still
exercised; a donated buffer is consumed, which is why ``_donatable`` demands
the group's copy be the only one); on a real slice, groups are disjoint
device sets.
"""

from __future__ import annotations

import dataclasses
import functools
import time
import warnings
from typing import Iterable, Mapping

import jax

from .comm import CommEngine
from .spans import (
    EXEC_ACCOUNT,
    EXEC_COMPILE,
    EXEC_LAUNCH,
    EXEC_PULL,
    EXEC_SELECT,
    EXEC_WAIT,
    Spans,
)
from ..kernels.ops import build_chain

# sampled timing: about this many timed kernels per group and session
TIMED_PER_GROUP = 5


@dataclasses.dataclass
class ExecResult:
    outputs: dict  # block name -> array (exit kernels)
    makespan_ms: float
    n_transfers: int
    bytes_transferred: int
    kernels_per_group: dict
    kernel_ms: dict = dataclasses.field(default_factory=dict)
    #                                   # kernel -> wall ms of the timed kernels
    reexecuted: list = dataclasses.field(default_factory=list)
    #                                   # kernels re-run after group eviction
    model_makespan_ms: float = 0.0  # two-resource virtual-clock makespan
    lane_busy_ms: dict = dataclasses.field(default_factory=dict)
    n_prefetched: int = 0
    tier_busy_ms: dict = dataclasses.field(default_factory=dict)
    #                                   # wire time per topology tier
    n_throttled: int = 0  # prefetches deferred by the throttle
    n_preempted: int = 0  # in-flight copies cancelled by a group eviction
    fused_steps: int = 0  # compiled group-steps dispatched (fused=True)
    cache_hits: int = 0  # super-step cache hits (this session)
    cache_misses: int = 0  # super-step compilations (this session)
    n_streamed: int = 0  # demand pulls executed as chunked channels
    n_stalled_chunks: int = 0  # chunks delayed by channel backpressure
    stream_busy_ms: float = 0.0  # lane time booked by channel chunks
    n_depth_adjust: int = 0  # adaptive prefetch-depth raises/lowers
    n_waves: int = 0  # fused dispatch barriers (== fused_steps serialized;
    #                                   # fewer with async_groups wave overlap)
    overlap_ms: float = 0.0  # virtual compute time co-scheduled inside waves
    #                                   # (sum of member spans minus wave span)
    n_donated: int = 0  # donated input buffers the fused calls consumed


@dataclasses.dataclass
class SuperStepRun:
    """One fused group-step: a whole intra-group kernel chain dispatched as
    a single jitted call (audit record for apportionment / donation)."""

    group: str
    members: list  # kernel names, chain order
    ms: float  # fused wall ms (one barrier for the whole chain)
    cache_hit: bool
    donated: list  # external input blocks donated to XLA
    n_transfers: int
    nbytes: int


class SuperStepCache:
    """Persistent compiled-group-step cache.

    Keys are ``(revision, group signature, shapes/dtypes)`` — the revision
    tag comes from the online partitioner (bumped only by full-repartition
    escalations, NOT by boundary-local FM moves or warm ingests), the group
    signature encodes the chain's ops + internal wiring + donation mask, and
    the shape/dtype tuple pins the compiled executable's layout.  Entries
    are AOT-compiled (``jit(...).lower(...).compile()``), so a cache hit
    dispatches with zero tracing/compilation on the timed path, and a miss
    compiles *outside* the timed region (compile time never pollutes the
    apportioned per-kernel wall times).

    The cache assumes the op -> implementation mapping is stable for its
    lifetime (one ``attach`` convention per serving executor): signatures
    name kernel *ops*, not the identity of the attached callables.
    """

    def __init__(self, max_entries: int = 512):
        self.max_entries = max_entries
        self._fns: dict = {}
        self.hits = 0
        self.misses = 0

    def __len__(self) -> int:
        return len(self._fns)

    def clear(self) -> None:
        self._fns.clear()

    def get_or_build(self, key, builder):
        """-> (compiled fn, hit).  ``builder`` runs only on a miss."""
        fn = self._fns.get(key)
        if fn is not None:
            self.hits += 1
            return fn, True
        self.misses += 1
        fn = builder()
        if len(self._fns) >= self.max_entries:  # bounded: drop oldest entry
            self._fns.pop(next(iter(self._fns)))
        self._fns[key] = fn
        return fn, False


@dataclasses.dataclass
class KernelRun:
    """One executed kernel (an :meth:`ExecSession.step` record)."""

    name: str
    group: str
    ms: float  # wall ms if timed, else an estimate (0.0 with time_kernels off)
    n_transfers: int  # transfers this kernel's input gather caused
    nbytes: int  # bytes those transfers moved
    t_start: float = 0.0  # virtual start (comm model attached)
    t_finish: float = 0.0  # virtual finish (compute + overlapped transfers)
    t_ready: float = 0.0  # perf_counter once its output was ready (timed),
    #                       else once its launch returned
    timed: bool = False  # ms was measured (a sampled or fused-apportioned run)


class ExecSession:
    """Incremental execution of a task graph over device groups.

    The session owns the data-consistency state (block -> group -> array) and
    executes kernels in dependency order, one per :meth:`step`.  Between steps
    the caller may rewrite placements and apply platform churn — exactly what
    an online scheduling policy needs to co-drive real execution.

    ``comm`` + ``group_nodes`` attach the shared communication model: every
    pull books a lane on the actual src-node -> dst-node link (every crossed
    tier of a hierarchical topology) and kernels get virtual start/finish
    times with transfers overlapping compute (``prefetch_depth`` next-ready
    kernels have their inputs staged early).

    ``time_kernels`` times a fixed sample of the kernels (module docstring,
    *Sampled timing*); :attr:`op_ms` holds the estimates of the others.

    ``spans`` records the session's host time by span name
    (:mod:`repro.core.spans`); the serving loop passes its own recorder.
    """

    def __init__(
        self,
        executor: "JaxExecutor",
        g,
        assignment: Mapping[str, str],
        inputs: Mapping[str, jax.Array] | None = None,
        *,
        host_group: str | None = None,
        time_kernels: bool = False,
        gated: Iterable[str] = (),
        comm: CommEngine | None = None,
        group_nodes: Mapping[str, int] | None = None,
        prefetch_depth: int = 2,
        fused: bool = False,
        cache: SuperStepCache | None = None,
        revision: int = 0,
        streaming: bool = False,
        chunk_bytes: int | None = None,
        stream_depth: int = 2,
        async_groups: bool = False,
        cost_clock: bool = False,
        spans: Spans | None = None,
    ):
        g.validate()
        self.spans = spans if spans is not None else Spans()
        self.ex = executor
        self.g = g
        self.assignment = dict(assignment)
        self.host_group = executor.resolve_host_group(host_group)
        self.time_kernels = time_kernels
        self.fused = fused
        self.cache = (
            cache if cache is not None else (SuperStepCache() if fused else None)
        )
        self.revision = revision
        self.fused_steps = 0
        self.cache_hits = 0
        self.cache_misses = 0
        self.n_donated = 0
        self.superstep_runs: list[SuperStepRun] = []
        self._fused_buf: list[KernelRun] = []
        # gated kernels exist in the graph but may not run until admitted
        # (online request streams: the task arrived in the revision but its
        # wall-clock arrival time has not passed yet)
        self.gated: set[str] = set(gated)
        self.comm = comm
        self.group_nodes = dict(group_nodes or {})
        if comm is not None and not self.group_nodes:
            raise ValueError("a comm model needs group_nodes (group -> node)")
        self.prefetch_depth = prefetch_depth if comm is not None else 0
        # streaming: demand pulls open chunked channels instead of bulk
        # fetches — the consumer's virtual start gates on the FIRST chunk and
        # residual arrivals drain against its compute (see comm.StreamChannel);
        # the real device_put happens chunk-wise too, depth-bounded
        self.streaming = streaming and comm is not None
        # None -> the topology picks a per-route chunk size (flat topologies
        # return the fixed default, so the resolved value is bit-identical)
        self.chunk_bytes = chunk_bytes
        self.stream_depth = stream_depth
        # async_groups: fused dispatch happens in dependency WAVES — every
        # group with a runnable chain launches in the same wave (one barrier
        # per wave, not per group) and cross-group pulls are booked at the
        # consumer's own gate instead of the previous group-step's finish
        self.async_groups = async_groups and fused
        # cost_clock: with time_kernels off, drive the virtual timeline from
        # the cost table instead of zero-width kernels — deterministic model
        # makespans for benches and simulator-agreement checks (fused paths)
        self.cost_clock = cost_clock
        self.n_waves = 0
        self.overlap_ms = 0.0
        self._pending_channels: list[tuple[str, str, object]] = []
        self._block_window: dict[str, tuple[float, float]] = {}
        self._inputs = dict(inputs or {})
        self.valid: dict[str, dict[str, jax.Array]] = {}  # block -> group -> arr
        # virtual timeline (comm model): when a block's copy lands per group,
        # when each group's compute stream frees, per-kernel earliest starts
        self.vt_block: dict[tuple[str, str], float] = {}
        self.group_free: dict[str, float] = {}
        self.earliest: dict[str, float] = {}
        self.vnow = 0.0
        self.vmax = 0.0
        self.prefetched: set[tuple[str, str]] = set()
        for name in self._inputs:
            self._seed(name)
        self.n_transfers = 0
        self.nbytes = 0
        self.per_group: dict[str, int] = {}
        self.kernel_ms: dict[str, float] = {}
        self.blocks: dict[str, jax.Array] = {}
        self.reexecuted: list[str] = []
        self._order = [n for n in g.topo_order() if g.nodes[n].op != "source"]
        self._done: set[str] = set()
        # sampled timing: a group's kernels at session start set its stride
        n_g: dict[str, int] = {}
        for n in self._order:
            grp = self.assignment.get(n, self.host_group)
            n_g[grp] = n_g.get(grp, 0) + 1
        self._stride = {grp: max(1, c // TIMED_PER_GROUP) for grp, c in n_g.items()}
        self._ran: dict[str, int] = {}  # group -> kernels executed
        self._last_out: dict = {}  # device -> last output launched to it
        # (op, group) -> ms: an untimed kernel's estimate.  Each timed kernel
        # writes it; the caller may seed it (e.g. from a measured cost model)
        self.op_ms: dict[tuple[str, str], float] = {}
        self._t0 = time.perf_counter()

    # -- state ---------------------------------------------------------------

    def _node_of(self, group: str) -> int:
        return self.group_nodes.get(group, 0)

    def _seed(self, block: str) -> None:
        """(Re-)materialize a host-resident input block on the host group."""
        dev = self.ex.groups[self.host_group]
        self.valid[block] = {self.host_group: jax.device_put(self._inputs[block], dev)}
        self.vt_block[(block, self.host_group)] = 0.0

    def pending(self) -> list[str]:
        return [n for n in self._order if n not in self._done]

    def done(self) -> bool:
        return len(self._done) == len(self._order)

    def reassign(self, mapping: Mapping[str, str]) -> None:
        """Rewrite placements for not-yet-executed kernels (policy refresh)."""
        self.assignment.update(mapping)

    def admit(self, names, at: float | None = None) -> None:
        """Lift the arrival gate from ``names`` (they become schedulable as
        soon as their dependencies are satisfied).  ``at`` floors their
        virtual start at the admitting stream clock."""
        names = list(names)
        self.gated.difference_update(names)
        if at is not None:
            for n in names:
                self.earliest[n] = max(self.earliest.get(n, 0.0), at)

    def next_ready(self) -> str | None:
        for n in self._order:
            if n in self._done or n in self.gated:
                continue
            if all(
                p in self._done or self.g.nodes[p].op == "source"
                for p in self.g.predecessors(n)
            ):
                return n
        return None

    def _ready_next(self, count: int) -> list[str]:
        """Up to ``count`` currently-ready kernels (prefetch targets)."""
        out: list[str] = []
        for n in self._order:
            if n in self._done or n in self.gated:
                continue
            if all(
                p in self._done or self.g.nodes[p].op == "source"
                for p in self.g.predecessors(n)
            ):
                out.append(n)
                if len(out) >= count:
                    break
        return out

    # -- eviction (worker-drop recovery) ---------------------------------------

    def _requeue(self, name: str) -> None:
        if name not in self._done:
            return
        self._done.discard(name)
        self.reexecuted.append(name)
        for p in self.g.predecessors(name):
            if self.g.nodes[p].op != "source" and p not in self.valid:
                self._requeue(p)

    def evict_group(self, group: str) -> list[str]:
        """Group memory is gone (worker drop): invalidate its block copies.

        A block whose *last* copy lived there is lost; host input blocks are
        re-seeded from the caller's arrays, while kernel outputs still needed
        by a pending consumer force their producer (transitively) back onto
        the queue.  Prefetched-but-unconsumed copies on the dead group are
        discarded from the comm model too, so the consumer's re-pull books a
        fresh transfer instead of riding a phantom one.  Copies still in
        flight toward the dead group's memory node are preempted on the comm
        engine — their remaining lane time is released and they count toward
        ``n_preempted``.  Returns the kernels re-queued for re-execution."""
        if self.comm is not None:
            node = self._node_of(group)
            if not any(
                self._node_of(g) == node for g in self.group_nodes if g != group
            ):
                self.comm.preempt_dst(node, self.vnow)
        for block, grp in list(self.vt_block):
            if grp == group:
                del self.vt_block[(block, grp)]
        for block, grp in list(self.prefetched):
            if grp == group:
                self.prefetched.discard((block, grp))
        if self._pending_channels:
            # undrained channels toward the dead group die with it (their
            # booked chunk-0 segments are released by preempt_dst above)
            self._pending_channels = [
                c for c in self._pending_channels if c[1] != group
            ]
        lost: list[str] = []
        for block, ent in list(self.valid.items()):
            if ent.pop(group, None) is not None and not ent:
                del self.valid[block]
                lost.append(block)
        before = len(self.reexecuted)
        for block in lost:
            if block in self._inputs:
                self._seed(block)
            elif block in self.g.nodes and any(
                s not in self._done for s in self.g.successors(block)
            ):
                self._requeue(block)
        if self._fused_buf:
            # an already-executed-but-unreported member whose kernel was just
            # re-queued will run (and be reported) again: drop its stale record
            self._fused_buf = [r for r in self._fused_buf if r.name in self._done]
        return self.reexecuted[before:]

    # -- execution -------------------------------------------------------------

    def _input_keys(self, name: str) -> list[tuple[str, int]]:
        """(block key, byte count) for every input of ``name``."""
        out: list[tuple[str, int]] = []
        preds = self.g.predecessors(name)
        if not preds and f"{name}/in" in self.valid:
            out.append((f"{name}/in", 0))  # source-less entry kernel
        for pred in preds:
            # entry kernels read their seeded "<kernel>/in" block
            if self.g.nodes[pred].op == "source":
                out.append((name + "/in", 0))
            else:
                out.append((pred, self.g.edge(pred, name).nbytes))
        return out

    def _pull(
        self, key: str, nbytes: int, grp: str, dev, kind: str, now: float | None = None
    ) -> int:
        """Copy ``key`` onto ``grp`` if missing; returns bytes moved (0 when
        already valid there, or when the contention throttle deferred a
        prefetch — the lanes are booked *before* the real ``device_put``, so
        a throttled prefetch costs nothing and retries later).  ``now``
        overrides the booking clock: the wave executor issues pulls at the
        consumer's own gate, not the previous group-step's finish."""
        ent = self.valid.get(key)
        if ent is None or grp in ent:
            return 0
        if self.comm is not None:
            donor_grp = min(ent, key=lambda g: (self.vt_block.get((key, g), 0.0), g))
        else:
            donor_grp = next(iter(ent))
        donor = ent[donor_grp]
        nb = nbytes or donor.size * donor.dtype.itemsize
        t_now = self.vnow if now is None else now
        if self.streaming and kind == "demand":
            win = self._block_window.get(key)
            src_ready = self.vt_block.get((key, donor_grp), 0.0)
            # pro-rata chunk readiness only when the donor copy IS the
            # producer's own output (its compute window ends at src_ready)
            src_start = (
                win[0] if win is not None and abs(win[1] - src_ready) <= 1e-9 else None
            )
            ch = self.comm.open_stream(
                key,
                self._node_of(donor_grp),
                self._node_of(grp),
                nb,
                now=t_now,
                src_start=src_start,
                src_ready=src_ready,
                chunk_bytes=self.chunk_bytes,
                depth=self.stream_depth,
            )
            if ch is not None:
                # provisional: chunk-0 arrival gates the consumer's start;
                # drain() (post-dispatch) rewrites it to the last arrival
                self.vt_block[(key, grp)] = ch.first_ready
                self._pending_channels.append((key, grp, ch))
                with self.spans(EXEC_PULL):
                    ent[grp] = self._stream_put(donor, dev, ch.n_chunks)
                return nb
            # same node: no wire — fall through to the free bulk path
        if self.comm is not None:
            src_ready = self.vt_block.get((key, donor_grp), 0.0)
            if self.async_groups and kind == "demand":
                # non-blocking pull: the booking happens now, completion is
                # charged to the lanes, and the handle's ETA (not a barrier)
                # gates the consumer's admission into its wave
                h = self.comm.fetch_async(
                    key,
                    self._node_of(donor_grp),
                    self._node_of(grp),
                    nb,
                    now=t_now,
                    src_ready=src_ready,
                    kind=kind,
                )
                te = h.eta
            else:
                te = self.comm.fetch(
                    key,
                    self._node_of(donor_grp),
                    self._node_of(grp),
                    nb,
                    now=t_now,
                    src_ready=src_ready,
                    kind=kind,
                )
            if te is None:  # throttled prefetch: nothing moved
                return 0
            self.vt_block[(key, grp)] = te
            if kind == "prefetch":
                self.prefetched.add((key, grp))
        with self.spans(EXEC_PULL):
            ent[grp] = jax.device_put(donor, dev)
        return nb

    def _stream_put(self, donor, dev, n_chunks: int):
        """Chunk-wise ``device_put``: the donor's leading axis is split into
        up to ``n_chunks`` slices copied as separate async dispatches, with at
        most ``stream_depth`` copies in flight (the real-transfer analogue of
        the channel's bounded depth); the slices reassemble bit-identically on
        the destination device."""
        if n_chunks <= 1 or donor.ndim == 0 or donor.shape[0] < 2:
            return jax.device_put(donor, dev)
        rows = donor.shape[0]
        step = -(-rows // min(n_chunks, rows))
        parts = []
        for i in range(0, rows, step):
            parts.append(jax.device_put(donor[i : i + step], dev))
            if self.stream_depth and len(parts) > self.stream_depth:
                with self.spans(EXEC_WAIT):
                    parts[-self.stream_depth - 1].block_until_ready()
        import jax.numpy as jnp

        with jax.default_device(dev):
            return jnp.concatenate(parts, axis=0)

    def _drain_channels(self, vstart: float, ms: float, vfinish: float) -> float:
        """Drain every channel opened for the kernel just dispatched against
        its compute window; returns the extended virtual finish (a consumer
        cannot retire before its last chunk arrives AND is consumed)."""
        for key, grp, ch in self._pending_channels:
            ch_finish, arrival_last = ch.drain(vstart, ms)
            vfinish = max(vfinish, ch_finish)
            self.vt_block[(key, grp)] = arrival_last
        self._pending_channels.clear()
        return vfinish

    def _gather(self, name: str, grp: str, dev) -> tuple[list, int, int, float]:
        """Pull input blocks for ``name`` onto ``grp``.
        Returns (args, n_transfers, nbytes, inputs-ready virtual time)."""
        args: list[jax.Array] = []
        nt = nb = 0
        ready_vt = 0.0
        for key, nbytes in self._input_keys(name):
            ent = self.valid.get(key)
            if ent is None:
                continue
            moved = self._pull(key, nbytes, grp, dev, "demand")
            if moved:
                nt += 1
                nb += moved
            self.prefetched.discard((key, grp))
            ready_vt = max(ready_vt, self.vt_block.get((key, grp), 0.0))
            args.append(ent[grp])
        return args, nt, nb, ready_vt

    def _prefetch_ready(self) -> None:
        """Stage inputs of the next ready kernels onto their assigned groups
        while "now" is still this kernel's finish — the staged copies ride
        comm lanes under the next kernels' compute."""
        if self.comm is None or self.prefetch_depth <= 0:
            return
        if self.streaming:
            return  # channels already overlap chunk-wise; no bulk speculation
        for n in self._ready_next(self.prefetch_depth):
            grp = self.assignment.get(n, self.host_group)
            dev = self.ex.groups[grp]
            for key, nbytes in self._input_keys(n):
                moved = self._pull(key, nbytes, grp, dev, "prefetch")
                if moved:
                    self.n_transfers += 1
                    self.nbytes += moved

    # -- fused super-steps -----------------------------------------------------

    def _plan_superstep(self) -> tuple[str | None, list[str]]:
        """-> (group, maximal runnable intra-group chain, topological order).

        The first ready kernel (what :meth:`next_ready` would return) anchors
        the chain and fixes the group; every later not-done, not-gated kernel
        of that group whose predecessors are all finished or earlier chain
        members joins it.  The anchor is always a member, so progress is
        guaranteed; kernels of other groups end up in later group-steps.
        ``(None, [])`` when nothing is ready."""
        members: list[str] = []
        member_set: set[str] = set()
        grp: str | None = None
        done = self._done
        gated = self.gated
        nodes = self.g.nodes
        predecessors = self.g.predecessors
        get_group = self.assignment.get
        host = self.host_group
        for n in self._order:
            if n in done or n in gated:
                continue
            n_grp = get_group(n, host)
            if grp is not None and n_grp != grp:
                continue
            if all(
                p in done or p in member_set or nodes[p].op == "source"
                for p in predecessors(n)
            ):
                if grp is None:
                    grp = n_grp
                members.append(n)
                member_set.add(n)
        return grp, members

    def _donatable(self, key: str, grp: str, member_set) -> bool:
        """May the group's copy of ``key`` be donated to the fused call?
        Only when it is dead afterwards: not a caller-owned seed (re-seeding
        reads it), not an exit output, the group's copy is the ONLY one (a
        sibling group may alias the same physical buffer on a shared
        device), and every not-yet-finished consumer is inside the chain."""
        if key in self._inputs:
            return False
        ent = self.valid.get(key)
        if ent is None or set(ent) != {grp}:
            return False
        if key in self.g.nodes:
            if not self.g.successors(key):
                return False  # exit output: result() must return it
            return all(
                s in self._done or s in member_set for s in self.g.successors(key)
            )
        return False

    def _fused_superstep(self, record: bool = True) -> bool:
        """Plan + dispatch one compiled group-step; with ``record`` it fills
        ``_fused_buf`` with per-kernel records (the :meth:`step` replay
        queue; :meth:`run_all` skips them).  False when nothing is ready.

        The planning scan inlines :meth:`_plan_superstep` (the reference
        spec) and classifies each member's predecessors in the same pass —
        this loop's per-kernel cost IS the fused path's dispatch overhead,
        so it stays a single lean sweep with no helper calls."""
        done = self._done
        gated = self.gated
        valid = self.valid
        vt_block = self.vt_block
        g_nodes = self.g.nodes
        successors = self.g.successors
        predecessors = self.g.predecessors
        g_edge = self.g.edge
        get_group = self.assignment.get
        host = self.host_group

        # pass 1 — membership + argument classification (side-effect free):
        # the first ready kernel anchors the chain and fixes the group; each
        # joining member's predecessors become int entries (intra-chain slot)
        # or (key, nbytes) entries (external block)
        grp: str | None = None
        dev = None
        members: list[str] = []
        midx: dict[str, int] = {}
        fns: list = []
        ops: list[str] = []
        costs: list[float] = []
        entries: list[list] = []
        with self.spans(EXEC_SELECT):
            for n in self._order:
                if n in done or n in gated:
                    continue
                n_grp = get_group(n, host)
                if grp is not None and n_grp != grp:
                    continue
                preds = predecessors(n)
                entry: list = []
                runnable = True
                for p in preds:
                    j = midx.get(p)
                    if j is not None:
                        entry.append(j)
                    elif g_nodes[p].op == "source":
                        entry.append((n + "/in", 0))  # entry kernel: seeded input
                    elif p in done:
                        entry.append((p, g_edge(p, n).nbytes))
                    else:
                        runnable = False
                        break
                if not runnable:
                    continue
                if not preds and (n + "/in") in valid:
                    entry.append((n + "/in", 0))  # source-less entry kernel
                k = g_nodes[n]
                if k.fn is None:
                    raise ValueError(f"kernel {n} has no fn")
                if grp is None:
                    grp = n_grp
                    dev = self.ex.groups[grp]
                midx[n] = len(members)
                members.append(n)
                fns.append(k.fn)
                ops.append(k.op)
                costs.append(k.costs.get(grp, 0.0))
                entries.append(entry)
        if grp is None:
            return False
        member_set = midx.keys()

        # pass 2 — gather external inputs once (demand pulls book comm lanes
        # exactly as the unfused path would, attributed to the first needing
        # kernel) and pick which outputs to materialize
        pull = self._pull
        prefetched_discard = self.prefetched.discard
        ext_keys: list[str] = []
        ext_index: dict[str, int] = {}
        plan: list[tuple] = []
        per_nt: list[int] = []
        per_nb: list[int] = []
        ready_vt: list[float] = []
        keep: list[int] = []
        out_slot: dict[str, int] = {}
        total_nt = total_nb = 0
        member_chans: list[list] = []  # channels attributed to each member
        pend = self._pending_channels
        for i, n in enumerate(members):
            srcs: list[tuple[str, int]] = []
            rv = 0.0
            nt = nb = 0
            nch0 = len(pend)
            for item in entries[i]:
                if type(item) is int:
                    srcs.append(("mem", item))
                    continue
                key, nbytes = item
                if key not in valid:
                    continue  # same skip as _gather on a missing block
                e = ext_index.get(key)
                if e is None:
                    moved = pull(key, nbytes, grp, dev, "demand")
                    if moved:
                        nt += 1
                        nb += moved
                    prefetched_discard((key, grp))
                    e = ext_index[key] = len(ext_keys)
                    ext_keys.append(key)
                srcs.append(("ext", e))
                rv = max(rv, vt_block.get((key, grp), 0.0))
            plan.append((ops[i], tuple(srcs)))
            per_nt.append(nt)
            per_nb.append(nb)
            total_nt += nt
            total_nb += nb
            ready_vt.append(rv)
            # materialize only LIVE outputs — exits, or blocks a kernel
            # outside this chain still needs; dead intermediates stay inside
            # the XLA computation where they fuse away (the dispatch win)
            succs = successors(n)
            if not succs or any(s not in done and s not in member_set for s in succs):
                out_slot[n] = len(keep)
                keep.append(i)
            member_chans.append(pend[nch0:])
        pend.clear()
        self.n_transfers += total_nt
        self.nbytes += total_nb

        ext_args = [valid[key][grp] for key in ext_keys]
        donate = tuple(
            i
            for i, key in enumerate(ext_keys)
            if self._donatable(key, grp, member_set)
        )
        sig = (
            self.revision,
            grp,
            tuple(plan),
            tuple(keep),
            tuple((a.shape, a.dtype) for a in ext_args),
            donate,
        )

        def compile_chain():
            chain = build_chain(
                [(fn, srcs) for fn, (_, srcs) in zip(fns, plan)], keep
            )
            specs = [jax.ShapeDtypeStruct(a.shape, a.dtype) for a in ext_args]
            with (
                self.spans(EXEC_COMPILE),
                jax.default_device(dev),
                warnings.catch_warnings(),
            ):
                # donation is advisory: XLA warns about a donated buffer
                # it cannot alias to an output (n_donated counts the rest)
                warnings.filterwarnings("ignore", message=".*donated.*")
                return jax.jit(chain, donate_argnums=donate).lower(*specs).compile()

        fn, hit = self.cache.get_or_build(sig, compile_chain)
        self.cache_hits += int(hit)
        self.cache_misses += int(not hit)

        ms = 0.0
        tk = self.time_kernels
        spans = self.spans
        if tk:
            # ONE host sync per group-step, outside the timed region: input
            # production time must not leak into the apportioned kernel times
            with spans(EXEC_WAIT):
                for a in ext_args:
                    if hasattr(a, "block_until_ready"):
                        a.block_until_ready()
            t0 = time.perf_counter()
        anchor = members[0]
        req = g_nodes[anchor].meta.get("req", "")
        with spans(EXEC_LAUNCH, kernel=anchor, op="superstep", req=req):
            if donate:
                with warnings.catch_warnings():
                    warnings.filterwarnings("ignore", message=".*donated.*")
                    outs = fn(*ext_args)
            else:
                outs = fn(*ext_args)
        if tk:
            with spans(EXEC_WAIT):
                for o in outs:
                    if hasattr(o, "block_until_ready"):
                        o.block_until_ready()
                t_ready = time.perf_counter()
            ms = (t_ready - t0) * 1e3
        else:
            t_ready = time.perf_counter()

        # donated external buffers are consumed: drop the group's copies
        donated = [ext_keys[i] for i in donate]
        self.n_donated += sum(ext_args[i].is_deleted() for i in donate)
        for key in donated:
            ent = valid.get(key)
            if ent is not None:
                ent.pop(grp, None)
                if not ent:
                    del valid[key]
            vt_block.pop((key, grp), None)

        # apportion the fused wall time to members by cost-table weight, so
        # MeasuredCostModel.observe / EWMA feedback keeps working per kernel
        weights = [c if c > 0.0 else 0.0 for c in costs]
        wsum = sum(weights)
        if wsum <= 0.0:
            weights = [1.0] * len(members)
            wsum = float(len(members))
        cc = self.cost_clock and not tk
        comm = self.comm
        kernel_ms = self.kernel_ms
        blocks = self.blocks
        buf_append = self._fused_buf.append
        for i, (n, w) in enumerate(zip(members, weights)):
            kms = costs[i] if cc else ms * w / wsum
            if tk:
                kernel_ms[n] = kms
            vstart = vfinish = 0.0
            if comm is not None:
                vstart = max(
                    self.group_free.get(grp, 0.0),
                    ready_vt[i],
                    self.earliest.get(n, 0.0),
                )
                vfinish = vstart + kms
                for key, cgrp, ch in member_chans[i]:
                    ch_finish, arrival_last = ch.drain(vstart, kms)
                    vfinish = max(vfinish, ch_finish)
                    vt_block[(key, cgrp)] = arrival_last
                self.group_free[grp] = vfinish
                self.vnow = vfinish
                self.vmax = max(self.vmax, vfinish)
                self._block_window[n] = (vstart, vfinish)
            slot = out_slot.get(n)
            if slot is not None:
                out = outs[slot]
                valid[n] = {grp: out}
                blocks[n] = out
                if comm is not None:
                    vt_block[(n, grp)] = vfinish
            done.add(n)
            if record:
                buf_append(
                    KernelRun(
                        n, grp, kms, per_nt[i], per_nb[i], vstart, vfinish, t_ready, tk
                    )
                )
        self.per_group[grp] = self.per_group.get(grp, 0) + len(members)
        self.fused_steps += 1
        self.n_waves += 1  # serialized dispatch: every group-step is a barrier
        self.superstep_runs.append(
            SuperStepRun(grp, members, ms, hit, donated, total_nt, total_nb)
        )
        self._prefetch_ready()
        return True

    def _fused_wave(self, record: bool = True) -> bool:
        """Plan + dispatch one dependency WAVE: every group with a runnable
        intra-group chain launches its fused super-step in the same round —
        one ``block_until_ready`` for the whole wave instead of one per
        group, so XLA runs independent groups' chains concurrently.

        Wave membership repeats the :meth:`_plan_superstep` scan once per
        still-unplanned group; a kernel whose predecessor sits in *another*
        chain of this wave is not runnable yet and joins a later wave, so
        chains are mutually independent by construction and waves are
        exactly the topological levels of the quotient (group) DAG.  Each
        chain's cross-group pulls are issued non-blocking at the consumer's
        own gate (``_pull(now=...)`` + :meth:`CommEngine.fetch_async`), and
        its virtual start floors at the last pull's ETA — ETA-gated
        admission, not a global barrier.  The wave wall is apportioned to
        ALL wave members by cost weight so ``MeasuredCostModel`` feedback
        survives; False when nothing is ready."""
        done = self._done
        gated = self.gated
        valid = self.valid
        vt_block = self.vt_block
        g_nodes = self.g.nodes
        successors = self.g.successors
        predecessors = self.g.predecessors
        g_edge = self.g.edge
        get_group = self.assignment.get
        host = self.host_group

        # pass 1 — wave membership: one maximal runnable chain per group
        # with ready work (identical scan to _fused_superstep, repeated with
        # already-claimed groups excluded)
        plans: list[dict] = []
        claimed: set[str] = set()
        with self.spans(EXEC_SELECT):
            while True:
                grp: str | None = None
                dev = None
                members: list[str] = []
                midx: dict[str, int] = {}
                fns: list = []
                ops: list[str] = []
                costs: list[float] = []
                entries: list[list] = []
                for n in self._order:
                    if n in done or n in gated:
                        continue
                    n_grp = get_group(n, host)
                    if n_grp in claimed or (grp is not None and n_grp != grp):
                        continue
                    preds = predecessors(n)
                    entry: list = []
                    runnable = True
                    for p in preds:
                        j = midx.get(p)
                        if j is not None:
                            entry.append(j)
                        elif g_nodes[p].op == "source":
                            entry.append((n + "/in", 0))
                        elif p in done:
                            entry.append((p, g_edge(p, n).nbytes))
                        else:
                            runnable = False
                            break
                    if not runnable:
                        continue
                    if not preds and (n + "/in") in valid:
                        entry.append((n + "/in", 0))
                    k = g_nodes[n]
                    if k.fn is None:
                        raise ValueError(f"kernel {n} has no fn")
                    if grp is None:
                        grp = n_grp
                        dev = self.ex.groups[grp]
                    midx[n] = len(members)
                    members.append(n)
                    fns.append(k.fn)
                    ops.append(k.op)
                    costs.append(k.costs.get(grp, 0.0))
                    entries.append(entry)
                if grp is None:
                    break
                claimed.add(grp)
                plans.append(
                    dict(
                        grp=grp,
                        dev=dev,
                        members=members,
                        midx=midx,
                        fns=fns,
                        ops=ops,
                        costs=costs,
                        entries=entries,
                    )
                )
        if not plans:
            return False

        # pass 2 — per chain: gather external inputs with non-blocking
        # pulls booked at the consumer's own gate (its group's free time /
        # admission floor), NOT the previous group-step's finish — that
        # booking clock is the whole serialization the wave mode removes
        pull = self._pull
        prefetched_discard = self.prefetched.discard
        pend = self._pending_channels
        consumers: dict[str, set[str]] = {}  # ext key -> pulling wave chains
        for pl in plans:
            grp = pl["grp"]
            dev = pl["dev"]
            members = pl["members"]
            entries = pl["entries"]
            member_set = pl["midx"].keys()
            gate = self.group_free.get(grp, 0.0)
            ext_keys: list[str] = []
            ext_index: dict[str, int] = {}
            plan: list[tuple] = []
            per_nt: list[int] = []
            per_nb: list[int] = []
            ready_vt: list[float] = []
            keep: list[int] = []
            out_slot: dict[str, int] = {}
            total_nt = total_nb = 0
            member_chans: list[list] = []
            for i, n in enumerate(members):
                srcs: list[tuple[str, int]] = []
                rv = 0.0
                nt = nb = 0
                nch0 = len(pend)
                for item in entries[i]:
                    if type(item) is int:
                        srcs.append(("mem", item))
                        continue
                    key, nbytes = item
                    if key not in valid:
                        continue
                    e = ext_index.get(key)
                    if e is None:
                        moved = pull(
                            key,
                            nbytes,
                            grp,
                            dev,
                            "demand",
                            now=max(gate, self.earliest.get(n, 0.0)),
                        )
                        if moved:
                            nt += 1
                            nb += moved
                        prefetched_discard((key, grp))
                        e = ext_index[key] = len(ext_keys)
                        ext_keys.append(key)
                        consumers.setdefault(key, set()).add(grp)
                    srcs.append(("ext", e))
                    rv = max(rv, vt_block.get((key, grp), 0.0))
                plan.append((pl["ops"][i], tuple(srcs)))
                per_nt.append(nt)
                per_nb.append(nb)
                total_nt += nt
                total_nb += nb
                ready_vt.append(rv)
                succs = successors(n)
                if not succs or any(
                    s not in done and s not in member_set for s in succs
                ):
                    out_slot[n] = len(keep)
                    keep.append(i)
                member_chans.append(pend[nch0:])
            pend.clear()
            self.n_transfers += total_nt
            self.nbytes += total_nb
            pl.update(
                plan=plan,
                per_nt=per_nt,
                per_nb=per_nb,
                ready_vt=ready_vt,
                keep=keep,
                out_slot=out_slot,
                ext_keys=ext_keys,
                member_chans=member_chans,
                total_nt=total_nt,
                total_nb=total_nb,
            )

        # wave seal — a block whose every remaining consumer sits inside
        # exactly ONE chain of this wave is dead outside it: drop the other
        # groups' copies (incl. stale prefetches) so the consuming chain's
        # copy becomes sole and _donatable can hand the buffer to the fused
        # call — donation across group boundaries, unlocked by the seal
        wave_grp_of: dict[str, str] = {}
        for pl in plans:
            for n in pl["members"]:
                wave_grp_of[n] = pl["grp"]
        for pl in plans:
            grp = pl["grp"]
            for key in pl["ext_keys"]:
                if key in self._inputs or key not in g_nodes:
                    continue  # caller-owned seed / seeded "<kernel>/in" block
                succs = successors(key)
                if not succs:
                    continue  # exit output: result() must return it
                if len(consumers.get(key, ())) != 1:
                    continue  # two chains pulled it: neither copy is sole
                if not all(
                    s in done or wave_grp_of.get(s) == grp for s in succs
                ):
                    continue  # a consumer outside this wave still needs it
                ent = valid.get(key)
                if ent is None:
                    continue
                for ogrp in [o for o in ent if o != grp]:
                    del ent[ogrp]
                    vt_block.pop((key, ogrp), None)
                    prefetched_discard((key, ogrp))

        # compile every chain (SuperStepCache reused unchanged), dispatch
        # them back to back, then ONE barrier for the whole wave
        cache = self.cache
        tk = self.time_kernels
        for pl in plans:
            grp = pl["grp"]
            dev = pl["dev"]
            ext_keys = pl["ext_keys"]
            ext_args = [valid[key][grp] for key in ext_keys]
            member_set = pl["midx"].keys()
            donate = tuple(
                i
                for i, key in enumerate(ext_keys)
                if self._donatable(key, grp, member_set)
            )
            sig = (
                self.revision,
                grp,
                tuple(pl["plan"]),
                tuple(pl["keep"]),
                tuple((a.shape, a.dtype) for a in ext_args),
                donate,
            )

            def compile_chain(pl=pl, ext_args=ext_args, dev=dev, donate=donate):
                chain = build_chain(
                    [(fn, srcs) for fn, (_, srcs) in zip(pl["fns"], pl["plan"])],
                    pl["keep"],
                )
                specs = [jax.ShapeDtypeStruct(a.shape, a.dtype) for a in ext_args]
                with (
                    self.spans(EXEC_COMPILE),
                    jax.default_device(dev),
                    warnings.catch_warnings(),
                ):
                    warnings.filterwarnings("ignore", message=".*donated.*")
                    return (
                        jax.jit(chain, donate_argnums=donate).lower(*specs).compile()
                    )

            fn, hit = cache.get_or_build(sig, compile_chain)
            self.cache_hits += int(hit)
            self.cache_misses += int(not hit)
            pl.update(fn=fn, hit=hit, ext_args=ext_args, donate=donate)

        wave_ms = 0.0
        spans = self.spans
        if tk:
            # ONE host sync for the whole wave's externals, outside the
            # timed region (input production must not leak into the wall)
            with spans(EXEC_WAIT):
                for pl in plans:
                    for a in pl["ext_args"]:
                        if hasattr(a, "block_until_ready"):
                            a.block_until_ready()
            t0 = time.perf_counter()
        for pl in plans:
            anchor = pl["members"][0]
            req = g_nodes[anchor].meta.get("req", "")
            with spans(EXEC_LAUNCH, kernel=anchor, op="superstep", req=req):
                if pl["donate"]:
                    with warnings.catch_warnings():
                        warnings.filterwarnings("ignore", message=".*donated.*")
                        pl["outs"] = pl["fn"](*pl["ext_args"])
                else:
                    pl["outs"] = pl["fn"](*pl["ext_args"])
        if tk:
            # the wave's single barrier
            with spans(EXEC_WAIT):
                for pl in plans:
                    for o in pl["outs"]:
                        if hasattr(o, "block_until_ready"):
                            o.block_until_ready()
                t_ready = time.perf_counter()
            wave_ms = (t_ready - t0) * 1e3
        else:
            t_ready = time.perf_counter()

        # retire: apportion the wave wall across ALL wave members by cost
        # weight (or read the cost clock), roll each chain's virtual times
        # forward independently, and account the wave's overlap
        all_costs = [c for pl in plans for c in pl["costs"]]
        weights = [c if c > 0.0 else 0.0 for c in all_costs]
        wsum = sum(weights)
        if wsum <= 0.0:
            weights = [1.0] * len(all_costs)
            wsum = float(len(all_costs))
        cc = self.cost_clock and not tk
        comm = self.comm
        kernel_ms = self.kernel_ms
        blocks = self.blocks
        buf_append = self._fused_buf.append
        wi = 0
        wave_lo: float | None = None
        wave_hi = 0.0
        busy = 0.0
        for pl in plans:
            grp = pl["grp"]
            donated = [pl["ext_keys"][i] for i in pl["donate"]]
            self.n_donated += sum(pl["ext_args"][i].is_deleted() for i in pl["donate"])
            for key in donated:
                ent = valid.get(key)
                if ent is not None:
                    ent.pop(grp, None)
                    if not ent:
                        del valid[key]
                vt_block.pop((key, grp), None)
            outs = pl["outs"]
            out_slot = pl["out_slot"]
            chain_ms = 0.0
            for i, n in enumerate(pl["members"]):
                w = weights[wi]
                wi += 1
                kms = pl["costs"][i] if cc else wave_ms * w / wsum
                chain_ms += kms
                if tk:
                    kernel_ms[n] = kms
                vstart = vfinish = 0.0
                if comm is not None:
                    vstart = max(
                        self.group_free.get(grp, 0.0),
                        pl["ready_vt"][i],
                        self.earliest.get(n, 0.0),
                    )
                    vfinish = vstart + kms
                    for key, cgrp, ch in pl["member_chans"][i]:
                        ch_finish, arrival_last = ch.drain(vstart, kms)
                        vfinish = max(vfinish, ch_finish)
                        vt_block[(key, cgrp)] = arrival_last
                    self.group_free[grp] = vfinish
                    self.vmax = max(self.vmax, vfinish)
                    self._block_window[n] = (vstart, vfinish)
                    wave_lo = vstart if wave_lo is None else min(wave_lo, vstart)
                    wave_hi = max(wave_hi, vfinish)
                    busy += vfinish - vstart
                slot = out_slot.get(n)
                if slot is not None:
                    out = outs[slot]
                    valid[n] = {grp: out}
                    blocks[n] = out
                    if comm is not None:
                        vt_block[(n, grp)] = vfinish
                done.add(n)
                if record:
                    buf_append(
                        KernelRun(
                            n, grp, kms, pl["per_nt"][i], pl["per_nb"][i],
                            vstart, vfinish, t_ready, tk,
                        )
                    )
            self.per_group[grp] = self.per_group.get(grp, 0) + len(pl["members"])
            self.fused_steps += 1
            self.superstep_runs.append(
                SuperStepRun(
                    grp,
                    pl["members"],
                    chain_ms,  # the chain's apportioned share of the wave
                    pl["hit"],
                    donated,
                    pl["total_nt"],
                    pl["total_nb"],
                )
            )
        if comm is not None and wave_lo is not None:
            self.vnow = max(self.vnow, wave_hi)
            # co-scheduled compute: member spans beyond the wave span
            self.overlap_ms += max(0.0, busy - (wave_hi - wave_lo))
            comm.poll(self.vnow)  # fire completion callbacks for landed pulls
        self.n_waves += 1
        self._prefetch_ready()
        return True

    def step(self) -> KernelRun | None:
        """Execute the next ready kernel; ``None`` when the graph is drained.

        In fused mode a whole group-step executes at once (one compiled
        dispatch, one barrier) and its per-kernel records are replayed one
        per call, so online callers consume the same stepwise interface."""
        spans = self.spans
        with spans(EXEC_ACCOUNT):
            if self.fused:
                dispatch = (
                    self._fused_wave if self.async_groups else self._fused_superstep
                )
                if not self._fused_buf and not dispatch():
                    return None
                return self._fused_buf.pop(0)
            with spans(EXEC_SELECT):
                name = self.next_ready()
            if name is None:
                return None
            k = self.g.nodes[name]
            grp = self.assignment.get(name, self.host_group)
            dev = self.ex.groups[grp]
            args, nt, nb, ready_vt = self._gather(name, grp, dev)
            self.n_transfers += nt
            self.nbytes += nb
            if k.fn is None:
                raise ValueError(f"kernel {name} has no fn")
            ms = 0.0
            timed = False
            if self.time_kernels:
                i = self._ran.get(grp, 0)
                self._ran[grp] = i + 1
                timed = i % self._stride.get(grp, 1) == 0
            if timed:
                # start on a drained device with ready inputs, so the time is
                # this kernel's launch and run, not the queued work before it
                with spans(EXEC_WAIT):
                    for a in args:
                        if hasattr(a, "block_until_ready"):
                            a.block_until_ready()
                    last = self._last_out.get(dev)
                    if last is not None:
                        last.block_until_ready()
                t0 = time.perf_counter()
            req = k.meta.get("req", "")
            with (
                spans(EXEC_LAUNCH, kernel=name, op=k.op, req=req),
                jax.default_device(dev),
            ):
                out = k.fn(*args)
            if timed:
                with spans(EXEC_WAIT):
                    if hasattr(out, "block_until_ready"):
                        out.block_until_ready()
                    t_ready = time.perf_counter()
                ms = (t_ready - t0) * 1e3
                self.kernel_ms[name] = ms
                self.op_ms[(k.op, grp)] = ms
            else:
                t_ready = time.perf_counter()
                if self.time_kernels:
                    ms = self.op_ms.get((k.op, grp), 0.0)
            if self.time_kernels and hasattr(out, "block_until_ready"):
                self._last_out[dev] = out
            vstart = vfinish = 0.0
            if self.comm is not None:
                vstart = max(
                    self.group_free.get(grp, 0.0),
                    ready_vt,
                    self.earliest.get(name, 0.0),
                )
                vfinish = vstart + ms
                if self._pending_channels:
                    vfinish = self._drain_channels(vstart, ms, vfinish)
                self.group_free[grp] = vfinish
                self.vnow = vfinish
                self.vmax = max(self.vmax, vfinish)
                self.vt_block[(name, grp)] = vfinish
                self._block_window[name] = (vstart, vfinish)
            self.valid[name] = {grp: out}
            self.blocks[name] = out
            self.per_group[grp] = self.per_group.get(grp, 0) + 1
            self._done.add(name)
            self._prefetch_ready()
            return KernelRun(name, grp, ms, nt, nb, vstart, vfinish, t_ready, timed)

    def run_all(self) -> None:
        if self.fused:
            # drain whole group-steps directly: no one-record-per-step()
            # replay, no per-kernel KernelRun construction — batch callers
            # only consume the aggregate result()/superstep_runs state
            self._fused_buf.clear()
            dispatch = self._fused_wave if self.async_groups else self._fused_superstep
            while not self.done() and dispatch(record=False):
                pass
            return
        while self.step() is not None:
            pass

    def result(self) -> ExecResult:
        outs = {n: self.blocks[n] for n in self.g.exit_nodes() if n in self.blocks}
        with self.spans(EXEC_WAIT):
            for a in outs.values():
                a.block_until_ready()
        dt = (time.perf_counter() - self._t0) * 1e3
        return ExecResult(
            outputs=outs,
            makespan_ms=dt,
            n_transfers=self.n_transfers,
            bytes_transferred=self.nbytes,
            kernels_per_group=self.per_group,
            kernel_ms=dict(self.kernel_ms),
            reexecuted=list(self.reexecuted),
            model_makespan_ms=self.vmax,
            lane_busy_ms=self.comm.lane_busy_ms() if self.comm else {},
            n_prefetched=self.comm.n_prefetched if self.comm else 0,
            tier_busy_ms=self.comm.tier_busy_ms() if self.comm else {},
            n_throttled=self.comm.n_throttled if self.comm else 0,
            n_preempted=self.comm.n_preempted if self.comm else 0,
            fused_steps=self.fused_steps,
            cache_hits=self.cache_hits,
            cache_misses=self.cache_misses,
            n_streamed=self.comm.n_streamed if self.comm else 0,
            n_stalled_chunks=self.comm.n_stalled_chunks if self.comm else 0,
            stream_busy_ms=self.comm.stream_busy_ms if self.comm else 0.0,
            n_depth_adjust=self.comm.n_depth_adjust if self.comm else 0,
            n_waves=self.n_waves,
            overlap_ms=self.overlap_ms,
            n_donated=self.n_donated,
        )


class JaxExecutor:
    def __init__(self, groups: Mapping[str, jax.Device]):
        """groups: group name -> representative device."""
        self.groups = dict(groups)

    def resolve_host_group(self, host_group: str | None = None) -> str:
        """The group seeding host-resident inputs.  Defaults to the
        lexicographically-first group name so multi-group placements never
        depend on dict insertion order."""
        if host_group is None:
            return min(self.groups)
        if host_group not in self.groups:
            raise KeyError(f"unknown host group {host_group!r}")
        return host_group

    def session(
        self,
        g,
        assignment: Mapping[str, str],
        inputs: Mapping[str, jax.Array] | None = None,
        *,
        host_group: str | None = None,
        time_kernels: bool = False,
        gated: Iterable[str] = (),
        comm: CommEngine | None = None,
        group_nodes: Mapping[str, int] | None = None,
        prefetch_depth: int = 2,
        fused: bool = False,
        cache: SuperStepCache | None = None,
        revision: int = 0,
        streaming: bool = False,
        chunk_bytes: int | None = None,
        stream_depth: int = 2,
        async_groups: bool = False,
        cost_clock: bool = False,
        spans: Spans | None = None,
    ) -> ExecSession:
        return ExecSession(
            self,
            g,
            assignment,
            inputs,
            host_group=host_group,
            time_kernels=time_kernels,
            gated=gated,
            comm=comm,
            group_nodes=group_nodes,
            prefetch_depth=prefetch_depth,
            fused=fused,
            cache=cache,
            revision=revision,
            streaming=streaming,
            chunk_bytes=chunk_bytes,
            stream_depth=stream_depth,
            async_groups=async_groups,
            cost_clock=cost_clock,
            spans=spans,
        )

    def run(
        self,
        g,
        assignment: Mapping[str, str],
        inputs: Mapping[str, jax.Array] | None = None,
        *,
        host_group: str | None = None,
        time_kernels: bool = False,
        fused: bool = False,
        cache: SuperStepCache | None = None,
    ) -> ExecResult:
        """assignment: kernel -> group name.  ``inputs`` seeds the source
        blocks (host-resident, like the paper's initial data) on
        ``host_group`` (explicit, or the deterministic default)."""
        s = self.session(
            g,
            assignment,
            inputs,
            host_group=host_group,
            time_kernels=time_kernels,
            fused=fused,
            cache=cache,
        )
        s.run_all()
        return s.result()


def _bind_kernels(g, fns: Mapping) -> list[str]:
    """Attach real implementations from ``fns`` (op -> callable) to every
    kernel; returns the entry kernels (one fed by the virtual source, or
    one with no predecessors at all), which read a ``<kernel>/in`` block."""
    entries = []
    for name, k in g.nodes.items():
        if k.op == "source":
            continue
        if k.op not in fns:
            raise KeyError(
                f"kernel {name!r} has op {k.op!r} without an "
                f"implementation (have {sorted(fns)})"
            )
        k.fn = fns[k.op]
        preds = g.predecessors(name)
        if not preds or any(g.nodes[p].op == "source" for p in preds):
            entries.append(name)
    return entries


def _attach_kernels(g, n: int, fns: Mapping, dtype: str, seed: int) -> dict:
    """:func:`_bind_kernels`, and a standard normal ``<kernel>/in`` host
    input block for each entry kernel.  Returns the inputs dict for
    :meth:`JaxExecutor.run`."""
    key = jax.random.PRNGKey(seed)
    inputs = {}
    for name in _bind_kernels(g, fns):
        key, sub = jax.random.split(key)
        inputs[name + "/in"] = jax.random.normal(sub, (n, n), dtype=dtype)
    return inputs


@functools.lru_cache(maxsize=None)
def _tile_drawer(count: int, n: int, dtype: str, scale: float):
    """One jitted call that draws ``count`` normal (n, n) tiles times
    ``scale`` (its module is ``jit_draw_tiles``)."""

    def draw_tiles(key):
        keys = jax.random.split(key, count)
        return tuple(
            jax.random.normal(keys[i], (n, n), dtype) * scale for i in range(count)
        )

    return jax.jit(draw_tiles)


def attach_matrix_kernels(g, n: int, dtype="float32") -> dict:
    """The paper's MA/MM kernels (via kernels/ops.py) as real fns."""
    from ..kernels import ops

    fns = {
        "matmul": lambda *xs: ops.matmul(xs[0], xs[1] if len(xs) > 1 else xs[0]),
        "matadd": lambda *xs: ops.matadd(xs[0], xs[1] if len(xs) > 1 else xs[0]),
    }
    return _attach_kernels(g, n, fns, dtype, seed=0)


def attach_request_kernels(g, n: int, dtype="float32") -> dict:
    """Real implementations for the serving request-chain DAGs
    (:func:`repro.core.arena.make_request_stream`): ``prefill`` is the
    compute-heavy matmul, ``decode`` the bandwidth-bound matadd — mirroring
    the cost-table asymmetry the scheduler reasons about."""
    from ..kernels import ops

    fns = {
        "prefill": lambda *xs: ops.matmul(xs[0], xs[0].T if len(xs) < 2 else xs[1]),
        "decode": lambda *xs: ops.matadd(xs[0], xs[1] if len(xs) > 1 else xs[0]),
    }
    return _attach_kernels(g, n, fns, dtype, seed=1)


def attach_tile_kernels(g, n: int, dtype="float32") -> dict:
    """The tile Cholesky's kernels (:func:`repro.core.graph.tile_cholesky_dag`)
    as real fns: ``gemm`` is ``ops.gemm_nt`` (C - A B^T), ``syrk`` the same
    with B = A, ``potrf``/``trsm`` XLA's Cholesky and triangular solve, and
    ``diag_shift`` at its kernel's ``meta["shift"]``.  Each tile a task
    reads first from the source is seeded at scale 1/sqrt(N), N the matrix
    order, so that the symmetric random part's spectrum lies in about
    [-2, 2] and the shift sets the least eigenvalue.  The tiles are drawn
    in one jitted call."""
    from ..kernels import ops

    fns = {
        "diag_shift": ops.diag_shift,
        "potrf": ops.potrf,
        "trsm": ops.trsm,
        "syrk": lambda c, a: ops.gemm_nt(c, a, a),
        "gemm": ops.gemm_nt,
    }
    entries = _bind_kernels(g, fns)
    for k in g.nodes.values():
        if k.op == "diag_shift":
            k.fn = functools.partial(ops.diag_shift, shift=k.meta["shift"])
    tiles = sum(k.op == "diag_shift" for k in g.nodes.values())
    draw = _tile_drawer(len(entries), n, str(dtype), (n * tiles) ** -0.5)
    blocks = draw(jax.random.PRNGKey(2))
    return {name + "/in": b for name, b in zip(entries, blocks)}
