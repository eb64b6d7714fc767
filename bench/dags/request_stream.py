"""Serving request stream: each DAG is one serving interval.

Every request is a chain of one ``prefill`` followed by ``decode_chunks``
``decode`` kernels.  Each interval retires the oldest ``churn`` share of
the active requests and admits as many new ones, whose prefills arrive at
virtual offsets inside the interval.  This is a copy of the program's
``repro.core.arena.make_request_stream`` (its LCG, churn plan and uniform
arrival stagger), so the benchmark's traffic cannot change when the
program's generator does.

Op semantics, as the program's ``attach_request_kernels`` implements them
and the reference restates them: ``prefill`` multiplies its input by that
input's transpose (or by its second input); ``decode`` adds its two inputs
(or doubles its one input).
"""

from __future__ import annotations

from yardstick.dag import Spec, lcg, resolve

# the roofline kernel each op runs as
KERNELS = {"prefill": "matmul", "decode": "matadd"}

OPS = {
    "prefill": lambda xs, ar: ar.matmul(xs[0], xs[1] if len(xs) > 1 else xs[0].T),
    "decode": lambda xs, ar: ar.add(xs[0], xs[1] if len(xs) > 1 else xs[0]),
}



def _offsets(rnd, spread_ms: float, entries: list[str]) -> dict[str, float] | None:
    """Arrival offsets of one interval's fresh requests, drawn uniformly in
    [0, spread)."""
    if spread_ms <= 0 or not entries:
        return None
    return {n: spread_ms * rnd(1000) / 1000.0 for n in entries}


def intervals(traffic: dict, seed: int):
    """Yield (spec, arrivals, tag) per interval, without end."""
    rnd = lcg(seed + 101)
    active = list(range(traffic["base_requests"]))
    next_rid = len(active)
    step = 0
    while True:
        fresh: list[int] = []
        if step > 0:
            n_churn = max(1, int(len(active) * traffic["churn"]))
            fresh = list(range(next_rid, next_rid + n_churn))
            next_rid += n_churn
            active = active[n_churn:] + fresh
        ops: dict[str, str] = {}
        args: dict[str, list[str]] = {}
        for rid in active:
            prev = f"r{rid}.prefill"
            ops[prev], args[prev] = "prefill", [prev + "/in"]
            for c in range(traffic["decode_chunks"]):
                name = f"r{rid}.dec{c}"
                ops[name], args[name] = "decode", [prev]
                prev = name
        arrivals = _offsets(rnd, traffic["arrival_spread_ms"], [f"r{rid}.prefill" for rid in fresh])
        yield Spec(ops, args), arrivals, f"step{step}:{len(active)}req"
        step += 1



def cost_tables(config: dict, platform) -> tuple[dict, dict]:
    """(prefill, decode) class -> ms, as the configuration builds them."""
    costs = config["costs"]
    if "builder" in costs:
        return resolve(costs["builder"])(platform)
    return dict(costs["prefill"]), dict(costs["decode"])


class Family:
    """The cell's DAGs, as specs for the reference and as the program's
    :class:`ArenaStep` revisions."""

    kernels = KERNELS
    ops = OPS

    def __init__(self, config: dict, traffic: dict, platform, seed: int):
        from repro.core.executor import attach_request_kernels

        self.attach = attach_request_kernels
        self.scale = float(traffic.get("input_scale", 1.0))
        self._prefill, self._decode = cost_tables(config, platform)
        self._kv = int(traffic["kv_mb"] * 2**20)
        self._gen = intervals(traffic, seed)
        self._made: list = []

    def __getitem__(self, i: int):
        """(spec, step) of the i-th interval."""
        while len(self._made) <= i:
            self._made.append(self._build(*next(self._gen)))
        return self._made[i]

    def _build(self, spec: Spec, arrivals, tag: str):
        from repro.core.arena import ArenaStep
        from repro.core.graph import TaskGraph

        g = TaskGraph()
        for n, op in spec.ops.items():
            costs = self._prefill if op == "prefill" else self._decode
            g.add(
                n,
                op=op,
                costs=dict(costs),
                out_bytes=self._kv,
                mem_bytes=self._kv,
                meta={"req": n.split(".")[0]},
            )
        for n, a in spec.args.items():
            for src in a:
                if src in spec.ops:
                    g.add_edge(src, n, nbytes=self._kv)
        g.validate()
        return spec, ArenaStep(graph=g, arrivals=arrivals, events=(), tag=tag)
