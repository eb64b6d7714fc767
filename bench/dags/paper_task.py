"""The paper's test task (arXiv:1502.07451, Sec. IV.A): 38 kernels of one
matrix op, 75 dependencies counting the arrows from the zero-weight source
kernel, two inputs and one output each.

The structure is a copy of the program's ``repro.core.graph.generate_dag``
(its LCG and parent draws), fixed by the mix's ``structure_seed``: every
DAG of the mix is the same graph with fresh seeded inputs.  Costs per
processor class come from the configuration's cost model, as the program
weighs the graph for its platform.

Op semantics, as the program's ``attach_matrix_kernels`` implements them
and the reference restates them: ``matmul`` multiplies its first input by
its second (or by itself), ``matadd`` adds them (or doubles its one input).
A kernel fed by the source reads its ``<kernel>/in`` block there.
"""

from __future__ import annotations

from yardstick.dag import Spec, lcg, resolve

KERNELS = {"matmul": "matmul", "matadd": "matadd"}

OPS = {
    "matmul": lambda xs, ar: ar.matmul(xs[0], xs[1] if len(xs) > 1 else xs[0]),
    "matadd": lambda xs, ar: ar.add(xs[0], xs[1] if len(xs) > 1 else xs[0]),
}

SOURCE = "__source__"



def structure(n_kernels: int, fan_in: int, recency: int, seed: int):
    """-> (kernel names, [(src, dst, blocks)] in insertion order).  Each
    kernel draws ``fan_in`` distinct parents, the first among the last
    ``recency`` kernels; a draw that finds none is a block from the source."""
    rnd = lcg(seed)
    names = [f"k{i}" for i in range(n_kernels)]
    edges: list[tuple[str, str, int]] = []
    for i, nm in enumerate(names):
        parents: list[str] = []
        host_blocks = 0
        for which in range(fan_in):
            pool_lo = max(0, i - recency) if which == 0 else 0
            cand = None
            for _ in range(8):
                if i == 0:
                    break
                j = pool_lo + rnd(i - pool_lo)
                if names[j] not in parents:
                    cand = names[j]
                    break
            if cand is None:
                host_blocks += 1
                continue
            parents.append(cand)
        edges += [(p, nm, 1) for p in parents]
        if host_blocks:
            edges.append((SOURCE, nm, host_blocks))
    return names, edges



class Family:
    kernels = KERNELS
    ops = OPS

    def __init__(self, config: dict, traffic: dict, platform, seed: int):
        from repro.core.arena import ArenaStep
        from repro.core.executor import attach_matrix_kernels
        from repro.core.graph import Kernel, TaskGraph

        self.attach = attach_matrix_kernels
        side = config["side"]
        self.scale = float(traffic["input_scale_times_sqrt_side"]) / side**0.5
        op = traffic["op"]
        names, edges = structure(
            traffic["n_kernels"], traffic["fan_in"], traffic["recency"],
            traffic["structure_seed"],
        )
        args: dict[str, list[str]] = {n: [] for n in names}
        for src, dst, _ in edges:
            args[dst].append(dst + "/in" if src == SOURCE else src)
        self.spec = Spec({n: op for n in names}, args)

        g = TaskGraph()
        for nm in names:
            g.add(nm, op=op)
        g.add_kernel(Kernel(name=SOURCE, op="source", costs={}))
        for src, dst, blocks in edges:
            g.add_edge(src, dst, blocks=blocks)
        g.validate()
        model = resolve(config["costs"]["model"])()
        g = model.weight_graph(g, {op: side})
        self.step = ArenaStep(graph=g, tag=f"paper-{op}")

    def __getitem__(self, i: int):
        return self.spec, self.step
