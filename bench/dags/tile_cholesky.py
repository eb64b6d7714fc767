"""Tile Cholesky factorization (PLASMA's pdpotrf, lower and right-looking;
Buttari et al., Parallel Computing 35(1), 2009), as StarPU/Chameleon and
XKaapi schedule it: every DAG factors one fresh seeded matrix of
``tiles`` x ``tiles`` square tiles of the configuration's side.

The structure is a copy of the program's
``repro.core.graph.tile_cholesky_dag``: one task per tile operation, each
writing a new version of its tile and reading the newest versions of its
inputs, in order.  A tile below the diagonal that no task has written yet
is the ``<kernel>/in`` block of its first reader.  The one exit is the last
``potrf``.  Costs per processor class come from the configuration's cost
model, as the program weighs the graph for its platform.

Op semantics, as the program's ``attach_tile_kernels`` implements them and
the reference restates them: ``diag_shift`` makes the symmetric tile that
its input's lower triangle defines and adds ``shift`` to its diagonal, so
the factored matrix is sym(R) + shift * I; ``potrf`` is the lower Cholesky
factor of its input's lower triangle; ``trsm`` is A L^-T for A and lower L;
``syrk`` is C - A A^T; ``gemm`` is C - A B^T.  The updates go through the
arithmetic's ``matmul`` and ``add``, so that the controls reach them.
"""

from __future__ import annotations

import functools

import jax.numpy as jnp
import jax.scipy.linalg as jsl

from yardstick.dag import Spec, resolve

# the roofline kernel each op runs as
KERNELS = {"potrf": "potrf", "trsm": "trsm", "syrk": "gemm_nt", "gemm": "gemm_nt"}


def _diag_shift(xs, ar, shift):
    low = jnp.tril(xs[0], -1)
    return low + low.T + jnp.diag(jnp.diagonal(xs[0]) + shift)


OPS = {
    "potrf": lambda xs, ar: jnp.linalg.cholesky(xs[0], symmetrize_input=False),
    # A L^-T, solved as L X^T = A^T
    "trsm": lambda xs, ar: jsl.solve_triangular(xs[1], xs[0].T, lower=True).T,
    "syrk": lambda xs, ar: ar.add(xs[0], -ar.matmul(xs[1], xs[1].T)),
    "gemm": lambda xs, ar: ar.add(xs[0], -ar.matmul(xs[1], xs[2].T)),
}


def structure(tiles: int) -> list[tuple[str, str, tuple[int, int], int, list]]:
    """-> [(kernel, op, tile written, step k, arguments)] in the program's
    order; an argument ``None`` is a seeded tile from the source."""
    newest: dict[tuple[int, int], str] = {}
    out = []

    def task(name, op, tile, step, args):
        out.append((name, op, tile, step, args))
        newest[tile] = name

    for i in range(tiles):
        task(f"diag_shift_{i}", "diag_shift", (i, i), -1, [None])
    for k in range(tiles):
        task(f"potrf_{k}", "potrf", (k, k), k, [newest[(k, k)]])
        for i in range(k + 1, tiles):
            task(f"trsm_{i}_{k}", "trsm", (i, k), k, [newest.get((i, k)), f"potrf_{k}"])
        for i in range(k + 1, tiles):
            task(f"syrk_{i}_{k}", "syrk", (i, i), k, [newest[(i, i)], f"trsm_{i}_{k}"])
            for j in range(k + 1, i):
                task(f"gemm_{i}_{j}_{k}", "gemm", (i, j), k,
                     [newest.get((i, j)), f"trsm_{i}_{k}", f"trsm_{j}_{k}"])
    return out


class Family:
    kernels = KERNELS

    def __init__(self, config: dict, traffic: dict, platform, seed: int):
        from repro.core.arena import ArenaStep
        from repro.core.executor import attach_tile_kernels
        from repro.core.graph import SOURCE, Kernel, TaskGraph

        self.attach = attach_tile_kernels
        side, tiles, shift = config["side"], traffic["tiles"], float(traffic["shift"])
        self.scale = float(traffic["input_scale_times_sqrt_N"]) / (tiles * side) ** 0.5
        self.ops = {**OPS, "diag_shift": functools.partial(_diag_shift, shift=shift)}
        tasks = structure(tiles)
        self.spec = Spec(
            {n: op for n, op, *_ in tasks},
            {n: [n + "/in" if a is None else a for a in args] for n, _, _, _, args in tasks},
        )

        g = TaskGraph()
        g.add_kernel(Kernel(name=SOURCE, op="source", costs={}))
        for n, op, tile, step, args in tasks:
            meta = {"tile": tile, "step": step}
            if op == "diag_shift":
                meta["shift"] = shift
            g.add(n, op=op, meta=meta)
            for a in args:
                g.add_edge(SOURCE if a is None else a, n, blocks=1)
        g.validate()
        model = resolve(config["costs"]["model"])()
        g = model.weight_graph(g, {op: side for op in (*OPS, "diag_shift")})
        self.step = ArenaStep(graph=g, tag=f"tile-cholesky-{tiles}")

    def __getitem__(self, i: int):
        return self.spec, self.step
