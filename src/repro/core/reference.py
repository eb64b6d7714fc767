"""Plain reference execution of the served request DAGs.

Every kernel is evaluated once, in topological order, with jnp at
``precision=HIGHEST`` on the default device — no placement, no transfers,
no fusion, no donation.  It shares no code with the executor: what the
executed path returns for an interval must equal what this returns for the
same graph and the same seeded inputs (:func:`interval_error`).

The op semantics are those of the request chains
(:func:`repro.core.arena.make_request_stream`): ``prefill`` multiplies its
input by its own transpose (or by its second input), ``decode`` adds its
two inputs (or doubles its one input).

The tile Cholesky (:func:`repro.core.graph.tile_cholesky_dag`) has its own
evaluation, :func:`tile_values`, and :func:`cholesky_tiles` assembles the
whole matrix and its factor from the tiles, so that both can be checked
against a Cholesky factorization of the whole matrix.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import jax.scipy.linalg as jsl

from .executor import attach_request_kernels

_HIGHEST = jax.lax.Precision.HIGHEST

REQUEST_OPS = {
    "prefill": lambda xs: jnp.matmul(
        xs[0], xs[1] if len(xs) > 1 else xs[0].T, precision=_HIGHEST
    ),
    "decode": lambda xs: xs[0] + (xs[1] if len(xs) > 1 else xs[0]),
}


def reference_outputs(g, inputs) -> dict:
    """Exit block -> array.  An entry kernel reads its ``<kernel>/in`` seed
    in place of a virtual source (or as its only input); an intermediate is
    dropped as soon as its last consumer has run."""
    vals: dict = {}
    left = {n: len(g.successors(n)) for n in g.nodes}
    for n in g.topo_order():
        k = g.nodes[n]
        if k.op == "source":
            continue
        preds = g.predecessors(n)
        args = [
            inputs[f"{n}/in"] if g.nodes[p].op == "source" else vals[p]
            for p in preds
        ] or [inputs[f"{n}/in"]]
        vals[n] = REQUEST_OPS[k.op](args)
        for p in preds:
            left[p] -= 1
            if not left[p] and p in vals:
                del vals[p]
    return {n: vals[n] for n in g.exit_nodes()}


def max_rel_error(outputs: dict, expected: dict) -> float:
    """max over blocks of ``max|out - ref| / max|ref|``, computed on the
    reference's device.  The block sets must match."""
    if set(outputs) != set(expected):
        missing = sorted(set(expected) ^ set(outputs))[:4]
        raise AssertionError(f"exit blocks differ from the reference: {missing}")
    worst = 0.0
    for n, ref in expected.items():
        out = jax.device_put(outputs[n], next(iter(ref.devices())))
        err = jnp.max(jnp.abs(out - ref)) / jnp.maximum(jnp.max(jnp.abs(ref)), 1e-30)
        worst = max(worst, float(err))
    return worst


def interval_error(step, outputs: dict, side: int, dtype="float32") -> float:
    """Relative error of one executed interval's exit ``outputs`` against
    the reference evaluation of ``step``'s graph, its inputs seeded exactly
    as the serving executor seeds them."""
    g = step.graph.copy()
    inputs = attach_request_kernels(g, side, dtype)
    return max_rel_error(outputs, reference_outputs(g, inputs))


def _sym_shift(x, shift):
    low = jnp.tril(x, -1)
    return low + low.T + jnp.diag(jnp.diagonal(x) + shift)


TILE_OPS = {
    "diag_shift": lambda xs, k: _sym_shift(xs[0], k.meta["shift"]),
    "potrf": lambda xs, k: jnp.linalg.cholesky(xs[0], symmetrize_input=False),
    # L_ik = A_ik L_kk^-T, solved as L_kk L_ik^T = A_ik^T
    "trsm": lambda xs, k: jsl.solve_triangular(xs[1], xs[0].T, lower=True).T,
    "syrk": lambda xs, k: xs[0] - jnp.matmul(xs[1], xs[1].T, precision=_HIGHEST),
    "gemm": lambda xs, k: xs[0] - jnp.matmul(xs[1], xs[2].T, precision=_HIGHEST),
}


def tile_values(g, inputs) -> dict:
    """Kernel -> the tile it writes, every kernel of a tile Cholesky DAG
    evaluated once in topological order (arguments in edge order, a source
    edge reading the kernel's ``<kernel>/in``), every version kept."""
    vals: dict = {}
    with jax.default_matmul_precision("highest"):
        for n in g.topo_order():
            k = g.nodes[n]
            if k.op == "source":
                continue
            args = [
                inputs[f"{n}/in"] if g.nodes[p].op == "source" else vals[p]
                for p in g.predecessors(n)
            ]
            vals[n] = TILE_OPS[k.op](args, k)
    return vals


def cholesky_tiles(g, inputs, vals) -> tuple[jax.Array, jax.Array]:
    """-> (A, L): the whole symmetric matrix the DAG factors, from its
    ``diag_shift`` tiles and seeded tiles below the diagonal, and the factor
    assembled from the last version of every tile (``potrf``, ``trsm``)."""
    tiles = sum(k.op == "diag_shift" for k in g.nodes.values())
    n = next(iter(vals.values())).shape[0]
    A = jnp.zeros((tiles * n, tiles * n), jnp.float32)
    L = jnp.zeros_like(A)

    def put(M, tile, block):
        i, j = tile
        return M.at[i * n:(i + 1) * n, j * n:(j + 1) * n].set(block)

    for name, k in g.nodes.items():
        if k.op == "diag_shift":
            A = put(A, k.meta["tile"], vals[name])
        elif f"{name}/in" in inputs:
            i, j = k.meta["tile"]
            seeded = inputs[f"{name}/in"]
            A = put(put(A, (i, j), seeded), (j, i), seeded.T)
        if k.op in ("potrf", "trsm"):
            L = put(L, k.meta["tile"], vals[name])
    return A, L
