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
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

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
