"""The plain reference and the comparison that decides ``correct``.

Every kernel of a :class:`~yardstick.dag.Spec` is evaluated once, in
dependency order, with ``jax.numpy`` on one device: no placement, no
transfers, no kernels of the program.  An intermediate block is dropped as
soon as its last consumer has run.

``HIGHEST`` is the reference: float32 at ``precision=HIGHEST``.  The
controls are arithmetics a step below what the configurations state (the
dot in one bf16 pass with float32 accumulation, blocks and the add in
float32), put in the program's place; each has to fail the comparison in
the cells it can reach.  ``CONTROL`` rounds the dot's operands to float8
e4m3 with a per-block scale and adds in bfloat16.  ``BF16_BLOCKS`` keeps
the dot as stated and stores every block in bfloat16: the dot's output is
rounded to bfloat16 and the add is done in bfloat16.  Its blocks keep the
float32 dtype, holding bfloat16 values, so the executor's buffers do not
change.
"""

from __future__ import annotations

import dataclasses
from typing import Callable, Mapping

import jax
import jax.numpy as jnp

_HIGHEST = jax.lax.Precision.HIGHEST
_E4M3_MAX = 448.0


def _fp8(x):
    scale = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / _E4M3_MAX
    return (x / scale).astype(jnp.float8_e4m3fn).astype(jnp.float32) * scale


@dataclasses.dataclass(frozen=True)
class Arithmetic:
    name: str
    matmul: Callable
    add: Callable


HIGHEST = Arithmetic(
    "highest",
    lambda a, b: jnp.matmul(a, b, precision=_HIGHEST),
    lambda a, b: a + b,
)


def _bf16_add(a, b):
    return (a.astype(jnp.bfloat16) + b.astype(jnp.bfloat16)).astype(a.dtype)


def _one_pass_bf16(a, b):
    return jnp.matmul(a.astype(jnp.bfloat16), b.astype(jnp.bfloat16),
                      preferred_element_type=jnp.float32)


CONTROL = Arithmetic(
    "fp8_dot",
    lambda a, b: jnp.matmul(_fp8(a), _fp8(b), precision=_HIGHEST),
    _bf16_add,
)
BF16_BLOCKS = Arithmetic(
    "bf16_blocks",
    lambda a, b: _one_pass_bf16(a, b).astype(jnp.bfloat16).astype(a.dtype),
    _bf16_add,
)
CONTROLS = {c.name: c for c in (CONTROL, BF16_BLOCKS)}


def evaluate(spec, inputs: Mapping, ops: Mapping[str, Callable], arith=HIGHEST) -> dict:
    """Exit kernel -> block.  ``ops[op](args, arith)`` is the family's
    definition of each op."""
    vals: dict = {}
    left: dict[str, int] = {}
    for v in spec.args.values():
        for a in v:
            left[a] = left.get(a, 0) + 1
    for n in spec.order():
        args = [inputs[a] if a.endswith("/in") else vals[a] for a in spec.args[n]]
        vals[n] = ops[spec.ops[n]](args, arith)
        for a in spec.args[n]:
            left[a] -= 1
            if not left[a]:
                vals.pop(a, None)
    return {n: vals[n] for n in spec.exits}


def compare(outputs: Mapping, expected: Mapping) -> dict:
    """``max_rel_err``: the largest over exit blocks of max|out - ref| /
    max|ref|, on the reference's device (NaN if any output is not finite);
    ``missing_blocks``: exit blocks absent from ``outputs`` or extra."""
    missing = len(set(expected) ^ set(outputs))
    errs = []
    for n, ref in expected.items():
        if n not in outputs:
            continue
        out = jax.device_put(outputs[n], next(iter(ref.devices())))
        if out.shape != ref.shape:
            errs.append(jnp.float32(jnp.nan))
            continue
        err = jnp.max(jnp.abs(out - ref)) / jnp.maximum(jnp.max(jnp.abs(ref)), 1e-30)
        errs.append(jnp.where(jnp.all(jnp.isfinite(out)), err, jnp.nan))
    worst = float(jnp.max(jnp.stack(errs))) if errs else 0.0
    return {"max_rel_err": worst, "missing_blocks": missing}
