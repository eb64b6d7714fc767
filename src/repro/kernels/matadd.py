"""The paper's MA kernel: elementwise matrix addition on the VPU.

Memory-bound by construction (3 bytes moved per FLOP·dtype) — the paper's
Fig 4 uses exactly this property.  Blocks are (8k, 128)-aligned VMEM tiles;
the kernel body is a single vectorized add.
"""

from __future__ import annotations

import functools

import jax
from jax.experimental import pallas as pl


def _add_kernel(a_ref, b_ref, o_ref):
    o_ref[...] = a_ref[...] + b_ref[...]


@functools.partial(jax.jit, static_argnames=("bm", "bn", "interpret"))
def matadd(a: jax.Array, b: jax.Array, *, bm: int = 256, bn: int = 256,
           interpret: bool = False) -> jax.Array:
    """Dims must be (8, 128) multiples (the ops.py wrapper pads): the gcd
    blocks below are then (8, 128)-aligned, as Mosaic requires."""
    assert a.shape == b.shape
    M, N = a.shape
    assert M % 8 == 0 and N % 128 == 0, (M, N)
    import math
    bm = math.gcd(M, min(bm, M))
    bn = math.gcd(N, min(bn, N))
    return pl.pallas_call(
        _add_kernel,
        grid=(M // bm, N // bn),
        in_specs=[pl.BlockSpec((bm, bn), lambda i, j: (i, j)),
                  pl.BlockSpec((bm, bn), lambda i, j: (i, j))],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j: (i, j)),
        out_shape=jax.ShapeDtypeStruct((M, N), a.dtype),
        interpret=interpret,
    )(a, b)
