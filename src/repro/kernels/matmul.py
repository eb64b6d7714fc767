"""The paper's MM kernel as a Pallas TPU matmul: MXU-aligned BlockSpec
tiling with an f32 VMEM accumulator.

Grid (M/bm, N/bn, K/bk); the K axis is the innermost ("arbitrary") grid
dimension so the (bm, bn) accumulator scratch persists across K steps —
the canonical TPU blocking: A and B stream HBM->VMEM tile by tile, the MXU
consumes (bm, bk) x (bk, bn), and the output writes once.

Blocks not passed are chosen from the operand shape (``choose_blocks``):
per axis the largest multiple of 128 that divides the dimension, up to
``MAX_BLOCKS``, shrunk until the pipelined tiles fit ``VMEM_LIMIT_BYTES``,
which the kernel sets itself.  Both costs of small blocks shrink as the
blocks grow: Pallas pays a fixed cost per grid step, and each input block
is read from HBM once per output tile of its row or column.  At side 2048,
128^3 blocks make 4,096 grid steps and read the inputs 16 times;
(1024, 1024, 512) makes 16 steps and reads them twice.

``gemm_nt`` is the tile Cholesky's update C - A @ B^T on the same block
rule and VMEM limit, with a body of its own: its accumulator starts from
C, and its dot contracts the last axis of both A and B, so B^T is never
formed.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

# Device time of one 2048^2 f32 product on a TPU v5e: 131 us at these blocks,
# 133 us with bk 256 or 1024, 170-174 us with one side 512, 209 us at 512^3
# and 1,623 us at 128^3.
MAX_BLOCKS = (1024, 1024, 512)
# Above Mosaic's default scoped limit (16 MiB), which (1024, 1024, 512) f32
# overflows, and a quarter of a v5e core's 128 MiB of VMEM.
VMEM_LIMIT_BYTES = 32 * 1024 * 1024
LANE = 128


def vmem_bytes(bm: int, bn: int, bk: int, itemsize: int, reads_c: bool = False) -> int:
    """VMEM that the pipelined blocks take: A, B, the output (and C, for
    ``gemm_nt``) double-buffered, and the f32 accumulator."""
    out_blocks = 2 if reads_c else 1
    return 2 * (bm * bk + bk * bn + out_blocks * bm * bn) * itemsize + 4 * bm * bn


def _divisors(dim: int, cap: int) -> list[int]:
    """Multiples of 128 that divide ``dim`` and are at most ``cap``,
    largest first."""
    return [b for b in range(min(dim, cap) // LANE * LANE, 0, -LANE)
            if dim % b == 0]


def choose_blocks(M: int, N: int, K: int, itemsize: int,
                  reads_c: bool = False) -> tuple[int, int, int]:
    """(bm, bn, bk) for an (M, K) @ (K, N) product of 128-aligned dims
    (``reads_c``: an (M, N) C block is read too, as ``gemm_nt`` does)."""
    options = [_divisors(d, cap) for d, cap in zip((M, N, K), MAX_BLOCKS)]
    blocks = [o.pop(0) for o in options]
    while vmem_bytes(*blocks, itemsize, reads_c) > VMEM_LIMIT_BYTES:
        # the largest block that can still shrink steps down to the next
        # divisor of its dimension
        axis = max((a for a in range(3) if options[a]), key=blocks.__getitem__)
        blocks[axis] = options[axis].pop(0)
    return tuple(blocks)


def _mm_kernel(a_ref, b_ref, o_ref, acc_ref, *, k_steps):
    @pl.when(pl.program_id(2) == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    acc_ref[...] += jnp.dot(a_ref[...], b_ref[...],
                            preferred_element_type=jnp.float32)

    @pl.when(pl.program_id(2) == k_steps - 1)
    def _done():
        o_ref[...] = acc_ref[...].astype(o_ref.dtype)


# contract the last axis of A with the last axis of B: A @ B^T
_NT = (((1,), (1,)), ((), ()))


def _gemm_nt_kernel(c_ref, a_ref, b_ref, o_ref, acc_ref, *, k_steps):
    @pl.when(pl.program_id(2) == 0)
    def _init():
        acc_ref[...] = c_ref[...].astype(jnp.float32)

    acc_ref[...] -= jax.lax.dot_general(a_ref[...], b_ref[...], _NT,
                                        preferred_element_type=jnp.float32)

    @pl.when(pl.program_id(2) == k_steps - 1)
    def _done():
        o_ref[...] = acc_ref[...].astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("bm", "bn", "bk", "interpret"))
def matmul(a: jax.Array, b: jax.Array, *, bm: int | None = None,
           bn: int | None = None, bk: int | None = None,
           interpret: bool = False) -> jax.Array:
    """a: (M, K) @ b: (K, N) -> (M, N).  Dims must divide by the block
    sizes (the ops.py wrapper pads to 128); blocks not given are chosen by
    ``choose_blocks``."""
    M, K = a.shape
    K2, N = b.shape
    assert K == K2, (a.shape, b.shape)
    if None in (bm, bn, bk):
        auto = choose_blocks(M, N, K, a.dtype.itemsize)
        bm, bn, bk = (x or y for x, y in zip((bm, bn, bk), auto))
    assert M % bm == 0 and N % bn == 0 and K % bk == 0, (M, N, K, bm, bn, bk)
    k_steps = K // bk
    grid = (M // bm, N // bn, k_steps)
    return pl.pallas_call(
        functools.partial(_mm_kernel, k_steps=k_steps),
        grid=grid,
        in_specs=[
            pl.BlockSpec((bm, bk), lambda i, j, k: (i, k)),
            pl.BlockSpec((bk, bn), lambda i, j, k: (k, j)),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, k: (i, j)),
        out_shape=jax.ShapeDtypeStruct((M, N), a.dtype),
        scratch_shapes=[pltpu.VMEM((bm, bn), jnp.float32)],
        compiler_params=pltpu.CompilerParams(vmem_limit_bytes=VMEM_LIMIT_BYTES),
        interpret=interpret,
    )(a, b)


@functools.partial(jax.jit, static_argnames=("bm", "bn", "bk", "interpret"))
def gemm_nt(c: jax.Array, a: jax.Array, b: jax.Array, *, bm: int | None = None,
            bn: int | None = None, bk: int | None = None,
            interpret: bool = False) -> jax.Array:
    """c: (M, N) - a: (M, K) @ b: (N, K)^T -> (M, N), in one pass over the
    blocks of ``matmul``'s rule (C read once per output tile).  Dims must
    divide by the block sizes (the ops.py wrapper pads to 128)."""
    M, K = a.shape
    N, K2 = b.shape
    assert K == K2 and c.shape == (M, N), (c.shape, a.shape, b.shape)
    if None in (bm, bn, bk):
        auto = choose_blocks(M, N, K, a.dtype.itemsize, reads_c=True)
        bm, bn, bk = (x or y for x, y in zip((bm, bn, bk), auto))
    assert M % bm == 0 and N % bn == 0 and K % bk == 0, (M, N, K, bm, bn, bk)
    k_steps = K // bk
    return pl.pallas_call(
        functools.partial(_gemm_nt_kernel, k_steps=k_steps),
        grid=(M // bm, N // bn, k_steps),
        in_specs=[
            pl.BlockSpec((bm, bn), lambda i, j, k: (i, j)),
            pl.BlockSpec((bm, bk), lambda i, j, k: (i, k)),
            pl.BlockSpec((bn, bk), lambda i, j, k: (j, k)),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, k: (i, j)),
        out_shape=jax.ShapeDtypeStruct((M, N), c.dtype),
        scratch_shapes=[pltpu.VMEM((bm, bn), jnp.float32)],
        compiler_params=pltpu.CompilerParams(vmem_limit_bytes=VMEM_LIMIT_BYTES),
        interpret=interpret,
    )(c, a, b)
