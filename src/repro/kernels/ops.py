"""Dispatching wrappers: Pallas TPU kernels on TPU, interpret-mode Pallas
for kernel tests, pure-jnp oracles otherwise (this CPU container).

``KERNEL_MODE``:
  auto      — pallas on TPU backends, ref on others (default)
  pallas    — force pallas (interpret=True off-TPU; slow, tests only)
  ref       — force the jnp oracle
"""

from __future__ import annotations

import functools
import os

import jax
import jax.numpy as jnp

from . import ref as _ref
from . import matmul as _mm
from . import matadd as _ma
from . import flash_attention as _fa
from . import wkv6 as _wkv

KERNEL_MODE = os.environ.get("REPRO_KERNEL_MODE", "auto")


def _on_tpu() -> bool:
    """The default backend is a TPU.  No guard: a backend that fails to
    initialise raises here instead of silently routing to the oracle."""
    return jax.devices()[0].platform == "tpu"


def _use_pallas() -> tuple[bool, bool]:
    """-> (use_pallas, interpret)"""
    if KERNEL_MODE == "ref":
        return False, False
    if KERNEL_MODE == "pallas":
        return True, not _on_tpu()
    return _on_tpu(), False


def _pad_to(x, mult, axis):
    pad = (-x.shape[axis]) % mult
    if not pad:
        return x, 0
    widths = [(0, 0)] * x.ndim
    widths[axis] = (0, pad)
    return jnp.pad(x, widths), pad


def matmul(a, b):
    use, interp = _use_pallas()
    if not use:
        return _ref.matmul(a, b)
    a2, pm = _pad_to(a, 128, 0)
    a2, pk = _pad_to(a2, 128, 1)
    b2, _ = _pad_to(b, 128, 0)
    b2, pn = _pad_to(b2, 128, 1)
    o = _mm.matmul(a2, b2, interpret=interp)
    return o[: a.shape[0], : b.shape[1]]


def matadd(a, b):
    use, interp = _use_pallas()
    if not use:
        return _ref.matadd(a, b)
    # pad to the (8, 128) tile so the kernel always finds aligned blocks
    a2, _ = _pad_to(a, 8, 0)
    a2, _ = _pad_to(a2, 128, 1)
    b2, _ = _pad_to(b, 8, 0)
    b2, _ = _pad_to(b2, 128, 1)
    o = _ma.matadd(a2, b2, interpret=interp)
    return o[: a.shape[0], : a.shape[1]]


def gemm_nt(c, a, b):
    """c - a @ b.T (the tile Cholesky's gemm; its syrk passes b = a)."""
    use, interp = _use_pallas()
    if not use:
        return _ref.gemm_nt(c, a, b)
    c2, _ = _pad_to(c, 128, 0)
    c2, _ = _pad_to(c2, 128, 1)
    a2, _ = _pad_to(a, 128, 0)
    a2, _ = _pad_to(a2, 128, 1)
    b2, _ = _pad_to(b, 128, 0)
    b2, _ = _pad_to(b2, 128, 1)
    o = _mm.gemm_nt(c2, a2, b2, interpret=interp)
    return o[: c.shape[0], : c.shape[1]]


# The tile Cholesky's panel ops are XLA's own, on every backend; jitted
# under these names so that a trace shows the modules jit_potrf, jit_trsm
# and jit_diag_shift.

@jax.jit
def potrf(a):
    """Lower Cholesky factor of ``a``, reading its lower triangle only (as
    LAPACK's potrf); the upper triangle of the result is zero."""
    return jax.lax.linalg.cholesky(a, symmetrize_input=False)


@jax.jit
def trsm(b, l):
    """b @ inv(l).T for lower-triangular ``l``: the solve x @ l.T = b."""
    return jax.lax.linalg.triangular_solve(l, b, left_side=False, lower=True,
                                           transpose_a=True)


@functools.partial(jax.jit, static_argnames="shift")
def diag_shift(x, shift):
    """tril(x) + tril(x, -1).T + shift * I: the symmetric tile that ``x``'s
    lower triangle defines, its diagonal raised by ``shift``."""
    low = jnp.tril(x, -1)
    eye = jnp.eye(x.shape[0], dtype=x.dtype)
    return low + low.T + (jnp.diagonal(x) + shift)[:, None] * eye


def flash_attention(q, k, v, *, causal=True, kv_len=None):
    """(B, H, S, hd) layout."""
    use, interp = _use_pallas()
    if not use:
        return _ref.flash_attention(q, k, v, causal=causal, kv_len=kv_len)
    return _fa.flash_attention(q, k, v, causal=causal, kv_len=kv_len,
                               interpret=interp)


def wkv6(r, k, v, w, u):
    use, interp = _use_pallas()
    if not use:
        return _ref.wkv6(r, k, v, w, u)[0]
    return _wkv.wkv6(r, k, v, w, u, interpret=interp)


# ---------------------------------------------------------------------------
# Super-step chain builder
# ---------------------------------------------------------------------------

def build_chain(steps, keep=None):
    """Compose a group's intra-group kernel chain into ONE callable.

    ``steps`` is a sequence of ``(fn, srcs)`` in topological order, where each
    ``srcs`` entry names one positional argument of ``fn``:

    * ``("ext", i)`` — the i-th *external* input of the chain (a block that
      lives outside the group-step: a host seed or another group's output);
    * ``("mem", j)`` — the output of the j-th earlier step (an intra-group
      edge; it never touches host or comm lanes).

    ``keep`` selects which step outputs the chain returns (default: all).
    Outputs that are dead after the chain — every consumer is an earlier
    ``("mem", ...)`` reference — should be omitted: XLA then fuses straight
    through them instead of materializing one buffer per kernel, which is
    most of the super-step's dispatch-overhead win.

    The returned ``superstep(*ext) -> tuple(kept outputs)`` is pure and
    jit-friendly: the executor jits it once per (revision, group signature,
    shapes/dtypes) with dead external buffers donated, so a whole partition
    group runs as a single XLA computation — one async dispatch and one
    ready-barrier per group-step instead of one per kernel.  Its name is
    the compiled module's (``jit_superstep``), which a profiler trace shows.
    """
    plan = [(fn, tuple(srcs)) for fn, srcs in steps]
    keep = tuple(range(len(plan))) if keep is None else tuple(keep)

    def superstep(*ext):
        outs = []
        for fn, srcs in plan:
            args = [ext[i] if kind == "ext" else outs[i] for kind, i in srcs]
            outs.append(fn(*args))
        return tuple(outs[i] for i in keep)

    return superstep
