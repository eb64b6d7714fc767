"""RWKV-6 WKV recurrence as a Pallas TPU kernel (beyond-paper kernel for the
rwkv6-3b / long-context cells).

    o_t = r_t @ (S + (u * k_t) v_t^T);   S <- diag(w_t) S + k_t v_t^T

Grid (B, H): each program owns one head's full sequence; the (N, N) state
lives in VMEM scratch and the sequence streams through a ``fori_loop``.
N = 64 fits the 128-lane VPU tile at f32; r/k/v/w sequence blocks are VMEM
resident (S·N·4 B = 1 MiB at S=4096).

The time loop is inherently sequential per (batch, head) — exactly why this
is a kernel: the jnp oracle pays HBM round-trips per chunk, the kernel pays
one stream in and one out.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _wkv_kernel(r_ref, k_ref, v_ref, w_ref, u_ref, o_ref, s_ref, *, S):
    s_ref[...] = jnp.zeros_like(s_ref)
    u = u_ref[0, 0]                                       # (N,)

    def step(t, _):
        rt = r_ref[0, 0, t]                               # (N,)
        kt = k_ref[0, 0, t]
        vt = v_ref[0, 0, t]
        wt = w_ref[0, 0, t]
        kv = kt[:, None] * vt[None, :]                    # (N, N)
        o = (rt[:, None] * (s_ref[...] + u[:, None] * kv)).sum(axis=0)
        s_ref[...] = wt[:, None] * s_ref[...] + kv
        o_ref[0, 0, t] = o.astype(o_ref.dtype)
        return ()

    jax.lax.fori_loop(0, S, step, ())


@functools.partial(jax.jit, static_argnames=("interpret",))
def wkv6(r, k, v, w, u, *, interpret: bool = False):
    """r/k/v/w: (B, H, S, N) f32; u: (H, N).  Returns o: (B, H, S, N).
    (The model's chunked-scan path also returns the final state; the kernel
    recomputes it host-side when needed — decode uses the state path.)"""
    B, H, S, N = r.shape
    grid = (B, H)
    seq_spec = pl.BlockSpec((1, 1, S, N), lambda b, h: (b, h, 0, 0))
    # u as (H, 1, N): a (1, 1, N) block spans the full last two dims, which
    # Mosaic accepts for any H (a (1, N) block of (H, N) does not)
    u3 = u.reshape(H, 1, N)
    return pl.pallas_call(
        functools.partial(_wkv_kernel, S=S),
        grid=grid,
        in_specs=[seq_spec, seq_spec, seq_spec, seq_spec,
                  pl.BlockSpec((1, 1, N), lambda b, h: (h, 0, 0))],
        out_specs=seq_spec,
        out_shape=jax.ShapeDtypeStruct((B, H, S, N), r.dtype),
        scratch_shapes=[pltpu.VMEM((N, N), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel"))
        if not interpret else None,
        interpret=interpret,
    )(r, k, v, w, u3)
