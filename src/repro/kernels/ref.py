"""Pure-jnp oracles for every Pallas kernel (the allclose targets).

Each function is the mathematical definition, written with no blocking or
VMEM concerns — tests sweep shapes/dtypes and assert the Pallas kernels
(interpret=True on CPU) match these.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp


def matmul(a: jax.Array, b: jax.Array) -> jax.Array:
    return jnp.dot(a, b, preferred_element_type=jnp.float32).astype(a.dtype)


def matadd(a: jax.Array, b: jax.Array) -> jax.Array:
    return a + b


def gemm_nt(c: jax.Array, a: jax.Array, b: jax.Array) -> jax.Array:
    return (c - jnp.dot(a, b.T, preferred_element_type=jnp.float32)).astype(c.dtype)


def flash_attention(q, k, v, *, causal: bool = True,
                    kv_len: int | None = None) -> jax.Array:
    """q: (B, H, Sq, hd); k/v: (B, H, Sk, hd) — MHA layout (GQA callers
    repeat kv heads before the kernel)."""
    B, H, Sq, hd = q.shape
    Sk = k.shape[2]
    s = jnp.einsum("bhqd,bhkd->bhqk", q.astype(jnp.float32),
                   k.astype(jnp.float32)) / math.sqrt(hd)
    if causal:
        mask = jnp.arange(Sq)[:, None] >= jnp.arange(Sk)[None, :]
        s = jnp.where(mask, s, -1e30)
    if kv_len is not None:
        s = jnp.where((jnp.arange(Sk) < kv_len)[None, None, None], s, -1e30)
    p = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("bhqk,bhkd->bhqd", p.astype(v.dtype), v).astype(q.dtype)


def wkv6(r, k, v, w, u) -> jax.Array:
    """RWKV-6 recurrence.  r/k/v/w: (B, H, S, N); u: (H, N).
    Returns (B, H, S, N) outputs and the final state (B, H, N, N)."""
    B, H, S, N = r.shape

    def step(state, t):
        rt, kt, vt, wt = r[:, :, t], k[:, :, t], v[:, :, t], w[:, :, t]
        kv = kt[..., :, None] * vt[..., None, :]          # (B,H,N,N)
        o = jnp.einsum("bhk,bhkn->bhn", rt, state + u[..., :, None] * kv)
        state = wt[..., :, None] * state + kv
        return state, o

    state = jnp.zeros((B, H, N, N), jnp.float32)
    state, os_ = jax.lax.scan(step, state,
                              jnp.arange(S))
    return jnp.moveaxis(os_, 0, 2), state
