"""Shared transformer layers: norms, rotary embeddings, dense MLP, GQA and MLA
attention (train/prefill chunked flash-style; decode with either a plain pjit
path or a seq-parallel shard_map flash-decode path).

All functions are pure: ``params`` pytrees in, arrays out.  Parameter builders
return :class:`repro.models.params.P` spec trees with logical axis names.

TPU adaptation notes (see DESIGN.md):
* prefill attention is computed blockwise (two-level ``lax.scan`` with online
  softmax) so the 32k×32k score matrix never materializes — this is the jnp
  oracle of ``kernels/flash_attention.py``;
* decode attention shards the KV cache **sequence** axis over the "model" mesh
  axis (flash-decode): each shard computes a partial softmax over its slice and
  the partials are combined with ``psum`` — the TPU-native analogue of the
  paper's "place work where the data is".
"""

from __future__ import annotations

import dataclasses
import functools
import math
from typing import Any, Mapping

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as PS

from .params import P
from ..parallel import sharding as shd


# ---------------------------------------------------------------------------
# context threaded through the model
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class Ctx:
    """Execution context: sharding rules + numerics + decode strategy."""

    rules: Mapping[str, object]
    dtype: Any = jnp.bfloat16          # activation dtype
    mesh: Mesh | None = None           # needed for shard_map decode
    decode_seqpar: bool = False        # shard KV-cache seq over "model"
    remat: bool = True
    q_chunk: int = 512
    kv_chunk: int = 1024
    causal_skip: bool = False          # skip fully-masked kv blocks (beyond-paper)
    fsdp_gather: bool = False          # ZeRO-3: gather layer weights before use
    moe_dedup: bool = False            # dedup EP dispatch (one send per shard)
    moe_dest_k: float | None = None    # expected distinct dest shards/token

    def cs(self, x, *axes):
        return shd.constraint(x, axes, self.rules)

    def gather_params(self, p):
        """FSDP: force-materialize the layer's full weights (all-gather over
        the sharded d_model axis) so matmuls run local — without this XLA
        may pick partial-product all-reduces over activations instead,
        which is catastrically worse at large batch (see §Perf)."""
        if not self.fsdp_gather:
            return p
        import jax as _jax

        return _jax.tree.map(
            lambda a: shd.constraint(a, (None,) * a.ndim, self.rules), p
        )


# ---------------------------------------------------------------------------
# norms
# ---------------------------------------------------------------------------

def rmsnorm_params(d: int) -> dict:
    return {"scale": P((d,), (None,), init="ones")}


def rmsnorm(p, x, eps: float = 1e-5):
    xf = x.astype(jnp.float32)
    var = jnp.mean(xf * xf, axis=-1, keepdims=True)
    y = xf * jax.lax.rsqrt(var + eps)
    return (y * p["scale"].astype(jnp.float32)).astype(x.dtype)


def layernorm_params(d: int) -> dict:
    return {
        "scale": P((d,), (None,), init="ones"),
        "bias": P((d,), (None,), init="zeros"),
    }


def layernorm(p, x, eps: float = 1e-5):
    xf = x.astype(jnp.float32)
    mu = jnp.mean(xf, axis=-1, keepdims=True)
    var = jnp.var(xf, axis=-1, keepdims=True)
    y = (xf - mu) * jax.lax.rsqrt(var + eps)
    return (y * p["scale"].astype(jnp.float32) + p["bias"].astype(jnp.float32)).astype(
        x.dtype
    )


# ---------------------------------------------------------------------------
# rotary position embeddings
# ---------------------------------------------------------------------------

def rope_freqs(hd: int, theta: float) -> jax.Array:
    return 1.0 / (theta ** (jnp.arange(0, hd, 2, dtype=jnp.float32) / hd))


def apply_rope(x: jax.Array, positions: jax.Array, theta: float) -> jax.Array:
    """x: (..., S, H, hd) [or (..., H, hd) with scalar-per-batch positions].

    positions broadcasts against x's sequence dim: shape (S,) or (B, S).
    Rotate-half convention.
    """
    hd = x.shape[-1]
    freqs = rope_freqs(hd, theta)                       # (hd/2,)
    ang = positions[..., None].astype(jnp.float32) * freqs  # (..., S, hd/2)
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    # broadcast over the head axis, which sits between seq and hd
    cos = cos[..., None, :]
    sin = sin[..., None, :]
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], axis=-1)
    return out.astype(x.dtype)


# ---------------------------------------------------------------------------
# dense MLP (SwiGLU)
# ---------------------------------------------------------------------------

def mlp_params(d: int, d_ff: int) -> dict:
    return {
        "wi_gate": P((d, d_ff), ("embed_fsdp", "mlp")),
        "wi_up": P((d, d_ff), ("embed_fsdp", "mlp")),
        "wo": P((d_ff, d), ("mlp", "embed_fsdp")),
    }


def mlp(p, x, ctx: Ctx):
    h = jnp.einsum("bsd,df->bsf", x, p["wi_gate"].astype(x.dtype))
    u = jnp.einsum("bsd,df->bsf", x, p["wi_up"].astype(x.dtype))
    h = ctx.cs(jax.nn.silu(h) * u, "batch", "seq", "mlp")
    out = jnp.einsum("bsf,fd->bsd", h, p["wo"].astype(x.dtype))
    return ctx.cs(out, "batch", "seq", "embed")


# ---------------------------------------------------------------------------
# blockwise (flash-style) attention — the jnp oracle
# ---------------------------------------------------------------------------

NEG_INF = -1e30


# "fusedkernel_" jit regions: these are the exact regions
# kernels/flash_attention.py implements as Pallas TPU kernels (scores stay in
# VMEM).  The roofline memory model (launch/flops.py) recognizes the prefix
# and accounts only the region's inputs+outputs as HBM traffic.

@functools.partial(
    jax.jit, static_argnames=("causal", "scale", "Cq", "Ck", "logit_cap", "kv_len")
)
def fusedkernel_flash_fwd(
    q, k, v, q_offset, *, causal, scale, Cq, Ck, logit_cap, kv_len=None
):
    return _flash_fwd_inner(
        q,
        k,
        v,
        causal=causal,
        q_offset=q_offset,
        scale=scale,
        Cq=Cq,
        Ck=Ck,
        logit_cap=logit_cap,
        kv_len=kv_len,
    )


def _flash_fwd_inner(
    q, k, v, *, causal, q_offset, scale, Cq, Ck, logit_cap, kv_len=None
):
    """Forward pass; also returns the log-sum-exp rows for the backward.
    q: (B, Sq, K, G, hd); k/v: (B, Sk, K, hd)."""
    B, Sq, K, G, hd = q.shape
    Sk = k.shape[1]
    nq, nk = Sq // Cq, Sk // Ck
    qc = jnp.moveaxis(q.reshape(B, nq, Cq, K, G, hd), 1, 0)
    kc = jnp.moveaxis(k.reshape(B, nk, Ck, K, hd), 1, 0)
    vc = jnp.moveaxis(v.reshape(B, nk, Ck, K, hd), 1, 0)

    def q_block(_, qi_and_q):
        qi, qblk = qi_and_q                       # (B, Cq, K, G, hd)

        def kv_block(state, ki_and_kv):
            m, l, acc = state
            ki, kblk, vblk = ki_and_kv
            s = jnp.einsum(
                "bqkgh,bckh->bkgqc", qblk, kblk, preferred_element_type=jnp.float32
            ) * scale
            if logit_cap > 0.0:
                s = logit_cap * jnp.tanh(s / logit_cap)
            kpos = ki * Ck + jnp.arange(Ck)
            if causal:
                qpos = q_offset + qi * Cq + jnp.arange(Cq)
                mask = qpos[:, None] >= kpos[None, :]
                if kv_len is not None:
                    mask = mask & (kpos < kv_len)[None, :]
                s = jnp.where(mask[None, None, None], s, NEG_INF)
            elif kv_len is not None:
                s = jnp.where((kpos < kv_len)[None, None, None, None], s, NEG_INF)
            m_new = jnp.maximum(m, s.max(axis=-1))
            pexp = jnp.exp(s - m_new[..., None])
            corr = jnp.exp(m - m_new)
            l_new = l * corr + pexp.sum(axis=-1)
            acc_new = acc * corr[..., None] + jnp.einsum(
                "bkgqc,bckh->bkgqh",
                pexp.astype(vblk.dtype),
                vblk,
                preferred_element_type=jnp.float32,
            )
            return (m_new, l_new, acc_new), None

        m0 = jnp.full((B, K, G, Cq), NEG_INF, jnp.float32)
        l0 = jnp.zeros((B, K, G, Cq), jnp.float32)
        a0 = jnp.zeros((B, K, G, Cq, hd), jnp.float32)
        ks = (jnp.arange(nk), kc, vc)
        (m, l, acc), _ = jax.lax.scan(kv_block, (m0, l0, a0), ks)
        out = acc / jnp.maximum(l, 1e-30)[..., None]      # (B,K,G,Cq,hd)
        lse = m + jnp.log(jnp.maximum(l, 1e-30))          # (B,K,G,Cq)
        return None, (jnp.moveaxis(out, 3, 1), lse)

    _, (blocks, lses) = jax.lax.scan(q_block, None, (jnp.arange(nq), qc))
    out = jnp.moveaxis(blocks, 0, 1).reshape(B, Sq, K, G, hd)
    # lses: (nq, B, K, G, Cq) -> (B, K, G, Sq)
    lse = jnp.moveaxis(lses, 0, 3).reshape(B, K, G, Sq)
    return out.astype(q.dtype), lse


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7, 8, 9))
def _flash_attend_core(
    q, k, v, causal, q_offset, scale, Cq, Ck, logit_cap, kv_len=None
):
    out, _ = fusedkernel_flash_fwd(
        q,
        k,
        v,
        q_offset,
        causal=causal,
        scale=scale,
        Cq=Cq,
        Ck=Ck,
        logit_cap=logit_cap,
        kv_len=kv_len,
    )
    return out


def _flash_fwd(q, k, v, causal, q_offset, scale, Cq, Ck, logit_cap, kv_len=None):
    out, lse = fusedkernel_flash_fwd(
        q,
        k,
        v,
        q_offset,
        causal=causal,
        scale=scale,
        Cq=Cq,
        Ck=Ck,
        logit_cap=logit_cap,
        kv_len=kv_len,
    )
    return out, (q, k, v, out, lse)


@functools.partial(
    jax.jit, static_argnames=("causal", "scale", "Cq", "Ck", "logit_cap", "kv_len")
)
def fusedkernel_flash_bwd(
    q, k, v, out, lse, do, q_offset, *, causal, scale, Cq, Ck, logit_cap, kv_len=None
):
    """FlashAttention-2-style backward in two linear-memory passes: P is
    recomputed per block from the saved LSE; dq accumulates in the q-pass,
    dk/dv in the kv-pass.  Residuals stay O(B·S·H·hd), never O(S^2)."""
    B, Sq, K, G, hd = q.shape
    Sk = k.shape[1]
    nq, nk = Sq // Cq, Sk // Ck
    delta = jnp.einsum(
        "bqkgh,bqkgh->bkgq", do.astype(jnp.float32), out.astype(jnp.float32)
    )  # rowsum(dO*O)
    qc = jnp.moveaxis(q.reshape(B, nq, Cq, K, G, hd), 1, 0)
    doc = jnp.moveaxis(do.reshape(B, nq, Cq, K, G, hd), 1, 0)
    lsec = jnp.moveaxis(lse.reshape(B, K, G, nq, Cq), 3, 0)
    dltc = jnp.moveaxis(delta.reshape(B, K, G, nq, Cq), 3, 0)
    kc = jnp.moveaxis(k.reshape(B, nk, Ck, K, hd), 1, 0)
    vc = jnp.moveaxis(v.reshape(B, nk, Ck, K, hd), 1, 0)

    def _scores(qi, qblk, ki, kblk, lseblk):
        s = jnp.einsum(
            "bqkgh,bckh->bkgqc", qblk, kblk, preferred_element_type=jnp.float32
        ) * scale
        if logit_cap > 0.0:
            s = logit_cap * jnp.tanh(s / logit_cap)
        kpos = ki * Ck + jnp.arange(Ck)
        if causal:
            qpos = q_offset + qi * Cq + jnp.arange(Cq)
            mask = qpos[:, None] >= kpos[None, :]
            if kv_len is not None:
                mask = mask & (kpos < kv_len)[None, :]
            s = jnp.where(mask[None, None, None], s, NEG_INF)
        elif kv_len is not None:
            s = jnp.where((kpos < kv_len)[None, None, None, None], s, NEG_INF)
        return jnp.exp(s - lseblk[..., None])            # (B,K,G,Cq,Ck)

    # pass 1: dq, scanning q blocks (inner accumulate over kv blocks)
    def q_pass(_, qs):
        qi, qblk, doblk, lseblk, dltblk = qs

        def inner(dq, ks):
            ki, kblk, vblk = ks
            p = _scores(qi, qblk, ki, kblk, lseblk)
            dp = jnp.einsum(
                "bqkgh,bckh->bkgqc", doblk, vblk, preferred_element_type=jnp.float32
            )
            ds = p * (dp - dltblk[..., None]) * scale
            dq = dq + jnp.einsum(
                "bkgqc,bckh->bqkgh",
                ds.astype(kblk.dtype),
                kblk,
                preferred_element_type=jnp.float32,
            )
            return dq, None

        dq0 = jnp.zeros((B, Cq, K, G, hd), jnp.float32)
        dq, _ = jax.lax.scan(inner, dq0, (jnp.arange(nk), kc, vc))
        return None, dq

    _, dq_blocks = jax.lax.scan(q_pass, None, (jnp.arange(nq), qc, doc, lsec, dltc))
    dq = jnp.moveaxis(dq_blocks, 0, 1).reshape(B, Sq, K, G, hd).astype(q.dtype)

    # pass 2: dk/dv, scanning kv blocks (inner accumulate over q blocks)
    def kv_pass(_, ks):
        ki, kblk, vblk = ks

        def inner(carry, qs):
            dk, dv = carry
            qi, qblk, doblk, lseblk, dltblk = qs
            p = _scores(qi, qblk, ki, kblk, lseblk)
            dp = jnp.einsum(
                "bqkgh,bckh->bkgqc", doblk, vblk, preferred_element_type=jnp.float32
            )
            ds = p * (dp - dltblk[..., None]) * scale
            dk = dk + jnp.einsum(
                "bkgqc,bqkgh->bckh",
                ds.astype(qblk.dtype),
                qblk,
                preferred_element_type=jnp.float32,
            )
            dv = dv + jnp.einsum(
                "bkgqc,bqkgh->bckh",
                p.astype(doblk.dtype),
                doblk,
                preferred_element_type=jnp.float32,
            )
            return (dk, dv), None

        dk0 = jnp.zeros((B, Ck, K, hd), jnp.float32)
        dv0 = jnp.zeros((B, Ck, K, hd), jnp.float32)
        (dk, dv), _ = jax.lax.scan(
            inner, (dk0, dv0), (jnp.arange(nq), qc, doc, lsec, dltc)
        )
        return None, (dk, dv)

    _, (dkc2, dvc2) = jax.lax.scan(kv_pass, None, (jnp.arange(nk), kc, vc))
    dk = jnp.moveaxis(dkc2, 0, 1).reshape(B, Sk, K, hd).astype(k.dtype)
    dv = jnp.moveaxis(dvc2, 0, 1).reshape(B, Sk, K, hd).astype(v.dtype)
    return dq, dk, dv


def _flash_bwd(causal, q_offset, scale, Cq, Ck, logit_cap, kv_len, res, do):
    q, k, v, out, lse = res
    return fusedkernel_flash_bwd(
        q,
        k,
        v,
        out,
        lse,
        do,
        q_offset,
        causal=causal,
        scale=scale,
        Cq=Cq,
        Ck=Ck,
        logit_cap=logit_cap,
        kv_len=kv_len,
    )


_flash_attend_core.defvjp(_flash_fwd, _flash_bwd)


def _flash_attend(q, k, v, *, causal: bool, q_offset, ctx: Ctx, logit_cap: float = 0.0):
    """Blockwise attention with online softmax and an FA2 custom backward.

    q: (B, Sq, K, G, hd) grouped query heads; k, v: (B, Sk, K, hd).
    ``q_offset``: absolute position of q[0] (for causal masking with a cache).
    Returns (B, Sq, K, G, hd).
    """
    B, Sq, K, G, hd = q.shape
    Sk = k.shape[1]
    scale = 1.0 / math.sqrt(hd)
    Cq = min(ctx.q_chunk, Sq)
    Ck = min(ctx.kv_chunk, Sk)
    pad_q = (-Sq) % Cq
    pad_k = (-Sk) % Ck
    kv_len = Sk if pad_k else None
    if pad_q:
        q = jnp.pad(q, ((0, 0), (0, pad_q), (0, 0), (0, 0), (0, 0)))
    if pad_k:
        k = jnp.pad(k, ((0, 0), (0, pad_k), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pad_k), (0, 0), (0, 0)))
    out = _flash_attend_core(
        q, k, v, causal, q_offset, scale, Cq, Ck, logit_cap, kv_len
    )
    return out[:, :Sq] if pad_q else out


def attention(q, k, v, *, causal: bool, ctx: Ctx, q_offset=0, logit_cap: float = 0.0):
    """q: (B, Sq, H, hd); k, v: (B, Sk, K, hd) with H = K * G."""
    B, Sq, H, hd = q.shape
    K = k.shape[2]
    G = H // K
    qg = q.reshape(B, Sq, K, G, hd)
    if Sq <= ctx.q_chunk and k.shape[1] <= 4 * ctx.kv_chunk:
        # small path: single einsum (cheaper to compile; smoke tests, short
        # cross-attention) — the flash path bounds score memory otherwise
        s = jnp.einsum(
            "bqkgh,bckh->bkgqc", qg, k, preferred_element_type=jnp.float32
        ) / math.sqrt(hd)
        if logit_cap > 0.0:
            s = logit_cap * jnp.tanh(s / logit_cap)
        if causal:
            qpos = q_offset + jnp.arange(Sq)
            kpos = jnp.arange(k.shape[1])
            s = jnp.where(
                (qpos[:, None] >= kpos[None, :])[None, None, None], s, NEG_INF
            )
        p = jax.nn.softmax(s, axis=-1).astype(v.dtype)
        out = jnp.einsum("bkgqc,bckh->bqkgh", p, v)
        return out.reshape(B, Sq, H, hd).astype(q.dtype)
    out = _flash_attend(
        qg, k, v, causal=causal, q_offset=q_offset, ctx=ctx, logit_cap=logit_cap
    )
    return out.reshape(B, Sq, H, hd)


# ---------------------------------------------------------------------------
# GQA attention block (attn mixer)
# ---------------------------------------------------------------------------

def attn_params(cfg) -> dict:
    d, H, K, hd = cfg.d_model, cfg.n_heads, cfg.n_kv_heads, cfg.hd
    p = {
        "wq": P((d, H, hd), ("embed_fsdp", "heads", "head_dim")),
        "wk": P((d, K, hd), ("embed_fsdp", "kv_heads", "head_dim")),
        "wv": P((d, K, hd), ("embed_fsdp", "kv_heads", "head_dim")),
        "wo": P((H, hd, d), ("heads", "head_dim", "embed_fsdp")),
    }
    if cfg.qkv_bias:
        p["bq"] = P((H, hd), ("heads", "head_dim"), init="zeros")
        p["bk"] = P((K, hd), ("kv_heads", "head_dim"), init="zeros")
        p["bv"] = P((K, hd), ("kv_heads", "head_dim"), init="zeros")
    return p


def _qkv(p, x, cfg, ctx: Ctx):
    q = jnp.einsum("bsd,dhk->bshk", x, p["wq"].astype(x.dtype))
    k = jnp.einsum("bsd,dhk->bshk", x, p["wk"].astype(x.dtype))
    v = jnp.einsum("bsd,dhk->bshk", x, p["wv"].astype(x.dtype))
    if cfg.qkv_bias:
        q = q + p["bq"].astype(x.dtype)
        k = k + p["bk"].astype(x.dtype)
        v = v + p["bv"].astype(x.dtype)
    return q, k, v


def attn_block(p, x, cfg, ctx: Ctx, *, positions, kv=None, causal=True):
    """Full-sequence attention (train / prefill).

    positions: (S,) or (B, S) absolute positions for rope.
    kv: optional (k, v) override for cross-attention.
    Returns (out, (k, v)) — the cache-ready keys/values.
    """
    q, k, v = _qkv(p, x, cfg, ctx)
    if kv is not None:
        k, v = kv
        q = apply_rope(q, positions, cfg.rope_theta)
    else:
        q = apply_rope(q, positions, cfg.rope_theta)
        k = apply_rope(k, positions, cfg.rope_theta)
    q = ctx.cs(q, "batch", "seq", "heads", "head_dim")
    k = ctx.cs(k, "batch", "seq", "kv_heads", "head_dim")
    v = ctx.cs(v, "batch", "seq", "kv_heads", "head_dim")
    o = attention(q, k, v, causal=causal, ctx=ctx, logit_cap=cfg.attn_logit_softcap)
    out = jnp.einsum("bshk,hkd->bsd", o, p["wo"].astype(x.dtype))
    return ctx.cs(out, "batch", "seq", "embed"), (k, v)


# ---------------------------------------------------------------------------
# decode attention (one new token against a cache)
# ---------------------------------------------------------------------------

def decode_attn_dense(q, ck, cv, k_new, v_new, pos, *, logit_cap=0.0):
    """Plain path: cache replicated/unsharded-seq.  q: (B,H,hd); caches
    (B,S,K,hd); pos: scalar int32 — write position of the new token."""
    B, S, K, hd = ck.shape
    H = q.shape[1]
    G = H // K
    ck = jax.lax.dynamic_update_slice(
        ck, k_new[:, None].astype(ck.dtype), (0, pos, 0, 0)
    )
    cv = jax.lax.dynamic_update_slice(
        cv, v_new[:, None].astype(cv.dtype), (0, pos, 0, 0)
    )
    qg = q.reshape(B, K, G, hd)
    s = jnp.einsum(
        "bkgh,bskh->bkgs", qg, ck, preferred_element_type=jnp.float32
    ) / math.sqrt(hd)
    if logit_cap > 0.0:
        s = logit_cap * jnp.tanh(s / logit_cap)
    valid = jnp.arange(S) <= pos
    s = jnp.where(valid[None, None, None], s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1).astype(cv.dtype)
    o = jnp.einsum("bkgs,bskh->bkgh", p, cv)
    return o.reshape(B, H, hd).astype(q.dtype), (ck, cv)


def decode_attn_seqpar(q, ck, cv, k_new, v_new, pos, *, ctx: Ctx, logit_cap=0.0):
    """Flash-decode: cache seq axis sharded over "model"; partial softmax per
    shard + psum combine.  The TPU-native adaptation of the paper's
    data-locality principle: compute moves to the cache shard, only the
    O(B·H·hd) partials cross the interconnect instead of the O(B·S·K·hd) cache.
    """
    mesh = ctx.mesh
    assert mesh is not None
    B, S, K, hd = ck.shape
    H = q.shape[1]
    G = H // K
    tp = mesh.shape["model"]
    S_loc = S // tp
    # batch sharding only where it divides (long_500k decodes at B=1:
    # batch replicates over dp, the cache still seq-shards over "model")
    dp = []
    rem = B
    for a in shd.dp_axes(mesh):
        n = mesh.shape[a]
        if rem % n == 0:
            dp.append(a)
            rem //= n
    bspec = tuple(dp) if len(dp) > 1 else (dp[0] if dp else None)

    def local(q, ck, cv, k_new, v_new, pos):
        # shapes: q (B_l, H, hd); ck/cv (B_l, S_loc, K, hd)
        idx = jax.lax.axis_index("model")
        off = idx * S_loc
        lpos = pos - off
        in_range = jnp.logical_and(lpos >= 0, lpos < S_loc)
        li = jnp.clip(lpos, 0, S_loc - 1)
        ck_upd = jax.lax.dynamic_update_slice(
            ck, k_new[:, None].astype(ck.dtype), (0, li, 0, 0)
        )
        cv_upd = jax.lax.dynamic_update_slice(
            cv, v_new[:, None].astype(cv.dtype), (0, li, 0, 0)
        )
        ck = jnp.where(in_range, ck_upd, ck)
        cv = jnp.where(in_range, cv_upd, cv)
        qg = q.reshape(-1, K, G, hd)
        s = jnp.einsum(
            "bkgh,bskh->bkgs", qg, ck, preferred_element_type=jnp.float32
        ) / math.sqrt(hd)
        if logit_cap > 0.0:
            s = logit_cap * jnp.tanh(s / logit_cap)
        valid = (off + jnp.arange(S_loc)) <= pos
        s = jnp.where(valid[None, None, None], s, NEG_INF)
        m_l = s.max(axis=-1)
        m_g = jax.lax.pmax(m_l, "model")
        pexp = jnp.exp(s - m_g[..., None])
        l_l = pexp.sum(axis=-1)
        o_l = jnp.einsum(
            "bkgs,bskh->bkgh",
            pexp.astype(cv.dtype),
            cv,
            preferred_element_type=jnp.float32,
        )
        l_g = jax.lax.psum(l_l, "model")
        o_g = jax.lax.psum(o_l, "model")
        o = o_g / jnp.maximum(l_g, 1e-30)[..., None]
        return o.reshape(-1, H, hd).astype(q.dtype), ck, cv

    from jax import shard_map

    f = shard_map(
        local,
        mesh=mesh,
        in_specs=(
            PS(bspec),
            PS(bspec, "model"),
            PS(bspec, "model"),
            PS(bspec),
            PS(bspec),
            PS(),
        ),
        out_specs=(PS(bspec), PS(bspec, "model"), PS(bspec, "model")),
        check_vma=False,
    )
    o, ck, cv = f(q, ck, cv, k_new, v_new, pos)
    return o, (ck, cv)


def attn_decode_block(p, x, cfg, ctx: Ctx, *, cache, pos):
    """x: (B, 1, d).  cache: {"k": (B,S,K,hd), "v": ...}.  Returns
    (out (B,1,d), new_cache)."""
    B = x.shape[0]
    q, k, v = _qkv(p, x, cfg, ctx)              # (B,1,H,hd)/(B,1,K,hd)
    posv = jnp.full((B, 1), pos, jnp.int32)
    q = apply_rope(q, posv, cfg.rope_theta)[:, 0]
    k = apply_rope(k, posv, cfg.rope_theta)[:, 0]
    v = v[:, 0]
    if (
        ctx.decode_seqpar
        and ctx.mesh is not None
        and ctx.mesh.shape.get("model", 1) > 1
    ):
        o, (ck, cv) = decode_attn_seqpar(
            q,
            cache["k"],
            cache["v"],
            k,
            v,
            pos,
            ctx=ctx,
            logit_cap=cfg.attn_logit_softcap,
        )
    else:
        o, (ck, cv) = decode_attn_dense(
            q, cache["k"], cache["v"], k, v, pos, logit_cap=cfg.attn_logit_softcap
        )
    out = jnp.einsum("bhk,hkd->bd", o, p["wo"].astype(x.dtype))[:, None]
    return ctx.cs(out, "batch", "seq", "embed"), {"k": ck, "v": cv}


# ---------------------------------------------------------------------------
# MLA (multi-head latent attention, MiniCPM3 / DeepSeek-V2 style)
# ---------------------------------------------------------------------------

def mla_params(cfg) -> dict:
    d, H = cfg.d_model, cfg.n_heads
    r_kv, r_q = cfg.kv_lora_rank, cfg.q_lora_rank
    dn, dr, dv = cfg.qk_nope_dim, cfg.qk_rope_dim, cfg.v_head_dim
    return {
        "wq_a": P((d, r_q), ("embed_fsdp", "q_lora")),
        "q_norm": rmsnorm_params(r_q),
        "wq_b": P((r_q, H, dn + dr), ("q_lora", "heads", "head_dim")),
        "wkv_a": P((d, r_kv + dr), ("embed_fsdp", "kv_lora")),
        "kv_norm": rmsnorm_params(r_kv),
        "wk_b": P((r_kv, H, dn), ("kv_lora", "heads", "head_dim")),
        "wv_b": P((r_kv, H, dv), ("kv_lora", "heads", "head_dim")),
        "wo": P((H, dv, d), ("heads", "head_dim", "embed_fsdp")),
    }


def _mla_q(p, x, cfg, ctx: Ctx, positions):
    dn, dr = cfg.qk_nope_dim, cfg.qk_rope_dim
    ql = rmsnorm(
        p["q_norm"],
        jnp.einsum("bsd,dr->bsr", x, p["wq_a"].astype(x.dtype)),
        cfg.norm_eps,
    )
    q = jnp.einsum("bsr,rhk->bshk", ql, p["wq_b"].astype(x.dtype))
    q_nope, q_rope = q[..., :dn], q[..., dn:]
    q_rope = apply_rope(q_rope, positions, cfg.rope_theta)
    return q_nope, q_rope


def _mla_latent(p, x, cfg, ctx: Ctx, positions):
    r_kv, dr = cfg.kv_lora_rank, cfg.qk_rope_dim
    kv = jnp.einsum("bsd,dr->bsr", x, p["wkv_a"].astype(x.dtype))
    latent, k_rope = kv[..., :r_kv], kv[..., r_kv:]
    latent = rmsnorm(p["kv_norm"], latent, cfg.norm_eps)
    # k_rope is a single shared rope head: (B, S, dr) -> (B, S, 1, dr)
    k_rope = apply_rope(k_rope[..., None, :], positions, cfg.rope_theta)[..., 0, :]
    return latent, k_rope


def mla_block(p, x, cfg, ctx: Ctx, *, positions):
    """Prefill/train MLA: expand K/V from the latent, blockwise attention.
    Returns (out, (latent, k_rope)) for caching."""
    B, S, d = x.shape
    H, dn, dr, dv = cfg.n_heads, cfg.qk_nope_dim, cfg.qk_rope_dim, cfg.v_head_dim
    q_nope, q_rope = _mla_q(p, x, cfg, ctx, positions)
    latent, k_rope = _mla_latent(p, x, cfg, ctx, positions)
    k_nope = jnp.einsum("bsr,rhk->bshk", latent, p["wk_b"].astype(x.dtype))
    v = jnp.einsum("bsr,rhk->bshk", latent, p["wv_b"].astype(x.dtype))
    q = jnp.concatenate([q_nope, q_rope], axis=-1)
    k = jnp.concatenate(
        [k_nope, jnp.broadcast_to(k_rope[:, :, None], (B, S, H, dr))], axis=-1
    )
    # pad v's head_dim up to qk dim for the shared attention routine, then cut
    o = attention(
        q,
        k,
        jnp.pad(v, ((0, 0), (0, 0), (0, 0), (0, dn + dr - dv))),
        causal=True,
        ctx=ctx,
    )[..., :dv]
    out = jnp.einsum("bshk,hkd->bsd", o, p["wo"].astype(x.dtype))
    return ctx.cs(out, "batch", "seq", "embed"), (latent, k_rope)


def mla_decode_block(p, x, cfg, ctx: Ctx, *, cache, pos):
    """Absorbed-weight MLA decode: score in latent space against the compact
    latent cache — cache reads are O(r_kv + dr) per token, not O(H·hd).
    cache: {"latent": (B,S,r_kv), "k_rope": (B,S,dr)}."""
    B = x.shape[0]
    H, dn, dr, dv = cfg.n_heads, cfg.qk_nope_dim, cfg.qk_rope_dim, cfg.v_head_dim
    posv = jnp.full((B, 1), pos, jnp.int32)
    q_nope, q_rope = _mla_q(p, x, cfg, ctx, posv)        # (B,1,H,·)
    latent_new, k_rope_new = _mla_latent(p, x, cfg, ctx, posv)
    cl = jax.lax.dynamic_update_slice(
        cache["latent"], latent_new.astype(cache["latent"].dtype), (0, pos, 0)
    )
    cr = jax.lax.dynamic_update_slice(
        cache["k_rope"], k_rope_new.astype(cache["k_rope"].dtype), (0, pos, 0)
    )
    S = cl.shape[1]
    # absorb wk_b into the query:  q_lat (B,H,r_kv)
    q_lat = jnp.einsum("bhk,rhk->bhr", q_nope[:, 0], p["wk_b"].astype(x.dtype))
    scale = 1.0 / math.sqrt(dn + dr)
    s = (
        jnp.einsum("bhr,bsr->bhs", q_lat, cl, preferred_element_type=jnp.float32)
        + jnp.einsum(
            "bhk,bsk->bhs", q_rope[:, 0], cr, preferred_element_type=jnp.float32
        )
    ) * scale
    valid = jnp.arange(S) <= pos
    s = jnp.where(valid[None, None], s, NEG_INF)
    w = jax.nn.softmax(s, axis=-1).astype(x.dtype)
    o_lat = jnp.einsum("bhs,bsr->bhr", w, cl)             # (B,H,r_kv)
    o = jnp.einsum("bhr,rhk->bhk", o_lat, p["wv_b"].astype(x.dtype))
    out = jnp.einsum("bhk,hkd->bd", o, p["wo"].astype(x.dtype))[:, None]
    return ctx.cs(out, "batch", "seq", "embed"), {"latent": cl, "k_rope": cr}
