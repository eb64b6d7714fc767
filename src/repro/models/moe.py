"""Mixture-of-Experts FFN: reference dense path + production expert-parallel
(EP) path built on ``shard_map`` + ``all_to_all``.

EP design (TPU adaptation — see DESIGN.md):

* experts are sharded over the "model" mesh axis (padded with never-routed
  dummy experts when ``E % tp != 0`` — granite-moe's 40 experts pad to 48;
  the router only ever emits logits for real experts);
* tokens enter sequence-sharded over "model" (sequence parallelism), each
  shard routes its local tokens, packs them into per-expert capacity buckets,
  and a single ``all_to_all`` moves buckets to their expert's owner;
* expert FFN runs locally; a second ``all_to_all`` returns results; weighted
  combine scatters back to token positions.

This is where the paper's graph-partition idea becomes a first-class feature:
:mod:`repro.core.placement` computes an expert->shard assignment minimizing
co-activation edge cut, and ``expert_perm`` applies it — co-locating experts
that fire together reduces duplicate token sends (see
``moe_dispatch_stats``).
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp
from jax import shard_map
from jax.sharding import PartitionSpec as PS

from .params import P
from .layers import Ctx
from ..parallel import sharding as shd


def padded_experts(n_experts: int, tp: int) -> int:
    return ((n_experts + tp - 1) // tp) * tp


def moe_params(cfg, tp: int = 1) -> dict:
    d, f = cfg.d_model, cfg.moe_d_ff
    e_pad = padded_experts(cfg.n_experts, tp)
    p = {
        "router": P((d, cfg.n_experts), ("embed_fsdp", None), init="small"),
        "w_gate": P((e_pad, d, f), ("experts", "embed_fsdp", "expert_mlp")),
        "w_up": P((e_pad, d, f), ("experts", "embed_fsdp", "expert_mlp")),
        "w_down": P((e_pad, f, d), ("experts", "expert_mlp", "embed_fsdp")),
    }
    if cfg.n_shared_experts:
        fs = cfg.moe_d_ff * cfg.n_shared_experts
        p["shared"] = {
            "wi_gate": P((d, fs), ("embed_fsdp", "mlp")),
            "wi_up": P((d, fs), ("embed_fsdp", "mlp")),
            "wo": P((fs, d), ("mlp", "embed_fsdp")),
        }
    return p


def _router(p, x2, cfg):
    """x2: (T, D) -> (weights (T,k), idx (T,k), aux_loss scalar)."""
    logits = jnp.einsum(
        "td,de->te", x2.astype(jnp.float32), p["router"].astype(jnp.float32)
    )
    probs = jax.nn.softmax(logits.astype(jnp.float32), axis=-1)
    w, idx = jax.lax.top_k(probs, cfg.top_k)
    w = w / jnp.maximum(w.sum(-1, keepdims=True), 1e-9)
    # load-balance auxiliary loss (Switch-style)
    me = probs.mean(axis=0)                                # (E,)
    ce = jnp.zeros_like(me).at[idx.reshape(-1)].add(
        jnp.ones((idx.size,), jnp.float32)
    ) / (x2.shape[0] * cfg.top_k)
    aux = cfg.n_experts * jnp.sum(me * ce)
    return w.astype(x2.dtype), idx, aux


def _expert_ffn(w_gate, w_up, w_down, xb, dtype):
    """xb: (E_loc, N, D) -> (E_loc, N, D)."""
    h = jnp.einsum("end,edf->enf", xb, w_gate.astype(dtype))
    u = jnp.einsum("end,edf->enf", xb, w_up.astype(dtype))
    return jnp.einsum("enf,efd->end", jax.nn.silu(h) * u, w_down.astype(dtype))


def _shared_ffn(ps, x, dtype):
    h = jax.nn.silu(x @ ps["wi_gate"].astype(dtype)) * (x @ ps["wi_up"].astype(dtype))
    return h @ ps["wo"].astype(dtype)


# ---------------------------------------------------------------------------
# reference path: compute every expert for every token (smoke-size graphs)
# ---------------------------------------------------------------------------

def moe_ref(p, x, cfg, ctx: Ctx):
    """Exact (dropless) MoE — every expert computed for every token.

    O(T·E·D·F) FLOPs, so reduced configs / tests only — EXCEPT decode
    (T = local batch, one token): there expert weights dominate the memory
    traffic, every shard reads its local experts exactly once either way, so
    this dense form is byte-optimal on TPU and doubles as the production
    decode path (experts sharded over "model", combine is one psum)."""
    B, S, D = x.shape
    x2 = x.reshape(B * S, D)
    w, idx, aux = _router(p, x2, cfg)
    e_pad = p["w_gate"].shape[0]
    all_out = _expert_ffn(
        p["w_gate"],
        p["w_up"],
        p["w_down"],
        jnp.broadcast_to(x2, (e_pad,) + x2.shape),
        x.dtype,
    )
    all_out = ctx.cs(all_out, "experts", None, None)
    onehot = jax.nn.one_hot(idx, e_pad, dtype=x.dtype)     # (T,k,E)
    out = jnp.einsum("tk,tke,etd->td", w, onehot, all_out)
    if cfg.n_shared_experts:
        out = out + _shared_ffn(p["shared"], x2, x.dtype)
    return out.reshape(B, S, D), aux


# ---------------------------------------------------------------------------
# production path: shard_map EP with capacity buckets + all_to_all
# ---------------------------------------------------------------------------

def moe_ep(
    p,
    x,
    cfg,
    ctx: Ctx,
    *,
    capacity_factor: float = 1.25,
    expert_perm: jax.Array | None = None,
):
    """x: (B, S, D) — will be resharded to (batch->dp, seq->model).

    ``expert_perm``: optional permutation mapping logical expert id ->
    physical slot (from the graph-partition placement); router indices are
    remapped so co-activated experts land on the same shard.
    """
    mesh = ctx.mesh
    assert mesh is not None, "moe_ep needs a mesh"
    tp = mesh.shape["model"]
    e_pad = p["w_gate"].shape[0]
    assert e_pad % tp == 0, (e_pad, tp)
    e_loc = e_pad // tp
    dp = shd.dp_axes(mesh)
    bspec = dp if len(dp) > 1 else (dp[0] if dp else None)
    B, S, D = x.shape
    dtype = x.dtype

    def local(x_loc, router_w, w_gate, w_up, w_down, perm):
        # x_loc: (B_l, S_l, D); experts local: (E_loc, D, F)
        Bl, Sl, _ = x_loc.shape
        T = Bl * Sl
        x2 = x_loc.reshape(T, D)
        w, idx, aux = _router({"router": router_w}, x2, cfg)
        if perm is not None:
            idx = perm[idx]                      # logical -> physical slot
        C = int(math.ceil(T * cfg.top_k / e_pad * capacity_factor))
        C = max(C, 4)
        # position of each (token, k) within its expert bucket
        flat_e = idx.reshape(-1)                              # (T*k,)
        onehot = jax.nn.one_hot(flat_e, e_pad, dtype=jnp.int32)
        pos = jnp.cumsum(onehot, axis=0) - onehot             # (T*k, E)
        pos_in_e = jnp.take_along_axis(pos, flat_e[:, None], axis=1)[:, 0]
        keep = pos_in_e < C
        slot = flat_e * C + pos_in_e                          # (T*k,)
        slot = jnp.where(keep, slot, e_pad * C)               # drop -> OOB
        # pack tokens into (E, C, D) send buckets
        tok = jnp.repeat(jnp.arange(T), cfg.top_k)
        buf = jnp.zeros((e_pad * C, D), dtype)
        buf = buf.at[slot].set(x2[tok], mode="drop")
        buf = buf.reshape(tp, e_loc * C, D)
        # all_to_all: axis0 enumerates destination shard -> source shard
        recv = jax.lax.all_to_all(
            buf, "model", split_axis=0, concat_axis=0, tiled=False
        )
        # recv: (tp_src, E_loc*C, D) -> (E_loc, tp_src*C, D)
        recv = recv.reshape(tp, e_loc, C, D).transpose(1, 0, 2, 3).reshape(
            e_loc, tp * C, D
        )
        out_e = _expert_ffn(w_gate, w_up, w_down, recv, dtype)
        # send back: inverse reshuffle
        back = out_e.reshape(e_loc, tp, C, D).transpose(1, 0, 2, 3).reshape(
            tp, e_loc * C, D
        )
        ret = jax.lax.all_to_all(
            back, "model", split_axis=0, concat_axis=0, tiled=False
        )
        ret = ret.reshape(e_pad * C, D)
        # combine: gather each (token,k) result, weight, accumulate
        gathered = jnp.where(
            keep[:, None], ret.at[slot, :].get(mode="fill", fill_value=0), 0
        ).astype(dtype)
        out = jnp.zeros((T, D), dtype).at[tok].add(gathered * w.reshape(-1)[:, None])
        # aux loss is averaged over shards
        aux = jax.lax.pmean(aux, "model")
        if dp:
            for a in dp:
                aux = jax.lax.pmean(aux, a)
        return out.reshape(Bl, Sl, D), aux

    perm_arg = expert_perm if expert_perm is not None else None
    in_specs = (
        PS(bspec, "model"),
        PS(),
        PS("model"),
        PS("model"),
        PS("model"),
        PS() if perm_arg is not None else None,
    )
    if perm_arg is None:

        def wrapped(x_loc, router_w, w_gate, w_up, w_down):
            return local(x_loc, router_w, w_gate, w_up, w_down, None)

        f = shard_map(
            wrapped,
            mesh=mesh,
            in_specs=in_specs[:5],
            out_specs=(PS(bspec, "model"), PS()),
            check_vma=False,
        )
        out, aux = f(x, p["router"], p["w_gate"], p["w_up"], p["w_down"])
    else:
        f = shard_map(
            local,
            mesh=mesh,
            in_specs=in_specs,
            out_specs=(PS(bspec, "model"), PS()),
            check_vma=False,
        )
        out, aux = f(x, p["router"], p["w_gate"], p["w_up"], p["w_down"], perm_arg)
    if cfg.n_shared_experts:
        out = out + _shared_ffn(p["shared"], x.reshape(-1, D), x.dtype).reshape(B, S, D)
    return out, aux


def moe_ep_dedup(
    p,
    x,
    cfg,
    ctx: Ctx,
    *,
    expert_perm=None,
    dest_k: float | None = None,
    capacity_factor: float = 1.25,
):
    """Deduplicated-dispatch EP: a token crosses the all_to_all ONCE PER
    DESTINATION SHARD, not once per expert — its routed local-expert ids +
    weights travel as side metadata and the weighted combine happens on the
    receiver.

    ``dest_k``: expected distinct destination shards per token, which sizes
    the per-destination capacity ``C_d = ceil(T·dest_k/tp·cf)``.  Random
    placement needs dest_k ~ E[#distinct shards] ≈ tp(1-(1-1/tp)^k); the
    graph-partition placement (core/placement.py) co-locates co-activated
    experts, pushing dest_k toward 1-2 — smaller buffers, fewer bytes on
    the wire.  This is the paper's edge-cut objective materialized as
    all-to-all traffic."""
    mesh = ctx.mesh
    assert mesh is not None
    tp = mesh.shape["model"]
    e_pad = p["w_gate"].shape[0]
    e_loc = e_pad // tp
    k = cfg.top_k
    dp = shd.dp_axes(mesh)
    bspec = dp if len(dp) > 1 else (dp[0] if dp else None)
    B, S, D = x.shape
    dtype = x.dtype
    if dest_k is None:
        dest_k = min(k, tp * (1.0 - (1.0 - 1.0 / tp) ** k))

    def local(x_loc, router_w, w_gate, w_up, w_down, perm):
        Bl, Sl, _ = x_loc.shape
        T = Bl * Sl
        x2 = x_loc.reshape(T, D)
        w, idx, aux = _router({"router": router_w}, x2, cfg)
        if perm is not None:
            idx = perm[idx]
        dest = idx // e_loc                                   # (T, k)
        local_e = idx % e_loc
        Cd = max(int(math.ceil(T * dest_k / tp * capacity_factor)), 4)
        # one-hot over destinations, deduped per token
        dest_oh = (jax.nn.one_hot(dest, tp, dtype=jnp.int32).sum(1) > 0).astype(
            jnp.int32
        )  # (T, tp)
        pos = jnp.cumsum(dest_oh, axis=0) - dest_oh           # (T, tp)
        keep = (pos < Cd) & (dest_oh > 0)
        slot = jnp.arange(tp)[None] * Cd + pos                # (T, tp)
        slot = jnp.where(keep, slot, tp * Cd)
        # payload rows + metadata (local expert ids / weights per row)
        xbuf = jnp.zeros((tp * Cd + 1, D), dtype)
        ebuf = jnp.full((tp * Cd + 1, k), -1, jnp.int32)
        wbuf = jnp.zeros((tp * Cd + 1, k), jnp.float32)
        tok_rows = jnp.broadcast_to(x2[:, None], (T, tp, D))
        xbuf = xbuf.at[slot].set(tok_rows, mode="drop")
        # expert j belongs in the row for shard dest[t, j]
        e_entry = jnp.where(
            dest[:, None, :] == jnp.arange(tp)[None, :, None], local_e[:, None, :], -1
        )  # (T, tp, k)
        w_entry = jnp.where(e_entry >= 0, w[:, None, :].astype(jnp.float32), 0.0)
        ebuf = ebuf.at[slot].set(e_entry, mode="drop")
        wbuf = wbuf.at[slot].set(w_entry, mode="drop")
        xs = xbuf[:-1].reshape(tp, Cd, D)
        es = ebuf[:-1].reshape(tp, Cd, k)
        ws = wbuf[:-1].reshape(tp, Cd, k)
        xr = jax.lax.all_to_all(xs, "model", 0, 0, tiled=False)
        er = jax.lax.all_to_all(es, "model", 0, 0, tiled=False)
        wr = jax.lax.all_to_all(ws, "model", 0, 0, tiled=False)
        rows = xr.reshape(tp * Cd, D)
        rexp = er.reshape(tp * Cd, k)
        rwgt = wr.reshape(tp * Cd, k)
        # bucket received (row, j) assignments per local expert: expected
        # assignments per dest shard = T·k (T per-source tokens x k, 1/tp
        # of which land here, from tp sources) -> per local expert T·k/e_loc
        N = tp * Cd
        Ce = max(int(math.ceil(T * k / e_pad * capacity_factor)) * tp, 4)
        flat_e = rexp.reshape(-1)                             # (N*k,)
        valid = flat_e >= 0
        oh = jax.nn.one_hot(
            jnp.where(valid, flat_e, e_loc), e_loc + 1, dtype=jnp.int32
        )[:, :e_loc]
        bpos = jnp.cumsum(oh, axis=0) - oh
        bpos_j = jnp.take_along_axis(
            bpos, jnp.clip(flat_e, 0, e_loc - 1)[:, None], axis=1
        )[:, 0]
        bkeep = valid & (bpos_j < Ce)
        bslot = jnp.where(bkeep, jnp.clip(flat_e, 0) * Ce + bpos_j, e_loc * Ce)
        rowid = jnp.repeat(jnp.arange(N), k)
        bbuf = jnp.zeros((e_loc * Ce + 1, D), dtype)
        bbuf = bbuf.at[bslot].set(rows[rowid], mode="drop")
        out_e = _expert_ffn(
            w_gate, w_up, w_down, bbuf[:-1].reshape(e_loc, Ce, D), dtype
        )
        # weighted combine back onto rows
        gathered = out_e.reshape(e_loc * Ce, D).at[bslot, :].get(
            mode="fill", fill_value=0
        )
        gathered = jnp.where(bkeep[:, None], gathered, 0).astype(jnp.float32)
        contrib = gathered * rwgt.reshape(-1)[:, None]
        row_out = jnp.zeros((N, D), jnp.float32).at[rowid].add(contrib)
        back = jax.lax.all_to_all(
            row_out.reshape(tp, Cd, D).astype(dtype), "model", 0, 0, tiled=False
        )
        ret = back.reshape(tp * Cd, D)
        # scatter rows back to tokens (sum over destination shards)
        got = jnp.where(
            keep.reshape(-1)[:, None],
            ret.at[slot.reshape(-1), :].get(mode="fill", fill_value=0),
            0,
        )
        out = got.reshape(T, tp, D).sum(axis=1).astype(dtype)
        aux = jax.lax.pmean(aux, "model")
        for a in dp:
            aux = jax.lax.pmean(aux, a)
        return out.reshape(Bl, Sl, D), aux

    if expert_perm is None:

        def wrapped(x_loc, rw, wg, wu, wd):
            return local(x_loc, rw, wg, wu, wd, None)

        f = shard_map(
            wrapped,
            mesh=mesh,
            in_specs=(PS(bspec, "model"), PS(), PS("model"), PS("model"), PS("model")),
            out_specs=(PS(bspec, "model"), PS()),
            check_vma=False,
        )
        out, aux = f(x, p["router"], p["w_gate"], p["w_up"], p["w_down"])
    else:
        f = shard_map(
            local,
            mesh=mesh,
            in_specs=(
                PS(bspec, "model"),
                PS(),
                PS("model"),
                PS("model"),
                PS("model"),
                PS(),
            ),
            out_specs=(PS(bspec, "model"), PS()),
            check_vma=False,
        )
        out, aux = f(x, p["router"], p["w_gate"], p["w_up"], p["w_down"], expert_perm)
    if cfg.n_shared_experts:
        out = out + _shared_ffn(p["shared"], x.reshape(-1, D), x.dtype).reshape(B, S, D)
    return out, aux


def moe_apply(p, x, cfg, ctx: Ctx, *, expert_perm=None):
    """Dispatch: shard_map EP for multi-token shapes on a sharded mesh;
    dense-sharded reference for decode (seq==1) and single-device runs."""
    tp = ctx.mesh.shape.get("model", 1) if ctx.mesh is not None else 1
    if tp > 1 and x.shape[1] >= tp:
        if ctx.moe_dedup:
            return moe_ep_dedup(
                p, x, cfg, ctx, expert_perm=expert_perm, dest_k=ctx.moe_dest_k
            )
        return moe_ep(p, x, cfg, ctx, expert_perm=expert_perm)
    return moe_ref(p, x, cfg, ctx)


# ---------------------------------------------------------------------------
# dispatch statistics for the placement objective (core/placement.py)
# ---------------------------------------------------------------------------

def coactivation_counts(idx: jax.Array, n_experts: int) -> jax.Array:
    """idx: (T, k) routed expert ids -> (E, E) co-activation counts.
    Edge weight (i, j) = #tokens routed to both i and j — exactly the graph
    whose partition minimizes duplicate token sends across EP shards."""
    oh = jax.nn.one_hot(idx, n_experts, dtype=jnp.float32)   # (T,k,E)
    per_tok = oh.sum(axis=1)                                 # (T,E)
    co = per_tok.T @ per_tok
    return co - jnp.diag(jnp.diag(co))


def dispatch_bytes(
    idx: jax.Array, expert_to_shard: jax.Array, d_model: int, bytes_per: int = 2
) -> jax.Array:
    """Bytes sent over the interconnect for routing table ``idx`` given an
    expert->shard placement, counting ONE send per (token, destination shard)
    (deduplicated dispatch).  The quantity the partition minimizes."""
    shards = expert_to_shard[idx]                            # (T,k)
    n_shards = int(expert_to_shard.max()) + 1
    oh = jax.nn.one_hot(shards, n_shards, dtype=jnp.float32)  # (T,k,S)
    dest_any = jnp.clip(oh.sum(axis=1), 0, 1)                # (T,S)
    return dest_any.sum() * d_model * bytes_per
