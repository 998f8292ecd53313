"""Context-parallel (striped) KV cache backends — beyond-paper extension.

The paper's adaptive block sizing (Eq. 3) pools KV capacity only while
per-device KV heads can split further; on a 16-256-wide TPU engine tile,
GQA (kv=8) saturates immediately and MLA/MQA caches never shard at all.
Striping restores Eq. 3 universally: token t lives on the TP-group rank
``t % F`` (F = tp degree), holding ALL kv heads for its tokens. Decode
attention becomes context-parallel:

  1. all-gather the (tiny) per-step queries to full heads,
  2. each device attends over ITS sequence stripe (online softmax),
  3. merge partials across stripes with one LSE-combine (pmax + 2 psums),
  4. slice back to local heads for the row-parallel output projection.

MLA uses the ABSORBED form: scores via W_uk^T q against the compressed
cache; per-head value read is the compressed context vector, up-projected
locally after the merge — so only [B,H,R] crosses the wire.

Per-token write cost: one all-gather of the new token's kv heads
([B,KV,hd], a few KB) — negligible against the HBM reads it saves.
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax

from repro.core.views import TPContext
from repro.models.cache import NEG_INF, paged_gather

# ---------------------------------------------------------------------------
# shared striped primitives
# ---------------------------------------------------------------------------


def stripe_write_slot(positions, stripe, F, block_table, page):
    """positions [B,T] absolute; returns flat local slots [B,T] (-1 if the
    token belongs to another stripe). block_table [B,MB] covers local
    blocks of `page` stripe-local tokens each."""
    mine = (positions % F) == stripe
    local = positions // F
    blk = jnp.take_along_axis(block_table, local // page, axis=1)
    slot = blk * page + local % page
    return jnp.where(mine, slot, -1)


def stripe_counts(context_len, stripe, F):
    """Number of stripe-local tokens among [0, context_len)."""
    return (context_len + F - 1 - stripe) // F


def _partial_attention(q, k, v, valid, scale):
    """q [B,H,hd]; k/v [B,Tl,KV,hd]; valid [B,Tl] -> (acc [B,H,hd] fp32
    unnormalized, l [B,H], m [B,H]). Grouped GQA, storage-dtype dots with
    f32 accumulation (no repeated/f32-materialized context copies)."""
    B, H, hd = q.shape
    KV = k.shape[2]
    rep = H // KV
    qg = q.reshape(B, KV, rep, hd).astype(k.dtype)
    s = jnp.einsum("bgrd,btgd->bgrt", qg, k,
                   preferred_element_type=jnp.float32) * scale
    s = s.reshape(B, H, -1)
    s = jnp.where(valid[:, None, :], s, NEG_INF)
    m = jnp.max(s, axis=-1)
    p = jnp.exp(s - m[..., None])
    p = jnp.where(valid[:, None, :], p, 0.0)
    l = jnp.sum(p, axis=-1)
    acc = jnp.einsum("bgrt,btgd->bgrd",
                     p.reshape(B, KV, rep, -1).astype(v.dtype), v,
                     preferred_element_type=jnp.float32)
    return acc.reshape(B, H, hd), l, m


@dataclass(frozen=True)
class StripedDecodeBackend:
    """Decode over the striped pool. State per layer: (k_pool, v_pool)
    stored [nblk, page, KV_full*hd] (GQA; read as [nblk, page, KV_full,
    hd]) or (pool,) [nblk, page, W] (MLA, via attend_mla)."""
    ctx: TPContext
    block_table: jax.Array   # [B, MB]
    context_len: jax.Array   # [B] incl. current token
    n_q_heads: int = 0       # logical head counts (set by the step builder)
    n_kv_heads: int = 0
    window: Optional[int] = None

    def _stripe(self):
        F = self.ctx.tp
        return self.ctx.stripe_index(), F

    def attend(self, state, q, k, v, *, positions, window=None):
        """q [B,1,Hl,hd]; k/v [B,1,KVl,hd] (local heads, new token)."""
        tctx = self.ctx
        cfg_window = window if window is not None else self.window
        hd = q.shape[-1]
        stored = state[0].shape
        k_pool, v_pool = (p.reshape(stored[:2] + (-1, hd)) for p in state)
        page = k_pool.shape[1]
        stripe, F = self._stripe()

        # 1. gather new-token kv to full heads; write my stripe's tokens
        KV_full = k_pool.shape[2]
        KV_l = k.shape[2]
        kf = tctx.gather_heads(k[:, 0], self.n_kv_heads, axis=1) \
            if KV_l != KV_full else k[:, 0]
        vf = tctx.gather_heads(v[:, 0], self.n_kv_heads, axis=1) \
            if KV_l != KV_full else v[:, 0]
        pos = positions[:, 0]
        slot = stripe_write_slot(pos[:, None], stripe, F,
                                 self.block_table, page)[:, 0]
        k_pool = _write_token(k_pool, kf, slot)
        v_pool = _write_token(v_pool, vf, slot)

        # 2. gather q to full logical heads (pool-dtype wire: bf16 in
        # production, §Perf C1)
        qf = tctx.gather_heads(q[:, 0].astype(k_pool.dtype),
                               self.n_q_heads, axis=1)

        # 3. local partial attention over my stripe
        kg = paged_gather(k_pool, self.block_table)   # [B, Tl, KV, hd]
        vg = paged_gather(v_pool, self.block_table)
        Tl = kg.shape[1]
        cnt = stripe_counts(self.context_len, stripe, F)
        idx = jnp.arange(Tl)[None, :]
        valid = idx < cnt[:, None]
        if cfg_window is not None:
            # absolute position of local index j is j*F + stripe
            abs_pos = idx * F + stripe
            valid &= abs_pos >= (self.context_len[:, None] - cfg_window)
        acc, l, m = _partial_attention(qf, kg, vg, valid, hd ** -0.5)

        # 4. merge across stripes; slice back to my q heads
        wire = k_pool.dtype if k_pool.dtype == jnp.bfloat16 else None
        out_full = tctx.lse_merge(acc, l, m, wire_dtype=wire)  # [B,H,hd]
        out = _take_local_heads(tctx, out_full, self.n_q_heads)
        return out[:, None].astype(q.dtype), (k_pool.reshape(stored),
                                              v_pool.reshape(stored))

    # ---- MLA absorbed path ------------------------------------------------
    def attend_mla(self, state, q_abs, q_pe, cache_entry, *, R: int,
                   n_heads: int):
        """q_abs [B,Hl,R] (W_uk^T q_nope); q_pe [B,Hl,Rr]; cache_entry
        [B, R+Rr] (new token, identical on all ranks). Returns the merged
        compressed context [B,Hl,R] (caller up-projects with local W_uv)
        and the new state."""
        tctx = self.ctx
        (pool,) = state
        page = pool.shape[1]
        stripe, F = self._stripe()
        B = q_abs.shape[0]

        pos = self.context_len - 1
        slot = stripe_write_slot(pos[:, None], stripe, F,
                                 self.block_table, page)[:, 0]
        pool = _write_token(pool, cache_entry, slot)

        # gather queries at pool dtype (bf16 in production): halves the
        # wire bytes (§Perf C1); scores still accumulate in f32
        qa = tctx.gather_heads(q_abs.astype(pool.dtype), n_heads, axis=1)
        qp = tctx.gather_heads(q_pe.astype(pool.dtype), n_heads, axis=1)

        ctx_tok = paged_gather(pool, self.block_table)   # [B,Tl,R+Rr]
        c, pe = ctx_tok[..., :R], ctx_tok[..., R:]
        Tl = ctx_tok.shape[1]
        cnt = stripe_counts(self.context_len, stripe, F)
        valid = jnp.arange(Tl)[None, :] < cnt[:, None]

        # score scale (1/sqrt(qk_head_dim)) is baked into q_abs/q_pe by
        # the caller. NOTE: qa/qp stay bf16 into the dot (accumulate f32
        # via preferred_element_type) — a convert back to f32 here lets
        # XLA's simplifier fold the bf16 wire cast away and re-widen the
        # all-gather (§Perf C1, refuted first attempt).
        s = jnp.einsum("bhr,btr->bht", qa, c.astype(qa.dtype),
                       preferred_element_type=jnp.float32) \
            + jnp.einsum("bhr,btr->bht", qp, pe.astype(qp.dtype),
                         preferred_element_type=jnp.float32)
        s = jnp.where(valid[:, None, :], s, NEG_INF)
        mx = jnp.max(s, axis=-1)
        p = jnp.exp(s - mx[..., None])
        p = jnp.where(valid[:, None, :], p, 0.0)
        l = jnp.sum(p, axis=-1)
        acc = jnp.einsum("bht,btr->bhr", p.astype(ctx_tok.dtype), c,
                         preferred_element_type=jnp.float32)  # [B,H,R]
        wire = pool.dtype if pool.dtype == jnp.bfloat16 else None
        out_full = tctx.lse_merge(acc, l, mx, wire_dtype=wire)  # [B,H,R]
        out = _take_local_heads(tctx, out_full, n_heads)
        return out, (pool,)


@dataclass(frozen=True)
class StripedPrefillBackend:
    """Fresh prefill with striped writes: in-chunk causal attention (all
    tokens are live activations) + scatter of each device's stripe."""
    ctx: TPContext
    block_table: jax.Array
    window: Optional[int] = None

    def attend(self, state, q, k, v, *, positions, window=None):
        from repro.models.cache import causal_attention
        stored = state[0].shape
        k_pool, v_pool = (p.reshape(stored[:2] + (-1, q.shape[-1]))
                          for p in state)
        page = k_pool.shape[1]
        stripe = self.ctx.stripe_index()
        F = self.ctx.tp
        KV_full = k_pool.shape[2]
        KV_l = k.shape[2]
        kf = self.ctx.gather_heads(k, KV_full, axis=2) \
            if KV_l != KV_full else k
        vf = self.ctx.gather_heads(v, KV_full, axis=2) \
            if KV_l != KV_full else v
        slots = stripe_write_slot(positions, stripe, F, self.block_table,
                                  page)
        from repro.models.cache import paged_append
        k_pool = paged_append(k_pool, kf, slots)
        v_pool = paged_append(v_pool, vf, slots)
        w = window if window is not None else self.window
        out = causal_attention(q, k, v, window=w)
        return out, (k_pool.reshape(stored), v_pool.reshape(stored))

    def append_ctx(self, state, vals, *, positions):
        """MLA prefill: write striped, return the in-line context."""
        from repro.models.cache import paged_append
        (pool,) = state
        page = pool.shape[1]
        stripe = self.ctx.stripe_index()
        slots = stripe_write_slot(positions, stripe, self.ctx.tp,
                                  self.block_table, page)
        pool = paged_append(pool, vals, slots)
        return vals, None, (pool,)


def _write_token(pool, vals, slot):
    """pool [nblk, page, ...]; vals [B, ...]; slot [B] (-1 parks)."""
    nblk, page = pool.shape[0], pool.shape[1]
    flat = pool.reshape(nblk * page, *pool.shape[2:])
    safe = jnp.where(slot >= 0, slot, nblk * page - 1)
    keep = (slot >= 0).reshape((-1,) + (1,) * (vals.ndim - 1))
    flat = flat.at[safe].set(jnp.where(keep, vals.astype(pool.dtype),
                                       flat[safe]))
    return flat.reshape(pool.shape)


def _take_local_heads(tctx: TPContext, full, n: int):
    """Slice [.., H_full, ..] back to this device's compute slice (the
    traced inverse of gather_heads)."""
    if tctx.tp == 1:
        return full
    want = tctx.compute_shards(n)
    per = full.shape[1] // want
    stored = tctx.stored_shards(n)
    if stored == 1:
        idx = (tctx.storage_major_rank() * want) // tctx.tp
    else:
        rep = tctx.tp // want
        idx = tctx.storage_rank() * (want // stored) \
            + tctx.view_rank() // rep
    return lax.dynamic_slice_in_dim(full, idx * per, per, axis=1)
