"""Pure-jnp oracles for the paged decode-attention kernels."""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

NEG_INF = -0.7 * float(jnp.finfo(jnp.float32).max)


def pool_view(pool: jax.Array, hs: int, hd: int) -> jax.Array:
    """Physical pool [nblk, page, W] -> the head-split-``hs`` logical view
    [nblk, page*hs, W/(hs*hd), hd] the oracles below read (view token
    t*hs + j is lane group j of physical row t)."""
    nblk, page, W = pool.shape
    return pool.reshape(nblk, page * hs, W // (hs * hd), hd)


def paged_attention_ref(q: jax.Array, k_pool: jax.Array, v_pool: jax.Array,
                        block_table: jax.Array, context_len: jax.Array, *,
                        window: Optional[int] = None,
                        softmax_scale: Optional[float] = None) -> jax.Array:
    """q [B,H,hd]; pools [nblk, page, KV, hd]; block_table [B,MB];
    context_len [B] (tokens valid, including the current one).
    Returns [B,H,hd] (q.dtype)."""
    B, H, hd = q.shape
    page, KV = k_pool.shape[1], k_pool.shape[2]
    k = k_pool[jnp.maximum(block_table, 0)]       # [B,MB,page,KV,hd]
    v = v_pool[jnp.maximum(block_table, 0)]
    MB = block_table.shape[1]
    k = k.reshape(B, MB * page, KV, hd)
    v = v.reshape(B, MB * page, KV, hd)
    rep = H // KV
    k = jnp.repeat(k, rep, axis=2)
    v = jnp.repeat(v, rep, axis=2)
    scale = softmax_scale if softmax_scale is not None else hd ** -0.5
    s = jnp.einsum("bhd,bthd->bht", q.astype(jnp.float32),
                   k.astype(jnp.float32)) * scale
    pos = jnp.arange(MB * page)[None, None, :]
    mask = pos < context_len[:, None, None]
    if window is not None:
        mask &= pos >= context_len[:, None, None] - window
    s = jnp.where(mask, s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    out = jnp.einsum("bht,bthd->bhd", p, v.astype(jnp.float32))
    return out.astype(q.dtype)


def paged_attention_with_lse_ref(q, k_pool, v_pool, block_table,
                                 context_len, *,
                                 window: Optional[int] = None,
                                 softmax_scale: Optional[float] = None):
    """Like ``paged_attention_ref`` but returns (out [B,H,hd] fp32,
    lse [B,H] fp32) for LSE-merging with other block segments (§D8).
    Grouped GQA math — never materializes repeated copies of the
    gathered context. Rows with no live keys get lse = NEG_INF, out 0."""
    B, H, hd = q.shape
    page, KV = k_pool.shape[1], k_pool.shape[2]
    k = k_pool[jnp.maximum(block_table, 0)]
    v = v_pool[jnp.maximum(block_table, 0)]
    MB = block_table.shape[1]
    k = k.reshape(B, MB * page, KV, hd)
    v = v.reshape(B, MB * page, KV, hd)
    rep = H // KV
    scale = softmax_scale if softmax_scale is not None else hd ** -0.5
    qg = q.reshape(B, KV, rep, hd)
    s = jnp.einsum("bgrd,btgd->bgrt", qg.astype(jnp.float32),
                   k.astype(jnp.float32)) * scale
    s = s.reshape(B, H, MB * page)
    pos = jnp.arange(MB * page)[None, None, :]
    mask = pos < context_len[:, None, None]
    if window is not None:
        mask &= pos >= context_len[:, None, None] - window
    s = jnp.where(mask, s, NEG_INF)
    m = jnp.max(s, axis=-1)
    p = jnp.where(mask, jnp.exp(s - m[..., None]), 0.0)
    l = jnp.sum(p, axis=-1)
    out = jnp.einsum("bgrt,btgd->bgrd", p.reshape(B, KV, rep, -1),
                     v.astype(jnp.float32)).reshape(B, H, hd)
    out = out / jnp.maximum(l[..., None], 1e-30)
    lse = jnp.where(l > 0.0, m + jnp.log(jnp.maximum(l, 1e-30)), NEG_INF)
    return out, lse


def paged_append_token_ref(pools, vals, slots):
    """Oracle for ``paged_append_token_kernel``: write each request's
    new-token row at its flat slot (negative slots park to the reserved
    scratch row). pools: tuple [nblk,page,*w]; vals: tuple [B,*w]."""
    out = []
    for pool, v in zip(pools, vals):
        nblk, page = pool.shape[0], pool.shape[1]
        flat = pool.reshape(nblk * page, *pool.shape[2:])
        safe = jnp.where(slots >= 0, slots, nblk * page - 1)
        flat = flat.at[safe].set(v.astype(pool.dtype))
        out.append(flat.reshape(pool.shape))
    return tuple(out)


def paged_append_chunk_ref(pools, vals, slots):
    """Oracle for ``paged_append_chunk_kernel``: scatter each request's
    chunk rows at their flat slots (negative slots park to the reserved
    scratch row). pools: tuple [nblk,page,*w]; vals: tuple [B,T,*w];
    slots [B,T]."""
    out = []
    flat_slots = slots.reshape(-1)
    for pool, v in zip(pools, vals):
        nblk, page = pool.shape[0], pool.shape[1]
        flat = pool.reshape(nblk * page, *pool.shape[2:])
        safe = jnp.where(flat_slots >= 0, flat_slots, nblk * page - 1)
        flat = flat.at[safe].set(
            v.reshape(-1, *v.shape[2:]).astype(pool.dtype))
        out.append(flat.reshape(pool.shape))
    return tuple(out)


def paged_mla_attention_ref(q_cat: jax.Array, pool: jax.Array,
                            block_table: jax.Array, context_len: jax.Array,
                            *, R: int, window: Optional[int] = None,
                            softmax_scale: float = 1.0) -> jax.Array:
    """Absorbed-MLA decode oracle over the compressed paged cache.

    q_cat [B,H,W] = [q_nope·W_uk ++ q_pe] (caller pre-scales);
    pool [nblk, page, W] with W = R + Rr cached [c_kv ++ k_pe] entries.
    Scores are q_cat·entry (= q_abs·c + q_pe·pe); the value read is the
    compressed context vector, so nothing of shape [B,Tk,H,·] is ever
    materialized. Returns [B,H,R] fp32 (caller up-projects with W_uv)."""
    B, H, W = q_cat.shape
    page = pool.shape[1]
    ctx = pool[jnp.maximum(block_table, 0)]        # [B,MB,page,W]
    MB = block_table.shape[1]
    ctx = ctx.reshape(B, MB * page, W)
    s = jnp.einsum("bhw,btw->bht", q_cat.astype(jnp.float32),
                   ctx.astype(jnp.float32)) * softmax_scale
    pos = jnp.arange(MB * page)[None, None, :]
    mask = pos < context_len[:, None, None]
    if window is not None:
        mask &= pos >= context_len[:, None, None] - window
    s = jnp.where(mask, s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("bht,btr->bhr", p, ctx[..., :R].astype(jnp.float32))
