"""Attention cache backends.

The model code is cache-agnostic: each layer calls ``backend.attend(...)``
(or the raw ``append/gather`` primitives for MLA-style compressed caches)
and threads a per-layer ``state`` pytree through ``lax.scan``. Backends:

- ``TrainBackend``     — no cache; full-sequence causal (optionally windowed).
- ``PrefillBackend``   — causal over the fresh chunk (+ merged attention over
  previously cached pages: chunked prefill), writes new KV into pages.
- ``DecodeBackend``    — single-token append + paged attention over the pool.

Paged states are the PHYSICAL per-device pools of the KV Cache Adaptor
(core/kv_adaptor.py): per layer ``k/v: [num_blocks, B_base, W]`` with
``W`` the row of all base-mode KV heads (or ``[num_blocks, page,
width]`` for compressed MLA caches). A mode reads them under its head
split ``hs`` (view token ``t*hs + j`` = lane group ``j`` of row ``t``);
the kernels index that in place and the references reshape to the
logical view ``[num_blocks, B_base*hs, kvh/hs, hd]`` (paper §4.2).
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax

from repro.kernels.paged_attention.ref import pool_view

NEG_INF = -0.7 * float(jnp.finfo(jnp.float32).max)


# ---------------------------------------------------------------------------
# reference attention math (pure jnp; Pallas kernels are drop-ins via ops)
# ---------------------------------------------------------------------------

def _repeat_kv(k: jax.Array, n_rep: int) -> jax.Array:
    if n_rep == 1:
        return k
    return jnp.repeat(k, n_rep, axis=2)


def causal_attention(q, k, v, *, q_offset=0, window: Optional[int] = None,
                     softmax_scale: Optional[float] = None):
    """q [B,Tq,H,hd], k/v [B,Tk,KV,hd]; causal with optional sliding window.
    ``q_offset``: absolute position of q[0] minus that of k[0]."""
    B, Tq, H, hd = q.shape
    Tk, KV = k.shape[1], k.shape[2]
    scale = softmax_scale if softmax_scale is not None else hd ** -0.5
    k = _repeat_kv(k, H // KV)
    v = _repeat_kv(v, H // KV)
    s = jnp.einsum("bqhd,bkhd->bhqk", q.astype(jnp.float32),
                   k.astype(jnp.float32)) * scale
    qpos = jnp.arange(Tq)[:, None] + q_offset
    kpos = jnp.arange(Tk)[None, :]
    mask = kpos <= qpos
    if window is not None:
        mask &= kpos > qpos - window
    s = jnp.where(mask[None, None], s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    out = jnp.einsum("bhqk,bkhd->bqhd", p, v.astype(jnp.float32))
    return out.astype(q.dtype)


def attention_with_lse(q, k, v, mask, softmax_scale):
    """Returns (out [B,Tq,H,hd] fp32, lse [B,H,Tq] fp32); mask [B,1,Tq,Tk]
    or broadcastable.

    GQA is computed GROUPED (q reshaped [KV, rep] against unrepeated K/V)
    and K/V stay in their storage dtype until the dot — no repeated or
    fp32-materialized copies of the (large) gathered context (§Perf A1).
    """
    B, Tq, H, hd = q.shape
    KV = k.shape[2]
    rep = H // KV
    qg = q.reshape(B, Tq, KV, rep, hd)
    # dots accumulate in fp32 without materializing fp32 copies of k/v
    s = jnp.einsum("bqgrd,bkgd->bgrqk", qg, k,
                   preferred_element_type=jnp.float32) * softmax_scale
    s = s.reshape(B, H, Tq, s.shape[-1])
    s = jnp.where(mask, s, NEG_INF)
    m = jnp.max(s, axis=-1, keepdims=True)
    e = jnp.exp(s - m)
    denom = jnp.sum(e, axis=-1, keepdims=True)
    p = (e / jnp.maximum(denom, 1e-30)).reshape(B, KV, rep, Tq, -1)
    out = jnp.einsum("bgrqk,bkgd->bqgrd", p.astype(q.dtype), v,
                     preferred_element_type=jnp.float32)
    out = out.reshape(B, Tq, H, hd)
    lse = (m + jnp.log(jnp.maximum(denom, 1e-30)))[..., 0]
    return out, lse


def merge_attention(outs_lses):
    """Combine partial attentions over disjoint key sets via their LSEs.
    outs: [B,Tq,H,hd] fp32; lses: [B,H,Tq]."""
    ms = jnp.stack([l for _, l in outs_lses])          # [P,B,H,Tq]
    m = jnp.max(ms, axis=0)
    ws = jnp.exp(ms - m[None])                          # [P,B,H,Tq]
    num = sum(o * jnp.transpose(w, (0, 2, 1))[..., None]
              for (o, _), w in zip(outs_lses, ws))
    den = jnp.transpose(jnp.sum(ws, axis=0), (0, 2, 1))[..., None]
    return num / jnp.maximum(den, 1e-30)


# ---------------------------------------------------------------------------
# paged pool primitives (jnp reference; serving uses kernels/paged_attention)
# ---------------------------------------------------------------------------

def paged_append(pool: jax.Array, vals: jax.Array, slots: jax.Array):
    """pool [nblk, page, ...]; vals [B,T,...]; slots [B,T] flat token slots
    (= block_id*page + offset; negative => drop)."""
    nblk, page = pool.shape[0], pool.shape[1]
    flat = pool.reshape(nblk * page, *pool.shape[2:])
    v = vals.reshape(-1, *vals.shape[2:]).astype(pool.dtype)
    s = slots.reshape(-1)
    # parked writes (slot < 0) target the reserved scratch slot: the last
    # slot of the last block, which the adaptor never allocates.
    safe = jnp.where(s >= 0, s, nblk * page - 1)
    keep = (s >= 0).reshape((-1,) + (1,) * (v.ndim - 1))
    flat = flat.at[safe].set(jnp.where(keep, v, flat[safe]))
    return flat.reshape(pool.shape)


def paged_gather(pool: jax.Array, block_table: jax.Array):
    """pool [nblk, page, ...]; block_table [B, max_blocks] -> [B,
    max_blocks*page, ...] (unmasked; caller masks by context length)."""
    g = pool[jnp.maximum(block_table, 0)]  # [B, mb, page, ...]
    B, mb, page = g.shape[0], g.shape[1], g.shape[2]
    return g.reshape(B, mb * page, *g.shape[3:])


def paged_attention_ref(q, k_pool, v_pool, block_table, context_len, *,
                        window: Optional[int] = None,
                        softmax_scale: Optional[float] = None):
    """Decode attention: q [B,H,hd]; pools [nblk,page,KV,hd];
    block_table [B,mb]; context_len [B] (includes the current token)."""
    hd = q.shape[-1]
    scale = softmax_scale if softmax_scale is not None else hd ** -0.5
    k = paged_gather(k_pool, block_table)  # [B, Tk, KV, hd]
    v = paged_gather(v_pool, block_table)
    Tk = k.shape[1]
    kpos = jnp.arange(Tk)[None, :]
    mask = kpos < context_len[:, None]
    if window is not None:
        mask &= kpos >= (context_len[:, None] - window)
    out, _ = attention_with_lse(q[:, None], k, v, mask[:, None, None, :],
                                scale)
    return out[:, 0].astype(q.dtype)


# ---------------------------------------------------------------------------
# backends
# ---------------------------------------------------------------------------

@dataclass(frozen=True)
class TrainBackend:
    """Full-sequence causal attention, no cache (training / eval)."""
    window: Optional[int] = None

    def attend(self, state, q, k, v, *, positions, window=None):
        w = window if window is not None else self.window
        return causal_attention(q, k, v, window=w), state

    # MLA-style raw primitives: keep the full sequence in-line.
    def append_ctx(self, state, vals, *, positions):
        return vals, None, state  # ctx, mask(None->causal inline), state


@dataclass(frozen=True)
class PrefillBackend:
    """Fresh-or-chunked prefill: causal within the chunk, merged with paged
    attention over previously cached pages; writes the chunk's KV to pages.

    ``slots [B,T]`` flat write slots; ``prior_len [B]`` tokens already in
    cache (0 for fresh prefill); ``block_table [B,mb]`` covers prior pages
    (and, on the kernel path, the chunk's own pages).

    ``impl`` follows the decode tri-state (``resolve_impl``): the kernel
    path runs the fused chunk append + paged flash-prefill kernel
    (§Perf D6) — chunk-proportional aliased row writes and an
    mb-bucket-bounded online-softmax sweep of the block table; the
    dense ``attention_with_lse``-over-``paged_gather`` math below
    survives only as the jnp reference."""
    slots: jax.Array
    prior_len: jax.Array
    block_table: jax.Array
    chunked: bool = False
    impl: Optional[str] = None
    hs: int = 1               # head split of the mode reading the pools

    def attend(self, state, q, k, v, *, positions, window=None):
        from repro.kernels.paged_attention import ops as pa_ops
        k_pool, v_pool = state
        if self.chunked and pa_ops.resolve_impl(self.impl) != "ref":
            from repro.kernels.flash_prefill import ops as fp_ops
            out, k_pool, v_pool = fp_ops.paged_flash_prefill(
                q, k, v, k_pool, v_pool, self.slots, self.block_table,
                self.prior_len, hs=self.hs, window=window, impl=self.impl)
            return out, (k_pool, v_pool)
        hd = q.shape[-1]
        kv = paged_append(pool_view(k_pool, self.hs, hd), k, self.slots)
        vv = paged_append(pool_view(v_pool, self.hs, hd), v, self.slots)
        new_state = (kv.reshape(k_pool.shape), vv.reshape(v_pool.shape))
        scale = hd ** -0.5
        if not self.chunked:
            out = causal_attention(q, k, v, window=window)
            return out, new_state
        # chunked reference: merge in-chunk causal with attention over
        # prior pages
        B, Tq = q.shape[0], q.shape[1]
        qpos = jnp.arange(Tq)[None, :, None] + self.prior_len[:, None, None]
        inmask = (jnp.arange(Tq)[None, None, :] <=
                  jnp.arange(Tq)[None, :, None])
        if window is not None:
            inmask = inmask & (jnp.arange(Tq)[None, None, :] >
                               jnp.arange(Tq)[None, :, None] - window)
        o1, l1 = attention_with_lse(q, k, v, inmask[:, None], scale)
        kp = paged_gather(kv, self.block_table)
        vp = paged_gather(vv, self.block_table)
        Tk = kp.shape[1]
        pmask = jnp.arange(Tk)[None, None, None, :] < \
            self.prior_len[:, None, None, None]
        if window is not None:
            pmask = pmask & (jnp.arange(Tk)[None, None, None, :] >=
                             qpos[:, None, :, 0:1] - window + 1)
        o2, l2 = attention_with_lse(q, kp, vp, pmask, scale)
        out = merge_attention([(o1, l1), (o2, l2)])
        return out.astype(q.dtype), new_state

    def append_ctx(self, state, vals, *, positions):
        (pool,) = state if isinstance(state, tuple) and len(state) == 1 \
            else (state,)
        pool = paged_append(pool, vals, self.slots)
        ctx = paged_gather(pool, self.block_table)
        return ctx, None, (pool,)


@dataclass(frozen=True)
class DecodeBackend:
    """Single-token decode over the paged pool.

    ``impl`` selects the attention implementation (resolved by
    ``kernels/paged_attention/ops.resolve_impl``): ``None``/"auto"
    dispatches to the Pallas kernel where compiled support exists (TPU)
    and the jnp reference elsewhere; "force" insists on the kernel
    (interpret-mode on CPU: the parity path); "ref" pins the reference.
    The kernel path fuses the single-token KV append (an aliased
    per-request row write) instead of the two full-pool scatters."""
    slots: jax.Array          # [B] flat write slot of the new token
    block_table: jax.Array    # [B, max_blocks] (mb-bucketed width)
    context_len: jax.Array    # [B] incl. the new token
    impl: Optional[str] = None
    hs: int = 1               # head split of the mode reading the pools

    def attend(self, state, q, k, v, *, positions, window=None):
        from repro.kernels.paged_attention import ops as pa_ops
        k_pool, v_pool = state
        if pa_ops.resolve_impl(self.impl) == "ref":
            # deliberately NOT ops.paged_attention_decode(impl="ref"):
            # this grouped attention (attention_with_lse) never
            # materializes repeated/fp32 copies of the gathered context
            # (§Perf A1) — the kernels-local oracle does, and is a test
            # oracle, not a serving path
            hd = q.shape[-1]
            kv = paged_append(pool_view(k_pool, self.hs, hd), k,
                              self.slots[:, None])
            vv = paged_append(pool_view(v_pool, self.hs, hd), v,
                              self.slots[:, None])
            out = paged_attention_ref(q[:, 0], kv, vv, self.block_table,
                                      self.context_len, window=window)
            k_pool, v_pool = kv.reshape(k_pool.shape), vv.reshape(
                v_pool.shape)
        else:
            out, k_pool, v_pool = pa_ops.paged_attention_decode(
                q[:, 0], k[:, 0], v[:, 0], k_pool, v_pool, self.slots,
                self.block_table, self.context_len, hs=self.hs,
                window=window, impl=self.impl)
        return out[:, None], (k_pool, v_pool)

    def attend_mla_absorbed(self, state, q_abs, q_pe, entry, *, R: int,
                            window=None):
        """Absorbed MLA decode (§Perf D5): q_abs [B,Hl,R] = q_nope·W_uk,
        q_pe [B,Hl,Rr] (both pre-scaled), entry [B,R+Rr] the new token's
        compressed cache row. Scores run against the compressed pool
        directly; returns ([B,Hl,R] fp32 context read, new state) for
        the caller to up-project with W_uv — the naive path's
        [B,Tk,H,·] K/V expansion is never materialized."""
        from repro.kernels.paged_attention import ops as pa_ops
        (pool,) = state if isinstance(state, tuple) else (state,)
        q_cat = jnp.concatenate([q_abs, q_pe], axis=-1)
        out_c, pool = pa_ops.paged_mla_attention_decode(
            q_cat, entry, pool, self.slots, self.block_table,
            self.context_len, R=R, window=window, impl=self.impl)
        return out_c, (pool,)

    def append_ctx(self, state, vals, *, positions):
        (pool,) = state if isinstance(state, tuple) and len(state) == 1 \
            else (state,)
        pool = paged_append(pool, vals[:, None] if vals.ndim == 2 else vals,
                            self.slots[:, None])
        ctx = paged_gather(pool, self.block_table)
        return ctx, self.context_len, (pool,)


# ---------------------------------------------------------------------------
# live cross-layout backends (docs/PERF.md §D8)
# ---------------------------------------------------------------------------
#
# A request riding a LIVE rebind holds block SEGMENTS written under
# earlier merges. A tag-t segment's per-device head slices physically
# live on the t engines of its owner group (a buddy-aligned subset of
# the current group), under the tag-t pool view [nb, B_base*t, kvh/t,
# hd]. The live backends therefore compute attention in the STORED head
# frame (the full storage-shard head set; ``gqa_attention`` skips the
# merge-view weight slice when ``backend.stored_frame``): each device
# sweeps every segment it owns — under that segment's view, for the
# stored-head sub-slice its old view rank held — producing partial
# (out, lse) pairs; partials merge locally across segments, then across
# the merge axis with one flash-style LSE collective
# (``TPContext.lse_merge(axes=view_axes)``), and the merged stored-frame
# output is sliced back to the current mode's local heads for the
# unchanged output projection. New tokens are always written under the
# CURRENT view (the host retags pending slots at rebind), so writes
# never cross layouts — only reads do. No block moves, no reallocation.

def _seg_scatter(out_t, lse_t, v_old, ok, H_st, head_axis):
    """Scatter one segment sweep's (out, lse) — computed for the Hq_t
    stored-head sub-slice at per-row offset ``v_old*Hq_t`` — into the
    full stored-head frame. Absent heads get a zero output and -inf lse
    so the LSE merges ignore them."""
    Hq_t = out_t.shape[head_axis]
    jpos = jnp.arange(H_st)[None, :] - v_old[:, None] * Hq_t     # [B,H_st]
    okj = ok[:, None] & (jpos >= 0) & (jpos < Hq_t)
    src = jnp.clip(jpos, 0, Hq_t - 1)
    if head_axis == 1:        # decode: out [B,Hq,hd], lse [B,Hq]
        o = jnp.take_along_axis(out_t, src[:, :, None], axis=1)
        o = jnp.where(okj[:, :, None], o, 0.0)
        l = jnp.take_along_axis(lse_t, src, axis=1)
        l = jnp.where(okj, l, NEG_INF)
    else:                     # prefill: out [B,T,Hq,hd], lse [B,Hq,T]
        o = jnp.take_along_axis(out_t, src[:, None, :, None], axis=2)
        o = jnp.where(okj[:, None, :, None], o, 0.0)
        l = jnp.take_along_axis(lse_t, src[:, :, None], axis=1)
        l = jnp.where(okj[:, :, None], l, NEG_INF)
    return o, l


def _merge_sweeps(outs_lses):
    """Local (out, lse) -> (m, weights, l) combine across segment
    sweeps, ready for the cross-rank ``lse_merge``. Each normalized
    sweep is an (acc=out, l=1, m=lse) partial; heads absent from every
    local sweep keep m = -inf and weight out to zero in the
    collective."""
    ms = jnp.stack([l for _, l in outs_lses])              # [S,...]
    m = jnp.max(ms, axis=0)
    ws = jnp.exp(ms - m[None])
    ws = jnp.where(ms <= NEG_INF / 2, 0.0, ws)
    l = jnp.sum(ws, axis=0)
    return m, ws, l


@dataclass(frozen=True)
class LiveDecodeBackend:
    """Decode over a request set whose KV spans mode-tagged segments.

    ``segs``: one static entry per placement LANE — (tag, block_table
    [B, mb_t], seg_len [B], owner [B]) where ``seg_len`` is the lane's
    token count per row (0 = row has no such lane) and ``owner`` the
    merge-axis index where the lane's owner group starts within the
    current group. Tags may REPEAT across lanes (§D12 sequence
    parallelism: one lane per SP shard). The write-tag lane holding each
    row's live segment carries a count that INCLUDES the new token
    (appended before the sweep) — all masking derives from the per-lane
    counts, so no separate total context length is carried.

    ``sp`` > 1 selects the sequence-parallel write: the new token is
    written under the SHARD-width tag ``merge // sp`` to the per-row
    owner shard (``write_own`` [B], merge-axis offset) only; non-owner
    ranks park the write in the reserved scratch block. ``sp=1`` keeps
    the classic whole-group write, byte-identical to the pre-SP path."""
    ctx: "TPContext"
    slots: jax.Array          # [B] write-view slot of the new token
    segs: Tuple[Tuple[int, jax.Array, jax.Array, jax.Array], ...]
    merge: int                # current mode (the state view's tag)
    block_base: int           # B_base: tokens/block at merge=1
    window: Optional[int] = None
    impl: Optional[str] = None
    sp: int = 1               # sequence-parallel degree (divides merge)
    write_own: Optional[jax.Array] = None   # [B] owner shard offset
    stored_frame = True       # gqa_attention: project q/k/v un-view-sliced

    def attend(self, state, q, k, v, *, positions, window=None):
        from repro.kernels.paged_attention import ops as pa_ops
        assert (window or self.window) is None, \
            "live cross-layout reads do not support sliding windows " \
            "(absolute positions are lost in segment-local sweeps)"
        k_pool, v_pool = state                  # current-tag view
        B = q.shape[0]
        H_st, hd = q.shape[2], q.shape[3]
        KV_st = k.shape[2]
        m = self.merge
        nb = k_pool.shape[0]
        v_idx = self.ctx.view_rank()
        scale = hd ** -0.5

        if self.sp == 1:
            # write the new token under the CURRENT view: this device's
            # current-mode head slice of the stored-frame projection
            kv_loc = KV_st // m
            k_new = lax.dynamic_slice_in_dim(k[:, 0], v_idx * kv_loc,
                                             kv_loc, 1)
            v_new = lax.dynamic_slice_in_dim(v[:, 0], v_idx * kv_loc,
                                             kv_loc, 1)
            k_pool, v_pool = pa_ops.paged_append_token(
                (k_pool, v_pool), (k_new, v_new), self.slots, hs=m,
                impl=self.impl)
        else:
            # §D12 sequence-parallel write: shard-width tag, per-row
            # owner shard. The parking (non-owner ranks write the
            # reserved scratch slot) is computed HERE, outside the
            # kernels, so both the reference and Pallas append paths run
            # unchanged.
            wt = m // self.sp
            cap_w = self.block_base * wt
            kvh_w = KV_st // wt
            own = self.write_own
            is_owner = (own <= v_idx) & (v_idx < own + wt)       # [B]
            v_w = jnp.clip(v_idx - own, 0, wt - 1)
            idx = v_w[:, None] * kvh_w + jnp.arange(kvh_w)[None, :]
            k_new = jnp.take_along_axis(k[:, 0], idx[:, :, None], axis=1)
            v_new = jnp.take_along_axis(v[:, 0], idx[:, :, None], axis=1)
            park = nb * cap_w - 1   # last slot of the reserved block
            slots_w = jnp.where(is_owner, self.slots, park).astype(
                self.slots.dtype)
            k_pool, v_pool = pa_ops.paged_append_token(
                (k_pool, v_pool), (k_new, v_new), slots_w, hs=wt,
                impl=self.impl)

        q_st = q[:, 0]                           # [B, H_st, hd]
        partials = []
        for tag, bt_t, len_t, own_t in self.segs:
            Hq_t = H_st // tag
            ok = (own_t <= v_idx) & (v_idx < own_t + tag)       # [B]
            eff = jnp.where(ok, len_t, 0).astype(jnp.int32)
            v_old = jnp.clip(v_idx - own_t, 0, tag - 1)
            idx = v_old[:, None] * Hq_t + jnp.arange(Hq_t)[None, :]
            q_sub = jnp.take_along_axis(q_st, idx[:, :, None], axis=1)
            out_t, lse_t = pa_ops.paged_attention_with_lse(
                q_sub, k_pool, v_pool, bt_t, eff, hs=tag,
                softmax_scale=scale, impl=self.impl)
            partials.append(_seg_scatter(out_t, lse_t, v_old,
                                         ok & (len_t > 0), H_st, 1))
        m_loc, ws, l_loc = _merge_sweeps(partials)
        acc = sum(o * w[..., None] for (o, _), w in zip(partials, ws))
        out_full = self.ctx.lse_merge(acc, l_loc, m_loc,
                                      axes=self.ctx.view_axes)  # [B,H_st,hd]
        h_loc = H_st // m
        out = lax.dynamic_slice_in_dim(out_full, v_idx * h_loc, h_loc, 1)
        return out[:, None].astype(q.dtype), (k_pool, v_pool)


@dataclass(frozen=True)
class LivePrefillBackend:
    """Chunked prefill whose PRIOR context spans mode-tagged segments.

    The chunk itself always lands in the write-tag lane: its pages are
    in that lane's ``segs`` table and the causal in-chunk +
    lane-prior attention is one sweep (``seg_len`` for the causal lane
    = prior tokens within that lane, NOT counting the chunk). All other
    lanes get prior-only sweeps. With ``sp=1`` the causal lane is the
    (unique) current-tag lane; with ``sp>1`` it is the LAST lane — the
    host stages each row's owner shard there (§D12), and the chunk is
    written shard-width to the per-row owner (``write_own``) only."""
    ctx: "TPContext"
    slots: jax.Array          # [B,T] write-view chunk write slots
    segs: Tuple[Tuple[int, jax.Array, jax.Array, jax.Array], ...]
    merge: int
    block_base: int
    window: Optional[int] = None
    impl: Optional[str] = None
    sp: int = 1               # sequence-parallel degree (divides merge)
    write_own: Optional[jax.Array] = None   # [B] owner shard offset
    stored_frame = True

    def attend(self, state, q, k, v, *, positions, window=None):
        from repro.kernels.flash_prefill import ops as fp_ops
        from repro.kernels.paged_attention import ops as pa_ops
        assert (window or self.window) is None, \
            "live cross-layout reads do not support sliding windows"
        k_pool, v_pool = state
        B, T, H_st, hd = q.shape
        KV_st = k.shape[2]
        m = self.merge
        nb = k_pool.shape[0]
        v_idx = self.ctx.view_rank()
        scale = hd ** -0.5

        if self.sp == 1:
            kv_loc = KV_st // m
            k_new = lax.dynamic_slice_in_dim(k, v_idx * kv_loc, kv_loc, 2)
            v_new = lax.dynamic_slice_in_dim(v, v_idx * kv_loc, kv_loc, 2)
            k_pool, v_pool = pa_ops.paged_append_chunk(
                (k_pool, v_pool), (k_new, v_new), self.slots, hs=m,
                impl=self.impl)
        else:
            # §D12: shard-width owner-masked chunk write (the engine
            # guarantees each row's chunk lies within ONE block, so one
            # owner shard covers the whole row); parking is computed
            # outside the kernels.
            wt = m // self.sp
            cap_w = self.block_base * wt
            kvh_w = KV_st // wt
            own = self.write_own
            is_owner = (own <= v_idx) & (v_idx < own + wt)       # [B]
            v_w = jnp.clip(v_idx - own, 0, wt - 1)
            idx = v_w[:, None] * kvh_w + jnp.arange(kvh_w)[None, :]
            k_new = jnp.take_along_axis(k, idx[:, None, :, None], axis=2)
            v_new = jnp.take_along_axis(v, idx[:, None, :, None], axis=2)
            park = nb * cap_w - 1
            slots_w = jnp.where(is_owner[:, None] & (self.slots >= 0),
                                self.slots, park).astype(self.slots.dtype)
            k_pool, v_pool = pa_ops.paged_append_chunk(
                (k_pool, v_pool), (k_new, v_new), slots_w, hs=wt,
                impl=self.impl)

        partials = []
        for i, (tag, bt_t, len_t, own_t) in enumerate(self.segs):
            Hq_t = H_st // tag
            ok = (own_t <= v_idx) & (v_idx < own_t + tag)
            eff = jnp.where(ok, len_t, 0).astype(jnp.int32)
            v_old = jnp.clip(v_idx - own_t, 0, tag - 1)
            idx = v_old[:, None] * Hq_t + jnp.arange(Hq_t)[None, :]
            q_sub = jnp.take_along_axis(q, idx[:, None, :, None], axis=2)
            cur = (tag == m) if self.sp == 1 \
                else (i == len(self.segs) - 1)
            out_t, lse_t = fp_ops.paged_prefill_sweep_with_lse(
                q_sub, k_pool, v_pool, bt_t, eff, hs=tag,
                prior_only=not cur, softmax_scale=scale, impl=self.impl)
            # the causal-lane sweep is causal over [prior, prior+T): it
            # always contributes (the chunk row itself, on the owner
            # ranks); other lanes only where the lane exists
            ok_any = ok if cur else (ok & (len_t > 0))
            partials.append(_seg_scatter(out_t, lse_t, v_old, ok_any,
                                         H_st, 2))
        m_loc, ws, l_loc = _merge_sweeps(partials)       # lse-shaped [B,H,T]
        # weights [B,H_st,T] -> [B,T,H_st,1] against out rows [B,T,H_st,hd]
        acc = sum(o * jnp.moveaxis(w, 1, -1)[..., None]
                  for (o, _), w in zip(partials, ws))
        out_full = self.ctx.lse_merge(
            acc, jnp.moveaxis(l_loc, 1, -1), jnp.moveaxis(m_loc, 1, -1),
            axes=self.ctx.view_axes)                     # [B,T,H_st,hd]
        h_loc = H_st // m
        out = lax.dynamic_slice_in_dim(out_full, v_idx * h_loc, h_loc, 2)
        return out.astype(q.dtype), (k_pool, v_pool)
