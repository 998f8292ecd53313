"""Paged decode-attention Pallas TPU kernels over the PHYSICAL pool.

Pool layout (KV Cache Adaptor): every per-device pool is stored
``[num_blocks, B_base, W]`` — one page of ``B_base`` token rows, each row
``W = kvh_dev * hd`` lanes (all of this device's base-mode KV heads).
That is the layout the chip holds without lane padding, and the one
every mode reads in place: a merge whose head split is ``hs`` views a
block as ``B_base * hs`` tokens of ``W / hs`` lanes, view token
``t * hs + j`` being lane group ``j`` of physical row ``t``. The kernels
take the physical pool plus ``hs`` and do that index arithmetic
themselves, so no mode ever reshapes a pool — on TPU a reshape that
moves the lane dimension is a full relayout copy, not a view.

Decode attention: grid = (batch, num_kv_blocks); the block table and
context lengths are SCALAR-PREFETCHED so each grid step's BlockSpec
index_map DMAs the right physical page. Scores for every (lane group,
view head) column come from one dense pass, ``(k * q_tiled) @ seg``,
where ``q_tiled`` carries each query head's vector under its KV head's
lanes and ``seg`` [W, W/hd] sums each hd-lane group; the value read is
the mirror ``sum_t (p @ seg^T) * v``. No in-kernel reshape splits the
lane dimension (Mosaic refuses those at hd < 128). Each column keeps its
own online-softmax state; the ``hs`` columns of one head (its tokens
interleave across lane groups) merge by LSE after the sweep. Pages
whose tokens all fall outside [ctx-window, ctx) are skipped via
@pl.when.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -0.7 * float(jnp.finfo(jnp.float32).max)


def _group_of_column(shape, kvv: int, hs: int):
    """Lane-group index j of each score column g = j * kvv + h."""
    g = jax.lax.broadcasted_iota(jnp.int32, shape, len(shape) - 1)
    j = jnp.zeros(shape, jnp.int32)
    for i in range(1, hs):
        j = j + (g >= i * kvv).astype(jnp.int32)
    return j


# seg is exact 0/1; HIGHEST keeps the f32 operands from being rounded to
# bf16 on the MXU, so the lane-group sums are f32 dot products
_EXACT = jax.lax.Precision.HIGHEST


def _expand(x, seg):
    """[R, G] per-column values -> [R, W] on each column's hd lanes."""
    return jax.lax.dot_general(x, seg, (((1,), (1,)), ((), ())),
                               precision=_EXACT,
                               preferred_element_type=jnp.float32)


def _kernel(tables_ref, ctx_ref, q_ref, seg_ref, k_ref, v_ref, out_ref,
            lse_ref, m_ref, l_ref, acc_ref, *, page: int, hs: int, kvv: int,
            rep: int, window: Optional[int], mb: int, scale: float):
    b = pl.program_id(0)
    j = pl.program_id(1)
    ctx = ctx_ref[b]

    @pl.when(j == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    cap = page * hs                 # view tokens per block
    start = j * cap
    lo = ctx - window if window is not None else 0
    live = (start < ctx) & (start + cap > lo)

    @pl.when(live)
    def _body():
        k = k_ref[0].astype(jnp.float32)            # [page, W]
        v = v_ref[0].astype(jnp.float32)
        seg = seg_ref[...]                          # [W, G]
        shape = (page, seg.shape[1])
        t = jax.lax.broadcasted_iota(jnp.int32, shape, 0)
        pos = start + t * hs + _group_of_column(shape, kvv, hs)
        mask = pos < ctx
        if window is not None:
            mask &= pos >= ctx - window
        for r in range(rep):
            q = q_ref[0, r:r + 1, :]                # [1, W]
            s = jnp.dot(k * q, seg, precision=_EXACT,
                        preferred_element_type=jnp.float32)
            s = jnp.where(mask, s * scale, NEG_INF)  # [page, G]
            m_prev = m_ref[r:r + 1, :]
            m_new = jnp.maximum(m_prev, jnp.max(s, axis=0, keepdims=True))
            alpha = jnp.exp(m_prev - m_new)
            p = jnp.where(mask, jnp.exp(s - m_new), 0.0)
            l_ref[r:r + 1, :] = alpha * l_ref[r:r + 1, :] + \
                jnp.sum(p, axis=0, keepdims=True)
            pv = jnp.sum(_expand(p, seg) * v, axis=0, keepdims=True)
            acc_ref[r:r + 1, :] = _expand(alpha, seg) * acc_ref[r:r + 1, :] \
                + pv
            m_ref[r:r + 1, :] = m_new

    @pl.when(j == mb - 1)
    def _fin():
        l = l_ref[...]
        out_ref[0] = acc_ref[...] / jnp.maximum(
            _expand(l, seg_ref[...]), 1e-30)
        lse_ref[0] = jnp.where(l > 0.0,
                               m_ref[...] + jnp.log(jnp.maximum(l, 1e-30)),
                               NEG_INF)


def paged_attention_kernel(q, k_pool, v_pool, block_table, context_len, *,
                           hs: int = 1, window: Optional[int] = None,
                           softmax_scale: Optional[float] = None,
                           return_lse: bool = False,
                           interpret: bool = False):
    """q [B,H,hd] (the hs-view's heads); pools [nblk, page, W] physical;
    block_table [B,MB] int32; context_len [B] int32 (view tokens) ->
    [B,H,hd]. ``softmax_scale`` overrides the default 1/sqrt(hd)
    (absorbed-MLA callers pre-scale q and pass 1.0). ``return_lse``
    additionally returns the per-head log-sum-exp [B,H] (fp32; NEG_INF
    for rows with no live keys) so callers can LSE-merge this sweep with
    partials over other block segments (§D8)."""
    B, H, hd = q.shape
    nblk, page, W = k_pool.shape
    G = W // hd                     # hd-lane groups per row
    kvv = G // hs                   # view KV heads
    rep = H // kvv
    assert G * hd == W and kvv * hs == G and rep * kvv == H, \
        (q.shape, k_pool.shape, hs)
    MB = block_table.shape[1]
    scale = softmax_scale if softmax_scale is not None else hd ** -0.5
    # q_tiled[b, r, (j*kvv + h)*hd + d] = q[b, h*rep + r, d] for every j
    qt = q.astype(jnp.float32).reshape(B, kvv, rep, hd)
    qt = jnp.tile(jnp.swapaxes(qt, 1, 2).reshape(B, rep, kvv * hd),
                  (1, 1, hs))
    seg = (jnp.arange(W)[:, None] // hd ==
           jnp.arange(G)[None, :]).astype(jnp.float32)
    kern = functools.partial(_kernel, page=page, hs=hs, kvv=kvv, rep=rep,
                             window=window, mb=MB, scale=scale)
    row = lambda b, j, t, c: (b, 0, 0)  # noqa: E731
    out, lse = pl.pallas_call(
        kern,
        name="paged_decode",
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,  # block_table, context_len
            grid=(B, MB),
            in_specs=[
                pl.BlockSpec((1, rep, W), row),
                pl.BlockSpec((W, G), lambda b, j, t, c: (0, 0)),
                pl.BlockSpec((1, page, W), lambda b, j, t, c: (t[b, j], 0, 0)),
                pl.BlockSpec((1, page, W), lambda b, j, t, c: (t[b, j], 0, 0)),
            ],
            out_specs=[pl.BlockSpec((1, rep, W), row),
                       pl.BlockSpec((1, rep, G), row)],
            scratch_shapes=[
                pltpu.VMEM((rep, G), jnp.float32),
                pltpu.VMEM((rep, G), jnp.float32),
                pltpu.VMEM((rep, W), jnp.float32),
            ],
        ),
        out_shape=[jax.ShapeDtypeStruct((B, rep, W), jnp.float32),
                   jax.ShapeDtypeStruct((B, rep, G), jnp.float32)],
        interpret=interpret,
    )(block_table, context_len, qt, seg, k_pool, v_pool)
    out = out.reshape(B, rep, hs, kvv, hd)
    lse = lse.reshape(B, rep, hs, kvv)
    if hs == 1:
        out, lse = out[:, :, 0], lse[:, :, 0]
    else:
        # one head's tokens sit in hs columns: merge those partials
        mx = jnp.max(lse, axis=2, keepdims=True)
        w = jnp.where(lse <= NEG_INF / 2, 0.0, jnp.exp(lse - mx))
        den = jnp.sum(w, axis=2)
        out = jnp.sum(out * w[..., None], axis=2) / \
            jnp.maximum(den, 1e-30)[..., None]
        lse = jnp.where(den > 0.0,
                        mx[:, :, 0] + jnp.log(jnp.maximum(den, 1e-30)),
                        NEG_INF)
    out = jnp.swapaxes(out, 1, 2).reshape(B, H, hd).astype(q.dtype)
    if return_lse:
        return out, jnp.swapaxes(lse, 1, 2).reshape(B, H)
    return out


# ---------------------------------------------------------------------------
# fused appends: the serving paths' pool writes
# ---------------------------------------------------------------------------

def _append_kernel(blk_ref, off_ref, *refs, n: int, T: int, hs: int,
                   wv: int):
    # grid (B, T): step i = b*T + t rewrites the one physical page that
    # view slot (blk, off) lives on — row off // hs, lanes of group
    # off % hs. A page the previous step also wrote is still resident in
    # the output buffer (same block index: no write-back, no re-fetch),
    # so the step builds on that buffer instead of the stale input.
    val_refs, in_refs, out_refs = refs[:n], refs[n:2 * n], refs[2 * n:]
    i = pl.program_id(0) * T + pl.program_id(1)
    blk = blk_ref[i]
    off = off_ref[i]
    chained = (i > 0) & (blk_ref[jnp.maximum(i - 1, 0)] == blk)
    shape = out_refs[0].shape[1:]
    rows = jax.lax.broadcasted_iota(jnp.int32, shape, 0)
    lanes = jax.lax.broadcasted_iota(jnp.int32, shape, 1)
    lane0 = (off % hs) * wv
    mask = (rows == off // hs) & (lanes >= lane0) & (lanes < lane0 + wv)
    for v_ref, p_ref, o_ref in zip(val_refs, in_refs, out_refs):
        val = v_ref[0, 0].astype(o_ref.dtype)       # [1, W]

        @pl.when(chained)
        def _chain(v_=val, o_ref=o_ref):
            o_ref[0] = jnp.where(mask, v_, o_ref[0])

        @pl.when(jnp.logical_not(chained))
        def _fresh(v_=val, p_ref=p_ref, o_ref=o_ref):
            o_ref[0] = jnp.where(mask, v_, p_ref[0])


def _append(pools, vals, slots, *, hs: int, interpret: bool, name: str):
    """pools: tuple of physical [nblk, page, W]; vals: matching tuple of
    [B, T, W // hs] view-frame rows; slots [B, T] int32 view slots
    (block * page*hs + offset; negative => parked to the reserved
    scratch slot, the last of the last block, which the adaptor never
    allocates)."""
    n = len(pools)
    B, T = slots.shape
    nblk, page, W = pools[0].shape
    cap = page * hs
    wv = W // hs
    slots = slots.astype(jnp.int32).reshape(-1)
    parked = slots < 0
    blk = jnp.where(parked, nblk - 1, slots // cap)
    off = jnp.where(parked, cap - 1, slots % cap)
    # every lane group holds the row; the mask picks the one written
    tiled = [jnp.tile(v.reshape(B, T, 1, wv), (1, 1, 1, hs)) for v in vals]
    val_spec = pl.BlockSpec((1, 1, 1, W), lambda b, t, bl, of: (b, t, 0, 0))
    page_spec = pl.BlockSpec((1, page, W),
                             lambda b, t, bl, of: (bl[b * T + t], 0, 0))
    outs = pl.pallas_call(
        functools.partial(_append_kernel, n=n, T=T, hs=hs, wv=wv),
        name=name,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,  # blk, off
            grid=(B, T),
            in_specs=[val_spec] * n + [page_spec] * n,
            out_specs=[page_spec] * n,
        ),
        out_shape=[jax.ShapeDtypeStruct(p.shape, p.dtype) for p in pools],
        # alias indices count the scalar-prefetch operands too:
        # (blk, off, *vals, *pools) -> pool i is operand 2 + n + i
        input_output_aliases={2 + n + i: i for i in range(n)},
        interpret=interpret,
    )(blk, off, *tiled, *pools)
    return tuple(outs)


def paged_append_token_kernel(pools, vals, slots, *, hs: int = 1,
                              interpret: bool = False):
    """In-place single-token append into physical paged pools.

    pools: tuple of [nblk, page, W]; vals: matching tuple of [B, ...]
    new-token rows of W // hs elements (the hs-view's KV heads); slots
    [B] int32 view slots (negative => parked). Returns the updated
    pools, buffer-aliased to the inputs when XLA honors the donation.
    Grid (B,): one page read-modify-write per request. Distinct live
    requests never share a page (block tables are disjoint per adaptor),
    and parked rows all target the don't-care scratch slot."""
    B = slots.shape[0]
    return _append(pools, [v.reshape(B, 1, -1) for v in vals],
                   slots.reshape(B, 1), hs=hs, interpret=interpret,
                   name="paged_append_token")


def paged_append_chunk_kernel(pools, vals, slots, *, hs: int = 1,
                              interpret: bool = False):
    """Multi-token chunk append into physical paged pools: the prefill-
    side generalization of ``paged_append_token_kernel`` (grid (B, T)
    instead of (B,)). vals: tuple of [B, T, ...] rows of W // hs
    elements; slots [B, T] (negative => parked). Consecutive tokens of
    one request that share a page chain through the resident output
    buffer, so the chunk's writes compose — chunk-proportional, never
    O(pool)."""
    B, T = slots.shape
    return _append(pools, [v.reshape(B, T, -1) for v in vals], slots,
                   hs=hs, interpret=interpret, name="paged_append_chunk")
