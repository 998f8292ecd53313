"""Blocked causal flash-attention Pallas TPU kernel (chunked prefill).

grid = (B, H, num_q_blocks, num_kv_blocks), kv innermost so the online
softmax accumulators live in VMEM scratch across the kv sweep. Causal +
sliding-window structure prunes dead kv blocks with @pl.when — for the
window variant the sweep is O(T * W) not O(T^2), which is what makes
long_500k dense-arch decode-prefill sub-quadratic (DESIGN.md §5).
Block sizes default to (128, 128): MXU-aligned, ~1MB VMEM working set.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -0.7 * float(jnp.finfo(jnp.float32).max)


def _kernel(q_ref, k_ref, v_ref, out_ref, m_ref, l_ref, acc_ref, *,
            blk_q: int, blk_k: int, n_k: int, window: Optional[int]):
    i = pl.program_id(2)
    j = pl.program_id(3)

    @pl.when(j == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    q_start = i * blk_q
    k_start = j * blk_k
    causal_live = k_start <= q_start + blk_q - 1
    win_live = True if window is None else \
        (k_start + blk_k - 1 > q_start - window)

    @pl.when(causal_live & win_live)
    def _body():
        q = q_ref[0, 0].astype(jnp.float32)        # [blk_q, hd]
        k = k_ref[0, 0].astype(jnp.float32)        # [blk_k, hd]
        v = v_ref[0, 0].astype(jnp.float32)
        hd = q.shape[-1]
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32)
        s = s * (hd ** -0.5)
        qpos = q_start + jax.lax.broadcasted_iota(jnp.int32,
                                                  (blk_q, blk_k), 0)
        kpos = k_start + jax.lax.broadcasted_iota(jnp.int32,
                                                  (blk_q, blk_k), 1)
        mask = kpos <= qpos
        if window is not None:
            mask &= kpos > qpos - window
        s = jnp.where(mask, s, NEG_INF)

        m_prev = m_ref[...]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.exp(s - m_new)
        l_ref[...] = alpha * l_ref[...] + jnp.sum(p, -1, keepdims=True)
        acc_ref[...] = alpha * acc_ref[...] + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        m_ref[...] = m_new

    @pl.when(j == n_k - 1)
    def _fin():
        out_ref[0, 0] = (acc_ref[...] /
                         jnp.maximum(l_ref[...], 1e-30)).astype(out_ref.dtype)


def flash_prefill_kernel(q, k, v, *, window: Optional[int] = None,
                         blk_q: int = 128, blk_k: int = 128,
                         interpret: bool = False):
    """q [B,H,T,hd]; k/v [B,KV,T,hd] with H == KV (pre-repeated by ops)."""
    B, H, T, hd = q.shape
    blk_q = min(blk_q, T)
    blk_k = min(blk_k, T)
    n_q = T // blk_q
    n_k = T // blk_k
    kern = functools.partial(_kernel, blk_q=blk_q, blk_k=blk_k, n_k=n_k,
                             window=window)
    return pl.pallas_call(
        kern,
        name="flash_prefill",
        grid=(B, H, n_q, n_k),
        in_specs=[
            pl.BlockSpec((1, 1, blk_q, hd), lambda b, h, i, j: (b, h, i, 0)),
            pl.BlockSpec((1, 1, blk_k, hd), lambda b, h, i, j: (b, h, j, 0)),
            pl.BlockSpec((1, 1, blk_k, hd), lambda b, h, i, j: (b, h, j, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, blk_q, hd),
                               lambda b, h, i, j: (b, h, i, 0)),
        scratch_shapes=[
            pltpu.VMEM((blk_q, 1), jnp.float32),
            pltpu.VMEM((blk_q, 1), jnp.float32),
            pltpu.VMEM((blk_q, hd), jnp.float32),
        ],
        out_shape=jax.ShapeDtypeStruct((B, H, T, hd), q.dtype),
        interpret=interpret,
    )(q, k, v)


# ---------------------------------------------------------------------------
# paged flash-prefill: chunked prefill straight over the paged KV pool
# ---------------------------------------------------------------------------
#
# The chunk's K/V rows are appended to the pool FIRST (fused chunk append,
# kernels/paged_attention), so one kernel covers both attention terms of
# chunked prefill: in-chunk causal AND attention over prior context, all
# consumed through the scalar-prefetched block table. Query rows at
# absolute positions prior_len[b] + i attend every pool position
# kpos <= qpos (optionally windowed) — prior tokens and the causal chunk
# prefix are the same sweep, no separate merge pass. Pages whose token
# range falls entirely outside [qpos_min - window + 1, qpos_max] are
# skipped via @pl.when, so per-chunk cost tracks live context
# (mb-bucket-bounded), not the engine's worst-case table width.
#
# Pools arrive PHYSICAL, [nblk, page, W] (kernels/paged_attention/
# kernel.py): view token t*hs + j of a block is lane group j of row t.
# Each (lane group, KV head) is a static hd-lane slice of the loaded
# page, swept by the q heads that read it; every q head keeps its own
# online-softmax state across all lane groups.

def _paged_kernel(tables_ref, prior_ref, q_ref, k_ref, v_ref, out_ref,
                  lse_ref, m_ref, l_ref, acc_ref, *, page: int, hs: int,
                  kvv: int, rep: int, blk_q: int, mb: int,
                  window: Optional[int], scale: float, prior_only: bool):
    b = pl.program_id(0)
    i = pl.program_id(1)
    j = pl.program_id(2)
    prior = prior_ref[b]

    @pl.when(j == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    cap = page * hs                   # view tokens per block
    start = j * cap
    q_lo = prior + i * blk_q          # absolute position of first q row
    q_hi = q_lo + blk_q - 1
    if prior_only:
        # frozen-segment sweep (§D8 live reads): every chunk row attends
        # exactly the segment's [0, prior) tokens — no causal coupling
        # between the segment-local key positions and the (current-
        # segment-relative) query positions
        live = start < prior
    else:
        live = start <= q_hi          # causal: no keys beyond the q block
    if window is not None:
        live &= start + cap > q_lo - window

    @pl.when(live)
    def _body():
        k_page = k_ref[0].astype(jnp.float32)       # [page, W]
        v_page = v_ref[0].astype(jnp.float32)
        hd = acc_ref.shape[-1]
        shape = (blk_q, page)
        qpos = q_lo + jax.lax.broadcasted_iota(jnp.int32, shape, 0)
        t = jax.lax.broadcasted_iota(jnp.int32, shape, 1)
        for jj in range(hs):
            kpos = start + t * hs + jj
            mask = (kpos < prior) if prior_only else (kpos <= qpos)
            if window is not None:
                mask &= kpos > qpos - window
            for h in range(kvv):
                lo = (jj * kvv + h) * hd
                k = k_page[:, lo:lo + hd]               # [page, hd]
                v = v_page[:, lo:lo + hd]
                for r in range(rep):
                    hq = h * rep + r
                    q = q_ref[0, hq].astype(jnp.float32)  # [blk_q, hd]
                    s = jax.lax.dot_general(
                        q, k, (((1,), (1,)), ((), ())),
                        preferred_element_type=jnp.float32)
                    s = jnp.where(mask, s * scale, NEG_INF)
                    m_prev = m_ref[hq]                   # [blk_q, 1]
                    m_new = jnp.maximum(
                        m_prev, jnp.max(s, axis=-1, keepdims=True))
                    alpha = jnp.exp(m_prev - m_new)
                    p = jnp.where(mask, jnp.exp(s - m_new), 0.0)
                    l_ref[hq] = alpha * l_ref[hq] + \
                        jnp.sum(p, -1, keepdims=True)
                    acc_ref[hq] = alpha * acc_ref[hq] + jnp.dot(
                        p, v, preferred_element_type=jnp.float32)
                    m_ref[hq] = m_new

    @pl.when(j == mb - 1)
    def _fin():
        l = l_ref[...]
        out_ref[0] = (acc_ref[...] /
                      jnp.maximum(l, 1e-30)).astype(out_ref.dtype)
        lse_ref[0] = jnp.where(l > 0.0,
                               m_ref[...] + jnp.log(jnp.maximum(l, 1e-30)),
                               NEG_INF)


# query rows (heads x q block) per grid step: bounds the f32 softmax
# state and acc scratch plus the q/out blocks inside the 16 MiB scoped
# VMEM default of a v5e core at 32 heads (128 rows per head overflows it)
MAX_Q_ROWS = 2048


def q_block(blk_q: int, T: int, H: int) -> int:
    """The q block a chunk of T rows over H heads runs with: ``blk_q``
    capped by T and by the VMEM row budget (a power of two >= 8)."""
    cap = 8
    while cap * 2 * H <= MAX_Q_ROWS:
        cap *= 2
    return min(blk_q, T, cap)


def paged_flash_prefill_kernel(q, k_pool, v_pool, block_table, prior_len, *,
                               hs: int = 1, window: Optional[int] = None,
                               softmax_scale: Optional[float] = None,
                               blk_q: int = 128, prior_only: bool = False,
                               return_lse: bool = False,
                               interpret: bool = False):
    """q [B,H,T,hd] (the hs-view's heads; T a multiple of
    ``q_block(blk_q, T, H)``; absolute
    position of q[:, :, i] is prior_len[b] + i); pools [nblk, page, W]
    physical, already holding the chunk's rows; block_table [B,MB]
    int32; prior_len [B] int32 -> [B,H,T,hd].

    ``prior_only`` sweeps a FROZEN block segment (§D8 live reads): every
    query row attends exactly the segment's first ``prior_len[b]``
    tokens, with no causal term — the segment belongs entirely to the
    past. ``return_lse`` adds the per-(head, row) log-sum-exp
    [B,H,T] fp32 for LSE-merging this sweep with other segments'."""
    B, H, T, hd = q.shape
    nblk, page, W = k_pool.shape
    kvv = W // (hd * hs)
    rep = H // kvv
    assert kvv * hd * hs == W and rep * kvv == H, (q.shape, k_pool.shape, hs)
    MB = block_table.shape[1]
    blk_q = q_block(blk_q, T, H)
    n_q = T // blk_q
    scale = softmax_scale if softmax_scale is not None else hd ** -0.5
    kern = functools.partial(_paged_kernel, page=page, hs=hs, kvv=kvv,
                             rep=rep, blk_q=blk_q, mb=MB, window=window,
                             scale=scale, prior_only=prior_only)
    rows = lambda b, i, j, t, p: (b, 0, i, 0)  # noqa: E731
    page_spec = pl.BlockSpec((1, page, W),
                             lambda b, i, j, t, p: (t[b, j], 0, 0))
    out, lse = pl.pallas_call(
        kern,
        name="paged_flash_prefill",
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,  # block_table, prior_len
            grid=(B, n_q, MB),
            in_specs=[pl.BlockSpec((1, H, blk_q, hd), rows),
                      page_spec, page_spec],
            out_specs=[pl.BlockSpec((1, H, blk_q, hd), rows),
                       pl.BlockSpec((1, H, blk_q, 1), rows)],
            scratch_shapes=[
                pltpu.VMEM((H, blk_q, 1), jnp.float32),
                pltpu.VMEM((H, blk_q, 1), jnp.float32),
                pltpu.VMEM((H, blk_q, hd), jnp.float32),
            ],
        ),
        out_shape=[jax.ShapeDtypeStruct((B, H, T, hd), q.dtype),
                   jax.ShapeDtypeStruct((B, H, T, 1), jnp.float32)],
        interpret=interpret,
    )(block_table, prior_len, q, k_pool, v_pool)
    if return_lse:
        return out, lse[..., 0]
    return out
