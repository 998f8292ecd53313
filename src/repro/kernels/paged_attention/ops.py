"""Dispatch layer for paged decode attention (serving hot path).

Implementations, selected per call via ``impl`` (docs/PERF.md §D5):

- ``"kernel"``    — the compiled Pallas TPU kernel (fused single-token
  append + context-proportional online-softmax attention).
- ``"interpret"`` — the SAME kernel through the Pallas interpreter:
  slow, but traces/compiles on any backend — the CPU parity path the
  token-identity tests force.
- ``"ref"``       — the pure-jnp oracle (gather-based), also the fast
  path on CPU where interpret-mode kernels lose to fused XLA.

``"auto"``/None resolves to ``kernel`` on TPU and ``ref`` elsewhere;
``"force"`` (what ``use_kernel=True`` maps to) resolves to ``kernel``
on TPU and ``interpret`` elsewhere.

Pools are PHYSICAL ``[nblk, page, W]`` (kernel.py); ``hs`` is the head
split of the mode reading them. The kernels index that layout directly;
the reference paths reshape it to the logical view ``[nblk, page*hs,
W/(hs*hd), hd]`` (``pool_view``) and back.

These functions are called from inside the compiled serve step (no
inner jit: an extra jit boundary would block XLA from threading the
pool aliasing into the step's donated state buffers).
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

from repro.kernels.paged_attention.kernel import (paged_append_chunk_kernel,
                                                  paged_append_token_kernel,
                                                  paged_attention_kernel)
from repro.kernels.paged_attention.ref import (paged_append_chunk_ref,
                                               paged_append_token_ref,
                                               paged_attention_ref,
                                               paged_attention_with_lse_ref,
                                               paged_mla_attention_ref,
                                               pool_view)

IMPLS = ("kernel", "interpret", "ref")


def resolve_impl(impl: Optional[str] = None) -> str:
    """Resolve an impl request to one of ``kernel|interpret|ref``."""
    on_tpu = jax.default_backend() == "tpu"
    if impl in (None, "auto"):
        return "kernel" if on_tpu else "ref"
    if impl == "force":
        return "kernel" if on_tpu else "interpret"
    if impl not in IMPLS:
        raise ValueError(f"unknown paged-attention impl {impl!r}; valid: "
                         f"{IMPLS + ('auto', 'force')}")
    return impl


def paged_attention(q, k_pool, v_pool, block_table, context_len, *,
                    hs: int = 1, window: Optional[int] = None,
                    softmax_scale: Optional[float] = None,
                    impl: Optional[str] = None):
    """q [B,H,hd]; pools [nblk,page,W] physical; block_table [B,MB];
    context_len [B] -> [B,H,hd]."""
    impl = resolve_impl(impl)
    if impl == "ref":
        hd = q.shape[-1]
        return paged_attention_ref(q, pool_view(k_pool, hs, hd),
                                   pool_view(v_pool, hs, hd), block_table,
                                   context_len, window=window,
                                   softmax_scale=softmax_scale)
    return paged_attention_kernel(
        q, k_pool, v_pool, block_table.astype(jnp.int32),
        context_len.astype(jnp.int32), hs=hs, window=window,
        softmax_scale=softmax_scale, interpret=(impl == "interpret"))


def paged_attention_with_lse(q, k_pool, v_pool, block_table, context_len, *,
                             hs: int = 1, window: Optional[int] = None,
                             softmax_scale: Optional[float] = None,
                             impl: Optional[str] = None):
    """Partial paged decode attention over ONE block segment: returns
    (out [B,H,hd] fp32, lse [B,H] fp32) so the live cross-layout read
    path (§D8) can merge sweeps over differently-tagged segments — and
    across TP ranks — with a flash-style LSE combine. The same entry
    point serves sequence-parallel placements (§D12): a segment there
    is one SHARD's resident token range, the non-owner ranks sweep it
    with zero ``context_len``, and the final cross-shard combine is the
    identical LSE merge — the kernel never needs to know a placement
    tag from a mode tag. Rows with ``context_len == 0`` contribute
    nothing (lse = -inf)."""
    impl = resolve_impl(impl)
    if impl == "ref":
        hd = q.shape[-1]
        return paged_attention_with_lse_ref(
            q, pool_view(k_pool, hs, hd), pool_view(v_pool, hs, hd),
            block_table, context_len, window=window,
            softmax_scale=softmax_scale)
    out, lse = paged_attention_kernel(
        q.astype(jnp.float32), k_pool, v_pool,
        block_table.astype(jnp.int32), context_len.astype(jnp.int32),
        hs=hs, window=window, softmax_scale=softmax_scale, return_lse=True,
        interpret=(impl == "interpret"))
    return out.astype(jnp.float32), lse


def _view_append(ref_fn, pools, vals, slots, hs):
    """Reference append through the logical view of physical pools."""
    hd = vals[0].shape[-1]
    views = tuple(pool_view(p, hs, hd) for p in pools)
    return tuple(v.reshape(p.shape) for v, p in
                 zip(ref_fn(views, vals, slots), pools))


def paged_append_token(pools, vals, slots, *, hs: int = 1,
                       impl: Optional[str] = None):
    """Write each request's new-token row ``vals`` [B, kvh, hd] (the
    hs-view's KV heads) at its view slot ``slots`` [B] (negative parks
    to the reserved scratch slot). pools: tuple of physical
    [nblk, page, W]. The kernel path is an aliased in-place page
    write, never a full-pool scatter."""
    impl = resolve_impl(impl)
    slots = slots.astype(jnp.int32)
    if impl == "ref":
        return _view_append(paged_append_token_ref, pools, vals, slots, hs)
    return paged_append_token_kernel(pools, vals, slots, hs=hs,
                                     interpret=(impl == "interpret"))


def paged_append_chunk(pools, vals, slots, *, hs: int = 1,
                       impl: Optional[str] = None):
    """Chunk form of ``paged_append_token``: vals [B, T, kvh, hd],
    slots [B, T]."""
    impl = resolve_impl(impl)
    slots = slots.astype(jnp.int32)
    if impl == "ref":
        return _view_append(paged_append_chunk_ref, pools, vals, slots, hs)
    return paged_append_chunk_kernel(pools, vals, slots, hs=hs,
                                     interpret=(impl == "interpret"))


def paged_attention_decode(q, k_new, v_new, k_pool, v_pool, slots,
                           block_table, context_len, *, hs: int = 1,
                           window: Optional[int] = None,
                           softmax_scale: Optional[float] = None,
                           impl: Optional[str] = None):
    """Fused single-token KV append + paged decode attention.

    q [B,H,hd]; k_new/v_new [B,KV,hd] (the step's new token, written at
    view ``slots`` [B] before attending); pools [nblk,page,W] physical.
    Returns (out [B,H,hd], k_pool, v_pool)."""
    k_pool, v_pool = paged_append_token((k_pool, v_pool), (k_new, v_new),
                                        slots, hs=hs, impl=impl)
    out = paged_attention(q, k_pool, v_pool, block_table, context_len,
                          hs=hs, window=window, softmax_scale=softmax_scale,
                          impl=impl)
    return out, k_pool, v_pool


def paged_mla_attention_decode(q_cat, entry_new, pool, slots, block_table,
                               context_len, *, R: int,
                               window: Optional[int] = None,
                               softmax_scale: float = 1.0,
                               impl: Optional[str] = None):
    """Absorbed-MLA fused decode over the compressed paged cache.

    q_cat [B,H,W] = [q_nope·W_uk ++ q_pe] (pre-scaled by the caller, so
    ``softmax_scale`` defaults to 1); entry_new [B,W] new-token
    [c_kv ++ k_pe]; pool [nblk,page,W]. Returns (out_c [B,H,R] fp32,
    pool). The kernel path reads the pool as ONE head of width W —
    scores are q_cat·entry and the value read is the compressed entry
    itself (the first R lanes of the kernel output), so the expanded
    [B,Tk,H,·] K/V of the naive path never exists."""
    impl = resolve_impl(impl)
    slots = slots.astype(jnp.int32)
    if impl == "ref":
        (pool,) = paged_append_token_ref((pool,), (entry_new,), slots)
        out = paged_mla_attention_ref(q_cat, pool, block_table, context_len,
                                      R=R, window=window,
                                      softmax_scale=softmax_scale)
        return out, pool
    interp = impl == "interpret"
    (pool,) = paged_append_token_kernel((pool,), (entry_new,), slots,
                                        interpret=interp)
    out = paged_attention_kernel(
        q_cat.astype(jnp.float32), pool, pool,
        block_table.astype(jnp.int32), context_len.astype(jnp.int32),
        window=window, softmax_scale=softmax_scale, interpret=interp)
    return out[..., :R], pool


__all__ = ["paged_append_chunk", "paged_append_token", "paged_attention",
           "paged_attention_decode", "paged_attention_with_lse",
           "paged_mla_attention_decode", "paged_attention_ref",
           "resolve_impl"]
