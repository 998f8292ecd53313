"""Pallas kernels vs pure-jnp oracles: shape/dtype sweeps, interpret mode
(deliverable c: per-kernel allclose)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

key = jax.random.key(7)


def tols(dtype):
    return dict(rtol=3e-2, atol=3e-2) if dtype == jnp.bfloat16 \
        else dict(rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("B,H,KV,hd,page,nblk,MB,window", [
    (4, 8, 8, 128, 16, 32, 8, None),    # MHA
    (4, 8, 2, 128, 16, 32, 8, None),    # GQA
    (2, 4, 1, 64, 8, 16, 4, 32),        # MQA + sliding window
    (3, 16, 4, 128, 32, 64, 6, None),
    (1, 2, 2, 64, 8, 8, 2, 8),
])
def test_paged_attention(B, H, KV, hd, page, nblk, MB, window, dtype):
    from repro.kernels.paged_attention.ops import (paged_attention,
                                                   paged_attention_ref)
    ks = jax.random.split(key, 5)
    q = jax.random.normal(ks[0], (B, H, hd), dtype)
    kp = jax.random.normal(ks[1], (nblk, page, KV, hd), dtype)
    vp = jax.random.normal(ks[2], (nblk, page, KV, hd), dtype)
    bt = jax.random.randint(ks[3], (B, MB), 0, nblk)
    cl = jax.random.randint(ks[4], (B,), 1, MB * page + 1)
    # impl="interpret" forces the Pallas kernel through the interpreter
    # (the auto dispatch picks the jnp ref on CPU — that would compare
    # the oracle against itself)
    out = paged_attention(q, _phys(kp), _phys(vp), bt, cl, window=window,
                          impl="interpret")
    ref = paged_attention_ref(q, kp, vp, bt, cl, window=window)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref, np.float32), **tols(dtype))


def _phys(view):
    """The stored pool layout [nblk, page, KV*hd] of a head-split-1 view
    [nblk, page, KV, hd] (the same bytes, row-major)."""
    return view.reshape(view.shape[0], view.shape[1], -1)


def _disjoint_tables(k, B, MB, nblk):
    """Per-request block tables with DISJOINT block ids (the serving
    invariant: one adaptor never shares a block between requests), so
    no two rows can target the same write slot — the fused append
    kernel's documented precondition. Excludes the scratch block."""
    assert B * MB <= nblk - 1
    return jax.random.permutation(k, nblk - 1)[:B * MB].reshape(B, MB)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("B,H,KV,hd,page,nblk,MB,window", [
    (4, 8, 2, 128, 16, 64, 8, None),    # GQA
    (2, 4, 1, 64, 8, 16, 4, 32),        # MQA + sliding window
    (3, 16, 4, 128, 32, 64, 6, None),
])
def test_paged_attention_decode_fused_append(B, H, KV, hd, page, nblk, MB,
                                             window, dtype):
    """The fused single-token append + attend kernel path must match
    the unfused reference (scatter append, then oracle attention),
    including a parked (slot<0) row and pool write-back."""
    from repro.kernels.paged_attention.ops import paged_attention_decode
    from repro.kernels.paged_attention.ref import (paged_append_token_ref,
                                                   paged_attention_ref)
    ks = jax.random.split(key, 7)
    q = jax.random.normal(ks[0], (B, H, hd), dtype)
    kp = jax.random.normal(ks[1], (nblk, page, KV, hd), dtype)
    vp = jax.random.normal(ks[2], (nblk, page, KV, hd), dtype)
    kn = jax.random.normal(ks[3], (B, KV, hd), dtype)
    vn = jax.random.normal(ks[4], (B, KV, hd), dtype)
    bt = _disjoint_tables(ks[5], B, MB, nblk)
    cl = jax.random.randint(ks[6], (B,), 1, MB * page + 1)
    slots = (bt[jnp.arange(B), (cl - 1) // page] * page
             + (cl - 1) % page).astype(jnp.int32)
    slots = slots.at[0].set(-1)  # parked row -> scratch, never read
    out, ko, vo = paged_attention_decode(q, kn, vn, _phys(kp), _phys(vp),
                                         slots, bt, cl, window=window,
                                         impl="interpret")
    kr, vr = paged_append_token_ref((kp, vp), (kn, vn), slots)
    ref = paged_attention_ref(q, kr, vr, bt, cl, window=window)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref, np.float32), **tols(dtype))
    np.testing.assert_array_equal(np.asarray(ko), np.asarray(_phys(kr)))
    np.testing.assert_array_equal(np.asarray(vo), np.asarray(_phys(vr)))


@pytest.mark.parametrize("B,H,R,Rr,page,nblk,MB", [
    (2, 8, 32, 16, 8, 16, 4),
    (3, 4, 64, 32, 16, 32, 6),
])
def test_paged_mla_attention_decode_kernel_vs_ref(B, H, R, Rr, page, nblk,
                                                  MB):
    """Absorbed-MLA fused decode: the KV=1 kernel view over the
    compressed pool matches the jnp oracle."""
    from repro.kernels.paged_attention.ops import paged_mla_attention_decode
    W = R + Rr
    ks = jax.random.split(key, 5)
    qc = jax.random.normal(ks[0], (B, H, W))
    pool = jax.random.normal(ks[1], (nblk, page, W))
    en = jax.random.normal(ks[2], (B, W))
    bt = _disjoint_tables(ks[3], B, MB, nblk)
    cl = jax.random.randint(ks[4], (B,), 1, MB * page + 1)
    slots = (bt[jnp.arange(B), (cl - 1) // page] * page
             + (cl - 1) % page).astype(jnp.int32)
    scale = W ** -0.5
    oi, pi = paged_mla_attention_decode(qc, en, pool, slots, bt, cl, R=R,
                                        softmax_scale=scale,
                                        impl="interpret")
    orf, prf = paged_mla_attention_decode(qc, en, pool, slots, bt, cl, R=R,
                                          softmax_scale=scale, impl="ref")
    assert oi.shape == (B, H, R)
    np.testing.assert_allclose(np.asarray(oi), np.asarray(orf),
                               rtol=2e-5, atol=2e-5)
    np.testing.assert_array_equal(np.asarray(pi), np.asarray(prf))


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("B,T,KV,w,page,nblk,MB", [
    (3, 8, 2, 64, 4, 32, 8),
    (2, 16, 1, 48, 8, 24, 2),        # MLA-ish: KV=1, odd width
])
def test_paged_append_chunk(B, T, KV, w, page, nblk, MB, dtype):
    """The fused multi-token chunk append (grid (B,T), aliased row
    writes) must match the scatter oracle, including a parked (slot<0)
    row and chunk positions straddling block boundaries."""
    from repro.kernels.paged_attention.kernel import paged_append_chunk_kernel
    from repro.kernels.paged_attention.ref import paged_append_chunk_ref
    ks = jax.random.split(key, 4)
    kp = jax.random.normal(ks[0], (nblk, page, KV, w), dtype)
    kn = jax.random.normal(ks[1], (B, T, KV, w), dtype)
    bt = _disjoint_tables(ks[2], B, MB, nblk)
    prior = jax.random.randint(ks[3], (B,), 0, MB * page - T + 1)
    pos = prior[:, None] + jnp.arange(T)[None]
    slots = (bt[jnp.arange(B)[:, None], pos // page] * page
             + pos % page).astype(jnp.int32)
    slots = slots.at[0, -1].set(-1)  # parked row -> scratch, never read
    (ko,) = paged_append_chunk_kernel((_phys(kp),), (kn,), slots,
                                      interpret=True)
    (kr,) = paged_append_chunk_ref((kp,), (kn,), slots)
    np.testing.assert_array_equal(np.asarray(ko), np.asarray(_phys(kr)))


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("B,T,H,KV,hd,page,nblk,MB,window,priors", [
    (3, 8, 8, 2, 64, 4, 32, 8, None, (0, 5, 13)),   # GQA, straddling
    (2, 16, 4, 1, 64, 8, 32, 3, None, (0, 7)),      # MQA, fresh + prior
    (2, 8, 4, 4, 32, 4, 24, 6, 6, (3, 9)),          # MHA + window
    (2, 8, 4, 1, 48, 8, 16, 2, None, (0, 2)),       # MLA-ish odd width
    (1, 12, 2, 2, 32, 4, 16, 4, None, (1,)),        # ragged T -> padding
])
def test_paged_flash_prefill(B, T, H, KV, hd, page, nblk, MB, window,
                             priors, dtype):
    """Paged flash-prefill (fused chunk append + one causal sweep over
    the scalar-prefetched block table) vs the gathered oracle: GQA/MQA/
    MLA-width heads, windowed, chunks straddling block boundaries, and
    nonzero prior context."""
    from repro.kernels.flash_prefill.ops import paged_flash_prefill
    ks = jax.random.split(key, 6)
    q = jax.random.normal(ks[0], (B, T, H, hd), dtype)
    kn = jax.random.normal(ks[1], (B, T, KV, hd), dtype)
    vn = jax.random.normal(ks[2], (B, T, KV, hd), dtype)
    kp = jax.random.normal(ks[3], (nblk, page, KV, hd), dtype)
    vp = jax.random.normal(ks[4], (nblk, page, KV, hd), dtype)
    bt = _disjoint_tables(ks[5], B, MB, nblk)
    prior = jnp.asarray(priors, jnp.int32)
    pos = prior[:, None] + jnp.arange(T)[None]
    slots = (bt[jnp.arange(B)[:, None], pos // page] * page
             + pos % page).astype(jnp.int32)
    kp, vp = _phys(kp), _phys(vp)
    oi, ki, vi = paged_flash_prefill(q, kn, vn, kp, vp, slots, bt, prior,
                                     window=window, blk_q=8,
                                     impl="interpret")
    orf, krf, vrf = paged_flash_prefill(q, kn, vn, kp, vp, slots, bt,
                                        prior, window=window, impl="ref")
    np.testing.assert_allclose(np.asarray(oi, np.float32),
                               np.asarray(orf, np.float32), **tols(dtype))
    np.testing.assert_array_equal(np.asarray(ki), np.asarray(krf))
    np.testing.assert_array_equal(np.asarray(vi), np.asarray(vrf))


@pytest.mark.parametrize("op", ["decode", "decode_lse", "append_token",
                                "append_chunk", "prefill", "prefill_lse"])
@pytest.mark.parametrize("hs", [2, 4])
def test_head_split_view_kernels(hs, op):
    """A merge reads the physical pool [nblk, page, W] under head split
    hs as [nblk, page*hs, W/(hs*hd), hd] (view token t*hs + j = lane
    group j of row t). Every kernel indexes that view in place and must
    match the oracles run on the explicitly reshaped view."""
    from repro.kernels.flash_prefill.ops import (paged_flash_prefill,
                                                 paged_prefill_sweep_with_lse)
    from repro.kernels.paged_attention import ops
    from repro.kernels.paged_attention.ref import pool_view
    B, KV, rep, hd, page, nblk, MB, T = 3, 8, 2, 32, 4, 40, 3, 8
    kvv, cap = KV // hs, page * hs
    H = kvv * rep
    ks = jax.random.split(key, 8)
    kp = jax.random.normal(ks[0], (nblk, page, KV * hd))
    vp = jax.random.normal(ks[1], (nblk, page, KV * hd))
    bt = _disjoint_tables(ks[2], B, MB, nblk)
    run = {}
    for impl in ("interpret", "ref"):
        if op.startswith("decode"):
            q = jax.random.normal(ks[3], (B, H, hd))
            cl = jnp.asarray([1, cap + 3, MB * cap], jnp.int32)
            fn = ops.paged_attention_with_lse if op == "decode_lse" \
                else ops.paged_attention
            run[impl] = fn(q, kp, vp, bt, cl, hs=hs, impl=impl)
        elif op.startswith("append"):
            T_ = 1 if op == "append_token" else T
            vals = jax.random.normal(ks[4], (B, T_, kvv, hd))
            pos = jnp.asarray([0, 5, cap + 1], jnp.int32)[:, None] + \
                jnp.arange(T_)[None]
            slots = (bt[jnp.arange(B)[:, None], pos // cap] * cap
                     + pos % cap).astype(jnp.int32)
            slots = slots.at[0, -1].set(-1)
            if op == "append_token":
                run[impl] = ops.paged_append_token(
                    (kp,), (vals[:, 0],), slots[:, 0], hs=hs, impl=impl)
            else:
                run[impl] = ops.paged_append_chunk((kp,), (vals,), slots,
                                                   hs=hs, impl=impl)
        else:
            q = jax.random.normal(ks[5], (B, T, H, hd))
            prior = jnp.asarray([0, 3, cap + 2], jnp.int32)
            if op == "prefill_lse":
                run[impl] = paged_prefill_sweep_with_lse(
                    q, kp, vp, bt, prior, hs=hs, prior_only=True,
                    impl=impl)
            else:
                kn = jax.random.normal(ks[6], (B, T, kvv, hd))
                vn = jax.random.normal(ks[7], (B, T, kvv, hd))
                pos = prior[:, None] + jnp.arange(T)[None]
                slots = (bt[jnp.arange(B)[:, None], pos // cap] * cap
                         + pos % cap).astype(jnp.int32)
                run[impl] = paged_flash_prefill(
                    q, kn, vn, kp, vp, slots, bt, prior, hs=hs, blk_q=8,
                    impl=impl)
    assert pool_view(kp, hs, hd).shape == (nblk, cap, kvv, hd)
    for a, b in zip(jax.tree.leaves(run["interpret"]),
                    jax.tree.leaves(run["ref"])):
        a, b = np.asarray(a), np.asarray(b)
        if op.startswith("append"):
            np.testing.assert_array_equal(a, b)
        else:
            ok = b > -1e30     # lse of rows with nothing live: NEG_INF
            np.testing.assert_allclose(np.where(ok, a, 0), np.where(ok, b, 0),
                                       rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("B,T,H,KV,hd,window,blk", [
    (2, 128, 4, 4, 64, None, 64),
    (2, 100, 4, 2, 64, None, 32),    # ragged T -> padding path
    (1, 256, 8, 1, 128, 64, 64),     # MQA + window
    (2, 64, 2, 2, 32, 16, 32),
])
def test_flash_prefill(B, T, H, KV, hd, window, blk, dtype):
    from repro.kernels.flash_prefill.ops import (flash_prefill,
                                                 flash_prefill_ref)
    ks = jax.random.split(key, 3)
    q = jax.random.normal(ks[0], (B, T, H, hd), dtype)
    k = jax.random.normal(ks[1], (B, T, KV, hd), dtype)
    v = jax.random.normal(ks[2], (B, T, KV, hd), dtype)
    out = flash_prefill(q, k, v, window=window, blk=blk)
    ref = flash_prefill_ref(q, k, v, window=window)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref, np.float32), **tols(dtype))


@pytest.mark.parametrize("Bs,T,H,hd,S,chunk", [
    (2, 64, 4, 32, 16, 32),
    (1, 96, 2, 64, 128, 32),   # T not a multiple of chunk after min()
    (2, 128, 8, 64, 64, 64),
])
def test_ssd_scan(Bs, T, H, hd, S, chunk):
    from repro.kernels.ssd_scan.ops import ssd_scan, ssd_scan_ref
    ks = jax.random.split(key, 5)
    x = jax.random.normal(ks[0], (Bs, T, H, hd), jnp.float32)
    dt = jax.nn.softplus(jax.random.normal(ks[1], (Bs, T, H)))
    A = -jnp.exp(jax.random.normal(ks[2], (H,)))
    B = jax.random.normal(ks[3], (Bs, T, S)) * 0.5
    C = jax.random.normal(ks[4], (Bs, T, S)) * 0.5
    y, hT = ssd_scan(x, dt, A, B, C, chunk=chunk)
    yr, hr = ssd_scan_ref(x, dt, A, B, C, jnp.zeros((Bs, H, hd, S)))
    np.testing.assert_allclose(np.asarray(y), np.asarray(yr),
                               rtol=1e-4, atol=1e-4)
    np.testing.assert_allclose(np.asarray(hT), np.asarray(hr),
                               rtol=1e-4, atol=1e-4)


def test_ssd_matches_model_chunked_form():
    """The kernel, the model's chunked form, and the sequential oracle
    agree (three-way)."""
    from repro.kernels.ssd_scan.ops import ssd_scan, ssd_scan_ref
    from repro.models.mamba2 import ssd_chunked
    ks = jax.random.split(key, 5)
    Bs, T, H, hd, S = 2, 64, 4, 32, 16
    x = jax.random.normal(ks[0], (Bs, T, H, hd), jnp.float32)
    dt = jax.nn.softplus(jax.random.normal(ks[1], (Bs, T, H)))
    A = -jnp.exp(jax.random.normal(ks[2], (H,)))
    B = jax.random.normal(ks[3], (Bs, T, S)) * 0.5
    C = jax.random.normal(ks[4], (Bs, T, S)) * 0.5
    h0 = jnp.zeros((Bs, H, hd, S))
    y1, h1 = ssd_scan(x, dt, A, B, C, chunk=32)
    y2, h2 = ssd_chunked(x, dt, A, B, C, h0, chunk=32)
    y3, h3 = ssd_scan_ref(x, dt, A, B, C, h0)
    np.testing.assert_allclose(np.asarray(y1), np.asarray(y3), rtol=1e-4,
                               atol=1e-4)
    np.testing.assert_allclose(np.asarray(y2), np.asarray(y3), rtol=1e-4,
                               atol=1e-4)


@pytest.mark.parametrize("B,T,C,bt,bc", [
    (2, 64, 128, 32, 64),
    (1, 100, 96, 32, 32),   # ragged both dims
    (2, 256, 256, 128, 128),
])
def test_rglru_scan(B, T, C, bt, bc):
    from repro.kernels.rglru_scan.ops import rglru_scan, rglru_scan_ref
    ks = jax.random.split(key, 2)
    a = jax.nn.sigmoid(jax.random.normal(ks[0], (B, T, C)))
    g = jax.random.normal(ks[1], (B, T, C)) * 0.5
    y, hT = rglru_scan(a, g, blk_t=bt, blk_c=bc)
    yr, hr = rglru_scan_ref(a, g, jnp.zeros((B, C)))
    np.testing.assert_allclose(np.asarray(y), np.asarray(yr),
                               rtol=2e-5, atol=2e-5)
    np.testing.assert_allclose(np.asarray(hT), np.asarray(hr),
                               rtol=2e-5, atol=2e-5)
