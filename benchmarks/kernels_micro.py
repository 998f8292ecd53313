"""Kernel microbenchmarks: ``name,us_per_call,derived`` rows.

us_per_call: wall-clock per call on the device this runs on (the jnp
oracles, plus the Pallas kernels where noted). derived: the roofline
time of the kernel's working set on THAT device, from its published
peaks (``serving/hardware.peaks_for``); a device without published
peaks (the CPU) is an error, not a stand-in for the chip.
"""
from __future__ import annotations

import time

import jax
import jax.numpy as jnp

from benchmarks.common import csv_row
from repro.serving.hardware import peaks_for


def _time(fn, *args, iters=5):
    fn(*args)  # compile
    t0 = time.perf_counter()
    for _ in range(iters):
        jax.block_until_ready(fn(*args))
    return (time.perf_counter() - t0) / iters * 1e6


def run():
    hw = peaks_for(jax.devices()[0].device_kind)
    rows = []
    key = jax.random.key(0)

    # paged decode attention: B=8, H=32/16=2 local heads, 32k ctx
    from repro.kernels.paged_attention.ref import paged_attention_ref
    B, H, KV, hd, page, nblk = 8, 2, 1, 128, 16, 2048
    ks = jax.random.split(key, 5)
    q = jax.random.normal(ks[0], (B, H, hd), jnp.float32)
    kp = jax.random.normal(ks[1], (nblk, page, KV, hd), jnp.float32)
    vp = jax.random.normal(ks[2], (nblk, page, KV, hd), jnp.float32)
    bt = jax.random.randint(ks[3], (B, nblk // B), 0, nblk)
    cl = jnp.full((B,), (nblk // B) * page, jnp.int32)
    us = _time(jax.jit(lambda *a: paged_attention_ref(*a)), q, kp, vp, bt,
               cl)
    hbm = 2 * nblk * page * KV * hd * 2  # k+v pool bytes (bf16 target)
    rows.append(csv_row("kernels", "paged_attention/8x32k_ref", f"{us:.0f}",
                        f"roofline_us={hbm / hw.hbm_bw * 1e6:.0f}"))

    # flash prefill: 2 x 2048 x 4 heads
    from repro.kernels.flash_prefill.ref import flash_prefill_ref
    B2, T, H2, hd2 = 2, 2048, 4, 128
    q2 = jax.random.normal(ks[0], (B2, T, H2, hd2), jnp.float32)
    k2 = jax.random.normal(ks[1], (B2, T, H2, hd2), jnp.float32)
    v2 = jax.random.normal(ks[2], (B2, T, H2, hd2), jnp.float32)
    us = _time(jax.jit(lambda *a: flash_prefill_ref(*a)), q2, k2, v2)
    fl = 4 * B2 * H2 * T * T / 2 * hd2
    rows.append(csv_row("kernels", "flash_prefill/2x2048_ref", f"{us:.0f}",
                        f"roofline_us={fl / hw.peak_flops_bf16 * 1e6:.1f}"))

    # the SAME Pallas kernel through the interpreter (small shape — the
    # interpreter re-traces the body per grid step, so this times the
    # kernel program itself rather than only the jnp oracle)
    from repro.kernels.flash_prefill.ops import flash_prefill
    Ti = 256
    us = _time(lambda *a: flash_prefill(*a, blk=128),
               q2[:1, :Ti], k2[:1, :Ti], v2[:1, :Ti], iters=3)
    fli = 4 * 1 * H2 * Ti * Ti / 2 * hd2
    rows.append(csv_row(
        "kernels", "flash_prefill/1x256_interp_kernel", f"{us:.0f}",
        f"roofline_us={fli / hw.peak_flops_bf16 * 1e6:.2f}"))

    # paged flash-prefill (chunked prefill over the pool, §Perf D6):
    # interpret-mode kernel vs jnp oracle on one chunk with prior context
    from repro.kernels.flash_prefill.ops import paged_flash_prefill
    Bp, Tc, KVp, hdp, page, nblk = 2, 128, 2, 128, 16, 64
    MBp = nblk // Bp // 2
    qp = jax.random.normal(ks[0], (Bp, Tc, H2, hdp), jnp.float32)
    knp = jax.random.normal(ks[1], (Bp, Tc, KVp, hdp), jnp.float32)
    vnp = jax.random.normal(ks[2], (Bp, Tc, KVp, hdp), jnp.float32)
    # physical pools [nblk, page, KV*hd]
    kpp = jax.random.normal(ks[3], (nblk, page, KVp * hdp), jnp.float32)
    vpp = jax.random.normal(ks[4], (nblk, page, KVp * hdp), jnp.float32)
    btp = jax.random.permutation(ks[3], nblk - 1)[:Bp * MBp].reshape(Bp,
                                                                     MBp)
    prior = jnp.full((Bp,), 64, jnp.int32)
    posp = prior[:, None] + jnp.arange(Tc)[None]
    slotp = (btp[jnp.arange(Bp)[:, None], posp // page] * page
             + posp % page).astype(jnp.int32)
    hbm_p = 2 * Bp * MBp * page * KVp * hdp * 2 \
        + 4 * Bp * Tc * KVp * hdp * 2
    for impl in ("interpret", "ref"):
        us = _time(lambda *a, i=impl: paged_flash_prefill(
            *a, window=None, impl=i), qp, knp, vnp, kpp, vpp, slotp, btp,
            prior, iters=3)
        rows.append(csv_row(
            "kernels", f"paged_flash_prefill/2x128c_{impl}", f"{us:.0f}",
            f"roofline_us={hbm_p / hw.hbm_bw * 1e6:.1f}"))

    # ssd scan: 2 x 2048 x 8 heads
    from repro.kernels.ssd_scan.ref import ssd_scan_ref
    Bs, Ts, Hs, hds, S = 2, 2048, 8, 64, 128
    x = jax.random.normal(ks[0], (Bs, Ts, Hs, hds), jnp.float32)
    dt = jax.nn.softplus(jax.random.normal(ks[1], (Bs, Ts, Hs)))
    A = -jnp.exp(jax.random.normal(ks[2], (Hs,)))
    Bm = jax.random.normal(ks[3], (Bs, Ts, S)) * 0.5
    Cm = jax.random.normal(ks[4], (Bs, Ts, S)) * 0.5
    h0 = jnp.zeros((Bs, Hs, hds, S))
    us = _time(jax.jit(lambda *a: ssd_scan_ref(*a)), x, dt, A, Bm, Cm, h0)
    fl = 6 * Bs * Ts * Hs * hds * S
    rows.append(csv_row("kernels", "ssd_scan/2x2048_ref", f"{us:.0f}",
                        f"roofline_us={fl / hw.peak_flops_bf16 * 1e6:.2f}"))

    # rglru scan: 2 x 2048 x 1024 channels
    from repro.kernels.rglru_scan.ref import rglru_scan_ref
    Br, Tr, Cr = 2, 2048, 1024
    a = jax.nn.sigmoid(jax.random.normal(ks[0], (Br, Tr, Cr)))
    g = jax.random.normal(ks[1], (Br, Tr, Cr)) * 0.5
    us = _time(jax.jit(lambda *a_: rglru_scan_ref(*a_)), a, g,
               jnp.zeros((Br, Cr)))
    hbm = 3 * Br * Tr * Cr * 2
    rows.append(csv_row("kernels", "rglru_scan/2x2048_ref", f"{us:.0f}",
                        f"roofline_us={hbm / hw.hbm_bw * 1e6:.1f}"))
    return rows


if __name__ == "__main__":
    for r in run():
        print(r)
