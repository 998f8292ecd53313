"""Plain reference of the dense decoder the configurations describe.

Straightforward ``jax.numpy`` in float32 at ``highest`` matmul
precision: no cache, no paging, no kernels, no batching. It follows the
configuration file (the published architecture, with the departures the
file lists under ``reduced``) and imports nothing of the program; its
weights are the benchmark's own (``weights.named``).

Per layer: pre-norm (RMSNorm), attention with RoPE on the first
``partial_rotary_factor`` of each head (rotate-half form), optional
per-head RMS qk-norm before RoPE, grouped KV heads, causal softmax;
pre-norm SwiGLU MLP; a final RMSNorm and the vocabulary projection
(the token embedding, transposed, where tied).

``quant="fp8"`` is the control: the same arithmetic with every matmul
operand rounded to float8 e4m3 (scaled per row or per output column to
the format's range), the precision a later change might be tempted to
serve bf16 weights in.
"""
from __future__ import annotations

from functools import partial
from typing import Dict, Optional

import jax
import jax.numpy as jnp
from jax import lax

F8_MAX = 448.0          # largest finite float8_e4m3fn


def _q8(x, axis: int):
    """Round to float8 e4m3 with a scale per slice along ``axis``."""
    s = jnp.max(jnp.abs(x), axis=axis, keepdims=True) / F8_MAX
    s = jnp.where(s > 0, s, 1.0)
    return (x / s).astype(jnp.float8_e4m3fn).astype(jnp.float32) * s


def _mm(a, b, quant: Optional[str]):
    """a [..., k] @ b [k, n] in float32."""
    if quant == "fp8":
        a, b = _q8(a, -1), _q8(b, 0)
    return jnp.matmul(a, b, precision=lax.Precision.HIGHEST)


def _rms(x, w, eps):
    x = x * lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps)
    return x * w


def _rope(x, pos, theta, rot):
    """x [T, H, hd]; rotate the first ``rot`` dims of each head."""
    if rot == 0:
        return x
    freqs = 1.0 / (theta ** (jnp.arange(0, rot, 2, dtype=jnp.float32) / rot))
    ang = pos[:, None].astype(jnp.float32) * freqs          # [T, rot/2]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    xr, xp = x[..., :rot], x[..., rot:]
    x1, x2 = jnp.split(xr, 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)
    return jnp.concatenate([out, xp], axis=-1)


def _attention(q, k, v, n_valid, quant, q_block: int):
    """Causal softmax attention over the first ``n_valid`` keys.
    q [T, H, hd], k/v [T, KV, hd]; query rows in blocks of ``q_block``."""
    T, H, hd = q.shape
    KV = k.shape[1]
    rep = H // KV
    kk = jnp.repeat(k, rep, axis=1)
    vv = jnp.repeat(v, rep, axis=1)
    kpos = jnp.arange(T)
    outs = []
    for lo in range(0, T, q_block):
        qb = q[lo:lo + q_block]                          # [Q, H, hd]
        qh, kh = jnp.swapaxes(qb, 0, 1), jnp.swapaxes(kk, 0, 1)
        if quant == "fp8":
            qh, kh = _q8(qh, -1), _q8(kh, -1)
        s = jnp.einsum("hqd,hkd->hqk", qh, kh,
                       precision=lax.Precision.HIGHEST) * hd ** -0.5
        qpos = lo + jnp.arange(qb.shape[0])
        mask = (kpos[None, :] <= qpos[:, None]) & (kpos[None, :] < n_valid)
        s = jnp.where(mask[None], s, -1e30)
        p = jax.nn.softmax(s, axis=-1)
        vh = jnp.swapaxes(vv, 0, 1)
        if quant == "fp8":
            p, vh = _q8(p, -1), _q8(vh, 1)
        o = jnp.einsum("hqk,hkd->qhd", p, vh,
                       precision=lax.Precision.HIGHEST)
        outs.append(o)
    return jnp.concatenate(outs, axis=0)


@partial(jax.jit, static_argnames=("cfg_key", "quant", "out_n", "q_block"))
def _forward(w, tokens, n_valid, out_lo, *, cfg_key, quant, out_n,
             q_block):
    cfg = dict(cfg_key)
    H, KV = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    hd = cfg["head_dim"]
    eps = cfg["rms_norm_eps"]
    rot = int(hd * cfg["partial_rotary_factor"])
    T = tokens.shape[0]
    f32 = partial(jnp.asarray, dtype=jnp.float32)
    x = f32(w["tok"])[tokens]
    pos = jnp.arange(T)

    def layer(x, lw):
        lw = jax.tree.map(f32, lw)
        h = _rms(x, lw["norm1"], eps)
        q = _mm(h, lw["wq"], quant).reshape(T, H, hd)
        k = _mm(h, lw["wk"], quant).reshape(T, KV, hd)
        v = _mm(h, lw["wv"], quant).reshape(T, KV, hd)
        if "q_norm" in lw:
            q = _rms(q, lw["q_norm"], eps)
            k = _rms(k, lw["k_norm"], eps)
        q = _rope(q, pos, cfg["rope_theta"], rot)
        k = _rope(k, pos, cfg["rope_theta"], rot)
        o = _attention(q, k, v, n_valid, quant, q_block).reshape(T, H * hd)
        x = x + _mm(o, lw["wo"], quant)
        h2 = _rms(x, lw["norm2"], eps)
        g = jax.nn.silu(_mm(h2, lw["w_gate"], quant))
        x = x + _mm(g * _mm(h2, lw["w_up"], quant), lw["w_down"], quant)
        return x, None

    x, _ = lax.scan(layer, x, w["layers"])
    x = lax.dynamic_slice_in_dim(x, out_lo, out_n, axis=0)
    x = _rms(x, f32(w["norm_f"]), eps)
    head = f32(w["tok"]).T if w["head"] is None else f32(w["head"])
    return _mm(x, head, quant)


def _cfg_key(cfg: Dict) -> tuple:
    hd = cfg.get("head_dim") or cfg["hidden_size"] // cfg["num_attention_heads"]
    if cfg.get("norm_type", "rms") != "rms":
        raise ValueError("the reference implements RMSNorm only")
    if cfg.get("use_qkv_bias") or cfg.get("attention_bias"):
        raise ValueError("the reference has no attention biases")
    eps = cfg.get("rms_norm_eps", cfg.get("layer_norm_eps"))
    return tuple(sorted({
        "num_attention_heads": cfg["num_attention_heads"],
        "num_key_value_heads": cfg["num_key_value_heads"],
        "head_dim": hd, "rms_norm_eps": float(eps),
        "partial_rotary_factor": float(cfg.get("partial_rotary_factor",
                                               1.0)),
        "rope_theta": float(cfg["rope_theta"])}.items()))


def bucket(n: int, lo: int = 512) -> int:
    """Padded sequence length: a power of two, at least ``lo``, so a
    whole run compiles the reference for a handful of lengths."""
    b = lo
    while b < n:
        b *= 2
    return b


def logits_at(w: Dict, cfg: Dict, seq, out_lo: int, out_n: int,
              quant: Optional[str] = None, q_block: int = 512,
              lo: int = 512):
    """Logits [out_n, vocab] of positions ``out_lo .. out_lo+out_n-1`` of
    the token sequence ``seq`` (each predicting the next token)."""
    import numpy as np
    n = len(seq)
    T = bucket(max(n, out_lo + out_n), lo)
    toks = np.zeros((T,), np.int32)
    toks[:n] = seq
    with jax.default_matmul_precision("highest"):
        return _forward(w, jnp.asarray(toks), jnp.int32(n),
                        jnp.int32(out_lo), cfg_key=_cfg_key(cfg),
                        quant=quant, out_n=out_n,
                        q_block=min(q_block, T))
