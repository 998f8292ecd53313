"""Public model API: build any assigned architecture from its ArchConfig.

``Model`` is pure-functional: ``init`` makes the param pytree (stacked per
scan group), ``forward`` runs train/prefill/decode with a pluggable cache
backend and a TPContext (single-device, GSPMD-train, or flying-serving
shard_map). State pytrees (paged pools / recurrent states / cross-KV) are
inputs and outputs — persistence is the engine's job (core/engine.py).
"""
from __future__ import annotations

import math
from dataclasses import dataclass
from functools import cached_property, partial
from typing import Any, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
from jax import lax

from repro.configs.base import ArchConfig
from repro.core.views import SINGLE, TPContext
from repro.models import transformer as tfm
from repro.models.attention import mla_cache_width
from repro.models.common import sinusoidal_positions
from repro.models.mamba2 import dims as mamba_dims
from repro.models.rglru import CONV_W as RG_CONV_W
from repro.models.rglru import width as rg_width


def _stack(trees: Sequence[Any]):
    return jax.tree.map(lambda *xs: jnp.stack(xs), *trees)


@dataclass
class Model:
    cfg: ArchConfig
    dtype: Any = jnp.bfloat16
    # groups with <= unroll layers run as an inlined python loop instead of
    # lax.scan — the roofline probes use this (XLA cost analysis counts a
    # scan body once regardless of trip count)
    unroll: int = 1
    # rematerialize layer activations in the backward pass (training)
    remat: bool = True
    # thread layer states through scan as an indexed CARRY instead of
    # xs/ys: the while-loop carry aliases in place, so per-layer pool
    # updates stop copying the whole pool slice (§Perf A2)
    states_as_carry: bool = False

    @cached_property
    def plan(self):
        return tfm.stack_plan(self.cfg)

    # ------------------------------------------------------------------
    # params
    # ------------------------------------------------------------------
    def init(self, key) -> Dict[str, Any]:
        cfg = self.cfg
        keys = jax.random.split(key, len(self.plan) + 2)
        params: Dict[str, Any] = {
            "embed": tfm.init_embed(keys[0], cfg, self.dtype)}
        if cfg.enc_dec is not None:
            params["encoder"] = tfm.init_encoder(keys[1], cfg, self.dtype)
        groups = []
        for gi, (kind_seq, n) in enumerate(self.plan):
            gkeys = jax.random.split(keys[2 + gi], n * len(kind_seq))
            stacked = []
            for si, kind in enumerate(kind_seq):
                per = [tfm.init_layer(gkeys[li * len(kind_seq) + si], cfg,
                                      kind, self.dtype) for li in range(n)]
                stacked.append(_stack(per))
            groups.append(tuple(stacked))
        params["groups"] = groups
        return params

    def param_specs(self):
        return jax.eval_shape(lambda: self.init(jax.random.key(0)))

    # ------------------------------------------------------------------
    # per-layer cache/state construction
    # ------------------------------------------------------------------
    def layer_state(self, kind, *, ctx: TPContext, batch: int,
                    num_blocks: int, page: int, enc_frames: int = 0,
                    mode: str = "decode", make=jnp.zeros):
        """One (unstacked) layer's state pytree for prefill/decode."""
        cfg = self.cfg
        mixer, _ = kind
        hd = cfg.resolved_head_dim
        st: Dict[str, Any] = {}
        if mixer in ("gqa", "gqa_win"):
            # physical pool rows: all local KV heads of one token
            W = ctx.local_units(cfg.num_kv_heads) * hd
            st["mixer"] = (make((num_blocks, page, W), self.dtype),
                           make((num_blocks, page, W), self.dtype))
        elif mixer == "mla":
            w = mla_cache_width(cfg)
            st["mixer"] = (make((num_blocks, page, w), self.dtype),)
        elif mixer == "mamba":
            d_in, nh, mhd, S, cw = mamba_dims(cfg)
            nhl = nh // ctx.compute_shards(nh)
            st["mixer"] = (make((batch, cw - 1, nhl * mhd + 2 * S),
                                self.dtype),
                           make((batch, nhl, mhd, S), jnp.float32))
        elif mixer == "rglru":
            w = rg_width(cfg)
            wl = w // ctx.compute_shards(w)
            st["mixer"] = (make((batch, RG_CONV_W - 1, wl), self.dtype),
                           make((batch, wl), jnp.float32))
        if cfg.enc_dec is not None and mixer in ("gqa", "gqa_win"):
            KVl = ctx.local_units(cfg.num_kv_heads)
            st["cross"] = (make((batch, enc_frames, KVl, hd), self.dtype),
                           make((batch, enc_frames, KVl, hd), self.dtype))
        return st

    def init_states(self, *, ctx: TPContext, batch: int, num_blocks: int,
                    page: int, enc_frames: int = 0, mode: str = "decode",
                    make=jnp.zeros):
        """Full stacked state pytree aligned with the scan plan."""
        groups = []
        for kind_seq, n in self.plan:
            per_kind = []
            for kind in kind_seq:
                one = self.layer_state(kind, ctx=ctx, batch=batch,
                                       num_blocks=num_blocks, page=page,
                                       enc_frames=enc_frames, mode=mode,
                                       make=make)
                per_kind.append(jax.tree.map(
                    lambda s: make((n,) + tuple(s.shape), s.dtype)
                    if hasattr(s, "shape") else s, one))
            groups.append(tuple(per_kind))
        return groups

    # ------------------------------------------------------------------
    # forward
    # ------------------------------------------------------------------
    def forward(self, params, ctx: TPContext, *, mode: str,
                tokens=None, positions=None, backend=None, states=None,
                embeds=None, enc_len=None, window: Optional[int] = None,
                frontend_embeds=None, last_pos=None):
        """Returns (local vocab-shard logits fp32, new_states, aux_loss).

        mode: 'train' | 'prefill' | 'decode'. ``frontend_embeds`` feeds the
        stubbed modality frontend (vlm patches / audio frames).
        ``positions`` [B,T] absolute positions. ``last_pos`` [B] (prefill
        only): per-request index of the final REAL prompt token, so the
        sampled logits don't depend on batch padding; defaults to the
        last position of the padded window.
        """
        cfg = self.cfg
        enc_out = None
        if cfg.enc_dec is not None and frontend_embeds is not None:
            enc_out = tfm.encode(cfg, params["encoder"], frontend_embeds,
                                 ctx, frame_len=enc_len)

        x = tfm.embed_tokens(cfg, params["embed"], tokens, ctx)
        if cfg.frontend is not None and cfg.frontend.kind == "vision" \
                and frontend_embeds is not None:
            patches = (frontend_embeds @ params["embed"]["projector"]) \
                .astype(x.dtype)
            x = jnp.concatenate([patches, x], axis=1)

        B, T = x.shape[0], x.shape[1]
        if positions is None:
            positions = jnp.broadcast_to(jnp.arange(T)[None], (B, T))
        if cfg.enc_dec is not None:
            # whisper: learned/sinusoidal positions on the decoder side
            pe = sinusoidal_positions(int(cfg.max_decode_context),
                                      cfg.d_model)
            x = x + pe[positions].astype(x.dtype)

        aux_total = jnp.zeros((), jnp.float32)
        new_groups = []
        for gi, (kind_seq, n) in enumerate(self.plan):
            p_group = params["groups"][gi]
            st_group = states[gi] if states is not None else None

            def body(carry, inp, kind_seq=kind_seq):
                x_c, aux_c = carry
                if st_group is not None:
                    ps, sts = inp
                else:
                    ps, sts = inp, tuple({} for _ in kind_seq)
                new_sts = []
                for si, kind in enumerate(kind_seq):
                    st_in = sts[si] if st_group is not None else {"mixer":
                                                                  None}
                    enc_kv = None
                    if cfg.enc_dec is not None and "cross" in ps[si]:
                        if enc_out is not None:   # train / prefill
                            enc_kv = _make_cross_kv(cfg, ps[si]["cross"],
                                                    enc_out, ctx)
                        else:                      # decode: cached
                            enc_kv = st_in.get("cross")
                    x_c, st_out, aux = tfm.apply_layer(
                        cfg, kind, ps[si], x_c,
                        ctx, backend, st_in, positions=positions, mode=mode,
                        enc_kv=enc_kv, enc_len=enc_len, window=window)
                    if "cross" in st_in:
                        st_out["cross"] = enc_kv if enc_out is not None \
                            else st_in["cross"]
                    new_sts.append(st_out)
                return (x_c, aux_c + aux), (tuple(new_sts)
                                            if st_group is not None else 0)

            if mode == "train" and self.remat:
                body = jax.checkpoint(body)

            if self.states_as_carry and st_group is not None \
                    and n > max(self.unroll, 1):
                def carry_body(carry, inp, body=body):
                    x_c, aux_c, sts = carry
                    ps, li = inp
                    st_i = jax.tree.map(
                        lambda a: lax.dynamic_index_in_dim(
                            a, li, 0, keepdims=False), sts)
                    (x_c, aux_c), new_st = body((x_c, aux_c), (ps, st_i))
                    sts = jax.tree.map(
                        lambda a, u: lax.dynamic_update_index_in_dim(
                            a, u, li, 0), sts, new_st)
                    return (x_c, aux_c, sts), None
                (x, aux_total, st_new), _ = lax.scan(
                    carry_body, (x, aux_total, st_group),
                    (p_group, jnp.arange(n)))
                new_groups.append(st_new)
                continue

            xs = (p_group, st_group) if st_group is not None else p_group
            if n <= max(self.unroll, 1):
                ys_list = []
                for li in range(n):
                    one_p = jax.tree.map(lambda a: a[li], p_group)
                    one_s = jax.tree.map(lambda a: a[li], st_group) \
                        if st_group is not None else None
                    inp = (one_p, one_s) if st_group is not None else one_p
                    (x, aux_total), ys = body((x, aux_total), inp)
                    ys_list.append(ys)
                new_groups.append(
                    jax.tree.map(lambda *a: jnp.stack(a), *ys_list)
                    if st_group is not None else None)
            else:
                (x, aux_total), ys = lax.scan(body, (x, aux_total), xs)
                new_groups.append(ys if st_group is not None else None)

        x = tfm.rms_norm(x, params["embed"]["norm_f"], cfg.norm_eps)
        if mode == "prefill":
            # only the final prompt position's logits are sampled
            if last_pos is not None:
                x = x[jnp.arange(x.shape[0]), last_pos][:, None, :]
            else:
                x = x[:, -1:]
        logits = tfm.lm_head(cfg, params["embed"], x, ctx)
        return logits, (new_groups if states is not None else None), \
            aux_total


def _make_cross_kv(cfg, p_cross, enc_out, ctx: TPContext):
    KV, hd = cfg.num_kv_heads, cfg.resolved_head_dim
    KVl = ctx.local_units(KV)
    B, F, _ = enc_out.shape
    k = (enc_out @ ctx.activate(p_cross["wk"], 1, KV)).reshape(B, F, KVl, hd)
    v = (enc_out @ ctx.activate(p_cross["wv"], 1, KV)).reshape(B, F, KVl, hd)
    return (k, v)


def build_model(cfg: ArchConfig, dtype=jnp.bfloat16) -> Model:
    return Model(cfg, dtype)
