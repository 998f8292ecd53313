"""Step-program builders: the SPMD programs the Communicator Pool compiles.

``build_serve_step`` returns a jit-able shard_map program for one flying
mode (merge factor). Batch layout: requests sharded over ('pod','dp');
activations replicated within a TP group ('merge','ed','model'). Weights
arrive in canonical storage layout (replicated over DP axes, engine-tile
sharded) and are *activated* per-rank inside (core/views.py) — GSPMD
cannot express storage != compute sharding, which is exactly the paper's
zero-copy trick, hence shard_map.

``build_train_step`` is the GSPMD path: plain jit with NamedShardings.
"""
from __future__ import annotations

from functools import partial
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import NamedSharding
from jax.sharding import PartitionSpec as P

from repro.configs.base import ArchConfig
from repro.core.kv_adaptor import PoolGeometry
from repro.core.modes import MODE_AXES, FlyingMode, mode_mesh
from repro.core.views import TPContext, make_serving_ctx
from repro.core.weights_manager import WeightsManager
from repro.models.cache import DecodeBackend, PrefillBackend, TrainBackend
from repro.models.model import Model
from repro.models.transformer import tp_cross_entropy

DP_AXES = ("pod", "dp")
TP_AXES = ("merge", "ed", "model")


def serving_ctx(mode: FlyingMode, cfg: ArchConfig) -> TPContext:
    n_exp = cfg.moe.num_experts if cfg.moe else 0
    return make_serving_ctx(mode.merge, mode.plan.engine_rows,
                            mode.plan.tp_base, n_exp)


# ---------------------------------------------------------------------------
# batch specs: what the host supplies per step
# ---------------------------------------------------------------------------

def decode_batch_spec():
    """Per-request arrays (leading dim = global decode batch)."""
    return {
        "tokens": P(DP_AXES, None),       # [B,1]
        "positions": P(DP_AXES, None),    # [B,1]
        "slots": P(DP_AXES,),             # [B]
        "block_table": P(DP_AXES, None),  # [B, max_blocks]
        "context_len": P(DP_AXES,),       # [B]
    }


def prefill_batch_spec():
    return {
        "tokens": P(DP_AXES, None),       # [B,T]
        "positions": P(DP_AXES, None),
        "slots": P(DP_AXES, None),        # [B,T]
        "block_table": P(DP_AXES, None),
        "prior_len": P(DP_AXES,),
    }


def mixed_batch_spec():
    """Unified mixed-phase step (§Perf D6): one compiled program packs
    the prefill chunk rows (``p_*``) and the decode batch (``d_*``).
    ``d_src_rows`` [B] holds, for decode rows whose request finished
    prefill THIS step, the (group-local) prefill row producing its input
    token (-1 otherwise) — the first generated token feeds the first
    decode inside the same launch, never through the host."""
    spec = {"p_" + k: v for k, v in prefill_batch_spec().items()}
    spec.update({"d_" + k: v for k, v in decode_batch_spec().items()})
    spec["p_last_pos"] = P(DP_AXES,)
    spec["d_src_rows"] = P(DP_AXES,)
    return spec


# ---------------------------------------------------------------------------
# serve step
# ---------------------------------------------------------------------------

def build_serve_step(model: Model, mode: FlyingMode, geom: PoolGeometry, *,
                     phase: str, window: Optional[int] = None,
                     use_kernel: Optional[bool] = None,
                     chunked: bool = False,
                     sample: Optional[Tuple[float, int]] = None,
                     live: Optional[Tuple[int, ...]] = None,
                     sp: int = 1,
                     mesh=None):
    """Build the shard_map step fn for (arch, mode, phase).

    ``live`` (docs/PERF.md §D8/§D12) compiles the cross-layout read
    variant: an ordered tuple of placement LANES — one per (tag,
    owner-shard) slice of the batch's KV, possibly with repeated tags.
    The batch then carries, per lane i of tag t=live[i], ``lt{i}_bt``
    [B, mb_i] segment block tables, ``lt{i}_len`` [B] segment token
    counts, and ``lt{i}_own`` [B] merge-axis owner offsets; attention
    runs per-lane partial sweeps plus one LSE-combine collective over
    the merge axis instead of the single-view sweep. A plain rebind
    rider has one lane per distinct tag; ``live=None`` (or the single
    current tag) is the unchanged fast path.

    ``sp`` > 1 (§D12) compiles the sequence-parallel variant of the live
    program: each merge group holds ``sp`` shards of ``merge // sp``
    engines, new KV is written under the SHARD-width tag to the per-row
    owner shard only (batch key ``write_own`` [B] carries each row's
    owner merge-offset; non-owner ranks park the write in the reserved
    scratch block), and prefill's causal current-chunk sweep is the LAST
    lane (each row's owner shard — the host rotates lanes per row so the
    static lane choice holds for every row).

    ``mesh`` overrides the default ``mode_mesh(mode)``: island runners
    pass an AbstractMesh of the island SHAPE, so one traced program
    serves every same-shape island (the concrete device slice resolves
    from the island-committed params/states at call time).

    ``use_kernel``: None dispatches decode attention by platform (Pallas
    kernel where compiled support exists, jnp reference elsewhere);
    True forces the kernel (interpret-mode parity on CPU); False pins
    the reference path. See kernels/paged_attention/ops.resolve_impl.

    ``sample=(temperature, top_k)`` fuses token sampling into the
    compiled step: the program returns device-resident ``[B]`` int32
    token ids instead of gathered ``[B, V]`` logits, so steady-state
    serving never materializes logits on the host (§Perf D1). Greedy
    (temperature<=0) uses the gather-free distributed argmax; stochastic
    sampling reads per-row seeds from ``batch['sample_seeds']``.
    ``sample=None`` keeps the logits-returning contract (reference paths
    and consistency tests).

    States layout (engine-owned): every leaf is ``[L, G1, G2, *per-device
    dims]`` with G1 sharded over ('pod','dp','merge') and G2 over
    ('ed','model'), so each device holds exactly its own
    ``[L, 1, 1, ...]`` slice; for paged pools that slice is the physical
    ``[num_blocks, B_base, W]`` pool (``PoolGeometry.pool_shape``).
    """
    cfg = model.cfg
    ctx = serving_ctx(mode, cfg)
    if mesh is None:
        mesh = mode_mesh(mode)
    merge = mode.merge
    model.states_as_carry = True  # §Perf A2: in-place pool updates

    from repro.models.transformer import (gather_vocab, sample_tokens,
                                          tp_argmax)

    striped = geom.layout == "striped"
    hs = geom.head_split(merge)
    impl = {None: "auto", True: "force", False: "ref"}[use_kernel]

    assert sp >= 1 and merge % sp == 0, (sp, merge)
    wtag = merge // sp
    if sp > 1:
        assert live is not None, \
            "sequence-parallel serving always runs the live lane program"
    if live is not None:
        assert phase in ("decode", "prefill"), \
            "live cross-layout reads cover the paged decode/prefill " \
            "steps (mixed ticks fall back to the sequential pair)"
        assert not striped and cfg.enc_dec is None and cfg.mla is None, \
            "live reads need the head-layout paged pool"
        assert window is None, "live reads do not support sliding windows"
        # sp=1: the write tag IS the merge and exactly one lane carries
        # it. sp>1: the write-tag lanes are the sp shard lanes.
        assert wtag in live and all(t <= merge for t in live), (live, sp)
        for t in live:
            assert geom.live_readable(t) and geom.live_readable(merge), \
                (t, merge, "architecture is not tag-readable (§D8)")

    def live_segs(batch):
        return tuple((t, batch[f"lt{i}_bt"], batch[f"lt{i}_len"],
                      batch[f"lt{i}_own"]) for i, t in enumerate(live))

    def mixed_step(params, states, batch):
        """One launch per scheduler tick (§Perf D6): chunked prefill for
        the admission rows, then decode for the running batch, over the
        same donated state pytree. Token-identical to the sequential
        prefill->decode launches — the math is the same two forwards,
        compiled into one executable keyed by
        (merge, batch_bucket, chunk_bucket, mb_bucket)."""
        assert not striped and cfg.enc_dec is None, \
            "mixed step covers paged attention archs only"
        sts = _local(states)
        pb = PrefillBackend(
            slots=batch["p_slots"], prior_len=batch["p_prior_len"],
            block_table=batch["p_block_table"], chunked=True, impl=impl,
            hs=hs)
        logits_p, sts, _ = model.forward(
            params, ctx, mode="prefill", tokens=batch["p_tokens"],
            positions=batch["p_positions"], backend=pb, states=sts,
            window=window, last_pos=batch["p_last_pos"])
        if sample is not None:
            temp, top_k = sample
            p_toks = sample_tokens(cfg, logits_p[:, -1], ctx,
                                   temperature=temp, top_k=top_k,
                                   seeds=batch.get("p_sample_seeds"))
        else:
            # logits-returning contract: route src rows via the greedy
            # distributed argmax (the legacy host path is greedy-only)
            p_toks = tp_argmax(cfg, logits_p[:, -1], ctx)
        # decode rows promoted out of THIS step's prefill read their
        # input token from the prefill output row, on device
        src = batch["d_src_rows"]
        d_in = jnp.where(src[:, None] >= 0,
                         jnp.take(p_toks, jnp.maximum(src, 0),
                                  axis=0)[:, None].astype(jnp.int32),
                         batch["d_tokens"])
        db = DecodeBackend(
            slots=batch["d_slots"], block_table=batch["d_block_table"],
            context_len=batch["d_context_len"], impl=impl, hs=hs)
        logits_d, sts, _ = model.forward(
            params, ctx, mode="decode", tokens=d_in,
            positions=batch["d_positions"], backend=db, states=sts,
            window=window)
        new_states = _stacked(sts)
        if sample is not None:
            temp, top_k = sample
            d_toks = sample_tokens(cfg, logits_d[:, -1], ctx,
                                   temperature=temp, top_k=top_k,
                                   seeds=batch.get("d_sample_seeds"))
            return (p_toks, d_toks), new_states
        return (gather_vocab(cfg, logits_p[:, -1], ctx),
                gather_vocab(cfg, logits_d[:, -1], ctx)), new_states

    def step(params, states, batch):
        sts = _local(states)
        if live is not None and phase == "decode":
            from repro.models.cache import LiveDecodeBackend
            backend = LiveDecodeBackend(
                ctx=ctx, slots=batch["slots"], segs=live_segs(batch),
                merge=merge, block_base=geom.block_base, impl=impl,
                sp=sp, write_own=batch.get("write_own"))
        elif live is not None:
            from repro.models.cache import LivePrefillBackend
            backend = LivePrefillBackend(
                ctx=ctx, slots=batch["slots"], segs=live_segs(batch),
                merge=merge, block_base=geom.block_base, impl=impl,
                sp=sp, write_own=batch.get("write_own"))
        elif phase == "decode" and striped:
            from repro.models.striped import StripedDecodeBackend
            backend = StripedDecodeBackend(
                ctx=ctx, block_table=batch["block_table"],
                context_len=batch["context_len"],
                n_q_heads=cfg.num_heads, n_kv_heads=cfg.num_kv_heads,
                window=window)
        elif phase == "decode":
            backend = DecodeBackend(
                slots=batch["slots"], block_table=batch["block_table"],
                context_len=batch["context_len"], impl=impl, hs=hs)
        elif striped:
            from repro.models.striped import StripedPrefillBackend
            backend = StripedPrefillBackend(
                ctx=ctx, block_table=batch["block_table"], window=window)
        else:
            backend = PrefillBackend(
                slots=batch["slots"], prior_len=batch["prior_len"],
                block_table=batch["block_table"], chunked=chunked,
                impl=impl, hs=hs)
        logits, new_sts, _ = model.forward(
            params, ctx, mode=phase, tokens=batch["tokens"],
            positions=batch["positions"], backend=backend, states=sts,
            window=window, enc_len=batch.get("enc_len"),
            frontend_embeds=batch.get("frontend_embeds"),
            last_pos=batch.get("last_pos"))
        new_states = _stacked(new_sts)
        if sample is not None:
            temp, top_k = sample
            tokens = sample_tokens(cfg, logits[:, -1], ctx,
                                   temperature=temp, top_k=top_k,
                                   seeds=batch.get("sample_seeds"))
            return tokens, new_states
        return gather_vocab(cfg, logits[:, -1], ctx), new_states

    # shard_map wrapping
    wm = WeightsManager(cfg, mode.plan)
    pspecs = wm.partition_specs(model.param_specs())

    def make_state_spec(leaf_ndim):
        # state leaves: [n_layers, G1=pod*dp*merge, G2=ed*model, *device dims]
        return P(None, ("pod", "dp", "merge"), ("ed", "model"),
                 *([None] * (leaf_ndim - 3)))

    def run(params, states, batch):
        base = {"decode": decode_batch_spec, "prefill": prefill_batch_spec,
                "mixed": mixed_batch_spec}[phase]()
        bspecs = {k: base.get(k, P(DP_AXES, *([None] * (batch[k].ndim - 1))))
                  for k in batch}
        sspecs = jax.tree.map(lambda a: make_state_spec(a.ndim), states)
        tok_spec = P(DP_AXES,) if sample is not None else P(DP_AXES, None)
        out_spec = (tok_spec, tok_spec) if phase == "mixed" else tok_spec
        fn = jax.shard_map(
            mixed_step if phase == "mixed" else step, mesh=mesh,
            in_specs=(pspecs, sspecs, bspecs),
            out_specs=(out_spec, sspecs), check_vma=False)
        return fn(params, states, batch)

    return run, mesh, ctx


def _local(states):
    """shard_map blocks ``[n_layers, 1, 1, *per_device_dims]`` (the two
    singleton dims are the sharded group/tile axes) -> the per-device
    ``[n_layers, *per_device_dims]`` states the model threads. Paged
    pools keep their stored ``[num_blocks, B_base, W]`` layout — the
    backends read it under the mode's head split (paper §4.2: a mode
    switch reshapes nothing)."""
    return jax.tree.map(lambda p: p.reshape((p.shape[0],) + p.shape[3:]),
                        states)


def _stacked(states):
    """Inverse of ``_local``: outputs land back in the stored layout."""
    return jax.tree.map(lambda p: p.reshape((p.shape[0], 1, 1) + p.shape[1:]),
                        states)
