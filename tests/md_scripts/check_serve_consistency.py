"""Multi-device consistency check (run with 8 forced host devices):
distributed flying-serve step (prefill + decode) under every merge mode
must match the single-device reference forward.

Exercised mechanisms: logical weight views (merge slicing), vocab-sharded
embed/head with replication scaling, paged pools in the invariant flat
layout with mode views, recurrent state sharding, MoE expert parallelism.
"""
import os
import sys

os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"

import numpy as np
import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.configs import get_config
from repro.core.kv_adaptor import KVCacheAdaptor, PoolGeometry
from repro.core.modes import FlyingMode, ParallelPlan, mode_mesh
from repro.core.steps import build_serve_step
from repro.core.views import SINGLE
from repro.core.weights_manager import WeightsManager
from repro.models.cache import TrainBackend
from repro.models.model import build_model
from repro.models.transformer import gather_vocab


def global_states(model, geom, mode, batch_per_group, mesh, phase,
                  enc_frames=0):
    """Zeros state pytree in engine layout [n, G1, G2, *device dims]."""
    from repro.core.views import make_serving_ctx
    cfg = model.cfg
    ctx = make_serving_ctx(mode.merge, mode.plan.engine_rows,
                           mode.plan.tp_base,
                           cfg.moe.num_experts if cfg.moe else 0)
    G1 = mode.plan.pods * mode.plan.dp_engines  # pod*dp*merge, mode-invariant
    G2 = mode.plan.engine_rows * mode.plan.tp_base
    groups = []
    for kind_seq, n in model.plan:
        per = []
        for kind in kind_seq:
            st = model.layer_state(kind, ctx=ctx, batch=batch_per_group,
                                   num_blocks=geom.num_blocks,
                                   page=geom.capacity(mode.merge),
                                   enc_frames=enc_frames,
                                   make=jax.ShapeDtypeStruct)
            st = dict(st)
            if kind[0] in ("gqa", "gqa_win", "mla"):
                st["mixer"] = tuple(
                    jax.ShapeDtypeStruct(geom.pool_shape(), s.dtype)
                    for s in st["mixer"])
            new = {}
            for k, leaves in st.items():
                new[k] = tuple(
                    jnp.zeros((n, G1, G2) + tuple(s.shape), s.dtype)
                    for s in leaves)
            per.append(new)
        groups.append(tuple(per))
    spec = P(None, ("pod", "dp", "merge"), ("ed", "model"))

    def put(a):
        s = NamedSharding(mesh, P(*(spec + P(*([None] * (a.ndim - 3))))))
        return jax.device_put(a, s)
    return jax.tree.map(put, groups)


def run_arch(name, merges=(1, 2), rtol=3e-3, atol=3e-3, layout="head"):
    cfg = get_config(name).reduced()
    model = build_model(cfg, jnp.float32)
    params = model.init(jax.random.key(0))

    plan = ParallelPlan(engine_rows=1, tp_base=2, data_rows=4, pods=1)
    B, T = 4, 10  # global batch, prompt len

    toks = jax.random.randint(jax.random.key(1), (B, T + 1), 0,
                              cfg.vocab_size)
    fee = None
    prefix = 0
    if cfg.frontend is not None:
        w = cfg.frontend.embed_width or cfg.d_model
        fee = jax.random.normal(jax.random.key(9),
                                (B, cfg.frontend.num_embeds, w),
                                jnp.float32) * 0.1
        if cfg.frontend.kind == "vision":
            prefix = cfg.frontend.num_embeds
    # single-device reference
    ref, _, _ = model.forward(params, SINGLE, mode="train", tokens=toks,
                              backend=TrainBackend(), frontend_embeds=fee)

    for merge in merges:
        mode = FlyingMode(plan, merge)
        mesh = mode_mesh(mode)
        wm = WeightsManager(cfg, plan)
        p_sh = jax.device_put(params, wm.shardings(params, mesh))

        groups = plan.pods * mode.dp     # independent groups
        bpg = B // groups                 # requests per group
        probe = PoolGeometry(cfg, plan, num_blocks=2, block_base=4,
                             layout=layout)
        cap = probe.capacity(merge)
        need = bpg * (-(-(T + prefix + 1) // cap)) + 2
        geom = PoolGeometry(cfg, plan, num_blocks=max(need, 10),
                            block_base=4, layout=layout)

        # per-group adaptors produce identical block layouts
        Tp = T + prefix
        adaptors = [KVCacheAdaptor(geom) for _ in range(groups)]
        for a in adaptors:
            a.switch_mode(merge)
        slots = np.stack([
            np.concatenate([adaptors[b // bpg].append_slots(f"r{b}", Tp)])
            for b in range(B)])
        max_blocks = -(-(Tp + 1) // geom.capacity(merge)) + 1
        btab = np.stack([adaptors[b // bpg].block_table(f"r{b}", max_blocks)
                         for b in range(B)])

        enc_f = cfg.frontend.num_embeds if cfg.enc_dec is not None else 0
        st = global_states(model, geom, mode, bpg, mesh, "prefill",
                           enc_frames=enc_f)
        prefill, _, _ = build_serve_step(model, mode, geom, phase="prefill")
        batch = {
            "tokens": jnp.asarray(toks[:, :T]),
            "positions": jnp.broadcast_to(jnp.arange(Tp)[None], (B, Tp)),
            "slots": jnp.asarray(slots),
            "block_table": jnp.asarray(btab),
            "prior_len": jnp.zeros((B,), jnp.int32),
        }
        if fee is not None:
            batch["frontend_embeds"] = jnp.asarray(fee)
        if cfg.enc_dec is not None:
            batch["enc_len"] = jnp.full((B,), enc_f, jnp.int32)
        lp, st = jax.jit(prefill)(p_sh, st, batch)
        np.testing.assert_allclose(np.asarray(lp), np.asarray(ref[:, -2]),
                                   rtol=rtol, atol=atol)

        dslots = np.stack([adaptors[b // bpg].append_slots(f"r{b}", 1)[0]
                           for b in range(B)])
        btab2 = np.stack([adaptors[b // bpg].block_table(f"r{b}", max_blocks)
                          for b in range(B)])
        decode, _, _ = build_serve_step(model, mode, geom, phase="decode")
        dbatch = {
            "tokens": jnp.asarray(toks[:, T:T + 1]),
            "positions": jnp.full((B, 1), Tp, jnp.int32),
            "slots": jnp.asarray(dslots),
            "block_table": jnp.asarray(btab2),
            "context_len": jnp.full((B,), Tp + 1, jnp.int32),
        }
        if cfg.enc_dec is not None:
            dbatch["enc_len"] = jnp.full((B,), enc_f, jnp.int32)
        ld, st = jax.jit(decode)(p_sh, st, dbatch)
        np.testing.assert_allclose(np.asarray(ld), np.asarray(ref[:, -1]),
                                   rtol=rtol, atol=atol)
        print(f"  {name} merge={merge} layout={layout} OK "
              f"({mode.describe()})")


if __name__ == "__main__":
    layout = "head"
    args = [a for a in sys.argv[1:] if a != "--striped"]
    if "--striped" in sys.argv[1:]:
        layout = "striped"
    archs = args or ["stablelm-1.6b", "llama3-8b", "mamba2-2.7b",
                     "recurrentgemma-9b", "deepseek-v2-236b",
                     "phi3.5-moe-42b-a6.6b", "qwen3-4b"]
    for a in archs:
        run_arch(a, layout=layout)
    print("ALL CONSISTENT")
