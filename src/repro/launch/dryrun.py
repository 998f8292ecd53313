import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

"""Multi-pod dry-run (deliverable e) + roofline extraction (deliverable g).

For every (architecture x input shape): lower + compile the step program
on the single-pod (16,16) mesh AND the 2-pod (2,16,16) mesh, with
ShapeDtypeStruct inputs (no allocation). Prints memory_analysis (fits?)
and cost_analysis (FLOPs/bytes), parses collective bytes from the
compiled HLO, and lowers two small UNROLLED probes to scale scan-body
costs by trip count (analysis/roofline.py).

Usage:
  PYTHONPATH=src python -m repro.launch.dryrun                # all pairs
  PYTHONPATH=src python -m repro.launch.dryrun --arch llama3-8b \
      --shape decode_32k [--multi-pod] [--no-probes]
Results accumulate in results/dryrun/<arch>__<shape>__<mesh>.json.
"""
import argparse
import dataclasses
import json
import time
import traceback
from typing import Dict, Optional, Tuple

import numpy as np

import jax
import jax.numpy as jnp

from repro.analysis import roofline as rl
from repro.configs import ASSIGNED_ARCHS, get_config, get_shape
from repro.configs.base import ArchConfig
from repro.configs.shapes import InputShape
from repro.core.kv_adaptor import PoolGeometry
from repro.core.modes import FlyingMode, ParallelPlan, plan_for
from repro.core.steps import build_serve_step
from repro.core.views import make_serving_ctx
from repro.core.weights_manager import WeightsManager
from repro.models.model import Model, build_model

RESULTS_DIR = os.path.join(os.path.dirname(__file__), "..", "..", "..",
                           "results", "dryrun")

# documented skips (DESIGN.md §5)
SKIPS = {
    ("whisper-base", "long_500k"):
        "enc-dec decoder context is 448 tokens; 500k decode undefined",
}

S = jax.ShapeDtypeStruct


# ---------------------------------------------------------------------------
# per-(arch, shape) execution plan
# ---------------------------------------------------------------------------

def layout_for(cfg: ArchConfig, shape: InputShape) -> str:
    if cfg.family == "ssm":
        return "head"  # no paged pools at all
    if cfg.mla is not None:
        return "striped"  # compressed cache cannot head-shard
    if shape.name == "long_500k":
        return "striped"  # context-parallel capacity pooling
    if cfg.name.startswith("mistral") and shape.name == "decode_32k":
        return "striped"  # 88-layer KV exceeds HBM under head layout
    return "head"


def merge_for(cfg: ArchConfig, shape: InputShape, plan: ParallelPlan) -> int:
    if shape.name == "long_500k":
        return plan.valid_merges()[-1]  # use case 3: bind the whole pod
    return 1


def window_for(cfg: ArchConfig, shape: InputShape) -> Optional[int]:
    if shape.name == "long_500k" and cfg.family in ("dense", "moe", "vlm"):
        return cfg.long_context_window  # sub-quadratic dense variant
    return None


def batch_geometry(cfg: ArchConfig, shape: InputShape, plan: ParallelPlan,
                   merge: int, layout: str):
    """Returns (batch_per_group, ctx_tokens, geom, max_blocks)."""
    groups = plan.pods * (plan.dp_engines // merge)
    if shape.phase == "prefill":
        bpg = 1  # production prefill: one request per group per step
        ctx = shape.seq_len
    else:
        bpg = max(shape.global_batch // groups, 1)
        ctx = shape.seq_len
    block_base = 16
    geom0 = PoolGeometry(cfg, plan, num_blocks=1, block_base=block_base,
                         layout=layout)
    cap = geom0.capacity(merge)
    per_req_blocks = -(-ctx // cap)
    num_blocks = bpg * per_req_blocks + 1
    geom = PoolGeometry(cfg, plan, num_blocks=num_blocks,
                        block_base=block_base, layout=layout)
    return bpg, ctx, geom, per_req_blocks


def abstract_states(model: Model, geom: PoolGeometry, mode: FlyingMode,
                    bpg: int, enc_frames: int = 0):
    ctx = make_serving_ctx(mode.merge, mode.plan.engine_rows,
                           mode.plan.tp_base,
                           model.cfg.moe.num_experts if model.cfg.moe else 0)
    G1 = mode.plan.pods * mode.plan.dp_engines
    G2 = mode.plan.engine_rows * mode.plan.tp_base
    groups = []
    for kind_seq, n in model.plan:
        per = []
        for kind in kind_seq:
            st = model.layer_state(kind, ctx=ctx, batch=bpg,
                                   num_blocks=geom.num_blocks,
                                   page=geom.capacity(mode.merge),
                                   enc_frames=enc_frames,
                                   make=jax.ShapeDtypeStruct)
            st = dict(st)
            if kind[0] in ("gqa", "gqa_win", "mla"):
                st["mixer"] = tuple(S(geom.pool_shape(), s.dtype)
                                    for s in st["mixer"])
            per.append({k: tuple(S((n, G1, G2) + tuple(s.shape), s.dtype)
                                 for s in v) for k, v in st.items()})
        groups.append(tuple(per))
    return groups


def abstract_batch(cfg: ArchConfig, shape: InputShape, plan: ParallelPlan,
                   merge: int, bpg: int, ctx_tokens: int, max_blocks: int):
    groups = plan.pods * (plan.dp_engines // merge)
    B = groups * bpg
    i32 = jnp.int32
    if shape.phase == "decode":
        batch = {
            "tokens": S((B, 1), i32), "positions": S((B, 1), i32),
            "slots": S((B,), i32), "block_table": S((B, max_blocks), i32),
            "context_len": S((B,), i32),
        }
        if cfg.enc_dec is not None:
            batch["enc_len"] = S((B,), i32)
        return batch, B
    # prefill
    T = ctx_tokens
    fe_tokens = 0
    extras = {}
    if cfg.enc_dec is not None:
        # whisper: the 32k stress goes through the ENCODER memory; the
        # decoder prompt is its 448-token context (DESIGN.md §5)
        F = ctx_tokens
        T = min(cfg.max_decode_context, 448)
        extras["frontend_embeds"] = S((B, F, cfg.d_model), jnp.bfloat16)
        extras["enc_len"] = S((B,), i32)
    elif cfg.frontend is not None:
        P_ = cfg.frontend.num_embeds
        T = ctx_tokens - P_
        fe_tokens = P_
        extras["frontend_embeds"] = S(
            (B, P_, cfg.frontend.embed_width or cfg.d_model), jnp.bfloat16)
    batch = {
        "tokens": S((B, T), i32),
        "positions": S((B, T + fe_tokens), i32),
        "slots": S((B, T + fe_tokens), i32),
        "block_table": S((B, max_blocks), i32),
        "prior_len": S((B,), i32),
    }
    batch.update(extras)
    return batch, B


# ---------------------------------------------------------------------------
# lower + compile one pair
# ---------------------------------------------------------------------------

def lower_serve(cfg: ArchConfig, shape: InputShape, *, pods: int,
                num_layers: Optional[int] = None, unroll: int = 1):
    base = cfg if num_layers is None else \
        dataclasses.replace(cfg, num_layers=num_layers)
    model = build_model(base, jnp.bfloat16)
    model.unroll = unroll
    plan = plan_for(base, pods=pods)
    layout = layout_for(base, shape)
    merge = merge_for(base, shape, plan)
    mode = FlyingMode(plan, merge)
    bpg, ctx_tokens, geom, max_blocks = batch_geometry(
        base, shape, plan, merge, layout)
    enc_frames = ctx_tokens if base.enc_dec is not None else 0
    run, mesh, _ = build_serve_step(model, mode, geom, phase=shape.phase,
                                    window=window_for(base, shape))
    params = model.param_specs()
    states = abstract_states(model, geom, mode, bpg, enc_frames=enc_frames)
    batch, B = abstract_batch(base, shape, plan, merge, bpg, ctx_tokens,
                              max_blocks)
    lowered = jax.jit(run, donate_argnums=(1,)).lower(params, states, batch)
    return lowered, dict(merge=merge, layout=layout, bpg=bpg,
                         batch_global=B, max_blocks=max_blocks,
                         tp=mode.tp, groups=plan.pods * mode.dp)


def lower_train(cfg: ArchConfig, shape: InputShape, *, pods: int,
                num_layers: Optional[int] = None, unroll: int = 1):
    from repro.training.optimizer import AdamW
    from repro.training.train_step import build_train_step, train_mesh
    base = cfg if num_layers is None else \
        dataclasses.replace(cfg, num_layers=num_layers)
    model = build_model(base, jnp.bfloat16)
    model.unroll = unroll
    plan = plan_for(base, pods=pods)
    mesh = train_mesh(plan)
    opt = AdamW()
    step, psh, osh, bsh = build_train_step(model, plan, mesh, opt=opt,
                                           donate=False)
    params = model.param_specs()
    ost = jax.eval_shape(opt.init, params)
    B, T = shape.global_batch, shape.seq_len
    batch = {"tokens": S((B, T), jnp.int32), "labels": S((B, T), jnp.int32)}
    if base.enc_dec is not None:
        T = min(base.max_decode_context, 448)
        F = shape.seq_len - T
        batch = {"tokens": S((B, T), jnp.int32),
                 "labels": S((B, T), jnp.int32),
                 "frontend_embeds": S((B, F, base.d_model), jnp.bfloat16)}
    elif base.frontend is not None:
        P_ = base.frontend.num_embeds
        batch = {"tokens": S((B, T - P_), jnp.int32),
                 "labels": S((B, T - P_), jnp.int32),
                 "frontend_embeds": S(
                     (B, P_, base.frontend.embed_width or base.d_model),
                     jnp.bfloat16)}
    lowered = step.lower((params, ost), batch)
    return lowered, dict(merge=0, layout="train", bpg=B // plan.data_rows
                         // plan.pods, batch_global=B, max_blocks=0,
                         tp=plan.tp_base, groups=plan.pods * plan.data_rows)


def probe_layers(cfg: ArchConfig) -> Tuple[int, int]:
    """(L1, L2) for the unrolled roofline probes."""
    from repro.models.transformer import stack_plan
    if cfg.hybrid is not None:
        k = len(cfg.hybrid.pattern)
        return k, 2 * k
    if cfg.mla is not None and cfg.moe is not None:
        return 2, 3
    return 1, 2


def run_pair(arch: str, shape_name: str, *, multi_pod: bool,
             probes: bool = True, force: bool = False) -> Dict:
    os.makedirs(RESULTS_DIR, exist_ok=True)
    mesh_tag = "pod2" if multi_pod else "pod1"
    out_path = os.path.join(RESULTS_DIR, f"{arch}__{shape_name}__"
                                         f"{mesh_tag}.json")
    if os.path.exists(out_path) and not force:
        with open(out_path) as f:
            return json.load(f)
    cfg = get_config(arch)
    shape = get_shape(shape_name)
    if (arch, shape_name) in SKIPS:
        res = {"arch": arch, "shape": shape_name, "mesh": mesh_tag,
               "skipped": SKIPS[(arch, shape_name)]}
        with open(out_path, "w") as f:
            json.dump(res, f, indent=1)
        return res

    pods = 2 if multi_pod else 1
    lower_fn = lower_train if shape.phase == "train" else lower_serve
    t0 = time.time()
    lowered, meta = lower_fn(cfg, shape, pods=pods)
    t_lower = time.time() - t0
    t0 = time.time()
    compiled = lowered.compile()
    t_compile = time.time() - t0

    mem = compiled.memory_analysis()
    cost = compiled.cost_analysis()
    hlo = compiled.as_text()
    coll = rl.collective_bytes(hlo)

    res = {
        "arch": arch, "shape": shape_name, "mesh": mesh_tag,
        "meta": meta,
        "lower_s": t_lower, "compile_s": t_compile,
        "memory": {
            "argument_bytes": mem.argument_size_in_bytes,
            "output_bytes": mem.output_size_in_bytes,
            "temp_bytes": mem.temp_size_in_bytes,
            "alias_bytes": mem.alias_size_in_bytes,
            "code_bytes": mem.generated_code_size_in_bytes,
        },
        "cost_raw": {k: float(v) for k, v in cost.items()
                     if isinstance(v, (int, float))},
        "collectives_raw": coll,
    }

    if probes and not multi_pod:
        L1, L2 = probe_layers(cfg)
        c1, c2, b1, b2 = {}, {}, 0.0, 0.0
        for which, L in (("p1", L1), ("p2", L2)):
            lw, _ = lower_fn(cfg, shape, pods=pods, num_layers=L, unroll=L2)
            cp = lw.compile()
            ca = cp.cost_analysis()
            if shape.phase == "train":
                cb = rl.collective_bytes(cp.as_text())
            else:
                # serve paths: shard_map collectives are explicit in the
                # StableHLO with target-faithful dtypes (the CPU backend
                # widens bf16 collectives in compiled HLO)
                cb = rl.collective_bytes_stablehlo(lw.as_text())
            wb = rl.wire_bytes(cb, tp_hint=max(meta["tp"], 2))
            if which == "p1":
                c1 = {k: float(v) for k, v in ca.items()}
                b1 = wb
            else:
                c2 = {k: float(v) for k, v in ca.items()}
                b2 = wb
        L = cfg.num_layers
        sc = rl.scaled_cost(c1, c2, L1, L2, L)
        res["probes"] = {"L1": L1, "L2": L2, "cost1": {
            k: c1.get(k, 0.0) for k in ("flops", "bytes accessed")},
            "cost2": {k: c2.get(k, 0.0) for k in ("flops",
                                                  "bytes accessed")},
            "wire1": b1, "wire2": b2}
        res["scaled"] = {
            "flops_per_dev": sc["flops"],
            "hbm_bytes_per_dev": sc["bytes accessed"],
            "wire_bytes_per_dev": rl.scaled_collectives(b1, b2, L1, L2, L),
        }
        terms = rl.RooflineTerms(
            flops=sc["flops"], hbm_bytes=sc["bytes accessed"],
            coll_bytes=res["scaled"]["wire_bytes_per_dev"],
            chips=256 * pods)
        mf = rl.model_flops(cfg, shape, shape.phase)
        # the compiled step may process only part of the shape's global
        # batch (prefill: 1 request per group per step) — scale the
        # useful-work yardstick to the step's actual token share
        step_share = meta["batch_global"] / max(shape.global_batch, 1)
        res["roofline"] = terms.row()
        res["roofline"]["model_flops_total"] = mf
        res["roofline"]["step_share"] = step_share
        chips = 256 * pods
        res["roofline"]["useful_flops_ratio"] = \
            mf * step_share / max(sc["flops"] * chips, 1.0)

    with open(out_path, "w") as f:
        json.dump(res, f, indent=1)
    return res


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None)
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--both-meshes", action="store_true")
    ap.add_argument("--no-probes", action="store_true")
    ap.add_argument("--force", action="store_true")
    args = ap.parse_args()

    archs = [args.arch] if args.arch else list(ASSIGNED_ARCHS)
    shapes = [args.shape] if args.shape else \
        ["train_4k", "prefill_32k", "decode_32k", "long_500k"]
    meshes = [False, True] if args.both_meshes else [args.multi_pod]

    failures = []
    for arch in archs:
        for shp in shapes:
            for mp in meshes:
                tag = f"{arch} x {shp} x {'pod2' if mp else 'pod1'}"
                try:
                    t0 = time.time()
                    res = run_pair(arch, shp, multi_pod=mp,
                                   probes=not args.no_probes,
                                   force=args.force)
                    if "skipped" in res:
                        print(f"[skip] {tag}: {res['skipped']}", flush=True)
                        continue
                    mem = res["memory"]
                    per_dev = (mem["argument_bytes"] + mem["temp_bytes"])
                    line = (f"[ok]   {tag}: args+temp/dev="
                            f"{per_dev / 1e9:.2f}GB")
                    if "roofline" in res:
                        r = res["roofline"]
                        line += (f" compute={r['t_compute_s'] * 1e3:.2f}ms"
                                 f" memory={r['t_memory_s'] * 1e3:.2f}ms"
                                 f" coll={r['t_collective_s'] * 1e3:.2f}ms"
                                 f" dom={r['dominant']}")
                    line += f" ({time.time() - t0:.0f}s)"
                    print(line, flush=True)
                except Exception as e:
                    failures.append((tag, repr(e)))
                    print(f"[FAIL] {tag}: {e!r}", flush=True)
                    traceback.print_exc()
    if failures:
        print(f"\n{len(failures)} FAILURES")
        raise SystemExit(1)
    print("\nDRY-RUN COMPLETE: all combinations lowered and compiled.")


if __name__ == "__main__":
    main()
