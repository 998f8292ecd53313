"""Model Weights Manager (paper §4.1).

Weights are loaded ONCE into the *canonical storage layout*: every tensor
sharded over the engine-tile axes ``('ed','model')`` on its partition dim
(when divisible — the same rule ``TPContext.stored_shards`` assumes) and
replicated over the DP axes ``('pod','dp','merge')``. Because every mode
mesh reinterprets the same device order, re-binding the params to another
mode's sharding is a pure metadata operation — no bytes move (the paper's
zero-copy invariant; asserted by ``reinterpret(..., check_zero_copy=True)``
via buffer-pointer comparison).

TP execution then *activates* per-rank views inside the step program
(core/views.py), never resharding storage. This module owns the
name->rule mapping that keeps weights_manager specs and TPContext.activate
consistent.
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import Any, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding
from jax.sharding import PartitionSpec as P

from repro.configs.base import ArchConfig
from repro.core.modes import FlyingMode, ParallelPlan, mode_mesh
from repro.models.mamba2 import dims as mamba_dims
from repro.models.rglru import width as rg_width

# rule kinds
DENSE = "dense"       # partition dim over ('ed','model') jointly
EXPERT = "expert"     # expert dim over 'ed'
MODEL_ONLY = "model"  # dim over 'model' (expert d_ff; merge adds views)
REPL = "repl"


@dataclass(frozen=True)
class Rule:
    """(axis_from_end, logical unit count, kind) per sharded dim."""
    dims: Tuple[Tuple[int, int, str], ...] = ()


def _units(cfg: ArchConfig) -> Dict[str, int]:
    u = {
        "H": cfg.num_heads, "KV": cfg.num_kv_heads, "DFF": cfg.d_ff,
        "V": cfg.vocab_size,
    }
    if cfg.moe:
        u["E"] = cfg.moe.num_experts
        u["DFFE"] = cfg.moe.d_ff_expert
        u["SDFF"] = cfg.moe.num_shared_experts * cfg.moe.d_ff_expert
    if cfg.ssm:
        u["NH"] = mamba_dims(cfg)[1]
    if cfg.hybrid:
        u["W"] = rg_width(cfg)
    return u


def rule_for(cfg: ArchConfig, path: Tuple[str, ...]) -> Rule:
    """Shard rule for a param identified by its (parent..., name) path."""
    u = _units(cfg)
    name = path[-1]
    parent = next((p for p in reversed(path[:-1])
                   if p in ("attn", "cross", "mixer", "ffn", "shared",
                            "embed", "encoder")), "")

    if parent in ("attn", "cross"):
        table = {
            "wq": ((-1, u["H"], DENSE),), "wo": ((-2, u["H"], DENSE),),
            "wk": ((-1, u["KV"], DENSE),), "wv": ((-1, u["KV"], DENSE),),
            "wuq": ((-1, u["H"], DENSE),), "wuk": ((-1, u["H"], DENSE),),
            "wuv": ((-1, u["H"], DENSE),),
        }
        return Rule(table.get(name, ()))
    if parent == "shared":
        table = {
            "w_up": ((-1, u.get("SDFF", 0), DENSE),),
            "w_gate": ((-1, u.get("SDFF", 0), DENSE),),
            "w_down": ((-2, u.get("SDFF", 0), DENSE),),
        }
        return Rule(table.get(name, ()))
    if parent == "ffn":
        table = {
            "w_up": ((-1, u["DFF"], DENSE),),
            "w_gate": ((-1, u["DFF"], DENSE),),
            "w_down": ((-2, u["DFF"], DENSE),),
            "e_gate": ((-3, u.get("E", 0), EXPERT),
                       (-1, u.get("DFFE", 0), MODEL_ONLY)),
            "e_up": ((-3, u.get("E", 0), EXPERT),
                     (-1, u.get("DFFE", 0), MODEL_ONLY)),
            "e_down": ((-3, u.get("E", 0), EXPERT),
                       (-2, u.get("DFFE", 0), MODEL_ONLY)),
        }
        return Rule(table.get(name, ()))
    if parent == "mixer":
        n = u["NH"] if cfg.ssm else u.get("W", 0)
        table = {
            "w_z": ((-1, n, DENSE),), "w_x": ((-1, n, DENSE),),
            "w_dt": ((-1, n, DENSE),), "conv_x": ((-1, n, DENSE),),
            "conv_b_x": ((-1, n, DENSE),), "A_log": ((-1, n, DENSE),),
            "D": ((-1, n, DENSE),), "dt_bias": ((-1, n, DENSE),),
            "norm_w": ((-1, n, DENSE),), "w_out": ((-2, n, DENSE),),
            "w_gate": ((-1, n, DENSE),), "conv_w": ((-1, n, DENSE),),
            "conv_b": ((-1, n, DENSE),), "lam": ((-1, n, DENSE),),
            "gate_a_w": ((-1, n, DENSE),), "gate_a_b": ((-1, n, DENSE),),
            "gate_i_w": ((-1, n, DENSE),), "gate_i_b": ((-1, n, DENSE),),
        }
        return Rule(table.get(name, ()))
    # embed level
    table = {
        "tok": ((-2, u["V"], DENSE),),
        "head": ((-1, u["V"], DENSE),),
    }
    return Rule(table.get(name, ()))


def _path_keys(path) -> Tuple[str, ...]:
    keys = []
    for e in path:
        if isinstance(e, jax.tree_util.DictKey):
            keys.append(str(e.key))
        elif isinstance(e, jax.tree_util.SequenceKey):
            keys.append(f"[{e.idx}]")
        else:
            keys.append(str(e))
    return tuple(k for k in keys if not k.startswith("["))


class WeightsManager:
    """Owns the canonical layout + zero-copy reinterpretation."""

    def __init__(self, cfg: ArchConfig, plan: ParallelPlan):
        self.cfg = cfg
        self.plan = plan
        self.storage = plan.engine_rows * plan.tp_base

    # -- specs ----------------------------------------------------------
    def _spec_for(self, rule: Rule, shape: Tuple[int, ...],
                  train: bool) -> P:
        ndim = len(shape)
        entries: List[Any] = [None] * ndim
        for (from_end, n, kind) in rule.dims:
            d = ndim + from_end
            if d < 0 or n <= 0:
                continue
            if kind == DENSE:
                if train:
                    if n % self.plan.tp_base == 0:
                        entries[d] = "model"
                elif n % self.storage == 0:
                    entries[d] = ("ed", "model") if self.plan.engine_rows > 1 \
                        else "model"
            elif kind == EXPERT and not train:
                if self.plan.engine_rows > 1 and \
                        n % self.plan.engine_rows == 0:
                    entries[d] = "ed"
            elif kind == EXPERT and train:
                # EP over 'model' in training: batch is data-sharded, so
                # expert-local compute only pays one y-combine all-reduce
                # over 'model' instead of resharding the dispatch buffer
                # against the token sharding (§Perf B1)
                if n % self.plan.tp_base == 0:
                    entries[d] = "model"
            elif kind == MODEL_ONLY:
                if n % self.plan.tp_base == 0 and "model" not in entries:
                    entries[d] = "model"
        if train and self.plan.engine_rows > 1 and "data" not in entries:
            # ZeRO-3-style: giants additionally shard a free large dim over
            # 'data'; GSPMD inserts the per-layer all-gathers.
            for d in range(ndim):
                if entries[d] is None and shape[d] % self.plan.data_rows == 0 \
                        and shape[d] >= 1024:
                    entries[d] = "data"
                    break
        return P(*entries)

    def partition_specs(self, params_tree, train: bool = False):
        """Pytree of PartitionSpec matching ``params_tree`` structure."""
        def per_leaf(path, leaf):
            rule = rule_for(self.cfg, _path_keys(path))
            return self._spec_for(rule, tuple(leaf.shape), train)
        return jax.tree_util.tree_map_with_path(per_leaf, params_tree)

    def shardings(self, params_tree, mesh, train: bool = False):
        specs = self.partition_specs(params_tree, train)
        return jax.tree.map(lambda s: NamedSharding(mesh, s), specs)

    def init_params(self, model, mesh, key):
        """Seeded random weights created directly in the canonical
        placement over ``mesh``: every device materializes only its own
        shard, never the whole model first."""
        shardings = self.shardings(model.param_specs(), mesh)
        return jax.jit(model.init, out_shardings=shardings)(key)

    # -- zero-copy mode reinterpretation (paper Table 2 '15 ms live') ----
    def reinterpret(self, params, new_mesh, *, check_zero_copy: bool = False):
        """Re-bind the params to another mode mesh. Physically a no-op:
        same device order, same per-device shards."""
        sh = self.shardings(params, new_mesh)
        if check_zero_copy:
            before = jax.tree.leaves(jax.tree.map(_ptrs, params))
        out = jax.tree.map(lambda a, s: jax.device_put(a, s), params, sh)
        if check_zero_copy:
            after = jax.tree.leaves(jax.tree.map(_ptrs, out))
            assert before == after, "reinterpretation moved bytes!"
        return out

    # -- per-island views (heterogeneous fleet layouts) ------------------
    def island_view(self, params, isl_mesh, *,
                    check_zero_copy: bool = False):
        """Island-local view of the canonical params: the same logical
        weights re-bound over ONE island's sub-mesh. Since the canonical
        layout shards only ('ed','model') (replicated over the DP axes
        the island subsets), every island device already holds exactly
        the shard the island sharding asks for — assembly is pure
        metadata over the resident buffers, asserted when requested."""
        sh = self.shardings(params, isl_mesh)
        return jax.tree.map(
            lambda a, s: shard_view(a, s, check_zero_copy=check_zero_copy),
            params, sh)


def shard_view(a, sharding, shape: Optional[Tuple[int, ...]] = None, *,
               check_zero_copy: bool = False):
    """Assemble an array over a sub-mesh from the per-device shards of
    arrays already resident on those devices — zero-copy (the paper's
    reinterpretation trick, island-locally). ``a`` may be a single source
    array or a dict ``{device: single-device shard}`` drawn from several
    source arrays (a rebind regrouping islands)."""
    if isinstance(a, dict):
        by_dev = a
    else:
        by_dev = {s.device: s.data for s in a.addressable_shards}
        if shape is None:
            shape = tuple(a.shape)
    devs = sharding.mesh.devices.flat
    sds = [by_dev[d] for d in devs]
    out = jax.make_array_from_single_device_arrays(
        tuple(shape), sharding, sds)
    if check_zero_copy:
        before = tuple(sorted(s.unsafe_buffer_pointer() for s in sds))
        assert _ptrs(out) == before, "island view moved bytes!"
    return out


def _ptrs(a):
    return tuple(sorted(s.data.unsafe_buffer_pointer()
                        for s in a.addressable_shards))
