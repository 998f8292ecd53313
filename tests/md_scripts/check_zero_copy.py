"""Zero-copy invariants (paper §4.1/§4.2) with 8 forced host devices:
mode-mesh reinterpretation of weights AND the flat KV pool moves no
bytes (buffer pointers identical)."""
import os

os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.configs import get_config
from repro.core.kv_adaptor import PoolGeometry
from repro.core.modes import FlyingMode, ParallelPlan, mode_mesh
from repro.core.weights_manager import WeightsManager, _ptrs
from repro.models.model import build_model


def main():
    cfg = get_config("llama3-8b").reduced()
    model = build_model(cfg, jnp.float32)
    params = model.init(jax.random.key(0))
    plan = ParallelPlan(engine_rows=1, tp_base=2, data_rows=4)
    wm = WeightsManager(cfg, plan)

    meshes = {m: mode_mesh(FlyingMode(plan, m)) for m in (1, 2, 4)}
    p = jax.device_put(params, wm.shardings(params, meshes[1]))
    base_ptrs = jax.tree.leaves(jax.tree.map(_ptrs, p))
    for m in (2, 4, 1, 2):
        p = wm.reinterpret(p, meshes[m], check_zero_copy=True)
        assert jax.tree.leaves(jax.tree.map(_ptrs, p)) == base_ptrs
    print("weights: zero-copy across merge modes 1<->2<->4 OK")

    # KV pool: [G1, G2, nblk, B_base, W] leaf, same story
    geom = PoolGeometry(cfg, plan, num_blocks=16, block_base=4)
    pool = jnp.zeros((plan.dp_engines, plan.engine_rows * plan.tp_base)
                     + geom.pool_shape(), jnp.float32)
    spec = P(("pod", "dp", "merge"), ("ed", "model"), None, None, None)
    a = jax.device_put(pool, NamedSharding(meshes[1], spec))
    ptrs = _ptrs(a)
    for m in (2, 4, 1):
        a = jax.device_put(a, NamedSharding(meshes[m], spec))
        assert _ptrs(a) == ptrs, f"pool moved at merge={m}"
    print("kv pool: zero-copy across merge modes OK")
    print("ZERO-COPY OK")


if __name__ == "__main__":
    main()
