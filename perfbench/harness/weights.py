"""The benchmark's own weights, made on the device from ``--seed``.

The program is handed these in place of the ones it would make: the
program's seam (``WeightsManager.init_params``) is swapped for
:func:`make_params` while the stack is built, so the program places
them in its own layout and shardings, and the reference reads the very
same arrays without taking anything the program made.

Every matrix is drawn N(0, 1/fan_in), so each layer's output has unit
scale and the logits come out about N(0, 1): the top tokens are not tied
and not far apart, which is what lets a logit gap tell a correct token
from a wrong one. Norm gains are U(0.8, 1.2) so that a norm weight left
out changes the result. The token embedding is N(0, 1).
"""
from __future__ import annotations

import contextlib
from typing import Dict

import jax
import jax.numpy as jnp


def seed_key(seed: int):
    """A PRNG key from a seed of any size: the low and the high 32 bits
    both count (``jax.random.key`` alone drops the high ones)."""
    s = int(seed) % (1 << 64)
    k = jax.random.key(s & 0xFFFFFFFF)
    return jax.random.fold_in(k, s >> 32)


def _leaf(name: str, shape, dtype, key):
    if name.startswith("norm") or name in ("q_norm", "k_norm"):
        v = jax.random.uniform(key, shape, jnp.float32, 0.8, 1.2)
    elif name == "tok":
        v = jax.random.normal(key, shape, jnp.float32)
    elif len(shape) >= 2:
        v = jax.random.normal(key, shape, jnp.float32) * shape[-2] ** -0.5
    else:
        v = jax.random.normal(key, shape, jnp.float32) * 0.1
    return v.astype(dtype)


def _name(path) -> str:
    keys = [str(e.key) for e in path
            if isinstance(e, jax.tree_util.DictKey)]
    return keys[-1]


def make_params(specs, shardings, seed: int):
    """Weights for the parameter tree ``specs`` (shapes and dtypes), in
    one jitted call that writes each device's shard in place."""
    flat, treedef = jax.tree_util.tree_flatten_with_path(specs)

    def gen(key):
        return treedef.unflatten([
            _leaf(_name(path), s.shape, s.dtype, jax.random.fold_in(key, i))
            for i, (path, s) in enumerate(flat)])

    return jax.jit(gen, out_shardings=shardings)(seed_key(seed))


@contextlib.contextmanager
def weights_from_seed(seed: int):
    """While active, the program's weight initialization makes the
    benchmark's weights instead."""
    from repro.core.weights_manager import WeightsManager
    orig = WeightsManager.init_params

    def init_params(self, model, mesh, key):
        specs = model.param_specs()
        return make_params(specs, self.shardings(specs, mesh), seed)

    WeightsManager.init_params = init_params
    try:
        yield
    finally:
        WeightsManager.init_params = orig


def named(params) -> Dict:
    """The tensors the reference reads, by name, from the tree the
    weights were handed to the program in: ``groups[0][0]`` holds the
    layers stacked on a leading axis. On several devices each holds a
    whole copy; the one on the first device is taken."""
    def local(a):
        s = a.addressable_shards[0]
        assert s.data.shape == a.shape, "weights are expected replicated"
        return s.data

    emb = params["embed"]
    layer = params["groups"][0][0]
    out = {"tok": local(emb["tok"]), "norm_f": local(emb["norm_f"]),
           "head": local(emb["head"]) if "head" in emb else None}
    att, ffn = layer["attn"], layer["ffn"]
    out["layers"] = {
        "norm1": local(layer["norm1"]), "norm2": local(layer["norm2"]),
        "wq": local(att["wq"]), "wk": local(att["wk"]),
        "wv": local(att["wv"]), "wo": local(att["wo"]),
        "w_up": local(ffn["w_up"]), "w_gate": local(ffn["w_gate"]),
        "w_down": local(ffn["w_down"])}
    if "q_norm" in att:
        out["layers"]["q_norm"] = local(att["q_norm"])
        out["layers"]["k_norm"] = local(att["k_norm"])
    return out
