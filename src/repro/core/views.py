"""Logical weight views — the device-side half of the Model Weights
Manager (paper §4.1, Eq. 1).

Storage convention (DESIGN.md §2.2): weights are *stored* sharded over the
engine-tile axes ``('ed','model')`` (when the partition dim divides) and
**replicated** over the DP axes ``('dp','merge')``. Inside a mode's
``shard_map`` each device holds its full engine shard. Merging ``m``
engines into a TP group does not reshard storage; each device *activates*
a rank-selected slice of its resident shard:

    W_active = View(W_full, dim, rank, m)          (paper Eq. 1)

All parallel degrees here are powers of two (mesh axes are), which gives
nested shardings: for a dimension of n units the compute shard count is
``want = min(2**v2(n), tp)``; devices in excess of ``want`` replicate
compute (``rep = tp // want``) and row-parallel partial sums are
pre-scaled by ``1/rep`` so a single full-group psum stays correct. This
generalizes the paper's per-head views to GQA KV heads (kv < tp) and to
architectures whose head counts don't divide the TP degree.

``TPContext`` is static per compiled mode (the communicator pool compiles
one program per mode); with ``tp == 1`` every helper degrades to the
identity so the same model code serves the single-device reference path
and the GSPMD training path.
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax


def v2(n: int) -> int:
    """2-adic valuation."""
    if n <= 0:
        return 0
    k = 0
    while n % 2 == 0:
        n //= 2
        k += 1
    return k


def pow2_shards(n: int, tp: int) -> int:
    """Largest power-of-two shard count for an n-unit dim under degree tp."""
    return min(1 << v2(n), tp) if n > 0 else 1


@dataclass(frozen=True)
class TPContext:
    """Static parallel-execution geometry for one compiled mode."""

    tp: int = 1           # total TP degree = view_m * storage_shards
    view_m: int = 1       # merge factor realized by views over replicated storage
    tp_axes: Tuple[str, ...] = ()     # ('merge','ed','model') on the mode mesh
    view_axes: Tuple[str, ...] = ()   # ('merge',)
    ep_axes: Tuple[str, ...] = ()     # expert-parallel storage axes ('ed',)
    ep: int = 1
    # GSPMD training: dispatch MoE per data shard (capacity and scatter
    # stay shard-local; §Perf B2). 1 = global dispatch.
    moe_groups: int = 1

    @property
    def storage_shards(self) -> int:
        return self.tp // self.view_m

    # ---- traced ranks ------------------------------------------------
    def _rank_over(self, axes: Tuple[str, ...]):
        r = 0
        for ax in axes:
            r = r * lax.axis_size(ax) + lax.axis_index(ax)
        return r

    def view_rank(self):
        return self._rank_over(self.view_axes) if self.view_axes else 0

    def storage_rank(self):
        axes = tuple(a for a in self.tp_axes if a not in self.view_axes)
        return self._rank_over(axes) if axes else 0

    def storage_major_rank(self):
        """Rank ordering in which consecutive ranks share a storage shard
        contiguously: r = storage_rank * view_m + view_rank."""
        if self.tp == 1:
            return 0
        return self.storage_rank() * self.view_m + self.view_rank()

    def ep_rank(self):
        return self._rank_over(self.ep_axes) if self.ep_axes else 0

    # ---- sharding arithmetic ------------------------------------------
    def stored_shards(self, n: int) -> int:
        """Storage shard count the weights manager uses for an n-unit dim:
        full engine-tile sharding when divisible, else replicated."""
        s = self.storage_shards
        return s if (n % s == 0) else 1

    def compute_shards(self, n: int) -> int:
        return pow2_shards(n, self.tp)

    def replication(self, n: int) -> int:
        """How many devices replicate each compute slice of an n-unit dim
        (row-parallel partials must be pre-scaled by 1/replication)."""
        return self.tp // self.compute_shards(n)

    def local_units(self, n: int) -> int:
        return n // self.compute_shards(n)

    def stored_units(self, n: int) -> int:
        """Units of an n-unit dim in this device's STORAGE shard (the
        merge-1-equivalent frame): what the live cross-layout read path
        computes in before slicing back to the mode's compute shard."""
        return n // self.stored_shards(n)

    # ---- the view primitive (paper Eq. 1) ------------------------------
    def activate(self, w: jax.Array, dim: int, n: int) -> jax.Array:
        """Produce this device's compute slice of a weight whose ``dim``
        holds ``n`` logical units. ``w`` is the local *storage* shard
        (``stored_shards(n)``-way). Identity when nothing to slice."""
        if self.tp == 1:
            return w
        stored = self.stored_shards(n)
        want = self.compute_shards(n)
        if want == stored:
            return w
        if stored == 1:
            idx = (self.storage_major_rank() * want) // self.tp
            cnt = want
        else:
            rep = self.tp // want
            idx = self.view_rank() // rep
            cnt = want // stored
        size = w.shape[dim] // cnt
        starts = [0] * w.ndim
        starts[dim] = idx * size
        sizes = list(w.shape)
        sizes[dim] = size
        return lax.dynamic_slice(w, starts, sizes)

    def activate_view(self, w: jax.Array, dim: int) -> jax.Array:
        """Slice ``dim`` by the merge (view) rank only — for tensors whose
        storage axes are managed separately (e.g. MoE expert weights:
        expert dim over 'ed', d_ff over 'model', merge realized here)."""
        if self.view_m == 1:
            return w
        size = w.shape[dim] // self.view_m
        starts = [0] * w.ndim
        starts[dim] = self.view_rank() * size
        sizes = list(w.shape)
        sizes[dim] = size
        return lax.dynamic_slice(w, starts, sizes)

    # ---- striped-cache (context-parallel) helpers -----------------------
    def slice_of_rank(self, r: int, n: int) -> int:
        """STATIC map: which logical slice of an n-unit dim rank r computes
        (mirrors activate()'s traced indexing)."""
        stored = self.stored_shards(n)
        want = self.compute_shards(n)
        storage = self.storage_shards
        view_rank = r // storage
        storage_rank = r % storage
        if stored == 1:
            smr = storage_rank * self.view_m + view_rank
            return (smr * want) // self.tp
        rep = self.tp // want
        return storage_rank * (want // stored) + view_rank // rep

    def gather_heads(self, x: jax.Array, n: int, axis: int) -> jax.Array:
        """All-gather a head-sharded tensor back to full logical heads
        (deduplicating replicas, restoring logical order). x has n//shards
        units along ``axis``; returns n units. Used by the striped-cache
        attention (context parallelism), where every device needs all
        query heads against its sequence stripe."""
        if self.tp == 1:
            return x
        want = self.compute_shards(n)
        g = lax.all_gather(x, self.tp_axes, axis=0, tiled=False)  # [tp,...]
        # pin the wire dtype: without the barrier the CPU backend widens
        # the downstream bf16 dot to f32 and the simplifier hoists the
        # convert back across the gather, silently re-widening the wire
        # (§Perf C1; TPU consumes bf16 natively)
        g = lax.optimization_barrier(g)
        # one representative rank per logical slice, in slice order
        reps = [None] * want
        for r in range(self.tp):
            s = self.slice_of_rank(r, n)
            if reps[s] is None:
                reps[s] = r
        g = g[jnp.asarray(reps)]                  # [want, ...]
        g = jnp.moveaxis(g, 0, axis)              # [..., want, local, ...]
        shape = list(x.shape)
        shape[axis] = shape[axis] * want
        return g.reshape(shape)

    def stripe_index(self):
        """This device's sequence-stripe index within the TP group (the
        rank ordering is arbitrary but fixed; writes and reads agree)."""
        return self._rank_over(self.tp_axes) if self.tp_axes else 0

    def lse_merge(self, acc: jax.Array, l: jax.Array, m: jax.Array,
                  wire_dtype=None, axes: Optional[Tuple[str, ...]] = None):
        """Merge online-softmax partials across devices: acc [..,H,D]
        fp32 unnormalized, l [..,H] denominators, m [..,H] maxima ->
        full attention output [..,H,D]. ``axes`` defaults to the full TP
        group (striped/context-parallel merge); the live cross-layout
        read path passes ``view_axes`` only — partials for the SAME
        stored head live across the merge axis, while other
        ('ed','model') positions hold different heads entirely.
        ``wire_dtype`` (e.g. bf16) halves the psum bytes (§Perf C1):
        with w <= 1 the summand is max-normalized, so bf16's 8-bit
        exponent loses only mantissa bits relative to the f32 result."""
        axes = self.tp_axes if axes is None else axes
        if not axes or self.tp == 1:
            return acc / jnp.maximum(l[..., None], 1e-30)
        m_g = lax.pmax(m, axes)
        w = jnp.exp(m - m_g)
        num_in = acc * w[..., None]
        if wire_dtype is not None:
            num_in = num_in.astype(wire_dtype)
        num = lax.psum(num_in, axes)
        if wire_dtype is not None:
            num = lax.optimization_barrier(num)  # keep the wire narrow
        num = num.astype(jnp.float32)
        den = lax.psum(l * w, axes)
        return num / jnp.maximum(den[..., None], 1e-30)

    # ---- collectives ----------------------------------------------------
    def psum(self, x: jax.Array, n: int = 0) -> jax.Array:
        """Row-parallel reduction over the TP group; if the reduced dim had
        ``n`` logical units with replication, pre-scale so duplicates do
        not over-count."""
        if not self.tp_axes or self.tp == 1:
            return x
        if n:
            rep = self.replication(n)
            if rep > 1:
                x = x / rep
        return lax.psum(x, self.tp_axes)

    def psum_scaled(self, x: jax.Array, rep: int) -> jax.Array:
        if not self.tp_axes or self.tp == 1:
            return x
        if rep > 1:
            x = x / rep
        return lax.psum(x, self.tp_axes)

    # ---- expert parallel -------------------------------------------------
    def ep_stored(self, n_experts: int) -> int:
        return self.ep if (self.ep > 1 and n_experts % self.ep == 0) else 1


SINGLE = TPContext()


def make_serving_ctx(merge: int, engine_rows: int, tp_base: int,
                     n_experts: int = 0) -> TPContext:
    """TPContext for a flying-serving mode under shard_map on the mode
    mesh ('dp','merge','ed','model')."""
    tp = merge * engine_rows * tp_base
    ep = engine_rows if (n_experts and n_experts % engine_rows == 0
                         and engine_rows > 1) else 1
    return TPContext(
        tp=tp,
        view_m=merge,
        tp_axes=("merge", "ed", "model"),
        view_axes=("merge",),
        ep_axes=("ed",) if ep > 1 else (),
        ep=ep,
    )
