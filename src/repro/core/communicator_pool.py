"""Communicator Pool (paper §4.3) — TPU adaptation.

On GPU the reconfiguration bottleneck is NCCL process-group creation
(seconds); on TPU/XLA it is *compilation* of the per-mode SPMD program.
The pool therefore eagerly builds, for every topologically valid mode
(contiguous power-of-two merges — paper §4.3 step 1):

  - the mode Mesh (the "communicator group": which devices collective
    with which, over which axes), and
  - the compiled step executables, keyed by island SHAPE —
    ``(island_merge, phase, variants..., n_engines)`` (paper step 2's
    ``Map<Tuple[int], Group>`` hash map).

Heterogeneous fleet layouts (``modes.FleetLayout``) run one step
program per ISLAND. Runners are keyed by the island's shape, not its
position: the step is traced over an AbstractMesh of the shape, so
every same-shape island — wherever it sits in the fleet — shares one
runner and the key space stays linear (``modes.island_shapes``), the
concrete device slice resolving from the island-committed params and
states at call time.

At runtime a mode switch is an O(1) dict lookup (paper: "retrieved in
O(1) time"); nothing is created on the critical path. ``stats`` counts
the step programs built (compiled, or loaded from the persistent cache)
and their host seconds, on the serving path too: a runner call whose
jitted function's cache grew across the call was a build. The cache's
size is ``jax.jit``'s private ``_cache_size()``, read twice a launch
with two clock reads; a JAX that drops it fails
``test_a_runner_call_that_builds_counts_once`` on the CPU.

Each step program is named for its phase and island shape
(``fs_<phase>_n<engines>m<merge>``, ``s<sp>`` on SP islands): the XLA
module of every run carries the name, and each call is an ``fs.launch``
span (``core/tracing.py``).

Hot-path contract (§Perf D):
  - ``runner(..., sampled=True)`` compiles the sampling-fused step:
    outputs are device-resident ``[B]`` token ids, never host logits.
  - ``runner(..., donate=True)`` donates the state pytree
    (``jax.jit(..., donate_argnums=(1,))``): per-layer KV pools update
    in place instead of being duplicated every step — the multi-GB
    state tree is never copied on the critical path, halving peak state
    memory.
  - batch/seq extents are bucketed with ``bucket_pow2``; callers pad
    their host batches to the bucket so chunk-length variation hits an
    already-compiled executable instead of triggering a recompile.
"""
from __future__ import annotations

import time
import warnings
from dataclasses import dataclass, field
from typing import Any, Callable, Dict, Optional, Tuple

import jax

from repro.configs.base import ArchConfig
from repro.core import tracing
from repro.core.kv_adaptor import PoolGeometry
from repro.core.modes import (FlyingMode, Island, ParallelPlan,
                              island_abstract_mesh, island_mesh,
                              island_mode, mode_mesh)
from repro.core.steps import build_serve_step

_donation_quieted = False


def _quiet_unused_donation() -> None:
    """The CPU backend copies instead of aliasing when XLA declines a
    donation; the fallback is correct, just not in-place — don't warn
    once per step. Registered once, only when the first donating runner
    is created, never as an import side effect (runner keys multiply
    with mb/seq buckets; re-registering would grow warnings.filters)."""
    global _donation_quieted
    if _donation_quieted:
        return
    _donation_quieted = True
    warnings.filterwarnings(
        "ignore", message="Some donated buffers were not usable")


def bucket_pow2(n: int, lo: int = 1) -> int:
    b = lo
    while b < n:
        b *= 2
    return b


def _runner_key(island: Island, phase: str, sampled: bool, donate: bool,
                bb, sb, mb, live) -> Tuple:
    return (island.merge, phase, sampled, donate, bb, sb, mb,
            island.n_engines, live, island.sp)


def _program(name: str, key: Tuple) -> str:
    """Short id of a runner key: ``fs_decode_n1m1/b16/s1/mb8``."""
    bb, sb, mb, live = key[4], key[5], key[6], key[8]
    return "/".join([name, f"b{bb}", f"s{sb}", f"mb{mb}"]
                    + (["-".join(map(str, live))] if live else []))


@dataclass
class PoolStats:
    """Step programs built: compiled, or loaded from the persistent
    compilation cache (``precompile`` and serving-path calls alike)."""
    compiles: int = 0
    compile_s: float = 0.0          # host seconds of the calls that built
    last_compiled: str = ""         # program id of the newest build


class CommunicatorPool:
    """Per-mode meshes + eagerly compiled executables."""

    def __init__(self, model, plan: ParallelPlan, geom: PoolGeometry, *,
                 use_kernel: Optional[bool] = None,
                 chunked_prefill: bool = True,
                 window: Optional[int] = None,
                 sample: Tuple[float, int] = (0.0, 0)):
        self.model = model
        self.plan = plan
        self.geom = geom
        self.use_kernel = use_kernel
        self.chunked = chunked_prefill
        self.window = window
        self.sample = sample  # (temperature, top_k) for sampled runners
        # step 1: topology-aware group identification (contiguous, pow2)
        self.modes: Dict[int, FlyingMode] = {
            m: FlyingMode(plan, m) for m in plan.valid_merges()}
        self.meshes: Dict[int, jax.sharding.Mesh] = {
            m: mode_mesh(fm) for m, fm in self.modes.items()}
        self._island_meshes: Dict[Island, jax.sharding.Mesh] = {}
        self._runners: Dict[Tuple, Callable] = {}
        self._jitted: Dict[Tuple, Callable] = {}
        self._compiled: Dict[Tuple, Any] = {}
        self.stats = PoolStats()

    # ------------------------------------------------------------------
    def _as_island(self, island) -> Island:
        """Accept an Island or (seed-era API) a bare fleet-wide merge."""
        if isinstance(island, Island):
            return island
        return Island(0, self.plan.pods * self.plan.dp_engines, island)

    def island_mesh(self, island: Island) -> jax.sharding.Mesh:
        """Concrete mesh over one island's device slice (cached)."""
        m = self._island_meshes.get(island)
        if m is None:
            m = island_mesh(self.plan, island)
            self._island_meshes[island] = m
        return m

    def runner(self, island, phase: str, *, sampled: bool = False,
               donate: bool = False, batch_bucket: Optional[int] = None,
               seq_bucket: Optional[int] = None,
               mb_bucket: Optional[int] = None,
               live: Optional[Tuple[int, ...]] = None) -> Callable:
        """Step fn for (island shape, phase, variant): a call of its
        jitted step program, under an ``fs.launch`` span, that counts a
        build in ``stats`` when the call compiled (or loaded) one.

        ``island`` is an ``Island`` (or a bare merge, meaning the
        degenerate whole-fleet island). The runner key is the island's
        SHAPE — ``(merge, phase, variants..., n_engines)`` — so two
        same-shape islands anywhere in the fleet share one runner: the
        step is traced over an AbstractMesh and the concrete devices
        resolve from the committed inputs.

        ``batch_bucket``/``seq_bucket``/``mb_bucket`` are ``bucket_pow2``
        extents the caller pads its host batch to (§4.3 step 2 key
        tuple); they keep one compiled shape per bucketed runner so
        chunk-length variation never recompiles on the critical path.
        ``mb_bucket`` is the block-table width (§Perf D5): a batch of
        short contexts runs a narrow executable whose attention cost
        tracks live context, even when the engine is configured for a
        long-context ``max_blocks``.

        ``live`` (§D8/§D12) selects the cross-layout read variant: the
        ordered lane-tag tuple of the block segments the batch may carry
        (the per-lane table widths ride in the traced batch shapes).
        ``None`` is the unchanged single-view program. A
        sequence-parallel island (``island.sp > 1``) compiles the SP
        write variant of the live program — ``sp`` is part of the
        runner key, so an SP island never shares an executable with a
        plain merge island of the same shape.
        """
        island = self._as_island(island)
        key = _runner_key(island, phase, sampled, donate, batch_bucket,
                          seq_bucket, mb_bucket, live)
        fn = self._runners.get(key)
        if fn is None:
            jitted = self._jit(island, phase, key)
            program = _program(jitted.__name__, key)

            def fn(params, states, batch):
                with tracing.span("fs.launch", program=program):
                    n = jitted._cache_size()
                    t0 = time.perf_counter()
                    out = jitted(params, states, batch)
                    if jitted._cache_size() > n:
                        self._built(program, time.perf_counter() - t0)
                return out
            self._runners[key] = fn
        return fn

    def _jit(self, island: Island, phase: str, key: Tuple):
        """The jitted step program of a runner key, named
        ``fs_<phase>_n<engines>m<merge>`` (``s<sp>`` on SP islands)."""
        jitted = self._jitted.get(key)
        if jitted is None:
            sampled, donate, live, sp = key[2], key[3], key[8], key[9]
            if donate:
                _quiet_unused_donation()
            run, _, _ = build_serve_step(
                self.model, island_mode(self.plan, island), self.geom,
                phase=phase, window=self.window, use_kernel=self.use_kernel,
                chunked=(phase == "prefill" and self.chunked),
                sample=self.sample if sampled else None, live=live, sp=sp,
                mesh=island_abstract_mesh(self.plan, island.shape))
            name = f"fs_{phase}_n{island.n_engines}m{island.merge}" \
                + (f"s{sp}" if sp > 1 else "")

            def step(params, states, batch):
                with jax.named_scope(name):
                    return run(params, states, batch)
            step.__name__ = step.__qualname__ = name
            jitted = self._jitted[key] = jax.jit(
                step, donate_argnums=(1,) if donate else ())
        return jitted

    def _built(self, program: str, seconds: float) -> None:
        self.stats.compiles += 1
        self.stats.compile_s += seconds
        self.stats.last_compiled = program

    # -- step 2: pre-initialization --------------------------------------
    def precompile(self, island, phase: str, abstract_args, *,
                   sampled: bool = False, donate: bool = False,
                   live: Optional[Tuple[int, ...]] = None) -> Any:
        """Eagerly lower+compile one executable (startup phase).
        ``island`` is an Island or a bare whole-fleet merge."""
        island = self._as_island(island)
        key = self._key(island, phase, abstract_args, sampled, donate)
        if key in self._compiled:
            return self._compiled[key]
        t0 = time.perf_counter()
        rkey = _runner_key(island, phase, sampled, donate, *key[4:7], live)
        jitted = self._jit(island, phase, rkey)
        compiled = jitted.lower(*abstract_args).compile()
        self._built(_program(jitted.__name__, rkey),
                    time.perf_counter() - t0)
        self._compiled[key] = compiled
        return compiled

    def get(self, island, phase: str, abstract_args,
            allow_compile: bool = True, *, sampled: bool = False,
            donate: bool = False) -> Any:
        """O(1) retrieval on the serving critical path."""
        island = self._as_island(island)
        key = self._key(island, phase, abstract_args, sampled, donate)
        hit = self._compiled.get(key)
        if hit is not None:
            return hit
        if not allow_compile:
            raise KeyError(f"executable {key} not pre-initialized")
        return self.precompile(island, phase, abstract_args,
                               sampled=sampled, donate=donate)

    def _key(self, island: Island, phase: str, abstract_args,
             sampled: bool = False, donate: bool = False) -> Tuple:
        """(merge, phase, variant, batch_bucket, seq_bucket, mb_bucket,
        n_engines, shapes) — the §4.3 hash-map key, island-shape scoped.
        Callers pad their host batches to pow2 buckets BEFORE calling
        (the engine does), so the padded token extents AND the
        block-table width ARE the bucket ids — deriving them from the
        abstract shapes keeps precompile/get keys identical to the
        runner keys the engine uses at serve time."""
        batch = abstract_args[2]
        get = batch.get if hasattr(batch, "get") else (lambda k: None)
        # mixed-phase batches prefix their parts: the chunk bucket is the
        # prefill token extent, the (shared) mb bucket its table width
        tok = get("tokens")
        if tok is None:
            tok = get("p_tokens")
        bt = get("block_table")
        if bt is None:
            bt = get("p_block_table")
        bb = tok.shape[0] if tok is not None else None
        sb = tok.shape[1] if tok is not None and tok.ndim > 1 else None
        mb = bt.shape[1] if bt is not None and bt.ndim > 1 else None
        if hasattr(batch, "items"):
            # NAMED shapes: live-variant batches (§D8) differ by which
            # per-tag tables they carry even when the leaf shapes
            # coincide — anonymous leaves would collide executables
            shapes = tuple(sorted(
                (k, tuple(a.shape), str(a.dtype))
                for k, a in batch.items()))
        else:
            shapes = tuple(jax.tree.leaves(jax.tree.map(
                lambda a: (tuple(a.shape), str(a.dtype)), batch)))
        return (island.merge, phase, sampled, donate, bb, sb, mb,
                island.n_engines, shapes)

    def memory_overhead_bytes(self) -> int:
        """Analogue of the paper's ~2MB/group measurement: serialized
        executable sizes held by the pool."""
        return sum(c.memory_analysis().generated_code_size_in_bytes
                   for c in self._compiled.values())
