"""FlyingEngine: real-execution runtime over a heterogeneous fleet.

Binds the four substrate pieces on actual devices: canonical-layout
weights (Model Weights Manager), invariant flat KV pools (KV Cache
Adaptor), per-island-shape meshes + compiled executables (Communicator
Pool), and per-engine allocators. Implements the scheduler Backend
protocol, so the same DynamicScheduler drives simulation and real
execution.

The fleet runs a ``FleetLayout``: an ordered partition of the engine
tiles into contiguous pow2-aligned islands, each with its OWN merge
(``modes.FleetLayout``; a uniform mode is the single-island degenerate
case). Every island owns a zero-copy *view* of the canonical params and
of its slice of the state pools, plus its own async token ring, decode
cache, and sync counters — per-island launches dispatch back-to-back,
so JAX async dispatch overlaps islands the way it overlaps steps.

``rebind(layout)`` is the partial-transition primitive: (a) O(1)
executable lookup per island shape, (b) zero-copy re-assembly of
param/state views for RESHAPED islands only (asserted: same buffer
pointers), (c) O(1) adaptor metadata update. Islands present in both
layouts are untouched — their in-flight windows stay open, their decode
caches stay warm, their ``sync_stats.drains`` does not move. Recurrent
states (SSM/hybrid) are the one piece the paper's KV trick cannot
virtualize — reshaped islands rebuild them (documented in DESIGN.md §5).

Zero-sync hot path (docs/PERF.md): steady-state decode performs no host
synchronization and no per-token device->host transfer. Sampling is
fused into the compiled step (device-resident ``[B]`` token ids feed
straight back into the next step), the state pytree is donated so KV
pools update in place, host batch prep is vectorized numpy over
persistent per-island buffers, and steps run ahead of the host inside a
bounded per-island in-flight window. Tokens surface only at drain
points (island rebinds, ``generated_tokens``) as batched transfers.
``sync_stats`` counts every class of host crossing fleet-wide;
``island_sync_stats`` scopes the same counters per island so tests can
assert a rebind drained ONLY the islands it reshaped.

Prefill is truly chunked (§Perf D6): long prompts stream through
``prefill_chunk``-sized slices with absolute positions and per-request
prior lengths, and when prefill chunks co-reside with a decode batch
the scheduler drives ``mixed()`` — one compiled launch per island
covering both phases, with promoted requests' first tokens routed on
device.
"""
from __future__ import annotations

import time
from dataclasses import dataclass
from typing import Dict, List, Optional, Sequence, Tuple, Union

import numpy as np

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding
from jax.sharding import PartitionSpec as P

from repro.core import tracing
from repro.core.communicator_pool import CommunicatorPool, bucket_pow2
from repro.core.faults import TransitionFault
from repro.core.kv_adaptor import (KVCacheAdaptor, PoolGeometry,
                                   bind_fleet, ragged_arange)
from repro.core.modes import FleetLayout, Island, ParallelPlan
from repro.core.task_pool import Request, prompt_token_ids
from repro.core.views import make_serving_ctx
from repro.core.weights_manager import WeightsManager, shard_view
from repro.models.model import Model


@dataclass
class SyncStats:
    """Host<->device crossings on the serving path.

    ``host_argmax`` is the guarded quantity: per-request device->host
    logit reads (the legacy path does one per token). The fused path
    keeps it at zero; tokens leave the device only as whole-batch
    harvests (``d2h_batched``) at drain points."""
    steps: int = 0            # compiled steps launched
    host_argmax: int = 0      # per-token device->host reads (legacy path)
    d2h_batched: int = 0      # batched [B] token harvests (drain points)
    window_waits: int = 0     # bounded in-flight window completion waits
    drains: int = 0           # explicit drain events (rebinds, readout)


class _DecodeCache:
    """Steady-state decode batch state: persistent numpy buffers plus
    incrementally-advanced per-request metadata. While the running set
    is unchanged, per-step batch prep is a handful of whole-array numpy
    ops (lengths += 1, vectorized slot math) — no per-request Python.
    ``mb`` is the bucketed block-table width the staging buffers were
    built for; crossing a bucket boundary rebuilds the cache (§Perf D5).
    ``live`` (§D8) is the sorted tag tuple when any entry's KV spans
    mode-tagged segments: the cache is then re-staged every step (the
    per-tag tables shift as the live segment grows) but keeps its KEY,
    so the device token ring's feed-back fast path — and the zero-sync
    contract — survive the rebind the segments came from."""
    __slots__ = ("key", "rows", "row_reqs", "entries", "lengths", "nblk",
                 "cap", "bufs", "mb", "live")

    def __init__(self, key, rows, row_reqs, entries, lengths, nblk, cap,
                 bufs, mb, live=None):
        self.key = key
        self.rows = rows
        self.row_reqs = row_reqs
        self.entries = entries
        self.lengths = lengths
        self.nblk = nblk
        self.cap = cap
        self.bufs = bufs
        self.mb = mb
        self.live = live


class _IslandRT:
    """Per-island runtime: zero-copy device views plus all the state the
    hot path keeps warm between steps. Untouched by rebinds of OTHER
    islands — the partial-drain contract rides on this isolation."""
    __slots__ = ("island", "mesh", "params", "states", "B", "stats",
                 "pending", "last_tok", "last_src", "last_key", "steady")

    def __init__(self, island: Island, mesh, params, states, B: int):
        self.island = island
        self.mesh = mesh
        self.params = params    # island view of the canonical weights
        self.states = states    # island slice of the state pools
        self.B = B              # island batch rows = n_engines * bpe
        self.stats = SyncStats()
        # async token ring: device arrays not yet harvested to the host
        self.pending: List[Tuple[jax.Array, Tuple[Tuple[int, str], ...]]] \
            = []
        self.last_tok: Dict[str, Tuple[jax.Array, int]] = {}
        self.last_src: Optional[jax.Array] = None
        self.last_key = None
        self.steady: Optional[_DecodeCache] = None


class FlyingEngine:
    def __init__(self, model: Model, plan: ParallelPlan, geom: PoolGeometry,
                 params, *, batch_per_engine: int = 4,
                 max_blocks_per_req: int = 16, prefill_len: int = 32,
                 check_zero_copy: bool = False,
                 use_kernel: Optional[bool] = None,
                 fused_sampling: bool = True, donate_states: bool = True,
                 async_window: int = 2, temperature: float = 0.0,
                 top_k: int = 0, harvest_limit: int = 512,
                 mixed_step: bool = True,
                 layout: Optional[FleetLayout] = None,
                 injector=None, seed_mode: str = "fleet"):
        self.model = model
        self.cfg = model.cfg
        self.plan = plan
        self.geom = geom
        self.bpe = batch_per_engine
        self.max_blocks = max_blocks_per_req
        # retained for callers' convenience only: prompts are NEVER
        # truncated to it (§Perf D6) — chunk extents come from the
        # scheduler's slot allocations, seq buckets from the chunks
        self.prefill_len = prefill_len
        self.check_zero_copy = check_zero_copy
        self.fused = fused_sampling
        self.donate = donate_states
        self.window = max(int(async_window), 0)
        self.temperature = temperature
        self.harvest_limit = max(int(harvest_limit), 1)
        self.mixed_step = mixed_step
        assert seed_mode in ("fleet", "request"), seed_mode
        self.seed_mode = seed_mode
        assert fused_sampling or temperature <= 0.0, \
            "the legacy host path samples greedily; temperature/top_k " \
            "need fused_sampling=True"

        self.pool = CommunicatorPool(model, plan, geom,
                                     use_kernel=use_kernel,
                                     sample=(temperature, top_k))
        self.wm = WeightsManager(self.cfg, plan)
        # canonical placement: the fleet-wide merge=1 mesh; every island
        # holds a zero-copy VIEW of these buffers
        self.mesh = self.pool.meshes[1]
        self.params = jax.device_put(params,
                                     self.wm.shardings(params, self.mesh))
        self.adaptors = [KVCacheAdaptor(geom)
                         for _ in range(plan.dp_engines * plan.pods)]
        self.layout = layout or FleetLayout.uniform(plan, 1)
        assert self.layout.plan == plan
        self.islands: List[_IslandRT] = [
            self._make_rt(isl) for isl in self.layout.islands]
        self._rt_of: Dict[Island, _IslandRT] = {
            rt.island: rt for rt in self.islands}
        bind_fleet(self.adaptors, self.layout)
        self.switch_log: List[float] = []
        self.sync_stats = SyncStats()
        # scripted fault schedule (core/faults.py); the scheduler adopts
        # it from here so one deterministic script drives injection AND
        # detection on the real-execution path
        self.injector = injector
        self._token_buf: Dict[str, List[int]] = {}
        # aborted requests (§D11): ids whose rows were retired WITHOUT
        # an island drain. Their tokens may still sit in in-flight
        # pending rings; harvests drop them instead of buffering.
        # Cleared at the next fleet-wide drain (no pending refs remain).
        self._retired: set = set()
        self._prompt_cache: Dict[str, np.ndarray] = {}
        # legacy host path (fused_sampling=False): latest logits per request
        self.last_logits: Dict[str, np.ndarray] = {}
        # recovery-folded prompts: orig prompt ++ harvested tokens. The
        # seed-based regeneration in _prompt_tokens knows nothing about
        # folds, so recovered requests' prompts must be pinned verbatim.
        self._pinned_prompts: Dict[str, np.ndarray] = {}
        self._bt_scratch: Optional[np.ndarray] = None
        self._host_bufs: Dict[Tuple, Dict[str, np.ndarray]] = {}
        self._seed_iota: Dict[int, jax.Array] = {}
        self._seed_cursor = 0

    # ------------------------------------------------------------------
    @property
    def n_engines(self) -> int:
        return self.plan.dp_engines * self.plan.pods

    @property
    def merge(self) -> int:
        """Fleet-wide merge of the degenerate uniform layout (seed-era
        API); heterogeneous layouts report their widest island."""
        return self.layout.uniform_merge or self.layout.max_merge

    @property
    def states(self):
        """Per-island state trees, in island order (a uniform fleet has
        exactly one)."""
        return [rt.states for rt in self.islands]

    @property
    def _steady(self) -> Optional[_DecodeCache]:
        """Seed-era accessor: the decode cache of a uniform fleet."""
        return self.islands[0].steady if len(self.islands) == 1 else None

    def island_sync_stats(self, island: Island) -> SyncStats:
        """Per-island host-crossing counters: the partial-rebind contract
        surface (an untouched island's ``drains`` must not move)."""
        return self._rt_of[island].stats

    def _global_batch(self) -> int:
        return self.n_engines * self.bpe

    def _resolve(self, island: Union[Island, int]) -> _IslandRT:
        """Island handle -> runtime. A bare int merge (seed-era API)
        addresses the degenerate uniform layout."""
        if isinstance(island, Island):
            rt = self._rt_of.get(island)
            assert rt is not None, \
                f"{island} not in live layout {self.layout.describe()}"
            return rt
        assert self.layout.uniform_merge == island, \
            f"merge={island} vs live layout {self.layout.describe()}"
        return self.islands[0]

    # ------------------------------------------------------------------
    # island views: zero-copy params/state assembly
    # ------------------------------------------------------------------
    def _state_sharding(self, a, mesh):
        spec = P(None, ("pod", "dp", "merge"), ("ed", "model"),
                 *([None] * (a.ndim - 3)))
        return NamedSharding(mesh, spec)

    def _fresh_states(self, isl: Island, mesh):
        """Island state layout [n, G1=isl.n_engines, G2, *per-device
        dims]; paged pools in their physical ``geom.pool_shape()``.
        Identical per-device content to the uniform fleet layout —
        islands only re-scope the group axis. Every leaf is created
        directly with its sharding, so each device allocates only its
        own slice (never the whole fleet's pool on one device)."""
        cfg = self.cfg
        ctx = make_serving_ctx(isl.merge, self.plan.engine_rows,
                               self.plan.tp_base,
                               cfg.moe.num_experts if cfg.moe else 0)
        G1 = isl.n_engines
        G2 = self.plan.engine_rows * self.plan.tp_base
        bpg = self.bpe * isl.merge
        enc_f = cfg.frontend.num_embeds if (cfg.frontend and cfg.enc_dec) \
            else 0
        groups = []
        for kind_seq, n in self.model.plan:
            per = []
            for kind in kind_seq:
                st = self.model.layer_state(
                    kind, ctx=ctx, batch=bpg, num_blocks=self.geom.num_blocks,
                    page=self.geom.capacity(isl.merge), enc_frames=enc_f,
                    make=jax.ShapeDtypeStruct)
                st = dict(st)
                if kind[0] in ("gqa", "gqa_win", "mla"):
                    st["mixer"] = tuple(
                        jax.ShapeDtypeStruct(self.geom.pool_shape(), s.dtype)
                        for s in st["mixer"])
                per.append({k: tuple(
                    jax.ShapeDtypeStruct((n, G1, G2) + tuple(s.shape),
                                         s.dtype)
                    for s in v) for k, v in st.items()})
            groups.append(tuple(per))
        return jax.tree.map(
            lambda s: jnp.zeros(s.shape, s.dtype,
                                device=self._state_sharding(s, mesh)),
            groups)

    def _assemble_states(self, isl: Island, mesh,
                         sources: Sequence[_IslandRT]):
        """Re-scope state pools to a reshaped island from the per-device
        shards the outgoing islands already hold — pure metadata, no
        bytes move (pointer-asserted under check_zero_copy). Only valid
        for paged (batch-invariant flat-pool) states; recurrent archs
        rebuild instead."""
        flats = [jax.tree_util.tree_flatten(rt.states) for rt in sources]
        treedef = flats[0][1]
        n_leaves = len(flats[0][0])
        devs = set(mesh.devices.flat)
        out_leaves = []
        for li in range(n_leaves):
            by_dev = {}
            for leaves, _ in flats:
                for s in leaves[li].addressable_shards:
                    if s.device in devs:
                        by_dev[s.device] = s.data
            src_shape = flats[0][0][li].shape
            shape = (src_shape[0], isl.n_engines) + tuple(src_shape[2:])
            sharding = self._state_sharding(flats[0][0][li], mesh)
            out_leaves.append(shard_view(
                by_dev, sharding, shape,
                check_zero_copy=self.check_zero_copy))
        return jax.tree_util.tree_unflatten(treedef, out_leaves)

    def _make_rt(self, isl: Island,
                 sources: Optional[Sequence[_IslandRT]] = None) -> _IslandRT:
        mesh = self.pool.island_mesh(isl)
        params = self.wm.island_view(self.params, mesh,
                                     check_zero_copy=self.check_zero_copy)
        if sources is None:
            states = self._fresh_states(isl, mesh)
        else:
            states = self._assemble_states(isl, mesh, sources)
        return _IslandRT(isl, mesh, params, states, isl.n_engines * self.bpe)

    # ------------------------------------------------------------------
    # the bind/release primitive: partial rebind
    # ------------------------------------------------------------------
    def rebind(self, layout: Union[FleetLayout, int]) -> float:
        """Transition to another fleet layout, draining ONLY the islands
        it reshapes. Untouched islands (same start/size/merge) keep
        their async in-flight windows, decode caches, and device token
        rings; reshaped islands hit the §5.3 step-boundary safe point,
        then their param/state views re-assemble zero-copy from the
        buffers the outgoing islands held."""
        if isinstance(layout, int):
            layout = FleetLayout.uniform(self.plan, layout)
        assert layout.plan == self.plan
        if layout == self.layout:
            return 0.0
        with tracing.span("fs.rebind", frm=self.layout.describe,
                          to=layout.describe):
            inj = self.injector
            if inj is not None:
                s = inj.take_rebind_fault()
                if s is not None:
                    # scripted failure BEFORE any state moves: the engine
                    # stays bound to the old layout, which is exactly what
                    # the scheduler's rollback assumes
                    raise TransitionFault(
                        f"scripted rebind failure (tick {inj.tick})")
            t0 = time.perf_counter()
            new_set = set(layout.islands)
            changed = [rt for rt in self.islands if rt.island not in new_set]
            changed_engs = {e for rt in changed for e in rt.island.engines()}
            dead: set = set()
            if inj is not None:
                s = inj.take_drain_corrupt(changed_engs)
                if s is not None:
                    bad = (set(s.engines) & changed_engs) or set(s.engines)
                    # the corruption IS the loss of in-flight tokens on the
                    # named islands; layout state is untouched, so rollback
                    # plus recovery (re-prefill from harvested tokens) is
                    # still well-defined
                    for rt in changed:
                        if set(rt.island.engines()) & bad:
                            self._discard_island(rt)
                    raise TransitionFault(
                        "drain corrupted at the rebind safe point",
                        engines=bad)
                dead = set(inj.dead_engines())
            with tracing.span("fs.drain", islands=len(changed)):
                for rt in changed:
                    if set(rt.island.engines()) & dead:
                        # a dead engine cannot answer the drain transfer: its
                        # island's unharvested tokens are lost (recovery
                        # folds whatever reached the host buffer earlier)
                        self._discard_island(rt)
                    else:
                        self._drain_island(rt)
            # recurrent states are per-request and batch-dense, and enc-dec
            # cross caches carry merge-dependent per-device shapes: reshaped
            # islands rebuild those (the documented exception to zero-copy;
            # only batch-invariant flat paged pools re-assemble)
            rebuild = self.cfg.family in ("ssm", "hybrid") \
                or self.cfg.enc_dec is not None
            keep = self._rt_of
            self.islands = [
                keep.get(isl) or self._make_rt(
                    isl, sources=None if rebuild else changed)
                for isl in layout.islands]
            self._rt_of = {rt.island: rt for rt in self.islands}
            self.layout = layout
            bind_fleet(self.adaptors, layout)
            # staging buffers are keyed per island: drop dead islands' so
            # layout churn doesn't grow host memory without bound
            live = set(layout.islands)
            self._host_bufs = {k: v for k, v in self._host_bufs.items()
                               if k[1] in live}
            dt = time.perf_counter() - t0
            self.switch_log.append(dt)
            return dt

    def switch(self, old: int, new: int) -> float:
        """Seed-era uniform transition: rebind to the uniform layout of
        ``new`` (a whole-fleet reshape — everything drains)."""
        if old == new:
            return 0.0
        assert self.layout.uniform_merge == old, \
            f"switch({old},...) vs live layout {self.layout.describe()}"
        return self.rebind(FleetLayout.uniform(self.plan, new))

    # ------------------------------------------------------------------
    # batched execution over the scheduler's request lists
    # ------------------------------------------------------------------
    def _rows(self, reqs: Sequence[Request],
              isl: Island) -> Dict[str, int]:
        """Assign each request a padded-batch row within its island's
        group (requests record ABSOLUTE lead engines, stable across
        rebinds)."""
        bpg = self.bpe * isl.merge
        counters: Dict[int, int] = {}
        rows: Dict[str, int] = {}
        for r in reqs:
            assert isl.start <= r.engine_group < isl.stop, \
                (r.req_id, r.engine_group, isl)
            g = (r.engine_group - isl.start) // isl.merge
            i = counters.get(g, 0)
            assert i < bpg, "group batch overflow"
            rows[r.req_id] = g * bpg + i
            counters[g] = i + 1
        return rows

    def _bufs(self, key: Tuple) -> Dict[str, np.ndarray]:
        """Persistent preallocated host staging buffers, keyed by
        (phase, island, batch, mb_bucket[, seq]) — the block-table stage
        is built at the bucketed width, so short-context batches upload
        (and compile against) a narrow table (§Perf D5). Keyed per
        ISLAND (not shape): two same-shape islands stage concurrently
        within one tick and must not alias rows. Reused across steps; a
        decode cache rebuild re-initializes the rows it owns."""
        b = self._host_bufs.get(key)
        if b is not None:
            return b
        phase, _, B, mb = key[0], key[1], key[2], key[3]
        if phase == "decode":
            b = {"toks": np.zeros((B, 1), np.int32),
                 "pos": np.zeros((B, 1), np.int32),
                 "slots": np.full((B,), -1, np.int32),
                 "btab": np.zeros((B, mb), np.int32),
                 "ctxl": np.ones((B,), np.int32)}
        else:
            T = key[4]
            b = {"toks": np.zeros((B, T), np.int32),
                 "pos": np.zeros((B, T), np.int32),
                 "slots": np.full((B, T), -1, np.int32),
                 "btab": np.zeros((B, mb), np.int32),
                 "prior": np.zeros((B,), np.int32),
                 "lastp": np.zeros((B,), np.int32)}
        self._host_bufs[key] = b
        return b

    def _mb_bucket(self, max_need_blocks: int) -> int:
        """Bucketed block-table width: pow2 over the max blocks any live
        request needs, capped at the engine's configured max."""
        return min(bucket_pow2(max(int(max_need_blocks), 1)),
                   self.max_blocks)

    @staticmethod
    def _h2d(buf: np.ndarray) -> jax.Array:
        """Upload a REUSED staging buffer. The numpy-level .copy() is
        synchronous, so the device transfer — which JAX defers and may
        even zero-copy-alias — only ever sees a frozen snapshot. Feeding
        `buf` (or any lazy jnp copy of it) directly races with the async
        in-flight window: the next step mutates the buffer before the
        previous step's transfer has executed."""
        return jnp.asarray(buf.copy())

    def _fill_block_tables(self, btab: np.ndarray, rows: np.ndarray,
                           reqs: Sequence[Request]) -> None:
        """Scatter the adaptors' vectorized batch tables into the padded
        host buffer (one block_table_batch per engine-group adaptor,
        staged through a persistent scratch buffer — the scatter
        assignment copies synchronously, so reuse across groups is
        safe)."""
        if self._bt_scratch is None:
            self._bt_scratch = np.zeros(
                (self._global_batch(), self.max_blocks), np.int32)
        mb = btab.shape[1]
        by_ad: Dict[int, List[int]] = {}
        for i, r in enumerate(reqs):
            by_ad.setdefault(r.engine_group, []).append(i)
        for g, idxs in by_ad.items():
            ad = self.adaptors[g]
            rids = [reqs[i].req_id for i in idxs]
            btab[rows[np.asarray(idxs)]] = \
                ad.block_table_batch(rids, mb,
                                     out=self._bt_scratch[:, :mb])

    # -- device token ring ---------------------------------------------
    def _tokens_in(self, rt: _IslandRT, reqs: Sequence[Request],
                   rows: np.ndarray, key, host: np.ndarray) -> jax.Array:
        """Previous-token batch input [B,1] without any device->host
        read: rows whose last token is still device-resident are gathered
        on device from the producing step's output array; rows already
        harvested (post-drain) come from the host token buffer."""
        B = host.shape[0]
        if key is not None and key == rt.last_key \
                and rt.last_src is not None:
            # unchanged membership: the previous step's [B] output IS
            # this step's input — feed it straight back
            return rt.last_src.reshape(B, 1)
        host.fill(0)
        per_src: Dict[int, Tuple[jax.Array, List[int], List[int]]] = {}
        for r, row in zip(reqs, rows):
            ent = rt.last_tok.get(r.req_id)
            if ent is None:
                buf = self._token_buf.get(r.req_id)
                if buf:
                    host[row, 0] = buf[-1]
            else:
                src, srow = ent
                rec = per_src.setdefault(id(src), (src, [], []))
                rec[1].append(srow)
                rec[2].append(int(row))
        tok = self._h2d(host)  # `host` is a reused staging buffer
        for src, srows, drows in per_src.values():
            tok = tok.at[jnp.asarray(np.asarray(drows)), 0].set(
                src[jnp.asarray(np.asarray(srows))])
        return tok

    def _note_tokens(self, rt: _IslandRT, key, toks_dev: jax.Array,
                     row_reqs: Tuple[Tuple[int, str], ...]) -> None:
        rt.pending.append((toks_dev, row_reqs))
        for row, rid in row_reqs:
            rt.last_tok[rid] = (toks_dev, row)
        rt.last_src = toks_dev
        rt.last_key = key
        if self.window == 0:
            # depth-0 window = fully synchronous dispatch (tokens still
            # stay on device; only completion is awaited)
            with tracing.span("fs.wait"):
                toks_dev.block_until_ready()
            self.sync_stats.window_waits += 1
            rt.stats.window_waits += 1
        elif len(rt.pending) > self.window:
            # bounded in-flight window: wait for the step that left the
            # window to COMPLETE (no transfer — tokens stay on device)
            with tracing.span("fs.wait"):
                rt.pending[-self.window - 1][0].block_until_ready()
            self.sync_stats.window_waits += 1
            rt.stats.window_waits += 1
        if len(rt.pending) >= self.harvest_limit:
            self._harvest(rt)

    def _harvest(self, rt: _IslandRT) -> None:
        """Move one island's pending device token arrays into the host
        token buffer (one batched [B] transfer per step harvested, never
        per-token)."""
        with tracing.span("fs.harvest", steps=len(rt.pending)):
            for toks_dev, row_reqs in rt.pending:
                arr = np.asarray(toks_dev)
                self.sync_stats.d2h_batched += 1
                rt.stats.d2h_batched += 1
                for row, rid in row_reqs:
                    if rid in self._retired:
                        continue    # aborted mid-flight: drop, don't buffer
                    self._token_buf.setdefault(rid, []).append(
                        int(arr[row]))
        rt.pending.clear()
        rt.last_tok.clear()

    def _drain_island(self, rt: _IslandRT) -> None:
        """Safe-point synchronization scoped to ONE island: surface its
        in-flight tokens and drop its device-resident feeding state.
        Called when a rebind reshapes the island and before host
        readout; never on the steady-state path — and never for islands
        a rebind leaves alone."""
        if rt.pending:
            self._harvest(rt)
            self.sync_stats.drains += 1
            rt.stats.drains += 1
        rt.last_tok.clear()
        rt.last_src = None
        rt.last_key = None

    def _discard_island(self, rt: _IslandRT) -> None:
        """Fault path: drop one island's in-flight tokens WITHOUT
        harvesting (the device they live on is dead or the drain was
        corrupted). Only the host token buffer survives for recovery."""
        rt.pending.clear()
        rt.last_tok.clear()
        rt.last_src = None
        rt.last_key = None
        rt.steady = None

    def _fault_gate(self, isl: Island) -> None:
        """Raise EngineFault when a scripted-dead engine sits in this
        island's collective (any launch spanning it would hang on real
        hardware); stall factors are meaningless for wall-clock
        execution and are ignored."""
        if self.injector is not None:
            self.injector.check_launch(list(isl.engines()))

    def drain(self) -> None:
        """Fleet-wide safe point (scheduler end-of-run, host readout)."""
        with tracing.span("fs.drain", islands=len(self.islands)):
            for rt in self.islands:
                self._drain_island(rt)
        # no pending ring references any retired row anymore
        self._retired.clear()

    def abort_request(self, r: Request) -> None:
        """Scheduler abort hook (§D11): retire one request's device-side
        row WITHOUT draining its island. Steps already launched may
        still carry the row — the retired id tombstones it so harvests
        drop its tokens instead of buffering them; the decode cache
        keys on batch membership, so the island's next launch rebuilds
        without the row. No safe-point synchronization, no disruption
        to the island's other residents."""
        rid = r.req_id
        self._retired.add(rid)
        self._token_buf.pop(rid, None)
        self._prompt_cache.pop(rid, None)
        self._pinned_prompts.pop(rid, None)
        for rt in self.islands:
            rt.last_tok.pop(rid, None)

    # -- sampling seeds -------------------------------------------------
    def _seeds(self, B: int) -> Optional[jax.Array]:
        """Per-row sampling seeds without per-step host uploads: the [B]
        iota is a cached device array per batch size; each step adds only
        the scalar step offset on device (same uint32 values as the old
        host-built ``base + arange`` mod 2**32)."""
        if self.temperature <= 0.0:
            return None
        iota = self._seed_iota.get(B)
        if iota is None:
            iota = jnp.arange(B, dtype=jnp.uint32)
            self._seed_iota[B] = iota
        # a fleet-wide cursor advanced by each draw's OWN batch size:
        # launches with different per-island batches still get disjoint
        # seed ranges (a step-counter * B base collides across islands);
        # for a uniform fleet the sequence is identical to the seed-era
        # counter * global-B bases
        base = self._seed_cursor
        self._seed_cursor = (base + B) & 0xFFFFFFFF
        return iota + jnp.uint32(base)

    def _sample_seeds(self, B: int, reqs: Sequence[Request], rows,
                      phase: str) -> Optional[jax.Array]:
        """Per-launch sampling seeds. ``seed_mode='fleet'`` (default) is
        the cursor draw above — cheapest, but the stream depends on how
        many launches preceded this one. ``'request'`` derives each
        row's seed from (req_id, output index), making token streams
        independent of batching AND of how much prefill actually ran —
        a prefix-cache hit skips launches, which would shift the
        cursor. The output index at launch time: the scheduler promotes
        (generated += 1) BEFORE launching, so a final prefill chunk
        samples index ``generated - 1`` (== 0) and decode rows sample
        ``generated`` — identical for mixed and sequential paths."""
        if self.temperature <= 0.0:
            return None
        if self.seed_mode != "request":
            return self._seeds(B)
        host = np.zeros((B,), np.uint32)
        for r, row in zip(reqs, rows):
            idx = max(r.generated - 1, 0) if phase == "prefill" \
                else r.generated
            host[int(row)] = abs(hash((r.req_id, int(idx)))) & 0xFFFFFFFF
        return jnp.asarray(host)

    # ------------------------------------------------------------------
    def _stage_prefill(self, rt: _IslandRT, reqs: Sequence[Request],
                       mb_min: int = 1):
        """Host staging for one chunked-prefill launch (§Perf D6). Each
        request's chunk covers prompt positions
        ``[r.prefilled, min(entry.length, prompt_len))``: the scheduler
        has already allocated the chunk's slots (Alg. 1 step 4) and only
        advances ``prefilled`` after the launch, so at staging time
        ``prefilled`` IS the prior context length — long prompts stream
        through in ``prefill_chunk``-sized slices with true absolute
        positions, never truncated. Returns (batch, rows, final_mask,
        T, mb)."""
        isl = rt.island
        B = rt.B
        n = len(reqs)
        prompts = [self._prompt_tokens(r) for r in reqs]
        rows_map = self._rows(reqs, isl)
        rows = np.fromiter((rows_map[r.req_id] for r in reqs), np.int64, n)
        entries = [self.adaptors[r.engine_group].table[r.req_id]
                   for r in reqs]
        plens = np.fromiter((len(p) for p in prompts), np.int64, n)
        elens = np.fromiter((e.length for e in entries), np.int64, n)
        # prompt positions cached once this chunk lands (entry.length may
        # already include the first decode token's slot on final chunks)
        end = np.minimum(elens, plens)
        prior = np.fromiter((max(int(r.prefilled), 0) for r in reqs),
                            np.int64, n)
        prior = np.minimum(prior, end)
        chunk = end - prior
        final = end >= plens
        # seq bucket: pad the CHUNK extent to pow2 so chunk-length
        # variation reuses one compiled executable per bucket;
        # mb bucket: block-table width tracks the widest live request
        T = bucket_pow2(max(int(chunk.max()), 1))
        nblocks = max(len(e.block_ids) for e in entries)
        if isl.sp > 1:
            # blocks spread across sp lanes; each lane's table is bounded
            assert -(-nblocks // isl.sp) <= self.max_blocks, \
                f"request needs {-(-nblocks // isl.sp)} blocks/lane > " \
                f"max_blocks_per_req={self.max_blocks}"
            mb = max(self._mb_bucket(-(-nblocks // isl.sp)), mb_min)
        else:
            assert nblocks <= self.max_blocks, \
                f"request needs {nblocks} blocks > max_blocks_per_req=" \
                f"{self.max_blocks}"
            mb = max(self._mb_bucket(nblocks), mb_min)
        live = self._live_tags(entries, isl)
        bufs = self._bufs(("prefill", isl, B, mb, T))
        toks, slots, btab = bufs["toks"], bufs["slots"], bufs["btab"]
        toks.fill(0)
        slots.fill(-1)
        btab.fill(0)
        cap = self.geom.capacity(isl.write_tag if isl.sp > 1
                                 else isl.merge)
        write_segs: List = [None] * n
        if live is None:
            self._fill_block_tables(btab, rows, reqs)
        if isl.sp > 1:
            # §D12: each chunk lands in exactly ONE per-block SP segment
            # (the write program carries one owner shard per row), so
            # the scheduler must issue block-aligned chunks on SP islands
            for i, (r, e) in enumerate(zip(reqs, entries)):
                lo, hi = int(prior[i]), int(end[i])
                if hi <= lo:
                    continue
                assert lo // cap == (hi - 1) // cap, \
                    (r.req_id, "SP chunk spans blocks", lo, hi, cap)
                sg = next(s2 for s2 in reversed(e.segments)
                          if s2.start <= lo < s2.start + cap
                          and s2.shard >= 0)
                write_segs[i] = sg
        if int(chunk.sum()):
            rowcat = np.repeat(np.arange(n), chunk)
            offcat = ragged_arange(chunk)
            poscat = np.repeat(prior, chunk) + offcat
            rcat = rows[rowcat]
            toks[rcat, offcat] = np.concatenate(
                [p[lo:hi] for p, lo, hi in zip(prompts, prior, end)])
            if live is None:
                # seed-era vectorized slot math: single-segment entries,
                # global positions index the staged table directly
                blockcat = btab[rcat, poscat // cap].astype(np.int64)
                slots[rcat, offcat] = blockcat * cap + poscat % cap
            elif isl.sp > 1:
                # slot = write block * cap + block-local offset
                blk = np.fromiter(
                    (sg.ids[0] if sg is not None else 0
                     for sg in write_segs), np.int64, n)
                st = np.fromiter(
                    (sg.start if sg is not None else 0
                     for sg in write_segs), np.int64, n)
                slots[rcat, offcat] = np.repeat(blk, chunk) * cap \
                    + (poscat - np.repeat(st, chunk))
            else:
                # §D8: chunk write slots are RUN-LOCAL against each
                # entry's live (current-tag) run — a rebind froze
                # earlier segments, so global positions no longer index
                # the concatenated table uniformly. Writes stay past
                # any shared prefix blocks (prior >= cached tokens).
                tails = [self._seg_runs(e)[-1] for e in entries]
                for r, t_run in zip(reqs, tails):
                    assert t_run[0] == isl.merge, \
                        (r.req_id, "chunk not under the island merge",
                         t_run[0], isl.merge)
                seg_start = np.fromiter((t_run[1] for t_run in tails),
                                        np.int64, n)
                spos = poscat - np.repeat(seg_start, chunk)
                maxb = max(len(t_run[2]) for t_run in tails)
                segtab = np.zeros((n, maxb), np.int64)
                for i, t_run in enumerate(tails):
                    segtab[i, :len(t_run[2])] = t_run[2]
                slots[rcat, offcat] = segtab[rowcat, spos // cap] * cap \
                    + spos % cap
        priorb = bufs["prior"]
        priorb.fill(0)
        priorb[rows] = prior
        # sample each request at its true final chunk position: the token
        # must not depend on the padded window length (seq bucket) or on
        # which other requests are co-batched
        lastp = bufs["lastp"]
        lastp.fill(0)
        lastp[rows] = np.maximum(chunk - 1, 0)
        posb = bufs["pos"]
        posb[:] = np.arange(T, dtype=np.int32)[None]
        posb[rows] += prior[:, None].astype(np.int32)
        batch = {
            "tokens": self._h2d(toks),
            "positions": self._h2d(posb),
            "slots": self._h2d(slots),
            "last_pos": self._h2d(lastp),
        }
        if live is None:
            batch["block_table"] = self._h2d(btab)
            batch["prior_len"] = self._h2d(priorb)
        elif isl.sp > 1:
            lt = self._sp_lanes(isl, reqs, entries, rows, B, prior,
                                write_segs)
            for k, v in lt.items():
                batch[k] = self._h2d(v)
            wown = np.zeros((B,), np.int32)
            for i, (r, sg) in enumerate(zip(reqs, write_segs)):
                if sg is None:
                    continue
                g_lead = isl.start + ((r.engine_group - isl.start)
                                      // isl.merge) * isl.merge
                wown[rows[i]] = min(o.engine_id for o in sg.owners) - g_lead
            batch["write_own"] = self._h2d(wown)
        else:
            cur_start = np.fromiter(
                (self._seg_runs(e)[-1][1] for e in entries), np.int64, n)
            lt = self._seg_arrays(isl, reqs, entries, rows, B, live,
                                  (prior - cur_start).astype(np.int64))
            for k, v in lt.items():
                batch[k] = self._h2d(v)
        return batch, rows, final, T, mb, live

    def _step_span(self, phase: str, rt: _IslandRT,
                   pre: Sequence[Request] = (), dec: Sequence[Request] = ()):
        """The ``fs.step`` span of one launch, read before it runs: its
        island, each decode row's (lead engine, context incl. the new
        token) and each prefill chunk's (lead engine, prior tokens,
        chunk tokens, final)."""
        def entry(r):
            return self.adaptors[r.engine_group].table[r.req_id]

        def chunks():
            out = []
            for r in pre:
                end = min(int(entry(r).length), int(r.prompt_len))
                prior = min(max(int(r.prefilled), 0), end)
                out.append((r.engine_group, prior, end - prior,
                            end >= r.prompt_len))
            return tracing.rows(out)

        isl = rt.island
        return tracing.span(
            "fs.step", phase=phase, step=self.sync_stats.steps,
            island=lambda: f"{isl.start}/{isl.n_engines}/{isl.merge}",
            rids=lambda: ":".join(r.req_id for r in (*pre, *dec)),
            dec=lambda: tracing.rows((r.engine_group, entry(r).length)
                                     for r in dec),
            pre=chunks)

    def prefill(self, reqs: Sequence[Request], island: Union[Island, int],
                chunk_tokens: int) -> float:
        rt = self._resolve(island)
        self._fault_gate(rt.island)
        t0 = time.perf_counter()
        with self._step_span("prefill", rt, pre=reqs):
            B = rt.B
            batch, rows, final, T, mb, live = self._stage_prefill(rt, reqs)
            seeds = self._sample_seeds(B, reqs, rows, "prefill")
            if seeds is not None:
                batch["sample_seeds"] = seeds
            runner = self.pool.runner(
                rt.island, "prefill", sampled=self.fused, donate=self.donate,
                batch_bucket=B, seq_bucket=T, mb_bucket=mb, live=live)
            self.sync_stats.steps += 1
            rt.stats.steps += 1
            if self.fused:
                toks_dev, rt.states = runner(rt.params, rt.states, batch)
                # only FINAL chunks emit a token; mid-prompt chunks leave the
                # device token ring (and its decode feed-back key) untouched
                row_reqs = tuple((int(row), r.req_id)
                                 for row, r, f in zip(rows, reqs, final) if f)
                if row_reqs:
                    # prefill membership never matches a decode key: the next
                    # decode gathers these first tokens on device by row map
                    self._note_tokens(rt, None, toks_dev, row_reqs)
            else:
                logits, rt.states = runner(rt.params, rt.states, batch)
                self._host_sample(rt, [(r, row) for r, row, f in
                                       zip(reqs, rows, final) if f], logits)
        return time.perf_counter() - t0

    # ------------------------------------------------------------------
    def request_fits(self, r: Request, merge) -> bool:
        """Admission gate: can this request's full context EVER sit in
        one ``max_blocks_per_req``-wide block table under ``merge`` (a
        bare merge or an Island)? Chunked prefill streams the whole
        prompt (no more silent truncation), so over-cap requests must be
        rejected up front — otherwise they would crash the serve loop
        mid-stream once their block count outgrows the table. On an SP
        island the blocks round-robin across ``sp`` lanes, so the gate
        is per-LANE width: context capacity scales with shard count."""
        if isinstance(merge, Island) and merge.sp > 1:
            cap = self.geom.capacity(merge.write_tag)
            need = -(-r.total_context() // cap)
            return -(-need // merge.sp) <= self.max_blocks
        m = merge.merge if isinstance(merge, Island) else merge
        cap = self.geom.capacity(m)
        need = -(-r.total_context() // cap)
        return need <= self.max_blocks

    def live_readable(self) -> bool:
        """Scheduler capability hook (§D8): can this backend carry
        in-flight requests' KV across a rebind in place? The geometry
        half (``PoolGeometry.live_readable`` per tag) is checked by the
        scheduler; this half covers what the step programs implement —
        the head-layout paged pool, no sliding window, non-recurrent,
        non-enc-dec. Striped pools satisfy Eq. 3 universally but their
        live read program is not implemented here (they fall back to
        HARD/SOFT; the simulation backend models them as readable)."""
        cfg = self.cfg
        return (self.geom.layout == "head" and cfg.mla is None
                and cfg.enc_dec is None
                and cfg.family not in ("ssm", "hybrid")
                and self.pool.window is None)

    def supports_mixed(self) -> bool:
        """Mixed steps cover the paged-attention serving path: recurrent
        states (SSM/hybrid) are batch-dense — a full-batch prefill pass
        would clobber decode rows' states — and enc-dec prefill needs
        frontend embeds. Those fall back to sequential launches."""
        return (self.mixed_step and self.fused and self.cfg.enc_dec is None
                and self.cfg.family not in ("ssm", "hybrid")
                and self.geom.layout != "striped")

    def mixed(self, prefills: Sequence[Request], decodes: Sequence[Request],
              island: Union[Island, int], chunk_tokens: int) -> float:
        """One compiled launch for a Sarathi-style mixed step (§Perf D6)
        on ONE island: prefill chunk rows and the decode batch share a
        single executable keyed
        ``(island_merge, 'mixed', batch_bucket, chunk_bucket, mb_bucket,
        n_engines)``. ``decodes`` may include requests whose FINAL chunk
        is in ``prefills`` this step (the scheduler promotes before
        launching); their first-token input routes on device from the
        prefill output rows via ``d_src_rows`` — token-identical to the
        sequential prefill->decode pair, in one step launch."""
        rt = self._resolve(island)
        isl = rt.island
        self._fault_gate(isl)
        assert self.fused, "mixed step requires fused sampling"
        ents = [self.adaptors[r.engine_group].table[r.req_id]
                for r in list(prefills) + list(decodes)]
        if self._live_tags(ents, isl) is not None:
            # cross-tag segments in the tick (§D8): the fused program
            # has no live variant — run the token-identical sequential
            # prefill->decode pair for this transient phase instead
            return (self.prefill(prefills, island, chunk_tokens)
                    + self.decode(decodes, island))
        t0 = time.perf_counter()
        with self._step_span("mixed", rt, pre=prefills, dec=decodes):
            B = rt.B
            cap = self.geom.capacity(isl.merge)
            # shared mb bucket: the widest need of either phase, so both
            # block tables stage (and compile) at one width per runner key
            pre_blocks = max(len(self.adaptors[r.engine_group]
                                 .table[r.req_id].block_ids) for r in prefills)
            dec_len = max(self.adaptors[r.engine_group].table[r.req_id].length
                          for r in decodes)
            mb = max(self._mb_bucket(pre_blocks),
                     self._mb_bucket(-(-int(dec_len) // cap)))
            pbatch, prows, final, T, mb, _ = self._stage_prefill(rt, prefills,
                                                                 mb_min=mb)
            c = self._decode_cache(rt, decodes, mb_min=mb)
            bufs, drows = c.bufs, c.rows
            tokens = self._stage_decode(rt, decodes, c)
            # on-device routing for rows promoted out of THIS step's prefill:
            # group-local prefill row index (both rows live on the same
            # engine-group shard)
            bpg = self.bpe * isl.merge
            src = np.full((B,), -1, np.int32)
            p_row_of = {r.req_id: int(row)
                        for r, row, f in zip(prefills, prows, final) if f}
            for r, drow in zip(decodes, drows):
                pr = p_row_of.get(r.req_id)
                if pr is not None:
                    src[drow] = pr % bpg
            batch = {"p_" + k: v for k, v in pbatch.items()}
            batch.update({
                "d_tokens": tokens,
                "d_positions": self._h2d(bufs["pos"]),
                "d_slots": self._h2d(bufs["slots"]),
                "d_block_table": self._h2d(bufs["btab"]),
                "d_context_len": self._h2d(bufs["ctxl"]),
                "d_src_rows": jnp.asarray(src),
            })
            # two seed draws mirror the sequential two-launch assignment, so
            # stochastic sampling stays token-identical across the fusion
            p_seeds = self._sample_seeds(B, prefills, prows, "prefill")
            d_seeds = self._sample_seeds(B, decodes, drows, "decode")
            if p_seeds is not None:
                batch["p_sample_seeds"] = p_seeds
                batch["d_sample_seeds"] = d_seeds
            runner = self.pool.runner(
                rt.island, "mixed", sampled=True, donate=self.donate,
                batch_bucket=B, seq_bucket=T, mb_bucket=mb)
            self.sync_stats.steps += 1  # ONE launch for the island's tick
            rt.stats.steps += 1
            (p_toks, d_toks), rt.states = runner(rt.params, rt.states, batch)
            prow_reqs = tuple((int(row), r.req_id)
                              for row, r, f in zip(prows, prefills, final) if f)
            if prow_reqs:
                self._note_tokens(rt, None, p_toks, prow_reqs)
            self._note_tokens(rt, c.key, d_toks, c.row_reqs)
        return time.perf_counter() - t0

    # ------------------------------------------------------------------
    # live cross-layout staging (§D8)
    # ------------------------------------------------------------------
    def _live_tags(self, entries, island):
        """Lane-tag tuple when the step must run a live (multi-lane)
        program; None selects the single-view fast path (the seed-era
        staging, byte-identical). ``island`` is the serving Island (or
        a bare merge for seed-era callers).

        Two triggers: (a) any entry's KV spans segments tagged beyond
        the island's current merge (§D8) — lanes are the sorted
        distinct tags, one per tag; (b) the island is sequence-parallel
        (§D12) — lanes are one per SP shard, ALL carrying the write tag
        (repeated tags are fine: lane identity is positional), and the
        island is always live because blocks round-robin across shards,
        so flat concatenated position math never applies. Same-tag
        shared prefix segments alone DON'T trigger the live path: their
        blocks are full and block-aligned under the same capacity, so
        the flat concatenated table stays position-correct."""
        if isinstance(island, Island) and island.sp > 1:
            return (island.write_tag,) * island.sp
        merge = island.merge if isinstance(island, Island) else island
        tags = {s.tag for e in entries for s in e.segments}
        if tags <= {merge}:
            return None
        tags.add(merge)
        return tuple(sorted(tags))

    @staticmethod
    def _seg_runs(e):
        """Contiguous same-tag segments merged into one logical run
        each: ``[tag, start, ids, owners]`` in order. A warm request's
        shared prefix head and its private same-tag continuation are
        block-aligned under one capacity, so position math over the
        concatenated ids is valid — the staging paths below only ever
        see runs, never raw segments."""
        runs = []
        for s in e.segments:
            if runs and runs[-1][0] == s.tag:
                runs[-1][2].extend(s.ids)
            else:
                runs.append([s.tag, s.start, list(s.ids), s.owners])
        return runs

    def _seg_arrays(self, isl: Island, reqs: Sequence[Request], entries,
                    rows: np.ndarray, B: int, tags, cur_len):
        """Per-LANE (block table, token count, owner offset) host arrays
        for the live step — lane ``i`` carries tag ``tags[i]`` and emits
        ``lt{i}_bt``/``lt{i}_len``/``lt{i}_own`` (matching
        ``build_serve_step``'s positional lane convention). ``cur_len[i]``
        is the current-tag RUN's token count contribution for entry i
        (decode: incl. the incoming token; prefill: prior tokens only).
        Owner offsets are merge-axis engine offsets of the group that
        wrote the run — derived from the owners' fleet positions when
        recorded (an attached shared prefix may be owned by a group
        unrelated to the reader's lead engine), falling back to the
        buddy-alignment formula."""
        m = isl.merge
        out: Dict[str, np.ndarray] = {}
        runs_of = [self._seg_runs(e) for e in entries]
        for lane, t in enumerate(tags):
            per = []
            for i, (r, e) in enumerate(zip(reqs, entries)):
                runs = runs_of[i]
                match = [k for k, run in enumerate(runs) if run[0] == t]
                assert len(match) <= 1, \
                    (r.req_id, "non-contiguous tag runs", e.tags())
                if not match:
                    per.append((i, [], 0, 0))
                    continue
                k = match[0]
                _, start, ids, owners = runs[k]
                if t == m:
                    ntok = cur_len[i]
                else:
                    end = runs[k + 1][1] if k + 1 < len(runs) else e.length
                    ntok = end - start
                g_lead = isl.start + ((r.engine_group - isl.start)
                                      // m) * m
                own_lead = (min(o.engine_id for o in owners) if owners
                            else (r.engine_group // t) * t)
                own = own_lead - g_lead
                assert 0 <= own <= m - t, (r.req_id, t, own, m)
                per.append((i, ids, ntok, own))
            mb_t = bucket_pow2(max([len(ids) for _, ids, _, _ in per] + [1]))
            bt = np.zeros((B, mb_t), np.int32)
            ln = np.zeros((B,), np.int32)
            ow = np.zeros((B,), np.int32)
            for i, ids, ntok, own in per:
                row = rows[i]
                bt[row, :len(ids)] = ids
                ln[row] = ntok
                ow[row] = own
            out[f"lt{lane}_bt"] = bt
            out[f"lt{lane}_len"] = ln
            out[f"lt{lane}_own"] = ow
        return out

    def _sp_lanes(self, isl: Island, reqs: Sequence[Request], entries,
                  rows: np.ndarray, B: int, upto, write_segs):
        """Per-lane host arrays for a sequence-parallel island (§D12):
        lane j holds shard j's resident blocks of each request, in
        allocation order. ``upto[i]`` bounds the token count credited
        per lane for entry i (decode: ``entry.length`` incl. the pending
        token; prefill: prior tokens only — the chunk's keys enter via
        the causal lane). ``write_segs[i]`` (or None) is the row's
        write-block segment: its shard is ROTATED to the LAST lane slot,
        which the prefill program treats as the causal lane — lane-local
        key positions stay consistent because every block of a lane
        before its last is full. Lane lens/tables stay valid across an
        SP-degree rebind: lanes are resolved from each segment's OWNERS
        relative to the group lead, not from the rotation slot recorded
        at write time."""
        m, t, s = isl.merge, isl.write_tag, isl.sp
        n = len(reqs)
        cap = self.geom.capacity(t)
        ids_rl: List[List[List[int]]] = [[[] for _ in range(s)]
                                         for _ in range(n)]
        len_rl = np.zeros((n, s), np.int64)
        perm = np.tile(np.arange(s), (n, 1))
        for i, (r, e) in enumerate(zip(reqs, entries)):
            g_lead = isl.start + ((r.engine_group - isl.start) // m) * m
            for sg in e.segments:
                assert sg.tag == t and sg.shard >= 0, \
                    (r.req_id, "non-SP segment on an SP island",
                     sg.tag, sg.shard)
                lane = (min(o.engine_id for o in sg.owners) - g_lead) // t
                assert 0 <= lane < s, (r.req_id, lane, s)
                ids_rl[i][lane].extend(sg.ids)
                len_rl[i][lane] += min(
                    max(int(upto[i]) - sg.start, 0), cap * len(sg.ids))
            w = write_segs[i]
            if w is not None:
                wl = (min(o.engine_id for o in w.owners) - g_lead) // t
                perm[i] = [j for j in range(s) if j != wl] + [wl]
        mb_l = bucket_pow2(max(
            [len(ids) for per in ids_rl for ids in per] + [1]))
        out: Dict[str, np.ndarray] = {}
        for q in range(s):
            bt = np.zeros((B, mb_l), np.int32)
            ln = np.zeros((B,), np.int32)
            ow = np.zeros((B,), np.int32)
            for i in range(n):
                j = int(perm[i][q])
                row = rows[i]
                ids = ids_rl[i][j]
                bt[row, :len(ids)] = ids
                ln[row] = len_rl[i][j]
                ow[row] = j * t
            out[f"lt{q}_bt"] = bt
            out[f"lt{q}_len"] = ln
            out[f"lt{q}_own"] = ow
        return out

    # ------------------------------------------------------------------
    def _decode_cache(self, rt: _IslandRT, reqs: Sequence[Request],
                      mb_min: int = 1) -> _DecodeCache:
        key = (rt.island, tuple(r.req_id for r in reqs))
        c = rt.steady
        if c is not None and c.key == key and c.live is None:
            self._decode_advance(c)
            # crossing an mb bucket boundary (pow2 of the max live
            # blocks, or a mixed step's shared-width floor) rebuilds the
            # cache against wider staging buffers; within a bucket the
            # steady path is untouched. Live (cross-tag) caches re-stage
            # every step instead — their key is preserved so the device
            # token ring still feeds back without a host round trip.
            need = max(self._mb_bucket(-(-int(c.lengths.max()) // c.cap)),
                       mb_min)
            if need == c.mb:
                return c
        return self._decode_build(rt, key, reqs, mb_min)

    def _decode_build(self, rt: _IslandRT, key, reqs: Sequence[Request],
                      mb_min: int = 1) -> _DecodeCache:
        isl = rt.island
        B = rt.B
        n = len(reqs)
        rows_map = self._rows(reqs, isl)
        rows = np.fromiter((rows_map[r.req_id] for r in reqs), np.int64, n)
        entries = [self.adaptors[r.engine_group].table[r.req_id]
                   for r in reqs]
        cap = self.geom.capacity(isl.merge)
        lengths = np.fromiter((e.length for e in entries), np.int64, n)
        live = self._live_tags(entries, isl)
        if isl.sp > 1:
            return self._decode_build_sp(rt, key, reqs, entries, rows,
                                         lengths, live)
        if live is not None:
            return self._decode_build_live(rt, key, reqs, entries, rows,
                                           lengths, live)
        nblk = np.fromiter((len(e.block_ids) for e in entries), np.int64, n)
        mb = max(self._mb_bucket(-(-int(lengths.max()) // cap) if n else 1),
                 mb_min)
        bufs = self._bufs(("decode", isl, B, mb))
        # reset: rows not owned by this membership must stay inert
        bufs["slots"].fill(-1)
        bufs["btab"].fill(0)
        bufs["ctxl"].fill(1)
        bufs["pos"].fill(0)
        self._fill_block_tables(bufs["btab"], rows, reqs)
        row_reqs = tuple((int(row), r.req_id) for row, r in zip(rows, reqs))
        c = _DecodeCache(key, rows, row_reqs, entries, lengths, nblk,
                         cap, bufs, mb)
        rt.steady = c
        return c

    def _decode_build_live(self, rt: _IslandRT, key, reqs, entries,
                           rows: np.ndarray, lengths: np.ndarray,
                           live) -> _DecodeCache:
        """Stage a decode batch whose KV spans mode-tagged segments: the
        incoming token's slot is segment-local against the CURRENT
        segment (the scheduler retagged pending slots at the rebind),
        and each tag gets its own (table, count, owner) row set. Fresh
        arrays each step — the live phase lasts only until the riding
        requests complete, and correctness beats incremental reuse
        here."""
        isl = rt.island
        assert self.geom.layout == "head", \
            "live cross-layout staging covers the head-layout pool"
        B = rt.B
        n = len(reqs)
        cap = self.geom.capacity(isl.merge)
        tails = [self._seg_runs(e)[-1] for e in entries]
        for r, t_run in zip(reqs, tails):
            assert t_run[0] == isl.merge, \
                (r.req_id, "pending slot not retagged", t_run[0], isl.merge)
        seg_start = np.fromiter((t[1] for t in tails), np.int64, n)
        cur_len = (lengths - seg_start).astype(np.int64)
        bufs = {
            "toks": np.zeros((B, 1), np.int32),
            "pos": np.zeros((B, 1), np.int32),
            "slots": np.full((B,), -1, np.int32),
        }
        p = lengths - 1                     # absolute (rope) positions
        p_loc = p - seg_start               # segment-local write offset
        bufs["pos"][rows, 0] = p
        slot_blk = np.fromiter(
            (t[2][int(pl) // cap] for t, pl in zip(tails, p_loc)),
            np.int64, n)
        bufs["slots"][rows] = slot_blk * cap + p_loc % cap
        bufs.update(self._seg_arrays(isl, reqs, entries, rows, B, live,
                                     cur_len))
        row_reqs = tuple((int(row), r.req_id) for row, r in zip(rows, reqs))
        nblk = np.fromiter((len(e.block_ids) for e in entries), np.int64, n)
        c = _DecodeCache(key, rows, row_reqs, entries, lengths, nblk,
                         cap, bufs, 0, live=live)
        rt.steady = c
        return c

    def _decode_build_sp(self, rt: _IslandRT, key, reqs, entries,
                         rows: np.ndarray, lengths: np.ndarray,
                         live) -> _DecodeCache:
        """Stage a decode batch on a sequence-parallel island (§D12):
        the incoming token's write slot is block-local against the LIVE
        per-block segment and ``write_own`` names its owner shard; each
        SP lane gets its own (table, count, owner) row set via
        ``_sp_lanes``. Re-staged every step like the live cross-tag path
        (per-lane tables shift as blocks rotate across shards), but the
        cache KEY is preserved so the device token ring still feeds back
        without a host round trip."""
        isl = rt.island
        assert self.geom.layout == "head", \
            "SP staging covers the head-layout pool"
        B = rt.B
        n = len(reqs)
        cap = self.geom.capacity(isl.write_tag)
        bufs = {
            "toks": np.zeros((B, 1), np.int32),
            "pos": np.zeros((B, 1), np.int32),
            "slots": np.full((B,), -1, np.int32),
            "write_own": np.zeros((B,), np.int32),
        }
        for i, (r, e) in enumerate(zip(reqs, entries)):
            sg = e.segments[-1]
            assert sg.shard >= 0 and sg.tag == isl.write_tag, \
                (r.req_id, "pending slot not SP-placed", sg.tag, sg.shard)
            p = int(lengths[i]) - 1          # absolute (rope) position
            assert sg.start <= p < sg.start + cap, (r.req_id, p, sg.start)
            row = rows[i]
            bufs["pos"][row, 0] = p
            bufs["slots"][row] = sg.ids[0] * cap + (p - sg.start)
            g_lead = isl.start + ((r.engine_group - isl.start)
                                  // isl.merge) * isl.merge
            bufs["write_own"][row] = \
                min(o.engine_id for o in sg.owners) - g_lead
        bufs.update(self._sp_lanes(isl, reqs, entries, rows, B, lengths,
                                   [None] * n))
        row_reqs = tuple((int(row), r.req_id) for row, r in zip(rows, reqs))
        nblk = np.fromiter((len(e.block_ids) for e in entries), np.int64, n)
        c = _DecodeCache(key, rows, row_reqs, entries, lengths, nblk,
                         cap, bufs, 0, live=live)
        rt.steady = c
        return c

    def _decode_advance(self, c: _DecodeCache) -> None:
        """Steady-state step: O(1) whole-array numpy ops. The scheduler
        appended exactly one slot per request since the last step, so
        lengths advance by one; block tables change only on a block
        boundary (every ``capacity`` steps)."""
        c.lengths += 1
        need = -(-c.lengths // c.cap)
        grew = need > c.nblk
        if grew.any():
            btab = c.bufs["btab"]
            for i in np.nonzero(grew)[0]:
                e = c.entries[i]
                ids = e.ids_np()
                row = c.rows[i]
                btab[row, : min(len(ids), c.mb)] = ids[: c.mb]
                c.nblk[i] = len(e.block_ids)

    def _stage_decode(self, rt: _IslandRT, reqs: Sequence[Request],
                      c: _DecodeCache) -> jax.Array:
        """Per-step decode staging over the island cache's persistent
        buffers: vectorized position/slot/context math plus the
        device-resident previous-token gather. Shared by ``decode`` and
        ``mixed`` — the mixed-vs-sequential token-identity contract
        rides on the two paths staging identically."""
        bufs, rows, cap = c.bufs, c.rows, c.cap
        if c.live is None:
            p = c.lengths - 1
            bufs["pos"][rows, 0] = p
            bufs["slots"][rows] = \
                bufs["btab"][rows, p // cap].astype(np.int64) * cap + p % cap
            bufs["ctxl"][rows] = c.lengths
        # live caches staged positions/slots at build time (segment-local
        # slot math); only the token feed-back remains per step
        return self._tokens_in(rt, reqs, rows, c.key, bufs["toks"])

    def decode(self, reqs: Sequence[Request],
               island: Union[Island, int]) -> float:
        rt = self._resolve(island)
        self._fault_gate(rt.island)
        t0 = time.perf_counter()
        with self._step_span("decode", rt, dec=reqs):
            B = rt.B
            c = self._decode_cache(rt, reqs)
            bufs = c.bufs
            tokens = self._stage_decode(rt, reqs, c)
            batch = {
                "tokens": tokens,
                "positions": self._h2d(bufs["pos"]),
                "slots": self._h2d(bufs["slots"]),
            }
            if c.live is None:
                batch["block_table"] = self._h2d(bufs["btab"])
                batch["context_len"] = self._h2d(bufs["ctxl"])
            else:
                # no total context length: the live program masks entirely
                # from the per-lane segment counts
                for k in bufs:
                    if k.startswith("lt") or k == "write_own":
                        batch[k] = self._h2d(bufs[k])
            seeds = self._sample_seeds(B, reqs, c.rows, "decode")
            if seeds is not None:
                batch["sample_seeds"] = seeds
            runner = self.pool.runner(
                rt.island, "decode", sampled=self.fused, donate=self.donate,
                batch_bucket=B, seq_bucket=1, mb_bucket=c.mb, live=c.live)
            self.sync_stats.steps += 1
            rt.stats.steps += 1
            if self.fused:
                toks_dev, rt.states = runner(rt.params, rt.states, batch)
                self._note_tokens(rt, c.key, toks_dev, c.row_reqs)
            else:
                logits, rt.states = runner(rt.params, rt.states, batch)
                self._host_sample(rt, list(zip(reqs, c.rows)), logits)
        return time.perf_counter() - t0

    def _host_sample(self, rt: _IslandRT, req_rows, logits) -> None:
        """Legacy host path: one [B, V] logits transfer, greedy argmax
        per emitting row on the host. Each request's logits row stays in
        ``last_logits`` until its next token, so reference checks can
        compare engine variants by logits rather than tokens."""
        if not req_rows:
            return
        host = np.asarray(logits)
        for r, row in req_rows:
            self.sync_stats.host_argmax += 1
            rt.stats.host_argmax += 1
            self.last_logits[r.req_id] = host[row]
            self._token_buf.setdefault(r.req_id, []).append(
                int(host[row].argmax()))

    # ------------------------------------------------------------------
    def _prompt_tokens(self, r: Request) -> np.ndarray:
        p = self._pinned_prompts.get(r.req_id)
        if p is not None:
            # recovery fold: the prompt is orig ++ harvested tokens and
            # CANNOT be regenerated from the req_id seed
            assert len(p) == r.prompt_len, \
                (r.req_id, "pinned prompt out of sync", len(p), r.prompt_len)
            return p
        p = self._prompt_cache.get(r.req_id)
        if p is None:
            if len(self._prompt_cache) >= 4096:
                # bounded: eviction is safe, prompts regenerate from the
                # req_id seed deterministically
                self._prompt_cache.pop(next(iter(self._prompt_cache)))
            # the FULL prompt: chunked prefill streams it in slices (the
            # seed-era cap at prefill_len silently truncated long
            # prompts). Shared helper so scheduler-side content hashing
            # sees exactly the bytes this backend will prefill.
            p = prompt_token_ids(r, self.cfg.vocab_size)
            self._prompt_cache[r.req_id] = p
        return p

    def prompt_tokens(self, r: Request) -> np.ndarray:
        """Scheduler hook: the exact token ids this backend prefills for
        ``r`` — the prefix cache hashes these for content addressing."""
        return self._prompt_tokens(r)

    def recover_request(self, r: Request) -> int:
        """Scheduler recovery hook: surface whatever of this request's
        output survives, pin the recovery prompt (orig prompt ++
        harvested tokens — the fold makes ``prompt_len`` grow past what
        the seed regenerates), and return the kept-token count. Called
        BEFORE the scheduler's fold bookkeeping, so ``r`` still carries
        its pre-fold prompt/engine placement."""
        rid = r.req_id
        g = r.engine_group
        if g >= 0:
            rt = self._rt_of.get(self.layout.island_of(g))
            if rt is not None:
                dead = set(self.injector.dead_engines()) \
                    if self.injector is not None else set()
                if set(rt.island.engines()) & dead:
                    # in-flight tokens died with the island; everyone
                    # resident there is being recovered anyway
                    self._discard_island(rt)
                else:
                    # healthy island (backpressure eviction): harvest
                    # so the fold keeps every produced token
                    self._drain_island(rt)
        orig = np.asarray(self._prompt_tokens(r)[:r.prompt_len],
                          dtype=np.int64)
        toks = self._token_buf.get(rid, [])
        self._pinned_prompts[rid] = np.concatenate(
            [orig, np.asarray(toks, dtype=np.int64)])
        self._prompt_cache.pop(rid, None)
        for rt in self.islands:
            rt.last_tok.pop(rid, None)
        return len(toks)

    def generated_tokens(self, req_id: str) -> List[int]:
        self.drain()
        return self._token_buf.get(req_id, [])

    def harvested_tokens(self, req_id: str) -> List[int]:
        """Non-draining peek at the tokens already surfaced for one
        request (§D13 streaming). The async serve loop polls this every
        tick: it must NEVER force a safe point, so it only sees tokens
        the in-flight window has already harvested — the depth-2 ring
        means the tail lags by a couple of tokens until the next
        harvest, and the terminal flush (``generated_tokens``) drains
        for the remainder once the request finishes."""
        return list(self._token_buf.get(req_id, []))
