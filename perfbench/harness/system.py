"""The system under test: the program's real served stack, built by its
own launcher, plus the harness-side spans around its calls.

``build`` registers the configuration from the benchmark's file, builds
the stack with ``launch/serve.py``'s ``build_stack``/``build_door``
(real engine, one per chip, KV pool sized by the program's
``kv_pool_blocks``) while the program's weight initialization makes the
benchmark's weights, and boots the async serve loop that the HTTP
server fronts.

``Probe`` wraps the engine's step launches and rebinds: it records
each launch's rows (the contexts the kernels attend over) and times
each rebind on the host clock, and names the host's activity in the
profiler trace (``bench.tick``, ``bench.decode``, ``bench.prefill``,
``bench.mixed``, ``bench.rebind``) so idle gaps can be attributed. It
changes nothing the program computes.
"""
from __future__ import annotations

import time
from dataclasses import dataclass, field
from typing import Dict, List, Tuple

def arch_from_config(name: str, c: Dict):
    """The program's ``ArchConfig`` for a configuration file. The program
    runs RMSNorm, rotary on whole heads and no attention biases; a file
    asking for anything else cannot be served as written."""
    from repro.configs.base import ArchConfig
    if c.get("norm_type", "rms") != "rms" \
            or float(c.get("partial_rotary_factor", 1.0)) != 1.0 \
            or c.get("use_qkv_bias") or c.get("attention_bias"):
        raise ValueError(f"{name}: the program serves RMSNorm, full-head "
                         f"rotary and no attention bias only")
    return ArchConfig(
        name=name, family="dense", source=c["source"],
        num_layers=c["num_hidden_layers"], d_model=c["hidden_size"],
        num_heads=c["num_attention_heads"],
        num_kv_heads=c["num_key_value_heads"],
        d_ff=c["intermediate_size"], vocab_size=c["vocab_size"],
        head_dim=c.get("head_dim", 0), qk_norm=bool(c.get("qk_norm")),
        rope_theta=float(c["rope_theta"]),
        norm_eps=float(c.get("rms_norm_eps", c.get("layer_norm_eps"))),
        tie_embeddings=bool(c.get("tie_word_embeddings")),
        engine_rows=1)


@dataclass
class Launch:
    """One step launch as the engine was asked for it."""
    phase: str                      # decode|prefill|mixed
    t0: float                       # host clock (perf_counter)
    t1: float
    island: Tuple[int, int, int]    # (start engine, n_engines, merge)
    # decode rows: (lead engine, context incl. the new token)
    decode: List[Tuple[int, int]] = field(default_factory=list)
    # prefill chunks: (lead engine, prior tokens, chunk tokens, final)
    prefill: List[Tuple[int, int, int, bool]] = field(default_factory=list)

    @property
    def tokens(self) -> int:
        """Output tokens the launch produces: one per decode row, and
        one per final prefill chunk (a request's first token)."""
        return len(self.decode) + sum(1 for *_, f in self.prefill if f)


class Probe:
    """Harness-side spans around the engine's launches and rebinds."""

    def __init__(self, door):
        import jax
        self._jax = jax
        self._ann = jax.profiler.TraceAnnotation
        self.door = door
        self.sched = door.sched
        self.engine = door.sched.backend
        self.launches: List[Launch] = []
        self.rebinds: List[Tuple[float, float]] = []   # (t0, seconds)
        self.layouts: List[str] = []                   # after each rebind
        # programs launched while recording: (island shape, phase, seq
        # bucket, block-table bucket, live variant, token input fed back)
        self.programs: Dict[Tuple, int] = {}
        self.before: set = set()     # programs launched while not recording
        # per island, the smallest output of its newest launch: ready
        # once that launch (and every earlier one on its chips) has run
        self.newest: Dict[Tuple, object] = {}
        self.recording = False
        eng = self.engine
        for phase in ("decode", "prefill", "mixed"):
            setattr(eng, phase, self._wrap_step(phase, getattr(eng, phase)))
        eng.rebind = self._wrap_rebind(eng.rebind)
        eng.pool.runner = self._wrap_runner(eng.pool.runner)
        tick = door.tick

        def traced_tick():
            with self._ann("bench.tick"):
                return tick()
        door.tick = traced_tick

    def _rows(self, phase, args):
        eng = self.engine
        tab = lambda r: eng.adaptors[r.engine_group].table[r.req_id]
        dec, pre = [], []
        if phase == "decode":
            reqs, isl = args[0], args[1]
            dec = [(r.engine_group, int(tab(r).length)) for r in reqs]
        elif phase == "prefill":
            reqs, isl = args[0], args[1]
            pre = self._chunks(reqs, tab)
        else:
            pre = self._chunks(args[0], tab)
            dec = [(r.engine_group, int(tab(r).length)) for r in args[1]]
            isl = args[2]
        isl = isl if hasattr(isl, "start") else None
        key = (isl.start, isl.n_engines, isl.merge) if isl else (0, 1, 1)
        return key, dec, pre

    @staticmethod
    def _chunks(reqs, tab):
        out = []
        for r in reqs:
            end = min(int(tab(r).length), int(r.prompt_len))
            prior = min(max(int(r.prefilled), 0), end)
            out.append((r.engine_group, prior, end - prior,
                        end >= r.prompt_len))
        return out

    def _wrap_step(self, phase, fn):
        def step(*args, **kw):
            key, dec, pre = self._rows(phase, args) if self.recording \
                else (None, None, None)
            t0, n0 = time.perf_counter(), len(self.launches)
            with self._ann("bench." + phase):
                out = fn(*args, **kw)
            # a mixed step that falls back to a prefill and a decode
            # launch has recorded those two; record it only otherwise
            if self.recording and len(self.launches) == n0:
                self.launches.append(Launch(phase, t0, time.perf_counter(),
                                            key, dec, pre))
            return out
        return step

    def _wrap_runner(self, runner):
        def wrapped(island, phase, **kw):
            fn = runner(island, phase, **kw)
            shape = (island.n_engines, island.merge) \
                if hasattr(island, "merge") else (0, island)

            where = (island.start, island.n_engines) \
                if hasattr(island, "start") else (0, 0)

            def call(params, states, batch):
                tok = batch.get("tokens", batch.get("d_tokens"))
                key = (shape, phase, kw.get("seq_bucket"),
                       kw.get("mb_bucket"), kw.get("live") is not None,
                       bool(getattr(tok, "committed", False))
                       if phase != "prefill" else False)
                if self.recording:
                    self.programs[key] = self.programs.get(key, 0) + 1
                else:
                    self.before.add(key)
                out = fn(params, states, batch)
                leaves = self._jax.tree.leaves(out)
                if leaves:
                    self.newest[where] = min(leaves, key=lambda a: a.size)
                return out
            return call
        return wrapped

    def settle(self) -> float:
        """Wait until every step launched so far has run on the device;
        returns the host clock (perf_counter) after the wait. Called
        between ticks, so nothing is launched meanwhile."""
        live = [a for a in self.newest.values() if not a.is_deleted()]
        self._jax.block_until_ready(live)
        return time.perf_counter()

    def new_in_window(self) -> List[Tuple]:
        """Programs first launched while recording."""
        return sorted((k for k in self.programs if k not in self.before),
                      key=repr)

    def _wrap_rebind(self, fn):
        def rebind(*args, **kw):
            t0 = time.perf_counter()
            with self._ann("bench.rebind"):
                out = fn(*args, **kw)
            self.rebinds.append((t0, time.perf_counter() - t0))
            self.layouts.append(self.engine.layout.describe())
            return out
        return rebind


def sync_counts(engine) -> Dict[str, int]:
    s = engine.sync_stats
    return {"steps": s.steps, "host_argmax": s.host_argmax,
            "d2h_batched": s.d2h_batched, "window_waits": s.window_waits,
            "drains": s.drains}


def build(cell, seed: int):
    """(front door, serve loop, probe) for this cell."""
    from repro.configs.base import list_configs, register
    from repro.launch import serve as launcher
    from repro.serving.asyncloop import AsyncServeLoop
    from repro.serving.metrics import RollingTierMetrics
    from .weights import weights_from_seed

    c = cell.config_data
    if int(c["max_position_embeddings"]) != launcher.MAX_CONTEXT:
        raise ValueError(f"{cell.config}: max_position_embeddings "
                         f"{c['max_position_embeddings']} but the program "
                         f"serves {launcher.MAX_CONTEXT}")
    list_configs()                        # the program's own registry first
    register(arch_from_config(cell.config, c))
    serve = cell.traffic_data.get("serve", {})
    # no shedding and no deadlines: a request fails only on a real error;
    # the stream buffer holds a whole answer, so a healthy client is never
    # aborted for reading after a burst of harvested tokens
    cfg = launcher.ServeConfig(real=True, arch=cell.config,
                               strategy=serve.get("strategy", "hard"),
                               no_shed=True, queue_cap=1 << 30,
                               stream_buf=4096, pace="wall")
    with weights_from_seed(seed):
        sched, _spec, _inj = launcher.build_stack(cfg)
    if serve.get("live_policy"):
        from repro.core.policy import FlyingPolicy
        sched.policy = FlyingPolicy(live=True)
    door = launcher.build_door(cfg, sched)
    loop = AsyncServeLoop(door, pace="wall", stream_buf=cfg.stream_buf,
                          rolling=RollingTierMetrics(
                              window_s=cfg.metrics_window))
    return door, loop, Probe(door)


class CompileCounter:
    """Counts XLA compilations (and persistent-cache loads) and traces."""

    def __init__(self):
        import jax
        self.compiles = 0
        self.compile_s = 0.0
        self.traces = 0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event, duration, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.compiles += 1
            self.compile_s += duration
        elif event == "/jax/core/compile/jaxpr_trace_duration":
            self.traces += 1

    def snap(self) -> Tuple[int, int, float]:
        return self.compiles, self.traces, self.compile_s


def free_pools(engine) -> None:
    """Free the KV pools once the window is over (weights stay: they are
    the benchmark's, and the reference reads them)."""
    import jax
    for rt in engine.islands:
        for a in jax.tree.leaves(rt.states):
            a.delete()

