"""Context-proportional chunked prefill + unified mixed-phase step
(§Perf D6), single device: true chunking (long prompts stream through
``prefill_chunk`` slices — the seed silently truncated them at
``prefill_len``), mixed-step token identity vs the sequential
prefill->decode launches across kernel dispatch impls, one step launch
per scheduler tick with co-resident prefills+decodes, and the jaxpr
guard that the serving prefill program never materializes a full-pool
gather or a dense [B,H,Tq,Tk] score tensor."""
import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.extend import core as jex_core

from repro.configs import get_config
from repro.core.engine import FlyingEngine
from repro.core.kv_adaptor import PoolGeometry
from repro.core.modes import ParallelPlan
from repro.core.scheduler import DynamicScheduler, SchedulerConfig
from repro.core.task_pool import Request
from repro.models.model import build_model

PLAN = ParallelPlan(engine_rows=1, tp_base=1, data_rows=1)


@pytest.fixture(scope="module")
def setup():
    cfg = get_config("llama3-8b").reduced()
    model = build_model(cfg, jnp.float32)
    params = model.init(jax.random.key(0))
    return cfg, model, params


# ---------------------------------------------------------------------------
# true chunking: long prompts are no longer truncated
# ---------------------------------------------------------------------------

def chunked_prefill(setup, prompt_len, chunk, *, use_kernel=None,
                    decode_steps=2):
    cfg, model, params = setup
    geom = PoolGeometry(cfg, PLAN, num_blocks=64, block_base=16)
    eng = FlyingEngine(model, PLAN, geom, params, batch_per_engine=2,
                       max_blocks_per_req=64, prefill_len=chunk,
                       use_kernel=use_kernel)
    r = Request(req_id=f"long{prompt_len}", arrival=0.0,
                prompt_len=prompt_len, output_len=1 << 30)
    r.engine_group = 0
    while r.prefilled < prompt_len:
        c = min(chunk, prompt_len - r.prefilled)
        eng.adaptors[0].append_slots(r.req_id, c)
        eng.prefill([r], 1, chunk)
        r.prefilled += c
    if decode_steps:
        eng.adaptors[0].append_slots(r.req_id, 1)
        for _ in range(decode_steps):
            eng.decode([r], 1)
            eng.adaptors[0].append_slots(r.req_id, 1)
    return eng, r


def test_512_prompt_prefills_to_full_length(setup):
    """Regression (seed bug): a 512-token prompt's KV lengths must reach
    512 — ``chunk_tokens`` honored, ``_prompt_tokens`` uncapped."""
    eng, r = chunked_prefill(setup, 512, 64, decode_steps=0)
    entry = eng.adaptors[0].table[r.req_id]
    assert entry.length == 512
    assert len(eng._prompt_tokens(r)) == 512
    # mid-prompt chunks emit no token; the final chunk emits exactly one
    assert len(eng.generated_tokens(r.req_id)) == 1


def test_chunk_size_invariance(setup):
    """The generated stream depends only on the prompt, not on how the
    prefill was chunked (64- vs 256-token chunks, and on the forced
    kernel path)."""
    e1, r1 = chunked_prefill(setup, 512, 64)
    e2, r2 = chunked_prefill(setup, 512, 256)
    e3, r3 = chunked_prefill(setup, 512, 64, use_kernel=True)
    t1 = e1.generated_tokens(r1.req_id)
    t2 = e2.generated_tokens(r2.req_id)
    t3 = e3.generated_tokens(r3.req_id)
    assert t1 == t2 == t3
    assert e1.sync_stats.host_argmax == 0
    # chunk-token seq buckets: chunk 64 compiles T=64, never T=512
    assert all(k[5] <= 64 for k in e1.pool._runners if k[1] == "prefill")


# ---------------------------------------------------------------------------
# unified mixed-phase step
# ---------------------------------------------------------------------------

def run_sched(setup, *, mixed, use_kernel=None, temperature=0.0):
    cfg, model, params = setup
    geom = PoolGeometry(cfg, PLAN, num_blocks=64, block_base=4)
    eng = FlyingEngine(model, PLAN, geom, params, batch_per_engine=2,
                       max_blocks_per_req=16, prefill_len=8,
                       mixed_step=mixed, use_kernel=use_kernel,
                       temperature=temperature)
    sched = DynamicScheduler(
        PLAN, geom, eng,
        SchedulerConfig(strategy="hard", max_batch_per_group=2,
                        prefill_chunk=8))
    # staggered arrivals: "b" admits (and chunk-prefills) while "a"
    # decodes, so prefills and decodes co-reside in the same ticks
    sched.submit(Request(req_id="a", arrival=0.0, prompt_len=24,
                         output_len=6))
    sched.submit(Request(req_id="b", arrival=0.001, prompt_len=8,
                         output_len=8))
    sched.run(max_steps=200)
    toks = {rid: eng.generated_tokens(rid) for rid in ("a", "b")}
    return toks, [l.phase for l in sched.log], eng, sched


@pytest.mark.parametrize("use_kernel", [None, True])
def test_mixed_step_token_identity_vs_sequential(setup, use_kernel):
    """Acceptance: the one-launch mixed step is token-identical to the
    sequential prefill+decode launches, with ``use_kernel`` auto and
    force (Pallas interpret on CPU)."""
    toks_m, phases_m, eng_m, _ = run_sched(setup, mixed=True,
                                           use_kernel=use_kernel)
    toks_s, phases_s, eng_s, _ = run_sched(setup, mixed=False,
                                           use_kernel=use_kernel)
    assert toks_m == toks_s
    assert "mixed" in phases_m and "mixed" not in phases_s
    assert eng_m.sync_stats.host_argmax == 0
    assert eng_s.sync_stats.host_argmax == 0


def test_mixed_step_one_launch_per_tick(setup):
    """Acceptance: with co-resident prefills+decodes the engine launches
    ONE compiled step per scheduler tick (sequential needs two)."""
    toks, phases, eng, sched = run_sched(setup, mixed=True)
    assert eng.sync_stats.steps == len(phases)  # one launch per tick
    mixed_logs = [l for l in sched.log if l.phase == "mixed"]
    assert mixed_logs and all(l.n_running > 0 for l in mixed_logs)
    _, phases_s, eng_s, _ = run_sched(setup, mixed=False)
    assert eng_s.sync_stats.steps == len(phases_s)  # still 1:1 with logs
    assert eng.sync_stats.steps < eng_s.sync_stats.steps


def test_over_cap_request_rejected_not_crashed(setup):
    """With prompts no longer truncated, a request whose full context
    can never fit a ``max_blocks_per_req``-wide table must be REJECTED
    at admission (``FlyingEngine.request_fits``) — not crash the serve
    loop mid-prefill — while co-resident requests complete."""
    cfg, model, params = setup
    geom = PoolGeometry(cfg, PLAN, num_blocks=64, block_base=4)
    eng = FlyingEngine(model, PLAN, geom, params, batch_per_engine=2,
                       max_blocks_per_req=8, prefill_len=8)  # cap: 32 tok
    sched = DynamicScheduler(
        PLAN, geom, eng,
        SchedulerConfig(strategy="hard", max_batch_per_group=2,
                        prefill_chunk=8))
    sched.submit(Request(req_id="huge", arrival=0.0, prompt_len=100,
                         output_len=4))
    sched.submit(Request(req_id="ok", arrival=0.0, prompt_len=8,
                         output_len=4))
    sched.run(max_steps=100)
    assert sched.pool.all["huge"].state == "rejected"
    assert sched.pool.all["ok"].state == "done"
    assert len(eng.generated_tokens("ok")) >= 4


def test_mixed_step_temperature_sampling_identical(setup):
    """Seeded stochastic sampling: the mixed step draws the same
    per-launch seed sequence as the sequential pair (two seed draws per
    mixed tick), so temperature>0 streams match too."""
    toks_m, _, _, _ = run_sched(setup, mixed=True, temperature=0.7)
    toks_s, _, _, _ = run_sched(setup, mixed=False, temperature=0.7)
    assert toks_m == toks_s


# ---------------------------------------------------------------------------
# jaxpr guard: the serving prefill program is gather-free and never
# materializes a dense fp32 score tensor (mirror of the MLA
# no-expansion assertion)
# ---------------------------------------------------------------------------

def _iter_eqns(jaxpr):
    for eqn in jaxpr.eqns:
        yield eqn
        for p in eqn.params.values():
            subs = p if isinstance(p, (tuple, list)) else (p,)
            for q in subs:
                if isinstance(q, jex_core.ClosedJaxpr):
                    yield from _iter_eqns(q.jaxpr)
                elif isinstance(q, jex_core.Jaxpr):
                    yield from _iter_eqns(q)


def _prefill_shapes(setup, impl, *, B=2, T=8, page=4, nblk=16, MB=6,
                    prior=8):
    """Trace one chunked-prefill forward; return the banned shapes found:
    the full-pool gather [B, MB*page, KV, hd] and dense fp32 scores
    [B, H, T, *]."""
    cfg, model, params = setup
    from repro.core.views import SINGLE
    from repro.models.cache import PrefillBackend
    KV, hd, H = cfg.num_kv_heads, cfg.resolved_head_dim, cfg.num_heads
    st = model.init_states(ctx=SINGLE, batch=B, num_blocks=nblk, page=page,
                           mode="prefill")
    bt = jnp.arange(B * MB).reshape(B, MB)
    pos = jnp.full((B,), prior, jnp.int32)[:, None] + jnp.arange(T)[None]
    slots = (bt[jnp.arange(B)[:, None], pos // page] * page + pos % page)
    backend = PrefillBackend(slots=slots,
                             prior_len=jnp.full((B,), prior, jnp.int32),
                             block_table=bt, chunked=True, impl=impl)
    toks = jnp.zeros((B, T), jnp.int32)
    jaxpr = jax.make_jaxpr(
        lambda p, s, t, po: model.forward(
            p, SINGLE, mode="prefill", tokens=t, positions=po,
            backend=backend, states=s))(params, st, toks,
                                        pos.astype(jnp.int32))
    banned_gather = {(B, MB * page, KV, hd)}
    # dense [B,H,Tq,Tk] fp32 scores: Tk is the in-chunk extent or the
    # gathered pool width (hd is chosen to collide with neither, so the
    # legitimate [B,H,T,hd] layout tensors never match)
    assert hd not in (T, MB * page)
    banned_scores = {(B, H, T, T), (B, H, T, MB * page)}
    found = set()
    for eqn in _iter_eqns(jaxpr.jaxpr):
        for v in eqn.outvars:
            shape = tuple(getattr(v.aval, "shape", ()))
            if shape in banned_gather:
                found.add(("gather", shape))
            if shape in banned_scores:
                found.add(("dense_scores", shape))
    return found


def test_kernel_prefill_program_is_gather_free(setup):
    """Acceptance: the forced-kernel serving prefill jaxpr contains no
    full-width pool gather and no dense [B,H,Tq,Tk] score tensor; the
    reference program contains both (proving the detector works)."""
    assert _prefill_shapes(setup, "force") == set()
    ref = _prefill_shapes(setup, "ref")
    assert any(k == "gather" for k, _ in ref)
    assert any(k == "dense_scores" for k, _ in ref)
