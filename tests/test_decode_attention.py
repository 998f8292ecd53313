"""Context-proportional decode attention (§Perf D5), single device:
kernel-dispatch vs reference token identity through the full compiled
serve step, mb-bucketed runner keys / staging widths, and the absorbed
MLA decode contract (allclose to the naive expansion, and the expanded
[B,Tk,H,*] K/V provably absent from the decode jaxpr)."""
from dataclasses import dataclass

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.extend import core as jex_core

from repro.configs import get_config
from repro.core.engine import FlyingEngine
from repro.core.kv_adaptor import PoolGeometry
from repro.core.modes import ParallelPlan
from repro.core.task_pool import Request
from repro.models.model import build_model

PLAN = ParallelPlan(engine_rows=1, tp_base=1, data_rows=1)
PROMPT = 8


@pytest.fixture(scope="module")
def setup():
    cfg = get_config("llama3-8b").reduced()
    model = build_model(cfg, jnp.float32)
    params = model.init(jax.random.key(0))
    return cfg, model, params


def make_engine(setup, *, use_kernel=None, max_blocks=16):
    cfg, model, params = setup
    geom = PoolGeometry(cfg, PLAN, num_blocks=64, block_base=4)
    return FlyingEngine(model, PLAN, geom, params, batch_per_engine=2,
                        max_blocks_per_req=max_blocks, prefill_len=PROMPT,
                        use_kernel=use_kernel)


def drive(eng, steps, n=2):
    reqs = []
    for i in range(n):
        r = Request(req_id=f"q{i}", arrival=0.0, prompt_len=PROMPT,
                    output_len=1 << 30)
        r.engine_group = 0
        reqs.append(r)
    for r in reqs:
        eng.adaptors[0].append_slots(r.req_id, PROMPT)
    eng.prefill(reqs, 1, PROMPT)
    for r in reqs:
        eng.adaptors[0].append_slots(r.req_id, 1)
    for _ in range(steps):
        eng.decode(reqs, 1)
        for r in reqs:
            eng.adaptors[0].append_slots(r.req_id, 1)
    return {r.req_id: eng.generated_tokens(r.req_id) for r in reqs}, eng


def test_kernel_dispatch_token_identity_through_serve_step(setup):
    """Acceptance: the forced-kernel path (Pallas interpret on CPU,
    fused single-token append) produces bit-identical greedy tokens to
    the reference path through the full compiled serve step, across a
    window long enough to cross block boundaries and mb buckets."""
    toks_ref, eng_ref = drive(make_engine(setup, use_kernel=False), 12)
    toks_ker, eng_ker = drive(make_engine(setup, use_kernel=True), 12)
    toks_auto, _ = drive(make_engine(setup, use_kernel=None), 12)
    assert toks_ref == toks_ker
    assert toks_ref == toks_auto
    assert eng_ker.sync_stats.host_argmax == 0
    assert eng_ref.sync_stats.host_argmax == 0


def test_mb_bucket_narrow_program_and_growth(setup):
    """A long-context-configured engine (max_blocks=64) must run short
    batches through a NARROW bucketed executable: the decode runner key
    carries mb_bucket, staging block tables are bucket-width, and
    crossing a pow2 boundary rebuilds onto the next bucket — with
    tokens identical to a narrow (max_blocks=16) engine throughout."""
    eng = make_engine(setup, max_blocks=64)
    toks_wide, eng = drive_and_return(eng)
    toks_narrow, _ = drive_and_return(make_engine(setup, max_blocks=16))
    assert toks_wide == toks_narrow
    # ctx 9..21 over the window: need 3..6 blocks -> buckets 4 then 8,
    # never the configured 64
    mb_keys = sorted(k[6] for k in eng.pool._runners if k[1] == "decode")
    assert mb_keys == [4, 8]
    c = eng._steady
    assert c.mb == 8
    assert c.bufs["btab"].shape[1] == 8
    # prefill key carries its own (narrow) mb bucket
    pre = [k for k in eng.pool._runners if k[1] == "prefill"]
    assert pre and all(k[6] <= 4 for k in pre)


def drive_and_return(eng):
    return drive(eng, 12)


def test_mb_bucket_respects_configured_max(setup):
    """The bucket never exceeds max_blocks_per_req: at full capacity
    (ctx -> max_blocks*cap) the widest runner key equals the configured
    max, not the next pow2."""
    eng = make_engine(setup, max_blocks=4)
    toks, eng = drive(eng, 7)  # ctx reaches 16 = max_blocks * block_base
    mb_keys = {k[6] for k in eng.pool._runners if k[1] == "decode"}
    assert max(mb_keys) == 4
    toks16, _ = drive(make_engine(setup, max_blocks=16), 7)
    assert toks == toks16


# ---------------------------------------------------------------------------
# absorbed MLA decode
# ---------------------------------------------------------------------------

@dataclass(frozen=True)
class _NaivePagedDecode:
    """The pre-absorption decode backend contract: append the token,
    hand the gathered compressed context back to the naive-expansion
    math in mla_attention (not a DecodeBackend, so the absorbed branch
    does not trigger)."""
    slots: jax.Array
    block_table: jax.Array
    context_len: jax.Array

    def append_ctx(self, state, vals, *, positions):
        from repro.models.cache import paged_append, paged_gather
        (pool,) = state if isinstance(state, tuple) else (state,)
        pool = paged_append(pool, vals[:, None] if vals.ndim == 2 else vals,
                            self.slots[:, None])
        ctx = paged_gather(pool, self.block_table)
        return ctx, self.context_len, (pool,)


@pytest.fixture(scope="module")
def mla_setup():
    cfg = get_config("deepseek-v2-236b").reduced()
    model = build_model(cfg, jnp.float32)
    params = model.init(jax.random.key(0))
    B, T = 2, 12
    toks = jax.random.randint(jax.random.key(1), (B, T + 1), 0,
                              cfg.vocab_size)
    from repro.core.views import SINGLE
    from repro.models.cache import PrefillBackend
    page, nblk = 4, 24
    st = model.init_states(ctx=SINGLE, batch=B, num_blocks=nblk, page=page,
                           mode="prefill")
    nb = (T + page) // page + 1
    bt = jnp.arange(2 * nb).reshape(2, nb)
    slots = (bt[:, :, None] * page
             + jnp.arange(page)[None, None]).reshape(B, -1)[:, :T]
    pk = PrefillBackend(slots=slots, prior_len=jnp.zeros(B, jnp.int32),
                        block_table=bt)
    _, st, _ = model.forward(params, SINGLE, mode="prefill",
                             tokens=toks[:, :T], backend=pk, states=st)
    dslots = bt.reshape(B, -1)[:, T // page] * page + (T % page)
    dargs = dict(slots=dslots, block_table=bt,
                 context_len=jnp.full((B,), T + 1, jnp.int32))
    dbatch = dict(tokens=toks[:, T:T + 1],
                  positions=jnp.full((B, 1), T, jnp.int32))
    return cfg, model, params, st, dargs, dbatch


def _decode_logits(mla_setup, backend):
    cfg, model, params, st, dargs, dbatch = mla_setup
    from repro.core.views import SINGLE
    ld, _, _ = model.forward(params, SINGLE, mode="decode",
                             backend=backend, states=st, **dbatch)
    return ld[:, 0]


@pytest.mark.parametrize("impl", ["ref", "interpret"])
def test_absorbed_mla_decode_matches_naive(mla_setup, impl):
    """Absorbed (q·W_uk against the compressed cache) == naive
    (materialized k_nope/vexp) MLA decode, on both dispatch impls."""
    from repro.models.cache import DecodeBackend
    cfg, model, params, st, dargs, dbatch = mla_setup
    naive = _decode_logits(mla_setup, _NaivePagedDecode(**dargs))
    absorbed = _decode_logits(mla_setup, DecodeBackend(impl=impl, **dargs))
    np.testing.assert_allclose(np.asarray(absorbed), np.asarray(naive),
                               rtol=2e-4, atol=2e-4)


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


def _expanded_shapes(mla_setup, backend):
    """All [B,Tk,H,Dn|Dv] intermediate shapes in the decode jaxpr —
    the naive path's expanded K/V; must be empty for absorbed."""
    cfg, model, params, st, dargs, dbatch = mla_setup
    from repro.core.views import SINGLE
    B = dbatch["tokens"].shape[0]
    Tk = int(dargs["block_table"].shape[1]) * 4  # page=4
    H, m = cfg.num_heads, cfg.mla
    banned = {(B, Tk, H, m.qk_nope_head_dim), (B, Tk, H, m.v_head_dim)}
    jaxpr = jax.make_jaxpr(
        lambda p, s, t, pos: model.forward(
            p, SINGLE, mode="decode", tokens=t, positions=pos,
            backend=backend, states=s))(
        params, st, dbatch["tokens"], dbatch["positions"])
    found = set()
    for eqn in _iter_eqns(jaxpr.jaxpr):
        for v in eqn.outvars:
            shape = tuple(getattr(v.aval, "shape", ()))
            if shape in banned:
                found.add(shape)
    return found


def test_absorbed_mla_never_materializes_expanded_kv(mla_setup):
    """Acceptance: the paged decode jaxpr contains NO [B,Tk,H,*]
    expanded K/V tensor; the naive reference backend does (which also
    proves the detector works)."""
    from repro.models.cache import DecodeBackend
    cfg, model, params, st, dargs, dbatch = mla_setup
    assert _expanded_shapes(mla_setup, DecodeBackend(impl="ref", **dargs)) \
        == set()
    assert _expanded_shapes(mla_setup, _NaivePagedDecode(**dargs)) != set()
