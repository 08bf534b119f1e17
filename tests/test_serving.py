"""Serving subsystem tests (docs/serving.md).

The load-bearing property: decode over the paged KV cache is
BIT-IDENTICAL to decode over a contiguous cache holding the same
context — across ragged per-request lengths, sliding windows
straddling page boundaries, shuffled physical page assignments, and
alloc/free/realloc churn that leaves stale tenants' kv in reused
pages.  Plus allocator invariants, the continuous engine against a
straightforward per-request serving loop, preemption under memory
pressure, and the paged regime's tuner pricing / persistent-cache
behavior.  The 8-device paged-ring execution test runs in a
subprocess (forced host devices), marked slow like its siblings.
"""
import math
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings
from hypothesis import strategies as st

from repro.configs import get_config
from repro.core import api
from repro.core.chain import attention_chain
from repro.core.perf_model import (MeshSpec, paged_gather_bytes,
                                   paged_gather_seconds)
from repro.kernels.attention import (INVALID_POS, fused_attention,
                                     fused_attention_paged,
                                     fused_attention_partial)
from repro.dist.ring_dispatch import finalize_partials
from repro.models.lm import LM, Runtime
from repro.serving import ServingEngine
from repro.serving import kv_pages as KP

CFG = get_config("qwen3_8b", smoke=True)


# ---------------------------------------------------------------------------
# allocator invariants
# ---------------------------------------------------------------------------

@settings(max_examples=25, deadline=None)
@given(st.integers(2, 40), st.integers(0, 2 ** 31))
def test_page_pool_invariants(n_pages, seed):
    """Random alloc/free churn: the scratch page is never handed out,
    no page is live twice, and accounting balances."""
    rng = np.random.RandomState(seed % (2 ** 32 - 1))
    pool = KP.PagePool(n_pages, page_size=4)
    live: list[list[int]] = []
    for _ in range(50):
        if live and rng.rand() < 0.4:
            pool.free(live.pop(rng.randint(len(live))))
        else:
            got = pool.alloc(int(rng.randint(0, 4)))
            if got is not None:
                live.append(got)
        flat = [p for g in live for p in g]
        assert KP.SCRATCH_PAGE not in flat
        assert len(set(flat)) == len(flat)
        assert pool.n_free + len(flat) == n_pages - 1
    for g in live:
        pool.free(g)
    assert pool.n_free == n_pages - 1


def test_page_pool_errors():
    pool = KP.PagePool(4, 8)
    assert pool.alloc(5) is None and pool.n_free == 3
    pages = pool.alloc(3)
    assert pool.alloc(1) is None
    pool.free(pages)
    with pytest.raises(ValueError):
        pool.free([pages[0]])          # double free
    with pytest.raises(ValueError):
        KP.PagePool(1, 8)              # no room beside scratch


def test_request_pages_ensure_growth_and_failure():
    pool = KP.PagePool(5, page_size=8)   # 4 allocatable
    req = KP.RequestPages()
    assert req.ensure(1, pool) and len(req.pages) == 1
    assert req.ensure(8, pool) and len(req.pages) == 1   # same page
    assert req.ensure(9, pool) and len(req.pages) == 2   # boundary
    other = pool.alloc(2)
    before = list(req.pages)
    assert not req.ensure(25, pool)      # needs 2 more, pool has 0
    assert req.pages == before           # failure left state unchanged
    pool.free(other)
    assert req.ensure(25, pool) and len(req.pages) == 4
    assert req.n_live == 4
    # pages wholly below position 17 go back; logical order stays
    assert req.reclaim_below(17, pool) == 2 and pool.n_free == 2
    assert req.pages[:2] == [KP.RECLAIMED] * 2 and req.n_live == 2
    assert req.ensure(33, pool) and req.n_live == 3
    req.release(pool)
    assert pool.n_free == 4 and req.n_live == 0


# ---------------------------------------------------------------------------
# bit-identity: paged vs contiguous
# ---------------------------------------------------------------------------

def _paged_setup(rng, b, hkv, d, ps, mp, n_pool, lengths):
    """Scatter per-request kv (position order) into a shuffled page
    assignment of page-major (n_pool, ps, hkv, d) pools; returns
    (pools, table, dense) where dense is the contiguous (B, hkv,
    mp*ps, d) layout with garbage beyond length."""
    n_ctx = mp * ps
    dense_k = jnp.asarray(rng.randn(b, hkv, n_ctx, d), jnp.float32)
    dense_v = jnp.asarray(rng.randn(b, hkv, n_ctx, d), jnp.float32)
    pool_k = jnp.asarray(rng.randn(n_pool, ps, hkv, d), jnp.float32)
    pool_v = jnp.asarray(rng.randn(n_pool, ps, hkv, d), jnp.float32)
    order = rng.permutation(n_pool - 1) + 1   # never the scratch page
    table = np.full((b, mp), -1, np.int32)
    nxt = 0
    for i in range(b):
        npages = math.ceil(lengths[i] / ps)
        for j in range(npages):
            pg = int(order[nxt]); nxt += 1
            table[i, j] = pg
            page = slice(j * ps, (j + 1) * ps)
            pool_k = pool_k.at[pg].set(dense_k[i, :, page].transpose(1, 0, 2))
            pool_v = pool_v.at[pg].set(dense_v[i, :, page].transpose(1, 0, 2))
    return pool_k, pool_v, jnp.asarray(table), dense_k, dense_v


@settings(max_examples=8, deadline=None)
@given(st.integers(0, 2 ** 30), st.integers(0, 1), st.integers(0, 2))
def test_paged_kernel_bit_identical_ragged(seed, m_choice, win_choice):
    """fused_attention_paged == the dense-layout partial kernel,
    bitwise, on ragged batches — windows chosen to straddle page
    boundaries."""
    rng = np.random.RandomState(seed % (2 ** 32 - 1))
    b, hq, hkv, d, ps, mp = 3, 4, 2, 8, 4, 5
    n_ctx = mp * ps
    m = (1, 4)[m_choice]
    window = (0, 6, 11)[win_choice]     # 6 and 11 straddle ps=4 pages
    lengths = [int(rng.randint(m, n_ctx + 1)) for _ in range(b)]
    pool_k, pool_v, table, dense_k, dense_v = _paged_setup(
        rng, b, hkv, d, ps, mp, n_pool=b * mp + 2, lengths=lengths)
    q = jnp.asarray(rng.randn(b, hq, m, d), jnp.float32)
    larr = jnp.asarray(lengths, jnp.int32)

    got = fused_attention_paged(q, pool_k, pool_v, table, larr,
                                bq=4, bkv=8, window=window,
                                interpret=True)
    # dense reference: same N, rows at each request's tail, slots past
    # the length (and the stale garbage they hold) rejected causally
    q_pos = larr[:, None] - m + jnp.arange(m, dtype=jnp.int32)
    o, _, l = fused_attention_partial(
        q, dense_k, dense_v, jnp.arange(n_ctx, dtype=jnp.int32), q_pos,
        bq=4, bkv=8, causal=True, window=window, interpret=True)
    want = finalize_partials(o, l, q.dtype)
    assert np.array_equal(np.asarray(got), np.asarray(want))


def test_paged_kernel_matches_fused_full_context():
    """When every slot is real, the paged kernel reproduces
    ``fused_attention`` on the contiguous cache bit-for-bit."""
    rng = np.random.RandomState(0)
    b, hq, hkv, d, ps, mp = 2, 4, 2, 8, 4, 4
    n = mp * ps
    lengths = [n] * b
    pool_k, pool_v, table, dense_k, dense_v = _paged_setup(
        rng, b, hkv, d, ps, mp, n_pool=b * mp + 2, lengths=lengths)
    q = jnp.asarray(rng.randn(b, hq, n, d), jnp.float32)
    want = fused_attention(q, dense_k, dense_v, bq=8, bkv=8,
                           causal=True, interpret=True)
    got = fused_attention_paged(q, pool_k, pool_v, table,
                                jnp.asarray(lengths, jnp.int32),
                                bq=8, bkv=8, interpret=True)
    assert np.array_equal(np.asarray(got), np.asarray(want))


def test_paged_chunked_merge_close():
    """pages_per_chunk exercises the log-sum-exp merge across chunk
    boundaries: f32-exact association differences only."""
    rng = np.random.RandomState(1)
    b, hq, hkv, d, ps, mp = 2, 2, 2, 8, 4, 6
    lengths = [21, 9]
    pool_k, pool_v, table, *_ = _paged_setup(
        rng, b, hkv, d, ps, mp, n_pool=b * mp + 2, lengths=lengths)
    q = jnp.asarray(rng.randn(b, hq, 1, d), jnp.float32)
    larr = jnp.asarray(lengths, jnp.int32)
    whole = fused_attention_paged(q, pool_k, pool_v, table, larr,
                                  interpret=True)
    for cpp in (1, 2, 4):
        chunked = fused_attention_paged(q, pool_k, pool_v, table, larr,
                                        pages_per_chunk=cpp,
                                        interpret=True)
        np.testing.assert_allclose(np.asarray(chunked), np.asarray(whole),
                                   atol=1e-6)


def _pallas_names(fn) -> set:
    """Names of the Pallas kernels ``fn()`` traces."""
    def walk(jaxpr):
        for eqn in jaxpr.eqns:
            if eqn.primitive.name == "pallas_call":
                yield eqn.params["name"]
            for v in eqn.params.values():
                inner = getattr(v, "jaxpr", None)
                if inner is not None:
                    yield from walk(getattr(inner, "jaxpr", inner))
    return set(walk(jax.make_jaxpr(fn)().jaxpr))


@pytest.mark.parametrize("hq,hkv,pages_per_block,window", [
    (4, 4, 1, 0),       # group 1 (MHA), a block of one page
    (8, 2, 3, 0),       # group 4, a block of three pages
    (4, 1, 2, 0),       # Hkv = 1 (MQA)
    (8, 2, 1, 7),       # windows of 7 straddle 4-slot blocks
    (8, 2, 3, 7),       # ... and the 12-slot block boundary
])
def test_paged_decode_kernel_bit_identical(hq, hkv, pages_per_block,
                                           window):
    """The in-place decode kernel == the dense-layout partial kernel,
    bitwise: an inactive slot (length 0, an all -1 row), lengths on a
    page boundary and at n_ctx, a shuffled table with an unallocated
    entry inside a live range (it reads the scratch page, which every
    -1 entry shares)."""
    rng = np.random.RandomState(hq * 100 + hkv * 10 + pages_per_block)
    d, ps, mp = 8, 4, 6
    n_ctx = mp * ps
    bkv = pages_per_block * ps
    lengths = [0, 8, n_ctx, 13, 5]
    b = len(lengths)
    pool_k, pool_v, table, dense_k, dense_v = _paged_setup(
        rng, b, hkv, d, ps, mp, n_pool=b * mp + 2, lengths=lengths)
    table = table.at[3, 1].set(-1)      # a hole in slot 3's live range
    q = jnp.asarray(rng.randn(b, hq, 1, d), jnp.float32)
    larr = jnp.asarray(lengths, jnp.int32)

    def run():
        return fused_attention_paged(q, pool_k, pool_v, table, larr,
                                     bkv=bkv, window=window,
                                     interpret=True)

    assert _pallas_names(run) == {"paged_decode_attention"}
    kv_pos = KP.paged_kv_positions(table, ps, invalid=INVALID_POS)
    o, _, l = fused_attention_partial(
        q, dense_k, dense_v, kv_pos, larr[:, None] - 1, bq=1, bkv=bkv,
        causal=True, window=window, interpret=True)
    want = finalize_partials(o, l, q.dtype)
    got = run()
    assert np.array_equal(np.asarray(got), np.asarray(want))
    assert not np.asarray(got[0]).any()    # the inactive slot


@pytest.mark.parametrize("m,bkv,pages_per_chunk", [
    (4, 8, 0),          # prefill rows
    (1, 8, 2),          # chunked
    (1, 6, 0),          # a block of a page and a half
])
def test_paged_gather_path_kept(m, bkv, pages_per_chunk):
    """Query blocks, chunked tables and blocks that split a page keep
    the gather + partial path."""
    q = jnp.zeros((2, 4, m, 8))
    pages = jnp.zeros((9, 4, 2, 8))
    table = jnp.zeros((2, 6), jnp.int32)
    names = _pallas_names(lambda: fused_attention_paged(
        q, pages, pages, table, jnp.full((2,), 10, jnp.int32), bkv=bkv,
        pages_per_chunk=pages_per_chunk, interpret=True))
    assert names == {"fused_attention_partial"}


def test_model_paged_decode_bit_identical_with_churn():
    """End-to-end model property: prefill + decode through the paged
    cache equals the contiguous-cache model bitwise — including after
    alloc/free/realloc churn leaves stale kv in reused pages."""
    model = LM(CFG)
    params = model.init_params(jax.random.PRNGKey(0))
    ps, mp = 4, 6
    n_ctx = ps * mp
    pool = KP.PagePool(10, ps)
    pcache = model.init_paged_cache(10, ps)
    prefill_p = jax.jit(model.prefill_paged)
    decode_p = jax.jit(model.decode_step_paged)
    prefill = jax.jit(model.prefill)
    decode = jax.jit(model.decode_step)

    def run_one(seed, plen, gen):
        prompt = jax.random.randint(jax.random.PRNGKey(seed), (1, plen),
                                    0, CFG.vocab)
        cache = model.init_cache(1, n_ctx)
        logits, cache = prefill(params, prompt, cache)
        ref_l = [np.asarray(logits)]
        toks = [int(jnp.argmax(logits, -1)[0])]
        for i in range(gen - 1):
            logits, cache = decode(params, cache,
                                   jnp.array([toks[-1]], jnp.int32),
                                   jnp.int32(plen + i))
            ref_l.append(np.asarray(logits))
            toks.append(int(jnp.argmax(logits, -1)[0]))

        req = KP.RequestPages()
        assert req.ensure(plen, pool)
        s_pad = math.ceil(plen / ps) * ps
        tp = jnp.concatenate(
            [prompt, jnp.zeros((1, s_pad - plen), jnp.int32)], 1)
        nonlocal pcache
        logits, pcache = prefill_p(
            params, tp, pcache,
            jnp.asarray(KP.table_array([req], mp)), jnp.int32(plen))
        got_l = [np.asarray(logits)]
        ptoks = [int(jnp.argmax(logits, -1)[0])]
        for i in range(gen - 1):
            assert req.ensure(plen + i + 1, pool)
            logits, pcache = decode_p(
                params, pcache, jnp.array([ptoks[-1]], jnp.int32),
                jnp.array([plen + i], jnp.int32),
                jnp.asarray(KP.table_array([req], mp)))
            got_l.append(np.asarray(logits))
            ptoks.append(int(jnp.argmax(logits, -1)[0]))
        req.release(pool)     # churn: next request reuses these pages
        for a, b in zip(ref_l, got_l):
            assert np.array_equal(a, b)
        assert toks == ptoks

    # ragged lengths; page reuse across iterations leaves stale kv
    for seed, plen, gen in [(1, 5, 4), (2, 9, 6), (3, 13, 3), (4, 4, 8)]:
        run_one(seed, plen, gen)
    assert pool.n_free == pool.n_pages - 1


# ---------------------------------------------------------------------------
# the continuous engine
# ---------------------------------------------------------------------------

def _reference_serve(model, params, reqs, n_ctx):
    """Straightforward per-request contiguous serving (the semantics
    the engine must reproduce)."""
    prefill = jax.jit(model.prefill)
    decode = jax.jit(model.decode_step)
    out = []
    for prompt, gen in reqs:
        cache = model.init_cache(1, n_ctx)
        logits, cache = prefill(params, jnp.asarray(prompt)[None], cache)
        toks = [int(jnp.argmax(logits, -1)[0])]
        for i in range(gen - 1):
            logits, cache = decode(params, cache,
                                   jnp.array([toks[-1]], jnp.int32),
                                   jnp.int32(len(prompt) + i))
            toks.append(int(jnp.argmax(logits, -1)[0]))
        out.append(toks)
    return out


def test_engine_matches_reference_on_ragged_workload():
    model = LM(CFG)
    params = model.init_params(jax.random.PRNGKey(0))
    rng = np.random.RandomState(0)
    reqs = [(rng.randint(0, CFG.vocab, size=int(rng.randint(3, 14)))
             .astype(np.int32), int(g))
            for g in (3, 9, 1, 6, 12, 2)]
    eng = ServingEngine(model, params, max_batch=3, page_size=4,
                        n_pages=32, max_pages_per_seq=8,
                        choose_regime=False)
    results, stats = eng.run(reqs)
    assert [r.rid for r in results] == list(range(len(reqs)))
    assert [len(r.tokens) for r in results] == [g for _, g in reqs]
    assert stats["generated"] == sum(g for _, g in reqs)
    # iteration-level batching actually happened: fewer decode steps
    # than the fixed lock-step baseline would need
    assert stats["decode_steps"] < sum(g for _, g in reqs)
    ref = _reference_serve(model, params, reqs, eng.n_ctx)
    for r, want in zip(results, ref):
        assert r.tokens == want
    assert eng.pool.n_free == eng.pool.n_pages - 1
    # the inter-token-latency trace covers every decode step
    itl = stats["decode_step_wall_s"]
    assert len(itl) == stats["decode_steps"]
    assert all(dt > 0.0 for dt in itl)


def test_itl_percentile_helper():
    """The bench_serving percentile (linear interpolation between
    closest ranks) on a deterministic synthetic trace, pinned against
    hand-computed values and numpy's default."""
    root = os.path.join(os.path.dirname(__file__), "..")
    if root not in sys.path:
        sys.path.insert(0, root)
    from benchmarks.bench_serving import percentile

    trace = [5.0, 1.0, 3.0, 2.0, 4.0]  # unsorted on purpose
    assert percentile(trace, 0.0) == 1.0
    assert percentile(trace, 100.0) == 5.0
    assert percentile(trace, 50.0) == 3.0
    assert percentile(trace, 25.0) == 2.0
    # pos = 4 * 0.99 = 3.96 -> 4.0 + 0.96 * (5.0 - 4.0)
    assert percentile(trace, 99.0) == pytest.approx(4.96)
    assert percentile([7.0], 99.0) == 7.0
    rng = np.random.RandomState(0)
    for t in rng.rand(4, 9):
        for q in (0.0, 10.0, 37.5, 50.0, 90.0, 99.0, 100.0):
            assert percentile(list(t), q) == pytest.approx(
                float(np.percentile(t, q)))
    with pytest.raises(ValueError):
        percentile([], 50.0)
    with pytest.raises(ValueError):
        percentile([1.0], 101.0)


def test_engine_preemption_recovers():
    model = LM(CFG)
    params = model.init_params(jax.random.PRNGKey(0))
    rng = np.random.RandomState(1)
    reqs = [(rng.randint(0, CFG.vocab, size=6).astype(np.int32), 10)
            for _ in range(4)]
    eng = ServingEngine(model, params, max_batch=4, page_size=4,
                        n_pages=10, max_pages_per_seq=4,
                        choose_regime=False)
    results, stats = eng.run(reqs)
    assert stats["preemptions"] > 0
    assert [len(r.tokens) for r in results] == [10] * 4
    assert any(r.n_preempted for r in results)
    assert eng.pool.n_free == eng.pool.n_pages - 1
    for r in results:
        assert r.t_submit <= r.t_admit <= r.t_first <= r.t_finish


def test_engine_repeated_preemption_prompt_consistent():
    """A request preempted more than once must not duplicate its
    recomputed tokens in the rebuilt prompt: every queued recompute
    holds exactly base_prompt ++ generated-so-far."""
    model = LM(CFG)
    params = model.init_params(jax.random.PRNGKey(0))
    rng = np.random.RandomState(2)
    prompt = rng.randint(0, CFG.vocab, size=8).astype(np.int32)
    eng = ServingEngine(model, params, max_batch=1, page_size=4,
                        n_pages=12, max_pages_per_seq=6,
                        choose_regime=False)
    eng.submit(prompt, 12)
    eng.step()                      # admit + first decode
    first = (eng.slots[0].t_admit, eng.slots[0].t_first)
    for round_ in range(2):         # force-preempt the same request
        eng.step()
        idx = next(i for i, s in enumerate(eng.slots) if s is not None)
        eng._preempt(idx)
        p = eng.queue[0]
        assert len(p.prompt) == p.base_prompt_len + len(p.done)
        assert p.prompt[:8].tolist() == prompt.tolist()
        assert p.prompt[8:].tolist() == p.done
        eng.step()                  # readmit (recompute prefill)
    while eng.queue or any(s is not None for s in eng.slots):
        eng.step()
    (res,) = eng.finished
    assert len(res.tokens) == 12 and res.n_preempted == 2
    # re-admissions keep the first admission's stamps
    assert (res.t_admit, res.t_first) == first
    assert res.t_submit <= res.t_admit <= res.t_first <= res.t_finish
    assert eng.pool.n_free == eng.pool.n_pages - 1


def test_engine_submit_validation_and_eos():
    model = LM(CFG)
    params = model.init_params(jax.random.PRNGKey(0))
    eng = ServingEngine(model, params, max_batch=2, page_size=4,
                        n_pages=12, max_pages_per_seq=4,
                        choose_regime=False)
    with pytest.raises(ValueError):
        eng.submit(np.zeros(20, np.int32), 1)       # > n_ctx
    # eos cuts generation short and the report stays honest
    probe = ServingEngine(model, params, max_batch=1, page_size=4,
                          n_pages=12, max_pages_per_seq=4,
                          choose_regime=False)
    prompt = np.arange(5, dtype=np.int32)
    first, _ = probe.run([(prompt, 2)])
    eos = first[0].tokens[0]
    eng.eos_id = eos
    res, _ = eng.run([(prompt, 8)])
    assert res[0].tokens[0] == eos and len(res[0].tokens) == 1


def test_engine_rejects_non_attention_arch():
    cfg = get_config("mamba2_1p3b", smoke=True)
    model = LM(cfg)
    with pytest.raises(NotImplementedError):
        model.init_paged_cache(8, 4)


# ---------------------------------------------------------------------------
# planner-served traffic: Runtime(planner=True) through the engine
# (core/planner.py decode/prefill DAGs executed by run_planned_layer)
# ---------------------------------------------------------------------------

@pytest.fixture()
def _plan_cache(tmp_path, monkeypatch):
    """Isolate planner memo + disk records from the user's real cache."""
    from repro.core import planner
    monkeypatch.setenv("REPRO_CACHE_DIR", str(tmp_path))
    planner.clear_memo()
    yield planner
    planner.clear_memo()


@pytest.mark.parametrize("stitch", [False, True])
def test_engine_planner_matches_hand_wired(stitch, _plan_cache):
    """The planner-served engine — prefill and decode blocks executed
    from carved phase-keyed plans — emits token streams bit-identical
    to the hand-wired paged path on this f32 config, across ragged
    lengths, with stitching off AND on (stitched glue's one boundary
    downcast is a no-op on float32)."""
    planner = _plan_cache
    hand = LM(CFG)
    params = hand.init_params(jax.random.PRNGKey(0))
    rng = np.random.RandomState(0)
    reqs = [(rng.randint(0, CFG.vocab, size=int(rng.randint(3, 14)))
             .astype(np.int32), int(g))
            for g in (3, 9, 1, 6, 12, 2)]
    kw = dict(max_batch=3, page_size=4, n_pages=32, max_pages_per_seq=8,
              choose_regime=False)
    base, _ = ServingEngine(hand, params, **kw).run(reqs)

    planned = LM(CFG, Runtime(planner=True, stitch=stitch))
    eng = ServingEngine(planned, params, **kw)
    results, stats = eng.run(reqs)
    assert [r.tokens for r in results] == [r.tokens for r in base]
    assert stats["generated"] == sum(g for _, g in reqs)
    # both serving phases actually planned (phase at key index 8)
    phases = {k[8] for k in planner._PLAN_MEMO}
    assert {"prefill", "decode"} <= phases
    assert eng.pool.n_free == eng.pool.n_pages - 1
    if not stitch:
        ref = _reference_serve(hand, params, reqs, eng.n_ctx)
        for r, want in zip(results, ref):
            assert r.tokens == want


@pytest.mark.parametrize("stitch", [False, True])
def test_engine_planner_matches_hand_wired_windowed(stitch, _plan_cache):
    """The same comparison under a sliding window shorter than the
    prompts: prefill masks inside the prompt, and decode crosses the
    window's edge at every step and reclaims the pages below it.  The
    planned layers read the window from the one rule the hand-wired
    blocks read (``ModelConfig.attn_window``), so the token streams are
    bit-identical with stitching off, and on this float32 config with
    it on."""
    import dataclasses as _dc
    planner = _plan_cache
    cfg = _dc.replace(CFG, window=6)
    hand = LM(cfg)
    params = hand.init_params(jax.random.PRNGKey(0))
    rng = np.random.RandomState(2)
    reqs = [(rng.randint(0, cfg.vocab, size=n).astype(np.int32), g)
            for n, g in ((9, 7), (13, 10), (7, 12), (11, 3))]
    kw = dict(max_batch=3, page_size=4, n_pages=32, max_pages_per_seq=8,
              choose_regime=False)
    base, _ = ServingEngine(hand, params, **kw).run(reqs)

    eng = ServingEngine(LM(cfg, Runtime(planner=True, stitch=stitch)),
                        params, **kw)
    results, stats = eng.run(reqs)
    assert [r.tokens for r in results] == [r.tokens for r in base]
    assert stats["reclaimed_pages"] > 0
    keys = list(planner._PLAN_MEMO)
    assert {"prefill", "decode"} <= {k[8] for k in keys}
    assert all(k[2] == planner.config_fingerprint(cfg) for k in keys)


def test_engine_planner_preemption_recovers(_plan_cache):
    """Preemption + recompute-prefill through planner-served blocks:
    same recovery semantics and the same tokens as the hand-wired
    engine under identical memory pressure."""
    hand = LM(CFG)
    params = hand.init_params(jax.random.PRNGKey(0))
    rng = np.random.RandomState(1)
    reqs = [(rng.randint(0, CFG.vocab, size=6).astype(np.int32), 10)
            for _ in range(4)]
    kw = dict(max_batch=4, page_size=4, n_pages=10, max_pages_per_seq=4,
              choose_regime=False)
    base, base_stats = ServingEngine(hand, params, **kw).run(reqs)
    assert base_stats["preemptions"] > 0

    eng = ServingEngine(LM(CFG, Runtime(planner=True)), params, **kw)
    results, stats = eng.run(reqs)
    assert stats["preemptions"] > 0
    assert [len(r.tokens) for r in results] == [10] * 4
    assert [r.tokens for r in results] == [r.tokens for r in base]
    assert eng.pool.n_free == eng.pool.n_pages - 1


# ---------------------------------------------------------------------------
# tuner pricing + persistent cache
# ---------------------------------------------------------------------------

def test_paged_gather_term_and_localization():
    chain = attention_chain(1, 256, 64, 64, heads=4, batch=2)
    whole = paged_gather_bytes(chain, page_size=16)
    kv = 256 * (64 + 64) * 4 * 8          # n*(k+h)*f32*batch(=b*heads)
    assert whole == 2 * kv + (256 // 16) * 4 * 8
    ring = MeshSpec(axes=(("model", 4),), placement=(("n", "model"),))
    local = paged_gather_bytes(chain, page_size=16, mesh=ring)
    assert local < whole / 3              # each shard gathers ~1/4
    assert paged_gather_seconds(chain, 16) > 0


def test_fuse_attention_paged_cached_under_paged_fingerprint(monkeypatch,
                                                             tmp_path):
    monkeypatch.setenv("REPRO_CACHE_DIR", str(tmp_path))
    api.clear_cache()
    kw = dict(page_size=8, heads=2, batch=2, dtype="float32",
              interpret=True)
    tk = api.fuse_attention_paged(1, 64, 16, 16, **kw)
    assert tk.source == "search"
    plain = api.fuse_attention(1, 64, 16, 16, heads=2, batch=2,
                               causal=True, interpret=True)
    # the paged report carries the gather term on top of eq (2')
    assert tk.report.best_time > plain.report.best_time
    # warm start: in-process cache dropped, outcome replayed from disk
    api._CACHE.clear()
    tk2 = api.fuse_attention_paged(1, 64, 16, 16, **kw)
    assert tk2.source == "disk"
    assert tk2.report.best_time == pytest.approx(tk.report.best_time)
    # a different page size is a different cache population
    api._CACHE.clear()
    tk3 = api.fuse_attention_paged(1, 64, 16, 16, page_size=16, heads=2,
                                   batch=2, dtype="float32",
                                   interpret=True)
    assert tk3.source == "search"
    api.clear_cache()


def test_paged_regime_choice_consistent():
    from repro.dist.sharding import Rules
    from repro.kernels import ops
    mesh = jax.make_mesh((max(jax.device_count(), 1),), ("model",))
    rules = Rules(data=(), model="model", tp="model")
    choice, plan = ops.paged_attention_regime_choice(
        rules, mesh, batch=2, q_heads=4, kv_heads=2, q_len=1,
        kv_len=128, head_dim=16, page_size=16)
    assert choice is not None
    # the dispatched regime is the one the model ranked fastest
    assert choice.times[choice.regime] == min(choice.times.values())
    assert all(t > 0 for t in choice.times.values())
    if plan is not None:
        assert "paged-ring" in choice.times


# ---------------------------------------------------------------------------
# 8-device paged-ring execution (subprocess, slow lane)
# ---------------------------------------------------------------------------

RING_SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import json, math
import jax, jax.numpy as jnp
import numpy as np
from repro.configs import get_config
from repro.launch.serve import sharded_runtime
from repro.launch import steps as S
from repro.models.lm import LM
from repro.models.layers import _paged_positional_attention
from repro.serving import ServingEngine, kv_pages as KP
from repro.dist import ring_dispatch as RD
from repro.dist.sharding import Rules

out = {}

# ring decode attention vs the single-device twin, window straddling
mesh, rules, rt = sharded_runtime(4)
b, hq, hkv, d, ps, MP = 2, 4, 2, 16, 8, 8
kp = jax.random.normal(jax.random.PRNGKey(0), (20, ps, hkv, d))
vp = jax.random.normal(jax.random.PRNGKey(1), (20, ps, hkv, d))
q = jax.random.normal(jax.random.PRNGKey(2), (b, hq, 1, d))
table = np.full((b, MP), -1, np.int32)
table[0, :3] = [7, 2, 11]; table[1, :2] = [4, 5]
table = jnp.asarray(table)
positions = jnp.array([18, 11], jnp.int32)
group = hq // hkv
kk = jnp.repeat(KP.gather_pages(kp, table), group, axis=1)
vv = jnp.repeat(KP.gather_pages(vp, table), group, axis=1)
kv_pos = KP.paged_kv_positions(table, ps)
diffs, pipe_diffs, pipe_vs_serial = [], [], []
with jax.set_mesh(mesh):
    for win in (0, 10):
        ref = _paged_positional_attention(q, kk, vv, positions[:, None],
                                          kv_pos, win, d ** -0.5)
        got = RD.paged_ring_decode_attention(
            q, kp, vp, table, positions, window=win, scale=d ** -0.5,
            rules=rules, mesh=mesh, batch_axes=("data",))
        diffs.append(float(jnp.max(jnp.abs(ref - got))))
        # pipelined ppermute combine: same rescaled addends as the
        # serial psum, rotated f32 association
        piped = RD.paged_ring_decode_attention(
            q, kp, vp, table, positions, window=win, scale=d ** -0.5,
            rules=rules, mesh=mesh, batch_axes=("data",),
            pipelined=True)
        pipe_diffs.append(float(jnp.max(jnp.abs(ref - piped))))
        pipe_vs_serial.append(float(jnp.max(jnp.abs(got - piped))))
out["ring_max_diff"] = max(diffs)
out["pipe_max_diff"] = max(pipe_diffs)
out["pipe_vs_serial"] = max(pipe_vs_serial)

# the engine under the mesh: tuner-chosen regime, full workload
cfg = get_config("qwen3_8b", smoke=True)
model = S.build_model(cfg, rt)
ref_model = LM(cfg)
params = ref_model.init_params(jax.random.PRNGKey(0))
rng = np.random.RandomState(3)
reqs = [(rng.randint(0, cfg.vocab, size=9).astype(np.int32), g)
        for g in (3, 8, 5, 2)]
with jax.set_mesh(mesh):
    sparams = jax.device_put(params,
                             S.shardings_for(mesh, model.param_specs()))
    eng = ServingEngine(model, sparams, max_batch=4, page_size=8,
                        n_pages=24, max_pages_per_seq=8)
    res, stats = eng.run(reqs)
out["regime"] = eng.regime
out["rt_ring"] = eng.model.rt.dist_decode_attn
out["rt_pipe"] = eng.model.rt.dist_decode_pipelined
out["counts"] = [len(r.tokens) for r in res]
out["pool_clean"] = eng.pool.n_free == eng.pool.n_pages - 1
print(json.dumps(out))
"""


@pytest.mark.slow
def test_paged_ring_execution_8dev():
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(
        os.path.dirname(__file__), "..", "src")
    proc = subprocess.run([sys.executable, "-c", RING_SCRIPT], env=env,
                          capture_output=True, text=True, timeout=1200)
    assert proc.returncode == 0, proc.stderr[-4000:]
    out = __import__("json").loads(proc.stdout.strip().splitlines()[-1])
    assert out["ring_max_diff"] < 1e-5
    assert out["pipe_max_diff"] < 1e-5
    # f32 combine, same addends: serial vs pipelined differ only by the
    # summation rotation
    assert out["pipe_vs_serial"] < 2e-6
    assert out["counts"] == [3, 8, 5, 2]
    assert out["pool_clean"]
    assert out["regime"] in ("paged-spatial", "paged-ring",
                             "paged-ring-pipelined")
    # the regime threads into the Runtime the engine executes
    assert out["rt_ring"] == (out["regime"] != "paged-spatial")
    assert out["rt_pipe"] == (out["regime"] == "paged-ring-pipelined")


# ---------------------------------------------------------------------------
# contiguous-cache guard + sliding-window page reclamation
# ---------------------------------------------------------------------------

def test_run_planned_layer_rejects_contiguous_cache():
    """Planner-executed decode is paged-only: a contiguous (ring) cache
    reaching run_planned_layer must fail loudly with the remediation
    (Runtime(planner=False)) — not silently read the wrong kv layout."""
    from repro.models import layers as L
    x = jnp.zeros((1, 1, CFG.d_model), jnp.float32)
    rt = Runtime()
    with pytest.raises(NotImplementedError, match="planner=False"):
        L.run_planned_layer(object(), {"mix": {}, "ff": {}}, x, CFG,
                            rt.rules, positions=jnp.zeros((1, 1), jnp.int32),
                            rt=rt, cache={"k": None})


def test_planner_runtime_contiguous_decode_falls_back(_plan_cache):
    """Runtime(planner=True) serving a CONTIGUOUS cache (the reference
    serving loop, no page table) transparently takes the hand-wired
    path instead of tripping the paged-only planner executor — same
    tokens as the plain model."""
    hand = LM(CFG)
    params = hand.init_params(jax.random.PRNGKey(0))
    rng = np.random.RandomState(0)
    reqs = [(rng.randint(0, CFG.vocab, size=int(rng.randint(3, 10)))
             .astype(np.int32), int(g)) for g in (4, 7)]
    want = _reference_serve(hand, params, reqs, 32)
    got = _reference_serve(LM(CFG, Runtime(planner=True)), params,
                           reqs, 32)
    assert got == want


def test_window_reclamation_transparent_and_counted():
    """Sliding-window page reclamation (kv_pages.reclaim_below wired
    into the engine step): pages wholly below the attention window go
    back to the pool mid-request, the RECLAIMED placeholder keeps
    logical indexing intact, and the served tokens are bit-identical
    to the same engine with reclamation disabled — the window mask
    already rejected every position those pages held."""
    import dataclasses as _dc
    cfg = _dc.replace(CFG, window=6)
    model = LM(cfg)
    params = model.init_params(jax.random.PRNGKey(0))
    rng = np.random.RandomState(0)
    reqs = [(rng.randint(0, cfg.vocab, size=8).astype(np.int32), 10),
            (rng.randint(0, cfg.vocab, size=5).astype(np.int32), 12)]
    kw = dict(max_batch=2, page_size=4, n_pages=32, max_pages_per_seq=8,
              choose_regime=False)

    base_eng = ServingEngine(model, params, **kw)
    base_eng._window = 0               # reclamation off, window mask on
    base, base_stats = base_eng.run(list(reqs))
    assert base_stats["reclaimed_pages"] == 0

    eng = ServingEngine(model, params, **kw)
    res, stats = eng.run(list(reqs))
    assert stats["reclaimed_pages"] > 0
    assert [r.tokens for r in res] == [r.tokens for r in base]
    assert [len(r.tokens) for r in res] == [10, 12]
    # reclaimed pages really returned: accounting balances at the end
    assert eng.pool.n_free == eng.pool.n_pages - 1
    # the occupancy telemetry is honest about the smaller footprint
    assert stats["page_slot_steps"] < base_stats["page_slot_steps"]
