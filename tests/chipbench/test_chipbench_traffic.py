"""The seeded traffic generator and the serving loop that sends it."""
import statistics
import time

import numpy as np
import pytest

from chipbench_helpers import FakeEngine, TINY_CHAT, tiny_cell

from harness import serve_loop, spec, traffic_gen


@pytest.fixture(scope="module")
def chat():
    return spec.load_cell("qwen3-8b-4L.chat")


@pytest.fixture(scope="module")
def reason():
    return spec.load_cell("mistral-nemo-12b-4L.reason")


def test_same_seed_same_requests_and_tokens(chat):
    a = traffic_gen.Traffic(chat.traffic, 2**31 + 11, rate=4.0)
    b = traffic_gen.Traffic(chat.traffic, 2**31 + 11, rate=4.0)
    for i in range(300):
        ra, rb = a.next(i), b.next(i)
        assert ra == rb
    np.testing.assert_array_equal(a.tokens(a.next(5), 151936),
                                  b.tokens(b.next(5), 151936))


def test_every_stretch_of_requests_holds_the_same_mix(chat):
    """Prompt and answer lengths are independent of each other, and any
    64 consecutive requests hold the block's mean within a few percent,
    whatever the seed's starting point."""
    t = traffic_gen.Traffic(chat.traffic, 5, rate=4.0)
    n = traffic_gen.BLOCK
    reqs = [t.next(i) for i in range(3 * n)]
    p = np.array([r.prompt_len for r in reqs], float)
    o = np.array([r.max_new for r in reqs], float)
    assert abs(np.corrcoef(p[:n], o[:n])[0, 1]) < 0.1
    for a in range(0, 2 * n, 7):
        assert p[a:a + 64].mean() == pytest.approx(p[:n].mean(), rel=0.05)
        assert o[a:a + 64].mean() == pytest.approx(o[:n].mean(), rel=0.05)


def test_seeds_reorder_the_same_block_of_sizes(chat):
    a = traffic_gen.Traffic(chat.traffic, 1, rate=4.0)
    b = traffic_gen.Traffic(chat.traffic, 2, rate=4.0)
    n = traffic_gen.BLOCK
    sa = [(r.prompt_len, r.max_new) for r in map(a.next, range(n))]
    sb = [(r.prompt_len, r.max_new) for r in map(b.next, range(n))]
    assert sa != sb
    assert sorted(sa) == sorted(sb)
    # every seed meets the same arrivals; a block spans n / rate seconds
    assert [a.next(i).due for i in range(2 * n)] == [
        b.next(i).due for i in range(2 * n)]
    assert a.next(n).due == pytest.approx(n / 4.0, rel=1e-12)


@pytest.mark.parametrize("cell", ["qwen3-8b-4L.chat",
                                  "mistral-nemo-12b-4L.reason"])
def test_lengths_snap_and_clip(cell):
    mix = spec.load_cell(cell).traffic
    prompts = traffic_gen.prompt_block(mix)
    outs = traffic_gen.output_block(mix)
    assert set(prompts) <= set(mix["prompt"]["snap"])
    assert len(set(prompts)) >= len(mix["prompt"]["snap"]) - 1
    assert outs.min() >= mix["output"]["min"]
    assert outs.max() <= mix["output"]["max"]
    assert np.median(outs) == pytest.approx(mix["output"]["median"], rel=0.05)


def test_snap_picks_nearest_and_ties_low():
    pts = [128, 192, 256]
    assert traffic_gen.snap(10, pts) == 128
    assert traffic_gen.snap(160, pts) == 128      # tie between 128, 192
    assert traffic_gen.snap(161, pts) == 192
    assert traffic_gen.snap(9999, pts) == 256


def test_gamma_gaps_have_the_mix_cv_and_exact_mean(chat):
    g = traffic_gen.gap_block(chat.traffic, rate=5.0)
    assert g.mean() == pytest.approx(0.2, rel=1e-12)
    cv = statistics.pstdev(g) / g.mean()
    assert 1.5 < cv < 2.5, cv


def test_first_budgets_follow_residual_life(reason):
    """The closed loop starts in its steady state: each client's request
    in progress has a length-biased answer, a uniform share of it served
    (prefilled as context), and the rest as its budget."""
    mix = reason.traffic
    n_ctx = reason.max_prompt + reason.max_output
    start = traffic_gen.Traffic(mix, 7).steady_start(n_ctx)
    assert len(start) == mix["clients"]
    ctx = np.array([c for c, _ in start], float)
    budget = np.array([b for _, b in start], float)
    assert set(ctx) <= set(mix["start"]["snap"])
    assert budget.min() >= 1 and (ctx + budget).max() <= n_ctx
    # length-biased times a uniform share: served and left both average
    # E[L^2] / (2 E[L]); the context adds the prompt's mean
    outs = traffic_gen.output_block(mix).astype(float)
    half = (outs ** 2).mean() / (2 * outs.mean())
    prompt = traffic_gen.prompt_block(mix).mean()
    assert budget.mean() == pytest.approx(half, rel=0.25)
    assert ctx.mean() == pytest.approx(prompt + half, rel=0.15)
    assert ctx.max() >= 3 * reason.max_prompt
    assert sorted(start) == sorted(traffic_gen.Traffic(mix, 8).steady_start(
        n_ctx))


def _runner(traffic, seconds=0.3):
    cell = tiny_cell(traffic=traffic)
    r = serve_loop.Runner(cell, 3, seconds, False, time.perf_counter())
    r.engine = FakeEngine(slots=cell.params["slots"])
    r._instrument(r.engine)
    return r


def test_closed_loop_refills_each_completion():
    mix = dict(TINY_CHAT, loop="closed", clients=3, warmup_s=0.05,
               start={"snap": [16, 40, 56]})
    mix.pop("arrivals")
    r = _runner(mix)
    r.warm_up()
    rec = r.window()
    eng = r.engine
    live = len(eng.queue) + sum(s is not None for s in eng.slots)
    assert live == 3                     # one request per client, always
    done = sum(1 for q in rec.requests if q.finish_t is not None)
    assert len(rec.requests) == 3 + done
    # warm-up requests of every prompt and start shape came first, then
    # each client's request in progress, prefilled with its context
    assert [p for _, p, _ in eng.submitted[:5]] == [16, 32, 40, 48, 56]
    first = eng.submitted[5:8]
    assert [(p, m) for _, p, m in first] == r.traffic.steady_start(
        eng.n_ctx)
    assert all(p + m <= eng.n_ctx for _, p, m in first)


def test_open_loop_submits_on_schedule_and_times_tokens():
    r = _runner(dict(TINY_CHAT, warmup_s=0.05))
    r.warm_up()
    rec = r.window()
    subs = [q for q in rec.requests]
    assert subs and all(q.submit_t >= q.due for q in subs)
    for q in subs:
        assert q.times == sorted(q.times)
        if q.finish_t is not None:
            assert len(q.times) == q.max_new
            assert q.times[0] <= rec.steps[q.admit_step].t1
    assert rec.compiles_in_window == 0
