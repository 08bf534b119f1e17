"""Fault injection + graceful degradation (repro.reliability;
docs/reliability.md).

Unit coverage for the deterministic fault registry, the circuit
breaker's persistent quarantine, the step watchdog, and the engine's
hardening (admission requeue, deadlines, preemption budget, drain,
bounded stall) — plus the chaos acceptance suite: for every fault
class the engine completes the ragged workload with tokens
bit-identical to the fault-free run (f32, stitch off), the breaker
quarantines the failing fingerprint, and a relaunch replays from cache
without touching the quarantined entry.
"""
import glob
import json
import os
import threading
import time

import jax
import numpy as np
import pytest

from repro.configs import get_config
from repro.core import api, planner, schedule_cache
from repro.core.perf_model import V5E
from repro.models.lm import LM, Runtime
from repro.reliability import breaker, chaos, faults, sentinels
from repro.reliability.faults import InjectedFault
from repro.reliability.watchdog import StepWatchdog
from repro.serving.engine import ServingEngine

CFG = get_config("qwen3_8b", smoke=True)


@pytest.fixture(autouse=True)
def _hermetic(tmp_path, monkeypatch):
    """Every test gets an empty cache dir and clean registry/breaker/
    sentinel state — chaos runs must never leak quarantine records
    into each other (or into the rest of the suite)."""
    monkeypatch.setenv("REPRO_CACHE_DIR", str(tmp_path))
    faults.clear()
    breaker.reset()
    sentinels.disable()
    planner.clear_memo()
    api.clear_cache()
    yield tmp_path
    faults.clear()
    breaker.reset()
    sentinels.disable()
    planner.clear_memo()
    api.clear_cache()


@pytest.fixture(scope="module")
def _model():
    model = LM(CFG)
    return model, model.init_params(jax.random.PRNGKey(0))


ENG_KW = dict(max_batch=2, page_size=4, n_pages=16, max_pages_per_seq=4,
              choose_regime=False)


# ---------------------------------------------------------------------------
# fault registry
# ---------------------------------------------------------------------------

def test_registry_is_deterministic():
    def pattern(seed):
        faults.inject("engine_step", rate=0.3, seed=seed)
        out = [faults.check("engine_step") for _ in range(50)]
        faults.clear("engine_step")
        return out

    a, b = pattern(7), pattern(7)
    assert a == b                      # same seed -> same firing
    assert any(a) and not all(a)       # rate actually thins
    assert pattern(8) != a             # seed is live


def test_nth_fires_exactly_once():
    spec = faults.inject("page_exhaustion", nth=2)
    assert [faults.check("page_exhaustion") for _ in range(6)] \
        == [False, False, True, False, False, False]
    assert spec.n_fired == 1 and spec.n_seen == 6


def test_trigger_and_context():
    faults.inject("cache_corrupt",
                  trigger=lambda ctx: "bad" in ctx.get("path", ""))
    assert not faults.check("cache_corrupt", path="/ok.json")
    assert faults.check("cache_corrupt", path="/bad.json")
    with pytest.raises(InjectedFault) as ei:
        faults.fault_point("cache_corrupt", path="really bad")
    assert ei.value.kind == "cache_corrupt"


def test_unknown_kind_rejected():
    with pytest.raises(ValueError):
        faults.inject("disk_on_fire")
    assert not faults.check("engine_step")  # nothing armed: free


# ---------------------------------------------------------------------------
# circuit breaker + persistent quarantine
# ---------------------------------------------------------------------------

def test_breaker_opens_and_survives_relaunch():
    key = ("attn", 128, 128, 64, 64, 4, 1, "float32", True, 0)
    assert not breaker.is_open(key)
    assert breaker.record_failure(key, reason="lowering failed")
    assert breaker.is_open(key)
    # "relaunch": a fresh in-process breaker sees the disk denylist
    fresh = breaker.CircuitBreaker()
    assert fresh.is_open(key)
    rec = schedule_cache.is_quarantined(key, V5E)
    assert rec is not None and "lowering failed" in rec["reason"]
    # operator override lifts it
    assert schedule_cache.clear_quarantine(key, V5E)
    assert not breaker.CircuitBreaker().is_open(key)


def test_quarantine_is_not_deletion(tmp_path):
    """The denylist record leaves the cached entry readable — skipping
    happens at dispatch, so lifting the quarantine costs no retune."""
    tk = api.fuse_gemm_chain(512, 512, 128, 128, dtype="bfloat16")
    key = ("plan-ish", "whatever")
    schedule_cache.quarantine(key, V5E, reason="x")
    assert schedule_cache.is_quarantined(key, V5E) is not None
    api.clear_cache()
    warm = api.fuse_gemm_chain(512, 512, 128, 128, dtype="bfloat16")
    assert warm.source == "disk"     # entry untouched by the denylist
    assert tk.report.best.key() == warm.report.best.key()
    assert len(schedule_cache.list_quarantined()) == 1


def test_guarded_kernel_tail_degrades_to_ref():
    """ops-level tier: an injected dispatch fault on the fused MLP tail
    returns the XLA twin's exact output and opens the breaker; the next
    call routes straight to the twin without the fault armed."""
    rng = np.random.RandomState(0)
    x = rng.randn(32, 16).astype(np.float32)
    wu = rng.randn(16, 32).astype(np.float32)
    wd = rng.randn(32, 16).astype(np.float32)
    from repro.kernels import ops
    want = np.asarray(ops.mlp_chain(x, wu, wd, mode="ref"))
    with faults.injected("kernel_dispatch", nth=0):
        got = np.asarray(ops.mlp_chain(x, wu, wd, mode="interpret"))
    np.testing.assert_array_equal(got, want)  # fallback IS the twin
    fp = ("mlp", 32, 32, 16, "float32", False, "silu")
    assert breaker.is_open(fp)
    again = np.asarray(ops.mlp_chain(x, wu, wd, mode="interpret"))
    np.testing.assert_array_equal(again, want)


def test_watchdog_counts_breaches():
    wd = StepWatchdog(budget_s=0.0)
    with wd.watch("s1"):
        pass
    assert wd.breaches == 1 and wd.max_step_s > 0.0
    calm = StepWatchdog()          # no budget: observe only
    with calm.watch("s1"):
        pass
    assert calm.breaches == 0 and calm.n_steps == 1


# ---------------------------------------------------------------------------
# engine hardening
# ---------------------------------------------------------------------------

def test_admission_requeues_on_alloc_failure(_model):
    model, params = _model
    eng = ServingEngine(model, params, **ENG_KW)
    prompt = np.arange(5, dtype=np.int32) % CFG.vocab
    eng.submit(prompt, 3)
    with faults.injected("page_exhaustion", nth=0):
        eng.step()                 # admission alloc denied -> requeue
    assert eng.stats["admit_requeues"] == 1
    assert len(eng.queue) == 1 and eng.pool.n_free == eng.pool.n_pages - 1
    while eng.queue or any(s is not None for s in eng.slots):
        eng.step()                 # fault disarmed: admits and finishes
    (res,) = eng.finished
    assert res.outcome == "complete" and len(res.tokens) == 3


def test_deadline_evicts_running_request(_model):
    model, params = _model
    eng = ServingEngine(model, params, **ENG_KW)
    prompt = np.arange(4, dtype=np.int32) % CFG.vocab
    eng.submit(prompt, 10, deadline_steps=3)
    results, stats = eng.run([])
    (res,) = results
    assert res.outcome == "deadline"
    assert 0 < len(res.tokens) < 10    # honest partial tokens
    assert stats["deadline_evictions"] == 1
    assert eng.pool.n_free == eng.pool.n_pages - 1


def test_deadline_evicts_queued_request(_model):
    model, params = _model
    eng = ServingEngine(model, params, max_batch=1, page_size=4,
                        n_pages=16, max_pages_per_seq=4,
                        choose_regime=False)
    p = np.arange(4, dtype=np.int32) % CFG.vocab
    eng.submit(p, 8)                        # hogs the only slot
    eng.submit(p, 8, deadline_steps=2)      # starves in the queue
    results, stats = eng.run([])
    by_rid = {r.rid: r for r in results}
    assert by_rid[0].outcome == "complete" and len(by_rid[0].tokens) == 8
    assert by_rid[1].outcome == "deadline" and by_rid[1].tokens == []
    assert stats["deadline_evictions"] == 1


def test_preemption_budget_fails_honestly(_model):
    model, params = _model
    eng = ServingEngine(model, params, max_preemptions=0, **ENG_KW)
    prompt = np.arange(4, dtype=np.int32) % CFG.vocab
    eng.submit(prompt, 10)
    eng.step()
    idx = next(i for i, s in enumerate(eng.slots) if s is not None)
    eng._preempt(idx)              # budget 0: fails instead of requeue
    (res,) = eng.finished
    assert res.outcome == "preempt_budget" and res.n_preempted == 1
    assert len(res.tokens) >= 1    # partial output reported
    assert eng.stats["preempt_failures"] == 1
    assert not eng.queue and eng.pool.n_free == eng.pool.n_pages - 1


def test_drain_finishes_in_flight_and_fails_queued(_model):
    model, params = _model
    eng = ServingEngine(model, params, max_batch=1, page_size=4,
                        n_pages=16, max_pages_per_seq=4,
                        choose_regime=False)
    p = np.arange(4, dtype=np.int32) % CFG.vocab
    eng.submit(p, 6)
    eng.submit(p, 6)
    eng.step()                     # rid 0 in flight, rid 1 queued
    drained = eng.drain()
    by_rid = {r.rid: r for r in drained}
    assert by_rid[0].outcome == "complete" and len(by_rid[0].tokens) == 6
    assert by_rid[1].outcome == "drained" and by_rid[1].tokens == []
    assert eng.stats["drained"] == 1
    assert eng.pool.n_free == eng.pool.n_pages - 1
    # drain is idempotent and the engine stays usable
    assert eng.drain() == []
    eng.submit(p, 2)
    results, _ = eng.run([])
    assert results[-1].outcome == "complete"


def test_drain_deadline_zero_evicts_in_flight(_model):
    model, params = _model
    eng = ServingEngine(model, params, **ENG_KW)
    p = np.arange(4, dtype=np.int32) % CFG.vocab
    eng.submit(p, 10)
    eng.step()
    drained = eng.drain(deadline=0.0)
    (res,) = drained
    assert res.outcome == "drained" and 1 <= len(res.tokens) < 10
    assert eng.pool.n_free == eng.pool.n_pages - 1


def test_reset_in_flight_warns_and_drains(_model):
    model, params = _model
    eng = ServingEngine(model, params, **ENG_KW)
    p = np.arange(4, dtype=np.int32) % CFG.vocab
    eng.submit(p, 10)
    eng.step()
    with pytest.warns(DeprecationWarning, match="drain"):
        eng.reset()                # formerly: RuntimeError
    assert eng.finished == [] and eng.step_no == 0
    assert all(v == 0 for v in eng.stats.values())
    assert eng.pool.n_free == eng.pool.n_pages - 1


def test_stall_is_bounded_not_instant(_model):
    """Persistent allocation failure raises only after stall_limit
    consecutive barren steps — transient faults recover, genuine
    geometry stalls still surface instead of livelocking."""
    model, params = _model
    eng = ServingEngine(model, params, stall_limit=3, **ENG_KW)
    eng.submit(np.arange(4, dtype=np.int32) % CFG.vocab, 2)
    with faults.injected("page_exhaustion"):     # always fires
        for _ in range(3):
            eng.step()             # barren but tolerated
        with pytest.raises(RuntimeError, match="stalled"):
            eng.step()
    while eng.queue or any(s is not None for s in eng.slots):
        eng.step()                 # disarmed: recovers the same engine
    assert eng.finished and eng.finished[0].outcome == "complete"


def test_tier_chain_reaches_eager_twin(_model):
    """Two stacked dispatch failures demote configured -> xla-twin ->
    eager-twin; tokens match the healthy run bit-for-bit."""
    model, params = _model
    reqs = [(np.arange(5, dtype=np.int32) % CFG.vocab, 4)]
    base, _ = ServingEngine(model, params, **ENG_KW).run(list(reqs))
    eng = ServingEngine(model, params, **ENG_KW)
    with faults.injected("kernel_dispatch", nth=0):
        with faults.injected("engine_step", nth=0):
            results, stats = eng.run(list(reqs))
    assert stats["exec_tier"] == "eager-twin"
    assert stats["tier_demotions"] == 2
    assert [r.tokens for r in results] == [r.tokens for r in base]


# ---------------------------------------------------------------------------
# chaos acceptance: one fault class at a time, tokens bit-identical
# ---------------------------------------------------------------------------

def test_chaos_kernel_dispatch_quarantines_and_replays():
    out = chaos.run_chaos("kernel_dispatch", {"nth": 0}, planner=True)
    assert out.fired == 1
    assert out.tokens_identical
    assert out.faulted_stats["tier_demotions"] == 1
    # the decode plan fingerprint is denylisted on disk ...
    dkey = planner.plan_key(CFG, 3, 1, False, phase="decode", paged=4,
                            kv_len=32)
    assert schedule_cache.is_quarantined(dkey, V5E) is not None
    # ... and the relaunch never touched it: healthy tier, no demotion,
    # no decode plan in the fresh memo (prefill plans replay fine)
    assert out.relaunch_stats["exec_tier"] == "configured"
    assert out.relaunch_stats["tier_demotions"] == 0
    assert all(k[8] != "decode" for k in planner._PLAN_MEMO)
    assert any(k[8] == "prefill" for k in planner._PLAN_MEMO)


def test_chaos_cache_corruption_quarantines_file(tmp_path):
    out = chaos.run_chaos("cache_corrupt", {"nth": 0},
                          choose_regime=True)
    assert out.fired == 1
    assert out.tokens_identical
    corrupt = glob.glob(str(tmp_path / "*.corrupt"))
    assert len(corrupt) == 1       # evidence preserved, not deleted
    # the retuned replacement landed at the original path and the
    # relaunch replayed it without another quarantine
    assert out.relaunch_stats["tier_demotions"] == 0


def test_chaos_plan_load_quarantines_record(tmp_path):
    out = chaos.run_chaos("plan_load", {"nth": 0}, planner=True)
    assert out.fired == 1
    assert out.tokens_identical
    assert len(glob.glob(str(tmp_path / "*.corrupt"))) == 1
    assert out.relaunch_stats["tier_demotions"] == 0


def test_chaos_page_exhaustion_backs_off():
    out = chaos.run_chaos("page_exhaustion", {"nth": 2})
    assert out.fired == 1
    assert out.tokens_identical
    assert (out.faulted_stats["admit_requeues"]
            + out.faulted_stats["preemptions"]) >= 1


# ---------------------------------------------------------------------------
# schedule-cache hardening details the chaos suite leans on
# ---------------------------------------------------------------------------

def test_corrupt_plan_quarantined_to_corrupt_file(tmp_path):
    key = planner.plan_key(CFG, 2, 64, True)
    schedule_cache.store_plan(key, V5E, {"version": 1})
    path = schedule_cache.plan_entry_path(key, V5E)
    path.write_text('{"schema": 2, "trunc')
    assert schedule_cache.load_plan(key, V5E) is None
    assert not path.exists()
    evidence = path.with_name(path.name + ".corrupt")
    assert evidence.exists()
    assert evidence.read_text().startswith('{"schema": 2, "trunc')


def test_mangled_plan_payload_quarantined_and_recarved(tmp_path):
    """A plan record that parses as JSON but whose payload is mangled
    is quarantined by plan_model (not silently re-carved forever) and
    a fresh record lands at the original path."""
    plan = planner.plan_model(CFG, 2, 16, stitch=False)
    key = planner.plan_key(CFG, 2, 16, False)
    path = schedule_cache.plan_entry_path(key, V5E)
    rec = json.loads(path.read_text())
    rec["plan"] = {"version": planner.PLANNER_VERSION}  # fields gone
    path.write_text(json.dumps(rec))

    planner.clear_memo()
    replanned = planner.plan_model(CFG, 2, 16, stitch=False)
    assert replanned == plan               # deterministic re-carve
    evidence = path.with_name(path.name + ".corrupt")
    assert evidence.exists()               # mangled bytes preserved
    assert path.exists()                   # fresh record, same path
    planner.clear_memo()
    assert planner.plan_model(CFG, 2, 16, stitch=False) == plan


def test_stale_schema_is_not_quarantined(tmp_path, monkeypatch):
    """A valid record from an older schema is a miss, not corruption —
    it must stay in place, not be renamed to *.corrupt."""
    key = planner.plan_key(CFG, 2, 64, True)
    schedule_cache.store_plan(key, V5E, {"version": 1})
    path = schedule_cache.plan_entry_path(key, V5E)
    rec = json.loads(path.read_text())
    rec["schema"] = schedule_cache.SCHEMA_VERSION - 1
    path.write_text(json.dumps(rec))
    assert schedule_cache.load_plan(key, V5E) is None
    assert path.exists()
    assert not glob.glob(str(tmp_path / "*.corrupt"))


def test_concurrent_plan_writers_race_same_key(tmp_path):
    """N threads hammering store_plan on one key: the surviving record
    is one complete payload (atomic replace + advisory lock), never a
    torn mix, and no temp files leak."""
    key = planner.plan_key(CFG, 4, 128, True)
    n = 8
    barrier = threading.Barrier(n)

    def write(i):
        barrier.wait()
        for _ in range(10):
            schedule_cache.store_plan(key, V5E,
                                      {"version": 1, "writer": i,
                                       "pad": "x" * (1000 + i)})

    threads = [threading.Thread(target=write, args=(i,))
               for i in range(n)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    rec = schedule_cache.load_plan(key, V5E)
    assert rec is not None and rec["version"] == 1
    w = rec["writer"]
    assert rec["pad"] == "x" * (1000 + w)    # payload internally whole
    assert not list(tmp_path.glob("*.tmp"))
    assert not glob.glob(str(tmp_path / "*.corrupt"))


# ---------------------------------------------------------------------------
# correctness sentinels: shadow verification, golden probes, health
# ---------------------------------------------------------------------------

def test_shadow_sampler_is_deterministic():
    def pattern(seed, rate=0.25, n=200):
        spec = sentinels.SentinelSpec(rate=rate, seed=seed)
        return [spec.sample() for _ in range(n)]

    a, b = pattern(3), pattern(3)
    assert a == b                      # same seed -> same ordinals
    assert any(a) and not all(a)       # rate actually thins
    assert pattern(4) != a             # seed is live
    assert 20 <= sum(a) <= 80          # ~rate * n, deterministic
    assert all(sentinels.SentinelSpec(rate=1.0).sample()
               for _ in range(10))
    assert not any(sentinels.SentinelSpec(rate=0.0).sample()
                   for _ in range(10))
    with pytest.raises(ValueError):
        sentinels.enable(rate=1.5)
    assert sentinels.active() is None  # failed enable arms nothing


def test_shadow_catches_wrong_answer_at_kernel_seam():
    """wrong_answer perturbs the fused MLP output without raising; the
    armed shadow sampler re-runs the XLA twin, serves ITS values on the
    detecting call, and quarantines the fingerprint on disk."""
    rng = np.random.RandomState(0)
    x = rng.randn(32, 16).astype(np.float32)
    wu = rng.randn(16, 32).astype(np.float32)
    wd = rng.randn(32, 16).astype(np.float32)
    from repro.kernels import ops
    want = np.asarray(ops.mlp_chain(x, wu, wd, mode="ref"))
    fp = ("mlp", 32, 32, 16, "float32", False, "silu")
    with sentinels.shadowing(1.0) as sp:
        with faults.injected("wrong_answer", rate=1.0) as spec:
            got = np.asarray(ops.mlp_chain(x, wu, wd, mode="interpret"))
        assert spec.n_fired >= 1
    np.testing.assert_array_equal(got, want)   # twin's output served
    assert sp.n_checked == 1 and sp.n_mismatched == 1
    assert breaker.is_open(fp)
    assert schedule_cache.is_quarantined(fp, V5E) is not None
    # without the sentinels armed the corruption would have sailed
    # through: the crash path sees no exception (lift the quarantine
    # first — an open breaker routes to the twin and would mask it)
    faults.clear()
    schedule_cache.clear_quarantine(fp, V5E)
    breaker.reset()
    with faults.injected("wrong_answer", rate=1.0):
        silent = np.asarray(ops.mlp_chain(x, wu, wd, mode="interpret"))
    assert not np.array_equal(silent, want)


def test_sentinels_no_fault_bit_identical(_model):
    """Sentinels armed at rate 1.0 with no fault: every engine dispatch
    shadow-verified, zero mismatches, and the served tokens are
    bit-identical to a sentinel-free run."""
    model, params = _model
    p = np.arange(5, dtype=np.int32) % CFG.vocab
    reqs = [(p, 4), (np.arange(7, dtype=np.int32) % CFG.vocab, 6)]
    base, _ = ServingEngine(model, params, **ENG_KW).run(list(reqs))
    with sentinels.shadowing(1.0):
        eng = ServingEngine(model, params, **ENG_KW)
        res, stats = eng.run(list(reqs))
    assert [r.tokens for r in res] == [r.tokens for r in base]
    assert stats["golden_probes"] == 1
    assert stats["golden_mismatches"] == 0
    assert stats["shadow_checks"] > 0
    assert stats["shadow_mismatches"] == 0
    assert stats["exec_tier"] == "configured"


def test_golden_probe_demotes_before_traffic(_model):
    """A wrong answer on the construction probe's canned dispatch means
    the engine never serves a token from the bad tier: demoted to the
    XLA twin before the first request, tokens identical."""
    model, params = _model
    p = np.arange(5, dtype=np.int32) % CFG.vocab
    base, _ = ServingEngine(model, params, **ENG_KW).run([(p, 4)])
    with sentinels.shadowing(0.0, probe=True):
        with faults.injected(
                "wrong_answer",
                trigger=lambda ctx: ctx.get("op") == "engine-golden"):
            eng = ServingEngine(model, params, **ENG_KW)
    assert eng.exec_tier == 1
    assert eng.stats["golden_probes"] == 1
    assert eng.stats["golden_mismatches"] == 1
    assert eng.stats["tier_demotions"] == 1
    res, _ = eng.run([(p, 4)])
    assert [r.tokens for r in res] == [r.tokens for r in base]


def test_health_monitor_evicts_nan_decode_slot(_model):
    import jax.numpy as jnp
    _, params = _model
    model = LM(CFG, Runtime(sentinels=True))
    eng = ServingEngine(model, params, **ENG_KW)
    p = np.arange(5, dtype=np.int32) % CFG.vocab
    eng.submit(p, 6)
    eng.step()                         # healthy admit + first decode
    orig = eng._decode

    def poisoned(*args):
        logits, cache = orig(*args)
        return jnp.full_like(logits, jnp.nan), cache

    eng._decode = poisoned
    eng.step()
    (res,) = eng.finished
    assert res.outcome == "health"
    assert 1 <= len(res.tokens) < 6    # honest partial tokens
    assert eng.stats["health_evictions"] == 1
    assert eng.pool.n_free == eng.pool.n_pages - 1
    eng._decode = orig                 # engine stays serviceable
    res2, _ = eng.run([(p, 2)])
    assert res2[-1].outcome == "complete"


def test_health_monitor_rejects_inf_prefill(_model):
    _, params = _model
    model = LM(CFG, Runtime(sentinels=True))
    eng = ServingEngine(model, params, **ENG_KW)
    orig = eng._prefill

    def poisoned(*args):
        logits, cache = orig(*args)
        import jax.numpy as jnp
        return jnp.full_like(logits, jnp.inf), cache

    eng._prefill = poisoned
    eng.submit(np.arange(5, dtype=np.int32) % CFG.vocab, 4)
    eng.step()
    (res,) = eng.finished
    assert res.outcome == "health" and res.tokens == []
    assert eng.stats["health_evictions"] == 1
    assert all(s is None for s in eng.slots)
    assert eng.pool.n_free == eng.pool.n_pages - 1


def test_healthy_flags_nan_inf_and_explosion():
    import jax.numpy as jnp
    rows = jnp.stack([
        jnp.array([0.5, -1.0, 2.0]),           # fine
        jnp.array([0.5, jnp.nan, 2.0]),        # NaN
        jnp.array([0.5, jnp.inf, 2.0]),        # Inf
        jnp.array([0.5, -1.0, 2e4]),           # exploded
    ])
    assert np.asarray(sentinels.healthy(rows)).tolist() == \
        [True, False, False, False]


# ---------------------------------------------------------------------------
# warm-load golden probes + schedule re-validation (core/api.py)
# ---------------------------------------------------------------------------

GEMM_ARGS = (256, 256, 128, 128)


def _gemm_record_path():
    from repro.core.perf_model import V5E as _hw
    key = ("gemm", *GEMM_ARGS, 1, "float32", _hw.name, 128, None, 0)
    return schedule_cache.entry_path(key, _hw)


def test_warm_load_probe_on_host_change(tmp_path):
    tk = api.fuse_gemm_chain(*GEMM_ARGS)
    path = _gemm_record_path()
    rec = json.loads(path.read_text())
    assert rec["host"] == schedule_cache.host_fingerprint()
    rec["host"] = "0" * 16             # pretend it tuned elsewhere
    path.write_text(json.dumps(rec))
    api.clear_cache()
    with sentinels.shadowing(0.0) as spec:
        warm = api.fuse_gemm_chain(*GEMM_ARGS)
    assert warm.source == "disk"       # probe passed, entry trusted
    assert spec.n_probed == 1 and spec.n_probe_mismatched == 0
    assert tk.report.best.key() == warm.report.best.key()
    # the record was re-stamped: the next load on this host skips the
    # probe entirely
    assert json.loads(path.read_text())["host"] == \
        schedule_cache.host_fingerprint()
    api.clear_cache()
    with sentinels.shadowing(0.0) as spec2:
        again = api.fuse_gemm_chain(*GEMM_ARGS)
    assert again.source == "disk" and spec2.n_probed == 0


def test_warm_load_probe_mismatch_quarantines_and_retunes(tmp_path):
    api.fuse_gemm_chain(*GEMM_ARGS)
    path = _gemm_record_path()
    rec = json.loads(path.read_text())
    rec["host"] = "0" * 16
    path.write_text(json.dumps(rec))
    api.clear_cache()
    with sentinels.shadowing(0.0) as spec:
        with faults.injected(
                "wrong_answer",
                trigger=lambda ctx: ctx.get("op") == "probe-gemm"):
            warm = api.fuse_gemm_chain(*GEMM_ARGS)
    assert spec.n_probed == 1 and spec.n_probe_mismatched == 1
    assert warm.source == "search"     # entry distrusted -> retune
    assert glob.glob(str(tmp_path / "*.corrupt"))  # evidence kept
    # the retuned record replays clean (current host, no probe due)
    api.clear_cache()
    assert api.fuse_gemm_chain(*GEMM_ARGS).source == "disk"


def test_warm_load_probe_not_due_without_sentinels(tmp_path):
    """Host changes alone never block serving: with the sentinels
    disarmed the warm load replays exactly as before this layer."""
    api.fuse_gemm_chain(*GEMM_ARGS)
    path = _gemm_record_path()
    rec = json.loads(path.read_text())
    rec["host"] = "0" * 16
    path.write_text(json.dumps(rec))
    api.clear_cache()
    warm = api.fuse_gemm_chain(*GEMM_ARGS)
    assert warm.source == "disk"
    assert json.loads(path.read_text())["host"] == "0" * 16


def test_warm_load_revalidates_pruning_rules(tmp_path):
    """A parseable record whose schedule violates Rule 3 (mangled tile
    consistent across tile_sizes and params, so the kwargs cross-check
    passes) is quarantined and retuned — never dispatched."""
    api.fuse_gemm_chain(*GEMM_ARGS)
    path = _gemm_record_path()
    rec = json.loads(path.read_text())
    rec["tile_sizes"]["m"] = 96        # 256/96: 12.5% padding waste
    rec["params"]["bm"] = 96
    path.write_text(json.dumps(rec))
    api.clear_cache()
    warm = api.fuse_gemm_chain(*GEMM_ARGS)
    assert warm.source == "search"
    assert glob.glob(str(tmp_path / "*.corrupt"))
    api.clear_cache()
    assert api.fuse_gemm_chain(*GEMM_ARGS).source == "disk"


# ---------------------------------------------------------------------------
# chaos acceptance: wrong_answer (silent corruption) end to end
# ---------------------------------------------------------------------------

def _decode_plan_key():
    return planner.plan_key(CFG, 3, 1, False, phase="decode", paged=4,
                            kv_len=32)


def test_chaos_wrong_answer_golden_probe_blocks_before_traffic():
    """Corruption armed on every sentinel seam: the construction probe
    catches it before the first request, the decode plan is
    quarantined on disk, every served token comes from the twin
    (bit-identical), and the relaunch replays clean at tier
    ``configured`` with zero demotions."""
    out = chaos.run_chaos("wrong_answer", {"rate": 1.0}, planner=True,
                          sentinel_rate=1.0)
    assert out.fired >= 1
    assert out.tokens_identical
    f, r = out.faulted_stats, out.relaunch_stats
    assert f["golden_probes"] == 1 and f["golden_mismatches"] == 1
    assert f["exec_tier"] == "xla-twin" and f["tier_demotions"] == 1
    from repro.core.perf_model import V5E as _hw
    assert schedule_cache.is_quarantined(_decode_plan_key(), _hw) \
        is not None
    assert r["exec_tier"] == "configured"
    assert r["tier_demotions"] == 0 and r["golden_mismatches"] == 0


def test_chaos_wrong_answer_shadow_detects_mid_traffic():
    """Corruption restricted to live decode dispatches (the golden
    probe's canned input stays clean): the shadow sampler detects on
    the first corrupted decode, the detecting call already serves the
    twin's output, and tokens stay bit-identical throughout."""
    out = chaos.run_chaos(
        "wrong_answer",
        {"trigger": lambda ctx: ctx.get("op") == "engine-decode"},
        planner=True, sentinel_rate=1.0)
    assert out.fired >= 1
    assert out.tokens_identical
    f, r = out.faulted_stats, out.relaunch_stats
    assert f["golden_mismatches"] == 0      # probe input was clean
    assert f["shadow_mismatches"] == 1      # first decode detected
    assert f["exec_tier"] == "xla-twin" and f["tier_demotions"] == 1
    from repro.core.perf_model import V5E as _hw
    assert schedule_cache.is_quarantined(_decode_plan_key(), _hw) \
        is not None
    assert r["exec_tier"] == "configured"
    assert r["tier_demotions"] == 0 and r["shadow_mismatches"] == 0


# ---------------------------------------------------------------------------
# PR 8 leftovers: watchdog under a slow step, quarantine round-trip
# ---------------------------------------------------------------------------

def test_watchdog_counts_slow_injected_step(_model):
    """A deliberately slow (not failing) injected step breaches the
    watchdog budget without killing the request."""
    model, params = _model
    eng = ServingEngine(model, params, watchdog_s=0.01, **ENG_KW)
    eng.submit(np.arange(4, dtype=np.int32) % CFG.vocab, 2)
    with faults.injected(
            "engine_step",
            trigger=lambda ctx: time.sleep(0.05) or False):
        eng.step()
    assert eng.watchdog.breaches >= 1
    while eng.queue or any(s is not None for s in eng.slots):
        eng.step()
    assert eng.finished[0].outcome == "complete"
    assert eng.stats["tier_demotions"] == 0   # slow is not broken


def test_clear_quarantine_reenables_decode_preplan(_model):
    """Operator round-trip: engine construction plans nothing, and with
    the decode plan fingerprint quarantined the first decode step runs
    the hand-wired layer instead of planning it; clear_quarantine + a
    breaker reset plans it again at the next relaunch's first decode
    step."""
    _, params = _model
    from repro.core.perf_model import V5E as _hw
    dkey = planner.plan_key(CFG, 2, 1, False, phase="decode", paged=4,
                            kv_len=16)
    breaker.record_failure(dkey, reason="operator test")

    def relaunch():
        # a fresh model per relaunch, so no trace is reused
        planned = LM(CFG, Runtime(planner=True, stitch=False))
        eng = ServingEngine(planned, params, **ENG_KW)
        assert not planner._PLAN_MEMO
        eng.submit(np.arange(4, dtype=np.int32) % CFG.vocab, 2)
        while eng.stats["decode_steps"] == 0:
            eng.step()

    relaunch()
    assert all(k[8] != "decode" for k in planner._PLAN_MEMO)
    assert any(k[8] == "prefill" for k in planner._PLAN_MEMO)
    assert schedule_cache.clear_quarantine(dkey, _hw)
    breaker.reset()                    # relaunch: fresh memoization
    planner.clear_memo()
    relaunch()
    assert dkey in planner._PLAN_MEMO