"""Graph-level fusion planner (core/planner.py): differential harness
against the hand-wired layers, carve/stitch property tests, and the
stitched kernel hooks.

The load-bearing claim: ``Runtime(planner=True)`` executes every
plannable config end-to-end from planner output alone — zero
hand-specified chains — and is *bit-identical* to the hand-wired path
when stitching is disabled, tolerance-bounded when stitching fuses
glue wide (f32) into carved units.  Property tests pin the carve
invariants (partition of the op DAG, MBCI predicate on fused chains,
determinism) over random shapes via hypothesis (conftest.py installs a
deterministic stand-in when the real library is absent).
"""
import dataclasses
import json
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings
from hypothesis import strategies as st

from repro.configs import ARCHS, get_config
from repro.core import planner
from repro.core.perf_model import MeshSpec, V5E
from repro.launch import steps as S
from repro.models.lm import Runtime

BATCH, SEQ = 2, 64

PLANNABLE = [a for a in ARCHS
             if planner.plannable(get_config(a, smoke=True))]


@pytest.fixture(autouse=True)
def _fresh_memo():
    planner.clear_memo()
    yield
    planner.clear_memo()


def test_plannable_set():
    """Every dense attention-only arch plans; moe/ssm/rglru/encdec
    fall back (Runtime(planner=True) must not change them)."""
    assert sorted(PLANNABLE) == ["codeqwen15_7b", "granite_20b",
                                 "granite_34b", "pixtral_12b",
                                 "qwen3_8b"]
    for arch in ARCHS:
        if arch not in PLANNABLE:
            with pytest.raises(ValueError):
                planner.plan_model(get_config(arch, smoke=True),
                                   BATCH, SEQ)


# ---------------------------------------------------------------------------
# Differential harness: hand-wired vs planner-driven forward
# ---------------------------------------------------------------------------

def _forward(cfg, rt, params, toks, prefix):
    model = S.build_model(cfg, rt)
    return jax.jit(model.forward)(params, toks, prefix)


def _inputs(cfg):
    toks = jax.random.randint(jax.random.PRNGKey(1), (BATCH, SEQ), 0,
                              cfg.vocab)
    prefix = None
    if cfg.n_prefix_embeds:
        prefix = jax.random.normal(
            jax.random.PRNGKey(2), (BATCH, cfg.n_prefix_embeds,
                                    cfg.d_model))
    return toks, prefix


@pytest.mark.parametrize("arch", PLANNABLE)
def test_planner_bit_identical_stitch_disabled(arch):
    """Stitching off: the planner path must run the exact jnp program
    the hand-wired layers run — bit-for-bit equal logits."""
    cfg = get_config(arch, smoke=True)
    toks, prefix = _inputs(cfg)
    params = S.build_model(cfg, Runtime(remat=False)).init_params(
        jax.random.PRNGKey(0))
    hand = _forward(cfg, Runtime(remat=False), params, toks, prefix)
    planned = _forward(cfg, Runtime(remat=False, planner=True,
                                    stitch=False), params, toks, prefix)
    assert np.array_equal(np.asarray(hand), np.asarray(planned))


@pytest.mark.parametrize("arch", PLANNABLE)
def test_planner_stitched_within_tolerance(arch):
    """Stitching on: glue fused into carved units computes wide (f32)
    with one boundary downcast — tolerance-bounded vs hand-wired, and
    still bitwise on these float32 smoke configs (the downcast is a
    no-op there, which this asserts as the stronger property)."""
    cfg = get_config(arch, smoke=True)
    toks, prefix = _inputs(cfg)
    params = S.build_model(cfg, Runtime(remat=False)).init_params(
        jax.random.PRNGKey(0))
    hand = _forward(cfg, Runtime(remat=False), params, toks, prefix)
    stitched = _forward(cfg, Runtime(remat=False, planner=True,
                                     stitch=True), params, toks, prefix)
    np.testing.assert_allclose(np.asarray(stitched), np.asarray(hand),
                               rtol=1e-5, atol=1e-5)
    if cfg.dtype == "float32":
        assert np.array_equal(np.asarray(hand), np.asarray(stitched))


@pytest.mark.parametrize("stitch", [False, True])
def test_planner_paged_windowed_steps(stitch):
    """Paged prefill and decode steps under a sliding window shorter
    than the prompt, so decode crosses the window's edge: the planner
    walk against the hand-wired blocks — bit-for-bit with stitching
    off, within the stitched tolerance with it on (and bitwise on this
    float32 config)."""
    cfg = dataclasses.replace(get_config("qwen3_8b", smoke=True), window=6)
    params = S.build_model(cfg, Runtime(remat=False)).init_params(
        jax.random.PRNGKey(0))
    toks = jax.random.randint(jax.random.PRNGKey(1), (1, 16), 0, cfg.vocab)
    table = jnp.arange(1, 9, dtype=jnp.int32)[None]     # 8 pages of 4
    outs = []
    for rt in (Runtime(remat=False),
               Runtime(remat=False, planner=True, stitch=stitch)):
        model = S.build_model(cfg, rt)
        cache = model.init_paged_cache(10, 4)
        logits, cache = jax.jit(model.prefill_paged)(
            params, toks, cache, table, jnp.int32(13))
        steps = [logits]
        for i in range(3):
            logits, cache = jax.jit(model.decode_step_paged)(
                params, cache, toks[:, i], jnp.array([13 + i], jnp.int32),
                table)
            steps.append(logits)
        outs.append(np.asarray(jnp.stack(steps)))
    assert {k[8] for k in planner._PLAN_MEMO} == {"prefill", "decode"}
    hand, planned = outs
    np.testing.assert_allclose(planned, hand, rtol=1e-5, atol=1e-5)
    if not stitch or cfg.dtype == "float32":
        assert np.array_equal(hand, planned)


@pytest.mark.parametrize("smoke", [False, True])
@pytest.mark.parametrize("arch", ARCHS)
def test_attn_window_rule(arch, smoke):
    """One window rule for attention layers: a hybrid's local window,
    else ``window`` — and the two agree wherever the planner runs."""
    cfg = get_config(arch, smoke=smoke)
    assert cfg.attn_window == (cfg.rglru.local_window if cfg.rglru
                               else cfg.window)
    assert not planner.plannable(cfg) or cfg.attn_window == cfg.window


def test_planner_stitched_bf16_tolerance():
    """bf16 stitching genuinely moves rounding (wide glue, boundary
    downcast): not bitwise, but within bf16 resolution of hand-wired."""
    cfg = dataclasses.replace(get_config("qwen3_8b", smoke=True),
                              dtype="bfloat16")
    toks, prefix = _inputs(cfg)
    params = S.build_model(cfg, Runtime(remat=False)).init_params(
        jax.random.PRNGKey(0))
    hand = _forward(cfg, Runtime(remat=False), params, toks, prefix)
    stitched = _forward(cfg, Runtime(remat=False, planner=True,
                                     stitch=True), params, toks, prefix)
    h = np.asarray(hand, np.float32)
    st_ = np.asarray(stitched, np.float32)
    # bf16 has ~8 mantissa bits: on logits of scale ~5 each relocated
    # rounding contributes ~2^-8 * |x|, compounding across layers
    np.testing.assert_allclose(st_, h, rtol=5e-2, atol=1e-1)
    assert np.abs(st_ - h).mean() < 2e-2


def test_planner_cache_and_decode_fall_back():
    """planner=True must leave cached prefill/decode on the hand-wired
    path: decode through a planner Runtime matches the plain one."""
    cfg = get_config("qwen3_8b", smoke=True)
    toks, _ = _inputs(cfg)
    params = S.build_model(cfg, Runtime(remat=False)).init_params(
        jax.random.PRNGKey(0))
    for rt in (Runtime(remat=False),
               Runtime(remat=False, planner=True)):
        model = S.build_model(cfg, rt)
        cache = model.init_cache(BATCH, SEQ)
        out, _ = jax.jit(model.prefill)(params, toks, cache)
        if rt.planner:
            np.testing.assert_array_equal(np.asarray(out),
                                          np.asarray(ref_out))
        else:
            ref_out = out


@pytest.mark.slow
@pytest.mark.parametrize("arch", ["qwen3_8b", "granite_20b"])
def test_planner_full_config_differential(arch):
    """FULL (bf16, big dims) configs: planner forward stays within bf16
    tolerance of hand-wired with stitching enabled."""
    cfg = dataclasses.replace(get_config(arch), n_layers=2, vocab=1024)
    toks = jax.random.randint(jax.random.PRNGKey(1), (1, 512), 0,
                              cfg.vocab)
    params = S.build_model(cfg, Runtime(remat=False)).init_params(
        jax.random.PRNGKey(0))
    hand = _forward(cfg, Runtime(remat=False), params, toks, None)
    planned = _forward(cfg, Runtime(remat=False, planner=True),
                       params, toks, None)
    np.testing.assert_allclose(np.asarray(planned, np.float32),
                               np.asarray(hand, np.float32),
                               rtol=5e-2, atol=1e-1)


# ---------------------------------------------------------------------------
# Golden decisions (tests/golden_plans.json; replay is covered in
# test_schedule_cache.py)
# ---------------------------------------------------------------------------

def test_golden_fixture_current():
    """The committed fixture matches today's planner output — if a
    carve/stitch change is intentional, bump PLANNER_VERSION and
    regenerate the fixture (plan_to_json at its batch/seq)."""
    golden = json.loads(
        (Path(__file__).parent / "golden_plans.json").read_text())
    for name, payload in golden["plans"].items():
        plan = planner.plan_model(get_config(name), golden["batch"],
                                  golden["seq"], use_cache=False)
        assert planner.plan_to_json(plan) == payload, name
        assert payload["version"] == planner.PLANNER_VERSION


def test_golden_phase_plans_current():
    """Serving-phase fixtures (decode / prefill over a paged cache)
    match today's planner output byte-for-byte, including the phase /
    paged / kv_len identity the v2 fingerprint keys on."""
    golden = json.loads(
        (Path(__file__).parent / "golden_plans.json").read_text())
    assert golden["phase_plans"], "fixture must pin serving phases"
    seen = set()
    for entry in golden["phase_plans"]:
        cfg = get_config(entry["arch"], smoke=entry["smoke"])
        plan = planner.plan_model(
            cfg, entry["batch"], entry["seq"], stitch=entry["stitch"],
            phase=entry["phase"], paged=entry["paged"],
            kv_len=entry["kv_len"], use_cache=False)
        payload = planner.plan_to_json(plan)
        assert payload == entry["plan"], (entry["arch"], entry["phase"])
        assert payload["phase"] == entry["phase"]
        assert payload["paged"] == entry["paged"]
        assert payload["kv_len"] == entry["kv_len"]
        # the serving DAG's cache write is always standalone glue
        assert "kv_write" in entry["plan"]["layer"]["glue"]
        seen.add((entry["smoke"], entry["phase"]))
    assert seen == {(False, "decode"), (False, "prefill"),
                    (True, "decode"), (True, "prefill")}


def test_golden_qwen3_decisions():
    """Spot-check the load-bearing decisions the fixture pins: fused
    MBCI attention, split compute-bound FULL MLP, qk_norm+rope stitched
    onto the q/k projections, residuals stitched as epilogues."""
    golden = json.loads(
        (Path(__file__).parent / "golden_plans.json").read_text())
    chains = {tuple(c["ops"]): c
              for c in golden["plans"]["qwen3_8b"]["layer"]["chains"]}
    attn = chains[("qk", "softmax", "pv")]
    assert attn["fused"] and attn["ai"] < planner.ridge_intensity()
    assert ("w_gate", "w_up", "act_gate", "w_down") not in chains
    assert chains[("w_up",)]["ai"] > planner.ridge_intensity()
    assert chains[("wq",)]["epilogue"] == ["qk_norm_q", "rope_q"]
    assert chains[("wo",)]["epilogue"] == ["res1"]
    assert chains[("w_down",)]["epilogue"] == ["res2"]


# ---------------------------------------------------------------------------
# Property tests (hypothesis; conftest stub when unavailable)
# ---------------------------------------------------------------------------

_MESHES = [None,
           MeshSpec(axes=(("data", 2), ("model", 4)),
                    placement=(("h", "model"),), batch_axes=("data",))]


@settings(max_examples=20, deadline=None)
@given(arch=st.sampled_from(PLANNABLE),
       batch=st.integers(1, 4),
       seq=st.sampled_from([16, 64, 128, 512, 2048]),
       stitch=st.booleans(),
       smoke=st.booleans())
def test_property_chains_partition_dag(arch, batch, seq, stitch, smoke):
    """Carved chains + stitched glue + standalone glue partition the op
    DAG: every node executed exactly once, none lost, none duplicated."""
    cfg = get_config(arch, smoke=smoke)
    plan = planner.plan_model(cfg, batch, seq, stitch=stitch,
                              use_cache=False)
    covered = []
    for c in plan.layer.chains:
        covered += list(c.ops) + list(c.prologue) + list(c.epilogue)
    covered += list(plan.layer.glue)
    assert sorted(covered) == sorted(n.name for n in plan.layer.nodes)
    # dropped stitches stayed standalone, not vanished
    assert set(plan.layer.dropped) <= set(plan.layer.glue)


@settings(max_examples=20, deadline=None)
@given(arch=st.sampled_from(PLANNABLE),
       batch=st.integers(1, 4),
       seq=st.sampled_from([16, 64, 128, 512, 2048]),
       mesh_i=st.integers(0, len(_MESHES) - 1),
       smoke=st.booleans())
def test_property_fused_chains_are_mbci(arch, batch, seq, mesh_i, smoke):
    """Every chain the planner keeps fused passes the MBCI predicate —
    localized arithmetic intensity under the ridge point — and every
    multi-op template it split was compute-bound."""
    cfg = get_config(arch, smoke=smoke)
    plan = planner.plan_model(cfg, batch, seq, mesh=_MESHES[mesh_i],
                              use_cache=False)
    ridge = planner.ridge_intensity(V5E)
    for c in plan.layer.chains:
        if c.fused:
            assert len(c.ops) > 1
            assert c.ai < ridge, (c.kind, c.ai)


@settings(max_examples=10, deadline=None)
@given(arch=st.sampled_from(PLANNABLE),
       batch=st.integers(1, 4),
       seq=st.sampled_from([16, 64, 128, 512, 2048]),
       stitch=st.booleans())
def test_property_planning_deterministic(arch, batch, seq, stitch):
    """Fixed (config, shape, MeshSpec) -> identical plan, every time
    (plans are cached/replayed, so nondeterminism would poison disk)."""
    cfg = get_config(arch, smoke=True)
    a = planner.plan_model(cfg, batch, seq, stitch=stitch,
                           use_cache=False)
    b = planner.plan_model(cfg, batch, seq, stitch=stitch,
                           use_cache=False)
    assert a == b
    assert planner.plan_to_json(a) == planner.plan_to_json(b)


@settings(max_examples=20, deadline=None)
@given(arch=st.sampled_from(PLANNABLE),
       batch=st.integers(1, 4),
       phase=st.sampled_from(["prefill", "decode"]),
       paged=st.sampled_from([None, 4, 16]),
       stitch=st.booleans(),
       mesh_i=st.integers(0, len(_MESHES) - 1))
def test_property_serving_phases_partition(arch, batch, phase, paged,
                                           stitch, mesh_i):
    """Serving-phase DAGs (prefill / decode, contiguous and paged) obey
    the same carve invariants as the forward: chains + glue partition
    the op DAG, fused chains are MBCI, and the cache write (kv_write)
    is always standalone glue — never stitched into a carved unit."""
    cfg = get_config(arch, smoke=True)
    seq = 1 if phase == "decode" else 8
    kv_len = 32
    plan = planner.plan_model(cfg, batch, seq, stitch=stitch,
                              mesh=_MESHES[mesh_i], phase=phase,
                              paged=paged, kv_len=kv_len,
                              use_cache=False)
    covered = []
    for c in plan.layer.chains:
        covered += list(c.ops) + list(c.prologue) + list(c.epilogue)
    covered += list(plan.layer.glue)
    assert sorted(covered) == sorted(n.name for n in plan.layer.nodes)
    assert "kv_write" in plan.layer.glue
    for c in plan.layer.chains:
        assert "kv_write" not in c.prologue + c.epilogue
        if c.fused:
            assert c.ai < planner.ridge_intensity(V5E), (c.kind, c.ai)
    assert plan.phase == phase and plan.paged == paged
    assert plan.kv_len == kv_len


@settings(max_examples=10, deadline=None)
@given(arch=st.sampled_from(PLANNABLE),
       batch=st.integers(1, 4),
       phase=st.sampled_from(["prefill", "decode"]),
       paged=st.sampled_from([None, 4, 16]),
       mesh_i=st.integers(0, len(_MESHES) - 1))
def test_property_serving_planning_deterministic(arch, batch, phase,
                                                 paged, mesh_i):
    """Fixed (config, phase, mesh, page size) -> identical serving
    plan every time, and a distinct fingerprint per phase/page-size so
    cached decode plans can never serve a prefill lookup."""
    cfg = get_config(arch, smoke=True)
    seq = 1 if phase == "decode" else 8
    kw = dict(mesh=_MESHES[mesh_i], phase=phase, paged=paged,
              kv_len=32, use_cache=False)
    a = planner.plan_model(cfg, batch, seq, **kw)
    b = planner.plan_model(cfg, batch, seq, **kw)
    assert a == b
    assert planner.plan_to_json(a) == planner.plan_to_json(b)
    key = planner.plan_key(cfg, batch, seq, True, V5E,
                           _MESHES[mesh_i], phase, paged, 32)
    other = "prefill" if phase == "decode" else "decode"
    assert key != planner.plan_key(cfg, batch, seq, True, V5E,
                                   _MESHES[mesh_i], other, paged, 32)
    assert key != planner.plan_key(cfg, batch, seq, True, V5E,
                                   _MESHES[mesh_i], phase, 8, 32)


# ---------------------------------------------------------------------------
# Stitched kernel hooks (kernels/gemm_chain.py, kernels/attention.py)
# ---------------------------------------------------------------------------

def test_gemm_chain_hooks_interpret():
    """prologue/epilogue callables fold into the fused GEMM-chain kernel
    exactly like applying them outside (the stitched-execution twin)."""
    from repro.kernels.gemm_chain import fused_gemm_chain
    from repro.kernels.ref import gemm_chain_ref

    a = jax.random.normal(jax.random.PRNGKey(0), (1, 128, 64))
    b = jax.random.normal(jax.random.PRNGKey(1), (1, 64, 128))
    d = jax.random.normal(jax.random.PRNGKey(2), (1, 128, 64))
    out = fused_gemm_chain(a, b, d, bm=64, bn=64, bk=64, bh=64,
                           style="flat", interpret=True,
                           prologue=jnp.tanh,
                           epilogue=lambda x: x * 0.5)
    ref = gemm_chain_ref(jnp.tanh(a), b, d) * 0.5
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=3e-4, atol=1e-3)


def test_fused_mlp_chain_interpret():
    """The planner's gated-MLP kernel (silu(A Wg) * (A Wu)) Wd vs jnp."""
    from repro.kernels.gemm_chain import fused_mlp_chain

    a = jax.random.normal(jax.random.PRNGKey(0), (1, 128, 64))
    wu = jax.random.normal(jax.random.PRNGKey(1), (1, 64, 128))
    wg = jax.random.normal(jax.random.PRNGKey(2), (1, 64, 128))
    wd = jax.random.normal(jax.random.PRNGKey(3), (1, 128, 64))
    out = fused_mlp_chain(a, wu, wd, wg=wg, act="silu", bm=64, bn=64,
                          bk=64, bh=64, style="deep", interpret=True)
    ref = (jax.nn.silu(a @ wg) * (a @ wu)) @ wd
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=3e-4, atol=1e-3)
    # ungated
    out_u = fused_mlp_chain(a, wu, wd, act="gelu", bm=64, bn=64,
                            bk=64, bh=64, style="flat", interpret=True)
    ref_u = jax.nn.gelu(a @ wu) @ wd
    np.testing.assert_allclose(np.asarray(out_u), np.asarray(ref_u),
                               rtol=3e-4, atol=1e-3)


def test_attention_hooks_interpret():
    """q/k prologues and the o epilogue on the fused attention kernel
    equal the same transforms applied outside the kernel."""
    from repro.kernels.attention import fused_attention

    q = jax.random.normal(jax.random.PRNGKey(0), (1, 2, 128, 64))
    k = jax.random.normal(jax.random.PRNGKey(1), (1, 2, 128, 64))
    v = jax.random.normal(jax.random.PRNGKey(2), (1, 2, 128, 64))
    out = fused_attention(q, k, v, causal=True, bq=64, bkv=64,
                          interpret=True,
                          q_prologue=lambda x: x * 2.0,
                          o_epilogue=lambda x: x + 1.0)
    ref = fused_attention(q * 2.0, k, v, causal=True, bq=64, bkv=64,
                          interpret=True) + 1.0
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=3e-4, atol=1e-3)


# ---------------------------------------------------------------------------
# Kernel dispatch oracle: planned MLP chains through fused_mlp_chain
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("stitch", [False, True])
def test_planned_mlp_kernel_dispatch_oracle(monkeypatch, stitch):
    """Runtime(kernel_ops=True, planner=True) must route the planner's
    fused MLP chain through kernels.gemm_chain.fused_mlp_chain (asserted
    by counting kernel entries), and the kernel path — interpret mode,
    the hardware twin — must match the XLA node walk it replaces, with
    the stitched ln2 prologue / res2 epilogue surviving the dispatch."""
    from repro.kernels import ops
    from repro.models import layers as L

    cfg = get_config("qwen3_8b", smoke=True)
    plan = planner.plan_model(cfg, BATCH, SEQ, stitch=stitch,
                              use_cache=False)
    mlp = next(c for c in plan.layer.chains if c.kind == "mlp")
    assert mlp.fused, "smoke MLP must carve as one MBCI chain"

    rt_ref = Runtime(remat=False, planner=True, stitch=stitch)
    params = S.build_model(cfg, Runtime(remat=False)).init_params(
        jax.random.PRNGKey(0))
    p = jax.tree.map(lambda a: a[0], params["stack"]["b0_attn"])
    x = jax.random.normal(jax.random.PRNGKey(3),
                          (BATCH, SEQ, cfg.d_model)).astype(cfg.dtype)
    positions = jnp.arange(SEQ, dtype=jnp.int32)
    ref, _ = L.run_planned_layer(plan.layer, p, x, cfg, rt_ref.rules,
                                 positions=positions, rt=rt_ref)

    calls = []
    real = ops._mlp_chain_kernel
    monkeypatch.setattr(ops, "_backend_mode", lambda mode: "interpret")
    monkeypatch.setattr(ops, "_mlp_chain_kernel",
                        lambda *a, **k: calls.append(1) or real(*a, **k))
    rt_k = Runtime(remat=False, planner=True, stitch=stitch,
                   kernel_ops=True)
    out, _ = L.run_planned_layer(plan.layer, p, x, cfg, rt_k.rules,
                                 positions=positions, rt=rt_k)
    assert len(calls) == 1, "planned MLP chain must enter the kernel"
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=3e-4, atol=1e-3)
