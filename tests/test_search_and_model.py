"""Algorithm 1 (heuristic search) + analytical performance model."""
import math

import pytest
from hypothesis import given, settings, strategies as st

from repro.core import api
from repro.core.chain import attention_chain, gemm_chain
from repro.core.codegen import to_attention_params, to_gemm_chain_params
from repro.core.dag import build_schedule
from repro.core.perf_model import (V5E, alpha, estimate, roofline_bound,
                                   t_comp, t_mem, vmem_estimate, fits_vmem)
from repro.core.pruning import generate_candidates
from repro.core.search import heuristic_search
from repro.core.tiling import deep_tiling


def test_search_beats_median_candidate():
    ch = gemm_chain(1024, 1024, 256, 256)
    report = heuristic_search(ch, seed=0)
    cands = generate_candidates(ch)
    ests = sorted(estimate(c, V5E) for c in cands)
    median = ests[len(ests) // 2]
    assert report.best_time <= median
    assert report.best_time >= roofline_bound(report.best, V5E) * 0.99


def test_search_is_deterministic():
    ch = gemm_chain(512, 512, 128, 128)
    r1 = heuristic_search(ch, seed=3)
    r2 = heuristic_search(ch, seed=3)
    assert r1.best.key() == r2.best.key()


def test_search_measures_only_topk_subset():
    """The 70x tuning-time claim: measurements << candidates."""
    ch = gemm_chain(2048, 2048, 256, 256)
    report = heuristic_search(ch, topk=8)
    assert report.n_candidates > 100
    assert report.n_measured <= 8 * report.n_iterations
    assert report.n_measured < report.n_candidates / 4


def test_search_converges_without_iteration_budget():
    ch = gemm_chain(1024, 512, 128, 128)
    report = heuristic_search(ch, max_iterations=64)
    assert report.n_iterations < 64  # epsilon criterion fired


def test_alpha_penalizes_small_grids():
    ch = gemm_chain(256, 256, 128, 128)
    big = build_schedule(ch, deep_tiling("mhnk"),
                         {"m": 128, "n": 128, "k": 128, "h": 128})
    small = build_schedule(ch, deep_tiling("mhnk"),
                           {"m": 256, "n": 256, "k": 128, "h": 256})
    assert alpha(small, V5E) > alpha(big, V5E) >= 1.0


def test_mbci_shift_reflected_in_model():
    """Paper §II: shrinking K turns the UNFUSED chain memory-bound
    (phi < P/W); MCFuser fusion then removes that bottleneck."""
    compute_bound = gemm_chain(2048, 2048, 2048, 2048, dtype="bfloat16")
    memory_bound = gemm_chain(2048, 2048, 16, 16, dtype="bfloat16")

    def unfused_mem_over_comp(ch):
        return ((ch.io_bytes() / V5E.hbm_bw)
                / (ch.total_flops() / V5E.peak_flops))

    assert unfused_mem_over_comp(memory_bound) > 1.0   # MBCI
    assert unfused_mem_over_comp(compute_bound) < 1.0  # classic GEMM
    # fusion keeps C in VMEM: tuned traffic << unfused traffic
    s = heuristic_search(memory_bound, seed=0).best
    assert t_mem(s, V5E) < (memory_bound.io_bytes() / V5E.hbm_bw) / 5


def test_fusion_beats_unfused_estimate():
    """The whole point: fused schedule traffic < unfused two-kernel
    traffic for MBCI shapes (C never round-trips HBM)."""
    ch = gemm_chain(1024, 1024, 64, 64, dtype="bfloat16")
    s = heuristic_search(ch, seed=0).best
    unfused_bytes = ch.io_bytes()
    fused_bytes = t_mem(s, V5E) * V5E.hbm_bw
    assert fused_bytes < unfused_bytes


def test_vmem_estimates_within_budget_after_pruning():
    ch = attention_chain(2048, 2048, 128, 128)
    for c in generate_candidates(ch):
        assert vmem_estimate(c, V5E) <= V5E.vmem_budget <= V5E.vmem_bytes


@given(m=st.sampled_from([512, 1024]), k=st.sampled_from([32, 64, 256]))
@settings(max_examples=10, deadline=None)
def test_estimate_above_roofline_bound(m, k):
    ch = gemm_chain(m, m, k, k)
    for c in generate_candidates(ch)[:50]:
        assert estimate(c, V5E) >= roofline_bound(c, V5E) * 0.99


def test_api_cache_and_codegen():
    tk1 = api.fuse_gemm_chain(512, 512, 128, 128)
    tk2 = api.fuse_gemm_chain(512, 512, 128, 128)
    assert tk1 is tk2  # cached: tuning paid once per shape
    p = to_gemm_chain_params(tk1.report.best)
    assert p.style in ("flat", "deep")
    assert all(v >= 1 for v in (p.bm, p.bn, p.bk, p.bh))

    tk3 = api.fuse_attention(512, 512, 64, 64, heads=4)
    ap = to_attention_params(tk3.report.best)
    assert 512 % ap.bq == 0 and 512 % ap.bkv == 0


def test_tpu_spec_lookup_by_device_kind():
    """The tuner's hardware comes from a table keyed by device_kind; a
    kind it does not hold is an error, never priced as a v5e."""
    from repro.core.perf_model import device_spec, tpu_spec
    assert tpu_spec("TPU v5 lite") is V5E
    with pytest.raises(ValueError, match="TPU v4"):
        tpu_spec("TPU v4")
    assert device_spec() is V5E     # CPU backend: the v5e target
