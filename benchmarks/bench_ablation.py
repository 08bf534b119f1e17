"""Ablation: what each pruning rule buys (extends the paper's Fig. 7).

For the paper's running example (M=N=1024, K=H=512, unit=16) and a
TPU-sized variant (unit=128), we disable rules one at a time and report
candidate counts, search wall-clock, and found-schedule quality
relative to the all-rules tuner.
"""
import time

from repro.core.chain import gemm_chain
from repro.core.perf_model import V5E, estimate, vmem_estimate
from repro.core.pruning import PruneStats, generate_candidates
from repro.core.search import heuristic_search


def run() -> list[dict]:
    import repro.core.pruning as PR

    ch = gemm_chain(1024, 1024, 512, 512, dtype="bfloat16")
    rows = []

    # full pipeline
    t0 = time.perf_counter()
    rep = heuristic_search(ch, seed=0)
    full_t = time.perf_counter() - t0
    best_full = rep.best_time
    rows.append({"variant": "all_rules", "candidates": rep.n_candidates,
                 "search_s": full_t, "best_us": best_full * 1e6,
                 "quality_vs_full": 1.0})

    # no Rule 2 (kn-class kept, Rule 4 must catch the blow-ups)
    stats = PruneStats()
    cands = generate_candidates(ch, hard_rule2=False, stats=stats)
    best = min(estimate(c, V5E) for c in cands)
    rows.append({"variant": "no_rule2", "candidates": stats.n_kept,
                 "search_s": None, "best_us": best * 1e6,
                 "quality_vs_full": best_full / best})

    # no Rule 3 (padding tiles kept) — count only; the exhaustive
    # space is enumerable at unit=128
    stats = PruneStats()
    orig = PR.rule3_padding_ok
    try:
        PR.rule3_padding_ok = lambda *a, **k: True
        cands = generate_candidates(ch, stats=stats)
        best = min(estimate(c, V5E) for c in cands)
    finally:
        PR.rule3_padding_ok = orig
    rows.append({"variant": "no_rule3", "candidates": stats.n_kept,
                 "search_s": None, "best_us": best * 1e6,
                 "quality_vs_full": best_full / best})

    # no Rule 4 (VMEM-infeasible schedules kept in the candidate set)
    stats = PruneStats()
    cands = generate_candidates(
        ch, hw=V5E.__class__(name="no_r4", vmem_budget=1 << 62), stats=stats)
    n_infeasible = sum(
        1 for c in cands if vmem_estimate(c, V5E) > V5E.vmem_budget)
    rows.append({"variant": "no_rule4", "candidates": stats.n_kept,
                 "search_s": None, "best_us": None,
                 "quality_vs_full": None,
                 "infeasible_kept": n_infeasible})
    return rows


def main():
    print("name,us_per_call,derived")
    for r in run():
        extra = (f" infeasible_kept={r['infeasible_kept']}"
                 if "infeasible_kept" in r else
                 f" quality={r['quality_vs_full']:.3f}")
        best = f"{r['best_us']:.2f}" if r["best_us"] else "-"
        print(f"ablate_{r['variant']},{best},"
              f"cands={r['candidates']}{extra}")


if __name__ == "__main__":
    main()
