"""Define a CUSTOM MBCI chain (three back-to-back GEMMs), run it through
the full MCFuser pipeline — enumeration, DAG hoisting, pruning,
analytical search — and inspect what the tuner decided.

Shows the paper's machinery is not hard-coded to 2-op chains.

    PYTHONPATH=src python examples/fuse_custom_chain.py
"""
from repro.core.chain import gemm_chain3
from repro.core.perf_model import (V5E, estimate, t_comp, t_mem,
                                   vmem_estimate)
from repro.core.pruning import PruneStats, generate_candidates
from repro.core.search import heuristic_search
from repro.core.tiling import enumerate_tilings, expr_repr


def main():
    # G = ((A@B)@D)@F with small reduction dims -> MBCI
    ch = gemm_chain3(M=1024, N=512, K=64, H=64, G=64, dtype="bfloat16")
    print(f"chain: {ch.name}  loops={ch.loops}")
    print(f"arithmetic intensity (unfused): "
          f"{ch.arithmetic_intensity():.1f} flops/byte "
          f"(MXU needs {V5E.peak_flops/V5E.hbm_bw:.0f}+ to stay busy -> "
          f"memory-bound unfused)")

    exprs = enumerate_tilings(ch)
    print(f"\ntiling expressions: {len(exprs)} "
          f"(e.g. {expr_repr(exprs[0])}, {expr_repr(exprs[-1])})")

    stats = PruneStats()
    cands = generate_candidates(ch, stats=stats)
    print(f"raw space {stats.n_total:,} -> kept {stats.n_kept:,} "
          f"(rule2 pruned {stats.n_rule2:,}, rule3 {stats.n_rule3:,}, "
          f"rule4 {stats.n_rule4:,})")

    rep = heuristic_search(ch, seed=0)
    s = rep.best
    print(f"\nbest schedule : {s.sub_expr()}  grid={s.grid}")
    print(f"tile sizes    : {s.tile_sizes}")
    print(f"VMEM estimate : {vmem_estimate(s, V5E)/2**20:.1f} MiB "
          f"(budget {V5E.vmem_budget/2**20:.0f} MiB)")
    print(f"est. time     : {estimate(s, V5E)*1e6:.2f} us  "
          f"[mem {t_mem(s, V5E)*1e6:.2f}, comp {t_comp(s, V5E)*1e6:.2f}]")
    unfused = ch.io_bytes() / V5E.hbm_bw
    print(f"unfused HBM floor alone would take {unfused*1e6:.2f} us -> "
          f"fusion win >= {unfused/estimate(s, V5E):.1f}x")
    print(f"search measured {rep.n_measured}/{rep.n_candidates} candidates "
          f"in {rep.n_iterations} iterations")


if __name__ == "__main__":
    main()
