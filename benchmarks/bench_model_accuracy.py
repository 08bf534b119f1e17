"""Paper §VI-E (Figs. 10-11): estimator quality.

Fig. 10 analogue — VMEM estimation: eq. (1)'s estimate vs the exact
VMEM a Pallas lowering of the schedule would allocate (block buffers
x double-buffering + accumulator scratch, computable precisely from the
emitted BlockSpecs).  We report quadrant accuracy at the Rule-4
budget line (``TpuSpec.vmem_budget``), as the paper does at its slack
line (>90% expected).

Fig. 11 analogue — performance model fidelity: analytical estimate vs
interpret-mode wall-clock over a candidate sample.  Interpret mode
executes the real kernel dataflow (per-block work scales with the
schedule), so rank correlation is the meaningful statistic on CPU.
"""
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.chain import gemm_chain
from repro.core.codegen import schedule_style, to_gemm_chain_params
from repro.core.perf_model import V5E, estimate, vmem_estimate
from repro.core.pruning import generate_candidates
from repro.kernels.gemm_chain import fused_gemm_chain


def pallas_actual_vmem(sched) -> int:
    """VMEM of the emitted kernel's buffers: in/out blocks (each
    double-buffered, as Mosaic allocates) + f32 scratch accumulators."""
    p = to_gemm_chain_params(sched)
    ts = sched.tile_sizes
    dt = 2 if sched.chain.tensors["A"].dtype == "bfloat16" else 4
    h_full = sched.chain.loops["h"]
    if p.style == "flat":
        blocks = (p.bm * p.bk + p.bk * p.bn + p.bn * h_full) * 2 * dt
        out = p.bm * h_full * 2 * dt
        scratch = (p.bm * p.bn + p.bm * h_full) * 4
    else:
        blocks = (p.bm * p.bk + p.bk * p.bn + p.bn * p.bh) * 2 * dt
        out = p.bm * p.bh * 2 * dt
        scratch = (p.bm * p.bn + p.bm * p.bh) * 4
    return blocks + out + scratch


def vmem_quadrants(n_shapes: int = 4) -> dict:
    shapes = [(1024, 1024, 512, 512), (512, 512, 256, 1024),
              (2048, 1024, 128, 128), (1024, 2048, 1024, 256)]
    pts = []
    for m, n, k, h in shapes[:n_shapes]:
        ch = gemm_chain(m, n, k, h, dtype="bfloat16")
        for sched in generate_candidates(ch):
            if schedule_style(sched) == "materialize":
                continue
            est = vmem_estimate(sched, V5E)
            act = pallas_actual_vmem(sched)
            pts.append((est, act))
    lim = V5E.vmem_budget
    slack = lim
    q1 = sum(1 for e, a in pts if e <= slack and a <= lim)   # keep, fits
    q3 = sum(1 for e, a in pts if e > slack and a > lim)     # prune, OOM
    q2 = sum(1 for e, a in pts if e > slack and a <= lim)    # over-prune
    q4 = sum(1 for e, a in pts if e <= slack and a > lim)    # under-prune
    n = len(pts)
    return {"n": n, "correct_pct": 100.0 * (q1 + q3) / n,
            "over_pruned_pct": 100.0 * q2 / n,
            "missed_pct": 100.0 * q4 / n}


def perf_correlation(n_samples: int = 10, reps: int = 3) -> dict:
    """Estimate-vs-measured over tuned-space candidates (Fig. 11)."""
    ch = gemm_chain(512, 512, 256, 256)
    cands = generate_candidates(ch)
    rng = np.random.default_rng(0)
    sample = [cands[i] for i in
              rng.choice(len(cands), min(n_samples, len(cands)),
                         replace=False)]
    a = jax.random.normal(jax.random.PRNGKey(0), (1, 512, 256))
    b = jax.random.normal(jax.random.PRNGKey(1), (1, 256, 512))
    d = jax.random.normal(jax.random.PRNGKey(2), (1, 512, 256))
    ests, meas = [], []
    for sched in sample:
        try:
            p = to_gemm_chain_params(sched)
        except NotImplementedError:
            continue
        fn = lambda: fused_gemm_chain(a, b, d, interpret=True,
                                      **p.as_kwargs()).block_until_ready()
        fn()  # warm the trace cache
        ts = [time.perf_counter() for _ in range(1)]
        t0 = time.perf_counter()
        for _ in range(reps):
            fn()
        meas.append((time.perf_counter() - t0) / reps)
        ests.append(estimate(sched, V5E))
    ests, meas = np.array(ests), np.array(meas)

    def rank(x):
        return np.argsort(np.argsort(x)).astype(float)

    pearson = float(np.corrcoef(ests, meas)[0, 1])
    spearman = float(np.corrcoef(rank(ests), rank(meas))[0, 1])
    return {"n": len(ests), "pearson": pearson, "spearman": spearman}


def run() -> dict:
    return {"vmem": vmem_quadrants(), "perf": perf_correlation()}


def main():
    out = run()
    print("name,us_per_call,derived")
    v = out["vmem"]
    print(f"vmem_estimator,0,n={v['n']} correct={v['correct_pct']:.1f}% "
          f"over_pruned={v['over_pruned_pct']:.1f}% "
          f"missed={v['missed_pct']:.1f}%")
    p = out["perf"]
    print(f"perf_model,0,n={p['n']} pearson={p['pearson']:.2f} "
          f"spearman={p['spearman']:.2f}")


if __name__ == "__main__":
    main()
