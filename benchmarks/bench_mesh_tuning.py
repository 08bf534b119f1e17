"""Mesh-aware tuning: tile picks and time terms per parallelism regime.

For each workload, tune the fused GEMM chain for three regimes and
report what moved (docs/tuning.md worked example, generalized):

  * single   — the paper's single-chip model (eq 2)
  * dp2xtp4  — batch over data=2, output features over model=4
               (the regime kernels/ops.py dispatches; collective-free,
               tile pick moves through localization)
  * ring4    — reduction loop n over model=4 (ring decomposition);
               the collective term prices the partial-sum all-reduce

`changed` marks workloads where the mesh regime picks a different
schedule (tile sizes or class) than the single-chip tuner — the
reason the mesh must be visible to the search, not applied after it.

The attention section sweeps the *dispatchable* regime triple —
spatial, ring (kv-sharded partial-softmax + blocking psum combine,
``dist/ring_dispatch.py``), and ring-pipelined (the same sharding with
the per-hop ppermute combine, ``MeshSpec(pipelined=True)``) — via
``api.fuse_attention_regimes`` on an 8-way model axis, over the paper's
short-context modules and long-context shapes where the crossover
flips.  ``--smoke`` is the CI lane: asserts the regime search prices
all regimes, lands on ring-pipelined for the compute-rich long
contexts, serial ring for the thin-output one, spatial for short —
and that the pipelined combine's executed collective-permute bytes on
a compiled 8-device program equal the eq (2') overlap-term pricing.
"""
import json
import os
import subprocess
import sys
import time

from repro.core.chain import gemm_chain
from repro.core.perf_model import (MeshSpec, V5E, alpha, estimate, t_comp,
                                   t_mem, t_coll)
from repro.core.search import heuristic_search
from repro.kernels import ops

from .workloads import (ATTENTION, GEMM_CHAINS, RING_ATTENTION,
                        ring_sweep_setup)

REGIMES = {
    "single": lambda: None,
    "dp2xtp4": lambda: MeshSpec(axes=(("data", 2), ("model", 4)),
                                placement=(("h", "model"),),
                                batch_axes=("data",)),
    "ring4": lambda: MeshSpec(axes=(("model", 4),),
                              placement=(("n", "model"),)),
}


def run() -> list[dict]:
    rows = []
    for name, (b, m, n, k, h) in list(GEMM_CHAINS.items()):
        ch = gemm_chain(m, n, k, h, batch=b, dtype="bfloat16")
        picks = {}
        for regime, make in REGIMES.items():
            mesh = make()
            t0 = time.perf_counter()
            rep = heuristic_search(ch, mesh=mesh, seed=0)
            dt = time.perf_counter() - t0
            s = rep.best
            picks[regime] = {
                "tiles": dict(s.tile_sizes), "expr": s.sub_expr(),
                "t_mem": t_mem(s, V5E), "t_comp": t_comp(s, V5E),
                "alpha": alpha(s, V5E),
                "t_coll": t_coll(s, mesh) if mesh is not None else 0.0,
                "t_estm": estimate(s, V5E, mesh), "tune_s": dt,
            }
        base = picks["single"]
        for regime, p in picks.items():
            rows.append({
                "name": f"{name}_{regime}",
                "t_estm": p["t_estm"],
                "expr": p["expr"],
                "tiles": p["tiles"],
                "t_coll": p["t_coll"],
                "changed": (regime != "single"
                            and (p["tiles"] != base["tiles"]
                                 or p["expr"] != base["expr"])),
            })
    return rows


# Attention regime sweep: paper modules (short kv) + the shared
# long-context crossover shapes, on an 8-way model axis.  The expected
# winner per shape pins the three-way crossover: spatial for short kv,
# ring-pipelined for long kv with enough output to overlap, serial
# ring for long kv whose thin output cannot amortize the hop launches.
ATTN_SWEEP = {
    "S1": (ATTENTION["S1"][:5], "spatial"),
    "S4": (ATTENTION["S4"][:5], "spatial"),
    "long_8k": (RING_ATTENTION["L1_tail_8k"], "ring-pipelined"),
    "long_32k": (RING_ATTENTION["L2_tail_32k"], "ring-pipelined"),
    "long_thin_8k": ((4, 64, 8192, 64, 64), "ring"),
}


def run_attention() -> list[dict]:
    mesh, rules = ring_sweep_setup()
    rows = []
    for name, ((heads, m, n, k, h), want) in ATTN_SWEEP.items():
        choice, _ = ops.attention_regime_choice(
            rules, mesh, batch=1, q_heads=heads, kv_heads=heads,
            q_len=m, kv_len=n, head_dim=k, v_dim=h, dtype="bfloat16",
            causal=True, interpret=True)
        assert choice is not None, f"{name}: kv not divisible by axis"
        ring_rep = choice.kernels["ring"].report
        rows.append({
            "name": name, "regime": choice.regime, "want": want,
            "t_spatial": choice.times["spatial"],
            "t_ring": choice.times["ring"],
            "t_ring_pipe": choice.times["ring-pipelined"],
            "t_coll_ring": t_coll(ring_rep.best, ring_rep.mesh),
        })
    return rows


# Executed-bytes differential: compiled on 8 forced host devices, the
# pipelined combine's collective-permute traffic must equal the
# pipelined_collective_bytes pricing (3(n-1) permute hops + the pmax
# all-reduce, nothing else).
_PIPE_WIRE_SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import json
import jax, jax.numpy as jnp
from repro.core.chain import attention_chain
from repro.core.perf_model import MeshSpec, pipelined_collective_bytes
from repro.dist import ring_dispatch
from repro.launch import hlo_analysis

mesh = jax.make_mesh((8,), ("model",),
                     axis_types=(jax.sharding.AxisType.Auto,))
B, Hq, M, N, D = 1, 2, 64, 1024, 32
kx = jax.random.split(jax.random.PRNGKey(0), 3)
q = jax.random.normal(kx[0], (B, Hq, M, D), jnp.float32)
k = jax.random.normal(kx[1], (B, Hq, N, D), jnp.float32)
v = jax.random.normal(kx[2], (B, Hq, N, D), jnp.float32)
fn = jax.jit(lambda a, b, c: ring_dispatch.ring_attention(
    a, b, c, mesh=mesh, axis="model", causal=True, bq=32, bkv=32,
    pipelined=True, interpret=True))
stats = hlo_analysis.parse_collectives(
    fn.lower(q, k, v).compile().as_text())
spec = MeshSpec(axes=(("model", 8),), placement=(("n", "model"),),
                pipelined=True)
chain = attention_chain(M, N, D, D, heads=Hq, batch=B,
                        dtype="float32", causal=True)
print("RESULT " + json.dumps(
    {"executed": stats.traffic_bytes,
     "priced": pipelined_collective_bytes(spec.localize(chain), spec),
     "permutes": stats.counts.get("collective-permute", 0)}))
"""


def _pipelined_wire_smoke() -> list[str]:
    # the child only counts bytes on forced host devices: keep it off
    # the accelerator, which this process may already hold
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    src = os.path.join(os.path.dirname(__file__), "..", "src")
    env["PYTHONPATH"] = os.pathsep.join(
        p for p in (src, env.get("PYTHONPATH")) if p)
    proc = subprocess.run([sys.executable, "-c", _PIPE_WIRE_SCRIPT],
                          env=env, capture_output=True,
                          text=True, timeout=600)
    if proc.returncode != 0:
        return [f"pipelined wire subprocess died: {proc.stderr[-500:]}"]
    line = [l for l in proc.stdout.splitlines()
            if l.startswith("RESULT ")]
    if not line:
        return [f"pipelined wire subprocess printed no RESULT: "
                f"{proc.stdout[-300:]}"]
    out = json.loads(line[-1][len("RESULT "):])
    fails = []
    if abs(out["executed"] - out["priced"]) > 1e-6 * out["priced"]:
        fails.append(f"pipelined executed bytes {out['executed']} != "
                     f"priced {out['priced']}")
    if out["permutes"] != 3 * 7:
        fails.append(f"pipelined ring emitted {out['permutes']} "
                     f"collective-permutes, expected {3 * 7}")
    print(f"smoke pipelined wire: executed={out['executed']:.0f}B "
          f"priced={out['priced']:.0f}B permutes={out['permutes']}")
    return fails


def smoke() -> int:
    """CI lane (benchmarks/run.py --smoke): the regime search must
    price all regimes, flip at the right scales, and the pipelined
    combine's executed wire must match its eq (2') pricing."""
    failures = []
    for r in run_attention():
        if r["t_coll_ring"] <= 0.0:
            failures.append(f"{r['name']}: ring regime priced no "
                            "collective term")
        if r["regime"] != r["want"]:
            failures.append(f"{r['name']}: picked {r['regime']}, "
                            f"expected {r['want']} "
                            f"(spatial={r['t_spatial']:.2e}s "
                            f"ring={r['t_ring']:.2e}s "
                            f"pipe={r['t_ring_pipe']:.2e}s)")
        # the serial-vs-pipelined pricing crossover, explicitly: the
        # winner's time is strictly under the loser's
        if r["want"] == "ring-pipelined" \
                and r["t_ring_pipe"] >= r["t_ring"]:
            failures.append(f"{r['name']}: pipelined priced no faster "
                            "than serial ring")
        if r["want"] == "ring" and r["t_ring"] >= r["t_ring_pipe"]:
            failures.append(f"{r['name']}: serial ring priced no "
                            "faster than pipelined")
        print(f"smoke regime {r['name']}: {r['regime']} "
              f"spatial={r['t_spatial']*1e6:.1f}us "
              f"ring={r['t_ring']*1e6:.1f}us "
              f"pipe={r['t_ring_pipe']*1e6:.1f}us")
    failures += _pipelined_wire_smoke()
    # gemm ring regime: the collective term must steer the tuner away
    # at paper scale (docs/tuning.md worked example)
    b, m, n, k, h = GEMM_CHAINS["G10"]
    ch = gemm_chain(m, n, k, h, batch=b, dtype="bfloat16")
    rep_single = heuristic_search(ch, seed=0)
    rep_ring = heuristic_search(ch, mesh=REGIMES["ring4"](), seed=0)
    if rep_ring.best_time <= rep_single.best_time:
        failures.append("G10: ring-sharded GEMM reduction priced "
                        "cheaper than single chip — collective term "
                        "missing?")
    for f in failures:
        print(f"SMOKE FAIL: {f}", file=sys.stderr)
    print(f"mesh-tuning smoke: {'FAIL' if failures else 'OK'}",
          file=sys.stderr)
    return 1 if failures else 0


def main():
    print("name,us_per_call,derived")
    for r in run():
        ts = r["tiles"]
        print(f"mesh_tune_{r['name']},{r['t_estm']*1e6:.2f},"
              f"expr={r['expr']} "
              f"tiles=m{ts['m']}/n{ts['n']}/k{ts['k']}/h{ts['h']} "
              f"t_coll_us={r['t_coll']*1e6:.2f} "
              f"changed={'yes' if r['changed'] else 'no'}")
    for r in run_attention():
        best = min(r["t_spatial"], r["t_ring"], r["t_ring_pipe"])
        print(f"mesh_regime_{r['name']},{best*1e6:.2f},"
              f"regime={r['regime']} "
              f"spatial={r['t_spatial']*1e6:.2f}us "
              f"ring={r['t_ring']*1e6:.2f}us "
              f"ring_pipe={r['t_ring_pipe']*1e6:.2f}us "
              f"t_coll_ring={r['t_coll_ring']*1e6:.2f}us")


if __name__ == "__main__":
    import argparse
    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true",
                    help="CI assertions: regimes priced + crossover")
    if ap.parse_args().smoke:
        sys.exit(smoke())
    main()
