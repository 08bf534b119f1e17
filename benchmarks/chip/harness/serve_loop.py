"""One run of a serving cell: set-up, warm-up, the timed window, and the
record the metric readers take their numbers from.

The window drives the program's public serving loop, ``submit()`` and
``step()`` of the ``ServingEngine`` that ``launch.serve.make_engine``
builds, on one process with one thread.  The harness watches from
outside the engine instance: it wraps the instance's ``_admit_one``
(the host holds a request's first token when it returns) and ``_exec``
(the slot contexts each decode call serves), and reads the tokens each
slot holds after every ``step()``.  No file of the program is changed.

Every host span the harness records is a ``jax.profiler.TraceAnnotation``
(``bench.step`` around ``engine.step()``, ``bench.wait`` while the
open loop has nothing due, ``bench.generate`` while it makes and submits
requests), so a traced run puts them on the device trace's clock.
"""
from __future__ import annotations

import dataclasses
import gc
import os
import shutil
import time

import numpy as np

from . import spec as spec_mod
from . import traffic_gen
from . import weights as W

clock = time.perf_counter
TRACE_SECONDS = 4.0          # the traced sub-window, at the window's end
TRACE_DIR = spec_mod.ROOT / ".cache" / "chipbench_trace"
SCHEDULE_DIR = spec_mod.ROOT / ".cache" / "chipbench_schedules"


def configure_caches() -> str:
    """JAX's persistent compilation cache where ``JAX_COMPILATION_CACHE_DIR``
    says, else at the fixed ``<checkout>/.cache/jax``; every program is
    kept, however quick its compile.  The program's tuned schedules go to
    the fixed ``<checkout>/.cache/chipbench_schedules``.  Call before the
    program is imported."""
    os.environ["REPRO_CACHE_DIR"] = str(SCHEDULE_DIR)
    import jax
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR") or str(
        spec_mod.ROOT / ".cache" / "jax")
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path


def make_weights(cell, seed: int, shardings=None):
    """The canonical draw (for the reference) or the program's tree, in
    one jitted call on the device, as the cell's architecture module
    lays them out."""
    import jax
    import jax.numpy as jnp
    arch, c = cell.arch, cell.config
    dt = jnp.dtype(c["torch_dtype"])
    sd = float(c["initializer_range"])

    def canon(key):
        return arch.canonical(c, key, dt, sd)

    def program(key):
        return arch.to_program(canon(key), c)

    return canon, program, jax.jit(program, out_shardings=shardings)


def reference_weights(cell, seed: int, devices):
    """The canonical draw for the reference, spread over ``devices``:
    each leaf split along the axis the architecture module's ``spread``
    names, or whole on each.  The values are those of the draw on one
    device (the random bits are made whole and then split), so a cell on
    four chips checks against the same weights as on one, with a quarter
    of them on each chip."""
    import jax
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
    canon, _, _ = make_weights(cell, seed)
    mesh = Mesh(np.asarray(devices), ("chips",))
    n = len(devices)

    def place(name, leaf):
        axis = cell.arch.spread(name, leaf, n)
        if axis is None:
            return NamedSharding(mesh, P())
        spec = [None] * leaf.ndim
        spec[axis] = "chips"
        return NamedSharding(mesh, P(*spec))

    shapes = jax.eval_shape(canon, W.seed_key(0))
    out = {k: place(k, v) for k, v in shapes.items()}
    return jax.jit(canon, out_shardings=out)(W.seed_key(seed))


def regime_line(engine) -> str:
    """The decode regime the engine picked and the times it priced."""
    times = ", ".join(f"{k} {v * 1e6:.1f}us"
                      for k, v in engine.regime_times.items())
    return (f"decode regime {engine.regime} (priced: {times}; schedule "
            f"from {engine.regime_source})")


def cache_layout(cache) -> str:
    """The KV pool's sharding and its bytes on the fullest chip."""
    import jax
    leaves = jax.tree.leaves(cache)
    per_dev: dict = {}
    for x in leaves:
        for sh in x.addressable_shards:
            per_dev[sh.device] = per_dev.get(sh.device, 0) + sh.data.nbytes
    spec = getattr(leaves[0].sharding, "spec", leaves[0].sharding)
    return (f"{spec}, {sum(x.nbytes for x in leaves)} B in all, "
            f"{max(per_dev.values())} B on the fullest of {len(per_dev)} "
            f"chips")


@dataclasses.dataclass
class Req:
    index: int
    due: float              # absolute host time it was due
    prompt_len: int
    max_new: int
    rid: int = -1
    submit_t: float = 0.0
    admit_step: int = -1    # index into Record.steps
    times: list = dataclasses.field(default_factory=list)
    finish_t: float | None = None
    outcome: str | None = None
    tokens: list | None = None


@dataclasses.dataclass
class Step:
    t0: float
    t1: float
    prefills: list          # real prompt lengths admitted in this step
    ctx: list               # context length of each active slot decoded


@dataclasses.dataclass
class Record:
    cell: object
    seed: int
    chips: int
    t_start: float
    t_end: float
    setup_s: float
    requests: list
    steps: list
    counters_start: dict
    counters_end: dict
    compiles_in_window: int
    trace: object = None        # trace_reduce.Reduced
    traced_steps: tuple = ()    # (first, last + 1) indices of traced steps
    peaks: object = None

    @property
    def window_s(self) -> float:
        return self.t_end - self.t_start

    def in_window(self, t) -> bool:
        return t is not None and self.t_start <= t <= self.t_end


class Runner:
    """Drives one cell's traffic through the engine and records it."""

    def __init__(self, cell, seed: int, seconds: float, trace: bool,
                 t_process: float, log=print):
        self.cell, self.seed, self.seconds = cell, int(seed), float(seconds)
        self.trace = trace
        self.t_process = t_process
        self.log = log
        p = cell.params
        self.traffic = traffic_gen.Traffic(cell.traffic, seed,
                                           rate=p.get("rate_per_s"))
        self.reqs: dict[int, Req] = {}      # by engine rid
        self.order: list[Req] = []
        self.steps: list[Step] = []
        self._cur: Step | None = None
        self._n_issued = 0

    # -- building -------------------------------------------------------
    def build(self):
        import jax
        from repro.launch import steps as S
        from repro.launch.serve import make_engine, sharded_runtime
        cell = self.cell
        cfg = cell.arch.model_config(cell.config)
        mesh, _, rt = sharded_runtime(cell.chips, kernel_ops=True,
                                      planner=True)
        self.mesh = mesh
        model = S.build_model(cfg, rt)
        shardings = (S.shardings_for(mesh, model.param_specs())
                     if mesh is not None else None)
        _, program, jitted = make_weights(cell, self.seed, shardings)
        expected = jax.eval_shape(program, W.seed_key(0))
        W.check_layout(expected, model.abstract_params())
        t = clock()
        params = jax.block_until_ready(jitted(W.seed_key(self.seed)))
        self.weights_s = clock() - t
        p = cell.params
        with self.mesh_context():
            t = clock()
            self.engine = make_engine(
                model, params, batch=p["slots"], prompt_len=cell.max_prompt,
                gen=cell.max_output, page_size=p["page_size"], verbose=False)
            self.engine_s = clock() - t
        self._instrument(self.engine)

    def mesh_context(self):
        import contextlib
        import jax
        return (jax.set_mesh(self.mesh) if self.mesh is not None
                else contextlib.nullcontext())

    def _instrument(self, eng):
        admit, exec_ = eng._admit_one, eng._exec

        def admit_one():
            head = eng.queue[0] if eng.queue else None
            ok = admit()
            if ok and head is not None and head.rid in self.reqs:
                r = self.reqs[head.rid]
                r.times.append(clock())
                r.admit_step = len(self.steps)
                if self._cur is not None:
                    self._cur.prefills.append(len(head.prompt))
            return ok

        def exec_phase(phase, *args):
            if phase == "decode" and self._cur is not None:
                self._cur.ctx = [s.pos + 1 for s in eng.slots
                                 if s is not None]
            return exec_(phase, *args)

        eng._admit_one = admit_one
        eng._exec = exec_phase

    # -- traffic --------------------------------------------------------
    def _submit(self, req: traffic_gen.Request, due: float):
        import jax
        with jax.profiler.TraceAnnotation("bench.generate"):
            toks = self.traffic.tokens(req, self.cell.config["vocab_size"])
            r = Req(req.index, due, req.prompt_len, req.max_new)
            r.submit_t = clock()
            r.rid = self.engine.submit(toks, r.max_new)
            self.reqs[r.rid] = r
            self.order.append(r)
            self._n_issued += 1

    def _step(self):
        import jax
        eng = self.engine
        self._cur = Step(clock(), 0.0, [], [])
        with jax.profiler.TraceAnnotation("bench.step"):
            done = eng.step()
        t = clock()
        self._cur.t1 = t
        self.steps.append(self._cur)
        self._cur = None
        for s in eng.slots:
            if s is not None and s.rid in self.reqs:
                r = self.reqs[s.rid]
                r.times += [t] * (len(s.generated) - len(r.times))
        for f in done:
            r = self.reqs.get(f.rid)
            if r is None:
                continue
            r.times += [t] * (len(f.tokens) - len(r.times))
            r.finish_t, r.outcome, r.tokens = t, f.outcome, list(f.tokens)
            if self.traffic.loop == "closed":
                self._submit(self.traffic.next(self._n_issued), t)

    def _busy(self) -> bool:
        eng = self.engine
        return bool(eng.queue) or any(s is not None for s in eng.slots)

    def _drive(self, t0: float, until: float):
        """Run the traffic that started at ``t0`` up to host time
        ``until`` (open loop: submit what is due, step while busy, wait
        for the next arrival when idle)."""
        import jax
        open_loop = self.traffic.loop == "open"
        while True:
            now = clock()
            if now >= until:
                return
            if open_loop:
                nxt = self.traffic.next(self._n_issued)
                while t0 + nxt.due <= now:
                    self._submit(nxt, t0 + nxt.due)
                    nxt = self.traffic.next(self._n_issued)
            if self._busy():
                self._step()
            elif open_loop:
                with jax.profiler.TraceAnnotation("bench.wait"):
                    time.sleep(max(0.0, min(t0 + nxt.due, until) - now))
            else:
                raise RuntimeError("closed loop went idle")

    def warm_up(self):
        """One request of every prompt length the run will prefill (and,
        for a closed loop, every length its steady start snaps to),
        served to its end, again until a pass compiles nothing new; then
        the traffic itself.  A closed loop starts in its steady state:
        each client's request in progress, prefilled with its context,
        all admitted before the mix's warm-up seconds begin.  (On a mesh
        the decode program hands the cache back with another sharding
        than it had when the first prefill ran, so that prefill compiles
        again on the second pass.)"""
        closed = self.traffic.loop == "closed"
        lengths = set(self.cell.traffic["prompt"]["snap"])
        if closed:
            lengths |= set(self.cell.traffic["start"]["snap"])
        while True:
            known = len(self.engine.programs)
            for length in sorted(lengths):
                self.engine.submit(self.traffic.warmup_tokens(
                    length, self.cell.config["vocab_size"]), 2)
                while self._busy():
                    self.engine.step()
            if len(self.engine.programs) == known:
                break
        t0 = clock()
        if closed:
            for ctx, budget in self.traffic.steady_start(self.engine.n_ctx):
                self._submit(traffic_gen.Request(self._n_issued, ctx,
                                                 budget, 0.0), t0)
            while self.engine.queue:
                self._step()
            t0 = clock()
        self._t0 = t0
        self._drive(t0, t0 + float(self.cell.traffic["warmup_s"]))

    # -- the window -----------------------------------------------------
    def window(self) -> Record:
        import jax
        eng = self.engine
        # what set-up built stays: later collections need not walk it
        gc.collect()
        gc.freeze()
        t_start = clock()
        t_end = t_start + self.seconds
        n_prog = len(eng.programs)
        c_start = dict(eng.stats)
        traced = ()
        if self.trace:
            self._drive(self._t0, max(t_start, t_end - TRACE_SECONDS))
            shutil.rmtree(TRACE_DIR, ignore_errors=True)
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            t = clock()
            jax.profiler.start_trace(str(TRACE_DIR), profiler_options=opts)
            self.log(f"profiler start took {clock() - t:.3f}s")
            first = len(self.steps)
            try:
                self._drive(self._t0, t_end)
            finally:
                jax.profiler.stop_trace()
            traced = (first, len(self.steps))
        else:
            self._drive(self._t0, t_end)
        rec = Record(
            cell=self.cell, seed=self.seed, chips=self.cell.chips,
            t_start=t_start, t_end=t_end, setup_s=t_start - self.t_process,
            requests=list(self.order), steps=list(self.steps),
            counters_start=c_start, counters_end=dict(eng.stats),
            compiles_in_window=len(eng.programs) - n_prog,
            traced_steps=traced)
        if self.trace:
            from . import trace_reduce
            meta = trace_reduce.ops_metadata(
                p.as_text() for k, p in eng.programs.items() if k[0] == 0)
            rec.trace = trace_reduce.reduce(
                trace_reduce.latest_xplane(str(TRACE_DIR)), meta)
        return rec

    def release(self):
        """Drop the program's state so the reference has the chip."""
        self.engine = None
        gc.unfreeze()
        gc.collect()


def device_peak_bytes(devices) -> int:
    return max(int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
               for d in devices)


def sample_for_check(rec: Record, n: int, seed: int) -> list:
    """Requests finished inside the window, drawn from the seed, the
    longest (prompt plus served tokens) always among them."""
    done = [r for r in rec.requests if rec.in_window(r.finish_t)
            and r.outcome == "complete"]
    if not done:
        return []
    longest = max(done, key=lambda r: (r.prompt_len + len(r.tokens),
                                       r.index))
    rest = [r for r in done if r is not longest]
    rng = np.random.Generator(np.random.PCG64(
        np.random.SeedSequence([seed, 5])))
    pick = rng.permutation(len(rest))[: max(0, n - 1)]
    return [longest] + [rest[i] for i in sorted(pick)]
