"""Orca-style continuous-batching scheduler over the paged KV cache
(docs/serving.md).

One ``step()`` is one scheduler iteration:

1. **admit** — pop FIFO requests into free batch slots while the page
   pool can cover their prompt plus one page of decode headroom, and
   prefill each (batch-1, padded to a page multiple) straight into its
   freshly allocated pages;
2. **decode** — every running request advances one token in a single
   ragged batched ``decode_step_paged`` call (inactive slots ride along
   masked: position -1, kv to the scratch page, logits ignored);
3. **evict** — requests that hit their token budget (or ``eos_id``)
   free their pages back to the pool and leave the batch.

Iteration-level scheduling is what makes the batch *continuous*: a
finished request's slot and pages are reusable on the very next step,
so ragged generation lengths never strand slot-steps the way
fixed-batch serving does (benchmarks/bench_serving.py measures the
gap).  Under memory pressure the **newest** running request is
preempted and requeued for recompute (its prompt plus
tokens-generated-so-far become the new prompt) — freeing the most
recently allocated pages first, the standard vLLM-style policy.

The regime the decode attention runs under is a tuner decision, as
everywhere else in this repo: at construction the engine prices
paged-spatial vs paged-ring vs paged-ring-pipelined for its decode
shape (``kernels.ops.paged_attention_regime_choice``,
persistent-cached) and enables the kv-sharded ring path — with the
per-hop ppermute combine when the pipelined variant wins — only when
the model ranks it fastest.

Degradation (docs/reliability.md): the engine never dies on a fused
unit that fails at dispatch.  Execution runs through a **tiered
fallback chain** — tier 0 is the configured model (planner/kernel
paths as built), tier 1 its XLA twin (planner, kernel_ops and the ring
decode disabled), tier 2 the same twin executed eagerly (no jit) —
demoting stickily on a dispatch failure and quarantining the failing
plan fingerprint through the circuit breaker so relaunches skip it.
Compiling a step program is not a dispatch: a program the compiler
refuses raises.  Requests carry an
optional per-request **deadline** (evicted honestly past it), a
preemption **retry budget** bounds recompute livelock, a soft
**watchdog** times every step, and ``drain()`` replaces the
``reset()``-while-in-flight error with a graceful stop.
"""
from __future__ import annotations

import dataclasses
import math
import time
import warnings
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.profiler import StepTraceAnnotation, TraceAnnotation

from ..reliability import breaker as _breaker
from ..reliability import faults as _faults
from ..reliability import sentinels as _sentinels
from ..reliability.watchdog import StepWatchdog
from . import kv_pages as KP

#: Execution tiers, best first (docs/reliability.md §3).
TIERS = ("configured", "xla-twin", "eager-twin")

#: Per-request outcomes reported on ``FinishedRequest.outcome``.
#: "health" = evicted by the activation health monitor
#: (``Runtime(sentinels=True)``): its step produced NaN/Inf/exploded
#: logits, and the partial tokens are reported honestly.
OUTCOMES = ("complete", "deadline", "preempt_budget", "drained",
            "health")


@dataclasses.dataclass
class FinishedRequest:
    """One completed request, in submission order from ``run()``."""

    rid: int
    prompt_len: int
    tokens: list[int]            # generated tokens (may be < requested
    submit_step: int             # budget when eos_id fired)
    finish_step: int
    n_preempted: int = 0
    outcome: str = "complete"    # one of OUTCOMES; anything but
    #                              "complete" means tokens is partial
    # host clock (``time.perf_counter``) at submit, first admission,
    # first token held by the host, and finish; None where the request
    # never got that far.  A preemption keeps the first admission's.
    t_submit: float = 0.0
    t_admit: Optional[float] = None
    t_first: Optional[float] = None
    t_finish: Optional[float] = None


@dataclasses.dataclass
class _Pending:
    rid: int
    prompt: np.ndarray           # original prompt ++ recomputed tokens
    base_prompt_len: int
    done: list[int]
    max_new: int
    submit_step: int
    n_preempted: int = 0
    deadline: Optional[int] = None   # absolute step number, inclusive
    t_submit: float = 0.0            # FinishedRequest's stamps
    t_admit: Optional[float] = None
    t_first: Optional[float] = None


@dataclasses.dataclass
class _Slot:
    rid: int
    prompt: np.ndarray           # original prompt (++ recomputed tokens
    base_prompt_len: int         # after a preemption)
    generated: list[int]
    max_new: int
    alloc: KP.RequestPages
    submit_step: int
    admit_seq: int               # preemption order: newest goes first
    n_preempted: int = 0
    n_done_admit: int = 0        # generated tokens already inside
    #                              ``prompt`` (recompute re-prefilled them)
    deadline: Optional[int] = None
    t_submit: float = 0.0
    t_admit: Optional[float] = None
    t_first: Optional[float] = None

    @property
    def pos(self) -> int:
        """Absolute position the next decode step writes: kv holds the
        prompt plus every post-admission token except the newest
        (whose kv is written by the step that consumes it).  Tokens
        re-prefilled after a preemption live in ``prompt`` AND
        ``generated`` — count them once."""
        return (len(self.prompt) + len(self.generated)
                - self.n_done_admit - 1)


class ServingEngine:
    """Continuous-batching serving over a paged KV cache.

    model/params: an attention-only ``models.lm.LM`` and its weights
    (sharded by the caller when a mesh is ambient — run ``step()`` /
    ``run()`` inside ``jax.set_mesh`` then, as ``launch.serve`` does).
    max_batch: decode slot count (the ragged batch width).
    page_size / n_pages: the pool (page 0 is scratch, so ``n_pages - 1``
    are allocatable).  max_pages_per_seq: page-table width; a request
    may span at most ``max_pages_per_seq * page_size`` positions.

    A model built with ``Runtime(planner=True)`` serves planner-carved
    blocks: prefill and decode steps execute phase-keyed plans from
    ``core.planner`` (decode pre-planned at construction), bit-identical
    to the hand-wired paged path on f32 configs with stitching off
    (docs/planner.md §7, tests/test_serving.py).
    """

    def __init__(self, model, params, *, max_batch: int = 4,
                 page_size: int = 16, n_pages: int = 64,
                 max_pages_per_seq: int = 8,
                 eos_id: Optional[int] = None,
                 choose_regime: bool = True, verbose: bool = False,
                 max_preemptions: int = 8,
                 watchdog_s: Optional[float] = None,
                 stall_limit: int = 8):
        self.params = params
        self.max_batch = max_batch
        self.page_size = page_size
        self.max_pages = max_pages_per_seq
        self.n_ctx = max_pages_per_seq * page_size
        self.eos_id = eos_id
        self.verbose = verbose
        self.max_preemptions = max_preemptions
        self.stall_limit = stall_limit
        self.watchdog = StepWatchdog(budget_s=watchdog_s)
        self.pool = KP.PagePool(n_pages, page_size)
        self.queue: list[_Pending] = []
        self.slots: list[Optional[_Slot]] = [None] * max_batch
        self.finished: list[FinishedRequest] = []
        self.step_no = 0
        self._next_rid = 0
        self._admit_seq = 0
        self._stall = 0              # consecutive barren steps
        self._draining = False
        self.exec_tier = 0           # index into TIERS; sticky demotion
        self.stats = {"decode_steps": 0, "prefills": 0, "preemptions": 0,
                      "generated": 0, "slot_steps": 0,
                      "page_slot_steps": 0,
                      "admit_requeues": 0, "tier_demotions": 0,
                      "deadline_evictions": 0, "preempt_failures": 0,
                      "drained": 0, "shadow_checks": 0,
                      "shadow_mismatches": 0, "golden_probes": 0,
                      "golden_mismatches": 0, "health_evictions": 0,
                      "reclaimed_pages": 0}
        # wall seconds of each decode step run() drove — the
        # inter-token-latency trace bench_serving reduces to p50/p99
        self.decode_step_wall_s: list[float] = []
        self.regime, self.regime_source, self.regime_times, tiles = \
            self._choose_regime(model) if choose_regime else \
            ("paged-spatial", None, {}, None)
        rt = model.rt
        want_ring = self.regime in ("paged-ring", "paged-ring-pipelined")
        want_pipe = self.regime == "paged-ring-pipelined"
        if ((rt.dist_decode_attn != want_ring
             or rt.dist_decode_pipelined != want_pipe)
                and rt.mesh is not None) \
                or tiles != rt.paged_block:
            # the tuner's decision is authoritative in BOTH directions:
            # enable the kv-sharded decode path when a ring regime wins
            # (and its pipelined ppermute combine when that variant
            # wins), disable it when the collective-free regime does,
            # and thread the winning (bq, bkv) tiles so the kernel path
            # executes the schedule the model priced.  The model is a
            # stateless wrapper — rebuilding is free.
            model = type(model)(model.cfg, dataclasses.replace(
                rt, dist_decode_attn=want_ring and rt.mesh is not None,
                dist_decode_pipelined=want_pipe and rt.mesh is not None,
                paged_block=tiles))
        self.model = model
        self._window = int(model.cfg.window or 0)
        self._shadow_fns = None      # lazily jitted tier-1 twin pair
        # compiled step programs of the jitted tiers, keyed by
        # (tier, phase, input avals and shardings), the seconds each
        # took to lower and compile, and the temporary bytes each holds
        # on the device (None where the backend gives none): a step
        # that updates the KV pool in place holds no layer's pool there
        # (``_program``)
        self.programs: dict[tuple, object] = {}
        self.compile_s: dict[tuple, float] = {}
        self.temp_bytes: dict[tuple, Optional[int]] = {}
        self.cache = model.init_paged_cache(n_pages, page_size)
        self._build_exec()
        if model.rt.planner:
            # Pre-plan the steady-state decode DAG at construction so
            # the first serving step never pays the carve: every later
            # decode_step_paged hits the plan memo (and relaunches
            # replay the ("plan", …, phase, paged) disk record —
            # core/schedule_cache.py).  Prefill shapes vary per prompt
            # and are planned (then memoized) on first sight.  A
            # quarantined decode plan (circuit breaker) is skipped —
            # the layer-level dispatch degrades to the hand-wired twin
            # instead of re-carving a denylisted fingerprint.
            from ..core import planner as planner_mod
            if planner_mod.plannable(model.cfg):
                dkey = planner_mod.plan_key(
                    model.cfg, self.max_batch, 1, model.rt.stitch,
                    phase="decode", paged=self.page_size,
                    kv_len=self.n_ctx)
                if not _breaker.is_open(dkey):
                    planner_mod.plan_model(
                        model.cfg, self.max_batch, 1,
                        stitch=model.rt.stitch, phase="decode",
                        paged=self.page_size, kv_len=self.n_ctx)
        self._golden_probe()

    # ------------------------------------------------------------------
    # Tiered execution (fused/planned -> XLA twin -> eager twin)
    # ------------------------------------------------------------------
    def _tier_model(self, tier: int):
        """The model executing at ``tier``.  Tiers 1–2 strip every
        fused/planned/collective decode feature; what remains is the
        plain XLA paged path, bit-identical to tier 0 on f32 configs
        with stitching off (tests/test_serving.py pins that twin
        equality)."""
        if tier == 0:
            return self.model
        rt = self.model.rt
        twin_rt = dataclasses.replace(rt, planner=False,
                                      kernel_ops=False,
                                      dist_decode_attn=False,
                                      dist_decode_pipelined=False)
        return type(self.model)(self.model.cfg, twin_rt)

    def _build_exec(self) -> None:
        m = self._tier_model(self.exec_tier)
        if self.exec_tier < len(TIERS) - 1:
            self._decode = jax.jit(m.decode_step_paged)
            self._prefill = jax.jit(m.prefill_paged)
        else:
            # last resort runs eagerly: no jit pipeline to fail
            self._decode = m.decode_step_paged
            self._prefill = m.prefill_paged

    def _program(self, phase: str, args):
        """The step program that runs ``args`` at the current tier.

        Jitted tiers are lowered and compiled on the first sight of each
        input signature, outside the fallback chain: a step program the
        compiler refuses raises instead of demoting the engine, since a
        refused kernel is a bug and not a transient fault.  A step that
        is not jitted (the eager last tier) runs as it is."""
        fn = self._decode if phase == "decode" else self._prefill
        if not hasattr(fn, "lower"):
            return fn
        key = (self.exec_tier, phase) + tuple(
            (x.shape, x.dtype, getattr(x, "sharding", None))
            for x in jax.tree.leaves(args[1:]))
        prog = self.programs.get(key)
        if prog is None:
            t0 = time.perf_counter()
            prog = fn.lower(*args).compile()
            self.compile_s[key] = time.perf_counter() - t0
            self.programs[key] = prog
            mem = prog.memory_analysis()
            self.temp_bytes[key] = getattr(mem, "temp_size_in_bytes", None)
            if self.verbose:
                print(f"compiled {phase} step (tier {self.exec_tier}) in "
                      f"{self.compile_s[key]:.1f}s; temporaries "
                      f"{self.temp_bytes[key]} B")
        return prog

    def _note_tier_failure(self, phase: str, reason: str) -> None:
        """Quarantine what tier 0 was executing before demoting, so a
        relaunch starts on the degraded path instead of re-failing.
        ``reason`` is recorded verbatim on the breaker denylist entry —
        crashes pass ``"TypeName: msg"``, sentinel mismatches pass a
        shadow/golden-probe description."""
        if self.exec_tier == 0 and self.model.rt.planner:
            from ..core import planner as planner_mod
            if planner_mod.plannable(self.model.cfg):
                dkey = planner_mod.plan_key(
                    self.model.cfg, self.max_batch, 1,
                    self.model.rt.stitch, phase="decode",
                    paged=self.page_size, kv_len=self.n_ctx)
                _breaker.record_failure(
                    dkey, reason=f"engine {phase}: {reason}")
        if self.verbose:
            print(f"serving tier demotion on {phase}: "
                  f"{TIERS[self.exec_tier]} -> "
                  f"{TIERS[self.exec_tier + 1]} ({reason})")

    def _demote_tier0(self, phase: str, reason: str) -> None:
        """Sticky demotion off the configured tier on a *correctness*
        signal (shadow or golden-probe mismatch) — same quarantine +
        rebuild path the crash handler takes, minus the exception."""
        if self.exec_tier != 0:
            return
        self._note_tier_failure(phase, reason)
        self.exec_tier += 1
        self.stats["tier_demotions"] += 1
        self._build_exec()

    def _shadow_exec(self, phase: str, args):
        """Run ``args`` through the tier-1 XLA twin — the reference the
        sentinels compare against.  Jitted lazily and cached: the twin
        pair is tier-independent, so a later demotion does not
        invalidate it."""
        if self._shadow_fns is None:
            m = self._tier_model(1)
            self._shadow_fns = (jax.jit(m.prefill_paged),
                                jax.jit(m.decode_step_paged))
        fn = self._shadow_fns[1] if phase == "decode" \
            else self._shadow_fns[0]
        return fn(*args)

    def _sentinel_check(self, phase: str, args, out):
        """Sampled shadow verification of one tier-0 dispatch
        (docs/reliability.md §Sentinels).  On the sampler's draw the
        SAME pure inputs re-run through the XLA twin; a bitwise
        mismatch (the serving contract is bit-identity — f32, stitching
        off) quarantines the decode plan, demotes stickily to the twin,
        and serves the twin's output (its cache is the one that was
        verified)."""
        spec = _sentinels.active()
        if spec is None:
            return out
        if _faults.armed():
            out = _sentinels.corrupt_if_armed(out, op=f"engine-{phase}")
        if not spec.sample():
            return out
        self.stats["shadow_checks"] += 1
        ref = self._shadow_exec(phase, args)
        ok = _sentinels.outputs_equal(out, ref)
        spec.note_check(ok)
        if ok:
            return out
        self.stats["shadow_mismatches"] += 1
        self._demote_tier0(
            phase, "shadow mismatch: configured output diverged "
                   "from the XLA twin on identical inputs")
        return ref

    def _golden_probe(self) -> None:
        """Golden probe at construction: before any traffic, one canned
        all-inactive decode dispatch (every slot masked to the scratch
        page) runs through the configured tier AND the XLA twin and
        must agree.  Catches a corrupt cached plan/schedule *before* it
        serves a token — a probe mismatch quarantines the decode plan
        and starts the engine on the twin tier.  Outputs are discarded;
        ``self.cache`` is untouched."""
        spec = _sentinels.active()
        if spec is None or not spec.probe:
            return
        self.stats["golden_probes"] += 1
        tokens = jnp.zeros((self.max_batch,), jnp.int32)
        positions = jnp.full((self.max_batch,), -1, jnp.int32)
        table = jnp.asarray(KP.table_array([None] * self.max_batch,
                                           self.max_pages))
        args = (self.params, self.cache, tokens, positions, table)
        prog = self._program("decode", args)
        try:
            out = prog(*args)
            out = _sentinels.corrupt_if_armed(out, op="engine-golden")
            ref = self._shadow_exec("decode", args)
            ok = _sentinels.outputs_equal(out, ref)
        except Exception as e:  # noqa: BLE001 - probe failure = probe
            ok = False          # mismatch; serve from the twin
            if self.verbose:
                print(f"golden probe raised: {type(e).__name__}: {e}")
        spec.note_probe(ok)
        if not ok:
            self.stats["golden_mismatches"] += 1
            self._demote_tier0(
                "decode", "golden probe: canned dispatch diverged "
                          "from the XLA twin before serving")

    def _exec(self, phase: str, *args):
        """Run one prefill/decode dispatch through the fallback chain.

        Inputs are pure (params, cache, host-built arrays), so a failed
        dispatch is retried at the next tier with the SAME inputs —
        degradation changes which program computes the step, never
        which step is computed, which is what keeps chaos-run tokens
        bit-identical (tests/test_reliability.py).  Compiling is not
        part of the chain (``_program``)."""
        with TraceAnnotation("engine.dispatch", phase=phase):
            while True:
                prog = self._program(phase, args)
                try:
                    if self.exec_tier == 0:
                        _faults.fault_point("kernel_dispatch",
                                            op=f"engine-{phase}")
                    _faults.fault_point("engine_step", op=phase,
                                        tier=self.exec_tier)
                    out = prog(*args)
                    if self.exec_tier == 0:
                        out = self._sentinel_check(phase, args, out)
                    return out
                except Exception as e:  # noqa: BLE001 - demote, retry
                    if self.exec_tier >= len(TIERS) - 1:
                        raise
                    self._note_tier_failure(phase,
                                            f"{type(e).__name__}: {e}")
                    self.exec_tier += 1
                    self.stats["tier_demotions"] += 1
                    self._build_exec()

    # ------------------------------------------------------------------
    def _choose_regime(self, model):
        """(regime, cache source, times, (bq, bkv)) for this engine's
        decode shape (q=1 row over the full ``n_ctx`` paged context) —
        served from the persistent schedule cache on warm starts."""
        from ..kernels import ops
        cfg, rt = model.cfg, model.rt
        if rt.mesh is None or not rt.rules.enabled:
            from ..core import api
            tk = api.fuse_attention_paged(
                1, self.n_ctx, cfg.dh, cfg.dh, page_size=self.page_size,
                heads=cfg.n_heads, batch=self.max_batch,
                dtype=str(jnp.dtype(cfg.dtype)), causal=True)
            if self.verbose:
                print(f"paged regime[decode q=1 kv={self.n_ctx}]: "
                      f"paged-spatial (no mesh; "
                      f"{tk.report.best_time * 1e6:.1f}us, "
                      f"schedule from {tk.source})")
            return "paged-spatial", tk.source, \
                {"paged-spatial": tk.report.best_time}, \
                (tk.params.bq, tk.params.bkv)
        choice, _ = ops.paged_attention_regime_choice(
            rt.rules, rt.mesh, batch=self.max_batch,
            q_heads=cfg.n_heads, kv_heads=cfg.n_kv_heads, q_len=1,
            kv_len=self.n_ctx, head_dim=cfg.dh,
            page_size=self.page_size,
            dtype=str(jnp.dtype(cfg.dtype)))
        src = choice.kernel.source
        if self.verbose:
            times = " ".join(f"{k}={v * 1e6:.1f}us"
                             for k, v in choice.times.items())
            print(f"paged regime[decode q=1 kv={self.n_ctx}]: "
                  f"{choice.regime} ({times}; schedule from {src})")
        return choice.regime, src, dict(choice.times), \
            (choice.kernel.params.bq, choice.kernel.params.bkv)

    # ------------------------------------------------------------------
    def submit(self, prompt, max_new: int,
               deadline_steps: Optional[int] = None) -> int:
        """Queue one request; returns its id.  Validated against the
        engine's hard geometry so admission can never dead-lock — the
        pool must cover the WORST-CASE re-admission after a preemption
        (recompute prompt = prompt ++ up to ``max_new - 1`` generated
        tokens, plus the one-page admission headroom), not just the
        request's total footprint.

        deadline_steps: SLO budget in scheduler steps; past it the
        request is evicted with ``outcome="deadline"`` and whatever
        tokens it produced — honest partial results, not a hang."""
        prompt = np.asarray(prompt, np.int32).reshape(-1)
        if max_new < 1:
            raise ValueError("max_new must be >= 1: greedy serving "
                             "always emits the prefill's first token")
        if deadline_steps is not None and deadline_steps < 1:
            raise ValueError(f"bad deadline_steps {deadline_steps}")
        total = len(prompt) + max_new
        if total > self.n_ctx:
            raise ValueError(
                f"prompt {len(prompt)} + gen {max_new} = {total} "
                f"exceeds n_ctx {self.n_ctx}")
        worst = math.ceil((total - 1) / self.page_size) + 1
        if worst > self.pool.n_pages - 1:
            raise ValueError(
                f"request needs up to {worst} pages after a recompute "
                f"but the pool holds {self.pool.n_pages - 1}")
        rid = self._next_rid
        self._next_rid += 1
        deadline = (self.step_no + deadline_steps
                    if deadline_steps is not None else None)
        self.queue.append(_Pending(rid, prompt, len(prompt), [], max_new,
                                   self.step_no, deadline=deadline,
                                   t_submit=time.perf_counter()))
        return rid

    # ------------------------------------------------------------------
    def _admit_one(self) -> bool:
        """Admission policy (docs/serving.md): FIFO head-of-line; the
        head is admitted iff a slot is free AND the pool covers its
        prompt pages plus the slot its first decode token writes —
        allocated UP FRONT, so a freshly admitted request can never be
        the same step's preemption victim (``step()`` grows the
        already-running slots before admitting)."""
        free = [i for i, s in enumerate(self.slots) if s is None]
        if not self.queue or not free:
            return False
        pend = self.queue[0]
        plen = len(pend.prompt)
        if self.pool.n_free < math.ceil((plen + 1) / self.page_size):
            return False
        alloc = KP.RequestPages()
        if not alloc.ensure(plen + 1, self.pool):
            # admission raced the free list (or an injected
            # page-exhaustion fault): leave the head queued and let a
            # later step retry instead of dying — nothing was
            # allocated, so the engine state is untouched
            self.stats["admit_requeues"] += 1
            return False
        s_pad = math.ceil(plen / self.page_size) * self.page_size
        with TraceAnnotation("engine.admit", rid=pend.rid, tokens=plen,
                             padded=s_pad):
            self.queue.pop(0)
            if pend.t_admit is None:
                pend.t_admit = time.perf_counter()
            toks = np.zeros((1, s_pad), np.int32)
            toks[0, :plen] = pend.prompt
            table = jnp.asarray(KP.table_array([alloc], self.max_pages))
            logits, self.cache = self._exec(
                "prefill", self.params, jnp.asarray(toks), self.cache,
                table, jnp.int32(plen))
            self.stats["prefills"] += 1
            if self.model.rt.sentinels and not bool(
                    np.all(np.asarray(_sentinels.healthy(logits[:1])))):
                # activation health monitor: the prefill produced
                # NaN/Inf/exploded logits — evict honestly instead
                # of admitting a request whose every future token is
                # garbage
                alloc.release(self.pool)
                self.stats["health_evictions"] += 1
                self._finish_request(pend, pend.done, "health")
                return True
            tok = int(jnp.argmax(logits[0]))
            if pend.t_first is None:
                pend.t_first = time.perf_counter()
            self.slots[free[0]] = _Slot(
                pend.rid, pend.prompt, pend.base_prompt_len,
                pend.done + [tok], pend.max_new, alloc,
                pend.submit_step, self._admit_seq,
                pend.n_preempted, n_done_admit=len(pend.done),
                deadline=pend.deadline, t_submit=pend.t_submit,
                t_admit=pend.t_admit, t_first=pend.t_first)
            self._admit_seq += 1
            self._maybe_finish(free[0])
            return True

    def _preempt(self, idx: int) -> None:
        """Requeue slot ``idx`` for recompute: its pages go back to the
        pool and its prompt ++ generated tokens become the new prompt
        (greedy decode is deterministic, so the continuation picks up
        where it left off).  Only post-admission tokens are appended —
        after an earlier preemption ``prompt`` already ends with the
        first ``n_done_admit`` generated tokens.

        Retry budget + backoff (docs/reliability.md §4): a request
        preempted more than ``max_preemptions`` times finishes with
        ``outcome="preempt_budget"`` and its partial tokens instead of
        thrashing the pool forever; and while the first recompute
        requeues at the head (FIFO fairness), repeat victims back off
        to the tail so one pathological request cannot livelock
        admission."""
        slot = self.slots[idx]
        slot.alloc.release(self.pool)
        self.slots[idx] = None
        if slot.n_preempted + 1 > self.max_preemptions:
            slot.n_preempted += 1
            self._finish_request(slot, slot.generated, "preempt_budget")
            self.stats["preempt_failures"] += 1
            return
        fresh = slot.generated[slot.n_done_admit:]
        pend = _Pending(
            slot.rid,
            np.concatenate([slot.prompt, np.asarray(fresh, np.int32)]),
            slot.base_prompt_len, list(slot.generated), slot.max_new,
            slot.submit_step, slot.n_preempted + 1,
            deadline=slot.deadline, t_submit=slot.t_submit,
            t_admit=slot.t_admit, t_first=slot.t_first)
        if slot.n_preempted == 0:
            self.queue.insert(0, pend)
        else:
            self.queue.append(pend)
        self.stats["preemptions"] += 1

    def _maybe_finish(self, idx: int) -> None:
        slot = self.slots[idx]
        done_n = len(slot.generated)
        hit_eos = (self.eos_id is not None and done_n
                   and slot.generated[-1] == self.eos_id)
        if done_n >= slot.max_new or hit_eos:
            slot.alloc.release(self.pool)
            self.slots[idx] = None
            self._finish_request(slot, slot.generated, "complete")

    def _grow_or_preempt(self) -> list[int]:
        """Every active slot gets capacity for the position it is about
        to write, preempting newest-first under pressure."""
        while True:
            active = [i for i, s in enumerate(self.slots)
                      if s is not None]
            blocked = [i for i in active
                       if not self.slots[i].alloc.ensure(
                           self.slots[i].pos + 1, self.pool)]
            if not blocked:
                return active
            victim = max(active, key=lambda i: self.slots[i].admit_seq)
            self._preempt(victim)

    def _finish_request(self, req, tokens, outcome: str) -> None:
        """Report ``req`` (a ``_Pending`` or ``_Slot``) finished with
        ``tokens`` under ``outcome``."""
        self.finished.append(FinishedRequest(
            req.rid, req.base_prompt_len, list(tokens), req.submit_step,
            self.step_no, req.n_preempted, outcome=outcome,
            t_submit=req.t_submit, t_admit=req.t_admit,
            t_first=req.t_first, t_finish=time.perf_counter()))
        self.stats["generated"] += len(tokens)

    def _evict_slot(self, idx: int, outcome: str) -> None:
        """Honest eviction: pages back to the pool, partial tokens
        reported under ``outcome``."""
        slot = self.slots[idx]
        slot.alloc.release(self.pool)
        self.slots[idx] = None
        self._finish_request(slot, slot.generated, outcome)

    def _expire_deadlines(self) -> None:
        """SLO-aware eviction: queued or running requests past their
        deadline finish NOW with ``outcome="deadline"`` and whatever
        they have — freeing pages for requests that can still meet
        theirs."""
        kept = []
        for pend in self.queue:
            if pend.deadline is not None and self.step_no > pend.deadline:
                self._finish_request(pend, pend.done, "deadline")
                self.stats["deadline_evictions"] += 1
            else:
                kept.append(pend)
        self.queue[:] = kept
        for i, slot in enumerate(self.slots):
            if (slot is not None and slot.deadline is not None
                    and self.step_no > slot.deadline):
                self._evict_slot(i, "deadline")
                self.stats["deadline_evictions"] += 1

    # ------------------------------------------------------------------
    def step(self) -> list[FinishedRequest]:
        """One scheduler iteration; returns requests finished in it."""
        n_done = len(self.finished)
        self.step_no += 1
        with StepTraceAnnotation("engine.step", step_num=self.step_no), \
                self.watchdog.watch(f"step{self.step_no}"):
            self._step_inner()
        return self.finished[n_done:]

    def _reclaim_window(self) -> None:
        """Sliding-window page reclamation: once a request's next write
        position ``p`` puts every kv slot below ``p - window + 1``
        permanently outside the attention window, the pages wholly
        covered by those slots go back to the pool (kv_pages.py
        ``reclaim_below``).  Bit-identical to keeping them — the window
        mask already rejected those slots — but the freed pages fund
        admission and growth, so long windowed generations stop
        monopolising the pool."""
        if self._window <= 0:
            return
        for slot in self.slots:
            if slot is None:
                continue
            self.stats["reclaimed_pages"] += slot.alloc.reclaim_below(
                slot.pos + 1 - self._window, self.pool)

    def _step_inner(self) -> None:
        with TraceAnnotation("engine.schedule"):
            self._expire_deadlines()
            self._reclaim_window()
            # running slots take their growth pages BEFORE admission
            # sees the free count, and admission reserves each fresh
            # request's first decode slot — so the second growth pass
            # below can only preempt on genuine cross-step pressure,
            # never a request admitted this step
            self._grow_or_preempt()
        admitted = False
        if not self._draining:
            while self._admit_one():
                admitted = True
        with TraceAnnotation("engine.schedule"):
            active = self._grow_or_preempt()
        if not active:
            if self.queue and not admitted and not self._draining:
                # barren step with work queued: count it, and only die
                # after stall_limit in a row — a transient allocation
                # failure (free-list race, injected exhaustion)
                # recovers on a later step, a genuine geometry stall
                # does not
                self._stall += 1
                if self._stall > self.stall_limit:
                    raise RuntimeError(
                        "scheduler stalled: pool cannot cover the "
                        "queue head even when idle — shrink prompts "
                        "or grow n_pages")
            return
        self._stall = 0

        with TraceAnnotation("engine.inputs") as span:
            tokens = np.zeros((self.max_batch,), np.int32)
            positions = np.full((self.max_batch,), -1, np.int32)
            ctx_tokens = 0
            for i in active:
                tokens[i] = self.slots[i].generated[-1]
                positions[i] = pos = self.slots[i].pos
                ctx_tokens += pos + 1
            table = jnp.asarray(KP.table_array(
                [s.alloc if s is not None else None for s in self.slots],
                self.max_pages))
            inputs = (jnp.asarray(tokens), jnp.asarray(positions), table)
            span.set_metadata(active=len(active), ctx_tokens=ctx_tokens)
        logits, self.cache = self._exec(
            "decode", self.params, self.cache, *inputs)
        with TraceAnnotation("engine.sample"):
            nxt = np.asarray(jnp.argmax(logits, axis=-1))
            health = np.asarray(_sentinels.healthy(logits)) \
                if self.model.rt.sentinels else None
        with TraceAnnotation("engine.commit"):
            self.stats["decode_steps"] += 1
            self.stats["slot_steps"] += self.max_batch
            for i in active:
                slot = self.slots[i]
                self.stats["page_slot_steps"] += slot.alloc.n_live
                if health is not None and not health[i]:
                    # activation health monitor: this slot's logits
                    # went NaN/Inf/exploded — its kv is poisoned, evict
                    # with the partial tokens instead of sampling from
                    # garbage
                    self.stats["health_evictions"] += 1
                    self._evict_slot(i, "health")
                    continue
                slot.generated.append(int(nxt[i]))
                self._maybe_finish(i)

    # ------------------------------------------------------------------
    def drain(self, deadline: Optional[float] = None,
              max_steps: Optional[int] = None) -> list[FinishedRequest]:
        """Graceful stop: admission closes, in-flight requests run to
        completion, and whatever cannot finish inside ``deadline``
        wall-seconds (or ``max_steps`` scheduler steps) is evicted with
        ``outcome="drained"`` and its partial tokens.  Queued requests
        that never reached a slot are failed immediately the same way
        — honestly, not silently dropped.  Returns the requests that
        finished (by any outcome) during the drain."""
        n_done = len(self.finished)
        self._draining = True
        try:
            def _fail_queue():
                for pend in self.queue:
                    self._finish_request(pend, pend.done, "drained")
                    self.stats["drained"] += 1
                self.queue.clear()

            _fail_queue()
            t0 = time.perf_counter()
            steps = 0
            while any(s is not None for s in self.slots):
                if deadline is not None \
                        and time.perf_counter() - t0 >= deadline:
                    break
                if max_steps is not None and steps >= max_steps:
                    break
                self.step()
                steps += 1
                _fail_queue()   # preemption refugees drain too
            for i, slot in enumerate(self.slots):
                if slot is not None:
                    self._evict_slot(i, "drained")
                    self.stats["drained"] += 1
        finally:
            self._draining = False
        return self.finished[n_done:]

    # ------------------------------------------------------------------
    def reset(self) -> None:
        """Zero the counters between ``run()`` calls (benchmarks warm
        the compiled steps with a throwaway workload first).

        Calling it with requests in flight — formerly a hard
        ``RuntimeError`` — now emits a ``DeprecationWarning`` and
        drains immediately (``drain(deadline=0)``): in-flight work is
        evicted honestly as ``outcome="drained"`` before the counters
        zero."""
        if self.queue or any(s is not None for s in self.slots):
            warnings.warn(
                "reset() with requests in flight is deprecated; "
                "draining them first — call drain() explicitly to "
                "control the deadline", DeprecationWarning,
                stacklevel=2)
            self.drain(deadline=0.0)
        assert self.pool.n_free == self.pool.n_pages - 1
        self.finished = []
        self.step_no = 0
        self._next_rid = 0
        self._stall = 0
        self.watchdog.reset()
        self.decode_step_wall_s = []
        for k in self.stats:
            self.stats[k] = 0

    def run(self, requests) -> tuple[list[FinishedRequest], dict]:
        """Drive ``step()`` until every submitted request finishes.

        requests: iterable of (prompt, max_new).  Returns results in
        submission order plus a stats dict (wall seconds, tokens/s, and
        the step counters).
        """
        for prompt, max_new in requests:
            self.submit(prompt, max_new)
        t0 = time.perf_counter()
        while self.queue or any(s is not None for s in self.slots):
            before = self.stats["decode_steps"]
            ts = time.perf_counter()
            self.step()
            if self.stats["decode_steps"] > before:
                # a step that ran the batched decode: its wall time is
                # the inter-token latency every active slot just paid
                self.decode_step_wall_s.append(time.perf_counter() - ts)
        dt = time.perf_counter() - t0
        out = sorted(self.finished, key=lambda r: r.rid)
        stats = dict(self.stats)
        stats["wall_s"] = dt
        stats["tok_per_s"] = stats["generated"] / dt if dt > 0 else 0.0
        stats["regime"] = self.regime
        stats["exec_tier"] = TIERS[self.exec_tier]
        stats["watchdog_breaches"] = self.watchdog.breaches
        stats["max_step_s"] = self.watchdog.max_step_s
        stats["decode_step_wall_s"] = list(self.decode_step_wall_s)
        return out, stats
