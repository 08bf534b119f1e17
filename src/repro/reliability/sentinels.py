"""Correctness sentinels: online detection of silently wrong answers.

The crash-path reliability layer (``faults.py`` / ``breaker.py`` /
the tiered engine executor) only reacts when something *raises*.  A
miscompiled Pallas lowering, a schedule replayed on hardware it was
not tuned for, or a stitched-epilogue numerics bug serves wrong tokens
with no exception — and the breaker never trips.  This module turns
"wrong answer" into a detectable, quarantinable event using the one
asset every fused unit in this repo already has: a bit-identical
XLA/eager twin (the differential-test contract, docs/design.md).

Three detectors, all feeding the existing per-fingerprint breaker:

* **sampled shadow verification** — :func:`shadow_kernel` re-runs the
  reference twin on ~1/N of guarded dispatches (a seeded sha256 draw
  over the dispatch ordinal, the exact design of
  ``faults.FaultSpec``) and compares within per-dtype tolerance; a
  mismatch records a breaker failure against the fingerprint, so the
  entry is quarantined on disk and the *current* call already returns
  the twin's (correct) output.
* **golden probes** — the serving engine runs one canned input through
  its tier-0 executable vs the XLA twin before serving traffic, and
  ``core.api`` numerically probes a warm cache entry whose stored host
  fingerprint differs from the current host before trusting the
  replay (``schedule_cache.host_fingerprint``).
* **activation health** — :func:`healthy` is a jit-compatible
  NaN/Inf/magnitude check the engine applies to step logits when
  ``Runtime(sentinels=True)``; an unhealthy slot is evicted with the
  honest per-request outcome ``"health"``.

Sampling determinism mirrors ``faults.py``: whether dispatch ordinal
``i`` is shadow-verified is a pure function of ``(seed, i)`` — no wall
clock, no global RNG — so a detection replays bit-identically and a
failing seed is a reproducer.  Nothing here is armed by default:
:func:`active` returns ``None`` and every hook is a cheap early-out
until :func:`enable` (or the :func:`shadowing` context manager) arms a
:class:`SentinelSpec`.

The matching fault class is ``faults.inject("wrong_answer", ...)``:
instead of raising, it *perturbs* a fused output at the guarded seams
(:func:`corrupt_if_armed`), modelling exactly the silent corruption
the crash-path faults cannot express.  See docs/reliability.md
("Sentinels") for the tolerance policy and probe semantics.
"""
from __future__ import annotations

import contextlib
import dataclasses
import hashlib
import threading
from typing import Callable, Iterator, Optional

import jax
import jax.numpy as jnp
import numpy as np

from . import faults as _faults

__all__ = [
    "SentinelSpec", "DEFAULT_RATE", "HEALTH_MAX_ABS", "TOLERANCES",
    "enable", "disable", "active", "shadowing",
    "corrupt_if_armed", "shadow_kernel", "outputs_close",
    "outputs_equal", "healthy",
]

#: Default shadow-verification sampling rate: ~1 in 64 dispatches.
DEFAULT_RATE = 1.0 / 64

#: Activation-health bound: any |logit| at or past this is an
#: explosion (qk-norm'd smoke configs peak around |logit| ~ 1e1).
HEALTH_MAX_ABS = 1e4

#: Per-dtype (rtol, atol) for kernel-vs-twin comparison.  f32 gets a
#: small tolerance because a fused kernel's accumulation order differs
#: from the XLA twin's; the *engine* twin comparison instead passes
#: ``bitwise_f32=True`` — the serving contract is bit-identity there
#: (f32, stitching off; docs/serving.md).
TOLERANCES = {
    "float64": (1e-12, 1e-12),
    "float32": (1e-5, 1e-6),
    "bfloat16": (2e-2, 2e-2),
    "float16": (2e-3, 2e-3),
}


#: Ordinals per precomputed draw block: :meth:`SentinelSpec.sample`
#: sits on every guarded dispatch, so its hot path must be an integer
#: increment plus a set lookup — the sha256 drawing work runs once per
#: ``_BLOCK`` ordinals (and for block 0 at construction, off the
#: serving path), producing bit-identical draws to hashing per call.
_BLOCK = 512


@dataclasses.dataclass
class SentinelSpec:
    """One armed sentinel configuration plus its observability counters.

    ``rate`` is the shadow-sampling probability; drawing mirrors
    ``faults.FaultSpec``: dispatch ordinal ``n_seen`` is verified iff
    ``sha256(f"{seed}:shadow:{n_seen}")`` maps below ``rate``.
    ``probe=False`` disarms the construction/warm-load golden probes
    while keeping shadow sampling (the bench overhead lane uses it to
    isolate steady-state cost)."""

    rate: float = DEFAULT_RATE
    seed: int = 0
    probe: bool = True
    n_seen: int = 0           # dispatches observed at shadow seams
    n_checked: int = 0        # dispatches actually shadow-verified
    n_mismatched: int = 0     # shadow comparisons that diverged
    n_probed: int = 0         # golden probes run (engine + warm-load)
    n_probe_mismatched: int = 0
    _block: int = dataclasses.field(default=-1, repr=False,
                                    compare=False)
    _draws: frozenset = dataclasses.field(default=frozenset(),
                                          repr=False, compare=False)

    def __post_init__(self) -> None:
        if 0.0 < self.rate < 1.0:
            self._block, self._draws = 0, self._draws_for(0)

    def _draws_for(self, block: int) -> frozenset:
        lo = block * _BLOCK
        draws = set()
        for n in range(lo, lo + _BLOCK):
            blob = f"{self.seed}:shadow:{n}".encode()
            u = int.from_bytes(hashlib.sha256(blob).digest()[:8], "big")
            if u / 2.0 ** 64 < self.rate:
                draws.add(n)
        return frozenset(draws)

    def note_check(self, ok: bool) -> None:
        """Count one shadow comparison and its outcome (engine seam —
        the kernel seam counts inside :func:`shadow_kernel`)."""
        with _LOCK:
            self.n_checked += 1
            if not ok:
                self.n_mismatched += 1

    def note_probe(self, ok: bool) -> None:
        """Count one golden probe and its outcome."""
        with _LOCK:
            self.n_probed += 1
            if not ok:
                self.n_probe_mismatched += 1

    def sample(self) -> bool:
        """Advance the dispatch ordinal; True iff this one is verified."""
        with _LOCK:
            n = self.n_seen
            self.n_seen += 1
            if self.rate >= 1.0:
                return True
            if self.rate <= 0.0:
                return False
            block = n // _BLOCK
            if block != self._block:
                self._block = block
                self._draws = self._draws_for(block)
            return n in self._draws


_SPEC: Optional[SentinelSpec] = None
_LOCK = threading.Lock()


def enable(rate: float = DEFAULT_RATE, *, seed: int = 0,
           probe: bool = True) -> SentinelSpec:
    """Arm the sentinels process-wide; replaces any armed spec."""
    global _SPEC
    if not 0.0 <= rate <= 1.0:
        raise ValueError(f"rate must be in [0, 1], got {rate}")
    spec = SentinelSpec(rate=rate, seed=seed, probe=probe)
    with _LOCK:
        _SPEC = spec
    return spec


def disable() -> None:
    global _SPEC
    with _LOCK:
        _SPEC = None


def active() -> Optional[SentinelSpec]:
    return _SPEC


@contextlib.contextmanager
def shadowing(rate: float = DEFAULT_RATE, *, seed: int = 0,
              probe: bool = True) -> Iterator[SentinelSpec]:
    """Arm the sentinels for the duration of a ``with`` block."""
    spec = enable(rate, seed=seed, probe=probe)
    try:
        yield spec
    finally:
        disable()


# ---------------------------------------------------------------------
# silent-corruption fault seam
# ---------------------------------------------------------------------

def _corrupt(out):
    """Shape/dtype-preserving perturbation of every inexact leaf.

    A one-slot roll along the last axis changes the argmax of a logits
    row and the values of a KV page while keeping the pytree structure
    valid — the corruption a crashing fault cannot model.  Pure jnp, so
    it is trace-safe: armed under ``jax.jit`` it bakes into the
    compiled step, which is exactly what a miscompiled kernel does.
    """
    def leaf(a):
        if hasattr(a, "dtype") and jnp.issubdtype(a.dtype, jnp.inexact):
            return jnp.roll(a, 1, axis=-1)
        return a
    return jax.tree.map(leaf, out)


def corrupt_if_armed(out, *, op: str):
    """The ``wrong_answer`` fault seam: perturb ``out`` iff armed+fired.

    Free when the fault registry is empty (``faults.check`` fast path).
    """
    if _faults.check("wrong_answer", op=op):
        return _corrupt(out)
    return out


# ---------------------------------------------------------------------
# comparison
# ---------------------------------------------------------------------

def _has_tracer(out) -> bool:
    return any(isinstance(leaf, jax.core.Tracer)
               for leaf in jax.tree.leaves(out))


def outputs_close(got, want, *, bitwise_f32: bool = False) -> bool:
    """Per-dtype comparison of two output pytrees (``TOLERANCES``).

    ``bitwise_f32=True`` demands exact equality for f32/f64 leaves —
    the serving twin contract (f32, stitching off) is bit-identity, so
    the engine's shadow comparison must not forgive reordered
    accumulation the way the kernel-vs-reference comparison does.
    """
    got_l, got_def = jax.tree.flatten(got)
    want_l, want_def = jax.tree.flatten(want)
    if got_def != want_def or len(got_l) != len(want_l):
        return False
    for g, w in zip(got_l, want_l):
        g, w = np.asarray(g), np.asarray(w)
        if g.shape != w.shape or g.dtype != w.dtype:
            return False
        if not np.issubdtype(g.dtype, np.inexact):
            if not np.array_equal(g, w):
                return False
            continue
        name = jnp.dtype(g.dtype).name
        if bitwise_f32 and name in ("float32", "float64"):
            if not np.array_equal(g, w, equal_nan=True):
                return False
            continue
        rtol, atol = TOLERANCES.get(name, (1e-5, 1e-6))
        if not np.allclose(np.asarray(g, np.float64),
                           np.asarray(w, np.float64),
                           rtol=rtol, atol=atol, equal_nan=True):
            return False
    return True


def _eq_leaves(got_leaves, want_leaves):
    oks = [jnp.array_equal(
        g, w, equal_nan=bool(jnp.issubdtype(jnp.asarray(g).dtype,
                                            jnp.inexact)))
        for g, w in zip(got_leaves, want_leaves)]
    return jnp.all(jnp.stack(oks)) if oks else jnp.bool_(True)


_eq_jit = jax.jit(_eq_leaves)


def outputs_equal(got, want) -> bool:
    """Bitwise pytree equality, reduced on device (single scalar sync).

    The serving engine's steady-state shadow comparison: its contract
    is bit-identity (f32, stitching off), so the whole comparison can
    stay a device-side reduction — :func:`outputs_close` would instead
    materialize host copies of every leaf (multi-MB of KV cache per
    sampled check), and on a CPU host that memory traffic costs more
    than the twin execution itself.  Structure/shape/dtype mismatches
    are decided host-side from metadata, with no transfer.
    """
    got_l, got_def = jax.tree.flatten(got)
    want_l, want_def = jax.tree.flatten(want)
    if got_def != want_def or len(got_l) != len(want_l):
        return False
    for g, w in zip(got_l, want_l):
        if getattr(g, "shape", None) != getattr(w, "shape", None) or \
                getattr(g, "dtype", None) != getattr(w, "dtype", None):
            return False
    return bool(_eq_jit(got_l, want_l))


def shadow_kernel(fingerprint: tuple, out, ref_fn: Callable[[], object],
                  *, bitwise_f32: bool = False):
    """Sampled shadow verification for a guarded fused dispatch.

    Called by the kernel tails (``kernels/ops.py::guarded``) and the
    fused paged-attention branch (``models/layers.py``) with the fused
    output and a thunk for the XLA twin.  Early-outs: sentinels not
    armed, tracing (a ``jax.core.Tracer`` has no concrete value to
    compare — the engine-level sentinel covers jitted steps), or the
    seeded sampler skipping this ordinal.  On mismatch the fingerprint
    takes a breaker failure (quarantined on disk like a crash would
    be) and the twin's output is returned — the caller serves the
    correct value on the very dispatch that detected the corruption.
    """
    spec = _SPEC
    if spec is None or _has_tracer(out):
        return out
    if not spec.sample():
        return out
    with _LOCK:
        spec.n_checked += 1
    ref = ref_fn()
    if outputs_close(out, ref, bitwise_f32=bitwise_f32):
        return out
    with _LOCK:
        spec.n_mismatched += 1
    from . import breaker as _breaker
    _breaker.record_failure(
        fingerprint,
        reason="shadow mismatch: fused output diverged from XLA twin")
    return ref


# ---------------------------------------------------------------------
# activation health
# ---------------------------------------------------------------------

def healthy(logits, max_abs: float = HEALTH_MAX_ABS):
    """Per-row activation health: finite and below the explosion bound.

    ``logits`` is ``(..., vocab)``; returns a boolean array over the
    leading dims.  Pure jnp — callable inside or outside ``jax.jit``.
    """
    x = jnp.asarray(logits)
    finite = jnp.all(jnp.isfinite(x), axis=-1)
    bounded = jnp.max(jnp.abs(x), axis=-1) < max_abs
    return jnp.logical_and(finite, bounded)
