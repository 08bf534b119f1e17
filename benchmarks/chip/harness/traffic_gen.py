"""The one seeded generator that every traffic mix file drives.

A mix is data (``traffic/<mix>.json``).  Its laws are turned into a
fixed block of sizes and gaps that does not depend on the seed:
lengths at the stratified quantiles of their lognormal law, gaps drawn
once from a fixed stream and rescaled to the block's exact mean.  The
block's prompts and answers are laid out in a fixed mixed order (ranks
from a Halton sequence in bases 2 and 3), so prompt and answer lengths
are independent of each other and every stretch of a few dozen
consecutive requests holds a near-even share of short and long ones.
The run's seed picks where in that order the run starts (a rotation)
and draws the token ids.  So every seed sends the same work in another
order, and a window of a few hundred requests sees the same work
whatever the seed: with a fresh shuffle per block, output tokens/s of
the chat cell differed by 6% between seeds, and two runs of one seed
agreed (PERF.md).

Open loop: requests are due at the running sum of the gaps, at the
cell's fixed rate.  The gaps keep their drawn order for every seed, so
each seed meets the same bursts at the same times and only which
request arrives in a burst changes: the p95 of a few hundred requests
is set by the few largest bursts, and with a burst pattern drawn anew
per run the knee sweep read p95s from 0.2 to 0.9 s at rates 10% apart
(PERF.md).

Closed loop: ``clients`` callers each send their next request when the
last one completes.  A request lasts thousands of decode steps, far
longer than a window, so the loop starts in its steady state rather
than from empty: each client's first request is the one it is in the
middle of at a random moment.  Its answer length is length-biased (a
client spends time on a request in proportion to its answer), the share
of it already served is uniform, and that request is sent as one prompt
holding its own prompt plus the tokens already served (the context,
snapped to the mix's ``start`` lengths) with the rest of the answer as
its budget.  So the window's contexts, and the pool in use, follow the
mix's declared lengths from its first step.
"""
from __future__ import annotations

import dataclasses
import statistics
import zlib

import numpy as np

BLOCK = 128          # requests per block of the fixed multiset


def _fixed_rng(mix: dict, what: str) -> np.random.Generator:
    tag = zlib.crc32(f"{mix['name']}/{what}".encode())
    return np.random.Generator(np.random.PCG64(tag))


def snap(x: float, points) -> int:
    """The nearest of ``points`` (ties go to the smaller)."""
    return int(min(sorted(points), key=lambda p: (abs(p - x), p)))


def lognormal_quantiles(median: float, sigma: float, n: int) -> np.ndarray:
    nd = statistics.NormalDist()
    z = np.array([nd.inv_cdf((j + 0.5) / n) for j in range(n)])
    return median * np.exp(sigma * z)


def prompt_block(mix: dict) -> np.ndarray:
    p = mix["prompt"]
    raw = lognormal_quantiles(p["median"], p["sigma"], BLOCK)
    return np.array([snap(x, p["snap"]) for x in raw], np.int64)


def output_block(mix: dict) -> np.ndarray:
    o = mix["output"]
    raw = lognormal_quantiles(o["median"], o["sigma"], BLOCK)
    return np.clip(np.rint(raw), o["min"], o["max"]).astype(np.int64)


def gap_block(mix: dict, rate: float) -> np.ndarray:
    """Inter-arrival gaps (s) of a Gamma renewal process with the mix's
    coefficient of variation, rescaled so the block's mean is exactly
    ``1 / rate``."""
    a = mix["arrivals"]
    if a["process"] != "gamma":
        raise ValueError(f"unknown arrival process {a['process']!r}")
    shape = 1.0 / a["cv"] ** 2
    g = _fixed_rng(mix, "gaps").gamma(shape, 1.0, BLOCK)
    return g / g.mean() / rate


def _radical_inverse(i: int, base: int) -> float:
    x, f = 0.0, 1.0 / base
    while i:
        i, d = divmod(i, base)
        x += d * f
        f /= base
    return x


def mixed_ranks(base: int) -> np.ndarray:
    """For each position of a block, the rank (0 = shortest) of the size
    it takes: the ranks of the block's Halton points in ``base``."""
    pts = [_radical_inverse(i, base) for i in range(1, BLOCK + 1)]
    return np.argsort(np.argsort(pts))


@dataclasses.dataclass(frozen=True)
class Request:
    index: int           # order of issue; the token ids follow from it
    prompt_len: int
    max_new: int
    due: float           # seconds after traffic start (open loop)


class Traffic:
    """Requests of one run, in order of issue.

    ``next(i)`` gives the i-th request; ``tokens(req, vocab)`` its
    prompt ids.  Where the run starts in the block comes from the
    seed."""

    def __init__(self, mix: dict, seed: int, rate: float | None = None):
        self.mix = mix
        self.seed = int(seed)
        self.loop = mix["loop"]
        self._outputs = output_block(mix)
        self._prompt_at = prompt_block(mix)[mixed_ranks(2)]
        self._output_at = self._outputs[mixed_ranks(3)]
        self._start = int(np.random.Generator(np.random.PCG64(
            np.random.SeedSequence([self.seed, 1]))).integers(BLOCK))
        if self.loop == "open":
            if not rate or rate <= 0:
                raise ValueError("an open-loop mix needs a rate")
            self._gaps = gap_block(mix, rate)
        elif self.loop != "closed":
            raise ValueError(f"unknown loop {self.loop!r}")
        self._t = [0.0]       # running due time at the start of request i

    def _sizes(self, i: int) -> tuple[int, int, float]:
        j = (self._start + i) % BLOCK
        gap = float(self._gaps[i % BLOCK]) if self.loop == "open" else 0.0
        return int(self._prompt_at[j]), int(self._output_at[j]), gap

    def next(self, i: int) -> Request:
        while len(self._t) <= i:
            _, _, gap = self._sizes(len(self._t) - 1)
            self._t.append(self._t[-1] + gap)
        plen, out, _ = self._sizes(i)
        return Request(i, plen, out, self._t[i])

    def steady_start(self, n_ctx: int) -> list[tuple[int, int]]:
        """Closed loop: each client's first request as (context, budget),
        from the residual-life law: a length-biased answer length
        (stratified over the block's total), a stratified uniform share
        of it already served, and a prompt at a stratified quantile of
        the block, each paired by a fixed draw.  The context (prompt plus
        the share served) snaps to the mix's ``start`` lengths; the budget
        is the answer's rest, cut so the request fits ``n_ctx``.  The
        seed only decides which client gets which pair."""
        n = int(self.mix["clients"])
        outs = np.sort(self._outputs)
        cum = np.cumsum(outs) / outs.sum()
        answers = [int(outs[np.searchsorted(cum, (c + 0.5) / n)])
                   for c in range(n)]
        served = (_fixed_rng(self.mix, "residual").permutation(n) + 0.5) / n
        prompts = np.sort(prompt_block(self.mix))[
            (_fixed_rng(self.mix, "start_prompt").permutation(n) * BLOCK)
            // n]
        start = self.mix["start"]["snap"]
        pairs = []
        for a, f, p in zip(answers, served, prompts):
            done = int(a * f)
            ctx = snap(int(p) + done, start)
            pairs.append((ctx, max(1, min(a - done, n_ctx - ctx))))
        order = np.random.Generator(np.random.PCG64(
            np.random.SeedSequence([self.seed, 2]))).permutation(n)
        return [pairs[k] for k in order]

    def tokens(self, req: Request, vocab: int) -> np.ndarray:
        ss = np.random.SeedSequence([self.seed, 3, req.index])
        return np.random.Generator(np.random.PCG64(ss)).integers(
            0, vocab, req.prompt_len, dtype=np.int32)

    def warmup_tokens(self, length: int, vocab: int) -> np.ndarray:
        ss = np.random.SeedSequence([self.seed, 4, length])
        return np.random.Generator(np.random.PCG64(ss)).integers(
            0, vocab, length, dtype=np.int32)
