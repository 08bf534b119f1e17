"""Decoder-only LM covering 8 of the 10 assigned architectures
(qwen3 / granite-20b / granite-34b / codeqwen / mixtral / olmoe /
mamba2 / recurrentgemma / pixtral-backbone).

Layer layout is a repeating `pattern` of temporal-mix block types
("attn" | "mamba" | "rglru"); homogeneous stacks scan over stacked
params (compile-time O(1) in depth).  A trailing remainder (n_layers %
len(pattern)) runs unscanned — RecurrentGemma's 26 = 8x(R,R,A) + (R,R).

API (shared with whisper.EncDec):
    init_params(rng) / abstract_params()
    param_specs()                  -> PartitionSpec pytree
    forward(params, batch)         -> logits           (training path)
    loss(params, batch)            -> scalar
    init_cache(batch, max_len)     / abstract_cache()
    prefill(params, batch)         -> (last_logits, cache)
    decode_step(params, cache, tokens, pos) -> (logits, cache)
"""
from __future__ import annotations

import dataclasses
import functools
import math
from typing import Any, Optional

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from ..core import planner as planner_mod
from ..dist.sharding import Rules, batch_placement, constrain
from ..reliability import breaker as _breaker
from . import layers as L
from .config import ModelConfig


@dataclasses.dataclass(frozen=True)
class Runtime:
    """Execution context threaded through model code."""

    rules: Rules = dataclasses.field(default_factory=Rules.disabled)
    mesh: Optional[jax.sharding.Mesh] = None
    bkv: int = 512          # MCFuser-tuned KV streaming block
    remat: bool = True      # activation checkpointing on scanned blocks
    remat_policy: Optional[str] = None  # None=full | "dots" | "none"
    dist_decode_attn: bool = False  # decode attention over a
    # seq-sharded KV cache via per-shard partial softmax (no cache
    # gather) — SS Perf hillclimb #1; enable for production serving.
    dist_decode_pipelined: bool = False  # run the dist-decode combine
    # as the per-hop ppermute ring (paged-ring-pipelined regime,
    # docs/design.md §7) instead of the serial pmax/psum; serving
    # threads the tuner's per-shape pick here.
    unroll: bool = False    # unroll all scans (dry-run cost accounting:
    # XLA HloCostAnalysis counts while bodies ONCE; trip-count-1 loops
    # restore correct flops/bytes in cost_analysis())
    kernel_ops: bool = False  # route cache-free attention through
    # kernels.ops: the MCFuser-tuned kernel, shard_map-dispatched per
    # shard when a mesh is set (docs/design.md §7); off by default —
    # the streaming XLA twin remains the portable path.
    paged_block: Optional[tuple] = None  # (bq, bkv) tiles the paged
    # regime search picked — serving.engine threads them so the kernel
    # path executes the schedule the tuner priced (docs/serving.md).
    planner: bool = False   # run attention blocks from core.planner
    # output — chains carved + glue stitched from the config alone,
    # zero hand-specified chains (docs/planner.md).  Covers the
    # cache-free forward AND paged serving (prefill_paged /
    # decode_step_paged trace phase-keyed DAGs with an explicit
    # kv_write node); contiguous-cache decode and non-plannable
    # configs fall back to the hand-wired path.
    stitch: bool = True     # planner mode only: stitch memory-bound
    # glue into carved chains as prologue/epilogue (FusionStitching).
    # False keeps every glue op standalone — bit-identical to the
    # hand-wired layer, which tests/test_planner.py asserts.
    sentinels: bool = False  # arm the in-step activation health
    # monitors (reliability/sentinels.py::healthy): the serving engine
    # checks prefill/decode logits for NaN/Inf/explosion and evicts
    # the offending slot with the honest "health" outcome.  Off by
    # default — the check is cheap but not free on the decode path.


def _layer_types(cfg: ModelConfig) -> tuple[list[str], int, list[str]]:
    pat = list(cfg.pattern)
    n_super = cfg.n_layers // len(pat)
    rem = [pat[i] for i in range(cfg.n_layers - n_super * len(pat))]
    return pat, n_super, rem


def _chunk_len(s: int, target: int = 512) -> int:
    """Largest divisor of s that is <= target."""
    best = 1
    for c in range(1, min(s, target) + 1):
        if s % c == 0:
            best = c
    return best


def chunked_ce(hidden: jax.Array, unembed_w: jax.Array, labels: jax.Array,
               tied: bool, unroll: bool = False) -> jax.Array:
    """Cross-entropy scanning over sequence chunks so the (B, S, V)
    logits tensor never materializes (256k-vocab archs would otherwise
    spend GBs per device on it); jax.checkpoint makes the backward
    recompute each chunk's logits instead of storing them.

    hidden: (B, S, D) post-final-norm; labels: (B, S), -100 masked.
    """
    b, s, d = hidden.shape
    c = _chunk_len(s)
    nc = s // c
    hc = jnp.moveaxis(hidden.reshape(b, nc, c, d), 1, 0)
    lc = jnp.moveaxis(labels.reshape(b, nc, c), 1, 0)

    @jax.checkpoint
    def body(carry, xs):
        xch, lch = xs
        if tied:
            logits = jnp.einsum("bcd,vd->bcv", xch, unembed_w)
        else:
            logits = jnp.einsum("bcd,dv->bcv", xch, unembed_w)
        lf = logits.astype(jnp.float32)
        lse = jax.nn.logsumexp(lf, axis=-1)
        tgt = jnp.take_along_axis(
            lf, jnp.maximum(lch, 0)[..., None], axis=-1)[..., 0]
        mask = (lch >= 0).astype(jnp.float32)
        tot, cnt = carry
        return (tot + jnp.sum((lse - tgt) * mask),
                cnt + jnp.sum(mask)), None

    (tot, cnt), _ = jax.lax.scan(body, (jnp.float32(0), jnp.float32(0)),
                                 (hc, lc), unroll=nc if unroll else 1)
    return tot / jnp.maximum(cnt, 1.0)


def _plan_phase(seq: int, page_size: Optional[int],
                kv_len: Optional[int]) -> dict:
    """``core.planner.plan_model``'s phase keywords: the cache-free
    forward, or prefill/decode (by row count) over a paged cache."""
    if page_size is None:
        return {}
    return dict(phase="prefill" if seq > 1 else "decode", paged=page_size,
                kv_len=kv_len)


class LM:
    def __init__(self, cfg: ModelConfig, rt: Optional[Runtime] = None):
        self.cfg = cfg
        self.rt = rt or Runtime()

    # ------------------------------------------------------------------
    # params
    # ------------------------------------------------------------------
    def _init_layer(self, rng, kind: str) -> dict:
        cfg = self.cfg
        r = jax.random.split(rng, 4)
        p: dict[str, Any] = {"ln1": L.init_norm(cfg)}
        if kind == "attn":
            p["mix"] = L.init_attention(r[0], cfg)
        elif kind == "mamba":
            p["mix"] = L.init_mamba(r[0], cfg)
        elif kind == "rglru":
            p["mix"] = L.init_rglru(r[0], cfg)
        else:
            raise ValueError(kind)
        if cfg.d_ff > 0:
            p["ln2"] = L.init_norm(cfg)
            p["ff"] = (L.init_moe(r[1], cfg) if cfg.moe
                       else L.init_mlp(r[1], cfg))
        return p

    def _layer_specs(self, kind: str) -> dict:
        cfg, rules = self.cfg, self.rt.rules
        n_model = self.rt.mesh.shape[rules.model] \
            if (self.rt.mesh and rules.model) else 16
        s: dict[str, Any] = {"ln1": L.specs_norm(cfg, rules)}
        if kind == "attn":
            s["mix"] = L.specs_attention(cfg, rules)
        elif kind == "mamba":
            s["mix"] = L.specs_mamba(cfg, rules)
        else:
            s["mix"] = L.specs_rglru(cfg, rules)
        if cfg.d_ff > 0:
            s["ln2"] = L.specs_norm(cfg, rules)
            s["ff"] = (L.specs_moe(cfg, rules, n_model) if cfg.moe
                       else L.specs_mlp(cfg, rules))
        return s

    def init_params(self, rng) -> dict:
        cfg = self.cfg
        pat, n_super, rem = _layer_types(cfg)
        keys = jax.random.split(rng, 4 + len(rem))
        dt = jnp.dtype(cfg.dtype)
        params: dict[str, Any] = {
            "embed": L.dense_init(keys[0], (cfg.vocab, cfg.d_model), dt,
                                  scale=0.02),
            "final_norm": L.init_norm(cfg),
        }
        if not cfg.use_rope:
            params["pos_embed"] = L.dense_init(
                keys[1], (65536, cfg.d_model), dt, scale=0.02)
        if not cfg.tie_embeddings:
            params["lm_head"] = L.dense_init(
                keys[2], (cfg.d_model, cfg.vocab), dt)

        def stack(kind, rng):
            ls = [self._init_layer(k, kind)
                  for k in jax.random.split(rng, n_super)]
            return jax.tree.map(lambda *xs: jnp.stack(xs), *ls)

        params["stack"] = {
            f"b{i}_{kind}": stack(kind, jax.random.fold_in(keys[3], i))
            for i, kind in enumerate(pat)
        }
        params["tail"] = [self._init_layer(keys[4 + i], kind)
                          for i, kind in enumerate(rem)]
        return params

    def abstract_params(self) -> dict:
        return jax.eval_shape(lambda: self.init_params(jax.random.PRNGKey(0)))

    def param_specs(self) -> dict:
        cfg, rules = self.cfg, self.rt.rules
        pat, n_super, rem = _layer_types(cfg)
        # vocab dims shard over model only when divisible (whisper 51865
        # and mamba2 50280 are not 16-divisible; d_model always is)
        n_model = (self.rt.mesh.shape[rules.model]
                   if (self.rt.mesh and rules.model) else 1)
        vocab_ok = cfg.vocab % max(n_model, 1) == 0
        specs: dict[str, Any] = {
            "embed": (rules.spec("model", "data") if vocab_ok
                      else rules.spec(None, "model")),
            "final_norm": L.specs_norm(cfg, rules),
        }
        if not cfg.use_rope:
            specs["pos_embed"] = rules.spec(None, "data")
        if not cfg.tie_embeddings:
            specs["lm_head"] = (rules.spec("data", "model") if vocab_ok
                                else rules.spec("model", None))

        def stacked(kind):
            base = self._layer_specs(kind)
            return jax.tree.map(
                lambda sp: P(None, *sp), base,
                is_leaf=lambda x: isinstance(x, P))

        specs["stack"] = {f"b{i}_{kind}": stacked(kind)
                          for i, kind in enumerate(pat)}
        specs["tail"] = [self._layer_specs(kind) for kind in rem]
        return specs

    # ------------------------------------------------------------------
    # layer application
    # ------------------------------------------------------------------
    def layer_key(self, batch: int, seq: int,
                  page_size: Optional[int] = None,
                  kv_len: Optional[int] = None) -> Optional[tuple]:
        """The key of the plan an attention layer runs on ``batch`` x
        ``seq`` rows (``core.planner.plan_key``), over a paged cache of
        ``page_size``-slot pages and ``kv_len`` slots per row where a
        page size is given; None where this model never plans.  The
        circuit breaker quarantines plans by this key
        (docs/reliability.md)."""
        if not (self.rt.planner and planner_mod.plannable(self.cfg)):
            return None
        return planner_mod.plan_key(self.cfg, batch, seq, self.rt.stitch,
                                    **_plan_phase(seq, page_size, kv_len))

    def _layer_plan(self, kind: str, x: jax.Array, cache: Optional[dict],
                    page_table: Optional[jax.Array]) -> Optional[tuple]:
        """Which plan runs this layer: ``(key, LayerPlan)``, or None
        where the hand-wired block runs.

        Attention layers of a plannable config plan under
        ``Runtime(planner=True)``, cache-free or over a paged cache
        (phase from the row count, ``paged`` and ``kv_len`` from the
        pool and the page table); contiguous caches stay hand-wired.  A
        plan quarantined by the circuit breaker (docs/reliability.md)
        runs the hand-wired twin, bit-identical with stitching off,
        and a plan that fails to carve is recorded there and degrades
        the same way."""
        paged = (cache is not None and page_table is not None
                 and "k_pages" in cache)
        if kind != "attn" or not (paged or (cache is None
                                            and page_table is None)):
            return None
        b, s = int(x.shape[0]), int(x.shape[1])
        ps = int(cache["k_pages"].shape[-3]) if paged else None
        kv_len = int(page_table.shape[1]) * ps if paged else None
        key = self.layer_key(b, s, ps, kv_len)
        if key is None or _breaker.is_open(key):
            return None
        try:
            plan = planner_mod.plan_model(self.cfg, b, s,
                                          stitch=self.rt.stitch,
                                          **_plan_phase(s, ps, kv_len))
        except Exception as e:  # noqa: BLE001 - degrade
            _breaker.record_failure(key, reason=f"{type(e).__name__}: {e}")
            return None
        return key, plan.layer

    def _apply_layer(self, kind: str, p: dict, x: jax.Array,
                     positions: jax.Array, cache: Optional[dict],
                     layer_idx_in_pattern: int,
                     page_table: Optional[jax.Array] = None
                     ) -> tuple[jax.Array, Any]:
        cfg, rt = self.cfg, self.rt
        planned = self._layer_plan(kind, x, cache, page_table)
        if planned is not None:
            key, plan = planned
            try:
                return L.run_planned_layer(
                    plan, p, x, cfg, rt.rules, positions=positions, rt=rt,
                    cache=cache, page_table=page_table)
            except Exception as e:  # noqa: BLE001 - degrade
                _breaker.record_failure(
                    key, reason=f"{type(e).__name__}: {e}")
        h = L.apply_norm(p["ln1"], x, cfg)
        if kind == "attn":
            if cache is not None and "k_pages" in cache:
                mix, new_cache = L.paged_attention_block(
                    p["mix"], h, cfg, rt.rules, positions=positions,
                    cache=cache, page_table=page_table, mesh=rt.mesh,
                    dist_decode=rt.dist_decode_attn,
                    dist_pipelined=rt.dist_decode_pipelined,
                    kernel_ops=rt.kernel_ops, block=rt.paged_block)
            else:
                mix, new_cache = L.attention_block(
                    p["mix"], h, cfg, rt.rules, positions=positions,
                    cache=cache, causal=True, bkv=rt.bkv,
                    unroll=rt.unroll, mesh=rt.mesh,
                    dist_decode=rt.dist_decode_attn,
                    kernel_ops=rt.kernel_ops)
        elif kind == "mamba":
            mix, new_cache = L.mamba_block(p["mix"], h, cfg, rt.rules,
                                           state=cache, unroll=rt.unroll)
        else:
            mix, new_cache = L.rglru_block(p["mix"], h, cfg, rt.rules,
                                           state=cache)
        x = L.residual(x, mix)
        if cfg.d_ff > 0:
            h2 = L.apply_norm(p["ln2"], x, cfg)
            if cfg.moe:
                ff = L.moe_block(p["ff"], h2, cfg, rt.rules, rt.mesh)
            else:
                ff = L.mlp_block(p["ff"], h2, cfg, rt.rules)
            x = L.residual(x, ff)
        return x, new_cache

    def _run_blocks(self, params: dict, x: jax.Array, positions: jax.Array,
                    caches: Optional[dict],
                    page_table: Optional[jax.Array] = None
                    ) -> tuple[jax.Array, Any]:
        """Scan the super-block stack, then the tail.

        Paged caches (a ``page_table`` is given) ride the scan's carry
        whole, with the layer index: each attention site writes this
        step's rows into its layer of the stacked pool in place and
        reads that layer from the stack, so no layer's pool is sliced
        out or stacked back.  Contiguous caches go through ``xs``/``ys``
        one layer at a time."""
        cfg, rt = self.cfg, self.rt
        pat, n_super, rem = _layer_types(cfg)

        def super_block(x, layer_params, layer_caches, layer=None):
            new_caches = []
            for i, kind in enumerate(pat):
                c = layer_caches[i] if layer_caches is not None else None
                if layer is not None:
                    c = dict(c, layer=layer)
                x, nc = self._apply_layer(kind, layer_params[f"b{i}_{kind}"],
                                          x, positions, c, i,
                                          page_table=page_table)
                if layer is not None:
                    nc = {k: a for k, a in nc.items() if k != "layer"}
                new_caches.append(nc)
            return x, (tuple(new_caches) if layer_caches is not None
                       else None)

        body = super_block
        if rt.remat:
            policy = None
            if rt.remat_policy == "dots":
                policy = jax.checkpoint_policies.dots_with_no_batch_dims_saveable
            body = jax.checkpoint(super_block, policy=policy,
                                  static_argnums=())

        if caches is None:
            def scan_fn(x, lp):
                x, _ = body(x, lp, None)
                return x, None
            x, _ = jax.lax.scan(scan_fn, x, params["stack"],
                                unroll=n_super if rt.unroll else 1)
            new_stack_caches = None
        elif page_table is not None:
            def scan_fn(carry, lp):
                x, stack, layer = carry
                x, stack = body(x, lp, stack, layer)
                return (x, stack, layer + 1), None
            (x, new_stack_caches, _), _ = jax.lax.scan(
                scan_fn, (x, caches["stack"], jnp.int32(0)),
                params["stack"], unroll=n_super if rt.unroll else 1)
        else:
            def scan_fn(x, xs):
                lp, lc = xs
                x, nc = body(x, lp, lc)
                return x, nc
            x, new_stack_caches = jax.lax.scan(
                scan_fn, x, (params["stack"], caches["stack"]),
                unroll=n_super if rt.unroll else 1)

        new_tail = []
        for i, kind in enumerate(rem):
            c = caches["tail"][i] if caches is not None else None
            x, nc = self._apply_layer(kind, params["tail"][i], x,
                                      positions, c, i,
                                      page_table=page_table)
            new_tail.append(nc)
        new_caches = (None if caches is None
                      else {"stack": new_stack_caches, "tail": new_tail})
        return x, new_caches

    # ------------------------------------------------------------------
    # public API
    # ------------------------------------------------------------------
    def _embed(self, params: dict, tokens: jax.Array,
               positions: jax.Array,
               prefix_embeds: Optional[jax.Array]) -> jax.Array:
        cfg = self.cfg
        x = jnp.take(params["embed"], tokens, axis=0)
        if cfg.tie_embeddings:  # gemma-style scaled tied embeddings
            x = x * math.sqrt(cfg.d_model)
        if prefix_embeds is not None:
            x = jnp.concatenate([prefix_embeds.astype(x.dtype), x], axis=1)
        if not cfg.use_rope:
            x = x + jnp.take(params["pos_embed"], positions, axis=0)
        return constrain(x, self.rt.rules, "batch", "seq", None)

    def _unembed(self, params: dict, x: jax.Array) -> jax.Array:
        cfg = self.cfg
        x = L.apply_norm(params["final_norm"], x, cfg)
        if cfg.tie_embeddings:
            logits = jnp.einsum("bsd,vd->bsv", x, params["embed"])
        else:
            logits = jnp.einsum("bsd,dv->bsv", x, params["lm_head"])
        return constrain(logits, self.rt.rules, "batch", None, "tp")

    def forward(self, params: dict, tokens: jax.Array,
                prefix_embeds: Optional[jax.Array] = None) -> jax.Array:
        """Training forward: tokens (B, S) [-> logits (B, S(+P), V)]."""
        n_pre = prefix_embeds.shape[1] if prefix_embeds is not None else 0
        total = tokens.shape[1] + n_pre
        positions = jnp.arange(total, dtype=jnp.int32)
        x = self._embed(params, tokens, positions, prefix_embeds)
        x, _ = self._run_blocks(params, x, positions, None)
        return self._unembed(params, x)

    def loss(self, params: dict, batch: dict) -> jax.Array:
        """batch: {"tokens","labels"[, "prefix_embeds"]}; labels aligned
        with tokens (-100 = masked).  Chunked CE — no (B,S,V) logits."""
        cfg = self.cfg
        prefix = batch.get("prefix_embeds")
        n_pre = prefix.shape[1] if prefix is not None else 0
        tokens = batch["tokens"]
        total = tokens.shape[1] + n_pre
        positions = jnp.arange(total, dtype=jnp.int32)
        x = self._embed(params, tokens, positions, prefix)
        x, _ = self._run_blocks(params, x, positions, None)
        x = L.apply_norm(params["final_norm"], x, cfg)
        if n_pre:
            x = x[:, n_pre:]
        w = params["embed"] if cfg.tie_embeddings else params["lm_head"]
        return chunked_ce(x, w, batch["labels"], cfg.tie_embeddings,
                          unroll=self.rt.unroll)

    # ------------------------------------------------------------------
    # serving
    # ------------------------------------------------------------------
    def _init_layer_cache(self, kind: str, batch: int, max_len: int,
                          dtype=None):
        cfg = self.cfg
        if kind == "attn":
            return L.init_attn_cache(cfg, batch, max_len, dtype=dtype)
        dt = dtype or jnp.dtype(cfg.dtype)
        if kind == "mamba":
            s = cfg.ssm
            din = s.expand * cfg.d_model
            H = din // s.head_dim
            conv_dim = din + 2 * s.n_groups * s.d_state
            return {"conv": jnp.zeros((batch, s.conv_kernel - 1, conv_dim), dt),
                    "ssm": jnp.zeros((batch, H, s.n_groups * s.d_state,
                                      s.head_dim), jnp.float32)}
        w = int(cfg.rglru.width_mult * cfg.d_model)
        return {"conv": jnp.zeros((batch, cfg.rglru.conv_kernel - 1, w), dt),
                "lru": jnp.zeros((batch, w), jnp.float32)}

    def init_cache(self, batch: int, max_len: int, dtype=None) -> dict:
        pat, n_super, rem = _layer_types(self.cfg)

        def stack_cache(kind):
            one = self._init_layer_cache(kind, batch, max_len, dtype)
            return jax.tree.map(
                lambda a: jnp.broadcast_to(a, (n_super,) + a.shape).copy(),
                one)

        return {
            "stack": tuple(stack_cache(kind) for kind in pat),
            "tail": [self._init_layer_cache(kind, batch, max_len, dtype)
                     for kind in rem],
        }

    def cache_specs(self, batch_size: int) -> dict:
        """PartitionSpecs mirroring init_cache output."""
        cfg, rules, mesh = self.cfg, self.rt.rules, self.rt.mesh
        pat, n_super, rem = _layer_types(cfg)

        def layer_spec(kind, stacked: bool):
            lead = (None,) if stacked else ()
            b = batch_placement(rules, mesh, batch_size) or None
            if kind == "attn":
                # shard kv heads over model when divisible, else seq
                n_model = mesh.shape[rules.model] if mesh else 1
                if rules.enabled and cfg.n_kv_heads % max(n_model, 1) == 0 \
                        and cfg.n_kv_heads >= n_model:
                    kv = P(*lead, b, rules.model, None, None)
                else:
                    kv = P(*lead, b, None, rules.model, None)
                return {"k": kv, "v": kv, "pos": P(*lead, None)}
            if kind == "mamba":
                return {"conv": P(*lead, b, None, None),
                        "ssm": P(*lead, b, rules.model, None, None)}
            return {"conv": P(*lead, b, None, None),
                    "lru": P(*lead, b, rules.model)}

        return {
            "stack": tuple(layer_spec(kind, True) for kind in pat),
            "tail": [layer_spec(kind, False) for kind in rem],
        }

    def prefill(self, params: dict, tokens: jax.Array, cache: dict,
                prefix_embeds: Optional[jax.Array] = None
                ) -> tuple[jax.Array, dict]:
        n_pre = prefix_embeds.shape[1] if prefix_embeds is not None else 0
        total = tokens.shape[1] + n_pre
        positions = jnp.arange(total, dtype=jnp.int32)
        x = self._embed(params, tokens, positions, prefix_embeds)
        x, cache = self._run_blocks(params, x, positions, cache)
        logits = self._unembed(params, x[:, -1:])
        return logits[:, 0], cache

    def decode_step(self, params: dict, cache: dict, tokens: jax.Array,
                    pos: jax.Array) -> tuple[jax.Array, dict]:
        """tokens: (B,) int32; pos: scalar int32 absolute position."""
        positions = pos[None].astype(jnp.int32)
        x = self._embed(params, tokens[:, None], positions, None)
        x, cache = self._run_blocks(params, x, positions, cache)
        logits = self._unembed(params, x)
        return logits[:, 0], cache

    # ------------------------------------------------------------------
    # paged serving (docs/serving.md; driven by serving.engine)
    # ------------------------------------------------------------------
    def init_paged_cache(self, n_pages: int, page_size: int,
                         dtype=None) -> dict:
        """Paged KV cache pytree: the same ``{"stack", "tail"}`` layout
        as ``init_cache``, but every attention site holds a shared page
        pool ``(n_pages, page_size, n_kv_heads, dh)``, page-major, with
        NO batch dim (``(n_super, ...)`` stacked for the scanned sites)
        — the engine's page tables map requests onto pages, and page 0
        is the scratch page (``serving.kv_pages``).  Attention-only
        stacks for now: SSM/hybrid recurrent state is per-request, not
        per-position, so those blocks need slot-state swapping rather
        than paging (ROADMAP follow-up)."""
        cfg = self.cfg
        pat, n_super, rem = _layer_types(cfg)
        if any(kind != "attn" for kind in list(pat) + list(rem)):
            raise NotImplementedError(
                f"paged serving covers attention-only stacks; "
                f"{cfg.name} has pattern {cfg.pattern}")
        if cfg.n_prefix_embeds:
            raise NotImplementedError(
                f"paged serving does not thread prefix embeddings yet; "
                f"{cfg.name} needs n_prefix_embeds={cfg.n_prefix_embeds}")
        dt = dtype or jnp.dtype(cfg.dtype)
        shape = (n_pages, page_size, cfg.n_kv_heads, cfg.dh)

        def site():
            return {"k_pages": jnp.zeros(shape, dt),
                    "v_pages": jnp.zeros(shape, dt)}

        def stack_site():
            return jax.tree.map(
                lambda a: jnp.broadcast_to(a, (n_super,) + a.shape).copy(),
                site())

        return {"stack": tuple(stack_site() for _ in pat),
                "tail": [site() for _ in rem]}

    def prefill_paged(self, params: dict, tokens: jax.Array, cache: dict,
                      page_table: jax.Array, length: jax.Array
                      ) -> tuple[jax.Array, dict]:
        """One request's prefill into its pages.

        tokens: (1, S) prompt padded to a page multiple; ``length``
        (int32 scalar, traceable) is the real prompt length — padding
        rows get position -1, so their kv lands on the scratch page and
        their logits are never read.  Attention runs over the full
        page-table gather (the same N as every later decode step, so
        prefill and decode see bit-identical softmax geometry).
        Returns (logits of the last REAL token (1, V), cache)."""
        b, s = tokens.shape
        ar = jnp.arange(s, dtype=jnp.int32)
        positions = jnp.broadcast_to(
            jnp.where(ar < length, ar, -1)[None, :], (b, s))
        x = self._embed(params, tokens, jnp.clip(positions, 0), None)
        x, cache = self._run_blocks(params, x, positions, cache,
                                    page_table=page_table)
        x = jax.lax.dynamic_slice_in_dim(
            x, jnp.clip(length - 1, 0), 1, axis=1)
        logits = self._unembed(params, x)
        return logits[:, 0], cache

    def decode_step_paged(self, params: dict, cache: dict,
                          tokens: jax.Array, positions: jax.Array,
                          page_table: jax.Array
                          ) -> tuple[jax.Array, dict]:
        """One ragged decode step over the whole slot batch.

        tokens: (B,) last emitted token per slot; positions: (B,)
        absolute position each slot writes this step — i.e. its
        current context length (-1 = inactive slot: kv goes to the
        scratch page, logits are garbage and ignored); page_table:
        (B, max_pages).  Returns (logits (B, V), cache)."""
        pos2 = positions.astype(jnp.int32)[:, None]
        x = self._embed(params, tokens[:, None], jnp.clip(pos2, 0), None)
        x, cache = self._run_blocks(params, x, pos2, cache,
                                    page_table=page_table)
        logits = self._unembed(params, x)
        return logits[:, 0], cache
