"""Whisper-style encoder-decoder backbone (arXiv:2212.04356).

The conv/mel frontend is a STUB per the assignment: `input_specs`
provides precomputed frame embeddings (B, n_frames, D) directly —
the transformer backbone (what the shape cells exercise) is real.

Same external API as models.lm.LM so the launcher treats all archs
uniformly; batches carry {"frames", "tokens", "labels"}.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Optional

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from ..dist.sharding import Rules, batch_placement, constrain
from . import layers as L
from .config import ModelConfig
from .lm import Runtime


class EncDec:
    def __init__(self, cfg: ModelConfig, rt: Optional[Runtime] = None):
        assert cfg.encoder is not None
        self.cfg = cfg
        self.rt = rt or Runtime()

    # ------------------------------------------------------------------
    def _init_enc_layer(self, rng) -> dict:
        cfg = self.cfg
        r = jax.random.split(rng, 2)
        return {"ln1": L.init_norm(cfg),
                "attn": L.init_attention(r[0], cfg),
                "ln2": L.init_norm(cfg),
                "ff": L.init_mlp(r[1], cfg)}

    def _init_dec_layer(self, rng) -> dict:
        cfg = self.cfg
        r = jax.random.split(rng, 3)
        return {"ln1": L.init_norm(cfg),
                "self_attn": L.init_attention(r[0], cfg),
                "ln_x": L.init_norm(cfg),
                "cross_attn": L.init_cross_attention(r[1], cfg),
                "ln2": L.init_norm(cfg),
                "ff": L.init_mlp(r[2], cfg)}

    def init_params(self, rng) -> dict:
        cfg = self.cfg
        enc = cfg.encoder
        keys = jax.random.split(rng, 6)
        dt = jnp.dtype(cfg.dtype)

        def stack(fn, rng, n):
            ls = [fn(k) for k in jax.random.split(rng, n)]
            return jax.tree.map(lambda *xs: jnp.stack(xs), *ls)

        return {
            "enc_pos": L.dense_init(keys[0], (enc.n_frames, cfg.d_model), dt,
                                    scale=0.02),
            "enc_stack": stack(self._init_enc_layer, keys[1], enc.n_layers),
            "enc_norm": L.init_norm(cfg),
            "embed": L.dense_init(keys[2], (cfg.vocab, cfg.d_model), dt,
                                  scale=0.02),
            "dec_pos": L.dense_init(keys[3], (65536, cfg.d_model), dt,
                                    scale=0.02),
            "dec_stack": stack(self._init_dec_layer, keys[4], cfg.n_layers),
            "final_norm": L.init_norm(cfg),
        }

    def abstract_params(self) -> dict:
        return jax.eval_shape(lambda: self.init_params(jax.random.PRNGKey(0)))

    def param_specs(self) -> dict:
        cfg, rules = self.cfg, self.rt.rules

        def stacked(base):
            return jax.tree.map(lambda sp: P(None, *sp), base,
                                is_leaf=lambda x: isinstance(x, P))

        enc_layer = {"ln1": L.specs_norm(cfg, rules),
                     "attn": L.specs_attention(cfg, rules),
                     "ln2": L.specs_norm(cfg, rules),
                     "ff": L.specs_mlp(cfg, rules)}
        dec_layer = {"ln1": L.specs_norm(cfg, rules),
                     "self_attn": L.specs_attention(cfg, rules),
                     "ln_x": L.specs_norm(cfg, rules),
                     "cross_attn": L.specs_cross_attention(cfg, rules),
                     "ln2": L.specs_norm(cfg, rules),
                     "ff": L.specs_mlp(cfg, rules)}
        n_model = (self.rt.mesh.shape[rules.model]
                   if (self.rt.mesh and rules.model) else 1)
        vocab_ok = cfg.vocab % max(n_model, 1) == 0
        return {
            "enc_pos": rules.spec(None, "data"),
            "enc_stack": stacked(enc_layer),
            "enc_norm": L.specs_norm(cfg, rules),
            "embed": (rules.spec("model", "data") if vocab_ok
                      else rules.spec(None, "model")),
            "dec_pos": rules.spec(None, "data"),
            "dec_stack": stacked(dec_layer),
            "final_norm": L.specs_norm(cfg, rules),
        }

    # ------------------------------------------------------------------
    def encode(self, params: dict, frames: jax.Array) -> jax.Array:
        """frames: (B, T, D) precomputed frame embeddings (frontend stub)."""
        cfg, rt = self.cfg, self.rt
        t = frames.shape[1]
        x = frames + params["enc_pos"][None, :t]
        x = constrain(x, rt.rules, "batch", "seq", None)
        positions = jnp.arange(t, dtype=jnp.int32)

        def layer(x, p):
            h = L.apply_norm(p["ln1"], x, cfg)
            mix, _ = L.attention_block(p["attn"], h, cfg, rt.rules,
                                       positions=positions, causal=False,
                                       bkv=rt.bkv)
            x = x + mix
            h2 = L.apply_norm(p["ln2"], x, cfg)
            return x + L.mlp_block(p["ff"], h2, cfg, rt.rules), None

        body = jax.checkpoint(layer) if rt.remat else layer
        x, _ = jax.lax.scan(lambda c, p: body(c, p), x, params["enc_stack"],
                            unroll=cfg.encoder.n_layers if rt.unroll else 1)
        return L.apply_norm(params["enc_norm"], x, cfg)

    def _dec_layer(self, p, x, positions, enc_out, self_cache, cross_kv):
        cfg, rt = self.cfg, self.rt
        h = L.apply_norm(p["ln1"], x, cfg)
        mix, self_cache = L.attention_block(
            p["self_attn"], h, cfg, rt.rules, positions=positions,
            cache=self_cache, causal=True, bkv=rt.bkv)
        x = x + mix
        hx = L.apply_norm(p["ln_x"], x, cfg)
        cmix, cross_kv = L.cross_attention_block(
            p["cross_attn"], hx, cfg, rt.rules, enc_out=enc_out,
            kv_cache=cross_kv)
        x = x + cmix
        h2 = L.apply_norm(p["ln2"], x, cfg)
        x = x + L.mlp_block(p["ff"], h2, cfg, rt.rules)
        return x, self_cache, cross_kv

    def _decode_stack(self, params, x, positions, enc_out, caches):
        rt = self.rt

        def layer(x, p, c):
            sc = c["self"] if c is not None else None
            ck = c["cross"] if c is not None else None
            x, sc, ck = self._dec_layer(p, x, positions, enc_out, sc, ck)
            return x, ({"self": sc, "cross": ck} if c is not None else None)

        body = jax.checkpoint(layer) if (rt.remat and caches is None) else layer
        if caches is None:
            def scan_fn(c, p):
                x, _ = body(c, p, None)
                return x, None
            x, _ = jax.lax.scan(scan_fn, x, params["dec_stack"],
                                unroll=self.cfg.n_layers if rt.unroll else 1)
            return x, None
        def scan_fn(c, xs):
            p, cc = xs
            x, nc = body(c, p, cc)
            return x, nc
        x, new_caches = jax.lax.scan(
            scan_fn, x, (params["dec_stack"], caches),
            unroll=self.cfg.n_layers if rt.unroll else 1)
        return x, new_caches

    # ------------------------------------------------------------------
    def forward(self, params: dict, tokens: jax.Array,
                frames: jax.Array) -> jax.Array:
        cfg, rt = self.cfg, self.rt
        enc_out = self.encode(params, frames)
        s = tokens.shape[1]
        positions = jnp.arange(s, dtype=jnp.int32)
        x = jnp.take(params["embed"], tokens, axis=0)
        x = x + jnp.take(params["dec_pos"], positions, axis=0)
        x = constrain(x, rt.rules, "batch", "seq", None)
        x, _ = self._decode_stack(params, x, positions, enc_out, None)
        x = L.apply_norm(params["final_norm"], x, cfg)
        logits = jnp.einsum("bsd,vd->bsv", x, params["embed"])
        return constrain(logits, rt.rules, "batch", None, "tp")

    def loss(self, params: dict, batch: dict) -> jax.Array:
        cfg, rt = self.cfg, self.rt
        enc_out = self.encode(params, batch["frames"])
        tokens = batch["tokens"]
        positions = jnp.arange(tokens.shape[1], dtype=jnp.int32)
        x = jnp.take(params["embed"], tokens, axis=0)
        x = x + jnp.take(params["dec_pos"], positions, axis=0)
        x = constrain(x, rt.rules, "batch", "seq", None)
        x, _ = self._decode_stack(params, x, positions, enc_out, None)
        x = L.apply_norm(params["final_norm"], x, cfg)
        from .lm import chunked_ce
        return chunked_ce(x, params["embed"], batch["labels"], tied=True,
                          unroll=rt.unroll)

    # ------------------------------------------------------------------
    def init_cache(self, batch: int, max_len: int, dtype=None) -> dict:
        cfg = self.cfg
        enc = cfg.encoder
        dt = dtype or jnp.dtype(cfg.dtype)
        n = cfg.n_layers
        self_c = L.init_attn_cache(cfg, batch, max_len, window=0, dtype=dt)
        cross = {
            "k": jnp.zeros((batch, cfg.n_kv_heads, enc.n_frames, cfg.dh), dt),
            "v": jnp.zeros((batch, cfg.n_kv_heads, enc.n_frames, cfg.dh), dt),
        }
        stack = jax.tree.map(
            lambda a: jnp.broadcast_to(a, (n,) + a.shape).copy(),
            {"self": self_c, "cross": cross})
        return stack

    def cache_specs(self, batch_size: int) -> dict:
        cfg, rules, mesh = self.cfg, self.rt.rules, self.rt.mesh
        b = batch_placement(rules, mesh, batch_size) or None
        kv = P(None, b, None, rules.model, None)  # kv=12 < 16: shard seq
        # cross KV covers 1500 frames (not 16-divisible): batch-shard only
        ckv = P(None, b, None, None, None)
        return {"self": {"k": kv, "v": kv, "pos": P(None, None)},
                "cross": {"k": ckv, "v": ckv}}

    def prefill(self, params: dict, tokens: jax.Array, cache: dict,
                frames: jax.Array) -> tuple[jax.Array, dict]:
        cfg, rt = self.cfg, self.rt
        enc_out = self.encode(params, frames)
        s = tokens.shape[1]
        positions = jnp.arange(s, dtype=jnp.int32)
        x = jnp.take(params["embed"], tokens, axis=0)
        x = x + jnp.take(params["dec_pos"], positions, axis=0)
        # prefill recomputes the cross-attn KV from enc_out and stores it
        x, new_caches = self._prefill_stack(params, x, positions, enc_out,
                                            cache)
        x = L.apply_norm(params["final_norm"], x[:, -1:], cfg)
        logits = jnp.einsum("bsd,vd->bsv", x, params["embed"])
        return logits[:, 0], new_caches

    def _prefill_stack(self, params, x, positions, enc_out, caches):
        def scan_fn(c, xs):
            p, cc = xs
            xo, sc, ck = self._dec_layer(p, c, positions, enc_out,
                                         cc["self"], None)
            return xo, {"self": sc, "cross": ck}
        x, new_caches = jax.lax.scan(
            scan_fn, x, (params["dec_stack"], caches),
            unroll=self.cfg.n_layers if self.rt.unroll else 1)
        return x, new_caches

    def decode_step(self, params: dict, cache: dict, tokens: jax.Array,
                    pos: jax.Array) -> tuple[jax.Array, dict]:
        cfg = self.cfg
        positions = pos[None].astype(jnp.int32)
        x = jnp.take(params["embed"], tokens[:, None], axis=0)
        x = x + params["dec_pos"][positions]
        x, new_caches = self._decode_stack(params, x, positions, None, cache)
        x = L.apply_norm(params["final_norm"], x, cfg)
        logits = jnp.einsum("bsd,vd->bsv", x, params["embed"])
        return logits[:, 0], new_caches
