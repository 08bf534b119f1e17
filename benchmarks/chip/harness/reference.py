"""Plain float32 forward pass of a dense GQA decoder: the reference that
decides ``correct``.  No kernels, no cache, no batching; one sequence at
a time, every matmul at ``precision=HIGHEST``.

It follows the published description (Qwen3 / Mistral ``modeling``
code): RMSNorm ``x * rsqrt(mean(x^2) + eps) * g``, q/k RMSNorm per head
where the configuration has it, RoPE by halves ("rotate_half") with
``inv_freq = theta ** (-2i / head_dim)``, grouped-query attention with
a causal mask, SwiGLU ``down(silu(gate x) * up x)``, untied
unembedding.  Departure: none in the arithmetic; the weights are the
benchmark's own seeded draw (``weights.canonical``), not trained ones.

``control=True`` computes the same pass with every matmul operand
rounded to float8 e4m3 (per-row scales for activations, per-column for
weights, products summed in float32): the nearest precision below the
configuration's bfloat16, the step a later change would be tempted by.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

HI = jax.lax.Precision.HIGHEST
F8_MAX = 448.0           # largest finite float8_e4m3fn


def _fp8(x, axis):
    """Round ``x`` to float8 e4m3 with one scale per slice along
    ``axis`` (the contracted dimension), back in float32."""
    amax = jnp.max(jnp.abs(x), axis=axis, keepdims=True)
    scale = jnp.where(amax > 0, amax / F8_MAX, 1.0)
    return (x / scale).astype(jnp.float8_e4m3fn).astype(jnp.float32) * scale


def _rms(x, g, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * g


def _rope(x, theta):
    """x: (S, H, dh) at positions 0..S-1."""
    s, _, dh = x.shape
    inv = theta ** (-jnp.arange(0, dh, 2, dtype=jnp.float32) / dh)
    ang = jnp.arange(s, dtype=jnp.float32)[:, None] * inv[None, :]
    emb = jnp.concatenate([ang, ang], -1)[:, None, :]
    x1, x2 = x[..., : dh // 2], x[..., dh // 2:]
    rot = jnp.concatenate([-x2, x1], -1)
    return x * jnp.cos(emb) + rot * jnp.sin(emb)


def _forward(w, tokens, cfg, control: bool):
    """Final-normed hidden states (S, D) of one sequence."""
    L = cfg["num_hidden_layers"]
    H, KV, dh = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                 cfg["head_dim"])
    eps, theta = cfg["rms_norm_eps"], cfg["rope_theta"]
    qk_norm = cfg.get("qk_norm", False)
    f32 = jnp.float32

    def mm(a, b):
        a, b = a.astype(f32), b.astype(f32)
        if control:
            a, b = _fp8(a, -1), _fp8(b, 0)
        return jnp.dot(a, b, precision=HI)

    s = tokens.shape[0]
    x = w["embed"][tokens].astype(f32)
    causal = jnp.tril(jnp.ones((s, s), bool))

    def layer(x, p):
        h = _rms(x, p["attn_norm"], eps)
        q = mm(h, p["wq"]).reshape(s, H, dh)
        k = mm(h, p["wk"]).reshape(s, KV, dh)
        v = mm(h, p["wv"]).reshape(s, KV, dh)
        if qk_norm:
            q = _rms(q, p["q_norm"], eps)
            k = _rms(k, p["k_norm"], eps)
        q, k = _rope(q, theta), _rope(k, theta)
        k = jnp.repeat(k, H // KV, axis=1)
        v = jnp.repeat(v, H // KV, axis=1)
        if control:
            q, k, v = _fp8(q, -1), _fp8(k, -1), _fp8(v, 0)
        sc = jnp.einsum("qhd,khd->hqk", q, k, precision=HI) / jnp.sqrt(
            jnp.float32(dh))
        sc = jnp.where(causal[None], sc, -jnp.inf)
        pr = jax.nn.softmax(sc, axis=-1)
        if control:
            pr = _fp8(pr, -1)
        o = jnp.einsum("hqk,khd->qhd", pr, v, precision=HI)
        x = x + mm(o.reshape(s, H * dh), p["wo"])
        h = _rms(x, p["mlp_norm"], eps)
        x = x + mm(jax.nn.silu(mm(h, p["w_gate"])) * mm(h, p["w_up"]),
                   p["w_down"])
        return x, None

    names = ["attn_norm", "wq", "wk", "wv", "wo", "mlp_norm", "w_gate",
             "w_up", "w_down"] + (["q_norm", "k_norm"] if qk_norm else [])
    x, _ = jax.lax.scan(layer, x, {n: w[n] for n in names}, length=L)
    return _rms(x, w["final_norm"], eps)


ROWS = 256           # rows of logits made at once


@functools.partial(jax.jit, static_argnames=("cfg_items", "control"))
def gaps(w, tokens, targets, cfg_items, control: bool = False):
    """Per position p of ``tokens`` (S,): the gap by which the logit of
    ``targets[p]`` lies below the reference's best logit, and with
    ``control`` also the gap of the token the control ranks first.
    S must be a multiple of ROWS; positions past the real length are
    causal padding and are ignored by the caller."""
    cfg = dict(cfg_items)
    with jax.default_matmul_precision("highest"):
        ref_h = _forward(w, tokens, cfg, control=False)
        ctl_h = _forward(w, tokens, cfg, control=True) if control else ref_h
        head = w["lm_head"].astype(jnp.float32)
        head8 = _fp8(head, 0) if control else head
        s, d = ref_h.shape

        def rows(args):
            rh, ch, tg = args
            ref = jnp.dot(rh, head, precision=HI)
            best = jnp.max(ref, -1)
            g_served = best - jnp.take_along_axis(ref, tg[:, None], -1)[:, 0]
            if not control:
                return g_served, jnp.zeros_like(g_served)
            ctl = jnp.dot(_fp8(ch, -1), head8, precision=HI)
            pick = jnp.argmax(ctl, -1)
            g_ctl = best - jnp.take_along_axis(ref, pick[:, None], -1)[:, 0]
            return g_served, g_ctl

        n = s // ROWS
        g_s, g_c = jax.lax.map(rows, (ref_h.reshape(n, ROWS, d),
                                      ctl_h.reshape(n, ROWS, d),
                                      targets.reshape(n, ROWS)))
    return g_s.reshape(s), g_c.reshape(s)


def cfg_items(config: dict) -> tuple:
    keys = ("num_hidden_layers", "num_attention_heads",
            "num_key_value_heads", "head_dim", "rms_norm_eps",
            "rope_theta", "qk_norm")
    return tuple((k, config.get(k, False)) for k in keys)


@functools.partial(jax.jit, static_argnames=("cfg_items", "control"))
def logits(w, tokens, cfg_items, control: bool = False):
    """All logits (S, V) of one sequence; for tests at small sizes."""
    with jax.default_matmul_precision("highest"):
        h = _forward(w, tokens, dict(cfg_items), control)
        head = w["lm_head"].astype(jnp.float32)
        if control:
            h, head = _fp8(h, -1), _fp8(head, 0)
        return jnp.dot(h, head, precision=HI)
