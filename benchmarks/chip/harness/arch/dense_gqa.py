"""Dense GQA decoder (Qwen3, Mistral): the program's configuration, the
weight draw and its layout in the program, the plain float32 reference,
and the operations and bytes of its work.

The reference follows the published description (Qwen3 / Mistral
``modeling`` code): RMSNorm ``x * rsqrt(mean(x^2) + eps) * g``, q/k
RMSNorm per head where the configuration has it, RoPE by halves
("rotate_half") with ``inv_freq = theta ** (-2i / head_dim)``,
grouped-query attention with a causal mask, SwiGLU
``down(silu(gate x) * up x)``, untied unembedding.  Departure: none in
the arithmetic; the weights are the benchmark's own seeded draw, not
trained ones.

Costs: "needed" means what the algorithm must do for the real tokens:
the K/V of the positions a query may attend to, not the page table's
full width; the active slots, not the padded batch.  A kernel that moves
more than this reads below 100% of its roofline, which is the point.
Counts are per call of the whole model (all layers).
"""
from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp

from harness import reference as R
from harness import weights as W

ARCHITECTURES = ("Qwen3ForCausalLM", "MistralForCausalLM")

SMOKE = {
    "name": "smoke-dense-gqa", "source": "test",
    "architectures": ["Qwen3ForCausalLM"], "hidden_size": 256,
    "intermediate_size": 512, "num_attention_heads": 4,
    "num_key_value_heads": 2, "head_dim": 64, "num_hidden_layers": 2,
    "vocab_size": 2048, "rope_theta": 10000.0, "rms_norm_eps": 1e-6,
    "hidden_act": "silu", "tie_word_embeddings": False,
    "initializer_range": 0.02, "torch_dtype": "bfloat16", "qk_norm": True,
    "reduced": {}, "assumed": {}, "deployment": {}, "chips": 1}


def model_config(config: dict):
    """The program's ``ModelConfig`` for a configuration file (HF keys)."""
    from repro.models.config import ModelConfig
    c = config
    if c.get("hidden_act", "silu") != "silu":
        raise ValueError(f"only SwiGLU (silu) MLPs: {c['hidden_act']}")
    return ModelConfig(
        name=c["name"], family="dense", n_layers=c["num_hidden_layers"],
        d_model=c["hidden_size"], n_heads=c["num_attention_heads"],
        n_kv_heads=c["num_key_value_heads"], d_ff=c["intermediate_size"],
        vocab=c["vocab_size"], head_dim=c["head_dim"],
        qk_norm=bool(c.get("qk_norm", False)),
        rope_theta=float(c["rope_theta"]), norm_eps=float(c["rms_norm_eps"]),
        tie_embeddings=bool(c["tie_word_embeddings"]),
        dtype=c["torch_dtype"], act="swiglu")


# -- weights ------------------------------------------------------------

def leaf_shapes(config: dict) -> dict:
    s = shape_of(config)
    L, d, f = s.layers, s.d_model, s.d_ff
    hd, kvd = s.heads * s.head_dim, s.kv_heads * s.head_dim
    shapes = {
        "embed": (s.vocab, d), "lm_head": (d, s.vocab),
        "final_norm": (d,),
        "attn_norm": (L, d), "wq": (L, d, hd), "wk": (L, d, kvd),
        "wv": (L, d, kvd), "wo": (L, hd, d), "mlp_norm": (L, d),
        "w_gate": (L, d, f), "w_up": (L, d, f), "w_down": (L, f, d),
    }
    if _qk_norm(config):
        shapes["q_norm"] = (L, s.head_dim)
        shapes["k_norm"] = (L, s.head_dim)
    return shapes


def canonical(config: dict, key, dtype, init_sd: float) -> dict:
    return W.draw(leaf_shapes(config), key, dtype, init_sd)


def to_program(c: dict, config: dict) -> dict:
    """The program's parameter tree for a uniform stack of attention
    layers (one scanned super-block of pattern ``("attn",)``).  The
    program keeps an RMSNorm weight ``w`` and scales by ``1 + w``
    (``models/layers.py``)."""
    mix = {"wq": c["wq"], "wk": c["wk"], "wv": c["wv"], "wo": c["wo"]}
    if _qk_norm(config):
        mix["q_norm"] = c["q_norm"] - 1.0
        mix["k_norm"] = c["k_norm"] - 1.0
    layer = {"ln1": {"w": c["attn_norm"] - 1.0}, "mix": mix,
             "ln2": {"w": c["mlp_norm"] - 1.0},
             "ff": {"w_gate": c["w_gate"], "w_up": c["w_up"],
                    "w_down": c["w_down"]}}
    return {"embed": c["embed"], "final_norm": {"w": c["final_norm"] - 1.0},
            "lm_head": c["lm_head"], "stack": {"b0_attn": layer},
            "tail": []}


def spread(name: str, leaf, n: int):
    """Every matrix split along its last (output) axis where it divides;
    the RMSNorm gains whole on each chip."""
    if W.is_gain(name) or leaf.ndim < 2 or leaf.shape[-1] % n:
        return None
    return leaf.ndim - 1


# -- the reference ------------------------------------------------------

def _rope(x, theta):
    """x: (S, H, dh) at positions 0..S-1."""
    s, _, dh = x.shape
    inv = theta ** (-jnp.arange(0, dh, 2, dtype=jnp.float32) / dh)
    ang = jnp.arange(s, dtype=jnp.float32)[:, None] * inv[None, :]
    emb = jnp.concatenate([ang, ang], -1)[:, None, :]
    x1, x2 = x[..., : dh // 2], x[..., dh // 2:]
    rot = jnp.concatenate([-x2, x1], -1)
    return x * jnp.cos(emb) + rot * jnp.sin(emb)


def hidden(w, tokens, cfg: dict, control: bool):
    """Final-normed hidden states (S, D) of one sequence."""
    L = cfg["num_hidden_layers"]
    H, KV, dh = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                 cfg["head_dim"])
    eps, theta = cfg["rms_norm_eps"], cfg["rope_theta"]
    qk_norm = _qk_norm(cfg)
    f32, HI, fp8, rms = jnp.float32, R.HI, R.fp8, R.rms

    def mm(a, b):
        a, b = a.astype(f32), b.astype(f32)
        if control:
            a, b = fp8(a, -1), fp8(b, 0)
        return jnp.dot(a, b, precision=HI)

    s = tokens.shape[0]
    x = w["embed"][tokens].astype(f32)
    causal = jnp.tril(jnp.ones((s, s), bool))

    def layer(x, p):
        h = rms(x, p["attn_norm"], eps)
        q = mm(h, p["wq"]).reshape(s, H, dh)
        k = mm(h, p["wk"]).reshape(s, KV, dh)
        v = mm(h, p["wv"]).reshape(s, KV, dh)
        if qk_norm:
            q = rms(q, p["q_norm"], eps)
            k = rms(k, p["k_norm"], eps)
        q, k = _rope(q, theta), _rope(k, theta)
        k = jnp.repeat(k, H // KV, axis=1)
        v = jnp.repeat(v, H // KV, axis=1)
        if control:
            q, k, v = fp8(q, -1), fp8(k, -1), fp8(v, 0)
        sc = jnp.einsum("qhd,khd->hqk", q, k, precision=HI) / jnp.sqrt(
            jnp.float32(dh))
        sc = jnp.where(causal[None], sc, -jnp.inf)
        pr = jax.nn.softmax(sc, axis=-1)
        if control:
            pr = fp8(pr, -1)
        o = jnp.einsum("hqk,khd->qhd", pr, v, precision=HI)
        x = x + mm(o.reshape(s, H * dh), p["wo"])
        h = rms(x, p["mlp_norm"], eps)
        x = x + mm(jax.nn.silu(mm(h, p["w_gate"])) * mm(h, p["w_up"]),
                   p["w_down"])
        return x, None

    names = ["attn_norm", "wq", "wk", "wv", "wo", "mlp_norm", "w_gate",
             "w_up", "w_down"] + (["q_norm", "k_norm"] if qk_norm else [])
    x, _ = jax.lax.scan(layer, x, {n: w[n] for n in names}, length=L)
    return rms(x, w["final_norm"], eps)


# -- costs --------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class Shape:
    """The widths of a dense GQA decoder, as a configuration states them."""
    layers: int
    d_model: int
    heads: int
    kv_heads: int
    head_dim: int
    d_ff: int
    vocab: int
    act_bytes: int = 2      # bf16 activations, weights and KV

    @property
    def layer_matmul_params(self) -> int:
        d, hd, kvd = self.d_model, self.heads * self.head_dim, \
            self.kv_heads * self.head_dim
        return d * hd + 2 * d * kvd + hd * d + 3 * d * self.d_ff


def shape_of(config: dict) -> Shape:
    c = config
    return Shape(
        layers=c["num_hidden_layers"], d_model=c["hidden_size"],
        heads=c["num_attention_heads"],
        kv_heads=c["num_key_value_heads"], head_dim=c["head_dim"],
        d_ff=c["intermediate_size"], vocab=c["vocab_size"])


def _qk_norm(config: dict) -> bool:
    return bool(config.get("qk_norm", False))


def paged_attention(config: dict, ctx_lens) -> tuple[float, float]:
    """(flops, bytes) of one decode step's attention over all layers.

    ctx_lens: for each active slot, the positions its query attends to
    (its context length including the token written this step).  Per
    layer and slot: QK^T and PV at 2 flops per multiply-add each over
    ``ctx * head_dim`` for every q-head, the slot's K and V for each
    kv-head, and its q read and output written."""
    tot = float(sum(ctx_lens))
    n = len(ctx_lens)
    s = shape_of(config)
    flops = 4.0 * tot * s.heads * s.head_dim * s.layers
    kv = 2.0 * tot * s.kv_heads * s.head_dim * s.act_bytes
    qo = 2.0 * n * s.heads * s.head_dim * s.act_bytes
    return flops, (kv + qo) * s.layers


def mlp_chain(config: dict, rows: int) -> tuple[float, float]:
    """(flops, bytes) of one SwiGLU chain per layer, over all layers:
    the gate, up and down matrices read once and ``rows`` activations
    read and written."""
    s = shape_of(config)
    flops = 6.0 * rows * s.d_model * s.d_ff
    nbytes = (3.0 * s.d_model * s.d_ff + 2.0 * rows * s.d_model) \
        * s.act_bytes
    return flops * s.layers, nbytes * s.layers


def decode_model_flops(config: dict, ctx_lens) -> float:
    """Model FLOPs of one decode step for the active slots only: every
    matmul of every layer and the unembedding, plus attention over each
    slot's context."""
    s = shape_of(config)
    n = len(ctx_lens)
    dense = 2.0 * n * (s.layer_matmul_params * s.layers
                       + s.d_model * s.vocab)
    return dense + paged_attention(config, ctx_lens)[0]


def prefill_model_flops(config: dict, prompt_len: int) -> float:
    """Model FLOPs of prefilling ``prompt_len`` real tokens: every
    matmul for each token, causal attention (token i attends to i + 1
    positions), and one row of logits (the engine unembeds only the
    last token)."""
    s = shape_of(config)
    p = prompt_len
    dense = 2.0 * p * s.layer_matmul_params * s.layers
    attn_pairs = p * (p + 1) / 2.0
    attn = 4.0 * attn_pairs * s.heads * s.head_dim * s.layers
    return dense + attn + 2.0 * s.d_model * s.vocab
