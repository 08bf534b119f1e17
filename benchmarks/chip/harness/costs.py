"""Operations and bytes that the served work needs, from shapes alone.

"Needed" means what the algorithm must do for the real tokens: the K/V
of the positions a query may attend to, not the page table's full
width; the active slots, not the padded batch.  A kernel that moves
more than this reads below 100% of its roofline, which is the point.
Counts are per call of the whole model (all layers); the benchmark
keeps them here so that no change to the program can change them.
"""
from __future__ import annotations

import dataclasses


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


def paged_attention(shape: Shape, ctx_lens) -> tuple[float, float]:
    """(flops, bytes) of one decode step's attention over all layers.

    ctx_lens: for each active slot, the positions its query attends to
    (its context length including the token written this step).  Per
    layer and slot: QK^T and PV at 2 flops per multiply-add each over
    ``ctx * head_dim`` for every q-head, the slot's K and V for each
    kv-head, and its q read and output written."""
    tot = float(sum(ctx_lens))
    n = len(ctx_lens)
    s = shape
    flops = 4.0 * tot * s.heads * s.head_dim * s.layers
    kv = 2.0 * tot * s.kv_heads * s.head_dim * s.act_bytes
    qo = 2.0 * n * s.heads * s.head_dim * s.act_bytes
    return flops, (kv + qo) * s.layers


def mlp_chain(shape: Shape, rows: int) -> tuple[float, float]:
    """(flops, bytes) of one SwiGLU chain per layer, over all layers:
    the gate, up and down matrices read once and ``rows`` activations
    read and written."""
    s = shape
    flops = 6.0 * rows * s.d_model * s.d_ff
    nbytes = (3.0 * s.d_model * s.d_ff + 2.0 * rows * s.d_model) \
        * s.act_bytes
    return flops * s.layers, nbytes * s.layers


def decode_model_flops(shape: Shape, ctx_lens) -> float:
    """Model FLOPs of one decode step for the active slots only: every
    matmul of every layer and the unembedding, plus attention over each
    slot's context."""
    s = shape
    n = len(ctx_lens)
    dense = 2.0 * n * (s.layer_matmul_params * s.layers
                       + s.d_model * s.vocab)
    return dense + paged_attention(shape, ctx_lens)[0]


def prefill_model_flops(shape: Shape, prompt_len: int) -> float:
    """Model FLOPs of prefilling ``prompt_len`` real tokens: every
    matmul for each token, causal attention (token i attends to i + 1
    positions), and one row of logits (the engine unembeds only the
    last token)."""
    s = shape
    p = prompt_len
    dense = 2.0 * p * s.layer_matmul_params * s.layers
    attn_pairs = p * (p + 1) / 2.0
    attn = 4.0 * attn_pairs * s.heads * s.head_dim * s.layers
    return dense + attn + 2.0 * s.d_model * s.vocab
