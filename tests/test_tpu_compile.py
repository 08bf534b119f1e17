"""Compile the main path's fused kernels for a TPU v5e, without the chip.

The interpret-mode tests run the kernels' dataflow on the CPU but never
ask Mosaic whether it accepts them.  Here each kernel is lowered and
compiled for a v5e that is described, not attached
(``jax.experimental.topologies``), at qwen3-8b widths, with the tiles
the tuner picks — the tiles and block shapes the chip would be handed.  A kernel whose blocks break
the TPU tiling rules, or whose tiles overrun VMEM, fails here at no chip
time.

The topology is described inside a module-scoped fixture, never at
import: only one process may load the TPU library, and the test workers
all import this file.
"""
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.core import api
from repro.core.perf_model import V5E, vmem_estimate

D_MODEL, D_FF, HEADS, KV_HEADS, HEAD_DIM = 4096, 12288, 32, 8, 128
BF16 = "bfloat16"


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - any failure means no topology
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip can be written to the persistent
    # cache but never read back without one: keep the cache out of it
    jax.config.update("jax_enable_compilation_cache", False)
    return SingleDeviceSharding(topo.devices[0])


def _shape(sharding, shape, dtype=jnp.bfloat16):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _compile(fn, kernel: str, *args):
    """Compile ``fn`` for the described chip; its Mosaic kernels must
    carry the instruction name ``kernel`` that the chip's trace shows
    and the benchmark's breakdown keys on."""
    compiled = jax.jit(fn).lower(*args).compile()
    names = [re.match(r"\s*(?:ROOT\s+)?%([\w.\-]+) = ", line).group(1)
             for line in compiled.as_text().splitlines()
             if "tpu_custom_call" in line]
    assert names and {re.sub(r"\.\d+$", "", n) for n in names} == {kernel}
    return compiled


def _with_vmem_limit(monkeypatch, module, limit: int):
    """Compile ``module``'s kernels under ``limit`` bytes of VMEM."""
    from dataclasses import replace
    monkeypatch.setattr(module, "device_spec",
                        lambda: replace(V5E, vmem_budget=limit))
    jax.clear_caches()


@pytest.mark.parametrize("m", [256, 2048])
def test_mlp_chain_compiles(one_chip, m):
    from repro.kernels.gemm_chain import fused_mlp_chain
    tk = api.fuse_mlp_chain(m, D_FF, D_MODEL, dtype=BF16, gated=True,
                            act="silu", interpret=False)
    kw = tk.params.as_kwargs()
    assert vmem_estimate(tk.report.best, V5E) <= V5E.vmem_budget
    _compile(lambda a, wu, wd, wg: fused_mlp_chain(a, wu, wd, wg=wg, **kw),
             "fused_mlp_chain",
             _shape(one_chip, (1, m, D_MODEL)),
             _shape(one_chip, (1, D_MODEL, D_FF)),
             _shape(one_chip, (1, D_FF, D_MODEL)),
             _shape(one_chip, (1, D_MODEL, D_FF)))


@pytest.mark.parametrize("m", [256, 2048])
def test_mlp_chain_vmem_estimate_covers_mosaic(one_chip, monkeypatch, m):
    """Rule 4's estimate is at least what Mosaic allocates: the tuned
    kernel still compiles with VMEM limited to the estimate itself."""
    from repro.kernels import gemm_chain
    tk = api.fuse_mlp_chain(m, D_FF, D_MODEL, dtype=BF16, gated=True,
                            act="silu", interpret=False)
    kw = tk.params.as_kwargs()
    _with_vmem_limit(monkeypatch, gemm_chain,
                     vmem_estimate(tk.report.best, V5E))
    _compile(lambda a, wu, wd, wg: gemm_chain.fused_mlp_chain(
                 a, wu, wd, wg=wg, **kw), "fused_mlp_chain",
             _shape(one_chip, (1, m, D_MODEL)),
             _shape(one_chip, (1, D_MODEL, D_FF)),
             _shape(one_chip, (1, D_FF, D_MODEL)),
             _shape(one_chip, (1, D_MODEL, D_FF)))


def test_paged_decode_attention_compiles(one_chip):
    """The engine's decode kernel at B=8 (the (1, bkv) position block
    over a (B, N) array was refused here at B > 1)."""
    from repro.kernels.attention import fused_attention_paged
    b, page, pages_per_seq = 8, 16, 64
    n_pages = b * pages_per_seq + 1
    tk = api.fuse_attention_paged(1, page * pages_per_seq, HEAD_DIM,
                                  HEAD_DIM, page_size=page, heads=HEADS,
                                  batch=b, dtype=BF16, causal=True,
                                  interpret=False)
    kw = tk.params.as_kwargs()
    _compile(lambda q, kp, vp, tbl, ln: fused_attention_paged(
                 q, kp, vp, tbl, ln, **kw), "paged_decode_attention",
             _shape(one_chip, (b, HEADS, 1, HEAD_DIM)),
             _shape(one_chip, (n_pages, page, KV_HEADS, HEAD_DIM)),
             _shape(one_chip, (n_pages, page, KV_HEADS, HEAD_DIM)),
             _shape(one_chip, (b, pages_per_seq), jnp.int32),
             _shape(one_chip, (b,), jnp.int32))


@pytest.mark.parametrize("b,pages_per_seq", [(64, 80), (48, 256)])
def test_paged_decode_attention_compiles_at_cell_shapes(one_chip, b,
                                                        pages_per_seq):
    """The benchmark cells' decode shapes (qwen3-8b chat: 64 slots of
    1280; mistral-nemo reason: 48 of 4096) with the tuner's tiles.  The
    pools are the serving step's: stacked for the cells' 4 layers and
    page-major (layer, page, slot, kv-head, dim), the order the kv
    scatter writes.  The kernel reads a traced layer's pages in place,
    so the program stages neither a gathered table nor a copy of a
    layer's pool."""
    from repro.kernels.attention import fused_attention_paged
    page, n_layers = 16, 4
    n_pages = b * pages_per_seq + 1
    pool = _shape(one_chip, (n_layers, n_pages, page, KV_HEADS, HEAD_DIM))
    tk = api.fuse_attention_paged(1, page * pages_per_seq, HEAD_DIM,
                                  HEAD_DIM, page_size=page, heads=HEADS,
                                  batch=b, dtype=BF16, causal=True,
                                  hw=V5E, interpret=False)
    kw = tk.params.as_kwargs()
    compiled = _compile(
        lambda q, kp, vp, tbl, ln, layer: fused_attention_paged(
            q, kp, vp, tbl, ln, layer, **kw), "paged_decode_attention",
        _shape(one_chip, (b, HEADS, 1, HEAD_DIM)), pool, pool,
        _shape(one_chip, (b, pages_per_seq), jnp.int32),
        _shape(one_chip, (b,), jnp.int32),
        _shape(one_chip, (), jnp.int32))
    assert compiled.memory_analysis().temp_size_in_bytes < 1 << 20


@pytest.mark.parametrize("seq", [512, 2048])
def test_prefill_attention_compiles(one_chip, seq):
    from repro.kernels.attention import fused_attention
    tk = api.fuse_attention(seq, seq, HEAD_DIM, HEAD_DIM, heads=HEADS,
                            batch=1, dtype=BF16, causal=True,
                            interpret=False)
    kw = tk.params.as_kwargs()
    _compile(lambda q, k, v: fused_attention(q, k, v, causal=True, **kw),
             "fused_attention",
             _shape(one_chip, (1, HEADS, seq, HEAD_DIM)),
             _shape(one_chip, (1, KV_HEADS, seq, HEAD_DIM)),
             _shape(one_chip, (1, KV_HEADS, seq, HEAD_DIM)))


def test_gemm_chain_compiles(one_chip):
    from repro.kernels.gemm_chain import fused_gemm_chain
    m = 2048
    tk = api.fuse_gemm_chain(m, D_FF, D_MODEL, D_MODEL, dtype=BF16,
                             interpret=False)
    kw = tk.params.as_kwargs()
    _compile(lambda a, b, d: fused_gemm_chain(a, b, d, **kw),
             "fused_gemm_chain",
             _shape(one_chip, (1, m, D_MODEL)),
             _shape(one_chip, (1, D_MODEL, D_FF)),
             _shape(one_chip, (1, D_FF, D_MODEL)))


def test_gemm_chain3_compiles(one_chip):
    """The three-GEMM chain at its default tiles, small trailing dims
    held whole in VMEM."""
    from repro.kernels.gemm_chain3 import fused_gemm_chain3
    m, k, n, h = 1024, D_MODEL, 1024, 256
    _compile(fused_gemm_chain3, "fused_gemm_chain3",
             _shape(one_chip, (1, m, k)), _shape(one_chip, (1, k, n)),
             _shape(one_chip, (1, n, h)), _shape(one_chip, (1, h, h)))


def _pallas_vmem_limits(jaxpr) -> list:
    out = []
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "pallas_call":
            out.append(eqn.params["compiler_params"]["mosaic_tpu"]
                       .vmem_limit_bytes)
        for v in eqn.params.values():
            inner = getattr(v, "jaxpr", None)
            if inner is not None:
                out += _pallas_vmem_limits(getattr(inner, "jaxpr", inner))
    return out


def test_budget_is_every_kernels_vmem_limit():
    """Every kernel hands Mosaic the budget Rule 4 prices against, and
    that budget fits the chip."""
    from repro.kernels.attention import (fused_attention,
                                         fused_attention_paged)
    from repro.kernels.gemm_chain import fused_gemm_chain, fused_mlp_chain
    from repro.kernels.gemm_chain3 import fused_gemm_chain3
    assert V5E.vmem_budget <= V5E.vmem_bytes
    z = jnp.zeros
    a, w = z((1, 256, 256)), z((1, 256, 256))
    q, kv = z((2, 4, 8, 128)), z((2, 2, 128, 128))
    pages, tbl = z((9, 16, 2, 128)), jnp.zeros((2, 4), jnp.int32)
    calls = [
        lambda: fused_gemm_chain(a, w, w, style="flat", interpret=True),
        lambda: fused_gemm_chain(a, w, w, style="deep", interpret=True),
        lambda: fused_mlp_chain(a, w, w, wg=w, interpret=True),
        lambda: fused_gemm_chain3(a, w, w, w, interpret=True),
        lambda: fused_attention(q, kv, kv, causal=True, interpret=True),
        lambda: fused_attention_paged(q[:, :, :1], pages, pages, tbl,
                                      jnp.full((2,), 40, jnp.int32),
                                      interpret=True),
    ]
    for call in calls:
        limits = _pallas_vmem_limits(jax.make_jaxpr(call)().jaxpr)
        assert limits and all(x == V5E.vmem_budget for x in limits)
