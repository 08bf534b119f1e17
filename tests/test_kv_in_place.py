"""The paged KV pool is updated in place through the layer scan
(docs/serving.md §Page-table layout).

The stacked pools ride the scan's carry with the layer index; each
attention site writes this step's rows into its layer and reads that
layer from the stack.  So the compiled step program holds no copy of a
layer's pool, and at most one pass over each stacked pool remains: the
entry copy of the parameter, which only donating the cache removes.
The kernel, the gather path and the XLA twin read a layer of the stack
bit for bit as they read that layer's pool alone.
"""
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.kernels.attention import fused_attention_paged
from repro.models import layers as L
from repro.models.lm import LM, Runtime
from repro.serving import ServingEngine
from repro.serving import kv_pages as KP

CFG = get_config("qwen3_8b", smoke=True)
N_PAGES, PAGE, MAX_PAGES = 11, 4, 6

_COMPUTATION = re.compile(r"(?:ENTRY\s+)?%([\w.\-]+) \(")
_INSTRUCTION = re.compile(
    r"\s+(ROOT\s+)?%[\w.\-]+ = \w+\[([\d,]*)\](?:\{[^}]*\})? ([\w\-]+)\(")


def _extent(dims) -> tuple:
    """The dims of a shape in any order, unit dims left out: a
    transposed, relaid or one-layer-sliced copy of an array has the
    extent of the array."""
    return tuple(sorted(d for d in dims if d != 1))


def _instructions(hlo: str):
    """(opcode, extent, root opcode of the computation it calls) of
    every array-valued instruction of an optimized HLO module."""
    comp, roots, found = None, {}, []
    for line in hlo.splitlines():
        if not line.startswith(" "):
            m = _COMPUTATION.match(line)
            comp = m.group(1) if m else None
            continue
        m = _INSTRUCTION.match(line)
        if m is None:
            continue
        dims = _extent(int(d) for d in m.group(2).split(",") if d)
        calls = re.search(r"calls=%([\w.\-]+)", line)
        found.append((m.group(3), dims, calls and calls.group(1)))
        if m.group(1):
            roots[comp] = m.group(3)
    return [(op, dims, roots.get(called)) for op, dims, called in found]


def _step_hlo(planner: bool, phase: str, plan_dir, monkeypatch):
    """The optimized HLO of a paged step program and the shape of its
    first stacked pool."""
    from repro.core import planner as planner_mod
    monkeypatch.setenv("REPRO_CACHE_DIR", str(plan_dir))
    planner_mod.clear_memo()
    model = LM(CFG, Runtime(planner=planner))
    params = model.init_params(jax.random.PRNGKey(0))
    cache = model.init_paged_cache(N_PAGES, PAGE)
    if phase == "decode":
        b = 3
        fn, args = model.decode_step_paged, (
            params, cache, jnp.zeros((b,), jnp.int32),
            jnp.zeros((b,), jnp.int32),
            jnp.zeros((b, MAX_PAGES), jnp.int32))
    else:
        fn, args = model.prefill_paged, (
            params, jnp.zeros((1, 2 * PAGE), jnp.int32), cache,
            jnp.zeros((1, MAX_PAGES), jnp.int32), jnp.int32(5))
    hlo = jax.jit(fn).lower(*args).compile().as_text()
    planner_mod.clear_memo()
    return hlo, cache["stack"][0]["k_pages"].shape


@pytest.mark.parametrize("planner", [False, True], ids=["twin", "planner"])
@pytest.mark.parametrize("phase", ["decode", "prefill"])
def test_step_program_updates_pool_in_place(planner, phase, tmp_path,
                                            monkeypatch):
    """No op of the step program makes a layer's pool, in any order of
    its dims (only a scatter may have that extent, and here none does),
    and every stacked pool is materialised at most once: the entry
    copy of the parameter.  The in-loop scatter writes into the carried
    stack and is not a copy."""
    hlo, shape = _step_hlo(planner, phase, tmp_path, monkeypatch)
    stacked, layer = _extent(shape), _extent(shape[1:])
    ops = _instructions(hlo)
    per_layer = [op for op, dims, _ in ops
                 if dims == layer and op != "scatter"]
    assert per_layer == [], per_layer
    passes = [(op, root) for op, dims, root in ops if dims == stacked
              and op not in ("parameter", "get-tuple-element", "bitcast",
                             "scatter")
              and not (op == "fusion" and root == "scatter")]
    n_pools = 2                       # one k and one v stack, one site
    assert len(passes) <= n_pools, passes
    assert sum(op == "scatter" or root == "scatter"
               for op, dims, root in ops if dims == stacked) >= n_pools


def _stacked_pools(rng, n_layers, b, hkv, d, lengths):
    """Random page-major stacked pools (every layer different) and a
    shuffled page table covering ``lengths``."""
    shape = (n_layers, N_PAGES, PAGE, hkv, d)
    k = jnp.asarray(rng.randn(*shape), jnp.float32)
    v = jnp.asarray(rng.randn(*shape), jnp.float32)
    order = list(rng.permutation(N_PAGES - 1) + 1)
    table = np.full((b, MAX_PAGES), -1, np.int32)
    for i, n in enumerate(lengths):
        for j in range(-(-n // PAGE)):
            table[i, j] = order.pop()
    return k, v, jnp.asarray(table)


LAYERS = [0, 2]          # the first and the last of a stack of three


@pytest.mark.parametrize("layer", LAYERS)
@pytest.mark.parametrize("m,bkv", [(1, 8), (4, 8)],
                         ids=["decode_kernel", "gather"])
def test_kernel_reads_layer_from_stack(layer, m, bkv):
    """``fused_attention_paged`` over the stacked pools at ``layer``
    equals it over that layer's pools alone, bitwise: the in-place
    decode kernel (m = 1) and the gather + partial path (m = 4)."""
    rng = np.random.RandomState(10 + layer + m)
    b, hq, hkv, d = 2, 4, 2, 8
    lengths = [13, 22]
    k, v, table = _stacked_pools(rng, 3, b, hkv, d, lengths)
    q = jnp.asarray(rng.randn(b, hq, m, d), jnp.float32)
    larr = jnp.asarray(lengths, jnp.int32)
    got = fused_attention_paged(q, k, v, table, larr, jnp.int32(layer),
                                bq=4, bkv=bkv, interpret=True)
    want = fused_attention_paged(q, k[layer], v[layer], table, larr,
                                 bq=4, bkv=bkv, interpret=True)
    assert np.array_equal(np.asarray(got), np.asarray(want))


@pytest.mark.parametrize("layer", LAYERS)
def test_xla_twin_reads_layer_from_stack(layer):
    """The XLA twin of ``_paged_attention_body`` reads a layer of the
    stack bitwise as it reads that layer's pool alone."""
    rng = np.random.RandomState(20 + layer)
    b, hq, hkv, d = 2, 4, 2, 8
    lengths = [9, 17]
    k, v, table = _stacked_pools(rng, 3, b, hkv, d, lengths)
    qt = jnp.asarray(rng.randn(b, hq, 1, d), jnp.float32)
    positions = jnp.asarray(lengths, jnp.int32)[:, None] - 1
    kw = dict(group=hq // hkv, win=0, scale=d ** -0.5,
              rules=Runtime().rules)
    got = L._paged_attention_body(
        qt, {"k_pages": k, "v_pages": v, "layer": jnp.int32(layer)},
        table, positions, **kw)
    want = L._paged_attention_body(
        qt, {"k_pages": k[layer], "v_pages": v[layer]}, table, positions,
        **kw)
    assert np.array_equal(np.asarray(got), np.asarray(want))


@pytest.mark.parametrize("layer", LAYERS)
def test_scatter_writes_only_its_layer(layer):
    """A write into layer ``layer`` of the stack is that layer's write
    alone; the other layers are left as they were."""
    rng = np.random.RandomState(30 + layer)
    hkv, d = 2, 8
    k, _, table = _stacked_pools(rng, 3, 2, hkv, d, [7, 3])
    positions = jnp.asarray([[5, 6], [2, -1]], jnp.int32)
    phys, off = KP.slot_coords(table, positions, PAGE)
    rows = jnp.asarray(rng.randn(2, 2, hkv, d), jnp.float32)
    got = KP.scatter_pages(k, phys, off, rows, jnp.int32(layer))
    want = k.at[layer].set(KP.scatter_pages(k[layer], phys, off, rows))
    assert np.array_equal(np.asarray(got), np.asarray(want))


def test_engine_records_step_temporaries():
    """The engine keeps each compiled step program's temporary bytes
    beside its compile seconds.  With the pool updated in place, the
    decode program holds less than one layer's pool in temporaries."""
    model = LM(CFG)
    params = model.init_params(jax.random.PRNGKey(0))
    n_pages = 2048                    # a layer's pool outweighs the rest
    eng = ServingEngine(model, params, max_batch=2, page_size=PAGE,
                        n_pages=n_pages, max_pages_per_seq=4,
                        choose_regime=False)
    eng.run([(np.arange(5, dtype=np.int32), 3)])
    assert set(eng.temp_bytes) == set(eng.programs) == set(eng.compile_s)
    layer_pool = n_pages * PAGE * CFG.n_kv_heads * CFG.dh * 4
    decode = [t for key, t in eng.temp_bytes.items() if key[1] == "decode"]
    assert decode and all(0 <= t < layer_pool for t in decode)
