"""The plain reference against the engine at small sizes on the CPU, for
every architecture module of ``harness/arch/`` at its ``SMOKE`` size:
prefill, then decode through the paged cache; the weights as the program
gets them; the float8 control, which the comparison has to fail.  Also:
the dense GQA draw and reference give what the harness gave before the
architecture modules, and the reference spread over four devices reads
as on one."""
import hashlib
import json
import os
import subprocess
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from chipbench_helpers import ROOT, arch_id, tiny_cell

from harness import check, reference, serve_loop, spec, weights as W

ARCHS = spec.arch_modules()


def _engine(config, seed):
    cell = tiny_cell(config=config)
    r = serve_loop.Runner(cell, seed, 1.0, False, time.perf_counter())
    r.build()
    canon, _, _ = serve_loop.make_weights(cell, seed)
    return r, cell, jax.jit(canon)(W.seed_key(seed))


def _serve(eng, prompts, max_new):
    for p in prompts:
        eng.submit(p, max_new)
    while eng.queue or any(s is not None for s in eng.slots):
        eng.step()


@pytest.mark.parametrize("arch", ARCHS, ids=arch_id)
def test_engine_prefill_and_paged_decode_match_the_reference(arch):
    """float32 end to end: the engine's prefill logits and every decode
    step's logits through the paged cache agree with the reference's
    full forward pass to float32 rounding."""
    r, cell, w = _engine(dict(arch.SMOKE, torch_dtype="float32"), 5)
    eng = r.engine
    seen = []
    exec_ = eng._exec

    def capture(phase, *args):
        logits, cache = exec_(phase, *args)
        seen.append((phase, np.asarray(logits)[0]))
        return logits, cache
    eng._exec = capture
    prompt = np.arange(3, 3 + 21, dtype=np.int32) * 7 % 512
    _serve(eng, [prompt], 6)
    tokens = eng.finished[-1].tokens
    seq = np.concatenate([prompt, tokens[:-1]]).astype(np.int32)
    ref = np.asarray(reference.logits(w, jnp.asarray(seq), arch.hidden,
                                      reference.cfg_key(cell.config)))
    assert [p for p, _ in seen] == ["prefill"] + ["decode"] * 5
    scale = np.abs(ref).max()
    for k, (_, got) in enumerate(seen):
        want = ref[len(prompt) - 1 + k]
        assert np.abs(got - want).max() <= 1e-4 * scale, k
        assert int(np.argmax(got)) == tokens[k]


@pytest.mark.parametrize("arch", ARCHS, ids=arch_id)
def test_weights_reach_the_program_unchanged(arch):
    """Every leaf of the program's tree is one leaf of the canonical
    draw, each used once: a matrix as drawn, in the served dtype, and an
    RMSNorm gain g as w = g - 1 exactly (the program scales by 1 + w).
    A seed past 2**31 draws other values."""
    cell = tiny_cell(config=arch.SMOKE)
    canon, program, _ = serve_loop.make_weights(cell, 9)
    c = jax.jit(canon)(W.seed_key(9))
    p = jax.tree.leaves(jax.jit(program)(W.seed_key(9)))
    served = jnp.dtype(arch.SMOKE["torch_dtype"])
    unused = {}
    for k, v in c.items():
        v = np.asarray(v)
        assert (v.dtype == np.float32) if W.is_gain(k) else (v.dtype == served)
        unused[k] = v - np.float32(1.0) if W.is_gain(k) else v
    assert len(p) == len(c)
    for leaf in map(np.asarray, p):
        match = next(k for k, v in unused.items() if v.dtype == leaf.dtype
                     and v.size == leaf.size
                     and np.array_equal(v.ravel(), leaf.ravel()))
        del unused[match]
    big = jax.jit(canon)(W.seed_key(2**31 + 9))
    assert not np.array_equal(np.asarray(big["embed"]),
                              np.asarray(c["embed"]))


@pytest.mark.parametrize("arch", ARCHS, ids=arch_id)
def test_the_float8_control_fails_the_comparison(arch):
    """At the module's ``SMOKE`` widths (bf16 program), the served
    tokens' widest gap stays small and the control's gap is several
    times larger and above the limit set between them."""
    cfg = arch.SMOKE
    r, cell, w = _engine(cfg, 13)
    eng = r.engine
    vocab = cfg["vocab_size"]
    rng = np.random.Generator(np.random.PCG64(13))
    prompts = [rng.integers(0, vocab, n, dtype=np.int32) for n in (40, 24, 33)]
    _serve(eng, prompts, 24)
    sample = []
    for i, f in enumerate(sorted(eng.finished, key=lambda f: f.rid)):
        q = serve_loop.Req(i, 0.0, f.prompt_len, 24)
        q.tokens = f.tokens
        sample.append(q)

    class Prompts:
        def __init__(self, prompts):
            self.p = prompts

        def tokens(self, req, vocab):
            return self.p[req.index]

    seqs = check.sequences(sample, Prompts(prompts), vocab)
    gap, ctl, n = check.gaps(w, cell, seqs, control=True)
    assert n == 3 * 24
    assert ctl > 3 * gap
    assert ctl > LIMIT >= gap


# readings at dense_gqa's SMOKE size on the CPU: served gap 0.0, control
# 0.0719; a module's SMOKE is wide enough for its control to read above
LIMIT = 0.03

# What the harness drew and the reference read before the architecture
# modules, at dense_gqa's SMOKE widths: a sha256 over each leaf's name,
# dtype, shape and bytes (leaves by sorted name), for Qwen3 and for
# Mistral (no q/k norm) at two seeds; a sha256 of the sorted
# [name, shape, dtype] list of each configuration file's draw; and the
# sum and largest of the served and control gaps over 512 seeded tokens.
DRAWS = {
    ("Qwen3ForCausalLM", 9):
        "73044ff673e711d2aff2d01649bb5a6bfbdfcccc4022d544b8afbb075d2f3778",
    ("Qwen3ForCausalLM", 2**31 + 9):
        "eb9e325b82f20b51283427df1087bbdeefa2f40520cd3445a9ab589ebf44c6da",
    ("MistralForCausalLM", 9):
        "2dc1329da983f5d19e290b7ce4ce9a9c642d8469256828114417837868555013",
    ("MistralForCausalLM", 2**31 + 9):
        "7d765728a677441e465bf5e012d14eee229eb2cff97356f0e3ef07e765b37eec",
}
LAYOUTS = {
    "qwen3-8b-4L":
        "0075d930a0101005592b4871cdbcd4a2433ab47fb14e7c56e81e36ffd9446065",
    "mistral-nemo-12b-4L":
        "f8f11c20289d009778ef16aec0e7bf8050abb077d1c8d7a6d1f23102e9511062",
    "qwen3-8b-tp4":
        "5dd72131ef8f48471a02c20d290d5554baac8cbb5252fc288ef7ad5c29863717",
}
GAPS = {"served": (2.1400084495544434, 567.945068359375),
        "control": (0.1073768138885498, 2.4760007858276367)}


def _dense_smoke(name):
    arch = spec.architecture({"architectures": [name]})
    return dict(arch.SMOKE, architectures=[name],
                qk_norm=name == "Qwen3ForCausalLM")


@pytest.mark.parametrize("name,seed", sorted(DRAWS), ids=str)
def test_the_dense_draw_is_bit_identical_to_the_earlier_one(name, seed):
    canon = serve_loop.make_weights(tiny_cell(config=_dense_smoke(name)),
                                    seed)[0]
    c = jax.jit(canon)(W.seed_key(seed))
    h = hashlib.sha256()
    for k in sorted(c):
        a = np.asarray(c[k])
        h.update(f"{k}:{a.dtype}:{a.shape}".encode())
        h.update(a.tobytes())
    assert h.hexdigest() == DRAWS[(name, seed)]


@pytest.mark.parametrize("config", sorted(LAYOUTS))
def test_each_configuration_draws_the_earlier_leaves(config):
    """Same leaf names, so the same ``fold_in`` order, shapes and dtypes
    for every configuration file of the dense architecture."""
    cfg = json.loads((spec.CHIP / "configs" / f"{config}.json").read_text())
    canon = serve_loop.make_weights(tiny_cell(config=cfg), 0)[0]
    s = jax.eval_shape(canon, W.seed_key(0))
    layout = [[k, list(v.shape), str(v.dtype)] for k, v in sorted(s.items())]
    assert hashlib.sha256(json.dumps(layout).encode()).hexdigest() == \
        LAYOUTS[config]


def test_the_dense_reference_reads_the_earlier_gaps():
    cell = tiny_cell(config=_dense_smoke("Qwen3ForCausalLM"))
    w = jax.jit(serve_loop.make_weights(cell, 0)[0])(W.seed_key(17))
    rng = np.random.Generator(np.random.PCG64(17))
    toks = jnp.asarray(rng.integers(0, 2048, 512).astype(np.int32))
    tg = jnp.asarray(rng.integers(0, 2048, 512).astype(np.int32))
    g_s, g_c = reference.gaps(w, toks, tg, cell.arch.hidden,
                              reference.cfg_key(cell.config), True)
    for got, (top, total) in ((g_s, GAPS["served"]), (g_c, GAPS["control"])):
        got = np.asarray(got)
        np.testing.assert_allclose([got.max(), got.sum()], [top, total],
                                   rtol=1e-5)


FOUR_CHIPS = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
import json, sys
sys.path.insert(0, sys.argv[1])
import numpy as np
import jax
from chipbench_helpers import TINY_CONFIG, tiny_cell
from harness import check, serve_loop, weights as W

cell = tiny_cell(config=dict(TINY_CONFIG, num_attention_heads=8,
                             num_key_value_heads=4, hidden_size=128,
                             initializer_range=0.16))
seed = 2**31 + 21
plain = jax.jit(serve_loop.make_weights(cell, seed)[0])(W.seed_key(seed))
one = serve_loop.reference_weights(cell, seed, jax.devices()[:1])
four = serve_loop.reference_weights(cell, seed, jax.devices()[:4])
rng = np.random.Generator(np.random.PCG64(seed))
seqs = []
for n, first in ((40, 20), (600, 500)):
    seq = rng.integers(0, 512, n).astype(np.int32)
    seqs.append((seq, rng.integers(0, 512, n).astype(np.int32), first))
g1 = [check.gaps(one, cell, [s], control=True) for s in seqs]
g4 = [check.gaps(four, cell, [s], control=True) for s in seqs]
print("RESULT " + json.dumps({
    "same_draw": all(np.array_equal(np.asarray(plain[k]), np.asarray(one[k]))
                     and np.array_equal(np.asarray(plain[k]),
                                        np.asarray(four[k])) for k in plain),
    "split": sorted(k for k, v in four.items()
                    if v.addressable_shards[0].data.shape != v.shape),
    "one": [g[:2] for g in g1], "four": [g[:2] for g in g4]}))
"""


def test_the_reference_on_four_chips_reads_as_on_one():
    """On four forced host devices the reference's weights are the same
    draw, every matrix a quarter on each device, and its gaps (served
    and control) equal the one-device reference's to float32 rounding."""
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("XLA_FLAGS", None)
    p = subprocess.run(
        [sys.executable, "-c", FOUR_CHIPS, str(ROOT / "tests" / "chipbench")],
        env=env, capture_output=True, text=True, timeout=600)
    assert p.returncode == 0, p.stderr[-3000:]
    out = json.loads(next(ln for ln in p.stdout.splitlines()
                          if ln.startswith("RESULT "))[len("RESULT "):])
    assert out["same_draw"]
    assert out["split"] == ["embed", "lm_head", "w_down", "w_gate", "w_up",
                            "wk", "wo", "wq", "wv"]
    for a, b in zip(out["one"], out["four"]):
        assert a[0] > 0 and a[1] > 0
        np.testing.assert_allclose(b, a, rtol=1e-5, atol=1e-5)
