"""The plain reference against the engine at a small size on the CPU:
prefill, then decode through the paged cache; the float8 control, which
the comparison has to fail; and the reference spread over four devices,
which reads as on one."""
import json
import os
import subprocess
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np

from chipbench_helpers import ROOT, TINY_CONFIG, tiny_cell

from harness import check, reference, serve_loop, weights as W

F32 = dict(TINY_CONFIG, torch_dtype="float32")


def _engine(config, seed):
    cell = tiny_cell(config=config)
    r = serve_loop.Runner(cell, seed, 1.0, False, time.perf_counter())
    r.build()
    canon, _, _ = serve_loop.make_weights(cell, seed)
    return r, cell, jax.jit(canon)(W.seed_key(seed))


def test_engine_prefill_and_paged_decode_match_the_reference():
    """float32 end to end: the engine's prefill logits and every decode
    step's logits through the paged cache agree with the reference's
    full forward pass to float32 rounding."""
    r, cell, w = _engine(F32, 5)
    eng = r.engine
    seen = []
    exec_ = eng._exec

    def capture(phase, *args):
        logits, cache = exec_(phase, *args)
        seen.append((phase, np.asarray(logits)[0]))
        return logits, cache
    eng._exec = capture
    prompt = np.arange(3, 3 + 21, dtype=np.int32) * 7 % 512
    eng.submit(prompt, 6)
    while eng.queue or any(s is not None for s in eng.slots):
        eng.step()
    tokens = eng.finished[-1].tokens
    seq = np.concatenate([prompt, tokens[:-1]]).astype(np.int32)
    ref = np.asarray(reference.logits(w, jnp.asarray(seq),
                                      reference.cfg_items(cell.config)))
    assert [p for p, _ in seen] == ["prefill"] + ["decode"] * 5
    scale = np.abs(ref).max()
    for k, (_, got) in enumerate(seen):
        want = ref[len(prompt) - 1 + k]
        assert np.abs(got - want).max() <= 1e-4 * scale, k
        assert int(np.argmax(got)) == tokens[k]


def test_weights_reach_the_program_unchanged():
    """The program's RMSNorm weight w scales by 1 + w: the adapter gives
    w = g - 1 exactly, and every matrix is the canonical draw."""
    cell = tiny_cell()
    canon, program, _ = serve_loop.make_weights(cell, 9)
    c = jax.jit(canon)(W.seed_key(9))
    p = jax.jit(program)(W.seed_key(9))
    g = np.asarray(c["attn_norm"])
    np.testing.assert_array_equal(
        1.0 + np.asarray(p["stack"]["b0_attn"]["ln1"]["w"]), g)
    np.testing.assert_array_equal(np.asarray(p["lm_head"]),
                                  np.asarray(c["lm_head"]))
    assert np.asarray(c["wq"]).dtype == jnp.bfloat16
    big = jax.jit(canon)(W.seed_key(2**31 + 9))
    assert not np.array_equal(np.asarray(big["embed"]),
                              np.asarray(c["embed"]))


def test_the_float8_control_fails_the_comparison():
    """At a width a test can hold (bf16 program, d_model 256, vocab 2048),
    the served tokens' widest gap stays small and the control's gap is
    several times larger and above the limit set between them."""
    cfg = dict(TINY_CONFIG, hidden_size=256, intermediate_size=512,
               num_attention_heads=4, num_key_value_heads=2, head_dim=64,
               vocab_size=2048)
    r, cell, w = _engine(cfg, 13)
    eng = r.engine
    rng = np.random.Generator(np.random.PCG64(13))
    prompts = [rng.integers(0, 2048, n, dtype=np.int32) for n in (40, 24, 33)]
    for p in prompts:
        eng.submit(p, 24)
    while eng.queue or any(s is not None for s in eng.slots):
        eng.step()
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

    seqs = check.sequences(sample, Prompts(prompts), 2048)
    gap, ctl, n = check.gaps(w, cell.config, seqs, control=True)
    assert n == 3 * 24
    assert ctl > 3 * gap
    assert ctl > LIMIT >= gap


# readings at this size on the CPU: served gap 0.0, control 0.0719
LIMIT = 0.03


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
g1 = [check.gaps(one, cell.config, [s], control=True) for s in seqs]
g4 = [check.gaps(four, cell.config, [s], control=True) for s in seqs]
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
