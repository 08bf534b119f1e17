"""End-to-end behaviour tests for the whole system (paper technique +
training/serving substrate wired together)."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.data.pipeline import DataConfig, TokenPipeline
from repro.launch import steps as S
from repro.launch.serve import generate
from repro.models.lm import Runtime
from repro.optim.adamw import AdamW, cosine_schedule


def test_end_to_end_training_reduces_loss(tmp_path):
    """Short real training run through the fault-tolerant runner:
    loss must drop and checkpoints must land."""
    from repro.ckpt import checkpoint as ckpt
    from repro.runtime.fault_tolerance import StepRunner

    cfg = get_config("qwen3_8b", smoke=True)
    model = S.build_model(cfg, Runtime(remat=False))
    opt = AdamW(lr=cosine_schedule(1e-2, warmup=2, total=30),
                weight_decay=0.0)
    params = model.init_params(jax.random.PRNGKey(0))
    opt_state = opt.init(params)
    pipe = TokenPipeline(DataConfig(vocab=cfg.vocab, seq_len=32,
                                    global_batch=4, seed=0))
    train_step = jax.jit(S.make_train_step(model, opt),
                         donate_argnums=(0, 1))
    losses = []

    def step_fn(state, batch):
        p, o = state
        b = {k: jnp.asarray(v) for k, v in batch.items()}
        p, o, info = train_step(p, o, b)
        losses.append(float(info["loss"]))
        return (p, o), {"loss": losses[-1]}

    runner = StepRunner(step_fn=step_fn, batch_at=pipe.batch_at,
                        ckpt_dir=str(tmp_path), ckpt_every=10)
    runner.run((params, opt_state), 20)
    assert ckpt.latest_step(str(tmp_path)) == 20
    assert np.mean(losses[-5:]) < np.mean(losses[:5]), losses


def test_end_to_end_generation():
    cfg = get_config("recurrentgemma_2b", smoke=True)
    model = S.build_model(cfg, Runtime(remat=False))
    params = model.init_params(jax.random.PRNGKey(0))
    prompts = jax.random.randint(jax.random.PRNGKey(1), (2, 24), 0,
                                 cfg.vocab)
    toks = generate(model, params, prompts, gen=8)
    assert toks.shape == (2, 8)
    assert np.all((toks >= 0) & (toks < cfg.vocab))
    # greedy decode is deterministic
    toks2 = generate(model, params, prompts, gen=8)
    np.testing.assert_array_equal(toks, toks2)


def test_mcfuser_attention_drives_model_numerics():
    """The model's streaming-attention path (the MCFuser fused-schedule
    twin) must agree with the naive unfused path on the same weights."""
    from repro.models.config import ModelConfig
    from repro.models.lm import LM

    base = ModelConfig("t", "dense", 2, 64, 4, 2, 128, 256,
                       dtype="float32")
    toks = jax.random.randint(jax.random.PRNGKey(1), (1, 96), 0, 256)
    m1 = LM(dataclasses.replace(base, use_fused_attention=True),
            Runtime(remat=False, bkv=32))   # 96 > 2*32 -> streaming
    m2 = LM(dataclasses.replace(base, use_fused_attention=False),
            Runtime(remat=False))
    params = m1.init_params(jax.random.PRNGKey(0))
    lf = m1.forward(params, toks)
    ln = m2.forward(params, toks)
    np.testing.assert_allclose(np.asarray(lf), np.asarray(ln),
                               rtol=2e-4, atol=2e-4)


@pytest.fixture
def restore_cache_dir():
    old = jax.config.jax_compilation_cache_dir
    yield
    jax.config.update("jax_compilation_cache_dir", old)


@pytest.mark.parametrize("env_set", [True, False])
def test_compile_cache_path(monkeypatch, tmp_path, restore_cache_dir,
                            env_set):
    """The persistent compilation cache stays where
    JAX_COMPILATION_CACHE_DIR says; unset, it goes to a fixed directory
    of the checkout, never to a temp name."""
    from repro.launch.compile_cache import CHECKOUT, enable_compile_cache
    before = jax.config.jax_compilation_cache_dir
    if env_set:
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
        assert enable_compile_cache() == str(tmp_path)
        assert jax.config.jax_compilation_cache_dir == before
    else:
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
        want = str(CHECKOUT / ".cache" / "jax")
        assert enable_compile_cache() == want
        assert jax.config.jax_compilation_cache_dir == want
        assert (CHECKOUT / "src" / "repro").is_dir()


def test_cut_layers_keeps_widths():
    from repro.launch.serve import cut_layers
    full = get_config("qwen3_8b")
    cut = cut_layers(full, 4)
    assert cut.n_layers == 4 and cut.d_model == full.d_model
    assert cut.d_ff == full.d_ff and cut.vocab == full.vocab
    with pytest.raises(ValueError):
        cut_layers(full, 0)
