"""Multi-device EXECUTION tests (subprocess: 8 forced host devices).

The dry-run proves lowering; these prove the sharded programs compute
the same numbers as the single-device reference — including the
distributed flash-decode path (SS Perf hillclimb #1).
"""
import json
import os
import subprocess
import sys

import pytest

SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import json
import jax, jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P
from repro.configs import get_config
from repro.dist.sharding import Rules
from repro.launch import steps as S
from repro.models.lm import LM, Runtime

cfg = get_config("qwen3_8b", smoke=True)
mesh = jax.make_mesh((2, 4), ("data", "model"),
                     axis_types=(jax.sharding.AxisType.Auto,) * 2)
out = {}

# --- sharded vs single-device train step --------------------------------
rules = Rules(data=("data",), model="model", tp="model", seq=None)
rt = Runtime(rules=rules, mesh=mesh, remat=False)
sh_model = LM(cfg, rt)
ref_model = LM(cfg, Runtime(remat=False))
params = ref_model.init_params(jax.random.PRNGKey(0))
toks = jax.random.randint(jax.random.PRNGKey(1), (4, 32), 0, cfg.vocab)
batch = {"tokens": toks, "labels": toks}

with jax.set_mesh(mesh):
    p_sh = S.shardings_for(mesh, sh_model.param_specs())
    params_sharded = jax.device_put(params, p_sh)
    loss_sh = jax.jit(sh_model.loss)(params_sharded, batch)
loss_ref = jax.jit(ref_model.loss)(params, batch)
out["loss_sharded"] = float(loss_sh)
out["loss_ref"] = float(loss_ref)

# --- distributed flash-decode vs reference decode -----------------------
with jax.set_mesh(mesh):
    dd_model = LM(cfg, Runtime(rules=rules, mesh=mesh, remat=False,
                               dist_decode_attn=True))
    cache = jax.device_put(dd_model.init_cache(4, 64),
                           S.shardings_for(mesh, dd_model.cache_specs(4)))
    lg, cache = jax.jit(dd_model.prefill)(params_sharded, toks[:, :31],
                                          cache)
    lg_dd, _ = jax.jit(dd_model.decode_step)(params_sharded, cache,
                                             toks[:, 31], jnp.int32(31))
cache_ref = ref_model.init_cache(4, 64)
lg2, cache_ref = jax.jit(ref_model.prefill)(params, toks[:, :31], cache_ref)
lg_ref, _ = jax.jit(ref_model.decode_step)(params, cache_ref,
                                           toks[:, 31], jnp.int32(31))
out["decode_maxerr"] = float(jnp.max(jnp.abs(
    lg_dd.astype(jnp.float32) - lg_ref.astype(jnp.float32))))
print("RESULT " + json.dumps(out))
"""


@pytest.mark.slow
def test_sharded_execution_matches_reference(tmp_path):
    script = tmp_path / "dist_exec.py"
    script.write_text(SCRIPT)
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(os.path.dirname(__file__), "..", "src")
    proc = subprocess.run([sys.executable, str(script)], env=env,
                          capture_output=True, text=True, timeout=900)
    assert proc.returncode == 0, proc.stderr[-3000:]
    line = [l for l in proc.stdout.splitlines() if l.startswith("RESULT ")]
    assert line, proc.stdout
    out = json.loads(line[-1][len("RESULT "):])
    assert abs(out["loss_sharded"] - out["loss_ref"]) < 1e-3, out
    assert out["decode_maxerr"] < 1e-2, out


SHARDED_OPS_SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import json
import jax, jax.numpy as jnp
from repro.core import api
from repro.core.perf_model import MeshSpec
from repro.dist.sharding import Rules
from repro.kernels import ops

mesh = jax.make_mesh((2, 4), ("data", "model"),
                     axis_types=(jax.sharding.AxisType.Auto,) * 2)
rules = Rules(data=("data",), model="model", tp="model")
kx = jax.random.split(jax.random.PRNGKey(0), 6)
out = {}

# --- sharded fused gemm chain vs single-device fused kernel -------------
B, M, K, N, H = 4, 256, 128, 256, 512
a = jax.random.normal(kx[0], (B, M, K), jnp.float32)
b = jax.random.normal(kx[1], (B, K, N), jnp.float32)
d = jax.random.normal(kx[2], (B, N, H), jnp.float32) * 0.1
with jax.set_mesh(mesh):
    e_sh = ops.gemm_chain(a, b, d, mode="interpret", mesh=mesh,
                          rules=rules)
e_one = ops.gemm_chain(a, b, d, mode="interpret")
out["gemm_maxerr"] = float(jnp.max(jnp.abs(e_sh - e_one)))
# the dispatched schedule was tuned for the LOCAL block (H/4): refetch
# the cached TunedKernel under the same MeshSpec ops.py built
spec = MeshSpec.from_mesh(mesh, placement=(("h", "model"),),
                          batch_axes=("data",))
tk_mesh = api.fuse_gemm_chain(M, N, K, H, batch=B, dtype="float32",
                              mesh=spec, interpret=True)
out["mesh_bh"] = tk_mesh.params.bh
out["local_h"] = H // mesh.shape["model"]

# --- sharded fused GQA attention vs single-device fused kernel ----------
Bq, Hq, Hkv, S, Dh = 2, 8, 4, 256, 64
q = jax.random.normal(kx[3], (Bq, Hq, S, Dh), jnp.float32)
k = jax.random.normal(kx[4], (Bq, Hkv, S, Dh), jnp.float32)
v = jax.random.normal(kx[5], (Bq, Hkv, S, Dh), jnp.float32)
with jax.set_mesh(mesh):
    o_sh = ops.attention(q, k, v, causal=True, mode="interpret",
                         mesh=mesh, rules=rules)
o_one = ops.attention(q, k, v, causal=True, mode="interpret")
out["attn_maxerr"] = float(jnp.max(jnp.abs(o_sh - o_one)))

# --- tensor-parallel fused MLP chain vs single-device fused kernel ------
x = jax.random.normal(kx[0], (64, 128), jnp.float32)
wg_, wu_ = (jax.random.normal(kx[i], (128, 512), jnp.float32) * 0.1
            for i in (1, 2))
wd_ = jax.random.normal(kx[3], (512, 128), jnp.float32) * 0.1
with jax.set_mesh(mesh):
    y_sh = ops.mlp_chain(x, wu_, wd_, w_gate=wg_, mode="interpret",
                         mesh=mesh, rules=rules)
y_one = ops.mlp_chain(x, wu_, wd_, w_gate=wg_, mode="interpret")
out["mlp_maxerr"] = float(jnp.max(jnp.abs(y_sh - y_one)))

# --- Runtime(kernel_ops=True) under the ambient mesh --------------------
from repro.configs import get_config
from repro.launch import steps as S_
from repro.models.lm import LM, Runtime
cfg = get_config("qwen3_8b", smoke=True)
m_ko = LM(cfg, Runtime(rules=rules, mesh=mesh, remat=False,
                       kernel_ops=True))
m_tw = LM(cfg, Runtime(rules=rules, mesh=mesh, remat=False))
params = m_tw.init_params(jax.random.PRNGKey(7))
toks = jax.random.randint(jax.random.PRNGKey(8), (4, 32), 0, cfg.vocab)
with jax.set_mesh(mesh):
    psh = jax.device_put(params, S_.shardings_for(mesh, m_tw.param_specs()))
    lm_batch = {"tokens": toks, "labels": toks}
    l_ko = float(jax.jit(m_ko.loss)(psh, lm_batch))
    l_tw = float(jax.jit(m_tw.loss)(psh, lm_batch))
out["kernel_ops_loss_diff"] = abs(l_ko - l_tw)
print("RESULT " + json.dumps(out))
"""


@pytest.mark.slow
def test_sharded_kernel_dispatch_matches_single_device(tmp_path):
    """docs/design.md §7: the MCFuser-tuned kernel dispatched through
    shard_map (batch over data, features/heads over model) computes the
    single-device fused kernel's numbers on the 2x4 host-device mesh."""
    script = tmp_path / "sharded_ops.py"
    script.write_text(SHARDED_OPS_SCRIPT)
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(os.path.dirname(__file__), "..", "src")
    proc = subprocess.run([sys.executable, str(script)], env=env,
                          capture_output=True, text=True, timeout=900)
    assert proc.returncode == 0, proc.stderr[-3000:]
    line = [l for l in proc.stdout.splitlines() if l.startswith("RESULT ")]
    assert line, proc.stdout
    out = json.loads(line[-1][len("RESULT "):])
    assert out["gemm_maxerr"] < 1e-3, out
    assert out["attn_maxerr"] < 1e-3, out
    assert out["mlp_maxerr"] < 1e-3, out
    # the dispatched schedule is the per-shard one, not the global one
    assert out["mesh_bh"] <= out["local_h"], out
    # the model wiring (Runtime(kernel_ops=True)) agrees with the twin
    assert out["kernel_ops_loss_diff"] < 1e-3, out


ELASTIC_SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import json
import jax, jax.numpy as jnp
import numpy as np
from repro.ckpt import checkpoint as ckpt
from repro.configs import get_config
from repro.dist.sharding import Rules
from repro.launch import steps as S
from repro.models.lm import LM, Runtime
from repro.runtime.fault_tolerance import elastic_remesh, replace_state

cfg = get_config("granite_20b", smoke=True)
mesh8 = jax.make_mesh((4, 2), ("data", "model"),
                      axis_types=(jax.sharding.AxisType.Auto,) * 2)
rules = Rules(data=("data",), model="model", tp="model")
model = LM(cfg, Runtime(rules=rules, mesh=mesh8, remat=False))
params = model.init_params(jax.random.PRNGKey(0))
toks = jax.random.randint(jax.random.PRNGKey(1), (4, 32), 0, cfg.vocab)

with jax.set_mesh(mesh8):
    p8 = jax.device_put(params, S.shardings_for(mesh8, model.param_specs()))
    loss8 = float(jax.jit(model.loss)(p8, {"tokens": toks, "labels": toks}))

# checkpoint from the 8-device world
ckpt.save("/tmp/elastic_ckpt", 1, jax.tree.map(np.asarray, p8))

# "two hosts died": rebuild a 6-device mesh, keep the model axis whole
mesh6 = elastic_remesh(mesh8, list(jax.devices())[:6], ("data", "model"),
                       model_axis_size=2)
assert mesh6.devices.shape == (2, 2)   # data axis rounds down to 2^k
model6 = LM(cfg, Runtime(rules=rules, mesh=mesh6, remat=False))
restored = ckpt.restore("/tmp/elastic_ckpt", 1, params)
with jax.set_mesh(mesh6):
    p6 = replace_state(restored, mesh6,
                       model6.param_specs())
    loss6 = float(jax.jit(model6.loss)(
        p6, {"tokens": toks[:2], "labels": toks[:2]}))
ref = LM(cfg, Runtime(remat=False))
loss_ref = float(jax.jit(ref.loss)(params,
                                   {"tokens": toks[:2], "labels": toks[:2]}))
print("RESULT " + json.dumps({"loss6": loss6, "loss_ref": loss_ref,
                              "loss8": loss8}))
"""


@pytest.mark.slow
def test_elastic_reshard_after_node_loss(tmp_path):
    """Full elastic path: checkpoint on 8 devices -> 2 'die' -> rebuild a
    6-device mesh (model axis intact) -> re-place the checkpoint -> the
    resharded model computes the same loss."""
    script = tmp_path / "elastic.py"
    script.write_text(ELASTIC_SCRIPT)
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(os.path.dirname(__file__), "..", "src")
    proc = subprocess.run([sys.executable, str(script)], env=env,
                          capture_output=True, text=True, timeout=900)
    assert proc.returncode == 0, proc.stderr[-3000:]
    line = [l for l in proc.stdout.splitlines() if l.startswith("RESULT ")]
    out = json.loads(line[-1][len("RESULT "):])
    assert abs(out["loss6"] - out["loss_ref"]) < 1e-3, out
