"""Distribution correctness: multi-device (host-platform) runs match
single-device numerics; runs in a subprocess so the device count doesn't
leak into other tests."""

import json
import os
import subprocess
import sys

import pytest

SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import sys, json, dataclasses
sys.path.insert(0, "src")
import jax, jax.numpy as jnp, numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P
from repro.configs import get_config, get_shape
from repro.configs.base import ShapeCfg
from repro.launch import steps as stp
from repro.launch.mesh import make_host_mesh
from repro.models import lm
from repro.optim import adamw

cfg = get_config("qwen3-1.7b", smoke=True)
mesh = make_host_mesh(2, 4)
tcfg = stp.TrainCfg(lr=1e-3, warmup_steps=2, total_steps=10)
params = lm.init(cfg, jax.random.PRNGKey(0))
state = {"params": params, "opt": adamw.init_opt_state(params, tcfg.adam)}
rng = np.random.RandomState(0)
B, S = 8, 64
batch = {"tokens": jnp.asarray(rng.randint(1, cfg.vocab_size, (B, S)), jnp.int32)}
batch["targets"] = batch["tokens"]

# single-device reference
step1 = jax.jit(stp.make_train_step(cfg, tcfg))
s1, m1 = step1(jax.tree.map(jnp.copy, state), batch)

# distributed
with jax.set_mesh(mesh):
    shape = ShapeCfg("t", S, B, "train")
    jitted, ss, bspec = stp.make_jitted_train_step(cfg, mesh, tcfg, shape)
    # deep-copy before device_put: the jitted step donates its state arg and
    # device_put may alias the source buffers on the host platform
    sh_state = jax.device_put(jax.tree.map(jnp.copy, state), jax.tree.map(
        lambda p: NamedSharding(mesh, p), ss,
        is_leaf=lambda x: isinstance(x, P)))
    sh_batch = jax.device_put(batch, jax.tree.map(
        lambda p: NamedSharding(mesh, p), bspec,
        is_leaf=lambda x: isinstance(x, P)))
    s2, m2 = jitted(sh_state, sh_batch)

out = {"loss1": float(m1["loss"]), "loss2": float(m2["loss"])}
d = 0.0
for a, b in zip(jax.tree.leaves(s1["params"]), jax.tree.leaves(s2["params"])):
    d = max(d, float(jnp.max(jnp.abs(np.asarray(a, np.float32) - np.asarray(b, np.float32)))))
out["max_param_diff"] = d

# decode path: seq-sharded attention on the mesh vs local.  Dense mode:
# sparse selection is per-shard under sequence sharding (a documented
# approximation), so exactness is asserted on the dense lse-combine path.
shape_d = ShapeCfg("d", 128, 8, "decode")
cfg_d = dataclasses.replace(
    cfg, leoam=dataclasses.replace(cfg.leoam, min_seq_for_sparse=10**9))
cache = lm.abstract_cache(cfg_d, 8, 128)
cache = jax.tree.map(lambda s: jnp.zeros(s.shape, s.dtype), cache)
prompt = jnp.asarray(rng.randint(1, cfg.vocab_size, (8, 96)), jnp.int32)
_, cache_local = lm.prefill(params, cfg_d, {"tokens": prompt}, max_len=128)
logits_local, _ = lm.decode_step(params, cfg_d, cache_local,
                                 {"token": prompt[:, -1]}, jnp.int32(96))
with jax.set_mesh(mesh):
    jd = stp.make_jitted_decode(cfg_d, mesh, shape_d)
    csh = jax.tree.map(lambda p: NamedSharding(mesh, p),
                       stp.cache_specs(cfg_d, mesh, shape_d),
                       is_leaf=lambda x: isinstance(x, P))
    cache_sh = jax.device_put(jax.tree.map(jnp.copy, cache_local), csh)
    psh = stp.param_shardings(cfg_d, mesh)
    params_sh = jax.device_put(params, psh)
    tok_sh = jax.device_put(prompt[:, -1], NamedSharding(mesh, P("data")))
    logits_sh, _ = jd(params_sh, cache_sh, {"token": tok_sh}, jnp.int32(96))
out["decode_diff"] = float(jnp.max(jnp.abs(
    np.asarray(logits_local, np.float32) - np.asarray(logits_sh, np.float32))))
print("RESULT " + json.dumps(out))
"""


@pytest.mark.slow
def test_multidevice_matches_single(tmp_path):
    p = subprocess.run([sys.executable, "-c", SCRIPT], capture_output=True,
                       text=True, cwd=os.path.dirname(os.path.dirname(__file__)),
                       timeout=900)
    assert p.returncode == 0, p.stderr[-3000:]
    line = [l for l in p.stdout.splitlines() if l.startswith("RESULT ")][-1]
    out = json.loads(line[len("RESULT "):])
    assert abs(out["loss1"] - out["loss2"]) < 2e-2, out
    assert out["max_param_diff"] < 2e-2, out
    assert out["decode_diff"] < 2e-2, out
