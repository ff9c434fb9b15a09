"""End-to-end sharding correctness on a real (8-fake-device) mesh.

Runs in a SUBPROCESS (device count must be set before jax initializes, and
the main test process must keep its single CPU device): a reduced GQA model
is trained one step and served (prefill + decode) under the production
sharding rules on a (data=2, model=4) mesh, and every result is compared
against the plain unsharded single-device execution.  This is the numeric
proof that the TRAIN/SERVE rules + constraints don't change the math —
the multi-pod dry-run proves compilability, this proves equivalence.
"""

import os
import subprocess
import sys

import pytest

SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import dataclasses
import numpy as np
import jax, jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.configs import ARCHS
from repro.data import DataConfig, SyntheticLMData
from repro.launch.mesh import auto_mesh
from repro.distributed.sharding import (SERVE_RULES, TRAIN_FSDP_RULES,
                                        activate, param_shardings, spec_for)
from repro.models import init_params, model_spec
from repro.models.transformer import cache_axes, decode_step, prefill
from repro.optim import adamw_init, constant_schedule
from repro.train.step import TrainConfig, make_train_step

assert len(jax.devices()) == 8, jax.devices()
mesh = auto_mesh((2, 4), ("data", "model"))

def reduced(arch):
    cfg = ARCHS[arch].reduced()
    kw = {"dtype": "float32"}
    if cfg.n_experts:
        kw["capacity_factor"] = 8.0        # no drops → sharded == unsharded
    return dataclasses.replace(cfg, **kw)

# ---- sharded train step == unsharded, across three families -------------
for arch in ("qwen2.5-3b", "deepseek-v2-236b", "rwkv6-3b"):
    cfg = reduced(arch)
    spec_tree = model_spec(cfg)
    params = init_params(spec_tree, jax.random.PRNGKey(0))
    data = SyntheticLMData(DataConfig(8, 32, cfg.vocab, seed=0), cfg)
    batch = {k: jnp.asarray(v) for k, v in data.batch_at(0).items()}

    tcfg = TrainConfig(remat="none", microbatches=1)
    step_ref = jax.jit(make_train_step(cfg, tcfg, constant_schedule(1e-3)))
    state0 = {"params": params, "opt": adamw_init(params)}
    ref_state, ref_metrics = step_ref(
        jax.tree_util.tree_map(jnp.copy, state0), batch)

    rules = TRAIN_FSDP_RULES
    p_sh = param_shardings(spec_tree, rules, mesh)
    state_sh = {"params": p_sh,
                "opt": {"m": p_sh, "v": p_sh,
                        "step": NamedSharding(mesh, P())}}
    with activate(rules, mesh):
        batch_sh = {k: NamedSharding(mesh, spec_for(
            ("batch", None), rules, mesh, tuple(v.shape)))
            for k, v in batch.items()}
    state_placed = jax.device_put(state0, state_sh)
    batch_placed = {k: jax.device_put(v, batch_sh[k])
                    for k, v in batch.items()}

    def wrapped(state, b, cfg=cfg, rules=rules, tcfg=tcfg):
        with activate(rules, mesh):
            return make_train_step(cfg, tcfg, constant_schedule(1e-3))(
                state, b)

    step_sh = jax.jit(wrapped, in_shardings=(state_sh, batch_sh))
    with mesh:
        sh_state, sh_metrics = step_sh(state_placed, batch_placed)

    np.testing.assert_allclose(float(sh_metrics["loss"]),
                               float(ref_metrics["loss"]), rtol=5e-5)
    for a, b in zip(jax.tree_util.tree_leaves(ref_state["params"]),
                    jax.tree_util.tree_leaves(sh_state["params"])):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=5e-4, atol=5e-4)
    print(f"TRAIN-EQUIV-OK {arch}")
print("TRAIN-EQUIV-OK")

# ---- sharded serving: prefill + decode under SERVE_RULES -----------------
cfg = reduced("qwen2.5-3b")
spec_tree = model_spec(cfg)
params = init_params(spec_tree, jax.random.PRNGKey(0))
toks = jnp.asarray(np.random.default_rng(1).integers(
    0, cfg.vocab, (8, 16)), jnp.int32)
ref_logits, ref_cache = prefill(params, {"tokens": toks}, cfg, max_len=20,
                                cache_dtype=jnp.float32)
ref_l2, _ = decode_step(params, ref_cache,
                        jnp.argmax(ref_logits, -1).astype(jnp.int32),
                        jnp.int32(16), cfg)

def serve_wrapped(p, t):
    with activate(SERVE_RULES, mesh):
        logits, cache = prefill(p, {"tokens": t}, cfg, max_len=20,
                                cache_dtype=jnp.float32)
        nxt = jnp.argmax(logits, -1).astype(jnp.int32)
        l2, _ = decode_step(p, cache, nxt, jnp.int32(16), cfg)
        return logits, l2

p_sh_serve = param_shardings(spec_tree, SERVE_RULES, mesh)
with activate(SERVE_RULES, mesh):
    t_sh = NamedSharding(mesh, spec_for(("batch", None), SERVE_RULES, mesh,
                                        (8, 16)))
serve = jax.jit(serve_wrapped, in_shardings=(p_sh_serve, t_sh))
with mesh:
    sh_logits, sh_l2 = serve(jax.device_put(params, p_sh_serve),
                             jax.device_put(toks, t_sh))
np.testing.assert_allclose(np.asarray(sh_logits), np.asarray(ref_logits),
                           rtol=2e-4, atol=2e-4)
np.testing.assert_allclose(np.asarray(sh_l2), np.asarray(ref_l2),
                           rtol=2e-4, atol=2e-4)
print("SERVE-EQUIV-OK")
"""


@pytest.mark.timeout(560)
def test_sharded_equals_unsharded_on_8_device_mesh():
    env = dict(os.environ)
    env["PYTHONPATH"] = "src"
    out = subprocess.run([sys.executable, "-c", SCRIPT], env=env,
                         capture_output=True, text=True, timeout=540,
                         cwd=os.path.join(os.path.dirname(__file__), ".."))
    assert out.returncode == 0, (out.stdout[-1500:], out.stderr[-3000:])
    assert "TRAIN-EQUIV-OK" in out.stdout
    assert "SERVE-EQUIV-OK" in out.stdout
