"""Multi-device distribution modes, validated in a subprocess with 8 forced
host devices (jax locks the device count at first init, so the main test
process cannot do this itself)."""
import subprocess
import sys

import pytest

SCRIPT = r"""
import os
os.environ['XLA_FLAGS'] = '--xla_force_host_platform_device_count=8'
import sys; sys.path.insert(0, 'src')
import jax, jax.numpy as jnp, numpy as np
from jax.sharding import AxisType, NamedSharding, PartitionSpec as P
from repro.configs.base import get_config, SHAPES, ShardingConfig, TrainConfig
from repro.distributed import axisenv, sharding as shd
from repro.models import api, moe
from repro.launch import steps

mesh = jax.make_mesh((2, 4), ('data', 'model'),
                     axis_types=(AxisType.Auto, AxisType.Auto))

# 1. shard_map EP MoE == GSPMD dropping path (no drops)
cfg = get_config('kimi-k2-1t-a32b', reduced=True).replace(
    capacity_factor=8.0, compute_dtype='float32', param_dtype='float32')
params = api.init_params(cfg, jax.random.PRNGKey(1))
p = jax.tree.map(lambda t: t[0], params['stack']['uniform']['ffn'])
x = jax.random.normal(jax.random.PRNGKey(2), (4, 16, cfg.d_model))
y_ref, _ = moe.moe_dropping(p, x, cfg)
def f(p_, x_):
    with axisenv.activation_axes(batch=('data',), batch_sizes=(2,),
                                 model='model', model_size=4, mesh=mesh):
        return moe.moe_ep(p_, x_, cfg)
with mesh:
    y_ep, _ = jax.jit(f, in_shardings=(
        None, NamedSharding(mesh, P('data', None, None))))(p, x)
assert float(jnp.max(jnp.abs(y_ep - y_ref))) < 1e-4
print('EP_OK')

# 1b. fill-gather MoE dispatch under GSPMD with the seq-parallel layout
# (token axis sharded over "model") matches the unsharded reference --
# regression net for the concat-across-a-sharded-dim miscompile class
def g(p_, x_):
    with axisenv.activation_axes(batch=('data',), batch_sizes=(2,),
                                 model='model', model_size=4, mesh=mesh):
        return moe.moe_dropping(p_, x_, cfg)
with mesh:
    y_sp, _ = jax.jit(g, in_shardings=(
        None, NamedSharding(mesh, P('data', 'model', None))))(p, x)
assert float(jnp.max(jnp.abs(y_sp - y_ref))) < 1e-4
print('SP_MOE_OK')

# 2. a real sharded train step runs and matches the single-device step
cfg2 = get_config('internlm2-1.8b', reduced=True).replace(remat='none')
tc = TrainConfig(lr=1e-3, warmup_steps=0, total_steps=10)
for mode in ('dp_tp', 'dp_only', 'fsdp_tp'):
    sc = ShardingConfig(mode=mode)
    shape = SHAPES['train_4k']
    import dataclasses
    shape = dataclasses.replace(shape, seq_len=64, global_batch=8)
    with mesh:
        jfn, args = steps.build_program(cfg2, shape, mesh, tc=tc, sc=sc)
        state = steps.init_state(cfg2, jax.random.PRNGKey(0))
        batch = {
            'tokens': jnp.zeros((8, 64), jnp.int32),
            'labels': jnp.ones((8, 64), jnp.int32),
        }
        new_state, metrics = jfn(state, batch)
        loss = float(metrics['loss'])
        assert np.isfinite(loss), (mode, loss)
        print(f'{mode}_loss={loss:.6f}')
print('MODES_OK')
"""


@pytest.mark.slow
def test_multidevice_modes():
    out = subprocess.run([sys.executable, "-c", SCRIPT], cwd=".",
                         capture_output=True, text=True, timeout=900)
    assert "EP_OK" in out.stdout, out.stdout + out.stderr
    assert "SP_MOE_OK" in out.stdout, out.stdout + out.stderr
    assert "MODES_OK" in out.stdout, out.stdout + out.stderr
    # every mode computes the same loss (sharding never changes semantics)
    losses = [float(line.split("=")[1]) for line in out.stdout.splitlines()
              if "_loss=" in line]
    assert len(losses) == 3
    # bf16 partial-sum order differs across shardings; semantics identical
    assert max(losses) - min(losses) < 0.02, losses
