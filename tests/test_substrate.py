"""Substrate layers: sharding rules, optimizer, checkpointing, data."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from repro.checkpoint import store
from repro.checkpoint.manager import CheckpointManager
from repro.configs.base import TrainConfig, get_config
from repro.data import molecules, tokens
from repro.distributed import sharding as shd
from repro.optim import adamw, clip, schedules


# ---------------------------------------------------------------------------
# sharding rules
# ---------------------------------------------------------------------------

def _mesh(shape=(2, 2), axes=("data", "model")):
    devs = np.array(jax.devices()[:1] * (shape[0] * shape[1]))
    # host has 1 device; use abstract mesh via make_mesh only when enough
    # devices exist.  For rule tests we only need the .shape mapping:
    class FakeMesh:
        def __init__(self):
            self.shape = dict(zip(axes, shape))
    return FakeMesh()


def test_spec_for_tp_rules():
    mesh = _mesh((2, 4))
    # ff divisible -> model; embed replicated
    assert shd.spec_for(("embed", "ff"), (128, 512), mesh) == P(None, "model")
    # vocab divisible -> model
    assert shd.spec_for(("vocab", "embed"), (1024, 128), mesh) == \
        P("model", None)
    # non-divisible falls back to replication
    assert shd.spec_for(("kv_heads", "head_dim"), (3, 64), mesh) == \
        P(None, None)
    # a mesh axis is never used twice
    spec = shd.spec_for(("ff", "experts"), (512, 8), mesh)
    used = [s for s in spec if s is not None]
    assert len(used) == len(set(used))


def test_spec_for_fsdp_adds_data_axis():
    mesh = _mesh((4, 4))
    spec = shd.spec_for(("embed", "ff"), (1024, 4096), mesh, mode="fsdp_tp")
    assert spec == P("data", "model")
    # small params stay replicated even in fsdp mode
    spec_small = shd.spec_for(("embed",), (128,), mesh, mode="fsdp_tp")
    assert spec_small == P(None)


def test_zero_spec_shards_moments():
    mesh = _mesh((4, 4))
    zs = shd.zero_spec(P(None, "model"), (1024, 4096), mesh)
    assert zs == P("data", "model")
    # already data-sharded spec untouched
    assert shd.zero_spec(P("data", None), (1024, 64), mesh) == P("data", None)


def test_batch_axes_divisibility():
    mesh3 = _mesh((2, 16, 16), ("pod", "data", "model"))
    assert shd.batch_axes(mesh3, 256) == ("pod", "data")
    assert shd.batch_axes(mesh3, 1) == ()
    mesh2 = _mesh((16, 16), ("data", "model"))
    assert shd.batch_axes(mesh2, 128) == ("data",)


# ---------------------------------------------------------------------------
# optimizer
# ---------------------------------------------------------------------------

def test_adamw_optimizes_quadratic():
    tc = TrainConfig(lr=0.1, weight_decay=0.0, warmup_steps=0,
                     total_steps=100)
    params = {"w": jnp.asarray([5.0, -3.0])}
    state = adamw.init(params)
    for _ in range(200):
        grads = {"w": 2 * params["w"]}        # d/dw of w^2
        params, state = adamw.update(grads, state, params, 0.05, tc)
    assert float(jnp.max(jnp.abs(params["w"]))) < 0.05


def test_clip_by_global_norm():
    g = {"a": jnp.ones(4) * 10.0}
    clipped, norm = clip.clip_by_global_norm(g, 1.0)
    assert abs(float(norm) - 20.0) < 1e-4
    from repro.utils.trees import tree_global_norm
    assert abs(float(tree_global_norm(clipped)) - 1.0) < 1e-4


def test_nonfinite_guard():
    g = {"a": jnp.asarray([1.0, jnp.nan])}
    fixed, bad = clip.zero_nonfinite(g)
    assert bool(bad)
    assert float(jnp.sum(jnp.abs(fixed["a"]))) == 0.0


def test_warmup_cosine_schedule():
    kw = dict(lr=1.0, warmup_steps=10, total_steps=100)
    s0 = float(schedules.warmup_cosine(jnp.asarray(0), **kw))
    s10 = float(schedules.warmup_cosine(jnp.asarray(10), **kw))
    s100 = float(schedules.warmup_cosine(jnp.asarray(100), **kw))
    assert s0 == 0.0 and abs(s10 - 1.0) < 0.01 and s100 <= 0.11


# ---------------------------------------------------------------------------
# checkpointing
# ---------------------------------------------------------------------------

def test_store_roundtrip_bf16(tmp_path):
    tree = {"a": jnp.ones((4, 3), jnp.bfloat16) * 1.5,
            "b": {"c": jnp.arange(5, dtype=jnp.int32)},
            "s": jnp.asarray(2.0)}
    path = str(tmp_path / "ck")
    store.save(path, tree)
    back = store.restore(path, tree)
    assert back["a"].dtype == jnp.bfloat16
    np.testing.assert_array_equal(np.asarray(back["a"], np.float32),
                                  np.asarray(tree["a"], np.float32))
    np.testing.assert_array_equal(back["b"]["c"], tree["b"]["c"])


def test_manager_rotation_and_corruption_fallback(tmp_path):
    m = CheckpointManager(str(tmp_path), keep=2)
    tree = {"x": jnp.arange(10, dtype=jnp.float32)}
    for step in (1, 2, 3):
        m.save(step, {"x": tree["x"] * step}, blocking=True)
    assert m.steps() == [2, 3]            # rotated
    # corrupt the newest shard
    import os
    shard = os.path.join(str(tmp_path), "step_3", store.SHARD)
    with open(shard, "wb") as f:
        f.write(b"garbage")
    step, back = m.restore(tree)
    assert step == 2                       # fell back to older valid ckpt
    np.testing.assert_array_equal(back["x"], tree["x"] * 2)


def test_train_resume_bitexact(tmp_path):
    """Fault-tolerance: resume reproduces the uninterrupted run."""
    from repro.launch.train import train
    kw = dict(reduced=True, batch=2, seq=32, lr=1e-3, log_every=100,
              print_fn=lambda *a: None)
    # uninterrupted 8 steps
    s_full, _ = train("internlm2-1.8b", steps_total=8, **kw)
    # interrupted at 4 + resume (same schedule: steps_total stays 8)
    ck = str(tmp_path / "ck")
    train("internlm2-1.8b", steps_total=8, stop_after=4, ckpt_dir=ck,
          ckpt_every=100, **kw)
    s_res, _ = train("internlm2-1.8b", steps_total=8, ckpt_dir=ck,
                     resume=True, **kw)
    for a, b in zip(jax.tree.leaves(s_full["params"]),
                    jax.tree.leaves(s_res["params"])):
        np.testing.assert_allclose(np.asarray(a, np.float32),
                                   np.asarray(b, np.float32),
                                   rtol=1e-5, atol=1e-6)


# ---------------------------------------------------------------------------
# data
# ---------------------------------------------------------------------------

def test_lm_batches_deterministic():
    cfg = get_config("internlm2-1.8b", reduced=True)
    b1 = tokens.lm_batch(cfg, 4, 16, step=7, seed=0)
    b2 = tokens.lm_batch(cfg, 4, 16, step=7, seed=0)
    np.testing.assert_array_equal(b1["tokens"], b2["tokens"])
    b3 = tokens.lm_batch(cfg, 4, 16, step=8, seed=0)
    assert not np.array_equal(b1["tokens"], b3["tokens"])
    # labels are next-token shifted
    assert b1["tokens"].max() < cfg.vocab_size


def test_molecules_deterministic_and_oracle_range():
    space = molecules.MoleculeSpace(num_molecules=100)
    a1 = molecules.generate_molecule(space, 7)
    a2 = molecules.generate_molecule(space, 7)
    np.testing.assert_array_equal(a1[1], a2[1])
    vals = molecules.oracle_batch(space, range(50))
    assert np.all(vals > 3.9) and np.all(vals < 12.1)
    assert vals.std() > 0.1                # non-degenerate landscape
    # symmetric bonds
    assert np.array_equal(a1[1], a1[1].T)


def test_prefetch_loader_order():
    from repro.data.loader import PrefetchLoader
    loader = PrefetchLoader(lambda step: step * 10, start_step=3, depth=2)
    got = [next(loader) for _ in range(3)]
    loader.close()
    assert got == [(3, 30), (4, 40), (5, 50)]


def test_compile_cache_follows_the_env_or_the_checkout(monkeypatch):
    from repro.utils import compile_cache
    before = jax.config.jax_compilation_cache_dir
    try:
        monkeypatch.setenv(compile_cache.ENV_VAR, "/from/the/env")
        assert compile_cache.use_compile_cache() == "/from/the/env"
        assert jax.config.jax_compilation_cache_dir == before  # set nothing
        monkeypatch.delenv(compile_cache.ENV_VAR)
        path = compile_cache.use_compile_cache()
        assert path == str(compile_cache.DEFAULT_DIR)
        assert jax.config.jax_compilation_cache_dir == path
        # a fixed directory inside the checkout, beside pyproject.toml
        assert (compile_cache.DEFAULT_DIR.parent / "pyproject.toml").exists()
    finally:
        jax.config.update("jax_compilation_cache_dir", before)
