"""The train step and its sharded program.

- ``init_state(cfg, key)`` / ``abstract_state(cfg)`` / ``state_shardings``
- ``make_train_step(cfg, tc, sc)`` : grad accumulation, clip, AdamW, guards
- ``build_program(cfg, global_batch, seq_len, mesh)`` : the train step
  jitted with its state and batch shardings on a mesh

The served prefill and decode programs are built by ``serving.Engine``.
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.configs.base import ModelConfig, ShardingConfig, TrainConfig
from repro.distributed import axisenv, sharding as shd
from repro.models import api
from repro.optim import adamw, clip, schedules


def _with_axisenv(fn, mesh, global_batch, mode="dp_tp"):
    """Wrap a step fn so model-level sharding constraints resolve during
    tracing (axisenv is consulted at trace time)."""
    bax = shd.batch_axes(mesh, global_batch, mode)
    sizes = tuple(int(mesh.shape[a]) for a in bax)
    # in dp_only mode no tensor axis lives on "model"
    model = "model" if "model" in mesh.shape and mode != "dp_only" else None
    msize = int(mesh.shape.get("model", 1))

    def wrapped(*args):
        with axisenv.activation_axes(batch=bax, batch_sizes=sizes,
                                     model=model, model_size=msize,
                                     mesh=mesh):
            return fn(*args)
    return wrapped


# ---------------------------------------------------------------------------
# Train batch
# ---------------------------------------------------------------------------


def _sds(shape, dtype):
    return jax.ShapeDtypeStruct(shape, jnp.dtype(dtype))


def batch_specs(cfg: ModelConfig, B: int, S: int):
    """Abstract (B, S) train batch: model inputs and labels."""
    cd = cfg.compute_dtype
    out = {}
    if cfg.family == "vlm":
        out["embeds"] = _sds((B, S, cfg.d_model), cd)
        out["positions"] = _sds((3, B, S), "int32")
    else:
        out["tokens"] = _sds((B, S), "int32")
    if cfg.is_encdec:
        out["frames"] = _sds((B, S, cfg.d_model), cd)
    out["labels"] = _sds((B, S), "int32")
    return out


def _batch_shardings(specs, mesh, global_batch, mode="dp_tp"):
    """Each batch input split over the batch axes on its batch dimension."""
    bax = shd.batch_axes(mesh, global_batch, mode)
    lead = bax if bax else None

    def spec_of(name, s):
        if name == "positions":
            return P(None, lead, None)
        return P(lead, *([None] * (len(s.shape) - 1)))

    return {name: NamedSharding(mesh, spec_of(name, s))
            for name, s in specs.items()}


# ---------------------------------------------------------------------------
# Train state
# ---------------------------------------------------------------------------


def abstract_state(cfg: ModelConfig):
    params = api.abstract_params(cfg)
    opt = jax.eval_shape(adamw.init, params)
    return {"params": params, "opt": opt}


def init_state(cfg: ModelConfig, key):
    params = api.init_params(cfg, key)
    return {"params": params, "opt": adamw.init(params)}


def state_shardings(cfg: ModelConfig, mesh,
                    sc: Optional[ShardingConfig] = None):
    sc = sc or ShardingConfig()
    abs_params = api.abstract_params(cfg)
    pspecs = shd.tree_specs(api.param_specs(cfg), abs_params, mesh, sc.mode)

    def moment_spec(ps, ap):
        return shd.zero_spec(ps, ap.shape, mesh) if sc.zero >= 1 else ps

    mspecs = jax.tree.map(moment_spec, pspecs, abs_params,
                          is_leaf=lambda x: isinstance(x, P))
    to_sh = lambda t: jax.tree.map(
        lambda p: NamedSharding(mesh, p), t,
        is_leaf=lambda x: isinstance(x, P))
    return {
        "params": to_sh(pspecs),
        "opt": adamw.AdamWState(step=NamedSharding(mesh, P()),
                                m=to_sh(mspecs), v=to_sh(mspecs)),
    }


# ---------------------------------------------------------------------------
# Programs
# ---------------------------------------------------------------------------


def make_train_step(cfg: ModelConfig, tc: TrainConfig,
                    sc: Optional[ShardingConfig] = None):
    sc = sc or ShardingConfig()

    def grads_of(params, batch):
        def lf(p):
            return api.loss_fn(p, cfg, batch)
        (loss, metrics), grads = jax.value_and_grad(
            lf, has_aux=True)(params)
        metrics = {**metrics, "loss": loss}
        return grads, metrics

    def train_step(state, batch):
        params = state["params"]
        if sc.microbatches > 1:
            k = sc.microbatches

            def resh(t):
                b = t.shape[0]
                assert b % k == 0, (b, k)
                return t.reshape((k, b // k) + t.shape[1:])

            # positions (3,B,S) carries batch on dim 1
            mb = {}
            for name, t in batch.items():
                if name == "positions":
                    b = t.shape[1]
                    mb[name] = jnp.moveaxis(
                        t.reshape((3, k, b // k) + t.shape[2:]), 1, 0)
                else:
                    mb[name] = resh(t)

            def acc_body(carry, microbatch):
                g_acc, m_acc = carry
                g, m = grads_of(params, microbatch)
                g_acc = jax.tree.map(lambda a, b: a + b / k, g_acc, g)
                m_acc = jax.tree.map(lambda a, b: a + b / k, m_acc, m)
                return (g_acc, m_acc), None

            g0 = jax.tree.map(
                lambda p: jnp.zeros(p.shape, jnp.float32), params)
            m0 = {"ce": 0.0, "aux": 0.0, "tokens": 0.0, "loss": 0.0}
            m0 = jax.tree.map(jnp.float32, m0)
            (grads, metrics), _ = jax.lax.scan(acc_body, (g0, m0), mb)
        else:
            grads, metrics = grads_of(params, batch)

        grads, nonfinite = clip.zero_nonfinite(grads)
        grads, gnorm = clip.clip_by_global_norm(grads, tc.grad_clip)
        lr = schedules.warmup_cosine(
            state["opt"].step, lr=tc.lr, warmup_steps=tc.warmup_steps,
            total_steps=tc.total_steps)
        new_params, new_opt = adamw.update(grads, state["opt"], params,
                                           lr, tc)
        metrics = {**metrics, "grad_norm": gnorm, "lr": lr,
                   "skipped": nonfinite.astype(jnp.float32)}
        return {"params": new_params, "opt": new_opt}, metrics

    return train_step


# ---------------------------------------------------------------------------
# Jitted + sharded program assembly
# ---------------------------------------------------------------------------


def build_program(cfg: ModelConfig, global_batch: int, seq_len: int, mesh, *,
                  tc: Optional[TrainConfig] = None,
                  sc: Optional[ShardingConfig] = None):
    """The sharded train step for a (global_batch, seq_len) batch:
    (jitted_fn, example_args as ShapeDtypeStructs)."""
    tc = tc or TrainConfig()
    sc = sc or ShardingConfig()
    specs = batch_specs(cfg, global_batch, seq_len)
    st_sh = state_shardings(cfg, mesh, sc)
    fn = _with_axisenv(make_train_step(cfg, tc, sc), mesh, global_batch,
                       sc.mode)
    jfn = jax.jit(fn,
                  in_shardings=(st_sh, _batch_shardings(
                      specs, mesh, global_batch, sc.mode)),
                  out_shardings=(st_sh, None),
                  donate_argnums=(0,))
    return jfn, (abstract_state(cfg), specs)
