"""Batched serving engine: prefill + KV-cache decode.

Requests are grouped into equal-prompt-length micro-batches (bucketed
continuous batching; per-row ragged prompts would need scatter cache
writes, which this engine does not do).  The engine jits one prefill and
one decode program per (batch, prompt_len) bucket and reuses them across
calls (the warm-executable cache that plays the role of the paper's warm
Python workers).

Two ways to drive it:

- ``generate``: run a whole batch to completion (the original per-call
  library API).
- the stepwise triple ``prefill_batch`` / ``decode_batch`` /
  ``gather_rows``: what the inference shard (``serving/shard.py``) uses
  for continuous batching -- admit a new prefill between other groups'
  decode steps, stream rows out as they finish, and gather a group's
  surviving rows into a smaller batch bucket (slot reuse) so retired
  sequences stop costing decode FLOPs.

The engine keeps no clock.  The first call for a new batch shape
compiles, and jax dispatch is asynchronous, so whoever times it must
block on the result and count a shape's first call as warmup; the chip
benchmark times the served path itself.
"""
from __future__ import annotations

from typing import Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from repro import observability as obs
from repro.configs.base import ModelConfig
from repro.models import api


class GenState:
    """One decode group's device state between steps.

    Each decode step is given the cache to reuse in place (donated): the
    arrays it read are deleted.  So a cache handed out through ``cache``,
    read or set by a caller who may still hold it, is copied before the
    next step donates it; the engine's own steps pass theirs on uncopied.
    """

    def __init__(self, cache, cur, pos: int, reserve: int, padded_b: int):
        self._cache = cache       # pytree; every leaf leads with batch
        self._shared = False      # a caller may hold ``_cache``
        self.cur = cur            # (B, 1) last emitted token per row
        self.pos = pos            # tokens already written to the cache
        self.reserve = reserve    # cache capacity (prompt + generation)
        self.padded_b = padded_b  # current batch dimension

    @property
    def cache(self):
        self._shared = True
        return self._cache

    @cache.setter
    def cache(self, cache):
        self._cache, self._shared = cache, True

    def take_cache(self):
        """The cache for a step that consumes it: a copy if shared."""
        if self._shared:
            return jax.tree.map(jnp.copy, self._cache)
        return self._cache

    def put_cache(self, cache):
        """Hold a step's new cache, which no caller has seen."""
        self._cache, self._shared = cache, False


class Engine:
    def __init__(self, cfg: ModelConfig, params, *, max_new: int = 32):
        self.cfg = cfg
        self.params = params
        self.max_new = max_new

        # named, so a profile's XLA modules read jit_prefill and
        # jit_decode_step
        def prefill(params, batch):
            return api.prefill(params, cfg, batch)

        def decode_step(params, cache, cur, pos):
            return api.decode_step(params, cfg, cache, cur, pos)

        self._prefill = jax.jit(prefill)
        # the cache is donated: a step writes its new positions in place
        self._decode = jax.jit(decode_step, donate_argnums=1)

    def device_info(self) -> dict:
        """The device holding this engine's weights, as JAX reports it."""
        (dev,) = jax.tree.leaves(self.params)[0].devices()
        coords = getattr(dev, "coords", None)       # TPU only
        return {"platform": dev.platform, "kind": dev.device_kind,
                "id": dev.id,
                "coords": None if coords is None else list(coords)}

    # -- stepwise API (continuous batching) ---------------------------------

    def prefill_batch(self, tokens: np.ndarray, *,
                      reserve: Optional[int] = None,
                      frames: Optional[np.ndarray] = None
                      ) -> tuple:
        """Prefill one equal-length micro-batch and reserve cache room
        for generation.  tokens (B, S) -> ((B,) first generated tokens,
        GenState positioned for decode)."""
        B, S = tokens.shape
        reserve = reserve if reserve is not None else S + self.max_new
        with obs.region("engine.prefill"):
            batch = {"tokens": jnp.asarray(tokens, jnp.int32)}
            if self.cfg.is_encdec:
                if frames is None:
                    frames = np.zeros((B, S, self.cfg.d_model), np.float32)
                batch["frames"] = jnp.asarray(frames)
            logits, cache = self._prefill(self.params, batch)
            with obs.region("engine.grow_cache"):
                cache = api.grow_cache(self.cfg, cache, reserve)
            first = jnp.argmax(logits, axis=-1).astype(jnp.int32)
            state = GenState(cache=cache, cur=first[:, None], pos=S,
                             reserve=reserve, padded_b=B)
            with obs.region("engine.readback"):
                return np.asarray(first), state

    def decode_batch(self, state: GenState) -> np.ndarray:
        """One decode step for every row of the group; returns the (B,)
        next tokens and advances the state."""
        if state.pos >= state.reserve:
            raise ValueError(
                f"decode past reserved cache length {state.reserve}")
        with obs.region("engine.decode"):
            with obs.region("engine.dispatch"):
                logits, cache = self._decode(
                    self.params, state.take_cache(), state.cur,
                    jnp.asarray(state.pos, jnp.int32))
                state.put_cache(cache)
                nxt = jnp.argmax(logits, axis=-1).astype(jnp.int32)
            state.cur = nxt[:, None]
            state.pos += 1
            with obs.region("engine.readback"):
                return np.asarray(nxt)

    def gather_rows(self, state: GenState, rows: Sequence[int]) -> GenState:
        """Slot reuse: re-pack the group's state down to ``rows`` (engine
        batch indices, typically the survivors padded to a smaller batch
        bucket).  Decode cost drops to the new batch shape from the next
        step on."""
        with obs.region("engine.gather"):
            idx = jnp.asarray(list(rows), jnp.int32)
            # every cache family is stacked over layers: leaves are
            # (num_layers, batch, ...), so the batch gather is along axis 1
            cache = jax.tree_util.tree_map(
                lambda x: jnp.take(x, idx, axis=1), state._cache)
            return GenState(cache=cache, cur=state.cur[idx], pos=state.pos,
                            reserve=state.reserve, padded_b=len(rows))

    # -- run-to-completion API ----------------------------------------------

    def generate(self, tokens: np.ndarray, *, max_new: Optional[int] = None,
                 frames: Optional[np.ndarray] = None) -> np.ndarray:
        """tokens (B, S) equal-length prompts -> (B, S + max_new)."""
        max_new = max_new or self.max_new
        _, state = self.prefill_batch(
            tokens, reserve=tokens.shape[1] + max_new, frames=frames)
        out = [state.cur[:, 0]]
        for _ in range(max_new - 1):
            self.decode_batch(state)
            out.append(state.cur[:, 0])
        gen = jnp.stack(out, axis=1)
        return np.concatenate([tokens, np.asarray(gen)], axis=1)
