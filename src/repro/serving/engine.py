"""Batched serving engine: prefill + KV-cache decode.

Requests are grouped into equal-prompt-length micro-batches (bucketed
continuous batching; per-row ragged prompts would need scatter cache
writes -- see DESIGN.md simplifications).  The engine jits one prefill and
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

Timing honesty: the first ``generate`` call for a given (batch,
prompt_len, max_new) shape triggers XLA compilation, and jax dispatch is
asynchronous -- so the stop-clock only runs after ``block_until_ready``,
and a first-per-shape call's wall goes to ``stats["compile_wall"]``
(warmup), not ``stats["wall"]``.  ``throughput()`` is therefore
steady-state tokens/sec over warm executables only.
"""
from __future__ import annotations

import time
from dataclasses import dataclass
from typing import Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ModelConfig
from repro.models import api


@dataclass
class GenState:
    """One decode group's device state between steps."""

    cache: object                 # pytree; every leaf leads with batch
    cur: jnp.ndarray              # (B, 1) last emitted token per row
    pos: int                      # tokens already written to the cache
    reserve: int                  # cache capacity (prompt + generation)
    padded_b: int                 # current batch dimension


class Engine:
    def __init__(self, cfg: ModelConfig, params, *, max_new: int = 32):
        self.cfg = cfg
        self.params = params
        self.max_new = max_new
        self._prefill = jax.jit(
            lambda p, b: api.prefill(p, cfg, b))
        self._decode = jax.jit(
            lambda p, c, t, n: api.decode_step(p, cfg, c, t, n))
        self._warm: set = set()   # (B, S, max_new) shapes already compiled
        self.stats = {"prefill_calls": 0, "decode_steps": 0,
                      "tokens_out": 0, "wall": 0.0, "compile_wall": 0.0,
                      "warm_tokens": 0}

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
        batch = {"tokens": jnp.asarray(tokens, jnp.int32)}
        if self.cfg.is_encdec:
            if frames is None:
                frames = np.zeros((B, S, self.cfg.d_model), np.float32)
            batch["frames"] = jnp.asarray(frames)
        logits, cache = self._prefill(self.params, batch)
        cache = api.grow_cache(self.cfg, cache, reserve)
        self.stats["prefill_calls"] += 1
        first = jnp.argmax(logits, axis=-1).astype(jnp.int32)
        state = GenState(cache=cache, cur=first[:, None], pos=S,
                         reserve=reserve, padded_b=B)
        self.stats["tokens_out"] += int(B)
        return np.asarray(first), state

    def decode_batch(self, state: GenState) -> np.ndarray:
        """One decode step for every row of the group; returns the (B,)
        next tokens and advances the state."""
        if state.pos >= state.reserve:
            raise ValueError(
                f"decode past reserved cache length {state.reserve}")
        logits, state.cache = self._decode(
            self.params, state.cache, state.cur,
            jnp.asarray(state.pos, jnp.int32))
        nxt = jnp.argmax(logits, axis=-1).astype(jnp.int32)
        state.cur = nxt[:, None]
        state.pos += 1
        self.stats["decode_steps"] += 1
        self.stats["tokens_out"] += int(state.padded_b)
        return np.asarray(nxt)

    def gather_rows(self, state: GenState, rows: Sequence[int]) -> GenState:
        """Slot reuse: re-pack the group's state down to ``rows`` (engine
        batch indices, typically the survivors padded to a smaller batch
        bucket).  Decode cost drops to the new batch shape from the next
        step on."""
        idx = jnp.asarray(list(rows), jnp.int32)
        # every cache family is stacked over layers: leaves are
        # (num_layers, batch, ...), so the batch gather is along axis 1
        cache = jax.tree_util.tree_map(
            lambda x: jnp.take(x, idx, axis=1), state.cache)
        return GenState(cache=cache, cur=state.cur[idx], pos=state.pos,
                        reserve=state.reserve, padded_b=len(rows))

    # -- run-to-completion API ----------------------------------------------

    def generate(self, tokens: np.ndarray, *, max_new: Optional[int] = None,
                 frames: Optional[np.ndarray] = None) -> np.ndarray:
        """tokens (B, S) equal-length prompts -> (B, S + max_new)."""
        t_start = time.perf_counter()
        max_new = max_new or self.max_new
        B, S = tokens.shape
        first, state = self.prefill_batch(tokens, reserve=S + max_new,
                                          frames=frames)
        out = [state.cur[:, 0]]
        for _ in range(max_new - 1):
            self.decode_batch(state)
            out.append(state.cur[:, 0])
        gen = jnp.stack(out, axis=1)
        # the stop-clock only runs once the device is done -- without the
        # sync, async dispatch would make throughput() a dispatch rate
        gen = jax.block_until_ready(gen)
        elapsed = time.perf_counter() - t_start
        key = (B, S, max_new)
        if key in self._warm:
            self.stats["wall"] += elapsed
            self.stats["warm_tokens"] += int(B * max_new)
        else:
            self._warm.add(key)
            self.stats["compile_wall"] += elapsed
        return np.concatenate([tokens, np.asarray(gen)], axis=1)

    def throughput(self) -> float:
        """Steady-state tokens/sec: warm-executable calls only (first
        call per shape is compile-dominated and counted in
        ``stats["compile_wall"]``)."""
        return self.stats["warm_tokens"] / max(self.stats["wall"], 1e-9)
