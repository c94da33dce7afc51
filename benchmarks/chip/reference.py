"""Plain float32 reference of the models the benchmark serves.

Written from the architectures, not from the program: it imports
nothing of ``repro`` and takes nothing the program made.  Each
configuration's family (``families``) runs the forward pass over each
prompt followed by the tokens the server generated, with no cache,
padding or batching of the program's kind, its weights from ``weights``
and the seed, made in the dtype they are served in and cast to float32,
with every matrix product at ``jax.default_matmul_precision("highest")``.
This module holds what the families share, and what reads their logits.

``quant="fp8"`` is the control: the same forward with the input of
every linear layer and the weight rounded to float8 e4m3 (scaled per
row and per output column to its range) -- the step below the bf16 the
configurations state.  ``greedy`` serves the sampled prompts with it in
the program's place, and the check judges its tokens as it judges the
program's.  (Rounding to int8 the same way read too close to the bf16
program on the chip to separate them; PERF.md.)
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.chip import families

F32 = jnp.float32


def _quant_fp8(x, axis):
    s = jnp.max(jnp.abs(x), axis=axis, keepdims=True) / 448.0
    s = jnp.where(s == 0, 1.0, s)
    return (x / s).astype(jnp.float8_e4m3fn).astype(F32) * s


def _mm(x, w, quant):
    """x (..., k) @ w (k, n) in float32; quantised when asked."""
    if quant == "fp8":
        x, w = _quant_fp8(x, -1), _quant_fp8(w, 0)
    elif quant is not None:
        raise ValueError(f"unknown quantisation {quant!r}")
    return x @ w


def _norm(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * scale


def _rope(t, cos, sin):
    """Rotate-half RoPE of (N, T, heads, hd)."""
    half = t.shape[-1] // 2
    t1, t2 = t[..., :half], t[..., half:]
    return jnp.concatenate([t1 * cos - t2 * sin, t2 * cos + t1 * sin], -1)


def logits(c: dict, seed: int, tokens: np.ndarray, positions: np.ndarray,
           quant=None) -> np.ndarray:
    """Float32 logits (N, P, V) of the next token after each of
    ``positions`` (N, P) of the rows of ``tokens`` (N, T): the
    configuration's family's reference."""
    return families.of(c).logits(c, seed, tokens, positions, quant)


def greedy(c: dict, seed: int, prompts: list, counts: list,
           quant=None) -> list:
    """The first ``counts[i]`` greedy tokens after each prompt, one full
    pass over every row per token (no cache): the reference, or with
    ``quant`` its control, serving the prompts in the program's place."""
    n, P = len(prompts), max(counts)
    T = max(len(p) + m - 1 for p, m in zip(prompts, counts))
    tokens = np.zeros((n, T), np.int32)
    for i, p in enumerate(prompts):
        tokens[i, :len(p)] = p
    out = [[] for _ in prompts]
    for j in range(P):
        pos = np.asarray([[min(len(p) - 1 + j, T - 1)] for p in prompts],
                         np.int32)
        pick = logits(c, seed, tokens, pos, quant)[:, 0].argmax(-1)
        for i, (p, m) in enumerate(zip(prompts, counts)):
            if j < m:
                out[i].append(int(pick[i]))
                if len(p) + j < T:
                    tokens[i, len(p) + j] = pick[i]
    return out


def served_gaps(c: dict, seed: int, prompts: list, served: list) -> dict:
    """Teacher-forced check of served tokens.

    For each request, the reference runs over its prompt followed by
    its served tokens and reads, at each generated position, how far
    the served token's logit lies below the reference's best.  Returns
    ``{"gaps": [per-request arrays], "scale": max |logit|}``."""
    n = len(prompts)
    lens = [len(p) + len(s) - 1 for p, s in zip(prompts, served)]
    T = max(lens)
    P = max(len(s) for s in served)
    tokens = np.zeros((n, T), np.int32)
    pos = np.zeros((n, P), np.int32)
    for i, (p, s) in enumerate(zip(prompts, served)):
        seq = list(p) + list(s)[:-1]
        tokens[i, :len(seq)] = seq
        # position j predicts served token j: row len(p) - 1 + j
        pos[i] = np.minimum(len(p) - 1 + np.arange(P), len(seq) - 1)
    ref = logits(c, seed, tokens, pos)
    out = {"gaps": [], "scale": float(np.max(np.abs(ref)))}
    best = ref.max(-1)
    for i, s in enumerate(served):
        m = len(s)
        got = ref[i, np.arange(m), np.asarray(s)]
        out["gaps"].append(best[i, :m] - got)
    return out
