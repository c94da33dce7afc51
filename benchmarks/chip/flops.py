"""Operations and bytes of one engine call, computed from its shapes and
the configuration file (not from the program), by the configuration's
family (``families``).

A prefill call runs ``B`` prompts of ``S`` tokens through every layer
and the head at the last position.  A decode call runs ``B`` rows one
token on, attending ``pos + 1`` positions (the ``pos`` already cached
and the new one).  Attention counts the causal pairs only.  Bytes are
what the call has to move at the least, in the served dtype: every
weight except the embedding table, of which only the batch's rows, and
the KV entries it reads and writes.  ``B`` is whichever batch the
caller counts: the padded one the device runs (roofline) or the
requests in it (MFU).
"""
from __future__ import annotations

from benchmarks.chip import families


def prefill_flops(c: dict, B: int, S: int) -> float:
    return families.of(c).prefill_flops(c, B, S)


def decode_flops(c: dict, B: int, pos: int) -> float:
    return families.of(c).decode_flops(c, B, pos)


def weight_bytes(c: dict, rows: int) -> float:
    """Weights one call reads: all but the embedding table, plus the
    table's ``rows`` rows."""
    return families.of(c).weight_bytes(c, rows)


def prefill_bytes(c: dict, B: int, S: int) -> float:
    return families.of(c).prefill_bytes(c, B, S)


def decode_bytes(c: dict, B: int, pos: int) -> float:
    return families.of(c).decode_bytes(c, B, pos)


def least_seconds(flops: float, nbytes: float, peak: dict) -> tuple:
    """(seconds, bound): the larger of compute and memory time at the
    chip's peaks, and which of the two it is."""
    t_c = flops / peak["bf16_flops_per_s"]
    t_m = nbytes / peak["hbm_bytes_per_s"]
    return (t_c, "compute") if t_c >= t_m else (t_m, "memory")
