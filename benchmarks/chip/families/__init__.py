"""Configuration families: one module per architecture the benchmark
serves, ``families/<family>.py``, found by the ``"family"`` its
configuration file names, as ``gen/<kind>.py`` is found by the
``"generator"`` of a traffic file.  A family knows its architecture;
``weights``, ``reference``, ``flops`` and ``shard_side`` know only what
every family shares, and go through the family for the rest.  So a new
architecture lands as a new configuration file and a new family module,
with no edit to any file the benchmark has.

Every family module provides, for a configuration file's dict ``c``:

- ``program_config(c)``: the program's config for the architecture,
  with every size as the file states it.  It alone imports the program.
- ``leaves(c)``: ``{path: (shape of one layer's leaf, fan_in, layers)}``
  for every leaf of the program's parameter tree.  ``fan_in`` is None
  for a norm scale (drawn around 1) and 1 for an embedding table.
  ``layers`` is 0 for a leaf outside the layer stack, and the depth of
  its group for a stacked leaf, whose array in the program is
  ``(layers,) + shape``.  A stacked leaf's path starts with its group,
  ``<stack>/<group>/``; groups may differ in depth.
- ``logits(c, seed, tokens, positions, quant)``: the plain float32
  reference, float32 logits ``(N, P, V)`` of the next token after each
  of ``positions`` (N, P) of the rows of ``tokens`` (N, T), every matrix
  product at ``highest`` precision.  It imports nothing of ``repro``,
  takes its weights from ``weights`` and the seed, and honours
  ``quant="fp8"``, the control (``reference._mm``).
- ``prefill_flops(c, B, S)``, ``decode_flops(c, B, pos)``,
  ``weight_bytes(c, rows)``, ``prefill_bytes(c, B, S)`` and
  ``decode_bytes(c, B, pos)``: the operations and bytes of one engine
  call, as ``flops`` describes them.
"""
from __future__ import annotations

import importlib
from pathlib import Path


def of(c: dict):
    """The family module of configuration ``c``."""
    name = c.get("family")
    looked = " or ".join(str(Path(p) / f"{name or '<family>'}.py")
                         for p in __path__)
    if not isinstance(name, str) or not name.isidentifier():
        raise ValueError(f"configuration {c.get('name')!r} names no family"
                         f" module (its \"family\" is {name!r}): a family"
                         f" is a file {looked}")
    module = f"{__name__}.{name}"
    try:
        return importlib.import_module(module)
    except ModuleNotFoundError as e:
        if e.name != module:
            raise
        raise ModuleNotFoundError(
            f"configuration {c.get('name')!r} names family {name!r}, and"
            f" there is no file {looked}") from None
