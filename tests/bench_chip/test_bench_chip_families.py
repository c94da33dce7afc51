"""Configuration families: every configuration names the module that
knows its architecture, and a new architecture lands as new files.  The
test-only family ``extra_families/kimi_moe.py`` (the repository's MoE
decoder at its reduced size) is registered on the package's path, the
way the metric-reader test registers a reader, and goes through
``make_params``, the served ``Engine``, ``reference`` and ``flops``, and
through a whole reduced run on the CPU (``bench_chip_cpu_run.py``)."""
import json
import os
import subprocess
import sys
from pathlib import Path

import numpy as np
import pytest

import jax

from benchmarks.chip import families, flops, reference, spec
from benchmarks.chip import weights as W
from benchmarks.chip.shard_side import make_params, program_config

EXTRA = Path(__file__).resolve().parent / "extra_families"
INTERFACE = ["program_config", "leaves", "logits", "prefill_flops",
             "decode_flops", "weight_bytes", "prefill_bytes",
             "decode_bytes"]
SEED = 2**31 + 505


@pytest.fixture
def moe(monkeypatch):
    """The test-only MoE configuration, its family on the path."""
    monkeypatch.setattr(families, "__path__",
                        list(families.__path__) + [str(EXTRA)])
    return json.loads((EXTRA / "kimi-k2-reduced.json").read_text())


def test_every_configuration_names_a_family_with_the_interface():
    for entry in spec.benchmark()["configs"]:
        c = spec.config(entry["name"])
        fam = families.of(c)
        assert fam.__name__ == f"benchmarks.chip.families.{c['family']}"
        assert all(callable(getattr(fam, f)) for f in INTERFACE)


@pytest.mark.parametrize("family", [None, "no_such_family", "../dense"])
def test_a_configuration_without_a_known_family_raises(family):
    c = {"name": "lost"}
    if family is not None:
        c["family"] = family
    with pytest.raises((ValueError, ModuleNotFoundError)) as e:
        families.of(c)
    msg = str(e.value)
    assert str(Path(families.__path__[0])) in msg and ".py" in msg
    assert "'lost'" in msg


def test_moe_family_is_found_on_the_path(moe):
    fam = families.of(moe)
    assert fam.__file__ == str(EXTRA / "kimi_moe.py")
    assert all(callable(getattr(fam, f)) for f in INTERFACE)


def test_moe_program_drops_no_token(moe):
    cfg = program_config(moe)
    assert cfg.is_moe and cfg.moe_impl == "dropping"
    assert (cfg.num_experts, cfg.num_experts_per_token) == (8, 2)
    # one slot per token of a row in every expert
    assert cfg.capacity_factor * cfg.num_experts_per_token \
        == cfg.num_experts


def test_moe_params_are_the_programs_tree_from_the_seed(moe):
    moe = dict(moe, dtype="bfloat16")
    cfg = program_config(moe)
    params = make_params(cfg, moe, SEED)
    flat = W.flatten(params)
    assert flat["stack/uniform/ffn/router"].shape == (3, 128, 8)
    assert flat["stack/uniform/ffn/wi_gate"].shape == (3, 8, 128, 64)
    assert flat["stack/uniform/ffn/wo"].shape == (3, 8, 64, 128)
    key = W.seed_key(SEED)
    for name, leaf in W.one_layer(key, moe, 2).items():
        np.testing.assert_array_equal(
            np.asarray(flat["stack/uniform/" + name][2]), np.asarray(leaf))
    np.testing.assert_array_equal(
        np.asarray(flat["tok/unembed"]),
        np.asarray(W.global_leaf(key, moe, "tok/unembed")))
    # the bytes a step reads: every weight but the embedding table
    n = sum(a.size for p, a in flat.items() if p != "tok/embed")
    assert flops.weight_bytes(moe, 0) == 2 * n


def test_moe_engine_prefill_and_decode_match_the_family_reference(moe):
    """One prefill and one decode step through the served engine's own
    compiled calls, in float32, against the family's float32 logits."""
    from repro.serving.engine import Engine
    c = moe
    assert c["dtype"] == "float32"
    cfg = program_config(c)
    eng = Engine(cfg, make_params(cfg, c, SEED), max_new=4)
    seen = []
    for name in ("_prefill", "_decode"):
        call = getattr(eng, name)

        def record(*a, call=call):
            out = call(*a)
            seen.append(np.asarray(out[0]))
            return out
        setattr(eng, name, record)
    toks = np.random.default_rng(3).integers(
        0, c["vocab_size"], (2, 24)).astype(np.int32)
    with jax.default_matmul_precision("highest"):
        first, state = eng.prefill_batch(toks, reserve=28)
        second = eng.decode_batch(state)
    ext = np.concatenate([toks, first[:, None]], axis=1)
    want = reference.logits(c, SEED, ext, np.asarray([[23, 24]] * 2,
                                                     np.int32))
    np.testing.assert_allclose(seen[0].reshape(2, -1), want[:, 0],
                               rtol=1e-4, atol=1e-4)
    np.testing.assert_allclose(seen[1].reshape(2, -1), want[:, 1],
                               rtol=1e-4, atol=1e-4)
    np.testing.assert_array_equal(first, want[:, 0].argmax(-1))
    np.testing.assert_array_equal(second, want[:, 1].argmax(-1))


def test_moe_reference_routes_and_honours_the_control(moe):
    """The routing is part of the reference (dense mixing of all experts
    reads other logits), and ``quant="fp8"`` reaches the family."""
    from benchmarks.chip.families import kimi_moe
    toks = np.random.default_rng(4).integers(
        0, moe["vocab_size"], (1, 16)).astype(np.int32)
    pos = np.asarray([[15]], np.int32)
    base = reference.logits(moe, SEED, toks, pos)
    assert np.abs(reference.logits(moe, SEED, toks, pos, "fp8")
                  - base).max() > 1e-3
    all_experts = dict(moe, num_experts_per_tok=moe["n_routed_experts"])
    assert kimi_moe.dims(all_experts)["K"] == 8
    assert np.abs(reference.logits(all_experts, SEED, toks, pos)
                  - base).max() > 1e-3


def test_flops_dispatch_to_the_moe_family(moe):
    from benchmarks.chip.families import kimi_moe
    for f in ("prefill_flops", "decode_flops", "prefill_bytes",
              "decode_bytes"):
        assert getattr(flops, f)(moe, 4, 32) == getattr(kimi_moe, f)(
            moe, 4, 32)
    # a token computes its K of E experts: fewer operations than a
    # model that ran every expert
    every = dict(moe, num_experts_per_tok=moe["n_routed_experts"])
    assert flops.decode_flops(moe, 1, 0) < flops.decode_flops(every, 1, 0)
    assert flops.weight_bytes(moe, 0) == flops.weight_bytes(every, 0)


def moe_cpu_run(*args):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run(
        [sys.executable, str(Path(__file__).parent / "bench_chip_cpu_run.py"),
         "--config", "kimi-k2-reduced", *args],
        cwd=spec.ROOT, env=env, capture_output=True, text=True, timeout=400)
    assert p.returncode == 0, p.stderr[-3000:]
    return json.loads(p.stdout.splitlines()[-1])


def test_moe_family_run_is_correct():
    r = moe_cpu_run()
    assert r["correct"], r["checks"]
    assert r["attempted"] == 24 and r["failed"] == 0
    gap = r["checks"]["logit_gap"]
    assert gap["value"] < gap["limit"]


@pytest.mark.parametrize("args", [["--control", "fp8"],
                                  ["--fault", "alter_tokens"]])
def test_moe_family_control_and_fault_are_not_correct(args):
    r = moe_cpu_run(*args)
    assert not r["correct"]
    gap = r["checks"]["logit_gap"]
    assert gap["value"] > gap["limit"]


TWO_GROUPS = '''
def leaves(c):
    return {"tok/embed": ((16, 4), 1, 0),
            "stack/dense/ffn/w": ((4, 8), 4, 2),
            "stack/moe/ffn/w": ((3, 4, 8), 4, 5),
            "stack/moe/ln/scale": ((4,), None, 5)}
'''


def test_groups_of_different_depth(tmp_path, monkeypatch):
    """A family may stack two groups of different depth: each is drawn
    over its own layers, and a layer of either is found by its group."""
    (tmp_path / "two_groups_for_test.py").write_text(TWO_GROUPS)
    monkeypatch.setattr(families, "__path__",
                        list(families.__path__) + [str(tmp_path)])
    c = {"name": "two", "family": "two_groups_for_test"}
    key = W.seed_key(SEED)
    flat = jax.jit(lambda k: W.all_leaves(k, c))(key)
    assert flat["stack/dense/ffn/w"].shape == (2, 4, 8)
    assert flat["stack/moe/ffn/w"].shape == (5, 3, 4, 8)
    one = W.one_layer(key, c, 4, group="stack/moe")
    assert set(one) == {"ffn/w", "ln/scale"}
    for name, leaf in one.items():
        np.testing.assert_array_equal(
            np.asarray(flat["stack/moe/" + name][4]), np.asarray(leaf))
    with pytest.raises(ValueError, match="stack/dense"):
        W.one_layer(key, c, 0)
