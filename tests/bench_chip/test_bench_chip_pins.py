"""Pins of what the benchmark reads, recorded before the dense decoder
moved into its configuration family: the bits of every weight and of the
float32 reference's logits (and its float8 control's) at a reduced size,
and every operation and byte count at the cells' full-size shapes.
Moving code between the benchmark's modules must reproduce each one
exactly: a reading that moves is a different benchmark.

    python tests/bench_chip/test_bench_chip_pins.py

prints the pins of the tree it runs in."""
import hashlib
import sys
from pathlib import Path

import numpy as np
import pytest

if __name__ == "__main__":
    ROOT = Path(__file__).resolve().parents[2]
    sys.path[:0] = [str(ROOT), str(ROOT / "src")]

import jax                                                # noqa: E402
import jax.numpy as jnp                                   # noqa: E402

from benchmarks.chip import flops, reference, spec        # noqa: E402
from benchmarks.chip import weights as W                  # noqa: E402

SMALL = dict(hidden_size=64, num_hidden_layers=2, num_attention_heads=4,
             num_key_value_heads=2, head_dim=16, intermediate_size=128,
             vocab_size=256)
SEED = 2**31 + 12345
NAMES = ["internlm2-1.8b", "qwen3-8b"]
DTYPES = ["bfloat16", "float32"]
QUANTS = [None, "fp8"]
BATCHES = [1, 2, 32]
LENGTHS = [64, 127, 128, 191]               # prompt S, or decode pos P
COUNTS = ["prefill_flops", "decode_flops", "prefill_bytes", "decode_bytes"]


def small(name, dtype):
    c = spec.config(name)
    c.update(SMALL, dtype=dtype)
    return c


def _digest(arrays) -> str:
    h = hashlib.sha256()
    for name, a in arrays:
        a = np.asarray(a)
        h.update(f"{name}:{a.dtype}:{a.shape}".encode())
        h.update(np.ascontiguousarray(a).tobytes())
    return h.hexdigest()


def weights_digest(name, dtype) -> str:
    c = small(name, dtype)
    flat = jax.jit(lambda k: W.all_leaves(k, c, jnp.dtype(dtype)))(
        W.seed_key(SEED))
    return _digest((p, flat[p]) for p in sorted(flat))


def logits_digest(name, dtype, quant) -> str:
    c = small(name, dtype)
    toks = np.random.default_rng(0).integers(
        0, c["vocab_size"], (2, 24)).astype(np.int32)
    pos = np.asarray([[3, 23], [0, 11]], np.int32)
    out = reference.logits(c, SEED, toks, pos, quant)
    assert out.dtype == np.float32 and out.shape == (2, 2, c["vocab_size"])
    return _digest([("logits", out)])


def counts(name) -> dict:
    c = spec.config(name)
    out = {f"weight_bytes:{B}": flops.weight_bytes(c, B)
           for B in [0] + BATCHES}
    for f in COUNTS:
        for B in BATCHES:
            for n in LENGTHS:
                out[f"{f}:{B}:{n}"] = getattr(flops, f)(c, B, n)
    return out


# recorded on the benchmark before the move, by this file's __main__
WEIGHTS = {
    ('internlm2-1.8b', 'bfloat16'):
        '8347597eda88d9ff2812bd91cc126a8eee7565ee30d6eb2ab53d7050b2e9edde',
    ('internlm2-1.8b', 'float32'):
        'b08e02174b9a6be5b8f6a3787821d226ab45e520c2745aedfd20288d82e1adfa',
    ('qwen3-8b', 'bfloat16'):
        '1896aa3cdbab78fc831abfdc981ca4ae2eb724b30032ac3e1a612b878a63dac1',
    ('qwen3-8b', 'float32'):
        'd19a6ac594718a65fdfbf595f7edef458e84f47cd8c65f7a560f88fbed9ad2ad',
}
LOGITS = {
    ('internlm2-1.8b', 'bfloat16', None):
        'babfa56a6531c21d9da969f38374608f25f638194cc3281d143d49cc281f7569',
    ('internlm2-1.8b', 'bfloat16', 'fp8'):
        '4fc1e1cc45b0488083fa345b0061fca982990e5c78654f6c8959d9cc74b00806',
    ('internlm2-1.8b', 'float32', None):
        'c11bdcb566c895726631a7b8de878d10ae364e8611f2173f2a0cfee56dd34e50',
    ('internlm2-1.8b', 'float32', 'fp8'):
        '70f89c12f2c970d087789f891560fb035b5983b557a6085afdb89d72a723bbc0',
    ('qwen3-8b', 'bfloat16', None):
        '5cc93e07bbec3c3fbc6abe7ccee7da0ea184827b919c9c9a42977d3fe1e39057',
    ('qwen3-8b', 'bfloat16', 'fp8'):
        '878d052efcf0c23758f35561068d48558380487603952c8c85a59955aec4db0e',
    ('qwen3-8b', 'float32', None):
        'd1826e3a2f7b5a9269e884ad33d70cdf4d422743007f2618ead972828bb09497',
    ('qwen3-8b', 'float32', 'fp8'):
        'b31c2c197212cb702ad085f1956fd1a4a78f677678f742bc9e75dc6b82b24091',
}
COUNT_PINS = {
    'internlm2-1.8b': {
        'weight_bytes:0': 3399159808,
        'weight_bytes:1': 3399163904,
        'weight_bytes:2': 3399168000,
        'weight_bytes:32': 3399290880,
        'prefill_flops:1:64': 194061533184.0,
        'prefill_flops:1:127': 385504247808.0,
        'prefill_flops:1:128': 388549312512.0,
        'prefill_flops:1:191': 580784750592.0,
        'prefill_flops:2:64': 388123066368.0,
        'prefill_flops:2:127': 771008495616.0,
        'prefill_flops:2:128': 777098625024.0,
        'prefill_flops:2:191': 1161569501184.0,
        'prefill_flops:32:64': 6209969061888.0,
        'prefill_flops:32:127': 12336135929856.0,
        'prefill_flops:32:128': 12433578000384.0,
        'prefill_flops:32:191': 18585112018944.0,
        'decode_flops:1:64': 3411738624.0,
        'decode_flops:1:127': 3424124928.0,
        'decode_flops:1:128': 3424321536.0,
        'decode_flops:1:191': 3436707840.0,
        'decode_flops:2:64': 6823477248.0,
        'decode_flops:2:127': 6848249856.0,
        'decode_flops:2:128': 6848643072.0,
        'decode_flops:2:191': 6873415680.0,
        'decode_flops:32:64': 109175635968.0,
        'decode_flops:32:127': 109571997696.0,
        'decode_flops:32:128': 109578289152.0,
        'decode_flops:32:191': 109974650880.0,
        'prefill_bytes:1:64': 3405713408.0,
        'prefill_bytes:1:127': 3412164608.0,
        'prefill_bytes:1:128': 3412267008.0,
        'prefill_bytes:1:191': 3418718208.0,
        'prefill_bytes:2:64': 3412267008.0,
        'prefill_bytes:2:127': 3425169408.0,
        'prefill_bytes:2:128': 3425374208.0,
        'prefill_bytes:2:191': 3438276608.0,
        'prefill_bytes:32:64': 3608875008.0,
        'prefill_bytes:32:127': 3815313408.0,
        'prefill_bytes:32:128': 3818590208.0,
        'prefill_bytes:32:191': 4025028608.0,
        'decode_bytes:1:64': 3405553664.0,
        'decode_bytes:1:127': 3411746816.0,
        'decode_bytes:1:128': 3411845120.0,
        'decode_bytes:1:191': 3418038272.0,
        'decode_bytes:2:64': 3411947520.0,
        'decode_bytes:2:127': 3424333824.0,
        'decode_bytes:2:128': 3424530432.0,
        'decode_bytes:2:191': 3436916736.0,
        'decode_bytes:32:64': 3603763200.0,
        'decode_bytes:32:127': 3801944064.0,
        'decode_bytes:32:128': 3805089792.0,
        'decode_bytes:32:191': 4003270656.0,
    },
    'qwen3-8b': {
        'weight_bytes:0': 8190739456,
        'weight_bytes:1': 8190747648,
        'weight_bytes:2': 8190755840,
        'weight_bytes:32': 8191001600,
        'prefill_flops:1:64': 446387191808.0,
        'prefill_flops:1:127': 885754167296.0,
        'prefill_flops:1:128': 892737683456.0,
        'prefill_flops:1:191': 1333293744128.0,
        'prefill_flops:2:64': 892774383616.0,
        'prefill_flops:2:127': 1771508334592.0,
        'prefill_flops:2:128': 1785475366912.0,
        'prefill_flops:2:191': 2666587488256.0,
        'prefill_flops:32:64': 14284390137856.0,
        'prefill_flops:32:127': 28344133353472.0,
        'prefill_flops:32:128': 28567605870592.0,
        'prefill_flops:32:191': 42665399812096.0,
        'decode_flops:1:64': 8209596416.0,
        'decode_flops:1:127': 8228175872.0,
        'decode_flops:1:128': 8228470784.0,
        'decode_flops:1:191': 8247050240.0,
        'decode_flops:2:64': 16419192832.0,
        'decode_flops:2:127': 16456351744.0,
        'decode_flops:2:128': 16456941568.0,
        'decode_flops:2:191': 16494100480.0,
        'decode_flops:32:64': 262707085312.0,
        'decode_flops:32:127': 263301627904.0,
        'decode_flops:32:128': 263311065088.0,
        'decode_flops:32:191': 263905607680.0,
        'prefill_bytes:1:64': 8195982336.0,
        'prefill_bytes:1:127': 8201143296.0,
        'prefill_bytes:1:128': 8201225216.0,
        'prefill_bytes:1:191': 8206386176.0,
        'prefill_bytes:2:64': 8201225216.0,
        'prefill_bytes:2:127': 8211547136.0,
        'prefill_bytes:2:128': 8211710976.0,
        'prefill_bytes:2:191': 8222032896.0,
        'prefill_bytes:32:64': 8358511616.0,
        'prefill_bytes:32:127': 8523662336.0,
        'prefill_bytes:32:128': 8526283776.0,
        'prefill_bytes:32:191': 8691434496.0,
        'decode_bytes:1:64': 8195539968.0,
        'decode_bytes:1:127': 8200184832.0,
        'decode_bytes:1:128': 8200258560.0,
        'decode_bytes:1:191': 8204903424.0,
        'decode_bytes:2:64': 8200340480.0,
        'decode_bytes:2:127': 8209630208.0,
        'decode_bytes:2:128': 8209777664.0,
        'decode_bytes:2:191': 8219067392.0,
        'decode_bytes:32:64': 8344355840.0,
        'decode_bytes:32:127': 8492991488.0,
        'decode_bytes:32:128': 8495350784.0,
        'decode_bytes:32:191': 8643986432.0,
    },
}


@pytest.mark.parametrize("dtype", DTYPES)
@pytest.mark.parametrize("name", NAMES)
def test_weights_keep_their_bits(name, dtype):
    assert weights_digest(name, dtype) == WEIGHTS[name, dtype]


@pytest.mark.parametrize("quant", QUANTS)
@pytest.mark.parametrize("dtype", DTYPES)
@pytest.mark.parametrize("name", NAMES)
def test_reference_keeps_its_logits(name, dtype, quant):
    assert logits_digest(name, dtype, quant) == LOGITS[name, dtype, quant]


@pytest.mark.parametrize("name", NAMES)
def test_counts_keep_their_values(name):
    got = counts(name)
    assert got == COUNT_PINS[name]
    assert ({k: type(v) for k, v in got.items()}
            == {k: type(v) for k, v in COUNT_PINS[name].items()})


if __name__ == "__main__":
    print("WEIGHTS = {")
    for n in NAMES:
        for d in DTYPES:
            print(f"    ({n!r}, {d!r}):\n        {weights_digest(n, d)!r},")
    print("}\nLOGITS = {")
    for n in NAMES:
        for d in DTYPES:
            for q in QUANTS:
                print(f"    ({n!r}, {d!r}, {q!r}):\n"
                      f"        {logits_digest(n, d, q)!r},")
    print("}\nCOUNT_PINS = {")
    for n in NAMES:
        print(f"    {n!r}: {{")
        for k, v in counts(n).items():
            print(f"        {k!r}: {v!r},")
        print("    },")
    print("}")
