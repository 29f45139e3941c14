#!/usr/bin/env python
"""Build a REAL-FORMAT Q40 checkpoint + byte-level tokenizer from a seed.

The container the framework is developed in has zero network egress, so the model zoo
(launch.py) is unreachable; this builds a Llama-architecture model through the same
file-format path a converted checkpoint takes (formats.mfile / formats.tfile — the
byte-compatible `.m`/`.t` writers the converter uses), with deterministic seeded
weights. Everything downstream of conversion — header parse, tensor mmap, Q40
dequant, engine, tokenizer — is exactly the real-checkpoint code path.

Default: the tiny example model (dim 256, 4 layers, ~1.6 MB). `--arch NAME` writes a
published geometry of models/presets.py instead (llama3_8b: 6.3 GB), which is what
chip_smoke.py loads on the chip. Either way the file is written tensor by tensor
with random Q40 blocks drawn directly, so the model never exists in f32.

Usage: python examples/make_tiny_model.py [outdir] [--arch NAME] [--seed S]
"""

import argparse
import json
import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

from distributed_llama_tpu.formats.mfile import write_model
from distributed_llama_tpu.formats.tfile import TokenizerData, write_tokenizer
from distributed_llama_tpu.models.params import block_tensor_shapes
from distributed_llama_tpu.models.presets import ARCHS
from distributed_llama_tpu.models.spec import ArchType, ModelSpec, RopeType
from distributed_llama_tpu.quants import QK, FloatType, QTensor

TINY = dict(arch_type=ArchType.LLAMA, dim=256, hidden_dim=512, n_layers=4,
            n_heads=8, n_kv_heads=4, vocab_size=260, seq_len=1024,
            rope_type=RopeType.LLAMA)
# weights of std ~0.02 like init_random_params: a Q40 value is (nibble - 8) *
# delta with the nibble on 1..15 (std 4.3), so delta centres on 0.02/4.3
_DELTA = 0.02 / 4.3
_ROWS = 8192  # rows per generated chunk: bounds the f32 embedding transient


def _random_q40(rng: np.random.Generator, rows: int, n: int) -> QTensor:
    nb = n // QK
    packed = rng.integers(0, 256, size=(rows, nb, QK // 2), dtype=np.uint8)
    # nibble 0 (value -8) becomes 8 (value 0): uniform 0..15 has mean -0.5, and
    # a weight matrix with a nonzero mean is rank one plus noise. Every layer
    # then amplifies the mean of its input, the hidden state collapses onto
    # the all-ones direction, and bf16 rounding alone flips the logits' sign.
    packed |= ((packed & 0x0F) == 0).astype(np.uint8) << 3
    packed |= ((packed & 0xF0) == 0).astype(np.uint8) << 7
    deltas = (rng.random((rows, nb), dtype=np.float32) + 0.5) * _DELTA
    return QTensor(FloatType.Q40, packed, deltas.astype(np.float16))


def synthetic_tensors(spec: ModelSpec, seed: int):
    """(name, tensor) in `.m` order for a dense model: Q40 blocks drawn
    directly, norms around 1, the f32 embedding in row chunks."""
    assert not spec.is_moe, "synthetic writer covers the dense block graph"
    rng = np.random.default_rng(seed)

    def matrix(name, rows, n):
        for r0 in range(0, rows, _ROWS):
            yield name, _random_q40(rng, min(_ROWS, rows - r0), n)

    for r0 in range(0, spec.vocab_size, _ROWS):
        r = min(_ROWS, spec.vocab_size - r0)
        yield "embedding", rng.standard_normal(
            (r, spec.dim), dtype=np.float32) * 0.02
    shapes = block_tensor_shapes(spec)
    for _ in range(spec.n_layers):
        for name in ("wq", "wk", "wv", "wo", "w1", "w2", "w3"):
            yield from matrix(name, *shapes[name][0])
        for name in ("rms_att", "rms_ffn"):
            yield name, 1.0 + 0.02 * rng.standard_normal(
                spec.dim, dtype=np.float32)
    yield "rms_final", 1.0 + 0.02 * rng.standard_normal(
        spec.dim, dtype=np.float32)
    yield from matrix("wcls", spec.vocab_size, spec.dim)


def byte_tokenizer(vocab_size: int) -> TokenizerData:
    """Byte-level tokenizer: ids 3..258 are the 256 raw bytes, so any prompt
    encodes via the reference's +3 byte-fallback rule (tokenizer.cpp:247-253).
    Ids past them, up to the model's vocabulary, are ASCII filler pieces that
    no merge reaches; a random-weight model emits them as readable text."""
    vocab = [b"<unk>", b"<s>", b"</s>"] + [bytes([i]) for i in range(256)]
    vocab += [b"<pad>"] + [b" t%06d" % i for i in range(260, vocab_size)]
    return TokenizerData(vocab=vocab[:vocab_size], scores=[0.0] * vocab_size,
                         bos_id=1, eos_id=2,
                         chat_template="{% llama2 %}[INST] {{content}} [/INST]")


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("outdir", nargs="?", default="/tmp/dlt_determinism")
    ap.add_argument("--arch", choices=sorted(ARCHS), default=None,
                    help="a published geometry (models/presets.py); default: "
                         "the tiny example model")
    ap.add_argument("--seed", type=int, default=20260729)
    args = ap.parse_args(argv)

    os.makedirs(args.outdir, exist_ok=True)
    name = args.arch or "tiny"
    spec = ModelSpec(**(ARCHS[args.arch] if args.arch else TINY)).resolved()
    mpath = os.path.join(args.outdir, f"{name}.m")
    tpath = os.path.join(args.outdir, f"{name}.t")
    t0 = time.perf_counter()
    write_model(mpath, spec, synthetic_tensors(spec, args.seed), FloatType.Q40)
    write_tokenizer(tpath, byte_tokenizer(spec.vocab_size))
    print(json.dumps({
        "model": mpath, "tokenizer": tpath, "arch": name,
        "n_layers": spec.n_layers, "vocab_size": spec.vocab_size,
        "bytes": os.path.getsize(mpath) + os.path.getsize(tpath),
        "seconds": round(time.perf_counter() - t0, 2)}))


if __name__ == "__main__":
    main()
