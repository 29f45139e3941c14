"""What the chip's compiler makes of a whole step program, without the chip.

    JAX_PLATFORMS=cpu python3 perf/aot_step.py <configuration> [--rows 8] [--chunk 64]

Compiles `models/forward.forward` (kernels on, paged KV, bf16) for a described
`v5e:2x2` chip at a benchmark configuration's widths and full depth, with a
small vocabulary: a 4-layer model is drawn on the CPU for the parameter tree
and its block leaves are re-shaped to the full depth as `ShapeDtypeStruct`s,
so nothing of the model's size is ever held. Prints the program's temporaries
and every `copy`, `dynamic-slice` or fusion that RESULTS in packed weights
(`u8[...]`) or their scales (`s16[...]`): the copies XLA puts in front of a
kernel show only here (the kernels alone compile in tests/test_tpu_compile.py).
Nothing runs: no time comes out of this (PERF.md section 6, PR 33).
"""

from __future__ import annotations

import argparse
import os
import re
import sys
import time

os.environ.setdefault("TPU_LOG_DIR", "disabled")
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
from jax.experimental import topologies  # noqa: E402
from jax.sharding import SingleDeviceSharding  # noqa: E402

from benchmark import cells  # noqa: E402
from benchmark import weights as W  # noqa: E402
from distributed_llama_tpu.models.forward import forward  # noqa: E402
from distributed_llama_tpu.models.params import prepare_for_pallas  # noqa: E402
from distributed_llama_tpu.ops.rope import RopeTables  # noqa: E402

CUT = 4  # layers drawn: one period of any per-layer pattern in the cells
_MOVES = re.compile(r"\s*(?:ROOT )?%?([\w.\-]+) = ((?:u8|s16)\[[\d,]+\])")


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("config")
    ap.add_argument("--rows", type=int, default=8)
    ap.add_argument("--chunk", type=int, default=64)
    args = ap.parse_args()
    full = dict(cells.load_config(args.config), vocab_size=512)
    cut = {**full, "num_hidden_layers": CUT,
           **{k: full[k][:CUT] for k in ("rope_layout", "sliding_window_layout")
              if k in full}}
    family = cells.load_family(full["family"])
    params = prepare_for_pallas(W.to_program_params(W.make_weights(cut, 7)),
                                spec=family.model_spec(cut))
    spec = family.model_spec(full)
    chip = SingleDeviceSharding(topologies.get_topology_desc(
        platform="tpu", topology_name="v5e:2x2").devices[0])

    def sds(a, shape=None):
        return jax.ShapeDtypeStruct(shape or a.shape, a.dtype, sharding=chip)

    shapes = {**jax.tree.map(sds, params), "blocks": jax.tree.map(
        lambda a: sds(a, (spec.n_layers, *a.shape[1:])), params["blocks"])}
    b, bt = args.rows, full["engine"]["kv_block_tokens"]
    pool = jax.ShapeDtypeStruct(
        (spec.n_layers, 256, spec.n_kv_heads, bt, spec.head_size),
        jnp.bfloat16, sharding=chip)

    def i32(*shape):
        return jax.ShapeDtypeStruct(shape, jnp.int32, sharding=chip)

    def step(p, rope, toks, kc, vc, start, tables):
        return forward(p, spec, rope, toks, kc, vc, start, use_pallas=True,
                       dtype=jnp.bfloat16, block_tables=tables,
                       block_tokens=bt, paged_kernel=True, attn_window=1024,
                       moe_stats=True)

    t0 = time.time()
    compiled = jax.jit(step).lower(
        shapes, jax.tree.map(sds, RopeTables.create(spec)),
        i32(b, args.chunk), pool, pool, i32(b), i32(b, 64)).compile()
    text, mem = compiled.as_text(), compiled.memory_analysis()
    print(f"{args.config}: {spec.n_layers} layers, {b} x {args.chunk} rows, "
          f"compiled in {time.time() - t0:.1f} s; temporaries "
          f"{mem.temp_size_in_bytes / 1e9:.3f} GB, arguments "
          f"{mem.argument_size_in_bytes / 1e9:.3f} GB, "
          f"{text.count('tpu_custom_call')} kernels")
    seen: dict[tuple[str, str], int] = {}
    for line in text.splitlines():
        m = _MOVES.match(line)
        if not m or "custom-call" in line or "parameter(" in line:
            continue
        name = re.sub(r"\.\d+$", "", m.group(1))
        if "dynamic-slice" in line or name.startswith("copy") or "fusion" in name:
            seen[name, m.group(2)] = seen.get((name, m.group(2)), 0) + 1
    for (name, shape), n in sorted(seen.items()):
        print(f"  {name} -> {shape} x {n}")


if __name__ == "__main__":
    main()
