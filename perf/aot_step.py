"""What the chip's compiler makes of a whole step program, without the chip.

    JAX_PLATFORMS=cpu python3 perf/aot_step.py <configuration> [--rows 8] [--chunk 64] [--scan K] [--rectangle 1] [--own-vocab 1]

Compiles `models/forward.forward` (kernels on, paged KV, bf16, the pools
DONATED as the engines donate them: undonated, XLA copies each pool once
more to keep the argument) for a described `v5e:2x2` chip at a benchmark
configuration's widths and full depth, with a small vocabulary: a few layers
are drawn on the CPU for the parameter tree (four, or one of each stack of a
family that has `stacks`) and the leaves of each stack are re-shaped to its
full depth as `ShapeDtypeStruct`s, so nothing of the model's size is ever
held. `--scan K` compiles K decode steps as one `lax.scan` with the pools in
its carry, the form of `runtime/device_loop.make_batched_decode_loop`. A
chunk (`--chunk` > 1) is compiled as the scheduler dispatches one, told which
row prefills, so that the weights run over `forward.compact_rows` rows (72 of
8 x 64) and the head over one position a row; `--rectangle 1` leaves that
entry out and compiles the program of a block whose every position is real.

Prints the program's temporaries and every `copy`, `dynamic-slice` or fusion
that RESULTS in packed weights (`u8[...]`), their scales (`s16[...]`) or an
array of the KV pool's shape (`bf16[L,N,hk,bt,hs]`, with its layout): the
copies XLA puts around a kernel or a scatter show only here (the kernels
alone compile in tests/test_tpu_compile.py, which also holds the step
programs to `pool_relayouts`, `scale_relayouts` and `weight_conversions`
being empty). The last is every instruction that computes a whole weight
from the weights alone: a block-quantized matrix dequantized by XLA, the
embedding table cast (`--own-vocab 1` gives the table and the head the
file's vocabulary, where the table's cast is its real size). Nothing runs:
no time comes out of this (PERF.md section 6, PRs 33, 37, 46 and 49).
"""

from __future__ import annotations

import argparse
import math
import os
import re
import sys
import time

os.environ.setdefault("TPU_LOG_DIR", "disabled")
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
from jax.sharding import SingleDeviceSharding  # noqa: E402

from benchmark import cells  # noqa: E402
from benchmark import weights as W  # noqa: E402
from distributed_llama_tpu.models.forward import (  # noqa: E402
    StateCache, compact_rows, forward, init_state)
from distributed_llama_tpu.models.params import (  # noqa: E402
    _FUSE_GROUPS, hold_dense, prepare_for_pallas, run_tensor_shapes,
    stack_names)
from distributed_llama_tpu.ops.rope import RopeTables  # noqa: E402

CUT = 4  # layers drawn: one period of any per-layer pattern in the cells
_RESULT = re.compile(
    r"\s*(?:ROOT )?%?([\w.\-]+) = ((?:u8|s16|bf16)\[[\d,]+\])(\{[^}]*\})?")


def describe_chip():
    """One chip of a described `v5e:2x2`, as a sharding for every shape.
    Call it from `main` or a test's fixture, never at import: one process
    at a time may load the TPU's library."""
    from jax.experimental import topologies

    return SingleDeviceSharding(topologies.get_topology_desc(
        platform="tpu", topology_name="v5e:2x2").devices[0])


def _sds(a, chip, shape=None):
    return jax.ShapeDtypeStruct(shape or a.shape, a.dtype, sharding=chip)


def model_shapes(config: str, chip, own_vocab: bool = False, **keys):
    """(spec, parameter shapes, configuration) of `config` with vocabulary
    512 and `keys` over the file's: the parameter tree of a drawn cut, each
    stack's leaves given the depth the spec states. `own_vocab`: the table
    and the head re-shaped to the file's vocabulary as the depth is (what a
    program does to the whole table shows at its own size)."""
    file_cfg = cells.load_config(config)
    full = dict(file_cfg, **{"vocab_size": 512, **keys})
    family = cells.load_family(full["family"])
    if hasattr(family, "one_layer_a_stack"):
        # the family cuts itself: a layer of each stack, two experts a layer
        # (the expert axis is re-shaped below, as the depth is)
        cut = family.one_layer_a_stack(full, experts=2)
    elif hasattr(family, "stacks"):
        # one layer of each stack: both hold the same tensors at any depth
        drawn = [(p, min(d, 1)) for p, d in family.stacks(full)]
        n = sum(d for _, d in drawn)
        cut = {**full, "first_k_dense_replace": drawn[0][1],
               "num_hidden_layers": n, "layers_here": n}
    else:
        n = min(CUT, full["num_hidden_layers"])
        cut = {**full, "num_hidden_layers": n,
               **{k: full[k][:n] for k in ("rope_layout",
                                           "sliding_window_layout")
                  if k in full}}
    # as runtime/engine.py prepares a loader's parameters
    cut_spec = family.model_spec(cut)
    params = prepare_for_pallas(
        hold_dense(W.to_program_params(W.make_weights(cut, 7), cut),
                   jnp.bfloat16, cut_spec), spec=cut_spec)
    if own_vocab:
        full["vocab_size"] = file_cfg["vocab_size"]
    spec = family.model_spec(full)
    # a tensor is as deep as its run, a mixer's of a model with state layers
    # as its kind's layers of the run (a fused group as its first member)
    by_run = {run.name: (run, run_tensor_shapes(spec, run))
              for run in spec.runs()}

    def depth(st, name):
        run, own = by_run[st]
        name = _FUSE_GROUPS.get(name, (name,))[0]
        return own[name][0][0] if name in own else run.depth

    shapes = jax.tree.map(lambda a: _sds(a, chip), params)
    for st in stack_names(params):
        for name, t in params[st].items():
            # the full depth, and where fewer experts were drawn than the
            # file holds every expert (the router's rows with them)
            wide = {"router": spec.n_router,
                    "router_bias": spec.n_router}.get(
                name, spec.n_experts if name.startswith("moe_") else None)
            shapes[st][name] = jax.tree.map(
                lambda a, n=depth(st, name), e=wide: _sds(a, chip, (
                    n, *((e,) if e else a.shape[1:2]), *a.shape[2:])), t)
    for name in ("embedding", "wcls"):
        shapes[name] = jax.tree.map(lambda a: _sds(a, chip, (
            spec.vocab_size, *a.shape[1:])), shapes[name])
    return spec, shapes, full


def pool_shape(spec, cfg):
    """The two sides of the block pool (the second is empty for a latent
    spec), as `runtime/engine.py` builds them, of the cell's own blocks: a
    pool of a few MB XLA moves to another memory space (`S(1)` in its
    layout, Laguna's at 256 blocks under a compact chunk's smaller
    temporaries) and the text reads as a copy the cell's program has not."""
    return [(len(spec.cache_layers), cfg["engine"]["kv_pool_blocks"],
             spec.n_kv_heads, cfg["engine"]["kv_block_tokens"],
             -(-w // 128) * 128)  # whole lanes, as runtime/engine.py pads
            for w in spec.cache_widths]


def held_pools(spec, cfg):
    """The distinct shapes among the pool's sides that hold anything."""
    return list(dict.fromkeys(s for s in pool_shape(spec, cfg) if s[-1]))


def _bf16(shape) -> str:
    return "bf16[" + ",".join(str(d) for d in shape) + "]"


def compile_step(spec, shapes, cfg, chip, *, rows: int = 8, chunk: int = 64,
                 scan: int = 0, rectangle: bool = False):
    """The compiled step program: `jit_step` at `rows` x `chunk`, or with
    `scan` K > 0 a K-step greedy decode scan with the pools in its carry. A
    chunk is a prefill dispatch's, one entry behind the rows' positions
    naming the row that prefills, unless `rectangle`."""
    bt = cfg["engine"]["kv_block_tokens"]
    kc, vc = (jax.ShapeDtypeStruct(s, jnp.bfloat16, sharding=chip)
              for s in pool_shape(spec, cfg))
    if spec.mixed:  # the state layers' ring and the blocks' snapshots
        vc = StateCache(vc, *(
            jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=chip)
            for a in jax.eval_shape(lambda: init_state(
                spec, rows, cfg["engine"]["kv_pool_blocks"], jnp.bfloat16))))

    def i32(*shape):
        return jax.ShapeDtypeStruct(shape, jnp.int32, sharding=chip)

    def fwd(p, rope, toks, kc, vc, start, tables):
        return forward(p, spec, rope, toks, kc, vc, start, use_pallas=True,
                       dtype=jnp.bfloat16, block_tables=tables,
                       block_tokens=bt, paged_kernel=True, attn_window=1024,
                       moe_stats=True)

    def loop(p, rope, tok, kc, vc, start, tables):
        def step(carry, _):
            tok, pos, kc, vc = carry
            logits, kc, vc, _ = fwd(p, rope, tok[:, None], kc, vc, pos, tables)
            nxt = jnp.argmax(logits[:, -1], axis=-1).astype(jnp.int32)
            return (nxt, pos + 1, kc, vc), nxt

        (tok, pos, kc, vc), out = jax.lax.scan(
            step, (tok, start, kc, vc), None, length=scan)
        return out, tok, pos, kc, vc

    toks = i32(rows) if scan else i32(rows, chunk)
    lead = not scan and chunk > 1 and not rectangle
    return jax.jit(loop if scan else fwd, donate_argnums=(3, 4)).lower(
        shapes, jax.tree.map(lambda a: _sds(a, chip), RopeTables.create(spec)),
        toks, kc, vc, i32(rows + lead), i32(rows, 64)).compile()


def results(text: str):
    """(instruction, shape, layout, line) of every instruction of a compiled
    program's text that results in a `u8`, `s16` or `bf16` array."""
    for line in text.splitlines():
        m = _RESULT.match(line)
        if m:
            yield m.group(1), m.group(2), m.group(3) or "", line


def logits_blocks(text: str, rows: int, chunk: int, vocab: int) -> list[str]:
    """The instructions that result in a float32 (rows, chunk, vocab) or
    (rows x chunk, vocab) array, the head's logits of every position: a
    chunk whose head runs at the sampled positions alone has none. (An
    attention kernel's result is left out: the latent kernel's context of
    one query a slot, (8, 64 heads, 512 values), is that shape by chance at
    the small vocabulary these compiles use.)"""
    return [name for name in re.findall(
        rf"%([\w.\-]+) = \(?f32\[(?:{rows},{chunk}|{rows * chunk})"
        rf",{vocab}\]", text) if "paged_attention" not in name]


def _entry(text: str) -> str:
    """The text of the program's ENTRY computation, whose parameters are the
    program's own (a fusion's body has parameters too)."""
    lines = text.splitlines()
    start = next(i for i, ln in enumerate(lines) if ln.startswith("ENTRY "))
    end = next(i for i in range(start, len(lines)) if lines[i] == "}")
    return "\n".join(lines[start:end])


def pool_relayouts(text: str, shape) -> list[str]:
    """The instructions that result in an array of the pool's shape in
    ANOTHER layout than the program's parameter of that shape has, and every
    `copy` of one: a program that updates the donated pool in place has
    none."""
    want = _bf16(shape)
    rows = [r for r in results(text) if r[1] == want]
    own = {layout for _, shape, layout, line in results(_entry(text))
           if shape == want and " parameter(" in line}
    assert len(own) == 1, f"the pool's parameters lie in {own}"
    return [f"{name} -> {want}{layout}" for name, _, layout, line in rows
            if layout not in own or re.search(r"\bcopy\(", line)]


# a scale plane brought into the faster memory space as it is stored,
# row-major in (8,128)(2,1) tiles: XLA's prefetch of a plane small enough for
# that space (a 512-row head, Mixtral's `s16[8,4096,128]`, the dense layers'
# planes of Laguna and Granite), overlapped with the work before its use. It
# pads and transposes nothing
PLANE_PREFETCH = re.compile(
    r"copy-done -> s16\[[\d,]+\]\{(\d+,)*1,0:T\(8,128\)\(2,1\)S\(1\)\}")


def scale_relayouts(text: str) -> list[str]:
    """Every `copy`, `copy-done` or fusion of a compiled program that results
    in an `s16[...]` array, a Q40 weight's scale plane, as "operation ->
    shape and layout", but for the prefetches (`PLANE_PREFETCH`): a program
    whose kernels read the planes where and as they are stored has none.
    (Until PR 46 a plane of K/32 columns that were not whole lane tiles lay
    on the chip with its ROWS minor, and every program re-laid each stack,
    padded to 128 lanes, before its layer scan.)"""
    found = [f"{re.sub(r'[.][0-9]+$', '', name)} -> {shape}{layout}"
             for name, shape, layout, line in results(text)
             if shape.startswith("s16")
             and re.search(r" (?:copy|copy-done|fusion)\(", line)]
    return [r for r in found if not PLANE_PREFETCH.fullmatch(r)]


_FLOAT_RESULT = re.compile(
    r"\s*(?:ROOT )?%?([\w.\-]+) = ((?:bf16|f16|f32)\[([\d,]+)\])"
    r"(?:\{[^}]*\})? ([\w\-]+)\(")
# what hands an array on as it is: the table itself, or a weight held dense,
# as a parameter, an element of a loop's carry, another view of the bytes, or
# XLA's prefetch of an array small enough into the faster memory space (the
# 512-row table of the tests' compiles, as `PLANE_PREFETCH` for the planes)
_HANDS_ON = {"parameter", "get-tuple-element", "bitcast", "tuple", "while",
             "conditional", "call", "optimization-barrier", "copy-start",
             "copy-done"}


def weight_conversions(text: str, shapes, floor: int = 1 << 20) -> list[str]:
    """Every instruction of a compiled program that computes a whole weight
    from the weights alone, as "operation -> shape". Of two kinds. One whose
    result is a block-quantized matrix of the layers (`shapes`' stacks; the
    router, planar by design, apart) BY BLOCKS, (N, K/32, 32) in a float
    dtype under any leading axes, of `floor` elements or more: XLA's
    dequantization multiplies the values by their block's scale in that form,
    inside a fusion or not, and no activation has it (the logical (N, K) is
    also the shape of 576 rows of an 8-expert chunk against A.X-K1's `wkv_a`
    and of a 72-row chunk against Laguna's gate, so it is not asked). And one
    whose result is an array of the embedding table's (vocabulary, dim): the
    table cast or copied. A program whose kernels read every matrix packed,
    and which gathers the table's rows in the dtype it computes in, has
    none. (Until PR 49 A.X-K1's dense `w2`, K 18432 over the one-row
    matvec's bound, stayed planar and XLA dequantized it every step, and
    the K-step scan of every configuration but Granite's cast the float32
    table once a block.)"""
    blocks = set()
    for st in stack_names(shapes):
        for name, t in shapes[st].items():
            if name != "router" and getattr(t, "scales", None) is not None:
                n, k = t.shape[-2:]
                blocks.add((n, k // 32, 32))
    table = tuple(shapes["embedding"].shape)
    found = []
    for line in text.splitlines():
        m = _FLOAT_RESULT.match(line)
        if not m or m.group(4) in _HANDS_ON:
            continue
        dims = tuple(int(d) for d in m.group(3).split(","))
        if dims == table or (dims[-3:] in blocks
                             and math.prod(dims) >= floor):
            found.append(f"{re.sub(r'[.][0-9]+$', '', m.group(1))} -> "
                         f"{m.group(2)}")
    return found


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("config")
    ap.add_argument("--rows", type=int, default=8)
    ap.add_argument("--chunk", type=int, default=64)
    ap.add_argument("--scan", type=int, default=0, metavar="K",
                    help="K decode steps as one scan, the pools in its carry")
    ap.add_argument("--own-vocab", type=int, default=0,
                    help="1: the configuration's own vocabulary, not 512")
    ap.add_argument("--rectangle", type=int, default=0,
                    help="1: a chunk whose every position is real (no row "
                    "map), not a prefill dispatch's")
    args = ap.parse_args()
    chip = describe_chip()
    spec, shapes, cfg = model_shapes(args.config, chip,
                                     own_vocab=bool(args.own_vocab))
    t0 = time.time()
    compiled = compile_step(spec, shapes, cfg, chip, rows=args.rows,
                            chunk=args.chunk, scan=args.scan,
                            rectangle=bool(args.rectangle))
    text, mem = compiled.as_text(), compiled.memory_analysis()
    what = (f"a scan of {args.scan} steps" if args.scan
            else f"{args.rows} x {args.chunk} rows")
    if not args.scan and args.chunk > 1:
        what += (", every position real" if args.rectangle else
                 f", the weights over {compact_rows(args.chunk, args.rows)} "
                 "compact rows")
    print(f"{args.config}: {spec.n_layers} layers, {what}, "
          f"compiled in {time.time() - t0:.1f} s; temporaries "
          f"{mem.temp_size_in_bytes / 1e9:.3f} GB, arguments "
          f"{mem.argument_size_in_bytes / 1e9:.3f} GB, "
          f"{text.count('tpu_custom_call')} kernels")
    if not args.scan and args.chunk > 1:
        left = logits_blocks(text, args.rows, args.chunk, cfg["vocab_size"])
        print(f"  float32 ({args.rows}, {args.chunk}, vocabulary) logits: "
              f"{left or 'none left'}")
    pools = {_bf16(side) for side in held_pools(spec, cfg)}
    seen: dict[tuple[str, str], int] = {}
    for name, shape, layout, line in results(text):
        pool = shape in pools
        if (shape.startswith("bf16") and not pool) or "custom-call" in line \
                or " parameter(" in line:
            continue
        name = re.sub(r"\.\d+$", "", name)
        if "dynamic-slice" in line or name.startswith("copy") or "fusion" in name:
            key = name, shape + (layout if pool else "")
            seen[key] = seen.get(key, 0) + 1
    for (name, shape), n in sorted(seen.items()):
        print(f"  {name} -> {shape} x {n}")
    for side in held_pools(spec, cfg):
        print(f"  pool {side}: re-laid or copied by "
              f"{pool_relayouts(text, side) or 'nothing'}")
    print(f"  scale planes (s16): re-laid or copied by "
          f"{scale_relayouts(text) or 'nothing'} (prefetches to S(1) apart)")
    print(f"  whole weights computed from the weights alone: "
          f"{weight_conversions(text, shapes) or 'none'}")


if __name__ == "__main__":
    main()
