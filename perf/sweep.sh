#!/bin/bash
# One-shot hardware measurement sweep: run on a TPU chip to collect every
# pending A/B, one bench process after another. Each line is a JSON record and
# names the device it ran on; tee everything into perf/sweep_results.jsonl.
#
#   bash perf/sweep.sh [outfile]
#
# Every emitted line is valid JSON (command markers are {"section":"cmd",...}
# records, not '#' comments), and a command that dies still leaves an explicit
# {"section":"error",...} record instead of silently vanishing from the file.
set -e -o pipefail
# shared run()/run_all()/err_record() helpers (watchdog + stderr-tail records);
# resolve before the cd so any invocation cwd works
source "$(cd "$(dirname "$0")" && pwd)/_bench_lib.sh"
cd "$(dirname "$0")/.."
OUT="${1:-perf/sweep_results.jsonl}"
: > "$OUT"

# platform characteristics (dispatch overhead, streaming ceiling, kernel GB/s,
# windowed-vs-full attention) — includes the i4p vs i4p-inline vs i8 kernel A/B
run_all python perf/microbench.py

# quantized_psum numerics + quantize/dequant compute cost on the 8-way virtual CPU
# mesh (one real chip has no ICI; the record carries mesh=cpu so it cannot be
# mistaken for an ICI time)
run_all env JAX_PLATFORMS=cpu XLA_FLAGS=--xla_force_host_platform_device_count=8 \
    python perf/microbench.py --section collectives

# headline decode: 4-bit kernel, windowed attention, host loop
run python bench.py --steps 64

# kernel layout A/B at the model level
run python bench.py --steps 64 --layout i8

# merged projection launches A/B (wqkv/w13 fusion, default on)
run python bench.py --steps 64 --no-fuse

# window sweep: growing live-context cost (watchdog grows the bucket as needed)
run python bench.py --steps 64 --window 2048

# device loop: dispatch amortization after the carry-based cache redesign
run python bench.py --steps 64 --device-loop 8
run python bench.py --steps 64 --device-loop 32

# prefill throughput (chunked prefill is a capability win over the reference)
run python bench.py --prefill 64 --steps 16
run python bench.py --prefill 128 --steps 16

# the other BASELINE.json configs
run python bench.py --arch tinyllama_1_1b --steps 64
run python bench.py --arch llama3_8b --steps 64
run python bench.py --arch mixtral_8x7b_l8 --steps 32
run python bench.py --arch grok1_l2 --steps 32

echo "sweep complete -> $OUT"
