"""The benchmark of distributed-llama-tpu's batched serving path.

Everything the benchmark runs lives in this directory: traffic generation,
the seeded Q40 weight generator, the plain float32 reference and the output
check, the load driver, the metric arithmetic and the reduction from the
profiler's trace.
From the program it takes `BatchEngine` and its counters, nothing else.
`BENCHMARK.json` at the root of the repo names the cells; a cell is found by
name: `configs/<config>.json`, `traffic/<traffic>.json`,
`layer_metrics/<metric>.py`, and a configuration's model family (the
program's `ModelSpec` for it, its tensors, its float32 reference) by the
file's `family`: `families/<family>.py`. Adding one needs new files and new
entries only.
"""
