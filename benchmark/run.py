"""One run of one cell.

    python3 benchmark/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

A new process: it makes the cell's weights from the seed on the device,
builds `BatchEngine` with the configuration's settings, warms every shape the
traffic can reach, decides `correct` by the output check (`probe.py`), lets
every client complete one request, and only then opens the timed window.
The last line of its standard output is one JSON object (its last key,
`compared`, holds each number the output check compared beside its limit;
the same are the last lines on standard error); every fault other
than "the logits disagree with the reference" is a message on stderr and a
non-zero exit with no result line.

`--rehearse 1` with `JAX_PLATFORMS=cpu` runs the same code on the CPU at
the toy size the configuration's file names (`toy`): the result is marked as a rehearsal
and its numbers carry the prefix `rehearsal.`, never a metric's name.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()  # before anything heavy is imported

import argparse  # noqa: E402
import gc
import json
import logging
import os
import sys

if __package__ in (None, ""):  # run as a file: make `benchmark` importable
    sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import numpy as np  # noqa: E402

from benchmark import cells  # noqa: E402

EXIT_NO_DEVICE, EXIT_FAULT = 3, 4
COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"


def fault(msg: str, code: int = EXIT_FAULT):
    print("benchmark: " + msg, file=sys.stderr, flush=True)
    sys.stdout.flush()
    os._exit(code)  # no result line; daemon threads must not hold the exit


def log(msg: str) -> None:
    print(msg, flush=True)


class Compiles(logging.Handler):
    """Backend compiles seen by this process (jax.monitoring), and while
    `watch()` is on the names JAX logs for them."""

    def __init__(self):
        import jax

        super().__init__(logging.WARNING)
        self.n, self.seconds, self.names = 0, 0.0, []
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def watch(self, on: bool) -> None:
        import jax

        jax.config.update("jax_log_compiles", on)
        logger = logging.getLogger("jax")
        (logger.addHandler if on else logger.removeHandler)(self)

    def emit(self, record) -> None:
        msg = record.getMessage()
        if msg.startswith("Finished XLA compilation of"):
            self.names.append(msg.split(" of ", 1)[1].split(" in ")[0])

    def _on(self, event, seconds, **_kw):
        if event == COMPILE_EVENT:
            self.n += 1
            self.seconds += seconds


def place_cache() -> str:
    """JAX's persistent compilation cache: where JAX_COMPILATION_CACHE_DIR
    says, else at one fixed path inside the checkout. Every program is
    kept, however quick its compile, so a second run compiles nothing."""
    import jax

    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = os.path.join(cells.ROOT, ".jax_cache")
        jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    return path


def device_block(chips: int, rehearse: bool) -> dict:
    import jax

    try:
        devs = jax.devices()
    except Exception as e:
        fault(f"JAX found no device: {e!r}", EXIT_NO_DEVICE)
    platform = devs[0].platform
    if platform != "tpu" and not (rehearse and platform == "cpu"):
        fault(f"the platform is {platform!r}, not tpu; a CPU run is a "
              "rehearsal and has to be asked for by name "
              "(JAX_PLATFORMS=cpu and --rehearse 1)", EXIT_NO_DEVICE)
    if rehearse and os.environ.get("JAX_PLATFORMS") != "cpu":
        fault("--rehearse 1 needs JAX_PLATFORMS=cpu", EXIT_NO_DEVICE)
    if len(devs) < chips:
        fault(f"the cell needs {chips} chips, JAX sees {len(devs)}",
              EXIT_NO_DEVICE)
    return {"platform": platform, "kind": devs[0].device_kind,
            "count": len(devs)}


def memory_peak(chips: int):
    import jax

    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use")
             for d in jax.devices()[:chips]]
    peaks = [p for p in peaks if p is not None]
    return max(peaks) if peaks else None


def window_buckets(seq_len: int, max_pos: int) -> list:
    """The attention-window buckets the engine compiles for positions up to
    max_pos: powers of two from 256, None for the whole context."""
    out, w = [], 256
    while True:
        if w >= seq_len:
            out.append(None)
            return out
        out.append(w)
        if w >= max_pos:
            return out
        w *= 2


def warm_shapes(be, cfg: dict, max_pos: int, vocab: int) -> None:
    """Every program the traffic can reach, before anything is timed. One
    request per window bucket, each a longer cut of ONE seeded sequence: what
    it adds to the one before ends inside its bucket in chunks of 64, 8 and
    1 (a remainder of 9 tokens beyond whole chunks), and its reply runs a
    K-step scan from host state, a second one chained from the first one's
    device state (its own executable: the inputs are placed differently)
    and a single step. Where the engine rewinds a slot over a shared prefix
    the buckets cost one long prefill together; where it does not, the
    shapes are covered all the same."""
    from distributed_llama_tpu.runtime.sampler import Sampler

    k = cfg["engine"]["superstep"]
    reply = 2 * k + 3
    buckets = window_buckets(cfg["context"], max_pos)
    longest = cfg["context"] - reply - 1
    seq = np.random.default_rng([0x3A2B, vocab]).integers(
        3, vocab, size=longest).tolist()
    lower = 0
    for i, w in enumerate(buckets):
        n = min(lower + 64 + 9 * (i + 1), longest)
        be.submit(seq[:n], reply, Sampler(vocab, temperature=0.0)).wait(600)
        if w is None:
            break
        lower = w
    # a prompt that shares a few leading tokens with a slot's history makes
    # the engine copy the shared boundary block before writing (one jitted
    # block copy): rare with seeded prompts, so it is met here once
    other = np.random.default_rng([0x3A2C, vocab]).integers(3, vocab, size=9)
    be.submit(seq[:8] + other.tolist(), 2, Sampler(vocab, temperature=0.0)
              ).wait(600)
    # the prefix cache's demotion of a pool block to the host is two eager
    # slices that would otherwise compile at the first eviction in the window
    reader = getattr(be, "_read_block", None)
    if getattr(be, "kv_pool", None) is not None and reader is not None:
        reader(0)


class Ctx:
    """What a per-layer metric's reader is given."""

    def __init__(self, config, trace, before, after, client, trace_dir=None):
        self.config, self.trace = config, trace
        self.client = client  # what e2e.reduce made of the clients' records
        # where the window's profile was written, for `host_spans.window_trace`
        self.trace_dir = trace_dir
        self._before, self._after = before, after
        self._memo: dict = {}

    @staticmethod
    def _pick(snap, name, label):
        v = snap.get(name)
        if isinstance(v, dict) and label is not None:
            v = v.get(label)
        return v

    def counter_delta(self, name, label=None):
        a = self._pick(self._after, name, label)
        b = self._pick(self._before, name, label)
        if a is None:
            return None
        return a - (b or 0.0)

    def hist_delta(self, name, label=None):
        a = self._pick(self._after, name, label)
        b = self._pick(self._before, name, label) or {"count": 0, "sum": 0.0}
        if a is None:
            return 0, 0.0
        return a["count"] - b["count"], a["sum"] - b["sum"]

    def metric(self, name):
        if name not in self._memo:
            self._memo[name] = cells.load_reader(name).read(self)
        return self._memo[name]


def main(argv=None) -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearse", type=int, choices=(0, 1), default=0)
    ap.add_argument("--gaps", default="", metavar="FILE",
                    help="write every gap the window counted to FILE "
                         "(benchmark/gapstat.py reads such files)")
    args = ap.parse_args(argv)

    bench = cells.benchmark_json()
    cell = cells.cell(bench, args.workload)
    try:
        import distributed_llama_tpu  # noqa: F401  the system under test
    except ImportError as e:
        fault(f"the program is not in this checkout: {e}")
    chips = cell["chips"]
    rehearse = bool(args.rehearse)
    device = device_block(chips, rehearse)
    compiles = Compiles()
    cache_dir = place_cache()

    from benchmark import e2e, host_spans, load, probe, traffic, trace_reduce
    from benchmark import weights as W

    cfg = cells.load_config(cell["config"])
    tr = traffic.load(cell["traffic"])
    if rehearse:
        toy, context = cfg["toy"], cfg["context"]
        cfg = cells.load_config(toy)
        log(f"REHEARSAL on the CPU at the toy size of configs/{toy}.json: "
            "no number below is a device number")
        # the toy's context holds prompts shorter by as much as it is shorter
        # than the configuration's own; replies keep their lengths, so that
        # decoding and not admission fills the toy window
        scale = cfg["context"] / context
        tr["prompt_tokens"] = {
            **tr["prompt_tokens"],
            "min": max(2, int(tr["prompt_tokens"]["min"] * scale)),
            "max": max(4, int(tr["prompt_tokens"]["max"] * scale))}
    log(f"cell {cell['name']}: config {cell['config']}, traffic "
        f"{cell['traffic']}, seed {args.seed}, device {json.dumps(device)}, "
        f"compile cache {cache_dir}")

    # ---- set-up -----------------------------------------------------------
    t = time.perf_counter()
    weights = W.make_weights(cfg, args.seed)
    t_weights = time.perf_counter() - t
    t = time.perf_counter()
    be = probe.build_engine(cfg, weights)
    t_engine = time.perf_counter() - t
    t = time.perf_counter()
    warm_shapes(be, cfg, traffic.max_position(tr), cfg["vocab_size"])
    t_warm = time.perf_counter() - t
    n_warm, s_warm = compiles.n, compiles.seconds
    t = time.perf_counter()
    verdict = probe.check(cfg, weights, args.seed, be, log=log)
    t_check = time.perf_counter() - t
    correct = bool(verdict["correct"])
    del weights
    gc.collect()

    # ---- the window -------------------------------------------------------
    from distributed_llama_tpu.obs import metrics as program_metrics
    from distributed_llama_tpu.runtime.sampler import Sampler

    trace_dir = os.path.join(cells.ROOT, ".bench_trace", cell["name"])
    state: dict = {}

    def at_open():
        state["setup_s"] = time.perf_counter() - T_START
        state["compiles_open"] = compiles.n
        compiles.watch(True)
        state["before"] = program_metrics.snapshot()
        if args.trace:
            import jax
            import shutil

            shutil.rmtree(trace_dir, ignore_errors=True)
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            opts.host_tracer_level = 1
            jax.profiler.start_trace(trace_dir, profiler_options=opts)

    def at_close():
        state["after"] = program_metrics.snapshot()
        state["compiles_close"] = compiles.n
        compiles.watch(False)
        if args.trace:
            import jax

            t_stop = time.perf_counter()
            jax.profiler.stop_trace()
            state["stop_trace_s"] = time.perf_counter() - t_stop

    plans = traffic.plan(tr, cfg["vocab_size"], args.seed)
    t = time.perf_counter()
    records, win = load.run_closed(
        be, plans, lambda: Sampler(cfg["vocab_size"], temperature=0.0),
        args.seconds, tr["stagger_s"], tr.get("think_s", 0.0), at_open, at_close)
    t_clients_warm = win.open_t - t
    be.close()
    in_window = state["compiles_close"] - state["compiles_open"]
    log(f"setup_s {state['setup_s']:.3f} = weights {t_weights:.2f} + engine "
        f"{t_engine:.2f} + shape warm-up {t_warm:.2f} ({n_warm} programs "
        f"compiled or fetched, {s_warm:.2f} s in the compiler) + check "
        f"{t_check:.2f} + clients' first requests {t_clients_warm:.2f} + "
        "imports and start-up")
    if in_window:
        fault(f"{in_window} programs were compiled inside the timed window "
              f"({', '.join(compiles.names) or 'names not logged'}): a shape "
              "was not warmed up")
    # the gap statistics are the cell's: its end-to-end entries that name
    # one, and what its per-layer readers ask for (`GAPS`); `itl_p95_ms` is
    # logged beside them so that the ledger's older readings stay comparable
    readers = {m["name"]: cells.load_reader(m["name"]) for m in
               cells.metrics_of(bench, "per_layer", cell["name"])}
    gap_names = ["itl_p95_ms"]
    gap_names += [m["name"] for m in
                  cells.metrics_of(bench, "end_to_end", cell["name"])
                  if e2e.GAP_METRIC.match(m["name"])]
    gap_names += [n for r in readers.values() for n in getattr(r, "GAPS", ())]
    gap_names = list(dict.fromkeys(gap_names))
    metrics, samples, counts = e2e.reduce(records, win.open_t, win.close_t,
                                          chips, gap_names)
    if args.gaps:
        os.makedirs(os.path.dirname(os.path.abspath(args.gaps)), exist_ok=True)
        with open(args.gaps, "w") as f:
            json.dump({"workload": cell["name"], "seed": args.seed,
                       "trace": args.trace, "rehearsal": rehearse,
                       "superstep": cfg["engine"]["superstep"],
                       "window_s": win.close_t - win.open_t,
                       "columns": e2e.GAP_COLUMNS,
                       "rows": e2e.gap_rows(records, win.open_t,
                                            win.close_t)}, f)
    metrics["setup_s"] = state["setup_s"]
    log("samples: " + json.dumps(samples) + " requests: " + json.dumps(counts))
    log("as the clients saw it (bounded only where BENCHMARK.json says so): "
        + json.dumps({k: round(v, 3) for k, v in metrics.items()}))
    moved = {k: round(state["after"][k] - state["before"].get(k, 0.0), 6)
             for k, v in state["after"].items()
             if isinstance(v, (int, float))
             and v != state["before"].get(k, 0.0)}
    log("program counters that moved in the window: " + json.dumps(moved))
    peak = memory_peak(chips)
    log(f"peak_bytes_in_use after the window: {peak}")
    dev = dict(device, memory_peak_bytes=peak)

    prefix = "rehearsal." if rehearse else ""
    out_metrics: dict = {}
    breakdown = None
    if not args.trace:
        for m in cells.metrics_of(bench, "end_to_end", cell["name"]):
            if m["name"] not in metrics:
                fault(f"no sample for {m['name']} in this window "
                      f"(samples {samples})")
            out_metrics[prefix + m["name"]] = {"value": metrics[m["name"]],
                                               "unit": m["unit"]}
    else:
        reduced = None
        t_read = time.perf_counter()
        if not rehearse:
            # the programs this cell's readers declare, by their jitted names
            programs = {}
            for r in readers.values():
                programs.update(getattr(r, "PROGRAMS", {}))
            trace = host_spans.window_trace(trace_dir)  # the readers' too
            if trace is None:
                fault(f"the traced window left no profile under {trace_dir}")
            reduced = trace_reduce.reduce(trace, programs,
                                          host_spans.gap_namer(trace))
            if reduced["missing"]:
                fault("the trace has no event for the declared programs "
                      f"{reduced['missing']}")
            dev["busy_s"] = reduced["busy_s"]
            dev["window_s"] = reduced["window_s"]
            breakdown = {"device_ops": reduced["device_ops"],
                         "idle_gaps": reduced["idle_gaps"][:10]}
        t_readers = time.perf_counter()
        ctx = Ctx(cfg, reduced, state["before"], state["after"], metrics,
                  None if rehearse else trace_dir)
        for m in cells.metrics_of(bench, "per_layer", cell["name"]):
            if rehearse and readers[m["name"]].SOURCE == "device_trace":
                continue
            value = ctx.metric(m["name"])
            if value is not None:
                out_metrics[prefix + m["name"]] = {"value": value,
                                                   "unit": m["unit"]}
        # a traced run has to end within its allowance, and most of what it
        # takes beyond an untraced one is spent here
        log(f"after the window: stopping the profiler "
            f"{state['stop_trace_s']:.1f} s, reading its profile "
            f"{t_readers - t_read:.1f} s, the readers "
            f"{time.perf_counter() - t_readers:.1f} s")
    result = {"correct": correct, "attempted": counts["attempted"],
              "failed": counts["failed"], "metrics": out_metrics,
              "device": dev}
    if breakdown is not None:
        result["breakdown"] = breakdown
    if rehearse:
        result["rehearsal"] = True
    # each number the output check compared, beside its limit: last on
    # standard error, and last in the result's line
    # (a statistic that is not finite goes out as null: the line stays JSON)
    result["compared"] = {
        name: {"value": (verdict[name]["stat"]
                         if np.isfinite(verdict[name]["stat"]) else None),
               "limit": verdict[name]["tol"]}
        for name in ("shallow", "full")}
    for name, c in result["compared"].items():
        print(f"compared {name}: {c['value']} limit {c['limit']:g}",
              file=sys.stderr, flush=True)
    print(json.dumps(result), flush=True)
    os._exit(0)  # every engine is closed; daemon client threads hold nothing


if __name__ == "__main__":
    main()
