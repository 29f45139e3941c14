"""The output check looped over seeds in one process.

    python -m benchmark.check --config <name> --seeds 1-12 [--deep 4]
                              [--controls fp8] [--canary 1] [--trace 1]
                              [--dump FILE]

The SAME check code as a run's set-up (`probe.check`), with new weights per
seed and the programs compiled once: how a dozen seeds per configuration are
afforded in a few chip-minutes. The first `--deep` seeds (default: all) run
both passes, the controls and the canary; the others the shallow pass alone,
which carries the verdict on precision and needs no engine at full depth.
`--controls` reads what the reference computed in a lower precision gives in
the program's place (`fp8`, the precision below the configuration's
bfloat16, has to fail; `q80` and `bfloat16` are for information), `--canary
1` what an engine with one matrix's scales off by an eighth gives in the
shallow pass (the matrix is `check.canary` of the configuration's file, `wo`
where it names none). `--trace 1` keeps the profiler running meanwhile. `--dump`
writes every position's error, row and router margin as JSON lines: the
limits in `configs/<name>.json` are set from such a file, not from a guess.
One JSON line per seed, then a summary; exit 1 if a sound run failed or the
fp8 control passed.
"""

from __future__ import annotations

import argparse
import gc
import json
import os
import sys
import time

if __package__ in (None, ""):
    sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import numpy as np  # noqa: E402

from benchmark import cells, run  # noqa: E402


def parse_seeds(text: str) -> list[int]:
    out = []
    for part in text.split(","):
        if "-" in part:
            a, b = part.split("-")
            out.extend(range(int(a), int(b) + 1))
        else:
            out.append(int(part))
    return out


def main(argv=None) -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--config", required=True)
    ap.add_argument("--seeds", default="1-12")
    ap.add_argument("--deep", type=int, default=None)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--controls", default="")
    ap.add_argument("--canary", type=int, choices=(0, 1), default=0)
    ap.add_argument("--dump", default="")
    ap.add_argument("--rehearse", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    device = run.device_block(1, bool(args.rehearse))
    run.place_cache()
    import jax

    from benchmark import probe
    from benchmark import weights as W

    cfg = cells.load_config(args.config)
    lim = cfg["check"]
    controls = [c for c in args.controls.split(",") if c]
    seeds = parse_seeds(args.seeds)
    deep = len(seeds) if args.deep is None else args.deep
    print(f"check {args.config} on {json.dumps(device)}; limits "
          f"{json.dumps({k: v for k, v in lim.items() if k != 'reason'})}",
          flush=True)
    if args.trace:
        jax.profiler.start_trace(
            os.path.join(cells.ROOT, ".bench_trace", "check"))
    dump = open(args.dump, "w") if args.dump else None
    stats: dict = {}  # arm -> pass -> [stat per seed]
    ok = True

    def record(line, seed, arm, name, pe):
        res = probe.judge(pe, lim[name])
        line[f"{arm}.{name}"] = {k: res[k] for k in (
            "stat", "worst_row", "judged", "positions", "over_tol_judged",
            "p50", "p90", "max", "within")}
        stats.setdefault(arm, {}).setdefault(name, []).append(res["stat"])
        if dump:
            dump.write(json.dumps(
                {"seed": seed, "arm": arm, "pass": name,
                 **{k: np.asarray(v).tolist() for k, v in pe.items()}}) + "\n")
            dump.flush()
        return res["within"]

    for n, seed in enumerate(seeds):
        t0 = time.perf_counter()
        passes = ("shallow", "full") if n < deep else ("shallow",)
        weights = W.make_weights(cfg, seed)
        probes = probe.probe_tokens(cfg, seed)
        be = probe.build_engine(cfg, weights) if "full" in passes else None
        line = {"seed": seed, "trace": args.trace}
        refs = {name: {} for name in passes}
        for name in passes:
            pe = probe.pass_errors(cfg, weights, probes, lim[name],
                                   probe.engine_logits(cfg, be), refs[name])
            ok = record(line, seed, "sound", name, pe) and ok
        if be is not None:
            probe.free_engine(be)
            del be
        for c in (controls if n < deep else []):
            for name in passes:
                pe = probe.pass_errors(
                    cfg, weights, probes, lim[name],
                    lambda cut, w, pr: probe.reference_rows(cfg, w, pr, c)[0],
                    refs[name])
                within = record(line, seed, c, name, pe)
                if c == "fp8" and name == "shallow" and within:
                    ok = False  # the precision below came out correct
        if args.canary and n < deep:
            bad = W.mis_scaled(weights, lim.get("canary", "wo"), 1.125)
            pe = probe.pass_errors(
                cfg, weights, probes, lim["shallow"],
                lambda cut, w, pr: probe.engine_logits(cfg)(
                    cut, W.layer_cut(bad, cut, cfg), pr), refs["shallow"])
            record(line, seed, "canary", "shallow", pe)
        del weights, refs
        gc.collect()
        line["seconds"] = round(time.perf_counter() - t0, 1)
        print(json.dumps(line), flush=True)
    if args.trace:
        jax.profiler.stop_trace()
    summary = {"config": args.config, "seeds": len(seeds), "deep": deep,
               "trace": args.trace, "ok": ok}
    for arm, by_pass in stats.items():
        for name, vals in by_pass.items():
            summary[f"{arm}.{name}"] = [len(vals), min(vals), max(vals)]
    print(json.dumps(summary), flush=True)
    os._exit(0 if ok else 1)


if __name__ == "__main__":
    main()
