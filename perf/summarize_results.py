#!/usr/bin/env python
"""Summarize a bench-results JSONL (perf/sweep.sh) into a markdown table.

    python perf/summarize_results.py [perf/sweep_results.jsonl]

Groups each result under its preceding {"section":"cmd"} marker, skips meta/
heartbeat records, flags errors and profiler-instrumented rows, and prints one
table. Pure stdlib — safe anywhere.
"""

import json
import sys


def rows(path):
    cmd = None
    for line in open(path):
        line = line.strip()
        if not line:
            continue
        try:
            rec = json.loads(line)
        except ValueError:
            yield cmd, {"error": f"unparseable line: {line[:80]}"}
            continue
        sec = rec.get("section")
        if sec == "cmd":
            cmd = rec.get("argv", "?")
        elif sec == "error":
            yield rec.get("argv", cmd), {"error": rec.get("error", "?")[:80]}
        elif sec == "meta":
            continue
        elif "metric" in rec:
            yield cmd, rec


def main():
    path = sys.argv[1] if len(sys.argv) > 1 else "perf/sweep_results.jsonl"
    seen = []
    for cmd, rec in rows(path):
        seen.append((cmd, rec))
    if not seen:
        print(f"(no results in {path})")
        return
    print("| config | tok/s | ms/tok | GB/s | layout | notes |")
    print("|---|---|---|---|---|---|")
    for cmd, rec in seen:
        cfg = (cmd or "?").replace("bench.py ", "")
        if "error" in rec:
            print(f"| `{cfg}` | — | — | — | — | ERROR: {rec['error']} |")
            continue
        notes = []
        if rec.get("profiled"):
            notes.append("profiled (not comparable)")
        if rec.get("fallback_reason"):
            notes.append(f"fallback: {rec['fallback_reason'][:50]}")
        if rec.get("provenance"):
            notes.append(f"{rec['provenance']} age={rec.get('age_s')}s")
        if "prefill_kernel" in rec:
            notes.append(f"prefill_kernel={rec['prefill_kernel']}"
                         + (f" cov={rec['prefill_kernel_coverage']}"
                            if "prefill_kernel_coverage" in rec else ""))
        ms = rec.get("ms_per_token", rec.get("ms_per_chunk", ""))
        print(f"| `{cfg}` | {rec.get('value', '')} | {ms} | "
              f"{rec.get('achieved_gbps', '')} | {rec.get('layout', '')} | "
              f"{'; '.join(notes)} |")


if __name__ == "__main__":
    main()
