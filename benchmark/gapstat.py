"""Which gap statistic can carry a bound: read offline from recorded gaps.

    python3 benchmark/gapstat.py FILE... [--stats itl_p85_ms,itl_block_p50_ms]
                                         [--bands 1]

FILE is what `run.py --gaps FILE` wrote: every gap between two tokens of one
request that ended in a run's window (`e2e.gap_rows`). The files are grouped
by cell. For every candidate statistic (`e2e.gap_metric` reads it, so what is
chosen here is computed in a run by the same code) a line gives each run's
value and, over the runs:

spread   largest minus smallest over the median, leaving out the run
         farthest from the median where that narrows it: how the driver
         reads whether a difference can be told (half the bound at most)
iqr      the distance between the first and third quartile
         (`statistics.quantiles(values, n=4)`) over the median: what a
         bound is set from
room     per run, the share of the statistic's own gaps between v / 1.03
         and v, and between v and 1.03 v, in points, for the run's value v;
         the line shows the smallest over the runs on either side. A
         statistic with 3 points on either side moves by under 3 % when the
         schedule shifts 3 % of the gaps past it; one that stands on a step
         between two bands has none on one side. No band is looked for.
         A mean (`itl_mean_ms`) has no room to read: it stands at no
         quantile, and moves by the share of the gaps that move times how
         far they move; its line says `room n/a`.

`--bands 1` prints, a run, the bands the gaps of 1 ms and more fall in
(share of all gaps and median), split wherever two neighbouring gaps differ
by more than 3 %, bands under 0.3 % of the gaps lumped with their neighbour
above: a picture for PERF.md, never an input of a statistic.
"""

from __future__ import annotations

import argparse
import json
import os
import statistics
import sys

if __package__ in (None, ""):
    sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from benchmark import e2e  # noqa: E402

ROOM = 1.03
CANDIDATES = (["itl_mean_ms"]
              + [f"itl_p{q}_ms" for q in range(50, 100, 5)]
              + [f"itl_rider_p{q}_ms" for q in range(50, 100, 5)]
              + ["itl_block_p50_ms", "itl_block_p90_ms",
                 "itl_blocktok_p50_ms", "itl_blocktok_p90_ms"])


def load(path: str) -> dict:
    with open(path) as f:
        run = json.load(f)
    if tuple(run["columns"]) != e2e.GAP_COLUMNS:
        raise ValueError(f"{path}: columns {run['columns']}")
    run["rows"] = [tuple(r) for r in run["rows"]]
    run["file"] = os.path.basename(path)
    return run


def spread(values) -> float:
    """Largest minus smallest over the median, without the run farthest
    from the median where leaving it out narrows it."""
    v = sorted(values)
    if len(v) < 2:
        return 0.0
    med = statistics.median(v)
    far = max(v, key=lambda x: abs(x - med))
    kept = list(v)
    if len(v) > 2:
        kept.remove(far)
    return min((max(s) - min(s)) / statistics.median(s) for s in (v, kept))


def iqr(values) -> float:
    """Third minus first quartile over the median."""
    q1, _, q3 = statistics.quantiles(values, n=4)
    return (q3 - q1) / statistics.median(values)


def room(values, v: float) -> tuple[float, float]:
    """Points of `values` in [v / ROOM, v) and in (v, v * ROOM]."""
    n = len(values)
    below = sum(1 for x in values if v / ROOM <= x < v)
    above = sum(1 for x in values if v < x <= v * ROOM)
    return 100.0 * below / n, 100.0 * above / n


def bands(rows, floor: float = 0.3) -> list[tuple[float, float, int]]:
    """(share of all gaps in %, median, count) of each band of the gaps of
    `e2e.BLOCK_MS` and more."""
    gaps = sorted(g for *_, g, _f in rows if g >= e2e.BLOCK_MS)
    groups, cur = [], []
    for g in gaps:
        if cur and g > cur[-1] * ROOM:
            groups.append(cur)
            cur = []
        cur.append(g)
    if cur:
        groups.append(cur)
    out, carry = [], []
    for grp in groups:
        carry += grp
        if 100.0 * len(carry) / len(rows) >= floor:
            out.append((100.0 * len(carry) / len(rows),
                        statistics.median(carry), len(carry)))
            carry = []
    if carry:
        out.append((100.0 * len(carry) / len(rows), statistics.median(carry),
                    len(carry)))
    return out


def table(runs: list[dict], names) -> list[dict]:
    """One line a candidate over the runs of one cell."""
    lines = []
    for name in names:
        kind, _q, mean = e2e.GAP_METRIC.match(name).groups()
        values, counts, rooms = [], [], []
        for run in runs:
            v, n = e2e.gap_metric(run["rows"], name)
            counts.append(n)
            if v is not None:
                values.append(v)
                rooms.append(room(e2e.gap_values(run["rows"], kind), v))
        line = {"name": name, "values": values, "samples": counts}
        if len(values) == len(runs) and len(values) >= 2:
            line["spread"] = spread(values)
            line["iqr"] = iqr(values) if len(values) >= 3 else None
            # a mean stands at no quantile: it has no room to read
            line["room_below"] = None if mean else min(r[0] for r in rooms)
            line["room_above"] = None if mean else min(r[1] for r in rooms)
        lines.append(line)
    return lines


def main(argv=None) -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("files", nargs="+")
    ap.add_argument("--stats", default=",".join(CANDIDATES))
    ap.add_argument("--bands", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    by_cell: dict = {}
    for path in args.files:
        run = load(path)
        by_cell.setdefault(run["workload"], []).append(run)
    for cell, runs in by_cell.items():
        print(f"{cell}: {len(runs)} runs: "
              + " ".join(f"{r['file']}(seed {r['seed']}, K {r['superstep']}, "
                         f"{len(r['rows'])} gaps)" for r in runs))
        for line in table(runs, args.stats.split(",")):
            vals = " ".join(f"{v:8.2f}" for v in line["values"])
            if "spread" in line:
                q = "   n/a" if line["iqr"] is None else f"{100 * line['iqr']:6.2f}"
                rm = ("  n/a" if line["room_below"] is None else
                      f"{line['room_below']:5.1f} / {line['room_above']:5.1f}")
                tail = (f" spread {100 * line['spread']:6.2f} % iqr {q} % "
                        f"room {rm} samples "
                        f"{min(line['samples'])}-{max(line['samples'])}")
            else:
                tail = f" samples {line['samples']}"
            print(f"  {line['name']:22s} {vals}{tail}")
        if args.bands:
            for r in runs:
                inside = 100.0 * sum(1 for *_, g, _f in r["rows"]
                                     if g < e2e.BLOCK_MS) / len(r["rows"])
                print(f"  {r['file']}: under 1 ms {inside:.1f} %; "
                      + "; ".join(f"{s:.1f} % at {m:.1f}"
                                  for s, m, _ in bands(r["rows"])))


if __name__ == "__main__":
    main()
