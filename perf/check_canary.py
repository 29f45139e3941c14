"""`python -m benchmark.check` for a configuration whose canary matrix lies in
a named stack.

`benchmark/check.py --canary 1` mis-scales the matrix `check.canary` of the
configuration's file, `wo` where the file names none. A family with two
stacks has no tensor `wo` (`lead.wo`, `blocks.wo`), and
`benchmark/tests/test_benchmark_schema.py` holds a cell's `check` block to
exactly five keys, so the file of such a cell states the matrix one level up,
as `check_canary`, and this wrapper hands it to the check in memory:

    python3 perf/check_canary.py --config ax-k1-ep4-l7 --seeds ... --canary 1

Every argument goes to `benchmark.check.main` as it is.
"""

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from benchmark import cells, check  # noqa: E402

_load = cells.load_config


def load_config(name: str) -> dict:
    cfg = _load(name)
    if "check_canary" in cfg:
        cfg["check"] = {**cfg["check"], "canary": cfg["check_canary"]}
    return cfg


if __name__ == "__main__":
    cells.load_config = load_config
    check.main()
