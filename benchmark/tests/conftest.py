"""The benchmark's own tests run on the CPU at toy sizes:
`python -m pytest benchmark/tests -q`."""

import os
import sys

os.environ["JAX_PLATFORMS"] = "cpu"
os.environ.pop("JAX_COMPILATION_CACHE_DIR", None)
os.environ["JAX_ENABLE_COMPILATION_CACHE"] = "0"
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))
