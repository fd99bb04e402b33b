"""Run one benchmark cell once and print its result as the last line.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> \
        --trace <0|1>

The cells, metrics and bounds are in ``BENCHMARK.json`` at the root of the
checkout; see ``bench/harness.py``. Exits non-zero and prints no result
where JAX finds no TPU or fewer chips than the cell asks for.
"""
import os
import sys

_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [_ROOT, os.path.join(_ROOT, "src")]

from bench.harness import main, process_start  # noqa: E402

if __name__ == "__main__":
    sys.exit(main(sys.argv[1:], process_start()))
