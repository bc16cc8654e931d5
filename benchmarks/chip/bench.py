"""Chip benchmark of the memory-tier training and serving system.

    python3 benchmarks/chip/bench.py --workload <cell> --seed <n> \
        --seconds <s> --trace <0|1>

Runs one cell of ``BENCHMARK.json`` once, on the chips of the machine it
is started on, and prints the result as the last line of standard output
(see ``harness/runner.py``).  It fails, with no result, where JAX finds
no TPU or fewer chips than the cell asks for.
"""
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

from harness import runner  # noqa: E402

if __name__ == "__main__":
    sys.exit(runner.main())
