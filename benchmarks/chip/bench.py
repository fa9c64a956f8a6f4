"""The chip benchmark's one command.

    python3 benchmarks/chip/bench.py --workload <cell> --seed <n> \\
        --seconds <s> --trace <0|1>

See ``chipbench/harness.py`` for what a run does and prints.
"""
import os
import sys
import time

T_START = time.perf_counter()

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

from chipbench import harness  # noqa: E402

if __name__ == "__main__":
    sys.exit(harness.main(sys.argv[1:], T_START))
