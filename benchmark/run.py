"""Run one cell of BENCHMARK.json once.

    python benchmark/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

See ``benchmark/harness/cli.py``. Set-up time counts from here: the clock
starts before anything heavy is imported.
"""

import time

T_PROCESS_START = time.perf_counter()

import os  # noqa: E402
import sys  # noqa: E402

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from benchmark.harness import cli  # noqa: E402

if __name__ == "__main__":
    sys.exit(cli.main(sys.argv[1:], T_PROCESS_START))
