"""python benchmark/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

One process that owns the cell's chips.  Without a TPU, or with fewer
chips than the cell asks for, it exits non-zero and prints no result.
"""

import os
import sys
import time

_T_PROCESS = time.perf_counter()  # set-up is counted from here

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from benchmark import harness  # noqa: E402

if __name__ == "__main__":
    sys.exit(harness.main(t_process=_T_PROCESS))
