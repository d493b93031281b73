#!/usr/bin/env python3
"""``python3 chipbench/run.py --workload <cell> --seed <n> --seconds <s>
--trace <0|1>``: one run of one cell on the machine it is started on.
The last line of standard output is the result (see README.md)."""

import os
import sys
import time

T_PROCESS = time.perf_counter()

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(
    __file__))))

if __name__ == '__main__':
    from chipbench import harness
    sys.exit(harness.main(sys.argv[1:], T_PROCESS))
