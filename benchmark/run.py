"""The benchmark's command: one run of one cell.

    python benchmark/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

One process, one chip.  Its last line of standard output is the result:
one JSON object with `correct`, `attempted`, `failed`, `metrics` and
`device` (and `breakdown` in a traced run).  It exits non-zero and
prints no result where jax finds no TPU, a chip that is not in
`benchmark/peaks.json`, or fewer chips than the cell asks for.
"""

import time

T_PROCESS = time.time()     # setup_s counts from here

import os  # noqa: E402
import sys  # noqa: E402

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

if __name__ == "__main__":
    from benchmark.harness import runner

    sys.exit(runner.main(t_process=T_PROCESS))
