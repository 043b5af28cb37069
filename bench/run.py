"""Run one benchmark cell on the chip and print its result line.

    python3 bench/run.py --workload nonsmooth.expander.trace25 \
        --seed 7 --seconds 10 --trace 0

The last line of standard output is one JSON object: `correct`,
`attempted`, `failed`, `metrics`, `device` and, with `--trace 1`,
`breakdown`, then `checks`, each number compared with its limit (also
the last lines of standard error). Exits non-zero, printing no result,
when JAX finds no accelerator or fewer chips than the cell needs.
"""

import pathlib
import sys
import time

T_START = time.perf_counter()
sys.path.insert(0, str(pathlib.Path(__file__).resolve().parents[1]))

if __name__ == "__main__":
    from bench import harness
    sys.exit(harness.main(t_start=T_START))
