"""The benchmark: DDA solves of the paper's problems on the chip.

`python3 bench/run.py --workload <cell> --seed <n> --seconds <s>
--trace <0|1>` runs one cell of `BENCHMARK.json`; see `bench/harness.py`.
"""
