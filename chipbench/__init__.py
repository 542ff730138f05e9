"""On-chip benchmark of the Lustre storage path and its training user.

`python3 chipbench/run.py --workload <name> --seed <n> --seconds <s>
--trace <0|1>` runs one cell of `BENCHMARK.json`. Everything a cell
needs is found by name: its configuration in `configs/`, its traffic mix
in `traffic/`, the code for the mix's kind in `drivers/`, and each metric's
reader in `metrics/`.
"""
