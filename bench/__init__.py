"""The benchmark of record: one cell (configuration x traffic) per run, on the chip.

``python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>``
"""
