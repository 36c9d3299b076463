"""Chip benchmark of the merged multi-model server (see BENCHMARK.json).

``python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>``
runs one cell once on the accelerator it is started on.
"""
