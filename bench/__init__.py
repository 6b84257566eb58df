"""On-chip benchmark of the CollaFuse serve runtime.

``python3 bench/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>``
runs one cell of ``BENCHMARK.json``.  Everything a cell needs is found by
name: its configuration in ``configs/``, its traffic in ``traffic/``, the
loop its traffic names in ``loops/``, and each per-layer metric's reader in
``metrics/``.
"""
