"""On-chip benchmark of the served learned index.

``python3 bench/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>``
runs one cell of ``BENCHMARK.json`` once.  Everything a cell needs is
found by name: its configuration under ``bench/configs/``, its key
generator under ``bench/keys/``, its traffic mix under
``bench/traffic/`` and each metric's reader under ``bench/metrics/``.
"""
