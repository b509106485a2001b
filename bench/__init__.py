"""The on-chip benchmark of the Sinnamon serving path.

``python3 -m bench.run --workload <cell> --seed <n> --seconds <s> --trace <0|1>``
runs one cell of ``BENCHMARK.json`` once and prints one JSON result line.
Everything a cell needs is found by name: configurations under
``bench/configs/``, traffic mixes under ``bench/traffic/``, each cell's fixed
rate and correctness limits under ``bench/cells/``, and one reader module
per per-layer metric under ``bench/metrics/``.
"""
