"""On-chip benchmark of the serving system: one command, cells found by name.

``python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>``
runs one cell of ``BENCHMARK.json`` on the accelerator it is started on.
Configurations (``configs/``), traffic mixes (``traffic/``) and metric readers
(``metrics/``) are files found by the names ``BENCHMARK.json`` gives them.
"""
