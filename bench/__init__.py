"""On-chip benchmark of the ToaD training and serving paths.

``python bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>``
runs one cell of ``BENCHMARK.json`` on the machine it is started on.  Every
piece that belongs to one configuration, traffic mix, per-layer metric or
kernel lives in a file of its own under this directory and is found by the
name that ``BENCHMARK.json`` gives it.
"""
